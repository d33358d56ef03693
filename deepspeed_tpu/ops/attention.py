"""Attention ops.

The XLA reference implementation lives here; the Pallas flash-attention
kernel (replacing the reference's fused CUDA attention in
``csrc/transformer/softmax_kernels.cu`` + ``transform_kernels.cu``) plugs in
behind the same signature and is selected automatically on TPU.
"""

import collections
from typing import Dict, Optional

import jax
import jax.numpy as jnp


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# Which path served each attention call, counted while tracing (a compiled
# program replays its choice without passing here again):
#   "flash" / "flash_bthd"  the Pallas kernel, folded / strided layout
#   "xla"                   the reference, because the call asked for it
#                           (mask, bias, dropout, use_flash=False, no TPU)
#   "xla_ineligible"        the reference, because the kernel was wanted
#                           but cannot serve these shapes
_dispatch_counts = collections.Counter()


def record_dispatch(path: str):
    _dispatch_counts[path] += 1


def dispatch_counts() -> Dict[str, int]:
    """Copy of the per-path attention dispatch counts of this process."""
    return dict(_dispatch_counts)


_FORCE_DECODE_KERNEL = False  # tests flip this to exercise the Pallas path


def use_decode_kernel() -> bool:
    """Whether the Pallas decode-attention kernel should serve KV-cache
    attention (TPU, or forced for interpret-mode testing)."""
    return _FORCE_DECODE_KERNEL or _on_tpu()


def attention_reference(q, k, v, mask=None, causal=True, softmax_scale=None,
                        dropout_rate=0.0, dropout_rng=None, bias=None):
    """Plain XLA attention: q,k,v [batch, heads, seq, head_dim].

    Softmax in fp32 regardless of input dtype (the reference CUDA softmax
    also accumulates in fp32: ``csrc/transformer/softmax_kernels.cu``).
    ``bias``: additive logits bias broadcastable to [batch, heads, q, k]
    (ALiBi slopes, relative-position biases).
    """
    *_, q_len, head_dim = q.shape
    k_len = k.shape[-2]
    scale = softmax_scale if softmax_scale is not None else head_dim**-0.5
    logits = jnp.einsum("...qd,...kd->...qk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    if causal:
        causal_mask = jnp.tril(jnp.ones((q_len, k_len), dtype=bool), k=k_len - q_len)
        logits = jnp.where(causal_mask, logits, jnp.finfo(jnp.float32).min)
    if mask is not None:
        logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    if dropout_rate > 0.0 and dropout_rng is not None:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_rate), 0.0)
    return jnp.einsum("...qk,...kd->...qd", probs, v)


def attention(q, k, v, mask=None, causal=True, softmax_scale=None,
              dropout_rate=0.0, dropout_rng=None,
              use_flash: Optional[bool] = None, bias=None,
              _sp_dispatch=True):
    """Dispatching attention entry point.

    Auto mode (``use_flash=None``): seq axis active on the mesh → sequence
    parallelism when shapes allow — ulysses all-to-all when the head count
    divides the seq axis (full-seq flash locally), ring otherwise; else the
    Pallas flash kernel on TPU; else the XLA reference. An explicit
    ``use_flash`` bool bypasses SP dispatch (the escape hatch for numerics
    comparison). ``bias`` (additive logits bias, e.g. ALiBi) always takes
    the XLA reference path — the Pallas kernels don't consume it.
    ``_sp_dispatch=False`` is the internal re-entry guard for SP bodies
    that are already under ``shard_map``.
    """
    if bias is not None:
        if use_flash or (use_flash is None and _on_tpu() and mask is None):
            _warn_fallback(q.shape, k.shape,
                           "additive logits bias (ALiBi/rpe) — the Pallas "
                           "kernels don't consume it")
        record_dispatch("xla")
        return attention_reference(q, k, v, mask=mask, causal=causal,
                                   softmax_scale=softmax_scale,
                                   dropout_rate=dropout_rate,
                                   dropout_rng=dropout_rng, bias=bias)
    from deepspeed_tpu.parallel.topology import AXIS_SEQ, get_topology

    topo = get_topology(create_if_missing=False)
    if (_sp_dispatch and use_flash is None and topo is not None
            and topo.axis_size(AXIS_SEQ) > 1
            and mask is None and dropout_rate == 0.0
            and q.shape[-2] == k.shape[-2]
            and q.shape[-2] % topo.axis_size(AXIS_SEQ) == 0):
        from deepspeed_tpu.parallel.topology import AXIS_MODEL

        n_seq = topo.axis_size(AXIS_SEQ)
        # heads are sharded over the model axis when TP is active — the
        # all_to_all scatters each device's LOCAL head group, so the
        # per-device head count is what must divide the seq axis
        n_tp = topo.axis_size(AXIS_MODEL)
        heads = q.shape[-3]
        if heads % n_tp == 0 and (heads // n_tp) % n_seq == 0:
            # enough heads to scatter: one all_to_all each way and the
            # attention itself stays a full-sequence flash-kernel call
            from deepspeed_tpu.ops.ulysses_attention import ulysses_attention

            return ulysses_attention(q, k, v, causal=causal,
                                     softmax_scale=softmax_scale,
                                     mesh=topo.mesh)
        from deepspeed_tpu.ops.ring_attention import ring_attention

        return ring_attention(q, k, v, causal=causal,
                              softmax_scale=softmax_scale, mesh=topo.mesh)
    if use_flash is None:
        use_flash = _on_tpu() and dropout_rate == 0.0 and mask is None
    if use_flash:
        from deepspeed_tpu.ops.flash_attention import (
            flash_attention_sharded, flash_ineligible)

        # decided from the shapes, before the call: an error raised by the
        # kernel itself is a fault and propagates
        reason = flash_ineligible(q.shape, k.shape, layout="bhtd",
                                  dtype=q.dtype)
        if reason is None:
            record_dispatch("flash")
            _note_flash_plan(q.shape, k.shape, causal, q.dtype)
            return flash_attention_sharded(q, k, v, causal=causal,
                                           softmax_scale=softmax_scale)
        # e.g. no block of whole tiles divides the sequence — take the XLA
        # path, but SAY so: losing the kernel is a perf cliff the user
        # should see (once per offending shape) and a counter can assert on
        _warn_fallback(q.shape, k.shape, reason)
        record_dispatch("xla_ineligible")
    else:
        record_dispatch("xla")
    return attention_reference(q, k, v, mask=mask, causal=causal,
                               softmax_scale=softmax_scale,
                               dropout_rate=dropout_rate, dropout_rng=dropout_rng)


_noted_plans = set()


def _note_flash_plan(q_shape, k_shape, causal, dtype):
    """Log the kernel's schedule (``flash_plan``: what a grid step fetches,
    the compute chunk, how many chunks and tiles run unmasked / masked /
    are skipped) once per shape, while tracing: the static counter of the
    tile schedule, as ``GatherPlan.describe`` is ZeRO-3's."""
    key = (tuple(q_shape), tuple(k_shape), bool(causal), str(dtype))
    if key in _noted_plans:
        return
    _noted_plans.add(key)
    from deepspeed_tpu.ops.flash_attention import flash_plan
    from deepspeed_tpu.utils.logging import logger

    plan = flash_plan(q_shape, k_shape, causal, dtype)
    logger.info(f"flash_attention q{tuple(q_shape)} k{tuple(k_shape)} "
                f"causal={bool(causal)}: {plan.describe()}")


_warned_shapes = set()


def _warn_fallback(q_shape, k_shape, reason: str):
    key = (tuple(q_shape), tuple(k_shape))
    if key in _warned_shapes:
        return
    _warned_shapes.add(key)
    from deepspeed_tpu.utils.logging import logger

    logger.warning(
        f"flash_attention unavailable for q{tuple(q_shape)} k{tuple(k_shape)} "
        f"({reason}); taking the dense XLA attention path — pad the sequence "
        f"to a multiple of the kernel's 128-row tile (or keep it at 512 or "
        f"under, where one compute chunk takes it whole) to regain the fused "
        f"kernel")
