"""GPT-2 family, TPU-first.

The flagship training model (BASELINE.json config #1: GPT-2 125M). Built for
the sharded engine: weights carry logical partitioning metadata (consumed by
the ZeRO/TP partitioner), layers can run under ``lax.scan`` (one compiled
layer body — fast compiles, per-layer ZeRO-3 gather), and attention routes
through ``deepspeed_tpu.ops.attention`` (Pallas flash kernel on TPU).

Capability reference: the reference wraps HF/Megatron GPT-2 via
``DeepSpeedEngine`` and injects fused kernels
(``deepspeed/ops/transformer/transformer.py:459``); here the model is native.
"""

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.decode_utils import (cache_attn_mask,
                                               decode_positions,
                                               embed_lookup, pad_lengths,
                                               paged_positions,
                                               paged_write_slots,
                                               row_positions)
from deepspeed_tpu.ops.attention import attention
from deepspeed_tpu.models.remat_utils import offload_policy, saved_block_input


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    n_positions: int = 1024
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    dropout: float = 0.0
    layer_norm_epsilon: float = 1e-5
    dtype: Any = jnp.bfloat16  # compute/activation dtype (params kept fp32)
    scan_layers: bool = True
    remat: bool = False  # activation checkpointing over blocks
    # remat granularity: "full" recomputes the whole block in backward;
    # "dots" saves matmul outputs and recomputes only elementwise chains
    # (LN/gelu/residual) — the usual best trade on TPU where HBM, not the
    # MXU, is the scarce resource
    remat_policy: str = "full"
    # reference activation_checkpointing/checkpointing.py:485
    # (cpu_checkpointing): the saved inter-layer residual-stream tensors
    # move to HOST memory during forward and stream back for backward
    # recompute. TPU-native form: one outer jax.checkpoint over the whole
    # block stack whose policy offloads the named "block_in" values to
    # pinned_host — everything else recomputes (same profile as the
    # reference: checkpoints on CPU + full segment recompute)
    cpu_checkpointing: bool = False
    # reference checkpointing.py:372 (partition_activations): saved
    # activations are partitioned across model-parallel ranks instead of
    # replicated, gathered back at recompute. TPU-native form: a sharding
    # constraint on the saved "block_in" value spreading the sequence dim
    # over the model axis — GSPMD stores the shard, all-gathers in backward
    partition_activations: bool = False
    use_flash: Optional[bool] = None
    # "bthd": run flash attention in the projection-natural [B, T, H, D]
    # layout (ops/flash_attention.py flash_attention_bthd) — no QKV/output
    # transposes, so XLA inserts no HBM relayout copies around the pallas
    # custom-call (PERF.md "remaining headroom": ~10-16 ms/step at the
    # bench config). Falls back to the standard path whenever the fast
    # path can't serve (mask/bias/window/SP/decode).
    attn_layout: str = "bhtd"
    # decode mode: attention reads/writes a KV cache (mutable "cache"
    # collection) — the TPU-native form of the reference's inference
    # workspace (csrc/transformer/inference/includes/inference_context.h)
    decode: bool = False
    # padded decode: the batch was prefetched with LEFT-padded prompts
    # (attention_mask at prefill); decode steps mask the padded cache
    # prefix per row and compute per-row positions. Static so unpadded
    # serving keeps the Pallas decode kernel
    padded: bool = False
    # paged decode (the serving layer's continuous-batching cache): KV
    # lives in a SHARED block pool ([paged_num_blocks, paged_block_size,
    # H, D] per layer in the "cache" collection) instead of per-batch
    # append buffers; per-request block tables / lengths / valid counts
    # arrive via the ``paging`` call argument, so sequences of different
    # lengths share one allocation and advance independently
    paged: bool = False
    paged_num_blocks: int = 0
    paged_block_size: int = 0
    # paged-KV pool dtype: "" stores blocks in the compute dtype; "int8"
    # quantizes K/V per pool row (ops.quantizer.quantize_rowwise — one
    # f32 scale per token x head in a side pool indexed by the same
    # block table) for 2-4x more concurrent sequences per HBM byte
    paged_kv_dtype: str = ""
    # --- canonical-decoder knobs: this model executes the whole fused-
    # c_attn decoder family the state-dict factory normalizes to (GPT-2,
    # OPT, BLOOM — reference model_implementations/ arch classes) ---
    # MLP activation: "gelu" tanh-approx (GPT-2/GPT-J) | "gelu_exact"
    # erf-based (GPT-NeoX) | "relu" (OPT)
    activation: str = "gelu"
    # positions: "learned" (GPT-2/OPT wpe table) | "alibi" (BLOOM slopes)
    # | "rotary" (GPT-J/GPT-NeoX — applied to q/k inside attention)
    position_embedding: str = "learned"
    # OPT quirk: its embed_positions table has 2 pad rows; lookups offset
    position_offset: int = 0
    # BLOOM applies a layernorm right after the token embedding
    embedding_layernorm: bool = False
    # --- rotary knobs (position_embedding="rotary") ---
    # rotate only the first rotary_dim dims of each head (GPT-J 64 of 256,
    # NeoX rotary_pct); 0 = full head_dim
    rotary_dim: int = 0
    # GPT-J interleaves rotated pairs (rotate_every_two); NeoX splits the
    # rotary slice in contiguous halves (rotate_half)
    rotary_interleaved: bool = False
    rope_theta: float = 10000.0
    # --- block residual layout ---
    # "sequential": x + attn(ln_1 x); then + mlp(ln_2 ·)  (GPT-2/OPT/BLOOM)
    # "parallel_single_ln": h = ln_1 x; x + attn(h) + mlp(h)  (GPT-J)
    # "parallel_two_ln": x + attn(ln_1 x) + mlp(ln_2 x)  (GPT-NeoX)
    residual: str = "sequential"
    # GPT-J's attention projections carry no bias terms
    attn_bias: bool = True
    # GPT-Neo quirk: bias-free q/k/v but a BIASED output projection
    # (None = follow attn_bias)
    attn_out_bias: Optional[bool] = None
    # GPT-Neo quirk: attention logits are NOT scaled by 1/sqrt(head_dim)
    # (None = standard scaling)
    attn_scale: Optional[float] = None
    # sliding-window ("local") attention per layer (GPT-Neo alternates
    # global/local with window 256): entry i is 0 for global or the window
    # size. Requires scan_layers=False (the window is a static per-layer
    # property; a scanned body would force the masked path on all layers)
    attention_windows: Optional[tuple] = None
    # tied_head: LM head reuses wte (GPT-2/OPT/BLOOM); GPT-J/NeoX train a
    # separate lm_head matrix (GPT-J's with a bias)
    tied_head: bool = True
    lm_head_bias: bool = False
    # progressive layer drop (reference runtime/progressive_layer_drop.py:5):
    # when on, the forward accepts a traced ``pld_theta`` scalar and each
    # block's residual is stochastically ZEROED with depth-scaled keep
    # probability 1 - i/L * (1 - theta) (paper eq. 6), with inverted-residual
    # scaling so eval uses all layers unchanged. Note: under jit/scan the
    # dropped block's compute still executes (static shapes — the gain here
    # is the regularization/convergence effect, not per-step FLOPs; the
    # reference's eager gating skips compute, a dynamic-control-flow shape
    # XLA cannot express inside one compiled step)
    pld: bool = False

    def for_decode(self, padded: bool = False):
        return dataclasses.replace(self, decode=True, dropout=0.0,
                                   padded=padded)

    def for_paged_decode(self, num_blocks: int, block_size: int,
                         kv_dtype: str = ""):
        """Serving variant: decode mode whose KV cache is a shared block
        pool (block 0 reserved as the garbage sink — see
        ``ops.decode_attention.GARBAGE_BLOCK``). The pool is ONE pair of
        ``cache`` variables owned by the block stack,
        ``transformer/key_pool`` and ``transformer/value_pool`` of shape
        ``[n_layer, num_blocks, block_size, n_head * head_dim]``, which
        every layer writes and reads in place (``_paged_pool_vars``).
        Mutually exclusive with ``padded``: ragged prompts are the block
        table's job here. ``kv_dtype="int8"`` stores the pool quantized
        per row with ``key_scale`` / ``value_scale`` side pools of the
        same leading shape (the serving ``kv_cache_dtype`` knob)."""
        return dataclasses.replace(self, decode=True, dropout=0.0,
                                   padded=False, paged=True,
                                   paged_num_blocks=int(num_blocks),
                                   paged_block_size=int(block_size),
                                   paged_kv_dtype=str(kv_dtype))

    @staticmethod
    def gpt2_125m(**kw):
        return GPT2Config(n_embd=768, n_layer=12, n_head=12, **kw)

    @staticmethod
    def gpt2_350m(**kw):
        return GPT2Config(n_embd=1024, n_layer=24, n_head=16, **kw)

    @staticmethod
    def tiny(**kw):
        kw.setdefault("vocab_size", 256)
        kw.setdefault("n_positions", 64)
        kw.setdefault("n_embd", 64)
        kw.setdefault("n_layer", 2)
        kw.setdefault("n_head", 4)
        return GPT2Config(**kw)


def _bthd_serves() -> bool:
    """Whether the strided flash path can run here: a real TPU (or forced
    interpret mode for tests) with no sequence-parallel axis active (SP
    has its own dispatch in ops/attention.py)."""
    from deepspeed_tpu.ops.attention import _on_tpu
    from deepspeed_tpu.parallel.topology import AXIS_SEQ, get_topology

    topo = get_topology(create_if_missing=False)
    if topo is not None and topo.axis_size(AXIS_SEQ) > 1:
        return False
    if _on_tpu():
        return True
    # interpret-mode testing on CPU
    from jax._src import config as _jax_config

    return (_jax_config.pallas_tpu_interpret_mode_context_manager.value
            is not None)


def _dense_init(scale=0.02):
    return nn.initializers.normal(stddev=scale)


def alibi_slopes(n_head: int) -> np.ndarray:
    """ALiBi per-head slopes (BLOOM's formula: geometric 2^(-8i/n) for
    power-of-two head counts, interpolated otherwise)."""
    import math

    def pow2(n):
        start = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
        return [start ** (i + 1) for i in range(n)]

    if math.log2(n_head).is_integer():
        return np.asarray(pow2(n_head), np.float32)
    p = 2 ** int(math.floor(math.log2(n_head)))
    return np.asarray(pow2(p) + pow2(2 * p)[0::2][:n_head - p], np.float32)


def _alibi_bias(cfg, key_positions):
    """[1, H, 1, K] additive logits bias: slope * key_position. Softmax is
    shift-invariant per query row, so this equals the slope*(j-i) distance
    form under the causal mask (the identity HF BLOOM also relies on)."""
    slopes = jnp.asarray(alibi_slopes(cfg.n_head))
    return (slopes[:, None, None]
            * key_positions.astype(jnp.float32)[None, None, :])[None]


def apply_rotary(x, positions, rotary_dim: int, theta: float,
                 interleaved: bool):
    """Rotary position embedding on [B, T, H, D] (reference capability:
    ``apply_rotary_pos_emb.cu``, csrc/transformer/inference/csrc/, which
    serves the same GPT-J/NeoX archs). Only the first ``rotary_dim`` dims
    rotate; ``interleaved`` picks GPT-J's rotate-every-two pairing over
    NeoX's contiguous-halves rotate-half. ``positions``: [T] shared, or
    [B, T] per-row (left-padded batches)."""
    D = x.shape[-1]
    rd = rotary_dim or D
    inv = 1.0 / (theta ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd))
    positions = jnp.asarray(positions, jnp.float32)
    if positions.ndim == 1:
        positions = positions[None]  # [1, T] broadcasts over batch
    freqs = positions[:, :, None] * inv[None, None]  # [B|1, T, rd/2]
    cos = jnp.cos(freqs)[:, :, None, :]  # [B|1, T, 1, rd/2]
    sin = jnp.sin(freqs)[:, :, None, :]
    rot, rest = x[..., :rd].astype(jnp.float32), x[..., rd:]
    if interleaved:
        x1, x2 = rot[..., 0::2], rot[..., 1::2]
    else:
        x1, x2 = rot[..., : rd // 2], rot[..., rd // 2:]
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    if interleaved:
        out = jnp.stack([o1, o2], axis=-1).reshape(rot.shape)
    else:
        out = jnp.concatenate([o1, o2], axis=-1)
    out = out.astype(x.dtype)
    return jnp.concatenate([out, rest], axis=-1) if rd < D else out


def _remat_block(cfg, block=None):
    """``block`` (default :class:`Block`) wrapped per the config's
    activation-checkpointing policy."""
    block = block or Block
    if not cfg.remat:
        return block
    if cfg.cpu_checkpointing:
        # the OUTER stack-level checkpoint (see GPT2LMHeadModel) owns both
        # the recompute and the host offload; an inner wrap would save the
        # block inputs on-device, defeating the offload
        return block
    policy = None
    if cfg.remat_policy == "dots":
        # save matmul outputs AND the flash-attention residuals (named in
        # ops/flash_attention.py) — backward recomputes only the cheap
        # elementwise chains (LN / gelu / residual adds)
        names = ("flash_q", "flash_k", "flash_v", "flash_o", "flash_lse")
        if block is not Block:
            # a ZeRO-3 use site (_AtUseBlock): memory is what stage 3 is
            # for, and q, k, v are transposed slices of the c_attn output
            # that checkpoint_dots keeps anyway. Saved beside it in the
            # kernel's layout (head size 64 padded to 128 lanes) they cost
            # 3.5 GB a chip at GPT-2 XL, the room the compiler needs to run
            # a layer's collectives beside its compute (PERF.md, PR 32);
            # backward redoes the three transposes instead
            names = names[3:]
        policy = jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.checkpoint_dots,
            jax.checkpoint_policies.save_only_these_names(*names))
    # deterministic (arg index 2; 0 is self) is branched on in Python —
    # it must stay static under jax.checkpoint, and therefore must be
    # passed POSITIONALLY at every call site of the wrapped block
    return nn.remat(block, prevent_cse=False, policy=policy,
                    static_argnums=(2,))


class CausalSelfAttention(nn.Module):
    config: GPT2Config
    # sliding-window size for this layer (0 = global); a static module
    # attribute so each unrolled layer compiles its own mask shape
    window: int = 0

    def _paged_kv_attend(self, q4, k, v, paging, kv, B, T, head_dim):
        """Paged decode (serving): this step's KV rows go in place into
        this layer of the stacked block pool, and the step attends them
        with what the pool held. A decode step on the Pallas kernel hands
        its ONE new row a sequence to the kernel's call, which writes it
        for the rows of its work list only (``paged_call_writes``: an idle
        slot has no step and no write, and the program holds no scatter on
        a pool); a prefill (``paging["prefill"]``, rows fresh at length 0),
        a ``k + 1``-row verify step and the dense gather oracle scatter
        their rows first (pads and idle slots into the garbage block:
        ``paged_write_slots``). Prefill then falls through to the standard
        causal path over its own keys, the same program the append-cache
        prefill compiles. ``kv`` is ``(pools, layer)`` as the block stack
        threads it (see :func:`_paged_pool_vars`); the updated pools go
        back the same way. ``paging["work"]``, where the model put it, is
        the kernel's work list of this step's lengths (and, for a call
        that writes, its write list), made once for every layer.
        Returns ``(q4, k4, v4, y, cached_attn, pools)``; ``y is None`` on
        the prefill fall-through."""
        cfg = self.config
        if paging is None:
            raise ValueError(
                "paged decode needs the `paging` call argument: "
                '{"block_tables": [B, MB] int32, "lengths": [B] int32, '
                '"num_valid": [B] int32, "prefill": bool}')
        if kv is None:
            raise ValueError(
                "paged decode runs under the block stack (ScanBlocks / "
                "LoopBlocks), which owns the KV pool and threads it "
                "through the layers as `kv`")
        pools, layer = kv
        bs = cfg.paged_block_size
        tables = paging["block_tables"]
        lengths = paging["lengths"]
        quant = "key_scale" in pools
        k4 = k.reshape(B, T, cfg.n_head, head_dim)
        v4 = v.reshape(B, T, cfg.n_head, head_dim)
        pos = paged_positions(lengths, T)  # [B, T] logical slots
        if cfg.position_embedding == "rotary":
            # rotate by absolute position BEFORE pooling, mirroring the
            # append cache: pooled keys are post-rotation
            q4 = apply_rotary(q4, pos, cfg.rotary_dim, cfg.rope_theta,
                              cfg.rotary_interleaved)
            k4 = apply_rotary(k4, pos, cfg.rotary_dim, cfg.rope_theta,
                              cfg.rotary_interleaved)
        rows = {"key_pool": k4, "value_pool": v4}
        if quant:
            from deepspeed_tpu.ops.quantizer import quantize_rowwise

            # per-row scale side pools (one f32 scale per token x head)
            # ride the SAME (layer, block, offset) address as the int8
            # pools, so the block table stays the single source of
            # placement truth
            rows["key_pool"], rows["key_scale"] = quantize_rowwise(k4)
            rows["value_pool"], rows["value_scale"] = quantize_rowwise(v4)
        alibi = cfg.position_embedding == "alibi"
        kernel = not paging.get("prefill") and _paged_kernel_serves(
            cfg, self.window)
        # who writes is read off what is static in the call (its query
        # rows, the mesh it sits on), never off a model's name or a flag
        in_call = kernel and _paged_call_writes(B, T, cfg.n_head)
        if not in_call:
            blk, off = paged_write_slots(tables, pos, paging["num_valid"], bs)
            # B*T rows of H*D lanes (H for the scales) written where they
            # lie: no layer is sliced out of the pool, nothing re-stacked
            pools = {name: pool.at[layer, blk, off].set(_pool_rows(
                rows[name].reshape(B, T, -1), pool.shape[-1]))
                for name, pool in pools.items()}
        if paging.get("prefill"):
            return q4, k4, v4, None, False, pools
        if kernel:
            # heads partitioned over tp; per-shard KV pools. Where the
            # call writes, the pools it hands back hold the step's rows
            # and every other byte as it was (the garbage block too: an
            # idle slot's row goes nowhere)
            y4, pools = _paged_kernel_attend(
                q4, pools, tables, lengths, layer, cfg.attn_scale,
                paging.get("work"), paging["num_valid"],
                rows if in_call else None)
            y = y4.transpose(0, 2, 1, 3)
        else:
            from deepspeed_tpu.ops.decode_attention import (
                gather_paged_cache, gather_paged_cache_int8)

            S = tables.shape[-1] * bs
            if quant:
                kd, vd = (gather_paged_cache_int8(
                    pools[f"{n}_pool"], pools[f"{n}_scale"], tables, layer,
                    cfg.n_head, cfg.dtype) for n in ("key", "value"))
            else:
                kd, vd = (gather_paged_cache(
                    pools[f"{n}_pool"], tables, layer, cfg.n_head)
                    for n in ("key", "value"))
            # per-row lengths: each serving slot is at its own position
            mask = cache_attn_mask(S, lengths, T, window=self.window)
            bias = _alibi_bias(cfg, jnp.arange(S)) if alibi else None
            y = attention(q4.transpose(0, 2, 1, 3),
                          kd.transpose(0, 2, 1, 3), vd.transpose(0, 2, 1, 3),
                          mask=mask, bias=bias, causal=False,
                          softmax_scale=cfg.attn_scale, use_flash=False)
        return q4, k4, v4, y, True, pools

    @nn.compact
    def __call__(self, x, deterministic=True, attention_mask=None,
                 paging=None, kv=None):
        cfg = self.config
        B, T, C = x.shape
        head_dim = cfg.n_embd // cfg.n_head
        # fused QKV projection: one big matmul for the MXU
        qkv = nn.Dense(3 * cfg.n_embd, dtype=cfg.dtype, kernel_init=_dense_init(),
                       use_bias=cfg.attn_bias, name="c_attn")(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q4 = q.reshape(B, T, cfg.n_head, head_dim)  # [B, T, H, D]
        rotary = cfg.position_embedding == "rotary"
        # left-padded rows: position 0 at the first REAL token
        row_pos = (row_positions(attention_mask)
                   if attention_mask is not None else None)
        if rotary and not cfg.decode:
            pos = row_pos if row_pos is not None else jnp.arange(T)
            q4 = apply_rotary(q4, pos, cfg.rotary_dim,
                              cfg.rope_theta, cfg.rotary_interleaved)
            k = apply_rotary(k.reshape(B, T, cfg.n_head, head_dim),
                             pos, cfg.rotary_dim, cfg.rope_theta,
                             cfg.rotary_interleaved).reshape(B, T, C)
        cached_attn = False
        if cfg.decode and cfg.paged:
            # serving block-pool cache; paged prefill falls through to
            # the standard causal path below (cached_attn stays False)
            q4, k4, v4, y, cached_attn, pools = self._paged_kv_attend(
                q4, k, v, paging, kv, B, T, head_dim)
        elif cfg.decode:
            # KV cache: [B, n_positions, H, D] append buffer (the TPU-native
            # form of the reference's softmax_context KV workspace,
            # csrc/transformer/inference/csrc/softmax.cu). Prefill — the call
            # that creates the cache — is a separate compiled program; it
            # writes the cache but attends causally over only its own T keys
            # (the plain path below), not the zero-padded window.
            is_prefill = not self.has_variable("cache", "cached_key")
            k4 = k.reshape(B, T, cfg.n_head, head_dim)
            v4 = v.reshape(B, T, cfg.n_head, head_dim)
            cache_shape = (B, cfg.n_positions, cfg.n_head, head_dim)
            ck = self.variable("cache", "cached_key", jnp.zeros, cache_shape,
                               cfg.dtype)
            cv = self.variable("cache", "cached_value", jnp.zeros, cache_shape,
                               cfg.dtype)
            cidx = self.variable("cache", "cache_index",
                                 lambda: jnp.zeros((), jnp.int32))
            idx = cidx.value  # 0 on prefill (freshly created)
            pad = None
            if cfg.padded:
                # per-row padded-prefix length, set at prefill from the
                # attention mask (left padding: pads occupy cache [0, pad))
                pl = self.variable("cache", "pad_len",
                                   lambda: jnp.zeros((B,), jnp.int32))
                if is_prefill and attention_mask is not None:
                    pl.value = pad_lengths(attention_mask, T)
                pad = pl.value
            if rotary:
                # rotate by absolute position BEFORE caching: cached keys are
                # post-rotation, so decode attention needs no re-rotation
                if cfg.padded and is_prefill and row_pos is not None:
                    pos = row_pos  # [B, T]: 0 at each row's first real token
                elif cfg.padded and not is_prefill:
                    pos = decode_positions(idx, T, pad)
                else:
                    pos = idx + jnp.arange(T)
                q4 = apply_rotary(q4, pos, cfg.rotary_dim, cfg.rope_theta,
                                  cfg.rotary_interleaved)
                k4 = apply_rotary(k4, pos, cfg.rotary_dim, cfg.rope_theta,
                                  cfg.rotary_interleaved)
            ck.value = jax.lax.dynamic_update_slice(ck.value, k4, (0, idx, 0, 0))
            cv.value = jax.lax.dynamic_update_slice(cv.value, v4, (0, idx, 0, 0))
            cidx.value = idx + T
            if not is_prefill:
                from deepspeed_tpu.ops.attention import use_decode_kernel

                alibi = cfg.position_embedding == "alibi"
                if (use_decode_kernel() and not alibi and not cfg.padded
                        and not self.window):
                    # Pallas decode kernel: reads the cache in its native
                    # [B, S, H, D] layout (no per-token cache transpose) and
                    # only the valid [0, idx+T) prefix does compute
                    from deepspeed_tpu.ops.decode_attention import (
                        decode_attention_tp)

                    y4 = decode_attention_tp(q4, ck.value, cv.value, idx,
                                             softmax_scale=cfg.attn_scale)
                    y = y4.transpose(0, 2, 1, 3)
                else:
                    kc = ck.value.transpose(0, 2, 1, 3)
                    vc = cv.value.transpose(0, 2, 1, 3)
                    # query at slot idx+t sees keys at slots <= idx+t,
                    # minus each row's padded prefix / local window
                    mask = cache_attn_mask(cfg.n_positions, idx, T,
                                            pad if cfg.padded else None,
                                            window=self.window)
                    bias = (_alibi_bias(cfg, jnp.arange(cfg.n_positions))
                            if alibi else None)
                    y = attention(q4.transpose(0, 2, 1, 3), kc, vc,
                                  mask=mask, bias=bias, causal=False,
                                  softmax_scale=cfg.attn_scale,
                                  use_flash=False)
                cached_attn = True
        y_btc = None  # set by the transpose-free [B, T, H, D] fast path
        if not cached_attn:  # training forward, or decode-mode prefill
            if cfg.decode:  # k4/v4 exist (and carry the rotary rotation)
                k, v = k4, v4
            else:
                k = k.reshape(B, T, cfg.n_head, head_dim)
                v = v.reshape(B, T, cfg.n_head, head_dim)
            bias = (_alibi_bias(cfg, jnp.arange(T))
                    if cfg.position_embedding == "alibi" else None)
            if (cfg.attn_layout == "bthd" and bias is None
                    and attention_mask is None and not self.window
                    and cfg.use_flash is not False and _bthd_serves()):
                from deepspeed_tpu.ops.attention import record_dispatch
                from deepspeed_tpu.ops.flash_attention import (
                    flash_attention_bthd_tp, flash_ineligible)

                # Decided from the shapes before the call. A shape the
                # strided kernel cannot serve (no Pallas-legal head group,
                # a multiple of 8 or all heads, fits its VMEM budget at
                # any tile) goes to the standard dispatch below, which
                # makes and counts its own decision.
                if flash_ineligible(q4.shape, k.shape, layout="bthd") is None:
                    record_dispatch("flash_bthd")
                    y_btc = flash_attention_bthd_tp(
                        q4, k, v, causal=True,
                        softmax_scale=cfg.attn_scale).reshape(B, T, C)
            if y_btc is None:
                k = k.transpose(0, 2, 1, 3)
                v = v.transpose(0, 2, 1, 3)
                key_valid = (attention_mask[:, None, None, :].astype(bool)
                             if attention_mask is not None else None)
                if self.window:
                    # banded causal window (GPT-Neo local attention): query
                    # t sees keys in (t - window, t]
                    t_idx = jnp.arange(T)
                    band = (t_idx[None, :] > t_idx[:, None] - self.window
                            )[None, None]
                    key_valid = band if key_valid is None \
                        else key_valid & band
                y = attention(q4.transpose(0, 2, 1, 3), k, v, causal=True,
                              mask=key_valid, bias=bias,
                              softmax_scale=cfg.attn_scale,
                              use_flash=cfg.use_flash
                              if (attention_mask is None and not self.window)
                              else False)
        y = y_btc if y_btc is not None \
            else y.transpose(0, 2, 1, 3).reshape(B, T, C)
        y = nn.Dense(cfg.n_embd, dtype=cfg.dtype,
                     kernel_init=_dense_init(0.02 / (2 * cfg.n_layer) ** 0.5),
                     use_bias=cfg.attn_bias if cfg.attn_out_bias is None
                     else cfg.attn_out_bias, name="c_proj")(y)
        if cfg.dropout > 0:
            y = nn.Dropout(cfg.dropout)(y, deterministic=deterministic)
        return y if kv is None else (y, pools)


class MLP(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, x, deterministic=True):
        cfg = self.config
        h = nn.Dense(4 * cfg.n_embd, dtype=cfg.dtype, kernel_init=_dense_init(),
                     name="c_fc")(x)
        h = (nn.relu(h) if cfg.activation == "relu"
             else nn.gelu(h, approximate=cfg.activation != "gelu_exact"))
        h = nn.Dense(cfg.n_embd, dtype=cfg.dtype,
                     kernel_init=_dense_init(0.02 / (2 * cfg.n_layer) ** 0.5),
                     name="c_proj")(h)
        if cfg.dropout > 0:
            h = nn.Dropout(cfg.dropout)(h, deterministic=deterministic)
        return h


class Block(nn.Module):
    config: GPT2Config
    window: int = 0  # sliding-window size for this layer (0 = global)

    @nn.compact
    def __call__(self, x, deterministic=True, pld_theta=None, layer_frac=0.0,
                 attention_mask=None, paging=None, kv=None):
        """``kv`` is the serving KV pool on its way through the layers,
        ``(pools, layer)``; with it the block returns ``(x, pools)``."""
        cfg = self.config
        pld_on = cfg.pld and pld_theta is not None and not deterministic
        if pld_on:
            # progressive layer drop (reference progressive_layer_drop.py:5 +
            # engine.py:1800-1802 threading): depth-scaled keep probability,
            # inverted-residual scaling so eval runs all layers unchanged.
            # The residual is zeroed, not skipped — see GPT2Config.pld
            keep = jnp.asarray(1.0 - layer_frac * (1.0 - pld_theta), jnp.float32)

            def _gate(residual):
                g = jax.random.bernoulli(self.make_rng("pld"), keep)
                return jnp.where(g, residual / keep.astype(residual.dtype),
                                 jnp.zeros_like(residual))
        ln_1 = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon, dtype=cfg.dtype,
                            name="ln_1")
        if cfg.residual != "sequential":
            # parallel residual (GPT-J single-LN / NeoX two-LN): the attn and
            # MLP branches read the SAME input and their outputs sum into one
            # residual add — XLA overlaps the two branch matmul chains
            h1 = ln_1(x)
            if cfg.residual == "parallel_two_ln":
                h2 = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon,
                                  dtype=cfg.dtype, name="ln_2")(x)
            else:  # "parallel_single_ln"
                h2 = h1
            attn_out = CausalSelfAttention(cfg, window=self.window,
                                           name="attn")(
                h1, deterministic=deterministic,
                attention_mask=attention_mask, paging=paging, kv=kv)
            if kv is not None:
                attn_out, pools = attn_out
            mlp_out = MLP(cfg, name="mlp")(h2, deterministic=deterministic)
            if pld_on:
                attn_out, mlp_out = _gate(attn_out), _gate(mlp_out)
            x = x + attn_out + mlp_out
            return x if kv is None else (x, pools)
        attn_out = CausalSelfAttention(cfg, window=self.window,
                                       name="attn")(
            ln_1(x), deterministic=deterministic,
            attention_mask=attention_mask, paging=paging, kv=kv)
        if kv is not None:
            attn_out, pools = attn_out
        if pld_on:
            attn_out = _gate(attn_out)
        x = x + attn_out
        mlp_out = MLP(cfg, name="mlp")(
            nn.LayerNorm(epsilon=cfg.layer_norm_epsilon, dtype=cfg.dtype, name="ln_2")(x),
            deterministic=deterministic)
        if pld_on:
            mlp_out = _gate(mlp_out)
        x = x + mlp_out
        return x if kv is None else (x, pools)


def _pool_rows(rows, lanes: int):
    """``[B, T, n]`` rows widened with zero lanes to a pool's row (the
    scale rows: a lane a head, padded to whole registers)."""
    return jnp.pad(rows, ((0, 0), (0, 0), (0, lanes - rows.shape[-1])))


def _paged_pool_vars(module, cfg):
    """The serving KV pool, declared ONCE by the block stack (``None``
    unless this is a paged-decode model): ``cache`` variables of the one
    resident shape ``[n_layer, blocks, block_size, lanes]`` —
    ``key_pool`` / ``value_pool`` with ``lanes = n_head * head_dim`` and,
    for ``paged_kv_dtype="int8"``, the f32 ``key_scale`` / ``value_scale``
    side pools with ``scale_lanes(n_head)`` lanes
    (``ops/decode_attention.py``, "THE POOL'S ONE SHAPE"). The stack
    hands ``(pools, layer)`` down to each layer's attention and takes the
    updated pools back — a scan CARRIES them, so the compiled program
    writes and reads one buffer in place where a scanned ``cache``
    collection would slice a layer out and re-stack the pool every
    step."""
    if not (cfg.decode and cfg.paged):
        return None
    if cfg.padded:
        raise ValueError("paged and padded decode are mutually "
                         "exclusive: ragged prompts are the block "
                         "table's job in paged mode")
    nb, bs = cfg.paged_num_blocks, cfg.paged_block_size
    if nb <= 1 or bs <= 0:
        raise ValueError(
            f"paged decode needs paged_num_blocks > 1 (got {nb}; "
            f"block 0 is the reserved garbage sink) and "
            f"paged_block_size > 0 (got {bs})")
    if cfg.paged_kv_dtype not in ("", "int8"):
        raise ValueError(f"paged_kv_dtype must be '' or 'int8', got "
                         f"{cfg.paged_kv_dtype!r}")
    quant = cfg.paged_kv_dtype == "int8"
    leaves = {name: (cfg.n_embd, jnp.int8 if quant else cfg.dtype)
              for name in ("key_pool", "value_pool")}
    if quant:
        from deepspeed_tpu.ops.decode_attention import scale_lanes

        leaves.update({name: (scale_lanes(cfg.n_head), jnp.float32)
                       for name in ("key_scale", "value_scale")})
    return {name: module.variable("cache", name, jnp.zeros,
                                  (cfg.n_layer, nb, bs, lanes), dtype)
            for name, (lanes, dtype) in leaves.items()}


class _ScanBody(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, carry, deterministic, pld_theta, layer_frac,
                 attention_mask, paging, layer):
        """``carry`` is ``x``, or ``(x, pools)`` when ``layer`` (the
        scanned-in layer index of a paged-decode stack) is given."""
        cfg = self.config
        x, pools = (carry, None) if layer is None else carry
        if cfg.remat:
            x = saved_block_input(x, cfg)
        out = _remat_block(cfg)(
            cfg, name="block")(
            x, deterministic, pld_theta, layer_frac, attention_mask, paging,
            None if layer is None else (pools, layer))
        return out, None


class _AtUseBlock(Block):
    """:class:`Block` as the scanned stack's ZeRO-3 use site
    (:func:`_stack_gathered_ahead`, at the end of this file): a class of
    its own so that :func:`_remat_block` gives it the use site's policy
    (``block is not Block``: q, k, v are not saved beside the ``c_attn``
    output). Its weights arrive gathered; it gathers nothing itself."""


def _no_cotangent(tree):
    """Zero cotangents for the arguments nothing is differentiated by:
    floating zeros, ``float0`` for integer leaves (rng keys)."""
    return jax.tree_util.tree_map(
        lambda a: jnp.zeros_like(a)
        if jnp.issubdtype(a.dtype, jnp.inexact)
        else np.zeros(a.shape, jax.dtypes.float0), tree)


def _gathering() -> bool:
    """Whether the engine is tracing a ZeRO-3 step (an active
    ``partition.GatherPlan``): the scanned stack is then the use site of
    its own weights. Never while serving, in ``init``, at a lower stage
    or on one device."""
    from deepspeed_tpu.runtime.zero import partition as zero

    return zero.gathering()


class ScanBlocks(nn.Module):
    """All transformer blocks as one scanned body: params get a leading
    ``n_layer`` axis and XLA compiles a single block. Under the engine's
    ZeRO-3 step the stack is the use site of its own weights and runs
    :func:`_stack_gathered_ahead` instead; a layer's small leaves (biases
    and norms) are persistent: whole on every chip, never gathered."""

    config: GPT2Config

    @nn.compact
    def __call__(self, x, deterministic=True, pld_theta=None,
                 attention_mask=None, paging=None):
        cfg = self.config
        pools = _paged_pool_vars(self, cfg)
        if pools is None and _gathering():
            return _stack_gathered_ahead(self, x, deterministic, pld_theta,
                                         attention_mask)
        ScannedBlock = nn.scan(
            _ScanBody,
            variable_axes={"params": 0, "cache": 0},
            split_rngs={"params": True, "dropout": True, "pld": True},
            in_axes=(nn.broadcast, nn.broadcast, 0, nn.broadcast,
                     nn.broadcast, nn.broadcast if pools is None else 0),
            length=cfg.n_layer,
            metadata_params={nn.meta.PARTITION_NAME: "layers"},
        )
        # 1-indexed depth fractions (paper eq. 6 / layer_keep_probs): layer i
        # of L keeps with prob 1 - i/L*(1-theta), i = 1..L
        fracs = (jnp.arange(cfg.n_layer, dtype=jnp.float32) + 1.0) / max(
            1, cfg.n_layer)
        if pools is None:
            carry, layers = x, None
        else:
            # the pool rides the scan as CARRY beside x and the layer index
            # is scanned in like ``fracs``: every layer scatters into and
            # reads from the one stacked buffer
            carry = (x, {n: v.value for n, v in pools.items()})
            layers = jnp.arange(cfg.n_layer, dtype=jnp.int32)
        carry, _ = ScannedBlock(cfg, name="h")(
            carry, deterministic, pld_theta, fracs, attention_mask, paging,
            layers)
        if pools is None:
            return carry
        x, new = carry
        for n, v in pools.items():
            v.value = new[n]
        return x


class LoopBlocks(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, x, deterministic=True, pld_theta=None,
                 attention_mask=None, paging=None):
        cfg = self.config
        block_cls = _remat_block(cfg)
        windows = cfg.attention_windows or (0,) * cfg.n_layer
        pools = _paged_pool_vars(self, cfg)
        vals = pools and {n: v.value for n, v in pools.items()}
        for i in range(cfg.n_layer):
            if cfg.remat:
                x = saved_block_input(x, cfg)
            out = block_cls(cfg, window=windows[i], name=f"h_{i}")(
                x, deterministic, pld_theta, (i + 1) / max(1, cfg.n_layer),
                attention_mask, paging, pools and (vals, i))
            x, vals = out if pools else (out, None)
        for n, v in (pools or {}).items():
            v.value = vals[n]
        return x


class GPT2LMHeadModel(nn.Module):
    """GPT-2 with tied-embedding LM head.

    ``__call__(input_ids)`` → logits. ``loss(params, batch)`` (via
    :func:`gpt2_loss_fn`) is the engine-facing objective.
    """

    config: GPT2Config
    # the parameter ``__call__`` looks its tokens up in: the serving engine
    # reads this leaf's layout to choose ``paging["lookup"]``
    lookup_table = "wte"

    @nn.compact
    def __call__(self, input_ids, deterministic=True, return_hidden=False,
                 pld_theta=None, attention_mask=None, paging=None):
        cfg = self.config
        B, T = input_ids.shape
        wte = self.param("wte", _dense_init(), (cfg.vocab_size, cfg.n_embd), jnp.float32)
        # a paged serving program names the access pattern its builder
        # chose from the table's real layout (decode_utils.lookup_form);
        # every other caller reads rows, ``wte[input_ids]``
        x = embed_lookup(wte, input_ids,
                         (paging or {}).get("lookup", "rows")).astype(cfg.dtype)
        if cfg.position_embedding == "learned":
            # table carries position_offset pad rows (OPT stores 2)
            wpe = self.param("wpe", _dense_init(0.01),
                             (cfg.n_positions + cfg.position_offset,
                              cfg.n_embd), jnp.float32)
            if cfg.decode and cfg.paged:
                if paging is None:
                    raise ValueError("paged decode needs the `paging` "
                                     "call argument")
                # per-row positions from the paging lengths — no shared
                # `position` cache variable: serving slots advance
                # independently (pads read a garbage position; their
                # outputs are never consumed)
                pos_ids = jnp.clip(paged_positions(paging["lengths"], T),
                                   0, cfg.n_positions - 1)
                pos_emb = wpe[pos_ids + cfg.position_offset]  # [B, T, C]
            elif cfg.decode:
                # track the absolute position across prefill/decode calls
                pos_var = self.variable("cache", "position",
                                        lambda: jnp.zeros((), jnp.int32))
                pos = pos_var.value
                pos_var.value = pos + T
                if cfg.padded:
                    # per-row positions: pads shift each row's position 0
                    # to its first real token (left padding)
                    pl = self.variable("cache", "pad_len",
                                       lambda: jnp.zeros((B,), jnp.int32))
                    if attention_mask is not None:  # prefill
                        pl.value = pad_lengths(attention_mask, T)
                        pos_ids = row_positions(attention_mask)
                    else:  # decode step
                        pos_ids = jnp.clip(
                            (pos + jnp.arange(T))[None] - pl.value[:, None],
                            0)
                    pos_emb = wpe[pos_ids + cfg.position_offset]  # [B, T, C]
                else:
                    pos_emb = jax.lax.dynamic_slice(
                        wpe, (pos + cfg.position_offset, 0),
                        (T, cfg.n_embd))[None]
            elif attention_mask is not None:
                pos_ids = row_positions(attention_mask)
                pos_emb = wpe[pos_ids + cfg.position_offset]
            else:
                pos_emb = wpe[None, cfg.position_offset:
                              cfg.position_offset + T]
            x = x + pos_emb.astype(cfg.dtype)
        # "alibi": no position table — the bias lives in attention logits
        # (per-row pad shifts cancel under softmax's shift invariance)
        if cfg.embedding_layernorm:  # BLOOM's word_embeddings_layernorm
            x = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon, dtype=cfg.dtype,
                             name="emb_ln")(x)
        if cfg.dropout > 0:
            x = nn.Dropout(cfg.dropout)(x, deterministic=deterministic)
        if cfg.attention_windows is not None and cfg.scan_layers:
            raise ValueError(
                "attention_windows (per-layer local attention) needs "
                "scan_layers=False: the window is a static per-layer "
                "property, but a scanned stack compiles ONE body")
        blocks = ScanBlocks if cfg.scan_layers else LoopBlocks
        if cfg.decode and cfg.paged and paging and not paging.get("prefill"):
            from deepspeed_tpu.ops.attention import (record_dispatch,
                                                     use_decode_kernel)
            from deepspeed_tpu.ops.decode_attention import (paged_plan,
                                                            paged_step_work)

            if use_decode_kernel():
                # the paged kernel's grid follows this step's lengths and
                # nothing a layer changes: listed once here, as the layers'
                # calls take it, not once a layer inside the stack
                record_dispatch("paged_decode_tile%d" % paged_plan(
                    cfg.paged_block_size).tile_keys)
                paging = {**paging, "work": paged_step_work(
                    paging["lengths"], paging["block_tables"], T,
                    cfg.paged_block_size, _paged_valid(paging, cfg, T))}
        if cfg.remat and cfg.cpu_checkpointing:
            # cpu_checkpointing: ONE checkpoint over the whole stack whose
            # policy host-offloads the per-layer "block_in" residuals (the
            # values the reference moves to CPU, checkpointing.py:485);
            # backward streams them back and recomputes each block.
            # deterministic (arg 2 counting self) is Python-branched inside,
            # so it is static and must be passed positionally
            blocks = nn.remat(blocks, prevent_cse=False,
                              policy=offload_policy(cfg),
                              static_argnums=(2,))
            x = blocks(cfg, name="transformer")(x, deterministic, pld_theta,
                                                attention_mask, paging)
        else:
            x = blocks(cfg, name="transformer")(x, deterministic=deterministic,
                                                pld_theta=pld_theta,
                                                attention_mask=attention_mask,
                                                paging=paging)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon, dtype=cfg.dtype, name="ln_f")(x)
        if cfg.tied_head:
            head_w, head_b = wte, None
        else:  # GPT-J/NeoX: separate lm_head (GPT-J's carries a bias)
            head_w = self.param("lm_head", _dense_init(),
                                (cfg.vocab_size, cfg.n_embd), jnp.float32)
            head_b = (self.param("lm_head_bias", nn.initializers.zeros,
                                 (cfg.vocab_size,), jnp.float32)
                      if cfg.lm_head_bias else None)
        if return_hidden:
            return x, head_w
        # logits in fp32 for a stable softmax-xent
        logits = jnp.einsum("btc,vc->btv", x, head_w.astype(cfg.dtype),
                            preferred_element_type=jnp.float32)
        if head_b is not None:
            logits = logits + head_b
        return logits


def chunked_softmax_xent(hidden, wte, labels, chunk: int = 128,
                         ignore_index: int = -100, bias=None):
    """Softmax cross-entropy against a tied embedding WITHOUT materializing
    the full [B, T, V] fp32 logits — the LM-head memory hog on long
    sequences. Computes per-sequence-chunk logits inside a remat'd scan, so
    peak memory is [B, chunk, V] and backward recomputes each chunk.
    """
    B, T, C = hidden.shape
    chunk = min(chunk, T)
    pad = (-T) % chunk
    if pad:  # pad to a chunk multiple; padded tokens are ignore_index
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=ignore_index)
        T += pad
    n_chunks = T // chunk
    h = hidden.reshape(B, n_chunks, chunk, C).transpose(1, 0, 2, 3)
    lab = labels.reshape(B, n_chunks, chunk).transpose(1, 0, 2)
    w = wte.astype(hidden.dtype)

    @jax.checkpoint
    def chunk_loss(hc, lc):
        logits = jnp.einsum("btc,vc->btv", hc, w,
                            preferred_element_type=jnp.float32)
        if bias is not None:
            logits = logits + bias
        valid = lc != ignore_index
        safe = jnp.where(valid, lc, 0)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
        return jnp.sum((logz - gold) * valid), jnp.sum(valid)

    def body(carry, xs):
        total, count = carry
        l, n = chunk_loss(*xs)
        return (total + l, count + n), None

    (total, count), _ = jax.lax.scan(body, (jnp.zeros((), jnp.float32),
                                            jnp.zeros((), jnp.int32)), (h, lab))
    return total / jnp.maximum(count, 1)


def shift_labels(labels, ignore_index: int = -100):
    """Next-token shift: labels[t] ← labels[t+1], last column ignored."""
    return jnp.concatenate(
        [labels[:, 1:],
         jnp.full((labels.shape[0], 1), ignore_index, labels.dtype)], axis=1)


def lm_head_loss(hidden, head_w, shifted_labels, bias=None,
                 dense_budget: int = 1_000_000_000, chunk: int = 512):
    """LM-head cross-entropy with the dense-vs-chunked switch: materialize
    the full [B, T, V] fp32 logits when they fit ``dense_budget`` bytes
    (faster — one fused program, no recompute), else the remat'd chunked
    scan. The single policy point for every engine tier."""
    B, T, _ = hidden.shape
    V = head_w.shape[0]
    if B * T * V * 4 <= dense_budget:
        logits = jnp.einsum("btc,vc->btv", hidden, head_w.astype(hidden.dtype),
                            preferred_element_type=jnp.float32)
        if bias is not None:
            logits = logits + bias
        return cross_entropy_loss(logits, shifted_labels)
    return chunked_softmax_xent(hidden, head_w, shifted_labels, chunk=chunk,
                                bias=bias)


def cross_entropy_loss(logits, labels, ignore_index: int = -100):
    """Mean token cross-entropy, masked where ``labels == ignore_index``."""
    valid = labels != ignore_index
    safe_labels = jnp.where(valid, labels, 0)
    logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(logits.astype(jnp.float32),
                               safe_labels[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * valid
    return nll.sum() / jnp.maximum(valid.sum(), 1)


class GPT2ForTraining:
    """Engine-ready wrapper: ``initialize(model=GPT2ForTraining(cfg))``.

    Exposes the engine contract — ``loss_fn(params, batch, rngs)`` and
    ``init(rng, batch)`` — around :class:`GPT2LMHeadModel`.
    """

    def __init__(self, config: GPT2Config):
        self.config = config
        self.model = GPT2LMHeadModel(config)
        self.loss_fn = gpt2_loss_fn(self.model)

    @staticmethod
    def _input_ids(batch):
        if isinstance(batch, dict):
            return batch["input_ids"]
        if isinstance(batch, (tuple, list)):
            return batch[0]
        return batch

    def init(self, rng, batch):
        return self.model.init(rng, self._input_ids(batch))

    def apply(self, variables, batch, rngs=None):
        return self.model.apply(variables, self._input_ids(batch), rngs=rngs)

    def zero3_use_sites(self):
        """Engine hook: where this model asks
        ``runtime/zero/partition.gather_at_use`` for its weights, as
        param-path prefix -> leading scanned dims. The scanned stack
        gathers a layer a scan step; the tables are gathered once in the
        loss. None for the unrolled stack, nor where a stack-level
        checkpoint or a model-axis constraint on the saved activations
        (``cpu_checkpointing``, ``partition_activations``) owns the
        block's boundary: those keep the engine's GSPMD program."""
        cfg = self.config
        if (not cfg.scan_layers or cfg.cpu_checkpointing
                or cfg.partition_activations):
            return {}
        return {"transformer/h/block": 1, **{k: 0 for k in _TABLES}}

    def with_activation_checkpointing(self, enabled: bool, policy: str = "full",
                                      cpu_checkpointing: bool = False,
                                      partition_activations: bool = False):
        """Engine hook: the ds-config ``activation_checkpointing`` section
        overrides the model's remat setting (reference ``configure``,
        runtime/activation_checkpointing/checkpointing.py:830 — there the
        config drives CheckpointFunction; here it drives jax.checkpoint).
        ``cpu_checkpointing`` host-offloads the saved inter-layer residuals
        (ref :485); ``partition_activations`` shards them over the model
        axis (ref :372)."""
        if policy == "none":
            enabled, policy = False, "full"
        cfg = dataclasses.replace(
            self.config, remat=enabled, remat_policy=policy,
            cpu_checkpointing=cpu_checkpointing,
            partition_activations=partition_activations)
        return GPT2ForTraining(cfg)

    def with_progressive_layer_drop(self, enabled: bool = True):
        """Engine hook: PLD config turns on the drop-capable block stack
        (reference threads pld into forward, engine.py:1800-1802)."""
        return GPT2ForTraining(dataclasses.replace(self.config, pld=enabled))


class GPT2Embed(nn.Module):
    """Input embedding layer for the pipeline layout (stage-0 work). Its
    parameters are tied with the LM head via ``TiedLayerSpec(key="embed")``.
    """

    config: GPT2Config

    @nn.compact
    def __call__(self, input_ids, deterministic=True):
        cfg = self.config
        wte = self.param("wte", _dense_init(), (cfg.vocab_size, cfg.n_embd),
                         jnp.float32)
        wpe = self.param("wpe", _dense_init(0.01), (cfg.n_positions, cfg.n_embd),
                         jnp.float32)
        T = input_ids.shape[-1]
        x = wte[input_ids].astype(cfg.dtype) + wpe[None, :T].astype(cfg.dtype)
        if cfg.dropout > 0:
            x = nn.Dropout(cfg.dropout)(x, deterministic=deterministic)
        return x


class GPT2FinalNorm(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        return nn.LayerNorm(epsilon=cfg.layer_norm_epsilon, dtype=cfg.dtype,
                            name="ln_f")(x)


def gpt2_pipe(config: GPT2Config):
    """GPT-2 as a :class:`PipelineModule` layer list (reference: GPT2 built
    from ``LayerSpec`` lists for ``PipelineModule`` in Megatron-DeepSpeed).

    Layout: tied embedding → n_layer Blocks (sharded over ``pipe``) →
    final LN → tied LM head. Loss shifts labels internally.
    """
    from deepspeed_tpu.runtime.pipe.module import (LayerSpec, PipelineModule,
                                                   TiedLayerSpec)

    def head_fn(embed_params, x):
        wte = embed_params["wte"]
        return jnp.einsum("btc,vc->btv", x, wte.astype(x.dtype),
                          preferred_element_type=jnp.float32)

    def loss_fn(logits, labels):
        return cross_entropy_loss(logits, shift_labels(labels))

    layers = [
        TiedLayerSpec(GPT2Embed, config, key="embed"),
        *[LayerSpec(Block, config) for _ in range(config.n_layer)],
        LayerSpec(GPT2FinalNorm, config),
        TiedLayerSpec(GPT2Embed, config, key="embed", forward_fn=head_fn),
    ]
    return PipelineModule(layers=layers, loss_fn=loss_fn,
                          partition_method="parameters",
                          use_rngs=config.dropout > 0)


# the model-level leaves that are ZeRO-3 use sites beside the scanned stack
_TABLES = ("wte", "wpe", "lm_head")


def _tables_at_use(params, cfg):
    """The embedding tables and an untied head through the ZeRO-3 seam,
    once for all their uses (``wte`` is looked up AND is the tied head):
    the compute dtype on the wire, the values back in float32, because
    the lookup's scatter-add and the sum of the two uses' cotangents are
    taken in the table's dtype. The identity outside a stage-3 step."""
    from deepspeed_tpu.runtime.zero import partition as zero

    if not zero.gathering():
        return params
    return {**params, **{
        k: zero.gather_at_use(params[k], (k,), dtype=cfg.dtype,
                              keep_dtype=True)
        for k in _TABLES if k in params}}


def gpt2_loss_fn(model: GPT2LMHeadModel):
    """Engine-facing loss: ``fn(params, batch, rngs=None) -> loss``.

    ``batch`` is ``(input_ids, labels)`` or a dict with those keys; standard
    next-token objective (labels shifted internally).
    """

    def loss_fn(params, batch, rngs=None, pld_theta=None):
        if isinstance(batch, dict):
            input_ids, labels = batch["input_ids"], batch.get("labels")
        else:
            input_ids, labels = batch
        if labels is None:
            labels = input_ids
        params = _tables_at_use(params, model.config)
        hidden, wte = model.apply({"params": params}, input_ids,
                                  deterministic=rngs is None, rngs=rngs,
                                  return_hidden=True, pld_theta=pld_theta)
        # wte is the LM-head matrix: the tied embedding, or the separate
        # lm_head (whose optional bias lives beside it in the param tree).
        # Without remat the saved block activations already crowd HBM —
        # only afford the dense head a smaller logits budget there
        return lm_head_loss(
            hidden, wte, shift_labels(labels), bias=params.get("lm_head_bias"),
            dense_budget=3_500_000_000 if model.config.remat
            else 1_000_000_000)

    return loss_fn


# ---------------------------------------------------------------------------
# The scanned stack under ZeRO-3: a layer's weights gathered one layer ahead.
# (Here, behind everything else, so that no other program's source lines
# move: a Pallas kernel's compiled text carries its callers' line numbers.)
def _stack_gathered_ahead(stack, x, deterministic, pld_theta, attention_mask):
    """:class:`ScanBlocks` (``stack``) under the ZeRO-3 plan: each layer's
    weights are gathered ONE LAYER AHEAD in the forward pass (the
    reference's parameter prefetch, ``partitioned_param_coordinator.py``).
    A compiled loop body overlaps a collective only with work of the same
    iteration, and a layer's first weights have nothing of their own layer
    before them: so scan step ``i`` runs layer ``i`` on the weights that
    step ``i - 1`` gathered, carried in, and gathers layer ``i + 1``'s
    beside its own matmuls (one more layer of compute-dtype weights live),
    the leaves that can in one collective (``partition._gather_together``).

    No gathered weight is saved for it. A layer is a ``custom_vjp``: its
    forward rule differentiates the block (under the block's own remat
    policy, so the residuals are what they were) and DROPS the carried
    weights from what it keeps; its backward rule gathers the layer's
    weights again inside its own scan step, as the rematerialised backward
    always has, hands them to the block's pullback, and scatters the
    float32 gradients (``partition.gather_at_use``'s transpose: the ring).
    The backward does not gather ahead: on the chip its gathers then share
    the wire with the ring's permutes and lose what they gain (PERF.md,
    PR 47). Dropout and layer drop draw from one key a layer."""
    from deepspeed_tpu.runtime.zero import partition as zero

    cfg, n = stack.config, stack.config.n_layer
    shards = nn.meta.unbox(stack.variables["params"]["h"]["block"])
    site = stack.path + ("h", "block")
    whole = zero.gatherer(site, dtype=cfg.dtype, stacked=1)
    ahead_of = zero.gatherer(site, dtype=cfg.dtype, stacked=1, ahead=n)
    block = _remat_block(cfg, _AtUseBlock)(cfg)
    # 1-indexed depth fractions, as ScanBlocks scans them in
    fracs = (jnp.arange(n, dtype=jnp.float32) + 1.0) / max(1, n)
    keys = {name: jax.random.split(stack.make_rng(name), n)
            for name in ("dropout", "pld") if stack.has_rng(name)}

    def run(weights, x, frac, keys):
        return block.apply({"params": weights}, x, deterministic, pld_theta,
                           frac, attention_mask, None, None,
                           rngs=keys or None)

    kept = {}  # the pullback's structure: static, so not a residual

    @jax.custom_vjp
    def layer(weights, mine, x, frac, keys):
        return run(weights, x, frac, keys)

    def layer_fwd(weights, mine, x, frac, keys):
        out, pullback = jax.vjp(lambda w, x: run(w, x, frac, keys),
                                weights, x)
        leaves, kept["tree"] = jax.tree_util.tree_flatten(pullback)
        carried = jax.tree_util.tree_leaves(weights)
        # which of the pullback's residuals ARE the carried weights
        kept["weights"] = [next((j for j, w in enumerate(carried)
                                 if leaf is w), None) for leaf in leaves]
        return out, ([leaf for leaf, j in zip(leaves, kept["weights"])
                      if j is None], mine, (frac, keys))

    def layer_bwd(residuals, ct):
        saved, mine, rest = residuals
        weights, scatter = jax.vjp(whole, mine)
        again, saved = jax.tree_util.tree_leaves(weights), iter(saved)
        pullback = jax.tree_util.tree_unflatten(kept["tree"], [
            next(saved) if j is None else again[j] for j in kept["weights"]])
        ct_weights, ct_x = pullback(ct)
        return (_no_cotangent(weights), scatter(ct_weights)[0], ct_x,
                *_no_cotangent(rest))

    layer.defvjp(layer_fwd, layer_bwd)

    def at(i):
        return jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
            shards)

    def step(carry, xs):
        x, weights = carry
        i, mine, frac, keys = xs
        # the next layer's weights: nothing here depends on this layer
        # (the last step gathers its own again: a forty-eighth more)
        ahead = jax.lax.stop_gradient(
            ahead_of(at(jnp.minimum(i + 1, n - 1))))
        if cfg.remat:
            x = saved_block_input(x, cfg)
        return (layer(weights, mine, x, frac, keys), ahead), None

    (x, _), _ = jax.lax.scan(
        step, (x, jax.lax.stop_gradient(ahead_of(at(0)))),
        (jnp.arange(n, dtype=jnp.int32), shards, fracs, keys))
    return x


# ---------------------------------------------------------------------------
# the paged decode kernel's seam (here, at the file's end: a Pallas kernel's
# lowered text carries its callers' line numbers, so what stands above keeps
# its lines)
def _paged_kernel_serves(cfg, window) -> bool:
    """Whether a layer's paged decode step runs the Pallas kernel (else the
    dense gather oracle: no TPU, ALiBi's bias, a local window)."""
    from deepspeed_tpu.ops.attention import use_decode_kernel

    return (use_decode_kernel() and cfg.position_embedding != "alibi"
            and not window)


def _paged_call_writes(batch: int, tq: int, heads: int) -> bool:
    from deepspeed_tpu.ops.decode_attention import paged_call_writes

    return paged_call_writes(batch, tq, heads)


def _paged_valid(paging, cfg, tq: int):
    """``paging["num_valid"]`` for the step's work list where the layers'
    calls write the step's rows themselves (the list then carries where
    each goes), else None."""
    batch = paging["lengths"].shape[0]
    return (paging["num_valid"]
            if _paged_call_writes(batch, tq, cfg.n_head) else None)


def _paged_kernel_attend(q4, pools, tables, lengths, layer, scale, work,
                         num_valid, rows):
    """One layer's paged kernel call over the ``pools`` dict (bf16, or int8
    with its scale side pools). ``rows`` (the step's new rows by pool name,
    ``[B, 1, H, D]``, a scale ``[B, 1, H]``) where the call writes them, or
    None where the caller scattered them. Returns ``(y4, pools)``."""
    from deepspeed_tpu.ops import decode_attention as ops

    names = ("key_pool", "value_pool") + (
        ("key_scale", "value_scale") if "key_scale" in pools else ())
    attend = (ops.decode_attention_paged_int8_tp if len(names) == 4
              else ops.decode_attention_paged_tp)
    args = (q4, *(pools[n] for n in names), tables, lengths, layer)
    if rows is None:
        return attend(*args, softmax_scale=scale, work=work), pools
    b = q4.shape[0]
    y4, written = attend(
        *args, softmax_scale=scale, work=work, valid=num_valid,
        rows=tuple(_pool_rows(rows[n].reshape(b, 1, -1), pools[n].shape[-1])
                   for n in names))
    return y4, {**pools, **dict(zip(names, written))}


def _kv_write_most(self, batch: int, tq: int):
    """The most busy rows of a paged step over ``[batch, tq]`` tokens whose
    KV rows every layer's kernel call writes itself (a step with more
    scatters every slot's row: ``paged_most_writers``), or None where the
    step's program always scatters. What ``ServingEngine`` counts its steps
    by (``stats()["kv_write"]``)."""
    from deepspeed_tpu.ops.decode_attention import paged_most_writers

    cfg = self.config
    windows = cfg.attention_windows or (0,)
    if not (all(_paged_kernel_serves(cfg, w) for w in windows)
            and _paged_call_writes(batch, tq, cfg.n_head)):
        return None
    return paged_most_writers(batch, 4 if cfg.paged_kv_dtype == "int8" else 2)


# (assigned here and not in the class body, whose lines the flash kernels'
# lowered text carries)
GPT2LMHeadModel.kv_write_most = _kv_write_most
