"""Dropless mixture-of-experts for an expert-parallel share.

``moe/sharded_moe.py`` is GShard's layer: top-1 / top-2 gating into
``[groups, tokens, experts, capacity]`` tensors, tokens over capacity
dropped. The models served today route differently: a sigmoid score for
every one of the published experts, the top ``k`` of score + a selection
bias chosen, the chosen scores renormalised, NO token dropped; and a chip
holds only some of the experts. This module is that layer, told which
experts it holds:

- :func:`route` scores ALL ``n_routed`` experts in float32 (matmul,
  sigmoid, bias add and top-k: a routed set that flips at a near tie moves
  the output by more than rounding) and returns the chosen experts and
  their weights, chosen inside the best groups where the family routes by
  groups (:func:`within_groups`);
- :func:`expert_ffn` adds, for each token, the terms of the experts held
  HERE (``first_expert .. first_expert + E``) and nothing for the others:
  on one chip of an expert-parallel group that is the layer without its
  exchange, and the sum over all the shares is the whole layer
  (``tests/unit/test_mimo_v2.py``, the share test).

The grouped matmul sorts the (token, expert) pairs routed here by expert,
pads each expert's group to whole row tiles, and runs ONE Pallas kernel
over the live tiles: a tile's expert is a scalar-prefetched operand, so
its three weight matrices stream through VMEM once a tile, and an expert
that no token of the step chose is never read. The grid's length is the
traced number of live tiles; buffers have the worst case's static shape.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.utils.compat import tpu_compiler_params

# the counters a layer call returns, in this order (int32 each)
COUNTERS = ("experts_touched", "experts_held", "pairs_here", "pairs_all")


def within_groups(select, top_k: int, n_group: int = 1, topk_group: int = 1):
    """The ``top_k`` experts of each row of ``select [T, experts]`` (score
    + bias), int ``[T, k]``. With ``n_group`` > 1 they are chosen INSIDE
    the ``topk_group`` best of ``n_group`` groups of contiguous experts, a
    group's score the sum of its two largest entries (group-limited
    routing: a token's experts lie on at most ``topk_group`` of the
    ``n_group`` chips that hold a group each). One group: the plain top
    ``k``, the one operation there always was."""
    if n_group > 1:
        rows, experts = select.shape
        grouped = select.reshape(rows, n_group, experts // n_group)
        # (two maxima, the second with the first's place taken out: a
        # ``top_k`` of 2 is a sort of every group on the chip)
        first = jnp.argmax(grouped, axis=-1, keepdims=True)
        rest = jnp.where(first == jnp.arange(grouped.shape[-1]), -jnp.inf,
                         grouped)
        score = jnp.max(grouped, axis=-1) + jnp.max(rest, axis=-1)
        _, best = jax.lax.top_k(score, topk_group)
        kept = jnp.any(best[..., None] == jnp.arange(n_group), axis=1)
        select = jnp.where(kept[..., None], grouped, -jnp.inf).reshape(
            rows, experts)
    return jax.lax.top_k(select, top_k)[1]


def route(x, router_kernel, selection_bias, top_k: int, *,
          norm_eps: float = 0.0, scale: float = 1.0,
          scoring: str = "sigmoid", renormalize: bool = True,
          n_group: int = 1, topk_group: int = 1):
    """``x [T, D]`` -> ``(experts [T, k] int32, weights [T, k] float32)``:
    ``s = scoring(x W_r)`` over every published expert, the top ``k`` of
    ``s + bias`` chosen (inside the ``topk_group`` best of ``n_group``
    groups, where there are groups: :func:`within_groups`), ``w = scale *
    s[chosen] / (sum s[chosen] + norm_eps)``. All float32. ``scoring`` is
    the family's own function of the gate's logits: ``"sigmoid"``, a score
    an expert by itself (MiMo-V2, LFM2-MoE), or ``"softmax"`` over all the
    experts (DeepSeek-V2, whose chosen scores are the weights as they
    stand: ``renormalize=False``, and which has no selection bias:
    ``None``). The two constants are a family's own too (LFM2-MoE: 1e-6
    and its ``routed_scaling_factor``). At its defaults each argument adds
    no operation to the program."""
    logits = jnp.dot(
        x.astype(jnp.float32), router_kernel.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST)
    if scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    elif scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"scoring {scoring!r}: 'sigmoid' or 'softmax'")
    select = scores
    if selection_bias is not None:
        select = scores + selection_bias.astype(jnp.float32)[None]
    experts = within_groups(select, top_k, n_group, topk_group)
    weights = jnp.take_along_axis(scores, experts, axis=1)
    if renormalize:
        total = jnp.sum(weights, axis=1, keepdims=True)
        if norm_eps:
            total = total + norm_eps
        weights = weights / total
    if scale != 1.0:
        weights = weights * scale
    return experts.astype(jnp.int32), weights


def _counters(local, held, n_held: int, valid):
    counts = jnp.sum((local[..., None] == jnp.arange(n_held))
                     & held[..., None], axis=(0, 1), dtype=jnp.int32)
    return counts, jnp.stack([
        jnp.sum(counts > 0, dtype=jnp.int32), jnp.asarray(n_held, jnp.int32),
        jnp.sum(counts), jnp.sum(valid, dtype=jnp.int32) * local.shape[1]])


def glu(gate, up, limit: float = 0.0):
    """``silu(gate) * up``; with ``limit`` > 0 the clamped form: ``gate <-
    min(gate, limit)``, ``up <- clip(up, -limit, limit)`` first. At 0 the
    operations there always were."""
    if limit:
        gate, up = jnp.minimum(gate, limit), jnp.clip(up, -limit, limit)
    return jax.nn.silu(gate) * up


def _ffn_rows(x, gate, up, down, limit: float = 0.0):
    h = jnp.dot(x, gate, preferred_element_type=jnp.float32)
    u = jnp.dot(x, up, preferred_element_type=jnp.float32)
    return jnp.dot(glu(h, u, limit).astype(x.dtype), down,
                   preferred_element_type=jnp.float32)


def _expert_ffn_dense(x, local, held, weights, gate, up, down,
                      limit: float = 0.0):
    """The plain form: every held expert over every token, weighted by the
    token's weight for it (0 where it did not choose it). Reads every
    held expert and multiplies ``E`` times too much: the oracle, and what
    serves where no TPU does."""
    n_held = gate.shape[0]
    combine = jnp.sum(jnp.where(
        held[..., None] & (local[..., None] == jnp.arange(n_held)),
        weights[..., None], 0.0), axis=1)                         # [T, E]

    def one(carry, ew):
        g, u, d, c = ew
        return carry + c[:, None] * _ffn_rows(x, g, u, d, limit), None

    out, _ = jax.lax.scan(one, jnp.zeros(x.shape, jnp.float32),
                          (gate, up, down, combine.T))
    return out


def _gmm_kernel(expert_ref, x_ref, g_ref, u_ref, d_ref, o_ref, *, limit):
    # the output tile stays in VMEM across the steps over the experts'
    # width (same block index): it is the float32 accumulator
    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]
    h = jnp.dot(x, g_ref[...], preferred_element_type=jnp.float32)
    u = jnp.dot(x, u_ref[...], preferred_element_type=jnp.float32)
    o_ref[...] += jnp.dot(glu(h, u, limit).astype(x.dtype), d_ref[...],
                          preferred_element_type=jnp.float32)


def width_tile(f: int, tile_f: int = 512) -> int:
    """Columns of an expert's width a grid step takes: ``tile_f`` where it
    divides the width (or the whole of a narrower one); else the widest
    run of whole 128-lane registers up to twice that which does (1792 =
    2 x 896: two steps a tile of rows, each matrix's block no larger than
    a 4096 x 512 one). A width of a PRIME number of registers has no such
    run but one register: 1408 = 11 x 128 (DeepSeek-V2-Lite's experts)
    takes 128, eleven steps a tile of rows, each moving three blocks of
    2048 x 128 (0.5 MB a matrix). That costs nothing a run can see: the
    whole width in ONE step (three blocks of 5.8 MB) read 1.543 ms a layer
    against 1.532 at a decode step's 48 rows and 2.152 against 2.129 at a
    chunk's 512, on the chip over 64 experts (PERF.md, PR 45): the
    kernel's time is the weights' bytes, whatever the step that moves
    them, so the rule stands as it stood."""
    tile_f = min(tile_f, f)
    if f % tile_f:
        fits = [w for w in range(128, 2 * tile_f + 1, 128) if f % w == 0]
        tile_f = max(fits, default=tile_f)
    return tile_f


_noted_tiles = set()


def _note_grouped_tile(rows_shape, gate_shape, tile_rows: int, tile_f: int):
    """Log the grouped matmul's tile once a shape, while tracing (as the
    decode kernels log their plans)."""
    key = (tuple(rows_shape), tuple(gate_shape), tile_rows, tile_f)
    if key in _noted_tiles:
        return
    _noted_tiles.add(key)
    from deepspeed_tpu.utils.logging import logger

    n_held, d, f = gate_shape
    logger.info(f"grouped_ffn rows{tuple(rows_shape)} experts"
                f"{tuple(gate_shape)}: tiles of {tile_rows} rows x {tile_f} "
                f"columns, {f // tile_f} steps a tile of rows, three blocks "
                f"of {d} x {tile_f}")


def grouped_ffn(rows, tile_expert, live_tiles, gate, up, down, *,
                tile_rows: int, tile_f: int = 512, limit: float = 0.0):
    """``rows [R, D]`` (each tile of ``tile_rows`` rows belongs to expert
    ``tile_expert[i]``) -> ``down_e(silu(gate_e r) * up_e r)`` a row
    (:func:`glu` at ``limit``), in float32 (the caller weights and sums
    them: rounding each term first would cost what the float32 accumulator
    held), for the first ``live_tiles`` tiles; rows of later tiles are not
    written."""
    n_rows, d = rows.shape
    n_held, _, f = gate.shape
    tile_f = width_tile(f, tile_f)
    if n_rows % tile_rows or f % tile_f:
        raise ValueError(f"{n_rows} rows in tiles of {tile_rows}, width "
                         f"{f} in tiles of {tile_f}")
    _note_grouped_tile(rows.shape, gate.shape, tile_rows, tile_f)

    def at_tile(i, n, expert):
        return (i, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(live_tiles, f // tile_f),
        in_specs=[
            pl.BlockSpec((tile_rows, d), at_tile),
            pl.BlockSpec((None, d, tile_f),
                         lambda i, n, expert: (expert[i], 0, n)),
            pl.BlockSpec((None, d, tile_f),
                         lambda i, n, expert: (expert[i], 0, n)),
            pl.BlockSpec((None, tile_f, d),
                         lambda i, n, expert: (expert[i], n, 0)),
        ],
        out_specs=pl.BlockSpec((tile_rows, d), at_tile),
    )
    # no ``name=``: the device trace prints the kernel under the caller's
    # scope (``moe._expert_matmul.N``), which the benchmark's reader matches
    return pl.pallas_call(
        functools.partial(_gmm_kernel, limit=limit),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_rows, d), jnp.float32),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=96 * 1024 * 1024),
    )(tile_expert, rows, gate, up, down)


def tile_rows_for(tokens: int, top_k: int, n_routed: int) -> int:
    """Rows a tile: twice what an expert expects of this many tokens under
    even routing, a power of two between 16 (a bf16 register's sublanes)
    and 256, so that an expert's group is mostly one tile."""
    want = max(1, 2 * tokens * top_k // n_routed)
    return int(min(256, max(16, 1 << (want - 1).bit_length())))


def _expert_ffn_grouped(x, local, held, weights, counts, gate, up, down,
                        tile_rows: int, limit: float = 0.0):
    tokens, d = x.shape
    top_k = local.shape[1]
    n_held = gate.shape[0]
    pairs = tokens * top_k
    n_rows = -(-(pairs + n_held * (tile_rows - 1)) // tile_rows) * tile_rows
    # where each expert's (padded) group starts, and each pair's row in it
    padded = -(-counts // tile_rows) * tile_rows
    ends = jnp.cumsum(padded)
    starts = ends - padded
    key = jnp.where(held, local, n_held).reshape(pairs)
    order = jnp.argsort(key, stable=True)
    first = jnp.cumsum(counts) - counts           # in the sorted, unpadded
    sorted_key = key[order]
    expert = jnp.minimum(sorted_key, n_held - 1)
    rank = jnp.arange(pairs, dtype=jnp.int32) - first[expert]
    row_sorted = jnp.where(sorted_key < n_held, starts[expert] + rank,
                           n_rows)                 # not held: out of range
    row_of_pair = jnp.zeros((pairs,), jnp.int32).at[order].set(
        row_sorted.astype(jnp.int32))
    token_of_row = jnp.full((n_rows,), tokens, jnp.int32).at[
        row_of_pair].set(jnp.arange(pairs, dtype=jnp.int32) // top_k,
                         mode="drop")
    rows = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)])[token_of_row]
    tiles = n_rows // tile_rows
    tile_expert = jnp.minimum(jnp.searchsorted(
        ends, jnp.arange(tiles, dtype=jnp.int32) * tile_rows, side="right"),
        n_held - 1).astype(jnp.int32)
    with jax.named_scope("moe._expert_matmul"):
        out_rows = grouped_ffn(rows, tile_expert, ends[-1] // tile_rows,
                               gate, up, down, tile_rows=tile_rows,
                               limit=limit)
    # back to the pairs; a pair whose expert lives elsewhere adds nothing
    # (its row index points past the rows, some of which no tile wrote)
    picked = out_rows[jnp.minimum(row_of_pair, n_rows - 1)].reshape(
        tokens, top_k, d)
    return jnp.sum(jnp.where(held[..., None], weights[..., None] * picked,
                             0.0), axis=1)


def expert_ffn(x, experts, weights, gate, up, down, *, first_expert: int,
               valid=None, n_routed: int = 0, use_kernel=None,
               limit: float = 0.0):
    """The held experts' part of the layer's output.

    Args:
      x: ``[T, D]`` tokens; ``experts`` / ``weights``: :func:`route`'s.
      gate / up / down: ``[E, D, F]``, ``[E, D, F]``, ``[E, F, D]``: the
        experts ``first_expert .. first_expert + E`` of the published
        ``n_routed``.
      valid: ``[T]`` bool: tokens that are real (a bucket's padding and an
        idle slot's row are not, and touch no expert).
      use_kernel: the Pallas grouped matmul (default: on a TPU) or the
        dense oracle; which of them a program took is counted under
        ``moe_experts_*`` beside the attention paths.
      limit: > 0: the experts' SwiGLU clamped (:func:`glu`).

    Returns ``(y [T, D] float32, counters int32)``: ``COUNTERS``, this
    call's (``experts_held`` is ``E``: what ``experts_touched`` is out of).
    """
    n_held = gate.shape[0]
    if valid is None:
        valid = jnp.ones((x.shape[0],), bool)
    local = experts - first_expert
    held = (local >= 0) & (local < n_held) & valid[:, None]
    counts, counters = _counters(local, held, n_held, valid)
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    # noted at trace time beside the attention paths (``stats()``'s
    # ``attention_paths``): which form a program's experts took
    from deepspeed_tpu.ops.attention import record_dispatch

    record_dispatch("moe_experts_grouped_kernel" if use_kernel
                    else "moe_experts_dense_xla")
    if use_kernel:
        tile_rows = tile_rows_for(x.shape[0], experts.shape[1],
                                  n_routed or n_held)
        y = _expert_ffn_grouped(x, local, held, weights, counts, gate, up,
                                down, tile_rows, limit)
    else:
        y = _expert_ffn_dense(x, local, held, weights, gate, up, down, limit)
    return y, counters


def held_range(n_routed: int, ep_rank: int, ep_size: int):
    """``(first, count)`` of the experts rank ``ep_rank`` of ``ep_size``
    holds: contiguous equal shares."""
    if n_routed % ep_size or not 0 <= ep_rank < ep_size:
        raise ValueError(f"{n_routed} experts over {ep_size} ranks, rank "
                         f"{ep_rank}")
    count = n_routed // ep_size
    return ep_rank * count, count
