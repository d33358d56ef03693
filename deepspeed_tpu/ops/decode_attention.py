"""Decode attention — Pallas TPU kernel for KV-cache generation.

Replaces the reference's ``softmax_context`` CUDA path
(``csrc/transformer/inference/csrc/softmax.cu:488``,
``pt_binding.cpp:1701-1775``): attention of a small query step against the
valid ``[0, cache_index + T_q)`` prefix of an append-style KV cache.

TPU-native design points:

- Operates directly on the cache's native ``[B, S, H, D]`` layout with
  strided block DMA — no per-token transpose of the whole cache (the dense
  XLA fallback pays two ``[B, S, H, D] -> [B, H, S, D]`` copies per decoded
  token).
- ``cache_index`` is a *scalar-prefetch* operand: the append-cache
  kernel's grid is static over the full window, but blocks past the valid
  prefix skip both compute and the online-softmax update (``pl.when``),
  and the boundary block is iota-masked. fp32 accumulation throughout.
- All heads are processed per grid step: decode tiles are tiny, so what a
  grid step costs whether it is live or dead (the pipeline's bookkeeping
  for its operands), not FLOPs or bytes, dominates. The paged kernel,
  which serving runs with most of its tables empty, therefore has NO dead
  steps: its grid is one traced axis over the live blocks of all rows, a
  tile of them a step (see ``_paged_call`` and ``paged_plan``).
"""

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.utils.compat import tpu_compiler_params

NEG_INF = -1e30
DEFAULT_BLOCK_K = 256
# pool block 0 is the reserved garbage sink: block tables pad with it,
# bucketed-prefill pad tokens scatter into it, and the masked/pl.when
# paths guarantee it never contributes to any output
GARBAGE_BLOCK = 0


def _kernel(idx_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
            *, scale, bk, tq, heads, d, num_kb):
    ki = pl.program_id(1)
    idx = idx_ref[0]

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # keys at positions < idx + tq are (potentially) visible
    @pl.when(ki * bk < idx + tq)
    def _body():
        q = q_ref[...].reshape(tq, heads, d).transpose(1, 0, 2)   # [H,tq,d]
        k = k_ref[...].reshape(bk, heads, d).transpose(1, 0, 2)   # [H,bk,d]
        v = v_ref[...].reshape(bk, heads, d).transpose(1, 0, 2)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale            # [H,tq,bk]
        # query row r sits at absolute position idx + r; it sees keys <= that
        rows = jax.lax.broadcasted_iota(jnp.int32, (heads, tq, bk), 1)
        cols = jax.lax.broadcasted_iota(jnp.int32, (heads, tq, bk), 2) + ki * bk
        s = jnp.where(cols <= idx + rows, s, NEG_INF)
        m_prev = m_scr[:, :, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        # every row sees at least its own key, so no fully-masked rows and
        # exp(NEG_INF - finite) underflows to exactly 0 — no select needed
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scr[:, :, 0:1] + jnp.sum(p, axis=2, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)                    # [H,tq,d]
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == num_kb - 1)
    def _finish():
        l = l_scr[:, :, 0:1]
        out = acc_scr[:] / jnp.where(l == 0.0, 1.0, l)             # [H,tq,d]
        o_ref[...] = out.transpose(1, 0, 2).reshape(1, tq, heads, d) \
            .astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, cache_index, softmax_scale=None,
                     block_k=None):
    """Attend a decode step against the valid prefix of an append KV cache.

    ``block_k=None`` resolves through the live-tunable registry
    (``autotuning/runtime_tunables``, key
    ``ops.decode_attention.block_k``): an explicit argument wins, a
    tuned-artifact value beats the built-in default, and with nothing
    installed this traces exactly as before (zero-overhead contract).

    Args:
      q: ``[B, T_q, H, D]`` query step (``T_q`` small: 1 for plain decode).
      k_cache / v_cache: ``[B, S, H, D]`` append buffers whose rows
        ``[0, cache_index + T_q)`` are valid — this step's keys must already
        be written at ``[cache_index, cache_index + T_q)``.
      cache_index: scalar int32 — number of cache rows valid *before* this
        step.

    Returns ``[B, T_q, H, D]`` in the query's dtype.
    """
    from deepspeed_tpu.autotuning import runtime_tunables

    block_k = runtime_tunables.resolve(
        block_k, "ops.decode_attention.block_k", DEFAULT_BLOCK_K)
    b, tq, heads, d = q.shape
    s_len = k_cache.shape[1]
    bk = min(block_k, s_len)
    if s_len % bk:
        raise ValueError(f"cache length {s_len} not divisible by block {bk}")
    num_kb = s_len // bk
    scale = softmax_scale if softmax_scale is not None else d ** -0.5

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, num_kb),
        in_specs=[
            pl.BlockSpec((1, tq, heads, d), lambda bi, ki, idx: (bi, 0, 0, 0)),
            pl.BlockSpec((1, bk, heads, d), lambda bi, ki, idx: (bi, ki, 0, 0)),
            pl.BlockSpec((1, bk, heads, d), lambda bi, ki, idx: (bi, ki, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, tq, heads, d),
                               lambda bi, ki, idx: (bi, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((heads, tq, 128), jnp.float32),   # m
            pltpu.VMEM((heads, tq, 128), jnp.float32),   # l
            pltpu.VMEM((heads, tq, d), jnp.float32),     # acc
        ],
    )
    kernel = functools.partial(_kernel, scale=scale, bk=bk, tq=tq,
                               heads=heads, d=d, num_kb=num_kb)
    idx = jnp.asarray(cache_index, jnp.int32).reshape(1)
    return pl.pallas_call(
        kernel,
        name="decode_attn",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, tq, heads, d), q.dtype),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
    )(idx, q, k_cache, v_cache)


# ---------------------------------------------------------------------------
# Paged variant: the KV cache is ONE resident, layer-stacked block pool and
# each sequence owns a block table mapping its logical blocks to pool
# blocks — the serving layer's continuous-batching cache (vLLM-style
# paging, TPU-native via scalar-prefetch block DMA). The kernel walks each
# row's OWN blocks: its grid is the live blocks of all rows, row after row,
# a tile of consecutive blocks a step (``paged_plan``), read from
# ``lengths``, and no step exists past a row's live prefix
# (``_paged_call``). The dense append-cache kernel above is
# kept untouched: it serves the legacy generate() path and is the
# correctness oracle for this one.
#
# THE POOL'S ONE SHAPE. Every pool leaf is ``[layers, blocks, block_size,
# lanes]``: ``lanes = H * D`` for the key/value pools (head ``h`` is lanes
# ``[h*D, (h+1)*D)``); the int8 scale side pools hold head ``h``'s scale
# in lane ``h`` of ``scale_lanes(H)`` lanes, whole 128-lane registers. A
# row of ``H * D`` lanes tiles the TPU's (8, 128) registers with a few
# percent of padding and a whole-register row with none, so the array's
# default device layout is row-major — the layout the kernel's block DMA
# reads — where ``[..., H, D]`` pads H to 8 sublanes and D to 128 lanes
# (and ``[..., bs, H]`` of a few heads puts the BLOCK axis minor-most) and
# makes XLA convert the whole pool around every call. The kernel is handed
# the STACKED pool and the layer index as a scalar-prefetch operand and
# addresses ``(layer, table[b, j])`` itself, so no program ever slices a
# layer out: writers put rows in place (``pool.at[layer, block,
# offset]``, or the write call, ``_paged_write``) and this kernel reads in
# place. POOL_BLOCK_AXIS /
# POOL_LANE_AXIS are the one spelling of "which axis" that the model, the
# serving programs (cow, migrate, export) and the tp sharding rule share.
#
# MULTI-QUERY-ROW (verify) CONTRACT: the kernel is written over T_q query
# rows per sequence, not 1 — query row r of sequence b sits at absolute
# position lengths[b] + r and is causally masked to keys at positions
# <= lengths[b] + r, including the OTHER rows of the same step (their KV
# is in the pool before the kernel attends: a T_q = 1 call handed the
# step's rows, ``rows=``, puts them there itself, for the rows of its work
# list only, and every other caller scatters them first, pads and idle
# slots into the garbage block). T_q = 1 is plain decode; T_q = k + 1 is
# speculative decoding's k-token verify step: the pending token plus k
# proposed continuation tokens score in one dispatch, each row seeing
# exactly the prefix it would have seen decoded sequentially — the
# property that makes greedy verify an exact accept oracle. Proposal
# rows past a sequence's real count are right-padded junk whose writes
# went to the garbage block; their outputs are computed and discarded
# (static shapes — the zero-retrace pin), never read back.
# ---------------------------------------------------------------------------

POOL_BLOCK_AXIS = 1
POOL_LANE_AXIS = 3


def scale_lanes(heads: int) -> int:
    """Lanes of an int8 scale pool row: one lane a head, padded to whole
    128-lane registers (a row of a few dozen lanes would make the block
    axis the minor-most of the default device layout)."""
    return -(-heads // 128) * 128


def gather_paged_cache(pool, block_tables, layer, heads):
    """Assemble the dense ``[B, MB*bs, H, D]`` logical window of one layer
    from pool blocks — the XLA fallback (CPU serving, alibi/window models)
    and the correctness oracle the paged kernel is tested against.
    ``pool`` is the stacked ``[L, nb, bs, H*D]`` pool. Gathered rows land
    at their logical positions; table entries past a sequence's allocation
    point at the garbage block and are masked by the caller's length
    mask."""
    b, mb = block_tables.shape
    _, _, bs, lanes = pool.shape
    return pool[layer, block_tables].reshape(b, mb * bs, heads,
                                             lanes // heads)


def gather_paged_cache_int8(pool, scales, block_tables, layer, heads,
                            dtype=jnp.float32):
    """Dense-dequantize one layer of an int8 pool through a block table:
    the XLA fallback (CPU serving) and the correctness oracle for the int8
    paged kernel. ``pool`` is ``[L, nb, bs, H*D]`` int8, ``scales`` the
    ``[L, nb, bs, scale_lanes(H)]`` f32 side pool written by the same
    scatter. Returns the ``[B, MB*bs, H, D]`` logical window in
    ``dtype``."""
    q = gather_paged_cache(pool, block_tables, layer, heads)
    s = gather_paged_cache(scales[..., :heads], block_tables, layer, heads)
    return (q.astype(jnp.float32) * s).astype(dtype)


def _heads_of(block, heads, d):
    """``[rows, H*d]`` lanes -> ``[H, rows, d]``: each head's lanes sliced
    out statically (the pool row is lane-dense; no relayout of the pool
    ever happens outside this register-level shuffle)."""
    return jnp.stack([block[:, h * d:(h + 1) * d] for h in range(heads)])


class PagedPlan(NamedTuple):
    """What one grid step of the paged kernel attends; static in the
    shapes (:func:`paged_plan`)."""
    tile_blocks: int   # consecutive blocks of a row a grid step attends
    block_size: int    # keys a pool block

    @property
    def tile_keys(self) -> int:
        return self.tile_blocks * self.block_size

    def describe(self) -> str:
        return (f"one softmax update a tile of {self.tile_blocks} x "
                f"{self.block_size} = {self.tile_keys} keys")


# keys a tile aims at: one 128-lane register of float32 scores a head and
# query row
PAGED_TILE_KEYS = 128


def paged_plan(block_size):
    """The tile of the paged kernel: as many consecutive blocks of a row as
    hold ``PAGED_TILE_KEYS`` keys (4 blocks of 32, 8 of 16; one block where
    a block is 128 keys or more). The query rows do not move it: what a
    step holds in VMEM is its ``[heads, tq, 128-lane]`` scores, query and
    accumulator, which a block of 32 keys pads to as a tile of 128 fills
    them, so the chip's compiler takes a tile wherever it takes a block (at
    25 heads of 64 both to 176 query rows, neither from 192); and a ``k +
    1``-row verify step has to take the decode step's tile, or its row
    ``r`` would not be the ``tq = 1`` call at ``lengths + r`` to the bit."""
    return PagedPlan(max(1, PAGED_TILE_KEYS // block_size), block_size)


_noted_plans = set()


def _note_paged_plan(plan, q_shape, pool_shape, quant, writes):
    """Log the tile and who writes the step's rows once a shape, while
    tracing (as ``flash_plan`` is)."""
    key = (plan, tuple(q_shape), tuple(pool_shape), quant, writes)
    if key in _noted_plans:
        return
    _noted_plans.add(key)
    from deepspeed_tpu.utils.logging import logger

    logger.info(f"decode_attention_paged q{tuple(q_shape)} pool"
                f"{tuple(pool_shape)}{' int8' if quant else ''}: "
                f"{plan.describe()}; the step's rows "
                + ("written by the call, a busy row's only" if writes
                   else "scattered by the caller"))


def _paged_kernel(row_ref, first_ref, tables_ref, lens_ref, at_ref, q_ref,
                  *rest, scale, bs, tq, heads, d, quant, head_shard, tile,
                  batch):
    # ``tile`` refs a pool, one a block of this step's tile, in order; then
    # the zeros the output starts as (never read here)
    pools = [rest[i * tile:(i + 1) * tile] for i in range(4 if quant else 2)]
    _, o_ref, m_scr, l_scr, acc_scr = rest[len(pools) * tile:]
    keys = tile * bs
    # this grid step is tile ji of row bi's live prefix (see
    # paged_work_list); an idle serving slot has no step, and its rows of
    # the output stay the zeros they started as
    step = pl.program_id(0)
    bi = row_ref[step]
    ji = step - first_ref[bi]
    idx = lens_ref[bi]  # this row's valid length BEFORE the step
    # a batch of idle slots only still runs the grid's one step, on no row
    owns = step < first_ref[batch]

    @pl.when(jnp.logical_not(owns))
    def _idle():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(owns & (ji == 0))
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def rows_of(refs):
        # the tile's blocks one under the other: [keys, lanes]
        return jnp.concatenate([r[...] for r in refs], axis=0)

    @pl.when(owns)
    def _tile():
        q = q_ref[...].reshape(tq, heads, d).transpose(1, 0, 2)   # [H,tq,d]
        k = _heads_of(rows_of(pools[0]), heads, d)                # [H,keys,d]
        v = _heads_of(rows_of(pools[1]), heads, d)
        if quant:
            # dequantize in-register: int8 rows x the side-pool scales
            ks, vs = rows_of(pools[2]), rows_of(pools[3])
            if head_shard:
                # a tp shard holds heads [h0, h0 + heads) of the (whole,
                # replicated) scale row: bring lane h0 to lane 0
                back = ks.shape[1] - at_ref[1]
                ks, vs = pltpu.roll(ks, back, 1), pltpu.roll(vs, back, 1)
            q = q.astype(jnp.float32)
            k = k.astype(jnp.float32) * _heads_of(ks, heads, 1)
            v = v.astype(jnp.float32) * _heads_of(vs, heads, 1)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale            # [H,tq,keys]
        # query row r sits at absolute position idx + r and sees keys <=
        # that: the boundary block's rows past the prefix, and the blocks
        # of a tile past the row's live prefix (which hold a live block's
        # rows again: see pool_spec), are masked here by their POSITION
        rows = jax.lax.broadcasted_iota(jnp.int32, (heads, tq, keys), 1)
        cols = jax.lax.broadcasted_iota(jnp.int32, (heads, tq, keys), 2) \
            + ji * keys
        s = jnp.where(cols <= idx + rows, s, NEG_INF)
        m_prev = m_scr[:, :, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scr[:, :, 0:1] + jnp.sum(p, axis=2, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)                    # [H,tq,d]
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    # the row's last step is the one before the next row's first
    @pl.when(owns & (step + 1 == first_ref[bi + 1]))
    def _finish():
        l = l_scr[:, :, 0:1]
        out = acc_scr[:] / jnp.where(l == 0.0, 1.0, l)             # [H,tq,d]
        o_ref[...] = out.transpose(1, 0, 2).reshape(1, tq, heads, d) \
            .astype(o_ref.dtype)


def paged_step_lengths(lengths, block_tables, tq):
    """``lengths`` as :func:`paged_work_list` takes them for the paged
    kernel: an idle serving slot (length 0 AND a table that starts at the
    garbage block) is handed ``-tq``, which is what it holds with this
    step's ``tq`` rows counted: no key, so no live block and NO grid step
    (its output rows are zeros, which the caller discards). Any other row
    keeps its length and is attended over whatever its table names, the
    garbage block included: a fresh row (length 0) on a block of its own
    over its ``tq`` keys."""
    lens = jnp.asarray(lengths, jnp.int32)
    tables = jnp.asarray(block_tables, jnp.int32)
    return jnp.where((lens == 0) & (tables[:, 0] == GARBAGE_BLOCK), -tq, lens)


def paged_work_list(lengths, tq, block_size, max_blocks, *, tile_blocks=1):
    """A paged kernel's grid, made from ``lengths`` alone: ``(row_of,
    first)``. Row ``r`` of the batch owns grid steps ``[first[r], first[r +
    1])``, one a tile of ``tile_blocks`` live blocks, ``cdiv(n_r,
    tile_blocks)`` of them with ``n_r = min(cdiv(lengths[r] + tq,
    block_size), max_blocks)`` the row's live blocks, and ``first[B]`` is
    the grid's length; ``row_of[s]`` is the last row that has started by
    step ``s`` (``B * cdiv(max_blocks, tile_blocks) + 1`` entries: one more
    than the longest grid, for the pipeline's look at the step after the
    last, which stays the last row's). A row of length 0 or more has a
    step (``tq >= 1``): an idle slot listed at its length 0 would have one,
    on the garbage block, so every kernel's caller hands it
    ``-tq`` (:func:`paged_step_lengths`): it has no key and no step, and
    ``row_of`` passes over it. ``tile_blocks`` is the plan's of the call
    the list is made for (:func:`paged_plan` here, ``hybrid_plan`` for the
    hybrid kernel); at its default a step is a block.

    It depends on nothing a layer changes, so a program that calls the
    kernel once a layer makes it ONCE, before the layer loop, and hands it
    to every call as ``work=`` (``models/gpt2.py`` does); a call given none
    makes its own. It costs a ``(B + 1) x (B * cdiv(max_blocks,
    tile_blocks) + 1)`` compare and sum, and ``row_of`` takes about the
    SMEM the block tables do."""
    lens = jnp.asarray(lengths, jnp.int32)
    b = lens.shape[0]
    live = jnp.minimum((lens + (tq + block_size - 1)) // block_size,
                       max_blocks)
    # (at the default the list is traced as it always was, operation for
    # operation)
    tiles = (live if tile_blocks == 1
             else (live + (tile_blocks - 1)) // tile_blocks)
    first = jnp.sum(jnp.tril(jnp.broadcast_to(tiles, (b + 1, b)), -1),
                    axis=1, dtype=jnp.int32)
    steps = jnp.arange(b * -(-max_blocks // tile_blocks) + 1, dtype=jnp.int32)
    # the rows that have started by s, less one
    row_of = jnp.minimum(jnp.sum(first[:, None] <= steps[None, :], axis=0,
                                 dtype=jnp.int32), b) - 1
    return row_of, first


def paged_step_work(lengths, block_tables, tq, block_size, valid=None):
    """:func:`paged_work_list` as a call of the paged kernel takes it: in
    :func:`paged_plan`'s tiles, idle slots without a step
    (:func:`paged_step_lengths`). What a program makes once a step, before
    its layers, and hands to every layer's call as ``work=``. With
    ``valid`` (``[B]``: the new rows a sequence brings, 1 or 0) it is the
    work of a call that writes them (``rows=``): :func:`paged_write_list`
    behind the two."""
    work = paged_work_list(
        paged_step_lengths(lengths, block_tables, tq), tq, block_size,
        block_tables.shape[-1],
        tile_blocks=paged_plan(block_size).tile_blocks)
    if valid is None:
        return work
    return work + paged_write_list(lengths, block_tables, valid, block_size)


def paged_write_list(lengths, block_tables, valid, block_size):
    """Where a decode step's ONE new row a sequence goes, for the write
    call (:func:`_paged_write`): ``(order [B + 1], count [1], block [B],
    offset [B])``. ``order`` is the batch rows with the WRITING ones first,
    in ascending order (one more entry for the pipeline's look at the step
    after the last), ``count`` how many write; a writing row's new row lies
    at ``offset`` of pool block ``block``: position ``lengths[b]`` through
    its table (in the last block of a table that is full, as
    ``paged_write_slots`` clips it). A row writes if it has a step
    (:func:`paged_step_lengths`: an idle serving slot has none) and brings
    a row (``valid``); every other row names row 0 of the garbage block,
    which a call with no writer at all reads and puts back as it was."""
    lens = jnp.asarray(lengths, jnp.int32)
    tables = jnp.asarray(block_tables, jnp.int32)
    writes = ((paged_step_lengths(lens, tables, 1) >= 0)
              & (jnp.asarray(valid, jnp.int32) > 0))
    order = jnp.argsort(~writes, stable=True).astype(jnp.int32)
    last = jnp.minimum(lens // block_size, tables.shape[-1] - 1)
    block = jnp.take_along_axis(tables, last[:, None], axis=1)[:, 0]
    return (jnp.concatenate([order, order[-1:]]),
            jnp.sum(writes, dtype=jnp.int32).reshape(1),
            jnp.where(writes, block, GARBAGE_BLOCK),
            jnp.where(writes, lens % block_size, 0))


def _words(dtype):
    """The 32-bit type that holds every value of ``dtype``: the write
    kernel reads ONE row at a traced index, which Mosaic takes of whole
    words only (a bfloat16 or int8 row is a part of a sublane)."""
    return jnp.float32 if jnp.issubdtype(dtype, jnp.floating) else jnp.int32


def _strip_rows(block_size, dtype):
    """Rows of the strip of a block that the write call moves: one sublane
    tile of the pool's dtype (8 rows of 32 bits, 16 of bfloat16, 32 of
    int8), the least a block DMA can write of a tiled pool; the whole block
    where that does not divide it."""
    rows = 32 // jnp.dtype(dtype).itemsize
    return rows if block_size % rows == 0 else block_size


def _paged_write_kernel(order_ref, count_ref, block_ref, offset_ref, at_ref,
                        *refs, strips):
    del block_ref, at_ref
    n = len(strips)
    news, srcs, dsts, words = (refs[i * n:(i + 1) * n] for i in range(4))
    step = pl.program_id(0)
    row = order_ref[step]

    @pl.when(step == 0)
    def _as_words():
        # the step's rows, value for value in 32 bits: a row a grid step
        # is read from these at a traced index
        for new, word in zip(news, words):
            word[...] = new[...].astype(word.dtype)

    # a call with no writer still runs the grid's one step: on the garbage
    # block's first strip, which goes back as it came
    writes = step < count_ref[0]
    for word, src, dst, strip in zip(words, srcs, dsts, strips):
        held = src[...]                                      # [strip, lanes]
        here = (jax.lax.broadcasted_iota(jnp.int32, held.shape, 0)
                == offset_ref[row] % strip) & writes
        dst[...] = jnp.where(here, word[pl.ds(row, 1), :],
                             held.astype(word.dtype)).astype(dst.dtype)


def _paged_write(pools, rows, layer, put):
    """The step's new rows into their places in ``layer`` of the stacked
    ``pools``, by ``put`` (:func:`paged_write_list`): ONE call for all the
    pools, each an operand ONCE and aliased to its result, so the program
    holds one buffer a pool and no scatter on it. No DMA can write ONE
    bfloat16 row of a tiled pool (it is half a sublane), so a grid step is
    a writing row's strip: the sublane tile of its block that holds the
    offset (:func:`_strip_rows`) comes in by the pipeline's block DMA
    (which moves a row of any width, where a manual copy of any slice of
    GPT-2 XL's 1600-lane rows is refused, whole rows too), the row is
    replaced in VMEM, and the strip goes back; the rows themselves are
    whole ``[B, lanes]`` arrays in VMEM, not an operand a step. Rows that
    write nothing have no step. (An output block of the ATTENTION call
    aliased to one of its four operands a pool would save this call, and
    makes XLA copy the whole pool in and out: PERF.md section 6, PR 55.)"""
    order, count, block, offset = put
    bs = pools[0].shape[2]
    strips = [_strip_rows(bs, p.dtype) for p in pools]

    def strip_spec(width, strip):
        def index(s, order, count, block, offset, at):
            row = order[s]
            return (at[0], block[row], offset[row] // strip, 0)
        return pl.BlockSpec((None, None, strip, width), index)

    specs = [strip_spec(p.shape[POOL_LANE_AXIS], strip)
             for p, strip in zip(pools, strips)]
    n = len(pools)
    return tuple(pl.pallas_call(
        functools.partial(_paged_write_kernel, strips=strips),
        name="paged_kv_write",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(jnp.maximum(count[0], 1),),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * n + specs,
            out_specs=specs,
            scratch_shapes=[
                pltpu.VMEM((r.shape[0], r.shape[-1]), _words(r.dtype))
                for r in rows]),
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools],
        input_output_aliases={5 + n + i: i for i in range(n)},
        compiler_params=tpu_compiler_params(
            dimension_semantics=("arbitrary",)),
    )(order, count, block, offset,
      jnp.asarray(layer, jnp.int32).reshape(1),
      *(r.reshape(r.shape[0], -1) for r in rows), *pools))


def paged_most_writers(batch: int, pools: int) -> int:
    """The most writing rows of ``batch`` that the write call takes; a
    step with more scatters every row instead (one ``lax.cond`` on the
    write list's count, :func:`_paged_put`). Read on a v5e at GPT-2 XL's
    shapes, 32 slots (PERF.md section 6, PR 55, us a layer call): the
    write call takes 1.8 + 0.53 a writer, a grid step each (0.55 with
    int8's four pools), the scatters 14.0 (two pools) or 19.2 (four,
    two of them a register wide) whatever the writers, every slot's row
    one after another: they meet at 23 writers of 32 for two pools and at
    the whole batch for four."""
    return 3 * batch // 4 if pools == 2 else batch


def _paged_put(pools, rows, layer, put):
    """The step's new rows into ``layer`` of the ``pools``, by ``put``
    (:func:`paged_write_list`): the write call (:func:`_paged_write`), or,
    where more rows write than :func:`paged_most_writers`, every slot's
    row scattered as a program without the write call does (a row that
    writes nothing onto the garbage block). ONE algorithm chosen by what
    the step holds; both branches write the pools in place."""
    batch = rows[0].shape[0]
    most = paged_most_writers(batch, len(pools))
    if most >= batch:
        return _paged_write(pools, rows, layer, put)
    _, count, block, offset = put

    def scatter(pools):
        return tuple(p.at[layer, block, offset].set(r[:, 0])
                     for p, r in zip(pools, rows))

    return jax.lax.cond(
        count[0] > most, scatter,
        lambda pools: _paged_write(pools, rows, layer, put), tuple(pools))


def _paged_call(q, pools, block_tables, lengths, layer, softmax_scale,
                head0=None, work=None, rows=None, valid=None):
    """The one ``pallas_call`` behind both paged entry points: ``pools`` is
    ``(k_pool, v_pool)`` or ``(k_pool, v_pool, k_scale, v_scale)``.
    ``head0`` (tp shards only) is the first head this call's ``q`` and K/V
    lanes hold, for the scale rows, which stay whole. ``work`` is
    :func:`paged_step_work` of these ``lengths`` and tables, made here if
    not given. With ``rows`` (and ``valid``: see
    :func:`decode_attention_paged`) the step's new rows are put into the
    pools first, under the same scope (:func:`_paged_put`), and the result
    is ``(out, pools)``.

    THE GRID FOLLOWS ``lengths``, NOT ``block_tables.shape``: it is one
    axis whose (traced) length is the number of live TILES of all rows,
    ``sum_b cdiv(n_b, tile_blocks)`` with ``n_b = min(cdiv(lengths[b] +
    T_q, bs), MB)`` the row's live blocks, and step ``s`` is the next tile
    of the last row that has started by ``s``: row 0's tiles in ascending
    order, then row 1's, and so on (``row_of`` and ``first``, the work
    list, are two more scalar-prefetch operands). A fixed ``(B, MB)`` grid
    pays the pipeline's bookkeeping for every step of every table, dead or
    live, and that bookkeeping, not the bytes, was the kernel's time while
    few slots were busy; this one runs no step past a row's live prefix,
    and none at all for an idle serving slot (:func:`paged_step_lengths`):
    a step's bookkeeping grows with its operands (about 0.2 us a step of
    two pool operands, 0.7 of eight: PERF.md section 6), and a call with 3
    of 32 slots busy would spend more on its 29 idle steps than on its
    live ones. The output starts as zeros (an operand aliased to it), so
    the rows no step writes are zeros.

    A STEP IS A TILE, NOT A BLOCK. Tile ``j`` of a row is its blocks ``[j *
    tile_blocks, (j + 1) * tile_blocks)``, each its own operand (so
    ``tile_blocks`` ``BlockSpec``s a pool, moved by the pipeline's own
    block DMA, which moves a row of any width), laid one under the other
    in the body: ONE maximum, ONE ``exp``, ONE rescale of the accumulator
    and one pair of batched matmuls over ``tile_blocks * bs`` keys in
    ascending order, in float32 but for the probabilities, which are
    rounded to the values' dtype for the second matmul. What a live step
    costs is by the step, not by the key (about 1 us for a block of 32
    keys whose bytes take 0.25), so a row of 10 blocks takes 3 steps where
    it took 10. Tiles are ABSOLUTE: tile ``j`` holds the same key positions
    whatever the row's length or ``T_q``, and a tile whose keys a query row
    cannot see leaves that row's state as it was to the bit (``exp(-1e30 -
    m) = 0``, ``alpha = 1``), so row ``r`` of a ``T_q = k + 1`` call is the
    ``T_q = 1`` call at ``lengths + r`` to the bit. A block of a tile past
    the row's live prefix is never named: that operand names the block it
    held a step ago (the same index, so the pipeline moves nothing) or, in
    the row's first tile, the row's block 0; its scores are masked by
    position, and its value rows are a live block's, so nothing a dead
    block holds (NaN included) reaches the matmul. (A grid over rows with a
    manual ``make_async_copy`` loop inside would do the same, but Mosaic
    refuses a DMA slice of a pool row whose lanes are no multiple of 128,
    GPT-2 XL's 1600 for one.)

    The one axis is ``arbitrary``: the softmax state is carried from step
    to step in scratch, and rows are of unequal length, so a chip with two
    TensorCores (v4, v5p) cannot split rows across them as the fixed
    grid's ``parallel`` row axis let it. The v5e has one."""
    b, tq, heads, d = q.shape
    if tq < 1:
        raise ValueError(f"need at least one query row per sequence, "
                         f"got T_q={tq}")
    _, _, bs, lanes = pools[0].shape
    if lanes != heads * d:
        raise ValueError(f"pool rows hold {lanes} lanes, the query needs "
                         f"heads x dim = {heads} x {d}")
    quant = len(pools) == 4
    if quant and (pools[2].shape[:3] != pools[0].shape[:3]
                  or pools[2].shape[3] % 128 or pools[2].shape[3] < heads):
        raise ValueError(
            f"scale pool shape {pools[2].shape} is not "
            f"{pools[0].shape[:3]} + (whole 128-lane registers holding "
            f"{heads} heads,): one f32 scale per pool row x head")
    mb = block_tables.shape[-1]
    scale = softmax_scale if softmax_scale is not None else d ** -0.5
    tables = jnp.asarray(block_tables, jnp.int32)
    lens = jnp.asarray(lengths, jnp.int32)
    plan = paged_plan(bs)
    _note_paged_plan(plan, q.shape, pools[0].shape, quant, rows is not None)
    tile = plan.tile_blocks
    if rows is not None and (tq != 1 or len(rows) != len(pools) or any(
            r.shape != (b, 1, p.shape[POOL_LANE_AXIS]) or r.dtype != p.dtype
            for r, p in zip(rows, pools))):
        raise ValueError(
            f"a call writes ONE new row a sequence and pool (see "
            f"paged_call_writes): rows {[(r.shape, r.dtype) for r in rows]} "
            f"for T_q={tq} and pools {[(p.shape, p.dtype) for p in pools]}")
    if work is None:
        work = paged_step_work(lens, tables, tq, bs)
    (row_of, first), put = work[:2], work[2:]
    if rows is not None:
        pools = _paged_put(pools, rows, layer, put or paged_write_list(
            lens, tables, jnp.ones_like(lens) if valid is None else valid,
            bs))
    if row_of.shape != (b * -(-mb // tile) + 1,) or first.shape != (b + 1,):
        raise ValueError(
            f"work list of shapes {row_of.shape}, {first.shape} is not "
            f"paged_work_list's for {b} rows of {mb} blocks in tiles of "
            f"{tile}")

    def pool_spec(width, i):
        # (layer, table[row, block]) picked by the DMA itself: the stacked
        # pool is an operand as it lies in HBM, never a slice of it. Block
        # i of the step's tile, or, where the row's live prefix ends before
        # it, a live block that costs no fetch (see the docstring)
        def index(s, row_of, first, tab, ln, at):
            row = row_of[s]
            j = (s - first[row]) * tile + i
            live = jnp.minimum((ln[row] + (tq + bs - 1)) // bs, mb)
            return (at[0], tab[row, jnp.where(j < live, j,
                                              jnp.maximum(j - tile, 0))],
                    0, 0)
        return pl.BlockSpec((None, None, bs, width), index)

    q_spec = pl.BlockSpec((1, tq, heads, d),
                          lambda s, row_of, first, tab, ln, at:
                          (row_of[s], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        # a batch of idle slots only has no step: one, on no row, runs
        grid=(jnp.maximum(first[b], 1),),
        in_specs=[q_spec] + [pool_spec(p.shape[POOL_LANE_AXIS], i)
                             for p in pools for i in range(tile)]
        + [pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((heads, tq, 128), jnp.float32),   # m
            pltpu.VMEM((heads, tq, 128), jnp.float32),   # l
            pltpu.VMEM((heads, tq, d), jnp.float32),     # acc
        ],
    )
    kernel = functools.partial(_paged_kernel, scale=scale, bs=bs, tq=tq,
                               heads=heads, d=d, quant=quant,
                               head_shard=quant and head0 is not None,
                               tile=tile, batch=b)
    at = jnp.stack([jnp.asarray(layer, jnp.int32).reshape(()),
                    jnp.asarray(0 if head0 is None else head0, jnp.int32)])
    # no ``name=`` here: a pallas_call's name is also a named scope, and
    # the device trace already prints this kernel under the caller's scope
    # (``attn._paged_kv_attend.N``), which the benchmark's paged-decode
    # roofline reader matches by that name
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, tq, heads, d), q.dtype),
        # the operand after the scalars, q and the pools is the output's
        # own buffer, zeros: a row no step visits is never written
        input_output_aliases={6 + len(pools) * tile: 0},
        compiler_params=tpu_compiler_params(
            dimension_semantics=("arbitrary",)),
    )(row_of, first, tables, lens, at, q,
      *(p for p in pools for _ in range(tile)), jnp.zeros_like(q))
    return out if rows is None else (out, pools)


def decode_attention_paged(q, k_pool, v_pool, block_tables, lengths,
                           layer=0, softmax_scale=None, work=None, rows=None,
                           valid=None):
    """Attend a decode (or k-token verify, or prefill-chunk) step against
    one layer of the paged KV pool.

    Args:
      q: ``[B, T_q, H, D]`` query step. ``T_q = 1`` is plain decode;
        ``T_q = k + 1`` is the speculative verify step (pending token +
        ``k`` proposed tokens per sequence, one dispatch). Each query
        row r attends causally at its own absolute position
        ``lengths[b] + r`` — bitwise the attention sequential decode
        would have computed, which is what makes greedy verify exact.
      k_pool / v_pool: the STACKED ``[layers, num_blocks, block_size,
        H*D]`` pools (see "THE POOL'S ONE SHAPE" above). Without ``rows``
        this step's keys must already be scattered at each row's
        ``[lengths[b], lengths[b] + T_q)`` logical positions of ``layer``
        (verify pads scatter into the garbage block and are never read).
      block_tables: ``[B, MB]`` int32 — row b's logical block j lives in
        pool block ``block_tables[b, j]``; entries past the allocation
        point at the reserved garbage block (their blocks skip compute).
      lengths: ``[B]`` int32 — valid tokens per row *before* this step.
      layer: int32 scalar (traced inside a layer scan, or a Python int) —
        which layer of the stacked pool to read.
      work: :func:`paged_step_work` of these ``lengths`` and tables, for a
        caller that runs many layers on one step's lengths and makes it
        once (with ``valid`` where the call writes); made here if None.
      rows: ``(k, v)``, each ``[B, 1, H*D]`` in the pools' dtype: this
        step's ONE new row a sequence (``T_q = 1``; see
        :func:`paged_call_writes`). The call then leaves them in the pools
        itself, at ``lengths[b]`` through each row's table, for the rows
        of its work list that bring one (:func:`_paged_put`: an idle slot
        has no step and no write, and no byte of the garbage block moves
        unless the step is so crowded that every row is scattered), and
        attends over them: output and pools are scatter-then-attend's to
        the bit. Returns ``(out, (k_pool, v_pool))``.
      valid: ``[B]`` int32 with ``rows``: 0 for a row that brings no row
        (it attends what its pool holds); all ones if None. Not read where
        ``work`` already carries the write list.

    The block table, lengths and layer are *scalar-prefetch* operands:
    the grid is one axis over the live blocks of all rows, a tile of
    :func:`paged_plan`'s ``tile_blocks`` a step, ``sum_b cdiv(min(cdiv(
    lengths[b] + T_q, block_size), MB), tile_blocks)`` steps, a traced
    number; each step DMAs exactly the ``(layer, block)``s the table names
    for its tile — ``block_size`` rows of ``H*D`` lanes each, unpadded —
    and no block past ``lengths[b] + T_q`` is ever named. The fp32
    online-softmax update runs once a tile, tiles in ascending order. An
    idle serving slot (length 0 AND a table that starts at the garbage
    block) has no step and gets zeros; any other row is attended over what
    its table names, the garbage block included.

    Returns ``[B, T_q, H, D]`` in the query's dtype.
    """
    return _paged_call(q, (k_pool, v_pool), block_tables, lengths, layer,
                       softmax_scale, work=work, rows=rows, valid=valid)


def decode_attention_paged_int8(q, k_pool, v_pool, k_scale, v_scale,
                                block_tables, lengths, layer=0,
                                softmax_scale=None, work=None, rows=None,
                                valid=None):
    """Attend a decode (or k-token verify) step against one layer of an
    int8-quantized paged KV pool.

    Same contract and the same kernel as :func:`decode_attention_paged`
    (including the multi-query-row verify semantics), except ``k_pool`` /
    ``v_pool`` are ``[layers, num_blocks, block_size, H*D]`` int8 and
    ``k_scale`` / ``v_scale`` are their ``[layers, num_blocks,
    block_size, scale_lanes(H)]`` f32 per-row scales (one scale per
    token x head, head ``h`` in lane ``h`` —
    ``ops.quantizer.quantize_rowwise``). The scale side pools ride the
    same ``(layer, block)`` address: each grid step DMAs its tile's pool
    blocks *and* their scale rows, dequantizes in-register, and runs the
    identical fp32 online-softmax update; :func:`gather_paged_cache_int8`
    is the dense oracle it is tested against with a pinned tolerance.
    ``rows`` is ``(k, v, k_scale, v_scale)`` rows, ``[B, 1, H*D]`` int8 and
    ``[B, 1, scale_lanes(H)]`` f32, and the call returns ``(out, (k_pool,
    v_pool, k_scale, v_scale))``.
    """
    return _paged_call(q, (k_pool, v_pool, k_scale, v_scale), block_tables,
                       lengths, layer, softmax_scale, work=work, rows=rows,
                       valid=valid)


# ---------------------------------------------------------------------------
# Tensor-parallel wrappers: heads partitioned over the tp mesh axis.
#
# A pallas_call is a custom call GSPMD cannot partition, so on a mesh the
# kernel runs inside the shard_map ops/kernel_mesh.py plans (the one place
# that decides where a kernel call sits): each tp shard keeps its LOCAL
# head group (queries, append caches and paged pools are all stored
# head-sharded by the SpecLayout / decode_cache_specs, so no data moves
# to get here) and runs the identical kernel on heads/tp heads. Decode
# attention reduces only over positions — never across heads — so no
# tp collective is needed at all: the per-shard outputs ARE the
# head-sharded attention output the (row-parallel) output projection
# consumes next.
# ---------------------------------------------------------------------------


def decode_attention_tp(q, k_cache, v_cache, cache_index,
                        softmax_scale=None, block_k=None, mesh=None,
                        axis=None):
    """TP-aware :func:`decode_attention`: [B, S, H, D] append caches and
    [B, T_q, H, D] queries head-sharded over ``axis``, one kernel call
    per shard. The plain kernel where :func:`~deepspeed_tpu.ops.
    kernel_mesh.kernel_mesh_plan` asks for no ``shard_map``."""
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.ops.kernel_mesh import kernel_mesh_plan

    def kernel(qs, ks, vs, idx):
        return decode_attention(qs, ks, vs, idx, softmax_scale=softmax_scale,
                                block_k=block_k)

    plan = kernel_mesh_plan(q.shape[0], q.shape[2], mesh=mesh, axis=axis)
    if plan is None:
        return kernel(q, k_cache, v_cache, cache_index)
    hs = P(plan.batch, None, plan.heads, None)
    return plan.shard_map(kernel, (hs, hs, hs, P()), hs)(
        q, k_cache, v_cache, jnp.asarray(cache_index, jnp.int32))


def paged_call_writes(batch, tq, heads, mesh=None, axis=None) -> bool:
    """Whether a paged call over ``[batch, tq, heads, ...]`` queries on this
    mesh takes the step's new rows (``rows=``) and leaves them in the pool
    itself, or its caller scatters them first (``paged_write_slots``).
    Read off what is static in the call: ONE new row a sequence (a ``k +
    1``-row verify step's rows may straddle two blocks, and a prefill's
    are a whole prompt: both scatter), and a batch that is whole inside
    the ``shard_map`` the call sits in (where data axes split it, each
    shard would write its own rows into its own replica of the pool, and
    the replicas would part; the scatter outside gathers the rows)."""
    from deepspeed_tpu.ops.kernel_mesh import kernel_mesh_plan

    if tq != 1:
        return False
    plan = kernel_mesh_plan(batch, heads, mesh=mesh, axis=axis)
    return plan is None or plan.batch is None


def _paged_tp(q, pools, block_tables, lengths, layer, softmax_scale,
              mesh, axis, work=None, rows=None, valid=None):
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.ops.kernel_mesh import kernel_mesh_plan

    tables = jnp.asarray(block_tables, jnp.int32)
    lens = jnp.asarray(lengths, jnp.int32)
    layer = jnp.asarray(layer, jnp.int32)
    plan = kernel_mesh_plan(q.shape[0], q.shape[2], mesh=mesh, axis=axis)
    if plan is None:
        return _paged_call(q, pools, tables, lens, layer, softmax_scale,
                           work=work, rows=rows, valid=valid)
    if rows is not None and plan.batch is not None:
        raise ValueError("a batch split over data axes scatters its rows "
                         "outside the call: see paged_call_writes")
    # a work list made for the whole batch serves every shard only while
    # the batch is whole inside; where data axes split it, each shard lists
    # its own rows
    work = () if work is None or plan.batch is not None else tuple(work)
    n = len(pools)
    written = () if rows is None else (
        *rows, jnp.ones_like(lens) if valid is None else valid)

    def kernel(qs, t, ln, ly, *rest):
        ps, wk, new = rest[:n], rest[n:n + len(work)], rest[n + len(work):]
        head0 = (None if plan.heads is None else
                 jax.lax.axis_index(plan.heads) * qs.shape[2])
        return _paged_call(qs, ps, t, ln, ly, softmax_scale, head0,
                           work=wk or None, rows=new[:n] or None,
                           valid=new[n] if new else None)

    # pools are the SHARED per-replica cache: the K/V lane axis split into
    # tp groups of heads/tp contiguous heads, replicated over data; the
    # scale rows (a lane a head, padded to whole registers) stay whole and
    # the kernel finds its heads in them; per-row operands follow the
    # batch entry. The step's new rows lie as the pools' rows do, and a
    # shard's call writes its own lanes of them (a scale row whole, into
    # its own copy)
    qs_spec = P(plan.batch, None, plan.heads, None)
    pool_specs = (P(None, None, None, plan.heads),) * 2 + (P(),) * (n - 2)
    row_specs = () if rows is None else (
        (P(None, None, plan.heads),) * 2 + (P(),) * (n - 2 + 1))
    return plan.shard_map(
        kernel, (qs_spec, P(plan.batch), P(plan.batch), P()) + pool_specs
        + (P(),) * len(work) + row_specs,
        qs_spec if rows is None else (qs_spec, pool_specs),
        name="paged_kv_attend")(q, tables, lens, layer, *pools, *work,
                                *written)


def decode_attention_paged_tp(q, k_pool, v_pool, block_tables, lengths,
                              layer=0, softmax_scale=None, mesh=None,
                              axis=None, work=None, rows=None, valid=None):
    """TP-aware :func:`decode_attention_paged`: the stacked pools live
    tp-sharded on their lane axis (per-shard KV pools — each tp shard
    holds heads/tp contiguous heads of every pool row), block
    tables/lengths follow the batch, the layer index and the work list
    are replicated."""
    return _paged_tp(q, (k_pool, v_pool), block_tables, lengths, layer,
                     softmax_scale, mesh, axis, work, rows, valid)


def decode_attention_paged_int8_tp(q, k_pool, v_pool, k_scale, v_scale,
                                   block_tables, lengths, layer=0,
                                   softmax_scale=None, mesh=None,
                                   axis=None, work=None, rows=None,
                                   valid=None):
    """TP-aware :func:`decode_attention_paged_int8`: int8 pools
    lane-sharded over ``axis``, their f32 scale side pools replicated."""
    return _paged_tp(q, (k_pool, v_pool, k_scale, v_scale), block_tables,
                     lengths, layer, softmax_scale, mesh, axis, work, rows,
                     valid)
