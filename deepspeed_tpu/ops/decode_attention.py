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
- ``cache_index`` is a *scalar-prefetch* operand: the grid is static over
  the full window, but blocks past the valid prefix skip both compute and
  the online-softmax update (``pl.when``), and the boundary block is
  iota-masked. fp32 accumulation throughout.
- All heads are processed per grid step (grid = batch x kv-blocks): decode
  tiles are tiny, so per-step grid overhead, not FLOPs, dominates.
"""

import functools

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
# Paged variant: the KV cache is a SHARED block pool ([num_blocks,
# block_size, H, D]) and each sequence owns a block table mapping its
# logical blocks to pool blocks — the serving layer's continuous-batching
# cache (vLLM-style paging, TPU-native via scalar-prefetch block DMA).
# The dense append-cache kernel above is kept untouched: it serves the
# legacy generate() path and is the correctness oracle for this one.
#
# MULTI-QUERY-ROW (verify) CONTRACT: the kernel is written over T_q query
# rows per sequence, not 1 — query row r of sequence b sits at absolute
# position lengths[b] + r and is causally masked to keys at positions
# <= lengths[b] + r, including the OTHER rows of the same step (their KV
# must already be scattered into the pool, which the paged write path
# does before attending). T_q = 1 is plain decode; T_q = k + 1 is
# speculative decoding's k-token verify step: the pending token plus k
# proposed continuation tokens score in one dispatch, each row seeing
# exactly the prefix it would have seen decoded sequentially — the
# property that makes greedy verify an exact accept oracle. Proposal
# rows past a sequence's real count are right-padded junk whose writes
# went to the garbage block; their outputs are computed and discarded
# (static shapes — the zero-retrace pin), never read back.
# ---------------------------------------------------------------------------


def gather_paged_cache(pool, block_tables):
    """Assemble the dense ``[B, MB*bs, H, D]`` logical window from pool
    blocks — the XLA fallback (CPU serving, alibi/window models) and the
    correctness oracle the paged kernel is tested against. Gathered rows
    land at their logical positions; table entries past a sequence's
    allocation point at the garbage block and are masked by the caller's
    length mask."""
    b, mb = block_tables.shape
    nb, bs, heads, d = pool.shape
    return pool[block_tables].reshape(b, mb * bs, heads, d)


def gather_paged_cache_int8(pool, scales, block_tables, dtype=jnp.float32):
    """Dense-dequantize an int8 pool through a block table: the XLA
    fallback (CPU serving) and the correctness oracle for the int8 paged
    kernel. ``pool`` is ``[nb, bs, H, D]`` int8, ``scales`` the
    ``[nb, bs, H, 1]`` f32 side pool written by the same
    ``paged_write_rows`` scatter. Returns the ``[B, MB*bs, H, D]``
    logical window in ``dtype``."""
    b, mb = block_tables.shape
    nb, bs, heads, d = pool.shape
    q = pool[block_tables].reshape(b, mb * bs, heads, d).astype(jnp.float32)
    s = scales[block_tables].reshape(b, mb * bs, heads, 1)
    return (q * s).astype(dtype)


def _paged_kernel(tables_ref, lens_ref, q_ref, k_ref, v_ref, o_ref, m_scr,
                  l_scr, acc_scr, *, scale, bs, tq, heads, d, num_kb):
    bi = pl.program_id(0)
    ji = pl.program_id(1)
    idx = lens_ref[bi]  # this row's valid length BEFORE the step

    @pl.when(ji == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # logical block ji covers key positions [ji*bs, (ji+1)*bs); anything
    # at or past idx + tq is invalid (unallocated tables point at the
    # garbage block — skipped here before its DMA'd bytes ever matter)
    @pl.when(ji * bs < idx + tq)
    def _body():
        q = q_ref[...].reshape(tq, heads, d).transpose(1, 0, 2)   # [H,tq,d]
        k = k_ref[...].reshape(bs, heads, d).transpose(1, 0, 2)   # [H,bs,d]
        v = v_ref[...].reshape(bs, heads, d).transpose(1, 0, 2)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale            # [H,tq,bs]
        rows = jax.lax.broadcasted_iota(jnp.int32, (heads, tq, bs), 1)
        cols = jax.lax.broadcasted_iota(jnp.int32, (heads, tq, bs), 2) \
            + ji * bs
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

    @pl.when(ji == num_kb - 1)
    def _finish():
        l = l_scr[:, :, 0:1]
        out = acc_scr[:] / jnp.where(l == 0.0, 1.0, l)             # [H,tq,d]
        o_ref[...] = out.transpose(1, 0, 2).reshape(1, tq, heads, d) \
            .astype(o_ref.dtype)


def decode_attention_paged(q, k_pool, v_pool, block_tables, lengths,
                           softmax_scale=None):
    """Attend a decode (or k-token verify) step against a paged KV cache.

    Args:
      q: ``[B, T_q, H, D]`` query step. ``T_q = 1`` is plain decode;
        ``T_q = k + 1`` is the speculative verify step (pending token +
        ``k`` proposed tokens per sequence, one dispatch). Each query
        row r attends causally at its own absolute position
        ``lengths[b] + r`` — bitwise the attention sequential decode
        would have computed, which is what makes greedy verify exact.
      k_pool / v_pool: ``[num_blocks, block_size, H, D]`` shared block
        pools; this step's keys must already be scattered at each row's
        ``[lengths[b], lengths[b] + T_q)`` logical positions (verify
        pads scatter into the garbage block and are never read).
      block_tables: ``[B, MB]`` int32 — row b's logical block j lives in
        pool block ``block_tables[b, j]``; entries past the allocation
        point at the reserved garbage block (their blocks skip compute).
      lengths: ``[B]`` int32 — valid tokens per row *before* this step.

    The block table and lengths are *scalar-prefetch* operands: the grid
    is static over ``(B, MB)``, each grid step DMAs exactly the pool
    block the table names, and blocks past ``lengths[b] + T_q`` skip both
    the fetch's compute and the online-softmax update.

    Returns ``[B, T_q, H, D]`` in the query's dtype.
    """
    b, tq, heads, d = q.shape
    if tq < 1:
        raise ValueError(f"need at least one query row per sequence, "
                         f"got T_q={tq}")
    nb, bs, ph, pd = k_pool.shape
    if (ph, pd) != (heads, d):
        raise ValueError(f"pool heads/dim {(ph, pd)} != query {(heads, d)}")
    mb = block_tables.shape[-1]
    scale = softmax_scale if softmax_scale is not None else d ** -0.5

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, mb),
        in_specs=[
            pl.BlockSpec((1, tq, heads, d),
                         lambda bi, ji, tab, ln: (bi, 0, 0, 0)),
            pl.BlockSpec((1, bs, heads, d),
                         lambda bi, ji, tab, ln: (tab[bi, ji], 0, 0, 0)),
            pl.BlockSpec((1, bs, heads, d),
                         lambda bi, ji, tab, ln: (tab[bi, ji], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, tq, heads, d),
                               lambda bi, ji, tab, ln: (bi, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((heads, tq, 128), jnp.float32),   # m
            pltpu.VMEM((heads, tq, 128), jnp.float32),   # l
            pltpu.VMEM((heads, tq, d), jnp.float32),     # acc
        ],
    )
    kernel = functools.partial(_paged_kernel, scale=scale, bs=bs, tq=tq,
                               heads=heads, d=d, num_kb=mb)
    tables = jnp.asarray(block_tables, jnp.int32)
    lens = jnp.asarray(lengths, jnp.int32)
    # no ``name=`` here or on the int8 twin below: a pallas_call's name is
    # also a named scope, and the device trace already prints this kernel
    # under the caller's scope (``attn._paged_kv_attend.N``), which the
    # benchmark's paged-decode roofline reader matches by that name
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, tq, heads, d), q.dtype),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
    )(tables, lens, q, k_pool, v_pool)


# ---------------------------------------------------------------------------
# int8 paged variant: the pools hold per-row symmetric int8 KV
# (ops.quantizer.quantize_rowwise — one f32 scale per token x head in a
# side pool indexed by the SAME block table), and the kernel dequantizes
# inside the block DMA's compute step. Attention math is unchanged and
# stays fp32-accumulated; gather_paged_cache_int8 above is the dense
# oracle this kernel is tested against with a pinned tolerance.
# ---------------------------------------------------------------------------


def _paged_int8_kernel(tables_ref, lens_ref, q_ref, k_ref, v_ref, ks_ref,
                       vs_ref, o_ref, m_scr, l_scr, acc_scr, *, scale, bs,
                       tq, heads, d, num_kb):
    bi = pl.program_id(0)
    ji = pl.program_id(1)
    idx = lens_ref[bi]

    @pl.when(ji == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(ji * bs < idx + tq)
    def _body():
        q = q_ref[...].reshape(tq, heads, d).transpose(1, 0, 2) \
            .astype(jnp.float32)                                   # [H,tq,d]
        # dequantize in-register: int8 rows x the side-pool scales
        ks = ks_ref[...].reshape(bs, heads, 1).transpose(1, 0, 2)  # [H,bs,1]
        vs = vs_ref[...].reshape(bs, heads, 1).transpose(1, 0, 2)
        k = k_ref[...].reshape(bs, heads, d).transpose(1, 0, 2) \
            .astype(jnp.float32) * ks                              # [H,bs,d]
        v = v_ref[...].reshape(bs, heads, d).transpose(1, 0, 2) \
            .astype(jnp.float32) * vs
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale            # [H,tq,bs]
        rows = jax.lax.broadcasted_iota(jnp.int32, (heads, tq, bs), 1)
        cols = jax.lax.broadcasted_iota(jnp.int32, (heads, tq, bs), 2) \
            + ji * bs
        s = jnp.where(cols <= idx + rows, s, NEG_INF)
        m_prev = m_scr[:, :, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scr[:, :, 0:1] + jnp.sum(p, axis=2, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)                    # [H,tq,d]
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ji == num_kb - 1)
    def _finish():
        l = l_scr[:, :, 0:1]
        out = acc_scr[:] / jnp.where(l == 0.0, 1.0, l)             # [H,tq,d]
        o_ref[...] = out.transpose(1, 0, 2).reshape(1, tq, heads, d) \
            .astype(o_ref.dtype)


def decode_attention_paged_int8(q, k_pool, v_pool, k_scale, v_scale,
                                block_tables, lengths, softmax_scale=None):
    """Attend a decode (or k-token verify) step against an
    int8-quantized paged KV cache.

    Same contract as :func:`decode_attention_paged` (including the
    multi-query-row verify semantics), except ``k_pool`` /
    ``v_pool`` are ``[num_blocks, block_size, H, D]`` int8 and
    ``k_scale`` / ``v_scale`` are their ``[num_blocks, block_size, H,
    1]`` f32 per-row scales (one scale per token x head —
    ``ops.quantizer.quantize_rowwise``). The scale side pools ride the
    same scalar-prefetch block table: each grid step DMAs the named pool
    block *and* its scale rows, dequantizes in-register, and runs the
    identical fp32 online-softmax update.
    """
    b, tq, heads, d = q.shape
    if tq < 1:
        raise ValueError(f"need at least one query row per sequence, "
                         f"got T_q={tq}")
    nb, bs, ph, pd = k_pool.shape
    if (ph, pd) != (heads, d):
        raise ValueError(f"pool heads/dim {(ph, pd)} != query {(heads, d)}")
    if k_scale.shape != (nb, bs, heads, 1):
        raise ValueError(
            f"scale pool shape {k_scale.shape} != {(nb, bs, heads, 1)} "
            f"(one f32 scale per pool row x head)")
    mb = block_tables.shape[-1]
    scale = softmax_scale if softmax_scale is not None else d ** -0.5

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, mb),
        in_specs=[
            pl.BlockSpec((1, tq, heads, d),
                         lambda bi, ji, tab, ln: (bi, 0, 0, 0)),
            pl.BlockSpec((1, bs, heads, d),
                         lambda bi, ji, tab, ln: (tab[bi, ji], 0, 0, 0)),
            pl.BlockSpec((1, bs, heads, d),
                         lambda bi, ji, tab, ln: (tab[bi, ji], 0, 0, 0)),
            pl.BlockSpec((1, bs, heads, 1),
                         lambda bi, ji, tab, ln: (tab[bi, ji], 0, 0, 0)),
            pl.BlockSpec((1, bs, heads, 1),
                         lambda bi, ji, tab, ln: (tab[bi, ji], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, tq, heads, d),
                               lambda bi, ji, tab, ln: (bi, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((heads, tq, 128), jnp.float32),   # m
            pltpu.VMEM((heads, tq, 128), jnp.float32),   # l
            pltpu.VMEM((heads, tq, d), jnp.float32),     # acc
        ],
    )
    kernel = functools.partial(_paged_int8_kernel, scale=scale, bs=bs,
                               tq=tq, heads=heads, d=d, num_kb=mb)
    tables = jnp.asarray(block_tables, jnp.int32)
    lens = jnp.asarray(lengths, jnp.int32)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, tq, heads, d), q.dtype),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
    )(tables, lens, q, k_pool, v_pool, k_scale, v_scale)


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


def decode_attention_paged_tp(q, k_pool, v_pool, block_tables, lengths,
                              softmax_scale=None, mesh=None, axis=None):
    """TP-aware :func:`decode_attention_paged`: the shared block pools
    live tp-sharded on their head dim (per-shard KV pools — each tp
    shard holds heads/tp of every pool block), block tables/lengths
    follow the batch."""
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.ops.kernel_mesh import kernel_mesh_plan

    def kernel(qs, ks, vs, t, ln):
        return decode_attention_paged(qs, ks, vs, t, ln,
                                      softmax_scale=softmax_scale)

    plan = kernel_mesh_plan(q.shape[0], q.shape[2], mesh=mesh, axis=axis)
    if plan is None:
        return kernel(q, k_pool, v_pool, block_tables, lengths)
    # pools are the SHARED per-replica cache: head-sharded over tp,
    # replicated over data; per-row operands follow the batch entry
    qs_spec = P(plan.batch, None, plan.heads, None)
    pool_spec = P(None, None, plan.heads, None)
    return plan.shard_map(
        kernel,
        (qs_spec, pool_spec, pool_spec, P(plan.batch), P(plan.batch)),
        qs_spec, name="paged_kv_attend")(q, k_pool, v_pool, jnp.asarray(block_tables, jnp.int32),
                 jnp.asarray(lengths, jnp.int32))


def decode_attention_paged_int8_tp(q, k_pool, v_pool, k_scale, v_scale,
                                   block_tables, lengths,
                                   softmax_scale=None, mesh=None,
                                   axis=None):
    """TP-aware :func:`decode_attention_paged_int8`: int8 pools AND
    their f32 scale side pools head-sharded over ``axis``."""
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.ops.kernel_mesh import kernel_mesh_plan

    def kernel(qs, ks, vs, kss, vss, t, ln):
        return decode_attention_paged_int8(qs, ks, vs, kss, vss, t, ln,
                                           softmax_scale=softmax_scale)

    plan = kernel_mesh_plan(q.shape[0], q.shape[2], mesh=mesh, axis=axis)
    if plan is None:
        return kernel(q, k_pool, v_pool, k_scale, v_scale, block_tables,
                      lengths)
    qs_spec = P(plan.batch, None, plan.heads, None)
    pool_spec = P(None, None, plan.heads, None)
    return plan.shard_map(
        kernel,
        (qs_spec, pool_spec, pool_spec, pool_spec, pool_spec,
         P(plan.batch), P(plan.batch)),
        qs_spec, name="paged_kv_attend")(q, k_pool, v_pool, k_scale, v_scale,
                 jnp.asarray(block_tables, jnp.int32),
                 jnp.asarray(lengths, jnp.int32))
