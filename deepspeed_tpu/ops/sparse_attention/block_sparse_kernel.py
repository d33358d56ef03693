"""Block-sparse attention — Pallas TPU kernels (fwd + bwd).

The real-compute-savings replacement for the reference's Triton SDD/DSD/DDS
block-sparse matmuls + block-sparse softmax
(``/root/reference/deepspeed/ops/sparse_attention/matmul.py:212``,
``softmax.py:142``): the ``SparsityConfig`` block layout is flattened
host-side into a per-head list of active (q_block, k_block) entries, and
the Pallas grid walks ONLY those entries — the step count (and so FLOPs,
DMA traffic, and wall-clock) scales with layout density, not seq².

Why flattened and not per-row: a per-row grid must pad every row to the
densest row's active count, and layouts like BigBird contain fully-dense
global rows — padding would erase all savings. Flattening keeps each row's
entries contiguous; the online-softmax state (re)initializes when the
entry's q_block differs from the previous entry's, and the output block is
written at each row's last entry (exactly the flash-kernel finish pattern,
``ops/flash_attention.py``).

Scalar-prefetch (``pltpu.PrefetchScalarGridSpec``) carries the entry lists
in SMEM; BlockSpec index maps read them to steer block fetches. Blocks are
all-or-nothing (the reference's block-granular semantics) so kernel bodies
need no iota masks. The full batch rides in every grid step (layouts are
batch-invariant): per-step dots are [B, bq, d]-batched, amortizing grid
overhead the way the flash kernel's bh-grouping does (PERF.md).

Layout contract: ``layout[H, num_q_blocks, num_k_blocks]`` bool, square
blocks, and every (head, q_block) row must have at least one active block
(an unwritten output block would otherwise be returned uninitialized).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.utils.compat import tpu_compiler_params

NEG_INF = -1e30

_DN_QK = (((2,), (2,)), ((0,), (0,)))   # [B,bq,d] x [B,bk,d] -> [B,bq,bk]
_DN_PV = (((2,), (1,)), ((0,), (0,)))   # [B,bq,bk] x [B,bk,d] -> [B,bq,d]
_DN_TT = (((1,), (1,)), ((0,), (0,)))   # [B,bq,bk] x [B,bq,d] -> [B,bk,d]


def flatten_layout(layout: np.ndarray):
    """[H, nq, nk] bool → (qrow[H, A], kcol[H, A], counts[H]) where A is the
    max total active entries over heads; each head's entries are row-major
    (a row's columns contiguous) and the tail is padded by repeating the
    last real entry (same q_block ⇒ no spurious state resets or writes)."""
    h, nq, nk = layout.shape
    per_head = []
    for hi in range(h):
        qs, ks = np.nonzero(layout[hi])
        if len(qs) == 0:
            raise ValueError(f"layout head {hi} has no active blocks")
        per_head.append((qs.astype(np.int32), ks.astype(np.int32)))
    counts = np.array([len(qs) for qs, _ in per_head], np.int32)
    a = int(counts.max())
    qrow = np.zeros((h, a), np.int32)
    kcol = np.zeros((h, a), np.int32)
    for hi, (qs, ks) in enumerate(per_head):
        n = len(qs)
        qrow[hi, :n], kcol[hi, :n] = qs, ks
        qrow[hi, n:], kcol[hi, n:] = qs[-1], ks[-1]
    return qrow, kcol, counts


def _row_has_gap(layout: np.ndarray) -> bool:
    return bool((layout.sum(axis=2) == 0).any())


# ----------------------------------------------------------------------
# forward


def _fwd_kernel(qrow_ref, kcol_ref, cnt_ref, q_ref, k_ref, v_ref,
                o_ref, lse_ref, m_scr, l_scr, acc_scr, *, scale, total):
    h = pl.program_id(0)
    t = pl.program_id(1)

    row = qrow_ref[h, t]
    prev_row = qrow_ref[h, jnp.maximum(t - 1, 0)]
    first = (t == 0) | (row != prev_row)
    cnt = cnt_ref[h]
    active = t < cnt
    nxt = qrow_ref[h, jnp.minimum(t + 1, total - 1)]
    last = (t == cnt - 1) | (active & (nxt != row) & (t + 1 < cnt))

    @pl.when(first)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(active)
    def _accum():
        q = q_ref[0]                                 # [B, bq, d]
        k = k_ref[0]                                 # [B, bk, d]
        v = v_ref[0]                                 # [B, bk, d]
        s = jax.lax.dot_general(
            q, k, _DN_QK, preferred_element_type=jnp.float32) * scale
        m_prev = m_scr[:, :, 0:1]                    # [B, bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = jnp.broadcast_to(
            alpha * l_scr[:, :, 0:1] + jnp.sum(p, axis=2, keepdims=True),
            l_scr.shape)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, _DN_PV, preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(last)
    def _finish():
        l = l_scr[:, :, 0:1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)
        lse_ref[0] = (m_scr[:, :, 0:1] + jnp.log(safe_l)).transpose(0, 2, 1)


def _sparse_forward_impl(qh, kh, vh, qrow, kcol, cnt, scale, *, nq, nk):
    # qh/kh/vh: [H, B, S, D] (head-major: the batch is one contiguous block)
    h, b, sq, d = qh.shape
    sk = kh.shape[2]
    a = qrow.shape[1]
    bq = sq // nq
    bk = sk // nk

    def _qmap(hi, t, qrow_r, kcol_r, cnt_r):
        return (hi, 0, qrow_r[hi, t], 0)

    def _kmap(hi, t, qrow_r, kcol_r, cnt_r):
        return (hi, 0, kcol_r[hi, t], 0)

    def _lmap(hi, t, qrow_r, kcol_r, cnt_r):
        return (hi, 0, 0, qrow_r[hi, t])

    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, total=a),
        name="block_sparse_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(h, a),
            in_specs=[
                pl.BlockSpec((1, b, bq, d), _qmap),
                pl.BlockSpec((1, b, bk, d), _kmap),
                pl.BlockSpec((1, b, bk, d), _kmap),
            ],
            out_specs=(
                pl.BlockSpec((1, b, bq, d), _qmap),
                pl.BlockSpec((1, b, 1, bq), _lmap),
            ),
            scratch_shapes=[
                pltpu.VMEM((b, bq, 128), jnp.float32),   # m
                pltpu.VMEM((b, bq, 128), jnp.float32),   # l
                pltpu.VMEM((b, bq, d), jnp.float32),     # acc
            ],
        ),
        out_shape=(
            jax.ShapeDtypeStruct((h, b, sq, d), qh.dtype),
            jax.ShapeDtypeStruct((h, b, 1, sq), jnp.float32),
        ),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
    )(qrow, kcol, cnt, qh, kh, vh)
    return o, lse.reshape(h, b, sq)


# ----------------------------------------------------------------------
# backward


def _bwd_dq_kernel(qrow_ref, kcol_ref, cnt_ref, q_ref, k_ref, v_ref, do_ref,
                   lse_ref, delta_ref, dq_ref, dq_scr, *, scale, total):
    h = pl.program_id(0)
    t = pl.program_id(1)

    row = qrow_ref[h, t]
    prev_row = qrow_ref[h, jnp.maximum(t - 1, 0)]
    first = (t == 0) | (row != prev_row)
    cnt = cnt_ref[h]
    active = t < cnt
    nxt = qrow_ref[h, jnp.minimum(t + 1, total - 1)]
    last = (t == cnt - 1) | (active & (nxt != row) & (t + 1 < cnt))

    @pl.when(first)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(active)
    def _accum():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0].transpose(0, 2, 1)        # [B, bq, 1]
        delta = delta_ref[0].transpose(0, 2, 1)
        s = jax.lax.dot_general(q, k, _DN_QK,
                                preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, _DN_QK,
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(k.dtype)
        dq_scr[:] += jax.lax.dot_general(
            ds, k, _DN_PV, preferred_element_type=jnp.float32)

    @pl.when(last)
    def _finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(krow_ref, qcol_ref, cnt_ref, q_ref, k_ref, v_ref, do_ref,
                    lse_ref, delta_ref, dk_ref, dv_ref, dk_scr, dv_scr,
                    *, scale, total):
    h = pl.program_id(0)
    t = pl.program_id(1)

    col = krow_ref[h, t]
    prev_col = krow_ref[h, jnp.maximum(t - 1, 0)]
    first = (t == 0) | (col != prev_col)
    cnt = cnt_ref[h]
    active = t < cnt
    nxt = krow_ref[h, jnp.minimum(t + 1, total - 1)]
    last = (t == cnt - 1) | (active & (nxt != col) & (t + 1 < cnt))

    @pl.when(first)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(active)
    def _accum():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0].transpose(0, 2, 1)        # [B, bq, 1]
        delta = delta_ref[0].transpose(0, 2, 1)
        s = jax.lax.dot_general(q, k, _DN_QK,
                                preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse)                       # [B, bq, bk]
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, _DN_TT,
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, _DN_QK,
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        dk_scr[:] += jax.lax.dot_general(
            ds, q, _DN_TT, preferred_element_type=jnp.float32)

    @pl.when(last)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _sparse_backward(qh, kh, vh, oh, lse, g, lists, scale, nq, nk):
    qrow, kcol, cnt, krow_t, qcol_t, cnt_t = lists
    h, b, sq, d = qh.shape
    sk = kh.shape[2]
    a, at = qrow.shape[1], krow_t.shape[1]
    bq, bk = sq // nq, sk // nk

    delta = jnp.sum(g.astype(jnp.float32) * oh.astype(jnp.float32),
                    axis=-1)                        # [h, b, sq]
    lse4 = lse.reshape(h, b, 1, sq)
    delta4 = delta.reshape(h, b, 1, sq)

    def _qmap(hi, t, qrow_r, kcol_r, cnt_r):
        return (hi, 0, qrow_r[hi, t], 0)

    def _kmap(hi, t, qrow_r, kcol_r, cnt_r):
        return (hi, 0, kcol_r[hi, t], 0)

    def _lmap(hi, t, qrow_r, kcol_r, cnt_r):
        return (hi, 0, 0, qrow_r[hi, t])

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, total=a),
        name="block_sparse_bwd_dq",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(h, a),
            in_specs=[
                pl.BlockSpec((1, b, bq, d), _qmap),      # q
                pl.BlockSpec((1, b, bk, d), _kmap),      # k
                pl.BlockSpec((1, b, bk, d), _kmap),      # v
                pl.BlockSpec((1, b, bq, d), _qmap),      # do
                pl.BlockSpec((1, b, 1, bq), _lmap),      # lse
                pl.BlockSpec((1, b, 1, bq), _lmap),      # delta
            ],
            out_specs=pl.BlockSpec((1, b, bq, d), _qmap),
            scratch_shapes=[pltpu.VMEM((b, bq, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((h, b, sq, d), qh.dtype),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
    )(qrow, kcol, cnt, qh, kh, vh, g, lse4, delta4)

    # dk/dv walk the transposed entry list: column-major, q steered
    def _qmap_t(hi, t, krow_r, qcol_r, cnt_r):
        return (hi, 0, qcol_r[hi, t], 0)

    def _kmap_t(hi, t, krow_r, qcol_r, cnt_r):
        return (hi, 0, krow_r[hi, t], 0)

    def _lmap_t(hi, t, krow_r, qcol_r, cnt_r):
        return (hi, 0, 0, qcol_r[hi, t])

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, total=at),
        name="block_sparse_bwd_dkv",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(h, at),
            in_specs=[
                pl.BlockSpec((1, b, bq, d), _qmap_t),    # q (steered)
                pl.BlockSpec((1, b, bk, d), _kmap_t),    # k
                pl.BlockSpec((1, b, bk, d), _kmap_t),    # v
                pl.BlockSpec((1, b, bq, d), _qmap_t),    # do (steered)
                pl.BlockSpec((1, b, 1, bq), _lmap_t),    # lse (steered)
                pl.BlockSpec((1, b, 1, bq), _lmap_t),    # delta (steered)
            ],
            out_specs=(
                pl.BlockSpec((1, b, bk, d), _kmap_t),
                pl.BlockSpec((1, b, bk, d), _kmap_t),
            ),
            scratch_shapes=[pltpu.VMEM((b, bk, d), jnp.float32),
                            pltpu.VMEM((b, bk, d), jnp.float32)],
        ),
        out_shape=(jax.ShapeDtypeStruct((h, b, sk, d), kh.dtype),
                   jax.ShapeDtypeStruct((h, b, sk, d), vh.dtype)),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
    )(krow_t, qcol_t, cnt_t, qh, kh, vh, g, lse4, delta4)
    return dq, dk, dv


# ----------------------------------------------------------------------
# public entry


def block_sparse_attention(q, k, v, layout: np.ndarray, softmax_scale=None):
    """Attention restricted to the block ``layout`` (all-or-nothing blocks,
    reference block-sparse semantics). q/k/v: ``[B, H, S, D]``; layout:
    ``[H, S//block, S//block]`` bool (static numpy), every row non-empty.

    Differentiable (custom VJP, flash-style two-kernel backward). Grid
    steps — and so FLOPs, DMA traffic, and wall-clock — scale with the
    number of active blocks, not seq².
    """
    b, h, sq, d = q.shape
    sk = k.shape[2]
    layout = np.asarray(layout, bool)
    if layout.ndim != 3 or layout.shape[0] != h:
        raise ValueError(f"layout must be [H={h}, nq, nk]; got {layout.shape}")
    nq, nk = layout.shape[1], layout.shape[2]
    if sq % nq or sk % nk or sq // nq != sk // nk:
        raise ValueError(
            f"layout {layout.shape} incompatible with seq {sq}/{sk}: "
            "square blocks required")
    if _row_has_gap(layout):
        raise ValueError(
            "every (head, q_block) row needs at least one active block "
            "(an empty row would leave its output block unwritten)")
    if _row_has_gap(layout.transpose(0, 2, 1)):
        raise ValueError(
            "every (head, k_block) column needs at least one active block "
            "(the backward dk/dv walk would leave that column's gradient "
            "blocks unwritten — garbage, not zeros)")
    scale = softmax_scale if softmax_scale is not None else 1.0 / (d ** 0.5)
    bq = sq // nq

    # group as many (batch, head) rows per grid step as VMEM allows — the
    # dominant perf lever (grid-step overhead rivals the MXU work at these
    # tile sizes; cf. the flash kernel's bh-grouping, PERF.md)
    def _group(n_rows):
        per_row = (bq * bq * 4 + 9 * bq * d * 4 + 2 * bq * 128 * 4)
        budget = 10 * 1024 * 1024
        for g in range(min(n_rows, max(1, budget // per_row)), 0, -1):
            if n_rows % g == 0:
                return g
        return 1

    same_layout = bool(np.all(layout == layout[0:1]))
    if same_layout:
        # one layout for every head: fold batch*heads into the grouped dim
        rows = b * h
        g = _group(rows)
        qh = q.transpose(1, 0, 2, 3).reshape(rows // g, g, sq, d)
        kh = k.transpose(1, 0, 2, 3).reshape(rows // g, g, sk, d)
        vh = v.transpose(1, 0, 2, 3).reshape(rows // g, g, sk, d)
        tile = rows // g
        layout_eff = np.broadcast_to(layout[0:1], (tile, nq, nk))
    else:
        # distinct per-head layouts: heads stay the steering dim, the
        # batch rides along (split if it alone overflows VMEM)
        if _group(b) < b:
            half = b // 2
            return jnp.concatenate([
                block_sparse_attention(q[:half], k[:half], v[:half], layout,
                                       softmax_scale),
                block_sparse_attention(q[half:], k[half:], v[half:], layout,
                                       softmax_scale)], axis=0)
        qh = q.transpose(1, 0, 2, 3)
        kh = k.transpose(1, 0, 2, 3)
        vh = v.transpose(1, 0, 2, 3)
        layout_eff = layout

    qrow, kcol, cnt = flatten_layout(layout_eff)
    # transposed walk for dk/dv: sort entries column-major
    krow_t, qcol_t, cnt_t = flatten_layout(layout_eff.transpose(0, 2, 1))
    lists = tuple(jnp.asarray(x)
                  for x in (qrow, kcol, cnt, krow_t, qcol_t, cnt_t))

    @jax.custom_vjp
    def _attn(qh, kh, vh):
        o, _ = _sparse_forward_impl(qh, kh, vh, lists[0], lists[1], lists[2],
                                    scale, nq=nq, nk=nk)
        return o

    def _fwd(qh, kh, vh):
        o, lse = _sparse_forward_impl(qh, kh, vh, lists[0], lists[1],
                                      lists[2], scale, nq=nq, nk=nk)
        return o, (qh, kh, vh, o, lse)

    def _bwd(res, g):
        qh, kh, vh, o, lse = res
        return _sparse_backward(qh, kh, vh, o, lse, g, lists, scale, nq, nk)

    _attn.defvjp(_fwd, _bwd)
    out = _attn(qh, kh, vh)
    if same_layout:
        return out.reshape(h, b, sq, d).transpose(1, 0, 2, 3)
    return out.transpose(1, 0, 2, 3)
