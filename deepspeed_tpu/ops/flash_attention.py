"""Flash attention — Pallas TPU kernels (fwd + bwd).

Replaces the reference's fused CUDA attention path
(``csrc/transformer/softmax_kernels.cu``, ``transform_kernels.cu``,
``csrc/transformer/inference/csrc/softmax.cu``) with an online-softmax tiled
kernel: O(T) memory (never materializes the [T, T] score matrix), fp32
accumulation on the MXU, causal block skipping.

Layout: q, k, v are [batch, heads, seq, head_dim]. The grid walks
(batch*heads / G, q_block, k_block) with the k dimension innermost — TPU
grids execute sequentially, so the online-softmax state (m, l, acc) lives in
VMEM scratch carried across k steps. G batch*head rows are processed per
grid step (batched dots): transformer shapes make single-(bh, q, k) tiles so
small that per-step grid overhead, not the MXU, dominates — batching G rows
amortizes it (measured 3-4x on GPT-2 125M shapes on v5e).

Backward is the standard two-kernel flash bwd (dq by rows, dk/dv by columns)
using the saved logsumexp and D = rowsum(dO * O).
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.utils.compat import tpu_compiler_params

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
NEG_INF = -1e30

# batched dot_general dimension numbers: contract last dims, batch dim 0
_DN_QK = (((2,), (2,)), ((0,), (0,)))   # [G,bq,d] x [G,bk,d] -> [G,bq,bk]
_DN_PV = (((2,), (1,)), ((0,), (0,)))   # [G,bq,bk] x [G,bk,d] -> [G,bq,d]
_DN_TT = (((1,), (1,)), ((0,), (0,)))   # [G,bq,bk] x [G,bq,d] -> [G,bk,d]


def _block_sizes(seq_q, seq_k, block_q, block_k):
    bq = min(block_q, seq_q)
    bk = min(block_k, seq_k)
    if seq_q % bq or seq_k % bk:
        raise ValueError(
            f"flash_attention requires seq divisible by block sizes: "
            f"seq_q={seq_q} bq={bq}, seq_k={seq_k} bk={bk}")
    return bq, bk


def _row_vmem_bytes(bq: int, bk: int, d: int) -> int:
    """Per-(batch*head)-row VMEM for one grid step: scores + softmax state
    + accumulators + io blocks. Single source for both kernel families —
    the folded and strided drivers must size tiles from the same model."""
    return (
        bq * bk * 4            # scores / p / ds transient
        + 2 * bq * 128 * 4     # m, l scratch (lanes padded to 128)
        + 3 * bq * d * 4       # fp32 accumulators (acc / dk+dv)
        + 3 * (bq + bk) * d * 2  # in/out blocks incl. double buffering
    )


def _bh_group(bh: int, bq: int, bk: int, d: int) -> int:
    """Rows of the folded batch*heads dim processed per grid step, bounded
    so per-step VMEM (scores + softmax state + accumulators + io blocks)
    stays under the ~16 MiB scoped-vmem stack limit."""
    per_row = _row_vmem_bytes(bq, bk, d)
    budget = 10 * 1024 * 1024
    for g in (16, 8, 4, 2):
        if bh % g == 0 and g * per_row <= budget:
            return g
    return 1


# ----------------------------------------------------------------------
# forward
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, causal, bq, bk, num_kb, off):
    # ``off = seq_k - seq_q``: causal masks are bottom-right aligned (row i
    # attends to cols <= i + off), matching ``attention_reference``'s
    # ``tril(k=k_len-q_len)`` for kv-cache style seq_q != seq_k calls.
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # causal: skip blocks entirely above the diagonal; blocks entirely below
    # it need no mask at all (saves the iota/compare/select VPU passes, which
    # rival the MXU work at transformer tile sizes)
    run = True
    on_diag = causal
    if causal:
        run = (ki * bk) <= (qi * bq + bq - 1 + off)
        on_diag = run & ((ki * bk + bk - 1) > (qi * bq + off))

    def _accum(masked):
        q = q_ref[...]                             # [G, bq, d] input dtype
        k = k_ref[...]                             # [G, bk, d]
        v = v_ref[...]                             # [G, bk, d]
        # multiply at input precision (bf16 on the MXU's native rate),
        # accumulate fp32 — the flash-attention standard
        s = jax.lax.dot_general(q, k, _DN_QK,
                                preferred_element_type=jnp.float32) * scale
        g = s.shape[0]
        if masked:
            rows = jax.lax.broadcasted_iota(jnp.int32, (g, bq, bk), 1) + qi * bq
            cols = jax.lax.broadcasted_iota(jnp.int32, (g, bq, bk), 2) + ki * bk
            s = jnp.where(rows + off >= cols, s, NEG_INF)
        m_prev = m_scr[:, :, 0:1]                  # [G, bq, 1]
        m_cur = jnp.max(s, axis=2, keepdims=True)  # [G, bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        if masked and off < 0:
            # fully-masked rows (seq_q > seq_k with causal): m_new stays
            # NEG_INF and exp(s - m_new) would be exp(0)=1 per masked col —
            # force p to 0. Unneeded when off >= 0: exp(NEG_INF - finite) = 0.
            p = jnp.where(s > NEG_INF * 0.5, jnp.exp(s - m_new), 0.0)
        else:
            p = jnp.exp(s - m_new)                 # [G, bq, bk]
        alpha = jnp.exp(m_prev - m_new)            # [G, bq, 1]
        l_new = alpha * l_scr[:, :, 0:1] + jnp.sum(p, axis=2, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, _DN_PV, preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    if causal:
        @pl.when(on_diag)
        def _body_masked():
            _accum(True)

        @pl.when(run & ~on_diag)
        def _body_full():
            _accum(False)
    else:
        _accum(False)

    @pl.when(ki == num_kb - 1)
    def _finish():
        l = l_scr[:, :, 0:1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_scr[:] / safe_l).astype(o_ref.dtype)
        lse_ref[...] = (m_scr[:, :, 0:1] + jnp.log(safe_l)).transpose(0, 2, 1)


def _flash_forward(q, k, v, scale, causal, block_q, block_k):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq, bk = _block_sizes(sq, sk, block_q, block_k)
    num_kb = sk // bk
    bh = b * h
    g = _bh_group(bh, bq, bk, d)
    grid = (bh // g, sq // bq, num_kb)

    qs = pl.BlockSpec((g, bq, d), lambda bhi, qi, ki: (bhi, qi, 0),
                      memory_space=pltpu.VMEM)
    ks = pl.BlockSpec((g, bk, d), lambda bhi, qi, ki: (bhi, ki, 0),
                      memory_space=pltpu.VMEM)
    vs = pl.BlockSpec((g, bk, d), lambda bhi, qi, ki: (bhi, ki, 0),
                      memory_space=pltpu.VMEM)
    os_ = pl.BlockSpec((g, bq, d), lambda bhi, qi, ki: (bhi, qi, 0),
                       memory_space=pltpu.VMEM)
    ls = pl.BlockSpec((g, 1, bq), lambda bhi, qi, ki: (bhi, 0, qi),
                      memory_space=pltpu.VMEM)

    q3 = q.reshape(bh, sq, d)
    k3 = k.reshape(bh, sk, d)
    v3 = v.reshape(bh, sk, d)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk, num_kb=num_kb, off=sk - sq)
    o, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=grid,
        in_specs=[qs, ks, vs],
        out_specs=(os_, ls),
        out_shape=(
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32),
        ),
        scratch_shapes=[
            pltpu.VMEM((g, bq, 128), jnp.float32),   # m
            pltpu.VMEM((g, bq, 128), jnp.float32),   # l
            pltpu.VMEM((g, bq, d), jnp.float32),     # acc
        ],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(q3, k3, v3)
    return o.reshape(b, h, sq, d), lse.reshape(b, h, sq)


# ----------------------------------------------------------------------
# backward
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_scr, *, scale, causal, bq, bk, num_kb, off):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    run = True
    on_diag = causal
    if causal:
        run = (ki * bk) <= (qi * bq + bq - 1 + off)
        on_diag = run & ((ki * bk + bk - 1) > (qi * bq + off))

    def _accum(masked):
        q = q_ref[...]
        k = k_ref[...]
        v = v_ref[...]
        do = do_ref[...]
        lse = lse_ref[...].transpose(0, 2, 1)      # [G, bq, 1]
        delta = delta_ref[...].transpose(0, 2, 1)  # [G, bq, 1]
        s = jax.lax.dot_general(q, k, _DN_QK,
                                preferred_element_type=jnp.float32) * scale
        g = s.shape[0]
        if masked:
            rows = jax.lax.broadcasted_iota(jnp.int32, (g, bq, bk), 1) + qi * bq
            cols = jax.lax.broadcasted_iota(jnp.int32, (g, bq, bk), 2) + ki * bk
            s = jnp.where(rows + off >= cols, s, NEG_INF)
        if masked and off < 0:
            # masked cols → p=0 incl. fully-masked rows where lse is NEG_INF
            p = jnp.where(s > NEG_INF * 0.5, jnp.exp(s - lse), 0.0)
        else:
            p = jnp.exp(s - lse)                   # [G, bq, bk]
        dp = jax.lax.dot_general(do, v, _DN_QK,
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(k.dtype)
        dq_scr[:] += jax.lax.dot_general(ds, k, _DN_PV,
                                         preferred_element_type=jnp.float32)

    if causal:
        @pl.when(on_diag)
        def _body_masked():
            _accum(True)

        @pl.when(run & ~on_diag)
        def _body_full():
            _accum(False)
    else:
        _accum(False)

    @pl.when(ki == num_kb - 1)
    def _finish():
        dq_ref[...] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr,
                    *, scale, causal, bq, bk, num_qb, off):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run = True
    on_diag = causal
    if causal:  # q block must reach the (offset) diagonal
        run = (qi * bq + bq - 1 + off) >= (ki * bk)
        on_diag = run & ((ki * bk + bk - 1) > (qi * bq + off))

    def _accum(masked):
        q = q_ref[...]
        k = k_ref[...]
        v = v_ref[...]
        do = do_ref[...]
        lse = lse_ref[...].transpose(0, 2, 1)      # [G, bq, 1]
        delta = delta_ref[...].transpose(0, 2, 1)  # [G, bq, 1]
        s = jax.lax.dot_general(q, k, _DN_QK,
                                preferred_element_type=jnp.float32) * scale
        g = s.shape[0]
        if masked:
            rows = jax.lax.broadcasted_iota(jnp.int32, (g, bq, bk), 1) + qi * bq
            cols = jax.lax.broadcasted_iota(jnp.int32, (g, bq, bk), 2) + ki * bk
            s = jnp.where(rows + off >= cols, s, NEG_INF)
        if masked and off < 0:
            # masked cols → p=0 incl. fully-masked rows where lse is NEG_INF
            p = jnp.where(s > NEG_INF * 0.5, jnp.exp(s - lse), 0.0)
        else:
            p = jnp.exp(s - lse)                   # [G, bq, bk]
        p_lp = p.astype(do.dtype)
        dv_scr[:] += jax.lax.dot_general(p_lp, do, _DN_TT,
                                         preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, _DN_QK,
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(q.dtype)  # [G, bq, bk]
        dk_scr[:] += jax.lax.dot_general(ds, q, _DN_TT,
                                         preferred_element_type=jnp.float32)

    if causal:
        @pl.when(on_diag)
        def _body_masked():
            _accum(True)

        @pl.when(run & ~on_diag)
        def _body_full():
            _accum(False)
    else:
        _accum(False)

    @pl.when(qi == num_qb - 1)
    def _finish():
        dk_ref[...] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[:].astype(dv_ref.dtype)


def _flash_backward(res, g, scale, causal, block_q, block_k):
    q, k, v, o, lse = res
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq, bk = _block_sizes(sq, sk, block_q, block_k)
    num_qb, num_kb = sq // bq, sk // bk
    bh = b * h
    gg = _bh_group(bh, bq, bk, d)

    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)  # [b,h,sq]

    q3 = q.reshape(bh, sq, d)
    k3 = k.reshape(bh, sk, d)
    v3 = v.reshape(bh, sk, d)
    do3 = g.reshape(bh, sq, d)
    lse3 = lse.reshape(bh, 1, sq)
    delta3 = delta.reshape(bh, 1, sq)

    def _spec(rows, map_fn):
        return pl.BlockSpec((gg, rows[0], rows[1]), map_fn,
                            memory_space=pltpu.VMEM)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, num_kb=num_kb, off=sk - sq),
        name="flash_bwd_dq",
        grid=(bh // gg, num_qb, num_kb),
        in_specs=[
            _spec((bq, d), lambda bhi, qi, ki: (bhi, qi, 0)),
            _spec((bk, d), lambda bhi, qi, ki: (bhi, ki, 0)),
            _spec((bk, d), lambda bhi, qi, ki: (bhi, ki, 0)),
            _spec((bq, d), lambda bhi, qi, ki: (bhi, qi, 0)),
            _spec((1, bq), lambda bhi, qi, ki: (bhi, 0, qi)),
            _spec((1, bq), lambda bhi, qi, ki: (bhi, 0, qi)),
        ],
        out_specs=_spec((bq, d), lambda bhi, qi, ki: (bhi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((gg, bq, d), jnp.float32)],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(q3, k3, v3, do3, lse3, delta3)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, num_qb=num_qb, off=sk - sq),
        name="flash_bwd_dkv",
        grid=(bh // gg, num_kb, num_qb),
        in_specs=[
            _spec((bq, d), lambda bhi, ki, qi: (bhi, qi, 0)),
            _spec((bk, d), lambda bhi, ki, qi: (bhi, ki, 0)),
            _spec((bk, d), lambda bhi, ki, qi: (bhi, ki, 0)),
            _spec((bq, d), lambda bhi, ki, qi: (bhi, qi, 0)),
            _spec((1, bq), lambda bhi, ki, qi: (bhi, 0, qi)),
            _spec((1, bq), lambda bhi, ki, qi: (bhi, 0, qi)),
        ],
        out_specs=(
            _spec((bk, d), lambda bhi, ki, qi: (bhi, ki, 0)),
            _spec((bk, d), lambda bhi, ki, qi: (bhi, ki, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
        ),
        scratch_shapes=[pltpu.VMEM((gg, bk, d), jnp.float32),
                        pltpu.VMEM((gg, bk, d), jnp.float32)],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(q3, k3, v3, do3, lse3, delta3)

    return (dq.reshape(b, h, sq, d), dk.reshape(b, h, sk, d),
            dv.reshape(b, h, sk, d))


# ----------------------------------------------------------------------
# strided [B, T, H, D] entry — no HBM relayout
#
# The [B, H, T, D] entry forces the model to transpose QKV before and the
# output after every layer; because the pallas custom-call pins default
# layouts, XLA materializes those as {2,3,1,0}→{3,2,1,0} HBM copies
# (~10-16 ms/step on the GPT-2 bench, PERF.md "remaining headroom").
# These wrappers keep tensors in the projection's natural [B, T, H, D]
# layout end to end: BlockSpecs fetch (1, bq, g, d) tiles — contiguous
# (row, heads-group) strips, a strided but DMA-friendly pattern — and a
# cheap VMEM-local swap presents them to the unchanged kernel bodies as
# [g, bq, d].

class _SwapRef:
    """[1, rows, g, d] block ref viewed as the kernels' [g, rows, d]."""

    def __init__(self, ref):
        self._ref = ref

    def __getitem__(self, idx):
        return self._ref[...][0].swapaxes(0, 1)

    def __setitem__(self, idx, val):
        self._ref[...] = val.swapaxes(0, 1)[None]

    @property
    def dtype(self):
        return self._ref.dtype


class _LseRef:
    """[1, 1, g, bq] block ref viewed as the kernels' [g, 1, bq]."""

    def __init__(self, ref):
        self._ref = ref

    def __getitem__(self, idx):
        return self._ref[...][0].swapaxes(0, 1)  # [g, 1, bq]

    def __setitem__(self, idx, val):
        self._ref[...] = val.swapaxes(0, 1)[None]

    @property
    def dtype(self):
        return self._ref.dtype


def _head_group(h: int, bq: int, bk: int, d: int) -> int:
    """Heads per grid step for the strided layout: same VMEM budget as the
    folded layout, but the group is the block's second-to-last dim, so
    Pallas additionally requires it be a multiple of 8 OR the full head
    count (the folded layout has no such constraint — its head dim is the
    leading block dim). Returns 0 when no legal group fits the budget —
    ``_bthd_tiles`` then shrinks the seq tiles and retries, raising
    ValueError when nothing legal exists (``models/gpt2.py`` catches that
    and dispatches the folded kernel instead)."""
    per_row = _row_vmem_bytes(bq, bk, d)
    # measured on v5e: the strided backward's true VMEM stack is ~2x this
    # estimate (extra score/ds transients + double-buffered 4D io blocks),
    # so its budget is half the folded kernel's 10 MiB
    budget = 5 * 1024 * 1024
    for g in (h, 16, 8):
        if g % 8 == 0 or g == h:
            if h % g == 0 and g * per_row <= budget:
                return g
    return 0


def _tile_divisors(s: int, cap: int):
    """Divisors of ``s`` in [floor, cap], descending — every legal tile
    size, not just the halving chain (seq 384 must be able to reach 128
    even though 384 -> 192 -> 96 skips it). The floor is 128 for the
    default walk, but an explicitly smaller ``cap`` (a caller-passed
    sub-128 block size) is honored as its own floor.

    Only sublane-aligned tiles (multiples of 8) are admitted, unless the
    tile IS the full dim (the always-legal fallback): a tile like 300 for
    s=600 divides the seq but dies inside Mosaic lowering — not a
    ValueError, so the caller's standard-path fallback would never engage
    and the forward would crash instead of dispatching dense attention."""
    floor = min(128, cap)
    return [t for t in range(min(cap, s), floor - 1, -1)
            if s % t == 0 and (t % 8 == 0 or t == s)]


def _bthd_tiles(sq, sk, h, d, block_q, block_k):
    """(bq, bk, g) for the strided layout: shrink the seq tiles (128
    floor by default; an explicitly sub-128 ``block_q``/``block_k`` is
    its own floor) until a Pallas-legal head group — a multiple of 8, or
    all ``h`` heads — fits the VMEM budget. Walks the full divisor lattice,
    largest tiles first, shrinking the larger of the two (keeps tiles
    squarish). Deterministic in its static args, so the fwd and bwd
    drivers always agree."""
    # do NOT route through _block_sizes here: its divisibility raise would
    # reject sq=768 at the default 512 block even though the divisor walk
    # below holds legal tiles (384/256/192/128). The walk owns
    # divisibility; the full-seq tile is the always-legal fallback.
    bq0, bk0 = min(block_q, sq), min(block_k, sk)
    qd = _tile_divisors(sq, bq0) or [sq]
    kd = _tile_divisors(sk, bk0) or [sk]
    i = j = 0
    while True:
        g = _head_group(h, qd[i], kd[j], d)
        if g:
            return qd[i], kd[j], g
        if kd[j] >= qd[i] and j + 1 < len(kd):
            j += 1
        elif i + 1 < len(qd):
            i += 1
        elif j + 1 < len(kd):
            j += 1
        else:
            raise ValueError(
                f"flash_attention_bthd: no legal head group for {h} "
                f"heads at any tile size (needs a group that is a "
                "multiple of 8, or all heads, within the VMEM budget) — "
                "use the folded [B, H, T, D] kernel for this shape")


def _fwd_kernel_bthd(q_ref, k_ref, v_ref, o_ref, lse_ref, m, l, acc, **kw):
    _fwd_kernel(_SwapRef(q_ref), _SwapRef(k_ref), _SwapRef(v_ref),
                _SwapRef(o_ref), _LseRef(lse_ref), m, l, acc, **kw)


def _bwd_dq_kernel_bthd(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                        dq_ref, dq_scr, **kw):
    _bwd_dq_kernel(_SwapRef(q_ref), _SwapRef(k_ref), _SwapRef(v_ref),
                   _SwapRef(do_ref), _LseRef(lse_ref), _LseRef(delta_ref),
                   _SwapRef(dq_ref), dq_scr, **kw)


def _bwd_dkv_kernel_bthd(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dk_ref, dv_ref, dk_scr, dv_scr, **kw):
    _bwd_dkv_kernel(_SwapRef(q_ref), _SwapRef(k_ref), _SwapRef(v_ref),
                    _SwapRef(do_ref), _LseRef(lse_ref), _LseRef(delta_ref),
                    _SwapRef(dk_ref), _SwapRef(dv_ref), dk_scr, dv_scr, **kw)


def _flash_forward_bthd(q, k, v, scale, causal, block_q, block_k):
    b, sq, h, d = q.shape
    sk = k.shape[1]
    bq, bk, g = _bthd_tiles(sq, sk, h, d, block_q, block_k)
    num_kb = sk // bk
    hpg = h // g
    grid = (b * hpg, sq // bq, num_kb)

    def qspec(bhi, qi, ki):
        return (bhi // hpg, qi, bhi % hpg, 0)

    def kspec(bhi, qi, ki):
        return (bhi // hpg, ki, bhi % hpg, 0)

    qs = pl.BlockSpec((1, bq, g, d), qspec, memory_space=pltpu.VMEM)
    ks = pl.BlockSpec((1, bk, g, d), kspec, memory_space=pltpu.VMEM)
    os_ = pl.BlockSpec((1, bq, g, d), qspec, memory_space=pltpu.VMEM)
    ls = pl.BlockSpec((1, 1, g, bq),
                      lambda bhi, qi, ki: (bhi // hpg, bhi % hpg, 0, qi),
                      memory_space=pltpu.VMEM)
    kernel = functools.partial(_fwd_kernel_bthd, scale=scale, causal=causal,
                               bq=bq, bk=bk, num_kb=num_kb, off=sk - sq)
    o, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=grid,
        in_specs=[qs, ks, ks],
        out_specs=(os_, ls),
        out_shape=(
            jax.ShapeDtypeStruct((b, sq, h, d), q.dtype),
            jax.ShapeDtypeStruct((b, hpg, g, sq), jnp.float32),
        ),
        scratch_shapes=[
            pltpu.VMEM((g, bq, 128), jnp.float32),
            pltpu.VMEM((g, bq, 128), jnp.float32),
            pltpu.VMEM((g, bq, d), jnp.float32),
        ],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(q, k, v)
    return o, lse


def _flash_backward_bthd(res, dout, scale, causal, block_q, block_k):
    q, k, v, o, lse = res  # lse: [b, hpg, g, sq]
    b, sq, h, d = q.shape
    sk = k.shape[1]
    bq, bk, g = _bthd_tiles(sq, sk, h, d, block_q, block_k)
    num_qb, num_kb = sq // bq, sk // bk
    hpg = h // g

    # D = rowsum(dO * O): [b, sq, h] -> the lse tiling [b, hpg, g, sq]
    delta = jnp.sum(dout.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)
    delta = delta.transpose(0, 2, 1).reshape(b, hpg, g, sq)

    def qmap(bhi, qi, ki):
        return (bhi // hpg, qi, bhi % hpg, 0)

    def kmap(bhi, qi, ki):
        return (bhi // hpg, ki, bhi % hpg, 0)

    def lmap(bhi, qi, ki):
        return (bhi // hpg, bhi % hpg, 0, qi)

    qs = pl.BlockSpec((1, bq, g, d), qmap, memory_space=pltpu.VMEM)
    ks = pl.BlockSpec((1, bk, g, d), kmap, memory_space=pltpu.VMEM)
    ls = pl.BlockSpec((1, 1, g, bq), lmap, memory_space=pltpu.VMEM)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel_bthd, scale=scale, causal=causal,
                          bq=bq, bk=bk, num_kb=num_kb, off=sk - sq),
        name="flash_bwd_dq",
        grid=(b * hpg, num_qb, num_kb),
        in_specs=[qs, ks, ks, qs, ls, ls],
        out_specs=qs,
        out_shape=jax.ShapeDtypeStruct((b, sq, h, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((g, bq, d), jnp.float32)],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(q, k, v, dout, lse, delta)

    def kmap2(bhi, ki, qi):
        return (bhi // hpg, ki, bhi % hpg, 0)

    def qmap2(bhi, ki, qi):
        return (bhi // hpg, qi, bhi % hpg, 0)

    def lmap2(bhi, ki, qi):
        return (bhi // hpg, bhi % hpg, 0, qi)

    qs2 = pl.BlockSpec((1, bq, g, d), qmap2, memory_space=pltpu.VMEM)
    ks2 = pl.BlockSpec((1, bk, g, d), kmap2, memory_space=pltpu.VMEM)
    ls2 = pl.BlockSpec((1, 1, g, bq), lmap2, memory_space=pltpu.VMEM)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel_bthd, scale=scale, causal=causal,
                          bq=bq, bk=bk, num_qb=num_qb, off=sk - sq),
        name="flash_bwd_dkv",
        grid=(b * hpg, num_kb, num_qb),
        in_specs=[qs2, ks2, ks2, qs2, ls2, ls2],
        out_specs=(ks2, ks2),
        out_shape=(
            jax.ShapeDtypeStruct((b, sk, h, d), k.dtype),
            jax.ShapeDtypeStruct((b, sk, h, d), v.dtype),
        ),
        scratch_shapes=[pltpu.VMEM((g, bk, d), jnp.float32),
                        pltpu.VMEM((g, bk, d), jnp.float32)],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(q, k, v, dout, lse, delta)
    return dq, dk, dv


def _resolved_tiles(block_q, block_k):
    """Tile defaults through the live-tunable registry (explicit arg >
    tuned artifact > built-in default). Runs at trace time only; with
    nothing installed the traced program is byte-identical to the
    pre-registry kernel (zero-overhead contract). Resolved inside each
    custom_vjp leg because the vjp machinery forwards the call-site
    (possibly None) values to fwd and bwd."""
    from deepspeed_tpu.autotuning import runtime_tunables

    return (runtime_tunables.resolve(block_q, "ops.flash_attention.block_q",
                                     DEFAULT_BLOCK_Q),
            runtime_tunables.resolve(block_k, "ops.flash_attention.block_k",
                                     DEFAULT_BLOCK_K))


def flash_ineligible(q_shape, k_shape, layout, block_q=None, block_k=None):
    """Why the kernel cannot serve these shapes, or ``None`` when it can.

    The dispatchers ask this BEFORE calling the kernel, so the XLA path is
    taken by a decision made from the shapes (and counted,
    ``ops.attention.dispatch_counts``) and never by catching what the
    kernel raises. ``layout``: ``"bhtd"`` (folded, [B, H, T, D]) or
    ``"bthd"`` (strided, [B, T, H, D], judged on the head group one shard
    of :func:`flash_attention_bthd_tp` hands the kernel). The tile rules
    themselves live in ``_block_sizes`` / ``_bthd_tiles``, which the
    kernels call again; what they raise is the reason."""
    block_q, block_k = _resolved_tiles(block_q, block_k)
    try:
        if layout == "bthd":
            from deepspeed_tpu.ops.kernel_mesh import kernel_mesh_plan

            _, seq_q, heads, head_dim = q_shape
            plan = kernel_mesh_plan(q_shape[0], heads, seqlen=seq_q)
            if plan is not None:
                heads //= plan.size(plan.heads) * plan.size(plan.seq)
            _bthd_tiles(seq_q, k_shape[1], heads, head_dim, block_q, block_k)
        else:
            _block_sizes(q_shape[-2], k_shape[-2], block_q, block_k)
    except ValueError as e:
        return str(e)
    return None


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention_bthd(q, k, v, causal=True, softmax_scale=None,
                         block_q=None, block_k=None):
    """Flash attention over the projection-natural layout.

    q, k, v: [batch, seq, heads, head_dim] — the shape a fused QKV
    projection produces — returning the same layout, so the surrounding
    program needs no transposes (and XLA inserts no HBM relayout copies
    around the custom-call).
    """
    scale = softmax_scale if softmax_scale is not None else q.shape[-1] ** -0.5
    block_q, block_k = _resolved_tiles(block_q, block_k)
    o, _ = _flash_forward_bthd(q, k, v, scale, causal, block_q, block_k)
    return o


def _fab_fwd(q, k, v, causal, softmax_scale, block_q, block_k):
    from jax.ad_checkpoint import checkpoint_name

    scale = softmax_scale if softmax_scale is not None else q.shape[-1] ** -0.5
    block_q, block_k = _resolved_tiles(block_q, block_k)
    q = checkpoint_name(q, "flash_q")
    k = checkpoint_name(k, "flash_k")
    v = checkpoint_name(v, "flash_v")
    o, lse = _flash_forward_bthd(q, k, v, scale, causal, block_q, block_k)
    o = checkpoint_name(o, "flash_o")
    lse = checkpoint_name(lse, "flash_lse")
    return o, (q, k, v, o, lse)


def _fab_bwd(causal, softmax_scale, block_q, block_k, res, g):
    q = res[0]
    scale = softmax_scale if softmax_scale is not None else q.shape[-1] ** -0.5
    block_q, block_k = _resolved_tiles(block_q, block_k)
    return _flash_backward_bthd(res, g, scale, causal, block_q, block_k)


flash_attention_bthd.defvjp(_fab_fwd, _fab_bwd)


# ----------------------------------------------------------------------
# public op
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal=True, softmax_scale=None,
                    block_q=None, block_k=None):
    """Tiled online-softmax attention. q,k,v: [batch, heads, seq, head_dim].

    ``block_q``/``block_k`` default through the live-tunable registry
    (``ops.flash_attention.block_q``/``block_k`` — see
    :func:`_resolved_tiles`)."""
    scale = softmax_scale if softmax_scale is not None else q.shape[-1] ** -0.5
    block_q, block_k = _resolved_tiles(block_q, block_k)
    o, _ = _flash_forward(q, k, v, scale, causal, block_q, block_k)
    return o


def _fa_fwd(q, k, v, causal, softmax_scale, block_q, block_k):
    from jax.ad_checkpoint import checkpoint_name

    scale = softmax_scale if softmax_scale is not None else q.shape[-1] ** -0.5
    block_q, block_k = _resolved_tiles(block_q, block_k)
    # name the residuals so activation-checkpointing policies can keep them:
    # under remat with e.g. checkpoint_dots + save_only_these_names(
    # "flash_q","flash_k","flash_v","flash_o","flash_lse"), the backward pass
    # reuses these instead of replaying the forward kernel (and the layout
    # transposes feeding it)
    q = checkpoint_name(q, "flash_q")
    k = checkpoint_name(k, "flash_k")
    v = checkpoint_name(v, "flash_v")
    o, lse = _flash_forward(q, k, v, scale, causal, block_q, block_k)
    o = checkpoint_name(o, "flash_o")
    lse = checkpoint_name(lse, "flash_lse")
    return o, (q, k, v, o, lse)


def _fa_bwd(causal, softmax_scale, block_q, block_k, res, g):
    q = res[0]
    scale = softmax_scale if softmax_scale is not None else q.shape[-1] ** -0.5
    block_q, block_k = _resolved_tiles(block_q, block_k)
    dq, dk, dv = _flash_backward(res, g, scale, causal, block_q, block_k)
    return dq, dk, dv


flash_attention.defvjp(_fa_fwd, _fa_bwd)


def flash_attention_sharded(q, k, v, causal=True, softmax_scale=None):
    """:func:`flash_attention` ([B, H, T, D]) as the dispatcher calls it:
    the plain kernel, or the kernel inside the ``shard_map`` that
    :func:`~deepspeed_tpu.ops.kernel_mesh.kernel_mesh_plan` asks for, with
    the batch over the data axes and the heads over tp where they divide.
    Attention never reduces across batch or heads, so no collective."""
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.ops.kernel_mesh import kernel_mesh_plan

    def kernel(qs, ks, vs):
        return flash_attention(qs, ks, vs, causal=causal,
                               softmax_scale=softmax_scale)

    plan = kernel_mesh_plan(q.shape[0], q.shape[1])
    if plan is None:
        return kernel(q, k, v)
    spec = P(plan.batch, plan.heads, None, None)
    return plan.shard_map(kernel, (spec, spec, spec), spec)(q, k, v)


def flash_attention_bthd_tp(q, k, v, causal=True, softmax_scale=None,
                            block_q=None, block_k=None, mesh=None,
                            axis=None, seq_axis=None):
    """TP- and SP-aware :func:`flash_attention_bthd`: heads (dim 2 of
    the [B, T, H, D] layout) partitioned over the ``tp`` mesh axis AND,
    when the mesh carries a live ``seq`` axis, tokens (dim 1)
    partitioned over it Ulysses-style (arXiv:2309.14509) — each shard
    runs the kernel (forward AND custom-vjp backward) on its local
    slice. Attention never reduces across heads, so tp emits no
    collective here; the head-sharded output feeds the row-parallel
    output projection, whose all-reduce the SpecLayout places.

    Sequence parallelism needs the FULL sequence inside the softmax, so
    the sp legs bracket the kernel with two seq-axis ``all_to_all``s:
    [B, T/sp, H/tp, D] → (split heads, concat tokens) →
    [B, T, H/(tp·sp), D] → kernel → (split tokens, concat heads) back.
    Both redistributions are linear, so autodiff transposes them to the
    mirror all_to_all in the backward pass. sp participates only when
    the post-tp head group divides by sp and the sequence divides by sp;
    with sp inactive the emitted program is the exact tp-only one (and
    on a one-device mesh, the plain kernel) — zero-overhead fallbacks
    pinned by the parity tests. Which ``shard_map`` (none, the whole
    mesh, or the axes an enclosing one left Auto) is
    :func:`~deepspeed_tpu.ops.kernel_mesh.kernel_mesh_plan`'s decision."""
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.ops.kernel_mesh import kernel_mesh_plan

    plan = kernel_mesh_plan(q.shape[0], q.shape[2], seqlen=q.shape[1],
                            mesh=mesh, axis=axis, seq_axis=seq_axis)
    sp_axis = plan.seq if plan is not None else None

    def local_attn(qs, ks, vs):
        if sp_axis:
            # Ulysses leg 1: trade local heads for the full sequence
            qs, ks, vs = (jax.lax.all_to_all(
                t, sp_axis, split_axis=2, concat_axis=1, tiled=True)
                for t in (qs, ks, vs))
        o = flash_attention_bthd(qs, ks, vs, causal=causal,
                                 softmax_scale=softmax_scale,
                                 block_q=block_q, block_k=block_k)
        if sp_axis:
            # Ulysses leg 2: give the sequence back, regain the heads
            o = jax.lax.all_to_all(o, sp_axis, split_axis=1,
                                   concat_axis=2, tiled=True)
        return o

    if plan is None:
        return local_attn(q, k, v)
    hs = P(plan.batch, plan.seq, plan.heads, None)
    return plan.shard_map(local_attn, (hs, hs, hs), hs)(q, k, v)
