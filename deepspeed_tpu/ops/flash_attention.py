"""Flash attention — Pallas TPU kernels (fwd + bwd).

Replaces the reference's fused CUDA attention path
(``csrc/transformer/softmax_kernels.cu``, ``transform_kernels.cu``,
``csrc/transformer/inference/csrc/softmax.cu``) with an online-softmax tiled
kernel: O(T) memory (never materializes the [T, T] score matrix), fp32
accumulation on the MXU, causal skipping inside the kernel.

Two sizes, not one (:func:`flash_plan` derives both from the shapes):

* what a grid step FETCHES: ``g`` rows of the folded batch*heads dim, a
  block of ``bq`` query rows and a panel of ``bk`` keys and values
  (``block_q`` / ``block_k`` bound them; at GPT-2's 1024 x 64 a row's whole
  K and V are 128 kB each, so the panel is the sequence and the k grid axis
  has one step). A block whose index does not change is not fetched again,
  and a panel the causal bound excludes is neither fetched nor walked.
* what a compute step WORKS ON: a ``ck x cq`` chunk of scores, 512 x 512
  where that divides the blocks. An in-kernel loop walks the chunks of one
  query chunk only as far as the causal diagonal: first the chunks wholly
  below it, unmasked, then the one it crosses; chunks above it are never
  touched. On the v5e the chunk is as large as VMEM lets it be and not
  register-sized: a matmul 512 wide feeds all four MXUs, 128 x 128 chunks
  in a loop fed one (2.3x slower, PERF.md PR 38). The tile, 128 x 128, is
  the unit of the diagonal instead: ``flash_fwd`` and ``flash_bwd_dkv``
  walk the chunk the diagonal crosses by key tiles, each against the
  queries from its own diagonal tile on, the mask on that tile alone (10
  of the chunk's 16 tiles run), with every cell's first matmuls issued
  before the softmax and the second ones after it, so that they are
  independent work. ``flash_bwd_dq`` masks the crossed chunk whole: by
  tiles it read no faster. The ``g`` rows of a grid step (two) go through
  every operation together, batched: the second row's matmuls are what
  runs under the first row's softmax.

Scores are held transposed, keys on sublanes and queries on lanes
(``s_t = k q^T``): the softmax state ``m``, ``l`` and the saved ``lse`` /
``delta`` are then lane rows ``[1, cq]`` and not 128-lane broadcasts, the
reductions over keys are elementwise across registers, and at head size 64
the accumulators ``[d, cq]`` fill their lanes. ``scale`` is folded into q
(forward, dq) or k (dk/dv) where that is exact in the input dtype, i.e. a
power of two (1/8 at head size 64), and stays on the float32 scores
elsewhere.

Layout: q, k, v are [batch, heads, seq, head_dim]. The grid is
(batch*heads / g, q blocks, k panels), k innermost; with more than one panel
the state crosses grid steps in VMEM scratch of its own width. Backward is
the standard two-kernel flash bwd (dq by rows, dk/dv by columns) using the
saved logsumexp and D = rowsum(dO * O). Exactly three kernels an attention
call: ``flash_fwd``, ``flash_bwd_dq``, ``flash_bwd_dkv``.
"""

import functools
import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.utils.compat import tpu_compiler_params

DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
NEG_INF = -1e30
TILE = 128                        # one lane tile: the unit of the diagonal
_CHUNKS = (512, 384, 256, 128)    # rows of a compute chunk, largest first
_VMEM_BUDGET = 10 * 1024 * 1024   # of the ~16 MiB scoped-vmem stack limit
_MAX_ROWS = 2                     # batch*heads rows a grid step works on at once

# batched over the g rows of a grid step: one operation for all of them
# keeps the kernel's program short, and the rows' matmuls side by side
_NT = (((2,), (2,)), ((0,), (0,)))   # [g, m, d] x [g, n, d] -> [g, m, n]
_NN = (((2,), (1,)), ((0,), (0,)))   # [g, m, k] x [g, k, n] -> [g, m, n]
_TN = (((1,), (1,)), ((0,), (0,)))   # [g, k, m] x [g, k, n] -> [g, m, n]


# ----------------------------------------------------------------------
# the schedule
class FlashPlan(NamedTuple):
    """What one attention call fetches, works on and skips; static in the
    shapes. The counts are per batch*heads row: ``tiles`` is what
    ``flash_fwd`` and ``flash_bwd_dkv`` run, in ``tile x tile`` tiles where
    they walk the diagonal by tiles; ``chunks`` is what ``flash_bwd_dq``
    runs, in ``ck x cq`` chunks, and what all three run where the plan has
    no tile."""
    g: int          # batch*heads rows a grid step
    bq: int         # query rows a grid step fetches
    bk: int         # keys / values a grid step fetches (the panel)
    cq: int         # query rows of a compute chunk
    ck: int         # keys of a compute chunk
    tile: int       # fwd, dk/dv walk a crossed chunk by tiles of this size (0: masked whole)
    chunks: Tuple[int, int, int]   # (unmasked, masked, skipped)
    tiles: Tuple[int, int, int]    # (unmasked, masked, skipped)

    def executed_share(self, kernel: str = "flash_fwd") -> float:
        """Share of the seq_q x seq_k square that ``kernel`` computes."""
        unmasked, masked, skipped = (
            self.chunks if kernel == "flash_bwd_dq" else self.tiles)
        return (unmasked + masked) / float(unmasked + masked + skipped)

    def describe(self) -> str:
        def runs(counts, unit):
            return (f"{counts[0]} unmasked + {counts[1]} masked {unit} run, "
                    f"{counts[2]} skipped")

        text = (f"g={self.g} fetch q{self.bq}/k{self.bk}: "
                f"{runs(self.chunks, f'{self.ck}x{self.cq} chunks')} "
                f"({100 * self.executed_share('flash_bwd_dq'):.1f}% of the "
                "square)")
        if self.tile:
            text += (f" in dq; fwd, dk/dv "
                     f"{runs(self.tiles, f'{self.tile}x{self.tile} tiles')} "
                     f"({100 * self.executed_share():.1f}%)")
        return text


def _fetched(seq, block):
    """Rows of one sequence axis a grid step fetches: the whole axis when
    it fits ``block``, else the largest divisor that whole tiles fill (a
    caller-passed sub-tile ``block`` is honoured as its own unit)."""
    if seq <= block:
        return seq
    unit = TILE if block >= TILE else 8
    for b in range(block - block % unit, 0, -unit):
        if seq % b == 0:
            return b
    raise ValueError(
        f"flash_attention requires a block of whole {unit}-row tiles that "
        f"divides the sequence: seq={seq}, block<={block}")


def _chunks(bq, bk):
    """(cq, ck): the largest chunk that divides both fetched blocks, so the
    causal diagonal crosses chunks corner to corner; a block that whole
    tiles do not fill is one chunk."""
    both = [c for c in _CHUNKS if bq % c == 0 and bk % c == 0]
    if both:
        return both[0], both[0]
    cq, ck = (next((c for c in _CHUNKS if b % c == 0), b) for b in (bq, bk))
    if max(cq, ck) > _CHUNKS[0]:
        raise ValueError(
            f"flash_attention: a block of {max(cq, ck)} rows that {TILE} "
            "does not divide is too large for one compute chunk")
    return cq, ck


def _vmem_row_bytes(bq, bk, d, itemsize, strided=False):
    """VMEM one batch*heads row takes in a grid step, the largest of the
    three kernels: double-buffered io blocks (dq: q, do, dq and k, v; dk/dv:
    q, do and k, v, dk, dv), the float32 state that crosses panels, and for
    the strided layout the swapped copy of every block. Single source for
    both layouts."""
    io = 2 * itemsize * d * (3 * bq + 4 * bk)
    state = 4 * (d + 16) * (bq + 2 * bk)
    return io * (2 if strided else 1) + state


def _causal_units(sq, sk, uq, uk, causal):
    """(unmasked, masked, skipped) units of ``uk x uq`` scores in the
    seq_q x seq_k square."""
    nq, nk = sq // uq, sk // uk
    if not causal:
        return nq * nk, 0, 0
    off = sk - sq
    unmasked = masked = 0
    for i in range(nq):
        full = min(max(i * uq + off + 1, 0) // uk, nk)
        run = min(max(i * uq + uq - 1 + off + uk, 0) // uk, nk)
        unmasked += full
        masked += run - full
    return unmasked, masked, nq * nk - unmasked - masked


def flash_plan(q_shape, k_shape, causal=True, dtype=jnp.bfloat16,
               block_q=None, block_k=None, layout="bhtd") -> FlashPlan:
    """The schedule of one attention call, from its shapes alone: ``g``,
    the fetched block and panel, the compute chunk, the tile the forward
    and dk/dv walk the diagonal by, and how many chunks and tiles run
    unmasked, run masked and are skipped. The drivers size themselves from it and the dispatcher
    logs it once a shape; raises ``ValueError`` (the reason
    :func:`flash_ineligible` gives) when no legal schedule exists.
    ``layout="bthd"`` ([B, T, H, D]) takes ``q_shape[2]`` as the head group
    one kernel call sees."""
    block_q, block_k = _resolved_tiles(block_q, block_k)
    itemsize = jnp.dtype(dtype).itemsize
    if layout == "bthd":
        _, sq, h, d = q_shape
        sk = k_shape[1]
        bq, bk, g = _bthd_tiles(sq, sk, h, d, block_q, block_k, itemsize)
    else:
        *lead, sq, d = q_shape
        sk = k_shape[-2]
        bq, bk = _fetched(sq, block_q), _fetched(sk, block_k)
        bh = math.prod(lead)
        per_row = _vmem_row_bytes(bq, bk, d, itemsize)
        g = max((g for g in range(1, min(bh, _MAX_ROWS) + 1)
                 if bh % g == 0 and g * per_row <= _VMEM_BUDGET), default=1)
    cq, ck = _chunks(bq, bk)
    # where the diagonal leaves every chunk it crosses at a corner, the
    # forward and dk/dv walk that chunk by key tiles, each from its own
    # diagonal tile on
    tile = 0
    if causal and cq == ck and (sk - sq) % cq == 0 and cq % TILE == 0:
        tile = TILE
    return FlashPlan(
        g, bq, bk, cq, ck, tile, _causal_units(sq, sk, cq, ck, causal),
        _causal_units(sq, sk, tile or cq, tile or ck, causal))


def _is_pow2(x: float) -> bool:
    return x > 0 and math.frexp(x)[0] == 0.5


# ----------------------------------------------------------------------
# what the three kernel bodies share
#
# The bodies are written in ``lax``: every program that calls the kernels
# traces them anew, and on a tracer every ``jnp`` function and operator is a
# nested jit of its own (a millisecond each on the benchmark's host, a
# second a program at these bodies' size, counted in its set-up).
def _for(lo, hi, body):
    """``body(i)`` for ``i`` in ``[lo, hi)``: a ``fori_loop`` (the bounds
    may be traced); a static single trip is inlined with a static index."""
    if isinstance(lo, int) and isinstance(hi, int) and hi - lo <= 1:
        if hi - lo == 1:
            body(lo)
        return
    lax.fori_loop(lo, hi, lambda i, c: body(i) or c, 0)


def _at(i, size, count, sub=0, rows=None):
    """Rows ``[sub, sub + rows)`` of chunk ``i`` of ``count`` chunks of
    ``size`` rows. A single chunk is addressed statically whatever the loop
    index is: its size need not be whole tiles, and only a static start is
    legal then; several chunks are whole tiles each."""
    rows = size if rows is None else rows
    if count == 1:
        return pl.ds(sub, rows)
    if isinstance(i, int):
        return pl.ds(i * size + sub, rows)
    return pl.ds(pl.multiple_of(lax.add(lax.mul(i, size), sub), TILE), rows)


def _chunks_below(x, size, count):
    """``clip(x, 0) // size`` capped at ``count``, on a traced scalar."""
    return lax.min(lax.div(lax.max(x, 0), size), count)


def _key_bounds(q0, kb, n_ck, *, cq, ck, off, causal):
    """For the query chunk at global row ``q0`` and the panel at global key
    ``kb``: key chunks ``[0, full)`` are wholly visible, ``[full, run)``
    are crossed by the diagonal."""
    if not causal:
        return n_ck, n_ck
    rel = lax.sub(q0, kb)
    return (_chunks_below(lax.add(rel, off + 1), ck, n_ck),
            _chunks_below(lax.add(rel, cq - 1 + off + ck), ck, n_ck))


def _query_bounds(k0, qb, n_cq, *, cq, ck, off, causal):
    """For the key chunk at global key ``k0`` and the query block at global
    row ``qb``: query chunks ``[first, full)`` are crossed by the diagonal,
    ``[full, n_cq)`` see the whole key chunk."""
    if not causal:
        return 0, 0
    rel = lax.sub(k0, qb)
    return (_chunks_below(lax.add(rel, -off), cq, n_cq),
            _chunks_below(lax.add(lax.max(lax.add(rel, ck - 1 - off), 0),
                                  cq - 1), cq, n_cq))


def _offset_mask(s, k0, q0, off):
    """Mask of a whole chunk the diagonal crosses: row i attends to cols
    <= i + off (bottom-right aligned, matching ``attention_reference``'s
    ``tril(k=k_len-q_len)``). ``s_t`` [g, keys, queries] holds the keys from
    ``k0`` on its sublanes and the queries from ``q0`` on its lanes."""
    rel = lax.sub(lax.broadcasted_iota(jnp.int32, s.shape, 2),
                  lax.broadcasted_iota(jnp.int32, s.shape, 1))
    first = lax.broadcast(lax.sub(lax.sub(k0, q0), off), s.shape)
    return lax.select(lax.ge(rel, first), s, lax.full_like(s, NEG_INF))


def _diagonal_mask(s):
    """Mask of a cell the diagonal enters at its top left corner: ``s_t``
    is [g, t, n * t], one key tile against the query tiles from its own on.
    Its first t lanes hold the tile the diagonal crosses (key row a is
    visible to query lane b iff a <= b); the lanes after it are wholly
    visible and pay nothing."""
    g, t, n_q = s.shape
    keep = lax.le(lax.broadcasted_iota(jnp.int32, (g, t, t), 1),
                  lax.broadcasted_iota(jnp.int32, (g, t, t), 2))
    first = lax.slice_in_dim(s, 0, t, axis=2)
    first = lax.select(keep, first, lax.full_like(first, NEG_INF))
    if n_q == t:
        return first
    return lax.concatenate([first, lax.slice_in_dim(s, t, n_q, axis=2)], 2)


def _cells(plan, off, crossed, k0, q0, by_tiles=True):
    """The cells one chunk is run as, each ``(key, n_keys, query, n_queries,
    mask, guard)``: the keys ``[key, key + n_keys)`` of the key chunk
    against the queries ``[query, query + n_queries)`` of the query chunk.
    A chunk the diagonal does not cross is one unmasked cell. One it
    crosses is walked by key tiles where the plan has a tile and the kernel
    asks for it (each key tile against the queries from its own diagonal
    tile on and none before, the mask on that tile alone) and is one cell
    masked whole elsewhere; ``k0`` and ``q0``, the global key and query row
    the chunk starts at, place that mask."""
    cq, ck, tile = plan.cq, plan.ck, plan.tile
    if not crossed:
        return [(0, ck, 0, cq, None, False)]
    if tile and by_tiles:
        return [(a, tile, a, cq - a, _diagonal_mask, False)
                for a in range(0, ck, tile)]
    mask = functools.partial(_offset_mask, k0=k0, q0=q0, off=off)
    return [(0, ck, 0, cq, mask, off < 0)]


def _over_keys(row, like):
    """A per-query row [g, 1, n] against every key (or feature) of ``like``
    [g, m, n]."""
    return lax.broadcast_in_dim(row, like.shape, (0, 1, 2))


def _key_max(x):
    return lax.expand_dims(lax.reduce_max(x, (1,)), (1,))    # [g, 1, n]


def _key_sum(x):
    return lax.expand_dims(lax.reduce_sum(x, (1,)), (1,))    # [g, 1, n]


def _scaled(x, scale, fold):
    """``x * scale`` where this operand takes the scale (``fold``: exact in
    its dtype, a power of two) and ``x`` elsewhere."""
    return lax.mul(x, x.dtype.type(scale)) if fold else x


def _scores_t(k, q, scale, mask):
    """``s_t = k q^T`` in float32, [g, keys, queries]; ``scale`` is None
    when it was folded into an operand."""
    s = lax.dot_general(k, q, _NT, preferred_element_type=jnp.float32)
    if scale is not None:
        s = lax.mul(s, s.dtype.type(scale))
    return s if mask is None else mask(s)


def _probs_t(s, row, guard):
    """exp(s_t - row). ``guard``: force masked entries to 0 where a row may
    be fully masked (seq_q > seq_k with causal, in a chunk masked whole):
    there ``row`` stays NEG_INF and exp(s - row) would be exp(0) = 1 per
    masked col. Elsewhere exp(NEG_INF - finite) = 0 does it."""
    p = lax.exp(lax.sub(s, _over_keys(row, s)))
    if guard:
        p = lax.select(lax.gt(s, lax.full_like(s, NEG_INF * 0.5)), p,
                       lax.full_like(p, 0.0))
    return p


# ----------------------------------------------------------------------
# forward
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, causal, plan, num_kb, off):
    g, bq, bk, cq, ck = plan[:5]
    n_cq, n_ck = bq // cq, bk // ck
    qi, ki = pl.program_id(1), pl.program_id(2)
    qb, kb = lax.mul(qi, bq), lax.mul(ki, bk)
    fold = _is_pow2(scale)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, m_scr.dtype)
        l_scr[...] = jnp.zeros(l_scr.shape, l_scr.dtype)
        acc_scr[...] = jnp.zeros(acc_scr.shape, acc_scr.dtype)

    def q_chunk(i):
        at = _at(i, cq, n_cq)
        q0 = lax.add(qb, lax.mul(i, cq))

        def key_chunk(j, crossed):
            cells = _cells(plan, off, crossed, lax.add(kb, lax.mul(j, ck)), q0)
            w = cells[-1][3]        # query tile: the last cell's queries
            # exact: a power of two in the input dtype
            q = _scaled(q_ref[:, at, :], scale, fold)     # [g, cq, d]
            # every cell's QK^T before the softmax, every PV after it:
            # independent matmuls for the four MXUs
            st = {}         # st[c, b]: the scores of cell c at query tile b
            for c, (key, n_keys, query, _, mask, _) in enumerate(cells):
                s = _scores_t(k_ref[:, _at(j, ck, n_ck, key, n_keys), :],
                              lax.slice_in_dim(q, query, cq, axis=1),
                              None if fold else scale, mask)
                for b in range(c, len(cells)):
                    st[c, b] = lax.slice_in_dim(
                        s, (b - c) * w, (b - c + 1) * w, axis=2)
            for b in range(len(cells)):
                lanes = _at(i, cq, n_cq, b * w, w)
                m = m_scr[:, :, lanes]               # [g, 1, w]
                m_new = functools.reduce(
                    lax.max, [m] + [_key_max(st[c, b]) for c in range(b + 1)])
                for c in range(b + 1):
                    st[c, b] = _probs_t(st[c, b], m_new, cells[c][5])
                alpha = lax.exp(lax.sub(m, m_new))
                l_scr[:, :, lanes] = functools.reduce(
                    lax.add, [lax.mul(alpha, l_scr[:, :, lanes])]
                    + [_key_sum(st[c, b]) for c in range(b + 1)])
                m_scr[:, :, lanes] = m_new
                acc = acc_scr[:, :, lanes]
                acc_scr[:, :, lanes] = lax.mul(acc, _over_keys(alpha, acc))
            # multiply at input precision (bf16 on the MXU's native rate),
            # accumulate fp32 — the flash-attention standard
            for c, (key, n_keys, query, n_queries, _, _) in enumerate(cells):
                v = v_ref[:, _at(j, ck, n_ck, key, n_keys), :]
                p = [st[c, b] for b in range(c, len(cells))]
                p = p[0] if len(p) == 1 else lax.concatenate(p, 2)
                lanes = _at(i, cq, n_cq, query, n_queries)
                acc_scr[:, :, lanes] = lax.add(
                    acc_scr[:, :, lanes], lax.dot_general(
                        v, lax.convert_element_type(p, v.dtype), _TN,
                        preferred_element_type=jnp.float32))  # [g, d, n_q]

        full, run = _key_bounds(q0, kb, n_ck, cq=cq, ck=ck, off=off,
                                causal=causal)
        _for(0, full, lambda j: key_chunk(j, False))
        if causal:
            _for(full, run, lambda j: key_chunk(j, True))

        @pl.when(ki == num_kb - 1)
        def _finish():
            for r in range(g):
                l = l_scr[r, :, at]
                safe_l = jnp.where(l == 0.0, 1.0, l)
                o_ref[r, at, :] = (acc_scr[r, :, at] / safe_l).T.astype(
                    o_ref.dtype)
                lse_ref[r, :, at] = m_scr[r, :, at] + jnp.log(safe_l)

    _for(0, n_cq, q_chunk)


def _clamp_panel(causal, bq, bk, off):
    """Index of the k panel a grid step fetches: its own, or (causal) the
    last one its query block needs, so a skipped step re-fetches nothing."""
    if not causal:
        return lambda qi, ki: ki
    return lambda qi, ki: jnp.minimum(
        ki, jnp.maximum(qi * bq + bq - 1 + off, 0) // bk)


def _flash_forward(q, k, v, scale, causal, block_q, block_k):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    plan = flash_plan(q.shape, k.shape, causal, q.dtype, block_q, block_k)
    g, bq, bk = plan.g, plan.bq, plan.bk
    num_kb = sk // bk
    bh = b * h
    off = sk - sq
    grid = (bh // g, sq // bq, num_kb)
    panel = _clamp_panel(causal, bq, bk, off)

    qs = pl.BlockSpec((g, bq, d), lambda bhi, qi, ki: (bhi, qi, 0),
                      memory_space=pltpu.VMEM)
    ks = pl.BlockSpec((g, bk, d), lambda bhi, qi, ki: (bhi, panel(qi, ki), 0),
                      memory_space=pltpu.VMEM)
    ls = pl.BlockSpec((g, 1, bq), lambda bhi, qi, ki: (bhi, 0, qi),
                      memory_space=pltpu.VMEM)

    q3 = q.reshape(bh, sq, d)
    k3 = k.reshape(bh, sk, d)
    v3 = v.reshape(bh, sk, d)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               plan=plan, num_kb=num_kb, off=off)
    o, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=grid,
        in_specs=[qs, ks, ks],
        out_specs=(qs, ls),
        out_shape=(
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32),
        ),
        scratch_shapes=_fwd_state(g, bq, d),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(q3, k3, v3)
    return o.reshape(b, h, sq, d), lse.reshape(b, h, sq)


def _fwd_state(g, bq, d):
    """m, l, acc^T of a query block, crossing its k panels."""
    return [pltpu.VMEM((g, 1, bq), jnp.float32),
            pltpu.VMEM((g, 1, bq), jnp.float32),
            pltpu.VMEM((g, d, bq), jnp.float32)]


# ----------------------------------------------------------------------
# backward
def _grads_t(s, dp, lse, delta, scale, guard):
    """(p_t, ds_t) [g, keys, queries] from the scores, ``dp_t = v do^T`` and
    the saved per-query rows; ``scale`` is None when it was folded."""
    p = _probs_t(s, lse, guard)
    ds = lax.mul(p, lax.sub(dp, _over_keys(delta, dp)))
    return p, ds if scale is None else lax.mul(ds, ds.dtype.type(scale))


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_scr, *, scale, causal, plan, num_kb, off):
    g, bq, bk, cq, ck = plan[:5]
    n_cq, n_ck = bq // cq, bk // ck
    qi, ki = pl.program_id(1), pl.program_id(2)
    qb, kb = lax.mul(qi, bq), lax.mul(ki, bk)
    fold = _is_pow2(scale)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros(dq_scr.shape, dq_scr.dtype)

    def q_chunk(i):
        at = _at(i, cq, n_cq)
        q0 = lax.add(qb, lax.mul(i, cq))

        def key_chunk(j, crossed):
            # dq masks a crossed chunk whole: walked by tiles its three
            # matmuls push 2.5 times the weight tiles for 5/8 of the rows
            # and read the same 0.43 ms a call (PERF.md, PR 38)
            (_, _, _, _, mask, guard), = _cells(
                plan, off, crossed, lax.add(kb, lax.mul(j, ck)), q0,
                by_tiles=False)
            keys = _at(j, ck, n_ck)
            k = k_ref[:, keys, :]
            s = _scores_t(k, _scaled(q_ref[:, at, :], scale, fold),
                          None if fold else scale, mask)     # [g, ck, cq]
            dp = lax.dot_general(v_ref[:, keys, :], do_ref[:, at, :], _NT,
                                 preferred_element_type=jnp.float32)
            _, ds = _grads_t(s, dp, lse_ref[:, :, at], delta_ref[:, :, at],
                             None if fold else scale, guard)
            dq_scr[:, :, at] = lax.add(dq_scr[:, :, at], lax.dot_general(
                k, lax.convert_element_type(ds, k.dtype), _TN,
                preferred_element_type=jnp.float32))         # [g, d, cq]

        full, run = _key_bounds(q0, kb, n_ck, cq=cq, ck=ck, off=off,
                                causal=causal)
        _for(0, full, lambda j: key_chunk(j, False))
        if causal:
            _for(full, run, lambda j: key_chunk(j, True))

        @pl.when(ki == num_kb - 1)
        def _finish():
            for r in range(g):
                dq_t = _scaled(dq_scr[r, :, at], scale, fold)
                dq_ref[r, at, :] = dq_t.T.astype(dq_ref.dtype)

    _for(0, n_cq, q_chunk)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr,
                    *, scale, causal, plan, num_qb, off):
    g, bq, bk, cq, ck = plan[:5]
    n_cq, n_ck = bq // cq, bk // ck
    ki, qi = pl.program_id(1), pl.program_id(2)
    qb, kb = lax.mul(qi, bq), lax.mul(ki, bk)
    fold = _is_pow2(scale)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, dk_scr.dtype)
        dv_scr[...] = jnp.zeros(dv_scr.shape, dv_scr.dtype)

    def k_chunk(j):
        at = _at(j, ck, n_ck)
        k0 = lax.add(kb, lax.mul(j, ck))

        def query_chunk(i, crossed):
            k = _scaled(k_ref[:, at, :], scale, fold)        # [g, ck, d]
            v = v_ref[:, at, :]
            dvs, dks = [], []
            for key, n_keys, query, n_queries, mask, guard in _cells(
                    plan, off, crossed, k0, lax.add(qb, lax.mul(i, cq))):
                rows = _at(i, cq, n_cq, query, n_queries)
                q, do = q_ref[:, rows, :], do_ref[:, rows, :]
                s = _scores_t(
                    lax.slice_in_dim(k, key, key + n_keys, axis=1), q,
                    None if fold else scale, mask)   # [g, n_keys, n_queries]
                dp = lax.dot_general(
                    lax.slice_in_dim(v, key, key + n_keys, axis=1), do, _NT,
                    preferred_element_type=jnp.float32)
                p, ds = _grads_t(s, dp, lse_ref[:, :, rows],
                                 delta_ref[:, :, rows],
                                 None if fold else scale, guard)
                dvs.append(lax.dot_general(
                    lax.convert_element_type(p, do.dtype), do, _NN,
                    preferred_element_type=jnp.float32))     # [g, n_keys, d]
                dks.append(lax.dot_general(
                    lax.convert_element_type(ds, q.dtype), q, _NN,
                    preferred_element_type=jnp.float32))
            # the cells hold distinct keys: side by side, one update
            for scr, parts in ((dv_scr, dvs), (dk_scr, dks)):
                new = parts[0] if len(parts) == 1 else lax.concatenate(
                    parts, 1)
                scr[:, at, :] = lax.add(scr[:, at, :], new)

        first, full = _query_bounds(k0, qb, n_cq, cq=cq, ck=ck, off=off,
                                    causal=causal)
        if causal:
            _for(first, full, lambda i: query_chunk(i, True))
        _for(full, n_cq, lambda i: query_chunk(i, False))

        @pl.when(qi == num_qb - 1)
        def _finish():
            dk = _scaled(dk_scr[:, at, :], scale, fold)
            dk_ref[:, at, :] = dk.astype(dk_ref.dtype)
            dv_ref[:, at, :] = dv_scr[:, at, :].astype(dv_ref.dtype)

    _for(0, n_ck, k_chunk)


def _flash_backward(res, g, scale, causal, block_q, block_k):
    q, k, v, o, lse = res
    b, h, sq, d = q.shape
    sk = k.shape[2]
    plan = flash_plan(q.shape, k.shape, causal, q.dtype, block_q, block_k)
    gg, bq, bk = plan.g, plan.bq, plan.bk
    num_qb, num_kb = sq // bq, sk // bk
    bh = b * h
    off = sk - sq

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

    panel = _clamp_panel(causal, bq, bk, off)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          plan=plan, num_kb=num_kb, off=off),
        name="flash_bwd_dq",
        grid=(bh // gg, num_qb, num_kb),
        in_specs=[
            _spec((bq, d), lambda bhi, qi, ki: (bhi, qi, 0)),
            _spec((bk, d), lambda bhi, qi, ki: (bhi, panel(qi, ki), 0)),
            _spec((bk, d), lambda bhi, qi, ki: (bhi, panel(qi, ki), 0)),
            _spec((bq, d), lambda bhi, qi, ki: (bhi, qi, 0)),
            _spec((1, bq), lambda bhi, qi, ki: (bhi, 0, qi)),
            _spec((1, bq), lambda bhi, qi, ki: (bhi, 0, qi)),
        ],
        out_specs=_spec((bq, d), lambda bhi, qi, ki: (bhi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((gg, d, bq), jnp.float32)],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(q3, k3, v3, do3, lse3, delta3)

    first = _first_block(causal, bq, bk, off, num_qb)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          plan=plan, num_qb=num_qb, off=off),
        name="flash_bwd_dkv",
        grid=(bh // gg, num_kb, num_qb),
        in_specs=[
            _spec((bq, d), lambda bhi, ki, qi: (bhi, first(ki, qi), 0)),
            _spec((bk, d), lambda bhi, ki, qi: (bhi, ki, 0)),
            _spec((bk, d), lambda bhi, ki, qi: (bhi, ki, 0)),
            _spec((bq, d), lambda bhi, ki, qi: (bhi, first(ki, qi), 0)),
            _spec((1, bq), lambda bhi, ki, qi: (bhi, 0, first(ki, qi))),
            _spec((1, bq), lambda bhi, ki, qi: (bhi, 0, first(ki, qi))),
        ],
        out_specs=(
            _spec((bk, d), lambda bhi, ki, qi: (bhi, ki, 0)),
            _spec((bk, d), lambda bhi, ki, qi: (bhi, ki, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
        ),
        scratch_shapes=[pltpu.VMEM((gg, bk, d), jnp.float32)] * 2,
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(q3, k3, v3, do3, lse3, delta3)

    return (dq.reshape(b, h, sq, d), dk.reshape(b, h, sk, d),
            dv.reshape(b, h, sk, d))


def _first_block(causal, bq, bk, off, num_qb):
    """Index of the query block a dk/dv grid step fetches: its own, or
    (causal) the first one that reaches its key block's diagonal."""
    if not causal:
        return lambda ki, qi: qi
    return lambda ki, qi: jnp.maximum(
        qi, jnp.minimum(jnp.maximum(ki * bk - off, 0) // bq, num_qb - 1))


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
# cheap VMEM-local swap, once a grid step, presents them to the SAME kernel
# bodies as [g, bq, d], which then index rows and chunks of them as they do
# of the folded layout's blocks.

class _Swapped:
    """A [1, rows, g, d] (or [1, 1, g, rows]) block ref behind the kernels'
    [g, rows, d] ([g, 1, rows]) indexing. The swap is made once a grid step
    into VMEM scratch of the kernels' layout; every read and write of the
    bodies (a row, a chunk of it) indexes that; :meth:`flush` swaps an
    output back into its block."""

    def __init__(self, ref, scr, load=True):
        self._ref, self._scr = ref, scr
        if load:
            scr[...] = ref[...][0].swapaxes(0, 1)

    def __getitem__(self, idx):
        return self._scr[idx]

    def __setitem__(self, idx, val):
        self._scr[idx] = val

    def flush(self):
        self._ref[...] = self._scr[...].swapaxes(0, 1)[None]

    @property
    def shape(self):
        return self._scr.shape

    @property
    def dtype(self):
        return self._scr.dtype


def _head_group(h: int, bq: int, bk: int, d: int, itemsize: int = 2) -> int:
    """Heads per grid step for the strided layout: the folded layout's VMEM
    model (with the swapped copies), but the group is the block's
    second-to-last dim, so Pallas additionally requires it be a multiple of
    8 OR the full head count (the folded layout has no such constraint —
    its head dim is the leading block dim). Returns 0 when no legal group
    fits the budget — ``_bthd_tiles`` then shrinks the seq tiles and
    retries, raising ValueError when nothing legal exists (the dispatcher
    asks :func:`flash_ineligible` first and takes the folded kernel)."""
    per_row = _vmem_row_bytes(bq, bk, d, itemsize, strided=True)
    for g in (h, 16, 8):
        if g % 8 == 0 or g == h:
            if h % g == 0 and g * per_row <= _VMEM_BUDGET:
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


def _bthd_tiles(sq, sk, h, d, block_q, block_k, itemsize=2):
    """(bq, bk, g) for the strided layout: shrink the fetched seq tiles
    (128 floor by default; an explicitly sub-128 ``block_q``/``block_k`` is
    its own floor) until a Pallas-legal head group — a multiple of 8, or
    all ``h`` heads — fits the VMEM budget. Walks the full divisor lattice,
    largest tiles first, shrinking the larger of the two (keeps tiles
    squarish). Deterministic in its static args, so the fwd and bwd
    drivers always agree."""
    # the walk owns divisibility (768 at a 512 block holds legal tiles
    # 384/256/192/128); the full-seq tile is the always-legal fallback.
    # a tile is whole lane tiles of the lse block (or whole caller-passed
    # sub-128 blocks), or the full sequence
    bq0, bk0 = min(block_q, sq), min(block_k, sk)
    qd = [t for t in _tile_divisors(sq, bq0)
          if t % min(TILE, bq0) == 0 or t == sq] or [sq]
    kd = [t for t in _tile_divisors(sk, bk0)
          if t % min(TILE, bk0) == 0 or t == sk] or [sk]
    i = j = 0
    while True:
        g = _head_group(h, qd[i], kd[j], d, itemsize)
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


def _swap_scratch(g, d, dtype, rows):
    """VMEM for the swapped copy of each block: ``rows`` names, in kernel
    argument order, the sequence extent of a [g, rows, d] block, or
    ``("row", n)`` for a float32 [g, 1, n] lse / delta block."""
    return [pltpu.VMEM((g, 1, r[1]), jnp.float32) if isinstance(r, tuple)
            else pltpu.VMEM((g, r, d), dtype) for r in rows]


def _on_swapped(body, n_in, n_out):
    """``body`` (a kernel of the folded layout) as a kernel of the strided
    one: the first ``n_in + n_out`` scratch buffers hold the swapped blocks,
    the rest is the body's own state."""
    n = n_in + n_out

    def kernel(*refs, **kw):
        blocks, scratch = refs[:n], refs[n:]
        views = [_Swapped(ref, scr, load=i < n_in)
                 for i, (ref, scr) in enumerate(zip(blocks, scratch[:n]))]
        body(*views, *scratch[n:], **kw)
        for view in views[n_in:]:
            view.flush()

    return kernel


def _flash_forward_bthd(q, k, v, scale, causal, block_q, block_k):
    b, sq, h, d = q.shape
    sk = k.shape[1]
    plan = flash_plan(q.shape, k.shape, causal, q.dtype, block_q, block_k,
                      layout="bthd")
    g, bq, bk = plan.g, plan.bq, plan.bk
    num_kb = sk // bk
    hpg = h // g
    off = sk - sq
    grid = (b * hpg, sq // bq, num_kb)
    panel = _clamp_panel(causal, bq, bk, off)

    def qspec(bhi, qi, ki):
        return (bhi // hpg, qi, bhi % hpg, 0)

    def kspec(bhi, qi, ki):
        return (bhi // hpg, panel(qi, ki), bhi % hpg, 0)

    qs = pl.BlockSpec((1, bq, g, d), qspec, memory_space=pltpu.VMEM)
    ks = pl.BlockSpec((1, bk, g, d), kspec, memory_space=pltpu.VMEM)
    ls = pl.BlockSpec((1, 1, g, bq),
                      lambda bhi, qi, ki: (bhi // hpg, bhi % hpg, 0, qi),
                      memory_space=pltpu.VMEM)
    kernel = functools.partial(_on_swapped(_fwd_kernel, 3, 2), scale=scale,
                               causal=causal, plan=plan, num_kb=num_kb,
                               off=off)
    o, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=grid,
        in_specs=[qs, ks, ks],
        out_specs=(qs, ls),
        out_shape=(
            jax.ShapeDtypeStruct((b, sq, h, d), q.dtype),
            jax.ShapeDtypeStruct((b, hpg, g, sq), jnp.float32),
        ),
        scratch_shapes=(
            _swap_scratch(g, d, q.dtype, (bq, bk, bk, bq, ("row", bq)))
            + _fwd_state(g, bq, d)),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(q, k, v)
    return o, lse


def _flash_backward_bthd(res, dout, scale, causal, block_q, block_k):
    q, k, v, o, lse = res  # lse: [b, hpg, g, sq]
    b, sq, h, d = q.shape
    sk = k.shape[1]
    plan = flash_plan(q.shape, k.shape, causal, q.dtype, block_q, block_k,
                      layout="bthd")
    g, bq, bk = plan.g, plan.bq, plan.bk
    num_qb, num_kb = sq // bq, sk // bk
    hpg = h // g
    off = sk - sq

    # D = rowsum(dO * O): [b, sq, h] -> the lse tiling [b, hpg, g, sq]
    delta = jnp.sum(dout.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)
    delta = delta.transpose(0, 2, 1).reshape(b, hpg, g, sq)

    panel = _clamp_panel(causal, bq, bk, off)

    def qmap(bhi, qi, ki):
        return (bhi // hpg, qi, bhi % hpg, 0)

    def kmap(bhi, qi, ki):
        return (bhi // hpg, panel(qi, ki), bhi % hpg, 0)

    def lmap(bhi, qi, ki):
        return (bhi // hpg, bhi % hpg, 0, qi)

    qs = pl.BlockSpec((1, bq, g, d), qmap, memory_space=pltpu.VMEM)
    ks = pl.BlockSpec((1, bk, g, d), kmap, memory_space=pltpu.VMEM)
    ls = pl.BlockSpec((1, 1, g, bq), lmap, memory_space=pltpu.VMEM)
    row = ("row", bq)

    dq = pl.pallas_call(
        functools.partial(_on_swapped(_bwd_dq_kernel, 6, 1), scale=scale,
                          causal=causal, plan=plan, num_kb=num_kb, off=off),
        name="flash_bwd_dq",
        grid=(b * hpg, num_qb, num_kb),
        in_specs=[qs, ks, ks, qs, ls, ls],
        out_specs=qs,
        out_shape=jax.ShapeDtypeStruct((b, sq, h, d), q.dtype),
        scratch_shapes=(
            _swap_scratch(g, d, q.dtype, (bq, bk, bk, bq, row, row, bq))
            + [pltpu.VMEM((g, d, bq), jnp.float32)]),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(q, k, v, dout, lse, delta)

    first = _first_block(causal, bq, bk, off, num_qb)

    def kmap2(bhi, ki, qi):
        return (bhi // hpg, ki, bhi % hpg, 0)

    def qmap2(bhi, ki, qi):
        return (bhi // hpg, first(ki, qi), bhi % hpg, 0)

    def lmap2(bhi, ki, qi):
        return (bhi // hpg, bhi % hpg, 0, first(ki, qi))

    qs2 = pl.BlockSpec((1, bq, g, d), qmap2, memory_space=pltpu.VMEM)
    ks2 = pl.BlockSpec((1, bk, g, d), kmap2, memory_space=pltpu.VMEM)
    ls2 = pl.BlockSpec((1, 1, g, bq), lmap2, memory_space=pltpu.VMEM)
    dk, dv = pl.pallas_call(
        functools.partial(_on_swapped(_bwd_dkv_kernel, 6, 2), scale=scale,
                          causal=causal, plan=plan, num_qb=num_qb, off=off),
        name="flash_bwd_dkv",
        grid=(b * hpg, num_kb, num_qb),
        in_specs=[qs2, ks2, ks2, qs2, ls2, ls2],
        out_specs=(ks2, ks2),
        out_shape=(
            jax.ShapeDtypeStruct((b, sk, h, d), k.dtype),
            jax.ShapeDtypeStruct((b, sk, h, d), v.dtype),
        ),
        scratch_shapes=(
            _swap_scratch(g, d, q.dtype, (bq, bk, bk, bq, row, row, bk, bk))
            + [pltpu.VMEM((g, bk, d), jnp.float32)] * 2),
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


def flash_ineligible(q_shape, k_shape, layout, block_q=None, block_k=None,
                     dtype=jnp.bfloat16):
    """Why the kernel cannot serve these shapes, or ``None`` when it can.

    The dispatchers ask this BEFORE calling the kernel, so the XLA path is
    taken by a decision made from the shapes (and counted,
    ``ops.attention.dispatch_counts``) and never by catching what the
    kernel raises. ``layout``: ``"bhtd"`` (folded, [B, H, T, D]) or
    ``"bthd"`` (strided, [B, T, H, D], judged on the head group one shard
    of :func:`flash_attention_bthd_tp` hands the kernel). The tile rules
    themselves live in :func:`flash_plan`, which the drivers call again;
    what it raises is the reason."""
    try:
        if layout == "bthd":
            from deepspeed_tpu.ops.kernel_mesh import kernel_mesh_plan

            batch, seq_q, heads, head_dim = q_shape
            plan = kernel_mesh_plan(batch, heads, seqlen=seq_q)
            if plan is not None:
                heads //= plan.size(plan.heads) * plan.size(plan.seq)
            q_shape = (batch, seq_q, heads, head_dim)
        flash_plan(q_shape, k_shape, True, dtype, block_q, block_k, layout)
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
