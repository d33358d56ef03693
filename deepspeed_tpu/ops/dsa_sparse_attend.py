"""Latent attention over the keys a selection CHOSE (DeepSeek-V3.2's
sparse attention): softmax over ``S_t`` only, ``S_t`` the indexer's
(``ops/dsa_index_select.py``).

- DECODE, ABSORBED OVER GATHERED ROWS (:func:`attend_chosen_rows`). With
  ``W_kvb`` folded into the query and the output
  (``models/deepseek_v2.py``) a query's heads all read the SAME latent
  rows, and keys and values are the same bytes, read once
  (``ops/latent_decode_attention.py``); here the rows are not a sequence's
  consecutive blocks but the ``k`` positions the selection chose, each
  found through the block table: position ``j`` lies at row ``table[j //
  block_size] * block_size + j % block_size`` of the layer's pool. XLA:
  one gather of ``k`` rows a query (1,280 B each), two einsums over them.
- A CHUNK, DECOMPRESSED UNDER THE SELECTION'S MASK: every live key is
  taken through ``W_kvb`` and scored as a dense chunk would, and a query's
  unchosen keys are masked before the softmax: exact, and the set never
  becomes positions (a chunk's 512 x 2,048 chosen rows gathered would be
  1.3 GB a layer, a million copies of 1,280 B). On a TPU the Pallas kernel
  ``dsa_sparse_attend`` (:func:`attend_masked`): a grid step is a group of
  heads x a tile of keys; the tile's latent rows go through the group's
  slice of ``W_kvb`` in VMEM, every head of the group scores the chunk's
  queries against them, the mask comes as a bias (0 or -1e30) and the
  softmax is online over the tiles, as many as the row has live keys. In
  XLA the same tile's ``[heads, queries, keys]`` float32 scores are written
  to HBM and read back three times: 2.3 ms a tile of 1,024 keys a layer at
  128 heads against the matrix unit's 0.4 (my chip run, PR 59).
  Elsewhere :func:`mask_tile` hands the model's tile loop
  (``models/deepseek_v32.py``) a tile of the packed mask; that loop and its
  online softmax are the dense latent models' own.
"""

import functools

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.dsa_index_select import UNCHOSEN, unpack_bits

_NEG = UNCHOSEN
# the kernel's tiles: keys a grid step, heads a group
KERNEL_KEYS = 512
KERNEL_HEADS = 8


def pool_rows_of(positions, table, block_size: int):
    """``positions [B, K]`` of a sequence (-1: none) -> the rows of one
    layer's pool (``[blocks * block_size, lanes]``) they lie at, through
    ``table [B, blocks a sequence]``; none stays -1."""
    at = jnp.maximum(positions, 0)
    block = jnp.take_along_axis(table, at // block_size, axis=1)
    return jnp.where(positions >= 0, block * block_size + at % block_size,
                     -1)


def attend_chosen_rows(q_full, pool, layer, rows, *, rank: int,
                       scale: float):
    """``q_full [B, H, lanes]`` (``[q_nope W_K | q_pe | zeros]`` a head)
    over the rows ``rows [B, K]`` (-1: none) of layer ``layer`` of ``pool
    [layers, blocks, block_size, lanes]`` -> ``[B, H, rank]``: softmax of
    the chosen rows' scores in float32, the values their first ``rank``
    lanes."""
    layers, blocks, bs, lanes = pool.shape
    chosen = rows >= 0
    with jax.named_scope("dsa_sparse_attend.gather"):
        flat = pool.reshape(layers * blocks * bs, lanes)
        got = flat[layer * blocks * bs + jnp.maximum(rows, 0)]
        # an unchosen place weighs 0, and 0 x whatever block 0 holds (NaN
        # included) must stay 0
        got = jnp.where(chosen[..., None], got, jnp.zeros_like(got))
    with jax.named_scope("dsa_sparse_attend.attend"):
        s = jnp.einsum("bhc,bkc->bhk", q_full, got,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(chosen[:, None], s, _NEG)
        p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(chosen[:, None], p, 0.0)
        total = jnp.sum(p, axis=-1, keepdims=True)
        out = jnp.einsum("bhk,bkc->bhc", p.astype(got.dtype),
                         got[..., :rank], preferred_element_type=jnp.float32)
        return (out / jnp.where(total == 0.0, 1.0, total)).astype(
            q_full.dtype)


def mask_tile(mask, j, tile: int):
    """Keys ``[j * tile, (j + 1) * tile)`` of a packed mask ``[.., words]``
    (``dsa_index_select.pack_bits``) -> ``[.., tile]`` bool; ``tile`` whole
    words."""
    words = jax.lax.dynamic_slice_in_dim(mask, j * (tile // 32), tile // 32,
                                         mask.ndim - 1)
    return unpack_bits(words)


# ---------------------------------------------------------------------------
# the chunk's kernel

def kernel_serves(queries: int, heads: int, nope: int, rope: int, dv: int,
                  rank: int, lanes: int, keys: int) -> bool:
    """Whether :func:`attend_masked` can take a chunk: a TPU (or the
    interpreter forced), whole registers everywhere, the rotated key the
    row's last register."""
    from deepspeed_tpu.ops.attention import use_decode_kernel

    return (use_decode_kernel() and queries % 128 == 0
            and heads % KERNEL_HEADS == 0 and nope % 128 == 0
            and dv % 128 == 0 and rank % 128 == 0 and rope <= 128
            and lanes == rank + 128 and keys % KERNEL_KEYS == 0)


def _masked_kernel(tiles_ref, qn_ref, qp_ref, rows_ref, w_ref, bias_ref,
                   o_ref, m_scr, l_scr, acc_scr, *, group, rank, nope, dv,
                   scale):
    from jax.experimental import pallas as pl

    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full(m_scr.shape, _NEG, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when(j < tiles_ref[0])
    def _():
        rows = rows_ref[...]
        # the tile's keys and values by heads, this group's: never in HBM
        kv = jnp.dot(rows[:, :rank], w_ref[0],
                     preferred_element_type=jnp.float32).astype(rows.dtype)
        k_pe = rows[:, rank:]
        bias = bias_ref[...].astype(jnp.float32)
        last = (((1,), (1,)), ((), ()))
        for h in range(group):
            at = h * (nope + dv)
            s = (jax.lax.dot_general(qn_ref[h], kv[:, at:at + nope], last,
                                     preferred_element_type=jnp.float32)
                 + jax.lax.dot_general(qp_ref[h], k_pe, last,
                                       preferred_element_type=jnp.float32))
            s = s * scale + bias
            m = m_scr[h]
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            # (an unchosen key's exp(-1e30 - m) is 0 once a chosen one has
            # set m; what gathered before that is wiped by alpha = 0)
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l_scr[h] = alpha * l_scr[h] + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[h] = alpha * acc_scr[h] + jnp.dot(
                p.astype(rows.dtype), kv[:, at + nope:at + nope + dv],
                preferred_element_type=jnp.float32)
            m_scr[h] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        total = l_scr[...]
        o_ref[...] = (acc_scr[...] / jnp.where(total == 0.0, 1.0, total)
                      ).astype(o_ref.dtype)


def attend_masked(q_nope, q_pe, rows, w_kvb, mask, live_keys, *,
                  scale: float, bias=None):
    """One row's chunk: ``q_nope [T, H, nope]``, ``q_pe [T, H, rope]``
    (rotated) over the sequence's latent rows as they lie side by side
    (``rows [S, lanes]``, ``[c | k_pe | zeros]``: gathered through the
    block table by the caller), ``w_kvb [rank, H, nope + dv]``, ``mask [T,
    S / 32]`` the selection's packed mask (causal and live inside it), or
    ``bias [T, S]`` the same set as the selection's kernel laid it (0 for
    a chosen key, ``UNCHOSEN`` for every other); tiles of keys past
    ``live_keys`` are not computed. -> ``[T, H, dv]``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from deepspeed_tpu.utils.compat import tpu_compiler_params

    t, heads, nope = q_nope.shape
    s, lanes = rows.shape
    rank = w_kvb.shape[0]
    dv = w_kvb.shape[-1] - nope
    group, tk = KERNEL_HEADS, KERNEL_KEYS
    groups = heads // group
    qn = q_nope.swapaxes(0, 1)
    qp = jnp.pad(q_pe, ((0, 0), (0, 0), (0, lanes - rank - q_pe.shape[-1]))
                 ).swapaxes(0, 1)
    w = w_kvb.reshape(rank, groups, group * (nope + dv)).swapaxes(0, 1)
    if bias is None:
        # the mask as a bias: 0 for a chosen key, -1e30 for every other
        bias = jnp.where(unpack_bits(mask, s), 0.0, _NEG).astype(
            jnp.bfloat16)
    tiles = jnp.reshape((live_keys + tk - 1) // tk, (1,)).astype(jnp.int32)

    def key_tile(j, tiles_ref):
        return jnp.maximum(jnp.minimum(j, tiles_ref[0] - 1), 0)

    out = pl.pallas_call(
        functools.partial(_masked_kernel, group=group, rank=rank, nope=nope,
                          dv=dv, scale=float(scale)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(groups, s // tk),
            in_specs=[
                pl.BlockSpec((group, t, nope), lambda g, j, n: (g, 0, 0)),
                pl.BlockSpec((group, t, lanes - rank),
                             lambda g, j, n: (g, 0, 0)),
                pl.BlockSpec((tk, lanes),
                             lambda g, j, n: (key_tile(j, n), 0)),
                pl.BlockSpec((1, rank, group * (nope + dv)),
                             lambda g, j, n: (g, 0, 0)),
                pl.BlockSpec((t, tk), lambda g, j, n: (0, key_tile(j, n))),
            ],
            out_specs=pl.BlockSpec((group, t, dv), lambda g, j, n: (g, 0, 0)),
            scratch_shapes=[pltpu.VMEM((group, t, 1), jnp.float32),
                            pltpu.VMEM((group, t, 1), jnp.float32),
                            pltpu.VMEM((group, t, dv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((heads, t, dv), q_nope.dtype),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=96 * 1024 * 1024),
        name="dsa_sparse_attend")(tiles, qn, qp, rows, w, bias)
    return out.swapaxes(0, 1)
