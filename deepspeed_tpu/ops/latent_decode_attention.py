"""Paged decode attention over LATENT rows: multi-head latent attention
(MLA) with the key/value up-projection absorbed into the query and the
output.

A token keeps ONE row a layer, shared by all heads: ``[c (rank) | k_pe
(rope) | zeros]``, the normalised compressed key/value and the rotated
positional key, padded to whole 128-lane registers (DeepSeek-V2: 512 + 64
of 640 lanes). With ``W_kvb`` folded away (``models/deepseek_v2.py``) a
decode step is MULTI-QUERY attention of ``H`` heads over that one row:

    s[h, j] = q[h] . row[j]            q[h] = [q_nope[h] W_K[h] | q_pe[h] | 0]
    o[h]    = softmax_j(s[h]) . row[j, :rank]

The keys and the values are THE SAME BYTES, read once: the value of key
``j`` is the first ``rank`` lanes of its row (a register-aligned slice of
the block already in VMEM), which no other kernel in the tree does
(``ops/decode_attention.py`` reads a key pool and a value pool by heads;
``ops/hybrid_decode_attention.py`` two pools of two widths).

SPLIT from those kernels, not a kind of either: their compiled programs
are what three cells are judged on and stay byte for byte. What is shared
is shared: the pool's one shape (``[layers, blocks, block_size, lanes]``,
lanes whole registers, the stacked pool and the layer index handed to the
kernel), the garbage block, and the work list (``paged_work_list``, in
tiles, idle slots without a step: PR 44's findings on the GPT-2 kernel,
which carry over because a step's cost is by the step here too).

One query row a sequence (plain decode).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.decode_attention import (NEG_INF, paged_step_lengths,
                                                paged_work_list)
from deepspeed_tpu.utils.compat import tpu_compiler_params

# keys a grid step attends: consecutive blocks of a row, each its own
# operand, laid one under the other (``ops/decode_attention.py`` says why a
# step is a tile). 512 and not that kernel's 128: a step's scores here are
# ``[heads, keys]`` for ALL heads at once (one row a key), 16 x 512 float32
# are four registers a head, and contexts are thousands of keys long. Read
# on the chip over 48 rows of about 6,300 keys in six layers (PERF.md,
# PR 45): 13.1 ms at 128 keys a step, 9.9 at 256, 8.3 at 512
LATENT_TILE_KEYS = 512


def latent_tile_blocks(block_size: int) -> int:
    return max(1, LATENT_TILE_KEYS // block_size)


def latent_step_work(lengths, block_tables, block_size: int):
    """The kernel's grid for one decode step, made once before the layers
    (it depends on nothing a layer changes): ``paged_work_list`` in this
    kernel's tiles, an idle slot (length 0 on the garbage block) without a
    step."""
    return paged_work_list(
        paged_step_lengths(lengths, block_tables, 1), 1, block_size,
        block_tables.shape[-1],
        tile_blocks=latent_tile_blocks(block_size))


def _kernel(row_ref, first_ref, tables_ref, lens_ref, at_ref, q_ref, *rest,
            scale, bs, heads, rank, tile, batch):
    blocks = rest[:tile]
    _, o_ref, m_scr, l_scr, acc_scr = rest[tile:]
    keys = tile * bs
    step = pl.program_id(0)
    bi = row_ref[step]
    ji = step - first_ref[bi]
    idx = lens_ref[bi]  # the query's position: tokens written BEFORE it
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

    @pl.when(owns)
    def _tile():
        q = q_ref[...].reshape(heads, q_ref.shape[-1])           # [H, lanes]
        rows = jnp.concatenate([r[...] for r in blocks], axis=0)  # [keys, lanes]
        # every head against the one row a key: one matmul, heads its rows
        s = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale           # [H, keys]
        pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + ji * keys
        s = jnp.where(pos <= idx, s, NEG_INF)
        m_prev = m_scr[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # the row's first tile holds key 0, which every query sees: m is
        # finite from then on, and exp(NEG_INF - m) is exactly 0
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scr[:, 0:1] + jnp.sum(p, axis=1, keepdims=True)
        # the values ARE the keys' first ``rank`` lanes. Rows past the
        # query's position (the boundary block's tail, a block named again
        # past the live prefix) weigh 0, and 0 x whatever they hold (NaN
        # included) must stay 0
        at = jax.lax.broadcasted_iota(jnp.int32, (keys, 1), 0) + ji * keys
        v = jnp.where(at <= idx, rows[:, :rank], jnp.zeros_like(
            rows[:, :rank]))
        acc_scr[:] = acc_scr[:] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(owns & (step + 1 == first_ref[bi + 1]))
    def _finish():
        l = l_scr[:, 0:1]
        out = acc_scr[:] / jnp.where(l == 0.0, 1.0, l)            # [H, rank]
        o_ref[...] = out.reshape(o_ref.shape).astype(o_ref.dtype)


def decode_attention_latent(q, pool, block_tables, lengths, layer, *,
                            rank: int, scale: float, work=None):
    """One decode step of one layer against its paged latent rows.

    Args:
      q: ``[B, 1, H, lanes]``: the absorbed query a head, ``[q_nope W_K |
        q_pe (rotated) | zeros]``, unscaled; the query of row ``b`` sits
        at position ``lengths[b]``.
      pool: the stacked latent pool ``[layers, blocks, block_size,
        lanes]``; this step's row already written at position
        ``lengths[b]``. Lanes past ``rank + rope`` hold zeros in every row
        ever written.
      block_tables: ``[B, MB]``: the sequence's blocks in order.
      layer: which layer of the stacked pool.
      rank: the leading lanes of a row that are also its value.
      scale: the softmax scale (the model's: ``qk_head_dim ** -0.5`` times
        YaRN's ``mscale ** 2``).
      work: :func:`latent_step_work` of ``lengths`` and the tables.

    Returns ``[B, 1, H, rank]`` in the query's dtype: ``softmax(s) c`` a
    head, which the caller takes through ``W_V``.
    """
    b, tq, heads, lanes = q.shape
    if tq != 1:
        raise ValueError(f"one query row a sequence, got {tq}")
    _, _, bs, width = pool.shape
    if width != lanes or lanes % 128 or rank % 128 or rank > lanes:
        raise ValueError(
            f"pool rows of {width} lanes, a query of {lanes}, values the "
            f"first {rank}: all whole 128-lane registers, and equal")
    mb = block_tables.shape[-1]
    tables = jnp.asarray(block_tables, jnp.int32)
    lens = jnp.asarray(lengths, jnp.int32)
    tile = latent_tile_blocks(bs)
    row_of, first = (latent_step_work(lens, tables, bs) if work is None
                     else work)
    if row_of.shape != (b * -(-mb // tile) + 1,) or first.shape != (b + 1,):
        raise ValueError(
            f"work list of shapes {row_of.shape}, {first.shape} is not "
            f"latent_step_work's for {b} rows of {mb} blocks in tiles of "
            f"{tile}")

    def pool_spec(i):
        # block i of the step's tile, or, where the row's live prefix ends
        # before it, a live block that costs no fetch (the one this operand
        # held a step ago, or the row's block 0): masked by position
        def index(s, row_of, first, tab, ln, at):
            row = row_of[s]
            j = (s - first[row]) * tile + i
            live = jnp.minimum((ln[row] + bs) // bs, mb)
            return (at[0], tab[row, jnp.where(j < live, j,
                                              jnp.maximum(j - tile, 0))],
                    0, 0)
        return pl.BlockSpec((None, None, bs, lanes), index)

    def row_spec(width):
        return pl.BlockSpec((1, 1, heads, width),
                            lambda s, row_of, first, tab, ln, at:
                            (row_of[s], 0, 0, 0))

    out_shape = jax.ShapeDtypeStruct((b, 1, heads, rank), q.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        # a batch of idle slots only has no step: one, on no row, runs
        grid=(jnp.maximum(first[b], 1),),
        in_specs=[row_spec(lanes)] + [pool_spec(i) for i in range(tile)]
        + [pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=row_spec(rank),
        scratch_shapes=[
            pltpu.VMEM((heads, 128), jnp.float32),    # m
            pltpu.VMEM((heads, 128), jnp.float32),    # l
            pltpu.VMEM((heads, rank), jnp.float32),   # acc
        ],
    )
    kernel = functools.partial(_kernel, scale=float(scale), bs=bs,
                               heads=heads, rank=rank, tile=tile, batch=b)
    at = jnp.asarray(layer, jnp.int32).reshape(1)
    # no ``name=``: the device trace prints the kernel under the caller's
    # scope (``attn._latent_kv_attend.N``), which the benchmark's reader
    # matches; no reader of ``attn._hybrid_kv_attend`` counts it
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        # the operand after the scalars, q and the tile's blocks is the
        # output's own buffer, zeros: a row no step visits (an idle slot)
        # is never written
        input_output_aliases={6 + tile: 0},
        compiler_params=tpu_compiler_params(
            dimension_semantics=("arbitrary",)),
    )(row_of, first, tables, lens, at, q, *([pool] * tile),
      jnp.zeros(out_shape.shape, out_shape.dtype))
