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
are what other cells are judged on and stay byte for byte. What is shared
is shared: the pool's one shape (``[layers, blocks, block_size, lanes]``,
lanes whole registers, the stacked pool and the layer index handed to the
kernel), the garbage block, and the work list (``paged_work_list``, in
tiles, idle slots without a step).

THE KERNEL COPIES ITS OWN TILES (PR 60), as the hybrid kernel does since
PR 50. The pool stays in HBM; a grid step waits for its tile's live blocks
(ONE ``make_async_copy`` a live block, into one of two VMEM tiles), having
started the NEXT step's copies first, the next row's first tile too. Until
then a tile was 16 ``BlockSpec`` operands of 32 x 640 bfloat16 = 40 kB, and
the pipeline's bookkeeping an operand a step was the kernel's time (0.44 us
a step + 0.12 us a block where a block's bytes take 0.05). The copies are a
LOOP over the tile's live blocks: a block past the row's live prefix is
neither named nor copied. What a copy costs now is its scalar chain (the
table entry from SMEM, two addresses, the descriptor): the table lies FLAT
in SMEM and the call compiles WITHOUT Mosaic's bounds checks (17 bundles a
block where they made 35; the block index is clamped to the pool instead).
A tile the query sees to its last key takes neither position mask. The
call is a jitted function of its own (``_attend``), the layer index an
argument: a program's latent layers are ONE trace and lowering.

On the chip (``tools/probe_latent_decode.py``, the kernel alone, my chip
runs, PR 60, call 3: us a layer call and % of the live rows' bytes' time,
1,152 B a token a layer over 819 GB/s):

    form            dsv2lite (16 heads, 6 rows,     ling3 (32 heads, 105 rows,
                    57,480 live tokens)             268,205 live tokens)
    BlockSpec, 512      237.6   34.0                   1231.2   30.6
    own copies, 512     136.3   59.3                    694.9   54.3
    own copies, 1024    115.3   70.2                    607.7   62.1
    own copies, 2048    113.8   71.1                    605.7   62.3

At 1,024 keys a step of a whole tile is 1.9 us where its 1.31 MB (640
lanes) take 1.6: what is left is the copies' issue (32 x 17 bundles) and a
step's fixed cost, not bytes.

One query row a sequence (plain decode).
"""

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.decode_attention import (NEG_INF, paged_step_lengths,
                                                paged_work_list)
from deepspeed_tpu.utils.compat import tpu_compiler_params


class LatentPlan(NamedTuple):
    """What one grid step of the latent kernel attends; static in the
    shapes (:func:`latent_plan`)."""
    tile_blocks: int   # consecutive blocks of a row a step attends
    block_size: int    # keys a pool block
    max_blocks: int    # blocks a table row holds

    @property
    def tile_keys(self) -> int:
        return self.tile_blocks * self.block_size


# keys a tile aims at: 32 blocks of 32. The chip's table (the header's) for
# BOTH callers' shapes: 512 -> 1,024 keys takes 15% (dsv2lite) and 13%
# (ling3) off a layer call, a step's fixed cost over twice the keys; 2,048
# takes 1% more and costs a short row a longer masked tile
LATENT_TILE_KEYS = 1024
# pool values the two tiles may hold: 8 MiB of a kernel's scoped VMEM in
# bfloat16 (two tiles of 1,024 keys of 640 lanes take 1.3 M)
_TILE_BUFFER_VALUES = 4 << 20


def latent_plan(block_size: int, lanes: int, max_blocks: int) -> LatentPlan:
    """The tile of the latent kernel, read from a call's static shapes: as
    many blocks as hold ``LATENT_TILE_KEYS`` keys (32 blocks of 32; the
    constant is read when the plan is made), no more than a table row has
    and than two tiles hold ``_TILE_BUFFER_VALUES`` in."""
    tile = max(1, min(LATENT_TILE_KEYS // block_size, max_blocks))
    while tile > 1 and 2 * tile * block_size * lanes > _TILE_BUFFER_VALUES:
        tile //= 2
    return LatentPlan(tile, block_size, max_blocks)


def latent_step_work(lengths, block_tables, block_size: int, lanes: int):
    """The kernel's grid for one decode step, made once before the layers
    (it depends on nothing a layer changes): ``paged_work_list`` in the
    tiles of :func:`latent_plan` for a pool of these blocks and lanes and
    these tables, an idle slot (length 0 on the garbage block) without a
    step. Counts the form it took (``latent_decode_tile<keys>`` in
    ``stats()["attention_paths"]``, once a traced decode program)."""
    from deepspeed_tpu.ops.attention import record_dispatch

    plan = latent_plan(block_size, lanes, block_tables.shape[-1])
    record_dispatch(f"latent_decode_tile{plan.tile_keys}")
    return paged_work_list(
        paged_step_lengths(lengths, block_tables, 1), 1, plan.block_size,
        plan.max_blocks, tile_blocks=plan.tile_blocks)


def _kernel(row_ref, first_ref, tables_ref, lens_ref, at_ref, q_ref, pool_hbm,
            _, o_ref, buf, sems, m_scr, l_scr, acc_scr, *, scale, bs, heads,
            rank, tile, batch, mb):
    keys, blocks = tile * bs, pool_hbm.shape[1]
    # this grid step is tile ji of row bi's live prefix (``paged_work_list``)
    step = pl.program_id(0)
    steps = first_ref[batch]
    bi = row_ref[step]
    ji = step - first_ref[bi]
    idx = lens_ref[bi]  # the query's position: tokens written BEFORE it
    # a batch of idle slots only still runs the grid's one step, on no row
    owns = step < steps
    slot = jax.lax.rem(step, 2)

    def each_live(s, into, act):
        """``act`` (start or wait) on the copies of step ``s``'s tile into
        buffer ``into``: ONE a live block (its values are its keys' first
        ``rank`` lanes), a loop as long as the tile has live blocks. A
        block past the row's live prefix is never named and never copied:
        the buffer keeps there what it held."""
        row = row_ref[s]
        first_block = (s - first_ref[row]) * tile
        live = jnp.minimum((lens_ref[row] + bs) // bs, mb)
        named = row * mb + first_block      # the tables lie flat

        def block(i, carry):
            # (clamped: the call compiles without Mosaic's bounds checks)
            block_id = jnp.clip(tables_ref[named + i], 0, blocks - 1)
            act(pltpu.make_async_copy(
                pool_hbm.at[at_ref[0], block_id],
                buf.at[into, pl.ds(pl.multiple_of(i * bs, bs), bs)],
                sems.at[into]))
            return carry

        jax.lax.fori_loop(0, jnp.clip(live - first_block, 0, tile), block, 0)

    @pl.when(owns & (step == 0))
    def _first():
        # dead blocks of a tile are masked by position, but a matmul reads
        # their rows: they hold zeros or an older tile's live rows, never
        # what the buffers were allocated with
        buf[...] = jnp.zeros_like(buf)
        each_live(0, 0, lambda copy: copy.start())

    # the next step's tile (the next row's first, at a row's end) is on its
    # way while this one is attended
    @pl.when(step + 1 < steps)
    def _ahead():
        each_live(step + 1, 1 - slot, lambda copy: copy.start())

    @pl.when(jnp.logical_not(owns))
    def _idle():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(owns & (ji == 0))
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def attend(masked):
        each_live(step, slot, lambda copy: copy.wait())
        q = q_ref[...].reshape(heads, q_ref.shape[-1])           # [H, lanes]
        rows = buf[slot]                                      # [keys, lanes]
        # every head against the one row a key: one matmul, heads its rows
        s = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale           # [H, keys]
        # the values ARE the keys' first ``rank`` lanes
        v = rows[:, :rank]
        if masked:
            pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + ji * keys
            s = jnp.where(pos <= idx, s, NEG_INF)
            # rows past the query's position (the boundary block's tail, a
            # dead block's rows of an older tile) weigh 0, and 0 x whatever
            # they hold (NaN included) must stay 0
            at = jax.lax.broadcasted_iota(jnp.int32, (keys, 1), 0) + ji * keys
            v = jnp.where(at <= idx, v, jnp.zeros_like(v))
        m_prev = m_scr[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # the row's first tile holds key 0, which every query sees: m is
        # finite from then on, and exp(NEG_INF - m) is exactly 0
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scr[:, 0:1] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    # a tile the query sees to its last key (every tile of a row but its
    # last) needs neither mask: the same sums, without two selects over the
    # tile
    whole = (ji + 1) * keys - 1 <= idx

    @pl.when(owns & whole)
    def _tile():
        attend(masked=False)

    @pl.when(owns & jnp.logical_not(whole))
    def _last_tile():
        attend(masked=True)

    # the row's last step is the one before the next row's first
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
      work: :func:`latent_step_work` of ``lengths``, the tables and this
        pool's block size and lanes; made here if None.

    THE GRID is one traced axis over the live TILES of all rows, row after
    row; a row no step visits (an idle slot) keeps the zeros the output
    starts as (an operand aliased to it). A STEP is one float32
    online-softmax update over ``tile_blocks * bs`` keys for all heads. Its
    blocks arrive by the kernel's own copies (see the header),
    double-buffered across steps and rows. A block of a tile past the
    row's live prefix is neither named nor copied; its scores and its
    value rows are masked by POSITION, so nothing a dead block or a live
    block's tail holds (NaN included) reaches the sums.

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
    plan = latent_plan(bs, lanes, mb)
    row_of, first = (latent_step_work(lens, tables, bs, lanes)
                     if work is None else work)
    steps = b * -(-mb // plan.tile_blocks) + 1
    if row_of.shape != (steps,) or first.shape != (b + 1,):
        raise ValueError(
            f"work list of shapes {row_of.shape}, {first.shape} is not "
            f"latent_step_work's for {b} rows of {mb} blocks in tiles of "
            f"{plan.tile_blocks}")
    return _attend(row_of, first, tables, lens,
                   jnp.asarray(layer, jnp.int32).reshape(1), q, pool,
                   rank=int(rank), scale=float(scale), tile=plan.tile_blocks)


@functools.partial(jax.jit, static_argnames=("rank", "scale", "tile"))
def _attend(row_of, first, tables, lens, at, q, pool, *, rank, scale, tile):
    """The kernel call behind :func:`decode_attention_latent`, a jitted
    function of its own with the layer index an argument: the latent
    layers of one program are ONE trace and ONE lowering of the kernel (as
    ``ops/hybrid_decode_attention.py``'s ``_attend``: a process pays a
    kernel's trace and lowering at every start, compile cache or not, which
    is ``setup_s``)."""
    b, _, heads, lanes = q.shape
    bs = pool.shape[2]
    mb = tables.shape[-1]

    def row_spec(width):
        return pl.BlockSpec((1, 1, heads, width),
                            lambda s, row_of, first, tab, ln, at:
                            (row_of[s], 0, 0, 0))

    # the pool as it lies in HBM, and the output's own buffer, zeros
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    out_shape = jax.ShapeDtypeStruct((b, 1, heads, rank), q.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        # a batch of idle slots only has no step: one, on no row, runs
        grid=(jnp.maximum(first[b], 1),),
        in_specs=[row_spec(lanes), in_hbm, in_hbm],
        out_specs=row_spec(rank),
        scratch_shapes=[
            pltpu.VMEM((2, tile * bs, lanes), pool.dtype),  # the two tiles
            pltpu.SemaphoreType.DMA((2,)),            # one a tile buffer
            pltpu.VMEM((heads, 128), jnp.float32),    # m
            pltpu.VMEM((heads, 128), jnp.float32),    # l
            pltpu.VMEM((heads, rank), jnp.float32),   # acc
        ],
    )
    kernel = functools.partial(_kernel, scale=scale, bs=bs, heads=heads,
                               rank=rank, tile=tile, batch=b, mb=mb)
    # no ``name=``, and the callers' scope again here, inside the jitted
    # function: the device trace prints the kernel under the innermost
    # scope (``attn._latent_kv_attend.N``), which the benchmark's reader
    # matches; no reader of ``attn._hybrid_kv_attend`` counts it
    with jax.named_scope("attn._latent_kv_attend"):
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=out_shape,
            # the operand after the scalars, q and the pool is the output's
            # own buffer, zeros: a row no step visits (an idle slot) is
            # never written
            input_output_aliases={7: 0},
            compiler_params=tpu_compiler_params(
                dimension_semantics=("arbitrary",),
                disable_bounds_checks=True),
        )(row_of, first, tables.reshape(-1), lens, at, q, pool,
          jnp.zeros(out_shape.shape, out_shape.dtype))
