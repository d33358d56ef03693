"""Paged decode attention for grouped-query heads, keys wider than values,
sliding-window layers held in per-slot rings, and a learnable sink.

SPLIT from ``ops/decode_attention.py``, not an adaptation of it: that
kernel serves GPT-2 (one head size, a KV head a query head; since PR 55
its call also writes the step's rows, which the callers here scatter), and
a change to either leaves the other's programs be. Shared: the pool's shape
(``[layers, blocks, block_size, lanes]``, whole 128-lane registers, the
stacked pool and the layer index handed to the kernel), the garbage block,
and the work list (``paged_work_list``): a grid of one traced axis over the
live blocks of all rows, a TILE of them a step, nothing fetched past a
row's live prefix and no step for an idle slot.

What differs:

- GROUPED QUERIES, AS ONE MATMUL OVER THE WHOLE ROW. The ``G = heads /
  kv_heads`` query heads of one KV head read that head's lanes of a pool
  row and no others. The row's queries are laid out once a row as ``[H,
  kv_heads * dk]``, head ``h`` in the lanes of its KV head and zeros in the
  rest, so the scores of all heads are ONE matmul against the tile's rows
  as they lie (``[H, lanes] x [keys, lanes]``), with no slicing of a pool
  row into heads; the values' matmul gives ``[H, kv_heads * dv]`` and a
  head keeps its VALUE GROUP's lanes of it at the row's end: its own KV
  head's, or (``value_group`` > 1: differential attention, whose query
  pair weighs ``[v1 | v2]`` under either key) those of that many adjacent
  KV heads side by side. A head's key lanes and its value lanes are named
  apart; the matmuls are the same. The zeros cost matrix-unit passes the
  kernel has to spare and add exact zeros.
- TWO WIDTHS. A key row is ``kv_heads * dk`` lanes, a value row
  ``kv_heads * dv``: two pools, one table.
- THE KERNEL COPIES ITS OWN TILES. The pools stay in HBM; a step waits for
  its tile's blocks (one ``make_async_copy`` a live block a pool, into one
  of two VMEM tiles), having started the NEXT step's copies first, the
  next row's first tile too. The pipeline's own block DMA costs a step
  about 0.08 us an operand, which at 64-160 kB a block was the kernel's
  time (PERF.md section 6, PR 50); these pools' rows are whole registers,
  which a DMA slice needs and GPT-2 XL's 1600 lanes are not. The copies
  are a LOOP over the tile's live blocks, not the tile unrolled, and the
  layers of a kind share one trace of the kernel (``_attend``): a process
  traces and lowers a kernel at every start, which is ``setup_s``.
- WINDOW LAYERS LIVE IN A RING. A window layer's table row is a ring of
  ``ring`` blocks: position ``p`` is at ring block ``(p // bs) % ring``,
  offset ``p % bs``. The kernel works out which position each row of a ring
  block holds from the row's length alone, a block of the tile at a time
  (each has its own latest lap), and masks what lies outside ``(L -
  window, L]``: a row left over from an older lap, or from another
  request, computes to a position the mask refuses, so a ring needs no
  cleaning between requests.
- THE SINK. A window layer's softmax has one more term in its denominator,
  ``exp(s_h)`` for a learnable ``s_h`` a head, with no value row: the
  running maximum starts at ``s_h`` and the running sum at 1.

One query row a sequence (plain decode): speculation's k-row verify is not
served for these models (``serving/engine.py`` refuses it by name).
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


def ring_positions(lengths, ring_rows: int):
    """``[B, ring_rows]``: the position each row of a slot's ring holds
    once ``lengths[b]`` tokens are written (row ``r`` takes positions
    ``r, r + ring_rows, ...``: the latest below the length), -1 where none
    is yet."""
    rows = jnp.arange(ring_rows, dtype=jnp.int32)[None]
    last = jnp.asarray(lengths, jnp.int32)[:, None] - 1
    laps = jnp.floor_divide(last - rows, ring_rows)
    return jnp.where(last >= rows, rows + laps * ring_rows, -1)


class HybridPlan(NamedTuple):
    """What one grid step of the hybrid kernel attends; static in the
    shapes (:func:`hybrid_plan`)."""
    tile_blocks: int   # consecutive blocks of a row (or ring) a step attends
    block_size: int    # keys a pool block
    max_blocks: int    # blocks a table row, or a ring, holds

    @property
    def tile_keys(self) -> int:
        return self.tile_blocks * self.block_size

    def describe(self) -> str:
        return (f"one softmax update a tile of {self.tile_blocks} x "
                f"{self.block_size} = {self.tile_keys} keys, copied by the "
                f"kernel into one of two VMEM tiles; rows of "
                f"{self.max_blocks} blocks")


# keys a tile aims at: past 512 a step's fixed cost is a tenth of its
# bytes' time and rows of a dozen blocks fill less of a tile (the probe's
# table: CHANGES.md, PR 50)
HYBRID_TILE_KEYS = 512
# pool values the two tiles of both pools may hold: 8 MiB of a kernel's
# scoped VMEM in bfloat16
_TILE_BUFFER_VALUES = 4 << 20


def hybrid_plan(block_size: int, key_lanes: int, value_lanes: int,
                max_blocks: int) -> HybridPlan:
    """The tile of the hybrid kernel, read from a call's static shapes: as
    many blocks as hold ``HYBRID_TILE_KEYS`` keys (16 blocks of 32), no
    more than a row has (a ring of 5 is ONE step a row) and than two tiles
    of both pools hold ``_TILE_BUFFER_VALUES`` in (the widest rows served,
    2560 lanes a token, take 2.6 M at 512 keys)."""
    tile = max(1, min(HYBRID_TILE_KEYS // block_size, max_blocks))
    block_values = block_size * (key_lanes + value_lanes)
    while tile > 1 and 2 * tile * block_values > _TILE_BUFFER_VALUES:
        tile //= 2
    return HybridPlan(tile, block_size, max_blocks)


_noted_plans = set()


def _note_hybrid_plan(plan, q_shape, k_shape, v_shape):
    """Log the tile once a shape, while tracing (as ``paged_plan`` is)."""
    key = (plan, tuple(q_shape), tuple(k_shape), tuple(v_shape))
    if key in _noted_plans:
        return
    _noted_plans.add(key)
    from deepspeed_tpu.utils.logging import logger

    logger.info(f"decode_attention_hybrid q{tuple(q_shape)} pools"
                f"{tuple(k_shape)} / {tuple(v_shape)}: {plan.describe()}")


def hybrid_work_list(lengths, block_tables, plan: HybridPlan):
    """:func:`~deepspeed_tpu.ops.decode_attention.paged_work_list` for one
    query row a sequence in ``plan``'s tiles: the global layers' with the
    table's width, the window layers' with the ring's (a ring has
    ``min(cdiv(L + 1, bs), ring)`` live blocks). An idle slot (length 0 AND
    ``block_tables`` starting at the garbage block:
    :func:`~deepspeed_tpu.ops.decode_attention.paged_step_lengths`) owns no
    step; ``block_tables`` is what says so, the sequence's own table (a
    ring belongs to its slot whether the slot is busy or not, so a window
    layer's list is made from the global part too). A program makes each
    once a step, for all the layers of that kind, and counts the form it
    took (``hybrid_decode_tile<keys>`` in ``stats()["attention_paths"]``).
    """
    from deepspeed_tpu.ops.attention import record_dispatch

    record_dispatch(f"hybrid_decode_tile{plan.tile_keys}")
    return paged_work_list(paged_step_lengths(lengths, block_tables, 1), 1,
                           plan.block_size, plan.max_blocks,
                           tile_blocks=plan.tile_blocks)


def _kernel(row_ref, first_ref, tables_ref, lens_ref, at_ref, q_ref, k_hbm,
            v_hbm, *rest, scale, bs, kv_heads, group, dk, dv, window, ring,
            has_sink, tile, batch, mb, value_group):
    if has_sink:
        sink_ref, rest = rest[0], rest[1:]
    # (the zeros the output starts as are never read here)
    _, o_ref, k_buf, v_buf, sems, q_scr, m_scr, l_scr, acc_scr = rest
    heads, keys = kv_heads * group, tile * bs
    # this grid step is tile ji of row bi's live prefix (see
    # paged_work_list); an idle serving slot has no step, and its row of the
    # output stays the zeros it started as
    step = pl.program_id(0)
    steps = first_ref[batch]
    bi = row_ref[step]
    ji = step - first_ref[bi]
    idx = lens_ref[bi]  # the query's position: tokens written BEFORE it
    # a batch of idle slots only still runs the grid's one step, on no row
    owns = step < steps
    slot = jax.lax.rem(step, 2)

    def live_blocks(row):
        return jnp.minimum((lens_ref[row] + bs) // bs, mb)

    def each_live(s, into, act):
        """``act`` (start or wait) on the copies of step ``s``'s tile into
        buffer ``into``: two a live block, a loop as long as the tile has
        live blocks. A block past the row's live prefix is never named and
        never copied: the buffer keeps there what it held."""
        row = row_ref[s]
        first_block = (s - first_ref[row]) * tile

        def block(i, carry):
            at = (at_ref[0], tables_ref[row, first_block + i])
            rows = pl.ds(pl.multiple_of(i * bs, bs), bs)
            for pool, buf, sem in ((k_hbm, k_buf, 0), (v_hbm, v_buf, 1)):
                act(pltpu.make_async_copy(pool.at[at], buf.at[into, rows],
                                          sems.at[into, sem]))
            return carry

        jax.lax.fori_loop(
            0, jnp.clip(live_blocks(row) - first_block, 0, tile), block, 0)

    @pl.when(owns & (step == 0))
    def _first():
        # dead blocks of a tile are masked by position, but a matmul reads
        # their value rows: they hold zeros or an older tile's live rows,
        # never what the buffers were allocated with
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
        each_live(0, 0, lambda copy: copy.start())

    # the next step's tile (the next row's first, at a row's end) is on its
    # way while this one is attended
    @pl.when(step + 1 < steps)
    def _ahead():
        each_live(step + 1, 1 - slot, lambda copy: copy.start())

    @pl.when(jnp.logical_not(owns))
    def _idle():
        o_ref[...] = jnp.zeros_like(o_ref)

    def own_lanes(shape, width, group=group):
        """Head ``h`` (a row) against the lanes of its KV head (of its
        value group: ``group`` query heads over ``width`` lanes)."""
        row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        return row // group == lane // width

    @pl.when(owns & (ji == 0))
    def _init():
        q = jnp.concatenate([q_ref[...].reshape(heads, dk)] * kv_heads,
                            axis=1)                          # [H, KV * dk]
        q_scr[...] = jnp.where(own_lanes(q.shape, dk), q, jnp.zeros_like(q))
        if has_sink:
            m_scr[...] = sink_ref[...]
            l_scr[...] = jnp.ones_like(l_scr)
        else:
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def positions(shape):
        """The position each key of this step's tile holds: block ``i`` of
        the tile is the row's block ``ji * tile + i`` or, in a ring, the
        latest lap of that ring block; a block past the live prefix holds
        none."""
        key = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        if not ring:
            return key + ji * keys
        # worked out a key, on one row of the tile's shape
        key = jax.lax.broadcasted_iota(jnp.int32, (1, keys), 1)
        block = ji * tile + jax.lax.div(key, bs)
        now = idx // bs
        logical = now - jax.lax.rem(now - block + ring, ring)
        pos = jax.lax.rem(key, bs) + logical * bs
        return jnp.broadcast_to(
            jnp.where(block < live_blocks(bi), pos, -1), shape)

    @pl.when(owns)
    def _tile():
        each_live(step, slot, lambda copy: copy.wait())
        s = jax.lax.dot_general(
            q_scr[...], k_buf[slot], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale        # [H, keys]
        pos = positions(s.shape)
        seen = (pos <= idx) & (pos >= 0)
        if window:
            seen = seen & (pos > idx - window)
        s = jnp.where(seen, s, NEG_INF)
        m_prev = m_scr[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # a tile wholly outside the window leaves m at NEG_INF (no sink):
        # exp(NEG_INF - NEG_INF) = 1 per masked key would count them, so
        # the mask is applied to p as well
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scr[:, 0:1] + jnp.sum(p, axis=1, keepdims=True)
        v = v_buf[slot]
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # [H, KV * dv]
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    # the row's last step is the one before the next row's first
    @pl.when(owns & (step + 1 == first_ref[bi + 1]))
    def _finish():
        l = l_scr[:, 0:1]
        out = acc_scr[...] / jnp.where(l == 0.0, 1.0, l)
        # a head keeps its VALUE GROUP's lanes: its own KV head's, or
        # those of ``value_group`` adjacent KV heads side by side
        wide = value_group * dv
        out = jnp.where(own_lanes(out.shape, wide, value_group * group), out,
                        0.0)
        out = sum(out[:, h * wide:(h + 1) * wide]
                  for h in range(kv_heads // value_group))
        o_ref[...] = out.reshape(o_ref.shape).astype(o_ref.dtype)


def decode_attention_hybrid(q, k_pool, v_pool, block_tables, lengths, layer,
                            *, kv_heads: int, window: int = 0,
                            ring: bool = False, sink=None, work=None,
                            value_group: int = 1):
    """One decode step of one layer against its paged keys and values.

    Args:
      q: ``[B, 1, H, dk]``, rotated and unscaled; the query of row ``b``
        sits at position ``lengths[b]``.
      k_pool / v_pool: the stacked pools of this KIND of layer, ``[layers,
        blocks, block_size, kv_heads * dk]`` and ``[..., kv_heads * dv]``;
        this step's key and value already written at position
        ``lengths[b]``.
      block_tables: ``[B, MB]``: the sequence's blocks in order, or, with
        ``ring``, the slot's ring (``MB`` = its blocks).
      layer: which layer of the stacked pools (an index among the layers
        of this kind).
      window: keys at positions ``(L - window, L]`` are seen; 0 = all.
      sink: ``[H]`` float32, the learnable sink a head, or None.
      work: :func:`hybrid_work_list` in :func:`hybrid_plan`'s tiles for
        these pools and ``MB``; made here if None, idle slots read from
        ``block_tables`` (a ring's caller hands the list in).
      value_group: a query head scores against its own KV head's key lanes
        (head ``h``: KV head ``h // G``) and keeps the value lanes of
        ``value_group`` ADJACENT KV heads side by side (those of KV heads
        ``value_group * (h // (value_group * G)) ..``): 1, a head's own; 2,
        differential attention's pair ``[v1 | v2]`` under either key.

    THE GRID is one traced axis over the live TILES of all rows, row after
    row (``row_of`` and ``first``, the work list, are scalar-prefetch
    operands beside tables, lengths and layer); a row no step visits (an
    idle slot) keeps the zeros the output starts as (an operand aliased to
    it). A STEP is one float32 online-softmax update over ``tile_blocks *
    bs`` keys: ONE maximum, ONE ``exp``, ONE rescale of the accumulator and
    one pair of matmuls over all heads, the probabilities rounded to the
    values' dtype for the second. Its blocks arrive by the kernel's own
    copies (see the header), double-buffered across steps and rows. A
    block of a tile past the row's live prefix is neither named nor
    copied; its scores are masked by POSITION and its value rows are
    zeros or an older tile's live rows, so nothing a dead block holds
    (NaN included) reaches a matmul.

    Returns ``[B, 1, H, value_group * dv]`` in the query's dtype.
    """
    b, tq, heads, dk = q.shape
    if tq != 1:
        raise ValueError(f"one query row a sequence, got {tq}")
    if heads % kv_heads or kv_heads % value_group:
        raise ValueError(f"{heads} query heads over {kv_heads} KV heads in "
                         f"value groups of {value_group}")
    _, _, bs, klanes = k_pool.shape
    if klanes != kv_heads * dk or v_pool.shape[:3] != k_pool.shape[:3]:
        raise ValueError(
            f"pools {k_pool.shape} / {v_pool.shape} do not hold "
            f"{kv_heads} KV heads of {dk}-wide keys")
    mb = block_tables.shape[-1]
    if ring and window > (mb - 1) * bs + 1:
        raise ValueError(f"a ring of {mb} blocks of {bs} cannot hold a "
                         f"window of {window}")
    tables = jnp.asarray(block_tables, jnp.int32)
    lens = jnp.asarray(lengths, jnp.int32)
    plan = hybrid_plan(bs, klanes, v_pool.shape[-1], mb)
    _note_hybrid_plan(plan, q.shape, k_pool.shape, v_pool.shape)
    row_of, first = (hybrid_work_list(lens, tables, plan) if work is None
                     else work)
    steps = b * -(-mb // plan.tile_blocks) + 1
    if row_of.shape != (steps,) or first.shape != (b + 1,):
        raise ValueError(
            f"work list of shapes {row_of.shape}, {first.shape} is not "
            f"hybrid_work_list's for {b} rows of {mb} blocks in tiles of "
            f"{plan.tile_blocks}")
    return _attend(row_of, first, tables, lens,
                   jnp.asarray(layer, jnp.int32).reshape(1), q, k_pool,
                   v_pool, sink, kv_heads=kv_heads, window=int(window),
                   ring=bool(ring), tile=plan.tile_blocks,
                   value_group=int(value_group))


@functools.partial(jax.jit,
                   static_argnames=("kv_heads", "window", "ring", "tile",
                                    "value_group"))
def _attend(row_of, first, tables, lens, at, q, k_pool, v_pool, sink, *,
            kv_heads, window, ring, tile, value_group=1):
    """The kernel call behind :func:`decode_attention_hybrid`, a jitted
    function of its own with the layer index an argument: the layers of a
    kind in one program are ONE trace and ONE lowering of the kernel (a
    decode program's layers each traced and lowered their own, and a
    process pays that at every start, compile cache or not: ``setup_s``)."""
    b, _, heads, dk = q.shape
    _, _, bs, klanes = k_pool.shape
    vlanes = v_pool.shape[-1]
    dv = vlanes // kv_heads
    mb = tables.shape[-1]

    def row_spec(width):
        return pl.BlockSpec((1, 1, heads, width),
                            lambda s, row_of, first, tab, ln, at:
                            (row_of[s], 0, 0, 0))

    # the pools as they lie in HBM, and the output's own buffer, zeros
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [row_spec(dk), in_hbm, in_hbm]
    operands = [q, k_pool, v_pool]
    if sink is not None:
        in_specs.append(pl.BlockSpec(
            (heads, 128), lambda s, row_of, first, tab, ln, at: (0, 0)))
        operands.append(jnp.broadcast_to(
            jnp.asarray(sink, jnp.float32).reshape(heads, 1), (heads, 128)))
    out_shape = jax.ShapeDtypeStruct((b, 1, heads, value_group * dv),
                                     q.dtype)
    in_specs.append(in_hbm)
    operands.append(jnp.zeros(out_shape.shape, out_shape.dtype))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        # a batch of idle slots only has no step: one, on no row, runs
        grid=(jnp.maximum(first[b], 1),),
        in_specs=in_specs,
        out_specs=row_spec(value_group * dv),
        scratch_shapes=[
            pltpu.VMEM((2, tile * bs, klanes), k_pool.dtype),  # key tiles
            pltpu.VMEM((2, tile * bs, vlanes), v_pool.dtype),  # value tiles
            pltpu.SemaphoreType.DMA((2, 2)),        # a tile buffer, a pool
            pltpu.VMEM((heads, klanes), q.dtype),   # the row's queries
            pltpu.VMEM((heads, 128), jnp.float32),  # m
            pltpu.VMEM((heads, 128), jnp.float32),  # l
            pltpu.VMEM((heads, vlanes), jnp.float32),          # acc
        ],
    )
    kernel = functools.partial(
        _kernel, scale=dk ** -0.5, bs=bs, kv_heads=kv_heads,
        group=heads // kv_heads, dk=dk, dv=dv, window=window,
        ring=mb if ring else 0, has_sink=sink is not None, tile=tile,
        batch=b, mb=mb, value_group=value_group)
    # no ``name=``, and the callers' scope again here, inside the jitted
    # function: the device trace prints the kernel under the innermost
    # scope (``attn._hybrid_kv_attend.N``), which the benchmark's reader
    # matches, as it does ``attn._paged_kv_attend`` for GPT-2
    with jax.named_scope("attn._hybrid_kv_attend"):
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=out_shape,
            # the last operand is the output's own buffer: a row no step
            # visits is never written
            input_output_aliases={5 + len(operands) - 1: 0},
            compiler_params=tpu_compiler_params(
                dimension_semantics=("arbitrary",)),
        )(row_of, first, tables, lens, at, *operands)
