"""Paged decode attention for grouped-query heads, keys wider than values,
sliding-window layers held in per-slot rings, and a learnable sink.

SPLIT from ``ops/decode_attention.py``, not an adaptation of it: that
kernel serves GPT-2 (one head size, a KV head a query head, every live
block) and its compiled program is what ``serve-xl-chat`` is judged on, so
it stays byte for byte. What the two share is shared: the pool's one shape
(``[layers, blocks, block_size, lanes]``, whole 128-lane registers, the
stacked pool and the layer index handed to the kernel), the garbage block,
and the work list (``paged_work_list``): a grid of one traced axis over the
live blocks of all rows, nothing fetched past a row's live prefix.

What differs:

- GROUPED QUERIES. The ``G = heads / kv_heads`` query heads of one KV head
  are the rows of one matmul against that head's block, so a block is
  fetched once for all of them.
- TWO WIDTHS. A key row is ``kv_heads * dk`` lanes, a value row
  ``kv_heads * dv``: two pools, two block DMAs a step, one table.
- WINDOW LAYERS LIVE IN A RING. A window layer's table row is a ring of
  ``ring`` blocks: position ``p`` is at ring block ``(p // bs) % ring``,
  offset ``p % bs``. The kernel works out which position each row of a ring
  block holds from the row's length alone, and masks what lies outside
  ``(L - window, L]``: a row left over from an older lap, or from another
  request, computes to a position the mask refuses, so a ring needs no
  cleaning between requests.
- THE SINK. A window layer's softmax has one more term in its denominator,
  ``exp(s_h)`` for a learnable ``s_h`` a head, with no value row: the
  running maximum starts at ``s_h`` and the running sum at 1.

One query row a sequence (plain decode): speculation's k-row verify is not
served for these models (``serving/engine.py`` refuses it by name).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.decode_attention import (GARBAGE_BLOCK, NEG_INF,
                                                _heads_of, paged_work_list)
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


def _kernel(row_ref, first_ref, tables_ref, lens_ref, at_ref, q_ref, k_ref,
            v_ref, *rest, scale, bs, kv_heads, group, dk, dv, window, ring,
            has_sink):
    if has_sink:
        sink_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    step = pl.program_id(0)
    bi = row_ref[step]
    ji = step - first_ref[bi]
    idx = lens_ref[bi]  # the query's position: tokens written BEFORE it
    owns = (idx > 0) | (tables_ref[bi, 0] != GARBAGE_BLOCK)

    @pl.when(jnp.logical_not(owns))
    def _idle():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(owns & (ji == 0))
    def _init():
        if has_sink:
            m_scr[:] = sink_ref[...]
            l_scr[:] = jnp.ones_like(l_scr)
        else:
            m_scr[:] = jnp.full_like(m_scr, NEG_INF)
            l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(owns)
    def _block():
        q = q_ref[...].reshape(kv_heads, group, dk)
        k = _heads_of(k_ref[...], kv_heads, dk)                  # [KV,bs,dk]
        v = _heads_of(v_ref[...], kv_heads, dv)                  # [KV,bs,dv]
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale          # [KV,G,bs]
        # the logical block this step's pool block holds: the ji-th of the
        # sequence, or, in a ring, the latest lap of ring block ji
        if ring:
            now = idx // bs
            logical = now - jax.lax.rem(now - ji + ring, ring)
        else:
            logical = ji
        pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2) + logical * bs
        seen = (pos <= idx) & (pos >= 0)
        if window:
            seen = seen & (pos > idx - window)
        s = jnp.where(seen, s, NEG_INF)
        m_prev = m_scr[:, :, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        # a block wholly outside the window leaves m at NEG_INF (no sink):
        # exp(NEG_INF - NEG_INF) = 1 per masked key would count them, so
        # the mask is applied to p as well
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scr[:, :, 0:1] + jnp.sum(p, axis=2, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)                  # [KV,G,dv]
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(owns & (step + 1 == first_ref[bi + 1]))
    def _finish():
        l = l_scr[:, :, 0:1]
        out = acc_scr[:] / jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = out.reshape(o_ref.shape).astype(o_ref.dtype)


def hybrid_work_list(lengths, block_size: int, max_blocks: int):
    """:func:`~deepspeed_tpu.ops.decode_attention.paged_work_list` for one
    query row a sequence over tables of ``max_blocks`` blocks: the global
    layers' with the table's width, the window layers' with the ring's (a
    ring has ``min(cdiv(L + 1, bs), ring)`` live blocks). A program makes
    each once a step, for all the layers of that kind."""
    return paged_work_list(lengths, 1, block_size, max_blocks)


def decode_attention_hybrid(q, k_pool, v_pool, block_tables, lengths, layer,
                            *, kv_heads: int, window: int = 0,
                            ring: bool = False, sink=None, work=None):
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
      work: :func:`hybrid_work_list` of ``lengths`` and ``MB``.

    Returns ``[B, 1, H, dv]`` in the query's dtype.
    """
    b, tq, heads, dk = q.shape
    if tq != 1:
        raise ValueError(f"one query row a sequence, got {tq}")
    if heads % kv_heads:
        raise ValueError(f"{heads} query heads over {kv_heads} KV heads")
    group = heads // kv_heads
    _, _, bs, klanes = k_pool.shape
    dv = v_pool.shape[-1] // kv_heads
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
    row_of, first = (hybrid_work_list(lens, bs, mb) if work is None
                     else work)

    def pool_spec(width):
        def index(s, row_of, first, tab, ln, at):
            row = row_of[s]
            return (at[0], tab[row, jnp.minimum(s - first[row], mb - 1)],
                    0, 0)
        return pl.BlockSpec((None, None, bs, width), index)

    def row_spec(width):
        return pl.BlockSpec((1, 1, heads, width),
                            lambda s, row_of, first, tab, ln, at:
                            (row_of[s], 0, 0, 0))

    in_specs = [row_spec(dk), pool_spec(klanes), pool_spec(kv_heads * dv)]
    operands = [q, k_pool, v_pool]
    if sink is not None:
        in_specs.append(pl.BlockSpec(
            (kv_heads, group, 128),
            lambda s, row_of, first, tab, ln, at: (0, 0, 0)))
        operands.append(jnp.broadcast_to(
            jnp.asarray(sink, jnp.float32).reshape(kv_heads, group, 1),
            (kv_heads, group, 128)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(first[b],),
        in_specs=in_specs,
        out_specs=row_spec(dv),
        scratch_shapes=[
            pltpu.VMEM((kv_heads, group, 128), jnp.float32),   # m
            pltpu.VMEM((kv_heads, group, 128), jnp.float32),   # l
            pltpu.VMEM((kv_heads, group, dv), jnp.float32),    # acc
        ],
    )
    kernel = functools.partial(
        _kernel, scale=dk ** -0.5, bs=bs, kv_heads=kv_heads, group=group,
        dk=dk, dv=dv, window=int(window), ring=mb if ring else 0,
        has_sink=sink is not None)
    at = jnp.asarray(layer, jnp.int32).reshape(1)
    # no ``name=``: the device trace prints the kernel under the caller's
    # scope (``attn._hybrid_kv_attend.N``), which the benchmark's reader
    # matches, as it does ``attn._paged_kv_attend`` for GPT-2
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, heads, dv), q.dtype),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("arbitrary",)),
    )(row_of, first, tables, lens, at, *operands)
