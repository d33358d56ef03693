"""One decode step of a Mamba-2 layer's recurrence, on the state pool in
place.

A decode slot keeps, a layer, a state ``S [H, P, N]`` (``H`` heads of width
``P``, state size ``N``) in row ``1 + slot`` of ``ssm_state_pool [layers, 1
+ slots, H, P, N]`` (row 0: what idle rows would write). A step does, a
busy row and head, ``S' = a S + (delta x) B^T`` and ``y = S' C``: every
value of the state is read once and written once, and nothing else of any
size moves, so the step is bound by the state's bytes, twice.

- :func:`state_update_kernel`: the Pallas kernel. The pool is aliased to
  its output; a grid step is a busy row and a tile of heads, the row's
  pool row found through scalar prefetch (the block table's last entry), so
  a state goes HBM -> VMEM -> HBM once, and an idle slot has no step (its
  state is not touched, its ``y`` is zero). A gather, an update and a
  scatter through XLA would move each state three times.
- :func:`state_update_xla`: the same arithmetic in XLA, where no TPU is.

Both take the decay already zeroed (``a = 0``) for a row whose sequence
starts here, which is how a slot's last tenant's state is forgotten (its
values are finite: ``0 * S = 0``). The arithmetic is float32; the pool
rounds once a step (its dtype).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.utils.compat import tpu_compiler_params

# heads a grid step: 32 x [64, 128] bfloat16 are 512 KB a block, in and out
# double-buffered 2 MB of VMEM. On a v5e 16, 32 and 64 read alike, 33-34% of
# the bandwidth bound at 40 busy rows, and so does the outer product and the
# readout as matmuls (36%): the vector unit's work a value (two converts, a
# multiply-add) binds, not a step's DMA (PERF.md, PR 49)
HEAD_TILE = 32


def busy_rows(slot_rows):
    """The kernel's work list, from the pool row of each batch row
    (``slot_rows [B]``, 0 for an idle one): ``(order [B + 1], count
    [1])``, the batch rows with the busy ones first, and how many are busy.
    It depends on nothing a layer changes: a program makes it once a step."""
    idle = slot_rows == 0
    order = jnp.argsort(idle, stable=True).astype(jnp.int32)
    # (one more entry: the pipeline looks at the step after the last)
    return (jnp.concatenate([order, order[-1:]]),
            jnp.sum(~idle, dtype=jnp.int32).reshape(1))


def state_update_xla(pool, layer, slot_rows, a, dx, b, c):
    """``pool [layers, rows, H, P, N]``; ``slot_rows [B]``; ``a [B, H]``
    the step's decay; ``dx [B, H, P]`` = ``delta x``; ``b`` / ``c [B, N]``.
    -> ``(y [B, H, P] float32, pool)``. Idle rows (pool row 0) write row
    0."""
    f32 = jnp.float32
    state = pool[layer, slot_rows].astype(f32)
    state = (a.astype(f32)[..., None, None] * state
             + dx.astype(f32)[..., None] * b.astype(f32)[:, None, None, :])
    y = jnp.sum(state * c.astype(f32)[:, None, None, :], axis=-1)
    return y, pool.at[layer, slot_rows].set(state.astype(pool.dtype))


def _kernel(order_ref, count_ref, slots_ref, layer_ref, a_ref, dx_ref, b_ref,
            c_ref, pool_ref, y_ref, out_ref, *, tile):
    del order_ref, count_ref, slots_ref, layer_ref
    bv = b_ref[...]                                          # [1, N]
    cv = c_ref[...]
    for h in range(tile):
        # this head's decay [1, 1] and its (delta x) down the sublanes [P, 1]
        state = (a_ref[:, h:h + 1] * pool_ref[h].astype(jnp.float32)
                 + dx_ref[:, h:h + 1] * bv)                  # [P, N]
        y_ref[:, h:h + 1] = jnp.sum(state * cv, axis=-1, keepdims=True)
        out_ref[h] = state.astype(out_ref.dtype)


def state_update_kernel(pool, layer, slot_rows, a, dx, b, c, work=None,
                        head_tile: int = HEAD_TILE):
    """:func:`state_update_xla`'s arguments and result, the pool updated in
    place (aliased), idle rows skipped (their ``y`` is 0). ``work``:
    :func:`busy_rows` of ``slot_rows``."""
    rows, heads, width = dx.shape
    n = b.shape[-1]
    tile = min(head_tile, heads)
    if heads % tile:
        raise ValueError(f"{heads} heads in tiles of {tile}")
    tiles = heads // tile
    f32 = jnp.float32
    order, count = busy_rows(slot_rows) if work is None else work
    # a head's scalars lie along the lanes of a [*, tile] block, a tile of
    # heads a block: the kernel slices a lane a head, never a sublane
    a_t = a.astype(f32).reshape(rows, tiles, 1, tile)
    dx_t = dx.astype(f32).reshape(rows, tiles, tile, width).swapaxes(2, 3)
    row = lambda i, j, order, count, slots, at: (order[i], j, 0, 0)
    vec = lambda i, j, order, count, slots, at: (order[i], 0, 0)
    state = lambda i, j, order, count, slots, at: (
        at[0], slots[order[i]], j, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(jnp.maximum(count[0], 1), tiles),
        in_specs=[pl.BlockSpec((None, None, 1, tile), row),
                  pl.BlockSpec((None, None, width, tile), row),
                  pl.BlockSpec((None, 1, n), vec),
                  pl.BlockSpec((None, 1, n), vec),
                  pl.BlockSpec((None, None, tile, width, n), state)],
        out_specs=[pl.BlockSpec((None, None, width, tile), row),
                   pl.BlockSpec((None, None, tile, width, n), state)],
    )
    # no ``name=``: the device trace prints the kernel under the caller's
    # scope (``ssm._state_update.N``), as the attention kernels'
    y_t, pool = pl.pallas_call(
        functools.partial(_kernel, tile=tile),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((rows, tiles, width, tile), f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operand 8 (after the four prefetched scalars) is the pool
        input_output_aliases={8: 1},
        compiler_params=tpu_compiler_params(
            dimension_semantics=("arbitrary", "arbitrary")),
    )(order, count, jnp.asarray(slot_rows, jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), a_t, dx_t,
      b.astype(f32)[:, None], c.astype(f32)[:, None], pool)
    y = y_t.swapaxes(2, 3).reshape(rows, heads, width)
    # a row without a step holds whatever the buffer held
    return jnp.where((slot_rows != 0)[:, None, None], y, 0.0), pool
