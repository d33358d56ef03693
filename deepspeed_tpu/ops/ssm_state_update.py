"""One decode step of a Mamba-2 layer's recurrence, on the state pool in
place.

A decode slot keeps, a layer, a state ``S [H, P, N]`` (``H`` heads of width
``P``, state size ``N``) in row ``1 + slot`` of ``ssm_state_pool`` (row 0:
what idle rows would write). A step does, a busy row and head, ``S' = a S +
(delta x) B^T`` and ``y = S' C``: every value of the state is read once and
written once, and nothing else of any size moves, so the step is bound by
the state's bytes, twice.

THE LAYOUT. The pool is ALLOCATED as ``[layers, 1 + slots, H, P, N]`` and a
row's ``H P N`` values LIE in it as ``[H P / L, N, L]`` (:func:`lane_view`,
a reshape that moves nothing): lane groups of ``L`` consecutive ``(head,
width)`` columns along the lanes, the state size down the sublanes. ``L`` is
128 where ``H P`` is a multiple of 128 (two heads of 64 at the published
widths), else all of ``H P`` (:func:`lane_width`). In that layout a step
crosses no lane: the decay and ``delta x`` are lane rows broadcast down the
sublanes, ``B`` and ``C`` one column a ROW (the same for every head), the
readout a sum DOWN the sublanes (adds of whole registers and one sublane
reduction a lane group) and ``y`` leaves as dense rows of ``L`` lanes. With
the state size along the lanes (``[H, P, N]`` as it is written, the layout
until PR 51) a head cost eight lane broadcasts of ``delta x``, eight
cross-lane reductions and eight one-lane stores of ``y``, and the vector
unit's work bound the step at 36% of the bytes' time (PERF.md, PR 51).
:func:`to_lanes` and :func:`from_lanes` convert a state between the scan's
``[.., H, P, N]`` and the pool's ``[.., H P / L, N, L]``; the prefill side
(``models/granite_hybrid.py``) reads and writes the pool through them.

- :func:`state_update_kernel`: the Pallas kernel. The pool is aliased to
  its output; a grid step is a busy row and a tile of lane groups, the row's
  pool row found through scalar prefetch (the block table's last entry), so
  a state goes HBM -> VMEM -> HBM once, and an idle slot has no step (its
  state is not touched, its ``y`` is zero). A gather, an update and a
  scatter through XLA would move each state three times. It serves where
  :func:`kernel_serves` says the blocks are whole registers.
- :func:`state_update_xla`: the same layout and the same float32 arithmetic
  in XLA, elsewhere and where no TPU is.

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

LANES = 128
# lane groups a grid step: the whole row where it has no more (32 x [128,
# 128] bfloat16 are 1 MB a block at the published widths, in and out
# double-buffered 4 MB of VMEM); on a v5e the probe
# (tools/probe_ssm_state_update.py) read tiles of 4 / 8 / 16 / 32 groups
# (PERF.md, PR 51)
GROUP_TILE = 32


def lane_width(heads: int, width: int) -> int:
    """``L``: how many consecutive ``(head, width)`` columns lie along the
    lanes of one lane group."""
    inner = heads * width
    return LANES if inner % LANES == 0 else inner


def kernel_serves(heads: int, width: int, n: int) -> bool:
    """Whether the Pallas kernel's blocks are whole registers at these
    sizes: lane groups of 128, the state size whole sublane tiles of a
    16-bit pool."""
    return lane_width(heads, width) == LANES and n % 16 == 0


def to_lanes(state):
    """``[.., H, P, N]`` (the scan's) -> ``[.., H P / L, N, L]`` (the
    pool's)."""
    *lead, heads, width, n = state.shape
    lanes = lane_width(heads, width)
    return state.reshape(*lead, heads * width // lanes, lanes,
                         n).swapaxes(-1, -2)


def from_lanes(state, heads: int, width: int):
    """:func:`to_lanes`' inverse: ``[.., H P / L, N, L] -> [.., H, P, N]``."""
    *lead, _, n, _ = state.shape
    return state.swapaxes(-1, -2).reshape(*lead, heads, width, n)


def lane_view(pool):
    """The pool as its values lie, ``[layers, rows, H P / L, N, L]``, of the
    allocation ``[layers, rows, H, P, N]``: a reshape of the trailing axes
    (a bitcast where ``P`` is whole sublane tiles, as 64 is), nothing
    moves."""
    *lead, heads, width, n = pool.shape
    lanes = lane_width(heads, width)
    return pool.reshape(*lead, heads * width // lanes, n, lanes)


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


def _lane_rows(a, dx, lanes):
    """The decay a head and ``delta x`` as rows of the lane groups:
    ``(a [B, H], dx [B, H, P]) -> (a, dx) [B, H P / L, L]`` float32."""
    rows, heads, width = dx.shape
    shape = (rows, heads * width // lanes, lanes)
    f32 = jnp.float32
    return (jnp.broadcast_to(a.astype(f32)[..., None], dx.shape).reshape(
        shape), dx.astype(f32).reshape(shape))


def state_update_xla(pool, layer, slot_rows, a, dx, b, c):
    """``pool [layers, rows, H, P, N]`` (its values as :func:`lane_view`
    says); ``slot_rows [B]``; ``a [B, H]`` the step's decay; ``dx [B, H,
    P]`` = ``delta x``; ``b`` / ``c [B, N]``. -> ``(y [B, H, P] float32,
    pool)``. Idle rows (pool row 0) write row 0."""
    f32 = jnp.float32
    view = lane_view(pool)
    a_row, dx_row = _lane_rows(a, dx, view.shape[-1])
    state = view[layer, slot_rows].astype(f32)               # [B, G, N, L]
    state = (a_row[:, :, None] * state
             + b.astype(f32)[:, None, :, None] * dx_row[:, :, None])
    y = jnp.sum(state * c.astype(f32)[:, None, :, None], axis=2)
    view = view.at[layer, slot_rows].set(state.astype(pool.dtype))
    return y.reshape(dx.shape), view.reshape(pool.shape)


def _kernel(order_ref, count_ref, slots_ref, layer_ref, a_ref, dx_ref, b_ref,
            c_ref, pool_ref, y_ref, out_ref, b_col, c_col, *, tile):
    del order_ref, count_ref, slots_ref, layer_ref
    n, lanes = b_col.shape

    @pl.when(pl.program_id(1) == 0)
    def _columns():
        # the row's B and C [1, N] down the sublanes and across every lane,
        # once a row: the only values that change axis in the step
        b_col[...] = jnp.broadcast_to(b_ref[...], (lanes, n)).T
        c_col[...] = jnp.broadcast_to(c_ref[...], (lanes, n)).T

    def group(g, carry):
        at = pl.ds(g, 1)
        state = (a_ref[at, :] * pool_ref[g].astype(jnp.float32)
                 + b_col[...] * dx_ref[at, :])               # [N, L]
        y_ref[at, :] = jnp.sum(state * c_col[...], axis=0, keepdims=True)
        out_ref[g] = state.astype(out_ref.dtype)
        return carry

    jax.lax.fori_loop(0, tile, group, 0)


def state_update_kernel(pool, layer, slot_rows, a, dx, b, c, work=None,
                        group_tile: int = GROUP_TILE):
    """:func:`state_update_xla`'s arguments and result, the pool updated in
    place (aliased), idle rows skipped (their ``y`` is 0). ``work``:
    :func:`busy_rows` of ``slot_rows``."""
    _, heads, width = dx.shape
    if not kernel_serves(heads, width, b.shape[-1]):
        raise ValueError(
            f"{heads} heads of {width} with a state of {b.shape[-1]} are "
            "not whole registers (kernel_serves): state_update_xla serves "
            "them")
    groups = heads * width // LANES
    tile = min(group_tile, groups)
    if groups % tile:
        raise ValueError(f"{groups} lane groups in tiles of {tile}")
    order, count = busy_rows(slot_rows) if work is None else work
    return _update(order, count, jnp.asarray(slot_rows, jnp.int32),
                   jnp.asarray(layer, jnp.int32).reshape(1), a, dx, b, c,
                   pool, tile=tile)


@functools.partial(jax.jit, static_argnames=("tile",))
def _update(order, count, slot_rows, at, a, dx, b, c, pool, *, tile):
    """The kernel call behind :func:`state_update_kernel`, a jitted function
    of its own with the layer index an argument: a program's Mamba layers
    are ONE trace and ONE lowering of the kernel (a process pays those at
    every start, compile cache or not: ``setup_s``)."""
    rows, heads, width = dx.shape
    n = b.shape[-1]
    f32 = jnp.float32
    view = lane_view(pool)
    tiles = view.shape[2] // tile
    a_row, dx_row = (v.reshape(rows, tiles, tile, LANES)
                     for v in _lane_rows(a, dx, LANES))
    row = lambda i, j, order, count, slots, at: (order[i], j, 0, 0)
    vec = lambda i, j, order, count, slots, at: (order[i], 0, 0)
    state = lambda i, j, order, count, slots, at: (
        at[0], slots[order[i]], j, 0, 0)
    by_row = pl.BlockSpec((None, None, tile, LANES), row)
    in_pool = pl.BlockSpec((None, None, tile, n, LANES), state)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(jnp.maximum(count[0], 1), tiles),
        in_specs=[by_row, by_row, pl.BlockSpec((None, 1, n), vec),
                  pl.BlockSpec((None, 1, n), vec), in_pool],
        out_specs=[by_row, in_pool],
        scratch_shapes=[pltpu.VMEM((n, LANES), f32),         # B's column
                        pltpu.VMEM((n, LANES), f32)],        # C's
    )
    # no ``name=``, and the callers' scope again here, inside the jitted
    # function: the device trace prints the kernel under the innermost scope
    # (``ssm._state_update.N``), as the attention kernels'
    with jax.named_scope("ssm._state_update"):
        y, view = pl.pallas_call(
            functools.partial(_kernel, tile=tile),
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct(a_row.shape, f32),
                       jax.ShapeDtypeStruct(view.shape, view.dtype)],
            # operand 8 (after the four prefetched scalars) is the pool
            input_output_aliases={8: 1},
            compiler_params=tpu_compiler_params(
                dimension_semantics=("arbitrary", "arbitrary")),
        )(order, count, slot_rows, at, a_row, dx_row, b.astype(f32)[:, None],
          c.astype(f32)[:, None], view)
    # a row without a step holds whatever the buffer held
    y = jnp.where((slot_rows != 0)[:, None, None],
                  y.reshape(rows, heads, width), 0.0)
    return y, view.reshape(pool.shape)
