"""One decode step of a KDA layer's recurrence (Kimi Delta Attention: the
delta rule with a decay for every key channel), on the state pool in place.

A decode slot keeps, a layer and head, a state ``S [K, V]`` (``K`` the key
width, ``V`` the value width: 128 x 128 float32 at the published widths)
in row ``1 + slot`` of ``kda_state_pool [layers, 1 + slots, H, K, V]`` (row
0: what idle rows would write). A step does, a busy row and head,

    S' = Diag(alpha) S            alpha [K] in (e^-5, 1), a key channel's own
    u  = beta (v - S'^T k)        the delta rule's correction, [V]
    S  = S' + k u^T               rank one
    o  = S^T q                    [V]

so every value of the state is read once and written once, and nothing else
of any size moves: the step is bound by the state's bytes, twice. Both
products and the rank-one update happen on the tile while it is in VMEM.

THE LAYOUT is the allocation's: a head's ``[K, V]`` tile has the key
channels down the sublanes and the values along the lanes (whole registers
at 128 x 128 either way round). In it the two products ``S'^T k`` and
``S'^T q`` are sums DOWN the sublanes (adds of whole registers and one
sublane reduction) and ``v``, ``u`` and ``o`` are dense lane rows; what
varies by key channel (``alpha``, ``k``, ``q``) arrives as lane rows and is
needed as a COLUMN broadcast along the lanes. The kernel turns each of the
three ONCE A GRID STEP, for all the step's heads together (``[tile, K] ->
[K, tile]``: the one transpose of the step, into VMEM scratch), and a head
then takes its own lane of the turned tile across all 128 (a lane
broadcast a register: sixteen an operand). Until PR 58 each column was a
transpose of a sublane-broadcast ``[128, 128]`` tile, three a head: sixteen
registers in and sixteen out for 128 lanes that are all alike. Both
products come off ONE pass over the decayed state, ``o = S'^T q + (k . q)
u`` (``S^T q`` with ``S = S' + k u^T`` multiplied out), so the ``q`` product
does not wait for the rank-one update; the state written is the same to the
bit, ``o`` rounds one float32 sum otherwise. On a v5e every cross-lane
operation (a transpose's register, a lane broadcast, a lane sum) is a push
and a pop on one of three units that each take one every 6-7 cycles, so
the 48 lane broadcasts a head keep them busy for some 110 cycles: the
loop body a head is 139 bundles (169 until PR 58) against 154 cycles of a
head's bytes (PERF.md, PR 58).

- :func:`state_update_kernel`: the Pallas kernel. The pool is aliased to
  its output; a grid step is a busy row and a tile of heads (the whole row
  at the published 32), the row's pool row found through scalar prefetch
  (the block table's last entry), so a state goes HBM -> VMEM -> HBM once,
  and an idle slot has no step (its state is not touched, its ``o`` is
  zero). It serves where :func:`kernel_serves` says the tiles are whole
  registers.
- :func:`state_update_xla`: the same float32 arithmetic in XLA (a gather,
  the update, a scatter), elsewhere and where no TPU is.

Both take ``alpha`` already zeroed for a row whose sequence starts here,
which is how a slot's last tenant's state is forgotten (its values are
finite: ``0 * S = 0``). The arithmetic and the pool are float32: the
correction ``v - S'^T k`` is a difference of near-equal terms once a key
has been written, and a bfloat16 state loses it (``models/
bailing_hybrid.py`` says what was read on the chip).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.ssm_state_update import busy_rows  # noqa: F401
from deepspeed_tpu.utils.compat import tpu_compiler_params

LANES = 128
# heads a grid step, at most: the whole row at the published 32 heads. 32 x
# [128, 128] float32 are 2 MB a block, in and out double-buffered 8 MB of
# VMEM beside 192 kB of turned operands (inside the 16 MB a kernel may take
# by default). On a v5e the probe (tools/probe_kda_state_update.py, 7 layer
# calls on the cell's pool, 40 / 85 / 110 busy rows) read tiles of 8 / 16 /
# 32 heads at 62.8-64.1% / 69.9-71.6% / 73.5-75.8% of the bytes' time
# (PERF.md, PR 58): a grid step's own cost is paid once a row
HEAD_TILE = 32


def kernel_serves(heads: int, key: int, value: int) -> bool:
    """Whether the Pallas kernel's tiles are whole registers at these
    sizes: a head's state 128 lanes of values, and 128 key channels (a
    turned operand's sublanes are the state tile's)."""
    return key == LANES and value == LANES and heads >= 1


def state_update_xla(pool, layer, slot_rows, alpha, k, v, q, beta):
    """``pool [layers, rows, H, K, V]`` float32; ``slot_rows [B]``;
    ``alpha`` / ``k`` / ``q [B, H, K]``, ``v [B, H, V]``, ``beta [B, H]``.
    -> ``(o [B, H, V] float32, pool)``. Idle rows (pool row 0) write row
    0."""
    f32 = jnp.float32
    alpha, k, v, q, beta = (x.astype(f32) for x in (alpha, k, v, q, beta))
    state = alpha[..., None] * pool[layer, slot_rows].astype(f32)
    u = beta[..., None] * (v - jnp.sum(state * k[..., None], axis=2))
    state = state + k[..., None] * u[:, :, None]
    o = jnp.sum(state * q[..., None], axis=2)
    return o, pool.at[layer, slot_rows].set(state.astype(pool.dtype))


def _kernel(order_ref, count_ref, slots_ref, layer_ref, alpha_ref, k_ref,
            q_ref, v_ref, beta_ref, pool_ref, o_ref, out_ref, alpha_t, k_t,
            q_t, kq_row, *, tile):
    del order_ref, count_ref, slots_ref, layer_ref
    keys = pool_ref.shape[-2]
    # the step's one change of axis, for all its heads: [tile, K] -> [K,
    # tile], head h in lane h
    alpha_t[:, :tile] = alpha_ref[...].T
    k_t[:, :tile] = k_ref[...].T
    q_t[:, :tile] = q_ref[...].T
    # k . q a head, across the lanes of its row
    kq_row[...] = jnp.broadcast_to(jnp.sum(
        k_ref[...] * q_ref[...], axis=1, keepdims=True), kq_row.shape)

    def head(h, carry):
        at = pl.ds(h, 1)
        lane = jnp.full((keys, LANES), h, jnp.int32)
        # a head's lane of a turned tile across all the lanes: [K, V]
        column = lambda turned: jnp.take_along_axis(turned[...], lane, axis=1)
        k_col = column(k_t)
        state = column(alpha_t) * pool_ref[h].astype(jnp.float32)
        miss = v_ref[at, :] - jnp.sum(state * k_col, axis=0, keepdims=True)
        read = jnp.sum(state * column(q_t), axis=0, keepdims=True)
        u = beta_ref[at, :] * miss                            # [1, V]
        o_ref[at, :] = read + kq_row[at, :] * u
        out_ref[h] = (state + k_col * u).astype(out_ref.dtype)
        return carry

    jax.lax.fori_loop(0, tile, head, 0)


def state_update_kernel(pool, layer, slot_rows, alpha, k, v, q, beta,
                        work=None, head_tile: int = HEAD_TILE):
    """:func:`state_update_xla`'s arguments and result, the pool updated in
    place (aliased), idle rows skipped (their ``o`` is 0). ``work``:
    :func:`busy_rows` of ``slot_rows``; ``head_tile``: the most heads a
    grid step takes."""
    _, heads, keys = k.shape
    if not kernel_serves(heads, keys, v.shape[-1]):
        raise ValueError(
            f"{heads} heads of {keys} x {v.shape[-1]} are not whole "
            "registers (kernel_serves): state_update_xla serves them")
    # the most heads a step that divide the row, not above ``head_tile``
    tile = max(t for t in range(1, min(head_tile, heads) + 1)
               if heads % t == 0)
    order, count = busy_rows(slot_rows) if work is None else work
    return _update(order, count, jnp.asarray(slot_rows, jnp.int32),
                   jnp.asarray(layer, jnp.int32).reshape(1), alpha, k, v, q,
                   beta, pool, tile=tile)


@functools.partial(jax.jit, static_argnames=("tile",))
def _update(order, count, slot_rows, at, alpha, k, v, q, beta, pool, *, tile):
    """The kernel call behind :func:`state_update_kernel`, a jitted function
    of its own with the layer index an argument: a program's KDA layers are
    ONE trace and ONE lowering of the kernel (a process pays those at every
    start, compile cache or not: ``setup_s``)."""
    rows, heads, keys = k.shape
    values = v.shape[-1]
    f32 = jnp.float32
    tiles = heads // tile
    by_head = lambda x, width: x.astype(f32).reshape(rows, tiles, tile, width)
    row = lambda i, j, order, count, slots, at: (order[i], j, 0, 0)
    state = lambda i, j, order, count, slots, at: (
        at[0], slots[order[i]], j, 0, 0)
    key_rows = pl.BlockSpec((None, None, tile, keys), row)
    value_rows = pl.BlockSpec((None, None, tile, values), row)
    in_pool = pl.BlockSpec((None, None, tile, keys, values), state)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(jnp.maximum(count[0], 1), tiles),
        in_specs=[key_rows, key_rows, key_rows, value_rows, value_rows,
                  in_pool],
        out_specs=[value_rows, in_pool],
        scratch_shapes=[pltpu.VMEM((keys, LANES), f32),      # alpha turned
                        pltpu.VMEM((keys, LANES), f32),      # k
                        pltpu.VMEM((keys, LANES), f32),      # q
                        pltpu.VMEM((tile, LANES), f32)],     # k . q
    )
    # no ``name=``: the device trace prints the kernel under the innermost
    # scope (``kda_state_update.N``), which the benchmark's reader matches
    with jax.named_scope("kda_state_update"):
        o, pool = pl.pallas_call(
            functools.partial(_kernel, tile=tile),
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((rows, tiles, tile, values), f32),
                       jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
            # operand 9 (after the four prefetched scalars) is the pool
            input_output_aliases={9: 1},
            compiler_params=tpu_compiler_params(
                dimension_semantics=("arbitrary", "arbitrary")),
        )(order, count, slot_rows, at, by_head(alpha, keys),
          by_head(k, keys), by_head(q, keys), by_head(v, values),
          by_head(jnp.broadcast_to(beta[..., None], v.shape), values), pool)
    # a row without a step holds whatever the buffer held
    o = jnp.where((slot_rows != 0)[:, None, None],
                  o.reshape(rows, heads, values), 0.0)
    return o, pool
