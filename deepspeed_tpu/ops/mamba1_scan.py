"""The recurrence of a Mamba-1 layer (the selective scan), in its two serving
forms over one state pool.

A channel ``c`` of the layer's inner width ``C`` keeps ``N`` states (16
published) and decays each by ITS OWN rate: ``h_t[c, n] = exp(delta_t[c]
A[c, n]) h_{t-1}[c, n] + delta_t[c] B_t[n] x_t[c]``, ``y_t[c] = sum_n
C_t[n] h_t[c, n]`` (``A = -exp(A_log)``, ``[C, N]``; ``B_t``, ``C_t [N]``
shared by the channels; the ``D x`` term and the gate are the caller's). A
decay a (channel, state) pair is what parts it from Mamba-2
(``ops/ssd_chunk_scan.py``: a scalar a head, so a chunk is matmuls): here
the work of a position is ``C x N`` exponentials and multiply-adds on the
vector unit, whatever the form, and the forms differ in what they MOVE.

THE LAYOUT. A row of ``ssm_state_pool`` is ``[C / L, N, L]`` float32: lane
groups of ``L = 128`` channels along the lanes (all ``C`` where it is no
multiple of 128: the tests' sizes), the ``N`` states down the sublanes. A
step crosses no lane: ``delta`` and ``delta x`` are lane rows broadcast down
the sublanes, ``B_t`` and ``C_t`` columns broadcast along the lanes, the
readout a sum down the sublanes (a state size along the lanes read 33% of
its bound: PERF.md, PR 51). :func:`grouped` lays a ``[.., C]`` row out as
``[.., C / L, L]``; :func:`rate_lanes` the layer's ``A`` as ``[C / L, N,
L]``.

- :func:`mamba1_state_update`: one decode step, the pool in place. On a TPU
  a Pallas kernel with the pool aliased to its output: a grid step is a busy
  row (found through scalar prefetch: the block table's last entry), its
  state goes HBM -> VMEM -> HBM once, an idle slot has no step. Bound:
  ``2 x C x N x 4`` bytes a row a layer.
- :func:`mamba1_chunk_scan`: ``T`` positions from the state handed in, the
  state handed back: a prompt's state crosses program calls. On a TPU a
  Pallas kernel: a grid step is a row, a tile of lane groups and a block of
  positions, the tile's state in VMEM from the row's first block to its
  last and written once; ``[T, C, N]`` never exists in HBM. ``B_t`` and
  ``C_t`` reach it already broadcast along the lanes (``[T, 2 N, L]``, 8 MB
  a 512-token call, made in XLA): a column a position cannot be taken from a
  row of positions without crossing lanes.

Elsewhere (the CPU, sizes that are no whole registers) each has an XLA form
of the same float32 arithmetic: a gather, the update and a scatter; a
``lax.scan`` over the positions. A position with ``delta = 0`` (a bucket's
padding) leaves the state as it is; a row whose sequence starts here
(``fresh``) starts from zeros whatever its slot held.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.ssm_state_update import busy_rows  # noqa: F401
from deepspeed_tpu.utils.compat import tpu_compiler_params

LANES = 128
# lane groups a grid step of the chunk scan (8 x [16, 128] float32 of state)
GROUP_TILE = 8
# positions a grid step of the chunk scan: ``[64, 2 N, 128]`` float32 of
# broadcast ``B`` and ``C`` are 1 MB
TIME_BLOCK = 64


def lane_width(channels: int) -> int:
    return LANES if channels % LANES == 0 else channels


def kernel_serves(channels: int, n: int) -> bool:
    """Whether the kernels' blocks are whole registers: lane groups of 128
    channels, the states whole float32 sublane tiles."""
    return channels % LANES == 0 and n % 8 == 0


def grouped(v):
    """``[.., C] -> [.., C / L, L]``: a row of channels as lane groups."""
    lanes = lane_width(v.shape[-1])
    return v.reshape(*v.shape[:-1], v.shape[-1] // lanes, lanes)


def rate_lanes(a_log):
    """``A_log [C, N]`` (the parameter) -> ``A = -exp(A_log)`` as the state
    lies, ``[C / L, N, L]`` float32."""
    channels, n = a_log.shape
    lanes = lane_width(channels)
    a = -jnp.exp(a_log.astype(jnp.float32))
    return a.reshape(channels // lanes, lanes, n).swapaxes(1, 2)


def pool_row_shape(channels: int, n: int):
    """What a slot's row of ``ssm_state_pool`` holds a layer."""
    lanes = lane_width(channels)
    return (channels // lanes, n, lanes)


def _use_kernel(use_kernel, channels, n):
    if use_kernel is None:
        from deepspeed_tpu.ops.attention import use_decode_kernel

        use_kernel = use_decode_kernel()
    return bool(use_kernel) and kernel_serves(channels, n)


def _columns(b, c, lanes):
    """``B`` and ``C`` ``[.., N]`` one above the other and along the lanes:
    ``[.., 2 N, L]`` float32."""
    bc = jnp.concatenate([b, c], axis=-1).astype(jnp.float32)
    return jnp.broadcast_to(bc[..., None], (*bc.shape, lanes))


# ---------------------------------------------------------------------------
# a decode step

def state_update_xla(pool, layer, slot_rows, delta, x, fresh, a, b, c):
    """:func:`mamba1_state_update` in XLA: the rows' states gathered,
    updated and scattered. Idle rows (pool row 0) write row 0."""
    f32 = jnp.float32
    d, dx = grouped(delta.astype(f32)), grouped((delta * x).astype(f32))
    held = pool[layer, slot_rows].astype(f32)                # [B, G, N, L]
    held = jnp.where(fresh[:, None, None, None], 0.0, held)
    state = (jnp.exp(d[:, :, None] * a[None]) * held
             + dx[:, :, None] * b.astype(f32)[:, None, :, None])
    y = jnp.sum(state * c.astype(f32)[:, None, :, None], axis=2)
    return (y.reshape(x.shape),
            pool.at[layer, slot_rows].set(state.astype(pool.dtype)))


def _update_kernel(order_ref, count_ref, slots_ref, layer_ref, d_ref, dx_ref,
                   keep_ref, bc_ref, a_ref, pool_ref, y_ref, out_ref):
    del order_ref, count_ref, slots_ref, layer_ref
    groups, n, _ = a_ref.shape
    b_col, c_col = bc_ref[:n], bc_ref[n:]                    # [N, L]
    keep = keep_ref[...] > 0.0                               # [1, L]

    def group(g, carry):
        at = pl.ds(g, 1)
        held = jnp.where(keep, pool_ref[g].astype(jnp.float32), 0.0)
        state = (jnp.exp(d_ref[at, :] * a_ref[g]) * held
                 + dx_ref[at, :] * b_col)                    # [N, L]
        y_ref[at, :] = jnp.sum(state * c_col, axis=0, keepdims=True)
        out_ref[g] = state.astype(out_ref.dtype)
        return carry

    jax.lax.fori_loop(0, groups, group, 0)


@jax.jit
def _update(order, count, slot_rows, at, d, dx, keep, bc, a, pool):
    """The kernel call behind :func:`mamba1_state_update`, a jitted function
    of its own with the layer index an argument: a program's Mamba layers
    are ONE trace and ONE lowering of the kernel."""
    rows, groups, lanes = d.shape
    n = a.shape[1]
    f32 = jnp.float32
    by_row = pl.BlockSpec((None, groups, lanes),
                          lambda i, order, count, slots, at: (order[i], 0, 0))
    flag = pl.BlockSpec((None, 1, lanes),
                        lambda i, order, count, slots, at: (order[i], 0, 0))
    cols = pl.BlockSpec((None, 2 * n, lanes),
                        lambda i, order, count, slots, at: (order[i], 0, 0))
    rate = pl.BlockSpec((groups, n, lanes),
                        lambda i, order, count, slots, at: (0, 0, 0))
    in_pool = pl.BlockSpec(
        (None, None, groups, n, lanes),
        lambda i, order, count, slots, at: (at[0], slots[order[i]], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(jnp.maximum(count[0], 1),),
        in_specs=[by_row, by_row, flag, cols, rate, in_pool],
        out_specs=[by_row, in_pool],
    )
    # no ``name=``, and the scope here, inside the jitted function: the
    # device trace prints the kernel under the innermost scope
    # (``mamba1_state_update.N``), which the benchmark's reader matches
    with jax.named_scope("mamba1_state_update"):
        y, pool = pl.pallas_call(
            _update_kernel,
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct(d.shape, f32),
                       jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
            # operand 9 (after the four prefetched scalars) is the pool
            input_output_aliases={9: 1},
            compiler_params=tpu_compiler_params(
                dimension_semantics=("arbitrary",)),
        )(order, count, slot_rows, at, d, dx, keep, bc, a, pool)
    # a row without a step holds whatever the buffer held
    return jnp.where((slot_rows != 0)[:, None, None], y, 0.0), pool


def mamba1_state_update(pool, layer, slot_rows, delta, x, fresh, a, b, c,
                        work=None, use_kernel=None):
    """One decode step of layer ``layer`` of ``pool [layers, rows, C / L,
    N, L]`` float32, in place.

    ``slot_rows [B]``: each batch row's pool row (0: idle); ``delta`` / ``x
    [B, C]`` float32; ``fresh [B]`` bool: the sequence starts here (its
    state is zeros); ``a``: :func:`rate_lanes`; ``b`` / ``c [B, N]``;
    ``work``: :func:`busy_rows` of ``slot_rows``, made here if None.
    -> ``(y [B, C] float32, pool)``; an idle row's ``y`` is 0 under the
    kernel."""
    channels, n = x.shape[-1], b.shape[-1]
    if not _use_kernel(use_kernel, channels, n):
        return state_update_xla(pool, layer, slot_rows, delta, x, fresh, a,
                                b, c)
    f32 = jnp.float32
    order, count = busy_rows(slot_rows) if work is None else work
    delta = delta.astype(f32)
    keep = jnp.broadcast_to(
        jnp.where(fresh, 0.0, 1.0).astype(f32)[:, None, None],
        (x.shape[0], 1, LANES))
    y, pool = _update(order, count, jnp.asarray(slot_rows, jnp.int32),
                      jnp.asarray(layer, jnp.int32).reshape(1),
                      grouped(delta), grouped(delta * x.astype(f32)), keep,
                      _columns(b, c, LANES), a, pool)
    return y.reshape(x.shape), pool


# ---------------------------------------------------------------------------
# a chunk of positions

def chunk_scan_xla(x, delta, a, b, c, state):
    """:func:`mamba1_chunk_scan` as a ``lax.scan`` over the positions."""
    f32 = jnp.float32
    d, dx = grouped(delta.astype(f32)), grouped((delta * x).astype(f32))

    def step(h, at):
        d_t, dx_t, b_t, c_t = at
        h = (jnp.exp(d_t[:, :, None] * a[None]) * h
             + dx_t[:, :, None] * b_t[:, None, :, None])
        return h, jnp.sum(h * c_t[:, None, :, None], axis=2)

    over = lambda v: jnp.moveaxis(v.astype(f32), 1, 0)
    state, y = jax.lax.scan(step, state.astype(f32),
                            (over(d), over(dx), over(b), over(c)))
    return jnp.moveaxis(y, 0, 1).reshape(x.shape), state


def _scan_kernel(x_ref, d_ref, a_ref, bc_ref, s_in_ref, y_ref, s_out_ref,
                 held, *, tile, block, lanes):
    k = pl.program_id(2)
    n = a_ref.shape[1]

    @pl.when(k == 0)
    def _first():
        held[...] = s_in_ref[...]

    def eight(t8, carry):
        # eight positions a turn: a dynamic index loads and stores whole
        # sublane tiles, and the rows inside one are taken statically
        base = pl.multiple_of(t8 * 8, 8)
        rows = pl.ds(base, 8)
        for g in range(tile):
            of = slice(g * lanes, (g + 1) * lanes)
            d8 = d_ref[rows, of]                             # [8, L]
            dx8 = d8 * x_ref[rows, of]
            rate, state, ys = a_ref[g], held[g], []          # [N, L]
            for r in range(8):
                cols = bc_ref[base + r]                      # [2 N, L]
                state = (jnp.exp(d8[r:r + 1] * rate) * state
                         + dx8[r:r + 1] * cols[:n])
                ys.append(jnp.sum(state * cols[n:], axis=0, keepdims=True))
            held[g] = state
            y_ref[rows, of] = jnp.concatenate(ys, axis=0)
        return carry

    jax.lax.fori_loop(0, block // 8, eight, 0)

    @pl.when(k == pl.num_programs(2) - 1)
    def _last():
        s_out_ref[...] = held[...]


@functools.partial(jax.jit, static_argnames=("block",))
def _scan(x, delta, a, bc, state, *, block):
    rows, t, channels = x.shape
    groups, n, lanes = a.shape
    tile = GROUP_TILE if groups % GROUP_TILE == 0 else groups
    f32 = jnp.float32
    seq = pl.BlockSpec((None, block, tile * lanes), lambda r, j, k: (r, k, j))
    cols = pl.BlockSpec((None, block, 2 * n, lanes),
                        lambda r, j, k: (r, k, 0, 0))
    rate = pl.BlockSpec((tile, n, lanes), lambda r, j, k: (j, 0, 0))
    kept = pl.BlockSpec((None, tile, n, lanes), lambda r, j, k: (r, j, 0, 0))
    with jax.named_scope("mamba1_chunk_scan"):
        return pl.pallas_call(
            functools.partial(_scan_kernel, tile=tile, block=block,
                              lanes=lanes),
            grid=(rows, groups // tile, t // block),
            in_specs=[seq, seq, rate, cols, kept],
            out_specs=[seq, kept],
            out_shape=[jax.ShapeDtypeStruct(x.shape, f32),
                       jax.ShapeDtypeStruct(state.shape, f32)],
            scratch_shapes=[pltpu.VMEM((tile, n, lanes), f32)],
            compiler_params=tpu_compiler_params(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
        )(x, delta, a, bc, state)


def mamba1_chunk_scan(x, delta, a, b, c, state, use_kernel=None,
                      block: int = TIME_BLOCK):
    """``T`` positions of the recurrence from ``state``.

    ``x`` / ``delta [B, T, C]`` float32 (``delta`` 0 at a padded position);
    ``a``: :func:`rate_lanes`; ``b`` / ``c [B, T, N]``; ``state [B, C / L,
    N, L]`` float32. -> ``(y [B, T, C] float32, the state after the last
    position)``. The kernel serves whole registers and whole blocks of
    ``block`` positions; the ``lax.scan`` the rest."""
    t, channels = x.shape[1:]
    n = b.shape[-1]
    block = min(block, t)
    if not _use_kernel(use_kernel, channels, n) or t % block or block % 8:
        return chunk_scan_xla(x, delta, a, b, c, state)
    f32 = jnp.float32
    y, state = _scan(x.astype(f32), delta.astype(f32), a,
                     _columns(b, c, LANES), state.astype(f32), block=block)
    return y, state
