"""The chunked (matmul) form of a Mamba-2 layer's recurrence, for the
positions of a prompt or of a prefill chunk.

A head keeps a state ``S [P, N]`` (``P`` its width, ``N`` the state size),
and a position ``t`` does ``S_t = a_t S_{t-1} + (delta_t x_t) B_t^T``,
``y_t = S_t C_t`` with ``a_t = exp(delta_t A)``, ``A < 0`` a scalar a head
and ``B_t``, ``C_t [N]`` shared by the heads (one group). One position at a
time that is ``T`` dependent steps of no matmul at all. In chunks of ``Q``
positions (state-space duality) it is three matmuls a chunk and ONE
dependent step a chunk:

- within the chunk ``y = (L * (C B^T)) (delta x)``, ``L[t, s] = a_{s+1} ..
  a_t`` for ``s <= t`` and 0 above the diagonal;
- the chunk's incoming state through ``C``: ``y_t += (a_1 .. a_t) C_t S_in``;
- the state handed on: ``S_out = (a_1 .. a_Q) S_in + sum_s (a_{s+1} .. a_Q)
  (delta_s x_s) B_s^T``.

The products of ``a`` are exponentials of differences of ``cumsum(delta
A)`` (never above 0: nothing overflows). A position with ``delta = 0``
(a bucket's padding) has ``a = 1`` and adds nothing: it leaves the state
as it is, so the state after the last chunk is the state at the row's last
real position. The call starts from a state handed in and returns the one
it leaves, so a prompt's state crosses program calls.

The decay products and sums are float32; what the three matmuls read is
``dtype`` (bfloat16 in a served program, as every matmul's inputs there;
float32 accumulation). Two forms of the same arithmetic under the scope
``ssm._chunk_scan``: a Pallas kernel on a TPU (a grid step a row, a tile of
heads and a chunk, the chunks in order with the tile's state in VMEM
between them: a head's ``[Q, Q]`` decay matrix never reaches HBM), and XLA
einsums elsewhere and for sizes that are not whole registers (as einsums on
the chip the 64 heads' decay matrices are 16.8 MB a chunk and layer, in and
out of HBM a few times: PERF.md, PR 49).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.utils.compat import tpu_compiler_params

# heads a grid step of the kernel: 8 x 64 lanes of x and y
HEAD_TILE = 8


def ssd_chunk_scan(x, delta, a_log_rate, b, c, state, chunk: int,
                   dtype=jnp.float32, use_kernel=None):
    """``x [B, T, H, P]``, ``delta [B, T, H]`` (0 at a padded position),
    ``a_log_rate [H]`` (``A``, negative), ``b`` / ``c [B, T, N]``, ``state
    [B, H P / L, N, L]`` float32: the state before the call's first
    position, as a row of the decode state pool lies
    (``ops/ssm_state_update.py``: ``to_lanes`` of ``[B, H, P, N]``). ->
    ``(y [B, T, H, P] float32, state after the last position)``, the state
    in the same layout: the kernel reads and writes such blocks as they lie
    and turns them in VMEM at a row's first and last chunk, so a served
    chunk's state is never transposed through HBM (in XLA, around this call,
    the chip's compiler re-laid the whole pool: PERF.md, PR 51). ``T`` is
    padded up to whole chunks of ``chunk`` with ``delta = 0``.
    ``use_kernel``: None is the kernel where a TPU is and the sizes are
    whole registers."""
    from deepspeed_tpu.ops.attention import use_decode_kernel
    from deepspeed_tpu.ops.ssm_state_update import from_lanes, to_lanes

    rows, t, heads, width = x.shape
    pad = -t % chunk
    if pad:
        grow = lambda v: jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),)
                                 * (v.ndim - 2))
        x, delta, b, c = grow(x), grow(delta), grow(b), grow(c)
    if use_kernel is None:
        use_kernel = use_decode_kernel() and kernel_serves(
            chunk, heads, width, b.shape[-1])
    state = state.astype(jnp.float32)
    terms = (x, delta.astype(jnp.float32), a_log_rate.astype(jnp.float32), b,
             c)
    with jax.named_scope("ssm._chunk_scan"):
        if use_kernel:
            y, state = _scan_kernel(*terms, state, chunk, dtype)
        else:
            # the einsums take a head's state as a [P, N] matrix
            y, state = _scan_einsums(
                *terms, from_lanes(state, heads, width), chunk, dtype)
            state = to_lanes(state)
    return y[:, :t], state


def _scan_einsums(x, delta, a_log_rate, b, c, state, chunk, dtype):
    """Whole chunks of ``chunk`` positions, as XLA einsums under a scan
    over the chunks."""
    rows, full, heads, width = x.shape
    k = full // chunk
    f32 = jnp.float32
    # by chunk (the scan's axis first) and by head: a head's [Q, Q] decay
    # matrix keeps Q on the lanes
    split = lambda v: jnp.moveaxis(
        v.reshape(rows, k, chunk, *v.shape[2:]), 1, 0)
    dx = split(delta[..., None] * x.astype(f32)).transpose(
        0, 1, 3, 2, 4)                                       # [k,B,H,Q,P]
    la = split(delta * a_log_rate).transpose(0, 1, 3, 2)     # [k,B,H,Q]
    bs, cs = split(b), split(c)                             # [k,B,Q,N]
    below = jnp.tril(jnp.ones((chunk, chunk), bool))        # s <= t

    def one_chunk(s_in, args):
        dx, la, b, c = args
        cum = jnp.cumsum(la, axis=-1)                       # [B,H,Q]
        # L[t, s] = exp(cum_t - cum_s), s <= t
        diff = cum[..., :, None] - cum[..., None, :]        # [B,H,Qt,Qs]
        decay = jnp.exp(jnp.where(below, diff, -jnp.inf))
        scores = jnp.einsum("btn,bsn->bts", c.astype(dtype), b.astype(dtype),
                            preferred_element_type=f32)
        mixed = (decay * scores[:, None]).astype(dtype)
        y = jnp.einsum("bhts,bhsp->bhtp", mixed, dx.astype(dtype),
                       preferred_element_type=f32)
        y = y + jnp.exp(cum)[..., None] * jnp.einsum(
            "btn,bhpn->bhtp", c.astype(dtype), s_in.astype(dtype),
            preferred_element_type=f32)
        last = cum[..., -1:]                                # [B,H,1]
        carried = (jnp.exp(last - cum)[..., None] * dx).astype(dtype)
        s_out = jnp.exp(last)[..., None] * s_in + jnp.einsum(
            "bhsp,bsn->bhpn", carried, b.astype(dtype),
            preferred_element_type=f32)
        return s_out, y

    state, ys = jax.lax.scan(one_chunk, state, (dx, la, bs, cs))
    # [k,B,H,Q,P] -> [B, k Q, H, P]
    return ys.transpose(1, 0, 3, 2, 4).reshape(rows, full, heads,
                                               width), state


# ---------------------------------------------------------------------------
# the Pallas form

def kernel_serves(chunk: int, heads: int, width: int, n: int) -> bool:
    """Whether the kernel's blocks are whole registers at these sizes (a
    tile of heads whole lane groups of the state's layout, a head whole
    sublane tiles)."""
    tile = min(HEAD_TILE, heads)
    return (chunk % 128 == 0 and n % 128 == 0 and heads % tile == 0
            and (tile * width) % 128 == 0 and tile % 8 == 0
            and width % 8 == 0)


def _kernel(dx_ref, cum_ref, cum_t_ref, b_ref, c_ref, s_in_ref, y_ref,
            s_out_ref, state, *, tile, width, chunk, dtype):
    f32 = jnp.float32
    k = pl.program_id(2)

    # the tile's states [tile x P, N] between the chunks; in HBM they lie
    # as lane groups [N, 128 columns]: turned here, at a row's first and
    # last chunk
    groups, _, lanes = s_in_ref.shape

    @pl.when(k == 0)
    def _first():
        for g in range(groups):
            state[g * lanes:(g + 1) * lanes, :] = s_in_ref[g].T

    bv, cv = b_ref[...], c_ref[...]                          # [Q, N]
    nt = (((1,), (1,)), ((), ()))
    scores = jax.lax.dot_general(cv, bv, nt, preferred_element_type=f32)
    below = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
             >= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))
    for h in range(tile):
        # the head's cumulative log decay down the sublanes and along the
        # lanes: L[t, s] = exp(cum_t - cum_s), s <= t
        col = cum_ref[:, h:h + 1]                            # [Q, 1]
        row = cum_t_ref[h:h + 1, :]                          # [1, Q]
        decay = jnp.exp(jnp.where(below, col - row, -jnp.inf))
        mixed = (decay * scores).astype(dtype)
        dx = dx_ref[:, h * width:(h + 1) * width]            # [Q, P]
        s_in = state[h * width:(h + 1) * width, :]           # [P, N]
        y = jnp.dot(mixed, dx, preferred_element_type=f32)
        y = y + jnp.exp(col) * jax.lax.dot_general(
            cv, s_in.astype(dtype), nt, preferred_element_type=f32)
        last = cum_t_ref[h:h + 1, chunk - 1:chunk]           # [1, 1]
        carried = (jnp.exp(last - col) * dx.astype(f32)).astype(dtype)
        state[h * width:(h + 1) * width, :] = jnp.exp(
            last) * s_in + jax.lax.dot_general(
            carried, bv, (((0,), (0,)), ((), ())),
            preferred_element_type=f32)
        y_ref[:, h * width:(h + 1) * width] = y

    @pl.when(k == pl.num_programs(2) - 1)
    def _last():
        for g in range(groups):
            s_out_ref[g] = state[g * lanes:(g + 1) * lanes, :].T


def _scan_kernel(x, delta, a_log_rate, b, c, state, chunk, dtype):
    """Whole chunks of ``chunk`` positions, as the Pallas kernel; ``state
    [B, H P / 128, N, 128]``, in and out."""
    rows, full, heads, width = x.shape
    n = b.shape[-1]
    tile = min(HEAD_TILE, heads)
    tiles = heads // tile
    lanes = state.shape[-1]
    k = full // chunk
    f32 = jnp.float32
    dx = (delta[..., None] * x.astype(f32)).astype(dtype).reshape(
        rows, full, heads * width)
    # the cumulative log decay inside each chunk, float32, in the two
    # layouts the kernel reads: positions down the sublanes, and along the
    # lanes
    cum = jnp.cumsum((delta * a_log_rate).reshape(
        rows, k, chunk, tiles, tile), axis=2)
    cum_s = cum.reshape(rows, full, tiles, tile).transpose(0, 2, 1, 3)
    cum_t = cum_s.swapaxes(2, 3)                             # [B,tiles,tile,T]
    seq = lambda lanes: pl.BlockSpec((None, chunk, lanes),
                                     lambda r, j, k: (r, k, 0))
    held = pl.BlockSpec((None, tile * width // lanes, n, lanes),
                        lambda r, j, k: (r, j, 0, 0))
    by_head = pl.BlockSpec((None, chunk, tile * width),
                           lambda r, j, k: (r, k, j))
    y, state = pl.pallas_call(
        functools.partial(_kernel, tile=tile, width=width, chunk=chunk,
                          dtype=dtype),
        grid=(rows, tiles, k),
        in_specs=[by_head,
                  pl.BlockSpec((None, None, chunk, tile),
                               lambda r, j, k: (r, j, k, 0)),
                  pl.BlockSpec((None, None, tile, chunk),
                               lambda r, j, k: (r, j, 0, k)),
                  seq(n), seq(n), held],
        out_specs=[by_head, held],
        out_shape=[jax.ShapeDtypeStruct((rows, full, heads * width), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        scratch_shapes=[pltpu.VMEM((tile * width, n), f32)],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(dx, cum_s, cum_t, b.astype(dtype), c.astype(dtype), state)
    return y.reshape(rows, full, heads, width), state
