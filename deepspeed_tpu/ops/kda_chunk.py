"""The chunked form of a KDA layer's recurrence (the delta rule with a decay
a key channel), for the positions of a prompt or of a prefill chunk.

A head keeps a state ``S [K, V]`` and a position ``t`` does ``S' =
Diag(alpha_t) S``, ``S = S' + beta_t k_t (v_t - S'^T k_t)^T``, ``o_t = S^T
q_t`` (``ops/kda_state_update.py``), ``alpha_t = exp(g_t)``, ``g_t [K] <=
0``. A position at a time that is ``T`` dependent steps of no matmul. In
SUB-CHUNKS of ``C`` positions (16) it is a triangular system a sub-chunk,
all sub-chunks at once, and ONE dependent step a sub-chunk:

WITHIN a sub-chunk (:func:`sub_chunk_terms`, XLA: einsums and one
``solve_triangular``; every sub-chunk of the call in parallel). With ``G_i
= g_1 + .. + g_i`` the cumulative log decay from the sub-chunk's start,
``w_j = v_j - (Diag(alpha_j) S_{j-1})^T k_j`` solves the delta rule's
triangular system (the WY / UT form)

    (I + A) w = V - Kbar S_0,   A[j, l] = beta_l sum_c k_j[c] k_l[c]
                                          exp(G_j[c] - G_l[c]),  l < j

with ``Kbar_j = k_j * exp(G_j)``; and ``o_i = Qbar_i S_0 + sum_{j <= i}
B[i, j] w_j``, ``B`` as ``A`` with ``q_i`` for ``k_j`` and its diagonal.
``A`` and ``B`` are products of ``x * exp(G - G_mid)`` and ``k * exp(G_mid
- G)``, ``G_mid`` the cumulative decay at the sub-chunk's middle: every
exponent is bounded by half a sub-chunk at the lower bound (8 x 5 = 40:
``e^40``, inside float32, which is what ``kda_lower_bound`` is for), the
ones ABOVE the diagonal are masked, and every other exponent taken here is a
difference of cumulative decays that is ``<= 0`` (``exp(G_i)``, ``exp(G_C -
G_j)``). Nothing computes ``exp(-G)`` over a whole chunk.

BETWEEN sub-chunks (:func:`carry_state`): from the state ``S_0`` a
sub-chunk starts from, ``w = T V - T Kbar S_0`` (``T = (I + A)^-1``), ``o =
(Qbar - B T Kbar) S_0 + B T V`` and ``S_C = Diag(exp(G_C)) S_0 + Khat^T w``
with ``Khat_j = beta_j k_j * exp(G_C - G_j)``: two matmuls through the
state a sub-chunk, in order. Two forms of it under the scope ``kda_chunk``:
a Pallas kernel on a TPU (a grid step a row, a tile of heads and a
sub-chunk, the tile's state in VMEM from the row's first sub-chunk to its
last: as a scan in XLA the state, 2 MB a row and layer, goes through HBM 32
times a 512-token chunk), and that scan elsewhere.

A position with ``g = 0`` and ``beta = 0`` (a bucket's padding) leaves the
state as it is. The call starts from a state handed in and returns the one
it leaves, so a prompt's state crosses program calls. All float32, matmuls
at ``highest`` precision: the system is solved on differences of near-equal
terms.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from deepspeed_tpu.utils.compat import tpu_compiler_params

SUB_CHUNK = 16
# heads a grid step of the kernel: 8 x [128, 128] float32 of state in VMEM
HEAD_TILE = 8
_HIGHEST = jax.lax.Precision.HIGHEST


def kernel_serves(heads: int, key: int, value: int) -> bool:
    """Whether the kernel's tiles are whole registers at these sizes."""
    return key == 128 and value == 128 and heads >= 1


def sub_chunk_terms(q, k, v, g, beta, sub: int = SUB_CHUNK):
    """What the carry reads of each sub-chunk: ``q`` / ``k`` / ``g [B, T,
    H, K]``, ``v [B, T, H, V]``, ``beta [B, T, H]``, ``T`` in whole
    sub-chunks -> ``(wq [B, H, n, 2 C, K], uo [B, H, n, 2 C, V], khat [B,
    H, n, C, K], decay [B, H, n, 1, K])``: ``wq = [T Kbar | Qbar - B T
    Kbar]`` (what multiplies the incoming state), ``uo = [T V | B T V]``,
    the rows the outgoing state takes, and the sub-chunk's whole decay."""
    f32 = jnp.float32
    b, t, h, _ = q.shape
    n = t // sub

    def blocks(x):
        return x.astype(f32).reshape(b, n, sub, h, -1).transpose(
            0, 3, 1, 2, 4)

    q, k, v, g = (blocks(x) for x in (q, k, v, g))
    beta = blocks(beta[..., None])                        # [B, H, n, C, 1]
    cum = jnp.cumsum(g, axis=-2)
    mid = cum[..., sub // 2 - 1:sub // 2, :]
    up, down = jnp.exp(cum - mid), jnp.exp(mid - cum)
    k_down = k * down
    pairs = functools.partial(jnp.einsum, "...ic,...jc->...ij",
                              precision=_HIGHEST)
    i, j = jnp.arange(sub)[:, None], jnp.arange(sub)[None]
    by_beta = beta.swapaxes(-1, -2)                       # of the column
    a = jnp.where(j < i, pairs(k * up, k_down) * by_beta, 0.0)
    bm = jnp.where(j <= i, pairs(q * up, k_down) * by_beta, 0.0)
    decayed = jnp.exp(cum)
    kbar, qbar = k * decayed, q * decayed
    solved = jax.scipy.linalg.solve_triangular(
        a + jnp.eye(sub, dtype=f32), jnp.concatenate([kbar, v], -1),
        lower=True, unit_diagonal=True)
    mix = functools.partial(jnp.matmul, precision=_HIGHEST)
    through = mix(bm, solved)
    width = k.shape[-1]
    wq = jnp.concatenate([solved[..., :width], qbar - through[..., :width]],
                         axis=-2)
    uo = jnp.concatenate([solved[..., width:], through[..., width:]],
                         axis=-2)
    last = cum[..., -1:, :]
    return wq, uo, k * beta * jnp.exp(last - cum), jnp.exp(last)


def carry_state_xla(wq, uo, khat, decay, state):
    """The sub-chunks in order, a scan: ``state [B, H, K, V]`` -> ``(o [B,
    H, n, C, V], state after the last)``."""
    sub = khat.shape[-2]
    mix = functools.partial(jnp.matmul, precision=_HIGHEST)

    def one(s, terms):
        wq_, uo_, khat_, decay_ = terms
        through = mix(wq_, s)
        w = uo_[..., :sub, :] - through[..., :sub, :]
        o = uo_[..., sub:, :] + through[..., sub:, :]
        return (decay_.swapaxes(-1, -2) * s
                + mix(khat_.swapaxes(-1, -2), w)), o

    state, o = jax.lax.scan(
        one, state, tuple(jnp.moveaxis(x, 2, 0) for x in (wq, uo, khat,
                                                           decay)))
    return jnp.moveaxis(o, 0, 2), state


def _kernel(wq_ref, uo_ref, khat_ref, decay_ref, in_ref, o_ref, s_ref, *,
            tile):
    sub = khat_ref.shape[-2]
    keys, values = s_ref.shape[-2:]

    @pl.when(pl.program_id(2) == 0)
    def _start():
        s_ref[...] = in_ref[...]

    def head(h, carry):
        s = s_ref[h]
        through = jnp.dot(wq_ref[h], s, precision=_HIGHEST,
                          preferred_element_type=jnp.float32)
        w = uo_ref[h, :sub, :] - through[:sub]
        o_ref[h] = uo_ref[h, sub:, :] + through[sub:]
        decay = jnp.broadcast_to(decay_ref[h], (values, keys)).T
        s_ref[h] = decay * s + jax.lax.dot_general(
            khat_ref[h], w, (((0,), (0,)), ((), ())), precision=_HIGHEST,
            preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(0, tile, head, 0)


@functools.partial(jax.jit, static_argnames=("tile",))
def carry_state_kernel(wq, uo, khat, decay, state, tile: int = HEAD_TILE):
    """:func:`carry_state_xla`'s arguments and result through the Pallas
    kernel: the output state's block stays in VMEM across a row's
    sub-chunks (the same block index) and IS the carried state."""
    b, h, n, sub, keys = khat.shape
    values = uo.shape[-1]
    tile = min(tile, h)
    if h % tile:
        raise ValueError(f"{h} heads in tiles of {tile}")
    at = lambda r, j, c: (r, j, c, 0, 0)
    whole = lambda r, j, c: (r, j, 0, 0)
    f32 = jnp.float32
    with jax.named_scope("kda_chunk"):
        o, state = pl.pallas_call(
            functools.partial(_kernel, tile=tile),
            grid=(b, h // tile, n),
            in_specs=[pl.BlockSpec((None, tile, None, 2 * sub, keys), at),
                      pl.BlockSpec((None, tile, None, 2 * sub, values), at),
                      pl.BlockSpec((None, tile, None, sub, keys), at),
                      pl.BlockSpec((None, tile, None, 1, keys), at),
                      pl.BlockSpec((None, tile, keys, values), whole)],
            out_specs=[pl.BlockSpec((None, tile, None, sub, values), at),
                       pl.BlockSpec((None, tile, keys, values), whole)],
            out_shape=[jax.ShapeDtypeStruct((b, h, n, sub, values), f32),
                       jax.ShapeDtypeStruct(state.shape, f32)],
            compiler_params=tpu_compiler_params(
                dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        )(wq, uo, khat, decay, state.astype(f32))
    return o, state


def kda_chunk(q, k, v, g, beta, state, sub: int = SUB_CHUNK,
              use_kernel=None):
    """``q`` / ``k [B, T, H, K]`` (normalised), ``v [B, T, H, V]``, ``g [B,
    T, H, K]`` (the log decay, ``<= 0``; 0 at a padded position), ``beta
    [B, T, H]`` (0 at a padded position), ``state [B, H, K, V]``: the
    state before the call's first position. ``T`` any length (padded here
    to whole sub-chunks). -> ``(o [B, T, H, V] float32, state after the
    last position)``."""
    b, t, h, keys = q.shape
    pad = -t % sub
    if pad:
        q, k, v, g = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                      for x in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    if use_kernel is None:
        use_kernel = (jax.default_backend() == "tpu"
                      and kernel_serves(h, keys, v.shape[-1]))
    with jax.named_scope("kda._sub_chunk_terms"):
        terms = sub_chunk_terms(q, k, v, g, beta, sub)
    carry = carry_state_kernel if use_kernel else carry_state_xla
    o, state = carry(*terms, state.astype(jnp.float32))
    o = o.transpose(0, 2, 3, 1, 4).reshape(b, t + pad, h, -1)
    return o[:, :t], state
