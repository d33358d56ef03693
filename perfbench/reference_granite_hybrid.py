"""Granite-4.0-H's dense language model in plain ``jax.numpy``: the
benchmark's reference for ``correct`` (equations: ISSUE 49 / PERF.md, from
the published ``config.json``; every inference is under ``assumed`` in the
configuration file).

``x_0 = embedding_multiplier * E[ids]``; layer ``i``, pre-norm, RMSNorm
with a learned weight, no bias in a projection: ``h = x + r Mix_i(norm
x)``, ``x' = h + r MLP(norm h)`` with ``r = residual_multiplier`` and
``MLP(u) = (silu(u W_g) * (u W_u)) W_d``; then a final norm, the embedding
as the head (tied) and ``/ logits_scaling``.

- ``types[i] == "attention"``: ``heads`` query heads over ``kv_heads``
  key/value heads of ``hidden / heads``, NO rotation, causal ``softmax(q
  k^T * attention_multiplier) v``.
- ``"mamba"``: ``[z | xBC | dt] = u W_in``; ``xBC <- silu(conv(xBC) + b)``,
  depthwise over ``K`` taps with zeros before the sequence's start (the
  last tap meets the current position); ``x [H, P] | B [N] | C [N]``;
  ``delta = softplus(dt + dt_bias)``, ``a = exp(-delta exp(A_log))``; a
  head's state ``S_t = a_t S_{t-1} + delta_t x_t B_t^T``, ``y_t = S_t C_t
  + D x_t``; ``RMSNorm_w(y * silu(z)) W_out``, the norm over all ``H P``.

The recurrence runs ONE position at a time (``lax.scan`` over ``t``): no
chunks, no matmul form, no state handed between calls. float32, matmuls at
``highest`` precision, no kernel, no cache, and no call into
``deepspeed_tpu/models/``. It reads the program's own parameter tree and
upcasts one layer at a time (12.8 GB of float32 weights never exist);
attention runs a chunk of queries at a time. On the chip the benchmark runs
it a layer a program (:func:`logits_a_layer_a_program`), beside 11 GB of
served weights and pools.

Departures from the source, each in form only: the SwiGLU's two input
matrices are apart (``gate_proj``, ``up_proj``: the source's
``input_linear`` is ``[W_g | W_u]``), and the convolution's weight is
``[channels, taps]`` (the source's has a middle axis of 1).
"""

import jax
import jax.numpy as jnp

from perfbench.reference_mimo_v2 import _f32, _rms, _swiglu

_QUERY_CHUNK = 512


def recurrence(x, delta, a, b, c):
    """``S_t = a_t S_{t-1} + (delta_t x_t) B_t^T``, ``y_t = S_t C_t`` from
    ``S = 0``, one position at a time: ``x [rows, T, H, P]``, ``delta`` /
    ``a [rows, T, H]``, ``b`` / ``c [rows, T, N]`` -> ``(y [rows, T, H, P],
    the last state [rows, H, P, N])``."""
    rows, _, heads, width = x.shape

    def step(state, at):
        x, delta, a, b, c = at
        state = (a[..., None, None] * state
                 + (delta[..., None] * x)[..., None] * b[:, None, None, :])
        return state, jnp.einsum("rhpn,rn->rhp", state, c)

    first = jnp.zeros((rows, heads, width, b.shape[-1]), jnp.float32)
    state, y = jax.lax.scan(step, first, tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, delta, a, b, c)))
    return jnp.moveaxis(y, 0, 1), state


def mamba(u, p, shape):
    """The Mamba-2 mixer of whole sequences ``u [rows, T, d]``."""
    rows, seq, _ = u.shape
    heads, width, n = shape["ssm_heads"], shape["ssm_head"], shape["ssm_state"]
    inner = heads * width
    mixed = u @ _f32(p["in_proj"])
    z, xbc, dt = (mixed[..., :inner], mixed[..., inner:-heads],
                  mixed[..., -heads:])
    taps = _f32(p["conv"])                                   # [C, K]
    k = taps.shape[1]
    line = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(taps[:, j] * line[:, j:j + seq] for j in range(k))
    xbc = jax.nn.silu(conv + _f32(p["conv_bias"]))
    x = xbc[..., :inner].reshape(rows, seq, heads, width)
    b, c = xbc[..., inner:inner + n], xbc[..., inner + n:]
    delta = jax.nn.softplus(dt + _f32(p["dt_bias"]))
    a = jnp.exp(-delta * jnp.exp(_f32(p["A_log"])))
    y, _ = recurrence(x, delta, a, b, c)
    y = y + _f32(p["D"])[:, None] * x
    gated = y.reshape(rows, seq, inner) * jax.nn.silu(z)
    return _rms(gated, p["norm"]["scale"], shape["eps"]) \
        @ _f32(p["out_proj"]["kernel"])


def attention(x, p, shape):
    rows, seq, hidden = x.shape
    heads, kv = shape["heads"], shape["kv_heads"]
    dh, group = hidden // heads, heads // kv
    q = (x @ _f32(p["q_proj"]["kernel"])).reshape(rows, seq, heads, dh)
    k = (x @ _f32(p["k_proj"]["kernel"])).reshape(rows, seq, kv, dh)
    v = (x @ _f32(p["v_proj"]["kernel"])).reshape(rows, seq, kv, dh)
    pos = jnp.arange(seq)
    step = _QUERY_CHUNK if seq % _QUERY_CHUNK == 0 else seq

    def one_chunk(start):
        qc = jax.lax.dynamic_slice_in_dim(q, start, step, 1)
        a = jnp.einsum("rtkgd,rskd->rkgts",
                       qc.reshape(rows, step, kv, group, dh),
                       k) * shape["attention_multiplier"]
        seen = pos[None, :] <= (start + jnp.arange(step))[:, None]
        a = jax.nn.softmax(jnp.where(seen, a, -jnp.inf), axis=-1)
        return jnp.einsum("rkgts,rskd->rtkgd", a, v).reshape(
            rows, step, hidden)

    chunks = jax.lax.map(one_chunk, jnp.arange(0, seq, step))
    y = chunks.transpose(1, 0, 2, 3).reshape(rows, seq, hidden)
    return y @ _f32(p["o_proj"]["kernel"])


def _layer(x, p, kind, shape):
    """One layer over the stream ``x``: ``p = (norm, mixer, norm, mlp)``,
    its four entries of the parameter tree. -> ``(x, [rms of the stream, of
    the mixer's term, of the MLP's])``."""
    norm1, mixer, norm2, mlp = p
    eps, r = shape["eps"], shape["residual_multiplier"]
    rms = lambda t: jnp.sqrt(jnp.mean(t * t))
    u = _rms(x, norm1["scale"], eps)
    a = r * (mamba(u, mixer, shape) if kind == "mamba"
             else attention(u, mixer, shape))
    x = x + a
    h = _rms(x, norm2["scale"], eps)
    y = r * _swiglu(h, mlp["gate_proj"]["kernel"], mlp["up_proj"]["kernel"],
                    mlp["down_proj"]["kernel"])
    x = x + y
    return x, jnp.stack([rms(x), rms(a), rms(y)])


def _layer_params(params, i, kind):
    at = f"layers_{i}"
    return (params[f"{at}_input_layernorm"],
            params[f"{at}_{'mamba' if kind == 'mamba' else 'attn'}"],
            params[f"{at}_post_attention_layernorm"], params[f"{at}_mlp"])


def _embed(table, input_ids, shape):
    return shape["embedding_multiplier"] * _f32(table[input_ids])


def _head(x, norm, table, shape, at=None):
    if at is not None:
        x = x[:, at]
    x = _rms(x, norm["scale"], shape["eps"])
    return x @ _f32(table).T / shape["logits_scaling"]


def _forward(params, input_ids, shape):
    """``(final residual stream, per layer: the root mean square of the
    stream and of the two terms it gained)``."""
    x = _embed(params["embed_tokens"], input_ids, shape)
    terms = []
    for i, kind in enumerate(shape["types"]):
        x, seen = _layer(x, _layer_params(params, i, kind), kind, shape)
        terms.append(seen)
    return x, jnp.stack(terms)


def term_shares(params, input_ids, shape):
    """``[layers, 3]``: after each layer the root mean square of the
    residual stream, of the mixer's term and of the MLP's, multipliers
    included: what share of the stream each kind of layer adds (the
    configuration file's ``weights`` quotes it)."""
    with jax.default_matmul_precision("highest"):
        return _forward(params, input_ids, shape)[1]


def logits(params, input_ids, shape, at=None):
    """Float32 logits of ``input_ids [rows, T]``: ``[rows, T, vocab]``, or
    with ``at [n]`` (positions) ``[rows, n, vocab]``. One traceable
    function: jitted whole, its program holds every layer."""
    with jax.default_matmul_precision("highest"):
        x, _ = _forward(params, input_ids, shape)
        return _head(x, params["norm"], params["embed_tokens"], shape, at)


def logits_a_layer_a_program(shape):
    """``f(params, input_ids, at=None)``: :func:`logits`, the same
    functions in the same order, with each layer a compiled program of its
    own (one a kind of layer and width, run 36 and 4 times): what the
    device holds at once is one layer's float32 weights and temporaries,
    where the whole model in one program asked for 5.3 GB at 8,192
    positions beside 11 GB of served weights and pools."""
    def highest(fn):
        def run(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args)
        return jax.jit(run)

    layer = {kind: highest(lambda x, p, kind=kind: _layer(x, p, kind,
                                                          shape)[0])
             for kind in ("mamba", "attention")}
    embed = highest(lambda table, ids: _embed(table, ids, shape))
    head = highest(lambda x, norm, table, at: _head(x, norm, table, shape,
                                                    at))

    def f(params, input_ids, at=None):
        x = embed(params["embed_tokens"], input_ids)
        for i, kind in enumerate(shape["types"]):
            x = layer[kind](x, _layer_params(params, i, kind))
        return head(x, params["norm"], params["embed_tokens"], at)

    return f
