"""Phi-4-mini-flash's language model ("SambaY") in plain ``jax.numpy``: the
benchmark's reference for ``correct`` (equations: ISSUE 63 / PERF.md, from
the published ``config.json`` and the design's papers; every inference is
under ``assumed`` in the configuration file).

``x_0 = E[ids]``; layer ``i``, pre-norm, LayerNorm with weight and bias:
``h = x + Mix_i(LN x)``, ``x' = h + MLP(LN h)``, ``MLP(u) = (silu(u W_g) *
(u W_u)) W_d``; a final LayerNorm and the embedding as the head (tied). No
positional encoding. With ``half = layers / 2``, by ``kinds[i]``:

- ``"mamba"`` (even ``i <= half``): ``[x | z] = u W_in``; ``x <-
  silu(conv(x) + b)``, depthwise over ``K`` taps with zeros before the
  start (the last tap meets the current position); ``[dt | B | C] = x
  W_x``; ``delta = softplus(dt W_dt + b_dt)``; ``A = -exp(A_log)``; a
  channel's states ``h_t[c, n] = exp(delta_t[c] A[c, n]) h_{t-1}[c, n] +
  delta_t[c] B_t[n] x_t[c]``, ``y_t[c] = sum_n C_t[n] h_t[c, n] + D[c]
  x_t[c]``; ``(y * silu(z)) W_out``. Layer ``half`` also hands on ``m = y``.
- ``"window"`` (odd ``i < half``): differential attention, a query sees
  itself and the ``window - 1`` positions before it.
- ``"full"`` (``i = half + 1``): differential attention, causal; its keys
  and values are what the cross layers read.
- ``"gmu"`` (even ``i > half``): ``(m * silu(u W_1)) W_2``.
- ``"cross"`` (odd ``i > half + 1``): ``q = u W_q + b`` against the full
  layer's keys and values, causal, differential.
- differential attention: query heads ``(2j, 2j + 1)`` are ``q1, q2``; KV
  heads ``(2m, 2m + 1)`` are ``k1, k2`` and ``v1, v2``, ``m = j // 2``;
  ``o_s = softmax(q_s k_s^T / sqrt(dk)) [v1 | v2]``; ``o = o_1 - lambda
  o_2``, ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``,
  ``lambda_init = 0.8 - 0.6 exp(-0.3 i)``; ``RMSNorm(o) (1 - lambda_init)``
  over the ``2 dk`` values; the pairs side by side into ``W_o`` (bias).

The recurrence runs ONE position at a time (``lax.scan`` over ``t``) on a
state ``[C, N]`` as it is written: no chunks, no lane layout, no state
handed between calls. float32, matmuls at ``highest`` precision, no kernel,
no cache, no batching of requests, and no call into ``deepspeed_tpu/``. It
reads the program's own parameter tree and upcasts one layer at a time;
attention runs a chunk of queries at a time, and the head a slice of the
vocabulary at a time (the embedding is 2 GB in float32). On the chip the
benchmark runs it a layer a program (:func:`logits_a_layer_a_program`),
beside the served weights and pools.

Departures from the source, each in form only: the SwiGLU's two input
matrices are apart (``gate_proj``, ``up_proj``: the source's is ``[W_g |
W_u]``), the convolution's weight is ``[channels, taps]``, and ``A_log`` is
``[channels, states]``.
"""

import math

import jax
import jax.numpy as jnp

_QUERY_CHUNK = 512
# positions a block of the MLP
_MLP_ROWS = 1024
# slices of the vocabulary the head is taken in
_VOCAB_SLICES = 8


def _f32(x):
    return x.astype(jnp.float32)


def _ln(x, p, eps):
    x = x - jnp.mean(x, -1, keepdims=True)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return x * _f32(p["scale"]) + _f32(p["bias"])


def _linear(x, p):
    y = x @ _f32(p["kernel"])
    return y + _f32(p["bias"]) if "bias" in p else y


def recurrence(x, delta, a, b, c, real=None):
    """``h_t = exp(delta_t A) h_{t-1} + (delta_t x_t) B_t^T``, ``y_t = h_t
    C_t`` from ``h = 0``, one position at a time: ``x`` / ``delta [rows, T,
    C]``, ``a [C, N]``, ``b`` / ``c [rows, T, N]`` -> ``(y [rows, T, C], the
    state [rows, C, N])``: the last position's, or with ``real [k]``
    (counts of positions) the states after positions ``real - 1``: ``[k,
    rows, C, N]``."""
    rows, seq, channels = x.shape
    first = jnp.zeros((rows, channels, a.shape[-1]), jnp.float32)
    if real is None:
        kept = first
    else:
        real = jnp.atleast_1d(jnp.asarray(real, jnp.int32))
        kept = jnp.zeros((real.shape[0], *first.shape), jnp.float32)

    def step(carry, at):
        h, kept = carry
        k, x, delta, b, c = at
        h = (jnp.exp(delta[..., None] * a) * h
             + (delta * x)[..., None] * b[:, None, :])
        y = jnp.einsum("rcn,rn->rc", h, c)
        if real is not None:
            kept = jnp.where((k == real - 1)[:, None, None, None], h[None],
                             kept)
        return (h, kept), y

    (state, kept), y = jax.lax.scan(step, (first, kept), (
        jnp.arange(seq), *(jnp.moveaxis(v, 1, 0) for v in (x, delta, b, c))))
    return jnp.moveaxis(y, 0, 1), state if real is None else kept


def mamba(u, p, shape, real=None):
    """The Mamba-1 mixer of whole sequences ``u [rows, T, d]`` -> ``(its
    term, the memory y [rows, T, C], the state [rows, C, N])``."""
    seq = u.shape[1]
    n = shape["ssm_state"]
    xz = u @ _f32(p["in_proj"])
    c = xz.shape[-1] // 2
    x, z = xz[..., :c], xz[..., c:]
    taps = _f32(p["conv"])                                   # [C, K]
    k = taps.shape[1]
    line = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(taps[:, j] * line[:, j:j + seq] for j in range(k))
    x = jax.nn.silu(conv + _f32(p["conv_bias"]))
    dbc = x @ _f32(p["x_proj"])
    rank = dbc.shape[-1] - 2 * n
    dt, b, cm = dbc[..., :rank], dbc[..., rank:rank + n], dbc[..., rank + n:]
    delta = jax.nn.softplus(dt @ _f32(p["dt_proj"]) + _f32(p["dt_bias"]))
    y, state = recurrence(x, delta, -jnp.exp(_f32(p["A_log"])), b, cm, real)
    y = y + _f32(p["D"]) * x
    return (y * jax.nn.silu(z)) @ _f32(p["out_proj"]["kernel"]), y, state


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def projected(x, p, shape):
    """``(q [rows, T, H, dk], k, v [rows, T, KV, dk])`` of an attention
    layer that has keys and values of its own."""
    rows, seq, hidden = x.shape
    heads, kv = shape["heads"], shape["kv_heads"]
    dh = hidden // heads
    qkv = _linear(x, p["qkv_proj"])
    q = qkv[..., :heads * dh].reshape(rows, seq, heads, dh)
    k = qkv[..., heads * dh:(heads + kv) * dh].reshape(rows, seq, kv, dh)
    v = qkv[..., (heads + kv) * dh:].reshape(rows, seq, kv, dh)
    return q, k, v


def differential(q, k, v, p, init, window=0):
    """Differential attention of queries ``q [rows, T, H, dk]`` over ``k``,
    ``v [rows, T, KV, dk]``, causal, inside ``window`` where there is one;
    ``init``: the layer's ``lambda_init``. -> ``[rows, T, H dk]``, what
    ``W_o`` takes."""
    rows, seq, heads, dh = q.shape
    pairs = k.shape[2] // 2
    group = heads // 2 // pairs          # query pairs a KV pair
    q = q.reshape(rows, seq, pairs, group, 2, dh)
    k = k.reshape(rows, seq, pairs, 2, dh)
    values = v.reshape(rows, seq, pairs, 2 * dh)             # [v1 | v2]
    pos = jnp.arange(seq)
    step = _QUERY_CHUNK if seq % _QUERY_CHUNK == 0 else seq

    def one_chunk(start):
        at = start + jnp.arange(step)
        seen = pos[None, :] <= at[:, None]
        if window:
            seen = seen & (pos[None, :] > at[:, None] - window)
        out = []
        for s in range(2):
            qs = jax.lax.dynamic_slice_in_dim(q[:, :, :, :, s], start, step, 1)
            a = jnp.einsum("rtmgd,rsmd->rmgts", qs, k[:, :, :, s]) \
                * dh ** -0.5
            a = jax.nn.softmax(jnp.where(seen, a, -jnp.inf), axis=-1)
            out.append(jnp.einsum("rmgts,rsme->rtmge", a, values))
        return out[0], out[1]

    o1, o2 = jax.lax.map(one_chunk, jnp.arange(0, seq, step))
    join = lambda o: o.transpose(1, 0, 2, 3, 4, 5).reshape(
        rows, seq, pairs, group, 2 * dh)
    o1, o2 = join(o1), join(o2)
    lam = (jnp.exp(jnp.sum(_f32(p["lambda_q1"]) * _f32(p["lambda_k1"])))
           - jnp.exp(jnp.sum(_f32(p["lambda_q2"]) * _f32(p["lambda_k2"])))
           + init)
    o = o1 - lam * o2
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + 1e-5) \
        * _f32(p["subln"]["scale"]) * (1.0 - init)
    return o.reshape(rows, seq, heads * dh)


def attention(x, p, shape, init, kind, shared=None):
    """An attention layer's term and the keys and values it leaves for the
    cross layers (``shared``: the full layer's, which a cross layer
    reads)."""
    if kind == "cross":
        rows, seq, hidden = x.shape
        q = _linear(x, p["q_proj"]).reshape(rows, seq, shape["heads"], -1)
        k, v = shared
    else:
        q, k, v = projected(x, p, shape)
    o = differential(q, k, v, p, init,
                     shape["window"] if kind == "window" else 0)
    return _linear(o, p["o_proj"]), (k, v)


def gmu(u, p, memory):
    gate = jax.nn.silu(u @ _f32(p["in_proj"]["kernel"]))
    return (memory * gate) @ _f32(p["out_proj"]["kernel"])


def _swiglu(x, p):
    gate, up, down = (_f32(p[f"{name}_proj"]["kernel"])
                      for name in ("gate", "up", "down"))

    def rows(x):
        return (jax.nn.silu(x @ gate) * (x @ up)) @ down

    seq = x.shape[1]
    if seq <= _MLP_ROWS or seq % _MLP_ROWS:
        return rows(x)
    # a block of positions at a time: 7,168 x 10,240 float32 three times
    # over is 0.9 GB beside the served weights and pools
    blocks = x.reshape(x.shape[0], seq // _MLP_ROWS, _MLP_ROWS, -1)
    return jax.lax.map(rows, blocks.swapaxes(0, 1)).swapaxes(0, 1).reshape(
        x.shape)


_MIXER = {"mamba": "mamba", "gmu": "gmu", "window": "attn", "full": "attn",
          "cross": "attn"}


def _layer(x, carried, p, kind, init, shape, real=None, hands_on=False):
    """One layer over the stream ``x``; ``carried = (memory, shared keys
    and values)``, what earlier layers handed on (zeros before they have);
    ``p = (norm, mixer, norm, mlp)``; ``init``: an attention layer's
    ``lambda_init``; ``hands_on``: the Mamba layer whose scan output is the
    memory. -> ``(x, carried, [rms of the stream, of the mixer's term, of
    the MLP's], the Mamba state or None)``."""
    norm1, mixer, norm2, mlp = p
    memory, shared = carried
    eps = shape["eps"]
    rms = lambda t: jnp.sqrt(jnp.mean(t * t))
    u = _ln(x, norm1, eps)
    state = None
    if kind == "mamba":
        a, m, state = mamba(u, mixer, shape, real)
        if hands_on:
            memory = m
    elif kind == "gmu":
        a = gmu(u, mixer, memory)
    else:
        a, kv = attention(u, mixer, shape, init, kind, shared)
        if kind == "full":
            shared = kv
    x = x + a
    y = _swiglu(_ln(x, norm2, eps), mlp)
    x = x + y
    return x, (memory, shared), jnp.stack([rms(x), rms(a), rms(y)]), state


def _layer_params(params, i, kind):
    at = f"layers_{i}"
    return (params[f"{at}_input_layernorm"], params[f"{at}_{_MIXER[kind]}"],
            params[f"{at}_post_attention_layernorm"], params[f"{at}_mlp"])


def _nothing_carried(x, params, shape):
    """What ``carried`` holds before the layers that fill it have run:
    shapes only (a jitted layer takes arrays)."""
    rows, seq, hidden = x.shape
    inner = params["layers_0_mamba"]["A_log"].shape[0]
    kv = jnp.zeros((rows, seq, shape["kv_heads"], hidden // shape["heads"]),
                   jnp.float32)
    return jnp.zeros((rows, seq, inner), jnp.float32), (kv, kv)


def _forward(params, input_ids, shape, real=None):
    """``(final stream, per layer the root mean squares of _layer, the
    Mamba layers' states in order)``."""
    x = _f32(params["embed_tokens"][input_ids])
    carried = _nothing_carried(x, params, shape)
    terms, states = [], []
    for i, kind in enumerate(shape["kinds"]):
        x, carried, seen, state = _layer(
            x, carried, _layer_params(params, i, kind), kind, lambda_init(i),
            shape, real, i == len(shape["kinds"]) // 2)
        terms.append(seen)
        if state is not None:
            states.append(state)
    return x, jnp.stack(terms), states


def _head(x, norm, table, shape, at=None):
    if at is not None:
        x = x[:, at]
    x = _ln(x, norm, shape["eps"])
    rows = table.shape[0]
    n = _VOCAB_SLICES if rows % _VOCAB_SLICES == 0 else 1
    return jnp.concatenate(
        [x @ _f32(part).T for part in jnp.split(table, n)], axis=-1)


def term_shares(params, input_ids, shape):
    """``[layers, 3]``: after each layer the root mean square of the
    residual stream, of the mixer's term and of the MLP's (the
    configuration file's ``weights`` quotes it)."""
    with jax.default_matmul_precision("highest"):
        return _forward(params, input_ids, shape)[1]


def logits(params, input_ids, shape, at=None):
    """Float32 logits of ``input_ids [rows, T]``: ``[rows, T, vocab]``, or
    with ``at [n]`` (positions) ``[rows, n, vocab]``. One traceable
    function."""
    with jax.default_matmul_precision("highest"):
        x, _, _ = _forward(params, input_ids, shape)
        return _head(x, params["norm"], params["embed_tokens"], shape, at)


def mamba_states(params, input_ids, shape, real):
    """The states each Mamba layer holds after positions ``real - 1`` of
    ``input_ids [rows, T]`` (``real [k]``: counts of positions): ``[mamba
    layers, k, rows, C, N]`` float32, as the recurrence writes them."""
    with jax.default_matmul_precision("highest"):
        return jnp.stack(_forward(params, input_ids, shape, real)[2])


def logits_a_layer_a_program(shape):
    """``f(params, input_ids, at=None, real=None)``: :func:`logits` as a
    numpy array, the same functions in the same order, each layer a compiled
    program of its own (one a kind of layer and width) and the head a block
    of positions and a slice of the vocabulary a program: what the device
    holds at once is one layer's float32 weights and temporaries beside the
    served weights and pools. With ``real`` (counts of positions, ``[k]``) it
    returns ``(logits, {mamba layer's place: its states after positions real
    - 1, [k, rows, C, N]})`` for the places in ``shape["kept_states"]``. Call
    it as it is, not under ``jax.jit``."""
    def highest(fn, **kw):
        def run(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args)
        return jax.jit(run, **kw)

    def one(kind, hands_on=False):
        def run(x, carried, p, init, real):
            x, carried, _, state = _layer(x, carried, p, kind, init, shape,
                                          real, hands_on)
            return x, carried, state
        return highest(run)

    half = len(shape["kinds"]) // 2
    layer = {kind: one(kind) for kind in _MIXER}
    memory_layer = one("mamba", True)
    embed = highest(lambda table, ids: _f32(table[ids]))
    normed = highest(lambda x, norm: _ln(x, norm, shape["eps"]))
    head = highest(lambda x, part: x @ _f32(part).T)

    def f(params, input_ids, at=None, real=None):
        import numpy as np

        x = embed(params["embed_tokens"], input_ids)
        carried = _nothing_carried(x, params, shape)
        states, place = {}, 0
        count = jnp.atleast_1d(jnp.asarray(
            input_ids.shape[1] if real is None else real, jnp.int32))
        for i, kind in enumerate(shape["kinds"]):
            run = memory_layer if i == half else layer[kind]
            x, carried, state = run(
                x, carried, _layer_params(params, i, kind),
                jnp.asarray(lambda_init(i), jnp.float32), count)
            if kind == "mamba":
                if place in shape.get("kept_states", ()):
                    states[place] = np.asarray(state)
                place += 1
        del carried
        if at is not None:
            x = x[:, at]
        table = params["embed_tokens"]
        n = _VOCAB_SLICES if table.shape[0] % _VOCAB_SLICES == 0 else 1
        step = table.shape[0] // n
        out = []
        # the head a block of positions and a slice of the vocabulary at a
        # time, joined on the host: 3,072 positions x 200,064 logits are 2.5
        # GB
        for first in range(0, x.shape[1], _QUERY_CHUNK):
            rows = normed(x[:, first:first + _QUERY_CHUNK], params["norm"])
            out.append(np.concatenate(
                [np.asarray(head(rows, table[j * step:(j + 1) * step]))
                 for j in range(n)], axis=-1))
        out = np.concatenate(out, axis=1)
        return out if real is None else (out, states)

    return f
