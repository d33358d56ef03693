"""MiMo-V2's language model in plain ``jax.numpy``: the benchmark's
reference for ``correct`` (equations: ISSUE 39 / PERF.md, from the
published ``config.json``; every inference is under ``assumed`` in the
configuration file).

Per layer ``l``, pre-norm, RMSNorm, no bias: ``x += Attn_l(norm x)``,
``x += FFN_l(norm x)``; then a final norm and an untied head.

- Attention: ``pattern[l]`` 0 = global, 1 = window (its own KV-head count,
  RoPE base, the last ``window`` keys inclusive of the query's own, and a
  learnable sink ``s_h`` a head: ``p_j = exp(a_j - m) / (exp(s_h - m) +
  sum_j' exp(a_j' - m))``). Queries and keys ``head_dim`` wide, the first
  ``rotary_dim`` of each rotated by halves; values ``v_head_dim`` wide and
  scaled; query head ``h`` reads KV head ``h // (heads / kv_heads)``.
- FFN: ``moe[l]`` 0 = SwiGLU; 1 = ``s = sigmoid(W_r x)`` over ALL published
  experts, the top ``k`` of ``s + b`` chosen, ``w = s[chosen] / sum``, and
  the sum over the chosen experts HELD HERE (``first_expert ..``) of
  ``w_k down_k(silu(gate_k x) * up_k x)``: the chip's share, as the program
  computes it.

float32, matmuls at ``highest`` precision, no kernel, no cache, and no call
into ``deepspeed_tpu/models/``. It reads the program's own parameter tree
and upcasts one layer (one expert) at a time; attention runs a KV head and
a chunk of queries at a time, so that 4,096 positions fit beside the
served weights and the pool.
"""

import jax
import jax.numpy as jnp

_QUERY_CHUNK = 512


def _f32(x):
    return x.astype(jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _f32(scale)


def _rotate(x, positions, rotary_dim, theta):
    """``x [rows, T, heads, d]``: the first ``rotary_dim`` dims of each head
    rotated, first half against second half; the rest passed through."""
    half = rotary_dim // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None] * inv[None]   # [T, half]
    cos, sin = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
    a, b, rest = x[..., :half], x[..., half:rotary_dim], x[..., rotary_dim:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], -1)


def _attention(x, p, shape, window, theta):
    rows, seq, _ = x.shape
    heads, dk, dv = shape["heads"], shape["head_dim"], shape["v_head_dim"]
    kv = shape["swa_kv_heads"] if window else shape["kv_heads"]
    group = heads // kv
    q = (x @ _f32(p["q_proj"]["kernel"])).reshape(rows, seq, heads, dk)
    k = (x @ _f32(p["k_proj"]["kernel"])).reshape(rows, seq, kv, dk)
    v = (x @ _f32(p["v_proj"]["kernel"])).reshape(rows, seq, kv, dv)
    v = v * shape["value_scale"]
    pos = jnp.arange(seq)
    q = _rotate(q, pos, shape["rotary_dim"], theta)
    k = _rotate(k, pos, shape["rotary_dim"], theta)
    sink = _f32(p["sink"]) if "sink" in p else None
    step = _QUERY_CHUNK if seq % _QUERY_CHUNK == 0 else seq

    def one_chunk(start):
        qc = jax.lax.dynamic_slice_in_dim(q, start, step, 1)
        qc = qc.reshape(rows, step, kv, group, dk)
        a = jnp.einsum("rtkgd,rskd->rkgts", qc, k) / dk ** 0.5
        i = (start + jnp.arange(step))[:, None]
        seen = pos[None, :] <= i
        if window:
            seen = seen & (pos[None, :] >= i - (window - 1))
        a = jnp.where(seen, a, -jnp.inf)
        m = a.max(-1, keepdims=True)
        if sink is not None:
            s = sink.reshape(1, kv, group, 1, 1)
            m = jnp.maximum(m, s)
        e = jnp.exp(a - m)
        denom = e.sum(-1, keepdims=True)
        if sink is not None:
            denom = denom + jnp.exp(s - m)
        out = jnp.einsum("rkgts,rskd->rtkgd", e / denom, v)
        return out.reshape(rows, step, heads * dv)

    chunks = jax.lax.map(one_chunk, jnp.arange(0, seq, step))
    y = chunks.transpose(1, 0, 2, 3).reshape(rows, seq, heads * dv)
    return y @ _f32(p["o_proj"]["kernel"])


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ _f32(gate)) * (x @ _f32(up))) @ _f32(down)


def routed(x, p, top_k, given=None):
    """``(chosen experts [tokens, k], weights [tokens, k], margin
    [tokens], differs [tokens])`` of ``x [tokens, d]``: the published gate.
    ``given
    [tokens, k]``: the sets another computation chose, taken in place of
    this gate's own wherever their first entry is not negative; the
    weights are this gate's scores of whatever is chosen. ``margin``: how
    far below this gate's own k-th selection score the lowest of the
    chosen lies (0 for its own choice): a set handed in is a near tie of
    this gate's only if that is rounding. ``differs``: the chosen set is
    not this gate's own."""
    scores = jax.nn.sigmoid(x @ _f32(p["router"]))
    select = scores + _f32(p["router_bias"])[None]
    best, own = jax.lax.top_k(select, top_k)
    chosen = own if given is None else jnp.where(given[:, :1] >= 0, given,
                                                 own)
    margin = best[:, -1] - jnp.take_along_axis(select, chosen, 1).min(-1)
    differs = (jnp.sort(chosen, -1) != jnp.sort(own, -1)).any(-1)
    picked = jnp.take_along_axis(scores, chosen, 1)
    return chosen, picked / picked.sum(-1, keepdims=True), margin, differs


def expert_terms(flat, p, first_expert, chosen, weights):
    """``flat [tokens, d]`` -> the sum over the chosen experts HELD here of
    ``w_k down_k(silu(gate_k x) * up_k x)``."""
    def one_expert(acc, expert):
        e, gate, up, down = expert
        w = jnp.sum(jnp.where(chosen == first_expert + e, weights, 0.0), -1)
        return acc + w[:, None] * _swiglu(flat, gate, up, down), None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(flat),
        (jnp.arange(p["gate"].shape[0]), p["gate"], p["up"], p["down"]))
    return out


def _sparse(x, p, shape, given=None):
    rows, seq, d = x.shape
    flat = x.reshape(rows * seq, d)
    chosen, weights, margin, differs = routed(
        flat, p, shape["top_k"],
        None if given is None else given.reshape(rows * seq, -1))
    out = expert_terms(flat, p, shape["first_expert"], chosen, weights)
    return (out.reshape(rows, seq, d), chosen.reshape(rows, seq, -1),
            {"margin": margin.reshape(rows, seq),
             "differs": differs.reshape(rows, seq)})


def _forward(params, input_ids, shape, given=None):
    """``(final residual stream, per sparse layer: its float32 input, the
    chosen experts, their margin and whether they differ)``. ``given [rows, T, sparse
    layers, k]``: see :func:`routed`."""
    x = _f32(params["embed_tokens"])[input_ids]
    eps, seen = shape["eps"], []
    for i, (window, sparse) in enumerate(zip(shape["pattern"],
                                             shape["moe"])):
        at = f"layers_{i}"
        x = x + _attention(
            _rms(x, params[f"{at}_input_layernorm"]["scale"], eps),
            params[f"{at}_attn"], shape,
            shape["window"] if window else 0,
            shape["swa_rope_theta"] if window else shape["rope_theta"])
        h = _rms(x, params[f"{at}_post_attention_layernorm"]["scale"], eps)
        mlp = params[f"{at}_mlp"]
        if sparse:
            y, picked, tie = _sparse(
                h, mlp, shape,
                None if given is None else given[:, :, len(seen)])
            seen.append((h, picked, tie))
        else:
            y = _swiglu(h, mlp["gate_proj"]["kernel"],
                        mlp["up_proj"]["kernel"], mlp["down_proj"]["kernel"])
        x = x + y
    return x, seen


def routed_sets(params, input_ids, shape):
    """``[sparse layers, rows, T, k]``: the experts the reference chooses
    for every token in every sparse layer."""
    with jax.default_matmul_precision("highest"):
        return jnp.stack([picked for _, picked, _ in
                          _forward(params, input_ids, shape)[1]])


def logits(params, input_ids, shape, given=None, with_layers=False):
    """``[rows, T, vocab]`` float32 logits of ``input_ids [rows, T]``.
    ``given [rows, T, sparse layers, k]`` int32: routed sets to take in
    place of the reference's own (negative: its own), for a comparison
    with a program whose sets flip at near ties. ``with_layers``: also
    ``{"inputs": [layers, rows, T, d], "margin": [layers, rows, T],
    "differs": [layers, rows, T]}``, each sparse layer's float32 input,
    how far from this gate's own choice the chosen sets lie, and where
    they are not its own."""
    with jax.default_matmul_precision("highest"):
        x, seen = _forward(params, input_ids, shape, given)
        x = _rms(x, params["norm"]["scale"], shape["eps"])
        out = x @ _f32(params["lm_head"]).T
        if not with_layers:
            return out
        return out, {"inputs": jnp.stack([h for h, _, _ in seen]),
                     "margin": jnp.stack([t["margin"] for *_, t in seen]),
                     "differs": jnp.stack([t["differs"] for *_, t in seen])}
