"""K-EXAONE's language model (``model_type: exaone_moe``) in plain
``jax.numpy``: the benchmark's reference for ``correct`` (equations: ISSUE
52 / PERF.md, from the published ``config.json``; every inference is under
``assumed`` in the configuration file, and this file departs from the
published description in nothing else).

Per layer ``l``, pre-norm, RMSNorm, no bias: ``x += Attn_l(norm x)``,
``x += FFN_l(norm x)``; then a final norm and an untied head.

- Attention: ``q``, ``k``, ``v`` heads of ``head_dim``; RMSNorm over every
  ``q`` and ``k`` head (one weight a projection) BEFORE any rotation.
  ``window[l]`` > 0 (``sliding_attention``): every dim of each head rotated
  by halves (RoPE, ``rope_theta``), query ``i`` sees keys ``j`` with ``0 <=
  i - j < window``; 0 (``full_attention``): NO rotation, causal over the
  whole context. Scores ``q k / sqrt(head_dim)``, softmax, no sink; query
  head ``h`` reads KV head ``h // (heads / kv_heads)``.
- FFN: ``sparse[l]`` false = SwiGLU; true = ``s = sigmoid(W_r x)`` over ALL
  published experts, the top ``k`` of ``s + b`` chosen, ``w = s[chosen] /
  (sum + 1e-20) x route_scale``, and the sum over the chosen experts HELD
  HERE (``first_expert ..``) of ``w_k down_k(silu(gate_k x) * up_k x)``,
  plus the shared expert's SwiGLU, ungated: the chip's share, as the
  program computes it.

``balanced_biases`` is no part of the model: it makes seeded weights'
selection biases what training leaves them (the configuration file's
``weights.selection_bias_balance``), through this same forward pass.

float32, matmuls at ``highest`` precision, no kernel, no cache, and no call
into ``deepspeed_tpu/models/``. It reads the program's own parameter tree
and upcasts one matrix (one expert) at a time; attention runs a chunk of
queries at a time, so that 4,096 positions fit beside the served weights
and the pool.
"""

import jax
import jax.numpy as jnp

_QUERY_CHUNK = 512
_NORM_EPS = 1e-20


def _f32(x):
    return x.astype(jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _f32(scale)


def _rotate(x, positions, theta):
    """``x [rows, T, heads, d]``: every dim rotated, first half against
    second half."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None] * inv[None]   # [T, half]
    cos, sin = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention(x, p, shape, window, positions=None):
    """One attention layer over ``x [rows, T, d]`` (already normed);
    ``positions [T]``: what the rotation takes for each token's position
    (default 0, 1, ...; a layer that reads no position gives the same
    whatever they are). Who sees whom goes by a token's place."""
    rows, seq, _ = x.shape
    heads, kv, dh = shape["heads"], shape["kv_heads"], shape["head_dim"]
    group = heads // kv
    q = (x @ _f32(p["q_proj"]["kernel"])).reshape(rows, seq, heads, dh)
    k = (x @ _f32(p["k_proj"]["kernel"])).reshape(rows, seq, kv, dh)
    v = (x @ _f32(p["v_proj"]["kernel"])).reshape(rows, seq, kv, dh)
    q = _rms(q, p["q_norm"]["scale"], shape["eps"])
    k = _rms(k, p["k_norm"]["scale"], shape["eps"])
    pos = jnp.arange(seq)
    if window:
        at = pos if positions is None else positions
        q = _rotate(q, at, shape["rope_theta"])
        k = _rotate(k, at, shape["rope_theta"])
    step = _QUERY_CHUNK if seq % _QUERY_CHUNK == 0 else seq

    def one_chunk(start):
        qc = jax.lax.dynamic_slice_in_dim(q, start, step, 1)
        qc = qc.reshape(rows, step, kv, group, dh)
        a = jnp.einsum("rtkgd,rskd->rkgts", qc, k) / dh ** 0.5
        i = (start + jnp.arange(step))[:, None]
        seen = pos[None, :] <= i
        if window:
            seen = seen & (pos[None, :] >= i - (window - 1))
        a = jnp.where(seen, a, -jnp.inf)
        e = jnp.exp(a - a.max(-1, keepdims=True))
        out = jnp.einsum("rkgts,rskd->rtkgd", e / e.sum(-1, keepdims=True), v)
        return out.reshape(rows, step, heads * dh)

    chunks = jax.lax.map(one_chunk, jnp.arange(0, seq, step))
    y = chunks.transpose(1, 0, 2, 3).reshape(rows, seq, heads * dh)
    return y @ _f32(p["o_proj"]["kernel"])


def swiglu(x, p):
    """One SwiGLU of the program's tree (``gate_proj``, ``up_proj``,
    ``down_proj``)."""
    return _swiglu(x, p["gate_proj"]["kernel"], p["up_proj"]["kernel"],
                   p["down_proj"]["kernel"])


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ _f32(gate)) * (x @ _f32(up))) @ _f32(down)


def routed(x, p, shape, given=None):
    """``(chosen experts [tokens, k], weights [tokens, k], margin
    [tokens], differs [tokens])`` of ``x [tokens, d]``: the published gate.
    ``given [tokens, k]``: the sets another computation chose, taken in
    place of this gate's own wherever their first entry is not negative;
    the weights are this gate's scores of whatever is chosen. ``margin``:
    how far below this gate's own k-th selection score the lowest of the
    chosen lies (0 for its own choice): a set handed in is a near tie of
    this gate's only if that is rounding. ``differs``: the chosen set is
    not this gate's own."""
    scores = jax.nn.sigmoid(x @ _f32(p["router"]))
    select = scores + _f32(p["router_bias"])[None]
    best, own = jax.lax.top_k(select, shape["top_k"])
    chosen = own if given is None else jnp.where(given[:, :1] >= 0, given,
                                                 own)
    margin = best[:, -1] - jnp.take_along_axis(select, chosen, 1).min(-1)
    differs = (jnp.sort(chosen, -1) != jnp.sort(own, -1)).any(-1)
    picked = jnp.take_along_axis(scores, chosen, 1)
    weights = (picked / (picked.sum(-1, keepdims=True) + _NORM_EPS)
               * shape["route_scale"])
    return chosen, weights, margin, differs


def balanced_bias(x, p, shape, steps, rate):
    """The selection bias ``[experts]`` that training's balancing leaves
    for the tokens ``x [tokens, d]``: from ``p``'s own bias, ``steps``
    times ``b_e -= rate x sign(load_e - mean load)`` over the top ``k`` of
    ``s + b`` (the auxiliary-loss-free rule the sigmoid gate's
    ``e_score_correction_bias`` is trained by); the scores never change."""
    scores = jax.nn.sigmoid(x @ _f32(p["router"]))
    experts = scores.shape[-1]

    def step(_, bias):
        chosen = jax.lax.top_k(scores + bias[None], shape["top_k"])[1]
        load = jnp.zeros(experts).at[chosen.reshape(-1)].add(1.0)
        return bias - rate * jnp.sign(load - chosen.size / experts)

    return jax.lax.fori_loop(0, steps, step, _f32(p["router_bias"]))


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


def sparse(x, p, shape, given=None):
    """``(the held routed experts' sum, the shared expert's term, chosen,
    {margin, differs})`` of ``x [rows, T, d]``: the two terms apart, so
    that shares can be summed with the shared term counted once."""
    rows, seq, d = x.shape
    flat = x.reshape(rows * seq, d)
    chosen, weights, margin, differs = routed(
        flat, p, shape, None if given is None
        else given.reshape(rows * seq, -1))
    out = expert_terms(flat, p, shape["first_expert"], chosen, weights)
    return (out.reshape(rows, seq, d), swiglu(x, p["shared_experts"]),
            chosen.reshape(rows, seq, -1),
            {"margin": margin.reshape(rows, seq),
             "differs": differs.reshape(rows, seq)})


def _forward(params, input_ids, shape, given=None, rebias=None):
    """``(final residual stream, per sparse layer: its float32 input, the
    chosen experts, their margin and whether they differ)``. ``given
    [rows, T, sparse layers, k]``: see :func:`routed`. ``rebias(layer's
    input [tokens, d], layer's params) -> [experts]``: the selection bias a
    sparse layer routes by, in place of its own."""
    x = _f32(params["embed_tokens"])[input_ids]
    eps, seen = shape["eps"], []
    for i, (window, is_sparse) in enumerate(zip(shape["windows"],
                                                shape["sparse"])):
        at = f"layers_{i}"
        x = x + attention(
            _rms(x, params[f"{at}_input_layernorm"]["scale"], eps),
            params[f"{at}_attn"], shape, window)
        h = _rms(x, params[f"{at}_post_attention_layernorm"]["scale"], eps)
        mlp = params[f"{at}_mlp"]
        if is_sparse:
            if rebias is not None:
                mlp = {**mlp, "router_bias": rebias(
                    h.reshape(-1, h.shape[-1]), mlp)}
            y, shared, picked, tie = sparse(
                h, mlp, shape,
                None if given is None else given[:, :, len(seen)])
            y = y + shared
            seen.append((h, picked, tie))
        else:
            y = swiglu(h, mlp)
        x = x + y
    return x, seen


def routed_sets(params, input_ids, shape):
    """``[sparse layers, rows, T, k]``: the experts the reference chooses
    for every token in every sparse layer."""
    with jax.default_matmul_precision("highest"):
        return jnp.stack([picked for _, picked, _ in
                          _forward(params, input_ids, shape)[1]])


def balanced_biases(params, input_ids, shape, steps, rate):
    """``[sparse layers, experts]``: every sparse layer's selection bias
    balanced over ``input_ids [rows, T]`` (:func:`balanced_bias`), layer by
    layer, a later layer's input routed by the earlier layers' balanced
    biases."""
    found = []

    def rebias(x, mlp):
        found.append(balanced_bias(x, mlp, shape, steps, rate).astype(
            mlp["router_bias"].dtype))   # as it will be served
        return found[-1]

    with jax.default_matmul_precision("highest"):
        _forward(params, input_ids, shape, rebias=rebias)
    return jnp.stack(found)


def logits(params, input_ids, shape, given=None, with_layers=False):
    """``[rows, T, vocab]`` float32 logits of ``input_ids [rows, T]`` over
    the slice of the vocabulary held. ``given [rows, T, sparse layers, k]``
    int32: routed sets to take in place of the reference's own (negative:
    its own), for a comparison with a program whose sets flip at near
    ties. ``with_layers``: also ``{"inputs": [layers, rows, T, d],
    "margin": [layers, rows, T], "differs": [layers, rows, T]}``, each
    sparse layer's float32 input, how far from this gate's own choice the
    chosen sets lie, and where they are not its own."""
    with jax.default_matmul_precision("highest"):
        x, seen = _forward(params, input_ids, shape, given)
        x = _rms(x, params["norm"]["scale"], shape["eps"])
        out = x @ _f32(params["lm_head"]).T
        if not with_layers:
            return out
        return out, {"inputs": jnp.stack([h for h, _, _ in seen]),
                     "margin": jnp.stack([t["margin"] for *_, t in seen]),
                     "differs": jnp.stack([t["differs"] for *_, t in seen])}
