"""Ling-3.0's language model (``model_type: bailing_hybrid``) in plain
``jax.numpy``: the benchmark's reference for ``correct`` (equations: ISSUE
57 / PERF.md, from the published ``config.json``; every reading of a key
name is under ``assumed`` in the configuration file, and this file departs
from that description in nothing else).

Per layer ``i``, pre-norm, RMSNorm, no bias: ``x += Mixer_i(norm x)``, ``x
+= FFN_i(norm x)``; then a final norm and an untied head.

- KDA mixer (``kinds[i] == "kda"``), heads of key and value width
  ``head_dim``: ``q, k, v = silu(conv(x W_q)), silu(conv(x W_k)),
  silu(conv(x W_v))`` (causal, depthwise, ``taps`` taps, zeros before the
  start); ``q <- q / |q|_2 / sqrt(head_dim)``, ``k <- k / |k|_2``; ``g_t =
  lower_bound * sigmoid(exp(A_log_h) (x_t W_f + dt_bias))`` a key channel,
  ``alpha_t = exp(g_t)``; ``beta_t = sigmoid(x_t W_b)`` a head; a head's
  state ``S [key, value]`` from zeros, A TOKEN AT A TIME: ``S' =
  Diag(alpha_t) S``; ``S = S' + beta_t k_t (v_t - S'^T k_t)^T``; ``o_t =
  S^T q_t``; ``y = (sigmoid(x W_g) * RMSNorm_head(o)) W_o``.
- Latent mixer (``"latent"``): ``q = x W_q``, a head ``[q_nope | q_pe]``;
  ``[c_kv | k_pe] = x W_kva``; ``c = RMSNorm(c_kv)``; a head's ``[k_nope |
  v] = c W_kvb``; ``q_pe`` and the one ``k_pe`` rotated (interleaved pairs
  brought to halves, ``rope_theta``, no scaling); scores ``* (nope + rope)
  ** -0.5``, causal softmax; ``o_h <- sigmoid(x W_a)_h * o_h``; ``W_o``.
- FFN: ``sparse[i]`` false = SwiGLU; true = ``s = sigmoid(x W_r)`` over ALL
  published experts; ``c = s + b``; a group's score the sum of its two
  largest ``c`` (``n_group`` groups of contiguous experts); the
  ``topk_group`` best groups stay; the ``k`` largest ``c`` inside them are
  chosen; ``w = route_scale * s[chosen] / sum s[chosen]``; the sum over the
  chosen experts HELD HERE (``first_expert ..``) of ``w_k E_k(x)``, plus the
  shared expert's SwiGLU: the chip's share, as the program computes it.
  Where ``limits[i]`` / ``shared_limits[i]`` is ``L > 0`` the experts' / the
  shared expert's SwiGLU clamps ``gate <- min(gate, L)``, ``up <- clip(up,
  -L, L)`` first.

``recurrence`` is the KDA mixer's delta rule by itself: the cell's check
also runs it over the inputs the PROGRAM's first layer hands its own
(``families/bailing_hybrid.py:first_kda_recurrence``), where what is
compared is the state's arithmetic alone.

``balanced_biases`` is no part of the model: it makes seeded weights'
selection biases what training leaves them (the configuration file's
``weights.selection_bias_balance``), through this same forward pass.

float32, matmuls at ``highest`` precision, no kernel, no cache, no
batching, and no call into ``deepspeed_tpu/``. It reads the program's own
parameter tree and upcasts one matrix (one expert) at a time; attention runs
a block of queries at a time, so that a long request fits beside the served
weights and the pools.
"""

import jax
import jax.numpy as jnp

_QUERY_BLOCK = 512


def _f32(x):
    return x.astype(jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _f32(scale)


def _l2(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def _conv(z, taps):
    """``z [rows, T, C]`` through the depthwise causal convolution ``taps
    [C, L]`` (the last tap meets the current position), zeros before the
    start."""
    keep = taps.shape[1] - 1
    line = jnp.pad(z, ((0, 0), (keep, 0), (0, 0)))
    w = _f32(taps)
    return sum(w[None, None, :, j] * line[:, j:j + z.shape[1]]
               for j in range(keep + 1))


def recurrence(q, k, v, g, beta, stop=None):
    """The delta rule a token at a time from a zero state: ``q, k, v, g
    [rows, T, heads, width]`` (``g`` the log decay a key channel), ``beta
    [rows, T, heads]`` -> ``(o [rows, T, heads, width], the state [rows,
    heads, key, value] after position ``stop - 1`` (default: the last))``."""
    rows, seq, heads, width = q.shape
    stop = seq if stop is None else stop

    def step(state, at):
        t, q_t, k_t, v_t, g_t, beta_t = at
        decayed = jnp.exp(g_t)[..., None] * state
        miss = v_t - jnp.einsum("rhkv,rhk->rhv", decayed, k_t)
        new = decayed + (beta_t[..., None] * k_t)[..., None] * miss[:, :, None]
        return (jnp.where(t < stop, new, state),
                jnp.einsum("rhkv,rhk->rhv", new, q_t))

    state, o = jax.lax.scan(
        step, jnp.zeros((rows, heads, width, width), jnp.float32),
        (jnp.arange(seq), *(_f32(u).swapaxes(0, 1)
                            for u in (q, k, v, g, beta))))
    return o.swapaxes(0, 1), state


def kda(x, p, shape, stop=None):
    """One KDA layer over ``x [rows, T, d]`` (already normed) -> ``(its
    term, the state [rows, heads, key, value] after position ``stop - 1``
    (default: the last))``: the recurrence a token at a time."""
    rows, seq, _ = x.shape
    heads, width = shape["heads"], shape["head_dim"]

    def heads_of(y):
        return y.reshape(rows, seq, heads, width)

    qkv = jnp.concatenate([x @ _f32(p[name]["kernel"]) for name in
                           ("q_proj", "k_proj", "v_proj")], -1)
    q, k, v = (heads_of(u) for u in jnp.split(
        jax.nn.silu(_conv(qkv, p["conv"])), 3, -1))
    q, k = _l2(q) / width ** 0.5, _l2(k)
    rate = jnp.exp(_f32(p["A_log"]))[:, None]
    g = shape["lower_bound"] * jax.nn.sigmoid(
        rate * heads_of(x @ _f32(p["f_proj"]) + _f32(p["dt_bias"])))
    beta = jax.nn.sigmoid(x @ _f32(p["b_proj"]))            # [rows, T, H]
    o, state = recurrence(q, k, v, g, beta, stop)
    o = _rms(o, p["o_norm"]["scale"], shape["eps"])
    gate = jax.nn.sigmoid(heads_of(x @ _f32(p["g_proj"]["kernel"])))
    return ((gate * o).reshape(rows, seq, heads * width)
            @ _f32(p["o_proj"]["kernel"])), state


def _rotate(x, positions, theta):
    """``x [rows, T, ..., rope]``: lanes ``0, 2, 4, ..`` then ``1, 3, 5,
    ..``, the first half rotated against the second."""
    dim = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angle = positions.astype(jnp.float32)[:, None] * inv[None]    # [T, r/2]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    if x.ndim == 4:
        cos, sin = cos[:, None], sin[:, None]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def latent(x, p, shape):
    """One latent-attention layer over ``x [rows, T, d]`` (already
    normed), its output gated a head."""
    rows, seq, _ = x.shape
    heads, nope, rope, dv, rank = (shape["heads"], shape["nope"],
                                   shape["rope"], shape["v_dim"],
                                   shape["rank"])
    q = (x @ _f32(p["q_proj"]["kernel"])).reshape(rows, seq, heads,
                                                  nope + rope)
    kva = x @ _f32(p["kv_a_proj_with_mqa"]["kernel"])
    c = _rms(kva[..., :rank], p["kv_a_layernorm"]["scale"], shape["eps"])
    kv = (c @ _f32(p["kv_b_proj"])).reshape(rows, seq, heads, nope + dv)
    pos = jnp.arange(seq)
    theta = shape["rope_theta"]
    q = jnp.concatenate([q[..., :nope], _rotate(q[..., nope:], pos, theta)],
                        -1)
    k_pe = _rotate(kva[..., rank:], pos, theta)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_pe[:, :, None], (rows, seq, heads, rope))], -1)
    v = kv[..., nope:]
    step = _QUERY_BLOCK if seq % _QUERY_BLOCK == 0 else seq
    scale = (nope + rope) ** -0.5

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, step, 1)
        a = jnp.einsum("rthd,rshd->rhts", qb, k) * scale
        seen = pos[None, :] <= (start + jnp.arange(step))[:, None]
        a = jax.nn.softmax(jnp.where(seen, a, -jnp.inf), axis=-1)
        return jnp.einsum("rhts,rshd->rthd", a, v)

    blocks = jax.lax.map(one_block, jnp.arange(0, seq, step))
    y = blocks.transpose(1, 0, 2, 3, 4).reshape(rows, seq, heads, dv)
    gate = jax.nn.sigmoid(x @ _f32(p["gate_proj"]["kernel"]))
    return (gate[..., None] * y).reshape(rows, seq, heads * dv) @ _f32(
        p["o_proj"]["kernel"])


def _glu(gate, up, limit):
    if limit:
        gate, up = jnp.minimum(gate, limit), jnp.clip(up, -limit, limit)
    return jax.nn.silu(gate) * up


def swiglu(x, p, limit=0.0):
    """One SwiGLU of the program's tree (``gate_proj``, ``up_proj``,
    ``down_proj``)."""
    return _swiglu(x, p["gate_proj"]["kernel"], p["up_proj"]["kernel"],
                   p["down_proj"]["kernel"], limit)


def _swiglu(x, gate, up, down, limit=0.0):
    return _glu(x @ _f32(gate), x @ _f32(up), limit) @ _f32(down)


def group_scores(select, shape):
    """``[tokens, n_group]``: a group's score, the sum of its two largest
    entries of ``select [tokens, experts]`` (groups of contiguous
    experts)."""
    tokens, experts = select.shape
    by_group = select.reshape(tokens, shape["n_group"], -1)
    return jnp.sort(by_group, -1)[..., -2:].sum(-1)


def group_limited(select, shape):
    """``select [tokens, experts]`` (score + bias) with everything outside
    the ``topk_group`` best of ``n_group`` groups at ``-inf``."""
    groups, kept = shape["n_group"], shape["topk_group"]
    if groups <= 1:
        return select
    score = group_scores(select, shape)
    rank = jnp.argsort(jnp.argsort(-score, -1), -1)      # 0: the best group
    return jnp.where(jnp.repeat(rank < kept, select.shape[1] // groups, -1),
                     select, -jnp.inf)


def routed(x, p, shape, given=None):
    """``(chosen experts [tokens, k], weights [tokens, k], margin
    [tokens], differs [tokens])`` of ``x [tokens, d]``: the published gate.
    ``given [tokens, k]``: the sets another computation chose, taken in
    place of this gate's own wherever their first entry is not negative;
    the weights are this gate's scores of whatever is chosen. ``margin``:
    how far from this gate's own choice the chosen set lies (0 for its
    own), the larger of two distances: of the worst GROUP a chosen expert
    lies in under this gate's ``topk_group``-th best group score, and of the
    lowest chosen selection score under the k-th best INSIDE the groups the
    chosen lie in. A set handed in is a near tie of this gate's only if
    both are rounding. ``differs``: the chosen set is not this gate's
    own."""
    scores = jax.nn.sigmoid(x @ _f32(p["router"]))
    select = scores + _f32(p["router_bias"])[None]
    k, groups = shape["top_k"], shape["n_group"]
    own = jax.lax.top_k(group_limited(select, shape), k)[1]
    chosen = own if given is None else jnp.where(given[:, :1] >= 0, given,
                                                 own)
    size = select.shape[1] // groups
    of_group = group_scores(select, shape)
    in_group = chosen // size                               # [tokens, k]
    kept = jnp.sort(of_group, -1)[:, groups - shape["topk_group"]]
    group_margin = jnp.maximum(kept - jnp.take_along_axis(
        of_group, in_group, 1).min(-1), 0.0)
    theirs = (in_group[..., None] == jnp.arange(groups)).any(1)
    inside = jnp.where(jnp.repeat(theirs, size, -1), select, -jnp.inf)
    margin = jnp.maximum(group_margin, jax.lax.top_k(inside, k)[0][:, -1]
                         - jnp.take_along_axis(select, chosen, 1).min(-1))
    differs = (jnp.sort(chosen, -1) != jnp.sort(own, -1)).any(-1)
    picked = jnp.take_along_axis(scores, chosen, 1)
    weights = picked / picked.sum(-1, keepdims=True) * shape["route_scale"]
    return chosen, weights, margin, differs


def balanced_bias(x, p, shape, steps, rate):
    """The selection bias ``[experts]`` that training's balancing leaves
    for the tokens ``x [tokens, d]``: from ``p``'s own bias, ``steps``
    times ``b_e -= rate x sign(load_e - mean load)`` over the chosen sets
    (the auxiliary-loss-free rule the sigmoid gate's expert bias is trained
    by, through the groups); the scores never change."""
    scores = jax.nn.sigmoid(x @ _f32(p["router"]))
    experts = scores.shape[-1]

    def step(_, bias):
        chosen = jax.lax.top_k(group_limited(scores + bias[None], shape),
                               shape["top_k"])[1]
        load = jnp.zeros(experts).at[chosen.reshape(-1)].add(1.0)
        return bias - rate * jnp.sign(load - chosen.size / experts)

    return jax.lax.fori_loop(0, steps, step, _f32(p["router_bias"]))


def expert_terms(flat, p, first_expert, chosen, weights, limit=0.0):
    """``flat [tokens, d]`` -> the sum over the chosen experts HELD here of
    ``w_k down_k(glu(gate_k x, up_k x))``."""
    def one_expert(acc, expert):
        e, gate, up, down = expert
        w = jnp.sum(jnp.where(chosen == first_expert + e, weights, 0.0), -1)
        return acc + w[:, None] * _swiglu(flat, gate, up, down, limit), None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(flat),
        (jnp.arange(p["gate"].shape[0]), p["gate"], p["up"], p["down"]))
    return out


def sparse(x, p, shape, layer, given=None):
    """``(the held routed experts' sum, the shared expert's term, chosen,
    {margin, differs})`` of ``x [rows, T, d]``: the two terms apart, so
    that shares can be summed with the shared term counted once."""
    rows, seq, d = x.shape
    flat = x.reshape(rows * seq, d)
    chosen, weights, margin, differs = routed(
        flat, p, shape, None if given is None
        else given.reshape(rows * seq, -1))
    out = expert_terms(flat, p, shape["first_expert"], chosen, weights,
                       shape["limits"][layer])
    return (out.reshape(rows, seq, d),
            swiglu(x, p["shared_experts"], shape["shared_limits"][layer]),
            chosen.reshape(rows, seq, -1),
            {"margin": margin.reshape(rows, seq),
             "differs": differs.reshape(rows, seq)})


def _forward(params, input_ids, shape, given=None, rebias=None, stop=None):
    """``(final residual stream, per sparse layer: its float32 input, the
    chosen experts, their margin and whether they differ; per KDA layer its
    state after position ``stop - 1``)``. ``given [rows, T, sparse layers,
    k]``: see :func:`routed`. ``rebias(layer's input [tokens, d], layer's
    params) -> [experts]``: the selection bias a sparse layer routes by, in
    place of its own."""
    x = _f32(params["embed_tokens"])[input_ids]
    eps, seen, states = shape["eps"], [], []
    for i, (kind, is_sparse) in enumerate(zip(shape["kinds"],
                                              shape["sparse"])):
        at = f"layers_{i}"
        u = _rms(x, params[f"{at}_input_layernorm"]["scale"], eps)
        if kind == "kda":
            a, state = kda(u, params[f"{at}_kda"], shape, stop)
            states.append(state)
        else:
            a = latent(u, params[f"{at}_attn"], shape)
        x = x + a
        h = _rms(x, params[f"{at}_post_attention_layernorm"]["scale"], eps)
        mlp = params[f"{at}_mlp"]
        if is_sparse:
            if rebias is not None:
                mlp = {**mlp, "router_bias": rebias(
                    h.reshape(-1, h.shape[-1]), mlp)}
            y, shared, picked, tie = sparse(
                h, mlp, shape, i,
                None if given is None else given[:, :, len(seen)])
            y = y + shared
            seen.append((h, picked, tie))
        else:
            y = swiglu(h, mlp)
        x = x + y
    return x, seen, states


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


def logits(params, input_ids, shape, given=None, with_layers=False,
           stop=None):
    """``[rows, T, vocab]`` float32 logits of ``input_ids [rows, T]`` over
    the slice of the vocabulary held. ``given [rows, T, sparse layers, k]``
    int32: routed sets to take in place of the reference's own (negative:
    its own), for a comparison with a program whose sets flip at near
    ties. ``with_layers``: also ``{"inputs": [layers, rows, T, d],
    "margin": [layers, rows, T], "differs": [layers, rows, T], "states":
    [KDA layers, rows, heads, key, value]}``: each sparse layer's float32
    input, how far from this gate's own choice the chosen sets lie and where
    they are not its own, and each KDA layer's state after position ``stop
    - 1`` (a traced scalar; default: the last)."""
    with jax.default_matmul_precision("highest"):
        x, seen, states = _forward(params, input_ids, shape, given,
                                   stop=stop)
        x = _rms(x, params["norm"]["scale"], shape["eps"])
        out = x @ _f32(params["lm_head"]).T
        if not with_layers:
            return out
        layers = {"states": jnp.stack(states)}
        if seen:
            layers.update(
                inputs=jnp.stack([h for h, _, _ in seen]),
                margin=jnp.stack([t["margin"] for *_, t in seen]),
                differs=jnp.stack([t["differs"] for *_, t in seen]))
        return out, layers
