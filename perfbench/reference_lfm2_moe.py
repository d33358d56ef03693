"""LFM2-MoE's language model in plain ``jax.numpy``: the benchmark's
reference for ``correct`` (equations: ISSUE 43 / PERF.md, from the
published ``config.json``; every inference is under ``assumed`` in the
configuration file).

Layer ``i``, pre-norm, RMSNorm with a learned weight, no bias:
``h = x + Op_i(norm x)``, ``out = h + FFN_i(norm h)``; then a final norm,
and the embedding as the head (tied).

- ``types[i] == "conv"``: ``[B | C | X] = u W_in``; ``z = B * X``;
  ``c_t = sum_j w[:, j] z_{t-(L-1)+j}`` with zeros before the sequence's
  start (tap ``w[:, L-1]`` meets the current position); ``(C * c) W_out``.
- ``"full_attention"``: ``heads`` query heads over ``kv_heads`` key/value
  heads of ``hidden / heads``; RMSNorm over every query and key head (a
  weight a projection) BEFORE the rotation of all its dims, first half
  against second half; causal ``softmax(q k^T / sqrt(d)) v``.
- FFN: SwiGLU for ``i < dense``; else ``s = sigmoid(W_r x)`` over all the
  experts, the top ``k`` of ``s + b`` chosen, ``w = scale * s[chosen] /
  (sum s[chosen] + eps)``, ``sum_k w_k down_k(silu(gate_k x) * up_k x)``.

float32, matmuls at ``highest`` precision, no kernel, no cache, no state
carried (the convolution sees the whole sequence), and no call into
``deepspeed_tpu/models/``. It reads the program's own parameter tree and
upcasts one layer (one expert) at a time; attention runs a chunk of
queries at a time. What it shares with ``reference_mimo_v2`` is that
file's plain helpers (the norm, the rotation, SwiGLU, the experts' sum).
"""

import jax
import jax.numpy as jnp

from perfbench.reference_mimo_v2 import (_f32, _rms, _rotate, _swiglu,
                                         expert_terms)

_QUERY_CHUNK = 512


def short_conv(u, p):
    """The gated short convolution of whole sequences ``u [rows, T, d]``."""
    seq, d = u.shape[1], u.shape[2]
    mixed = u @ _f32(p["in_proj"])
    b, c, x = mixed[..., :d], mixed[..., d:2 * d], mixed[..., 2 * d:]
    taps = _f32(p["conv"])                                       # [d, L]
    width = taps.shape[1]
    z = jnp.pad(b * x, ((0, 0), (width - 1, 0), (0, 0)))
    conv = sum(taps[:, j] * z[:, j:j + seq] for j in range(width))
    return (c * conv) @ _f32(p["out_proj"]["kernel"])


def attention(x, p, shape):
    rows, seq, hidden = x.shape
    heads, kv = shape["heads"], shape["kv_heads"]
    dh, group = hidden // heads, heads // kv
    q = (x @ _f32(p["q_proj"]["kernel"])).reshape(rows, seq, heads, dh)
    k = (x @ _f32(p["k_proj"]["kernel"])).reshape(rows, seq, kv, dh)
    v = (x @ _f32(p["v_proj"]["kernel"])).reshape(rows, seq, kv, dh)
    pos = jnp.arange(seq)
    q = _rotate(_rms(q, p["q_layernorm"]["scale"], shape["eps"]), pos, dh,
                shape["rope_theta"])
    k = _rotate(_rms(k, p["k_layernorm"]["scale"], shape["eps"]), pos, dh,
                shape["rope_theta"])
    step = _QUERY_CHUNK if seq % _QUERY_CHUNK == 0 else seq

    def one_chunk(start):
        qc = jax.lax.dynamic_slice_in_dim(q, start, step, 1)
        a = jnp.einsum("rtkgd,rskd->rkgts",
                       qc.reshape(rows, step, kv, group, dh), k) / dh ** 0.5
        seen = pos[None, :] <= (start + jnp.arange(step))[:, None]
        a = jax.nn.softmax(jnp.where(seen, a, -jnp.inf), axis=-1)
        return jnp.einsum("rkgts,rskd->rtkgd", a, v).reshape(
            rows, step, hidden)

    chunks = jax.lax.map(one_chunk, jnp.arange(0, seq, step))
    y = chunks.transpose(1, 0, 2, 3).reshape(rows, seq, hidden)
    return y @ _f32(p["o_proj"]["kernel"])


def routed(x, p, shape, given=None):
    """``(chosen [tokens, k], weights, margin [tokens], differs
    [tokens])`` of ``x [tokens, d]``: the published gate, and what
    ``reference_mimo_v2.routed`` says of sets handed in (``given``: taken
    in place of this gate's own wherever their first entry is not
    negative; ``margin``: how far below this gate's own k-th selection
    score the lowest of the chosen lies; ``differs``: not its own set)."""
    scores = jax.nn.sigmoid(x @ _f32(p["router"]))
    select = scores + _f32(p["router_bias"])[None]
    best, own = jax.lax.top_k(select, shape["top_k"])
    chosen = own if given is None else jnp.where(given[:, :1] >= 0, given,
                                                 own)
    margin = best[:, -1] - jnp.take_along_axis(select, chosen, 1).min(-1)
    differs = (jnp.sort(chosen, -1) != jnp.sort(own, -1)).any(-1)
    picked = jnp.take_along_axis(scores, chosen, 1)
    weights = shape["route_scale"] * picked / (
        picked.sum(-1, keepdims=True) + shape["route_eps"])
    return chosen, weights, margin, differs


def sparse(x, p, shape, given=None):
    rows, seq, d = x.shape
    flat = x.reshape(rows * seq, d)
    chosen, weights, margin, differs = routed(
        flat, p, shape, None if given is None
        else given.reshape(rows * seq, -1))
    out = expert_terms(flat, p, 0, chosen, weights)
    return (out.reshape(rows, seq, d), chosen.reshape(rows, seq, -1),
            {"margin": margin.reshape(rows, seq),
             "differs": differs.reshape(rows, seq)})


def _forward(params, input_ids, shape, given=None):
    """``(final residual stream, per sparse layer: its float32 input, the
    chosen experts, their margin and whether they differ, per layer: the
    root mean square of the stream and of the two terms it gained)``."""
    x = _f32(params["embed_tokens"])[input_ids]
    eps, seen, terms = shape["eps"], [], []
    rms = lambda t: jnp.sqrt(jnp.mean(t * t))
    for i, kind in enumerate(shape["types"]):
        at = f"layers_{i}"
        u = _rms(x, params[f"{at}_operator_norm"]["scale"], eps)
        a = (short_conv(u, params[f"{at}_conv"]) if kind == "conv"
             else attention(u, params[f"{at}_attn"], shape))
        x = x + a
        h = _rms(x, params[f"{at}_ffn_norm"]["scale"], eps)
        mlp = params[f"{at}_mlp"]
        if i >= shape["dense"]:
            y, picked, tie = sparse(
                h, mlp, shape,
                None if given is None else given[:, :, len(seen)])
            seen.append((h, picked, tie))
        else:
            y = _swiglu(h, mlp["gate_proj"]["kernel"],
                        mlp["up_proj"]["kernel"], mlp["down_proj"]["kernel"])
        x = x + y
        terms.append(jnp.stack([rms(x), rms(a), rms(y)]))
    return x, seen, jnp.stack(terms)


def routed_sets(params, input_ids, shape):
    """``[sparse layers, rows, T, k]``: the experts the reference chooses
    for every token in every sparse layer."""
    with jax.default_matmul_precision("highest"):
        return jnp.stack([picked for _, picked, _ in
                          _forward(params, input_ids, shape)[1]])


def term_shares(params, input_ids, shape):
    """``[layers, 3]``: after each layer the root mean square of the
    residual stream, of the operator's term and of the FFN's: what share
    of the stream each operator adds (the configuration file's
    ``weights`` quotes it)."""
    with jax.default_matmul_precision("highest"):
        return _forward(params, input_ids, shape)[2]


def logits(params, input_ids, shape, given=None, with_layers=False):
    """``[rows, T, vocab]`` float32 logits of ``input_ids [rows, T]``;
    ``given`` and ``with_layers`` as ``reference_mimo_v2.logits``."""
    with jax.default_matmul_precision("highest"):
        x, seen, _ = _forward(params, input_ids, shape, given)
        x = _rms(x, params["norm"]["scale"], shape["eps"])
        out = x @ _f32(params["embed_tokens"]).T
        if not with_layers:
            return out
        return out, {"inputs": jnp.stack([h for h, _, _ in seen]),
                     "margin": jnp.stack([t["margin"] for *_, t in seen]),
                     "differs": jnp.stack([t["differs"] for *_, t in seen])}
