"""DeepSeek-V2's language model in plain ``jax.numpy``: the benchmark's
reference for ``correct`` (equations: ISSUE 45 / PERF.md, from the
published ``config.json``; every inference is under ``assumed`` in the
configuration file).

Layer ``i``, pre-norm, RMSNorm with a learned weight, no bias:
``h = x + Attn(norm x)``, ``out = h + FFN_i(norm h)``; then a final norm
and an untied head.

- Attention, NOT absorbed: ``q = x W_q``, a head ``[q_nope | q_pe]``;
  ``[c_kv | k_pe] = x W_kva``; ``c = RMSNorm(c_kv)``; a head's ``[k_nope |
  v] = c W_kvb``; ``k = [k_nope | k_pe]``, the one ``k_pe`` for every head;
  ``q_pe`` and ``k_pe`` rotated (the interleaved pairs de-interleaved, then
  the half-rotation, YaRN's blended frequencies, cos and sin times
  ``mscale / mscale_all_dim``); ``softmax(q k^T * (nope + rope) ** -0.5 *
  m ** 2) v`` causally, ``m = 0.1 * mscale_all_dim * ln(factor) + 1``.
- FFN: SwiGLU for ``i < dense``; else ``p = softmax(x W_g)`` over all the
  experts, the ``k`` largest chosen, weights ``scale * p`` as they stand,
  ``sum_k w_k E_k(x) + S(x)``, ``S`` the shared experts (one SwiGLU).

float32, matmuls at ``highest`` precision, no kernel, no cache, no
batching, and no call into ``deepspeed_tpu/`` (the norm and the upcast are
``reference_mimo_v2``'s plain helpers). It reads the program's own
parameter tree and upcasts one layer (one expert) at a time. What keeps a
context of 16k beside the served weights and the pool: attention runs a
block of queries at a time; the dense FFN a block of rows at a time; the
experts' sum walks the (token, choice) pairs IN EXPERT ORDER, a window of
rows at a time through one expert's matrices (every expert over every
token, ``reference_mimo_v2``'s way, is 64 x 16k SwiGLUs a layer); and the
head is taken only at the positions asked for (``at``: a whole context's
logits are 6.7 GB).
"""

import math

import jax
import jax.numpy as jnp

from perfbench.reference_mimo_v2 import _f32, _rms

_QUERY_BLOCK = 512
_ROW_BLOCK = 2048
_PAIR_WINDOW = 512


def _mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn(dim: int, theta: float, scaling):
    """``(inverse frequencies [dim / 2], the factor on cos and sin)``."""
    base = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if not scaling:
        return base, 1.0
    original = scaling["original_max_position_embeddings"]

    def where(rotations):
        return (dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(where(scaling["beta_fast"])), 0)
    high = min(math.ceil(where(scaling["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp
    return (base / scaling["factor"]) * (1.0 - keep) + base * keep, (
        _mscale(scaling["factor"], scaling["mscale"])
        / _mscale(scaling["factor"], scaling["mscale_all_dim"]))


def _rotate(x, positions, shape):
    """``x [rows, T, ..., rope]``: lanes ``0, 2, 4, ..`` then ``1, 3, 5,
    ..``, the first half rotated against the second."""
    inv, factor = yarn(x.shape[-1], shape["rope_theta"], shape["yarn"])
    angle = positions.astype(jnp.float32)[:, None] * inv[None]    # [T, r/2]
    cos, sin = jnp.cos(angle) * factor, jnp.sin(angle) * factor
    if x.ndim == 4:
        cos, sin = cos[:, None], sin[:, None]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def softmax_scale(shape) -> float:
    scale = (shape["nope"] + shape["rope"]) ** -0.5
    if shape["yarn"]:
        m = _mscale(shape["yarn"]["factor"], shape["yarn"]["mscale_all_dim"])
        scale *= m * m
    return scale


def attention(x, p, shape):
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
    q = jnp.concatenate([q[..., :nope], _rotate(q[..., nope:], pos, shape)],
                        -1)
    k_pe = _rotate(kva[..., rank:], pos, shape)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_pe[:, :, None], (rows, seq, heads, rope))], -1)
    v = kv[..., nope:]
    step = _QUERY_BLOCK if seq % _QUERY_BLOCK == 0 else seq
    scale = softmax_scale(shape)

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, step, 1)
        a = jnp.einsum("rthd,rshd->rhts", qb, k) * scale
        seen = pos[None, :] <= (start + jnp.arange(step))[:, None]
        a = jax.nn.softmax(jnp.where(seen, a, -jnp.inf), axis=-1)
        return jnp.einsum("rhts,rshd->rthd", a, v).reshape(
            rows, step, heads * dv)

    blocks = jax.lax.map(one_block, jnp.arange(0, seq, step))
    y = blocks.transpose(1, 0, 2, 3).reshape(rows, seq, heads * dv)
    return y @ _f32(p["o_proj"]["kernel"])


def swiglu(x, p):
    """``x [..., d]`` through one SwiGLU, a block of rows at a time."""
    gate, up, down = (_f32(p[k]["kernel"]) for k in (
        "gate_proj", "up_proj", "down_proj"))
    flat = x.reshape(-1, x.shape[-1])
    n = flat.shape[0]
    step = _ROW_BLOCK if n % _ROW_BLOCK == 0 else n
    out = jax.lax.map(lambda r: (jax.nn.silu(r @ gate) * (r @ up)) @ down,
                      flat.reshape(n // step, step, -1))
    return out.reshape(x.shape)


def routed(x, p, shape, given=None):
    """``(chosen [tokens, k], weights, margin [tokens], differs
    [tokens])`` of ``x [tokens, d]``: the published gate, and what
    ``reference_mimo_v2.routed`` says of sets handed in (``given``: taken
    in place of this gate's own wherever their first entry is not
    negative; ``margin``: how far below this gate's own k-th probability
    the lowest of the chosen lies; ``differs``: not its own set)."""
    probs = jax.nn.softmax(x @ _f32(p["router"]), axis=-1)
    best, own = jax.lax.top_k(probs, shape["top_k"])
    chosen = own if given is None else jnp.where(given[:, :1] >= 0, given,
                                                 own)
    picked = jnp.take_along_axis(probs, chosen, 1)
    margin = best[:, -1] - picked.min(-1)
    differs = (jnp.sort(chosen, -1) != jnp.sort(own, -1)).any(-1)
    return chosen, shape["route_scale"] * picked, margin, differs


def expert_terms(flat, p, first_expert, chosen, weights):
    """``flat [tokens, d]`` -> the sum over the chosen experts HELD here
    (``first_expert ..``) of ``w_k down_k(silu(gate_k x) * up_k x)``: the
    (token, choice) pairs in expert order, each expert's run a window of
    ``_PAIR_WINDOW`` rows at a time."""
    tokens, top_k = chosen.shape
    held = p["gate"].shape[0]
    local = (chosen - first_expert).reshape(-1)
    local = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(local, stable=True)
    token = order // top_k                         # pairs in expert order
    weight = weights.reshape(-1)[order]
    counts = jnp.sum(local[:, None] == jnp.arange(held)[None], axis=0)
    starts = jnp.cumsum(counts) - counts
    lanes = jnp.arange(_PAIR_WINDOW)

    def one_expert(out, e):
        gate, up, down = (_f32(p[k][e]) for k in ("gate", "up", "down"))

        def one_window(j, out):
            at = starts[e] + j * _PAIR_WINDOW + lanes
            mine = j * _PAIR_WINDOW + lanes < counts[e]
            who = token[jnp.minimum(at, tokens * top_k - 1)]
            rows = flat[who]
            y = (jax.nn.silu(rows @ gate) * (rows @ up)) @ down
            w = jnp.where(mine, weight[jnp.minimum(at, tokens * top_k - 1)],
                          0.0)
            return out.at[who].add(w[:, None] * y)

        windows = (counts[e] + _PAIR_WINDOW - 1) // _PAIR_WINDOW
        return jax.lax.fori_loop(0, windows, one_window, out), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(flat), jnp.arange(held))
    return out


def sparse(x, p, shape, given=None):
    """``(routed experts' terms + the shared experts' term, chosen, {margin,
    differs})``."""
    rows, seq, d = x.shape
    flat = x.reshape(rows * seq, d)
    chosen, weights, margin, differs = routed(
        flat, p, shape, None if given is None
        else given.reshape(rows * seq, -1))
    out = expert_terms(flat, p, shape["first_expert"], chosen, weights)
    out = out.reshape(rows, seq, d) + swiglu(x, p["shared_experts"])
    return (out, chosen.reshape(rows, seq, -1),
            {"margin": margin.reshape(rows, seq),
             "differs": differs.reshape(rows, seq)})


def _forward(params, input_ids, shape, given=None):
    """``(final residual stream, per sparse layer: its float32 input, the
    chosen experts, their margin and whether they differ)``."""
    x = _f32(params["embed_tokens"][input_ids])
    eps, seen = shape["eps"], []
    for i in range(shape["layers"]):
        at = f"layers_{i}"
        x = x + attention(
            _rms(x, params[f"{at}_input_layernorm"]["scale"], eps),
            params[f"{at}_attn"], shape)
        h = _rms(x, params[f"{at}_post_attention_layernorm"]["scale"], eps)
        mlp = params[f"{at}_mlp"]
        if i >= shape["dense"]:
            y, picked, tie = sparse(
                h, mlp, shape,
                None if given is None else given[:, :, len(seen)])
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


def logits(params, input_ids, shape, given=None, with_layers=False,
           at=None):
    """Float32 logits of ``input_ids [rows, T]``: ``[rows, T, vocab]``, or
    with ``at [n]`` (positions) ``[rows, n, vocab]``. ``given`` and
    ``with_layers`` as ``reference_mimo_v2.logits``."""
    with jax.default_matmul_precision("highest"):
        x, seen = _forward(params, input_ids, shape, given)
        if at is not None:
            x = x[:, at]
        x = _rms(x, params["norm"]["scale"], shape["eps"])
        out = x @ _f32(params["lm_head"]).T
        if not with_layers:
            return out
        return out, {"inputs": jnp.stack([h for h, _, _ in seen]),
                     "margin": jnp.stack([t["margin"] for *_, t in seen]),
                     "differs": jnp.stack([t["differs"] for *_, t in seen])}
