"""DeepSeek-V3.2's language model (``model_type: deepseek_v32``) in plain
``jax.numpy``: the benchmark's reference for ``correct`` (equations: ISSUE
59 / PERF.md, from the published ``config.json``, the V3.2-Exp report and
its published ``inference/model.py``; every reading is under ``assumed`` in
the configuration file, and this file departs from that description in
nothing else).

Layer ``i``, pre-norm, RMSNorm with a learned weight, no bias but the
indexer's LayerNorm: ``h = x + Attn(norm x)``, ``out = h + FFN_i(norm h)``;
a final norm; an untied head.

- Attention, NOT absorbed: ``c_q = RMSNorm(x W_qa)``; ``q = c_q W_qb``, a
  head ``[q_nope | q_pe]``; ``[c_kv | k_pe] = x W_kva``, ``c =
  RMSNorm(c_kv)``; a head's ``[k_nope | v] = c W_kvb``; ``k = [k_nope |
  k_pe]``, the one ``k_pe`` for every head; ``q_pe``, ``k_pe`` rotated
  (interleaved pairs brought to halves, YaRN's blended frequencies);
  ``softmax_{j in S_t}(q k^T * (nope + rope) ** -0.5 * m ** 2) v``, ``m =
  0.1 * mscale_all_dim * ln(factor) + 1``.
- The indexer: ``qI = c_q W_Iq`` (``index_heads`` heads of ``index_dim``);
  ``kI = LayerNorm(x W_Ik)`` (weight and bias, eps 1e-6), one row a token;
  the FIRST ``rope`` values of each rotated at the same frequencies by
  halves (not interleaved); ``w = (x W_Iw) * index_heads ** -0.5 *
  index_dim ** -0.5``; ``I[t, j] = sum_h w[t, h] relu(qI[t, h] . kI[j])``,
  ``j <= t``; ``S_t`` the ``min(index_topk, t + 1)`` positions of largest
  ``I[t, j]``, equal scores to the lower ``j``: THE SCORES OF EVERY PAIR AND
  A PLAIN SORT (``lax.top_k`` is one, exact) for the k-th score.
- FFN: SwiGLU for ``i < dense``; else the grouped sigmoid gate with its
  selection bias, the held experts' terms and the shared expert
  (``reference_bailing_hybrid``'s ``routed`` and ``expert_terms``: the same
  published rule, ``noaux_tc``).

float32, matmuls at ``highest`` precision, no kernel, no cache, no
batching, no call into ``deepspeed_tpu/``. It reads the program's own
parameter tree and upcasts a matrix at a time. What keeps a context of
32,768 beside 10.5 GB of served weights and pools: one layer is one call
(:func:`layer`: the family jits it and hands the stream from call to call),
the selection runs a block of QUERIES at a time (a 30k request's scores are
3.6 GB whole), attention a group of heads at a time over blocks of queries
with the stream as the loop's carry, both FFNs a block of rows at a time,
and the head is taken at the positions asked for.

SETS HANDED IN (``selected``): a bfloat16 program's index scores are off by
some 0.4% of themselves and the k-th of 10,000 scores has neighbours a
hundredth of that away, so a query's set differs from this file's in some
tens of its keys, on a rounding. The check therefore holds the program's
sets to this file's scores (``select_margin``: how far on the wrong side of
this file's k-th score a key chosen, or left out, lies, as a share of the
row's largest score; ``select_flips``: how many such keys a query) and then
attends THE PROGRAM'S sets.
"""

import jax
import jax.numpy as jnp

from perfbench import reference_bailing_hybrid as grouped
from perfbench.reference_deepseek_v2 import softmax_scale, yarn
from perfbench.reference_mimo_v2 import _f32, _rms

_SELECT_BLOCK = 512     # queries a block of the selection
_ATTEND_BLOCK = 128     # queries a block of the attention
_HEAD_GROUP = 8
_ROW_BLOCK = 2048       # rows a block of the FFNs


def _block(n: int, size: int) -> int:
    return size if n % size == 0 else n


def _angles(positions, shape):
    inv, factor = yarn(shape["rope"], shape["rope_theta"], shape["yarn"])
    angle = positions.astype(jnp.float32)[:, None] * inv[None]    # [T, r/2]
    return jnp.cos(angle) * factor, jnp.sin(angle) * factor


def rotate_pairs(x, positions, shape):
    """``x [T, (heads,) rope]``: lanes ``0, 2, 4, ..`` then ``1, 3, 5, ..``,
    the first half rotated against the second (the main path's)."""
    cos, sin = _angles(positions, shape)
    if x.ndim == 3:
        cos, sin = cos[:, None], sin[:, None]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def rotate_first_halves(x, positions, shape):
    """``x [T, (heads,) index_dim]``: its first ``rope`` values rotated by
    halves (``x1, x2 = split(x_rope, 2)``), the rest as they are (the
    indexer's)."""
    rope = shape["rope"]
    cos, sin = _angles(positions, shape)
    if x.ndim == 3:
        cos, sin = cos[:, None], sin[:, None]
    a, b = x[..., :rope // 2], x[..., rope // 2:rope]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rope:]], -1)


def _layer_norm(x, p, eps=1e-6):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * _f32(p["scale"]) \
        + _f32(p["bias"])


def _kernel(p, name):
    return _f32(p[name]["kernel"])


# ---------------------------------------------------------------------------
# the indexer and the selection

def index_scores(q_i, w, k_i):
    """``I [Q, T]``: ``q_i [Q, heads, dim]``, ``w [Q, heads]``, ``k_i [T,
    dim]``, a head at a time."""
    def one_head(total, head):
        q_h, w_h = head
        return total + w_h[:, None] * jax.nn.relu(q_h @ k_i.T), None

    return jax.lax.scan(
        one_head, jnp.zeros((q_i.shape[0], k_i.shape[0]), jnp.float32),
        (q_i.swapaxes(0, 1), w.T))[0]


def kth_score(scores, valid, k: int):
    """``[Q]``: a query's k-th largest valid score, ``-inf`` where it has
    fewer than ``k``."""
    width = min(k, scores.shape[1])
    best = jax.lax.top_k(jnp.where(valid, scores, -jnp.inf), width)[0]
    return best[:, -1] if width == k else jnp.full(scores.shape[:1], -jnp.inf)


def own_selection(scores, valid, k: int):
    """``[Q, T]`` bool: the ``min(k, valid)`` keys of largest score, equal
    scores to the lower position."""
    kth = kth_score(scores, valid, k)[:, None]
    above = valid & (scores > kth)
    at = valid & (scores == kth)
    ties = k - above.sum(-1, keepdims=True)
    return above | (at & (jnp.cumsum(at, -1) - at < ties))


def unpack(words, keys: int):
    """``[Q, words]`` uint32 (key ``j`` bit ``j % 32`` of word ``j // 32``)
    -> ``[Q, keys]`` bool."""
    bits = (words[..., None] >> jnp.arange(32, dtype=jnp.uint32)) & 1
    return bits.reshape(words.shape[0], -1)[:, :keys].astype(bool)


def pack(mask):
    q, t = mask.shape
    mask = jnp.pad(mask, ((0, 0), (0, -t % 32)))
    return jnp.sum(mask.reshape(q, -1, 32).astype(jnp.uint32)
                   << jnp.arange(32, dtype=jnp.uint32), -1, dtype=jnp.uint32)


def selection(queries_of, w, k_i, shape, given=None):
    """``(mask [T, ceil(T / 32)] uint32 packed, margin [T], flips [T])`` of
    one sequence: each query's set, a block of queries at a time
    (``queries_of(start, count) -> [count, heads, dim]``: the block's index
    queries, made a block at a time: a context of 32,768 has 1.07 GB of
    them). ``given
    [T, words]``: sets another computation chose (packed the same way, at
    least ``ceil(T / 32)`` words), taken in place of this file's own (a row
    with no key set: this file's own);
    ``margin``: the farthest a key of such a set lies on the wrong side of
    this file's k-th score (chosen and under it, or left out and over it),
    over the largest score of the query's row; ``flips``: how many keys lie
    so. Both 0 for this file's own sets."""
    t, k = k_i.shape[0], shape["index_topk"]
    step = _block(t, _SELECT_BLOCK)
    words = -(-t // 32)
    k_pos = jnp.arange(t)

    def one_block(start):
        cut = lambda u: jax.lax.dynamic_slice_in_dim(u, start, step, 0)
        scores = index_scores(queries_of(start, step), cut(w), k_i)
        valid = k_pos[None] <= (start + jnp.arange(step))[:, None]
        if given is None:
            mask = own_selection(scores, valid, k)
            return pack(mask), jnp.zeros((step,)), jnp.zeros((step,),
                                                             jnp.int32)
        # (a row handed in empty, a width's padding, takes this file's own
        # set: a query with no key at all is NaN, and 0 x NaN of a padded
        # key would reach the real rows)
        theirs = unpack(cut(given), t) & valid
        handed = theirs.any(-1, keepdims=True)
        mask = jnp.where(handed, theirs, own_selection(scores, valid, k))
        kth = kth_score(scores, valid, k)[:, None]
        wrong = jnp.where(mask, kth - scores, scores - kth)
        wrong = jnp.where(valid & handed & jnp.isfinite(kth), wrong, 0.0)
        top = jnp.max(jnp.where(valid, jnp.abs(scores), 0.0), -1)
        return (pack(mask), jnp.max(wrong, -1) / jnp.maximum(top, 1e-30),
                jnp.sum(wrong > 0.0, -1, dtype=jnp.int32))

    mask, margin, flips = jax.lax.map(one_block, jnp.arange(0, t, step))
    return mask.reshape(t, words), margin.reshape(t), flips.reshape(t)


# ---------------------------------------------------------------------------
# a layer

def attention(x, u, p, shape, given=None):
    """``(x + Attn(u), {"select_margin", "select_flips", "selected"})`` of
    one sequence: ``x [T, d]`` the stream, ``u`` its norm."""
    t = x.shape[0]
    heads, nope, rope, dv, rank = (shape["heads"], shape["nope"],
                                   shape["rope"], shape["v_dim"],
                                   shape["rank"])
    pos = jnp.arange(t)
    c_q = _rms(u @ _kernel(p, "q_a_proj"), p["q_a_layernorm"]["scale"],
               shape["eps"])
    kva = u @ _kernel(p, "kv_a_proj_with_mqa")
    c = _rms(kva[:, :rank], p["kv_a_layernorm"]["scale"], shape["eps"])
    k_pe = rotate_pairs(kva[:, rank:], pos, shape)
    w_iq = _kernel(p, "index_q_proj")

    def index_queries(start, count):
        rows = jax.lax.dynamic_slice_in_dim(c_q, start, count, 0)
        return rotate_first_halves(
            (rows @ w_iq).reshape(count, shape["index_heads"],
                                  shape["index_dim"]),
            start + jnp.arange(count), shape)

    k_i = rotate_first_halves(
        _layer_norm(u @ _kernel(p, "index_k_proj"), p["index_k_norm"]), pos,
        shape)
    w = (u @ _f32(p["index_weights_proj"])) * (
        shape["index_heads"] ** -0.5 * shape["index_dim"] ** -0.5)
    packed, margin, flips = selection(index_queries, w, k_i, shape, given)

    group = _block(heads, _HEAD_GROUP)
    step = _block(t, _ATTEND_BLOCK)
    scale = softmax_scale(shape)
    w_q = _kernel(p, "q_b_proj").reshape(-1, heads // group, group,
                                         nope + rope)
    w_kv = _f32(p["kv_b_proj"]).reshape(rank, heads // group, group,
                                        nope + dv)
    w_o = _kernel(p, "o_proj").reshape(heads // group, group * dv, -1)

    def one_group(x, weights):
        g_q, g_kv, g_o = weights
        kv = jnp.einsum("tc,chd->thd", c, g_kv)
        k_nope, v = kv[..., :nope], kv[..., nope:]

        def one_block(start):
            cut = lambda a: jax.lax.dynamic_slice_in_dim(a, start, step, 0)
            q = jnp.einsum("tc,chd->thd", cut(c_q), g_q)
            q_pe = rotate_pairs(q[..., nope:], start + jnp.arange(step),
                                shape)
            s = (jnp.einsum("thd,shd->hts", q[..., :nope], k_nope)
                 + jnp.einsum("thr,sr->hts", q_pe, k_pe)) * scale
            seen = unpack(cut(packed), t)[None]
            s = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
            return jnp.einsum("hts,shd->thd", s, v).reshape(step, group * dv)

        o = jax.lax.map(one_block, jnp.arange(0, t, step)).reshape(t, -1)
        return x + o @ g_o, None

    x, _ = jax.lax.scan(one_group, x, (w_q.swapaxes(0, 1),
                                       w_kv.swapaxes(0, 1), w_o))
    return x, {"select_margin": margin, "select_flips": flips,
               "selected": packed}


def _rows(fn, x):
    """``fn`` over ``x [T, d]`` a block of rows at a time."""
    t = x.shape[0]
    step = _block(t, _ROW_BLOCK)
    return jax.lax.map(fn, x.reshape(t // step, step, -1)).reshape(t, -1)


def layer(x, p, shape, sparse: bool, routed=None, selected=None,
          keep: int = 0):
    """One layer over one sequence's stream ``x [T, d]`` -> ``(x, seen)``.
    ``p``: the layer's own entries of the parameter tree (``attn``,
    ``mlp``, ``input_layernorm``, ``post_attention_layernorm``); ``routed
    [T, k]``: the experts another computation chose, in place of this
    gate's own (negative: its own); ``selected [T, words]``: the keys
    another computation chose (:func:`selection`). ``seen``: the
    selection's ``select_margin [T]`` / ``select_flips [T]`` /
    ``selected``; of a sparse layer also ``chosen``, ``margin [T]`` and
    ``differs [T]`` (``reference_bailing_hybrid.routed``'s) and ``inputs
    [keep, d]``, its float32 input at the first ``keep`` positions."""
    eps = shape["eps"]
    x, seen = attention(x, _rms(x, p["input_layernorm"]["scale"], eps),
                        p["attn"], shape, selected)
    scale, mlp = p["post_attention_layernorm"]["scale"], p["mlp"]
    if not sparse:
        return x + _rows(lambda r: grouped.swiglu(_rms(r, scale, eps), mlp),
                         x), seen
    t = x.shape[0]
    step = _block(t, _ROW_BLOCK)
    if routed is None:
        routed = jnp.full((t, shape["top_k"]), -1, jnp.int32)

    def block(args):
        r, given = args
        h = _rms(r, scale, eps)
        chosen, weights, margin, differs = grouped.routed(h, mlp, shape,
                                                          given)
        y = grouped.expert_terms(h, mlp, shape["first_expert"], chosen,
                                 weights)
        return (r + y + grouped.swiglu(h, mlp["shared_experts"]), chosen,
                margin, differs)

    out, chosen, margin, differs = jax.lax.map(
        block, (x.reshape(t // step, step, -1),
                routed.reshape(t // step, step, -1)))
    seen.update(chosen=chosen.reshape(t, -1), margin=margin.reshape(t),
                differs=differs.reshape(t))
    if keep:
        seen["inputs"] = _rms(x[:keep], scale, eps)
    return out.reshape(t, -1), seen


def layer_params(params, i: int) -> dict:
    """Layer ``i``'s own entries of the parameter tree, by :func:`layer`'s
    names."""
    return {name: params[f"layers_{i}_{name}"] for name in (
        "attn", "mlp", "input_layernorm", "post_attention_layernorm")}


def embed(params, input_ids):
    return _f32(params["embed_tokens"][input_ids])


def head(params, x, shape, at=None):
    """The final norm and the untied head, at the positions ``at``."""
    if at is not None:
        x = x[at]
    return _rms(x, params["norm"]["scale"], shape["eps"]) @ _f32(
        params["lm_head"]).T


def sparse_layers(shape) -> list:
    return list(range(shape["dense"], shape["layers"]))


def _forward(params, ids, shape, given=None, selected=None):
    """One sequence ``ids [T]`` through every layer -> ``(stream, [seen a
    layer])``. ``given [T, sparse layers, k]``, ``selected [T, layers,
    words]``."""
    x, seen = embed(params, ids), []
    sparse = sparse_layers(shape)
    for i in range(shape["layers"]):
        x, saw = layer(
            x, layer_params(params, i), shape, i in sparse,
            None if given is None or i not in sparse
            else given[:, sparse.index(i)],
            None if selected is None else selected[:, i])
        seen.append(saw)
    return x, seen


def logits(params, input_ids, shape, given=None, selected=None,
           with_layers=False, at=None):
    """Float32 logits of ``input_ids [rows, T]`` over the slice of the
    vocabulary held: ``[rows, T, vocab]``, or with ``at [n]`` ``[rows, n,
    vocab]``. ``given [rows, T, sparse layers, k]`` int32 routed sets and
    ``selected [rows, T, layers, words]`` uint32 chosen keys, to take in
    place of the reference's own. ``with_layers``: also ``{"selected":
    [rows, T, layers, words], "select_margin" / "select_flips": [rows,
    layers, T], "margin" / "differs": [rows, sparse layers, T], "chosen":
    [rows, T, sparse layers, k]}``."""
    with jax.default_matmul_precision("highest"):
        outs, layers = [], []
        for row in range(input_ids.shape[0]):
            x, seen = _forward(
                params, input_ids[row], shape,
                None if given is None else given[row],
                None if selected is None else selected[row])
            outs.append(head(params, x, shape, at))
            layers.append(seen)
        out = jnp.stack(outs)
        if not with_layers:
            return out

        def stacked(key, axis=0, only_sparse=False):
            return jnp.stack([jnp.stack(
                [saw[key] for saw in seen if not only_sparse
                 or "margin" in saw], axis) for seen in layers])

        more = {"selected": stacked("selected", 1),
                "select_margin": stacked("select_margin"),
                "select_flips": stacked("select_flips")}
        if sparse_layers(shape):
            more.update(margin=stacked("margin", 0, True),
                        differs=stacked("differs", 0, True),
                        chosen=stacked("chosen", 1, True))
        return out, more


def balanced_biases(params, input_ids, shape, steps, rate):
    """``[sparse layers, experts]``: every sparse layer's selection bias
    balanced over ``input_ids [rows, T]`` (``reference_bailing_hybrid
    .balanced_bias``: the auxiliary-loss-free rule, through the groups),
    layer by layer, a later layer's input routed by the earlier layers'
    balanced biases. No part of the model: it makes a seed's selection
    biases what training leaves them."""
    found = {}
    rows = input_ids.shape[0]

    with jax.default_matmul_precision("highest"):
        xs = [embed(params, input_ids[row]) for row in range(rows)]
        for i in range(shape["layers"]):
            name = f"layers_{i}_mlp"
            if i >= shape["dense"]:
                # the layer's FFN input: the stream behind its attention
                mids = [attention(
                    x, _rms(x, params[f"layers_{i}_input_layernorm"]["scale"],
                            shape["eps"]), params[f"layers_{i}_attn"],
                    shape)[0] for x in xs]
                h = jnp.concatenate([_rms(
                    m, params[f"layers_{i}_post_attention_layernorm"][
                        "scale"], shape["eps"]) for m in mids])
                found[i] = grouped.balanced_bias(
                    h, params[name], shape, steps, rate).astype(
                        params[name]["router_bias"].dtype)
                params = {**params, name: {**params[name],
                                           "router_bias": found[i]}}
            xs = [layer(x, layer_params(params, i), shape,
                        i >= shape["dense"])[0] for x in xs]
    return jnp.stack([found[i] for i in sparse_layers(shape)])
