"""GPT-2's published equations in plain ``jax.numpy``: the benchmark's
reference for ``correct``.

Radford et al. 2019 / the ``openai-community/gpt2`` implementation:
learned token + position embeddings, ``n_layer`` pre-LN blocks
(``x + attn(ln_1 x)``, then ``x + mlp(ln_2 x)``, tanh-approximated GELU,
causal softmax attention scaled by ``1/sqrt(head_dim)``), a final LN, and
logits against the tied token embedding. float32, matmuls at
``highest`` precision (a TPU otherwise multiplies f32 in bf16 passes), no
kernel, no cache, and no call into ``deepspeed_tpu/models/gpt2.py``.

It reads the program's own parameter tree (layers stacked on a leading
axis under ``transformer/h/block``, the layout ``scan_layers`` gives) and
upcasts one layer at a time inside a scan, so it needs no second copy of
the weights.
"""

import math

import jax
import jax.numpy as jnp

_LN_EPS = 1e-5


def _ln(x, p):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return ((x - mu) / jnp.sqrt(var + _LN_EPS) * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32))


def _dense(x, p):
    return x @ p["kernel"].astype(jnp.float32) + p["bias"].astype(jnp.float32)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, p, n_head: int):
    rows, seq, d = x.shape
    hd = d // n_head
    qkv = _dense(_ln(x, p["ln_1"]), p["attn"]["c_attn"])
    q, k, v = (t.reshape(rows, seq, n_head, hd).transpose(0, 2, 1, 3)
               for t in jnp.split(qkv, 3, axis=-1))
    scores = q @ k.transpose(0, 1, 3, 2) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    att = jax.nn.softmax(scores, axis=-1) @ v
    att = att.transpose(0, 2, 1, 3).reshape(rows, seq, d)
    x = x + _dense(att, p["attn"]["c_proj"])
    h = _gelu_tanh(_dense(_ln(x, p["ln_2"]), p["mlp"]["c_fc"]))
    return x + _dense(h, p["mlp"]["c_proj"])


def logits(params, input_ids, n_head: int):
    """``[rows, T, vocab]`` float32 logits of ``input_ids`` ``[rows, T]``."""
    with jax.default_matmul_precision("highest"):
        wte = params["wte"].astype(jnp.float32)
        seq = input_ids.shape[1]
        x = wte[input_ids] + params["wpe"][:seq].astype(jnp.float32)[None]

        def body(x, layer):
            return _block(x, layer, n_head), None

        x, _ = jax.lax.scan(body, x, params["transformer"]["h"]["block"])
        return _ln(x, params["ln_f"]) @ wte.T


def next_token_loss(params, input_ids, n_head: int):
    """(sum of next-token negative log-likelihoods, token count) over
    ``input_ids``: position t predicts token t+1, the last predicts
    nothing. Sums, so that callers can add chunks of rows."""
    lg = logits(params, input_ids, n_head)[:, :-1]
    gold = input_ids[:, 1:]
    nll = jax.nn.logsumexp(lg, axis=-1) - jnp.take_along_axis(
        lg, gold[..., None], axis=-1)[..., 0]
    return nll.sum(), gold.size
