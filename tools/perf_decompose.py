"""Decompose the bench step time: body vs LM-head loss vs optimizer apply."""

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def timeit(fn, *args, steps=10):
    import jax

    sync = jax.block_until_ready

    out = fn(*args)
    sync(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    sync(out)
    return (time.perf_counter() - t0) / steps * 1000  # ms


def main():
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.gpt2 import (GPT2Config, GPT2LMHeadModel,
                                           chunked_softmax_xent,
                                           cross_entropy_loss, gpt2_loss_fn)

    B, T = 16, 1024
    cfg = GPT2Config(vocab_size=50257, n_positions=1024, n_embd=768,
                     n_layer=12, n_head=12, dtype=jnp.bfloat16,
                     scan_layers=True, remat=False)
    model = GPT2LMHeadModel(cfg)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)), jnp.int32)
    params = jax.jit(lambda r: model.init(r, ids[:2])["params"])(
        jax.random.PRNGKey(0))
    print("params dtypes:", {jax.tree_util.tree_leaves(params)[0].dtype})

    # 1. full loss fwd+bwd (the engine's micro_step core)
    loss_fn = gpt2_loss_fn(model)
    full = jax.jit(jax.value_and_grad(lambda p: loss_fn(p, (ids, ids))))
    print(f"full fwd+bwd: {timeit(full, params):.1f} ms")

    # 2. body only: hidden out, dummy loss
    def body_loss(p):
        hidden, _ = model.apply({"params": p}, ids, return_hidden=True)
        return jnp.sum(hidden.astype(jnp.float32))

    body = jax.jit(jax.value_and_grad(body_loss))
    print(f"body fwd+bwd: {timeit(body, params):.1f} ms")

    # 3. head only: fixed hidden, loss vs labels (chunked)
    hidden = jnp.asarray(rng.normal(size=(B, T, cfg.n_embd)), jnp.bfloat16)
    wte = params["wte"]

    def head_loss(w, h):
        return chunked_softmax_xent(h, w, ids)

    head = jax.jit(jax.value_and_grad(head_loss))
    print(f"head(chunk128) fwd+bwd: {timeit(head, wte, hidden):.1f} ms")

    def head_loss_c512(w, h):
        return chunked_softmax_xent(h, w, ids, chunk=512)

    head512 = jax.jit(jax.value_and_grad(head_loss_c512))
    print(f"head(chunk512) fwd+bwd: {timeit(head512, wte, hidden):.1f} ms")

    def head_dense(w, h):
        logits = jnp.einsum("btc,vc->btv", h, w.astype(h.dtype),
                            preferred_element_type=jnp.float32)
        return cross_entropy_loss(logits, ids)

    headd = jax.jit(jax.value_and_grad(head_dense))
    print(f"head(dense) fwd+bwd: {timeit(headd, wte, hidden):.1f} ms")

    # 4. fwd only of full loss
    fwd = jax.jit(lambda p: loss_fn(p, (ids, ids)))
    print(f"full fwd only: {timeit(fwd, params):.1f} ms")

    # 5. body fwd only
    fwd_body = jax.jit(
        lambda p: model.apply({"params": p}, ids, return_hidden=True)[0])
    print(f"body fwd only: {timeit(fwd_body, params):.1f} ms")

    # 6. one block fwd+bwd standalone (scan body cost x12 ~ body?)
    # attention-only timing via ops.attention
    from deepspeed_tpu.ops.attention import attention
    from deepspeed_tpu.ops.flash_attention import flash_attention

    q = jnp.asarray(rng.normal(size=(B, 12, T, 64)), jnp.bfloat16)

    def att_loss(q):
        return jnp.sum(flash_attention(q, q, q, True).astype(jnp.float32))

    att = jax.jit(jax.value_and_grad(att_loss))
    print(f"flash attn fwd+bwd (1 layer): {timeit(att, q):.1f} ms")

    def att_ref_loss(q):
        from deepspeed_tpu.ops.attention import attention_reference

        return jnp.sum(attention_reference(q, q, q).astype(jnp.float32))

    attr = jax.jit(jax.value_and_grad(att_ref_loss))
    print(f"xla attn fwd+bwd (1 layer): {timeit(attr, q):.1f} ms")


if __name__ == "__main__":
    main()
