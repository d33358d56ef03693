"""Calibrate the chip: device kind, achievable matmul TFLOP/s, splash attn."""

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def timeit(fn, *args, steps=20):
    import jax

    sync = jax.block_until_ready

    out = fn(*args)
    sync(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    sync(out)
    return (time.perf_counter() - t0) / steps * 1000


def main():
    import jax
    import jax.numpy as jnp

    d = jax.devices()[0]
    print("device_kind:", repr(getattr(d, "device_kind", None)),
          "platform:", d.platform)

    rng = np.random.default_rng(0)
    # big square bf16 matmul: the achievable MXU ceiling
    for m, k, n in [(8192, 8192, 8192), (16384, 768, 3072), (16384, 3072, 768)]:
        a = jnp.asarray(rng.normal(size=(m, k)), jnp.bfloat16)
        b = jnp.asarray(rng.normal(size=(k, n)), jnp.bfloat16)
        f = jax.jit(lambda a, b: (a @ b).sum())
        ms = timeit(f, a, b)
        tflops = 2 * m * k * n / (ms / 1000) / 1e12
        print(f"matmul {m}x{k}x{n}: {ms:.2f} ms = {tflops:.1f} TFLOP/s")

    # chained matmuls (12 layers' worth of mlp-ish work, sequential)
    a = jnp.asarray(rng.normal(size=(16384, 768)), jnp.bfloat16)
    ws = [jnp.asarray(rng.normal(size=(768, 768)), jnp.bfloat16)
          for _ in range(24)]

    def chain(a, ws):
        for w in ws:
            a = jnp.tanh(a @ w)
        return a.sum()

    ms = timeit(jax.jit(chain), a, ws)
    tflops = 2 * 16384 * 768 * 768 * 24 / (ms / 1000) / 1e12
    print(f"chain 24x(16384x768x768): {ms:.2f} ms = {tflops:.1f} TFLOP/s")

    # splash attention (jax builtin production kernel)
    try:
        from jax.experimental.pallas.ops.tpu.splash_attention import (
            splash_attention_kernel as sk,
            splash_attention_mask as sm)

        B, H, T, D = 16, 12, 1024, 64
        q = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.bfloat16)
        mask = sm.MultiHeadMask(
            [sm.CausalMask((T, T)) for _ in range(H)])
        kernel = sk.make_splash_mha(
            mask=mask, head_shards=1, q_seq_shards=1)
        vkernel = jax.vmap(kernel)

        def loss(q):
            return jnp.sum(vkernel(q * (D ** -0.5), q, q).astype(jnp.float32))

        f = jax.jit(jax.value_and_grad(loss))
        print(f"splash attn fwd+bwd: {timeit(f, q):.2f} ms")
    except Exception as e:
        print("splash failed:", repr(e)[:300])


if __name__ == "__main__":
    main()
