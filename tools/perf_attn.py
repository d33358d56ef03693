"""Calibrate flash-attention variants on the real chip."""

import functools
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

    from deepspeed_tpu.ops.flash_attention import flash_attention

    B, H, T, D = 16, 12, 1024, 64
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.bfloat16)

    for bq, bk in [(512, 512), (1024, 512), (1024, 1024), (256, 1024)]:
        def loss(q, bq=bq, bk=bk):
            return jnp.sum(flash_attention(q, q, q, True, None, bq, bk)
                           .astype(jnp.float32))

        f = jax.jit(jax.value_and_grad(loss))
        print(f"ours bq={bq} bk={bk}: {timeit(f, q):.2f} ms")

    # jax built-in TPU flash attention for calibration
    try:
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            BlockSizes, flash_attention as jx_flash)

        bs = BlockSizes(block_q=512, block_k_major=512, block_k=512,
                        block_b=1,
                        block_q_major_dkv=512, block_k_major_dkv=512,
                        block_k_dkv=512, block_q_dkv=512,
                        block_k_major_dq=512, block_k_dq=512,
                        block_q_dq=512)

        def jloss(q):
            return jnp.sum(jx_flash(q, q, q, causal=True, block_sizes=bs)
                           .astype(jnp.float32))

        jf = jax.jit(jax.value_and_grad(jloss))
        print(f"jax builtin flash: {timeit(jf, q):.2f} ms")
    except Exception as e:
        print("jax builtin failed:", repr(e)[:200])


if __name__ == "__main__":
    main()
