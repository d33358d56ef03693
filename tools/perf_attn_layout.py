"""BHTD vs BTHD flash-attention layout on the real chip.

PERF.md names ~10-16 ms/step of XLA layout copies around the pallas
custom-call in the [B, H, T, D] path. flash_attention_bthd reads the
projection-natural [B, T, H, D] strided instead. This measures, at the
GPT-2 bench shapes, (a) the bare kernels including the transposes the
BHTD path forces, and (b) a full train-step A/B via attn_layout.
If BTHD wins, flip ``attn_layout="bthd"`` in bench.py's GPT2Config.
Run on the chip: python tools/perf_attn_layout.py
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.ops.flash_attention import (flash_attention,
                                               flash_attention_bthd)
from deepspeed_tpu.utils.marginal_bench import marginal_cost_ms

B, T, H, D = 16, 1024, 12, 64


def kernel_ab():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (B, T, H, D), jnp.bfloat16) * 0.3
               for kk in ks)

    def bhtd(q, k, v):
        # includes the transposes the model would pay around the kernel
        t = lambda x: x.transpose(0, 2, 1, 3)
        return flash_attention(t(q), t(k), t(v), causal=True) \
            .transpose(0, 2, 1, 3)

    def bthd(q, k, v):
        return flash_attention_bthd(q, k, v, causal=True)

    def bhtd_grad(q, k, v):
        return jax.grad(lambda a, b, c: jnp.sum(
            bhtd(a, b, c).astype(jnp.float32)), argnums=(0, 1, 2))(q, k, v)

    def bthd_grad(q, k, v):
        return jax.grad(lambda a, b, c: jnp.sum(
            bthd(a, b, c).astype(jnp.float32)), argnums=(0, 1, 2))(q, k, v)

    for name, fn in (("fwd bhtd+T", bhtd), ("fwd bthd   ", bthd),
                     ("fwdbwd bhtd+T", bhtd_grad), ("fwdbwd bthd   ", bthd_grad)):
        print(f"{name}: {marginal_cost_ms(fn, q, k, v, iters=12):7.2f} ms")


def step_ab():
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2ForTraining
    from deepspeed_tpu.parallel.topology import reset_topology

    ids = np.random.default_rng(0).integers(0, 50257, (B, T)).astype(np.int32)
    for layout in ("bhtd", "bthd"):
        reset_topology()
        cfg = GPT2Config(dtype=jnp.bfloat16, scan_layers=True, remat=True,
                         remat_policy="dots", attn_layout=layout)
        engine, *_ = deepspeed_tpu.initialize(
            model=GPT2ForTraining(cfg),
            config={"train_micro_batch_size_per_gpu": B,
                    "gradient_accumulation_steps": 1,
                    "optimizer": {"type": "AdamW", "params": {"lr": 6e-4}},
                    "bf16": {"enabled": True}, "fused_step": True,
                    "steps_per_print": 100_000})
        batch = {"input_ids": ids}
        loss = engine(batch); engine.backward(loss); engine.step()
        jax.block_until_ready(engine.state.params)
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(5):
                loss = engine(batch); engine.backward(loss); engine.step()
            jax.block_until_ready((loss, engine.state.params))
            best = min(best, (time.perf_counter() - t0) / 5)
        print(f"train step {layout}: {1e3 * best:7.1f} ms")


if __name__ == "__main__":
    kernel_ab()
    step_ab()
