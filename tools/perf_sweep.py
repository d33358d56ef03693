"""Perf sweep for the bench config (GPT-2 125M, 1 chip).

Runs a matrix of {remat, batch, flash, loss-chunk} variants and prints
tokens/s + MFU for each. Scratch tool behind bench.py tuning.
"""

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_variant(name, cfg_kw, batch, steps=10, seq=1024):
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2ForTraining
    from deepspeed_tpu.parallel.topology import reset_topology

    reset_topology()
    cfg = GPT2Config(vocab_size=50257, n_positions=1024, n_embd=768,
                     n_layer=12, n_head=12, dtype=jnp.bfloat16,
                     scan_layers=True, **cfg_kw)
    model = GPT2ForTraining(cfg)
    engine, *_ = deepspeed_tpu.initialize(
        model=model,
        config={
            "train_batch_size": batch,
            "optimizer": {"type": "AdamW",
                          "params": {"lr": 6e-4, "weight_decay": 0.1}},
            "gradient_clipping": 1.0,
            "bf16": {"enabled": True},
            "zero_optimization": {"stage": 0},
            "steps_per_print": 10_000,
        })
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32)

    def _sync():
        jax.block_until_ready(engine.state.params)

    loss = engine({"input_ids": ids})
    engine.backward(loss)
    engine.step()
    _sync()

    t0 = time.perf_counter()
    for _ in range(steps):
        loss = engine({"input_ids": ids})
        engine.backward(loss)
        engine.step()
    float(loss)
    _sync()
    dt = time.perf_counter() - t0

    tps = steps * batch * seq / dt
    n_params = sum(int(np.prod(p.shape)) for p in
                   jax.tree_util.tree_leaves(engine.state.params))
    mfu = tps * 6 * n_params / 394e12
    print(json.dumps({"variant": name, "batch": batch,
                      "tokens_per_sec": round(tps, 1),
                      "mfu_pct": round(100 * mfu, 2),
                      "step_ms": round(1000 * dt / steps, 1)}), flush=True)
    del engine, model
    gc.collect()
    return tps


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--set", default="base")
    args = ap.parse_args()

    if args.set == "base":
        run_variant("r1_baseline(remat,b16)", {"remat": True}, 16)
        run_variant("no_remat_b16", {"remat": False}, 16)
        run_variant("no_remat_b32", {"remat": False}, 32)
        run_variant("no_remat_b64", {"remat": False}, 64)
    elif args.set == "flash":
        run_variant("no_remat_b32_noflash", {"remat": False, "use_flash": False}, 32)
        run_variant("no_remat_b32_flash", {"remat": False, "use_flash": True}, 32)


if __name__ == "__main__":
    main()
