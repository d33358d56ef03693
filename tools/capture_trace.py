"""Capture an xprof trace of the bench train step or a decode step.

    python tools/capture_trace.py --what train --out /tmp/xprof
    python tools/capture_trace.py --what decode

Writes a TensorBoard-compatible XPlane trace directory (open with
``tensorboard --logdir <out>`` + the profile plugin, or
``xprof <out>``). The per-op breakdown there answers scheduling
questions the chained timers in ``perf_*.py`` cannot (which fusion, which
copy, which custom call).
"""

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--what", default="train", choices=("train", "decode"))
    p.add_argument("--out", default="/tmp/ds_tpu_xprof")
    p.add_argument("--steps", type=int, default=5,
                   help="traced steps (after an untraced warmup)")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel

    on_tpu = jax.devices()[0].platform != "cpu"
    if args.what == "train":
        from deepspeed_tpu.models.gpt2 import GPT2ForTraining

        cfg = (GPT2Config.gpt2_125m(vocab_size=50257, n_positions=1024,
                                    dtype=jnp.bfloat16, scan_layers=True)
               if on_tpu else GPT2Config.tiny())
        B, T = (16, 1024) if on_tpu else (2, 16)
        engine, *_ = deepspeed_tpu.initialize(
            model=GPT2ForTraining(cfg),
            config={"train_batch_size": B, "fused_step": True,
                    "optimizer": {"type": "AdamW", "params": {"lr": 6e-4}},
                    "bf16": {"enabled": on_tpu}, "steps_per_print": 10_000})
        ids = np.random.default_rng(0).integers(
            0, cfg.vocab_size, (B, T)).astype(np.int32)

        def step():
            loss = engine({"input_ids": ids})
            engine.backward(loss)
            engine.step()
            return loss

    else:
        cfg = (GPT2Config.gpt2_125m(vocab_size=50257, n_positions=1024,
                                    dtype=jnp.bfloat16, scan_layers=True)
               if on_tpu else GPT2Config.tiny())
        B, prompt = (8, 128) if on_tpu else (2, 8)
        engine = deepspeed_tpu.init_inference(
            GPT2LMHeadModel(cfg), dtype=cfg.dtype,
            max_out_tokens=cfg.n_positions)
        ids = np.random.default_rng(0).integers(
            0, cfg.vocab_size, (B, prompt)).astype(np.int32)

        def step():
            return engine.generate(ids, max_new_tokens=16, do_sample=False)

    out = step()  # warmup/compile outside the trace
    jax.block_until_ready(out)

    with jax.profiler.trace(args.out):
        for _ in range(args.steps):
            out = step()
        jax.block_until_ready(out)
    print(f"trace written to {args.out} "
          f"({args.steps} {args.what} steps, platform="
          f"{jax.devices()[0].platform})")


if __name__ == "__main__":
    main()
