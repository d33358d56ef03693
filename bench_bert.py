"""Benchmark: BERT-large MLM pretrain throughput on one chip.

The reference's headline training benchmark ("fastest BERT", BASELINE.md
rows 1-2: 64 TFLOP/s per V100 at seq 128, 53 at seq 512). Prints ONE JSON
line mirroring bench.py's contract:
``{"metric", "value", "unit", "vs_baseline"}`` where ``vs_baseline`` is
sustained TFLOP/s divided by the reference's 64 TFLOP/s seq-128 number —
>1.0 beats the reference hardware-for-era.
"""

import time

import numpy as np

from deepspeed_tpu.utils.device import (cpu_requested, emit_result,
                                        require_device)

REF_TFLOPS = 64.0  # docs/_posts/2020-05-28-fastest-bert-training.md:37
# the smoke name under an explicit JAX_PLATFORMS=cpu: a CPU run is never
# filed under the device metric
METRIC = ("bert_tiny_cpu_smoke_tflops" if cpu_requested()
          else "bert_large_mlm_tflops_per_chip")


def main():
    # the TPU, or the CPU when it was asked for by name; anything else raises
    dev = require_device("tpu")

    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models.bert import BertConfig, BertForTraining

    on_tpu = dev["platform"] == "tpu"
    if on_tpu:
        cfg = BertConfig.bert_large(dtype=jnp.bfloat16, remat=True,
                                    remat_policy="dots",
                                    max_position_embeddings=512)
        batch, seq, steps = 64, 128, 10
    else:  # JAX_PLATFORMS=cpu was asked for: a tiny run, under the smoke name
        cfg = BertConfig.tiny(dtype=jnp.float32)
        batch, seq, steps = 8, 32, 3

    model = BertForTraining(cfg)
    engine, *_ = deepspeed_tpu.initialize(
        model=model,
        config={
            "train_micro_batch_size_per_gpu": batch,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "Adam",
                          "params": {"lr": 1e-4, "weight_decay": 0.01}},
            "gradient_clipping": 1.0,
            "bf16": {"enabled": on_tpu},
            "fused_step": True,
            "zero_optimization": {"stage": 2 if on_tpu else 0},
            "steps_per_print": 10_000,
        })
    rng = np.random.default_rng(0)
    ids = rng.integers(4, cfg.vocab_size, (batch, seq)).astype(np.int32)
    labels = np.where(rng.random((batch, seq)) < 0.15, ids, -100)
    batch_data = {"input_ids": ids, "labels": labels.astype(np.int32)}

    def _sync():
        jax.block_until_ready(engine.state.params)

    loss = engine(batch_data)
    engine.backward(loss)
    engine.step()
    _sync()

    t0 = time.perf_counter()
    for _ in range(steps):
        loss = engine(batch_data)
        engine.backward(loss)
        engine.step()
    float(loss)
    _sync()
    dt = time.perf_counter() - t0

    samples_per_sec = steps * batch / dt
    n_params = sum(int(np.prod(p.shape)) for p in
                   jax.tree_util.tree_leaves(engine.state.params))
    # 6N per token fwd+bwd + bidirectional attention (12·L·T·C per token)
    flops_per_token = (6 * n_params
                       + 12 * cfg.num_hidden_layers * seq * cfg.hidden_size)
    tflops = samples_per_sec * seq * flops_per_token / 1e12
    emit_result({
        "metric": METRIC,
        "value": round(tflops, 2),
        "unit": "TFLOP/s",
        "vs_baseline": round(tflops / REF_TFLOPS, 4),
        "flops_formula": ("tflops = samples_per_sec * seq * (6N + 12*L*T*C)"
                          " / 1e12, T=seq (bidirectional attn);"
                          f" vs_baseline = tflops / {REF_TFLOPS} (reference"
                          " V100 seq-128 headline)"),
    })


if __name__ == "__main__":
    main()
