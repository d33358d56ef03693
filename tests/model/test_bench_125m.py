"""The ACTUAL 125M smoke program shape, exercised off-chip (VERDICT r4 next #3).

``chip_smoke.py``'s train phase trains GPT-2 125M (seq 1024, bf16,
dots-remat, fused step, dense→chunked LM-head auto-switch). The correctness
suite otherwise runs at toy dims, so the exact program the smoke compiles
was never exercised without the chip. Here, on the CPU mesh:

- the REAL bench-shape program (batch 16 x 1024) is lowered + compiled
  and its ``memory_analysis()`` numbers pinned — the chunked-head switch
  and the dots-remat policy each move temp by gigabytes if they regress;
- a batch-2 variant of the same config RUNS for three steps, pinning the
  loss trajectory (golden values recorded from this gate's first run).

Reference analog: ``tests/model/Megatron_GPT2/run_sanity_check.py`` runs
the real model configs, not proxies.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2ForTraining
from deepspeed_tpu.parallel.topology import MeshTopology, reset_topology

SEQ = 1024
VOCAB = 50257


def _bench_engine(batch):
    """Mirrors chip_smoke.py's 125M train engine (single-chip mesh)."""
    reset_topology()
    topo = MeshTopology(axis_sizes={"data": 1}, devices=jax.devices()[:1])
    cfg = GPT2Config(vocab_size=VOCAB, n_positions=SEQ, n_embd=768,
                     n_layer=12, n_head=12, dtype=jnp.bfloat16,
                     scan_layers=True, remat=True, remat_policy="dots")
    engine, *_ = deepspeed_tpu.initialize(
        model=GPT2ForTraining(cfg),
        mesh=topo,
        config={
            "train_micro_batch_size_per_gpu": batch,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "AdamW",
                          "params": {"lr": 6e-4, "weight_decay": 0.1}},
            "gradient_clipping": 1.0,
            "bf16": {"enabled": True},
            "fused_step": True,
            "zero_optimization": {"stage": 0},
            "steps_per_print": 10_000,
        })
    return cfg, engine


def _ids(batch, seed=0):
    return np.random.default_rng(seed).integers(
        0, VOCAB, (batch, SEQ)).astype(np.int32)


@pytest.mark.heavy
def test_bench_program_compiles_with_pinned_memory():
    """Compile (don't run) the exact batch-16 bench step and pin the
    compiled memory profile."""
    cfg, engine = _bench_engine(16)
    # init params with a TINY batch (param shapes are batch-independent):
    # flax init EXECUTES a forward, and a batch-16 x 1024 forward on one
    # virtual CPU device takes minutes this gate doesn't need
    engine._ensure_state(engine._shard_batch(
        {"input_ids": np.zeros((1, 8), np.int32)}))
    batch = engine._shard_batch({"input_ids": _ids(16)})
    fn = engine._jit_fused
    assert fn is not None, "bench config must take the fused-step path"
    # lower/compile the REAL batch-16 program abstractly — no execution
    ma = fn.lower(engine.state, batch,
                  engine._lr_override()).compile().memory_analysis()
    gib = 2**30
    n_params = sum(int(np.prod(p.shape)) for p in
                   jax.tree_util.tree_leaves(engine.state.params))
    assert n_params == pytest.approx(124.4e6, rel=0.01)  # the "125M"
    # TrainState: fp32 masters + adam mu/nu, measured 1.391 GiB (12
    # bytes/param; 1.854 until PR 32, when the fused step stopped carrying
    # the zero accumulation buffer through every step); a duplicated
    # state copy moves this by ~0.5 GiB
    assert engine.state.grad_acc == {}
    arg = ma.argument_size_in_bytes / gib
    assert 1.2 < arg < 1.6, f"bench TrainState bytes drifted: {arg:.2f} GiB"
    # donation: the state updates in place
    assert ma.alias_size_in_bytes >= 0.9 * ma.argument_size_in_bytes
    # dots-remat pin. Calibrated on this stack (XLA:CPU overestimates via
    # no-reuse + bf16→f32 upcasts, but the DELTA is loud): bench program
    # measured 21.4 GiB temp; the same program with remat OFF measured
    # 42.7 GiB. A remat regression doubles this number.
    temp = ma.temp_size_in_bytes / gib
    assert temp < 30.0, (
        f"bench-step temp {temp:.2f} GiB (calibrated 21.4; remat-off "
        "measures 42.7): the dots-remat policy regressed")


def test_lm_head_auto_switch_boundary(monkeypatch):
    """The dense↔chunked LM-head switch at the BENCH shape: b16 x 1024 x
    50257 fp32 logits are 3.29 GB — under the 3.5 GB remat-mode budget,
    so the bench program takes the DENSE head (PERF.md r2 item 3: dense
    beats chunked when it fits); doubling the batch must flip to the
    chunked path. Checked via eval_shape — no FLOPs run."""
    import deepspeed_tpu.models.gpt2 as G

    calls = []

    def spy(*a, **k):
        calls.append("chunked")
        return G.jnp.zeros(())

    monkeypatch.setattr(G, "chunked_softmax_xent", spy)
    hidden16 = jax.ShapeDtypeStruct((16, SEQ, 768), jnp.bfloat16)
    hidden32 = jax.ShapeDtypeStruct((32, SEQ, 768), jnp.bfloat16)
    wte = jax.ShapeDtypeStruct((VOCAB, 768), jnp.float32)
    labels16 = jax.ShapeDtypeStruct((16, SEQ), jnp.int32)
    labels32 = jax.ShapeDtypeStruct((32, SEQ), jnp.int32)
    budget = 3_500_000_000  # gpt2_loss_fn's remat-mode dense budget
    jax.eval_shape(lambda h, w, l: G.lm_head_loss(
        h, w, l, dense_budget=budget), hidden16, wte, labels16)
    assert not calls, "bench shape (3.29 GB logits) must take the dense head"
    jax.eval_shape(lambda h, w, l: G.lm_head_loss(
        h, w, l, dense_budget=budget), hidden32, wte, labels32)
    assert calls == ["chunked"], (
        "2x batch (6.6 GB logits) must flip to the chunked head")


@pytest.mark.heavy
def test_bench_config_loss_trajectory():
    """RUN the bench config (batch 2 for CPU runtime; everything else
    identical) and pin the loss trajectory."""
    cfg, engine = _bench_engine(2)
    ids = _ids(2)  # ONE fixed batch every step, as chip_smoke.py trains
    losses = []
    for _ in range(3):
        loss = engine({"input_ids": ids})
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    # uniform-random tokens: initial loss == ln(V) within bf16 noise
    assert losses[0] == pytest.approx(np.log(VOCAB), abs=0.3)
    assert losses[2] < losses[0], losses
    # golden trajectory from this gate's first green run (bf16, fused
    # step, dots-remat; jax 0.9/XLA:CPU) — drift means the compiled math
    # changed, not just noise
    golden = [10.9606, 10.5073, 9.9036]
    np.testing.assert_allclose(losses, golden, atol=0.05, err_msg=(
        "bench-config loss trajectory drifted from the recorded golden"))
