"""Benchmark: GPT-2 125M training throughput on one chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
``vs_baseline`` is model FLOPs utilization (MFU) relative to the repo's
north-star target of 40% MFU (BASELINE.json: "GPT-2 ... ZeRO-3 ... at >=40%
MFU"); >1.0 beats the target.
"""

import json
import os
import time

import numpy as np

from deepspeed_tpu.utils.compat import arm_compilation_cache
from deepspeed_tpu.utils.device import (cpu_requested, emit_result, peaks,
                                        require_device)

# The smoke name under an explicit JAX_PLATFORMS=cpu, so that a CPU run is
# never filed under the TPU series. Any other platform than these two is
# refused by require_device, so the choice is total.
METRIC = ("gpt2_tiny_cpu_smoke_tokens_per_sec" if cpu_requested()
          else "gpt2_125m_train_tokens_per_sec_per_chip")


def load_autotuned():
    """Best config from ``python -m deepspeed_tpu.autotuning``, if tuned
    FOR THIS bench model (gpt2-125m @ seq 1024) — a config tuned for a
    different model/seq is ignored with a note, not silently applied.

    The autotuner writes autotuning_results/best_config.json; the bench
    honors its micro-batch / zero-stage / remat / fused-step choices so the
    tuned result is what gets reported (VERDICT r1 #7: "the bench uses it").
    """
    for base in (os.path.dirname(os.path.abspath(__file__)), os.getcwd()):
        path = os.path.join(base, "autotuning_results", "best_config.json")
        if not os.path.exists(path):
            continue
        with open(path) as f:
            tuned = json.load(f)
        import jax
        import sys

        mc = (tuned.get("model_spec") or {}).get("config", {})
        if (tuned.get("seq_len") == 1024 and mc.get("n_layer") == 12
                and mc.get("n_embd") == 768
                and mc.get("vocab_size") == 50257
                and tuned.get("dp", 1) == jax.device_count()):
            return tuned
        print(f"bench: ignoring {path} "
              "(tuned for a different model/seq/chip-count)",
              file=sys.stderr)
    return None


def main():
    # the TPU, or the CPU when it was asked for by name; anything else raises
    dev = require_device("tpu")

    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2ForTraining

    arm_compilation_cache()
    # passive compile watchdog: the jax.monitoring listener costs nothing
    # on the hot path and attributes every compile in this process — the
    # telemetry series below reads it without touching the headline run
    from deepspeed_tpu.telemetry import compile_watch

    compile_watch.install()
    on_tpu = dev["platform"] == "tpu"
    tuned = load_autotuned() if on_tpu else None
    if on_tpu:
        # tuned: selective ("dots") remat keeps matmul + flash-attention
        # outputs and recomputes only elementwise chains; fused_step compiles
        # fwd+bwd+optimizer into one program (no grad-acc round trip)
        remat, remat_policy, zero_stage, fused = True, "dots", 0, True
        batch, seq, steps = 16, 1024, 10
        if tuned:
            c = tuned["candidate"]
            batch = int(c["micro_batch"])
            zero_stage = int(c["zero_stage"])
            fused = bool(c.get("fused_step", True))
            remat = c["remat_policy"] != "none"
            remat_policy = c["remat_policy"] if remat else "full"
        cfg = GPT2Config(vocab_size=50257, n_positions=1024, n_embd=768,
                         n_layer=12, n_head=12, dtype=jnp.bfloat16,
                         scan_layers=True, remat=remat,
                         remat_policy=remat_policy)
    else:  # JAX_PLATFORMS=cpu was asked for: a tiny run, under the smoke name
        cfg = GPT2Config.tiny(dtype=jnp.float32)
        batch, seq, steps = 8, 64, 3

    # `batch` is per-chip (matching the trial semantics of the autotuner:
    # train_micro_batch_size_per_gpu); global rows = batch x local chips
    n_dev = jax.device_count()
    rows = batch * n_dev
    model = GPT2ForTraining(cfg)
    engine, *_ = deepspeed_tpu.initialize(
        model=model,
        config={
            "train_micro_batch_size_per_gpu": batch,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "AdamW",
                          "params": {"lr": 6e-4, "weight_decay": 0.1}},
            "gradient_clipping": 1.0,
            "bf16": {"enabled": on_tpu},
            "fused_step": fused if on_tpu else True,
            "zero_optimization": {"stage": zero_stage if on_tpu else 0},
            "steps_per_print": 10_000,
        })
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (rows, seq)).astype(np.int32)

    # warmup / compile
    loss = engine({"input_ids": ids})
    engine.backward(loss)
    engine.step()
    jax.block_until_ready(engine.state.params)
    warm_mark = compile_watch.snapshot()

    t0 = time.perf_counter()
    for _ in range(steps):
        loss = engine({"input_ids": ids})
        engine.backward(loss)
        engine.step()
    jax.block_until_ready((loss, engine.state.params))
    dt = time.perf_counter() - t0

    tokens_per_sec = steps * rows * seq / dt / n_dev  # per chip
    n_params = sum(int(np.prod(p.shape)) for p in
                   jax.tree_util.tree_leaves(engine.state.params))
    # 6N matmul flops (fwd+bwd) + causal attention (PaLM appendix B);
    # single source shared with the autotuner's cost model
    from deepspeed_tpu.autotuning.space import ModelProfile

    model_flops_per_token = ModelProfile(
        n_params=n_params, n_layer=cfg.n_layer, n_embd=cfg.n_embd,
        vocab_size=cfg.vocab_size, seq_len=seq).flops_per_token
    # the one peaks table (utils/device.py); a CPU run has no peak and so
    # no MFU: "not measured", never a nominal denominator
    peak = peaks(dev["kind"]).bf16_flops if on_tpu else None
    mfu = tokens_per_sec * model_flops_per_token / peak if peak else None
    # peak + formula inline so the driver capture is self-auditing (no
    # PERF.md cross-reference needed to re-derive the MFU arithmetic)
    emit_result({
        "metric": METRIC,
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.40, 4) if mfu is not None else None,
        "mfu": round(mfu, 4) if mfu is not None else None,
        "peak_tflops_bf16": round(peak / 1e12, 1) if peak else None,
        "flops_per_token": int(model_flops_per_token),
        "mfu_formula": ("mfu = tokens_per_sec * flops_per_token / peak_bf16;"
                        " flops_per_token = 6N + 12*L*T*C/2 (causal attn,"
                        " PaLM appx B); vs_baseline = mfu / 0.40"),
    })
    # Everything below is an extra series; one that fails raises and the
    # run exits non-zero. Each series function RETURNS its payload (importable through
    # run_series — the live autotuner calls them in-process); the CLI
    # emits them here, in the same order as always
    emit_result(_telemetry_series(warm_mark, steps))
    emit_result(_resilience_series(cfg, batch, seq, on_tpu))
    emit_result(_comm_compression_series(cfg, batch, seq, on_tpu))
    emit_result(_elastic_resume_series(cfg, batch, seq, on_tpu))
    emit_result(_startup_series(cfg, batch, seq, on_tpu))
    emit_result(_tracing_series(cfg, batch, seq, on_tpu))
    emit_result(_metrics_series(cfg, batch, seq, on_tpu))
    emit_result(_tp_series(cfg, batch, seq, on_tpu))
    emit_result(_overlap_series(cfg, batch, seq, on_tpu))


def _telemetry_series(warm_mark, steps):
    """Optional extra series: compile seconds, retrace count over the
    timed window, and peak device memory — read from the passive compile
    watchdog + accelerator stats, so the headline run's dispatch path is
    untouched. A retrace count > 0 here means the timed steps paid
    compile time and the headline number is not a steady-state rate."""

    from deepspeed_tpu.accelerator import get_accelerator
    from deepspeed_tpu.telemetry import compile_watch

    snap = compile_watch.snapshot()
    retraces = (snap["backend_compiles"]
                - warm_mark["backend_compiles"])
    mem = get_accelerator().memory_stats()
    return {
        "metric": METRIC + "_telemetry",
        "value": round(snap["backend_compile_secs"], 3),
        "unit": "compile_seconds",
        "vs_baseline": None,
        "backend_compiles": snap["backend_compiles"],
        "retraces_in_timed_window": retraces,
        "timed_steps": steps,
        "jaxpr_trace_seconds": snap["jaxpr_trace_secs"],
        "persistent_cache_hits": snap["persistent_cache_hits"],
        "peak_bytes_in_use": mem.get("peak_bytes_in_use"),
        "bytes_in_use": mem.get("bytes_in_use"),
        "memory_source": mem.get("source"),
    }


def _resilience_series(cfg, batch, seq, on_tpu, steps=5):
    """Optional extra series: sentinel+watchdog overhead. Two proofs on
    one JSON line — (1) with resilience DISABLED the step program XLA
    sees is identical to a resilience-free build (the zero-overhead
    contract, compared on the lowered step text so no extra backend
    compile is paid); (2) with resilience ENABLED (sentinel warn policy +
    armed watchdog) the wall-clock per step is unchanged within noise
    (`vs_baseline` = enabled/disabled step rate, expected ~1.0 — the
    dispatch path gains only a deque append and a lagged float())."""
    import jax
    import numpy as np_

    import deepspeed_tpu

    from deepspeed_tpu.models.gpt2 import GPT2ForTraining

    n_dev = jax.device_count()
    rows = batch * n_dev
    rng = np_.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (rows, seq)).astype(np_.int32)

    def build(resilience):
        from deepspeed_tpu.parallel.topology import reset_topology

        reset_topology()
        config = {
            "train_micro_batch_size_per_gpu": batch,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "AdamW", "params": {"lr": 6e-4}},
            "bf16": {"enabled": on_tpu},
            "zero_optimization": {"stage": 0},
            "steps_per_print": 10_000,
        }
        if resilience is not None:
            config["resilience"] = resilience
        engine, *_ = deepspeed_tpu.initialize(
            model=GPT2ForTraining(cfg), config=config)
        return engine

    def step_text(engine):
        # lowered (pre-backend-compile) text: program equality proof
        # without paying a second XLA compile
        return engine._jit_micro.lower(
            engine.state, engine._shard_batch({"input_ids": ids})
        ).as_text()

    def rate(engine):
        loss = engine({"input_ids": ids})
        engine.backward(loss)
        engine.step()
        jax.block_until_ready(engine.state.params)
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = engine({"input_ids": ids})
            engine.backward(loss)
            engine.step()
        float(loss)
        jax.block_until_ready(engine.state.params)
        return steps / (time.perf_counter() - t0)

    absent = build(None)
    absent._ensure_state(absent._shard_batch({"input_ids": ids}))
    text_absent = step_text(absent)
    absent_rate = rate(absent)
    absent.destroy()

    disabled = build({"enabled": False})
    disabled._ensure_state(disabled._shard_batch({"input_ids": ids}))
    hlo_identical = step_text(disabled) == text_absent
    disabled.destroy()

    enabled = build({
        "enabled": True,
        "sentinel": {"policy": "warn", "sync_lag": 1},
        "watchdog": {"timeout_secs": 3600, "abort": False}})
    enabled_rate = rate(enabled)
    enabled.destroy()

    return {
        "metric": METRIC + "_resilience",
        "value": round(enabled_rate, 3),
        "unit": "steps/s",
        "vs_baseline": round(enabled_rate / absent_rate, 4)
        if absent_rate else None,
        "disabled_steps_per_sec": round(absent_rate, 3),
        "enabled_steps_per_sec": round(enabled_rate, 3),
        "hlo_identical_when_disabled": bool(hlo_identical),
        "sentinel_policy": "warn",
        "watchdog_armed": True,
        "n_dev": n_dev,
    }


def _comm_compression_series(cfg, batch, seq, on_tpu, steps=5):
    """Optional extra series: wall-clock of the same train step with the
    gradient reduction on the dense vs int8 wire (``comm_quantization``).
    One JSON line of its own, emitted AFTER the headline. On a single
    chip the engine falls back to the dense path (dp=1, nothing crosses a
    wire) and the line records that honestly — the series becomes
    meaningful on a multi-chip window."""
    import jax
    import numpy as np_

    import deepspeed_tpu

    from deepspeed_tpu.models.gpt2 import GPT2ForTraining

    n_dev = jax.device_count()
    rows = batch * n_dev
    rng = np_.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (rows, seq)).astype(np_.int32)

    def rate(cq):
        config = {
            "train_micro_batch_size_per_gpu": batch,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "AdamW", "params": {"lr": 6e-4}},
            "bf16": {"enabled": on_tpu},
            "zero_optimization": {"stage": 0},
            "steps_per_print": 10_000,
        }
        if cq:
            config["comm_quantization"] = cq
        from deepspeed_tpu.parallel.topology import reset_topology

        reset_topology()
        engine, *_ = deepspeed_tpu.initialize(
            model=GPT2ForTraining(cfg), config=config)
        active = engine.comm_quantization_enabled()
        loss = engine({"input_ids": ids})
        engine.step()
        jax.block_until_ready(engine.state.params)
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = engine({"input_ids": ids})
            engine.backward(loss)
            engine.step()
        float(loss)
        jax.block_until_ready(engine.state.params)
        engine.destroy()
        return steps * rows * seq / (time.perf_counter() - t0) / n_dev, \
            active

    dense_tps, _ = rate(None)
    int8_tps, int8_active = rate(
        {"enabled": True, "dtype": "int8"})
    return {
        "metric": METRIC + "_comm_compression",
        "value": round(int8_tps, 1),
        "unit": "tokens/s",
        "dense_tokens_per_sec": round(dense_tps, 1),
        "int8_tokens_per_sec": round(int8_tps, 1),
        "int8_wire_active": bool(int8_active),
        "n_dev": n_dev,
        "vs_baseline": round(int8_tps / dense_tps, 4) if dense_tps else None,
    }


def _elastic_resume_series(cfg, batch, seq, on_tpu):
    """Optional extra series: checkpoint restore wall time, same-mesh vs
    reshard-at-load onto HALF the mesh (the elastic topology-shift
    path — a checkpoint saved at N-way partitioning materialized under
    N/2-way sharding from the saved topology manifest). One JSON line
    emitted AFTER the headline; `vs_baseline` = reshard/same-mesh
    restore time (~1.0 means the reshard path costs nothing extra). On
    a single chip the reshard leg records null — the series becomes
    meaningful on a multi-chip window."""
    import shutil
    import tempfile

    import jax
    import numpy as np_

    import deepspeed_tpu

    from deepspeed_tpu.models.gpt2 import GPT2ForTraining
    from deepspeed_tpu.parallel.topology import (MeshTopology,
                                                 reset_topology)

    n_dev = jax.device_count()
    rows = batch * n_dev  # global batch held constant across meshes
    rng = np_.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (rows, seq)).astype(np_.int32)

    def build(ndev):
        reset_topology()
        topo = MeshTopology(axis_sizes={"data": ndev},
                            devices=jax.devices()[:ndev])
        engine, *_ = deepspeed_tpu.initialize(
            model=GPT2ForTraining(cfg), mesh=topo,
            config={
                "train_batch_size": rows,
                "optimizer": {"type": "AdamW", "params": {"lr": 6e-4}},
                "bf16": {"enabled": on_tpu},
                "zero_optimization": {"stage": 0},
                "steps_per_print": 10_000,
                # arms the topology manifest on every save
                "elasticity": {"enabled": True,
                               "max_train_batch_size": rows,
                               "micro_batch_sizes": [batch],
                               "min_gpus": 1, "max_gpus": n_dev,
                               "version": 0.1},
            })
        return engine

    def step(engine):
        loss = engine({"input_ids": ids})
        engine.backward(loss)
        engine.step()
        float(loss)
        jax.block_until_ready(engine.state.params)

    def timed_restore(ndev, save_dir):
        engine = build(ndev)
        step(engine)  # template state + compile outside the window
        t0 = time.perf_counter()
        engine.load_checkpoint(save_dir, tag="bench")
        jax.block_until_ready(engine.state.params)
        dt = time.perf_counter() - t0
        engine.destroy()
        return dt

    save_dir = tempfile.mkdtemp(prefix="bench_elastic_")
    try:
        saver = build(n_dev)
        step(saver)
        saver.save_checkpoint(save_dir, tag="bench")
        saver.destroy()
        same = timed_restore(n_dev, save_dir)
        half = (timed_restore(n_dev // 2, save_dir)
                if n_dev >= 2 else None)
    finally:
        shutil.rmtree(save_dir, ignore_errors=True)

    return {
        "metric": METRIC + "_elastic_resume",
        "value": round(same, 4),
        "unit": "restore_seconds",
        "vs_baseline": round(half / same, 4) if half else None,
        "same_mesh_restore_secs": round(same, 4),
        "reshard_restore_secs": round(half, 4) if half is not None
        else None,
        "saved_world": n_dev,
        "reshard_world": n_dev // 2 if n_dev >= 2 else None,
    }


def _train_step_series(cfg, batch, seq, on_tpu, steps=3, ds_overrides=None,
                       tunables=None):
    """Importable, parameterized train-step measurement — the live
    autotuner's training-side hook (``run_series("train_step", ...)``).
    Builds a telemetry-enabled engine with the candidate's ds-config
    overrides (and, for tile axes, temporarily-installed kernel
    tunables), then reports the telemetry-stream objectives next to the
    step rate: compile seconds, retraces INSIDE the timed window, and
    the compiled step's collective wire bytes (the step_cost events) —
    a candidate that is fast but retraces every step must lose."""
    import jax
    import numpy as np_

    import deepspeed_tpu
    from deepspeed_tpu.autotuning import runtime_tunables
    from deepspeed_tpu.models.gpt2 import GPT2ForTraining
    from deepspeed_tpu.parallel.topology import reset_topology

    n_dev = jax.device_count()
    rows = batch * n_dev
    rng = np_.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (rows, seq)).astype(np_.int32)
    config = {
        "train_micro_batch_size_per_gpu": batch,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 6e-4}},
        "bf16": {"enabled": on_tpu},
        "zero_optimization": {"stage": 0},
        "steps_per_print": 10_000,
        "telemetry": {"enabled": True, "jsonl": False, "memory": False},
    }
    for k, v in (ds_overrides or {}).items():
        if isinstance(v, dict):
            config[k] = {**config.get(k, {}), **v}
        else:
            config[k] = v
    token = runtime_tunables.install(dict(tunables)) if tunables else None
    engine = None
    try:
        reset_topology()
        engine, *_ = deepspeed_tpu.initialize(model=GPT2ForTraining(cfg),
                                              config=config)
        loss = engine({"input_ids": ids})
        engine.backward(loss)
        engine.step()
        jax.block_until_ready(engine.state.params)
        warm = engine.telemetry.summary()
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = engine({"input_ids": ids})
            engine.backward(loss)
            engine.step()
        float(loss)
        jax.block_until_ready(engine.state.params)
        dt = time.perf_counter() - t0
        summary = engine.telemetry.summary()
        costs = [e["data"] for e in engine.telemetry.tail(200)
                 if e["kind"] == "step_cost"]
        wire = max((c.get("collective_operand_bytes") or 0 for c in costs),
                   default=0)
        per_axis = (max(costs, key=lambda c:
                        c.get("collective_operand_bytes") or 0)
                    .get("collective_bytes_per_axis") or {}) if costs else {}
    finally:
        # a failed candidate is tuner EVIDENCE, not a crash — the next
        # candidate must not measure against this one's leaked engine
        # (live telemetry, still-allocated device arrays), and even a
        # RAISING destroy() must not leave this candidate's tunables
        # installed for every later trial
        try:
            if engine is not None:
                engine.destroy()
        finally:
            runtime_tunables.uninstall(token)
    compiles = {k: v["compiles"] for k, v in summary["per_function"].items()}
    warm_compiles = sum(v["compiles"] for v in warm["per_function"].values())
    retraces = sum(compiles.values()) - warm_compiles
    return {
        "metric": METRIC + "_train_step",
        "steps_per_sec": round(steps / dt, 4),
        "tokens_per_sec": round(steps * rows * seq / dt / n_dev, 1),
        "compile_secs": round(sum(v["compile_secs"] for v in
                                  summary["per_function"].values()), 3),
        "retraces_in_timed_window": int(retraces),
        "collective_wire_bytes": int(wire),
        "collective_bytes_per_axis": {k: int(v) for k, v in per_axis.items()},
        "n_dev": n_dev, "batch": batch, "seq": seq, "steps": steps,
        "ds_overrides": ds_overrides or {},
        "tunables": dict(tunables or {}),
    }


def _tp_series(cfg, batch, seq, on_tpu, steps=3):
    """Optional extra series (after the headline JSON): tensor
    parallelism on the 3-axis mesh. Runs the SAME train-step
    measurement at tp=1 (pure DP baseline) and tp=2 (SpecLayout
    column/row-parallel weights, ZeRO-2 over data) and reports
    tokens/s plus the compiled step's collective wire bytes for each —
    on the CPU smoke mesh the numbers prove the plumbing and make the
    tp collectives' wire cost visible; on real chips they answer
    whether trading data width for tp pays at this model size."""
    import jax

    if jax.device_count() < 2:
        return {"metric": METRIC + "_tp", "value": None,
                "unit": "tokens_per_sec",
                "not_measured": "needs >= 2 devices for a tp=2 mesh"}
    base = _train_step_series(
        cfg, batch, seq, on_tpu, steps=steps,
        ds_overrides={"mesh": {"data": -1, "fsdp": 1, "tp": 1},
                      "zero_optimization": {"stage": 2}})
    tp2 = _train_step_series(
        cfg, batch, seq, on_tpu, steps=steps,
        ds_overrides={"mesh": {"data": -1, "fsdp": 1, "tp": 2},
                      "zero_optimization": {"stage": 2}})
    return {
        "metric": METRIC + "_tp",
        "value": tp2["tokens_per_sec"],
        "unit": "tokens_per_sec",
        "vs_baseline": (round(tp2["tokens_per_sec"]
                              / base["tokens_per_sec"], 4)
                        if base["tokens_per_sec"] else None),
        "tp1_tokens_per_sec": base["tokens_per_sec"],
        "tp2_tokens_per_sec": tp2["tokens_per_sec"],
        "tp1_collective_wire_bytes": base["collective_wire_bytes"],
        "tp2_collective_wire_bytes": tp2["collective_wire_bytes"],
    }


def _overlap_series(cfg, batch, seq, on_tpu, steps=3):
    """Optional extra series (after the headline JSON): the
    overlap-everything knobs. (1) ZeRO-3 param gather flat vs
    hierarchical (`zero_optimization.hierarchical_gather`, ZeRO++ hpZ)
    on a data x fsdp mesh — the SAME train-step measurement twice.
    Note the wire-bytes column is summed OPERAND bytes: the hpZ gather
    ships a larger operand over a smaller group, so that column can
    rise while per-member received bytes drop — the received-bytes
    comparison is pinned in `tests/unit/test_zero_hierarchical.py`
    and measured in `tools/perf_comm_wire.py`.
    (2) The pipeline-schedule bubble fractions (1F1B / interleaved v=2
    / ZB-H1) from the validated instruction streams — pure schedule
    algebra, no devices, so they report even on a 1-chip host."""
    import jax

    from deepspeed_tpu.runtime.pipe.schedule import (InterleavedSchedule,
                                                     TrainSchedule,
                                                     ZeroBubbleSchedule,
                                                     validate_schedule)

    bubbles = {
        name: round(validate_schedule(sched, 8, 4,
                                      **kw)["bubble_fraction"], 4)
        for name, sched, kw in (
            ("1f1b", TrainSchedule, {}),
            ("interleaved_v2", InterleavedSchedule, {"virtual_stages": 2}),
            ("zero_bubble", ZeroBubbleSchedule, {}),
        )}
    out = {"metric": METRIC + "_overlap", "unit": "tokens_per_sec",
           "bubble_fraction": bubbles}
    if jax.device_count() < 4:
        return {**out, "value": None,
                "not_measured": "needs >= 4 devices for a data x fsdp mesh"}
    zero3 = {"stage": 3, "stage3_param_persistence_threshold": 0}
    tracing = {"telemetry": {"tracing": {"enabled": True}}}
    flat = _train_step_series(
        cfg, batch, seq, on_tpu, steps=steps,
        ds_overrides={"mesh": {"data": -1, "fsdp": 2},
                      "zero_optimization": zero3, **tracing})
    hier = _train_step_series(
        cfg, batch, seq, on_tpu, steps=steps,
        ds_overrides={"mesh": {"data": -1, "fsdp": 2},
                      "zero_optimization": {**zero3,
                                            "hierarchical_gather": True},
                      **tracing})
    return {
        **out,
        "value": hier["tokens_per_sec"],
        "vs_baseline": (round(hier["tokens_per_sec"]
                              / flat["tokens_per_sec"], 4)
                        if flat["tokens_per_sec"] else None),
        "flat_tokens_per_sec": flat["tokens_per_sec"],
        "hierarchical_tokens_per_sec": hier["tokens_per_sec"],
        "flat_collective_wire_bytes": flat["collective_wire_bytes"],
        "hierarchical_collective_wire_bytes":
            hier["collective_wire_bytes"],
        "flat_collective_bytes_per_axis":
            flat["collective_bytes_per_axis"],
        "hierarchical_collective_bytes_per_axis":
            hier["collective_bytes_per_axis"],
    }


def _tracing_series(cfg, batch, seq, on_tpu, steps=3):
    """Optional extra series (after the headline JSON): the span-tracing
    overhead bound. Two identical telemetry-enabled measured windows —
    spans off vs spans on (`telemetry.tracing.enabled`) — so the delta
    is EXACTLY the span layer's host-side bookkeeping (the compiled
    programs are byte-identical by the zero-overhead pin; this series
    bounds the part the pin can't see)."""

    # both legs telemetry-enabled: the delta isolates the SPAN layer,
    # not the (always-on-in-this-series) collector stack around it
    base = _train_step_series(
        cfg, batch, seq, on_tpu, steps=steps,
        ds_overrides={"telemetry": {
            "enabled": True, "jsonl": False, "memory": False}})
    traced = _train_step_series(
        cfg, batch, seq, on_tpu, steps=steps,
        ds_overrides={"telemetry": {
            "enabled": True, "jsonl": False, "memory": False,
            "tracing": {"enabled": True}}})
    off = base["steps_per_sec"]
    on = traced["steps_per_sec"]
    return {
        "metric": METRIC + "_tracing",
        "steps_per_sec_tracing_off": off,
        "steps_per_sec_tracing_on": on,
        "overhead_pct": round(100.0 * (off - on) / off, 2)
        if off else None,
        "n_dev": base["n_dev"], "batch": batch, "seq": seq,
        "steps": steps,
    }


def _metrics_series(cfg, batch, seq, on_tpu, steps=3):
    """Optional extra series (after the headline JSON): the live
    metrics plane's overhead bound. Three numbers on one line —
    (1) steps/s with the registry + flight recorder OFF vs ON (both
    legs telemetry-enabled, so the delta isolates the metrics plane;
    the compiled programs are byte-identical by the zero-overhead pin,
    this bounds the host-side part the pin can't see); (2) scrape
    latency against a live endpoint serving a populated registry;
    (3) the flight-recorder ring's per-event overhead."""

    base = _train_step_series(
        cfg, batch, seq, on_tpu, steps=steps,
        ds_overrides={"telemetry": {
            "enabled": True, "jsonl": False, "memory": False}})
    metered = _train_step_series(
        cfg, batch, seq, on_tpu, steps=steps,
        ds_overrides={"telemetry": {
            "enabled": True, "jsonl": False, "memory": False,
            "metrics_port": 0,
            "flight_recorder": {"enabled": True}}})
    off = base["steps_per_sec"]
    on = metered["steps_per_sec"]

    # scrape latency against a live endpoint with representative
    # content (step gauges + latency histograms + label fan-out)
    import tempfile
    import urllib.request

    from deepspeed_tpu.telemetry import Telemetry

    with tempfile.TemporaryDirectory(prefix="bench_metrics_") as d:
        t = Telemetry({"enabled": True, "dir": d, "jsonl": False,
                       "memory": False, "metrics_port": 0})
        m = t.metrics
        for i in range(200):
            m.histogram("ds_serving_ttft_ms").observe(1.0 + i)
            m.histogram("ds_serving_queue_ms").observe(0.5 + i)
            m.counter("ds_serving_requests_total",
                      ("outcome",)).labels(outcome="finished").inc()
        for i in range(8):
            m.gauge("ds_replica_health", ("replica", "state"),
                    max_label_sets=256).labels(
                        replica=str(i), state="healthy").set(1)
        url = t._metrics_server.url
        lat = []
        body = b""
        for _ in range(5):
            t0 = time.perf_counter()
            body = urllib.request.urlopen(url, timeout=5).read()
            lat.append(1e3 * (time.perf_counter() - t0))
        scrape_ms = round(sorted(lat)[len(lat) // 2], 3)
        scrape_bytes = len(body)

        # flight-recorder ring: ns per recorded event (pure deque
        # append + trigger check; the dump path is off-budget)
        t2 = Telemetry({"enabled": True, "dir": d, "jsonl": False,
                        "memory": False,
                        "flight_recorder": {"enabled": True,
                                            "max_dumps": 1}})
        n = 20_000
        t0 = time.perf_counter()
        for i in range(n):
            t2.emit("step", "bench", step=i)
        ring_ns = round(1e9 * (time.perf_counter() - t0) / n)
        t2.close()
        t.close()
    return {
        "metric": METRIC + "_metrics",
        "steps_per_sec_metrics_off": off,
        "steps_per_sec_metrics_on": on,
        "overhead_pct": round(100.0 * (off - on) / off, 2)
        if off else None,
        "scrape_ms_p50": scrape_ms,
        "scrape_bytes": scrape_bytes,
        "recorder_ns_per_event": ring_ns,
        "n_dev": base["n_dev"], "batch": batch, "seq": seq,
        "steps": steps,
    }


def _startup_series(cfg, batch, seq, on_tpu, steps=3):
    """Optional extra series (after the headline JSON): what the AOT
    program cache buys on restart. One engine (telemetry + aot enabled)
    trains briefly and saves a checkpoint carrying its compiled
    programs; a FRESH same-topology engine then resumes — its
    time-to-first-step and in-window backend-compile count are the
    warm numbers (zero compiles). Plus tuned-vs-default steady-state
    step rate when a tuned.json artifact of this topology is present."""
    import shutil
    import sys
    import tempfile

    import jax
    import numpy as np_

    import deepspeed_tpu
    from deepspeed_tpu.telemetry import compile_watch

    from deepspeed_tpu.models.gpt2 import GPT2ForTraining
    from deepspeed_tpu.parallel.topology import reset_topology

    n_dev = jax.device_count()
    rows = batch * n_dev
    rng = np_.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (rows, seq)).astype(np_.int32)

    def build(tuning=False):
        reset_topology()
        config = {
            "train_micro_batch_size_per_gpu": batch,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "AdamW", "params": {"lr": 6e-4}},
            "bf16": {"enabled": on_tpu},
            "zero_optimization": {"stage": 0},
            "steps_per_print": 10_000,
            "telemetry": {"enabled": True, "jsonl": False,
                          "memory": False},
            "aot": {"enabled": True},
        }
        if tuning:
            config["tuning"] = {"enabled": True}
        engine, *_ = deepspeed_tpu.initialize(
            model=GPT2ForTraining(cfg), config=config)
        return engine

    def first_step_secs(engine):
        t0 = time.perf_counter()
        loss = engine({"input_ids": ids})
        engine.backward(loss)
        engine.step()
        float(loss)
        jax.block_until_ready(engine.state.params)
        return time.perf_counter() - t0

    from deepspeed_tpu.utils.compat import compilation_cache_off

    save_dir = tempfile.mkdtemp(prefix="bench_aot_")
    # the persistent compile cache stays out of this series: its cold first
    # step is a compile, and what the resumed engine is handed comes from
    # the checkpoint's AOT bundle and from nowhere else
    try:
        with compilation_cache_off():
            saver = build()
            cold_tffs = first_step_secs(saver)
            saver.save_checkpoint(save_dir, tag="startup")
            aot_events = [e["name"] for e in saver.telemetry.tail(50)
                          if e["kind"] == "aot"]
            saver.destroy()

            resumed = build()
            resumed.load_checkpoint(save_dir, tag="startup")
            mark = compile_watch.snapshot()["backend_compiles"]
            warm_tffs = first_step_secs(resumed)
            warm_compiles = (compile_watch.snapshot()["backend_compiles"]
                             - mark)
            resumed.destroy()
    finally:
        shutil.rmtree(save_dir, ignore_errors=True)

    # tuned-vs-default steady-state step rate, only when the live
    # autotuner has written an artifact for THIS topology. Whether it has
    # is decided here, before any run, by the gate the engine itself
    # applies; what the two runs raise is a fault and propagates.
    tuned_rate = default_rate = None
    if os.path.exists(os.path.join("autotuning_results", "tuned.json")):
        from deepspeed_tpu.autotuning.artifact import (TunedArtifactError,
                                                       load_for_config)

        try:
            load_for_config({})
        except TunedArtifactError as e:
            print(f"# startup tuned-vs-default skipped: {e}",
                  file=sys.stderr, flush=True)
        else:
            def rate(tuning):
                engine = build(tuning=tuning)
                first_step_secs(engine)  # compile outside the window
                t0 = time.perf_counter()
                for _ in range(steps):
                    loss = engine({"input_ids": ids})
                    engine.backward(loss)
                    engine.step()
                float(loss)
                jax.block_until_ready(engine.state.params)
                dt = time.perf_counter() - t0
                engine.destroy()
                return steps / dt

            default_rate = rate(False)
            tuned_rate = rate(True)

    return {
        "metric": METRIC + "_startup",
        "value": round(warm_tffs, 3),
        "unit": "warm_restart_first_step_seconds",
        "vs_baseline": round(warm_tffs / cold_tffs, 4)
        if cold_tffs else None,
        "cold_first_step_secs": round(cold_tffs, 3),
        "warm_first_step_secs": round(warm_tffs, 3),
        "warm_backend_compiles": int(warm_compiles),
        "aot_save_events": aot_events,
        "tuned_steps_per_sec": round(tuned_rate, 3)
        if tuned_rate else None,
        "default_steps_per_sec": round(default_rate, 3)
        if default_rate else None,
        "n_dev": n_dev,
    }


# ---------------------------------------------------------------------------
# importable series registry: run_series(name, config) -> payload dict.
# The live autotuner (autotuning/measure.py) drives these in-process
# instead of shelling out; the CLI keeps emitting the same JSON lines in
# the same order (headline first) as before.
def _series_context(config=None):
    """Model/batch/seq defaults shared by every importable series. The
    in-process callers never subprocess-probe the backend — whatever
    platform jax already initialized is the measurement platform."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.gpt2 import GPT2Config

    config = dict(config or {})
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        cfg = GPT2Config(vocab_size=50257, n_positions=1024, n_embd=768,
                         n_layer=12, n_head=12, dtype=jnp.bfloat16,
                         scan_layers=True)
        batch, seq, steps = 16, 1024, 5
    else:
        cfg = GPT2Config.tiny(dtype=jnp.float32)
        batch, seq, steps = 4, 32, 2
    return {
        "cfg": config.get("model_config") or cfg,
        "batch": int(config.get("batch", batch)),
        "seq": int(config.get("seq", seq)),
        "steps": int(config.get("steps", steps)),
        "on_tpu": on_tpu,
        "ds_overrides": config.get("ds_config") or {},
        "tunables": config.get("tunables") or {},
    }


def run_series(name, config=None):
    """Run ONE bench series in-process and return its payload dict
    (never emits). ``config`` keys: ``model_config`` (a GPT2Config),
    ``batch``/``seq``/``steps``, ``ds_config`` (overrides merged into
    the engine config), ``tunables`` (kernel-registry values installed
    for the measurement window only)."""
    ctx = _series_context(config)
    cfg, batch, seq = ctx["cfg"], ctx["batch"], ctx["seq"]
    on_tpu = ctx["on_tpu"]
    if name == "train_step":
        return _train_step_series(cfg, batch, seq, on_tpu,
                                  steps=ctx["steps"],
                                  ds_overrides=ctx["ds_overrides"],
                                  tunables=ctx["tunables"])
    if name == "startup":
        return _startup_series(cfg, batch, seq, on_tpu, steps=ctx["steps"])
    if name == "telemetry":
        import numpy as np_

        import deepspeed_tpu
        from deepspeed_tpu.models.gpt2 import GPT2ForTraining
        from deepspeed_tpu.parallel.topology import reset_topology
        from deepspeed_tpu.telemetry import compile_watch

        # a standalone invocation needs its own measured window (the
        # CLI couples this series to the headline's timed steps): warm
        # one step, snapshot, then run the window — a retrace inside it
        # is actually reportable
        compile_watch.install()
        reset_topology()
        engine, *_ = deepspeed_tpu.initialize(
            model=GPT2ForTraining(cfg),
            config={"train_micro_batch_size_per_gpu": batch,
                    "gradient_accumulation_steps": 1,
                    "optimizer": {"type": "AdamW", "params": {"lr": 6e-4}},
                    "bf16": {"enabled": on_tpu},
                    "zero_optimization": {"stage": 0},
                    "steps_per_print": 10_000})
        import jax as _jax

        rows = batch * _jax.device_count()
        ids = np_.random.default_rng(0).integers(
            0, cfg.vocab_size, (rows, seq)).astype(np_.int32)
        try:
            loss = engine({"input_ids": ids})
            engine.backward(loss)
            engine.step()
            _jax.block_until_ready(engine.state.params)
            warm_mark = compile_watch.snapshot()
            for _ in range(ctx["steps"]):
                loss = engine({"input_ids": ids})
                engine.backward(loss)
                engine.step()
            float(loss)
            _jax.block_until_ready(engine.state.params)
        finally:
            engine.destroy()
        return _telemetry_series(warm_mark, ctx["steps"])
    if name == "resilience":
        return _resilience_series(cfg, batch, seq, on_tpu)
    if name == "comm_compression":
        return _comm_compression_series(cfg, batch, seq, on_tpu)
    if name == "elastic_resume":
        return _elastic_resume_series(cfg, batch, seq, on_tpu)
    if name == "tracing":
        return _tracing_series(cfg, batch, seq, on_tpu, steps=ctx["steps"])
    if name == "metrics":
        return _metrics_series(cfg, batch, seq, on_tpu, steps=ctx["steps"])
    if name == "tp":
        return _tp_series(cfg, batch, seq, on_tpu, steps=ctx["steps"])
    if name == "overlap":
        return _overlap_series(cfg, batch, seq, on_tpu, steps=ctx["steps"])
    raise KeyError(f"unknown bench series {name!r}; available: "
                   f"{sorted(SERIES)}")


SERIES = ("train_step", "startup", "telemetry", "resilience",
          "comm_compression", "elastic_resume", "tracing", "metrics", "tp",
          "overlap")


if __name__ == "__main__":
    main()
