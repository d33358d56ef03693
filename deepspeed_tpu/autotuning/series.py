"""The live tuner's measurement series: what one trial of an axis runs.

``run(name, config) -> dict`` builds the engine a candidate needs
in-process, runs a warmed window and returns the measurement dict whose
keys the axes in ``live.py`` name as objectives. Four series, one per
kind of axis: ``train_step`` (ZeRO/comm/flash axes), ``decode_attention``
(the dense decode kernel's tile), ``serving_chunk`` (prefill chunk and
bucket axes), ``spec_decode`` (speculation depth).

On a TPU the shapes are GPT-2 125M's; on any other backend a tiny model
runs (Pallas kernels interpreted), which ranks candidates through the
same plumbing and is not a device time. Either size is far below a
``perfbench`` cell's (ROADMAP D13). jax is imported inside the
functions: importing this module touches no device.
"""

import contextlib
import time

import numpy as np


def _model(on_tpu):
    import jax.numpy as jnp

    from deepspeed_tpu.models.gpt2 import GPT2Config

    if on_tpu:
        return GPT2Config(vocab_size=50257, n_positions=1024, n_embd=768,
                          n_layer=12, n_head=12, dtype=jnp.bfloat16,
                          scan_layers=True)
    return GPT2Config.tiny(dtype=jnp.float32)


def _decode_context(config):
    """Model + serving defaults shared by the three decode-side series."""
    import jax

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        batch, new_tokens = 8, 128
        scfg = {"block_size": 32, "decode_slots": batch,
                "max_queue_depth": 4 * batch}
        lens, srv_new = [64, 128, 192], new_tokens
    else:
        batch, new_tokens = 2, 8
        scfg = {"block_size": 8, "decode_slots": 2, "max_queue_depth": 16}
        lens, srv_new = [4, 6, 8], 4
    return {
        "cfg": config.get("model_config") or _model(on_tpu),
        "on_tpu": on_tpu,
        "batch": int(config.get("batch", batch)),
        "new_tokens": int(config.get("new_tokens", new_tokens)),
        "scfg": {**scfg, **(config.get("serving") or {})},
        "lens": lens,
        "srv_new": int(config.get("srv_new", srv_new)),
        "srv_rng": np.random.default_rng(1),
    }


def _build_serving(ctx, extra=None, telemetry=False):
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel
    from deepspeed_tpu.parallel.topology import reset_topology
    from deepspeed_tpu.serving import ServingEngine

    cfg = ctx["cfg"]
    reset_topology()
    kwargs = {}
    if telemetry:
        # serving_chunk reads compile counts off the telemetry stream;
        # spec_decode keeps the watch layer out of its measured window
        kwargs["telemetry"] = {"enabled": True, "jsonl": False,
                               "memory": False}
    return ServingEngine(deepspeed_tpu.init_inference(
        GPT2LMHeadModel(cfg), dtype=cfg.dtype,
        tensor_parallel={"tp_size": 1}, max_out_tokens=cfg.n_positions,
        serving={**ctx["scfg"], **(extra or {})}, **kwargs))


def _drain(eng, prompts, max_new_tokens):
    """Submit every prompt, step until nothing is pending; seconds."""
    t0 = time.perf_counter()
    for p in prompts:
        eng.submit(p, max_new_tokens=max_new_tokens)
    while eng.pending:
        eng.step()
    eng.drain()
    return time.perf_counter() - t0


def _tokens_out(eng):
    return sum(r["new_tokens"] for r in eng.records if r["state"] != "shed")


# ---------------------------------------------------------------------------
def _train_step(config):
    """A telemetry-enabled engine with the candidate's ds-config
    overrides (and, for tile axes, temporarily-installed kernel
    tunables). Reports the telemetry-stream objectives next to the step
    rate: compile seconds, retraces INSIDE the timed window, and the
    compiled step's collective wire bytes (the step_cost events) — a
    candidate that is fast but retraces every step must lose."""
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.autotuning import runtime_tunables
    from deepspeed_tpu.models.gpt2 import GPT2ForTraining
    from deepspeed_tpu.parallel.topology import reset_topology

    # whatever platform jax already initialized is the measurement platform
    on_tpu = jax.default_backend() == "tpu"
    batch, seq, steps = (16, 1024, 5) if on_tpu else (4, 32, 2)
    cfg = config.get("model_config") or _model(on_tpu)
    batch = int(config.get("batch", batch))
    seq = int(config.get("seq", seq))
    steps = int(config.get("steps", steps))
    ds_overrides = config.get("ds_config") or {}
    tunables = config.get("tunables") or {}
    n_dev = jax.device_count()
    rows = batch * n_dev
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (rows, seq)).astype(np.int32)
    ds_config = {
        "train_micro_batch_size_per_gpu": batch,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 6e-4}},
        "bf16": {"enabled": on_tpu},
        "zero_optimization": {"stage": 0},
        "steps_per_print": 10_000,
        "telemetry": {"enabled": True, "jsonl": False, "memory": False},
    }
    for k, v in ds_overrides.items():
        if isinstance(v, dict):
            ds_config[k] = {**ds_config.get(k, {}), **v}
        else:
            ds_config[k] = v
    token = runtime_tunables.install(dict(tunables)) if tunables else None
    engine = None
    try:
        reset_topology()
        engine, *_ = deepspeed_tpu.initialize(model=GPT2ForTraining(cfg),
                                              config=ds_config)
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
    compiles = sum(v["compiles"] for v in summary["per_function"].values())
    warm_compiles = sum(v["compiles"] for v in warm["per_function"].values())
    return {
        "steps_per_sec": round(steps / dt, 4),
        "tokens_per_sec": round(steps * rows * seq / dt / n_dev, 1),
        "compile_secs": round(sum(v["compile_secs"] for v in
                                  summary["per_function"].values()), 3),
        "retraces_in_timed_window": int(compiles - warm_compiles),
        "collective_wire_bytes": int(wire),
        "collective_bytes_per_axis": {k: int(v) for k, v in per_axis.items()},
        "n_dev": n_dev, "batch": batch, "seq": seq, "steps": steps,
        "ds_overrides": ds_overrides,
        "tunables": dict(tunables),
    }


def _decode_attention(config):
    """Microbench of the dense decode-attention kernel at one ``block_k``
    candidate. On TPU the real Pallas kernel runs; on CPU the interpret-
    mode emulation runs (relative ranking only — same plumbing, honest
    ``backend`` field). The tuned value feeds the kernel-default
    registry (``ops.decode_attention.block_k``)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.decode_attention import decode_attention
    from deepspeed_tpu.utils.compat import tpu_interpret_mode

    on_tpu = jax.default_backend() == "tpu"
    block_k = config.get("block_k")
    reps = 20 if on_tpu else 3
    b, heads, d = (8, 12, 64) if on_tpu else (2, 2, 8)
    s_len = 1024 if on_tpu else 512
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(b, 1, heads, d)), jnp.float32)
    k_cache = jnp.asarray(rng.normal(size=(b, s_len, heads, d)), jnp.float32)
    v_cache = jnp.asarray(rng.normal(size=(b, s_len, heads, d)), jnp.float32)
    idx = jnp.asarray(s_len // 2, jnp.int32)

    with contextlib.nullcontext() if on_tpu else tpu_interpret_mode():
        fn = jax.jit(lambda q, k, v, i: decode_attention(
            q, k, v, i, block_k=block_k))
        out = fn(q, k_cache, v_cache, idx)
        jax.block_until_ready(out)  # compile outside the window
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(q, k_cache, v_cache, idx)
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
    return {
        "per_call_ms": round(1e3 * dt / reps, 4),
        "block_k": block_k,
        "cache_len": s_len, "batch": b, "heads": heads, "head_dim": d,
        "backend": "tpu" if on_tpu else "cpu_interpret",
        "reps": reps,
    }


def _serving_chunk(config):
    """Serving-shape measurement for the chunk-size / bucket-set axes:
    one long prompt ahead of short requests, reporting the short
    requests' TTFT p95 (what a chunk budget buys), drain tokens/s, and
    the telemetry-side compile count of the window's programs."""
    ctx = _decode_context(config)
    cfg, srv_rng, lens = ctx["cfg"], ctx["srv_rng"], ctx["lens"]
    serving_overrides = config.get("serving") or {}
    long_len = (8 if ctx["on_tpu"] else 4) * ctx["scfg"]["block_size"]
    n_short = ctx["batch"] if ctx["on_tpu"] else 3

    eng = _build_serving(ctx, serving_overrides, telemetry=True)

    def window():
        prompts = [srv_rng.integers(0, cfg.vocab_size,
                                    long_len).astype(np.int32)]
        prompts += [srv_rng.integers(0, cfg.vocab_size,
                                     lens[i % len(lens)]).astype(np.int32)
                    for i in range(n_short)]
        return _drain(eng, prompts, ctx["srv_new"])

    window()  # warm the programs
    eng.reset_stats()
    elapsed = window()
    ttfts = [r["ttft_ms"] for r in eng.records
             if r["state"] != "shed" and r["prompt_len"] < long_len
             and r["ttft_ms"] is not None]
    summary = eng.telemetry.summary()
    payload = {
        "short_ttft_ms_p95": round(float(np.percentile(ttfts, 95)), 2)
        if ttfts else None,
        "tokens_per_sec": round(_tokens_out(eng) / elapsed, 1)
        if elapsed > 0 else None,
        "compiled_programs": sum(v["compiles"] for v in
                                 summary["per_function"].values()),
        "long_prompt_len": long_len, "n_short": n_short,
        "serving_overrides": dict(serving_overrides),
    }
    eng.destroy()
    return payload


def _spec_decode(config):
    """Draft-and-verify against the non-speculative baseline on a
    prompt-lookup-friendly workload (repetitive prompts, whose greedy
    continuations the n-gram proposer predicts well): decode tokens/s
    with and without the verify program, accepted tokens per verify
    dispatch, acceptance rate, and TTFT p50/p95 both ways. The measured
    window drains the SAME prompt set through both engines; greedy
    bit-exactness (pinned in test_serving.py) means the token streams
    are identical, so tokens/s is the whole story."""
    ctx = _decode_context(config)
    cfg, scfg, srv_rng = ctx["cfg"], ctx["scfg"], ctx["srv_rng"]
    spec_block = dict(scfg.get("speculative")
                      or {"num_speculative_tokens": 4})
    # enabled:false measures the MACHINERY-OFF candidate (the tuner's
    # "off" grid point): only the baseline leg runs and its throughput
    # IS the objective value — never a fake ~1.0 "speedup" from
    # comparing two identical engines
    spec_off = spec_block.get("enabled", True) is False
    if ctx["on_tpu"]:
        motif, prompt_len = 16, 4 * scfg["block_size"]
        new_tok, n_requests = ctx["new_tokens"], 2 * ctx["batch"]
    else:
        motif, prompt_len, new_tok, n_requests = 4, 16, 16, 6

    # ONE prompt set: both engines decode the same work
    batch = [np.tile(srv_rng.integers(0, cfg.vocab_size, motif),
                     prompt_len // motif + 1)[:prompt_len].astype(np.int32)
             for _ in range(n_requests)]

    def window(eng):
        elapsed = _drain(eng, batch, new_tok)
        st = eng.stats()
        return {
            "tokens_per_sec": round(_tokens_out(eng) / elapsed, 1)
            if elapsed > 0 else None,
            "ttft_ms_p50": st["ttft_ms_p50"],
            "ttft_ms_p95": st["ttft_ms_p95"],
            "speculative": st["speculative"],
        }

    measured = {}
    legs = [("baseline", {"speculative": None})]
    if not spec_off:
        legs.append(("spec", {"speculative": spec_block}))
    for label, extra in legs:
        eng = _build_serving(ctx, extra)
        window(eng)   # warm the programs (prefill buckets + step)
        eng.reset_stats()
        measured[label] = window(eng)
        eng.destroy()
        del eng
    base = measured["baseline"]
    spec = measured.get("spec", base)
    sp = spec["speculative"] or {}
    speedup = (round(spec["tokens_per_sec"] / base["tokens_per_sec"], 3)
               if not spec_off and base["tokens_per_sec"]
               and spec["tokens_per_sec"] else None)
    return {
        "speculation_enabled": not spec_off,
        "tokens_per_sec_baseline": base["tokens_per_sec"],
        # the objective key: spec-leg throughput, or (machinery off)
        # the baseline's — "off" competes in the same units
        "spec_tokens_per_sec": spec["tokens_per_sec"],
        "speedup": speedup,
        "accepted_tokens_per_step": sp.get("accepted_tokens_per_step"),
        "acceptance_rate": sp.get("acceptance_rate"),
        "draft_tokens": sp.get("draft_tokens"),
        "ttft_ms_p50_baseline": base["ttft_ms_p50"],
        "ttft_ms_p95_baseline": base["ttft_ms_p95"],
        "ttft_ms_p50_spec": spec["ttft_ms_p50"],
        "ttft_ms_p95_spec": spec["ttft_ms_p95"],
        "proposer": sp.get("proposer"),
        "num_speculative_tokens": int(
            spec_block.get("num_speculative_tokens", 4)),
        "requests": n_requests, "prompt_len": prompt_len,
        "new_tokens": new_tok,
    }


_SERIES = {"train_step": _train_step,
           "decode_attention": _decode_attention,
           "serving_chunk": _serving_chunk,
           "spec_decode": _spec_decode}


def run(name, config=None):
    """Run ONE series in-process and return its measurement dict.
    ``config`` keys: ``model_config`` (a GPT2Config), ``batch``;
    train_step: ``seq``, ``steps``, ``ds_config`` (overrides merged into
    the engine config), ``tunables`` (kernel-registry values installed
    for the measurement window only); decode side: ``serving``
    (overrides merged into the serving block), ``block_k``,
    ``new_tokens``, ``srv_new``."""
    if name not in _SERIES:
        raise KeyError(f"unknown series {name!r}; available: "
                       f"{sorted(_SERIES)}")
    return _SERIES[name](dict(config or {}))
