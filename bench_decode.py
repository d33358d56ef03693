"""Inference benchmark: GPT-2 125M decode throughput + TTFT on one chip.

The BASELINE.md inference metric ("DS-Inference p50 TTFT"; reference
benchmarks/inference/gpt-bench.py prints p50/p90 latency). Prints ONE JSON
line::

    {"metric": "gpt2_125m_decode", "ttft_ms_p50": ..., "decode_tokens_per_sec":
     ..., "per_token_ms": ...}

TTFT = prefill latency on the prompt (first compiled forward after warmup);
decode tokens/s = steady-state autoregressive rate through the jitted
scanned decode loop with the Pallas decode-attention kernel on the KV
cache. Without a TPU the script fails; under an explicit JAX_PLATFORMS=cpu
it runs a tiny proxy under the ``gpt2_decode_cpu_smoke`` metric name.

Every series is an importable ``run_series(name, config) -> dict`` (the
live autotuner drives ``decode_attention`` and ``serving_chunk``
in-process instead of shelling out); the CLI emits the same JSON lines
in the same order as always, headline first.
"""

import time

import numpy as np

from deepspeed_tpu.utils.device import (cpu_requested, emit_result,
                                        require_device)

# the smoke name under an explicit JAX_PLATFORMS=cpu: a CPU run is never
# filed under the device metric
METRIC = ("gpt2_decode_cpu_smoke" if cpu_requested()
          else "gpt2_125m_decode")


def _decode_context(config=None, on_tpu=None):
    """Model + serving defaults shared by every series (one source: the
    CLI main and the importable run_series must measure the same
    shapes)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.gpt2 import GPT2Config

    config = dict(config or {})
    if on_tpu is None:
        on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        cfg = GPT2Config(vocab_size=50257, n_positions=1024, n_embd=768,
                         n_layer=12, n_head=12, dtype=jnp.bfloat16,
                         scan_layers=True)
        batch, prompt, new_tokens, reps = 8, 128, 128, 5
        scfg = {"block_size": 32, "decode_slots": batch,
                "max_queue_depth": 4 * batch}
        n_requests, arrive_every = 4 * batch, 2
        lens = [prompt // 2, prompt, prompt + prompt // 2]
        srv_new = new_tokens
    else:
        cfg = GPT2Config.tiny(dtype=jnp.float32)
        batch, prompt, new_tokens, reps = 2, 8, 8, 2
        scfg = {"block_size": 8, "decode_slots": 2, "max_queue_depth": 16}
        n_requests, arrive_every = 6, 1
        lens = [4, 6, 8]
        srv_new = 4
    ctx = {
        "cfg": config.get("model_config") or cfg,
        "on_tpu": on_tpu,
        "batch": int(config.get("batch", batch)),
        "prompt": int(config.get("prompt", prompt)),
        "new_tokens": int(config.get("new_tokens", new_tokens)),
        "reps": int(config.get("reps", reps)),
        "scfg": {**scfg, **(config.get("serving") or {})},
        "n_requests": int(config.get("n_requests", n_requests)),
        "arrive_every": arrive_every,
        "lens": lens,
        "srv_new": int(config.get("srv_new", srv_new)),
        "srv_rng": np.random.default_rng(1),
    }
    return ctx


# ---------------------------------------------------------------------------
# headline: TTFT + steady-state decode rate (bf16 and int8 weight-only)
def _headline_series(ctx):
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel

    cfg = ctx["cfg"]
    batch, prompt = ctx["batch"], ctx["prompt"]
    new_tokens, reps = ctx["new_tokens"], ctx["reps"]

    engine = deepspeed_tpu.init_inference(
        GPT2LMHeadModel(cfg),
        dtype=cfg.dtype, tensor_parallel={"tp_size": 1},
        max_out_tokens=cfg.n_positions)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (batch, prompt)).astype(np.int32)

    # --- TTFT: prefill-only latency (the first forward of a request).
    # Serving needs only the LAST position's logits to pick the first
    # token, so the serving-true prefill is forward_last (XLA cuts the
    # vocab projection to one position); the full-logits forward is kept
    # as a secondary series for scoring-style callers ---
    def p50(fn):
        jax.block_until_ready(fn())  # compile
        ms = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            ms.append(1e3 * (time.perf_counter() - t0))
        return float(np.percentile(ms, 50))

    # ttft_ms_p50 keeps its historical meaning (full-logits forward, the
    # series PERF.md records); the serving-true prefill gets its own key
    ttft_serving_p50 = p50(lambda: engine.forward_last(ids))
    ttft_p50 = p50(lambda: engine.forward(ids))

    # --- steady-state decode rate: marginal cost between two generation
    # lengths — (T(2N) - T(N)) / N cancels prefill and per-call dispatch
    # (same methodology as tools/perf_sparse.py)
    def per_token(eng):
        def gen_time(n):
            eng.generate(ids, max_new_tokens=n, do_sample=False)  # warm
            best = float("inf")
            for _ in range(reps):
                t0 = time.perf_counter()
                eng.generate(ids, max_new_tokens=n, do_sample=False)
                best = min(best, time.perf_counter() - t0)
            return best

        t1 = gen_time(new_tokens)
        t2 = gen_time(2 * new_tokens)
        # a non-positive marginal window means timer noise swamped the
        # decode cost (tiny CPU-smoke models); report null, not a
        # nonsense rate
        return (t2 - t1) / new_tokens if t2 > t1 else None

    def rate(per_token_s):
        if per_token_s is None:
            return {"tokens_per_sec": None, "per_token_ms": None}
        return {"tokens_per_sec": round(batch / per_token_s, 1),
                "per_token_ms": round(1e3 * per_token_s, 3)}

    per_token_s = per_token(engine)

    # int8 weight-only decode: small-batch decode is weight-bandwidth
    # bound, so halved at-rest bytes should approach 2x tokens/s — the
    # same reason the reference pairs its inference kernels with
    # weight quantization
    del engine
    engine8 = deepspeed_tpu.init_inference(
        GPT2LMHeadModel(cfg), dtype="int8", tensor_parallel={"tp_size": 1},
        max_out_tokens=cfg.n_positions)
    per_token_s8 = per_token(engine8)
    del engine8

    bf16, int8 = rate(per_token_s), rate(per_token_s8)
    return {
        "metric": METRIC,
        "ttft_ms_p50": round(ttft_p50, 2),
        "ttft_serving_ms_p50": round(ttft_serving_p50, 2),
        "decode_tokens_per_sec": bf16["tokens_per_sec"],
        "per_token_ms": bf16["per_token_ms"],
        "int8_decode_tokens_per_sec": int8["tokens_per_sec"],
        "int8_per_token_ms": int8["per_token_ms"],
        "batch": batch, "prompt": prompt, "new_tokens": new_tokens,
    }


# ---------------------------------------------------------------------------
# serving: continuous batching under mixed arrivals
def _build_serving(ctx, extra=None, telemetry=False):
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel
    from deepspeed_tpu.parallel.topology import reset_topology
    from deepspeed_tpu.serving import ServingEngine

    cfg = ctx["cfg"]
    reset_topology()
    kwargs = {}
    if telemetry:
        # tuner series read compile counts off the telemetry stream;
        # the headline/serving series keep the exact build they always
        # had (no watch layer in the measured window). A dict is used
        # verbatim (the tracing series turns the span layer on)
        kwargs["telemetry"] = telemetry if isinstance(telemetry, dict) \
            else {"enabled": True, "jsonl": False, "memory": False}
    return ServingEngine(deepspeed_tpu.init_inference(
        GPT2LMHeadModel(cfg), dtype=cfg.dtype,
        tensor_parallel={"tp_size": 1}, max_out_tokens=cfg.n_positions,
        serving={**ctx["scfg"], **(extra or {})}, **kwargs))


def _serving_series(ctx):
    """Mixed-arrival tokens/s + TTFT p50/p95 + shed rate under
    continuous batching (per-request records over the measured window
    only)."""
    cfg, scfg = ctx["cfg"], ctx["scfg"]
    n_requests, arrive_every = ctx["n_requests"], ctx["arrive_every"]
    lens, srv_new, srv_rng = ctx["lens"], ctx["srv_new"], ctx["srv_rng"]
    srv = _build_serving(ctx)

    def run_mixed():
        pending = [srv_rng.integers(0, cfg.vocab_size,
                                    lens[i % len(lens)]).astype(np.int32)
                   for i in range(n_requests)]
        t0 = time.perf_counter()
        while pending or srv.pending:
            for _ in range(arrive_every):
                if pending:
                    srv.submit(pending.pop(0), max_new_tokens=srv_new)
            srv.step()
        srv.drain()
        return time.perf_counter() - t0

    run_mixed()  # warm the bucket set + decode program
    srv.reset_stats()  # records AND scheduler counters: the emitted
    elapsed = run_mixed()  # series must cover only the measured window
    st = srv.stats()
    tokens_out = sum(r["new_tokens"] for r in srv.records
                     if r["state"] != "shed")
    payload = {
        "metric": f"{METRIC}_serving",
        "mixed_arrival_tokens_per_sec": round(tokens_out / elapsed, 1)
        if elapsed > 0 else None,
        "ttft_ms_p50": st["ttft_ms_p50"],
        "ttft_ms_p95": st["ttft_ms_p95"],
        "shed_rate": st["shed_rate"],
        "decode_slots": scfg["decode_slots"],
        "requests": n_requests, "new_tokens": srv_new,
    }
    srv.destroy()
    return payload


# ---------------------------------------------------------------------------
# serving fast path: prefix cache / chunked prefill / int8 KV
def _serving_fastpath_series(ctx):
    """Three scenarios over the same model, one payload: (a) shared
    system prompt under the radix prefix cache (hit rate + drain
    tokens/s); (b) short requests behind one long prompt, whole-prompt
    vs chunked prefill (short TTFT p95); (c) KV bytes per sequence f32
    vs int8 (max concurrent sequences at a fixed pool budget)."""
    cfg, scfg = ctx["cfg"], ctx["scfg"]
    on_tpu, batch = ctx["on_tpu"], ctx["batch"]
    lens, srv_new, srv_rng = ctx["lens"], ctx["srv_new"], ctx["srv_rng"]

    def drain_all(eng, prompts, new_tok):
        t0 = time.perf_counter()
        for p in prompts:
            eng.submit(p, max_new_tokens=new_tok)
        while eng.pending:
            eng.step()
        eng.drain()
        return time.perf_counter() - t0

    bs = scfg["block_size"]
    if on_tpu:
        sys_len, tail_len, n_shared = 4 * bs, bs, 2 * batch
        long_len, n_short = 8 * bs, batch
    else:
        sys_len, tail_len, n_shared = 2 * bs, 4, 6
        long_len, n_short = 4 * bs, 3

    # (a) shared system prompt under the prefix cache. Warm run compiles
    # the chunk/decode programs on a throwaway system prompt; the
    # measured window uses a FRESH system prompt so its first request is
    # the genuine cold miss and the rest are genuine hits.
    def shared_prompts():
        sys_ids = srv_rng.integers(0, cfg.vocab_size, sys_len)
        return [np.concatenate([
            sys_ids, srv_rng.integers(0, cfg.vocab_size, tail_len)]
        ).astype(np.int32) for _ in range(n_shared)]

    pfx = _build_serving(ctx, {"prefix_cache": True})
    drain_all(pfx, shared_prompts(), srv_new)  # warm programs
    pfx.reset_stats()
    pfx_elapsed = drain_all(pfx, shared_prompts(), srv_new)
    pst = pfx.stats()
    pfx_tokens = sum(r["new_tokens"] for r in pfx.records
                     if r["state"] != "shed")
    prefix_series = {
        "prefix_hit_rate": pst["prefix_cache"]["window_hit_rate"],
        "shared_tokens_per_sec": round(pfx_tokens / pfx_elapsed, 1)
        if pfx_elapsed > 0 else None,
        "shared_ttft_ms_p50": pst["ttft_ms_p50"],
        "cached_blocks": pst["prefix_cache"]["cached_blocks"],
    }
    pfx.destroy()
    del pfx

    # (b) short requests behind a long prompt, whole-prompt vs chunked
    # prefill. Same arrival order both times: the long prompt submits
    # first, the shorts immediately after — chunking bounds how long the
    # long prefill can hold the step loop before a short's first token.
    def short_ttft_p95(eng):
        prompts = [srv_rng.integers(0, cfg.vocab_size,
                                    long_len).astype(np.int32)]
        prompts += [srv_rng.integers(0, cfg.vocab_size,
                                     lens[i % len(lens)]).astype(np.int32)
                    for i in range(n_short)]
        drain_all(eng, prompts, srv_new)  # warm
        eng.reset_stats()
        drain_all(eng, prompts, srv_new)
        ttfts = [r["ttft_ms"] for r in eng.records
                 if r["state"] != "shed" and r["prompt_len"] < long_len
                 and r["ttft_ms"] is not None]
        return float(np.percentile(ttfts, 95)) if ttfts else None

    whole = _build_serving(ctx)
    whole_p95 = short_ttft_p95(whole)
    whole.destroy()
    del whole
    chunked = _build_serving(ctx, {"prefill_chunk_tokens": bs})
    chunked_p95 = short_ttft_p95(chunked)
    chunked.destroy()
    del chunked
    prefix_series.update({
        "short_ttft_ms_p95_whole_prefill": round(whole_p95, 2)
        if whole_p95 is not None else None,
        "short_ttft_ms_p95_chunked_prefill": round(chunked_p95, 2)
        if chunked_p95 is not None else None,
        "prefill_chunk_tokens": bs, "long_prompt_len": long_len,
    })

    # (c) KV bytes per concurrent sequence, read off the LIVE pool
    # arrays (int8 includes its scale side pools), and the max
    # concurrent sequences a fixed pool budget holds — the budget is
    # pinned to what the f32 pool actually costs here.
    def kv_bytes_per_seq(eng):
        import jax as _jax
        total = sum(leaf.nbytes
                    for leaf in _jax.tree_util.tree_leaves(eng.cache))
        return total // eng.num_blocks * eng.blocks_per_seq

    f32_eng = _build_serving(ctx)
    f32_bytes = kv_bytes_per_seq(f32_eng)
    f32_eng.destroy()
    del f32_eng
    int8_eng = _build_serving(ctx, {"kv_cache_dtype": "int8"})
    int8_bytes = kv_bytes_per_seq(int8_eng)
    int8_eng.destroy()
    del int8_eng
    pool_budget = f32_bytes * scfg["decode_slots"]
    prefix_series.update({
        "kv_bytes_per_seq_f32": int(f32_bytes),
        "kv_bytes_per_seq_int8": int(int8_bytes),
        "max_concurrent_seqs_f32": int(pool_budget // f32_bytes),
        "max_concurrent_seqs_int8": int(pool_budget // int8_bytes),
    })
    return {
        "metric": f"{METRIC}_serving_fastpath",
        **prefix_series,
        "requests_shared": n_shared, "system_prompt_len": sys_len,
        "new_tokens": srv_new,
    }


# ---------------------------------------------------------------------------
# router: two replicas behind the resilient front door
def _router_series(ctx):
    """The availability tier: the same mixed-arrival window run clean
    and with replica 1 crashed mid-window (deterministic chaos) — the
    gap between the two availability numbers is what failover with
    deterministic replay buys."""
    from deepspeed_tpu.runtime.resilience.chaos import ChaosReplica
    from deepspeed_tpu.serving.router import ReplicaRouter

    cfg = ctx["cfg"]
    n_requests, arrive_every = ctx["n_requests"], ctx["arrive_every"]
    lens, srv_new, srv_rng = ctx["lens"], ctx["srv_new"], ctx["srv_rng"]

    replicas = [_build_serving(ctx), _build_serving(ctx)]
    router = ReplicaRouter(replicas, config={"max_failovers": 2})

    def run_router():
        pending = [srv_rng.integers(0, cfg.vocab_size,
                                    lens[i % len(lens)]).astype(np.int32)
                   for i in range(n_requests)]
        t0 = time.perf_counter()
        while pending or router.pending:
            for _ in range(arrive_every):
                if pending:
                    router.submit(pending.pop(0), max_new_tokens=srv_new)
            router.step()
        return time.perf_counter() - t0

    def router_window(elapsed_s):
        rst = router.stats()
        toks = sum(len(r.tokens) for r in router.finished
                   if r.state == "finished")
        return {
            "tokens_per_sec": round(toks / elapsed_s, 1)
            if elapsed_s > 0 else None,
            "ttft_ms_p95": rst["ttft_ms_p95"],
            "availability": rst["availability"],
            "failovers": rst["failovers"],
        }

    run_router()  # warm both replicas' bucket sets + decode programs
    for rep in replicas:
        rep.reset_stats()
    router.reset_stats()
    clean = router_window(run_router())
    # crash replica 1 a few decode steps into the measured window: its
    # in-flight requests fail over to replica 0 and replay
    router.replicas[1] = ChaosReplica(replicas[1],
                                      crash_at_step=max(2, srv_new // 2))
    for rep in replicas:
        rep.reset_stats()
    router.reset_stats()
    killed = router_window(run_router())
    return {
        "metric": f"{METRIC}_router",
        "replicas": 2,
        "clean_tokens_per_sec": clean["tokens_per_sec"],
        "clean_ttft_ms_p95": clean["ttft_ms_p95"],
        "clean_availability": clean["availability"],
        "killed_tokens_per_sec": killed["tokens_per_sec"],
        "killed_ttft_ms_p95": killed["ttft_ms_p95"],
        "killed_availability": killed["availability"],
        "killed_failovers": killed["failovers"],
        "requests": n_requests, "new_tokens": srv_new,
    }


# ---------------------------------------------------------------------------
# fleet: replayed-trace SLO attainment, fixed vs autoscaled, warm vs cold
def _fleet_series(ctx):
    """The elasticity tier: ONE seeded diurnal+burst arrival trace
    replayed (fake clocks, faster than real time) against (a) the
    static minimum fleet — one replica — and (b) the autoscaled fleet
    (min 1, max 2, SLO error budgets) built through the cold
    ``ReplicaFactory`` path. Reports SLO attainment + tokens per
    simulated second for both, plus the scale-up time-to-first-token
    for a WARM replica (parked engine, compiled programs live) vs a
    COLD one (fresh build, full compile) — the number the PR 8 AOT
    bundle exists to shrink."""
    from deepspeed_tpu.serving.replay import (ReplayClock, TraceReplayer,
                                              synthesize_trace)
    from deepspeed_tpu.serving.router import (CallableReplicaFactory,
                                              FleetManager, ReplicaRouter)

    cfg, scfg = ctx["cfg"], ctx["scfg"]
    on_tpu, srv_new = ctx["on_tpu"], ctx["srv_new"]
    if on_tpu:
        duration, base_rate, burst = 60.0, 2.0, (15.0, 15.0, 8.0)
        prompt_mean, prompt_max = ctx["prompt"] // 2, ctx["prompt"]
        queue_cap, step_secs = 8, 0.25
    else:
        duration, base_rate, burst = 16.0, 1.0, (4.0, 5.0, 5.0)
        prompt_mean, prompt_max = 5, 8
        queue_cap, step_secs = 3, 0.25
    trace = synthesize_trace(
        duration, seed=23, base_rate=base_rate,
        diurnal_fraction=0.3, diurnal_period_secs=duration,
        bursts=[burst], prompt_len_mean=prompt_mean,
        prompt_len_max=prompt_max, gen_mean=srv_new, gen_sigma=0.2,
        gen_max=srv_new)
    slo = {"ttft_p95_ms": 1000.0, "shed_rate": 0.05}
    fleet_cfg = {"min_replicas": 1, "max_replicas": 2,
                 "target_ttft_p95_ms": slo["ttft_p95_ms"],
                 "target_shed_rate": slo["shed_rate"],
                 "fast_window_steps": 6, "slow_window_steps": 40,
                 "scale_up_load": 0.6, "scale_up_cooldown_steps": 2,
                 "scale_down_cooldown_steps": 8,
                 "scale_down_quiet_steps": 10}
    build = lambda: _build_serving(ctx, {"max_queue_depth": queue_cap})  # noqa: E731

    def leg(autoscale):
        clock = ReplayClock()
        # shed_priority_floor 0 disables the degradation ladder's
        # priority shed for this all-priority-0 trace: this series
        # measures the CAPACITY axis (sheds = queue_full backpressure),
        # the ladder axis is the *_router series' job — identical
        # router config on both legs either way
        router = ReplicaRouter([build()], clock=clock,
                               config={"shed_priority_floor": 0})
        target = FleetManager(router,
                              factory=CallableReplicaFactory(build),
                              config=fleet_cfg) if autoscale else router
        t0 = time.perf_counter()
        rep = TraceReplayer(target, trace, clock, step_secs=step_secs,
                            seed=31, max_steps=20000)
        rep.run()
        wall = time.perf_counter() - t0
        out = rep.report(slo=slo)
        stats = target.stats() if autoscale else {}
        return target, out, wall, stats

    static_t, static, static_wall, _ = leg(False)
    fleet_t, auto, auto_wall, fstats = leg(True)

    # warm vs cold scale-up TTFT (wall time): a parked engine that
    # already served the replay vs a factory-fresh engine paying its
    # compiles — both measured submit -> first token on an idle replica
    def first_token_secs(engine):
        seen = []
        t0 = time.perf_counter()
        engine.submit(np.arange(1, prompt_max + 1, dtype=np.int32),
                      max_new_tokens=2,
                      stream=lambda r, t, d: seen.append(t))
        while not seen:
            engine.step()
        dt = time.perf_counter() - t0
        engine.drain()
        return dt

    warm_engine = fleet_t.router.replicas[0]      # served the replay
    warm_secs = first_token_secs(warm_engine)
    cold_engine = build()
    cold_secs = first_token_secs(cold_engine)

    payload = {
        "metric": f"{METRIC}_fleet",
        "trace_requests": len(trace),
        "sim_secs": auto["sim_secs"],
        "static_slo_attainment": static.get("slo_attainment"),
        "static_ttft_ms_p95": static["ttft_ms_p95"],
        "static_shed_rate": static["shed_rate"],
        "static_tokens_per_sim_sec": static["tokens_per_sim_sec"],
        "autoscaled_slo_attainment": auto.get("slo_attainment"),
        "autoscaled_ttft_ms_p95": auto["ttft_ms_p95"],
        "autoscaled_shed_rate": auto["shed_rate"],
        "autoscaled_tokens_per_sim_sec": auto["tokens_per_sim_sec"],
        "scale_ups": fstats.get("scale_ups"),
        "scale_downs": fstats.get("scale_downs"),
        "max_replicas": fleet_cfg["max_replicas"],
        "replay_wall_secs_static": round(static_wall, 3),
        "replay_wall_secs_autoscaled": round(auto_wall, 3),
        "warm_scale_up_ttft_ms": round(1e3 * warm_secs, 2),
        "cold_scale_up_ttft_ms": round(1e3 * cold_secs, 2),
    }
    cold_engine.destroy()
    static_t.destroy()
    fleet_t.destroy()
    return payload


# ---------------------------------------------------------------------------
# live KV migration: moving state vs replaying work
def _migration_series(ctx):
    """Optional extra series (after the headline JSON): what moving KV
    blocks instead of replaying work buys, in three numbers:

    - **failover** — time from a breaker trip to the first RESUMED
      token of the moved stream, migrate vs full replay (replay pays a
      fresh prefill plus regenerating every delivered token just to
      swallow them);
    - **drain** — sweeps for a scale-down drain to empty the replica,
      migrate-based vs finishing the work in place;
    - **wire** — exported bytes per sequence at the full KV dtype vs
      ``kv_cache_dtype: "int8"`` (side pools + scales ride the same
      block indices, so the quantized move ships ~4x fewer bytes from
      f32 pools)."""

    from deepspeed_tpu.runtime.resilience.chaos import ChaosReplica
    from deepspeed_tpu.serving.router import ReplicaRouter

    cfg = ctx["cfg"]
    srv_new, srv_rng = ctx["srv_new"], ctx["srv_rng"]
    L = max(ctx["lens"])

    def prompt():
        return srv_rng.integers(0, cfg.vocab_size, L).astype(np.int32)

    def warmed_pair():
        pair = (_build_serving(ctx), _build_serving(ctx))
        for s in pair:
            s.submit(prompt(), max_new_tokens=2)
            s.drain()
            s.reset_stats()
        return pair

    def failover_leg(migration):
        # replica 0 trips its breaker after the first decode step; the
        # gap between the stream's first and second token timestamps IS
        # the time-to-first-resumed-token (with migration the survivor
        # lands the blocks and decodes; with replay it re-prefills and
        # regenerates the delivered prefix, which the shim swallows)
        s0, s1 = warmed_pair()
        router = ReplicaRouter(
            [ChaosReplica(s0, fail_step_at=2, fail_step_times=3), s1],
            config={"failure_threshold": 3, "max_failovers": 2},
            migration=migration)
        stamps = []
        r = router.submit(prompt(), max_new_tokens=srv_new,
                          stream=lambda _r, t, d:
                          stamps.append(time.perf_counter()))
        router.drain(max_steps=500)
        moved = router.stats()["migrations"]
        router.destroy()
        gap = (round(1e3 * (stamps[1] - stamps[0]), 2)
               if r.state == "finished" and len(stamps) > 1 else None)
        return gap, moved

    def drain_leg(migration):
        # the fleet drain sweep, one step at a time: how many sweeps
        # until the draining replica is empty
        s0, s1 = warmed_pair()
        router = ReplicaRouter([s0, s1],
                               config={"failure_threshold": 3},
                               migration=migration)
        router.submit(prompt(), max_new_tokens=srv_new)
        router.step()                     # running, first token out
        router.start_drain(0)
        t0 = time.perf_counter()
        steps = 0
        while router.assigned(0) and steps < 500:
            router.migrate_work(0, "drain")
            if router.assigned(0):
                router.step()
            steps += 1
        ms = round(1e3 * (time.perf_counter() - t0), 2)
        router.drain(max_steps=200)       # finish moved/remaining work
        router.destroy()
        return steps, ms

    def wire_leg(extra):
        srv = _build_serving(ctx, extra)
        r = srv.submit(prompt(), max_new_tokens=srv_new)
        for _ in range(2):
            srv.step()
        export = srv.export_sequence(r.request_id)
        wire = int(export["wire_bytes"]) if export else None
        srv.destroy()
        return wire

    mig_gap, moved = failover_leg({"enabled": True})
    replay_gap, _ = failover_leg(None)
    mig_steps, mig_ms = drain_leg({"enabled": True})
    yield_steps, yield_ms = drain_leg(None)
    wire_full = wire_leg(None)
    wire_int8 = wire_leg({"kv_cache_dtype": "int8"})
    return {
        "metric": f"{METRIC}_migration",
        "migrations_in_window": moved,
        "migrate_resume_gap_ms": mig_gap,
        "replay_resume_gap_ms": replay_gap,
        "migrate_drain_steps": mig_steps,
        "yield_drain_steps": yield_steps,
        "migrate_drain_ms": mig_ms,
        "yield_drain_ms": yield_ms,
        "export_wire_bytes": wire_full,
        "export_wire_bytes_int8": wire_int8,
        "wire_ratio": (round(wire_full / wire_int8, 2)
                       if wire_full and wire_int8 else None),
        "prompt_len": L, "new_tokens": srv_new,
    }


# ---------------------------------------------------------------------------
# gateway: the HTTP/SSE front door's cost + quota-shed correctness
def _gateway_series(ctx):
    """Two questions, measured: (1) what does the HTTP hop cost —
    tokens/s and TTFT p95 for the SAME mixed workload submitted
    directly vs POSTed through a running ``ServingGateway``; (2) do
    per-tenant quotas actually isolate — a two-tenant concurrent burst
    where the gold tenant must come through clean while the
    rate-capped best_effort tenant sheds at the door."""
    import json as _json
    import urllib.error
    import urllib.request
    from concurrent.futures import ThreadPoolExecutor

    from deepspeed_tpu.serving.gateway import ServingGateway

    cfg = ctx["cfg"]
    n_requests = ctx["n_requests"]
    lens, srv_new, srv_rng = ctx["lens"], ctx["srv_new"], ctx["srv_rng"]

    def prompts():
        return [srv_rng.integers(0, cfg.vocab_size,
                                 lens[i % len(lens)]).astype(np.int32)
                for i in range(n_requests)]

    def post(gw, prompt, key=None, timeout=120.0):
        headers = {"Content-Type": "application/json"}
        if key:
            headers["Authorization"] = f"Bearer {key}"
        body = _json.dumps({"prompt": [int(t) for t in prompt],
                            "max_new_tokens": srv_new,
                            "stream": False}).encode("utf-8")
        resp = urllib.request.urlopen(urllib.request.Request(
            gw.url + "/v1/generate", data=body, headers=headers,
            method="POST"), timeout=timeout)
        return _json.loads(resp.read().decode("utf-8"))

    # leg 1: direct submit/step, the Python-path floor
    srv = _build_serving(ctx)
    work = prompts()

    def run_direct():
        pending = list(work)
        t0 = time.perf_counter()
        while pending or srv.pending:
            if pending:
                srv.submit(pending.pop(0), max_new_tokens=srv_new)
            srv.step()
        srv.drain()
        return time.perf_counter() - t0

    run_direct()  # warm bucket set + decode program
    srv.reset_stats()
    elapsed = run_direct()
    st = srv.stats()
    direct_tokens = sum(r["new_tokens"] for r in srv.records
                        if r["state"] != "shed")
    direct_rate = (round(direct_tokens / elapsed, 1)
                   if elapsed > 0 else None)
    direct_ttft = st["ttft_ms_p95"]
    srv.destroy()

    # leg 2: the SAME workload through the gateway (pump thread
    # steps; concurrent JSON posts; TTFT observed server-side)
    srv = _build_serving(ctx)
    gw = ServingGateway(srv, {"pump": True,
                              "poll_secs": 0.002}).start()
    try:
        post(gw, work[0])  # warm through the full HTTP path
        t0 = time.perf_counter()
        with ThreadPoolExecutor(max_workers=n_requests) as pool:
            outs = list(pool.map(lambda p: post(gw, p), work))
        elapsed = time.perf_counter() - t0
        gw_tokens = sum(len(o["tokens"]) for o in outs
                        if o["state"] == "finished")
        gw_rate = (round(gw_tokens / elapsed, 1)
                   if elapsed > 0 else None)
        ttfts = sorted(o["record"]["ttft_ms"] for o in outs
                       if o["record"].get("ttft_ms") is not None)
        gw_ttft = (round(ttfts[min(len(ttfts) - 1,
                                   int(0.95 * len(ttfts)))], 2)
                   if ttfts else None)
    finally:
        gw.destroy()

    # leg 3: two-tenant concurrent burst — gold unlimited,
    # best_effort capped at 1 req/s with burst 1
    srv = _build_serving(ctx)
    gw = ServingGateway(srv, {
        "pump": True, "poll_secs": 0.002,
        "tenants": [
            {"name": "gold", "api_key": "gold-key",
             "slo_class": "gold", "requests_per_sec": 10000.0},
            {"name": "be", "api_key": "be-key",
             "slo_class": "best_effort", "requests_per_sec": 1.0,
             "burst_requests": 1},
        ]}).start()
    try:
        def burst_one(args):
            key, prompt = args
            try:
                out = post(gw, prompt, key=key)
                return key, out["state"]
            except urllib.error.HTTPError as e:
                code = e.code
                e.close()
                return key, f"http_{code}"

        jobs = [("gold-key" if i % 2 == 0 else "be-key", p)
                for i, p in enumerate(prompts())]
        with ThreadPoolExecutor(max_workers=len(jobs)) as pool:
            results = list(pool.map(burst_one, jobs))
        gold_n = sum(1 for k, _ in results if k == "gold-key")
        gold_ok = sum(1 for k, s in results
                      if k == "gold-key" and s == "finished")
        be_429 = sum(1 for k, s in results
                     if k == "be-key" and s == "http_429")
        be_ok = sum(1 for k, s in results
                    if k == "be-key" and s == "finished")
    finally:
        gw.destroy()

    return {
        "metric": f"{METRIC}_gateway",
        "direct_tokens_per_sec": direct_rate,
        "direct_ttft_ms_p95": direct_ttft,
        "gateway_tokens_per_sec": gw_rate,
        "gateway_ttft_ms_p95": gw_ttft,
        "gateway_overhead_pct": (
            round(100.0 * (1.0 - gw_rate / direct_rate), 1)
            if direct_rate and gw_rate else None),
        "burst_gold_ok": gold_ok, "burst_gold_requests": gold_n,
        "burst_best_effort_ok": be_ok,
        "burst_best_effort_429": be_429,
        "requests": n_requests, "new_tokens": srv_new,
    }


# ---------------------------------------------------------------------------
# tuner series: the live autotuner's decode-side measurement hooks
def _decode_attention_series(ctx, block_k=None, reps=None):
    """Microbench of the dense decode-attention kernel at one ``block_k``
    candidate. On TPU the real Pallas kernel runs; on CPU the interpret-
    mode emulation runs (relative ranking only — same plumbing, honest
    ``backend`` field). The tuned value feeds the kernel-default
    registry (``ops.decode_attention.block_k``)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.decode_attention import decode_attention
    from deepspeed_tpu.utils.compat import tpu_interpret_mode

    on_tpu = ctx["on_tpu"]
    reps = reps or (20 if on_tpu else 3)
    b, heads, d = (8, 12, 64) if on_tpu else (2, 2, 8)
    s_len = 1024 if on_tpu else 512
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(b, 1, heads, d)), jnp.float32)
    k_cache = jnp.asarray(rng.normal(size=(b, s_len, heads, d)), jnp.float32)
    v_cache = jnp.asarray(rng.normal(size=(b, s_len, heads, d)), jnp.float32)
    idx = jnp.asarray(s_len // 2, jnp.int32)

    import contextlib
    interp = contextlib.nullcontext() if on_tpu else tpu_interpret_mode()
    with interp:
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
        "metric": f"{METRIC}_decode_attention",
        "per_call_ms": round(1e3 * dt / reps, 4),
        "block_k": block_k,
        "cache_len": s_len, "batch": b, "heads": heads, "head_dim": d,
        "backend": "tpu" if on_tpu else "cpu_interpret",
        "reps": reps,
    }


def _serving_chunk_series(ctx, serving_overrides=None):
    """Serving-shape measurement for the chunk-size / bucket-set axes:
    one long prompt ahead of short requests, reporting the short
    requests' TTFT p95 (what a chunk budget buys), drain tokens/s, and
    the telemetry-side compile count of the window's programs."""
    cfg, scfg = ctx["cfg"], ctx["scfg"]
    lens, srv_new, srv_rng = ctx["lens"], ctx["srv_new"], ctx["srv_rng"]
    bs = scfg["block_size"]
    long_len = (8 if ctx["on_tpu"] else 4) * bs
    n_short = ctx["batch"] if ctx["on_tpu"] else 3

    eng = _build_serving(ctx, serving_overrides or {}, telemetry=True)

    def drain_all(prompts):
        t0 = time.perf_counter()
        for p in prompts:
            eng.submit(p, max_new_tokens=srv_new)
        while eng.pending:
            eng.step()
        eng.drain()
        return time.perf_counter() - t0

    def window():
        prompts = [srv_rng.integers(0, cfg.vocab_size,
                                    long_len).astype(np.int32)]
        prompts += [srv_rng.integers(0, cfg.vocab_size,
                                     lens[i % len(lens)]).astype(np.int32)
                    for i in range(n_short)]
        return drain_all(prompts)

    window()  # warm the programs
    eng.reset_stats()
    elapsed = window()
    ttfts = [r["ttft_ms"] for r in eng.records
             if r["state"] != "shed" and r["prompt_len"] < long_len
             and r["ttft_ms"] is not None]
    tokens_out = sum(r["new_tokens"] for r in eng.records
                     if r["state"] != "shed")
    summary = eng.telemetry.summary()
    payload = {
        "metric": f"{METRIC}_serving_chunk",
        "short_ttft_ms_p95": round(float(np.percentile(ttfts, 95)), 2)
        if ttfts else None,
        "tokens_per_sec": round(tokens_out / elapsed, 1)
        if elapsed > 0 else None,
        "compiled_programs": sum(v["compiles"] for v in
                                 summary["per_function"].values()),
        "long_prompt_len": long_len, "n_short": n_short,
        "serving_overrides": dict(serving_overrides or {}),
    }
    eng.destroy()
    return payload


# ---------------------------------------------------------------------------
# speculative decoding: draft-and-verify vs the non-speculative baseline
def _spec_decode_series(ctx):
    """The speculative-decoding win on a prompt-lookup-friendly workload
    (repetitive/extractive prompts, whose greedy continuations the
    n-gram proposer predicts well): decode tokens/s with and without
    the verify program, accepted tokens per verify dispatch, acceptance
    rate, and TTFT p50/p95 both ways — speculation must buy decode
    throughput without touching time-to-first-token (prefill is not
    speculated). The measured window drains the SAME prompt set through
    both engines; greedy bit-exactness (pinned in test_serving.py)
    means the token streams are identical, so tokens/s is the whole
    story. Also the measurement hook behind the live autotuner's
    ``serving.num_speculative_tokens`` axis."""
    cfg, scfg = ctx["cfg"], ctx["scfg"]
    srv_rng = ctx["srv_rng"]
    spec_block = dict(scfg.get("speculative")
                      or {"num_speculative_tokens": 4})
    # enabled:false measures the MACHINERY-OFF candidate (the tuner's
    # "off" grid point): only the baseline leg runs and its throughput
    # IS the objective value — never a fake ~1.0 "speedup" from
    # comparing two identical engines
    spec_off = spec_block.get("enabled", True) is False
    k = int(spec_block.get("num_speculative_tokens", 4))
    if ctx["on_tpu"]:
        motif, prompt_len, new_tok = 16, 4 * scfg["block_size"], \
            ctx["new_tokens"]
        n_requests = 2 * ctx["batch"]
    else:
        motif, prompt_len, new_tok, n_requests = 4, 16, 16, 6

    def prompts():
        out = []
        for _ in range(n_requests):
            m = srv_rng.integers(0, cfg.vocab_size, motif)
            out.append(np.tile(m, prompt_len // motif
                               + 1)[:prompt_len].astype(np.int32))
        return out

    def window(eng, batch):
        t0 = time.perf_counter()
        for p in batch:
            eng.submit(p, max_new_tokens=new_tok)
        while eng.pending:
            eng.step()
        eng.drain()
        elapsed = time.perf_counter() - t0
        st = eng.stats()
        tokens_out = sum(r["new_tokens"] for r in eng.records
                         if r["state"] != "shed")
        return {
            "tokens_per_sec": round(tokens_out / elapsed, 1)
            if elapsed > 0 else None,
            "ttft_ms_p50": st["ttft_ms_p50"],
            "ttft_ms_p95": st["ttft_ms_p95"],
            "speculative": st["speculative"],
        }

    measured = {}
    batch = prompts()  # ONE prompt set: both engines decode the same work
    legs = [("baseline", {"speculative": None})]
    if not spec_off:
        legs.append(("spec", {"speculative": spec_block}))
    for label, extra in legs:
        eng = _build_serving(ctx, extra)
        window(eng, batch)   # warm the programs (prefill buckets + step)
        eng.reset_stats()
        measured[label] = window(eng, batch)
        eng.destroy()
        del eng
    base = measured["baseline"]
    spec = measured.get("spec", base)
    sp = spec["speculative"] or {}
    speedup = (round(spec["tokens_per_sec"] / base["tokens_per_sec"], 3)
               if not spec_off and base["tokens_per_sec"]
               and spec["tokens_per_sec"] else None)
    return {
        "metric": f"{METRIC}_spec_decode",
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
        "num_speculative_tokens": k,
        "requests": n_requests, "prompt_len": prompt_len,
        "new_tokens": new_tok,
    }


# ---------------------------------------------------------------------------
# span tracing: serving tokens/s with the span layer off vs on
def _tp_series(ctx):
    """Optional extra series (after the headline JSON): paged-decode
    serving under tensor parallelism. Builds the SAME serving engine at
    tp=1 and tp=2 (SpecLayout weight sharding, KV pools head-sharded
    per shard by ``decode_cache_specs``), runs the same decode
    workload, and reports tokens/s plus the compiled single-step decode
    program's collective operand bytes at each tp — TP's decode comm
    cost next to its throughput, on the CPU smoke mesh or real chips
    alike."""
    import jax

    if jax.device_count() < 2:
        return {"metric": f"{METRIC}_tp", "value": None,
                "unit": "tokens_per_sec",
                "not_measured": "needs >= 2 devices for a tp=2 mesh"}

    def measure(tp):
        import deepspeed_tpu
        from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel
        from deepspeed_tpu.parallel.topology import reset_topology
        from deepspeed_tpu.serving import ServingEngine

        cfg, srv_rng = ctx["cfg"], np.random.default_rng(7)
        reset_topology()
        srv = ServingEngine(deepspeed_tpu.init_inference(
            GPT2LMHeadModel(cfg), dtype=cfg.dtype,
            tensor_parallel={"tp_size": tp},
            max_out_tokens=cfg.n_positions, serving=dict(ctx["scfg"])))
        lens, srv_new = ctx["lens"], ctx["srv_new"]
        n_requests = max(4, ctx["n_requests"] // 2)

        def run():
            pending = [srv_rng.integers(0, cfg.vocab_size,
                                        lens[i % len(lens)]).astype(
                np.int32) for i in range(n_requests)]
            t0 = time.perf_counter()
            while pending or srv.pending:
                if pending:
                    srv.submit(pending.pop(0), max_new_tokens=srv_new)
                srv.step()
            srv.drain()
            return time.perf_counter() - t0

        run()  # warm: compile the bucket set + decode program
        srv.reset_stats()
        elapsed = run()
        tokens_out = sum(r["new_tokens"] for r in srv.records
                         if r["state"] != "shed")
        tok_s = round(tokens_out / elapsed, 1) if elapsed > 0 else None
        srv.destroy()
        return tok_s

    tp1_tok = measure(1)
    tp2_tok = measure(2)
    wire = _tp_decode_wire_bytes(ctx)
    return {
        "metric": f"{METRIC}_tp",
        "value": tp2_tok,
        "unit": "tokens_per_sec",
        "vs_baseline": (round(tp2_tok / tp1_tok, 4)
                        if tp1_tok and tp2_tok else None),
        "tp1_tokens_per_sec": tp1_tok,
        "tp2_tokens_per_sec": tp2_tok,
        "tp1_decode_wire_bytes": wire.get(1),
        "tp2_decode_wire_bytes": wire.get(2),
    }


def _tp_decode_wire_bytes(ctx):
    """Collective operand bytes of ONE compiled decode step at tp=1 and
    tp=2: params sharded by the live policy, paged KV pools head-sharded
    by ``decode_cache_specs`` — the decode program the serving loop
    dispatches, lowered standalone so its HLO is readable."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.module_inject.policies import (decode_cache_specs,
                                                      get_tp_policy,
                                                      specs_from_policy)
    from deepspeed_tpu.parallel.topology import MeshTopology, reset_topology
    from deepspeed_tpu.runtime.zero.partition import replicated
    from deepspeed_tpu.utils.hlo_inspect import parse_collectives

    cfg = ctx["cfg"]
    bs = int(ctx["scfg"].get("block_size", 8))
    out = {}
    for tp in (1, 2):
        reset_topology()
        topo = MeshTopology(axis_sizes={"tp": tp})
        mesh = topo.mesh
        dcfg = cfg.for_paged_decode(num_blocks=8, block_size=bs)
        from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel

        dmodel = GPT2LMHeadModel(dcfg)
        B = 2
        pg = {"block_tables": jnp.zeros((B, 4), jnp.int32),
              "lengths": jnp.zeros((B,), jnp.int32),
              "num_valid": jnp.ones((B,), jnp.int32), "prefill": False}
        abstract = jax.eval_shape(
            lambda: dmodel.init(jax.random.PRNGKey(0),
                                jnp.zeros((B, 1), jnp.int32), paging=pg))
        params_abs, cache_abs = abstract["params"], abstract["cache"]
        from jax.sharding import NamedSharding, PartitionSpec as P

        specs = specs_from_policy(get_tp_policy("gpt2"), params_abs, mesh)
        psh = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s if s is not None else P()),
            specs, is_leaf=lambda s: s is None or isinstance(s, P))
        csh = decode_cache_specs(cache_abs, mesh, heads=cfg.n_head)

        def step(p, c, tok, tables, lengths):
            o, vars_ = dmodel.apply(
                {"params": p, "cache": c}, tok, mutable=["cache"],
                paging={"block_tables": tables, "lengths": lengths,
                        "num_valid": jnp.ones_like(lengths),
                        "prefill": False})
            o = o[0] if isinstance(o, tuple) else o
            return jnp.argmax(o[:, -1], axis=-1), vars_["cache"]

        hlo = jax.jit(step, in_shardings=(psh, csh, replicated(mesh),
                                          replicated(mesh),
                                          replicated(mesh)),
                      out_shardings=(replicated(mesh), csh)) \
            .lower(params_abs, cache_abs,
                   jax.ShapeDtypeStruct((B, 1), jnp.int32),
                   jax.ShapeDtypeStruct((B, 4), jnp.int32),
                   jax.ShapeDtypeStruct((B,), jnp.int32)) \
            .compile().as_text()
        colls = [c for c in parse_collectives(hlo)
                 if c["operand_bytes"] >= 16]
        out[tp] = sum(c["operand_bytes"] for c in colls)
    reset_topology()
    return out


def _serving_tracing_series(ctx):
    """Optional extra series (after the headline JSON): the span-tracing
    overhead bound on the serving side — the SAME mixed-arrival workload
    as the `*_serving` series, run once with telemetry+tracing off and
    once with request-span tracing on (queue/prefill/decode spans per
    request). The compiled programs are byte-identical either way (the
    zero-overhead pin); this bounds the host-side span bookkeeping."""

    cfg = ctx["cfg"]
    n_requests, arrive_every = ctx["n_requests"], ctx["arrive_every"]
    lens, srv_new, srv_rng = ctx["lens"], ctx["srv_new"], ctx["srv_rng"]

    def run_mixed(srv):
        pending = [srv_rng.integers(0, cfg.vocab_size,
                                    lens[i % len(lens)]).astype(np.int32)
                   for i in range(n_requests)]
        t0 = time.perf_counter()
        while pending or srv.pending:
            for _ in range(arrive_every):
                if pending:
                    srv.submit(pending.pop(0), max_new_tokens=srv_new)
            srv.step()
        srv.drain()
        return time.perf_counter() - t0

    rates = {}
    spans = 0
    # both legs telemetry-enabled: the delta isolates the SPAN
    # layer, not the collector stack around it (same contract as
    # bench.py's train-side tracing series)
    for label, telemetry in (
            ("off", {"enabled": True, "jsonl": False, "memory": False}),
            ("on", {"enabled": True, "jsonl": False, "memory": False,
                    "tracing": {"enabled": True}})):
        srv = _build_serving(ctx, telemetry=telemetry)
        run_mixed(srv)       # warm the bucket set + decode program
        srv.reset_stats()
        mark = srv.telemetry.tracer.emitted
        elapsed = run_mixed(srv)
        tokens_out = sum(r["new_tokens"] for r in srv.records
                         if r["state"] != "shed")
        rates[label] = (round(tokens_out / elapsed, 1)
                        if elapsed > 0 else None)
        if label == "on":
            # tracer-side counter: the telemetry tail is a bounded
            # ring and would undercount a real window
            spans = srv.telemetry.tracer.emitted - mark
        srv.destroy()
    off, on = rates["off"], rates["on"]
    return {
        "metric": f"{METRIC}_tracing",
        "tokens_per_sec_tracing_off": off,
        "tokens_per_sec_tracing_on": on,
        "overhead_pct": round(100.0 * (off - on) / off, 2)
        if off and on is not None else None,
        "spans_in_window": spans,
        "requests": n_requests, "new_tokens": srv_new,
    }


# ---------------------------------------------------------------------------
# keyed sampling: in-graph filtering overhead + sampled-stream failover
def _sampling_series(ctx):
    """Optional extra series (after the headline JSON): what the
    reproducible-sampling contract costs and buys — (1) the SAME
    mixed-arrival workload decoded greedy vs keyed-sampled (the keyed
    program folds a threefry key and filters logits in-graph every
    step, so the delta bounds that overhead); (2) failover
    time-to-first-resumed-token for a SAMPLED stream, migrate vs full
    replay (keyed replay regenerates the delivered prefix bit-exactly
    and the shim swallows it — pre-contract, this request was simply
    shed)."""

    from deepspeed_tpu.runtime.resilience.chaos import ChaosReplica
    from deepspeed_tpu.serving.router import ReplicaRouter

    cfg = ctx["cfg"]
    n_requests, arrive_every = ctx["n_requests"], ctx["arrive_every"]
    lens, srv_new, srv_rng = ctx["lens"], ctx["srv_new"], ctx["srv_rng"]
    L = max(lens)
    SAMP = {"sampling": {"enabled": True}}

    def long_prompt():
        return srv_rng.integers(0, cfg.vocab_size, L).astype(np.int32)

    def run_mixed(srv, sampled):
        pending = [srv_rng.integers(0, cfg.vocab_size,
                                    lens[i % len(lens)]).astype(np.int32)
                   for i in range(n_requests)]
        i = 0
        t0 = time.perf_counter()
        while pending or srv.pending:
            for _ in range(arrive_every):
                if pending:
                    kw = ({"do_sample": True, "seed": 1000 + i,
                           "temperature": 0.9, "top_p": 0.95}
                          if sampled else {})
                    srv.submit(pending.pop(0), max_new_tokens=srv_new,
                               **kw)
                    i += 1
            srv.step()
        srv.drain()
        return time.perf_counter() - t0

    def throughput_leg(sampled):
        srv = _build_serving(ctx, SAMP)
        run_mixed(srv, sampled)   # warm the bucket set + decode program
        srv.reset_stats()
        elapsed = run_mixed(srv, sampled)
        tokens_out = sum(r["new_tokens"] for r in srv.records
                         if r["state"] != "shed")
        srv.destroy()
        return (round(tokens_out / elapsed, 1) if elapsed > 0 else None)

    def failover_leg(migration):
        # replica 0 trips after the first decode step of a SAMPLED
        # stream; first->second stream timestamp gap = time to the
        # first resumed token (migrate moves the KV and the sampling
        # counters; replay re-prefills and regenerates the delivered
        # prefix from (seed, position), deduped by the shim)
        pair = []
        for _ in range(2):
            s = _build_serving(ctx, SAMP)
            s.submit(long_prompt(), max_new_tokens=2, do_sample=True,
                     seed=7)
            s.drain()
            s.reset_stats()
            pair.append(s)
        s0, s1 = pair
        router = ReplicaRouter(
            [ChaosReplica(s0, fail_step_at=2, fail_step_times=3), s1],
            config={"failure_threshold": 3, "max_failovers": 2},
            migration=migration)
        stamps = []
        r = router.submit(long_prompt(), max_new_tokens=srv_new,
                          do_sample=True, seed=42, temperature=0.9,
                          stream=lambda _r, t, d:
                          stamps.append(time.perf_counter()))
        router.drain(max_steps=500)
        moved = router.stats()["migrations"]
        router.destroy()
        gap = (round(1e3 * (stamps[1] - stamps[0]), 2)
               if r.state == "finished" and len(stamps) > 1 else None)
        return gap, moved

    greedy_tps = throughput_leg(False)
    sampled_tps = throughput_leg(True)
    mig_gap, moved = failover_leg({"enabled": True})
    replay_gap, _ = failover_leg(None)
    return {
        "metric": f"{METRIC}_sampling",
        "greedy_tokens_per_sec": greedy_tps,
        "sampled_tokens_per_sec": sampled_tps,
        "sampling_overhead_pct": round(
            100.0 * (greedy_tps - sampled_tps) / greedy_tps, 2)
        if greedy_tps and sampled_tps is not None else None,
        "migrations_in_window": moved,
        "sampled_migrate_resume_gap_ms": mig_gap,
        "sampled_replay_resume_gap_ms": replay_gap,
        "requests": n_requests, "new_tokens": srv_new,
        "prompt_len": L,
    }


# ---------------------------------------------------------------------------
def run_series(name, config=None):
    """Run ONE decode-bench series in-process and return its payload
    dict (never emits). ``config`` keys: ``serving`` (overrides merged
    into the serving block), ``block_k`` (decode_attention series),
    ``batch``/``prompt``/``new_tokens``/``reps``."""
    config = dict(config or {})
    ctx = _decode_context(config)
    if name == "headline":
        return _headline_series(ctx)
    if name == "serving":
        return _serving_series(ctx)
    if name == "serving_fastpath":
        return _serving_fastpath_series(ctx)
    if name == "router":
        return _router_series(ctx)
    if name == "fleet":
        return _fleet_series(ctx)
    if name == "gateway":
        return _gateway_series(ctx)
    if name == "migration":
        return _migration_series(ctx)
    if name == "decode_attention":
        return _decode_attention_series(ctx, block_k=config.get("block_k"))
    if name == "serving_chunk":
        return _serving_chunk_series(ctx,
                                     serving_overrides=config.get("serving"))
    if name == "serving_tracing":
        return _serving_tracing_series(ctx)
    if name == "serving_sampling":
        return _sampling_series(ctx)
    if name == "spec_decode":
        return _spec_decode_series(ctx)
    if name == "tp":
        return _tp_series(ctx)
    raise KeyError(f"unknown decode series {name!r}; available: "
                   f"{sorted(SERIES)}")


SERIES = ("headline", "serving", "serving_fastpath", "router", "fleet",
          "migration", "gateway", "decode_attention", "serving_chunk",
          "serving_tracing", "serving_sampling", "spec_decode", "tp")


def main():
    # the TPU, or the CPU when it was asked for by name; anything else raises
    dev = require_device("tpu")
    on_tpu = dev["platform"] == "tpu"
    ctx = _decode_context(on_tpu=on_tpu)

    # headline first; a series that fails raises and the run exits non-zero
    emit_result(_headline_series(ctx))
    emit_result(_serving_series(ctx))
    emit_result(_serving_fastpath_series(ctx))
    emit_result(_router_series(ctx))
    emit_result(_fleet_series(ctx))
    emit_result(_migration_series(ctx))
    emit_result(_gateway_series(ctx))
    emit_result(_spec_decode_series(ctx))
    emit_result(_serving_tracing_series(ctx))
    emit_result(_sampling_series(ctx))
    emit_result(_tp_series(ctx))


if __name__ == "__main__":
    main()
