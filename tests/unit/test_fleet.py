"""Fleet manager: trace-driven load replay, the SLO error-budget
autoscaler, and chaos-proven elastic scale over the replica router.

Five tiers, the first four pure host-side (fake replicas + the replay
fake clock — no jax, millisecond tier-1):

- trace format + synthetic generators (determinism, diurnal/burst
  shapes, heavy tails, shared-prefix tenants) and the replayer;
- the capacity model (latency-vs-load curves from Histogram merges,
  ``fleet_size_for``) and the error-budget autoscaler policy;
- the fleet acceptance run: a seeded diurnal+burst trace where the
  autoscaled fleet beats the static minimum fleet on BOTH SLO axes,
  scaling up cold (factory) then warm (parked engines), and the whole
  run is bit-deterministic;
- chaos during scaling: replica killed mid-drain (exactly-once streams
  vs the clean run), a flaky factory (exponential backoff), a burst
  storm during scale-down (the drain is cancelled, not raced), and a
  wedged drain (timeout yields work, never deadlocks ``drain()``);
- heavy: real two-replica ServingEngines under the fleet manager, and
  the zero-overhead pin — a ``serving.fleet`` block leaves the compiled
  decode HLO byte-identical (the PR 2-12 convention).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from deepspeed_tpu.runtime.resilience import chaos
from deepspeed_tpu.runtime.resilience.chaos import (ChaosIOError,
                                                    ChaosReplica,
                                                    FlakyFactory)
from deepspeed_tpu.serving import request as rq
from deepspeed_tpu.serving.autoscaler import (SCALE_DOWN, SCALE_UP,
                                              Autoscaler, BudgetWindow)
from deepspeed_tpu.serving.capacity import CapacityModel
from deepspeed_tpu.serving.config import (FleetConfig, ReplayConfig,
                                          ServingConfig)
from deepspeed_tpu.serving.health import DEAD, DRAINING, HEALTHY
from deepspeed_tpu.serving.replay import (Arrival, ReplayClock,
                                          TraceReplayer, burst_trace,
                                          diurnal_trace, load_trace,
                                          save_trace, synthesize_trace)
from deepspeed_tpu.serving.router import (CallableReplicaFactory,
                                          FleetManager, ReplicaRouter)

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))


def _greedy(prompt, pos):
    """Deterministic decode shared by every fake replica: same prompt ->
    same token at every position on every replica (the bit-reproducible
    greedy contract the real engines pin)."""
    return (31 * sum(int(t) for t in prompt) + 7 * pos) % 997


class FakeReplica:
    """Minimal ServingEngine surface: bounded queue -> slots -> one
    deterministic token per running request per step()."""

    def __init__(self, slots=2, queue_cap=8, buckets=(8, 16)):
        self.slots, self.queue_cap = slots, queue_cap
        self.buckets = list(buckets)
        self.queue, self.running = [], []
        self.submits = self.steps = 0

    def submit(self, prompt, max_new_tokens=0, request_id=None,
               eos_token_id=-1, deadline_ms=0.0, stream=None):
        self.submits += 1
        req = rq.Request(prompt=[int(t) for t in prompt],
                         max_new_tokens=int(max_new_tokens) or 4,
                         request_id=request_id or f"f-{self.submits}",
                         eos_token_id=eos_token_id,
                         deadline_ms=deadline_ms, stream=stream)
        if len(self.queue) >= self.queue_cap:
            req.state, req.finish_reason = rq.SHED, "queue_full"
            return req
        req.state = rq.QUEUED
        self.queue.append(req)
        return req

    def step(self):
        self.steps += 1
        while self.queue and len(self.running) < self.slots:
            head = self.queue.pop(0)
            head.state = rq.RUNNING
            self.running.append(head)
        for req in list(self.running):
            pos = len(req.tokens)
            tok = _greedy(req.prompt, pos)
            done = (tok == req.eos_token_id
                    or pos + 1 >= req.max_new_tokens)
            req.emit_token(tok, done)
            if done:
                req.state = rq.FINISHED
                req.finish_reason = ("eos" if tok == req.eos_token_id
                                     else "max_tokens")
                self.running.remove(req)

    def gauges(self):
        return {"queue_depth": len(self.queue),
                "queue_capacity": self.queue_cap,
                "slots_busy": len(self.running),
                "slots_total": self.slots, "free_blocks": 99}

    def stats(self):
        return {"ttft_ms_p95": None, "shed_rate": None}


class StuckReplica(FakeReplica):
    """Admits work, never finishes it: step() makes no progress (the
    wedged-drain shape — no exception, no stall verdict, just an
    assignment that never empties)."""

    def step(self):
        self.steps += 1


class GaugeStub(FakeReplica):
    """Queue-pressure dial for load-driven autoscaler legs."""

    def __init__(self, depth=0, cap=10, **kw):
        super().__init__(**kw)
        self.depth, self.cap = depth, cap

    def gauges(self):
        g = super().gauges()
        g["queue_depth"], g["queue_capacity"] = self.depth, self.cap
        return g


class MigratableReplica(FakeReplica):
    """FakeReplica plus the engine's live-migration surface (the
    test_router.py twin): export hands out the host-visible sequence
    state with block/wire accounting, import SEEDS the delivered prefix
    without re-emitting it, migrate_out detaches the source copy."""

    block_size = 8

    def __init__(self, **kw):
        super().__init__(**kw)
        self.imports = self.outs = 0

    def export_sequence(self, request_id):
        req = next((r for r in self.running
                    if r.request_id == request_id), None)
        if req is None:
            return None
        covered = len(req.prompt) + len(req.tokens)
        blocks = max(1, -(-covered // self.block_size))
        return {"request_id": req.request_id, "prompt": list(req.prompt),
                "tokens": list(req.tokens),
                "max_new_tokens": req.max_new_tokens,
                "eos_token_id": req.eos_token_id,
                "deadline_ms": req.deadline_ms,
                "blocks": blocks, "wire_bytes": 512 * blocks}

    def import_sequence(self, export, deadline_ms=None, stream=None,
                        request_id=None, trace=None):
        if len(self.running) >= self.slots:
            return None
        self.imports += 1
        req = rq.Request(prompt=list(export["prompt"]),
                         max_new_tokens=int(export["max_new_tokens"]),
                         request_id=request_id or export["request_id"],
                         eos_token_id=export["eos_token_id"],
                         deadline_ms=(export["deadline_ms"]
                                      if deadline_ms is None
                                      else deadline_ms),
                         stream=stream)
        req.tokens = list(export["tokens"])  # seeded, NOT re-emitted
        req.state = rq.RUNNING
        self.running.append(req)
        return req

    def migrate_out(self, request_id):
        req = next((r for r in self.running
                    if r.request_id == request_id), None)
        if req is None:
            return False
        req.state, req.finish_reason = rq.SHED, "migrated"
        self.running.remove(req)
        self.outs += 1
        return True


class FragStub(MigratableReplica):
    """Fragmentation dial for the migrate-based rebalance legs."""

    def __init__(self, frag=0.0, **kw):
        super().__init__(**kw)
        self.frag = frag

    def gauges(self):
        g = super().gauges()
        g["kv_fragmentation"] = self.frag
        return g


class FakeTelemetry:
    enabled = True

    def __init__(self):
        self.events = []

    def emit(self, kind, name, step=None, **data):
        self.events.append({"kind": kind, "name": name, "step": step,
                            "data": data.get("data", data)})

    def of(self, name, kind=None):
        return [e for e in self.events if e["name"] == name
                and (kind is None or e["kind"] == kind)]


def _fleet(replicas, clock=None, telemetry=None, factory=None,
           capacity=None, router_cfg=None, migration=None, **cfg):
    clock = clock or ReplayClock()
    router = ReplicaRouter(replicas,
                           config={"failure_threshold": 3,
                                   **(router_cfg or {})},
                           clock=clock, telemetry=telemetry
                           or FakeTelemetry(), migration=migration)
    cfg.setdefault("min_replicas", 1)
    cfg.setdefault("max_replicas", 4)
    return FleetManager(router, factory=factory, config=cfg,
                        capacity=capacity), clock


# ---------------------------------------------------------------------------
# trace format + generators
# ---------------------------------------------------------------------------
class TestTraceGenerators:
    def test_same_seed_is_bit_identical(self):
        kw = dict(seed=11, base_rate=2.0, diurnal_fraction=0.4,
                  bursts=[(5, 2, 6.0)], tenants=3, shared_fraction=0.5,
                  shared_prefix_len=4)
        assert synthesize_trace(20, **kw) == synthesize_trace(20, **kw)

    def test_different_seeds_differ(self):
        a = synthesize_trace(20, seed=1, base_rate=2.0)
        b = synthesize_trace(20, seed=2, base_rate=2.0)
        assert a != b

    def test_jsonl_roundtrip(self, tmp_path):
        trace = synthesize_trace(15, seed=3, base_rate=2.0, tenants=2,
                                 shared_fraction=0.6, shared_prefix_len=8,
                                 priorities=3, deadline_ms=500.0)
        path = str(tmp_path / "trace.jsonl")
        save_trace(path, trace)
        assert load_trace(path) == trace
        # the open format: every line is plain JSON with the documented
        # required keys
        with open(path) as f:
            rows = [json.loads(line) for line in f]
        assert all({"arrival_ts", "prompt_len", "max_new_tokens"}
                   <= set(r) for r in rows)

    def test_arrivals_are_time_ordered_and_bounded(self):
        trace = synthesize_trace(30, seed=7, base_rate=3.0)
        ts = [a.arrival_ts for a in trace]
        assert ts == sorted(ts)
        assert all(0 <= t < 30 for t in ts)

    def test_diurnal_wave_modulates_rate(self):
        """Peak half-period vs trough half-period arrival counts must
        reflect the sinusoid (sin > 0 on [0, T/2), < 0 after)."""
        trace = diurnal_trace(200, seed=5, base_rate=4.0,
                              peak_fraction=0.9, period_secs=200)
        peak = sum(1 for a in trace if a.arrival_ts < 100)
        trough = len(trace) - peak
        assert peak > 1.5 * trough, (peak, trough)

    def test_burst_window_is_denser(self):
        trace = burst_trace(60, seed=5, base_rate=1.0,
                            bursts=[(20, 10, 9.0)])
        inside = sum(1 for a in trace if 20 <= a.arrival_ts < 30)
        outside = len(trace) - inside
        # 10s at ~10/s inside vs 50s at ~1/s outside
        assert inside > outside, (inside, outside)

    def test_lengths_are_heavy_tailed(self):
        trace = synthesize_trace(300, seed=9, base_rate=3.0,
                                 prompt_len_mean=32, prompt_len_sigma=1.0,
                                 prompt_len_max=4096)
        lens = sorted(a.prompt_len for a in trace)
        median = lens[len(lens) // 2]
        assert lens[-1] > 4 * median  # a real tail, not a clipped bump
        assert all(a.max_new_tokens >= 1 for a in trace)

    def test_tenant_mix_shares_prefixes(self):
        trace = synthesize_trace(100, seed=13, base_rate=3.0, tenants=3,
                                 shared_fraction=0.7, shared_prefix_len=16,
                                 prompt_len_mean=64)
        shared = [a for a in trace if a.tenant]
        assert shared and len(shared) < len(trace)
        assert all(a.prefix_len == 16 for a in shared)
        assert all(a.prompt_len > a.prefix_len for a in shared)
        # Zipf skew: the hottest tenant dominates
        counts = {}
        for a in shared:
            counts[a.tenant] = counts.get(a.tenant, 0) + 1
        assert counts["t1"] == max(counts.values())

    def test_validation(self):
        with pytest.raises(ValueError):
            synthesize_trace(0, seed=0, base_rate=1.0)
        with pytest.raises(ValueError):
            synthesize_trace(10, seed=0, base_rate=0)
        with pytest.raises(ValueError):
            synthesize_trace(10, seed=0, base_rate=1.0,
                             diurnal_fraction=1.5)

    def test_sampled_fraction_arrivals_carry_keyed_fields(self, tmp_path):
        """``sampled_fraction`` marks that share of arrivals with
        keyed-sampling fields: a per-arrival seed (each its own stream)
        plus the shared knobs — and the JSONL round trip keeps them."""
        trace = synthesize_trace(60, seed=17, base_rate=2.0,
                                 sampled_fraction=0.5, temperature=0.8,
                                 top_p=0.9)
        sampled = [a for a in trace if a.do_sample]
        greedy = [a for a in trace if not a.do_sample]
        assert sampled and greedy          # really a mix
        assert all(a.seed > 0 for a in sampled)
        assert len({a.seed for a in sampled}) == len(sampled)
        assert all(a.temperature == 0.8 and a.top_p == 0.9
                   for a in sampled)
        # greedy arrivals carry NO sampling noise
        assert all(a.seed == 0 and a.temperature == 0.0 for a in greedy)
        path = str(tmp_path / "sampled.jsonl")
        save_trace(path, trace)
        assert load_trace(path) == trace
        # the JSONL stays open-format: greedy rows have no sampling keys
        # at all, so pre-sampling consumers parse the file unchanged
        with open(path) as f:
            rows = [json.loads(line) for line in f]
        assert all("do_sample" not in r and "seed" not in r
                   for r, a in zip(rows, trace) if not a.do_sample)

    def test_sampled_fraction_zero_is_bit_identical_to_legacy(self):
        """The no-extra-rng-draws guarantee: at ``sampled_fraction=0``
        the generator's draw sequence is untouched, so the trace is
        bit-identical to one synthesized without the knob."""
        kw = dict(seed=11, base_rate=2.0, tenants=2, shared_fraction=0.5,
                  shared_prefix_len=4)
        legacy = synthesize_trace(30, **kw)
        assert synthesize_trace(30, sampled_fraction=0.0,
                                temperature=0.8, **kw) == legacy


# ---------------------------------------------------------------------------
# replayer
# ---------------------------------------------------------------------------
class TestTraceReplayer:
    def test_prompt_synthesis_shares_tenant_prefixes(self):
        clock = ReplayClock()
        router = ReplicaRouter([FakeReplica()], clock=clock,
                               telemetry=FakeTelemetry())
        rep = TraceReplayer(router, [], clock, seed=4)
        a1 = Arrival(0.0, 10, 4, tenant="tA", prefix_len=6)
        a2 = Arrival(1.0, 12, 4, tenant="tA", prefix_len=6)
        b = Arrival(2.0, 10, 4, tenant="tB", prefix_len=6)
        p1, p2, p3 = (rep.prompt_for(a1, 0), rep.prompt_for(a2, 1),
                      rep.prompt_for(b, 2))
        assert p1[:6] == p2[:6]          # same tenant: shared prefix
        assert p1[:6] != p3[:6]          # different tenant: different
        assert p1[6:] != p2[6:]          # tails unique per arrival
        assert len(p1) == 10 and len(p2) == 12
        # same seed, fresh replayer: bit-identical synthesis (the
        # cross-process determinism contract — no salted hash())
        rep2 = TraceReplayer(router, [], clock, seed=4)
        assert rep2.prompt_for(a1, 0) == p1

    def test_replay_drives_router_and_reports(self):
        trace = synthesize_trace(10, seed=2, base_rate=1.0,
                                 prompt_len_mean=4, prompt_len_max=8,
                                 gen_mean=3, gen_max=4)
        clock = ReplayClock()
        router = ReplicaRouter([FakeReplica(slots=4)], clock=clock,
                               telemetry=FakeTelemetry())
        rep = TraceReplayer(router, trace, clock, step_secs=0.25, seed=2)
        out = rep.run()
        assert out["requests"] == len(trace)
        assert out["finished"] == len(trace) and out["shed"] == 0
        assert out["incomplete"] == 0
        assert out["tokens_out"] > 0 and out["tokens_per_sim_sec"] > 0
        assert out["ttft_ms_p95"] is not None
        assert rep.handles[0].tokens[0] == _greedy(
            rep.prompt_for(trace[0], 0), 0)

    def test_replay_is_faster_than_real_time(self):
        """A 1000-simulated-second trace must replay in well under a
        second of wall time — the whole point of the fake clock."""
        import time as wall

        trace = synthesize_trace(1000, seed=2, base_rate=0.05,
                                 gen_mean=2, gen_max=2)
        clock = ReplayClock()
        router = ReplicaRouter([FakeReplica(slots=4)], clock=clock,
                               telemetry=FakeTelemetry())
        t0 = wall.monotonic()
        out = TraceReplayer(router, trace, clock, step_secs=1.0,
                            seed=0).run()
        assert wall.monotonic() - t0 < 5.0
        assert out["sim_secs"] >= trace[-1].arrival_ts  # replayed it all
        assert out["finished"] == len(trace)

    def test_slo_attainment_counts_sheds_as_misses(self):
        clock = ReplayClock()
        router = ReplicaRouter([FakeReplica(slots=1, queue_cap=1)],
                               clock=clock, telemetry=FakeTelemetry())
        trace = [Arrival(0.0, 4, 4) for _ in range(8)]  # storm at t=0
        rep = TraceReplayer(router, trace, clock, step_secs=0.5, seed=1)
        rep.run()
        out = rep.report(slo={"ttft_ms_p95": 1e9, "shed_rate": 0.0})
        assert out["shed"] > 0
        assert out["slo_attainment"] < 1.0
        assert out["slo_ok"] is False

    def test_max_steps_bounds_a_wedged_target(self):
        clock = ReplayClock()
        router = ReplicaRouter([StuckReplica()], clock=clock,
                               telemetry=FakeTelemetry())
        rep = TraceReplayer(router, [Arrival(0.0, 4, 4)], clock,
                            step_secs=0.5, max_steps=25)
        out = rep.run()
        assert rep.steps == 25 and out["incomplete"] == 1

    def test_sampled_arrivals_thread_seed_and_split_report(self):
        """Sampled arrivals replay with their seed/knobs threaded to the
        replica, and ``report()`` splits SLO attainment into sampled vs
        greedy populations so the keyed-decode overhead cannot hide in
        the aggregate."""

        class WideReplica(FakeReplica):
            """FakeReplica with the sampling-aware submit surface the
            router forwards keyed kwargs through."""

            def __init__(self, **kw):
                super().__init__(**kw)
                self.samp_seen = []

            def submit(self, prompt, max_new_tokens=0, request_id=None,
                       eos_token_id=-1, deadline_ms=0.0, stream=None,
                       do_sample=False, seed=None, temperature=None,
                       top_k=None, top_p=None):
                if do_sample:
                    self.samp_seen.append(
                        {"seed": seed, "temperature": temperature,
                         "top_p": top_p})
                return super().submit(prompt,
                                      max_new_tokens=max_new_tokens,
                                      request_id=request_id,
                                      eos_token_id=eos_token_id,
                                      deadline_ms=deadline_ms,
                                      stream=stream)

        trace = [Arrival(0.0, 4, 3, do_sample=True, seed=101,
                         temperature=0.8, top_p=0.9),
                 Arrival(0.5, 5, 3),
                 Arrival(1.0, 4, 3, do_sample=True, seed=202),
                 Arrival(1.5, 6, 3)]
        clock = ReplayClock()
        replica = WideReplica(slots=4)
        router = ReplicaRouter([replica], clock=clock,
                               telemetry=FakeTelemetry())
        rep = TraceReplayer(router, trace, clock, step_secs=0.25, seed=3)
        out = rep.run()
        assert out["finished"] == 4 and out["shed"] == 0
        # the per-arrival seeds arrived verbatim, in arrival order
        assert replica.samp_seen == [
            {"seed": 101, "temperature": 0.8, "top_p": 0.9},
            {"seed": 202, "temperature": None, "top_p": None}]
        split = out["sampling"]
        assert split["sampled"]["requests"] == 2
        assert split["greedy"]["requests"] == 2
        assert split["sampled"]["finished"] == 2
        assert split["greedy"]["ttft_ms_p95"] is not None
        # a greedy-only replay carries no sampling block at all — the
        # report shape is unchanged for pre-sampling consumers
        clock2 = ReplayClock()
        router2 = ReplicaRouter([WideReplica(slots=4)], clock=clock2,
                                telemetry=FakeTelemetry())
        out2 = TraceReplayer(router2, [Arrival(0.0, 4, 3)], clock2,
                             step_secs=0.25, seed=3).run()
        assert "sampling" not in out2

    def test_replay_config_defaults_flow(self):
        cfg = ReplayConfig(step_secs=0.5, seed=7, vocab_size=50,
                           max_steps=3)
        clock = ReplayClock()
        router = ReplicaRouter([FakeReplica()], clock=clock,
                               telemetry=FakeTelemetry())
        rep = TraceReplayer(router, [], clock, config=cfg)
        assert (rep.step_secs, rep.seed, rep.vocab, rep.max_steps) \
            == (0.5, 7, 50, 3)
        with pytest.raises(ValueError):
            ReplayConfig(step_secs=0)


# ---------------------------------------------------------------------------
# capacity model
# ---------------------------------------------------------------------------
class TestCapacityModel:
    def _loaded(self):
        m = CapacityModel(n_buckets=8, max_load=2.0)
        # low load: fast + modest throughput; high load: slow + saturated
        for _ in range(50):
            m.observe(0.3, ttft_ms=50, queue_ms=5, tokens=4, secs=1.0)
            m.observe(1.1, ttft_ms=400, queue_ms=200, tokens=8, secs=1.0)
            m.observe(1.9, ttft_ms=3000, queue_ms=2500, tokens=8.5,
                      secs=1.0)
        return m

    def test_curves_rise_with_load(self):
        m = self._loaded()
        assert m.ttft_p95_at(0.3) < m.ttft_p95_at(1.1) \
            < m.ttft_p95_at(1.9)
        assert m.queue_p95_at(0.3) < m.queue_p95_at(1.9)
        curve = m.curve()
        assert len(curve) == 3
        assert all({"load", "ttft_ms_p95", "tokens_per_sec"} <= set(r)
                   for r in curve)

    def test_sustainable_rate_respects_slo(self):
        m = self._loaded()
        # at a 512ms TTFT SLO the 1.9-load bucket (p95 ~3000ms) is out:
        # the sustainable rate is the 1.1-load bucket's 8 tok/s
        assert m.sustainable_tokens_per_sec(512) == pytest.approx(8.0)
        # unconstrained: the fastest bucket wins regardless of latency
        assert m.sustainable_tokens_per_sec() == pytest.approx(8.5)
        # an impossibly tight SLO only the idle bucket meets
        assert m.sustainable_tokens_per_sec(64) == pytest.approx(4.0)

    def test_fleet_size_for_is_ceil_and_clamped(self):
        m = self._loaded()
        slo = {"ttft_p95_ms": 512}
        assert m.fleet_size_for(8.0, slo) == 1
        assert m.fleet_size_for(8.1, slo) == 2     # ceil, not round
        assert m.fleet_size_for(33, slo) == 5
        assert m.fleet_size_for(33, slo, max_size=4) == 4
        assert m.fleet_size_for(0.1, slo, min_size=2) == 2

    def test_no_evidence_answers_the_floor(self):
        m = CapacityModel()
        assert m.fleet_size_for(1e6, {"ttft_p95_ms": 1}, min_size=3) == 3

    def test_merge_combines_histograms_and_throughput(self):
        a, b = CapacityModel(), CapacityModel()
        a.observe(0.5, ttft_ms=100, tokens=5, secs=1.0)
        b.observe(0.5, ttft_ms=900, tokens=15, secs=1.0)
        a.merge(b)
        assert a.ttft_p95_at(0.5) >= 900  # b's tail is in the merge
        assert a.throughput_at(0.5) == pytest.approx(10.0)
        with pytest.raises(ValueError):
            a.merge(CapacityModel(n_buckets=3))

    def test_fit_from_event_stream(self):
        """The offline path: step.gauges give per-step load, serving
        request.finish records give latencies/throughput, span queue
        legs add queue-wait observations."""
        events = []
        for step, (busy, depth) in enumerate([(1, 0), (2, 6), (2, 6)]):
            events.append({"kind": "serving", "name": "step.gauges",
                           "step": step,
                           "data": {"slots_busy": busy,
                                    "queue_depth": depth,
                                    "slots_total": 2}})
        events.append({"kind": "serving", "name": "request.finish",
                       "step": 0, "data": {"ttft_ms": 40, "queue_ms": 2,
                                           "new_tokens": 8,
                                           "tokens_per_sec": 16.0}})
        events.append({"kind": "serving", "name": "request.finish",
                       "step": 2, "data": {"ttft_ms": 800,
                                           "queue_ms": 600,
                                           "new_tokens": 8,
                                           "tokens_per_sec": 4.0}})
        events.append({"kind": "span", "name": "queue",
                       "data": {"step": 2, "start_ns": 0,
                                "end_ns": int(5e8)}})
        m = CapacityModel(n_buckets=8, max_load=4.0)
        assert m.fit_events(events) == 3
        assert m.ttft_p95_at(0.5) == pytest.approx(40, rel=0.7)
        assert m.ttft_p95_at(4.0) >= 800
        assert m.queue_p95_at(4.0) >= 500
        # no gauges at all: nothing to attribute against
        assert CapacityModel().fit_events(
            [{"kind": "serving", "name": "request.finish", "step": 1,
              "data": {"ttft_ms": 1}}]) == 0

    def test_validation(self):
        with pytest.raises(ValueError):
            CapacityModel(n_buckets=0)


# ---------------------------------------------------------------------------
# error budgets + autoscaler policy
# ---------------------------------------------------------------------------
class TestBudgetWindow:
    def test_burn_rate_is_observed_over_allowed(self):
        w = BudgetWindow(4, allowed_rate=0.1)
        assert w.burn_rate() is None          # no evidence yet
        w.observe(9, 1)                       # 10% shed at 10% allowed
        assert w.burn_rate() == pytest.approx(1.0)
        assert w.remaining() == 0.0
        w.observe(10, 0)
        assert w.burn_rate() == pytest.approx(0.5)
        assert w.remaining() == 0.5

    def test_window_slides(self):
        w = BudgetWindow(2, allowed_rate=0.5)
        w.observe(0, 10)
        w.observe(10, 0)
        w.observe(10, 0)                      # the bad step aged out
        assert w.burn_rate() == 0.0

    def test_zero_allowed_is_infinite_burn_not_crash(self):
        w = BudgetWindow(4, allowed_rate=0.0)
        w.observe(5, 0)
        assert w.burn_rate() == 0.0
        w.observe(5, 1)
        assert w.burn_rate() == float("inf")
        assert w.remaining() == 0.0


class TestAutoscalerPolicy:
    def _scaler(self, **over):
        cfg = dict(min_replicas=1, max_replicas=4,
                   target_ttft_p95_ms=100.0, target_shed_rate=0.1,
                   fast_window_steps=4, slow_window_steps=16,
                   scale_up_cooldown_steps=2,
                   scale_down_cooldown_steps=4,
                   scale_down_quiet_steps=3)
        cfg.update(over)
        return Autoscaler(FleetConfig(**cfg))

    def test_ttft_burn_triggers_scale_up(self):
        a = self._scaler()
        # >5% of requests over the p95 target: budget burns at rate > 1
        a.observe_requests([{"state": "finished", "ttft_ms": 500}] * 2
                           + [{"state": "finished", "ttft_ms": 10}] * 8)
        a.observe_step(overload=0.0)
        d = a.decide(1)
        assert d is not None and d.action == SCALE_UP
        assert d.reason == "ttft_burn" and d.burn > 1.0

    def test_shed_burn_triggers_scale_up(self):
        a = self._scaler()
        a.observe_requests([{"state": "shed"}] * 3
                           + [{"state": "finished", "ttft_ms": 1}] * 7)
        a.observe_step(overload=0.0)
        d = a.decide(1)
        assert d is not None and (d.action, d.reason) \
            == (SCALE_UP, "shed_burn")

    def test_load_triggers_scale_up_before_any_burn(self):
        a = self._scaler()
        a.observe_step(overload=0.95)
        d = a.decide(1)
        assert d is not None and (d.action, d.reason) == (SCALE_UP, "load")

    def test_cooldown_blocks_back_to_back_ups(self):
        a = self._scaler(scale_up_cooldown_steps=3)
        a.observe_step(overload=0.95)
        assert a.decide(1).action == SCALE_UP
        a.observe_step(overload=0.95)
        assert a.decide(2) is None            # cooling down
        a.observe_step(overload=0.95)
        a.observe_step(overload=0.95)
        assert a.decide(2).action == SCALE_UP

    def test_max_fleet_clamps(self):
        a = self._scaler()
        a.observe_step(overload=0.95)
        assert a.decide(4) is None            # already at max_replicas

    def test_scale_down_needs_consecutive_quiet(self):
        a = self._scaler(scale_down_quiet_steps=3,
                         scale_down_cooldown_steps=1)
        a.observe_step(overload=0.0)
        a.observe_step(overload=0.0)
        assert a.decide(2) is None            # only 2 quiet steps
        a.observe_step(overload=0.9)          # spike resets the streak
        a.observe_step(overload=0.0)
        a.observe_step(overload=0.0)
        assert a.decide(2) is None
        a.observe_step(overload=0.0)
        d = a.decide(2)
        assert d is not None and (d.action, d.reason) \
            == (SCALE_DOWN, "quiet")

    def test_min_fleet_clamps(self):
        a = self._scaler(scale_down_quiet_steps=1,
                         scale_down_cooldown_steps=1)
        a.observe_step(overload=0.0)
        assert a.decide(1) is None            # already at min_replicas

    def test_budget_remaining_reports_enabled_budgets(self):
        a = self._scaler()
        a.observe_requests([{"state": "finished", "ttft_ms": 1}] * 10)
        a.observe_step(overload=0.0)
        rem = a.budget_remaining()
        assert rem == {"ttft": 1.0, "shed": 1.0}
        off = Autoscaler(FleetConfig())       # both budgets off
        assert off.budget_remaining() == {}

    def test_config_validation(self):
        with pytest.raises(ValueError):
            FleetConfig(min_replicas=3, max_replicas=2)
        with pytest.raises(ValueError):
            FleetConfig(scale_down_load=0.9, scale_up_load=0.8)
        with pytest.raises(ValueError):
            FleetConfig(fast_window_steps=0)
        with pytest.raises(ValueError):
            ServingConfig(fleet={"min_replicas": 1})  # fleet sans router
        ServingConfig(router={"replicas": 2}, fleet={"min_replicas": 1})
        ServingConfig(fleet={"enabled": False})       # off switch is fine


# ---------------------------------------------------------------------------
# satellite: drain/reactivate hardening
# ---------------------------------------------------------------------------
class TestDrainReactivateHardening:
    def test_start_drain_is_idempotent(self):
        telem = FakeTelemetry()
        clock = ReplayClock()
        router = ReplicaRouter([FakeReplica(), FakeReplica()],
                               clock=clock, telemetry=telem)
        router.submit([1, 2], max_new_tokens=3)
        router.start_drain(0)
        states = len(telem.of("replica.state"))
        router.start_drain(0)                 # second call: no-op
        router.start_drain(0)
        assert router.health[0].state == DRAINING
        assert len(telem.of("replica.state")) == states  # no new events
        router.drain(max_steps=10)
        assert telem.of("replica.drained")

    def test_start_drain_does_not_clear_probe_bookkeeping(self):
        """A repeated drain call on an already-DRAINING replica must not
        touch the probe registry either (the bookkeeping-reset bug)."""
        clock = ReplayClock()
        router = ReplicaRouter([FakeReplica(), FakeReplica()],
                               clock=clock, telemetry=FakeTelemetry())
        router.start_drain(0)
        router._probe_req[1] = "sentinel"     # unrelated replica's probe
        router.start_drain(0)
        assert router._probe_req == {1: "sentinel"}

    def test_start_drain_on_dead_does_not_resurrect(self):
        clock = ReplayClock()
        router = ReplicaRouter([FakeReplica(), FakeReplica()],
                               clock=clock, telemetry=FakeTelemetry())
        router.health[0].record_crash("crash")
        router.start_drain(0)
        assert router.health[0].state == DEAD

    def test_reactivate_live_replica_raises(self):
        router = ReplicaRouter([FakeReplica(), FakeReplica()],
                               clock=ReplayClock(),
                               telemetry=FakeTelemetry())
        with pytest.raises(ValueError, match="is live"):
            router.reactivate(0)
        with pytest.raises(ValueError, match="start_drain"):
            router.reactivate(0, replica=FakeReplica())
        # the engine was NOT swapped
        assert isinstance(router.replicas[0], FakeReplica)

    def test_reactivate_drained_and_dead_still_work(self):
        router = ReplicaRouter([FakeReplica(), FakeReplica()],
                               clock=ReplayClock(),
                               telemetry=FakeTelemetry())
        router.start_drain(0)
        router.reactivate(0)
        assert router.health[0].state == HEALTHY
        router.health[1].record_crash("crash")
        fresh = FakeReplica()
        router.reactivate(1, replica=fresh)
        assert router.replicas[1] is fresh
        assert router.health[1].state == HEALTHY


# ---------------------------------------------------------------------------
# satellite: merged fleet view (gauges + stats + report section)
# ---------------------------------------------------------------------------
class TestFleetGauges:
    def test_router_fleet_gauges_merge_states_and_queues(self):
        router = ReplicaRouter(
            [FakeReplica(), GaugeStub(depth=5, cap=10), FakeReplica()],
            clock=ReplayClock(), telemetry=FakeTelemetry())
        router.start_drain(2)
        g = router.fleet_gauges()
        assert g["replicas"] == 3 and g["routable"] == 2
        assert g["by_state"][HEALTHY] == 2
        assert g["by_state"][DRAINING] == 1
        assert g["queue_depth"] == 5
        assert g["queue_capacity"] == 10 + 2 * 8
        assert g["slots_total"] == 6
        assert 0.0 <= g["overload"] <= 1.0

    def test_fleet_manager_stats_and_gauge_event(self):
        telem = FakeTelemetry()
        fm, _ = _fleet([FakeReplica(), FakeReplica()], telemetry=telem,
                       target_ttft_p95_ms=100.0, target_shed_rate=0.1)
        fm.submit([1, 2], max_new_tokens=2)
        fm.step()
        st = fm.stats()
        assert st["active"] == 2 and st["parked"] == 0
        assert st["min_replicas"] == 1 and st["max_replicas"] == 4
        assert set(st["budget_remaining"]) == {"ttft", "shed"}
        assert {"scale_ups", "scale_downs", "parks", "factory_builds",
                "drains_lost"} <= set(st)
        assert st["router"]["finished"] >= 0
        gauges = telem.of("fleet.gauges", kind="fleet")
        assert gauges, "no fleet.gauges event on the stream"
        assert {"by_state", "active", "parked", "budget_remaining",
                "queue_depth", "overload"} <= set(gauges[-1]["data"])

    def test_report_renders_fleet_section(self, tmp_path):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import telemetry_report
        finally:
            sys.path.pop(0)
        telem = FakeTelemetry()
        fm, clock = _fleet(
            [GaugeStub(depth=9, cap=10)], telemetry=telem,
            factory=CallableReplicaFactory(FakeReplica),
            scale_up_cooldown_steps=1, target_shed_rate=0.1)
        fm.submit([1, 2], max_new_tokens=2)
        fm.drain(max_steps=20)
        path = tmp_path / "telemetry.jsonl"
        with open(path, "w") as f:
            for e in telem.events:
                f.write(json.dumps({
                    "ts": 0, "kind": e["kind"], "name": e["name"],
                    "step": e["step"], "rank": 0, "data": e["data"]},
                    default=str) + "\n")
        for markdown in (False, True):
            text = telemetry_report.render(str(path), markdown=markdown)
            assert "fleet:" in text and "scale-up" in text
            assert "SLO budget remaining" in text
        agg = telemetry_report.aggregate(
            telemetry_report.load_all_events(str(path)))
        assert agg["fleet"]["scale_ups"] >= 1
        assert agg["fleet"]["decisions"]
        assert json.dumps(agg, default=str)   # --json payload is safe


# ---------------------------------------------------------------------------
# fleet manager mechanics
# ---------------------------------------------------------------------------
class TestFleetManagerMechanics:
    def test_scale_down_drains_then_parks_then_warm_unpark(self):
        telem = FakeTelemetry()
        fm, _ = _fleet([FakeReplica(), FakeReplica()], telemetry=telem)
        r = fm.submit([1, 2], max_new_tokens=3)
        assert r.replica == 0
        assert fm.scale_down(1) is not None
        assert fm.scale_down(1) is None       # idempotent
        fm.drain(max_steps=10)
        assert r.state == rq.FINISHED
        st = fm.stats()
        assert st["parked"] == 1 and st["active"] == 1
        assert telem.of("replica.parked", kind="fleet")
        parked_engine = fm.router.replicas[1]
        # warm scale-up: the SAME engine object returns, no factory
        detail = fm.scale_up()
        assert detail == {"source": "parked", "replica": 1, "warm": True}
        assert fm.router.replicas[1] is parked_engine
        assert fm.router.health[1].state == HEALTHY
        assert fm.stats()["unparks"] == 1

    def test_factory_scale_up_appends_replica(self):
        telem = FakeTelemetry()
        built = []

        def build():
            rep = FakeReplica()
            built.append(rep)
            return rep

        fm, _ = _fleet([FakeReplica()], telemetry=telem,
                       factory=CallableReplicaFactory(build, warm=True))
        detail = fm.scale_up()
        assert detail["source"] == "factory" and detail["warm"] is True
        assert len(fm.router.replicas) == 2
        assert fm.router.replicas[1] is built[0]
        assert fm.active_size == 2
        assert telem.of("replica.added", kind="router")
        # the new replica takes traffic immediately: replica 0 now has
        # queued work, so least-loaded routing picks the fresh one
        fm.submit([1, 2], max_new_tokens=2)
        r = fm.submit([9], max_new_tokens=2)
        assert r.replica == 1

    def test_scale_up_without_factory_is_blocked_loudly(self):
        telem = FakeTelemetry()
        fm, _ = _fleet([FakeReplica()], telemetry=telem)
        assert fm.scale_up() is None
        assert fm.stats()["scale_ups"] == 0

    def test_factory_replaces_dead_slot_before_appending(self):
        fm, _ = _fleet([FakeReplica(), FakeReplica()],
                       factory=CallableReplicaFactory(FakeReplica))
        fm.router.health[1].record_crash("crash")
        detail = fm.scale_up()
        assert detail["source"] == "factory" and detail["replica"] == 1
        assert detail.get("replaced_dead") is True
        assert len(fm.router.replicas) == 2   # no blind growth
        assert fm.router.health[1].state == HEALTHY

    def test_submit_time_sheds_feed_the_budget(self):
        fm, _ = _fleet([FakeReplica(slots=1, queue_cap=1)],
                       target_shed_rate=0.5, fast_window_steps=2)
        for _ in range(6):
            fm.submit([1], max_new_tokens=2)
        fm.step()
        assert fm.autoscaler._shed_fast.rate > 0.5

    def test_max_replicas_is_a_hard_ceiling_after_recovery(self):
        """Breaker recovery can push the routable count past the bound
        (a scale-up replaced tripped replicas that later probed back):
        the fleet drains the excess instead of holding it forever."""
        telem = FakeTelemetry()
        fm, _ = _fleet([FakeReplica(), FakeReplica(), FakeReplica()],
                       telemetry=telem, max_replicas=2,
                       scale_down_quiet_steps=64)  # quiet gate can't fire
        assert fm.active_size == 3
        for _ in range(10):
            fm.step()
            if fm.active_size <= 2 and not fm._draining:
                break
        assert fm.active_size == 2
        downs = [e for e in telem.events if e["kind"] == "fleet"
                 and e["name"] == "scale.down"]
        assert downs and downs[0]["data"]["reason"] == "max_replicas"

    def test_routable_load_excludes_parked_slots(self):
        """The capacity model's load denominator counts ROUTABLE slots
        only — a parked replica's idle slots must not dilute a
        saturated survivor's load bucket."""
        dial = GaugeStub(depth=2, cap=10)
        fm, _ = _fleet([dial, FakeReplica()])
        fm.scale_down(1)
        fm.step()                             # empty replica parks
        assert fm.stats()["parked"] == 1
        # routable: dial only — (0 busy + 2 queued) / 2 slots = 1.0;
        # the all-alive fleet view would have said (0+2)/4 = 0.5
        assert fm._routable_load() == pytest.approx(1.0)
        assert fm.router.fleet_gauges()["slots_total"] == 4

    def test_yield_work_sheds_reach_step_result_and_budget(self):
        """A drain-timeout yield whose survivor rejects the work sheds
        it AFTER the router's step snapshot — the fleet must still
        return it from step() and feed the shed budget (the overload
        shed it exists to catch). The survivor fakes healthy gauges
        (low overload: the autoscaler must not rescue the drain) but
        admits nothing."""
        full = GaugeStub(depth=0, cap=10, queue_cap=0)  # sheds all work
        fm, _ = _fleet([StuckReplica(), full], drain_timeout_steps=2,
                       target_shed_rate=0.1, fast_window_steps=4,
                       router_cfg={"max_failovers": 1})
        r = fm.submit([1, 2], max_new_tokens=3)
        assert r.replica == 0                 # stuck replica holds it
        fm.scale_down(0)
        done = []
        for _ in range(6):
            done.extend(fm.step())
            if r.done:
                break
        assert r.state == rq.SHED and r.finish_reason == "queue_full"
        assert r in done                      # visible to drain() callers
        assert fm.autoscaler._shed_fast.rate > 0  # budget saw it
        assert fm.stats()["drain_timeouts"] == 1

    def test_prebuilt_replicas_honor_engine_carried_fleet_block(self):
        """Mirror of the router-block fallback: prebuilt replicas whose
        own serving config carries router+fleet must come back as a
        FleetManager, not silently as a static router."""
        import deepspeed_tpu

        carried = ServingConfig(router={"replicas": 2},
                                fleet={"min_replicas": 1,
                                       "max_replicas": 3})
        a, b = FakeReplica(), FakeReplica()
        a.config = b.config = carried
        fm = deepspeed_tpu.init_serving(None, replicas=[a, b])
        assert isinstance(fm, FleetManager)
        assert fm.config.max_replicas == 3
        # explicit caller block still wins over the carried one
        fm2 = deepspeed_tpu.init_serving(
            None, replicas=[a, b],
            serving={"router": {"replicas": 2},
                     "fleet": {"min_replicas": 1, "max_replicas": 5}})
        assert fm2.config.max_replicas == 5
        # carried fleet with enabled=false stays a plain router
        off = ServingConfig(router={"replicas": 2},
                            fleet={"enabled": False})
        c, d = FakeReplica(), FakeReplica()
        c.config = d.config = off
        assert isinstance(deepspeed_tpu.init_serving(None,
                                                     replicas=[c, d]),
                          ReplicaRouter)

    def test_autoscale_span_on_trace_stream(self):
        from deepspeed_tpu.telemetry.tracing import Tracer

        telem = FakeTelemetry()
        telem.tracer = Tracer(emit=telem.emit)
        fm, _ = _fleet([GaugeStub(depth=9, cap=10)], telemetry=telem,
                       factory=CallableReplicaFactory(FakeReplica),
                       scale_up_cooldown_steps=1)
        fm.submit([1], max_new_tokens=2)
        fm.drain(max_steps=10)
        spans = [e for e in telem.events if e["kind"] == "span"
                 and e["name"] == "autoscale"]
        assert spans, "no autoscale span emitted"
        d = spans[0]["data"]
        assert d["action"] == "up" and d["to_size"] == d["from_size"] + 1
        assert d["trace"].endswith("fleet")


# ---------------------------------------------------------------------------
# acceptance: seeded diurnal+burst trace, autoscaled vs static minimum
# ---------------------------------------------------------------------------
FLEET_CFG = {"min_replicas": 1, "max_replicas": 4,
             "target_ttft_p95_ms": 1000.0, "target_shed_rate": 0.02,
             "fast_window_steps": 6, "slow_window_steps": 40,
             "burn_rate_fast": 1.0, "scale_up_load": 0.6,
             "scale_up_cooldown_steps": 2,
             "scale_down_cooldown_steps": 8,
             "scale_down_quiet_steps": 10}


def _acceptance_trace():
    """Diurnal base + two bursts: the first forces cold factory builds,
    the trough between them forces drains/parks, the second proves warm
    unparks."""
    return synthesize_trace(60, seed=5, base_rate=0.8,
                            diurnal_fraction=0.3, diurnal_period_secs=60,
                            bursts=[(10, 8, 5.0), (38, 8, 5.0)],
                            prompt_len_mean=5, prompt_len_max=8,
                            gen_mean=4, gen_sigma=0.3, gen_max=6)


def _run_leg(trace, autoscale, telemetry=None, capacity=None):
    clock = ReplayClock()
    telemetry = telemetry or FakeTelemetry()
    router = ReplicaRouter([FakeReplica()],
                           config={"failure_threshold": 3},
                           clock=clock, telemetry=telemetry)
    if autoscale:
        target = FleetManager(
            router, factory=CallableReplicaFactory(FakeReplica),
            config=FLEET_CFG, capacity=capacity)
    else:
        target = router
    rep = TraceReplayer(target, trace, clock, step_secs=0.25, seed=9,
                        max_steps=5000)
    out = rep.run()
    return target, rep, out


class TestFleetAcceptance:
    def test_autoscaled_beats_static_minimum_on_both_slo_axes(self):
        trace = _acceptance_trace()
        _, _, static = _run_leg(trace, autoscale=False)
        telem = FakeTelemetry()
        capacity = CapacityModel()
        fm, rep, auto = _run_leg(trace, autoscale=True, telemetry=telem,
                                 capacity=capacity)
        # the static minimum fleet visibly violates the SLO...
        assert static["shed_rate"] > 0.1
        assert static["ttft_ms_p95"] > FLEET_CFG["target_ttft_p95_ms"]
        # ...and the autoscaled fleet is STRICTLY better on both axes
        assert auto["shed_rate"] < static["shed_rate"]
        assert auto["ttft_ms_p95"] < static["ttft_ms_p95"]
        assert auto["finished"] > static["finished"]
        st = fm.stats()
        # scaled up (cold factory first, warm parked engines on the
        # second burst) and back down via drains
        assert st["factory_builds"] >= 1
        assert st["unparks"] >= 1
        assert st["scale_downs"] >= 1 and st["parks"] >= 1
        scale_events = [e for e in telem.events if e["kind"] == "fleet"
                        and e["name"].startswith("scale.")]
        sources = [e["data"].get("source") for e in scale_events
                   if e["name"] == "scale.up"]
        assert "factory" in sources and "parked" in sources
        warm = [e["data"] for e in scale_events
                if e["data"].get("source") == "parked"]
        assert all(d["warm"] for d in warm)
        # the capacity model fitted real curves during the replay and
        # sizes the burst load above one replica
        assert capacity.curve()
        burst_load = 5.8 * 4.5    # req/s * mean tokens/req, roughly
        assert capacity.fleet_size_for(
            burst_load, {"ttft_p95_ms": 1000.0}, max_size=8) >= 2

    def test_whole_run_is_deterministic(self):
        """Same trace + same seeds + fake clocks: two fleet runs emit
        bit-identical reports, scale sequences and token streams."""
        trace = _acceptance_trace()
        legs = []
        for _ in range(2):
            telem = FakeTelemetry()
            fm, rep, out = _run_leg(trace, autoscale=True,
                                    telemetry=telem)
            scale_seq = [(e["name"], e["data"].get("source"),
                          e["data"].get("from_size"),
                          e["data"].get("to_size"))
                         for e in telem.events if e["kind"] == "fleet"
                         and e["name"].startswith("scale.")]
            tokens = {h.request_id: list(h.tokens) for h in rep.handles}
            legs.append((out, scale_seq, tokens,
                         {k: fm.stats()[k] for k in
                          ("scale_ups", "scale_downs", "parks",
                           "unparks", "factory_builds")}))
        assert legs[0] == legs[1]

    def test_every_finished_stream_is_greedy_exact(self):
        """Scaling actions never touch token delivery: every finished
        request's stream is the deterministic greedy continuation of its
        prompt, each position exactly once."""
        trace = _acceptance_trace()
        fm, rep, out = _run_leg(trace, autoscale=True)
        assert out["finished"] > 0
        for i, h in enumerate(rep.handles):
            if h.state != rq.FINISHED:
                continue
            prompt = rep.prompt_for(trace[i], i)
            assert h.tokens == [_greedy(prompt, p)
                                for p in range(len(h.tokens))]
        assert fm.router.stats()["replay_divergence"] == 0


# ---------------------------------------------------------------------------
# chaos during scaling
# ---------------------------------------------------------------------------
class TestChaosDuringScaling:
    def test_replica_killed_mid_drain_hands_work_over_exactly_once(self):
        """The drain victim dies with in-flight work: the router fails
        it over and the client streams stay bit-identical to a clean
        run — each position exactly once — while the fleet accounts the
        slot as lost, not parked. drain() terminates."""
        def run(chaos):
            telem = FakeTelemetry()
            clock = ReplayClock()
            replicas = [FakeReplica(), FakeReplica()]
            if chaos:
                replicas[1] = ChaosReplica(replicas[1], crash_at_step=2)
            router = ReplicaRouter(replicas,
                                   config={"failure_threshold": 3},
                                   clock=clock, telemetry=telem)
            fm = FleetManager(router, config={"min_replicas": 1,
                                              "max_replicas": 2})
            streams = {}
            reqs = []
            for i, (prompt, n) in enumerate([([1, 2], 6), ([3, 4], 6),
                                             ([5], 5)]):
                streams[i] = []
                cb = (lambda ix: lambda r, t, d:
                      streams[ix].append(t))(i)
                reqs.append(fm.submit(prompt, max_new_tokens=n,
                                      stream=cb))
            # make sure replica 1 holds work, then drain it
            assert any(r.replica == 1 for r in reqs)
            fm.scale_down(1)
            done = fm.drain(max_steps=40)
            return fm, telem, reqs, streams, done

        _, _, clean_reqs, clean_streams, _ = run(chaos=False)
        fm, telem, reqs, streams, _ = run(chaos=True)
        assert fm.router.health[1].state == DEAD
        assert fm.router.stats()["failovers"] >= 1
        for i, (req, clean) in enumerate(zip(reqs, clean_reqs)):
            assert req.state == rq.FINISHED, (i, req.finish_reason)
            assert req.tokens == clean.tokens, i
            assert streams[i] == clean_streams[i] == req.tokens, i
        assert fm.router.stats()["replay_divergence"] == 0
        st = fm.stats()
        assert st["drains_lost"] == 1 and st["parks"] == 0
        assert telem.of("drain.lost", kind="fleet")
        assert not fm.pending                 # no deadlock

    def test_flaky_factory_backs_off_exponentially(self):
        """A factory that fails N times: every failure doubles the
        retry distance (the retry_io series), the failures are loud
        fleet events, the budget accounting stays clamped-sane, and the
        fleet eventually scales through the same factory."""
        telem = FakeTelemetry()
        factory = FlakyFactory(CallableReplicaFactory(FakeReplica),
                               fail_times=3)
        fm, _ = _fleet([GaugeStub(depth=9, cap=10)], telemetry=telem,
                       factory=factory, scale_up_cooldown_steps=1,
                       factory_backoff_steps=2,
                       target_shed_rate=0.02, fast_window_steps=4,
                       slow_window_steps=16)
        fm.submit([1], max_new_tokens=2)
        for _ in range(40):
            fm.step()
            if fm.stats()["factory_builds"]:
                break
        st = fm.stats()
        assert factory.failures == 3
        assert st["factory_failures"] == 3
        assert st["factory_builds"] == 1 and st["scale_ups"] == 1
        fails = telem.of("factory.failed", kind="fleet")
        assert len(fails) == 3
        # the published retry schedule doubles: +2, +4, +8 steps
        gaps = [e["data"]["retry_step"] - e["step"] for e in fails]
        assert gaps == [2, 4, 8]
        # budget accounting never goes negative while the factory flaps
        rem = fm.autoscaler.budget_remaining()
        assert all(v is None or v >= 0.0 for v in rem.values())

    def test_burst_during_scale_down_cancels_the_drain(self):
        """Load returns while a replica is draining: scale-up must take
        the cheapest path — reactivate the draining replica in place
        (its work never moved) — not build new capacity."""
        telem = FakeTelemetry()
        dial = GaugeStub(depth=0, cap=10)
        built = []
        fm, _ = _fleet(
            [FakeReplica(), dial], telemetry=telem,
            factory=CallableReplicaFactory(
                lambda: built.append(1) or FakeReplica()),
            scale_up_cooldown_steps=1, scale_down_quiet_steps=2,
            scale_down_cooldown_steps=2)
        r = fm.submit([1, 2], max_new_tokens=8)
        fm.scale_down(0 if r.replica == 0 else 1)
        victim = r.replica
        assert fm.router.health[victim].state == DRAINING
        dial.depth = 9                        # the burst storm arrives
        for _ in range(5):
            fm.step()
            if fm.stats()["drains_cancelled"]:
                break
        st = fm.stats()
        assert st["drains_cancelled"] == 1 and not built
        assert fm.router.health[victim].state == HEALTHY
        ups = [e for e in telem.events if e["kind"] == "fleet"
               and e["name"] == "scale.up"]
        assert ups and ups[0]["data"]["source"] == "cancelled_drain"
        assert r.replica == victim            # work never moved
        fm.drain(max_steps=20)
        assert r.state == rq.FINISHED

    def test_wedged_drain_times_out_instead_of_deadlocking(self):
        """A draining replica that admits work but never finishes it:
        without the timeout, drain() would spin forever. With it, the
        stragglers yield to survivors (exactly once) and the slot parks."""
        telem = FakeTelemetry()
        fm, _ = _fleet([StuckReplica(), FakeReplica()], telemetry=telem,
                       drain_timeout_steps=3)
        streams = []
        r = fm.submit([1, 2], max_new_tokens=3,
                      stream=lambda rr, t, d: streams.append(t))
        assert r.replica == 0                 # stuck replica holds it
        fm.scale_down(0)
        done = fm.drain(max_steps=30)
        assert r.state == rq.FINISHED and r in done
        assert r.attempt == 1 and r.replica == 1
        expected = [_greedy([1, 2], p) for p in range(3)]
        assert r.tokens == expected and streams == expected
        st = fm.stats()
        assert st["drain_timeouts"] == 1 and st["parks"] == 1
        assert telem.of("drain.timeout", kind="fleet")
        assert not fm.pending


# ---------------------------------------------------------------------------
# live KV migration: drain-via-migration + migrate-based rebalance
# ---------------------------------------------------------------------------
class TestFleetMigration:
    """The fleet manager's two migration consumers: scale-down drains
    MOVE in-flight work to survivors (``drain_timeout_steps`` demotes to
    the fallback), and the ``kv_fragmentation`` gauge triggers bounded
    migrate-based rebalance sweeps."""

    @pytest.fixture(autouse=True)
    def _no_chaos_leak(self):
        yield
        chaos.clear()

    def test_drain_migrates_work_then_parks_without_timeout(self):
        telem = FakeTelemetry()
        fm, _ = _fleet([MigratableReplica(), MigratableReplica()],
                       telemetry=telem, migration={"enabled": True},
                       drain_timeout_steps=50)
        streams = []
        r = fm.submit([1, 2], max_new_tokens=6,
                      stream=lambda rr, t, d: streams.append(t))
        assert r.replica == 0
        fm.step()                          # running, one token delivered
        fm.scale_down(0)
        fm.drain(max_steps=30)
        expected = [_greedy([1, 2], p) for p in range(6)]
        assert r.state == rq.FINISHED and r.replica == 1
        # the stream continued mid-sequence on the survivor: each
        # position exactly once, nothing replayed, nothing lost
        assert r.tokens == expected and streams == expected
        st = fm.stats()
        assert st["drain_migrations"] == 1
        assert st["drain_timeouts"] == 0   # the timeout stayed a fallback
        assert st["parks"] == 1            # drained slot parked at once
        assert telem.of("drain.migrated", kind="fleet")
        assert fm.router.stats()["migrations"] == 1

    def test_drain_falls_back_to_timeout_when_move_impossible(self):
        """A draining replica with NO export surface cannot migrate:
        the wedged-drain timeout keeps the scale-down from deadlocking
        exactly as before migration existed."""
        fm, _ = _fleet([StuckReplica(), MigratableReplica()],
                       migration={"enabled": True}, drain_timeout_steps=3)
        r = fm.submit([1, 2], max_new_tokens=3)
        assert r.replica == 0
        fm.scale_down(0)
        fm.drain(max_steps=30)
        assert r.state == rq.FINISHED and r.replica == 1
        assert r.tokens == [_greedy([1, 2], p) for p in range(3)]
        st = fm.stats()
        assert st["drain_migrations"] == 0 and st["drain_timeouts"] == 1

    def test_crash_during_drain_migration_falls_back_exactly_once(self):
        """Chaos kill between the drain sweep's export and the target
        commit: the move aborts with the source untouched, the crash
        then surfaces as a real DEAD verdict, and the router's replay
        finishes the stream bit-identical with exactly-once delivery."""
        telem = FakeTelemetry()
        fm, _ = _fleet(
            [ChaosReplica(MigratableReplica(), crash_during_migration=1),
             MigratableReplica()],
            telemetry=telem, migration={"enabled": True},
            drain_timeout_steps=5)
        streams = []
        r = fm.submit([1, 2], max_new_tokens=6,
                      stream=lambda rr, t, d: streams.append(t))
        assert r.replica == 0
        fm.step()                          # one token delivered pre-drain
        fm.scale_down(0)
        fm.drain(max_steps=40)
        expected = [_greedy([1, 2], p) for p in range(6)]
        assert r.state == rq.FINISHED and r.replica == 1
        assert r.tokens == expected and streams == expected
        st = fm.stats()
        assert st["drain_migrations"] == 0
        assert st["drains_lost"] == 1      # the crash was a real death
        assert telem.of("drain.lost", kind="fleet")
        assert fm.router.replicas[1].imports == 0

    def test_rebalance_moves_work_off_fragmented_replica(self):
        telem = FakeTelemetry()
        fm, _ = _fleet([FragStub(frag=0.8), FragStub(frag=0.1)],
                       telemetry=telem, migration={"enabled": True},
                       rebalance_fragmentation=0.5,
                       rebalance_cooldown_steps=4)
        r1 = fm.submit([1, 2], max_new_tokens=8)
        r2 = fm.submit([3], max_new_tokens=8)
        assert r1.replica == 0 and r2.replica == 1
        fm.step()
        st = fm.stats()
        assert st["rebalances"] == 1
        ev = telem.of("rebalance", kind="fleet")
        assert ev and ev[0]["data"]["replica"] == 0
        assert ev[0]["data"]["fragmentation"] == pytest.approx(0.8)
        assert fm.router.assigned(0) == 0 and fm.router.assigned(1) == 2
        fm.drain(max_steps=30)
        assert r1.state == rq.FINISHED and r1.replica == 1
        assert r1.tokens == [_greedy([1, 2], p) for p in range(8)]
        assert r2.state == rq.FINISHED

    def test_rebalance_cooldown_and_limit_bound_the_sweep(self):
        """One bounded sweep per cooldown window, never a migration
        storm: with two sequences on the fragmented replica and
        ``rebalance_max_requests: 1``, exactly one moves."""
        fm, _ = _fleet([FragStub(frag=0.9), FragStub(frag=0.0)],
                       migration={"enabled": True},
                       rebalance_fragmentation=0.5,
                       rebalance_cooldown_steps=100,
                       rebalance_max_requests=1)
        r1 = fm.submit([1, 2], max_new_tokens=12)
        r2 = fm.submit([3, 4], max_new_tokens=12)
        r3 = fm.submit([5], max_new_tokens=12)
        assert (r1.replica, r2.replica, r3.replica) == (0, 1, 0)
        fm.drain(max_steps=40)
        st = fm.stats()
        assert st["rebalances"] == 1       # limit 1, then cooldown holds
        assert fm.router.stats()["migrations"] == 1
        for r in (r1, r2, r3):
            assert r.state == rq.FINISHED

    def test_rebalance_respects_consumer_gate(self):
        """`rebalance: false` turns only that consumer off — work stays
        put and finishes in place."""
        fm, _ = _fleet([FragStub(frag=0.9), FragStub(frag=0.0)],
                       migration={"enabled": True, "rebalance": False},
                       rebalance_fragmentation=0.5)
        r = fm.submit([1, 2], max_new_tokens=4)
        fm.step()
        assert fm.stats()["rebalances"] == 0
        assert fm.router.assigned(0) == 1
        fm.drain(max_steps=20)
        assert r.state == rq.FINISHED and r.replica == 0

    def test_rebalance_off_by_default(self):
        """`rebalance_fragmentation: 0` (the default) never sweeps,
        even with migration on and a fragmented replica."""
        fm, _ = _fleet([FragStub(frag=0.9), FragStub(frag=0.0)],
                       migration={"enabled": True})
        r = fm.submit([1, 2], max_new_tokens=4)
        fm.drain(max_steps=20)
        assert fm.stats()["rebalances"] == 0
        assert r.state == rq.FINISHED and r.replica == 0


# ---------------------------------------------------------------------------
# tools/trace_gen.py CLI
# ---------------------------------------------------------------------------
class TestTraceGenCLI:
    def _gen(self, *args):
        return subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "trace_gen.py"),
             *args],
            capture_output=True, text=True, cwd=REPO, timeout=300)

    def test_writes_deterministic_jsonl(self, tmp_path):
        out = str(tmp_path / "t.jsonl")
        args = ["--pattern", "diurnal_burst", "--duration", "30",
                "--rate", "2", "--seed", "17", "--burst", "10:5:6",
                "--tenants", "2", "--shared-fraction", "0.5",
                "--prefix-len", "8", "--out", out]
        res = self._gen(*args)
        assert res.returncode == 0, res.stderr
        assert "# summary" in res.stderr
        first = load_trace(out)
        assert first and any(a.tenant for a in first)
        res2 = self._gen(*args)
        assert res2.returncode == 0
        assert load_trace(out) == first       # seed-deterministic

    def test_sampled_fraction_flag_emits_keyed_arrivals(self, tmp_path):
        out = str(tmp_path / "s.jsonl")
        res = self._gen("--pattern", "poisson", "--duration", "30",
                        "--rate", "2", "--seed", "11",
                        "--sampled-fraction", "0.5",
                        "--temperature", "0.8", "--top-p", "0.9",
                        "--out", out)
        assert res.returncode == 0, res.stderr
        assert "sampled" in res.stderr
        trace = load_trace(out)
        sampled = [a for a in trace if a.do_sample]
        assert sampled and len(sampled) < len(trace)
        assert all(a.seed > 0 and a.temperature == 0.8 and a.top_p == 0.9
                   for a in sampled)

    def test_stdout_mode_and_bad_burst_spec(self):
        res = self._gen("--pattern", "poisson", "--duration", "5",
                        "--rate", "1", "--seed", "3")
        assert res.returncode == 0
        assert all(json.loads(line) for line in
                   res.stdout.strip().splitlines())
        bad = self._gen("--pattern", "burst", "--duration", "5",
                        "--rate", "1", "--burst", "oops")
        assert bad.returncode == 1 and "error" in bad.stderr
        missing = self._gen("--pattern", "burst", "--duration", "5",
                            "--rate", "1")
        assert missing.returncode == 1


# ---------------------------------------------------------------------------
# heavy: the real substrate + the zero-overhead pin
# ---------------------------------------------------------------------------
def _tiny_engine(seed=0, serving=None):
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    from deepspeed_tpu.parallel.topology import reset_topology

    reset_topology()
    cfg = GPT2Config.tiny(dtype=jnp.float32)
    return cfg, deepspeed_tpu.init_inference(
        GPT2LMHeadModel(cfg), dtype="fp32", seed=seed,
        serving=serving or {"block_size": 8, "decode_slots": 2,
                            "default_max_new_tokens": 4})


@pytest.mark.heavy
class TestFleetOverRealEngines:
    def test_kill_mid_drain_bit_identical_and_factory_scale_up(self):
        """Acceptance on the real substrate: two ServingEngines with
        identical params under the fleet manager; the drain victim is
        chaos-killed mid-drain, its streams finish bit-identical to a
        clean run on the survivor, and a factory-built third replica
        (same params) joins the fleet and serves."""
        from deepspeed_tpu.serving import ServingEngine

        _, ref = _tiny_engine()
        params = ref.params
        rng = np.random.default_rng(7)
        prompts = [rng.integers(1, 256, n) for n in (5, 9, 3)]
        news = [5, 4, 6]

        def build_engine():
            _, e = _tiny_engine()
            e.params = params
            return ServingEngine(e)

        def run(chaos):
            replicas = [build_engine(), build_engine()]
            if chaos:
                replicas[1] = ChaosReplica(replicas[1], crash_at_step=2)
            router = ReplicaRouter(replicas, config={"max_failovers": 2})
            fm = FleetManager(router,
                              factory=CallableReplicaFactory(build_engine),
                              config={"min_replicas": 1,
                                      "max_replicas": 3})
            streams = {i: [] for i in range(len(prompts))}
            reqs = []
            for i, (p, n) in enumerate(zip(prompts, news)):
                cb = (lambda ix: lambda r, t, d:
                      streams[ix].append(t))(i)
                reqs.append(fm.submit(p, max_new_tokens=n, stream=cb))
            if chaos:
                fm.scale_down(1)              # drain the doomed replica
            fm.drain(max_steps=200)
            return fm, reqs, streams

        _, clean_reqs, clean_streams = run(chaos=False)
        fm, reqs, streams = run(chaos=True)
        assert fm.router.health[1].state == DEAD
        assert fm.stats()["drains_lost"] == 1
        for i, (req, clean) in enumerate(zip(reqs, clean_reqs)):
            assert req.state == rq.FINISHED, (i, req.finish_reason)
            assert req.tokens == clean.tokens, i
            assert streams[i] == clean_streams[i] == req.tokens, i
        assert fm.router.stats()["replay_divergence"] == 0
        # warm the fleet back up through the factory into the DEAD slot
        detail = fm.scale_up()
        assert detail["source"] == "factory"
        out = fm.generate_batch([[5, 6, 7]], max_new_tokens=2)
        assert out[0] is not None and len(out[0]) == 2
        fm.destroy()

    def test_init_serving_builds_fleet_from_config(self):
        import deepspeed_tpu
        import jax.numpy as jnp

        from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
        from deepspeed_tpu.parallel.topology import reset_topology

        reset_topology()
        cfg = GPT2Config.tiny(dtype=jnp.float32)
        fm = deepspeed_tpu.init_serving(
            GPT2LMHeadModel(cfg), dtype="fp32",
            serving={"block_size": 8, "decode_slots": 2,
                     "router": {"replicas": 2},
                     "fleet": {"min_replicas": 1, "max_replicas": 3}})
        assert isinstance(fm, FleetManager)
        assert fm.config.max_replicas == 3
        assert fm.factory is not None         # default clone factory
        out = fm.generate_batch([[5, 6, 7], [9, 10]], max_new_tokens=2)
        assert all(t is not None and len(t) == 2 for t in out)
        # the clone factory really builds a serving replica
        detail = fm.scale_up()
        assert detail is not None and detail["source"] == "factory"
        assert fm.active_size == 3
        fm.destroy()

    def test_init_serving_fleet_disabled_is_plain_router(self):
        import deepspeed_tpu
        import jax.numpy as jnp

        from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
        from deepspeed_tpu.parallel.topology import reset_topology

        reset_topology()
        cfg = GPT2Config.tiny(dtype=jnp.float32)
        router = deepspeed_tpu.init_serving(
            GPT2LMHeadModel(cfg), dtype="fp32",
            serving={"block_size": 8, "decode_slots": 2,
                     "router": {"replicas": 2},
                     "fleet": {"enabled": False}})
        assert isinstance(router, ReplicaRouter)
        router.destroy()

    def test_engine_clock_seam_drives_deadlines_in_sim_time(self):
        """init_serving(clock=...) threads the replay clock through the
        ServingEngines too (scheduler deadline sweeps, request
        timestamps) — a simulated deadline must shed in simulated time,
        not wall time."""
        import deepspeed_tpu
        import jax.numpy as jnp

        from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
        from deepspeed_tpu.parallel.topology import reset_topology

        reset_topology()
        clock = ReplayClock()
        cfg = GPT2Config.tiny(dtype=jnp.float32)
        fm = deepspeed_tpu.init_serving(
            GPT2LMHeadModel(cfg), dtype="fp32", clock=clock,
            serving={"block_size": 8, "decode_slots": 1,
                     "default_max_new_tokens": 8,
                     "router": {"replicas": 1},
                     "fleet": {"min_replicas": 1, "max_replicas": 2}})
        assert isinstance(fm, FleetManager) and fm.clock is clock
        assert fm.router.replicas[0].clock is clock
        assert fm.router.replicas[0].sched.clock is clock
        blocker = fm.submit([1, 2, 3], max_new_tokens=8)
        doomed = fm.submit([4, 5], max_new_tokens=8, deadline_ms=2000.0)
        fm.step()                             # blocker takes the slot
        clock.advance(10.0)                   # sim time blows the deadline
        fm.drain(max_steps=40)
        assert blocker.state == rq.FINISHED
        assert doomed.state == rq.SHED
        assert doomed.finish_reason == "deadline"
        fm.destroy()

    def test_fleet_block_leaves_decode_hlo_byte_identical(self):
        """Zero-overhead pin (the PR 2-12 convention): the fleet layer
        is pure host-side policy over the router — a serving config
        WITH fleet+replay blocks compiles the exact same decode program
        as one without."""
        import jax.numpy as jnp

        from deepspeed_tpu.serving import ServingEngine

        texts = []
        for extra in ({}, {"router": {"replicas": 2},
                           "fleet": {"min_replicas": 1,
                                     "max_replicas": 3},
                           "replay": {"step_secs": 0.1}}):
            _, eng = _tiny_engine(serving={"block_size": 8,
                                           "decode_slots": 2, **extra})
            srv = ServingEngine(eng)
            fn = srv._build_decode()
            lowered = fn.lower(
                eng.params, srv.cache,
                jnp.zeros((2, 1), jnp.int32),
                jnp.asarray(srv._tables), jnp.asarray(srv._lengths),
                srv._next_rng())
            texts.append(lowered.compile().as_text())
            srv.destroy()
        assert texts[0] == texts[1]
