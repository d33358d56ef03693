"""Span-based distributed tracing (ISSUE 10).

Proof obligations:

- the span layer's primitives (Tracer/StepTrace/Brackets/histogram) are
  correct, exception-isolated, and inert when disabled;
- a traced training run emits causally-linked step traces (phase
  children under one per-step root), and the zero-overhead pin holds:
  with tracing absent the compiled step program is byte-identical to a
  tracing-enabled engine's;
- a request routed through the multi-replica front door and killed
  mid-decode by chaos renders as ONE trace with two `attempt` subtrees
  and exactly-once (position-disjoint) `deliver` spans;
- the JSONL sink rotates at the configured size keeping the last K
  segments, and the report/export tools read the segments back as one
  stream;
- `tools/trace_export.py` produces valid nonempty Chrome/Perfetto JSON
  (subprocess exit-code contract included).
"""

import json
import os
import subprocess
import sys

import pytest

from deepspeed_tpu.serving import request as rq
from deepspeed_tpu.telemetry.events import SPANS, load_all_events
from deepspeed_tpu.telemetry.metrics import Histogram
from deepspeed_tpu.telemetry.tracing import (NULL_TRACER, Brackets,
                                             StepTrace, Tracer, end_span,
                                             to_ns)

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))


class Collector:
    """Minimal telemetry surface: an emit() that keeps every event."""

    enabled = True

    def __init__(self):
        self.events = []

    def emit(self, kind, name, step=None, data=None, **fields):
        payload = dict(data or {})
        payload.update(fields)
        self.events.append({"kind": kind, "name": name, "step": step,
                            "data": payload})

    def spans(self, name=None):
        return [e for e in self.events if e["kind"] == "span"
                and (name is None or e["name"] == name)]


def _tracer(collector=None):
    c = collector or Collector()
    return Tracer(emit=c.emit), c


# ---------------------------------------------------------------------------
class TestTracer:
    def test_record_span_schema(self):
        tr, c = _tracer()
        sid = tr.record_span("queue", "t1", 10, 20, parent="s0", slot=3)
        (e,) = c.spans("queue")
        d = e["data"]
        assert d["trace"] == "t1" and d["span"] == sid
        assert d["parent"] == "s0"
        assert d["start_ns"] == 10 and d["end_ns"] == 20
        assert d["slot"] == 3

    def test_begin_end_and_ctx_manager(self):
        tr, c = _tracer()
        h = tr.begin("request", "t1", start_ns=5, request_id="r")
        h.end(end_ns=9, state="finished")
        h.end(end_ns=99)  # idempotent: no double emit
        with Brackets("serve", tracer=tr)(
                "decode", span="decode",
                trace={"trace": "t1", "serve_id": h.span}, tokens=2):
            pass
        assert len(c.spans("request")) == 1
        (req,) = c.spans("request")
        assert req["data"]["end_ns"] == 9
        assert req["data"]["state"] == "finished"
        (dec,) = c.spans("decode")
        assert dec["data"]["parent"] == req["data"]["span"]

    def test_disabled_tracer_is_inert(self):
        assert NULL_TRACER.enabled is False
        assert NULL_TRACER.record_span("queue", "t", 0, 1) is None
        assert NULL_TRACER.begin("request", "t") is None
        end_span(None)  # tolerates the disabled-path None
        with Brackets("serve")("decode", span="decode",
                               trace={"trace": "t"}) as b:
            pass
        assert b.t0 is None  # no sink wanted a time: no clock was read

    def test_emit_exceptions_are_isolated(self):
        def boom(*a, **k):
            raise RuntimeError("sink died")

        tr = Tracer(emit=boom)
        assert tr.record_span("queue", "t", 0, 1) is not None
        h = tr.begin("request", "t")
        h.end()
        assert tr.dropped == 2

    def test_to_ns_roundtrip(self):
        assert to_ns(1.5) == 1_500_000_000

    def test_span_names_used_by_the_repo_are_registered(self):
        """Every span-name literal this test file exercises (and the
        GL05 lint pins repo-wide) exists in the registry."""
        for name in ("request", "attempt", "deliver", "serve", "queue",
                     "prefill", "prefill_chunk", "cow", "decode", "shed",
                     "step", "data", "fwd_bwd", "optimizer", "ckpt_io",
                     "serve_step", "schedule", "decode_step", "emit"):
            assert name in SPANS, name


# ---------------------------------------------------------------------------
class TestStepTrace:
    def test_phases_nest_under_one_step_root(self):
        tr, c = _tracer()
        st = StepTrace(tr)
        bracket = Brackets("train", tracer=tr, step_trace=st)
        with bracket("data", span="data"):
            pass
        with bracket("fwd_bwd", span="fwd_bwd"):
            pass
        with bracket("optimizer", span="optimizer"):
            pass
        trace = st.flush(7, busy=3)
        (root,) = c.spans("step")
        assert root["data"]["trace"] == trace
        assert root["data"]["step"] == 7
        assert root["data"]["busy"] == 3
        for name in ("data", "fwd_bwd", "optimizer"):
            (child,) = c.spans(name)
            assert child["data"]["trace"] == trace
            assert child["data"]["parent"] == root["data"]["span"]
        # flushed: the next boundary starts clean
        assert st.flush(8) is None and len(c.spans("step")) == 1

    def test_no_phases_no_empty_step_span(self):
        tr, c = _tracer()
        st = StepTrace(tr)
        assert st.flush(1) is None
        assert not c.events

    def test_disabled_phase_records_nothing(self):
        st = StepTrace(NULL_TRACER)
        reads = []
        bracket = Brackets("train", step_trace=st,
                           clock=lambda: reads.append(1) or 0.0)
        with bracket("data", span="data"):
            pass
        assert not reads  # tracing off: the bracket read no clock
        st.mark("data", 0, 1)
        assert st.flush(1) is None


# ---------------------------------------------------------------------------
class TestHistogram:
    def test_percentiles_fixed_buckets(self):
        h = Histogram(bounds=[1, 2, 4, 8, 16])
        h.observe_many([1, 1, 2, 3, 5, 20])
        s = h.summary()
        assert s["count"] == 6
        assert s["min"] == 1 and s["max"] == 20
        # p50 falls in the <=2 bucket; estimates are bucket upper bounds
        assert s["p50"] == 2
        assert s["p95"] == 20  # overflow bucket clamps to the true max

    def test_merge_and_scale(self):
        a, b = Histogram(bounds=[10, 100]), Histogram(bounds=[10, 100])
        a.observe(5)
        b.observe(50)
        a.merge(b)
        assert a.count == 2 and a.max == 50
        assert a.summary(scale=0.1)["max"] == 5.0
        with pytest.raises(ValueError):
            a.merge(Histogram(bounds=[1, 2]))

    def test_bad_bounds_rejected(self):
        with pytest.raises(ValueError):
            Histogram(bounds=[2, 1])
        with pytest.raises(ValueError):
            Histogram(bounds=[])

    def test_empty(self):
        assert Histogram().summary() == {"count": 0}
        assert Histogram().percentile(50) is None


# ---------------------------------------------------------------------------
class TestPerAxisAttribution:
    """Each compiled collective's replica groups name the mesh axis
    whose wire it rides (``collective_bytes_per_axis`` of the step-cost
    payload)."""

    def test_compiled_attribution_keys_match_mesh_axes(self):
        """End-to-end: a compiled sharded program's collectives land on
        the axes their replica groups actually span."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from deepspeed_tpu.telemetry.jit_watch import compiled_cost_summary

        devs = np.array(jax.devices()[:4]).reshape(2, 2)
        mesh = Mesh(devs, ("data", "fsdp"))
        w = jax.ShapeDtypeStruct((256, 64), jnp.float32)
        compiled = jax.jit(
            lambda v: v + 0.0,
            in_shardings=NamedSharding(mesh, P("fsdp")),
            out_shardings=NamedSharding(mesh, P())).lower(w).compile()
        cost = compiled_cost_summary(compiled, compiled.as_text(),
                                     axis_sizes=[("data", 2), ("fsdp", 2)])
        per_axis = cost["collective_bytes_per_axis"]
        assert set(per_axis) == {"fsdp"}
        assert per_axis["fsdp"] == 256 * 64 * 4 // 2  # shard x (group-1)

    def test_engine_hands_mesh_identity_to_telemetry(self):
        import deepspeed_tpu
        from deepspeed_tpu.parallel.topology import reset_topology

        from tests.unit.simple_model import simple_loss_fn, simple_params

        reset_topology()
        try:
            engine, *_ = deepspeed_tpu.initialize(
                model=simple_loss_fn,
                model_parameters=simple_params(),
                config={"train_batch_size": 32,
                        "optimizer": {"type": "Adam",
                                      "params": {"lr": 0.01}},
                        "mesh": {"data": 4, "fsdp": 2}})
            sizes = dict(engine.telemetry.axis_sizes)
            assert sizes["data"] == 4 and sizes["fsdp"] == 2
        finally:
            reset_topology()


# ---------------------------------------------------------------------------
class TestSinkRotation:
    def _sink(self, tmp_path, rotate_bytes, keep=2):
        from deepspeed_tpu.telemetry.sink import JsonlSink

        return JsonlSink(str(tmp_path / "telemetry.jsonl"),
                         rotate_bytes=rotate_bytes, rotate_keep=keep)

    def test_rotation_boundary_and_keep_k(self, tmp_path):
        from deepspeed_tpu.telemetry.events import make_event

        sink = self._sink(tmp_path, rotate_bytes=400, keep=2)
        for i in range(40):
            sink.write(make_event("step", "t", i, 0, {"i": i}))
        sink.close()
        path = str(tmp_path / "telemetry.jsonl")
        assert sink.rotations >= 3
        # keep-last-K: live file + exactly K rotated segments
        segs = [p for p in os.listdir(tmp_path)
                if p.startswith("telemetry.jsonl")]
        assert sorted(segs) == ["telemetry.jsonl", "telemetry.jsonl.1",
                                "telemetry.jsonl.2"]
        # each rotated segment respects the byte bound (one line of slack)
        assert os.path.getsize(path + ".1") <= 400 + 120
        # the retained window is the TAIL of the stream, in order
        events = load_all_events(path)
        ids = [e["data"]["i"] for e in events]
        assert ids == sorted(ids) and ids[-1] == 39
        assert len(ids) < 40  # the oldest segment was dropped

    def test_fresh_run_purges_previous_runs_rotated_segments(self, tmp_path):
        """Truncate-per-run covers the WHOLE segment chain: a previous
        run's telemetry.jsonl.N must not leak into this run's
        segment-aware readers."""
        from deepspeed_tpu.telemetry.events import make_event

        path = tmp_path / "telemetry.jsonl"
        for stale in (path, tmp_path / "telemetry.jsonl.1",
                      tmp_path / "telemetry.jsonl.2"):
            stale.write_text(json.dumps(make_event(
                "step", "previous-run", 1, 0, {"i": -1})) + "\n")
        sink = self._sink(tmp_path, rotate_bytes=0)
        sink.write(make_event("step", "t", 1, 0, {"i": 0}))
        sink.close()
        events = load_all_events(str(path))
        assert [e["data"]["i"] for e in events] == [0]
        assert not os.path.exists(str(path) + ".1")

    def test_two_sinks_one_path_rotate_coherently(self, tmp_path):
        """The documented multi-engine shared-dir stream: sibling sinks
        share ONE writer state, so rotation never strands a stale fd
        writing into a renamed segment and the size threshold is
        path-global."""
        from deepspeed_tpu.telemetry.events import make_event

        a = self._sink(tmp_path, rotate_bytes=400, keep=8)
        b = self._sink(tmp_path, rotate_bytes=400, keep=8)
        for i in range(30):
            (a if i % 2 == 0 else b).write(
                make_event("step", "t", i, 0, {"i": i}))
        a.close()
        b.close()
        assert a.rotations + b.rotations >= 2
        events = load_all_events(str(tmp_path / "telemetry.jsonl"))
        ids = [e["data"]["i"] for e in events]
        # every event exactly once, in emit order, across segments
        assert ids == list(range(30))

    def test_no_rotation_by_default(self, tmp_path):
        from deepspeed_tpu.telemetry.events import make_event

        sink = self._sink(tmp_path, rotate_bytes=0)
        for i in range(50):
            sink.write(make_event("step", "t", i, 0, {"i": i}))
        sink.close()
        assert sink.rotations == 0
        assert len(load_all_events(str(tmp_path / "telemetry.jsonl"))) == 50

    def test_report_reads_across_segments(self, tmp_path):
        """Satellite acceptance: the report tool aggregates the rotated
        stream as one run."""
        from deepspeed_tpu.telemetry.events import make_event

        sink = self._sink(tmp_path, rotate_bytes=300, keep=10)
        tr = Tracer(emit=lambda kind, name, step=None, data=None:
                    sink.write(make_event(kind, name, step, 0, data)))
        for i in range(6):
            t = tr.new_trace(hint=f"s{i}")
            root = tr.record_span("step", t, i * 100, i * 100 + 50, step=i)
            tr.record_span("fwd_bwd", t, i * 100, i * 100 + 40, parent=root)
        sink.close()
        assert sink.rotations >= 1
        from tools.telemetry_report import aggregate, render

        agg = aggregate(load_all_events(str(tmp_path / "telemetry.jsonl")))
        assert agg["spans"]["count"] == 12  # nothing lost to rotation
        text = render(str(tmp_path / "telemetry.jsonl"))
        assert "per-step phases" in text


# ---------------------------------------------------------------------------
def _traced_fake_telemetry():
    """test_router's FakeTelemetry with a span tracer attached (its
    ``emit(**data)`` shape is adapted to the manager's ``data=``
    convention so span payloads land unnested)."""
    from tests.unit.test_router import FakeTelemetry

    telemetry = FakeTelemetry()
    telemetry.tracer = Tracer(
        emit=lambda kind, name, step=None, data=None:
        telemetry.emit(kind, name, step=step, **(data or {})))
    return telemetry


class TestFailoverTraceContinuity:
    """Satellite acceptance: chaos-kill a replica mid-decode; the
    request renders as ONE trace with two `attempt` subtrees and no
    duplicated token-delivery spans."""

    def _run_chaos(self):
        from deepspeed_tpu.runtime.resilience.chaos import ChaosReplica
        from tests.unit.test_router import FakeReplica, _Clock
        from deepspeed_tpu.serving.config import RouterConfig
        from deepspeed_tpu.serving.router import ReplicaRouter

        telemetry = _traced_fake_telemetry()
        router = ReplicaRouter(
            [ChaosReplica(FakeReplica(), crash_at_step=2), FakeReplica()],
            config=RouterConfig(failure_threshold=1),
            clock=_Clock(), telemetry=telemetry)
        req = router.submit([3, 1, 4, 1], max_new_tokens=6)
        router.drain(max_steps=50)
        assert req.state == rq.FINISHED and req.attempt == 1
        spans = [e for e in telemetry.events if e["kind"] == "span"]
        return req, spans

    def test_one_trace_two_attempt_subtrees(self):
        req, spans = self._run_chaos()
        assert spans, "tracing produced no spans"
        traces = {e["data"]["trace"] for e in spans}
        assert traces == {req.trace_id}, (
            f"failover must CONTINUE the trace, got {traces}")
        (root,) = [e for e in spans if e["name"] == "request"]
        attempts = [e for e in spans if e["name"] == "attempt"]
        assert len(attempts) == 2
        assert all(a["data"]["parent"] == root["data"]["span"]
                   for a in attempts)
        assert [a["data"]["attempt"] for a in attempts] == [0, 1]
        assert attempts[0]["data"]["replica"] != \
            attempts[1]["data"]["replica"]
        assert attempts[0]["data"]["outcome"].startswith("failover:")
        assert attempts[1]["data"]["outcome"] == "finished"
        assert root["data"]["state"] == rq.FINISHED
        assert root["data"]["failovers"] == 1

    def test_deliver_spans_are_position_disjoint(self):
        req, spans = self._run_chaos()
        delivers = [e for e in spans if e["name"] == "deliver"]
        assert delivers, "no deliver spans"
        ranges = sorted((d["data"]["from_pos"], d["data"]["to_pos"])
                        for d in delivers)
        covered = []
        for lo, hi in ranges:
            assert lo < hi
            assert not covered or lo >= covered[-1][1], (
                f"overlapping deliver spans: {ranges} — a replayed "
                "position was streamed twice")
            covered.append((lo, hi))
        # every generated token was delivered exactly once overall
        assert sum(hi - lo for lo, hi in ranges) == len(req.tokens)
        # each deliver nests under ITS attempt
        attempts = {e["data"]["span"]: e["data"]["attempt"]
                    for e in spans if e["name"] == "attempt"}
        assert all(d["data"]["parent"] in attempts for d in delivers)

    def test_export_renders_failover_across_replica_lanes(self, tmp_path):
        req, spans = self._run_chaos()
        from tools.trace_export import to_trace_events

        events = to_trace_events(spans)
        slices = [e for e in events if e.get("ph") == "X"]
        assert slices
        lanes = {e["tid"] for e in events if e.get("ph") == "M"
                 and e["name"] == "thread_name"
                 and e["args"]["name"].startswith("replica")}
        assert len(lanes) == 2, "both replicas must render as lanes"

    def test_tracing_off_leaves_router_silent(self):
        from deepspeed_tpu.runtime.resilience.chaos import ChaosReplica
        from tests.unit.test_router import FakeReplica, FakeTelemetry, _Clock
        from deepspeed_tpu.serving.config import RouterConfig
        from deepspeed_tpu.serving.router import ReplicaRouter

        telemetry = FakeTelemetry()  # no .tracer attribute
        router = ReplicaRouter(
            [ChaosReplica(FakeReplica(), crash_at_step=2), FakeReplica()],
            config=RouterConfig(failure_threshold=1),
            clock=_Clock(), telemetry=telemetry)
        req = router.submit([3, 1, 4, 1], max_new_tokens=6)
        router.drain(max_steps=50)
        assert req.state == rq.FINISHED
        assert not [e for e in telemetry.events if e["kind"] == "span"]
        assert req.trace_id is None


# ---------------------------------------------------------------------------
class TestSchedulerSpans:
    """Host-level: the scheduler establishes the replica-side context at
    admission (queue span + open serve root) and records sheds."""

    def _sched(self, tracer, clock, **over):
        from deepspeed_tpu.serving.blocks import BlockManager
        from deepspeed_tpu.serving.config import ServingConfig
        from deepspeed_tpu.serving.scheduler import (
            ContinuousBatchingScheduler)

        cfg = ServingConfig(block_size=8, decode_slots=2,
                            default_max_new_tokens=4, **over)
        blocks = BlockManager(16, 8, 4)
        return ContinuousBatchingScheduler(cfg, blocks, 32, [8, 16],
                                           clock=clock, tracer=tracer)

    def test_admit_opens_serve_root_and_queue_span(self):
        from tests.unit.test_router import _Clock

        tr, c = _tracer()
        clock = _Clock()
        sched = self._sched(tr, clock)
        req = rq.Request(prompt=[1] * 8, max_new_tokens=4)
        assert sched.submit(req)
        clock.advance(0.5)
        (admitted, _) = sched.admit()
        assert len(admitted) == 1
        assert req.trace and "serve_id" in req.trace
        (q,) = c.spans("queue")
        assert q["data"]["trace"] == req.trace["trace"]
        assert q["data"]["parent"] == req.trace["serve_id"]
        assert q["data"]["end_ns"] - q["data"]["start_ns"] == to_ns(0.5)
        # serve root is OPEN (ends at engine finish/shed)
        assert not c.spans("serve")
        req.trace["serve"].end(state="finished")
        assert c.spans("serve")

    def test_router_stamped_context_is_reused(self):
        from tests.unit.test_router import _Clock

        tr, c = _tracer()
        sched = self._sched(tr, _Clock())
        req = rq.Request(prompt=[1] * 8, max_new_tokens=4,
                         trace={"trace": "t-client", "parent": "s-attempt",
                                "attempt": 2})
        assert sched.submit(req)
        sched.admit()
        assert req.trace["trace"] == "t-client"
        (q,) = c.spans("queue")
        assert q["data"]["trace"] == "t-client"
        serve = req.trace["serve"]
        assert serve.parent == "s-attempt" and serve.attrs["attempt"] == 2

    def test_deadline_shed_records_shed_span(self):
        from tests.unit.test_router import _Clock

        tr, c = _tracer()
        clock = _Clock()
        sched = self._sched(tr, clock, deadline_ms=100.0)
        req = rq.Request(prompt=[1] * 8, max_new_tokens=4,
                         trace={"trace": "t-client", "parent": "s-att"})
        assert sched.submit(req)
        clock.advance(1.0)  # deadline blown in queue
        admitted, shed = sched.admit()
        assert not admitted and shed
        (s,) = c.spans("shed")
        assert s["data"]["trace"] == "t-client"
        assert s["data"]["reason"] == "deadline"
        # a pre-admission shed has no serve root yet: it must attach to
        # the router-stamped attempt parent, never float as a fake root
        assert s["data"]["parent"] == "s-att"

    def test_submit_time_shed_without_context_is_silent(self):
        from tests.unit.test_router import _Clock

        tr, c = _tracer()
        sched = self._sched(tr, _Clock())
        req = rq.Request(prompt=[1] * 64, max_new_tokens=4)  # no bucket
        assert not sched.submit(req)
        assert not c.events


# ---------------------------------------------------------------------------
class TestConfigAndZeroOverhead:
    def test_tracing_defaults_off(self):
        from deepspeed_tpu.runtime.config import TelemetryConfig

        t = TelemetryConfig()
        assert t.tracing.enabled is False
        assert set(type(t.tracing).model_fields) == {"enabled"}
        assert t.rotate_bytes == 0 and t.rotate_keep == 4

    def test_validation(self):
        from deepspeed_tpu.runtime.config import (TelemetryConfig,
                                                  TelemetryTracingConfig)

        with pytest.raises(Exception):
            TelemetryTracingConfig(ici_gbps=90.0)  # a key that is gone
        with pytest.raises(Exception):
            TelemetryConfig(rotate_bytes=-1)
        with pytest.raises(Exception):
            TelemetryConfig(rotate_keep=0)

    def test_disabled_manager_has_inert_tracer(self):
        from deepspeed_tpu.telemetry import Telemetry

        t = Telemetry()
        assert t.tracer.enabled is False
        assert t.step_trace.enabled is False

    def test_step_hlo_byte_identical_with_tracing(self):
        """Zero-overhead pin: `tracing` present+enabled changes only
        host-side bookkeeping — the engine's compiled step program is
        byte-identical to a config with NO telemetry section at all."""
        from tests.unit.test_telemetry import _engine
        from tests.unit.simple_model import random_dataset

        x, y = random_dataset(64, 8)
        batch = (x[:32], y[:32])

        def step_hlo(engine):
            raw = engine._jit_micro
            raw = getattr(raw, "_fn", raw)  # unwrap a WatchedFunction
            engine((batch[0], batch[1]))
            return raw.lower(engine.state,
                             engine._shard_batch(batch)).compile().as_text()

        from deepspeed_tpu.parallel.topology import reset_topology

        reset_topology()
        plain = _engine()
        plain_hlo = step_hlo(plain)
        reset_topology()
        traced = _engine(telemetry={"enabled": True, "jsonl": False,
                                    "memory": False,
                                    "tracing": {"enabled": True}})
        traced_hlo = step_hlo(traced)
        assert plain_hlo == traced_hlo
        traced.telemetry.close()


# ---------------------------------------------------------------------------
class TestTrainingStepTraces:
    """A real (tiny) training engine with tracing on emits causal step
    traces through the standard step boundary."""

    def _run(self, tmp_path, steps=3):
        import deepspeed_tpu
        from deepspeed_tpu.parallel.topology import reset_topology
        from tests.unit.simple_model import (random_dataset, simple_loss_fn,
                                             simple_params)

        reset_topology()
        engine, *_ = deepspeed_tpu.initialize(
            model=simple_loss_fn, model_parameters=simple_params(),
            config={"train_batch_size": 32,
                    "optimizer": {"type": "Adam", "params": {"lr": 0.05}},
                    "steps_per_print": 10_000,
                    "telemetry": {"enabled": True, "dir": str(tmp_path),
                                  "memory": False,
                                  "tracing": {"enabled": True}}})
        x, y = random_dataset(64, 8)
        it = iter([(x[:32], y[:32])] * steps)
        for _ in range(steps):
            engine.train_batch(data_iter=it)
        engine.telemetry.flush()
        events = load_all_events(str(tmp_path / "telemetry.jsonl"))
        return engine, [e for e in events if e["kind"] == "span"]

    def test_step_roots_with_phase_children(self, tmp_path):
        engine, spans = self._run(tmp_path)
        roots = [e for e in spans if e["name"] == "step"]
        assert len(roots) == 3
        assert [r["data"]["step"] for r in roots] == [1, 2, 3]
        for root in roots:
            children = [e for e in spans
                        if e["data"].get("parent") == root["data"]["span"]]
            names = {c["name"] for c in children}
            assert {"data", "fwd_bwd", "optimizer"} <= names, names
            assert all(c["data"]["trace"] == root["data"]["trace"]
                       for c in children)
        engine.telemetry.close()

    def test_ckpt_io_span(self, tmp_path):
        engine, _ = self._run(tmp_path, steps=1)
        engine.save_checkpoint(str(tmp_path / "ckpt"))
        engine.load_checkpoint(str(tmp_path / "ckpt"))
        engine.telemetry.flush()
        events = load_all_events(str(tmp_path / "telemetry.jsonl"))
        ckpt = [e for e in events if e["kind"] == "span"
                and e["name"] == "ckpt_io"]
        actions = [c["data"]["action"] for c in ckpt]
        assert actions == ["save", "load"]
        # own trace, not glued onto a step trace
        steps = {e["data"]["trace"] for e in events if e["kind"] == "span"
                 and e["name"] == "step"}
        assert all(c["data"]["trace"] not in steps for c in ckpt)
        engine.telemetry.close()


# ---------------------------------------------------------------------------
class TestTraceExportTool:
    def _make_sink(self, tmp_path):
        from deepspeed_tpu.telemetry import Telemetry

        t = Telemetry({"enabled": True, "dir": str(tmp_path),
                       "tracing": {"enabled": True},
                       "compile_watchdog": False, "memory": False})
        tr = t.tracer
        trace = tr.new_trace(hint="req-1")
        root = tr.begin("request", trace, start_ns=0, request_id="req-1")
        tr.record_span("queue", trace, 0, 5_000_000, parent=root.span)
        tr.record_span("decode", trace, 5_000_000, 9_000_000,
                       parent=root.span, tokens=4)
        root.end(end_ns=9_000_000, state="finished", tokens=4)
        t.flush()
        t.close()
        return os.path.join(str(tmp_path), "telemetry.jsonl")

    def test_subprocess_smoke(self, tmp_path):
        """Satellite acceptance: exit 0, valid JSON, nonempty
        trace_events."""
        sink = self._make_sink(tmp_path)
        out = str(tmp_path / "trace.json")
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "trace_export.py"),
             sink, "-o", out],
            capture_output=True, text=True, cwd=REPO,
            env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=300)
        assert proc.returncode == 0, proc.stderr
        payload = json.loads(open(out).read())
        slices = [e for e in payload["traceEvents"] if e["ph"] == "X"]
        assert slices and {e["name"] for e in slices} == \
            {"request", "queue", "decode"}
        assert all(e["dur"] >= 0 for e in slices)

    def test_exit_codes(self, tmp_path):
        tool = os.path.join(REPO, "tools", "trace_export.py")
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        # 2: no sink at all
        proc = subprocess.run(
            [sys.executable, tool, str(tmp_path / "nope.jsonl")],
            capture_output=True, text=True, cwd=REPO, env=env, timeout=300)
        assert proc.returncode == 2
        # 1: a sink with no span events
        empty = tmp_path / "telemetry.jsonl"
        empty.write_text(json.dumps(
            {"ts": 0, "kind": "step", "name": "t", "step": 1, "rank": 0,
             "data": {}}) + "\n")
        proc = subprocess.run([sys.executable, tool, str(empty)],
                              capture_output=True, text=True, cwd=REPO,
                              env=env, timeout=300)
        assert proc.returncode == 1

    def test_report_renders_request_waterfall(self, tmp_path):
        sink = self._make_sink(tmp_path)
        from tools.telemetry_report import render

        text = render(sink)
        assert "tracing: " in text
        assert "request req-1: finished" in text
        for name in ("queue", "decode"):
            assert name in text


# ---------------------------------------------------------------------------
@pytest.mark.heavy
class TestEndToEndServingTrace:
    """Acceptance criterion: a replica killed mid-decode yields ONE
    exported Perfetto trace containing submit→chunk→decode→failover→
    finish spans across BOTH replicas — real engines, real chaos."""

    def test_chaos_failover_exports_one_causal_trace(self, tmp_path):
        from deepspeed_tpu.runtime.resilience.chaos import ChaosReplica
        from deepspeed_tpu.serving import ServingEngine
        from deepspeed_tpu.serving.config import RouterConfig
        from deepspeed_tpu.serving.router import ReplicaRouter
        from tests.unit.test_serving import _tiny_serving

        telemetry_cfg = {"enabled": True, "dir": str(tmp_path),
                         "memory": False, "tracing": {"enabled": True}}
        serving = {"block_size": 8, "decode_slots": 2,
                   "default_max_new_tokens": 8,
                   "prefill_chunk_tokens": 4}
        _, e0 = _tiny_serving(serving=serving, telemetry=telemetry_cfg)
        _, e1 = _tiny_serving(serving=serving, telemetry=telemetry_cfg)
        s0, s1 = ServingEngine(e0), ServingEngine(e1)
        router = ReplicaRouter(
            [ChaosReplica(s0, crash_at_step=3), s1],
            config=RouterConfig(failure_threshold=1),
            telemetry=s0.telemetry)
        req = router.submit(list(range(1, 9)), max_new_tokens=6)
        router.drain(max_steps=200)
        assert req.state == rq.FINISHED and req.attempt == 1
        s0.telemetry.flush()
        s1.telemetry.flush()
        events = load_all_events(str(tmp_path / "telemetry.jsonl"))
        spans = [e for e in events if e["kind"] == "span"
                 and e["data"].get("trace") == req.trace_id]
        names = {e["name"] for e in spans}
        assert {"request", "attempt", "serve", "queue", "prefill_chunk",
                "decode", "deliver"} <= names, names
        # two attempts, each with a replica-side serve subtree
        attempts = sorted((e for e in spans if e["name"] == "attempt"),
                          key=lambda e: e["data"]["attempt"])
        assert len(attempts) == 2
        serves = [e for e in spans if e["name"] == "serve"]
        att_ids = {a["data"]["span"] for a in attempts}
        assert {s["data"]["parent"] for s in serves} <= att_ids
        assert len(serves) == 2
        # export: one Perfetto process for the trace, both replica lanes
        from tools.trace_export import export

        payload = export(str(tmp_path / "telemetry.jsonl"),
                         only_trace=req.trace_id)
        slices = [e for e in payload["traceEvents"] if e["ph"] == "X"]
        assert {e["name"] for e in slices} >= {"request", "attempt",
                                               "serve", "decode"}
        assert len({e["pid"] for e in slices}) == 1  # ONE trace
        router.destroy()
