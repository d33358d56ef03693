"""The one bracket around every host phase (ISSUE 26).

- the ledger and the request's record under a fake clock: where a
  request's decode life went, to the millisecond;
- the ``ds.*`` annotations in a real ``jax.profiler`` trace, children
  inside parents, for a tiny serving engine and a tiny train engine;
- telemetry off: no event; ``telemetry.tracing`` on: the JSONL spans with
  the ids they had, plus the serving step's own trace;
- the compiled decode and train programs do not know about any of it;
- the serving programs have names.
"""

import json
import threading
import time

import numpy as np
import pytest

from tests.unit.test_serving import _SERVING, _tiny_serving

LIFE = ("decode_ms", "blocked_ms", "host_ms")
NEW_FIELDS = ("prefill_ms", "decode_steps", "batch_mean") + LIFE


class MsClock:
    """Whole milliseconds, advanced by hand: time passes only where a
    test says it does."""

    def __init__(self):
        self.ms = 0

    def __call__(self):
        return self.ms / 1000.0

    def advance(self, ms):
        self.ms += ms


def _slow(fn, clock, ms):
    def call(*args):
        clock.advance(ms)
        return fn(*args)
    return call


PREFILL_MS, DECODE_MS, STREAM_MS, ADMIT_MS = 50, 10, 1, 2


@pytest.fixture(scope="module")
def two_requests():
    """A (4 new tokens) decodes alone, B (2 new tokens) arrives after A's
    second token: B's prefill lands inside A's decode life. A prefill
    program takes 50 ms, a decode program 10 (at its dispatch: the fake
    clock has no device beside it), a stream callback 1, an admission pass
    2; nothing else takes time. The decode loop runs one step ahead: a
    ``step()`` dispatches the step after the one it fetches."""
    from deepspeed_tpu.serving import ServingEngine

    clock = MsClock()
    _, engine = _tiny_serving(serving={**_SERVING, "decode_slots": 2})
    srv = ServingEngine(engine, clock=clock)
    for T in srv.buckets:
        srv._prefill_fns[T] = _slow(srv._build_prefill(T), clock, PREFILL_MS)
    srv._decode_fn = _slow(srv._build_decode(), clock, DECODE_MS)
    srv.sched.admit = _slow(srv.sched.admit, clock, ADMIT_MS)

    def stream(req, token, done):
        clock.advance(STREAM_MS)

    t_start = clock()
    a = srv.submit([5, 6, 7, 8], max_new_tokens=4, stream=stream)
    srv.step()          # A: prefill, token 1; steps 1, 2 out; 1 in: A 2
    b = srv.submit([9, 10, 11, 12, 13], max_new_tokens=2, stream=stream)
    srv.step()          # B: prefill, token 1; step 3 out (A, B); 2 in: A 3
    srv.step()          # both end at step 3: none out; 3 in: A 4, B 2
    assert a.done and b.done and not srv.pending
    out = {"a": a.record(), "b": b.record(), "reqs": (a, b),
           "wall": clock() - t_start, "stats": srv.stats()}
    srv.reset_stats()
    out["stats_after_reset"] = srv.stats()
    srv.step()                      # an idle step: admission only
    out["stats_idle"] = srv.stats()
    out["shed"] = srv.submit(list(range(1, 400)), max_new_tokens=4).record()
    yield out
    srv.destroy()


# (a) -----------------------------------------------------------------------
@pytest.mark.parametrize("who,expected", [
    # A: live at 52 (admit 2, prefill 50), finished at 140: 88 ms of life =
    # 3 decode steps (30) + B's prefill (50) + 2 admission passes that ran
    # inside it (4) + 4 stream callbacks (4)
    ("a", {"prefill_ms": 50.0, "decode_steps": 3, "decode_ms": 30.0,
           "blocked_ms": 50.0, "host_ms": 8.0, "batch_mean": 1.333}),
    # B: live at 126, with step 2 in flight, which is none of its steps;
    # finished at 140 after one step beside A (10), 2 stream callbacks
    # and an admission pass (4)
    ("b", {"prefill_ms": 50.0, "decode_steps": 1, "decode_ms": 10.0,
           "blocked_ms": 0.0, "host_ms": 4.0, "batch_mean": 2.0}),
])
def test_record_says_where_the_decode_life_went(two_requests, who, expected):
    rec = two_requests[who]
    assert {k: rec[k] for k in expected} == expected


@pytest.mark.parametrize("who", ["a", "b"])
def test_decode_blocked_host_add_up_to_the_decode_life(two_requests, who):
    rec = two_requests[who]
    req = two_requests["reqs"][who == "b"]
    life_ms = 1e3 * (req.finish_ts - req.first_token_ts)
    assert sum(rec[k] for k in LIFE) == pytest.approx(life_ms, abs=1e-9)
    assert rec["ttft_ms"] is not None and rec["state"] == "finished"


# (b) -----------------------------------------------------------------------
def test_phase_seconds_tile_the_stepped_wall_time(two_requests):
    stats = two_requests["stats"]
    assert set(stats["phase_seconds"]) == {"schedule", "prefill", "decode",
                                           "emit"}
    assert sum(stats["phase_seconds"].values()) == pytest.approx(
        two_requests["wall"], abs=1e-12)
    assert stats["phase_seconds"]["prefill"] == pytest.approx(0.100)
    assert stats["phase_seconds"]["decode"] == pytest.approx(0.030)
    assert stats["phase_seconds"]["schedule"] == pytest.approx(0.006)
    assert stats["phase_seconds"]["emit"] == pytest.approx(0.006)
    assert stats["prefill_calls"] == 2
    assert stats["decode_steps"] == 3
    assert stats["busy_slot_steps"] == 1 + 2 + 1


def test_reset_stats_clears_the_ledger(two_requests):
    after = two_requests["stats_after_reset"]
    assert after["phase_seconds"] == {"schedule": 0.0, "prefill": 0.0,
                                      "decode": 0.0, "emit": 0.0}
    assert after["prefill_calls"] == 0 and after["busy_slot_steps"] == 0
    # and counts on from there
    idle = two_requests["stats_idle"]
    assert idle["phase_seconds"]["schedule"] == pytest.approx(0.002)
    assert idle["phase_seconds"]["decode"] == 0.0


# (c) -----------------------------------------------------------------------
def test_shed_request_carries_none_in_the_new_fields(two_requests):
    rec = two_requests["shed"]
    assert rec["state"] == "shed"
    assert [rec[k] for k in NEW_FIELDS] == [None] * len(NEW_FIELDS)


# (d) -----------------------------------------------------------------------
def _annotations(run):
    """``[(name, start_ns, end_ns)]`` of the ``ds.*`` host annotations a
    real profiler session saw while ``run()`` ran."""
    return [event[1:] for event in _thread_annotations(run)]


def _thread_annotations(run):
    """``[(thread, name, start_ns, end_ns)]`` of the same, ``thread`` the
    profiler's line the annotation lies on."""
    import glob
    import os
    import tempfile

    import jax
    from jax.profiler import ProfileData

    with tempfile.TemporaryDirectory() as trace_dir:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            run()
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                         "*", "*.xplane.pb"))
        data = ProfileData.from_file(path)
        return [((plane.name, i), e.name, int(e.start_ns),
                 int(e.start_ns + e.duration_ns))
                for plane in data.planes if plane.name.startswith("/host:")
                for i, line in enumerate(plane.lines) for e in line.events
                if e.name.startswith("ds.")]


def _inside(events, child, parent):
    parents = [(s, e) for n, s, e in events if n == parent]
    kids = [(s, e) for n, s, e in events if n == child]
    assert kids and parents, (child, parent, sorted({n for n, _, _ in events}))
    return all(any(ps <= s and e <= pe for ps, pe in parents)
               for s, e in kids)


@pytest.fixture(scope="module")
def profiled():
    """One profiler session over a tiny serving engine (telemetry at its
    default: off) and a tiny train engine."""
    import deepspeed_tpu
    from deepspeed_tpu.parallel.topology import reset_topology
    from deepspeed_tpu.serving import ServingEngine
    from tests.unit.simple_model import (random_dataset, simple_loss_fn,
                                         simple_params)

    _, engine = _tiny_serving(serving=_SERVING)
    srv = ServingEngine(engine)
    assert not srv.telemetry.enabled
    srv.submit([1, 2, 3], max_new_tokens=2)
    srv.drain()                     # compiled before the session

    def serve():
        srv.submit([5, 6, 7, 8], max_new_tokens=3)
        srv.drain()

    serve_events = _annotations(serve)
    srv.destroy()

    reset_topology()
    train, *_ = deepspeed_tpu.initialize(
        model=simple_loss_fn, model_parameters=simple_params(),
        config={"train_batch_size": 32, "steps_per_print": 10_000,
                "optimizer": {"type": "Adam", "params": {"lr": 0.05}}})
    x, y = random_dataset(64, 8)
    batches = iter([(x[:32], y[:32])] * 3)
    train.train_batch(data_iter=batches)
    train_events = _annotations(
        lambda: train.train_batch(data_iter=batches))
    reset_topology()
    return {"serve": serve_events, "train": train_events}


def test_profiler_sees_every_serving_phase(profiled):
    names = {n for n, _, _ in profiled["serve"]}
    assert {"ds.serve.step", "ds.serve.schedule", "ds.serve.prefill",
            "ds.serve.prefill.dispatch", "ds.serve.prefill.sync",
            "ds.serve.decode", "ds.serve.decode.dispatch",
            "ds.serve.decode.sync", "ds.serve.emit"} <= names, names


@pytest.mark.parametrize("child,parent", [
    ("ds.serve.schedule", "ds.serve.step"),
    ("ds.serve.prefill", "ds.serve.step"),
    ("ds.serve.decode", "ds.serve.step"),
    ("ds.serve.emit", "ds.serve.step"),
    ("ds.serve.prefill.dispatch", "ds.serve.prefill"),
    ("ds.serve.prefill.sync", "ds.serve.prefill"),
    ("ds.serve.decode.dispatch", "ds.serve.decode"),
    ("ds.serve.decode.sync", "ds.serve.decode"),
])
def test_children_lie_inside_their_parents(profiled, child, parent):
    assert _inside(profiled["serve"], child, parent)


def test_profiler_sees_the_three_train_phases(profiled):
    names = {n for n, _, _ in profiled["train"]}
    assert {"ds.train.data", "ds.train.fwd_bwd",
            "ds.train.optimizer"} <= names, names


# (e) -----------------------------------------------------------------------
def _spans(telemetry, name=None):
    return [e for e in telemetry.tail(256) if e["kind"] == "span"
            and (name is None or e["name"] == name)]


def test_telemetry_off_emits_no_event():
    from deepspeed_tpu.serving import ServingEngine

    _, engine = _tiny_serving(serving=_SERVING)
    srv = ServingEngine(engine)
    srv.submit([5, 6, 7, 8], max_new_tokens=3)
    srv.drain()
    assert srv.telemetry.tail(256) == []
    assert srv.stats()["phase_seconds"]["decode"] > 0   # the ledger ran
    srv.destroy()


def test_tracing_on_emits_the_spans_with_their_ids():
    from deepspeed_tpu.serving import ServingEngine

    _, engine = _tiny_serving(
        serving=_SERVING,
        telemetry={"enabled": True, "jsonl": False, "memory": False,
                   "tracing": {"enabled": True}})
    srv = ServingEngine(engine)
    req = srv.submit([5, 6, 7, 8], max_new_tokens=3)
    srv.drain()
    tel = srv.telemetry
    (serve,) = _spans(tel, "serve")
    (prefill,) = _spans(tel, "prefill")
    (decode,) = _spans(tel, "decode")
    for span in (prefill, decode):      # request-scoped: as they were
        assert span["data"]["trace"] == serve["data"]["trace"]
        assert span["data"]["parent"] == serve["data"]["span"]
    assert prefill["data"]["prompt_len"] == 4
    assert prefill["data"]["request_id"] == req.request_id
    # step-scoped: one trace per scheduler iteration
    roots = _spans(tel, "serve_step")
    assert len(roots) == srv.stats()["decode_steps"] == 2
    for root in roots:
        kids = [e for e in _spans(tel)
                if e["data"].get("parent") == root["data"]["span"]]
        assert {"schedule", "decode_step", "emit"} <= \
            {k["name"] for k in kids}
        assert all(k["data"]["trace"] == root["data"]["trace"]
                   for k in kids)
        assert all(root["data"]["start_ns"] <= k["data"]["start_ns"]
                   and k["data"]["end_ns"] <= root["data"]["end_ns"]
                   for k in kids)
    assert [r["data"]["step"] for r in roots] == [1, 2]
    assert _spans(tel, "decode_step")[0]["data"]["active"] == 1
    srv.destroy()


# (f) -----------------------------------------------------------------------
def test_decode_hlo_byte_identical_with_tracing_absent_off_on():
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.serving import ServingEngine

    texts = []
    for telemetry in (None, {"enabled": False},
                      {"enabled": True, "jsonl": False, "memory": False,
                       "compile_watchdog": False, "hlo_cost": False,
                       "tracing": {"enabled": True}}):
        _, engine = _tiny_serving(serving=_SERVING, telemetry=telemetry)
        srv = ServingEngine(engine)
        fn = srv._build_decode()
        n = srv.config.decode_slots
        texts.append(fn.lower(
            engine.params, srv.cache, jnp.zeros((n, 1), jnp.int32),
            jnp.zeros((n, srv.blocks_per_seq), jnp.int32),
            jnp.zeros((n,), jnp.int32),
            jax.random.PRNGKey(0)).compile().as_text())
        srv.destroy()
    assert texts[0] == texts[1] == texts[2]


def test_train_step_hlo_byte_identical_with_tracing_absent_off_on():
    from deepspeed_tpu.parallel.topology import reset_topology
    from tests.unit.simple_model import random_dataset
    from tests.unit.test_telemetry import _engine

    x, y = random_dataset(64, 8)
    batch = (x[:32], y[:32])
    texts = []
    for telemetry in (None, {"enabled": False},
                      {"enabled": True, "jsonl": False, "memory": False,
                       "tracing": {"enabled": True}}):
        reset_topology()
        engine = (_engine() if telemetry is None
                  else _engine(telemetry=telemetry))
        raw = engine._jit_micro
        raw = getattr(raw, "_fn", raw)  # unwrap a WatchedFunction
        engine(batch)
        texts.append(raw.lower(
            engine.state, engine._shard_batch(batch)).compile().as_text())
        engine.telemetry.close()
    reset_topology()
    assert texts[0] == texts[1] == texts[2]


# (g) -----------------------------------------------------------------------
def test_serving_programs_are_named():
    """The profiler's program lane and the HLO dump read the program's
    own name: they were all ``jit_fn``."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.serving import ServingEngine

    _, engine = _tiny_serving(
        serving={**_SERVING, "prefix_cache": True,
                 "speculative": {"enabled": True,
                                 "num_speculative_tokens": 2}})
    srv = ServingEngine(engine)
    built = {"serving_decode": srv._build_decode(),
             "serving_prefill_T8": srv._build_prefill(8),
             "serving_chunk_T16": srv._build_chunk(16),
             "serving_verify": srv._build_verify(),
             "serving_cow": srv._build_cow(),
             "serving_migrate_B3": srv._build_migrate(3)}
    for name, fn in built.items():
        assert fn.__name__ == name, (name, fn.__name__)
    n = srv.config.decode_slots
    text = built["serving_decode"].lower(
        engine.params, srv.cache, jnp.zeros((n, 1), jnp.int32),
        jnp.zeros((n, srv.blocks_per_seq), jnp.int32),
        jnp.zeros((n,), jnp.int32), jax.random.PRNGKey(0)).as_text()
    assert "module @jit_serving_decode" in text and "jit_fn" not in text
    srv.destroy()


# (h) -----------------------------------------------------------------------
def test_sse_done_event_carries_the_new_fields():
    from tests.unit.test_gateway import _post, _real_gateway, _sse_events

    gw = _real_gateway(serving={"block_size": 8, "decode_slots": 2,
                                "default_max_new_tokens": 8,
                                "gateway": {}})
    try:
        events = []
        reader = threading.Thread(
            target=lambda: events.extend(_sse_events(_post(
                gw.url, {"prompt": [5, 6, 7, 8], "max_new_tokens": 4}))),
            daemon=True)
        reader.start()
        deadline = time.monotonic() + 60
        while reader.is_alive() and time.monotonic() < deadline:
            if gw.pending:
                gw.step()
            else:
                time.sleep(0.01)
        reader.join(5)
        assert events and events[-1][0] == "done", events[-3:]
        record = events[-1][1]
        json.dumps(record)
        assert record["decode_steps"] == 3 and record["batch_mean"] == 1.0
        assert record["prefill_ms"] > 0 and record["decode_ms"] > 0
        assert record["blocked_ms"] == 0.0 and record["host_ms"] >= 0
        assert all(record[k] is not None for k in NEW_FIELDS)
    finally:
        gw.destroy()


def test_gateway_brackets_without_a_backend_telemetry():
    """A backend with no telemetry gets brackets with no profiler sink,
    and the gateway still never imports jax to make them."""
    from deepspeed_tpu.serving.gateway import ServingGateway
    from deepspeed_tpu.telemetry.tracing import Brackets
    from tests.unit.test_gateway import FakeBackend

    gw = ServingGateway(FakeBackend(), {})
    assert isinstance(gw._bracket, Brackets) and gw._bracket.annotate is None
    with gw._bracket("pump_idle") as b:
        pass
    assert b.t0 is None and gw._bracket.prefix == "ds.gateway."
    assert np.isfinite(gw.clock())
