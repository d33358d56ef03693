"""The ledger of the process (ISSUE 54, ``telemetry/process_ledger.py``).

- start-up under a fake clock: top-level phases + ``outside_s`` =
  ``ready_s`` to the millisecond, children inside parents, nothing after
  ``ready`` moves it;
- a CPU engine's first calls listed by program name with their trace /
  lower / compile split, a second call listed nowhere, a program first
  called after ``ready`` in ``late_programs``; ``reset_stats()`` leaves it;
- the collector's pauses: ``gc_ms`` and ``host_pauses`` after a forced
  ``gc.collect()`` inside a step, the three-term identity still exact;
- the slowest steps under a fake clock: a 300 ms ``emit`` is kept, named
  and logged once;
- ``ds.startup.*``, ``ds.host.gc`` and ``ds.gateway.pump_turn`` in a real
  profiler session, and what ``trace_reduce.idle_gaps`` makes of them on a
  hand-made trace;
- the JSONL spans and the report's waterfall under ``telemetry.tracing``.
"""

import gc
import logging
import time

import pytest

from deepspeed_tpu.telemetry import compile_watch, process_ledger
from deepspeed_tpu.telemetry.events import SPANS
from deepspeed_tpu.telemetry.process_ledger import ProcessLedger
from tests.unit.test_brackets import LIFE, MsClock, _annotations, _slow
from tests.unit.test_serving import _SERVING, _tiny_serving


@pytest.fixture
def fresh_ledger(monkeypatch):
    """A process ledger of the test's own in the process's place (real
    clock, the profiler's annotations): what an engine built inside the
    test writes to, whatever ran in this process before."""
    import jax

    led = ProcessLedger(annotate=jax.profiler.TraceAnnotation)
    monkeypatch.setattr(process_ledger, "LEDGER", led)
    process_ledger.install(jax.profiler.TraceAnnotation)
    return led


# ---------------------------------------------------------------------------
# (a) the tiling, under a fake clock

def _fake_startup():
    clock = MsClock()
    led = ProcessLedger(clock=clock, started_at=0.0)
    clock.advance(300)                          # the interpreter
    t0 = clock()
    clock.advance(1900)
    led.stamp_import(t0, clock())               # import 1.9
    clock.advance(4000)                         # the caller: weights
    with led.startup_bracket("inference_init", span="startup.inference_init"):
        clock.advance(2200)
    with led.startup_bracket("serving_init", span="startup.serving_init"):
        clock.advance(100)
        with led.startup_bracket("pool", span="startup.pool"):
            clock.advance(1900)
        with led.startup_bracket("weight_layouts",
                                 span="startup.weight_layouts"):
            clock.advance(400)
    for name, ms in (("serving_prefill_T128", 4100), ("serving_decode", 900)):
        clock.advance(50)                       # the caller, between them
        with led.first_call(name):
            clock.advance(ms)
    assert led.first_call("serving_decode") is None     # its second call
    with led.startup_bracket("gateway_start", span="startup.gateway_start"):
        clock.advance(5)
    return clock, led


def test_top_level_phases_and_outside_add_up_to_ready():
    clock, led = _fake_startup()
    snap = led.ready("serving")
    assert snap["ready"] and snap["ready_by"] == "serving"
    ph = snap["phases"]
    assert ph == {"import": 1.9, "inference_init": 2.2, "serving_init": 2.4,
                  "pool": 1.9, "weight_layouts": 0.4, "program": 5.0,
                  "gateway_start": 0.005}
    top = ("import", "inference_init", "serving_init", "program",
           "gateway_start")
    assert snap["ready_s"] == pytest.approx(15.905, abs=1e-9)
    assert sum(ph[k] for k in top) + snap["outside_s"] == pytest.approx(
        snap["ready_s"], abs=1e-9)
    # interpreter 0.3 + the caller's 4.0 + 2 x 0.05
    assert snap["outside_s"] == pytest.approx(4.4, abs=1e-9)
    # children lie inside their parents
    assert ph["pool"] + ph["weight_layouts"] <= ph["serving_init"]
    assert [(p["program"], p["at_s"], p["wall_s"]) for p in snap["programs"]] \
        == [("serving_prefill_T128", 10.85, 4.1), ("serving_decode", 15.0, 0.9)]
    assert snap["compile"]["wall_s"] == pytest.approx(ph["program"])
    assert snap["late_programs"] == []
    # the same brackets in the order they ran: the gaps are outside_s's
    line = snap["timeline"]
    assert [(t["phase"], t["at_s"], t["secs"]) for t in line] == [
        ("import", 0.3, 1.9), ("inference_init", 6.2, 2.2),
        ("serving_init", 8.4, 2.4), ("program", 10.85, 4.1),
        ("program", 15.0, 0.9), ("gateway_start", 15.9, 0.005)]
    assert line[3]["program"] == "serving_prefill_T128"
    assert snap["top_level"] == {k: ph[k] for k in top}


def test_nothing_after_ready_moves_the_ledger():
    clock, led = _fake_startup()
    first = led.ready("serving")
    assert led.ready("training") is None         # the first call wins
    clock.advance(60_000)
    with led.first_call("serving_prefill_T512"):
        clock.advance(3000)
    with led.startup_bracket("pool", span="startup.pool"):
        clock.advance(10)
    later = led.snapshot()
    assert [p["program"] for p in later.pop("late_programs")] == \
        ["serving_prefill_T512"]
    first.pop("ready_by"), first.pop("late_programs")
    assert later == first


def test_before_ready_the_snapshot_runs_to_now():
    clock, led = _fake_startup()
    clock.advance(1000)
    snap = led.snapshot()
    assert not snap["ready"]
    assert snap["ready_s"] == pytest.approx(16.905, abs=1e-9)
    assert snap["outside_s"] == pytest.approx(5.4, abs=1e-9)


def test_the_one_log_line_at_ready():
    _, led = _fake_startup()
    line = ProcessLedger.ready_line(led.ready("serving"))
    assert line == (
        "start-up 15.9 s: import 1.9, inference_init 2.2, serving_init 2.4 "
        "(pool 1.9, weight_layouts 0.4), gateway_start 0.0, programs 5.0 "
        "(trace 0.0, lower 0.0, compile 0.0, 0 cache hits; slowest "
        "serving_prefill_T128 4.1), gc 0.0, outside 4.4")


def test_the_process_start_is_the_operating_systems():
    """Before the first line of Python: the package's import began after
    it, and this test runs long after both."""
    led = ProcessLedger()
    assert led.started_at < time.monotonic() - 0.5
    snap = process_ledger.snapshot()
    assert snap["phases"]["import"] > 0
    assert snap["outside_s"] >= 0


# ---------------------------------------------------------------------------
# (b) a CPU engine's first calls, by name

@pytest.fixture
def served(fresh_ledger):
    from deepspeed_tpu.serving import ServingEngine
    from deepspeed_tpu.serving.gateway import ServingGateway

    _, engine = _tiny_serving(serving=_SERVING)
    srv = ServingEngine(engine)
    srv.submit([1, 2, 3, 4, 5], max_new_tokens=3)
    srv.drain()
    once = [p["program"] for p in fresh_ledger.programs]
    srv.submit([5, 4, 3, 2, 1], max_new_tokens=3)       # the same programs
    srv.drain()
    gateway = ServingGateway(srv, {"port": 0}).start()  # ready
    try:
        srv.submit(list(range(1, 20)), max_new_tokens=2)    # another bucket
        srv.drain()
        yield {"srv": srv, "once": once, "ledger": fresh_ledger}
    finally:
        gateway.close()
        srv.destroy()


def test_first_calls_are_listed_by_program_name(served):
    startup = served["srv"].stats()["startup"]
    assert startup["ready"]
    names = [p["program"] for p in startup["programs"]]
    # a second call of the same program is listed nowhere
    assert names == served["once"] == [
        "serving_prefill_T8", "serving_decode_feed", "serving_decode"]
    for row in startup["programs"]:
        # attributed by label: each was traced, lowered and compiled (or
        # fetched from the persistent cache) inside its own first call,
        # the decode program while it was asked how the weights should lie
        assert row["trace_s"] > 0 and row["lower_s"] > 0, row
        assert row["compile_s"] + row["cache_retrieval_s"] > 0, row
        assert row["compiles"] >= 1 and row["wall_s"] > 0
        assert 0 < row["at_s"] < startup["ready_s"]
    assert startup["compile"]["trace_s"] == pytest.approx(
        sum(p["trace_s"] for p in startup["programs"]), abs=1e-5)
    # the program first called after ready is no start-up, and has a name
    assert [p["program"] for p in startup["late_programs"]] == \
        ["serving_prefill_T32"]
    assert startup["late_programs"][0]["at_s"] > startup["ready_s"]


def test_the_engines_phases_tile_and_nest(served):
    startup = served["srv"].stats()["startup"]
    ph = startup["phases"]
    assert {"inference_init", "serving_init", "pool", "weight_layouts",
            "program", "gateway_start"} <= set(ph)
    assert ph["pool"] + ph["weight_layouts"] <= ph["serving_init"]
    top = [k for k in ph if k not in ("pool", "weight_layouts")]
    assert sum(ph[k] for k in top) + startup["outside_s"] == pytest.approx(
        startup["ready_s"], abs=1e-4)
    assert startup["outside_s"] > 0


def test_reset_stats_leaves_the_startup_ledger(served):
    srv = served["srv"]
    before = srv.stats()["startup"]
    srv.reset_stats()
    after = srv.stats()
    assert after["startup"] == before
    assert after["slow_steps"] == [] and after["prefill_calls"] == 0


def test_the_train_engine_reports_its_startup(fresh_ledger):
    import deepspeed_tpu
    from deepspeed_tpu.parallel.topology import reset_topology
    from tests.unit.simple_model import (random_dataset, simple_loss_fn,
                                         simple_params)

    reset_topology()
    train, *_ = deepspeed_tpu.initialize(
        model=simple_loss_fn, model_parameters=simple_params(),
        config={"train_batch_size": 32, "steps_per_print": 10_000,
                "optimizer": {"type": "Adam", "params": {"lr": 0.05}}})
    x, y = random_dataset(64, 8)
    assert not train.describe_topology(False)["startup"]["ready"]
    batches = iter([(x[:32], y[:32])] * 2)
    train.train_batch(data_iter=batches)     # the first step's boundary
    train.train_batch(data_iter=batches)
    train.eval_batch((x[:32], y[:32]))       # first called after ready
    startup = train.describe_topology(include_tensors=False)["startup"]
    reset_topology()
    assert startup["ready"]
    assert {"initialize", "state", "program"} <= set(startup["phases"])
    assert startup["phases"]["state"] <= startup["phases"]["initialize"]
    assert [p["program"] for p in startup["programs"]] == [
        "train_micro_step", "train_apply_step"]
    assert all(p["trace_s"] > 0 and p["lower_s"] > 0
               for p in startup["programs"])
    assert [p["program"] for p in startup["late_programs"]] == \
        ["train_eval_step"]
    assert (startup["phases"]["initialize"] + startup["phases"]["program"]
            + startup["outside_s"]) == pytest.approx(startup["ready_s"],
                                                     abs=1e-4)


def test_compile_watch_keeps_seconds_under_every_open_label():
    import jax
    import jax.numpy as jnp

    compile_watch.install()

    @jax.jit
    def helper(x):
        return jnp.sin(x) * 3.0

    def program(x):
        return helper(x) + helper(x * 2.0).sum()

    x = jnp.arange(7.0) + 54.0
    with compile_watch.label_scope("t54.outer"):
        with compile_watch.label_scope("t54.inner"):
            assert compile_watch.current_label() == "t54.inner"
            jax.block_until_ready(jax.jit(program)(x))
        assert compile_watch.current_label() == "t54.outer"
    assert compile_watch.current_label() is None
    outer = compile_watch.label_totals("t54.outer")
    assert outer == compile_watch.label_totals("t54.inner")
    assert outer["compiles"] >= 1 and outer["secs"] > 0
    assert outer["trace_secs"] > 0 and outer["lower_secs"] > 0
    assert compile_watch.label_totals("t54.never")["compiles"] == 0


def test_a_trace_inside_a_trace_is_counted_once():
    """A jitted helper traced while a program is traced closes first and
    lies inside the program's span: the program's own share is its span
    less the helper's."""
    def own(start, end, at=4e9):    # (after every real trace of this thread)
        return compile_watch._own_secs(at + start, at + end)

    assert own(10.0, 10.5) == pytest.approx(0.5)         # helper
    assert own(10.6, 10.7) == pytest.approx(0.1)         # helper
    assert own(9.0, 12.0) == pytest.approx(3.0 - 0.6)    # the program
    assert own(13.0, 14.0) == pytest.approx(1.0)         # the next one


# ---------------------------------------------------------------------------
# (c) the collector's pauses

def test_gc_ms_is_a_part_of_the_decode_life_not_a_fourth_term(fresh_ledger):
    from deepspeed_tpu.serving import ServingEngine

    clock = MsClock()
    _, engine = _tiny_serving(serving={**_SERVING, "decode_slots": 2})
    srv = ServingEngine(engine, clock=clock)
    srv._decode_fn = _slow(srv._build_decode(), clock, 10)
    srv.reset_stats()

    def stream(req, token, done):
        clock.advance(1)
        if len(req.tokens) == 2:
            gc.collect()            # inside a step, inside the decode life

    req = srv.submit([5, 6, 7, 8], max_new_tokens=4, stream=stream)
    srv.drain()
    rec = req.record()
    assert rec["gc_ms"] > 0
    life_ms = 1e3 * (req.finish_ts - req.first_token_ts)
    assert sum(rec[k] for k in LIFE) == pytest.approx(life_ms, abs=1e-9)
    stats = srv.stats()
    assert set(stats["phase_seconds"]) == {"schedule", "prefill", "decode",
                                           "emit"}
    pauses = stats["host_pauses"]
    assert pauses["gc_collections"][2] >= 1
    assert pauses["gc_pause_secs"] >= 1e-3 * rec["gc_ms"] - 1e-9
    srv.reset_stats()
    again = srv.stats()["host_pauses"]
    assert again["gc_collections"][2] == 0 and again["gc_long_pauses"] == []
    srv.destroy()
    # a request that never went live carries None
    assert req.__class__(request_id="x", prompt=[1]).record()["gc_ms"] is None


def test_long_collections_are_kept_with_their_times():
    clock = MsClock()
    led = ProcessLedger(clock=clock, started_at=0.0)
    for at_ms, took_ms, gen in ((100, 2, 0), (500, 40, 2), (900, 7, 1)):
        clock.ms = at_ms
        led.on_gc("start", {"generation": gen})
        clock.advance(took_ms)
        led.on_gc("stop", {"generation": gen})
    base = led.host_pauses()
    assert base["gc_collections"] == [1, 1, 1]
    assert base["gc_pause_secs"] == pytest.approx(0.049)
    assert base["gc_pause_max_ms"] == 40.0
    assert [(p["at_s"], p["ms"]) for p in base["gc_long_pauses"]] == \
        [(0.5, 40.0), (0.9, 7.0)]
    assert led.gc["pause_max_at_s"] == 0.5
    clock.ms = 2000
    led.on_gc("start", {"generation": 2})
    clock.advance(300)
    led.on_gc("stop", {"generation": 2})
    since = led.host_pauses(since=base)
    assert since["gc_collections"] == [0, 0, 1]
    assert since["gc_pause_secs"] == pytest.approx(0.3)
    assert since["gc_pause_max_ms"] == 300.0
    assert [p["at_s"] for p in since["gc_long_pauses"]] == [2.0]


# ---------------------------------------------------------------------------
# (d) the slowest steps

class _Lines(logging.Handler):
    def __init__(self):
        super().__init__(logging.WARNING)
        self.lines = []

    def emit(self, record):
        self.lines.append(record.getMessage())


def test_a_300_ms_emit_is_kept_named_and_logged_once(fresh_ledger):
    from deepspeed_tpu.serving import ServingEngine
    from deepspeed_tpu.utils.logging import logger

    clock = MsClock()
    _, engine = _tiny_serving(serving={**_SERVING, "decode_slots": 2})
    srv = ServingEngine(engine, clock=clock)
    for T in srv.buckets:
        srv._prefill_fns[T] = _slow(srv._build_prefill(T), clock, 50)
    srv._decode_fn = _slow(srv._build_decode(), clock, 10)
    srv.submit([1, 2, 3], max_new_tokens=2)
    srv.drain()                         # the feed program's first call
    srv.reset_stats()

    def stream(req, token, done):
        clock.advance(300 if len(req.tokens) == 3 else 1)

    seen = _Lines()
    logger.addHandler(seen)
    try:
        srv.submit([5, 6, 7, 8], max_new_tokens=5, stream=stream)
        srv.step()
        clock.advance(40)               # the loop stands still between two
        srv.drain()
    finally:
        logger.removeHandler(seen)
    slow = srv.stats()["slow_steps"]
    assert 1 < len(slow) <= 8
    worst = slow[0]
    assert set(worst) == {"at_s", "step", "wall_ms", "seam_ms", "schedule_ms",
                          "prefill_ms", "decode_ms", "emit_ms", "gc_ms",
                          "dispatch_ms", "sync_ms", "busy", "queue_depth",
                          "first_call"}
    assert worst["emit_ms"] == pytest.approx(300.0)
    assert worst["wall_ms"] == pytest.approx(
        worst["schedule_ms"] + worst["prefill_ms"] + worst["decode_ms"]
        + worst["emit_ms"])
    assert not worst["first_call"]
    assert [r["wall_ms"] + r["seam_ms"] for r in slow] == sorted(
        (r["wall_ms"] + r["seam_ms"] for r in slow), reverse=True)
    # the seam after the first step (it left a step in flight): 40 ms
    assert [r["seam_ms"] for r in slow if r["seam_ms"]] == [40.0]
    logged = [ln for ln in seen.lines if ln.startswith("serving step")]
    assert len(logged) == 1, seen.lines
    assert f"serving step {worst['step']} took" in logged[0]
    assert "emit 300" in logged[0] and "dispatch 10 and sync 0" in logged[0]
    assert worst["dispatch_ms"] + worst["sync_ms"] == pytest.approx(
        worst["prefill_ms"] + worst["decode_ms"])
    srv.destroy()


def test_no_seam_after_a_step_that_left_nothing_to_do(fresh_ledger):
    from deepspeed_tpu.serving import ServingEngine

    clock = MsClock()
    _, engine = _tiny_serving(serving=_SERVING)
    srv = ServingEngine(engine, clock=clock)
    srv.submit([1, 2, 3], max_new_tokens=2)
    srv.drain()
    clock.advance(60_000)               # idle for want of work
    srv.submit([3, 2, 1], max_new_tokens=2)
    srv.drain()
    assert all(r["seam_ms"] == 0.0 for r in srv.stats()["slow_steps"])
    srv.destroy()


def test_the_pumps_idle_wait_is_no_seam(fresh_ledger):
    """What ``ds.gateway.pump_idle`` covers comes off the seam: the
    gateway's bracket adds it to the process's ledger."""
    from deepspeed_tpu.serving.gateway import ServingGateway
    from tests.unit.test_gateway import FakeBackend

    clock = MsClock()
    gw = ServingGateway(FakeBackend(), {}, clock=clock)
    with gw._bracket("pump_idle", ledger="pump_idle"):
        clock.advance(250)
    assert fresh_ledger.seconds["pump_idle"] == pytest.approx(0.25)
    assert "pump_idle" not in fresh_ledger.snapshot()["phases"]


# ---------------------------------------------------------------------------
# (e) on the profiler's clock

def test_a_profiler_session_sees_startup_gc_and_the_pumps_turn(fresh_ledger):
    from deepspeed_tpu.serving import ServingEngine
    from deepspeed_tpu.serving.gateway import ServingGateway

    box = {}

    def run():
        _, engine = _tiny_serving(serving=_SERVING)
        box["srv"] = srv = ServingEngine(engine)
        srv.submit([1, 2, 3], max_new_tokens=2)
        srv.drain()
        box["gw"] = gw = ServingGateway(
            srv, {"port": 0, "pump": True, "poll_secs": 0.002}).start()
        req = srv.submit([4, 5, 6], max_new_tokens=3,
                         stream=lambda r, t, d: gc.collect())
        gw._wake.set()
        deadline = time.monotonic() + 30
        while not req.done and time.monotonic() < deadline:
            time.sleep(0.01)
        assert req.done

    try:
        events = _annotations(run)
    finally:
        box["gw"].close()
        box["srv"].destroy()
    names = {n for n, _, _ in events}
    assert {"ds.startup.inference_init", "ds.startup.serving_init",
            "ds.startup.pool", "ds.startup.weight_layouts",
            "ds.startup.program", "ds.startup.gateway_start",
            "ds.host.gc", "ds.gateway.pump_turn",
            "ds.gateway.pump_idle"} <= names, sorted(names)

    def inside(child, parent):
        spans = [(s, e) for n, s, e in events if n == parent]
        return [any(ps <= s and e <= pe for ps, pe in spans)
                for n, s, e in events if n == child]

    assert all(inside("ds.startup.pool", "ds.startup.serving_init"))
    # the pump's steps lie inside its turns, a collection inside a step
    assert any(inside("ds.serve.step", "ds.gateway.pump_turn"))
    assert any(inside("ds.host.gc", "ds.serve.step"))


def test_idle_gaps_names_the_collector_and_the_pumps_turn():
    """A hand-made trace: the device runs 1 ms in every 10; the benchmark
    sleeps over the whole window on the main thread; on the pump's thread
    a turn holds two steps, a collection inside the second, and the pump
    waits idle after it."""
    from perfbench import trace_reduce as tr

    ms = 1_000_000
    host = [(tr.WINDOW_ANNOTATION, 0, 100 * ms),
            ("perfbench.serve.wait_for_client", 0, 100 * ms),
            ("ds.gateway.pump_turn", 5 * ms, 60 * ms),
            ("ds.serve.step", 10 * ms, 8 * ms),
            ("ds.serve.step", 30 * ms, 30 * ms),
            ("ds.host.gc", 41 * ms, 9 * ms),
            ("ds.gateway.pump_idle", 66 * ms, 20 * ms)]
    device = [("%fusion", t * ms, ms) for t in range(0, 100, 10)]
    got = tr.idle_gaps(device, 0, 100 * ms, host)
    assert sum(got.values()) == 90 * ms
    assert got == {
        # [1, 10) and [21, 30): their middles lie in the turn and in no
        # step, so they are the turn's and no longer the benchmark's sleep
        "ds.gateway.pump_turn": (9 + 9) * ms,
        "ds.serve.step": (9 + 9 + 9) * ms,      # [11,20) [31,40) [51,60)
        "ds.host.gc": 9 * ms,                   # [41, 50): inside a step
        "ds.gateway.pump_idle": (9 + 9) * ms,   # [71,80) [81,90)
        # [61, 70) (its middle after the turn's end, before the idle
        # wait's start) and [91, 100): under no ds.* bracket of any thread
        "perfbench.serve.wait_for_client": (9 + 9) * ms}


# ---------------------------------------------------------------------------
# (f) the JSONL spans, the registry and the report

def test_the_new_span_names_are_registered():
    assert {"startup", "startup.import", "startup.inference_init",
            "startup.serving_init", "startup.pool", "startup.weight_layouts",
            "startup.gateway_start", "startup.initialize", "startup.params",
            "startup.state", "startup.program"} <= set(SPANS)


def test_tracing_on_emits_the_startup_trace_and_the_report_renders_it(
        fresh_ledger, tmp_path):
    from deepspeed_tpu.serving import ServingEngine
    from deepspeed_tpu.serving.gateway import ServingGateway
    from deepspeed_tpu.telemetry.events import load_all_events
    from tools.telemetry_report import render

    tele = {"enabled": True, "dir": str(tmp_path), "tracing": {"enabled": True},
            "metrics_file": str(tmp_path / "metrics.prom")}
    _, engine = _tiny_serving(serving=_SERVING, telemetry=tele)
    srv = ServingEngine(engine)
    srv.submit([1, 2, 3], max_new_tokens=2)
    srv.drain()
    gw = ServingGateway(srv, {"port": 0}).start()
    gw.close()
    snap = srv.stats()["startup"]
    gauges = srv.telemetry.metrics.snapshot()
    srv.destroy()
    events = [e for e in load_all_events(str(tmp_path / "telemetry.jsonl"))
              if e["kind"] == "span" and e["name"].startswith("startup")]
    assert len({e["data"]["trace"] for e in events}) == 1
    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e["data"])
    (root,) = by_name["startup"]
    assert root["parent"] is None
    assert root["end_ns"] - root["start_ns"] == pytest.approx(
        1e9 * snap["ready_s"], rel=1e-6)
    # (the package's import was stamped into the process's own ledger,
    # which ``fresh_ledger`` stands in for: no ``startup.import`` here)
    assert set(by_name) - {"startup"} == {
        "startup.inference_init", "startup.serving_init",
        "startup.pool", "startup.weight_layouts", "startup.program",
        "startup.gateway_start"}
    (serving_init,) = by_name["startup.serving_init"]
    assert by_name["startup.pool"][0]["parent"] == serving_init["span"]
    assert by_name["startup.inference_init"][0]["parent"] == root["span"]
    assert sorted(d["program"] for d in by_name["startup.program"]) == sorted(
        p["program"] for p in snap["programs"])
    # the registry: one gauge a phase, set once at ready
    text = str(gauges)
    assert "ds_startup_seconds" in text and "serving_init" in text
    # the report's waterfall
    out = render(str(tmp_path / "telemetry.jsonl"))
    assert "start-up:" in out and "startup.weight_layouts" in out
    assert "program=serving_decode" in out
