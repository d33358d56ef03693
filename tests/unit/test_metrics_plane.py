"""Live metrics plane + flight recorder (ISSUE 14).

Five tiers, the first four host-only (no jax on the hot path —
millisecond tier-1):

- the ``telemetry/metrics.Histogram`` merge/percentile edge cases the
  capacity model now leans on;
- the labeled registry (types, label cardinality bound, determinism),
  OpenMetrics exposition + parse round-trip, the stdlib endpoint
  (in-process and subprocess smoke), and the ``metrics_dump.py`` CLI;
- the flight recorder: ring bounds, atomic dumps, every trigger path
  (fault event, breaker trip, a REAL ``HangWatchdog`` firing), and the
  dump-tail-matches-the-JSONL-sink acceptance;
- manager/fleet wiring: training gauges through ``on_step_boundary``,
  the single-source exposed-comm contract (event field == span attr ==
  gauge), a fake-replica fleet under the PR 13 trace replay scraping
  byte-identically across two seeded runs, and
  ``CapacityModel.fit_snapshot``;
- heavy: a real ServingEngine's scrape (TTFT buckets, KV-pool
  occupancy) and the zero-overhead HLO pins (train step + decode).
"""

import json
import os
import subprocess
import sys
import time
import urllib.request

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))

from deepspeed_tpu.telemetry.flightrec import (FlightRecorder,  # noqa: E402
                                               find_dumps, is_trigger,
                                               load_dump)
from deepspeed_tpu.telemetry.metrics import (DEFAULT_BOUNDS,  # noqa: E402
                                             MS_BOUNDS, Histogram)
from deepspeed_tpu.telemetry.prom import (MetricsServer,  # noqa: E402
                                          parse_exposition,
                                          render_exposition,
                                          snapshot_from_file,
                                          write_textfile)
from deepspeed_tpu.telemetry.registry import (NAMES,  # noqa: E402
                                              NULL_REGISTRY, MetricError,
                                              MetricRegistry)


# ---------------------------------------------------------------------------
# Histogram edge cases (the capacity model's new load-bearing surface)
# ---------------------------------------------------------------------------
class TestHistogramEdgeCases:
    def test_empty_merge_is_identity(self):
        h = Histogram(MS_BOUNDS)
        h.observe_many([1.0, 5.0, 900.0])
        before = (list(h.counts), h.count, h.total, h.min, h.max,
                  h.percentile(50), h.percentile(95))
        h.merge(Histogram(MS_BOUNDS))
        after = (list(h.counts), h.count, h.total, h.min, h.max,
                 h.percentile(50), h.percentile(95))
        assert before == after

    def test_empty_merge_into_empty_stays_empty(self):
        h = Histogram(MS_BOUNDS).merge(Histogram(MS_BOUNDS))
        assert h.count == 0 and h.percentile(50) is None

    def test_single_bucket_saturation(self):
        """Every observation in ONE bucket: all percentiles collapse to
        that bucket (clamped to the true max — never above it)."""
        h = Histogram(bounds=[1, 2, 4, 8])
        for _ in range(1000):
            h.observe(3.0)   # all land in the (2, 4] bucket
        for q in (1, 50, 95, 99, 100):
            assert h.percentile(q) == 3.0  # min(bound 4, max 3.0)

    def test_overflow_bucket_percentile(self):
        """Ranks past the last bound land in the overflow bucket, whose
        'upper bound' is the true max (not infinity, not the last
        bound)."""
        h = Histogram(bounds=[1, 2])
        h.observe_many([0.5, 100.0, 200.0, 300.0])
        assert h.counts[-1] == 3            # overflow bucket holds 3
        assert h.percentile(99) == 300.0    # true max, not bound 2
        assert h.percentile(25) == 1.0      # first bucket's bound
        assert h.percentile(100) == 300.0

    def test_merge_of_disjoint_bucket_ranges(self):
        """Two histograms over the SAME ladder with observations in
        disjoint bucket ranges merge to the exact union."""
        lo, hi = Histogram(MS_BOUNDS), Histogram(MS_BOUNDS)
        lo.observe_many([0.02, 0.05, 0.1])      # sub-ms buckets
        hi.observe_many([5000.0, 9000.0])       # multi-second buckets
        lo.merge(hi)
        assert lo.count == 5
        assert lo.min == 0.02 and lo.max == 9000.0
        assert lo.total == pytest.approx(0.17 + 14000.0)
        # ranks: p40 (rank 2) still in the low range, p90 (rank 5) high
        assert lo.percentile(40) <= 0.0625
        assert lo.percentile(90) >= 5000.0
        # and the bucket counts are the exact sum, bucket by bucket
        again = Histogram(MS_BOUNDS)
        again.observe_many([0.02, 0.05, 0.1, 5000.0, 9000.0])
        assert lo.counts == again.counts

    def test_merge_rejects_foreign_ladder(self):
        with pytest.raises(ValueError, match="different"):
            Histogram(MS_BOUNDS).merge(Histogram(DEFAULT_BOUNDS))


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------
class TestRegistry:
    def test_counter_gauge_histogram_basics(self):
        r = MetricRegistry()
        r.counter("ds_steps_total").inc().inc(3)
        r.gauge("ds_fleet_overload").set(0.7)
        r.gauge("ds_fleet_overload").inc(0.1)
        r.histogram("ds_serving_ttft_ms").observe(12.0)
        snap = r.snapshot()
        assert snap["ds_steps_total"]["series"][0]["value"] == 4
        assert snap["ds_fleet_overload"]["series"][0]["value"] == \
            pytest.approx(0.8)
        assert snap["ds_serving_ttft_ms"]["series"][0]["count"] == 1
        assert snap["ds_serving_ttft_ms"]["series"][0]["bounds"] == \
            list(MS_BOUNDS)

    def test_unregistered_name_raises(self):
        with pytest.raises(MetricError, match="NAMES"):
            MetricRegistry().counter("ds_bogus_total")

    def test_type_conflict_raises(self):
        r = MetricRegistry()
        with pytest.raises(MetricError, match="registered as a counter"):
            r.gauge("ds_steps_total")

    def test_counter_cannot_decrease(self):
        r = MetricRegistry()
        with pytest.raises(MetricError, match="decrease"):
            r.counter("ds_steps_total").inc(-1)

    def test_labeled_family(self):
        r = MetricRegistry()
        g = r.gauge("ds_slo_burn_rate", ("slo", "window"))
        g.labels(slo="ttft", window="fast").set(2.0)
        g.labels(slo="ttft", window="slow").set(0.5)
        rows = r.snapshot()["ds_slo_burn_rate"]["series"]
        assert [row["labels"] for row in rows] == [
            {"slo": "ttft", "window": "fast"},
            {"slo": "ttft", "window": "slow"}]

    def test_label_name_mismatch_raises(self):
        r = MetricRegistry()
        g = r.gauge("ds_slo_burn_rate", ("slo", "window"))
        with pytest.raises(MetricError, match="label names"):
            g.labels(slo="ttft")
        with pytest.raises(MetricError, match="declares labels"):
            g.set(1.0)
        with pytest.raises(MetricError, match="declared with label"):
            r.gauge("ds_slo_burn_rate", ("slo",))

    def test_cardinality_bound_folds_into_overflow(self):
        """A label exploding in cardinality (the request-id-as-label
        mistake) degrades into one overflow series + a drop count —
        never unbounded memory."""
        r = MetricRegistry(max_label_sets=4)
        c = r.counter("ds_events_total", ("kind",))
        for i in range(20):
            c.labels(kind=f"k{i}").inc()
        fam = r.snapshot()["ds_events_total"]
        assert len(fam["series"]) == 5      # 4 real + 1 overflow
        over = [row for row in fam["series"]
                if row["labels"].get("overflow") == "true"]
        assert over and over[0]["value"] == 16
        assert fam["dropped_label_sets"] == 16

    def test_null_registry_is_inert(self):
        n = NULL_REGISTRY
        n.counter("anything_goes").inc()
        n.gauge("even_unregistered", ("x",)).labels(x="1").set(5)
        n.histogram("names").observe(1)
        assert n.snapshot() == {} and n.expose() == ""

    def test_names_table_covers_types(self):
        assert all(t in ("counter", "gauge", "histogram")
                   for t, _ in NAMES.values())


# ---------------------------------------------------------------------------
# exposition + parse
# ---------------------------------------------------------------------------
def _populated_registry():
    r = MetricRegistry()
    r.counter("ds_steps_total").inc(7)
    g = r.gauge("ds_slo_burn_rate", ("slo", "window"))
    g.labels(slo="ttft", window="fast").set(1.25)
    h = r.histogram("ds_serving_ttft_ms")
    h.observe(3.0)
    h.observe(700.0)
    return r


class TestExposition:
    def test_format_and_determinism(self):
        text = _populated_registry().expose()
        assert text == _populated_registry().expose()
        assert "# HELP ds_steps_total" in text
        assert "# TYPE ds_serving_ttft_ms histogram" in text
        assert 'ds_slo_burn_rate{slo="ttft",window="fast"} 1.25' in text
        assert 'ds_serving_ttft_ms_bucket{le="+Inf"} 2' in text
        assert "ds_serving_ttft_ms_sum 703" in text
        assert "ds_serving_ttft_ms_count 2" in text
        assert text.endswith("# EOF\n")

    def test_label_escaping(self):
        text = render_exposition({
            "ds_events_total": {"type": "counter", "help": "h",
                                "series": [{"labels":
                                            {"kind": 'a"b\\c\nd'},
                                            "value": 1}]}})
        assert 'kind="a\\"b\\\\c\\nd"' in text
        parsed = parse_exposition(text)
        assert parsed["ds_events_total"]["series"][0]["labels"][
            "kind"] == 'a"b\\c\nd'

    def test_parse_round_trip(self):
        r = _populated_registry()
        snap = parse_exposition(r.expose())
        assert snap["ds_steps_total"]["series"][0]["value"] == 7
        hist = snap["ds_serving_ttft_ms"]["series"][0]
        assert hist["count"] == 2 and hist["sum"] == 703.0
        # non-cumulative counts reconstruct the original buckets
        orig = r.snapshot()["ds_serving_ttft_ms"]["series"][0]
        assert hist["counts"] == orig["counts"]
        assert hist["bounds"] == orig["bounds"]

    def test_snapshot_from_file_sniffs_json_and_text(self, tmp_path):
        r = _populated_registry()
        pj = tmp_path / "snap.json"
        pj.write_text(json.dumps(r.snapshot()))
        pt = tmp_path / "metrics.prom"
        pt.write_text(r.expose())
        assert snapshot_from_file(str(pj))["ds_steps_total"][
            "series"][0]["value"] == 7
        assert snapshot_from_file(str(pt))["ds_steps_total"][
            "series"][0]["value"] == 7


# ---------------------------------------------------------------------------
# the endpoint
# ---------------------------------------------------------------------------
class TestMetricsServer:
    def test_bind_scrape_404_close(self):
        r = _populated_registry()
        srv = MetricsServer(r, port=0)
        try:
            assert srv.port > 0
            body = urllib.request.urlopen(srv.url, timeout=5).read()
            assert b"ds_steps_total 7" in body
            # the scrape itself is counted
            body2 = urllib.request.urlopen(srv.url, timeout=5).read()
            assert b"ds_scrapes_total 2" in body2
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(
                    srv.url.replace("/metrics", "/nope"), timeout=5)
            assert e.value.code == 404
        finally:
            srv.close()
        # closed means closed: the port no longer accepts
        with pytest.raises(Exception):
            urllib.request.urlopen(srv.url, timeout=0.5)

    def test_subprocess_smoke(self):
        """The satellite contract: bind port 0, one scrape, clean
        shutdown — in a fresh interpreter, end to end."""
        script = (
            "import urllib.request\n"
            "from deepspeed_tpu.telemetry.registry import MetricRegistry\n"
            "from deepspeed_tpu.telemetry.prom import MetricsServer\n"
            "r = MetricRegistry()\n"
            "r.counter('ds_steps_total').inc(3)\n"
            "s = MetricsServer(r, port=0)\n"
            "body = urllib.request.urlopen(s.url, timeout=10)"
            ".read().decode()\n"
            "assert 'ds_steps_total 3' in body, body\n"
            "s.close()\n"
            "print('SCRAPE_OK', s.port)\n")
        res = subprocess.run([sys.executable, "-c", script],
                             capture_output=True, text=True, cwd=REPO,
                             timeout=120)
        assert res.returncode == 0, res.stderr
        assert "SCRAPE_OK" in res.stdout

    def test_write_textfile_atomic(self, tmp_path):
        path = str(tmp_path / "sub" / "metrics.prom")
        write_textfile(path, "ds_steps_total 1\n")
        write_textfile(path, "ds_steps_total 2\n")
        assert open(path).read() == "ds_steps_total 2\n"
        assert [f for f in os.listdir(tmp_path / "sub")] == \
            ["metrics.prom"]  # no tmp orphans

    def test_metrics_dump_cli(self, tmp_path):
        prom = tmp_path / "metrics.prom"
        prom.write_text(_populated_registry().expose())
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "metrics_dump.py"),
             "--file", str(prom), "--grep", "ds_steps"],
            capture_output=True, text=True, cwd=REPO, timeout=120)
        assert out.returncode == 0, out.stderr
        assert "ds_steps_total 7" in out.stdout
        as_json = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "metrics_dump.py"),
             "--file", str(prom), "--json"],
            capture_output=True, text=True, cwd=REPO, timeout=120)
        snap = json.loads(as_json.stdout)
        assert snap["ds_serving_ttft_ms"]["series"][0]["count"] == 2
        missing = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "metrics_dump.py"),
             "--file", str(tmp_path / "nope.prom")],
            capture_output=True, text=True, cwd=REPO, timeout=120)
        assert missing.returncode == 1


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------
class TestFlightRecorder:
    def test_ring_is_bounded(self):
        rec = FlightRecorder("/tmp/unused", events=8, snapshots=2)
        for i in range(100):
            rec.record_event({"kind": "step", "name": "e", "step": i})
            rec.record_snapshot(i, {"s": i})
        assert len(rec.tail(100)) == 8
        assert rec.tail(100)[-1]["step"] == 99

    def test_dump_contents_and_atomicity(self, tmp_path):
        rec = FlightRecorder(str(tmp_path), events=16)
        for i in range(5):
            rec.record_event({"kind": "step", "name": "b", "step": i})
        rec.record_snapshot(4, {"ds_steps_total": {"series": []}})
        r = _populated_registry()
        path = rec.dump("fault:test", registry=r,
                        trigger={"kind": "fault", "name": "x"})
        assert path is not None and os.path.isdir(path)
        assert not [d for d in os.listdir(tmp_path)
                    if d.endswith(".tmp")]
        d = load_dump(path)
        assert d["meta"]["reason"] == "fault:test"
        assert d["meta"]["last_step"] == 4
        assert [e["step"] for e in d["events"]] == [0, 1, 2, 3, 4]
        assert d["snapshots"][0]["step"] == 4
        assert "ds_steps_total 7" in d["metrics_text"]
        assert find_dumps(str(tmp_path)) == [path]

    def test_dump_budget(self, tmp_path):
        rec = FlightRecorder(str(tmp_path), max_dumps=2)
        rec.record_event({"kind": "fault", "name": "x", "step": 1})
        assert rec.dump("a") and rec.dump("b")
        assert rec.dump("c") is None
        assert len(find_dumps(str(tmp_path))) == 2

    def test_trigger_table(self):
        assert is_trigger("fault", "sentinel.trip")
        assert is_trigger("fault", "watchdog.hang")
        assert is_trigger("router", "breaker.trip")
        assert not is_trigger("router", "failover")
        assert not is_trigger("step", "engine")
        # the recorder's own marker can never re-trigger a dump
        assert not is_trigger("fault", "flightrec.dump")

    def _telemetry(self, d, **over):
        from deepspeed_tpu.telemetry import Telemetry

        cfg = {"enabled": True, "dir": d, "memory": False,
               "flight_recorder": {"enabled": True, "on_sigterm": False}}
        cfg.update(over)
        return Telemetry(cfg)

    def test_fault_event_dumps_and_tail_matches_sink(self, tmp_path):
        """The acceptance contract: the dump's event tail is the SAME
        window the JSONL sink holds — byte-comparable records."""
        t = self._telemetry(str(tmp_path))
        for i in range(1, 6):
            t.on_step_boundary(i)
        t.emit("fault", "ckpt.fallback", step=5, tag="t5")
        dumps = find_dumps(str(tmp_path))
        assert len(dumps) == 1
        d = load_dump(dumps[0])
        sink = [json.loads(line) for line in
                open(os.path.join(str(tmp_path), "telemetry.jsonl"))
                if line.strip()]
        # the sink additionally carries the post-dump flightrec.dump
        # marker; up to that marker the two surfaces are identical
        marker = [e for e in sink if e["name"] == "flightrec.dump"]
        assert len(marker) == 1
        window = sink[:sink.index(marker[0])]
        assert d["events"] == window
        assert d["events"][-1]["name"] == "ckpt.fallback"
        t.close()

    def test_breaker_trip_dumps(self, tmp_path):
        t = self._telemetry(str(tmp_path))
        t.emit("router", "replica.state", step=1, to_state="tripped")
        assert not find_dumps(str(tmp_path))
        t.emit("router", "breaker.trip", step=1, replica=0)
        assert len(find_dumps(str(tmp_path))) == 1
        t.close()

    def test_real_watchdog_fire_dumps(self, tmp_path):
        """Chaos-injected watchdog fire: a REAL HangWatchdog (abort
        off) judges a stalled loop, emits its fault through the
        telemetry stream, and the flight recorder dumps — with the
        watchdog's own dump artifact alongside."""
        from deepspeed_tpu.runtime.resilience.watchdog import HangWatchdog

        t = self._telemetry(str(tmp_path))
        wd = HangWatchdog(
            timeout_secs=0.15, poll_secs=0.03, dump_dir=str(tmp_path),
            abort=False, tail_fn=t.tail,
            emit=lambda name, step=None, **data: t.emit(
                "fault", name, step=step, **data),
            flush=t.flush)
        wd.start()
        wd.notify(step=1)             # arm, then stall
        deadline = time.monotonic() + 5.0
        while not wd.fired and time.monotonic() < deadline:
            time.sleep(0.02)
        wd.stop()
        assert wd.fired
        dumps = find_dumps(str(tmp_path))
        assert len(dumps) == 1
        d = load_dump(dumps[0])
        assert d["meta"]["reason"] == "fault:watchdog.hang"
        assert d["events"][-1]["name"] == "watchdog.hang"
        t.close()

    def test_dump_reentrant_under_held_lock(self, tmp_path):
        """Signal-safety contract: a SIGTERM handler runs in the main
        thread between bytecodes — dump() must succeed even while that
        same thread already holds the recorder lock (RLock, not
        Lock)."""
        rec = FlightRecorder(str(tmp_path))
        rec.record_event({"kind": "step", "name": "x", "step": 1})
        with rec._lock:               # as if interrupted mid-append
            assert rec.dump("sigterm") is not None

    def test_sigterm_disarm(self, tmp_path):
        """``arm_sigterm`` returns a disarm handle; after disarm the
        chain link is inert (a closed Telemetry must not re-dump its
        stale ring on a later SIGTERM) and the previous disposition is
        still reached."""
        import signal as _signal

        from deepspeed_tpu.telemetry.flightrec import arm_sigterm

        calls = []
        prev_calls = []
        old = _signal.signal(_signal.SIGTERM,
                             lambda s, f: prev_calls.append(s))
        try:
            disarm = arm_sigterm(lambda: calls.append(1))
            assert disarm is not None
            handler = _signal.getsignal(_signal.SIGTERM)
            handler(_signal.SIGTERM, None)
            assert calls == [1] and prev_calls == [_signal.SIGTERM]
            disarm()
            handler(_signal.SIGTERM, None)
            assert calls == [1]                   # inert after disarm
            assert prev_calls == [_signal.SIGTERM] * 2   # chain intact
        finally:
            _signal.signal(_signal.SIGTERM, old)

    def test_manager_close_disarms_sigterm(self, tmp_path):
        import signal as _signal

        from deepspeed_tpu.telemetry import Telemetry

        # benign previous disposition: the chained handler must not be
        # able to re-raise a real SIGTERM into the test process
        old = _signal.signal(_signal.SIGTERM, lambda s, f: None)
        try:
            t = self._telemetry(str(tmp_path),
                                flight_recorder={"enabled": True,
                                                 "on_sigterm": True})
            assert t._sigterm_disarm is not None
            t.close()
            assert t._sigterm_disarm is None
            handler = _signal.getsignal(_signal.SIGTERM)
            if callable(handler):
                handler(_signal.SIGTERM, None)    # inert: no dump
            assert find_dumps(str(tmp_path)) == []
        finally:
            _signal.signal(_signal.SIGTERM, old)

    def test_zero_snapshots_config(self, tmp_path):
        rec = FlightRecorder(str(tmp_path), snapshots=0)
        rec.record_snapshot(1, {"x": 1})
        rec.record_event({"kind": "fault", "name": "x", "step": 1})
        d = load_dump(rec.dump("fault:x"))
        assert d["snapshots"] == [] and len(d["events"]) == 1


# ---------------------------------------------------------------------------
# manager wiring
# ---------------------------------------------------------------------------
class TestManagerWiring:
    def test_disabled_manager_has_null_registry(self):
        from deepspeed_tpu.telemetry import Telemetry

        t = Telemetry()
        assert t.metrics is NULL_REGISTRY
        assert t._recorder is None and t._metrics_server is None
        # enabled but unarmed: still the null registry (zero cost)
        t2 = Telemetry({"enabled": True, "jsonl": False,
                        "memory": False})
        assert t2.metrics is NULL_REGISTRY
        t2.close()

    def test_metrics_file_arms_without_server(self, tmp_path):
        from deepspeed_tpu.telemetry import Telemetry

        path = str(tmp_path / "metrics.prom")
        t = Telemetry({"enabled": True, "dir": str(tmp_path),
                       "jsonl": False, "memory": False,
                       "metrics_file": path})
        assert t.metrics is not NULL_REGISTRY
        assert t._metrics_server is None
        t.on_step_boundary(1)
        t.on_step_boundary(2)
        assert "ds_steps_total 2" in open(path).read()
        t.close()

    def test_step_boundary_feeds_training_gauges(self, tmp_path):
        from deepspeed_tpu.telemetry import Telemetry

        t = Telemetry({"enabled": True, "dir": str(tmp_path),
                       "jsonl": False, "memory": False,
                       "metrics_port": 0})
        for i in range(1, 4):
            t.on_step_boundary(i, samples=8)
        snap = t.metrics.snapshot()
        assert snap["ds_steps_total"]["series"][0]["value"] == 3
        assert snap["ds_samples_total"]["series"][0]["value"] == 24
        assert snap["ds_steps_per_sec"]["series"][0]["value"] > 0
        t.close()

    def test_compile_counters(self, tmp_path):
        from deepspeed_tpu.telemetry import Telemetry

        t = Telemetry({"enabled": True, "dir": str(tmp_path),
                       "jsonl": False, "memory": False,
                       "metrics_port": 0, "warmup_steps": 0})

        class FakeWatched:
            name = "decode[T=8]"

        class FakeCompiled:
            def as_text(self):
                raise RuntimeError("no hlo")

        t.warm = True
        for _ in range(2):
            t.record_compile(FakeWatched(), trace_secs=0.5,
                             compile_secs=1.5, compiled=FakeCompiled())
        snap = t.metrics.snapshot()
        fam = snap["ds_compiles_total"]["series"]
        assert fam == [{"labels": {"family": "decode"}, "value": 2}]
        assert snap["ds_retraces_after_warmup_total"]["series"][0][
            "value"] == 1
        assert snap["ds_compile_seconds_total"]["series"][0][
            "value"] == pytest.approx(4.0)
        t.close()


# ---------------------------------------------------------------------------
# fleet scrape acceptance (fake replicas under the PR 13 trace replay)
# ---------------------------------------------------------------------------
def _fleet_scrape(tmp_dir):
    """One seeded fake-replica fleet under the trace replayer, scraped
    live over HTTP at the end. Returns (exposition_text, dump_dirs)."""
    from tests.unit.test_fleet import FakeReplica, _fleet

    from deepspeed_tpu.serving.replay import (ReplayClock, TraceReplayer,
                                              synthesize_trace)
    from deepspeed_tpu.telemetry import Telemetry

    t = Telemetry({"enabled": True, "dir": tmp_dir, "memory": False,
                   "metrics_port": 0,
                   "flight_recorder": {"enabled": True,
                                       "on_sigterm": False}})
    clock = ReplayClock()
    fm, _ = _fleet([FakeReplica(), FakeReplica()], clock=clock,
                   telemetry=t, target_ttft_p95_ms=40.0,
                   target_shed_rate=0.05)
    trace = synthesize_trace(20, seed=11, base_rate=1.5,
                             bursts=[(5, 3, 5.0)])
    TraceReplayer(fm, trace, clock, step_secs=0.05, seed=3,
                  vocab_size=128, max_steps=2000).run()
    body = urllib.request.urlopen(t._metrics_server.url,
                                  timeout=5).read().decode()
    # drop the scrape self-counter: run A scrapes once, run B scrapes
    # once — identical — but keeping it in the comparison would couple
    # the test to urllib retry behavior
    text = "\n".join(line for line in body.splitlines()
                     if "ds_scrapes_total" not in line
                     and "ds_events_total" not in line)
    t.close()
    return text, find_dumps(tmp_dir)


class TestFleetScrapeAcceptance:
    def test_live_scrape_has_fleet_surfaces_and_is_deterministic(
            self, tmp_path):
        """A live HTTP scrape of a replayed fleet returns OpenMetrics
        text with per-replica health, SLO burn-rate/budget gauges and
        fleet state — and two identical seeded runs under fake clocks
        scrape byte-identically."""
        a, dumps_a = _fleet_scrape(str(tmp_path / "a"))
        b, _ = _fleet_scrape(str(tmp_path / "b"))
        for needle in (
                'ds_replica_health{replica="0",state="healthy"}',
                'ds_replica_health{replica="1",state="healthy"}',
                'ds_slo_burn_rate{slo="ttft",window="fast"}',
                'ds_slo_burn_rate{slo="shed",window="slow"}',
                'ds_slo_budget_remaining{slo="ttft"}',
                "ds_fleet_active_replicas 2",
                "# TYPE ds_fleet_replicas gauge"):
            assert needle in a, f"scrape missing {needle}"
        assert a == b, "fleet scrape is not bit-deterministic"
        assert dumps_a == []   # a clean run triggers no dumps

    def test_autoscaler_burn_rates_surface(self):
        from deepspeed_tpu.serving.autoscaler import Autoscaler

        a = Autoscaler({"target_ttft_p95_ms": 100.0,
                        "target_shed_rate": 0.1,
                        "fast_window_steps": 2, "slow_window_steps": 8})
        a.observe_requests([{"state": "finished", "ttft_ms": 500.0},
                            {"state": "shed"}])
        a.observe_step(0.5)
        rates = a.burn_rates()
        assert set(rates) == {"ttft", "shed"}
        # the one measured TTFT is over target: rate 1.0 / allowed 0.05
        assert rates["ttft"]["fast"] == pytest.approx(20.0)
        # 1 shed of 2 submits: rate 0.5 / allowed 0.1
        assert rates["shed"]["fast"] == pytest.approx(5.0)
        assert rates["ttft"]["slow"] == rates["ttft"]["fast"]
        assert a.budget_remaining()["ttft"] == 0.0


# ---------------------------------------------------------------------------
# capacity model: the snapshot-consuming path
# ---------------------------------------------------------------------------
class TestCapacityFitSnapshot:
    def test_fit_from_registry_snapshot(self):
        from deepspeed_tpu.serving.capacity import CapacityModel

        r = MetricRegistry()
        h = r.histogram("ds_serving_ttft_ms")
        for v in (10.0, 20.0, 900.0):
            h.observe(v)
        r.histogram("ds_serving_queue_ms").observe(5.0)
        r.gauge("ds_serving_queue_depth").set(2)
        r.gauge("ds_serving_slots_busy").set(2)
        r.gauge("ds_serving_slots_total").set(4)
        model = CapacityModel()
        used = model.fit_snapshot(r.snapshot())   # load from the gauges
        assert used == 4
        load = (2 + 2) / 4
        assert model.ttft_p95_at(load) == 900.0   # exact: true max rides
        assert model.queue_p95_at(load) == 5.0    # clamped to true max

    def test_fit_from_parsed_scrape(self):
        """The same merge works from a PARSED scrape (no min/max in the
        text format — the top bucket bound stands in, still a legal
        Histogram)."""
        from deepspeed_tpu.serving.capacity import CapacityModel

        r = MetricRegistry()
        h = r.histogram("ds_serving_ttft_ms")
        h.observe(10.0)
        h.observe(20.0)
        snap = parse_exposition(r.expose())
        model = CapacityModel()
        assert model.fit_snapshot(snap, load=0.25) == 2
        assert model.ttft_p95_at(0.25) == 32.0    # bucket upper bound

    def test_foreign_ladder_is_skipped_not_crashed(self):
        from deepspeed_tpu.serving.capacity import CapacityModel

        snap = {"ds_serving_ttft_ms": {
            "type": "histogram",
            "series": [{"labels": {}, "bounds": [1, 2, 4],
                        "counts": [1, 0, 0, 0], "count": 1,
                        "sum": 0.5, "min": 0.5, "max": 0.5}]}}
        model = CapacityModel()
        assert model.fit_snapshot(snap, load=0.5) == 0

    def test_merged_curve_matches_direct_observation(self):
        """Exactness contract: snapshot-merged evidence equals the same
        observations fed through observe() — bucket by bucket."""
        from deepspeed_tpu.serving.capacity import CapacityModel

        values = [1.0, 3.0, 50.0, 220.0, 7000.0]
        r = MetricRegistry()
        h = r.histogram("ds_serving_ttft_ms")
        for v in values:
            h.observe(v)
        via_snap = CapacityModel()
        via_snap.fit_snapshot(r.snapshot(), load=0.5)
        direct = CapacityModel()
        for v in values:
            direct.observe(0.5, ttft_ms=v)
        i = direct.bucket(0.5)
        assert via_snap._ttft[i].counts == direct._ttft[i].counts
        for q in (50, 95, 99):
            assert via_snap._ttft[i].percentile(q) == \
                direct._ttft[i].percentile(q)


# ---------------------------------------------------------------------------
# report tool integration
# ---------------------------------------------------------------------------
class TestReportIntegration:
    def test_prom_and_flightrec_sections(self, tmp_path):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import telemetry_report
        finally:
            sys.path.pop(0)
        from deepspeed_tpu.telemetry import Telemetry

        d = str(tmp_path)
        t = Telemetry({"enabled": True, "dir": d, "memory": False,
                       "metrics_port": 0,
                       "flight_recorder": {"enabled": True,
                                           "on_sigterm": False}})
        t.on_step_boundary(1)
        t.emit("fleet", "fleet.gauges", step=1, active=2, replicas=2,
               queue_depth=0, queue_capacity=8, overload=0.1,
               by_state={"healthy": 2},
               budget_remaining={"ttft": 0.9})
        t.metrics.gauge("ds_slo_budget_remaining", ("slo",)).labels(
            slo="ttft").set(0.75)
        t.emit("fault", "sentinel.trip", step=1, loss=9.0)
        prom_path = str(tmp_path / "metrics.prom")
        write_textfile(prom_path, t.metrics.expose())
        t.flush()
        t.close()
        prom = snapshot_from_file(prom_path)
        out = telemetry_report.render(
            os.path.join(d, "telemetry.jsonl"), prom=prom)
        # the fleet section reads the budget from the REGISTRY snapshot
        # (0.75), not the event gauge (0.9)
        assert "SLO budget remaining (registry): ttft: 0.75" in out
        assert "metrics registry:" in out
        assert "flight recorder dump: flightrec-" in out
        assert "reason: fault:sentinel.trip" in out
        # markdown mode renders too (smoke)
        md = telemetry_report.render(
            os.path.join(d, "telemetry.jsonl"), markdown=True, prom=prom)
        assert "| `ds_slo_budget_remaining` | gauge |" in md


# ---------------------------------------------------------------------------
# heavy: real engines — serving scrape + the zero-overhead HLO pins
# ---------------------------------------------------------------------------
@pytest.mark.heavy
class TestRealEngineMetrics:
    def test_serving_scrape_has_ttft_and_kv_pool(self, tmp_path):
        """A real ServingEngine with the plane armed scrapes TTFT
        histogram buckets, KV-pool occupancy and queue gauges."""
        import numpy as np

        from tests.unit.test_serving import _SERVING, _tiny_serving

        from deepspeed_tpu.serving import ServingEngine

        _, engine = _tiny_serving(
            serving=_SERVING,
            telemetry={"enabled": True, "dir": str(tmp_path),
                       "jsonl": False, "memory": False,
                       "metrics_port": 0})
        srv = ServingEngine(engine)
        rng = np.random.default_rng(0)
        srv.generate_batch([rng.integers(1, 128, 5),
                            rng.integers(1, 128, 9)], max_new_tokens=4)
        body = urllib.request.urlopen(
            srv.telemetry._metrics_server.url, timeout=10).read().decode()
        for needle in ("ds_serving_ttft_ms_bucket",
                       "ds_serving_ttft_ms_count 2",
                       'ds_serving_requests_total{outcome="finished"} 2',
                       "ds_kv_pool_occupancy",
                       'ds_kv_pool_blocks{tier="free"}',
                       "ds_serving_slots_total 3",
                       "ds_serving_tokens_total 8"):
            assert needle in body, f"scrape missing {needle}"
        srv.destroy()

    def test_spec_and_prefix_gauges_in_scrape(self, tmp_path):
        """With speculation + the prefix cache on, the scrape carries
        spec-decode acceptance and the prefix hit-rate gauge."""
        import numpy as np

        from tests.unit.test_serving import _SERVING, _tiny_serving

        from deepspeed_tpu.serving import ServingEngine

        _, engine = _tiny_serving(
            serving={**_SERVING, "prefix_cache": True,
                     "speculative": {"enabled": True,
                                     "proposer": "prompt_lookup",
                                     "num_speculative_tokens": 2}},
            telemetry={"enabled": True, "dir": str(tmp_path),
                       "jsonl": False, "memory": False,
                       "metrics_port": 0})
        srv = ServingEngine(engine)
        # lookup-friendly repetitive prompt; two shared-prefix prompts
        base = np.asarray([7, 8, 9, 7, 8, 9, 7, 8] * 2)
        srv.generate_batch([base, base.copy()], max_new_tokens=4)
        body = urllib.request.urlopen(
            srv.telemetry._metrics_server.url, timeout=10).read().decode()
        assert "ds_prefix_cache_hit_rate" in body
        assert "ds_spec_draft_tokens_total" in body
        assert "ds_spec_accepted_tokens_total" in body
        assert "ds_spec_acceptance_rate" in body
        snap = parse_exposition(body)
        drafts = snap["ds_spec_draft_tokens_total"]["series"][0]["value"]
        assert drafts > 0
        srv.destroy()

    def test_fleet_replay_scrape_has_all_surfaces(self, tmp_path):
        """The full acceptance shape: a real two-replica serving fleet
        under the PR 13 trace replay, scraped live over HTTP — one
        exposition carrying per-replica health, KV-pool occupancy, TTFT
        histogram buckets, spec-decode acceptance, and SLO burn-rate
        gauges."""
        import numpy as np  # noqa: F401 — parity with sibling tests

        from tests.unit.test_serving import _tiny_serving

        from deepspeed_tpu.serving import ServingEngine
        from deepspeed_tpu.serving.replay import (ReplayClock,
                                                  TraceReplayer,
                                                  synthesize_trace)
        from deepspeed_tpu.serving.router import (FleetManager,
                                                  ReplicaRouter)

        clock = ReplayClock()
        serving = {"block_size": 8, "decode_slots": 2,
                   "default_max_new_tokens": 4,
                   "speculative": {"enabled": True,
                                   "proposer": "prompt_lookup",
                                   "num_speculative_tokens": 2}}
        _, e0 = _tiny_serving(
            serving=serving,
            telemetry={"enabled": True, "dir": str(tmp_path),
                       "jsonl": False, "memory": False,
                       "metrics_port": 0})
        r0 = ServingEngine(e0, clock=clock)
        _, e1 = _tiny_serving(serving=serving)
        e1.params = e0.params
        r1 = ServingEngine(e1, clock=clock)
        router = ReplicaRouter([r0, r1], clock=clock)   # r0's telemetry
        fm = FleetManager(router, config={
            "min_replicas": 1, "max_replicas": 2,
            "target_ttft_p95_ms": 50.0, "target_shed_rate": 0.05})
        trace = synthesize_trace(4, seed=5, base_rate=1.0)
        TraceReplayer(fm, trace, clock, step_secs=0.05, seed=3,
                      vocab_size=64, max_steps=400).run()
        body = urllib.request.urlopen(
            r0.telemetry._metrics_server.url, timeout=10).read().decode()
        for needle in (
                'ds_replica_health{replica="0",state="healthy"} 1',
                'ds_replica_health{replica="1",state="healthy"} 1',
                "ds_kv_pool_occupancy",
                "ds_serving_ttft_ms_bucket",
                "ds_spec_draft_tokens_total",
                'ds_slo_burn_rate{slo="ttft",window="fast"}',
                'ds_slo_budget_remaining{slo="shed"}'):
            assert needle in body, f"fleet scrape missing {needle}"
        fm.destroy()

    def test_train_step_hlo_byte_identical_with_metrics(self, tmp_path):
        """Zero-overhead pin: metrics_file + flight_recorder change only
        host-side bookkeeping — the compiled train-step program is
        byte-identical to a config with NO telemetry at all."""
        from tests.unit.simple_model import random_dataset
        from tests.unit.test_telemetry import _engine

        from deepspeed_tpu.parallel.topology import reset_topology
        from deepspeed_tpu.utils.compat import compilation_cache_off

        x, y = random_dataset(64, 8)
        batch = (x[:32], y[:32])

        def step_hlo(engine):
            raw = engine._jit_micro
            raw = getattr(raw, "_fn", raw)
            engine((batch[0], batch[1]))
            # not from the persistent cache: an entry another test file's
            # worker wrote between the two compiles carries THAT file's
            # source lines in its text (seen once under xdist, PR 50)
            with compilation_cache_off():
                return raw.lower(
                    engine.state,
                    engine._shard_batch(batch)).compile().as_text()

        reset_topology()
        plain = _engine()
        plain_hlo = step_hlo(plain)
        reset_topology()
        metered = _engine(telemetry={
            "enabled": True, "jsonl": False, "memory": False,
            "metrics_file": str(tmp_path / "metrics.prom"),
            "flight_recorder": {"enabled": True, "on_sigterm": False}})
        metered_hlo = step_hlo(metered)
        assert plain_hlo == metered_hlo
        assert metered.telemetry.metrics is not NULL_REGISTRY
        metered.telemetry.close()

    def test_decode_hlo_byte_identical_with_metrics(self, tmp_path):
        """Zero-overhead pin, serving side: arming the metrics plane +
        recorder compiles the exact same decode program."""
        import jax.numpy as jnp

        from tests.unit.test_serving import _tiny_serving

        from deepspeed_tpu.serving import ServingEngine

        texts = []
        for telemetry in (None,
                          {"enabled": True, "dir": str(tmp_path),
                           "jsonl": False, "memory": False,
                           "metrics_file": str(tmp_path / "m.prom"),
                           "flight_recorder": {"enabled": True,
                                               "on_sigterm": False}}):
            _, eng = _tiny_serving(
                serving={"block_size": 8, "decode_slots": 2},
                telemetry=telemetry)
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
