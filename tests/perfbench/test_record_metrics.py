"""The per-layer metrics that read the program's per-request record
(``decode_step_p50_ms``, ``prefill_p50_ms``, ``tpot_prefill_blocked_share``,
``step_host_share``): each reader on hand-made ``facts`` with the values
worked out by hand, and the four metrics in the ``--trace 1`` line of the
tiny serve cell."""

import json
import os

import pytest

from perfbench import run as bench_run
from perfbench.readers import record_percentile, record_share

from .conftest import REPO
from .test_harness import _run

NEW_METRICS = {"decode_step_p50_ms", "prefill_p50_ms",
               "tpot_prefill_blocked_share", "step_host_share"}


def _req(ok=True, **record):
    return {"ok": ok, "record": record}


# three finished requests, one that failed, one whose record predates the
# fields (an older program), one that took no decode step
FACTS = {"requests": [
    _req(prefill_ms=200.0, decode_ms=1800.0, decode_steps=10,
         blocked_ms=400.0, host_ms=20.0),
    _req(prefill_ms=100.0, decode_ms=600.0, decode_steps=4,
         blocked_ms=0.0, host_ms=30.0),
    _req(prefill_ms=400.0, decode_ms=2000.0, decode_steps=8,
         blocked_ms=100.0, host_ms=50.0),
    _req(ok=False, prefill_ms=9999.0, decode_ms=9999.0, decode_steps=1,
         blocked_ms=9999.0, host_ms=9999.0),
    _req(queue_ms=3.0),
    _req(prefill_ms=150.0, decode_ms=0.0, decode_steps=0, blocked_ms=0.0,
         host_ms=0.0),
]}
WHOLE = ["decode_ms", "blocked_ms", "host_ms"]


@pytest.mark.parametrize("spec,expected", [
    # prefill_ms of the four finished records that have it: 100 150 200 400
    ({"field": "prefill_ms", "q": 50}, 175.0),
    ({"field": "prefill_ms", "q": 100}, 400.0),
    # decode_ms / decode_steps: 180, 150, 250 (no step taken: left out)
    ({"field": "decode_ms", "per": "decode_steps", "q": 50}, 180.0),
    ({"field": "decode_ms", "per": "decode_steps", "q": 0}, 150.0),
])
def test_record_percentile_by_hand(spec, expected):
    assert record_percentile.read(spec, FACTS) == pytest.approx(expected)


@pytest.mark.parametrize("field,expected", [
    # the whole: 2220 + 630 + 2150 + 0 = 5000
    ("blocked_ms", 100.0 * 500.0 / 5000.0),
    ("host_ms", 100.0 * 100.0 / 5000.0),
    ("decode_ms", 100.0 * 4400.0 / 5000.0),
])
def test_record_share_by_hand(field, expected):
    spec = {"field": field, "of": WHOLE}
    assert record_share.read(spec, FACTS) == pytest.approx(expected)


@pytest.mark.parametrize("facts", [
    {},                                             # no requests at all
    {"requests": []},
    {"requests": [_req(queue_ms=3.0, ttft_ms=5.0)]},    # the parent's record
    {"requests": [_req(ok=False, prefill_ms=1.0, decode_ms=1.0,
                       decode_steps=1, blocked_ms=1.0, host_ms=1.0)]},
    {"requests": [{"ok": True}]},                   # no record came back
])
def test_nothing_to_read_is_none(facts):
    assert record_percentile.read({"field": "prefill_ms", "q": 50},
                                  facts) is None
    assert record_percentile.read(
        {"field": "decode_ms", "per": "decode_steps", "q": 50}, facts) is None
    assert record_share.read({"field": "host_ms", "of": WHOLE},
                             facts) is None


def test_the_four_metrics_are_declared_like_queue_p95_ms():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        declared = {m["name"]: m for m in json.load(f)["per_layer"]}
    assert NEW_METRICS <= set(declared)
    like = declared["queue_p95_ms"]
    for name in NEW_METRICS:
        m = declared[name]
        assert {k: m[k] for k in ("source", "layer", "moves", "workloads")} \
            == {k: like[k] for k in ("source", "layer", "moves", "workloads")}
        assert m["better"] == "lower"
        spec = bench_run._load_json(os.path.join(
            REPO, "perfbench", "layer_metrics", f"{name}.json"), name)
        assert spec["reader"] in ("record_percentile", "record_share")


def test_the_four_metrics_are_host_side_times_read_off_the_chip_too():
    """Their files say ``"needs_chip": false``, so the CPU rehearsal of
    every serving cell runs their readers through the whole harness."""
    for name in NEW_METRICS:
        spec = bench_run._load_json(os.path.join(
            REPO, "perfbench", "layer_metrics", f"{name}.json"), name)
        assert spec["needs_chip"] is False


def test_tiny_serve_trace_line_carries_the_four_metrics(bench_copy):
    root, _ = bench_copy
    rc, lines = _run(root, "--workload", "tiny-serve", "--seed",
                     "3000000007", "--seconds", "2", "--trace", "1")
    assert rc == 0
    last = json.loads(lines[-1])
    metrics = last["metrics"]
    assert NEW_METRICS <= set(metrics), sorted(metrics)
    assert metrics["decode_step_p50_ms"]["value"] > 0
    assert metrics["prefill_p50_ms"]["value"] > 0
    blocked = metrics["tpot_prefill_blocked_share"]["value"]
    host = metrics["step_host_share"]["value"]
    assert 0 <= blocked <= 100 and 0 <= host <= 100
    assert blocked + host < 100     # the rest is inside decode programs
    assert {metrics[m]["unit"] for m in NEW_METRICS} == {"ms", "%"}
