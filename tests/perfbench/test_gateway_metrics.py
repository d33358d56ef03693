"""The front door's four per-layer metrics (``gateway_ingress_p95_ms``,
``gateway_egress_p95_ms``, ``gateway_write_p50_ms``,
``ttft_server_p50_ms``): declared alike in ``BENCHMARK.json``, read by
``record_percentile`` from the gateway's fields of the per-request record,
and printed in the ``--trace 1`` line of the tiny serve cell.

Their files say ``"needs_chip": true`` (``test_harness.py`` holds the exact
set of metrics a tiny serve cell prints off the chip), so the run through
the whole harness is made on a private copy with the flag flipped."""

import json
import os
import shutil

import pytest

from perfbench import run as bench_run
from perfbench.readers import record_percentile

from .conftest import REPO
from .test_harness import _run

DOOR_METRICS = {
    "gateway_ingress_p95_ms": {"field": "ingress_ms", "q": 95},
    "gateway_egress_p95_ms": {"field": "egress_mean_ms", "q": 95},
    "gateway_write_p50_ms": {"field": "write_ms", "per": "new_tokens",
                             "q": 50},
    "ttft_server_p50_ms": {"field": "ttft_wire_ms", "q": 50},
}
SERVE_CELLS = ["serve-xl-chat", "serve-mimo-hybrid-mixed"]


def _spec(name, root=os.path.join(REPO, "perfbench")):
    return bench_run._load_json(
        os.path.join(root, "layer_metrics", f"{name}.json"), name)


def _declared():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted(DOOR_METRICS))
def test_metric_is_declared_for_both_serve_cells(name):
    bench = _declared()
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": "ms", "better": "lower",
                     "source": "program_span", "layer": "front door",
                     "moves": "served_tok_s", "workloads": SERVE_CELLS}
    # the one end-to-end metric that both cells report
    (moved,) = [m for m in bench["end_to_end"]
                if m["name"] == entry["moves"]]
    assert set(SERVE_CELLS) <= set(moved["workloads"])


@pytest.mark.parametrize("name", sorted(DOOR_METRICS))
def test_metric_file_is_record_percentile_and_needs_the_chip(name):
    assert _spec(name) == {"reader": "record_percentile", "needs_chip": True,
                           **DOOR_METRICS[name]}


def test_the_four_come_last_and_nothing_else_was_declared_for_them():
    """Additions only: the four entries end ``per_layer``, and the older
    metrics of the layer, which time it from outside, stay."""
    per_layer = _declared()["per_layer"]
    assert [m["name"] for m in per_layer[-4:]] == [
        "gateway_ingress_p95_ms", "gateway_egress_p95_ms",
        "gateway_write_p50_ms", "ttft_server_p50_ms"]
    door = [m["name"] for m in per_layer if m["layer"] == "front door"]
    assert door[:2] == ["ttft_p50_ms", "ttft_p95_ms"] and len(door) == 6


def _req(ok=True, **record):
    return {"ok": ok, "record": record}


# three streamed requests, one that failed, a JSON reply (no flush a
# token) and the parent's record, which has no field of the gateway's
FACTS = {"requests": [
    _req(ingress_ms=1.0, egress_mean_ms=0.2, write_ms=4.0, new_tokens=100,
         ttft_wire_ms=30.0),
    _req(ingress_ms=3.0, egress_mean_ms=0.4, write_ms=9.0, new_tokens=300,
         ttft_wire_ms=20.0),
    _req(ingress_ms=2.0, egress_mean_ms=2.4, write_ms=10.0, new_tokens=200,
         ttft_wire_ms=40.0),
    _req(ok=False, ingress_ms=999.0, egress_mean_ms=999.0, write_ms=999.0,
         new_tokens=1, ttft_wire_ms=999.0),
    _req(ingress_ms=5.0, egress_mean_ms=None, write_ms=None, new_tokens=8,
         ttft_wire_ms=None),
    _req(queue_ms=3.0, ttft_ms=5.0, new_tokens=8),
]}


@pytest.mark.parametrize("name,expected", [
    # ingress of the four finished records that have it: 1 2 3 5
    ("gateway_ingress_p95_ms", 4.7),
    # mean waits 0.2 0.4 2.4
    ("gateway_egress_p95_ms", 2.2),
    # write a token: 0.04 0.03 0.05
    ("gateway_write_p50_ms", 0.04),
    ("ttft_server_p50_ms", 30.0),
])
def test_reader_by_hand(name, expected):
    assert record_percentile.read(_spec(name), FACTS) \
        == pytest.approx(expected)


@pytest.mark.parametrize("name", sorted(DOOR_METRICS))
def test_parent_records_read_as_nothing(name):
    """Laid over a program that lacks the fields (the parent commit) the
    reader returns None and the line leaves the metric out."""
    facts = {"requests": [_req(queue_ms=3.0, ttft_ms=5.0, new_tokens=8)]}
    assert record_percentile.read(_spec(name), facts) is None


@pytest.fixture(scope="module")
def flipped_copy(bench_copy, tmp_path_factory):
    """The copied ``perfbench`` once more, the four files saying
    ``"needs_chip": false``."""
    root, _ = bench_copy
    top = tmp_path_factory.mktemp("bench_door")
    mine = os.path.join(top, "perfbench")
    shutil.copytree(root, mine, ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(os.path.dirname(root), "BENCHMARK.json"), top)
    for name in DOOR_METRICS:
        path = os.path.join(mine, "layer_metrics", f"{name}.json")
        with open(path) as f:
            spec = json.load(f)
        with open(path, "w") as f:
            json.dump({**spec, "needs_chip": False}, f)
    return mine


@pytest.fixture(scope="module")
def traced_line(flipped_copy):
    rc, lines = _run(flipped_copy, "--workload", "tiny-serve", "--seed",
                     "3000000011", "--seconds", "2", "--trace", "1")
    assert rc == 0
    return json.loads(lines[-1])


@pytest.mark.parametrize("name", sorted(DOOR_METRICS))
def test_tiny_serve_trace_line_carries_the_metric(traced_line, name):
    metric = traced_line["metrics"][name]
    assert metric["unit"] == "ms" and metric["value"] > 0


def test_tiny_serve_trace_line_keeps_what_it_printed(traced_line):
    from .test_harness import SERVE_PER_LAYER_OFF_CHIP

    assert set(traced_line["metrics"]) \
        == SERVE_PER_LAYER_OFF_CHIP | set(DOOR_METRICS)
    assert traced_line["correct"] is True and traced_line["failed"] == 0


def test_server_ttft_is_inside_the_clients(traced_line):
    """Accept -> first flush lies inside due -> first token event at the
    client: the rest is the generator's lateness, the connect and the
    accept loop."""
    metrics = traced_line["metrics"]
    assert metrics["ttft_server_p50_ms"]["value"] \
        <= metrics["ttft_p50_ms"]["value"]
    assert metrics["gateway_ingress_p95_ms"]["value"] \
        < metrics["ttft_p95_ms"]["value"]
