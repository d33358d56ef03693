"""The harness at a tiny size on the CPU: one last JSON line with the
contract's keys, cells found by name, data files consistent with
BENCHMARK.json. A CPU run proves nothing about speed; every line it prints
says ``"platform": "cpu"``."""

import io
import json
import os
from contextlib import redirect_stdout

import pytest

from perfbench import run as bench_run

from .conftest import REPO, TINY_CELLS

PERFBENCH = os.path.join(REPO, "perfbench")
LAST_LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _run(root, *argv):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = bench_run.main(list(argv), root=root)
    lines = [ln for ln in out.getvalue().splitlines() if ln.strip()]
    return rc, lines


def _bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


SERVE_END_TO_END = {"tpot_p95_ms", "served_tok_s", "setup_s"}
# off the chip no number is written under a device metric's name, so the
# traced CPU runs report only counts and host-side times
SERVE_PER_LAYER_OFF_CHIP = {"queue_p95_ms", "decode_occupancy", "ttft_p50_ms",
                            "ttft_p95_ms"}


@pytest.mark.parametrize("cell,metrics", [
    ("tiny-train", {"train_tok_s_chip", "setup_s"}),
    ("tiny-serve", SERVE_END_TO_END),
    ("tiny-serve-offline", SERVE_END_TO_END),    # all due at t = 0
    ("tiny-serve-burst", SERVE_END_TO_END),      # bursts of 3x the rate
])
def test_untraced_run_prints_the_end_to_end_line(bench_copy, cell, metrics):
    root, _ = bench_copy
    rc, lines = _run(root, "--workload", cell, "--seed", "3000000001",
                     "--seconds", "2", "--trace", "0")
    assert rc == 0
    last = json.loads(lines[-1])
    assert LAST_LINE_KEYS <= set(last)
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert set(last["metrics"]) == metrics
    for m in last["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert last["device"]["platform"] == "cpu"   # and so proves nothing
    assert {"kind", "count", "memory_peak_bytes"} <= set(last["device"])


@pytest.mark.parametrize("cell,reported", [
    ("tiny-train", set()),
    ("tiny-serve", SERVE_PER_LAYER_OFF_CHIP),
])
def test_traced_run_prints_per_layer_metrics_and_breakdown(bench_copy, cell,
                                                           reported):
    """The tiny cells report the committed cells' metrics because the
    copy's BENCHMARK.json lists them under those metrics' ``workloads``:
    no file of the metric or the cell says so again."""
    root, _ = bench_copy
    rc, lines = _run(root, "--workload", cell, "--seed", "7",
                     "--seconds", "2", "--trace", "1")
    assert rc == 0
    last = json.loads(lines[-1])
    assert LAST_LINE_KEYS | {"breakdown"} <= set(last)
    assert set(last["metrics"]) == reported
    assert all(m["value"] >= 0 for m in last["metrics"].values())
    assert last["device"]["platform"] == "cpu"
    assert last["device"]["busy_s"] > 0
    assert last["device"]["window_s"] >= last["device"]["busy_s"]
    for key in ("device_ops", "idle_gaps"):
        assert len(last["breakdown"][key]) <= 10


def test_unknown_workload_fails_by_name(bench_copy, capsys):
    root, _ = bench_copy
    rc = bench_run.main(["--workload", "no-such-cell"], root=root)
    assert rc != 0
    err = capsys.readouterr().err
    assert "no-such-cell" in err and "tiny-train" in err


@pytest.fixture(params=["committed", "with_cells_added"])
def benchmark(request, bench_copy):
    """(root of ``perfbench``, BENCHMARK.json as a dict): the repo's own,
    and the copy a later PR's procedure made of it."""
    if request.param == "committed":
        return PERFBENCH, _bench()
    return bench_copy


def test_every_cell_file_names_what_exists_and_is_declared(benchmark):
    root, bench = benchmark
    cells = {w["name"]: w for w in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}
    folder = os.path.join(root, "workloads")
    for fname in sorted(os.listdir(folder)):
        name = fname[:-5]
        cell = bench_run.load_cell(name, root)   # config, traffic exist
        assert os.path.isfile(os.path.join(root, "jobs", f"{cell['job']}.py"))
        if name not in cells:
            continue  # a cell kept for later (PERF.md, Open questions)
        entry = cells[name]
        assert entry["config"] == cell["config"]
        assert entry["traffic"] == cell["traffic"]
        assert entry["chips"] == cell["chips"]
        assert entry["why"] == cell["why"] and len(entry["why"]) <= 200
        assert configs[cell["config"]]["file"] == \
            f"perfbench/configs/{cell['config']}.json"
        assert cell["config_file"]["source"] == \
            configs[cell["config"]]["source"]
        assert cell["config_file"]["reduced"] == \
            configs[cell["config"]]["reduced"] == []
    assert set(cells) <= {f[:-5] for f in os.listdir(folder)}


def test_declared_metrics_resolve_for_every_cell(benchmark):
    """BENCHMARK.json alone says which cells report a metric, its unit, its
    layer and what it moves; ``layer_metrics/<metric>.json`` says only how
    it is read. What the harness resolves for a cell is what BENCHMARK.json
    lists for it."""
    root, bench = benchmark
    cells = [w["name"] for w in bench["workloads"]]
    end_to_end = {m["name"]: m for m in bench["end_to_end"]}
    declared = {m["name"]: m for m in bench["per_layer"]}
    on_disk = {f[:-5] for f in os.listdir(os.path.join(root, "layer_metrics"))}
    assert on_disk == set(declared)
    for name in on_disk:
        with open(os.path.join(root, "layer_metrics", f"{name}.json")) as f:
            how = json.load(f)
        # each fact is written once: none of BENCHMARK.json's in the file
        assert not set(how) & {"layer", "unit", "moves", "source", "cells",
                               "workloads", "better"}, name
        assert os.path.isfile(os.path.join(root, "readers",
                                           f"{how['reader']}.py"))
    for cell in cells:
        specs = bench_run.layer_metric_specs(bench_run.load_cell(cell, root),
                                             root)
        listed = {n for n, m in declared.items()
                  if cell in m.get("workloads", cells)}
        assert {s["name"] for s in specs} == listed
        assert listed, f"{cell} reports no per-layer metric"
        reports = {n for n, m in end_to_end.items()
                   if cell in m.get("workloads", cells)}
        assert "setup_s" in reports and len(reports) >= 2
        # a per-layer metric moves an end-to-end metric its cell reports
        for s in specs:
            assert s["moves"] in reports, (cell, s["name"])


def test_a_new_cell_is_only_new_files(bench_copy):
    """The copy the other tests ran in differs from ``perfbench/`` by added
    files alone (the tiny cells, one of which takes up ``queue_p95_ms`` and
    the other serving metrics without a word in any file of theirs), and
    its BENCHMARK.json by added entries and added names in ``workloads``
    lists alone."""
    root, bench = bench_copy
    for folder, _, files in os.walk(PERFBENCH):
        if "__pycache__" in folder:
            continue
        for f in files:
            src = os.path.join(folder, f)
            dst = os.path.join(root, os.path.relpath(src, PERFBENCH))
            with open(src, "rb") as a, open(dst, "rb") as b:
                assert a.read() == b.read(), f"{src} was edited in the copy"
    added = {f[:-5] for f in os.listdir(os.path.join(root, "workloads"))
             if f.startswith("tiny-")}
    assert added == set(TINY_CELLS)
    committed = _bench()
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for old, new in zip(committed[kind], bench[kind]):
            grown = new.get("workloads", [])[:len(old.get("workloads", []))]
            assert {**new, "workloads": grown} == {**old, "workloads": grown} \
                and grown == old.get("workloads", []), (kind, old["name"])
