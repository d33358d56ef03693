"""The harness at a tiny size on the CPU: one last JSON line with the
contract's keys, cells found by name, data files consistent with
BENCHMARK.json. A CPU run proves nothing about speed; every line it prints
says ``"platform": "cpu"``."""

import copy
import io
import json
import os
import shutil
import sys
from contextlib import redirect_stdout

import pytest

from perfbench import byname
from perfbench import run as bench_run
from perfbench.byname import BenchError

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
                            "ttft_p95_ms", "decode_step_p50_ms",
                            "prefill_p50_ms", "tpot_prefill_blocked_share",
                            "step_host_share"}


@pytest.mark.parametrize("cell,metrics", [
    ("tiny-train", {"train_tok_s_chip", "setup_s"}),
    ("tiny-serve", SERVE_END_TO_END),
    ("tiny-serve-offline", SERVE_END_TO_END),    # all due at t = 0
    ("tiny-serve-burst", SERVE_END_TO_END),      # bursts of 3x the rate
    # a second family, cut in depth, found by its name alone
    ("tiny-alt-train", {"train_tok_s_chip", "setup_s"}),
    ("tiny-alt-serve", SERVE_END_TO_END),
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
    ("tiny-alt-train", set()),
    ("tiny-alt-serve", SERVE_PER_LAYER_OFF_CHIP),
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


def test_unknown_family_fails_by_name(bench_copy, tmp_path, capsys):
    """A configuration that names a family with no file: the run gives no
    result and says which families there are."""
    root, _ = bench_copy
    top = tmp_path / "perfbench"
    for folder in ("workloads", "traffic"):
        shutil.copytree(os.path.join(root, folder), top / folder)
    os.mkdir(top / "configs")
    config_file = _config_file(root, "tiny-gpt2")
    with open(top / "configs" / "tiny-gpt2.json", "w") as f:
        json.dump({**config_file, "family": "no-such-family"}, f)
    shutil.copy(os.path.join(os.path.dirname(root), "BENCHMARK.json"),
                tmp_path)
    rc = bench_run.main(["--workload", "tiny-train"], root=str(top))
    assert rc != 0
    err = capsys.readouterr().err
    assert "no-such-family" in err and "'gpt2'" in err and "tiny-alt" in err


def test_code_found_by_name_is_a_module_like_any_other(bench_copy, tmp_path,
                                                       monkeypatch):
    """All four kinds come through their package: one module object a
    name, known to ``sys.modules``, so that a file may hold what looks its
    own module up there (a dataclass under postponed annotations does)."""
    from perfbench import families
    from perfbench.families import gpt2
    from perfbench.jobs import serve
    from perfbench.readers import percentile

    (tmp_path / "with-dataclass.py").write_text(
        "from __future__ import annotations\n"
        "from dataclasses import dataclass\n\n\n"
        "@dataclass\nclass Shapes:\n    heads: int\n    head_dim: int = 64\n")
    monkeypatch.setattr(families, "__path__",
                        [*families.__path__, str(tmp_path)])
    mod = byname.module("families", "with-dataclass")
    assert mod.Shapes(heads=25).head_dim == 64
    assert byname.module("families", "with-dataclass") is mod
    monkeypatch.delitem(sys.modules, mod.__name__)
    root, _ = bench_copy
    assert bench_run.load_cell("tiny-train", root)["family"] is gpt2
    # what a test-only family imports is the harness's copy of it too
    alt = bench_run.load_cell("tiny-alt-train", root)["family"]
    assert alt is sys.modules["perfbench.families.tiny-alt"]
    assert alt.gpt2 is gpt2
    assert byname.module("jobs", "serve") is serve
    assert byname.module("readers", "percentile") is percentile
    for kind, has in (("jobs", "train"), ("readers", "kernel_roofline")):
        with pytest.raises(BenchError, match=f"no-such.*{has}"):
            byname.module(kind, "no-such")


def test_a_cell_serves_the_longest_context_its_traffic_sends(bench_copy):
    """The mix's ``max_total`` sizes the pool; one over the family's
    largest gives no result."""
    from perfbench.jobs import serve

    root, _ = bench_copy
    cell = bench_run.load_cell("tiny-alt-serve", root)
    assert cell["traffic_file"]["max_total"] == 64 < \
        cell["family"].max_context(cell["config_file"]) == 96
    assert "max_context" not in cell["serve"]
    cell["traffic_file"]["max_total"] = 97
    with pytest.raises(BenchError, match="tiny-chat.*97.*at most 96"):
        serve.setup(cell, 0, {})


def _config_file(root, name):
    with open(os.path.join(root, "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,declared", [
    ("gpt2-medium", []), ("gpt2-xl", []), ("tiny-gpt2", []),
    ("tiny-alt", ["num_hidden_layers"]),
    ("tiny-alt", None),     # a configuration BENCHMARK.json does not list
])
def test_configurations_keep_to_the_form_of_a_cut(bench_copy, name, declared):
    root, _ = bench_copy
    bench_run.check_cut(_config_file(root, name), declared)


def _drop(key):
    return lambda c: c.pop(key)


@pytest.mark.parametrize("name,declared,alter,says", [
    # uncut, and yet it says what it was cut from
    ("gpt2-xl", [], lambda c: c.update(published={"n_layer": 96}),
     "without a key in reduced"),
    ("gpt2-xl", [], lambda c: c.update(deployment="depth only"),
     "without a key in reduced"),
    # cut, and it does not say what it stands for, or from what
    ("tiny-alt", ["num_hidden_layers"], _drop("deployment"), "deployment"),
    ("tiny-alt", ["num_hidden_layers"], lambda c: c.update(deployment=" "),
     "deployment"),
    ("tiny-alt", ["num_hidden_layers"], _drop("published"), "published"),
    ("tiny-alt", ["num_hidden_layers"],
     lambda c: c["published"].update(vocab_size=1024), "published"),
    ("tiny-alt", ["num_hidden_layers"],
     lambda c: c["published"].update(num_hidden_layers=2),
     "published value in model"),
    # a key in reduced that model lacks
    ("tiny-alt", ["n_layer"], lambda c: c.update(reduced=["n_layer"]),
     "model lacks"),
    # the file and BENCHMARK.json disagree, either way
    ("tiny-alt", [], lambda c: None, "BENCHMARK.json"),
    ("gpt2-xl", ["n_layer"], lambda c: None, "BENCHMARK.json"),
])
def test_a_malformed_cut_is_refused(bench_copy, name, declared, alter, says):
    root, _ = bench_copy
    config_file = copy.deepcopy(_config_file(root, name))
    alter(config_file)
    with pytest.raises(BenchError, match=says):
        bench_run.check_cut(config_file, declared)


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
        # a cut configuration says what it was cut from and what it stands
        # for; an uncut one is held to [] on both sides and says neither
        bench_run.check_cut(cell["config_file"],
                            configs[cell["config"]]["reduced"])
        assert cell["family"].vocab_size(cell["config_file"]) > 0
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
    the other serving metrics without a word in any file of theirs; a second
    family with its cut configuration, its cells and a kernel's arithmetic),
    and its BENCHMARK.json by added entries and added names in ``workloads``
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
    for folder, new in (("families", {"tiny-alt.py"}),
                        ("kernels", {"tiny-matmul.py"}),
                        ("configs", {"tiny-gpt2.json", "tiny-alt.json"}),
                        ("layer_metrics", {"tiny-matmul_roofline.json"})):
        assert set(os.listdir(os.path.join(root, folder))) - \
            set(os.listdir(os.path.join(PERFBENCH, folder))) == new
    committed = _bench()
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for old, new in zip(committed[kind], bench[kind]):
            grown = new.get("workloads", [])[:len(old.get("workloads", []))]
            assert {**new, "workloads": grown} == {**old, "workloads": grown} \
                and grown == old.get("workloads", []), (kind, old["name"])
