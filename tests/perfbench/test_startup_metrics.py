"""The six per-layer metrics that read the process's ledger (ISSUE 54:
``deepspeed_tpu/telemetry/process_ledger.py``), PARKED as PRs 43, 45 and 49
parked theirs: an entry appended to ``per_layer`` makes
``test_gateway_metrics.py``'s pin of the four front-door entries to the end
false, and that file is the benchmark's. So the committed benchmark does
not declare them; the throw-away copy below does, as the ``benchmark`` PR
of ROADMAP R3(b) would: ``PARKED`` are its entries, ``cells_startup/`` the
files it adds (``metric.<name>.json`` under ``perfbench/layer_metrics/``,
``startup_seconds.py`` under ``perfbench/readers/``). ``lay_parked`` is
what the builder's chip runs use too (``tools/chip_startup_ledger.py``).

One rehearsal of the tiny serve cell on the CPU reads all six; a program
without the ledger (the parent) reads None."""

import importlib
import json
import os
import shutil
import sys

import pytest

from perfbench import readers as readers_package
from perfbench import run as bench_run

from .conftest import CELLS as TINY_CELLS_DIR
from .conftest import REPO
from .test_harness import _run

CELLS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "cells_startup")
SERVE_CELLS = ["serve-xl-chat", "serve-mimo-hybrid-mixed",
               "serve-lfm2-conv-chat", "serve-dsv2lite-mla-longdoc",
               "serve-granite-h-ssm-agents", "serve-kexaone-reasoning-out"]
_STARTUP = {"unit": "s", "better": "lower", "layer": "start-up",
            "moves": "setup_s"}
# (no ``workloads``: every cell reports ``setup_s``, those later PRs add too)
PARKED = [
    {"name": "startup_state_s", "source": "program_span", **_STARTUP},
    {"name": "startup_programs_s", "source": "program_span", **_STARTUP},
    {"name": "startup_lowering_s", "source": "program_counter", **_STARTUP},
    {"name": "startup_compile_s", "source": "program_counter", **_STARTUP},
    {"name": "startup_outside_s", "source": "program_span", **_STARTUP},
    {"name": "host_gc_share", "unit": "%", "better": "lower",
     "source": "program_span", "layer": "serving", "moves": "served_tok_s",
     "workloads": list(SERVE_CELLS)}]
CELL = "tiny-serve"


def lay_parked(top: str, source: str = REPO) -> str:
    """A copy of ``source``'s ``perfbench/`` and ``BENCHMARK.json`` under
    ``top`` with the parked files and entries in place; the copy's
    ``readers/`` goes on the package's ``__path__``. Returns the copy's
    ``perfbench``."""
    root = os.path.join(top, "perfbench")
    shutil.copytree(os.path.join(source, "perfbench"), root,
                    ignore=shutil.ignore_patterns("__pycache__"))
    for fname in sorted(os.listdir(CELLS)):
        dst = (os.path.join(root, "layer_metrics", fname[len("metric."):])
               if fname.startswith("metric.")
               else os.path.join(root, "readers", fname))
        assert not os.path.exists(dst), f"{dst} would overwrite a file"
        shutil.copy(os.path.join(CELLS, fname), dst)
    with open(os.path.join(source, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["per_layer"].extend(json.loads(json.dumps(PARKED)))
    with open(os.path.join(top, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    readers_package.__path__.append(os.path.join(root, "readers"))
    importlib.invalidate_caches()
    return root


@pytest.fixture(scope="module")
def parked_copy(tmp_path_factory):
    top = str(tmp_path_factory.mktemp("bench-startup"))
    root = lay_parked(top)
    # the tiny serve cell, as conftest.py adds it
    for fname, folder in (("config.tiny-gpt2.json", "configs"),
                          ("traffic.tiny-chat.json", "traffic"),
                          (f"workload.{CELL}.json", "workloads")):
        shutil.copy(os.path.join(TINY_CELLS_DIR, fname),
                    os.path.join(root, folder, fname.split(".", 1)[1]))
    with open(os.path.join(top, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(root, "configs", "tiny-gpt2.json")) as f:
        config_file = json.load(f)
    bench["configs"].append(
        {"name": "tiny-gpt2", "source": config_file["source"],
         "file": "perfbench/configs/tiny-gpt2.json",
         "reduced": config_file["reduced"], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "tiny-gpt2",
                               "traffic": "tiny-chat", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "serve-xl-chat" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    with open(os.path.join(top, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    yield root
    readers_package.__path__.pop()


@pytest.fixture(scope="module")
def rehearsed(parked_copy):
    """The tiny serve cell rehearsed ONCE, over a ledger of the test's own
    (this process has long been ``ready`` for some earlier test)."""
    import jax

    from deepspeed_tpu.telemetry import process_ledger

    own = process_ledger.ProcessLedger(
        annotate=jax.profiler.TraceAnnotation)
    was, process_ledger.LEDGER = process_ledger.LEDGER, own
    try:
        rc, lines = _run(parked_copy, "--workload", CELL, "--seed",
                         "5400000011", "--seconds", "2", "--trace", "1")
        snap = process_ledger.snapshot()
    finally:
        process_ledger.LEDGER = was
    assert rc == 0
    return json.loads(lines[-1]), snap


def test_the_six_are_declared_as_the_benchmark_pr_would(parked_copy):
    declared = {m["name"]: m for m in
                bench_run.declared_metrics(parked_copy)["per_layer"]}
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        committed = json.load(f)
    end_to_end = {m["name"] for m in committed["end_to_end"]}
    layers = {m["layer"] for m in committed["per_layer"]}
    for entry in PARKED:
        assert entry["name"] not in {m["name"]
                                     for m in committed["per_layer"]}
        # (the fixture's tiny cell takes up what ``serve-xl-chat`` reports)
        got = dict(declared[entry["name"]])
        if "workloads" in got:
            got["workloads"] = [w for w in got["workloads"] if w != CELL]
        assert got == entry
        assert entry["moves"] in end_to_end
        assert set(entry) <= {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
        spec = bench_run._load_json(os.path.join(
            parked_copy, "layer_metrics", f"{entry['name']}.json"), "metric")
        assert spec["needs_chip"] is False
        assert spec["reader"] in ("startup_seconds", "record_share")
    # ``serving`` is a layer the benchmark names; ``start-up`` is new
    assert "serving" in layers and "start-up" not in layers
    assert sorted(SERVE_CELLS) == sorted(
        w["name"] for w in committed["workloads"]
        if w["name"].startswith("serve-"))
    # every committed cell would report the five, a serve cell the sixth
    for cell in committed["workloads"]:
        names = {m["name"] for m in bench_run.metrics_of(
            cell["name"], list(declared.values()))}
        assert {e["name"] for e in PARKED[:5]} <= names
        assert ("host_gc_share" in names) == cell["name"].startswith("serve-")


def test_one_rehearsal_reads_all_six(rehearsed):
    last, snap = rehearsed
    assert last["correct"] and last["device"]["platform"] == "cpu"
    metrics = last["metrics"]
    assert {e["name"] for e in PARKED} <= set(metrics), sorted(metrics)
    value = {k: v["value"] for k, v in metrics.items()}
    assert all(metrics[e["name"]]["unit"] == e["unit"] for e in PARKED)
    top = snap["top_level"]
    assert value["startup_state_s"] == pytest.approx(
        top["inference_init"] + top["serving_init"])
    assert top["serving_init"] > 0 and top["program"] > 0
    assert value["startup_programs_s"] == pytest.approx(top["program"])
    assert value["startup_lowering_s"] == pytest.approx(
        snap["compile"]["trace_s"] + snap["compile"]["lower_s"])
    assert value["startup_lowering_s"] > 0
    assert value["startup_compile_s"] == pytest.approx(
        snap["compile"]["compile_s"])
    assert value["startup_compile_s"] >= 0
    assert value["startup_outside_s"] == pytest.approx(snap["outside_s"])
    assert value["startup_outside_s"] > 0
    assert 0 <= value["host_gc_share"] <= 100
    # the five tile: state + programs + outside (+ the gateway's start,
    # milliseconds) is the whole start-up
    assert (value["startup_state_s"] + value["startup_programs_s"]
            + value["startup_outside_s"] + top["gateway_start"]
            ) == pytest.approx(snap["ready_s"], abs=1e-4)
    # the warm-up's programs by name, and none first called in the window
    assert {"serving_decode", "serving_decode_feed"} <= {
        p["program"] for p in snap["programs"]}
    assert snap["late_programs"] == []


def test_a_program_without_the_ledger_reads_none(parked_copy, monkeypatch):
    """The parent: ``deepspeed_tpu.telemetry`` has no ``process_ledger``,
    and its records no ``gc_ms``."""
    reader = importlib.import_module("perfbench.readers.startup_seconds")
    monkeypatch.setitem(sys.modules,
                        "deepspeed_tpu.telemetry.process_ledger", None)
    import deepspeed_tpu.telemetry as telemetry_package

    monkeypatch.delattr(telemetry_package, "process_ledger", raising=False)
    for entry in PARKED[:5]:
        spec = bench_run._load_json(os.path.join(
            parked_copy, "layer_metrics", f"{entry['name']}.json"), "metric")
        assert reader.read(spec, {}) is None
    from perfbench.readers import record_share

    spec = bench_run._load_json(os.path.join(
        parked_copy, "layer_metrics", "host_gc_share.json"), "metric")
    parents = {"requests": [{"ok": True, "record": {
        "decode_ms": 10.0, "blocked_ms": 0.0, "host_ms": 1.0}}]}
    assert record_share.read(spec, parents) is None


def test_the_reader_sums_what_the_snapshot_has(parked_copy, monkeypatch):
    from deepspeed_tpu.telemetry import process_ledger

    reader = importlib.import_module("perfbench.readers.startup_seconds")
    snap = {"ready": True, "outside_s": 9.9,
            "top_level": {"inference_init": 2.2, "serving_init": 2.4,
                          "program": 14.8},
            "compile": {"trace_s": 3.1, "lower_s": 6.0, "compile_s": 0.0}}
    monkeypatch.setattr(process_ledger, "snapshot", lambda: dict(snap))
    read = lambda *keys: reader.read({"keys": list(keys)}, {})  # noqa: E731
    assert read("top_level/inference_init", "top_level/serving_init",
                "top_level/initialize") == pytest.approx(4.6)
    assert read("compile/trace_s", "compile/lower_s") == pytest.approx(9.1)
    assert read("compile/compile_s") == 0.0       # a warm start: 0, not None
    assert read("outside_s") == 9.9
    assert read("top_level/initialize") is None   # a serving process
    snap["ready"] = False
    assert read("outside_s") is None              # start-up is not over
