"""A throw-away copy of ``perfbench/`` and ``BENCHMARK.json`` with the
test-only tiny cells added the way ``perfbench/README.md`` tells a later PR
to add a cell: new files, a new entry under ``workloads``, and the cell's
name added to the ``workloads`` of each metric it reports. A second model
family, cut in depth, comes the same way, with its configuration, its
cells, a kernel's arithmetic and the metric that names it. No file that is
there is edited, which is the proof that the harness finds everything by
name."""

import importlib
import json
import os
import shutil

import pytest

from perfbench import families, kernels

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELLS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "cells")
# the tiny cells, each with its configuration and traffic mix; between them
# the mixes use every arrival process and option of perfbench/traffic.py
TINY_CELLS = {"tiny-train": ("tiny-gpt2", "tiny-train"),
              "tiny-serve": ("tiny-gpt2", "tiny-chat"),
              "tiny-serve-offline": ("tiny-gpt2", "tiny-offline"),
              "tiny-serve-burst": ("tiny-gpt2", "tiny-burst"),
              "tiny-alt-train": ("tiny-alt", "tiny-train"),
              "tiny-alt-serve": ("tiny-alt", "tiny-chat")}
FOLDERS = {"config": "configs", "traffic": "traffic", "workload": "workloads",
           "family": "families", "kernel": "kernels",
           "metric": "layer_metrics"}


@pytest.fixture(scope="session")
def bench_copy(tmp_path_factory):
    """(root of the copied ``perfbench``, its BENCHMARK.json as a dict).
    While it lives, the copy's ``families/`` and ``kernels/`` are on their
    packages' ``__path__``, behind the committed folders."""
    top = tmp_path_factory.mktemp("bench")
    root = os.path.join(top, "perfbench")
    shutil.copytree(os.path.join(REPO, "perfbench"), root,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {os.path.join(d, f) for d, _, fs in os.walk(root) for f in fs}
    for fname in os.listdir(CELLS):
        kind, rest = fname.split(".", 1)
        dst = os.path.join(root, FOLDERS[kind], rest)
        assert dst not in before, f"{dst} would overwrite a file"
        shutil.copy(os.path.join(CELLS, fname), dst)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for config in sorted({c for c, _ in TINY_CELLS.values()}):
        with open(os.path.join(root, "configs", f"{config}.json")) as f:
            config_file = json.load(f)
        bench["configs"].append(
            {"name": config, "source": config_file["source"],
             "file": f"perfbench/configs/{config}.json",
             "reduced": config_file["reduced"], "why": "test"})
    for name, (config, traffic) in TINY_CELLS.items():
        bench["workloads"].append(
            {"name": name, "config": config, "traffic": traffic,
             "chips": 1, "why": "test"})
        # the cell takes up the metrics of the committed cell of its kind
        like = "train-medium-1chip" if "train" in name else "serve-xl-chat"
        for m in bench["end_to_end"] + bench["per_layer"]:
            if like in m.get("workloads", ()):
                m["workloads"].append(name)
    # a new metric: the test-only kernel's share of its roofline
    bench["per_layer"].append(
        {"name": "tiny-matmul_roofline", "unit": "%", "better": "higher",
         "source": "device_trace", "layer": "kernels",
         "moves": "train_tok_s_chip", "workloads": ["tiny-alt-train"]})
    with open(os.path.join(top, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    for package in (families, kernels):
        package.__path__.append(
            os.path.join(root, package.__name__.rpartition(".")[2]))
    importlib.invalidate_caches()
    yield root, bench
    for package in (families, kernels):
        package.__path__.pop()
