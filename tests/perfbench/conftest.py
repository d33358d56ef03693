"""A throw-away copy of ``perfbench/`` and ``BENCHMARK.json`` with the
test-only tiny cells added the way ``perfbench/README.md`` tells a later PR
to add a cell: new files, a new entry under ``workloads``, and the cell's
name added to the ``workloads`` of each metric it reports. No file that is
there is edited, which is the proof that the harness finds everything by
name."""

import json
import os
import shutil

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELLS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "cells")
# the tiny cells and their traffic mixes; between them the mixes use every
# arrival process and option of perfbench/traffic.py
TINY_CELLS = {"tiny-train": "tiny-train", "tiny-serve": "tiny-chat",
              "tiny-serve-offline": "tiny-offline",
              "tiny-serve-burst": "tiny-burst"}


@pytest.fixture(scope="session")
def bench_copy(tmp_path_factory):
    """(root of the copied ``perfbench``, its BENCHMARK.json as a dict)."""
    top = tmp_path_factory.mktemp("bench")
    root = os.path.join(top, "perfbench")
    shutil.copytree(os.path.join(REPO, "perfbench"), root,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {os.path.join(d, f) for d, _, fs in os.walk(root) for f in fs}
    for fname in os.listdir(CELLS):
        kind, rest = fname.split(".", 1)
        folder = {"config": "configs", "traffic": "traffic",
                  "workload": "workloads"}[kind]
        dst = os.path.join(root, folder, rest)
        assert dst not in before, f"{dst} would overwrite a file"
        shutil.copy(os.path.join(CELLS, fname), dst)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append(
        {"name": "tiny-gpt2", "source": "test only",
         "file": "perfbench/configs/tiny-gpt2.json", "reduced": [],
         "why": "test"})
    for name, traffic in TINY_CELLS.items():
        bench["workloads"].append(
            {"name": name, "config": "tiny-gpt2", "traffic": traffic,
             "chips": 1, "why": "test"})
        # the cell takes up the metrics of the committed cell of its kind
        like = "train-medium-1chip" if "train" in name else "serve-xl-chat"
        for m in bench["end_to_end"] + bench["per_layer"]:
            if like in m.get("workloads", ()):
                m["workloads"].append(name)
    with open(os.path.join(top, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root, bench
