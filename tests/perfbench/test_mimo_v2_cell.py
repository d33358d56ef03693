"""The ``mimo_v2`` family in the harness, at a tiny size on the CPU: a cell
cut in depth, experts AND vocabulary (``check_cut``'s cut form with
list-valued keys), its rehearsal through job ``serve_counted``, the new
readers and kernels' arithmetic on hand-made ``facts``, and the committed
configuration file against the catalog. The cell is added as
``tests/perfbench/conftest.py`` adds its own: new files and new entries in
a throw-away copy."""

import io
import json
import os
import shutil
from contextlib import redirect_stdout

import pytest

from perfbench import byname
from perfbench import run as bench_run
from perfbench.byname import BenchError
from perfbench.kernels import expert_matmul, paged_decode_hybrid
from perfbench.readers import counted_kernel_roofline, stats_share

from .conftest import REPO

CELLS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "cells_mimo_v2")
FOLDERS = {"config": "configs", "traffic": "traffic", "workload": "workloads"}
CELL, CONFIG = "tiny-mimo-serve", "tiny-mimo"
COMMITTED = os.path.join(REPO, "perfbench", "configs", "mimo-v2.5-ep16.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
COUNTED = ["moe_touched_share", "moe_routed_here_share", "kv_window_share"]


@pytest.fixture(scope="module")
def mimo_copy(tmp_path_factory):
    top = tmp_path_factory.mktemp("bench-mimo")
    root = os.path.join(top, "perfbench")
    shutil.copytree(os.path.join(REPO, "perfbench"), root,
                    ignore=shutil.ignore_patterns("__pycache__"))
    for fname in os.listdir(CELLS):
        kind, rest = fname.split(".", 1)
        dst = os.path.join(root, FOLDERS[kind], rest)
        assert not os.path.exists(dst)
        shutil.copy(os.path.join(CELLS, fname), dst)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(root, "configs", f"{CONFIG}.json")) as f:
        config_file = json.load(f)
    bench["configs"].append(
        {"name": CONFIG, "source": config_file["source"],
         "file": f"perfbench/configs/{CONFIG}.json",
         "reduced": config_file["reduced"], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": CONFIG,
                               "traffic": "tiny-mixed", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "serve-mimo-hybrid-mixed" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    with open(os.path.join(top, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def _run(root, *argv):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = bench_run.main(list(argv), root=root)
    return rc, [ln for ln in out.getvalue().splitlines() if ln.strip()]


@pytest.mark.parametrize("trace", [0, 1])
def test_the_tiny_cell_rehearses_on_the_cpu(mimo_copy, trace):
    rc, lines = _run(mimo_copy, "--workload", CELL, "--seed", "3000000017",
                     "--seconds", "2", "--trace", str(trace))
    assert rc == 0
    last = json.loads(lines[-1])
    assert last["correct"] and last["failed"] == 0
    assert last["device"]["platform"] == "cpu"
    if trace:
        # counts are read off the chip too; no device metric is
        assert sorted(last["metrics"]) == sorted(COUNTED)
        # a quarter of the experts held: about a quarter of the pairs
        assert 5 < last["metrics"]["moe_routed_here_share"]["value"] < 60
        assert 0 < last["metrics"]["kv_window_share"]["value"] < 100
    else:
        assert set(last["metrics"]) == {"served_tok_s", "setup_s"}
    window = next(json.loads(ln) for ln in lines
                  if ln.startswith('{"phase": "window"'))
    assert window["compiles_in_window"] == 0
    paths = window["engine_stats"]["attention_paths"]
    assert {"mimo_window_cached_xla", "moe_experts_dense_xla"} <= set(paths)


def test_the_tiny_cut_has_list_valued_keys(mimo_copy):
    cell = bench_run.load_cell(CELL, mimo_copy)
    cut = cell["config_file"]
    assert isinstance(cut["published"]["hybrid_layer_pattern"], list)
    fam = cell["family"]
    assert fam.vocab_size(cut) == 128 and fam.max_context(cut) == 256
    shapes = fam.attention_shapes(cut)
    assert shapes["global"]["layers"] == 2 and shapes["window"] == {
        "layers": 2, "kv_heads": 2, "k_dim": 24, "v_dim": 16, "window": 8}
    assert shapes["experts"] == {"layers": 3, "held": 8, "hidden": 64,
                                 "width": 32}
    with pytest.raises(BenchError, match="no training cell"):
        fam.training_model(cut, None, "full")
    # a published list left in model is refused like a published number
    same = {**cut, "model": {**cut["model"], "hybrid_layer_pattern":
                             cut["published"]["hybrid_layer_pattern"]}}
    with pytest.raises(BenchError, match="published value"):
        bench_run.check_cut(same, cut["reduced"])


def test_the_family_refuses_what_it_does_not_implement(mimo_copy):
    cell = bench_run.load_cell(CELL, mimo_copy)
    cut = cell["config_file"]
    odd = {**cut, "model": {**cut["model"], "scoring_func": "softmax",
                            "swa_head_dim": 32}}
    with pytest.raises(BenchError, match="scoring_func") as e:
        cell["family"].attention_shapes(odd)
    assert "swa_head_dim" in str(e.value)


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="no catalog here")
def test_the_committed_configuration_is_the_catalogs_row_but_for_its_cut():
    with open(COMMITTED) as f:
        cut = json.load(f)
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == cut["source"])
    assert cut["reduced"] == ["num_hidden_layers", "hybrid_layer_pattern",
                              "moe_layer_freq", "n_routed_experts",
                              "vocab_size"]
    assert set(cut["model"]) == set(row["config"])
    # the driver's check against the catalog reads the keys at the file's
    # top level (PR 36 and this PR's first round were refused there);
    # check_cut and the family read ``model``: one set of values, twice
    assert {k: cut[k] for k in row["config"]} == cut["model"]
    with pytest.raises(BenchError, match="top-level .'head_dim'. differ"):
        byname.module("families", "mimo_v2").attention_shapes(
            dict(cut, head_dim=128))
    differs = sorted(k for k, v in row["config"].items()
                     if cut["model"][k] != v)
    assert differs == sorted(cut["reduced"])
    assert cut["published"] == {k: row["config"][k] for k in cut["reduced"]}
    # the cut's own arithmetic: layer 0 and the first regular period
    period = row["config"]["hybrid_layer_pattern"][6:12]
    assert cut["model"]["hybrid_layer_pattern"] == [0] + period
    assert cut["model"]["vocab_size"] * 8 == row["config"]["vocab_size"]
    bench_run.check_cut(cut, cut["reduced"])
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        declared = {c["name"]: c for c in json.load(f)["configs"]}
    assert declared["mimo-v2.5-ep16"]["reduced"] == cut["reduced"]
    assert declared["mimo-v2.5-ep16"]["source"] == cut["source"]


# ------------------------------------------------ readers on hand-made facts
def _stats(touched=80, held=96, here=30, total=480, window=40, whole=100):
    return {"model_counters": {
        "decode": {"experts_touched": touched, "experts_held": held,
                   "pairs_here": here, "pairs_all": total},
        "prefill": {"experts_touched": 16, "experts_held": 16,
                    "pairs_here": 70, "pairs_all": 1120}},
        "kv_live_bytes": {"window": window, "global": whole - window}}


def _spec(name):
    with open(os.path.join(REPO, "perfbench", "layer_metrics",
                           f"{name}.json")) as f:
        return json.load(f)


def test_counter_shares_read_the_engines_stats():
    facts = {"engine_stats": _stats()}
    assert stats_share.read(_spec("moe_touched_share"), facts) == \
        pytest.approx(100 * 80 / 96)
    assert stats_share.read(_spec("moe_routed_here_share"), facts) == \
        pytest.approx(100 * (30 + 70) / (480 + 1120))
    assert stats_share.read(_spec("kv_window_share"), facts) == 40.0


@pytest.mark.parametrize("facts", [
    {}, {"engine_stats": None}, {"engine_stats": {}},
    {"engine_stats": {"model_counters": {}, "kv_live_bytes": {}}},
    {"engine_stats": {"model_counters": {
        phase: dict.fromkeys(("experts_touched", "experts_held",
                              "pairs_here", "pairs_all"), 0)
        for phase in ("decode", "prefill")},
        "kv_live_bytes": {"window": 0, "global": 0}}},
], ids=["no-stats", "none", "empty", "no-counters", "zero-whole"])
def test_counter_shares_find_nothing_where_nothing_is(facts):
    """A program without the counters (the parent commit, GPT-2) or a run
    that took no decode step: None, not a division and not a KeyError."""
    for name in COUNTED:
        assert stats_share.read(_spec(name), facts) is None


class _Family:
    @staticmethod
    def attention_shapes(config_file):
        return {"heads": 64,
                "global": {"layers": 2, "kv_heads": 4, "k_dim": 192,
                           "v_dim": 128, "window": 0},
                "window": {"layers": 5, "kv_heads": 8, "k_dim": 192,
                           "v_dim": 128, "window": 128},
                "experts": {"layers": 6, "held": 16, "hidden": 4096,
                            "width": 2048}}


PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def _facts(**more):
    return {"cell": {"family": _Family, "config_file": {}}, **more}


def test_expert_matmul_counts_touched_experts_and_routed_rows():
    span = {"model_counters": {"decode": {"experts_touched": 10,
                                          "pairs_here": 40},
                               "prefill": {"experts_touched": 16,
                                           "pairs_here": 2000}}}
    weights = 3 * 4096 * 2048
    got = expert_matmul.least_seconds({}, _facts(engine_span=span), 7, PEAK)
    by_bytes = 2 * (26 * weights + 2040 * 2 * 4096) / 819e9
    by_flops = 2 * 2040 * weights / 197e12
    assert got == pytest.approx(max(by_bytes, by_flops))
    assert by_bytes > by_flops
    # a long prompt alone: compute-bound
    span["model_counters"]["prefill"]["pairs_here"] = 20000
    got = expert_matmul.least_seconds({}, _facts(engine_span=span), 7, PEAK)
    assert got == pytest.approx(2 * 20040 * weights / 197e12)


@pytest.mark.parametrize("span", [
    None, {}, {"model_counters": {}},
    {"model_counters": {"decode": {"experts_touched": 0, "pairs_here": 0}}},
], ids=["no-span", "empty", "no-counters", "touched-0"])
def test_expert_matmul_finds_nothing_without_counters(span):
    """Touched = 0 gives None, not a division; so does a program with no
    such counter."""
    assert expert_matmul.least_seconds(
        {}, _facts(engine_span=span), 3, PEAK) is None


def test_counted_roofline_reader_passes_none_on(monkeypatch):
    class _Trace:
        pass

    from perfbench import trace_reduce

    monkeypatch.setattr(trace_reduce, "window_events", lambda trace: {
        0: [("%moe._expert_matmul.6 = custom-call(", 0, 1000)]})
    monkeypatch.setattr(trace_reduce, "time_matching",
                        lambda events, pattern: (1000, 1))
    spec = _spec("expert_matmul_roofline_share")
    facts = _facts(trace_events=_Trace(), device={"kind": "TPU v5 lite"},
                   engine_span=None)
    assert counted_kernel_roofline.read(spec, facts) is None
    facts["engine_span"] = {"model_counters": {"decode": {
        "experts_touched": 1, "pairs_here": 1}}}
    share = counted_kernel_roofline.read(spec, facts)
    assert share == pytest.approx(
        100 * 2 * (3 * 4096 * 2048 + 2 * 4096) / 819e9 / 1e-6)
    assert counted_kernel_roofline.read(spec, {"trace_events": None}) is None


def test_hybrid_decode_bytes_stop_at_the_window():
    reqs = [{"prompt_len": 100, "arrivals": [0.5, 1.5, 2.5, 9.0]},
            {"prompt_len": 1000, "arrivals": [1.2, 1.4]}]
    got = paged_decode_hybrid.least_seconds(
        {}, _facts(requests=reqs, traced_span_s=[1.0, 3.0]), 0, PEAK)
    # tokens inside the span (never a request's first): live 101, 102, 1001
    row_g, row_w = 4 * 320 * 2, 8 * 320 * 2
    want = (2 * (101 + 102 + 1001) * row_g
            + 5 * (101 + 102 + 128) * row_w) / 819e9
    assert got == pytest.approx(want)


def test_new_metric_patterns_match_the_kernels_names():
    import re

    for name, event in (
            ("expert_matmul_roofline_share", "moe._expert_matmul.11"),
            ("hybrid_decode_roofline_share", "attn._hybrid_kv_attend.7")):
        line = (f'%{event} = (bf16[64,1,64,128]{{3,2,1,0}}) custom-call('
                f'%a), custom_call_target="tpu_custom_call"')
        assert re.search(_spec(name)["pattern"], line)
        assert not re.search(_spec(name)["pattern"],
                             line.replace(event, "attn._paged_kv_attend.9"))


def test_the_committed_cell_loads_and_declares_its_metrics():
    cell = bench_run.load_cell("serve-mimo-hybrid-mixed")
    assert cell["job"] == "serve_counted" and cell["chips"] == 1
    # serve's set-up and teardown; its rule and limits inside a check of
    # its own, which hands the program's routed sets to the reference
    job, serve = byname.module("jobs", cell["job"]), byname.module(
        "jobs", "serve")
    assert job.setup is serve.setup and job.teardown is serve.teardown
    assert job.check is not serve.check
    assert cell["serve"]["serving"]["routed_experts_kept"] >= 275
    names = {s["name"] for s in bench_run.layer_metric_specs(cell)}
    assert set(COUNTED) | {"expert_matmul_roofline_share",
                           "hybrid_decode_roofline_share"} <= names
    mix = cell["traffic_file"]
    assert mix["max_total"] == 4096 and "bursts" not in mix["arrivals"]
    serving = cell["serve"]["serving"]
    assert serving["decode_slots"] == 64 and serving["block_size"] == 32


def _tool():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "chip_logits_mimo_v2",
        os.path.join(REPO, "tools", "chip_logits_mimo_v2.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool


@pytest.fixture
def plain_dropless():
    """The controls put ``moe/dropless.py`` into a lower precision."""
    from deepspeed_tpu.moe import dropless

    plain = dropless.expert_ffn, dropless.route
    yield
    dropless.expert_ffn, dropless.route = plain


def test_the_chip_logits_tool_rehearses_on_the_tiny_cell(
        mimo_copy, capsys, plain_dropless):
    """``tools/chip_logits_mimo_v2.py`` end to end at the tiny cell's size
    (float32 there, so its limits are met with room): whole-prompt and
    chunked prefill, decode through the cache, the program's routed sets
    handed to the reference, and the three controls: every matrix in
    float8 against the logits' limits, the expert matrices alone and the
    gate's input alone against the sparse layers'."""
    rc = _tool().main(["--workload", CELL, "--root", mimo_copy, "--seed",
                       "5", "--prompt", "29", "--chunked-prompt", "22",
                       "--chunk", "8", "--steps", "6", "--pad", "8",
                       "--more-seeds", "1"])
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    last = lines[-1]
    whole, chunked, experts, gate, every, other, other_low, _ = lines[:8]
    assert last["bf16_inside"] == [True, True, True] and rc in (0, 1)
    assert other["seed"] == 6 and other["max_rel"] < 1e-4
    assert other["largest_logit"] != whole["largest_logit"]
    assert whole["positions"] == 29 + 6 - 1
    assert chunked["positions"] == 22 + 6 - 1
    assert whole["routed_sets_differ"] == 0.0 and whole["max_rel"] < 1e-4
    assert chunked["max_rel"] < 1e-4 and max(whole["expert_error"]) < 1e-5
    # every matrix through float8: far from float32 on the logits
    assert every["rms_rel"] > 1000 * whole["rms_rel"] and not every["inside"]
    # the experts alone: the logits' limits pass them, the layers' do not
    for low in (experts, other_low):
        assert low["inside"] and not low["experts_inside"]
        assert min(low["expert_error"]) > 0.03
    # the gate's input through bfloat16 moves what it chooses, by near ties
    assert 0 < gate["gate_margin"] < 1e-2 and max(gate["expert_error"]) < 0.01
    assert whole["gate_margin"] == 0.0


@pytest.mark.parametrize("part", ["experts", "gate"])
def test_a_lower_precision_shows_in_the_cells_own_check(
        mimo_copy, capsys, plain_dropless, part):
    """The tiny cell through the harness with one part of the sparse layer
    in the precision below: the experts in float8 make ``correct`` false
    by the layers' limit and by nothing else; the gate's input in bfloat16
    is seen by the gate's margin (at this size of a few hundred tokens its
    largest near tie may lie inside the limit the chip's 10,000 set)."""
    from perfbench.jobs import serve_counted

    rc = _tool().through_check(part, [
        "--workload", CELL, "--seed", "3000000017", "--seconds", "2",
        "--trace", "0"], mimo_copy)
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    check = next(ln for ln in lines if ln.get("phase") == "check")
    assert check["largest_gap_rel"] <= check["near_tie_rtol"]
    assert check["requests_without_routed_sets"] == []
    if part == "experts":
        assert rc == 0 and check["correct"] is False
        assert check["expert_error"] > 3 * serve_counted.EXPERT_ERROR_MAX
        assert check["gate_margin"] <= serve_counted.GATE_MARGIN_MAX
    else:
        assert check["gate_margin"] > 1e-6
        assert check["expert_error"] < serve_counted.EXPERT_ERROR_MAX
        assert check["correct"] == (
            check["gate_margin"] <= serve_counted.GATE_MARGIN_MAX)
