"""The ``deepseek_v2`` family in the harness, at a tiny size on the CPU: a
cell cut in depth only, its rehearsal through job
``serve_counted_deepseek_v2`` with chunked prefill (every expert held:
``moe_routed_here_share`` reads 100; the latent bytes in the cell's facts),
the four controls that have to read ``correct`` false through the cell's
own check, the chip tool's rehearsal, the kernel's arithmetic, and the
committed configuration file against the catalog's row and the program's
own parameter tree. The cell is added as ``tests/perfbench/conftest.py``
adds its own: new files and new entries in a throw-away copy."""

import importlib.util
import io
import json
import math
import os
import shutil
from contextlib import redirect_stdout

import pytest

from perfbench import byname
from perfbench import run as bench_run
from perfbench.byname import BenchError
from perfbench.kernels import mla_decode

from .conftest import REPO

CELLS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "cells_deepseek_v2")
FOLDERS = {"config": "configs", "traffic": "traffic", "workload": "workloads",
           "metric": "layer_metrics"}
CELL, CONFIG = "tiny-dsv2-serve", "tiny-dsv2"
COMMITTED_CELL = "serve-dsv2lite-mla-longdoc"
COMMITTED_CONFIG = "deepseek-v2-lite-l6"
COMMITTED = os.path.join(REPO, "perfbench", "configs",
                         f"{COMMITTED_CONFIG}.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
COUNTED = ["moe_touched_share", "moe_routed_here_share"]
TAKEN_UP = ["expert_matmul_roofline_share", "moe_touched_share",
            "moe_routed_here_share"]
# What ISSUE 45 asked for besides and a ``benchmark`` PR has to bring: the
# ``per_layer`` entry of the decode kernel's own metric. ISSUE 45 had it
# INSERTED before the four front-door entries, and the driver's check
# refused that as a change to ``gateway_ingress_p95_ms`` (a PR that changes
# the program adds entries at the END of a list); appended, it fails
# ``tests/perfbench/test_gateway_metrics.py``, which holds those four to the
# end of ``per_layer`` and is the benchmark's file. So the committed
# benchmark does not declare it; the throw-away copy below does, as that PR
# would (``cells_deepseek_v2/metric.mla_decode_roofline_share.json`` is the
# file it adds under ``perfbench/layer_metrics/``: a metric's file with no
# entry in ``BENCHMARK.json`` fails ``test_harness.py``).
MLA_SHARE = {"name": "mla_decode_roofline_share", "unit": "%",
             "better": "higher", "source": "device_trace",
             "layer": "kernels", "moves": "served_tok_s"}
DOOR = ["gateway_ingress_p95_ms", "gateway_egress_p95_ms",
        "gateway_write_p50_ms", "ttft_server_p50_ms"]


@pytest.fixture(scope="module")
def dsv2_copy(tmp_path_factory):
    top = tmp_path_factory.mktemp("bench-dsv2")
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
                               "traffic": "tiny-longdoc", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if COMMITTED_CELL in m.get("workloads", ()):
            m["workloads"].append(CELL)
    bench["per_layer"].append({**MLA_SHARE, "workloads": [CELL]})
    with open(os.path.join(top, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def _run(root, *argv):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = bench_run.main(list(argv), root=root)
    return rc, [ln for ln in out.getvalue().splitlines() if ln.strip()]


def _phase(lines, phase):
    return next(json.loads(ln) for ln in lines
                if ln.startswith(f'{{"phase": "{phase}"'))


@pytest.mark.parametrize("trace", [0, 1])
def test_the_tiny_cell_rehearses_on_the_cpu(dsv2_copy, trace):
    rc, lines = _run(dsv2_copy, "--workload", CELL, "--seed", "3000000017",
                     "--seconds", "2", "--trace", str(trace))
    assert rc == 0
    last = json.loads(lines[-1])
    assert last["correct"] and last["failed"] == 0
    assert last["device"]["platform"] == "cpu"
    if trace:
        # counts are read off the chip too; no device metric is
        assert sorted(last["metrics"]) == sorted(COUNTED)
        # the cut is in depth only: every expert held, every pair here
        assert last["metrics"]["moe_routed_here_share"]["value"] == 100.0
    else:
        assert set(last["metrics"]) == {"served_tok_s", "setup_s"}
    window = _phase(lines, "window")
    assert window["compiles_in_window"] == 0
    stats = window["engine_stats"]
    assert {"mla_chunk_decompressed_xla", "mla_decode_absorbed_xla",
            "moe_experts_dense_xla"} <= set(stats["attention_paths"])
    kv = stats["kv_live_bytes"]
    # four layers' 128 + 8 float32 values a live token a step
    assert set(kv) == {"latent"} and kv["latent"] > 0
    assert kv["latent"] % (4 * 136 * 4) == 0
    check = _phase(lines, "check")
    # float32 against float32: the program's sets are the reference's own
    assert check["routed_margin"] == 0.0 and check["tokens_judged"] > 20
    assert check["gate_margin"] == 0.0 and check["expert_error"] < 1e-5
    assert check["tokens_exact_argmax"] == check["tokens_judged"]
    # each request checked at its own length, not at the mix's longest
    assert check["reference_widths"] and max(check["reference_widths"]) <= 64


def _tool():
    spec = importlib.util.spec_from_file_location(
        "chip_logits_deepseek_v2",
        os.path.join(REPO, "tools", "chip_logits_deepseek_v2.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool


@pytest.mark.parametrize("control", ["latent", "kvb", "experts", "gate"])
def test_a_control_shows_in_the_cells_own_check(dsv2_copy, capsys, control,
                                                monkeypatch):
    """The tiny cell through the harness with one control in force has to
    read ``correct`` false: the latent rows in float8 on their way into
    the pool, and ``W_kvb``'s absorbed halves in float8 (served tokens no
    longer the reference's, or routed sets no near ties of its gate's);
    the experts in float8 (the sparse layers' limit); the gate's input in
    bfloat16 (the gate's margin: over float32 inputs a float32 gate flips
    nothing)."""
    from deepspeed_tpu.models import deepseek_v2
    from deepspeed_tpu.moe import dropless
    from perfbench.jobs import serve_counted_deepseek_v2 as own

    # the limits are the published size's, between the chip's bfloat16
    # readings and its controls'. Here the plain program is float32 and
    # reads exactly the reference's tokens and sets (the rehearsal above),
    # so each limit on those is set to what float32 reads at this size
    for name, value in (("NEAR_TIE_RTOL", 0.0), ("MIN_EXACT_SHARE", 1.0),
                        ("ROUTED_MARGIN_MAX", 0.0),
                        ("GATE_MARGIN_MAX", 0.0)):
        monkeypatch.setattr(own, name, value)
    plain = (deepseek_v2.pool_row, deepseek_v2.absorbed_halves,
             dropless.expert_ffn, dropless.route)
    rc = _tool().through_check(control, [
        "--workload", CELL, "--seed", "3000000017", "--seconds", "2",
        "--trace", "0"], dsv2_copy)
    # (the tool puts back what it patched)
    assert plain == (deepseek_v2.pool_row, deepseek_v2.absorbed_halves,
                     dropless.expert_ffn, dropless.route)
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    check = next(ln for ln in lines if ln.get("phase") == "check")
    assert rc == 0 and check["correct"] is False
    assert check["requests_without_routed_sets"] == []
    if control == "experts":
        assert check["expert_error"] > 3 * own.EXPERT_ERROR_MAX
    elif control == "gate":
        assert check["gate_margin"] > 0.0
        assert check["expert_error"] < own.EXPERT_ERROR_MAX
    else:
        assert check["expert_error"] < own.EXPERT_ERROR_MAX
        assert (check["tokens_exact_argmax"] < check["tokens_judged"]
                or check["routed_margin"] > 0.0)
    assert check["near_tie_rtol"] == own.NEAR_TIE_RTOL
    assert check["min_exact_share"] == own.MIN_EXACT_SHARE
    assert check["routed_margin_max"] == own.ROUTED_MARGIN_MAX


def test_the_tiny_cut_is_in_depth_only(dsv2_copy):
    cell = bench_run.load_cell(CELL, dsv2_copy)
    cut = cell["config_file"]
    assert cut["reduced"] == ["num_hidden_layers"]
    fam = cell["family"]
    assert fam.vocab_size(cut) == 128 and fam.max_context(cut) == 4096
    shapes = fam.attention_shapes(cut)
    assert shapes["heads"] == 4
    assert shapes["latent"] == {"layers": 4, "rank": 128, "rope": 8,
                                "row": 136}
    assert shapes["experts"] == {"layers": 3, "held": 16, "hidden": 64,
                                 "width": 32}
    assert "global" not in shapes and "window" not in shapes
    assert fam.sparse_layers(cut) == [f"layers_{i}_mlp" for i in (1, 2, 3)]
    with pytest.raises(BenchError, match="no training cell"):
        fam.training_model(cut, None, "full")
    with pytest.raises(BenchError, match="no training cell"):
        fam.train_flops_per_token(cut, 128)


def test_the_kernels_arithmetic_counts_a_latent_row_a_token_a_layer(
        dsv2_copy):
    """``kernels/mla_decode.py``: a token that arrived in the traced span
    was made by a step that read its request's prompt and the tokens
    before it, ONE row of rank + rope values a layer, shared by the heads;
    the larger of bytes over bandwidth and operations over peak."""
    cell = bench_run.load_cell(CELL, dsv2_copy)
    # the metric's file, laid beside the accepted ones, resolves to the
    # accepted reader and to this arithmetic
    (spec,) = [s for s in bench_run.layer_metric_specs(cell, dsv2_copy)
               if s["name"] == MLA_SHARE["name"]]
    assert (spec["reader"], spec["kernel"]) == ("kernel_roofline",
                                                "mla_decode")
    assert spec["needs_chip"] and "_latent_kv_attend" in spec["pattern"]
    reqs = [{"prompt_len": 100, "arrivals": [0.5, 1.5, 2.5]}]
    facts = {"cell": cell, "requests": reqs, "traced_span_s": [1.0, 3.0]}
    peak = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    live = 101 + 102
    assert mla_decode.least_seconds({}, facts, 0, peak) == pytest.approx(
        max(live * 4 * 136 * 2 / 819e9,
            live * 4 * 4 * (136 + 128) * 2 / 197e12))
    # at the published widths the bytes bind, by 8
    committed = bench_run.load_cell(COMMITTED_CELL)
    got = mla_decode.least_seconds(
        {}, {**facts, "cell": committed}, 0, peak)
    assert got == pytest.approx(live * 6 * 1152 / 819e9)
    assert 16 * (576 + 512) * 2 == 34_816
    assert (1152 / 819e9) / (34_816 / 197e12) == pytest.approx(8, rel=0.01)


def test_the_family_refuses_what_it_does_not_implement(dsv2_copy):
    cell = bench_run.load_cell(CELL, dsv2_copy)
    cut = cell["config_file"]
    odd = {**cut, "model": {**cut["model"], "q_lora_rank": 1536,
                            "scoring_func": "sigmoid"}}
    with pytest.raises(BenchError, match="q_lora_rank") as e:
        cell["family"].attention_shapes(odd)
    assert "scoring_func" in str(e.value)
    grouped = {**cut, "model": {**cut["model"], "num_key_value_heads": 2}}
    with pytest.raises(BenchError, match="num_key_value_heads"):
        cell["family"].reference_shape(grouped)


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="no catalog here")
def test_the_committed_configuration_is_the_catalogs_row_but_for_its_cut():
    with open(COMMITTED) as f:
        cut = json.load(f)
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == cut["source"])
    assert row["name"] == "DeepSeek-V2-Lite"
    assert cut["reduced"] == ["num_hidden_layers"]
    assert set(cut["model"]) == set(row["config"])
    # the driver's check against the catalog reads the keys at the file's
    # top level; check_cut and the family read ``model``: one set of
    # values, twice, the null and the nested group included
    assert {k: cut[k] for k in row["config"]} == cut["model"]
    assert "q_lora_rank" in cut and cut["q_lora_rank"] is None
    assert cut["rope_scaling"] == row["config"]["rope_scaling"]
    with pytest.raises(BenchError, match="top-level .'hidden_size'. differ"):
        byname.module("families", "deepseek_v2").attention_shapes(
            dict(cut, hidden_size=1024))
    differs = sorted(k for k, v in row["config"].items()
                     if cut["model"][k] != v)
    assert differs == ["num_hidden_layers"]
    assert cut["published"] == {"num_hidden_layers": 27}
    assert cut["model"]["num_hidden_layers"] == 6
    # no width changed, all 64 experts and all 102,400 rows held
    assert cut["model"]["n_routed_experts"] == 64
    assert cut["model"]["vocab_size"] == 102400
    assert cut["deployment"].startswith("depth only: the first of five "
                                        "pipeline stages")
    bench_run.check_cut(cut, cut["reduced"])
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        declared = {c["name"]: c for c in json.load(f)["configs"]}
    assert declared[COMMITTED_CONFIG]["reduced"] == cut["reduced"]
    assert declared[COMMITTED_CONFIG]["source"] == cut["source"]
    assert declared[COMMITTED_CONFIG]["file"] == (
        f"perfbench/configs/{COMMITTED_CONFIG}.json")


def test_the_committed_parameters_are_the_programs_tree():
    """``parameters`` in the file is what the program's own tree holds at
    the cut (shapes only: nothing is allocated), and the issue's sum."""
    import jax
    import jax.numpy as jnp

    with open(COMMITTED) as f:
        cut = json.load(f)
    module = byname.module("families", "deepseek_v2").serving_module(
        cut, jnp.bfloat16)
    tree = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    count = sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(tree))
    attention = (2048 * 3072 + 2048 * 576 + 512 + 512 * 4096 + 2048 * 2048
                 + 4096)
    dense = 3 * 2048 * 10944
    sparse = 64 * 3 * 2048 * 1408 + 3 * 2048 * 2816 + 2048 * 64
    ends = 2 * 102400 * 2048 + 2048
    assert attention == 13_767_168 and attention + dense == 81_007_104
    assert attention + sparse == 584_847_872 and ends == 419_432_448
    assert count == cut["parameters"] == 3_424_678_912 == (
        6 * attention + dense + 5 * sparse + ends)
    cfg = module.config
    assert cfg.kv_bytes_per_token() == {"latent": 6_912}
    assert (cfg.latent_row, cfg.latent_lanes) == (576, 640)
    assert cfg.softmax_scale == pytest.approx(0.114721, rel=1e-4)
    assert cfg.sparse_layers == 5 and cfg.routed_width == 30


def test_the_committed_cell_loads_and_declares_its_metrics():
    cell = bench_run.load_cell(COMMITTED_CELL)
    assert cell["job"] == "serve_counted_deepseek_v2" and cell["chips"] == 1
    # serve_counted's set-up, window and teardown; a check of its own
    job, base = byname.module("jobs", cell["job"]), byname.module(
        "jobs", "serve_counted")
    assert (job.setup, job.run, job.teardown) == (base.setup, base.run,
                                                  base.teardown)
    assert job.check is not base.check
    # a softmax over 64: the routed margin's limit is far under a sigmoid's
    assert job.ROUTED_MARGIN_MAX < base.ROUTED_MARGIN_MAX / 5
    assert cell["config"] == COMMITTED_CONFIG and cell["traffic"] == \
        "long-doc"
    names = {s["name"] for s in bench_run.layer_metric_specs(cell)}
    assert names == set(TAKEN_UP)
    declared = bench_run.declared_metrics()
    assert [m["name"] for m in bench_run.metrics_of(
        COMMITTED_CELL, declared["end_to_end"])] == ["served_tok_s",
                                                     "setup_s"]
    # additions only: what PR 41's test holds stands as it stood, and the
    # cell is under none of those four
    assert [m["name"] for m in declared["per_layer"][-4:]] == DOOR
    for m in declared["per_layer"][-4:]:
        assert COMMITTED_CELL not in m["workloads"]
    assert not any(m["name"] == MLA_SHARE["name"]
                   for m in declared["per_layer"])
    mix = cell["traffic_file"]
    assert mix["max_total"] == 16384 and "bursts" not in mix["arrivals"]
    assert mix["arrivals"]["process"] == "poisson"
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 6144,
                                 "sigma": 0.7, "min": 1024, "max": 15872}
    assert mix["new_tokens"] == {"dist": "lognormal", "median": 128,
                                 "sigma": 0.7, "min": 16, "max": 512}
    assert isinstance(mix["schedule_seed"], int)
    serving = cell["serve"]["serving"]
    assert serving["decode_slots"] == 48 and serving["block_size"] == 32
    assert serving["max_model_len"] == 16384
    assert serving["prefill_chunk_tokens"] == 512
    assert serving["num_blocks"] == 16385
    # a window's requests keep their routed sets for the check
    window = 50 * mix["arrivals"]["rate_per_s"]
    assert serving["routed_experts_kept"] >= 1.2 * window
    assert cell["serve"]["gateway"]["poll_secs"] == 0.05
    assert cell["serve"]["trace_start_s"] == 30
    assert cell["serve"]["trace_seconds"] == 5
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(w for w in bench["workloads"]
                 if w["name"] == COMMITTED_CELL)
    assert entry["why"] == cell["why"] and len(entry["why"]) <= 200
    assert len(bench["workloads"]) == 6
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
