"""The ``exaone_moe`` family in the harness, at a tiny size on the CPU: a
cell cut in depth, experts, vocabulary AND the multi-token-prediction keys,
its rehearsal through job ``serve_counted_exaone_moe`` (chunked prefill
through ring and table, every host-side reader), the controls that have to
make ``correct`` false, and the committed configuration, cell and
``BENCHMARK.json`` entries, each found BY NAME (nothing here is pinned by
position or by count). The cell is added as ``tests/perfbench/conftest.py``
adds its own: new files and new entries in a throw-away copy."""

import importlib.util
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

from .conftest import REPO

CELLS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "cells_exaone_moe")
FOLDERS = {"config": "configs", "traffic": "traffic", "workload": "workloads"}
CELL, CONFIG = "tiny-exaone-serve", "tiny-exaone"
COMMITTED_CELL, COMMITTED_CONFIG = ("serve-kexaone-reasoning-out",
                                    "k-exaone-236b-ep8")
COMMITTED = os.path.join(REPO, "perfbench", "configs",
                         f"{COMMITTED_CONFIG}.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
COUNTED = ["moe_touched_share", "moe_routed_here_share", "kv_window_share"]
ON_CHIP = ["expert_matmul_roofline_share", "hybrid_decode_roofline_share"]
REDUCED = ["num_hidden_layers", "layer_types", "mlp_layer_types",
           "sliding_windows", "num_experts", "vocab_size",
           "num_nextn_predict_layers", "mtp_layer_types",
           "mtp_sliding_windows"]


@pytest.fixture(scope="module")
def exaone_copy(tmp_path_factory):
    top = tmp_path_factory.mktemp("bench-exaone")
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
                               "traffic": "tiny-reasoning", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if COMMITTED_CELL in m.get("workloads", ()):
            m["workloads"].append(CELL)
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
                if ln.startswith('{"phase": "%s"' % phase))


@pytest.mark.parametrize("trace", [0, 1])
def test_the_tiny_cell_rehearses_on_the_cpu(exaone_copy, trace):
    rc, lines = _run(exaone_copy, "--workload", CELL, "--seed", "5200000017",
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
    window = _phase(lines, "window")
    assert window["compiles_in_window"] == 0
    # every prompt in chunks: the ring gathered, the table a tile at a time
    paths = window["engine_stats"]["attention_paths"]
    assert {"exaone_window_cached_xla", "exaone_global_cached_tiled_xla",
            "moe_experts_dense_xla"} <= set(paths)
    assert set(window["engine_stats"]["kv_live_bytes"]) == {"global",
                                                            "window"}
    check = _phase(lines, "check")
    # float32 here: the program chooses the reference's sets, and every
    # served token is its argmax; each request at its own width
    assert check["tokens_judged"] > 20 and check["largest_gap_rel"] < 1e-4
    assert check["routed_margin"] < 1e-5 and check["gate_margin"] == 0.0
    assert check["expert_error"] < 1e-5
    assert check["reference_widths"] and set(check["reference_widths"]) == {
        64}
    assert check["requests_without_routed_sets"] == []


def test_the_balanced_selection_bias_evens_the_experts_loads(exaone_copy):
    """What the job's set-up does to the seeded weights: every sparse
    layer's bias balanced over seeded tokens through the reference; no
    other leaf changes, and tokens it never saw route more evenly too."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perfbench import reference_exaone_moe as reference

    cell = bench_run.load_cell(CELL, exaone_copy)
    family, config_file = cell["family"], cell["config_file"]
    module = family.serving_module(config_file, jnp.float32)
    params = module.init(jax.random.PRNGKey(7),
                         jnp.zeros((1, 8), jnp.int32))["params"]
    balanced = family.balanced_weights(config_file)(params, 7)
    names = family.sparse_layers(config_file)
    before = jax.tree_util.tree_leaves_with_path(params)
    after = jax.tree_util.tree_leaves_with_path(balanced)
    moved = [jax.tree_util.keystr(path) for (path, a), (_, b) in
             zip(before, after) if not np.array_equal(a, b)]
    assert moved == [f"['{name}']['router_bias']" for name in names]
    shape = family.reference_shape(config_file)
    experts = config_file["published"]["num_experts"]
    fresh = np.random.default_rng(3).integers(
        0, family.vocab_size(config_file), (8, 64)).astype(np.int32)

    def unevenness(tree):
        sets = np.asarray(reference.routed_sets(tree, fresh, shape))
        loads = np.stack([np.bincount(layer.reshape(-1), minlength=experts)
                          for layer in sets])
        return float((loads.std(-1) / loads.mean(-1)).mean())

    assert unevenness(balanced) < 0.6 * unevenness(params)
    # a file that asks for none leaves the weights as the seed made them
    plain = {**config_file, "weights": {"selection_bias_std": 0.01}}
    assert family.balanced_weights(plain) is None


def _tool():
    spec = importlib.util.spec_from_file_location(
        "chip_logits_exaone_moe",
        os.path.join(REPO, "tools", "chip_logits_exaone_moe.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool


@pytest.mark.parametrize("part", ["experts", "gate", "pool", "stale"])
def test_a_control_shows_in_the_cells_own_check(exaone_copy, capsys, part):
    """The tiny cell through the harness with one control in force
    (``tools/chip_logits_exaone_moe.py --through-check``): the expert
    matrices in float8 make ``correct`` false by the layers' limit; the
    gate's input in bfloat16 is seen by the gate's margin; keys and values
    through float8 on their way into the pools, and one ring row a slot
    left stale (a break of the timed path, not a precision), by the served
    tokens."""
    job = byname.module("jobs", "serve_counted_exaone_moe")
    tool = _tool()
    rc = tool.through_check(part, [
        "--workload", CELL, "--seed", "5200000017", "--seconds", "2",
        "--trace", "0"], exaone_copy)
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    check = next(ln for ln in lines if ln.get("phase") == "check")
    assert check["requests_without_routed_sets"] == []
    # the control left nothing behind
    from deepspeed_tpu.models import blocks
    from deepspeed_tpu.moe import dropless
    assert blocks.ring_gqa.__module__ == blocks.__name__
    assert dropless.route.__module__ == dropless.__name__
    if part == "experts":
        assert rc == 0 and check["correct"] is False
        assert check["expert_error"] > 3 * job.EXPERT_ERROR_MAX
        assert check["gate_margin"] <= job.GATE_MARGIN_MAX
        assert check["largest_gap_rel"] <= check["near_tie_rtol"]
    elif part == "gate":
        # (at this size of a few hundred tokens its largest near tie may
        # lie inside the limit the chip's thousands set)
        assert check["gate_margin"] > 1e-6
        assert check["expert_error"] < job.EXPERT_ERROR_MAX
        assert check["correct"] == (
            check["gate_margin"] <= job.GATE_MARGIN_MAX)
    else:
        # the sparse layers, over the reference's own inputs, see neither
        assert check["expert_error"] < job.EXPERT_ERROR_MAX
        assert check["gate_margin"] <= job.GATE_MARGIN_MAX
        assert rc == 0 and check["correct"] is False
        assert (check["largest_gap_rel"] > check["near_tie_rtol"]
                or check["tokens_exact_argmax"]
                < check["min_exact_share"] * check["tokens_judged"]
                or check["routed_margin"] > check["routed_margin_max"])


def test_the_chip_logits_tool_rehearses_on_the_tiny_cell(exaone_copy, capsys):
    """``tools/chip_logits_exaone_moe.py`` end to end at the tiny cell's
    size (float32 there, so its limits are met with room): a prompt in
    chunks through ring and table, decode past the ring's rows, the
    program's routed sets handed to the reference, and the five controls:
    every matrix in float8, a float8 pool and a stale ring row against the
    logits' limits, the expert matrices alone and the gate's input alone
    against the sparse layers'."""
    rc = _tool().main(["--workload", CELL, "--root", exaone_copy, "--seed",
                       "5", "--prompt", "29", "--steps", "16", "--pad", "8"])
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    base, every, pool, stale, experts, gate, last = lines
    assert rc in (0, 1) and last["bf16_inside"] == [True, True]
    assert base["positions"] == 29 + 16 - 1 > 3 * 12
    assert base["routed_sets_differ"] == 0.0 and base["max_rel"] < 1e-4
    assert max(base["expert_error"]) < 1e-5 and base["gate_margin"] == 0.0
    # far from float32 on the logits, each of the three
    for low in (every, pool, stale):
        assert low["rms_rel"] > 100 * base["rms_rel"] and not low["inside"]
    # a stale row shows only once decode reads the ring
    assert stale["decode_p95_rel"] > 100 * base["decode_p95_rel"]
    # the experts alone: the layers' limit sees them
    assert not experts["experts_inside"] and min(
        experts["expert_error"]) > 0.03
    # the gate's input through bfloat16 moves what it chooses, by near ties
    assert 0 < gate["gate_margin"] < 1e-2 and max(gate["expert_error"]) < 0.01
    assert {"exaone_window_cached_xla", "exaone_global_cached_tiled_xla"} <= (
        set(last["attention_paths"]))


def test_the_tiny_cut_takes_the_mtp_keys_with_the_depth(exaone_copy):
    cell = bench_run.load_cell(CELL, exaone_copy)
    cut = cell["config_file"]
    assert cut["reduced"] == REDUCED
    assert cut["published"]["mtp_layer_types"] == ["full_attention"]
    assert cut["model"]["mtp_layer_types"] == []
    fam = cell["family"]
    assert fam.vocab_size(cut) == 128 and fam.max_context(cut) == 256
    shapes = fam.attention_shapes(cut)
    one_row = {"kv_heads": 2, "k_dim": 16, "v_dim": 16}
    assert shapes["global"] == {"layers": 1, **one_row, "window": 0}
    assert shapes["window"] == {"layers": 4, **one_row, "window": 8}
    assert shapes["experts"] == {"layers": 4, "held": 8, "hidden": 64,
                                 "width": 32}
    assert fam.sparse_layers(cut) == [f"layers_{i}_mlp" for i in (1, 2, 3, 4)]
    served = fam.serving_module(cut, "float32").config
    assert (served.num_experts, served.ep_size, served.ep_rank) == (32, 4, 1)
    assert served.sparse_ffn()["shared_width"] == 32
    with pytest.raises(BenchError, match="no training cell"):
        fam.training_model(cut, None, "full")
    with pytest.raises(BenchError, match="no training cell"):
        fam.train_flops_per_token(cut, 128)
    # a published list left in model is refused like a published number
    same = {**cut, "model": {**cut["model"], "mtp_layer_types":
                             cut["published"]["mtp_layer_types"]}}
    with pytest.raises(BenchError, match="published value"):
        bench_run.check_cut(same, cut["reduced"])


@pytest.mark.parametrize("change, said", [
    ({"scoring_func": "softmax"}, "scoring_func"),
    ({"num_nextn_predict_layers": 1}, "num_nextn_predict_layers"),
    ({"sliding_windows": [8, 8, 8, 8, 8]}, "sliding_windows"),
    ({"mlp_layer_types": ["sparse"] * 5}, "first_k_dense_replace"),
    ({"rope_parameters": {"rope_theta": 1e6, "rope_type": "yarn"}},
     "rope_type"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
], ids=lambda x: x if isinstance(x, str) else "")
def test_the_family_refuses_what_it_does_not_implement(exaone_copy, change,
                                                       said):
    cell = bench_run.load_cell(CELL, exaone_copy)
    cut = cell["config_file"]
    odd = {**cut, "model": {**cut["model"], **change}}
    with pytest.raises(BenchError, match=said):
        cell["family"].attention_shapes(odd)


def test_the_family_refuses_a_share_that_does_not_divide(exaone_copy):
    cut = bench_run.load_cell(CELL, exaone_copy)["config_file"]
    odd = {**cut, "published": {**cut["published"], "num_experts": 20}}
    with pytest.raises(BenchError, match="do not divide"):
        byname.module("families", "exaone_moe").serving_module(odd, "float32")


# ---------------------------------------------------------------------------
# what is committed, each entry found by name
# ---------------------------------------------------------------------------
def _entry(entries, name):
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, name
    return found[0]


def _benchmark():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="no catalog here")
def test_the_committed_configuration_is_the_catalogs_row_but_for_its_cut():
    with open(COMMITTED) as f:
        cut = json.load(f)
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == cut["source"])
    assert row["name"] == "K-EXAONE-236B-A23B"
    assert cut["reduced"] == REDUCED
    assert set(cut["model"]) == set(row["config"])
    # the driver's check against the catalog reads the keys at the file's
    # top level; check_cut and the family read ``model``: one set of
    # values, twice, strings, lists and the nested group included
    assert {k: cut[k] for k in row["config"]} == cut["model"]
    assert "twice" in cut
    changed = {k for k, v in row["config"].items() if cut["model"][k] != v}
    assert changed == set(REDUCED)
    assert cut["published"] == {k: row["config"][k] for k in REDUCED}
    # no width is cut
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "head_dim", "num_attention_heads", "num_key_value_heads",
                "num_experts_per_tok", "sliding_window"):
        assert cut["model"][key] == row["config"][key] and key not in REDUCED
    assert cut["model"]["rope_parameters"] == row["config"]["rope_parameters"]
    # the source's layers 0-4 as they stand
    for key in ("layer_types", "mlp_layer_types", "sliding_windows"):
        assert cut["model"][key] == row["config"][key][:5]
    with pytest.raises(BenchError, match="top-level .'head_dim'. differ"):
        byname.module("families", "exaone_moe").attention_shapes(
            {**cut, "head_dim": 64})


def test_the_committed_configuration_states_its_cut():
    with open(COMMITTED) as f:
        cut = json.load(f)
    entry = _entry(_benchmark()["configs"], COMMITTED_CONFIG)
    bench_run.check_cut(cut, entry["reduced"])
    assert entry["file"] == f"perfbench/configs/{COMMITTED_CONFIG}.json"
    assert entry["source"] == cut["source"] and len(entry["why"]) <= 200
    assert cut["deployment"].startswith("8 chips share each layer")
    assert cut["held"]["ep_size"] == 8 and cut["held"]["ep_rank"] == 0
    assert cut["held"]["experts"] == [0, 16]
    assert cut["held"]["vocabulary_rows"] == [0, 19200]
    assert cut["published"]["num_experts"] == 8 * cut["model"]["num_experts"]
    assert cut["published"]["vocab_size"] == 8 * cut["model"]["vocab_size"]
    assert {"norm_placement", "qk_norm", "rope_on_sliding_layers_only",
            "window_edge", "selection_bias", "renormalisation",
            "shared_expert"} <= set(cut["assumed"])
    assert cut["parameters"] == 3_712_028_416
    assert cut["weights"]["selection_bias_std"] > 0
    fam = byname.module("families", "exaone_moe")
    served = fam.serving_module(cut, "bfloat16").config
    assert (served.num_experts, served.ep_size, served.vocab_size) == (
        128, 8, 19200)
    assert served.layer_types.count("full_attention") == 1
    shapes = fam.attention_shapes(cut)
    assert shapes["experts"] == {"layers": 4, "held": 16, "hidden": 6144,
                                 "width": 2048}
    # ONE row shape in both kinds: 8 KV heads x (128 + 128)
    for kind in ("global", "window"):
        assert (shapes[kind]["kv_heads"], shapes[kind]["k_dim"],
                shapes[kind]["v_dim"]) == (8, 128, 128)
    assert shapes["window"]["window"] == 128 and shapes["window"][
        "layers"] == 4


def test_the_committed_cell_loads_and_declares_its_metrics():
    cell = bench_run.load_cell(COMMITTED_CELL)
    assert cell["config"] == COMMITTED_CONFIG and cell["chips"] == 1
    assert cell["job"] == "serve_counted_exaone_moe"
    job, serve = byname.module("jobs", cell["job"]), byname.module(
        "jobs", "serve")
    # the set-up is ``serve``'s and then the balanced selection biases
    assert job.setup is not serve.setup and job.teardown is serve.teardown
    assert job.check is not serve.check
    assert job.check is not byname.module("jobs", "serve_counted").check
    bench = _benchmark()
    entry = _entry(bench["workloads"], COMMITTED_CELL)
    assert entry["config"] == COMMITTED_CONFIG and entry["chips"] == 1
    assert entry["traffic"] == cell["traffic"] == "reasoning-out"
    assert entry["why"] == cell["why"] and len(entry["why"]) <= 200
    names = {s["name"] for s in bench_run.layer_metric_specs(cell)}
    assert names == set(COUNTED) | set(ON_CHIP)
    end = {m["name"] for m in bench_run.metrics_of(COMMITTED_CELL,
                                                   bench["end_to_end"])}
    assert end == {"served_tok_s", "setup_s"}
    # every per-layer metric it reports moves an end-to-end metric it reports
    for name in names:
        assert _entry(bench["per_layer"], name)["moves"] == "served_tok_s"
    serving = cell["serve"]["serving"]
    assert serving["decode_slots"] == 64 and serving["block_size"] == 32
    assert serving["prefill_chunk_tokens"] == 512
    assert serving["max_model_len"] == 4096


def test_the_committed_traffic_sends_answers_longer_than_its_prompts():
    from perfbench import traffic

    cell = bench_run.load_cell(COMMITTED_CELL)
    mix = cell["traffic_file"]
    assert mix["max_total"] == 4096 and "bursts" not in mix["arrivals"]
    assert mix["arrivals"]["process"] == "poisson"
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 256,
                                 "sigma": 1.0, "min": 32, "max": 2048}
    assert mix["new_tokens"] == {"dist": "lognormal", "median": 768,
                                 "sigma": 0.7, "min": 128, "max": 2048}
    assert mix["prompt_len"]["max"] + mix["new_tokens"]["max"] <= (
        mix["max_total"])
    reqs = traffic.requests(mix, 5200000001, 50.0, 19200)
    rate = mix["arrivals"]["rate_per_s"]
    assert abs(len(reqs) - 50 * rate) <= 0.2 * 50 * rate
    prompts = sum(len(r["prompt"]) for r in reqs)
    answers = sum(r["max_new_tokens"] for r in reqs)
    assert 1.5 * prompts < answers
    # a window's requests, the warm-up's and the drain's keep their sets
    kept = cell["serve"]["serving"]["routed_experts_kept"]
    assert kept >= len(reqs) + 2
    assert max(max(r["prompt"]) for r in reqs) < 19200


def test_the_kernels_arithmetic_reads_the_committed_shapes():
    """The two rooflines' least times from hand-made facts at the
    committed widths: an expert is 3 x 6144 x 2048 values, a token's row
    2,048 lanes a layer, a window layer's stop at 128 keys."""
    cell = bench_run.load_cell(COMMITTED_CELL)
    peak = {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e15}
    facts = {"cell": cell, "engine_span": {"model_counters": {
        "decode": {"experts_touched": 10, "pairs_here": 20}}}}
    weights = 3 * 6144 * 2048
    assert expert_matmul.least_seconds({}, facts, 1, peak) == pytest.approx(
        2 * (10 * weights + 20 * 2 * 6144) / 1e9)
    facts = {"cell": cell, "traced_span_s": [0.0, 1.0], "requests": [
        {"prompt_len": 1000, "arrivals": [0.1, 0.2, 0.3]}]}
    # two decode steps at 1,001 and 1,002 live tokens
    row = 8 * 256 * 2
    want = (1001 + 1002) * row + 4 * (128 + 128) * row
    assert paged_decode_hybrid.least_seconds({}, facts, 2, peak) == (
        pytest.approx(want / 1e9))
