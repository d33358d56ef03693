"""The ``deepseek_v32`` family in the harness, at a tiny size on the CPU: a
cell cut in depth, experts, vocabulary, the leading dense layers and the
prediction layer, its rehearsal through job ``serve_counted_deepseek_v32``
(chunked prefill and decode through BOTH pools, every host-side reader, the
selection checked as a set by a replay and then taken as given), the
controls that have to make ``correct`` false, the share's test, the parked
per-layer metrics and their kernels' arithmetic, and the committed
configuration, cell and ``BENCHMARK.json`` entries, each found BY NAME
(nothing here is pinned by position or by count). The cell is added as
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
from perfbench.kernels import dsa_sparse_attend as attend_arith

from .conftest import REPO

CELLS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "cells_deepseek_v32")
FOLDERS = {"config": "configs", "traffic": "traffic", "workload": "workloads",
           "metric": "layer_metrics"}
CELL, CONFIG = "tiny-dsv32-serve", "tiny-dsv32"
COMMITTED_CELL, COMMITTED_CONFIG = ("serve-dsv32-dsa-longctx",
                                    "deepseek-v3.2-ep32")
COMMITTED = os.path.join(REPO, "perfbench", "configs",
                         f"{COMMITTED_CONFIG}.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
END_TO_END = {"served_tok_s", "setup_s"}
COUNTED = ["moe_touched_share", "moe_routed_here_share"]
ON_CHIP = ["expert_matmul_roofline_share"]
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
           "vocab_size", "num_nextn_predict_layers"]
# per-layer metrics this PR brings and does NOT declare in BENCHMARK.json
# (the driver takes ``per_layer`` entries at the end only, and five tests
# pin the four front-door metrics there: ROADMAP R3(b)): their files lie
# beside the tiny cell, the copy declares them, and the builder reads them
# on the chip over a scratch copy
PARKED = {
    "dsa_attend_roofline_share": {
        "unit": "%", "better": "higher", "source": "device_trace",
        "layer": "kernels", "moves": "served_tok_s"},
    "dsa_selected_share": {
        "unit": "%", "better": "lower", "source": "program_counter",
        "layer": "serving engine", "moves": "served_tok_s"},
    "index_cache_share": {
        "unit": "%", "better": "lower", "source": "program_counter",
        "layer": "serving engine", "moves": "served_tok_s"},
}


@pytest.fixture(scope="module")
def dsv32_copy(tmp_path_factory):
    top = tmp_path_factory.mktemp("bench-dsv32")
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
                               "traffic": "tiny-longctx", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if COMMITTED_CELL in m.get("workloads", ()):
            m["workloads"].append(CELL)
    for name, entry in PARKED.items():
        bench["per_layer"].append({"name": name, **entry,
                                   "workloads": [CELL, COMMITTED_CELL]})
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


def test_the_tiny_cell_rehearses_on_the_cpu(dsv32_copy):
    """The traced run (the untraced line is read off a control's run
    below: the same set-up, window and check)."""
    rc, lines = _run(dsv32_copy, "--workload", CELL, "--seed", "5900000017",
                     "--seconds", "2", "--trace", "1")
    assert rc == 0
    last = json.loads(lines[-1])
    check = _phase(lines, "check")
    assert last["correct"] and last["failed"] == 0, check
    assert last["device"]["platform"] == "cpu"
    # counts are read off the chip too; no device metric is
    assert sorted(last["metrics"]) == sorted(
        COUNTED + ["dsa_selected_share", "index_cache_share"])
    # a quarter of the experts held
    assert 5 < last["metrics"]["moe_routed_here_share"]["value"] < 60
    # contexts of 24-108 tokens, 16 keys chosen of them
    assert 10 < last["metrics"]["dsa_selected_share"]["value"] < 70
    # a step reads every live index row (128 values) and 16 latent rows
    # (136): the index rows are most of a step's bytes
    assert 50 < last["metrics"]["index_cache_share"]["value"] < 100
    window = _phase(lines, "window")
    assert window["compiles_in_window"] == 0
    stats = window["engine_stats"]
    assert {"dsa_chunk_masked_decompressed_xla",
            "dsa_decode_absorbed_gathered_xla",
            "moe_experts_dense_xla"} <= set(stats["attention_paths"])
    assert set(stats["kv_live_bytes"]) == {"latent", "index"}
    for kind in ("prefill", "decode"):
        counted = stats["model_counters"][kind]
        assert {"dsa_keys_live", "dsa_keys_selected",
                "pairs_here"} <= set(counted)
        assert 0 < counted["dsa_keys_selected"] < counted["dsa_keys_live"]
    # float32 here: the program chooses the reference's keys and experts,
    # every served token is the reference's argmax
    assert check["tokens_judged"] > 20 and check["largest_gap_rel"] < 1e-4
    assert check["routed_margin"] < 1e-5 and check["gate_margin"] == 0.0
    assert check["expert_error"] < 1e-5
    assert check["replayed_tokens_differ"] == 0
    assert check["select_margin"] < 1e-5
    assert check["select_flips_mean"] < 0.01
    assert check["requests_without_routed_sets"] == []
    assert check["requests_without_selected_keys"] == []


def test_the_balanced_selection_bias_evens_the_experts(dsv32_copy):
    """What the job's set-up does to the seeded weights: every sparse
    layer's bias balanced over seeded tokens through the reference and its
    groups; no other leaf changes, and tokens it never saw route more
    evenly."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perfbench import reference_deepseek_v32 as reference

    cell = bench_run.load_cell(CELL, dsv32_copy)
    family, config_file = cell["family"], cell["config_file"]
    module = family.serving_module(config_file, jnp.float32)
    params = jax.jit(module.init)(jax.random.PRNGKey(7),
                                  jnp.zeros((1, 8), jnp.int32))["params"]
    balanced = family.balanced_weights(config_file)(params, 7)
    names = family.sparse_layers(config_file)
    before = jax.tree_util.tree_leaves_with_path(params)
    after = jax.tree_util.tree_leaves_with_path(balanced)
    moved = [jax.tree_util.keystr(path) for (path, a), (_, b) in
             zip(before, after) if not np.array_equal(a, b)]
    assert moved == [f"['{name}']['router_bias']" for name in names]
    shape = family.reference_shape(config_file)
    experts = config_file["published"]["n_routed_experts"]
    fresh = np.random.default_rng(3).integers(
        0, family.vocab_size(config_file), (4, 64)).astype(np.int32)

    chosen = jax.jit(lambda tree: reference.logits(
        tree, jnp.asarray(fresh), shape, with_layers=True)[1]["chosen"])

    def unevenness(tree):
        sets = np.asarray(chosen(tree))
        loads = np.stack([np.bincount(sets[:, :, layer].reshape(-1),
                                      minlength=experts)
                          for layer in range(sets.shape[2])])
        return float((loads.std(-1) / loads.mean(-1)).mean())

    assert unevenness(balanced) < 0.8 * unevenness(params)
    plain = {**config_file, "weights": {"selection_bias_std": 0.01}}
    assert family.balanced_weights(plain) is None


def test_the_shares_add_up_to_the_uncut_layer(dsv32_copy):
    """THE SHARE'S TEST. The tiny deployment's four chips each hold eight
    of 32 experts: every share's routed sum (the program's sparse FFN told
    which experts it holds, the router whole), with the shared expert
    counted ONCE, adds up to the uncut reference's layer; and a share's
    program is the reference's same share."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.models.deepseek_v32 import SparseExperts
    from perfbench import reference_bailing_hybrid as grouped

    cell = bench_run.load_cell(CELL, dsv32_copy)
    family, cut = cell["family"], cell["config_file"]
    whole = {**cut, "model": {**cut["model"], "n_routed_experts": 32},
             "published": {**cut["published"], "n_routed_experts": 64},
             "held": {"ep_size": 2, "ep_rank": 0}}
    # (a file that holds all 32 of a published 64: the uncut layer is the
    # first half's; what matters here is one set of 32 experts' weights)
    uncut = family.serving_module(
        {**whole, "published": {**cut["published"], "n_routed_experts": 32},
         "held": {"ep_size": 1, "ep_rank": 0}}, jnp.float32).config
    assert (uncut.n_routed_experts, uncut.ep_size) == (32, 1)
    layer = SparseExperts(uncut)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 19, 64))
    params = layer.init(jax.random.PRNGKey(5), x)["params"]
    shape = {**family.reference_shape(cut), "first_expert": 0}
    flat = x.reshape(-1, 64)
    with jax.default_matmul_precision("highest"):
        picked, weights, _, _ = grouped.routed(flat, params, shape)
        want = (grouped.expert_terms(flat, params, 0, picked, weights)
                + grouped.swiglu(flat, params["shared_experts"]))
    total, shared_terms = 0.0, []
    for rank in range(4):
        part = family.serving_module(
            {**cut, "held": {"ep_size": 4, "ep_rank": rank}},
            jnp.float32).config
        assert (part.n_routed_experts, part.ep_size, part.ep_rank) == (
            32, 4, rank)
        held = {**params, **{k: params[k][8 * rank:8 * rank + 8]
                             for k in ("gate", "up", "down")}}
        y, shared, counters, chosen = SparseExperts(part).apply(
            {"params": held}, x)
        assert (np.sort(chosen.reshape(-1, 4), -1)
                == np.sort(picked, -1)).all()
        assert int(counters[1]) == 8                # experts held here
        with jax.default_matmul_precision("highest"):
            ref_part = grouped.expert_terms(flat, held, 8 * rank, picked,
                                            weights)
        assert np.abs(np.asarray(y.reshape(-1, 64) - ref_part)).max() < 1e-5
        total = total + y
        shared_terms.append(shared)
    for other in shared_terms[1:]:
        assert np.abs(np.asarray(other - shared_terms[0])).max() == 0.0
    assert np.abs(np.asarray((total + shared_terms[0]).reshape(-1, 64)
                             - want)).max() < 1e-5


def _tool():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "chip_logits_deepseek_v32",
        os.path.join(REPO, "tools", "chip_logits_deepseek_v32.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool


# what the cell's check has to read as NOT correct (ISSUE 59): the selection
# ignored (every live key attended); the most recent keys chosen instead of
# the best; a chunk's index rows not written to ``index_pool``
CONTROLS = ("ignored", "recent", "not-written")


@pytest.mark.parametrize("part", CONTROLS)
def test_a_control_shows_in_the_cells_own_check(dsv32_copy, capsys, part):
    """The tiny cell through the harness with one control in force
    (``tools/chip_logits_deepseek_v32.py --through-check``): each makes
    ``correct`` false by the selection's margin: keys far under the
    reference's own k-th score chosen, or keys far over it left out."""
    job = byname.module("jobs", "serve_counted_deepseek_v32")
    tool = _tool()
    assert tool.CONTROLS == CONTROLS
    from deepspeed_tpu.models.deepseek_v32 import SparseLatentAttention
    from deepspeed_tpu.ops import dsa_index_select

    paged = SparseLatentAttention.__dict__["_paged"]
    rc = tool.through_check(part, [
        "--workload", CELL, "--seed", "5900000017", "--seconds", "1",
        "--trace", "0"], dsv32_copy)
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    check = next(ln for ln in lines if ln.get("phase") == "check")
    assert check["requests_without_routed_sets"] == []
    assert check["requests_without_selected_keys"] == []
    # the untraced line: the end-to-end metrics and no other
    assert set(lines[-2]["metrics"]) == END_TO_END
    # the control left nothing behind
    for name in ("select_mask", "select_positions", "index_scores"):
        assert getattr(dsa_index_select, name).__module__ == (
            dsa_index_select.__name__)
    assert SparseLatentAttention.__dict__["_paged"] is paged
    assert rc == 0 and check["correct"] is False
    if part != "not-written":
        # (index rows never written: a query scores whatever the block's
        # last tenant left there, and a replay in other blocks is another
        # request)
        assert check["replayed_tokens_differ"] == 0
    assert check["select_margin"] > 2 * job.SELECT_MARGIN_MAX
    assert check["select_flips_mean"] > 1.0
    # the sparse layers, over the reference's own inputs, see none of it
    assert check["expert_error"] < job.EXPERT_ERROR_MAX
    assert check["gate_margin"] <= job.GATE_MARGIN_MAX


def test_the_chip_logits_tool_rehearses_on_the_tiny_cell(dsv32_copy, capsys):
    """``tools/chip_logits_deepseek_v32.py`` end to end at the tiny cell's
    size (float32 there, so its limits are met with room): a prompt in
    chunks and decode through both pools, the program's routed sets and
    chosen keys handed to the reference, the reference once more choosing
    its own, and the attention's lower-precision control (the three
    selection controls go through the cell's own check above; the experts'
    and the gate's are ``tools/chip_logits_mimo_v2.py``'s, rehearsed with
    that cell)."""
    rc = _tool().main(["--workload", CELL, "--root", dsv32_copy, "--seed",
                       "5", "--prompt", "61", "--steps", "12",
                       "--control-prompt", "45", "--control-steps", "6",
                       "--positions", "16", "--pad", "8", "--controls",
                       "latent"])
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    base, latent, last = lines
    assert rc == 0 and last["passes"]
    assert last["served_inside"] == [True, True, True]
    assert base["positions"] == 16 + 12 - 1
    assert base["max_rel"] < 1e-4 and base["select_margin"] < 1e-5
    assert base["select_flips_mean"] == 0.0
    assert base["routed_sets_differ"] == 0.0
    # float32: the program's sets ARE the reference's own
    assert base["against_its_own_sets"]["max_rel"] < 1e-4
    assert max(base["expert_error"]) < 1e-5 and base["gate_margin"] == 0.0
    # the pooled latent row in float8: the logits tell (the step's and the
    # chunk's keys and values both come from the pool), the sparse layers
    # over the reference's own inputs do not
    assert not latent["inside"] and latent["max_rel"] > 100 * base["max_rel"]
    assert latent["experts_inside"]
    assert last["controls_logits_inside"] == {"latent": False}
    assert last["controls_selection_inside"] == {}
    assert last["controls_experts_inside"] == {}
    assert {"dsa_chunk_masked_decompressed_xla",
            "dsa_decode_absorbed_gathered_xla"} <= set(
                last["attention_paths"])


def test_the_tiny_cut_takes_the_dense_layers_and_the_mtp_key_with_the_depth(
        dsv32_copy):
    cell = bench_run.load_cell(CELL, dsv32_copy)
    cut = cell["config_file"]
    assert cut["reduced"] == REDUCED
    fam = cell["family"]
    assert fam.vocab_size(cut) == 128 and fam.max_context(cut) == 256
    shapes = fam.attention_shapes(cut)
    assert shapes["latent"] == {"layers": 2, "rank": 128, "rope": 8,
                                "nope": 16, "v": 16, "row": 136}
    assert shapes["index"] == {"layers": 2, "heads": 4, "dim": 128,
                               "topk": 16}
    assert shapes["experts"] == {"layers": 1, "held": 8, "hidden": 64,
                                 "width": 32}
    assert fam.sparse_layers(cut) == ["layers_1_mlp"]
    served = fam.serving_module(cut, "float32").config
    assert (served.n_routed_experts, served.ep_size, served.ep_rank) == (
        32, 4, 1)
    assert (served.n_group, served.topk_group, served.index_topk) == (
        4, 2, 16)
    assert fam.reference_shape(cut)["first_expert"] == 8
    with pytest.raises(BenchError, match="no training cell"):
        fam.training_model(cut, None, "full")
    with pytest.raises(BenchError, match="no training cell"):
        fam.train_flops_per_token(cut, 128)


@pytest.mark.parametrize("change, said", [
    ({"scoring_func": "softmax"}, "scoring_func"),
    ({"num_nextn_predict_layers": 1}, "num_nextn_predict_layers"),
    ({"topk_method": "greedy"}, "topk_method"),
    ({"q_lora_rank": None}, "q_lora_rank"),
    ({"rope_scaling": {"type": "linear"}}, "rope_scaling"),
    ({"attention_bias": True}, "attention_bias"),
    ({"moe_layer_freq": 2}, "moe_layer_freq"),
    ({"num_key_value_heads": 1}, "num_key_value_heads"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"model_type": "deepseek_v3"}, "model_type"),
], ids=lambda x: x if isinstance(x, str) else "")
def test_the_family_refuses_what_it_does_not_implement(dsv32_copy, change,
                                                       said):
    cell = bench_run.load_cell(CELL, dsv32_copy)
    cut = cell["config_file"]
    odd = {**cut, "model": {**cut["model"], **change}}
    with pytest.raises(BenchError, match=said):
        cell["family"].attention_shapes(odd)


def test_the_family_refuses_a_share_that_does_not_divide(dsv32_copy):
    cut = bench_run.load_cell(CELL, dsv32_copy)["config_file"]
    odd = {**cut, "published": {**cut["published"], "n_routed_experts": 20}}
    with pytest.raises(BenchError, match="do not divide"):
        byname.module("families", "deepseek_v32").serving_module(
            odd, "float32")


def test_the_parked_metrics_have_their_files_and_their_kernels(dsv32_copy):
    """Each parked metric: a file beside the tiny cell that names a reader
    there is and, for a kernel's share, a kernel's arithmetic there is and a
    pattern that matches the names the op's events carry."""
    import re

    for name, entry in PARKED.items():
        with open(os.path.join(CELLS, f"metric.{name}.json")) as f:
            how = json.load(f)
        assert byname.module("readers", how["reader"]).read
        assert entry["moves"] == "served_tok_s"
        assert not os.path.exists(os.path.join(
            REPO, "perfbench", "layer_metrics", f"{name}.json"))
        if "kernel" in how:
            assert name.endswith("_roofline_share") and entry["unit"] == "%"
            assert byname.module("kernels", how["kernel"]).least_seconds
            assert how["kernel"] == "dsa_sparse_attend"
            # a device event's name is the instruction's text: a Pallas
            # call carries its own name, an XLA fusion only ``fusion.N``
            # (which is why the index scores and the selection, XLA
            # fusions, have no roofline share: PERF.md, section 7)
            kernel = (f'%{how["kernel"]}.7 = (f32[8]{{0}}) custom-call(%a), '
                      'custom_call_target="tpu_custom_call"')
            fusion = "%fusion.12 = f32[512,1024]{1,0} fusion(%p), kind=kLoop"
            assert re.search(how["pattern"], kernel)
            assert not re.search(how["pattern"], fusion)
    declared = {m["name"] for m in _benchmark()["per_layer"]}
    assert not declared & set(PARKED)


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
    assert row["name"] == "DeepSeek-V3.2"
    assert cut["reduced"] == REDUCED
    assert set(cut["model"]) == set(row["config"])
    # the driver's check against the catalog reads the keys at the file's
    # top level; check_cut and the family read ``model``: one set of
    # values, twice, the nested rope_scaling included
    assert {k: cut[k] for k in row["config"]} == cut["model"]
    assert "twice" in cut
    changed = {k for k, v in row["config"].items() if cut["model"][k] != v}
    assert changed == set(REDUCED)
    assert cut["published"] == {k: row["config"][k] for k in REDUCED}
    # no width is cut
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_attention_heads", "num_key_value_heads", "q_lora_rank",
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "index_n_heads", "index_head_dim",
                "index_topk", "num_experts_per_tok", "n_group", "topk_group",
                "n_shared_experts", "rope_scaling"):
        assert cut["model"][key] == row["config"][key] and key not in REDUCED
    assert (cut["model"]["index_topk"], cut["model"]["index_n_heads"],
            cut["model"]["index_head_dim"]) == (2048, 64, 128)
    with pytest.raises(BenchError, match="top-level .'index_topk'. differ"):
        byname.module("families", "deepseek_v32").attention_shapes(
            {**cut, "index_topk": 64})


def test_the_committed_configuration_states_its_cut():
    with open(COMMITTED) as f:
        cut = json.load(f)
    entry = _entry(_benchmark()["configs"], COMMITTED_CONFIG)
    bench_run.check_cut(cut, entry["reduced"])
    assert entry["file"] == f"perfbench/configs/{COMMITTED_CONFIG}.json"
    assert entry["source"] == cut["source"] and len(entry["why"]) <= 200
    assert cut["deployment"].startswith("32 chips share each layer")
    assert cut["held"]["ep_size"] == 32 and cut["held"]["ep_rank"] == 0
    assert cut["held"]["experts"] == [0, 8]
    assert cut["held"]["vocabulary_rows"] == [0, 16160]
    model, published = cut["model"], cut["published"]
    assert published["n_routed_experts"] == 32 * model["n_routed_experts"]
    assert published["vocab_size"] == 8 * model["vocab_size"]
    assert published["num_hidden_layers"] == 61
    assert published["first_k_dense_replace"] == 3
    # the floors: every kind of layer, four layers after the dense one, 8
    # experts, an eighth of the vocabulary
    assert model["first_k_dense_replace"] >= 1
    assert model["num_hidden_layers"] - model["first_k_dense_replace"] >= 4
    assert model["n_routed_experts"] >= 8
    assert {"indexer_form", "indexer_rotation", "index_key_precision",
            "tie_rule", "softmax_scale", "routing", "inert_keys",
            "multi_token_prediction"} <= set(cut["assumed"])
    assert cut["parameters"] == 3_226_232_064
    assert cut["weights"]["selection_bias_std"] > 0
    fam = byname.module("families", "deepseek_v32")
    served = fam.serving_module(cut, "bfloat16").config
    assert (served.n_routed_experts, served.ep_size, served.vocab_size) == (
        256, 32, 16160)
    assert [served.sparse(i) for i in range(5)] == [False] + [True] * 4
    assert served.kv_bytes_per_token() == {"latent": 5 * 1152,
                                           "index": 5 * 256}
    shapes = fam.attention_shapes(cut)
    assert shapes["experts"] == {"layers": 4, "held": 8, "hidden": 7168,
                                 "width": 2048}
    assert shapes["latent"]["row"] == 576 and shapes["heads"] == 128
    assert shapes["index"] == {"layers": 5, "heads": 64, "dim": 128,
                               "topk": 2048}
    # the program's own count of what the file says it holds
    import jax
    import jax.numpy as jnp

    module = fam.serving_module(cut, jnp.bfloat16)
    tree = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    assert sum(x.size for x in jax.tree_util.tree_leaves(tree)) == (
        cut["parameters"])


def test_the_committed_cell_loads_and_declares_its_metrics():
    cell = bench_run.load_cell(COMMITTED_CELL)
    assert cell["config"] == COMMITTED_CONFIG and cell["chips"] == 1
    assert cell["job"] == "serve_counted_deepseek_v32"
    job, serve = byname.module("jobs", cell["job"]), byname.module(
        "jobs", "serve")
    assert job.setup is not serve.setup and job.teardown is serve.teardown
    assert job.check is not serve.check
    assert job.check is not byname.module("jobs", "serve_counted").check
    bench = _benchmark()
    entry = _entry(bench["workloads"], COMMITTED_CELL)
    assert entry["config"] == COMMITTED_CONFIG and entry["chips"] == 1
    assert entry["traffic"] == cell["traffic"] == "long-ctx-qa"
    assert entry["why"] == cell["why"] and len(entry["why"]) <= 200
    names = {s["name"] for s in bench_run.layer_metric_specs(cell)}
    assert names == set(COUNTED) | set(ON_CHIP)
    end = {m["name"] for m in bench_run.metrics_of(COMMITTED_CELL,
                                                   bench["end_to_end"])}
    assert end == END_TO_END
    for name in names:
        assert _entry(bench["per_layer"], name)["moves"] == "served_tok_s"
    serving = cell["serve"]["serving"]
    assert serving["decode_slots"] == 16 and serving["block_size"] == 32
    assert serving["prefill_chunk_tokens"] == 512
    assert serving["max_model_len"] == 32768
    assert serving["prompt_buckets"] == [32768]


def test_the_committed_traffic_sends_long_prompts_past_the_selection():
    from perfbench import traffic

    cell = bench_run.load_cell(COMMITTED_CELL)
    mix = cell["traffic_file"]
    assert mix["max_total"] == 32768 and "bursts" not in mix["arrivals"]
    assert mix["arrivals"]["process"] == "poisson"
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 8192,
                                 "sigma": 0.7, "min": 2560, "max": 30720}
    assert mix["new_tokens"] == {"dist": "lognormal", "median": 256,
                                 "sigma": 0.7, "min": 64, "max": 1024}
    assert mix["prompt_len"]["max"] + mix["new_tokens"]["max"] <= (
        mix["max_total"])
    # every prompt is longer than index_topk: every request crosses the
    # selection, and every decode step selects
    assert mix["prompt_len"]["min"] > cell["config_file"]["model"][
        "index_topk"]
    reqs = traffic.requests(mix, 5900000001, 50.0, 16160)
    rate = mix["arrivals"]["rate_per_s"]
    assert abs(len(reqs) - 50 * rate) <= 0.35 * 50 * rate
    assert any(len(r["prompt"]) > 16384 for r in reqs)
    kept = cell["serve"]["serving"]["routed_experts_kept"]
    assert kept >= len(reqs) + 6
    assert max(max(r["prompt"]) for r in reqs) < 16160
    assert mix["drain_seconds"] >= 60


def test_the_kernels_arithmetic_reads_the_committed_shapes():
    """The parked roofline's least times from hand-made facts at the
    committed widths: a chosen pair is 278,528 operations and 1,152 B in a
    step, 81,920 operations in a chunk."""
    cell = bench_run.load_cell(COMMITTED_CELL)
    fast_math = {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e18}
    fast_memory = {"hbm_bytes_per_s": 1e18, "bf16_flops_per_s": 1e9}
    span = {"model_counters": {
        "prefill": {"dsa_keys_live": 512 * 1000, "dsa_keys_selected": 5120},
        "decode": {"dsa_keys_live": 3000, "dsa_keys_selected": 200}}}
    facts = {"cell": cell, "engine_span": span}
    assert attend_arith.least_seconds({}, facts, 9, fast_math) == (
        pytest.approx((200 + 10) * 1152 / 1e9))
    assert attend_arith.least_seconds({}, facts, 9, fast_memory) == (
        pytest.approx((200 * 278_528 + 5120 * 81_920) / 1e9))
    # a program that counts nothing: nothing to hold the time against
    assert attend_arith.least_seconds({}, {"cell": cell}, 9, fast_math) is None
    other = bench_run.load_cell("serve-dsv2lite-mla-longdoc")
    assert attend_arith.least_seconds(
        {}, {"cell": other, "engine_span": span}, 9, fast_math) is None
