"""The ``bailing_hybrid`` family in the harness, at a tiny size on the CPU:
a cell cut in depth, experts (one routing group held), vocabulary, the
clamp lists and the prediction layer, its rehearsal through job
``serve_counted_bailing_hybrid`` (chunked prefill through the latent pool
and the slot's delta-rule state, every host-side reader, the final-state
check by a replay), the controls that have to make ``correct`` false, the
parked per-layer metrics and their kernels' arithmetic, and the committed
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
from perfbench.kernels import kda_chunk as kda_chunk_arith
from perfbench.kernels import kda_state_update as kda_step_arith

from .conftest import REPO

CELLS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "cells_bailing_hybrid")
FOLDERS = {"config": "configs", "traffic": "traffic", "workload": "workloads",
           "metric": "layer_metrics"}
CELL, CONFIG = "tiny-ling-serve", "tiny-ling"
COMMITTED_CELL, COMMITTED_CONFIG = ("serve-ling3-kda-longgen",
                                    "ling-3.0-flash-ep8")
COMMITTED = os.path.join(REPO, "perfbench", "configs",
                         f"{COMMITTED_CONFIG}.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
COUNTED = ["moe_touched_share", "moe_routed_here_share"]
ON_CHIP = ["expert_matmul_roofline_share"]
REDUCED = ["num_hidden_layers", "expert_swiglu_limit_list",
           "share_expert_swiglu_limit_list", "num_experts", "vocab_size",
           "num_nextn_predict_layers"]
# per-layer metrics this PR brings and does NOT declare in BENCHMARK.json
# (the driver takes ``per_layer`` entries at the end only, and five tests
# pin the four front-door metrics there: ROADMAP R3(b)): their files lie
# beside the tiny cell, the copy declares them, and the builder reads them
# on the chip over a scratch copy
PARKED = {
    "kda_decode_roofline_share": {
        "unit": "%", "better": "higher", "source": "device_trace",
        "layer": "kernels", "moves": "served_tok_s"},
    "kda_chunk_roofline_share": {
        "unit": "%", "better": "higher", "source": "device_trace",
        "layer": "kernels", "moves": "served_tok_s"},
    "kda_state_share": {
        "unit": "%", "better": "higher", "source": "program_counter",
        "layer": "serving engine", "moves": "served_tok_s"},
    "moe_group_here_share": {
        "unit": "%", "better": "higher", "source": "program_counter",
        "layer": "model", "moves": "served_tok_s"},
}


@pytest.fixture(scope="module")
def ling_copy(tmp_path_factory):
    top = tmp_path_factory.mktemp("bench-ling")
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
                               "traffic": "tiny-longgen", "chips": 1,
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


@pytest.mark.parametrize("trace", [0, 1])
def test_the_tiny_cell_rehearses_on_the_cpu(ling_copy, trace):
    rc, lines = _run(ling_copy, "--workload", CELL, "--seed", "5700000017",
                     "--seconds", "2", "--trace", str(trace))
    assert rc == 0
    last = json.loads(lines[-1])
    check = _phase(lines, "check")
    assert last["correct"] and last["failed"] == 0, check
    assert last["device"]["platform"] == "cpu"
    if trace:
        # counts are read off the chip too; no device metric is
        assert sorted(last["metrics"]) == sorted(
            COUNTED + ["kda_state_share", "moe_group_here_share"])
        # a quarter of the experts held, one group of four, two kept
        assert 5 < last["metrics"]["moe_routed_here_share"]["value"] < 60
        assert 25 < last["metrics"]["moe_group_here_share"]["value"] < 75
        assert 20 < last["metrics"]["kda_state_share"]["value"] < 100
    else:
        assert set(last["metrics"]) == {"served_tok_s", "setup_s"}
    window = _phase(lines, "window")
    assert window["compiles_in_window"] == 0
    stats = window["engine_stats"]
    assert {"kda_prefill_chunk", "kda_decode_xla",
            "mla_chunk_decompressed_xla", "mla_decode_absorbed_xla",
            "moe_experts_dense_xla"} <= set(stats["attention_paths"])
    assert set(stats["kv_live_bytes"]) == {"latent", "state", "conv"}
    assert {"tokens_group_here", "tokens_routed", "pairs_here"} <= set(
        stats["model_counters"]["decode"])
    # float32 here: the program chooses the reference's sets, every served
    # token is its argmax, and the replayed state is the recurrence's
    assert check["tokens_judged"] > 20 and check["largest_gap_rel"] < 1e-4
    assert check["routed_margin"] < 1e-5 and check["gate_margin"] == 0.0
    assert check["expert_error"] < 1e-5
    assert check["replayed_tokens_differ"] == 0
    assert 0 < check["state_error"] < 1e-5
    assert check["state_error_first"] < 1e-5
    # the first layer's state is read at the request's end and after half
    # and one and a half of the mix's shortest answer (6: 3 and 9 tokens),
    # the least of them held
    by_stop = [r["state_first_by_stop"] for r in check["by_request"]]
    assert all(str(r["served"]) in stops for r, stops in
               zip(check["by_request"], by_stop))
    assert {"3", "9"} <= set().union(*by_stop)
    assert check["state_error_first"] == max(
        min(stops.values()) for stops in by_stop)
    assert all(len(e) == 4 for e in check["state_error_by_layer"])
    assert check["requests_without_routed_sets"] == []


def test_the_balanced_selection_bias_evens_experts_and_groups(ling_copy):
    """What the job's set-up does to the seeded weights: every sparse
    layer's bias balanced over seeded tokens through the reference AND its
    groups; no other leaf changes, and tokens it never saw route more
    evenly, the held group chosen by about half of them."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perfbench import reference_bailing_hybrid as reference

    cell = bench_run.load_cell(CELL, ling_copy)
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
        here = (sets // 8 == 1).any(-1).mean()
        return float((loads.std(-1) / loads.mean(-1)).mean()), float(here)

    (even, here), (uneven, _) = unevenness(balanced), unevenness(params)
    assert even < 0.7 * uneven and 0.35 < here < 0.65
    # a file that asks for none leaves the weights as the seed made them
    plain = {**config_file, "weights": {"selection_bias_std": 0.01}}
    assert family.balanced_weights(plain) is None


def _tool():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "chip_logits_bailing_hybrid",
        os.path.join(REPO, "tools", "chip_logits_bailing_hybrid.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool


# what the cell's check has to read as NOT correct (ISSUE 57): the state
# held in bfloat16 where the file says float32, the chunk form's end state
# not written back; and the sparse layers' two
CONTROLS = ("bf16-state", "not-written", "experts", "gate")


@pytest.mark.parametrize("part", CONTROLS)
def test_a_control_shows_in_the_cells_own_check(ling_copy, capsys, part):
    """The tiny cell through the harness with one control in force
    (``tools/chip_logits_bailing_hybrid.py --through-check``): a bfloat16
    state and a chunk's end state not written back make ``correct`` false
    by the final state's limit (the second by the served tokens too); the
    expert matrices in float8 by the layers' limit; the gate's input in
    bfloat16 is seen by the gate's margin."""
    job = byname.module("jobs", "serve_counted_bailing_hybrid")
    tool = _tool()
    assert tool.CONTROLS == CONTROLS
    rc = tool.through_check(part, [
        "--workload", CELL, "--seed", "5700000017", "--seconds", "2",
        "--trace", "0"], ling_copy)
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    check = next(ln for ln in lines if ln.get("phase") == "check")
    assert check["requests_without_routed_sets"] == []
    # the control left nothing behind
    from deepspeed_tpu.moe import dropless
    from deepspeed_tpu.ops import kda_chunk, kda_state_update
    assert dropless.route.__module__ == dropless.__name__
    assert kda_chunk.kda_chunk.__module__ == kda_chunk.__name__
    assert (kda_state_update.state_update_xla.__module__
            == kda_state_update.__name__)
    if part == "experts":
        assert rc == 0 and check["correct"] is False
        assert check["expert_error"] > 3 * job.EXPERT_ERROR_MAX
        assert check["gate_margin"] <= job.GATE_MARGIN_MAX
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
        # float32 reads 1e-6 here: bfloat16 a thousand times that, a state
        # not written back its whole size
        low = {"bf16-state": 1e-3, "not-written": 0.1}[part]
        assert check["state_error"] > low > check["state_error_max"]
        assert check["state_error_first"] > 0.3 * low
        # the first layer's reading, which has the projections on both
        # sides, is the one a bfloat16 state cannot hide from
        assert check["state_error_first"] > 3 * check["state_error_first_max"]


def test_the_chip_logits_tool_rehearses_on_the_tiny_cell(ling_copy, capsys):
    """``tools/chip_logits_bailing_hybrid.py`` end to end at the tiny
    cell's size (float32 there, so its limits are met with room): a prompt
    in chunks through the latent pool and the slot's state, decode through
    both, the program's routed sets handed to the reference, the final
    state against the recurrence's, and the five controls."""
    rc = _tool().main(["--workload", CELL, "--root", ling_copy, "--seed",
                       "5", "--prompt", "29", "--steps", "16", "--pad", "8"])
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    base, state, unwritten, latent, experts, gate, last = lines
    assert rc in (0, 1) and last["served_inside"] == [True, True, True]
    assert base["positions"] == 29 + 16 - 1
    assert base["routed_sets_differ"] == 0.0 and base["max_rel"] < 1e-4
    assert max(base["expert_error"]) < 1e-5 and base["gate_margin"] == 0.0
    assert base["state_error"] < 1e-5
    # the state's limit sees both of the state's controls
    assert state["state_error"] > 100 * base["state_error"]
    assert not state["state_inside"] and not unwritten["state_inside"]
    assert unwritten["state_error"] > 0.1 and not unwritten["inside"]
    assert latent["rms_rel"] > 100 * base["rms_rel"]
    assert latent["state_error"] < 0.05
    assert not experts["experts_inside"] and min(
        experts["expert_error"]) > 0.03
    assert 0 < gate["gate_margin"] < 1e-2 and max(gate["expert_error"]) < 0.01
    assert {"kda_prefill_chunk", "kda_decode_xla",
            "mla_chunk_decompressed_xla"} <= set(last["attention_paths"])


def test_the_tiny_cut_takes_the_clamps_and_the_mtp_key_with_the_depth(
        ling_copy):
    cell = bench_run.load_cell(CELL, ling_copy)
    cut = cell["config_file"]
    assert cut["reduced"] == REDUCED
    assert cut["model"]["expert_swiglu_limit_list"] == cut["published"][
        "expert_swiglu_limit_list"][:6]
    assert cut["model"]["expert_swiglu_limit_list"][4] == 0.5
    fam = cell["family"]
    assert fam.vocab_size(cut) == 128 and fam.max_context(cut) == 256
    shapes = fam.attention_shapes(cut)
    assert shapes["latent"] == {"layers": 2, "row": 40, "rank": 32}
    assert shapes["kda"] == {"layers": 4, "heads": 4, "key": 16, "value": 16,
                             "taps": 4, "sub_chunk": 16}
    assert shapes["experts"] == {"layers": 5, "held": 8, "hidden": 64,
                                 "width": 32}
    assert fam.sparse_layers(cut) == [f"layers_{i}_mlp" for i in range(1, 6)]
    assert fam.kda_layers(cut) == [0, 1, 3, 4]
    served = fam.serving_module(cut, "float32").config
    assert (served.num_experts, served.ep_size, served.ep_rank) == (32, 4, 1)
    assert (served.n_group, served.topk_group) == (4, 2)
    at_5 = served.sparse_ffn_at(5)
    assert (at_5["limit"], at_5["shared_limit"], at_5["shared_width"]) == (
        0.5, 0.25, 32)
    assert served.sparse_ffn_at(1)["limit"] == 0.0
    with pytest.raises(BenchError, match="no training cell"):
        fam.training_model(cut, None, "full")
    with pytest.raises(BenchError, match="no training cell"):
        fam.train_flops_per_token(cut, 128)
    # a published list left in model is refused like a published number
    same = {**cut, "model": {**cut["model"], "expert_swiglu_limit_list":
                             cut["published"]["expert_swiglu_limit_list"]}}
    with pytest.raises(BenchError, match="published value"):
        bench_run.check_cut(same, cut["reduced"])


@pytest.mark.parametrize("change, said", [
    ({"scoring_func": "softmax"}, "scoring_func"),
    ({"num_nextn_predict_layers": 1}, "num_nextn_predict_layers"),
    ({"use_kda_lora": True}, "use_kda_lora"),
    ({"kda_safe_gate": False}, "kda_safe_gate"),
    ({"q_lora_rank": 1536}, "q_lora_rank"),
    ({"rope_scaling": {"type": "yarn"}}, "rope_scaling"),
    ({"gated_attention_proj_granularity_type": "element_wise"},
     "gated_attention_proj_granularity_type"),
    ({"expert_swiglu_limit_list": [0, 0]}, "clamp lists"),
    ({"value_norm": True}, "value_norm"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
], ids=lambda x: x if isinstance(x, str) else "")
def test_the_family_refuses_what_it_does_not_implement(ling_copy, change,
                                                       said):
    cell = bench_run.load_cell(CELL, ling_copy)
    cut = cell["config_file"]
    odd = {**cut, "model": {**cut["model"], **change}}
    with pytest.raises(BenchError, match=said):
        cell["family"].attention_shapes(odd)


def test_the_family_refuses_a_share_that_does_not_divide(ling_copy):
    cut = bench_run.load_cell(CELL, ling_copy)["config_file"]
    odd = {**cut, "published": {**cut["published"], "num_experts": 20}}
    with pytest.raises(BenchError, match="do not divide"):
        byname.module("families", "bailing_hybrid").serving_module(
            odd, "float32")


def test_the_parked_metrics_have_their_files_and_their_kernels(ling_copy):
    """Each parked metric: a file beside the tiny cell that names a reader
    there is and, for a kernel's share, a kernel's arithmetic there is and a
    pattern that matches the name the kernel's events have."""
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
            event = (f'%{how["kernel"]}.7 = (f32[8]{{0}}) custom-call(%a), '
                     'custom_call_target="tpu_custom_call"')
            assert re.search(how["pattern"], event)
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
    assert row["name"] == "Ling-3.0-flash"
    assert cut["reduced"] == REDUCED
    assert set(cut["model"]) == set(row["config"])
    # the driver's check against the catalog reads the keys at the file's
    # top level; check_cut and the family read ``model``: one set of
    # values, twice, strings, lists and nulls included
    assert {k: cut[k] for k in row["config"]} == cut["model"]
    assert "twice" in cut
    changed = {k for k, v in row["config"].items() if cut["model"][k] != v}
    assert changed == set(REDUCED)
    assert cut["published"] == {k: row["config"][k] for k in REDUCED}
    # no width is cut
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "moe_shared_expert_intermediate_size", "head_dim",
                "num_attention_heads", "num_key_value_heads", "kv_lora_rank",
                "qk_nope_head_dim", "qk_rope_head_dim", "qk_head_dim",
                "v_head_dim", "rotary_dim", "num_experts_per_tok", "n_group",
                "topk_group", "short_conv_kernel_size", "layer_group_size",
                "first_k_dense_replace", "kda_lower_bound"):
        assert cut["model"][key] == row["config"][key] and key not in REDUCED
    # the source's layers 0-7 as they stand: the clamps' first 8 entries
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        assert cut["model"][key] == row["config"][key][:8] == [0] * 8
    with pytest.raises(BenchError, match="top-level .'head_dim'. differ"):
        byname.module("families", "bailing_hybrid").attention_shapes(
            {**cut, "head_dim": 64})


def test_the_committed_configuration_states_its_cut():
    with open(COMMITTED) as f:
        cut = json.load(f)
    entry = _entry(_benchmark()["configs"], COMMITTED_CONFIG)
    bench_run.check_cut(cut, entry["reduced"])
    assert entry["file"] == f"perfbench/configs/{COMMITTED_CONFIG}.json"
    assert entry["source"] == cut["source"] and len(entry["why"]) <= 200
    assert cut["deployment"].startswith("8 chips share each layer")
    assert "one ROUTING GROUP a chip" in cut["deployment"]
    assert cut["held"]["ep_size"] == 8 and cut["held"]["ep_rank"] == 0
    assert cut["held"]["experts"] == [0, 64]
    assert cut["held"]["vocabulary_rows"] == [0, 19648]
    assert cut["published"]["num_experts"] == 8 * cut["model"]["num_experts"]
    assert cut["published"]["vocab_size"] == 8 * cut["model"]["vocab_size"]
    assert cut["published"]["num_hidden_layers"] == 42
    # the floors: a whole period, six layers after the dense ones, 64
    # experts, an eighth of the vocabulary
    model = cut["model"]
    assert model["num_hidden_layers"] >= model["layer_group_size"]
    assert model["num_hidden_layers"] - model["first_k_dense_replace"] >= 4
    assert model["num_experts"] >= 8
    assert {"kda_decay_bounded_form", "kda_qk_norm", "head_wise_gate_place",
            "group_score_sum_of_two", "clamp_two_sides", "inert_keys",
            "state_precision", "selection_bias"} <= set(cut["assumed"])
    assert cut["parameters"] == 2_976_509_024
    assert cut["weights"]["selection_bias_std"] > 0
    fam = byname.module("families", "bailing_hybrid")
    served = fam.serving_module(cut, "bfloat16").config
    assert (served.num_experts, served.ep_size, served.vocab_size) == (
        512, 8, 19648)
    assert [served.kind(i) for i in range(8)] == [
        "kda"] * 5 + ["latent"] + ["kda"] * 2
    assert served.state_bytes_per_slot()["state"] == 14_680_064
    shapes = fam.attention_shapes(cut)
    assert shapes["experts"] == {"layers": 6, "held": 64, "hidden": 2560,
                                 "width": 768}
    assert shapes["latent"] == {"layers": 1, "row": 576, "rank": 512}
    assert shapes["kda"]["layers"] == 7 and shapes["kda"]["key"] == 128


def test_the_committed_cell_loads_and_declares_its_metrics():
    cell = bench_run.load_cell(COMMITTED_CELL)
    assert cell["config"] == COMMITTED_CONFIG and cell["chips"] == 1
    assert cell["job"] == "serve_counted_bailing_hybrid"
    job, serve = byname.module("jobs", cell["job"]), byname.module(
        "jobs", "serve")
    # the set-up is ``serve``'s and then the balanced selection biases
    assert job.setup is not serve.setup and job.teardown is serve.teardown
    assert job.check is not serve.check
    assert job.check is not byname.module("jobs", "serve_counted").check
    bench = _benchmark()
    entry = _entry(bench["workloads"], COMMITTED_CELL)
    assert entry["config"] == COMMITTED_CONFIG and entry["chips"] == 1
    assert entry["traffic"] == cell["traffic"] == "long-gen"
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
    assert serving["decode_slots"] == 128 and serving["block_size"] == 32
    assert serving["prefill_chunk_tokens"] == 512
    assert serving["max_model_len"] == 11264
    assert serving["prompt_buckets"] == [11264]


def test_the_committed_traffic_sends_long_prompts_and_long_answers():
    from perfbench import traffic

    cell = bench_run.load_cell(COMMITTED_CELL)
    mix = cell["traffic_file"]
    assert mix["max_total"] == 11264 and "bursts" not in mix["arrivals"]
    assert mix["arrivals"]["process"] == "poisson"
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 1024,
                                 "sigma": 1.0, "min": 64, "max": 8192}
    assert mix["new_tokens"] == {"dist": "lognormal", "median": 1024,
                                 "sigma": 0.7, "min": 128, "max": 3072}
    assert mix["prompt_len"]["max"] + mix["new_tokens"]["max"] <= (
        mix["max_total"])
    reqs = traffic.requests(mix, 5700000001, 50.0, 19648)
    rate = mix["arrivals"]["rate_per_s"]
    assert abs(len(reqs) - 50 * rate) <= 0.2 * 50 * rate
    # a window's requests, the warm-up's, the drain's and the check's
    # replays keep their sets
    kept = cell["serve"]["serving"]["routed_experts_kept"]
    assert kept >= len(reqs) + 6
    assert max(max(r["prompt"]) for r in reqs) < 19648
    # the longest request finishes within the drain
    assert mix["drain_seconds"] >= 60


def test_the_kernels_arithmetic_reads_the_committed_shapes():
    """The two parked rooflines' least times from hand-made facts at the
    committed widths: a slot's matrices are 7 x 32 x 128 x 128 float32, a
    512-token chunk 32 sub-chunks of 16."""
    cell = bench_run.load_cell(COMMITTED_CELL)
    peak = {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e15}
    slot = 7 * 32 * 128 * 128 * 4
    facts = {"cell": cell, "engine_span": {"kv_live_bytes": {
        "state": 30 * slot, "conv": 1, "latent": 1}}}
    # thirty busy-row steps: each state read once and written once
    assert kda_step_arith.least_seconds({}, facts, 7, peak) == pytest.approx(
        2 * 30 * slot / 1e9)
    assert kda_step_arith.least_seconds({}, {"cell": cell}, 7, peak) is None
    per_head = 4 * (32 * (3 * 16 * 128 + 3 * 16 * 128 + 128)
                    + 2 * 128 * 128)
    assert kda_chunk_arith.least_seconds({}, {"cell": cell}, 3, peak) == (
        pytest.approx(3 * 32 * per_head / 1e9))
    # against the other peak: 6 C K V a head and sub-chunk, counted once
    # (the float32 product's six passes are the kernel's cost)
    slow = {"hbm_bytes_per_s": 1e15, "bf16_flops_per_s": 1e9}
    assert kda_chunk_arith.least_seconds({}, {"cell": cell}, 1, slow) == (
        pytest.approx(32 * 32 * 6 * 16 * 128 * 128 / 1e9))
