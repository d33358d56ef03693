"""The ``granite_hybrid`` family in the harness, at a tiny size on the CPU:
an uncut cell, its rehearsal through job ``serve_counted_granite_hybrid``
(prefill chunks of 8 = two scan chunks of 4, the per-slot state in the
engine's counters), the three controls that have to read ``correct`` false
through the cell's own check (``CONTROLS``: the builder's chip runs import
them from here), the two parked per-layer metrics and their kernels'
arithmetic, and the committed configuration file against the catalog's row
and the program's own parameter tree. The cell is added as
``tests/perfbench/conftest.py`` adds its own: new files and new entries in
a throw-away copy."""

import contextlib
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
from perfbench.kernels import (paged_decode_hybrid, ssd_chunk_scan,
                               ssm_state_update)
from perfbench.readers import counted_kernel_roofline, stats_share

from .conftest import REPO

CELLS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "cells_granite_hybrid")
FOLDERS = {"config": "configs", "traffic": "traffic", "workload": "workloads",
           "metric": "layer_metrics"}
CELL, CONFIG = "tiny-granite-serve", "tiny-granite"
COMMITTED_CELL = "serve-granite-h-ssm-agents"
COMMITTED_CONFIG = "granite-4.0-h-micro"
COMMITTED = os.path.join(REPO, "perfbench", "configs",
                         f"{COMMITTED_CONFIG}.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# What ISSUE 49 parks and a ``benchmark`` PR has to bring (ROADMAP R3(b):
# ``tests/perfbench/test_gateway_metrics.py`` holds the four front-door
# entries to the END of ``per_layer``, so no ``model_config`` PR can
# declare a per-layer metric): the two kernels' roofline shares, and
# LFM2's parked ``state_cache_share``, which reads this cell's counters
# too. The throw-away copy below declares all three, as that PR would.
PARKED = [
    {"name": "ssm_decode_roofline_share", "unit": "%", "better": "higher",
     "source": "device_trace", "layer": "kernels", "moves": "served_tok_s"},
    {"name": "ssd_scan_roofline_share", "unit": "%", "better": "higher",
     "source": "device_trace", "layer": "kernels", "moves": "served_tok_s"},
    {"name": "state_cache_share", "unit": "%", "better": "lower",
     "source": "program_counter", "layer": "serving",
     "moves": "served_tok_s"}]
DOOR = ["gateway_ingress_p95_ms", "gateway_egress_p95_ms",
        "gateway_write_p50_ms", "ttft_server_p50_ms"]
# What BENCHMARK.json holds of this cell since PR 49, each entry at the END
# of its list: the configuration, the cell (its ``why`` the cell file's own)
# and the cell's name at the end of these metrics' ``workloads``. A seventh
# cell makes ``test_deepseek_v2_cell.py``'s pin of six
# (``assert len(bench["workloads"]) == 6``) false: that file is the
# benchmark's, no ``model_config`` PR may edit it, and the driver refuses
# one that declares no configuration, so that ONE test fails until a
# ``benchmark`` PR finds PR 45's cell by name (PERF.md section 7).
DECLARED_CONFIG = {
    "name": "granite-4.0-h-micro",
    "source": "https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/"
              "main/config.json",
    "file": "perfbench/configs/granite-4.0-h-micro.json", "reduced": [],
    "why": "Mamba-2 layers (a 64 x 128 state a head a slot: 38.7 MB a slot, "
           "chunked scan in prefill, in-place update in decode) beside 4 GQA "
           "layers without positions; dense, whole: 40 layers, 3.19G, "
           "6.38 GB"}
DECLARED_CELL = {"name": "serve-granite-h-ssm-agents",
                 "config": "granite-4.0-h-micro", "traffic": "agent-gen",
                 "chips": 1}     # "why": the cell file's own
# (not ``tpot_p95_ms``, which shows a change of the engine's speed one for
# one where ``served_tok_s`` shows two fifths of it: three sets of six read
# spreads of 0.5%, 2.2% and 2.8% in this cell, and a cell is admitted under
# 1.5%, half that metric's bound: PERF.md sections 6 and 7)
TAKEN_UP = ["served_tok_s", "hybrid_decode_roofline_share"]
PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


# ---------------------------------------------------------------------------
# the controls: each puts one fault into every program traced under it

def _through_e4m3():
    spec = importlib.util.spec_from_file_location(
        "chip_logits_mimo_v2",
        os.path.join(REPO, "tools", "chip_logits_mimo_v2.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool.through_e4m3


@contextlib.contextmanager
def _patched(module, **attrs):
    plain = {name: getattr(module, name) for name in attrs}
    for name, value in attrs.items():
        setattr(module, name, value)
    try:
        yield
    finally:
        for name, value in plain.items():
            setattr(module, name, value)


def not_carried():
    """Every call starts from zeros: the state is not carried between a
    prompt's chunks (decode steps keep theirs: the kernel reads the pool
    itself)."""
    import jax.numpy as jnp

    from deepspeed_tpu.models import granite_hybrid

    return _patched(granite_hybrid, state_in=lambda pool, index, rows, fresh:
                    jnp.zeros_like(pool[index, rows]))


def not_reset():
    """A sequence at length 0 starts from what its slot's last tenant
    left."""
    from deepspeed_tpu.models import granite_hybrid

    return _patched(granite_hybrid, state_in=lambda pool, index, rows, fresh:
                    pool[index, rows])


@contextlib.contextmanager
def float8_state():
    """The state pools in float8 (e4m3, rounded by arithmetic): every state
    a program reads, chunk or decode step, has been through it."""
    from deepspeed_tpu.models import granite_hybrid
    from deepspeed_tpu.ops import ssm_state_update as op

    e4m3 = _through_e4m3()
    plain_in = granite_hybrid.state_in

    def lowered(update):
        def step(pool, layer, slot_rows, *rest, **kw):
            pool = pool.at[layer, slot_rows].set(e4m3(pool[layer, slot_rows]))
            return update(pool, layer, slot_rows, *rest, **kw)
        return step

    with _patched(granite_hybrid, state_in=lambda *a: e4m3(plain_in(*a))), \
            _patched(op, state_update_kernel=lowered(op.state_update_kernel),
                     state_update_xla=lowered(op.state_update_xla)):
        yield


CONTROLS = {"not-carried": not_carried, "not-reset": not_reset,
            "float8-state": float8_state}


# ---------------------------------------------------------------------------
def _committed_bench() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def granite_copy(tmp_path_factory):
    top = tmp_path_factory.mktemp("bench-granite")
    root = os.path.join(top, "perfbench")
    shutil.copytree(os.path.join(REPO, "perfbench"), root,
                    ignore=shutil.ignore_patterns("__pycache__"))
    for fname in os.listdir(CELLS):
        kind, rest = fname.split(".", 1)
        dst = os.path.join(root, FOLDERS[kind], rest)
        assert not os.path.exists(dst)
        shutil.copy(os.path.join(CELLS, fname), dst)
    shutil.copy(os.path.join(os.path.dirname(CELLS), "cells_lfm2_moe",
                             "metric.state_cache_share.json"),
                os.path.join(root, "layer_metrics", "state_cache_share.json"))
    with open(os.path.join(root, "configs", f"{CONFIG}.json")) as f:
        config_file = json.load(f)
    bench = _committed_bench()
    bench["configs"].append(
        {"name": CONFIG, "source": config_file["source"],
         "file": f"perfbench/configs/{CONFIG}.json",
         "reduced": config_file["reduced"], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": CONFIG,
                               "traffic": "tiny-agent", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if COMMITTED_CELL in m.get("workloads", ()):
            m["workloads"].append(CELL)
    for entry in PARKED:
        bench["per_layer"].append({**entry, "workloads": [CELL]})
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


ARGS = ("--workload", CELL, "--seed", "4900000017", "--seconds", "2")


@pytest.mark.parametrize("trace", [0, 1])
def test_the_tiny_cell_rehearses_on_the_cpu(granite_copy, trace):
    rc, lines = _run(granite_copy, *ARGS, "--trace", str(trace))
    assert rc == 0
    last = json.loads(lines[-1])
    assert last["correct"] and last["failed"] == 0
    assert last["device"]["platform"] == "cpu"
    if trace:
        # counts are read off the chip too; no device metric is
        assert sorted(last["metrics"]) == ["state_cache_share"]
        # tiny: 4 layers x (8 x 16 x 128 + 3 x 384) a slot beside 2 KV
        # heads x 16 a token: nearly all of it the state
        assert 90 < last["metrics"]["state_cache_share"]["value"] < 100
    else:
        assert set(last["metrics"]) == {"served_tok_s", "setup_s"}
    window = _phase(lines, "window")
    assert window["compiles_in_window"] == 0
    stats = window["engine_stats"]
    assert {"granite_ssm_prefill_chunk", "granite_ssm_decode_xla",
            "granite_attn_cached_xla"} <= set(stats["attention_paths"])
    kv = stats["kv_live_bytes"]
    assert set(kv) == {"global", "state"} and kv["state"] > 0
    assert kv["state"] % (4 * (8 * 16 * 128 + 3 * 384) * 4) == 0
    check = _phase(lines, "check")
    assert check["tokens_judged"] > 40
    assert check["tokens_exact_argmax"] == check["tokens_judged"]
    assert check["largest_gap_rel"] == 0.0
    # float32 against float32, through the pools: an aid, three requests
    assert len(check["replayed_logits_rel"]) == 3
    assert max(check["replayed_logits_rel"]) < 1e-4
    # the shortest prompts of the second half beside the sample; one
    # request crosses program calls
    assert set(check["short_requests"]) < set(check["requests_checked"])
    assert len(check["requests_checked"]) > len(check["short_requests"]) > 1
    assert max(check["prompt_lengths"]) > 8
    assert set(check["reference_widths"]) == {64}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_a_control_shows_in_the_cells_own_check(granite_copy, control):
    """The tiny cell through the harness with one control in force has to
    read ``correct`` false."""
    from deepspeed_tpu.models import granite_hybrid
    from deepspeed_tpu.ops import ssm_state_update as op

    plain = (granite_hybrid.state_in, op.state_update_xla,
             op.state_update_kernel)
    with CONTROLS[control]():
        rc, lines = _run(granite_copy, *ARGS, "--trace", "0")
    assert plain == (granite_hybrid.state_in, op.state_update_xla,
                     op.state_update_kernel)
    check = _phase(lines, "check")
    assert rc == 0 and check["correct"] is False
    assert json.loads(lines[-1])["correct"] is False
    # by the rule over the tokens the window served, and by a wide margin
    assert (check["largest_gap_rel"] > 3 * check["near_tie_rtol"]
            or check["tokens_exact_argmax"]
            < 0.8 * check["min_exact_share"] * check["tokens_judged"])


def test_the_sample_holds_the_shortest_prompts_and_a_chunked_one():
    from perfbench.jobs import serve_counted_granite_hybrid as job

    lengths = [900, 40, 700, 30, 650, 20, 610, 10, 600, 25]
    reqs = [{"ok": i != 7, "tokens": [1]} for i in range(len(lengths))]
    prompts = [{"prompt": [0] * n} for n in lengths]
    short, sample = job.picked_requests(reqs, prompts, 5, chunk=512)
    # of the second half of arrivals (5-9; 7 did not finish): 20, 25, 600
    # and 610 (at most half of the nine finished, SHORT_REQUESTS of them)
    assert short == [5, 6, 8, 9] and len(sample) == 4
    assert not set(short) & set(sample)
    assert any(lengths[i] > 512 for i in sample)
    many = [{"ok": True, "tokens": [1]}] * 40
    short, sample = job.picked_requests(
        many, [{"prompt": [0] * (100 - n)} for n in range(40)], 5, 512)
    assert short == list(range(34, 40)) and len(sample) == 4
    short, sample = job.picked_requests(reqs[:3], prompts[:3], 5, 512)
    assert short == [1] and sorted(sample) == [0, 2]
    assert job.picked_requests([], [], 5, 512) == ([], [])


def test_a_served_tokens_gap_is_of_its_positions_largest_logit():
    import numpy as np

    from perfbench.jobs import serve_counted_granite_hybrid as job

    logits = np.array([[1.0, -4.0, 0.5], [0.0, 2.0, 1.0], [9.0, 9.0, 9.0]])
    gaps = job.served_gaps(logits, np.array([0, 2]))
    assert gaps.tolist() == [0.0, 0.5]


# ---------------------------------------------------------------------------
# the kernels' arithmetic and the parked metrics
# ---------------------------------------------------------------------------
def test_the_family_says_its_shapes(granite_copy):
    cell = bench_run.load_cell(CELL, granite_copy)
    tiny = cell["config_file"]
    fam = cell["family"]
    assert tiny["reduced"] == [] and "published" not in tiny
    assert fam.vocab_size(tiny) == 128 and fam.max_context(tiny) == 256
    shapes = fam.attention_shapes(tiny)
    assert shapes["heads"] == 8
    assert shapes["global"] == {"layers": 1, "kv_heads": 2, "k_dim": 8,
                                "v_dim": 8, "window": 0}
    assert shapes["window"]["layers"] == 0
    assert shapes["ssm"] == {"layers": 4, "heads": 8, "head": 16,
                             "state": 128, "taps": 4, "chunk": 4}
    with pytest.raises(BenchError, match="no training cell"):
        fam.training_model(tiny, None, "full")
    with pytest.raises(BenchError, match="no training cell"):
        fam.train_flops_per_token(tiny, 128)
    # the hybrid kernel's arithmetic reads a family without window layers
    reqs = [{"prompt_len": 100, "arrivals": [0.5, 1.5, 2.5]}]
    got = paged_decode_hybrid.least_seconds(
        {}, {"cell": cell, "requests": reqs, "traced_span_s": [1.0, 3.0]}, 0,
        PEAK)
    assert got == pytest.approx(1 * (101 + 102) * 2 * 16 * 2 / 819e9)


def test_the_family_refuses_what_it_does_not_implement(granite_copy):
    cell = bench_run.load_cell(CELL, granite_copy)
    tiny = cell["config_file"]
    odd = {**tiny, "model": {**tiny["model"], "num_local_experts": 72,
                             "position_embedding_type": "rope"}}
    with pytest.raises(BenchError, match="num_local_experts = 72") as e:
        cell["family"].attention_shapes(odd)
    assert "position_embedding_type" in str(e.value)
    short = {**tiny, "model": {**tiny["model"], "layer_types": ["mamba"]}}
    with pytest.raises(BenchError, match="one entry a layer"):
        cell["family"].reference_shape(short)


def _committed_cell():
    """(its configuration is held to BENCHMARK.json's empty ``reduced``
    and to the form of an uncut file)"""
    return bench_run.load_cell(COMMITTED_CELL)


def test_the_state_updates_bound_is_the_busy_rows_states_twice():
    """Busy rows x 36 layers x 2 x 1,048,576 B over 819 GB/s, the rows
    from the program's counter (what a slot keeps, a busy row a step)."""
    cell = _committed_cell()
    slot = 36 * (64 * 64 * 128 + 3 * 4352) * 2
    assert slot == 38_688_768
    facts = {"cell": cell,
             "engine_span": {"kv_live_bytes": {"state": 40 * 300 * slot}}}
    got = ssm_state_update.least_seconds({}, facts, 36 * 300, PEAK)
    assert got == pytest.approx(40 * 300 * 36 * 2 * 1_048_576 / 819e9)
    # a program without the counter (the parent; another family): nothing
    for span in (None, {}, {"kv_live_bytes": {"global": 5}}):
        assert ssm_state_update.least_seconds(
            {}, {"cell": cell, "engine_span": span}, 3, PEAK) is None
    lfm2 = bench_run.load_cell("serve-lfm2-conv-chat")
    assert ssm_state_update.least_seconds(
        {}, {"cell": lfm2, "engine_span": {"kv_live_bytes": {"state": 9}}},
        3, PEAK) is None


def test_the_scans_bound_is_the_larger_of_operations_and_bytes():
    """A matched event is one layer of one 512-token call: two scan chunks
    of 2 x 256 x 256 x 128 + 64 x (2 x 256 x 256 x 64 + 4 x 256 x 64 x
    128) operations (11.1 us at 197 TFLOP/s) against x in and y out, B, C,
    delta and the state twice a call (10.8 MB, 13.2 us at 819 GB/s): the
    bytes bind, by a fifth."""
    cell = _committed_cell()
    ops = 2 * (2 * 256 * 256 * 128 + 64 * (2 * 256 * 256 * 64
                                            + 4 * 256 * 64 * 128))
    assert ops == 2_181_038_080
    nbytes = 2 * (2 * (2 * 256 * 4096 + 2 * 256 * 128 + 256 * 64)
                  + 2 * 64 * 64 * 128)
    assert nbytes == 10_813_440
    assert ops / 197e12 < nbytes / 819e9 < 1.25 * ops / 197e12
    got = ssd_chunk_scan.least_seconds({}, {"cell": cell}, 72, PEAK)
    assert got == pytest.approx(72 * nbytes / 819e9)
    lfm2 = bench_run.load_cell("serve-lfm2-conv-chat")
    assert ssd_chunk_scan.least_seconds({}, {"cell": lfm2}, 72, PEAK) is None


@pytest.mark.parametrize("metric, kernel", [
    ("ssm_decode_roofline_share", "ssm_state_update"),
    ("ssd_scan_roofline_share", "ssd_chunk_scan")])
def test_a_parked_metric_reads_its_kernels_events(metric, kernel):
    with open(os.path.join(CELLS, f"metric.{metric}.json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "counted_kernel_roofline"
    assert spec["kernel"] == kernel and spec["needs_chip"] is True
    import re

    scope = kernel.replace("ssm_state_update", "ssm._state_update").replace(
        "ssd_chunk_scan", "ssm._chunk_scan")
    name = (f'%{scope}.7 = (f32[64,2,64,32]{{3,2,1,0}}) custom-call(s32[65] '
            '%a), custom_call_target="tpu_custom_call"')
    assert re.search(spec["pattern"], name)
    assert not re.search(spec["pattern"], name.replace("ssm.", "attn."))
    # no trace, or a program without such events: nothing, and no error
    assert counted_kernel_roofline.read(spec, {}) is None
    # not declared: R3(b)
    declared = bench_run.declared_metrics()["per_layer"]
    assert not any(m["name"] == metric for m in declared)
    assert [m["name"] for m in declared[-4:]] == DOOR


def test_lfm2s_parked_state_share_reads_this_familys_counters():
    with open(os.path.join(os.path.dirname(CELLS), "cells_lfm2_moe",
                           "metric.state_cache_share.json")) as f:
        spec = json.load(f)
    facts = {"engine_stats": {"kv_live_bytes": {"state": 900, "global": 100}}}
    assert stats_share.read(spec, facts) == pytest.approx(90.0)


# ---------------------------------------------------------------------------
# the committed configuration and cell
# ---------------------------------------------------------------------------
@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="no catalog here")
def test_the_committed_configuration_is_the_catalogs_row_uncut():
    with open(COMMITTED) as f:
        whole = json.load(f)
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == whole["source"])
    assert row["name"] == "granite-4.0-h-micro"
    assert whole["reduced"] == []
    assert "published" not in whole and "deployment" not in whole
    # the driver's check against the catalog reads the keys at the file's
    # top level; check_cut and the family read ``model``: one set of
    # values, twice, and both the catalog's
    assert whole["model"] == row["config"]
    assert {k: whole[k] for k in row["config"]} == row["config"]
    with pytest.raises(BenchError, match="top-level .'hidden_size'. differ"):
        byname.module("families", "granite_hybrid").attention_shapes(
            dict(whole, hidden_size=1024))
    assert whole["model"]["num_hidden_layers"] == 40
    assert whole["model"]["layer_types"].count("attention") == 4
    assert [i for i, k in enumerate(whole["model"]["layer_types"])
            if k == "attention"] == [5, 15, 25, 35]
    bench_run.check_cut(whole, [])
    assert DECLARED_CONFIG == {
        "name": COMMITTED_CONFIG, "source": whole["source"],
        "file": f"perfbench/configs/{COMMITTED_CONFIG}.json",
        "reduced": [], "why": DECLARED_CONFIG["why"]}
    assert len(DECLARED_CONFIG["why"]) <= 200
    # the last of ``configs``: an addition at the list's end
    assert _committed_bench()["configs"][-1] == DECLARED_CONFIG
    assert float(whole["weights"]["embedding_std"]) < 0.02


def test_the_committed_parameters_are_the_programs_tree():
    """``parameters`` in the file is what the program's own tree holds
    (shapes only: nothing is allocated), and the issue's sum."""
    import jax
    import jax.numpy as jnp

    with open(COMMITTED) as f:
        whole = json.load(f)
    module = byname.module("families", "granite_hybrid").serving_module(
        whole, jnp.bfloat16)
    tree = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    count = sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(tree))
    mixer = 17_432_576 + 4352 * 4 + 4352 + 3 * 64 + 4096 + 8_388_608
    mamba, attn = mixer + 4096 + 50_331_648, 10_485_760 + 4096 + 50_331_648
    assert (mixer, mamba, attn) == (25_847_232, 76_182_976, 60_821_504)
    assert count == whole["parameters"] == 3_191_396_096 == (
        36 * mamba + 4 * attn + 205_520_896 + 2048)
    cfg = module.config
    assert cfg.state_bytes_per_slot() == 38_688_768
    assert cfg.kv_bytes_per_token() == {"global": 8_192}
    assert (cfg.head_dim, cfg.mamba_inner, cfg.conv_width) == (64, 4096, 4352)
    assert cfg.attention_multiplier * cfg.head_dim ** 0.5 == 0.125
    pools = type(module)(cfg.for_paged_decode(
        8193, 32, state_slots=64)).pool_shapes(8193, 32)
    assert pools == {"global_key_pool": (4, 8193, 32, 512),
                     "global_value_pool": (4, 8193, 32, 512),
                     "ssm_state_pool": (36, 65, 64, 64, 128),
                     "ssm_conv_pool": (36, 65, 3 * 4352)}
    held = 2 * (count + sum(math.prod(s) for s in pools.values()))
    assert 11.0e9 < held < 11.1e9


def test_the_committed_cell_loads_and_its_entries_resolve():
    cell = _committed_cell()
    assert cell["job"] == "serve_counted_granite_hybrid"
    assert cell["chips"] == 1
    job, base = byname.module("jobs", cell["job"]), byname.module(
        "jobs", "serve_counted")
    assert (job.setup, job.run, job.teardown) == (base.setup, base.run,
                                                  base.teardown)
    assert job.check is not base.check
    assert cell["config"] == COMMITTED_CONFIG
    assert cell["traffic"] == "agent-gen"
    # the accepted share it takes up by name (the two of its own are
    # parked: ``PARKED``), and what it reports
    names = {s["name"] for s in bench_run.layer_metric_specs(cell)}
    assert names == {"hybrid_decode_roofline_share"}
    declared = bench_run.declared_metrics()
    assert [m["name"] for m in bench_run.metrics_of(
        COMMITTED_CELL, declared["end_to_end"])] == ["served_tok_s",
                                                     "setup_s"]
    mix = cell["traffic_file"]
    assert mix["max_total"] == 8192 and "bursts" not in mix["arrivals"]
    assert mix["arrivals"]["process"] == "poisson"
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 1024,
                                 "sigma": 1.0, "min": 64, "max": 7168}
    assert mix["new_tokens"] == {"dist": "lognormal", "median": 256,
                                 "sigma": 0.7, "min": 32, "max": 1024}
    assert mix["prompt_len"]["max"] + mix["new_tokens"]["max"] == 8192
    serving = cell["serve"]["serving"]
    assert serving == {"decode_slots": 64, "block_size": 32,
                       "max_model_len": 8192, "num_blocks": 8193,
                       "prefill_chunk_tokens": 512, "max_queue_depth": 256,
                       "prompt_buckets": [8192]}
    assert cell["serve"]["gateway"]["poll_secs"] == 0.05
    # additions only, each at the END of its list
    bench = _committed_bench()
    entry = bench["workloads"][-1]
    assert entry == {**DECLARED_CELL, "why": cell["why"]}
    assert len(entry["why"]) <= 200
    assert [w["name"] for w in bench["workloads"]].count(COMMITTED_CELL) == 1
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    for m in bench["end_to_end"] + bench["per_layer"]:
        listed = m.get("workloads", [])
        assert (COMMITTED_CELL in listed) == (m["name"] in TAKEN_UP)
        assert (listed[-1:] == [COMMITTED_CELL]) == (m["name"] in TAKEN_UP)
    assert [m["name"] for m in bench["per_layer"][-4:]] == DOOR
