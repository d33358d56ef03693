"""The ``phi4flash`` family in the harness, at a tiny size on the CPU: an
uncut cell, its rehearsal through job ``serve_counted_phi4flash`` (prefill
chunks of 8 that stop at the cache, a ring and a state row a slot, the one
shared pool in the engine's counters), the three controls that have to read
``correct`` false through the cell's own check (``CONTROLS``: the builder's
chip runs import them from here), the four parked per-layer metrics and
their kernels' arithmetic, and the committed configuration file against the
catalog's row (found BY NAME) and the program's own parameter tree. The cell
is added as ``tests/perfbench/conftest.py`` adds its own: new files and new
entries in a throw-away copy. Nothing here pins an entry of
``BENCHMARK.json`` by position, by count or by list (ROADMAP R3(b))."""

import contextlib
import io
import json
import math
import os
import re
import shutil
from contextlib import redirect_stdout

import pytest

from perfbench import byname
from perfbench import run as bench_run
from perfbench.byname import BenchError
from perfbench.kernels import (mamba1_decode, mamba1_scan,
                               paged_decode_hybrid)
from perfbench.readers import counted_kernel_roofline, stats_share

from .conftest import REPO

CELLS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "cells_phi4flash")
FOLDERS = {"config": "configs", "traffic": "traffic", "workload": "workloads",
           "metric": "layer_metrics"}
CELL, CONFIG = "tiny-phi4flash-serve", "tiny-phi4flash"
COMMITTED_CELL = "serve-phi4flash-yoco-reasoning"
COMMITTED_CONFIG = "phi-4-mini-flash-reasoning"
COMMITTED = os.path.join(REPO, "perfbench", "configs",
                         f"{COMMITTED_CONFIG}.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CATALOG_NAME = "Phi-4-mini-flash-reasoning"
# What ISSUE 63 parks and a ``benchmark`` PR has to bring (ROADMAP R3(b): no
# ``model_config`` PR can declare a per-layer metric). The throw-away copy
# below declares all four, as that PR would.
PARKED = [
    {"name": "mamba1_decode_roofline_share", "unit": "%", "better": "higher",
     "source": "device_trace", "layer": "kernels", "moves": "served_tok_s"},
    {"name": "mamba1_scan_roofline_share", "unit": "%", "better": "higher",
     "source": "device_trace", "layer": "kernels", "moves": "served_tok_s"},
    {"name": "shared_cache_read_share", "unit": "%", "better": "lower",
     "source": "program_counter", "layer": "serving engine",
     "moves": "served_tok_s"},
    {"name": "cross_rows_share", "unit": "%", "better": "lower",
     "source": "program_counter", "layer": "serving engine",
     "moves": "served_tok_s"}]
TAKEN_UP = ["served_tok_s", "hybrid_decode_roofline_share", "kv_window_share"]
PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


# ---------------------------------------------------------------------------
# the controls: each puts one fault into every program traced under it

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


def through_bf16(x):
    """``x`` rounded to bfloat16's eight bits of mantissa BY ARITHMETIC
    (the chip's compiler fuses a pair of ``astype``s and keeps the excess
    precision: PR 39): Dekker's splitting, exact in float32."""
    import jax.numpy as jnp

    x = x.astype(jnp.float32)
    big = x * jnp.float32(2 ** 16 + 1)
    return big - (big - x)


def bfloat16_state():
    """The Mamba state pool in bfloat16: every state a program writes, chunk
    or decode step, is rounded to bfloat16 as it is stored."""
    from deepspeed_tpu.ops import mamba1_scan as op

    def scan(*args, **kw):
        y, state = _PLAIN["scan"](*args, **kw)
        return y, through_bf16(state)

    def update(pool, layer, slot_rows, *rest, **kw):
        y, pool = _PLAIN["update"](pool, layer, slot_rows, *rest, **kw)
        return y, pool.at[layer, slot_rows].set(
            through_bf16(pool[layer, slot_rows]))

    return _patched(op, mamba1_chunk_scan=scan, mamba1_state_update=update)


def others_blocks():
    """The cross layers read the blocks of the row before (a decode step's
    neighbour slot: another sequence's cache, or the garbage block)."""
    import jax.numpy as jnp

    from deepspeed_tpu.models import phi4flash

    def crossed(cfg, q, paging, table, *rest):
        return _PLAIN["cross"](cfg, q, paging, jnp.roll(table, 1, axis=0),
                               *rest)

    return _patched(phi4flash, cross_attend=crossed)


def lambda_zero():
    """``lambda_init`` forced to 0 in every attention layer: ``lambda =
    exp(lq1 . lk1) - exp(lq2 . lk2) + 0`` is then 0 to two places (the
    vectors are N(0, 0.1)), the second softmax drops out of the difference
    and the norm's ``(1 - lambda_init)`` is 1."""
    from deepspeed_tpu.models import phi4flash

    return _patched(phi4flash.Phi4FlashConfig,
                    lambda_init=lambda self, i: 0.0)


def _plain():
    from deepspeed_tpu.models import phi4flash
    from deepspeed_tpu.ops import mamba1_scan as op

    return {"scan": op.mamba1_chunk_scan, "update": op.mamba1_state_update,
            "cross": phi4flash.cross_attend}


_PLAIN = _plain()
CONTROLS = {"bfloat16-state": bfloat16_state, "others-blocks": others_blocks,
            "lambda-zero": lambda_zero}


# ---------------------------------------------------------------------------
def _committed_bench() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _named(entries, name):
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, (name, len(found))
    return found[0]


@pytest.fixture(scope="module")
def phi_copy(tmp_path_factory):
    top = tmp_path_factory.mktemp("bench-phi4flash")
    root = os.path.join(top, "perfbench")
    shutil.copytree(os.path.join(REPO, "perfbench"), root,
                    ignore=shutil.ignore_patterns("__pycache__"))
    for fname in os.listdir(CELLS):
        kind, rest = fname.split(".", 1)
        dst = os.path.join(root, FOLDERS[kind], rest)
        assert not os.path.exists(dst)
        shutil.copy(os.path.join(CELLS, fname), dst)
    with open(os.path.join(root, "configs", f"{CONFIG}.json")) as f:
        config_file = json.load(f)
    bench = _committed_bench()
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
    for entry in PARKED:
        bench["per_layer"].append({**entry,
                                   "workloads": [COMMITTED_CELL, CELL]})
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


ARGS = ("--workload", CELL, "--seed", "6300000017", "--seconds", "2")


@pytest.mark.parametrize("trace", [0, 1])
def test_the_tiny_cell_rehearses_on_the_cpu(phi_copy, trace):
    rc, lines = _run(phi_copy, *ARGS, "--trace", str(trace))
    assert rc == 0
    last = json.loads(lines[-1])
    assert last["correct"] and last["failed"] == 0
    assert last["device"]["platform"] == "cpu"
    window = _phase(lines, "window")
    assert window["compiles_in_window"] == 0
    stats = window["engine_stats"]
    if trace:
        # counts are read off the chip too; no device metric is
        assert sorted(last["metrics"]) == ["cross_rows_share",
                                           "kv_window_share",
                                           "shared_cache_read_share"]
        # chunks of 8 over prompts of 4-20: one cross row for every 4-8
        # tokens the self-decoder ran
        assert 10 < last["metrics"]["cross_rows_share"]["value"] < 30
        assert 0 < last["metrics"]["shared_cache_read_share"]["value"] < 50
    else:
        assert set(last["metrics"]) == {"served_tok_s", "setup_s"}
    assert {"phi4_ssm_prefill_chunk", "phi4_ssm_decode",
            "phi4_window_cached_xla", "phi4_cross_cached_xla"} <= set(
                stats["attention_paths"])
    kv = stats["kv_live_bytes"]
    assert set(kv) == {"global", "window", "state"} and kv["state"] > 0
    # a slot: 3 Mamba layers x (128 channels x 8 states float32 + 3 rows)
    assert kv["state"] % (3 * (128 * 8 * 4 + 3 * 128 * 4)) == 0
    counted = stats["model_counters"]
    assert 0 < counted["prefill"]["cross_rows"] < counted["prefill"][
        "self_tokens"]
    assert counted["decode"]["cross_rows"] == counted["decode"]["self_tokens"]
    check = _phase(lines, "check")
    assert check["tokens_judged"] > 40
    assert check["tokens_exact_argmax"] == check["tokens_judged"]
    assert check["largest_gap_rel"] == 0.0
    # the window's own state rows, float32 against float32
    assert len(check["state_rel"]) == len(check["memory_state_rel"]) == 2
    assert max(check["state_rel"] + check["memory_state_rel"]) < 1e-5
    assert set(check["short_requests"]) < set(check["requests_checked"])
    assert set(check["state_requests"]) <= set(check["requests_checked"])
    assert max(check["prompt_lengths"]) > 8
    assert set(check["reference_widths"]) == {64}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_a_control_shows_in_the_cells_own_check(phi_copy, control):
    """The tiny cell through the harness with one control in force has to
    read ``correct`` false."""
    from deepspeed_tpu.models import phi4flash
    from deepspeed_tpu.ops import mamba1_scan as op

    with CONTROLS[control]():
        rc, lines = _run(phi_copy, *ARGS, "--trace", "0")
    assert (op.mamba1_chunk_scan, op.mamba1_state_update) == (
        _PLAIN["scan"], _PLAIN["update"])
    assert phi4flash.cross_attend is _PLAIN["cross"]
    assert phi4flash.Phi4FlashConfig().lambda_init(0) == pytest.approx(0.2)
    check = _phase(lines, "check")
    assert rc == 0 and check["correct"] is False
    assert json.loads(lines[-1])["correct"] is False
    if control == "bfloat16-state":
        assert max(check["state_rel"]) > 10 * check["state_rel_max"]
        assert max(check["memory_state_rel"]) > 10 * check[
            "memory_state_rel_max"]
    else:
        assert (check["largest_gap_rel"] > 3 * check["near_tie_rtol"]
                or check["tokens_exact_argmax"]
                < 0.9 * check["min_exact_share"] * check["tokens_judged"])


def _tool():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "chip_logits_phi4flash",
        os.path.join(REPO, "tools", "chip_logits_phi4flash.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool


def test_the_chip_tool_rehearses_at_the_tiny_size(phi_copy, capsys):
    """``tools/chip_logits_phi4flash.py`` end to end at the tiny cell's
    size (bfloat16 against the float32 reference: its verdict is the
    chip's to give), and its ``--through-check`` with a control in force:
    exit 0 because ``correct`` is false."""
    tool = _tool()
    assert sorted(tool.controls()) == sorted(CONTROLS)
    rc = tool.main(["--workload", CELL, "--root", phi_copy, "--seed", "3",
                    "--prompt", "30", "--steps", "5", "--skip-controls"])
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    assert rc in (0, 1) and lines[-1]["limits"] == tool.LIMITS
    first = lines[0]
    assert first["long"]["rows"] == 1 + 5 and first["short"]["rows"] == 1 + 5
    assert len(first["long"]["state_rel"]) == 2
    assert max(first["long"]["state_rel"]) < 0.1
    assert any("term_shares_rms [stream, mixer, mlp]" in ln for ln in lines)
    assert tool.main(["--workload", CELL, "--root", phi_copy, "--seed", "3",
                      "--seconds", "2", "--through-check",
                      "bfloat16-state"]) == 0


def test_the_sample_holds_short_prompts_a_chunked_one_and_last_tenants():
    import types

    from perfbench.jobs import serve_counted_phi4flash as job

    lengths = [900, 40, 700, 30, 650, 20, 610, 10, 600, 25]
    reqs = [{"ok": i != 7, "tokens": [1], "record": {"request_id": f"r{i}"}}
            for i in range(len(lengths))]
    prompts = [{"prompt": [0] * n} for n in lengths]
    short, sample = job.picked_requests(reqs, prompts, 5, chunk=512)
    assert short == [5, 6, 8, 9] and len(sample) == 4
    assert not set(short) & set(sample)
    assert any(lengths[i] > 512 for i in sample)
    assert job.picked_requests([], [], 5, 512) == ([], [])
    # slot 0: r1 then r3 (the last); slot 1: r2; slot 2: an unknown request
    done = [types.SimpleNamespace(request_id=f"r{i}", slot=s, finish_ts=t,
                                  prompt=[0] * lengths[i], tokens=[1])
            for i, s, t in ((1, 0, 1.0), (3, 0, 2.0), (2, 1, 1.5))]
    done.append(types.SimpleNamespace(request_id="warm", slot=2,
                                      finish_ts=0.1, prompt=[0], tokens=[1]))
    srv = types.SimpleNamespace(finished=done)
    assert job.last_tenants(srv, reqs) == {3: 0, 2: 1}
    import numpy as np

    logits = np.array([[1.0, -4.0, 0.5], [0.0, 2.0, 1.0], [9.0, 9.0, 9.0]])
    assert job.served_gaps(logits, np.array([0, 2])).tolist() == [0.0, 0.5]
    rows = np.arange(2 * 3 * 4).reshape(1, 2, 3, 4)   # [G, N, L]
    assert job.written(rows).shape == (1, 8, 3)
    assert job.written(rows)[0, 5, 2] == rows[0, 1, 2, 1]


# ---------------------------------------------------------------------------
# the kernels' arithmetic and the parked metrics
# ---------------------------------------------------------------------------
def test_the_family_says_its_shapes(phi_copy):
    cell = bench_run.load_cell(CELL, phi_copy)
    tiny, fam = cell["config_file"], cell["family"]
    assert tiny["reduced"] == [] and "published" not in tiny
    assert fam.vocab_size(tiny) == 128 and fam.max_context(tiny) == 256
    assert fam.kinds(tiny) == ("mamba", "window", "mamba", "window", "mamba",
                               "full", "gmu", "cross")
    shapes = fam.attention_shapes(tiny)
    assert shapes["heads"] == 8
    # the READING layers of the one pool: the full layer and the cross one
    assert shapes["global"] == {"layers": 2, "kv_heads": 4, "k_dim": 8,
                                "v_dim": 8, "window": 0}
    assert shapes["window"] == {"layers": 2, "kv_heads": 4, "k_dim": 8,
                                "v_dim": 8, "window": 8}
    assert shapes["ssm"] == {"layers": 3, "channels": 128, "state": 8,
                             "taps": 4}
    for call in (lambda: fam.training_model(tiny, None, "full"),
                 lambda: fam.train_flops_per_token(tiny, 128)):
        with pytest.raises(BenchError, match="no training cell"):
            call()
    # the hybrid kernel's arithmetic, unedited, counts what the algorithm
    # reads: every live token in 2 layers, the last 8 in 2 more
    reqs = [{"prompt_len": 100, "arrivals": [0.5, 1.5, 2.5]}]
    got = paged_decode_hybrid.least_seconds(
        {}, {"cell": cell, "requests": reqs, "traced_span_s": [1.0, 3.0]}, 0,
        PEAK)
    assert got == pytest.approx((2 * (101 + 102) + 2 * 16) * 4 * 16 * 2
                                / 819e9)
    odd = {**tiny, "model": {**tiny["model"], "mb_per_layer": 4,
                             "head_dim": 8}}
    with pytest.raises(BenchError, match="mb_per_layer = 4") as e:
        fam.attention_shapes(odd)
    assert "head_dim" in str(e.value)


def _committed_cell():
    return bench_run.load_cell(COMMITTED_CELL)


def test_the_state_updates_bound_is_the_busy_rows_states_twice():
    """Busy rows x 9 layers x 2 x 327,680 B over 819 GB/s, the rows from
    the program's counter (what a slot keeps, a busy row a step)."""
    cell = _committed_cell()
    slot = 9 * (5120 * 16 * 4 + 3 * 5120 * 2)
    assert slot == 3_225_600
    facts = {"cell": cell,
             "engine_span": {"kv_live_bytes": {"state": 70 * 300 * slot}}}
    got = mamba1_decode.least_seconds({}, facts, 9 * 300, PEAK)
    assert got == pytest.approx(70 * 300 * 9 * 2 * 327_680 / 819e9)
    # a program without the counter (the parent; another family): nothing
    for span in (None, {}, {"kv_live_bytes": {"global": 5}}):
        assert mamba1_decode.least_seconds(
            {}, {"cell": cell, "engine_span": span}, 3, PEAK) is None
    granite = bench_run.load_cell("serve-granite-h-ssm-agents")
    span = {"kv_live_bytes": {"state": 9}}
    assert mamba1_decode.least_seconds(
        {}, {"cell": granite, "engine_span": span}, 3, PEAK) is None
    assert mamba1_scan.least_seconds({}, {"cell": granite}, 3, PEAK) is None


def test_the_scans_bound_is_its_bytes():
    """A matched event is one layer of one 512-token call: x, delta and y
    (512 x 5,120 float32 each), B and C, the state in and out: 32.2 MB."""
    cell = _committed_cell()
    nbytes = 4 * (3 * 512 * 5120 + 2 * 512 * 16 + 2 * 5120 * 16)
    assert nbytes == 32_178_176
    got = mamba1_scan.least_seconds({}, {"cell": cell}, 18, PEAK)
    assert got == pytest.approx(18 * nbytes / 819e9)


@pytest.mark.parametrize("metric, kernel, scope", [
    ("mamba1_decode_roofline_share", "mamba1_decode", "mamba1_state_update"),
    ("mamba1_scan_roofline_share", "mamba1_scan", "mamba1_chunk_scan")])
def test_a_parked_roofline_share_reads_its_kernels_events(metric, kernel,
                                                          scope):
    with open(os.path.join(CELLS, f"metric.{metric}.json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "counted_kernel_roofline"
    assert spec["kernel"] == kernel and spec["needs_chip"] is True
    name = (f'%{scope}.7 = (f32[96,40,128]{{2,1,0}}) custom-call(s32[97] '
            '%a), custom_call_target="tpu_custom_call"')
    assert re.search(spec["pattern"], name)
    assert not re.search(spec["pattern"], name.replace("mamba1", "ssm"))
    # no trace, or a program without such events: nothing, and no error
    assert counted_kernel_roofline.read(spec, {}) is None
    # not declared: R3(b)
    declared = bench_run.declared_metrics()["per_layer"]
    assert not any(m["name"] == metric for m in declared)


def test_the_parked_shares_read_the_programs_counters():
    read = {}
    for name in ("shared_cache_read_share", "cross_rows_share"):
        with open(os.path.join(CELLS, f"metric.{name}.json")) as f:
            read[name] = json.load(f)
    facts = {"engine_stats": {
        "kv_live_bytes": {"global": 650, "window": 160, "state": 190},
        "model_counters": {"prefill": {"cross_rows": 2, "self_tokens": 1024},
                           "decode": {"cross_rows": 7, "self_tokens": 7}}}}
    assert stats_share.read(read["shared_cache_read_share"],
                            facts) == pytest.approx(65.0)
    assert stats_share.read(read["cross_rows_share"],
                            facts) == pytest.approx(100 * 2 / 1024)
    # another family's counters (no such kind, no such counter): nothing
    other = {"engine_stats": {"kv_live_bytes": {"global": 5, "state": 1},
                              "model_counters": {"prefill": {}}}}
    assert stats_share.read(read["shared_cache_read_share"], other) is None
    assert stats_share.read(read["cross_rows_share"], other) is None


def test_the_parked_metrics_resolve_over_the_copy(phi_copy):
    cell = bench_run.load_cell(COMMITTED_CELL, phi_copy)
    names = {s["name"] for s in bench_run.layer_metric_specs(cell, phi_copy)}
    assert names == {"hybrid_decode_roofline_share", "kv_window_share"} | {
        m["name"] for m in PARKED}


# ---------------------------------------------------------------------------
# the committed configuration and cell
# ---------------------------------------------------------------------------
@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="no catalog here")
def test_the_committed_configuration_is_the_catalogs_row_uncut():
    with open(COMMITTED) as f:
        whole = json.load(f)
    with open(CATALOG) as f:
        row = _named(list(map(json.loads, f)), CATALOG_NAME)
    assert whole["source"] == row["source_url"]
    assert whole["reduced"] == []
    assert "published" not in whole and "deployment" not in whole
    # the driver's check against the catalog reads the keys at the file's
    # top level; check_cut and the family read ``model``: one set of
    # values, twice, and both the catalog's
    assert whole["model"] == row["config"] and len(row["config"]) == 17
    assert {k: whole[k] for k in row["config"]} == row["config"]
    # the row has no head size, and the file adds none (PR 36)
    assert row["head_dim"] is None
    assert "head_dim" not in whole and "head_dim" not in whole["model"]
    sizes = whole["assumed"]["sizes"]
    assert sizes == {"d_state": 16, "d_conv": 4, "expand": 2, "dt_rank": 160,
                     "head_dim": 64}
    assert sizes["head_dim"] * row["num_attention_heads"] == row[
        "hidden_size"]
    for said in ("why_sizes", "conv_bias", "attention_bias", "window",
                 "positions", "norms", "differential", "layers", "mlp",
                 "state_pool"):
        assert len(whole["assumed"][said]) > 40, said
    family = byname.module("families", "phi4flash")
    with pytest.raises(BenchError, match="top-level .'hidden_size'. differ"):
        family.attention_shapes(dict(whole, hidden_size=1024))
    assert (whole["model"]["num_hidden_layers"], whole["model"]["vocab_size"],
            whole["model"]["sliding_window"]) == (32, 200064, 512)
    kinds = family.kinds(whole)
    assert [kinds.count(k) for k in ("mamba", "window", "full", "gmu",
                                     "cross")] == [9, 8, 1, 7, 7]
    assert kinds[16:20] == ("mamba", "full", "gmu", "cross")
    bench_run.check_cut(whole, [])
    declared = _named(_committed_bench()["configs"], COMMITTED_CONFIG)
    assert declared == {"name": COMMITTED_CONFIG, "source": whole["source"],
                        "file": f"perfbench/configs/{COMMITTED_CONFIG}.json",
                        "reduced": [], "why": declared["why"]}
    assert len(declared["why"]) <= 200


def test_the_committed_parameters_are_the_programs_tree():
    """``parameters`` in the file is what the program's own tree holds
    (shapes only: nothing is allocated), and the issue's sum with the
    biases, the norms and the lambda vectors it leaves out."""
    import jax
    import jax.numpy as jnp

    with open(COMMITTED) as f:
        whole = json.load(f)
    module = byname.module("families", "phi4flash").serving_module(
        whole, jnp.bfloat16)
    tree = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    count = sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(tree))
    d, c, f, v = 2560, 5120, 10240, 200064
    mamba = d * 2 * c + c * d + c * 192 + 160 * c + c * (16 + 4 + 3)
    attn = d * 2 * d + 2 * d + d * d + d + 4 * 64 + 128
    cross = 2 * (d * d + d) + 4 * 64 + 128
    mlp, norms = 3 * d * f, 4 * d
    assert (mamba, 2 * d * c, mlp) == (41_241_600, 26_214_400, 78_643_200)
    assert count == whole["parameters"] == (
        9 * mamba + 9 * attn + 7 * 2 * d * c + 7 * cross
        + 32 * (mlp + norms) + v * d + 2 * d)
    assert round(count / 1e6) == 3853
    cfg = module.config
    assert cfg.state_bytes_per_slot() == 3_225_600
    assert cfg.kv_bytes_per_token() == {"global": 5120, "window": 40960}
    assert (cfg.head_dim, cfg.mamba_inner, cfg.dt_rank) == (64, 5120, 160)
    serving = _committed_cell()["serve"]["serving"]
    slots, blocks = serving["decode_slots"], serving["num_blocks"]
    pools = type(module)(cfg.for_paged_decode(
        blocks, 32, state_slots=slots)).pool_shapes(blocks, 32)
    ring = 1 + slots * 17
    assert pools == {"global_key_pool": (1, blocks, 32, 1280),
                     "global_value_pool": (1, blocks, 32, 1280),
                     "window_key_pool": (8, ring, 32, 1280),
                     "window_value_pool": (8, ring, 32, 1280),
                     "ssm_state_pool": (9, 1 + slots, 40, 16, 128),
                     "ssm_conv_pool": (9, 1 + slots, 15360)}
    held = 2 * count + sum(
        math.prod(s) * (4 if name == "ssm_state_pool" else 2)
        for name, s in pools.items())
    # the programs' arguments: 70-85% of a chip's 16 GB
    assert 0.70 * 16e9 < held < 0.85 * 16e9


def test_the_committed_cell_loads_and_its_entries_resolve():
    cell = _committed_cell()
    assert cell["job"] == "serve_counted_phi4flash" and cell["chips"] == 1
    job, base = byname.module("jobs", cell["job"]), byname.module(
        "jobs", "serve_counted")
    assert (job.setup, job.run, job.teardown) == (base.setup, base.run,
                                                  base.teardown)
    assert job.check is not base.check
    assert cell["config"] == COMMITTED_CONFIG
    assert cell["traffic"] == "reasoning-long"
    # the accepted metrics it takes up by name (its own are parked), and
    # what it reports
    names = {s["name"] for s in bench_run.layer_metric_specs(cell)}
    assert names == {"hybrid_decode_roofline_share", "kv_window_share"}
    declared = bench_run.declared_metrics()
    assert {m["name"] for m in bench_run.metrics_of(
        COMMITTED_CELL, declared["end_to_end"])} == {"served_tok_s",
                                                     "setup_s"}
    mix = cell["traffic_file"]
    assert mix["max_total"] == 7168 and "bursts" not in mix["arrivals"]
    assert mix["arrivals"]["process"] == "poisson"
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 512,
                                 "sigma": 1.0, "min": 64, "max": 4096}
    assert mix["new_tokens"] == {"dist": "lognormal", "median": 1536,
                                 "sigma": 0.6, "min": 256, "max": 3072}
    assert mix["prompt_len"]["max"] + mix["new_tokens"]["max"] == 7168
    serving = cell["serve"]["serving"]
    assert (serving["block_size"], serving["max_model_len"],
            serving["prefill_chunk_tokens"], serving["prompt_buckets"]) == (
                32, 7168, 512, [7168])
    assert serving["decode_slots"] in (64, 96)
    # additions only, found by name: one configuration, one cell, and the
    # cell's name in three lists
    bench = _committed_bench()
    entry = _named(bench["workloads"], COMMITTED_CELL)
    assert entry == {"name": COMMITTED_CELL, "config": COMMITTED_CONFIG,
                     "traffic": "reasoning-long", "chips": 1,
                     "why": cell["why"]}
    assert len(entry["why"]) <= 200
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert (COMMITTED_CELL in m.get("workloads", ())) == (
            m["name"] in TAKEN_UP), m["name"]
