"""A kernel's arithmetic is a file of its own, found by the ``kernel`` of
the metric's file; it asks the cell's family for the model's shapes. The
two committed kernels by hand at the committed cells' shapes, and a
test-only kernel of a test-only family resolved from the throw-away copy."""

import pytest

from perfbench import run as bench_run
from perfbench import trace_reduce as tr
from perfbench.byname import BenchError
from perfbench.readers import kernel_roofline

from .test_harness import PERFBENCH

V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
KIND = {"kind": "TPU v5 lite"}


def _spec(cell, metric, root):
    (spec,) = [s for s in bench_run.layer_metric_specs(cell, root)
               if s["name"] == metric]
    return spec


def _flash(name, start):
    return (f"%{name} = bf16[8,16,1024,64]{{3,2,1,0}} custom-call("
            'bf16[8,16,1024,64] %q), custom_call_target="tpu_custom_call"',
            start, 100)


@pytest.mark.parametrize("cell,rows,heads", [
    ("train-medium-1chip", 8, 16), ("train-xl-zero3-4chip", 16, 25)])
def test_flash_train_by_hand(cell, rows, heads):
    """Three events are one attention call, forward + backward, on the
    rows one chip holds: 3 * 4 * B * H * T * T * D / 2 operations at 197
    TFLOP/s (25 MB of traffic at 819 GB/s is far less). Of the events,
    only the flash kernels match: not the paged kernel, not a fusion that
    reads a flash kernel's result."""
    cell = bench_run.load_cell(cell, PERFBENCH)
    spec = _spec(cell, "flash_roofline_share", PERFBENCH)
    chips = cell["chips"]
    ops = [_flash("flash_fwd.3", 0), _flash("flash_bwd_dq.10", 100),
           _flash("flash_bwd_dkv", 200), _flash("flash_fwd.3", 300),
           _flash("flash_bwd_dq.10", 400), _flash("flash_bwd_dkv", 500),
           _flash("attn._paged_kv_attend.9", 600),
           ("%fusion.7 = bf16[8]{0} fusion(bf16[8] %flash_fwd.3), "
            'kind=kLoop, note="tpu_custom_call"', 700, 100)]
    trace = tr.Trace({c: list(ops) for c in range(chips)},
                     [("perfbench.window", 0, 1000)])
    facts = {"cell": cell, "device": KIND, "chips": chips, "rows": rows,
             "seq_len": 1024, "trace_events": trace}
    per_call = 3 * 4 * (rows // chips) * heads * 1024 * 1024 * 64 / 2 / 197e12
    assert kernel_roofline.read(spec, facts) == pytest.approx(
        100.0 * (2 * chips * per_call) / (6 * chips * 100e-9), rel=1e-12)
    trace.device_ops = {0: ops[6:]}
    assert kernel_roofline.read(spec, facts) is None


def test_paged_decode_by_hand():
    """Tokens that arrived inside the traced span [10, 20) were produced by
    a step that read the prompt and the tokens before them: request one's
    tokens 1 and 2 (30 + 1, 30 + 2 live), request two's token 1 (5 + 1);
    first tokens come from a prefill and are not counted. Keys and values,
    48 layers x 25 heads x 64, bf16, over 819 GB/s."""
    cell = bench_run.load_cell("serve-xl-chat", PERFBENCH)
    spec = _spec(cell, "paged_decode_roofline_share", PERFBENCH)
    kernel = ('%attn._paged_kv_attend.9 = bf16[32,1600]{1,0} custom-call('
              'bf16[32,1600] %q), custom_call_target="tpu_custom_call"')
    trace = tr.Trace({0: [(kernel, 0, 400), (kernel, 500, 100)]},
                     [("perfbench.window", 0, 1000)])
    facts = {"cell": cell, "device": KIND, "chips": 1,
             "trace_events": trace, "traced_span_s": [10.0, 20.0],
             "requests": [
                 {"prompt_len": 30, "arrivals": [9.0, 10.5, 11.0, 20.0]},
                 {"prompt_len": 5, "arrivals": [12.0, 13.0]},
                 {"prompt_len": 7, "arrivals": []}]}
    live = 31 + 32 + 6
    least_s = 2 * live * 48 * 25 * 64 * 2 / 819e9
    assert kernel_roofline.read(spec, facts) == pytest.approx(
        100.0 * least_s / 500e-9, rel=1e-12)


def test_a_new_kernel_is_found_by_the_metric_files_kernel(bench_copy):
    """``tiny-matmul_roofline`` exists only in the copy: its metric file
    names ``kernels/tiny-matmul.py``, which asks the family ``tiny-alt``
    for its shapes (4 heads of 8: 2 * 32^3 operations an event)."""
    root, _ = bench_copy
    cell = bench_run.load_cell("tiny-alt-train", root)
    spec = _spec(cell, "tiny-matmul_roofline", root)
    assert spec["kernel"] == "tiny-matmul" and spec["needs_chip"] is True
    fusion = "%fusion.1 = f32[32,32]{1,0} fusion(f32[32,32] %p), kind=kLoop"
    trace = tr.Trace({0: [(fusion, 0, 50), (fusion, 100, 50),
                          ("%copy.2 = f32[8]{0} copy(f32[8] %x)", 200, 50)]},
                     [("perfbench.window", 0, 1000)])
    facts = {"cell": cell, "device": KIND, "chips": 1, "trace_events": trace}
    assert kernel_roofline.read(spec, facts) == pytest.approx(
        100.0 * (2 * 2.0 * 32 ** 3 / 197e12) / 100e-9, rel=1e-12)
    # a name with no file: no result, and the names there are
    with pytest.raises(BenchError, match="no-such-kernel.*flash_train"):
        kernel_roofline.read({**spec, "kernel": "no-such-kernel"}, facts)
