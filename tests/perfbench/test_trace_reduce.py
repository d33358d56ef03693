"""``trace_reduce`` on a fixture small enough to check by hand.

One chip, times in ns. Window [0, 1000).

    device ops                      host annotations
    while.1 (container)  [100,700)  perfbench.window      [0,1000)
    fusion.1             [100,300)  perfbench.train.step  [0,720)
    attn.2 custom-call   [300,500)  perfbench.train.batch [720,850)
    all-gather.3         [450,700)
    fusion.4             [900,1100)  -> clipped to [900,1000)

busy = [100,700) + [900,1000) = 700;  idle gaps: [0,100) and [700,900).
"""

import pytest

from perfbench import trace_reduce as tr

WHILE = ("%while.1 = (s32[]{:T(128)}, bf16[8,16]{1,0:T(8,128)(2,1)}) "
         "while((s32[]{:T(128)}) %tuple.1), condition=%c, body=%b")
FUSION1 = "%fusion.1 = bf16[8,16]{1,0:T(8,128)(2,1)} fusion(bf16[8,16] %p), kind=kLoop"
ATTN = ("%attn.2 = bf16[8,16]{1,0:T(8,128)(2,1)} custom-call(bf16[8,16] %q), "
        'custom_call_target="tpu_custom_call"')
GATHER = "%all-gather.3 = f32[32]{0:T(128)} all-gather(f32[8] %x), dimensions={0}"
FUSION4 = "%fusion.4 = f32[8]{0:T(128)} fusion(f32[8] %y), kind=kInput"


@pytest.fixture
def trace():
    ops = [(WHILE, 100, 600), (FUSION1, 100, 200), (ATTN, 300, 200),
           (GATHER, 450, 250), (FUSION4, 900, 200)]
    host = [("perfbench.window", 0, 1000), ("perfbench.train.step", 0, 720),
            ("perfbench.train.batch", 720, 130)]
    return tr.Trace({0: ops}, host)


def test_short_names_and_containers():
    assert tr.short_name(WHILE) == ("while.1", "while")
    assert tr.short_name(ATTN) == ("attn.2", "custom-call")
    assert tr.short_name("not an instruction") == ("not an instruction", "")
    assert [tr.short_name(e[0])[0] for e in tr.leaves(
        [(WHILE, 0, 1), (ATTN, 0, 1)])] == ["attn.2"]


def test_interval_arithmetic():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tr.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert tr.clip([("a", 0, 10), ("b", 20, 5)], 5, 22) == \
        [("a", 5, 5), ("b", 20, 2)]


def test_summary_by_hand(trace):
    s = tr.summarize(trace)
    assert s["window_s"] == pytest.approx(1000e-9)
    assert s["busy_s"] == pytest.approx(700e-9)
    assert s["chips"] == 1
    assert s["op_seconds"] == pytest.approx({
        "fusion.1 fusion": 200e-9, "attn.2 custom-call": 200e-9,
        "all-gather.3 all-gather": 250e-9, "fusion.4 fusion": 100e-9})
    # the gather runs [450,700); attn covers [450,500): 200 ns are exposed
    assert s["exposed_collective_s"] == pytest.approx(200e-9)
    # [0,100) lies under train.step; the middle of [700,900) under
    # train.batch, the innermost annotation there
    assert s["idle_gap_seconds"] == pytest.approx({
        "perfbench.train.step": 100e-9, "perfbench.train.batch": 200e-9})
    assert s["breakdown"]["device_ops"][0] == \
        ["all-gather.3 all-gather", pytest.approx(250e-9)]


def test_gap_without_annotation_is_unattributed(trace):
    trace.host[:] = [("perfbench.window", 0, 1000)]
    assert tr.summarize(trace)["idle_gap_seconds"] == \
        pytest.approx({"unattributed": 300e-9})


def test_time_matching_finds_kernels_by_full_text(trace):
    events = tr.window_events(trace)[0]
    assert tr.time_matching(
        events, r'custom-call\(.*custom_call_target="tpu_custom_call"') == \
        (200, 1)
    assert tr.time_matching(events, "no such op") == (0, 0)


def test_two_chips_are_averaged(trace):
    trace.device_ops[1] = [(FUSION1, 0, 1000)]
    s = tr.summarize(trace)
    assert s["chips"] == 2
    assert s["busy_s"] == pytest.approx((700e-9 + 1000e-9) / 2)


def test_operand_bytes_of_collectives():
    a2a = ("%all-to-all.16 = bf16[4,1024,4,1600]{1,3,0,2:T(8,128)(2,1)} "
           "all-to-all(bf16[4,1024,4,1600]{1,3,0,2:T(8,128)(2,1)} %copy.314), "
           "channel_id=34, replica_groups=[1,4]<=[4]")
    assert tr.operand_bytes(a2a) == 4 * 1024 * 4 * 1600 * 2
    start = ("%collective-permute-start.2 = (bf16[1,400,1600]{2,1,0}, "
             "bf16[1,400,1600]{2,1,0}, u32[]{:S(2)}, u32[]{:S(2)}) "
             "collective-permute-start(bf16[1,400,1600]{2,1,0:T(8,128)(2,1)} "
             "%x), source_target_pairs={{0,1}}")
    assert tr.operand_bytes(start) == 400 * 1600 * 2
    done = ("%collective-permute-done.2 = bf16[1,400,1600]{2,1,0} "
            "collective-permute-done((bf16[1,400,1600]{2,1,0}, "
            "bf16[1,400,1600]{2,1,0}, u32[]{:S(2)}, u32[]{:S(2)}) %s)")
    assert tr.operand_bytes(done) == 0
    assert tr.operand_bytes(GATHER) == 8 * 4
    assert tr.operand_bytes("not an instruction") == 0


def test_collective_bytes_per_step_from_executed_events(trace):
    from perfbench.readers import collective_bytes_step

    # one all-gather of f32[8] in the window, two steps, one chip
    assert collective_bytes_step.read(
        {}, {"trace_events": trace, "steps": 2}) == 32 / 2
    trace.device_ops[0] = [e for e in trace.device_ops[0] if e[0] != GATHER]
    assert collective_bytes_step.read(
        {}, {"trace_events": trace, "steps": 2}) is None


def test_the_programs_own_brackets_name_a_gap(trace):
    """``ds.*`` annotations are read beside ``perfbench.*``, and the
    innermost covering one names a gap: [0,100) lies under ds.serve.step
    and, inside it, ds.serve.decode.sync; [700,900) has its middle, 800,
    under ds.serve.step alone (another thread's ds.gateway.sse_write ended
    at 790). The benchmark's wait covers everything and names nothing."""
    trace.host[:] = [
        ("perfbench.window", 0, 1000),
        ("perfbench.serve.wait_for_client", 0, 1000),
        ("ds.serve.step", 0, 950), ("ds.serve.decode", 0, 500),
        ("ds.serve.decode.sync", 40, 80), ("ds.gateway.sse_write", 690, 100),
        ("some.other.annotation", 0, 10)]
    assert tr.summarize(trace)["idle_gap_seconds"] == pytest.approx({
        "ds.serve.decode.sync": 100e-9, "ds.serve.step": 200e-9})
    assert tr.ANNOTATION.match("ds.serve.step")
    assert tr.ANNOTATION.match("perfbench.train.step")
    assert not tr.ANNOTATION.match("some.other.annotation")
    assert not tr.ANNOTATION.match("dsx.serve")


def _idle_gaps_one_by_one(events, lo, hi, host):
    """``idle_gaps`` as it was before it became one sweep: every gap asks
    every annotation. Kept here as the reference."""
    out = {}
    for s, e in tr.subtract([(lo, hi)], tr.union(tr._spans(events))):
        mid = (s + e) // 2
        covering = [(d, n) for n, hs, d in host
                    if hs <= mid < hs + d and n != tr.WINDOW_ANNOTATION]
        name = min(covering)[1] if covering else "unattributed"
        out[name] = out.get(name, 0) + (e - s)
    return out


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_sweep_names_gaps_as_asking_every_annotation_did(seed):
    import numpy as np

    rng = np.random.default_rng(seed)
    starts = np.sort(rng.integers(0, 100_000, 400))
    events = [("%op", int(s), int(d))
              for s, d in zip(starts, rng.integers(1, 300, 400))]
    host = [("perfbench.window", 0, 100_000)] + [
        (f"ds.layer.{i % 7}", int(s), int(d))
        for i, (s, d) in enumerate(zip(rng.integers(0, 100_000, 300),
                                       rng.integers(1, 20_000, 300)))]
    got = tr.idle_gaps(events, 0, 100_000, host)
    assert got == _idle_gaps_one_by_one(events, 0, 100_000, host)
    assert len(got) > 3 and sum(got.values()) == \
        100_000 - tr.busy_ns(tr.clip(events, 0, 100_000))
