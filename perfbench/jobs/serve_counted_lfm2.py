"""Job ``serve_counted`` (``jobs/serve_counted.py``: its set-up, window,
counters, teardown and every comparison of its ``check``, none of it
restated here) with the limits of the served-token rule and of the routed
sets read on THIS configuration, ``lfm2-8b-a1b-l14``.

``serve_counted``'s limits lie between chip readings of MiMo-V2.5's share
(a vocabulary slice of 19,072, one expert in sixteen held). Read against
them, this model's served program does not lie inside with room (PERF.md,
PR 43, has every run): with a tied head of N(0, 0.02) over the WHOLE
vocabulary of 65,536 the reference's largest two logits lie 0.2 apart at
a largest |logit| of 4.5-5.2, and the bfloat16 program's logits lie
0.0036-0.0039 of that (rms) from the float32 reference's, so 94.5% of the
served tokens were the reference's argmax in the first run and 94% is the
limit; the float8 controls read 57%. So three limits are this file's own,
each between this configuration's two readings, in ratio about midway;
the two of the sparse layers (``EXPERT_ERROR_MAX``, ``GATE_MARGIN_MAX``)
lie between its readings as they stand (0.0029 against 0.056; 0.0 against
the gate's input in bfloat16) and are ``serve_counted``'s.
"""

from perfbench.jobs import serve_counted
from perfbench.jobs.serve_counted import (run, setup,  # noqa: F401
                                          teardown)

# share of the judged tokens that are the reference's argmax itself: the
# bfloat16 program read 0.945 at the first seed; the convolutions' W_in and
# state in float8 0.575
MIN_EXACT_SHARE = 0.80
# a served token's distance under the reference's argmax, of the largest
# |logit|: bfloat16 0.0144; float8 convolutions 0.210; the state taken at
# the bucket's end 1.0 at a request's first decode step
NEAR_TIE_RTOL = 0.06
# how far under the reference's own 4th selection score a served set's
# lowest lies: bfloat16 0.0103-0.0188; float8 convolutions 0.140-0.148;
# the state at the bucket's end 0.574
ROUTED_MARGIN_MAX = 0.05


def check(state: dict, result: dict) -> dict:
    seen = serve_counted.check(state, result)
    correct = bool(
        seen["requests_checked"] and not seen["requests_without_routed_sets"]
        and seen["largest_gap_rel"] <= NEAR_TIE_RTOL
        and seen["tokens_exact_argmax"]
        >= MIN_EXACT_SHARE * seen["tokens_judged"]
        and seen["routed_margin"] <= ROUTED_MARGIN_MAX
        and seen["gate_margin"] <= serve_counted.GATE_MARGIN_MAX
        and seen["expert_error"] <= serve_counted.EXPERT_ERROR_MAX)
    return {**seen, "correct": correct, "near_tie_rtol": NEAR_TIE_RTOL,
            "min_exact_share": MIN_EXACT_SHARE,
            "routed_margin_max": ROUTED_MARGIN_MAX}
