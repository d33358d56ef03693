"""Job ``serve_counted`` (``jobs/serve_counted.py``: its set-up, window,
counters and teardown, none of it restated here) for ``deepseek-v2-lite-l6``,
with a ``check`` of its own: ``serve_counted``'s comparisons, every one,
made so that a context of 16,384 fits, and held to limits read on THIS
configuration.

What differs from ``serve_counted.check`` and why:

- THE REFERENCE'S WIDTH AND ITS HEAD. ``serve_counted`` runs the reference
  over the mix's longest context whatever the request, and takes its
  logits at every position: 16,384 positions over a vocabulary of 102,400
  are 6.7 GB of float32, beside 10.9 GB of weights and pool. Here a request
  is checked at its own length in whole ``WIDTH_STEP``s (a few compiled
  widths, not one a request), and the reference's head is taken at the
  positions the served tokens are judged at (at most the mix's longest
  answer).
- THE SPARSE LAYER HAS TWO TERMS. The family's ``expert_layer_error``
  holds the routed experts' sum and the shared experts' term each to the
  reference's, apart (in their sum float8 routed experts would hide behind
  bfloat16 shared ones).
- THE LIMITS. ``serve_counted``'s lie between chip readings of MiMo-V2.5's
  share, whose selection scores are sigmoids of order 1; this gate's are a
  softmax's over 64 experts, of order 1/64, and the k-th and the next lie
  proportionally closer. Each limit below lies between this
  configuration's bfloat16 readings and its lower-precision controls'
  (PERF.md, section 6, PR 45, has every reading and the call it came
  from; ``tools/chip_logits_deepseek_v2.py --through-check`` runs the cell
  through this check with each control in force).
"""

import numpy as np

from perfbench import traffic
from perfbench.jobs import serve
from perfbench.jobs.serve_counted import (run, setup,  # noqa: F401
                                          teardown)

# the reference's width: a checked request's length in whole steps of this
# (a multiple of the reference's blocks of queries and of rows)
WIDTH_STEP = 4096
# Each limit between two chip readings, in ratio about midway (my chip
# runs, PR 45: PERF.md, section 6, has the calls). The controls: the
# latent row in float8 on its way into the pool; ``W_kvb``'s absorbed
# halves in float8 (decode steps alone read them); the expert matrices in
# float8; the gate's input in bfloat16.
# Share of the judged tokens that are the reference's argmax itself: the
# bfloat16 program read 0.964-0.985; of the DECODE steps' logits (every
# judged token but a request's first is one) the absorbed halves in float8
# left 0.77 the reference's argmax, the latent pool in float8 0.91 of all
MIN_EXACT_SHARE = 0.90
# a served token's distance under the reference's argmax, of the largest
# |logit|: bfloat16 0.0020-0.0072, and its logits lie 0.0075-0.0089 (95th
# percentile of positions) from the reference's; float8 absorbed halves
# 0.055 at the decode steps, a float8 latent pool 0.049-0.055 everywhere
NEAR_TIE_RTOL = 0.02
# how far under the reference's own 6th probability (of a softmax over 64:
# the 6th lies near 0.02, a sigmoid's scores near 0.5) a served set's
# lowest lies: bfloat16 0.0012-0.0020 over seventeen runs; float8 absorbed
# halves 0.0055, a float8 latent pool 0.0073 (a quarter of its sets are
# not the reference's own, bfloat16's 4-5%)
ROUTED_MARGIN_MAX = 0.0035
# the same for the program's gate over the reference's OWN float32 inputs,
# where what is left is the gate's arithmetic: the float32 gate read 0.0
# every time, its input in bfloat16 1.8e-4 over one short sequence
GATE_MARGIN_MAX = 2e-5
# of the root mean square of a sparse layer's routed sum, and of its shared
# experts' term, the larger: bfloat16 0.00352-0.00354 in every layer, the
# expert matrices in float8 0.0562-0.0563
EXPERT_ERROR_MAX = 0.0125


def check(state: dict, result: dict) -> dict:
    import jax
    import jax.numpy as jnp

    srv, cell, seed = state["srv"], state["cell"], state["seed"]
    family, config_file = cell["family"], cell["config_file"]
    reqs = state["requests"]
    prompts = traffic.requests(state["mix"], seed, result["notes"]["window_s"],
                               state["vocab"])
    done = [i for i, r in enumerate(reqs) if r["ok"] and r["tokens"]]
    rng = np.random.default_rng([int(seed), 13])
    picked = sorted(rng.choice(done, min(serve.CHECKED_REQUESTS, len(done)),
                               replace=False).tolist()) if done else []
    params = srv.engine.params
    layers = family.sparse_layers(config_file)
    ref = jax.jit(family.reference_logits_given(config_file))
    layer_error = jax.jit(family.expert_layer_error(
        config_file, srv.engine.module.config))
    answers = int(state["mix"]["new_tokens"]["max"])
    judged = exact = handed = differ = 0
    worst = margin = gate_margin = expert_error = 0.0
    unrouted, widths = [], []
    for i in picked:
        prompt, served = prompts[i]["prompt"], reqs[i]["tokens"]
        sets = srv.routed_experts(reqs[i]["record"]["request_id"])
        n = len(prompt) + len(served) - 1     # the last was never fed back
        if sets is None or len(sets) != n:
            unrouted.append(i)
            continue
        width = min(-(-(n + 1) // WIDTH_STEP) * WIDTH_STEP,
                    max(state["max_context"], n + 1))
        widths.append(width)
        ids = np.zeros((1, width), np.int32)  # right padding: causal, unseen
        ids[0, :n + 1] = prompt + served
        given = np.full((1, width, len(layers), sets.shape[1] // len(layers)),
                        -1, np.int32)
        given[0, :n] = sets.reshape(n, *given.shape[2:])
        # the positions the served tokens are judged at
        at = np.minimum(len(prompt) - 1 + np.arange(answers), width - 1)
        logits, seen = ref(params, jnp.asarray(ids), jnp.asarray(given),
                           jnp.asarray(at, jnp.int32))
        logits = np.asarray(logits)[0]
        for k, token in enumerate(served):
            row = logits[k]
            gap = float(row.max() - row[token]) / float(np.abs(row).max())
            judged, exact = judged + 1, exact + (gap == 0.0)
            worst = max(worst, gap)
        margin = max(margin, float(np.asarray(seen["margin"])[:, 0, :n].max()))
        handed += len(layers) * n
        differ += int(np.asarray(seen["differs"])[:, 0, :n].sum())
        valid = jnp.arange(width) < n
        for place, name in enumerate(layers):
            error, tie = layer_error(params[name], seen["inputs"][place, 0],
                                     valid)
            expert_error = max(expert_error, float(error))
            gate_margin = max(gate_margin, float(tie))
        del seen, logits
    return {"correct": bool(picked and not unrouted
                            and worst <= NEAR_TIE_RTOL
                            and exact >= MIN_EXACT_SHARE * judged
                            and margin <= ROUTED_MARGIN_MAX
                            and gate_margin <= GATE_MARGIN_MAX
                            and expert_error <= EXPERT_ERROR_MAX),
            "requests_checked": picked, "reference_widths": widths,
            "tokens_judged": judged,
            "tokens_exact_argmax": exact, "largest_gap_rel": worst,
            "near_tie_rtol": NEAR_TIE_RTOL,
            "min_exact_share": MIN_EXACT_SHARE,
            "requests_without_routed_sets": unrouted,
            "routed_sets_differ_share": differ / handed if handed else None,
            "routed_margin": margin, "routed_margin_max": ROUTED_MARGIN_MAX,
            "gate_margin": gate_margin, "gate_margin_max": GATE_MARGIN_MAX,
            "expert_error": expert_error,
            "expert_error_max": EXPERT_ERROR_MAX}
