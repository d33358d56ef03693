"""Job ``serve_counted`` (``jobs/serve_counted.py``: its set-up, window,
counters and teardown, none of it restated here) for ``k-exaone-236b-ep8``,
with the seeded selection biases balanced behind the set-up (``setup``) and
a ``check`` of its own: ``serve_counted``'s comparisons, every one,
over the tokens the WINDOW's requests were served (their routed sets the
program's own, ``serving.routed_experts_kept``), each request at its own
length, and held to limits read on THIS configuration.

What differs from ``serve_counted.check`` and why:

- THE REFERENCE'S WIDTH. ``serve_counted`` runs the reference over the
  mix's longest context whatever the request. This mix's answers are three
  times its prompts and a checked request is 300 to 4,096 tokens long, so a
  request is checked at its own length in whole ``WIDTH_STEP``s (at most
  four compiled widths, not one a request): the float32 reference of a
  sparse layer at 4,096 positions stands beside 8.7 GB of weights and pool.
- THE SPARSE LAYER HAS TWO TERMS. The family's ``expert_layer_error`` holds
  the held routed experts' sum and the shared expert's term each to the
  reference's, apart (in their sum float8 routed experts would hide behind
  a bfloat16 shared expert: this chip adds ONE routed term a token beside
  the unweighted shared one).
- THE LIMITS. ``serve_counted``'s lie between chip readings of MiMo-V2.5's
  share (256 experts, no shared expert, no QK-norm, 4096 wide). Each limit
  below lies between this configuration's bfloat16 readings and its
  lower-precision controls' (PERF.md, section 6, PR 52, has every reading
  and the call it came from; ``tools/chip_logits_exaone_moe.py
  --through-check`` runs the cell through this check with each control in
  force).
"""

import numpy as np

from perfbench import traffic
from perfbench.jobs import serve
from perfbench.jobs import serve_counted
from perfbench.jobs.serve_counted import run, teardown  # noqa: F401

# the reference's width: a checked request's length in whole steps of this
# (a multiple of the reference's blocks of 512 queries)
WIDTH_STEP = 1024
# Each limit between two chip readings, in ratio about midway (my chip
# runs, PR 52: PERF.md, section 6, has the calls and every run's numbers).
# The controls (``tools/chip_logits_exaone_moe.py --through-check``, 20 s
# windows): ``pool``, the keys and values through float8 on their way into
# both pools; ``stale``, one row of every slot's ring never written;
# ``experts``, the expert matrices in float8; ``gate``, the gate's input in
# bfloat16.
# Share of the judged tokens that are the reference's argmax itself: the
# bfloat16 program read 0.976-0.986 over thirty-four runs on as many seeds
# (seven of them with the biases balanced: 0.979-0.984; 1,535-5,960 tokens
# judged a run); a float8 pool 0.890, a stale ring row 0.818
MIN_EXACT_SHARE = 0.94
# a served token's distance under the reference's argmax, of the largest
# |logit|: bfloat16 0.0030-0.0114 over the thirty-four; a float8 pool
# 0.0413, a stale ring row 0.136 (float8 experts, which this limit need
# not catch, 0.0155)
NEAR_TIE_RTOL = 0.021
# how far under the reference's own 8th selection score (a sigmoid's plus
# the bias, of order 0.5) a served set's lowest lies: bfloat16
# 0.0036-0.0069 over the thirty-four (7-9% of its sets are not the
# reference's own; balanced biases 0.0043-0.0069); a float8 pool 0.0328
# (38% are not), a stale ring row 0.113
ROUTED_MARGIN_MAX = 0.014
# the same for the program's gate over the reference's OWN float32 inputs,
# where what is left is the gate's arithmetic: the float32 gate read 0.0
# every time (it chooses the reference's sets), its input in bfloat16 7.6e-4
GATE_MARGIN_MAX = 1e-4
# of the root mean square of a sparse layer's held routed sum, and of its
# shared expert's term, the larger: bfloat16 0.00345-0.00348 in all of them,
# the expert matrices in float8 0.102
EXPERT_ERROR_MAX = 0.019


def setup(cell: dict, seed: int, device: dict) -> dict:
    """``serve_counted``'s, and then the selection biases BALANCED where
    the configuration asks for it (``weights.selection_bias_balance``; the
    family's ``balanced_weights``): the engine hands its parameters to
    every call, so the tree is replaced leaf for leaf and no program
    changes."""
    state = serve_counted.setup(cell, seed, device)
    balanced = cell["family"].balanced_weights(cell["config_file"])
    if balanced is not None:
        engine = state["srv"].engine
        engine.params = balanced(engine.params, seed)
    return state


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
        logits, seen = ref(params, jnp.asarray(ids), jnp.asarray(given))
        logits = np.asarray(logits)[0]
        for k, token in enumerate(served):
            row = logits[len(prompt) - 1 + k]
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
