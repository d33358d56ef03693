"""Job ``serve_counted`` (``jobs/serve_counted.py``: its set-up, window,
counters and teardown, none of it restated here) for ``deepseek-v3.2-ep32``,
with the seeded selection biases balanced behind the set-up (``setup``) and
a ``check`` of its own: ``serve_counted``'s comparisons, every one, over the
tokens the WINDOW's requests were served (their routed sets the program's
own, ``serving.routed_experts_kept``), each request at its own length, one
of them past 16,384 tokens, held to limits read on THIS configuration; and,
new here, THE SELECTION IS CHECKED AS A SET AND THEN TAKEN AS GIVEN.

What differs from ``serve_counted_bailing_hybrid.check`` and why:

- THE SELECTED KEYS. A bfloat16 index score is off by some 0.4% of itself
  and the 2,048th of 10,000 scores has neighbours a hundredth of that
  away: a query's set differs from the float32 reference's in some tens of
  its keys, on a rounding, in every query, and a comparison of logits that
  let the reference choose for itself would need a tolerance wide enough to
  hide a real fault. So each checked request is served ONCE MORE after the
  drain, alone, through the gateway's own engine and therefore by the
  window's own compiled chunk and decode programs, asking for its chosen
  keys (``keep_selected``: the programs return them always, the engine
  fetches them only now); it has to come out token for token as the window
  served it (``replayed_tokens_differ``), and then (i) every key the
  program chose and the reference did not, and the reverse, has a
  reference score within ``SELECT_MARGIN_MAX`` of the reference's own
  2,048th (as a share of the row's largest score; ``select_flips_mean``
  reports how many such keys a query has), and (ii) the logits are compared
  with the reference attending THE PROGRAM'S sets at every position.
- ONE LAYER A CALL, in widths of ``WIDTH_STEP``: a context of 32,768 is a
  float32 stream of 0.94 GB (``families/deepseek_v32.py:
  reference_by_layer``); the head at the judged positions alone; a sparse
  layer's error over the first ``ERROR_ROWS`` positions' inputs.
- A REQUEST PAST 16,384 TOKENS is among the checked wherever the window
  finished one (the seeded sample's last place goes to it).
- THE LIMITS are this configuration's (the readings beside each).

``CONTROLS`` of ``tests/perfbench/test_deepseek_v32_cell.py`` are the forms
the check has to read as not correct (the selection ignored; the most recent
keys chosen instead of the best; a chunk's index rows not written);
``tools/chip_logits_deepseek_v32.py --through-check`` runs the cell through
this check with each in force on the chip.
"""

import time

import numpy as np

from perfbench import traffic
from perfbench.jobs import serve
from perfbench.jobs import serve_counted
from perfbench.jobs.serve_counted import run, teardown  # noqa: F401

# the reference's width: a checked request's length in whole steps of this
# (a multiple of every block of the reference; two layer programs a width)
WIDTH_STEP = 8192
# a request longer than this is among the checked
LONG_REQUEST = 16384
# positions whose float32 inputs a sparse layer's error is taken over
ERROR_ROWS = 2048
# seconds a replayed request may take (the longest is 60 chunks and 1,024
# steps of one busy row)
REPLAY_TIMEOUT_S = 180.0
# Each limit between two chip readings (my chip runs, PR 59: PERF.md,
# section 6, has the calls and every run's numbers). The served program's
# are the cell's own runs (call 4 and the two sets of six) and
# ``tools/chip_logits_deepseek_v32.py`` (call 5: 16,384 + 256 tokens); the
# controls' are that tool's, on a 4,096-token prompt, and THROUGH THIS CHECK
# at the cell's size (``--through-check``, 20 s windows: the first session's
# calls 7-8 and the review round's call R1): ``ignored`` (every live key
# attended), ``recent`` (the most recent 2,048 keys), ``not-written`` (a
# chunk's index rows not pooled), ``latent`` (the pooled latent row, the
# sparse attention's keys and values, in float8: the nearest precision
# below the configuration's for the path the new attention reads),
# ``experts`` (the expert matrices in float8), ``gate`` (the gate's input in
# bfloat16).
# Share of the judged tokens that are the reference's argmax itself, the
# reference attending the PROGRAM's keys and experts: served 0.9579-0.9760
# a run over thirty-seven runs on as many seeds (656-2,017 tokens judged a
# run), 0.949-0.976 a request; the latent row in float8, through this
# check: 0.830 of 1,013 (0.783-0.871 a request); with the experts in float8
# and the gate's input in bfloat16 0.965 (they do not pass through here).
# The dense latent model's limit (``serve_counted_deepseek_v2``: 0.90
# between its 0.964-0.985 and a float8 half's 0.77) holds at 128 heads
MIN_EXACT_SHARE = 0.90
# a served token's distance under the reference's argmax, of the largest
# |logit|: served 0.0051-0.0183 a run; the logits themselves lie 0.0129
# (95th percentile of positions) and 0.0141 (largest) from the reference's
# when it attends the program's keys, and 0.154 and 0.348 when it chooses
# ITS OWN (argmax agreement 0.957 against 0.738): a flipped key costs ten
# times the arithmetic, which is why the sets are handed in; the latent
# row in float8, through this check: 0.0726 (0.056-0.073 a request)
NEAR_TIE_RTOL = 0.03
# how far from the reference gate's own choice a served set lies, through
# the groups (``reference_bailing_hybrid.routed``; selection scores of order
# 0.5): served 0.0080-0.0128 (13% of its sets are not the reference's own);
# the latent row in float8 0.0694 (0.049-0.069 a request: 56% of its sets
# differ); the family's limit for this gate
# (``serve_counted_bailing_hybrid``: 0.03 under a state not written back's
# 0.79)
ROUTED_MARGIN_MAX = 0.03
# the same for the program's gate over the reference's OWN float32 inputs:
# the float32 gate read 0.0 every time, its input in bfloat16 7.7e-4 by
# the tool and 9.8e-4 through this check
GATE_MARGIN_MAX = 1e-4
# of the root mean square of a sparse layer's held routed sum, and of its
# shared expert's term, the larger: bfloat16 0.00348-0.00351 in every layer
# of every run, the expert matrices in float8 0.0565-0.0569 (0.0569
# through this check)
EXPERT_ERROR_MAX = 0.014
# how far on the wrong side of the reference's own 2,048th index score a
# key the program chose (or left out) lies, over the largest score of the
# query's row, the worst of all queries and layers: served 0.0324-0.0428
# over the thirty-seven runs (29.6-42.9 such keys a query in the mean: bfloat16
# index scores flip the near ties); every live key attended 1.19-1.46, the
# most recent 2,048 chosen 1.58 (both ways), a chunk's index rows not written 1.57-1.75
# (524-640 such keys a query on a 4,096-token prompt, 2,400-6,800 through
# the cell's own check: 1.455 / 1.584 / 1.753); the latent row in float8
# 0.224 (189 flips a query: the stream the later layers' indexers read is
# off)
SELECT_MARGIN_MAX = 0.15


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


def replayed(state: dict, prompt, served):
    """``(tokens, selected keys [tokens processed, layers, words] uint32)``:
    the request served once more, alone, by the gateway's engine (its own
    compiled programs), asking for the keys its queries chose. The engine
    is idle: its requests have drained."""
    srv, gateway = state["srv"], state["gateway"]
    req = gateway.submit(prompt, max_new_tokens=len(served),
                         keep_selected=True)
    wake = getattr(gateway, "_wake", None)
    if wake is not None:
        wake.set()
    deadline = time.monotonic() + REPLAY_TIMEOUT_S
    while req.finish_reason is None and time.monotonic() < deadline:
        time.sleep(0.01)
    # (the step that finished it lays the request's sets together before it
    # leaves the loop: 0.6 GB for a request of 30,000 tokens)
    while (req.finish_reason is not None and time.monotonic() < deadline
           and srv.selected_keys(req.request_id) is None):
        time.sleep(0.01)
    if req.finish_reason is None:
        return None, None
    return list(req.tokens), srv.selected_keys(req.request_id)


def _picked(state: dict, reqs, prompts, seed) -> list:
    """The checked requests: a seeded sample of the finished, its last
    place given to a request past ``LONG_REQUEST`` tokens where the sample
    holds none and the window finished one."""
    done = [i for i, r in enumerate(reqs) if r["ok"] and r["tokens"]]
    if not done:
        return []
    rng = np.random.default_rng([int(seed), 13])
    picked = rng.choice(done, min(serve.CHECKED_REQUESTS, len(done)),
                        replace=False).tolist()
    size = lambda i: len(prompts[i]["prompt"]) + len(reqs[i]["tokens"])
    long = [i for i in done if size(i) > LONG_REQUEST]
    if long and not any(size(i) > LONG_REQUEST for i in picked):
        picked[-1] = int(rng.choice(long))
    return sorted(picked)


def check(state: dict, result: dict) -> dict:
    import jax
    import jax.numpy as jnp

    srv, cell, seed = state["srv"], state["cell"], state["seed"]
    family, config_file = cell["family"], cell["config_file"]
    reqs = state["requests"]
    prompts = traffic.requests(state["mix"], seed, result["notes"]["window_s"],
                               state["vocab"])
    picked = _picked(state, reqs, prompts, seed)
    params = srv.engine.params
    layers = family.sparse_layers(config_file)
    limits = {"near_tie_rtol": NEAR_TIE_RTOL,
              "min_exact_share": MIN_EXACT_SHARE,
              "routed_margin_max": ROUTED_MARGIN_MAX,
              "gate_margin_max": GATE_MARGIN_MAX,
              "expert_error_max": EXPERT_ERROR_MAX,
              "select_margin_max": SELECT_MARGIN_MAX,
              **cell["serve"].get("limits", {})}
    ref = family.reference_by_layer(config_file)
    layer_error = jax.jit(family.expert_layer_error(
        config_file, srv.engine.module.config))
    answers = int(state["mix"]["new_tokens"]["max"])
    judged = exact = handed = differ = replays_differ = 0
    flips = queries = 0
    worst = margin = gate_margin = expert_error = select_margin = 0.0
    unrouted, unselected, widths, by_request = [], [], [], []
    for i in picked:
        prompt, served = prompts[i]["prompt"], reqs[i]["tokens"]
        sets = srv.routed_experts(reqs[i]["record"]["request_id"])
        n = len(prompt) + len(served) - 1     # the last was never fed back
        if sets is None or len(sets) != n:
            unrouted.append(i)
            continue
        tokens, chosen = replayed(state, prompt, served)
        if tokens != list(served):
            replays_differ += 1
        if chosen is None or len(chosen) != n:
            unselected.append(i)
            continue
        width = min(-(-(n + 1) // WIDTH_STEP) * WIDTH_STEP,
                    max(state["max_context"], n + 1))
        widths.append(width)
        ids = np.zeros((width,), np.int32)    # right padding: causal, unseen
        ids[:n + 1] = prompt + served
        given = np.full((width, len(layers), sets.shape[1] // len(layers)),
                        -1, np.int32)
        given[:n] = sets.reshape(n, *given.shape[1:])
        words = -(-width // 32)
        selected = np.zeros((width, chosen.shape[1], words), np.uint32)
        selected[:n] = chosen[:, :, :words]
        # the positions the served tokens are judged at, a fixed count
        at = np.minimum(len(prompt) - 1 + np.arange(answers), width - 1)
        keep = min(ERROR_ROWS, width)
        logits, seen = ref(params, ids, given, selected, at, keep)
        here = {"request": i, "prompt": len(prompt), "served": len(served),
                "exact": 0, "gap": 0.0}
        for k, token in enumerate(served):
            row = logits[k]
            gap = float(row.max() - row[token]) / float(np.abs(row).max())
            judged, exact = judged + 1, exact + (gap == 0.0)
            here["exact"] += gap == 0.0
            if gap > here["gap"]:
                here["gap"], here["gap_at"] = gap, k
        worst = max(worst, here["gap"])
        here["select_margin"] = max(
            float(saw["select_margin"][:n].max()) for saw in seen)
        here["select_flips"] = sum(
            int(saw["select_flips"][:n].sum()) for saw in seen)
        select_margin = max(select_margin, here["select_margin"])
        flips, queries = flips + here["select_flips"], queries + n * len(seen)
        sparse = [saw for saw in seen if "margin" in saw]
        here["margin"] = max(float(saw["margin"][:n].max()) for saw in sparse)
        margin = max(margin, here["margin"])
        by_request.append(here)
        handed += len(layers) * n
        differ += sum(int(saw["differs"][:n].sum()) for saw in sparse)
        valid = jnp.arange(keep) < n
        for name, saw in zip(layers, sparse):
            error, tie = layer_error(params[name], saw["inputs"], valid)
            expert_error = max(expert_error, float(error))
            gate_margin = max(gate_margin, float(tie))
        del seen, logits
    return {"correct": bool(picked and not unrouted and not unselected
                            and not replays_differ
                            and worst <= limits["near_tie_rtol"]
                            and exact >= limits["min_exact_share"] * judged
                            and margin <= limits["routed_margin_max"]
                            and gate_margin <= limits["gate_margin_max"]
                            and expert_error <= limits["expert_error_max"]
                            and select_margin
                            <= limits["select_margin_max"]),
            "requests_checked": picked, "reference_widths": widths,
            "tokens_judged": judged,
            "tokens_exact_argmax": exact, "largest_gap_rel": worst,
            "requests_without_routed_sets": unrouted,
            "requests_without_selected_keys": unselected,
            "routed_sets_differ_share": differ / handed if handed else None,
            "routed_margin": margin, "gate_margin": gate_margin,
            "expert_error": expert_error, "select_margin": select_margin,
            "select_flips_mean": flips / queries if queries else None,
            "by_request": by_request,
            "replayed_tokens_differ": replays_differ, **limits}
