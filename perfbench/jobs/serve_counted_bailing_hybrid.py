"""Job ``serve_counted`` (``jobs/serve_counted.py``: its set-up, window,
counters and teardown, none of it restated here) for ``ling-3.0-flash-ep8``,
with the seeded selection biases balanced behind the set-up (``setup``) and
a ``check`` of its own: ``serve_counted``'s comparisons, every one, over the
tokens the WINDOW's requests were served (their routed sets the program's
own, ``serving.routed_experts_kept``), each request at its own length, held
to limits read on THIS configuration; and, new here, the slot's FINAL
delta-rule state of each checked request against the reference's
recurrence.

What differs from ``serve_counted_exaone_moe.check`` and why:

- THE FINAL STATE. The logits see a wrong state late and weakly (a head's
  state is one of 224 a token reads, behind a norm and a gate), so each
  checked request is served ONCE MORE after the drain, alone, through the
  gateway's own engine and therefore by the window's own compiled chunk and
  decode programs; it has to come out token for token as the window served
  it (greedy, the same programs: ``replayed_tokens_differ`` counts the
  requests that do not, and one is not ``correct``), and the KDA layers'
  rows of its slot are then read out of ``kda_state_pool`` and held to the
  reference's recurrence after the same ``prompt + served - 1`` tokens:
  the root mean square of the difference over that of the reference's
  state, a layer. TWO numbers of it. ``state_error``: every layer against
  the reference's whole forward pass, the largest over the layers (the
  stream a deeper layer reads is bfloat16 arithmetic away from the
  reference's, and the error grows with the depth: 0.004 at the first KDA
  layer, 0.03 at the seventh): it sees a state lost or not carried.
  ``state_error_first``: the FIRST layer's state against the reference's
  recurrence over the inputs the PLAIN call of the program's own first
  mixer hands its recurrence (``family.first_kda_recurrence``: the served
  model's ``q, k, v``, decays and write strengths at its own types).
  Against the whole reference that layer reads 0.0035-0.0039 and a state
  held in bfloat16 0.0050-0.0055: the bfloat16 projections' rounding is as
  large as a bfloat16 state's and hides it (1.3 times apart: no limit
  stands between). With the projections on both sides what is left is the
  state's own arithmetic (the chunk form, the step kernel, the pool's
  type) AND the positions whose NORMED INPUT the serving programs round
  otherwise than the plain call does: about one position in a thousand
  (two compiled programs of one bfloat16 function are not equal to the
  bit: the plain call at width 2,048 differs from itself at 1,024, 3,072
  and 512 in one position of 463, all its channels, on seed 1094922483).
  Such a position puts some 3e-4 into the state and the state FORGETS it
  within a hundred steps or two, where a state held in bfloat16 reads
  0.004 wherever it is read, because it is rounded at every step. So the
  number is the LEAST of the readings at up to three points of the
  stream: its end (the replay above) and two early stops (the same prompt
  served once more for 64 and for 192 tokens, half and one and a half of
  the mix's shortest answer, where the request has more: a second a
  replay). The driver's seed 1094922483 read 2.7e-4 at the end
  of request 40 alone (its three others 8e-8) against the limit of 1e-4
  and was called not correct; the same request reads 1.0e-6 after 64
  tokens and 8.9e-8 after 192 (PERF.md, section 6, "refusal round"). (The
  engine dispatches no step past a request's last by count: the pool
  holds exactly that state.)
- THE GROUPS. A routed set handed to the reference is held to the
  reference's own choice THROUGH the groups: ``routed_margin`` is the larger
  of the distance of the worst group a chosen expert lies in under the 4th
  best group's score, and of the lowest chosen score under the 8th best
  inside the groups the chosen lie in (``reference_bailing_hybrid.routed``).
- THE LIMITS are this configuration's (the readings beside each).

``CONTROLS`` of ``tests/perfbench/test_bailing_hybrid_cell.py`` are the
lower-precision and left-out forms the check has to read as not correct;
``tools/chip_logits_bailing_hybrid.py --through-check`` runs the cell
through this check with each in force on the chip.
"""

import time

import numpy as np

from perfbench import traffic
from perfbench.jobs import serve
from perfbench.jobs import serve_counted
from perfbench.jobs.serve_counted import run, teardown  # noqa: F401

# the reference's width: a checked request's length in whole steps of this
# (a multiple of the reference's blocks of 512 queries)
WIDTH_STEP = 1024
# seconds a replayed request may take (the longest is 16 chunks and 3,072
# steps of one busy row)
REPLAY_TIMEOUT_S = 120.0
# Each limit between two chip readings (my chip runs, PR 57: PERF.md,
# section 6, has the calls and every run's numbers). The controls
# (``tools/chip_logits_bailing_hybrid.py``, alone and ``--through-check``
# with 20 s windows): ``bf16-state``, the delta-rule state through bfloat16
# at every step and chunk; ``not-written``, a chunk's end state not written
# back; ``experts``, the expert matrices in float8; ``gate``, the gate's
# input in bfloat16.
# Share of the judged tokens that are the reference's argmax itself: the
# served program read 0.959-0.974 over nineteen runs on as many seeds
# (3,937-4,559 tokens judged a run); a
# chunk's end state not written back 0.9195
MIN_EXACT_SHARE = 0.94
# a served token's distance under the reference's argmax, of the largest
# |logit|: served 0.0110-0.0179 over the nineteen; a state not written
# back 0.966
NEAR_TIE_RTOL = 0.03
# how far from the reference gate's own choice a served set lies, THROUGH
# the groups (``reference_bailing_hybrid.routed``; selection scores of
# order 0.5): served 0.0073-0.0135 (14-17% of its sets are not the
# reference's own: a near tie between two GROUPS flips up to eight experts
# at once, where an ungrouped gate's flips one); a state not written back
# 0.79
ROUTED_MARGIN_MAX = 0.03
# the same for the program's gate over the reference's OWN float32 inputs,
# where what is left is the gate's arithmetic: the float32 gate read 0.0
# every time, its input in bfloat16 7.1e-4
GATE_MARGIN_MAX = 1e-4
# of the root mean square of a sparse layer's held routed sum, and of its
# shared expert's term, the larger: bfloat16 0.00350-0.00351 in every layer,
# the expert matrices in float8 0.064-0.071
EXPERT_ERROR_MAX = 0.019
# the root mean square of (served state - reference's) over that of the
# reference's state, the worst KDA layer of the worst checked request:
# served 0.0185-0.0292 (the seventh layer: its inputs are six layers of
# bfloat16 arithmetic from the reference's); a chunk's end state not
# written back 0.191 behind 64 decode steps (behind 1,024 the state has
# forgotten its start and reads as served, 0.019: the served tokens are
# what fails then)
STATE_ERROR_MAX = 0.05
# the FIRST layer's stored state against the reference's recurrence over
# the plain call's first-layer inputs (``family.first_kda_recurrence``), the
# LEAST of its readings at the request's end and after 64 and 192 tokens. At ONE point the float32 pool reads 7.9e-8 to 1.05e-7 at a
# request's end and after 192 tokens, 8.2e-7 to 1.0e-6 after 64 (what the
# chunk form leaves, forgotten by 192), and 2.7e-4 where the serving program
# rounded one position's normed input otherwise than the plain call within
# the last steps (one end of twenty-four: seed 1094922483, request 40); the
# state through bfloat16 0.00377-0.00413 at EVERY point (the four requests
# of seed 1987654321; 0.00407 and 0.00421 at the ends of seeds 5700001101,
# 5700001304); a chunk's end state not written back 0.00194 at the end.
# The least of three: float32 7.9e-8 to 8.9e-8, bfloat16 0.00377-0.00398,
# the limit a thousand times the first and forty times under the second
# (my chip runs, PR 57, the refusal round's calls A and C). Against the
# whole reference that layer read 0.00349-0.00391 / 0.00504-0.00545 over
# nineteen runs: 1.3 times apart, the projections' bfloat16 rounding as
# large as the state's, and no limit stood between; this one is the only
# number of the check that sees a bfloat16 state, whose logits are inside
# every limit: section 7
STATE_ERROR_FIRST_MAX = 1e-4


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


def replayed_state(state: dict, prompt, served):
    """``(tokens, kda states [layers, heads, key, value] float32)``: the
    request served once more, alone, by the gateway's engine (its own
    compiled programs), and what its slot's rows of the state pool hold
    when it has finished. The engine is idle: its requests have drained."""
    srv, gateway = state["srv"], state["gateway"]
    req = gateway.submit(prompt, max_new_tokens=len(served))
    wake = getattr(gateway, "_wake", None)
    if wake is not None:
        wake.set()
    deadline = time.monotonic() + REPLAY_TIMEOUT_S
    while req.finish_reason is None and time.monotonic() < deadline:
        time.sleep(0.01)
    if req.finish_reason is None:
        return None, None
    time.sleep(0.05)    # the step that finished it has left the loop
    pool = srv.cache["kda_state_pool"]
    return list(req.tokens), np.asarray(pool[:, 1 + req.slot],
                                        dtype=np.float32)


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
    at_layer = [int(name.split("_")[1]) for name in layers]
    limits = {"near_tie_rtol": NEAR_TIE_RTOL,
              "min_exact_share": MIN_EXACT_SHARE,
              "routed_margin_max": ROUTED_MARGIN_MAX,
              "gate_margin_max": GATE_MARGIN_MAX,
              "expert_error_max": EXPERT_ERROR_MAX,
              "state_error_max": STATE_ERROR_MAX,
              "state_error_first_max": STATE_ERROR_FIRST_MAX,
              **cell["serve"].get("limits", {})}
    ref = jax.jit(family.reference_logits_given(config_file))
    layer_error = jax.jit(family.expert_layer_error(
        config_file, srv.engine.module.config), static_argnums=3)
    first_state = jax.jit(family.first_kda_recurrence(
        config_file, srv.engine.module.config))
    # where a checked request's first-layer state is read besides its end:
    # after half and after one and a half of the mix's shortest answer (64
    # and 192 tokens here; a stop at or past the request's own length is
    # left out). The state forgets a position's rounding within a hundred
    # steps or two, so two stops 128 apart do not see one
    shortest = int(state["mix"]["new_tokens"]["min"])
    early_stops = (shortest // 2, shortest * 3 // 2)
    judged = exact = handed = differ = replays_differ = 0
    worst = margin = gate_margin = expert_error = 0.0
    state_error = state_first = 0.0
    unrouted, widths, state_errors, by_request = [], [], [], []
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
        logits, seen = ref(params, jnp.asarray(ids), jnp.asarray(given),
                           jnp.asarray(n, jnp.int32))
        logits = np.asarray(logits)[0]
        here = {"request": i, "prompt": len(prompt), "served": len(served),
                "exact": 0, "gap": 0.0}
        for k, token in enumerate(served):
            row = logits[len(prompt) - 1 + k]
            gap = float(row.max() - row[token]) / float(np.abs(row).max())
            judged, exact = judged + 1, exact + (gap == 0.0)
            here["exact"] += gap == 0.0
            if gap > here["gap"]:
                here["gap"], here["gap_at"] = gap, k
        worst = max(worst, here["gap"])
        here["margin"] = float(np.asarray(seen["margin"])[:, 0, :n].max())
        margin = max(margin, here["margin"])
        by_request.append(here)
        handed += len(layers) * n
        differ += int(np.asarray(seen["differs"])[:, 0, :n].sum())
        valid = jnp.arange(width) < n
        for place, name in enumerate(layers):
            error, tie = layer_error(params[name], seen["inputs"][place, 0],
                                     valid, at_layer[place])
            expert_error = max(expert_error, float(error))
            gate_margin = max(gate_margin, float(tie))
        want = np.asarray(seen["states"])[:, 0]   # [KDA layers, H, K, V]
        del seen, logits
        tokens, got = replayed_state(state, prompt, served)
        stops = {len(served): (tokens, got)}
        for stop in early_stops:
            if stop < len(served):
                stops[stop] = replayed_state(state, prompt, served[:stop])
        parts = [stop for stop, (early, _) in stops.items()
                 if early != list(served[:stop])]
        if parts:
            replays_differ += 1
            here["replays_part_at_stops"] = parts
        if any(held is None for _, held in stops.values()):
            state_errors.append(None)
            state_error = state_first = float("inf")
            continue
        by_layer = np.sqrt(((got - want) ** 2).mean((1, 2, 3))
                           / np.maximum((want ** 2).mean((1, 2, 3)), 1e-30))
        state_errors.append([float(e) for e in by_layer])
        state_error = max(state_error, float(by_layer.max()))
        by_stop = {}
        for stop, (_, held) in stops.items():   # the last was never fed back
            want_first = np.asarray(first_state(
                params, jnp.asarray(ids),
                jnp.asarray(len(prompt) + stop - 1, jnp.int32)))
            by_stop[stop] = float(np.sqrt(
                ((held[0] - want_first) ** 2).mean()
                / max((want_first ** 2).mean(), 1e-30)))
        here["state_first_by_stop"] = by_stop
        here["state_first"] = min(by_stop.values())
        state_first = max(state_first, here["state_first"])
    return {"correct": bool(picked and not unrouted and not replays_differ
                            and worst <= limits["near_tie_rtol"]
                            and exact >= limits["min_exact_share"] * judged
                            and margin <= limits["routed_margin_max"]
                            and gate_margin <= limits["gate_margin_max"]
                            and expert_error <= limits["expert_error_max"]
                            and state_error <= limits["state_error_max"]
                            and state_first
                            <= limits["state_error_first_max"]),
            "requests_checked": picked, "reference_widths": widths,
            "tokens_judged": judged,
            "tokens_exact_argmax": exact, "largest_gap_rel": worst,
            "requests_without_routed_sets": unrouted,
            "routed_sets_differ_share": differ / handed if handed else None,
            "routed_margin": margin, "gate_margin": gate_margin,
            "expert_error": expert_error, "state_error": state_error,
            "state_error_first": state_first,
            "state_error_by_layer": state_errors, "by_request": by_request,
            "replayed_tokens_differ": replays_differ, **limits}
