"""Job ``serve_counted`` (``jobs/serve_counted.py``: its set-up, window,
counters and teardown, none of it restated here) for
``phi-4-mini-flash-reasoning``, with a ``check`` of its own. ``correct`` is
decided on what the WINDOW's compiled chunk and decode programs served and
left behind, at the rows the window kept busy:

- THE TOKENS. ``serve``'s rule: every judged token at most a near tie under
  the reference's argmax, and nearly all of them the argmax itself, on the
  reference's logits over prompt + served tokens, ONE full forward pass a
  request (no cache, no ring, no state handed on) at the request's own
  length. A served token went through every mechanism: the chunk program's
  one row, the rings, the cache eight layers share, the state.
- THE STATE. A decode slot's rows of ``ssm_state_pool`` still hold what its
  LAST tenant left when the engine has drained. For ``STATE_REQUESTS`` such
  tenants the rows of the first Mamba layer and of the memory layer (places
  0 and ``layers - 1`` of the pool: layers 0 and 16) are held against the
  reference's recurrence, one position at a time in float32, after the
  tokens the programs were fed: all but the last served one, or all of them
  where the decode loop, which runs a step ahead, had dispatched the row
  once more before it learnt that the request was over (the nearer of the
  two counts). ``state_rel``: the largest difference over the reference
  state's largest value.

What differs from ``serve_counted.check`` and why:

- NO ROUTED SETS. No layer is sparse.
- THE REFERENCE'S WIDTH AND ITS HEAD. A request is checked at its own
  length in whole ``WIDTH_STEP``s, and the reference's head is taken at the
  positions the served tokens are judged at, a block of positions and a
  slice of the vocabulary at a time: 3,072 answers x 200,064 logits are
  2.5 GB.
- WHICH REQUESTS. ``serve.CHECKED_REQUESTS`` are the seeded sample of
  ``serve``, held to contain a prompt over ``prefill_chunk_tokens`` (it
  crosses program calls). ``SHORT_REQUESTS`` more are the requests with the
  SHORTEST prompts among those that finished of the window's second half of
  arrivals: by then a request takes a slot that has had tenants, and a last
  tenant's state or ring shows behind a short prompt, all along the answer.
  ``STATE_REQUESTS`` more are the last tenants of their slots with the
  fewest tokens.

Each limit lies between this configuration's bfloat16 readings and its
controls' (PERF.md, section 6, PR 63, has every reading and the call it came
from; the controls are ``tests/perfbench/test_phi4flash_cell.py``'s
``CONTROLS``, run through this check at a tiny size there and on the chip at
the published widths by the builder).
"""

import numpy as np

from perfbench import traffic
from perfbench.jobs import serve
from perfbench.jobs.serve_counted import (run, setup,  # noqa: F401
                                          teardown)

# the reference's width: a checked request's length in whole steps of this
# (a multiple of the reference's blocks of queries)
WIDTH_STEP = 2048
# checked beside ``serve.CHECKED_REQUESTS``: the shortest prompts of the
# window's second half; and the last tenants of their slots
SHORT_REQUESTS = 4
STATE_REQUESTS = 2
# Each limit between this configuration's two readings (my chip runs, PR 63;
# PERF.md section 6 has the calls). A served token's distance under the
# reference's argmax, of the largest |logit| of its position: thirty-two
# layers of bfloat16 leave the logits 4% of the largest apart at the 95th
# percentile (200,064 candidates), and the bfloat16 program read
# 0.029-0.051 in fourteen runs (250,000 tokens); a bfloat16 state pool
# 0.106, cross layers reading another slot's blocks 0.142, lambda forced
# to 0 1.37
NEAR_TIE_RTOL = 0.075
# share of the judged tokens that are the reference's argmax itself: the
# bfloat16 program read 0.909-0.922; another slot's blocks 0.700, lambda at
# 0 0.003 (a bfloat16 state pool 0.895: the state's limit is what holds it)
MIN_EXACT_SHARE = 0.85
# the first Mamba layer's state in a slot against the reference's
# recurrence, of the reference state's largest value: the bfloat16 program
# with its float32 pool read 0.0017-0.0066 (thirty-four rows), the same
# with a bfloat16 pool 0.052 and 0.085
STATE_REL_MAX = 0.015
# the memory layer's state: its input has come through sixteen bfloat16
# layers (0.014-0.044 in the program, 0.046-0.061 with a bfloat16 pool:
# too near to part them); held to what a state lost, not carried or fed by
# another attention reads (lambda at 0: 1.13)
MEMORY_STATE_REL_MAX = 0.1


def picked_requests(reqs, prompts, seed, chunk: int):
    """``(short, sample)``: the shortest prompts among the requests that
    finished of the second half of arrivals, and a seeded sample of the
    other finished requests (one of them longer than ``chunk`` where any
    is)."""
    done = [i for i, r in enumerate(reqs) if r["ok"] and r["tokens"]]
    rng = np.random.default_rng([int(seed), 13])
    late = [i for i in done if i >= len(reqs) // 2]
    short = sorted(late, key=lambda i: (len(prompts[i]["prompt"]), i))[
        :min(SHORT_REQUESTS, len(done) // 2)]
    rest = [i for i in done if i not in short]
    sample = rng.choice(rest, min(serve.CHECKED_REQUESTS, len(rest)),
                        replace=False).tolist() if rest else []
    chunked = [i for i in rest if len(prompts[i]["prompt"]) > chunk]
    if chunked and not any(i in chunked for i in sample):
        sample[-1] = int(rng.choice(chunked))
    return sorted(short), sorted(sample)


def last_tenants(srv, reqs) -> dict:
    """``{index into reqs: its decode slot}`` for the finished requests
    that were the LAST to hold their slot (the slot's state rows are still
    theirs), the ``STATE_REQUESTS`` with the fewest tokens."""
    by_id = {r["record"].get("request_id"): i for i, r in enumerate(reqs)
             if r["ok"] and r["tokens"] and r.get("record")}
    last = {}
    for req in srv.finished:
        if req.slot >= 0 and (req.slot not in last
                              or req.finish_ts >= last[req.slot].finish_ts):
            last[req.slot] = req
    mine = sorted((len(req.prompt) + len(req.tokens), by_id[req.request_id],
                   slot) for slot, req in last.items()
                  if req.request_id in by_id)
    return {i: slot for _, i, slot in mine[:STATE_REQUESTS]}


def served_gaps(logits, served):
    """How far each served token lies under the reference's argmax of its
    position, of that position's largest |logit|; ``logits [>= tokens,
    vocab]``, the reference's at the positions the tokens were sampled
    at."""
    rows = logits[:len(served)]
    at = rows[np.arange(len(served)), served]
    return (rows.max(-1) - at) / np.abs(rows).max(-1)


def written(rows):
    """A slot's pool rows ``[.., C / L, N, L]`` as the plain recurrence
    writes a state, ``[.., C, N]``."""
    rows = np.asarray(rows, np.float32)
    *lead, groups, n, lanes = rows.shape
    return np.swapaxes(rows, -1, -2).reshape(*lead, groups * lanes, n)


def check(state: dict, result: dict) -> dict:
    import jax.numpy as jnp

    srv, cell, seed = state["srv"], state["cell"], state["seed"]
    family, config_file = cell["family"], cell["config_file"]
    serving = cell["serve"]["serving"]
    reqs = state["requests"]
    prompts = traffic.requests(state["mix"], seed, result["notes"]["window_s"],
                               state["vocab"])
    short, sample = picked_requests(
        reqs, prompts, seed, int(serving.get("prefill_chunk_tokens") or 0))
    tenants = last_tenants(srv, reqs)
    picked = sorted(set(short + sample) | set(tenants))
    params = srv.engine.params
    pool = srv.cache["ssm_state_pool"]
    places = (0, pool.shape[0] - 1)      # the first Mamba layer, the memory
    ref = family.reference_logits(config_file, kept_states=places)
    # (a cell served in another precision states its own: the tests' tiny
    # cell is float32 against a float32 reference, and holds it to that)
    limits = {"near_tie_rtol": NEAR_TIE_RTOL,
              "min_exact_share": MIN_EXACT_SHARE,
              "state_rel_max": STATE_REL_MAX,
              "memory_state_rel_max": MEMORY_STATE_REL_MAX,
              **cell["serve"].get("limits", {})}
    answers = int(state["mix"]["new_tokens"]["max"])
    judged = exact = 0
    worst = 0.0
    widths, lengths, inexact, state_rel, memory_rel = [], [], [], [], []
    for i in picked:
        prompt, served = prompts[i]["prompt"], reqs[i]["tokens"]
        n = len(prompt) + len(served)
        width = min(-(-n // WIDTH_STEP) * WIDTH_STEP,
                    max(state["max_context"], n))
        widths.append(width)
        lengths.append(len(prompt))
        ids = np.zeros((1, width), np.int32)  # right padding: causal, unseen
        ids[0, :n] = prompt + served
        # the positions the served tokens are judged at
        at = np.minimum(len(prompt) - 1 + np.arange(min(answers,
                                                        len(served))),
                        width - 1)
        logits, states = ref(params, jnp.asarray(ids),
                             jnp.asarray(at, jnp.int32),
                             jnp.asarray([n - 1, n], jnp.int32))
        gaps = served_gaps(logits[0], np.asarray(served))
        judged, exact = judged + len(gaps), exact + int((gaps == 0.0).sum())
        worst = max(worst, float(gaps.max()))
        inexact.append(int((gaps > 0.0).sum()))
        del logits
        if i in tenants:
            row = 1 + tenants[i]
            for place in places:
                held = written(pool[place, row])
                want = np.asarray(states[place])[:, 0]       # [2, C, N]
                (state_rel if place == 0 else memory_rel).append(float(min(
                    np.abs(held - w).max() / np.abs(w).max() for w in want)))
    return {"correct": bool(sample and state_rel
                            and worst <= limits["near_tie_rtol"]
                            and exact >= limits["min_exact_share"] * judged
                            and max(state_rel) <= limits["state_rel_max"]
                            and max(memory_rel)
                            <= limits["memory_state_rel_max"]),
            "requests_checked": picked, "short_requests": short,
            "state_requests": sorted(tenants),
            "prompt_lengths": lengths, "reference_widths": widths,
            "tokens_judged": judged, "tokens_exact_argmax": exact,
            "tokens_inexact": inexact, "largest_gap_rel": worst,
            "state_rel": state_rel, "memory_state_rel": memory_rel,
            **limits}
