"""Job ``serve_counted`` (``jobs/serve_counted.py``: its set-up, window,
counters and teardown, none of it restated here) for ``granite-4.0-h-micro``,
with a ``check`` of its own. ``correct`` is ``serve``'s rule over the tokens
the WINDOW served, by the compiled chunk and decode programs at the rows the
window kept busy: every judged token at most a near tie under the
reference's argmax, and nearly all of them the argmax itself.

What differs from ``serve_counted.check`` and why:

- NO ROUTED SETS. No layer is sparse: nothing is handed to the reference
  and no expert layer is compared.
- THE REFERENCE'S WIDTH AND ITS HEAD (as ``serve_counted_deepseek_v2``). A
  request is checked at its own length in whole ``WIDTH_STEP``s (at most
  four compiled widths), and the reference's head is taken at the positions
  the served tokens are judged at: the reference's recurrence runs one
  position at a time, 36 layers x 8,192 dependent steps at the longest.
- THE EMBEDDING'S SCALE MAKES THE RULE SEE. The head is tied to an
  embedding that enters ``embedding_multiplier`` = 12 times over: drawn
  N(0, 0.02) as every other matrix, a position's own token stands 3.4
  standard deviations over the other 100,351 logits, greedy decoding locks
  onto repeating it, and the served tokens are the reference's argmax
  whatever a Mamba layer's state holds (every control below served 100%
  exact argmax at a gap of 0.0). The configuration file draws the embedding
  at ``weights.embedding_std`` instead, so that the stack decides the
  argmax (the file has the readings).
- WHICH REQUESTS. ``serve.CHECKED_REQUESTS`` are the seeded sample of
  ``serve``, held to contain a prompt over ``prefill_chunk_tokens`` (it
  crosses program calls). ``SHORT_REQUESTS`` more are the requests with the
  SHORTEST prompts among those that finished of the window's second half
  of arrivals: a request takes the lowest free slot, so by then it takes
  one that has had tenants; a last tenant's state decays along the new
  prompt (a head forgets over 10 to 1,000 positions), so a state not reset
  at length 0 shows behind 64-300 positions, all along the answer, and
  behind the median 1,024 it hardly does.
- THE REPLAY, AN AID THAT DECIDES NOTHING. ``REPLAYED`` of the checked
  requests are run once more after the drain through the engine's paged
  module, pools, block manager and slot table (one slot live: NOT the
  window's programs), the LOGITS handed back and held against the
  reference's: ``replayed_logits_rel`` says how far the bfloat16 arithmetic
  through the pools lies from float32, which served tokens only bound.

Each limit lies between this configuration's bfloat16 readings and its
controls' (PERF.md, section 6, PR 49, has every reading and the call it
came from; the controls are ``tests/perfbench/test_granite_hybrid_cell.py``'s
``CONTROLS``, run through this check at a tiny size there and on the chip
at the published widths by the builder).
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
# window's second half
SHORT_REQUESTS = 6
# of the checked requests, replayed for their logits; decode steps replayed
REPLAYED = 3
REPLAY_STEPS = 64
# ``serve``'s limits, each between this configuration's two readings (my
# chip runs, PR 49, calls 12 to 14; PERF.md section 6). A served token's
# distance under the reference's argmax, of the largest |logit| of its
# position: the bfloat16 program read 0.0063-0.0111 in nine runs (33,120
# tokens); a state not reset at length 0 0.087-0.172 (0.031 in a window of
# 20 s, whose short prompts mostly took slots no one had used), a float8
# state pool 0.108-0.153, a state not carried 0.96-1.0
NEAR_TIE_RTOL = serve.NEAR_TIE_RTOL
# share of the judged tokens that are the reference's argmax itself: the
# bfloat16 program read 0.968-0.978; a state not reset 0.887-0.925 (0.953
# in the 20 s window: there the gap alone catches it), a float8 state pool
# 0.732-0.774, a state not carried 0.007-0.016
MIN_EXACT_SHARE = serve.MIN_EXACT_SHARE


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


def served_gaps(logits, served):
    """How far each served token lies under the reference's argmax of its
    position, of that position's largest |logit|; ``logits [>= tokens,
    vocab]``, the reference's at the positions the tokens were sampled
    at."""
    rows = logits[:len(served)]
    at = rows[np.arange(len(served)), served]
    return (rows.max(-1) - at) / np.abs(rows).max(-1)


def replay_program(srv):
    """The engine's paged module over its own parameters and pools, as its
    serving programs call it, the logits at each row's last real position
    handed back: ``f(params, cache, ids, tables, lengths, num_valid) ->
    (logits [rows, vocab], cache)``. One function, compiled for a chunk's
    shape and for a decode step's."""
    import jax
    import jax.numpy as jnp

    dm, dequant = srv._dmodule, srv.engine._dequantize
    logits_of = srv.engine._logits_of

    def call(qparams, cache, ids, tables, lengths, num_valid):
        out, vars_ = dm.apply(
            {"params": dequant(qparams), "cache": cache}, ids,
            mutable=["cache"],
            paging=srv._paging(ids, tables, lengths, num_valid))
        last = jnp.take_along_axis(
            logits_of(out), (num_valid - 1)[:, None, None], axis=1)[:, 0]
        return last, vars_["cache"]

    return jax.jit(call, donate_argnums=srv._donate(1))


def replayed_logits(srv, fn, prompt, served, slot: int = 0):
    """``[1 + steps, vocab]`` float32: the logits ``fn``
    (:func:`replay_program`) gives for ``prompt`` (chunks of the engine's
    ``chunk_tokens``, or the whole prompt where it has none, from length 0
    in ``slot``) at the prompt's last position, and for the first
    ``REPLAY_STEPS`` of ``served`` fed back a decode step each, the other
    slots idle. The engine is idle: its requests have drained."""
    import jax.numpy as jnp

    steps = min(REPLAY_STEPS, len(served) - 1)
    rid = f"perfbench-replay-{len(prompt)}-{steps}"
    table = srv._slot_table(slot, srv.block_mgr.allocate(
        rid, len(prompt) + steps))
    i32 = lambda x: jnp.asarray(x, jnp.int32)
    width = srv.chunk_tokens or len(prompt)
    rows = []
    try:
        for at in range(0, len(prompt), width):
            real = min(width, len(prompt) - at)
            ids = np.zeros((1, width), np.int32)
            ids[0, :real] = prompt[at:at + real]
            last, srv.cache = fn(srv.engine.params, srv.cache, i32(ids),
                                 i32(table[None]), i32([at]), i32([real]))
        rows.append(np.asarray(last[0]))
        slots = srv.config.decode_slots
        tables = np.zeros((slots, len(table)), np.int32)
        tables[slot] = table
        for k in range(steps):
            lengths = np.zeros(slots, np.int32)
            token = np.zeros((slots, 1), np.int32)
            lengths[slot], token[slot] = len(prompt) + k, served[k]
            last, srv.cache = fn(srv.engine.params, srv.cache, i32(token),
                                 i32(tables), i32(lengths),
                                 jnp.ones(slots, jnp.int32))
            rows.append(np.asarray(last[slot]))
    finally:
        srv.block_mgr.release(rid)
    return np.stack(rows)


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
    picked = sorted(short + sample)
    # the two shortest prompts and the longest of the checked
    by_length = sorted(picked, key=lambda i: len(prompts[i]["prompt"]))
    replayed = set(by_length[:REPLAYED - 1] + by_length[-1:])
    params = srv.engine.params
    ref = family.reference_logits(config_file)
    replay = replay_program(srv)
    # (a cell served in another precision states its own: the tests' tiny
    # cell is float32 against a float32 reference, and holds it to that)
    limits = {"near_tie_rtol": NEAR_TIE_RTOL,
              "min_exact_share": MIN_EXACT_SHARE,
              **cell["serve"].get("limits", {})}
    answers = int(state["mix"]["new_tokens"]["max"])
    judged = exact = 0
    worst = 0.0
    widths, lengths, apart, inexact = [], [], [], []
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
        at = np.minimum(len(prompt) - 1 + np.arange(answers), width - 1)
        logits = np.asarray(ref(params, jnp.asarray(ids),
                                jnp.asarray(at, jnp.int32)))[0]
        gaps = served_gaps(logits, np.asarray(served))
        judged, exact = judged + len(gaps), exact + int((gaps == 0.0).sum())
        worst = max(worst, float(gaps.max()))
        inexact.append(int((gaps > 0.0).sum()))
        if i in replayed:
            got = replayed_logits(srv, replay, prompt, served)
            want = logits[:len(got)]
            apart.append(float((np.abs(got - want).max(-1)
                                / np.abs(want).max(-1)).max()))
        del logits
    return {"correct": bool(sample and worst <= limits["near_tie_rtol"]
                            and exact >= limits["min_exact_share"] * judged),
            "requests_checked": picked, "short_requests": short,
            "prompt_lengths": lengths, "reference_widths": widths,
            "tokens_judged": judged, "tokens_exact_argmax": exact,
            "tokens_inexact": inexact, "largest_gap_rel": worst,
            **limits, "replayed_logits_rel": apart}
