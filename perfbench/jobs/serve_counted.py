"""Job ``serve`` (``jobs/serve.py``: the same set-up, window, client and
teardown, none of it restated here) for a SPARSE model whose program
counts what a call did: ``facts`` gains

- ``engine_stats``: ``ServingEngine.stats()`` at the end of the run (from
  GO to the end of the drain): the model's own counters by kind of
  program, live KV bytes by kind of layer, the attention path each call
  site took;
- ``engine_span``: in a traced run, what the counters gained while the
  profiler ran, for the roofline shares that count the traced events'
  work from them.

A model that counts nothing gives empty dicts, and a program whose
``stats()`` lacks the keys gives none: the readers then find nothing to
read.

``correct`` is ``serve``'s rule over the same four seeded requests, with
``serve``'s limits, and holds the experts, which that rule alone cannot:

- A bfloat16 program and a float32 reference choose another set of
  experts wherever the k-th and the next score lie closer than rounding
  (one (token, layer) pair in ten at 256 experts), and a flipped set is a
  WHOLE expert's term: it moves a logit by more than any arithmetic this
  check is to catch. So the cell's serving block asks the engine for the
  routed sets of what it served (``serving.routed_experts_kept``), the
  reference takes them in place of its own, and what is left between the
  two is arithmetic. A set handed in has to BE a near tie of the
  reference's gate: its lowest selection score at most
  ``ROUTED_MARGIN_MAX`` under the reference's own k-th.
- A chip that holds one expert in sixteen adds half an expert's term a
  token and layer, so the held experts in float8 move the served logits
  by less than bfloat16's own distance from float32, and the served-token
  rule passes them. So each sparse layer of the served model, as the
  engine holds it, also runs over the reference's own float32 inputs of
  the checked requests, against the reference's experts on the program's
  routed sets: at most ``EXPERT_ERROR_MAX`` of the output's root mean
  square; and the sets its gate chooses there at most ``GATE_MARGIN_MAX``
  from the reference's own (over the same inputs a float32 gate has
  nothing to flip on).

Each limit lies between two chip readings (PERF.md, PR 39): the served
program's over its seeds, and the same with the lower precision (every
matrix in float8; the gate's input in bfloat16; the expert matrices in
float8). ``tools/chip_logits_mimo_v2.py --through-check`` runs the cell
through this check with either in force."""

import contextlib

import numpy as np

from perfbench import traffic
from perfbench.jobs import serve
from perfbench.jobs.serve import setup, teardown  # noqa: F401

# Each limit between two chip readings, in ratio about midway (my chip
# runs, PR 39: PERF.md, section 6, has the calls).
# Selection scores (sigmoid + bias, of order 1). What the served program
# flips is as near a tie as its activations' rounding moves the scores: the
# bfloat16 program read 0.0033-0.0085 over 33 seeds (10% of the sets
# are not the reference's own), every matrix in float8 0.114
ROUTED_MARGIN_MAX = 0.03
# the same for the program's gate over the reference's OWN float32 inputs,
# where what is left is the gate's arithmetic: the float32 gate read 0.0
# every time (it chooses the reference's sets), its input in bfloat16
# 5.0e-4 to 7.3e-4 (four seeds)
GATE_MARGIN_MAX = 1e-4
# of the root mean square of a sparse layer's output: bfloat16 experts read
# 0.0029 in every layer of every seed, the expert matrices in float8
# 0.056-0.063
EXPERT_ERROR_MAX = 0.0125

_COUNTED = ("model_counters", "kv_live_bytes")


def _counted(stats: dict) -> dict:
    return {k: stats[k] for k in _COUNTED if k in stats}


def _gained(after, before):
    if isinstance(after, dict):
        return {k: _gained(v, before.get(k, type(v)())) for k, v in
                after.items()}
    return after - (before or 0)


class _CountingTracer:
    """The harness's tracer, with the engine's counters read as the
    profiler starts and as it stops."""

    def __init__(self, tracer, srv):
        self._tracer, self._srv, self.span = tracer, srv, None

    def __getattr__(self, name):
        return getattr(self._tracer, name)

    @contextlib.contextmanager
    def window(self):
        with self._tracer.window():
            before = _counted(self._srv.stats())
            yield
            self.span = _gained(_counted(self._srv.stats()), before)


def run(state: dict, seconds: float, tracer) -> dict:
    counting = _CountingTracer(tracer, state["srv"])
    result = serve.run(state, seconds, counting)
    stats = state["srv"].stats()
    result["facts"]["engine_stats"] = {
        **_counted(stats), "attention_paths": stats.get("attention_paths")}
    result["facts"]["engine_span"] = counting.span
    result["notes"]["engine_stats"] = result["facts"]["engine_stats"]
    return result


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
    width = state["max_context"]
    judged = exact = handed = differ = 0
    worst = margin = gate_margin = expert_error = 0.0
    unrouted = []
    for i in picked:
        prompt, served = prompts[i]["prompt"], reqs[i]["tokens"]
        sets = srv.routed_experts(reqs[i]["record"]["request_id"])
        n = len(prompt) + len(served) - 1     # the last was never fed back
        if sets is None or len(sets) != n:
            unrouted.append(i)
            continue
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
        for at, name in enumerate(layers):
            error, tie = layer_error(params[name], seen["inputs"][at, 0],
                                     valid)
            expert_error = max(expert_error, float(error))
            gate_margin = max(gate_margin, float(tie))
        del seen
    return {"correct": bool(picked and not unrouted
                            and worst <= serve.NEAR_TIE_RTOL
                            and exact >= serve.MIN_EXACT_SHARE * judged
                            and margin <= ROUTED_MARGIN_MAX
                            and gate_margin <= GATE_MARGIN_MAX
                            and expert_error <= EXPERT_ERROR_MAX),
            "requests_checked": picked, "tokens_judged": judged,
            "tokens_exact_argmax": exact, "largest_gap_rel": worst,
            "near_tie_rtol": serve.NEAR_TIE_RTOL,
            "min_exact_share": serve.MIN_EXACT_SHARE,
            "requests_without_routed_sets": unrouted,
            "routed_sets_differ_share": differ / handed if handed else None,
            "routed_margin": margin, "routed_margin_max": ROUTED_MARGIN_MAX,
            "gate_margin": gate_margin, "gate_margin_max": GATE_MARGIN_MAX,
            "expert_error": expert_error,
            "expert_error_max": EXPERT_ERROR_MAX}
