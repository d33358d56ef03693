"""Serving job: ``init_inference`` -> ``ServingEngine`` -> ``ServingGateway``
on a loopback port, driven over HTTP/SSE by the open-loop load generator
(``perfbench/loadgen.py``, a process of its own).

The cell's ``serve`` block: ``serving`` (the program's ``serving`` config
block: ``decode_slots``, ``block_size``, ...), ``gateway`` (its gateway
block), and for the traced run ``trace_start_s`` / ``trace_seconds``: the
profiler runs over a steady slice of a shorter window. The model comes from
the configuration's family (``perfbench/families/``). The longest context
the cell serves, which sizes the pool, is the longest its traffic sends:
the mix's ``max_total``, at most the family's largest, which it is where
the mix gives none.

Every time is taken at the client. ``ttft``: from the moment the request
was DUE to its first token event. ``tpot``: (last token arrival - first) /
(tokens - 1) per request. In both, a request that failed, was shed or did
not finish by the deadline counts as the window's length, so that shedding
work never improves a tail. ``served_tok_s``: token events that arrived
inside the window / the window. TTFT's median and 95th percentile are
per-layer metrics here, not end-to-end ones: PERF.md, section 2, says why.

``correct``: the reference's logits over prompt + served tokens of a seeded
sample of finished requests make every served token the argmax or a near
tie with it (``NEAR_TIE_RTOL``), and nearly all of them the argmax itself
(``MIN_EXACT_SHARE``).
"""

import json
import os
import select
import subprocess
import sys
import time

import numpy as np

from perfbench import traffic
from perfbench.byname import BenchError

# The served logits are bf16 arithmetic, the reference's float32, so where
# the reference holds two candidates closer than bf16 can tell apart,
# either may be served. Seen on the chip over 16 runs (PERF.md, PR 25):
# 6070 of 6122 judged tokens the reference's argmax, per run 97.9% to 100%;
# the largest gap of the others, per run, 0 to 1.17% of the largest |logit|
# at their position, in three runs over 1%. So a tolerance of 0.01
# (chip_smoke.py's, for 8 requests of one seed) fails one seed in five
# here. It is three bf16 steps (2^-7 each) of the largest |logit|, twice
# the largest gap seen, because the check runs on a new seed some hundred
# times a year and the largest of 400 gaps has a long tail. With random
# weights the logits are flat, and that much of the largest spans several
# candidates, so the share of exact argmaxes is held too: 94%, three times
# the largest miss rate seen (2.05%). Arithmetic that moves every logit a
# little (an int8 cache, int8 weights) can stay inside the first rule, and
# flips far more tokens.
NEAR_TIE_RTOL = 3 * 2.0 ** -7
MIN_EXACT_SHARE = 0.94
CHECKED_REQUESTS = 4
TICK_S = 0.1
_PACKAGE_PARENT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _pct(values, q):
    return float(np.percentile(np.asarray(values, np.float64), q))


def setup(cell: dict, seed: int, device: dict) -> dict:
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.parallel.topology import reset_topology
    from deepspeed_tpu.serving import ServingEngine
    from deepspeed_tpu.serving.gateway import ServingGateway

    job, family, config_file = (cell["serve"], cell["family"],
                                cell["config_file"])
    dtype = getattr(jnp, job.get("dtype", "bfloat16"))
    vocab = family.vocab_size(config_file)
    mix = cell["traffic_file"]
    largest = family.max_context(config_file)
    context = int(mix.get("max_total") or largest)
    if context > largest:
        raise BenchError(f"traffic mix {cell['traffic']!r} sends contexts of "
                         f"{context}: the family serves at most {largest}")
    reset_topology()
    module = family.serving_module(config_file, dtype)

    # the weights: on the device, in one jitted call from the seed, in the
    # type they are served in
    @jax.jit
    def make(key):
        tree = module.init(key, jnp.zeros((1, 8), jnp.int32))
        return jax.tree_util.tree_map(lambda x: x.astype(dtype), tree)

    # (no name is bound to the tree here: the engine places its own copy,
    # and a second 3 GB of weights would not leave room for the KV pool)
    srv = ServingEngine(deepspeed_tpu.init_inference(
        module, params=make(jax.random.PRNGKey(int(seed) % (2 ** 31))),
        dtype=dtype, seed=int(seed) % (2 ** 31),
        tensor_parallel={"tp_size": int(cell["chips"])},
        max_out_tokens=context, serving=job["serving"]))

    # warm exactly the prefill buckets this mix's prompt lengths can hit,
    # and decode: one short request per bucket, straight into the engine
    lo, hi = mix["prompt_len"]["min"], mix["prompt_len"]["max"]
    rng = np.random.default_rng([int(seed), 11])
    hit = [b for i, b in enumerate(srv.buckets)
           if b >= lo and (i == 0 or srv.buckets[i - 1] < hi)]
    for bucket in hit:
        srv.submit(rng.integers(0, vocab, min(bucket, hi)),
                   max_new_tokens=2)
        srv.drain()
    srv.reset_stats()

    gateway = ServingGateway(srv, {"pump": True, "poll_secs": 0.002,
                                   **job.get("gateway", {})}).start()
    state = {"cell": cell, "seed": seed, "srv": srv, "gateway": gateway,
             "mix": mix, "vocab": vocab, "max_context": context,
             "child": None}
    return state


def _start_child(state: dict, seconds: float):
    child = subprocess.Popen(
        [sys.executable, "-m", "perfbench.loadgen"], cwd=_PACKAGE_PARENT,
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    state["child"] = child
    child.stdin.write(json.dumps({
        "url": state["gateway"].url, "mix": state["mix"],
        "seed": state["seed"], "seconds": seconds,
        "vocab_size": state["vocab"], "warmup": 2}) + "\n")
    child.stdin.flush()
    line = child.stdout.readline().strip()
    if line != "READY":
        raise RuntimeError(f"the load generator did not get ready: {line!r}")


def _wait_for_client(child) -> dict:
    """Wait for the child's one line. Meanwhile wake every ``TICK_S`` and
    note how late the latest wake-up came: one that comes seconds late
    says that this process, or the whole machine, was not running."""
    own, late_ms, at_s = time.process_time(), 0.0, 0.0
    t0 = last = time.monotonic()
    while not select.select([child.stdout], [], [], TICK_S)[0]:
        now = time.monotonic()
        if 1e3 * (now - last - TICK_S) > late_ms:
            late_ms, at_s = 1e3 * (now - last - TICK_S), last - t0
        last = now
    return {"server_tick_late_max_ms": late_ms,
            "server_tick_late_max_at_s": at_s,
            "server_own_cpu_s": time.process_time() - own}


def _longest_token_gap(reqs: list) -> dict:
    """The longest time in which no token of any request reached the
    client, and when it began: a stalled engine shows here."""
    ts = sorted(t for r in reqs for t in r["arrivals"])
    gaps = np.diff(ts)
    if not len(gaps):
        return {}
    i = int(np.argmax(gaps))
    return {"token_gap_max_ms": 1e3 * float(gaps[i]),
            "token_gap_max_at_s": float(ts[i])}


def run(state: dict, seconds: float, tracer) -> dict:
    srv, job = state["srv"], state["cell"]["serve"]
    start_s = float(job.get("trace_start_s", 5.0))
    if tracer.on:
        seconds = min(seconds, start_s + float(job.get("trace_seconds", 5.0)))
    # the child's start, its requests and its HTTP warm-up are set-up
    _start_child(state, seconds)
    child = state["child"]
    steps0 = srv.stats()["decode_steps"]
    started_at = time.perf_counter()
    child.stdin.write("GO\n")
    child.stdin.flush()
    span = None
    if tracer.on:
        time.sleep(min(start_s, max(0.0, seconds - 1.0)))
        with tracer.window():
            span = [time.monotonic()]
            with tracer.annotate("serve.wait_for_client"):
                time.sleep(max(0.5, seconds - start_s))
            span.append(time.monotonic())
    watched = _wait_for_client(child)
    reply = json.loads(child.stdout.readline())
    if span:  # on the client's clock: seconds from its start
        span = [t - reply["t0_monotonic"] for t in span]
    child.wait(timeout=30)
    steps = srv.stats()["decode_steps"] - steps0
    reqs = reply["requests"]

    ok = [r for r in reqs if r["ok"]]
    ttft = [1e3 * (r["arrivals"][0] - r["due_s"]) if r["ok"]
            else 1e3 * seconds for r in reqs]
    tpot = [1e3 * (r["arrivals"][-1] - r["arrivals"][0])
            / (len(r["tokens"]) - 1) if r["ok"] else 1e3 * seconds
            for r in reqs if len(r["tokens"]) > 1 or not r["ok"]]
    half = [[t for t, r in zip(ttft, reqs) if lo <= r["due_s"] < hi]
            for lo, hi in ((0, seconds / 2), (seconds / 2, seconds))]
    late = [1e3 * (r["sent_s"] - r["due_s"]) for r in reqs if "sent_s" in r]
    in_window = sum(1 for r in reqs for t in r["arrivals"] if t < seconds)
    every = sum(len(r["tokens"]) for r in reqs)
    started = sum(1 for r in reqs if r["tokens"])
    metrics = {"tpot_p95_ms": _pct(tpot, 95) if tpot else 1e3 * seconds,
               "served_tok_s": in_window / seconds}
    hop = [1e3 * (r["arrivals"][0] - r["sent_s"]) - r["record"]["ttft_ms"]
           for r in ok if r["record"].get("ttft_ms") is not None]
    queue = [r["record"]["queue_ms"] for r in ok
             if r["record"].get("queue_ms") is not None]
    slots = int(job["serving"]["decode_slots"])
    state["requests"] = reqs
    return {
        "attempted": len(reqs), "failed": len(reqs) - len(ok),
        "started_at": started_at, "metrics": metrics,
        "facts": {"ttft_ms": ttft, "queue_ms": queue, "decode_steps": steps,
                  "decode_slots": slots, "decode_tokens": every - started,
                  "window_s": seconds, "requests": reqs,
                  "traced_span_s": span},
        "notes": {"requests": len(reqs), "finished": len(ok),
                  "window_s": seconds, "ttft_p50_ms": _pct(ttft, 50),
                  "ttft_p95_ms": _pct(ttft, 95),
                  "tpot_p50_ms": _pct(tpot, 50) if tpot else None,
                  # the generator's lateness and the HTTP hop (client TTFT
                  # - lateness - the record's ttft_ms): the yardstick's own
                  # soundness, read by no metric
                  "gen_late_p95_ms": _pct(late, 95) if late else None,
                  "gateway_hop_p50_ms": _pct(hop, 50) if hop else None,
                  **_longest_token_gap(reqs), **watched,
                  # a backlog that grows shows as a second half slower than
                  # the first
                  "ttft_p50_ms_by_half": [_pct(h, 50) if h else None
                                          for h in half],
                  "offered_tokens": sum(r["max_new_tokens"] for r in reqs),
                  "tokens_in_window": in_window, "tokens_delivered": every,
                  "decode_steps": steps, **metrics,
                  "errors": sorted({str(r.get("error"))[:80] for r in reqs
                                    if not r["ok"]})[:5]},
    }


def check(state: dict, result: dict) -> dict:
    import jax
    import jax.numpy as jnp

    srv, cell, seed = state["srv"], state["cell"], state["seed"]
    reqs = state["requests"]
    window = result["notes"]["window_s"]
    prompts = traffic.requests(state["mix"], seed, window, state["vocab"])
    done = [i for i, r in enumerate(reqs) if r["ok"] and r["tokens"]]
    rng = np.random.default_rng([int(seed), 13])
    picked = sorted(rng.choice(done, min(CHECKED_REQUESTS, len(done)),
                               replace=False).tolist()) if done else []
    ref = jax.jit(cell["family"].reference_logits(cell["config_file"]))
    width = state["max_context"]
    judged = exact = 0
    worst = 0.0
    for i in picked:
        prompt, served = prompts[i]["prompt"], reqs[i]["tokens"]
        ids = np.zeros((1, width), np.int32)  # right padding: causal, unseen
        ids[0, :len(prompt) + len(served)] = prompt + served
        logits = np.asarray(ref(srv.engine.params, jnp.asarray(ids)))[0]
        for k, token in enumerate(served):
            row = logits[len(prompt) - 1 + k]
            gap = float(row.max() - row[token]) / float(np.abs(row).max())
            judged, exact = judged + 1, exact + (gap == 0.0)
            worst = max(worst, gap)
    return {"correct": bool(picked and worst <= NEAR_TIE_RTOL
                            and exact >= MIN_EXACT_SHARE * judged),
            "requests_checked": picked, "tokens_judged": judged,
            "tokens_exact_argmax": exact, "largest_gap_rel": worst,
            "near_tie_rtol": NEAR_TIE_RTOL,
            "min_exact_share": MIN_EXACT_SHARE}


def teardown(state: dict):
    child = state.get("child")
    if child is not None:
        if child.poll() is None:
            child.kill()
        child.wait(timeout=30)
        for pipe in (child.stdin, child.stdout):
            if pipe is not None:
                pipe.close()
    state["gateway"].close()
    state["srv"].destroy()
