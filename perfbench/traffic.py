"""One general traffic generator, driven by a data file and ``--seed``.

A traffic mix is ``perfbench/traffic/<name>.json``. The arithmetic is that
of ``deepspeed_tpu/serving/replay.py`` (Poisson arrivals, lognormal
lengths; every prompt unshared), with two differences the benchmark
needs: it runs on the wall clock, and every seed gets THE SAME multiset of
gaps and lengths in another order. The gaps are the exponential
distribution's quantiles at ``(i + 0.5) / n``, the lengths the lognormal's,
both shuffled by the seed: the work in a window does not depend on the
seed, only its order does. Where the order itself moves what is measured
(a tail over a few dozen requests does: PERF.md, PR 25), the mix fixes it
with ``schedule_seed``, and ``--seed`` then draws the token ids alone.

Pure host code: numpy only, never JAX (the load generator's process must
not touch the chip).

Serving mix::

    {"kind": "requests",
     "arrivals": {"process": "poisson", "rate_per_s": 8.0,
                  "bursts": {"every_s": 10, "for_s": 3, "times": 3}},
     # or {"process": "all_at_zero", "count": 256}
     "prompt_len": {"dist": "lognormal", "median": 192, "sigma": 0.8,
                    "min": 16, "max": 768},
     "new_tokens": {"dist": "lognormal", "median": 96, "sigma": 0.6,
                    "min": 8, "max": 256},
     "max_total": 1024, "schedule_seed": 5,
     "drain_seconds": 20}

Training mix::

    {"kind": "train_batches", "seq_len": 1024}
"""

import json
import math
import os
from statistics import NormalDist
from typing import List

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str, root: str = _HERE) -> dict:
    path = os.path.join(root, "traffic", f"{name}.json")
    if not os.path.isfile(path):
        raise FileNotFoundError(
            f"no traffic mix {name!r}: {path} does not exist")
    with open(path) as f:
        return json.load(f)


def _rng(seed: int, stream: int) -> np.random.Generator:
    # SeedSequence takes any non-negative whole number, however large
    return np.random.default_rng([int(seed), stream])


def _quantile_points(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` whole lengths: the distribution's quantiles, shuffled."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = np.array([NormalDist().inv_cdf(float(p)) for p in _quantile_points(n)])
    vals = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    vals = np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)
    rng.shuffle(vals)
    return vals


def _cumulative_rate(spec: dict, seconds: float):
    """Breakpoints ``(t, expected arrivals up to t)`` of the piecewise
    constant rate: the base rate, multiplied by ``times`` in the first
    ``for_s`` of every ``every_s``."""
    rate = float(spec["rate_per_s"])
    bursts = spec.get("bursts")
    ts, edges = [0.0], [0.0]
    if bursts:
        t = 0.0
        while t < seconds:
            for end, r in ((t + bursts["for_s"], rate * bursts["times"]),
                           (t + bursts["every_s"], rate)):
                end = min(end, seconds)
                if end > ts[-1]:
                    edges.append(edges[-1] + r * (end - ts[-1]))
                    ts.append(end)
            t += bursts["every_s"]
    else:
        ts.append(seconds)
        edges.append(rate * seconds)
    return np.array(ts), np.array(edges)


def arrival_times(spec: dict, seconds: float,
                  rng: np.random.Generator) -> np.ndarray:
    """Due times in ``[0, seconds)``, ascending."""
    if spec["process"] == "all_at_zero":
        return np.zeros(int(spec["count"]))
    if spec["process"] != "poisson":
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    ts, cum = _cumulative_rate(spec, seconds)
    n = int(round(cum[-1]))
    if n < 1:
        raise ValueError("the traffic mix gives no request in the window")
    gaps = -np.log1p(-_quantile_points(n))      # Exp(1) quantiles
    rng.shuffle(gaps)
    # unit-rate arrivals: the first at 0, the gaps scaled to fill the
    # window, so that every seed has the same gaps in another order
    gaps *= cum[-1] / gaps.sum()
    unit = np.cumsum(gaps) - gaps[0]
    return np.interp(unit, cum, ts)


def requests(mix: dict, seed: int, seconds: float, vocab_size: int) -> List[dict]:
    """``[{"due_s", "prompt", "max_new_tokens"}]`` in due order."""
    if mix.get("kind") != "requests":
        raise ValueError(f"traffic kind {mix.get('kind')!r} is not 'requests'")
    # the order of gaps and lengths: the mix's own ``schedule_seed`` where
    # it has one (then ``--seed`` draws the token ids alone, and every run
    # of the cell replays one schedule), else ``--seed``
    order = mix.get("schedule_seed", seed)
    due = arrival_times(mix["arrivals"], seconds, _rng(order, 0))
    n = len(due)
    prompt_len = lengths(mix["prompt_len"], n, _rng(order, 1))
    new_tokens = lengths(mix["new_tokens"], n, _rng(order, 2))
    max_total = int(mix.get("max_total", 0))
    if max_total:
        new_tokens = np.minimum(new_tokens, max_total - prompt_len)
        if (new_tokens < 1).any():
            raise ValueError("a prompt leaves no room under max_total")
    tok = _rng(seed, 3)
    return [{"due_s": float(due[i]),
             "prompt": [int(t) for t in
                        tok.integers(0, vocab_size, int(prompt_len[i]))],
             "max_new_tokens": int(new_tokens[i])} for i in range(n)]


def train_batch(mix: dict, seed: int, step: int, rows: int,
                vocab_size: int) -> np.ndarray:
    """The ``[rows, seq_len]`` int32 batch of step ``step``: fresh every
    step, the same for the same seed."""
    if mix.get("kind") != "train_batches":
        raise ValueError(
            f"traffic kind {mix.get('kind')!r} is not 'train_batches'")
    rng = np.random.default_rng([int(seed), 7, int(step)])
    return rng.integers(0, vocab_size, (rows, int(mix["seq_len"])),
                        dtype=np.int32)
