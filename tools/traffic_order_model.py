#!/usr/bin/env python3
"""How far a serve cell's ``served_tok_s`` moves with the engine's speed
under one ORDER of its traffic mix, by a queue model of the step loop; and
the orders of one mix ranked by it.

``served_tok_s`` counts the tokens that arrive inside the window. An open
loop below the knee serves what is offered, so the count moves with the
engine's speed only through the requests in flight at the cut, and how many
those are depends on where the order of lengths and gaps (the mix's
``schedule_seed``) puts its long prompts. ``perfbench/traffic/
agent-gen.json``'s order was chosen with this model (PERF.md, PR 49); the
chip's runs, not the model, say what an order's spread is.

The model: the engine's loop runs one prefill chunk (``--chunk-s``) of the
oldest unfinished prompt in turn, then one decode step of every live row
(``--step-s`` + ``--row-s`` a row); a request holds one of ``--slots`` from
its arrival to its last token. No device, no jax: the mix's lengths and
gaps from ``perfbench.traffic``.

    python tools/traffic_order_model.py perfbench/traffic/agent-gen.json
    python tools/traffic_order_model.py MIX --rank 250 --from-seed 4900000050

prints one JSON line an order: ``tokens_s`` in the window, ``per_speed``
(the change of it over the change of speed, between 0.98 and 1.02: 1 is one
for one), ``stall_loss`` (the largest share lost to one stall of
``--stall-s`` at a quarter, a half or three quarters of the window).
Exit 0, or 2 on a usage error.
"""

import argparse
import copy
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def tokens_in_window(reqs, window, chunk_tokens, chunk_s, step_s, row_s,
                     slots, speed=1.0, stall=None, key_s=0.0,
                     chunk_speed=1.0, step_speed=1.0):
    """Tokens delivered by ``window`` seconds; ``reqs`` as
    ``perfbench.traffic.requests`` gives them, ``stall = (at_s, for_s)``;
    a chunk takes ``chunk_s + key_s x the keys live behind it`` (attention
    that reads every live key), ``chunk_speed`` and ``step_speed`` on top
    of ``speed`` for the two programs apart."""
    due = [r["due_s"] for r in reqs]
    todo = [len(r["prompt"]) for r in reqs]
    new = [r["max_new_tokens"] for r in reqs]
    t, nxt, turn, count = 0.0, 0, 0, 0
    prefilling, live = [], {}    # [index, tokens done]; index -> tokens out
    while t < window and (nxt < len(reqs) or prefilling or live):
        while (nxt < len(reqs) and due[nxt] <= t
               and len(prefilling) + len(live) < slots):
            prefilling.append([nxt, 0])
            nxt += 1
        if not prefilling and not live:
            t = due[nxt]
            continue
        if stall and t >= stall[0]:
            t, stall = t + stall[1], None
        if prefilling:
            turn %= len(prefilling)
            item = prefilling[turn]
            item[1] += chunk_tokens
            t += (chunk_s + key_s * min(item[1], todo[item[0]])) / (
                speed * chunk_speed)
            if item[1] >= todo[item[0]]:
                prefilling.pop(turn)
                count += t < window
                if new[item[0]] > 1:
                    live[item[0]] = 1
            else:
                turn += 1
        if live:
            t += (step_s + row_s * len(live)) / (speed * step_speed)
            count += len(live) * (t < window)
            for i in [i for i in live if live[i] + 1 >= new[i]]:
                del live[i]
            for i in live:
                live[i] += 1
    return count


def score(mix, order, window, stall_s, **loop):
    """One order's ``{"schedule_seed", "tokens_s", "per_speed",
    "stall_loss"}``; ``loop``: :func:`tokens_in_window`'s."""
    from perfbench import traffic

    mix = {**copy.deepcopy(mix), "schedule_seed": int(order)}
    reqs = traffic.requests(mix, 0, window, 8)
    base = tokens_in_window(reqs, window, **loop)
    slow, fast = (tokens_in_window(reqs, window, speed=s, **loop)
                  for s in (0.98, 1.02))
    # the two programs apart, and a tenth instead of a fiftieth: an order
    # whose count moves by steps (a request falls inside the window or out)
    # reads otherwise at the two widths
    apart = {f"per_{name}": (
        tokens_in_window(reqs, window, **{name: 1.02}, **loop)
        - tokens_in_window(reqs, window, **{name: 0.98}, **loop))
        / base / 0.04 for name in ("chunk_speed", "step_speed")}
    wide = (tokens_in_window(reqs, window, speed=1.1, **loop)
            - tokens_in_window(reqs, window, speed=0.9, **loop)) / base / 0.2
    stalled = [tokens_in_window(reqs, window, stall=(q * window, stall_s),
                                **loop) for q in (0.25, 0.5, 0.75)]
    return {"schedule_seed": int(order), "tokens_s": base / window,
            "per_speed": (fast - slow) / base / 0.04,
            "per_speed_tenth": wide, **apart,
            "stall_loss": max(base - s for s in stalled) / base}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("mix", help="a traffic file of kind 'requests'")
    parser.add_argument("--window", type=float, default=50.0)
    parser.add_argument("--rank", type=int, default=0,
                        help="score this many orders and print the ten "
                             "nearest --per-speed (0: the mix's own order)")
    parser.add_argument("--per-speed", type=float, default=0.0,
                        help="the change of the count over the change of "
                             "speed a ranking looks for: 0 is a count deaf "
                             "to the engine (a mix ABOVE its knee has the "
                             "rate to say it), 1 is one for one")
    parser.add_argument("--from-seed", type=int, default=1)
    parser.add_argument("--chunk-tokens", type=int, default=512)
    parser.add_argument("--chunk-s", type=float, default=0.046)
    parser.add_argument("--step-s", type=float, default=0.018)
    parser.add_argument("--row-s", type=float, default=0.00015)
    parser.add_argument("--key-s", type=float, default=0.0,
                        help="seconds a chunk takes more for each key "
                             "live behind it")
    parser.add_argument("--slots", type=int, default=64)
    parser.add_argument("--stall-s", type=float, default=1.5)
    try:
        args = parser.parse_args(argv)
    except SystemExit as e:
        return 2 if e.code else 0
    with open(args.mix) as f:
        mix = json.load(f)
    if mix.get("kind") != "requests":
        print(f"{args.mix}: not a mix of kind 'requests'", file=sys.stderr)
        return 2
    loop = dict(chunk_tokens=args.chunk_tokens, chunk_s=args.chunk_s,
                step_s=args.step_s, row_s=args.row_s, slots=args.slots,
                key_s=args.key_s)
    orders = (range(args.from_seed, args.from_seed + args.rank) if args.rank
              else [mix.get("schedule_seed", 0)])
    rows = [score(mix, order, args.window, args.stall_s, **loop)
            for order in orders]
    rows.sort(key=lambda r: abs(r["per_speed"] - args.per_speed)
              + abs(r["per_speed_tenth"] - args.per_speed)
              + 10 * r["stall_loss"])
    for row in rows[:10]:
        print(json.dumps(row))
    return 0


if __name__ == "__main__":
    sys.exit(main())
