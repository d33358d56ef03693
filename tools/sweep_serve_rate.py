"""The highest rate a serve cell sustains with no growing backlog, found
once, by a sweep on the chip: ONE engine for the seed (the cell's own job's
set-up), the cell's own schedule and drain, a window a rate.

    chiprun -- python tools/sweep_serve_rate.py --workload <cell> --seed N \\
        --rates 0.3,0.4,0.5 [--seconds 50]

One JSON line a rate: requests due and finished, ``served_tok_s``, TPOT, the
TTFT's 95th percentile and its median BY HALF of the window (a backlog that
grows shows as a second half many times the first), THE BACKLOG ITSELF at
the window's half and end (``waiting_at``: requests due and without their
first token; ``in_flight_at``: due and not finished), the busy rows a
decode step. The cell is rated at four fifths of the knee; the table goes
into its traffic file's note and PERF.md.
"""

import argparse
import json
import sys
import time

sys.path.insert(0, ".")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--schedule-seeds", default="",
                    help="orders of the mix to run each rate under (the "
                    "mix's own where none is given): how steady an order's "
                    "count is over --seed is the chip's to say")
    args = ap.parse_args()

    from perfbench import byname
    from perfbench import run as bench

    cell = bench.load_cell(args.workload)
    dev = bench.check_device(int(cell["chips"]))

    from deepspeed_tpu.telemetry import compile_watch
    from deepspeed_tpu.utils.compat import arm_compilation_cache

    arm_compilation_cache()
    compile_watch.install()
    job = byname.module("jobs", cell["job"])
    state = job.setup(cell, args.seed, dev)
    tracer = bench.Tracer(False)
    try:
        orders = [int(x) for x in args.schedule_seeds.split(",") if x] or [
            state["mix"]["schedule_seed"]]
        for rate, order in ((float(r), o) for r in args.rates.split(",")
                            for o in orders):
            state["mix"] = {**state["mix"], "schedule_seed": order,
                            "arrivals": {**state["mix"]["arrivals"],
                                         "rate_per_s": rate}}
            state["srv"].reset_stats()
            notes = job.run(state, args.seconds, tracer)["notes"]
            stats = state["srv"].stats()
            marks = (args.seconds / 2, args.seconds)
            reqs = state["requests"]
            waiting = [sum(r["due_s"] <= t and not (
                r["arrivals"] and r["arrivals"][0] <= t) for r in reqs)
                for t in marks]
            in_flight = [sum(r["due_s"] <= t and not (
                r["ok"] and r["arrivals"][-1] <= t) for r in reqs)
                for t in marks]
            print(json.dumps({
                "rate": rate, "schedule_seed": order, "seed": args.seed,
                "due": notes["requests"],
                "finished": notes["finished"],
                "served_tok_s": notes["served_tok_s"],
                "tpot_p50_ms": notes["tpot_p50_ms"],
                "tpot_p95_ms": notes["tpot_p95_ms"],
                "ttft_p95_ms": notes["ttft_p95_ms"],
                "ttft_p50_ms_by_half": notes["ttft_p50_ms_by_half"],
                "waiting_at": waiting, "in_flight_at": in_flight,
                "decode_steps": notes["decode_steps"],
                "prefill_calls": stats.get("prefill_calls"),
                "busy_rows_mean": stats["busy_slot_steps"]
                / max(1, stats["decode_steps"]),
                "tokens_delivered": notes["tokens_delivered"],
                "offered_tokens": notes["offered_tokens"],
                "errors": notes["errors"][:3]}), flush=True)
            time.sleep(2)
    finally:
        job.teardown(state)


if __name__ == "__main__":
    main()
