"""Where a benchmark cell's start-up goes, and what paused its step loop:
one run of ``perfbench/run.py`` a process, with the process's own ledger
(``deepspeed_tpu/telemetry/process_ledger.py``) written beside the result.

    chiprun -- python tools/chip_startup_ledger.py \\
        --cells serve-granite-h-ssm-agents,serve-lfm2-conv-chat \\
        --runs cold,warm,parked --seconds 20 --tag a

For each cell and each of ``--runs``, in order, a child process runs the
cell through the harness (``perfbench.run.main``, nothing of it edited) and
writes ``chiprun_out/c54_<cell>_<run>_<tag>.json``: the result line, the
harness's ``setup_s``, ``process_ledger.snapshot()`` (``ready_s`` by
phase, every program's first call), the start-up log line, and the engine's
``stats()["host_pauses"]`` and ``["slow_steps"]`` taken before teardown.
The child's whole output is ``..._<tag>.log``. The parent stays off JAX:
a chip belongs to one process at a time.

Runs: ``cold`` empties the compile cache first; ``warm`` runs as the cache
lies (``--trace 0`` both); ``parked`` runs ``--trace 1`` over a throw-away
copy of the benchmark with the six parked metrics declared
(``tests/perfbench/test_startup_metrics.py:lay_parked``) and keeps their
values; ``tracing`` is ``warm`` with ``telemetry.tracing`` on in the engine
(the JSONL spans to a temporary directory), for what the instrumentation
costs when it is on. ``--root`` names another copy of ``perfbench`` (the
CPU rehearsal's, with a tiny cell in it).
"""

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(REPO, "chiprun_out")


def _cache_dir() -> str:
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(REPO, ".jax_compile_cache"))


def one(args) -> int:
    """The child: one run of one cell in this process."""
    sys.path.insert(0, REPO)
    from perfbench import byname
    from perfbench import run as bench_run     # its _T0: the harness's start

    kept = {}
    root = args.root or bench_run.HERE
    if args.run == "parked":
        from tests.perfbench.test_startup_metrics import lay_parked

        root = lay_parked(tempfile.mkdtemp(prefix="c54-parked-"),
                          source=os.path.dirname(root))
    found = byname.module

    def module(kind, name):
        mod = found(kind, name)
        if kind == "jobs" and not hasattr(mod, "_c54_teardown"):
            mod._c54_teardown, setup = mod.teardown, mod.setup

            def teardown(state):
                srv, engine = state.get("srv"), state.get("engine")
                if srv is not None:
                    stats = srv.stats()
                    kept.update({k: stats.get(k) for k in (
                        "host_pauses", "slow_steps", "decode_steps")})
                if engine is not None:
                    kept["describe_topology"] = engine.describe_topology(
                        include_tensors=False, include_data=False).get(
                            "startup")
                return mod._c54_teardown(state)

            def traced_setup(cell, seed, device):
                import deepspeed_tpu

                plain = deepspeed_tpu.init_inference
                spans = tempfile.mkdtemp(prefix="c54-spans-")
                deepspeed_tpu.init_inference = lambda *a, **k: plain(
                    *a, telemetry={"enabled": True, "dir": spans,
                                   "tracing": {"enabled": True}}, **k)
                try:
                    return setup(cell, seed, device)
                finally:
                    deepspeed_tpu.init_inference = plain

            mod.teardown = teardown
            if args.run == "tracing":
                mod.setup = traced_setup
        return mod

    byname.module = module
    rc = bench_run.main(
        ["--workload", args.one, "--seed", str(args.seed), "--seconds",
         str(args.seconds), "--trace", "1" if args.run == "parked" else "0"],
        root=root)
    try:
        from deepspeed_tpu.telemetry import process_ledger

        kept["snapshot"] = process_ledger.snapshot()
        kept["ready_line"] = process_ledger.ProcessLedger.ready_line(
            kept["snapshot"])
        kept["host_pauses_process"] = process_ledger.LEDGER.host_pauses()
    except ImportError:             # a parent from before the ledger
        kept["snapshot"] = None
    with open(args.out, "w") as f:
        json.dump(kept, f)
    return rc


def _json_lines(text: str):
    for line in text.splitlines():
        if line.startswith("{"):
            try:
                yield json.loads(line)
            except ValueError:
                pass


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cells", default="")
    ap.add_argument("--runs", default="warm")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=5400000101)
    ap.add_argument("--tag", default="a")
    ap.add_argument("--root", default=None)
    ap.add_argument("--one", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--run", default="warm", help=argparse.SUPPRESS)
    ap.add_argument("--out", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.one:
        return one(args)
    os.makedirs(OUT, exist_ok=True)
    seed, worst = args.seed, 0
    for cell in filter(None, args.cells.split(",")):
        for run in args.runs.split(","):
            seed += 1               # every run a seed of its own
            stem, again = os.path.join(OUT, f"c54_{cell}_{run}_{args.tag}"), 1
            while os.path.exists(stem + ".json"):   # the same run again
                again += 1
                stem = os.path.join(OUT, f"c54_{cell}_{run}{again}_{args.tag}")
            if run == "cold":
                shutil.rmtree(_cache_dir(), ignore_errors=True)
            cmd = [sys.executable, os.path.abspath(__file__), "--one", cell,
                   "--run", run, "--seed", str(seed), "--seconds",
                   str(args.seconds), "--out", stem + ".child.json"]
            if args.root:
                cmd += ["--root", args.root]
            done = subprocess.run(cmd, cwd=REPO, stdout=subprocess.PIPE,
                                  stderr=subprocess.STDOUT, text=True)
            with open(stem + ".log", "w") as f:
                f.write(done.stdout)
            kept = {}
            if os.path.exists(stem + ".child.json"):
                with open(stem + ".child.json") as f:
                    kept = json.load(f)
                os.remove(stem + ".child.json")
            lines = list(_json_lines(done.stdout))
            phases = {ln["phase"]: ln for ln in lines if "phase" in ln}
            result = next((ln for ln in reversed(lines) if "correct" in ln),
                          None)
            startup = next((ln.split("] ", 3)[-1]
                            for ln in done.stdout.splitlines()
                            if "start-up " in ln and "outside" in ln), None)
            slow = [ln for ln in done.stdout.splitlines()
                    if "serving step" in ln and " took " in ln]
            window = phases.get("window", {})
            record = {
                "cell": cell, "run": run, "seed": seed, "rc": done.returncode,
                "seconds": args.seconds, "result": result,
                "setup_s": phases.get("setup", {}).get("setup_s"),
                "backend_compiles_in_setup": phases.get("setup", {}).get(
                    "backend_compiles_in_setup"),
                "cache_hits_in_setup": phases.get("setup", {}).get(
                    "persistent_cache_hits_in_setup"),
                "window": {k: window.get(k) for k in (
                    "served_tok_s", "tpot_p50_ms", "tpot_p95_ms",
                    "token_gap_max_ms", "token_gap_max_at_s",
                    "server_tick_late_max_ms", "server_tick_late_max_at_s",
                    "compiles_in_window", "train_tok_s_chip")
                    if k in window},
                "startup_log_line": startup, "slow_step_log_lines": slow,
                **kept}
            with open(stem + ".json", "w") as f:
                json.dump(record, f, indent=1)
            snap = kept.get("snapshot") or {}
            print(json.dumps({
                "cell": cell, "run": run, "rc": done.returncode,
                "setup_s": record["setup_s"], "ready_s": snap.get("ready_s"),
                "top_level": snap.get("top_level"),
                "pool": (snap.get("phases") or {}).get("pool"),
                "weight_layouts": (snap.get("phases") or {}).get(
                    "weight_layouts"),
                "compile": snap.get("compile"),
                "outside_s": snap.get("outside_s"), "gc_s": snap.get("gc_s"),
                "late_programs": [p["program"] for p in
                                  snap.get("late_programs") or ()],
                "window": record["window"],
                "metrics": {k: v["value"] for k, v in (
                    (result or {}).get("metrics") or {}).items()
                    if k.startswith("startup_") or k in (
                        "host_gc_share", "setup_s", "served_tok_s",
                        "tpot_p95_ms", "train_tok_s_chip")},
                "host_pauses": kept.get("host_pauses"),
                "slow_steps": (kept.get("slow_steps") or [])[:3],
                "correct": (result or {}).get("correct")}), flush=True)
            worst = max(worst, done.returncode)
    return worst


if __name__ == "__main__":
    sys.exit(main())
