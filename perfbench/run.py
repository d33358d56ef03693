"""One run of one benchmark cell:

    python -m perfbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's data files, checks the device, builds the system under
test through its normal entry points, warms this cell's shapes (all of it
counted as ``setup_s``), measures for ``--seconds``, decides ``correct``
outside the window, and prints ONE JSON object as the last line of its
standard output. ``--trace 0`` reports the cell's end-to-end metrics;
``--trace 1`` runs a short window under the profiler and reports the
per-layer metrics. There is no fallback to another device: without a TPU
(and without ``JAX_PLATFORMS=cpu`` asked for by name, which a test does
and which proves nothing) the run exits non-zero and prints no result.

Everything that belongs to one cell, configuration, model family, traffic
mix, job kind, per-layer metric or kernel is a file of its own, found by
name: see README.md.
"""

import time

_T0 = time.perf_counter()  # process start, as near as Python lets us read it

import argparse
import contextlib
import json
import os
import shutil
import sys
import tempfile

from perfbench import byname
from perfbench.byname import BenchError

HERE = os.path.dirname(os.path.abspath(__file__))


def say(**fields):
    """An earlier line of standard output: JSON, never the last line."""
    print(json.dumps(fields), flush=True)


def _load_json(path: str, what: str) -> dict:
    if not os.path.isfile(path):
        raise BenchError(f"unknown {what}: {path} does not exist")
    with open(path) as f:
        return json.load(f)


def _benchmark(root: str) -> dict:
    return _load_json(os.path.join(os.path.dirname(root), "BENCHMARK.json"),
                      "BENCHMARK.json")


def check_cut(config_file: dict, declared=None):
    """Hold a configuration file to the form of a cut (README.md).
    ``reduced`` lists the keys of ``model`` that differ from the source, and
    is what BENCHMARK.json ``declared`` for the configuration, where it
    lists it. A cut file says what it was cut from, ``published`` (exactly
    those keys, with the source's values), and what it stands for,
    ``deployment``; an uncut one has neither."""
    reduced, model = config_file["reduced"], config_file["model"]
    wrong = None
    if declared is not None and reduced != declared:
        wrong = f"reduced {reduced} is not BENCHMARK.json's {declared}"
    elif [k for k in reduced if k not in model]:
        wrong = f"reduced {reduced} names a key that model lacks"
    elif not reduced:
        if "published" in config_file or "deployment" in config_file:
            wrong = "published or deployment without a key in reduced"
    elif sorted(config_file.get("published", ())) != sorted(reduced):
        wrong = f"published has to hold exactly the keys {reduced}"
    elif [k for k in reduced if config_file["published"][k] == model[k]]:
        wrong = "a key in reduced has its published value in model"
    elif not str(config_file.get("deployment", "")).strip():
        wrong = "a cut configuration states its deployment"
    if wrong:
        raise BenchError(f"configuration of {config_file['source']!r}: "
                         f"{wrong}")


def load_cell(name: str, root: str = HERE) -> dict:
    """The cell ``name`` with its configuration, the configuration's family
    (the module ``families/<family>.py``) and its traffic mix, all found by
    name under ``root``; an unknown name is an error that says so."""
    known = sorted(f[:-5] for f in os.listdir(os.path.join(root, "workloads"))
                   if f.endswith(".json"))
    if name not in known:
        raise BenchError(f"unknown workload {name!r}; known: {known}")
    cell = _load_json(os.path.join(root, "workloads", f"{name}.json"),
                      "workload")
    cell["name"] = name
    cell["config_file"] = _load_json(
        os.path.join(root, "configs", f"{cell['config']}.json"),
        f"configuration {cell['config']!r}")
    declared = {c["name"]: c["reduced"] for c in _benchmark(root)["configs"]}
    check_cut(cell["config_file"], declared.get(cell["config"]))
    cell["family"] = byname.module("families", cell["config_file"]["family"])
    cell["traffic_file"] = _load_json(
        os.path.join(root, "traffic", f"{cell['traffic']}.json"),
        f"traffic mix {cell['traffic']!r}")
    return cell


def declared_metrics(root: str = HERE) -> dict:
    """``{"end_to_end": [entry], "per_layer": [entry]}`` of the
    BENCHMARK.json beside ``root``: the one place that says which cells
    report a metric (``workloads``; none means every cell), its unit, its
    layer and the end-to-end metric it should move."""
    bench = _benchmark(root)
    return {kind: bench[kind] for kind in ("end_to_end", "per_layer")}


def metrics_of(cell_name: str, entries: list) -> list:
    """The entries of one BENCHMARK.json metric list that ``cell_name``
    reports."""
    return [m for m in entries if cell_name in m.get("workloads", (cell_name,))]


def layer_metric_specs(cell: dict, root: str = HERE) -> list:
    """The per-layer metrics this cell reports, as BENCHMARK.json declares
    them, each with its reader and the reader's parameters from
    ``layer_metrics/<metric>.json``. A new cell takes up an existing metric
    by its name in that metric's ``workloads`` in BENCHMARK.json; a new
    metric is a new entry there and a new file here."""
    out = []
    for entry in metrics_of(cell["name"], declared_metrics(root)["per_layer"]):
        how = _load_json(
            os.path.join(root, "layer_metrics", f"{entry['name']}.json"),
            f"per-layer metric {entry['name']!r}")
        out.append({**how, **entry})
    return out


class Tracer:
    """The profiler around a job's measured window. Off (``--trace 0``) it
    does nothing. On, :meth:`window` starts the profiler, annotates the
    window as ``perfbench.window`` on the profiler's clock and stops the
    profiler; :attr:`summary` is the reduction of what it wrote. The raw
    trace lives under ``TMPDIR`` and is removed."""

    def __init__(self, on: bool, cpu_rehearsal: bool = False):
        self.on, self.summary, self.trace = on, None, None
        self.cpu_rehearsal = cpu_rehearsal

    @contextlib.contextmanager
    def window(self):
        if not self.on:
            yield
            return
        import jax

        from perfbench import trace_reduce

        trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-")
        try:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0   # host annotations, no frames
            options.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            try:
                with jax.profiler.TraceAnnotation(
                        trace_reduce.WINDOW_ANNOTATION):
                    yield
            finally:
                jax.profiler.stop_trace()
            path = trace_reduce.find_xplane(trace_dir)
            if path is None:
                raise BenchError("the profiler wrote no .xplane.pb")
            self.trace = trace_reduce.load(path, self.cpu_rehearsal)
            self.summary = trace_reduce.summarize(self.trace)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)

    @staticmethod
    def annotate(name: str):
        """A host span ``perfbench.<name>`` on the profiler's clock."""
        import jax

        return jax.profiler.TraceAnnotation(f"perfbench.{name}")


def check_device(chips: int) -> dict:
    """The device as JAX reports it, or an error: a TPU (the CPU only if
    asked for by name), holding at least the chips the cell asks for."""
    from deepspeed_tpu.utils import device

    dev = device.require_device("tpu")
    if dev["count"] < chips:
        raise BenchError(f"the cell needs {chips} chip(s), JAX sees "
                         f"{dev['count']} ({dev['kind']})")
    return dev


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device, as the backend reports it
    (0 where it reports nothing, as the CPU does)."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks, default=0))


def read_layer_metrics(cell: dict, facts: dict, root: str = HERE) -> dict:
    """Run each of the cell's per-layer metric readers on ``facts``; a
    reader that finds nothing to read returns None and is left out. Off the
    chip only the metrics whose file says ``"needs_chip": false`` (counts
    and host-side times of the rehearsal itself) are read."""
    out = {}
    on_chip = facts["device"]["platform"] == "tpu"
    for spec in layer_metric_specs(cell, root):
        if not on_chip and spec.get("needs_chip", True):
            continue  # a CPU rehearsal writes no number under a device metric
        value = byname.module("readers", spec["reader"]).read(spec, facts)
        if value is not None:
            out[spec["name"]] = {"value": value, "unit": spec["unit"]}
    return out


def run_cell(args, root: str = HERE) -> dict:
    """Everything between the arguments and the result line's object."""
    cell = load_cell(args.workload, root)
    declared = declared_metrics(root)
    dev = check_device(int(cell["chips"]))

    from deepspeed_tpu.telemetry import compile_watch
    from deepspeed_tpu.utils.compat import arm_compilation_cache

    cache_dir = arm_compilation_cache()
    compile_watch.install()
    job = byname.module("jobs", cell["job"])
    tracer = Tracer(bool(args.trace), cpu_rehearsal=dev["platform"] == "cpu")
    state = job.setup(cell, args.seed, dev)
    try:
        before = compile_watch.snapshot()
        result = job.run(state, float(args.seconds), tracer)
        # process start to the first measured step or request
        setup_s = result["started_at"] - _T0
        after = compile_watch.snapshot()
        compiles = after["backend_compiles"] - before["backend_compiles"]
        verdict = job.check(state, result)
    finally:
        job.teardown(state)
    say(phase="setup", setup_s=setup_s, compile_cache_dir=cache_dir,
        backend_compiles_in_setup=before["backend_compiles"],
        persistent_cache_hits_in_setup=before["persistent_cache_hits"])
    say(phase="window", compiles_in_window=compiles, **result["notes"])
    say(phase="check", **verdict)
    # each number compared beside its limit, where a failed run's record
    # keeps it: the end of standard error
    print("perfbench: check " + json.dumps(
        {**verdict, "compiles_in_window": compiles,
         "compiles_in_window_limit": 0}), file=sys.stderr, flush=True)

    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"], "memory_peak_bytes": memory_peak_bytes()}
    out = {"correct": bool(verdict["correct"] and compiles == 0),
           "attempted": int(result["attempted"]),
           "failed": int(result["failed"])}
    if args.trace:
        summary = tracer.summary
        facts = {**result["facts"], "cell": cell, "device": dev,
                 "trace": summary, "trace_events": tracer.trace,
                 "chips": int(cell["chips"])}
        out["metrics"] = read_layer_metrics(cell, facts, root)
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        out["breakdown"] = summary["breakdown"]
    else:
        values = {**result["metrics"], "setup_s": setup_s}
        out["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in metrics_of(cell["name"], declared["end_to_end"])
            if m["name"] in values}
    out["device"] = device
    return out


def main(argv=None, root: str = HERE) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    try:
        out = run_cell(args, root)
    except BenchError as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
