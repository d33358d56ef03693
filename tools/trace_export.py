"""Export telemetry `span` events to Chrome/Perfetto trace-event JSON.

The consumer side of ``deepspeed_tpu/telemetry/tracing.py``: converts a
telemetry JSONL sink (rotated segments included) into the
``trace_event`` format Perfetto (https://ui.perfetto.dev) and
``chrome://tracing`` open directly. Run::

    python tools/trace_export.py path/to/telemetry.jsonl -o trace.json
    python tools/trace_export.py path --trace <trace-id>   # one trace only
    python tools/trace_export.py path                      # JSON to stdout

Layout: each TRACE becomes one Perfetto "process" (named by its trace
id and root span), and within it each span lands on the "thread" of its
``replica``/``rank`` attribute (so a failover renders as the attempt
subtrees side by side on two replica lanes). Span attrs ride in
``args`` — click any slice to see request ids and token counts.
Exit codes: 0 = wrote a trace, 1 = no span events found
(enable ``telemetry.tracing``), 2 = bad input path.
"""

import argparse
import json
import os
import sys
from typing import Dict, List

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deepspeed_tpu.telemetry.events import (  # noqa: E402
    SPAN_META,
    load_all_events,
)


def _lane(data: Dict) -> str:
    """Thread lane within a trace: replica attr when present (router
    failovers show side by side), else the emitting rank."""
    if "replica" in data:
        return f"replica {data['replica']}"
    return "main"


def to_trace_events(events: List[Dict],
                    only_trace: str = None) -> List[Dict]:
    """Chrome trace-event list from telemetry events (spans only)."""
    spans = [e for e in events if e.get("kind") == "span"]
    if only_trace is not None:
        spans = [e for e in spans
                 if e.get("data", {}).get("trace") == only_trace]
    if not spans:
        return []
    pids: Dict[str, int] = {}
    tids: Dict[tuple, int] = {}
    out: List[Dict] = []
    # root span name per trace, for the process label
    roots = {}
    for e in spans:
        d = e.get("data", {})
        if d.get("parent") is None:
            roots.setdefault(d.get("trace"), e.get("name"))
    for e in spans:
        d = e.get("data", {})
        trace = str(d.get("trace"))
        if trace not in pids:
            pids[trace] = len(pids) + 1
            label = roots.get(d.get("trace"))
            out.append({"ph": "M", "name": "process_name",
                        "pid": pids[trace], "tid": 0,
                        "args": {"name": (f"{label}: {trace}" if label
                                          else trace)}})
        pid = pids[trace]
        lane = _lane(d)
        if (trace, lane) not in tids:
            tids[(trace, lane)] = len([k for k in tids
                                       if k[0] == trace]) + 1
            out.append({"ph": "M", "name": "thread_name", "pid": pid,
                        "tid": tids[(trace, lane)],
                        "args": {"name": lane}})
        start = int(d.get("start_ns", 0))
        end = max(int(d.get("end_ns", start)), start)
        args = {k: v for k, v in d.items() if k not in SPAN_META}
        args["span"] = d.get("span")
        if d.get("parent") is not None:
            args["parent"] = d.get("parent")
        out.append({
            "ph": "X",
            "name": e.get("name"),
            "cat": "span",
            "pid": pid,
            "tid": tids[(trace, lane)],
            "ts": start / 1e3,           # trace_event wants microseconds
            "dur": (end - start) / 1e3,
            "args": args,
        })
    return out


def export(path: str, only_trace: str = None) -> Dict:
    events = load_all_events(path)
    return {
        "traceEvents": to_trace_events(events, only_trace=only_trace),
        "displayTimeUnit": "ms",
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("path", help="telemetry.jsonl file (or its directory)")
    ap.add_argument("-o", "--output", default=None,
                    help="output .json path (default: stdout)")
    ap.add_argument("--trace", default=None,
                    help="export only the given trace id")
    args = ap.parse_args(argv)
    path = args.path
    if os.path.isdir(path):
        path = os.path.join(path, "telemetry.jsonl")
    if not os.path.exists(path) and not os.path.exists(f"{path}.1"):
        print(f"trace_export: no sink at {path!r}", file=sys.stderr)
        return 2
    payload = export(path, only_trace=args.trace)
    n = sum(1 for e in payload["traceEvents"] if e.get("ph") == "X")
    if n == 0:
        print("trace_export: no span events in the sink — enable "
              '"telemetry": {"tracing": {"enabled": true}}',
              file=sys.stderr)
        return 1
    text = json.dumps(payload)
    if args.output:
        with open(args.output, "w") as f:
            f.write(text)
        print(f"trace_export: wrote {n} span(s) from "
              f"{len({e['pid'] for e in payload['traceEvents']})} trace(s) "
              f"-> {args.output}", file=sys.stderr)
    else:
        print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
