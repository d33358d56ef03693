"""Render a telemetry JSONL sink into a human-readable summary.

The consumer side of ``deepspeed_tpu/telemetry``: aggregates the event
stream a run wrote (``telemetry.jsonl``) into compile / step-cost /
memory / trace-window / wallclock sections. Run::

    python tools/telemetry_report.py path/to/telemetry.jsonl
    python tools/telemetry_report.py path --markdown   # PERF.md tables
    python tools/telemetry_report.py path --json       # one JSON line

``render()`` is importable (the docs snippet and tests call it directly).
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
from deepspeed_tpu.telemetry.metrics import Histogram  # noqa: E402


def _fmt_bytes(n) -> str:
    if n is None:
        return "-"
    n = float(n)
    for unit in ("B", "KB", "MB", "GB", "TB"):
        if abs(n) < 1024 or unit == "TB":
            return f"{n:.1f} {unit}" if unit != "B" else f"{int(n)} B"
        n /= 1024
    return f"{n:.1f} TB"


def aggregate(events: List[Dict]) -> Dict:
    """Collapse an event list into the per-section aggregates the report
    renders (also the ``--json`` payload)."""
    compile_by_name: Dict[str, Dict] = {}
    step_cost_by_name: Dict[str, Dict] = {}
    memory = {"samples": 0, "last": {}, "peak_bytes_in_use": 0,
              "max_host_rss": 0}
    trace_windows = []
    wallclock: Dict[str, List[float]] = {}
    steps = {"count": 0, "last": 0}
    faults = {"by_name": {}, "recent": []}
    router = {"replica_states": {}, "breaker": {"trips": 0, "probes": 0,
                                                "closes": 0},
              "failovers": 0, "tier_transitions": [], "last_tier": 0,
              "finished": 0, "shed": 0, "shed_reasons": {},
              "replay_divergence": 0, "events": 0}
    serving = {"events": 0, "finished": 0, "shed": 0, "prompt_tokens": 0,
               "prefix_hit_tokens": 0, "hit_requests": 0, "blocks_shared": 0,
               "prefill_chunks": 0, "last_gauges": {},
               "draft_tokens": 0, "accepted_tokens": 0, "spec_requests": 0}
    fleet = {"events": 0, "scale_ups": 0, "scale_downs": 0, "parks": 0,
             "drains_lost": 0, "drain_timeouts": 0, "factory_failures": 0,
             "decisions": [], "last_gauges": {}}
    gateway = {"events": 0, "tenants": {}}
    aot = {"events": 0, "hits": 0, "hit_programs": {}, "captured": 0,
           "captured_bytes": 0, "disabled": [], "load_failed": 0,
           "armed_programs": 0}
    tuning = {"events": 0, "trials": {}, "applied": {}}
    span_events = []
    for e in events:
        kind, name, data = e.get("kind"), e.get("name"), e.get("data", {})
        if kind == "compile":
            c = compile_by_name.setdefault(
                name, {"compiles": 0, "trace_secs": 0.0, "compile_secs": 0.0,
                       "retraces_after_warmup": 0})
            c["compiles"] += 1
            c["trace_secs"] += data.get("trace_secs", 0.0)
            c["compile_secs"] += data.get("compile_secs", 0.0)
            if data.get("retrace") and data.get("after_warmup"):
                c["retraces_after_warmup"] += 1
        elif kind == "step_cost":
            step_cost_by_name[name] = data  # once per compile; keep latest
        elif kind == "memory":
            memory["samples"] += 1
            memory["last"] = data
            memory["peak_bytes_in_use"] = max(
                memory["peak_bytes_in_use"],
                data.get("peak_bytes_in_use", 0) or 0)
            memory["max_host_rss"] = max(
                memory["max_host_rss"], data.get("host_rss_bytes", 0) or 0)
        elif kind == "trace_window":
            trace_windows.append({"action": data.get("action"),
                                  "step": e.get("step"),
                                  "dir": data.get("dir")})
        elif kind == "wallclock":
            for k, v in data.items():
                if isinstance(v, (int, float)):
                    wallclock.setdefault(k, []).append(float(v))
        elif kind == "step":
            steps["count"] += 1
            steps["last"] = max(steps["last"], e.get("step") or 0)
        elif kind == "fault":
            faults["by_name"][name] = faults["by_name"].get(name, 0) + 1
            faults["recent"].append(
                {"name": name, "step": e.get("step"), **data})
            faults["recent"] = faults["recent"][-20:]
        elif kind == "router":
            router["events"] += 1
            if name == "replica.state":
                rep = str(data.get("replica"))
                router["replica_states"].setdefault(rep, []).append(
                    {"step": e.get("step"),
                     "to": data.get("to_state"),
                     "reason": data.get("reason")})
            elif name == "breaker.trip":
                router["breaker"]["trips"] += 1
            elif name == "breaker.probe":
                router["breaker"]["probes"] += 1
            elif name == "breaker.close":
                router["breaker"]["closes"] += 1
            elif name == "failover":
                router["failovers"] += 1
            elif name == "tier":
                router["tier_transitions"].append(
                    {"step": e.get("step"), "from": data.get("from_tier"),
                     "to": data.get("to_tier"), "score": data.get("score")})
                router["last_tier"] = data.get("to_tier", 0)
            elif name == "request.finish":
                router["finished"] += 1
            elif name == "request.shed":
                router["shed"] += 1
                reason = data.get("reason") or "?"
                router["shed_reasons"][reason] = \
                    router["shed_reasons"].get(reason, 0) + 1
            elif name == "replay.divergence":
                router["replay_divergence"] += 1
        elif kind == "serving":
            serving["events"] += 1
            if name == "request.finish":
                serving["finished"] += 1
                serving["prompt_tokens"] += data.get("prompt_len") or 0
                hit = data.get("prefix_hit_tokens") or 0
                serving["prefix_hit_tokens"] += hit
                if hit:
                    serving["hit_requests"] += 1
                serving["blocks_shared"] += data.get("blocks_shared") or 0
                serving["prefill_chunks"] += data.get("prefill_chunks") or 0
                drafts = data.get("draft_tokens") or 0
                serving["draft_tokens"] += drafts
                serving["accepted_tokens"] += \
                    data.get("accepted_tokens") or 0
                if drafts:
                    serving["spec_requests"] += 1
            elif name == "request.shed":
                serving["shed"] += 1
            elif name == "step.gauges":
                serving["last_gauges"] = data
        elif kind == "fleet":
            fleet["events"] += 1
            if name in ("scale.up", "scale.down"):
                key = "scale_ups" if name == "scale.up" else "scale_downs"
                fleet[key] += 1
                fleet["decisions"].append(
                    {"step": e.get("step"),
                     "action": name.split(".", 1)[1],
                     "reason": data.get("reason"),
                     "source": data.get("source"),
                     "from": data.get("from_size"),
                     "to": data.get("to_size")})
                fleet["decisions"] = fleet["decisions"][-20:]
            elif name == "replica.parked":
                fleet["parks"] += 1
            elif name == "drain.lost":
                fleet["drains_lost"] += 1
            elif name == "drain.timeout":
                fleet["drain_timeouts"] += 1
            elif name == "factory.failed":
                fleet["factory_failures"] += 1
            elif name == "fleet.gauges":
                fleet["last_gauges"] = data
        elif kind == "gateway":
            gateway["events"] += 1
            t = gateway["tenants"].setdefault(
                data.get("tenant") or "anonymous",
                {"finished": 0, "shed": 0, "rejected": 0, "tokens": 0,
                 "shed_reasons": {}, "reject_reasons": {},
                 "ttft_ms": [], "budget_remaining": None})
            if name == "request.finished":
                if data.get("outcome") == "ok":
                    t["finished"] += 1
                else:
                    t["shed"] += 1
                    reason = data.get("reason") or "?"
                    t["shed_reasons"][reason] = \
                        t["shed_reasons"].get(reason, 0) + 1
                t["tokens"] += data.get("tokens") or 0
                if data.get("ttft_ms") is not None:
                    t["ttft_ms"].append(float(data["ttft_ms"]))
                if data.get("budget_remaining") is not None:
                    t["budget_remaining"] = data["budget_remaining"]
            elif name == "request.rejected":
                t["rejected"] += 1
                reason = data.get("reason") or "?"
                t["reject_reasons"][reason] = \
                    t["reject_reasons"].get(reason, 0) + 1
        elif kind == "aot":
            aot["events"] += 1
            action = data.get("action")
            if action == "hit":
                aot["hits"] += 1
                aot["hit_programs"][name] = \
                    aot["hit_programs"].get(name, 0) + 1
            elif action == "armed":
                aot["armed_programs"] = data.get("programs", 0)
            elif action == "load_failed":
                aot["load_failed"] += 1
            elif name == "captured":
                aot["captured"] = data.get("programs", 0)
                aot["captured_bytes"] = data.get("bytes", 0)
            elif name == "disabled":
                aot["disabled"].append(
                    {"what": data.get("what"),
                     "reason": (data.get("reason") or "")[:120],
                     "step": e.get("step")})
        elif kind == "tuning":
            tuning["events"] += 1
            if name == "applied":
                tuning["applied"] = data
            else:
                ax = tuning["trials"].setdefault(name, [])
                ax.append({k: data.get(k) for k in
                           ("value", "objective", "score", "skipped",
                            "error") if data.get(k) is not None})
        elif kind == "span":
            span_events.append(e)
    for t in gateway["tenants"].values():
        ts = sorted(t.pop("ttft_ms"))
        t["ttft_ms_p50"] = round(ts[(len(ts) - 1) // 2], 3) if ts else None
        t["ttft_ms_p95"] = (round(ts[min(len(ts) - 1,
                                         int(0.95 * len(ts)))], 3)
                            if ts else None)
    return {
        "compile": compile_by_name,
        "step_cost": step_cost_by_name,
        "memory": memory,
        "trace_windows": trace_windows,
        "wallclock": {k: sum(v) / len(v) for k, v in wallclock.items()},
        "steps": steps,
        "faults": faults,
        "router": router,
        "fleet": fleet,
        "gateway": gateway,
        "serving": serving,
        "aot": aot,
        "tuning": tuning,
        "spans": _aggregate_spans(span_events),
    }


_STEP_PHASES = ("data", "fwd_bwd", "optimizer", "ckpt_io")


def _aggregate_spans(span_events: List[Dict]) -> Dict:
    """Span-trace aggregates: per-name duration histograms (fixed-bucket
    — constant memory over a long run), the per-step phase table, and
    waterfall data for the most recent request traces."""
    if not span_events:
        return {"count": 0}
    by_name: Dict[str, Histogram] = {}
    traces: Dict[str, List[Dict]] = {}
    for e in span_events:
        d = e.get("data", {})
        dur = max(int(d.get("end_ns", 0)) - int(d.get("start_ns", 0)), 0)
        h = by_name.get(e.get("name"))
        if h is None:  # setdefault would build a throwaway per event
            by_name[e.get("name")] = h = Histogram()
        h.observe(dur)
        traces.setdefault(str(d.get("trace")), []).append(e)
    steps, requests, startup = [], [], None
    for trace, evs in traces.items():
        root = next((e for e in evs
                     if e["data"].get("parent") is None), None)
        if root is None:
            continue
        d = root["data"]
        dur_ms = (int(d.get("end_ns", 0))
                  - int(d.get("start_ns", 0))) / 1e6
        if root["name"] == "step":
            row = {"step": d.get("step"),
                   "total_ms": round(dur_ms, 3),
                   "phases": {}}
            for e in evs:
                if e["name"] in _STEP_PHASES:
                    ph = e["data"]
                    ms = (int(ph.get("end_ns", 0))
                          - int(ph.get("start_ns", 0))) / 1e6
                    row["phases"][e["name"]] = round(
                        row["phases"].get(e["name"], 0.0) + ms, 3)
            steps.append(row)
        elif root["name"] in ("request", "serve", "startup"):
            row = {
                "trace": trace,
                "request_id": d.get("request_id"),
                "state": d.get("state"), "reason": d.get("reason"),
                "failovers": d.get("failovers"),
                "tokens": d.get("tokens"),
                "total_ms": round(dur_ms, 3),
                "spans": sorted(
                    ({"name": e["name"],
                      "span": e["data"].get("span"),
                      "parent": e["data"].get("parent"),
                      "start_ns": e["data"].get("start_ns"),
                      "end_ns": e["data"].get("end_ns"),
                      "attrs": {k: v for k, v in e["data"].items()
                                if k not in SPAN_META}}
                     for e in evs),
                    # parents first at equal starts (outermost = longest)
                    key=lambda s: (s["start_ns"], -(s["end_ns"] or 0))),
            }
            if root["name"] == "startup":
                # the process's start-up ledger: one trace a process
                startup = {**row, "ready_s": d.get("ready_s"),
                           "outside_s": d.get("outside_s")}
            else:
                requests.append(row)
    steps.sort(key=lambda r: r["step"] if r["step"] is not None else -1)
    return {
        "count": len(span_events),
        "by_name": {k: h.summary(scale=1e-6)
                    for k, h in sorted(by_name.items())},
        "steps": steps[-20:],
        "requests": requests[-5:],
        "startup": startup,
    }


def _serving_lines(agg: Dict, markdown: bool) -> List[str]:
    """Serving fast path: prefix-cache hit rate, block sharing, chunked
    prefill — the per-request ``serving`` event aggregates."""
    s = agg.get("serving") or {}
    if not s.get("events"):
        return []
    out = [""]
    head = (f"serving: {s['finished']} finished, {s['shed']} shed, "
            f"{s['prefill_chunks']} prefill chunks")
    out.append(("### " if markdown else "") + head)
    pad = "" if markdown else "  "
    if s["prompt_tokens"]:
        rate = s["prefix_hit_tokens"] / s["prompt_tokens"]
        out.append(
            f"{pad}prefix cache: {s['hit_requests']}/{s['finished']} "
            f"requests hit, {s['prefix_hit_tokens']}/{s['prompt_tokens']} "
            f"prompt tokens served from cache ({100 * rate:.1f}%), "
            f"{s['blocks_shared']} blocks mapped shared")
    if s.get("draft_tokens"):
        rate = s["accepted_tokens"] / s["draft_tokens"]
        out.append(
            f"{pad}speculation: {s['spec_requests']}/{s['finished']} "
            f"requests speculated, {s['accepted_tokens']}/"
            f"{s['draft_tokens']} draft tokens accepted "
            f"({100 * rate:.1f}%)")
    g = s.get("last_gauges") or {}
    if "cached_blocks" in g or "free_blocks" in g:
        out.append(f"{pad}pool at last step: "
                   f"{g.get('free_blocks', '?')} free blocks, "
                   f"{g.get('cached_blocks', 0)} cached")
    return out


def _router_lines(agg: Dict, markdown: bool) -> List[str]:
    """Multi-replica front door: replica state transitions, breaker
    activity, failovers, degradation-tier walks."""
    r = agg.get("router") or {}
    if not r.get("events"):
        return []
    out = [""]
    head = (f"router: {r['finished']} finished, {r['shed']} shed, "
            f"{r['failovers']} failovers, "
            f"{r['breaker']['trips']} breaker trips "
            f"({r['breaker']['probes']} probes, "
            f"{r['breaker']['closes']} closes), "
            f"tier {r['last_tier']}")
    out.append(("### " if markdown else "") + head)
    if r["replay_divergence"]:
        out.append(f"{'**' if markdown else '  '}REPLAY DIVERGENCE x"
                   f"{r['replay_divergence']} — greedy bit-reproducibility "
                   f"broken{'**' if markdown else ''}")
    if r["shed_reasons"]:
        sheds = ", ".join(f"{k}: {v}"
                          for k, v in sorted(r["shed_reasons"].items()))
        out.append(f"{'' if markdown else '  '}shed reasons: {sheds}")
    if markdown and r["replica_states"]:
        out.append("\n| replica | transitions |")
        out.append("|---|---|")
        for rep, ts in sorted(r["replica_states"].items()):
            chain = " -> ".join(f"{t['to']}({t['reason']})" for t in ts)
            out.append(f"| {rep} | {chain} |")
    elif r["replica_states"]:
        for rep, ts in sorted(r["replica_states"].items()):
            chain = " -> ".join(f"{t['to']}({t['reason']})" for t in ts)
            out.append(f"  replica {rep}: {chain}")
    for t in r["tier_transitions"][-5:]:
        out.append(f"{'' if markdown else '  '}tier {t['from']} -> "
                   f"{t['to']} at step {t['step']} (score {t['score']})")
    return out


def _prom_series(prom: Dict, name: str) -> List[Dict]:
    return (prom or {}).get(name, {}).get("series") or []


def _fleet_lines(agg: Dict, markdown: bool,
                 prom: Dict = None) -> List[str]:
    """Elastic fleet: scaling decisions, drains parked/lost, factory
    failures, and the last fleet gauge snapshot (per-state replica
    counts + SLO budget remaining). With ``--prom`` the error-budget
    numbers come from the registry snapshot — the autoscaler's own
    gauges — instead of being re-read from raw events."""
    f = agg.get("fleet") or {}
    if not f.get("events"):
        return []
    out = [""]
    head = (f"fleet: {f['scale_ups']} scale-up(s), "
            f"{f['scale_downs']} scale-down(s), {f['parks']} park(s)"
            + (f", {f['drains_lost']} drain(s) lost"
               if f.get("drains_lost") else "")
            + (f", {f['drain_timeouts']} drain timeout(s)"
               if f.get("drain_timeouts") else "")
            + (f", {f['factory_failures']} factory failure(s)"
               if f.get("factory_failures") else ""))
    out.append(("### " if markdown else "") + head)
    pad = "" if markdown else "  "
    g = f.get("last_gauges") or {}
    if g:
        states = g.get("by_state") or {}
        chain = ", ".join(f"{k}: {v}" for k, v in sorted(states.items())
                          if v)
        out.append(
            f"{pad}fleet at last step: {g.get('active', '?')} active of "
            f"{g.get('replicas', '?')} ({chain}), "
            f"{g.get('parked', 0)} parked, queue "
            f"{g.get('queue_depth', '?')}/{g.get('queue_capacity', '?')}, "
            f"overload {g.get('overload', '?')}")
        budget_rows = _prom_series(prom, "ds_slo_budget_remaining")
        if budget_rows:
            # the registry snapshot is the autoscaler's own gauge —
            # prefer it over re-reading the event stream
            out.append(f"{pad}SLO budget remaining (registry): "
                       + ", ".join(
                           f"{r['labels'].get('slo')}: {r.get('value')}"
                           for r in budget_rows))
            burn_rows = _prom_series(prom, "ds_slo_burn_rate")
            if burn_rows:
                out.append(f"{pad}SLO burn rates (registry): "
                           + ", ".join(
                               f"{r['labels'].get('slo')}/"
                               f"{r['labels'].get('window')}: "
                               f"{r.get('value')}"
                               for r in burn_rows))
        else:
            budget = g.get("budget_remaining") or {}
            if budget:
                out.append(f"{pad}SLO budget remaining: "
                           + ", ".join(f"{k}: {v}" for k, v in
                                       sorted(budget.items())))
    if markdown and f.get("decisions"):
        out.append("\n| step | action | reason | source | fleet |")
        out.append("|---|---|---|---|---|")
        for d in f["decisions"][-10:]:
            out.append(f"| {d['step']} | {d['action']} | {d['reason']} "
                       f"| {d.get('source') or '-'} "
                       f"| {d['from']} -> {d['to']} |")
    else:
        for d in (f.get("decisions") or [])[-10:]:
            out.append(f"{pad}step {d['step']}: {d['action']} "
                       f"({d['reason']}"
                       + (f", {d['source']}" if d.get("source") else "")
                       + f") {d['from']} -> {d['to']}")
    return out


def _gateway_lines(agg: Dict, markdown: bool,
                   prom: Dict = None) -> List[str]:
    """HTTP front door: per-tenant request/shed/reject counts, TTFT
    percentiles and error-budget remaining from the ``gateway`` event
    stream. With ``--prom`` the budget numbers come from the registry's
    own ``ds_gateway_budget_remaining`` gauge instead."""
    g = agg.get("gateway") or {}
    if not g.get("events"):
        return []
    tenants = g.get("tenants") or {}
    finished = sum(t["finished"] for t in tenants.values())
    shed = sum(t["shed"] for t in tenants.values())
    rejected = sum(t["rejected"] for t in tenants.values())
    out = [""]
    head = (f"gateway: {finished} finished, {shed} shed mid-stream, "
            f"{rejected} rejected at the door "
            f"({len(tenants)} tenant(s))")
    out.append(("### " if markdown else "") + head)
    pad = "" if markdown else "  "
    if markdown and tenants:
        out.append("\n| tenant | finished | shed | rejected | tokens "
                   "| ttft p50/p95 (ms) | budget left |")
        out.append("|---|---|---|---|---|---|---|")
        for name, t in sorted(tenants.items()):
            out.append(
                f"| {name} | {t['finished']} | {t['shed']} "
                f"| {t['rejected']} | {t['tokens']} "
                f"| {t['ttft_ms_p50']}/{t['ttft_ms_p95']} "
                f"| {t['budget_remaining']} |")
    else:
        for name, t in sorted(tenants.items()):
            out.append(
                f"{pad}tenant {name}: {t['finished']} finished, "
                f"{t['shed']} shed, {t['rejected']} rejected, "
                f"{t['tokens']} tokens, ttft p50/p95 "
                f"{t['ttft_ms_p50']}/{t['ttft_ms_p95']} ms, "
                f"budget left {t['budget_remaining']}")
    for name, t in sorted(tenants.items()):
        reasons = {**t["reject_reasons"], **t["shed_reasons"]}
        if reasons:
            chain = ", ".join(f"{k}: {v}"
                              for k, v in sorted(reasons.items()))
            out.append(f"{pad}{name} refusals: {chain}")
    budget_rows = _prom_series(prom, "ds_gateway_budget_remaining")
    if budget_rows:
        out.append(f"{pad}budget remaining (registry): "
                   + ", ".join(f"{r['labels'].get('tenant')}: "
                               f"{r.get('value')}"
                               for r in budget_rows))
    return out


def _prom_lines(prom: Dict, markdown: bool) -> List[str]:
    """Live metrics plane (``--prom``): one row per family from a
    registry snapshot (a ``metrics_dump.py --json`` payload, a
    ``telemetry.metrics_file`` / ``metrics.prom`` exposition text, or
    ``MetricRegistry.snapshot()`` JSON)."""
    if not prom:
        return []
    out = [""]
    out.append(("### " if markdown else "")
               + f"metrics registry: {len(prom)} families")
    pad = "" if markdown else "  "
    if markdown:
        out.append("\n| metric | type | series | value(s) |")
        out.append("|---|---|---|---|")
    for name in sorted(prom):
        fam = prom[name] or {}
        series = fam.get("series") or []
        vals = []
        for row in series[:4]:
            labels = row.get("labels") or {}
            tag = ",".join(f"{k}={v}" for k, v in sorted(labels.items()))
            if "count" in row or "counts" in row:
                v = f"count={row.get('count', 0)}"
            else:
                v = row.get("value")
            vals.append(f"{tag}: {v}" if tag else f"{v}")
        more = f" (+{len(series) - 4} more)" if len(series) > 4 else ""
        if markdown:
            out.append(f"| `{name}` | {fam.get('type', '?')} "
                       f"| {len(series)} | {'; '.join(map(str, vals))}"
                       f"{more} |")
        else:
            out.append(f"{pad}{name} [{fam.get('type', '?')}]: "
                       + "; ".join(map(str, vals)) + more)
    return out


def _flightrec_lines(dump_dirs: List[str], markdown: bool) -> List[str]:
    """Flight recorder: one block per dump dir — trigger reason, ring
    counts, the event tail, and whether a metrics exposition rode
    along."""
    from deepspeed_tpu.telemetry.flightrec import load_dump

    out = []
    pad = "" if markdown else "  "
    for path in dump_dirs:
        d = load_dump(path)
        meta = d.get("meta") or {}
        out.append("")
        out.append(("### " if markdown else "")
                   + f"flight recorder dump: {os.path.basename(path)}")
        out.append(f"{pad}reason: {meta.get('reason')} | "
                   f"{meta.get('events', len(d['events']))} event(s), "
                   f"{meta.get('snapshots', len(d['snapshots']))} metric "
                   f"snapshot(s), last step {meta.get('last_step')}"
                   + (" | metrics.prom attached"
                      if d.get("metrics_text") else ""))
        trigger = meta.get("trigger") or {}
        if trigger:
            out.append(f"{pad}trigger event: {trigger.get('kind')}/"
                       f"{trigger.get('name')} at step "
                       f"{trigger.get('step')}")
        tail = d["events"][-8:]
        if tail:
            out.append(f"{pad}event tail:")
            for e in tail:
                out.append(f"{pad}  step {e.get('step')}: "
                           f"{e.get('kind')}/{e.get('name')}")
        snaps = d.get("snapshots") or []
        if snaps:
            last = snaps[-1].get("snapshot") or {}
            out.append(f"{pad}last metric snapshot (step "
                       f"{snaps[-1].get('step')}): "
                       f"{len(last)} families")
    return out


def _fault_lines(agg: Dict, markdown: bool) -> List[str]:
    """Resilience-layer faults: checkpoint retries/fallbacks, sentinel
    trips/rollbacks, watchdog hang dumps."""
    faults = agg.get("faults") or {"by_name": {}, "recent": []}
    if not faults["by_name"]:
        return []
    out = []
    if markdown:
        out.append("\nFaults (resilience layer):\n")
        out.append("| fault | count |")
        out.append("|---|---|")
        for name, count in sorted(faults["by_name"].items()):
            out.append(f"| `{name}` | {count} |")
    else:
        out.append("")
        out.append("faults (resilience layer):")
        for name, count in sorted(faults["by_name"].items()):
            out.append(f"  {name:<44}{count:>9}")
    for f in faults["recent"][-5:]:
        detail = ", ".join(f"{k}={v}" for k, v in f.items()
                           if k not in ("name", "step") and v is not None)
        out.append(f"{'' if markdown else '  '}last: {f['name']} at step "
                   f"{f.get('step')}" + (f" ({detail})" if detail else ""))
    return out


def _aot_lines(agg: Dict, markdown: bool) -> List[str]:
    """AOT program cache: capture/arm/hit accounting + every loud
    ``disabled`` record (compat gate, identity mismatch)."""
    a = agg.get("aot") or {}
    if not a.get("events"):
        return []
    out = [""]
    head = (f"aot: {a['hits']} warm dispatch hit(s), "
            f"{a['armed_programs']} program(s) armed, "
            f"{a['captured']} captured"
            + (f" ({a['captured_bytes']:,} bytes)" if a.get("captured_bytes")
               else "")
            + (f", {a['load_failed']} load failure(s)"
               if a.get("load_failed") else ""))
    out.append(("### " if markdown else "") + head)
    pad = "" if markdown else "  "
    for name, n in sorted((a.get("hit_programs") or {}).items()):
        out.append(f"{pad}hit: {name} x{n}")
    for d in a.get("disabled") or []:
        out.append(f"{pad}DISABLED ({d.get('what')}): {d.get('reason')}")
    return out


def _tuning_lines(agg: Dict, markdown: bool,
                  tuned_artifact: Dict = None) -> List[str]:
    """Live-autotuner trials from the event stream, plus (``--tuned``)
    the artifact's chosen values with their measurement evidence."""
    t = agg.get("tuning") or {}
    if not t.get("events") and not tuned_artifact:
        return []
    out = [""]
    out.append(("### " if markdown else "") + "tuning:")
    pad = "" if markdown else "  "
    applied = t.get("applied") or {}
    if applied:
        ops = applied.get("ops") or {}
        out.append(f"{pad}applied at engine build: "
                   + (", ".join(f"{k}={v}" for k, v in sorted(ops.items()))
                      or "(config-section values only)")
                   + f" [tuned_hash {applied.get('tuned_hash')}]")
    for axis, trials in sorted((t.get("trials") or {}).items()):
        rendered = ", ".join(
            (f"{tr.get('value')}: skipped ({tr['skipped']})"
             if tr.get("skipped") else
             f"{tr.get('value')}: ERROR" if tr.get("error") else
             f"{tr.get('value')}: {tr.get('score')}")
            for tr in trials)
        out.append(f"{pad}{axis}: {rendered}")
    if tuned_artifact:
        axes = tuned_artifact.get("axes") or {}
        if markdown:
            out.append("\n| axis | chosen | objective | score | trials |")
            out.append("|---|---|---|---|---|")
            for name, ax in sorted(axes.items()):
                out.append(f"| `{name}` | {ax.get('value')} | "
                           f"{ax.get('objective')}"
                           f"{' (min)' if ax.get('minimize') else ''} | "
                           f"{ax.get('score')} | "
                           f"{len(ax.get('evidence') or [])} |")
        else:
            out.append(f"{pad}tuned artifact "
                       f"[{tuned_artifact.get('fingerprint_hash')}]:")
            for name, ax in sorted(axes.items()):
                out.append(f"{pad}  {name}: chose {ax.get('value')!r} "
                           f"({ax.get('objective')}={ax.get('score')}, "
                           f"{len(ax.get('evidence') or [])} trial(s))")
                for tr in (ax.get("evidence") or []):
                    if "skipped" in tr:
                        out.append(f"{pad}    {tr.get('value')!r}: skipped "
                                   f"— {tr['skipped']}")
                    elif "error" in tr:
                        out.append(f"{pad}    {tr.get('value')!r}: ERROR "
                                   f"— {tr['error'][:80]}")
                    else:
                        m = tr.get("measurements") or {}
                        score = m.get(ax.get("objective"))
                        out.append(f"{pad}    {tr.get('value')!r}: "
                                   f"{ax.get('objective')}={score}")
    return out


def _waterfall_lines(req: Dict, pad: str) -> List[str]:
    """One request trace as an indented causal waterfall (offsets are ms
    from the root span's start)."""
    spans = req.get("spans") or []
    if not spans:
        return []
    t0 = min(s["start_ns"] for s in spans)
    width = max(14, *(len(s["name"]) for s in spans))
    depth = {}
    parents = {s["span"]: s["parent"] for s in spans}
    for s in spans:
        d, p = 0, s["parent"]
        while p is not None and d < 8:
            d += 1
            p = parents.get(p)
        depth[s["span"]] = d
    out = []
    for s in spans:
        off = (s["start_ns"] - t0) / 1e6
        dur = (s["end_ns"] - s["start_ns"]) / 1e6
        hot = {k: v for k, v in (s.get("attrs") or {}).items()
               if k in ("attempt", "replica", "slot", "tokens", "reason",
                        "state", "outcome", "from_pos", "to_pos", "bucket",
                        "pos", "proposed", "accepted", "proposer",
                        "program")}
        detail = (" " + " ".join(f"{k}={v}" for k, v in hot.items())
                  if hot else "")
        out.append(f"{pad}{'  ' * depth[s['span']]}{s['name']:<{width}} "
                   f"+{off:8.2f} ms  {dur:8.2f} ms{detail}")
    return out


def _span_lines(agg: Dict, markdown: bool) -> List[str]:
    """Trace summary: per-span-name latency histograms, the per-step
    phase table, and per-request waterfalls."""
    s = agg.get("spans") or {}
    if not s.get("count"):
        return []
    out = [""]
    out.append(("### " if markdown else "")
               + f"tracing: {s['count']} spans")
    pad = "" if markdown else "  "
    by_name = s.get("by_name") or {}
    if by_name:
        if markdown:
            out.append("\n| span | count | p50 ms | p95 ms | max ms |")
            out.append("|---|---|---|---|---|")
            for name, h in by_name.items():
                out.append(f"| `{name}` | {h['count']} | {h.get('p50')} | "
                           f"{h.get('p95')} | {h.get('max')} |")
        else:
            out.append(f"{pad}{'span':<16}{'count':>7}{'p50 ms':>10}"
                       f"{'p95 ms':>10}{'max ms':>10}")
            for name, h in by_name.items():
                out.append(f"{pad}{name:<16}{h['count']:>7}"
                           f"{h.get('p50'):>10}{h.get('p95'):>10}"
                           f"{h.get('max'):>10}")
    steps = s.get("steps") or []
    if steps:
        phases = sorted({p for r in steps for p in r["phases"]})
        head = ["step", "total ms"] + [f"{p} ms" for p in phases]
        out.append("")
        if markdown:
            out.append("| " + " | ".join(head) + " |")
            out.append("|" + "---|" * len(head))
        else:
            out.append(pad + "per-step phases "
                       "(host-side dispatch walltime):")
            out.append(pad + "  ".join(f"{h:>12}" for h in head))
        for r in steps:
            cells = ([str(r["step"]), f"{r['total_ms']}"]
                     + [str(r["phases"].get(p, "-")) for p in phases])
            if markdown:
                out.append("| " + " | ".join(cells) + " |")
            else:
                out.append(pad + "  ".join(f"{c:>12}" for c in cells))
    if s.get("startup"):
        st = s["startup"]
        out.append("")
        out.append(pad + f"start-up: {st.get('ready_s')} s from the "
                   f"process's start to ready, {st.get('outside_s')} s of "
                   "it outside every bracket")
        out.extend(_waterfall_lines(st, pad))
    for req in (s.get("requests") or [])[-3:]:
        out.append("")
        head = (f"request {req.get('request_id') or req['trace']}: "
                f"{req.get('state')} ({req.get('reason')}), "
                f"{req.get('tokens')} token(s), "
                f"{req.get('failovers') or 0} failover(s), "
                f"{req['total_ms']} ms")
        out.append(pad + head)
        out.extend(_waterfall_lines(req, pad))
    return out


def _compile_table(agg: Dict, markdown: bool) -> List[str]:
    rows = sorted(agg["compile"].items())
    if not rows:
        return ["  (no compile events)"]
    out = []
    if markdown:
        out.append("| program | compiles | trace s | compile s | "
                   "retraces after warmup |")
        out.append("|---|---|---|---|---|")
        for name, c in rows:
            out.append(f"| `{name}` | {c['compiles']} | "
                       f"{c['trace_secs']:.2f} | {c['compile_secs']:.2f} | "
                       f"{c['retraces_after_warmup']} |")
    else:
        out.append(f"  {'program':<44}{'compiles':>9}{'trace s':>9}"
                   f"{'compile s':>11}{'retraces(warm)':>15}")
        for name, c in rows:
            out.append(f"  {name:<44}{c['compiles']:>9}"
                       f"{c['trace_secs']:>9.2f}{c['compile_secs']:>11.2f}"
                       f"{c['retraces_after_warmup']:>15}")
    return out


def _step_cost_lines(agg: Dict, markdown: bool) -> List[str]:
    out = []
    if not agg["step_cost"]:
        return ["  (no step_cost events)"]
    if markdown:
        out.append("| program | GFLOPs | collective bytes/member | "
                   "collectives | temp bytes |")
        out.append("|---|---|---|---|---|")
    for name, d in sorted(agg["step_cost"].items()):
        colls = d.get("collectives", {}) or {}
        coll_str = ", ".join(
            f"{op} x{v['count']} ({'+'.join(v.get('dtypes', []))})"
            for op, v in sorted(colls.items())) or "-"
        flops = d.get("flops")
        gflops = f"{flops / 1e9:.3f}" if flops is not None else "-"
        if markdown:
            out.append(
                f"| `{name}` | {gflops} | "
                f"{d.get('collective_operand_bytes', 0):,} | {coll_str} | "
                f"{d.get('temp_size_in_bytes', 0):,} |")
        else:
            out.append(f"  {name}")
            out.append(f"    flops: {gflops} GFLOP | bytes accessed: "
                       f"{_fmt_bytes(d.get('bytes_accessed'))}")
            out.append(
                "    memory: args "
                f"{_fmt_bytes(d.get('argument_size_in_bytes'))} | out "
                f"{_fmt_bytes(d.get('output_size_in_bytes'))} | temp "
                f"{_fmt_bytes(d.get('temp_size_in_bytes'))} | peak est "
                f"{_fmt_bytes(d.get('peak_bytes_estimate'))}")
            out.append(f"    collectives: {coll_str} | operand bytes/member "
                       f"{d.get('collective_operand_bytes', 0):,}")
    return out


def render(path: str, markdown: bool = False,
           tuned_artifact: Dict = None, prom: Dict = None,
           flightrec: List[str] = None) -> str:
    events = load_all_events(path)
    agg = aggregate(events)
    if flightrec is None:
        # auto-discover dumps the flight recorder left next to the sink
        from deepspeed_tpu.telemetry.flightrec import find_dumps

        flightrec = find_dumps(os.path.dirname(path) or ".")
    lines = []
    title = (f"Telemetry report — {os.path.basename(path)} "
             f"({len(events)} events, {agg['steps']['count']} steps)")
    if markdown:
        lines.append(f"### {title}\n")
        lines.append("Compile watchdog (per jitted program):\n")
        lines.extend(_compile_table(agg, True))
        lines.append("\nStatic step cost (once per compile, from the "
                     "compiled executable):\n")
        lines.extend(_step_cost_lines(agg, True))
    else:
        lines.append(title)
        lines.append("")
        lines.append("compile watchdog:")
        lines.extend(_compile_table(agg, False))
        lines.append("")
        lines.append("static step cost:")
        lines.extend(_step_cost_lines(agg, False))
    mem = agg["memory"]
    lines.append("")
    lines.append(
        f"{'### ' if markdown else ''}memory: {mem['samples']} samples | "
        f"peak device {_fmt_bytes(mem['peak_bytes_in_use'])} "
        f"({mem['last'].get('source', '?')}) | peak host RSS "
        f"{_fmt_bytes(mem['max_host_rss'])}")
    if agg["wallclock"]:
        wc = " | ".join(f"{k}: {v:.2f}"
                        for k, v in agg["wallclock"].items())
        lines.append(f"wallclock means (ms): {wc}")
    for w in agg["trace_windows"]:
        lines.append(f"trace window: {w['action']} at step {w['step']}"
                     + (f" -> {w['dir']}" if w.get("dir") else ""))
    lines.extend(_fault_lines(agg, markdown))
    lines.extend(_serving_lines(agg, markdown))
    lines.extend(_router_lines(agg, markdown))
    lines.extend(_fleet_lines(agg, markdown, prom))
    lines.extend(_gateway_lines(agg, markdown, prom))
    lines.extend(_span_lines(agg, markdown))
    lines.extend(_prom_lines(prom, markdown))
    lines.extend(_flightrec_lines(flightrec or [], markdown))
    lines.extend(_aot_lines(agg, markdown))
    lines.extend(_tuning_lines(agg, markdown, tuned_artifact))
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("path", help="telemetry.jsonl file (or its directory)")
    ap.add_argument("--markdown", action="store_true",
                    help="emit markdown tables (for PERF.md)")
    ap.add_argument("--json", action="store_true",
                    help="emit one JSON line of the aggregates")
    ap.add_argument("--tuned", default=None,
                    help="tuned.json artifact: render the live-tuner "
                         "trial measurements alongside the event stream")
    ap.add_argument("--prom", default=None,
                    help="metrics-plane snapshot: exposition text "
                         "(telemetry.metrics_file / a flight recorder's "
                         "metrics.prom) or snapshot JSON "
                         "(metrics_dump.py --json) — renders a metrics "
                         "section and feeds the fleet section's "
                         "error-budget gauges")
    ap.add_argument("--flightrec", action="append", default=None,
                    help="flight-recorder dump dir (flightrec-<ts>) to "
                         "render; repeatable. Default: auto-discover "
                         "next to the sink")
    args = ap.parse_args(argv)
    path = args.path
    if os.path.isdir(path):
        path = os.path.join(path, "telemetry.jsonl")
    tuned = None
    if args.tuned:
        with open(args.tuned) as f:
            tuned = json.load(f)
    prom = None
    if args.prom:
        from deepspeed_tpu.telemetry.prom import snapshot_from_file

        prom = snapshot_from_file(args.prom)
    if args.json:
        payload = {"metric": "telemetry_report", "path": path,
                   **aggregate(load_all_events(path))}
        if tuned is not None:
            payload["tuned_artifact"] = tuned
        if prom is not None:
            payload["metrics_registry"] = prom
        print(json.dumps(payload, default=str))
    else:
        print(render(path, markdown=args.markdown, tuned_artifact=tuned,
                     prom=prom, flightrec=args.flightrec))


if __name__ == "__main__":
    main()
