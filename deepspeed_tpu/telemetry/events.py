"""Telemetry event model.

One event = one JSON-serializable dict with a fixed envelope::

    {"ts": <unix seconds>, "kind": <family>, "name": <emitter>,
     "step": <global step or None>, "rank": <process index>, "data": {...}}

The four collector families the unified stream carries (plus the
satellite families that ride the same sink):

- ``compile``      — per-jitted-function compile wall time / retrace marks
                     (compile watchdog)
- ``step_cost``    — once-per-compile static cost model: FLOPs, collective
                     wire bytes, executable memory analysis
- ``memory``       — device/host memory stats sampled at step boundaries
- ``trace_window`` — jax.profiler trace start/stop markers
- ``step``         — step-boundary counters (samples, micro steps)
- ``wallclock``    — wall_clock_breakdown timer means (legacy flag routed
                     through the stream)
- ``comm``         — facade-level collective log mirrors
- ``fault``        — resilience-layer faults: checkpoint retries /
                     corruption / fallbacks / retention, sentinel trips
                     and rollbacks, watchdog hang dumps
- ``serving``      — per-request serving lifecycle: queued / finish
                     (TTFT, queue wait, tokens/s) / shed (reason)
- ``model_time``   — inference per-forward latencies (the
                     ``model_times()`` buffer mirrored into the stream)
- ``topology``     — checkpoint restores: saved vs. current mesh/world,
                     whether the load resharded (elastic resume)
- ``router``       — multi-replica front door: replica state / breaker /
                     failover / degradation-tier transitions
- ``aot``          — AOT program cache: store armed / per-program hits /
                     disabled (compat gate, identity mismatch) /
                     capture + load failures
- ``tuning``       — live-autotuner trials (axis, candidate value,
                     objective score / skip reason) and the tuned
                     values an engine applied at build
- ``span``         — causal tracing (``telemetry/tracing.py``): one
                     completed span per event — ``data`` carries
                     ``trace``/``span``/``parent`` ids plus
                     ``start_ns``/``end_ns`` monotonic bounds; the span
                     *name* must come from :data:`SPANS` (GL05 pins the
                     literals, same convention as ``KINDS``)
- ``fleet``        — elastic fleet manager: scale up/down decisions,
                     drains parked/lost/timed out, factory builds and
                     failures, per-step fleet gauges (replica-state
                     counts + SLO budget remaining)
- ``gateway``      — HTTP/SSE front door: per-tenant admission
                     (authorized / rejected with status + reason),
                     quota sheds (rate / tokens / inflight), stream
                     delivery outcomes, error-budget burn samples

Everything in ``data`` must be JSON-safe; :func:`json_safe` coerces numpy
scalars and drops device arrays (an event must never pin or sync device
buffers — the stream is passive by contract).
"""

import json
import os
import time
from typing import Any, Dict, Optional

KINDS = ("compile", "step_cost", "memory", "trace_window", "step",
         "wallclock", "comm", "fault", "serving", "model_time", "topology",
         "router", "aot", "tuning", "span", "fleet", "gateway")

# Registered span names (the ``span`` kind's analog of KINDS): the report
# tool groups phase tables and waterfalls by these literals and the
# Perfetto export categorizes by them, so an unregistered name is a span
# that renders in no summary. graft-lint GL05 reads this tuple from the
# AST and pins every literal span-name emit site against it.
SPANS = (
    # client/router level: one trace per request
    "request",        # root — submit to finish/shed, across failovers
    "attempt",        # one dispatch to one replica (attrs: attempt, replica)
    "deliver",        # tokens streamed to the client: by one attempt
    #                   (router), or first -> last flushed token event of
    #                   one HTTP reply (gateway; attrs: tokens,
    #                   egress_mean_ms, egress_max_ms)
    # gateway (HTTP front door) level: one trace per sampled HTTP request
    "gateway",        # root — handler accepted -> response flushed
    #                   (attrs: tenant, route, status, outcome, tokens)
    "ingress",        # accept -> backend.submit returned, or the door
    #                   refused (attrs: tenant, outcome)
    "quota",          # token-bucket/inflight admission decision, inside
    #                   ingress (attrs: tenant, outcome)
    # replica/serving-engine level
    "serve",          # one replica serving one attempt (engine-side root)
    "queue",          # submit/dispatch -> decode-slot admission
    "prefill",        # whole-prompt bucketed prefill (legacy path)
    "prefill_chunk",  # one chunked/tail prefill program call
    "cow",            # copy-on-write block copy before a shared-tail append
    "decode",         # first generated token -> finish (one decode segment)
    "draft",          # speculative proposer call (host-side, per request)
    "verify",         # the shared k-token verify dispatch, per-request view
    "spec_commit",    # accepted-prefix commit + rejected-tail drop
    "shed",           # admission/deadline shed (zero-work terminal span)
    # serving step level: one trace per scheduler iteration that did work
    "serve_step",     # root — first phase start -> last phase end
    #                   (attrs: step, busy, queue_depth)
    "schedule",       # deadline sweep + admission, host only
    "decode_step",    # the decode (or verify) program call + its host
    #                   sync for the whole slot batch (attrs: active)
    "emit",           # finish logic + stream callbacks of the step's tokens
    "autoscale",      # one fleet scaling action: decision -> executed
    #                   (attrs: action, reason, from_size, to_size, source)
    "migrate",        # one live KV-block migration: export -> transfer ->
    #                   import-commit (attrs: src, dst, reason, outcome,
    #                   blocks, wire_bytes)
    # training step level: one trace per optimizer step
    "step",           # root — first observed phase -> step boundary
    "data",           # host-side batch fetch/assembly
    "fwd_bwd",        # fused forward+backward(+in-graph reduce) dispatch
    "optimizer",      # optimizer apply dispatch
    "ckpt_io",        # checkpoint save/load IO (own trace, between steps)
    # process level (telemetry/process_ledger.py): one trace a process,
    # emitted at ``ready`` from the timestamps the start-up ledger kept
    "startup",        # root — the process's start (as the OS has it) ->
    #                   ready (attrs: ready_s, outside_s)
    "startup.import",          # deepspeed_tpu/__init__.py, first -> last line
    "startup.inference_init",  # InferenceEngine.__init__
    "startup.serving_init",    # ServingEngine.__init__
    "startup.pool",            # the KV pool's allocation (in serving_init)
    "startup.weight_layouts",  # weights laid out as decode asks (likewise)
    "startup.gateway_start",   # ServingGateway.start()
    "startup.initialize",      # DeepSpeedEngine.__init__
    "startup.params",          # sharded parameter init
    "startup.state",           # optimizer/train state build
    "startup.program",         # one program's FIRST call: build -> first
    #                            result on the host (attrs: program)
)

# the span event envelope's reserved ``data`` keys — everything else in
# a span's data is a user attribute (report tables and the Perfetto
# export both split on this; one definition so they cannot drift)
SPAN_META = ("trace", "span", "parent", "start_ns", "end_ns")


def json_safe(value: Any):
    """Coerce ``value`` to something ``json.dumps`` accepts: numpy/jax
    scalars via ``.item()``, sets/tuples to lists, everything else that
    fails a probe to ``repr``. Never calls ``float()`` on a device array
    of nonzero rank (that would be a hidden device sync on a live
    computation) — those become their repr."""
    if value is None or isinstance(value, (bool, int, float, str)):
        return value
    if isinstance(value, dict):
        return {str(k): json_safe(v) for k, v in value.items()}
    if isinstance(value, (list, tuple, set, frozenset)):
        return [json_safe(v) for v in value]
    shape = getattr(value, "shape", None)
    if shape == () and hasattr(value, "item"):
        try:
            return value.item()
        except Exception:
            return repr(value)
    return repr(value)


def make_event(kind: str, name: str, step: Optional[int], rank: int,
               data: Dict[str, Any]) -> Dict[str, Any]:
    return {
        "ts": round(time.time(), 6),
        "kind": kind,
        "name": name,
        "step": None if step is None else int(step),
        "rank": int(rank),
        "data": json_safe(data or {}),
    }


def dumps(event: Dict[str, Any]) -> str:
    return json.dumps(event, separators=(",", ":"), sort_keys=False)


def load_events(path: str):
    """Parse a JSONL sink file back into event dicts (report-tool side).
    Malformed lines — a truncated tail from a crash, or an interleaved
    partial line from concurrent writers — are skipped, not treated as
    end-of-file: everything parseable after them still counts."""
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                out.append(json.loads(line))
            except json.JSONDecodeError:
                continue
    return out


def segment_paths(path: str):
    """Every on-disk segment of a (possibly rotated) JSONL sink, oldest
    first: ``telemetry.jsonl.K`` .. ``telemetry.jsonl.1`` then the live
    ``telemetry.jsonl``. Rotation (``telemetry.rotate_bytes``) shifts
    ``.k`` -> ``.k+1`` so higher suffixes are older."""
    numbered = []
    k = 1
    while os.path.exists(f"{path}.{k}"):
        numbered.append(f"{path}.{k}")
        k += 1
    out = list(reversed(numbered))
    if os.path.exists(path):
        out.append(path)
    return out


def load_all_events(path: str):
    """Parse a JSONL sink *including its rotated segments* back into one
    chronological event list (the report/export tools' entry point — a
    long serving run must not lose its early events to rotation)."""
    out = []
    for p in segment_paths(path):
        out.extend(load_events(p))
    return out
