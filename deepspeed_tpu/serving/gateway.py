"""Multi-tenant HTTP/SSE front door for the serving stack.

:class:`ServingGateway` wraps ANY ``submit()/step()/drain()`` backend —
a ``FleetManager``, a ``ReplicaRouter`` or a bare ``ServingEngine`` —
behind a stdlib :class:`http.server.ThreadingHTTPServer` (the PR 14
metrics-server pattern: daemon threads, ephemeral ``port=0``,
deterministic ``close()``):

- ``POST /v1/generate`` — JSON in, SSE token stream out (``event:
  token`` per generated token, a terminal ``event: done`` carrying the
  backend record, or a typed ``event: error`` when the request was shed
  mid-stream); ``"stream": false`` selects a non-streaming JSON reply.
- ``GET /healthz`` — backend liveness + gauges.
- ``GET /metrics`` — the existing exposition, mounted on the same port.

Tenancy rides ``serving.gateway``: API-key identity, token-bucket rate
limits and inflight quotas (``tenancy.py``), SLO classes mapped onto the
scheduler's priority floor and deadline defaults. Overload answers 429/
503 with ``Retry-After`` instead of hanging sockets. Delivery is
decoupled from the step loop by a BOUNDED per-connection send queue: the
stream callback (step thread) never blocks — a slow reader overflows its
own queue and sheds that request only, via the backend ``cancel()``
seam, drained at the next :meth:`ServingGateway.step`.

The front door measures itself, always on (no config key): four stamps
a request on the gateway's one clock (``accept`` in the handler's
``setup()``, ``submitted`` when ``backend.submit`` returned,
``first_emit`` on the step thread, ``first_flush`` when the first token
event's flush returned) and a time on every token handed over. They
complete the record the ``done`` event carries (``ingress_ms``,
``first_egress_ms``, ``ttft_wire_ms``, ``egress_mean_ms``,
``egress_max_ms``, ``write_ms``), are what ``ds_gateway_ttft_ms`` and a
tenant's SLO outcome observe, and add up in ``stats()["front_door"]``.

Pure host code: never imports jax (GL01) and reads only the injected
clock (GL07) — the trace-replay harness runs the whole front door on
simulated time, bit-deterministically.
"""

import json
import queue
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, List, Optional, Tuple

from deepspeed_tpu.serving.config import GatewayConfig
from deepspeed_tpu.serving.tenancy import Tenant, TenantTable
from deepspeed_tpu.telemetry import process_ledger
from deepspeed_tpu.telemetry.registry import NULL_REGISTRY
from deepspeed_tpu.telemetry.prom import CONTENT_TYPE
from deepspeed_tpu.telemetry.tracing import (NULL_TRACER, Brackets,
                                             end_span, span_id, to_ns,
                                             trace_ctx)

GENERATE_ROUTE = "/v1/generate"

# admission reason -> HTTP status
_REASON_STATUS = {
    "auth": 401, "forbidden": 403, "bad_request": 400, "too_large": 413,
    "sampling_invalid": 400,
    "rate": 429, "tokens": 429, "inflight": 429, "overload": 503,
    "backend_shed": 503,
}


def _validate_sampling(body: dict) -> Optional[str]:
    """Range-check the keyed-sampling fields of a parsed request body.
    Returns a reject reason or None. Checked at the DOOR so an
    out-of-range temperature answers a typed 400, not a backend shed
    deep in the step loop."""
    if "do_sample" in body and not isinstance(body["do_sample"], bool):
        return "sampling_invalid"
    seed = body.get("seed")
    if seed is not None and (not isinstance(seed, int)
                             or isinstance(seed, bool) or seed < 0):
        return "sampling_invalid"
    temp = body.get("temperature")
    if temp is not None and (not isinstance(temp, (int, float))
                             or isinstance(temp, bool) or temp <= 0):
        return "sampling_invalid"
    top_k = body.get("top_k")
    if top_k is not None and (not isinstance(top_k, int)
                              or isinstance(top_k, bool) or top_k < 0):
        return "sampling_invalid"
    top_p = body.get("top_p")
    if top_p is not None and (not isinstance(top_p, (int, float))
                              or isinstance(top_p, bool)
                              or not 0.0 <= top_p <= 1.0):
        return "sampling_invalid"
    return None


class _NullTelemetry:
    enabled = False

    def emit(self, *a, **k):
        pass


def _ms(start: Optional[float], end: Optional[float]) -> Optional[float]:
    if start is None or end is None:
        return None
    return round(1e3 * (end - start), 3)


class _Stream:
    """Per-request delivery state shared between the step thread (the
    stream callback producing) and the handler thread (consuming), and
    the request's times at the front door, all on the gateway's clock.
    Each has ONE writer: ``first_ts`` the step thread, every other the
    request's own handler thread (``accept_ts`` / ``submitted_ts``
    before the step thread can emit; the rest in :meth:`delivered`)."""

    def __init__(self, request_id: str, maxsize: int, accept_ts: float):
        self.request_id = request_id
        self.q: "queue.Queue" = queue.Queue(maxsize=maxsize)
        self.accept_ts = accept_ts        # handler's setup(): thread runs
        self.submitted_ts: Optional[float] = None   # backend.submit returned
        self.first_ts: Optional[float] = None       # first token emitted
        self.first_flush_ts: Optional[float] = None
        self.last_flush_ts: Optional[float] = None
        self.flushed = 0            # token events written and flushed
        self.egress_secs = 0.0      # sum of flushed_at - emitted_at
        self.egress_max = 0.0
        self.write_secs = 0.0       # sum of the time inside sse_write
        self.tokens = 0
        self.overflow = False
        self.closed = False

    def delivered(self, flushed: int, first_flush: Optional[float],
                  last_flush: Optional[float], egress_secs: float,
                  egress_max: float, write_secs: float):
        """What the handler's write loop measured, kept in its locals
        while it ran: stored before the record or ``_finish`` read it."""
        self.flushed = flushed
        self.first_flush_ts, self.last_flush_ts = first_flush, last_flush
        self.egress_secs, self.egress_max = egress_secs, egress_max
        self.write_secs = write_secs

    def since_submit_ms(self) -> Optional[float]:
        """``first_emit - submitted``: TTFT as the engine's side of the
        door sees it, read on the step thread (deterministic under the
        replay harness' simulated time)."""
        if self.first_ts is None or self.submitted_ts is None:
            return None
        return 1e3 * max(self.first_ts - self.submitted_ts, 0.0)

    def record_fields(self) -> dict:
        """The gateway's fields of the request's record, as far as the
        request got. ``ttft_wire_ms = ingress_ms + (first_emit -
        submitted) + first_egress_ms``. A reply that streamed no token
        event (a JSON reply; a request shed before its first flush) has
        ``None`` in the egress fields and in ``ttft_wire_ms``."""
        n = self.flushed
        return {
            "ingress_ms": _ms(self.accept_ts, self.submitted_ts),
            "first_egress_ms": _ms(self.first_ts, self.first_flush_ts),
            "ttft_wire_ms": _ms(self.accept_ts, self.first_flush_ts),
            "egress_mean_ms": round(1e3 * self.egress_secs / n, 3)
            if n else None,
            "egress_max_ms": round(1e3 * self.egress_max, 3) if n else None,
            "write_ms": round(1e3 * self.write_secs, 3) if n else None,
        }


class ServingGateway:
    """The HTTP/SSE front door. Construct over a backend, ``start()``,
    then drive the backend loop through :meth:`step`/:meth:`drain` (or
    set ``serving.gateway.pump`` to own a daemon step loop)."""

    def __init__(self, backend, config=None, *, telemetry=None,
                 clock=time.monotonic):
        if isinstance(config, GatewayConfig):
            self.config = config
        else:
            self.config = GatewayConfig(**(config or {}))
        self.backend = backend
        self.clock = clock
        self.telemetry = (telemetry
                          or getattr(backend, "telemetry", None)
                          or _NullTelemetry())
        self._metrics = getattr(self.telemetry, "metrics", None) \
            or NULL_REGISTRY
        self._tracer = getattr(self.telemetry, "tracer", None) \
            or NULL_TRACER
        # the one bracket (ds.gateway.<phase> profiler annotations): the
        # backend's telemetry hands in the annotation factory; a backend
        # with none gets a bracket with no profiler sink (this module
        # never imports jax, GL01)
        # (its one ledger key, ``pump_idle``, is the process's: a step
        # loop takes it off the seam before a step, process_ledger.py)
        brackets = getattr(self.telemetry, "brackets", None)
        idle = process_ledger.LEDGER.seconds
        self._bracket = (brackets("gateway", clock=clock, ledger=idle)
                         if brackets
                         else Brackets("gateway", clock=clock, ledger=idle))
        self.tenants = TenantTable(self.config, clock=clock)
        self._routerlike = (hasattr(backend, "overload")
                            or hasattr(backend, "router"))
        self._lock = threading.Lock()
        self._streams: Dict[str, _Stream] = {}
        self._cancels: List[Tuple[str, str]] = []
        self._count = 0
        self._step_count = 0
        self._server: Optional[ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None
        self._pump_thread: Optional[threading.Thread] = None
        self._wake = threading.Event()
        self._running = False
        # per-tenant counters for stats()/bench (metrics may be off)
        self._counts: Dict[str, Dict[str, int]] = {}
        # the front door's own sums: each stream's added once, in
        # _finish, under the lock (never += from a handler's write loop)
        self._front_door = {"requests": 0, "tokens_written": 0,
                            "ingress_secs": 0.0, "write_secs": 0.0,
                            "egress_wait_secs": 0.0}

    # ------------------------------------------------------------------
    # lifecycle
    def start(self) -> "ServingGateway":
        if self._server is not None:
            return self
        with process_ledger.LEDGER.startup_bracket(
                "gateway_start", span="startup.gateway_start"):
            self._start()
        # serving is ready: the process's start-up ledger closes (the
        # first gateway of a process; later ones change nothing)
        process_ledger.LEDGER.ready("serving", self.telemetry)
        return self

    def _start(self):
        server = ThreadingHTTPServer((self.config.host, self.config.port),
                                     _Handler)
        server.daemon_threads = True
        server.gateway = self
        self._server = server
        self._running = True
        self._thread = threading.Thread(target=server.serve_forever,
                                        name="ds-gateway", daemon=True)
        self._thread.start()
        if self.config.pump:
            self._pump_thread = threading.Thread(
                target=self._pump, name="ds-gateway-pump", daemon=True)
            self._pump_thread.start()

    @property
    def port(self) -> int:
        return self._server.server_address[1] if self._server else 0

    @property
    def url(self) -> str:
        return f"http://{self.config.host}:{self.port}"

    def close(self):
        self._running = False
        self._wake.set()
        if self._pump_thread is not None:
            self._pump_thread.join(2.0)
            self._pump_thread = None
        if self._server is not None:
            self._server.shutdown()
            self._server.server_close()
            self._server = None
        if self._thread is not None:
            self._thread.join(2.0)
            self._thread = None

    def destroy(self):
        self.close()
        backend_destroy = getattr(self.backend, "destroy", None)
        if backend_destroy is not None:
            backend_destroy()

    def submit(self, prompt, **kwargs):
        """Direct Python-path passthrough — the backend surface stays
        reachable behind the gateway (no quotas, no HTTP)."""
        return self.backend.submit(prompt, **kwargs)

    # ------------------------------------------------------------------
    # step loop
    def step(self):
        """Drain deferred cancels (slow readers / disconnects, queued by
        handler threads where touching the scheduler would race the step
        loop), then advance the backend one step."""
        self._drain_cancels()
        self._step_count += 1
        return self.backend.step()

    def drain(self, max_steps: Optional[int] = None):
        self._drain_cancels()
        return self.backend.drain(max_steps)

    @property
    def pending(self) -> bool:
        return bool(getattr(self.backend, "pending", False))

    def _pump(self):
        while self._running:
            # nothing pending: idle for want of work, which a trace must
            # tell apart from a slow host loop
            with self._bracket("pump_idle", ledger="pump_idle"):
                self._wake.wait(self.config.poll_secs)
            # wake -> nothing pending: the longest of the nested brackets,
            # so it names an idle gap only where nothing inside it does
            # (the seam between two steps)
            with self._bracket("pump_turn"):
                self._wake.clear()
                while self._running and (self.pending or self._cancels):
                    self.step()

    def _drain_cancels(self):
        with self._lock:
            pending, self._cancels = self._cancels, []
        cancel = getattr(self.backend, "cancel", None)
        for request_id, reason in pending:
            if cancel is not None:
                cancel(request_id, reason)

    def _request_cancel(self, request_id: str, reason: str):
        with self._lock:
            self._cancels.append((request_id, reason))
        self._wake.set()

    # ------------------------------------------------------------------
    # accounting
    def _emit(self, name: str, **data):
        if getattr(self.telemetry, "enabled", False):
            self.telemetry.emit("gateway", name, step=self._step_count,
                                **data)

    def _bump(self, tenant: str, key: str, n: int = 1):
        with self._lock:
            row = self._counts.setdefault(tenant, {})
            row[key] = row.get(key, 0) + n

    def _reject(self, tenant_name: str, reason: str, status: int,
                trace=None):
        self._bump(tenant_name, "rejected")
        self._bump(tenant_name, f"http_{status}")
        self._metrics.counter("ds_gateway_requests_total",
                              labels=("tenant", "outcome")) \
            .labels(tenant=tenant_name, outcome="rejected").inc()
        self._metrics.counter("ds_gateway_rejects_total",
                              labels=("tenant", "reason")) \
            .labels(tenant=tenant_name, reason=reason).inc()
        self._emit("request.rejected", tenant=tenant_name, reason=reason,
                   status=status)
        if trace is not None:
            tid, root = trace
            now = self.clock()
            now_ns = to_ns(now)
            self._ingress_span(trace, now, tenant_name, reason)
            self._tracer.record_span("shed", tid, now_ns, now_ns,
                                     parent=span_id(root), reason=reason,
                                     tenant=tenant_name)
            end_span(root, end_ns=now_ns, status=status)

    def _ingress_span(self, trace, end_ts: float, tenant_name: str,
                      outcome: str):
        """accept (the root's start) -> the engine has the request, or
        the door refused it."""
        if trace is not None:
            tid, root = trace
            self._tracer.record_span("ingress", tid, root.start_ns,
                                     to_ns(end_ts), parent=span_id(root),
                                     tenant=tenant_name, outcome=outcome)

    def _finish(self, tenant: Tenant, stream: _Stream, outcome: str,
                reason: str = "", ttft_ms: Optional[float] = None,
                trace=None, status: int = 200):
        """Exactly-once terminal accounting for an admitted request."""
        if stream.closed:
            return
        stream.closed = True
        fields = stream.record_fields()
        with self._lock:
            self._streams.pop(stream.request_id, None)
            door = self._front_door
            door["requests"] += 1
            door["tokens_written"] += stream.flushed
            door["ingress_secs"] += stream.submitted_ts - stream.accept_ts
            door["write_secs"] += stream.write_secs
            door["egress_wait_secs"] += stream.egress_secs
        tenant.release()
        shed = outcome != "ok"
        tenant.record_outcome(shed, ttft_ms)
        self._bump(tenant.name, outcome)
        if not shed:
            self._bump(tenant.name, "finished")
        self._metrics.counter("ds_gateway_requests_total",
                              labels=("tenant", "outcome")) \
            .labels(tenant=tenant.name, outcome=outcome).inc()
        if stream.tokens:
            self._metrics.counter("ds_gateway_tokens_total",
                                  labels=("tenant",)) \
                .labels(tenant=tenant.name).inc(stream.tokens)
        if shed and reason:
            self._metrics.counter("ds_gateway_stream_sheds_total",
                                  labels=("tenant", "cause")) \
                .labels(tenant=tenant.name, cause=reason).inc()
        self._gauge_tenant(tenant)
        self._emit("request.finished", tenant=tenant.name, outcome=outcome,
                   reason=reason, request_id=stream.request_id,
                   tokens=stream.tokens, ttft_ms=ttft_ms,
                   budget_remaining=round(tenant.budget_remaining(), 6),
                   **fields)
        if trace is not None:
            tid, root = trace
            if stream.flushed:
                # one span a request, never one a token
                self._tracer.record_span(
                    "deliver", tid, to_ns(stream.first_flush_ts),
                    to_ns(stream.last_flush_ts), parent=span_id(root),
                    tokens=stream.flushed,
                    egress_mean_ms=fields["egress_mean_ms"],
                    egress_max_ms=fields["egress_max_ms"])
            end_span(root, end_ns=to_ns(self.clock()), status=status,
                     outcome=outcome, tokens=stream.tokens)

    def _gauge_tenant(self, tenant: Tenant):
        self._metrics.gauge("ds_gateway_inflight", labels=("tenant",)) \
            .labels(tenant=tenant.name).set(tenant.inflight)
        self._metrics.gauge("ds_gateway_budget_remaining",
                            labels=("tenant",)) \
            .labels(tenant=tenant.name).set(tenant.budget_remaining())

    def stats(self) -> dict:
        """Per-tenant gateway counters + budget remaining (host-side,
        independent of the metrics plane being armed)."""
        with self._lock:
            counts = {t: dict(row) for t, row in self._counts.items()}
            door = dict(self._front_door)
        # every admitted request that reached its end, and what its
        # handler spent: write_secs is wall time inside sse_write (the
        # work and the wait to get the interpreter lock back), so
        # write_secs / tokens_written x the tokens written a second is
        # the handler threads inside the bracket at any moment
        out = {"tenants": {},
               "front_door": {k: round(v, 6) if isinstance(v, float) else v
                              for k, v in door.items()}}
        for tenant in self.tenants.tenants:
            row = counts.get(tenant.name, {})
            row["inflight"] = tenant.inflight
            row["budget_remaining"] = round(tenant.budget_remaining(), 6)
            row["slo_class"] = tenant.slo_class
            out["tenants"][tenant.name] = row
        return out

    # ------------------------------------------------------------------
    # admission (handler thread)
    def _next_id(self) -> str:
        with self._lock:
            self._count += 1
            return f"gw-{self._count}"

    def authenticate(self, api_key: Optional[str]):
        """(tenant, error reason) — exactly one side is set."""
        if self.tenants.open:
            return self.tenants.resolve(None), ""
        if not api_key:
            return None, "auth"
        tenant = self.tenants.resolve(api_key)
        if tenant is None:
            return None, "forbidden"
        return tenant, ""

    def admit(self, tenant: Tenant, body: dict, accept_ts: float):
        """Quota + backend admission for a parsed, authenticated request
        whose handler began at ``accept_ts``. Returns ``(handle, stream,
        trace, retry_after, reason)`` — ``handle`` is None when
        rejected."""
        t0 = self.clock()
        trace = None
        if self._tracer.enabled and tenant.sample_trace():
            tid = self._tracer.new_trace(hint=tenant.name)
            root = self._tracer.begin("gateway", tid,
                                      start_ns=to_ns(accept_ts),
                                      tenant=tenant.name,
                                      route=GENERATE_ROUTE)
            trace = (tid, root)
        max_new = int(body.get("max_new_tokens", 0) or 0)
        overload = getattr(self.backend, "overload", None)
        threshold = self.config.overload_reject_threshold
        if (threshold > 0 and overload is not None
                and overload() >= threshold):
            self._reject(tenant.name, "overload", 503, trace)
            return None, None, None, self.config.retry_after_secs, \
                "overload"
        reason, wait = tenant.admit(est_tokens=float(max_new))
        if trace is not None:
            self._tracer.record_span("quota", trace[0], to_ns(t0),
                                     to_ns(self.clock()),
                                     parent=span_id(trace[1]),
                                     tenant=tenant.name,
                                     outcome=reason or "ok")
        if reason:
            self._reject(tenant.name, reason, 429, trace)
            return None, None, None, \
                max(wait, self.config.retry_after_secs), reason
        request_id = str(body.get("request_id") or self._next_id())
        stream = _Stream(request_id, self.config.send_queue_tokens,
                         accept_ts)
        kwargs: Dict[str, Any] = {
            "max_new_tokens": max_new,
            "request_id": request_id,
            "deadline_ms": float(body.get("deadline_ms")
                                 or tenant.deadline_ms),
            "stream": self._make_stream_cb(tenant, stream),
        }
        if "eos_token_id" in body:
            kwargs["eos_token_id"] = int(body["eos_token_id"])
        if body.get("do_sample"):
            # keyed sampling rides through verbatim: the seed IS the
            # reproducibility contract, so the gateway must not rewrite
            # or default it — the serving config owns knob defaults
            kwargs["do_sample"] = True
            if body.get("seed") is not None:
                kwargs["seed"] = int(body["seed"])
            if body.get("temperature") is not None:
                kwargs["temperature"] = float(body["temperature"])
            if body.get("top_k") is not None:
                kwargs["top_k"] = int(body["top_k"])
            if body.get("top_p") is not None:
                kwargs["top_p"] = float(body["top_p"])
        if self._routerlike:
            kwargs["priority"] = tenant.priority
        elif trace is not None:
            # bare-engine backend: its serve/decode spans join the
            # gateway trace (router backends manage their own trace)
            kwargs["trace"] = trace_ctx(trace[0],
                                        parent=span_id(trace[1]))
        with self._lock:
            self._streams[request_id] = stream
        handle = self.backend.submit(body["prompt"], **kwargs)
        stream.submitted_ts = self.clock()
        shed = getattr(handle, "state", "") == "shed"
        self._ingress_span(trace, stream.submitted_ts, tenant.name,
                           "backend_shed" if shed else "ok")
        if shed:
            # backend admission control said no (queue full / duplicate
            # id / inflight-token cap): surface it as 503, not a hang
            self._finish(tenant, stream, "shed",
                         reason=getattr(handle, "finish_reason", "")
                         or "backend_shed", trace=trace, status=503)
            return None, None, None, self.config.retry_after_secs, \
                "backend_shed"
        self._gauge_tenant(tenant)
        self._bump(tenant.name, "admitted")
        if body.get("do_sample"):
            # per-tenant replay breakdown: how much of this tenant's
            # admitted traffic is keyed-sampled (stats()/bench read it)
            self._bump(tenant.name, "sampled")
        self._wake.set()
        return handle, stream, trace, 0.0, ""

    def _make_stream_cb(self, tenant: Tenant, stream: _Stream):
        clock = self.clock

        def on_token(req, token: int, done: bool):
            if stream.closed or stream.overflow:
                return
            # step-thread clock read: deterministic under the replay
            # harness' simulated time. Every token carries the moment it
            # was handed over; the handler measures its wait from it.
            now = clock()
            if stream.first_ts is None:
                stream.first_ts = now
            try:
                stream.q.put_nowait(("token", int(token), now))
                stream.tokens += 1
                if done:
                    stream.q.put_nowait(("done",))
            except queue.Full:
                # slow reader: shed THIS request only — never block the
                # step loop. The cancel drains at the next gateway step.
                stream.overflow = True
                self._request_cancel(stream.request_id, "slow_reader")
        return on_token

    def observe_ttft(self, tenant: Tenant, stream: _Stream,
                     streamed: bool = True) -> Optional[float]:
        """The TTFT ``ds_gateway_ttft_ms`` and the tenant's SLO outcome
        observe: accept -> first flush where the reply streams (None if
        no token event reached the socket), ``first_emit - submitted``
        for a JSON reply, which has no flush a token."""
        ttft_ms = (_ms(stream.accept_ts, stream.first_flush_ts) if streamed
                   else stream.since_submit_ms())
        if ttft_ms is not None:
            self._metrics.histogram("ds_gateway_ttft_ms",
                                    labels=("tenant",)) \
                .labels(tenant=tenant.name).observe(ttft_ms)
        return ttft_ms

    def complete_record(self, record: dict, stream: _Stream) -> dict:
        """The backend's record (engine or router) with the gateway's own
        fields: what the ``done`` event and the JSON reply carry."""
        if record.get("ttft_ms") is None:
            # backends that don't stamp timestamps (or use a different
            # timebase) still report the gateway-observed TTFT, read on
            # the step thread
            since = stream.since_submit_ms()
            if since is not None:
                record["ttft_ms"] = round(since, 3)
        record.update(stream.record_fields())
        return record


def _sse(event: str, data: dict) -> bytes:
    return (f"event: {event}\ndata: {json.dumps(data, sort_keys=True)}"
            f"\n\n").encode("utf-8")


def _token_frame(request_id: str) -> bytes:
    """The bytes of one request's token events with the two numbers left
    open (``frame % (index, token)``): what ``_sse("token", {"index": i,
    "request_id": id, "token": t})`` builds, without a ``json.dumps`` a
    token on a handler thread (it pays for the stamps a token several
    times over)."""
    rid = json.dumps(request_id).replace("%", "%%")
    return (f'event: token\ndata: {{"index": %d, "request_id": {rid}, '
            f'"token": %d}}\n\n').encode("utf-8")


class _Handler(BaseHTTPRequestHandler):
    server_version = "ds-gateway/1.0"

    # ------------------------------------------------------------------
    def log_message(self, fmt, *args):  # silenced: telemetry covers it
        pass

    @property
    def gw(self) -> ServingGateway:
        return self.server.gateway

    def setup(self):
        # the request's first stamp: the connection is accepted and its
        # thread runs; request line, headers and body are still unread
        self.accept_ts = self.server.gateway.clock()
        super().setup()

    def _json(self, status: int, payload: dict, headers=()):
        body = json.dumps(payload, sort_keys=True).encode("utf-8")
        self.send_response(status)
        self.send_header("Content-Type", "application/json; charset=utf-8")
        self.send_header("Content-Length", str(len(body)))
        for k, v in headers:
            self.send_header(k, v)
        self.send_header("Connection", "close")
        self.end_headers()
        self.wfile.write(body)

    def _error(self, status: int, reason: str, tenant: str = "unknown",
               retry_after: float = 0.0):
        headers = []
        if status in (429, 503):
            secs = max(retry_after, self.gw.config.retry_after_secs)
            headers.append(("Retry-After", str(max(1, round(secs)))))
        self._json(status, {"error": {"status": status, "reason": reason,
                                      "tenant": tenant}}, headers)

    # ------------------------------------------------------------------
    def do_GET(self):
        gw = self.gw
        if self.path in ("/metrics", "/"):
            gw._metrics.counter("ds_scrapes_total").inc()
            body = gw._metrics.expose().encode("utf-8")
            self.send_response(200)
            self.send_header("Content-Type", CONTENT_TYPE)
            self.send_header("Content-Length", str(len(body)))
            self.send_header("Connection", "close")
            self.end_headers()
            self.wfile.write(body)
            return
        if self.path == "/healthz":
            backend = gw.backend
            payload = {"status": "ok", "pending": bool(gw.pending)}
            overload = getattr(backend, "overload", None)
            if overload is not None:
                payload["overload"] = round(float(overload()), 6)
            gauges = (getattr(backend, "fleet_gauges", None)
                      or getattr(backend, "gauges", None))
            if gauges is not None:
                payload["gauges"] = gauges()
            self._json(200, payload)
            return
        self._json(404, {"error": {"status": 404, "reason": "not_found"}})

    # ------------------------------------------------------------------
    def do_POST(self):
        if self.path != GENERATE_ROUTE:
            self._json(404, {"error": {"status": 404,
                                       "reason": "not_found"}})
            return
        gw = self.gw
        # a handler thread: everything up to the engine having the
        # request contends with the pump for the interpreter lock (a
        # refusal's reply is written inside the bracket too)
        with gw._bracket("ingress"):
            admitted = self._ingress(gw)
        if admitted is None:
            return
        tenant, body, handle, stream, trace = admitted
        if body.get("stream", True):
            self._stream_sse(gw, tenant, handle, stream, trace)
        else:
            self._respond_json(gw, tenant, handle, stream, trace)

    def _ingress(self, gw: ServingGateway):
        """Authenticate, read, parse, validate and admit one request:
        ``(tenant, body, handle, stream, trace)``, or None after the
        refusal was answered."""
        api_key = self._api_key()
        tenant, err = gw.authenticate(api_key)
        label = tenant.name if tenant is not None else "unknown"
        try:
            length = int(self.headers.get("Content-Length") or 0)
        except ValueError:
            length = -1
        if length <= 0:
            gw._reject(label, "bad_request", 400)
            self._error(400, "bad_request", label)
            return None
        if length > gw.config.max_body_bytes:
            gw._reject(label, "too_large", 413)
            self._error(413, "too_large", label)
            return None
        if tenant is None:
            status = _REASON_STATUS[err]
            gw._reject(label, err, status)
            self._error(status, err, label)
            return None
        raw = self.rfile.read(length)
        body = self._parse(raw)
        if body is None:
            gw._reject(tenant.name, "bad_request", 400)
            self._error(400, "bad_request", tenant.name)
            return None
        samp_err = _validate_sampling(body)
        if samp_err is not None:
            gw._reject(tenant.name, samp_err, 400)
            self._error(400, samp_err, tenant.name)
            return None
        handle, stream, trace, retry_after, reason = gw.admit(
            tenant, body, self.accept_ts)
        if handle is None:
            self._error(_REASON_STATUS.get(reason, 429), reason,
                        tenant.name, retry_after)
            return None
        return tenant, body, handle, stream, trace

    # ------------------------------------------------------------------
    def _api_key(self) -> Optional[str]:
        auth = self.headers.get("Authorization") or ""
        if auth.startswith("Bearer "):
            return auth[len("Bearer "):].strip()
        return self.headers.get("X-API-Key")

    def _parse(self, raw: bytes) -> Optional[dict]:
        try:
            body = json.loads(raw.decode("utf-8"))
        except (UnicodeDecodeError, ValueError):
            return None
        if not isinstance(body, dict):
            return None
        prompt = body.get("prompt")
        if (not isinstance(prompt, list) or not prompt
                or not all(isinstance(t, int) for t in prompt)):
            return None
        mnt = body.get("max_new_tokens", 0)
        if not isinstance(mnt, int) or mnt < 0:
            return None
        return body

    # ------------------------------------------------------------------
    def _pull(self, gw: ServingGateway, handle, stream: _Stream):
        """Yield queue items; on quiet polls, fall back to the handle's
        terminal state (a shed mid-decode emits no done marker)."""
        while True:
            try:
                yield stream.q.get(timeout=gw.config.poll_secs)
                continue
            except queue.Empty:
                pass
            if stream.overflow:
                yield ("error", "slow_reader")
                return
            state = getattr(handle, "state", "")
            if state == "shed" and stream.q.empty():
                yield ("error", getattr(handle, "finish_reason", "")
                       or "shed")
                return
            if state == "finished" and stream.q.empty():
                yield ("done",)
                return
            if not gw._running:
                yield ("error", "shutdown")
                return

    def _record_of(self, handle) -> dict:
        rec = getattr(handle, "record", None)
        if not callable(rec):
            return {}
        # the ("done",) marker is enqueued MID-step by the stream
        # callback; the backend marks the request terminal at the END of
        # that same step (router harvest). Wait it out — bounded — so
        # the record this response carries is the final one, not a
        # mid-harvest snapshot with state still "running".
        pause = threading.Event()
        for _ in range(2000):
            if getattr(handle, "state", "finished") in ("finished",
                                                        "shed"):
                break
            pause.wait(0.005)
        return rec()

    def _stream_sse(self, gw, tenant, handle, stream, trace):
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream; charset=utf-8")
        self.send_header("Cache-Control", "no-store")
        self.send_header("X-Request-Id", stream.request_id)
        self.send_header("Connection", "close")
        self.end_headers()
        clock, bracket = gw.clock, gw._bracket
        write, flush = self.wfile.write, self.wfile.flush
        frame = _token_frame(stream.request_id)
        # what this loop measures stays in its locals (two clock reads
        # and three adds a token) until delivered() stores it
        index = 0
        first_flush = t1 = None
        egress = egress_max = wrote = 0.0
        outcome, reason = "ok", ""
        try:
            for item in self._pull(gw, handle, stream):
                if item[0] == "token":
                    # a handler thread: these contend with the pump for
                    # the interpreter lock
                    with bracket("sse_write"):
                        t0 = clock()
                        write(frame % (index, item[1]))
                        flush()
                        t1 = clock()
                    # the token's wait between the step thread and the
                    # flushed socket, and the handler's time writing it
                    wait = t1 - item[2]
                    egress += wait
                    if wait > egress_max:
                        egress_max = wait
                    wrote += t1 - t0
                    if not index:
                        first_flush = t1
                    index += 1
                    continue
                stream.delivered(index, first_flush, t1, egress,
                                 egress_max, wrote)
                if item[0] == "done":
                    write(_sse("done", gw.complete_record(
                        self._record_of(handle), stream)))
                else:  # ("error", reason)
                    outcome, reason = "shed", item[1]
                    write(_sse("error", {
                        "reason": reason,
                        "request_id": stream.request_id}))
                flush()
                break
        except (BrokenPipeError, ConnectionError, OSError):
            # client went away mid-stream: cancel through the backend
            # seam so the slot and its KV blocks are released
            gw._request_cancel(stream.request_id, "disconnect")
            outcome, reason = "shed", "disconnect"
        stream.delivered(index, first_flush, t1, egress, egress_max, wrote)
        gw._finish(tenant, stream, outcome, reason=reason,
                   ttft_ms=gw.observe_ttft(tenant, stream), trace=trace)

    def _respond_json(self, gw, tenant, handle, stream, trace):
        tokens: List[int] = []
        outcome, reason = "ok", ""
        for item in self._pull(gw, handle, stream):
            if item[0] == "token":
                tokens.append(item[1])
            elif item[0] == "done":
                break
            else:
                outcome, reason = "shed", item[1]
                break
        ttft = gw.observe_ttft(tenant, stream, streamed=False)
        record = gw.complete_record(self._record_of(handle), stream)
        payload = {"request_id": stream.request_id,
                   "state": "finished" if outcome == "ok" else "shed",
                   "reason": reason, "tokens": tokens, "record": record}
        gw._finish(tenant, stream, outcome, reason=reason, ttft_ms=ttft,
                   trace=trace)
        self._json(200, payload)
