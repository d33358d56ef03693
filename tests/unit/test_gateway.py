"""Multi-tenant HTTP/SSE serving gateway.

Five tiers, the first four pure host-side (fake backends + fake
clocks — no jax, millisecond tier-1):

- tenancy primitives: token buckets on the injected clock, per-tenant
  admission (rate / tokens / inflight), deterministic trace sampling
  and the sliding-window error budget;
- the HTTP surface: SSE streaming + JSON fallback, ``/healthz`` and
  ``/metrics`` on the same port, malformed-input hardening (oversized
  bodies, bad JSON, bad prompts, missing/unknown API keys);
- quota enforcement proven end to end: 429 + ``Retry-After``, tenant-
  labeled metrics and shed spans, the in-quota tenant unaffected —
  plus the cancel seam (slow reader sheds only its own request, a
  client disconnect releases the slot through ``backend.cancel()``);
- trace replay THROUGH the gateway: the PR 13 replayer drives real
  HTTP against a fake-clock backend bit-deterministically, with
  per-tenant report breakdowns, and a fresh-interpreter subprocess
  smoke;
- heavy: the real substrate — greedy SSE streams bit-match direct
  ``submit()``, a disconnect frees real KV blocks, the seeded
  diurnal+Zipf e2e acceptance over a two-replica fleet, and the
  zero-overhead pin (a ``serving.gateway`` block leaves the compiled
  decode HLO byte-identical).
"""

import json
import os
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

from deepspeed_tpu.serving import request as rq
from deepspeed_tpu.serving.config import (GatewayConfig,
                                          GatewayTenantConfig,
                                          SloClassConfig)
from deepspeed_tpu.serving.gateway import ServingGateway
from deepspeed_tpu.serving.replay import (HttpReplayDriver, ReplayClock,
                                          TraceReplayer, synthesize_trace)
from deepspeed_tpu.serving.router import FleetManager, ReplicaRouter
from deepspeed_tpu.serving.tenancy import (ANONYMOUS, Tenant, TenantTable,
                                           TokenBucket)
from deepspeed_tpu.telemetry.registry import MetricRegistry
from deepspeed_tpu.telemetry.tracing import Tracer
from tests.unit.test_router import FakeReplica, FakeTelemetry, _Clock, _greedy

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))


class FakeBackend(FakeReplica):
    """A bare-engine-shaped gateway backend: FakeReplica's deterministic
    decode plus the ``pending`` / ``cancel`` seams the gateway drives."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.cancels = []

    def submit(self, prompt, max_new_tokens=0, request_id=None,
               eos_token_id=-1, deadline_ms=0.0, stream=None, **kw):
        # **kw swallows the bare-engine trace= context the gateway
        # forwards for sampled requests
        return super().submit(prompt, max_new_tokens=max_new_tokens,
                              request_id=request_id,
                              eos_token_id=eos_token_id,
                              deadline_ms=deadline_ms, stream=stream)

    @property
    def pending(self):
        return bool(self.queue or self.running)

    def cancel(self, request_id, reason="cancelled"):
        self.cancels.append((request_id, reason))
        for pool in (self.queue, self.running):
            for req in list(pool):
                if req.request_id == request_id:
                    req.state, req.finish_reason = rq.SHED, reason
                    pool.remove(req)
                    return True
        return False

    def drain(self, max_steps=None):
        steps = 0
        while self.pending:
            self.step()
            steps += 1
            if max_steps and steps >= max_steps:
                break
        return []


class ArmedTelemetry(FakeTelemetry):
    """FakeTelemetry plus a real metric registry and a span tracer, so
    gateway metrics/spans land somewhere assertable."""

    def __init__(self):
        super().__init__()
        self.metrics = MetricRegistry()
        self.tracer = Tracer(
            emit=lambda kind, name, step=None, data=None:
            self.emit(kind, name, step=step, **(data or {})))

    def spans(self, name=None):
        return [e for e in self.events if e["kind"] == "span"
                and (name is None or e["data"].get("name",
                                                   e["name"]) == name
                     or e["name"] == name)]


TENANTS = [
    {"name": "acme", "api_key": "acme-key", "slo_class": "gold",
     "requests_per_sec": 1000.0, "tokens_per_sec": 0.0},
    {"name": "spam", "api_key": "spam-key", "slo_class": "best_effort",
     "requests_per_sec": 1.0, "burst_requests": 1.0,
     "trace_sample_rate": 1.0},
]


def _gw(backend=None, config=None, clock=time.monotonic, telemetry=None):
    backend = backend if backend is not None else FakeBackend()
    return ServingGateway(backend, config or {}, telemetry=telemetry,
                          clock=clock).start()


def _post(url, body, key=None, timeout=20):
    headers = {"Content-Type": "application/json"}
    if key:
        headers["Authorization"] = f"Bearer {key}"
    req = urllib.request.Request(url + "/v1/generate",
                                 data=json.dumps(body).encode("utf-8"),
                                 headers=headers, method="POST")
    return urllib.request.urlopen(req, timeout=timeout)


def _post_err(url, body, key=None, raw=None):
    """POST expecting an HTTP error; returns (status, payload, headers)."""
    headers = {"Content-Type": "application/json"}
    if key:
        headers["Authorization"] = f"Bearer {key}"
    data = raw if raw is not None else json.dumps(body).encode("utf-8")
    req = urllib.request.Request(url + "/v1/generate", data=data,
                                 headers=headers, method="POST")
    with pytest.raises(urllib.error.HTTPError) as exc:
        urllib.request.urlopen(req, timeout=20)
    err = exc.value
    payload = json.loads(err.read().decode("utf-8"))
    return err.code, payload, dict(err.headers)


def _wait(cond, timeout=10.0):
    """Real-time wait for a handler-thread side effect (terminal
    accounting lands just after the last SSE byte)."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.01)
    return cond()


def _sse_events(resp):
    """Consume one SSE response fully into [(event, payload), ...]."""
    events, event, data = [], "", ""
    for raw in resp:
        line = raw.decode("utf-8").rstrip("\n")
        if line.startswith("event: "):
            event = line[len("event: "):]
        elif line.startswith("data: "):
            data = line[len("data: "):]
        elif line == "":
            events.append((event, json.loads(data)))
            if event in ("done", "error"):
                break
            event, data = "", ""
    resp.close()
    return events


# ---------------------------------------------------------------------------
class TestTokenBucket:
    def test_refill_ask_take(self):
        clock = _Clock()
        b = TokenBucket(rate=2.0, burst=4.0, clock=clock)
        assert b.ask(4.0) == 0.0
        b.take(4.0)
        # 1 token refills in 0.5s at 2/s
        assert b.ask(1.0) == pytest.approx(0.5)
        clock.advance(0.5)
        assert b.ask(1.0) == 0.0
        # refill caps at burst
        clock.advance(100.0)
        assert b.ask(4.0) == 0.0
        assert b.ask(5.0) > 0.0

    def test_zero_rate_is_unlimited(self):
        b = TokenBucket(rate=0.0, clock=_Clock())
        for _ in range(1000):
            assert b.ask(100.0) == 0.0
            b.take(100.0)

    def test_default_burst_is_one_second_of_rate(self):
        clock = _Clock()
        assert TokenBucket(5.0, clock=clock).burst == 5.0
        assert TokenBucket(0.25, clock=clock).burst == 1.0


class TestTenant:
    def _tenant(self, clock, *, slo=None, **cfg):
        row = GatewayTenantConfig(name="t", api_key="k", **cfg)
        return Tenant(row, slo or SloClassConfig(priority=1),
                      clock=clock, budget_window=4)

    def test_admit_charges_and_release(self):
        clock = _Clock()
        t = self._tenant(clock, requests_per_sec=1.0, burst_requests=1.0)
        assert t.admit() == ("", 0.0)
        assert t.inflight == 1
        reason, wait = t.admit()
        assert reason == "rate" and wait == pytest.approx(1.0)
        clock.advance(1.0)
        assert t.admit() == ("", 0.0)
        t.release()
        assert t.inflight == 1
        t.release()
        assert t.inflight == 0
        t.release()
        assert t.inflight == 0    # floored, never negative

    def test_token_budget_and_inflight_quotas(self):
        clock = _Clock()
        t = self._tenant(clock, tokens_per_sec=10.0, burst_tokens=10.0,
                         max_inflight=2)
        assert t.admit(est_tokens=8.0) == ("", 0.0)
        reason, wait = t.admit(est_tokens=8.0)
        assert reason == "tokens" and wait == pytest.approx(0.6)
        clock.advance(1.0)
        assert t.admit(est_tokens=8.0)[0] == ""
        # both slots now taken -> inflight quota fires before buckets
        clock.advance(10.0)
        assert t.admit()[0] == "inflight"

    def test_error_budget_burn(self):
        clock = _Clock()
        t = self._tenant(clock, slo=SloClassConfig(priority=1,
                                                   ttft_ms=100.0,
                                                   error_budget=0.5))
        assert t.budget_remaining() == 1.0
        t.record_outcome(shed=False, ttft_ms=50.0)    # good
        t.record_outcome(shed=False, ttft_ms=50.0)    # good
        t.record_outcome(shed=True)                   # shed burns
        t.record_outcome(shed=False, ttft_ms=500.0)   # ttft miss burns
        # 2/4 bad over a 0.5 budget -> fully spent
        assert t.budget_remaining() == 0.0
        for _ in range(4):                            # window slides clean
            t.record_outcome(shed=False, ttft_ms=10.0)
        assert t.budget_remaining() == 1.0

    def test_trace_sampling_is_a_deterministic_accumulator(self):
        t = self._tenant(_Clock(), trace_sample_rate=0.25)
        picks = [t.sample_trace() for _ in range(8)]
        assert picks == [False, False, False, True] * 2
        t2 = self._tenant(_Clock(), trace_sample_rate=0.25)
        assert [t2.sample_trace() for _ in range(8)] == picks
        assert not any(self._tenant(_Clock()).sample_trace()
                       for _ in range(8))

    def test_tenant_table_resolution(self):
        cfg = GatewayConfig(tenants=TENANTS)
        table = TenantTable(cfg, clock=_Clock())
        assert not table.open
        assert table.resolve("acme-key").name == "acme"
        assert table.resolve("acme-key").priority == 2       # gold
        assert table.resolve("spam-key").priority == 1       # best_effort
        assert table.resolve("nope") is None
        assert table.resolve(None) is None
        open_table = TenantTable(GatewayConfig(), clock=_Clock())
        assert open_table.open
        assert open_table.resolve(None).name == ANONYMOUS
        assert open_table.resolve("anything").name == ANONYMOUS


# ---------------------------------------------------------------------------
class TestGatewayHTTP:
    def test_sse_stream_happy_path(self):
        backend = FakeBackend()
        gw = _gw(backend, {"pump": True})
        try:
            prompt = [5, 6, 7]
            resp = _post(gw.url, {"prompt": prompt, "max_new_tokens": 4})
            assert resp.status == 200
            assert resp.headers["Content-Type"].startswith(
                "text/event-stream")
            rid = resp.headers["X-Request-Id"]
            events = _sse_events(resp)
            toks = [e[1]["token"] for e in events if e[0] == "token"]
            assert toks == [_greedy(prompt, i) for i in range(4)]
            assert [e[1]["index"] for e in events if e[0] == "token"] \
                == [0, 1, 2, 3]
            assert events[-1][0] == "done"
            assert events[-1][1]["request_id"] == rid
            assert events[-1][1]["state"] == rq.FINISHED
            assert _wait(lambda: gw.stats()["tenants"][ANONYMOUS]
                         .get("ok") == 1)
            assert gw.stats()["tenants"][ANONYMOUS]["inflight"] == 0
        finally:
            gw.close()

    def test_json_fallback(self):
        gw = _gw(FakeBackend(), {"pump": True})
        try:
            prompt = [9, 10]
            resp = _post(gw.url, {"prompt": prompt, "max_new_tokens": 3,
                                  "stream": False})
            out = json.loads(resp.read().decode("utf-8"))
            assert out["state"] == "finished"
            assert out["tokens"] == [_greedy(prompt, i) for i in range(3)]
            assert out["record"]["state"] == rq.FINISHED
        finally:
            gw.close()

    def test_healthz_and_metrics_same_port(self):
        telemetry = ArmedTelemetry()
        gw = _gw(FakeBackend(), {"pump": True}, telemetry=telemetry)
        try:
            health = json.loads(urllib.request.urlopen(
                gw.url + "/healthz", timeout=10).read())
            assert health["status"] == "ok"
            assert health["gauges"]["slots_total"] == 2
            _sse_events(_post(gw.url, {"prompt": [1], "max_new_tokens": 2}))
            assert _wait(lambda: gw.stats()["tenants"][ANONYMOUS]
                         .get("ok") == 1)
            body = urllib.request.urlopen(gw.url + "/metrics",
                                          timeout=10).read().decode()
            assert 'ds_gateway_requests_total{outcome="ok",' \
                   'tenant="anonymous"} 1' in body
            assert "ds_gateway_ttft_ms" in body
            assert "ds_scrapes_total" in body
        finally:
            gw.close()

    def test_unknown_routes_404(self):
        gw = _gw()
        try:
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(gw.url + "/nope", timeout=10)
            assert e.value.code == 404
            # POST off the generate route is a 404 too
            req = urllib.request.Request(gw.url + "/v2/generate",
                                         data=b"{}", method="POST")
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(req, timeout=10)
            assert e.value.code == 404
        finally:
            gw.close()

    def test_direct_submit_passthrough_and_close(self):
        backend = FakeBackend()
        gw = _gw(backend)
        try:
            handle = gw.submit([1, 2], max_new_tokens=2)
            gw.drain()
            assert handle.state == rq.FINISHED
            assert handle.tokens == [_greedy([1, 2], 0), _greedy([1, 2], 1)]
        finally:
            gw.close()
        with pytest.raises(Exception):
            urllib.request.urlopen(gw.url + "/healthz", timeout=0.5)

    def test_gateway_events_reach_telemetry(self):
        telemetry = ArmedTelemetry()
        gw = _gw(FakeBackend(), {"pump": True}, telemetry=telemetry)
        try:
            _sse_events(_post(gw.url, {"prompt": [3], "max_new_tokens": 2}))
            fins = lambda: [e for e in telemetry.events
                            if e["kind"] == "gateway"
                            and e["name"] == "request.finished"]
            assert _wait(lambda: len(fins()) == 1)
            (fin,) = fins()
            assert fin["data"]["tenant"] == ANONYMOUS
            assert fin["data"]["outcome"] == "ok"
            assert fin["data"]["tokens"] == 2
            assert 0.0 <= fin["data"]["budget_remaining"] <= 1.0
        finally:
            gw.close()


# ---------------------------------------------------------------------------
class TestHardening:
    @pytest.fixture()
    def gw(self):
        gw = _gw(FakeBackend(), {"pump": True, "max_body_bytes": 4096,
                                 "tenants": TENANTS})
        yield gw
        gw.close()

    def test_missing_auth_401(self, gw):
        code, payload, _ = _post_err(gw.url, {"prompt": [1]})
        assert code == 401 and payload["error"]["reason"] == "auth"
        assert gw.stats()["tenants"]["acme"].get("admitted", 0) == 0

    def test_unknown_tenant_403(self, gw):
        code, payload, _ = _post_err(gw.url, {"prompt": [1]}, key="wrong")
        assert code == 403 and payload["error"]["reason"] == "forbidden"

    def test_bad_json_400(self, gw):
        code, payload, _ = _post_err(gw.url, None, key="acme-key",
                                     raw=b"{not json")
        assert code == 400 and payload["error"]["reason"] == "bad_request"
        assert payload["error"]["tenant"] == "acme"

    @pytest.mark.parametrize("body", [
        [1, 2, 3],                                   # not an object
        {"max_new_tokens": 4},                       # no prompt
        {"prompt": []},                              # empty prompt
        {"prompt": "hi"},                            # wrong type
        {"prompt": [1, "x"]},                        # non-int tokens
        {"prompt": [1], "max_new_tokens": -1},       # negative budget
        {"prompt": [1], "max_new_tokens": 1.5},      # non-int budget
    ])
    def test_malformed_bodies_400(self, gw, body):
        code, payload, _ = _post_err(gw.url, body, key="acme-key")
        assert code == 400 and payload["error"]["reason"] == "bad_request"

    def test_empty_body_400(self, gw):
        code, payload, _ = _post_err(gw.url, None, key="acme-key", raw=b"")
        assert code == 400

    def test_oversized_body_413_before_read(self, gw):
        blob = {"prompt": [1] * 5000, "max_new_tokens": 1}
        code, payload, _ = _post_err(gw.url, blob, key="acme-key")
        assert code == 413 and payload["error"]["reason"] == "too_large"
        assert gw.stats()["tenants"]["acme"]["http_413"] == 1
        # the backend never saw it
        assert gw.backend.submits == 0


# ---------------------------------------------------------------------------
class SamplingBackend(FakeBackend):
    """FakeBackend with the WIDE submit surface: records the sampling
    kwargs the gateway threads through (and keeps decoding greedily —
    these tests pin the DOOR, the keyed decode is pinned elsewhere)."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.samp_seen = []

    def submit(self, prompt, max_new_tokens=0, request_id=None,
               eos_token_id=-1, deadline_ms=0.0, stream=None,
               do_sample=False, seed=None, temperature=None, top_k=None,
               top_p=None, **kw):
        if do_sample:
            self.samp_seen.append({"seed": seed, "temperature": temperature,
                                   "top_k": top_k, "top_p": top_p})
        return super().submit(prompt, max_new_tokens=max_new_tokens,
                              request_id=request_id,
                              eos_token_id=eos_token_id,
                              deadline_ms=deadline_ms, stream=stream)


class TestSamplingDoor:
    """``POST /v1/generate`` sampling fields: range-checked AT the door
    (typed 400 before the backend sees anything), threaded verbatim to
    ``submit()`` when valid, and counted per tenant."""

    @pytest.fixture()
    def gw(self):
        gw = _gw(SamplingBackend(), {"pump": True, "tenants": TENANTS})
        yield gw
        gw.close()

    def test_sampled_request_threads_knobs_verbatim(self, gw):
        resp = _post(gw.url, {"prompt": [1, 2], "max_new_tokens": 3,
                              "do_sample": True, "seed": 7,
                              "temperature": 0.8, "top_p": 0.9},
                     key="acme-key")
        events = _sse_events(resp)
        assert [e[0] for e in events] == ["token"] * 3 + ["done"]
        # the knobs arrived untouched; unset ones stay None — the
        # gateway never invents defaults (the serving config owns them)
        assert gw.backend.samp_seen == [
            {"seed": 7, "temperature": 0.8, "top_k": None, "top_p": 0.9}]
        assert _wait(lambda:
                     gw.stats()["tenants"]["acme"].get("sampled") == 1)
        assert gw.stats()["tenants"]["acme"]["admitted"] == 1

    def test_greedy_request_not_counted_sampled(self, gw):
        _sse_events(_post(gw.url, {"prompt": [1], "max_new_tokens": 2},
                          key="acme-key"))
        assert _wait(lambda:
                     gw.stats()["tenants"]["acme"].get("admitted") == 1)
        assert gw.stats()["tenants"]["acme"].get("sampled", 0) == 0
        assert gw.backend.samp_seen == []

    @pytest.mark.parametrize("fields", [
        {"seed": -1},                 # negative seed
        {"seed": 1.5},                # non-int seed
        {"seed": True},               # bool is not a seed
        {"seed": "7"},                # string seed
        {"temperature": 0},           # temperature must be > 0
        {"temperature": -0.5},
        {"temperature": "hot"},
        {"top_k": -1},
        {"top_k": 2.5},
        {"top_p": 1.5},               # out of [0, 1]
        {"top_p": -0.1},
        {"do_sample": "yes"},         # non-bool flag
    ])
    def test_invalid_sampling_typed_400(self, gw, fields):
        body = {"prompt": [1, 2], "max_new_tokens": 2,
                "do_sample": True, **fields}
        code, payload, _ = _post_err(gw.url, body, key="acme-key")
        assert code == 400
        assert payload["error"]["reason"] == "sampling_invalid"
        assert payload["error"]["tenant"] == "acme"
        # rejected at the door: the backend never saw the request and
        # nothing was admitted or counted sampled
        assert gw.backend.submits == 0
        assert gw.stats()["tenants"]["acme"].get("admitted", 0) == 0
        assert gw.stats()["tenants"]["acme"].get("sampled", 0) == 0

    def test_valid_knobs_without_do_sample_are_still_checked(self, gw):
        """Range checks apply even when do_sample is absent: a greedy
        body carrying a nonsense temperature is a client bug, answered
        with the same typed 400."""
        code, payload, _ = _post_err(
            gw.url, {"prompt": [1], "temperature": -2.0}, key="acme-key")
        assert code == 400
        assert payload["error"]["reason"] == "sampling_invalid"


# ---------------------------------------------------------------------------
class TestQuotaEnforcement:
    def test_429_retry_after_metrics_and_spans(self):
        """The acceptance proof: spam's second request inside the bucket
        window is a 429 with Retry-After; acme (in quota, gold) is
        untouched; the reject is tenant-labeled in metrics and renders
        a shed span under the sampled gateway root."""
        clock = _Clock()
        telemetry = ArmedTelemetry()
        backend = FakeBackend(slots=4, queue_cap=32)
        gw = _gw(backend, {"tenants": TENANTS}, clock=clock,
                 telemetry=telemetry)
        try:
            ok = _post(gw.url, {"prompt": [1, 2], "max_new_tokens": 2},
                       key="spam-key")
            code, payload, headers = _post_err(
                gw.url, {"prompt": [3], "max_new_tokens": 2},
                key="spam-key")
            assert code == 429
            assert payload["error"] == {"status": 429, "reason": "rate",
                                        "tenant": "spam"}
            assert int(headers["Retry-After"]) >= 1
            # acme admits fine while spam is throttled
            acme = _post(gw.url, {"prompt": [4, 5], "max_new_tokens": 2},
                         key="acme-key")
            while gw.pending:
                gw.step()
            assert [e[0] for e in _sse_events(ok)].count("token") == 2
            assert [e[0] for e in _sse_events(acme)].count("token") == 2
            assert _wait(lambda: gw.stats()["tenants"]["spam"]
                         .get("ok") == 1
                         and gw.stats()["tenants"]["acme"].get("ok") == 1)
            stats = gw.stats()["tenants"]
            assert stats["spam"]["http_429"] == 1
            assert stats["spam"]["ok"] == 1
            assert stats["acme"]["ok"] == 1
            assert "rejected" not in stats["acme"]
            # the bucket refills in simulated time
            clock.advance(1.0)
            again = _post(gw.url, {"prompt": [6], "max_new_tokens": 2},
                          key="spam-key")
            while gw.pending:
                gw.step()
            assert _sse_events(again)[-1][0] == "done"
            assert _wait(lambda: gw.stats()["tenants"]["spam"]
                         .get("ok") == 2)
            expo = telemetry.metrics.expose()
            assert 'ds_gateway_rejects_total{reason="rate",' \
                   'tenant="spam"} 1' in expo
            assert 'ds_gateway_requests_total{outcome="ok",' \
                   'tenant="acme"} 1' in expo
            # spam samples every request: the reject closed its root
            # with a shed child; every sampled request carries ingress
            # (accept -> the engine has it, or the refusal) + quota
            span_names = [e["name"] for e in telemetry.events
                          if e["kind"] == "span"]
            assert "gateway" in span_names and "shed" in span_names
            assert "ingress" in span_names and "quota" in span_names
            assert "auth" not in span_names
            ingress = [e["data"] for e in telemetry.events
                       if e["kind"] == "span" and e["name"] == "ingress"]
            assert sorted(s["outcome"] for s in ingress) \
                == ["ok", "ok", "rate"]
            assert all(s["tenant"] == "spam" for s in ingress)
            shed = [e for e in telemetry.events if e["kind"] == "span"
                    and e["name"] == "shed"]
            assert shed and all(s["data"].get("tenant") == "spam"
                                for s in shed)
        finally:
            gw.close()

    def test_inflight_quota_429(self):
        tenants = [{"name": "one", "api_key": "one-key",
                    "max_inflight": 1}]
        gw = _gw(FakeBackend(), {"tenants": tenants})
        try:
            first = _post(gw.url, {"prompt": [1], "max_new_tokens": 4},
                          key="one-key")             # admitted, streaming
            code, payload, headers = _post_err(
                gw.url, {"prompt": [2], "max_new_tokens": 4},
                key="one-key")
            assert code == 429
            assert payload["error"]["reason"] == "inflight"
            assert "Retry-After" in headers
            while gw.pending:
                gw.step()
            assert _sse_events(first)[-1][0] == "done"
            assert _wait(lambda: gw.stats()["tenants"]["one"]
                         ["inflight"] == 0)
            # slot free again
            ok = _post(gw.url, {"prompt": [3], "max_new_tokens": 2},
                       key="one-key")
            while gw.pending:
                gw.step()
            assert _sse_events(ok)[-1][0] == "done"
        finally:
            gw.close()

    def test_tokens_per_sec_quota(self):
        tenants = [{"name": "tk", "api_key": "tk-key",
                    "tokens_per_sec": 10.0, "burst_tokens": 10.0}]
        clock = _Clock()
        gw = _gw(FakeBackend(), {"tenants": tenants}, clock=clock)
        try:
            first = _post(gw.url, {"prompt": [1], "max_new_tokens": 8},
                          key="tk-key")
            code, payload, _ = _post_err(
                gw.url, {"prompt": [2], "max_new_tokens": 8}, key="tk-key")
            assert code == 429 and payload["error"]["reason"] == "tokens"
            while gw.pending:
                gw.step()
            assert _sse_events(first)[-1][0] == "done"
        finally:
            gw.close()

    def test_overload_rejects_503(self):
        class OverloadedRouter(FakeBackend):
            def overload(self):
                return 0.99

        gw = _gw(OverloadedRouter(),
                 {"overload_reject_threshold": 0.9, "retry_after_secs": 3})
        try:
            code, payload, headers = _post_err(gw.url, {"prompt": [1]})
            assert code == 503
            assert payload["error"]["reason"] == "overload"
            assert int(headers["Retry-After"]) == 3
        finally:
            gw.close()

    def test_backend_shed_surfaces_as_503(self):
        backend = FakeBackend(queue_cap=0)            # admits nothing
        gw = _gw(backend)
        try:
            code, payload, _ = _post_err(gw.url, {"prompt": [1],
                                                  "max_new_tokens": 2})
            assert code == 503
            assert payload["error"]["reason"] == "backend_shed"
            assert gw.stats()["tenants"][ANONYMOUS]["inflight"] == 0
        finally:
            gw.close()


# ---------------------------------------------------------------------------
class TestCancelSeam:
    def test_slow_reader_sheds_only_its_own_request(self):
        """A client that stops reading overflows ITS bounded send queue;
        the gateway cancels that request through the backend seam and
        every other stream is untouched."""
        backend = FakeBackend(slots=2, queue_cap=8)
        gw = _gw(backend, {"pump": True, "send_queue_tokens": 4,
                           "poll_secs": 0.01})
        try:
            # the victim: a long stream whose client never reads — the
            # handler blocks once the socket buffers fill, then the
            # send queue (4) overflows
            victim = _post(gw.url, {"prompt": [1, 1],
                                    "max_new_tokens": 50000})
            deadline = time.monotonic() + 30
            while not backend.cancels and time.monotonic() < deadline:
                time.sleep(0.02)
            assert backend.cancels, "slow reader never overflowed"
            rid, reason = backend.cancels[0]
            assert reason == "slow_reader"
            # the bystander still completes in full
            other = _post(gw.url, {"prompt": [2, 3], "max_new_tokens": 3,
                                   "stream": False})
            out = json.loads(other.read().decode("utf-8"))
            assert out["state"] == "finished" and len(out["tokens"]) == 3
            victim.close()
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                row = gw.stats()["tenants"][ANONYMOUS]
                if row.get("shed", 0) >= 1 and row["inflight"] == 0:
                    break
                time.sleep(0.02)
            row = gw.stats()["tenants"][ANONYMOUS]
            assert row["shed"] == 1 and row["ok"] == 1
            assert row["inflight"] == 0
        finally:
            gw.close()

    def test_client_disconnect_cancels_through_backend(self):
        """Dropping the TCP connection mid-stream releases the slot via
        ``backend.cancel(rid, "disconnect"|"slow_reader")`` and the
        tenant's inflight gauge returns to zero."""
        backend = FakeBackend(slots=2, queue_cap=8)
        gw = _gw(backend, {"pump": True, "send_queue_tokens": 8,
                           "poll_secs": 0.01})
        try:
            body = json.dumps({"prompt": [4, 4], "max_new_tokens": 100000}
                              ).encode("utf-8")
            conn = socket.create_connection(("127.0.0.1", gw.port),
                                            timeout=10)
            conn.sendall(b"POST /v1/generate HTTP/1.1\r\n"
                         b"Host: x\r\nContent-Type: application/json\r\n"
                         + f"Content-Length: {len(body)}\r\n\r\n".encode()
                         + body)
            # read until the first token event, then vanish
            seen = b""
            while b"event: token" not in seen:
                chunk = conn.recv(4096)
                assert chunk, "stream ended before first token"
                seen += chunk
            conn.close()
            deadline = time.monotonic() + 30
            while not backend.cancels and time.monotonic() < deadline:
                time.sleep(0.02)
            assert backend.cancels
            assert backend.cancels[0][1] in ("disconnect", "slow_reader")
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                row = gw.stats()["tenants"][ANONYMOUS]
                if row["inflight"] == 0 and not backend.running:
                    break
                time.sleep(0.02)
            assert gw.stats()["tenants"][ANONYMOUS]["inflight"] == 0
            assert not backend.running and not backend.queue
        finally:
            gw.close()


# ---------------------------------------------------------------------------
class TestRouterCancel:
    def test_router_cancel_sheds_and_dedupes(self):
        clock = _Clock()
        router = ReplicaRouter([FakeReplica(), FakeReplica()], clock=clock)
        tokens = []
        h = router.submit([1, 2], max_new_tokens=8,
                          stream=lambda r, t, d: tokens.append(t))
        router.step()
        seen = len(tokens)
        assert router.cancel(h.request_id) is True
        assert h.state == rq.SHED and h.finish_reason == "cancelled"
        assert router.cancel(h.request_id) is False      # already terminal
        assert router.cancel("nope") is False            # unknown id
        for _ in range(10):
            router.step()
        assert len(tokens) == seen    # no post-cancel stream callbacks

    def test_fleet_manager_delegates_cancel(self):
        clock = _Clock()
        router = ReplicaRouter([FakeReplica()], clock=clock)
        fm = FleetManager(router, config={"min_replicas": 1,
                                          "max_replicas": 1})
        h = fm.submit([3, 4], max_new_tokens=8)
        fm.step()
        assert fm.cancel(h.request_id, reason="disconnect") is True
        assert h.state == rq.SHED and h.finish_reason == "disconnect"


# ---------------------------------------------------------------------------
def _replay_setup(*, http, clock=None):
    """One gateway-or-direct replay rig over the fake backend. Same
    tenants, same trace, same seeds — the determinism comparisons."""
    clock = clock or ReplayClock()
    backend = FakeBackend(slots=4, queue_cap=64)
    trace = synthesize_trace(
        8.0, seed=23, base_rate=2.0, diurnal_fraction=0.5,
        diurnal_period_secs=8.0, tenants=2, shared_fraction=1.0,
        shared_prefix_len=3, prompt_len_mean=5.0, prompt_len_max=10,
        gen_mean=3.0, gen_max=6)
    if not http:
        replayer = TraceReplayer(backend, trace, clock, step_secs=0.05,
                                 seed=31, vocab_size=97, max_steps=20000)
        return None, replayer
    tenants = [{"name": "t1", "api_key": "t1-key", "slo_class": "gold",
                "trace_sample_rate": 0.5},
               {"name": "t2", "api_key": "t2-key"}]
    gw = ServingGateway(backend, {"tenants": tenants},
                        clock=clock).start()
    driver = HttpReplayDriver(gw)
    replayer = TraceReplayer(driver, trace, clock, step_secs=0.05,
                             seed=31, vocab_size=97, max_steps=20000)
    return gw, replayer


class TestHttpReplay:
    def test_replay_through_gateway_is_bit_deterministic(self):
        """The tentpole acceptance at tier-1: the same seeded trace
        replayed over real HTTP twice yields byte-identical reports and
        per-request token streams, which also match the direct-submit
        path (no gateway in the loop)."""
        runs = []
        for _ in range(2):
            gw, replayer = _replay_setup(http=True)
            try:
                report = replayer.run()
                streams = {h.request_id: tuple(h.tokens)
                           for h in replayer.handles}
                states = {h.request_id: h.state
                          for h in replayer.handles}
            finally:
                gw.close()
            runs.append((report, streams, states))
        assert runs[0] == runs[1]
        report, streams, states = runs[0]
        assert report["requests"] > 5
        assert report["incomplete"] == 0
        assert all(s == rq.FINISHED for s in states.values())
        # direct path: same backend decode, no HTTP — streams pin
        _, direct = _replay_setup(http=False)
        direct.run()
        direct_streams = {h.request_id: tuple(h.tokens)
                          for h in direct.handles}
        assert streams == direct_streams

    def test_report_carries_per_tenant_breakdowns(self):
        gw, replayer = _replay_setup(http=True)
        try:
            report = replayer.run()
            _wait(lambda: not gw._streams)
        finally:
            gw.close()
        tenants = report["tenants"]
        assert set(tenants) == {"t1", "t2"}
        total = 0
        for row in tenants.values():
            assert row["shed_rate"] == 0.0
            assert row["ttft_ms_p95"] is not None
            total += row["requests"]
        assert total == report["requests"]
        # the gateway's own per-tenant ledger agrees
        stats = gw.stats()["tenants"]
        assert stats["t1"]["ok"] == tenants["t1"]["finished"]
        assert stats["t2"]["ok"] == tenants["t2"]["finished"]

    def test_direct_replay_report_has_no_tenant_section_without_tenants(
            self):
        clock = ReplayClock()
        backend = FakeBackend(slots=4, queue_cap=64)
        trace = synthesize_trace(2.0, seed=5, base_rate=2.0,
                                 prompt_len_mean=4.0, prompt_len_max=8,
                                 gen_mean=3.0, gen_max=4)
        rep = TraceReplayer(backend, trace, clock, step_secs=0.05,
                            seed=7, vocab_size=97, max_steps=5000)
        report = rep.run()
        assert "tenants" not in report

    def test_rejected_requests_count_as_shed_in_report(self):
        clock = ReplayClock()
        backend = FakeBackend(slots=4, queue_cap=64)
        tenants = [{"name": "t1", "api_key": "t1-key",
                    "requests_per_sec": 0.5, "burst_requests": 1.0}]
        gw = ServingGateway(backend, {"tenants": tenants},
                            clock=clock).start()
        try:
            trace = synthesize_trace(4.0, seed=11, base_rate=3.0,
                                     tenants=1, shared_fraction=1.0,
                                     shared_prefix_len=2,
                                     prompt_len_mean=4.0,
                                     prompt_len_max=8,
                                     gen_mean=3.0, gen_max=4)
            rep = TraceReplayer(HttpReplayDriver(gw), trace, clock,
                                step_secs=0.05, seed=7, vocab_size=97,
                                max_steps=5000)
            report = rep.run()
            assert report["shed"] > 0
            assert report["finished"] > 0
            assert report["shed"] + report["finished"] \
                == report["requests"]
            shed = [h for h in rep.handles if h.state == rq.SHED]
            assert all(h._record["reason"] == "gateway_rate"
                       for h in shed)
            assert gw.stats()["tenants"]["t1"]["http_429"] == len(shed)
        finally:
            gw.close()


# ---------------------------------------------------------------------------
class TestSubprocessSmoke:
    def test_fresh_interpreter_serves_one_request(self):
        """The satellite contract: a fresh interpreter builds a gateway
        on port 0, answers /healthz and one generate, and shuts down
        cleanly — no jax import anywhere on the path.  The eager package
        ``__init__``s DO pull jax, so the script stubs the parent
        packages and imports the gateway's module graph directly: if
        gateway/tenancy/request or any of their leaf deps imported jax,
        the assertion below would trip."""
        script = (
            "import importlib, json, os, sys, types, urllib.request\n"
            "assert 'jax' not in sys.modules\n"
            "root = os.getcwd()\n"
            "for name in ('deepspeed_tpu', 'deepspeed_tpu.serving',\n"
            "             'deepspeed_tpu.telemetry',\n"
            "             'deepspeed_tpu.runtime', 'deepspeed_tpu.utils'):\n"
            "    pkg = types.ModuleType(name)\n"
            "    pkg.__path__ = [os.path.join(root, *name.split('.'))]\n"
            "    sys.modules[name] = pkg\n"
            "rq = importlib.import_module('deepspeed_tpu.serving.request')\n"
            "ServingGateway = importlib.import_module(\n"
            "    'deepspeed_tpu.serving.gateway').ServingGateway\n"
            "assert 'jax' not in sys.modules\n"
            "class Backend:\n"
            "    def __init__(self):\n"
            "        self.queue = []\n"
            "    def submit(self, prompt, max_new_tokens=0,\n"
            "               request_id=None, eos_token_id=-1,\n"
            "               deadline_ms=0.0, stream=None, **kw):\n"
            "        req = rq.Request(prompt=list(prompt),\n"
            "                         max_new_tokens=max_new_tokens or 2,\n"
            "                         request_id=request_id or 'r1',\n"
            "                         stream=stream)\n"
            "        req.state = rq.QUEUED\n"
            "        self.queue.append(req)\n"
            "        return req\n"
            "    @property\n"
            "    def pending(self):\n"
            "        return bool(self.queue)\n"
            "    def step(self):\n"
            "        for req in list(self.queue):\n"
            "            pos = len(req.tokens)\n"
            "            done = pos + 1 >= req.max_new_tokens\n"
            "            req.emit_token(7 + pos, done)\n"
            "            if done:\n"
            "                req.state = rq.FINISHED\n"
            "                req.finish_reason = 'max_tokens'\n"
            "                self.queue.remove(req)\n"
            "    def drain(self, max_steps=None):\n"
            "        while self.queue:\n"
            "            self.step()\n"
            "gw = ServingGateway(Backend(), {'pump': True}).start()\n"
            "port = gw.port\n"
            "assert port != 0\n"
            "health = json.loads(urllib.request.urlopen(\n"
            "    gw.url + '/healthz', timeout=10).read())\n"
            "assert health['status'] == 'ok', health\n"
            "body = json.dumps({'prompt': [1, 2, 3],\n"
            "                   'max_new_tokens': 3,\n"
            "                   'stream': False}).encode()\n"
            "req = urllib.request.Request(\n"
            "    gw.url + '/v1/generate', data=body,\n"
            "    headers={'Content-Type': 'application/json'},\n"
            "    method='POST')\n"
            "out = json.loads(urllib.request.urlopen(\n"
            "    req, timeout=30).read())\n"
            "assert out['state'] == 'finished', out\n"
            "assert out['tokens'] == [7, 8, 9], out\n"
            "gw.close()\n"
            "print('GATEWAY_OK', port)\n")
        res = subprocess.run([sys.executable, "-c", script],
                             capture_output=True, text=True, cwd=REPO,
                             timeout=120)
        assert res.returncode == 0, res.stderr
        assert "GATEWAY_OK" in res.stdout


# ---------------------------------------------------------------------------
class TestTelemetryReport:
    """The ``gateway`` section of ``tools/telemetry_report.py``: the
    per-tenant request/shed/reject/TTFT aggregates, in all three output
    formats."""

    def _write_events(self, tmp_path):
        from deepspeed_tpu.telemetry.events import dumps, make_event

        evs = [
            make_event("gateway", "request.finished", 1, 0,
                       {"tenant": "acme", "outcome": "ok", "reason": "",
                        "request_id": "gw-1", "tokens": 4,
                        "ttft_ms": 12.5, "budget_remaining": 1.0}),
            make_event("gateway", "request.finished", 2, 0,
                       {"tenant": "acme", "outcome": "ok", "reason": "",
                        "request_id": "gw-2", "tokens": 2,
                        "ttft_ms": 30.0, "budget_remaining": 1.0}),
            make_event("gateway", "request.finished", 3, 0,
                       {"tenant": "spam", "outcome": "shed",
                        "reason": "slow_reader", "request_id": "gw-3",
                        "tokens": 1, "ttft_ms": None,
                        "budget_remaining": 0.5}),
            make_event("gateway", "request.rejected", 4, 0,
                       {"tenant": "spam", "reason": "rate",
                        "status": 429}),
        ]
        path = tmp_path / "telemetry.jsonl"
        path.write_text("\n".join(dumps(e) for e in evs) + "\n")
        return str(path)

    def test_aggregate_and_render(self, tmp_path):
        from tools.telemetry_report import aggregate, render

        from deepspeed_tpu.telemetry.events import load_events

        path = self._write_events(tmp_path)
        agg = aggregate(load_events(path))["gateway"]
        assert agg["events"] == 4
        acme, spam = agg["tenants"]["acme"], agg["tenants"]["spam"]
        assert acme["finished"] == 2 and acme["tokens"] == 6
        assert acme["ttft_ms_p50"] == 12.5
        assert acme["ttft_ms_p95"] == 30.0
        assert spam["shed"] == 1 and spam["rejected"] == 1
        assert spam["shed_reasons"] == {"slow_reader": 1}
        assert spam["reject_reasons"] == {"rate": 1}
        assert spam["budget_remaining"] == 0.5
        text = render(path)
        assert ("gateway: 2 finished, 1 shed mid-stream, 1 rejected "
                "at the door (2 tenant(s))") in text
        assert "tenant acme: 2 finished" in text
        assert "spam refusals: rate: 1, slow_reader: 1" in text
        md = render(path, markdown=True)
        assert "### gateway:" in md
        assert "| tenant | finished | shed | rejected |" in md
        assert "| acme | 2 | 0 | 0 | 6 | 12.5/30.0 | 1.0 |" in md

    def test_json_payload_carries_gateway_bucket(self, tmp_path, capsys):
        from tools.telemetry_report import main

        path = self._write_events(tmp_path)
        main([path, "--json"])
        payload = json.loads(capsys.readouterr().out)
        assert payload["gateway"]["tenants"]["acme"]["finished"] == 2

    def test_empty_stream_renders_no_gateway_section(self, tmp_path):
        from tools.telemetry_report import render

        path = tmp_path / "telemetry.jsonl"
        path.write_text("")
        assert "gateway" not in render(str(path))


# ---------------------------------------------------------------------------
# heavy: the real substrate + the zero-overhead pin
# ---------------------------------------------------------------------------
def _real_gateway(serving=None, clock=None, seed=0):
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    from deepspeed_tpu.parallel.topology import reset_topology

    reset_topology()
    cfg = GPT2Config.tiny(dtype=jnp.float32)
    kwargs = {}
    if clock is not None:
        kwargs["clock"] = clock
    return deepspeed_tpu.init_serving(
        GPT2LMHeadModel(cfg), dtype="fp32", seed=seed,
        serving=serving, **kwargs)


@pytest.mark.heavy
class TestGatewayOverRealEngines:
    def test_sse_stream_bit_matches_direct_submit(self):
        """Acceptance: a greedy SSE stream through the gateway is
        byte-for-byte the direct ``submit()`` stream on the same
        engine."""
        gw = _real_gateway(serving={"block_size": 8, "decode_slots": 2,
                                    "default_max_new_tokens": 8,
                                    "gateway": {}})
        assert isinstance(gw, ServingGateway)
        try:
            prompt = [5, 6, 7, 8]
            direct = gw.submit(prompt, max_new_tokens=6)
            gw.drain(max_steps=100)
            assert direct.state == rq.FINISHED
            events = []
            reader = threading.Thread(
                target=lambda: events.extend(_sse_events(_post(
                    gw.url, {"prompt": prompt, "max_new_tokens": 6}))),
                daemon=True)
            reader.start()
            deadline = time.monotonic() + 60
            while reader.is_alive() and time.monotonic() < deadline:
                if gw.pending:
                    gw.step()
                else:
                    time.sleep(0.01)
            reader.join(5)
            assert not reader.is_alive()
            toks = [e[1]["token"] for e in events if e[0] == "token"]
            assert toks == direct.tokens
            assert events[-1][0] == "done"
        finally:
            gw.destroy()

    def test_sampled_sse_stream_bit_matches_keyed_generate(self):
        """The sampling contract through the front door: a seeded
        sampled request over HTTP emits exactly the tokens of the
        engine's solo keyed ``generate()`` — the gateway threads
        seed/knobs verbatim and the per-tenant sampled counter ticks."""
        import jax.numpy as jnp

        gw = _real_gateway(serving={"block_size": 8, "decode_slots": 2,
                                    "default_max_new_tokens": 8,
                                    "sampling": {"enabled": True},
                                    "gateway": {}})
        try:
            prompt = [5, 17, 42, 9]
            engine = gw.backend.engine
            out = engine.generate(jnp.asarray([prompt]), max_new_tokens=4,
                                  do_sample=True, seed=7, temperature=0.8,
                                  top_p=0.9)
            expect = [int(t) for t in out[0, len(prompt):]]
            events = []
            reader = threading.Thread(
                target=lambda: events.extend(_sse_events(_post(
                    gw.url, {"prompt": prompt, "max_new_tokens": 4,
                             "do_sample": True, "seed": 7,
                             "temperature": 0.8, "top_p": 0.9}))),
                daemon=True)
            reader.start()
            deadline = time.monotonic() + 60
            while reader.is_alive() and time.monotonic() < deadline:
                if gw.pending:
                    gw.step()
                else:
                    time.sleep(0.01)
            reader.join(5)
            assert not reader.is_alive()
            toks = [e[1]["token"] for e in events if e[0] == "token"]
            assert toks == expect
            assert events[-1][0] == "done"
            assert gw.stats()["tenants"][ANONYMOUS]["sampled"] == 1
        finally:
            gw.destroy()

    def test_disconnect_releases_real_kv_blocks(self):
        """A vanished client frees the decode slot AND its KV blocks on
        the real engine — pinned through the block-manager gauges."""
        gw = _real_gateway(serving={"block_size": 8, "decode_slots": 2,
                                    "default_max_new_tokens": 8,
                                    "gateway": {"pump": True,
                                                "poll_secs": 0.01}})
        try:
            free0 = gw.backend.gauges()["free_blocks"]
            # long enough to outlive the client, short enough to fit the
            # tiny engine's max_len=64 window (4096 would shed at admit)
            body = json.dumps({"prompt": [3, 4, 5],
                               "max_new_tokens": 48}).encode("utf-8")
            conn = socket.create_connection(("127.0.0.1", gw.port),
                                            timeout=30)
            conn.sendall(b"POST /v1/generate HTTP/1.1\r\n"
                         b"Host: x\r\nContent-Type: application/json\r\n"
                         + f"Content-Length: {len(body)}\r\n\r\n".encode()
                         + body)
            seen = b""
            while b"event: token" not in seen:
                chunk = conn.recv(4096)
                assert chunk, "stream ended before first token"
                seen += chunk
            gauges = gw.backend.gauges()
            assert gauges["free_blocks"] < free0     # blocks are held
            conn.close()
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                gauges = gw.backend.gauges()
                if gauges["free_blocks"] == free0 \
                        and gauges["slots_busy"] == 0:
                    break
                time.sleep(0.05)
            assert gauges["free_blocks"] == free0, gauges
            assert gauges["slots_busy"] == 0
            assert gw.stats()["tenants"][ANONYMOUS]["inflight"] == 0
        finally:
            gw.destroy()

    def test_e2e_trace_replay_over_two_replica_fleet(self):
        """The e2e acceptance: a seeded diurnal + Zipf-tenant trace over
        HTTP through the gateway against a REAL two-replica fleet is
        bit-deterministic across runs under fake clocks — per-tenant
        report, fleet decisions and every token stream pinned."""
        trace = synthesize_trace(
            3.0, seed=23, base_rate=1.5, diurnal_fraction=0.5,
            diurnal_period_secs=3.0, tenants=2, shared_fraction=1.0,
            shared_prefix_len=3, prompt_len_mean=4.0, prompt_len_max=8,
            gen_mean=3.0, gen_max=4)
        serving = {"block_size": 8, "decode_slots": 2,
                   "default_max_new_tokens": 4,
                   "router": {"replicas": 2},
                   "fleet": {"min_replicas": 1, "max_replicas": 2},
                   "gateway": {"tenants": [
                       {"name": "t1", "api_key": "t1-key",
                        "slo_class": "gold"},
                       {"name": "t2", "api_key": "t2-key"}]}}

        def run_once():
            clock = ReplayClock()
            gw = _real_gateway(serving=serving, clock=clock)
            assert isinstance(gw, ServingGateway)
            assert isinstance(gw.backend, FleetManager)
            try:
                rep = TraceReplayer(HttpReplayDriver(gw), trace, clock,
                                    step_secs=0.05, seed=31,
                                    vocab_size=97, max_steps=4000)
                report = rep.run()
                streams = {h.request_id: tuple(h.tokens)
                           for h in rep.handles}
                fleet = gw.backend.stats()
                decisions = {k: fleet.get(k) for k in
                             ("scale_ups", "scale_downs", "drains_lost")}
            finally:
                gw.destroy()
            return report, streams, decisions

        first, second = run_once(), run_once()
        assert first == second
        report, streams, _ = first
        assert report["incomplete"] == 0
        assert set(report["tenants"]) == {"t1", "t2"}
        assert all(streams.values())

    def test_gold_burst_finishes_whole_while_best_effort_sheds(self):
        """Quota isolation on the real engine: in one concurrent
        two-tenant burst through a pumping gateway every gold request
        finishes, while the rate-capped best_effort tenant is shed at
        the door with 429s."""
        from concurrent.futures import ThreadPoolExecutor

        gw = _real_gateway(serving={
            "block_size": 8, "decode_slots": 2, "max_queue_depth": 16,
            "gateway": {"pump": True, "poll_secs": 0.002, "tenants": [
                {"name": "gold", "api_key": "gold-key",
                 "slo_class": "gold", "requests_per_sec": 10000.0},
                {"name": "be", "api_key": "be-key",
                 "slo_class": "best_effort", "requests_per_sec": 1.0,
                 "burst_requests": 1}]}})

        def one(i):
            key = "gold-key" if i % 2 == 0 else "be-key"
            body = {"prompt": [3 + i, 4, 5, 6][:2 + i % 3],
                    "max_new_tokens": 4, "stream": False}
            try:
                with _post(gw.url, body, key=key, timeout=120) as resp:
                    return key, json.loads(resp.read())["state"]
            except urllib.error.HTTPError as e:
                with e:
                    return key, e.code

        try:
            with ThreadPoolExecutor(max_workers=6) as pool:
                results = list(pool.map(one, range(6)))
        finally:
            gw.destroy()
        assert [s for k, s in results if k == "gold-key"] \
            == [rq.FINISHED] * 3
        assert [s for k, s in results if k == "be-key"].count(429) >= 1

    def test_gateway_block_leaves_decode_hlo_byte_identical(self):
        """Zero-overhead pin (the PR 2-12 convention): the gateway is
        pure host-side policy — a serving config WITH a gateway+tenants
        block compiles the exact same decode program as one without."""
        import jax.numpy as jnp

        import deepspeed_tpu
        from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
        from deepspeed_tpu.parallel.topology import reset_topology
        from deepspeed_tpu.serving import ServingEngine

        texts = []
        for extra in ({}, {"gateway": {"tenants": TENANTS,
                                       "overload_reject_threshold": 0.9}}):
            reset_topology()
            cfg = GPT2Config.tiny(dtype=jnp.float32)
            eng = deepspeed_tpu.init_inference(
                GPT2LMHeadModel(cfg), dtype="fp32",
                serving={"block_size": 8, "decode_slots": 2, **extra})
            srv = ServingEngine(eng)
            fn = srv._build_decode()
            lowered = fn.lower(
                eng.params, srv.cache,
                jnp.zeros((2, 1), jnp.int32),
                jnp.asarray(srv._tables), jnp.asarray(srv._lengths),
                srv._next_rng())
            texts.append(lowered.compile().as_text())
            srv.destroy()
        assert texts[0] == texts[1]
