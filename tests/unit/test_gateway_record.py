"""The front door measures itself (ISSUE 41).

A real ``ServingGateway`` (real sockets, real handler threads) under a
clock that moves only where a test moves it: over a stub backend and over
``ServingEngine`` with a tiny model.

- the gateway's six fields of the request's record, worked out by hand,
  and the identity ``ttft_wire_ms = ingress_ms + (first_emit - submitted)
  + first_egress_ms``;
- a JSON reply's ``None``s; a request shed by the backend, a client that
  went away and a slow reader: fields as far as the request got, and
  ``stats()["front_door"]`` counting each once;
- ``ds.gateway.ingress`` and ``ds.gateway.sse_write`` in a real profiler
  trace, on a handler thread;
- the ``ingress`` and ``deliver`` spans under ``telemetry.tracing`` and
  none without it;
- ``ds_gateway_ttft_ms`` and the tenant's SLO outcome observing accept ->
  first flush.
"""

import json
import socket
import time

import pytest

from deepspeed_tpu.serving import gateway as gateway_mod
from deepspeed_tpu.serving.gateway import ServingGateway
from deepspeed_tpu.telemetry.events import SPANS
from tests.unit.test_brackets import MsClock
from tests.unit.test_gateway import (ANONYMOUS, ArmedTelemetry, FakeBackend,
                                     _post, _post_err, _sse_events, _wait)
from tests.unit.test_router import FakeTelemetry

DOOR_FIELDS = ("ingress_ms", "first_egress_ms", "ttft_wire_ms",
               "egress_mean_ms", "egress_max_ms", "write_ms")
# what takes time, in whole milliseconds; nothing else does
PARSE_MS, SUBMIT_MS = 2, 3      # on the handler thread, before the engine
QUEUED_MS = 20                  # the request waits for the first step
STEP_MS = 10                    # between two steps of the loop
HOPS_MS = (4, 9, 2)             # a token's wait for its handler to wake
WRITE_MS = 1                    # inside ds.gateway.sse_write, a token
PREFILL_MS = 50                 # the engine's prefill program
NEW_TOKENS = len(HOPS_MS)
KEY = "acme-key"
# gold's target lies between the engine's side of TTFT and the wire's
TENANTS = {"tenants": [{"name": "acme", "api_key": KEY, "slo_class": "gold",
                        "trace_sample_rate": 1.0}],
           "gold": {"ttft_ms": 27.0, "error_budget": 0.5}}


class _Scripted:
    """The hooks that make time pass ON the handler thread, and the count
    of token events its loop has dealt with (the step loop goes on only
    when the handler is waiting again, so no two threads move the clock
    at once)."""

    def __init__(self, mp, gw, clock):
        self.processed = 0
        hops = iter(HOPS_MS)
        inner_bracket, inner_submit = gw._bracket, gw.backend.submit
        setup, parse, pull = (gateway_mod._Handler.setup,
                              gateway_mod._Handler._parse,
                              gateway_mod._Handler._pull)

        def bracket(phase, **kw):
            if phase == "sse_write":
                clock.advance(next(hops))
            return inner_bracket(phase, **kw)

        class SlowSocket:
            """The handler's ``wfile``: a token event takes WRITE_MS."""

            def __init__(self, wfile):
                self.wfile = wfile

            def write(self, data):
                if data.startswith(b"event: token"):
                    clock.advance(WRITE_MS)
                return self.wfile.write(data)

            def __getattr__(self, name):
                return getattr(self.wfile, name)

        def slow_setup(handler):
            setup(handler)
            handler.wfile = SlowSocket(handler.wfile)

        def slow_parse(handler, raw):
            clock.advance(PARSE_MS)
            return parse(handler, raw)

        def slow_submit(prompt, **kw):
            handle = inner_submit(prompt, **kw)
            clock.advance(SUBMIT_MS)
            return handle

        def counted_pull(handler, gw_, handle, stream):
            for item in pull(handler, gw_, handle, stream):
                yield item
                self.processed += 1

        mp.setattr(gw, "_bracket", bracket)
        mp.setattr(gw.backend, "submit", slow_submit)
        mp.setattr(gateway_mod._Handler, "setup", slow_setup)
        mp.setattr(gateway_mod._Handler, "_parse", slow_parse)
        mp.setattr(gateway_mod._Handler, "_pull", counted_pull)


def _scripted_request(gw, clock, body, key=KEY):
    """One streamed request through the real HTTP path, the loop stepped
    by hand: ``(events, stream)``."""
    with pytest.MonkeyPatch.context() as mp:
        script = _Scripted(mp, gw, clock)
        resp = _post(gw.url, body, key=key)     # headers are out: admitted
        (stream,) = list(gw._streams.values())
        clock.advance(QUEUED_MS - STEP_MS)
        for _ in range(50):
            if not gw.pending:
                break
            clock.advance(STEP_MS)
            gw.step()
            assert _wait(lambda: script.processed >= stream.tokens)
        events = _sse_events(resp)
        assert _wait(lambda: stream.closed)
    return events, stream


def _span_events(events, name=None):
    return [e for e in events if e["kind"] == "span"
            and (name is None or e["name"] == name)]


# ---------------------------------------------------------------------------
# the stub backend: every number by hand
@pytest.fixture(scope="module")
def stub_run():
    clock = MsClock()
    clock.advance(1000)
    telemetry = ArmedTelemetry()
    gw = ServingGateway(FakeBackend(slots=2, queue_cap=8), TENANTS,
                        telemetry=telemetry, clock=clock).start()
    try:
        events, stream = _scripted_request(
            gw, clock, {"prompt": [1, 2], "max_new_tokens": NEW_TOKENS})
        (tenant,) = gw.tenants.tenants
        yield {"events": events, "record": events[-1][1], "stream": stream,
               "stats": gw.stats(), "telemetry": telemetry,
               "budget": tenant.budget_remaining(),
               "expo": telemetry.metrics.expose()}
    finally:
        gw.close()


# accept at 0; parse 2 + submit 3 -> submitted at 5; the first step at 25
# emits token 0; its handler wakes 4 later and writes for 1 -> flushed at
# 30. Token 1: emitted 40, flushed 50. Token 2: emitted 60, flushed 63.
STUB_EXPECTED = {"ingress_ms": 5.0, "first_egress_ms": 5.0,
                 "ttft_wire_ms": 30.0, "egress_mean_ms": 6.0,
                 "egress_max_ms": 10.0, "write_ms": 3.0}


@pytest.mark.parametrize("field", DOOR_FIELDS)
def test_stub_record_field_by_hand(stub_run, field):
    assert stub_run["record"][field] == pytest.approx(STUB_EXPECTED[field],
                                                      abs=1e-9)


def test_stub_record_keeps_the_backends_fields(stub_run):
    record = stub_run["record"]
    assert [e[0] for e in stub_run["events"]] == ["token"] * 3 + ["done"]
    assert record["state"] == "finished" and record["new_tokens"] == 3
    # a backend that stamps no time: first_emit - submitted, as before
    assert record["ttft_ms"] == 20.0
    json.dumps(record)


def test_stub_identity_of_the_wire_ttft(stub_run):
    record, stream = stub_run["record"], stub_run["stream"]
    assert stream.since_submit_ms() == pytest.approx(20.0, abs=1e-9)
    assert record["ttft_wire_ms"] == pytest.approx(
        record["ingress_ms"] + stream.since_submit_ms()
        + record["first_egress_ms"], abs=1e-9)


def test_stub_front_door_sums(stub_run):
    assert stub_run["stats"]["front_door"] == {
        "requests": 1, "tokens_written": 3, "ingress_secs": 0.005,
        "write_secs": 0.003, "egress_wait_secs": 0.018}


def test_ttft_histogram_observes_the_wire_value(stub_run):
    expo = stub_run["expo"]
    assert 'ds_gateway_ttft_ms_count{tenant="acme"} 1' in expo
    assert 'ds_gateway_ttft_ms_sum{tenant="acme"} 30' in expo


def test_slo_outcome_is_held_against_the_wire_value(stub_run):
    """gold's target is 27 ms: the engine's side of the door (20) meets
    it, accept -> first flush (30) does not."""
    assert stub_run["budget"] == 0.0
    (finished,) = stub_run["telemetry"].of("request.finished")
    assert finished["data"]["ttft_ms"] == pytest.approx(30.0)
    assert {k: finished["data"][k] for k in DOOR_FIELDS} == STUB_EXPECTED


def test_tracing_on_ingress_and_deliver_have_real_bounds(stub_run):
    events = stub_run["telemetry"].events
    (root,) = _span_events(events, "gateway")
    (ingress,) = _span_events(events, "ingress")
    (quota,) = _span_events(events, "quota")
    (deliver,) = _span_events(events, "deliver")
    def ms(span):       # from the accept, at 1000 ms on the clock
        return (span["data"]["start_ns"] / 1e6 - 1000.0,
                span["data"]["end_ns"] / 1e6 - 1000.0)

    assert ms(ingress) == pytest.approx((0.0, 5.0))
    assert ms(deliver) == pytest.approx((30.0, 63.0))
    assert ms(root)[0] == pytest.approx(0.0) and ms(root)[1] >= 63.0
    assert ms(ingress)[0] <= ms(quota)[0] <= ms(quota)[1] <= ms(ingress)[1]
    for child in (ingress, quota, deliver):
        assert child["data"]["parent"] == root["data"]["span"]
        assert child["data"]["trace"] == root["data"]["trace"]
    assert ingress["data"]["tenant"] == "acme"
    assert ingress["data"]["outcome"] == "ok"
    assert {k: deliver["data"][k] for k in
            ("tokens", "egress_mean_ms", "egress_max_ms")} == {
        "tokens": 3, "egress_mean_ms": 6.0, "egress_max_ms": 10.0}
    assert not _span_events(events, "auth")


@pytest.mark.parametrize("request_id", ["gw-1", 'q"uo\\te', "100%d", "é"])
def test_token_frame_is_the_sse_event_byte_for_byte(request_id):
    frame = gateway_mod._token_frame(request_id)
    for index, token in ((0, 0), (7, 815), (1023, 50256)):
        assert frame % (index, token) == gateway_mod._sse("token", {
            "token": token, "index": index, "request_id": request_id})


@pytest.mark.parametrize("gone", ["fwd", "bwd", "reduce", "auth"])
def test_span_names_nothing_emits_are_gone(gone):
    assert gone not in SPANS
    assert {"ingress", "deliver", "quota", "gateway"} <= set(SPANS)


# ---------------------------------------------------------------------------
# ServingEngine behind the same door
@pytest.fixture(scope="module", params=["tracing_off", "tracing_on"])
def engine_run(request):
    from deepspeed_tpu.serving import ServingEngine
    from tests.unit.test_brackets import _slow, _thread_annotations
    from tests.unit.test_serving import _SERVING, _tiny_serving

    tracing = request.param == "tracing_on"
    kwargs = {"telemetry": {"enabled": True, "jsonl": False, "memory": False,
                            "tracing": {"enabled": True}}} if tracing else {}
    clock = MsClock()
    clock.advance(1000)
    _, engine = _tiny_serving(serving=_SERVING, **kwargs)
    srv = ServingEngine(engine, clock=clock)
    for T in srv.buckets:
        srv._prefill_fns[T] = _slow(srv._build_prefill(T), clock, PREFILL_MS)
    gw = ServingGateway(srv, TENANTS, clock=clock).start()
    try:
        events, stream = _scripted_request(
            gw, clock, {"prompt": [5, 6, 7, 8], "max_new_tokens": NEW_TOKENS})
        out = {"tracing": tracing, "events": events,
               "record": events[-1][1], "stream": stream,
               "stats": gw.stats(), "spans": [
                   e for e in srv.telemetry.tail(512) if e["kind"] == "span"]}

        # a real profiler session over one more request, the clock
        # standing still: which thread opened which bracket
        def serve():
            resp = _post(gw.url, {"prompt": [9, 8, 7],
                                  "max_new_tokens": 2}, key=KEY)
            gw.drain()
            assert _sse_events(resp)[-1][0] == "done"

        out["annotations"] = [(name, thread) for thread, name, _, _
                              in _thread_annotations(serve)]
        yield out
    finally:
        gw.close()
        srv.destroy()


def test_engine_record_fields_by_hand(engine_run):
    """The same door in front of the engine: ingress 2 + 3; the first step
    comes 20 after, its prefill takes 50; hops and writes as scripted."""
    record = engine_run["record"]
    hop0 = HOPS_MS[0] + WRITE_MS
    assert {k: record[k] for k in DOOR_FIELDS} == pytest.approx({
        "ingress_ms": PARSE_MS + SUBMIT_MS, "first_egress_ms": hop0,
        "ttft_wire_ms": PARSE_MS + SUBMIT_MS + QUEUED_MS + PREFILL_MS + hop0,
        "egress_mean_ms": sum(HOPS_MS) / NEW_TOKENS + WRITE_MS,
        "egress_max_ms": max(HOPS_MS) + WRITE_MS,
        "write_ms": NEW_TOKENS * WRITE_MS}, abs=1e-9)


def test_engine_record_keeps_the_engines_fields(engine_run):
    record = engine_run["record"]
    assert record["state"] == "finished" and record["new_tokens"] == 3
    # the engine's own clock starts inside submit(): 3 + 20 + 50
    assert record["ttft_ms"] == SUBMIT_MS + QUEUED_MS + PREFILL_MS
    assert record["prefill_ms"] == PREFILL_MS
    assert record["decode_steps"] is not None
    json.dumps(record)


def test_engine_identity_of_the_wire_ttft(engine_run):
    record, stream = engine_run["record"], engine_run["stream"]
    assert stream.since_submit_ms() == pytest.approx(QUEUED_MS + PREFILL_MS)
    assert record["ttft_wire_ms"] == pytest.approx(
        record["ingress_ms"] + stream.since_submit_ms()
        + record["first_egress_ms"], abs=1e-9)


def test_engine_front_door_counts_the_request_once(engine_run):
    door = engine_run["stats"]["front_door"]
    assert door["requests"] == 1 and door["tokens_written"] == NEW_TOKENS
    assert door["ingress_secs"] == pytest.approx(0.005)
    assert door["write_secs"] == pytest.approx(0.003)
    assert door["egress_wait_secs"] == pytest.approx(0.018)


def test_engine_spans_only_under_tracing(engine_run):
    spans = engine_run["spans"]
    if not engine_run["tracing"]:
        assert spans == []
        return
    names = [s["name"] for s in spans]
    assert names.count("ingress") == names.count("deliver") == 1
    assert names.count("gateway") == 1 and "auth" not in names
    (root,) = [s for s in spans if s["name"] == "gateway"]
    (ingress,) = [s for s in spans if s["name"] == "ingress"]
    (deliver,) = [s for s in spans if s["name"] == "deliver"]
    # the engine's serve span joins the gateway's trace
    (serve,) = [s for s in spans if s["name"] == "serve"]
    for child in (ingress, deliver, serve):
        assert child["data"]["trace"] == root["data"]["trace"]
        assert child["data"]["parent"] == root["data"]["span"]
    # (to_ns truncates a float of seconds: a nanosecond either way)
    assert ingress["data"]["end_ns"] - ingress["data"]["start_ns"] \
        == pytest.approx((PARSE_MS + SUBMIT_MS) * 1_000_000, abs=2)
    assert deliver["data"]["tokens"] == NEW_TOKENS
    assert deliver["data"]["egress_max_ms"] == max(HOPS_MS) + WRITE_MS
    assert root["data"]["start_ns"] == ingress["data"]["start_ns"]
    assert root["data"]["end_ns"] >= deliver["data"]["end_ns"]


def test_profiler_sees_ingress_and_sse_write_on_a_handler_thread(engine_run):
    seen = engine_run["annotations"]
    names = {n for n, _ in seen}
    assert {"ds.gateway.ingress", "ds.gateway.sse_write",
            "ds.serve.step"} <= names, names
    step_threads = {t for n, t in seen if n == "ds.serve.step"}
    door_threads = {t for n, t in seen if n in ("ds.gateway.ingress",
                                                "ds.gateway.sse_write")}
    # the loop ran on this thread, the door's brackets on the handler's
    assert len(step_threads) == 1 and not door_threads & step_threads
    assert {t for n, t in seen if n == "ds.gateway.ingress"} \
        == {t for n, t in seen if n == "ds.gateway.sse_write"}
    assert sum(n == "ds.gateway.ingress" for n, _ in seen) == 1
    assert sum(n == "ds.gateway.sse_write" for n, _ in seen) == 2


# ---------------------------------------------------------------------------
# replies that stream nothing, and requests that do not reach their end
@pytest.fixture()
def plain_gw():
    telemetry = FakeTelemetry()
    backend = FakeBackend(slots=2, queue_cap=1)
    gw = ServingGateway(backend, {"pump": True, "poll_secs": 0.01,
                                  "send_queue_tokens": 4},
                        telemetry=telemetry).start()
    yield gw, backend, telemetry
    gw.close()


def test_json_reply_has_no_flush_a_token(plain_gw):
    gw, _, telemetry = plain_gw
    out = json.loads(_post(gw.url, {"prompt": [2, 3], "max_new_tokens": 3,
                                    "stream": False}).read())
    record = out["record"]
    assert out["state"] == "finished" and len(out["tokens"]) == 3
    assert record["ingress_ms"] is not None and record["ingress_ms"] >= 0
    assert [record[k] for k in DOOR_FIELDS if k != "ingress_ms"] == [None] * 5
    assert _wait(lambda: gw.stats()["front_door"]["requests"] == 1)
    door = gw.stats()["front_door"]
    assert door["tokens_written"] == 0 and door["write_secs"] == 0.0
    assert door["egress_wait_secs"] == 0.0 and door["ingress_secs"] >= 0
    # no tracer behind this gateway: events, and not one span
    assert telemetry.of("request.finished") and not _span_events(
        telemetry.events)


def test_backend_shed_is_counted_once_with_its_ingress(plain_gw):
    gw, backend, telemetry = plain_gw
    backend.queue_cap = 0           # the backend's own admission says no
    code, payload, _ = _post_err(gw.url, {"prompt": [1],
                                          "max_new_tokens": 2})
    assert code == 503 and payload["error"]["reason"] == "backend_shed"
    (finished,) = telemetry.of("request.finished")
    data = finished["data"]
    assert data["outcome"] == "shed" and data["ingress_ms"] >= 0
    assert [data[k] for k in DOOR_FIELDS if k != "ingress_ms"] == [None] * 5
    door = gw.stats()["front_door"]
    assert door["requests"] == 1 and door["tokens_written"] == 0


def test_refusal_at_the_door_is_no_front_door_request(plain_gw):
    gw, _, _ = plain_gw
    code, _, _ = _post_err(gw.url, {"prompt": "not a list"})
    assert code == 400
    assert gw.stats()["front_door"]["requests"] == 0


def test_disconnect_keeps_the_fields_as_far_as_it_got(plain_gw):
    gw, backend, telemetry = plain_gw
    body = json.dumps({"prompt": [4, 4],
                       "max_new_tokens": 100000}).encode("utf-8")
    conn = socket.create_connection(("127.0.0.1", gw.port), timeout=10)
    conn.sendall(b"POST /v1/generate HTTP/1.1\r\nHost: x\r\n"
                 b"Content-Type: application/json\r\n"
                 + f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
    seen = b""
    while b"event: token" not in seen:
        chunk = conn.recv(4096)
        assert chunk, "stream ended before first token"
        seen += chunk
    conn.close()
    assert _wait(lambda: telemetry.of("request.finished"), timeout=30)
    (finished,) = telemetry.of("request.finished")
    data = finished["data"]
    assert data["outcome"] == "shed"
    assert data["reason"] in ("disconnect", "slow_reader")
    # it streamed before the client went: every field has a value
    assert all(data[k] is not None and data[k] >= 0 for k in DOOR_FIELDS)
    assert data["ttft_ms"] == pytest.approx(data["ttft_wire_ms"], abs=1e-3)
    assert data["egress_max_ms"] >= data["egress_mean_ms"]
    time.sleep(0.1)                 # a second _finish would count again
    door = gw.stats()["front_door"]
    assert door["requests"] == 1 and door["tokens_written"] >= 1
    assert door["write_secs"] > 0 and door["egress_wait_secs"] > 0
    assert gw.stats()["tenants"][ANONYMOUS]["inflight"] == 0


def test_slow_reader_is_counted_once(plain_gw):
    gw, backend, telemetry = plain_gw
    victim = _post(gw.url, {"prompt": [1, 1], "max_new_tokens": 50000})
    assert _wait(lambda: backend.cancels, timeout=30)
    assert backend.cancels[0][1] == "slow_reader"
    victim.close()
    assert _wait(lambda: telemetry.of("request.finished"), timeout=30)
    (finished,) = telemetry.of("request.finished")
    data = finished["data"]
    assert data["outcome"] == "shed" and data["tokens"] >= 4
    assert data["ingress_ms"] >= 0 and data["ttft_wire_ms"] > 0
    # its queue overflowed: tokens waited there for a handler held by
    # the full socket, and the largest wait says so
    assert data["egress_max_ms"] >= data["egress_mean_ms"] > 0
    time.sleep(0.1)
    door = gw.stats()["front_door"]
    assert door["requests"] == 1
    assert 1 <= door["tokens_written"] <= data["tokens"]


def test_gateway_over_a_backend_with_no_telemetry_still_measures():
    gw = ServingGateway(FakeBackend(), {}).start()
    try:
        resp = _post(gw.url, {"prompt": [3], "max_new_tokens": 2})
        while gw.pending:
            gw.step()
        events = _sse_events(resp)
        record = events[-1][1]
        assert all(record[k] is not None and record[k] >= 0
                   for k in DOOR_FIELDS)
        assert record["ttft_wire_ms"] >= record["ingress_ms"] \
            + record["first_egress_ms"] - 2e-3
        assert record["egress_max_ms"] >= record["egress_mean_ms"]
        assert _wait(lambda: gw.stats()["front_door"]["requests"] == 1)
    finally:
        gw.close()
