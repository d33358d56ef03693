"""Span-based causal tracing over the telemetry event stream.

The flat JSONL families (PR 2) record *what* happened; spans record what
happened **because of what**: every span carries a ``trace`` id shared
by causally-related work, its own ``span`` id, an optional ``parent``
span id, and monotonic ``start_ns``/``end_ns`` bounds. Two trace shapes
ride the stream:

- **serving request traces** — one trace per request: ``serve`` root ->
  ``queue`` -> ``cow`` -> ``prefill`` or each ``prefill_chunk`` ->
  one ``decode`` segment -> finish/``shed`` (plus, under speculative
  decoding, per-step ``draft``/``verify``/``spec_commit`` legs), and
  (behind the multi-replica router) one ``attempt`` subtree per replica
  dispatch, so a failover CONTINUES the same trace on the survivor
  instead of starting a new one.
- **serving step traces** — one trace per scheduler iteration that did
  work: a ``serve_step`` root with ``schedule``/``decode_step``/``emit``
  children (the request-scoped prefill spans above carry the rest).
- **training step traces** — one trace per optimizer step: a ``step``
  root with phase children (``data``/``fwd_bwd``/``optimizer``);
  checkpoint IO gets its own ``ckpt_io`` trace.

Every host block is bracketed ONE way, by :class:`Brackets`: the bracket
always opens a profiler annotation ``ds.<layer>.<phase>`` (so any
profiler session sees the phase on the device trace's clock, telemetry
on or off), emits the JSONL span above when ``telemetry.tracing`` is on,
and adds its elapsed time to the owner's always-on ledger.

Design rules, all load-bearing:

- **Spans are emitted at END, as completed records.** There is no live
  context to propagate through the scheduler or across replicas — just
  timestamps the request/step bookkeeping already carries, converted at
  emit time. A crash mid-span loses exactly that span, nothing dangles.
- **Exception-isolated**: ``record_span`` never raises into the step or
  the serving loop; a broken sink degrades tracing, not training.
- **No host syncs, no device work**: span bookkeeping reads
  ``monotonic_ns`` and writes JSON lines. The compiled step/decode HLO
  is byte-identical with tracing absent, disabled, or enabled (pinned
  in ``tests/unit/test_tracing.py``).
- Span *names* are literals from :data:`telemetry.events.SPANS`
  (graft-lint GL05 pins every emit site); *ids* are process-local
  counters — cheap, deterministic under fake clocks, unique within the
  one rank-0 stream they land in.

This module is host-only (no jax imports — GL01-pinned) so the serving
policy tier and the report tooling can load it anywhere.
"""

import itertools
import time
from typing import Callable, Dict, List, Optional

from deepspeed_tpu.telemetry.events import SPANS

_span_ids = itertools.count(1)
_trace_ids = itertools.count(1)


def monotonic_ns() -> int:
    return time.monotonic_ns()


def to_ns(monotonic_secs: float) -> int:
    """Monotonic seconds (the request/scheduler timestamp base — real or
    fake clock) -> integer nanoseconds on the span timebase."""
    return int(monotonic_secs * 1e9)


class SpanHandle:
    """An OPEN span: holds ids + start; ``end()`` emits the record."""

    __slots__ = ("tracer", "name", "trace", "span", "parent", "start_ns",
                 "attrs", "_done")

    def __init__(self, tracer, name, trace, span, parent, start_ns, attrs):
        self.tracer = tracer
        self.name = name
        self.trace = trace
        self.span = span
        self.parent = parent
        self.start_ns = start_ns
        self.attrs = attrs
        self._done = False

    def end(self, end_ns: Optional[int] = None, **attrs):
        if self._done:  # idempotent: double-ends must not double-emit
            return
        self._done = True
        self.attrs.update(attrs)
        self.tracer._emit(self.name, self.trace, self.span, self.parent,
                          self.start_ns,
                          monotonic_ns() if end_ns is None else int(end_ns),
                          self.attrs)


class Tracer:
    """Span recorder over one telemetry ``emit`` callable. Disabled
    tracers are inert attribute bags — every public method is a
    two-instruction early return, so the hot paths can call them
    unconditionally."""

    def __init__(self, emit: Optional[Callable] = None, enabled: bool = True,
                 step_of: Optional[Callable] = None):
        self._emit_fn = emit
        self.enabled = bool(enabled) and emit is not None
        # optional current-step provider so span events land next to the
        # right step counter in the stream
        self._step_of = step_of
        self.dropped = 0

    # ------------------------------------------------------------------
    def new_trace(self, hint: Optional[str] = None) -> str:
        """Fresh trace id. ``hint`` (a request id, a step counter) makes
        the id human-greppable in the raw JSONL."""
        n = next(_trace_ids)
        return f"t{n}-{hint}" if hint else f"t{n}"

    def _emit(self, name, trace, span, parent, start_ns, end_ns, attrs):
        try:
            data = {"trace": trace, "span": span, "parent": parent,
                    "start_ns": int(start_ns), "end_ns": int(end_ns)}
            if attrs:
                data.update(attrs)
            step = self._step_of() if self._step_of is not None else None
            self._emit_fn("span", name, step=step, data=data)
        except Exception:  # noqa: BLE001 — tracing must never break a step
            self.dropped += 1

    def record_span(self, name: str, trace: str, start_ns: int,
                    end_ns: int, parent: Optional[str] = None,
                    **attrs) -> Optional[str]:
        """Emit one COMPLETED span retroactively from timestamps the
        caller already holds. Returns the span id (None when disabled)."""
        if not self.enabled:
            return None
        span = f"s{next(_span_ids)}"
        self._emit(name, trace, span, parent, start_ns, end_ns, attrs)
        return span

    def begin(self, name: str, trace: str, parent: Optional[str] = None,
              start_ns: Optional[int] = None, **attrs) -> Optional[SpanHandle]:
        """Open a span whose end is not yet known (e.g. an ``attempt``
        that outlives the current call). Returns None when disabled —
        callers keep the handle-or-None and call ``end()`` through
        :func:`end_span`."""
        if not self.enabled:
            return None
        return SpanHandle(self, name, trace, f"s{next(_span_ids)}", parent,
                          monotonic_ns() if start_ns is None
                          else int(start_ns), dict(attrs))


def end_span(handle: Optional[SpanHandle], end_ns: Optional[int] = None,
             **attrs) -> None:
    """``handle.end(...)`` that tolerates the disabled-tracer None."""
    if handle is not None:
        handle.end(end_ns=end_ns, **attrs)


def span_id(handle: Optional[SpanHandle]) -> Optional[str]:
    return None if handle is None else handle.span


# shared inert instance for components built without telemetry
NULL_TRACER = Tracer(emit=None, enabled=False)

class StepTrace:
    """Per-step phase accounting: the step-scoped sink of
    :class:`Brackets`.

    The training engines bracket host-observable phases (``data`` fetch,
    the ``fwd_bwd`` dispatch, the ``optimizer`` apply) and the serving
    engine its scheduler iteration (``schedule``/``decode_step``/
    ``emit``); each closed bracket lands here through :meth:`mark`. At
    the step boundary :meth:`flush` emits one root span covering
    first-phase-start -> boundary plus one child span per recorded
    phase, all under a fresh per-step trace id. With tracing off nothing
    is recorded and ``flush`` is an attribute read.

    Phase durations are HOST-side dispatch walltimes: under JAX's async
    dispatch a phase that merely enqueues device work reads as cheap
    unless an existing fence (loss fetch, donation pressure) already
    serializes it. That is by design — adding fences to make the numbers
    "device-true" would violate the no-added-host-syncs contract; the
    device-true picture is the profiler's, where the same brackets show
    as ``ds.*`` annotations beside the device operations.
    """

    def __init__(self, tracer: Tracer, rank: int = 0, root: str = "step"):
        self.tracer = tracer
        self.enabled = tracer.enabled
        self.rank = rank
        self.root = root
        self._phases: List[tuple] = []

    def mark(self, name: str, start_ns: int, end_ns: int, **attrs) -> None:
        """Record one timed phase of the current step."""
        if self.enabled:
            self._phases.append((name, int(start_ns), int(end_ns), attrs))

    def flush(self, step: int, **step_attrs) -> Optional[str]:
        """Emit the step's root span + phase children and reset. No-op
        (returns None) when nothing was recorded: a step that bracketed
        no phase emits no empty root."""
        if not self.enabled or not self._phases:
            self._phases = []
            return None
        phases, self._phases = self._phases, []
        trace = self.tracer.new_trace(hint=f"step{step}-r{self.rank}")
        start = min(t0 for _, t0, _, _ in phases)
        end = max(t1 for _, _, t1, _ in phases)
        root = self.tracer.record_span(
            self.root, trace, start, end, step=int(step), **step_attrs)
        for name, t0, t1, attrs in phases:
            self.tracer.record_span(name, trace, t0, t1, parent=root,
                                    **attrs)
        return trace


# ``trace=`` default of a bracket: the span belongs to the current step
STEP_SCOPE = object()


class _Open:
    """One open bracket (see :class:`Brackets`). ``t0``/``t1`` are the
    owner's clock at entry/exit, read only when a sink needs them (a
    ledger key or an active span sink): callers that bracket a fence may
    take their ``now`` from ``t1`` instead of reading the clock again."""

    __slots__ = ("_b", "_name", "_span", "_trace", "_ledger", "_attrs",
                 "_ann", "t0", "t1")

    def __init__(self, b, name, span, trace, ledger, attrs):
        self._b = b
        self._name = name
        self._span = span
        self._trace = trace
        self._ledger = ledger
        self._attrs = attrs
        self.t0 = self.t1 = None

    def __enter__(self):
        b = self._b
        if b.annotate is not None:
            self._ann = ann = (b.annotate(self._name, **self._attrs)
                               if self._attrs else b.annotate(self._name))
            ann.__enter__()
        if self._ledger is not None or self._span is not None:
            self.t0 = b.clock()
        return self

    def __exit__(self, *exc):
        b = self._b
        if self.t0 is not None:
            self.t1 = t1 = b.clock()
            if self._ledger is not None:
                b.ledger[self._ledger] += t1 - self.t0
            if self._span is not None:
                self._emit(b, to_ns(self.t0), to_ns(t1))
        if b.annotate is not None:
            self._ann.__exit__(*exc)
        return False

    def _emit(self, b, start_ns, end_ns):
        trace = self._trace
        if trace is STEP_SCOPE:
            b.step_trace.mark(self._span, start_ns, end_ns, **self._attrs)
        else:
            b.tracer.record_span(self._span, trace["trace"], start_ns,
                                 end_ns, parent=trace.get("serve_id"),
                                 **self._attrs)


class Brackets:
    """THE bracket around a host block: ``with brackets("decode",
    ledger="decode"): ...``. One context manager, three sinks:

    - **always** a profiler annotation ``ds.<layer>.<phase>`` (``attrs``
      ride along as its metadata), telemetry on or off: with no profiler
      session that is a sub-microsecond no-op, with one the phase lies on
      the device trace's own clock. This module stays jax-free: the
      annotation factory (``jax.profiler.TraceAnnotation``) is handed in
      by the telemetry manager; without one (a gateway over a backend
      with no telemetry) the bracket keeps its other two sinks.
    - with ``span=`` (a literal registered in
      :data:`telemetry.events.SPANS`, GL05) and ``telemetry.tracing``
      on, the completed JSONL span: in the request's trace when
      ``trace=`` is a request's context (``None`` = that request carries
      none: no span), else through the owner's :class:`StepTrace`.
    - with ``ledger=`` a key of the owner's ledger dict, the elapsed
      seconds added to it, read from the owner's injected clock so that
      fake-clock tests stay exact.
    """

    def __init__(self, layer: str, annotate: Optional[Callable] = None,
                 tracer: Tracer = None, step_trace: Optional[StepTrace] = None,
                 clock: Optional[Callable] = None,
                 ledger: Optional[Dict[str, float]] = None):
        self.prefix = f"ds.{layer}."
        self.annotate = annotate
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self._tracing = self.tracer.enabled  # fixed at construction
        self.step_trace = step_trace
        self.clock = clock if clock is not None else time.monotonic
        self.ledger = ledger

    def __call__(self, phase: str, span: Optional[str] = None,
                 trace=STEP_SCOPE, ledger: Optional[str] = None, **attrs):
        if span is not None and not (self._tracing
                                     and self._span_sink(trace)):
            span = None
        return _Open(self, self.prefix + phase, span, trace, ledger, attrs)

    def _span_sink(self, trace) -> bool:
        """Whether a span bracketed now has somewhere to go: the
        request's trace context, or the step accounting."""
        if trace is STEP_SCOPE:
            return self.step_trace is not None and self.step_trace.enabled
        return trace is not None


def trace_ctx(trace: str, parent: Optional[str] = None,
              **attrs) -> Dict:
    """The cross-component trace context: what the router hands each
    replica (via ``Request.trace``) so replica-side spans join the
    client's trace under the current attempt span."""
    return {"trace": trace, "parent": parent, **attrs}


__all__ = ["SPANS", "Tracer", "StepTrace", "SpanHandle", "Brackets",
           "STEP_SCOPE", "NULL_TRACER", "end_span", "span_id", "to_ns",
           "monotonic_ns", "trace_ctx"]
