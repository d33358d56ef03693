"""The ledger of the PROCESS, beside the engines' step ledgers: where the
seconds went that no step bracket covers.

**Start-up.** ``ds.startup.<phase>`` brackets tile process start ->
``ready``: the process's start as the OS has it, ``import`` (the first and
last line of ``deepspeed_tpu/__init__.py``), the engines' constructors
(``inference_init``, ``serving_init`` with ``pool`` and ``weight_layouts``
inside it, ``gateway_start``; ``initialize`` with ``params`` and ``state``)
and ``program``: the FIRST call of each compiled program, from before its
build to its first result on the host, split by the compile watchdog's
listener (``compile_watch.label_scope``) into tracing, lowering, backend
compile and the persistent cache's hits, the rest being load and first
run. ``ready`` is ``ServingGateway.start()`` returned, or the first
optimizer step's boundary; a program first called after it is no start-up
and is listed by name under ``late_programs``. Top-level brackets and
``outside_s`` (the interpreter, JAX's import, the backend's start, the
caller's own work) add up to ``ready_s`` by construction; children lie
inside their parents.

**Pauses.** One ``gc.callbacks`` entry brackets every collection as
``ds.host.gc`` and keeps the collector's pauses (count by generation,
seconds, the longest and when); the gateway's ``pump_idle`` seconds are
kept here too, so that a step loop can tell a seam spent idle for want of
work from one spent stalled.

Every bracket goes through the one :class:`~.tracing.Brackets`: the
profiler annotation always (so any profiler session sees the phase on the
device trace's clock), the ledger always. The JSONL spans (``startup``
root, one trace a process, and a child a bracket) are emitted at
``ready`` from the timestamps kept, as every span is emitted at its end.
Always on: with no profiler session it costs two clock reads a bracket,
one callback a collection. No thread, no switch. This module is host-only
(no jax imports, GL01): the annotation factory is handed in by
``telemetry/manager.py`` (:func:`install`), as it is to ``Brackets``.
"""

import collections
import gc
import itertools
import os
import threading
import time
from typing import Callable, Dict, List, Optional

from deepspeed_tpu.telemetry import compile_watch
from deepspeed_tpu.telemetry.tracing import Brackets, to_ns

# a collection that takes this long is kept with its time (the last 32)
LONG_PAUSE_MS = 5.0

# what a program's first call is split into, as compile_watch keeps it
_SPLIT = (("trace_s", "trace_secs"), ("lower_s", "lower_secs"),
          ("cache_retrieval_s", "cache_retrieval_secs"))


def _process_started(clock: Callable[[], float]) -> float:
    """The process's start on ``clock``'s timebase as the OS has it
    (``/proc/self/stat`` field 22, in ticks since boot), not the first
    line of Python; where the OS does not say, now."""
    try:
        with open("/proc/self/stat") as f:
            ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        age = (time.clock_gettime(time.CLOCK_BOOTTIME)
               - ticks / os.sysconf("SC_CLK_TCK"))
        return clock() - max(age, 0.0)
    except (OSError, ValueError, IndexError, AttributeError):
        return clock()


class _Phase:
    """One open start-up bracket: the ``Brackets`` bracket, and where it
    lies among the others (top level or inside another; before ``ready``
    or after)."""

    __slots__ = ("_led", "_open", "phase", "span", "attrs", "program",
                 "_n", "_parent", "_before", "_scope", "t0")

    def __init__(self, led, phase, span, program, attrs):
        self._led = led
        self.phase, self.span, self.program = phase, span, program
        self.attrs = attrs

    def __enter__(self):
        led = self._led
        stack = led._open_phases()
        # its number, and its parent's (None: it lies inside no other)
        self._n = next(led._numbers)
        self._parent = stack[-1]._n if stack else None
        stack.append(self)
        late = led.ready_at is not None
        self._open = led._bracket(
            self.phase, ledger="late_" + self.phase if late else self.phase,
            **self.attrs)
        if self.program is not None:
            self._scope = compile_watch.label_scope(self.program)
            self._scope.__enter__()
            self._before = (led._built_before.pop(self.program, None)
                            or compile_watch.label_totals(self.program))
        self._open.__enter__()
        self.t0 = self._open.t0
        return self

    def __exit__(self, *exc):
        self._open.__exit__(*exc)
        if self.program is not None:
            self._scope.__exit__(*exc)
        self._led._open_phases().pop()
        self._led._closed(self, self.t0, self._open.t1)
        return False


class ProcessLedger:
    """See the module docstring. One a process (:data:`LEDGER`); tests
    make their own over a fake clock."""

    def __init__(self, clock: Callable[[], float] = time.monotonic,
                 started_at: Optional[float] = None,
                 annotate: Optional[Callable] = None):
        self.clock = clock
        self.started_at = (_process_started(clock) if started_at is None
                           else float(started_at))
        # seconds by phase (children beside their parents), the gateway's
        # pump_idle among them: the ledger of the two Brackets below
        self.seconds: Dict[str, float] = collections.defaultdict(float)
        self._bracket = Brackets("startup", annotate=annotate, clock=clock,
                                 ledger=self.seconds)
        self._host = Brackets("host", annotate=annotate, clock=clock)
        self._local = threading.local()
        self._lock = threading.Lock()
        # the same of the brackets that lay inside no other, before ready:
        # with ``outside_s`` they add up to ``ready_s``
        self.top_level: Dict[str, float] = collections.defaultdict(float)
        self.ready_at: Optional[float] = None
        self.programs: List[dict] = []
        self.late_programs: List[dict] = []
        self._called = set()
        self.first_calls = 0
        self._built_before: Dict[str, dict] = {}
        # (span, number, parent's number, t0, t1, attrs, phase) of each
        # bracket closed before ``ready``: the JSONL spans, emitted then,
        # and the timeline
        self._spans: List[tuple] = []
        self._numbers = itertools.count(1)
        self.gc = {"collections": [0, 0, 0], "pause_secs": 0.0,
                   "pause_max_ms": 0.0, "pause_max_at_s": None}
        self.gc_long = collections.deque(maxlen=32)
        self._gc_open, self._gc_t0 = None, 0.0
        self._gc_at_ready = 0.0

    # ------------------------------------------------------------------
    def set_annotate(self, annotate: Callable) -> None:
        self._bracket.annotate = self._host.annotate = annotate

    def _open_phases(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def startup_bracket(self, phase: str, span: Optional[str] = None,
                        **attrs) -> _Phase:
        """``with ledger.startup_bracket("pool", span="startup.pool")``:
        ``ds.startup.pool`` on the profiler's clock, its seconds in the
        ledger, and the JSONL span ``span`` at ``ready``."""
        return _Phase(self, phase, span, None, attrs)

    def building(self, program: str):
        """A label scope around a program's build where that comes before
        its first call (the decode program, compiled to be asked how the
        weights should lie): the first call's row then carries the split
        of this build too."""
        self._built_before.setdefault(program,
                                      compile_watch.label_totals(program))
        return compile_watch.label_scope(program)

    def first_call(self, program: str) -> Optional[_Phase]:
        """The ``program`` bracket of a program's FIRST call, for the miss
        path that builds it; None for a program that has had one (a
        rebuilt program is no start-up)."""
        with self._lock:
            if program in self._called:
                return None
            self._called.add(program)
            self.first_calls += 1
        return _Phase(self, "program", "startup.program", program,
                      {"program": program})

    def stamp_import(self, t0: float, t1: float) -> None:
        """The package's import, from the stamps its first and last line
        took (no bracket can open before the package exists)."""
        if "import" in self.seconds:
            return
        self.seconds["import"] += t1 - t0
        self.top_level["import"] += t1 - t0
        self._spans.append(("startup.import", 0, None, t0, t1, {}, "import"))

    def _closed(self, ph: _Phase, t0: float, t1: float) -> None:
        late = self.ready_at is not None
        row = None
        if ph.program is not None:
            now = compile_watch.label_totals(ph.program)
            was = ph._before
            row = {"program": ph.program,
                   "at_s": round(t0 - self.started_at, 6),
                   "wall_s": round(t1 - t0, 6)}
            for key, kept in _SPLIT:
                row[key] = round(now[kept] - was[kept], 6)
            # the backend's seconds less the cache's retrieval: what a warm
            # start does not pay
            row["compile_s"] = round(max(
                now["secs"] - was["secs"] - row["cache_retrieval_s"], 0.0), 6)
            row["cache_hits"] = int(now["cache_hits"] - was["cache_hits"])
            row["compiles"] = int(now["compiles"] - was["compiles"])
        with self._lock:
            if row is not None:
                (self.late_programs if late else self.programs).append(row)
            if late:
                return
            if ph._parent is None:
                self.top_level[ph.phase] += t1 - t0
            self._spans.append((ph.span, ph._n, ph._parent, t0, t1, ph.attrs,
                                ph.phase))

    # ------------------------------------------------------------------
    def ready(self, kind: str, telemetry=None) -> Optional[dict]:
        """Start-up is over (the first call wins; later ones return None):
        the snapshot, ONE log line on rank 0, ``ds_startup_seconds{phase}``
        in ``telemetry``'s registry and, under ``telemetry.tracing``, the
        JSONL spans."""
        with self._lock:
            if self.ready_at is not None:
                return None
            self.ready_at = self.clock()
            self._gc_at_ready = self.gc["pause_secs"]
        snap = self.snapshot()
        snap["ready_by"] = kind
        try:
            from deepspeed_tpu.utils.logging import log_dist

            log_dist(self.ready_line(snap), ranks=[0])
            if telemetry is not None:
                self._publish(telemetry, snap)
        except Exception:  # noqa: BLE001 — a report never stops a start
            pass
        return snap

    def _publish(self, telemetry, snap: dict) -> None:
        metrics = getattr(telemetry, "metrics", None)
        if metrics is not None:
            gauge = metrics.gauge("ds_startup_seconds", ("phase",))
            for phase, secs in snap["phases"].items():
                gauge.labels(phase=phase).set(secs)
            gauge.labels(phase="outside").set(snap["outside_s"])
            gauge.labels(phase="ready").set(snap["ready_s"])
        tracer = getattr(telemetry, "tracer", None)
        if tracer is None or not tracer.enabled:
            return
        trace = tracer.new_trace(hint="startup")
        root = tracer.record_span(
            "startup", trace, to_ns(self.started_at), to_ns(self.ready_at),
            ready_s=snap["ready_s"], outside_s=snap["outside_s"])
        with self._lock:
            spans = list(self._spans)
        # parents close after their children: emitted last to first, each
        # child finds its parent's id
        ids: Dict[int, str] = {}
        for span, n, parent, t0, t1, attrs, _ in reversed(spans):
            if span is not None:
                ids[n] = tracer.record_span(
                    span, trace, to_ns(t0), to_ns(t1),
                    parent=ids.get(parent, root), **attrs)

    # ------------------------------------------------------------------
    def snapshot(self) -> dict:
        """Where the seconds from the process's start to ``ready`` (or to
        now, before it) went. ``phases`` holds every bracket's seconds by
        name, children beside their parents; the top-level ones and
        ``outside_s`` add up to ``ready_s``."""
        with self._lock:
            until = self.ready_at if self.ready_at is not None \
                else self.clock()
            programs = [dict(p) for p in self.programs]
            late = [dict(p) for p in self.late_programs]
            phases = {k: round(v, 6) for k, v in self.seconds.items()
                      if not k.startswith("late_") and k != "pump_idle"}
            top = {k: round(v, 6) for k, v in self.top_level.items()}
            inside = sum(self.top_level.values())
            # the top-level brackets in the order they ran: what lies
            # between two of them is ``outside_s``'s
            timeline = [
                {"phase": phase, "at_s": round(t0 - self.started_at, 6),
                 "secs": round(t1 - t0, 6), **attrs}
                for _, _, parent, t0, t1, attrs, phase in sorted(
                    self._spans, key=lambda row: row[3]) if parent is None]
            gc_s = (self._gc_at_ready if self.ready_at is not None
                    else self.gc["pause_secs"])
        ready_s = until - self.started_at
        total = {key: round(sum(p[key] for p in programs), 6)
                 for key in ("wall_s", "trace_s", "lower_s", "compile_s",
                             "cache_retrieval_s")}
        total["cache_hits"] = sum(p["cache_hits"] for p in programs)
        total["compiles"] = sum(p["compiles"] for p in programs)
        return {"ready": self.ready_at is not None,
                "ready_s": round(ready_s, 6), "phases": phases,
                "top_level": top, "timeline": timeline,
                "programs": programs, "compile": total,
                "gc_s": round(gc_s, 6),
                "outside_s": round(ready_s - inside, 6),
                "late_programs": late}

    @staticmethod
    def ready_line(snap: dict) -> str:
        """``start-up 31.2 s: import 1.9, ..., outside 9.9``."""
        ph, comp, top = snap["phases"], snap["compile"], snap["top_level"]
        inside = {"serving_init": ("pool", "weight_layouts"),
                  "initialize": ("params", "state")}
        parts = []
        for name in ("import", "inference_init", "serving_init",
                     "gateway_start", "initialize", "params", "state"):
            if name not in top:
                continue        # not run, or run inside another (below)
            kids = ", ".join(f"{k} {ph[k]:.1f}" for k in inside.get(name, ())
                             if k in ph and k not in top)
            parts.append(f"{name} {top[name]:.1f}"
                         + (f" ({kids})" if kids else ""))
        if snap["programs"]:
            slowest = max(snap["programs"], key=lambda p: p["wall_s"])
            parts.append(
                f"programs {comp['wall_s']:.1f} (trace {comp['trace_s']:.1f}"
                f", lower {comp['lower_s']:.1f}, compile "
                f"{comp['compile_s']:.1f}, {comp['cache_hits']} cache hits; "
                f"slowest {slowest['program']} {slowest['wall_s']:.1f})")
        parts += [f"gc {snap['gc_s']:.1f}", f"outside {snap['outside_s']:.1f}"]
        return f"start-up {snap['ready_s']:.1f} s: " + ", ".join(parts)

    # ------------------------------------------------------------------
    def on_gc(self, phase: str, info: dict) -> None:
        """The ``gc.callbacks`` entry: ``ds.host.gc`` around a collection
        and the pause in the ledger. (The collector runs one collection
        at a time, under the interpreter's lock: one open slot.)"""
        if phase == "start":
            self._gc_open = opened = self._host("gc")
            opened.__enter__()
            self._gc_t0 = self.clock()
        elif self._gc_open is not None:
            took = self.clock() - self._gc_t0
            opened, self._gc_open = self._gc_open, None
            opened.__exit__(None, None, None)
            g = self.gc
            g["collections"][min(int(info.get("generation", 2)), 2)] += 1
            g["pause_secs"] += took
            at_s = round(self._gc_t0 - self.started_at, 6)
            if 1e3 * took > g["pause_max_ms"]:
                g["pause_max_ms"], g["pause_max_at_s"] = 1e3 * took, at_s
            if 1e3 * took >= LONG_PAUSE_MS:
                self.gc_long.append({
                    "at_s": at_s, "ms": round(1e3 * took, 3),
                    "generation": info.get("generation")})

    def host_pauses(self, since: Optional[dict] = None) -> dict:
        """The collector's pauses: collections by generation, seconds, the
        collections of ``LONG_PAUSE_MS`` or more with their times, and the
        longest of them (None: none took that long). ``since`` an earlier
        return value, the same over the time since then."""
        g = self.gc
        out = {"at_s": round(self.clock() - self.started_at, 6),
               "gc_collections": list(g["collections"]),
               "gc_pause_secs": g["pause_secs"]}
        kept = list(self.gc_long)
        if since is not None:
            out["gc_collections"] = [n - b for n, b in zip(
                out["gc_collections"], since["gc_collections"])]
            out["gc_pause_secs"] -= since["gc_pause_secs"]
            kept = [p for p in kept if p["at_s"] >= since["at_s"]]
        out["gc_pause_secs"] = round(out["gc_pause_secs"], 6)
        out["gc_pause_max_ms"] = max((p["ms"] for p in kept), default=None)
        out["gc_long_pauses"] = kept
        return out


class FirstCalls:
    """What an engine needs to bracket its programs' first calls without a
    frame on the steady path: the miss path that finds a program unbuilt
    calls :meth:`_first_call` before it builds; the call site asks ``if
    self._first_open:`` after the first result has reached the host and
    closes what is open."""

    _first_open = ()

    def _first_call(self, program: str) -> None:
        if self._first_open:    # a first call that raised before its result
            self._first_result()
        ph = LEDGER.first_call(program)
        if ph is not None:
            ph.__enter__()
            self._first_open = (*self._first_open, ph)

    def _first_result(self) -> None:
        opened, self._first_open = self._first_open, ()
        for ph in reversed(opened):
            ph.__exit__(None, None, None)


LEDGER = ProcessLedger()
_installed = False
_install_lock = threading.Lock()


def install(annotate: Optional[Callable] = None) -> ProcessLedger:
    """The process's ledger with the profiler's annotation factory handed
    in, the collector's callback registered and the compile watchdog's
    listener installed: once a process, idempotent like
    ``compile_watch.install()``."""
    global _installed
    if _installed:
        return LEDGER
    with _install_lock:
        if not _installed:
            if annotate is not None:
                LEDGER.set_annotate(annotate)
            compile_watch.install()
            gc.callbacks.append(_on_gc)
            _installed = True
    return LEDGER


def _on_gc(phase: str, info: dict) -> None:
    led = LEDGER
    if led is not None:     # (None once the interpreter takes modules down)
        led.on_gc(phase, info)


def snapshot() -> dict:
    return LEDGER.snapshot()


__all__ = ["LEDGER", "ProcessLedger", "FirstCalls", "install", "snapshot"]
