"""Telemetry manager — the per-engine facade over the four collectors.

Construction is cheap and disabled-by-default: a disabled ``Telemetry``
is a handful of attribute reads on the hot path (``watch_jit`` returns
the raw jit unchanged, ``on_step_boundary`` is a single bool check), and
the engines' compiled step programs are untouched either way (the
zero-overhead guard test in ``tests/unit/test_telemetry.py`` asserts the
optimized HLO is byte-identical).

Collectors (tentpole contract, ISSUE 2):

1. **compile watchdog** — ``compile_watch`` global listener + per-engine
   :class:`~deepspeed_tpu.telemetry.jit_watch.WatchedFunction` wrappers;
   warns loudly on recompile storms after warmup.
2. **static step-cost accounting** — once per compile, FLOPs / collective
   wire bytes / executable memory analysis from the compiled executable
   (``jit_watch.compiled_cost_summary``), mirrored into the comms logger
   when that is enabled.
3. **device memory stats** — sampled at step boundaries through the
   accelerator abstraction; passive (no added host syncs — it piggybacks
   on the fences the step boundary already has).
4. **trace windows** — config-driven ``jax.profiler`` start/stop around
   exactly ``num_steps`` steps, with markers in the event stream.
"""

import functools
import os
from typing import Dict, Optional

from deepspeed_tpu.telemetry import compile_watch, process_ledger
from deepspeed_tpu.telemetry.events import make_event
from deepspeed_tpu.telemetry.jit_watch import (WatchedFunction,
                                               compiled_cost_summary)
from deepspeed_tpu.telemetry.registry import NULL_REGISTRY
from deepspeed_tpu.telemetry.sink import JsonlSink, MonitorBridge
from deepspeed_tpu.telemetry.tracing import (NULL_TRACER, Brackets,
                                             StepTrace, Tracer)
from deepspeed_tpu.utils.logging import log_dist, logger


def startup_bracket(phase: str, span: Optional[str] = None, **attrs):
    """``with startup_bracket("pool", span="startup.pool")``: one bracket
    of the process's start-up ledger (``telemetry/process_ledger.py``).
    That module is jax-free: the annotation factory is handed in from
    here, once a process."""
    import jax

    return process_ledger.install(
        jax.profiler.TraceAnnotation).startup_bracket(phase, span=span,
                                                      **attrs)


def constructor_bracket(phase: str, span: Optional[str] = None):
    """An engine's ``__init__`` inside :func:`startup_bracket`."""
    def around(init):
        @functools.wraps(init)
        def __init__(self, *args, **kwargs):
            with startup_bracket(phase, span=span):
                init(self, *args, **kwargs)
        return __init__
    return around


def _as_config(config):
    """Accept a parsed TelemetryConfig, a raw dict, or None."""
    if config is None:
        config = {}
    if isinstance(config, dict):
        from deepspeed_tpu.runtime.config import TelemetryConfig

        config = TelemetryConfig(**config)
    return config


class Telemetry:
    def __init__(self, config=None, monitor=None, name: str = "engine"):
        self.config = _as_config(config)
        self.enabled = bool(self.config.enabled)
        self.name = name
        self.warm = False
        self._sink: Optional[JsonlSink] = None
        self._bridge: Optional[MonitorBridge] = None
        self._compile_totals: Dict[str, Dict] = {}
        self._steps_seen = 0
        self._peak_bytes_seen = 0
        # mesh identity as ordered (axis, size) pairs — set by the engine
        # once the mesh exists; feeds the per-axis wire attribution of
        # compiled collectives (hlo_inspect.attribute_collectives)
        self.axis_sizes = None
        self._tracing = False
        self._trace_done = False
        self._trace_count = 0
        self._unlabeled_after_warm = 0
        self._storm_warned = set()
        # in-memory tail of recent events: the post-mortem context the
        # resilience watchdog dumps alongside the thread stacks
        import collections
        import weakref

        self._tail = collections.deque(maxlen=256)
        # live watched functions (weak: the engine's reference is the
        # only owner — see watch_jit) — the AOT capture walks these to
        # serialize the steady-state executables their caches hold
        self._watched = weakref.WeakSet()
        # AOT program store (deepspeed_tpu/aot): armed after a
        # checkpoint restore ships a bundle; consulted by
        # WatchedFunction._compile on every dispatch-cache miss
        self._aot_store = None
        # span tracer + per-step phase accounting (inert unless
        # telemetry AND telemetry.tracing are both enabled)
        self.tracer = NULL_TRACER
        self.step_trace = StepTrace(NULL_TRACER)
        # live metrics plane (telemetry/registry + prom): the inert
        # NULL_REGISTRY unless metrics_port/metrics_file arm it, so
        # instrumentation sites run unconditional everywhere
        self.metrics = NULL_REGISTRY
        self._metrics_server = None
        self._metrics_file = None
        self._recorder = None
        self._sigterm_disarm = None
        self._last_boundary_ns = None
        if not self.enabled:
            return
        try:
            import jax

            self._rank = jax.process_index()
        except Exception:
            self._rank = 0
        if self.config.jsonl:
            self._sink = JsonlSink(
                os.path.join(self.config.dir, "telemetry.jsonl"),
                rotate_bytes=self.config.rotate_bytes,
                rotate_keep=self.config.rotate_keep)
        self._bridge = MonitorBridge(monitor)
        if self.config.tracing.enabled:
            self.tracer = Tracer(self.emit,
                                 step_of=lambda: self._steps_seen)
            self.step_trace = StepTrace(self.tracer, rank=self._rank)
        if self.config.compile_watchdog:
            compile_watch.subscribe(self._on_global_compile)
        # live metrics plane: metrics_port or metrics_file arms the
        # registry (and the per-process scrape endpoint / textfile dump)
        if (self.config.metrics_port is not None
                or self.config.metrics_file):
            from deepspeed_tpu.telemetry.registry import MetricRegistry

            self.metrics = MetricRegistry()
            self._metrics_file = self.config.metrics_file
            if self.config.metrics_port is not None:
                try:
                    from deepspeed_tpu.telemetry.prom import MetricsServer

                    self._metrics_server = MetricsServer(
                        self.metrics, port=self.config.metrics_port,
                        host=self.config.metrics_host)
                    log_dist(
                        f"telemetry: metrics endpoint at "
                        f"{self._metrics_server.url}", ranks=[0])
                except OSError as e:
                    logger.warning(
                        f"telemetry: cannot bind metrics endpoint on "
                        f"{self.config.metrics_host}:"
                        f"{self.config.metrics_port} ({e}); registry "
                        f"stays live, endpoint disabled")
        # flight recorder: continuously armed ring of recent events +
        # metric snapshots, dumped on fault/breaker/SIGTERM triggers
        fr = self.config.flight_recorder
        if fr.enabled:
            from deepspeed_tpu.telemetry.flightrec import (FlightRecorder,
                                                           arm_sigterm,
                                                           is_trigger)

            self._recorder = FlightRecorder(
                fr.dump_dir or self.config.dir, events=fr.events,
                snapshots=fr.snapshots, max_dumps=fr.max_dumps)
            # bound once: emit() is the hot path (every span rides it)
            self._is_trigger = is_trigger
            if fr.on_sigterm:
                self._sigterm_disarm = arm_sigterm(
                    lambda: self._flight_dump("sigterm", trigger=None))

    # ------------------------------------------------------------------
    # event plumbing
    def emit(self, kind: str, name: str, step: Optional[int] = None,
             data: Optional[Dict] = None, **fields):
        if not self.enabled:
            return
        payload = dict(data or {})
        payload.update(fields)
        event = make_event(kind, name, step, getattr(self, "_rank", 0),
                           payload)
        self._tail.append(event)
        if self._sink is not None:
            self._sink.write(event)
        if self._bridge is not None:
            self._bridge.write(event)
        if self.metrics is not NULL_REGISTRY:
            self.metrics.counter("ds_events_total", ("kind",)).labels(
                kind=kind).inc()
        if self._recorder is not None:
            self._recorder.record_event(event)
            if self._is_trigger(kind, name):
                self._flight_dump(f"{kind}:{name}", trigger=event)

    def _flight_dump(self, reason: str, trigger=None):
        """One flight-recorder dump (fault event, breaker trip, SIGTERM,
        or an explicit call). Flushes the JSONL sink first so the dump's
        event tail and the sink agree on the same window, then records
        the dump itself as a ``flightrec.dump`` fault event (excluded
        from re-triggering)."""
        if self._recorder is None:
            return None
        self.flush()
        registry = self.metrics if self.metrics is not NULL_REGISTRY \
            else None
        path = self._recorder.dump(reason, registry=registry,
                                   trigger=trigger)
        if path is not None:
            self.metrics.counter(
                "ds_flightrec_dumps_total", ("reason",)).labels(
                    reason=reason.split(":", 1)[0]).inc()
            self.emit("fault", "flightrec.dump", step=self._steps_seen,
                      reason=reason, path=path)
            self.flush()
        return path

    def tail(self, n: int = 50):
        """The most recent ``n`` events (empty when disabled) — consumed
        by the resilience watchdog's hang dump."""
        return list(self._tail)[-n:]

    # ------------------------------------------------------------------
    # collector 1+2: compile watchdog + static step-cost accounting
    def watch_jit(self, fn, name: str):
        """Route a jitted hot path through the watchdog; identity when
        telemetry (or the watchdog+cost collectors) is off."""
        if not self.enabled or not (self.config.compile_watchdog
                                    or self.config.hlo_cost):
            return fn
        # deliberately NOT strongly retained here: the engine's
        # reference is the only owner, so its release paths (destroy,
        # load_checkpoint, cache clears) actually free the wrapped
        # compiled executables; the WeakSet only lets the AOT capture
        # enumerate whichever instances are still alive
        wf = WatchedFunction(fn, name, self)
        self._watched.add(wf)
        return wf

    def watched_functions(self):
        """The live watched functions (AOT capture walks their compiled
        caches)."""
        return list(self._watched)

    # ------------------------------------------------------------------
    # AOT program store (deepspeed_tpu/aot)
    def set_aot_store(self, store):
        """Arm (or, with None, disarm) the AOT program store. Emits the
        arming event so the stream records which restarts ran warm."""
        self._aot_store = store
        if store is not None:
            self.emit("aot", self.name, step=self._steps_seen,
                      action="armed", programs=len(store),
                      tuned_hash=store.manifest.get("tuned_hash"))

    def aot_lookup(self, name: str, sig_hash: str):
        """Shipped executable for a program signature, or None. Never
        raises: a broken store must degrade to normal compilation."""
        if self._aot_store is None:
            return None
        try:
            return self._aot_store.lookup(name, sig_hash)
        except Exception as e:  # noqa: BLE001 — dispatch must survive
            logger.warning(f"telemetry: AOT store lookup for {name!r} "
                           f"failed ({e}); compiling normally")
            return None

    def record_aot_hit(self, watched: WatchedFunction, sig_hash: str):
        """A dispatch-cache miss was served from the shipped bundle —
        the program the step runs was never compiled in this process.
        Deliberately NOT counted in the compile totals: the warm-restart
        pin asserts those stay at zero."""
        self.emit("aot", watched.name, step=self._steps_seen,
                  action="hit", sig_hash=sig_hash)

    @staticmethod
    def _family(name: str) -> str:
        """Watchdog grouping key: the program name minus any bracketed
        shape suffix. Drifting-shape instances of one entry point (a
        serving engine's ``inference.generate[T=...]`` programs) are
        distinct WatchedFunctions but ONE family — without this a
        request-shape recompile storm would never trip the watchdog,
        because every shape's instance sees exactly one compile."""
        return name.split("[", 1)[0]

    def record_compile(self, watched: WatchedFunction, *, trace_secs: float,
                       compile_secs: float, compiled):
        name = watched.name
        family = self._family(name)
        totals = self._compile_totals.setdefault(
            family, {"compiles": 0, "trace_secs": 0.0, "compile_secs": 0.0,
                     "retraces_after_warm": 0})
        retrace = totals["compiles"] > 0
        totals["compiles"] += 1
        totals["trace_secs"] += trace_secs
        totals["compile_secs"] += compile_secs
        if retrace and self.warm:
            totals["retraces_after_warm"] += 1
        m = self.metrics
        m.counter("ds_compiles_total", ("family",)).labels(
            family=family).inc()
        m.counter("ds_compile_seconds_total", ("family",)).labels(
            family=family).inc(trace_secs + compile_secs)
        if retrace and self.warm:
            m.counter("ds_retraces_after_warmup_total",
                      ("family",)).labels(family=family).inc()
        if self.config.compile_watchdog:
            self.emit("compile", name, step=self._steps_seen,
                      trace_secs=round(trace_secs, 6),
                      compile_secs=round(compile_secs, 6),
                      n_compiles=totals["compiles"], retrace=retrace,
                      after_warmup=self.warm)
            if (retrace and self.warm and totals["retraces_after_warm"]
                    >= self.config.recompile_warn_after
                    and family not in self._storm_warned):
                self._storm_warned.add(family)
                logger.warning(
                    f"telemetry: RECOMPILE STORM — {family!r} has "
                    f"recompiled {totals['retraces_after_warm']}x after "
                    f"warmup (latest: {name!r}, trace {trace_secs:.2f}s + "
                    f"backend {compile_secs:.2f}s). Shapes or static "
                    "arguments are changing across steps; every occurrence "
                    "stalls the pipeline for the full compile time.")
        if self.config.hlo_cost:
            try:
                hlo_text = compiled.as_text()
            except Exception:
                hlo_text = None
            cost = compiled_cost_summary(compiled, hlo_text,
                                         axis_sizes=self.axis_sizes)
            self.emit("step_cost", name, step=self._steps_seen, **cost)
            self._mirror_to_comms_logger(name, cost)

    def _mirror_to_comms_logger(self, name: str, cost: Dict):
        """Compiled-HLO collectives next to the facade-level ops in
        ``comm.log_summary()`` — the cross-reference the comms logger
        could never make alone (it sees trace-time requests; this is what
        XLA actually scheduled on the wire)."""
        from deepspeed_tpu.comm.comm import comms_logger, get_world_size

        if not comms_logger.enabled:
            return
        try:
            world = get_world_size()
        except Exception:
            world = 1
        for op, entry in (cost.get("collectives") or {}).items():
            comms_logger.append(
                op.replace("-", "_"), f"hlo:{name}:{op}", 0.0,
                entry["operand_bytes"], world)

    def _on_global_compile(self, label: str, duration: float):
        if label != "<unlabeled>":
            return  # watched fns emit their own, richer compile events
        if not compile_watch.is_primary(self._on_global_compile):
            return  # one reporter per process, or shared sinks double-count
        self.emit("compile", "<unlabeled>", step=self._steps_seen,
                  compile_secs=round(duration, 6), after_warmup=self.warm)
        if self.warm:
            self._unlabeled_after_warm += 1
            if (self._unlabeled_after_warm
                    == self.config.recompile_warn_after):
                logger.warning(
                    "telemetry: compiles are still happening after warmup "
                    "outside the watched engine entry points "
                    f"({self._unlabeled_after_warm} so far, latest "
                    f"{duration:.2f}s) — some helper computation retraces "
                    "every step")

    # ------------------------------------------------------------------
    # collector 3: device memory stats (passive)
    def _sample_memory(self, step: int):
        try:
            from deepspeed_tpu.accelerator import get_accelerator

            dev = get_accelerator().memory_stats()
        except Exception as e:
            self.emit("memory", self.name, step=step, error=str(e)[:200])
            return
        data = {k: dev[k] for k in ("bytes_in_use", "peak_bytes_in_use",
                                    "bytes_limit", "source") if k in dev}
        try:
            import psutil

            data["host_rss_bytes"] = int(
                psutil.Process().memory_info().rss)
        except Exception:
            pass
        self._peak_bytes_seen = max(self._peak_bytes_seen,
                                    int(data.get("peak_bytes_in_use", 0)))
        m = self.metrics
        if "bytes_in_use" in data:
            m.gauge("ds_device_bytes_in_use").set(data["bytes_in_use"])
        m.gauge("ds_device_peak_bytes").set(self._peak_bytes_seen)
        if "host_rss_bytes" in data:
            m.gauge("ds_host_rss_bytes").set(data["host_rss_bytes"])
        self.emit("memory", self.name, step=step, **data)

    # ------------------------------------------------------------------
    # collector 4: config-driven jax.profiler trace windows
    def _maybe_trace(self, step: int):
        """Boundary-counted window: the capture starts at the first
        boundary with ``step >= start_step`` and stops after ``num_steps``
        further boundaries — so exactly ``num_steps`` steps are traced
        regardless of where in the schedule the run is observed (incl.
        ``start_step: 0``, where boundaries are 1-indexed)."""
        tr = self.config.trace
        if tr.num_steps <= 0 or self._trace_done:
            return
        if not self._tracing and step > max(tr.start_step, 1):
            # the configured start boundary was never observed (checkpoint
            # resume past it, or skipped boundaries): capturing now would
            # trace steps outside the window while the markers claim the
            # configured one — record the miss instead
            self._trace_done = True
            self.emit("trace_window", self.name, step=step, action="missed",
                      start_step=tr.start_step, num_steps=tr.num_steps)
            return
        if self._tracing:
            self._trace_count += 1
            if self._trace_count < tr.num_steps:
                return
            try:
                import jax

                jax.profiler.stop_trace()
                self.emit("trace_window", self.name, step=step,
                          action="stop", dir=tr.dir,
                          num_steps=tr.num_steps)
                log_dist(f"telemetry: stopped jax.profiler trace after "
                         f"{tr.num_steps} step(s) -> {tr.dir}", ranks=[0])
            except Exception as e:
                self.emit("trace_window", self.name, step=step,
                          action="stop_failed", error=str(e)[:200])
            self._tracing = False
            self._trace_done = True
        elif not self._tracing and step >= tr.start_step:
            try:
                import jax

                os.makedirs(tr.dir, exist_ok=True)
                jax.profiler.start_trace(tr.dir)
                self._tracing = True
                self._trace_count = 0
                self.emit("trace_window", self.name, step=step,
                          action="start", dir=tr.dir,
                          start_step=tr.start_step, num_steps=tr.num_steps)
                log_dist(f"telemetry: jax.profiler trace started at step "
                         f"{step} for {tr.num_steps} step(s) -> {tr.dir}",
                         ranks=[0])
            except Exception as e:
                self._trace_done = True
                self.emit("trace_window", self.name, step=step,
                          action="start_failed", error=str(e)[:200])

    def brackets(self, layer: str, clock=None, ledger=None,
                 step_trace=None):
        """The one bracket for host phases (``telemetry/tracing.py``
        :class:`Brackets`), bound to this manager's sinks. Telemetry on
        or off it ALWAYS opens ``ds.<layer>.<phase>`` profiler
        annotations (the ``instrument_w_nvtx`` analog): tracing.py is
        jax-free, so the annotation factory is handed in from here."""
        import jax

        process_ledger.install(jax.profiler.TraceAnnotation)
        return Brackets(layer, annotate=jax.profiler.TraceAnnotation,
                        tracer=self.tracer,
                        step_trace=step_trace or self.step_trace,
                        clock=clock, ledger=ledger)

    # ------------------------------------------------------------------
    # step-boundary hook (one call per optimizer step, from the engines)
    def on_step_boundary(self, global_step: int, samples: Optional[int] = None,
                         micro_steps: Optional[int] = None):
        if not self.enabled:
            return
        step = int(global_step)
        self._steps_seen = step
        if not self.warm and step >= self.config.warmup_steps:
            self.warm = True
        self.emit("step", self.name, step=step, samples=samples,
                  micro_steps=micro_steps)
        m = self.metrics
        if m is not NULL_REGISTRY:
            import time as _time

            now_ns = _time.monotonic_ns()
            m.counter("ds_steps_total").inc()
            if samples:
                m.counter("ds_samples_total").inc(int(samples))
            if self._last_boundary_ns is not None \
                    and now_ns > self._last_boundary_ns:
                m.gauge("ds_steps_per_sec").set(
                    round(1e9 / (now_ns - self._last_boundary_ns), 4))
            self._last_boundary_ns = now_ns
        if self.step_trace.enabled:
            # flush the step's phase spans (no-op when the engine
            # bracketed none into THIS accounting: the serving engine
            # keeps its own, flushed per scheduler iteration)
            self.step_trace.flush(step)
        if (self.config.memory
                and step % max(1, self.config.sample_every) == 0):
            self._sample_memory(step)
        if step % max(1, self.config.sample_every) == 0:
            if self._recorder is not None and m is not NULL_REGISTRY:
                self._recorder.record_snapshot(step, m.snapshot())
            if self._metrics_file and m is not NULL_REGISTRY:
                self._write_metrics_file()
        self._maybe_trace(step)

    def _write_metrics_file(self):
        """Atomic exposition dump to ``telemetry.metrics_file`` (the
        scrape-less path). IO failures disable the file, not the run."""
        from deepspeed_tpu.telemetry.prom import write_textfile

        try:
            write_textfile(self._metrics_file, self.metrics.expose())
        except OSError as e:
            logger.warning(f"telemetry: metrics_file write failed "
                           f"({e}); disabling the textfile dump")
            self._metrics_file = None

    # ------------------------------------------------------------------
    # wall_clock_breakdown (legacy flag routed through the stream)
    def wallclock(self, means_ms: Dict[str, float],
                  step: Optional[int] = None):
        """Timer means (ms) at a report boundary. Always prints the legacy
        rank-0 line (the ``wall_clock_breakdown`` contract predates
        telemetry); additionally lands in the event stream when telemetry
        is enabled."""
        if not means_ms:
            return
        line = " | ".join(f"{k}: {v:.2f}" for k, v in means_ms.items())
        log_dist(f"time (ms) | {line}", ranks=[0])
        # data= keeps timer names (e.g. "step") out of emit's kwargs
        self.emit("wallclock", self.name, step=step,
                  data={k: round(float(v), 4) for k, v in means_ms.items()})

    # ------------------------------------------------------------------
    def summary(self) -> Dict:
        """Aggregates for benches / reports: per-fn compile totals, global
        compile counters, peak device bytes seen."""
        return {
            "per_function": {k: dict(v)
                             for k, v in self._compile_totals.items()},
            "global": compile_watch.snapshot(),
            "peak_bytes_in_use": self._peak_bytes_seen,
            "steps": self._steps_seen,
        }

    def flush(self):
        if self._sink is not None:
            self._sink.flush()

    def close(self):
        if self._tracing:
            try:
                import jax

                jax.profiler.stop_trace()
            except Exception:
                pass
            self._tracing = False
        if self.enabled and self.config.compile_watchdog:
            compile_watch.unsubscribe(self._on_global_compile)
        if self._metrics_file and self.metrics is not NULL_REGISTRY:
            self._write_metrics_file()  # final state for late scrapers
        if self._metrics_server is not None:
            self._metrics_server.close()
            self._metrics_server = None
        if self._sigterm_disarm is not None:
            # a closed recorder must not re-dump its stale ring on a
            # later SIGTERM (nor keep this manager alive via the chain)
            self._sigterm_disarm()
            self._sigterm_disarm = None
        if self._sink is not None:
            self._sink.close()
