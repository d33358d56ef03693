"""Global compile watchdog: ``jax.monitoring`` listener + attribution.

JAX records every jaxpr trace / MLIR lowering / XLA backend compile
through ``jax.monitoring.record_event_duration_secs`` (``jax/_src/
dispatch.py``: ``/jax/core/compile/*``). The listener here is *passive* —
it only runs when a compile actually happens, costs nothing on the hot
path, and works for compiles the engines never see (a user's own jits, a
library's helper programs). ``WatchedFunction`` (``jit_watch.py``) sets a
label around its lower/compile so durations attribute to the engine entry
point that triggered them; everything else lands under ``<unlabeled>``.

A scope hears everything inside it: where scopes nest (a program's first
call, ``process_ledger``, around a ``WatchedFunction``'s own), an event is
kept under every open label, so each can be asked what it cost
(:func:`label_totals`): traces, lowerings to MLIR (where a Pallas body's
lowering to Mosaic lands), backend compiles, and the persistent cache's
hits with the seconds their retrieval took. The subscribers and the
``<unlabeled>`` rule see the innermost label, as before.

``install()`` is idempotent and safe to call from benches and tests:
registration itself adds zero per-dispatch work (the listener list is
only walked inside compile paths).
"""

import threading
import weakref
from typing import Dict, Optional

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_JAXPR_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_MLIR_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"

# the two events kept by their time spans: (total's key, label's field)
_SPANS_KEPT = {_JAXPR_TRACE: ("jaxpr_trace_secs", "trace_secs"),
               _MLIR_LOWER: ("mlir_lower_secs", "lower_secs")}

_lock = threading.Lock()
_installed = False
_label = threading.local()

_counts: Dict[str, float] = {
    "backend_compiles": 0,
    "backend_compile_secs": 0.0,
    "jaxpr_trace_secs": 0.0,
    "mlir_lower_secs": 0.0,
    "persistent_cache_hits": 0,
    "cache_retrieval_secs": 0.0,
}
_by_label: Dict[str, Dict[str, float]] = {}
_subscribers = []


# what is kept by label; ``secs`` are the backend's (a cache hit's
# retrieval included, as JAX times it)
_PER_LABEL = {"compiles": 0, "secs": 0.0, "trace_secs": 0.0,
              "lower_secs": 0.0, "cache_hits": 0,
              "cache_retrieval_secs": 0.0}


def current_label() -> Optional[str]:
    stack = getattr(_label, "stack", None)
    return stack[-1] if stack else None


class label_scope:
    """Attribute compile events fired inside the scope to ``name``."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        stack = getattr(_label, "stack", None)
        if stack is None:
            stack = _label.stack = []
        stack.append(self.name)
        return self

    def __exit__(self, *exc):
        _label.stack.pop()
        return False


def _keep(field: str, amount) -> None:
    """``amount`` more of ``field`` under every open label (lock held)."""
    for key in dict.fromkeys(getattr(_label, "stack", None)
                             or ("<unlabeled>",)):
        per = _by_label.get(key)
        if per is None:
            per = _by_label[key] = dict(_PER_LABEL)
        per[field] += amount


def _own_secs(start: float, end: float) -> float:
    """The seconds of one trace or lowering that no trace or lowering
    inside it has counted: a jitted helper traced while a program is
    traced (or lowered) closes first and lies inside the program's span,
    so the program's own share is its span less those, and traces and
    lowerings together count every second once."""
    closed = getattr(_label, "spans", None)
    if closed is None:
        closed = _label.spans = []
    inside = 0.0
    while closed and closed[-1][0] >= start:
        inside += closed.pop()[1]
    closed.append((start, end - start))
    del closed[:-64]
    return max(end - start - inside, 0.0)


def _on_duration(event: str, duration: float, **kwargs):
    if event == _BACKEND_COMPILE:
        key = current_label() or "<unlabeled>"
        with _lock:
            _counts["backend_compiles"] += 1
            _counts["backend_compile_secs"] += duration
            _keep("compiles", 1)
            _keep("secs", duration)
        dead = []
        for ref in list(_subscribers):
            cb = ref()
            if cb is None:
                dead.append(ref)
                continue
            try:
                cb(key, duration)
            except Exception:
                pass
        for ref in dead:
            try:
                _subscribers.remove(ref)
            except ValueError:
                pass
    elif event == _CACHE_RETRIEVAL:
        with _lock:
            _counts["cache_retrieval_secs"] += duration
            _keep("cache_retrieval_secs", duration)


def _on_time_span(event: str, start_time: float, end_time: float, **kwargs):
    kept = _SPANS_KEPT.get(event)
    if kept is not None:
        own = _own_secs(start_time, end_time)
        with _lock:
            _counts[kept[0]] += own
            _keep(kept[1], own)


def _on_event(event: str, **kwargs):
    if event == _CACHE_HIT:
        with _lock:
            _counts["persistent_cache_hits"] += 1
            _keep("cache_hits", 1)


def install() -> None:
    """Register the jax.monitoring listeners (idempotent, passive)."""
    global _installed
    with _lock:
        if _installed:
            return
        _installed = True
    from jax._src import monitoring

    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_time_span_listener(_on_time_span)
    monitoring.register_event_listener(_on_event)


def subscribe(callback) -> None:
    """``callback(label, duration_secs)`` on every backend compile.

    Held WEAKLY (``WeakMethod`` for bound methods): a Telemetry instance
    whose engine was dropped without an explicit ``destroy()``/``close()``
    must not be pinned alive — and keep appending to its sink — for the
    rest of the process just because it once subscribed."""
    install()
    try:
        ref = weakref.WeakMethod(callback)
    except TypeError:
        ref = weakref.ref(callback)
    _subscribers.append(ref)


def unsubscribe(callback) -> None:
    for ref in list(_subscribers):
        cb = ref()
        # bound-method equality (same __self__ and __func__), not identity:
        # WeakMethod() rebuilds a fresh bound method on every deref
        if cb is None or cb == callback:
            try:
                _subscribers.remove(ref)
            except ValueError:
                pass


def is_primary(callback) -> bool:
    """True when ``callback`` is the first LIVE subscriber — the one
    designated to report ``<unlabeled>`` compiles. With several
    telemetry-enabled engines in one process, every instance hears every
    unlabeled compile; only the primary emits/warns, or a shared sink
    would double-count them (the role falls over automatically when the
    primary is closed or collected)."""
    for ref in _subscribers:
        cb = ref()
        if cb is not None:
            return cb == callback
    return False


def label_totals(label: str) -> Dict[str, float]:
    """What has been kept under ``label`` so far (zeros for one never
    heard of): two of these around a call say what the call cost."""
    with _lock:
        return dict(_by_label.get(label) or _PER_LABEL)


def snapshot() -> Dict:
    """Copy of the global counters + per-label attribution so far."""
    with _lock:
        return {**{k: (int(v) if isinstance(v, int) else round(v, 6))
                   for k, v in _counts.items()},
                "by_label": {k: dict(v) for k, v in _by_label.items()}}
