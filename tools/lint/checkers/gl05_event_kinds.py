"""GL05 event-kind-registry (and span-name registry).

Every telemetry emit must use a kind registered in
``telemetry/events.KINDS``: the report tool, the monitor bridge and the
resilience watchdog tail all route by kind, so an unregistered kind is
an event that silently renders nowhere. The registry is read from the
AST of ``deepspeed_tpu/telemetry/events.py`` (scan set first, lint root
as fallback) — never imported, so the checker stays jax-free even if
that module ever regressed.

The ``span`` kind has a second registry with the same contract: every
literal span NAME must come from ``telemetry/events.SPANS`` (the report
tool's phase tables / waterfalls and the Perfetto export group by these
names — an unregistered name is a span no summary renders).

Checked call shapes (literal arguments only — dynamic kinds/names are
the emitting wrapper's responsibility):

- ``<anything>.telemetry.emit("kind", ...)`` (and ``_telemetry``)
- ``make_event("kind", ...)``
- the same two with kind ``"span"``: the *name* argument is checked
  against SPANS
- tracer call shapes (``telemetry/tracing.py``): ``*tracer.record_span(
  "name", ...)`` / ``*tracer.begin("name", ...)`` /
  ``*step_trace.mark("name", ...)``
- the one bracket: ``*_bracket("phase", span="name", ...)`` — the
  ``span=`` keyword is the JSONL span name
"""

import ast
from typing import Iterable, Optional, Tuple

from tools.lint.core import Checker, Finding, LintContext, dotted, register
from tools.lint.core import str_const

EVENTS_MODULE = "deepspeed_tpu/telemetry/events.py"

# dotted-call suffixes whose FIRST argument is a span name
_TRACER_CALLS = ("tracer.record_span", "tracer.begin", "step_trace.mark")
# dotted-call suffix of the one bracket: its ``span=`` keyword names the span
_BRACKET_CALL = "_bracket"


def _registry_tuple(ctx: LintContext,
                    symbol: str) -> Optional[Tuple[str, ...]]:
    """A string-tuple assignment (``KINDS``/``SPANS``) extracted from the
    events module's AST (None when the module or the assignment cannot
    be found)."""
    mod = ctx.parse_under_root(EVENTS_MODULE)
    if mod is None or mod.tree() is None:
        return None
    for node in mod.tree().body:
        if isinstance(node, ast.Assign):
            targets = [t.id for t in node.targets
                       if isinstance(t, ast.Name)]
            if symbol in targets and isinstance(
                    node.value, (ast.Tuple, ast.List)):
                vals = [str_const(e) for e in node.value.elts]
                if all(v is not None for v in vals):
                    return tuple(vals)
    return None


def registry_kinds(ctx: LintContext) -> Optional[Tuple[str, ...]]:
    return _registry_tuple(ctx, "KINDS")


def registry_spans(ctx: LintContext) -> Optional[Tuple[str, ...]]:
    return _registry_tuple(ctx, "SPANS")


def _emit_kind_arg(call: ast.Call) -> Optional[ast.expr]:
    """The ``kind`` argument of a telemetry ``emit``/``make_event``
    call, or None when this call is not one."""
    d = dotted(call.func)
    if d is None:
        return None
    if d.endswith("telemetry.emit") or d.endswith("_telemetry.emit"):
        if call.args:
            return call.args[0]
        return next((k.value for k in call.keywords if k.arg == "kind"),
                    None)
    if d == "make_event" or d.endswith(".make_event"):
        if call.args:
            return call.args[0]
        return next((k.value for k in call.keywords if k.arg == "kind"),
                    None)
    return None


def _emit_name_arg(call: ast.Call) -> Optional[ast.expr]:
    """The ``name`` argument of an emit/make_event call (second
    positional, or the ``name=`` keyword)."""
    if len(call.args) >= 2:
        return call.args[1]
    return next((k.value for k in call.keywords if k.arg == "name"), None)


def _tracer_name_arg(call: ast.Call) -> Optional[ast.expr]:
    """The span-name argument of a tracer call shape, or None when this
    call is not one."""
    d = dotted(call.func)
    if d is None:
        return None
    if d.endswith(_BRACKET_CALL):
        return next((k.value for k in call.keywords if k.arg == "span"),
                    None)
    if not d.endswith(_TRACER_CALLS):
        return None
    if call.args:
        return call.args[0]
    return next((k.value for k in call.keywords if k.arg == "name"), None)


@register
class EventKindRegistry(Checker):
    code = "GL05"
    name = "event-kind-registry"
    description = ("every telemetry emit uses a kind registered in "
                   "telemetry/events.KINDS (unregistered kinds render "
                   "nowhere)")

    def run(self, ctx: LintContext) -> Iterable[Finding]:
        kinds = registry_kinds(ctx)
        if kinds is None:
            return  # no registry in reach (partial scan): nothing to pin
        spans = registry_spans(ctx)
        for mod in ctx.modules:
            # raw-source pre-filter: no emit call shape, no parse
            if not mod.mentions(".emit(", "make_event(", ".record_span(",
                                "tracer.begin(", "step_trace.mark(",
                                "_bracket("):
                continue
            for node in mod.nodes():
                if not isinstance(node, ast.Call):
                    continue
                span_name = None
                arg = _emit_kind_arg(node)
                if arg is not None:
                    kind = str_const(arg)
                    if kind is not None and kind not in kinds:
                        yield Finding(
                            code=self.code, path=mod.relpath,
                            line=node.lineno, col=node.col_offset,
                            message=(f"telemetry emit uses unregistered "
                                     f"kind {kind!r} — register it in "
                                     f"telemetry/events.KINDS (known: "
                                     f"{', '.join(kinds)})"))
                        continue
                    if kind == "span":
                        span_name = _emit_name_arg(node)
                else:
                    span_name = _tracer_name_arg(node)
                if span_name is None or spans is None:
                    continue
                name = str_const(span_name)
                if name is None or name in spans:
                    continue  # dynamic name: the wrapper's responsibility
                yield Finding(
                    code=self.code, path=mod.relpath, line=node.lineno,
                    col=node.col_offset,
                    message=(f"span emit uses unregistered span name "
                             f"{name!r} — register it in telemetry/"
                             f"events.SPANS (known: {', '.join(spans)})"))
