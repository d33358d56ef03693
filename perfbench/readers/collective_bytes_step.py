"""Operand bytes of the collective operations one training step executes,
summed over the chips: for every collective event of the traced window,
the bytes its HLO instruction takes as operands (``-done`` halves of
asynchronous pairs add nothing), over the steps of the window. Read from
the executed events, so an operation inside the scanned layer body counts
once per layer. A count, not a time. A trace with no collective: None."""

from perfbench import trace_reduce


def read(spec: dict, facts: dict):
    trace, steps = facts.get("trace_events"), facts.get("steps")
    if trace is None or not steps:
        return None
    lo, hi = trace_reduce.window_of(trace)
    total = 0
    for events in trace.device_ops.values():  # whole events that START inside
        total += sum(trace_reduce.operand_bytes(n)
                     for n, s, _ in trace_reduce.leaves(events)
                     if lo <= s < hi and trace_reduce.COLLECTIVE.search(
                         trace_reduce.short_name(n)[1]))
    return total / steps if total else None
