"""A kernel's share of its roofline: the least time the chip could take
for the calls the trace shows (the larger of operations over peak FLOP/s
and bytes over peak bytes/s, both computed from shapes) over the time the
kernel's events took. In %.

Parameters of the metric's file: ``pattern`` (a regular expression, searched
in the full event name, which is the HLO instruction) and ``kernel``, which
names the kernel's arithmetic: ``perfbench/kernels/<kernel>.py`` with one
function, ``least_seconds(spec, facts, count, peak)``, the least seconds
for the ``count`` matched events on a chip of the published peaks ``peak``.
It takes the algorithm's operations and bytes from ``perfbench/flops.py``
and the model's shapes from the cell's family.

A trace without matching events: None.
"""

from perfbench import byname, flops, trace_reduce


def read(spec: dict, facts: dict):
    trace = facts.get("trace_events")
    if trace is None:
        return None
    total_ns = count = 0
    for events in trace_reduce.window_events(trace).values():
        ns, n = trace_reduce.time_matching(events, spec["pattern"])
        total_ns, count = total_ns + ns, count + n
    if not count:
        return None
    peak = flops.peaks(facts["device"]["kind"])
    least_s = byname.module("kernels", spec["kernel"]).least_seconds(
        spec, facts, count, peak)
    return 100.0 * least_s / (total_ns / 1e9)
