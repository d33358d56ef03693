"""``kernel_roofline`` for a kernel whose work is counted by the program
and not given by shapes alone (how many experts a step touched): the
kernel's arithmetic (``kernels/<kernel>.py``, ``least_seconds``) may find
nothing to count (no counters in ``facts``, or none gained in the traced
span) and return None, and then so does this. In %."""

from perfbench import byname, flops, trace_reduce


def read(spec: dict, facts: dict):
    trace = facts.get("trace_events")
    if trace is None:
        return None
    total_ns = count = 0
    for events in trace_reduce.window_events(trace).values():
        ns, n = trace_reduce.time_matching(events, spec["pattern"])
        total_ns, count = total_ns + ns, count + n
    if not count or not total_ns:
        return None
    least_s = byname.module("kernels", spec["kernel"]).least_seconds(
        spec, facts, count, flops.peaks(facts["device"]["kind"]))
    if least_s is None:
        return None
    return 100.0 * least_s / (total_ns / 1e9)
