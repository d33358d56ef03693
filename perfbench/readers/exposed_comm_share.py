"""Share of the traced window in which a collective operation ran on a
chip and no other operation did: collective time that compute does not
hide, averaged over the chips (``trace_reduce.exposed_collective_ns``).
In %. A trace with no collective in it: None."""

from perfbench import trace_reduce


def read(spec: dict, facts: dict):
    trace = facts.get("trace")
    events = facts.get("trace_events")
    if not trace or events is None or trace["window_s"] <= 0:
        return None
    per_chip = trace_reduce.window_events(events)
    if not any(trace_reduce.COLLECTIVE.search(n)
               for ops in per_chip.values() for n, _, _ in ops):
        return None
    return 100.0 * trace["exposed_collective_s"] / trace["window_s"]
