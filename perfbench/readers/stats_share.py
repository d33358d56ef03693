"""A share, in %, of two sums of the program's own counters:
``facts["engine_stats"]`` (``jobs/serve_counted.py``: the model's counters
by kind of program, live KV bytes by kind of layer). Parameters: ``part``
and ``whole``, each a list of paths (``"model_counters/decode/pairs_here"``)
whose values are summed. A path the program does not report, or a whole of
0: None."""


def _sum(stats: dict, paths):
    total = 0
    for path in paths:
        at = stats
        for key in path.split("/"):
            if not isinstance(at, dict) or key not in at:
                return None
            at = at[key]
        total += at
    return total


def read(spec: dict, facts: dict):
    stats = facts.get("engine_stats")
    if not stats:
        return None
    part, whole = _sum(stats, spec["part"]), _sum(stats, spec["whole"])
    if part is None or not whole:
        return None
    return 100.0 * part / whole
