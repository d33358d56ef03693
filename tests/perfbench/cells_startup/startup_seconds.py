"""Seconds of the process's start-up, from the program's own ledger
(``deepspeed_tpu/telemetry/process_ledger.py``: ``snapshot()``), which
lives in the reader's own process: the harness builds the engine in the
process that prints the result line. Parameter: ``keys``, a list of paths
into the snapshot (``"top_level/serving_init"``, ``"compile/lower_s"``,
``"outside_s"``) whose values are summed; a path the snapshot lacks (a
serving cell has no ``initialize``) counts nothing. None where the program
has no such ledger (a parent from before it), where start-up is not over,
or where the snapshot has none of the paths."""


def read(spec: dict, facts: dict):
    try:
        from deepspeed_tpu.telemetry import process_ledger
    except ImportError:
        return None
    snap = process_ledger.snapshot()
    if not snap.get("ready"):
        return None
    total, found = 0.0, False
    for path in spec["keys"]:
        at = snap
        for key in path.split("/"):
            at = at.get(key) if isinstance(at, dict) else None
        if at is not None:
            total, found = total + float(at), True
    return total if found else None
