"""A percentile over the finished requests of one field of the program's
per-request record (``facts["requests"][i]["record"]``, what the SSE
``done`` event carried), or of the ratio of two. Parameters: ``field``,
``q`` (0-100) and optionally ``per`` (the field to divide by, request by
request; a request whose divisor is 0 or missing is left out). No record
has the field (an older program, a run with no finished request): None."""

import numpy as np


def read(spec: dict, facts: dict):
    values = []
    for req in facts.get("requests") or ():
        record = req.get("record") or {}
        value = record.get(spec["field"])
        if not req.get("ok") or value is None:
            continue
        if "per" in spec:
            per = record.get(spec["per"])
            if not per:
                continue
            value = value / per
        values.append(value)
    if not values:
        return None
    return float(np.percentile(np.asarray(values, np.float64), spec["q"]))
