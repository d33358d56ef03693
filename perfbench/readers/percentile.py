"""A percentile of a list of per-request numbers the job hands over.
Parameters: ``fact`` (the key under which the job put the list) and ``q``
(the percentile, 0-100). Nothing to read: None."""

import numpy as np


def read(spec: dict, facts: dict):
    values = facts.get(spec["fact"])
    if not values:
        return None
    return float(np.percentile(np.asarray(values, np.float64), spec["q"]))
