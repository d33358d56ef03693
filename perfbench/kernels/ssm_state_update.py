"""The decode step's in-place state update of the Mamba-2 layers
(``ops/ssm_state_update.py``): for every busy row and layer the kernel has
to read the row's state (``heads x head x state`` values) once and to write
it once; what else it moves (a row's ``delta x``, ``B``, ``C``, its ``y``)
is a thousandth of that and is left out. Bandwidth-bound: two operations a
value. What a step touched is data, not shape: the busy rows come from the
program's own counter over the traced span (``kv_live_bytes/state``: at
every decode step the busy rows times what a slot keeps in all the Mamba
layers, matrix and convolution rows). It counts what the algorithm needs: a
gather, an update and a scatter would move three times as much."""


def least_seconds(spec: dict, facts: dict, count: int, peak: dict):
    cell = facts["cell"]
    ssm = cell["family"].attention_shapes(cell["config_file"]).get("ssm")
    live = ((facts.get("engine_span") or {}).get("kv_live_bytes")
            or {}).get("state")
    if not ssm or not live:
        return None
    itemsize = 2
    inner = ssm["heads"] * ssm["head"]
    state = inner * ssm["state"] * itemsize
    slot = ssm["layers"] * (state + (ssm["taps"] - 1) * itemsize
                            * (inner + 2 * ssm["state"]))
    rows = live / slot                       # busy rows, summed over steps
    return rows * ssm["layers"] * 2 * state / peak["hbm_bytes_per_s"]
