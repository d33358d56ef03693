"""The decode step's in-place state update of the Mamba-1 layers
(``ops/mamba1_scan.py:mamba1_state_update``): for every busy row and layer
the kernel has to read the row's state (``channels x states`` float32
values) once and to write it once; what else it moves (a row's ``delta``,
``delta x``, ``B``, ``C``, its ``y``: 0.1 MB, and the layer's ``A`` once a
call) is a sixth of that and is left out. Bandwidth-bound. What a step
touched is data, not shape: the busy rows come from the program's own
counter over the traced span (``kv_live_bytes/state``: at every decode step
the busy rows times what a slot keeps in all the Mamba layers, state and
convolution rows). It counts what the algorithm needs: a gather, an update
and a scatter would move three times as much."""


def least_seconds(spec: dict, facts: dict, count: int, peak: dict):
    cell = facts["cell"]
    ssm = cell["family"].attention_shapes(cell["config_file"]).get("ssm")
    live = ((facts.get("engine_span") or {}).get("kv_live_bytes")
            or {}).get("state")
    if not ssm or "channels" not in ssm or not live:
        return None
    state = ssm["channels"] * ssm["state"] * 4
    slot = ssm["layers"] * (state + (ssm["taps"] - 1) * ssm["channels"] * 2)
    rows = live / slot                       # busy rows, summed over steps
    return rows * ssm["layers"] * 2 * state / peak["hbm_bytes_per_s"]
