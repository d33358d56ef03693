"""The decode step's in-place state update of the KDA layers
(``ops/kda_state_update.py``): for every busy row and layer the kernel has
to read the row's state (``heads x key x value`` float32 values) once and to
write it once; what else it moves (a row's ``alpha``, ``k``, ``q``, ``v``,
``beta`` and its output: 6 lane rows a head against 128) is a twentieth of
a per cent of that and is left out. Bandwidth-bound: seven operations a
value. What a step touched is data, not shape: the busy rows come from the
program's own counter over the traced span (``kv_live_bytes/state``: at
every decode step the busy rows times what a slot's matrices take in all
the KDA layers). It counts what the algorithm needs: a gather, an update
and a scatter would move three times as much."""


def least_seconds(spec: dict, facts: dict, count: int, peak: dict):
    cell = facts["cell"]
    kda = cell["family"].attention_shapes(cell["config_file"]).get("kda")
    live = ((facts.get("engine_span") or {}).get("kv_live_bytes")
            or {}).get("state")
    if not kda or not live:
        return None
    # ``live`` IS busy rows x layers x the state's bytes, summed over the
    # span's steps: read once, written once
    return 2.0 * live / peak["hbm_bytes_per_s"]
