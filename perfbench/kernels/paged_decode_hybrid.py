"""Paged decode attention over two kinds of layer: every decode step's
kernels read, for every live token of the batch, the keys and values of
each GLOBAL layer, and of each WINDOW layer those of the last ``window``
tokens only. The live tokens come from the client's record, as
``kernels/paged_decode.py`` takes them: a token that arrived inside the
traced span was produced by a step that read its request's prompt plus the
tokens before it. Bandwidth-bound. It counts what the algorithm needs: a
kernel that reads a whole ring where the window is shorter reads more."""


def least_seconds(spec: dict, facts: dict, count: int, peak: dict) -> float:
    cell = facts["cell"]
    shapes = cell["family"].attention_shapes(cell["config_file"])
    lo, hi = facts["traced_span_s"]
    live = [r["prompt_len"] + k for r in facts["requests"]
            for k, t in enumerate(r["arrivals"]) if k > 0 and lo <= t < hi]
    itemsize, nbytes = 2, 0.0
    for kind in ("global", "window"):
        s = shapes[kind]
        row = s["kv_heads"] * (s["k_dim"] + s["v_dim"]) * itemsize
        seen = sum(min(n, s["window"]) if s["window"] else n for n in live)
        nbytes += s["layers"] * seen * row
    return nbytes / peak["hbm_bytes_per_s"]
