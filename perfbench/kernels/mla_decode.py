"""Absorbed-weights decode attention over latent rows (multi-head latent
attention): every decode step has to read, for every live token of the
batch and every layer, ONE row of ``rank + rope`` values shared by all the
heads (keys and values are the same bytes, read once), and to multiply
each head's query through it (``row`` operations) and its probability
through the row's first ``rank`` values. The larger of bytes over bandwidth
and operations over peak; on a v5e the bytes bind by 8 (1,152 B against
34,816 FLOP a token a layer: 1.41 ns against 0.18).

The live tokens come from the client's record, as
``kernels/paged_decode.py`` takes them: a token that arrived inside the
traced span was produced by a step that read its request's prompt plus the
tokens before it. It counts what the algorithm needs, whatever implements
it: lanes a pool pads its rows to, a second pool, a step that decompresses
the rows first, do not change the count. (The reader calls this only for
events it matched, which only a family with latent layers emits.)"""


def least_seconds(spec: dict, facts: dict, count: int, peak: dict) -> float:
    cell = facts["cell"]
    shapes = cell["family"].attention_shapes(cell["config_file"])
    latent = shapes["latent"]
    lo, hi = facts["traced_span_s"]
    live = sum(r["prompt_len"] + k for r in facts["requests"]
               for k, t in enumerate(r["arrivals"]) if k > 0 and lo <= t < hi)
    itemsize = 2
    rows = live * latent["layers"]
    nbytes = rows * latent["row"] * itemsize
    ops = rows * shapes["heads"] * (latent["row"] + latent["rank"]) * 2.0
    return max(nbytes / peak["hbm_bytes_per_s"],
               ops / peak["bf16_flops_per_s"])
