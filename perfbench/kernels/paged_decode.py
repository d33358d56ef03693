"""Paged decode attention: every decode step's kernel reads the keys and
values of every live token of its batch, once per layer that keeps them in
the paged cache. The live tokens come from the client's record: a token
that arrived inside the traced span was produced by a step that read its
request's prompt plus the tokens before it. Bandwidth-bound."""

from perfbench import flops


def least_seconds(spec: dict, facts: dict, count: int, peak: dict) -> float:
    cell = facts["cell"]
    shapes = cell["family"].attention_shapes(cell["config_file"])
    lo, hi = facts["traced_span_s"]
    live = sum(r["prompt_len"] + k
               for r in facts["requests"]
               for k, t in enumerate(r["arrivals"]) if k > 0 and lo <= t < hi)
    nbytes = flops.paged_decode_bytes(
        live, shapes["paged_layers"], shapes["kv_heads"], shapes["head_dim"])
    return nbytes / peak["hbm_bytes_per_s"]
