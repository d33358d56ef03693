"""Latent attention over the CHOSEN keys (``ops/dsa_sparse_attend.py``; the
events under the program's ``dsa_sparse_attend`` scopes), counted by what
the algorithm needs whatever form the program runs: a (query, chosen key)
pair of a layer costs

- in a decode step (one query a row: the absorbed form, every head against
  the latent row as it lies) ``2 x heads x (row + rank)`` operations, and
  the row's ``row`` values read once (1,152 B: the bytes bind by 8 on a
  v5e, as ``kernels/mla_decode.py`` finds for the dense step);
- in a chunk ``2 x heads x (nope + rope + v)`` operations (the scores and
  the weighted values by heads; taking a key through ``W_kvb`` is counted
  for no one: a chosen key is shared by many of the chunk's queries), and
  the row read once a chunk for all its queries.

A chunk that computes EVERY live key and masks the unchosen is held to
this SPARSE count: the share reads the same work whatever implements it, and
cannot pass 100%. The pairs are the program's own count in the traced span
(``model_counters/<program>/dsa_keys_selected``); a program that counts
nothing gives None."""


def _pairs(facts: dict):
    span = (facts.get("engine_span") or {}).get("model_counters") or {}
    found = {kind: (span.get(kind) or {}).get("dsa_keys_selected")
             for kind in ("prefill", "decode")}
    return None if None in found.values() else found


def least_seconds(spec: dict, facts: dict, count: int, peak: dict):
    cell = facts["cell"]
    shapes = cell["family"].attention_shapes(cell["config_file"])
    latent = shapes.get("latent")
    width = int(cell["serve"]["serving"].get("prefill_chunk_tokens") or 0)
    pairs = _pairs(facts)
    if (not latent or "nope" not in latent or not width or pairs is None
            or not sum(pairs.values())):
        return None
    heads, row = shapes["heads"], 2.0 * latent["row"]         # bfloat16
    ops = {"decode": 2.0 * heads * (latent["row"] + latent["rank"]),
           "prefill": 2.0 * heads * (latent["nope"] + latent["rope"]
                                     + latent["v"])}
    total = 0.0
    for kind, queries_a_read in (("decode", 1), ("prefill", width)):
        total += max(pairs[kind] * ops[kind] / peak["bf16_flops_per_s"],
                     pairs[kind] / queries_a_read * row
                     / peak["hbm_bytes_per_s"])
    return total
