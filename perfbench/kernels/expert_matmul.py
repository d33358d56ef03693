"""The sparse FFN's grouped matmul: a call has to read the three matrices
of every expert that some token of the call chose (and of no other), the
rows routed here and their results, and to multiply each routed row through
the three matrices. What a call touched is data, not shape, so it comes
from the program's counters over the traced span (``facts["engine_span"]``,
decode and prefill programs together: both run the kernel). The larger of
bytes over bandwidth and operations over peak: bandwidth-bound while a step
routes a few rows an expert, compute-bound in a long prompt's prefill."""


def counted(span: dict):
    """``(experts touched, pairs routed here)`` gained in the span, over
    every kind of program; None where the program reported neither."""
    by_phase = (span or {}).get("model_counters") or {}
    seen = [c for c in by_phase.values()
            if "experts_touched" in c and "pairs_here" in c]
    if not seen:
        return None
    return (sum(c["experts_touched"] for c in seen),
            sum(c["pairs_here"] for c in seen))


def least_seconds(spec: dict, facts: dict, count: int, peak: dict):
    cell = facts["cell"]
    experts = cell["family"].attention_shapes(cell["config_file"])["experts"]
    got = counted(facts.get("engine_span"))
    if got is None or not got[0]:
        return None
    touched, pairs = got
    weights = 3 * experts["hidden"] * experts["width"]      # one expert's
    itemsize = 2
    nbytes = itemsize * (touched * weights + pairs * 2 * experts["hidden"])
    return max(nbytes / peak["hbm_bytes_per_s"],
               2.0 * pairs * weights / peak["bf16_flops_per_s"])
