"""A kernel's share of its roofline: the least time the chip could take
for the calls the trace shows (the larger of operations over peak FLOP/s
and bytes over peak bytes/s, both computed from shapes by
``perfbench/flops.py``) over the time the kernel's events took. In %.

Parameters of the metric's file: ``pattern`` (a regular expression, searched
in the full event name, which is the HLO instruction) and ``kernel`` (which
shape arithmetic):

- ``flash_train``: a flash forward + backward is ``events_per_call`` (three)
  kernels per layer and step; compute-bound at these shapes.
- ``paged_decode``: every decode step's kernel reads the keys and values of
  every live token of its batch, once per layer. The live tokens come from
  the client's record: a token that arrived inside the traced span was
  produced by a step that read its request's prompt plus the tokens before
  it. Bandwidth-bound.

A trace without matching events: None.
"""

from perfbench import flops, trace_reduce


def _flash_train(spec, facts, count, peak):
    model = facts["cell"]["config_file"]["model"]
    rows = facts["rows"] // facts["chips"]      # per chip
    head_dim = model["n_embd"] // model["n_head"]
    args = (rows, model["n_head"], facts["seq_len"], head_dim)
    calls = count / float(spec["events_per_call"])
    return calls * max(
        flops.flash_train_flops(*args) / peak["bf16_flops_per_s"],
        flops.flash_train_bytes(*args) / peak["hbm_bytes_per_s"])


def _paged_decode(spec, facts, count, peak):
    model = facts["model"]
    lo, hi = facts["traced_span_s"]
    live = sum(r["prompt_len"] + k
               for r in facts["requests"]
               for k, t in enumerate(r["arrivals"]) if k > 0 and lo <= t < hi)
    nbytes = flops.paged_decode_bytes(
        live, model["n_layer"], model["n_head"],
        model["n_embd"] // model["n_head"])
    return nbytes / peak["hbm_bytes_per_s"]


_KERNELS = {"flash_train": _flash_train, "paged_decode": _paged_decode}


def read(spec: dict, facts: dict):
    trace = facts.get("trace_events")
    if trace is None:
        return None
    total_ns = count = 0
    for events in trace_reduce.window_events(trace).values():
        ns, n = trace_reduce.time_matching(events, spec["pattern"])
        total_ns, count = total_ns + ns, count + n
    if not count:
        return None
    peak = flops.peaks(facts["device"]["kind"])
    least_s = _KERNELS[spec["kernel"]](spec, facts, count, peak)
    return 100.0 * least_s / (total_ns / 1e9)
