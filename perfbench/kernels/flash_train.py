"""Flash attention, forward + backward, in a training step: the three
kernels of one attention call (``events_per_call`` in the metric's file)
run once per layer and step, on the rows this chip holds. Compute-bound at
the shapes the benchmark trains at."""

from perfbench import flops


def least_seconds(spec: dict, facts: dict, count: int, peak: dict) -> float:
    cell = facts["cell"]
    shapes = cell["family"].attention_shapes(cell["config_file"])
    rows = facts["rows"] // facts["chips"]      # per chip
    args = (rows, shapes["heads"], facts["seq_len"], shapes["head_dim"])
    calls = count / float(spec["events_per_call"])
    return calls * max(
        flops.flash_train_flops(*args) / peak["bf16_flops_per_s"],
        flops.flash_train_bytes(*args) / peak["hbm_bytes_per_s"])
