"""Model FLOP/s utilization of a training window: tokens per second per
chip, times the operations a token requires (``perfbench/flops.py``:
forward + backward, recomputation not counted), over the chip's published
bf16 peak (``perfbench/peaks.json``). In %."""

from perfbench import flops


def read(spec: dict, facts: dict):
    if "train_tok_s_chip" not in facts:
        return None
    peak = flops.peaks(facts["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * facts["train_tok_s_chip"] * facts["flops_per_token"] / peak
