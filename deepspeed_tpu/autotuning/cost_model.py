"""Analytic + XLA-measured cost models for ranking candidates.

The reference fits an XGBoost cost model over measured experiments
(deepspeed/autotuning/tuner/cost_model.py:14, model_based_tuner.py:23). On
TPU the compiler itself is a better oracle: XLA's ``cost_analysis()``
reports FLOPs and bytes-accessed for the exact compiled program, and a
roofline over (MXU peak, HBM bandwidth) converts those to a step-time
estimate. The analytic model below needs no compile at all — it ranks the
space so the measurement budget is spent near the optimum; the model-based
tuner then calibrates it against the trials it actually runs.
"""

import dataclasses
from typing import Optional

from deepspeed_tpu.autotuning.space import Candidate, ModelProfile

# Assumed achievable fractions of nominal peak (priors for ranking only;
# the model-based tuner recalibrates against the trials it runs).
_MXU_EFF = 0.6
_HBM_EFF = 0.8

# Extra forward recompute in backward per remat policy, as a multiple of
# the 2N-per-token forward matmul FLOPs.
_REMAT_RECOMPUTE = {"none": 0.0, "dots": 0.05, "full": 1.0}


@dataclasses.dataclass
class ChipSpec:
    """The roofline's two rates for one chip, read from the one peaks table
    (``utils/device.py:PEAKS``). No default chip: an unknown
    ``device_kind`` raises ``DeviceError``."""
    peak_flops: float
    hbm_bandwidth: float

    @staticmethod
    def from_kind(kind: str) -> "ChipSpec":
        from deepspeed_tpu.utils.device import peaks

        p = peaks(kind)
        return ChipSpec(p.bf16_flops, p.hbm_bandwidth)

    @staticmethod
    def detect() -> "ChipSpec":
        import jax

        return ChipSpec.from_kind(jax.devices()[0].device_kind)


def probe_devices_subprocess():
    """(platform, device_kind, device_count, hbm_bytes|None) of the DEFAULT
    jax backend, probed in a throwaway subprocess that has exited before
    any trial starts.

    The autotuner parent must never initialize the TPU runtime itself — a
    chip belongs to one process until it exits, so a parent holding it would
    make every trial subprocess fail (see __main__.py). A probe that fails
    raises: nothing is assumed about a device that did not answer.
    """
    import json as _json
    import subprocess
    import sys

    code = (
        "import jax, json\n"
        "d = jax.devices()[0]\n"
        "hbm = (d.memory_stats() or {}).get('bytes_limit')\n"
        "print('\\n' + json.dumps([d.platform, d.device_kind, "
        "jax.device_count(), hbm]))")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, check=True)
    return tuple(_json.loads(out.stdout.strip().splitlines()[-1]))


def predict_step_time(profile: ModelProfile, cand: Candidate,
                      chip: Optional[ChipSpec] = None) -> float:
    """Roofline step-time estimate in seconds."""
    chip = chip or ChipSpec.detect()
    tokens = cand.micro_batch * profile.seq_len
    recompute = _REMAT_RECOMPUTE.get(cand.remat_policy, 0.05)
    flops = tokens * profile.flops_per_token * (1.0 + recompute / 3.0)

    # HBM traffic: bf16 params read in fwd + bwd, fp32 grads written, fp32
    # masters + both Adam moments read and written in the update.
    n = profile.n_params
    weight_bytes = (2 + 2) * n + 4 * n + 2 * (4 + 8) * n
    act_bytes = tokens * profile.n_layer * 12 * profile.n_embd * profile.act_bytes
    bytes_total = weight_bytes + act_bytes

    t_flops = flops / (chip.peak_flops * _MXU_EFF)
    t_mem = bytes_total / (chip.hbm_bandwidth * _HBM_EFF)
    dispatch_overhead = 2e-4 if cand.fused_step else 6e-4
    return max(t_flops, t_mem) + dispatch_overhead


def predict_throughput(profile: ModelProfile, cand: Candidate,
                       chip: Optional[ChipSpec] = None) -> float:
    """Tokens/s under the roofline estimate."""
    t = predict_step_time(profile, cand, chip)
    return cand.micro_batch * profile.seq_len / t


def xla_cost_analysis(fn, *args):
    """FLOPs + bytes of the compiled program, straight from XLA.

    The TPU-native replacement for the reference's measured model-info
    profile run (autotuner.py:426): one compile, no execution.
    """
    import jax

    compiled = jax.jit(fn).lower(*args).compile()
    cost = compiled.cost_analysis()
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
    }
