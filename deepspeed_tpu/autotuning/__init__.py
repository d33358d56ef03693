"""Autotuning: search {micro-batch, ZeRO stage, remat policy} for the best
measured throughput on the local chip.

Reference subsystem: deepspeed/autotuning (autotuner.py:31, scheduler.py:30,
tuner/cost_model.py:14 — 2.8k LoC). Usage::

    from deepspeed_tpu.autotuning import Autotuner, AutotuningConfig

    best = Autotuner(model_spec={"preset": "gpt2",
                                 "config": {"n_layer": 12, "n_embd": 768}},
                     base_ds_config={"optimizer": {...}},
                     config=AutotuningConfig(max_trials=8)).tune()

or ``python -m deepspeed_tpu.autotuning`` for GPT-2 125M at seq 1024; the
best config is written to ``<results-dir>/best_config.json`` for the
operator to read, as the reference's is.
"""

from deepspeed_tpu.autotuning import runtime_tunables
from deepspeed_tpu.autotuning.artifact import (TunedArtifactError,
                                               artifact_hash,
                                               make_artifact,
                                               read_tuned_artifact,
                                               verify_fingerprint,
                                               write_tuned_artifact)
from deepspeed_tpu.autotuning.autotuner import Autotuner, profile_model
from deepspeed_tpu.autotuning.config import AutotuningConfig
from deepspeed_tpu.autotuning.live import (LiveAxis, all_axes, default_axes,
                                           get_axis, register_axis)
from deepspeed_tpu.autotuning.measure import LiveTuner
from deepspeed_tpu.autotuning.cost_model import (ChipSpec, predict_step_time,
                                                 predict_throughput,
                                                 xla_cost_analysis)
from deepspeed_tpu.autotuning.space import (Candidate, ModelProfile,
                                            build_space, estimate_hbm_bytes)
from deepspeed_tpu.autotuning.tuner import (GridSearchTuner, ModelBasedTuner,
                                            RandomTuner, get_tuner)

__all__ = [
    "Autotuner", "AutotuningConfig", "Candidate", "ChipSpec",
    "GridSearchTuner", "LiveAxis", "LiveTuner", "ModelBasedTuner",
    "ModelProfile", "RandomTuner", "TunedArtifactError", "all_axes",
    "artifact_hash", "build_space", "default_axes", "estimate_hbm_bytes",
    "get_axis", "get_tuner", "make_artifact", "predict_step_time",
    "predict_throughput", "profile_model", "read_tuned_artifact",
    "register_axis", "runtime_tunables", "verify_fingerprint",
    "write_tuned_artifact", "xla_cost_analysis",
]
