"""The Autotuner: enumerate → prune → order → measure → emit best config.

Reference: ``Autotuner`` (deepspeed/autotuning/autotuner.py:31) — tuning
flow ``tune() -> model_info_profile_run -> tune_space -> run_after_tuning``
writing ``autotuning_results/`` with the best experiment. TPU-native
differences: the model-info "profile run" is a host-side ``jax.eval_shape``
(no device step needed to count params), the memory model is closed-form
(space.py), candidate ordering is a compiler-roofline cost model instead of
XGBoost (cost_model.py), and the tunable axes are micro-batch / ZeRO stage
/ remat policy / fused-step.
"""

import dataclasses
import json
import os
from typing import Dict, List, Optional, Tuple

from deepspeed_tpu.autotuning import constants as C
from deepspeed_tpu.autotuning.config import AutotuningConfig
from deepspeed_tpu.autotuning.cost_model import ChipSpec
from deepspeed_tpu.autotuning.scheduler import TrialResult, TrialScheduler
from deepspeed_tpu.autotuning.space import (Candidate, ModelProfile,
                                            build_space, device_hbm_bytes)
from deepspeed_tpu.autotuning.tuner import get_tuner
from deepspeed_tpu.utils.logging import logger


def profile_model(model_spec: Dict, seq_len: int) -> ModelProfile:
    """Host-side model-info profile (reference autotuner.py:426 does a
    device run for this; ``jax.eval_shape`` needs no device at all)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.autotuning._trial import _build_model

    spec = {"model": model_spec, "seq_len": seq_len,
            "ds_config": {"train_batch_size": 1}}
    model, batch = _build_model(spec)
    # abstract rng (raw uint32 key shape): eval_shape touches no device, so
    # a TPU-hosting parent never acquires the chip its trials need
    abstract = jax.eval_shape(
        lambda r: model.init(r, batch),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    n_params = sum(int(np.prod(l.shape)) for l in
                   jax.tree_util.tree_leaves(abstract))
    cfg = getattr(model, "config", None)
    return ModelProfile(
        n_params=n_params,
        n_layer=getattr(cfg, "n_layer", 12),
        n_embd=getattr(cfg, "n_embd", 768),
        vocab_size=getattr(cfg, "vocab_size", 50257),
        seq_len=seq_len)


class Autotuner:
    def __init__(self, model_spec: Dict, base_ds_config: Dict,
                 config: Optional[AutotuningConfig] = None,
                 seq_len: int = 1024, chip: Optional[ChipSpec] = None,
                 dp: Optional[int] = None):
        self.model_spec = model_spec
        self.base_ds_config = dict(base_ds_config)
        self.config = config or AutotuningConfig()
        self.seq_len = seq_len
        self.chip = chip or ChipSpec.detect()
        if dp is None:
            # trials don't carve model/pipe axes (see _trial.run_trial):
            # every local device is data-parallel
            try:
                import jax

                dp = jax.device_count()
            except Exception:
                dp = 1
        self.dp = dp
        self.results: List[Tuple[Candidate, TrialResult]] = []

    # -- space ----------------------------------------------------------
    def build_space(self, profile: ModelProfile) -> List[Candidate]:
        hbm = device_hbm_bytes(self.config.hbm_gib)
        space = build_space(
            profile,
            micro_batch_sizes=self.config.micro_batch_sizes,
            zero_stages=self.config.zero_stages,
            remat_policies=self.config.remat_policies,
            hbm_bytes=hbm,
            headroom=self.config.memory_headroom,
            dp=self.dp,
            fused_steps=self.config.fused_steps)
        logger.info(f"autotuning space: {len(space)} candidates "
                    f"(HBM budget {hbm / 2**30:.1f} GiB)")
        return space

    def _trial_spec(self, cand: Candidate) -> Dict:
        ds = dict(self.base_ds_config)
        for k, v in cand.ds_config_overrides().items():
            if isinstance(v, dict):
                merged = dict(ds.get(k, {}))
                merged.update(v)
                ds[k] = merged
            else:
                ds[k] = v
        ds.pop("train_batch_size", None)  # micro-batch is the tuned knob
        spec = {"model": self.model_spec, "ds_config": ds,
                "seq_len": self.seq_len,
                "steps": self.config.trial_steps,
                "warmup_steps": self.config.trial_warmup_steps}
        if self.config.trial_platform:
            spec["platform"] = self.config.trial_platform
        if self.config.trial_host_device_count:
            spec["host_device_count"] = self.config.trial_host_device_count
        return spec

    def _score(self, res: TrialResult) -> float:
        """Higher is better, per the configured metric."""
        if self.config.metric == C.AUTOTUNING_METRIC_LATENCY:
            return -res.step_ms
        return res.tokens_per_sec

    # -- main loop ------------------------------------------------------
    def tune(self) -> Optional[Dict]:
        cfg = self.config
        best_path = os.path.join(cfg.results_dir, C.BEST_CONFIG_FILE)
        if not cfg.overwrite and os.path.exists(best_path):
            # resume semantics (reference reuses finished experiments when
            # not overwriting, autotuning/autotuner.py "overwrite" knob)
            logger.info(f"autotuning: reusing existing {best_path} "
                        "(overwrite=False)")
            with open(best_path) as f:
                return json.load(f)
        profile = profile_model(self.model_spec, self.seq_len)
        space = self.build_space(profile)
        if not space:
            logger.warning("autotuning: no feasible candidates")
            return None
        tuner = get_tuner(cfg.tuner_type, space, profile, self.chip)
        sched = TrialScheduler(cfg.results_dir,
                               timeout_s=cfg.trial_timeout_s,
                               in_process=cfg.in_process)

        best: Optional[Tuple[Candidate, TrialResult]] = None
        since_improvement = 0
        for i, cand in enumerate(tuner.order()):
            if i >= cfg.max_trials:
                logger.info(f"autotuning: max_trials={cfg.max_trials} reached")
                break
            if since_improvement >= cfg.tuner_early_stopping:
                logger.info("autotuning: early stop "
                            f"({since_improvement} trials w/o improvement)")
                break
            res = sched.run(cand.name(), self._trial_spec(cand))
            tuner.record(cand, res.tokens_per_sec if res.ok else None)
            self.results.append((cand, res))
            logger.info(
                f"trial {cand.name()}: "
                + (f"{res.tokens_per_sec:,.0f} tokens/s "
                   f"({res.step_ms:.1f} ms/step)" if res.ok
                   else f"FAILED ({(res.error or '')[:120]})"))
            if res.ok and (best is None
                           or self._score(res) > self._score(best[1])):
                best, since_improvement = (cand, res), 0
            elif best is not None:
                since_improvement += 1
            # failures before the first success (e.g. the memory model was
            # optimistic and the big candidates OOM) never trigger the early
            # stop — max_trials still bounds the search

        self._write_summary(best)
        return self._best_payload(best) if best else None

    # -- outputs --------------------------------------------------------
    def _best_payload(self, best) -> Dict:
        cand, res = best
        return {
            "candidate": dataclasses.asdict(cand),
            "ds_config": self._trial_spec(cand)["ds_config"],
            # identity: whoever applies the tuned config must check it was
            # produced for THEIR model/seq/chip count before honoring it
            "model_spec": self.model_spec,
            "seq_len": self.seq_len,
            "dp": self.dp,
            "tokens_per_sec": res.tokens_per_sec,
            "step_ms": res.step_ms,
        }

    def _write_summary(self, best):
        os.makedirs(self.config.results_dir, exist_ok=True)
        summary = {
            "chip": dataclasses.asdict(self.chip),
            "trials": [{"candidate": dataclasses.asdict(c),
                        **r.to_json()} for c, r in self.results],
        }
        with open(os.path.join(self.config.results_dir, C.SUMMARY_FILE),
                  "w") as f:
            json.dump(summary, f, indent=2)
        if best:
            with open(os.path.join(self.config.results_dir,
                                   C.BEST_CONFIG_FILE), "w") as f:
                json.dump(self._best_payload(best), f, indent=2)
            logger.info(
                f"autotuning: best = {best[0].name()} "
                f"({best[1].tokens_per_sec:,.0f} tokens/s); configs written "
                f"to {self.config.results_dir}/")
