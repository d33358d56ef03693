"""Autotuning subsystem (reference tests/unit/autotuning/test_autotuning.py;
subsystem deepspeed/autotuning/autotuner.py:31)."""

import json
import os

import pytest

from deepspeed_tpu.autotuning import (Autotuner, AutotuningConfig, Candidate,
                                      ChipSpec, ModelProfile, build_space,
                                      estimate_hbm_bytes, get_tuner,
                                      predict_throughput, profile_model)

TINY = {"preset": "gpt2",
        "config": {"n_layer": 2, "n_embd": 64, "n_head": 4,
                   "vocab_size": 256, "n_positions": 64, "dtype": "float32"}}


# the tuner plans for a chip it can name; the CPU these tests run on has
# no peaks (utils/device.py), so they name the v5e
V5E = ChipSpec.from_kind("TPU v5 lite")


def _profile():
    return ModelProfile(n_params=125_000_000, n_layer=12, n_embd=768,
                        vocab_size=50257, seq_len=1024)


class TestMemoryModel:
    def test_zero_shards_shrink_footprint(self):
        p = _profile()
        c0 = Candidate(16, 0, "dots")
        c3 = Candidate(16, 3, "dots")
        assert estimate_hbm_bytes(p, c0, dp=8) > estimate_hbm_bytes(p, c3, dp=8)
        # on one chip the stages cost the same
        assert estimate_hbm_bytes(p, c0, dp=1) == estimate_hbm_bytes(p, c3, dp=1)

    def test_remat_policy_orders_activation_memory(self):
        p = _profile()
        none, dots, full = (estimate_hbm_bytes(p, Candidate(16, 0, pol))
                            for pol in ("none", "dots", "full"))
        assert none > dots > full

    def test_space_prunes_oversized_micro_batch(self):
        p = _profile()
        # 16 GiB chip: mb 512 at "none" cannot fit
        space = build_space(p, micro_batch_sizes=[8, 512], zero_stages=[0],
                            remat_policies=["none"], hbm_bytes=16 << 30)
        mbs = {c.micro_batch for c in space}
        assert 8 in mbs and 512 not in mbs

    def test_dp_unlocks_zero_stages(self):
        p = _profile()
        solo = build_space(p, None, None, ["dots"], 16 << 30, dp=1)
        fleet = build_space(p, None, None, ["dots"], 16 << 30, dp=8)
        assert {c.zero_stage for c in solo} == {0}
        assert {c.zero_stage for c in fleet} == {0, 1, 2, 3}

    def test_fused_step_axis_enumerable(self):
        p = _profile()
        space = build_space(p, [8], [0], ["dots"], 16 << 30,
                            fused_steps=[True, False])
        assert {c.fused_step for c in space} == {True, False}

    def test_space_derives_micro_batches(self):
        p = _profile()
        space = build_space(p, micro_batch_sizes=None, zero_stages=[0],
                            remat_policies=["full"], hbm_bytes=16 << 30)
        mbs = sorted({c.micro_batch for c in space})
        assert mbs and mbs == [2 ** i for i in range(len(mbs))]


class TestCostModel:
    def test_bigger_batch_amortizes_overhead(self):
        p = _profile()
        chip = V5E
        assert (predict_throughput(p, Candidate(16, 0, "dots"), chip)
                >= predict_throughput(p, Candidate(1, 0, "dots"), chip))

    def test_full_remat_costs_flops(self):
        p = _profile()
        chip = V5E
        assert (predict_throughput(p, Candidate(16, 0, "dots"), chip)
                > predict_throughput(p, Candidate(16, 0, "full"), chip))

    def test_model_based_tuner_orders_by_prediction(self):
        p = _profile()
        space = [Candidate(1, 0, "full"), Candidate(16, 0, "dots"),
                 Candidate(4, 0, "full")]
        tuner = get_tuner("model_based", space, p, V5E)
        ordered = tuner.order()
        preds = [predict_throughput(p, c, tuner.chip) for c in ordered]
        assert preds == sorted(preds, reverse=True)

    def test_gridsearch_and_random_cover_space(self):
        p = _profile()
        space = [Candidate(m, 0, "dots") for m in (1, 2, 4)]
        for kind in ("gridsearch", "random"):
            assert set(get_tuner(kind, space, p, V5E).order()) == set(space)


class TestProfileModel:
    def test_counts_params_without_device_step(self):
        prof = profile_model(TINY, seq_len=32)
        assert prof.n_layer == 2 and prof.n_embd == 64
        # wte 256*64 + wpe 64*64 + blocks + ln_f
        assert 100_000 < prof.n_params < 300_000


@pytest.mark.heavy
class TestAutotunerEndToEnd:
    @pytest.mark.parametrize("in_process", [True, False])
    def test_tunes_tiny_gpt2(self, tmp_path, in_process):
        atc = AutotuningConfig(
            enabled=True, max_trials=2, trial_steps=2, trial_warmup_steps=1,
            micro_batch_sizes=[2, 4], zero_stages=[0],
            remat_policies=["none"], results_dir=str(tmp_path),
            in_process=in_process, trial_timeout_s=300,
            trial_platform="cpu")
        base = {"optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "steps_per_print": 10_000}
        best = Autotuner(model_spec=TINY, base_ds_config=base, config=atc,
                         seq_len=32, chip=V5E).tune()
        assert best is not None and best["tokens_per_sec"] > 0
        assert best["candidate"]["micro_batch"] in (2, 4)
        assert os.path.exists(tmp_path / "best_config.json")
        summary = json.loads((tmp_path / "summary.json").read_text())
        assert len(summary["trials"]) == 2
        assert all(t["ok"] for t in summary["trials"])

    def test_failed_candidate_recorded_not_fatal(self, tmp_path):
        atc = AutotuningConfig(
            enabled=True, max_trials=2, trial_steps=1,
            micro_batch_sizes=[2], zero_stages=[0, 7],  # stage 7 is invalid
            remat_policies=["none"], results_dir=str(tmp_path),
            in_process=True)
        base = {"optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "steps_per_print": 10_000}
        best = Autotuner(model_spec=TINY, base_ds_config=base, config=atc,
                         seq_len=32, chip=V5E).tune()
        summary = json.loads((tmp_path / "summary.json").read_text())
        assert len(summary["trials"]) == 2
        assert sum(t["ok"] for t in summary["trials"]) == 1
        assert best is not None
