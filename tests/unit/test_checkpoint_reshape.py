"""Checkpoint reshaping tests (reference ``tests/unit/checkpoint/`` +
``tests/unit/model_parallelism``): restore across different zero stages,
mesh layouts, and TP degrees; fp32 consolidation."""

import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.checkpoint import (DeepSpeedCheckpoint,
                                      get_fp32_state_dict_from_zero_checkpoint)
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2ForTraining
from deepspeed_tpu.parallel.topology import reset_topology


@pytest.fixture(autouse=True)
def _fresh_topology():
    reset_topology()
    yield
    reset_topology()


def _engine(zero_stage=0, mesh=None, micro=1):
    cfg = GPT2Config.tiny(dtype=jnp.float32, use_flash=False)
    ds = {"train_batch_size": 8,
          "train_micro_batch_size_per_gpu": micro,
          "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
          "zero_optimization": {"stage": zero_stage}}
    if mesh:
        ds["mesh"] = mesh
    engine, *_ = deepspeed_tpu.initialize(model=GPT2ForTraining(cfg), config=ds)
    return engine


BATCH = {"input_ids": (np.arange(8 * 16).reshape(8, 16) % 23).astype(np.int32)}


class TestElasticRestore:
    @pytest.mark.parametrize("save_stage,load_stage", [(2, 0), (0, 2), (2, 3)])
    def test_restore_across_zero_stages(self, tmp_path, save_stage, load_stage):
        """The universal-checkpoint capability: consolidated storage restores
        under any partitioning (reference universal_checkpoint.py)."""
        e1 = _engine(zero_stage=save_stage)
        for _ in range(3):
            e1.train_batch(batch=BATCH)
        loss_before = e1.train_batch(batch=BATCH)
        e1.save_checkpoint(str(tmp_path))
        reset_topology()

        e2 = _engine(zero_stage=load_stage)
        e2.train_batch(batch=BATCH)  # build state under the new partitioning
        e2.load_checkpoint(str(tmp_path))
        p1 = jax.device_get(e1.state.params)
        p2 = jax.device_get(e2.state.params)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6), p1, p2)
        loss_after = e2.train_batch(batch=BATCH)
        # same params + same data → compatible loss trajectory
        assert abs(loss_after - loss_before) / loss_before < 0.2

    def test_restore_across_mesh_shapes(self, tmp_path):
        """Save on a pure-DP mesh, restore on a TP×DP mesh (reference
        reshape_meg_2d capability)."""
        e1 = _engine(zero_stage=1, mesh={"data": -1})
        e1.train_batch(batch=BATCH)
        e1.save_checkpoint(str(tmp_path))
        reset_topology()

        e2 = _engine(zero_stage=1, mesh={"data": -1, "model": 2}, micro=2)
        e2.train_batch(batch=BATCH)
        e2.load_checkpoint(str(tmp_path))
        p1 = jax.device_get(e1.state.params)
        p2 = jax.device_get(e2.state.params)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6), p1, p2)
        e2.train_batch(batch=BATCH)  # still trains


class TestDeepSpeedCheckpoint:
    def test_inspect_and_tp_slice(self, tmp_path):
        e = _engine()
        e.train_batch(batch=BATCH)
        e.save_checkpoint(str(tmp_path))

        ckpt = DeepSpeedCheckpoint(str(tmp_path), target_tp=4)
        names = ckpt.parameter_names()
        assert any("wte" in n for n in names)
        summary = ckpt.show_summary()
        assert summary["num_params"] == len(names)
        assert summary["global_steps"] == 1

        name = next(n for n in names if n.endswith("c_attn/kernel"))
        full = ckpt.get_parameter(name)
        shards = [ckpt.slice_for_tp(name, r, dim=-1) for r in range(4)]
        assert shards[0].shape[-1] == full.shape[-1] // 4
        merged = ckpt.merge_tp_slices(shards, dim=-1)
        np.testing.assert_array_equal(merged, full)

    def test_fp32_consolidation_and_cli(self, tmp_path):
        e = _engine()
        e.train_batch(batch=BATCH)
        e.save_checkpoint(str(tmp_path))

        sd = get_fp32_state_dict_from_zero_checkpoint(str(tmp_path))
        assert all(v.dtype == np.float32 for v in sd.values())
        live = jax.device_get(e.state.params)
        flat_live = {}

        def walk(tree, prefix=""):
            for k, v in tree.items():
                if isinstance(v, dict):
                    walk(v, f"{prefix}{k}/")
                else:
                    flat_live[f"{prefix}{k}"] = np.asarray(v)
        walk(live)
        assert set(sd) == set(flat_live)
        np.testing.assert_allclose(sd["wte"], flat_live["wte"], rtol=1e-6)

        out = str(tmp_path / "consolidated.npz")
        r = subprocess.run([sys.executable, "bin/zero_to_fp32",
                            str(tmp_path), out], capture_output=True,
                           text=True, cwd="/root/repo", timeout=300)
        assert r.returncode == 0, r.stderr
        loaded = np.load(out)
        np.testing.assert_allclose(loaded["wte"], sd["wte"])

    def test_module_loader_patches_flax_holder(self, tmp_path):
        # deepspeed.utils.zero_to_fp32.load_state_dict_from_zero_checkpoint:
        # the .params branch must install the NESTED tree and serve
        # identical logits through the inference engine
        from deepspeed_tpu.utils.zero_to_fp32 import (
            load_state_dict_from_zero_checkpoint)

        e = _engine(zero_stage=2, mesh={"data": 4, "model": 2})
        e.train_batch(batch=BATCH)
        e.save_checkpoint(str(tmp_path))
        live_logits = None

        class Holder:
            params = None

        reset_topology()
        holder = load_state_dict_from_zero_checkpoint(Holder(),
                                                      str(tmp_path))
        assert isinstance(holder.params, dict)
        from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel

        model = GPT2LMHeadModel(GPT2Config.tiny(dtype=jnp.float32,
                                                use_flash=False))
        eng = deepspeed_tpu.init_inference(model, params=holder.params,
                                           dtype="fp32")
        ids = BATCH["input_ids"][:2]
        got = np.asarray(eng(ids))
        want = np.asarray(jax.device_get(model.apply(
            {"params": jax.device_get(e.state.params)}, jnp.asarray(ids))))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
