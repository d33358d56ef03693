"""GPT-MoE decoder family (models/gpt2_moe.py): the BASELINE-tracked
MoE-expert-parallel config as a real transformer — scanned dense/MoE pair
layout, expert-axis sharding via the model's param_specs, aux-loss in the
objective, and decode (reference: Megatron-GPT + deepspeed.moe.layer.MoE)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.gpt2_moe import (GPTMoEConfig, GPTMoEForTraining,
                                           GPTMoEModel)
from deepspeed_tpu.parallel.topology import (MeshTopology, reset_topology,
                                             set_topology)


@pytest.fixture(autouse=True)
def _fresh_topology():
    reset_topology()
    yield
    reset_topology()


def _batch(seed=0, B=8, T=16):
    rng = np.random.default_rng(seed)
    return {"input_ids": rng.integers(0, 256, (B, T)).astype(np.int32)}


def _train(axis_sizes, steps=4, num_experts=4, scan=True, seed=0):
    reset_topology()
    n = int(np.prod(list(axis_sizes.values())))
    topo = MeshTopology(axis_sizes=axis_sizes, devices=jax.devices()[:n])
    set_topology(topo)
    cfg = GPTMoEConfig.tiny(num_experts=num_experts,
                            gpt_kw={"dtype": jnp.float32,
                                    "scan_layers": scan})
    model = GPTMoEForTraining(cfg)
    engine, *_ = deepspeed_tpu.initialize(
        model=model, mesh=topo,
        config={"train_batch_size": 8,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 1},
                "steps_per_print": 10_000})
    b = _batch(seed)
    losses = []
    for _ in range(steps):
        loss = engine(b)
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    return losses, engine


class TestGPTMoE:
    def test_forward_shapes_and_aux(self):
        cfg = GPTMoEConfig.tiny(gpt_kw={"dtype": jnp.float32})
        model = GPTMoEModel(cfg)
        ids = _batch()["input_ids"]
        params = jax.jit(model.init)(jax.random.PRNGKey(0), ids)["params"]
        logits, l_aux = jax.jit(model.apply)({"params": params}, ids)
        assert logits.shape == (8, 16, 256)
        assert float(l_aux) > 0  # load-balance loss is live, not a stub
        # scanned pair layout: expert params are [n_pairs, E, ...]
        wi = params["h"]["moe_block"]["moe"]["experts"]["wi"]["kernel"]
        assert wi.shape[:2] == (1, 4)

    def test_trains_dp(self):
        losses, _ = _train({"data": 8})
        assert losses[-1] < losses[0]

    def test_expert_parallel_matches_dp(self):
        """EP is a layout choice: the loss trajectory over {data:2,
        expert:4} must match pure DP (GShard all-to-all inserted by GSPMD
        preserves semantics)."""
        dp, _ = _train({"data": 8})
        ep, engine = _train({"data": 2, "expert": 4})
        np.testing.assert_allclose(dp, ep, rtol=2e-4, atol=2e-5)
        # expert params actually sharded: each device holds E/ep experts
        wi = engine.state.params["h"]["moe_block"]["moe"]["experts"]["wi"]["kernel"]
        shard = wi.addressable_shards[0].data
        assert shard.shape[1] == wi.shape[1] // 4

    @pytest.mark.heavy
    def test_ep_with_tp(self):
        losses, _ = _train({"data": 2, "expert": 2, "model": 2})
        dp, _ = _train({"data": 8})
        np.testing.assert_allclose(dp, losses, rtol=2e-4, atol=2e-5)

    def test_unrolled_layout_trains(self):
        losses, _ = _train({"data": 4}, scan=False)
        assert losses[-1] < losses[0]

    @pytest.mark.heavy
    def test_serves_through_inference_engine(self):
        """init_inference handles the (logits, aux) output contract: greedy
        generation continues the argmax chain of the dense forward."""
        cfg = GPTMoEConfig.tiny(gpt_kw={"dtype": jnp.float32,
                                        "n_positions": 16})
        model = GPTMoEModel(cfg)
        ids = np.array([[3, 17, 42, 99]], np.int32)
        params = jax.jit(model.init)(jax.random.PRNGKey(0), ids)["params"]
        engine = deepspeed_tpu.init_inference(model, params=params)
        out = np.asarray(engine.generate(ids, max_new_tokens=3,
                                         do_sample=False))
        # reference chain: greedy-extend with the dense (non-cached) model
        cur = ids
        for _ in range(3):
            logits, _ = jax.jit(model.apply)({"params": params}, cur)
            nxt = np.argmax(np.asarray(logits[:, -1]), axis=-1)
            cur = np.concatenate([cur, nxt[:, None].astype(np.int32)], axis=1)
        np.testing.assert_array_equal(out, cur)

    @pytest.mark.heavy
    def test_decode_matches_dense(self):
        cfg = GPTMoEConfig.tiny(gpt_kw={"dtype": jnp.float32,
                                        "n_positions": 16})
        model = GPTMoEModel(cfg)
        ids = np.array([[3, 17, 42, 99, 7, 23, 56, 1]], np.int32)
        params = jax.jit(model.init)(jax.random.PRNGKey(0), ids)["params"]
        dense, _ = jax.jit(model.apply)({"params": params}, ids)
        dmodel = GPTMoEModel(cfg.for_decode())
        vars0 = jax.jit(dmodel.init)(jax.random.PRNGKey(0), ids[:, :1])
        cache = jax.tree_util.tree_map(jnp.zeros_like, vars0["cache"])
        # (one program a step shape: op by op a pass compiles some hundreds)
        step = jax.jit(lambda p, cache, ids: dmodel.apply(
            {"params": p, "cache": cache}, ids, mutable=["cache"]))
        (logits, _), mut = step(params, cache, ids[:, :4])
        cache = mut["cache"]
        np.testing.assert_allclose(np.asarray(logits[:, -1]),
                                   np.asarray(dense[:, 3]),
                                   atol=3e-4, rtol=3e-4)
        for t in range(4, 8):
            (logits, _), mut = step(params, cache, ids[:, t:t + 1])
            cache = mut["cache"]
            np.testing.assert_allclose(np.asarray(logits[:, -1]),
                                       np.asarray(dense[:, t]),
                                       atol=3e-4, rtol=3e-4)
