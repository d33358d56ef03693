"""Llama model family: HF logits parity, engine training across ZeRO/TP,
decode. (BASELINE tracked config: Llama-2 7B ZeRO-3; reference surface:
model_implementations + llama-style replace policies.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.llama import (LlamaConfig, LlamaForTraining,
                                        LlamaModel)
from deepspeed_tpu.parallel.topology import MeshTopology, reset_topology
from deepspeed_tpu.runtime.state_dict_factory import (LlamaWeightMap,
                                                      detect_arch,
                                                      load_hf_llama)

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")


@pytest.fixture(autouse=True)
def _fresh_topology():
    reset_topology()
    yield
    reset_topology()


def _tiny_hf_llama(kv_heads=2):
    cfg = transformers.LlamaConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=kv_heads, max_position_embeddings=32,
        rms_norm_eps=1e-5, rope_theta=10000.0, tie_word_embeddings=False,
        attention_dropout=0.0)
    torch.manual_seed(0)
    return transformers.LlamaForCausalLM(cfg).eval(), cfg


class TestHFParity:
    @pytest.mark.parametrize("kv_heads", [4, 2])  # MHA and GQA
    def test_logits_match_hf(self, kv_heads):
        hf, cfg = _tiny_hf_llama(kv_heads)
        config, params = load_hf_llama(
            hf.state_dict(), num_attention_heads=cfg.num_attention_heads,
            num_key_value_heads=cfg.num_key_value_heads,
            max_position_embeddings=cfg.max_position_embeddings)
        assert config.num_hidden_layers == 2
        assert config.kv_heads == kv_heads
        model = LlamaModel(config)
        ids = np.array([[3, 17, 42, 99, 7, 23, 56, 1]], np.int32)
        ours = np.asarray(jax.jit(model.apply)({"params": params}, ids))
        with torch.no_grad():
            theirs = hf(torch.tensor(ids, dtype=torch.long)).logits.numpy()
        np.testing.assert_allclose(ours, theirs, atol=3e-4, rtol=3e-4)

    def test_detect_arch(self):
        hf, _ = _tiny_hf_llama()
        sd = {k: v.numpy() for k, v in hf.state_dict().items()}
        assert detect_arch(sd) == "llama"

    def test_loop_layout_agrees_with_scan(self):
        hf, cfg = _tiny_hf_llama()
        out = []
        for scan in (True, False):
            config, params = load_hf_llama(
                hf.state_dict(), scan_layers=scan,
                num_attention_heads=cfg.num_attention_heads,
                num_key_value_heads=cfg.num_key_value_heads,
                max_position_embeddings=32)
            ids = np.array([[1, 2, 3, 4]], np.int32)
            out.append(np.asarray(
                jax.jit(LlamaModel(config).apply)({"params": params}, ids)))
        np.testing.assert_allclose(out[0], out[1], atol=1e-5)


class TestTraining:
    @pytest.mark.parametrize("axes,stage", [({"data": 8}, 3),
                                            ({"data": 4, "model": 2}, 1)])
    def test_engine_trains(self, axes, stage):
        topo = MeshTopology(axis_sizes=axes)
        dp = topo.get_data_parallel_world_size()
        model = LlamaForTraining(LlamaConfig.tiny(
            dtype=jnp.float32, num_key_value_heads=2))
        engine, *_ = deepspeed_tpu.initialize(
            model=model, mesh=topo,
            config={"train_batch_size": 2 * dp,
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                    "zero_optimization": {"stage": stage},
                    "steps_per_print": 10_000})
        ids = np.random.default_rng(0).integers(
            0, 256, (2 * dp, 16)).astype(np.int32)
        losses = []
        for _ in range(3):
            loss = engine({"input_ids": ids})
            engine.backward(loss)
            engine.step()
            losses.append(float(loss))
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0]

    def test_activation_checkpointing_hook(self):
        model = LlamaForTraining(LlamaConfig.tiny(dtype=jnp.float32))
        engine, *_ = deepspeed_tpu.initialize(
            model=model,
            config={"train_batch_size": 8,
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                    "activation_checkpointing": {"enabled": True,
                                                 "policy": "dots"},
                    "steps_per_print": 10_000})
        assert engine.module.config.remat is True
        assert engine.module.config.remat_policy == "dots"


class TestDecode:
    def test_decode_matches_prefill_logits(self):
        """Prefill then token-by-token decode reproduce the dense forward's
        final-position logits (KV cache + RoPE positions correct)."""
        cfg = LlamaConfig.tiny(dtype=jnp.float32, num_key_value_heads=2,
                               scan_layers=True)
        model = LlamaModel(cfg)
        ids = np.array([[5, 9, 2, 7, 3, 8]], np.int32)
        params = jax.jit(model.init)(jax.random.PRNGKey(0), ids)["params"]
        dense = np.asarray(jax.jit(model.apply)({"params": params}, ids))

        dcfg = cfg.for_decode()
        dmodel = LlamaModel(dcfg)
        vars0 = jax.jit(dmodel.init)(jax.random.PRNGKey(0), ids[:, :1])
        # (one program a step shape: op by op a pass compiles some hundreds)
        step = jax.jit(lambda p, cache, ids: dmodel.apply(
            {"params": p, "cache": cache}, ids, mutable=["cache"]))
        # init runs a forward: reset the cache (index included) to zero
        cache = jax.tree_util.tree_map(jnp.zeros_like, vars0["cache"])
        # prefill on the first 3 tokens
        logits, mut = step(params, cache, ids[:, :3])
        cache = mut["cache"]
        np.testing.assert_allclose(np.asarray(logits[:, -1]),
                                   dense[:, 2], atol=2e-4, rtol=2e-4)
        # decode the rest one token at a time
        for t in range(3, 6):
            logits, mut = step(params, cache, ids[:, t:t + 1])
            cache = mut["cache"]
            np.testing.assert_allclose(np.asarray(logits[:, -1]),
                                       dense[:, t], atol=2e-4, rtol=2e-4)


class TestWeightMap:
    def test_map_covers_hf_keys(self):
        hf, _ = _tiny_hf_llama()
        sd = {k: v.numpy() for k, v in hf.state_dict().items()}
        wm = LlamaWeightMap()
        lw = wm.layer_weights(sd, 0)
        assert set(lw) == set(wm.layer_map)
        top = wm.top_weights(sd)
        assert {"embed_tokens", "norm.scale", "lm_head"} <= set(top)
        # orientation: HF [out, in] -> flax [in, out]
        assert lw["self_attn.q_proj.kernel"].shape == (32, 32)
        assert lw["mlp.gate_proj.kernel"].shape == (32, 64)
