"""GPT-J and GPT-NeoX served by the canonical fused decoder: HF logits
parity, rotary decode-cache consistency, and engine training (reference
arch coverage: module_inject/replace_policy.py GPTJ/GPTNEOX entries;
weight maps in runtime/state_dict_factory.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference import from_pretrained
from deepspeed_tpu.models.gpt2 import GPT2ForTraining, GPT2LMHeadModel
from deepspeed_tpu.parallel.topology import reset_topology
from deepspeed_tpu.runtime.state_dict_factory import (detect_arch,
                                                      load_hf_gpt_neox,
                                                      load_hf_gptj)

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")


@pytest.fixture(autouse=True)
def _fresh_topology():
    reset_topology()
    yield
    reset_topology()


def _tiny_hf_gptj():
    cfg = transformers.GPTJConfig(
        vocab_size=128, n_embd=32, n_layer=2, n_head=4, n_positions=32,
        rotary_dim=4, n_inner=None, resid_pdrop=0.0, embd_pdrop=0.0,
        attn_pdrop=0.0)
    torch.manual_seed(0)
    return transformers.GPTJForCausalLM(cfg).eval(), cfg


def _tiny_hf_neox(parallel=True):
    cfg = transformers.GPTNeoXConfig(
        vocab_size=128, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=128, rotary_pct=0.25,
        max_position_embeddings=32, use_parallel_residual=parallel,
        hidden_dropout=0.0, attention_dropout=0.0)
    torch.manual_seed(0)
    return transformers.GPTNeoXForCausalLM(cfg).eval(), cfg


IDS = np.array([[3, 17, 42, 99, 7, 23, 56, 1]], np.int32)


def _decode_consistency(config, params, atol=3e-4):
    """Prefill + token-by-token decode reproduces the dense forward —
    exercises the rotate-before-cache rotary path."""
    model = GPT2LMHeadModel(config)
    dense = np.asarray(jax.jit(model.apply)({"params": params}, IDS))
    dmodel = GPT2LMHeadModel(config.for_decode())
    vars0 = jax.jit(dmodel.init)(jax.random.PRNGKey(0), IDS[:, :1])
    cache = jax.tree_util.tree_map(jnp.zeros_like, vars0["cache"])
    # (one program a step shape: op by op a pass compiles some hundreds)
    step = jax.jit(lambda p, cache, ids: dmodel.apply(
        {"params": p, "cache": cache}, ids, mutable=["cache"]))
    logits, mut = step(params, cache, IDS[:, :4])
    cache = mut["cache"]
    np.testing.assert_allclose(np.asarray(logits[:, -1]), dense[:, 3],
                               atol=atol, rtol=atol)
    for t in range(4, 8):
        logits, mut = step(params, cache, IDS[:, t:t + 1])
        cache = mut["cache"]
        np.testing.assert_allclose(np.asarray(logits[:, -1]), dense[:, t],
                                   atol=atol, rtol=atol)


class TestGPTJ:
    def test_logits_match_hf(self):
        hf, cfg = _tiny_hf_gptj()
        config, params = load_hf_gptj(hf.state_dict(), n_head=cfg.n_head,
                                      rotary_dim=cfg.rotary_dim,
                                      n_positions=cfg.n_positions)
        assert config.position_embedding == "rotary"
        assert config.rotary_interleaved
        assert config.residual == "parallel_single_ln"
        assert not config.attn_bias
        assert not config.tied_head and config.lm_head_bias
        ours = np.asarray(GPT2LMHeadModel(config).apply(
            {"params": params}, IDS))
        with torch.no_grad():
            theirs = hf(torch.tensor(IDS, dtype=torch.long)).logits.numpy()
        np.testing.assert_allclose(ours, theirs, atol=3e-4, rtol=3e-4)

    def test_detect_arch(self):
        hf, _ = _tiny_hf_gptj()
        assert detect_arch({k: None for k in hf.state_dict()}) == "gptj"

    def test_decode_matches_dense(self):
        hf, cfg = _tiny_hf_gptj()
        config, params = load_hf_gptj(hf.state_dict(), n_head=cfg.n_head,
                                      rotary_dim=cfg.rotary_dim,
                                      n_positions=16)
        _decode_consistency(config, params)

    def test_trains_through_engine(self):
        hf, cfg = _tiny_hf_gptj()
        config, params = load_hf_gptj(hf.state_dict(), n_head=cfg.n_head,
                                      rotary_dim=cfg.rotary_dim,
                                      n_positions=cfg.n_positions)
        model = GPT2ForTraining(config)
        engine, *_ = deepspeed_tpu.initialize(
            model=model, model_parameters=params,
            config={"train_batch_size": 8,
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                    "zero_optimization": {"stage": 1},
                    "steps_per_print": 10_000})
        ids = np.random.default_rng(0).integers(0, 128, (8, 16)).astype(
            np.int32)
        losses = []
        for _ in range(3):
            loss = engine({"input_ids": ids})
            engine.backward(loss)
            engine.step()
            losses.append(float(loss))
        assert losses[-1] < losses[0]


class TestGPTNeoX:
    @pytest.mark.parametrize("parallel", [True, False])
    def test_logits_match_hf(self, parallel):
        hf, cfg = _tiny_hf_neox(parallel)
        config, params = load_hf_gpt_neox(
            hf.state_dict(), n_head=cfg.num_attention_heads,
            rotary_pct=cfg.rotary_pct, use_parallel_residual=parallel,
            max_positions=cfg.max_position_embeddings)
        assert config.position_embedding == "rotary"
        assert not config.rotary_interleaved
        assert config.residual == ("parallel_two_ln" if parallel
                                   else "sequential")
        assert config.activation == "gelu_exact"
        assert not config.tied_head and not config.lm_head_bias
        ours = np.asarray(GPT2LMHeadModel(config).apply(
            {"params": params}, IDS))
        with torch.no_grad():
            theirs = hf(torch.tensor(IDS, dtype=torch.long)).logits.numpy()
        np.testing.assert_allclose(ours, theirs, atol=3e-4, rtol=3e-4)

    def test_detect_arch(self):
        hf, _ = _tiny_hf_neox()
        assert detect_arch({k: None for k in hf.state_dict()}) == "gpt-neox"

    def test_decode_matches_dense(self):
        hf, cfg = _tiny_hf_neox()
        config, params = load_hf_gpt_neox(
            hf.state_dict(), n_head=cfg.num_attention_heads,
            rotary_pct=cfg.rotary_pct, max_positions=16)
        _decode_consistency(config, params)


class TestAutoServe:
    def test_from_pretrained_gptj(self, tmp_path):
        """End-to-end: HF dir on disk → arch detection → serving engine →
        greedy tokens match HF (reference init_inference + policy flow)."""
        hf, cfg = _tiny_hf_gptj()
        hf.save_pretrained(tmp_path)
        engine = from_pretrained(str(tmp_path))
        out = engine.generate(IDS, max_new_tokens=4, do_sample=False)
        with torch.no_grad():
            ref = hf.generate(torch.tensor(IDS, dtype=torch.long),
                              max_new_tokens=4, do_sample=False).numpy()
        np.testing.assert_array_equal(np.asarray(out), ref)
