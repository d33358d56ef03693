"""Inference engine tests.

Mirrors the reference ``tests/unit/inference/test_inference.py`` strategy —
generation correctness across dtypes and TP degrees — on the virtual CPU
mesh instead of downloaded HF models.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference import DeepSpeedInferenceConfig, InferenceEngine
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2ForTraining, GPT2LMHeadModel
from deepspeed_tpu.parallel.topology import reset_topology


@pytest.fixture(autouse=True)
def _fresh_topology():
    reset_topology()
    yield
    reset_topology()


def _tiny(dtype=jnp.float32, **kw):
    return GPT2Config.tiny(dtype=dtype, use_flash=False, **kw)


class TestDecodeParity:
    """KV-cache decode must match the full (uncached) forward — the analog
    of the reference kernel-vs-baseline checks in tests/unit/ops."""

    @pytest.mark.parametrize("scan_layers", [True, False])
    def test_prefill_and_decode_match_full_forward(self, scan_layers):
        cfg = _tiny(scan_layers=scan_layers)
        model = GPT2LMHeadModel(cfg)
        rng = jax.random.PRNGKey(0)
        ids = jax.random.randint(rng, (2, 12), 0, cfg.vocab_size)
        params = model.init(rng, ids)["params"]
        full = model.apply({"params": params}, ids)

        dmodel = GPT2LMHeadModel(cfg.for_decode())
        out, vars_ = dmodel.apply({"params": params}, ids[:, :7],
                                  mutable=["cache"])
        np.testing.assert_allclose(out, full[:, :7], rtol=2e-4, atol=2e-4)
        cache = vars_["cache"]
        for t in range(7, 12):
            out, vars_ = dmodel.apply({"params": params, "cache": cache},
                                      ids[:, t:t + 1], mutable=["cache"])
            cache = vars_["cache"]
            np.testing.assert_allclose(out[:, 0], full[:, t],
                                       rtol=2e-4, atol=2e-4)


class TestInferenceEngine:
    def test_greedy_generate_matches_manual_argmax(self):
        cfg = _tiny()
        model = GPT2LMHeadModel(cfg)
        engine = deepspeed_tpu.init_inference(model, dtype="fp32")
        prompt = np.arange(5, dtype=np.int32)[None] % cfg.vocab_size
        out = engine.generate(prompt, max_new_tokens=4)
        assert out.shape == (1, 9)
        # manual greedy rollout through the uncached forward
        ids = prompt.copy()
        for _ in range(4):
            logits = np.asarray(engine.forward(jnp.asarray(ids)))
            nxt = logits[:, -1].argmax(-1)[:, None]
            ids = np.concatenate([ids, nxt], axis=1)
        np.testing.assert_array_equal(out, ids)

    @pytest.mark.parametrize("dtype", ["fp32", "int8"])
    def test_forward_last_matches_full_forward(self, dtype):
        # the serving prefill (TTFT): last-position logits
        # sliced INSIDE the jit must equal the full forward's last column
        # — including through the int8 dequant path
        cfg = _tiny()
        engine = deepspeed_tpu.init_inference(GPT2LMHeadModel(cfg),
                                              dtype=dtype)
        ids = np.random.default_rng(0).integers(
            0, cfg.vocab_size, (2, 7)).astype(np.int32)
        np.testing.assert_allclose(
            np.asarray(engine.forward_last(ids)),
            np.asarray(engine.forward(ids))[:, -1], rtol=1e-6, atol=1e-6)

    def test_inert_options_warn_and_tuple_policy_resolves(self, monkeypatch):
        # assert on the warn CALLS (the logger's stream binding predates
        # pytest's capture, so output-based assertions are unreliable)
        import deepspeed_tpu.inference.engine as eng_mod

        calls = []
        monkeypatch.setattr(eng_mod, "log_dist",
                            lambda msg, ranks=None: calls.append(msg))
        cfg = _tiny()
        deepspeed_tpu.init_inference(GPT2LMHeadModel(cfg), dtype="fp32",
                                     enable_cuda_graph=True)
        assert any("enable_cuda_graph" in m and "no effect" in m
                   for m in calls)
        # unset inert keys stay silent
        calls.clear()
        deepspeed_tpu.init_inference(GPT2LMHeadModel(cfg), dtype="fp32")
        assert not any("no effect" in m for m in calls)
        # reference injection_policy_tuple (bare tuple of row-parallel
        # outputs) resolves to a usable policy
        eng = deepspeed_tpu.init_inference(
            GPT2LMHeadModel(cfg), dtype="fp32",
            injection_policy_tuple=("attn.c_proj",))
        assert eng(np.array([[1, 2, 3]], np.int32)).shape == (1, 3,
                                                              cfg.vocab_size)

    def test_training_wrapper_accepted(self):
        cfg = _tiny()
        engine = deepspeed_tpu.init_inference(GPT2ForTraining(cfg), dtype="fp32")
        out = engine.generate(np.array([[1, 2, 3]], dtype=np.int32),
                              max_new_tokens=2)
        assert out.shape == (1, 5)

    def test_sampled_generate_shapes_and_window_check(self):
        cfg = _tiny()
        engine = deepspeed_tpu.init_inference(GPT2LMHeadModel(cfg), dtype="fp32")
        out = engine.generate(np.array([[1, 2, 3]], dtype=np.int32),
                              max_new_tokens=3, do_sample=True,
                              temperature=0.7, top_k=5)
        assert out.shape == (1, 6)
        assert (out < cfg.vocab_size).all()
        with pytest.raises(ValueError, match="exceeds"):
            engine.generate(np.zeros((1, 60), np.int32), max_new_tokens=10)

    def test_top_p_nucleus_sampling(self):
        """top_p → 0 keeps only the most probable token: nucleus sampling
        must reproduce the greedy chain exactly; a loose top_p still
        produces in-vocab tokens."""
        cfg = _tiny()
        engine = deepspeed_tpu.init_inference(GPT2LMHeadModel(cfg),
                                              dtype="fp32")
        ids = np.array([[1, 2, 3]], dtype=np.int32)
        greedy = engine.generate(ids, max_new_tokens=4, do_sample=False)
        nucleus = engine.generate(ids, max_new_tokens=4, do_sample=True,
                                  top_p=1e-9)
        np.testing.assert_array_equal(nucleus, greedy)
        # a loose nucleus over the near-flat logits of a random-init model
        # must actually SAMPLE: different rng draws yield different tokens
        # (guards against the cutoff degenerating to greedy)
        import jax

        draws = {
            tuple(np.asarray(engine.generate(
                ids, max_new_tokens=4, do_sample=True, top_p=0.95,
                temperature=1.0, rng=jax.random.PRNGKey(s)))[0].tolist())
            for s in range(5)}
        assert len(draws) > 1
        for d in draws:
            assert all(t < cfg.vocab_size for t in d)

    def test_eos_early_stop_pads_with_eos(self):
        cfg = _tiny()
        engine = deepspeed_tpu.init_inference(GPT2LMHeadModel(cfg), dtype="fp32")
        out = engine.generate(np.array([[1, 2]], dtype=np.int32),
                              max_new_tokens=6, eos_token_id=-5)
        # impossible eos: no early stop
        assert out.shape == (1, 8)
        # force eos to whatever greedy emits first → all subsequent = eos
        first = int(out[0, 2])
        out2 = engine.generate(np.array([[1, 2]], dtype=np.int32),
                               max_new_tokens=6, eos_token_id=first)
        assert (out2[0, 2:] == first).all()

    def test_model_times_recorded(self):
        cfg = _tiny()
        engine = deepspeed_tpu.init_inference(GPT2LMHeadModel(cfg), dtype="fp32")
        engine.generate(np.array([[1, 2, 3]], dtype=np.int32), max_new_tokens=2)
        times = engine.model_times()
        assert len(times) == 1 and times[0] > 0
        assert engine.model_times() == []


class TestInferenceTP:
    """Auto-TP over the model mesh axis (reference test_inference.py
    kernel-inject/auto-TP sweeps; replace_module.py weight slicing)."""

    def test_tp_generate_matches_single_device(self):
        cfg = _tiny()
        model = GPT2LMHeadModel(cfg)
        prompt = np.array([[3, 1, 4, 1, 5]], dtype=np.int32)

        e1 = deepspeed_tpu.init_inference(model, dtype="fp32", seed=7)
        out1 = e1.generate(prompt, max_new_tokens=4)
        reset_topology()
        e4 = deepspeed_tpu.init_inference(
            model, dtype="fp32", seed=7, params=e1.params,
            tensor_parallel={"tp_size": 4})
        assert e4.mp_world_size == 4
        # qkv and mlp weights actually sharded over the model axis
        flat = jax.tree_util.tree_leaves_with_path(e4.param_shardings)
        specs = {jax.tree_util.keystr(p): s.spec for p, s in flat}
        sharded = [k for k, s in specs.items() if any(e is not None for e in s)]
        assert any("c_attn" in k for k in sharded)
        assert any("c_fc" in k for k in sharded)
        out4 = e4.generate(prompt, max_new_tokens=4)
        np.testing.assert_array_equal(out1, out4)

    def test_mp_size_deprecated_alias(self):
        cfg = DeepSpeedInferenceConfig(mp_size=2)
        assert cfg.tensor_parallel.tp_size == 2

    def test_user_variables_dict_and_injection_dict(self):
        cfg = _tiny()
        model = GPT2LMHeadModel(cfg)
        variables = model.init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 4), jnp.int32))
        engine = deepspeed_tpu.init_inference(
            model, dtype="fp32", params=variables,
            injection_policy={"SelfAttention": ("attn.c_proj",)},
            tensor_parallel={"tp_size": 2})
        out = engine.generate(np.array([[1, 2, 3]], dtype=np.int32),
                              max_new_tokens=2)
        assert out.shape == (1, 5)

    def test_default_max_new_tokens_clamped_to_window(self):
        cfg = _tiny()  # n_positions=64 < max_out_tokens default 1024
        engine = deepspeed_tpu.init_inference(GPT2LMHeadModel(cfg), dtype="fp32")
        out = engine.generate(np.arange(60, dtype=np.int32)[None] % cfg.vocab_size)
        assert out.shape == (1, 64)


class TestInferenceQuant:
    def test_int8_weight_quant_generates_and_stays_close(self):
        cfg = _tiny()
        model = GPT2LMHeadModel(cfg)
        e_fp = deepspeed_tpu.init_inference(model, dtype="fp32", seed=3)
        e_q = deepspeed_tpu.init_inference(
            model, dtype="int8", seed=3, params=None,
            quant={"weight": {"num_bits": 8, "q_groups": 4}})
        assert e_q._quantized
        # int8 leaves present in the stored tree
        leaves = jax.tree_util.tree_leaves(e_q.params)
        assert any(l.dtype == jnp.int8 for l in leaves if hasattr(l, "dtype"))
        out = e_q.generate(np.array([[1, 2, 3]], dtype=np.int32),
                           max_new_tokens=3)
        assert out.shape == (1, 6)

    def test_fp16_conversion(self):
        cfg = _tiny()
        engine = deepspeed_tpu.init_inference(GPT2LMHeadModel(cfg), dtype="bf16")
        leaves = jax.tree_util.tree_leaves(engine.params)
        assert all(l.dtype == jnp.bfloat16 for l in leaves
                   if jnp.issubdtype(l.dtype, jnp.floating))


class TestCheckpointRoundTrip:
    @pytest.mark.parametrize("dtype", ["fp32", "bf16"])
    def test_checkpoint_kwarg_and_save_mp_fast_reload(self, tmp_path, dtype):
        # reference surface: init_inference(checkpoint=dir) loads at
        # construction; save_mp_checkpoint_path writes the CONVERTED
        # weights so the next engine reloads without re-conversion.
        # bf16 (the default dtype) pins the npz ml_dtypes round-trip
        cfg = _tiny()
        src = deepspeed_tpu.init_inference(
            GPT2LMHeadModel(cfg), dtype=dtype,
            save_mp_checkpoint_path=str(tmp_path / "mp"))
        ids = np.array([[5, 6, 7, 8]], dtype=np.int32)
        want = np.asarray(src(ids))

        again = deepspeed_tpu.init_inference(
            GPT2LMHeadModel(cfg), dtype=dtype,
            checkpoint=str(tmp_path / "mp"))
        np.testing.assert_array_equal(np.asarray(again(ids)), want)

        # a non-directory checkpoint value must FAIL LOUDLY, not serve
        # random weights
        from deepspeed_tpu.runtime.config import DeepSpeedConfigError

        with pytest.raises(DeepSpeedConfigError):
            deepspeed_tpu.init_inference(GPT2LMHeadModel(cfg), dtype="fp32",
                                         checkpoint="openai-community/gpt2")

    def test_zero_inference_checkpoint_kwarg(self, tmp_path):
        cfg = _tiny()
        src = deepspeed_tpu.init_inference(
            GPT2LMHeadModel(cfg), dtype="fp32",
            save_mp_checkpoint_path=str(tmp_path / "mp"))
        ids = np.array([[5, 6, 7, 8]], dtype=np.int32)
        want = np.asarray(src(ids))
        zeng = deepspeed_tpu.init_inference(
            GPT2LMHeadModel(cfg), dtype="fp32",
            checkpoint=str(tmp_path / "mp"),
            zero={"stage": 3, "offload_param": {"device": "cpu"}})
        np.testing.assert_allclose(np.asarray(zeng(ids)), want,
                                   rtol=2e-5, atol=2e-5)
        # the zero tier also WRITES the fast-reload cache, and base_dir
        # joins a relative checkpoint in both tiers
        zsave = deepspeed_tpu.init_inference(
            GPT2LMHeadModel(cfg), dtype="fp32",
            checkpoint=str(tmp_path / "mp"),
            save_mp_checkpoint_path=str(tmp_path / "zmp"),
            zero={"stage": 3, "offload_param": {"device": "cpu"}})
        del zsave
        back = deepspeed_tpu.init_inference(
            GPT2LMHeadModel(cfg), dtype="fp32",
            checkpoint="zmp", base_dir=str(tmp_path),
            zero={"stage": 3, "offload_param": {"device": "cpu"}})
        np.testing.assert_allclose(np.asarray(back(ids)),
                                   np.asarray(zeng(ids)), rtol=1e-6,
                                   atol=1e-6)

    def test_train_save_then_inference_load(self, tmp_path):
        cfg = _tiny()
        wrapper = GPT2ForTraining(cfg)
        ds = {"train_batch_size": 8, "train_micro_batch_size_per_gpu": 1,
              "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
              "bf16": {"enabled": False}}
        engine, *_ = deepspeed_tpu.initialize(model=wrapper, config=ds)
        batch = {"input_ids": np.ones((8, 16), np.int32)}
        engine.train_batch(batch=batch)
        engine.save_checkpoint(str(tmp_path))
        reset_topology()

        infer = deepspeed_tpu.init_inference(GPT2LMHeadModel(cfg), dtype="fp32")
        infer.load_checkpoint(str(tmp_path))
        trained = jax.device_get(engine.state.params)
        loaded = jax.device_get(infer.params)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6),
            trained, loaded)
        out = infer.generate(np.array([[1, 2, 3]], dtype=np.int32),
                             max_new_tokens=2)
        assert out.shape == (1, 5)
