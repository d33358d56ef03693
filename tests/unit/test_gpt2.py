"""GPT-2 model tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import (
    GPT2Config,
    GPT2ForTraining,
    GPT2LMHeadModel,
    cross_entropy_loss,
    gpt2_loss_fn,
)
from deepspeed_tpu.parallel.topology import reset_topology
from deepspeed_tpu.utils.compat import tpu_interpret_mode


@pytest.fixture(autouse=True)
def _fresh_topology():
    reset_topology()
    yield
    reset_topology()


class TestModel:
    def test_shapes(self):
        cfg = GPT2Config.tiny(dtype=jnp.float32)
        m = GPT2LMHeadModel(cfg)
        ids = jnp.ones((2, 16), jnp.int32)
        params = jax.jit(m.init)(jax.random.PRNGKey(0), ids)["params"]
        logits = jax.jit(m.apply)({"params": params}, ids)
        assert logits.shape == (2, 16, cfg.vocab_size)
        assert logits.dtype == jnp.float32

    def test_scan_and_loop_same_shapes(self):
        ids = jnp.ones((2, 16), jnp.int32)
        for scan in (True, False):
            cfg = GPT2Config.tiny(dtype=jnp.float32, scan_layers=scan)
            m = GPT2LMHeadModel(cfg)
            params = jax.jit(m.init)(jax.random.PRNGKey(0), ids)["params"]
            assert jax.jit(m.apply)({"params": params}, ids).shape == (2, 16, 256)

    @pytest.mark.parametrize("scan", [True, False])
    def test_train_forward_has_no_kv_pool(self, scan):
        """The block stack owns the serving KV pool, but only a
        paged-decode model has one: the train forward (no ``paging``,
        ``decode`` off) creates no ``cache`` variable at init or at apply,
        and its scan carries ``x`` alone — in the traced program the layer
        loop has no operand beyond the seed's (the lowered train step of
        the benchmark's medium cell is text-identical across PR 27)."""
        cfg = GPT2Config.tiny(dtype=jnp.float32, scan_layers=scan,
                              remat=True, remat_policy="dots")
        m = GPT2LMHeadModel(cfg)
        ids = jnp.ones((2, 16), jnp.int32)
        variables = jax.jit(m.init)(jax.random.PRNGKey(0), ids)
        assert set(variables) == {"params"}
        logits, mutated = m.apply({"params": variables["params"]}, ids,
                                  mutable=["cache"])
        assert logits.shape == (2, 16, 256) and not mutated.get("cache")
        if scan:
            jaxpr = jax.make_jaxpr(lambda p: m.apply({"params": p}, ids))(
                variables["params"])
            scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
            # carry: x; scanned in: the stacked params and the layer
            # fractions; no pool, no layer index
            stacked = len(jax.tree_util.tree_leaves(
                variables["params"]["transformer"]))
            assert len(scans) == 1 and scans[0].params["num_carry"] == 1
            assert len(scans[0].invars) - scans[0].params["num_consts"] \
                == 1 + stacked + 1

    def test_causality(self):
        """Changing a future token must not change past logits."""
        cfg = GPT2Config.tiny(dtype=jnp.float32)
        m = GPT2LMHeadModel(cfg)
        rng = np.random.default_rng(0)
        ids = jnp.asarray(rng.integers(0, 256, (1, 16)), jnp.int32)
        params = jax.jit(m.init)(jax.random.PRNGKey(0), ids)["params"]
        base = jax.jit(m.apply)({"params": params}, ids)
        ids2 = ids.at[0, 10].set((ids[0, 10] + 1) % 256)
        pert = jax.jit(m.apply)({"params": params}, ids2)
        np.testing.assert_allclose(base[0, :10], pert[0, :10], atol=1e-5)
        assert not np.allclose(base[0, 10:], pert[0, 10:], atol=1e-5)

    def test_cross_entropy_masking(self):
        logits = jnp.zeros((1, 4, 8))
        labels = jnp.asarray([[1, 2, -100, -100]])
        loss = cross_entropy_loss(logits, labels)
        np.testing.assert_allclose(loss, np.log(8), rtol=1e-5)

    def test_remat_variant_matches(self):
        ids = jnp.ones((2, 16), jnp.int32)
        cfg = GPT2Config.tiny(dtype=jnp.float32, remat=False)
        cfg_r = GPT2Config.tiny(dtype=jnp.float32, remat=True)
        m, mr = GPT2LMHeadModel(cfg), GPT2LMHeadModel(cfg_r)
        params = jax.jit(m.init)(jax.random.PRNGKey(0), ids)["params"]
        np.testing.assert_allclose(
            jax.jit(m.apply)({"params": params}, ids),
            jax.jit(mr.apply)({"params": params}, ids), atol=1e-5)


class TestEndToEnd:
    def test_trains_on_pattern(self):
        """Memorize a repeating pattern — loss must drop sharply."""
        cfg = GPT2Config.tiny(dtype=jnp.float32)
        model = GPT2ForTraining(cfg)
        pattern = np.tile(np.arange(8, dtype=np.int32), (32, 4))  # seq 32
        engine, *_ = deepspeed_tpu.initialize(
            model=model,
            config={"train_batch_size": 32,
                    "optimizer": {"type": "AdamW", "params": {"lr": 3e-3}},
                    "gradient_clipping": 1.0,
                    "zero_optimization": {"stage": 2},
                    "steps_per_print": 10_000})
        losses = []
        for _ in range(40):
            loss = engine({"input_ids": pattern})
            engine.backward(loss)
            engine.step()
            losses.append(float(loss))
        assert losses[-1] < 0.5, f"did not memorize pattern: {losses[-5:]}"
        assert losses[-1] < losses[0] / 4


class TestChunkedXent:
    @pytest.mark.parametrize("T", [64, 100, 127])  # incl. prime T
    def test_matches_full_logits(self, T):
        from deepspeed_tpu.models.gpt2 import chunked_softmax_xent

        rng = np.random.default_rng(0)
        B, C, V = 2, 16, 50
        hidden = jnp.asarray(rng.normal(size=(B, T, C)), jnp.float32)
        wte = jnp.asarray(rng.normal(size=(V, C)), jnp.float32)
        labels = jnp.asarray(rng.integers(0, V, (B, T)), jnp.int32)
        labels = labels.at[0, :5].set(-100)  # masked tokens
        full_logits = jnp.einsum("btc,vc->btv", hidden, wte)
        expect = cross_entropy_loss(full_logits, labels)
        got = chunked_softmax_xent(hidden, wte, labels, chunk=32)
        np.testing.assert_allclose(float(got), float(expect), rtol=1e-5)

    def test_padding_not_sequential(self):
        """Odd T must pad up to the chunk size, not degrade to chunk=1."""
        from deepspeed_tpu.models.gpt2 import chunked_softmax_xent

        hidden = jnp.ones((1, 127, 8), jnp.float32)
        wte = jnp.ones((16, 8), jnp.float32)
        labels = jnp.zeros((1, 127), jnp.int32)
        jaxpr = jax.make_jaxpr(
            lambda h, w, l: chunked_softmax_xent(h, w, l, chunk=64))(
                hidden, wte, labels)
        scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
        assert scans and scans[0].params["length"] == 2  # ceil(127/64)


@pytest.mark.heavy
class TestBthdAttentionLayout:
    """attn_layout="bthd": transpose-free strided flash path
    (ops/flash_attention.py flash_attention_bthd; PERF.md layout-copy
    headroom). Must be numerically identical to the default layout."""

    def test_logits_and_grads_match_default_layout(self):
        from deepspeed_tpu.utils.compat import tpu_interpret_mode

        ids = np.random.default_rng(0).integers(
            0, 512, (2, 256)).astype(np.int32)
        outs = {}
        for layout in ("bhtd", "bthd"):
            cfg = GPT2Config(vocab_size=512, n_positions=256, n_embd=128,
                             n_layer=2, n_head=4, dtype=jnp.float32,
                             scan_layers=True, use_flash=True,
                             attn_layout=layout)
            model = GPT2ForTraining(cfg)
            with tpu_interpret_mode():
                params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                             {"input_ids": ids})["params"]
                loss, grads = jax.block_until_ready(jax.jit(
                    jax.value_and_grad(lambda p: model.loss_fn(
                        p, {"input_ids": ids})))(params))
            outs[layout] = (float(loss), grads)
        assert outs["bhtd"][0] == pytest.approx(outs["bthd"][0], rel=1e-5)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-5),
            outs["bhtd"][1], outs["bthd"][1])

    def test_bthd_falls_back_when_masked(self):
        # attention_mask forces the standard path; must still run + match
        ids = np.random.default_rng(1).integers(
            0, 512, (2, 64)).astype(np.int32)
        mask = np.ones((2, 64), np.int32)
        mask[0, :10] = 0
        cfg = GPT2Config(vocab_size=512, n_positions=64, n_embd=64,
                         n_layer=2, n_head=4, dtype=jnp.float32,
                         attn_layout="bthd")
        model = GPT2LMHeadModel(cfg)
        with tpu_interpret_mode():
            params = jax.jit(model.init)(jax.random.PRNGKey(0), ids)["params"]
            logits = model.apply({"params": params}, ids,
                                 attention_mask=jnp.asarray(mask))
        assert np.isfinite(np.asarray(logits)).all()


class TestBthdTileSelection:
    """Pure tile-selection logic for the strided kernel (no Pallas run)."""

    def test_non_power_of_two_seq_reaches_128(self):
        # seq 384: the halving chain 384 -> 192 -> 96 skips 128; the
        # divisor walk must still reach the 128-tile floor when larger
        # tiles exhaust the head-group VMEM budget
        from deepspeed_tpu.ops.flash_attention import _tile_divisors

        assert _tile_divisors(384, 512) == [384, 192, 128]
        assert _tile_divisors(1024, 512) == [512, 256, 128]
        assert _tile_divisors(64, 512) == []  # below floor -> caller keeps bq0
        # an explicit sub-128 block size is its own floor (callers who
        # pass block_q=64 must keep getting 64-wide tiles, not full-seq)
        assert _tile_divisors(1024, 64) == [64]

    def test_tiles_deterministic_and_legal(self):
        from deepspeed_tpu.ops.flash_attention import _bthd_tiles

        # 768 is the shape the old _block_sizes gate rejected outright
        # (768 % 512 != 0) despite legal 384/256/192/128 divisor tiles
        for sq, h, d in ((384, 12, 64), (768, 12, 64), (1024, 12, 64),
                         (256, 4, 128), (512, 16, 64)):
            bq, bk, g = _bthd_tiles(sq, sq, h, d, 512, 512)
            assert sq % bq == 0 and sq % bk == 0
            assert g % 8 == 0 or g == h
            assert h % g == 0
            # static args -> same answer every call (fwd/bwd agreement)
            assert (bq, bk, g) == _bthd_tiles(sq, sq, h, d, 512, 512)
