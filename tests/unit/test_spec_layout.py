"""SpecLayout / 3-axis mesh (data x fsdp x tp) tests.

The one-authority layout contract (runtime/zero/partition.SpecLayout):
parameter families -> tp-axis specs, ZeRO layering over data x fsdp x
expert, batch over data x expert ONLY; spec serialization round-trips;
tp-axis reshard-at-load is bit-identical per logical tensor; a default
1x1x1 mesh compiles byte-identical HLO to a no-mesh config; the
injected TP layers match their dense math and put int8 on the tp wire.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2ForTraining
from deepspeed_tpu.parallel.topology import (AXIS_FSDP, AXIS_TP,
                                             MeshTopology, reset_topology)
from deepspeed_tpu.runtime.zero.partition import (BATCH_AXES, ZERO_AXES,
                                                  SpecLayout,
                                                  batch_sharding,
                                                  sharding_spec_entries,
                                                  spec_entries)


@pytest.fixture(autouse=True)
def _fresh_topology():
    reset_topology()
    yield
    reset_topology()


def _mesh3(data=2, fsdp=2, tp=2):
    return MeshTopology(axis_sizes={"data": data, "fsdp": fsdp, "tp": tp},
                        devices=jax.devices()[:8]).mesh


class TestSpecLayout:
    def test_axis_roles(self):
        assert AXIS_FSDP in ZERO_AXES
        assert AXIS_TP not in ZERO_AXES
        assert AXIS_FSDP not in BATCH_AXES and AXIS_TP not in BATCH_AXES

    def test_family_specs_gpt2(self):
        lay = SpecLayout(_mesh3(), policy="gpt2")
        # column: QKV + MLP-in shard the output dim over tp
        assert lay.base_spec("transformer/h/block/attn/c_attn/kernel",
                             (2, 64, 192)) == P(None, None, "tp")
        assert lay.base_spec("transformer/h/block/mlp/c_fc/kernel",
                             (2, 64, 256)) == P(None, None, "tp")
        # row: proj/MLP-out shard the input dim; row bias replicates
        assert lay.base_spec("transformer/h/block/attn/c_proj/kernel",
                             (2, 64, 64)) == P(None, "tp", None)
        assert lay.base_spec("transformer/h/block/attn/c_proj/bias",
                             (2, 64)) is None
        # vocab: embedding shards its largest dim
        assert lay.base_spec("wte", (256, 64)) == P("tp", None)
        # norms replicate
        assert lay.base_spec("ln_f/scale", (64,)) is None

    def test_families_named(self):
        lay = SpecLayout(_mesh3(), policy="gpt2")
        assert lay.family_of("transformer/h/block/attn/c_attn/kernel") \
            == "attn_qkv"
        assert lay.family_of("transformer/h/block/attn/c_proj/kernel") \
            == "attn_proj"
        assert lay.family_of("transformer/h/block/mlp/c_fc/kernel") \
            == "mlp_in"
        assert lay.family_of("transformer/h/block/mlp/c_proj/kernel") \
            == "mlp_out"
        assert lay.family_of("wte") == "embedding"
        assert lay.family_of("transformer/h/block/ln_1/scale") == "norm"

    def test_zero_layers_on_fsdp(self):
        """ZeRO-1 opt state shards over the flattened data x fsdp axes,
        layered on the dims TP left alone."""
        lay = SpecLayout(_mesh3(), policy="gpt2")
        base = lay.base_spec("transformer/h/block/attn/c_attn/kernel",
                             (2, 64, 192))
        spec = lay.opt_spec((2, 64, 192), base_spec=base, stage=1)
        flat = [a for e in spec for a in
                (e if isinstance(e, tuple) else (e,)) if a]
        assert "tp" in flat
        assert "data" in flat and "fsdp" in flat

    def test_batch_never_fsdp_tp(self):
        """The satellite regression: batch axes derive from the layout —
        fsdp/tp can never shard the batch dim (they shard weights;
        landing on the batch would silently change the global batch)."""
        mesh = _mesh3()
        for ndim in (1, 2, 3):
            sh = batch_sharding(mesh, ndim=ndim, shape=(8, 32, 4)[:ndim])
            flat = [a for e in sh.spec for a in
                    (e if isinstance(e, tuple) else (e,)) if a]
            assert "fsdp" not in flat and "tp" not in flat, sh.spec
            assert "data" in flat  # the data axis DOES shard the batch
        with pytest.raises(ValueError):
            SpecLayout(mesh, batch_axes=("data", "tp"))

    def test_describe_is_json_safe(self):
        desc = SpecLayout(_mesh3(), policy="gpt2").describe()
        assert json.loads(json.dumps(desc)) == desc
        assert desc["tp_size"] == 2
        assert desc["families"]["attn_qkv"] == [None, "tp"]
        assert desc["families"]["norm"] == []


class TestSpecEntriesRoundTrip:
    def test_three_axis_specs(self):
        """spec_entries over 3-axis specs (incl. flattened-axis tuples)
        survive a JSON wire round-trip losslessly."""
        cases = [
            P(None, "tp"),
            P("tp", None),
            P(("data", "fsdp"), None, "tp"),
            P(None, ("data", "fsdp", "expert")),
            P(),
            None,
        ]
        for spec in cases:
            entries = spec_entries(spec)
            wire = json.loads(json.dumps(entries))
            assert wire == entries
            # entries reconstruct the same spec shape
            rebuilt = P(*[tuple(e) if isinstance(e, list) else e
                          for e in wire])
            assert spec_entries(rebuilt) == entries

    def test_sharding_spec_entries(self):
        mesh = _mesh3()
        sh = NamedSharding(mesh, P(("data", "fsdp"), None, "tp"))
        assert sharding_spec_entries(sh) == [["data", "fsdp"], None, "tp"]
        assert sharding_spec_entries(NamedSharding(mesh, P())) == []

    def test_manifest_round_trip_on_3axis_engine(self):
        """The live engine's topology manifest carries fsdp/tp specs and
        survives the JSON wire."""
        engine, *_ = deepspeed_tpu.initialize(
            model=GPT2ForTraining(GPT2Config.tiny(dtype=jnp.float32,
                                                  use_flash=False)),
            config={"train_batch_size": 8,
                    "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                    "mesh": {"data": 2, "fsdp": 2, "tp": 2},
                    "zero_optimization": {"stage": 1},
                    "steps_per_print": 10_000})
        ids = (np.arange(8 * 16).reshape(8, 16) % 23).astype(np.int32)
        engine({"input_ids": ids})
        manifest = engine.describe_topology()
        wire = json.loads(json.dumps(manifest))
        assert wire["mesh"]["axes"]["fsdp"] == 2
        assert wire["mesh"]["axes"]["tp"] == 2
        specs = [t["spec"] for t in wire["tensors"].values()]
        flat = [a for s in specs for e in s
                for a in (e if isinstance(e, list) else [e]) if a]
        assert "tp" in flat and ("fsdp" in flat or "data" in flat)
        engine.destroy()


class TestMeshKnob:
    def test_config_parses_3axis(self):
        from deepspeed_tpu.runtime.config import DeepSpeedConfig

        cfg = DeepSpeedConfig({"train_batch_size": 8,
                               "mesh": {"data": 2, "fsdp": 2, "tp": 2}},
                              world_size=2)
        assert cfg.mesh.fsdp == 2 and cfg.mesh.tp == 2

    def test_model_alias_folds_into_tp(self):
        from deepspeed_tpu.runtime.config import DeepSpeedConfig

        cfg = DeepSpeedConfig({"train_batch_size": 8,
                               "mesh": {"data": 2, "model": 4}},
                              world_size=2)
        assert cfg.mesh.tp == 4

    def test_model_tp_conflict_raises(self):
        from deepspeed_tpu.runtime.config import DeepSpeedConfig

        with pytest.raises(Exception):
            DeepSpeedConfig({"train_batch_size": 8,
                             "mesh": {"model": 2, "tp": 4}}, world_size=2)

    def test_device_count_validated(self):
        with pytest.raises(ValueError):
            MeshTopology(axis_sizes={"data": 3, "fsdp": 2, "tp": 2},
                         devices=jax.devices()[:8])


def _engine(zero_stage=1, mesh=None, micro=1):
    cfg = GPT2Config.tiny(dtype=jnp.float32, use_flash=False)
    ds = {"train_batch_size": 8,
          "train_micro_batch_size_per_gpu": micro,
          "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
          "zero_optimization": {"stage": zero_stage}}
    if mesh:
        ds["mesh"] = mesh
    engine, *_ = deepspeed_tpu.initialize(model=GPT2ForTraining(cfg),
                                          config=ds)
    return engine


BATCH = {"input_ids": (np.arange(8 * 16).reshape(8, 16) % 23).astype(
    np.int32)}


@pytest.mark.heavy
class TestTPReshard:
    """tp-axis reshard-at-load: a checkpoint saved at one tp degree
    restores at another BIT-identically per logical tensor (sharding is
    an annotation, not a data transform), on the ZeRO-1 and ZeRO-3
    legs; an impossible reshard raises the structured
    TopologyShiftError, never a jax shape error."""

    @pytest.mark.parametrize("save_mesh,load_mesh,stage", [
        ({"data": -1, "tp": 1}, {"data": -1, "tp": 2}, 1),
        ({"data": -1, "tp": 2}, {"data": -1, "tp": 1}, 1),
        ({"data": -1, "tp": 1}, {"data": -1, "tp": 2}, 3),
        ({"data": -1, "tp": 2}, {"data": -1, "tp": 1}, 3),
        ({"data": -1, "fsdp": 1, "tp": 1}, {"data": 2, "fsdp": 2, "tp": 2},
         1),
    ])
    def test_bit_identical_across_tp(self, tmp_path, save_mesh, load_mesh,
                                     stage):
        e1 = _engine(zero_stage=stage, mesh=save_mesh)
        e1.train_batch(batch=BATCH)
        e1.save_checkpoint(str(tmp_path))
        p1 = jax.device_get(e1.state.params)
        reset_topology()

        e2 = _engine(zero_stage=stage, mesh=load_mesh)
        e2.train_batch(batch=BATCH)  # build state under the new layout
        e2.load_checkpoint(str(tmp_path))
        p2 = jax.device_get(e2.state.params)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)), p1, p2)
        e2.train_batch(batch=BATCH)  # still trains under the new tp

    def test_impossible_reshard_is_structured(self, tmp_path):
        """A tensor-shape mismatch raises TopologyShiftError carrying
        the axis-by-axis diff — never a shape error from inside jax."""
        from deepspeed_tpu.runtime.resilience.topology import (
            TopologyShiftError, diff_topology, validate_reshard)

        e1 = _engine(zero_stage=1, mesh={"data": -1, "tp": 1})
        e1.train_batch(batch=BATCH)
        saved = e1.describe_topology()
        reset_topology()

        # a DIFFERENT model (wider embd) on a tp=2 mesh: logical shapes
        # no longer match — no reshard can bridge that
        cfg = GPT2Config.tiny(dtype=jnp.float32, use_flash=False,
                              n_embd=128)
        e2, *_ = deepspeed_tpu.initialize(
            model=GPT2ForTraining(cfg),
            config={"train_batch_size": 8,
                    "train_micro_batch_size_per_gpu": 1,
                    "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                    "mesh": {"data": -1, "tp": 2},
                    "zero_optimization": {"stage": 1}})
        e2.train_batch(batch=BATCH)
        current = e2.describe_topology()
        with pytest.raises(TopologyShiftError) as ei:
            validate_reshard(saved, current, where="test")
        assert ei.value.diff["fatal"], ei.value.diff
        # the benign mesh shift still renders axis-by-axis
        d = diff_topology(saved, current)
        assert d["changed"].get("mesh.axes.tp") == {"saved": 1,
                                                    "current": 2}

    def test_model_alias_manifest_diffs_clean(self):
        """A pre-3-axis manifest naming the 'model' axis equals the same
        partitioning under the 'tp' name — no phantom diff."""
        from deepspeed_tpu.runtime.resilience.topology import diff_topology

        saved = {"mesh": {"axes": {"pipe": 1, "data": 4, "expert": 1,
                                   "seq": 1, "model": 2},
                          "world_size": 8, "process_count": 1}}
        current = {"mesh": {"axes": {"pipe": 1, "data": 4, "fsdp": 1,
                                     "expert": 1, "seq": 1, "tp": 2},
                            "world_size": 8, "process_count": 1}}
        d = diff_topology(saved, current)
        assert not d["changed"] and not d["fatal"], d


class TestDefaultMeshHLOPin:
    """Zero-overhead pin: a default {data: -1, fsdp: 1, tp: 1} mesh
    section compiles byte-identical programs to NO mesh section."""

    def test_train_step_hlo(self):
        from tests.unit.simple_model import random_dataset
        from tests.unit.test_telemetry import _engine as _t_engine

        x, y = random_dataset(64, 8)
        batch = (x[:32], y[:32])

        def step_hlo(engine):
            raw = engine._jit_micro
            raw = getattr(raw, "_fn", raw)
            engine((batch[0], batch[1]))
            return raw.lower(engine.state,
                             engine._shard_batch(batch)).compile().as_text()

        reset_topology()
        plain_hlo = step_hlo(_t_engine())
        reset_topology()
        meshed_hlo = step_hlo(_t_engine(
            mesh={"data": -1, "fsdp": 1, "tp": 1}))
        assert plain_hlo == meshed_hlo

    def test_decode_hlo(self):
        from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel
        from deepspeed_tpu.serving import ServingEngine

        cfg = GPT2Config.tiny(dtype=jnp.float32)
        texts = []
        for tp_cfg in ({}, {"tensor_parallel": {"tp_size": 1}}):
            reset_topology()
            eng = deepspeed_tpu.init_inference(
                GPT2LMHeadModel(cfg), dtype="fp32", seed=0,
                serving={"block_size": 8, "decode_slots": 2}, **tp_cfg)
            srv = ServingEngine(eng)
            fn = srv._build_decode()
            lowered = fn.lower(
                eng.params, srv.cache,
                jnp.zeros((2, 1), jnp.int32),
                jnp.asarray(srv._tables), jnp.asarray(srv._lengths),
                srv._next_rng())
            texts.append(lowered.compile().as_text())
            srv.destroy()
        assert texts[0] == texts[1]


@pytest.mark.heavy
class TestTPServing:
    def test_generate_parity_tp2(self):
        from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel

        cfg = GPT2Config.tiny(dtype=jnp.float32)
        model = GPT2LMHeadModel(cfg)
        e1 = deepspeed_tpu.init_inference(model, dtype="fp32", seed=7)
        prompt = np.array([[11, 23, 42, 7]], np.int32)
        out1 = e1.generate(prompt, max_new_tokens=6)
        reset_topology()
        e2 = deepspeed_tpu.init_inference(
            model, dtype="fp32", params=e1.params,
            tensor_parallel={"tp_size": 2})
        assert e2.topo.axis_size("tp") == 2
        out2 = e2.generate(prompt, max_new_tokens=6)
        np.testing.assert_array_equal(out1, out2)
        e1.destroy()
        e2.destroy()

    def test_paged_serving_parity_tp2_and_pool_sharded(self):
        """Greedy paged-decode streams are identical at tp=1 and tp=2,
        AND the tp=2 engine's KV pools actually live head-sharded over
        the tp axis (a per-shard pool per device group)."""
        from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel
        from deepspeed_tpu.serving import ServingEngine

        cfg = GPT2Config.tiny(dtype=jnp.float32)
        model = GPT2LMHeadModel(cfg)
        scfg = {"enabled": True, "decode_slots": 2, "block_size": 8,
                "max_model_len": 64}
        outs = {}
        for tp in (1, 2):
            reset_topology()
            eng = deepspeed_tpu.init_inference(
                model, dtype="fp32", seed=7,
                tensor_parallel={"tp_size": tp}, serving=scfg)
            srv = ServingEngine(eng)
            if tp == 2:
                pools = [l for p, l in _flat_paths(srv.cache)
                         if p.endswith(("key_pool", "value_pool"))]
                assert pools
                for pool in pools:
                    flat = [a for e in pool.sharding.spec for a in
                            (e if isinstance(e, tuple) else (e,)) if a]
                    assert "tp" in flat, pool.sharding
            r = srv.submit([11, 23, 42, 7], max_new_tokens=8)
            srv.drain()
            outs[tp] = list(r.tokens)
            srv.destroy()
        assert outs[1] == outs[2]


def _flat_paths(tree):
    from deepspeed_tpu.utils.pytree import flatten_with_path_strings

    return flatten_with_path_strings(tree)[0]


class TestInjectedLayers:
    def test_mlp_matches_dense(self):
        from deepspeed_tpu.module_inject import injected_mlp

        mesh = _mesh3()
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(4, 64)), jnp.float32)
        w_in = jnp.asarray(rng.normal(size=(64, 256)), jnp.float32) * 0.02
        b_in = jnp.asarray(rng.normal(size=(256,)), jnp.float32) * 0.02
        w_out = jnp.asarray(rng.normal(size=(256, 64)),
                            jnp.float32) * 0.02
        b_out = jnp.asarray(rng.normal(size=(64,)), jnp.float32) * 0.02
        got = injected_mlp(x, w_in, b_in, w_out, b_out, mesh)
        ref = jax.nn.gelu(x @ w_in + b_in, approximate=True) @ w_out \
            + b_out
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_column_row_pair_matches_dense(self):
        from deepspeed_tpu.module_inject import (column_parallel_linear,
                                                 row_parallel_linear)

        mesh = _mesh3()
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.normal(size=(4, 64)), jnp.float32)
        w1 = jnp.asarray(rng.normal(size=(64, 128)), jnp.float32) * 0.05
        b1 = jnp.asarray(rng.normal(size=(128,)), jnp.float32) * 0.05
        w2 = jnp.asarray(rng.normal(size=(128, 64)), jnp.float32) * 0.05
        h = column_parallel_linear(x, w1, b1, mesh)
        y = row_parallel_linear(h, w2, None, mesh)
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray((x @ w1 + b1) @ w2),
                                   rtol=2e-5, atol=2e-5)

    def test_int8_tier_rides_the_tp_wire(self):
        """The comm_quantization int8 tier applied to the NEW tp
        collective: the compiled row-parallel program's collectives
        carry int8 operands (plus f32 scales), and no f32 all-reduce
        remains."""
        from deepspeed_tpu.module_inject import row_parallel_linear
        from deepspeed_tpu.utils.hlo_inspect import parse_collectives

        mesh = _mesh3()
        x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        w = jax.ShapeDtypeStruct((64, 64), jnp.float32)

        def tier(comm_dtype):
            hlo = jax.jit(lambda xs, ws: row_parallel_linear(
                xs, ws, None, mesh, comm_dtype=comm_dtype)) \
                .lower(x, w).compile().as_text()
            return [c for c in parse_collectives(hlo)
                    if c["operand_bytes"] >= 16]

        dense = tier("none")
        assert any(c["op"] == "all-reduce" for c in dense)
        quant = tier("int8")
        dtypes = {d for c in quant for d, _ in c["operands"]}
        assert "s8" in dtypes, dtypes
        assert not any(c["op"] == "all-reduce" for c in quant)
        # int8 tier ships fewer bytes than the dense f32 psum
        assert sum(c["operand_bytes"] for c in quant) \
            < sum(c["operand_bytes"] for c in dense)

    def test_bad_tier_raises(self):
        from deepspeed_tpu.module_inject.layers import tp_all_reduce
        from deepspeed_tpu.utils.compat import shard_map

        mesh = _mesh3()
        with pytest.raises(ValueError):
            shard_map(lambda x: tp_all_reduce(x, "tp", 2, "1bit"),
                      mesh=mesh, in_specs=P("tp"), out_specs=P("tp"),
                      check_vma=False)(jnp.zeros((8,)))


class TestTPKernels:
    @staticmethod
    def _stacked(kv, L=2, B=2, H=4, D=8, nb=4, bs=8, seed=0):
        """``(q, pools)`` in the pool's one shape ``[L, nb, bs, lanes]``:
        K/V rows of ``H*D`` lanes and, for int8, the scale rows (a lane a
        head, whole registers)."""
        from deepspeed_tpu.ops.decode_attention import scale_lanes
        from deepspeed_tpu.ops.quantizer import quantize_rowwise

        rng = np.random.default_rng(seed)
        q = jnp.asarray(rng.normal(size=(B, 1, H, D)), jnp.float32)
        kv5 = [jnp.asarray(rng.normal(size=(L, nb, bs, H, D)), jnp.float32)
               for _ in range(2)]
        if kv != "int8":
            return q, tuple(p.reshape(L, nb, bs, H * D) for p in kv5)
        quant = [quantize_rowwise(p) for p in kv5]
        pad = ((0, 0),) * 3 + ((0, scale_lanes(H) - H),)
        return q, (tuple(qp.reshape(L, nb, bs, H * D) for qp, _ in quant)
                   + tuple(jnp.pad(sc[..., 0], pad) for _, sc in quant))

    @pytest.mark.parametrize("layer", [0, 1])
    @pytest.mark.parametrize("kv", ["", "int8"])
    def test_paged_tp_matches_dense_oracle(self, kv, layer):
        """The TP-aware paged decode kernel (K/V lanes split into tp
        groups of contiguous heads, per-shard pools; the int8 scale rows
        whole, each shard finding its heads in them) equals the dense
        gather oracle on a tp=2 mesh (interpret mode on CPU)."""
        from deepspeed_tpu.ops import attention as attn_mod
        from deepspeed_tpu.ops import decode_attention as da
        from deepspeed_tpu.utils.compat import tpu_interpret_mode

        mesh = MeshTopology(axis_sizes={"tp": 2},
                            devices=jax.devices()[:2]).mesh
        q, pools = self._stacked(kv)
        H, bs = q.shape[2], pools[0].shape[2]
        tables = jnp.asarray([[1, 2], [3, 1]], jnp.int32)
        lengths = jnp.asarray([5, 9], jnp.int32)
        fn = (da.decode_attention_paged_int8_tp if kv == "int8"
              else da.decode_attention_paged_tp)
        with tpu_interpret_mode():
            got = fn(q, *pools, tables, lengths, layer, mesh=mesh)
        if kv == "int8":
            kd, vd = (da.gather_paged_cache_int8(p, sc, tables, layer, H)
                      for p, sc in zip(pools[:2], pools[2:]))
        else:
            kd, vd = (da.gather_paged_cache(p, tables, layer, H)
                      for p in pools)
        S = tables.shape[-1] * bs
        pos = jnp.arange(S)[None, :]
        mask = (pos <= lengths[:, None])[:, None, None, :]
        ref = attn_mod.attention_reference(
            q.transpose(0, 2, 1, 3), kd.transpose(0, 2, 1, 3),
            vd.transpose(0, 2, 1, 3), mask=mask, causal=False)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref.transpose(0, 2, 1, 3)),
            rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("kv", ["", "int8"])
    def test_tp_wrapper_falls_back_off_mesh(self, kv):
        """With no live tp axis the wrapper IS the plain kernel call —
        the zero-overhead contract at tp=1."""
        from deepspeed_tpu.ops import decode_attention as da
        from deepspeed_tpu.utils.compat import tpu_interpret_mode

        q, pools = self._stacked(kv, B=1, nb=3, seed=1)
        tables = jnp.asarray([[1, 2]], jnp.int32)
        lengths = jnp.asarray([4], jnp.int32)
        tp, plain = ((da.decode_attention_paged_int8_tp,
                      da.decode_attention_paged_int8) if kv == "int8" else
                     (da.decode_attention_paged_tp,
                      da.decode_attention_paged))
        with tpu_interpret_mode():
            a = tp(q, *pools, tables, lengths, 1)
            b = plain(q, *pools, tables, lengths, 1)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("heads,tp,sharded", [(4, 2, True),
                                                  (3, 2, False),
                                                  (4, 1, False)])
    @pytest.mark.parametrize("kv", ["", "int8"])
    def test_decode_cache_specs_shard_the_head_lanes(self, kv, heads, tp,
                                                     sharded):
        """K/V pools shard their LANE axis (``heads / tp`` contiguous heads
        a shard) when the heads divide over tp and replicate when they do
        not, or when the caller does not say how many heads a row holds;
        the int8 scale rows always replicate."""
        from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel
        from deepspeed_tpu.module_inject.policies import decode_cache_specs
        from deepspeed_tpu.ops.decode_attention import (POOL_LANE_AXIS,
                                                        scale_lanes)

        mesh = MeshTopology(axis_sizes={"tp": tp},
                            devices=jax.devices()[:tp]).mesh
        cfg = GPT2Config.tiny(n_head=heads, n_embd=heads * 16,
                              dtype=jnp.float32)
        module = GPT2LMHeadModel(cfg.for_paged_decode(5, 8, kv))
        pg = {"block_tables": jnp.zeros((1, 2), jnp.int32),
              "lengths": jnp.zeros((1,), jnp.int32),
              "num_valid": jnp.ones((1,), jnp.int32), "prefill": True}
        cache = jax.eval_shape(lambda: module.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32),
            paging=pg))["cache"]
        leaves = dict(_flat_paths(cache))
        assert {p.rsplit("/", 1)[-1]: l.shape for p, l in leaves.items()} \
            == {**{f"{n}_pool": (cfg.n_layer, 5, 8, heads * 16)
                   for n in ("key", "value")},
                **({f"{n}_scale": (cfg.n_layer, 5, 8, scale_lanes(heads))
                    for n in ("key", "value")} if kv else {})}
        specs = dict(_flat_paths(decode_cache_specs(cache, mesh,
                                                    heads=heads)))
        for path, sh in specs.items():
            want = [None] * 4
            if sharded and path.endswith("_pool"):
                want[POOL_LANE_AXIS] = "tp"
            assert tuple(sh.spec) + (None,) * (4 - len(sh.spec)) \
                == tuple(want), (path, sh.spec)
        for sh in jax.tree_util.tree_leaves(decode_cache_specs(cache, mesh)):
            assert not any(sh.spec), sh.spec


class TestTPStepCost:
    def test_tp_collectives_feed_step_cost(self):
        """On a dp=1 / tp=2 mesh the ONLY collectives in the compiled
        step are tp-axis ones — the step_cost accounting must see them."""
        topo = MeshTopology(axis_sizes={"data": 1, "tp": 2},
                            devices=jax.devices()[:2])
        engine, *_ = deepspeed_tpu.initialize(
            model=GPT2ForTraining(GPT2Config.tiny(dtype=jnp.float32)),
            mesh=topo,
            config={"train_batch_size": 4,
                    "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                    "zero_optimization": {"stage": 0},
                    "telemetry": {"enabled": True, "jsonl": False,
                                  "memory": False, "hlo_cost": True,
                                  "tracing": {"enabled": True}},
                    "steps_per_print": 10_000})
        ids = np.zeros((4, 16), np.int32)
        for _ in range(2):
            loss = engine({"input_ids": ids})
            engine.backward(loss)
            engine.step()
        evs = engine.telemetry.tail(200)
        wire = max((e["data"].get("collective_operand_bytes") or 0
                    for e in evs if e["kind"] == "step_cost"), default=0)
        assert wire > 0, "tp collectives missing from step_cost"
        engine.destroy()


class TestLegacyModelAxisMesh:
    def test_raw_model_mesh_still_shards_tp(self):
        """A user-built mesh carrying the legacy 'model' axis name keeps
        real TP: SpecLayout resolves the axis through the alias, so specs
        name the axis the mesh actually has (silent replication would be
        an OOM on models that only fit sharded)."""
        from jax.sharding import Mesh

        mesh = Mesh(np.array(jax.devices()[:8]).reshape(4, 2),
                    ("data", "model"))
        lay = SpecLayout(mesh, policy="gpt2")
        assert lay.tp_axis == "model" and lay.tp_size == 2
        assert lay.base_spec("transformer/h/block/attn/c_attn/kernel",
                             (2, 64, 192)) == P(None, None, "model")
        from deepspeed_tpu.module_inject.policies import decode_cache_specs

        cache = {"h": {"attn": {"cached_key": jax.ShapeDtypeStruct(
            (2, 64, 4, 16), jnp.float32)}}}
        sh = decode_cache_specs(cache, mesh)
        spec = sh["h"]["attn"]["cached_key"].spec
        assert "model" in jax.tree_util.tree_leaves(list(spec)), spec

    def test_aot_identity_survives_axis_rename(self):
        """A bundle fingerprint stamped under the pre-3-axis axis names
        verifies clean against the renamed identity (same physical
        partitioning)."""
        from deepspeed_tpu.aot.bundle import (AOT_BUNDLE_VERSION,
                                              verify_manifest)
        from deepspeed_tpu.utils.fingerprint import (fingerprint_hash,
                                                     topology_fingerprint)

        old_fp = topology_fingerprint(mesh_axes={
            "pipe": 1, "data": 4, "expert": 1, "seq": 1, "model": 2})
        manifest = {"version": AOT_BUNDLE_VERSION,
                    "fingerprint": old_fp,
                    "fingerprint_hash": fingerprint_hash(old_fp),
                    "tuned_hash": "none"}
        new_fp = topology_fingerprint(mesh_axes={"data": 4, "tp": 2})
        current = {"fingerprint": new_fp,
                   "fingerprint_hash": fingerprint_hash(new_fp),
                   "tuned_hash": "none"}
        assert verify_manifest(manifest, current) == []
        # a REAL shape change still mismatches loudly
        other = topology_fingerprint(mesh_axes={"data": 2, "tp": 4})
        cur2 = {"fingerprint": other,
                "fingerprint_hash": fingerprint_hash(other),
                "tuned_hash": "none"}
        assert verify_manifest(manifest, cur2)
