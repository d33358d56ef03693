"""Wire-true compressed collectives: packed 1-bit + int8 gradient reduction.

Proof obligations (ISSUE 1):

- the packed 1-bit exchange's collective operand is **uint8** with >= 8x
  fewer payload bytes than a bf16 dense carrier — proven on compiled HLO,
  not on the Python that requested it;
- 1-bit Adam/LAMB trajectories with the packed wire match the dense-carrier
  trajectories **bit-for-bit** over >= 10 steps;
- int8 (EQuARX-style two-leg) and packed 1-bit reductions agree with the
  dense baseline across ZeRO stages 0-3 on the 8-device CPU mesh, including
  odd tensor sizes that exercise the bitfield/chunk padding.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.parallel.topology import MeshTopology, reset_topology
from deepspeed_tpu.runtime.comm.compressed import (compressed_allreduce,
                                                   pack_signs, unpack_signs)
from deepspeed_tpu.runtime.comm.quantized import int8_allreduce
from deepspeed_tpu.runtime.config import DeepSpeedConfigError
from deepspeed_tpu.runtime.zero.reduce import bucket_by_bytes
from deepspeed_tpu.utils.compat import shard_map
from deepspeed_tpu.utils.hlo_inspect import (collective_operand_dtypes,
                                             parse_collectives)


def _mesh(n=8):
    return Mesh(np.array(jax.devices()[:n]), ("data",))


# ----------------------------------------------------------------------
# bitfield packing
class TestPackedBitfield:
    @pytest.mark.parametrize("n", [1, 7, 8, 9, 37, 64, 129, 1000])
    def test_roundtrip_odd_sizes(self, n):
        v = np.random.default_rng(n).normal(size=(n,)).astype(np.float32)
        packed = pack_signs(jnp.asarray(v))
        assert packed.dtype == jnp.uint8
        assert packed.shape == (-(-n // 8),)  # lane-padded to byte multiple
        signs = np.asarray(unpack_signs(packed, n))
        np.testing.assert_array_equal(signs, np.where(v >= 0, 1.0, -1.0))

    def test_wire_is_32x_smaller_than_f32(self):
        v = jnp.ones((4096,), jnp.float32)
        assert pack_signs(v).nbytes * 32 == v.nbytes


# ----------------------------------------------------------------------
# collective-level parity (packed vs dense carrier, int8 vs exact mean)
class TestCollectiveParity:
    @pytest.mark.parametrize("n", [37, 64, 1023])
    def test_packed_bitexact_vs_dense(self, n):
        """Packed reconstruction accumulates workers left-to-right — the
        association psum uses — so avg AND error feedback are bit-equal."""
        mesh = _mesh()
        rng = np.random.default_rng(0)
        x = rng.normal(size=(8, n)).astype(np.float32) * 3
        e = rng.normal(size=(8, n)).astype(np.float32)

        def run(carrier):
            def f(v, err):
                avg, ne = compressed_allreduce(
                    v.reshape(n), err.reshape(n), "data", carrier=carrier)
                return avg, ne.reshape(1, n)

            return shard_map(f, mesh=mesh, in_specs=(P("data"), P("data")),
                             out_specs=(P(), P("data")), check_vma=False)(x, e)

        avg_p, err_p = run("packed")
        avg_d, err_d = run("dense")
        np.testing.assert_array_equal(np.asarray(avg_p), np.asarray(avg_d))
        np.testing.assert_array_equal(np.asarray(err_p), np.asarray(err_d))

    @pytest.mark.parametrize("n", [37, 1000, 8192])
    def test_int8_close_to_exact_mean(self, n):
        mesh = _mesh()
        x = np.random.default_rng(1).normal(size=(8, n)).astype(np.float32)

        def f(v):
            return int8_allreduce(v.reshape(n), "data", 8, group_size=256)

        out = np.asarray(shard_map(f, mesh=mesh, in_specs=P("data"),
                                   out_specs=P(), check_vma=False)(x))
        ref = x.mean(axis=0)
        assert np.abs(out - ref).max() <= 0.03 * np.abs(ref).max()

    def test_facade_ops(self):
        """deepspeed_tpu.comm surface: quantized_all_reduce /
        onebit_all_reduce inside shard_map resolve the world group."""
        import deepspeed_tpu.comm as dist
        from deepspeed_tpu.parallel import topology as topo_mod

        reset_topology()
        topo = MeshTopology(axis_sizes={"data": 8},
                            devices=jax.devices()[:8])
        topo_mod.set_topology(topo)
        assert dist.has_quantized_all_reduce()
        # the backend's advertised capability tuple must track the
        # canonical tier lists (it is user-facing parity surface; nothing
        # internal dispatches on it, so only this pin prevents drift)
        from deepspeed_tpu.runtime.comm.compressed import CARRIERS
        from deepspeed_tpu.runtime.comm.quantized import COMM_DTYPES

        assert set(dist.XlaBackend.comm_dtypes) == \
            {"dense"} | (set(COMM_DTYPES) - {"none"})
        assert set(CARRIERS) == {"packed", "dense"}
        assert dist.XlaBackend().supports_comm_dtype("int8")
        mesh = topo.mesh
        x = np.random.default_rng(2).normal(size=(8, 100)).astype(np.float32)

        def f(v):
            return dist.quantized_all_reduce(v.reshape(100), group="data",
                                             group_size=32)

        out = np.asarray(shard_map(f, mesh=mesh, in_specs=P("data"),
                                   out_specs=P(), check_vma=False)(x))
        assert np.abs(out - x.mean(axis=0)).max() <= 0.05
        reset_topology()


# ----------------------------------------------------------------------
# wire-true comms logging (ISSUE 2 satellite): the comms logger records
# the PACKED sizes (uint8 + scales), not the logical f32 size, so
# compressed and dense collectives are comparable in one log
class TestWireTrueCommsLog:
    def test_compressed_ops_log_wire_bytes(self):
        import deepspeed_tpu.comm as dist
        from deepspeed_tpu.parallel import topology as topo_mod
        from deepspeed_tpu.runtime.comm.compressed import onebit_wire_bytes
        from deepspeed_tpu.runtime.comm.quantized import int8_wire_bytes

        reset_topology()
        topo = MeshTopology(axis_sizes={"data": 8},
                            devices=jax.devices()[:8])
        topo_mod.set_topology(topo)
        logger = dist.comms_logger
        saved = (logger.enabled, logger.prof_all, dict(logger.comms_dict))
        logger.enabled, logger.prof_all = True, True
        logger.comms_dict.clear()
        n = 8192
        try:
            def f(v, e):
                avg = dist.quantized_all_reduce(v, group="data",
                                                comm_dtype="int8")
                ob, ne = dist.onebit_all_reduce(v, e, group="data")
                return avg, ob, ne

            sm = shard_map(f, mesh=topo.mesh, in_specs=(P(), P()),
                           out_specs=(P(), P(), P()), check_vma=False)
            jax.jit(sm).lower(jnp.ones((n,), jnp.float32),
                              jnp.zeros((n,), jnp.float32))
            d = dict(logger.comms_dict)
        finally:
            logger.enabled, logger.prof_all = saved[0], saved[1]
            logger.comms_dict.clear()
            logger.comms_dict.update(saved[2])
            reset_topology()
        q_sizes = list(d["quantized_all_reduce(traced)"])
        assert q_sizes == [int8_wire_bytes(n, 8, group_size=1024)]
        o_sizes = list(d["onebit_all_reduce(traced)"])
        assert o_sizes == [onebit_wire_bytes(n)]
        # wire-true means FAR below the logical f32 size
        assert q_sizes[0] < n * 4 / 3
        assert o_sizes[0] < n * 4 / 30

    def test_int8_wire_formula_matches_compiled_hlo(self):
        """The logged formula and the compiled program cannot disagree:
        sum of ALL collective operand bytes in the int8 allreduce HLO ==
        ``int8_wire_bytes``."""
        from deepspeed_tpu.runtime.comm.quantized import int8_wire_bytes

        n = 8192
        mesh = _mesh()

        def f(v):
            return int8_allreduce(v.reshape(n), "data", 8, group_size=1024)

        hlo = jax.jit(shard_map(f, mesh=mesh, in_specs=P("data"),
                                out_specs=P(), check_vma=False)).lower(
            jax.ShapeDtypeStruct((8, n), jnp.float32)).compile().as_text()
        total = sum(c["operand_bytes"] for c in parse_collectives(hlo))
        assert total == int8_wire_bytes(n, 8, group_size=1024)

    def test_onebit_wire_formula_matches_compiled_hlo(self):
        from deepspeed_tpu.runtime.comm.compressed import onebit_wire_bytes

        n = 8192
        mesh = _mesh()

        def f(v, e):
            avg, ne = compressed_allreduce(v.reshape(n), e.reshape(n),
                                           "data", carrier="packed")
            return avg, ne.reshape(1, n)

        hlo = jax.jit(shard_map(f, mesh=mesh,
                                in_specs=(P("data"), P("data")),
                                out_specs=(P(), P("data")),
                                check_vma=False)).lower(
            jax.ShapeDtypeStruct((8, n), jnp.float32),
            jax.ShapeDtypeStruct((8, n), jnp.float32)).compile().as_text()
        total = sum(c["operand_bytes"] for c in parse_collectives(hlo))
        assert total == onebit_wire_bytes(n)


# ----------------------------------------------------------------------
# bucketing
class TestBucketing:
    def test_bucket_by_bytes_reverse_walk(self):
        leaves = [np.zeros(s, np.float32) for s in (10, 20, 30, 1000)]
        buckets = bucket_by_bytes(leaves, 256)  # 64 f32 per bucket
        # reverse order: the big leaf (last flattened = first produced by
        # backward) leads, alone; the small ones pack together
        assert buckets[0] == [3]
        assert [i for b in buckets for i in b] == [3, 2, 1, 0]
        sizes = [sum(leaves[i].size * 4 for i in b) for b in buckets[1:]]
        assert all(s <= 256 for s in sizes)

    def test_each_bucket_is_an_independent_collective(self):
        """The overlap claim: K buckets -> K independent collectives in the
        compiled program, not one fused tail barrier."""
        from deepspeed_tpu.runtime.zero.reduce import reduce_gradients

        mesh = _mesh()
        grads = {f"l{i}": np.random.default_rng(i).normal(
            size=(8, 64)).astype(np.float32) for i in range(4)}

        def f(g):
            local = jax.tree_util.tree_map(lambda v: v.reshape(64), g)
            return reduce_gradients(local, "data", 8, comm_dtype="none",
                                    bucket_bytes=64 * 4)

        fn = jax.jit(shard_map(f, mesh=mesh, in_specs=P("data"),
                               out_specs=P(), check_vma=False))
        # The CPU backend's combiner merges collectives below its byte
        # threshold into one tuple all-reduce, and these 256-byte buckets
        # are far below it. What is pinned here is that the program hands
        # XLA K independent collectives which every OTHER pass keeps
        # apart, so that one pass is switched off for this compile.
        hlo = fn.lower(jax.tree_util.tree_map(
            lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype), grads)) \
            .compile(compiler_options={
                "xla_disable_hlo_passes": "cpu-all-reduce-combiner"}) \
            .as_text()
        n_ar = sum(1 for c in parse_collectives(hlo)
                   if c["op"] == "all-reduce")
        assert n_ar == 4, hlo


# ----------------------------------------------------------------------
# HLO wire proof (the ISSUE acceptance criterion)
class TestHloWireProof:
    N = 4096 + 3  # odd: exercises the bitfield padding in the lowered wire

    def _lowered(self, carrier):
        mesh = _mesh()
        n = self.N

        def f(v, err):
            avg, ne = compressed_allreduce(
                v.reshape(n), err.reshape(n), "data", carrier=carrier)
            return avg, ne.reshape(1, n)

        fn = jax.jit(shard_map(f, mesh=mesh, in_specs=(P("data"), P("data")),
                               out_specs=(P(), P("data")), check_vma=False))
        arg = jax.ShapeDtypeStruct((8, n), jnp.float32)
        return fn.lower(arg, arg).compile().as_text()

    def test_onebit_collective_operand_is_uint8_and_8x_smaller(self):
        hlo = self._lowered("packed")
        colls = [c for c in parse_collectives(hlo) if c["operand_bytes"] > 0]
        assert colls, "no collectives found in packed program"
        # every wire-significant operand is uint8; the f32 residue is the
        # per-tensor scale (4 bytes)
        payload = sum(b for c in colls for d, b in c["operands"] if d == "u8")
        scales = sum(b for c in colls for d, b in c["operands"] if d != "u8")
        assert payload == -(-self.N // 8), (payload, hlo)
        assert scales <= 8  # one f32 scale per member contribution
        # >= 8x vs a bf16 dense carrier (it is ~16x; vs f32, ~32x)
        bf16_dense = 2 * self.N
        assert bf16_dense / (payload + scales) >= 8
        # and the dense-carrier program really does ship full f32
        hlo_dense = self._lowered("dense")
        dense_bytes = sum(c["operand_bytes"]
                          for c in parse_collectives(hlo_dense))
        assert dense_bytes >= 4 * self.N
        assert "u8" not in collective_operand_dtypes(hlo_dense)

    def test_engine_int8_wire(self):
        """The engine's comm_quantization=int8 micro-step: both collective
        legs carry s8; no full-width f32 gradient all-reduce remains."""
        engine = _make_engine({"enabled": True, "dtype": "int8",
                               "group_size": 64, "bucket_bytes": 1 << 20})
        batch = _batch(np.random.default_rng(0))
        loss = engine(batch)
        engine.backward(loss)
        engine.step()
        hlo = engine._jit_micro.lower(
            engine.state, engine._shard_batch(batch)).compile().as_text()
        big = [c for c in parse_collectives(hlo) if c["operand_bytes"] >= 64]
        assert big, hlo
        assert any(c["op"] == "all-to-all" for c in big)  # scatter leg
        wire_dtypes = {d for c in big for d, b in c["operands"]}
        # s8 payload; f32 appears only for the chunk scales (allowed, tiny
        # relative to payload) — never a full-width f32 gradient reduce
        assert "s8" in wire_dtypes
        f32_bytes = sum(b for c in big for d, b in c["operands"] if d == "f32")
        s8_bytes = sum(b for c in big for d, b in c["operands"] if d == "s8")
        assert f32_bytes <= s8_bytes  # scales ride at 1/group_size density
        reset_topology()


# ----------------------------------------------------------------------
# engine-level parity across ZeRO stages
class _Net(nn.Module):
    dim: int = 16

    @nn.compact
    def __call__(self, x):
        h = nn.tanh(nn.Dense(4 * self.dim, name="fc1")(x))
        return nn.Dense(self.dim, name="fc2")(h)


class _Regression:
    def __init__(self):
        self.model = _Net()

    def init(self, rng, batch):
        return self.model.init(rng, batch[0])

    def loss_fn(self, params, batch, rngs=None):
        x, y = batch
        return jnp.mean((self.model.apply({"params": params}, x) - y) ** 2)


def _make_engine(cq=None, stage=0, opt=("Adam", {"lr": 1e-2}), dim=16):
    reset_topology()
    topo = MeshTopology(axis_sizes={"data": 4}, devices=jax.devices()[:4])
    config = {
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": opt[0], "params": opt[1]},
        "zero_optimization": {"stage": stage,
                              "param_persistence_threshold": 0},
        "steps_per_print": 10_000,
    }
    if cq is not None:
        config["comm_quantization"] = cq
    engine, *_ = deepspeed_tpu.initialize(model=_Regression(), mesh=topo,
                                          config=config)
    return engine


def _batch(rng, n=8, dim=16):
    x = rng.normal(size=(n, dim)).astype(np.float32)
    w = np.linspace(-1, 1, dim * dim).reshape(dim, dim).astype(np.float32)
    return x, np.tanh(x @ w)


def _train(engine, steps=8, seed=0):
    rng = np.random.default_rng(seed)
    losses = []
    for _ in range(steps):
        loss = engine(_batch(rng))
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    return losses


class TestEngineZeroStages:
    @pytest.mark.parametrize("stage", [0, 1, 2, 3])
    def test_int8_parity_vs_dense(self, stage):
        dense = _train(_make_engine(stage=stage))
        i8 = _train(_make_engine(
            cq={"enabled": True, "dtype": "int8", "group_size": 64,
                "bucket_bytes": 2048}, stage=stage))
        assert _make_engine(
            cq={"enabled": True, "dtype": "int8"},
            stage=stage).comm_quantization_enabled()
        # int8 is lossy but must track the dense trajectory closely
        np.testing.assert_allclose(i8, dense, rtol=0.05)
        reset_topology()

    @pytest.mark.parametrize("stage", [0, 1, 2, 3])
    def test_none_tier_bitexact_vs_gspmd(self, stage):
        """dtype='none' keeps full width: bucketing + explicit psum must
        reproduce the implicit GSPMD reduction (same association)."""
        dense = _train(_make_engine(stage=stage))
        bucketed = _train(_make_engine(
            cq={"enabled": True, "dtype": "none", "bucket_bytes": 2048},
            stage=stage))
        np.testing.assert_allclose(bucketed, dense, rtol=2e-4)
        reset_topology()


class TestEngineOnebitCarrier:
    @pytest.mark.parametrize("opt_type,opt_params", [
        ("OneBitAdam", {"lr": 1e-2, "freeze_step": 2}),
        ("OneBitLamb", {"lr": 5e-3, "freeze_step": 2}),
        ("ZeroOneAdam", {"lr": 1e-2, "var_sync_interval": 4}),
    ])
    def test_packed_wire_matches_dense_bitexact_12_steps(self, opt_type,
                                                         opt_params):
        """The acceptance criterion: >= 10 steps, packed vs dense carrier,
        identical losses AND identical final params, across the warmup ->
        compressed stage change (freeze_step=2)."""
        def run(carrier):
            engine = _make_engine(
                cq={"onebit_carrier": carrier}, opt=(opt_type, opt_params))
            losses = _train(engine, steps=12)
            return losses, jax.device_get(engine.state.params)

        losses_p, params_p = run("packed")
        losses_d, params_d = run("dense")
        assert losses_p == losses_d
        for a, b in zip(jax.tree_util.tree_leaves(params_p),
                        jax.tree_util.tree_leaves(params_d)):
            np.testing.assert_array_equal(a, b)
        reset_topology()

    def test_default_carrier_is_packed(self):
        engine = _make_engine(opt=("OneBitAdam", {"lr": 1e-2}))
        assert engine.optimizer.carrier == "packed"
        reset_topology()


class TestConfigGating:
    def test_1bit_requires_onebit_optimizer(self):
        with pytest.raises(DeepSpeedConfigError, match="1bit"):
            _make_engine(cq={"enabled": True, "dtype": "1bit"})
        reset_topology()

    def test_bad_dtype_rejected(self):
        with pytest.raises(Exception, match="comm_quantization.dtype"):
            _make_engine(cq={"enabled": True, "dtype": "fp4"})
        reset_topology()

    def test_facade_works_without_global_topology(self):
        """Regression: inside shard_map the group size resolves from the
        bound trace (psum constant-fold) even with NO global topology —
        previously a missing topology made the world size default to 1 and
        int8_allreduce silently skipped the reduction."""
        import deepspeed_tpu.comm as dist

        reset_topology()
        mesh = _mesh()
        x = np.random.default_rng(3).normal(size=(8, 64)).astype(np.float32)

        def f(v):
            return dist.quantized_all_reduce(v.reshape(64), group="data",
                                             group_size=32)

        out = np.asarray(shard_map(f, mesh=mesh, in_specs=P("data"),
                                   out_specs=P(), check_vma=False)(x))
        assert np.abs(out - x.mean(axis=0)).max() <= 0.05

    def test_model_parallel_falls_back(self):
        reset_topology()
        topo = MeshTopology(axis_sizes={"data": 2, "model": 2},
                            devices=jax.devices()[:4])
        engine, *_ = deepspeed_tpu.initialize(
            model=_Regression(), mesh=topo,
            config={"train_micro_batch_size_per_gpu": 2,
                    "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
                    "comm_quantization": {"enabled": True, "dtype": "int8"},
                    "steps_per_print": 10_000})
        assert not engine.comm_quantization_enabled()
        reset_topology()

    def test_gas_boundary_semantics_preserved(self):
        """comm_quantization with gradient accumulation: reduction happens
        inside each micro-step (same cadence as the GSPMD path), boundary
        apply consumes the accumulated sums — trajectories match dense."""
        def run(cq):
            reset_topology()
            topo = MeshTopology(axis_sizes={"data": 4},
                                devices=jax.devices()[:4])
            config = {"train_micro_batch_size_per_gpu": 2,
                      "gradient_accumulation_steps": 2,
                      "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
                      "steps_per_print": 10_000}
            if cq:
                config["comm_quantization"] = cq
            engine, *_ = deepspeed_tpu.initialize(model=_Regression(),
                                                  mesh=topo, config=config)
            rng = np.random.default_rng(0)
            losses = []
            for _ in range(4):
                for _ in range(2):
                    loss = engine(_batch(rng))
                    engine.backward(loss)
                    engine.step()
                losses.append(float(loss))
            return losses

        np.testing.assert_allclose(
            run({"enabled": True, "dtype": "none", "bucket_bytes": 4096}),
            run(None), rtol=2e-4)
        reset_topology()
