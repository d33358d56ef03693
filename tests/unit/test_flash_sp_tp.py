"""SP x TP flash attention composition (DeepSpeed-Ulysses, arXiv:2309.14509).

``flash_attention_bthd_tp`` shard_maps over heads (tp) AND sequence
(seq): the sp legs bracket the kernel with two seq-axis all_to_alls
(heads traded for the full sequence and back), tp stays collective-free.
Proofs: parity vs the dense attention oracle in interpret mode (forward
and grads, through BOTH mesh axes), zero-overhead fallbacks (sp=1
emits the exact tp-only program; a one-device mesh the plain kernel —
pinned byte-identical on lowered HLO; any larger mesh wraps the kernel in
a shard_map, which the chip's compiler requires), and the divisibility
degrade (a head group sp cannot split falls back to tp-only with no
all-to-all).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention import attention_reference
from deepspeed_tpu.ops.flash_attention import (flash_attention_bthd,
                                               flash_attention_bthd_tp)
from deepspeed_tpu.parallel.topology import MeshTopology, reset_topology
from deepspeed_tpu.utils.compat import tpu_interpret_mode


@pytest.fixture(autouse=True)
def _fresh_topology():
    reset_topology()
    yield
    reset_topology()


def _mesh(data=2, seq=2, tp=2):
    return MeshTopology(axis_sizes={"data": data, "seq": seq, "tp": tp},
                        devices=jax.devices()[:data * seq * tp]).mesh


def _qkv_bthd(B=2, T=256, H=4, D=64, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
                 for _ in range(3))


def _oracle(q, k, v, causal=True):
    """Dense reference over the same [B, T, H, D] layout."""
    bhtd = [t.transpose(0, 2, 1, 3) for t in (q, k, v)]
    return attention_reference(*bhtd, causal=causal).transpose(0, 2, 1, 3)


class TestSpTpParity:
    @pytest.mark.parametrize("causal", [True, False])
    def test_fwd_matches_dense_oracle(self, causal):
        mesh = _mesh()
        q, k, v = _qkv_bthd()
        with tpu_interpret_mode():
            o = jax.jit(lambda *t: flash_attention_bthd_tp(
                *t, causal=causal, block_q=128, block_k=128,
                mesh=mesh))(q, k, v)
        np.testing.assert_allclose(np.asarray(o),
                                   np.asarray(_oracle(q, k, v, causal)),
                                   rtol=2e-3, atol=2e-3)

    def test_grads_match_dense_oracle(self):
        mesh = _mesh()
        q, k, v = _qkv_bthd(T=128)

        def loss_sp(q, k, v):
            return jnp.sum(flash_attention_bthd_tp(
                q, k, v, causal=True, block_q=64, block_k=64,
                mesh=mesh) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(_oracle(q, k, v, causal=True) ** 2)

        with tpu_interpret_mode():
            gf = jax.jit(jax.grad(loss_sp, argnums=(0, 1, 2)))(q, k, v)
            # The interpreter's io_callbacks run JAX ops of their own on
            # the default device. Work dispatched there while the
            # interpreted program is still in flight (the oracle's eager
            # grad below) queues ahead of them and the two wait on each
            # other forever, so finish the interpreted program first.
            jax.block_until_ready(gf)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            scale = float(jnp.max(jnp.abs(b))) + 1e-9
            np.testing.assert_allclose(np.asarray(a) / scale,
                                       np.asarray(b) / scale,
                                       rtol=0, atol=5e-3)

    def test_sp_only_mesh(self):
        """tp=1 with a live seq axis: pure Ulysses, still the oracle."""
        mesh = _mesh(data=2, seq=4, tp=1)
        q, k, v = _qkv_bthd(H=4)
        with tpu_interpret_mode():
            o = jax.jit(lambda *t: flash_attention_bthd_tp(
                *t, causal=True, block_q=64, block_k=64,
                mesh=mesh))(q, k, v)
        np.testing.assert_allclose(np.asarray(o),
                                   np.asarray(_oracle(q, k, v)),
                                   rtol=2e-3, atol=2e-3)


class TestZeroOverheadFallbacks:
    def _lowered(self, mesh, q, k, v, **kw):
        with tpu_interpret_mode():
            return jax.jit(lambda *t: flash_attention_bthd_tp(
                *t, causal=True, block_q=128, block_k=128, mesh=mesh,
                **kw)).lower(q, k, v).as_text()

    def test_sp1_is_byte_identical_to_tp_only(self):
        """A seq axis of size 1 must not change the emitted program at
        all — same lowered HLO as a mesh that never had sp."""
        q, k, v = _qkv_bthd()
        a = self._lowered(_mesh(data=4, seq=1, tp=2), q, k, v)
        reset_topology()
        b = self._lowered(_mesh(data=4, seq=1, tp=2), q, k, v)
        assert a == b  # determinism of the comparison itself
        assert "all-to-all" not in a and "all_to_all" not in a

    def test_one_device_mesh_is_the_plain_kernel(self):
        mesh = _mesh(data=1, seq=1, tp=1)
        q, k, v = _qkv_bthd()
        with tpu_interpret_mode():
            via_tp = jax.jit(lambda *t: flash_attention_bthd_tp(
                *t, causal=True, block_q=128, block_k=128,
                mesh=mesh)).lower(q, k, v).as_text()
            plain = jax.jit(lambda *t: flash_attention_bthd(
                *t, causal=True, block_q=128,
                block_k=128)).lower(q, k, v).as_text()
        assert via_tp == plain

    def test_data_only_mesh_still_wraps_the_kernel(self):
        """tp=1, sp=1 over a data axis: no axis splits the heads or the
        tokens, but a Mosaic kernel cannot be partitioned by GSPMD (the
        chip's compiler refuses it), so the call must sit in a shard_map
        with the batch over the data axis — and still be the oracle.
        (data=4: eight interpreted shards of this size, one thread each,
        starve the interpreter's callbacks of the CPU client's threads.)"""
        mesh = _mesh(data=4, seq=1, tp=1)
        q, k, v = _qkv_bthd(B=4)
        with tpu_interpret_mode():
            fn = jax.jit(lambda *t: flash_attention_bthd_tp(
                *t, causal=True, block_q=128, block_k=128, mesh=mesh))
            o = np.asarray(fn(q, k, v))
            assert "shard_map" in str(jax.make_jaxpr(fn)(q, k, v))
        np.testing.assert_allclose(o, np.asarray(_oracle(q, k, v)),
                                   rtol=2e-3, atol=2e-3)

    def test_indivisible_head_group_degrades_to_tp_only(self):
        """H/tp = 1 head cannot split over sp=2: the sp legs must drop
        out (no all_to_all), leaving the tp-only program."""
        mesh = _mesh(data=2, seq=2, tp=2)
        q, k, v = _qkv_bthd(H=2)  # 2 heads / tp=2 -> 1 local head
        hlo = self._lowered(mesh, q, k, v)
        assert "all-to-all" not in hlo and "all_to_all" not in hlo
        with tpu_interpret_mode():
            o = jax.jit(lambda *t: flash_attention_bthd_tp(
                *t, causal=True, block_q=128, block_k=128,
                mesh=mesh))(q, k, v)
        np.testing.assert_allclose(np.asarray(o),
                                   np.asarray(_oracle(q, k, v)),
                                   rtol=2e-3, atol=2e-3)

    def test_sp_active_emits_all_to_all(self):
        """The positive control for the two pins above."""
        hlo = self._lowered(_mesh(), *_qkv_bthd())
        assert "all-to-all" in hlo or "all_to_all" in hlo


def _topology(**axis_sizes):
    from deepspeed_tpu.parallel.topology import set_topology

    n = int(np.prod(list(axis_sizes.values())))
    topo = MeshTopology(axis_sizes=axis_sizes, devices=jax.devices()[:n])
    set_topology(topo)
    return topo


class TestKernelMeshPlan:
    """``ops/kernel_mesh.kernel_mesh_plan``: the one decision where a
    Mosaic kernel call sits. Every flash and decode wrapper asks it."""

    def _plan(self, **kw):
        from deepspeed_tpu.ops.kernel_mesh import kernel_mesh_plan

        return kernel_mesh_plan(8, 4, **kw)

    def test_no_mesh_and_one_device_mesh_are_the_plain_kernel(self):
        assert self._plan() is None
        assert self._plan(mesh=_mesh(data=1, seq=1, tp=1)) is None

    def test_whole_mesh_from_gspmd_code(self):
        mesh = _mesh(data=2, seq=2, tp=2)
        plan = self._plan(mesh=mesh, seqlen=256)
        assert plan.mesh is mesh and plan.axis_names is None
        assert (plan.batch, plan.heads, plan.seq) == ("data", "tp", "seq")
        assert plan.size(plan.heads) * plan.size(plan.seq) == 4
        # sp is asked for by passing the token count, and needs the
        # post-tp head group (4/2) and the tokens to divide
        assert self._plan(mesh=mesh).seq is None
        assert self._plan(mesh=mesh, seqlen=255).seq is None

    def test_global_topology_is_the_default_mesh(self):
        topo = _topology(data=4, tp=2)
        plan = self._plan()
        assert plan.mesh is topo.mesh
        assert (plan.batch, plan.heads) == ("data", "tp")

    def test_indivisible_dims_stay_whole(self):
        from deepspeed_tpu.ops.kernel_mesh import kernel_mesh_plan

        plan = kernel_mesh_plan(3, 3, mesh=_mesh(data=2, seq=1, tp=2))
        assert plan is not None  # still a shard_map: GSPMD cannot have it
        assert (plan.batch, plan.heads) == (None, None)

    def test_inside_a_fully_manual_shard_map_is_the_plain_kernel(self):
        """The Ulysses and ring bodies: every axis is manual already, the
        operands are the local shards, and a second shard_map over the
        same mesh is an error at trace."""
        from jax.sharding import PartitionSpec as P

        from deepspeed_tpu.utils.compat import shard_map

        mesh = _mesh(data=2, seq=2, tp=2)
        seen = []

        def body(x):
            seen.append(self._plan(mesh=mesh, seqlen=256))
            return x

        jax.make_jaxpr(shard_map(body, mesh=mesh, in_specs=P("data"),
                                 out_specs=P("data")))(jnp.zeros((8,)))
        assert seen == [None]

    def test_inside_a_partly_manual_shard_map_takes_the_auto_axes(self):
        """The pipeline engine's shard_map is manual over ``pipe`` only.
        The chip's compiler wants EVERY axis manual around a Mosaic
        kernel, so the plan nests over all the axes left Auto, on the
        context's mesh, and never names the manual one in a spec."""
        from jax.sharding import PartitionSpec as P

        from deepspeed_tpu.utils.compat import shard_map

        mesh = _topology(pipe=2, data=2, tp=2).mesh
        seen = []

        def body(x):
            seen.append(self._plan())
            return x

        jax.make_jaxpr(shard_map(body, mesh=mesh, in_specs=P("pipe"),
                                 out_specs=P("pipe"), axis_names={"pipe"},
                                 check_vma=False))(jnp.zeros((8,)))
        (plan,) = seen
        assert plan.axis_names == frozenset(mesh.axis_names) - {"pipe"}
        assert plan.mesh.manual_axes == ("pipe",)
        assert (plan.batch, plan.heads) == ("data", "tp")


class TestInsideAnEnclosingShardMap:
    def test_ulysses_with_the_flash_kernel_matches_dense_oracle(self):
        """``ulysses_attention(use_flash=True)``, the TPU default: the
        dispatcher is re-entered inside the Ulysses shard_map and must
        call the plain kernel there."""
        from deepspeed_tpu.ops.ulysses_attention import ulysses_attention

        topo = _topology(data=2, seq=2)
        q, k, v = (t.transpose(0, 2, 1, 3) for t in _qkv_bthd())
        with tpu_interpret_mode():
            o = jax.jit(lambda *t: ulysses_attention(
                *t, mesh=topo.mesh, use_flash=True))(q, k, v)
            jax.block_until_ready(o)
        np.testing.assert_allclose(
            np.asarray(o), np.asarray(attention_reference(q, k, v)),
            rtol=2e-3, atol=2e-3)

    @pytest.mark.parametrize("layout", ["bhtd", "bthd"])
    def test_flash_inside_the_pipe_manual_shard_map_traces(self, layout):
        """Both dispatch paths under the pipeline engine's shard_map
        (``axis_names={"pipe"}``): the kernel sits in a NESTED shard_map
        over the remaining axes. Traced only: the CPU interpreter's
        callbacks refuse a partly manual context, the chip's compiler
        does not (``test_chip_compile.py`` compiles this case)."""
        from jax.sharding import PartitionSpec as P

        from deepspeed_tpu.ops.attention import attention
        from deepspeed_tpu.utils.compat import shard_map

        mesh = _topology(pipe=2, data=2, tp=2).mesh
        q, k, v = _qkv_bthd()
        if layout == "bhtd":
            q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))

        def stage(q, k, v):
            if layout == "bhtd":
                return attention(q, k, v, use_flash=True)
            return flash_attention_bthd_tp(q, k, v, block_q=128,
                                           block_k=128)

        def loss(q, k, v):
            return jnp.sum(shard_map(
                stage, mesh=mesh, in_specs=(P(), P(), P()), out_specs=P(),
                axis_names={"pipe"}, check_vma=False)(q, k, v) ** 2)

        text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(
            q, k, v))
        assert "pallas_call" in text
        assert text.count("shard_map") >= 2  # the stage's, and the kernel's
