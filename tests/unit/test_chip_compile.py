"""The kernels of the main path, compiled for a described TPU v5e.

Nothing runs: the TPU compiler that is installed here compiles for a chip
that is described and not attached, and it refuses what the interpret-mode
tests cannot see (misaligned tiles, too much VMEM, a kernel GSPMD cannot
partition). Each case asserts that the Mosaic kernel is in the compiled
program (``tpu_custom_call``) at the shapes GPT-2 125M drives it with.

The topology is described inside a module-scoped fixture of THIS file, and
only there: describing it loads the TPU library, which one process at a time
may hold. All such tests live in this one file so that one xdist worker gets
them all; no child process is started.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    from deepspeed_tpu.utils.compat import compilation_cache_off

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    with compilation_cache_off():
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — whatever the library raises
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _s(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# GPT-2 125M: 12 heads of 64; train batch 16 x 1024; serving 8 slots over a
# pool of 512 blocks of 32 tokens, 32 blocks a sequence
H, D = 12, 64


def test_flash_fwd_bwd_bthd_bench_shape(one_chip):
    from deepspeed_tpu.ops.flash_attention import flash_attention_bthd

    q = _s(one_chip, (16, 1024, H, D))

    def fwd_bwd(q, k, v):
        return jax.grad(lambda *a: flash_attention_bthd(*a).astype(
            jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    assert "tpu_custom_call" in _compiled_text(fwd_bwd, q, q, q)


def test_decode_attention(one_chip):
    from deepspeed_tpu.ops.decode_attention import decode_attention

    text = _compiled_text(
        decode_attention, _s(one_chip, (8, 1, H, D)),
        _s(one_chip, (8, 1024, H, D)), _s(one_chip, (8, 1024, H, D)),
        _s(one_chip, (), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("t_q", [1, 5])
def test_decode_attention_paged(one_chip, t_q):
    from deepspeed_tpu.ops.decode_attention import decode_attention_paged

    pool = _s(one_chip, (512, 32, H, D))
    text = _compiled_text(
        decode_attention_paged, _s(one_chip, (8, t_q, H, D)), pool, pool,
        _s(one_chip, (8, 32), jnp.int32), _s(one_chip, (8,), jnp.int32))
    assert "tpu_custom_call" in text


def test_decode_attention_paged_int8(one_chip):
    from deepspeed_tpu.ops.decode_attention import (
        decode_attention_paged_int8)

    pool = _s(one_chip, (512, 32, H, D), jnp.int8)
    scale = _s(one_chip, (512, 32, H, 1), jnp.float32)
    text = _compiled_text(
        decode_attention_paged_int8, _s(one_chip, (8, 1, H, D)), pool, pool,
        scale, scale, _s(one_chip, (8, 32), jnp.int32),
        _s(one_chip, (8,), jnp.int32))
    assert "tpu_custom_call" in text


def test_block_sparse(one_chip):
    from deepspeed_tpu.ops.sparse_attention.block_sparse_kernel import (
        block_sparse_attention)

    n = 1024 // 128  # causal band of 3 blocks plus a global first column
    i, j = np.arange(n)[:, None], np.arange(n)[None]
    layout = np.broadcast_to((j <= i) & ((i - j < 3) | (j == 0)), (H, n, n))
    q = _s(one_chip, (2, H, 1024, D))
    text = _compiled_text(
        lambda q, k, v: block_sparse_attention(q, k, v, layout), q, q, q)
    assert "tpu_custom_call" in text


def test_flash_on_a_mesh_goes_through_shard_map(topo):
    """What the first four-chip compile was refused for: under GSPMD a
    Mosaic kernel "cannot be automatically partitioned". On a mesh of more
    than one device the tp wrapper shard_maps it even when no axis splits
    the heads, and the chip's compiler then takes it."""
    from deepspeed_tpu.ops.flash_attention import flash_attention_bthd_tp

    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "tp"))
    q = _s(NamedSharding(mesh, P("data")), (16, 1024, H, D))
    text = _compiled_text(
        lambda q, k, v: flash_attention_bthd_tp(q, k, v, mesh=mesh), q, q, q)
    assert "tpu_custom_call" in text


@pytest.fixture
def _no_global_topology():
    from deepspeed_tpu.parallel.topology import reset_topology

    reset_topology()
    yield
    reset_topology()


def test_flash_inside_the_ulysses_shard_map(topo, _no_global_topology):
    """``use_flash=True`` is the TPU default, and the Ulysses body calls
    the dispatcher from inside its own fully manual shard_map: the kernel
    must be called plainly there (a second shard_map over the same mesh is
    an error at trace, on the chip as here)."""
    from deepspeed_tpu.ops.ulysses_attention import ulysses_attention
    from deepspeed_tpu.parallel.topology import MeshTopology, set_topology

    mt = MeshTopology(axis_sizes={"data": 2, "seq": 2}, devices=topo.devices)
    set_topology(mt)
    q = _s(NamedSharding(mt.mesh, P("data", None, "seq")), (4, H, 1024, D))
    text = _compiled_text(
        lambda q, k, v: ulysses_attention(q, k, v, mesh=mt.mesh,
                                          use_flash=True), q, q, q)
    assert "tpu_custom_call" in text and "all-to-all" in text


def test_flash_inside_the_pipe_manual_shard_map(topo):
    """The pipeline engine's shard_map is manual over ``pipe`` only; the
    chip's compiler wants every axis manual around a Mosaic kernel, so the
    kernel sits in a nested shard_map over the axes left Auto. Forward and
    backward, as a stage runs it."""
    from deepspeed_tpu.ops.flash_attention import flash_attention_bthd_tp
    from deepspeed_tpu.utils.compat import shard_map

    mesh = Mesh(np.array(topo.devices).reshape(2, 2, 1),
                ("pipe", "data", "tp"))
    q = _s(NamedSharding(mesh, P("data")), (4, 1024, H, D))

    def stage_grads(q, k, v):
        def loss(q, k, v):
            return shard_map(
                lambda *t: flash_attention_bthd_tp(*t, mesh=mesh),
                mesh=mesh, in_specs=(P(), P(), P()), out_specs=P(),
                axis_names={"pipe"}, check_vma=False)(q, k, v).astype(
                    jnp.float32).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    assert "tpu_custom_call" in _compiled_text(stage_grads, q, q, q)


# ---------------------------------------------------------------------------
# a name on every kernel: the HLO instruction the device trace prints
def _kernel_names(text):
    """Instruction names of the Mosaic kernels in a compiled program."""
    import re

    return [m.group(1) for m in re.finditer(
        r"^\s*(?:ROOT )?%([\w.\-]+) = [^\n]*custom_call_target="
        r'"tpu_custom_call"', text, re.M)]


FLASH_NAMES = ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]


def _stems(names, known=FLASH_NAMES):
    """Which known kernel name each instruction name carries. Autodiff
    decorates the scope (``jvp_flash_fwd_.1``; a scanned, rematerialized
    layer prints it bare), so the name is looked for inside."""
    return sorted(k for n in names for k in known if k in n)


@pytest.mark.parametrize("layout", ["bthd", "bhtd"])
def test_flash_kernels_are_named_by_what_they_are(one_chip, layout):
    """Forward, dK/dV and dQ carry their own names whatever scope calls
    them (they were ``attn.23/24/25`` by a flax scope and a counter)."""
    from deepspeed_tpu.ops import flash_attention as fa

    fn, shape = ((fa.flash_attention_bthd, (4, 1024, H, D))
                 if layout == "bthd" else
                 (fa.flash_attention, (4, H, 1024, D)))
    q = _s(one_chip, shape)

    def fwd_bwd(q, k, v):
        with jax.named_scope("attn"):
            return jax.grad(lambda *a: fn(*a).astype(jnp.float32).sum(),
                            argnums=(0, 1, 2))(q, k, v)

    names = _kernel_names(_compiled_text(fwd_bwd, q, q, q))
    assert len(names) == 3 and _stems(names) == FLASH_NAMES, names


def test_flash_kernels_keep_their_names_under_shard_map(topo):
    """On four chips they were ``shard_map.206-208``."""
    from deepspeed_tpu.ops.flash_attention import flash_attention_bthd_tp

    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "tp"))
    q = _s(NamedSharding(mesh, P("data")), (16, 1024, H, D))

    def fwd_bwd(q, k, v):
        return jax.grad(lambda *a: flash_attention_bthd_tp(
            *a, mesh=mesh).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    names = _kernel_names(_compiled_text(fwd_bwd, q, q, q))
    assert len(names) == 3 and _stems(names) == FLASH_NAMES, names


def test_dense_decode_kernel_is_named(one_chip):
    from deepspeed_tpu.ops.decode_attention import decode_attention

    names = _kernel_names(_compiled_text(
        decode_attention, _s(one_chip, (8, 1, H, D)),
        _s(one_chip, (8, 1024, H, D)), _s(one_chip, (8, 1024, H, D)),
        _s(one_chip, (), jnp.int32)))
    assert _stems(names, ["decode_attn"]) == ["decode_attn"], names


@pytest.mark.parametrize("mesh_shape", [None, (1, 4)])
def test_paged_decode_kernel_matches_the_benchmarks_reader(
        topo, one_chip, monkeypatch, _no_global_topology, mesh_shape):
    """The serving decode program of a GPT-2 (125M widths, two layers).
    Alone, its paged kernel is the instruction ``attn._paged_kv_attend.N``,
    which is the name the accepted reader of ``paged_decode_roofline_share``
    matches (the pattern is read from the benchmark's own file); with the
    heads over tp=4 it keeps ``paged_kv_attend`` in its name."""
    import json
    import os
    import re

    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    from deepspeed_tpu.ops import attention as ops_attention

    # the dispatcher asks whether a TPU is attached; here one is described
    monkeypatch.setattr(ops_attention, "use_decode_kernel", lambda: True)
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(repo, "perfbench", "layer_metrics",
                           "paged_decode_roofline_share.json")) as f:
        pattern = re.compile(json.load(f)["pattern"])

    slots, blocks, bs, per_seq = 8, 512, 32, 32
    cfg = GPT2Config(vocab_size=50257, n_positions=1024, n_embd=H * D,
                     n_layer=2, n_head=H, dtype=jnp.bfloat16)
    module = GPT2LMHeadModel(cfg.for_paged_decode(blocks, bs))
    if mesh_shape is None:
        place, mesh = one_chip, None
    else:
        from deepspeed_tpu.parallel.topology import (MeshTopology,
                                                     set_topology)

        mt = MeshTopology(axis_sizes={"data": 1, "tp": 4},
                          devices=topo.devices)
        set_topology(mt)
        mesh, place = mt.mesh, NamedSharding(mt.mesh, P())

    def paging(n):
        return {"block_tables": jnp.zeros((n, per_seq), jnp.int32),
                "lengths": jnp.zeros((n,), jnp.int32),
                "num_valid": jnp.ones((n,), jnp.int32), "prefill": False}

    shapes = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32),
        paging=paging(1)))
    put = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda s: _s(place, s.shape, s.dtype), tree)

    def decode(params, cache, tokens, tables, lengths):
        pg = {"block_tables": tables, "lengths": lengths,
              "num_valid": jnp.ones_like(lengths), "prefill": False}
        return module.apply({"params": params, "cache": cache}, tokens,
                            mutable=["cache"], paging=pg)

    text = _compiled_text(
        decode, put(shapes["params"]), put(shapes["cache"]),
        _s(place, (slots, 1), jnp.int32),
        _s(place, (slots, per_seq), jnp.int32),
        _s(place, (slots,), jnp.int32))
    names = _kernel_names(text)
    if mesh_shape is None:
        assert [ln for ln in map(str.strip, text.splitlines())
                if pattern.search(ln)], names
    else:
        # per shard the kernel sits in a shard_map, whose body is scoped
        # (it printed as ``shard_map.N``)
        assert names and all("paged_kv_attend" in n for n in names), names
