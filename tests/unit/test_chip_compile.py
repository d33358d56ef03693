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


@pytest.fixture
def _no_global_topology():
    from deepspeed_tpu.parallel.topology import reset_topology

    reset_topology()
    yield
    reset_topology()


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


# the paged kernel reads the STACKED pool [layers, blocks, bs, H*D] at a
# layer index: 125M's 12 heads of 64, XL's 25 (a row of 1600 lanes is no
# multiple of 128), head size 128, and the benchmark's serving cell as it is
# sized (GPT-2 XL, 32 slots x 32 blocks of 32 over a pool of 513)
# since PR 44 a grid step is a tile of 4 such blocks, each its own operand:
# the same shapes, and a prefill chunk's 128 query rows (the chip's
# compiler takes a tile wherever it takes a block: at 25 heads of 64 to 176
# rows, neither from 192)
@pytest.mark.parametrize("slots,t_q,heads,dim", [
    (8, 1, H, D), (8, 5, H, D), (8, 1, 25, 64), (8, 5, 25, 64),
    (8, 1, 8, 128), (32, 1, 25, 64), (32, 5, 25, 64), (1, 128, 25, 64),
    (4, 128, H, D)])
def test_decode_attention_paged(one_chip, slots, t_q, heads, dim):
    from deepspeed_tpu.ops.decode_attention import (decode_attention_paged,
                                                    paged_plan)

    assert paged_plan(32).tile_blocks == 4
    pool = _s(one_chip, (2, 513, 32, heads * dim))
    text = _compiled_text(
        decode_attention_paged, _s(one_chip, (slots, t_q, heads, dim)), pool,
        pool, _s(one_chip, (slots, 32), jnp.int32),
        _s(one_chip, (slots,), jnp.int32), _s(one_chip, (), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("slots,t_q,heads,dim", [
    (8, 1, H, D), (8, 1, 25, 64), (8, 1, 8, 128), (32, 1, 25, 64),
    (32, 5, 25, 64), (1, 128, 25, 64)])
def test_decode_attention_paged_int8(one_chip, slots, t_q, heads, dim):
    from deepspeed_tpu.ops.decode_attention import (
        decode_attention_paged_int8, scale_lanes)

    pool = _s(one_chip, (2, 513, 32, heads * dim), jnp.int8)
    scale = _s(one_chip, (2, 513, 32, scale_lanes(heads)), jnp.float32)
    text = _compiled_text(
        decode_attention_paged_int8, _s(one_chip, (slots, t_q, heads, dim)),
        pool, pool, scale, scale, _s(one_chip, (slots, 32), jnp.int32),
        _s(one_chip, (slots,), jnp.int32), _s(one_chip, (), jnp.int32))
    assert "tpu_custom_call" in text


def test_decode_attention_paged_int8_over_tp(topo, _no_global_topology):
    """Heads over tp=4: the K/V lanes are split, the scale rows stay whole
    and each shard rolls its heads' lanes to the front by a traced offset —
    the one thing in the kernel that only a mesh exercises."""
    from deepspeed_tpu.ops.decode_attention import (
        decode_attention_paged_int8_tp, scale_lanes)

    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "tp"))
    lanes = NamedSharding(mesh, P(None, None, None, "tp"))
    rep = NamedSharding(mesh, P())
    pool = _s(lanes, (2, 512, 32, H * D), jnp.int8)
    scale = _s(rep, (2, 512, 32, scale_lanes(H)), jnp.float32)
    text = _compiled_text(
        lambda *a: decode_attention_paged_int8_tp(*a, mesh=mesh),
        _s(NamedSharding(mesh, P(None, None, "tp")), (8, 1, H, D)), pool,
        pool, scale, scale, _s(rep, (8, 32), jnp.int32),
        _s(rep, (8,), jnp.int32), _s(rep, (), jnp.int32))
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


# the folded layout at the benchmark's shapes: the training cells' rows
# (medium 8 x 16 heads, XL 4 x 25 a chip: the panel is the sequence, two
# rows a grid step) forward + backward, and the serving cell's prefill, one
# request of 25 heads a call at each prompt bucket, forward only
@pytest.mark.parametrize("shape,grad", [
    ((8, 16, 1024, 64), True), ((4, 25, 1024, 64), True),
    ((1, 25, 16, 64), False), ((1, 25, 128, 64), False),
    ((1, 25, 512, 64), False), ((1, 25, 1024, 64), False),
    ((2, 8, 768, 128), True), ((1, 4, 2048, 64), True)])
def test_flash_cells_shapes(one_chip, shape, grad):
    from deepspeed_tpu.ops.flash_attention import flash_attention

    q = _s(one_chip, shape)

    def run(q, k, v):
        if not grad:
            return flash_attention(q, k, v)
        return jax.grad(lambda *a: flash_attention(*a).astype(
            jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    names = _kernel_names(_compiled_text(run, q, q, q))
    assert _stems(names) == (FLASH_NAMES if grad else ["flash_fwd"]), names
    assert len(names) == (3 if grad else 1)


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
    matches (the pattern is read from the benchmark's own file), and ONLY
    the attention kernel is: the call that writes the step's rows first
    (PR 55) has a name of its own, ``paged_kv_write.N``, so the reader's
    time is the kernel that reads the live KV, as its bytes are; with the
    heads over tp=4 the kernel keeps ``paged_kv_attend`` in its name."""
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
        matched = [ln for ln in map(str.strip, text.splitlines())
                   if pattern.search(ln)]
        assert len(matched) == 1 and "paged_kv_write" not in matched[0], names
        assert any(n.startswith("paged_kv_write") for n in names), names
    else:
        # per shard the kernel sits in a shard_map, whose body is scoped
        # (it printed as ``shard_map.N``); beside it the write call that
        # puts the step's rows into each shard's lanes of the pools (PR 55)
        assert sorted(n.split(".")[0] for n in names) == [
            "paged_kv_attend", "paged_kv_write"], names


# ---------------------------------------------------------------------------
# the serving KV pool: one resident form, written and read in place
def _results(text):
    """``(name, opcode, [(dtype, dims), ...], line)`` of every instruction
    in a compiled program's text (a tuple result lists its elements)."""
    import re

    head = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*)$")
    shape = re.compile(r"\b([a-z]+[0-9]+)\[([0-9,]*)\]")
    for line in text.splitlines():
        m = head.match(line)
        if not m:
            continue
        rest = m.group(2)
        # the result type ends where the opcode starts: the first
        # ``word(`` that is not inside the (possibly tuple) type
        op = re.search(r"(?:^|[\s)}])([a-z][\w\-]*)\(", rest)
        if not op:
            continue
        yield (m.group(1), op.group(1),
               [(d, tuple(int(x) for x in dims.split(",") if x))
                for d, dims in shape.findall(rest[:op.start(1)])], line)


def _roots(text):
    """computation name -> the opcode of its ROOT, for every computation
    of a compiled program's text (what a ``fusion`` really is)."""
    import re

    return {m.group(1): m.group(2) for m in re.finditer(
        r"^%?([\w.\-]+) [^\n]*\{\n(?:[^\n]*\n)*?\s*ROOT %[\w.\-]+ = "
        r"[^\n]*?\s([a-z][\w\-]*)\(", text, re.M)}


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("kv", ["", "int8"])
def test_serving_programs_update_the_kv_pool_in_place(one_chip, monkeypatch,
                                                      kv, program):
    """The serving decode program and a prefill program at GPT-2 XL widths
    (25 heads of 64; 32 slots; the default pool of 1025 blocks of 32), the
    pool donated, bf16 and int8. The pool has ONE resident form that the
    program writes and reads in place: program temporaries stay below ONE
    layer's pool and the input-output alias covers every pool byte. In the
    DECODE program the only instructions with a result of the pool's block
    dims are parameters, loop plumbing and the paged kernel's write call
    (``paged_kv_write``, PR 55: it takes each pool once, hands it back, and
    writes the step's rows for its busy rows only); with bf16's two pools
    also the ONE conditional that sends a step of more writers than
    ``paged_most_writers`` to the row scatters instead, the scatters its
    other branch and nowhere else. int8's four pools meet the scatters
    only at a full batch: no conditional, no scatter at all. No ``copy``,
    ``dynamic-slice``, ``dynamic-update-slice`` or ``AllocateBuffer`` over
    a pool or a layer's slice of one in any of them. A PREFILL program
    keeps its row scatters (T rows a call, XLA attention): there only
    parameters, loop plumbing and the scatters touch the pool. On the
    parent of PR 27 this found the layout conversions around the layer
    scan (``copy.30-33``), the scan's second pool (``AllocateBuffer``) and
    the copy back onto the donated argument (``copy.52/53``): two thirds of
    a decode step; in PR 55 it found that an attention call whose own
    output block is aliased to one of its four operands a pool makes the
    compiler copy the pool in and out.

    Eight layers, so that every pool leaf is larger than the chip's 128
    MiB of fast memory: the compiler prefetches a smaller one there whole
    (``copy-start``), which the 48-layer program cannot do."""
    import re

    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    from deepspeed_tpu.ops import attention as ops_attention

    monkeypatch.setattr(ops_attention, "use_decode_kernel", lambda: True)
    heads, dim, layers, slots, blocks, bs, per_seq = 25, 64, 8, 32, 1025, 32, 32
    # a small vocabulary: the embedding's own layout copy (not the pool's)
    # would otherwise be the program's largest temporary
    cfg = GPT2Config(vocab_size=1024, n_positions=1024, n_embd=heads * dim,
                     n_layer=layers, n_head=heads, dtype=jnp.bfloat16)
    module = GPT2LMHeadModel(cfg.for_paged_decode(blocks, bs, kv))
    shapes = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32),
        paging={"block_tables": jnp.zeros((1, per_seq), jnp.int32),
                "lengths": jnp.zeros((1,), jnp.int32),
                "num_valid": jnp.ones((1,), jnp.int32), "prefill": False}))
    pool = jax.tree_util.tree_leaves(shapes["cache"])
    assert len(pool) == (4 if kv else 2)
    pool_bytes = sum(int(np.prod(s.shape)) * s.dtype.itemsize for s in pool)

    n, t = (slots, 1) if program == "decode" else (1, 256)

    def fn(params, cache, ids, tables, lengths, num_valid):
        pg = {"block_tables": tables, "lengths": lengths,
              "num_valid": num_valid, "prefill": program == "prefill"}
        out, vars_ = module.apply({"params": params, "cache": cache}, ids,
                                  mutable=["cache"], paging=pg)
        return jnp.argmax(out[:, -1], axis=-1), vars_["cache"]

    put = lambda tree, dtype=None: jax.tree_util.tree_map(  # noqa: E731
        lambda s: _s(one_chip, s.shape, dtype or s.dtype), tree)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        put(shapes["params"], jnp.bfloat16), put(shapes["cache"]),
        _s(one_chip, (n, t), jnp.int32),
        _s(one_chip, (n, per_seq), jnp.int32),
        _s(one_chip, (n,), jnp.int32),
        _s(one_chip, (n,), jnp.int32)).compile()
    text = compiled.as_text()

    # an instruction "touches the pool" if a result of it has the pool's
    # (blocks, block_size) dims side by side: the stacked pool, a layer
    # of it, or any re-tiling that keeps the block axis
    touching = [(name, op, line) for name, op, res, line in _results(text)
                if any((blocks, bs) in zip(dims, dims[1:]) for _, dims in res)]
    assert touching
    plumbing = {"parameter", "get-tuple-element", "tuple", "while", "bitcast"}
    chooses = program == "decode" and not kv
    writers = ({"scatter", "fusion"} if program == "prefill" else
               {"custom-call", "conditional", "scatter", "fusion"} if chooses
               else {"custom-call"})
    assert {op for _, op, _ in touching} <= plumbing | writers, sorted(
        (name, op) for name, op, _ in touching
        if op not in plumbing | writers)
    # a fusion with a pool-sized result is the in-place scatter and
    # nothing else: its computation's root is the scatter
    roots = _roots(text)
    for name, op, line in touching:
        if op == "fusion":
            called = re.search(r"calls=%([\w.\-]+)", line).group(1)
            assert roots.get(called) in ("scatter", "bitcast"), (
                name, called, roots.get(called))
    scatters = sum(op in ("scatter", "fusion") and roots.get(
        (re.search(r"calls=%([\w.\-]+)", line) or [None, None])[1],
        op) == "scatter" for _, op, line in touching)
    if program == "decode":
        # the kernel's write call, once a layer (one scanned body), every
        # pool its operand once and its result
        calls = [(name, line) for name, op, line in touching
                 if op == "custom-call"]
        assert len(calls) == 1 and calls[0][0].startswith(
            "paged_kv_write"), calls
        assert "tpu_custom_call" in calls[0][1]
        aliased = re.search(r"output_to_operand_aliasing=\{(.*?\})\}",
                            calls[0][1]).group(1)
        assert len(re.findall(r"\{\d+\}: \(\d+, \{\}\)", aliased)) == len(
            pool), aliased
        conds = [line for _, op, line in touching if op == "conditional"]
        assert len(conds) == int(chooses)
        # where the program chooses, the scatters are its other branch: a
        # scatter a pool, under the conditional's computations only
        assert len(re.findall(r"\bscatter\(", text)) == (
            len(pool) if chooses else 0)
        if chooses:
            branches = re.search(r"branch_computations=\{([^}]*)\}",
                                 conds[0]).group(1).replace("%", "").split(
                                     ", ")
            assert len(branches) == 2 and sorted(
                roots.get(b) for b in branches) == ["custom-call", "tuple"], (
                branches, [roots.get(b) for b in branches])
    else:
        assert scatters >= len(pool)

    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < pool_bytes // layers, (
        mem.temp_size_in_bytes, pool_bytes // layers)
    assert mem.alias_size_in_bytes >= pool_bytes, (
        mem.alias_size_in_bytes, pool_bytes)
    if program == "decode":
        assert any("_paged_kv_attend" in k for k in _kernel_names(text))


# ---------------------------------------------------------------------------
# the token lookup reads the table as it lies
@pytest.mark.parametrize("width,heads,program,form", [
    (1600, 25, "decode", "columns"),
    (1024, 16, "decode", "rows"),
    (1600, 25, "prefill", "rows"),
], ids=["xl-decode", "medium-decode", "xl-prefill-768"])
def test_serving_lookup_reads_the_table_as_it_lies(one_chip, monkeypatch,
                                                   width, heads, program,
                                                   form):
    """GPT-2's 50,257-row bf16 table in a serving program (two layers, 32
    slots, pool donated). The backend lays ``[50257, 1600]`` with the
    VOCABULARY minor (1600 is 12.5 registers of 128 lanes; the tied head
    takes it so), and ``wte[ids]`` then costs a row-major copy of all 161
    MB in every program. ``lookup_form`` reads the layout (here: the format
    this compiler gives the parameter, as the serving engine reads its
    device array's) and the token count; the decode program at XL's width
    takes columns (the ``embed_lookup_columns`` kernel) and holds NO
    instruction with the table's dims but the parameter and its bitcasts
    (nor, with the XLA loop in the kernel's place, but those and the loop
    that carries the table); at 1024 wide the
    table lies row-major, the program takes rows and holds none either; a
    768-token prefill at XL's width is over the crossover, takes rows and
    pays the one copy."""
    import re

    from deepspeed_tpu.models.decode_utils import lookup_form
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    from deepspeed_tpu.ops import attention as ops_attention

    monkeypatch.setattr(ops_attention, "use_decode_kernel", lambda: True)
    vocab, layers, slots, blocks, bs, per_seq = 50257, 2, 32, 129, 32, 32
    cfg = GPT2Config(vocab_size=vocab, n_positions=1024, n_embd=width,
                     n_layer=layers, n_head=heads, dtype=jnp.bfloat16)
    module = GPT2LMHeadModel(cfg.for_paged_decode(blocks, bs))
    shapes = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32),
        paging={"block_tables": jnp.zeros((1, per_seq), jnp.int32),
                "lengths": jnp.zeros((1,), jnp.int32),
                "num_valid": jnp.ones((1,), jnp.int32), "prefill": False}))
    n, t = (slots, 1) if program == "decode" else (1, 768)

    # the layout the chip gives a parameter of the table's shape
    table = _s(one_chip, (vocab, width))
    lies = jax.jit(lambda w: w).lower(table).compile().input_formats[0][0]
    vocab_minor = tuple(lies.layout.major_to_minor) == (1, 0)
    assert vocab_minor == (width == 1600), lies
    chosen = lookup_form(jax.ShapeDtypeStruct(
        table.shape, table.dtype, sharding=lies), n * t)
    assert chosen == form

    def fn(params, cache, ids, tables, lengths, num_valid):
        pg = {"block_tables": tables, "lengths": lengths,
              "num_valid": num_valid, "prefill": program == "prefill",
              "lookup": chosen}
        out, vars_ = module.apply({"params": params, "cache": cache}, ids,
                                  mutable=["cache"], paging=pg)
        return jnp.argmax(out[:, -1], axis=-1), vars_["cache"]

    put = lambda tree, dtype=None: jax.tree_util.tree_map(  # noqa: E731
        lambda s: _s(one_chip, s.shape, dtype or s.dtype), tree)
    before = ops_attention.dispatch_counts().get(f"embed_lookup_{form}", 0)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        put(shapes["params"], jnp.bfloat16), put(shapes["cache"]),
        _s(one_chip, (n, t), jnp.int32),
        _s(one_chip, (n, per_seq), jnp.int32),
        _s(one_chip, (n,), jnp.int32),
        _s(one_chip, (n,), jnp.int32)).compile()
    assert ops_attention.dispatch_counts()[f"embed_lookup_{form}"] == \
        before + 1
    # the program takes the table in the layout the choice assumed
    assert compiled.input_formats[0][0]["wte"].layout == lies.layout

    text = compiled.as_text()
    table_dims = {(vocab, width), (width, vocab)}
    touching = [(name, op, line) for name, op, res, line in _results(text)
                if any(dims in table_dims for _, dims in res)]
    assert any(op == "parameter" for _, op, _ in touching)
    roots = _roots(text)
    moved = []
    for name, op, line in touching:
        if op == "fusion":
            op = roots.get(re.search(r"calls=%([\w.\-]+)", line).group(1))
        # (``copy-start`` / ``copy-done``: the prefetch of a table under the
        # chip's 128 MiB of fast memory, whole and in the layout it has; the
        # 103 MB table of the 1024-wide model gets one)
        if op not in ("parameter", "bitcast", "get-tuple-element", "tuple",
                      "while", "copy-start", "copy-done"):
            moved.append((name, op))
    if program == "decode":
        assert not moved, moved
    else:
        assert [op for _, op in moved] == ["copy"], moved
    # columns on the chip is the Pallas kernel (ops/embed_lookup.py)
    assert any("embed_lookup_columns" in k for k in _kernel_names(text)) \
        == (form == "columns")


# ---------------------------------------------------------------------------
# ZeRO-3's explicit program as the chip's compiler makes it
def test_zero3_step_gathers_bf16_weights_and_scatters_f32_gradients(
        topo, _no_global_topology):
    """The engine's stage-3 fused step for a scanned GPT-2 at XL's width
    (two layers) on ``data=4``, compiled for the described chips from
    abstract state: inside the layer loops the weights cross the wire as
    bf16 all-gathers; a kernel's gradient, and a table's outside them, is
    summed by the ring, float32 ``collective-permute``s (asynchronous
    pairs on the chip) each feeding a float32 add; what the backend still
    sums itself (the persistent leaves' gradients) is float32 too;
    nothing bf16 is summed across chips and nothing is an ``all-to-all``
    or a ``reduce-scatter``. (The CPU
    backend widens a bf16 collective to float32, so only this test reads
    the wire's dtype off a compiled program;
    tests/unit/test_zero3_gather_at_use.py has the rest.)"""
    import re

    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2ForTraining
    from deepspeed_tpu.parallel.topology import MeshTopology
    from deepspeed_tpu.runtime.engine import TrainState
    from deepspeed_tpu.runtime.zero.partition import (
        batch_sharding, build_opt_state_shardings, build_zero_shardings,
        replicated)
    from deepspeed_tpu.utils import hlo_inspect
    from deepspeed_tpu.utils.hlo_inspect import collectives_per_step

    layers, width, seq = 2, 1600, 256
    model = GPT2ForTraining(GPT2Config(
        vocab_size=1024, n_positions=seq, n_embd=width, n_layer=layers,
        n_head=25, dtype=jnp.bfloat16, scan_layers=True, remat=True,
        remat_policy="dots"))
    engine, *_ = deepspeed_tpu.initialize(
        model=model, mesh=MeshTopology(axis_sizes={"data": 4},
                                       devices=topo.devices[:4]),
        config={"train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "bf16": {"enabled": True}, "fused_step": True,
                "zero_optimization": {"stage": 3},
                "steps_per_print": 10 ** 9})
    mesh, rep = engine.mesh, replicated(engine.mesh)
    params = jax.eval_shape(lambda r: model.init(
        r, {"input_ids": jnp.zeros((1, 8), jnp.int32)})["params"],
        jax.random.PRNGKey(0))
    sites = engine._zero3_sites
    param_sh, _ = engine._shardings_for(params)
    opt = jax.eval_shape(engine.optimizer.init, params)
    opt_sh = build_opt_state_shardings(opt, params, mesh, stage=3,
                                       sites=sites)
    _, engine._grad_shardings = build_zero_shardings(
        params, mesh, stage=3, sites=sites)

    def shaped(tree, shardings):
        return jax.tree_util.tree_map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            tree, shardings)

    scalar = lambda dt: jax.ShapeDtypeStruct((), dt, sharding=rep)  # noqa: E731
    scaler = engine._initial_loss_scaler
    scaler_sh = jax.tree_util.tree_map(lambda _: rep, scaler)
    engine._state_shardings = TrainState(
        params=param_sh, opt_state=opt_sh, grad_acc={}, loss_scale=scaler_sh,
        global_step=rep, skipped_steps=rep, rng=rep)
    engine.state = TrainState(
        params=shaped(params, param_sh), opt_state=shaped(opt, opt_sh),
        grad_acc={}, loss_scale=shaped(jax.tree_util.tree_map(
            jnp.asarray, scaler), scaler_sh),
        global_step=scalar(jnp.int32), skipped_steps=scalar(jnp.int32),
        rng=jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep))
    engine._compile_steps()
    batch = {"input_ids": jax.ShapeDtypeStruct(
        (4, seq), jnp.int32,
        sharding=batch_sharding(mesh, ndim=2, shape=(4, seq)))}
    text = engine._jit_fused.lower(
        engine.state, batch, scalar(jnp.float32)).compile().as_text()
    assert engine._zero3_program["program"] == "gather_at_use"

    # every weight-sized collective of the step (the optimizer's own are
    # smaller)
    every = collectives_per_step(text)
    big = [c for c in every if c["operand_bytes"] >= width * width // 4]
    assert not [c for c in big if c["op"] == "all-to-all"]
    gathers = [c for c in big if c["op"] == "all-gather"]
    assert len(gathers) >= 2 * 4 * layers  # forward and backward, a layer
    assert all({d for d, _ in c["operands"]} == {"bf16"} for c in gathers)
    # the ring: as many permutes as the plan counts, each a float32 piece
    # of a kernel's gradient, consumed by float32 arithmetic only
    plan = engine._zero3_program
    permutes = [c for c in every if c["op"] == "collective-permute"]
    assert plan["leaves_scattered_by_ring"] == 6
    # (a kernel's in the backward loop's body, whose trip count the chip's
    # compiler does not print: once a layer; a table's in the entry)
    by_body = {}
    for c in permutes:
        by_body.setdefault(c["computation"], []).append(c["operand_bytes"])
    tables, kernels = sorted(by_body.values(), key=len)
    assert (len(tables), len(kernels)) == (2 * 3 * 2, 4 * 3 * 2)
    assert len(tables) + len(kernels) * layers == plan["ring_permutes_step"]
    assert sum(tables) + sum(kernels) * layers \
        == plan["ring_operand_bytes_step"]
    assert all({d for d, _ in c["operands"]} == {"f32"} for c in permutes)
    assert {dims for c in permutes for dims in c["operand_dims"]} == {
        (rows // 8, cols) for rows, cols in [
            (width, 3 * width), (width, width), (width, 4 * width),
            (4 * width, width), (1024, width), (seq, width)]}
    assert "collective-permute-start" in text   # asynchronous on the chip
    # one layer ahead (PR 47): the forward loop's body uses the weights the
    # step before gathered and gathers the next layer's beside its own
    # matmuls, in TWO collectives (c_attn, attention c_proj and c_fc
    # together; the MLP's c_proj alone). At this size (a quarter of the
    # cell's rows a layer) the chip's compiler runs one of the two beside
    # a matmul and one alone, where the parent's body, which had to have
    # its own layer's four first, ran 3 alone; at the cell's size both
    # run beside matmuls where the parent ran 2 alone and waited on a
    # third (compiled here, PERF.md PR 47). The backward loop gathers
    # inside its own step, leaf by leaf as it did: 1 alone of 4.
    _, comps = hlo_inspect._computations(text)
    loops = [b for b in re.findall(r"body=%?([\w.\-]+)", text)
             if any(" all-gather(" in line or "async-collective-start" in line
                    for line in comps[b])]
    assert len(loops) == 2
    backward = next(b for b in loops if b in by_body)
    (forward,) = [b for b in loops if b != backward]
    plain = {b: sum(" all-gather(" in line for line in comps[b])
             for b in loops}
    fused = {b: sum(bool(re.match(r"\s*%async-collective-start[.\d]* = ",
                                  line)) for line in comps[b])
             for b in loops}
    assert (plain[forward], fused[forward]) == (1, 1)
    assert (plain[backward], fused[backward]) == (1, 3)
    # the gathered weights ride the forward loop's carry, in bf16, and
    # are no residual: nothing holds a layer's whole kernel a layer
    carry = next(line for line in text.splitlines()
                 if line.startswith(f"%{forward} ("))
    for rows, cols in [(width, 3 * width), (width, 4 * width),
                       (4 * width, width)]:
        assert f"bf16[{rows},{cols}]" in carry
        assert not re.search(rf"\[{layers},{rows},{cols}\]", text)
    assert f"bf16[{width // 4},{8 * width}]" in "\n".join(comps[forward])
    assert plan["gathers_ahead_step"] == 4 * (layers - 1)
    fed = [line for line in text.splitlines()
           if re.search(r"\(.*%collective-permute-done", line)
           and " fusion(" in line]
    assert fed and all(
        re.match(r"\s*(ROOT )?%[\w.\-]+ = \(?f32\[", line) for line in fed)
    # what the backend still sums (a layer's stacked biases and norms)
    assert not [c for c in every if c["op"] == "reduce-scatter"]
    sums = [c for c in every if c["op"] == "all-reduce"
            and c["operand_bytes"] >= layers * width * 4]
    assert sums
    assert all({d for d, _ in c["operands"]} <= {"f32"} for c in sums)


def _reader_pattern(metric):
    """The instruction a benchmark reader matches in the device trace."""
    import glob
    import json
    import os
    import re

    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    path = os.path.join(repo, "perfbench", "layer_metrics", f"{metric}.json")
    if not os.path.isfile(path):
        # a metric's file that waits for its entry in BENCHMARK.json
        (path,) = glob.glob(os.path.join(repo, "tests", "perfbench", "cells_*",
                                         f"metric.{metric}.json"))
    with open(path) as f:
        return re.compile(json.load(f)["pattern"])


def _custom_calls(text):
    return [ln.strip().removeprefix("ROOT ") for ln in text.splitlines()
            if "tpu_custom_call" in ln]


# MiMo-V2.5 at its published widths: 64 query heads over 4 (global) and 8
# (window) KV heads, keys 192 wide and values 128, a window of 128 in rings
# of 5 blocks of 32, 16 held experts of 3 x 4096 x 2048; the benchmark cell's
# 64 decode slots
@pytest.mark.parametrize("kind", ["global", "window"])
def test_hybrid_decode_kernel_at_the_published_widths(one_chip, kind):
    """The GQA paged kernel compiles for the v5e at both row shapes (768 /
    512 lanes and 1536 / 1024: whole registers, which the kernel's own
    copies need), the window's with its ring and its sink, at the plan's
    tile (16 blocks of a table, the whole ring of 5: two such tiles of both
    pools are what it asks of scoped VMEM), and is the ONE instruction the
    benchmark's reader matches: no pool read outside it."""
    from deepspeed_tpu.ops.hybrid_decode_attention import (
        decode_attention_hybrid, hybrid_plan)

    pattern = _reader_pattern("hybrid_decode_roofline_share")
    slots, bs = 64, 32
    window = kind == "window"
    kv, layers, blocks, per_row = ((8, 5, 1 + slots * 5, 5) if window
                                   else (4, 2, 8193, 128))
    assert hybrid_plan(bs, kv * 192, kv * 128, per_row).tile_blocks == (
        5 if window else 16)

    def step(q, k, v, tables, lengths, sink):
        with jax.named_scope("attn._hybrid_kv_attend"):
            return decode_attention_hybrid(
                q, k, v, tables, lengths, layers - 1, kv_heads=kv,
                window=128 if window else 0, ring=window,
                sink=sink if window else None)

    text = _compiled_text(
        step, _s(one_chip, (slots, 1, 64, 192)),
        _s(one_chip, (layers, blocks, bs, kv * 192)),
        _s(one_chip, (layers, blocks, bs, kv * 128)),
        _s(one_chip, (slots, per_row), jnp.int32),
        _s(one_chip, (slots,), jnp.int32), _s(one_chip, (64,), jnp.float32))
    calls = _custom_calls(text)
    assert len(calls) == 1 and pattern.search(calls[0]), calls


@pytest.mark.parametrize("tokens", [64, 3072], ids=["decode", "prefill"])
def test_grouped_expert_kernel_at_the_published_widths(one_chip, tokens):
    """The dropless grouped matmul over 16 held experts of 3 x 4096 x 2048
    compiles for the v5e at a decode step's 64 rows and a long prompt's
    3,072 (its weight blocks take more fast memory than the default
    allows: the limit it asks for has to be granted), under the name the
    benchmark's reader matches."""
    from deepspeed_tpu.moe.dropless import expert_ffn

    pattern = _reader_pattern("expert_matmul_roofline_share")

    def layer(x, experts, weights, gate, up, down):
        return expert_ffn(x, experts, weights, gate, up, down,
                          first_expert=0, n_routed=256, use_kernel=True)

    text = _compiled_text(
        layer, _s(one_chip, (tokens, 4096)),
        _s(one_chip, (tokens, 8), jnp.int32),
        _s(one_chip, (tokens, 8), jnp.float32),
        _s(one_chip, (16, 4096, 2048)), _s(one_chip, (16, 4096, 2048)),
        _s(one_chip, (16, 2048, 4096)))
    calls = _custom_calls(text)
    assert len(calls) == 1 and pattern.search(calls[0]), calls


# LFM2-8B-A1B at its published widths: 32 query heads over 8 KV heads of 64
# (keys and values alike: a 512-lane pool row), no window layer; 32 held
# experts of 3 x 2048 x 1792; the benchmark cell's 64 decode slots of 80
# blocks of 32. granite-4.0-h-micro's four attention layers have the same
# rows, over tables of 256 blocks and a pool of 8,193
@pytest.mark.parametrize("layers,blocks,per_row", [
    (3, 1 + 64 * 80, 80), (4, 8193, 256)], ids=["lfm2", "granite"])
def test_hybrid_decode_kernel_at_lfm2s_widths(one_chip, layers, blocks,
                                              per_row):
    """The GQA paged kernel's global kind compiles for the v5e with four
    query heads a KV head and rows of 512 lanes at the plan's tile of 16
    blocks, under the name the benchmark's reader matches."""
    from deepspeed_tpu.ops.hybrid_decode_attention import (
        decode_attention_hybrid, hybrid_plan)

    pattern = _reader_pattern("hybrid_decode_roofline_share")
    slots, bs = 64, 32
    assert hybrid_plan(bs, 512, 512, per_row).tile_blocks == 16

    def step(q, k, v, tables, lengths):
        with jax.named_scope("attn._hybrid_kv_attend"):
            return decode_attention_hybrid(q, k, v, tables, lengths,
                                           layers - 1, kv_heads=8)

    text = _compiled_text(
        step, _s(one_chip, (slots, 1, 32, 64)),
        _s(one_chip, (layers, blocks, bs, 512)),
        _s(one_chip, (layers, blocks, bs, 512)),
        _s(one_chip, (slots, per_row), jnp.int32),
        _s(one_chip, (slots,), jnp.int32))
    calls = _custom_calls(text)
    assert len(calls) == 1 and pattern.search(calls[0]), calls


@pytest.mark.parametrize("tokens", [64, 2048], ids=["decode", "prefill"])
def test_grouped_expert_kernel_at_lfm2s_widths(one_chip, tokens):
    """The dropless grouped matmul over 32 held experts of width 1792,
    which the 512-column tile does not divide (``dropless.width_tile``:
    two tiles of 896), at a decode step's 64 rows and the longest prompt
    bucket's 2,048."""
    from deepspeed_tpu.moe.dropless import expert_ffn

    pattern = _reader_pattern("expert_matmul_roofline_share")

    def layer(x, experts, weights, gate, up, down):
        return expert_ffn(x, experts, weights, gate, up, down,
                          first_expert=0, n_routed=32, use_kernel=True)

    text = _compiled_text(
        layer, _s(one_chip, (tokens, 2048)),
        _s(one_chip, (tokens, 4), jnp.int32),
        _s(one_chip, (tokens, 4), jnp.float32),
        _s(one_chip, (32, 2048, 1792)), _s(one_chip, (32, 2048, 1792)),
        _s(one_chip, (32, 1792, 2048)))
    calls = _custom_calls(text)
    assert len(calls) == 1 and pattern.search(calls[0]), calls


# DeepSeek-V2-Lite at its published widths: 16 heads over ONE latent row a
# token of 512 + 64 values in 640 lanes; 64 held experts of 3 x 2048 x 1408;
# the benchmark cell's 48 decode slots of 512 blocks of 32
# ... and Ling-3.0-flash's one latent layer: 32 heads, 128 slots of 352
# blocks over the default pool of a full context a slot
@pytest.mark.parametrize("heads,slots,layers,per_row", [
    (16, 48, 6, 512), (32, 128, 1, 352)], ids=["dsv2lite", "ling3"])
def test_latent_decode_kernel_at_the_published_widths(one_chip, heads, slots,
                                                      layers, per_row):
    """The absorbed-weights multi-query kernel compiles for the v5e over
    rows of 640 lanes whose first 512 are also the values, at BOTH cells'
    pools and tables (Mosaic's answer to the kernel's own copies of a
    block of 32 x 640 into a tile of the plan's 32), under the name the
    benchmark's reader matches and no other reader does; its two tiles
    are all it takes of scoped VMEM beside a row's query and sums."""
    from deepspeed_tpu.ops.latent_decode_attention import (
        decode_attention_latent, latent_plan)

    pattern = _reader_pattern("mla_decode_roofline_share")
    bs = 32
    blocks = 16385 if layers == 6 else 1 + slots * per_row
    plan = latent_plan(bs, 640, per_row)
    assert (plan.tile_blocks, plan.tile_keys) == (32, 1024)
    # two tiles of 1,024 keys of 640 lanes in bfloat16: 2.5 MiB
    assert 2 * plan.tile_keys * 640 * 2 == 2_621_440

    def step(q, pool, tables, lengths):
        with jax.named_scope("attn._latent_kv_attend"):
            return decode_attention_latent(q, pool, tables, lengths,
                                           layers - 1, rank=512,
                                           scale=0.1147)

    text = _compiled_text(
        step, _s(one_chip, (slots, 1, heads, 640)),
        _s(one_chip, (layers, blocks, bs, 640)),
        _s(one_chip, (slots, per_row), jnp.int32),
        _s(one_chip, (slots,), jnp.int32))
    calls = _custom_calls(text)
    assert len(calls) == 1 and pattern.search(calls[0]), calls
    for other in ("hybrid_decode_roofline_share",
                  "paged_decode_roofline_share"):
        assert not any(_reader_pattern(other).search(ln) for ln in calls)


@pytest.mark.parametrize("tokens", [48, 512], ids=["decode", "chunk"])
def test_grouped_expert_kernel_at_deepseek_v2_lites_widths(one_chip, tokens):
    """The dropless grouped matmul over 64 held experts of width 1408 = 11
    x 128, a prime number of registers (``dropless.width_tile`` says what
    that takes), at a decode step's 48 rows and a prefill chunk's 512."""
    from deepspeed_tpu.moe.dropless import expert_ffn, width_tile

    pattern = _reader_pattern("expert_matmul_roofline_share")
    assert 1408 % width_tile(1408) == 0

    def layer(x, experts, weights, gate, up, down):
        return expert_ffn(x, experts, weights, gate, up, down,
                          first_expert=0, n_routed=64, use_kernel=True)

    text = _compiled_text(
        layer, _s(one_chip, (tokens, 2048)),
        _s(one_chip, (tokens, 6), jnp.int32),
        _s(one_chip, (tokens, 6), jnp.float32),
        _s(one_chip, (64, 2048, 1408)), _s(one_chip, (64, 2048, 1408)),
        _s(one_chip, (64, 1408, 2048)))
    calls = _custom_calls(text)
    assert len(calls) == 1 and pattern.search(calls[0]), calls


# granite-4.0-h-micro at its published widths: 36 Mamba-2 layers of 64 heads
# x 64 with a state of 128 in a pool of 1 + 64 slots; the benchmark cell's 64
# decode slots and its 512-token chunks in scan chunks of 256
def _largest_moved_bytes(text):
    """The largest array a ``copy`` or a ``transpose`` of a compiled
    program's text produces, in bytes (0 without one)."""
    import re

    sizes = {"bf16": 2, "f32": 4, "s32": 4, "f16": 2, "pred": 1, "s8": 1,
             "u8": 1, "u32": 4}
    worst = 0
    for ln in text.splitlines():
        found = re.search(
            r"= (\w+)\[([\d,]*)\]\S* (?:copy|copy-start|transpose)\(", ln)
        if found and found.group(1) in sizes:
            dims = [int(d) for d in found.group(2).split(",") if d]
            worst = max(worst, int(np.prod(dims)) * sizes[found.group(1)])
    return worst


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_ssm_state_update_kernel_is_in_place_and_named(one_chip, program):
    """The decode step's state update on the pool as its values lie
    (``[36, 65, 32, 128, 128]``, a bitcast of the allocation): the pool
    aliased to the kernel's output (no second 2.45 GB), ONE custom call a
    layer under the name the parked ``ssm_decode_roofline_share`` reads;
    and a prefill chunk's read and write of a row through the scan kernel,
    which turns the lane groups in VMEM. Neither program copies or transposes an array of the pool's
    size."""
    from deepspeed_tpu.models.granite_hybrid import (scan_state_in,
                                                     scan_state_out)
    from deepspeed_tpu.ops.ssd_chunk_scan import ssd_chunk_scan
    from deepspeed_tpu.ops.ssm_state_update import (kernel_serves,
                                                    state_update_kernel)

    pattern = _reader_pattern("ssm_decode_roofline_share")
    pool = (36, 65, 64, 64, 128)
    held = int(np.prod(pool)) * 2
    assert kernel_serves(64, 64, 128)

    def step(pool, slots, a, dx, b, c):
        with jax.named_scope("ssm._state_update"):
            return state_update_kernel(pool, 7, slots, a, dx, b, c)

    def chunk(pool, rows, fresh, *terms):
        _, state = ssd_chunk_scan(
            *terms, scan_state_in(pool, 7, rows, fresh), 256, jnp.bfloat16,
            use_kernel=True)
        return scan_state_out(pool, 7, rows, state)

    if program == "decode":
        compiled = jax.jit(step, donate_argnums=0).lower(
            _s(one_chip, pool), _s(one_chip, (64,), jnp.int32),
            _s(one_chip, (64, 64), jnp.float32),
            _s(one_chip, (64, 64, 64), jnp.float32),
            _s(one_chip, (64, 128), jnp.float32),
            _s(one_chip, (64, 128), jnp.float32)).compile()
        calls = _custom_calls(compiled.as_text())
        assert len(calls) == 1 and pattern.search(calls[0]), calls
        assert "bf16[36,65,32,128,128]" in calls[0]
    else:
        compiled = jax.jit(chunk, donate_argnums=0).lower(
            _s(one_chip, pool), _s(one_chip, (1,), jnp.int32),
            _s(one_chip, (1,), jnp.bool_),
            _s(one_chip, (1, 512, 64, 64), jnp.float32),
            _s(one_chip, (1, 512, 64), jnp.float32),
            _s(one_chip, (64,), jnp.float32),
            _s(one_chip, (1, 512, 128), jnp.float32),
            _s(one_chip, (1, 512, 128), jnp.float32)).compile()
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= held
    assert memory.temp_size_in_bytes < held // 100
    assert _largest_moved_bytes(compiled.as_text()) < held // 100


def test_ssd_chunk_scan_kernel_at_a_prefill_chunks_shape(one_chip):
    """The chunked scan of one layer of one 512-token call: one kernel,
    under the name the parked ``ssd_scan_roofline_share`` reads."""
    from deepspeed_tpu.ops.ssd_chunk_scan import kernel_serves, ssd_chunk_scan

    pattern = _reader_pattern("ssd_scan_roofline_share")
    assert kernel_serves(256, 64, 64, 128) and not kernel_serves(4, 8, 16, 32)

    def scan(x, delta, rate, b, c, state):
        return ssd_chunk_scan(x, delta, rate, b, c, state, 256, jnp.bfloat16,
                              use_kernel=True)

    f32 = jnp.float32
    text = _compiled_text(
        scan, _s(one_chip, (1, 512, 64, 64), f32),
        _s(one_chip, (1, 512, 64), f32), _s(one_chip, (64,), f32),
        _s(one_chip, (1, 512, 128), f32), _s(one_chip, (1, 512, 128), f32),
        # the state as a pool row lies: 32 lane groups of [N, 128]
        _s(one_chip, (1, 32, 128, 128), f32))
    calls = _custom_calls(text)
    assert len(calls) == 1 and pattern.search(calls[0]), calls


# ---------------------------------------------------------------------------
# The split between ``ops/decode_attention.py`` (GPT-2's kernel) and
# ``ops/hybrid_decode_attention.py`` stays a split: the lowered serving
# programs of the two cells that run neither the hybrid kernel nor
# ``models/blocks.py:paged_gqa`` are their parent's TEXT. sha256[:16] of
# ``lower(...).as_text()`` at the cells' own sizes, the decode kernels
# forced on, a kernel's Mosaic body without its debug info (it carries its
# callers' line numbers): recorded at commit 46ccfdd (the parent of PR 50)
# by the function below, and equal there and here; but GPT-2's decode
# program, which PR 55 meant to change (its paged kernel call writes the
# step's rows: ``2387c053ad0ea1c7`` until then) and recorded anew, and
# DeepSeek's, which PR 60 meant to (the latent kernel copies its own tiles,
# one lowering for the six layers: ``0454bbe358502ca6`` until then).
_PARENT_PROGRAMS = {
    ("gpt2-xl", "decode"): "941ef119564ed3b4",
    ("gpt2-xl", "prefill"): "5cde9a5d74d5fc46",
    ("deepseek-v2-lite-l6", "decode"): "6ebb6607600491db",
    ("deepseek-v2-lite-l6", "chunk"): "b47959bec6b2899b",
}
# configuration -> slots, pool blocks, a sequence's blocks, the tokens of
# the second program (GPT-2's a whole-prompt bucket, DeepSeek's a chunk of
# a longer prompt), for_paged_decode's other arguments
_CELL_SIZES = {
    "gpt2-xl": (32, 513, 32, 128, {}),
    "deepseek-v2-lite-l6": (48, 16385, 512, 512, {"return_routed": True}),
}


def _lowered_program_hash(monkeypatch, one_chip, config, program):
    import hashlib
    import json
    import pathlib

    from jax._src import tpu_custom_call
    from jaxlib.mlir.passmanager import PassManager

    from deepspeed_tpu.ops import attention as attn_mod
    from perfbench import byname

    lower_mosaic = tpu_custom_call._lower_mosaic_module_to_asm

    def without_debug_info(module, **kw):
        with module.context:
            PassManager.parse("builtin.module(strip-debuginfo)").run(
                module.operation)
        return lower_mosaic(module, **kw)

    monkeypatch.setattr(tpu_custom_call, "_lower_mosaic_module_to_asm",
                        without_debug_info)
    monkeypatch.setattr(attn_mod, "_FORCE_DECODE_KERNEL", True)
    root = pathlib.Path(__file__).resolve().parents[2]
    config_file = json.loads(
        (root / "perfbench" / "configs" / f"{config}.json").read_text())
    slots, blocks, per_seq, tokens, knobs = _CELL_SIZES[config]
    served = byname.module("families", config_file["family"]).serving_module(
        config_file, jnp.bfloat16)
    model = type(served)(served.config.for_paged_decode(blocks, 32, **knobs))
    b, t = (slots, 1) if program == "decode" else (1, tokens)

    def paging(tables, lengths, num_valid):
        return {"block_tables": tables, "lengths": lengths,
                "num_valid": num_valid, "prefill": program == "prefill"}

    ints = lambda *shape: _s(one_chip, shape, jnp.int32)
    variables = jax.tree_util.tree_map(
        lambda x: _s(one_chip, x.shape, x.dtype),
        jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
            paging={**paging(jnp.zeros((1, per_seq), jnp.int32),
                             jnp.zeros((1,), jnp.int32),
                             jnp.full((1,), 8, jnp.int32)),
                    "prefill": True})))

    def step(variables, ids, *rest):
        return model.apply(variables, ids, mutable=["cache"],
                           paging=paging(*rest))

    text = jax.jit(step).lower(variables, ints(b, t), ints(b, per_seq),
                               ints(b), ints(b)).as_text()
    assert ("tpu_custom_call" in text) == (program == "decode")
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("config,program", list(_PARENT_PROGRAMS))
def test_other_cells_serving_programs_are_the_parents_text(
        monkeypatch, one_chip, config, program):
    """``serve-xl-chat``'s and ``serve-dsv2lite-mla-longdoc``'s decode
    program and prefill (chunk) program lower to the text they had before
    the hybrid kernel took tiles (PR 50): neither model imports the hybrid
    file, and ``paged_work_list`` at their arguments is traced as it was.
    An edit that means to change one of these programs records its new
    hash here (the same function on the new parent)."""
    assert _lowered_program_hash(monkeypatch, one_chip, config,
                                 program) == _PARENT_PROGRAMS[(config,
                                                               program)]


# K-EXAONE-236B-A23B at its published widths: 64 query heads over 8 KV heads
# of 128, keys and values alike, in BOTH kinds of layer (a 1,024-lane pool
# row for keys and one for values: 2,048 lanes a token a layer), a window of
# 128 in rings of 5 blocks of 32 with no sink; 16 held experts of 3 x 6144 x
# 2048; the benchmark cell's 64 decode slots and 512-token chunks
@pytest.mark.parametrize("kind", ["global", "window"])
def test_hybrid_decode_kernel_at_one_row_shape_for_both_kinds(one_chip, kind):
    """The GQA paged kernel at the row shape neither kind had met (1,024 +
    1,024 lanes): the plan's tile is 16 blocks of a table and the whole
    ring of 5, as at the narrower rows (two tiles of both pools are 2.1 M
    values of the 4 M it may hold), the window kind runs with no sink, and
    the kernel is the ONE instruction the benchmark's reader matches."""
    from deepspeed_tpu.ops.hybrid_decode_attention import (
        decode_attention_hybrid, hybrid_plan)

    pattern = _reader_pattern("hybrid_decode_roofline_share")
    slots, bs, kv, lanes = 64, 32, 8, 8 * 128
    window = kind == "window"
    layers, blocks, per_row = ((4, 1 + slots * 5, 5) if window
                               else (1, 8193, 128))
    assert hybrid_plan(bs, lanes, lanes, per_row).tile_blocks == (
        5 if window else 16)

    def step(q, k, v, tables, lengths):
        with jax.named_scope("attn._hybrid_kv_attend"):
            return decode_attention_hybrid(
                q, k, v, tables, lengths, layers - 1, kv_heads=kv,
                window=128 if window else 0, ring=window)

    text = _compiled_text(
        step, _s(one_chip, (slots, 1, 64, 128)),
        _s(one_chip, (layers, blocks, bs, lanes)),
        _s(one_chip, (layers, blocks, bs, lanes)),
        _s(one_chip, (slots, per_row), jnp.int32),
        _s(one_chip, (slots,), jnp.int32))
    calls = _custom_calls(text)
    assert len(calls) == 1 and pattern.search(calls[0]), calls


@pytest.mark.parametrize("tokens", [64, 512], ids=["decode", "chunk"])
def test_grouped_expert_kernel_at_6144_rows_deep(one_chip, tokens):
    """The dropless grouped matmul over 16 held experts of 3 x 6144 x 2048:
    ``width_tile`` bounds a step's block by COLUMNS (512), so a block is
    6144 x 512 = 6.3 MB a matrix where the widest met was 4.2 MB: three
    matrices double-buffered are 37.7 MB under the 96 MB the kernel asks
    for, and it compiles at a decode step's 64 rows and a chunk's 512."""
    from deepspeed_tpu.moe.dropless import expert_ffn, width_tile

    assert width_tile(2048) == 512
    pattern = _reader_pattern("expert_matmul_roofline_share")

    def layer(x, experts, weights, gate, up, down):
        return expert_ffn(x, experts, weights, gate, up, down,
                          first_expert=0, n_routed=128, use_kernel=True)

    text = _compiled_text(
        layer, _s(one_chip, (tokens, 6144)),
        _s(one_chip, (tokens, 8), jnp.int32),
        _s(one_chip, (tokens, 8), jnp.float32),
        _s(one_chip, (16, 6144, 2048)), _s(one_chip, (16, 6144, 2048)),
        _s(one_chip, (16, 2048, 6144)))
    calls = _custom_calls(text)
    assert len(calls) == 1 and pattern.search(calls[0]), calls


# Where served weights lie (PR 53). The bare steps of a serve cell, at its
# own sizes and published widths (the depth cut to two layers for time),
# compiled over the tree as the backend lays it and over the tree as
# ``ServingEngine._lay_out_weights``'s rule lays it (every leaf for which
# the decode program, its weights' layouts left to the compiler, names
# another layout than the leaf has): ``tools/probe_weight_layouts.py``.
_LAID_OUT = {}


def _weight_layout_lines(monkeypatch, one_chip, cell_name, layers=2):
    import functools

    from deepspeed_tpu.moe import dropless
    from deepspeed_tpu.ops import attention as attn_mod
    from deepspeed_tpu.serving import weight_layouts
    from perfbench import run as bench
    from tools import probe_weight_layouts as probe

    if cell_name not in _LAID_OUT:
        monkeypatch.setattr(attn_mod, "_FORCE_DECODE_KERNEL", True)
        monkeypatch.setattr(dropless, "expert_ffn", functools.partial(
            dropless.expert_ffn, use_kernel=True))
        cell = bench.load_cell(cell_name)
        config_file = cell["config_file"]
        depth = config_file["model"]["num_hidden_layers"]
        for holder in (config_file["model"], config_file):
            for key, value in list(holder.items()):
                if key == "num_hidden_layers":
                    holder[key] = layers
                elif isinstance(value, list) and len(value) == depth:
                    holder[key] = value[:layers]
        lines = list(probe.probe_cell(cell, one_chip, weight_layouts))
        _LAID_OUT[cell_name] = ({ln["program"]: ln for ln in lines[:-1]},
                                lines[-1])
    return _LAID_OUT[cell_name]


@pytest.mark.parametrize("cell_name,program,turned,also", [
    ("serve-kexaone-reasoning-out", "decode", [8192, 6144], "k_proj"),
    ("serve-kexaone-reasoning-out", "chunk_T512", [8192, 6144], "k_proj"),
    ("serve-mimo-hybrid-mixed", "decode", [4096, 12288], "k_proj"),
])
def test_no_program_relays_a_weight_the_engine_laid_out(
        monkeypatch, one_chip, cell_name, program, turned, also):
    """Over the tree as it lies every layer's ``q_proj`` kernel is copied
    into the other order on EVERY call (K-EXAONE: 100.7 MB a layer for a
    matmul of 64 rows; asserted, so that a compiler that stops doing so by
    itself is noticed and the mechanism can go), and over the tree the
    engine's rule lays out no program copies any parameter: the decode
    step that was asked, and the chunk that was not. The leaves the rule
    moves are the attention's ``q_proj`` and ``k_proj`` kernels, to
    ``major_to_minor`` (1, 0), and nothing else."""
    layers = 2
    programs, cell = _weight_layout_lines(monkeypatch, one_chip, cell_name,
                                          layers)
    parent, change = programs[program]["parent"], programs[program]["change"]
    relaid = [c for c in parent["copies"]
              if "q_proj" in c[1] and c[2] == turned]
    assert len(relaid) == layers, parent["copies"]
    assert parent["parameter_bytes_copied"] >= layers * 2 * turned[0] * \
        turned[1]
    assert change == {"parameter_bytes_copied": 0, "copies": []}
    moved = {m["leaf"].split("/", 1)[1] for m in cell["moved"]}
    assert moved == {"q_proj/kernel", f"{also}/kernel"}
    assert cell["leaves_moved"] == 2 * layers
    assert {tuple(m["major_to_minor"]) for m in cell["moved"]} == {(1, 0)}


# Ling-3.0-flash at its published widths: 32 KDA heads of 128 x 128 float32
# state a slot a layer, 7 KDA layers and 128 decode slots in the benchmark
# cell; a prefill chunk of 512 positions in 32 sub-chunks of 16; one latent
# layer of 32 heads over rows of 640 lanes; 64 held experts of 3 x 2560 x 768
def test_kda_state_update_kernel_is_in_place_and_named(one_chip):
    """The decode step's delta-rule update compiles for the v5e over the
    cell's whole state pool (1.9 GB of float32), the pool aliased to its
    output (no copy of it in the program), under the name the parked
    reader matches."""
    from deepspeed_tpu.ops import kda_state_update

    pattern = _reader_pattern("kda_decode_roofline_share")
    rows, heads, width = 128, 32, 128
    assert kda_state_update.kernel_serves(heads, width, width)

    def step(pool, slots, alpha, k, v, q, beta):
        return kda_state_update.state_update_kernel(
            pool, 3, slots, alpha, k, v, q, beta)

    f32 = jnp.float32
    vec = _s(one_chip, (rows, heads, width), f32)
    text = jax.jit(step, donate_argnums=0).lower(
        _s(one_chip, (7, 1 + rows, heads, width, width), f32),
        _s(one_chip, (rows,), jnp.int32), vec, vec, vec, vec,
        _s(one_chip, (rows, heads), f32)).compile().as_text()
    calls = _custom_calls(text)
    assert len(calls) == 1 and pattern.search(calls[0]), calls
    assert "f32[7,129,32,128,128]" in calls[0]
    # the pool goes in and comes out as one buffer: nothing copies it
    copies = [ln for ln in text.splitlines()
              if " copy(" in ln and "f32[7,129,32,128,128]" in ln]
    assert not copies, copies[:2]


def test_kda_chunk_kernel_at_a_prefill_chunks_shape(one_chip):
    """The chunk form at the cell's chunk of 512 positions: the terms by
    XLA (one triangular solve a call), the carry through the sub-chunks by
    the Pallas kernel, under the name the parked reader matches."""
    from deepspeed_tpu.ops import kda_chunk

    pattern = _reader_pattern("kda_chunk_roofline_share")
    t, heads, width = 512, 32, 128
    assert kda_chunk.kernel_serves(heads, width, width)

    def chunk(q, k, v, g, beta, state):
        return kda_chunk.kda_chunk(q, k, v, g, beta, state, use_kernel=True)

    f32 = jnp.float32
    vec = _s(one_chip, (1, t, heads, width), f32)
    text = _compiled_text(chunk, vec, vec, vec, vec,
                          _s(one_chip, (1, t, heads), f32),
                          _s(one_chip, (1, heads, width, width), f32))
    calls = [c for c in _custom_calls(text)]
    assert len(calls) == 1 and pattern.search(calls[0]), calls
    assert not _reader_pattern("kda_decode_roofline_share").search(calls[0])


def test_the_clamped_grouped_expert_kernel_compiles(one_chip):
    """The grouped matmul with the clamp on its SwiGLU (a layer whose
    ``expert_swiglu_limit_list`` entry is not 0) at Ling-3.0-flash's
    widths, a decode step's 128 rows, under the experts' reader's name."""
    from deepspeed_tpu.moe.dropless import expert_ffn

    pattern = _reader_pattern("expert_matmul_roofline_share")

    def layer(x, experts, weights, gate, up, down):
        return expert_ffn(x, experts, weights, gate, up, down,
                          first_expert=0, n_routed=512, use_kernel=True,
                          limit=4.0)

    text = _compiled_text(
        layer, _s(one_chip, (128, 2560)),
        _s(one_chip, (128, 8), jnp.int32),
        _s(one_chip, (128, 8), jnp.float32),
        _s(one_chip, (64, 2560, 768)), _s(one_chip, (64, 2560, 768)),
        _s(one_chip, (64, 768, 2560)))
    calls = _custom_calls(text)
    assert len(calls) == 1 and pattern.search(calls[0]), calls


# DeepSeek-V3.2 at its published widths: 128 heads of 128 + 64 over a
# latent row of 512 + 64 values in 640 lanes, a 512-token chunk against a
# table of 32,768 keys; the benchmark cell's sizes
def test_the_masked_chunk_attention_kernel_at_the_published_widths(one_chip):
    """The sparse attention's chunk kernel (eight heads x 512 keys a grid
    step: the tile's rows through the group's slice of ``W_kvb`` in VMEM,
    1 MB of float32 scores a head) compiles for the v5e at 128 heads under
    the name the parked reader matches, and is the program's one kernel."""
    from deepspeed_tpu.ops import dsa_sparse_attend

    pattern = _reader_pattern("dsa_attend_roofline_share")
    t, heads, keys = 512, 128, 32768

    def chunk(q_nope, q_pe, rows, w_kvb, mask, live):
        return dsa_sparse_attend.attend_masked(
            q_nope, q_pe, rows, w_kvb, mask, live, scale=0.1352)

    text = _compiled_text(
        chunk, _s(one_chip, (t, heads, 128)), _s(one_chip, (t, heads, 64)),
        _s(one_chip, (keys, 640)), _s(one_chip, (512, heads, 256)),
        _s(one_chip, (t, keys // 32), jnp.uint32),
        _s(one_chip, (), jnp.int32))
    calls = _custom_calls(text)
    assert len(calls) == 1 and pattern.search(calls[0]), calls
    assert "dsa_sparse_attend" in calls[0]


def test_the_selection_kernel_at_the_published_widths(one_chip, monkeypatch):
    """The chunk selection's kernel (64 queries' 32,768 keys held in VMEM
    as integers of the scores' order, 8 MB; the k-th settled by signed
    compares) compiles for the v5e at the cell's sizes under the name the
    trace shows, beside the one XLA pass that lays the mask."""
    from deepspeed_tpu.ops import attention, dsa_index_select

    t, keys, k = 512, 32768, 2048
    monkeypatch.setattr(attention, "_FORCE_DECODE_KERNEL", True)
    assert dsa_index_select.kernel_serves(t, keys)
    text = _compiled_text(
        lambda scores, could, live: dsa_index_select.select_mask(
            scores, could, k, live),
        _s(one_chip, (t, keys), jnp.float32), _s(one_chip, (t,), jnp.int32),
        _s(one_chip, (), jnp.int32))
    calls = _custom_calls(text)
    assert len(calls) == 1 and "dsa_index_select" in calls[0], calls


# ---------------------------------------------------------------------------
# Phi-4-mini-flash at its published widths: nine Mamba-1 layers of 5,120
# channels x 16 states (a float32 row of 40 lane groups a slot), eight
# window layers and ONE shared pool of 20 KV heads of 64, a query head
# keeping the values of TWO adjacent KV heads; the benchmark cell's 96 slots
# and 512-token chunks
@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_mamba1_kernels_compile_in_place_and_named(one_chip, program):
    """The decode step's state update on the pool (``[9, 97, 40, 16, 128]``
    float32) aliased to the kernel's output (no second 286 MB), ONE custom
    call under the name the parked ``mamba1_decode_roofline_share`` reads;
    and the chunk scan of a 512-token call, one kernel under the name
    ``mamba1_scan_roofline_share`` reads, with no array of ``T x channels x
    states``."""
    from deepspeed_tpu.ops import mamba1_scan as ms

    f32, i32 = jnp.float32, jnp.int32
    slots, c, n, groups = 96, 5120, 16, 40
    assert ms.kernel_serves(c, n) and ms.pool_row_shape(c, n) == (groups, n,
                                                                  128)
    if program == "decode":
        pool = (9, 1 + slots, groups, n, 128)
        compiled = jax.jit(
            lambda pool, rows, d, x, fresh, a, b, cc: ms.mamba1_state_update(
                pool, 3, rows, d, x, fresh, a, b, cc, use_kernel=True),
            donate_argnums=0).lower(
                _s(one_chip, pool, f32), _s(one_chip, (slots,), i32),
                _s(one_chip, (slots, c), f32), _s(one_chip, (slots, c), f32),
                _s(one_chip, (slots,), jnp.bool_),
                _s(one_chip, (groups, n, 128), f32),
                _s(one_chip, (slots, n), f32),
                _s(one_chip, (slots, n), f32)).compile()
        held = int(np.prod(pool)) * 4
        memory = compiled.memory_analysis()
        assert memory.alias_size_in_bytes >= held
        assert memory.temp_size_in_bytes < held // 10
        metric = "mamba1_decode_roofline_share"
    else:
        compiled = jax.jit(
            lambda x, d, a, b, cc, state: ms.mamba1_chunk_scan(
                x, d, a, b, cc, state, use_kernel=True)).lower(
                _s(one_chip, (1, 512, c), f32), _s(one_chip, (1, 512, c), f32),
                _s(one_chip, (groups, n, 128), f32),
                _s(one_chip, (1, 512, n), f32), _s(one_chip, (1, 512, n), f32),
                _s(one_chip, (1, groups, n, 128), f32)).compile()
        # x, delta, y and the broadcast columns: nothing of 512 x 5120 x 16
        assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
        metric = "mamba1_scan_roofline_share"
    calls = _custom_calls(compiled.as_text())
    assert len(calls) == 1 and _reader_pattern(metric).search(calls[0]), calls


@pytest.mark.parametrize("kind", ["global", "window"])
def test_hybrid_decode_kernel_with_a_value_group_of_two(one_chip, kind):
    """Differential attention's form of the hybrid kernel at the cell's
    shapes (40 query heads over 20 KV heads of 64, rows of 1,280 lanes, a
    table of 224 blocks and a ring of 17): a head keeps the 128 value lanes
    of its KV pair, and the call is still the ONE instruction
    ``hybrid_decode_roofline_share`` matches."""
    from deepspeed_tpu.ops.hybrid_decode_attention import (
        decode_attention_hybrid, hybrid_plan)

    slots, bs = 96, 32
    window = kind == "window"
    layers, blocks, per_row = ((8, 1 + slots * 17, 17) if window
                               else (1, 11265, 224))
    assert hybrid_plan(bs, 1280, 1280, per_row).tile_blocks == 16

    def step(q, k, v, tables, lengths):
        with jax.named_scope("attn._hybrid_kv_attend"):
            return decode_attention_hybrid(
                q, k, v, tables, lengths, layers - 1, kv_heads=20,
                window=512 if window else 0, ring=window, value_group=2)

    compiled = jax.jit(step).lower(
        _s(one_chip, (slots, 1, 40, 64)),
        _s(one_chip, (layers, blocks, bs, 1280)),
        _s(one_chip, (layers, blocks, bs, 1280)),
        _s(one_chip, (slots, per_row), jnp.int32),
        _s(one_chip, (slots,), jnp.int32)).compile()
    assert compiled.output_shardings is not None
    calls = _custom_calls(compiled.as_text())
    pattern = _reader_pattern("hybrid_decode_roofline_share")
    assert len(calls) == 1 and pattern.search(calls[0]), calls
    assert "bf16[96,1,40,128]" in calls[0]
