"""Where served weights lie (PR 53): ``ServingEngine._lay_out_weights`` and
``serving/weight_layouts.py`` on the CPU.

The CPU's compiler asks for every leaf as it lies, so the engine's own
question moves nothing here; the cases that move leaves stub the answer
(``_asked_weight_formats``) to a transposed layout for two leaves, which is
what the chip's compiler answers for K-EXAONE's ``q_proj`` / ``k_proj``
(``tests/unit/test_chip_compile.py`` holds that answer at the published
widths, the benchmark cell its effect).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.layout import Format, Layout

import deepspeed_tpu
from deepspeed_tpu.models.exaone_moe import (ExaoneMoeConfig,
                                             ExaoneMoeForCausalLM)
from deepspeed_tpu.parallel.topology import MeshTopology, reset_topology
from deepspeed_tpu.serving import ServingEngine, weight_layouts
from deepspeed_tpu.telemetry import compile_watch

MOVED = ("layers_0_attn/q_proj/kernel", "layers_2_attn/k_proj/kernel")
# first dimension minor, as a compiled program names it on this backend
# (no tiling; a hand-written ``tiling=None`` is equal to no array's layout)
TURNED = Layout(major_to_minor=(1, 0), tiling=())
SERVING = {"decode_slots": 3, "block_size": 4, "max_model_len": 64,
           "prefill_chunk_tokens": 8}


@pytest.fixture(scope="module")
def tiny():
    cfg = ExaoneMoeConfig.tiny(dtype=jnp.float32)
    params = ExaoneMoeForCausalLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, params


def _transposed(monkeypatch, names=MOVED):
    """The chip's answer, stubbed: the named leaves asked for with their
    first dimension minor, every other leaf as it lies."""
    def asked(self):
        def one(path, leaf):
            if weight_layouts.leaf_name(path) in names:
                return Format(TURNED, leaf.sharding)
            return leaf.format
        return jax.tree_util.tree_map_with_path(one, self.engine.params)

    monkeypatch.setattr(ServingEngine, "_asked_weight_formats", asked)


def _one_device():
    return MeshTopology(devices=jax.devices()[:1])


def _engine(cfg, params, **serving):
    reset_topology()
    # one device, as a serving chip holds its weights (over the suite's
    # eight CPU devices a tree is replicated, and the engine leaves it be);
    # a copy: the engine takes a tree that lies on its device as it lies
    return ServingEngine(deepspeed_tpu.init_inference(
        ExaoneMoeForCausalLM(cfg), dtype=cfg.dtype, mesh=_one_device(),
        params=jax.tree_util.tree_map(jnp.array, params),
        serving={**SERVING, **serving}))


def _prompts(cfg):
    rng = np.random.default_rng(5)
    return [rng.integers(1, cfg.vocab_size, n).tolist() for n in (37, 6, 19)]


def _serve(srv, cfg):
    reqs = [srv.submit(p, max_new_tokens=n)
            for p, n in zip(_prompts(cfg), (14, 20, 9))]
    srv.drain()
    return [r.tokens for r in reqs]


def _flat(tree):
    return {weight_layouts.leaf_name(path): leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def test_the_cpus_compiler_asks_for_every_leaf_as_it_lies(tiny):
    """The engine's own question, unstubbed: one compile of the decode
    program with the layouts left open, an answer for every leaf the
    program reads, and nothing to move on this backend."""
    cfg, params = tiny
    srv = _engine(cfg, params)
    try:
        leaves = len(jax.tree_util.tree_leaves(params))
        assert srv.stats()["weight_layouts"] == {
            "asked_by": "serving_decode", "leaves_moved": 0,
            "bytes_moved": 0, "leaves": leaves}
        asked = _flat(srv._asked_weight_formats())
        lying = _flat(srv.engine.params)
        assert set(asked) == set(lying)
        assert all(asked[k] is None or asked[k].layout == lying[k].format.layout
                   for k in lying)
    finally:
        srv.destroy()


def test_the_program_that_was_asked_is_the_program_that_decodes(
        tiny, monkeypatch):
    """One lowering and one compile for asking and for running: the decode
    program is the executable that answered, it serves what ``jax.jit`` of
    the same function serves, and an argument it refuses (a weight that
    lies otherwise than it was compiled for) hands the program over to
    ``jax.jit``, which compiles for the argument as it lies."""
    cfg, params = tiny
    srv = _engine(cfg, params)
    try:
        asked = srv._decode_asked
        assert asked is not None
        got = _serve(srv, cfg)
        assert isinstance(srv._decode_fn, weight_layouts.AskedProgram)
        assert srv._decode_fn._compiled is asked and srv._decode_asked is None
        name = MOVED[0].split("/")
        tree = jax.tree_util.tree_map(lambda x: x, srv.engine.params)
        leaf = tree[name[0]][name[1]][name[2]]
        leaves = [leaf]
        weight_layouts.lay_out(leaves, [Format(TURNED, leaf.sharding)])
        tree[name[0]][name[1]][name[2]] = leaves[0]
        srv.engine.params = tree
        assert _serve(srv, cfg) == got
        assert srv._decode_fn._compiled is None          # handed over
    finally:
        srv.destroy()
    # the stub's answer keeps no executable: ``jax.jit`` alone, same tokens
    _transposed(monkeypatch, names=())
    plain = _engine(cfg, params)
    try:
        assert _serve(plain, cfg) == got
        assert not isinstance(plain._decode_fn, weight_layouts.AskedProgram)
    finally:
        plain.destroy()


def test_placed_leaves_hold_the_same_values_and_serve_the_same_tokens(
        tiny, monkeypatch):
    """Two leaves laid out transposed at start-up: every leaf reads bit
    for bit as before (``np.asarray`` sees the canonical array), the two
    lie as asked and the others as they did, chunked prefill and decode
    through ring and table serve the unplaced engine's tokens, and
    ``stats()`` counts the two and their bytes."""
    cfg, params = tiny
    plain = _engine(cfg, params)
    try:
        want = _serve(plain, cfg)
    finally:
        plain.destroy()
    _transposed(monkeypatch)
    srv = _engine(cfg, params)
    try:
        before, after = _flat(params), _flat(srv.engine.params)
        assert set(before) == set(after)
        for name, leaf in after.items():
            assert leaf.shape == before[name].shape
            assert leaf.dtype == before[name].dtype
            assert np.array_equal(np.asarray(leaf), np.asarray(before[name]))
            assert tuple(leaf.format.layout.major_to_minor) == (
                (1, 0) if name in MOVED else tuple(range(leaf.ndim)))
        assert srv.stats()["weight_layouts"] == {
            "asked_by": "serving_decode", "leaves_moved": 2,
            "bytes_moved": sum(before[n].nbytes for n in MOVED),
            "leaves": len(before)}
        assert _serve(srv, cfg) == want
        # (the window survived a reset of the window's counters)
        srv.reset_stats()
        assert srv.stats()["weight_layouts"]["leaves_moved"] == 2
    finally:
        srv.destroy()


@pytest.mark.parametrize("answer", ["stubbed", "the compiler's"])
def test_a_leaf_replaced_as_the_harness_does_serves_on_without_a_compile(
        tiny, monkeypatch, answer):
    """``perfbench/jobs/serve_counted_exaone_moe.py:setup`` puts new
    ``router_bias`` leaves into a warm engine by ``jax.device_put(bias,
    old.sharding)``: no program pins a format, so the compiled programs
    take them as they come, and the backend compiles nothing more (the
    event ``perfbench/run.py`` counts across the window). With the
    compiler's own answer the decode program is the executable that was
    asked, which takes the new leaves as ``jax.jit`` does."""
    cfg, params = tiny
    moved = MOVED if answer == "stubbed" else ()
    if moved:
        _transposed(monkeypatch)
    srv = _engine(cfg, params)
    try:
        compile_watch.install()
        _serve(srv, cfg)                                    # the warm-up
        old = srv.engine.params
        tree = jax.tree_util.tree_map_with_path(
            lambda path, x: jax.device_put(
                np.asarray(x) + np.float32(0.25), x.sharding)
            if path[-1].key == "router_bias" else x, old)
        assert sum(a is not b for a, b in zip(
            jax.tree_util.tree_leaves(tree),
            jax.tree_util.tree_leaves(old))) == cfg.sparse_layers
        srv.engine.params = tree
        before = compile_watch.snapshot()["backend_compiles"]
        tokens = _serve(srv, cfg)
        assert compile_watch.snapshot()["backend_compiles"] == before
        assert all(tokens)
        kept = _flat(srv.engine.params)
        assert all(tuple(kept[n].format.layout.major_to_minor) == (1, 0)
                   for n in moved)
        if not moved:
            assert srv._decode_fn._compiled is not None   # never refused
    finally:
        srv.destroy()


@pytest.mark.parametrize("how,kwargs", [
    ("tp_size 2", {"dtype": "fp32", "tensor_parallel": {"tp_size": 2}}),
    ("eight devices", {"dtype": "fp32"}),
    ("int8 weights", {"dtype": "int8", "one_device": True}),
    ("a proposer", {"dtype": "fp32", "one_device": True, "serving_extra": {
        "speculative": {"num_speculative_tokens": 2}}}),
])
def test_not_engaged_where_the_tree_is_not_plain_arrays_on_one_device(
        how, kwargs, monkeypatch):
    """A tree split over two devices or replicated over the suite's
    eight, a quantised tree and a proposer: the decode
    program is not asked at all (the stub would raise), nothing moves, and
    the engine serves as before."""
    def never(self):
        raise AssertionError(f"{how}: the decode program was asked")

    monkeypatch.setattr(ServingEngine, "_asked_weight_formats", never)
    kwargs = dict(kwargs)
    extra = kwargs.pop("serving_extra", {})
    if kwargs.pop("one_device", False):
        kwargs["mesh"] = _one_device()
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel

    reset_topology()
    srv = ServingEngine(deepspeed_tpu.init_inference(
        GPT2LMHeadModel(GPT2Config.tiny(dtype=jnp.float32)), seed=0,
        serving={"block_size": 8, "decode_slots": 3, **extra}, **kwargs))
    try:
        counted = srv.stats()["weight_layouts"]
        assert counted["asked_by"] is None and counted["leaves"] > 0
        assert counted["leaves_moved"] == counted["bytes_moved"] == 0
        toks = srv.generate_batch([[5, 6, 7], [9, 10, 11, 12]],
                                  max_new_tokens=3)
        assert all(t is not None and len(t) == 3 for t in toks)
    finally:
        srv.destroy()
        reset_topology()


def test_lay_out_moves_only_what_lies_otherwise_and_in_place():
    x = jnp.arange(12.0).reshape(3, 4)
    y = jnp.arange(6.0).reshape(2, 3)
    turned = Format(TURNED, x.sharding)
    leaves = [x, y, x + 1]
    moved = weight_layouts.lay_out(leaves, [turned, None, (x + 1).format])
    assert moved == [0] and leaves[1] is y
    assert tuple(leaves[0].format.layout.major_to_minor) == (1, 0)
    assert np.array_equal(np.asarray(leaves[0]), np.asarray(x))
    # and again: it lies as asked already
    assert weight_layouts.lay_out(leaves, [turned, None, None]) == []


def test_lay_out_keeps_the_persistent_cache_out_of_the_relaying(tmp_path):
    """What a warm start found on the chip (PERF.md, PR 53), in one
    process: the re-laying program handed back by the persistent cache
    gives an array that says it lies in the default layout (and on the CPU
    reads as another matrix). ``lay_out`` compiles it outside the cache,
    so a second engine start lays its leaves out as the first did."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    compilation_cache.reset_cache()
    try:
        x = jax.device_put(jnp.arange(12.0).reshape(3, 4), jax.devices()[0])
        turned = Format(TURNED, x.sharding)
        for start in range(2):
            leaves = [x]
            assert weight_layouts.lay_out(leaves, [turned]) == [0]
            assert leaves[0].format.layout == TURNED
            doubled = jax.jit(lambda a: a * 2)(leaves[0])
            assert np.array_equal(np.asarray(doubled), 2 * np.asarray(x))
            jax.clear_caches()    # the next start: only the disk remembers
        # (the cache was on all the while: the consumer above is in it)
        assert any(tmp_path.iterdir())
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
        compilation_cache.reset_cache()


# the chip's compiler, K-EXAONE's decode step (PERF.md, PR 53): a weight
# bitcast and copied, one only sliced, one copied outright, and a copy of
# the pool (an argument, not a weight)
_TEXT = """
HloModule jit_serving_decode

%fused (param_0: bf16[64,64,128]) -> bf16[64,64,128] {
  %param_0 = bf16[64,64,128]{2,1,0} parameter(0)
  ROOT %copy.9 = bf16[64,64,128]{2,0,1} copy(%param_0)
}

ENTRY %main.94 (qparams__layers_0_attn____q_proj____kernel__.1: bf16[6144,8192]) -> bf16[64] {
  %qparams__layers_0_attn____q_proj____kernel__.1 = bf16[6144,8192]{1,0:T(8,128)(2,1)} parameter(5), sharding={replicated}
  %qparams__layers_0_attn____k_proj____kernel__.1 = bf16[6144,1024]{1,0:T(8,128)(2,1)} parameter(2), sharding={replicated}
  %qparams__layers_1_attn____k_proj____kernel__.1 = bf16[6144,1024]{1,0:T(8,128)(2,1)} parameter(13)
  %cache__key_pool__.1 = bf16[4,321,32,1024]{3,2,1,0:T(8,128)(2,1)} parameter(0)
  %bitcast.571 = bf16[8192,6144]{0,1:T(8,128)(2,1)} bitcast(%qparams__layers_0_attn____q_proj____kernel__.1)
  %copy.176 = bf16[8192,6144]{1,0:T(8,128)(2,1)} copy(%bitcast.571), metadata={op_name="jit(serving_decode)/q_proj/dot_general"}
  %copy.177 = bf16[6144,1024]{0,1:T(8,128)(2,1)} copy(%qparams__layers_0_attn____k_proj____kernel__.1)
  %slice-start.56 = ((bf16[6144,1024]{1,0}), bf16[1536,1024]{1,0:S(1)}, s32[]{:S(2)}) slice-start(%qparams__layers_1_attn____k_proj____kernel__.1), slice={[0:1536], [0:1024]}
  %copy.293 = bf16[4,321,32,1024]{3,2,1,0:T(8,128)(2,1)} copy(%cache__key_pool__.1)
  ROOT %fusion.1 = bf16[64]{0} fusion(%copy.176, %copy.177), kind=kLoop, calls=%fused
}
"""


def test_parameter_copies_reads_a_compiled_programs_text():
    copies = weight_layouts.parameter_copies(_TEXT, "qparams")
    assert [(c.copy, c.parameter.split("____")[1], c.dims, c.bytes)
            for c in copies] == [
        ("copy.176", "q_proj", (8192, 6144), 8192 * 6144 * 2),
        ("copy.177", "k_proj", (6144, 1024), 6144 * 1024 * 2)]
    assert weight_layouts.parameter_copies(_TEXT, "cache")[0].copy == (
        "copy.293")
    assert weight_layouts.parameter_copies(_TEXT, "params") == []
