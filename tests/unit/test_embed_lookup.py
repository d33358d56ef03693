"""The token lookup of the paged serving programs
(``models/decode_utils.py``): one algorithm, two access patterns.

- ``lookup_columns`` (the transposed view, a lane-aligned chunk a token, one
  lane selected) gives the bits of ``table[ids]``, whatever lies in the
  table;
- ``lookup_form`` chooses columns only for a table that really lies with
  the vocabulary minor, on one device, as a plain array, and for a token
  count under the crossover;
- the serving engine hands its programs that choice, and
  ``stats()["attention_paths"]`` names the form each traced program took.

What the chip's compiler makes of the two forms at GPT-2 XL's widths is in
``test_chip_compile.py``.
"""

from unittest import mock

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.layout import Format, Layout
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepspeed_tpu.models import decode_utils
from deepspeed_tpu.models.decode_utils import (LOOKUP_COLUMNS_MAX_TOKENS,
                                               embed_lookup, lookup_columns,
                                               lookup_form, vocab_is_minor)
from deepspeed_tpu.ops.attention import dispatch_counts

WIDTH = 24


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """The persistent compilation cache keys a CPU program without its
    arguments' layouts: it hands the row-major program back for a
    vocabulary-minor table. Only these tests lay a table out by hand."""
    from deepspeed_tpu.utils.compat import compilation_cache_off

    with compilation_cache_off():
        yield


def _bits(x):
    x = np.asarray(x)
    return x.view(f"uint{8 * x.dtype.itemsize}")


def _table(vocab, dtype, seed=0):
    """A random table with an ``inf`` row, a ``nan`` row and a ``-0.0``
    row at the ids the cases look up."""
    rng = np.random.default_rng(seed)
    t = rng.standard_normal((vocab, WIDTH)).astype(np.float32)
    t[127 % vocab] = np.inf
    t[128 % vocab] = np.nan
    t[vocab - 1] = -0.0
    t[0, ::2] = -np.inf
    return jnp.asarray(t, dtype)


def _vocab_minor(x):
    """``x`` laid with its first dimension minor, as the chip lays a
    table whose width is no whole number of registers."""
    return jax.device_put(x, Format(Layout(major_to_minor=(1, 0)),
                                    x.sharding))


@pytest.mark.parametrize("shape", ["B1", "BT"])
@pytest.mark.parametrize("vocab", [50257, 50176, 100],
                         ids=["gpt2", "whole-registers", "under-a-register"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_columns_give_the_bits_of_rows(dtype, vocab, shape):
    """ids at both ends of the first chunk, across its edge, and in the
    last chunk, which is clamped to the table's edge where the vocabulary
    is no multiple of 128."""
    table = _table(vocab, dtype)
    ids = np.asarray([0, 127, 128, vocab - 129, vocab - 1], np.int32) % vocab
    ids = ids[:, None] if shape == "B1" else np.stack(
        [ids, ids[::-1], (ids + 1) % vocab], axis=1)
    want = table[ids]
    assert np.isinf(np.asarray(want, np.float32)).any()
    assert np.isnan(np.asarray(want, np.float32)).any()
    for t in (table, _vocab_minor(table)):
        got = jax.jit(lookup_columns)(t, ids)
        assert got.dtype == want.dtype and got.shape == ids.shape + (WIDTH,)
        np.testing.assert_array_equal(_bits(got), _bits(want))
        np.testing.assert_array_equal(
            _bits(jax.jit(lambda a, i: embed_lookup(a, i, "rows"))(t, ids)),
            _bits(want))


@pytest.mark.parametrize("tokens", [5, 130], ids=["one-tile", "two-tiles"])
@pytest.mark.parametrize("vocab", [50257, 50176, 100],
                         ids=["gpt2", "whole-registers", "under-a-register"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_the_kernel_gives_the_bits_of_rows(dtype, vocab, tokens, monkeypatch):
    """The Pallas form of the column read (``ops/embed_lookup.py``), in the
    TPU interpreter: the same ids, the same ``inf``, ``nan`` and ``-0.0``
    rows, and more tokens than one 128-lane output tile holds."""
    from deepspeed_tpu.ops import attention, embed_lookup as kernel
    from deepspeed_tpu.utils.compat import tpu_interpret_mode

    table = _table(vocab, dtype)
    assert kernel.kernel_serves(table)
    rng = np.random.default_rng(tokens)
    ids = np.concatenate([
        np.asarray([0, 127, 128, vocab - 129, vocab - 1]) % vocab,
        rng.integers(0, vocab, tokens - 5)]).astype(np.int32)[:, None]
    want = _bits(table[ids])
    calls, real = [], kernel.lookup_columns_kernel
    monkeypatch.setattr(kernel, "lookup_columns_kernel",
                        lambda *a: calls.append(1) or real(*a))
    # through the helper, as a TPU program takes it
    monkeypatch.setattr(attention, "use_decode_kernel", lambda: True)
    with tpu_interpret_mode():
        got = jax.block_until_ready(
            jax.jit(lambda t, i: embed_lookup(t, i, "columns"))(table, ids))
    assert calls == [1]
    np.testing.assert_array_equal(_bits(got), want)


def test_the_kernel_leaves_other_types_to_the_loop(monkeypatch):
    """A float16 table would not survive the kernel's way through
    float32's bits: it keeps the XLA loop, on the chip too."""
    from deepspeed_tpu.ops import attention, embed_lookup as kernel

    monkeypatch.setattr(attention, "use_decode_kernel", lambda: True)
    monkeypatch.setattr(kernel, "lookup_columns_kernel", lambda *a: 1 / 0)
    table = _table(300, jnp.float16)
    assert not kernel.kernel_serves(table)
    ids = jnp.asarray([[0], [127], [128], [299]])
    np.testing.assert_array_equal(_bits(lookup_columns(table, ids)),
                                  _bits(table[ids]))


def test_embed_lookup_counts_the_form_it_traced():
    table, ids = _table(300, jnp.float32), jnp.asarray([[3], [299]])
    before = dispatch_counts()
    for form in ("rows", "columns", "columns"):
        np.testing.assert_array_equal(
            _bits(embed_lookup(table, ids, form)), _bits(table[ids]))
    after = dispatch_counts()
    took = {k: after[k] - before.get(k, 0) for k in after}
    assert took["embed_lookup_rows"] == 1
    assert took["embed_lookup_columns"] == 2


# ---------------------------------------------------------------------------
# the choice, from what can be observed
def _cases():
    few = 32
    row_major = jnp.zeros((300, WIDTH), jnp.bfloat16)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
    return {
        "row-major": (lambda: row_major, few, "rows"),
        "vocab-minor": (lambda: _vocab_minor(row_major), few, "columns"),
        "at-the-crossover": (lambda: _vocab_minor(row_major),
                             LOOKUP_COLUMNS_MAX_TOKENS, "columns"),
        "over-the-crossover": (lambda: _vocab_minor(row_major),
                               LOOKUP_COLUMNS_MAX_TOKENS + 1, "rows"),
        "a-768-token-prefill": (lambda: _vocab_minor(row_major), 768, "rows"),
        "sharded": (lambda: jax.device_put(
            row_major, NamedSharding(mesh, P("tp", None))), few, "rows"),
        "replicated-on-two": (lambda: jax.device_put(
            row_major, NamedSharding(mesh, P())), few, "rows"),
        # what ``InferenceEngine._quantize_weights`` leaves in the tree
        "quantised": (lambda: {"q": jnp.zeros((300, WIDTH), jnp.int8),
                               "scale": jnp.ones((300,), jnp.float32)},
                      few, "rows"),
        "host-array": (lambda: np.zeros((300, WIDTH), np.float32), few,
                       "rows"),
        "no-table": (lambda: None, few, "rows"),
        # a table known by shape alone: no layout, so rows; with the
        # format a compile gave its parameter, that format decides
        "described": (lambda: jax.ShapeDtypeStruct(
            (300, WIDTH), jnp.bfloat16, sharding=row_major.sharding), few,
            "rows"),
        "described-vocab-minor": (lambda: jax.ShapeDtypeStruct(
            (300, WIDTH), jnp.bfloat16, sharding=Format(
                Layout(major_to_minor=(1, 0)), row_major.sharding)), few,
            "columns"),
    }


_LIE_VOCAB_MINOR = ("vocab-minor", "at-the-crossover", "over-the-crossover",
                    "a-768-token-prefill", "described-vocab-minor")


# (the names spelled out: no device is touched while the file is collected)
@pytest.mark.parametrize("case", _LIE_VOCAB_MINOR + (
    "row-major", "sharded", "replicated-on-two", "quantised", "host-array",
    "no-table", "described"))
def test_lookup_form_reads_layout_placement_and_count(case):
    make, tokens, want = _cases()[case]
    table = make()
    assert lookup_form(table, tokens) == want
    assert vocab_is_minor(table) == (case in _LIE_VOCAB_MINOR)


def test_a_tracer_reads_as_rows():
    """Inside a program the table has no layout to read: the choice is
    made outside, where the engine holds the array."""
    seen = []
    jax.jit(lambda t: seen.append(lookup_form(t, 1)) or t)(
        _vocab_minor(jnp.zeros((300, WIDTH))))
    assert seen == ["rows"]


# ---------------------------------------------------------------------------
# a tiny served model
_SERVING = {"block_size": 8, "decode_slots": 3, "default_max_new_tokens": 4}


def _served(layout, monkeypatch=None, max_tokens=None, **serving):
    """Tokens and the lookup paths of a tiny GPT-2 served with its table
    row-major (as the CPU lays it) or vocabulary-minor."""
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    from deepspeed_tpu.parallel.topology import MeshTopology, reset_topology
    from deepspeed_tpu.serving import ServingEngine

    reset_topology()
    if max_tokens is not None:
        monkeypatch.setattr(decode_utils, "LOOKUP_COLUMNS_MAX_TOKENS",
                            max_tokens)
    cfg = GPT2Config.tiny(dtype=jnp.float32)
    # one device, as a serving chip holds its table (on the suite's eight
    # CPU devices the table is replicated, which reads as rows)
    engine = deepspeed_tpu.init_inference(
        GPT2LMHeadModel(cfg), dtype="fp32", seed=3,
        mesh=MeshTopology(devices=jax.devices()[:1]),
        serving={**_SERVING, **serving})
    if layout == "vocab-minor":
        engine.params["wte"] = _vocab_minor(engine.params["wte"])
    # the chip's compiler, asked how the weights should lie (PR 53), leaves
    # the table as it lies (``tools/probe_weight_layouts.py``: XL's decode
    # program asks ``wpe`` alone to move); the CPU's names row-major for
    # every leaf, and the engine would put the hand-laid table back
    with mock.patch.object(
            ServingEngine, "_asked_weight_formats",
            lambda self: jax.tree_util.tree_map(lambda x: x.format,
                                                self.engine.params)):
        srv = ServingEngine(engine)
    # (building the engine traces the model's ``init`` for the pool's
    # shapes: one ``rows`` that is no program)
    before = dispatch_counts()
    try:
        rng = np.random.default_rng(5)
        prompts = [rng.integers(1, 256, n) for n in (5, 11, 3)]
        toks = srv.generate_batch(prompts, max_new_tokens=4)
        paths = srv.stats()["attention_paths"]
    finally:
        srv.destroy()
    took = {k: paths[k] - before.get(k, 0) for k in paths
            if k.startswith("embed_lookup_")}
    return toks, {k: v for k, v in took.items() if v}


def test_served_model_names_the_form_each_program_took(monkeypatch):
    """Row-major (every table on the CPU): rows everywhere. Vocabulary
    minor: the decode program (3 tokens) reads columns and so does the
    8-token prefill bucket, the 16-token bucket is over this test's
    crossover and reads rows; the served tokens are the same."""
    want, paths = _served("row-major")
    assert set(paths) == {"embed_lookup_rows"}
    got, paths = _served("vocab-minor", monkeypatch, max_tokens=8)
    assert got == want
    # decode + the 8-token bucket; the 16-token bucket
    assert paths == {"embed_lookup_columns": 2, "embed_lookup_rows": 1}


def test_chunked_prefill_and_verify_choose_by_their_own_counts(monkeypatch):
    """A chunk program looks up its chunk's tokens and the speculative
    verify program slots x (k + 1): each is held to the crossover by its
    own count."""
    spec = {"speculative": {"num_speculative_tokens": 2}}
    want, _ = _served("row-major", prefill_chunk_tokens=8, **spec)
    # verify: 3 slots x 3 rows = 9 tokens; a chunk: 8
    got, paths = _served("vocab-minor", monkeypatch, max_tokens=8,
                         prefill_chunk_tokens=8, **spec)
    assert got == want
    assert paths.get("embed_lookup_columns") and paths.get(
        "embed_lookup_rows")
    got, paths = _served("vocab-minor", monkeypatch, max_tokens=9,
                         prefill_chunk_tokens=8, **spec)
    assert got == want
    assert set(paths) == {"embed_lookup_columns"}


def test_a_quantised_table_keeps_rows():
    """int8 weights: the program rebuilds the table from ``q`` and
    ``scale``, so there is no array whose layout could be read."""
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    from deepspeed_tpu.parallel.topology import reset_topology
    from deepspeed_tpu.serving import ServingEngine

    reset_topology()
    engine = deepspeed_tpu.init_inference(
        GPT2LMHeadModel(GPT2Config.tiny(dtype=jnp.float32)), dtype="int8",
        seed=3, serving=_SERVING)
    srv = ServingEngine(engine)
    try:
        assert not vocab_is_minor(engine.params["wte"])
        before = dispatch_counts()
        srv.generate_batch([np.arange(1, 6)], max_new_tokens=2)
        after = dispatch_counts()
    finally:
        srv.destroy()
    assert after.get("embed_lookup_columns", 0) == before.get(
        "embed_lookup_columns", 0)
    assert after["embed_lookup_rows"] > before.get("embed_lookup_rows", 0)


# ---------------------------------------------------------------------------
# the train program never sees any of it
@pytest.mark.parametrize("config", ["gpt2-medium", "gpt2-xl"])
def test_the_train_program_is_the_plain_lookups(config, monkeypatch):
    """The lowered loss + grad program of the benchmark's train cells'
    model configs (bf16, dots-remat, scanned; ``train-medium-1chip`` at its
    8 x 1024 batch) is, character for character, the one whose lookup is
    written ``wte[input_ids]`` as it was before the helper: no transposed
    view, no loop. (Across PR 42 the medium program's text is 106,029
    characters, sha256 c3fe3e27...2dca17e2 on both sides: CHANGES.md.)"""
    import json
    import os

    from deepspeed_tpu.models import gpt2
    from perfbench.families import gpt2 as family

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "perfbench", "configs",
                           f"{config}.json")) as f:
        config_file = json.load(f)
    batch = {"input_ids": jnp.zeros((8, 1024), jnp.int32)}

    def lowered():
        model = family.training_model(config_file, jnp.bfloat16, "dots")
        params = jax.eval_shape(lambda r: model.init(r, batch)["params"],
                                jax.random.PRNGKey(0))
        return jax.jit(jax.value_and_grad(
            lambda p, b: model.loss_fn(p, b, None))).lower(
                params, batch).as_text()

    ours = lowered()
    monkeypatch.setattr(gpt2, "embed_lookup",
                        lambda table, ids, form="rows": table[ids])
    assert lowered() == ours
