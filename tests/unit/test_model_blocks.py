"""One set of decoder blocks (``models/blocks.py``): the import graph of the
served families read from the files' ASTs, their parameter trees held to
literals recorded at the parent of PR 48 (a renamed scope or a reordered
``self.param`` shows here, not as ``correct`` false on the chip), and the
contract with ``ServingEngine`` written once."""

import ast
import hashlib
import importlib
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import blocks

ROOT = pathlib.Path(__file__).resolve().parents[2]
MODELS = ROOT / "deepspeed_tpu" / "models"
# family -> config, module, the slots' keyword, the published configuration
FAMILIES = {
    "mimo_v2": ("MiMoV2Config", "MiMoV2ForCausalLM", "ring_slots",
                "mimo-v2.5-ep16"),
    "lfm2_moe": ("Lfm2MoeConfig", "Lfm2MoeForCausalLM", "state_slots",
                 "lfm2-8b-a1b-l14"),
    "deepseek_v2": ("DeepseekV2Config", "DeepseekV2ForCausalLM", None,
                    "deepseek-v2-lite-l6"),
}
# recorded at commit 1c0e8de (the parent of PR 48) by the functions below:
# sha256[:16] of the sorted "path shape dtype" lines of every collection,
# and over the parameters in path order sum_k (k + 1) * mean(x_k's first 16
# values) and sum_k (k + 1) * mean(|x_k|), from PRNGKey(0)
PARENT = {
    "mimo_v2": {"tiny": "712a84d920aa5929", "paged": "7c07ca335bee8941",
                "published": "9d1ba9924ccf7656",
                "values": (680.1739034087093, 904.1009290576002)},
    "lfm2_moe": {"tiny": "91641f1b17788f33", "paged": "5778801e0ce4cdc7",
                 "published": "eb4f6c8b670c9433",
                 "values": (588.4205612300077, 652.1512898514513)},
    "deepseek_v2": {"tiny": "fe81bed4a5233b54", "paged": "d580e69c2f9fd706",
                    "published": "42c0f51cbc937378",
                    "values": (361.69168401900424, 381.7692541634659)},
}


def _family(name):
    cfg, module, knob, published = FAMILIES[name]
    mod = importlib.import_module(f"deepspeed_tpu.models.{name}")
    return getattr(mod, cfg), getattr(mod, module), knob, published


def _lines(tree):
    return sorted(f"{jax.tree_util.keystr(p)} {tuple(x.shape)} "
                  f"{jnp.dtype(x.dtype).name}"
                  for p, x in jax.tree_util.tree_leaves_with_path(tree))


def structure(tree) -> str:
    return hashlib.sha256("\n".join(_lines(tree)).encode()).hexdigest()[:16]


def values(params):
    leaves = sorted(jax.tree_util.tree_leaves_with_path(params),
                    key=lambda px: jax.tree_util.keystr(px[0]))
    leaves = [np.asarray(x, np.float64).reshape(-1) for _, x in leaves]
    return (sum((k + 1) * x[:16].mean() for k, x in enumerate(leaves)),
            sum((k + 1) * np.abs(x).mean() for k, x in enumerate(leaves)))


def _paged_init(name):
    Config, Module, knob, _ = _family(name)
    cfg = Config.tiny().for_paged_decode(
        13, 4, return_routed=True, **({knob: 3} if knob else {}))
    state = knob and cfg.paged_slot_state_for(4)
    paging = {"block_tables": jnp.zeros(
                  (3, 4 + (state["entries"] if state else 0)), jnp.int32),
              "lengths": jnp.zeros((3,), jnp.int32),
              "num_valid": jnp.ones((3,), jnp.int32), "prefill": False}
    return Module(cfg).init(jax.random.PRNGKey(0),
                            jnp.zeros((3, 1), jnp.int32), paging=paging)


# ---------------------------------------------------------------------------
# (a) the import graph: arrows point one way

def _imports(path):
    """Every module a file imports, nested imports included, with the
    names taken from it (``from a import b`` may name the module a.b)."""
    found = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            found.update(alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            found.add(node.module)
            found.update(f"{node.module}.{alias.name}" for alias in node.names)
    return found


@pytest.mark.parametrize("name", FAMILIES)
def test_a_family_imports_no_other_family_and_not_llama(name):
    banned = {f"deepspeed_tpu.models.{other}"
              for other in (*FAMILIES, "llama") if other != name}
    assert not _imports(MODELS / f"{name}.py") & banned
    assert "deepspeed_tpu.models.blocks" in _imports(MODELS / f"{name}.py")


def test_blocks_imports_no_family_and_names_none():
    banned = {f"deepspeed_tpu.models.{name}" for name in (*FAMILIES, "llama")}
    assert not _imports(MODELS / "blocks.py") & banned
    tree = ast.parse((MODELS / "blocks.py").read_text())
    docstrings = {
        id(node.body[0].value) for node in ast.walk(tree)
        if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef))
        and node.body and isinstance(node.body[0], ast.Expr)
        and isinstance(node.body[0].value, ast.Constant)}
    named = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            if id(node) in docstrings:
                continue
            named.append(node.value)
        named += [getattr(node, field) for field in ("id", "attr", "name",
                                                      "arg")
                  if isinstance(getattr(node, field, None), str)]
    assert named
    assert not [text for text in named
                if any(word in text.lower()
                       for word in ("mimo", "lfm2", "deepseek"))]


def test_llama_takes_its_leaf_blocks_from_blocks():
    from deepspeed_tpu.models import llama

    assert llama.RMSNorm is blocks.RMSNorm
    assert llama.apply_rope is blocks.apply_rope
    assert llama.rope_frequencies is blocks.rope_frequencies


# ---------------------------------------------------------------------------
# (b) the parameter trees are the parent's

@pytest.mark.parametrize("name", FAMILIES)
def test_tiny_parameters_are_the_parents(name):
    Config, Module, _, _ = _family(name)
    tree = Module(Config.tiny()).init(jax.random.PRNGKey(0),
                                      jnp.zeros((1, 8), jnp.int32))
    assert structure(tree) == PARENT[name]["tiny"], "\n".join(_lines(tree))
    drawn, spread = values(tree["params"])
    want_drawn, want_spread = PARENT[name]["values"]
    assert spread == pytest.approx(want_spread, rel=1e-7)
    assert drawn == pytest.approx(want_drawn, abs=1e-7 * want_spread)


@pytest.mark.parametrize("name", FAMILIES)
def test_paged_parameters_and_pools_are_the_parents(name):
    tree = _paged_init(name)
    assert structure(tree) == PARENT[name]["paged"], "\n".join(_lines(tree))
    drawn, spread = values(tree["params"])
    want_drawn, want_spread = PARENT[name]["values"]
    assert spread == pytest.approx(want_spread, rel=1e-7)
    assert drawn == pytest.approx(want_drawn, abs=1e-7 * want_spread)


@pytest.mark.parametrize("name", FAMILIES)
def test_published_parameter_tree_is_the_parents(name):
    """Paths, shapes and dtypes at the benchmark's widths, under
    ``eval_shape`` (the dense FFN of two families keeps float32 weights
    whatever ``param_dtype``: ``PagedDecoder.dense_param_dtype``)."""
    published = FAMILIES[name][3]
    family = importlib.import_module(f"perfbench.families.{name}")
    config_file = json.loads(
        (ROOT / "perfbench" / "configs" / f"{published}.json").read_text())
    module = family.serving_module(config_file, jnp.bfloat16)
    shapes = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    assert structure(shapes) == PARENT[name]["published"]


def test_a_swapped_pair_of_parameters_moves_the_checksum():
    """The checksum is not blind to two leaves of one shape that trade
    their values (``gate`` and ``up`` drawn in the other order)."""
    Config, Module, _, _ = _family("mimo_v2")
    params = Module(Config.tiny()).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    mlp = params["layers_1_mlp"]
    traded = {**params, "layers_1_mlp": {**mlp, "gate": mlp["up"],
                                         "up": mlp["gate"]}}
    drawn, _ = values(traded)
    want_drawn, want_spread = PARENT["mimo_v2"]["values"]
    assert abs(drawn - want_drawn) > 100 * 1e-7 * want_spread


# ---------------------------------------------------------------------------
# (c) the contract is written once

def test_the_engine_keeps_no_ring_of_its_own():
    from tests.unit.test_mimo_v2 import FAMILY

    cfg, _, params = FAMILY.make()
    srv = FAMILY.serving_engine(params, cfg)
    try:
        assert not hasattr(srv, "ring_blocks")
        assert srv.slot_entries == cfg.paged_ring_blocks_for(
            srv.config.block_size)
    finally:
        srv.destroy()


@pytest.mark.parametrize("name", FAMILIES)
def test_one_for_paged_decode_and_one_shell(name):
    Config, Module, knob, _ = _family(name)
    assert Config.for_paged_decode is blocks.ServedConfig.for_paged_decode
    assert Module.__call__ is blocks.PagedDecoder.__call__
    assert Module.serve_counters and Module.serve_routed is True
    cfg = Config.tiny()
    assert cfg.slot_knob == knob
    assert hasattr(cfg, "paged_slot_state_for") == bool(knob)
    assert not knob or cfg.paged_slot_state_for(4)["knob"] == knob
    served = cfg.for_paged_decode(9, 4, return_routed=True,
                                  **({knob: 2} if knob else {}))
    assert served.serving and not cfg.serving
    assert (served.paged_num_blocks, served.paged_block_size) == (9, 4)
    assert served.pool_dims() == (9, 4) and served.paged_return_routed
    if knob:
        assert getattr(served, f"paged_{knob}") == 2
        with pytest.raises(ValueError, match=f"needs {knob}"):
            cfg.for_paged_decode(9, 4)
    with pytest.raises(ValueError, match="quantized pool"):
        cfg.for_paged_decode(9, 4, kv_dtype="int8",
                             **({knob: 2} if knob else {}))
    with pytest.raises(TypeError, match="another_familys_slots"):
        cfg.for_paged_decode(9, 4, another_familys_slots=2)
    with pytest.raises(ValueError, match="paged_num_blocks > 1"):
        cfg.for_paged_decode(1, 4, **({knob: 2} if knob else {})).pool_dims()
    sparse = sum(cfg.sparse(i) for i in range(cfg.num_hidden_layers))
    assert 0 < sparse < cfg.num_hidden_layers == len(
        [cfg.sparse(i) for i in range(cfg.num_hidden_layers)])
    assert cfg.routed_width == sparse * cfg.num_experts_per_tok
    assert cfg.n_head == cfg.num_attention_heads


@pytest.mark.parametrize("name", FAMILIES)
def test_a_config_says_its_own_routing(name):
    """``SparseFFN`` takes arguments, not a config: what a family's config
    says (``sparse_ffn``) is what its adapter builds, whichever file the
    adapter is imported from."""
    from deepspeed_tpu.models import deepseek_v2, mimo_v2

    Config, _, _, _ = _family(name)
    cfg = Config.tiny()
    said = cfg.sparse_ffn()
    adapter = (deepseek_v2 if name == "deepseek_v2" else mimo_v2).SparseExperts
    layer = adapter(cfg)
    assert isinstance(layer, blocks.SparseFFN)
    assert {k: getattr(layer, k) for k in said} == said
    want = {"mimo_v2": ("sigmoid", True, 0.0, 1.0, 0),
            "lfm2_moe": ("sigmoid", True, 1e-6, 1.0, 0),
            "deepseek_v2": ("softmax", False, 0.0, 1.0, 2 * 32)}[name]
    assert (layer.scoring, layer.renormalize, layer.norm_eps, layer.scale,
            layer.shared_width) == want
    assert (layer.bias_std is None) == (name == "deepseek_v2")
    out = layer.init_with_output(
        jax.random.PRNGKey(0), jnp.ones((1, 5, cfg.hidden_size)))[0]
    assert len(out) == (4 if name == "deepseek_v2" else 3)
    assert out[-1].shape == (1, 5, cfg.num_experts_per_tok)


def test_no_config_poses_as_anothers():
    from deepspeed_tpu.models.lfm2_moe import Lfm2MoeConfig

    cfg = Lfm2MoeConfig.tiny()
    for borrowed in ("n_routed_experts", "selection_bias_std", "ep_rank",
                     "ep_size"):
        assert not hasattr(cfg, borrowed), borrowed
    for name in FAMILIES:
        source = (MODELS / f"{name}.py").read_text()
        assert "getattr(cfg" not in source, name


def test_the_sparse_ffn_calls_dropless_through_the_module(monkeypatch):
    """The benchmark's controls replace ``dropless.route`` and
    ``dropless.expert_ffn`` as module attributes: the block has to find
    the replacements."""
    from deepspeed_tpu.moe import dropless

    seen = []
    route, ffn = dropless.route, dropless.expert_ffn
    monkeypatch.setattr(dropless, "route", lambda x, w, b, k, **kw: (
        seen.append(("route", sorted(kw))), route(x, w, b, k, **kw))[1])
    monkeypatch.setattr(
        dropless, "expert_ffn",
        lambda x, e, w, gate, up, down, **kw: (
            seen.append(("expert_ffn", sorted(kw))),
            ffn(x, e, w, gate, up, down, **kw))[1])
    layer = blocks.SparseFFN(experts=8, top_k=2, width=16,
                             dtype=jnp.float32)
    layer.init_with_output(jax.random.PRNGKey(0), jnp.ones((1, 3, 8)))
    assert [name for name, _ in seen] == ["route", "expert_ffn"]
    assert seen[0][1] == ["n_group", "norm_eps", "renormalize", "scale",
                          "scoring", "topk_group"]
    assert seen[1][1] == ["first_expert", "limit", "n_routed", "valid"]


def test_the_paged_step_counts_under_its_callers_label():
    from deepspeed_tpu.ops import attention as ops_attention

    q = jnp.ones((2, 3, 4, 8))
    k = v = jnp.ones((2, 3, 2, 8))
    pool = jnp.zeros((1, 5, 4, 16))
    paging = {"num_valid": jnp.asarray([3, 2]),
              "lengths": jnp.asarray([4, 0]), "prefill": False}
    pos = paging["lengths"][:, None] + jnp.arange(3)[None]
    table = jnp.asarray([[1, 2], [3, 4]])
    before = dict(ops_attention.dispatch_counts())
    y, k_pool, v_pool = blocks.paged_gqa(q, k, v, pos, paging, table, pool,
                                         pool, 0, "some_family_attn")
    after = ops_attention.dispatch_counts()
    assert after.get("some_family_attn_cached_xla", 0) == before.get(
        "some_family_attn_cached_xla", 0) + 1
    assert y.shape == (2, 3, 4, 8) and k_pool.shape == pool.shape
    # row 0 wrote positions 4..6 (block 2 of its table), row 1 its two real
    # positions 0..1 (block 3); its padded third went to the garbage block
    assert float(k_pool[0, 2].sum()) == 3 * 16
    assert float(k_pool[0, 3].sum()) == 2 * 16
    assert float(k_pool[0, 1].sum()) == float(k_pool[0, 4].sum()) == 0
