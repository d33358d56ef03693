"""The EXAONE-MoE family at a small size on the CPU: the program in float32
against the plain reference (``perfbench/reference_exaone_moe.py``) on
LOGITS, full forward and through the paged cache (a whole prompt, chunks
that cross the ring's seam, decode past the window and past a ring's
rows), the lower-precision control, the eight shares with the shared term
once, a global layer that reads no position beside a sliding one that does,
the parameter tree against the configuration's sum, the kernels in
interpret mode, and the mechanisms that refuse the model by name."""

import dataclasses
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import blocks
from deepspeed_tpu.models.exaone_moe import (GLOBAL, WINDOW, ExaoneAttention,
                                             ExaoneMoeConfig,
                                             ExaoneMoeForCausalLM,
                                             SparseExperts)
from deepspeed_tpu.moe import dropless
from perfbench import reference_exaone_moe as reference
from tests.unit.served_family import REFUSED, Family, highest, prompts  # noqa: F401

# float32 program against the float32 reference, on logits of order 1: the
# two differ by the order of their sums (the program's attention is an
# einsum over groups, its experts a weighted scan), some 1e-6
TOL = 1e-4
WINDOW_KEYS, BLOCK = 8, 4
ROOT = pathlib.Path(__file__).resolve().parents[2]


def shape_of(cfg: ExaoneMoeConfig, first_expert=None) -> dict:
    """The reference's view of a program config (the family builds the
    same from a configuration file)."""
    first, _ = dropless.held_range(cfg.num_experts, cfg.ep_rank, cfg.ep_size)
    return dict(
        heads=cfg.num_attention_heads, kv_heads=cfg.num_key_value_heads,
        head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
        eps=cfg.rms_norm_eps, top_k=cfg.num_experts_per_tok,
        route_scale=cfg.routed_scaling_factor,
        first_expert=first if first_expert is None else first_expert,
        windows=tuple(cfg.sliding_window if kind == WINDOW else 0
                      for kind in cfg.layer_types),
        sparse=tuple(kind == "sparse" for kind in cfg.mlp_layer_types))


def _norms_away_from_one(params):
    """Norm weights away from 1, so that a norm left out or misplaced
    shows."""
    return jax.tree_util.tree_map_with_path(
        lambda path, x: x * (1.0 + 0.3 * jnp.cos(jnp.arange(x.size)).reshape(
            x.shape)) if path[-1].key == "scale" else x, params)


FAMILY = Family(ExaoneMoeConfig, ExaoneMoeForCausalLM, reference, shape_of,
                TOL, serving={"decode_slots": 3, "block_size": BLOCK,
                              "max_model_len": 64},
                perturb=_norms_away_from_one, bucket_slack=0)
engines = FAMILY.engines()
make, reference_logits = FAMILY.make, FAMILY.reference_logits


def test_full_forward_matches_the_reference(highest):
    cfg, module, params = make()
    ids = np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 40))
    got = np.asarray(FAMILY.plain(cfg)(params, jnp.asarray(ids)))
    want = reference_logits(cfg, params, ids)
    assert np.abs(want).max() > 0.1
    assert np.abs(got - want).max() <= TOL
    attn = params["layers_3_attn"]
    assert attn["q_norm"]["scale"].shape == (cfg.head_dim,)
    assert attn["k_norm"]["scale"].shape == (cfg.head_dim,)
    assert "shared_experts" in params["layers_1_mlp"]
    assert "sink" not in attn and "router" not in params["layers_0_mlp"]


def test_bf16_fails_the_float32_tolerance():
    """The lower-precision control: the same comparison with the program
    in bfloat16 is outside the tolerance, so the tolerance tells them
    apart."""
    cfg, module, params = make()
    ids = np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 40))
    low = FAMILY.plain(dataclasses.replace(cfg, dtype=jnp.bfloat16))
    got = np.asarray(low(params, jnp.asarray(ids)))
    assert np.abs(got - reference_logits(cfg, params, ids)).max() > 10 * TOL


@pytest.mark.parametrize("part", ["q_norm", "k_norm"])
def test_the_head_norms_move_the_logits(highest, part):
    """A norm's weight changed is another function: the comparison above
    is not blind to the QK-norm, in either kind of layer."""
    cfg, module, params = make()
    ids = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab_size, (1, 24)))
    base = np.asarray(FAMILY.plain(cfg)(params, ids))
    for layer in ("layers_3_attn", "layers_4_attn"):   # global, sliding
        moved = {**params, layer: {**params[layer], part: {
            "scale": params[layer][part]["scale"][::-1]}}}
        got = np.asarray(FAMILY.plain(cfg)(moved, ids))
        assert np.abs(got - base).max() > 100 * TOL, layer


def test_a_global_layer_reads_no_position_and_a_sliding_layer_does(highest):
    """Every position shifted, each by another amount (RoPE is relative:
    one shift for all would move a rotated layer by rounding alone): the
    global kind's output does not change by one bit, a sliding layer's
    does, in the reference; and the program's global layer is the same
    function under another ``rope_theta``, its sliding layer is not."""
    cfg, _, params = make()
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, cfg.hidden_size))
    shape = shape_of(cfg)
    moved = {}
    for window, name in ((False, "layers_3_attn"), (True, "layers_4_attn")):
        p = params[name]
        keys = cfg.sliding_window if window else 0
        at = [np.asarray(reference.attention(x, p, shape, keys, positions))
              for positions in (None, 37 + 3 * jnp.arange(24))]
        moved[window] = np.abs(at[1] - at[0]).max()
        # the program's layer is the reference's at positions 0, 1, ...
        layer = ExaoneAttention(cfg, window)
        got = np.asarray(layer.apply({"params": p}, x)[0])
        assert np.abs(got - at[0]).max() <= TOL
        # ... and under another theta (every position's rotation changed)
        other = ExaoneAttention(dataclasses.replace(cfg, rope_theta=50.0),
                                window)
        shifted = np.asarray(other.apply({"params": p}, x)[0])
        assert (np.abs(shifted - got).max() == 0.0) == (not window)
    assert moved[False] == 0.0 and moved[True] > 100 * TOL


def test_the_eight_shares_add_up_to_the_uncut_layer_the_shared_term_once(
        highest):
    """Guide, section 4: the sparse layer's routed terms of all
    ``ep_size`` shares, summed, plus the shared expert's term ONCE, are the
    uncut reference's layer."""
    cfg = ExaoneMoeConfig.tiny(dtype=jnp.float32)
    ep = 8
    held = cfg.num_experts // ep
    d, f, n = cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts
    keys = jax.random.split(jax.random.PRNGKey(3), 9)
    whole = {"router": 0.5 * jax.random.normal(keys[0], (d, n)),
             "router_bias": 0.1 * jax.random.normal(keys[1], (n,)),
             "gate": 0.2 * jax.random.normal(keys[2], (n, d, f)),
             "up": 0.2 * jax.random.normal(keys[3], (n, d, f)),
             "down": 0.2 * jax.random.normal(keys[4], (n, f, d)),
             "shared_experts": {
                 name: {"kernel": 0.2 * jax.random.normal(key, shape)}
                 for name, key, shape in (
                     ("gate_proj", keys[5], (d, f)),
                     ("up_proj", keys[6], (d, f)),
                     ("down_proj", keys[7], (f, d)))}}
    x = jax.random.normal(keys[8], (1, 12, d))
    shape = {**shape_of(cfg), "first_expert": 0}
    routed, shared, chosen, _ = reference.sparse(x, whole, shape)
    want = routed + shared
    # the routed weights: the chosen scores over their sum, x 2.5
    assert np.allclose(np.asarray(reference.routed(
        x[0], whole, shape)[1]).sum(-1), cfg.routed_scaling_factor)
    total, shared_terms, pairs = 0.0, [], 0
    for rank in range(ep):
        own = slice(rank * held, (rank + 1) * held)
        share = {**whole, **{k: whole[k][own] for k in ("gate", "up",
                                                        "down")}}
        layer = SparseExperts(dataclasses.replace(cfg, ep_rank=rank,
                                                  ep_size=ep))
        y, shared_here, counters, picked = layer.apply({"params": share}, x)
        assert (np.sort(picked, -1) == np.sort(chosen, -1)).all()
        total, pairs = total + y, pairs + int(counters[2])
        shared_terms.append(np.asarray(shared_here))
    # every share computes the shared term alike: it is added ONCE
    assert all(np.abs(s - shared_terms[0]).max() == 0.0
               for s in shared_terms)
    assert np.abs(shared_terms[0] - np.asarray(shared)).max() <= TOL
    assert np.abs(np.asarray(total + shared_terms[0] - want)).max() <= TOL
    # summed eight times it would be off by seven shared terms
    assert np.abs(np.asarray(7 * shared)).max() > 100 * TOL
    # every (token, expert) pair is some rank's, once
    assert pairs == 12 * cfg.num_experts_per_tok


# ---------------------------------------------------------------------------
# the parameter tree
# ---------------------------------------------------------------------------
def _published_config():
    from perfbench.families import exaone_moe as family

    with open(ROOT / "perfbench/configs/k-exaone-236b-ep8.json") as f:
        config_file = json.load(f)
    return config_file, family.serving_module(config_file,
                                              jnp.bfloat16).config


def test_the_published_tree_is_the_configurations_sum():
    """ISSUE 52's arithmetic, held to the program's own tree under
    ``eval_shape``: 3,712,028,416 parameters, by part."""
    config_file, cfg = _published_config()
    tree = jax.eval_shape(
        lambda: ExaoneMoeForCausalLM(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    size = lambda t: sum(int(np.prod(x.shape))
                         for x in jax.tree_util.tree_leaves(t))
    layer = lambda i: size({k: v for k, v in tree.items()
                            if k.startswith(f"layers_{i}_")})
    by_part = config_file["parameters_by_part"]
    assert size(tree["layers_3_attn"]) - 2 * cfg.head_dim == (
        by_part["attention_a_layer"]) == 113_246_208
    assert layer(0) == by_part["layer_0"] == 452_997_376
    assert [layer(i) for i in range(1, 5)] == [755_773_824] * 4 == [
        by_part["a_sparse_layer"]] * 4
    assert size(tree["embed_tokens"]) + size(tree["lm_head"]) == (
        by_part["embedding_and_head"]) == 2 * 19_200 * 6_144
    assert size(tree) == config_file["parameters"] == 3_712_028_416
    mlp = tree["layers_1_mlp"]
    assert mlp["router"].shape == (6144, 128)      # ALL experts published
    assert mlp["gate"].shape == (16, 6144, 2048)   # the sixteen held
    assert mlp["shared_experts"]["down_proj"]["kernel"].shape == (2048, 6144)
    # made in the type they are served in: every matrix bfloat16
    assert all(x.dtype == jnp.bfloat16
               for x in jax.tree_util.tree_leaves(tree) if x.ndim > 1)


def test_the_pools_are_one_row_shape_and_the_ring_does_not_grow():
    _, cfg = _published_config()
    paged = cfg.for_paged_decode(1 + 64 * 128, 32, return_routed=True,
                                 ring_slots=64)
    shapes = ExaoneMoeForCausalLM(paged).pool_shapes(1 + 64 * 128, 32)
    # 8 KV heads x 128 lanes for keys, the same for values: 2,048 lanes a
    # token a layer, in both kinds
    assert shapes == {"global_key_pool": (1, 8193, 32, 1024),
                      "global_value_pool": (1, 8193, 32, 1024),
                      "window_key_pool": (4, 321, 32, 1024),
                      "window_value_pool": (4, 321, 32, 1024)}
    assert paged.paged_slot_state_for(32)["entries"] == 5
    assert paged.kv_bytes_per_token() == {"global": 4096, "window": 16384}
    # a 256-token row: two thirds of what a step reads is the rings'; at
    # 4,096 the rings still hold 160 rows
    live = paged.kv_live_bytes(np.asarray([256]))
    assert live == {"global": 256 * 4096, "window": 160 * 16384}
    live = paged.kv_live_bytes(np.asarray([4096]))
    assert live["window"] == 160 * 16384 and live["global"] == 4096 * 4096


def test_a_config_is_refused_where_its_lists_do_not_fit():
    with pytest.raises(ValueError, match="one entry a layer"):
        ExaoneMoeConfig.tiny(layer_types=(WINDOW, GLOBAL))
    with pytest.raises(ValueError, match="one entry a layer"):
        ExaoneMoeConfig.tiny(mlp_layer_types=("dense",) * 4 + ("moe",))
    with pytest.raises(ValueError, match="experts over"):
        ExaoneMoeConfig.tiny(ep_size=5)


# ---------------------------------------------------------------------------
# through the paged cache
# ---------------------------------------------------------------------------
def test_prefill_and_decode_through_the_cache(highest):
    """Contexts past the window and past a ring's 12 rows (the ring of 3
    blocks wraps several times), slots of unequal length, and a slot
    reused after a finish (5 requests over 3 slots)."""
    cfg, _, params = make()
    asked = prompts(cfg, [5, 19, 33, 9, 26])
    stats, _ = FAMILY.served_logits_match(
        cfg, params, list(zip(asked, [30, 12, 20, 25, 8])))
    counted = stats["model_counters"]
    sparse = cfg.sparse_layers
    assert sparse == 4
    assert counted["decode"]["pairs_all"] == (
        stats["busy_slot_steps"] * sparse * cfg.num_experts_per_tok)
    assert counted["decode"]["pairs_here"] == counted["decode"]["pairs_all"]
    assert counted["prefill"]["pairs_all"] == (
        sum(map(len, asked)) * sparse * cfg.num_experts_per_tok)
    kv = stats["kv_live_bytes"]
    assert 0 < kv["window"] and 0 < kv["global"]
    assert {"exaone_window_prefill_xla", "exaone_global_prefill_xla",
            "exaone_window_cached_xla", "exaone_global_cached_xla",
            "moe_experts_dense_xla"} <= set(stats["attention_paths"])


def test_a_prompt_through_chunked_prefill(highest):
    """Chunks of 8 through a ring of 12 rows: every chunk crosses the
    ring's seam or a lap of it, and the global layer takes its keys a tile
    at a time."""
    cfg, _, params = make()
    stats, _ = FAMILY.served_logits_match(
        cfg, params, list(zip(prompts(cfg, [37, 6]), [14, 14])),
        prefill_chunk_tokens=8)
    assert {"exaone_window_cached_xla", "exaone_global_cached_tiled_xla"} <= (
        set(stats["attention_paths"]))


def test_an_expert_share_serves_only_its_experts(highest):
    """``ep_size`` 8: the program adds the held experts' terms and the
    shared expert's, as the reference given the same share does, and
    counts about an eighth of the pairs as its own."""
    cfg, _, params = make(ep_size=8, ep_rank=3)
    assert params["layers_1_mlp"]["gate"].shape[0] == 4
    assert params["layers_1_mlp"]["router"].shape[1] == 32
    stats, _ = FAMILY.served_logits_match(
        cfg, params, list(zip(prompts(cfg, [11, 21]), [16, 16])))
    counted = stats["model_counters"]["decode"]
    assert 0 < counted["pairs_here"] < counted["pairs_all"] / 4
    assert 0 < counted["experts_touched"] <= counted["experts_held"]
    assert counted["experts_held"] == 4 * cfg.sparse_layers * (
        stats["decode_steps"])


def test_the_engine_hands_back_the_routed_sets(highest):
    cfg, _, params = make()
    asked = prompts(cfg, [21, 13])
    srv = FAMILY.shared_engine(params, cfg, routed_experts_kept=4,
                               prefill_chunk_tokens=8)
    reqs = [srv.submit(p, max_new_tokens=n) for p, n in zip(asked, [9, 5])]
    srv.drain()
    for req, prompt in zip(reqs, asked):
        FAMILY.routed_sets_are_the_references(srv, cfg, params, req, prompt)


def test_the_window_pool_does_not_grow_with_the_context(highest):
    cfg, _, params = make()
    sizes = {}
    for longest in (32, 64):
        srv = FAMILY.serving_engine(params, cfg, max_model_len=longest)
        sizes[longest] = {k: v.shape for k, v in srv.cache.items()}
        ring = srv.slot_entries
        srv.destroy()
    assert ring == WINDOW_KEYS // BLOCK + 1 == blocks.ring_blocks_for(
        WINDOW_KEYS, BLOCK)
    for name in ("window_key_pool", "window_value_pool"):
        assert sizes[32][name] == sizes[64][name]
        assert sizes[32][name][:2] == (4, 1 + 3 * ring)
    assert sizes[32]["global_key_pool"][0] == 1
    assert sizes[32]["global_key_pool"][1] < sizes[64]["global_key_pool"][1]
    assert sizes[32]["global_key_pool"][3] == sizes[32]["window_key_pool"][3]


@pytest.mark.parametrize("chunk", [0, 8, 5],
                         ids=["whole-prompt", "chunks-of-8", "chunks-of-5"])
def test_paged_logits_match_the_reference(highest, chunk):
    """Prefill then decode through the cache against the reference's full
    forward pass, on LOGITS at every position: a whole prompt, chunks that
    cross the ring's seam (8 of a ring of 12 rows; 5, which no block
    boundary divides), then decode past the window (8) and past a ring's
    rows (12) (what ``tools/chip_logits_exaone_moe.py`` does on the chip
    at the published widths)."""
    cfg, _, params = make()
    got, tokens = FAMILY.paged_logits(FAMILY.shared_engine(params, cfg),
                                      prompts(cfg, [27])[0], 16, chunk=chunk)
    want = reference_logits(cfg, params, [tokens])[0]
    assert len(got) == 27 + 16 > 3 * 12
    assert np.abs(got - want[:len(got)]).max() <= TOL


def test_a_stale_ring_row_moves_the_logits(highest):
    """The break the cell's check has to see: one row of one slot's ring
    not written (the newest key of every window layer left as zeros, as a
    ring that missed a write would hold an older lap's) moves the next
    step's logits far outside the tolerance."""
    cfg, _, params = make()
    srv = FAMILY.shared_engine(params, cfg)
    prompt = prompts(cfg, [27])[0]
    ring, slot = srv.slot_entries, 1
    last = len(prompt) - 1
    own = 1 + slot * ring + (last // BLOCK) % ring

    def spoil(cache):
        pool = cache["window_key_pool"]
        return {**cache, "window_key_pool": pool.at[
            :, own, last % BLOCK].set(0.0)}

    # (the shared engine: the row is the slot's next tenant's to overwrite)
    got, tokens = FAMILY.paged_logits(srv, prompt, 2, spoil=spoil)
    want = reference_logits(cfg, params, [tokens])[0]
    # the prompt's logits were made before the row went stale
    assert np.abs(got[:len(prompt)] - want[:len(prompt)]).max() <= TOL
    assert np.abs(got[len(prompt)] - want[len(prompt)]).max() > 100 * TOL


def test_decode_through_both_kernels_matches_the_xla_paths(monkeypatch):
    """The decode program with the Pallas kernels in it (interpret mode):
    the paged GQA kernel over the block table (not rotated) and over the
    ring (rotated, no sink), both at one row shape, and the grouped expert
    matmul, against the same steps on the XLA paths."""
    cfg, _, params = make()
    got, want, paths = FAMILY.decode_through_the_kernels(
        monkeypatch, cfg, params, prompts(cfg, [19])[0], 3)
    assert paths.get("exaone_window_decode_kernel") and paths.get(
        "exaone_global_decode_kernel")
    assert paths.get("moe_experts_grouped_kernel")
    assert np.abs(got - want).max() <= TOL


# ---------------------------------------------------------------------------
# refusals, by name
# ---------------------------------------------------------------------------
@REFUSED
def test_mechanisms_that_know_one_kind_of_row_refuse_the_model(serving,
                                                               mechanism):
    assert "ring a decode slot" in FAMILY.mechanism_refusal(serving,
                                                            mechanism)


def test_tensor_parallel_refuses_the_model():
    assert "ExaoneMoeForCausalLM" in FAMILY.tensor_parallel_refusal()


def test_migration_refuses_the_model():
    assert "ExaoneMoeForCausalLM" in FAMILY.migration_refusals()[0]


def test_the_quantized_pool_is_refused_by_the_config_too():
    with pytest.raises(ValueError, match="two kinds of KV layer"):
        ExaoneMoeConfig.tiny().for_paged_decode(9, 4, kv_dtype="int8",
                                                ring_slots=2)
    with pytest.raises(ValueError, match="ring_slots"):
        ExaoneMoeConfig.tiny().for_paged_decode(9, 4)


# ---------------------------------------------------------------------------
# the ring is the blocks', under its caller's label; arrows point one way
# ---------------------------------------------------------------------------
def test_the_ring_counts_under_its_callers_label_and_wraps():
    from deepspeed_tpu.ops import attention as ops_attention

    q = jnp.ones((2, 3, 4, 8))
    k = v = jnp.ones((2, 3, 2, 8))
    pool = jnp.zeros((1, 7, 4, 16))            # garbage block + 2 rings of 3
    paging = {"num_valid": jnp.asarray([3, 2]),
              "lengths": jnp.asarray([10, 0]), "prefill": False}
    pos = paging["lengths"][:, None] + jnp.arange(3)[None]
    table = jnp.asarray([[1, 2, 3], [4, 5, 6]])
    before = dict(ops_attention.dispatch_counts())
    y, k_pool, v_pool = blocks.ring_gqa(q, k, v, pos, paging, table, pool,
                                        pool, 0, "some_family_window",
                                        window=8)
    after = ops_attention.dispatch_counts()
    assert after.get("some_family_window_cached_xla", 0) == before.get(
        "some_family_window_cached_xla", 0) + 1
    assert y.shape == (2, 3, 4, 8) and k_pool.shape == pool.shape
    # row 0 wrote positions 10, 11 (block 10 // 4 % 3 = 2 of its ring:
    # pool block 3) and 12 (the ring wraps: block 0 of its ring, pool
    # block 1); row 1 its two real positions 0, 1 (pool block 4); its
    # padded third went to the garbage block
    per_row = 16
    assert float(k_pool[0, 3].sum()) == 2 * per_row
    assert float(k_pool[0, 1].sum()) == 1 * per_row
    assert float(k_pool[0, 4].sum()) == 2 * per_row
    assert float(k_pool[0, 2].sum()) == float(k_pool[0, 5].sum()) == 0
    assert float(k_pool[0, 6].sum()) == 0


def test_the_family_imports_no_other_and_blocks_does_not_name_it():
    import ast

    models = ROOT / "deepspeed_tpu" / "models"
    found = set()
    for node in ast.walk(ast.parse((models / "exaone_moe.py").read_text())):
        if isinstance(node, ast.Import):
            found.update(alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            found.add(node.module)
            found.update(f"{node.module}.{a.name}" for a in node.names)
    others = {f"deepspeed_tpu.models.{name}" for name in (
        "mimo_v2", "lfm2_moe", "deepseek_v2", "granite_hybrid", "llama",
        "gpt2")}
    assert not found & others
    assert "deepspeed_tpu.models.blocks" in found
    source = (models / "blocks.py").read_text().lower()
    assert "exaone" not in source and "def ring_gqa(" in source
    # the ring left the file it was private to
    assert "_ring(" not in (models / "mimo_v2.py").read_text()
    assert "blocks.ring_gqa" in (models / "mimo_v2.py").read_text()
    assert "blocks.ring_gqa" in (models / "exaone_moe.py").read_text()
