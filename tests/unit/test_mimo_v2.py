"""The MiMo-V2 family at a small size on the CPU: the program in float32
against the plain reference (``perfbench/reference_mimo_v2.py``) on
LOGITS, full forward and through the paged cache (rings that wrap, slots of
unequal length, a slot reused, a chunked prompt), the sink on and off, the
lower-precision control, the share test, the kernels in interpret mode,
and the mechanisms that refuse the model by name."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.mimo_v2 import (MiMoV2Config, MiMoV2ForCausalLM,
                                          SparseExperts)
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.parallel.topology import reset_topology
from deepspeed_tpu.serving import ServingEngine
from perfbench import reference_mimo_v2 as reference
from tests.unit.served_family import REFUSED, Family, highest, prompts  # noqa: F401

TOL = 1e-4   # float32 program against the float32 reference, on logits
WINDOW, BLOCK = 8, 4


def shape_of(cfg: MiMoV2Config, first_expert=None) -> dict:
    """The reference's view of a program config (the family builds the
    same from a configuration file)."""
    first, _ = dropless.held_range(cfg.n_routed_experts, cfg.ep_rank,
                                   cfg.ep_size)
    return dict(
        heads=cfg.num_attention_heads, kv_heads=cfg.num_key_value_heads,
        swa_kv_heads=cfg.swa_num_key_value_heads, head_dim=cfg.head_dim,
        v_head_dim=cfg.v_head_dim, value_scale=cfg.attention_value_scale,
        rotary_dim=cfg.rotary_dim, window=cfg.sliding_window,
        rope_theta=cfg.rope_theta, swa_rope_theta=cfg.swa_rope_theta,
        eps=cfg.layernorm_epsilon, top_k=cfg.num_experts_per_tok,
        first_expert=first if first_expert is None else first_expert,
        pattern=cfg.hybrid_layer_pattern, moe=cfg.moe_layer_freq)


FAMILY = Family(MiMoV2Config, MiMoV2ForCausalLM, reference, shape_of, TOL,
                serving={"decode_slots": 3, "block_size": BLOCK,
                         "max_model_len": 64}, bucket_slack=0)
engines = FAMILY.engines()
make, reference_logits = FAMILY.make, FAMILY.reference_logits


@pytest.mark.parametrize("sink", [True, False], ids=["sink", "no-sink"])
def test_full_forward_matches_the_reference(highest, sink):
    cfg, module, params = make(add_swa_attention_sink_bias=sink)
    ids = np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 40))
    got = np.asarray(FAMILY.plain(cfg)(params, jnp.asarray(ids)))
    want = reference_logits(cfg, params, ids)
    assert np.abs(got - want).max() <= TOL
    has_sink = "sink" in params["layers_1_attn"]
    assert has_sink == sink and "sink" not in params["layers_0_attn"]


def test_the_sink_moves_the_logits(highest):
    """Sink on and off are different functions: the comparison above is
    not blind to it."""
    cfg, module, params = make()
    ids = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab_size, (1, 24)))
    moved = jax.tree_util.tree_map_with_path(
        lambda path, x: x + 2.0 if path[-1].key == "sink" else x, params)
    a, b = (np.asarray(FAMILY.plain(cfg)(p, ids)) for p in (params, moved))
    assert np.abs(a - b).max() > 100 * TOL


def test_bf16_fails_the_float32_tolerance():
    """The lower-precision control: the same comparison with the program
    in bfloat16 is outside the tolerance, so the tolerance tells them
    apart."""
    cfg, module, params = make()
    ids = np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 40))
    low = FAMILY.plain(dataclasses.replace(cfg, dtype=jnp.bfloat16))
    got = np.asarray(low(params, jnp.asarray(ids)))
    assert np.abs(got - reference_logits(cfg, params, ids)).max() > 10 * TOL


def test_the_shares_add_up_to_the_uncut_layer(highest):
    """Guide, section 4: the sparse layer's outputs of all ``ep_size``
    shares, summed, are the uncut reference's layer."""
    cfg = MiMoV2Config.tiny(dtype=jnp.float32)
    ep = 4
    held = cfg.n_routed_experts // ep
    d, f = cfg.hidden_size, cfg.moe_intermediate_size
    keys = jax.random.split(jax.random.PRNGKey(3), 6)
    whole = {"router": 0.5 * jax.random.normal(keys[0],
                                               (d, cfg.n_routed_experts)),
             "router_bias": 0.1 * jax.random.normal(
                 keys[1], (cfg.n_routed_experts,)),
             "gate": 0.2 * jax.random.normal(
                 keys[2], (cfg.n_routed_experts, d, f)),
             "up": 0.2 * jax.random.normal(
                 keys[3], (cfg.n_routed_experts, d, f)),
             "down": 0.2 * jax.random.normal(
                 keys[4], (cfg.n_routed_experts, f, d))}
    x = jax.random.normal(keys[5], (1, 12, d))
    want, _, _ = reference._sparse(x, whole, {
        "top_k": cfg.num_experts_per_tok, "first_expert": 0})
    total, pairs = 0.0, 0
    for rank in range(ep):
        own = slice(rank * held, (rank + 1) * held)
        share = {**whole, **{k: whole[k][own] for k in ("gate", "up",
                                                        "down")}}
        layer = SparseExperts(dataclasses.replace(cfg, ep_rank=rank,
                                                  ep_size=ep))
        y, counters, _ = layer.apply({"params": share}, x)
        total, pairs = total + y, pairs + int(counters[2])
    assert np.abs(np.asarray(total - want)).max() <= TOL
    # every (token, expert) pair is some rank's, once
    assert pairs == 12 * cfg.num_experts_per_tok


# ---------------------------------------------------------------------------
# through the paged cache
# ---------------------------------------------------------------------------
def test_prefill_and_decode_through_the_cache(highest):
    """Contexts past the window (the ring of 3 blocks wraps several
    times), slots of unequal length, and a slot reused after a finish
    (5 requests over 3 slots)."""
    cfg, _, params = make()
    asked = prompts(cfg, [5, 19, 33, 9, 26])
    stats, _ = FAMILY.served_logits_match(
        cfg, params, list(zip(asked, [30, 12, 20, 25, 8])))
    counted = stats["model_counters"]
    sparse = sum(cfg.moe_layer_freq)
    # every decode step routed every busy slot's token in every sparse
    # layer; all experts are held, so every pair was routed here
    assert counted["decode"]["pairs_all"] == (
        stats["busy_slot_steps"] * sparse * cfg.num_experts_per_tok)
    assert counted["decode"]["pairs_here"] == counted["decode"]["pairs_all"]
    assert counted["prefill"]["pairs_all"] == (
        sum(map(len, asked)) * sparse * cfg.num_experts_per_tok)
    kv = stats["kv_live_bytes"]
    assert 0 < kv["window"] and 0 < kv["global"]
    assert {"mimo_window_cached_xla", "mimo_global_prefill_xla",
            "moe_experts_dense_xla"} <= set(stats["attention_paths"])


def test_a_prompt_through_chunked_prefill(highest):
    cfg, _, params = make()
    FAMILY.served_logits_match(
        cfg, params, list(zip(prompts(cfg, [37, 6]), [14, 14])),
        prefill_chunk_tokens=8)


def test_the_window_pool_does_not_grow_with_the_context(highest):
    cfg, _, params = make()
    sizes = {}
    for longest in (32, 64):
        srv = FAMILY.serving_engine(params, cfg, max_model_len=longest)
        sizes[longest] = {k: v.shape for k, v in srv.cache.items()}
        ring = srv.slot_entries
        srv.destroy()
    assert ring == WINDOW // BLOCK + 1
    for name in ("window_key_pool", "window_value_pool"):
        assert sizes[32][name] == sizes[64][name]
        assert sizes[32][name][1] == 1 + 3 * ring
    assert sizes[32]["global_key_pool"][1] < sizes[64]["global_key_pool"][1]


def test_an_expert_share_serves_only_its_experts(highest):
    """``ep_size`` 4: the program adds the held experts' terms only, as
    the reference given the same share does, and counts about a quarter
    of the pairs as its own."""
    cfg, _, params = make(ep_size=4, ep_rank=1)
    assert params["layers_1_mlp"]["gate"].shape[0] == 8
    stats, _ = FAMILY.served_logits_match(
        cfg, params, list(zip(prompts(cfg, [11, 21]), [16, 16])))
    counted = stats["model_counters"]["decode"]
    assert 0 < counted["pairs_here"] < counted["pairs_all"]
    assert 0 < counted["experts_touched"] < counted["experts_held"]
    assert counted["experts_held"] == 8 * sum(cfg.moe_layer_freq) * (
        stats["decode_steps"])


@pytest.mark.parametrize("chunk", [0, 8], ids=["whole", "chunked"])
def test_the_engine_hands_back_the_routed_sets(highest, chunk):
    """``serving.routed_experts_kept``: for each of the last N finished
    requests, the experts every processed token chose in every sparse
    layer: the prompt's through either kind of prefill, then each decode
    step's, in order. In float32 they are the reference's own."""
    cfg, _, params = make()
    asked = prompts(cfg, [21, 6, 13])
    # an engine of its own: which sets are kept depends on what finished
    # before
    srv = FAMILY.serving_engine(
        params, cfg, routed_experts_kept=2,
        **({"prefill_chunk_tokens": chunk} if chunk else {}))
    try:
        reqs = [srv.submit(p, max_new_tokens=n)
                for p, n in zip(asked, [9, 12, 5])]
        srv.drain()
        # finished in the order 2, 0, 1: the oldest of three is dropped
        assert srv.routed_experts(reqs[2].request_id) is None
        for req, prompt in list(zip(reqs, asked))[:2]:
            FAMILY.routed_sets_are_the_references(srv, cfg, params, req,
                                                  prompt)
            assert req.routed == []
    finally:
        srv.destroy()


def test_without_the_knob_the_programs_return_no_routed_sets(highest):
    cfg, _, params = make()
    srv = FAMILY.shared_engine(params, cfg)
    req = srv.submit(prompts(cfg, [7])[0], max_new_tokens=4)
    srv.drain()
    assert req.routed == [] and srv.routed_experts(req.request_id) is None
    assert not srv._dmodule.config.paged_return_routed


def test_a_dense_model_refuses_routed_experts_kept():
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel

    reset_topology()
    gcfg = GPT2Config(vocab_size=64, n_positions=32, n_embd=16, n_layer=1,
                      n_head=2, dtype=jnp.float32)
    module = GPT2LMHeadModel(gcfg)
    params = jax.jit(module.init)(jax.random.PRNGKey(0),
                                  jnp.zeros((1, 4), jnp.int32))["params"]
    with pytest.raises(Exception, match="routed_experts_kept") as e:
        ServingEngine(deepspeed_tpu.init_inference(
            module, params=params, dtype=jnp.float32,
            serving={"decode_slots": 2, "block_size": 4,
                     "max_model_len": 16, "routed_experts_kept": 4}))
    assert "GPT2LMHeadModel" in str(e.value)


def test_the_reference_takes_routed_sets_handed_in(highest):
    """Its own sets handed back change nothing; a set whose last expert
    is swapped for another moves that position's logits, is flagged, and
    its margin says how far from a tie the swap was."""
    cfg, _, params = make(ep_size=1)
    ids = jnp.asarray([prompts(cfg, [12])[0]])
    shape = shape_of(cfg)
    # (each form of the call ONE program: with sets handed in, without)
    handed = jax.jit(lambda given: reference.logits(
        params, ids, shape, given, with_layers=True))
    own = np.asarray(jax.jit(lambda: reference.routed_sets(
        params, ids, shape))())                                   # [L,1,T,k]
    given = own.transpose(1, 2, 0, 3).copy()
    base = np.asarray(jax.jit(lambda: reference.logits(params, ids,
                                                       shape))())
    same, seen = handed(jnp.asarray(given))
    assert np.abs(np.asarray(same) - base).max() == 0.0
    assert float(seen["margin"].max()) == 0.0 and not bool(
        seen["differs"].any())
    assert seen["inputs"].shape == (own.shape[0], 1, 12, cfg.hidden_size)
    # negative: the reference's own
    unset = np.full_like(given, -1)
    assert np.abs(np.asarray(handed(jnp.asarray(unset))[0])
                  - base).max() == 0.0
    # position 5, second sparse layer: an expert it did not choose
    other = next(e for e in range(cfg.n_routed_experts)
                 if e not in given[0, 5, 1])
    given[0, 5, 1, -1] = other
    moved, seen = handed(jnp.asarray(given))
    moved = np.abs(np.asarray(moved) - base).max(-1)[0]
    assert moved[5] > 10 * TOL and moved[:5].max() == 0.0
    differs = np.asarray(seen["differs"])[:, 0]
    assert differs[1, 5] and differs.sum() == 1
    assert float(seen["margin"][1, 0, 5]) > 0.0


@pytest.mark.parametrize("chunk", [0, 8], ids=["whole-prompt", "chunked"])
def test_paged_logits_match_the_reference(highest, chunk):
    """Prefill then decode through the cache against the reference's full
    forward pass, on LOGITS at every position, the prompt and the decode
    steps both past the window (what ``tools/chip_logits_mimo_v2.py`` does
    on the chip at the published widths)."""
    cfg, _, params = make()
    assert FAMILY.paged_logits_match(
        FAMILY.shared_engine(params, cfg), cfg, params,
        prompts(cfg, [27])[0], 14, chunk=chunk) <= TOL


def test_decode_through_both_kernels_matches_the_xla_paths(monkeypatch):
    """The decode program with the Pallas kernels in it (interpret mode):
    the paged GQA kernel over the block table and over the ring, and the
    grouped expert matmul, against the same steps on the XLA paths."""
    cfg, _, params = make()
    got, want, paths = FAMILY.decode_through_the_kernels(
        monkeypatch, cfg, params, prompts(cfg, [19])[0], 3)
    assert paths.get("mimo_window_decode_kernel") and paths.get(
        "mimo_global_decode_kernel")
    assert np.abs(got - want).max() <= TOL


# ---------------------------------------------------------------------------
# refusals, by name
# ---------------------------------------------------------------------------
@REFUSED
def test_mechanisms_that_know_one_kind_of_row_refuse_the_model(serving,
                                                               mechanism):
    FAMILY.mechanism_refusal(serving, mechanism)


def test_tensor_parallel_refuses_the_model():
    assert "MiMoV2ForCausalLM" in FAMILY.tensor_parallel_refusal()


def test_migration_refuses_the_model():
    assert len(FAMILY.migration_refusals()) == 2


# ---------------------------------------------------------------------------
# the kernels, in interpret mode
# ---------------------------------------------------------------------------
def _interpreted(fn, *args):
    from deepspeed_tpu.utils.compat import tpu_interpret_mode

    with tpu_interpret_mode():
        return jax.block_until_ready(jax.jit(fn)(*args))


# kind, keys a tile aims at (None: the plan's own), lengths a row (None: an
# idle slot, a table of garbage blocks), blocks a row or ring, unnamed blocks
# poisoned. Blocks of 4 keys; a window of 8.
_HYBRID_CASES = {
    # unequal lengths, an idle row and a fresh one, the plan's tile (the
    # whole table, the whole ring: one step a row)
    "global": ("global", None, [None, 3, 9, 30, 0], 9, False),
    "window-sink": ("window-sink", None, [None, 3, 9, 30, 0], 3, False),
    "window": ("window", None, [None, 3, 9, 30, 0], 3, False),
    # tiles of 2 blocks: live prefixes that end in a tile's first block (9
    # keys = 3 blocks, 32 = 9), at its boundary (8 = 2 blocks), in a
    # sequence's only block (1, 3), over the whole table (35)
    "ends-inside-a-tile": ("global", 8, [8, None, 7, 31, 0, 2, 34, 15], 9,
                           False),
    "rows-of-one-block": ("global", 8, [0, 2, None, 3, 1], 9, False),
    # a ring of 3 blocks in tiles of 2, wrapped: the newest block is the
    # tile's second (length 5), its first (9), the lone block of the second
    # step (11); the seam between laps falls inside a tile and between two
    "ring-seam-inside-a-tile": ("window", 8, [5, 9, 11, 14, 30, None, 2], 3,
                                False),
    "ring-seam-sink": ("window-sink", 8, [21, None, 12, 16, 17, 0], 3,
                       False),
    "idle-slots-only": ("global", 8, [None, None, None], 9, False),
    "idle-rings-only": ("window-sink", None, [None, None], 3, False),
    # whatever no table names is NaN: a tile's dead blocks are never read
    "nan-in-unnamed-blocks": ("global", 8, [8, None, 7, 31, 0, 2, 34], 9,
                              True),
    "nan-beside-a-ring": ("window", 8, [5, None, 11, 30], 3, True),
}


@pytest.mark.parametrize("case", _HYBRID_CASES)
def test_hybrid_decode_kernel_matches_the_masked_path(monkeypatch, case):
    """GQA (4 queries a KV head), keys 24 wide and values 16, against the
    masked XLA path over the gathered rows: the three kinds at the plan's
    tile, and what a tile brings (``_HYBRID_CASES``)."""
    from deepspeed_tpu.models.blocks import masked_gqa
    from deepspeed_tpu.ops import hybrid_decode_attention as hda

    kind, tile_keys, lengths, mb, poison = _HYBRID_CASES[case]
    if tile_keys:
        monkeypatch.setattr(hda, "HYBRID_TILE_KEYS", tile_keys)
    window = 0 if kind == "global" else WINDOW
    heads, kv, dk, dv, bs = 8, 2, 24, 16, BLOCK
    idle = np.asarray([n is None for n in lengths])
    lengths = np.asarray([n or 0 for n in lengths], np.int32)
    b = len(lengths)
    assert hda.hybrid_plan(bs, kv * dk, kv * dv, mb).tile_blocks == (
        tile_keys // bs if tile_keys else mb)
    rng = np.random.default_rng(7)
    k_pool = rng.standard_normal((2, 1 + b * mb, bs, kv * dk), np.float32)
    v_pool = rng.standard_normal((2, 1 + b * mb, bs, kv * dv), np.float32)
    tables = 1 + np.arange(b * mb, dtype=np.int32).reshape(b, mb)
    # what says a slot is idle is its sequence's table; its ring stays its
    # own (the engine zeroes a dead row's whole table: either is served)
    said_by = tables.copy()
    said_by[idle] = 0
    if not window:
        tables = said_by.copy()
        # a table names a sequence's live blocks and pads with the garbage
        # block
        live = -(-(lengths + 1) // bs)
        tables[np.arange(mb)[None] >= live[:, None]] = 0
    q = rng.standard_normal((b, 1, heads, dk), np.float32)
    sink = (rng.standard_normal(heads).astype(np.float32)
            if kind == "window-sink" else None)

    def run(k_pool, v_pool):
        plan = hda.hybrid_plan(bs, kv * dk, kv * dv, mb)
        return np.asarray(_interpreted(
            lambda q, k, v, t, said, n: hda.decode_attention_hybrid(
                q, k, v, t, n, 1, kv_heads=kv, window=window,
                ring=bool(window),
                sink=None if sink is None else jnp.asarray(sink),
                work=hda.hybrid_work_list(n, said, plan)),
            *map(jnp.asarray, (q, k_pool, v_pool, tables, said_by,
                               lengths))))

    got = run(k_pool, v_pool)
    rows = mb * bs
    keys = k_pool[1][tables].reshape(b, rows, kv, dk)
    vals = v_pool[1][tables].reshape(b, rows, kv, dv)
    # the pool already holds the step's own key at position L
    held = (np.asarray(hda.ring_positions(lengths + 1, rows)) if window
            else np.broadcast_to(np.arange(rows), (b, rows)))
    want = np.asarray(masked_gqa(
        jnp.asarray(q), jnp.asarray(keys), jnp.asarray(vals),
        jnp.asarray(lengths)[:, None], jnp.asarray(held),
        jnp.asarray(held >= 0), window,
        None if sink is None else jnp.asarray(sink)))
    if (~idle).any():
        assert np.abs(got[~idle] - want[~idle]).max() <= 1e-5
    assert not got[idle].any()       # an idle slot: zeros, no arithmetic
    if poison:
        # named: the live blocks of a busy row (a ring not yet full has
        # dead blocks too) and, by a table's padding, the garbage block
        named = np.zeros(k_pool.shape[1], bool)
        named[0] = not window
        live = np.minimum(-(-(lengths + 1) // bs), mb)
        for r in np.flatnonzero(~idle):
            named[tables[r, :live[r]]] = True
        assert (~named).sum() >= b
        k_pool[:, ~named] = np.nan
        v_pool[:, ~named] = np.nan
        np.testing.assert_array_equal(run(k_pool, v_pool), got)


@pytest.mark.parametrize("tokens, only", [(24, None), (24, 5), (3, None)],
                         ids=["spread", "one-expert", "few-tokens"])
def test_grouped_expert_kernel_matches_the_dense_form(tokens, only):
    """Dropless groups of unequal size, experts no token chose (empty
    groups: never read), pairs routed elsewhere, tokens that are not
    real."""
    d, f, held, n_routed, k = 64, 128, 8, 32, 4
    keys = jax.random.split(jax.random.PRNGKey(11), 6)
    x = jax.random.normal(keys[0], (tokens, d))
    gate, up = (0.2 * jax.random.normal(kk, (held, d, f)) for kk in keys[1:3])
    down = 0.2 * jax.random.normal(keys[3], (held, f, d))
    experts = jnp.argsort(jax.random.uniform(keys[4], (tokens, n_routed)),
                          axis=1)[:, :k].astype(jnp.int32)
    if only is not None:   # every held pair on one expert
        experts = jnp.where((experts >= 8) & (experts < 16), 8 + only,
                            experts)
    weights = jax.nn.softmax(jax.random.normal(keys[5], (tokens, k)))
    valid = jnp.arange(tokens) != 1

    def run(use_kernel):
        return lambda *a: dropless.expert_ffn(
            *a, first_expert=8, valid=valid, n_routed=n_routed,
            use_kernel=use_kernel)

    args = (x, experts, weights, gate, up, down)
    got, counted = _interpreted(run(True), *args)
    want, counted_dense = jax.jit(run(False))(*args)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() <= 1e-4
    assert np.array_equal(np.asarray(counted), np.asarray(counted_dense))
    here = np.asarray((experts >= 8) & (experts < 16) & valid[:, None])
    assert int(counted[2]) == here.sum() and int(counted[3]) == (
        (tokens - 1) * k) and int(counted[1]) == held
    if only is not None:
        assert int(counted[0]) == (1 if here.any() else 0)
    assert not np.asarray(got)[1].any()    # the token that is not real


def test_no_pair_routed_here_adds_nothing():
    d, f = 64, 128
    x = jnp.ones((4, d))
    w = 0.1 * jnp.ones((2, d, f))
    experts = jnp.full((4, 2), 9, jnp.int32)   # held: 0 and 1
    got, counted = _interpreted(
        lambda *a: dropless.expert_ffn(*a, first_expert=0, n_routed=16,
                                       use_kernel=True),
        x, experts, jnp.full((4, 2), 0.5), w, w, w.transpose(0, 2, 1))
    assert not np.asarray(got).any()
    assert np.asarray(counted).tolist() == [0, 2, 0, 8]


def test_route_is_float32_sigmoid_top_k_with_the_bias():
    x = jnp.asarray([[1.0, 0.0], [0.0, 1.0]], jnp.bfloat16)
    router = jnp.asarray([[2.0, 1.0, 0.0, -1.0], [0.0, 0.0, 0.0, 0.0]])
    bias = jnp.asarray([0.0, 0.0, 0.0, 1.0])
    experts, weights = dropless.route(x, router, bias, 2)
    s = jax.nn.sigmoid(jnp.asarray([2.0, 1.0, 0.0, -1.0]))
    # token 0: the bias lifts expert 3 over expert 1; the WEIGHT is the
    # unbiased score, renormalised
    assert experts[0].tolist() == [3, 0] and weights.dtype == jnp.float32
    assert np.allclose(weights[0], np.asarray([s[3], s[0]]) / (s[3] + s[0]))
    assert experts[1].tolist() == [3, 0]   # all 0.5 + bias; ties: lowest id
