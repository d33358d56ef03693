"""The LFM2-MoE family at a small size on the CPU: the program in float32
against the plain reference (``perfbench/reference_lfm2_moe.py``) on
LOGITS: the plain call and each operator alone; prefill then decode
through the paged cache AND the convolutions' per-slot state (prompts that
do not fill their bucket, a chunked prompt, a slot's next and shorter
request, 64 decode steps beside idle slots); the one convolution function
in its three forms; the router against its equations; the state's bytes
in the engine's ledger; the controls that the comparisons are not blind
to; and the mechanisms that refuse the model by name."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import lfm2_moe
from deepspeed_tpu.models.lfm2_moe import (Lfm2Attention, Lfm2MoeConfig,
                                           Lfm2MoeForCausalLM, ShortConv)
from deepspeed_tpu.models.mimo_v2 import SparseExperts
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.parallel.topology import reset_topology
from deepspeed_tpu.serving import ServingEngine
from perfbench import reference_lfm2_moe as reference
from tests.unit.served_family import REFUSED, Family, highest, prompts  # noqa: F401

# float32 program against the float32 reference, on logits of order 1: what
# another order of summation leaves (the two agree to 4e-7 here)
TOL = 1e-4
BLOCK = 4


def shape_of(cfg: Lfm2MoeConfig) -> dict:
    """The reference's view of a program config (the family builds the
    same from a configuration file)."""
    return dict(heads=cfg.num_attention_heads,
                kv_heads=cfg.num_key_value_heads, eps=cfg.norm_eps,
                rope_theta=cfg.rope_theta, top_k=cfg.num_experts_per_tok,
                route_eps=cfg.route_norm_eps,
                route_scale=cfg.routed_scaling_factor,
                types=cfg.layer_types, dense=cfg.num_dense_layers)


FAMILY = Family(Lfm2MoeConfig, Lfm2MoeForCausalLM, reference, shape_of, TOL,
                serving={"decode_slots": 3, "block_size": BLOCK,
                         "max_model_len": 64})
engines = FAMILY.engines()
make, reference_logits = FAMILY.make, FAMILY.reference_logits


# ---------------------------------------------------------------------------
# the plain call, and each operator alone
# ---------------------------------------------------------------------------
def test_full_forward_matches_the_reference(highest):
    cfg, module, params = make()
    ids = np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 40))
    got = np.asarray(FAMILY.plain(cfg)(params, jnp.asarray(ids)))
    assert np.abs(got - reference_logits(cfg, params, ids)).max() <= TOL
    # tied: no head of its own
    assert "lm_head" not in params and "layers_2_attn" in params
    assert set(params["layers_0_conv"]) == {"in_proj", "conv", "out_proj"}


@pytest.mark.parametrize("operator", ["conv", "attention", "sparse"])
def test_an_operator_alone_matches_the_reference(highest, operator):
    cfg, _, params = make()
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 21, cfg.hidden_size))
    shape = shape_of(cfg)
    if operator == "conv":
        p = params["layers_0_conv"]
        got, state = ShortConv(cfg).apply({"params": p}, x)
        want = reference.short_conv(x, p)
        # the state a whole sequence leaves: z at its last two positions
        b, _, xx = lfm2_moe.gated_inputs(x, p["in_proj"])
        assert np.abs(np.asarray(state - (b * xx)[:, -2:])).max() == 0.0
    elif operator == "attention":
        p = params["layers_2_attn"]
        got, _ = Lfm2Attention(cfg).apply({"params": p}, x)
        want = reference.attention(x, p, shape)
    else:
        p = params["layers_3_mlp"]
        got, _, chosen = SparseExperts(cfg).apply({"params": p}, x)
        want, picked, _ = reference.sparse(x, p, shape)
        assert (np.sort(chosen, -1) == np.sort(picked, -1)).all()
    scale = float(np.abs(np.asarray(want)).max())
    assert np.abs(np.asarray(got - want)).max() <= TOL * max(scale, 1.0)
    assert scale > 0


def test_every_operator_moves_the_logits(highest):
    """The comparison above is not blind to any of them: with the weights
    this size's preset draws, zeroing one convolution's taps, one
    attention's values or one sparse layer's experts moves the logits by
    several times the tolerance (the experts least: 32 of width 32 at 0.02
    beside convolutions that carry the stream)."""
    cfg, module, params = make()
    ids = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab_size, (1, 24)))
    base = np.asarray(FAMILY.plain(cfg)(params, ids))
    for layer, leaf in (("layers_3_conv", "conv"), ("layers_2_attn", "v_proj"),
                        ("layers_4_mlp", "down")):
        moved = dict(params)
        moved[layer] = {**params[layer], leaf: jax.tree_util.tree_map(
            jnp.zeros_like, params[layer][leaf])}
        got = np.asarray(FAMILY.plain(cfg)(moved, ids))
        assert np.abs(got - base).max() > 5 * TOL, layer


def test_bf16_fails_the_float32_tolerance():
    """The lower-precision control: the same comparison with the program
    in bfloat16 is outside the tolerance, so the tolerance tells them
    apart."""
    cfg, _, params = make()
    ids = np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 40))
    low = FAMILY.plain(dataclasses.replace(cfg, dtype=jnp.bfloat16))
    got = np.asarray(low(params, jnp.asarray(ids)))
    assert np.abs(got - reference_logits(cfg, params, ids)).max() > 10 * TOL


# ---------------------------------------------------------------------------
# one convolution function, three programs
# ---------------------------------------------------------------------------
def test_the_convolution_in_pieces_is_the_convolution_whole(highest):
    """A whole prompt (state zero), chunks that hand the state on, and
    steps of one position give the same output; the state is taken at
    each row's ``num_valid``, never at the bucket's end."""
    rng = np.random.default_rng(3)
    z = jnp.asarray(rng.normal(size=(2, 12, 16)), jnp.float32)
    taps = jnp.asarray(rng.normal(size=(16, 3)), jnp.float32)
    zero = jnp.zeros((2, 2, 16))
    full = jnp.full((2,), 12, jnp.int32)
    whole, last = lfm2_moe.short_conv(z, taps, zero, full)
    assert np.abs(np.asarray(last - z[:, -2:])).max() == 0.0
    # plain arithmetic: tap 2 meets the current position
    want = (taps[:, 2] * z[:, 5] + taps[:, 1] * z[:, 4]
            + taps[:, 0] * z[:, 3])
    assert np.abs(np.asarray(whole[:, 5] - want)).max() <= 1e-6
    state, pieces = zero, []
    for at, n in ((0, 5), (5, 1), (6, 1), (7, 5)):
        out, state = lfm2_moe.short_conv(
            z[:, at:at + n], taps, state, jnp.full((2,), n, jnp.int32))
        pieces.append(out)
    assert np.abs(np.asarray(jnp.concatenate(pieces, 1) - whole)).max() <= 1e-6
    assert np.abs(np.asarray(state - last)).max() == 0.0
    # a bucket of 12 holding 7 and 1 real positions, and a row with none
    valid = jnp.asarray([7, 1], jnp.int32)
    _, kept = lfm2_moe.short_conv(z, taps, zero + 5.0, valid)
    assert np.abs(np.asarray(kept[0] - z[0, 5:7])).max() == 0.0
    assert np.abs(np.asarray(kept[1, 1] - z[1, 0])).max() == 0.0
    assert np.abs(np.asarray(kept[1, 0] - 5.0)).max() == 0.0
    _, kept = lfm2_moe.short_conv(z, taps, zero + 5.0,
                                  jnp.zeros((2,), jnp.int32))
    assert np.abs(np.asarray(kept - 5.0)).max() == 0.0


# ---------------------------------------------------------------------------
# the router
# ---------------------------------------------------------------------------
def test_the_router_follows_its_equations():
    """Top 4 of 32 by sigmoid score + bias; the weights are the scores
    WITHOUT the bias over their sum + 1e-6, times the scaling factor."""
    rng = np.random.default_rng(7)
    x = rng.normal(size=(9, 16)).astype(np.float32)
    w = rng.normal(size=(16, 32)).astype(np.float32)
    bias = (0.3 * rng.normal(size=(32,))).astype(np.float32)
    experts, weights = dropless.route(jnp.asarray(x), jnp.asarray(w),
                                      jnp.asarray(bias), 4, norm_eps=1e-6,
                                      scale=2.5)
    scores = 1.0 / (1.0 + np.exp(-(x.astype(np.float64) @ w)))
    want = np.argsort(-(scores + bias), axis=1)[:, :4]
    assert (np.sort(np.asarray(experts), 1) == np.sort(want, 1)).all()
    # the bias chooses: without it some token's set is another
    plain, _ = dropless.route(jnp.asarray(x), jnp.asarray(w),
                              jnp.zeros((32,)), 4)
    assert (np.sort(np.asarray(plain), 1) != np.sort(want, 1)).any()
    picked = np.take_along_axis(scores, np.asarray(experts), 1)
    assert np.allclose(np.asarray(weights), 2.5 * picked / (
        picked.sum(1, keepdims=True) + 1e-6), rtol=1e-5)
    assert np.asarray(weights).sum(1).max() < 2.5   # the 1e-6 is there


def test_the_other_familys_router_is_the_program_it_was():
    """``route`` at its defaults traces the operations it traced before
    the two constants came (MiMo-V2's programs are the parent's text): no
    add of an epsilon, no multiply by a scale."""
    x, w, b = jnp.ones((5, 8)), jnp.ones((8, 16)), jnp.zeros((16,))

    def was(x, router_kernel, selection_bias, top_k=4):
        scores = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), router_kernel.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        _, experts = jax.lax.top_k(
            scores + selection_bias.astype(jnp.float32)[None], top_k)
        chosen = jnp.take_along_axis(scores, experts, axis=1)
        return (experts.astype(jnp.int32),
                chosen / jnp.sum(chosen, axis=1, keepdims=True))

    now = lambda x, w, b: dropless.route(x, w, b, 4)
    given = lambda x, w, b: dropless.route(x, w, b, 4, norm_eps=0.0,
                                           scale=1.0)
    text = str(jax.make_jaxpr(was)(x, w, b))
    assert str(jax.make_jaxpr(now)(x, w, b)) == text
    assert str(jax.make_jaxpr(given)(x, w, b)) == text
    other = lambda x, w, b: dropless.route(x, w, b, 4, norm_eps=1e-6)
    assert str(jax.make_jaxpr(other)(x, w, b)) != text


@pytest.mark.parametrize("width, tile", [(2048, 512), (1792, 896), (32, 32),
                                         (1536, 512), (640, 640), (96, 96)])
def test_an_experts_width_is_tiled_in_whole_registers(width, tile):
    """2048 (MiMo-V2) keeps its 512; 1792 = 2 x 896, which 512 does not
    divide; a narrow test width is one tile."""
    assert dropless.width_tile(width) == tile and width % tile == 0


# ---------------------------------------------------------------------------
# through the paged cache and the per-slot state
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("chunk", [0, 8], ids=["whole-prompt", "chunked"])
def test_paged_logits_match_the_reference(highest, chunk):
    """Prefill then decode through the cache and the state against the
    reference's full forward pass, on LOGITS at every position: a prompt
    of 27 in a bucket of 40 (the state is z_25, z_26, not the padding's),
    or in chunks of 8 (each past the first starts from the stored state,
    the last holds 3 real positions)."""
    cfg, _, params = make()
    assert FAMILY.paged_logits_match(
        FAMILY.shared_engine(params, cfg), cfg, params,
        prompts(cfg, [27])[0], 14, chunk=chunk) <= TOL


def test_a_slots_next_request_does_not_see_its_last_ones_state(highest):
    """Two requests one after the other in ONE slot, the second shorter
    than the first, whole-prompt and chunked: each is the reference's."""
    cfg, _, params = make()
    srv = FAMILY.shared_engine(params, cfg)
    first, second, third = prompts(cfg, [30, 7, 11])
    for prompt, chunk in ((first, 0), (second, 0), (third, 8)):
        assert FAMILY.paged_logits_match(srv, cfg, params, prompt, 5, slot=2,
                                         chunk=chunk) <= TOL, len(prompt)


@pytest.mark.parametrize("control", ["stale", "bucket-end"])
def test_a_wrong_state_moves_the_logits(highest, monkeypatch, control):
    """The comparisons above are not blind to the state: not restarted
    for the slot's next request, or taken at the bucket's end, it moves
    the logits far outside the tolerance."""
    cfg, _, params = make()
    if control == "stale":
        monkeypatch.setattr(
            lfm2_moe, "conv_state_in",
            lambda pool, index, rows, lengths: pool[index, rows])
    else:
        plain = lfm2_moe.short_conv
        monkeypatch.setattr(
            lfm2_moe, "short_conv",
            lambda z, taps, state, num_valid: plain(
                z, taps, state, jnp.full_like(num_valid, z.shape[1])))
    # the shared engine, its paged module traced anew under the patch
    srv = FAMILY.shared_engine(params, cfg)
    first, second = prompts(cfg, [30, 7])
    FAMILY.paged_logits(srv, first, 5, slot=2, retrace=True)
    assert FAMILY.paged_logits_match(srv, cfg, params, second, 5, slot=2,
                                     retrace=True) > 100 * TOL


def test_prefill_and_decode_through_the_engine(highest):
    """Prompts that do not fill their buckets (5 in 8, 19 in 32, 33 in
    64, ...), slots of unequal length, slots reused after a finish (5
    requests over 3 slots), through ``init_inference`` ->
    ``ServingEngine``; and the engine's counters."""
    cfg, _, params = make()
    asked = prompts(cfg, [5, 19, 33, 9, 26])
    stats, _ = FAMILY.served_logits_match(
        cfg, params, list(zip(asked, [30, 12, 20, 25, 8])))
    counted = stats["model_counters"]
    sparse, k = cfg.sparse_layers, cfg.num_experts_per_tok
    assert counted["decode"]["pairs_all"] == (
        stats["busy_slot_steps"] * sparse * k)
    # every expert held: every pair routed here
    assert counted["decode"]["pairs_here"] == counted["decode"]["pairs_all"]
    assert counted["prefill"]["pairs_all"] == (
        sum(map(len, asked)) * sparse * k)
    kv = stats["kv_live_bytes"]
    # host arithmetic at each step boundary: busy slots x the state's bytes
    assert kv["state"] == stats["busy_slot_steps"] * cfg.state_bytes_per_slot()
    assert cfg.state_bytes_per_slot() == 4 * 2 * cfg.hidden_size * 4
    assert 0 < kv["global"] and "window" not in kv
    assert {"lfm2_conv_prefill", "lfm2_conv_decode", "lfm2_attn_prefill_xla",
            "lfm2_attn_cached_xla", "moe_experts_dense_xla"} <= set(
        stats["attention_paths"])


def test_a_prompt_chunked_and_unchunked_serves_the_same_tokens(highest):
    cfg, _, params = make()
    requests = list(zip(prompts(cfg, [37, 6, 21]), [14, 14, 9]))
    _, whole = FAMILY.served_logits_match(cfg, params, requests)
    stats, chunked = FAMILY.served_logits_match(cfg, params, requests,
                                                prefill_chunk_tokens=8)
    assert [r.tokens for r in whole] == [r.tokens for r in chunked]
    assert stats["attention_paths"].get("lfm2_conv_chunk")
    assert max(r.prefill_chunks for r in chunked) == 5


def test_two_requests_one_after_the_other_on_one_slot(highest):
    """One decode slot: the second request, shorter than the first, is
    spliced into the slot the first left."""
    cfg, _, params = make()
    long, short = prompts(cfg, [29, 6])
    _, reqs = FAMILY.served_logits_match(
        cfg, params, [(long, 9), (short, 12)], decode_slots=1)
    assert reqs[0].slot == reqs[1].slot == 0


def test_sixty_four_decode_steps_beside_idle_slots(highest):
    """One sequence decodes 64 steps in a batch of four slots, three of
    them idle (their rows read and write state row 0 and route nowhere);
    a second joins and leaves meanwhile."""
    cfg, _, params = make()
    a, b = prompts(cfg, [9, 5])
    stats, _ = FAMILY.served_logits_match(
        cfg, params, [(a, 65), (b, 7)], decode_slots=4, max_model_len=96)
    assert stats["decode_steps"] >= 64
    assert stats["busy_slot_steps"] < 2 * stats["decode_steps"]


def test_the_state_pool_does_not_grow_with_the_context(highest):
    cfg, _, params = make()
    sizes = {}
    for longest in (32, 64):
        srv = FAMILY.serving_engine(params, cfg, max_model_len=longest)
        sizes[longest] = {k: v.shape for k, v in srv.cache.items()}
        entries = srv.slot_entries
        table = srv._slot_table(2, np.arange(3))
        srv.destroy()
    # a row a slot behind the idle rows' row 0; the table's last entry
    assert entries == 1 and table.tolist() == [0, 1, 2, 3]
    assert sizes[32]["conv_state_pool"] == sizes[64]["conv_state_pool"] == (
        4, 1 + 3, 2, cfg.hidden_size)
    assert sizes[32]["global_key_pool"][1] < sizes[64]["global_key_pool"][1]
    assert sizes[64]["global_key_pool"][0] == 2


def test_the_engine_hands_back_the_routed_sets(highest):
    cfg, _, params = make()
    asked = prompts(cfg, [21, 6])
    srv = FAMILY.shared_engine(params, cfg, routed_experts_kept=4)
    reqs = [srv.submit(p, max_new_tokens=n) for p, n in zip(asked, [9, 12])]
    srv.drain()
    for req, prompt in zip(reqs, asked):
        FAMILY.routed_sets_are_the_references(srv, cfg, params, req, prompt)


def test_decode_through_both_kernels_matches_the_xla_paths(monkeypatch):
    """The decode program with the Pallas kernels in it (interpret mode):
    the paged GQA kernel over the block table (its global kind, keys and
    values both 64... here 8 wide) and the grouped expert matmul, against
    the same steps on the XLA paths."""
    cfg, _, params = make()
    got, want, paths = FAMILY.decode_through_the_kernels(
        monkeypatch, cfg, params, prompts(cfg, [19])[0], 3)
    assert paths.get("lfm2_attn_decode_kernel") and paths.get(
        "moe_experts_grouped_kernel")
    assert np.abs(got - want).max() <= TOL


# ---------------------------------------------------------------------------
# refusals, by name
# ---------------------------------------------------------------------------
@REFUSED
def test_mechanisms_that_know_block_tables_only_refuse_the_model(serving,
                                                                 mechanism):
    assert "short-convolution layers keep a state" in (
        FAMILY.mechanism_refusal(serving, mechanism))


def test_tensor_parallel_refuses_the_model():
    assert "Lfm2MoeForCausalLM" in FAMILY.tensor_parallel_refusal()


def test_migration_refuses_the_model():
    assert all("short-convolution" in said
               for said in FAMILY.migration_refusals())


def test_the_other_familys_refusal_names_its_ring():
    """One check over the state a slot keeps beside the block table: the
    message says which state, in the model's own words."""
    from deepspeed_tpu.models.mimo_v2 import MiMoV2Config, MiMoV2ForCausalLM

    cfg = MiMoV2Config.tiny(dtype=jnp.float32)
    module = MiMoV2ForCausalLM(cfg)
    params = jax.jit(module.init)(jax.random.PRNGKey(0),
                                  jnp.zeros((1, 8), jnp.int32))["params"]
    reset_topology()
    with pytest.raises(Exception, match=r"serving\.prefix_cache") as e:
        ServingEngine(deepspeed_tpu.init_inference(
            module, params=params, dtype=cfg.dtype,
            serving={"decode_slots": 2, "block_size": BLOCK,
                     "max_model_len": 32, "prefix_cache": True}))
    assert "in a ring a decode slot" in str(e.value)
    assert "MiMoV2ForCausalLM" in str(e.value)


def test_for_paged_decode_refuses_what_it_cannot_size():
    cfg = Lfm2MoeConfig.tiny()
    with pytest.raises(ValueError, match="state_slots"):
        cfg.for_paged_decode(9, 4)
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        cfg.for_paged_decode(9, 4, kv_dtype="int8", state_slots=2)
    with pytest.raises(ValueError, match="layer_types"):
        Lfm2MoeConfig.tiny(layer_types=("conv", "window"))
    assert cfg.paged_slot_state_for(4)["entries"] == 1
    none = Lfm2MoeConfig.tiny(num_hidden_layers=1,
                              layer_types=("full_attention",))
    assert none.paged_slot_state_for(4) is None


def test_weights_that_lie_where_the_policy_wants_them_are_not_copied():
    """``init_inference`` takes a tree made on the one device of its mesh
    as it lies (the same buffers under the mesh's sharding): 9.33 GB of
    weights do not fit a chip beside a second copy of themselves. A tree
    that lies elsewhere is placed as before."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from deepspeed_tpu.module_inject.policies import shard_params_with_policy

    tree = jax.jit(lambda k: {"a": {"kernel": jax.random.normal(k, (8, 4))},
                              "b": jnp.arange(6.0)})(jax.random.PRNGKey(0))
    one = Mesh(np.array(jax.devices()[:1]), ("tp",))
    placed, shardings = shard_params_with_policy(tree, "gpt2", one)
    for got, was in zip(jax.tree_util.tree_leaves(placed),
                        jax.tree_util.tree_leaves(tree)):
        assert isinstance(got.sharding, NamedSharding)
        assert got.unsafe_buffer_pointer() == was.unsafe_buffer_pointer()
        assert not was.is_deleted()
    assert jax.tree_util.tree_leaves(shardings)[0] == NamedSharding(one, P())
    if len(jax.devices()) > 1:
        every = Mesh(np.array(jax.devices()), ("tp",))
        moved, _ = shard_params_with_policy(tree, "gpt2", every)
        leaf = jax.tree_util.tree_leaves(moved)[1]
        assert len(leaf.addressable_shards) == len(jax.devices())
        assert np.array_equal(np.asarray(leaf), np.arange(6.0))
    host, _ = shard_params_with_policy({"b": np.arange(6.0)}, "gpt2", one)
    assert np.array_equal(np.asarray(host["b"]), np.arange(6.0))
