"""The DeepSeek-V2 family at a small size on the CPU: the program in float32
against the plain reference (``perfbench/reference_deepseek_v2.py``) on
LOGITS: the plain call and each operator alone; prefill then decode
through the paged latent pool (a whole prompt in a bucket it does not fill;
chunks that divide the prompt, chunks that do not, a chunk boundary inside
a block); the absorbed decode against the decompressed form and the Pallas
kernel against both; idle rows and a pool full of NaN; the softmax router
against its equations and over expert-parallel shares; YaRN's frequencies
against the published formulas; the latent bytes in the engine's ledger;
the controls that the comparisons are not blind to; and the mechanisms
that refuse the model by name."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import deepseek_v2
from deepspeed_tpu.models.deepseek_v2 import (DeepseekV2Config,
                                              DeepseekV2ForCausalLM,
                                              LatentAttention, SparseExperts,
                                              YarnScaling)
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.ops import latent_decode_attention
from perfbench import reference_deepseek_v2 as reference
from tests.unit.served_family import REFUSED, Family, highest, prompts  # noqa: F401

# float32 program against the float32 reference, on logits of order 1: what
# another order of summation leaves (the two agree to 3e-7 here)
TOL = 1e-4
BLOCK = 4


def shape_of(cfg: DeepseekV2Config, first_expert: int = 0) -> dict:
    """The reference's view of a program config (the family builds the
    same from a configuration file)."""
    yarn = cfg.rope_scaling
    return dict(layers=cfg.num_hidden_layers, heads=cfg.num_attention_heads,
                nope=cfg.qk_nope_head_dim, rope=cfg.qk_rope_head_dim,
                v_dim=cfg.v_head_dim, rank=cfg.kv_lora_rank,
                eps=cfg.rms_norm_eps, rope_theta=cfg.rope_theta,
                yarn=None if yarn is None else dataclasses.asdict(yarn),
                top_k=cfg.num_experts_per_tok,
                route_scale=cfg.routed_scaling_factor,
                dense=cfg.first_k_dense_replace, first_expert=first_expert)


FAMILY = Family(DeepseekV2Config, DeepseekV2ForCausalLM, reference, shape_of,
                TOL, serving={"decode_slots": 3, "block_size": BLOCK,
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
    # an untied head; layer 0 dense, the others sparse with shared experts
    assert "lm_head" in params and "router" not in params["layers_0_mlp"]
    assert set(params["layers_1_mlp"]) == {"router", "gate", "up", "down",
                                           "shared_experts"}
    assert set(params["layers_0_attn"]) == {
        "q_proj", "kv_a_proj_with_mqa", "kv_a_layernorm", "kv_b_proj",
        "o_proj"}


@pytest.mark.parametrize("operator", ["attention", "sparse"])
def test_an_operator_alone_matches_the_reference(highest, operator):
    cfg, _, params = make()
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 21, cfg.hidden_size))
    shape = shape_of(cfg)
    if operator == "attention":
        p = params["layers_2_attn"]
        got, _ = LatentAttention(cfg).apply({"params": p}, x)
        want = reference.attention(x, p, shape)
    else:
        p = params["layers_3_mlp"]
        y, shared, _, chosen = SparseExperts(cfg).apply({"params": p}, x)
        got = y + shared
        want, picked, _ = reference.sparse(x, p, shape)
        assert (np.sort(chosen, -1) == np.sort(picked, -1)).all()
    scale = float(np.abs(np.asarray(want)).max())
    assert np.abs(np.asarray(got - want)).max() <= TOL * max(scale, 1.0)
    assert scale > 0


def test_every_operator_moves_the_logits(highest):
    """The comparison above is not blind to any of them: zeroing one
    layer's ``W_kvb``, its rope key, one sparse layer's experts or its
    shared experts moves the logits by several times the tolerance."""
    cfg, module, params = make()
    ids = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab_size, (1, 24)))
    base = np.asarray(FAMILY.plain(cfg)(params, ids))

    def zeroed(layer, leaf, keep=None):
        moved = dict(params)
        was = params[layer][leaf]
        new = jax.tree_util.tree_map(jnp.zeros_like, was)
        if keep is not None:       # zero only the columns past ``keep``
            new = {"kernel": was["kernel"].at[:, keep:].set(0.0)}
        moved[layer] = {**params[layer], leaf: new}
        return np.asarray(FAMILY.plain(cfg)(moved, ids))

    for layer, leaf, keep in (
            ("layers_1_attn", "kv_b_proj", None),
            ("layers_1_attn", "kv_a_proj_with_mqa", cfg.kv_lora_rank),
            ("layers_2_mlp", "down", None),
            ("layers_2_mlp", "shared_experts", None)):
        assert np.abs(zeroed(layer, leaf, keep) - base).max() > 5 * TOL, (
            layer, leaf)


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
# the rotation and its frequencies
# ---------------------------------------------------------------------------
def test_yarn_frequencies_follow_the_published_formulas():
    """The source's ``DeepseekV2YarnRotaryEmbedding`` in numpy, at the
    published sizes: correction dims 10 and 23 of 32, the blend between,
    cos and sin times mscale / mscale_all_dim = 1; the softmax scale
    0.1147."""
    dim, theta, s = 64, 10000.0, YarnScaling()
    inv, factor = deepseek_v2.yarn_frequencies(dim, theta, s)

    def where(rot):
        return dim * math.log(s.original_max_position_embeddings
                              / (rot * 2 * math.pi)) / (2 * math.log(theta))

    low, high = math.floor(where(s.beta_fast)), math.ceil(where(s.beta_slow))
    assert (low, high) == (10, 23)
    extra = 1.0 / theta ** (np.arange(0, dim, 2) / dim)
    mask = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    want = extra / s.factor * (1 - mask) + extra * mask
    assert np.allclose(np.asarray(inv), want, rtol=1e-6)
    assert np.allclose(want[:10], extra[:10]) and np.allclose(
        want[24:], extra[24:] / 40)
    assert factor == 1.0
    assert DeepseekV2Config().softmax_scale == pytest.approx(
        192 ** -0.5 * (0.1 * 0.707 * math.log(40) + 1) ** 2)
    ref_inv, ref_factor = reference.yarn(dim, theta, dataclasses.asdict(s))
    assert np.allclose(np.asarray(ref_inv), want, rtol=1e-6)
    assert ref_factor == 1.0


def test_the_rotation_deinterleaves_its_pairs():
    """``(x_0, x_1)`` is the first pair: it lands on lanes 0 and rope / 2,
    rotated by the first frequency."""
    cfg = DeepseekV2Config.tiny(dtype=jnp.float32, rope_scaling=None)
    x = np.zeros((1, 1, cfg.qk_rope_head_dim), np.float32)
    x[0, 0, 0], x[0, 0, 1] = 1.0, 2.0
    got = np.asarray(deepseek_v2.rotate_pairs(
        jnp.asarray(x), jnp.asarray([[3]]), cfg))[0, 0]
    half = cfg.qk_rope_head_dim // 2
    c, s = math.cos(3.0), math.sin(3.0)
    assert got[0] == pytest.approx(c - 2 * s) and got[half] == pytest.approx(
        2 * c + s)
    assert np.abs(np.delete(got, [0, half])).max() == 0.0


# ---------------------------------------------------------------------------
# the router
# ---------------------------------------------------------------------------
def test_the_router_follows_its_equations():
    """Softmax over all the experts in float32, the top k chosen, the
    weights the probabilities AS THEY STAND times the scaling factor: not
    renormalised, no bias."""
    rng = np.random.default_rng(7)
    x = rng.normal(size=(9, 16)).astype(np.float32)
    w = rng.normal(size=(16, 32)).astype(np.float32)
    experts, weights = dropless.route(
        jnp.asarray(x), jnp.asarray(w), None, 6, scale=2.5,
        scoring="softmax", renormalize=False)
    logits = x.astype(np.float64) @ w
    probs = np.exp(logits - logits.max(1, keepdims=True))
    probs /= probs.sum(1, keepdims=True)
    want = np.argsort(-probs, axis=1)[:, :6]
    assert (np.sort(np.asarray(experts), 1) == np.sort(want, 1)).all()
    picked = np.take_along_axis(probs, np.asarray(experts), 1)
    assert np.allclose(np.asarray(weights), 2.5 * picked, rtol=1e-5)
    assert (np.asarray(weights).sum(1) < 2.5).all()      # not renormalised
    with pytest.raises(ValueError, match="scoring"):
        dropless.route(jnp.asarray(x), jnp.asarray(w), None, 6,
                       scoring="tanh")


def test_the_other_families_router_is_the_program_it_was():
    """``route`` at its defaults, and with the new arguments spelled out
    at their defaults, traces the operations it traced before they came
    (MiMo-V2's and LFM2's programs are the parent's text)."""
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

    text = str(jax.make_jaxpr(was)(x, w, b))
    now = lambda x, w, b: dropless.route(x, w, b, 4)
    given = lambda x, w, b: dropless.route(x, w, b, 4, scoring="sigmoid",
                                           renormalize=True)
    soft = lambda x, w, b: dropless.route(x, w, None, 4, scoring="softmax",
                                          renormalize=False)
    assert str(jax.make_jaxpr(now)(x, w, b)) == text
    assert str(jax.make_jaxpr(given)(x, w, b)) == text
    assert str(jax.make_jaxpr(soft)(x, w, b)) != text


def test_the_shares_of_the_experts_add_up_to_the_whole_layer(highest):
    """Softmax routing over ALL the experts on every rank, each rank the
    terms of the experts it holds (``held_range``): the shares' sum, with
    the shared experts (which every rank computes alike) counted ONCE, is
    the uncut reference's layer."""
    cfg, _, params = make()
    p = params["layers_2_mlp"]
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 17, cfg.hidden_size))
    want, picked, _ = reference.sparse(x, p, shape_of(cfg))
    total, shared_terms = 0.0, []
    for rank in range(4):
        part = dataclasses.replace(cfg, ep_rank=rank, ep_size=4)
        first, count = dropless.held_range(cfg.n_routed_experts, rank, 4)
        held = {**p, **{k: p[k][first:first + count]
                        for k in ("gate", "up", "down")}}
        y, shared, counters, chosen = SparseExperts(part).apply(
            {"params": held}, x)
        assert (np.sort(chosen, -1) == np.sort(picked, -1)).all()
        assert int(counters[1]) == count           # experts held here
        # the reference's share of the same experts, without the shared
        ref_part = reference.expert_terms(
            x.reshape(-1, cfg.hidden_size), held, first,
            *reference.routed(x.reshape(-1, cfg.hidden_size), p,
                              shape_of(cfg))[:2]).reshape(x.shape)
        assert np.abs(np.asarray(y - ref_part)).max() <= TOL
        total = total + y
        shared_terms.append(shared)
    for other in shared_terms[1:]:
        assert np.abs(np.asarray(other - shared_terms[0])).max() == 0.0
    assert np.abs(np.asarray(total + shared_terms[0] - want)).max() <= TOL


@pytest.mark.parametrize("width, tile", [(2048, 512), (1792, 896),
                                         (1408, 128), (32, 32)])
def test_a_width_of_eleven_registers_is_tiled_by_one(width, tile):
    """1408 = 11 x 128, a prime number of registers: no run of whole
    registers up to 1,024 divides it but one (``width_tile``'s docstring
    and PERF.md say what that costs and what was read on the chip); 2048
    and 1792 keep the tiles they had."""
    assert dropless.width_tile(width) == tile and width % tile == 0


# ---------------------------------------------------------------------------
# through the paged latent pool
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("chunk", [0, 8, 7, 6], ids=[
    "whole-prompt", "chunks-of-8", "chunks-that-do-not-divide",
    "a-boundary-inside-a-block"])
def test_paged_logits_match_the_reference(highest, chunk):
    """Prefill then decode through the latent pool against the reference's
    full forward pass, on LOGITS at every position: a prompt of 27 in a
    bucket of 40; in chunks of 8 (the last holds 3 real positions); of 7
    (27 = 3 x 7 + 6); of 6 (every other chunk starts inside a block of 4).
    The prefill is decompressed, the decode steps absorbed."""
    cfg, _, params = make()
    srv = FAMILY.shared_engine(params, cfg)
    assert FAMILY.paged_logits_match(srv, cfg, params, prompts(cfg, [27])[0],
                                     14, chunk=chunk) <= TOL
    paths = srv.stats()["attention_paths"]
    assert paths.get("mla_decode_absorbed_xla")
    assert paths.get("mla_chunk_decompressed_xla" if chunk
                     else "mla_prefill_decompressed_xla")


def test_a_slots_next_request_reads_none_of_its_last_ones_rows(highest):
    """Two requests one after the other over the SAME blocks (the pool has
    room for one), the second shorter: each is the reference's."""
    cfg, _, params = make()
    srv = FAMILY.shared_engine(params, cfg, decode_slots=1,
                               num_blocks=1 + 10)
    for prompt, chunk in zip(prompts(cfg, [30, 7, 11]), (0, 0, 8)):
        assert FAMILY.paged_logits_match(srv, cfg, params, prompt, 5, slot=0,
                                         chunk=chunk) <= TOL, len(prompt)


def test_a_pool_full_of_nan_outside_the_live_prefixes_stays_outside(highest):
    """Idle rows and everything past a sequence's live prefix weigh 0 and
    are never read into a sum: with the pool filled with NaN first, every
    logit of every row (the idle slots' too) is finite, and the busy
    slot's are the reference's."""
    cfg, _, params = make()
    srv = FAMILY.shared_engine(params, cfg)
    was = srv.cache                # put back at the end: the engine is shared
    try:
        srv.cache = jax.tree_util.tree_map(
            lambda x: jnp.full_like(x, jnp.nan), srv.cache)
        for chunk in (0, 6):
            got, tokens = FAMILY.paged_logits(srv, prompts(cfg, [13])[0], 6,
                                              chunk=chunk)
            assert np.isfinite(got).all()
            want = reference_logits(cfg, params, [tokens])[0]
            assert np.abs(got - want[:len(got)]).max() <= TOL
        # the decode program's whole batch: the idle rows' logits too
        out, _ = jax.jit(
            lambda p, cache: srv._dmodule.apply(
                {"params": p, "cache": cache}, jnp.zeros((3, 1), jnp.int32),
                mutable=["cache"],
                paging={"block_tables": jnp.zeros((3, 16), jnp.int32),
                        "lengths": jnp.zeros((3,), jnp.int32),
                        "num_valid": jnp.ones((3,), jnp.int32),
                        "prefill": False}))(srv.engine.params, srv.cache)
        assert np.isfinite(np.asarray(out[0])).all()
    finally:
        srv.cache = was


def _applied(attn, paging):
    """One layer's paged call under ``paging`` as ONE program, traced when
    it is first called (after whatever the test patched)."""
    return jax.jit(lambda p, x, pool: attn.apply({"params": p}, x, paging,
                                                 pool, 1))


def _pooled_step(cfg, params, n=21, seed=3):
    """One layer's pool holding ``n`` rows of a sequence through a table in
    scrambled block order, and a decode step's queries at position ``n``:
    ``(module, bound call, its arguments)``."""
    rng = np.random.default_rng(seed)
    dcfg = cfg.for_paged_decode(12, BLOCK)
    attn = LatentAttention(dcfg)
    p = params["layers_1_attn"]
    blocks = rng.permutation(np.arange(1, 12))[:8]
    table = np.zeros((2, 8), np.int32)
    table[1] = blocks
    pool = jnp.zeros((dcfg.num_hidden_layers, 12, BLOCK, dcfg.latent_lanes))
    x = jax.random.normal(jax.random.PRNGKey(seed), (2, n + 1,
                                                     cfg.hidden_size))
    paging = {"block_tables": jnp.asarray(table),
              "lengths": jnp.zeros((2,), jnp.int32),
              "num_valid": jnp.asarray([0, n], jnp.int32), "prefill": True}
    _, pool = _applied(attn, paging)(p, x[:, :n], pool)
    step = {"block_tables": jnp.asarray(table),
            "lengths": jnp.asarray([0, n], jnp.int32),
            "num_valid": jnp.ones((2,), jnp.int32), "prefill": False}
    return attn, p, x, pool, step, n


def test_absorbed_decode_is_the_decompressed_form(highest, monkeypatch):
    """One decode step over the same pool rows both ways: ``W_kvb`` folded
    into the query and the output (what the step runs), and the rows
    decompressed into keys and values by heads (what a chunk of one token
    would run): the same function."""
    cfg, _, params = make()
    attn, p, x, pool, step, n = _pooled_step(cfg, params)
    absorbed, _ = _applied(attn, step)(p, x[:, n:], pool)
    # a "chunk" of one token: the decompressed path over the gathered rows
    forced = dict(step)
    monkeypatch.setattr(
        LatentAttention, "_absorbed_xla",
        lambda self, *a: (_ for _ in ()).throw(AssertionError("absorbed")))
    two = {**forced, "num_valid": jnp.asarray([1, 1], jnp.int32)}
    padded = jnp.concatenate([x[:, n:], jnp.zeros_like(x[:, n:])], axis=1)
    decompressed, _ = _applied(attn, two)(p, padded, pool)
    assert np.abs(np.asarray(absorbed[1, 0] - decompressed[1, 0])).max() \
        <= 1e-5
    # and both are the reference's attention at that position
    want = reference.attention(x[1:], p, shape_of(cfg))[0, n]
    assert np.abs(np.asarray(absorbed[1, 0] - want)).max() <= 1e-5


def test_the_latent_kernel_is_the_absorbed_xla_path(monkeypatch):
    """The Pallas kernel (interpret mode) over a table in scrambled block
    order, beside an idle slot, against the same step's XLA tiles; its
    idle row is zeros."""
    from deepspeed_tpu.ops import attention as ops_attention
    from deepspeed_tpu.utils.compat import tpu_interpret_mode

    cfg, _, params = make()
    attn, p, x, pool, step, n = _pooled_step(cfg, params, n=29)
    want, _ = _applied(attn, step)(p, x[:, n:], pool)
    monkeypatch.setattr(ops_attention, "use_decode_kernel", lambda: True)
    # 4 blocks of 4 keys a grid step: the row's 30 keys are two tiles, the
    # second with two blocks past the live prefix (at the published 512
    # keys a step the interpreter would move 128 operands a step)
    monkeypatch.setattr(latent_decode_attention, "LATENT_TILE_KEYS", 16)
    with tpu_interpret_mode():
        got, _ = jax.block_until_ready(_applied(attn, step)(p, x[:, n:],
                                                            pool))
    assert np.abs(np.asarray(got[1] - want[1])).max() <= 1e-5
    assert np.abs(np.asarray(want[1])).max() > 1e-3


def _absorbed_by_hand(q, pool, tables, lengths, layer, rank, scale):
    """The absorbed step in numpy, a busy row at a time: the row's keys
    gathered through its table up to the query's position, one softmax a
    head, the values the keys' first ``rank`` lanes; an idle row zeros."""
    q, pool = np.asarray(q, np.float64), np.asarray(pool, np.float64)
    out = np.zeros(q.shape[:3] + (rank,))
    for b, (table, n) in enumerate(zip(tables, lengths)):
        if table[0] == 0:
            continue
        rows = pool[layer, table].reshape(-1, pool.shape[-1])[:n + 1]
        s = q[b, 0] @ rows.T * scale
        p = np.exp(s - s.max(-1, keepdims=True))
        out[b, 0] = (p / p.sum(-1, keepdims=True)) @ rows[:, :rank]
    return out


@pytest.mark.parametrize("lengths,heads,nan", [
    ([21], 16, False),
    ([2, 40], 16, False),
    ([9, None, 33, None, 16], 32, False),
    ([None, None, None], 16, False),
    ([21, None, 3, 40], 32, True),
    ([15, 31, 0], 16, True),
], ids=["scrambled-ends-mid-block-mid-tile", "one-block-then-a-long-row",
        "idle-between-busy-32-heads", "idle-slots-only",
        "nan-in-dead-blocks-and-past-the-position",
        "rows-that-fill-their-tiles-and-a-fresh-row"])
def test_the_latent_kernel_copies_its_own_tiles(monkeypatch, lengths, heads,
                                                nan):
    """The kernel alone (interpret mode) in tiles of 4 blocks of 4 keys
    against the absorbed step by hand, tables in scrambled block order
    (``None``: an idle slot, length 0 on the garbage block): a row whose
    live prefix ends inside a block and inside a tile (the tile's other
    blocks are neither named nor copied); a row of one block before one of
    three tiles (the next row's first tile is on its way across the
    boundary); idle slots between busy rows and a batch of nothing else
    (one step on no row); 16 and 32 heads; and a pool that holds NaN in
    every row no query may see (dead blocks, the boundary block's tail)."""
    from deepspeed_tpu.ops.latent_decode_attention import (
        decode_attention_latent)
    from deepspeed_tpu.utils.compat import tpu_interpret_mode

    bs, lanes, rank, live_lanes, per_row, layers = 4, 256, 128, 192, 12, 2
    rng = np.random.default_rng(len(lengths) * 100 + heads)
    blocks = 1 + len(lengths) * per_row
    free = 1 + rng.permutation(blocks - 1)
    tables = np.zeros((len(lengths), per_row), np.int32)
    seen = np.zeros((blocks, bs), bool)
    for b, n in enumerate(lengths):
        if n is None:
            continue
        mine = free[b * per_row:b * per_row + n // bs + 1]
        tables[b, :len(mine)] = mine
        seen[mine] = True
        seen[mine[-1], n % bs + 1:] = False
    lens = np.asarray([n or 0 for n in lengths], np.int32)
    pool = rng.normal(size=(layers, blocks, bs, lanes)).astype(np.float32)
    pool[..., live_lanes:] = 0.0
    if nan:
        pool[:, ~seen] = np.nan
    q = rng.normal(size=(len(lengths), 1, heads, lanes)).astype(np.float32)
    q[..., live_lanes:] = 0.0
    monkeypatch.setattr(latent_decode_attention, "LATENT_TILE_KEYS", 16)
    with tpu_interpret_mode():
        got = np.asarray(jax.block_until_ready(decode_attention_latent(
            jnp.asarray(q), jnp.asarray(pool), jnp.asarray(tables),
            jnp.asarray(lens), 1, rank=rank, scale=0.2)))
    want = _absorbed_by_hand(q, pool, tables, lens, 1, rank, 0.2)
    assert got.shape == want.shape and np.isfinite(got).all()
    assert np.abs(got - want).max() <= 1e-5
    idle = [n is None for n in lengths]
    assert (got[idle] == 0).all()
    if not all(idle):
        assert np.abs(want).max() > 1e-2


def test_latent_plan_reads_its_tile_from_the_shapes(monkeypatch):
    """As many blocks as hold 1,024 keys, no more than a table row has
    and than two tiles fit their share of VMEM in: both cells' shapes
    (rows of 640 lanes over tables of 512 and 352 blocks of 32), short
    tables, larger blocks, rows too wide for a whole tile; and the constant
    is read when the plan is made."""
    from deepspeed_tpu.ops.latent_decode_attention import latent_plan

    assert [latent_plan(32, 640, mb).tile_blocks
            for mb in (512, 352, 8, 1)] == [32, 32, 8, 1]
    assert latent_plan(32, 640, 512).tile_keys == 1024
    assert latent_plan(128, 640, 64).tile_blocks == 8
    assert latent_plan(2048, 640, 64).tile_blocks == 1
    assert latent_plan(32, 8192, 128).tile_blocks == 8
    monkeypatch.setattr(latent_decode_attention, "LATENT_TILE_KEYS", 128)
    assert latent_plan(32, 640, 512) == (4, 32, 512)


@pytest.mark.parametrize("family", ["deepseek_v2", "bailing_hybrid"])
def test_latent_families_list_the_kernels_work_once_a_step(monkeypatch,
                                                           family):
    """A served latent family lists the kernel's work once a traced decode
    step in the plan's tiles, whatever its layers, and counts the form it
    took where ``stats()["attention_paths"]`` reads it; the latent layers
    of the program are one trace of the kernel's call; a prefill step runs
    no kernel and lists nothing."""
    import importlib

    from deepspeed_tpu.ops import attention as attn_mod

    mod = importlib.import_module(f"deepspeed_tpu.models.{family}")
    bs, slots, per_row = 4, 3, 4
    if family == "deepseek_v2":
        cfg = DeepseekV2Config.tiny().for_paged_decode(13, bs)
        model, latent = DeepseekV2ForCausalLM(cfg), cfg.num_hidden_layers
    else:
        cfg = mod.BailingHybridConfig.tiny(
            kv_lora_rank=128).for_paged_decode(13, bs, state_slots=slots)
        model = mod.BailingHybridForCausalLM(cfg)
        latent = len(cfg.layers_of(mod.LATENT))
    entries = cfg.paged_slot_state_for(bs)["entries"] \
        if family == "bailing_hybrid" else 0
    tables = np.zeros((slots, per_row + entries), np.int32)
    tables[0, :2], tables[2, :1] = [3, 5], [7]       # slot 1 is idle
    if entries:
        tables[[0, 2], per_row:] = [[1], [3]]

    def paging(lengths, n, prefill):
        return {"block_tables": jnp.asarray(tables),
                "lengths": jnp.asarray(lengths, jnp.int32),
                "num_valid": jnp.full((slots,), n, jnp.int32),
                "prefill": prefill}

    prompt = jnp.zeros((slots, 4), jnp.int32)
    variables = jax.jit(lambda ids: model.init(
        jax.random.PRNGKey(0), ids, paging=paging([0] * slots, 4, True)))(
            prompt)
    made = []
    real = latent_decode_attention.latent_step_work

    def spy(lengths, block_tables, block_size, lanes):
        made.append((block_tables.shape[-1], block_size, lanes))
        return real(lengths, block_tables, block_size, lanes)

    monkeypatch.setattr(latent_decode_attention, "latent_step_work", spy)
    monkeypatch.setattr(attn_mod, "_FORCE_DECODE_KERNEL", True)
    name = f"latent_decode_tile{per_row * bs}"
    counted = attn_mod.dispatch_counts().get(name, 0)
    bodies = []
    body = latent_decode_attention._kernel
    monkeypatch.setattr(latent_decode_attention, "_kernel", lambda *a, **kw:
                        bodies.append(kw["tile"]) or body(*a, **kw))
    latent_decode_attention._attend.clear_cache()

    def step(ids, lengths, n, prefill):
        return model.apply(variables, ids, mutable=["cache"],
                           paging=paging(lengths, n, prefill))

    jaxpr = jax.make_jaxpr(lambda ids, ln: step(ids, ln, 1, False))(
        prompt[:, :1], jnp.asarray([6, 0, 2], jnp.int32))
    # the sequence's own blocks, no slot's state entry; they are less than
    # a tile of 1,024 keys: one tile a row
    assert made == [(per_row, bs, cfg.latent_lanes)]
    assert attn_mod.dispatch_counts()[name] == counted + 1
    assert "pallas_call" in str(jaxpr)
    assert latent > 1 and bodies == [per_row]
    jax.make_jaxpr(lambda ids: step(ids, [0] * slots, 4, True))(prompt)
    assert len(made) == 1


def test_decode_through_both_kernels_matches_the_xla_paths(monkeypatch):
    """The decode program with the Pallas kernels in it (interpret mode):
    the latent multi-query kernel and the grouped expert matmul, against
    the same steps on the XLA paths."""
    cfg, _, params = make()
    monkeypatch.setattr(latent_decode_attention, "LATENT_TILE_KEYS", 16)
    got, want, paths = FAMILY.decode_through_the_kernels(
        monkeypatch, cfg, params, prompts(cfg, [19])[0], 3)
    assert paths.get("mla_decode_absorbed_kernel") and paths.get(
        "moe_experts_grouped_kernel")
    assert np.abs(got - want).max() <= TOL


@pytest.mark.parametrize("control", ["latent", "kvb"])
def test_a_lower_precision_moves_the_logits(highest, monkeypatch, control):
    """The controls the chip's check has to fail: the latent row through
    float8 on its way into the pool; ``W_kvb``'s absorbed halves through
    float8 (the decode steps alone feel those). Either moves the logits by
    hundreds of times the tolerance."""
    low = lambda w: w.astype(jnp.float8_e4m3fn).astype(w.dtype)
    if control == "latent":
        row = deepseek_v2.pool_row
        monkeypatch.setattr(deepseek_v2, "pool_row", lambda c, k_pe, lanes:
                            low(row(c, k_pe, lanes)))
    else:
        halves = deepseek_v2.absorbed_halves
        monkeypatch.setattr(
            deepseek_v2, "absorbed_halves",
            lambda w, nope: tuple(low(h) for h in halves(w, nope)))
    cfg, _, params = make()
    # the shared engine, its paged module traced anew under the patch
    prompt = prompts(cfg, [27])[0]
    got, tokens = FAMILY.paged_logits(FAMILY.shared_engine(params, cfg),
                                      prompt, 10, chunk=8, retrace=True)
    want = reference_logits(cfg, params, [tokens])[0]
    miss = np.abs(got - want[:len(got)]).max(-1)
    assert miss[len(prompt):].max() > 20 * TOL
    if control == "kvb":       # the prefill never reads those halves
        assert miss[:len(prompt)].max() <= TOL


# ---------------------------------------------------------------------------
# through the engine
# ---------------------------------------------------------------------------
def test_the_engine_serves_chunked_prefill_and_counts_latent_bytes(highest):
    """``init_inference`` -> ``ServingEngine`` with ``prefill_chunk_tokens``
    set: the served tokens are the reference's greedy tokens, the routed
    sets the reference's own, and the ledger counts 576 values a live
    token a layer at each step boundary."""
    cfg, _, params = make()
    asked = prompts(cfg, [21, 6, 33])
    stats, reqs = FAMILY.served_logits_match(
        cfg, params, list(zip(asked, [9, 12, 5])), prefill_chunk_tokens=8,
        routed_experts_kept=4)
    srv = FAMILY.shared_engine(params, cfg, prefill_chunk_tokens=8,
                               routed_experts_kept=4)
    for req, prompt in zip(reqs, asked):
        want = reference_logits(cfg, params,
                                [list(prompt) + req.tokens[:-1]])[0]
        assert req.tokens == want[len(prompt) - 1:].argmax(-1).tolist()
        FAMILY.routed_sets_are_the_references(srv, cfg, params, req, prompt)
    assert set(stats["kv_live_bytes"]) == {"latent"}
    row = cfg.num_hidden_layers * cfg.latent_row * 4       # float32
    assert stats["kv_live_bytes"]["latent"] > 0
    assert stats["kv_live_bytes"]["latent"] % row == 0
    assert srv._dmodule.config.kv_bytes_per_token() == {"latent": row}
    # (the paths are counted as programs are traced, process-wide)
    assert stats["attention_paths"].get("mla_chunk_decompressed_xla")
    assert set(srv._chunk_fns) == {8} and not srv._prefill_fns
    assert stats["model_counters"]["decode"]["experts_held"] > 0
    assert (stats["model_counters"]["prefill"]["pairs_here"]
            == stats["model_counters"]["prefill"]["pairs_all"])
    # ONE pool, a latent row a token, through the block table; no state a
    # slot
    assert {k: v.shape for k, v in srv.cache.items()} == {
        "latent_pool": (cfg.num_hidden_layers, srv.num_blocks, BLOCK,
                        cfg.latent_lanes)}
    assert srv.slot_state is None and srv.slot_entries == 0


def test_the_published_row_is_576_values_in_640_lanes():
    cfg = DeepseekV2Config(num_hidden_layers=6)
    assert (cfg.latent_row, cfg.latent_lanes) == (576, 640)
    assert cfg.kv_bytes_per_token() == {"latent": 6_912}
    assert cfg.kv_live_bytes(np.asarray([100, 28])) == {
        "latent": 128 * 6_912}
    # a ninth of what its heads' keys and values would keep
    by_heads = 16 * (192 + 128) * 2
    assert by_heads == 10_240 and 1_152 * 9 > by_heads > 1_152 * 8
    assert cfg.routed_width == 5 * 6 and cfg.sparse_layers == 5


# ---------------------------------------------------------------------------
# refusals, by name
# ---------------------------------------------------------------------------
@REFUSED
def test_mechanisms_that_read_rows_by_heads_refuse_the_model(serving,
                                                             mechanism):
    said = FAMILY.mechanism_refusal(serving, mechanism)
    assert "one latent row a token" in said
    assert "keys and values by heads" in said


def test_tensor_parallel_refuses_the_model():
    said = FAMILY.tensor_parallel_refusal()
    assert "DeepseekV2ForCausalLM" in said and "latent row" in said


def test_migration_refuses_the_model():
    assert all("latent row" in said for said in FAMILY.migration_refusals())


def test_for_paged_decode_refuses_what_it_cannot_size():
    cfg = DeepseekV2Config.tiny()
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        cfg.for_paged_decode(9, 4, kv_dtype="int8")
    with pytest.raises(ValueError, match="experts over"):
        DeepseekV2Config.tiny(ep_size=5)
    with pytest.raises(ValueError, match="rotates pairs"):
        DeepseekV2Config.tiny(qk_rope_head_dim=7)
    assert cfg.paged_row_kind()["kind"] == "latent"
    assert not hasattr(cfg, "paged_slot_state_for")
