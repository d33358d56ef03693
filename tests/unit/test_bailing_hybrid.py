"""The Ling-3.0 family (``bailing_hybrid``) at a small size on the CPU: the
program in float32 against the plain reference
(``perfbench/reference_bailing_hybrid``, the delta rule ONE position at a
time) on LOGITS: the plain call and each mixer alone; the recurrence in its
forms (one position at a time = the chunk form whole, from a non-zero state,
with every gate at its lower bound = the chunk form in pieces = decode
steps, XLA and kernel); prefill chunks then decode through BOTH of
``ServingEngine``'s seams (the latent pool through the block table, the
delta-rule state a slot) against the reference's full forward; a slot's
second tenant; group-limited selection against a plain re-implementation;
the clamp; the share's test; the engine's counters; and each refusal by
name."""

import ast
import dataclasses
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import bailing_hybrid, blocks
from deepspeed_tpu.models.bailing_hybrid import (BailingHybridConfig,
                                                 BailingHybridForCausalLM,
                                                 GatedLatentAttention,
                                                 KdaMixer)
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.ops import kda_chunk, kda_state_update
from perfbench import reference_bailing_hybrid as reference
from tests.unit.served_family import REFUSED, Family, highest, prompts  # noqa: F401

# float32 program against the float32 reference, on logits of order 0.6:
# what another order of summation leaves (the two agree to 6e-7 here)
TOL = 5e-6
BLOCK = 4


def shape_of(cfg: BailingHybridConfig) -> dict:
    """The reference's view of a program config (the family builds the
    same from a configuration file)."""
    n = cfg.num_hidden_layers
    first, _ = dropless.held_range(cfg.num_experts, cfg.ep_rank, cfg.ep_size)
    return dict(
        heads=cfg.num_attention_heads, head_dim=cfg.head_dim,
        lower_bound=cfg.kda_lower_bound, eps=cfg.rms_norm_eps,
        nope=cfg.qk_nope_head_dim, rope=cfg.qk_rope_head_dim,
        v_dim=cfg.v_head_dim, rank=cfg.kv_lora_rank,
        rope_theta=cfg.rope_theta, top_k=cfg.num_experts_per_tok,
        n_group=cfg.n_group, topk_group=cfg.topk_group,
        route_scale=cfg.routed_scaling_factor, first_expert=first,
        kinds=tuple(cfg.kind(i) for i in range(n)),
        sparse=tuple(cfg.sparse(i) for i in range(n)),
        limits=tuple(cfg.sparse_ffn_at(i)["limit"] for i in range(n)),
        shared_limits=tuple(cfg.sparse_ffn_at(i)["shared_limit"]
                            for i in range(n)))


FAMILY = Family(BailingHybridConfig, BailingHybridForCausalLM, reference,
                shape_of, TOL,
                serving={"decode_slots": 3, "block_size": BLOCK,
                         "max_model_len": 64, "prefill_chunk_tokens": 8})
engines = FAMILY.engines()
make, reference_logits = FAMILY.make, FAMILY.reference_logits


@pytest.fixture
def served():
    """``(cfg, params, engine)``: the shared engine, for the tests that
    drive its paged module, pools and tables themselves or serve through
    it."""
    cfg, _, params = make()
    return cfg, params, FAMILY.shared_engine(params, cfg)


# ---------------------------------------------------------------------------
# the plain call, each mixer alone
# ---------------------------------------------------------------------------
def test_full_forward_matches_the_reference(highest):
    cfg, module, params = make()
    ids = np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 37))
    got = np.asarray(FAMILY.plain(cfg)(params, jnp.asarray(ids)))
    assert np.abs(got - reference_logits(cfg, params, ids)).max() <= TOL
    # untied; two KDA layers to a latent one, the dense layer first
    assert "lm_head" in params and "layers_2_attn" in params
    assert [cfg.kind(i) for i in range(6)] == ["kda", "kda", "latent"] * 2
    assert set(params["layers_0_kda"]) == {
        "q_proj", "k_proj", "v_proj", "f_proj", "b_proj", "g_proj", "conv",
        "A_log", "dt_bias", "o_norm", "o_proj"}
    assert set(params["layers_2_attn"]) == {
        "q_proj", "kv_a_proj_with_mqa", "kv_a_layernorm", "kv_b_proj",
        "gate_proj", "o_proj"}
    assert params["layers_2_attn"]["gate_proj"]["kernel"].shape == (64, 4)
    assert "router" in params["layers_1_mlp"] and "gate_proj" in params[
        "layers_0_mlp"]


@pytest.mark.parametrize("mixer", ["kda", "latent"])
def test_a_mixer_alone_matches_the_reference(highest, mixer):
    cfg, _, params = make()
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 21, cfg.hidden_size))
    if mixer == "kda":
        p = params["layers_0_kda"]
        got, _ = KdaMixer(cfg).apply({"params": p}, x)
        want, _ = reference.kda(x, p, shape_of(cfg))
    else:
        p = params["layers_2_attn"]
        got, _ = GatedLatentAttention(cfg).apply({"params": p}, x)
        want = reference.latent(x, p, shape_of(cfg))
    scale = float(np.abs(np.asarray(want)).max())
    assert np.abs(np.asarray(got - want)).max() <= 1e-5 * scale and scale > 0


def test_the_head_gate_is_in_the_program_and_the_reference(highest):
    """The latent layer's gate a head: with its projection zeroed every
    head is halved, in both."""
    cfg, _, params = make()
    p = params["layers_2_attn"]
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 9, cfg.hidden_size))
    got, _ = GatedLatentAttention(cfg).apply({"params": p}, x)
    zero = {**p, "gate_proj": {"kernel": jnp.zeros_like(
        p["gate_proj"]["kernel"])}}
    halved, _ = GatedLatentAttention(cfg).apply({"params": zero}, x)
    want = reference.latent(x, zero, shape_of(cfg))
    assert np.abs(np.asarray(halved - want)).max() <= 1e-6
    assert np.abs(np.asarray(got - halved)).max() > 1e-4


def test_the_gates_initialisers_keep_a_state(highest):
    """``exp(A_log)`` in [0.5, 1], ``dt_bias`` in [-6, -2]: at a zero
    projection a channel keeps 0.26 to 0.99 of its state a position; and
    the decay stays inside ``(kda_lower_bound, 0)`` whatever the
    projection."""
    cfg, _, params = make()
    p = params["layers_0_kda"]
    rate = np.exp(np.asarray(p["A_log"]))
    assert rate.min() >= 0.5 and rate.max() <= 1.0
    bias = np.asarray(p["dt_bias"])
    assert bias.min() >= -6.0 and bias.max() <= -2.0
    f = jnp.asarray(np.random.default_rng(0).normal(
        size=(1, 50, cfg.kda_inner)) * 30.0, jnp.float32)
    g = np.asarray(bailing_hybrid.kda_gate(cfg, f, p["A_log"], p["dt_bias"]))
    assert g.min() >= cfg.kda_lower_bound and g.max() <= 0.0
    assert g.min() < -4.9 and g.max() > -0.1
    at_rest = np.exp(np.asarray(bailing_hybrid.kda_gate(
        cfg, jnp.zeros((1, 1, cfg.kda_inner)), p["A_log"], p["dt_bias"])))
    assert 0.25 < at_rest.min() < 0.9 and at_rest.max() < 0.995


# ---------------------------------------------------------------------------
# the recurrence in its forms
# ---------------------------------------------------------------------------
def _recurrence(q, k, v, g, beta, state):
    """The delta rule one position at a time (``reference.kda``'s step)."""
    def step(s, at):
        q_t, k_t, v_t, g_t, beta_t = at
        s = jnp.exp(g_t)[..., None] * s
        miss = v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t)
        s = s + (beta_t[..., None] * k_t)[..., None] * miss[:, :, None]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    state, o = jax.lax.scan(step, state, tuple(
        jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state


def _inputs(rows=2, t=50, heads=3, key=16, value=8, seed=0, bound=False):
    r = np.random.default_rng(seed)
    q, k = r.normal(size=(2, rows, t, heads, key))
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) / key ** 0.5
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    v = r.normal(size=(rows, t, heads, value))
    g = (np.full((rows, t, heads, key), -5.0) if bound else
         -5.0 / (1.0 + np.exp(-3.0 * r.normal(size=(rows, t, heads, key)))))
    beta = 1.0 / (1.0 + np.exp(-r.normal(size=(rows, t, heads))))
    state = r.normal(size=(rows, heads, key, value))
    return tuple(jnp.asarray(x, jnp.float32)
                 for x in (q, k, v, g, beta, state))


@pytest.mark.parametrize("bound", [False, True], ids=["mixed", "lower-bound"])
@pytest.mark.parametrize("t", [50, 64, 7])
def test_the_chunk_form_is_the_recurrence_from_a_state(highest, t, bound):
    """From a NON-ZERO state, a length that is no whole sub-chunk; with
    every gate at ``kda_lower_bound`` over the whole call (16 steps at the
    bound are ``e^-80``: finite, and equal)."""
    args = _inputs(t=t, bound=bound)
    want, last = _recurrence(*args)
    got, carried = kda_chunk.kda_chunk(*args)
    assert np.isfinite(np.asarray(got)).all()
    assert np.abs(np.asarray(got - want)).max() <= 2e-6
    assert np.abs(np.asarray(carried - last)).max() <= 2e-6


def test_every_exponent_of_the_chunk_form_is_bounded():
    """At the lower bound for 512 positions ``exp(-G)`` over the call
    would be ``e^2560``: the terms stay finite because they are taken a
    sub-chunk at a time around its middle."""
    q, k, v, g, beta, _ = _inputs(rows=1, t=512, heads=1, bound=True)
    terms = kda_chunk.sub_chunk_terms(q, k, v, g, beta)
    assert all(np.isfinite(np.asarray(x)).all() for x in terms)
    assert float(jnp.max(terms[-1])) == pytest.approx(np.exp(-80.0), rel=1e-4)


def test_the_chunk_form_in_pieces_carries_the_state(highest):
    """A prompt in calls of uneven real length with padding behind
    (``g = 0``, ``beta = 0``): the state crosses the calls."""
    q, k, v, g, beta, state = _inputs(t=40)
    want, last = _recurrence(q, k, v, g, beta, state)
    got = []
    for lo, hi in ((0, 16), (16, 29), (29, 40)):
        pad = 16 - (hi - lo)
        piece = [jnp.pad(x[:, lo:hi], ((0, 0), (0, pad)) + ((0, 0),) * (
            x.ndim - 2)) for x in (q, k, v, g, beta)]
        o, state = kda_chunk.kda_chunk(*piece, state)
        got.append(o[:, :hi - lo])
    assert np.abs(np.asarray(jnp.concatenate(got, 1) - want)).max() <= 2e-6
    assert np.abs(np.asarray(state - last)).max() <= 2e-6


def test_the_chunk_kernel_is_the_scan(highest):
    """The Pallas carry (interpret mode) at whole registers, three
    sub-chunks and a state handed in, against the XLA scan."""
    from deepspeed_tpu.utils.compat import tpu_interpret_mode

    args = _inputs(rows=1, t=48, heads=2, key=128, value=128, seed=4)
    assert kda_chunk.kernel_serves(2, 128, 128)
    want, last = kda_chunk.kda_chunk(*args, use_kernel=False)
    with tpu_interpret_mode():
        got, carried = jax.block_until_ready(jax.jit(
            lambda *a: kda_chunk.kda_chunk(*a, use_kernel=True))(*args))
    assert np.abs(np.asarray(got - want)).max() <= 2e-6
    assert np.abs(np.asarray(carried - last)).max() <= 2e-6


def _step_args(rows, heads, key, value, seed=2):
    q, k, v, g, beta, _ = _inputs(rows=rows, t=1, heads=heads, key=key,
                                  value=value, seed=seed)
    return jnp.exp(g[:, 0]), k[:, 0], v[:, 0], q[:, 0], beta[:, 0]


@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_a_decode_step_is_the_recurrence(highest, form):
    """Three rows of a pool of five (one idle: pool row 0), layer 1 of 2:
    the step's output and the rows it leaves, against one step of the
    recurrence; the other layer and the other rows untouched."""
    from deepspeed_tpu.utils.compat import tpu_interpret_mode

    heads, key = (2, 128) if form == "kernel" else (3, 16)
    pool = jnp.asarray(np.random.default_rng(1).normal(
        size=(2, 5, heads, key, key)), jnp.float32)
    rows = jnp.asarray([3, 0, 1], jnp.int32)
    alpha, k, v, q, beta = _step_args(3, heads, key, key)
    want, left = _recurrence(q[:, None], k[:, None], v[:, None],
                             jnp.log(alpha)[:, None], beta[:, None],
                             pool[1, rows])
    if form == "kernel":
        with tpu_interpret_mode():
            got, after = jax.block_until_ready(jax.jit(
                lambda *a: kda_state_update.state_update_kernel(
                    a[0], 1, *a[1:]))(pool, rows, alpha, k, v, q, beta))
    else:
        got, after = kda_state_update.state_update_xla(
            pool, 1, rows, alpha, k, v, q, beta)
    busy = np.asarray([0, 2])
    assert np.abs(np.asarray(got - want[:, 0]))[busy].max() <= 1e-6
    assert np.abs(np.asarray(after[1, rows] - left))[busy].max() <= 1e-6
    assert np.array_equal(np.asarray(after[0]), np.asarray(pool[0]))
    for row in (2, 4):
        assert np.array_equal(np.asarray(after[1, row]),
                              np.asarray(pool[1, row]))
    if form == "kernel":
        # an idle row has no step: its output is zero, row 0 as it was
        assert not np.asarray(got[1]).any()
        assert np.array_equal(np.asarray(after[1, 0]), np.asarray(pool[1, 0]))


@pytest.mark.parametrize("heads,head_tile,rows", [
    (4, 2, [3, 0, 1, 5, 2]),     # two head tiles a row, five rows
    (3, 1, [2, 4, 0]),           # a head a grid step
    (32, 32, [0, 2]),            # the cell's 32 heads in ONE grid step
    (32, 16, [1]),               # and in two
    (2, 2, [0, 0, 0]),           # every row idle: the grid's one step
], ids=["two-tiles", "a-head-a-step", "32-heads-a-step", "32-in-two",
        "all-idle"])
def test_the_step_kernel_in_its_tiles(highest, heads, head_tile, rows):
    """The Pallas kernel's form by head tile and row count, layer 1 of 2,
    against one step of the recurrence: the busy rows' output and states;
    an idle row's output zero; every row no busy batch row names as it was
    (pool row 0 is the idle rows' to write)."""
    from deepspeed_tpu.utils.compat import tpu_interpret_mode

    key = 128
    held = 1 + max(max(rows), 2)
    pool = jnp.asarray(np.random.default_rng(1).normal(
        size=(2, held, heads, key, key)), jnp.float32)
    slot_rows = jnp.asarray(rows, jnp.int32)
    alpha, k, v, q, beta = _step_args(len(rows), heads, key, key)
    want, left = _recurrence(q[:, None], k[:, None], v[:, None],
                             jnp.log(alpha)[:, None], beta[:, None],
                             pool[1, slot_rows])
    with tpu_interpret_mode():
        got, after = jax.block_until_ready(jax.jit(
            lambda *a: kda_state_update.state_update_kernel(
                a[0], 1, *a[1:], head_tile=head_tile))(
                    pool, slot_rows, alpha, k, v, q, beta))
    busy = np.flatnonzero(np.asarray(rows))
    idle = np.flatnonzero(np.asarray(rows) == 0)
    if len(busy):
        assert np.abs(np.asarray(got - want[:, 0]))[busy].max() <= 1e-6
        assert np.abs(np.asarray(after[1, slot_rows] - left))[
            busy].max() <= 1e-6
    assert not np.asarray(got)[idle].any()
    assert np.array_equal(np.asarray(after[0]), np.asarray(pool[0]))
    for row in set(range(1, held)) - set(rows):
        assert np.array_equal(np.asarray(after[1, row]),
                              np.asarray(pool[1, row]))


@pytest.mark.parametrize("heads,head_tile,tile", [
    (32, 32, 32), (32, 16, 16), (32, 8, 8), (4, 32, 4), (3, 2, 1),
    (48, 32, 24), (40, 32, 20)])
def test_a_grid_step_takes_the_most_heads_that_divide_the_row(
        monkeypatch, heads, head_tile, tile):
    """``head_tile`` is the most a step may take: the tile is the largest
    divisor of the heads not above it (the whole row at the published
    32)."""
    seen = {}

    def spy(*args, tile):
        seen["tile"] = tile
        return None, None

    monkeypatch.setattr(kda_state_update, "_update", spy)
    assert kda_state_update.HEAD_TILE == 32
    pool = jnp.zeros((1, 2, heads, 128, 128), jnp.float32)
    vec = jnp.zeros((1, heads, 128), jnp.float32)
    kda_state_update.state_update_kernel(
        pool, 0, jnp.asarray([1], jnp.int32), vec, vec, vec, vec,
        jnp.zeros((1, heads), jnp.float32), head_tile=head_tile)
    assert seen["tile"] == tile


def test_a_fresh_row_forgets_its_slots_last_tenant_through_the_kernel(
        highest):
    """``alpha = 0`` through the Pallas kernel: the step is the recurrence
    from zeros whatever the slot held (finite values: ``0 * S = 0``), and
    the row beside it, not fresh, keeps its own."""
    from deepspeed_tpu.utils.compat import tpu_interpret_mode

    heads, key = 2, 128
    pool = jnp.asarray(1e3 * np.random.default_rng(1).normal(
        size=(1, 3, heads, key, key)), jnp.float32)
    rows = jnp.asarray([2, 1], jnp.int32)
    alpha, k, v, q, beta = _step_args(2, heads, key, key)
    alpha = alpha.at[0].set(0.0)
    start = pool[0, rows].at[0].set(0.0)
    decay = jnp.log(alpha.at[0].set(1.0))
    want, left = _recurrence(q[:, None], k[:, None], v[:, None],
                             decay[:, None], beta[:, None], start)
    with tpu_interpret_mode():
        got, after = jax.block_until_ready(jax.jit(
            lambda *a: kda_state_update.state_update_kernel(
                a[0], 0, *a[1:]))(pool, rows, alpha, k, v, q, beta))
    assert np.abs(np.asarray(got - want[:, 0]))[0].max() <= 1e-6
    assert np.abs(np.asarray(after[0, rows] - left))[0].max() <= 1e-6
    # the other row's state is of the order of 1e3: float32's last place
    assert np.abs(np.asarray(after[0, rows] - left))[1].max() <= 1e-3
    assert np.abs(np.asarray(got - want[:, 0]))[1].max() <= 1e-3


def test_a_fresh_row_forgets_its_slots_last_tenant(highest):
    """``alpha = 0`` for a row whose sequence starts here: the step is the
    recurrence from zeros whatever the slot held."""
    pool = jnp.asarray(np.random.default_rng(1).normal(
        size=(1, 3, 2, 16, 16)), jnp.float32)
    rows = jnp.asarray([2], jnp.int32)
    _, k, v, q, beta = _step_args(1, 2, 16, 16)
    got, after = kda_state_update.state_update_xla(
        pool, 0, rows, jnp.zeros_like(k), k, v, q, beta)
    want, left = _recurrence(q[:, None], k[:, None], v[:, None],
                             jnp.zeros_like(k)[:, None], beta[:, None],
                             jnp.zeros_like(pool[0, rows]))
    assert np.abs(np.asarray(got - want[:, 0])).max() <= 1e-6
    assert np.abs(np.asarray(after[0, rows] - left)).max() <= 1e-6


def test_a_bfloat16_state_loses_the_delta_rules_correction(highest):
    """Why the pool is float32: the same steps with the state rounded to
    bfloat16 a step drift from the recurrence by far more than float32
    does."""
    q, k, v, g, beta, state = _inputs(rows=1, t=200, heads=2, seed=6)
    g = g * 0.02        # slow decay: what is written stays to be corrected
    _, want = _recurrence(q, k, v, g, beta, state)

    def steps(dtype):
        pool = jnp.zeros((1, 2, 2, 16, 8), dtype).at[0, 1].set(
            state[0].astype(dtype))
        rows = jnp.asarray([1], jnp.int32)
        for t in range(q.shape[1]):
            _, pool = kda_state_update.state_update_xla(
                pool, 0, rows, jnp.exp(g[:, t]), k[:, t], v[:, t], q[:, t],
                beta[:, t])
        err = np.asarray(pool[0, 1].astype(jnp.float32) - want[0])
        return float(np.sqrt((err ** 2).mean() / np.asarray(
            want ** 2).mean()))

    assert steps(jnp.float32) < 1e-5 < 1e-3 < steps(jnp.bfloat16)


# ---------------------------------------------------------------------------
# group-limited selection and the clamp (moe/dropless.py)
# ---------------------------------------------------------------------------
def _plain_selection(select, top_k, n_group, topk_group):
    """A plain re-implementation, a token at a time in numpy."""
    out = []
    for row in np.asarray(select):
        groups = row.reshape(n_group, -1)
        score = np.sort(groups, -1)[:, -2:].sum(-1)
        kept = np.argsort(-score, kind="stable")[:topk_group]
        masked = np.full_like(groups, -np.inf)
        masked[kept] = groups[kept]
        out.append(np.argsort(-masked.reshape(-1), kind="stable")[:top_k])
    return np.asarray(out)


def test_grouped_selection_is_the_plain_re_implementation():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(32, 64)) * 0.3, jnp.float32)
    bias = jnp.asarray(rng.normal(size=(64,)) * 0.05, jnp.float32)
    experts, weights = dropless.route(x, router, bias, 6, scale=2.5,
                                      n_group=8, topk_group=4)
    scores = jax.nn.sigmoid(x @ router)
    want = _plain_selection(scores + bias[None], 6, 8, 4)
    assert np.array_equal(np.sort(np.asarray(experts), -1), np.sort(want, -1))
    # at most four of the eight groups a token, the weights the scores' own
    assert max(len(set(row // 8)) for row in np.asarray(experts)) <= 4
    picked = np.take_along_axis(np.asarray(scores), np.asarray(experts), 1)
    assert np.allclose(np.asarray(weights),
                       2.5 * picked / picked.sum(-1, keepdims=True),
                       rtol=1e-6)
    # some token would have chosen outside its four groups
    free, _ = dropless.route(x, router, bias, 6, scale=2.5)
    assert not np.array_equal(np.sort(np.asarray(free), -1),
                              np.sort(np.asarray(experts), -1))


def _route_as_it_was(x, router_kernel, selection_bias, top_k, scale):
    """``dropless.route`` before it knew groups (sigmoid, renormalised)."""
    logits = jnp.dot(x.astype(jnp.float32), router_kernel.astype(
        jnp.float32), precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    select = scores + selection_bias.astype(jnp.float32)[None]
    _, experts = jax.lax.top_k(select, top_k)
    weights = jnp.take_along_axis(scores, experts, axis=1)
    weights = weights / jnp.sum(weights, axis=1, keepdims=True) * scale
    return experts.astype(jnp.int32), weights


def test_one_group_is_todays_route_bit_for_bit():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(40, 32)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(32, 64)) * 0.3, jnp.float32)
    bias = jnp.asarray(rng.normal(size=(64,)) * 0.05, jnp.float32)
    got = dropless.route(x, router, bias, 6, scale=2.5, n_group=1,
                         topk_group=1)
    want = _route_as_it_was(x, router, bias, 6, 2.5)
    for a, b in zip(got, want):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # and the traced operations are the same ones
    new = jax.make_jaxpr(lambda *a: dropless.route(*a, 6, scale=2.5))(
        x, router, bias)
    old = jax.make_jaxpr(lambda *a: _route_as_it_was(*a, 6, 2.5))(
        x, router, bias)
    assert [e.primitive.name for e in new.eqns] == [
        e.primitive.name for e in old.eqns]


def test_the_clamp_is_in_the_experts_and_in_the_shared_expert(highest):
    """``limit`` > 0: ``gate <- min(gate, L)``, ``up <- clip(up, -L, L)``
    in the dense form of the held experts and in ``blocks.SwiGLU``, each
    against the reference at inputs large enough for the clamp to bite; 0
    traces the operations there always were."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(12, 16)) * 4.0, jnp.float32)
    gate, up = jnp.asarray(rng.normal(size=(2, 4, 16, 8)), jnp.float32)
    down = jnp.asarray(rng.normal(size=(4, 8, 16)), jnp.float32)
    experts = jnp.asarray(rng.integers(0, 8, (12, 2)), jnp.int32)
    weights = jnp.asarray(rng.uniform(size=(12, 2)), jnp.float32)
    p = {"gate": gate, "up": up, "down": down}
    outs = {}
    for limit in (0.0, 0.5):
        got, _ = dropless.expert_ffn(x, experts, weights, gate, up, down,
                                     first_expert=2, n_routed=8,
                                     use_kernel=False, limit=limit)
        want = reference.expert_terms(x, p, 2, experts, weights, limit)
        assert np.abs(np.asarray(got - want)).max() <= 1e-4
        outs[limit] = np.asarray(got)
    assert np.abs(outs[0.0] - outs[0.5]).max() > 1.0
    # a plain argument all the way down: a jitted caller traces the clamp
    # it names, whatever was traced before it
    under_jit = jax.jit(lambda *a: dropless.expert_ffn(
        *a, first_expert=2, n_routed=8, use_kernel=False, limit=0.5)[0])(
            x, experts, weights, gate, up, down)
    assert np.abs(np.asarray(under_jit) - outs[0.5]).max() <= 1e-4
    ffn = blocks.SwiGLU(8, 16, jnp.float32, jnp.float32, 0.25)
    sp = ffn.init(jax.random.PRNGKey(0), x)["params"]
    sp = jax.tree_util.tree_map(lambda w: w * 20.0, sp)
    clamped = ffn.apply({"params": sp}, x)
    assert np.abs(np.asarray(clamped - reference.swiglu(
        x, sp, 0.25))).max() <= 1e-4
    free = blocks.SwiGLU(8, 16, jnp.float32, jnp.float32).apply(
        {"params": sp}, x)
    assert np.abs(np.asarray(free - clamped)).max() > 1.0
    names = lambda f, *a: [e.primitive.name for e in jax.make_jaxpr(f)(
        *a).eqns]
    assert names(lambda g, u: dropless.glu(g, u, 0.0), x, x) == names(
        lambda g, u: jax.nn.silu(g) * u, x, x)


def test_the_clamped_grouped_kernel_is_the_dense_form(highest):
    """The Pallas grouped matmul with the clamp (interpret mode) against
    the dense form with it."""
    from deepspeed_tpu.utils.compat import tpu_interpret_mode

    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(16, 128)) * 2.0, jnp.float32)
    gate, up = jnp.asarray(rng.normal(size=(2, 2, 128, 128)) * 0.2,
                           jnp.float32)
    down = jnp.asarray(rng.normal(size=(2, 128, 128)) * 0.2, jnp.float32)
    experts = jnp.asarray(rng.integers(0, 4, (16, 2)), jnp.int32)
    weights = jnp.asarray(rng.uniform(size=(16, 2)), jnp.float32)
    args = (x, experts, weights, gate, up, down)
    want, _ = dropless.expert_ffn(*args, first_expert=1, n_routed=4,
                                  use_kernel=False, limit=0.5)
    with tpu_interpret_mode():
        got, _ = jax.block_until_ready(jax.jit(
            lambda *a: dropless.expert_ffn(*a, first_expert=1, n_routed=4,
                                           use_kernel=True, limit=0.5))(
                                               *args))
    free, _ = dropless.expert_ffn(*args, first_expert=1, n_routed=4,
                                  use_kernel=False)
    scale = float(np.abs(np.asarray(want)).max())
    assert np.abs(np.asarray(got - want)).max() <= 1e-5 * scale
    assert np.abs(np.asarray(free - want)).max() > 0.05 * scale


# ---------------------------------------------------------------------------
# the share's test
# ---------------------------------------------------------------------------
def test_the_shares_routed_sums_add_up_to_the_uncut_layer(highest):
    """Layer 5 (its experts clamped at 0.5, its shared expert at 0.25) as
    four shares, one routing group each: the four held sums, with the
    shared expert counted ONCE, are the uncut reference's layer, in the
    reference and in the program alike."""
    cfg, _, params = make()
    mlp = params["layers_5_mlp"]
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 11, cfg.hidden_size))
    whole = dict(shape_of(cfg), first_expert=0)
    y, shared, chosen, _ = reference.sparse(x, mlp, whole, 5)
    want = y + shared
    total, program = shared, None
    per = cfg.num_experts // 4
    assert per == cfg.num_experts // cfg.n_group
    for rank in range(4):
        held = {**mlp, **{name: mlp[name][rank * per:(rank + 1) * per]
                          for name in ("gate", "up", "down")}}
        part, _, picked, _ = reference.sparse(
            x, held, dict(shape_of(cfg), first_expert=rank * per), 5)
        assert np.array_equal(np.asarray(picked), np.asarray(chosen))
        total = total + part
        share = dataclasses.replace(cfg, ep_size=4, ep_rank=rank)
        got, got_shared, counters, _ = bailing_hybrid.SparseExperts(
            share, 5).apply({"params": held}, x)
        assert np.abs(np.asarray(got - part)).max() <= 1e-5
        assert int(counters[1]) == per
        program = got_shared + got if program is None else program + got
    assert np.abs(np.asarray(total - want)).max() <= 1e-5
    assert np.abs(np.asarray(program - want)).max() <= 1e-5
    # a share holds a whole group: a token's experts lie on at most two
    groups = np.asarray(chosen) // per
    assert max(len(set(row)) for row in groups.reshape(-1, 4)) <= 2


# ---------------------------------------------------------------------------
# serving: both seams in one model
# ---------------------------------------------------------------------------
def test_the_engine_sees_both_seams(served):
    cfg, _, srv = served
    assert srv.slot_state["entries"] == 1 and srv.slot_entries == 1
    assert srv.slot_state["knob"] == "state_slots"
    assert srv.row_kind["kind"] == "latent"
    pools = srv.cache
    assert pools["kda_state_pool"].dtype == jnp.float32
    assert pools["kda_state_pool"].shape == (4, 4, 4, 16, 16)
    assert pools["kda_conv_pool"].shape == (4, 4, 3 * 3 * 64)
    assert pools["latent_pool"].shape[0] == 2 and pools[
        "latent_pool"].shape[-1] == 128
    # a slot's table: its sequence's blocks, then its state row
    table = srv._slot_table(2, np.arange(1, 17, dtype=np.int32))
    assert table[-1] == 3 and len(table) == 17


@pytest.mark.parametrize("chunk", [0, 8], ids=["whole-prompt", "chunked"])
def test_paged_logits_match_the_reference(highest, served, chunk):
    """Prefill then decode through the latent pool and the slot's state
    against the reference's full forward pass, on LOGITS at every position:
    a prompt of 27 in a bucket it does not fill, or in chunks of 8 (each
    past the first starts from the stored state and the stored convolution
    rows, the last holds 3 real positions)."""
    cfg, params, srv = served
    assert FAMILY.paged_logits_match(srv, cfg, params, prompts(cfg, [27])[0],
                                     14, chunk=chunk) <= TOL


def test_a_slots_second_tenant_does_not_see_the_firsts_state(highest, served):
    cfg, params, srv = served
    for prompt in prompts(cfg, [30, 7]):
        assert FAMILY.paged_logits_match(srv, cfg, params, prompt, 5, slot=2,
                                         chunk=8) <= TOL, len(prompt)


def not_carried(pool, index, rows, fresh):
    """Control: every call starts from zeros."""
    return jnp.zeros_like(pool[index, rows])


def not_reset(pool, index, rows, fresh):
    """Control: a sequence at length 0 starts from what its slot held."""
    return pool[index, rows]


@pytest.mark.parametrize("control", [not_carried, not_reset])
def test_a_wrong_state_moves_the_logits(highest, monkeypatch, served,
                                        control):
    cfg, params, srv = served
    monkeypatch.setattr(bailing_hybrid, "state_in", control)
    # (the paged module traced anew under the patch)
    assert max(FAMILY.paged_logits_match(srv, cfg, params, prompt, 3, slot=2,
                                         chunk=8, retrace=True)
               for prompt in prompts(cfg, [30, 7])) > 100 * TOL


def test_prefill_chunks_and_decode_through_the_engine(highest, served):
    """Prompts in chunks of 8 (5 requests over 3 slots: slots reused after
    a finish, rows of unequal length) through ``init_inference`` ->
    ``ServingEngine``: every served token the reference's argmax at its
    position (a tie inside TOL aside); the final state of a finished
    request in its slot's row is the reference's; and the engine's
    counters, the three kinds of live bytes and the group counter."""
    cfg, params, srv = served
    stats, reqs = FAMILY.served_logits_match(
        cfg, params, list(zip(prompts(cfg, [5, 19, 33, 9, 26]),
                              [30, 12, 20, 25, 8])))
    assert max(r.prefill_chunks for r in reqs) == 5
    assert len({r.slot for r in reqs}) == 3
    # the last to finish: its slot's rows hold the recurrence's state after
    # prompt + served - 1 tokens
    last = max(reqs, key=lambda r: r.finish_ts)
    ids = np.zeros((1, 64), np.int32)
    fed = list(last.prompt) + last.tokens[:-1]
    ids[0, :len(fed)] = fed
    _, layers = FAMILY.reference_program(
        cfg, with_layers=True, stop=len(fed))(params, jnp.asarray(ids))
    held = np.asarray(srv.cache["kda_state_pool"][:, 1 + last.slot])
    assert np.abs(held - np.asarray(layers["states"])[:, 0]).max() <= 1e-5
    kv = stats["kv_live_bytes"]
    per_slot = cfg.state_bytes_per_slot()
    assert per_slot == {"state": 4 * 4 * 16 * 16 * 4,
                        "conv": 4 * 3 * 192 * 4}
    for kind in ("state", "conv"):
        assert kv[kind] == stats["busy_slot_steps"] * per_slot[kind]
    assert 0 < kv["latent"] and set(kv) == {"latent", "state", "conv"}
    assert {"kda_prefill_chunk", "kda_decode_xla",
            "mla_chunk_decompressed_xla", "mla_decode_absorbed_xla",
            "moe_experts_dense_xla"} <= set(stats["attention_paths"])
    counted = stats["model_counters"]["decode"]
    assert set(counted) == set(dropless.COUNTERS) | {"tokens_group_here",
                                                     "tokens_routed"}
    # every expert held here: every token chose the group(s) held
    assert counted["tokens_group_here"] == counted["tokens_routed"] > 0
    assert counted["pairs_all"] == 4 * counted["tokens_routed"]


def test_the_group_counter_counts_the_tokens_that_chose_the_held_group(
        highest):
    """A share that holds one group of four, two kept a token: about half
    the (token, layer) pairs chose it, and each is counted once however
    many of its experts lie there."""
    cfg, module, params = make()
    share = dataclasses.replace(cfg, ep_size=4, ep_rank=1)
    per = cfg.num_experts // 4
    held = {name: ({**leaf, **{w: leaf[w][per:2 * per]
                               for w in ("gate", "up", "down")}}
                   if "router" in leaf else leaf)
            for name, leaf in params.items()}
    paged = BailingHybridForCausalLM(share.for_paged_decode(
        9, 4, return_routed=True, state_slots=2))
    ids = np.random.default_rng(3).integers(0, cfg.vocab_size, (1, 16))
    paging = {"block_tables": jnp.asarray([[1, 2, 3, 4, 1]], jnp.int32),
              "lengths": jnp.zeros((1,), jnp.int32),
              "num_valid": jnp.asarray([13], jnp.int32), "prefill": True}
    cache = jax.jit(lambda ids: paged.init(
        jax.random.PRNGKey(0), ids, paging=paging)["cache"])(jnp.asarray(ids))
    (_, aux), _ = jax.jit(lambda p, cache, ids: paged.apply(
        {"params": p, "cache": cache}, ids, mutable=["cache"],
        paging=paging))(held, cache, jnp.asarray(ids))
    routed = np.asarray(aux["routed"])[0, :13].reshape(13, 5, 4)
    here = ((routed >= per) & (routed < 2 * per)).any(-1).sum()
    counters = np.asarray(aux["counters"])
    assert counters[-2] == here and counters[-1] == 13 * 5
    assert 0 < here < 13 * 5
    assert counters[2] == ((routed >= per) & (routed < 2 * per)).sum()


def test_the_state_pools_do_not_grow_with_the_context(highest):
    cfg, _, params = make()
    sizes = []
    for length in (64, 128):
        srv = FAMILY.serving_engine(params, cfg, max_model_len=length)
        sizes.append({k: v.size for k, v in srv.cache.items()})
        srv.destroy()
    assert sizes[0]["kda_state_pool"] == sizes[1]["kda_state_pool"]
    assert sizes[0]["kda_conv_pool"] == sizes[1]["kda_conv_pool"]
    assert sizes[1]["latent_pool"] > 1.9 * sizes[0]["latent_pool"]


def test_decode_through_both_kernels_matches_the_xla_paths(monkeypatch):
    """The decode program with the Pallas kernels in it (interpret mode):
    the state update on the pool in place (heads of 128 x 128) and the
    latent kernel over the block table, beside idle slots, against the same
    steps on the XLA paths."""
    cfg, _, params = make(head_dim=128, num_attention_heads=2,
                          hidden_size=64, kv_lora_rank=128,
                          num_hidden_layers=3,
                          expert_swiglu_limit_list=(),
                          share_expert_swiglu_limit_list=())
    got, want, paths = FAMILY.decode_through_the_kernels(
        monkeypatch, cfg, params, prompts(cfg, [11])[0], 2, chunk=8,
        experts=False)
    assert paths.get("kda_decode_kernel") and paths.get(
        "mla_decode_absorbed_kernel")
    assert np.abs(got - want).max() <= 10 * TOL


# ---------------------------------------------------------------------------
# where the family lives, and what it is refused with
# ---------------------------------------------------------------------------
def test_the_family_is_a_client_of_the_shared_blocks():
    """It imports ``deepseek_v2`` for the latent attention and no other
    family; ``blocks.py`` and ``deepseek_v2.py`` do not name it; the
    config's ``for_paged_decode`` and the module's ``__call__`` are the
    shared ones."""
    models = pathlib.Path(bailing_hybrid.__file__).parent
    tree = ast.parse((models / "bailing_hybrid.py").read_text())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            imported |= {f"{node.module}.{a.name}" for a in node.names}
    assert {"deepspeed_tpu.models.blocks",
            "deepspeed_tpu.models.deepseek_v2"} <= imported
    assert not [m for m in imported
                for other in ("mimo_v2", "lfm2_moe", "granite_hybrid",
                              "exaone_moe", "llama", "gpt2")
                if m.startswith(f"deepspeed_tpu.models.{other}")]
    for name in ("blocks.py", "deepseek_v2.py"):
        text = (models / name).read_text().lower()
        assert "bailing" not in text and "kda" not in text
    assert (BailingHybridConfig.for_paged_decode
            is blocks.ServedConfig.for_paged_decode)
    assert BailingHybridForCausalLM.__call__ is blocks.PagedDecoder.__call__
    cfg = BailingHybridConfig.tiny()
    assert cfg.routed_width == 5 * 4 and cfg.slot_knob == "state_slots"
    engine = (models.parent / "serving" / "engine.py").read_text().lower()
    assert "bailing" not in engine and "kda" not in engine


def test_importing_the_package_imports_neither_the_family_nor_its_ops():
    """Nothing the other cells import grows: the model and its two ops are
    imported by the family's file and the model's own module only."""
    code = ("import sys, deepspeed_tpu, deepspeed_tpu.serving, "
            "deepspeed_tpu.models.blocks, deepspeed_tpu.models.deepseek_v2; "
            "print(sorted(m for m in sys.modules if 'kda' in m or 'bailing' "
            "in m))")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, check=True,
                         cwd=str(pathlib.Path(deepspeed_tpu.__file__).parents[
                             1]))
    assert out.stdout.strip().splitlines()[-1] == "[]"


@REFUSED
def test_mechanisms_that_know_block_tables_only_refuse_the_model(serving,
                                                                 mechanism):
    assert "delta-rule layers keep a state" in FAMILY.mechanism_refusal(
        serving, mechanism)


def test_tensor_parallel_refuses_the_model():
    assert "delta-rule layers keep a state" in (
        FAMILY.tensor_parallel_refusal())


def test_migration_refuses_the_model():
    assert all("delta-rule" in said for said in FAMILY.migration_refusals())


def test_the_config_refuses_what_the_family_does_not_implement():
    cfg = BailingHybridConfig.tiny()
    with pytest.raises(ValueError, match="state_slots"):
        cfg.for_paged_decode(9, 4)
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        cfg.for_paged_decode(9, 4, kv_dtype="int8", state_slots=2)
    with pytest.raises(ValueError, match="expert_swiglu_limit_list"):
        BailingHybridConfig.tiny(expert_swiglu_limit_list=(0, 4))
    with pytest.raises(ValueError, match="groups"):
        BailingHybridConfig.tiny(n_group=5)
    with pytest.raises(ValueError, match="experts over"):
        BailingHybridConfig.tiny(ep_size=3)
    assert cfg.paged_slot_state_for(4)["entries"] == 1
    assert cfg.paged_row_kind()["kind"] == "latent"
    # the published widths: 15.2 MB a slot whatever its length
    full = BailingHybridConfig(num_hidden_layers=8)
    assert full.state_bytes_per_slot() == {
        "state": 7 * 32 * 128 * 128 * 4, "conv": 7 * 3 * 12288 * 2}
    assert full.latent_lanes == 640 and full.kv_bytes_per_token() == {
        "latent": 1152}
