"""The Granite-4.0-H family at a small size on the CPU: the program in
float32 against the plain reference (``perfbench/reference_granite_hybrid``,
the recurrence ONE position at a time) on LOGITS: the plain call and each
mixer alone; the recurrence in its forms (one position at a time = the
chunked scan whole = the scan in pieces of uneven ``num_valid`` with
padding, state carried = decode steps, XLA and kernel); prefill chunks then
decode through ``ServingEngine``'s pools against the reference's full
forward; a slot's second tenant; the four multipliers; the controls the
comparisons are not blind to; the engine's counters; and each refusal by
name."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import blocks, granite_hybrid
from deepspeed_tpu.models.granite_hybrid import (GraniteAttention,
                                                 GraniteHybridConfig,
                                                 GraniteHybridForCausalLM,
                                                 Mamba2Mixer)
from deepspeed_tpu.ops import ssm_state_update
from deepspeed_tpu.ops.ssd_chunk_scan import ssd_chunk_scan
from deepspeed_tpu.ops.ssm_state_update import from_lanes, to_lanes
from perfbench import reference_granite_hybrid as reference
from tests.unit.served_family import REFUSED, Family, highest, prompts  # noqa: F401

# float32 program against the float32 reference, on logits of order 0.2:
# what another order of summation leaves (the two agree to 3e-8 here)
TOL = 2e-6
BLOCK = 4


def shape_of(cfg: GraniteHybridConfig) -> dict:
    """The reference's view of a program config (the family builds the
    same from a configuration file)."""
    return dict(heads=cfg.num_attention_heads,
                kv_heads=cfg.num_key_value_heads, eps=cfg.rms_norm_eps,
                types=cfg.layer_types, ssm_heads=cfg.mamba_n_heads,
                ssm_head=cfg.mamba_d_head, ssm_state=cfg.mamba_d_state,
                embedding_multiplier=cfg.embedding_multiplier,
                residual_multiplier=cfg.residual_multiplier,
                attention_multiplier=cfg.attention_multiplier,
                logits_scaling=cfg.logits_scaling)


# prompts in chunks of 8 = two scan chunks of 4
FAMILY = Family(GraniteHybridConfig, GraniteHybridForCausalLM, reference,
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
# the plain call, each mixer alone, the multipliers
# ---------------------------------------------------------------------------
def test_full_forward_matches_the_reference(highest):
    cfg, module, params = make()
    ids = np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 37))
    got = np.asarray(FAMILY.plain(cfg)(params, jnp.asarray(ids)))
    assert np.abs(got - reference_logits(cfg, params, ids)).max() <= TOL
    # tied: no head of its own; the Mamba layer's leaves
    assert "lm_head" not in params and "layers_2_attn" in params
    assert set(params["layers_0_mamba"]) == {
        "in_proj", "conv", "conv_bias", "dt_bias", "A_log", "D", "norm",
        "out_proj"}


@pytest.mark.parametrize("mixer", ["mamba", "attention"])
def test_a_mixer_alone_matches_the_reference(highest, mixer):
    cfg, _, params = make()
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 21, cfg.hidden_size))
    if mixer == "mamba":
        p = params["layers_0_mamba"]
        got, _ = Mamba2Mixer(cfg).apply({"params": p}, x)
        want = reference.mamba(x, p, shape_of(cfg))
    else:
        p = params["layers_2_attn"]
        got, _ = GraniteAttention(cfg).apply({"params": p}, x)
        want = reference.attention(x, p, shape_of(cfg))
    scale = float(np.abs(np.asarray(want)).max())
    assert np.abs(np.asarray(got - want)).max() <= 1e-5 * scale and scale > 0


def test_the_initialisers_are_mamba2s_own():
    """``A`` in [1, 16], ``delta`` at ``dt = 0`` log-uniform in [0.001,
    0.1], ``D`` = 1, taps in [-1/2, 1/2]: a head remembers between ten and
    a thousand positions, not two."""
    _, _, params = make(mamba_n_heads=64, mamba_d_head=2, hidden_size=64)
    p = params["layers_0_mamba"]
    rate = np.exp(np.asarray(p["A_log"]))
    delta = np.log1p(np.exp(np.asarray(p["dt_bias"])))
    assert 1.0 <= rate.min() and rate.max() <= 16.0 and rate.std() > 2
    assert 1e-3 <= delta.min() * 1.001 and delta.max() <= 0.1001
    assert np.log(delta).std() > 0.8
    assert (np.asarray(p["D"]) == 1).all()
    taps = np.asarray(p["conv"])
    assert np.abs(taps).max() <= 0.5 and taps.std() > 0.2


@pytest.mark.parametrize("field", ["embedding_multiplier",
                                   "residual_multiplier", "logits_scaling"])
def test_each_multiplier_is_in_the_program_and_the_reference(highest, field):
    """Another value of each moves the program's logits and the
    reference's alike."""
    cfg, _, params = make()
    ids = np.random.default_rng(1).integers(0, cfg.vocab_size, (1, 19))
    base = reference_logits(cfg, params, ids)
    moved = dataclasses.replace(cfg, **{field: 3.0 * getattr(cfg, field)})
    got = np.asarray(FAMILY.plain(moved)(params, jnp.asarray(ids)))
    want = reference_logits(moved, params, ids)
    assert np.abs(got - want).max() <= TOL
    assert np.abs(want - base).max() > 100 * TOL


def test_the_attentions_scale_is_the_multiplier_not_the_head_size(highest):
    """Scores are ``q k^T * attention_multiplier``: the queries take its
    ratio to ``dk ** -0.5`` before the shared attention paths; at the ratio
    1 (the multiplier ``dk ** -0.5``) the output is another."""
    cfg, _, params = make()
    p = params["layers_2_attn"]
    x = 4.0 * jax.random.normal(jax.random.PRNGKey(2), (1, 21,
                                                        cfg.hidden_size))
    want = np.asarray(reference.attention(x, p, shape_of(cfg)))
    for c in (cfg, dataclasses.replace(
            cfg, attention_multiplier=cfg.head_dim ** -0.5)):
        got, _ = GraniteAttention(c).apply({"params": p}, x)
        ref = np.asarray(reference.attention(x, p, shape_of(c)))
        assert np.abs(np.asarray(got) - ref).max() <= 1e-5 * np.abs(ref).max()
    assert np.abs(ref - want).max() > 1e-3 * np.abs(want).max()


def test_a_config_without_the_scalars_traces_no_multiply():
    """``blocks._scaled`` at 1 is its argument: the shell of a family that
    sets no multiplier traces the operations it always did."""
    x = jnp.ones((3,))
    assert blocks._scaled(x, 1) is x and blocks._scaled(x, 1.0) is x
    assert float(blocks._scaled(x, 0.22)[0]) == pytest.approx(0.22)
    assert (blocks.ServedConfig.embedding_multiplier
            == blocks.ServedConfig.residual_multiplier
            == blocks.ServedConfig.logits_scaling == 1.0)


def test_bf16_fails_the_float32_tolerance():
    cfg, _, params = make()
    ids = np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 37))
    low = FAMILY.plain(dataclasses.replace(cfg, dtype=jnp.bfloat16))
    got = np.asarray(low(params, jnp.asarray(ids)))
    assert np.abs(got - reference_logits(cfg, params, ids)).max() > 100 * TOL


# ---------------------------------------------------------------------------
# one recurrence, four forms
# ---------------------------------------------------------------------------
def _recurrence_inputs(rows=2, t=23, heads=4, width=8, n=16, seed=3):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    delta = jnp.asarray(rng.uniform(0.01, 0.5, (rows, t, heads)),
                        jnp.float32)
    rate = -jnp.asarray(rng.uniform(1, 16, (heads,)), jnp.float32)
    return f(rows, t, heads, width), delta, rate, f(rows, t, n), f(rows, t, n)


@pytest.mark.parametrize("chunk", [4, 8, 32])
def test_the_chunked_scan_is_the_recurrence_one_position_at_a_time(highest,
                                                                   chunk):
    x, delta, rate, b, c = _recurrence_inputs()
    want, last = reference.recurrence(x, delta, jnp.exp(delta * rate), b, c)
    zero = to_lanes(jnp.zeros(last.shape, jnp.float32))
    got, state = ssd_chunk_scan(x, delta, rate, b, c, zero, chunk)
    assert np.abs(np.asarray(got - want)).max() <= 1e-5
    assert np.abs(np.asarray(from_lanes(state, 4, 8) - last)).max() <= 1e-5


def test_the_scan_in_pieces_of_uneven_num_valid_carries_the_state(highest):
    """Pieces of 8 positions holding 8, 5, 8 and 2 real ones (padding
    behind them, ``delta`` = 0 there): each starts from the state the last
    left, and the pieces' real positions are the whole's."""
    x, delta, rate, b, c = _recurrence_inputs(t=23)
    want, last = reference.recurrence(x, delta, jnp.exp(delta * rate), b, c)
    state = to_lanes(jnp.zeros(last.shape, jnp.float32))
    at, rows = 0, []
    for real in (8, 5, 8, 2):
        def piece(v):
            v = v[:, at:at + real]
            return jnp.pad(v, ((0, 0), (0, 8 - real)) + ((0, 0),)
                           * (v.ndim - 2), constant_values=7.0)
        d = jnp.where(jnp.arange(8)[None, :, None] < real, piece(delta), 0.0)
        y, state = ssd_chunk_scan(piece(x), d, rate, piece(b), piece(c),
                                  state, 4)
        rows.append(y[:, :real])
        at += real
    assert np.abs(np.asarray(jnp.concatenate(rows, 1) - want)).max() <= 1e-5
    assert np.abs(np.asarray(from_lanes(state, 4, 8) - last)).max() <= 1e-5


def test_the_scan_kernel_is_the_einsum_form(highest):
    """The Pallas form (interpret mode) at sizes that are whole registers:
    three chunks of 128 with padding behind 300 positions, a state handed
    in, against the einsums."""
    from deepspeed_tpu.ops.ssd_chunk_scan import kernel_serves
    from deepspeed_tpu.utils.compat import tpu_interpret_mode

    x, delta, rate, b, c = _recurrence_inputs(2, 300, 8, 16, 128)
    state = to_lanes(jnp.asarray(np.random.default_rng(1).normal(
        size=(2, 8, 16, 128)), jnp.float32))
    assert kernel_serves(128, 8, 16, 128) and state.shape == (2, 1, 128, 128)
    want, last = ssd_chunk_scan(x, delta, rate, b, c, state, 128,
                                use_kernel=False)
    with tpu_interpret_mode():
        got, carried = jax.block_until_ready(jax.jit(
            lambda *a: ssd_chunk_scan(*a, 128, use_kernel=True))(
                x, delta, rate, b, c, state))
    scale = float(np.abs(np.asarray(want)).max())
    assert np.abs(np.asarray(got - want)).max() <= 1e-5 * scale
    assert np.abs(np.asarray(carried - last)).max() <= 1e-5


# (form, heads, width, state size): lane groups of 128 (one, and two in
# tiles of one) and, for the XLA form, a row narrower than 128 lanes (L = H P)
DECODE_SHAPES = [("xla", 8, 16, 128), ("kernel", 8, 16, 128),
                 ("xla", 4, 8, 16), ("xla", 8, 32, 32),
                 ("kernel", 8, 32, 32)]
_ids = lambda case: "-".join(map(str, case))


def _update(form):
    """The decode update of one form, the kernel in tiles of ONE lane group
    (two grid steps a row at 8 x 32)."""
    if form == "xla":
        return ssm_state_update.state_update_xla
    return lambda *args: ssm_state_update.state_update_kernel(
        *args, group_tile=1)


def _held(pool, layer, rows):
    """What ``rows`` of a layer of the pool hold, as the plain recurrence
    writes a state: ``[rows, H, P, N]``."""
    _, _, heads, width, _ = pool.shape
    return np.asarray(ssm_state_update.from_lanes(
        ssm_state_update.lane_view(pool)[layer, np.asarray(rows)], heads,
        width))


@pytest.mark.parametrize("case", DECODE_SHAPES, ids=_ids)
def test_decode_steps_are_the_recurrence(highest, case):
    """The in-place state update, a step a position, on a pool of several
    layers and slots, against a plain ``[H, P, N]`` float32 recurrence that
    knows nothing of the pool's layout: the rows' slots in any order, idle
    rows between them; ``a = 0`` restarts a row. The kernel (interpret
    mode) leaves the idle rows' row 0 and every other layer untouched."""
    from deepspeed_tpu.utils.compat import tpu_interpret_mode

    form, heads, width, n = case
    t = 6
    x, delta, rate, b, c = _recurrence_inputs(3, t, heads, width, n)
    want, last = reference.recurrence(x, delta, jnp.exp(delta * rate), b, c)
    rng = np.random.default_rng(0)
    pool = jnp.asarray(rng.normal(size=(2, 6, heads, width, n)), jnp.float32)
    slots = jnp.asarray([4, 0, 2, 0, 5], jnp.int32)     # rows 0, 2, 4 busy
    busy = np.asarray([0, 2, 4])
    assert ssm_state_update.kernel_serves(heads, width, n) or form == "xla"

    def spread(v):
        out = jnp.zeros((5, *v.shape[1:]), v.dtype)
        return out.at[busy].set(v)

    def step(pool, k):
        a = jnp.exp(delta[:, k] * rate)
        if k == 0:
            a = jnp.zeros_like(a)          # the sequences start here
        return _update(form)(
            pool, 1, slots, spread(a), spread(delta[:, k, :, None] * x[:, k]),
            spread(b[:, k]), spread(c[:, k]))

    before = np.asarray(pool)
    rows = []
    with tpu_interpret_mode():
        for k in range(t):
            y, pool = jax.block_until_ready(jax.jit(step, static_argnums=1)(
                pool, k))
            rows.append(np.asarray(y)[busy])
    assert pool.shape == before.shape
    assert np.abs(np.stack(rows, 1) - np.asarray(want)).max() <= 1e-5
    after = np.asarray(pool)
    assert np.abs(_held(pool, 1, [4, 2, 5]) - np.asarray(last)).max() <= 1e-5
    assert (after[0] == before[0]).all() and (after[1, [1, 3]]
                                              == before[1, [1, 3]]).all()
    if form == "kernel":
        assert (after[1, 0] == before[1, 0]).all()
        assert (np.asarray(y)[[1, 3]] == 0).all()


@pytest.mark.parametrize("case", [(8, 16, 32), (64, 64, 128), (4, 8, 16),
                                  (3, 8, 16)], ids=_ids)
def test_a_rows_lane_layout_is_its_own_inverse(case):
    """``to_lanes`` / ``from_lanes`` between the scan's ``[H, P, N]`` and the
    pool's ``[H P / L, N, L]``: lane groups of 128 where ``H P`` is a
    multiple of 128, else one group of all ``H P``; value ``(h, p, n)`` lies
    at group ``(h P + p) // L``, sublane ``n``, lane ``(h P + p) % L``; and
    ``lane_view`` of the allocation is a reshape of a row's values as they
    lie."""
    heads, width, n = case
    inner = heads * width
    lanes = ssm_state_update.lane_width(heads, width)
    assert lanes == (128 if inner % 128 == 0 else inner)
    assert ssm_state_update.kernel_serves(*case) == (lanes == 128)
    assert not ssm_state_update.kernel_serves(heads, width, n + 8)
    rng = np.random.default_rng(1)
    state = jnp.asarray(rng.normal(size=(2, 3, heads, width, n)), jnp.float32)
    lying = ssm_state_update.to_lanes(state)
    assert lying.shape == (2, 3, inner // lanes, n, lanes)
    assert (ssm_state_update.from_lanes(lying, heads, width) == state).all()
    column, at = int(rng.integers(inner)), int(rng.integers(n))
    assert (np.asarray(lying)[:, :, column // lanes, at, column % lanes]
            == np.asarray(state).reshape(2, 3, inner, n)[:, :, column,
                                                          at]).all()
    # the pool as allocated holds those values in that order, flat
    pool = lying.reshape(state.shape)
    assert ssm_state_update.lane_view(pool).shape == lying.shape
    assert (ssm_state_update.lane_view(pool) == lying).all()
    assert (np.asarray(pool).reshape(-1) == np.asarray(lying).reshape(
        -1)).all()


@pytest.mark.parametrize("order", ["chunk-then-steps", "steps-then-chunk",
                                   "chunk-steps-chunk"])
@pytest.mark.parametrize("case", [("xla", 8, 16, 128), ("kernel", 8, 32, 32),
                                  ("xla", 4, 8, 16)], ids=_ids)
def test_the_two_writers_of_the_pool_agree_on_its_layout(highest, case,
                                                         order):
    """A state WRITTEN by a prefill chunk (``scan_state_out``) and READ by
    decode steps on the pool in place, and a state left by decode steps and
    read by a second chunk (``scan_state_in``): twelve positions through
    the pool equal the plain recurrence one position at a time, every
    ``y`` and the state at the end."""
    from deepspeed_tpu.utils.compat import tpu_interpret_mode

    form, heads, width, n = case
    t = 12
    x, delta, rate, b, c = _recurrence_inputs(3, t, heads, width, n)
    want, last = reference.recurrence(x, delta, jnp.exp(delta * rate), b, c)
    pool = jnp.asarray(np.random.default_rng(0).normal(
        size=(2, 6, heads, width, n)), jnp.float32)
    slots = jnp.asarray([4, 2, 5], jnp.int32)
    pieces = {"chunk-then-steps": [("chunk", 8), ("steps", 4)],
              "steps-then-chunk": [("steps", 4), ("chunk", 8)],
              "chunk-steps-chunk": [("chunk", 5), ("steps", 3),
                                    ("chunk", 4)]}[order]

    def chunk(pool, at, size):
        piece = lambda v: v[:, at:at + size]
        state = granite_hybrid.scan_state_in(
            pool, 1, slots, jnp.full((3,), at == 0))
        y, state = ssd_chunk_scan(piece(x), piece(delta), rate, piece(b),
                                  piece(c), state, 4)
        return y, granite_hybrid.scan_state_out(pool, 1, slots, state)

    def step(pool, k):
        a = jnp.exp(delta[:, k] * rate)
        if k == 0:
            a = jnp.zeros_like(a)
        y, pool = _update(form)(pool, 1, slots, a,
                                delta[:, k, :, None] * x[:, k], b[:, k],
                                c[:, k])
        return y[:, None], pool

    at, rows = 0, []
    with tpu_interpret_mode():
        for kind, size in pieces:
            if kind == "chunk":
                y, pool = jax.block_until_ready(jax.jit(
                    chunk, static_argnums=(1, 2))(pool, at, size))
                rows.append(np.asarray(y))
            else:
                for k in range(at, at + size):
                    y, pool = jax.block_until_ready(jax.jit(
                        step, static_argnums=1)(pool, k))
                    rows.append(np.asarray(y))
            at += size
    assert np.abs(np.concatenate(rows, 1) - np.asarray(want)).max() <= 1e-5
    assert np.abs(_held(pool, 1, slots) - np.asarray(last)).max() <= 1e-5


@pytest.mark.parametrize("case", [("xla", 8, 32, 32), ("kernel", 8, 32, 32),
                                  ("xla", 4, 8, 16)], ids=_ids)
def test_a_fresh_row_forgets_its_slots_last_tenant_and_an_idle_row_is_left(
        highest, case):
    """``a = 0`` on a slot whose last tenant left LARGE values: the state
    after the step is ``(delta x) B^T`` alone and ``y`` its readout, to the
    bit of a state of zeros; an idle row's ``y`` is 0 and, under the kernel,
    no pool row but the busy ones changes by a bit."""
    from deepspeed_tpu.utils.compat import tpu_interpret_mode

    form, heads, width, n = case
    rng = np.random.default_rng(4)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    slots = jnp.asarray([0, 3, 0, 1], jnp.int32)
    a = jnp.zeros((4, heads), jnp.float32)
    args = (1, slots, a, f(4, heads, width), f(4, n), f(4, n))
    pool = 1e6 * (1 + jnp.abs(f(2, 5, heads, width, n)))
    with tpu_interpret_mode():
        run = jax.jit(_update(form))
        y, after = jax.block_until_ready(run(pool, *args))
        y0, zeros = jax.block_until_ready(run(jnp.zeros_like(pool), *args))
    busy = np.asarray([1, 3])
    assert (np.asarray(y)[busy] == np.asarray(y0)[busy]).all()
    assert (_held(after, 1, [3, 1]) == _held(zeros, 1, [3, 1])).all()
    dx, b, c = (np.asarray(v)[busy] for v in args[3:])
    state = dx[..., None] * b[:, None, None, :]
    assert np.abs(_held(after, 1, [3, 1]) - state).max() <= 1e-6
    assert np.abs(np.asarray(y)[busy] - np.einsum(
        "rhpn,rn->rhp", state, c)).max() <= 1e-4
    before, after = np.asarray(pool), np.asarray(after)
    assert (after[0] == before[0]).all()
    assert (after[1, [2, 4]] == before[1, [2, 4]]).all()
    if form == "kernel":
        assert (after[1, 0] == before[1, 0]).all()
        assert (np.asarray(y)[[0, 2]] == 0).all()


def test_the_kernels_work_list_puts_the_busy_rows_first():
    order, count = ssm_state_update.busy_rows(
        jnp.asarray([0, 3, 0, 1, 2, 0], jnp.int32))
    assert int(count[0]) == 3 and order.shape == (7,)
    assert order[:3].tolist() == [1, 3, 4]


# ---------------------------------------------------------------------------
# through the paged cache and the per-slot state
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("chunk", [0, 8], ids=["whole-prompt", "chunked"])
def test_paged_logits_match_the_reference(highest, served, chunk):
    """Prefill then decode through the cache and the state against the
    reference's full forward pass, on LOGITS at every position: a prompt
    of 27 in a bucket of 40 (the state is taken at position 26, not at the
    padding's end), or in chunks of 8 (each past the first starts from the
    stored state, the last holds 3 real positions)."""
    cfg, params, srv = served
    assert FAMILY.paged_logits_match(srv, cfg, params, prompts(cfg, [27])[0],
                                     14, chunk=chunk) <= TOL


def test_a_slots_second_tenant_does_not_see_the_firsts_state(highest, served):
    """Requests one after the other in ONE slot, the second shorter than
    the first: each is the reference's."""
    cfg, params, srv = served
    for prompt in prompts(cfg, [30, 7]):
        assert FAMILY.paged_logits_match(srv, cfg, params, prompt, 5, slot=2,
                                         chunk=8) <= TOL, len(prompt)


def not_carried(pool, index, rows, fresh):
    """Control: every call starts from zeros (the state is not carried
    between a prompt's chunks, nor into decode)."""
    return jnp.zeros_like(pool[index, rows])


def not_reset(pool, index, rows, fresh):
    """Control: a sequence at length 0 starts from what its slot held."""
    return pool[index, rows]


@pytest.mark.parametrize("control", [not_carried, not_reset])
def test_a_wrong_state_moves_the_logits(highest, monkeypatch, served,
                                        control):
    cfg, params, srv = served
    monkeypatch.setattr(granite_hybrid, "state_in", control)
    # (the paged module traced anew under the patch)
    assert max(FAMILY.paged_logits_match(srv, cfg, params, prompt, 3, slot=2,
                                         chunk=8, retrace=True)
               for prompt in prompts(cfg, [30, 7])) > 1000 * TOL


def test_prefill_chunks_and_decode_through_the_engine(highest, served):
    """Prompts in chunks of 8 (5 requests over 3 slots: slots reused after
    a finish, rows of unequal length) through ``init_inference`` ->
    ``ServingEngine``: every served token the reference's argmax at its
    position, on the reference's logits over prompt + served tokens (a tie
    inside TOL aside); and the engine's counters."""
    cfg, params, _ = served
    stats, reqs = FAMILY.served_logits_match(
        cfg, params, list(zip(prompts(cfg, [5, 19, 33, 9, 26]),
                              [30, 12, 20, 25, 8])))
    assert max(r.prefill_chunks for r in reqs) == 5
    assert len({r.slot for r in reqs}) == 3
    kv = stats["kv_live_bytes"]
    # host arithmetic at each step boundary: busy slots x the state's bytes
    assert kv["state"] == stats["busy_slot_steps"] * cfg.state_bytes_per_slot()
    per_layer = (cfg.mamba_inner * cfg.mamba_d_state
                 + 3 * (cfg.mamba_inner + 2 * cfg.mamba_d_state))
    assert cfg.state_bytes_per_slot() == 4 * per_layer * 4
    assert 0 < kv["global"] and set(kv) == {"global", "state"}
    assert {"granite_ssm_prefill_chunk", "granite_ssm_decode_xla",
            "granite_attn_cached_tiled_xla",
            "granite_attn_cached_xla"} <= set(stats["attention_paths"])
    # no layer is sparse: the counters are there and count nothing
    assert set(stats["model_counters"]["decode"].values()) == {0}


def test_the_state_pools_do_not_grow_with_the_context(highest):
    cfg, _, params = make()
    sizes = {}
    for longest in (32, 64):
        srv = FAMILY.serving_engine(params, cfg, max_model_len=longest)
        sizes[longest] = {k: v.shape for k, v in srv.cache.items()}
        entries = srv.slot_entries
        table = srv._slot_table(2, np.arange(3))
        srv.destroy()
    assert entries == 1 and table.tolist() == [0, 1, 2, 3]
    for longest in sizes:
        assert sizes[longest]["ssm_state_pool"] == (
            4, 1 + 3, cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state)
        assert sizes[longest]["ssm_conv_pool"] == (
            4, 1 + 3, 3 * (cfg.mamba_inner + 2 * cfg.mamba_d_state))
    assert sizes[32]["global_key_pool"][1] < sizes[64]["global_key_pool"][1]
    assert sizes[64]["global_key_pool"][0] == 1


def test_decode_through_both_kernels_matches_the_xla_paths(monkeypatch):
    """The decode program with the Pallas kernels in it (interpret mode):
    the state update on the pool in place (a state size of 128 lanes) and
    the paged GQA kernel over the block table, beside idle slots, against
    the same steps on the XLA paths."""
    cfg, _, params = make(mamba_d_state=128)
    got, want, paths = FAMILY.decode_through_the_kernels(
        monkeypatch, cfg, params, prompts(cfg, [19])[0], 3, chunk=8,
        experts=False)
    assert paths.get("granite_ssm_decode_kernel") and paths.get(
        "granite_attn_decode_kernel")
    assert np.abs(got - want).max() <= TOL


def test_a_chunks_attention_in_tiles_of_keys_is_the_whole_tables(highest):
    """``blocks.cached_gqa`` with ``key_tile``: as many tiles as the
    longest row has keys, under an online softmax, against the plain form
    over every block of the table; rows of unequal length, one of them
    shorter than a tile; a tile that does not divide the table falls back."""
    from deepspeed_tpu.ops.attention import dispatch_counts

    rng = np.random.default_rng(4)
    b, t, heads, kv, dk, bs, blocks_a_row = 2, 6, 4, 2, 8, 4, 6
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    k_pool, v_pool = f(2, 1 + b * blocks_a_row, bs, kv * dk), f(
        2, 1 + b * blocks_a_row, bs, kv * dk)
    table = jnp.asarray(1 + np.arange(b * blocks_a_row).reshape(b, -1),
                        jnp.int32)
    lengths = jnp.asarray([13, 1], jnp.int32)
    paging = {"lengths": lengths, "num_valid": jnp.asarray([6, 4], jnp.int32)}
    pos = lengths[:, None] + jnp.arange(t, dtype=jnp.int32)[None]
    q = f(b, t, heads, dk)
    args = (q, pos, paging, table, (k_pool, v_pool, 1), (kv, dk, dk), "test")
    want = blocks.cached_gqa(*args)
    before = dispatch_counts().get("test_cached_tiled_xla", 0)
    got = blocks.cached_gqa(*args, key_tile=8)
    assert dispatch_counts()["test_cached_tiled_xla"] == before + 1
    # (positions past a row's num_valid attend to keys no one wrote: both
    # forms see the same rows of the pool)
    assert np.abs(np.asarray(got - want)).max() <= 1e-5
    assert blocks.cached_gqa(*args, key_tile=16) is not None   # 4 blocks: no
    assert dispatch_counts()["test_cached_tiled_xla"] == before + 1


def test_the_family_is_a_client_of_the_shared_blocks():
    """It imports no other family; ``blocks.py`` does not name it; the
    config's ``for_paged_decode`` and the module's ``__call__`` are the
    shared ones."""
    import ast
    import pathlib

    models = pathlib.Path(granite_hybrid.__file__).parent
    tree = ast.parse((models / "granite_hybrid.py").read_text())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            imported |= {f"{node.module}.{a.name}" for a in node.names}
    assert "deepspeed_tpu.models.blocks" in imported
    assert not [m for m in imported
                for other in ("mimo_v2", "lfm2_moe", "deepseek_v2", "llama",
                              "gpt2")
                if m.startswith(f"deepspeed_tpu.models.{other}")]
    assert "granite" not in (models / "blocks.py").read_text().lower()
    assert (GraniteHybridConfig.for_paged_decode
            is blocks.ServedConfig.for_paged_decode)
    assert GraniteHybridForCausalLM.__call__ is blocks.PagedDecoder.__call__
    assert GraniteHybridForCausalLM.serve_routed is False
    cfg = GraniteHybridConfig.tiny()
    assert cfg.routed_width == 0 and cfg.slot_knob == "state_slots"


# ---------------------------------------------------------------------------
# refusals, by name
# ---------------------------------------------------------------------------
@REFUSED
def test_mechanisms_that_know_block_tables_only_refuse_the_model(serving,
                                                                 mechanism):
    assert "a matrix a head" in FAMILY.mechanism_refusal(serving, mechanism)


def test_tensor_parallel_refuses_the_model():
    assert "state-space layers keep a state" in (
        FAMILY.tensor_parallel_refusal())


def test_migration_refuses_the_model():
    assert all("state-space" in said for said in FAMILY.migration_refusals())


def test_routed_sets_are_refused_there_are_none():
    cfg, _, params = make()
    with pytest.raises(Exception, match="routed_experts_kept"):
        FAMILY.serving_engine(params, cfg, routed_experts_kept=4)


def test_the_config_refuses_what_the_family_does_not_implement():
    cfg = GraniteHybridConfig.tiny()
    with pytest.raises(ValueError, match="state_slots"):
        cfg.for_paged_decode(9, 4)
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        cfg.for_paged_decode(9, 4, kv_dtype="int8", state_slots=2)
    with pytest.raises(ValueError, match="layer_types"):
        GraniteHybridConfig.tiny(layer_types=("mamba", "window"))
    with pytest.raises(ValueError, match="num_local_experts 72"):
        GraniteHybridConfig.tiny(num_local_experts=72)
    with pytest.raises(ValueError, match="mamba_n_groups"):
        GraniteHybridConfig.tiny(mamba_n_groups=8)
    assert cfg.paged_slot_state_for(4)["entries"] == 1
