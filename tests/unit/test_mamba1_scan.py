"""The Mamba-1 recurrence's forms (``ops/mamba1_scan.py``) against the plain
recurrence one position at a time (``perfbench/reference_phi4flash``, a
state ``[C, N]`` that knows nothing of the pool's layout): the chunk scan,
the decode update applied a step a position, a state carried across calls
and between the two writers of the pool, the kernels under the interpreter
against the XLA forms, a fresh row and an idle one."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import mamba1_scan as ms
from deepspeed_tpu.utils.compat import tpu_interpret_mode
from perfbench import reference_phi4flash as reference
from tests.unit.served_family import highest  # noqa: F401

# (form, channels, states): whole registers (one lane group, and three) for
# either form, and for the XLA forms rows narrower than a register
SHAPES = [("xla", 128, 16), ("kernel", 128, 16), ("kernel", 384, 8),
          ("xla", 48, 4)]
_ids = lambda case: "-".join(map(str, case))


def _inputs(rows=2, t=24, channels=128, n=16, seed=3):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    delta = jnp.asarray(rng.uniform(0.001, 0.4, (rows, t, channels)),
                        jnp.float32)
    a_log = jnp.asarray(np.log(rng.uniform(1, 16, (channels, n))),
                        jnp.float32)
    return f(rows, t, channels), delta, a_log, f(rows, t, n), f(rows, t, n)


def _written(state, channels, n):
    """A state as the plain recurrence writes it, ``[.., C, N]``, of the
    pool's ``[.., C / L, N, L]``."""
    state = np.asarray(state)
    return np.swapaxes(state, -1, -2).reshape(*state.shape[:-3], channels, n)


def _zero(rows, channels, n):
    return jnp.zeros((rows, *ms.pool_row_shape(channels, n)), jnp.float32)


def _run(fn, *args, **static):
    with tpu_interpret_mode():
        return jax.block_until_ready(jax.jit(
            lambda *a: fn(*a, **static))(*args))


def test_the_layout_puts_the_states_down_the_sublanes():
    """``pool_row_shape``: lane groups of 128 channels along the lanes,
    the states down the sublanes; ``rate_lanes`` lays ``A`` out the same
    way; ``grouped`` a row of channels."""
    assert ms.pool_row_shape(5120, 16) == (40, 16, 128)
    assert ms.pool_row_shape(48, 4) == (1, 4, 48)
    assert ms.kernel_serves(5120, 16) and not ms.kernel_serves(48, 4)
    assert not ms.kernel_serves(128, 4)
    a_log = jnp.asarray(np.random.default_rng(0).normal(size=(256, 8)),
                        jnp.float32)
    a = np.asarray(ms.rate_lanes(a_log))
    assert a.shape == (2, 8, 128)
    assert a[1, 3, 5] == -np.exp(np.asarray(a_log)[128 + 5, 3])
    row = jnp.arange(256.0)
    assert ms.grouped(row).shape == (2, 128)
    assert float(ms.grouped(row)[1, 5]) == 133.0


@pytest.mark.parametrize("case", SHAPES, ids=_ids)
def test_the_chunk_scan_is_the_recurrence_one_position_at_a_time(highest,
                                                                 case):
    form, channels, n = case
    x, delta, a_log, b, c = _inputs(channels=channels, n=n)
    want, last = reference.recurrence(x, delta, -jnp.exp(a_log), b, c)
    y, state = _run(ms.mamba1_chunk_scan, x, delta, ms.rate_lanes(a_log), b,
                    c, _zero(2, channels, n), use_kernel=form == "kernel",
                    block=8)
    assert np.abs(np.asarray(y - want)).max() <= 1e-5
    assert np.abs(_written(state, channels, n) - np.asarray(last)).max() \
        <= 1e-5


@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_a_state_carried_across_two_chunks_is_one_call(highest, form):
    """Pieces of 16 positions holding 16 and 11 real ones (padding behind
    them, ``delta`` = 0 there): the second starts from the state the first
    left, and the pieces' real positions are the whole's."""
    x, delta, a_log, b, c = _inputs(t=27)
    a = ms.rate_lanes(a_log)
    want, last = reference.recurrence(x, delta, -jnp.exp(a_log), b, c)
    state, rows = _zero(2, 128, 16), []
    for at, real in ((0, 16), (16, 11)):
        def piece(v):
            v = v[:, at:at + real]
            return jnp.pad(v, ((0, 0), (0, 16 - real), (0, 0)),
                           constant_values=7.0)
        d = jnp.where(jnp.arange(16)[None, :, None] < real, piece(delta), 0.0)
        y, state = _run(ms.mamba1_chunk_scan, piece(x), d, a, piece(b),
                        piece(c), state, use_kernel=form == "kernel", block=8)
        rows.append(np.asarray(y)[:, :real])
    assert np.abs(np.concatenate(rows, 1) - np.asarray(want)).max() <= 1e-5
    assert np.abs(_written(state, 128, 16) - np.asarray(last)).max() <= 1e-5


def test_the_scan_kernel_is_the_xla_form(highest):
    """Three tiles of lane groups and four blocks of positions, a state
    handed in, against the ``lax.scan``; a width that is no whole block of
    positions takes the XLA form."""
    x, delta, a_log, b, c = _inputs(2, 64, 3 * 8 * 128, 8)
    a = ms.rate_lanes(a_log)
    state = jnp.asarray(np.random.default_rng(1).normal(
        size=(2, 24, 8, 128)), jnp.float32)
    want, last = ms.chunk_scan_xla(x, delta, a, b, c, state)
    got, carried = _run(ms.mamba1_chunk_scan, x, delta, a, b, c, state,
                        use_kernel=True, block=16)
    scale = float(np.abs(np.asarray(want)).max())
    assert np.abs(np.asarray(got - want)).max() <= 1e-5 * scale
    assert np.abs(np.asarray(carried - last)).max() <= 1e-5
    odd, _ = ms.mamba1_chunk_scan(x[:, :13], delta[:, :13], a, b[:, :13],
                                  c[:, :13], state, use_kernel=True)
    assert np.abs(np.asarray(odd - want[:, :13])).max() <= 1e-5 * scale


@pytest.mark.parametrize("case", SHAPES, ids=_ids)
def test_decode_steps_are_the_recurrence(highest, case):
    """The in-place update, a step a position, on a pool of several layers
    and slots: the rows' slots in any order, idle rows between them;
    ``fresh`` restarts a row. The kernel leaves the idle rows' row 0 and
    every other layer untouched, and an idle row's ``y`` is 0."""
    form, channels, n = case
    t = 6
    x, delta, a_log, b, c = _inputs(3, t, channels, n)
    a = ms.rate_lanes(a_log)
    want, last = reference.recurrence(x, delta, -jnp.exp(a_log), b, c)
    rng = np.random.default_rng(0)
    pool = jnp.asarray(rng.normal(size=(2, 6, *ms.pool_row_shape(channels,
                                                                 n))),
                       jnp.float32)
    slots = jnp.asarray([4, 0, 2, 0, 5], jnp.int32)     # rows 0, 2, 4 busy
    busy = np.asarray([0, 2, 4])

    def spread(v):
        return jnp.zeros((5, *v.shape[1:]), v.dtype).at[busy].set(v)

    before, rows = np.asarray(pool), []
    for k in range(t):
        fresh = jnp.full((5,), k == 0)
        y, pool = _run(ms.mamba1_state_update, pool, 1, slots,
                       spread(delta[:, k]), spread(x[:, k]), fresh, a,
                       spread(b[:, k]), spread(c[:, k]),
                       use_kernel=form == "kernel")
        rows.append(np.asarray(y)[busy])
    assert pool.shape == before.shape
    assert np.abs(np.stack(rows, 1) - np.asarray(want)).max() <= 1e-5
    after = np.asarray(pool)
    held = _written(after[1, [4, 2, 5]], channels, n)
    assert np.abs(held - np.asarray(last)).max() <= 1e-5
    assert (after[0] == before[0]).all()
    assert (after[1, [1, 3]] == before[1, [1, 3]]).all()
    if form == "kernel":
        assert (after[1, 0] == before[1, 0]).all()
        assert (np.asarray(y)[[1, 3]] == 0).all()


@pytest.mark.parametrize("form, order", [
    ("kernel", "chunk-then-steps"), ("kernel", "steps-then-chunk"),
    ("kernel", "chunk-steps-chunk"), ("xla", "chunk-steps-chunk")])
def test_the_two_writers_of_the_pool_agree_on_its_layout(highest, form,
                                                         order):
    """A state WRITTEN by a chunk and READ by decode steps on the pool in
    place, and the other way round: twenty-four positions through the pool
    equal the plain recurrence, every ``y`` and the state at the end."""
    t, channels, n = 24, 128, 16
    x, delta, a_log, b, c = _inputs(3, t, channels, n)
    a = ms.rate_lanes(a_log)
    want, last = reference.recurrence(x, delta, -jnp.exp(a_log), b, c)
    pool = jnp.asarray(np.random.default_rng(0).normal(
        size=(2, 6, 1, n, channels)), jnp.float32)
    slots = jnp.asarray([4, 2, 5], jnp.int32)
    pieces = {"chunk-then-steps": [("chunk", 16), ("steps", 8)],
              "steps-then-chunk": [("steps", 8), ("chunk", 16)],
              "chunk-steps-chunk": [("chunk", 8), ("steps", 8),
                                    ("chunk", 8)]}[order]
    kernel = form == "kernel"

    def chunk(pool, at, size):
        piece = lambda v: v[:, at:at + size]
        held = pool[1, slots]
        state = jnp.zeros_like(held) if at == 0 else held
        y, state = ms.mamba1_chunk_scan(piece(x), piece(delta), a, piece(b),
                                        piece(c), state, use_kernel=kernel,
                                        block=8)
        return y, pool.at[1, slots].set(state)

    def step(pool, k):
        y, pool = ms.mamba1_state_update(
            pool, 1, slots, delta[:, k], x[:, k], jnp.full((3,), k == 0), a,
            b[:, k], c[:, k], use_kernel=kernel)
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
    held = _written(np.asarray(pool)[1, np.asarray(slots)], channels, n)
    assert np.abs(held - np.asarray(last)).max() <= 1e-5


@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_a_fresh_row_forgets_its_slots_last_tenant(highest, form):
    """``fresh`` on a slot whose last tenant left LARGE values, NaN among
    them: the state after the step is ``(delta x) B^T`` alone, to the bit
    of a pool of zeros."""
    channels, n = 128, 16
    x, delta, a_log, b, c = _inputs(4, 1, channels, n)
    a = ms.rate_lanes(a_log)
    slots = jnp.asarray([0, 3, 0, 1], jnp.int32)
    pool = np.full((2, 5, 1, n, channels), 1e30, np.float32)
    pool[1, 3, 0, 2, 7] = np.nan
    args = (1, slots, delta[:, 0], x[:, 0], jnp.full((4,), True), a, b[:, 0],
            c[:, 0])
    y, after = _run(ms.mamba1_state_update, jnp.asarray(pool), *args,
                    use_kernel=form == "kernel")
    y0, zeros = _run(ms.mamba1_state_update, jnp.zeros_like(pool), *args,
                     use_kernel=form == "kernel")
    busy = np.asarray([1, 3])
    assert (np.asarray(y)[busy] == np.asarray(y0)[busy]).all()
    assert (np.asarray(after)[1, [3, 1]] == np.asarray(zeros)[1, [3, 1]]).all()
    state = (np.asarray(delta * x)[busy, 0][:, :, None]
             * np.asarray(b)[busy, 0][:, None, :])
    assert np.abs(_written(np.asarray(after)[1, [3, 1]], channels, n)
                  - state).max() <= 1e-6
