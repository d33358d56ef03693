"""``tools/probe_ssm_state_update.py`` on the CPU: its arithmetic (the
bytes' time, a form's share of it), and that the layout until PR 51 and the
tree's, each on a pool that lies its own way, give the same ``y`` (tiny
shapes, the Pallas interpreter). No time printed here is a device's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import ssm_state_update as op
from deepspeed_tpu.utils.compat import tpu_interpret_mode
from tools import probe_ssm_state_update as probe

TINY = dict(slots=4, heads=8, width=32, n=32)


def test_the_bytes_time_is_every_busy_rows_state_read_and_written_once():
    cell = probe.CELL
    assert (cell["heads"] * cell["width"] * cell["n"] * 2) == 1_048_576
    least = probe.least_seconds(20, cell["heads"], cell["width"], cell["n"],
                                2, probe.peaks("TPU v5 lite")[
                                    "hbm_bytes_per_s"])
    assert least == pytest.approx(2 * 20 * 1_048_576 / 819e9)
    # what the parked reader's function counts for one step of one layer
    assert least == pytest.approx(51.2e-6, rel=1e-3)
    assert probe.least_seconds(40, 64, 64, 128, 2, 819e9) == 2 * least


@pytest.mark.parametrize("in_lanes", [False, True])
def test_a_forms_pool_lies_its_own_way(in_lanes):
    pool, _ = probe.inputs(3, 2, layers=1, dtype=jnp.float32, **TINY)
    lying = probe.as_form_lies(pool, in_lanes)
    assert lying.shape == pool.shape
    held = (op.from_lanes(op.lane_view(lying), TINY["heads"], TINY["width"])
            if in_lanes else lying)
    assert (np.asarray(held) == np.asarray(pool)).all()


def test_the_busy_rows_sit_on_slots_of_their_own():
    _, (rows, a, dx, b, c) = probe.inputs(7, 3, layers=1, **TINY)
    rows = np.asarray(rows)
    busy = rows[rows != 0]
    assert len(busy) == 3 == len(set(busy)) and busy.max() <= TINY["slots"]
    assert a.shape == (4, 8) and dx.shape == (4, 8, 32)
    assert float(a.min()) >= 0.5 and b.shape == c.shape == (4, 32)


def test_both_layouts_read_out_the_same_y_and_the_share_is_of_the_bound():
    with tpu_interpret_mode():
        rows = probe.probe([2, 3], layers=2, reps=1, sets=1, seed=5,
                           tiles=(1, 2), sizes=TINY, dtype=jnp.float32,
                           bytes_per_s=1e9)
    assert [r["form"] for r in rows] == ["parent", "lanes-1", "lanes-2"] * 2
    assert [r["busy"] for r in rows] == [2, 2, 2, 3, 3, 3]
    for r in rows:
        assert r["y_gap"] <= 1e-5
        least = probe.least_seconds(r["busy"], 8, 32, 32, 4, 1e9)
        assert r["share_of_bytes_time"] == pytest.approx(
            100 * least / (r["us_a_layer_call"] * 1e-6))


def test_a_program_is_a_call_a_layer_on_one_pool():
    pool, args = probe.inputs(1, 2, layers=3, dtype=jnp.float32, **TINY)
    before = np.asarray(pool)
    update, _ = probe.forms((2,))["parent"]
    with tpu_interpret_mode():
        total, after = jax.block_until_ready(probe.program(update, 3)(
            pool, *args))
    rows = np.asarray(args[0])
    after = np.asarray(after)
    # every layer's busy rows moved, no other row did
    for layer in range(3):
        assert (after[layer, rows[rows != 0]]
                != before[layer, rows[rows != 0]]).any()
        idle = np.setdiff1d(np.arange(5), rows[rows != 0])
        assert (after[layer, idle] == before[layer, idle]).all()
    assert total.shape == (4, 8, 32) and (np.asarray(total)[rows == 0]
                                          == 0).all()
