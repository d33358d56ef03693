"""``tools/probe_kda_state_update.py`` on the CPU: its arithmetic (the bytes'
time, a form's share of it), and that the form until PR 58 (kept in the
tool) and the tree's give the same ``o`` and leave the same states (tiny
shapes, the Pallas interpreter). No time printed here is a device's."""

import jax
import numpy as np
import pytest

from deepspeed_tpu.ops import kda_state_update as op
from deepspeed_tpu.utils.compat import tpu_interpret_mode
from tools import probe_kda_state_update as probe

TINY = dict(slots=4, heads=4, width=128)


def test_the_bytes_time_is_every_busy_rows_state_read_and_written_once():
    cell = probe.CELL
    assert cell["heads"] * cell["width"] * cell["width"] * 4 == 2_097_152
    bytes_per_s = probe.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    least = probe.least_seconds(110, cell["heads"], cell["width"],
                                bytes_per_s)
    assert least == pytest.approx(2 * 110 * 2_097_152 / 819e9)
    # 5.12 us a busy row: what the parked reader's function counts for one
    # step of one layer
    assert least / 110 == pytest.approx(5.12e-6, rel=1e-3)
    assert probe.least_seconds(80, 32, 128, 819e9) == \
        2 * probe.least_seconds(40, 32, 128, 819e9)


def test_the_busy_rows_sit_on_slots_of_their_own():
    pool, (rows, alpha, k, v, q, beta) = probe.inputs(7, 3, layers=2, **TINY)
    assert pool.shape == (2, 5, 4, 128, 128) and pool.dtype == np.float32
    rows = np.asarray(rows)
    busy = rows[rows != 0]
    assert len(busy) == 3 == len(set(busy)) and busy.max() <= TINY["slots"]
    assert alpha.shape == k.shape == v.shape == q.shape == (4, 4, 128)
    assert beta.shape == (4, 4)
    assert np.exp(-5.0) <= float(alpha.min()) and float(alpha.max()) <= 1.0
    assert np.allclose(np.linalg.norm(np.asarray(k), axis=-1), 1.0,
                       atol=1e-5)
    assert np.allclose(np.linalg.norm(np.asarray(q), axis=-1), 128 ** -0.5,
                       atol=1e-6)
    assert 0.0 <= float(beta.min()) and float(beta.max()) <= 1.0


def test_the_forms_are_the_parents_and_the_trees_by_tile():
    forms = probe.forms((8, 32))
    assert list(forms) == ["parent", "tile-8", "tile-32"]
    assert forms["parent"] is probe.parent_update
    assert forms["tile-32"].func is op.state_update_kernel
    assert forms["tile-32"].keywords == {"head_tile": 32}


def test_both_forms_give_the_same_o_and_states_and_the_share_is_of_the_bound():
    with tpu_interpret_mode():
        rows = probe.probe([2, 3], layers=2, reps=1, sets=1, seed=5,
                           tiles=(2, 4), sizes=TINY, bytes_per_s=1e9)
    assert [r["form"] for r in rows] == ["parent", "tile-2", "tile-4"] * 2
    assert [r["busy"] for r in rows] == [2, 2, 2, 3, 3, 3]
    for r in rows:
        assert r["o_gap"] <= probe.GAP and r["state_gap"] <= probe.GAP
        least = probe.least_seconds(r["busy"], 4, 128, 1e9)
        assert r["share_of_bytes_time"] == pytest.approx(
            100 * least / (r["us_a_layer_call"] * 1e-6))
        assert r["us_a_busy_row"] == pytest.approx(
            r["us_a_layer_call"] / r["busy"])


def test_a_form_that_parts_is_seen():
    """The gaps are measured, not assumed: a form whose ``o`` is off by
    1e-3 and whose states by 1e-2 reads so."""
    def off(pool, layer, slot_rows, *terms, work=None):
        o, pool = probe.parent_update(pool, layer, slot_rows, *terms,
                                      work=work)
        return o + 1e-3, pool.at[layer, 1:].add(1e-2)

    with tpu_interpret_mode():
        rows = probe.probe([2], layers=1, reps=1, sets=1, seed=5, tiles=(),
                           sizes=TINY, bytes_per_s=1e9,
                           more_forms={"off": off})
    assert [r["form"] for r in rows] == ["parent", "off"]
    assert rows[0]["o_gap"] == rows[0]["state_gap"] == 0.0
    assert rows[1]["o_gap"] == pytest.approx(1e-3, rel=1e-2)
    assert rows[1]["state_gap"] == pytest.approx(1e-2, rel=1e-2)


def test_a_program_is_a_call_a_layer_on_one_pool():
    pool, args = probe.inputs(1, 2, layers=3, **TINY)
    before = np.asarray(pool)
    with tpu_interpret_mode():
        total, after = jax.block_until_ready(probe.program(
            probe.forms((4,))["tile-4"], 3)(pool, *args))
    rows = np.asarray(args[0])
    after = np.asarray(after)
    # every layer's busy rows moved, no other row did
    for layer in range(3):
        assert (after[layer, rows[rows != 0]]
                != before[layer, rows[rows != 0]]).any()
        idle = np.setdiff1d(np.arange(5), rows[rows != 0])
        assert (after[layer, idle] == before[layer, idle]).all()
    assert total.shape == (4, 4, 128) and (np.asarray(total)[rows == 0]
                                           == 0).all()
