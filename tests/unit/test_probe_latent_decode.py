"""``tools/probe_latent_decode.py`` on the CPU: its arithmetic (the bytes'
time, a form's share of it), its inputs, and that the form until PR 60 (the
pipeline's block operands, kept in the tool) and the tree's (the kernel's
own copies) give the same output at every tile (tiny shapes, the Pallas
interpreter). No time printed here is a device's."""

import jax
import numpy as np
import pytest

from deepspeed_tpu.ops import latent_decode_attention as op
from deepspeed_tpu.utils.compat import tpu_interpret_mode
from tools import probe_latent_decode as probe

TINY = dict(heads=4, slots=5, busy=3, layers=2, per_row=10, blocks=31,
            mean=20, most=38, block_size=4, lanes=256, rank=128, rope=64)


@pytest.fixture
def small_parent_tile(monkeypatch):
    # 4 blocks of 4 keys: at 512 the interpreter would move 128 operands
    monkeypatch.setattr(probe, "PARENT_TILE_KEYS", 16)


def test_the_bytes_time_is_every_live_tokens_row_read_once():
    bytes_per_s = probe.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    least = probe.least_seconds(105 * 2500, 512, 64, bytes_per_s)
    assert least == pytest.approx(105 * 2500 * 1152 / 819e9)
    # 1.41 ns a live token a layer: what the parked reader's function
    # counts (``perfbench/kernels/mla_decode.py``)
    assert least / (105 * 2500) == pytest.approx(1.41e-9, rel=5e-3)
    for cell in probe.CELLS.values():
        assert cell["most"] + 1 <= cell["per_row"] * 32
        assert cell["blocks"] - 1 >= cell["busy"] * (cell["most"] // 32 + 1)


def test_busy_rows_sit_on_blocks_of_their_own_and_idle_ones_on_garbage():
    q, pool, tables, lengths = probe.inputs(7, **TINY)
    assert q.shape == (5, 1, 4, 256) and pool.shape == (2, 31, 4, 256)
    tables, lengths = np.asarray(tables), np.asarray(lengths)
    busy = tables[:, 0] != 0
    assert busy.sum() == 3 and (lengths[~busy] == 0).all()
    assert (tables[~busy] == 0).all()
    assert ((lengths[busy] >= 1) & (lengths[busy] <= 38)).all()
    named = tables[tables != 0]
    assert len(named) == len(set(named)) == (lengths[busy] // 4 + 1).sum()
    # ``[c | k_pe | zeros]``
    assert (np.asarray(pool, np.float32)[..., 192:] == 0).all()
    assert (np.asarray(q, np.float32)[..., 192:] == 0).all()


def test_the_forms_are_the_parents_and_the_trees_by_tile():
    forms = probe.forms((8, 16))
    assert list(forms) == ["parent", "tile-8", "tile-16"]
    assert forms["parent"] == (probe.parent_work, probe.parent_attend)
    held = op.LATENT_TILE_KEYS
    lengths, tables = np.asarray([5, 0]), np.asarray([[3, 4, 0, 0], [0] * 4])
    row_of, _ = forms["tile-8"][0](lengths, tables, 4, 256)
    assert row_of.shape == (2 * 2 + 1,)        # tiles of 2 blocks of 4
    assert op.LATENT_TILE_KEYS == held


def test_all_forms_give_the_parents_output_and_the_share_is_of_the_bound(
        small_parent_tile):
    with tpu_interpret_mode():
        rows = probe.probe(["tiny"], reps=1, sets=1, seed=5,
                           bytes_per_s=1e9, tiles=(8, 16, 32), sizes=TINY)
    assert [r["form"] for r in rows] == ["parent", "tile-8", "tile-16",
                                         "tile-32"]
    _, _, tables, lengths = probe.inputs(5, **TINY)
    busy = np.asarray(tables)[:, 0] != 0
    live = int((np.asarray(lengths)[busy] + 1).sum())
    for r in rows:
        assert r["live_tokens"] == live and r["gap"] <= probe.GAP
        least = probe.least_seconds(live, 128, 64, 1e9)
        assert r["share_of_bytes_time"] == pytest.approx(
            100 * least / (r["us_a_layer_call"] * 1e-6))
    # the same tile sums in the same order
    assert rows[2]["gap"] == 0.0


def test_a_form_that_parts_is_seen(small_parent_tile):
    """The gap is measured, not assumed: a form whose output is off by a
    tenth of the largest value reads so."""
    def off(*a, **kw):
        out = probe.parent_attend(*a, **kw)
        return out + 0.1 * abs(out).max()

    with tpu_interpret_mode():
        rows = probe.probe(["tiny"], reps=1, sets=1, seed=5,
                           bytes_per_s=1e9, tiles=(), sizes=TINY,
                           more_forms={"off": (probe.parent_work, off)})
    assert [r["form"] for r in rows] == ["parent", "off"]
    assert rows[0]["gap"] == 0.0
    assert rows[1]["gap"] == pytest.approx(0.1, rel=0.1)
    assert rows[1]["gap"] > probe.GAP


def test_a_program_is_a_call_a_layer_behind_one_work_list(
        small_parent_tile, monkeypatch):
    made = []
    real = probe.parent_work
    args = probe.inputs(1, **TINY)
    form = (lambda *a: made.append(1) or real(*a), probe.parent_attend)
    with tpu_interpret_mode():
        out = jax.block_until_ready(probe.program(form, 2, rank=128)(*args))
    assert made == [1] and out.shape == (2, 5, 1, 4, 128)
    out = np.asarray(out, np.float32)
    busy = np.asarray(args[2])[:, 0] != 0
    assert (out[:, ~busy] == 0).all() and (out[:, busy] != 0).any()
    assert (out[0] != out[1]).any()           # a layer's own rows
