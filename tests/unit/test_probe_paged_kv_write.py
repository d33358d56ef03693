"""``tools/probe_paged_kv_write.py`` on the CPU: its inputs, its table's
arithmetic, and that the parent's form (two scatters, then the kernel), the
write call alone and the tree's (the kernel's call puts the rows in the
pools, by the write call or by the scatter) give one output and one pool
but for the garbage block, bf16 and int8 (tiny shapes, the Pallas
interpreter). No time printed here is a device's."""

import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.utils.compat import tpu_interpret_mode
from tools import probe_paged_kv_write as probe

TINY = dict(slots=4, heads=4, dim=32, blocks=33, block_size=8, row_blocks=3,
            table_blocks=4)


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_the_busy_rows_sit_on_blocks_of_their_own(quant):
    pools, (q, rows, tables, lengths) = probe.inputs(
        7, 3, quant, layers=2, **TINY)
    tables, lengths = np.asarray(tables), np.asarray(lengths)
    busy = lengths > 0
    assert busy.sum() == 3 and (tables[~busy] == 0).all()
    own = tables[busy][:, :3]
    assert (own > 0).all() and len(set(own.ravel())) == 9
    assert (tables[busy][:, 3:] == 0).all()
    # each some way into its last block
    assert ((lengths[busy] >= 16) & (lengths[busy] < 24)).all()
    assert len(pools) == len(rows) == (4 if quant else 2)
    assert all(p.shape[:3] == (2, 33, 8) for p in pools)
    assert [r.shape for r in rows[:2]] == [(4, 1, 128)] * 2
    assert all(r.dtype == p.dtype for r, p in zip(rows, pools))
    assert q.shape == (4, 1, 4, 32)


def test_more_rows_than_the_pool_holds_is_refused():
    with pytest.raises(ValueError, match="pool blocks"):
        probe.inputs(1, 4, False, layers=1, **{**TINY, "blocks": 12})


def test_every_writing_forms_output_and_pools_are_the_parents():
    # four slots, two pools: three writers are the write call's, four the
    # scatter's (``paged_most_writers``); int8's four pools never scatter
    def run(busy, quant, forms):
        with tpu_interpret_mode():
            return probe.probe([busy], layers=1, reps=1, sets=1, seed=5,
                               sizes=TINY, quants=(quant,), forms=forms)

    rows = (run(4, False, ("parent", "kernel", "change"))
            + run(3, False, ("parent", "change"))
            + run(4, True, ("parent", "change")))
    assert [(r["kv"], r["busy"], r["form"]) for r in rows] == [
        ("bf16", 4, "parent"), ("bf16", 4, "kernel"), ("bf16", 4, "change"),
        ("bf16", 3, "parent"), ("bf16", 3, "change"),
        ("int8", 4, "parent"), ("int8", 4, "change")]
    for r in rows:
        assert r["same_as_parent"] is (None if r["form"] == "parent"
                                       else True)
        assert r["us_a_layer_call"] > 0


def test_a_digest_tells_a_row_that_moved():
    pool = jnp.arange(2 * 3 * 4 * 8, dtype=jnp.float32).reshape(
        2, 3, 4, 8).astype(jnp.bfloat16)
    moved = pool.at[1, 2, jnp.asarray([0, 1])].set(pool[1, 2,
                                                        jnp.asarray([1, 0])])
    a, b = np.asarray(probe.digest(pool)), np.asarray(probe.digest(moved))
    assert a.shape == (2, 3) and a.dtype == np.uint32
    assert (a != b).sum() == 1 and a[1, 2] != b[1, 2]


def test_the_table_sets_the_saving_against_the_parents_step():
    rows = [{"kv": "bf16", "form": f, "busy": b, "us_a_layer_call": us,
             "same_as_parent": None}
            for b, times in ((3, (8.0, 19.0, 9.5, 10.0)),
                             (32, (100.0, 111.0, 115.0, 113.0)))
            for f, us in zip(probe.FORMS, times)]
    at3, at32 = probe.table(rows, layers=48)
    assert at3["saved_us"] == 9.0 and at3["parent_write_us"] == 11.0
    assert at3["change_write_us"] == 2.0 and at3["kernel_write_us"] == 1.5
    assert at32["kernel_write_us"] == 15.0
    assert at3["parent_step_ms"] == probe.CELL_STEP_MS
    assert at3["saved_share_of_step"] == pytest.approx(
        100 * 48 * 9e-3 / probe.CELL_STEP_MS)
    # the parent's step at 32 busy rows: the cell's plus 48 calls' more
    assert at32["parent_step_ms"] == pytest.approx(
        probe.CELL_STEP_MS + 48 * (111.0 - 19.0) / 1e3)
    assert at32["saved_share_of_step"] == pytest.approx(
        -100 * 48 * 2e-3 / at32["parent_step_ms"])


def test_main_refuses_to_time_anything_but_a_chip():
    with pytest.raises(SystemExit, match="no TPU"):
        probe.main([])
