"""The flash kernels' tile schedule: ``flash_plan`` (what a call fetches,
works on and skips) and the three kernels it drives, in interpreter mode on
the CPU against ``attention_reference``, at the shapes the schedule treats
differently: the training cells' and the serving prefill's, non-causal,
``seq_q != seq_k`` of either sign, sequences off the halving chain, below a
chunk, head size 128, and the strided layout through the same bodies."""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention import attention_reference
from deepspeed_tpu.ops.flash_attention import (
    flash_attention, flash_attention_bthd, flash_ineligible, flash_plan)
from deepspeed_tpu.utils.compat import tpu_interpret_mode


def _qkv(b, h, sq, sk, d, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(b, h, sq, d)), dtype),
            jnp.asarray(rng.normal(size=(b, h, sk, d)), dtype),
            jnp.asarray(rng.normal(size=(b, h, sk, d)), dtype))


def _check(flash, reference, qkv, grad, valid_from=0, tol=2e-3, gtol=5e-3):
    """``flash`` against ``reference`` on the query rows from
    ``valid_from`` on (the rows before see no key: the kernel gives 0 there,
    the XLA softmax a uniform row), forward and, with ``grad``, dq/dk/dv."""
    # (each side ONE program: op by op the reference alone compiles dozens)
    with tpu_interpret_mode():
        o = jax.block_until_ready(jax.jit(flash)(*qkv))
    o_ref = jax.jit(reference)(*qkv)
    np.testing.assert_allclose(np.asarray(o)[:, :, valid_from:],
                               np.asarray(o_ref)[:, :, valid_from:],
                               rtol=tol, atol=tol)
    if valid_from:
        np.testing.assert_allclose(np.asarray(o)[:, :, :valid_from], 0.0,
                                   atol=1e-6)
    if not grad:
        return

    def loss(f):
        return lambda *a: jnp.sum(
            f(*a)[:, :, valid_from:].astype(jnp.float32) ** 2)

    with tpu_interpret_mode():
        got = jax.block_until_ready(
            jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(*qkv))
    want = jax.jit(jax.grad(loss(reference), argnums=(0, 1, 2)))(*qkv)
    for name, a, b in zip("qkv", got, want):
        scale = float(jnp.max(jnp.abs(b))) + 1e-9
        np.testing.assert_allclose(
            np.asarray(a, np.float32) / scale, np.asarray(b, np.float32) / scale,
            rtol=0, atol=gtol, err_msg=f"d{name}")


# (batch, heads, seq_q, seq_k, head_dim, causal, grad, first row with a key)
CASES = {
    "medium_cell": (2, 16, 1024, 1024, 64, True, True, 0),
    "xl_row_grad": (1, 25, 1024, 1024, 64, True, True, 0),
    "prefill_16": (1, 25, 16, 16, 64, True, False, 0),
    "prefill_128": (1, 25, 128, 128, 64, True, False, 0),
    "prefill_512": (1, 25, 512, 512, 64, True, False, 0),
    "prefill_1024": (1, 25, 1024, 1024, 64, True, False, 0),
    "non_causal_two_chunks": (1, 1, 1024, 1024, 64, False, True, 0),
    "more_keys": (1, 2, 128, 384, 64, True, True, 0),
    "more_keys_off_the_tile": (1, 2, 256, 320, 64, True, True, 0),
    "fewer_keys": (1, 2, 256, 128, 64, True, True, 128),
    "fewer_keys_off_the_tile": (1, 2, 128, 64, 64, True, True, 64),
    "seq_384": (1, 3, 384, 384, 64, True, True, 0),
    "seq_768": (1, 2, 768, 768, 64, True, True, 0),
    "two_panels": (1, 1, 2048, 2048, 64, True, True, 0),
    "below_a_tile": (1, 2, 64, 64, 64, True, True, 0),
    "below_a_tile_odd": (1, 2, 40, 40, 64, True, True, 0),
    "head_dim_128": (1, 2, 256, 256, 128, True, True, 0),
    "head_dim_80": (1, 2, 256, 512, 80, True, True, 0),
}


@pytest.mark.parametrize("case", list(CASES))
def test_kernels_match_reference(case):
    b, h, sq, sk, d, causal, grad, valid_from = CASES[case]
    _check(lambda q, k, v: flash_attention(q, k, v, causal=causal),
           lambda q, k, v: attention_reference(q, k, v, causal=causal),
           _qkv(b, h, sq, sk, d), grad, valid_from)


def test_bf16_inputs_keep_float32_scores():
    """bf16 operands on the MXU, float32 scores and accumulators: as close
    to the float32 reference as bf16 inputs allow, with ``scale`` folded
    into q (1/8, exact in bf16)."""
    qkv = _qkv(1, 2, 512, 512, 64, jnp.bfloat16)
    _check(lambda q, k, v: flash_attention(q, k, v, causal=True),
           lambda q, k, v: attention_reference(
               q.astype(jnp.float32), k.astype(jnp.float32),
               v.astype(jnp.float32), causal=True),
           qkv, True, tol=2e-2, gtol=2e-2)


def test_scale_not_a_power_of_two_stays_on_the_scores():
    qkv = _qkv(1, 2, 256, 256, 64)
    _check(lambda q, k, v: flash_attention(q, k, v, causal=True,
                                           softmax_scale=0.3),
           lambda q, k, v: attention_reference(q, k, v, causal=True,
                                               softmax_scale=0.3),
           qkv, True)


@pytest.mark.parametrize("blocks", [(128, 128), (512, 256)])
def test_block_arguments_keep_their_meaning(blocks):
    """``block_q`` / ``block_k`` bound what a grid step fetches: several
    query blocks and key panels, the state crossing panels in scratch."""
    bq, bk = blocks
    plan = flash_plan((1, 2, 1024, 64), (1, 2, 1024, 64), True, jnp.float32,
                      bq, bk)
    assert (plan.bq, plan.bk) == (bq, bk)
    _check(lambda q, k, v: flash_attention(q, k, v, causal=True,
                                           block_q=bq, block_k=bk),
           lambda q, k, v: attention_reference(q, k, v, causal=True),
           _qkv(1, 2, 1024, 1024, 64), True)


@pytest.mark.parametrize("shape,causal", [
    ((1, 256, 2, 64), True), ((1, 1024, 2, 64), True),
    ((1, 384, 8, 64), True), ((1, 256, 2, 64), False)])
def test_strided_layout_runs_the_same_bodies(shape, causal):
    """[B, T, H, D]: the blocks are swapped once a grid step and the one
    set of kernel bodies indexes rows and chunks of the swapped copy."""
    b, t, h, d = shape
    q, k, v = _qkv(b, h, t, t, d)

    def strided(q, k, v):
        return flash_attention_bthd(
            q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2),
            causal=causal).swapaxes(1, 2)

    _check(strided,
           lambda q, k, v: attention_reference(q, k, v, causal=causal),
           (q, k, v), True)


class TestFlashPlan:
    def test_training_cells(self):
        medium = flash_plan((8, 16, 1024, 64), (8, 16, 1024, 64), True)
        xl = flash_plan((4, 25, 1024, 64), (4, 25, 1024, 64), True)
        for plan in (medium, xl):
            # the whole sequence is the panel, worked on in 512 x 512
            # chunks: of the four, two are crossed and one is skipped; dq
            # masks the crossed ones whole
            assert (plan.bq, plan.bk) == (1024, 1024)
            assert (plan.cq, plan.ck, plan.tile) == (512, 512, 128)
            assert plan.chunks == (1, 2, 1)
            assert plan.executed_share("flash_bwd_dq") == 0.75
            # the forward and dk/dv walk them by 128 x 128 tiles: 36 of 64
            # run, masked are the 8 the diagonal crosses and no other
            assert plan.tiles == (28, 1024 // plan.tile, 28)
            assert plan.executed_share("flash_fwd") <= 0.57
            assert plan.executed_share("flash_bwd_dkv") <= 0.57
            assert plan.g == 2
        assert "56.2%" in medium.describe() and "75.0%" in medium.describe()

    def test_serving_prefill_rows(self):
        for t in (16, 64, 128, 256, 512, 1024):
            shape = (1, 25, t, 64)
            assert flash_ineligible(shape, shape, "bhtd") is None
            plan = flash_plan(shape, shape, True)
            assert plan.g == 1                     # 25 rows: no even group
            assert plan.tiles[1] == max(t // 128, 1)
            assert plan.chunks[1] == max(t // 512, 1)

    def test_non_causal_runs_every_chunk_unmasked(self):
        plan = flash_plan((1, 2, 1024, 64), (1, 2, 1024, 64), False)
        assert plan.tile == 0
        assert plan.chunks == plan.tiles == (4, 0, 0)
        assert plan.executed_share() == 1.0

    def test_diagonal_off_the_corners_masks_whole_chunks(self):
        plan = flash_plan((1, 2, 256, 64), (1, 2, 320, 64), True)
        assert plan.tile == 0 and (plan.cq, plan.ck) == (256, 320)
        assert plan.chunks == plan.tiles == (0, 1, 0)

    def test_fully_masked_rows_are_skipped(self):
        plan = flash_plan((1, 2, 256, 64), (1, 2, 128, 64), True)
        assert (plan.cq, plan.tile) == (128, 128)
        assert plan.chunks == plan.tiles == (0, 1, 1)

    def test_chunks_off_the_halving_chain(self):
        assert flash_plan((1, 2, 768, 64), (1, 2, 768, 64)).cq == 384
        assert flash_plan((1, 2, 384, 64), (1, 2, 384, 64)).cq == 384
        assert flash_plan((1, 2, 1536, 64), (1, 2, 1536, 64)).bq == 768
        # 2048 at the default block: two panels of 1024, chunks of 512
        plan = flash_plan((1, 2, 2048, 64), (1, 2, 2048, 64))
        assert (plan.bq, plan.bk, plan.cq) == (1024, 1024, 512)
        assert plan.chunks == (6, 4, 6)
        assert plan.executed_share() == (16 * 17 // 2) / 256.0
        # below a tile the chunk is the sequence and dk/dv masks it whole
        assert flash_plan((1, 2, 40, 64), (1, 2, 40, 64)).tile == 0

    def test_ineligible_shapes_say_why(self):
        # no block of whole tiles divides 1100; 1100 itself is over a block
        reason = flash_ineligible((1, 2, 1100, 64), (1, 2, 1100, 64), "bhtd")
        assert reason is not None and "1100" in reason
        with pytest.raises(ValueError):
            flash_plan((1, 2, 1100, 64), (1, 2, 1100, 64))
        # 600 fits a block but is no whole number of tiles: one chunk of
        # 600 rows is over the largest chunk
        assert flash_ineligible((1, 2, 600, 64), (1, 2, 600, 64),
                                "bhtd") is not None
        assert flash_ineligible((1, 2, 768, 64), (1, 2, 768, 64),
                                "bhtd") is None

    def test_strided_layout_plans_from_the_same_rules(self):
        plan = flash_plan((2, 1024, 16, 64), (2, 1024, 16, 64), True,
                          layout="bthd")
        assert plan.tile == 128 and plan.executed_share() <= 0.57
        assert plan.g in (8, 16)


def test_dispatcher_logs_the_plan_once_a_shape():
    from deepspeed_tpu.ops import attention as dispatch
    from deepspeed_tpu.utils.logging import logger

    class Keep(logging.Handler):
        def __init__(self):
            super().__init__()
            self.lines = []

        def emit(self, record):
            self.lines.append(record.getMessage())

    keep = Keep()
    logger.addHandler(keep)
    try:
        shape = (3, 5, 1024, 64)
        for _ in range(2):
            dispatch._note_flash_plan(shape, shape, True, jnp.bfloat16)
    finally:
        logger.removeHandler(keep)
    lines = [ln for ln in keep.lines if "flash_attention q(3, 5, 1024, 64)" in ln]
    assert len(lines) == 1
    assert "1 unmasked + 2 masked 512x512 chunks run, 1 skipped" in lines[0]
    assert "28 unmasked + 8 masked 128x128 tiles run, 28 skipped" in lines[0]
