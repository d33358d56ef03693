"""Shared decode/padding position math (`models/decode_utils.py`).

Coverage the serving PR owed: the sliding-window (``window > 0``) and
ragged per-row ``pad`` paths of ``cache_attn_mask``, its new per-row
vector-``idx`` form (paged serving slots), and the paged write-row
mapping at block boundaries.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.decode_utils import (cache_attn_mask,
                                               decode_positions, pad_lengths,
                                               paged_positions,
                                               paged_write_slots,
                                               row_positions,
                                               validate_left_padded_mask)


def _brute_mask(S, q_pos_row, pad_row=None, window=0):
    """Reference semantics, element by element."""
    out = np.zeros((len(q_pos_row), S), bool)
    for t, qp in enumerate(q_pos_row):
        for s in range(S):
            ok = s <= qp
            if window:
                ok = ok and s > qp - window
            if pad_row is not None:
                ok = ok and s >= pad_row
            out[t, s] = ok
    return out


class TestCacheAttnMask:
    def test_scalar_idx_causal(self):
        m = np.asarray(cache_attn_mask(8, 3, 2))
        assert m.shape == (1, 1, 2, 8)
        np.testing.assert_array_equal(m[0, 0], _brute_mask(8, [3, 4]))

    @pytest.mark.parametrize("window", [1, 2, 4])
    def test_window_bands_the_causal_mask(self, window):
        S, idx, T = 16, 9, 3
        m = np.asarray(cache_attn_mask(S, idx, T, window=window))[0, 0]
        np.testing.assert_array_equal(
            m, _brute_mask(S, [idx + t for t in range(T)], window=window))
        # the window admits exactly `window` keys once enough history
        assert m.sum(axis=1).max() == window

    def test_window_wider_than_history_is_causal(self):
        m = np.asarray(cache_attn_mask(8, 2, 1, window=100))[0, 0, 0]
        np.testing.assert_array_equal(m, _brute_mask(8, [2])[0])

    def test_ragged_pad_rows(self):
        """Per-row left-pad exclusion: row b must not see cache slots
        below pad[b], on top of the causal bound."""
        S, idx, T = 12, 6, 2
        pad = jnp.asarray([0, 3, 5], jnp.int32)
        m = np.asarray(cache_attn_mask(S, idx, T, pad=pad))
        assert m.shape == (3, 1, T, S)
        for b, p in enumerate([0, 3, 5]):
            np.testing.assert_array_equal(
                m[b, 0], _brute_mask(S, [idx, idx + 1], pad_row=p))

    def test_ragged_pad_plus_window(self):
        S, idx, T, window = 12, 7, 1, 3
        pad = jnp.asarray([0, 6], jnp.int32)
        m = np.asarray(cache_attn_mask(S, idx, T, pad=pad, window=window))
        for b, p in enumerate([0, 6]):
            np.testing.assert_array_equal(
                m[b, 0], _brute_mask(S, [idx], pad_row=p, window=window))

    def test_vector_idx_matches_stacked_scalar_calls(self):
        """The paged-serving form: idx as [B] per-row lengths must equal
        the scalar mask evaluated per row."""
        S, T = 16, 2
        lens = [0, 5, 7, 15 - T + 1]
        m = np.asarray(cache_attn_mask(S, jnp.asarray(lens, jnp.int32), T))
        assert m.shape == (len(lens), 1, T, S)
        for b, idx in enumerate(lens):
            ref = np.asarray(cache_attn_mask(S, idx, T))[0]
            np.testing.assert_array_equal(m[b], ref)

    def test_vector_idx_with_window_and_pad(self):
        S, T = 16, 1
        lens = jnp.asarray([4, 9], jnp.int32)
        pad = jnp.asarray([1, 2], jnp.int32)
        m = np.asarray(cache_attn_mask(S, lens, T, pad=pad, window=4))
        for b in range(2):
            np.testing.assert_array_equal(
                m[b, 0], _brute_mask(S, [int(lens[b])], pad_row=int(pad[b]),
                                     window=4))


class TestPositionHelpers:
    def test_row_and_decode_positions_ragged(self):
        mask = jnp.asarray([[1, 1, 1, 1], [0, 0, 1, 1]], jnp.int32)
        np.testing.assert_array_equal(
            np.asarray(row_positions(mask)), [[0, 1, 2, 3], [0, 0, 0, 1]])
        pads = pad_lengths(mask, 4)
        np.testing.assert_array_equal(np.asarray(pads), [0, 2])
        # decode step at absolute slot 4: row 0 is at position 4, row 1
        # (2 pads) at position 2
        np.testing.assert_array_equal(
            np.asarray(decode_positions(4, 1, pads)), [[4], [2]])

    def test_validate_mask_contract(self):
        ids = jnp.ones((2, 3), jnp.int32)
        with pytest.raises(ValueError):  # right padding
            validate_left_padded_mask(ids, jnp.asarray([[1, 1, 0], [1, 1, 1]]))
        with pytest.raises(ValueError):  # all-pad row
            validate_left_padded_mask(ids, jnp.asarray([[0, 0, 0], [1, 1, 1]]))
        assert validate_left_padded_mask(
            ids, jnp.asarray([[1, 1, 1], [1, 1, 1]])) is None  # fast path


def paged_write_rows(tables, pos, num_valid, bs):
    """Flattened pool rows of ``paged_write_slots``' (block, offset)."""
    blk, off = paged_write_slots(tables, pos, num_valid, bs)
    assert blk.shape == off.shape == pos.shape
    return blk * bs + off


class TestPagedHelpers:
    def test_paged_positions(self):
        lens = jnp.asarray([0, 5], jnp.int32)
        np.testing.assert_array_equal(
            np.asarray(paged_positions(lens, 3)), [[0, 1, 2], [5, 6, 7]])

    def test_write_rows_map_through_table(self):
        # block table: logical block 0 -> pool 4, block 1 -> pool 2
        tables = jnp.asarray([[4, 2]], jnp.int32)
        pos = paged_positions(jnp.asarray([6], jnp.int32), 3)  # 6, 7, 8
        rows = np.asarray(paged_write_rows(tables, pos,
                                           jnp.asarray([3], jnp.int32), 8))
        # 6,7 live in logical block 0 (pool 4: rows 38, 39); 8 crosses the
        # block boundary into logical block 1 (pool 2: row 16)
        np.testing.assert_array_equal(rows, [[4 * 8 + 6, 4 * 8 + 7,
                                              2 * 8 + 0]])

    def test_write_rows_exact_block_boundary(self):
        """A decode step whose position lands exactly on a block boundary
        must write row 0 of the NEXT table entry."""
        tables = jnp.asarray([[3, 7, 5]], jnp.int32)
        for length, expect in [(3, 3 * 4 + 3), (4, 7 * 4 + 0),
                               (7, 7 * 4 + 3), (8, 5 * 4 + 0)]:
            pos = paged_positions(jnp.asarray([length], jnp.int32), 1)
            rows = np.asarray(paged_write_rows(
                tables, pos, jnp.asarray([1], jnp.int32), 4))
            assert rows[0, 0] == expect, (length, rows)

    def test_pad_tail_routes_to_garbage_block(self):
        tables = jnp.asarray([[4, 2]], jnp.int32)
        pos = paged_positions(jnp.asarray([0], jnp.int32), 6)
        rows = np.asarray(paged_write_rows(tables, pos,
                                           jnp.asarray([4], jnp.int32), 8))
        # 4 real tokens through the table, 2 pads into block 0 rows
        np.testing.assert_array_equal(rows[0, :4], [32, 33, 34, 35])
        assert (rows[0, 4:] < 8).all()  # garbage block 0

    def test_idle_slot_all_garbage(self):
        tables = jnp.asarray([[0, 0]], jnp.int32)
        pos = paged_positions(jnp.asarray([0], jnp.int32), 1)
        rows = np.asarray(paged_write_rows(tables, pos,
                                           jnp.asarray([0], jnp.int32), 8))
        assert (rows < 8).all()
