"""The two sparse-attention ops at a small size on the CPU
(``ops/dsa_index_select.py``, ``ops/dsa_sparse_attend.py``): the index
scores against the plain sum; the EXACT selection against a plain sort,
with ties, at ``live`` below, at and above ``k``; the chunk form's sets
equal to the step form's for the same queries; the order the scores' bits
keep; the packed mask there and back; the chunk form's Pallas kernel (the
interpreter here) against its XLA form, word for word; and the attention
over gathered rows against a plain softmax over the same rows."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import dsa_index_select as select_op
from deepspeed_tpu.ops import dsa_sparse_attend as attend_op


def plain_selection(scores, valid, k):
    """``[N, cap]`` bool by a plain stable sort: a query's ``min(k, valid)``
    keys of largest score, equal scores to the lower position."""
    out = np.zeros(scores.shape, bool)
    for n, (row, ok) in enumerate(zip(scores, valid)):
        at = np.flatnonzero(ok)
        order = at[np.argsort(-row[at].astype(np.float64), kind="stable")]
        out[n, order[:k]] = True
    return out


def causal_prefix(pos, live):
    """``could [N]``: the keys a query at ``pos`` may choose from are its
    first ``min(pos + 1, live)``."""
    return jnp.minimum(jnp.asarray(pos, jnp.int32) + 1, live)


def prefix_valid(could, cap):
    return np.arange(cap)[None] < np.asarray(could)[:, None]


def scores_with_ties(n, cap, seed, levels=0):
    rng = np.random.default_rng(seed)
    scores = rng.standard_normal((n, cap)).astype(np.float32)
    if levels:      # a few values only: ties everywhere, the k-th among them
        scores = np.round(scores * levels) / levels
    return scores


@pytest.mark.parametrize("levels", [0, 2, 1], ids=["distinct", "ties",
                                                   "mostly-ties"])
@pytest.mark.parametrize("first, k", [(0, 16), (8, 16), (16, 16), (40, 16),
                                      (100, 16), (60, 1), (0, 200)],
                         ids=["below", "crossing", "at", "above", "far-above",
                              "k-one", "k-over-all"])
def test_the_chunk_selection_is_a_plain_sorts(first, k, levels):
    n, cap = 24, 160
    scores = scores_with_ties(n, cap, first + k, levels)
    pos = first + jnp.arange(n, dtype=jnp.int32)
    live = first + n
    could = causal_prefix(pos, live)
    mask, chosen, _ = jax.jit(lambda s: select_op.select_mask(
        s, could, k, jnp.asarray(live)))(scores)
    got = np.asarray(select_op.unpack_bits(mask, cap))
    valid = prefix_valid(could, cap)
    want = plain_selection(scores, valid, k)
    assert (got == want).all()
    assert (np.asarray(chosen) == want.sum(-1)).all()
    assert (want.sum(-1) == np.minimum(valid.sum(-1), k)).all()


def test_the_selection_passes_stop_at_the_live_keys(monkeypatch):
    """In tiles of ``SELECT_TILE`` keys, as many as hold live keys: scores
    behind them are never read (NaN there changes nothing)."""
    monkeypatch.setattr(select_op, "SELECT_TILE", 64)
    n, cap, k, live = 8, 256, 16, 100
    scores = scores_with_ties(n, cap, 3, 2)
    scores[:, 128:] = np.nan
    pos = live - n + jnp.arange(n, dtype=jnp.int32)
    could = causal_prefix(pos, live)
    mask, _, _ = select_op.select_mask(jnp.asarray(scores), could, k,
                                       jnp.asarray(live))
    valid = prefix_valid(could, cap)
    assert (np.asarray(select_op.unpack_bits(mask, cap))
            == plain_selection(scores, valid, k)).all()


@pytest.mark.parametrize("levels", [0, 2])
@pytest.mark.parametrize("live", [5, 16, 90])
def test_the_step_form_chooses_the_chunk_forms_sets(live, levels):
    """One query a row at position ``live - 1``: ``lax.top_k``'s positions,
    and the mask laid from its k-th score, are the bisection's mask bit for
    bit."""
    n, cap, k = 6, 128, 16
    scores = scores_with_ties(n, cap, live, levels)
    lives = jnp.asarray([live, live, max(live - 3, 1), 1, live, cap])
    valid = jnp.arange(cap)[None] < lives[:, None]
    at, chosen, mask = select_op.select_positions(jnp.asarray(scores), valid,
                                                  k)
    step = np.asarray(select_op.unpack_bits(mask, cap))
    # the mask is the positions' own set
    laid = np.zeros((n, cap), bool)
    for row, places in enumerate(np.asarray(at)):
        laid[row, places[places >= 0]] = True
    assert (laid == step).all()
    for row in range(n):
        mask, count, _ = select_op.select_mask(
            jnp.asarray(scores[row:row + 1]), lives[row:row + 1], k,
            lives[row])
        assert (np.asarray(select_op.unpack_bits(mask, cap))[0]
                == step[row]).all()
        assert int(count[0]) == int(chosen[row]) == step[row].sum()
    want = plain_selection(scores, np.asarray(valid), k)
    assert (step == want).all()
    # by falling score, the lower position first among equals; -1 behind
    at = np.asarray(at)
    for row in range(n):
        real = at[row][at[row] >= 0]
        assert len(real) == min(k, int(lives[row]))
        keys = [(-scores[row, j], j) for j in real]
        assert keys == sorted(keys) and (at[row][len(real):] == -1).all()


# ---------------------------------------------------------------------------
# the chunk form's kernel (the interpreter here) against its XLA form
# ---------------------------------------------------------------------------
N_KERNEL, CAP_KERNEL = 64, 1024      # a tile of queries, four tiles of 256


def _signed_zeros(rng):
    return rng.choice(np.asarray([-0.0, 0.0, -1.0, -2.5, -1e-30, 0.5],
                                 np.float32), (N_KERNEL, CAP_KERNEL))


# name: (scores of a generator, the first query's position, live, k)
KERNEL_CASES = {
    "random": (lambda rng: rng.standard_normal(
        (N_KERNEL, CAP_KERNEL)).astype(np.float32), 936, 1000, 16),
    "all-equal": (lambda rng: np.full((N_KERNEL, CAP_KERNEL), 1.5,
                                      np.float32), 936, 1000, 16),
    "ties-at-the-kth": (lambda rng: scores_with_ties(
        N_KERNEL, CAP_KERNEL, 5, 2), 936, 1000, 16),
    "signed-zeros-and-negatives": (_signed_zeros, 936, 1000, 16),
    "fewer-than-k": (lambda rng: scores_with_ties(
        N_KERNEL, CAP_KERNEL, 6, 2), 0, 64, 100),
    "live-inside-a-tile-and-a-dead-tile": (lambda rng: scores_with_ties(
        N_KERNEL, CAP_KERNEL, 7, 4), 536, 600, 16),
    "prefix-ends-inside-a-register": (lambda rng: scores_with_ties(
        N_KERNEL, CAP_KERNEL, 8, 0), 70, 1000, 16),
    "k-over-all": (lambda rng: scores_with_ties(
        N_KERNEL, CAP_KERNEL, 9, 2), 200, 264, CAP_KERNEL),
}


@pytest.mark.parametrize("case", list(KERNEL_CASES))
def test_the_selection_kernel_is_the_xla_form_word_for_word(case,
                                                            monkeypatch):
    """``dsa_index_select`` settles the k-th key over a tile of queries'
    scores in VMEM and lays the set from there: the mask and ``chosen``
    are the XLA passes' to the bit, ties to the lower position, ``-0.0``
    as ``0.0``, no key past a query's prefix or the live keys (NaN there
    changes nothing)."""
    from deepspeed_tpu.ops import attention
    from deepspeed_tpu.utils.compat import tpu_interpret_mode

    make, first, live, k = KERNEL_CASES[case]
    monkeypatch.setattr(select_op, "SELECT_TILE", 256)
    scores = make(np.random.default_rng(11)).copy()
    dead = -(-live // 256) * 256
    scores[:, dead:] = np.nan
    could = causal_prefix(first + np.arange(N_KERNEL), live)
    pick = lambda s: select_op.select_mask(s, could, k, jnp.asarray(live))
    assert not select_op.kernel_serves(N_KERNEL, CAP_KERNEL)
    mask, chosen, none = jax.jit(pick)(scores)
    assert none is None
    before = attention.dispatch_counts().get("dsa_select_vmem_kernel", 0)
    monkeypatch.setattr(attention, "_FORCE_DECODE_KERNEL", True)
    assert select_op.kernel_serves(N_KERNEL, CAP_KERNEL)
    assert not select_op.kernel_serves(N_KERNEL - 8, CAP_KERNEL)
    with tpu_interpret_mode():
        # (a jit of its own: traced under the patch)
        mask_k, chosen_k, bias = jax.block_until_ready(
            jax.jit(lambda s: pick(s))(scores))
    assert attention.dispatch_counts()["dsa_select_vmem_kernel"] == before + 1
    assert mask_k.dtype == mask.dtype and mask_k.shape == mask.shape
    assert (np.asarray(mask_k) == np.asarray(mask)).all()
    assert (np.asarray(chosen_k) == np.asarray(chosen)).all()
    valid = prefix_valid(could, CAP_KERNEL)
    sets = np.asarray(select_op.unpack_bits(mask_k, CAP_KERNEL))
    assert (sets == plain_selection(np.nan_to_num(scores), valid, k)).all()
    # the same set as the attention kernel's bias, in the tiles laid
    assert bias.dtype == jnp.bfloat16 and bias.shape == scores.shape
    unchosen = np.asarray(jnp.asarray(select_op.UNCHOSEN, jnp.bfloat16),
                          np.float32)
    assert (np.asarray(bias, np.float32)[:, :dead]
            == np.where(sets[:, :dead], 0.0, unchosen)).all()


def test_a_chunk_of_the_mixer_takes_the_selection_kernel(monkeypatch):
    """Through ``SparseLatentAttention._chunk``: 128 queries over their
    own 128 keys take the kernel where it serves, the path is counted once
    a layer a chunk, and the chosen sets are the XLA form's."""
    from deepspeed_tpu.models.deepseek_v32 import (DeepseekV32Config,
                                                   SparseLatentAttention)
    from deepspeed_tpu.ops import attention
    from deepspeed_tpu.utils.compat import tpu_interpret_mode

    cfg = DeepseekV32Config.tiny(dtype=jnp.float32, param_dtype=jnp.float32)
    mixer = SparseLatentAttention(cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 128, cfg.hidden_size))
    params = jax.jit(lambda x: mixer.init(jax.random.PRNGKey(0), x))(x)
    apply = lambda p, x: mixer.apply(p, x)
    plain, _, seen = jax.jit(apply)(params, x)
    counts = attention.dispatch_counts()
    monkeypatch.setattr(attention, "_FORCE_DECODE_KERNEL", True)
    with tpu_interpret_mode():
        kernel, _, seen_k = jax.block_until_ready(
            jax.jit(lambda *a: apply(*a))(params, x))
    after = attention.dispatch_counts()
    assert after["dsa_select_vmem_kernel"] == counts.get(
        "dsa_select_vmem_kernel", 0) + 1
    assert after["dsa_select_passes_xla"] == counts["dsa_select_passes_xla"]
    for a, b in zip(seen, seen_k):
        assert (np.asarray(a) == np.asarray(b)).all()
    assert int(seen_k[2].max()) == cfg.index_topk
    assert np.abs(np.asarray(kernel - plain)).max() <= 1e-5


def test_the_scores_bits_keep_their_order():
    values = np.asarray([-np.inf, -3.5, -1e-30, -0.0, 0.0, 1e-30, 2.0,
                         np.inf], np.float32)
    bits = np.asarray(select_op.ordered_bits(jnp.asarray(values),
                                             jnp.ones(8, bool)))
    assert bits[3] == bits[4]                       # -0.0 is 0.0
    rest = np.delete(bits, 3)
    assert (np.diff(rest.astype(np.int64)) > 0).all() and rest.min() >= 1
    assert (np.asarray(select_op.ordered_bits(
        jnp.asarray(values), jnp.zeros(8, bool))) == 0).all()


def test_a_mask_packs_and_unpacks():
    rng = np.random.default_rng(0)
    for keys in (32, 96, 70):
        mask = rng.random((3, 5, keys)) < 0.4
        words = select_op.pack_bits(jnp.asarray(mask))
        assert words.shape == (3, 5, -(-keys // 32))
        assert words.dtype == jnp.uint32
        assert (np.asarray(select_op.unpack_bits(words, keys)) == mask).all()
        j = 37 % keys
        assert bool((np.asarray(words)[0, 0, j // 32] >> (j % 32)) & 1) == (
            mask[0, 0, j])
    tiles = attend_op.mask_tile(select_op.pack_bits(jnp.asarray(
        rng.random((2, 128)) < 0.5)), 1, 64)
    assert tiles.shape == (2, 64)


def test_the_index_scores_are_the_weighted_relu_sum():
    rng = np.random.default_rng(1)
    b, t, heads, width, tile, tiles = 2, 5, 3, 16, 8, 3
    q = rng.standard_normal((b, t, heads, width)).astype(np.float32)
    w = rng.standard_normal((b, t, heads)).astype(np.float32)
    keys = rng.standard_normal((b, tile * 4, width)).astype(np.float32)
    got = select_op.index_scores(
        jnp.asarray(q), jnp.asarray(w),
        lambda j: jax.lax.dynamic_slice_in_dim(jnp.asarray(keys), j * tile,
                                               tile, 1),
        jnp.asarray(tiles), tile, tile * 4)
    want = np.einsum("bth,bths->bts", w, np.maximum(
        np.einsum("bthd,bsd->bths", q, keys), 0.0))
    assert np.abs(np.asarray(got)[..., :tile * tiles]
                  - want[..., :tile * tiles]).max() < 1e-5
    # the tiles not taken read -inf: no one's choice
    assert np.isneginf(np.asarray(got)[..., tile * tiles:]).all()


def test_the_gathered_rows_attention_is_a_plain_softmax_over_them():
    rng = np.random.default_rng(2)
    layers, blocks, bs, lanes, rank, heads, k = 2, 9, 4, 256, 128, 3, 6
    pool = rng.standard_normal((layers, blocks, bs, lanes)).astype(np.float32)
    pool[:, 0] = np.nan                             # the garbage block
    table = np.asarray([[3, 1, 7], [2, 5, 0]], np.int32)
    positions = np.asarray([[0, 5, 9, 2, -1, -1], [4, 1, 0, 7, 6, 3]],
                           np.int32)
    rows = attend_op.pool_rows_of(jnp.asarray(positions), jnp.asarray(table),
                                  bs)
    assert np.asarray(rows)[0].tolist() == [12, 5, 29, 14, -1, -1]
    q = rng.standard_normal((2, heads, lanes)).astype(np.float32)
    got = attend_op.attend_chosen_rows(jnp.asarray(q), jnp.asarray(pool), 1,
                                       rows, rank=rank, scale=0.3)
    flat = pool[1].reshape(-1, lanes)
    for b in range(2):
        real = np.asarray(rows)[b][np.asarray(rows)[b] >= 0]
        s = q[b] @ flat[real].T * 0.3
        p = np.exp(s - s.max(-1, keepdims=True))
        want = (p / p.sum(-1, keepdims=True)) @ flat[real][:, :rank]
        assert np.abs(np.asarray(got)[b] - want).max() < 1e-5
    assert np.isfinite(np.asarray(got)).all()
