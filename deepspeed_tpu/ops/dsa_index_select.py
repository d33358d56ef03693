"""Sparse attention's lightning indexer: the index scores of queries
against the index rows of their sequences, and the EXACT selection of the
``k`` best keys a query (DeepSeek-V3.2's ``index_topk`` 2,048).

    I[t, j] = sum_h w[t, h] * relu(q[t, h] . key[j])        j <= t, float32
    S_t     = the min(k, t + 1) positions j <= t of largest I[t, j],
              equal scores broken towards the lower j

One function in two forms:

- CHUNK (:func:`select_mask`): many queries a row (a prefill chunk, a whole
  sequence). The set comes out as a MASK, 32 keys a word (``[.., keys /
  32]`` uint32), which is what a masked attention reads and what the
  serving programs hand back; no position is ever sorted. The k-th score
  is found by BISECTION ON THE SCORES' BITS: a float32 is brought to an
  unsigned integer of the same order, and its 32 bits are settled from the
  top, each by one compare-and-count over the scores (the largest integer
  ``c`` with ``count(key >= c) >= k``). Ties at the k-th score are given to
  the lower positions by a running count. A sort of 512 x 30,000 scores
  (``lax.top_k``) moves some 8 GB on this chip, the 33 passes 2 GB, and in
  tiles of keys as many as the longest row has.
- DECODE (:func:`select_positions`): one query a row. ``lax.top_k`` (exact:
  a sort; ties to the lower index by its contract) over ``[rows, keys]``
  gives the POSITIONS, which the step's attention gathers its rows by
  (``ops/dsa_sparse_attend.py``), and its k-th score the same set as the
  chunk form's mask.

``lax.approx_max_k`` is another function and is not used.

The scores (:func:`index_scores`) are taken a tile of keys at a time and
the heads summed before the next tile: ``[queries, heads, keys]`` is never
in memory (512 x 64 x 32,768 float32 would be 4.3 GB). XLA, both forms: a
Pallas kernel that keeps a tile's ``[heads x queries, keys]`` products in
VMEM was written and read the SAME times on the chip (0.90 / 1.48 / 2.23 ms
a layer at 4k / 16k / 30k live keys against XLA's 0.88 / 1.45 / 2.28: the
compiler fuses the ReLU, the weights and the sum over the heads into the
product's own loop, 117 TFLOP/s at 30k), so it was taken out again (my
chip run, PR 59).
"""

import jax
import jax.numpy as jnp

# keys a tile of the selection's passes (a pass over [512, 4096] uint32 is
# 8 MB: some 10 us, far over a loop step's cost)
SELECT_TILE = 4096


# ---------------------------------------------------------------------------
# scores

def index_scores(q, w, keys_of, tiles, tile: int, cap: int):
    """``I [B, T, cap]`` float32. ``q [B, T, heads, width]``, ``w [B, T,
    heads]`` float32, ``keys_of(j) -> [B, tile, width]``: the index rows at
    positions ``[j * tile, (j + 1) * tile)``, for ``j < tiles`` (may be
    traced). Positions of tiles not taken read ``-inf``; which of the rest
    a query may choose from is the selection's ``valid``."""
    b, t = q.shape[:2]

    def one_tile(j, out):
        keys = keys_of(j)
        s = jnp.einsum("bthd,bsd->bths", q, keys,
                       preferred_element_type=jnp.float32)
        s = jnp.sum(jnp.maximum(s, 0.0) * w[..., None], axis=2)
        return jax.lax.dynamic_update_slice_in_dim(out, s, j * tile, 2)

    with jax.named_scope("dsa_index_select.scores"):
        return jax.lax.fori_loop(
            0, tiles, one_tile, jnp.full((b, t, cap), -jnp.inf, jnp.float32))


# ---------------------------------------------------------------------------
# the exact selection

def ordered_bits(scores, valid):
    """Float32 scores as unsigned integers of the same order, 0 where a
    key is none a query may choose (a real score's is at least 1)."""
    # (-0.0 is 0.0, and the compiler folds ``x + 0.0`` away)
    bits = jax.lax.bitcast_convert_type(
        jnp.where(scores == 0.0, 0.0, scores), jnp.uint32)
    flipped = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))
    return jnp.where(valid, jnp.maximum(flipped, jnp.uint32(1)),
                     jnp.uint32(0))


def _tiles_of(cap: int, live_keys):
    """``(tile, tiles)`` of the selection's passes over ``cap`` keys of
    which the first ``live_keys`` (traced) may be chosen."""
    tile = SELECT_TILE if cap % SELECT_TILE == 0 else cap
    return tile, jnp.minimum((live_keys + tile - 1) // tile, cap // tile)


def pack_bits(mask):
    """``[.., keys]`` bool -> ``[.., keys / 32]`` uint32, key ``j`` bit ``j
    % 32`` of word ``j // 32``."""
    *lead, n = mask.shape
    pad = -n % 32
    if pad:
        mask = jnp.pad(mask, [(0, 0)] * len(lead) + [(0, pad)])
    words = mask.reshape(*lead, -1, 32).astype(jnp.uint32)
    return jnp.sum(words << jnp.arange(32, dtype=jnp.uint32), axis=-1,
                   dtype=jnp.uint32)


def unpack_bits(words, keys=None):
    """:func:`pack_bits` back: ``[.., words]`` uint32 -> ``[.., words * 32]``
    bool (cut to ``keys``)."""
    bits = (words[..., None] >> jnp.arange(32, dtype=jnp.uint32)) & 1
    out = bits.reshape(*words.shape[:-1], -1).astype(bool)
    return out if keys is None else out[..., :keys]


def select_mask(scores, valid_of, k: int, live_keys):
    """The CHUNK form. ``scores [N, cap]`` float32; ``valid_of(first,
    count) -> [N, count]`` bool, the keys at positions ``[first, first +
    count)`` a query may choose from (causal, live); ``live_keys``: keys
    past it are no one's (traced; the passes stop there).
    -> ``(mask [N, ceil(cap / 32)] uint32, chosen [N] int32)``: query
    ``n``'s set, exactly the ``min(k, its valid keys)`` of largest score,
    equal scores to the lower position."""
    n, cap = scores.shape
    tile, tiles = _tiles_of(cap, live_keys)

    def keys_of(j):
        part = jax.lax.dynamic_slice_in_dim(scores, j * tile, tile, 1)
        return ordered_bits(part, valid_of(j * tile, tile))

    def count(test):
        """``[N]``: how many of a query's keys pass ``test(keys tile)``."""
        return jax.lax.fori_loop(
            0, tiles, lambda j, acc: acc + jnp.sum(
                test(keys_of(j)), axis=1, dtype=jnp.int32),
            jnp.zeros((n,), jnp.int32))

    def settle(i, found):
        trial = found | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = count(lambda keys: keys >= trial[:, None]) >= k
        return jnp.where(enough, trial, found)

    with jax.named_scope("dsa_index_select.select"):
        # the k-th largest key, or 0 where a query has fewer than k
        kth = jax.lax.fori_loop(0, 32, settle, jnp.zeros((n,), jnp.uint32))
        above = count(lambda keys: keys > kth[:, None])
        # of the keys AT the k-th score, the first ``ties`` by position
        ties = k - above
        words = tile // 32 if tile % 32 == 0 else -(-cap // 32)

        def lay(j, carry):
            out, seen = carry
            keys = keys_of(j)
            at = (keys == kth[:, None]) & (keys > 0)
            rank = seen[:, None] + jnp.cumsum(at, axis=1, dtype=jnp.int32) - at
            mine = (keys > kth[:, None]) | (at & (rank < ties[:, None]))
            return (jax.lax.dynamic_update_slice_in_dim(
                out, pack_bits(mine), j * words, 1),
                    seen + jnp.sum(at, axis=1, dtype=jnp.int32))

        mask, seen = jax.lax.fori_loop(
            0, tiles, lay, (jnp.zeros((n, -(-cap // 32)), jnp.uint32),
                            jnp.zeros((n,), jnp.int32)))
    return mask, above + jnp.minimum(seen, jnp.maximum(ties, 0))


def select_positions(scores, valid, k: int):
    """The DECODE form. ``scores [N, cap]``, ``valid [N, cap]`` ->
    ``(positions [N, k] int32, chosen [N] int32, mask [N, ceil(cap / 32)]
    uint32)``: the chosen keys by falling score (equal scores: the lower
    position first), -1 behind the ``chosen = min(k, valid keys)`` there
    are; and the same set as the chunk form's mask, from the k-th score
    (a compare a key; a scatter of the positions costs 0.8 ms a layer on the
    chip), ties at it to the lower positions as ``lax.top_k`` gives them."""
    with jax.named_scope("dsa_index_select.select"):
        n, cap = scores.shape
        width = min(k, cap)
        # (-0.0 is 0.0 here as in ``ordered_bits``)
        scores = jnp.where(valid, jnp.where(scores == 0.0, 0.0, scores),
                           -jnp.inf)
        best, at = jax.lax.top_k(scores, width)
        chosen = jnp.minimum(jnp.sum(valid, axis=1, dtype=jnp.int32), k)
        at = jnp.where(jnp.arange(width)[None] < chosen[:, None], at, -1)
        if width < k:
            at = jnp.pad(at, ((0, 0), (0, k - width)), constant_values=-1)
        kth = best[:, -1:] if width == k else jnp.full((n, 1), -jnp.inf)
        above = valid & (scores > kth)
        level = valid & (scores == kth)
        ties = k - jnp.sum(above, axis=1, keepdims=True, dtype=jnp.int32)
        # the running count only where some row has more keys AT the k-th
        # score than places left
        level = jax.lax.cond(
            jnp.any(jnp.sum(level, axis=1, keepdims=True) > ties),
            lambda: level & (jnp.cumsum(level, axis=1, dtype=jnp.int32)
                             - level < ties), lambda: level)
        return at.astype(jnp.int32), chosen, pack_bits(above | level)
