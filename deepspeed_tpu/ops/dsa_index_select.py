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
  ``c`` with ``count(key >= c) >= k``); then the keys above it are counted
  and the mask is laid, ties at the k-th score to the lower positions by a
  running count. Integer compares only: no score is rounded. A sort of
  512 x 30,000 scores (``lax.top_k``) moves some 8 GB on this chip. The
  33 counting passes and the laying come in TWO FORMS, which choose the
  same set to the bit, and :func:`kernel_serves` says which a call takes,
  by its shapes and the platform alone:

  - the Pallas kernel ``dsa_index_select`` (a TPU; a whole number of
    ``SELECT_QUERIES`` queries; key tiles of whole 128-lane registers; a
    tile of queries' keys inside 32 MB of VMEM): a grid step is 64 queries
    x a tile of 4,096 keys, and a tile of queries goes over its live tiles
    TWICE. First sweep: a tile's scores come into VMEM ONCE, are brought
    to the integers' order there and kept, queries on sublanes and keys on
    lanes (8 MB at 32,768 keys); behind the last tile the 32 settle passes
    and the counts above and at the k-th key run over VMEM, a running
    count a lane and one reduction over the lanes a pass. Validity is a
    prefix a query, so the kernel takes one int32 a query and no mask;
    Mosaic compares signed integers, so the keys carry their top bit
    flipped. Ties need no running count: where some query of the tile has
    more keys AT its k-th score than places left, a second bisection, on
    the POSITION, finds the place that cuts them (the largest with at most
    ``ties`` such keys before it); elsewhere it is skipped. Second sweep:
    a tile's part of the set is laid from the kept keys, as the mask's
    words (32 keys a word, packed on the matrix unit: two products with
    0 / 2^b matrices, sums under 2^16, exact in float32) and as the bias a
    masked attention reads (``UNCHOSEN`` for a key not chosen), so the
    attention need not unpack what was just packed. The scores are read
    from HBM once where the XLA form reads them 34 times: five layers'
    selections in one program take 2.6 / 3.3 / 4.5 ms at 4k / 16k / 30k
    live keys where the XLA form takes 4.4 / 12.9 / 24.1 (my chip run,
    PR 62);
  - XLA everywhere else (no TPU, 24 queries, the tests' sizes), and as
    the kernel's oracle in ``tests/unit/test_dsa_ops.py``: every pass
    re-reads the scores from HBM in tiles of ``SELECT_TILE`` keys and
    makes ``ordered_bits`` again, 2 GB a layer at 30k keys; ties at the
    k-th score by a running count.
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

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.utils.compat import tpu_compiler_params

# keys a tile of the selection's passes (a pass over [512, 4096] uint32 is
# 8 MB: some 10 us, far over a loop step's cost)
SELECT_TILE = 4096
# queries a grid step of the kernel that settles the k-th score
SELECT_QUERIES = 64
# the kernel's integer of a key that is none a query may choose
_NO_KEY = -2 ** 31
# the bias of a key a query did not choose, under a masked attention's
# softmax (0 for a chosen one)
UNCHOSEN = -1e30


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


def _tile_of(cap: int) -> int:
    """Keys a tile of the selection's passes over ``cap`` keys."""
    return SELECT_TILE if cap % SELECT_TILE == 0 else cap


def _tiles_of(cap: int, live_keys):
    """``(tile, tiles)`` of the selection's passes over ``cap`` keys of
    which the first ``live_keys`` (traced) may be chosen."""
    tile = _tile_of(cap)
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


def kernel_serves(queries: int, cap: int) -> bool:
    """Whether the chunk form takes the kernel ``dsa_index_select``: a TPU
    (or the interpreter forced), query tiles and key tiles of whole
    registers, a tile of queries' keys inside VMEM."""
    from deepspeed_tpu.ops.attention import use_decode_kernel

    return (use_decode_kernel() and queries % SELECT_QUERIES == 0
            and _tile_of(cap) % 128 == 0
            and SELECT_QUERIES * cap * 4 <= 32 * 1024 * 1024)


def _select_kernel(tiles_ref, could_ref, scores_ref, low_ref, high_ref,
                   mask_ref, bias_ref, chosen_ref, keys_scr, kth_scr, cut_scr,
                   *, k, tile, slab, cap):
    """A grid step: a tile of queries x a tile of keys, the keys' tiles
    TWICE over. First sweep: a tile's scores are brought to the integers'
    order as they arrive and kept (``keys_scr [queries, cap]``: queries on
    sublanes, keys on lanes); behind its last tile the 32 bits of the k-th
    key are settled over them, the keys above it and at it counted and,
    where a query has more keys AT its k-th score than places left, the
    position that cuts them, ``slab`` keys a loop step, as many as are
    live. Second sweep: a tile's part of the set is laid from the kept
    keys, as the attention's bias and, packed on the matrix unit, as the
    mask's words."""
    j = pl.program_id(1)
    sweep = pl.num_programs(1) // 2
    queries = scores_ref.shape[0]

    def positions(first, width):
        return first + jax.lax.broadcasted_iota(jnp.int32, (queries, width),
                                                1)

    def tile_at(t):
        return pl.ds(pl.multiple_of(t * tile, tile), tile)

    @pl.when(j < tiles_ref[0])
    def _():
        x = scores_ref[...]
        # ``ordered_bits`` with its top bit flipped: the same order under
        # a SIGNED compare, no key the lowest integer, a real score's
        # above it
        bits = jax.lax.bitcast_convert_type(jnp.where(x == 0.0, 0.0, x),
                                            jnp.int32)
        key = jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits)
        mine = positions(j * tile, tile) < could_ref[...]
        keys_scr[:, tile_at(j)] = jnp.where(
            mine, jnp.maximum(key, _NO_KEY + 1), _NO_KEY)

    @pl.when(j == sweep - 1)
    def _():
        steps = tiles_ref[0] * (tile // slab)

        def count(test):
            """``[queries, 1]``: a query's live keys that pass ``test(keys,
            the position of the first)``."""
            def some(r, acc):
                first = pl.multiple_of(r * slab, slab)
                passed = test(keys_scr[:, pl.ds(first, slab)],
                              first).astype(jnp.int32)
                return acc + sum(passed[:, c:c + 128]
                                 for c in range(0, slab, 128))

            # (a running sum a lane, ONE reduction over the lanes a pass)
            return jnp.sum(jax.lax.fori_loop(
                0, steps, some, jnp.zeros((queries, 128), jnp.int32)),
                           axis=1, keepdims=True)

        def wide(column):
            return jnp.broadcast_to(column, (queries, slab))

        def settle(i, found):
            trial = found | jnp.left_shift(jnp.int32(1), 31 - i)
            at = wide(trial ^ _NO_KEY)
            return jnp.where(count(lambda keys, _: keys >= at) >= k, trial,
                             found)

        # (``found`` holds the UNSIGNED key's bits, as the XLA form's; 0:
        # the query has fewer than k keys and takes them all)
        found = jax.lax.fori_loop(0, 32, settle,
                                  jnp.zeros((queries, 1), jnp.int32))
        kth_scr[...] = found ^ _NO_KEY
        kth = wide(kth_scr[...])
        above = count(lambda keys, _: keys > kth)
        level = jnp.where(found == 0, 0,
                          count(lambda keys, _: keys == kth))
        ties = k - above

        def first_ties():
            """The largest position ``c`` with at most ``ties`` of the keys
            AT the k-th score before it: they are the ones chosen."""
            def narrow(i, cut):
                trial = cut | jnp.left_shift(jnp.int32(1), bits - 1 - i)
                before = wide(trial)
                seen = count(lambda keys, first: (keys == kth) & (
                    positions(first, slab) < before))
                return jnp.where(seen <= ties, trial, cut)

            bits = cap.bit_length()
            return jax.lax.fori_loop(0, bits, narrow,
                                     jnp.zeros((queries, 1), jnp.int32))

        cut = jax.lax.cond(jnp.max(level - ties) > 0, first_ties,
                           lambda: jnp.full((queries, 1), cap, jnp.int32))
        cut_scr[...] = jnp.where(found == 0, 0, cut)
        chosen_ref[...] = above + jnp.minimum(level, ties)

    @pl.when(j >= sweep)
    def _():
        first = j - sweep

        @pl.when(first < tiles_ref[0])
        def _():
            keys, kth = keys_scr[:, tile_at(first)], kth_scr[...]
            mine = (keys > kth) | ((keys == kth) & (
                positions(first * tile, tile) < cut_scr[...]))
            bias_ref[...] = jnp.where(mine, 0.0, UNCHOSEN).astype(
                bias_ref.dtype)
            # 32 keys a word, two halves of 16 bits: sums of distinct
            # powers of two under 2^16, exact in the matrix unit's float32
            chosen = jnp.where(mine, 1.0, 0.0).astype(low_ref.dtype)
            low, high = (jnp.dot(chosen, ref[...],
                                 preferred_element_type=jnp.float32
                                 ).astype(jnp.int32)
                         for ref in (low_ref, high_ref))
            mask_ref[...] = low | jnp.left_shift(high, 16)

        @pl.when(first >= tiles_ref[0])
        def _():
            mask_ref[...] = jnp.zeros(mask_ref.shape, mask_ref.dtype)


@functools.partial(jax.jit, static_argnames=("k", "tile"))
def _select_in_vmem(scores, could, tiles, *, k, tile):
    """The kernel call behind :func:`select_mask`, a jitted function of its
    own: a program's layers are ONE trace and ONE lowering of the kernel
    (a process pays both at every start, which is ``setup_s``). -> ``(mask
    [N, cap / 32] uint32, chosen [N] int32, bias [N, cap] bfloat16)`` over
    the first ``tiles`` tiles of ``tile`` keys; the bias past them is not
    written."""
    n, cap = scores.shape
    words, queries = tile // 32, SELECT_QUERIES
    sweep = cap // tile
    # a key's bit in its word's low or high half, for the packing matmuls
    key = jnp.arange(tile, dtype=jnp.int32)[:, None]
    word = jnp.arange(words, dtype=jnp.int32)[None]
    bit = jnp.where(key // 32 == word, key % 32, -1)
    halves = [jnp.where((bit >= first) & (bit < first + 16),
                        jnp.left_shift(1, jnp.maximum(bit - first, 0)), 0
                        ).astype(jnp.bfloat16) for first in (0, 16)]

    def arriving(i, j, tiles_ref):
        return i, jnp.maximum(jnp.minimum(j, tiles_ref[0] - 1), 0)

    def laid(i, j, tiles_ref):
        return i, jnp.maximum(j - sweep, 0)

    a_query = pl.BlockSpec((queries, 1), lambda i, j, tiles_ref: (i, 0))
    a_half = pl.BlockSpec((tile, words), lambda i, j, tiles_ref: (0, 0))
    mask, bias, chosen = pl.pallas_call(
        functools.partial(_select_kernel, k=k, tile=tile,
                          slab=math.gcd(tile, 1024), cap=cap),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n // queries, 2 * sweep),
            in_specs=[a_query, pl.BlockSpec((queries, tile), arriving),
                      a_half, a_half],
            out_specs=[pl.BlockSpec((queries, words), laid),
                       pl.BlockSpec((queries, tile), laid), a_query],
            scratch_shapes=[
                pltpu.VMEM((queries, cap), jnp.int32),  # the kept keys
                pltpu.VMEM((queries, 1), jnp.int32),    # the k-th key
                pltpu.VMEM((queries, 1), jnp.int32),    # the ties' cut
            ]),
        out_shape=[jax.ShapeDtypeStruct((n, cap // 32), jnp.int32),
                   jax.ShapeDtypeStruct((n, cap), jnp.bfloat16),
                   jax.ShapeDtypeStruct((n, 1), jnp.int32)],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        name="dsa_index_select")(
            jnp.reshape(tiles, (1,)).astype(jnp.int32),
            could.astype(jnp.int32)[:, None], scores, *halves)
    return (jax.lax.bitcast_convert_type(mask, jnp.uint32), chosen[:, 0],
            bias)


def select_mask(scores, could, k: int, live_keys):
    """The CHUNK form. ``scores [N, cap]`` float32; ``could [N]`` int32:
    the keys query ``n`` may choose from are its first ``could[n]`` (causal
    and live: a prefix); ``live_keys``: keys past it are no one's (traced;
    the passes stop there).
    -> ``(mask [N, ceil(cap / 32)] uint32, chosen [N] int32, bias)``: query
    ``n``'s set, exactly the ``min(k, could[n])`` of largest score, equal
    scores to the lower position; and, from the kernel only (the XLA form:
    None), the same set as a masked attention's bias (``[N, cap]``
    bfloat16, 0 for a chosen key and ``UNCHOSEN`` for every other, in the
    tiles that hold live keys)."""
    from deepspeed_tpu.ops.attention import record_dispatch

    n, cap = scores.shape
    tile, tiles = _tiles_of(cap, live_keys)
    if kernel_serves(n, cap):
        record_dispatch("dsa_select_vmem_kernel")
        with jax.named_scope("dsa_index_select.select"):
            return _select_in_vmem(scores, could, tiles, k=k, tile=tile)
    record_dispatch("dsa_select_passes_xla")

    def keys_of(j):
        part = jax.lax.dynamic_slice_in_dim(scores, j * tile, tile, 1)
        k_pos = j * tile + jnp.arange(tile, dtype=jnp.int32)
        return ordered_bits(part, k_pos[None] < could[:, None])

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
    return mask, above + jnp.minimum(seen, jnp.maximum(ties, 0)), None


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
