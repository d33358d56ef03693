"""Pallas decode-attention kernel tests (interpret mode on CPU).

Parity vs the dense masked path the model used before (reference capability:
``softmax_context``, ``csrc/transformer/inference/csrc/softmax.cu:488``).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from deepspeed_tpu.utils.compat import tpu_interpret_mode

from deepspeed_tpu.ops.attention import attention_reference
from deepspeed_tpu.ops.decode_attention import decode_attention


def _dense_decode(q4, k_cache, v_cache, idx):
    """The model's previous dense path: transpose cache + masked attention."""
    B, tq, H, D = q4.shape
    S = k_cache.shape[1]
    q = q4.transpose(0, 2, 1, 3)
    kc = k_cache.transpose(0, 2, 1, 3)
    vc = v_cache.transpose(0, 2, 1, 3)
    key_pos = jnp.arange(S)
    q_pos = idx + jnp.arange(tq)
    mask = key_pos[None, :] <= q_pos[:, None]
    y = attention_reference(q, kc, vc, mask=mask[None, None], causal=False)
    return y.transpose(0, 2, 1, 3)


@pytest.mark.parametrize("idx,tq", [(0, 1), (7, 1), (255, 1), (256, 1),
                                    (300, 4), (508, 4)])
def test_matches_dense(idx, tq):
    B, H, D, S = 2, 4, 64, 512
    rng = np.random.default_rng(idx + tq)
    k_cache = np.zeros((B, S, H, D), np.float32)
    v_cache = np.zeros((B, S, H, D), np.float32)
    # valid prefix [0, idx) plus this step's keys at [idx, idx+tq)
    k_cache[:, :idx + tq] = rng.normal(size=(B, idx + tq, H, D))
    v_cache[:, :idx + tq] = rng.normal(size=(B, idx + tq, H, D))
    q4 = jnp.asarray(rng.normal(size=(B, tq, H, D)), jnp.float32)
    k_cache = jnp.asarray(k_cache)
    v_cache = jnp.asarray(v_cache)

    with tpu_interpret_mode():
        out = decode_attention(q4, k_cache, v_cache, idx)
    ref = _dense_decode(q4, k_cache, v_cache, idx)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_garbage_tail_ignored():
    # rows past the valid prefix contain garbage — must not affect output
    B, H, D, S, idx = 1, 2, 64, 256, 10
    rng = np.random.default_rng(0)
    k_cache = rng.normal(size=(B, S, H, D)).astype(np.float32) * 100
    v_cache = rng.normal(size=(B, S, H, D)).astype(np.float32) * 100
    q4 = jnp.asarray(rng.normal(size=(B, 1, H, D)), jnp.float32)
    with tpu_interpret_mode():
        out1 = decode_attention(q4, jnp.asarray(k_cache), jnp.asarray(v_cache), idx)
    k2, v2 = k_cache.copy(), v_cache.copy()
    k2[:, idx + 1:] = 9999.0
    v2[:, idx + 1:] = -9999.0
    with tpu_interpret_mode():
        out2 = decode_attention(q4, jnp.asarray(k2), jnp.asarray(v2), idx)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2))


@pytest.mark.parametrize("idx", [63, 64, 65, 128, 192])
def test_dense_kernel_at_block_boundaries(idx):
    """cache_index values that land exactly on (or straddle) kernel block
    boundaries — the skip/boundary-mask edge the paged gather inherits."""
    B, H, D, S, bk = 1, 2, 64, 256, 64
    rng = np.random.default_rng(idx)
    k_cache = np.zeros((B, S, H, D), np.float32)
    v_cache = np.zeros((B, S, H, D), np.float32)
    k_cache[:, :idx + 1] = rng.normal(size=(B, idx + 1, H, D))
    v_cache[:, :idx + 1] = rng.normal(size=(B, idx + 1, H, D))
    q4 = jnp.asarray(rng.normal(size=(B, 1, H, D)), jnp.float32)
    with tpu_interpret_mode():
        out = decode_attention(q4, jnp.asarray(k_cache), jnp.asarray(v_cache),
                               idx, block_k=bk)
    ref = _dense_decode(q4, jnp.asarray(k_cache), jnp.asarray(v_cache), idx)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# paged (block-table) variant
# ---------------------------------------------------------------------------
LAYERS = 3  # every pool here is stacked: [LAYERS, nb, bs, H*D]
# a length of _paged_setup: the serving engine's idle slot (length 0, the
# table all garbage block)
IDLE = None


def _paged_setup(B, lengths, tq, bs, mb, H=2, D=64, seed=0, layer=1,
                 dtype=np.float32):
    """Random STACKED pool ``[LAYERS, nb, bs, H*D]`` (every layer holds
    different numbers, so reading the wrong one cannot pass) + per-row
    permuted block tables holding each row's prefix at its logical
    positions (the serving layout); a row of length ``IDLE`` owns no block.
    Returns the positional arguments of ``decode_attention_paged``,
    ``layer`` last."""
    from deepspeed_tpu.ops.decode_attention import GARBAGE_BLOCK

    rng = np.random.default_rng(seed)
    nb = 1 + B * mb
    k_pool = rng.normal(size=(LAYERS, nb, bs, H * D)).astype(np.float32)
    v_pool = rng.normal(size=(LAYERS, nb, bs, H * D)).astype(np.float32)
    tables = np.full((B, mb), GARBAGE_BLOCK, np.int32)
    free = list(rng.permutation(np.arange(1, nb)))
    for b, ln in enumerate(lengths):
        if ln is IDLE:
            continue
        need = max(1, -(-(ln + tq) // bs))
        tables[b, :need] = [free.pop() for _ in range(need)]
    q4 = rng.normal(size=(B, tq, H, D)).astype(np.float32)
    return (jnp.asarray(q4, dtype), jnp.asarray(k_pool, dtype),
            jnp.asarray(v_pool, dtype), jnp.asarray(tables),
            jnp.asarray([0 if ln is IDLE else ln for ln in lengths],
                        jnp.int32), layer)


def _live(lengths):
    """Rows of a ``_paged_setup`` batch that hold a sequence: an idle
    slot's output is whatever the kernel leaves there, and is discarded."""
    return [b for b, ln in enumerate(lengths) if ln is not IDLE]


def _mixed(tq, bs=32, mb=4):
    """One batch of every length of live prefix: an idle slot (no block),
    exactly one block, ``len + tq`` exactly on a block boundary, and all
    ``mb`` blocks of the table."""
    return [IDLE, bs - tq - 3, 2 * bs - tq, mb * bs - tq]


def _dense_ref(q4, kd, vd, tables, lengths, bs):
    """Attention of ``q4`` over dense ``[B, S, H, D]`` windows, masked with
    per-row lengths (decode_utils vector-idx form), in float32."""
    from deepspeed_tpu.models.decode_utils import cache_attn_mask

    mask = cache_attn_mask(tables.shape[-1] * bs, lengths, q4.shape[1])
    f32 = lambda a: jnp.asarray(a, jnp.float32).transpose(0, 2, 1, 3)  # noqa: E731
    return attention_reference(f32(q4), f32(kd), f32(vd), mask=mask,
                               causal=False).transpose(0, 2, 1, 3)


@jax.jit
def _paged_dense_ref(q4, k_pool, v_pool, tables, lengths, layer):
    """Oracle: gather one layer of the pool into the dense logical
    window (ONE program a shape, the layer traced: op by op it compiles
    some dozens a case)."""
    from deepspeed_tpu.ops.decode_attention import gather_paged_cache

    H = q4.shape[2]
    return _dense_ref(q4, gather_paged_cache(k_pool, tables, layer, H),
                      gather_paged_cache(v_pool, tables, layer, H),
                      tables, lengths, k_pool.shape[2])


@jax.jit
def _int8_dense_ref(q4, kq, vq, ks, vs, tables, lengths, layer):
    from deepspeed_tpu.ops.decode_attention import gather_paged_cache_int8

    H = q4.shape[2]
    return _dense_ref(q4, gather_paged_cache_int8(kq, ks, tables, layer, H),
                      gather_paged_cache_int8(vq, vs, tables, layer, H),
                      tables, lengths, kq.shape[2])


@pytest.mark.parametrize("lengths,tq", [
    ([0, 5], 1), ([7, 63], 1), ([64, 1], 1),       # boundary straddles
    ([32, 16], 1),                                  # exactly on boundaries
    ([0, 12], 4), ([60, 30], 4),                    # multi-query steps
    ([0, 31, 64], 5),                               # verify shapes (k+1
    ([3, 17, 40], 8),                               # rows, mixed depths)
    (_mixed(1), 1), (_mixed(4), 4),                 # idle .. the whole table
    ([IDLE, 70, IDLE, IDLE, 5, IDLE], 1),           # live among idle slots
])
def test_paged_matches_dense_gather(lengths, tq):
    from deepspeed_tpu.ops.decode_attention import decode_attention_paged

    args = _paged_setup(len(lengths), lengths, tq, bs=32, mb=4,
                        seed=sum(filter(None, lengths)) + tq)
    with tpu_interpret_mode():
        out = decode_attention_paged(*args)
    ref = _paged_dense_ref(*args)
    live = _live(lengths)
    np.testing.assert_allclose(np.asarray(out)[live], np.asarray(ref)[live],
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", ["bf16", "f32"])
def test_verify_rows_equal_sequential_single_row_calls(dtype):
    """The accept-oracle property at kernel level: row r of one
    multi-query verify call computes the SAME attention, to the bit, that a
    plain decode call would at length + r — the prefix each draft token
    would have seen decoded sequentially. This is what makes greedy k-token
    verify an exact oracle rather than an approximation. Tiles are
    absolute, so it holds where the rows straddle a tile's boundary too
    (126 + 4 rows: two see one tile, two see two; the ``tq = 1`` calls at
    126 and 127 have one step, those at 128 and 129 two). In bf16, what
    serving stores, the ``tq = 1`` call itself; in float32 the CPU's matmul
    of four rows is not its matmul of one row to the last bit (nor was it
    under the parent's kernel), so there the single row goes through a
    four-row call, at row 0."""
    from deepspeed_tpu.ops.decode_attention import decode_attention_paged

    tq = 4
    args = _paged_setup(3, [5, 37, 126], tq, bs=32, mb=8, seed=1,
                        dtype=jnp.bfloat16 if dtype == "bf16"
                        else np.float32)
    q4, k_pool, v_pool, tables, lens, layer = args
    with tpu_interpret_mode():
        multi = np.asarray(decode_attention_paged(*args), np.float32)
    for r in range(tq):
        rows = q4[:, r:r + 1] if dtype == "bf16" else jnp.roll(q4, -r, 1)
        with tpu_interpret_mode():
            single = decode_attention_paged(rows, k_pool, v_pool, tables,
                                            lens + r, layer)
        np.testing.assert_array_equal(
            multi[:, r], np.asarray(single, np.float32)[:, 0])


def test_verify_rejected_tail_rows_isolated():
    """The no-copy drop's kernel-level guarantee: row r reads only keys
    at positions <= lengths[b] + r, so scribbling the pool rows that
    held a REJECTED speculative tail (positions past the accepted
    prefix) leaves every accepted row's output bit-identical — dropping
    the tail needs no copy, no zeroing, nothing."""
    from deepspeed_tpu.ops.decode_attention import decode_attention_paged

    bs, tq, length, accepted = 8, 4, 10, 1
    q4, k_pool, v_pool, tables, lens, layer = _paged_setup(
        1, [length], tq, bs=bs, mb=4, seed=3)
    with tpu_interpret_mode():
        out1 = np.asarray(decode_attention_paged(q4, k_pool, v_pool,
                                                 tables, lens, layer))
    kp = np.asarray(k_pool).copy()
    vp = np.asarray(v_pool).copy()
    table = np.asarray(tables)[0]
    for pos in range(length + accepted + 1, length + tq):
        blk, off = table[pos // bs], pos % bs
        kp[layer, blk, off] = 7777.0
        vp[layer, blk, off] = -7777.0
    with tpu_interpret_mode():
        out2 = np.asarray(decode_attention_paged(q4, jnp.asarray(kp),
                                                 jnp.asarray(vp),
                                                 tables, lens, layer))
    # rows 0..accepted (the kept prefix + its correction row) untouched
    np.testing.assert_array_equal(out1[:, :accepted + 1],
                                  out2[:, :accepted + 1])


def test_paged_verify_rejects_zero_rows():
    from deepspeed_tpu.ops.decode_attention import (
        decode_attention_paged, decode_attention_paged_int8)

    q4, k_pool, v_pool, tables, lens, _ = _paged_setup(1, [5], 1, bs=8,
                                                       mb=4)
    with pytest.raises(ValueError, match="query row"):
        decode_attention_paged(q4[:, :0], k_pool, v_pool, tables, lens)
    kq, vq, ks, vs = _int8_pools(k_pool, v_pool, q4.shape[2])
    with pytest.raises(ValueError, match="query row"):
        decode_attention_paged_int8(q4[:, :0], kq, vq, ks, vs, tables, lens)


def test_paged_cache_index_exactly_on_block_boundary():
    """lengths == k*block_size: the incoming token is the first row of a
    fresh block — the gather edge case the block-table path adds."""
    from deepspeed_tpu.ops.decode_attention import decode_attention_paged

    for length in (32, 64, 96):
        args = _paged_setup(1, [length], 1, bs=32, mb=4, seed=length)
        with tpu_interpret_mode():
            out = decode_attention_paged(*args)
        ref = _paged_dense_ref(*args)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


def test_paged_garbage_blocks_ignored():
    """Unallocated table tail points at the garbage block: scribbling on
    it (and on unowned pool blocks) must not change any output."""
    from deepspeed_tpu.ops.decode_attention import decode_attention_paged

    q4, k_pool, v_pool, tables, lengths, layer = _paged_setup(
        1, [5], 1, bs=8, mb=4)
    with tpu_interpret_mode():
        out1 = decode_attention_paged(q4, k_pool, v_pool, tables, lengths,
                                      layer)
    kp = np.asarray(k_pool).copy()
    vp = np.asarray(v_pool).copy()
    owned = set(int(b) for b in np.asarray(tables)[0, :1])
    for blk in range(kp.shape[1]):
        if blk not in owned:
            kp[:, blk] = 9999.0
            vp[:, blk] = -9999.0
    # beyond the valid prefix in the same block, and the same block of
    # every OTHER layer
    kp[:, list(owned)[0], 6:] = 4444.0
    vp[:, list(owned)[0], 6:] = -4444.0
    for other in set(range(LAYERS)) - {layer}:
        kp[other] = 5555.0
        vp[other] = -5555.0
    with tpu_interpret_mode():
        out2 = decode_attention_paged(q4, jnp.asarray(kp), jnp.asarray(vp),
                                      tables, lengths, layer)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2))


def _aliased_setup(bs=8, mb=4, H=2, D=64, seed=0):
    """Two sequences whose block tables ALIAS the same physical prefix
    blocks (a shared system prompt mapped read-only by the prefix cache)
    plus private tails — the copy-on-write serving layout."""
    from deepspeed_tpu.ops.decode_attention import GARBAGE_BLOCK

    rng = np.random.default_rng(seed)
    nb = 1 + 6
    k_pool = rng.normal(size=(LAYERS, nb, bs, H * D)).astype(np.float32)
    v_pool = rng.normal(size=(LAYERS, nb, bs, H * D)).astype(np.float32)
    # rows share physical blocks 1,2 (16 shared prefix tokens); row 0
    # owns private block 3, row 1 owns private blocks 4,5
    tables = np.asarray([[1, 2, 3, GARBAGE_BLOCK],
                         [1, 2, 4, 5]], np.int32)
    lengths = np.asarray([19, 27], np.int32)
    q4 = rng.normal(size=(2, 1, H, D)).astype(np.float32)
    return (jnp.asarray(q4), jnp.asarray(k_pool), jnp.asarray(v_pool),
            jnp.asarray(tables), jnp.asarray(lengths), LAYERS - 1)


def test_paged_aliased_tables_match_dense():
    """Satellite: block tables that alias the same physical blocks (a
    shared prefix) stay bit-consistent with the dense gather oracle —
    sharing is pure indirection, never a math change."""
    from deepspeed_tpu.ops.decode_attention import decode_attention_paged

    args = _aliased_setup()
    with tpu_interpret_mode():
        out = decode_attention_paged(*args)
    ref = _paged_dense_ref(*args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_paged_aliased_garbage_isolation():
    """Scribbling on unowned pool blocks, and past both rows' valid
    prefixes inside their PRIVATE tail blocks, changes nothing — shared
    blocks only ever contribute their fully-valid rows."""
    from deepspeed_tpu.ops.decode_attention import decode_attention_paged

    q4, k_pool, v_pool, tables, lengths, layer = _aliased_setup()
    with tpu_interpret_mode():
        out1 = decode_attention_paged(q4, k_pool, v_pool, tables, lengths,
                                      layer)
    kp = np.asarray(k_pool).copy()
    vp = np.asarray(v_pool).copy()
    kp[:, 6] = 9999.0       # unowned block
    vp[:, 6] = -9999.0
    kp[:, 3, 4:] = 4444.0   # row 0 private tail: valid rows [0, 19-16+1)
    vp[:, 3, 4:] = -4444.0
    kp[:, 5, 4:] = 4444.0   # row 1 private tail: valid rows [0, 27-24+1)
    vp[:, 5, 4:] = -4444.0
    with tpu_interpret_mode():
        out2 = decode_attention_paged(q4, jnp.asarray(kp), jnp.asarray(vp),
                                      tables, lengths, layer)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2))


# ---------------------------------------------------------------------------
# int8 paged variant (the serving kv_cache_dtype: "int8" codec)
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnums=2)
def _int8_pools(k_pool, v_pool, H):
    """The pools as the model's write path stores them: rows quantized per
    token x head (``quantize_rowwise`` over ``[..., H, D]``), int8 rows
    back to ``H*D`` lanes, the scales a lane a head in whole registers."""
    from deepspeed_tpu.ops.decode_attention import scale_lanes
    from deepspeed_tpu.ops.quantizer import quantize_rowwise

    def quant(pool):
        q, s = quantize_rowwise(jnp.asarray(pool, jnp.float32).reshape(
            pool.shape[:3] + (H, -1)))
        return q.reshape(pool.shape), jnp.pad(
            s[..., 0], ((0, 0),) * 3 + ((0, scale_lanes(H) - H),))

    (kq, ks), (vq, vs) = quant(k_pool), quant(v_pool)
    return kq, vq, ks, vs


@pytest.mark.parametrize("lengths,tq", [([0, 5], 1), ([7, 63], 1),
                                        ([60, 30], 4),
                                        ([0, 23, 57], 5),   # verify shapes
                                        (_mixed(1), 1), (_mixed(4), 4)])
def test_paged_int8_kernel_matches_dequant_oracle(lengths, tq):
    """The int8 kernel dequantizes inside the block DMA; the dense
    gather-dequantize oracle must agree to fp32 round-off — both read
    the SAME int8 rows and scales, so this pins the kernel's dequant
    placement, not quantization error."""
    from deepspeed_tpu.models.decode_utils import cache_attn_mask
    from deepspeed_tpu.ops.decode_attention import (
        decode_attention_paged_int8, gather_paged_cache_int8)

    q4, k_pool, v_pool, tables, lens, layer = _paged_setup(
        len(lengths), lengths, tq, bs=32, mb=4,
        seed=sum(filter(None, lengths)) + tq)
    kq, vq, ks, vs = _int8_pools(k_pool, v_pool, q4.shape[2])
    with tpu_interpret_mode():
        out = decode_attention_paged_int8(q4, kq, vq, ks, vs, tables, lens,
                                          layer)
    ref = _int8_dense_ref(q4, kq, vq, ks, vs, tables, lens, layer)
    live = _live(lengths)
    np.testing.assert_allclose(np.asarray(out)[live], np.asarray(ref)[live],
                               rtol=2e-5, atol=2e-5)


def test_paged_int8_error_vs_f32_pinned():
    """Pinned quantization-error budget: int8 KV attention vs the exact
    f32 paged path. Per-row symmetric int8 on unit-normal KV keeps the
    attention output within a few percent — regressions in the codec
    (wrong scale axis, asymmetric drift) blow straight through this."""
    args = _paged_setup(2, [17, 40], 1, bs=32, mb=4, seed=7)
    q4, k_pool, v_pool, tables, lens, layer = args
    ref = _paged_dense_ref(*args)
    out = _int8_dense_ref(q4, *_int8_pools(k_pool, v_pool, q4.shape[2]),
                          tables, lens, layer)
    err = np.max(np.abs(np.asarray(out) - np.asarray(ref)))
    assert err < 0.05, f"int8 KV attention error {err} past the pinned budget"


@functools.lru_cache(maxsize=None)
def _lane_dense_inputs(tq, H, D, kv):
    """One batch of every length of live prefix and its pools, made once
    for the cases that differ only in ``layer``."""
    lengths = _mixed(tq, bs=16)
    q4, k_pool, v_pool, tables, lens, _ = _paged_setup(
        len(lengths), lengths, tq, bs=16, mb=4, H=H, D=D, seed=H * D + tq,
        dtype=jnp.bfloat16 if kv == "bf16" else np.float32)
    pools = (k_pool, v_pool) if kv == "bf16" else _int8_pools(k_pool, v_pool,
                                                              H)
    return lengths, q4, pools, tables, lens


@pytest.mark.parametrize("layer", [0, LAYERS - 1, "traced"])
@pytest.mark.parametrize("tq", [1, 4])
@pytest.mark.parametrize("H,D", [(2, 64), (2, 128), (3, 64), (5, 32)])
@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_stacked_lane_dense_pool_forms(layer, tq, H, D, kv):
    """The kernel's one pool form, ``[L, nb, bs, H*D]`` addressed by
    ``(layer, block)``: first and last layer as Python ints (an unrolled
    stack) and a traced layer index (a scanned one), decode and verify
    rows, head sizes 64 and 128, rows that are no multiple of 128 lanes (3
    x 64, 5 x 32), bf16 and int8 pools, over one batch of every length of
    live prefix (``_mixed``) — each against the dense oracle over the SAME
    stored numbers, so the tolerance is the kernel's own arithmetic (bf16
    probabilities into the value matmul), not the storage format's."""
    from deepspeed_tpu.ops.decode_attention import (
        decode_attention_paged, decode_attention_paged_int8)

    lengths, q4, pools, tables, lens = _lane_dense_inputs(tq, H, D, kv)
    if kv == "bf16":
        kernel, tol, oracle = decode_attention_paged, 2e-2, _paged_dense_ref
    else:
        kernel, tol, oracle = decode_attention_paged_int8, 2e-5, \
            _int8_dense_ref
    if layer == "traced":
        layer = 1
        run, at = jax.jit(kernel), (jnp.asarray(layer, jnp.int32),)
    else:
        # a Python int stays one inside the program
        run, at = jax.jit(lambda *a: kernel(*a, layer)), ()
    with tpu_interpret_mode():
        out = jax.block_until_ready(run(q4, *pools, tables, lens, *at))
    ref = oracle(q4, *pools, tables, lens, layer)
    assert out.shape == q4.shape and out.dtype == q4.dtype
    live = _live(lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32)[live],
                               np.asarray(ref)[live], rtol=tol, atol=tol)


@pytest.mark.parametrize("tq", [1, 4])
@pytest.mark.parametrize("kv", ["f32", "int8"])
def test_paged_kernel_fetches_nothing_past_a_rows_live_prefix(kv, tq):
    """The bound: the kernel walks ``min(cdiv(length + tq, bs), mb)``
    blocks of a row and no more. Every pool block that no row's live
    prefix names, the garbage block among them, is filled with NaN (the
    int8 pools' scales, and their rows with 127): one fetch past a live
    prefix, whatever mask follows it, makes an output NaN (0 x NaN), and
    the outputs have to stay finite and equal to the dense oracle's over
    the clean pool. No slot is idle here: an idle slot's one block IS the
    garbage block."""
    from deepspeed_tpu.ops.decode_attention import (
        GARBAGE_BLOCK, decode_attention_paged, decode_attention_paged_int8)

    bs, mb = 32, 4
    lengths = _mixed(tq, bs, mb)[1:] + [0]
    q4, k_pool, v_pool, tables, lens, layer = _paged_setup(
        len(lengths), lengths, tq, bs=bs, mb=mb, seed=11 + tq)
    named = {int(tables[b, j]) for b, ln in enumerate(lengths)
             for j in range(-(-(ln + tq) // bs))}
    assert GARBAGE_BLOCK not in named
    dead = [blk for blk in range(k_pool.shape[1]) if blk not in named]
    assert len(dead) >= len(lengths)  # each short row leaves some behind

    def poison(pool, value):
        return jnp.asarray(pool).at[:, jnp.asarray(dead)].set(value)

    if kv == "f32":
        pools, oracle = (k_pool, v_pool), _paged_dense_ref
        bad = tuple(poison(p, np.nan) for p in pools)
        kernel = decode_attention_paged
    else:
        pools, oracle = _int8_pools(k_pool, v_pool, q4.shape[2]), \
            _int8_dense_ref
        bad = tuple(poison(p, 127 if p.dtype == jnp.int8 else np.nan)
                    for p in pools)
        kernel = decode_attention_paged_int8
    with tpu_interpret_mode():
        out = np.asarray(kernel(q4, *bad, tables, lens, layer))
    assert np.isfinite(out).all()
    np.testing.assert_allclose(
        out, np.asarray(oracle(q4, *pools, tables, lens, layer)),
        rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("tq", [1, 4])
@pytest.mark.parametrize("kv", ["f32", "int8"])
def test_paged_rows_of_many_blocks(kv, tq):
    """Long rows in one batch with short ones, so the softmax state is
    carried over many steps and begun anew at every row: rows that end on
    a block boundary, one key into the next block, in the middle of the
    ninth and at the table's end, beside an idle slot and a one-block
    row."""
    from deepspeed_tpu.ops.decode_attention import (
        decode_attention_paged, decode_attention_paged_int8)

    bs, mb = 32, 10
    lengths = [4 * bs - tq, IDLE, 4 * bs - tq + 1, 5, 9 * bs - tq - 5,
               mb * bs - tq]
    q4, k_pool, v_pool, tables, lens, layer = _paged_setup(
        len(lengths), lengths, tq, bs=bs, mb=mb, seed=5 + tq)
    if kv == "f32":
        kernel, pools, oracle = decode_attention_paged, (k_pool, v_pool), \
            _paged_dense_ref
    else:
        kernel, pools, oracle = decode_attention_paged_int8, _int8_pools(
            k_pool, v_pool, q4.shape[2]), _int8_dense_ref
    with tpu_interpret_mode():
        out = np.asarray(kernel(q4, *pools, tables, lens, layer))
    live = _live(lengths)
    np.testing.assert_allclose(
        out[live], np.asarray(oracle(q4, *pools, tables, lens, layer))[live],
        rtol=2e-5, atol=2e-5)


def _oracle_error(kv, tq, batch):
    """``(largest, rms)`` error of the paged kernel over the live rows of
    one batch against the float32 dense-gather oracle over the SAME stored
    numbers: bf16 pools and queries (the probabilities round to bf16 for
    the value matmul), or int8 pools with float32 queries (everything in
    float32: the order of the sums alone). ``mixed`` is every length of
    live prefix in a table of 4 blocks, ``many`` rows of up to 10 blocks
    (three tiles of four) beside short ones."""
    from deepspeed_tpu.ops.decode_attention import (
        decode_attention_paged, decode_attention_paged_int8)

    bs = 32
    if batch == "mixed":
        mb, lengths = 4, _mixed(tq, bs, 4) + [40, 0]
    else:
        mb = 10
        lengths = [4 * bs - tq, IDLE, 4 * bs - tq + 1, 5, 9 * bs - tq - 5,
                   mb * bs - tq, 6 * bs + 3]
    q4, k_pool, v_pool, tables, lens, layer = _paged_setup(
        len(lengths), lengths, tq, bs=bs, mb=mb, H=5, D=64, seed=17 + tq,
        dtype=jnp.bfloat16 if kv == "bf16" else np.float32)
    if kv == "bf16":
        kernel, pools, oracle = decode_attention_paged, (k_pool, v_pool), \
            _paged_dense_ref
    else:
        kernel, pools, oracle = decode_attention_paged_int8, _int8_pools(
            k_pool, v_pool, q4.shape[2]), _int8_dense_ref
    with tpu_interpret_mode():
        out = jax.block_until_ready(kernel(q4, *pools, tables, lens, layer))
    live = _live(lengths)
    err = (np.asarray(out, np.float64)[live]
           - np.asarray(oracle(q4, *pools, tables, lens, layer),
                        np.float64)[live])
    return float(np.abs(err).max()), float(np.sqrt(np.mean(err ** 2)))


# the block-a-step form's error against that oracle, read on PR 44's parent
# (94a7eb4, interpret mode): (largest, rms) of each case
BLOCK_A_STEP_ERROR = {
    ("bf16", 1, "mixed"): (0.004032, 0.000468),
    ("bf16", 1, "many"): (0.004248, 0.0004971),
    ("bf16", 4, "mixed"): (0.007522, 0.0006416),
    ("bf16", 4, "many"): (0.00513, 0.0004665),
    ("bf16", 5, "mixed"): (0.007159, 0.0006585),
    ("bf16", 5, "many"): (0.004643, 0.0004133),
    ("int8", 1, "mixed"): (7.153e-07, 4.819e-08),
    ("int8", 1, "many"): (2.086e-07, 3.392e-08),
    ("int8", 4, "mixed"): (5.588e-07, 6.639e-08),
    ("int8", 4, "many"): (5.96e-07, 5.717e-08),
    ("int8", 5, "mixed"): (7.153e-07, 7.305e-08),
    ("int8", 5, "many"): (8.345e-07, 5.894e-08),
}


@pytest.mark.parametrize("kv,tq,batch", sorted(BLOCK_A_STEP_ERROR))
def test_paged_error_against_the_float32_oracle(kv, tq, batch):
    """What the tile form is held to: over the same batches its largest
    and its rms error against the float32 oracle are no more than 1.25 x
    what the block-a-step form read (it does the same arithmetic in the
    same precisions; only the maximum its probabilities are rounded
    against is taken over a tile of 128 keys)."""
    largest, rms = _oracle_error(kv, tq, batch)
    was_largest, was_rms = BLOCK_A_STEP_ERROR[kv, tq, batch]
    assert largest <= 1.25 * was_largest, (largest, was_largest)
    assert rms <= 1.25 * was_rms, (rms, was_rms)


@pytest.mark.parametrize("block_size,tq,tile_blocks", [
    (32, 1, 4), (32, 5, 4), (32, 256, 4), (16, 1, 8), (128, 1, 1),
    (256, 1, 1), (8, 4, 16)])
def test_paged_plan_reads_the_tile_from_the_shapes(monkeypatch, block_size,
                                                   tq, tile_blocks):
    """128 keys a tile, whole blocks, at least one; the query rows do not
    move it (so verify takes decode's tile): a traced call of that
    ``tq`` has ``tile_blocks`` operands a pool and lists its work in those
    tiles."""
    from deepspeed_tpu.ops import decode_attention as da

    plan = da.paged_plan(block_size)
    assert plan.tile_blocks == tile_blocks
    assert plan.tile_keys == max(128, block_size)
    assert f"{plan.tile_keys} keys" in plan.describe()
    seen = []
    real = da.pl.pallas_call

    def spy(kernel, *a, grid_spec, **kw):
        seen.append(len(grid_spec.in_specs))
        return real(kernel, *a, grid_spec=grid_spec, **kw)

    monkeypatch.setattr(da.pl, "pallas_call", spy)
    mb = 2 * tile_blocks
    args = _paged_setup(2, [3, block_size], tq, bs=block_size,
                        mb=max(mb, -(-(block_size + tq) // block_size)))
    jax.make_jaxpr(da.decode_attention_paged)(*args)
    assert seen == [1 + 2 * tile_blocks + 1]  # q, K and V tiles, the zeros
    row_of, _ = da.paged_step_work(args[4], args[3], tq, block_size)
    assert row_of.shape == (2 * -(-args[3].shape[1] // tile_blocks) + 1,)


def _tile_batch(tq, bs=32, mb=8, tile=4):
    """Idle; one block; to a tile's boundary; one block past it; a whole
    table of two tiles; idle; one key into a second block."""
    return [IDLE, bs - tq - 3, tile * bs - tq, tile * bs - tq + 1,
            mb * bs - tq, IDLE, bs]


@pytest.mark.parametrize("bs,mb,steps", [
    # tiles of 4 x 32: 0 + 1 + 1 + 2 + 2 + 0 + 1
    (32, 8, 7),
    # a block of 128 keys is a tile, so a step is a live block: 0 + 1 + 4
    # + 5 + 8 + 0 + 2 (tile=4 only places the lengths)
    (128, 8, 20)])
def test_paged_grid_is_the_live_blocks_of_all_rows(monkeypatch, bs, mb,
                                                   steps):
    """The kernel's iteration space follows ``lengths``, not
    ``block_tables.shape``: the ``pallas_call`` has ONE grid axis, its
    length is traced, and it comes to ``cdiv(live blocks, tile_blocks)``
    steps a row that holds a sequence and none for an idle slot, where the
    fixed grid had ``B x MB``."""
    from deepspeed_tpu.ops import decode_attention as da

    tq = 1
    lengths = _tile_batch(tq, bs, mb)
    args = _paged_setup(len(lengths), lengths, tq, bs=bs, mb=mb, seed=2)
    jaxpr = jax.make_jaxpr(da.decode_attention_paged)(*args)
    (call,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name ==
               "pallas_call"]
    mapping = call.params["grid_mapping"]
    assert len(mapping.grid) == 1 and mapping.num_dynamic_grid_bounds == 1
    assert not isinstance(mapping.grid[0], int)

    seen = []
    real = da.pl.pallas_call

    def spy(kernel, *a, grid_spec, **kw):
        seen.append(grid_spec.grid)
        return real(kernel, *a, grid_spec=grid_spec, **kw)

    monkeypatch.setattr(da.pl, "pallas_call", spy)
    with tpu_interpret_mode():
        out = jax.block_until_ready(da.decode_attention_paged(*args))
    (grid,) = seen
    assert [int(g) for g in grid] == [steps]
    assert int(grid[0]) < len(lengths) * mb
    live = _live(lengths)
    np.testing.assert_allclose(
        np.asarray(out)[live], np.asarray(_paged_dense_ref(*args))[live],
        rtol=2e-5, atol=2e-5)
    idle = [b for b in range(len(lengths)) if b not in live]
    np.testing.assert_array_equal(np.asarray(out)[idle], 0.0)


@pytest.mark.parametrize("kv", ["f32", "int8"])
def test_paged_batch_of_idle_slots_runs_one_step_on_no_row(monkeypatch, kv):
    """No slot holds a sequence (a warm-up call): no row has a step, the
    grid's one step touches none, and every output row is zero, whatever
    the garbage block holds."""
    from deepspeed_tpu.ops import decode_attention as da

    lengths = [IDLE] * 3
    q4, k_pool, v_pool, tables, lens, layer = _paged_setup(
        3, lengths, 1, bs=32, mb=8, seed=4)
    pools = (k_pool, v_pool)
    kernel = da.decode_attention_paged
    if kv == "int8":
        pools, kernel = _int8_pools(k_pool, v_pool, q4.shape[2]), \
            da.decode_attention_paged_int8
    pools = tuple(p.at[:, da.GARBAGE_BLOCK].set(
        127 if p.dtype == jnp.int8 else np.nan) for p in pools)
    row_of, first = da.paged_work_list(
        da.paged_step_lengths(lens, tables, 1), 1, 32, 8, tile_blocks=4)
    assert [int(f) for f in first] == [0, 0, 0, 0]
    seen = []
    real = da.pl.pallas_call

    def spy(kernel, *a, grid_spec, **kw):
        seen.append(grid_spec.grid)
        return real(kernel, *a, grid_spec=grid_spec, **kw)

    monkeypatch.setattr(da.pl, "pallas_call", spy)
    with tpu_interpret_mode():
        out = jax.block_until_ready(kernel(q4, *pools, tables, lens, layer))
    assert [int(g) for g in seen[0]] == [1]
    np.testing.assert_array_equal(np.asarray(out), 0.0)


@pytest.mark.parametrize("tile", ["default", 4])
def test_paged_work_list_made_once_serves_every_call(tile):
    """``paged_work_list`` is the grid: each row's first step, and each
    step's row. At its default tile (a block a step: what the hybrid
    kernel lists) it is the parent's list to the value; at the paged
    kernel's tile a call handed the list made outside it (as the model
    makes it, once before its layer loop) gives what a call that makes its
    own gives; one of another batch's shape, or of another tile, is
    refused."""
    from deepspeed_tpu.ops.decode_attention import (
        decode_attention_paged, paged_plan, paged_step_lengths,
        paged_work_list)

    bs, mb, tq = 32, 4, 1
    lengths = _mixed(tq, bs, mb) + [IDLE, 40]
    args = _paged_setup(len(lengths), lengths, tq, bs=bs, mb=mb, seed=2)
    if tile == "default":
        row_of, first = paged_work_list(args[4], tq, bs, mb)
        # idle 1; one block 1; to a boundary 2; the whole table 4; idle 1;
        # 41 keys 2
        assert [int(f) for f in first] == [0, 1, 2, 4, 8, 9, 11]
        assert [int(r) for r in row_of[:12]] == [0, 1, 2, 2, 3, 3, 3, 3, 4,
                                                 5, 5, 5]
        assert row_of.shape == (len(lengths) * mb + 1,)
        assert int(row_of[-1]) == len(lengths) - 1
        # the kernel takes tiles of four blocks here, and says so
        with pytest.raises(ValueError, match="work list"):
            decode_attention_paged(*args, work=(row_of, first))
        return
    assert paged_plan(bs).tile_blocks == tile
    mb = 8
    lengths = _tile_batch(tq, bs, mb, tile)
    args = _paged_setup(len(lengths), lengths, tq, bs=bs, mb=mb, seed=2)
    row_of, first = paged_work_list(args[4], tq, bs, mb, tile_blocks=tile)
    # listed at their length 0 (as the hybrid kernel lists them), idle
    # slots have a step: idle 1; one block 1; to the tile's boundary 1; a
    # block past it 2; the whole table 2; idle 1; 33 keys 1
    assert [int(f) for f in first] == [0, 1, 2, 3, 5, 7, 8, 9]
    assert [int(r) for r in row_of[:10]] == [0, 1, 2, 3, 3, 4, 4, 5, 6, 6]
    # ... and as the paged kernel's callers list them, none: ``row_of``
    # passes over them
    marked = paged_step_lengths(args[4], args[3], tq)
    assert [int(n) for n in marked] == [-tq] + [int(n) for n in
                                               args[4][1:5]] + [-tq, bs]
    row_of, first = paged_work_list(marked, tq, bs, mb, tile_blocks=tile)
    assert [int(f) for f in first] == [0, 0, 1, 2, 4, 6, 6, 7]
    assert [int(r) for r in row_of[:8]] == [1, 2, 3, 3, 4, 4, 6, 6]
    assert row_of.shape == (len(lengths) * (mb // tile) + 1,)
    assert int(row_of[-1]) == len(lengths) - 1
    with tpu_interpret_mode():
        own = jax.block_until_ready(decode_attention_paged(*args))
        given = jax.block_until_ready(decode_attention_paged(
            *args, work=(row_of, first)))
    live = _live(lengths)
    np.testing.assert_array_equal(np.asarray(own)[live],
                                  np.asarray(given)[live])
    with pytest.raises(ValueError, match="work list"):
        decode_attention_paged(*args, work=(row_of[:-1], first))


@pytest.mark.parametrize("max_blocks,tile,first,row_of", [
    # a ring of 5 blocks is one tile: a step a busy row
    (5, 5, [0, 0, 1, 2, 3, 4, 4, 5, 6], [1, 2, 3, 4, 6, 7] + [7] * 3),
    # a table of 40 blocks of 32 in tiles of 16: one block 1 step; 16
    # blocks 1; a key past the tile 2; 38 blocks 3; 8 blocks 1; a fresh row
    # (length 0 on a block of its own) 1
    (40, 16, [0, 0, 1, 2, 4, 7, 7, 8, 9],
     [1, 2, 3, 3, 4, 4, 4, 6, 7] + [7] * 16)])
def test_hybrid_work_list_is_in_the_plans_tiles(max_blocks, tile, first,
                                                row_of):
    """The hybrid kernel (``ops/hybrid_decode_attention.py``) lists its
    work in :func:`hybrid_plan`'s tiles since PR 50, and an idle slot
    (length 0 AND a table that starts at the garbage block: rows 0 and 5)
    owns no step; a fresh row on a block of its own keeps one."""
    from deepspeed_tpu.ops.attention import dispatch_counts
    from deepspeed_tpu.ops.hybrid_decode_attention import (hybrid_plan,
                                                           hybrid_work_list)

    plan = hybrid_plan(32, 512, 512, max_blocks)
    assert plan.tile_blocks == tile and plan.tile_keys == 32 * tile
    assert f"a tile of {tile} x 32 = {32 * tile} keys" in plan.describe()
    lengths = jnp.asarray([0, 5, 511, 512, 1200, 0, 255, 0])
    tables = jnp.asarray([[0], [3], [4], [9], [2], [0], [7], [8]])
    name = f"hybrid_decode_tile{32 * tile}"
    counted = dispatch_counts().get(name, 0)
    got_row_of, got_first = hybrid_work_list(lengths, tables, plan)
    assert dispatch_counts()[name] == counted + 1
    assert [int(f) for f in got_first] == first
    assert [int(r) for r in got_row_of] == row_of
    assert got_row_of.shape == (8 * -(-max_blocks // tile) + 1,)


def test_hybrid_plan_reads_its_tile_from_the_shapes():
    """As many blocks as hold 512 keys, no more than a row or a ring has,
    and no more than two tiles of both pools fit their share of VMEM in:
    the cells' shapes (LFM2 and granite: 512-lane rows over tables of 80
    and 256; mimo: 768 / 512 lanes over 128 blocks, a ring of 5 of 1536 /
    1024 lanes), larger blocks, and rows too wide for a whole tile."""
    from deepspeed_tpu.ops.hybrid_decode_attention import hybrid_plan

    assert [hybrid_plan(32, 512, 512, mb).tile_blocks
            for mb in (80, 256, 8, 1)] == [16, 16, 8, 1]
    assert hybrid_plan(32, 768, 512, 128).tile_blocks == 16
    assert hybrid_plan(32, 1536, 1024, 5).tile_blocks == 5
    assert hybrid_plan(128, 512, 512, 64).tile_blocks == 4
    assert hybrid_plan(1024, 512, 512, 64).tile_blocks == 1
    assert hybrid_plan(32, 4096, 4096, 128).tile_blocks == 8
    assert hybrid_plan(32, 8192, 8192, 128).tile_blocks == 4


@pytest.mark.parametrize("scan_layers", [True, False],
                         ids=["scanned", "unrolled"])
def test_model_lists_the_paged_kernels_work_once_a_step(monkeypatch,
                                                         scan_layers):
    """The work list depends on the step's lengths alone, so the model
    makes it before its layer stack and every layer's kernel call takes
    that one: one ``paged_work_list`` a traced decode step, whatever the
    number of layers; none in a prefill step, which runs no paged
    kernel."""
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    from deepspeed_tpu.ops import attention as attn_mod
    from deepspeed_tpu.ops import decode_attention as da

    cfg = GPT2Config.tiny(n_positions=64, dtype=jnp.float32,
                          scan_layers=scan_layers)
    assert cfg.n_layer > 1
    model = GPT2LMHeadModel(cfg.for_paged_decode(9, 8))
    tables = jnp.asarray([[3, 1, 5, 0], [2, 7, 4, 0]], jnp.int32)

    def paging(lengths, n, prefill):
        return {"block_tables": tables,
                "lengths": jnp.asarray(lengths, jnp.int32),
                "num_valid": jnp.full((2,), n, jnp.int32),
                "prefill": prefill}

    prompt = jnp.zeros((2, 8), jnp.int32)
    variables = jax.jit(lambda ids: model.init(
        jax.random.PRNGKey(0), ids, paging=paging([0, 0], 8, True)))(prompt)
    made = []
    real = da.paged_work_list

    def spy(*a, **kw):
        made.append(a[1:] + (kw,))
        return real(*a, **kw)

    monkeypatch.setattr(da, "paged_work_list", spy)
    monkeypatch.setattr(attn_mod, "_FORCE_DECODE_KERNEL", True)

    def step(ids, pg):
        return model.apply(variables, ids, mutable=["cache"], paging=pg)

    counted = attn_mod.dispatch_counts().get("paged_decode_tile128", 0)
    jaxpr = jax.make_jaxpr(lambda ids, ln: step(ids, paging(ln, 1, False)))(
        prompt[:, :1], jnp.asarray([6, 8], jnp.int32))
    # blocks of 8 keys: the kernel's tile, 16 of them
    assert made == [(1, 8, 4, {"tile_blocks": 16})]
    assert "pallas_call" in str(jaxpr)
    # ... and the form is counted once a traced program, not once a layer
    assert attn_mod.dispatch_counts().get("paged_decode_tile128", 0) \
        == counted + 1
    jax.make_jaxpr(lambda ids: step(ids, paging([0, 0], 8, True)))(prompt)
    assert len(made) == 1
    assert attn_mod.dispatch_counts()["paged_decode_tile128"] == counted + 1


@pytest.mark.parametrize("family,config,module,knob,tiles", [
    ("mimo_v2", "MiMoV2Config", "MiMoV2ForCausalLM", "ring_slots",
     # the global kind's table of 4 blocks, the window kind's ring
     lambda cfg: sorted({4, cfg.paged_ring_blocks_for(4)})),
    ("lfm2_moe", "Lfm2MoeConfig", "Lfm2MoeForCausalLM", "state_slots",
     lambda cfg: [4]),
    ("granite_hybrid", "GraniteHybridConfig", "GraniteHybridForCausalLM",
     "state_slots", lambda cfg: [4])])
def test_hybrid_families_list_the_kernels_work_once_a_step(
        monkeypatch, caplog, family, config, module, knob, tiles):
    """A served hybrid family lists the hybrid kernel's work once a KIND of
    layer a traced decode step, whatever its layers, in the plan's tiles;
    counts the form it took where ``stats()["attention_paths"]`` reads it;
    and the plan is logged once a shape, not once a layer or a trace."""
    import importlib
    import logging

    from deepspeed_tpu.ops import attention as attn_mod
    from deepspeed_tpu.ops import hybrid_decode_attention as hda

    mod = importlib.import_module(f"deepspeed_tpu.models.{family}")
    bs, slots = 4, 3
    cfg = getattr(mod, config).tiny().for_paged_decode(13, bs,
                                                       **{knob: slots})
    tiles = tiles(cfg)
    model = getattr(mod, module)(cfg)
    entries = cfg.paged_slot_state_for(bs)["entries"]
    tables = np.zeros((slots, 4 + entries), np.int32)
    tables[0, :2], tables[2, :1] = [3, 5], [7]       # slot 1 is idle
    tables[:, 4:] = 1 + np.arange(slots * entries).reshape(slots, entries)
    tables[1] = 0

    def paging(lengths, n, prefill):
        return {"block_tables": jnp.asarray(tables),
                "lengths": jnp.asarray(lengths, jnp.int32),
                "num_valid": jnp.full((slots,), n, jnp.int32),
                "prefill": prefill}

    prompt = jnp.zeros((slots, 4), jnp.int32)
    variables = jax.jit(lambda ids: model.init(
        jax.random.PRNGKey(0), ids, paging=paging([0] * slots, 4, True)))(
            prompt)
    made = []
    real = hda.hybrid_work_list

    def spy(lengths, block_tables, plan):
        made.append(plan)
        return real(lengths, block_tables, plan)

    monkeypatch.setattr(hda, "hybrid_work_list", spy)
    monkeypatch.setattr(attn_mod, "_FORCE_DECODE_KERNEL", True)
    monkeypatch.setattr(hda, "_noted_plans", set())
    names = [f"hybrid_decode_tile{t * bs}" for t in tiles]
    counted = [attn_mod.dispatch_counts().get(n, 0) for n in names]

    def step(ids, lengths, n, prefill):
        return model.apply(variables, ids, mutable=["cache"],
                           paging=paging(lengths, n, prefill))

    from deepspeed_tpu.utils.logging import logger

    logger.addHandler(caplog.handler)
    try:
        with caplog.at_level(logging.INFO, logger=logger.name):
            for _ in range(2):     # traced twice: listed and counted twice
                jaxpr = jax.make_jaxpr(
                    lambda ids, ln: step(ids, ln, 1, False))(
                        prompt[:, :1], jnp.asarray([6, 0, 2], jnp.int32))
    finally:
        logger.removeHandler(caplog.handler)
    assert sorted(p.tile_blocks for p in made) == sorted(tiles * 2)
    assert {p.block_size for p in made} == {bs}
    assert str(jaxpr).count("pallas_call") >= len(tiles)
    assert [attn_mod.dispatch_counts()[n] for n in names] == [
        c + 2 for c in counted]
    logged = [r.getMessage() for r in caplog.records
              if "decode_attention_hybrid q" in r.getMessage()]
    assert len(logged) == len(tiles), logged
    for plan in set(made):
        assert sum(plan.describe() in line for line in logged) == 1
    # ... and a prefill step runs no paged kernel and lists nothing
    jax.make_jaxpr(lambda ids: step(ids, [0] * slots, 4, True))(prompt)
    assert len(made) == 2 * len(tiles)


# ---------------------------------------------------------------------------
# the call writes the step's own rows (``rows=``), for busy rows only
def _step_rows(seed, B, H, D, pools):
    """A step's new rows as the pools take them: ``[B, 1, lanes]`` in the
    pools' dtype, int8 with its scale rows."""
    from deepspeed_tpu.ops.decode_attention import scale_lanes
    from deepspeed_tpu.ops.quantizer import quantize_rowwise

    rng = np.random.default_rng(seed)
    k4, v4 = (jnp.asarray(rng.normal(size=(B, 1, H, D)), jnp.float32)
              for _ in range(2))
    if len(pools) == 2:
        return tuple(r.reshape(B, 1, H * D).astype(pools[0].dtype)
                     for r in (k4, v4))
    held = [quantize_rowwise(r) for r in (k4, v4)]
    return tuple(h.reshape(B, 1, H * D) for h, _ in held) + tuple(
        jnp.pad(s.reshape(B, 1, H), ((0, 0), (0, 0), (0, scale_lanes(H) - H)))
        for _, s in held)


@jax.jit
def _scattered(pools, rows, tables, lens, layer, valid=None):
    """What the decode program did until the call wrote: every row
    through ``paged_write_slots``, an idle slot's (and a row of ``valid``
    0) onto the garbage block."""
    from deepspeed_tpu.models.decode_utils import (paged_positions,
                                                   paged_write_slots)

    blk, off = paged_write_slots(
        tables, paged_positions(lens, 1),
        jnp.ones_like(lens) if valid is None else valid, pools[0].shape[2])
    return tuple(p.at[layer, blk, off].set(r) for p, r in zip(pools, rows))


def _writing_setup(kv, lengths, bs, mb, seed, H=2, D=64):
    from deepspeed_tpu.ops import decode_attention as da

    q4, k_pool, v_pool, tables, lens, layer = _paged_setup(
        len(lengths), lengths, 1, bs=bs, mb=mb, H=H, D=D, seed=seed,
        dtype=jnp.float32 if kv == "int8" else jnp.bfloat16)
    pools, kernel = (k_pool, v_pool), da.decode_attention_paged
    if kv == "int8":
        pools, kernel = _int8_pools(k_pool, v_pool, H), \
            da.decode_attention_paged_int8
        q4 = q4.astype(jnp.bfloat16)
    return kernel, q4, pools, _step_rows(seed, len(lengths), H, D, pools), \
        tables, lens, layer


def _bits(a):
    a = np.asarray(a)
    return a.view({1: np.uint8, 2: np.uint16, 4: np.uint32}[
        a.dtype.itemsize])


def _writing(kernel, layer=None):
    """The call as ONE program: ``(q4, pools, rows, tables, lens[, layer],
    **traced) -> kernel(q4, *pools, tables, lens, layer, rows=rows, ...)``;
    ``layer`` given HERE stays a Python int inside it (a default that is not
    passed is not traced), passed to the call it is traced."""
    def call(q4, pools, rows, tables, lens, at=layer, **kw):
        return kernel(q4, *pools, tables, lens, at, rows=rows, **kw)
    return jax.jit(call)


_BS = 32
WRITE_CASES = {
    # on, before and after a block boundary (and a tile's: 4 blocks)
    "block-boundary": [_BS - 1, _BS, _BS + 1, IDLE, 4 * _BS - 1, 4 * _BS,
                       IDLE, IDLE],
    # a fresh row at length 0 on a block of its own, beside idle slots
    "fresh-row": [0, IDLE, 5, IDLE],
    # rows of many blocks: the last block is the third tile's second
    "many-blocks": [9 * _BS + 7, IDLE, 10 * _BS - 1, 3, 8 * _BS, IDLE],
    # no slot holds a sequence: the grid's one step puts back what it read
    "idle-only": [IDLE, IDLE, IDLE],
    # more writers than ``paged_most_writers`` of two pools: the program's
    # other branch, every slot's row scattered (int8's four pools: the
    # write call still)
    "crowded": [5, 40, IDLE, 70, 100],
}


@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("case", sorted(WRITE_CASES))
def test_paged_call_writes_the_steps_rows_as_the_scatter_did(kv, case):
    """``rows=``: the call's output AND the pools it hands back are
    scatter-then-attend's to the bit, in every layer and block but the
    garbage block, which an idle slot's row went to and now no byte of
    which moves (unless the step is so crowded that the call scatters
    too: then the pools are the scatter's whole)."""
    from deepspeed_tpu.ops.decode_attention import paged_most_writers

    lengths = WRITE_CASES[case]
    kernel, q4, pools, rows, tables, lens, layer = _writing_setup(
        kv, lengths, _BS, 10, seed=len(case))
    live = _live(lengths)
    scatters = len(live) > paged_most_writers(len(lengths), len(pools))
    assert scatters == (case == "crowded" and kv == "bf16")
    want_pools = _scattered(pools, rows, tables, lens, layer)
    with tpu_interpret_mode():
        want = jax.block_until_ready(_writing(kernel, layer)(
            q4, want_pools, None, tables, lens))
        out, got_pools = jax.block_until_ready(_writing(kernel, layer)(
            q4, pools, rows, tables, lens))
    np.testing.assert_array_equal(_bits(out)[live], _bits(want)[live])
    np.testing.assert_array_equal(np.asarray(out, np.float32)[
        [b for b in range(len(lengths)) if b not in live]], 0.0)
    for before, got, scattered, new in zip(pools, got_pools, want_pools,
                                           rows):
        assert got.dtype == before.dtype and got.shape == before.shape
        np.testing.assert_array_equal(_bits(got)[:, 1:],
                                      _bits(scattered)[:, 1:])
        np.testing.assert_array_equal(
            _bits(got)[:, 0], _bits(scattered if scatters else before)[:, 0])
        # ... and a busy row's new row is where its table says
        for b in live:
            ln = int(lens[b])
            np.testing.assert_array_equal(
                _bits(got[layer, tables[b, ln // _BS], ln % _BS]),
                _bits(new[b, 0]))


def test_paged_most_writers_is_where_the_two_writes_meet():
    from deepspeed_tpu.ops.decode_attention import paged_most_writers

    # (read on the chip at 32 slots: tools/probe_paged_kv_write.py)
    assert paged_most_writers(32, 2) == 24 and paged_most_writers(32, 4) == 32
    assert paged_most_writers(8, 2) == 6 and paged_most_writers(1, 2) == 0


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_paged_call_never_writes_a_block_two_tables_share(kv):
    """The prefix cache's case: two rows whose tables start with the SAME
    blocks, each appending to a block of its own (the engine copies on
    write at admission). The shared blocks keep every byte, and a row
    that brings no row (``valid`` 0) writes nothing and attends what its
    pool holds."""
    lengths = [2 * _BS + 3, 2 * _BS + 9, 5]
    kernel, q4, pools, rows, tables, lens, layer = _writing_setup(
        kv, lengths, _BS, 4, seed=11)
    tables = tables.at[1, :2].set(tables[0, :2])
    valid = jnp.asarray([1, 1, 0], jnp.int32)
    want_pools = _scattered(pools, rows, tables, lens, layer, valid)
    with tpu_interpret_mode():
        want = jax.block_until_ready(_writing(kernel, layer)(
            q4, want_pools, None, tables, lens))
        out, got_pools = jax.block_until_ready(_writing(kernel, layer)(
            q4, pools, rows, tables, lens, valid=valid))
    np.testing.assert_array_equal(_bits(out), _bits(want))
    shared = np.asarray(tables[0, :2])
    for before, got, scattered in zip(pools, got_pools, want_pools):
        np.testing.assert_array_equal(_bits(got)[:, shared],
                                      _bits(before)[:, shared])
        np.testing.assert_array_equal(_bits(got)[:, 1:],
                                      _bits(scattered)[:, 1:])
        # the third row's block is as it was: it brought no row
        np.testing.assert_array_equal(_bits(got)[:, tables[2, 0]],
                                      _bits(before)[:, tables[2, 0]])


def test_paged_write_list_names_the_writing_rows_and_their_places():
    from deepspeed_tpu.ops import decode_attention as da

    tables = jnp.asarray([[3, 4, 0], [0, 0, 0], [5, 6, 7], [8, 0, 0],
                          [9, 1, 2]], jnp.int32)
    lens = jnp.asarray([9, 0, 23, 0, 30], jnp.int32)
    valid = jnp.asarray([1, 1, 1, 1, 0], jnp.int32)
    order, count, block, offset = da.paged_write_list(lens, tables, valid, 8)
    # row 1 is idle, row 4 brings no row; row 3 is fresh on its own block;
    # row 2 has filled its table and writes into its last block
    assert [int(x) for x in count] == [3]
    assert [int(x) for x in order] == [0, 2, 3, 1, 4, 4]
    assert [int(x) for x in block] == [4, da.GARBAGE_BLOCK, 7, 8,
                                       da.GARBAGE_BLOCK]
    assert [int(x) for x in offset] == [1, 0, 7, 0, 0]
    work = da.paged_step_work(lens, tables, 1, 8, valid=valid)
    assert len(work) == 6 and len(da.paged_step_work(lens, tables, 1, 8)) == 2
    for a, b in zip(work[2:], (order, count, block, offset)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_paged_write_list_made_once_serves_every_layers_call(kv):
    """``work=paged_step_work(..., valid=)``, made once a step, is what a
    call makes for itself: every layer's call writes its own layer and no
    other."""
    from deepspeed_tpu.ops import decode_attention as da

    lengths = [40, IDLE, 7]
    kernel, q4, pools, rows, tables, lens, _ = _writing_setup(
        kv, lengths, _BS, 4, seed=3)
    work = da.paged_step_work(lens, tables, 1, _BS,
                              valid=jnp.ones_like(lens))
    # (the layer traced: two programs serve the three layers)
    call = _writing(kernel)
    with tpu_interpret_mode():
        got = pools
        for layer in range(LAYERS):
            alone = jax.block_until_ready(call(q4, got, rows, tables, lens,
                                               layer))
            out, got = jax.block_until_ready(call(q4, got, rows, tables, lens,
                                                  layer, work=work))
            np.testing.assert_array_equal(_bits(out), _bits(alone[0]))
    want = pools
    for layer in range(LAYERS):
        want = _scattered(want, rows, tables, lens, layer)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(_bits(a)[:, 1:], _bits(b)[:, 1:])


def test_paged_call_writes_one_row_a_sequence_or_refuses():
    from deepspeed_tpu.ops import decode_attention as da

    assert da.paged_call_writes(8, 1, 4) and not da.paged_call_writes(8, 3, 4)
    q4, k_pool, v_pool, tables, lens, layer = _paged_setup(
        2, [5, 9], 3, bs=8, mb=4)
    rows = tuple(jnp.zeros((2, 3, 128), jnp.float32) for _ in range(2))
    with pytest.raises(ValueError, match="ONE new row"):
        da.decode_attention_paged(q4, k_pool, v_pool, tables, lens, layer,
                                  rows=rows)
    with pytest.raises(ValueError, match="ONE new row"):
        da.decode_attention_paged(q4[:, :1], k_pool, v_pool, tables, lens,
                                  layer, rows=tuple(
                                      r[:, :1].astype(jnp.bfloat16)
                                      for r in rows))


def test_paged_live_row_on_the_garbage_block_is_attended():
    """Only a row of length 0 whose table starts at the garbage block is
    idle. A row that holds tokens is attended over whatever its table
    names, the garbage block too, as the fixed grid did; a fresh row
    (length 0) on a block of its own is attended over its ``tq`` keys."""
    from deepspeed_tpu.ops.decode_attention import (
        GARBAGE_BLOCK, decode_attention_paged)

    lengths, tq = [5, 0, IDLE], 4
    q4, k_pool, v_pool, tables, lens, layer = _paged_setup(
        3, lengths, tq, bs=32, mb=4, seed=9)
    tables = tables.at[0, 0].set(GARBAGE_BLOCK)
    with tpu_interpret_mode():
        out = np.asarray(decode_attention_paged(q4, k_pool, v_pool, tables,
                                                lens, layer))
    ref = np.asarray(_paged_dense_ref(q4, k_pool, v_pool, tables, lens,
                                      layer))
    np.testing.assert_allclose(out[:2], ref[:2], rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(out[2], 0.0)


def test_paged_pool_shape_is_checked():
    """A pool row must hold exactly the query's heads x dim lanes, and a
    scale row one lane a head."""
    from deepspeed_tpu.ops.decode_attention import (
        decode_attention_paged, decode_attention_paged_int8)

    q4, k_pool, v_pool, tables, lens, _ = _paged_setup(1, [5], 1, bs=8,
                                                       mb=4)
    with pytest.raises(ValueError, match="lanes"):
        decode_attention_paged(q4, k_pool[..., :64], v_pool[..., :64],
                               tables, lens)
    kq, vq, ks, vs = _int8_pools(k_pool, v_pool, q4.shape[2])
    with pytest.raises(ValueError, match="scale pool shape"):
        decode_attention_paged_int8(q4, kq, vq, ks[..., :1], vs[..., :1],
                                    tables, lens)


@pytest.mark.heavy
@pytest.mark.parametrize("kv", ["", "int8"])
@pytest.mark.parametrize("scan_layers", [True, False],
                         ids=["scanned", "unrolled"])
def test_paged_model_steps_kernel_matches_dense(monkeypatch, scan_layers, kv):
    """End-to-end through the model: a paged prefill, two decode steps and
    a 3-row verify step of a scanned stack (pool carried through the layer
    scan, the layer index scanned in) and of an unrolled one (static layer
    index), with the kernel reading the stacked pool at ``(layer, block)``,
    give the dense gather path's logits and leave the same pool behind.
    The decode steps run beside an idle third slot: the kernel's call
    writes the two busy rows' own rows and not a byte of the garbage
    block, which the dense path's scatter (and the verify step's, in both)
    sends the idle slot's rows to."""
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    from deepspeed_tpu.ops import attention as attn_mod

    cfg = GPT2Config.tiny(n_positions=64, dtype=jnp.float32,
                          scan_layers=scan_layers)
    model = GPT2LMHeadModel(cfg.for_paged_decode(9, 8, kv))
    rng = np.random.default_rng(0)
    tables = jnp.asarray([[3, 1, 5, 0], [2, 7, 4, 0]], jnp.int32)
    prompt = jnp.asarray(rng.integers(0, 256, (2, 8)), jnp.int32)
    n_prompt = jnp.asarray([6, 8], jnp.int32)

    def paging(lengths, num_valid, prefill=False):
        # (a step's third slot is idle: length 0 on garbage blocks)
        idle = lengths.shape[0] - tables.shape[0]
        return {"block_tables": jnp.pad(tables, ((0, idle), (0, 0))),
                "lengths": lengths, "num_valid": num_valid,
                "prefill": prefill}

    variables = jax.jit(lambda ids: model.init(
        jax.random.PRNGKey(0), ids, paging=paging(
            jnp.zeros((2,), jnp.int32), n_prompt, True)))(prompt)
    params = {"params": variables["params"]}
    cache0 = jax.tree_util.tree_map(jnp.zeros_like, variables["cache"])
    assert {k: v.shape for k, v in cache0["transformer"].items()} == {
        **{f"{n}_pool": (cfg.n_layer, 9, 8, cfg.n_embd)
           for n in ("key", "value")},
        **({f"{n}_scale": (cfg.n_layer, 9, 8, 128)
            for n in ("key", "value")} if kv else {})}

    def run(force):
        # each pass its own programs (one a step shape: the prefill, a
        # decode step, the verify step), traced under its setting
        monkeypatch.setattr(attn_mod, "_FORCE_DECODE_KERNEL", force)
        prefill = jax.jit(lambda p, cache: model.apply(
            {**p, "cache": cache}, prompt, mutable=["cache"], paging=paging(
                jnp.zeros((2,), jnp.int32), n_prompt, True)))
        step = jax.jit(lambda p, cache, tok, lengths: model.apply(
            {**p, "cache": cache}, tok, mutable=["cache"], paging=paging(
                lengths, jnp.full((3,), tok.shape[1], jnp.int32))))
        outs, cache, garbage = [], cache0, []
        lengths = jnp.pad(n_prompt, (0, 1))
        with tpu_interpret_mode() if force else _null():
            _, vars_ = prefill(params, cache)
            cache = jax.block_until_ready(vars_["cache"])
            for t in (1, 1, 3):
                tok = jnp.asarray(rng_tokens[len(outs)][:, :t])
                garbage.append([np.asarray(leaf[:, 0]) for leaf in
                                jax.tree_util.tree_leaves(cache)])
                logits, vars_ = step(params, cache, tok, lengths)
                logits, cache = jax.block_until_ready(
                    (logits, vars_["cache"]))
                lengths = lengths + jnp.asarray([t, t, 0])
                outs.append(np.asarray(logits)[:2])
        return outs, cache, garbage

    rng_tokens = rng.integers(0, 256, (3, 3, 3)).astype(np.int32)
    dense, dense_cache, dense_garbage = run(False)
    kern, kern_cache, kern_garbage = run(True)
    # the garbage block before the first decode step, the second and the
    # verify step: the kernel's decode steps left it as the prefill did
    for a, b in zip(kern_garbage[0], kern_garbage[2]):
        np.testing.assert_array_equal(a, b)
    assert any((a != b).any()
               for a, b in zip(dense_garbage[0], dense_garbage[2]))
    for a, b in zip(dense, kern):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)
    for a, b in zip(jax.tree_util.tree_leaves(dense_cache),
                    jax.tree_util.tree_leaves(kern_cache)):
        # garbage block 0 takes the pads' writes; everything else agrees
        np.testing.assert_allclose(np.asarray(a, np.float32)[:, 1:],
                                   np.asarray(b, np.float32)[:, 1:],
                                   rtol=2e-4, atol=2e-4)


def test_quantize_rowwise_roundtrip():
    from deepspeed_tpu.ops.quantizer import dequantize_rowwise, quantize_rowwise

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(3, 5, 4, 64)).astype(np.float32))
    q, s = quantize_rowwise(x)
    assert q.dtype == jnp.int8 and s.shape == (3, 5, 4, 1)
    back = dequantize_rowwise(q, s)
    assert float(jnp.max(jnp.abs(back - x))) < float(jnp.max(jnp.abs(x))) / 100
    # all-zero rows (the garbage block) round-trip to exact zeros
    z = jnp.zeros((1, 2, 2, 8), jnp.float32)
    qz, sz = quantize_rowwise(z)
    assert (np.asarray(qz) == 0).all() and (np.asarray(sz) == 1.0).all()
    assert (np.asarray(dequantize_rowwise(qz, sz)) == 0).all()


@pytest.mark.heavy
def test_model_decode_uses_kernel(monkeypatch):
    """End-to-end: GPT-2 decode with the kernel matches the dense path."""
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    from deepspeed_tpu.ops import attention as attn_mod

    cfg = GPT2Config.tiny(n_positions=128, dtype=jnp.float32).for_decode()
    model = GPT2LMHeadModel(cfg)
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, 256, (2, 16)), jnp.int32)
    variables = jax.jit(model.init)(jax.random.PRNGKey(0), prompt)
    params = {"params": variables["params"]}

    def run(force):
        # each pass its own program (one a step shape), traced under its
        # setting
        monkeypatch.setattr(attn_mod, "_FORCE_DECODE_KERNEL", force)
        apply = jax.jit(lambda p, cache, ids: model.apply(
            {**p, "cache": cache}, ids, mutable=["cache"]))
        ctx = tpu_interpret_mode() if force else _null()
        outs = []
        with ctx:
            logits, vars_ = jax.block_until_ready(apply(
                params, variables["cache"], prompt))
            tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
            cache = vars_["cache"]
            for _ in range(4):
                logits, vars_ = jax.block_until_ready(apply(params, cache,
                                                            tok))
                cache = vars_["cache"]
                tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
                outs.append(np.asarray(logits))
        return outs

    dense = run(False)
    kern = run(True)
    for a, b in zip(dense, kern):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


class _null:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


# (heads, KV heads, value group, window): differential attention's pairs
# (two query heads a KV head, a value group of two), a wider group, and the
# group of one that every other family runs, over a table and over a ring
VALUE_GROUP_CASES = [(8, 4, 2, 0), (8, 4, 2, 6), (8, 4, 4, 0), (4, 4, 2, 0),
                     (8, 4, 1, 0), (8, 4, 1, 6)]


@pytest.mark.parametrize("case", VALUE_GROUP_CASES,
                         ids=lambda c: "-".join(map(str, c)))
def test_hybrid_kernel_keeps_a_heads_value_group(case):
    """``value_group``: a head scores against its own KV head's key lanes
    and keeps the value lanes of ``value_group`` ADJACENT KV heads side by
    side: ``[B, 1, H, value_group * dv]``, against the masked XLA path over
    ``blocks.value_groups``; rows of unequal length, an idle one, a ring."""
    from deepspeed_tpu.models.blocks import masked_gqa, value_groups
    from deepspeed_tpu.ops import hybrid_decode_attention as hda

    heads, kv, group, window = case
    dk = dv = 16
    bs, mb = 4, 3 if window else 6
    lengths = np.asarray([0, 9, 3, 10 if window else 22], np.int32)
    idle = np.asarray([True, False, False, False])
    b = len(lengths)
    rng = np.random.default_rng(3)
    k_pool = rng.standard_normal((2, 1 + b * mb, bs, kv * dk), np.float32)
    v_pool = rng.standard_normal((2, 1 + b * mb, bs, kv * dv), np.float32)
    tables = 1 + np.arange(b * mb, dtype=np.int32).reshape(b, mb)
    said_by = tables.copy()
    said_by[idle] = 0
    if not window:
        tables = said_by.copy()
    q = rng.standard_normal((b, 1, heads, dk), np.float32)
    plan = hda.hybrid_plan(bs, kv * dk, kv * dv, mb)
    with tpu_interpret_mode():
        got = np.asarray(jax.block_until_ready(jax.jit(
            lambda q, k, v, t, said, n: hda.decode_attention_hybrid(
                q, k, v, t, n, 1, kv_heads=kv, window=window,
                ring=bool(window), value_group=group,
                work=hda.hybrid_work_list(n, said, plan)))(
                    *map(jnp.asarray, (q, k_pool, v_pool, tables, said_by,
                                       lengths)))))
    assert got.shape == (b, 1, heads, group * dv)
    rows = mb * bs
    keys = k_pool[1][tables].reshape(b, rows, kv, dk)
    vals = v_pool[1][tables].reshape(b, rows, kv, dv)
    held = (np.asarray(hda.ring_positions(lengths + 1, rows)) if window
            else np.broadcast_to(np.arange(rows), (b, rows)))
    want = np.asarray(masked_gqa(
        jnp.asarray(q), jnp.asarray(keys),
        value_groups(jnp.asarray(vals), group),
        jnp.asarray(lengths)[:, None], jnp.asarray(held),
        jnp.asarray(held >= 0), window))
    assert np.abs(got[~idle] - want[~idle]).max() <= 1e-5
    assert not got[idle].any()
    # head h keeps the values of KV heads group * (h // (group * G)) ..:
    # with one-hot scores the lanes say whose values they are
    per = heads // kv
    for h in (0, heads - 1):
        first = group * (h // (group * per))
        own = np.asarray(value_groups(jnp.asarray(vals), group))[
            :, :, h // per]
        assert (own[..., :dv] == vals[:, :, first]).all()


def test_hybrid_kernel_refuses_a_value_group_that_does_not_divide():
    from deepspeed_tpu.ops.hybrid_decode_attention import (
        decode_attention_hybrid)

    with pytest.raises(ValueError, match="value groups of 3"):
        decode_attention_hybrid(
            jnp.zeros((1, 1, 8, 16)), jnp.zeros((1, 4, 4, 64)),
            jnp.zeros((1, 4, 4, 64)), jnp.zeros((1, 3), jnp.int32),
            jnp.zeros((1,), jnp.int32), 0, kv_heads=4, value_group=3)
