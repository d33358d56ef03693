"""Shared decode/padding position math for every autoregressive family
(gpt2 canonical decoder, llama, future archs).

The single source for the left-padding convention: positions start at 0
at each row's first real token, the padded prefix occupies cache slots
``[0, pad)``, and the decode-step mask combines the causal bound, the
per-row pad exclusion, and an optional sliding window (GPT-Neo local
attention). Model files must use these — a private re-implementation
desynchronizing any one of them produces wrong positions with no error.
"""

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.attention import record_dispatch


def validate_left_padded_mask(input_ids, attention_mask):
    """The user-facing mask contract, shared by every serving tier
    (``InferenceEngine.generate`` and the ZeRO-Inference engine): promote
    1-D, require the same shape as ``input_ids``, require LEFT padding
    (non-decreasing rows) with at least one real token per row, and
    collapse an all-real mask to ``None`` (the unpadded fast path).
    Returns the validated ``[B, T]`` int32 mask, or ``None``."""
    import numpy as np

    attention_mask = jnp.asarray(attention_mask, jnp.int32)
    if attention_mask.ndim == 1:
        attention_mask = attention_mask[None]
    if attention_mask.shape != tuple(input_ids.shape):
        # a mis-shaped mask broadcasts through every position/validity
        # computation and generates garbage with no error
        raise ValueError(
            f"attention_mask shape {attention_mask.shape} must "
            f"match input_ids shape {tuple(input_ids.shape)}")
    host_mask = np.asarray(attention_mask)
    if not (np.diff(host_mask, axis=1) >= 0).all():
        # right padding would mask REAL cache slots and sample from a
        # pad position — wrong output, no error
        raise ValueError(
            "attention_mask must be LEFT-padded (non-decreasing "
            "along the sequence): pad tokens go before the prompt")
    if not host_mask[:, -1].all():
        # an all-pad row softmaxes over nothing (NaN logits) and the
        # first token samples from the masked last position
        raise ValueError(
            "attention_mask has a row whose final position is "
            "padding — every prompt needs at least one real token, "
            "and left padding puts it last")
    if host_mask.all():
        # the ubiquitous generate(**tokenizer(...)) pattern with an
        # equal-length batch: keep the unpadded fast path
        return None
    return attention_mask


def row_positions(attention_mask):
    """[B, T] per-row positions for LEFT-padded prompts: 0 at each row's
    first real token (pads clip to 0; their outputs are masked anyway)."""
    return jnp.clip(jnp.cumsum(attention_mask, axis=1) - 1, 0)


def pad_lengths(attention_mask, T: int):
    """[B] padded-prefix lengths (left padding occupies [0, pad))."""
    return (T - jnp.sum(attention_mask, axis=1)).astype(jnp.int32)


def decode_positions(idx, T: int, pad):
    """[B, T] per-row positions for a padded decode step: absolute cache
    slot minus the row's padded prefix (clipped at 0)."""
    return jnp.clip((idx + jnp.arange(T))[None] - pad[:, None], 0)


def cache_attn_mask(S: int, idx, T: int, pad=None, window: int = 0):
    """Decode-step attention mask over the [B?, 1, T, S] cache window:
    causal bound (key slot <= query slot) plus, when ``pad`` is given, the
    per-row padded-prefix exclusion, plus an optional sliding window
    (GPT-Neo local attention). ``idx`` may be a scalar (one shared cache
    index — the legacy generate() batch, which advances in lockstep) or a
    ``[B]`` vector of per-row valid lengths (paged serving slots, each at
    its own position)."""
    key_pos = jnp.arange(S)
    if getattr(idx, "ndim", 0) == 1:
        # ragged rows: query t of row b sits at slot idx[b] + t
        q_pos = idx[:, None] + jnp.arange(T)[None]          # [B, T]
        mask = key_pos[None, None, :] <= q_pos[:, :, None]  # [B, T, S]
        if window:
            mask = mask & (key_pos[None, None, :] > q_pos[:, :, None] - window)
        if pad is not None:
            mask = mask & (key_pos[None, None, :] >= pad[:, None, None])
        return mask[:, None]  # [B, 1, T, S]
    q_pos = idx + jnp.arange(T)
    mask = key_pos[None, :] <= q_pos[:, None]  # [T, S]
    if window:
        mask = mask & (key_pos[None, :] > q_pos[:, None] - window)
    if pad is None:
        return mask[None, None]  # [1, 1, T, S]
    mask = mask[None] & (key_pos[None, None, :] >= pad[:, None, None])
    return mask[:, None]  # [B, 1, T, S]


def paged_positions(lengths, T: int):
    """[B, T] absolute cache positions for a paged step: row b's input
    token t lands at logical slot ``lengths[b] + t`` (prefill starts at
    0; a decode step appends at the row's current length)."""
    return lengths[:, None] + jnp.arange(T)[None]


def paged_write_slots(block_tables, positions, num_valid, block_size: int):
    """``(block, offset)``, each ``[B, T]``: where a paged step's KV rows
    land in the pool.

    Real tokens (``t < num_valid[b]``) map through the row's block table:
    block ``table[b, pos // bs]``, offset ``pos % bs``. The padded tail of
    a bucketed prefill (and rows that bring nothing, ``num_valid == 0``)
    routes to the reserved garbage block 0 instead — pads must never
    overwrite another sequence's blocks, and clamping them onto real rows
    would corrupt this sequence's own prefix. An idle serving slot's rows
    land there too, through its table of garbage blocks: true of every
    program that scatters (prefill, chunk, verify, the hybrid and latent
    models' decode); GPT-2's decode step on the Pallas kernel does not pass
    here, its call writes its busy rows' rows itself
    (``ops/decode_attention.py:paged_call_writes``)."""
    B, T = positions.shape
    mb = block_tables.shape[-1]
    blk = jnp.clip(positions // block_size, 0, mb - 1)
    off = positions % block_size
    valid = jnp.arange(T)[None] < num_valid[:, None]
    return jnp.where(valid, jnp.take_along_axis(block_tables, blk, axis=1),
                     0), off


# ---------------------------------------------------------------------------
# token lookup: one algorithm (select a row of the table a token), two
# access patterns, chosen from how the table really lies on its device

# The most tokens a call looks up by columns. Measured on a v5e on GPT-2
# XL's table (bf16[50257, 1600], which the backend lays with the vocabulary
# minor because 1600 is 12.5 registers; PERF.md section 6, PR 42): by rows
# the program first copies the whole table into row-major order, 346-360 us
# whatever the count (450 us inside the decode program); by columns a token
# costs a chunk's read: for the decode program's 32 tokens under 40 us in
# the kernel and 73 us in the XLA loop, which beside the tied head alone
# read 191 us for 64 tokens, 361 us for 96 and 3.6-4.6 us a token from
# there. Loop and copy meet near 92 tokens: 64 is the largest power of two
# under it, and past it the one copy is the cheaper. (The kernel meets the
# copy later, not measured past 128 tokens; a prefill is too rare in the
# benchmark's cells to show the difference: ROADMAP S1b.)
LOOKUP_COLUMNS_MAX_TOKENS = 64


def vocab_is_minor(table) -> bool:
    """Whether a ``[vocab, width]`` table, as it really lies, has the
    vocabulary as its minor (lane) dimension: read off the array's own
    format, on one device. Anything else answers False: a row-major
    array, one sharded or replicated over several devices, a quantised
    leaf (a dict the program rebuilds the table from), a tracer."""
    layout = getattr(getattr(table, "format", None), "layout", None)
    sharding = getattr(table, "sharding", None)
    return (layout is not None and sharding is not None
            and len(sharding.device_set) == 1
            and tuple(layout.major_to_minor) == (1, 0))


def lookup_form(table, tokens: int) -> str:
    """``"columns"`` or ``"rows"``: how a program that looks ``tokens``
    token ids up in ``table`` should read it (:func:`embed_lookup`)."""
    return ("columns" if tokens <= LOOKUP_COLUMNS_MAX_TOKENS
            and vocab_is_minor(table) else "rows")


def lookup_columns(table, ids):
    """``table[ids]``, bit for bit, read through the table's transposed
    view: a token takes the lane-aligned chunk ``[width, 128]`` that holds
    its column (the last chunk clamped to the table's edge) and selects
    its lane. For a table that lies with the vocabulary minor the
    transpose is a bitcast and the chunk a run of whole registers, so the
    program holds no row-major copy of the table. The selection works on
    the bits (a ``where`` and an integer max over zeros): an ``inf``, a
    ``nan`` or a ``-0.0`` comes back as it went in (what a backend's own
    slice does to a value, it does here as in ``table[ids]``: the chip
    flushes a bfloat16 denormal in both, the CPU quiets a signalling nan
    of a bfloat16 chunk). On the chip the Pallas
    kernel reads the same chunks with the next one in flight
    (``ops/embed_lookup.py``); this loop is its oracle and every other
    backend's form."""
    from deepspeed_tpu.ops import attention, embed_lookup as kernel

    vocab, width = table.shape
    flat = jnp.clip(ids.reshape(-1).astype(jnp.int32), 0, vocab - 1)
    if attention.use_decode_kernel() and kernel.kernel_serves(table):
        rows = kernel.lookup_columns_kernel(table, flat)
        return rows.reshape(ids.shape + (width,))
    lanes = min(kernel.LANES, vocab)
    columns = table.T
    bits = jnp.dtype(f"uint{8 * jnp.dtype(table.dtype).itemsize}")
    lane = jax.lax.broadcasted_iota(jnp.int32, (width, lanes), 1)

    def one(token):
        # (the chunk is sliced before it is read as integers: the other way
        # round the chip's compiler writes the whole table out as integers)
        start = jnp.minimum(token // lanes * lanes, vocab - lanes)
        chunk = jax.lax.dynamic_slice(columns, (0, start), (width, lanes))
        chunk = jax.lax.bitcast_convert_type(chunk, bits)
        picked = jnp.where(lane == token - start, chunk, jnp.zeros((), bits))
        return jax.lax.bitcast_convert_type(jnp.max(picked, axis=1),
                                            table.dtype)

    return jax.lax.map(one, flat).reshape(ids.shape + (width,))


def embed_lookup(table, ids, form: str = "rows"):
    """The rows of ``table`` at ``ids``, by the access pattern ``form``
    names (:func:`lookup_form`; both give the same bits). Counted at trace
    time beside the attention paths, so ``stats()["attention_paths"]``
    says which form each compiled program took."""
    record_dispatch(f"embed_lookup_{form}")
    return lookup_columns(table, ids) if form == "columns" else table[ids]
