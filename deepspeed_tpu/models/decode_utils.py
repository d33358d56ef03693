"""Shared decode/padding position math for every autoregressive family
(gpt2 canonical decoder, llama, future archs).

The single source for the left-padding convention: positions start at 0
at each row's first real token, the padded prefix occupies cache slots
``[0, pad)``, and the decode-step mask combines the causal bound, the
per-row pad exclusion, and an optional sliding window (GPT-Neo local
attention). Model files must use these — a private re-implementation
desynchronizing any one of them produces wrong positions with no error.
"""

import jax.numpy as jnp


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
    a bucketed prefill (and idle serving slots, ``num_valid == 0``) routes
    to the reserved garbage block 0 instead — pads must never overwrite
    another sequence's blocks, and clamping them onto real rows would
    corrupt this sequence's own prefix."""
    B, T = positions.shape
    mb = block_tables.shape[-1]
    blk = jnp.clip(positions // block_size, 0, mb - 1)
    off = positions % block_size
    valid = jnp.arange(T)[None] < num_valid[:, None]
    return jnp.where(valid, jnp.take_along_axis(block_tables, blk, axis=1),
                     0), off
