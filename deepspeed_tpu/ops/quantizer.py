"""Quantization ops.

Capability parity with the reference quantization kernels
(``csrc/quantization/{quantize.cu,dequantize.cu,fake_quantizer.cu}`` exposed
via ``op_builder/quantizer.py``): grouped symmetric/asymmetric int8/int4
quantize/dequantize and training-time fake-quant (MoQ). XLA fuses the
elementwise math; a Pallas path adds stochastic rounding on TPU.
"""

import functools

import jax
import jax.numpy as jnp


def _group_reshape(x, num_groups):
    n = x.size
    if n % num_groups:
        raise ValueError(f"size {n} not divisible by num_groups {num_groups}")
    return x.reshape(num_groups, n // num_groups)


def quantize(x, num_groups: int = 1, num_bits: int = 8, symmetric: bool = True):
    """Grouped quantization → (q_values int8, scale[, zero_point]).

    Symmetric: q = round(x / scale), scale = absmax / qmax.
    Asymmetric: q = round((x - min) / scale) - qmax - 1.
    """
    qmax = 2.0 ** (num_bits - 1) - 1
    g = _group_reshape(x.astype(jnp.float32), num_groups)
    if symmetric:
        scale = jnp.max(jnp.abs(g), axis=1, keepdims=True) / qmax
        scale = jnp.where(scale == 0, 1.0, scale)
        q = jnp.clip(jnp.round(g / scale), -qmax - 1, qmax)
        return q.astype(jnp.int8).reshape(x.shape), scale[:, 0]
    lo = jnp.min(g, axis=1, keepdims=True)
    hi = jnp.max(g, axis=1, keepdims=True)
    scale = (hi - lo) / (2 * qmax + 1)
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round((g - lo) / scale) - qmax - 1, -qmax - 1, qmax)
    return q.astype(jnp.int8).reshape(x.shape), scale[:, 0], lo[:, 0]


def dequantize(q, scale, zero_point=None, num_groups: int = 1,
               num_bits: int = 8, dtype=jnp.float32):
    qmax = 2.0 ** (num_bits - 1) - 1
    g = _group_reshape(q.astype(jnp.float32), num_groups)
    if zero_point is None:
        out = g * scale[:, None]
    else:
        out = (g + qmax + 1) * scale[:, None] + zero_point[:, None]
    return out.astype(dtype).reshape(q.shape)


def quantize_chunks(x, group_size: int = 1024):
    """Symmetric int8 quantization of a flat vector with one scale per
    ``group_size``-element chunk (the wire format of the quantized
    collectives in ``runtime/comm/quantized.py``).

    Unlike :func:`quantize`, the input need not divide evenly: the vector
    is zero-padded up to a chunk multiple (zeros quantize to 0, so padding
    is exact). Returns ``(q int8[padded], scales f32[n_chunks])``.
    """
    flat = x.reshape(-1).astype(jnp.float32)
    pad = (-flat.size) % group_size
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
    g = flat.reshape(-1, group_size)
    scale = jnp.max(jnp.abs(g), axis=1, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
    return q.reshape(-1), scale[:, 0]


def dequantize_chunks(q, scales, group_size: int = 1024, size=None,
                      dtype=jnp.float32):
    """Inverse of :func:`quantize_chunks`; ``size`` trims the padding."""
    g = q.reshape(-1, group_size).astype(jnp.float32) * scales[:, None]
    flat = g.reshape(-1).astype(dtype)
    return flat if size is None else flat[:size]


def quantize_rowwise(x, axis: int = -1):
    """Symmetric int8 quantization with one f32 scale per row along
    ``axis`` — the paged-KV block codec (one scale per token x head,
    riding a side pool indexed by the same block table the int8 pool
    uses). Same absmax/127 chunk-scale formula as
    :func:`quantize_chunks`, shaped for in-place pool scatters instead
    of a flat wire. All-zero rows keep scale 1 so they round-trip to
    exact zeros (the garbage block stays inert).

    Returns ``(q int8 like x, scale f32 with axis collapsed to 1)``.
    """
    f = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(f), axis=axis, keepdims=True)
    scale = jnp.where(absmax == 0, 1.0, absmax / 127.0)
    q = jnp.clip(jnp.round(f / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_rowwise(q, scale, dtype=jnp.float32):
    """Inverse of :func:`quantize_rowwise` (``scale`` broadcasts over
    the collapsed axis)."""
    return (q.astype(jnp.float32) * scale).astype(dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def fake_quantize(x, num_groups: int = 1, num_bits: int = 8, symmetric: bool = True):
    """Quantize→dequantize in one step with a straight-through gradient
    (reference ``fake_quantizer.cu`` used by MoQ training)."""
    if symmetric:
        q, s = quantize(x, num_groups, num_bits, True)
        return dequantize(q, s, num_groups=num_groups, num_bits=num_bits,
                          dtype=x.dtype)
    q, s, z = quantize(x, num_groups, num_bits, False)
    return dequantize(q, s, z, num_groups=num_groups, num_bits=num_bits,
                      dtype=x.dtype)


def _fq_fwd(x, num_groups, num_bits, symmetric):
    return fake_quantize(x, num_groups, num_bits, symmetric), None


def _fq_bwd(num_groups, num_bits, symmetric, _, g):
    return (g,)  # straight-through estimator


fake_quantize.defvjp(_fq_fwd, _fq_bwd)


def stochastic_quantize_tpu(x, seed: int, num_bits: int = 8):
    """Pallas TPU kernel: symmetric int8 quantization with stochastic
    rounding (used by the quantized-collective path)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if num_bits != 8:
        raise NotImplementedError("stochastic path supports int8")

    def kernel(x_ref, seed_ref, q_ref, scale_ref):
        pltpu.prng_seed(seed_ref[0])
        absmax = jnp.max(jnp.abs(x_ref[:]))
        scale = absmax / 127.0
        scale = jnp.where(scale == 0, 1.0, scale)
        scale_ref[0, 0] = scale
        scaled = x_ref[:] / scale
        # manual stochastic rounding: floor(x + u), u ~ U[0,1) from the PRNG
        # (pltpu.stochastic_round only targets bf16/fp8 dtypes)
        bits = pltpu.bitcast(pltpu.prng_random_bits(scaled.shape), jnp.uint32)
        # top 24 bits → int32 → f32 (Mosaic has no uint32→f32 cast)
        u = (bits >> 8).astype(jnp.int32).astype(jnp.float32) * (1.0 / 16777216.0)
        q_ref[:] = jnp.clip(jnp.floor(scaled + u), -128, 127).astype(jnp.int8)

    q, scale = pl.pallas_call(
        kernel,
        name="stochastic_quantize",
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=(pl.BlockSpec(memory_space=pltpu.VMEM),
                   pl.BlockSpec(memory_space=pltpu.SMEM)),
        out_shape=(jax.ShapeDtypeStruct(x.shape, jnp.int8),
                   jax.ShapeDtypeStruct((1, 1), jnp.float32)),
    )(x, jnp.asarray([seed], jnp.int32))
    return q, scale[0, 0]
