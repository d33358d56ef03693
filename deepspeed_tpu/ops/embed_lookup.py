"""Token lookup through a table's transposed view, as a Pallas TPU kernel.

The chip lays a ``[vocab, width]`` table whose width is no whole number of
128-lane registers (GPT-2 XL: 1600) with the VOCABULARY minor, and
``table[ids]`` then costs a row-major copy of the whole table in every
program. This kernel reads the table as it lies: the transposed view is a
bitcast, token ``i`` takes the ``[width, 128]`` block that holds its column
(``ids`` by scalar prefetch, the next block in flight while this one is
read) and selects its lane. ``models/decode_utils.py`` chooses it
(``lookup_form``) and holds the plain XLA twin (``lookup_columns``), which
is its oracle and what every other backend runs.

In the GPT-2 XL decode program on a v5e (32 tokens) the kernel takes under
40 us (it is not among the program's first ten operations) where the XLA
loop takes 73 and the copy 450 (PERF.md section 6, PR 42).
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.utils.compat import tpu_compiler_params

# A TPU register holds 128 lanes of the minor dimension: a read of the table
# as it lies starts on a multiple of this.
LANES = 128


def _kernel(ids_ref, block_ref, out_ref, *, lanes):
    i = pl.program_id(0)
    slot = i % LANES

    @pl.when(slot == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    # the selection works on the bits (float32 holds a bfloat16 exactly, and
    # a sum over zeros moves no bit): an inf, a nan or a -0.0 comes back as
    # it went in, which a multiply by 0 and 1 would not give
    bits = pltpu.bitcast(block_ref[...].astype(jnp.float32), jnp.int32)
    lane = lax.broadcasted_iota(jnp.int32, bits.shape, 1)
    column = jnp.sum(jnp.where(lane == ids_ref[i] % lanes, bits, 0),
                     axis=1, keepdims=True)
    # token i's column becomes lane i of the output tile, which stays in
    # VMEM for the 128 tokens that share it
    out_lane = lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
    out_ref[...] = jnp.where(out_lane == slot, column, out_ref[...])


def kernel_serves(table) -> bool:
    """The kernel moves a value through float32: bfloat16 and float32
    tables come back bit for bit."""
    return table.dtype in (jnp.bfloat16, jnp.float32)


def lookup_columns_kernel(table, ids):
    """``table[ids]``, bit for bit (``kernel_serves(table)``), for
    ``table`` ``[vocab, width]`` and ``ids`` ``int32 [n]`` inside it."""
    vocab, width = table.shape
    lanes = min(LANES, vocab)
    n, = ids.shape
    out = pl.pallas_call(
        functools.partial(_kernel, lanes=lanes),
        name="embed_lookup_columns",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n,),
            # (the last block of a vocabulary that is no multiple of 128
            # hangs over the table's edge: its token's lane is inside)
            in_specs=[pl.BlockSpec((width, lanes),
                                   lambda i, ids: (0, ids[i] // lanes))],
            out_specs=pl.BlockSpec((width, LANES),
                                   lambda i, ids: (0, i // LANES))),
        out_shape=jax.ShapeDtypeStruct((width, pl.cdiv(n, LANES) * LANES),
                                       jnp.int32),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("arbitrary",)),
    )(ids, table.T)
    rows = out[:, :n].T
    if table.dtype == jnp.bfloat16:  # its bits are float32's upper half
        rows = lax.shift_right_logical(rows, 16).astype(jnp.uint16)
    return lax.bitcast_convert_type(rows, table.dtype)
