"""The latent (MLA) decode kernel ALONE, at a cell's shapes: microseconds a
layer call and the share of the bytes' time, the form until PR 60 (a tile's
16 blocks each a ``BlockSpec`` operand of the Pallas pipeline: ``parent``,
kept here) against the tree's (``ops/latent_decode_attention.py``: the
kernel copies its own tiles) at 512 / 1024 / 2048 keys a tile.

    chiprun -- python tools/probe_latent_decode.py [--cells dsv2lite ling3]

A program is ``layers`` kernel calls on one pool ``[layers, blocks, 32,
640]`` bfloat16 behind ONE work list, as a decode program's latent layers
are; a reading is the host's clock over ``--reps`` such programs, a layer
call's share of it, the median of ``--sets``. The bound is every live
token's row of ``rank + rope`` values read once a layer (1,152 B at 512 +
64 in bfloat16) over the chip's HBM bandwidth: what the parked reader's
function counts (``perfbench/kernels/mla_decode.py``). Every form attends
the same queries over the same pool and tables (busy rows on scrambled
blocks of their own, idle slots on the garbage block), and the probe fails
if a form's output parts from the parent's by more than ``GAP`` of the
largest value (a tile of another length sums in another order). On a CPU it
runs tiny shapes under the Pallas interpreter (its test:
``tests/unit/test_probe_latent_decode.py``) and prints no time as a
device's.
"""

import argparse
import functools
import json
import os
import statistics
import sys
import time
from unittest import mock

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops import latent_decode_attention as op
from deepspeed_tpu.ops.decode_attention import (NEG_INF, paged_step_lengths,
                                                paged_work_list)
from deepspeed_tpu.utils.compat import tpu_compiler_params
from perfbench.flops import peaks

# a decode step of each cell at its rated load: the rows busy and the
# contexts they hold (serve-dsv2lite-mla-longdoc: 5-6 rows of prompts ~6,144
# in 1,024-15,872; serve-ling3-kda-longgen: ~105 rows of 2-3k in 64-11,264)
CELLS = {
    "dsv2lite": dict(heads=16, slots=48, busy=6, layers=6, per_row=512,
                     blocks=16385, mean=6300, most=16000),
    "ling3": dict(heads=32, slots=128, busy=105, layers=1, per_row=352,
                  blocks=1 + 128 * 352, mean=2500, most=11000),
}
SHAPE = dict(block_size=32, lanes=640, rank=512, rope=64)
SCALE = 0.1147
PARENT_TILE_KEYS = 512
# what the forms may part by, of the output's largest value: bfloat16 keeps
# eight bits, and a tile of another length rounds another partial sum
GAP = 2.0 ** -6


# ---------------------------------------------------------------------------
# the parent's form (PR 45's kernel, as the tree had it until PR 60): the
# tile's blocks the pipeline's own operands, one ``BlockSpec`` each

def _parent_kernel(row_ref, first_ref, tables_ref, lens_ref, at_ref, q_ref,
                   *rest, scale, bs, heads, rank, tile, batch):
    blocks = rest[:tile]
    _, o_ref, m_scr, l_scr, acc_scr = rest[tile:]
    keys = tile * bs
    step = pl.program_id(0)
    bi = row_ref[step]
    ji = step - first_ref[bi]
    idx = lens_ref[bi]
    owns = step < first_ref[batch]

    @pl.when(jnp.logical_not(owns))
    def _idle():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(owns & (ji == 0))
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(owns)
    def _tile():
        q = q_ref[...].reshape(heads, q_ref.shape[-1])
        rows = jnp.concatenate([r[...] for r in blocks], axis=0)
        s = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + ji * keys
        s = jnp.where(pos <= idx, s, NEG_INF)
        m_prev = m_scr[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scr[:, 0:1] + jnp.sum(p, axis=1, keepdims=True)
        at = jax.lax.broadcasted_iota(jnp.int32, (keys, 1), 0) + ji * keys
        v = jnp.where(at <= idx, rows[:, :rank], jnp.zeros_like(
            rows[:, :rank]))
        acc_scr[:] = acc_scr[:] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(owns & (step + 1 == first_ref[bi + 1]))
    def _finish():
        l = l_scr[:, 0:1]
        out = acc_scr[:] / jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = out.reshape(o_ref.shape).astype(o_ref.dtype)


def _parent_tile(block_size):
    return max(1, PARENT_TILE_KEYS // block_size)


def parent_work(lengths, tables, block_size, lanes):
    del lanes
    return paged_work_list(
        paged_step_lengths(lengths, tables, 1), 1, block_size,
        tables.shape[-1], tile_blocks=_parent_tile(block_size))


def parent_attend(q, pool, tables, lengths, layer, *, rank, scale, work):
    b, _, heads, lanes = q.shape
    bs = pool.shape[2]
    mb = tables.shape[-1]
    tile = _parent_tile(bs)
    row_of, first = work

    def pool_spec(i):
        def index(s, row_of, first, tab, ln, at):
            row = row_of[s]
            j = (s - first[row]) * tile + i
            live = jnp.minimum((ln[row] + bs) // bs, mb)
            return (at[0], tab[row, jnp.where(j < live, j,
                                              jnp.maximum(j - tile, 0))],
                    0, 0)
        return pl.BlockSpec((None, None, bs, lanes), index)

    def row_spec(width):
        return pl.BlockSpec((1, 1, heads, width),
                            lambda s, row_of, first, tab, ln, at:
                            (row_of[s], 0, 0, 0))

    out_shape = jax.ShapeDtypeStruct((b, 1, heads, rank), q.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(jnp.maximum(first[b], 1),),
        in_specs=[row_spec(lanes)] + [pool_spec(i) for i in range(tile)]
        + [pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=row_spec(rank),
        scratch_shapes=[
            pltpu.VMEM((heads, 128), jnp.float32),
            pltpu.VMEM((heads, 128), jnp.float32),
            pltpu.VMEM((heads, rank), jnp.float32),
        ],
    )
    kernel = functools.partial(_parent_kernel, scale=float(scale), bs=bs,
                               heads=heads, rank=rank, tile=tile, batch=b)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases={6 + tile: 0},
        compiler_params=tpu_compiler_params(
            dimension_semantics=("arbitrary",)),
    )(row_of, first, jnp.asarray(tables, jnp.int32),
      jnp.asarray(lengths, jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q, *([pool] * tile),
      jnp.zeros(out_shape.shape, out_shape.dtype))


# ---------------------------------------------------------------------------
def _tile_keys(keys):
    """The tree's plan at ``keys`` a tile: it reads ``LATENT_TILE_KEYS``
    when it is made, which is while a program is traced."""
    return mock.patch.object(op, "LATENT_TILE_KEYS", keys)


def tree_form(keys: int):
    def work(lengths, tables, block_size, lanes):
        with _tile_keys(keys):
            return op.latent_step_work(lengths, tables, block_size, lanes)

    def attend(q, pool, tables, lengths, layer, *, rank, scale, work):
        with _tile_keys(keys):
            return op.decode_attention_latent(
                q, pool, tables, lengths, layer, rank=rank, scale=scale,
                work=work)

    return work, attend


def forms(tiles=(512, 1024, 2048)) -> dict:
    """A form's name -> ``(its work list, its call)``."""
    out = {"parent": (parent_work, parent_attend)}
    for keys in tiles:
        out[f"tile-{keys}"] = tree_form(keys)
    return out


# ---------------------------------------------------------------------------
def least_seconds(live_tokens: int, rank: int, rope: int,
                  bytes_per_s: float) -> float:
    """The bytes' time of one layer call: every live token's row of ``rank
    + rope`` bfloat16 values read once."""
    return live_tokens * (rank + rope) * 2 / bytes_per_s


def inputs(seed: int, *, heads, slots, busy, layers, per_row, blocks, mean,
           most, block_size, lanes, rank, rope):
    """``(q, pool, tables, lengths)``: ``busy`` of the ``slots`` rows hold
    contexts drawn about ``mean`` (at least 1, at most ``most``) on blocks
    of their own in no order, the others are idle on the garbage block; the
    pool's rows are ``[c | k_pe | zeros]`` as the model writes them."""
    rng = np.random.default_rng(seed)
    lengths = np.zeros(slots, np.int32)
    rows = rng.permutation(slots)[:busy]
    lengths[rows] = np.clip(rng.gamma(2.0, mean / 2.0, busy), 1,
                            most).astype(np.int32)
    tables = np.zeros((slots, per_row), np.int32)
    free = 1 + rng.permutation(blocks - 1)
    taken = 0
    for r in rows:
        n = lengths[r] // block_size + 1
        tables[r, :n] = free[taken:taken + n]
        taken += n
    keys = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31)), 2)
    dtype = jnp.bfloat16
    live = (jnp.arange(lanes) < rank + rope).astype(dtype)
    pool = jax.random.normal(keys[0], (layers, blocks, block_size, lanes),
                             dtype) * live
    q = jax.random.normal(keys[1], (slots, 1, heads, lanes), dtype) * live
    return q, pool, jnp.asarray(tables), jnp.asarray(lengths)


def program(form, layers: int, *, rank: int, scale: float = SCALE):
    """One decode program's worth: a call a layer behind one work list."""
    make_work, attend = form

    def run(q, pool, tables, lengths):
        work = make_work(lengths, tables, pool.shape[2], pool.shape[3])
        outs = []
        for layer in range(layers):
            with jax.named_scope("attn._latent_kv_attend"):
                outs.append(attend(q, pool, tables, lengths, layer,
                                   rank=rank, scale=scale, work=work))
        return jnp.stack(outs)
    return jax.jit(run)


@jax.jit
def _apart(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))


def measure(run, args, reps: int, sets: int, want=None):
    """``(seconds a program, the first program's output, its gap from
    ``want``)``: the median of ``sets`` readings of ``reps`` calls behind
    the one that compiles."""
    out = jax.block_until_ready(run(*args))                  # compiles
    gap = 0.0 if want is None else float(_apart(out, want))
    readings = []
    for _ in range(sets):
        start = time.perf_counter()
        for _ in range(reps):
            last = run(*args)
        jax.block_until_ready(last)
        readings.append((time.perf_counter() - start) / reps)
    return statistics.median(readings), out, gap


def probe(cells, reps, sets, seed, bytes_per_s, tiles=(512, 1024, 2048),
          sizes=None, more_forms=None):
    """The table's rows, a form a cell: ``{"cell", "form", "live_tokens",
    "us_a_layer_call", "share_of_bytes_time", "gap"}`` (the gap: the
    largest distance of the form's output from the parent's, of the
    parent's largest value)."""
    rows = []
    for cell in cells:
        shape = {**CELLS.get(cell, {}), **SHAPE, **(sizes or {})}
        args = inputs(seed, **shape)
        busy = np.asarray(args[2])[:, 0] != 0
        # (a step's query attends its own row too)
        live = int((np.asarray(args[3])[busy] + 1).sum())
        layers = shape["layers"]
        want = None
        for name, form in {**forms(tiles), **(more_forms or {})}.items():
            seconds, out, gap = measure(
                program(form, layers, rank=shape["rank"]), args, reps, sets,
                want)
            want = out if want is None else want
            least = least_seconds(live, shape["rank"], shape["rope"],
                                  bytes_per_s)
            rows.append({
                "cell": cell, "form": name, "live_tokens": live,
                "us_a_layer_call": 1e6 * seconds / layers,
                "share_of_bytes_time": 100 * least * layers / seconds,
                "gap": gap})
    return rows


def show(rows):
    print(f"{'cell':9s} {'form':10s} {'live tokens':>11s} "
          f"{'us/layer call':>14s} {'% of bytes time':>16s} {'gap':>9s}")
    for r in rows:
        print(f"{r['cell']:9s} {r['form']:10s} {r['live_tokens']:11d} "
              f"{r['us_a_layer_call']:14.2f} "
              f"{r['share_of_bytes_time']:16.2f} {r['gap']:9.2e}")


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--cells", nargs="+", default=list(CELLS),
                        choices=list(CELLS))
    parser.add_argument("--reps", type=int, default=20)
    parser.add_argument("--sets", type=int, default=5)
    parser.add_argument("--seed", type=int, default=60)
    parser.add_argument("--tiles", type=int, nargs="+",
                        default=[512, 1024, 2048])
    parser.add_argument("--busy", type=int, default=None,
                        help="busy rows, in place of each cell's own")
    parser.add_argument("--out", default="chiprun_out/probe_latent_decode"
                                         ".json")
    args = parser.parse_args(argv)
    device = jax.devices()[0]
    # an unknown kind (the CPU) is an error: a time from it is no device
    # metric (the rehearsal is tests/unit/test_probe_latent_decode.py)
    bytes_per_s = peaks(device.device_kind)["hbm_bytes_per_s"]
    sizes = {} if args.busy is None else {"busy": args.busy}
    rows = probe(args.cells, args.reps, args.sets, args.seed, bytes_per_s,
                 tuple(args.tiles), sizes=sizes)
    show(rows)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device_kind": device.device_kind, "cells": CELLS,
                   "shape": SHAPE, "busy": args.busy, "rows": rows}, f,
                  indent=1)
    worst = max(r["gap"] for r in rows)
    if worst > GAP:
        raise SystemExit(f"the forms part by {worst:.2e}")


if __name__ == "__main__":
    main()
