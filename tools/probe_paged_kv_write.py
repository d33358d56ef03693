"""GPT-2's paged decode step's KV write ALONE, at a cell's shapes:
microseconds a layer call of the form until PR 55 (two XLA scatters of every
slot's row, an idle slot's into the garbage block, then the kernel:
``parent``), of the write call at every occupancy
(``ops/decode_attention.py:_paged_write``, a strip a writing row:
``kernel``) and of the tree's (the kernel's call puts the step's rows in
the pools itself, by the write call or, past ``paged_most_writers`` writing
rows, by the scatter: ``change``), beside the kernel with no write at all
(``attend``), at 3, 8, 16 and 32 busy rows of 32, bf16 and int8 pools.

    chiprun -- python tools/probe_paged_kv_write.py [--busy 3 8 16 32]

A program is ``--layers`` layer calls on donated pools under one
``fori_loop``, as a decode program's layers are a scan; a reading is the
host's clock over ``--reps`` such programs, a layer call's share of it, the
median of ``--sets``. The parent's form is built HERE from
``paged_write_slots`` and ``decode_attention_paged`` (the package holds no
switch between the forms). Every form runs the same queries and new rows on
the same pools, and the probe fails unless each writing form's output AND
its pools (the garbage block apart, which only a scatter writes) are the
parent's to the bit. The last columns set what the tree saves a layer call
against the parent's whole decode step at that occupancy (the cell's step
plus what the parent's layer calls cost more there). On a CPU it runs tiny
shapes under the Pallas interpreter (its test:
``tests/unit/test_probe_paged_kv_write.py``) and prints no time as a
device's.
"""

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.decode_utils import (paged_positions,
                                               paged_write_slots)
from deepspeed_tpu.ops import decode_attention as op
from deepspeed_tpu.ops.quantizer import quantize_rowwise

# serve-xl-chat: 48 layers, 32 slots, 25 heads of 64, a pool of 513 blocks
# of 32, rows of 10 blocks
CELL = dict(layers=48, slots=32, heads=25, dim=64, blocks=513, block_size=32,
            row_blocks=10, table_blocks=32)
# what serve-xl-chat's decode step takes at 2-3 busy rows (the ledger's
# PR 54 line, decode_step_p50_ms) and the busy rows it was read at: the
# parent's step at another occupancy is this plus what its layer calls
# cost more there
CELL_STEP_MS, CELL_STEP_BUSY = 5.68, 3


def inputs(seed: int, busy: int, quant: bool, layers, slots, heads, dim,
           blocks, block_size, row_blocks, table_blocks):
    """``(pools, (q, rows, tables, lengths))``: ``busy`` of the ``slots``
    batch rows on ``row_blocks`` blocks of their own, in no order, each
    some way into its last block; the others idle (length 0, a table of
    garbage blocks). ``rows`` are the step's new rows as the pools take
    them (``[B, 1, lanes]``; int8 with its scales)."""
    rng = np.random.default_rng(seed)
    if blocks < 1 + busy * row_blocks:
        raise ValueError(f"{busy} rows of {row_blocks} blocks need more "
                         f"than {blocks} pool blocks")
    lanes = heads * dim
    tables = np.zeros((slots, table_blocks), np.int32)
    lengths = np.zeros(slots, np.int32)
    own = 1 + rng.permutation(blocks - 1)[:busy * row_blocks].reshape(
        busy, row_blocks)
    for row, mine in zip(rng.permutation(slots)[:busy], own):
        tables[row, :row_blocks] = mine
        lengths[row] = (row_blocks - 1) * block_size + rng.integers(
            0, block_size)
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    bf16 = jnp.bfloat16
    q, k, v = (jax.random.normal(key, (slots, 1, heads, dim), bf16)
               for key in keys[:3])

    def pool(key, draw):
        # one layer's draw, turned a layer: no pool is made in float32
        return jax.jit(lambda: jnp.stack([jnp.roll(draw(key), i, axis=0)
                                          for i in range(layers)]))()

    shape = (blocks, block_size, lanes)
    rows = (k.reshape(slots, 1, lanes), v.reshape(slots, 1, lanes))
    if not quant:
        pools = tuple(pool(key, lambda key: jax.random.normal(
            key, shape, bf16)) for key in keys[3:5])
    else:
        wide = op.scale_lanes(heads)
        pools = tuple(pool(key, lambda key: jax.random.randint(
            key, shape, -127, 128, jnp.int8)) for key in keys[3:5]) + tuple(
            pool(key, lambda key: jax.random.uniform(
                key, shape[:2] + (wide,), jnp.float32, 0.01, 0.02))
            for key in jax.random.split(keys[5]))
        held = [quantize_rowwise(r.reshape(slots, 1, heads, dim))
                for r in rows]
        rows = tuple(h.reshape(slots, 1, lanes) for h, _ in held) + tuple(
            jnp.pad(s.reshape(slots, 1, heads), ((0, 0), (0, 0),
                                                 (0, wide - heads)))
            for _, s in held)
    return pools, (q, rows, jnp.asarray(tables), jnp.asarray(lengths))


def _attend(quant):
    return op.decode_attention_paged_int8 if quant else \
        op.decode_attention_paged


def parent_call(q, pools, rows, tables, lengths, layer, work):
    """The form until PR 55: every slot's row scattered (an idle slot's
    onto the garbage block), then the kernel over the written pools."""
    block_size = pools[0].shape[2]
    blk, off = paged_write_slots(tables, paged_positions(lengths, 1),
                                 jnp.ones_like(lengths), block_size)
    pools = tuple(p.at[layer, blk, off].set(r) for p, r in zip(pools, rows))
    return _attend(len(pools) == 4)(q, *pools, tables, lengths, layer,
                                    work=work[:2]), pools


def kernel_call(q, pools, rows, tables, lengths, layer, work):
    """The write call at every occupancy (``_paged_write``: a strip a
    writing row), then the kernel: one branch of the tree's."""
    pools = op._paged_write(pools, rows, layer, work[2:])
    return _attend(len(pools) == 4)(q, *pools, tables, lengths, layer,
                                    work=work[:2]), pools


def change_call(q, pools, rows, tables, lengths, layer, work):
    """The tree's: the kernel's call takes the rows and puts them in the
    pools, by the write call or, past ``paged_most_writers`` writing rows,
    by the scatter (one ``lax.cond`` on the write list's count)."""
    return _attend(len(pools) == 4)(q, *pools, tables, lengths, layer,
                                    work=work, rows=rows)


def attend_call(q, pools, rows, tables, lengths, layer, work):
    """The kernel alone, nothing written: what a form's write costs is its
    reading less this one."""
    return _attend(len(pools) == 4)(q, *pools, tables, lengths, layer,
                                    work=work[:2]), pools


FORMS = {"attend": attend_call, "parent": parent_call, "kernel": kernel_call,
         "change": change_call}


def program(call, layers: int):
    """One decode program's worth: a call a layer on the donated pools, the
    work list made once before them."""
    def run(pools, q, rows, tables, lengths):
        block_size = pools[0].shape[2]
        work = op.paged_step_work(lengths, tables, 1, block_size,
                                  valid=jnp.ones_like(lengths))

        def layer(i, carry):
            total, pools = carry
            with jax.named_scope("attn._paged_kv_attend"):
                y, pools = call(q, pools, rows, tables, lengths, i, work)
            return total + y.astype(jnp.float32), pools

        return jax.lax.fori_loop(
            0, layers, layer, (jnp.zeros(q.shape, jnp.float32), pools))
    return jax.jit(run, donate_argnums=0)


def digest(pool):
    """``[layers, blocks]`` uint32: a sum of every block's bits, each
    weighted by its row in the block. Two pools of equal digests hold the
    same rows in the same places, as far as a sum can say (the kernel's
    tests compare the pools themselves; two whole XL pools beside the live
    ones do not fit the chip)."""
    bits = jax.lax.bitcast_convert_type(pool, {
        1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[pool.dtype.itemsize])
    weight = 7 + 31 * jnp.arange(pool.shape[2], dtype=jnp.uint32)
    return jnp.sum(bits.astype(jnp.uint32) * weight[:, None], axis=(2, 3),
                   dtype=jnp.uint32)


def measure(run, pools, args, reps: int, sets: int):
    """``(seconds a program, the first program's output sum, the digests
    of the pools after it)``: the median of ``sets`` readings of ``reps``
    calls behind the one that compiles."""
    first, pools = jax.block_until_ready(run(pools, *args))    # compiles
    first = np.asarray(first)
    after = [np.asarray(jax.jit(digest)(p)) for p in pools]
    readings = []
    for _ in range(sets):
        start = time.perf_counter()
        for _ in range(reps):
            total, pools = run(pools, *args)
        jax.block_until_ready((total, pools))
        readings.append((time.perf_counter() - start) / reps)
    return statistics.median(readings), first, after


def probe(busy_counts, layers, reps, sets, seed, quants=(False, True),
          sizes=None, forms=tuple(FORMS)):
    """The table's rows, a form a busy count and pool dtype: ``{"kv",
    "form", "busy", "us_a_layer_call", "same_as_parent"}``
    (``same_as_parent``: the form's first program gave the parent's output
    to the bit and, if it writes, the parent's pools but for the garbage
    block, and left the garbage block as it was)."""
    sizes = {**CELL, **(sizes or {})}
    sizes["layers"] = layers
    rows = []
    for quant in quants:
        for busy in busy_counts:
            want = None
            for name, call in ((f, FORMS[f]) for f in forms):
                pools, args = inputs(seed, busy, quant, **sizes)
                before = [np.asarray(jax.jit(digest)(p)) for p in pools]
                seconds, first, after = measure(program(call, layers), pools,
                                                args, reps, sets)
                same = None
                if name == "parent":
                    want = (first, after)
                elif name != "attend":
                    # the garbage block as it was, or (the tree's form at
                    # a step so crowded that it scatters) as the parent's
                    same = (np.array_equal(first, want[0]) and all(
                        np.array_equal(a[:, 1:], b[:, 1:])
                        and (np.array_equal(a[:, 0], g[:, 0])
                             or (name == "change"
                                 and np.array_equal(a[:, 0], b[:, 0])))
                        for a, b, g in zip(after, want[1], before)))
                rows.append({"kv": "int8" if quant else "bf16", "form": name,
                             "busy": busy,
                             "us_a_layer_call": 1e6 * seconds / layers,
                             "same_as_parent": same})
                del pools
            want = None
    return rows


def table(rows, layers: int):
    """A line a busy count and dtype: each form's us a layer call, what the
    change saves a call, and that saving over ``layers`` calls as a share
    of the parent's decode step at that occupancy."""
    by = {(r["kv"], r["busy"], r["form"]): r["us_a_layer_call"] for r in rows}
    at_cell = {kv: by.get((kv, CELL_STEP_BUSY, "parent"))
               for kv in {r["kv"] for r in rows}}
    out = []
    for kv, busy in sorted({(r["kv"], r["busy"]) for r in rows}):
        attend, parent, kernel, change = (by[kv, busy, f] for f in FORMS)
        line = {"kv": kv, "busy": busy, "attend_us": attend,
                "parent_us": parent, "kernel_us": kernel, "change_us": change,
                "parent_write_us": parent - attend,
                "kernel_write_us": kernel - attend,
                "change_write_us": change - attend,
                "saved_us": parent - change}
        if at_cell[kv] is not None:
            step_ms = CELL_STEP_MS + layers * (parent - at_cell[kv]) / 1e3
            line["parent_step_ms"] = step_ms
            line["saved_share_of_step"] = (
                100 * layers * (parent - change) / 1e3 / step_ms)
        out.append(line)
    return out


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--busy", type=int, nargs="+", default=[3, 8, 16, 32])
    parser.add_argument("--layers", type=int, default=CELL["layers"])
    parser.add_argument("--reps", type=int, default=20)
    parser.add_argument("--sets", type=int, default=5)
    parser.add_argument("--seed", type=int, default=55)
    parser.add_argument("--kv", nargs="+", default=["bf16", "int8"],
                        choices=["bf16", "int8"])
    parser.add_argument("--out", default="chiprun_out/probe_paged_kv_write"
                                         ".json")
    args = parser.parse_args(argv)
    device = jax.devices()[0]
    if device.platform != "tpu":
        # a time from anything else is no device metric (the rehearsal is
        # tests/unit/test_probe_paged_kv_write.py)
        raise SystemExit(f"no TPU here ({device.platform}): the probe's "
                         "times are a chip's or nothing")
    rows = probe(args.busy, args.layers, args.reps, args.sets, args.seed,
                 quants=tuple(kv == "int8" for kv in args.kv))
    lines = table(rows, args.layers)
    print(f"{'kv':5s} {'busy':>4s} {'attend':>8s} {'parent':>8s} "
          f"{'kernel':>8s} {'change':>8s} {'saved us':>9s} "
          f"{'parent step ms':>15s} {'saved % step':>13s}")
    for ln in lines:
        print(f"{ln['kv']:5s} {ln['busy']:4d} {ln['attend_us']:8.2f} "
              f"{ln['parent_us']:8.2f} {ln['kernel_us']:8.2f} "
              f"{ln['change_us']:8.2f} {ln['saved_us']:9.2f} "
              f"{ln.get('parent_step_ms', 0):15.3f} "
              f"{ln.get('saved_share_of_step', 0):13.2f}")
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device_kind": device.device_kind, "sizes": CELL,
                   "layers": args.layers, "rows": rows, "table": lines}, f,
                  indent=1)
    if not all(r["same_as_parent"] for r in rows
               if r["form"] in ("kernel", "change")):
        raise SystemExit("a form's output or pools part from the parent's")


if __name__ == "__main__":
    main()
