"""The Mamba-2 decode state update ALONE, at a cell's shapes: microseconds a
layer call and the share of the bytes' time, the layout until PR 51 (a
head's state ``[P, N]``, the state size along the lanes: ``parent``) against
the tree's (``[H P / 128, N, 128]``, ``ops/ssm_state_update.py``) in tiles of
4 / 8 / 16 / 32 lane groups.

    chiprun -- python tools/probe_ssm_state_update.py [--busy 11 20 40]

A program is ``--layers`` kernel calls on one donated pool, as a decode
program's Mamba layers are; a reading is the host's clock over ``--reps``
such programs, a layer call's share of it, the median of ``--sets``. The
bound is the busy rows' states read once and written once (``2 x busy x H P
N x itemsize`` bytes) over the chip's HBM bandwidth: what
``perfbench/kernels/ssm_state_update.py`` counts. Both forms run the same
``a``, ``delta x``, ``B``, ``C`` on the same states (the pool converted
between the layouts), and the probe fails if their ``y`` part. On a CPU it
runs tiny shapes under the Pallas interpreter (its test:
``tests/unit/test_probe_ssm_state_update.py``) and prints no time as a
device's.
"""

import argparse
import functools
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops import ssm_state_update as op
from deepspeed_tpu.utils.compat import tpu_compiler_params
from perfbench.flops import peaks

CELL = dict(layers=36, slots=64, heads=64, width=64, n=128)


# ---------------------------------------------------------------------------
# the parent's form (PR 49's kernel, as the tree had it until PR 51): the
# pool's values as [H, P, N], a grid step a busy row and a tile of heads

def _parent_kernel(order_ref, count_ref, slots_ref, layer_ref, a_ref, dx_ref,
                   b_ref, c_ref, pool_ref, y_ref, out_ref, *, tile):
    del order_ref, count_ref, slots_ref, layer_ref
    bv = b_ref[...]                                          # [1, N]
    cv = c_ref[...]
    for h in range(tile):
        state = (a_ref[:, h:h + 1] * pool_ref[h].astype(jnp.float32)
                 + dx_ref[:, h:h + 1] * bv)                  # [P, N]
        y_ref[:, h:h + 1] = jnp.sum(state * cv, axis=-1, keepdims=True)
        out_ref[h] = state.astype(out_ref.dtype)


def parent_update(pool, layer, slot_rows, a, dx, b, c, work=None,
                  head_tile: int = 32):
    rows, heads, width = dx.shape
    n = b.shape[-1]
    tile = min(head_tile, heads)
    tiles = heads // tile
    f32 = jnp.float32
    order, count = op.busy_rows(slot_rows) if work is None else work
    a_t = a.astype(f32).reshape(rows, tiles, 1, tile)
    dx_t = dx.astype(f32).reshape(rows, tiles, tile, width).swapaxes(2, 3)
    row = lambda i, j, order, count, slots, at: (order[i], j, 0, 0)
    vec = lambda i, j, order, count, slots, at: (order[i], 0, 0)
    state = lambda i, j, order, count, slots, at: (
        at[0], slots[order[i]], j, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(jnp.maximum(count[0], 1), tiles),
        in_specs=[pl.BlockSpec((None, None, 1, tile), row),
                  pl.BlockSpec((None, None, width, tile), row),
                  pl.BlockSpec((None, 1, n), vec),
                  pl.BlockSpec((None, 1, n), vec),
                  pl.BlockSpec((None, None, tile, width, n), state)],
        out_specs=[pl.BlockSpec((None, None, width, tile), row),
                   pl.BlockSpec((None, None, tile, width, n), state)],
    )
    y_t, pool = pl.pallas_call(
        functools.partial(_parent_kernel, tile=tile),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((rows, tiles, width, tile), f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={8: 1},
        compiler_params=tpu_compiler_params(
            dimension_semantics=("arbitrary", "arbitrary")),
    )(order, count, jnp.asarray(slot_rows, jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), a_t, dx_t,
      b.astype(f32)[:, None], c.astype(f32)[:, None], pool)
    y = y_t.swapaxes(2, 3).reshape(rows, heads, width)
    return jnp.where((slot_rows != 0)[:, None, None], y, 0.0), pool


def forms(tiles=(4, 8, 16, 32)) -> dict:
    """A form's name -> ``(update, whether its pool lies in lane groups)``."""
    out = {"parent": (parent_update, False)}
    for tile in tiles:
        out[f"lanes-{tile}"] = (functools.partial(
            op.state_update_kernel, group_tile=tile), True)
    return out


# ---------------------------------------------------------------------------
def least_seconds(busy: int, heads: int, width: int, n: int, itemsize: int,
                  bytes_per_s: float) -> float:
    """The bytes' time of one layer call: every busy row's state read once
    and written once."""
    return 2 * busy * heads * width * n * itemsize / bytes_per_s


def inputs(seed: int, busy: int, layers: int, slots: int, heads: int,
           width: int, n: int, dtype=jnp.bfloat16):
    """A pool as the scan writes it (``[layers, 1 + slots, H, P, N]``
    VALUES, whatever the layout), ``busy`` of the ``slots`` batch rows on
    slots of their own in no order, and a step's terms."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    pool = jax.random.normal(keys[0], (layers, 1 + slots, heads, width, n),
                             dtype)
    rng = np.random.default_rng(seed)
    rows = np.zeros(slots, np.int32)
    rows[rng.permutation(slots)[:busy]] = 1 + rng.permutation(slots)[:busy]
    f32 = jnp.float32
    return pool, (jnp.asarray(rows),
                  jax.random.uniform(keys[1], (slots, heads), f32, 0.5, 1.0),
                  0.1 * jax.random.normal(keys[2], (slots, heads, width),
                                          f32),
                  jax.random.normal(keys[3], (slots, n), f32),
                  jax.random.normal(keys[4], (slots, n), f32))


def as_form_lies(pool, in_lanes: bool):
    """The probe's pool of VALUES in a form's layout, at the allocation's
    shape."""
    return op.to_lanes(pool).reshape(pool.shape) if in_lanes else pool


def program(update, layers: int):
    """One decode program's worth: a call a layer on the donated pool."""
    def run(pool, slot_rows, a, dx, b, c):
        work = op.busy_rows(slot_rows)
        total = 0.0
        for layer in range(layers):
            with jax.named_scope("ssm._state_update"):
                y, pool = update(pool, layer, slot_rows, a, dx, b, c,
                                 work=work)
            total = total + y
        return total, pool
    return jax.jit(run, donate_argnums=0)


def measure(run, pool, args, reps: int, sets: int):
    """``(seconds a program, the first program's y sum)``: the median of
    ``sets`` readings of ``reps`` calls behind the one that compiles."""
    first, pool = jax.block_until_ready(run(pool, *args))    # compiles
    first = np.asarray(first)
    readings = []
    for _ in range(sets):
        start = time.perf_counter()
        for _ in range(reps):
            total, pool = run(pool, *args)
        jax.block_until_ready((total, pool))
        readings.append((time.perf_counter() - start) / reps)
    return statistics.median(readings), first


def probe(busy_counts, layers, reps, sets, seed, bytes_per_s,
          tiles=(4, 8, 16, 32), sizes=None, dtype=jnp.bfloat16):
    """The table's rows, a form a busy count: ``{"form", "busy",
    "us_a_layer_call", "share_of_bytes_time", "y_gap"}`` (``y_gap``: the
    largest distance of the form's first program's ``y`` sum from the
    parent's, relative to the largest value)."""
    sizes = {**CELL, **(sizes or {})}
    sizes["layers"] = layers
    rows = []
    for busy in busy_counts:
        want = None
        for name, (update, in_lanes) in forms(tiles).items():
            pool, args = inputs(seed, busy, **sizes, dtype=dtype)
            seconds, first = measure(program(update, layers), as_form_lies(
                pool, in_lanes), args, reps, sets)
            want = first if want is None else want
            least = least_seconds(
                busy, sizes["heads"], sizes["width"], sizes["n"],
                jnp.dtype(dtype).itemsize, bytes_per_s)
            rows.append({
                "form": name, "busy": busy,
                "us_a_layer_call": 1e6 * seconds / layers,
                "share_of_bytes_time": 100 * least * layers / seconds,
                "y_gap": float(np.abs(first - want).max()
                               / max(np.abs(want).max(), 1e-30))})
    return rows


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--busy", type=int, nargs="+", default=[11, 20, 40])
    parser.add_argument("--layers", type=int, default=CELL["layers"])
    parser.add_argument("--reps", type=int, default=20)
    parser.add_argument("--sets", type=int, default=5)
    parser.add_argument("--seed", type=int, default=51)
    parser.add_argument("--tiles", type=int, nargs="+",
                        default=[4, 8, 16, 32])
    parser.add_argument("--out", default="chiprun_out/probe_ssm_state_update"
                                         ".json")
    args = parser.parse_args(argv)
    device = jax.devices()[0]
    # an unknown kind (the CPU) is an error: a time from it is no device
    # metric (the rehearsal is tests/unit/test_probe_ssm_state_update.py)
    bytes_per_s = peaks(device.device_kind)["hbm_bytes_per_s"]
    rows = probe(args.busy, args.layers, args.reps, args.sets, args.seed,
                 bytes_per_s, tuple(args.tiles))
    print(f"{'form':10s} {'busy':>4s} {'us/layer call':>14s} "
          f"{'% of bytes time':>16s} {'y gap':>9s}")
    for r in rows:
        print(f"{r['form']:10s} {r['busy']:4d} {r['us_a_layer_call']:14.2f} "
              f"{r['share_of_bytes_time']:16.2f} {r['y_gap']:9.2e}")
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device_kind": device.device_kind, "sizes": CELL,
                   "layers": args.layers, "rows": rows}, f, indent=1)
    worst = max(r["y_gap"] for r in rows)
    if worst > 1e-3:
        raise SystemExit(f"the forms' y part by {worst:.2e}")


if __name__ == "__main__":
    main()
