"""The KDA decode-step kernel ALONE, at a cell's shapes: microseconds a layer
call and the share of the bytes' time, the form until PR 58 (a head's three
columns each a transpose of a sublane-broadcast ``[128, 128]`` tile, eight
heads a grid step: ``parent``, kept here) against the tree's
(``ops/kda_state_update.py``) at head tiles 8 / 16 / 32.

    chiprun -- python tools/probe_kda_state_update.py [--busy 40 85 110]

A program is ``--layers`` kernel calls on one donated pool
``[layers, 1 + slots, H, K, V]`` float32, as a decode program's KDA layers
are; a reading is the host's clock over ``--reps`` such programs, a layer
call's share of it, the median of ``--sets``. The bound is the busy rows'
states read once and written once (``2 x busy x H K V x 4`` bytes) over the
chip's HBM bandwidth: what the parked reader's function counts
(``tests/perfbench/cells_bailing_hybrid``). Every form runs the same
``alpha``, ``k``, ``v``, ``q``, ``beta`` on the same states, and the probe
fails if a form's ``o`` or its states part from the parent's by more than the
unit test's 1e-6. On a CPU it runs tiny shapes under the Pallas interpreter
(its test: ``tests/unit/test_probe_kda_state_update.py``) and prints no time
as a device's.
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

from deepspeed_tpu.ops import kda_state_update as op
from deepspeed_tpu.utils.compat import tpu_compiler_params
from perfbench.flops import peaks

CELL = dict(layers=7, slots=128, heads=32, width=128)
# what the forms may part by, ``o`` and states: the unit test's
GAP = 1e-6


# ---------------------------------------------------------------------------
# the parent's form (PR 57's kernel, as the tree had it until PR 58): a
# head's alpha, k and q each turned by a transpose of its sublane broadcast,
# the q product after the rank-one update, eight heads a grid step

def _parent_kernel(order_ref, count_ref, slots_ref, layer_ref, alpha_ref,
                   k_ref, q_ref, v_ref, beta_ref, pool_ref, o_ref, out_ref,
                   *, tile):
    del order_ref, count_ref, slots_ref, layer_ref
    keys, values = pool_ref.shape[-2:]

    def column(ref, at):
        return jnp.broadcast_to(ref[at, :], (values, keys)).T

    def head(h, carry):
        at = pl.ds(h, 1)
        k_col = column(k_ref, at)
        state = column(alpha_ref, at) * pool_ref[h].astype(jnp.float32)
        u = beta_ref[at, :] * (v_ref[at, :] - jnp.sum(
            state * k_col, axis=0, keepdims=True))
        state = state + k_col * u
        o_ref[at, :] = jnp.sum(state * column(q_ref, at), axis=0,
                               keepdims=True)
        out_ref[h] = state.astype(out_ref.dtype)
        return carry

    jax.lax.fori_loop(0, tile, head, 0)


def parent_update(pool, layer, slot_rows, alpha, k, v, q, beta, work=None,
                  head_tile: int = 8):
    rows, heads, keys = k.shape
    values = v.shape[-1]
    tile = min(head_tile, heads)
    tiles = heads // tile
    f32 = jnp.float32
    order, count = op.busy_rows(slot_rows) if work is None else work
    by_head = lambda x, width: x.astype(f32).reshape(rows, tiles, tile, width)
    row = lambda i, j, order, count, slots, at: (order[i], j, 0, 0)
    state = lambda i, j, order, count, slots, at: (
        at[0], slots[order[i]], j, 0, 0)
    key_rows = pl.BlockSpec((None, None, tile, keys), row)
    value_rows = pl.BlockSpec((None, None, tile, values), row)
    in_pool = pl.BlockSpec((None, None, tile, keys, values), state)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(jnp.maximum(count[0], 1), tiles),
        in_specs=[key_rows, key_rows, key_rows, value_rows, value_rows,
                  in_pool],
        out_specs=[value_rows, in_pool],
    )
    o, pool = pl.pallas_call(
        functools.partial(_parent_kernel, tile=tile),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((rows, tiles, tile, values), f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={9: 1},
        compiler_params=tpu_compiler_params(
            dimension_semantics=("arbitrary", "arbitrary")),
    )(order, count, jnp.asarray(slot_rows, jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), by_head(alpha, keys),
      by_head(k, keys), by_head(q, keys), by_head(v, values),
      by_head(jnp.broadcast_to(beta[..., None], v.shape), values), pool)
    o = jnp.where((slot_rows != 0)[:, None, None],
                  o.reshape(rows, heads, values), 0.0)
    return o, pool


def forms(tiles=(8, 16, 32)) -> dict:
    """A form's name -> its update."""
    out = {"parent": parent_update}
    for tile in tiles:
        out[f"tile-{tile}"] = functools.partial(op.state_update_kernel,
                                                head_tile=tile)
    return out


# ---------------------------------------------------------------------------
def least_seconds(busy: int, heads: int, width: int,
                  bytes_per_s: float) -> float:
    """The bytes' time of one layer call: every busy row's float32 state
    read once and written once."""
    return 2 * busy * heads * width * width * 4 / bytes_per_s


def inputs(seed: int, busy: int, layers: int, slots: int, heads: int,
           width: int):
    """A float32 pool ``[layers, 1 + slots, H, K, V]``, ``busy`` of the
    ``slots`` batch rows on slots of their own in no order, and a step's
    terms as the mixer hands them (``k`` of unit length, ``q`` of length
    ``K ** -0.5``, ``alpha`` in ``(e^-5, 1)``, ``beta`` in ``(0, 1)``)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    f32 = jnp.float32
    pool = jax.random.normal(keys[0], (layers, 1 + slots, heads, width,
                                       width), f32)
    rng = np.random.default_rng(seed)
    rows = np.zeros(slots, np.int32)
    rows[rng.permutation(slots)[:busy]] = 1 + rng.permutation(slots)[:busy]
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    vec = (slots, heads, width)
    return pool, (
        jnp.asarray(rows),
        jnp.exp(-5.0 * jax.random.uniform(keys[1], vec, f32)),
        unit(jax.random.normal(keys[2], vec, f32)),
        jax.random.normal(keys[3], vec, f32),
        unit(jax.random.normal(keys[4], vec, f32)) * width ** -0.5,
        jax.random.uniform(keys[5], (slots, heads), f32))


def program(update, layers: int):
    """One decode program's worth: a call a layer on the donated pool."""
    def run(pool, slot_rows, alpha, k, v, q, beta):
        work = op.busy_rows(slot_rows)
        total = 0.0
        for layer in range(layers):
            with jax.named_scope("kda._step"):
                o, pool = update(pool, layer, slot_rows, alpha, k, v, q,
                                 beta, work=work)
            total = total + o
        return total, pool
    return jax.jit(run, donate_argnums=0)


@jax.jit
def _apart(a, b):
    return jnp.max(jnp.abs(a - b))


def measure(run, pool, args, reps: int, sets: int, want=None):
    """``(seconds a program, the first program's (o sum, pool), its gaps
    from ``want``)``: the median of ``sets`` readings of ``reps`` calls
    behind the one that compiles. ``want``: another form's first ``(o sum,
    pool)``."""
    total, pool = jax.block_until_ready(run(pool, *args))    # compiles
    gaps = (0.0, 0.0) if want is None else tuple(
        float(_apart(got, w)) for got, w in zip((total, pool), want))
    first = (total, jnp.copy(pool)) if want is None else None
    readings = []
    for _ in range(sets):
        start = time.perf_counter()
        for _ in range(reps):
            total, pool = run(pool, *args)
        jax.block_until_ready((total, pool))
        readings.append((time.perf_counter() - start) / reps)
    return statistics.median(readings), first, gaps


def probe(busy_counts, layers, reps, sets, seed, bytes_per_s,
          tiles=(8, 16, 32), sizes=None, more_forms=None):
    """The table's rows, a form a busy count: ``{"form", "busy",
    "us_a_layer_call", "us_a_busy_row", "share_of_bytes_time", "o_gap",
    "state_gap"}`` (the gaps: the largest distance of the form's first
    program's ``o`` sum and pool from the parent's)."""
    sizes = {**CELL, **(sizes or {})}
    sizes["layers"] = layers
    rows = []
    for busy in busy_counts:
        want = None
        for name, update in {**forms(tiles), **(more_forms or {})}.items():
            pool, args = inputs(seed, busy, **sizes)
            seconds, first, gaps = measure(program(update, layers), pool,
                                           args, reps, sets, want)
            want = first if want is None else want
            least = least_seconds(busy, sizes["heads"], sizes["width"],
                                  bytes_per_s)
            rows.append({
                "form": name, "busy": busy,
                "us_a_layer_call": 1e6 * seconds / layers,
                "us_a_busy_row": 1e6 * seconds / layers / max(busy, 1),
                "share_of_bytes_time": 100 * least * layers / seconds,
                "o_gap": gaps[0], "state_gap": gaps[1]})
    return rows


def show(rows):
    print(f"{'form':10s} {'busy':>4s} {'us/layer call':>14s} "
          f"{'us/busy row':>12s} {'% of bytes time':>16s} {'o gap':>9s} "
          f"{'state gap':>10s}")
    for r in rows:
        print(f"{r['form']:10s} {r['busy']:4d} {r['us_a_layer_call']:14.2f} "
              f"{r['us_a_busy_row']:12.3f} {r['share_of_bytes_time']:16.2f} "
              f"{r['o_gap']:9.2e} {r['state_gap']:10.2e}")


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--busy", type=int, nargs="+", default=[40, 85, 110])
    parser.add_argument("--layers", type=int, default=CELL["layers"])
    parser.add_argument("--reps", type=int, default=20)
    parser.add_argument("--sets", type=int, default=5)
    parser.add_argument("--seed", type=int, default=58)
    parser.add_argument("--tiles", type=int, nargs="+", default=[8, 16, 32])
    parser.add_argument("--out", default="chiprun_out/probe_kda_state_update"
                                         ".json")
    args = parser.parse_args(argv)
    device = jax.devices()[0]
    # an unknown kind (the CPU) is an error: a time from it is no device
    # metric (the rehearsal is tests/unit/test_probe_kda_state_update.py)
    bytes_per_s = peaks(device.device_kind)["hbm_bytes_per_s"]
    rows = probe(args.busy, args.layers, args.reps, args.sets, args.seed,
                 bytes_per_s, tuple(args.tiles))
    show(rows)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device_kind": device.device_kind, "sizes": CELL,
                   "layers": args.layers, "rows": rows}, f, indent=1)
    worst = max(max(r["o_gap"], r["state_gap"]) for r in rows)
    if worst > GAP:
        raise SystemExit(f"the forms part by {worst:.2e}")


if __name__ == "__main__":
    main()
