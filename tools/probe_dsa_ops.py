"""Times, on the chip, of DeepSeek-V3.2's sparse-attention path at the
published widths: the serving programs of ``deepseek-v3.2-ep32``'s cut (a
512-token chunk at 4k, 16k and 30k live keys; a decode step of 14 busy rows)
and, apart, the pieces a layer of them is made of: a chunk's index scores,
its selection in both forms (the XLA passes; the kernel
``dsa_index_select``, which serves on a TPU: the two must choose the same
sets), its attention under the mask; a step's
scores, ``lax.top_k``, the gather and the attention over the gathered rows.

    chiprun -- python tools/probe_dsa_ops.py [--layers 5] [--reps 5]

Prints one JSON line a timing (milliseconds, the median of ``--reps`` calls
that end in ``block_until_ready``); PERF.md, section 5, quotes them.
"""

import argparse
import json
import statistics
import sys
import time

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.deepseek_v32 import (DeepseekV32Config,
                                               DeepseekV32ForCausalLM,
                                               SparseLatentAttention)
from deepspeed_tpu.ops import dsa_index_select as select_op
from deepspeed_tpu.ops import dsa_sparse_attend as attend_op

SLOTS, BLOCK, MAX_LEN, CHUNK = 16, 32, 32768, 512


def timed(fn, reps):
    jax.block_until_ready(fn())
    took = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        took.append(1e3 * (time.perf_counter() - t0))
    return statistics.median(took)


def say(**fields):
    print(json.dumps(fields), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=5)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--lives", default="4096,16384,30208")
    args = ap.parse_args()
    lives = [int(x) for x in args.lives.split(",")]
    cfg = DeepseekV32Config(
        num_hidden_layers=args.layers, first_k_dense_replace=1,
        vocab_size=16160, ep_size=32, selection_bias_std=0.02,
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    mb = MAX_LEN // BLOCK
    dcfg = cfg.for_paged_decode(1 + SLOTS * mb, BLOCK)
    dm = DeepseekV32ForCausalLM(dcfg)
    pg = {"block_tables": jnp.zeros((1, mb), jnp.int32),
          "lengths": jnp.zeros((1,), jnp.int32),
          "num_valid": jnp.zeros((1,), jnp.int32), "prefill": True}
    made = jax.jit(lambda key: dm.init(key, jnp.zeros((1, 1), jnp.int32),
                                       paging=pg))(jax.random.PRNGKey(0))
    params, cache = made["params"], made["cache"]
    # pools of unit-variance rows, as norms leave them
    cache = {name: jax.random.normal(jax.random.PRNGKey(i), pool.shape,
                                     pool.dtype)
             for i, (name, pool) in enumerate(sorted(cache.items()))}
    say(device=jax.devices()[0].device_kind,
        params=sum(x.size for x in jax.tree_util.tree_leaves(params)))

    def program(params, cache, ids, tables, lengths, num_valid):
        paging = {"block_tables": tables, "lengths": lengths,
                  "num_valid": num_valid, "prefill": False}
        (lg, aux), v = dm.apply({"params": params, "cache": cache}, ids,
                                mutable=["cache"], paging=paging)
        return (jnp.argmax(lg[:, -1], -1), aux["counters"], aux["selected"],
                v["cache"])

    run = jax.jit(program, donate_argnums=(1,))
    table = (1 + jnp.arange(mb, dtype=jnp.int32))[None]
    i32 = lambda x: jnp.asarray(x, jnp.int32)
    for live in lives:
        def chunk():
            nonlocal cache
            tok, counters, _, cache = run(
                params, cache, jnp.ones((1, CHUNK), jnp.int32), table,
                i32([live - CHUNK]), i32([CHUNK]))
            return counters
        ms = timed(chunk, args.reps)
        say(what="chunk program", live=live, ms=ms,
            counters=np.asarray(chunk()).tolist())
    # a decode step: 14 busy rows about a median request's length, 2 idle
    lengths = np.array([9000 + 700 * i for i in range(14)] + [0, 0])
    tables = np.zeros((SLOTS, mb), np.int32)
    for row in range(14):
        tables[row] = 1 + row * mb + np.arange(mb)

    def step():
        nonlocal cache
        tok, counters, _, cache = run(
            params, cache, jnp.ones((SLOTS, 1), jnp.int32), i32(tables), i32(lengths),
            i32(np.where(lengths > 0, 1, 1)))
        return counters
    say(what="decode program", busy=14, live=int(lengths.sum()),
        ms=timed(step, args.reps), counters=np.asarray(step()).tolist())

    # ---- the pieces, a layer (the weights' 6.4 GB make room first: a
    # mixer called alone copies the pool it writes)
    del params, made
    key = jax.random.PRNGKey(7)
    heads, width, k = cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk
    q = jax.random.normal(key, (CHUNK, heads, width), jnp.bfloat16)
    w = jax.random.normal(key, (CHUNK, heads), jnp.float32)
    keys = jax.random.normal(key, (MAX_LEN, width), jnp.bfloat16)
    tile = 1024
    scores_of = jax.jit(lambda q, w, keys, live: select_op.index_scores(
        q[None], w[None], lambda j: jax.lax.dynamic_slice_in_dim(
            keys, j * tile, tile, 0)[None],
        (live + tile - 1) // tile, tile, MAX_LEN)[0])

    def picked(kernel):
        """``select_mask`` in one of its two forms: the kernel
        ``dsa_index_select`` where it serves, or the XLA passes."""
        def pick(scores, live):
            could = jnp.minimum(
                live - CHUNK + 1 + jnp.arange(CHUNK, dtype=jnp.int32), live)
            serves = select_op.kernel_serves
            select_op.kernel_serves = (
                serves if kernel else lambda queries, cap: False)
            try:
                return select_op.select_mask(scores, could, k, live)
            finally:
                select_op.kernel_serves = serves
        return jax.jit(pick)

    forms = {"XLA passes": picked(False),
             "kernel dsa_index_select": picked(True)}
    top = jax.jit(lambda s: jax.lax.top_k(s, k))
    # a layer's queries each: ``--layers`` selections in ONE program are a
    # chunk program's (a call alone is a millisecond of launch besides)
    qs = [jax.random.normal(jax.random.PRNGKey(10 + i), q.shape, q.dtype)
          for i in range(args.layers)]
    for live in lives:
        at = jnp.asarray(live, jnp.int32)
        say(what="chunk index scores (XLA)", live=live,
            ms=timed(lambda: scores_of(q, w, keys, at), args.reps))
        stack = [scores_of(u, w, keys, at) for u in qs]
        scores = stack[0]
        sets = {}
        for name, pick in forms.items():
            sets[name] = pick(scores, at)
            layers = jax.jit(lambda stack, at: [pick(u, at) for u in stack])
            say(what=f"chunk selection ({name})", live=live,
                ms=timed(lambda: pick(scores, at), args.reps),
                ms_all_layers=timed(lambda: layers(stack, at), args.reps),
                chosen=int(sets[name][1].sum()))
        mask, chosen, _ = sets["XLA passes"]
        if not all(bool((m == mask).all()) and bool((c == chosen).all())
                   for m, c, _ in sets.values()):
            raise SystemExit(f"the forms' sets differ at {live} live keys")
    say(what="chunk selection (lax.top_k, for comparison)",
        ms=timed(lambda: top(scores), args.reps))
    # the mixer of one layer, whole, through the pools
    mixer = SparseLatentAttention(dcfg)
    pools = {"latent_pool": cache["latent_pool"],
             "index_pool": cache["index_pool"]}
    mp = jax.jit(lambda key, pools: mixer.init(
        key, jnp.zeros((1, 1, cfg.hidden_size), jnp.bfloat16),
        {"block_tables": table, "lengths": i32([0]), "num_valid": i32([1]),
         "prefill": False}, pools, 0))(key, pools)["params"]
    mix = jax.jit(lambda mp, pools, x, tables, lengths, num_valid:
                  mixer.apply({"params": mp}, x, {
                      "block_tables": tables, "lengths": lengths,
                      "num_valid": num_valid, "prefill": False}, pools, 0)[0])
    x = jax.random.normal(key, (1, CHUNK, cfg.hidden_size), jnp.bfloat16)
    for live in lives:
        say(what="chunk mixer, one layer (projections, scores, selection, "
            "masked attention)", live=live, ms=timed(
                lambda: mix(mp, pools, x, table, i32([live - CHUNK]),
                            i32([CHUNK])), args.reps))
    xs = jax.random.normal(key, (SLOTS, 1, cfg.hidden_size), jnp.bfloat16)
    say(what="decode mixer, one layer", ms=timed(
        lambda: mix(mp, pools, xs, i32(tables), i32(lengths),
                    i32(np.ones(SLOTS))), args.reps))
    # a step's pieces
    qs = jax.random.normal(key, (SLOTS, 1, heads, width), jnp.bfloat16)
    ws = jax.random.normal(key, (SLOTS, 1, heads), jnp.float32)
    per = tile // BLOCK

    def step_scores(pool, qs, ws, tables, lengths):
        def keys_of(j):
            at = jax.lax.dynamic_slice_in_dim(tables, j * per, per, 1)
            return pool[0, at].reshape(SLOTS, tile, -1)
        return select_op.index_scores(
            qs, ws, keys_of, (jnp.max(lengths) + tile) // tile, tile,
            MAX_LEN)[:, 0]

    sc_of = jax.jit(step_scores)
    say(what="step index scores (XLA, through the table)", ms=timed(
        lambda: sc_of(pools["index_pool"], qs, ws, i32(tables),
                      i32(lengths)), args.reps))
    sc = sc_of(pools["index_pool"], qs, ws, i32(tables), i32(lengths))
    valid = jnp.arange(MAX_LEN)[None] < i32(lengths)[:, None]
    pick = jax.jit(lambda sc, valid: select_op.select_positions(sc, valid, k))
    say(what="step selection (lax.top_k, and the mask from its k-th)",
        ms=timed(lambda: pick(sc, valid), args.reps))
    at = pick(sc, valid)[0]
    qf = jax.random.normal(key, (SLOTS, cfg.num_attention_heads,
                                 cfg.latent_lanes), jnp.bfloat16)
    rows = attend_op.pool_rows_of(at, i32(tables), BLOCK)
    attend = jax.jit(lambda qf, pool, rows: attend_op.attend_chosen_rows(
        qf, pool, 0, rows, rank=cfg.kv_lora_rank, scale=cfg.softmax_scale))
    say(what="step gather + attention over the chosen rows", ms=timed(
        lambda: attend(qf, pools["latent_pool"], rows), args.reps))


if __name__ == "__main__":
    main()
