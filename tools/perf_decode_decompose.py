"""Decompose the decode step: where do the ~4.2 ms/token (b8, 125M) go?

The bandwidth bound for one decode step is ~0.3 ms (250 MB of bf16
weights at v5e HBM rates) + ~0.4 ms of KV cache traffic at the 1024-slot
cache — the measured per-token cost is ~5x that. This script times, on
the real chip, the candidate explanations as separate compiled programs:

  1. the full generate marginal per-token (bench_decode's number)
  2. one whole-model cached decode step (embed + L layers + head),
     jitted standalone with the cache donated
  3. the same step WITHOUT cache donation (is the cache copied?)
  4. a scan of 16 decode steps inside ONE program (does the per-step
     dispatch/bookkeeping of the generate scan matter?)
  5. logits head alone, attention-layer stack alone

Run on the chip (any platform works, numbers only mean something there):
    python tools/perf_decode_decompose.py
"""

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def timeit(fn, *args, steps=20, **kw):
    import jax

    sync = jax.block_until_ready

    out = fn(*args, **kw)
    sync(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args, **kw)
    sync(out)
    return (time.perf_counter() - t0) / steps * 1000  # ms


def main():
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel

    on_tpu = jax.devices()[0].platform != "cpu"
    if on_tpu:
        cfg = GPT2Config.gpt2_125m(vocab_size=50257, n_positions=1024,
                                   dtype=jnp.bfloat16, scan_layers=True)
        B, prompt = 8, 128
    else:
        cfg = GPT2Config.tiny(dtype=jnp.float32)
        B, prompt = 2, 8

    model = GPT2LMHeadModel(cfg)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (B, prompt)).astype(np.int32)

    engine = deepspeed_tpu.init_inference(
        model, dtype=cfg.dtype, max_out_tokens=cfg.n_positions)

    # 1. the bench's marginal per-token number for reference
    def gen_time(n):
        engine.generate(ids, max_new_tokens=n, do_sample=False)
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            engine.generate(ids, max_new_tokens=n, do_sample=False)
            best = min(best, time.perf_counter() - t0)
        return best

    n = 32 if on_tpu else 8
    t1, t2 = gen_time(n), gen_time(2 * n)
    print(f"1. generate marginal: {1e3 * (t2 - t1) / n:.3f} ms/token")

    # build the standalone decode step the engine's scan body runs
    dmodule = engine._decode_module()
    params = engine.params
    dequant = engine._dequantize

    # prefill to get a live cache
    out, vars_ = jax.jit(
        lambda p, i: dmodule.apply({"params": dequant(p)}, i,
                                   mutable=["cache"]))(params, ids)
    cache0 = vars_["cache"]
    tok = np.full((B, 1), 17, np.int32)

    def step(p, cache, t):
        o, v = dmodule.apply({"params": dequant(p), "cache": cache},
                             t, mutable=["cache"])
        return jnp.argmax(o[:, -1], -1), v["cache"]

    donated = jax.jit(step, donate_argnums=(1,))
    plain = jax.jit(step)

    # fresh cache copies per timed call are NOT free; time with a pool
    def run_donated():
        nonlocal cache0
        t, cache0 = donated(params, cache0, tok)
        return t

    print(f"2. one decode step (cache donated):   "
          f"{timeit(run_donated):.3f} ms")
    cache_keep = jax.tree_util.tree_map(jnp.copy, cache0)
    print(f"3. one decode step (no donation):     "
          f"{timeit(lambda: plain(params, cache_keep, tok)[0]):.3f} ms")

    def scan16(p, cache, t0):
        def body(c, _):
            cache, t = c
            t2, cache2 = step(p, cache, t)
            return (cache2, t2[:, None]), ()

        (cache, t), _ = jax.lax.scan(body, (cache, t0), None, length=16)
        return t, cache

    scan16_j = jax.jit(scan16, donate_argnums=(1,))

    def run_scan():
        nonlocal cache0
        t, cache0 = scan16_j(params, cache0, tok)
        return t

    print(f"4. scanned 16 steps, per step:        "
          f"{timeit(run_scan) / 16:.3f} ms")

    # 5. parts: head alone on a [B,1] position (find the tied embedding
    # table by shape — the only [vocab, n_embd] leaf)
    h = jnp.zeros((B, 1, cfg.n_embd), cfg.dtype)
    wte = next((l for l in jax.tree_util.tree_leaves(dequant(params))
                if getattr(l, "shape", ()) == (cfg.vocab_size, cfg.n_embd)),
               None)
    if wte is not None:
        # mirror the model's head exactly (gpt2.py: bf16 x bf16 with f32
        # accumulation) — an f32-cast matmul would double the table
        # traffic and misattribute the head's share of the step
        head = jax.jit(lambda w, h: jnp.einsum(
            "btc,vc->btv", h, w, preferred_element_type=jnp.float32))
        print(f"5. lm head [B,1]x[V,C] alone:         "
              f"{timeit(head, wte, h):.3f} ms")

    # 6. batch sweep: off-chip XLA cost analysis says per-step memory
    # traffic is near-ideal (~1.7 GB fp32 incl. one cache-sized scan
    # temp), so if the measured per-step time is ~flat in batch, the
    # floor is MXU/VPU latency at tiny [B, C] operands (8 rows of a
    # 128-row MXU tile), NOT bandwidth — and decode tokens/s scales
    # ~linearly with batch until the tile fills
    if on_tpu:
        for B2 in (16, 32):
            ids2 = rng.integers(0, cfg.vocab_size,
                                (B2, prompt)).astype(np.int32)
            eng2 = deepspeed_tpu.init_inference(
                model, dtype=cfg.dtype, max_out_tokens=cfg.n_positions)
            eng2.generate(ids2, max_new_tokens=16, do_sample=False)
            ts = []
            for _ in range(3):
                t0 = time.perf_counter()
                eng2.generate(ids2, max_new_tokens=16, do_sample=False)
                ts.append(time.perf_counter() - t0)
            t16 = min(ts)
            eng2.generate(ids2, max_new_tokens=32, do_sample=False)
            ts = []
            for _ in range(3):
                t0 = time.perf_counter()
                eng2.generate(ids2, max_new_tokens=32, do_sample=False)
                ts.append(time.perf_counter() - t0)
            per = (min(ts) - t16) / 16
            print(f"6. batch {B2}: {1e3 * per:.3f} ms/step = "
                  f"{B2 / per:.0f} tokens/s")


if __name__ == "__main__":
    main()
