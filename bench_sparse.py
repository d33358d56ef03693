"""Long-sequence block-sparse attention benchmark (8k/16k, density < 0.17).

The reference's sparse-attention headline is long sequences: "10x longer,
up to 6.3x faster" (``docs/_posts/2020-09-09-sparse-attention.md:30-31``).
Round-2 measurement showed our Pallas kernel reaches ~parity with dense
flash at seq 4096 / density 0.32 — the win lives at 8k+ / density < 0.17,
which is what this bench demonstrates on-chip. Prints ONE JSON line with
the sparse-vs-dense-flash speedup at each sequence length;
``vs_baseline`` = (best fwd+bwd speedup) / 6.3 (the reference headline).

Methodology: marginal in-program cost — N chained evaluations inside one
compiled program, (T(N)-T(1))/(N-1) — which cancels per-call dispatch and
transfer overhead (same as tools/perf_sparse.py).
"""


import numpy as np

from deepspeed_tpu.utils.device import (cpu_requested, emit_result,
                                        require_device)
from deepspeed_tpu.utils.marginal_bench import marginal_cost_ms

# the smoke name under an explicit JAX_PLATFORMS=cpu: a CPU run is never
# filed under the device metric
METRIC = ("sparse_longseq_cpu_smoke" if cpu_requested()
          else "sparse_attention_longseq_speedup")
REF_SPEEDUP = 6.3  # docs/_posts/2020-09-09-sparse-attention.md:30


def _bench(fn, q, k, v, iters):
    return marginal_cost_ms(fn, q, k, v, iters=iters, repeats=4)


def main():
    # the TPU, or the CPU when it was asked for by name; anything else raises
    dev = require_device("tpu")

    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.flash_attention import flash_attention
    from deepspeed_tpu.ops.sparse_attention.block_sparse_kernel import (
        block_sparse_attention)
    from deepspeed_tpu.ops.sparse_attention.sparsity_config import (
        BigBirdSparsityConfig)

    on_tpu = dev["platform"] == "tpu"
    if on_tpu:
        B, H, D, BLOCK = 1, 12, 64, 256
        seqs, iters = (8192, 16384), 8
        ctx = None
    else:  # CPU smoke: interpret-mode kernels at tiny shapes
        from deepspeed_tpu.utils.compat import tpu_interpret_mode

        B, H, D, BLOCK = 1, 2, 32, 64
        seqs, iters = (256,), 2
        ctx = tpu_interpret_mode()
        ctx.__enter__()

    results = {}
    best_fwdbwd = 0.0
    for S in seqs:
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        dt = jnp.bfloat16 if on_tpu else jnp.float32
        q, k, v = (jax.random.normal(kk, (B, H, S, D), dt) * 0.3
                   for kk in ks)
        cfg = BigBirdSparsityConfig(num_heads=H, block=BLOCK,
                                    num_random_blocks=1,
                                    num_sliding_window_blocks=3,
                                    num_global_blocks=1)
        layout = np.asarray(cfg.make_layout(S), bool)
        density = float(layout.mean())

        def sparse_fwd(q, k, v):
            return block_sparse_attention(q, k, v, layout)

        def flash_fwd(q, k, v):
            return flash_attention(q, k, v, causal=False)

        def sparse_fb(q, k, v):
            return jax.grad(lambda a, b, c: jnp.sum(block_sparse_attention(
                a, b, c, layout).astype(jnp.float32)), argnums=(0, 1, 2))(
                q, k, v)

        def flash_fb(q, k, v):
            return jax.grad(lambda a, b, c: jnp.sum(flash_attention(
                a, b, c, causal=False).astype(jnp.float32)),
                argnums=(0, 1, 2))(q, k, v)

        t_s = _bench(sparse_fwd, q, k, v, iters)
        t_f = _bench(flash_fwd, q, k, v, iters)
        t_sb = _bench(sparse_fb, q, k, v, max(2, iters // 2))
        t_fb = _bench(flash_fb, q, k, v, max(2, iters // 2))
        results[f"seq{S}"] = {
            "density": round(density, 4),
            "fwd_ms": {"sparse": round(t_s, 2), "flash": round(t_f, 2)},
            "fwd_speedup": round(t_f / t_s, 2),
            "fwdbwd_ms": {"sparse": round(t_sb, 2), "flash": round(t_fb, 2)},
            "fwdbwd_speedup": round(t_fb / t_sb, 2),
        }
        best_fwdbwd = max(best_fwdbwd, t_fb / t_sb)

    emit_result({
        "metric": METRIC,
        "value": round(best_fwdbwd, 2),
        "unit": "x_vs_dense_flash",
        "vs_baseline": round(best_fwdbwd / REF_SPEEDUP, 4),
        "detail": results,
        "note": ("vs_baseline = best fwd+bwd speedup / 6.3 (reference "
                 "sparse-attention headline); BigBird block layout"),
    })


if __name__ == "__main__":
    main()
