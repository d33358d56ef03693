"""Marginal in-program cost measurement for kernel benchmarks.

Chain N dependent evaluations of an op inside ONE compiled program and
report ``(T(N) - T(1)) / (N - 1)``: the per-program dispatch and transfer
overhead cancels, and ``min`` over repeats rejects cross-dispatch noise
from a host whose cores are shared. Shared by the repo-root bench
scripts and the ``tools/perf_*`` investigation scripts so the methodology
can only be fixed in one place.
"""

import time


def marginal_cost_ms(fn, *args, iters: int = 16, repeats: int = 5) -> float:
    """Per-evaluation cost of ``fn(*args)`` in milliseconds.

    ``fn`` must accept the first arg as the value to chain through (its
    output's first leaf feeds a zero-scaled bump back into the next
    iteration's first arg, forcing sequential execution without changing
    the math).
    """
    import jax
    import jax.numpy as jnp

    def chained(n):
        def f(first, *rest):
            def body(c, _):
                out = fn(c, *rest)
                leaf = jnp.atleast_1d(jax.tree_util.tree_leaves(out)[0])
                bump = jnp.max(jnp.abs(
                    leaf[(0,) * (leaf.ndim - 1)][:2].astype(jnp.float32)))
                return c * (1.0 + 0.0 * bump).astype(c.dtype), ()

            cf, _ = jax.lax.scan(body, first, None, length=n)
            cf = jnp.atleast_1d(cf)
            return cf[(0,) * (cf.ndim - 1)][:2]  # tiny transfer

        return jax.jit(f)

    def timed(run):
        jax.block_until_ready(run(*args))  # compile + warm
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(run(*args))
            best = min(best, time.perf_counter() - t0)
        return best

    t_n = timed(chained(iters))
    t_1 = timed(chained(1))
    return 1e3 * max(1e-9, t_n - t_1) / (iters - 1)
