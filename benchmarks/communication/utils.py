"""Shared harness for the collective benchmarks (reference
``benchmarks/communication/utils.py`` + ``constants.py``: size sweeps,
algbw/busbw accounting, warmup/trials).

Timing is in-program chained (``lax.scan`` of dependent collective calls)
with marginal cost (T(N)-T(1))/(N-1): per-dispatch latency and host↔device
transfer are excluded, and min-over-repeats rides out chip sharing.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

DEFAULT_SIZES_BYTES = [2 ** p for p in range(12, 29, 2)]  # 4 KiB … 256 MiB
DEFAULT_TRIALS = 5
DEFAULT_ITERS = 8


def get_mesh(axis: str = "data"):
    """The global mesh topology (all local devices on one axis)."""
    from deepspeed_tpu.parallel.topology import MeshTopology, get_topology

    topo = get_topology(create_if_missing=False)
    if topo is None:
        topo = MeshTopology(axis_sizes={axis: len(jax.devices())})
    return topo


def chained_time_s(fn, x, iters: int = DEFAULT_ITERS,
                   trials: int = DEFAULT_TRIALS) -> float:
    """Seconds per evaluation of ``fn(x)`` (same shape in/out reduction to
    carry), marginal in-program cost."""

    def chained(n):
        def prog(x0):
            def body(c, _):
                y = fn(c)
                # data dependency without changing the value's scale
                return c + 0.0 * jnp.mean(y).astype(c.dtype), ()

            out, _ = jax.lax.scan(body, x0, None, length=n)
            return jnp.sum(out[..., :1])

        return jax.jit(prog)

    def timed(run):
        np.asarray(jax.device_get(run(x)))  # compile + warm
        best = float("inf")
        for _ in range(trials):
            t0 = time.perf_counter()
            np.asarray(jax.device_get(run(x)))
            best = min(best, time.perf_counter() - t0)
        return best

    t_n = timed(chained(iters))
    t_1 = timed(chained(1))
    return max(1e-9, (t_n - t_1) / (iters - 1))


def bw_report(op: str, size_bytes: int, t: float, world: int):
    """(algbw, busbw) GB/s — NCCL-tests accounting the reference's
    benchmarks print (benchmarks/communication/utils.py busbw factors)."""
    algbw = size_bytes / t / 1e9
    factor = {
        "all_reduce": 2 * (world - 1) / world,
        "all_gather": (world - 1) / world,
        "reduce_scatter": (world - 1) / world,
        "all_to_all": (world - 1) / world,
        "broadcast": 1.0,
        "pt2pt": 1.0,
    }.get(op, 1.0)
    return algbw, algbw * factor


def print_header(op: str, world: int):
    print(f"\n---- {op} (world={world}) ----")
    print(f"{'size':>12} {'time(ms)':>10} {'algbw(GB/s)':>12} "
          f"{'busbw(GB/s)':>12}")


def fmt_size(n: int) -> str:
    for unit in ("B", "KiB", "MiB", "GiB"):
        if n < 1024:
            return f"{n}{unit}"
        n //= 1024
    return f"{n}TiB"
