"""Test harness: fake an 8-device TPU-like mesh on CPU.

The reference simulates a cluster with N forked NCCL processes on one node
(``tests/unit/common.py``). The TPU-native equivalent is XLA's virtual host
devices: one process, 8 CPU devices, real GSPMD partitioning + collectives.
"""

import os
import sys

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)

import jax

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deepspeed_tpu.utils.compat import arm_compilation_cache  # noqa: E402

# Persistent XLA compilation cache: the suite's wall-clock is dominated by
# compiles of the (tiny but numerous) sharded train-step programs, and a
# warm cache cuts the heaviest tests 3-4x. JAX_COMPILATION_CACHE_DIR places
# it; unset, it is <checkout>/.jax_compile_cache (delete it to force cold
# compiles).
arm_compilation_cache()

import pytest  # noqa: E402


def pytest_configure(config):
    # suite split: `-m "not heavy"` is the fast development loop; the
    # tier-1 gate runs everything but `slow`. Heavy = big compiles or
    # real-text convergence runs.
    config.addinivalue_line(
        "markers", "heavy: slow tests (big compiles, convergence gates); "
        "deselect with -m 'not heavy'")
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 time-budgeted gate "
        "(`-m 'not slow'`)")


@pytest.fixture(scope="session", autouse=True)
def _assert_cpu_mesh():
    assert jax.device_count() >= 8, (
        "tests expect >=8 virtual CPU devices; got "
        f"{jax.device_count()} ({jax.devices()[0].platform})"
    )
