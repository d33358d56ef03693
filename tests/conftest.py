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

# Persistent XLA compilation cache. JAX_COMPILATION_CACHE_DIR places it;
# unset, it is <checkout>/.jax_compile_cache. A warm cache cuts the heaviest
# tests 3-4x, but that is a developer's SECOND run: the run that decides a
# PR starts from a fresh checkout and is cold, and only what two tests (or
# two of the six workers) compile alike is found again within it. Measure a
# test's seconds cold: JAX_COMPILATION_CACHE_DIR=$(mktemp -d). Every
# program is written, however small (the settings are perfbench's, in
# compat.py); JAX's own thresholds moved a cold file's time by less than
# its noise (PR 61: 54.3 s against 51.7 s), so they stay.
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
