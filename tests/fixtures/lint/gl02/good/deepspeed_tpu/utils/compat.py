"""The shim itself is the ONE exempt module: raw API access lives here."""

import jax
from jax import shard_map  # noqa: F401


def tpu_compiler_params(**kwargs):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(**kwargs)


def tpu_interpret_mode():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.force_tpu_interpret_mode()


def arm_compilation_cache():
    jax.config.update("jax_compilation_cache_dir", ".jax_compile_cache")
