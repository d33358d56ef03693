"""BAD: every routed JAX API used directly, one per line."""

import jax
from jax.experimental.shard_map import shard_map
from jax.experimental import serialize_executable
from jax.experimental.pallas import tpu as pltpu


def sharded(fn, mesh, specs):
    return shard_map(fn, mesh=mesh, in_specs=specs, out_specs=specs)


def compile_params():
    return pltpu.CompilerParams(dimension_semantics=("parallel",))


def interpret():
    return pltpu.force_tpu_interpret_mode()


def ship(compiled):
    return serialize_executable.serialize(compiled)


def arm_cache(path):
    jax.config.update("jax_compilation_cache_dir", path)
