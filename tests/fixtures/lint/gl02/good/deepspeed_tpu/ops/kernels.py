"""GOOD: the same capabilities, all routed through the compat shim."""

from deepspeed_tpu.utils.compat import (
    arm_compilation_cache,
    shard_map,
    tpu_compiler_params,
    tpu_interpret_mode,
)


def sharded(fn, mesh, specs):
    return shard_map(fn, mesh=mesh, in_specs=specs, out_specs=specs)


def compile_params():
    return tpu_compiler_params(dimension_semantics=("parallel",))


def interpret():
    return tpu_interpret_mode()


def arm_cache():
    return arm_compilation_cache()
