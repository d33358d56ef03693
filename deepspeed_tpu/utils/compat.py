"""The one door to the JAX APIs whose spelling this repo does not want
scattered over its call sites (``tools/lint`` rule GL02 keeps them here):
``shard_map``, the Pallas TPU compiler parameters and interpret mode, and
the arming of the persistent compilation cache.

Written for the one installation there is (jax 0.9): no branch here asks
which JAX is running.
"""

import contextlib
import os

import jax


def tpu_compiler_params(**kwargs):
    """``pltpu.CompilerParams`` — every Pallas kernel in the tree routes
    its ``compiler_params=`` through here."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(**kwargs)


def tpu_interpret_mode():
    """Context that runs every ``pl.pallas_call`` traced inside it in the
    TPU interpreter on the CPU.

    The interpreter's callbacks run JAX ops of their own on the default
    device: block on the interpreted program's outputs before dispatching
    other work, or the two can wait on each other forever."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.force_tpu_interpret_mode()


def shard_map(f, mesh, in_specs, out_specs, check_vma=None, axis_names=None):
    """``jax.shard_map``; ``check_vma`` / ``axis_names`` left at ``None``
    keep JAX's own defaults."""
    kwargs = {}
    if check_vma is not None:
        kwargs["check_vma"] = check_vma
    if axis_names is not None:
        kwargs["axis_names"] = axis_names
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)


# <checkout>/.jax_compile_cache, listed in .gitignore. The path is part of
# the cache's key, so it is fixed: never $HOME, a temp name, a pid or a time.
_CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_compile_cache")


def arm_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and
    return the directory it uses.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it, and no
    other directory is set in code; where it is not, the cache lives in one
    fixed directory inside the checkout. Used by ``chip_smoke.py``,
    ``perfbench/run.py`` and ``tests/conftest.py``."""
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = _CHECKOUT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # every program counts, however small or quick to compile
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir


@contextlib.contextmanager
def compilation_cache_off():
    """The persistent compilation cache out of the way for a block: for a
    measurement whose cold compile must BE one, and for compiles whose
    entries could not be read back (a described chip that is not
    attached). Also keeps a program that the cache handed back out of an
    AOT bundle: the installed jaxlib's CPU backend serializes such an
    executable without its fused kernels."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
