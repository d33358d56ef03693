"""How served weights lie on their device: asked of a compiled program.

A layout is not a value. The backend gives every array a default layout
from its shape and type alone, and a program whose matmul wants a weight
the other way round re-lays it INSIDE the program, every call: K-EXAONE's
decode step transposed 566 MB of ``q_proj`` / ``k_proj`` kernels a step,
an eighth of its device time (PERF.md, PR 53). The compiler says what it
wants when it is let: lowered with ``Layout.AUTO`` on a parameter, the
compiled program names the layout it chose for it (``input_formats``).

- :func:`ask` lowers a program so and returns the formats its compiled
  form asks of one argument's leaves, with the compiled program;
- :func:`lay_out` puts the leaves that lie otherwise into the asked format,
  one at a time (``jax.device_put``: the same values, bit for bit, in the
  same shape and type; ``np.asarray`` and a checkpoint see the canonical
  array);
- :func:`parameter_copies` reads a compiled program's text for what it
  still re-lays of its parameters: the probe
  (``tools/probe_weight_layouts.py``) and the chip-compile tests count
  with it.

``ServingEngine._lay_out_weights`` is the one caller that moves weights, and
:class:`AskedProgram` is how its decode program runs the very executable
that was asked.
"""

import re
from typing import Callable, List, NamedTuple

import numpy as np

# what ``stats()["weight_layouts"]`` reads where the mechanism is not engaged
NOT_ENGAGED = {"asked_by": None, "leaves_moved": 0, "bytes_moved": 0,
               "leaves": 0}


def _shape_of(x):
    import jax

    return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                sharding=getattr(x, "sharding", None))


def ask(fn: Callable, tree, rest: tuple, donate: tuple = ()):
    """``(formats, compiled)``: the formats the compiled ``fn(tree, *rest)``
    asks of ``tree``'s leaves when it may choose them, and the compiled
    program itself (which runs on a tree so laid). ``fn`` is lowered with
    ``Layout.AUTO`` on every leaf of ``tree`` (each at the sharding it has)
    and every other argument as it comes, compiled, and ``input_formats``
    read back: a tree of ``Format`` (None for a leaf the program never
    reads). Arrays are taken as their shapes: nothing runs and nothing is
    donated."""
    import jax
    from jax.experimental.layout import Format, Layout

    shapes = jax.tree_util.tree_map(_shape_of, (tree, *rest))
    auto = jax.tree_util.tree_map(
        lambda s: Format(Layout.AUTO, s.sharding), shapes[0])
    compiled = jax.jit(
        fn, in_shardings=(auto,) + (None,) * len(rest),
        donate_argnums=donate).lower(*shapes).compile()
    return compiled.input_formats[0][0], compiled


def leaf_name(path) -> str:
    """``layers_0_attn/q_proj/kernel`` for a leaf's path in its tree."""
    import jax

    return jax.tree_util.keystr(path, simple=True, separator="/")


def lay_out(leaves: list, asked: list) -> List[int]:
    """Put every array of ``leaves`` that lies otherwise than its entry of
    ``asked`` says (a ``Format``, or None for as it lies) into that format,
    IN PLACE in the list, and return the places that moved. One leaf at a
    time: where the list is its arrays' only owner the old buffer is
    dropped as the new one takes its place, and the peak is one leaf
    more.

    The persistent compilation cache is kept out of the re-laying (a
    program of one copy, compiled in milliseconds): an executable the
    cache hands back has lost its RESULT's layout. JAX 0.9.0 then takes the
    re-laid bytes for an array in the default layout, and every program
    reads another matrix on the CPU, or the same matrix re-laid by nobody
    on the chip (PERF.md, PR 53: a warm start served the parent's programs,
    copies and all). A program's ARGUMENTS' layouts survive the cache, so
    the serving programs compiled over the tree so laid are cached as any
    other. A leaf that does not come back as asked is an error here, not a
    wrong token later."""
    import jax

    from deepspeed_tpu.utils.compat import compilation_cache_off

    moved = []
    with compilation_cache_off():
        for i, fmt in enumerate(asked):
            if fmt is None or fmt.layout == leaves[i].format.layout:
                continue
            leaves[i] = jax.device_put(leaves[i], fmt)
            leaves[i].block_until_ready()
            if leaves[i].format.layout != fmt.layout:
                raise RuntimeError(
                    f"a leaf asked into {fmt.layout} lies as "
                    f"{leaves[i].format.layout}")
            moved.append(i)
    return moved


class AskedProgram:
    """The executable :func:`ask` compiled, called as the program it is,
    with ``jitted`` (``jax.jit`` of the same function, no layout of its
    own) behind it: start-up pays ONE lowering and one compile (or cache
    read) for asking and for running. An executable takes only arguments
    that lie as it was compiled for, where ``jax.jit`` would compile again:
    the first call it refuses (a leaf that had been moved replaced by a
    plain array, a pool committed elsewhere; refused before anything runs
    or is donated) hands this program over to ``jitted`` for good. Every
    other attribute is ``jitted``'s (``.lower``)."""

    def __init__(self, compiled, jitted):
        self._compiled, self._jitted = compiled, jitted

    def __getattr__(self, name):
        return getattr(self._jitted, name)

    def __call__(self, *args):
        if self._compiled is not None:
            try:
                return self._compiled(*args)
            except (ValueError, TypeError) as e:
                from deepspeed_tpu.utils.logging import logger

                logger.warning(
                    f"the program that was asked refused its arguments ({e});"
                    " compiling for them as they lie")
                self._compiled = None
        return self._jitted(*args)


class ParameterCopy(NamedTuple):
    """One ``copy`` of a compiled program whose source is a parameter."""
    copy: str          # the instruction's name: ``copy.176``
    parameter: str     # the parameter's: ``qparams__layers_0_attn__...``
    dims: tuple        # the copy's shape
    bytes: int         # of the result (as much again is read)


_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]*)\]\S* (\w[\w\-]*)\((.*)$")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "s16": 2,
          "u16": 2, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4,
          "s64": 8, "u64": 8, "f64": 8}


def parameter_copies(text: str, argument: str) -> List[ParameterCopy]:
    """The ``copy`` instructions of a compiled program's ``text``
    (``compiled.as_text()``) whose operand is, through bitcasts alone, a
    parameter of the ENTRY computation named for ``argument`` (a jitted
    function's argument ``qparams`` names its leaves
    ``qparams__<path>``). A copy of a parameter is the program re-laying
    a weight it was handed: the same bytes read and written on every call
    and no work of the model's."""
    entry = text[text.index("\nENTRY "):] if "\nENTRY " in text else text
    made = {}
    for line in entry.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            name, dtype, dims, op, rest = m.groups()
            operand = re.search(r"%([\w.\-]+)", rest)   # the first operand
            made[name] = (op, dtype, tuple(int(d) for d in dims.split(",")
                                           if d),
                          operand.group(1) if operand else None)
    out = []
    for name, (op, dtype, dims, source) in made.items():
        if op != "copy":
            continue
        while source in made and made[source][0] == "bitcast":
            source = made[source][3]
        if (source in made and made[source][0] == "parameter"
                and source.startswith(argument + "__")):
            out.append(ParameterCopy(
                name, source, dims,
                int(np.prod(dims, dtype=np.int64)) * _BYTES[dtype]))
    return out
