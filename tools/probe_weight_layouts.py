"""What a serve cell's programs re-lay of their weights on every call, as the
tree lies by default and as the engine lays it out (PR 53), WITHOUT a chip:
each program of the cell compiled for a described TPU v5e at the cell's
own sizes.

    python tools/probe_weight_layouts.py [--cells serve-kexaone-reasoning-out ...]
                                         [--buckets median|all]

For every serve cell of ``perfbench/workloads/`` (or those named): the
model's bare step (``module.apply`` over the paged cache, as
``tests/unit/test_chip_compile.py:_lowered_program_hash`` builds it) for
the DECODE program (``decode_slots`` x 1 token) and for the cell's second
kind of program (the ``prefill_chunk_tokens`` chunk where the cell chunks,
else a whole-prompt bucket: the one that holds the mix's median prompt, or
``--buckets all`` for every bucket the mix can hit), compiled

- ``parent``: every argument a shape, so every weight in the backend's
  default layout;
- ``asked``: the decode program with ``Layout.AUTO`` on every leaf of
  ``params`` (``serving/weight_layouts.py:ask``, what the engine
  does at start-up): the leaves whose asked layout is not the default are
  the leaves the engine moves;
- ``change``: every program with exactly those leaves declared in the asked
  format and all else as before.

One JSON line a program: the bytes of parameters it copies a call
(``parameter_copies``: ``copy`` instructions whose source is a parameter)
on the parent and on the change, and one line a cell with the moved leaves.
Nothing runs and no time is measured: a compile for a described chip says
what a program holds, not what it costs.

ON the chip, ``--running`` reads the same off the programs a cell's engine
really runs:

    chiprun -- python tools/probe_weight_layouts.py --running --seed N
                      --cells serve-kexaone-reasoning-out [--root <tree>]

The cell's own set-up (``perfbench/jobs/<job>.setup``: engine, weights from
the seed, warm-up), then every program the engine built is lowered again
at the arguments the engine calls it with (caught on one more request a
program), compiled, and its text read: one JSON line a program, and one
with ``stats()["weight_layouts"]``, the seconds of the set-up and the
backend's compiles and cache hits in it. ``--root`` runs ANOTHER tree's
engine and benchmark (a parent unpacked under ``.bench_scratch/``) with
this file's reader, for the two side by side.
"""

import argparse
import functools
import importlib.util
import json
import math
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")

HERE = os.path.dirname(os.path.abspath(__file__))


def _reader():
    """``serving/weight_layouts.py`` of THIS file's tree, by its path: the
    tree under ``--root`` may be a parent that has none."""
    spec = importlib.util.spec_from_file_location(
        "_weight_layouts", os.path.join(
            os.path.dirname(HERE), "deepspeed_tpu", "serving",
            "weight_layouts.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _copies(weight_layouts, text: str, argument: str) -> dict:
    copies = weight_layouts.parameter_copies(text, argument)
    return {"parameter_bytes_copied": sum(c.bytes for c in copies),
            "copies": [[c.copy, c.parameter[len(argument) + 2:],
                        list(c.dims)] for c in copies]}


# ---------------------------------------------------------------------------
# without a chip: the bare step, compiled for a described one

class _Lies:
    """A table's stand-in for ``lookup_form``: the format a compiled
    program gives the parameter, on the one described device."""

    def __init__(self, fmt):
        self.format, self.sharding = fmt, fmt.sharding


def cell_programs(cell: dict, buckets: str):
    """``(model, per_seq, [(name, rows, tokens, prefill)])``: the paged
    module at the engine's sizes (``ServingEngine.__init__``'s arithmetic
    over the cell's ``serving`` block) and the programs the cell runs."""
    import jax.numpy as jnp

    from deepspeed_tpu.serving.config import (blocks_for_tokens, bucket_for,
                                              resolve_buckets)

    family, config_file = cell["family"], cell["config_file"]
    serving, mix = cell["serve"]["serving"], cell["traffic_file"]
    served = family.serving_module(config_file, jnp.bfloat16)
    mcfg = served.config
    context = int(mix.get("max_total") or family.max_context(config_file))
    max_len = int(serving.get("max_model_len") or context)
    bs, slots = int(serving["block_size"]), int(serving["decode_slots"])
    per_seq = blocks_for_tokens(max_len, bs)
    blocks = int(serving.get("num_blocks") or 1 + slots * per_seq)
    knobs = {}
    state_for = getattr(mcfg, "paged_slot_state_for", None)
    state = (state_for(bs) if state_for else None) or None
    if state:
        knobs[state["knob"]] = slots
        per_seq += int(state["entries"])
    if serving.get("routed_experts_kept"):
        knobs["return_routed"] = True
    model = type(served)(mcfg.for_paged_decode(blocks, bs, **knobs))
    programs = [("decode", slots, 1, False)]
    chunk = int(serving.get("prefill_chunk_tokens") or 0)
    if chunk:
        programs.append((f"chunk_T{chunk}", 1, chunk, False))
    else:
        ladder = resolve_buckets(serving.get("prompt_buckets"), max_len,
                                 floor=bs)
        lo, hi = mix["prompt_len"]["min"], mix["prompt_len"]["max"]
        hit = [b for i, b in enumerate(ladder)
               if b >= lo and (i == 0 or ladder[i - 1] < hi)]
        if buckets != "all":
            hit = [bucket_for(int(mix["prompt_len"]["median"]), ladder)]
        programs += [(f"prefill_T{b}", 1, b, True) for b in hit]
    return model, per_seq, programs


def probe_cell(cell: dict, one, weight_layouts, buckets: str = "median"):
    import jax
    import jax.numpy as jnp
    from jax.experimental.layout import Format

    from deepspeed_tpu.models.decode_utils import lookup_form

    model, per_seq, programs = cell_programs(cell, buckets)
    table = getattr(type(model), "lookup_table", None)
    s = lambda shape, dtype=jnp.int32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one)

    def paging(tables, lengths, num_valid, prefill):
        return {"block_tables": tables, "lengths": lengths,
                "num_valid": num_valid, "prefill": prefill}

    # (the harness serves every leaf in bfloat16: ``jobs/serve.py:setup``)
    variables = jax.tree_util.tree_map(
        lambda x: s(x.shape, jnp.bfloat16 if jnp.issubdtype(
            x.dtype, jnp.floating) else x.dtype),
        jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
            paging=paging(jnp.zeros((1, per_seq), jnp.int32),
                          jnp.zeros((1,), jnp.int32),
                          jnp.full((1,), 8, jnp.int32), True))))
    params, cache = variables["params"], variables["cache"]

    def step_of(prefill, form):
        def step(params, cache, ids, *rest):
            pg = paging(*rest, prefill)
            if form:
                pg["lookup"] = form
            return model.apply({"params": params, "cache": cache}, ids,
                               mutable=["cache"], paging=pg)
        return step

    def shapes(rows, tokens):
        return (cache, s((rows, tokens)), s((rows, per_seq)), s((rows,)),
                s((rows,)))

    def compiled(fn, tree, rows, tokens):
        return jax.jit(fn).lower(tree, *shapes(rows, tokens)).compile()

    def form_for(formats, tokens):
        return (lookup_form(_Lies(formats[table]), tokens) if table else None)

    # how the backend lays each leaf when nobody asks: read off a compiled
    # program (the decode program; a layout follows shape and type alone)
    _, rows, tokens, _ = programs[0]
    lies = compiled(step_of(False, None), params, rows,
                    tokens).input_formats[0][0]
    asked, _ = weight_layouts.ask(
        step_of(False, form_for(lies, rows * tokens)), params,
        shapes(rows, tokens))
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    moved, placed, placed_lies = [], [], []
    for (path, shape), was, fmt in zip(flat, treedef.flatten_up_to(lies),
                                       treedef.flatten_up_to(asked)):
        move = fmt is not None and fmt.layout != was.layout
        placed.append(jax.ShapeDtypeStruct(
            shape.shape, shape.dtype,
            sharding=Format(fmt.layout, one) if move else one))
        placed_lies.append(fmt if move else was)
        if move:
            moved.append({
                "leaf": weight_layouts.leaf_name(path),
                "shape": list(shape.shape),
                "bytes": math.prod(shape.shape) * shape.dtype.itemsize,
                "major_to_minor": list(fmt.layout.major_to_minor)})
    placed = jax.tree_util.tree_unflatten(treedef, placed)
    placed_lies = jax.tree_util.tree_unflatten(treedef, placed_lies)

    for name, rows, tokens, prefill in programs:
        line = {"cell": cell["name"], "program": name}
        for side, tree, formats in (("parent", params, lies),
                                    ("change", placed, placed_lies)):
            line[side] = _copies(weight_layouts, compiled(
                step_of(prefill, form_for(formats, rows * tokens)), tree,
                rows, tokens).as_text(), "params")
        yield line
    yield {"cell": cell["name"], "leaves": len(flat),
           "leaves_moved": len(moved),
           "bytes_moved": sum(m["bytes"] for m in moved), "moved": moved}


def described(args, weight_layouts):
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from deepspeed_tpu.moe import dropless
    from deepspeed_tpu.ops import attention as attn_mod
    from deepspeed_tpu.utils.compat import compilation_cache_off
    from perfbench import run as bench

    # the chip's kernels, as on the chip (``jax.default_backend()`` is the
    # CPU here); and no persistent cache: a program compiled for a
    # described chip cannot be read back without one
    attn_mod._FORCE_DECODE_KERNEL = True
    dropless.expert_ffn = functools.partial(dropless.expert_ffn,
                                            use_kernel=True)
    with compilation_cache_off():
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        one = SingleDeviceSharding(desc.devices[0])
        for name in args.cells:
            for line in probe_cell(bench.load_cell(name), one,
                                   weight_layouts, args.buckets):
                print(json.dumps(line), flush=True)


# ---------------------------------------------------------------------------
# on the chip: the programs a cell's engine runs

def running(args, weight_layouts):
    import jax
    import numpy as np

    from deepspeed_tpu.telemetry import compile_watch
    from deepspeed_tpu.utils.compat import arm_compilation_cache
    from perfbench import byname, run as bench

    arm_compilation_cache()
    compile_watch.install()
    # (an array's shape WITH how it lies: a program is compiled for the
    # layout a committed argument has)
    shape_of = lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=x.format if isinstance(x, jax.Array)
        else getattr(x, "sharding", None))
    for name in args.cells:
        cell = bench.load_cell(name)
        dev = bench.check_device(int(cell["chips"]))
        job = byname.module("jobs", cell["job"])
        t0 = time.perf_counter()
        state = job.setup(cell, args.seed, dev)
        try:
            took, counted = time.perf_counter() - t0, compile_watch.snapshot()
            srv = state["srv"]
            # the engine is driven from here on, not by the gateway's pump
            state["gateway"].close()
            built = [("decode", srv.__dict__, "_decode_fn")]
            built += [(f"chunk_T{t}", srv._chunk_fns, t)
                      for t in srv._chunk_fns]
            built += [(f"prefill_T{t}", srv._prefill_fns, t)
                      for t in srv._prefill_fns]
            seen = {}

            def spy(program, fn):
                def call(*a):
                    seen.setdefault(program, jax.tree_util.tree_map(
                        shape_of, a))
                    return fn(*a)
                return call

            fns = {program: owner[key] for program, owner, key in built}
            for program, owner, key in built:
                owner[key] = spy(program, fns[program])
            hi = state["mix"]["prompt_len"]["max"]
            rng = np.random.default_rng([int(args.seed), 17])
            for t in sorted(srv._prefill_fns) or [hi]:
                srv.submit(rng.integers(0, state["vocab"], min(t, hi)),
                           max_new_tokens=2)
                srv.drain()
            for program, owner, key in built:
                owner[key] = fns[program]
                if program not in seen:
                    continue
                text = fns[program].lower(
                    *seen[program]).compile().as_text()
                if args.dump:
                    os.makedirs(args.dump, exist_ok=True)
                    with open(os.path.join(
                            args.dump, f"{name}.{program}.txt"), "w") as f:
                        f.write(text)
                print(json.dumps({
                    "cell": name, "program": program,
                    **_copies(weight_layouts, text, "qparams"),
                    "all_copies": sum(
                        1 for line in text.splitlines()
                        if " copy(" in line)}), flush=True)
            print(json.dumps({
                "cell": name, "setup_s": took,
                "backend_compiles_in_setup": counted["backend_compiles"],
                "backend_compile_secs": counted["backend_compile_secs"],
                "persistent_cache_hits_in_setup":
                    counted["persistent_cache_hits"],
                "weight_layouts": srv.stats().get("weight_layouts"),
                # the leaves that do not lie first dimension major
                "lying_otherwise": {
                    weight_layouts.leaf_name(path): str(leaf.format.layout)
                    for path, leaf in jax.tree_util.tree_flatten_with_path(
                        srv.engine.params)[0]
                    if isinstance(leaf, jax.Array) and tuple(
                        leaf.format.layout.major_to_minor) != tuple(
                            range(leaf.ndim))},
                "memory_peak_bytes": bench.memory_peak_bytes()}),
                flush=True)
        finally:
            job.teardown(state)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--cells", nargs="*")
    parser.add_argument("--buckets", choices=("median", "all"),
                        default="median")
    parser.add_argument("--running", action="store_true")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--root", default=os.path.dirname(HERE))
    parser.add_argument("--dump", default="")
    args = parser.parse_args(argv)
    root = os.path.abspath(args.root)
    sys.path.insert(0, root)
    os.chdir(root)
    args.cells = args.cells or sorted(f[:-5] for f in os.listdir(
        os.path.join(root, "perfbench", "workloads"))
        if f.startswith("serve-"))
    (running if args.running else described)(args, _reader())
    return 0


if __name__ == "__main__":
    sys.exit(main())
