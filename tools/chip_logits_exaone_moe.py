"""On the chip, once a change to the EXAONE-MoE path: the served programs'
LOGITS against the plain reference's full forward pass, at the published
widths and the benchmark cell's sizes (``model-configs`` guide, 3.3).

    chiprun -- python tools/chip_logits_exaone_moe.py [--seed N]

It builds the cell's engine (``perfbench`` configuration, family and
serving block), then drives the engine's own paged module with the
engine's own pool and tables: a prompt through the CHUNK program (chunks of
the cell's ``prefill_chunk_tokens``: the ring gathered and then the chunk's
own rows, the global layer's keys a tile at a time), then decode steps
through the cache (the Pallas kernels over ring and table, past the window
and past a ring's rows), greedy, the logits of every position; and
compares with ``perfbench/reference_exaone_moe.py`` (float32, ``highest``)
over the same ids, the reference taking the PROGRAM's routed sets in place
of its own. Then the CONTROLS, which have to FAIL what bfloat16 passes
(``CONTROLS``): ``all``: every matrix but the gate's through float8;
``pool``: the keys and values through float8 on their way into both pools;
``stale``: one row of every slot's ring never written; ``experts`` and
``gate``: the sparse layer's parts, against the cell's own limits on each
sparse layer (``jobs/serve_counted_exaone_moe.py``).

``--through-check experts|gate|pool|stale`` runs the CELL itself through
the harness with that control in force and exits 0 only if the harness's
own ``correct`` comes out false.

Two numbers a logits comparison, both relative to the largest |logit| of
the reference: the 95th percentile over positions of a position's largest
difference, and the root mean square difference (``LIMITS``: between the
bfloat16 readings and the controls', PERF.md, PR 52).
"""

import argparse
import contextlib
import functools
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

# relative to the largest |logit|, each between two chip readings (my chip
# run, PR 52, call 4, seed 5200000401: 1,100 prompt positions in three
# chunks and 199 decode steps): bfloat16 read p95_rel 0.0077 and rms_rel
# 0.0015; the nearest control, keys and values through float8, 0.0437 and
# 0.0090 (a stale ring row 0.0987 / 0.0104, every matrix in float8 0.387 /
# 0.084; the expert matrices alone 0.0206 / 0.0037, which the sparse
# layers' own limit is there to catch: 0.063-0.111 against bfloat16's
# 0.0033-0.0035)
LIMITS = {"p95_rel": 0.02, "rms_rel": 0.0045}
CONTROLS = ("experts", "gate", "pool", "stale")


@functools.lru_cache(maxsize=None)
def _sibling():
    """``tools/chip_logits_mimo_v2.py``: float8 by arithmetic, and the two
    controls of ``moe/dropless.py``."""
    spec = importlib.util.spec_from_file_location(
        "chip_logits_mimo_v2", os.path.join(HERE, "chip_logits_mimo_v2.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool


@contextlib.contextmanager
def control(part: str, stale_rows: int = 1):
    """Every program traced inside has ``part`` in force: ``experts`` /
    ``gate`` as the sibling has them; ``pool``: the keys and values of both
    kinds of layer rounded to float8 before they are written and attended;
    ``stale``: the first ``stale_rows`` rows of the FIRST block of every
    slot's ring keep what they held, whatever a step writes there. None:
    nothing."""
    from deepspeed_tpu.models import blocks
    from deepspeed_tpu.moe import dropless

    plain = (dropless.expert_ffn, dropless.route, blocks.paged_gqa,
             blocks.ring_gqa)
    sibling = _sibling()
    if part in ("experts", "gate"):
        sibling.lower_precision(part)
    elif part == "pool":
        low = sibling.through_e4m3

        def rounded(step):
            return lambda q, k, v, *rest, **kw: step(q, low(k), low(v),
                                                     *rest, **kw)

        blocks.paged_gqa, blocks.ring_gqa = map(rounded, plain[2:])
    elif part == "stale":
        def ring_gqa(q, k, v, pos, paging, table, k_pool, v_pool, index,
                     *rest, **kw):
            y, k_new, v_new = plain[3](q, k, v, pos, paging, table, k_pool,
                                       v_pool, index, *rest, **kw)
            first = table[:, 0]

            def keep(new, old):
                return new.at[index, first, :stale_rows].set(
                    old[index, first, :stale_rows])

            return y, keep(k_new, k_pool), keep(v_new, v_pool)

        blocks.ring_gqa = ring_gqa
    elif part is not None:
        raise ValueError(part)
    try:
        yield
    finally:
        (dropless.expert_ffn, dropless.route, blocks.paged_gqa,
         blocks.ring_gqa) = plain


def through_check(part: str, argv, root=None, stale_rows: int = 1) -> int:
    """The cell through the harness with ``part`` in force: 0 if the
    harness's ``correct`` is false."""
    import io

    from perfbench import run as bench

    out = io.StringIO()
    with control(part, stale_rows), contextlib.redirect_stdout(out):
        rc = bench.main(argv, root=root or bench.HERE)
    lines = [ln for ln in out.getvalue().splitlines() if ln.startswith("{")]
    print("\n".join(lines), flush=True)
    last = json.loads(lines[-1]) if lines else {}
    print(json.dumps({"through_check": part, "harness_rc": rc,
                      "correct": last.get("correct")}), flush=True)
    return 0 if last.get("correct") is False else 1


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--workload", default="serve-kexaone-reasoning-out")
    ap.add_argument("--root", default=None,
                    help="another copy of perfbench/ (the tests' tiny cell)")
    ap.add_argument("--prompt", type=int, default=1100)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--pad", type=int, default=512,
                    help="the reference runs on ids padded to a multiple")
    ap.add_argument("--stale-rows", type=int, default=1)
    ap.add_argument("--through-check", choices=CONTROLS,
                    help="run the cell through the harness with this "
                    "control; the other arguments go to perfbench.run")
    args, rest = ap.parse_known_args(argv)
    if args.through_check:
        return through_check(args.through_check, [
            "--workload", args.workload, "--seed", str(args.seed), *rest],
            args.root, args.stale_rows)

    import jax
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.serving import ServingEngine
    from deepspeed_tpu.utils import device
    from perfbench import byname
    from perfbench import run as bench

    dev = device.require_device("tpu")
    cell = bench.load_cell(args.workload, args.root or bench.HERE)
    job = byname.module("jobs", cell["job"])
    dtype = getattr(jnp, cell["serve"].get("dtype", "bfloat16"))
    family, config_file = cell["family"], cell["config_file"]
    module = family.serving_module(config_file, dtype)
    vocab = family.vocab_size(config_file)
    chunk = int(cell["serve"]["serving"]["prefill_chunk_tokens"])
    through_e4m3 = _sibling().through_e4m3

    @jax.jit
    def make(key):
        tree = module.init(key, jnp.zeros((1, 8), jnp.int32))
        return jax.tree_util.tree_map(lambda x: x.astype(dtype), tree)

    srv = ServingEngine(deepspeed_tpu.init_inference(
        module, params=make(jax.random.PRNGKey(args.seed % (2 ** 31))),
        dtype=dtype, seed=args.seed,
        max_out_tokens=int(cell["traffic_file"]["max_total"]),
        serving=cell["serve"]["serving"]))
    dmodule, params = srv._dmodule, srv.engine.params
    layers = family.sparse_layers(config_file)
    reference = jax.jit(family.reference_logits_given(config_file))
    rng = np.random.default_rng([args.seed, 52])

    def program(low):
        def fn(p, cache, ids, tables, lengths, num_valid):
            if low == "all":  # rounded inside the program: no second copy
                p = jax.tree_util.tree_map_with_path(
                    lambda path, x: through_e4m3(x) if x.ndim >= 2 and getattr(
                        path[-1], "key", "") != "router" else x, p)
            paging = {"block_tables": tables, "lengths": lengths,
                      "num_valid": num_valid, "prefill": False}
            out, v = dmodule.apply({"params": p, "cache": cache}, ids,
                                   mutable=["cache"], paging=paging)
            return out[0], out[1]["routed"], v["cache"]
        return jax.jit(fn, donate_argnums=(1,))

    def one(low, slot):
        """Serve one sequence in ``slot`` under control ``low``: logits and
        routed sets at every prompt position and decode step, and ids."""
        cached = program(low)
        i32 = lambda x: jnp.asarray(x, jnp.int32)
        rid = f"check-{slot}"
        with control(low if low in CONTROLS else None, args.stale_rows):
            table = srv._slot_table(slot, srv.block_mgr.allocate(
                rid, args.prompt + args.steps))
            prompt = rng.integers(0, vocab, args.prompt)
            rows, sets = [], []
            for at in range(0, args.prompt, chunk):
                n = min(chunk, args.prompt - at)
                ids = np.zeros((1, chunk), np.int32)
                ids[0, :n] = prompt[at:at + n]
                lg, routed, srv.cache = cached(
                    params, srv.cache, i32(ids), i32(table[None]), i32([at]),
                    i32([n]))
                rows.append(np.asarray(lg[0, :n]))
                sets.append(np.asarray(routed[0, :n]))
            # decode in the cell's own batch shape: this sequence in its
            # slot, every other slot idle
            slots = srv.config.decode_slots
            tables = np.zeros((slots, len(table)), np.int32)
            tables[slot] = table
            tokens = list(prompt)
            nxt = int(rows[-1][-1].argmax())
            for _ in range(args.steps - 1):
                tokens.append(nxt)
                lengths = np.zeros((slots,), np.int32)
                lengths[slot] = len(tokens) - 1
                last = np.zeros((slots, 1), np.int32)
                last[slot] = nxt
                lg, routed, srv.cache = cached(
                    params, srv.cache, i32(last), i32(tables), i32(lengths),
                    jnp.ones((slots,), jnp.int32))
                rows.append(np.asarray(lg[slot]))
                sets.append(np.asarray(routed[slot]))
                nxt = int(rows[-1][-1].argmax())
            srv.block_mgr.release(rid)
        return (np.concatenate(rows), np.concatenate(sets),
                np.asarray(tokens, np.int32))

    def compare(name, low, slot):
        got, sets, ids = one(low, slot)
        n = len(ids)
        padded = np.zeros((1, -(-n // args.pad) * args.pad), np.int32)
        padded[0, :n] = ids
        given = np.full((1, padded.shape[1], len(layers),
                         sets.shape[1] // len(layers)), -1, np.int32)
        given[0, :n] = sets.reshape(n, *given.shape[2:])
        want, seen = reference(params, jnp.asarray(padded),
                               jnp.asarray(given))
        want = np.asarray(want)[0, :n]
        top = float(np.abs(want).max())
        diff = got.astype(np.float64) - want
        at = np.abs(diff).max(-1) / top              # per position
        out = {"what": name, "seed": args.seed, "positions": int(n),
               "max_rel": float(at.max()),
               "p95_rel": float(np.percentile(at, 95)),
               "rms_rel": float(np.sqrt((diff ** 2).mean())) / top,
               "decode_p95_rel": float(np.percentile(at[args.prompt:], 95)),
               "argmax_agree": float(
                   (got.argmax(-1) == want.argmax(-1)).mean()),
               "largest_logit": top,
               "routed_sets_differ": float(
                   np.asarray(seen["differs"])[:, 0, :n].mean()),
               "routed_margin": float(
                   np.asarray(seen["margin"])[:, 0, :n].max())}
        read = []
        if low in (False, "experts", "gate"):
            # each sparse layer of the model as the engine holds it, over
            # the reference's own inputs: what the cell's ``correct`` holds
            with control(low or None):
                layer_error = jax.jit(family.expert_layer_error(
                    config_file, srv.engine.module.config))
                valid = jnp.arange(padded.shape[1]) < n
                read = [layer_error(params[name_], seen["inputs"][at_, 0],
                                    valid) for at_, name_ in enumerate(layers)]
        del seen
        out["expert_error"] = [float(e) for e, _ in read]
        out["gate_margin"] = max([float(m) for _, m in read], default=None)
        out["inside"] = bool(out["p95_rel"] <= LIMITS["p95_rel"]
                             and out["rms_rel"] <= LIMITS["rms_rel"])
        out["experts_inside"] = bool(
            read and max(out["expert_error"]) <= job.EXPERT_ERROR_MAX
            and out["gate_margin"] <= job.GATE_MARGIN_MAX)
        print(json.dumps(out), flush=True)
        return out

    what = "chunked prefill + decode through ring and table"
    base = compare(f"bf16: {what}", False, 1)
    slots = srv.config.decode_slots
    controls = {low: compare(f"control, {why}: {what}", low, slot % slots)
                for slot, (low, why) in enumerate((
                    ("all", "float8 matrices"),
                    ("pool", "float8 keys and values"),
                    ("stale", f"{args.stale_rows} stale ring row(s)"),
                    ("experts", "float8 expert matrices"),
                    ("gate", "the gate's input in bfloat16")), 2)}
    # bfloat16 inside both kinds of limit; float8 matrices, a float8 pool
    # and a stale ring row outside the logits'; the experts alone, and the
    # gate alone, outside the sparse layers'
    ok = (base["inside"] and base["experts_inside"]
          and not any(controls[c]["inside"] for c in ("all", "pool", "stale"))
          and not any(controls[c]["experts_inside"]
                      for c in ("experts", "gate")))
    print(json.dumps({
        "seed": args.seed, "device": dev["kind"],
        "limits": {**LIMITS, "expert_error": job.EXPERT_ERROR_MAX,
                   "gate_margin": job.GATE_MARGIN_MAX},
        "passes": ok, "bf16_inside": [base["inside"],
                                      base["experts_inside"]],
        "controls_inside": {name: [c["inside"], c["experts_inside"]]
                            for name, c in controls.items()},
        "attention_paths": srv.stats()["attention_paths"]}), flush=True)
    srv.destroy()
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
