"""On the chip, once a change to the DeepSeek-V2 path: the served programs'
LOGITS against the plain reference's full forward pass, at the published
widths and the benchmark cell's sizes (``model-configs`` guide, 3.3), as
``tools/chip_logits_mimo_v2.py`` does for MiMo-V2 (whose float8 rounding
and harness wrapper it takes).

    chiprun -- python tools/chip_logits_deepseek_v2.py [--seed N]

It builds the cell's engine, then drives the engine's own paged module
with the engine's own pool and tables, as the cell's programs run:

1. a LONG prompt (12,800 tokens by default) through the chunk program, a
   chunk of the cell's ``prefill_chunk_tokens`` at a time (decompressed
   attention over the gathered latent rows), then decode steps through the
   latent pool in the decode program's batch shape, every other slot idle
   (absorbed attention, the latent kernel);
2. a SHORT prompt whose last chunk is not full, the same way;

each against ``perfbench/reference_deepseek_v2.py`` (float32, ``highest``,
not absorbed, no cache) over the same ids, the reference taking the
PROGRAM's routed sets; the logits are compared at the last
``--positions`` prompt positions and at every decode step (a whole
context's logits do not fit the chip: the reference's head is taken
there alone). Then the CONTROLS on the short prompt, which have to FAIL
what bfloat16 passes: ``latent``: the row a token keeps rounded to float8
(e4m3) on its way into the pool, against the logits' limits; ``kvb``:
``W_kvb``'s absorbed halves in float8 (the decode steps alone read them),
against the logits' limits at the decode steps; ``experts``: the expert
matrices in float8, against the cell's limit on each sparse layer;
``gate``: the gate's input in bfloat16, against the cell's limit on the
gate's margin.

``--through-check latent|kvb|experts|gate`` runs the CELL itself through
the harness with that control in force and exits 0 only if the harness's
own ``correct`` comes out false.
"""

import argparse
import importlib.util
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# relative to the largest |logit| of the reference, each between two chip
# readings (PERF.md, PR 45): the bfloat16 programs read p95 0.0075-0.0088
# and rms 0.0014-0.0017 (a long and a short sequence), the latent row in
# float8 0.0488 and 0.0087, ``W_kvb``'s absorbed halves in float8 0.0554 at
# the decode steps (0.0096 at the prompt's positions, which never read
# them) and 0.0050
LIMITS = {"p95_rel": 0.02, "rms_rel": 0.003}
CONTROLS = ("latent", "kvb", "experts", "gate")


def _mimo_tool():
    spec = importlib.util.spec_from_file_location(
        "chip_logits_mimo_v2",
        os.path.join(REPO, "tools", "chip_logits_mimo_v2.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool


def lower_precision(part: str):
    """Put one part of the path into the control ``part`` names, for every
    program traced from here on; returns what undoes it."""
    from deepspeed_tpu.models import deepseek_v2
    from deepspeed_tpu.moe import dropless

    mimo = _mimo_tool()
    low = mimo.through_e4m3
    seams = [(deepseek_v2, "pool_row"), (deepseek_v2, "absorbed_halves"),
             (dropless, "expert_ffn"), (dropless, "route")]
    plain = [getattr(module, name) for module, name in seams]
    pool_row, absorbed_halves = plain[:2]

    def undo():
        for (module, name), was in zip(seams, plain):
            setattr(module, name, was)

    if part in ("experts", "gate"):
        # (the experts' three matrices through float8; the gate's input
        # through bfloat16: the other family's tool has both)
        mimo.lower_precision(part)
    elif part == "latent":
        deepseek_v2.pool_row = lambda c, k_pe, lanes: low(
            pool_row(c, k_pe, lanes))
    elif part == "kvb":
        deepseek_v2.absorbed_halves = lambda w, nope: tuple(
            low(h) for h in absorbed_halves(w, nope))
    else:
        raise ValueError(part)
    return undo


def through_check(part: str, argv, root=None) -> int:
    """The cell through the harness with the control ``part`` in force: 0
    if the harness's ``correct`` is false."""
    import contextlib
    import io

    from perfbench import run as bench

    undo = lower_precision(part)
    out = io.StringIO()
    try:
        with contextlib.redirect_stdout(out):
            rc = bench.main(argv, root=root or bench.HERE)
    finally:
        undo()
    lines = [ln for ln in out.getvalue().splitlines() if ln.startswith("{")]
    print("\n".join(lines), flush=True)
    last = json.loads(lines[-1]) if lines else {}
    print(json.dumps({"through_check": part, "harness_rc": rc,
                      "correct": last.get("correct")}), flush=True)
    return 0 if last.get("correct") is False else 1


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--workload", default="serve-dsv2lite-mla-longdoc")
    ap.add_argument("--root", default=None,
                    help="another copy of perfbench/ (the tests' tiny cell)")
    ap.add_argument("--long-prompt", type=int, default=12800)
    ap.add_argument("--short-prompt", type=int, default=1300)
    ap.add_argument("--steps", type=int, default=48)
    ap.add_argument("--positions", type=int, default=208,
                    help="prompt positions, its last, whose logits are "
                    "compared (with every decode step's)")
    ap.add_argument("--pad", type=int, default=2048,
                    help="the reference runs on ids padded to a multiple")
    ap.add_argument("--through-check", choices=CONTROLS,
                    help="run the cell through the harness with this "
                    "control; the other arguments go to perfbench.run")
    args, rest = ap.parse_known_args(argv)
    if args.through_check:
        return through_check(args.through_check, [
            "--workload", args.workload, "--seed", str(args.seed), *rest],
            args.root)

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
    context = int(cell["traffic_file"]["max_total"])
    chunk = int(cell["serve"]["serving"]["prefill_chunk_tokens"])

    @jax.jit
    def make(key):
        tree = module.init(key, jnp.zeros((1, 8), jnp.int32))
        return jax.tree_util.tree_map(lambda x: x.astype(dtype), tree)

    srv = ServingEngine(deepspeed_tpu.init_inference(
        module, params=make(jax.random.PRNGKey(args.seed % (2 ** 31))),
        dtype=dtype, seed=args.seed, max_out_tokens=context,
        serving=cell["serve"]["serving"]))
    dmodule, params = srv._dmodule, srv.engine.params
    layers = family.sparse_layers(config_file)
    reference = jax.jit(family.reference_logits_given(config_file))
    rng = np.random.default_rng([args.seed, 45])

    def program():
        def fn(p, cache, ids, tables, lengths, num_valid):
            paging = {"block_tables": tables, "lengths": lengths,
                      "num_valid": num_valid, "prefill": False}
            out, v = dmodule.apply({"params": p, "cache": cache}, ids,
                                   mutable=["cache"], paging=paging)
            return out[0], out[1]["routed"], v["cache"]
        return jax.jit(fn, donate_argnums=(1,))

    def serve(cached, slot, prompt_len):
        """One sequence in ``slot``: its logits at the last ``positions``
        prompt positions and every decode step, its routed sets at every
        position, and its ids."""
        rid = f"check-{slot}-{prompt_len}"
        table = srv._slot_table(slot, srv.block_mgr.allocate(
            rid, prompt_len + args.steps))
        tables = jnp.asarray(table[None])
        prompt = rng.integers(0, vocab, prompt_len)
        kept = min(args.positions, prompt_len)
        rows, sets = [], []
        for at in range(0, prompt_len, chunk):
            n = min(chunk, prompt_len - at)
            ids = np.zeros((1, chunk), np.int32)
            ids[0, :n] = prompt[at:at + n]
            lg, routed, srv.cache = cached(
                params, srv.cache, jnp.asarray(ids), tables,
                jnp.asarray([at], jnp.int32), jnp.asarray([n], jnp.int32))
            first = max(prompt_len - kept, at)
            if first < at + n:
                rows.append(np.asarray(lg[0, first - at:n]))
            sets.append(np.asarray(routed[0, :n]))
        slots = srv.config.decode_slots
        all_tables = np.zeros((slots, len(table)), np.int32)
        all_tables[slot] = table
        tokens = list(prompt)
        nxt = int(rows[-1][-1].argmax())
        for _ in range(args.steps - 1):
            tokens.append(nxt)
            lengths = np.zeros((slots,), np.int32)
            lengths[slot] = len(tokens) - 1
            last = np.zeros((slots, 1), np.int32)
            last[slot] = nxt
            lg, routed, srv.cache = cached(
                params, srv.cache, jnp.asarray(last), jnp.asarray(all_tables),
                jnp.asarray(lengths), jnp.ones((slots,), jnp.int32))
            rows.append(np.asarray(lg[slot]))
            sets.append(np.asarray(routed[slot]))
            nxt = int(rows[-1][-1].argmax())
        srv.block_mgr.release(rid)
        return (np.concatenate(rows), np.concatenate(sets),
                np.asarray(tokens, np.int32), prompt_len, kept)

    layer_error = {}

    def compare(name, low, served):
        got, sets, ids, prompt_len, kept = served
        n = len(ids)
        padded = np.zeros((1, -(-n // args.pad) * args.pad), np.int32)
        padded[0, :n] = ids
        given = np.full((1, padded.shape[1], len(layers),
                         sets.shape[1] // len(layers)), -1, np.int32)
        given[0, :n] = sets.reshape(n, *given.shape[2:])
        at = np.arange(prompt_len - kept, n)
        # one shape of positions whatever the prompt: one compiled head
        wide = np.minimum(np.arange(args.positions + args.steps)
                          + prompt_len - kept, padded.shape[1] - 1)
        want, seen = reference(params, jnp.asarray(padded),
                               jnp.asarray(given), jnp.asarray(wide,
                                                               jnp.int32))
        want = np.asarray(want)[0, :len(at)]
        top = float(np.abs(want).max())
        diff = got.astype(np.float64) - want
        rel = np.abs(diff).max(-1) / top                    # per position
        out = {"what": name, "seed": args.seed, "positions": int(len(at)),
               "prompt": int(prompt_len), "max_rel": float(rel.max()),
               "p95_rel": float(np.percentile(rel, 95)),
               "rms_rel": float(np.sqrt((diff ** 2).mean())) / top,
               # the decode steps alone: where the absorbed halves show
               "decode_p95_rel": float(np.percentile(rel[kept:], 95)),
               "prefill_p95_rel": float(np.percentile(rel[:kept], 95)),
               "argmax_agree": float(
                   (got.argmax(-1) == want.argmax(-1)).mean()),
               "largest_logit": top,
               "routed_sets_differ": float(
                   np.asarray(seen["differs"])[:, 0, :n].mean()),
               "routed_margin": float(
                   np.asarray(seen["margin"])[:, 0, :n].max())}
        if low not in layer_error:
            layer_error[low] = jax.jit(family.expert_layer_error(
                config_file, srv.engine.module.config))
        valid = jnp.arange(padded.shape[1]) < n
        read = [layer_error[low](params[name_], seen["inputs"][at_, 0], valid)
                for at_, name_ in enumerate(layers)]
        del seen
        out["expert_error"] = [float(e) for e, _ in read]
        out["gate_margin"] = max(float(m) for _, m in read)
        out["inside"] = bool(out["p95_rel"] <= LIMITS["p95_rel"]
                             and out["rms_rel"] <= LIMITS["rms_rel"]
                             and out["decode_p95_rel"] <= LIMITS["p95_rel"])
        out["experts_inside"] = bool(
            max(out["expert_error"]) <= job.EXPERT_ERROR_MAX
            and out["gate_margin"] <= job.GATE_MARGIN_MAX
            and out["routed_margin"] <= job.ROUTED_MARGIN_MAX)
        print(json.dumps(out), flush=True)
        return out

    last = srv.config.decode_slots - 1
    cached = program()
    results = [
        compare("bf16: a long prompt in chunks + decode", False,
                serve(cached, 1, args.long_prompt)),
        compare("bf16: a short prompt in chunks (the last not full) + "
                "decode", False, serve(cached, last, args.short_prompt))]
    controls = {}
    for part in CONTROLS:
        undo = lower_precision(part)
        try:
            low = program()    # traced at its first call, ``part`` in force
            controls[part] = compare(
                f"control {part}: a short prompt in chunks + decode", part,
                serve(low, 1, args.short_prompt))
        finally:
            undo()
    ok = (all(r["inside"] and r["experts_inside"] for r in results)
          and not controls["latent"]["inside"]
          and not controls["kvb"]["inside"]
          and not controls["experts"]["experts_inside"]
          and not controls["gate"]["experts_inside"])
    print(json.dumps({
        "seed": args.seed, "device": dev["kind"],
        "limits": {**LIMITS, "expert_error": job.EXPERT_ERROR_MAX,
                   "gate_margin": job.GATE_MARGIN_MAX,
                   "routed_margin": job.ROUTED_MARGIN_MAX},
        "passes": ok,
        "bf16_inside": [r["inside"] and r["experts_inside"] for r in results],
        "controls_inside": {name: [c["inside"], c["experts_inside"]]
                            for name, c in controls.items()},
        "attention_paths": srv.stats()["attention_paths"]}), flush=True)
    srv.destroy()
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
