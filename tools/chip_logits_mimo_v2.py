"""On the chip, once a change to the MiMo-V2 path: the served programs'
LOGITS against the plain reference's full forward pass, at the published
widths and the benchmark cell's sizes (``model-configs`` guide, 3.3).

    chiprun -- python tools/chip_logits_mimo_v2.py [--seed N]

It builds the cell's engine (``perfbench`` configuration, family and
serving block), then drives the engine's own paged module with the
engine's own pool and tables:

1. a prompt past the window through the whole-prompt prefill program (the
   ring has wrapped), logits at every prompt position;
2. decode steps through the cache (the Pallas kernels), greedy, the
   logits of every step;
3. a second prompt through chunked prefill (the cached XLA path over the
   ring and the block table), then decode steps;

and compares each with ``perfbench/reference_mimo_v2.py`` (float32,
``highest``) over the same ids, the reference taking the PROGRAM's routed
sets in place of its own (the serving programs return them under
``serving.routed_experts_kept``): a bfloat16 program chooses another set
than a float32 gate in one (token, layer) pair in ten, and a flipped set
is a whole expert's term, not arithmetic. Then the CONTROLS, which have to
FAIL what bfloat16 passes: every matrix but the gate's rounded to float8
(e4m3) before the program multiplies it, against the logits' limits; and
the expert matrices ALONE in float8, which on a chip that holds one expert
in sixteen the logits hardly feel, against the cell's own limit on each
sparse layer (``jobs/serve_counted.py``: ``EXPERT_ERROR_MAX``), read here
for both. ``--through-check experts|gate`` runs the CELL itself through
the harness with that part in the lower precision (the expert matrices in
float8; the gate's input in bfloat16) and exits 0 only if the harness's
own ``correct`` comes out false.

Two numbers a logits comparison, both relative to the largest |logit| of
the reference: the 95th percentile over positions of a position's largest
difference, and the root mean square difference. The limits (``LIMITS``)
lie between the bfloat16 readings and the float8 readings (PERF.md gives
both).
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# relative to the largest |logit| (PERF.md, PR 39, gives the readings on
# both sides)
LIMITS = {"p95_rel": 0.02, "rms_rel": 0.0045}


def through_e4m3(w):
    """``w`` rounded to float8 (e4m3fn: 3 mantissa bits, subnormals under
    2^-6, saturating at 448; nearest, ties to even) and back, BY
    ARITHMETIC. A pair of converts is not enough on the chip: its compiler
    may keep the excess precision of a convert pair it fuses
    (``xla_allow_excess_precision``), and then the control rounds nothing
    (the expert matrices 'in float8' read exactly bfloat16's error there:
    PERF.md, PR 39)."""
    import jax.numpy as jnp

    x = w.astype(jnp.float32)
    _, exponent = jnp.frexp(x)                      # |x| in [2^(e-1), 2^e)
    step = jnp.exp2(jnp.maximum(exponent - 1, -6).astype(jnp.float32) - 3.0)
    return jnp.clip(jnp.round(x / step) * step, -448.0, 448.0).astype(w.dtype)


def lower_precision(part: str):
    """Put one part of ``moe/dropless.py`` into the precision below, for
    every program traced from here on: ``experts``: the three expert
    matrices through float8 (e4m3); ``gate``: the gate's input through
    bfloat16 (its matmul, sigmoid and top-k stay float32). ``plain`` is
    no precision: the experts' dense XLA form in the kernel's place."""
    import jax.numpy as jnp

    from deepspeed_tpu.moe import dropless

    if part == "experts":
        plain = dropless.expert_ffn

        def expert_ffn(x, experts, weights, gate, up, down, **kw):
            low = through_e4m3
            return plain(x, experts, weights, low(gate), low(up), low(down),
                         **kw)

        dropless.expert_ffn = expert_ffn
    elif part == "plain":
        # no precision at all: the dense XLA form of the experts where the
        # Pallas grouped matmul would run, for a reading of what it buys
        grouped = dropless.expert_ffn

        def expert_ffn(*args, **kw):
            return grouped(*args, **{**kw, "use_kernel": False})

        dropless.expert_ffn = expert_ffn
    elif part == "gate":
        plain_route = dropless.route

        def route(x, router_kernel, selection_bias, top_k, **kw):
            return plain_route(x.astype(jnp.bfloat16), router_kernel,
                               selection_bias, top_k, **kw)

        dropless.route = route
    else:
        raise ValueError(part)


def through_check(part: str, argv, root=None) -> int:
    """The cell through the harness with ``part`` in the lower precision:
    0 if the harness's ``correct`` is false."""
    import contextlib
    import io

    from perfbench import run as bench

    lower_precision(part)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
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
    ap.add_argument("--workload", default="serve-mimo-hybrid-mixed")
    ap.add_argument("--root", default=None,
                    help="another copy of perfbench/ (the tests' tiny cell)")
    ap.add_argument("--prompt", type=int, default=700)
    ap.add_argument("--chunked-prompt", type=int, default=600)
    ap.add_argument("--chunk", type=int, default=256)
    ap.add_argument("--steps", type=int, default=48)
    ap.add_argument("--more-seeds", type=int, default=0,
                    help="further seeds, the first comparison only")
    ap.add_argument("--pad", type=int, default=512,
                    help="the reference runs on ids padded to a multiple")
    ap.add_argument("--through-check", choices=("experts", "gate", "plain"),
                    help="run the cell through the harness with this part "
                    "in the lower precision; the other arguments go to "
                    "perfbench.run")
    args, rest = ap.parse_known_args(argv)
    if args.through_check:
        return through_check(args.through_check, [
            "--workload", args.workload, "--seed", str(args.seed), *rest],
            args.root)

    import jax
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.moe import dropless
    from deepspeed_tpu.serving import ServingEngine
    from deepspeed_tpu.utils import device
    from perfbench import run as bench
    from perfbench.jobs import serve_counted

    dev = device.require_device("tpu")
    cell = bench.load_cell(args.workload, args.root or bench.HERE)
    dtype = getattr(jnp, cell["serve"].get("dtype", "bfloat16"))
    family, config_file = cell["family"], cell["config_file"]
    module = family.serving_module(config_file, dtype)
    vocab = family.vocab_size(config_file)
    context = int(cell["traffic_file"]["max_total"])

    @jax.jit
    def make(key):
        tree = module.init(key, jnp.zeros((1, 8), jnp.int32))
        return jax.tree_util.tree_map(lambda x: x.astype(dtype), tree)

    srv = ServingEngine(deepspeed_tpu.init_inference(
        module, params=make(jax.random.PRNGKey(args.seed % (2 ** 31))),
        dtype=dtype, seed=args.seed, max_out_tokens=context,
        serving=cell["serve"]["serving"]))
    dmodule = srv._dmodule
    state = {"params": srv.engine.params, "seed": args.seed}
    layers = family.sparse_layers(config_file)

    def to_fp8(p):
        # every matrix a matmul reads but the gate's, which the source
        # keeps in float32
        return jax.tree_util.tree_map_with_path(
            lambda path, x: through_e4m3(x) if x.ndim >= 2 and getattr(
                path[-1], "key", "") != "router" else x, p)

    def program(prefill, low):
        def fn(p, cache, ids, tables, lengths, num_valid):
            if low == "all":  # rounded inside the program: no second copy
                p = to_fp8(p)
            paging = {"block_tables": tables, "lengths": lengths,
                      "num_valid": num_valid, "prefill": prefill}
            out, v = dmodule.apply({"params": p, "cache": cache}, ids,
                                   mutable=["cache"], paging=paging)
            return out[0], out[1]["routed"], v["cache"]
        return jax.jit(fn, donate_argnums=(1,))

    # (a program is traced at its first call: ``patched`` puts dropless
    # into its precision before that)
    plain = (dropless.expert_ffn, dropless.route)
    programs = {low: (program(True, low), program(False, low))
                for low in (False, "experts", "gate", "all")}
    reference = jax.jit(family.reference_logits_given(config_file))
    layer_errors = {}
    rng = np.random.default_rng([args.seed, 39])

    def patched(low):
        """What is traced from here on has ``low`` in force."""
        dropless.expert_ffn, dropless.route = plain
        if low in ("experts", "gate"):
            lower_precision(low)

    def one(low, slot, prompt_len, chunk):
        """Serve one sequence in ``slot``: its logits and routed sets at
        every prompt position and every decode step, and its ids."""
        p = state["params"]
        whole, cached = programs[low]
        patched(low)
        total = prompt_len + args.steps
        rid = f"check-{slot}"
        table = srv._slot_table(slot, srv.block_mgr.allocate(rid, total))
        tables = jnp.asarray(table[None])
        prompt = rng.integers(0, vocab, prompt_len)
        rows, sets = [], []
        if chunk:
            for at in range(0, prompt_len, chunk):
                n = min(chunk, prompt_len - at)
                ids = np.zeros((1, chunk), np.int32)
                ids[0, :n] = prompt[at:at + n]
                lg, routed, srv.cache = cached(
                    p, srv.cache, jnp.asarray(ids), tables,
                    jnp.asarray([at], jnp.int32), jnp.asarray([n], jnp.int32))
                rows.append(np.asarray(lg[0, :n]))
                sets.append(np.asarray(routed[0, :n]))
        else:
            width = next(b for b in srv.buckets if b >= prompt_len)
            ids = np.zeros((1, width), np.int32)
            ids[0, :prompt_len] = prompt
            lg, routed, srv.cache = whole(
                p, srv.cache, jnp.asarray(ids), tables,
                jnp.zeros((1,), jnp.int32),
                jnp.asarray([prompt_len], jnp.int32))
            rows.append(np.asarray(lg[0, :prompt_len]))
            sets.append(np.asarray(routed[0, :prompt_len]))
        # decode in the cell's own batch shape: this sequence in its slot,
        # every other slot idle
        slots = srv.config.decode_slots
        all_tables = np.zeros((slots, len(table)), np.int32)
        all_tables[slot] = table
        tokens = list(prompt)
        nxt = int(rows[-1][-1].argmax())
        for step in range(args.steps - 1):
            tokens.append(nxt)
            lengths = np.zeros((slots,), np.int32)
            lengths[slot] = len(tokens) - 1
            last = np.zeros((slots, 1), np.int32)
            last[slot] = nxt
            lg, routed, srv.cache = cached(
                p, srv.cache, jnp.asarray(last), jnp.asarray(all_tables),
                jnp.asarray(lengths), jnp.ones((slots,), jnp.int32))
            rows.append(np.asarray(lg[slot]))
            sets.append(np.asarray(routed[slot]))
            nxt = int(rows[-1][-1].argmax())
        srv.block_mgr.release(rid)
        return (np.concatenate(rows), np.concatenate(sets),
                np.asarray(tokens, np.int32))

    def compare(name, low, slot, prompt_len, chunk):
        got, sets, ids = one(low, slot, prompt_len, chunk)
        n = len(ids)
        padded = np.zeros((1, -(-n // args.pad) * args.pad), np.int32)
        padded[0, :n] = ids
        given = np.full((1, padded.shape[1], len(layers),
                         sets.shape[1] // len(layers)), -1, np.int32)
        given[0, :n] = sets.reshape(n, *given.shape[2:])

        def against(given):
            want, seen = reference(state["params"], jnp.asarray(padded),
                                   jnp.asarray(given))
            want = np.asarray(want)[0, :n]
            top = float(np.abs(want).max())
            diff = got.astype(np.float64) - want
            at = np.abs(diff).max(-1) / top          # per position
            return {"max_rel": float(at.max()),
                    "p95_rel": float(np.percentile(at, 95)),
                    "rms_rel": float(np.sqrt((diff ** 2).mean())) / top,
                    "argmax_agree": float(
                        (got.argmax(-1) == want.argmax(-1)).mean()),
                    "largest_logit": top}, seen

        out, seen = against(given)
        out = {"what": name, "seed": state["seed"], "positions": int(n),
               **out,
               "routed_sets_differ": float(
                   np.asarray(seen["differs"])[:, 0, :n].mean()),
               "routed_margin": float(
                   np.asarray(seen["margin"])[:, 0, :n].max())}
        # each sparse layer of the model as the engine holds it, over the
        # reference's own inputs: what the cell's ``correct`` holds
        if low not in layer_errors and low != "all":
            patched(low)
            layer_errors[low] = jax.jit(family.expert_layer_error(
                config_file, srv.engine.module.config))
        # (``all`` rounds inside its own programs; it is the logits' control)
        valid = jnp.arange(padded.shape[1]) < n
        read = [layer_errors[low](state["params"][name_],
                                  seen["inputs"][at, 0], valid)
                for at, name_ in enumerate(layers)] if low != "all" else []
        out["expert_error"] = [float(e) for e, _ in read]
        out["gate_margin"] = max([float(m) for _, m in read], default=None)
        del seen
        if not low:   # what handing the sets over is worth
            own, _ = against(np.full_like(given, -1))
            out["with_the_references_own_sets"] = {
                k: own[k] for k in ("max_rel", "p95_rel", "rms_rel")}
        out["inside"] = bool(out["p95_rel"] <= LIMITS["p95_rel"]
                             and out["rms_rel"] <= LIMITS["rms_rel"])
        out["experts_inside"] = bool(
            read and max(out["expert_error"]) <= serve_counted.EXPERT_ERROR_MAX
            and out["gate_margin"] <= serve_counted.GATE_MARGIN_MAX)
        print(json.dumps(out), flush=True)
        return out

    results = [
        compare("bf16: whole-prompt prefill + decode", False, 1,
                args.prompt, 0),
        compare("bf16: chunked prefill + decode", False,
                srv.config.decode_slots - 1,
                args.chunked_prompt, args.chunk)]
    controls = {
        "experts": compare(
            "control, float8 expert matrices: whole-prompt prefill + decode",
            "experts", 2, args.prompt, 0),
        "gate": compare(
            "control, the gate's input in bfloat16: whole-prompt prefill + "
            "decode", "gate", 2, args.prompt, 0),
        "all": compare(
            "control, float8 matrices: whole-prompt prefill + decode",
            "all", 3, args.prompt, 0)}
    for extra in range(1, args.more_seeds + 1):
        # other weights through the same compiled programs
        state["seed"] = args.seed + extra
        state["params"] = srv.engine.params = None
        state["params"] = srv.engine.params = jax.device_put(
            make(jax.random.PRNGKey(state["seed"] % (2 ** 31)))["params"],
            srv.engine.param_shardings)
        results.append(compare("bf16: whole-prompt prefill + decode", False,
                               1 + extra % 8, args.prompt, 0))
        controls[f"experts, seed {state['seed']}"] = compare(
            "control, float8 expert matrices: whole-prompt prefill + decode",
            "experts", 2, args.prompt, 0)
        controls[f"gate, seed {state['seed']}"] = compare(
            "control, the gate's input in bfloat16: whole-prompt prefill + "
            "decode", "gate", 2, args.prompt, 0)
    # bfloat16 inside both kinds of limit; every matrix in float8 outside
    # the logits'; the experts alone, and the gate alone, outside the
    # sparse layers'
    ok = (all(r["inside"] and r["experts_inside"] for r in results)
          and not controls["all"]["inside"]
          and not any(c["experts_inside"] for name, c in controls.items()
                      if name != "all"))
    print(json.dumps({"seed": args.seed, "device": dev["kind"],
                      "limits": {**LIMITS,
                                 "expert_error": serve_counted.EXPERT_ERROR_MAX,
                                 "gate_margin": serve_counted.GATE_MARGIN_MAX},
                      "passes": ok, "more_seeds": args.more_seeds,
                      "bf16_inside": [r["inside"] and r["experts_inside"]
                                      for r in results],
                      "controls_inside": {
                          name: [c["inside"], c["experts_inside"]]
                          for name, c in controls.items()},
                      "attention_paths": srv.stats()["attention_paths"]}),
          flush=True)
    dropless.expert_ffn, dropless.route = plain
    srv.destroy()
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
