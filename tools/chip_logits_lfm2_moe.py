"""On the chip, once a change to the LFM2-MoE path: the served programs'
LOGITS against the plain reference's full forward pass, at the published
widths and the benchmark cell's sizes (``model-configs`` guide, 3.3), as
``tools/chip_logits_mimo_v2.py`` does for MiMo-V2 (whose helpers it takes).

    chiprun -- python tools/chip_logits_lfm2_moe.py [--seed N]

It builds the cell's engine, then drives the engine's own paged module
with the engine's own pools and tables:

1. a prompt that does NOT fill its bucket through the whole-prompt
   prefill program, logits at every prompt position; then decode steps
   through the cache and the convolutions' state in the decode program's
   batch shape (every other slot idle);
2. a SHORTER prompt in the SAME slot (the state its last tenant left must
   not be seen), whole-prompt, then decode;
3. a third prompt through chunked prefill (every chunk past the first
   starts from the stored state), then decode;

each against ``perfbench/reference_lfm2_moe.py`` (float32, ``highest``)
over the same ids, the reference taking the PROGRAM's routed sets. Then
the CONTROLS, which have to FAIL what bfloat16 passes: ``conv``: the
convolutions' ``W_in`` and their state through float8 (e4m3), against the
logits' limits; ``experts``: the expert matrices through float8, against
the cell's limit on each sparse layer (``jobs/serve_counted.py``);
``state``: the state taken at the bucket's END (the padding reaches it),
against the logits' limits at the decode steps; ``stale``: the state not
restarted for a slot's next request (only the first two positions of a
prompt feel it directly); ``gate``: the gate's input through bfloat16,
against the cell's limit on the gate's margin.

``--through-check conv|experts|state|stale|gate`` runs the CELL itself through
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
# readings (PERF.md, PR 43): the bfloat16 programs read p95 0.0184-0.0199
# and rms 0.0036-0.0039 (three sequences), the convolutions' W_in and
# state in float8 0.199 and 0.0385
LIMITS = {"p95_rel": 0.06, "rms_rel": 0.012}
CONTROLS = ("conv", "experts", "state", "stale", "gate")


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
    import jax.numpy as jnp

    from deepspeed_tpu.models import lfm2_moe
    from deepspeed_tpu.moe import dropless

    mimo = _mimo_tool()
    low = mimo.through_e4m3
    # what a control may replace, as it stood
    seams = [(lfm2_moe, "gated_inputs"), (lfm2_moe, "short_conv"),
             (lfm2_moe, "conv_state_in"), (dropless, "expert_ffn"),
             (dropless, "route")]
    plain = [getattr(module, name) for module, name in seams]
    gated_inputs, short_conv = plain[:2]

    def undo():
        for (module, name), was in zip(seams, plain):
            setattr(module, name, was)

    if part in ("experts", "gate"):
        # (the experts' three matrices through float8; the gate's input
        # through bfloat16: the other family's tool has both)
        mimo.lower_precision(part)
    elif part == "conv":
        lfm2_moe.gated_inputs = lambda u, w_in: gated_inputs(u, low(w_in))

        def in_float8(z, taps, state, num_valid):
            c, new = short_conv(z, taps, low(state), num_valid)
            return c, low(new)

        lfm2_moe.short_conv = in_float8
    elif part == "state":
        # the state after the bucket's last position, padding and all
        lfm2_moe.short_conv = lambda z, taps, state, num_valid: short_conv(
            z, taps, state, jnp.full_like(num_valid, z.shape[1]))
    elif part == "stale":
        lfm2_moe.conv_state_in = lambda pool, index, rows, lengths: pool[
            index, rows]
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
    ap.add_argument("--workload", default="serve-lfm2-conv-chat")
    ap.add_argument("--root", default=None,
                    help="another copy of perfbench/ (the tests' tiny cell)")
    ap.add_argument("--prompt", type=int, default=300)
    ap.add_argument("--second-prompt", type=int, default=90)
    ap.add_argument("--chunked-prompt", type=int, default=600)
    ap.add_argument("--chunk", type=int, default=256)
    ap.add_argument("--steps", type=int, default=48)
    ap.add_argument("--pad", type=int, default=512,
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
    dmodule, params = srv._dmodule, srv.engine.params
    layers = family.sparse_layers(config_file)
    reference = jax.jit(family.reference_logits_given(config_file))
    rng = np.random.default_rng([args.seed, 43])

    def programs():
        def program(prefill):
            def fn(p, cache, ids, tables, lengths, num_valid):
                paging = {"block_tables": tables, "lengths": lengths,
                          "num_valid": num_valid, "prefill": prefill}
                out, v = dmodule.apply({"params": p, "cache": cache}, ids,
                                       mutable=["cache"], paging=paging)
                return out[0], out[1]["routed"], v["cache"]
            return jax.jit(fn, donate_argnums=(1,))
        return program(True), program(False)

    def serve(progs, slot, prompt_len, chunk):
        """One sequence in ``slot``: its logits and routed sets at every
        prompt position and every decode step, and its ids."""
        whole, cached = progs
        rid = f"check-{slot}-{prompt_len}"
        table = srv._slot_table(slot, srv.block_mgr.allocate(
            rid, prompt_len + args.steps))
        tables = jnp.asarray(table[None])
        prompt = rng.integers(0, vocab, prompt_len)
        rows, sets = [], []
        if chunk:
            for at in range(0, prompt_len, chunk):
                n = min(chunk, prompt_len - at)
                ids = np.zeros((1, chunk), np.int32)
                ids[0, :n] = prompt[at:at + n]
                lg, routed, srv.cache = cached(
                    params, srv.cache, jnp.asarray(ids), tables,
                    jnp.asarray([at], jnp.int32), jnp.asarray([n], jnp.int32))
                rows.append(np.asarray(lg[0, :n]))
                sets.append(np.asarray(routed[0, :n]))
        else:
            width = next(b for b in srv.buckets if b >= prompt_len)
            ids = np.zeros((1, width), np.int32)
            ids[0, :prompt_len] = prompt
            lg, routed, srv.cache = whole(
                params, srv.cache, jnp.asarray(ids), tables,
                jnp.zeros((1,), jnp.int32),
                jnp.asarray([prompt_len], jnp.int32))
            rows.append(np.asarray(lg[0, :prompt_len]))
            sets.append(np.asarray(routed[0, :prompt_len]))
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
                np.asarray(tokens, np.int32), prompt_len)

    layer_error = {}

    def compare(name, low, served):
        got, sets, ids, prompt_len = served
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
        at = np.abs(diff).max(-1) / top                    # per position
        out = {"what": name, "seed": args.seed, "positions": int(n),
               "prompt": int(prompt_len), "max_rel": float(at.max()),
               "p95_rel": float(np.percentile(at, 95)),
               "rms_rel": float(np.sqrt((diff ** 2).mean())) / top,
               # the decode steps alone: where a wrong state shows
               "decode_p95_rel": float(np.percentile(at[prompt_len:], 95)),
               "decode_first_two_rel": [float(x) for x in
                                        at[prompt_len:prompt_len + 2]],
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
            max(out["expert_error"]) <= serve_counted.EXPERT_ERROR_MAX
            and out["gate_margin"] <= serve_counted.GATE_MARGIN_MAX)
        print(json.dumps(out), flush=True)
        return out

    last = srv.config.decode_slots - 1
    progs = programs()
    results = [
        compare("bf16: whole-prompt prefill (bucket not filled) + decode",
                False, serve(progs, 1, args.prompt, 0)),
        compare("bf16: a shorter prompt in the same slot + decode",
                False, serve(progs, 1, args.second_prompt, 0)),
        compare("bf16: chunked prefill + decode", False,
                serve(progs, last, args.chunked_prompt, args.chunk))]
    controls = {}
    for part in CONTROLS:
        undo = lower_precision(part)
        try:
            low = programs()    # traced at their first call, ``part`` in force
            # slot 1 again: its last tenant's state is there for ``stale``
            controls[part] = compare(
                f"control {part}: whole-prompt prefill + decode", part,
                serve(low, 1, args.second_prompt if part == "stale"
                      else args.prompt, 0))
        finally:
            undo()
    ok = (all(r["inside"] and r["experts_inside"] for r in results)
          and not controls["conv"]["inside"]
          and not controls["experts"]["experts_inside"]
          and not controls["state"]["inside"]
          and not controls["gate"]["experts_inside"])
    print(json.dumps({
        "seed": args.seed, "device": dev["kind"],
        "limits": {**LIMITS, "expert_error": serve_counted.EXPERT_ERROR_MAX,
                   "gate_margin": serve_counted.GATE_MARGIN_MAX},
        "passes": ok,
        "bf16_inside": [r["inside"] and r["experts_inside"] for r in results],
        "controls_inside": {name: [c["inside"], c["experts_inside"]]
                            for name, c in controls.items()},
        "attention_paths": srv.stats()["attention_paths"]}), flush=True)
    srv.destroy()
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
