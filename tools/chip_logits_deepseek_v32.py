"""On the chip, once a change to the DeepSeek-V3.2 path: the served
programs' LOGITS against the plain reference's full forward pass, at the
published widths and the benchmark cell's sizes (``model-configs`` guide,
3.3), as ``tools/chip_logits_deepseek_v2.py`` does for DeepSeek-V2.

    chiprun -- python tools/chip_logits_deepseek_v32.py [--seed N]

It builds the cell's engine, then drives the engine's own paged module with
the engine's own pools and tables, as the cell's programs run: a prompt of
16,384 tokens through the chunk program, a chunk of the cell's
``prefill_chunk_tokens`` at a time (index scores, the selection as a mask,
the masked decompressed attention), then 256 decode steps through BOTH
pools in the decode program's batch shape, every other slot idle (the
selection as positions, the absorbed attention over the gathered rows);
against ``perfbench/reference_deepseek_v32.py`` (float32, ``highest``, not
absorbed, no cache, the scores of every pair) over the same ids, a layer a
call, TWICE: the reference attending THE PROGRAM'S chosen keys (what the
cell's check compares), and the reference attending ITS OWN: the distance
between the two readings is what the flipped keys cost. The routed sets
are the program's both times. Logits are compared at the last
``--positions`` prompt positions and at every decode step.

Then the CONTROLS on a shorter prompt, which have to FAIL what the served
program passes: ``ignored``: every live key attended; ``recent``: the most
recent 2,048 keys chosen instead of the best; ``not-written``: a chunk's
index rows not kept in ``index_pool``. Each is seen by the selection's
margin (``jobs/serve_counted_deepseek_v32.py``). And the lower precisions:
``latent``: the row a token keeps in ``latent_pool`` rounded to float8 (the
sparse attention's keys and values, in a chunk and in a step), seen by the
logits; ``experts``: the expert matrices in float8; ``gate``: the gate's
input in bfloat16, both seen by the cell's limits on a sparse layer.

``--through-check <control>[,<control>]`` runs the CELL itself through the
harness with the controls in force and exits 0 only if the harness's own
``correct`` comes out false.
"""

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# relative to the largest |logit| of the reference, attending the program's
# sets: the served program read (my chip runs, PR 59; PERF.md, section 6)
LIMITS = {"p95_rel": 0.02, "rms_rel": 0.004}
CONTROLS = ("ignored", "recent", "not-written")
# the lower-precision controls: the pooled latent row in float8; and
# ``tools/chip_logits_mimo_v2.py``'s two for the sparse layers, the expert
# matrices in float8 and the gate's input in bfloat16
PRECISION_CONTROLS = ("latent", "experts", "gate")


def _mimo_tool():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "chip_logits_mimo_v2",
        os.path.join(REPO, "tools", "chip_logits_mimo_v2.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool


def lower_precision(part: str):
    """The pooled latent row through float8 (by arithmetic: the chip's
    compiler keeps the excess precision of a pair of converts), the expert
    matrices through float8 or the gate's input through bfloat16 (the other
    family's tool has both); returns what undoes it."""
    from deepspeed_tpu.models import deepseek_v32
    from deepspeed_tpu.moe import dropless

    plain = dropless.expert_ffn, dropless.route, deepseek_v32.pool_row

    def undo():
        dropless.expert_ffn, dropless.route, deepseek_v32.pool_row = plain

    if part == "latent":
        # (a token's row ``[B, T, lanes]``; the step's absorbed query,
        # which the same function lays out a head, stays as it is)
        low, pool_row = _mimo_tool().through_e4m3, plain[2]
        deepseek_v32.pool_row = lambda c, k_pe, lanes: (
            low(pool_row(c, k_pe, lanes)) if c.ndim == 3
            else pool_row(c, k_pe, lanes))
    else:
        _mimo_tool().lower_precision(part)
    return undo


def wrong_selection(part: str):
    """Put the control ``part`` in force for every program traced from here
    on; returns what undoes it."""
    import jax.numpy as jnp

    from deepspeed_tpu.models.deepseek_v32 import SparseLatentAttention
    from deepspeed_tpu.ops import dsa_index_select as select_op

    seams = [(select_op, "select_mask"), (select_op, "select_positions"),
             (select_op, "index_scores"), (SparseLatentAttention, "_paged")]
    plain = [module.__dict__[name] for module, name in seams]
    select_mask, select_positions, index_scores, paged = plain

    def undo():
        for (module, name), was in zip(seams, plain):
            setattr(module, name, was)

    if part == "ignored":       # every live key: k as wide as the table
        select_op.select_mask = lambda scores, valid_of, k, live: \
            select_mask(scores, valid_of, scores.shape[-1], live)
        select_op.select_positions = lambda scores, valid, k: \
            select_positions(scores, valid, scores.shape[-1])
    elif part == "recent":      # a key's score its position
        # (the real scores ride along at 1e-30 of themselves, under a
        # position's rounding: the indexer's weights stay operands of the
        # programs, which lay their weights out by what they read)
        select_op.index_scores = lambda *a: (
            jnp.arange(a[-1], dtype=jnp.float32)
            + 1e-30 * jnp.nan_to_num(index_scores(*a), neginf=0.0))
    elif part == "not-written":  # a chunk's index rows not kept
        # (the chunk's own queries score them; the pool it hands on is
        # the one it was handed, so no later call finds them)
        def forgetful(self, *args):
            y, pools, seen = paged(self, *args)
            k_i, handed = args[6], args[9]
            if k_i.shape[1] > 1:
                pools = dict(pools, index_pool=handed["index_pool"])
            return y, pools, seen
        SparseLatentAttention._paged = forgetful
    else:
        raise ValueError(part)
    return undo


def through_check(part: str, argv, root=None) -> int:
    """The cell through the harness with the control ``part`` in force: 0
    if the harness's ``correct`` is false."""
    import contextlib
    import io

    from perfbench import run as bench

    undos = [(lower_precision if one in PRECISION_CONTROLS
              else wrong_selection)(one) for one in part.split(",")]
    out = io.StringIO()
    try:
        with contextlib.redirect_stdout(out):
            rc = bench.main(argv, root=root or bench.HERE)
    finally:
        for undo in reversed(undos):
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
    ap.add_argument("--workload", default="serve-dsv32-dsa-longctx")
    ap.add_argument("--root", default=None,
                    help="another copy of perfbench/ (the tests' tiny cell)")
    ap.add_argument("--prompt", type=int, default=16384)
    ap.add_argument("--control-prompt", type=int, default=4096)
    ap.add_argument("--steps", type=int, default=256)
    ap.add_argument("--control-steps", type=int, default=32)
    ap.add_argument("--positions", type=int, default=256,
                    help="prompt positions, its last, whose logits are "
                    "compared (with every decode step's)")
    ap.add_argument("--pad", type=int, default=8192,
                    help="the reference runs on ids padded to a multiple")
    ap.add_argument("--controls", default=",".join(
        CONTROLS + PRECISION_CONTROLS), help="the controls to run, by name")
    ap.add_argument("--through-check", metavar="CONTROL[,CONTROL]",
                    help="run the cell through the harness with these "
                    "controls; the other arguments go to perfbench.run")
    args, rest = ap.parse_known_args(argv)
    if args.through_check:
        unknown = set(args.through_check.split(",")) - set(
            CONTROLS + PRECISION_CONTROLS)
        if unknown:
            ap.error(f"--through-check: no control {sorted(unknown)}")
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
        serving={**cell["serve"]["serving"], "routed_experts_kept": 1}))
    balanced = family.balanced_weights(config_file)
    if balanced is not None:
        srv.engine.params = balanced(srv.engine.params, args.seed)
    dmodule, params = srv._dmodule, srv.engine.params
    layers = family.sparse_layers(config_file)
    reference = family.reference_by_layer(config_file)
    rng = np.random.default_rng([args.seed, 59])

    def program():
        def fn(p, cache, ids, tables, lengths, num_valid):
            paging = {"block_tables": tables, "lengths": lengths,
                      "num_valid": num_valid, "prefill": False}
            (logits, aux), v = dmodule.apply(
                {"params": p, "cache": cache}, ids, mutable=["cache"],
                paging=paging)
            return logits, aux["routed"], aux["selected"], v["cache"]
        return jax.jit(fn, donate_argnums=(1,))

    def serve(cached, slot, prompt_len, steps):
        """One sequence in ``slot``: its logits at the last ``positions``
        prompt positions and every decode step, its routed sets and its
        chosen keys at every position, and its ids."""
        rid = f"check-{slot}-{prompt_len}"
        table = srv._slot_table(slot, srv.block_mgr.allocate(
            rid, prompt_len + steps))
        tables = jnp.asarray(table[None])
        prompt = rng.integers(0, vocab, prompt_len)
        kept = min(args.positions, prompt_len)
        rows, sets, keys = [], [], []
        for at in range(0, prompt_len, chunk):
            n = min(chunk, prompt_len - at)
            ids = np.zeros((1, chunk), np.int32)
            ids[0, :n] = prompt[at:at + n]
            lg, routed, chosen, srv.cache = cached(
                params, srv.cache, jnp.asarray(ids), tables,
                jnp.asarray([at], jnp.int32), jnp.asarray([n], jnp.int32))
            first = max(prompt_len - kept, at)
            if first < at + n:
                rows.append(np.asarray(lg[0, first - at:n]))
            sets.append(np.asarray(routed[0, :n]))
            keys.append(np.asarray(chosen[0, :n]))
        slots = srv.config.decode_slots
        all_tables = np.zeros((slots, len(table)), np.int32)
        all_tables[slot] = table
        tokens = list(prompt)
        nxt = int(rows[-1][-1].argmax())
        for _ in range(steps - 1):
            tokens.append(nxt)
            lengths = np.zeros((slots,), np.int32)
            lengths[slot] = len(tokens) - 1
            last = np.zeros((slots, 1), np.int32)
            last[slot] = nxt
            lg, routed, chosen, srv.cache = cached(
                params, srv.cache, jnp.asarray(last), jnp.asarray(all_tables),
                jnp.asarray(lengths), jnp.ones((slots,), jnp.int32))
            rows.append(np.asarray(lg[slot]))
            sets.append(np.asarray(routed[slot]))
            keys.append(np.asarray(chosen[slot]))
            nxt = int(rows[-1][-1].argmax())
        srv.block_mgr.release(rid)
        return (np.concatenate(rows), np.concatenate(sets),
                np.concatenate(keys), np.asarray(tokens, np.int32),
                prompt_len, kept)

    layer_error = jax.jit(family.expert_layer_error(
        config_file, srv.engine.module.config))

    def compare(name, served, own_too=False):
        got, sets, keys, ids, prompt_len, kept = served
        n = len(ids)
        width = -(-n // args.pad) * args.pad
        padded = np.zeros((width,), np.int32)
        padded[:n] = ids
        given = np.full((width, len(layers), sets.shape[1] // len(layers)),
                        -1, np.int32)
        given[:n] = sets.reshape(n, *given.shape[1:])
        words = -(-width // 32)
        selected = np.zeros((width, keys.shape[1], words), np.uint32)
        selected[:n] = keys[:, :, :words]
        at = np.arange(prompt_len - kept, n)
        # one shape of positions whatever the prompt: one compiled head
        wide = np.minimum(np.arange(args.positions + max(
            args.steps, args.control_steps)) + prompt_len - kept, width - 1)
        keep = min(job.ERROR_ROWS, width)
        want, seen = reference(params, padded, given, selected, wide, keep)
        want = want[:len(at)]
        top = float(np.abs(want).max())

        def apart(other):
            diff = got.astype(np.float64) - other
            rel = np.abs(diff).max(-1) / top                # per position
            return {"max_rel": float(rel.max()),
                    "p95_rel": float(np.percentile(rel, 95)),
                    "rms_rel": float(np.sqrt((diff ** 2).mean())) / top,
                    "decode_p95_rel": float(np.percentile(rel[kept:], 95)),
                    "argmax_agree": float(
                        (got.argmax(-1) == other.argmax(-1)).mean())}

        sparse = [saw for saw in seen if "margin" in saw]
        out = {"what": name, "seed": args.seed, "positions": int(len(at)),
               "prompt": int(prompt_len), **apart(want),
               "largest_logit": top,
               "select_margin": max(float(saw["select_margin"][:n].max())
                                    for saw in seen),
               "select_margin_by_layer": [
                   float(saw["select_margin"][:n].max()) for saw in seen],
               "select_flips_mean": float(np.mean(
                   [saw["select_flips"][:n].mean() for saw in seen])),
               "routed_sets_differ": float(np.mean(
                   [saw["differs"][:n].mean() for saw in sparse])),
               "routed_margin": max(float(saw["margin"][:n].max())
                                    for saw in sparse)}
        valid = jnp.arange(keep) < n
        read = [layer_error(params[name_], saw["inputs"], valid)
                for name_, saw in zip(layers, sparse)]
        out["expert_error"] = [float(e) for e, _ in read]
        out["gate_margin"] = max(float(m) for _, m in read)
        del seen
        if own_too:
            # the reference choosing its own keys: what a flip costs
            own = reference(params, padded, given, None, wide, 0)[0]
            out["against_its_own_sets"] = apart(own[:len(at)])
        out["inside"] = bool(out["p95_rel"] <= LIMITS["p95_rel"]
                             and out["rms_rel"] <= LIMITS["rms_rel"]
                             and out["decode_p95_rel"] <= LIMITS["p95_rel"])
        out["selection_inside"] = bool(
            out["select_margin"] <= job.SELECT_MARGIN_MAX)
        out["experts_inside"] = bool(
            max(out["expert_error"]) <= job.EXPERT_ERROR_MAX
            and out["gate_margin"] <= job.GATE_MARGIN_MAX
            and out["routed_margin"] <= job.ROUTED_MARGIN_MAX)
        print(json.dumps(out), flush=True)
        return out

    cached = program()
    base = compare("bf16: a long prompt in chunks + decode",
                   serve(cached, 1, args.prompt, args.steps), own_too=True)
    controls, asked = {}, [c for c in args.controls.split(",") if c]
    for part in (c for c in CONTROLS if c in asked):
        undo = wrong_selection(part)
        try:
            wrong = program()  # traced at its first call, ``part`` in force
            controls[part] = compare(
                f"control {part}: a shorter prompt in chunks + decode",
                serve(wrong, 1, args.control_prompt, args.control_steps))
        finally:
            undo()
    for part in (c for c in PRECISION_CONTROLS if c in asked):
        undo = lower_precision(part)
        # (the layer's error is the served module's own sparse FFN: traced
        # anew with the control in force)
        layer_error = jax.jit(family.expert_layer_error(
            config_file, srv.engine.module.config))
        try:
            controls[part] = compare(
                f"control {part}: a shorter prompt in chunks + decode",
                serve(program(), 1, args.control_prompt, args.control_steps))
        finally:
            undo()
    ok = (base["inside"] and base["selection_inside"]
          and base["experts_inside"]
          and not any(c["selection_inside"] for name, c in controls.items()
                      if name in CONTROLS)
          and not any(c["inside" if name == "latent" else "experts_inside"]
                      for name, c in controls.items()
                      if name in PRECISION_CONTROLS))
    print(json.dumps({
        "seed": args.seed, "device": dev["kind"],
        "limits": {**LIMITS, "select_margin": job.SELECT_MARGIN_MAX,
                   "expert_error": job.EXPERT_ERROR_MAX,
                   "gate_margin": job.GATE_MARGIN_MAX,
                   "routed_margin": job.ROUTED_MARGIN_MAX},
        "passes": ok,
        "served_inside": [base["inside"], base["selection_inside"],
                          base["experts_inside"]],
        "controls_selection_inside": {
            name: c["selection_inside"] for name, c in controls.items()
            if name in CONTROLS},
        "controls_logits_inside": {
            name: c["inside"] for name, c in controls.items()
            if name == "latent"},
        "controls_experts_inside": {
            name: c["experts_inside"] for name, c in controls.items()
            if name in PRECISION_CONTROLS and name != "latent"},
        "attention_paths": srv.stats()["attention_paths"]}), flush=True)
    srv.destroy()
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
