#!/usr/bin/env python3
"""Phi-4-mini-flash at its published widths on the chip: the served
programs' LOGITS and STATE against the plain reference.

    chiprun -- python tools/chip_logits_phi4flash.py --seed N

builds the committed configuration whole (32 layers, 3.85G parameters,
bfloat16) behind a small ``ServingEngine`` (4 slots), drives the engine's
own paged module with its pools and tables as its programs do (a prompt of
``--prompt`` tokens in chunks of 512 that stop at the cache, then
``--steps`` decode steps through the rings, the one shared pool and the
state; then a SHORTER prompt in the same slot) and compares with
``perfbench/reference_phi4flash`` (float32, one full forward pass, the
recurrence a position at a time): the logits of every row the programs
handed back, the Mamba state rows of layers 0 and 16, and per layer the
root mean square of the stream and of the terms it gains (what
``weights.embedding_std`` in the configuration file quotes). Then the same
under each control of ``tests/perfbench/test_phi4flash_cell.py``
(``CONTROLS``). Exit 0 where bfloat16 lies inside ``LIMITS`` and every
control outside one of them.

``--through-check bfloat16-state|others-blocks|lambda-zero [--seconds 20]``
runs the CELL itself through the harness with that control in force and
exits 0 only if ``correct`` is false.
"""

import argparse
import contextlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CELL = "serve-phi4flash-yoco-reasoning"
# of a position's largest |logit|, at the 95th percentile of the rows
# compared: bfloat16 read 0.039-0.043, another slot's blocks 0.166, lambda at
# 0 1.2-1.3; and the first Mamba layer's state of the reference state's
# largest value, after the tool's 138 and 1,348 tokens: bfloat16 with its
# float32 pool 0.0027-0.0031, a bfloat16 pool 0.0072-0.0137 (it grows with
# the length: 0.052-0.085 after the cell's requests, whose limit is the
# job's) (my chip run, PR 63, call 1)
LIMITS = {"p95_rel": 0.08, "state_rel": 0.005}


def controls():
    from tests.perfbench.test_phi4flash_cell import CONTROLS

    return CONTROLS


def through_check(part: str, argv, root=None) -> int:
    """The cell through the harness with ``part`` in force: 0 if the
    harness's ``correct`` is false."""
    import io

    from perfbench import run as bench

    out = io.StringIO()
    with controls()[part](), contextlib.redirect_stdout(out):
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
    ap.add_argument("--workload", default=CELL)
    ap.add_argument("--root", default=None)
    ap.add_argument("--prompt", type=int, default=1300)
    ap.add_argument("--steps", type=int, default=48)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--skip-controls", action="store_true")
    ap.add_argument("--through-check", default=None)
    args = ap.parse_args(argv)
    if args.through_check:
        return through_check(args.through_check, [
            "--workload", args.workload, "--seed", str(args.seed),
            "--seconds", str(args.seconds), "--trace", "0"], args.root)

    import jax
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.serving import ServingEngine
    from perfbench import reference_phi4flash as reference
    from perfbench import run as bench
    from perfbench.jobs.serve_counted_phi4flash import written

    cell = bench.load_cell(args.workload, args.root or bench.HERE)
    dev = bench.check_device(1)
    family, config_file = cell["family"], cell["config_file"]
    dtype = jnp.bfloat16
    module = family.serving_module(config_file, dtype)

    @jax.jit
    def make(key):
        tree = module.init(key, jnp.zeros((1, 8), jnp.int32))
        return jax.tree_util.tree_map(lambda x: x.astype(dtype), tree)

    chunk = 512
    serving = {"decode_slots": 4, "block_size": 32, "max_model_len": 2048,
               "prefill_chunk_tokens": chunk, "prompt_buckets": [2048]}

    def engine():
        return ServingEngine(deepspeed_tpu.init_inference(
            module, params=make(jax.random.PRNGKey(args.seed % 2 ** 31)),
            dtype=dtype, seed=args.seed % 2 ** 31, max_out_tokens=2048,
            serving=serving))

    shape = family.reference_shape(config_file)
    places = (0, shape["kinds"].count("mamba") - 1)
    ref = family.reference_logits(config_file, kept_states=places)
    rng = np.random.default_rng([args.seed, 7])
    vocab = family.vocab_size(config_file)
    prompts = [rng.integers(0, vocab, n).tolist()
               for n in (args.prompt, 90)]
    i32 = lambda x: jnp.asarray(x, jnp.int32)

    def driven(srv, call, prompt, slot):
        """The rows the programs hand back for ``prompt`` in chunks and
        ``--steps`` greedy decode steps in ``slot``: (logits [rows, vocab],
        their positions, the tokens fed)."""
        rid = f"tool-{slot}-{len(prompt)}"
        table = srv._slot_table(slot, srv.block_mgr.allocate(
            rid, len(prompt) + args.steps))
        rows, where, tokens = [], [], list(prompt)
        try:
            for at in range(0, len(prompt), chunk):
                m = min(chunk, len(prompt) - at)
                ids = np.zeros((1, chunk), np.int32)
                ids[0, :m] = prompt[at:at + m]
                lg, srv.cache = call(srv.engine.params, srv.cache, i32(ids),
                                     i32(table[None]), i32([at]), i32([m]))
                rows.append(np.asarray(lg[0], np.float32))
                where.append(at + m - 1)
            slots = srv.config.decode_slots
            tables = np.zeros((slots, len(table)), np.int32)
            tables[slot] = table
            for _ in range(args.steps):
                tokens.append(int(rows[-1][-1].argmax()))
                lengths = np.zeros(slots, np.int32)
                last = np.zeros((slots, 1), np.int32)
                lengths[slot], last[slot] = len(tokens) - 1, tokens[-1]
                lg, srv.cache = call(srv.engine.params, srv.cache, i32(last),
                                     i32(tables), i32(lengths),
                                     jnp.ones(slots, jnp.int32))
                rows.append(np.asarray(lg[slot], np.float32))
                where.append(len(tokens) - 1)
        finally:
            srv.block_mgr.release(rid)
        return np.concatenate(rows), where, tokens

    def compare(name, control):
        with control():
            srv = engine()
            dm = srv._dmodule

            @jax.jit
            def call(params, cache, ids, tables, lengths, num_valid):
                out, v = dm.apply(
                    {"params": params, "cache": cache}, ids,
                    mutable=["cache"],
                    paging=srv._paging(ids, tables, lengths, num_valid))
                return out[0], v["cache"]

            out = {"what": name, "seed": args.seed}
            for which, prompt in zip(("long", "short"), prompts):
                got, where, tokens = driven(srv, call, prompt, 1)
                n = len(tokens)
                ids = np.zeros((1, -(-n // 512) * 512), np.int32)
                ids[0, :n] = tokens
                want, states = ref(srv.engine.params, i32(ids), i32(where),
                                   i32([n]))
                want = want[0]
                top = np.abs(want).max(-1)
                at = np.abs(got - want).max(-1) / top
                pool = srv.cache["ssm_state_pool"]
                rel = []
                for place in places:
                    w = np.asarray(states[place])[0, 0]
                    rel.append(float(np.abs(written(pool[place, 2]) - w).max()
                                     / np.abs(w).max()))
                out[which] = {
                    "rows": len(where), "max_rel": float(at.max()),
                    "p95_rel": float(np.percentile(at, 95)),
                    "argmax_agree": float((got.argmax(-1)
                                           == want.argmax(-1)).mean()),
                    "own_token_is_argmax": float(np.mean(
                        want.argmax(-1) == np.asarray(tokens)[where])),
                    "largest_logit": float(top.max()), "state_rel": rel}
            out["inside"] = bool(all(
                out[w]["p95_rel"] <= LIMITS["p95_rel"]
                and out[w]["state_rel"][0] <= LIMITS["state_rel"]
                for w in ("long", "short")))
            out["attention_paths"] = srv.stats()["attention_paths"]
            params = srv.engine.params
            print(json.dumps(out), flush=True)
            if control is contextlib.nullcontext:
                ids = np.zeros((1, 512), np.int32)
                ids[0, :min(512, len(prompts[0]))] = prompts[0][:512]
                shares = np.asarray(jax.jit(
                    lambda p, i: reference.term_shares(p, i, shape))(
                        params, i32(ids)))
                print(json.dumps({"term_shares_rms [stream, mixer, mlp]": [
                    [round(float(v), 4) for v in row] for row in shares]}),
                    flush=True)
            srv.destroy()
            return out

    base = compare("bf16: chunked prefill that stops at the cache + decode "
                   "through rings, shared pool and state",
                   contextlib.nullcontext)
    read = {} if args.skip_controls else {
        name: compare(f"control {name}", control)
        for name, control in controls().items()}
    ok = base["inside"] and not any(c["inside"] for c in read.values())
    print(json.dumps({"seed": args.seed, "device": dev["kind"],
                      "limits": LIMITS, "passes": ok,
                      "controls_inside": {k: c["inside"]
                                          for k, c in read.items()}}),
          flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
