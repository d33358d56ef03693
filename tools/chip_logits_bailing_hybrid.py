"""On the chip, once a change to the Ling-3.0 (``bailing_hybrid``) path: the
served programs' LOGITS against the plain reference's ONE full forward pass,
at the published widths and the benchmark cell's sizes (``model-configs``
guide, 3.3).

    chiprun -- python tools/chip_logits_bailing_hybrid.py [--seed N]

It builds the cell's engine (``perfbench`` configuration, family and
serving block), then drives the engine's own paged module with the
engine's own pools and tables: a prompt of 8,192 through the CHUNK program
(16 chunks of the cell's ``prefill_chunk_tokens``: each starts from the
slot's stored delta-rule state and convolution rows and writes them back;
the latent layer takes the sequence's rows a tile at a time), then 1,024
decode steps through both pools (the two Pallas kernels), greedy, the
logits of every position; and compares with
``perfbench/reference_bailing_hybrid.py`` (float32, ``highest``, the
recurrence a token at a time) over the same ids, the reference taking the
PROGRAM's routed sets in place of its own; and the slot's final KDA state
with the reference's. Then the CONTROLS, which have to FAIL what the served
program passes: ``bf16-state``: the delta-rule state through bfloat16 at
every step and chunk (BY ARITHMETIC: the chip's compiler keeps a convert
pair's excess precision); ``not-written``: a chunk's end state is not
written back (decode then starts from the state the slot held before);
``latent``: the latent pool's rows through float8; ``experts`` and
``gate``: the sparse layer's parts, against the cell's own limits on each
sparse layer (``jobs/serve_counted_bailing_hybrid.py``).

``--through-check bf16-state|not-written|experts|gate`` runs the CELL
itself through the harness with that control in force and exits 0 only if
the harness's own ``correct`` comes out false.

Numbers of a logits comparison, relative to the largest |logit| of the
reference: the 95th percentile over positions of a position's largest
difference, and the root mean square difference (``LIMITS``: between the
served program's readings and the controls', PERF.md, PR 57); of the
state: the root mean square of the difference over that of the reference's
state, the worst layer.
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

# relative to the largest |logit|, each between two chip readings (PERF.md,
# PR 57, has the call and every reading)
LIMITS = {"p95_rel": 0.02, "rms_rel": 0.0045}
CONTROLS = ("bf16-state", "not-written", "experts", "gate")


@functools.lru_cache(maxsize=None)
def _sibling():
    """``tools/chip_logits_mimo_v2.py``: float8 by arithmetic, and the two
    controls of ``moe/dropless.py``."""
    spec = importlib.util.spec_from_file_location(
        "chip_logits_mimo_v2", os.path.join(HERE, "chip_logits_mimo_v2.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool


def through_bf16(x):
    """``x`` (float32) rounded to bfloat16's 8 significant bits and back,
    BY ARITHMETIC (Veltkamp's split: ``c = x (2^16 + 1)``, ``c - (c -
    x)``): a pair of converts may be fused away on the chip."""
    import jax.numpy as jnp

    x = x.astype(jnp.float32)
    c = x * 65537.0
    return c - (c - x)


@contextlib.contextmanager
def control(part):
    """Every program traced inside has ``part`` in force (None: nothing):
    ``experts`` / ``gate`` as the sibling has them; ``bf16-state``: the
    rows a decode step writes, and the state a chunk starts from and
    leaves, through bfloat16; ``not-written``: a chunk hands back the
    state it was given; ``latent``: the latent pool's rows through
    float8."""
    from deepspeed_tpu.models import deepseek_v2
    from deepspeed_tpu.moe import dropless
    from deepspeed_tpu.ops import kda_chunk, kda_state_update

    plain = (dropless.expert_ffn, dropless.route, kda_chunk.kda_chunk,
             kda_state_update.state_update_xla,
             kda_state_update.state_update_kernel, deepseek_v2.pool_row)
    sibling = _sibling()
    if part in ("experts", "gate"):
        sibling.lower_precision(part)
    elif part == "bf16-state":
        def chunk(q, k, v, g, beta, state, *rest, **kw):
            o, state = plain[2](q, k, v, g, beta, through_bf16(state), *rest,
                                **kw)
            return o, through_bf16(state)

        def rounded(step):
            def update(pool, layer, slot_rows, *rest, **kw):
                o, pool = step(pool, layer, slot_rows, *rest, **kw)
                return o, pool.at[layer, slot_rows].set(
                    through_bf16(pool[layer, slot_rows]))
            return update

        kda_chunk.kda_chunk = chunk
        kda_state_update.state_update_xla = rounded(plain[3])
        kda_state_update.state_update_kernel = rounded(plain[4])
    elif part == "not-written":
        def chunk(q, k, v, g, beta, state, *rest, **kw):
            o, _ = plain[2](q, k, v, g, beta, state, *rest, **kw)
            return o, state

        kda_chunk.kda_chunk = chunk
    elif part == "latent":
        low = sibling.through_e4m3
        deepseek_v2.pool_row = lambda c, k_pe, lanes: plain[5](
            low(c), low(k_pe), lanes)
    elif part is not None:
        raise ValueError(part)
    try:
        yield
    finally:
        (dropless.expert_ffn, dropless.route, kda_chunk.kda_chunk,
         kda_state_update.state_update_xla,
         kda_state_update.state_update_kernel, deepseek_v2.pool_row) = plain


def through_check(part: str, argv, root=None) -> int:
    """The cell through the harness with ``part`` in force: 0 if the
    harness's ``correct`` is false."""
    import io

    from perfbench import run as bench

    out = io.StringIO()
    with control(part), contextlib.redirect_stdout(out):
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
    ap.add_argument("--workload", default="serve-ling3-kda-longgen")
    ap.add_argument("--root", default=None,
                    help="another copy of perfbench/ (the tests' tiny cell)")
    ap.add_argument("--prompt", type=int, default=8192)
    ap.add_argument("--steps", type=int, default=1024)
    ap.add_argument("--pad", type=int, default=1024,
                    help="the reference runs on ids padded to a multiple")
    ap.add_argument("--controls", default="bf16-state,not-written,latent,"
                    "experts,gate")
    ap.add_argument("--control-prompt", type=int, default=0,
                    help="a shorter prompt for the controls (0: the same)")
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
    chunk = int(cell["serve"]["serving"]["prefill_chunk_tokens"])
    # (a cell served in another precision states its own limit: the tests'
    # tiny cell is float32 against a float32 reference)
    cell_limits = cell["serve"].get("limits", {})
    state_max = cell_limits.get("state_error_max", job.STATE_ERROR_MAX)
    first_max = cell_limits.get("state_error_first_max",
                                job.STATE_ERROR_FIRST_MAX)

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
    at_layer = [int(name.split("_")[1]) for name in layers]
    reference = jax.jit(family.reference_logits_given(config_file))
    first_state = jax.jit(family.first_kda_recurrence(config_file,
                                                      module.config))
    rng = np.random.default_rng([args.seed, 57])

    def program():
        def fn(p, cache, ids, tables, lengths, num_valid):
            paging = {"block_tables": tables, "lengths": lengths,
                      "num_valid": num_valid, "prefill": False}
            out, v = dmodule.apply({"params": p, "cache": cache}, ids,
                                   mutable=["cache"], paging=paging)
            return out[0], out[1]["routed"], v["cache"]
        return jax.jit(fn, donate_argnums=(1,))

    def one(low, slot, prompt_len):
        """Serve one sequence in ``slot`` under control ``low``: logits and
        routed sets at every prompt position and decode step, the ids, and
        the slot's final KDA state."""
        i32 = lambda x: jnp.asarray(x, jnp.int32)
        rid = f"check-{slot}"
        with control(low):
            cached = program()
            table = srv._slot_table(slot, srv.block_mgr.allocate(
                rid, prompt_len + args.steps))
            prompt = rng.integers(0, vocab, prompt_len)
            rows, sets = [], []
            for at in range(0, prompt_len, chunk):
                n = min(chunk, prompt_len - at)
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
            state = np.asarray(srv.cache["kda_state_pool"][:, 1 + slot],
                               np.float32)
        return (np.concatenate(rows), np.concatenate(sets),
                np.asarray(tokens, np.int32), state)

    def compare(name, low, slot, prompt_len):
        got, sets, ids, state = one(low, slot, prompt_len)
        n = len(ids)
        padded = np.zeros((1, -(-n // args.pad) * args.pad), np.int32)
        padded[0, :n] = ids
        given = np.full((1, padded.shape[1], len(layers),
                         sets.shape[1] // len(layers)), -1, np.int32)
        given[0, :n] = sets.reshape(n, *given.shape[2:])
        want, seen = reference(params, jnp.asarray(padded),
                               jnp.asarray(given), jnp.asarray(n, jnp.int32))
        want = np.asarray(want)[0, :n]
        top = float(np.abs(want).max())
        diff = got.astype(np.float64) - want
        at = np.abs(diff).max(-1) / top              # per position
        held = np.asarray(seen["states"])[:, 0]
        by_layer = np.sqrt(((state - held) ** 2).mean((1, 2, 3))
                           / (held ** 2).mean((1, 2, 3)))
        # the first layer's against the recurrence over the program's own
        # inputs (the job's docstring says why)
        own = np.asarray(first_state(params, jnp.asarray(padded),
                                     jnp.asarray(n, jnp.int32)))
        first = float(np.sqrt(((state[0] - own) ** 2).mean()
                              / (own ** 2).mean()))
        out = {"what": name, "seed": args.seed, "positions": int(n),
               "max_rel": float(at.max()),
               "p95_rel": float(np.percentile(at, 95)),
               "rms_rel": float(np.sqrt((diff ** 2).mean())) / top,
               "decode_p95_rel": float(np.percentile(at[prompt_len:], 95)),
               "argmax_agree": float(
                   (got.argmax(-1) == want.argmax(-1)).mean()),
               "largest_logit": top,
               "state_error": float(by_layer.max()),
               "state_error_first": first,
               "state_error_by_layer": [float(e) for e in by_layer],
               "routed_sets_differ": float(
                   np.asarray(seen["differs"])[:, 0, :n].mean()),
               "routed_margin": float(
                   np.asarray(seen["margin"])[:, 0, :n].max())}
        read = []
        if low in (None, "experts", "gate"):
            # each sparse layer of the model as the engine holds it, over
            # the reference's own inputs: what the cell's ``correct`` holds
            with control(low):
                layer_error = jax.jit(family.expert_layer_error(
                    config_file, srv.engine.module.config), static_argnums=3)
                valid = jnp.arange(padded.shape[1]) < n
                read = [layer_error(params[name_], seen["inputs"][at_, 0],
                                    valid, at_layer[at_])
                        for at_, name_ in enumerate(layers)]
        del seen
        out["expert_error"] = [float(e) for e, _ in read]
        out["gate_margin"] = max([float(m) for _, m in read], default=None)
        out["inside"] = bool(out["p95_rel"] <= LIMITS["p95_rel"]
                             and out["rms_rel"] <= LIMITS["rms_rel"])
        out["state_inside"] = bool(out["state_error"] <= state_max
                                   and first <= first_max)
        out["experts_inside"] = bool(
            read and max(out["expert_error"]) <= job.EXPERT_ERROR_MAX
            and out["gate_margin"] <= job.GATE_MARGIN_MAX)
        print(json.dumps(out), flush=True)
        return out

    what = "chunked prefill + decode through the latent pool and the state"
    base = compare(f"served: {what}", None, 1, args.prompt)
    slots = srv.config.decode_slots
    why = {"bf16-state": "the state through bfloat16",
           "not-written": "a chunk's end state not written back",
           "latent": "float8 latent rows",
           "experts": "float8 expert matrices",
           "gate": "the gate's input in bfloat16"}
    asked = [c for c in args.controls.split(",") if c]
    controls = {low: compare(f"control, {why[low]}: {what}", low,
                             slot % slots,
                             args.control_prompt or args.prompt)
                for slot, low in enumerate(asked, 2)}
    # the served program inside every limit; a bfloat16 state and a state
    # not written back outside the state's (and the second outside the
    # logits'); the experts alone, and the gate alone, outside the sparse
    # layers'
    fails = {"bf16-state": lambda c: not c["state_inside"],
             "not-written": lambda c: not c["state_inside"],
             # (ONE latent layer of eight: its rows in float8 are a
             # reading beside the others, not a limit's test)
             "latent": lambda c: True,
             "experts": lambda c: not c["experts_inside"],
             "gate": lambda c: not c["experts_inside"]}
    ok = (base["inside"] and base["experts_inside"] and base["state_inside"]
          and all(fails[name](c) for name, c in controls.items()))
    print(json.dumps({
        "seed": args.seed, "device": dev["kind"],
        "limits": {**LIMITS, "expert_error": job.EXPERT_ERROR_MAX,
                   "gate_margin": job.GATE_MARGIN_MAX,
                   "state_error": state_max,
                   "state_error_first": first_max},
        "passes": ok, "served_inside": [base["inside"],
                                        base["experts_inside"],
                                        base["state_inside"]],
        "controls_inside": {name: [c["inside"], c["experts_inside"],
                                   c["state_inside"]]
                            for name, c in controls.items()},
        "attention_paths": srv.stats()["attention_paths"]}), flush=True)
    srv.destroy()
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
