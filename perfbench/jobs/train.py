"""Training job: ``deepspeed_tpu.initialize`` -> fused steps on fresh
seeded batches, each step ended by ``block_until_ready`` on its loss.

The cell's ``train`` block: ``micro_batch_per_chip``, ``zero_stage``,
``remat_policy``, ``mesh`` (null = ``{"data": chips}``), and optionally
``trace_seconds`` (the traced run's window, default 3 s) and
``reference_rows`` (rows of the first batch the reference sees at a time).
The model comes from the configuration's family (``perfbench/families/``).

``correct``: every loss finite, and the first step's loss, the mean over
ALL rows of the first batch, within ``LOSS_ATOL`` of the loss that
the family's plain reference computes on those rows from the engine's
own initial parameters. (No compile inside the window is the harness's.)
"""

import math
import time

import numpy as np

from perfbench import traffic

# The engine computes in bf16 from f32 master weights, the reference in
# f32 at the highest matmul precision. Per token the two log-likelihoods
# differ by bf16 rounding of the logits, in both directions; the mean over
# thousands of tokens keeps only the systematic part. Seen on the chip
# (PERF.md, PR 25): 1.4e-5 to 1.4e-4 at a loss of 11.0 on medium, 5.4e-5 on
# XL under ZeRO-3 on four chips. 0.002 is ten times the largest of these;
# a term of the published equations left out, or fp8-like arithmetic,
# moves the loss by far more (the flash kernel against XLA's attention,
# both bf16, is 1.1e-4 by itself: PR 23).
LOSS_ATOL = 0.002


def setup(cell: dict, seed: int, device: dict) -> dict:
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.parallel.topology import reset_topology

    job, chips = cell["train"], int(cell["chips"])
    family, config_file = cell["family"], cell["config_file"]
    dtype = getattr(jnp, job.get("dtype", "bfloat16"))
    reset_topology()
    config = {
        "train_micro_batch_size_per_gpu": int(job["micro_batch_per_chip"]),
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW",
                      "params": {"lr": 6e-4, "weight_decay": 0.1}},
        "gradient_clipping": 1.0,
        "bf16": {"enabled": dtype == jnp.bfloat16},
        "fused_step": True,
        "zero_optimization": {"stage": int(job["zero_stage"])},
        "steps_per_print": 10 ** 9,
        "seed": int(seed) % (2 ** 31),
    }
    axes = job.get("mesh") or {"data": chips}
    if device["count"] == chips:
        config["mesh"], topology = axes, None   # the way a user's config does
    else:
        # more devices than the cell asks for (the 8 virtual CPU devices of
        # the tests): a mesh over the first `chips` of them
        from deepspeed_tpu.parallel.topology import MeshTopology

        topology = MeshTopology(axis_sizes=axes,
                                devices=jax.devices()[:chips])
    engine, *_ = deepspeed_tpu.initialize(
        model=family.training_model(config_file, dtype, job["remat_policy"]),
        config=config, mesh=topology)
    rows = int(job["micro_batch_per_chip"]) * chips
    mix = cell["traffic_file"]
    state = {"engine": engine, "cell": cell, "seed": seed, "rows": rows,
             "mix": mix, "vocab": family.vocab_size(config_file),
             "chips": chips,
             "flops_per_token": family.train_flops_per_token(
                 config_file, int(mix["seq_len"]))}

    # the reference's loss on the first batch, from the initial parameters
    # (eval_batch is the public call that builds them without a step)
    first = traffic.train_batch(mix, seed, 0, rows, state["vocab"])
    chunk = int(job.get("reference_rows", 2))
    engine.eval_batch({"input_ids": first[:chunk]})
    ref = jax.jit(family.reference_loss(config_file))
    total = count = 0.0
    for i in range(0, rows, chunk):
        nll, n = ref(engine.state.params, jnp.asarray(first[i:i + chunk]))
        total, count = total + float(nll), count + int(n)
    state["reference_first_loss"] = total / count

    # the step that compiles; the window then starts at step 1
    state["first_loss"] = float(_step(engine, first))
    state["next_step"] = 1
    return state


def _step(engine, ids):
    import jax

    loss = engine({"input_ids": ids})
    engine.backward(loss)
    engine.step()
    return jax.block_until_ready(loss)


def run(state: dict, seconds: float, tracer) -> dict:
    engine, cell = state["engine"], state["cell"]
    if tracer.on:
        seconds = min(seconds, float(cell["train"].get("trace_seconds", 3.0)))
    step, losses, ends = state["next_step"], [], []
    with tracer.window():
        cpu = [time.process_time()]
        t0 = now = time.perf_counter()
        while now - t0 < seconds:
            with tracer.annotate("train.batch"):
                ids = traffic.train_batch(state["mix"], state["seed"], step,
                                          state["rows"], state["vocab"])
            with tracer.annotate("train.step"):
                losses.append(_step(engine, ids))
            step += 1
            now = time.perf_counter()
            ends.append(now)
            cpu.append(time.process_time())
    window = now - t0
    step_ms = 1e3 * np.diff([t0] + ends)
    slow = step_ms > 1.2 * np.median(step_ms)
    state["next_step"] = step
    losses = [float(x) for x in losses]
    tokens = len(losses) * state["rows"] * int(state["mix"]["seq_len"])
    tok_s_chip = tokens / window / state["chips"]
    bad = sum(1 for x in losses if not math.isfinite(x))
    return {
        "attempted": len(losses), "failed": bad, "started_at": t0,
        "metrics": {"train_tok_s_chip": tok_s_chip},
        "facts": {"train_tok_s_chip": tok_s_chip, "window_s": window,
                  "steps": len(losses), "rows": state["rows"],
                  "seq_len": int(state["mix"]["seq_len"]),
                  "flops_per_token": state["flops_per_token"]},
        "notes": {"steps": len(losses), "window_s": window,
                  "step_ms_mean": 1e3 * window / max(len(losses), 1),
                  "step_ms_median": float(np.median(step_ms)),
                  "step_ms_max": float(step_ms.max()),
                  # steps over 1.2 x the median, at most eight: (index, ms,
                  # ms of this process's own CPU time inside the step)
                  "slow_steps": [[int(i), float(step_ms[i]),
                                  1e3 * (cpu[i + 1] - cpu[i])]
                                 for i in np.flatnonzero(slow)[:8]],
                  "slow_steps_excess_ms": float(
                      (step_ms[slow] - np.median(step_ms)).sum()),
                  # one run in eight is 2-3% slow in EVERY step and spends
                  # half as much CPU time again (PERF.md, PR 25)
                  "own_cpu_ms_per_step": 1e3 * (cpu[-1] - cpu[0])
                  / max(len(losses), 1),
                  "first_loss": state["first_loss"],
                  "last_loss": losses[-1] if losses else None,
                  "losses_head": losses[:4]},
        "losses": losses,
    }


def check(state: dict, result: dict) -> dict:
    first, ref = state["first_loss"], state["reference_first_loss"]
    finite = (math.isfinite(first)
              and all(math.isfinite(x) for x in result["losses"]))
    diff = abs(first - ref)
    return {"correct": bool(finite and result["attempted"] > 0
                            and diff <= LOSS_ATOL),
            "losses_finite": finite, "first_loss": first,
            "reference_first_loss": ref, "abs_diff": diff,
            "loss_atol": LOSS_ATOL}


def teardown(state: dict):
    state["engine"].destroy()
