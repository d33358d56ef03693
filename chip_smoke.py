"""chip_smoke.py: the quickest proof that the system still starts on the chip.

One process drives the main paths once through the entry points a user
calls, with GPT-2 125M at its published size and weights made from a seed:

1. **device**  ``jax.devices()`` must be the TPU; peaks come from the one
   table (``deepspeed_tpu/utils/device.py``), an unknown kind is an error.
2. **train**   ``deepspeed_tpu.initialize`` with the bench config, a few
   fused steps on one fixed batch. Loss finite, starting at ln(vocab) and
   falling; the Pallas flash kernel is in the compiled step; the first two
   losses agree with the XLA reference attention (``use_flash=False``).
3. **serve**   ``init_inference`` -> ``ServingEngine`` behind a
   ``ServingGateway`` on a loopback port; staggered greedy requests of
   mixed prompt lengths. Every request completes, tokens equal
   ``engine.generate()`` or part from it only at near ties (judged as the
   tp phase judges a parting), the paged decode kernel is in the decode
   program, nothing compiles after warm-up.

``--chips 4`` runs ONLY the multi-chip phases: ZeRO-3 over ``fsdp=4``
against stage 0 over ``data=4``, then greedy paged decode at ``tp_size=4``
against ``tp_size=1``.

Every number printed here is a smoke number: one short run, no repeats,
compile time included where it says so. Not a benchmark.

The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``;
a phase that fails makes it ``"ok": false`` and the exit code 1.
"""

import argparse
import dataclasses
import json
import math
import sys
import threading
import time
import traceback
import urllib.request

import numpy as np


@dataclasses.dataclass(frozen=True)
class Size:
    """What one run drives. ``FULL`` is what the program runs; the tests
    pass a tiny one to the same functions."""
    model: dict           # GPT2Config fields (dtype by name)
    batch: int            # train micro-batch per chip
    seq: int
    steps: int            # timed train steps after the warm-up step
    prompt_lens: tuple    # serve: prompt lengths, cycled over the requests
    requests: int
    new_tokens: int
    serving: dict         # the `serving` block


FULL = Size(
    model=dict(vocab_size=50257, n_positions=1024, n_embd=768, n_layer=12,
               n_head=12, dtype="bfloat16", scan_layers=True),
    batch=16, seq=1024, steps=5,
    prompt_lens=(64, 128, 192), requests=8, new_tokens=64,
    serving={"block_size": 32, "decode_slots": 8, "max_queue_depth": 32})

# two steps on the same batch, flash kernel vs XLA reference attention, both
# in bf16 with a loss near 10.8: they differ by rounding only
FLASH_VS_REFERENCE_ATOL = 0.05
# four chips: stage 3 over fsdp=4 vs stage 0 over data=4 partition the same
# bf16 matmuls differently, so the reductions round in another order
ZERO3_VS_STAGE0_ATOL = 0.05
# four chips: tp's row-parallel all-reduces round the hidden state to bf16
# in another order (24 of them on the way to the logits). Where the two
# greedy streams part, the tp=1 logits of the two candidates must be a near
# tie (seen on the chip: one parting in 512 tokens, at a gap of 0.0006 of
# the largest |logit| there), and the two engines' logits at that position
# must agree to a few bf16 steps of the largest |logit| (seen: 1.5 steps,
# 0.0262 at 2.21, as the largest difference over the 50257 entries). A
# stream that parts is then followed on from the tp=1 prefix; parting more
# often than this in one request is no rounding matter. The serve phase
# holds a served stream to ``generate()``'s by the same three: the paged
# decode path sums its softmax in another order than the append cache's.
TP_NEAR_TIE_RTOL = 0.01
TP_LOGITS_RTOL = 4 * 2.0 ** -7
TP_MAX_PARTINGS = 3

_TELEMETRY = {"enabled": True, "jsonl": False, "memory": False}


def say(phase: str, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


def say_numbers(phase: str, **fields):
    """A line that carries timings: it names the device they were taken on
    and that they are smoke numbers."""
    import jax

    say(phase, smoke_numbers=True,
        device_kind=jax.devices()[0].device_kind, **fields)


def check(cond: bool, what: str):
    if not cond:
        raise AssertionError(what)


def _model_config(size: Size, **overrides):
    import jax.numpy as jnp

    from deepspeed_tpu.models.gpt2 import GPT2Config

    fields = {**size.model, **overrides}
    fields["dtype"] = getattr(jnp, fields["dtype"])
    return GPT2Config(**fields)


def _train_engine(size: Size, seed: int, batch: int, *, use_flash=None,
                  remat_policy="dots", zero_stage=0, mesh=None):
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2ForTraining
    from deepspeed_tpu.parallel.topology import reset_topology

    reset_topology()
    cfg = _model_config(size, remat=True, remat_policy=remat_policy,
                        use_flash=use_flash)
    config = {
        "train_micro_batch_size_per_gpu": batch,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW",
                      "params": {"lr": 6e-4, "weight_decay": 0.1}},
        "gradient_clipping": 1.0,
        "bf16": {"enabled": size.model["dtype"] == "bfloat16"},
        "fused_step": True,
        "zero_optimization": {"stage": zero_stage},
        "steps_per_print": 10_000,
        "seed": seed,
        "telemetry": _TELEMETRY,
    }
    if mesh:
        config["mesh"] = mesh
    engine, *_ = deepspeed_tpu.initialize(model=GPT2ForTraining(cfg),
                                          config=config)
    return engine, cfg


def _train_steps(engine, ids, steps: int):
    """``steps`` fused steps on one batch; (losses, seconds per step), each
    step ended by ``block_until_ready`` on the loss."""
    import jax

    losses, secs = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        loss = engine({"input_ids": ids})
        engine.backward(loss)
        engine.step()
        jax.block_until_ready(loss)
        secs.append(time.perf_counter() - t0)
        losses.append(float(loss))
    return losses, secs


def _program_texts(telemetry):
    """Compiled-program text of every watched jitted function, by name."""
    return {wf.name: [c.as_text() for c in wf.programs()]
            for wf in telemetry.watched_functions()}


def _has_kernel(texts, name_part: str) -> bool:
    return any("tpu_custom_call" in t for name, ts in texts.items()
               if name_part in name for t in ts)


# ----------------------------------------------------------------------
def device_phase(platform: str, count: int):
    from deepspeed_tpu.utils import device

    dev = device.describe()
    check(dev["platform"] == platform,
          f"this run needs platform {platform!r}, JAX started on "
          f"{dev['platform']!r} ({dev['kind']})")
    check(dev["count"] == count,
          f"this run needs {count} device(s), JAX sees {dev['count']}")
    if platform == "tpu":
        p = device.peaks(dev["kind"])  # unknown kind: DeviceError
        say("device", **dev, peak_bf16_tflops=p.bf16_flops / 1e12,
            peak_hbm_gbps=p.hbm_bandwidth / 1e9)
    else:
        say("device", **dev)
    return dev


def train_phase(size: Size, seed: int, kernels: bool):
    import jax

    from deepspeed_tpu.ops.attention import dispatch_counts

    rng = np.random.default_rng(seed)
    ids = rng.integers(0, size.model["vocab_size"],
                       (size.batch, size.seq)).astype(np.int32)

    before = dispatch_counts()
    engine, cfg = _train_engine(size, seed, size.batch)
    warm, warm_secs = _train_steps(engine, ids, 1)  # compiles
    rest, secs = _train_steps(engine, ids, size.steps)
    losses = warm + rest
    after = dispatch_counts()
    texts = _program_texts(engine.telemetry)
    stats = jax.devices()[0].memory_stats() or {}
    engine.destroy()
    del engine

    check(all(math.isfinite(x) for x in losses), f"loss not finite: {losses}")
    ln_v = math.log(size.model["vocab_size"])
    check(abs(losses[0] - ln_v) < 0.3,
          f"first loss {losses[0]:.3f} is not ln(vocab)={ln_v:.3f} +- 0.3")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    took = {k: after.get(k, 0) - before.get(k, 0) for k in after}
    if kernels:
        check(took.get("flash", 0) + took.get("flash_bthd", 0) > 0
              and not took.get("xla", 0) and not took.get("xla_ineligible", 0),
              f"attention did not take the flash kernel: {took}")
        check(_has_kernel(texts, "fused"),
              "no tpu_custom_call in the compiled train step: the flash "
              f"kernel is not in the program ({sorted(texts)})")

    # The same two steps with the XLA reference attention. Its f32
    # [layers, B, H, T, T] scores kept under dots-remat do not fit the chip
    # at this batch (the v5e compiler: 16.29G of 15.75G), so this engine
    # recomputes everything: the same arithmetic, less of it kept.
    ref_engine, _ = _train_engine(size, seed, size.batch, use_flash=False,
                                  remat_policy="full")
    ref, _ = _train_steps(ref_engine, ids, 2)
    ref_engine.destroy()
    del ref_engine
    diff = max(abs(a - b) for a, b in zip(losses[:2], ref))
    check(diff <= FLASH_VS_REFERENCE_ATOL,
          f"flash {losses[:2]} vs reference attention {ref}: "
          f"differ by {diff:.4f} > {FLASH_VS_REFERENCE_ATOL}")

    step_s = float(np.median(secs))
    say_numbers("train", losses=[round(x, 4) for x in losses],
        reference_attention_losses=[round(x, 4) for x in ref],
        flash_vs_reference_max_abs_diff=round(diff, 5),
        attention_dispatch=took, flash_kernel_in_program=bool(kernels),
        batch=size.batch, seq=size.seq,
        first_step_secs_compile_included=round(warm_secs[0], 2),
        step_ms=round(1e3 * step_s, 2),
        tokens_per_sec=round(size.batch * size.seq / step_s, 1),
        peak_hbm_bytes=stats.get("peak_bytes_in_use"))


def _post(url: str, prompt, new_tokens: int, timeout: float):
    body = json.dumps({"prompt": [int(t) for t in prompt],
                       "max_new_tokens": new_tokens,
                       "stream": False}).encode("utf-8")
    req = urllib.request.Request(
        url + "/v1/generate", data=body, method="POST",
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read().decode("utf-8"))


def _staggered_round(url, prompts, new_tokens, gap_secs, timeout):
    """POST every prompt from its own thread, ``gap_secs`` apart, so that
    requests join a batch that is already decoding."""
    outs = [None] * len(prompts)
    errors = []

    def one(i):
        try:
            outs[i] = _post(url, prompts[i], new_tokens, timeout)
        except Exception as e:  # noqa: BLE001 — re-raised by the caller
            errors.append((i, e))

    threads = [threading.Thread(target=one, args=(i,), daemon=True)
               for i in range(len(prompts))]
    for t in threads:
        t.start()
        time.sleep(gap_secs)
    for t in threads:
        t.join(timeout)
    check(not any(t.is_alive() for t in threads),
          f"a request did not return within {timeout}s")
    if errors:
        raise RuntimeError(f"request {errors[0][0]} failed") from errors[0][1]
    return outs


def serve_phase(size: Size, seed: int, kernels: bool):
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel
    from deepspeed_tpu.parallel.topology import reset_topology
    from deepspeed_tpu.serving import ServingEngine
    from deepspeed_tpu.serving.gateway import ServingGateway
    from deepspeed_tpu.telemetry import compile_watch

    reset_topology()
    cfg = _model_config(size)
    rng = np.random.default_rng(seed + 1)
    prompts = [rng.integers(0, cfg.vocab_size,
                            size.prompt_lens[i % len(size.prompt_lens)]
                            ).astype(np.int32)
               for i in range(size.requests)]

    t0 = time.perf_counter()
    srv = ServingEngine(deepspeed_tpu.init_inference(
        GPT2LMHeadModel(cfg), dtype=cfg.dtype, seed=seed,
        tensor_parallel={"tp_size": 1}, max_out_tokens=cfg.n_positions,
        serving=size.serving, telemetry=_TELEMETRY))
    gw = ServingGateway(srv, {"pump": True, "poll_secs": 0.002}).start()
    try:
        # warm-up: the same traffic once, compiling the prefill buckets
        # and the decode program
        _staggered_round(gw.url, prompts, size.new_tokens, 0.03, 900.0)
        warm_secs = time.perf_counter() - t0
        mark = compile_watch.snapshot()["backend_compiles"]
        t1 = time.perf_counter()
        outs = _staggered_round(gw.url, prompts, size.new_tokens, 0.03,
                                300.0)
        window_secs = time.perf_counter() - t1
        compiles = compile_watch.snapshot()["backend_compiles"] - mark
    finally:
        gw.close()  # destroy() would take the serving engine with it
    texts = _program_texts(srv.engine.telemetry)

    for i, o in enumerate(outs):
        check(o["state"] == "finished"
              and len(o["tokens"]) == size.new_tokens,
              f"request {i}: state={o['state']} "
              f"tokens={len(o['tokens'])}/{size.new_tokens}")
    check(compiles == 0, f"{compiles} compile(s) after warm-up")
    if kernels:
        check(_has_kernel(texts, "serving.decode"),
              "no tpu_custom_call in the serving decode program: the paged "
              f"decode kernel is not in it ({sorted(texts)})")

    # the batch-invariance contract: what continuous batching served is
    # what generate() gives each prompt alone, but for near ties: where a
    # served stream parts, it is served on from generate()'s prefix and
    # the position judged by the logits of both paths
    max_steps = 4 * (size.new_tokens + 1)

    def serve_on(prefix, n):
        req = srv.submit(prefix, max_new_tokens=n)
        srv.drain(max_steps)
        check(len(req.tokens) == n, f"{len(req.tokens)} of {n} tokens "
              f"served in {max_steps} steps")
        return [int(t) for t in req.tokens]

    partings = []
    for i, (p, o) in enumerate(zip(prompts, outs)):
        alone = srv.engine.generate(jnp.asarray(p[None]),
                                    max_new_tokens=size.new_tokens,
                                    do_sample=False)
        alone = [int(t) for t in np.asarray(alone)[0, len(p):]]
        def after(n, p=p, alone=alone):
            return np.concatenate([p, np.asarray(alone[:n], np.int32)])

        for pos, served in follow_stream(
                alone, [int(t) for t in o["tokens"]],
                lambda n: serve_on(after(n), size.new_tokens - n),
                names=("generate()", "served")):
            partings.append({
                "request": i, "position": pos,
                "generate_token": alone[pos], "served_token": served,
                **judge_parting(
                    np.asarray(srv.engine.forward_last(
                        jnp.asarray(after(pos)[None])), np.float32)[0],
                    paged_last_logits(srv.engine, cfg, after(pos),
                                      size.serving["block_size"]),
                    alone[pos], served, names=("generate()", "served"))})
    srv.destroy()

    ttft = [o["record"]["ttft_ms"] for o in outs]
    rate = [o["record"]["tokens_per_sec"] for o in outs]
    say_numbers("serve", requests=len(outs),
        prompt_lens=[len(p) for p in prompts], new_tokens=size.new_tokens,
        decode_slots=size.serving["decode_slots"],
        tokens_equal_generate=not partings, partings=partings,
        near_tie_rtol=TP_NEAR_TIE_RTOL, logits_rtol=TP_LOGITS_RTOL,
        paged_kernel_in_program=bool(kernels),
        compiles_after_warmup=compiles,
        warmup_secs_compile_included=round(warm_secs, 2),
        window_secs=round(window_secs, 3),
        ttft_ms_median=float(np.median(ttft)), ttft_ms_max=max(ttft),
        per_token_ms_median=round(1e3 / float(np.median(rate)), 3),
        served_tokens_per_sec=round(
            len(outs) * size.new_tokens / window_secs, 1))


def multichip_phase(size: Size, seed: int, chips: int):
    """ZeRO-3 over ``fsdp=chips`` against stage 0 over ``data=chips``: same
    global batch, same seed, three steps each."""
    import jax

    from deepspeed_tpu.utils.hlo_inspect import parse_collectives

    rng = np.random.default_rng(seed)
    ids = rng.integers(0, size.model["vocab_size"],
                       (size.batch, size.seq)).astype(np.int32)

    def per_device_bytes(engine):
        """Parameter + optimizer-state bytes each device holds."""
        held = {}
        for leaf in jax.tree_util.tree_leaves(
                (engine.state.params, engine.state.opt_state)):
            if not hasattr(leaf, "addressable_shards"):
                continue
            for s in leaf.addressable_shards:
                held[s.device.id] = held.get(s.device.id, 0) + s.data.nbytes
        return held

    def run(stage, mesh, micro_batch):
        engine, _ = _train_engine(size, seed, micro_batch, zero_stage=stage,
                                  mesh=mesh)
        losses, secs = _train_steps(engine, ids, 3)
        held = per_device_bytes(engine)
        colls = [c for ts in _program_texts(engine.telemetry).values()
                 for t in ts for c in parse_collectives(t)]
        engine.destroy()
        return losses, secs, held, colls

    # data=N splits the batch, fsdp never does: same GLOBAL batch both ways
    check(size.batch % chips == 0, "batch must divide over the data axis")
    l0, s0, held0, _ = run(0, {"data": chips}, size.batch // chips)
    l3, s3, held3, colls = run(3, {"fsdp": chips}, size.batch)

    diff = max(abs(a - b) for a, b in zip(l0, l3))
    check(all(math.isfinite(x) for x in l0 + l3), f"loss not finite {l0} {l3}")
    check(diff <= ZERO3_VS_STAGE0_ATOL,
          f"stage 3 / fsdp={chips} {l3} vs stage 0 / data={chips} {l0}: "
          f"differ by {diff:.4f} > {ZERO3_VS_STAGE0_ATOL}")
    check(len(held3) == chips and len(held0) == chips,
          f"state lives on {len(held3)} device(s), not {chips}")
    share = max(held3.values()) / max(held0.values())
    check(share < 1.25 / chips,
          f"a stage-3 device holds {share:.3f} of a replicated device's "
          f"parameter+optimizer bytes, expected about 1/{chips}")
    by_op = {}
    for c in colls:
        if c["operand_bytes"] and (c["group_size"] or 0) == chips:
            by_op[c["op"]] = by_op.get(c["op"], 0) + 1
    check(by_op.get("all-gather", 0) > 0,
          f"no all-gather over {chips} devices found in the compiled "
          f"stage-3 step: {by_op}")
    say_numbers("zero3_vs_stage0", chips=chips,
        global_batch=size.batch, seq=size.seq,
        stage0_data_losses=[round(x, 4) for x in l0],
        stage3_fsdp_losses=[round(x, 4) for x in l3],
        max_abs_diff=round(diff, 5),
        state_bytes_per_device_stage0=max(held0.values()),
        state_bytes_per_device_stage3=max(held3.values()),
        stage3_share_of_replicated=round(share, 4),
        collectives_over_all_chips=by_op,
        stage0_step_ms=round(1e3 * float(np.median(s0[1:])), 2),
        stage3_step_ms=round(1e3 * float(np.median(s3[1:])), 2))


def paged_last_logits(engine, cfg, prefix, block_size: int):
    """The last position's logits as the serving programs compute them:
    all but the last token of ``prefix`` prefilled into a block pool of its
    own, then the last token one decode step through the block table (on
    the chip, the paged kernel)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel

    n = len(prefix)
    blocks = -(-n // block_size)
    model = GPT2LMHeadModel(cfg.for_paged_decode(blocks + 1, block_size))
    tables = jnp.arange(1, blocks + 1, dtype=jnp.int32)[None]

    def paging(length, num_valid, prefill):
        return {"block_tables": tables,
                "lengths": jnp.asarray([length], jnp.int32),
                "num_valid": jnp.asarray([num_valid], jnp.int32),
                "prefill": prefill}

    ids = jnp.asarray(prefix[None], jnp.int32)
    params = engine._dequantize(engine.params)
    cache = jax.tree_util.tree_map(
        lambda leaf: jnp.zeros(leaf.shape, leaf.dtype),
        jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), ids[:, :1],
            paging=paging(0, 1, True)))["cache"])
    _, filled = model.apply({"params": params, "cache": cache}, ids[:, :-1],
                            mutable=["cache"],
                            paging=paging(0, n - 1, True))
    out, _ = model.apply({"params": params, "cache": filled["cache"]},
                         ids[:, -1:], mutable=["cache"],
                         paging=paging(n - 1, 1, False))
    return np.asarray(engine._logits_of(out), np.float32)[0, -1]


def follow_stream(want, got, serve_from, names=("tp=1", "tp")):
    """Hold the stream ``got`` (the tp engine's; the served one) to the
    reference stream ``want`` (tp=1's; ``generate()``'s) over its whole
    length. Where they part, note the position and carry on from the
    reference's prefix (``serve_from(n)``: the tokens of the engine under
    test after ``want[:n]``), so that a fault after the first parting
    still shows. Returns the positions where they parted."""
    ref, ours = names
    parted, done = [], 0
    while True:
        j = next((k for k, (a, b) in enumerate(zip(want[done:], got))
                  if a != b), None)
        check(j is not None or len(got) == len(want) - done,
              f"{len(got)} tokens served, {len(want) - done} wanted")
        if j is None:
            return parted
        parted.append((done + j, got[j]))
        check(len(parted) <= TP_MAX_PARTINGS,
              f"the {ours} stream parts from {ref} at "
              f"{[p for p, _ in parted]}: "
              f"more than {TP_MAX_PARTINGS} times in one request")
        done += j + 1
        if done == len(want):
            return parted
        got = serve_from(done)


def judge_parting(logits_ref, logits_ours, ref_token: int, our_token: int,
                  names=("tp=1", "tp")):
    """One position where the streams part: a near tie by the reference's
    own logits, and the same logits from both to a few bf16 steps."""
    ref, ours = names
    scale = float(np.abs(logits_ref).max())
    gap = abs(float(logits_ref[ref_token]) - float(logits_ref[our_token]))
    dev = float(np.abs(logits_ref - logits_ours).max())
    check(gap <= TP_NEAR_TIE_RTOL * scale,
          f"{ours} picked token {our_token} where {ref} picked {ref_token} "
          f"and holds them {gap:.5f} apart: no near tie (> "
          f"{TP_NEAR_TIE_RTOL} of max |logit| {scale:.5f})")
    check(dev <= TP_LOGITS_RTOL * scale,
          f"{ours} logits differ from {ref} by {dev:.5f} (> "
          f"{TP_LOGITS_RTOL} of max |logit| {scale:.5f})")
    return {"logit_gap": round(gap, 6), "max_logit_diff": round(dev, 6),
            "max_abs_logit": round(scale, 5)}


def tp_decode_phase(size: Size, seed: int, chips: int, kernels: bool):
    """Greedy paged decode through the serving engine at ``tp_size=chips``
    (heads and KV pools sharded over tp, ``decode_attention_paged_tp``)
    against ``tp_size=1`` on the same host: the same tokens over the whole
    of every stream. Where bf16 parts two streams (``judge_parting``), the
    tp engine serves on from the tp=1 prefix (``follow_stream``)."""
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel
    from deepspeed_tpu.parallel.topology import reset_topology
    from deepspeed_tpu.serving import ServingEngine
    from deepspeed_tpu.utils.hlo_inspect import parse_collectives

    cfg = _model_config(size)
    check(cfg.n_head % chips == 0, "heads must divide over the tp axis")
    rng = np.random.default_rng(seed + 1)
    prompts = [rng.integers(0, cfg.vocab_size,
                            size.prompt_lens[i % len(size.prompt_lens)]
                            ).astype(np.int32)
               for i in range(size.requests)]
    # enough steps for every request to be prefilled and decoded in turn
    max_steps = 4 * size.requests * (size.new_tokens + 1)

    def build(tp):
        reset_topology()
        return ServingEngine(deepspeed_tpu.init_inference(
            GPT2LMHeadModel(cfg), dtype=cfg.dtype, seed=seed,
            tensor_parallel={"tp_size": tp}, max_out_tokens=cfg.n_positions,
            serving=size.serving, telemetry=_TELEMETRY))

    def serve(srv, asked):
        """Tokens for each (prompt, new_tokens) asked, served together."""
        reqs = [srv.submit(p, max_new_tokens=n) for p, n in asked]
        srv.drain(max_steps)
        check(all(len(r.tokens) == n for r, (_, n) in zip(reqs, asked)),
              f"not every request finished in {max_steps} steps: "
              f"{[len(r.tokens) for r in reqs]}")
        return [[int(t) for t in r.tokens] for r in reqs]

    def last_logits(srv, prefix):
        return np.asarray(srv.engine.forward_last(jnp.asarray(prefix[None])),
                          np.float32)[0]

    everything = [(p, size.new_tokens) for p in prompts]
    srv = build(1)
    mesh1 = dict(srv.engine.mesh.shape)
    t0 = time.perf_counter()
    tok1 = serve(srv, everything)
    secs1 = time.perf_counter() - t0
    srv.destroy()

    srv = build(chips)
    meshn = dict(srv.engine.mesh.shape)
    check(meshn.get("tp") == chips, f"tp mesh is {meshn}")
    t0 = time.perf_counter()
    tokn = serve(srv, everything)
    secsn = time.perf_counter() - t0
    partings = []
    for i, (want, got) in enumerate(zip(tok1, tokn)):
        def serve_from(n, i=i, want=want):
            prefix = np.concatenate([prompts[i],
                                     np.asarray(want[:n], np.int32)])
            return serve(srv, [(prefix, len(want) - n)])[0]

        for pos, tp_token in follow_stream(want, got, serve_from):
            prefix = np.concatenate([prompts[i],
                                     np.asarray(want[:pos], np.int32)])
            partings.append({"request": i, "position": pos,
                             "tp1_token": want[pos], "tp_token": tp_token,
                             "prefix": prefix,
                             "logits_tp": last_logits(srv, prefix)})
    texts = _program_texts(srv.engine.telemetry)
    srv.destroy()

    if partings:  # the tp=1 engine once more, to judge them
        srv = build(1)
        for f in partings:
            f.update(judge_parting(last_logits(srv, f.pop("prefix")),
                                   f.pop("logits_tp"), f["tp1_token"],
                                   f["tp_token"]))
        srv.destroy()
    reduces = sum(1 for name, ts in texts.items() if "serving.decode" in name
                  for t in ts for c in parse_collectives(t)
                  if c["op"] == "all-reduce" and c["group_size"] == chips)
    check(reduces > 0, f"no all-reduce over {chips} devices in the tp decode "
          f"program ({sorted(texts)})")
    if kernels:
        check(_has_kernel(texts, "serving.decode"),
              "no tpu_custom_call in the tp decode program")
    say_numbers("tp_decode", chips=chips, requests=len(prompts),
                new_tokens=size.new_tokens,
                requests_with_equal_tokens=len(prompts) - len(
                    {f["request"] for f in partings}),
                partings=partings, near_tie_rtol=TP_NEAR_TIE_RTOL,
                logits_rtol=TP_LOGITS_RTOL,
                distinct_tokens=len({t for ts in tokn for t in ts}),
                tp1_mesh={k: v for k, v in mesh1.items() if v > 1},
                tp_mesh={k: v for k, v in meshn.items() if v > 1},
                all_reduces_over_all_chips_in_decode=reduces,
                paged_kernel_in_program=bool(kernels),
                tp1_secs_compile_included=round(secs1, 2),
                tp_secs_compile_included=round(secsn, 2))


# ----------------------------------------------------------------------
def run_phases(args, size: Size, platform: str, result: dict):
    from deepspeed_tpu.telemetry import compile_watch
    from deepspeed_tpu.utils import device
    from deepspeed_tpu.utils.compat import arm_compilation_cache

    result["device"] = device.describe()  # named even when it is refused
    result["device"] = device_phase(platform, args.chips)
    cache_dir = arm_compilation_cache()
    compile_watch.install()
    kernels = platform == "tpu"
    t0 = time.perf_counter()
    if args.chips > 1:
        multichip_phase(size, args.seed, args.chips)
        tp_decode_phase(size, args.seed, args.chips, kernels)
    else:
        train_phase(size, args.seed, kernels)
        serve_phase(size, args.seed, kernels)
    snap = compile_watch.snapshot()
    say("compile_cache", dir=cache_dir,
        backend_compiles=snap["backend_compiles"],
        persistent_cache_hits=snap["persistent_cache_hits"],
        wall_secs=round(time.perf_counter() - t0, 1))


def main(argv=None, size: Size = FULL, platform: str = "tpu") -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: run only the multi-chip phases")
    parser.add_argument("--seed", type=int, default=0,
                        help="seeds the weights, the batch and the prompts")
    args = parser.parse_args(argv)
    result = {"ok": False, "device": None}
    try:
        run_phases(args, size, platform, result)
        result["ok"] = True
    except Exception:  # noqa: BLE001 — reported, and the exit code is 1
        traceback.print_exc()
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
