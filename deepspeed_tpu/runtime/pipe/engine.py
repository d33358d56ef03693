"""Pipeline-parallel training engine — the compiled 1F1B/GPipe schedule.

Capability parity with the reference ``PipelineEngine``
(``deepspeed/runtime/pipe/engine.py:36``): ``train_batch(data_iter)`` runs
``gas`` micro-batches through the stage pipeline and applies the optimizer.
The reference interprets a ``TrainSchedule`` instruction list with imperative
P2P sends (``pipe/p2p.py``) and per-buffer autograd; on TPU the *entire*
schedule is one XLA program:

- stages live on the ``pipe`` mesh axis; the model's repeated blocks are
  sharded over it (``PipelineModule``);
- a ``shard_map`` manual over ``pipe`` (auto/GSPMD over data/model/seq axes)
  runs ``M + P - 1`` "clock ticks"; each tick every stage applies its blocks
  and passes its activation to the next stage via ``lax.ppermute`` — the
  SendActivation/RecvActivation instructions;
- stage 0 injects micro-batch ``t`` (LoadMicroBatch) and the last stage
  computes the loss for micro-batch ``t - (P-1)`` under ``lax.cond`` so other
  stages skip the embedding/head FLOPs;
- ``jax.grad`` through the scan-of-ticks *is* the backward schedule: the
  transpose of ``ppermute`` sends grads backwards (SendGrad/RecvGrad), the
  transpose of the replicated-in tied/pre/post params is the tied-grad
  all-reduce over ``pipe`` (ReduceTiedGrads), and GSPMD's data-axis psum is
  ReduceGrads. Each tick is ``jax.checkpoint``-ed, so backward recomputes one
  tick's activations at a time (activation-checkpoint-per-micro-batch — the
  1F1B memory profile rather than GPipe's all-activations-live).
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.parallel.topology import AXIS_PIPE
from deepspeed_tpu.runtime.engine import DeepSpeedEngine, TrainState
from deepspeed_tpu.runtime.pipe.module import PipelineModule
from deepspeed_tpu.runtime.pipe.schedule import (InterleavedSchedule,
                                                 TrainSchedule,
                                                 ZeroBubbleSchedule)
from deepspeed_tpu.runtime.zero.partition import replicated
from deepspeed_tpu.utils.compat import shard_map
from deepspeed_tpu.utils.logging import log_dist


def _cond_skip(pred, fn, false_val, operands):
    """``lax.cond(pred, fn(operands), false_val)`` with an opaque VJP.

    With rng primitives inside one branch only, ``lax.scan``'s partial
    evaluation of the cond asserts on asymmetric branch residuals
    (``jax/_src/lax/control_flow/conditionals.py:619``). Hiding the cond
    behind a ``custom_vjp`` keeps it atomic to the scan: the backward pass
    re-linearizes the cond from its saved inputs — the same
    recompute-per-tick memory profile the tick remat already imposes —
    while the forward still executes only the taken branch (the
    FLOP-skipping the reference's per-stage instruction dispatch gets for
    free). ``fn`` must take ALL traced values through ``operands`` (a
    closure over tracers would leak through the custom_vjp boundary).
    """

    @jax.custom_vjp
    def run(pred, false_val, operands):
        return jax.lax.cond(pred, lambda: fn(operands), lambda: false_val)

    def fwd(pred, false_val, operands):
        return run(pred, false_val, operands), (pred, false_val, operands)

    def bwd(res, g):
        pred_, false_val_, operands_ = res
        _, vjp_fn = jax.vjp(
            lambda fv, ops: jax.lax.cond(
                pred_, lambda: fn(ops), lambda: fv),
            false_val_, operands_)
        d_fv, d_ops = vjp_fn(g)
        return (None, d_fv, d_ops)

    run.defvjp(fwd, bwd)
    return run(pred, false_val, operands)


def pipeline_loss_fn(module: PipelineModule, mesh, n_micro: int,
                     virtual_stages: int = 1):
    """Build ``loss(params, (inputs, labels), rng) -> mean loss`` running the
    pipelined schedule over ``n_micro`` micro-batches.

    ``inputs``/``labels`` are [M, mb, ...]; blocks params are [L, ...] sharded
    over ``pipe`` (L/P per stage).

    ``virtual_stages > 1`` compiles the interleaved schedule: each physical
    stage owns ``v`` round-robin layer chunks (virtual stage ``u = j*P + s``
    holds layers ``[u*Lc, (u+1)*Lc)``); a micro-batch rides the same
    ppermute ring ``v`` times, advancing one *virtual* stage per tick, so
    warmup/cooldown ramps fill ``v``x faster — the bubble shrinks toward
    ``(P-1)/(Mv+P-1)`` for ``v``x the per-stage activation traffic. Micro-
    batch ``m`` injects at tick ``(m % P) + (m // P)*v*P``; at tick ``t``
    stage ``s`` computes chunk ``j = ((t-s) // P) % v`` of micro-batch
    ``((t-s)//P//v)*P + (t-s)%P``. ``virtual_stages == 1`` traces the
    exact 1F1B program (HLO byte-identity pinned in tests)."""
    n_stages = mesh.shape[AXIS_PIPE]
    v = int(virtual_stages)
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    use_rngs = module.use_rngs
    # micro-batches live SHARDED over the pipe axis (stage s holds the
    # strided chunk {s, s+P, s+2P, ...} — M/P per stage, not M replicated
    # copies); each tick the owner stage publishes one micro-batch to the
    # ring via a where+psum select. Reference analog: LoadMicroBatch only
    # ever materializes data on stage 0 (pipe/engine.py:785).
    n_chunk = -(-n_micro // n_stages)
    n_pad = n_chunk * n_stages

    def body(params, inputs, labels, rng):
        stage = jax.lax.axis_index(AXIS_PIPE)
        extras = {"pre": params["pre"], "post": params["post"],
                  "tied": params["tied"]}
        blocks = params["blocks"]  # local view: [L/P, ...]
        # local strided chunks: [1, Mc, mb, ...] -> [Mc, mb, ...]
        inputs = jax.tree_util.tree_map(lambda a: a[0], inputs)
        labels = jax.tree_util.tree_map(lambda a: a[0], labels)

        def fetch(chunk, idx, owner):
            """Micro-batch ``idx`` (held by ``owner``'s chunk) delivered to
            every stage: owner publishes, psum routes. Transient — nothing
            [M]-sized is ever resident per stage."""
            if n_stages == 1:  # single stage owns everything; psum over a
                # size-1 manual axis trips the SPMD partitioner
                return jax.tree_util.tree_map(
                    lambda a: a[jnp.clip(idx, 0, n_chunk - 1)], chunk)

            def sel(a):
                row = a[jnp.clip(idx, 0, n_chunk - 1)]
                keep = (stage == owner).astype(a.dtype)
                shaped = keep.reshape((1,) * row.ndim)
                return jax.lax.psum(row * shaped, AXIS_PIPE)

            return jax.tree_util.tree_map(sel, chunk)

        def run_blocks(x, t, chunk=None):
            bp_stack = blocks
            if v > 1:
                # local blocks are [v, Lc, ...]; run this tick's chunk
                bp_stack = jax.tree_util.tree_map(
                    lambda b: jax.lax.dynamic_index_in_dim(
                        b, chunk, axis=0, keepdims=False), blocks)

            def blk(x, bp):
                return module.block_apply(bp, x,
                                          rngs=rngs_of(t, stage, rng)), None

            x, _ = jax.lax.scan(blk, x, bp_stack)
            return x

        mb0 = jax.tree_util.tree_map(lambda a: a[0], inputs)  # local shape
        act_shape = jax.eval_shape(
            lambda p, b: module.pre_apply(p, b), extras, mb0)
        zero_act = jnp.zeros(act_shape.shape, act_shape.dtype)

        def rngs_of(t, st, r):
            # every traced dependency (t, stage, rng key) arrives as an
            # argument: pre_fn/loss_of run inside _cond_skip's custom_vjp,
            # where a closure over an outer tracer would leak
            if not use_rngs:
                return None
            return {"dropout": jax.random.fold_in(
                jax.random.fold_in(r, t), st)}

        def pre_fn(ops):
            extras_, mb_, t_, st_, r_ = ops
            return module.pre_apply(extras_, mb_, rngs=rngs_of(t_, st_, r_))

        def loss_of(ops):
            extras_, y_, lab_, t_, st_, r_ = ops
            loss = module.loss_fn(
                module.post_apply(extras_, y_, rngs=rngs_of(t_, st_, r_)),
                lab_).astype(jnp.float32)
            # the per-tick loss is carried as [1], not a scalar: jax < 0.5's
            # shard_map transpose mis-names scalar float32 scan carries
            # ({0: all_axes} on a rank-0 aval) and grad fails to trace;
            # rank-1 is spec-legal on every path and numerically identical
            return loss.reshape((1,))

        def stage_select(pred, fn, false_val, operands):
            # lax.cond skips the untaken branch's FLOPs at runtime —
            # embedding/head work runs only on its own stage, bubble ticks
            # pay nothing. With dropout rngs a plain cond trips scan's
            # branch-residual assertion; _cond_skip wraps it atomically
            # (round-2's both-branch jnp.where fallback is gone).
            if not use_rngs:
                return jax.lax.cond(pred, lambda: fn(operands),
                                    lambda: false_val)
            return _cond_skip(pred, fn, false_val, operands)

        @jax.checkpoint
        def tick(carry, t):
            state, loss_sum, count = carry
            if v == 1:
                # micro-batch t lives in chunk slot t//P on stage t%P
                mb = fetch(inputs, t // n_stages, jnp.mod(t, n_stages))
                # LoadMicroBatch on stage 0; other stages use received act
                x = stage_select(stage == 0, pre_fn, state,
                                 (extras, mb, t, stage, rng))
                y = run_blocks(x, t)
                # last stage: loss of micro-batch t-(P-1) (if one arrived)
                out_idx = t - (n_stages - 1)
                lab = fetch(labels, out_idx // n_stages,
                            jnp.mod(out_idx, n_stages))
                take = jnp.logical_and(stage == n_stages - 1, out_idx >= 0)
            else:
                # interleaved: stage s at tick t runs chunk
                # j = ((t-s)//P) % v of micro-batch g*P + r where
                # r = (t-s)%P, g = (t-s)//P//v (docstring algebra)
                a = t - stage
                chunk = jnp.mod(a // n_stages, v)
                # chunk-0 injection on stage 0: mb ((t//P)//v)*P + t%P,
                # held by chunk slot (t//P)//v of its owner stage t%P
                inject = jnp.logical_and(
                    stage == 0, jnp.mod(t // n_stages, v) == 0)
                mb = fetch(inputs, (t // n_stages) // v,
                           jnp.mod(t, n_stages))
                x = stage_select(inject, pre_fn, state,
                                 (extras, mb, t, stage, rng))
                y = run_blocks(x, t, chunk=chunk)
                # loss leg: last stage, deepest chunk v-1
                a_out = t - (n_stages - 1)
                r_out = jnp.mod(a_out, n_stages)
                g_out = (a_out // n_stages) // v
                m_out = g_out * n_stages + r_out
                lab = fetch(labels, g_out, r_out)
                take = ((stage == n_stages - 1)
                        & (jnp.mod(a_out // n_stages, v) == v - 1)
                        & (a_out >= 0) & (m_out < n_micro))
            loss_t = stage_select(take, loss_of, jnp.zeros((1,), jnp.float32),
                                  (extras, y, lab, t, stage, rng))
            loss_sum = loss_sum + loss_t
            count = count + take.astype(jnp.int32)
            # SendActivation/RecvActivation: rotate stage outputs forward
            state = jax.lax.ppermute(y, AXIS_PIPE, perm)
            return (state, loss_sum, count), None

        if v == 1:
            total_ticks = n_micro + n_stages - 1
        else:
            # last micro-batch injects at tau = (M-1)%P + ((M-1)//P)*v*P
            # and needs v*P more ticks to clear all virtual stages
            tau_last = ((n_micro - 1) % n_stages
                        + ((n_micro - 1) // n_stages) * v * n_stages)
            total_ticks = tau_last + v * n_stages
        (_, loss_sum, count), _ = jax.lax.scan(
            tick, (zero_act, jnp.zeros((1,), jnp.float32),
                   jnp.zeros((), jnp.int32)),
            jnp.arange(total_ticks))
        # broadcast the last stage's mean loss to all stages
        loss_sum = jax.lax.psum(loss_sum, AXIS_PIPE)[0]
        count = jax.lax.psum(count, AXIS_PIPE)
        return loss_sum / count.astype(jnp.float32)

    # v > 1: blocks arrive pre-reshaped [v, L/v, ...] (loss_fn below), so
    # the pipe axis shards dim 1 — stage s owns chunk rows [j, s*Lc:(s+1)*Lc)
    blocks_spec = P(AXIS_PIPE) if v == 1 else P(None, AXIS_PIPE)
    spec_params = {"pre": P(), "blocks": blocks_spec, "post": P(), "tied": P()}
    smapped = shard_map(
        body, mesh=mesh,
        in_specs=(spec_params, P(AXIS_PIPE), P(AXIS_PIPE), P()),
        out_specs=P(),
        axis_names={AXIS_PIPE},
        check_vma=False)

    def stride(a):
        """[M, mb, ...] -> [P, Mc, mb, ...] with slot [s, k] = a[s + kP]
        (zero-padded to Mc*P): sharding the leading axis over pipe puts
        chunk s on stage s."""
        if n_pad > n_micro:
            a = jnp.concatenate(
                [a, jnp.zeros((n_pad - n_micro,) + a.shape[1:], a.dtype)], 0)
        return a.reshape((n_chunk, n_stages) + a.shape[1:]).swapaxes(0, 1)

    def loss_fn(params, batch, rngs=None):
        inputs, labels = batch
        if v > 1:
            # [L, ...] -> [v, L/v, ...]: row [j, s*Lc + i] is layer
            # (j*P + s)*Lc + i, i.e. virtual stage j*P + s owns a
            # round-robin chunk (free reshape; the pipe resharding of
            # dim 1 is the interleaving's extra param traffic)
            params = dict(params)
            params["blocks"] = jax.tree_util.tree_map(
                lambda b: b.reshape((v, b.shape[0] // v) + b.shape[1:]),
                params["blocks"])
        inputs = jax.tree_util.tree_map(stride, inputs)
        labels = jax.tree_util.tree_map(stride, labels)
        rng = rngs["dropout"] if isinstance(rngs, dict) else (
            rngs if rngs is not None else jax.random.PRNGKey(0))
        return smapped(params, inputs, labels, rng)

    return loss_fn


class PipelineEngine(DeepSpeedEngine):
    """Training engine for :class:`PipelineModule` models.

    ``forward``/``train_batch`` consume a *full* batch (``gas`` micro-batches
    at once) because the pipelined schedule over all micro-batches is a
    single compiled program; ``is_gradient_accumulation_boundary`` is
    therefore always True (reference parity: ``PipelineEngine.train_batch``
    also hides micro-batching from the user).
    """

    def __init__(self, *args, **kwargs):
        model = kwargs.get("model")
        if model is None and len(args) >= 2:
            model = args[1]
        assert isinstance(model, PipelineModule), \
            "PipelineEngine requires a PipelineModule"
        self._pipe_module = model
        self._pipe_ready = False
        # super().__init__ may already build state (model_parameters given),
        # which routes through _compile_steps → _finalize_pipe_setup
        super().__init__(*args, **kwargs)
        self._finalize_pipe_setup()

    def _finalize_pipe_setup(self):
        """Validate topology/config once both are parsed. Called from both
        ``__init__`` and ``_compile_steps`` (whichever runs first — state may
        be built inside ``super().__init__`` when params are passed in)."""
        if self._pipe_ready:
            return
        if self.zero_optimization_stage() > 2:
            raise ValueError(
                "ZeRO-3 is incompatible with pipeline parallelism "
                "(reference parity: engine.py asserts the same); use stage<=2")
        n_stages = self.topology.get_pipe_parallel_world_size()
        pipe_cfg = self._config.pipeline_config
        self.pipe_schedule = pipe_cfg.schedule
        self.virtual_stages = (pipe_cfg.virtual_stages
                               if pipe_cfg.schedule == "interleaved" else 1)
        self._pipe_module.validate_stages(
            n_stages, virtual_stages=self.virtual_stages)
        self.num_stages = n_stages
        self.micro_batches = self.gradient_accumulation_steps()
        self._pipe_ready = True
        log_dist(
            f"PipelineEngine: stages={n_stages} micro_batches="
            f"{self.micro_batches} schedule={self.pipe_schedule} "
            f"virtual_stages={self.virtual_stages} blocks/stage="
            f"{self._pipe_module.n_blocks // n_stages}", ranks=[0])

    # the PipelineModule is not a plain loss fn — the pipelined loss is
    # built in _compile_steps
    def _resolve_loss_fn(self, model):
        def unavailable(*a, **k):
            raise RuntimeError("pipeline loss is compiled in _compile_steps")

        return unavailable

    def _tp_base_specs(self, params_abstract):
        """Blocks carry the leading layer axis sharded over ``pipe``; pre/
        post/tied replicated (tied-layer replication, ``module.py:420``)."""
        def spec_blocks(leaf):
            return P(AXIS_PIPE, *([None] * (leaf.ndim - 1)))

        return {
            "pre": jax.tree_util.tree_map(lambda _: None, params_abstract["pre"]),
            "blocks": jax.tree_util.tree_map(
                spec_blocks, params_abstract["blocks"],
                is_leaf=lambda x: hasattr(x, "shape")),
            "post": jax.tree_util.tree_map(lambda _: None, params_abstract["post"]),
            "tied": jax.tree_util.tree_map(lambda _: None, params_abstract["tied"]),
        }

    def _init_params(self, batch):
        inputs, _ = self._split_batch_labels(batch)
        mb = jax.tree_util.tree_map(
            lambda a: np.asarray(a)[: self._micro_batch_rows()], inputs)
        seed = self._config._param_dict.get("seed", 42)
        params = self._pipe_module.init_params(jax.random.PRNGKey(seed), mb)
        return params

    def _micro_batch_rows(self) -> int:
        return (self.train_micro_batch_size_per_gpu()
                * self.topology.get_data_parallel_world_size())

    @staticmethod
    def _split_batch_labels(batch):
        if isinstance(batch, dict):
            inputs = batch["input_ids"] if "input_ids" in batch else batch["inputs"]
            labels = batch.get("labels", inputs)
            return inputs, labels
        if isinstance(batch, (tuple, list)) and len(batch) == 2:
            return batch[0], batch[1]
        return batch, batch

    def _compile_steps(self):
        self._finalize_pipe_setup()
        n_micro = self.micro_batches
        mesh = self.mesh
        pipe_loss = pipeline_loss_fn(self._pipe_module, mesh, n_micro,
                                     virtual_stages=self.virtual_stages)
        fp16 = self.fp16_enabled_
        self._set_grad_acc(True)  # the pipeline accumulates micro-batches
        grad_shardings = self._grad_shardings
        mb_rows = self._micro_batch_rows()

        def to_micro(a):
            return a.reshape((n_micro, mb_rows) + a.shape[1:])

        self._pipe_loss = pipe_loss
        self._to_micro = to_micro

        def micro_step(state: TrainState, batch):
            rng, sub = jax.random.split(state.rng)
            inputs, labels = self._split_batch_labels(batch)
            inputs = jax.tree_util.tree_map(to_micro, inputs)
            labels = jax.tree_util.tree_map(to_micro, labels)

            def scaled_loss(p):
                loss = pipe_loss(p, (inputs, labels),
                                 rngs={"dropout": sub}
                                 if self._pipe_module.use_rngs else None)
                return loss * (state.loss_scale.loss_scale if fp16 else 1.0)

            loss_scaled, grads = jax.value_and_grad(scaled_loss)(state.params)
            grads = jax.lax.with_sharding_constraint(grads, grad_shardings)
            grad_acc = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32), state.grad_acc, grads)
            loss = loss_scaled / (state.loss_scale.loss_scale if fp16 else 1.0)
            return state._replace(grad_acc=grad_acc, rng=rng), loss

        shardings = self._state_shardings
        self._jit_micro = self.telemetry.watch_jit(
            jax.jit(
                micro_step,
                in_shardings=(shardings, None),
                out_shardings=(shardings, replicated(mesh)),
                donate_argnums=(0,)),
            "pipe.micro_step")
        # reuse the base apply_step (optimizer/clip/loss-scale machinery)
        super()._compile_steps_apply_only()

    def is_gradient_accumulation_boundary(self) -> bool:
        return True

    def train_batch(self, data_iter=None, batch=None):
        """One full optimizer step: ``gas`` micro-batches through the
        pipeline (reference ``pipe/engine.py:294``)."""
        if batch is None:
            with self._bracket("data", span="data"):
                parts = [next(data_iter) for _ in range(self.micro_batches)]
                batch = jax.tree_util.tree_map(
                    # host-side batch assembly from the data iterator (input
                    # marshaling, not a device readback)
                    lambda *xs: np.concatenate([np.asarray(x) for x in xs]), *parts)  # graft-lint: disable=GL04
        loss = self.forward(batch)
        self.backward(loss)
        self.step()
        loss = float(loss)
        # this float() already paid the device sync — hand the value to
        # the step sentinel so its lagged fetch for this boundary is
        # superseded (no second sync, and the pipelined schedule's loss
        # is judged the step it happened, not sync_lag boundaries later)
        self.resilience.observe_synced_loss(self.global_steps, loss)
        return loss

    def eval_batch(self, batch):
        batch = self._shard_batch(batch)
        self._ensure_state(batch)
        if not hasattr(self, "_jit_eval"):
            pipe_loss, to_micro = self._pipe_loss, self._to_micro

            def eval_loss(params, batch):
                inputs, labels = self._split_batch_labels(batch)
                return pipe_loss(params,
                                 (jax.tree_util.tree_map(to_micro, inputs),
                                  jax.tree_util.tree_map(to_micro, labels)))

            self._jit_eval = self.telemetry.watch_jit(
                jax.jit(
                    eval_loss,
                    in_shardings=(self._state_shardings.params, None),
                    out_shardings=replicated(self.mesh)),
                "pipe.eval_step")
        return self._jit_eval(self.state.params, batch)

    def train_schedule(self, stage_id: int = 0) -> TrainSchedule:
        """The instruction schedule this engine's compiled program realizes
        (for inspection/validation — reference ``TrainSchedule``), selected
        by ``pipeline.schedule``. ``zero_bubble`` models the B/W split XLA's
        scan transpose already performs (losses stay bit-identical to 1f1b);
        ``interleaved`` mirrors the virtual-stage program compiled above."""
        if self.pipe_schedule == "interleaved":
            return InterleavedSchedule(micro_batches=self.micro_batches,
                                       stages=self.num_stages,
                                       stage_id=stage_id,
                                       virtual_stages=self.virtual_stages)
        if self.pipe_schedule == "zero_bubble":
            return ZeroBubbleSchedule(micro_batches=self.micro_batches,
                                      stages=self.num_stages,
                                      stage_id=stage_id)
        return TrainSchedule(micro_batches=self.micro_batches,
                             stages=self.num_stages, stage_id=stage_id)
