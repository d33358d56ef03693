"""Schedule-algebra tests: interleaved + zero-bubble generation and the
validator that every schedule — old and new — must pass.

Bubble fractions are pinned for P in {2,4}, m in {4,8}, v in {1,2}; the
orderings the ISSUE requires (zero-bubble strictly below 1F1B at equal
micro-batch count, interleaved v=2 strictly below v=1) are asserted
separately so a pin refresh can't silently drop them.
"""

import pytest

from deepspeed_tpu.runtime.pipe.schedule import (BackwardInput,
                                                 BackwardPass,
                                                 BackwardWeight,
                                                 ForwardPass,
                                                 InferenceSchedule,
                                                 InterleavedSchedule,
                                                 LoadMicroBatch,
                                                 OptimizerStep,
                                                 RecvActivation,
                                                 ScheduleValidationError,
                                                 SendActivation,
                                                 TrainSchedule,
                                                 ZeroBubbleSchedule,
                                                 validate_schedule,
                                                 validate_streams)

GRID = [(2, 4), (2, 8), (4, 4), (4, 8)]  # (stages P, micro-batches M)

# analytic bubble fractions from the discrete-event timeline,
# 1 - compute/(P * span); 1F1B column is the closed form (P-1)/(M+P-1)
BUBBLE_PINS = {
    # (P, M): {schedule: fraction}
    (2, 4): {"1f1b": 1 / 5, "interleaved_v2": 3 / 19, "zero_bubble": 1 / 7},
    (2, 8): {"1f1b": 1 / 9, "interleaved_v2": 3 / 35, "zero_bubble": 1 / 13},
    (4, 4): {"1f1b": 3 / 7, "interleaved_v2": 1 / 3, "zero_bubble": 1 / 3},
    (4, 8): {"1f1b": 3 / 11, "interleaved_v2": 5 / 21, "zero_bubble": 1 / 5},
}


class TestValidatorAccepts:
    @pytest.mark.parametrize("stages,micro", GRID)
    def test_1f1b(self, stages, micro):
        r = validate_schedule(TrainSchedule, micro, stages)
        assert r["violations"] == []
        assert r["span"] == 2 * (micro + stages - 1)

    @pytest.mark.parametrize("stages,micro", [(2, 4), (4, 6)])
    def test_inference(self, stages, micro):
        r = validate_schedule(InferenceSchedule, micro, stages)
        assert r["violations"] == []
        assert r["span"] == micro + stages - 1

    @pytest.mark.parametrize("stages,micro", GRID)
    @pytest.mark.parametrize("v", [1, 2])
    def test_interleaved(self, stages, micro, v):
        r = validate_schedule(InterleavedSchedule, micro, stages,
                              virtual_stages=v)
        assert r["violations"] == []

    @pytest.mark.parametrize("stages,micro", GRID)
    def test_zero_bubble(self, stages, micro):
        r = validate_schedule(ZeroBubbleSchedule, micro, stages)
        assert r["violations"] == []

    @pytest.mark.parametrize("stages,micro", [(3, 5), (1, 3)])
    def test_odd_shapes(self, stages, micro):
        validate_schedule(ZeroBubbleSchedule, micro, stages)
        validate_schedule(InterleavedSchedule, micro, stages,
                          virtual_stages=2)


class TestBubbleFraction:
    @pytest.mark.parametrize("stages,micro", GRID)
    def test_pinned_values(self, stages, micro):
        pins = BUBBLE_PINS[(stages, micro)]
        f1 = TrainSchedule(micro_batches=micro, stages=stages,
                           stage_id=0).bubble_fraction()
        il = InterleavedSchedule(micro_batches=micro, stages=stages,
                                 stage_id=0,
                                 virtual_stages=2).bubble_fraction()
        zb = ZeroBubbleSchedule(micro_batches=micro, stages=stages,
                                stage_id=0).bubble_fraction()
        assert f1 == pytest.approx(pins["1f1b"])
        assert il == pytest.approx(pins["interleaved_v2"])
        assert zb == pytest.approx(pins["zero_bubble"])

    @pytest.mark.parametrize("stages,micro", GRID)
    def test_orderings(self, stages, micro):
        f1 = TrainSchedule(micro_batches=micro, stages=stages,
                           stage_id=0).bubble_fraction()
        il1 = InterleavedSchedule(micro_batches=micro, stages=stages,
                                  stage_id=0,
                                  virtual_stages=1).bubble_fraction()
        il2 = InterleavedSchedule(micro_batches=micro, stages=stages,
                                  stage_id=0,
                                  virtual_stages=2).bubble_fraction()
        zb = ZeroBubbleSchedule(micro_batches=micro, stages=stages,
                                stage_id=0).bubble_fraction()
        # v == 1 reproduces 1F1B exactly; v == 2 and zero-bubble are
        # strictly better at equal micro-batch count
        assert il1 == pytest.approx(f1)
        assert il2 < f1
        assert zb < f1

    def test_validator_fraction_matches_analytic(self):
        r = validate_schedule(ZeroBubbleSchedule, 8, 4)
        zb = ZeroBubbleSchedule(micro_batches=8, stages=4, stage_id=0)
        assert r["bubble_fraction"] == pytest.approx(zb.bubble_fraction())


class TestMemoryProfile:
    @pytest.mark.parametrize("stages,micro", GRID)
    def test_zero_bubble_keeps_1f1b_peak(self, stages, micro):
        """ZB-H1's selling point: the weight-grad fill must not cost
        activation memory beyond the 1F1B warmup bound."""
        for s in range(stages):
            f1 = TrainSchedule(micro_batches=micro, stages=stages,
                               stage_id=s)
            zb = ZeroBubbleSchedule(micro_batches=micro, stages=stages,
                                    stage_id=s)
            assert zb.num_pipe_buffers() <= f1.num_pipe_buffers()

    def test_interleaved_v1_matches_1f1b_peak(self):
        for s in range(4):
            il = InterleavedSchedule(micro_batches=8, stages=4, stage_id=s,
                                     virtual_stages=1)
            assert il.num_pipe_buffers() == min(4 - s, 8)


class TestZeroBubbleStream:
    def test_backward_split(self):
        sched = ZeroBubbleSchedule(micro_batches=4, stages=2, stage_id=0)
        flat = [c for cmds in sched.steps() for c in cmds]
        bi = [c.micro_batch_id for c in flat if isinstance(c, BackwardInput)]
        bw = [c.micro_batch_id for c in flat if isinstance(c, BackwardWeight)]
        assert sorted(bi) == sorted(bw) == list(range(4))
        assert not any(isinstance(c, BackwardPass) for c in flat)
        # each W strictly after its B
        order = [(type(c), c.micro_batch_id) for c in flat
                 if isinstance(c, (BackwardInput, BackwardWeight))]
        for m in range(4):
            assert order.index((BackwardInput, m)) \
                < order.index((BackwardWeight, m))


class TestInterleavedStream:
    def test_chunks_round_robin(self):
        sched = InterleavedSchedule(micro_batches=4, stages=2, stage_id=0,
                                    virtual_stages=2)
        flat = [c for cmds in sched.steps() for c in cmds]
        fwd = [(c.micro_batch_id, c.chunk) for c in flat
               if isinstance(c, ForwardPass)]
        # stage 0 owns chunk 0 (u=0) and chunk 1 (u=2) of every mb
        assert sorted(fwd) == [(m, j) for m in range(4) for j in range(2)]

    def test_virtual_stages_validation(self):
        with pytest.raises(ValueError, match="virtual_stages"):
            InterleavedSchedule(micro_batches=4, stages=2, stage_id=0,
                                virtual_stages=0)


def _streams(schedule_cls, micro, stages, **kw):
    return [list(schedule_cls(micro_batches=micro, stages=stages,
                              stage_id=s, **kw).steps())
            for s in range(stages)]


class TestValidatorRejects:
    def test_missing_micro_batch(self):
        streams = _streams(TrainSchedule, 4, 2)
        streams[1] = [[c for c in cmds
                       if not (isinstance(c, ForwardPass)
                               and c.micro_batch_id == 2)]
                      for cmds in streams[1]]
        bad = validate_streams(streams, micro_batches=4)
        assert any("missing forward" in b for b in bad)

    def test_buffer_reuse_before_consume(self):
        streams = _streams(TrainSchedule, 4, 2)
        # force every stage-0 load into slot 0: the second load arrives
        # while slot 0 still holds the first un-backwarded activation
        for cmds in streams[0]:
            for c in cmds:
                if isinstance(c, (LoadMicroBatch, ForwardPass)):
                    c.buffer_id = 0
        bad = validate_streams(streams, micro_batches=4)
        assert any("reuse before consume" in b for b in bad)

    def test_clock_collision(self):
        streams = _streams(TrainSchedule, 4, 2)
        # teleport stage-1's backward of mb 3 to clock 0 — before its
        # own forward exists
        moved = [c for cmds in streams[1] for c in cmds
                 if isinstance(c, BackwardPass) and c.micro_batch_id == 3]
        streams[1] = [[c for c in cmds if c not in moved]
                      for cmds in streams[1]]
        streams[1][0] = list(streams[1][0]) + moved
        bad = validate_streams(streams, micro_batches=4)
        assert any("collision" in b for b in bad)

    def test_two_computes_one_clock(self):
        streams = _streams(TrainSchedule, 4, 2)
        extra = ForwardPass(1, micro_batch_id=99)
        streams[0][0] = list(streams[0][0]) + [extra]
        bad = validate_streams(streams, micro_batches=4)
        assert any("compute instructions in one clock" in b for b in bad)

    def test_recv_without_send(self):
        streams = _streams(TrainSchedule, 4, 2)
        streams[0] = [[c for c in cmds if not isinstance(c, SendActivation)]
                      for cmds in streams[0]]
        bad = validate_streams(streams, micro_batches=4)
        assert any("recv without matching send" in b for b in bad)

    def test_recv_same_clock_as_send(self):
        streams = _streams(TrainSchedule, 4, 2)
        # pull every stage-1 recv one clock earlier: recv must be
        # strictly after the send
        for t, cmds in enumerate(streams[1]):
            for c in list(cmds):
                if isinstance(c, RecvActivation):
                    cmds.remove(c)
                    streams[1][t - 1].append(c)
        bad = validate_streams(streams, micro_batches=4)
        assert any("not after send" in b for b in bad)

    def test_optimizer_step_misplaced(self):
        streams = _streams(TrainSchedule, 4, 2)
        streams[0] = [[c for c in cmds if not isinstance(c, OptimizerStep)]
                      for cmds in streams[0]]
        streams[0][0].append(OptimizerStep())
        bad = validate_streams(streams, micro_batches=4)
        assert any("OptimizerStep" in b for b in bad)

    def test_validate_schedule_raises(self):
        class Broken(TrainSchedule):
            def steps(self):
                for cmds in super().steps():
                    yield [c for c in cmds
                           if not (isinstance(c, BackwardPass)
                                   and c.micro_batch_id == 0)]

        with pytest.raises(ScheduleValidationError, match="missing backward"):
            validate_schedule(Broken, 4, 2)


class TestPipeVizTool:
    """Satellite acceptance: ``tools/pipe_viz.py`` renders a stage x
    clock grid for every schedule, validates before rendering, and
    honors the exit 0/1/2 contract (subprocess, like a user runs it)."""

    def _run(self, *argv):
        import os
        import subprocess
        import sys
        repo = os.path.abspath(
            os.path.join(os.path.dirname(__file__), "..", ".."))
        return subprocess.run(
            [sys.executable, os.path.join(repo, "tools", "pipe_viz.py"),
             *argv],
            capture_output=True, text=True, cwd=repo, timeout=300)

    @pytest.mark.parametrize("schedule", ["1f1b", "inference",
                                          "interleaved", "zero_bubble"])
    def test_renders_and_exits_zero(self, schedule):
        proc = self._run("--schedule", schedule, "--stages", "2",
                         "--micro-batches", "4")
        assert proc.returncode == 0, proc.stderr
        assert "stage 0" in proc.stdout and "stage 1" in proc.stdout
        assert "F0" in proc.stdout
        if schedule == "zero_bubble":
            assert "I0" in proc.stdout and "W0" in proc.stdout
        if schedule != "inference":
            assert "bubble_fraction=" in proc.stdout

    def test_markdown_grid(self):
        proc = self._run("--schedule", "interleaved", "--virtual-stages",
                         "2", "--stages", "2", "--micro-batches", "4",
                         "--markdown")
        assert proc.returncode == 0, proc.stderr
        assert proc.stdout.startswith("| stage \\ clock |")
        assert "F0'" in proc.stdout  # chunk-1 compute is visible

    def test_exit_2_on_usage_errors(self):
        assert self._run("--stages", "0").returncode == 2
        assert self._run("--schedule", "1f1b",
                         "--virtual-stages", "2").returncode == 2
        assert self._run("--schedule", "nonesuch").returncode == 2

    def test_exit_1_on_validation_failure(self, tmp_path):
        """Drive the tool's own validator path: a schedule class whose
        steps() drop a backward must exit 1 with the violation text."""
        import os
        import subprocess
        import sys
        repo = os.path.abspath(
            os.path.join(os.path.dirname(__file__), "..", ".."))
        stub = tmp_path / "broken_viz.py"
        stub.write_text(
            "import sys\n"
            f"sys.path.insert(0, {str(repo)!r})\n"
            "from deepspeed_tpu.runtime.pipe.schedule import (BackwardPass,\n"
            "    TrainSchedule)\n"
            "import tools.pipe_viz as pv\n"
            "class Broken(TrainSchedule):\n"
            "    def steps(self):\n"
            "        for cmds in super().steps():\n"
            "            yield [c for c in cmds\n"
            "                   if not (isinstance(c, BackwardPass)\n"
            "                           and c.micro_batch_id == 0)]\n"
            "pv.SCHEDULES['1f1b'] = Broken\n"
            "sys.exit(pv.main(['--schedule', '1f1b', '--stages', '2',\n"
            "                  '--micro-batches', '4']))\n")
        proc = subprocess.run([sys.executable, str(stub)],
                              capture_output=True, text=True, cwd=repo, timeout=300)
        assert proc.returncode == 1
        assert "VALIDATION FAILED" in proc.stderr
        assert "missing backward" in proc.stderr
