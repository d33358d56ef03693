"""chip_smoke.py driven in this process at a tiny size on the CPU, plus the
device policy (``utils/device.py``) and the compile-cache helper
(``compat.arm_compilation_cache``) it stands on.

The program itself only ever runs at its full size on a TPU; the tests pass
a tiny ``Size`` and ``platform="cpu"`` to the same functions. The harness's
eight virtual devices are cut to one with the launcher's existing chip cap
(``DS_TPU_CHIPS_PER_HOST``), set here, not in the program.
"""

import json
import os
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from deepspeed_tpu.utils import compat, device  # noqa: E402

TINY = chip_smoke.Size(
    model=dict(vocab_size=256, n_positions=64, n_embd=64, n_layer=2,
               n_head=4, dtype="float32", scan_layers=True),
    batch=4, seq=32, steps=3,
    prompt_lens=(4, 6, 8), requests=5, new_tokens=6,
    serving={"block_size": 8, "decode_slots": 2, "max_queue_depth": 16})

ONE_CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


@pytest.fixture
def one_device(monkeypatch):
    """One device for the engines, and a device phase that says so."""
    monkeypatch.setenv("DS_TPU_CHIPS_PER_HOST", "1")
    monkeypatch.setattr(chip_smoke, "device_phase",
                        lambda platform, count: dict(ONE_CPU))


def _last_line(capsys):
    lines = capsys.readouterr().out.strip().splitlines()
    return lines, json.loads(lines[-1])


class TestChipSmoke:
    def test_all_phases_tiny_on_cpu(self, one_device, capsys):
        rc = chip_smoke.main([], size=TINY, platform="cpu")
        lines, last = _last_line(capsys)
        assert rc == 0
        # the contract's last line: exactly these keys, device as reported
        assert last == {"ok": True, "device": ONE_CPU}
        phases = {json.loads(l)["phase"]: json.loads(l) for l in lines[:-1]
                  if l.startswith('{"phase"')}
        assert {"train", "serve", "compile_cache"} <= set(phases)
        train, serve = phases["train"], phases["serve"]
        assert train["losses"][-1] < train["losses"][0]
        assert train["flash_vs_reference_max_abs_diff"] <= \
            chip_smoke.FLASH_VS_REFERENCE_ATOL
        assert serve["requests"] == TINY.requests
        assert serve["tokens_equal_generate"] is True
        assert serve["partings"] == []
        assert serve["compiles_after_warmup"] == 0
        # numbers from this run are labelled as what they are
        assert train["smoke_numbers"] and train["device_kind"] == "cpu"

    def test_failing_phase_is_not_ok(self, one_device, monkeypatch, capsys):
        def boom(*a, **k):
            raise RuntimeError("phase failed on purpose")

        monkeypatch.setattr(chip_smoke, "train_phase", boom)
        rc = chip_smoke.main([], size=TINY, platform="cpu")
        _, last = _last_line(capsys)
        assert rc == 1
        assert last == {"ok": False, "device": ONE_CPU}

    def test_non_tpu_device_is_not_ok(self, capsys):
        # as the program runs it: platform "tpu" wanted, the CPU found
        rc = chip_smoke.main([], size=TINY)
        _, last = _last_line(capsys)
        assert rc == 1 and last["ok"] is False
        assert last["device"]["platform"] == "cpu"  # what was found

    def test_device_phase_reports_what_jax_reports(self, capsys):
        dev = chip_smoke.device_phase("cpu", jax.device_count())
        assert dev == {"platform": "cpu",
                       "kind": jax.devices()[0].device_kind,
                       "count": jax.device_count()}
        with pytest.raises(AssertionError, match="needs 1 device"):
            chip_smoke.device_phase("cpu", 1)
        with pytest.raises(AssertionError, match="needs platform 'tpu'"):
            chip_smoke.device_phase("tpu", jax.device_count())

    def test_four_chips_runs_only_the_multichip_phases(self, monkeypatch,
                                                       capsys):
        ran = []
        monkeypatch.setattr(chip_smoke, "device_phase",
                            lambda platform, count: {**ONE_CPU,
                                                     "count": count})
        for name in ("train_phase", "serve_phase", "multichip_phase",
                     "tp_decode_phase"):
            monkeypatch.setattr(chip_smoke, name,
                                lambda *a, _n=name, **k: ran.append(_n))
        assert chip_smoke.main(["--chips", "4"], size=TINY,
                               platform="cpu") == 0
        _, last = _last_line(capsys)
        assert ran == ["multichip_phase", "tp_decode_phase"]
        assert last["device"]["count"] == 4


    def test_four_chip_phases_on_four_virtual_devices(self, monkeypatch,
                                                      capsys):
        """The phases ``--chips 4`` runs, on four of the harness's CPU
        devices. Wide enough that ZeRO-3 shards most of the state (at TINY
        every leaf sits under the stage-3 persistence threshold)."""
        monkeypatch.setenv("DS_TPU_CHIPS_PER_HOST", "4")
        size = chip_smoke.Size(
            model=dict(TINY.model, vocab_size=4096, n_embd=512),
            batch=8, seq=64, steps=3, prompt_lens=TINY.prompt_lens,
            requests=TINY.requests, new_tokens=TINY.new_tokens,
            serving=TINY.serving)
        chip_smoke.multichip_phase(size, 0, 4)
        chip_smoke.tp_decode_phase(size, 0, 4, kernels=False)
        zero, tp = (json.loads(l) for l in
                    capsys.readouterr().out.strip().splitlines()
                    if l.startswith('{"phase"'))
        assert zero["max_abs_diff"] <= 1e-5  # f32 here: the CPU tests' pin
        assert 0.25 <= zero["stage3_share_of_replicated"] < 0.3
        assert zero["collectives_over_all_chips"]["all-gather"] > 0
        assert tp["requests_with_equal_tokens"] == size.requests
        assert tp["tp_mesh"] == {"tp": 4}
        assert tp["all_reduces_over_all_chips_in_decode"] > 0


class TestTpStreamsAreHeldOverTheirWholeLength:
    """Phase 4(b)'s guard: where bf16 parts the tp stream from the tp=1
    stream, the rest is still compared, and the parting itself judged."""

    def test_equal_streams_never_ask_for_more(self):
        assert chip_smoke.follow_stream([1, 2, 3], [1, 2, 3], None) == []

    def test_a_parting_is_followed_from_the_tp1_prefix(self):
        asked = []

        def serve_from(n):
            asked.append(n)
            return [5, 6]

        parted = chip_smoke.follow_stream([1, 2, 3, 4, 5, 6],
                                          [1, 2, 3, 9, 9, 9], serve_from)
        assert parted == [(3, 9)] and asked == [4]
        # parting at the last token leaves nothing to follow
        assert chip_smoke.follow_stream([1, 2], [1, 7], None) == [(1, 7)]

    def test_a_fault_after_the_first_parting_fails(self):
        want = list(range(10))
        with pytest.raises(AssertionError, match="more than 3 times"):
            chip_smoke.follow_stream(
                want, [0, 99] + want[2:],
                lambda n: [99] * (len(want) - n))
        with pytest.raises(AssertionError, match="2 tokens served"):
            chip_smoke.follow_stream(want, want[:2], None)

    def test_judge_parting(self):
        import numpy as np

        l1 = np.zeros(16, np.float32)
        l1[3], l1[4], l1[5] = 2.0, 1.999, 1.0
        ok = chip_smoke.judge_parting(l1, l1 + 1e-3, 3, 4)
        assert ok["logit_gap"] == pytest.approx(1e-3, abs=1e-6)
        assert ok["max_logit_diff"] == pytest.approx(1e-3, abs=1e-6)
        with pytest.raises(AssertionError, match="no near tie"):
            chip_smoke.judge_parting(l1, l1, 3, 5)
        with pytest.raises(AssertionError, match="tp logits differ"):
            chip_smoke.judge_parting(l1, l1 + 0.1, 3, 4)

    @pytest.mark.parametrize("near_tie_rtol", [None, 10.0])
    def test_a_wrong_token_on_four_virtual_devices(self, monkeypatch,
                                                   capsys, near_tie_rtol):
        """One token of one tp stream made wrong. The phase follows the
        stream on through the tp engine, fetches both engines' logits
        there, and fails because the two candidates are no near tie. With
        that one tolerance opened wide it passes, and shows that the
        logits of the two engines agree and the rest of the stream was
        equal."""
        monkeypatch.setenv("DS_TPU_CHIPS_PER_HOST", "4")
        real = chip_smoke.follow_stream

        def one_wrong_token(want, got, serve_from):
            if want[:2] == got[:2] and not spoiled:
                spoiled.append(True)
                got = got[:2] + [(want[2] + 1) % TINY.model["vocab_size"]]
            return real(want, got, serve_from)

        spoiled = []
        monkeypatch.setattr(chip_smoke, "follow_stream", one_wrong_token)
        if near_tie_rtol is None:
            with pytest.raises(AssertionError, match="no near tie"):
                chip_smoke.tp_decode_phase(TINY, 0, 4, kernels=False)
            return
        monkeypatch.setattr(chip_smoke, "TP_NEAR_TIE_RTOL", near_tie_rtol)
        chip_smoke.tp_decode_phase(TINY, 0, 4, kernels=False)
        (tp,) = (json.loads(l) for l in
                 capsys.readouterr().out.strip().splitlines()
                 if l.startswith('{"phase"'))
        (parting,) = tp["partings"]
        assert parting["position"] == 2
        assert parting["max_logit_diff"] <= 1e-4  # f32 here
        assert tp["requests_with_equal_tokens"] == TINY.requests - 1


class TestServedStreamsAreJudgedAsTpStreamsAre:
    """Phase 3's guard since PR 44: a served stream that parts from
    ``generate()`` is served on from ``generate()``'s prefix, the position
    held to the tp phase's near-tie and logits tolerances, at most
    ``TP_MAX_PARTINGS`` a request."""

    @staticmethod
    def _spoil(monkeypatch, times):
        """The first served stream's token 2 made wrong, and the first
        token of what is served on after it, ``times`` in all."""
        real = chip_smoke.follow_stream
        left = [times]

        def wrong(tokens, want):
            left[0] -= 1
            return [(want + 1) % TINY.model["vocab_size"]] + tokens[1:]

        def spoiled(want, got, serve_from, names):
            if not left[0]:
                return real(want, got, serve_from, names)
            got = got[:2] + wrong(got[2:], want[2])

            def from_(n):
                rest = serve_from(n)
                return wrong(rest, want[n]) if left[0] else rest

            return real(want, got, from_, names)

        monkeypatch.setattr(chip_smoke, "follow_stream", spoiled)

    @staticmethod
    def _serve_line(capsys):
        (serve,) = (json.loads(l) for l in
                    capsys.readouterr().out.strip().splitlines()
                    if l.startswith('{"phase": "serve"'))
        return serve

    def test_the_paged_paths_logits_are_the_whole_forwards(self, one_device):
        """``paged_last_logits`` (prefill into a pool, one decode step
        through the block table) against ``forward_last`` at f32."""
        import jax.numpy as jnp
        import numpy as np

        import deepspeed_tpu
        from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel

        cfg = chip_smoke._model_config(TINY)
        engine = deepspeed_tpu.init_inference(
            GPT2LMHeadModel(cfg), dtype=cfg.dtype, seed=0,
            tensor_parallel={"tp_size": 1}, max_out_tokens=cfg.n_positions)
        for n in (3, 8, 13):
            prefix = np.arange(n, dtype=np.int32) % cfg.vocab_size
            np.testing.assert_allclose(
                chip_smoke.paged_last_logits(engine, cfg, prefix, 8),
                np.asarray(engine.forward_last(jnp.asarray(prefix[None])),
                           np.float32)[0], atol=1e-4)

    def test_a_parting_that_is_no_near_tie_fails(self, one_device,
                                                 monkeypatch):
        self._spoil(monkeypatch, 1)
        with pytest.raises(AssertionError, match="served picked token .* "
                           "where generate\\(\\) picked .* no near tie"):
            chip_smoke.serve_phase(TINY, 0, kernels=False)

    def test_a_near_tie_passes_and_is_counted(self, one_device, monkeypatch,
                                              capsys):
        self._spoil(monkeypatch, 1)
        monkeypatch.setattr(chip_smoke, "TP_NEAR_TIE_RTOL", 10.0)
        chip_smoke.serve_phase(TINY, 0, kernels=False)
        serve = self._serve_line(capsys)
        assert serve["tokens_equal_generate"] is False
        (parting,) = serve["partings"]
        assert (parting["request"], parting["position"]) == (0, 2)
        assert parting["served_token"] != parting["generate_token"]
        assert parting["max_logit_diff"] <= 1e-4  # f32 here
        assert parting["logit_gap"] > 0

    def test_a_fourth_parting_in_one_request_fails(self, one_device,
                                                   monkeypatch):
        self._spoil(monkeypatch, 4)
        monkeypatch.setattr(chip_smoke, "TP_NEAR_TIE_RTOL", 10.0)
        with pytest.raises(AssertionError, match="the served stream parts "
                           "from generate\\(\\) at \\[2, 3, 4, 5\\]: "
                           "more than 3 times"):
            chip_smoke.serve_phase(TINY, 0, kernels=False)

    def test_three_partings_in_one_request_pass(self, one_device,
                                                monkeypatch, capsys):
        self._spoil(monkeypatch, 3)
        monkeypatch.setattr(chip_smoke, "TP_NEAR_TIE_RTOL", 10.0)
        chip_smoke.serve_phase(TINY, 0, kernels=False)
        serve = self._serve_line(capsys)
        assert [f["position"] for f in serve["partings"]] == [2, 3, 4]
        assert {f["request"] for f in serve["partings"]} == {0}


class TestDevicePolicy:
    def test_require_device_accepts_the_cpu_only_when_asked(self,
                                                            monkeypatch):
        # the harness asked for the CPU by name (conftest)
        assert device.cpu_requested()
        assert device.require_device("tpu")["platform"] == "cpu"
        monkeypatch.setattr(device, "cpu_requested", lambda: False)
        with pytest.raises(device.DeviceError, match="needs a 'tpu'"):
            device.require_device("tpu")

    def test_describe_is_what_jax_reports(self):
        assert device.describe() == {
            "platform": jax.devices()[0].platform,
            "kind": jax.devices()[0].device_kind,
            "count": len(jax.devices())}

    def test_unknown_kind_has_no_peaks(self):
        v5e = device.peaks("TPU v5 lite")
        assert (v5e.bf16_flops, v5e.hbm_bandwidth) == (197e12, 819e9)
        for kind in ("cpu", "", "TPU v99"):
            with pytest.raises(device.DeviceError, match="no published"):
                device.peaks(kind)


class TestCompilationCacheDir:
    @pytest.fixture
    def updates(self, monkeypatch):
        """What ``arm_compilation_cache`` sets, without setting it."""
        seen = {}
        monkeypatch.setattr(compat.jax.config, "update",
                            lambda k, v: seen.__setitem__(k, v))
        return seen

    def test_env_set_is_used_and_nothing_else_is_set(self, monkeypatch,
                                                     updates, tmp_path):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compat.arm_compilation_cache() == str(tmp_path)
        assert "jax_compilation_cache_dir" not in updates

    def test_env_unset_is_one_fixed_dir_in_the_checkout(self, monkeypatch,
                                                        updates):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(REPO, ".jax_compile_cache")
        assert compat.arm_compilation_cache() == want
        assert compat.arm_compilation_cache() == want  # no pid, no time
        assert updates["jax_compilation_cache_dir"] == want
        with open(os.path.join(REPO, ".gitignore")) as f:
            assert ".jax_compile_cache/" in f.read().split()

    def test_cache_off_block_restores_what_was(self):
        assert jax.config.jax_enable_compilation_cache  # conftest armed it
        with compat.compilation_cache_off():
            assert not jax.config.jax_enable_compilation_cache
            with compat.compilation_cache_off():
                pass
            assert not jax.config.jax_enable_compilation_cache
        assert jax.config.jax_enable_compilation_cache
