"""Live-tunable autotuning: the axis registry, the measured tuner, the
tuned-config artifact (round-trip / determinism / precedence /
fingerprint pinning), and consumption by rebuilt engines."""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.autotuning import (LiveTuner, all_axes, default_axes,
                                      get_axis, register_axis,
                                      runtime_tunables)
from deepspeed_tpu.autotuning.artifact import (TunedArtifactError,
                                               apply_section,
                                               artifact_hash,
                                               dumps_artifact,
                                               make_artifact, ops_choices,
                                               read_tuned_artifact,
                                               section_choices,
                                               verify_fingerprint,
                                               write_tuned_artifact)
from deepspeed_tpu.utils.fingerprint import topology_fingerprint

MiB = 1024 * 1024


def _artifact(tmp_path, axes=None, fingerprint=None):
    axes = axes or {
        "zero.reduce_bucket_bytes": {
            "target": "comm_quantization.bucket_bytes", "value": 4 * MiB,
            "objective": "steps_per_sec", "minimize": False, "score": 10.0,
            "evidence": [{"value": 4 * MiB,
                          "measurements": {"steps_per_sec": 10.0}}]},
        "decode_attention.block_k": {
            "target": "ops.decode_attention.block_k", "value": 512,
            "objective": "per_call_ms", "minimize": True, "score": 0.3,
            "evidence": [{"value": 512,
                          "measurements": {"per_call_ms": 0.3}}]},
    }
    art = make_artifact(axes, fingerprint=fingerprint)
    path = os.path.join(str(tmp_path), "tuned.json")
    write_tuned_artifact(path, art)
    return path, art


# ----------------------------------------------------------------------
class TestArtifact:
    def test_roundtrip_and_determinism(self, tmp_path):
        path, art = _artifact(tmp_path)
        loaded = read_tuned_artifact(path)
        assert loaded == art
        # byte-identical: same measurements -> same file, always
        assert dumps_artifact(loaded) == dumps_artifact(art)
        with open(path) as f:
            assert f.read() == dumps_artifact(art)
        assert artifact_hash(loaded) == artifact_hash(art)
        assert artifact_hash(None) == "none"

    def test_version_gate(self, tmp_path):
        path, art = _artifact(tmp_path)
        art["version"] = 99
        write_tuned_artifact(path, art)
        with pytest.raises(TunedArtifactError, match="version"):
            read_tuned_artifact(path)

    def test_choice_accessors(self, tmp_path):
        _, art = _artifact(tmp_path)
        assert section_choices(art, "comm_quantization") == {
            "bucket_bytes": 4 * MiB}
        assert ops_choices(art) == {"ops.decode_attention.block_k": 512}
        # user key wins in apply_section; artifact fills the gap
        assert apply_section({"bucket_bytes": 1}, art,
                             "comm_quantization") == {"bucket_bytes": 1}
        assert apply_section({}, art, "comm_quantization") == {
            "bucket_bytes": 4 * MiB}

    def test_nested_submodel_target_expands_and_merges(self, tmp_path):
        """A sub-model target ("serving.speculative.num_speculative_
        tokens") must expand into the nested block shape the pydantic
        config parses, and apply_section must fill INSIDE a user block
        without stomping the user's explicit sub-keys."""
        _, art = _artifact(tmp_path, axes={
            "serving.num_speculative_tokens": {
                "target": "serving.speculative.num_speculative_tokens",
                "value": 8, "objective": "spec_tokens_per_sec",
                "minimize": False, "score": 100.0, "evidence": []}})
        assert section_choices(art, "serving") == {
            "speculative": {"enabled": True, "num_speculative_tokens": 8}}
        # no user block: the whole nested choice fills in
        assert apply_section({}, art, "serving") == {
            "speculative": {"enabled": True, "num_speculative_tokens": 8}}
        # user block present: artifact fills only missing sub-keys
        merged = apply_section(
            {"speculative": {"proposer": "prompt_lookup"}}, art, "serving")
        assert merged == {"speculative": {"proposer": "prompt_lookup",
                                          "enabled": True,
                                          "num_speculative_tokens": 8}}
        # explicit user sub-key beats the artifact, one level down
        merged = apply_section(
            {"speculative": {"num_speculative_tokens": 2}}, art, "serving")
        assert merged["speculative"]["num_speculative_tokens"] == 2

    def test_sibling_nested_targets_merge_not_clobber(self, tmp_path):
        """Two axes under the same nested block must BOTH apply —
        dict.update clobbering would silently drop one tuned choice."""
        _, art = _artifact(tmp_path, axes={
            "serving.num_speculative_tokens": {
                "target": "serving.speculative.num_speculative_tokens",
                "value": 8, "objective": "spec_tokens_per_sec",
                "minimize": False, "score": 100.0, "evidence": []},
            "serving.prompt_lookup_max_ngram": {
                "target": "serving.speculative.prompt_lookup_max_ngram",
                "value": 2, "objective": "spec_tokens_per_sec",
                "minimize": False, "score": 90.0, "evidence": []}})
        assert section_choices(art, "serving") == {
            "speculative": {"enabled": True, "num_speculative_tokens": 8,
                            "prompt_lookup_max_ngram": 2}}

    def test_spec_decode_axis_registered(self):
        axis = get_axis("serving.num_speculative_tokens")
        assert axis.bench == "decode" and axis.series == "spec_decode"
        assert axis.objective == "spec_tokens_per_sec"
        assert axis.series_config(8) == {"serving": {"speculative": {
            "enabled": True, "num_speculative_tokens": 8}}}
        # the machinery-off candidate is IN the grid (comm.tier
        # convention): switching speculation on at all is measured
        assert "off" in axis.grid
        assert axis.series_config("off") == {"serving": {
            "speculative": {"enabled": False}}}

    def test_spec_off_choice_disables_instead_of_enabling(self, tmp_path):
        """An artifact whose measured winner was "off" must apply as
        enabled:false — never switch the verify program on behind a
        config that did not ask for it and whose workload lost."""
        _, art = _artifact(tmp_path, axes={
            "serving.num_speculative_tokens": {
                "target": "serving.speculative.num_speculative_tokens",
                "value": "off", "objective": "spec_tokens_per_sec",
                "minimize": False, "score": 50.0, "evidence": []}})
        assert section_choices(art, "serving") == {
            "speculative": {"enabled": False}}
        assert apply_section({}, art, "serving") == {
            "speculative": {"enabled": False}}

    def test_paired_tiles_target_expands_to_kernel_keys(self, tmp_path):
        """The flash tiles axis records ONE paired choice; consumption
        must expand it into the two per-key registry entries the kernel
        actually resolves (a verbatim 'tiles' key would never apply)."""
        _, art = _artifact(tmp_path, axes={
            "flash_attention.tiles": {
                "target": "ops.flash_attention.tiles",
                "value": [128, 256], "objective": "steps_per_sec",
                "minimize": False, "score": 1.0, "evidence": []}})
        assert ops_choices(art) == {
            "ops.flash_attention.block_q": 128,
            "ops.flash_attention.block_k": 256}
        bad = make_artifact({"flash_attention.tiles": {
            "target": "ops.flash_attention.tiles", "value": 128,
            "objective": "steps_per_sec", "minimize": False,
            "score": 1.0, "evidence": []}})
        with pytest.raises(TunedArtifactError, match="paired axis"):
            ops_choices(bad)

    def test_fingerprint_mismatch_is_structured(self, tmp_path):
        fp = dict(topology_fingerprint(), device_count=777,
                  device_kind="tpu-v9")
        _, art = _artifact(tmp_path, fingerprint=fp)
        with pytest.raises(TunedArtifactError) as ei:
            verify_fingerprint(art)
        err = ei.value
        assert "device_count" in err.diff and "device_kind" in err.diff
        assert err.diff["device_count"]["saved"] == 777
        assert err.diff["device_count"]["current"] == jax.device_count()
        # the rendering names both sides
        assert "saved=777" in str(err)

    def test_version_drift_warns_but_applies(self, tmp_path):
        fp = dict(topology_fingerprint(), jax_version="0.0.1")
        _, art = _artifact(tmp_path, fingerprint=fp)
        verify_fingerprint(art)  # soft field only: no raise


class TestConfigPrecedence:
    def test_artifact_beats_default_user_beats_artifact(self, tmp_path):
        from deepspeed_tpu.runtime.config import DeepSpeedConfig

        path, _ = _artifact(tmp_path)
        base = {"train_batch_size": 8}
        default = DeepSpeedConfig(dict(base))
        assert default.comm_quantization.bucket_bytes == 16 * MiB
        assert default.tuned_ops == {}
        assert default.tuned_artifact_hash == "none"

        tuned = DeepSpeedConfig(dict(
            base, tuning={"enabled": True, "artifact": path}))
        assert tuned.comm_quantization.bucket_bytes == 4 * MiB
        # bucket-bytes alone never flips the section on: switching
        # reduction machinery is the comm.tier axis's MEASURED decision
        assert tuned.comm_quantization.enabled is False
        assert tuned.tuned_ops == {"ops.decode_attention.block_k": 512}
        assert tuned.tuned_artifact_hash != "none"

        explicit = DeepSpeedConfig(dict(
            base, comm_quantization={"bucket_bytes": 999},
            tuning={"enabled": True, "artifact": path}))
        assert explicit.comm_quantization.bucket_bytes == 999

    def test_comm_tier_choice_owns_the_enable_decision(self, tmp_path):
        """The comm.tier grid measures the machinery-off default too, so
        the artifact's choice decides `enabled`: a winning wire tier
        arms the quantized reduction, an "off" win keeps the default
        GSPMD reduction, and an explicit user `enabled` always wins."""
        from deepspeed_tpu.runtime.config import DeepSpeedConfig

        def tier_artifact(value):
            return _artifact(tmp_path, axes={"comm.tier": {
                "target": "comm_quantization.tier", "value": value,
                "objective": "steps_per_sec", "minimize": False,
                "score": 1.0, "evidence": []}})[0]

        base = {"train_batch_size": 8}
        on = DeepSpeedConfig(dict(base, tuning={
            "enabled": True, "artifact": tier_artifact("int8")}))
        assert on.comm_quantization.enabled is True
        assert on.comm_quantization.dtype == "int8"

        off = DeepSpeedConfig(dict(base, tuning={
            "enabled": True, "artifact": tier_artifact("off")}))
        assert off.comm_quantization.enabled is False

        user = DeepSpeedConfig(dict(
            base, comm_quantization={"enabled": False},
            tuning={"enabled": True, "artifact": tier_artifact("int8")}))
        assert user.comm_quantization.enabled is False

    def test_enabled_without_artifact_is_loud(self, tmp_path):
        from deepspeed_tpu.runtime.config import (DeepSpeedConfig,
                                                  DeepSpeedConfigError)

        with pytest.raises(DeepSpeedConfigError, match="no tuned artifact"):
            DeepSpeedConfig({"train_batch_size": 8,
                             "tuning": {"enabled": True,
                                        "artifact": os.path.join(
                                            str(tmp_path), "missing.json")}})
        # inference builds through the SAME consumption helper, so the
        # missing-artifact guidance cannot drift from the training leg
        from deepspeed_tpu.autotuning.artifact import load_for_config

        with pytest.raises(FileNotFoundError, match="no tuned artifact"):
            load_for_config({"artifact": os.path.join(str(tmp_path),
                                                      "missing.json")})

    def test_mismatched_artifact_raises_at_config_parse(self, tmp_path):
        from deepspeed_tpu.runtime.config import DeepSpeedConfig

        fp = dict(topology_fingerprint(), device_count=777)
        path, _ = _artifact(tmp_path, fingerprint=fp)
        with pytest.raises(TunedArtifactError):
            DeepSpeedConfig({"train_batch_size": 8,
                             "tuning": {"enabled": True, "artifact": path}})


# ----------------------------------------------------------------------
class TestRuntimeTunables:
    def teardown_method(self):
        runtime_tunables.clear()

    def test_precedence(self):
        assert runtime_tunables.resolve(None, "k", 256) == 256
        token = runtime_tunables.install({"k": 512})
        assert runtime_tunables.resolve(None, "k", 256) == 512
        assert runtime_tunables.resolve(128, "k", 256) == 128
        runtime_tunables.uninstall(token)
        assert runtime_tunables.resolve(None, "k", 256) == 256

    def test_overlapping_engines_compose(self):
        """Overlapping installers (ReplicaRouter replicas, or two
        engines tuned from DIFFERENT artifacts): destroying one must
        neither strip a shared key from the survivor nor leave the dead
        engine's value in effect."""
        a = runtime_tunables.install({"k": 512})           # engine A
        b = runtime_tunables.install({"k": 256, "j": 1})   # engine B
        assert runtime_tunables.get("k") == 256            # youngest wins
        runtime_tunables.uninstall(b)                      # B destroyed
        assert runtime_tunables.get("k") == 512            # A's value back
        assert runtime_tunables.get("j") is None
        runtime_tunables.uninstall(a)
        assert runtime_tunables.get("k") is None
        # extra / None uninstalls are harmless
        runtime_tunables.uninstall(a)
        runtime_tunables.uninstall(None)

    def test_decode_attention_default_resolves_through_registry(self):
        """Tracing the kernel with an installed tuned block_k produces
        the same program as passing it explicitly — and a different one
        than the built-in default (the knob is live, not cosmetic)."""
        from deepspeed_tpu.ops.decode_attention import decode_attention
        from deepspeed_tpu.utils.compat import tpu_interpret_mode

        q = jnp.ones((1, 1, 2, 8), jnp.float32)
        kc = jnp.ones((1, 512, 2, 8), jnp.float32)
        idx = jnp.asarray(4, jnp.int32)

        def jaxpr(block_k):
            with tpu_interpret_mode():
                return str(jax.make_jaxpr(
                    lambda a, b, c, i: decode_attention(
                        a, b, c, i, block_k=block_k))(q, kc, kc, idx))

        explicit_128 = jaxpr(128)
        runtime_tunables.install({"ops.decode_attention.block_k": 128})
        tuned_128 = jaxpr(None)
        runtime_tunables.clear()
        default = jaxpr(None)  # built-in DEFAULT_BLOCK_K = 256
        assert tuned_128 == explicit_128
        assert tuned_128 != default

    def test_engine_installs_and_uninstalls(self, tmp_path):
        """An engine built with a tuning block installs the artifact's
        ops choices for its lifetime and removes exactly those keys at
        destroy — the next engine traces with built-in defaults."""
        import deepspeed_tpu
        from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2ForTraining
        from deepspeed_tpu.parallel.topology import reset_topology

        path, _ = _artifact(tmp_path)
        reset_topology()
        engine, *_ = deepspeed_tpu.initialize(
            model=GPT2ForTraining(GPT2Config.tiny(dtype=jnp.float32)),
            config={"train_batch_size": 8,
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                    "steps_per_print": 10_000,
                    "telemetry": {"enabled": True, "jsonl": False},
                    "tuning": {"enabled": True, "artifact": path}})
        assert runtime_tunables.get("ops.decode_attention.block_k") == 512
        applied = [e for e in engine.telemetry.tail(10)
                   if e["kind"] == "tuning" and e["name"] == "applied"]
        assert applied and applied[0]["data"]["ops"] == {
            "ops.decode_attention.block_k": 512}
        engine.destroy()
        assert runtime_tunables.get("ops.decode_attention.block_k") is None


# ----------------------------------------------------------------------
class TestAxisRegistry:
    def test_builtin_axes_registered(self):
        names = set(all_axes())
        assert {"decode_attention.block_k", "flash_attention.tiles",
                "zero.reduce_bucket_bytes", "comm.tier",
                "serving.prefill_chunk_tokens",
                "serving.prompt_buckets"} <= names
        assert [a.name for a in default_axes()][0] == \
            "decode_attention.block_k"

    def test_duplicate_registration_rejected(self):
        axis = get_axis("comm.tier")
        with pytest.raises(ValueError, match="already registered"):
            register_axis(axis)
        register_axis(axis, replace=True)  # explicit override allowed

    def test_validity_on_this_runtime(self):
        ok, _ = get_axis("zero.reduce_bucket_bytes").valid(4 * MiB)
        assert ok == (jax.device_count() > 1)
        ok, reason = get_axis("flash_attention.tiles").valid((128, 128))
        assert not ok and "tpu" in reason  # dense path on CPU


class TestLiveTuner:
    def test_fake_runner_search_chooses_and_records_evidence(
            self, tmp_path):
        calls = []

        def fake_train(series, config):
            calls.append((series, config))
            bb = config["ds_config"]["comm_quantization"]["bucket_bytes"]
            return {"steps_per_sec": {4 * MiB: 5.0, 16 * MiB: 9.0,
                                      64 * MiB: 7.0}[bb]}

        def fake_decode(series, config):
            if series == "decode_attention":
                return {"per_call_ms": {128: 0.9, 256: 0.5,
                                        512: 0.7}[config["block_k"]]}
            chunk = config["serving"]["prefill_chunk_tokens"]
            return {"short_ttft_ms_p95": 100.0 / chunk,
                    "tokens_per_sec": 1.0}

        tuner = LiveTuner(results_dir=str(tmp_path),
                          runners={"train": fake_train,
                                   "decode": fake_decode})
        art = tuner.tune(axis_names=["decode_attention.block_k",
                                     "zero.reduce_bucket_bytes",
                                     "serving.prefill_chunk_tokens"])
        axes = art["axes"]
        # minimize picks the smallest objective, maximize the largest
        assert axes["decode_attention.block_k"]["value"] == 256
        assert axes["zero.reduce_bucket_bytes"]["value"] == 16 * MiB
        assert axes["serving.prefill_chunk_tokens"]["value"] == 64
        # every candidate is recorded as evidence with its measurements
        for name in axes:
            assert len(axes[name]["evidence"]) == 3
            assert all(("measurements" in t) or ("skipped" in t)
                       or ("error" in t) for t in axes[name]["evidence"])
        # the artifact on disk is canonical and consumable
        loaded = read_tuned_artifact(os.path.join(str(tmp_path),
                                                  "tuned.json"))
        assert dumps_artifact(loaded) == dumps_artifact(art)
        verify_fingerprint(loaded)

    def test_skipped_axis_records_reason_without_choice(self, tmp_path):
        tuner = LiveTuner(results_dir=str(tmp_path), runners={
            "train": lambda s, c: pytest.fail("must not measure")})
        entry = tuner.tune_axis(get_axis("flash_attention.tiles"))
        assert entry["value"] is None
        assert all("skipped" in t for t in entry["evidence"])

    def test_failed_trial_is_evidence_not_crash(self, tmp_path):
        def flaky(series, config):
            if config["block_k"] == 256:
                raise RuntimeError("boom")
            return {"per_call_ms": float(config["block_k"])}

        tuner = LiveTuner(results_dir=str(tmp_path),
                          runners={"decode": flaky})
        entry = tuner.tune_axis(get_axis("decode_attention.block_k"))
        assert entry["value"] == 128  # minimize over the survivors
        errors = [t for t in entry["evidence"] if "error" in t]
        assert len(errors) == 1 and "boom" in errors[0]["error"]

    def test_missing_objective_is_loud(self, tmp_path):
        tuner = LiveTuner(results_dir=str(tmp_path),
                          runners={"decode": lambda s, c: {"wrong": 1}})
        entry = tuner.tune_axis(get_axis("decode_attention.block_k"))
        assert entry["value"] is None
        assert all("error" in t and "objective" in t["error"]
                   for t in entry["evidence"])

    def test_trials_land_in_telemetry_stream(self, tmp_path):
        from deepspeed_tpu.telemetry import Telemetry

        tele = Telemetry({"enabled": True, "jsonl": False})
        tuner = LiveTuner(results_dir=str(tmp_path), telemetry=tele,
                          runners={"decode": lambda s, c: {
                              "per_call_ms": float(c["block_k"])}})
        tuner.tune_axis(get_axis("decode_attention.block_k"))
        events = [e for e in tele.tail(20) if e["kind"] == "tuning"]
        assert len(events) == 3
        assert {e["data"]["value"] for e in events} == {128, 256, 512}


# ----------------------------------------------------------------------
class TestServingTuning:
    @pytest.mark.heavy
    def test_serving_keys_apply_with_user_precedence(self, tmp_path):
        import deepspeed_tpu
        from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
        from deepspeed_tpu.parallel.topology import reset_topology

        path, _ = _artifact(tmp_path, axes={
            "serving.prefill_chunk_tokens": {
                "target": "serving.prefill_chunk_tokens", "value": 32,
                "objective": "short_ttft_ms_p95", "minimize": True,
                "score": 1.0, "evidence": [
                    {"value": 32,
                     "measurements": {"short_ttft_ms_p95": 1.0}}]}})
        cfg = GPT2Config.tiny(dtype=jnp.float32)

        reset_topology()
        eng = deepspeed_tpu.init_inference(
            GPT2LMHeadModel(cfg), dtype=cfg.dtype,
            tensor_parallel={"tp_size": 1},
            serving={"block_size": 8, "decode_slots": 2},
            tuning={"enabled": True, "artifact": path})
        assert eng._serving_cfg.prefill_chunk_tokens == 32  # artifact
        eng.destroy()
        assert runtime_tunables.get("ops.decode_attention.block_k") is None

        reset_topology()
        eng2 = deepspeed_tpu.init_inference(
            GPT2LMHeadModel(cfg), dtype=cfg.dtype,
            tensor_parallel={"tp_size": 1},
            serving={"block_size": 8, "decode_slots": 2,
                     "prefill_chunk_tokens": 16},
            tuning={"enabled": True, "artifact": path})
        assert eng2._serving_cfg.prefill_chunk_tokens == 16  # user wins
        eng2.destroy()


# ----------------------------------------------------------------------
class TestTelemetryReportTuning:
    def test_tuning_section_renders_trials_and_artifact(self, tmp_path):
        from deepspeed_tpu.telemetry import Telemetry
        from tools.telemetry_report import aggregate, render

        from deepspeed_tpu.telemetry.events import load_events

        tele = Telemetry({"enabled": True, "dir": str(tmp_path)})
        tuner = LiveTuner(results_dir=str(tmp_path), telemetry=tele,
                          runners={"decode": lambda s, c: {
                              "per_call_ms": float(c["block_k"])}})
        art = tuner.tune(axes=[get_axis("decode_attention.block_k")])
        tele.emit("tuning", "applied",
                  data={"ops": {"ops.decode_attention.block_k": 128},
                        "tuned_hash": "beef"})
        tele.flush()
        path = os.path.join(str(tmp_path), "telemetry.jsonl")
        agg = aggregate(load_events(path))
        assert agg["tuning"]["events"] == 4
        assert len(agg["tuning"]["trials"]["decode_attention.block_k"]) == 3
        assert agg["tuning"]["applied"]["tuned_hash"] == "beef"
        text = render(path, tuned_artifact=art)
        assert "tuning:" in text
        assert "decode_attention.block_k: chose 128" in text
        md = render(path, markdown=True, tuned_artifact=art)
        assert "| axis | chosen |" in md
        tele.close()


# ----------------------------------------------------------------------
class TestBenchRunSeries:
    @pytest.mark.parametrize("name", ["startup", "gateway"])
    def test_unknown_series_rejected(self, name):
        """Only the four series the axis registry names came into the
        package: a train-side and a decode-side name of the bench
        scripts that went are unknown."""
        from deepspeed_tpu.autotuning import series

        with pytest.raises(KeyError, match="unknown series"):
            series.run(name)

    def test_default_runners_resolve_from_the_package_alone(self, tmp_path):
        """A library layer imports nothing above itself: with only the
        package importable (no checkout root on ``sys.path``, the
        working directory elsewhere) a default ``LiveTuner`` resolves
        both families' runners."""
        import subprocess
        import sys

        import deepspeed_tpu

        site = tmp_path / "site"
        site.mkdir()
        os.symlink(os.path.dirname(deepspeed_tpu.__file__),
                   site / "deepspeed_tpu")
        script = (
            "import os, sys\n"
            "import deepspeed_tpu.autotuning.measure as measure\n"
            "from deepspeed_tpu.autotuning import series\n"
            "tuner = measure.LiveTuner()\n"
            "assert tuner._runner('train') is series.run\n"
            "assert tuner._runner('decode') is series.run\n"
            "assert not any(os.path.exists(os.path.join(p, 'chip_smoke.py'))\n"
            "               for p in sys.path), sys.path\n"
            "print('RUNNERS_OK')\n")
        res = subprocess.run(
            [sys.executable, "-c", script], capture_output=True, text=True,
            cwd=str(tmp_path), timeout=120,
            env={**os.environ, "PYTHONPATH": str(site)})
        assert res.returncode == 0, res.stderr
        assert "RUNNERS_OK" in res.stdout

    @pytest.mark.heavy
    def test_acceptance_three_axes_on_real_bench(self, tmp_path):
        """ISSUE 8 acceptance: the live autotuner over the three named
        axes on the package's own series writes a tuned.json whose
        choices are backed by recorded measurement evidence and
        consumed by a rebuilt engine."""
        import deepspeed_tpu
        from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2ForTraining
        from deepspeed_tpu.parallel.topology import reset_topology
        from deepspeed_tpu.runtime.config import DeepSpeedConfig

        tuner = LiveTuner(base_config={"batch": 2, "seq": 16, "steps": 2},
                          results_dir=str(tmp_path))
        art = tuner.tune(axis_names=["decode_attention.block_k",
                                     "zero.reduce_bucket_bytes",
                                     "serving.prefill_chunk_tokens"])
        path = os.path.join(str(tmp_path), "tuned.json")
        assert os.path.exists(path)
        for name in ("decode_attention.block_k",
                     "zero.reduce_bucket_bytes",
                     "serving.prefill_chunk_tokens"):
            axis = art["axes"][name]
            assert axis["value"] is not None
            measured = [t for t in axis["evidence"] if "measurements" in t]
            assert measured, f"{name} has no measured evidence"
            assert all(axis["objective"] in t["measurements"]
                       for t in measured)

        # a rebuilt engine consumes the choices
        parsed = DeepSpeedConfig({"train_batch_size": 8,
                                  "tuning": {"enabled": True,
                                             "artifact": path}})
        assert parsed.comm_quantization.bucket_bytes == \
            art["axes"]["zero.reduce_bucket_bytes"]["value"]
        reset_topology()
        engine, *_ = deepspeed_tpu.initialize(
            model=GPT2ForTraining(GPT2Config.tiny(dtype=jnp.float32)),
            config={"train_batch_size": 8,
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                    "steps_per_print": 10_000,
                    "tuning": {"enabled": True, "artifact": path}})
        assert runtime_tunables.get("ops.decode_attention.block_k") == \
            art["axes"]["decode_attention.block_k"]["value"]
        ids = np.random.default_rng(0).integers(0, 256, (8, 16)).astype(
            np.int32)
        loss = engine({"input_ids": ids})
        engine.backward(loss)
        engine.step()
        float(loss)
        engine.destroy()
        assert runtime_tunables.get("ops.decode_attention.block_k") is None
