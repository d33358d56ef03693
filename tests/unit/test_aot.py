"""AOT program cache (``deepspeed_tpu/aot``): bundle format, dispatch
pre-population and checkpoint shipping.

- the bundle FORMAT and tooling are tested with real serialized bytes;
- the DISPATCH path (store hit -> zero compiles) is tested with a fake
  store holding the real compiled object, and end-to-end through the
  engine with the serialize/deserialize pair monkeypatched to a
  registry — everything except jax's own serializer runs for real.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.aot import (AOTStore, BundleReader, capture_entries,
                               current_bundle_identity, load_bundle,
                               read_bundle, save_bundle, verify_manifest)
from deepspeed_tpu.aot.bundle import blob_name
from deepspeed_tpu.runtime.checkpoint_engine.checkpoint_engine import (
    CheckpointEngine)
from deepspeed_tpu.telemetry import Telemetry
from deepspeed_tpu.telemetry import compile_watch
from deepspeed_tpu.telemetry.jit_watch import signature_fingerprint
from deepspeed_tpu.utils.fingerprint import (diff_fingerprint,
                                             fingerprint_hash,
                                             topology_fingerprint)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _watched_double(tele, name="demo.step"):
    wf = tele.watch_jit(jax.jit(lambda x: x * 2 + 1), name)
    wf(jnp.ones((4, 4)))  # one compile, one cache entry
    return wf


def _real_bundle(tmp_path, tele=None):
    tele = tele or Telemetry({"enabled": True, "jsonl": False})
    wf = _watched_double(tele)  # held: the watch registry is weak
    entries = capture_entries(tele)
    del wf
    tag = os.path.join(str(tmp_path), "tag")
    identity = current_bundle_identity(mesh_axes={"data": 1})
    manifest = save_bundle(CheckpointEngine(), tag, entries, identity)
    return tag, manifest, identity


# ----------------------------------------------------------------------
class TestFingerprint:
    def test_fields_and_hash_stability(self):
        fp = topology_fingerprint(mesh_axes={"data": 2})
        assert fp["backend"] == jax.default_backend()
        assert fp["device_count"] == jax.device_count()
        assert fp["mesh_axes"] == {"data": 2}
        assert fingerprint_hash(fp) == fingerprint_hash(
            json.loads(json.dumps(fp)))

    def test_diff_lists_saved_vs_current(self):
        a = topology_fingerprint()
        b = dict(a, device_count=999)
        d = diff_fingerprint(a, b)
        assert d == {"device_count": {"saved": a["device_count"],
                                      "current": 999}}


class TestSignature:
    def test_same_args_same_hash_and_shape_sensitivity(self):
        tele = Telemetry({"enabled": True, "jsonl": False})
        wf = tele.watch_jit(jax.jit(lambda x: x + 1), "sig.test")
        wf(jnp.ones((2, 3)))
        wf(jnp.ones((4, 3)))
        sigs = [signature_fingerprint(k) for k in wf._cache]
        assert len(sigs) == 2 and sigs[0] != sigs[1]
        # recomputing from the same key is stable
        k = next(iter(wf._cache))
        assert signature_fingerprint(k) == signature_fingerprint(k)


# ----------------------------------------------------------------------
class TestBundleFormat:
    def test_capture_save_read_roundtrip(self, tmp_path):
        tag, manifest, identity = _real_bundle(tmp_path)
        assert [p["name"] for p in manifest["programs"]] == ["demo.step"]
        reader = load_bundle(tag)
        assert len(reader) == 1
        prog = reader.programs()[0]
        blob = reader.read_blob(prog["name"], prog["sig_hash"])
        assert blob_name(blob) == prog["file"]
        assert reader.verify_all() == []
        assert verify_manifest(reader.manifest, identity) == []

    def test_no_bundle_is_none_and_torn_manifest_is_loud(self, tmp_path):
        assert read_bundle(str(tmp_path)) is None
        path = os.path.join(str(tmp_path), "aot_manifest.json")
        with open(path, "w") as f:
            f.write('{"version": 1, "programs": [')  # torn write
        with pytest.raises(OSError, match="unreadable"):
            read_bundle(str(tmp_path))

    def test_corrupt_blob_detected_before_deserialize(self, tmp_path):
        tag, manifest, _ = _real_bundle(tmp_path)
        prog = manifest["programs"][0]
        with open(os.path.join(tag, prog["file"]), "r+b") as f:
            f.write(b"\x00\x00\x00\x00")
        reader = BundleReader(tag)
        with pytest.raises(OSError, match="hash mismatch"):
            reader.read_blob(prog["name"], prog["sig_hash"])
        assert len(reader.verify_all()) == 1

    def test_identity_mismatch_is_structured(self, tmp_path):
        tag, manifest, identity = _real_bundle(tmp_path)
        other = {"fingerprint": dict(identity["fingerprint"],
                                     jaxlib_version="9.9.9"),
                 "fingerprint_hash": "f" * 16, "tuned_hash": "abcd"}
        fields = {m["field"] for m in verify_manifest(manifest, other)}
        assert "fingerprint_hash" in fields
        assert "tuned_hash" in fields
        assert "fingerprint.jaxlib_version" in fields


# ----------------------------------------------------------------------
class _FakeStore:
    """AOTStore stand-in holding the REAL compiled object — proves the
    WatchedFunction preload path (dispatch served without a compile)
    without any native deserialization."""

    def __init__(self, programs):
        self._programs = programs  # {(name, sig_hash): compiled}
        self.manifest = {"tuned_hash": "none"}
        self.hits = 0

    def __len__(self):
        return len(self._programs)

    def lookup(self, name, sig_hash):
        out = self._programs.get((name, sig_hash))
        if out is not None:
            self.hits += 1
        return out


class TestDispatchPrepopulation:
    def test_store_hit_skips_compile_and_emits_event(self):
        donor = Telemetry({"enabled": True, "jsonl": False})
        # donor compiles under a DIFFERENT label so the compile-watch
        # attribution check below can prove the consumer never compiled
        wf = _watched_double(donor, "prepop.donor")
        key, compiled = next(iter(wf._cache.items()))
        store = _FakeStore({("prepop.step",
                             signature_fingerprint(key)): compiled})

        tele = Telemetry({"enabled": True, "jsonl": False})
        tele.set_aot_store(store)
        compile_watch.install()
        x = jnp.ones((4, 4))
        wf2 = tele.watch_jit(jax.jit(lambda x: x * 2 + 1), "prepop.step")
        out = wf2(x)
        assert np.asarray(jax.device_get(out))[0, 0] == 3.0
        # the watched program itself never compiled: served entirely
        # from the store (a compile would land under its label and bump
        # the instance counter)
        assert "prepop.step" not in compile_watch.snapshot()["by_label"]
        assert wf2.compiles == 0
        assert store.hits == 1
        # the program never entered the compile totals: a warm restart's
        # watchdog records ZERO steady-state compiles
        assert tele.summary()["per_function"] == {}
        actions = [e["data"].get("action") for e in tele.tail()
                   if e["kind"] == "aot"]
        assert "armed" in actions and "hit" in actions

    def test_store_miss_compiles_normally(self):
        tele = Telemetry({"enabled": True, "jsonl": False})
        tele.set_aot_store(_FakeStore({}))
        wf = _watched_double(tele, "miss.step")
        assert wf.compiles == 1

    def test_aot_store_lazy_load_failure_falls_back(self, tmp_path):
        tag, manifest, _ = _real_bundle(tmp_path)
        prog = manifest["programs"][0]
        with open(os.path.join(tag, prog["file"]), "r+b") as f:
            f.write(b"\x00\x00\x00\x00")  # corrupt
        store = AOTStore(BundleReader(tag))
        assert store.lookup(prog["name"], prog["sig_hash"]) is None
        assert store.misses == 1
        # second miss comes from the failed-set, not a re-read
        assert store.lookup(prog["name"], prog["sig_hash"]) is None


# ----------------------------------------------------------------------
def _tiny_engine(tmp_path=None, ndev=1, aot=True, telemetry=True,
                 extra=None):
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2ForTraining
    from deepspeed_tpu.parallel.topology import MeshTopology, reset_topology

    reset_topology()
    cfg = GPT2Config.tiny(dtype=jnp.float32)
    topo = MeshTopology(axis_sizes={"data": ndev},
                        devices=jax.devices()[:ndev])
    config = {
        "train_batch_size": 2 * ndev,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 0},
        "steps_per_print": 10_000,
    }
    if telemetry:
        config["telemetry"] = {"enabled": True, "jsonl": False,
                               "memory": False}
    if aot:
        config["aot"] = {"enabled": True}
    config.update(extra or {})
    engine, *_ = deepspeed_tpu.initialize(
        model=GPT2ForTraining(cfg), mesh=topo, config=config)
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2 * ndev, 16)).astype(np.int32)
    return engine, ids


def _step(engine, ids):
    loss = engine({"input_ids": ids})
    engine.backward(loss)
    engine.step()
    float(loss)
    jax.block_until_ready(engine.state.params)


def _first_param(engine):
    return np.asarray(jax.device_get(
        jax.tree_util.tree_leaves(engine.state.params)[0]))


class TestEngineAOT:
    def test_aot_requires_telemetry(self):
        from deepspeed_tpu.runtime.config import (DeepSpeedConfig,
                                                  DeepSpeedConfigError)

        with pytest.raises(DeepSpeedConfigError, match="telemetry"):
            DeepSpeedConfig({"train_batch_size": 8,
                             "aot": {"enabled": True}})

    @pytest.mark.heavy
    def test_zero_overhead_pin(self):
        """No ``tuning``/``aot`` blocks vs explicitly-disabled blocks:
        the lowered step program is byte-identical (PR 2-7 convention)."""
        absent, ids = _tiny_engine(aot=False, telemetry=False)
        absent._ensure_state(absent._shard_batch({"input_ids": ids}))
        text_absent = absent._jit_micro.lower(
            absent.state, absent._shard_batch({"input_ids": ids})).as_text()
        absent.destroy()
        disabled, ids = _tiny_engine(
            aot=False, telemetry=False,
            extra={"tuning": {"enabled": False},
                   "aot": {"enabled": False}})
        disabled._ensure_state(disabled._shard_batch({"input_ids": ids}))
        text_disabled = disabled._jit_micro.lower(
            disabled.state,
            disabled._shard_batch({"input_ids": ids})).as_text()
        disabled.destroy()
        assert text_absent == text_disabled

    @pytest.mark.heavy
    def test_signature_stable_across_restart(self, tmp_path):
        """The invariant the AOT store keys on: a fresh engine that
        loads the checkpoint presents the SAME program signatures as
        the saved run's steady state (the loaded counters/rng are
        re-placed under the canonical shardings — without that, the
        first dispatch would retrace on sharding alone)."""
        a, ids = _tiny_engine(aot=False)
        _step(a, ids)
        sigs_a = {(wf.name, signature_fingerprint(k))
                  for wf in a.telemetry.watched_functions()
                  for k in wf._cache}
        a.save_checkpoint(str(tmp_path), tag="t1")
        a.destroy()

        b, ids = _tiny_engine(aot=False)
        b.load_checkpoint(str(tmp_path), tag="t1")
        _step(b, ids)
        sigs_b = {(wf.name, signature_fingerprint(k))
                  for wf in b.telemetry.watched_functions()
                  for k in wf._cache}
        b.destroy()
        assert sigs_a == sigs_b

    @pytest.mark.heavy
    def test_warm_restart_with_fake_serializer(self, tmp_path,
                                               monkeypatch):
        """End-to-end warm-restart pin with jax's native serializer
        swapped for a registry (everything else — capture, bundle
        files, integrity, identity verify, store arming, dispatch — is
        the real path): resume + first step records ZERO backend
        compiles for the steady-state programs."""
        from deepspeed_tpu.aot import capture as cap
        registry = {}

        def fake_serialize(compiled):
            token = f"prog{len(registry)}".encode()
            registry[token] = compiled
            return token

        monkeypatch.setattr(cap, "serialize_compiled", fake_serialize)
        monkeypatch.setattr(cap, "deserialize_compiled",
                            lambda blob: registry[blob])

        saver, ids = _tiny_engine()
        _step(saver, ids)
        saver.save_checkpoint(str(tmp_path), tag="t1")
        names = [e["name"] for e in saver.telemetry.tail(50)
                 if e["kind"] == "aot"]
        assert "captured" in names
        bundle_files = [f for f in
                        os.listdir(os.path.join(str(tmp_path), "t1"))
                        if f.startswith("aot_")]
        assert "aot_manifest.json" in bundle_files
        assert len(bundle_files) >= 2  # manifest + >=1 program blob
        saver.destroy()

        fresh, ids = _tiny_engine()
        fresh.load_checkpoint(str(tmp_path), tag="t1")
        mark = compile_watch.snapshot()["backend_compiles"]
        _step(fresh, ids)
        assert compile_watch.snapshot()["backend_compiles"] == mark
        assert fresh.telemetry.summary()["per_function"] == {}
        actions = [e["data"].get("action") for e in fresh.telemetry.tail(50)
                   if e["kind"] == "aot"]
        assert "armed" in actions and actions.count("hit") >= 2
        fresh.destroy()

    @pytest.mark.heavy
    def test_identity_mismatch_disables_store(self, tmp_path,
                                              monkeypatch):
        from deepspeed_tpu.aot import capture as cap
        registry = {}
        monkeypatch.setattr(
            cap, "serialize_compiled",
            lambda c: registry.setdefault(f"p{len(registry)}".encode(), c)
            and f"p{len(registry)-1}".encode())
        monkeypatch.setattr(cap, "deserialize_compiled",
                            lambda blob: registry[blob])

        saver, ids = _tiny_engine()
        _step(saver, ids)
        saver.save_checkpoint(str(tmp_path), tag="t1")
        saver.destroy()
        # doctor the manifest: a bundle from a different runtime
        man_path = os.path.join(str(tmp_path), "t1", "aot_manifest.json")
        with open(man_path) as f:
            manifest = json.load(f)
        manifest["fingerprint_hash"] = "0" * 16
        with open(man_path, "w") as f:
            json.dump(manifest, f)
        # the integrity layer is off in this config, so the edit is fine

        fresh, ids = _tiny_engine()
        fresh.load_checkpoint(str(tmp_path), tag="t1")
        events = [e for e in fresh.telemetry.tail(50)
                  if e["kind"] == "aot" and e["name"] == "disabled"]
        assert events and events[0]["data"]["reason"] == "identity_mismatch"
        assert any(m["field"] == "fingerprint_hash"
                   for m in events[0]["data"]["mismatches"])
        _step(fresh, ids)  # compiles normally
        assert fresh.telemetry.summary()["per_function"]
        fresh.destroy()

        # fail_on_mismatch raises instead
        strict, ids = _tiny_engine(extra={"aot": {
            "enabled": True, "fail_on_mismatch": True}})
        with pytest.raises(RuntimeError, match="different runtime"):
            strict.load_checkpoint(str(tmp_path), tag="t1")
        strict.destroy()


# ----------------------------------------------------------------------
class TestTelemetryReportAot:
    def test_aot_section_renders_hits_and_disabled(self, tmp_path):
        from tools.telemetry_report import aggregate, render

        from deepspeed_tpu.telemetry.events import load_events

        tele = Telemetry({"enabled": True, "dir": str(tmp_path)})
        tele.emit("aot", "captured", data={"programs": 2, "bytes": 1024})
        tele.emit("aot", "engine", data={"action": "armed", "programs": 2})
        tele.emit("aot", "engine.micro_step",
                  data={"action": "hit", "sig_hash": "ab"})
        tele.emit("aot", "disabled",
                  data={"what": "restore", "reason": "jaxlib < 0.5"})
        tele.flush()
        path = os.path.join(str(tmp_path), "telemetry.jsonl")
        agg = aggregate(load_events(path))["aot"]
        assert agg["hits"] == 1 and agg["armed_programs"] == 2
        assert agg["captured"] == 2
        assert agg["disabled"][0]["what"] == "restore"
        text = render(path)
        assert "aot: 1 warm dispatch hit(s)" in text
        assert "DISABLED (restore): jaxlib < 0.5" in text
        tele.close()


# ----------------------------------------------------------------------
class TestAotPackTool:
    def test_inspect_verify_and_exit_codes(self, tmp_path, capsys):
        # in-process main() keeps this a cheap tier-1 smoke (the heavy
        # subprocess leg below pins the CLI contract once)
        from tools.aot_pack import main as aot_pack_main

        tag, manifest, _ = _real_bundle(tmp_path)
        assert aot_pack_main([tag, "--verify", "--json"]) == 0
        payload = json.loads(capsys.readouterr().out)
        assert payload["verify"]["ok"] is True
        assert payload["programs"][0]["name"] == "demo.step"

        # corrupt a blob -> exit 2
        prog = manifest["programs"][0]
        with open(os.path.join(tag, prog["file"]), "r+b") as f:
            f.write(b"\x00\x00\x00\x00")
        assert aot_pack_main([tag, "--verify"]) == 2
        assert "MISMATCH" in capsys.readouterr().out

        # no bundle at all -> exit 1
        assert aot_pack_main([str(tmp_path)]) == 1

    @pytest.mark.heavy
    def test_cli_subprocess(self, tmp_path):
        tag, _, _ = _real_bundle(tmp_path)
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "aot_pack.py"),
             tag, "--verify"],
            capture_output=True, text=True, cwd=REPO, timeout=300)
        assert r.returncode == 0, r.stderr
        assert "every blob matches" in r.stdout
