"""The measured live-tuner: walk the axis registry, measure every valid
candidate on the package's own series, write the tuned-config artifact.

Unlike the offline :class:`~deepspeed_tpu.autotuning.autotuner.Autotuner`
(subprocess trials over launch-time choices, cost-model ordered), the
live tuner runs *in-process* against ``series.run``: each trial builds
a real engine, and the measurement dict carries the
telemetry-stream objectives (steps/s, compile seconds, retraces in the
timed window, collective wire bytes, TTFT percentiles) — not wall clock
alone. The output is a versioned, deterministic, fingerprint-pinned
``tuned.json`` (``artifact.py``) that ``runtime/config.py`` and the
serving build consume with explicit-user-key > artifact > default
precedence.

Usage::

    from deepspeed_tpu.autotuning.measure import LiveTuner

    artifact = LiveTuner(results_dir="autotuning_results").tune(
        axis_names=["decode_attention.block_k",
                    "zero.reduce_bucket_bytes",
                    "serving.prefill_chunk_tokens"])
    # -> autotuning_results/tuned.json; consume via
    #    {"tuning": {"enabled": True}} in the engine config
"""

import os
from typing import Callable, Dict, List, Optional, Sequence

from deepspeed_tpu.autotuning.artifact import (TUNED_ARTIFACT_NAME,
                                               make_artifact,
                                               write_tuned_artifact)
from deepspeed_tpu.autotuning.live import LiveAxis, default_axes, get_axis
from deepspeed_tpu.utils.fingerprint import topology_fingerprint
from deepspeed_tpu.utils.logging import logger


def _deep_merge(base: Dict, extra: Dict) -> Dict:
    out = dict(base or {})
    for k, v in (extra or {}).items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _deep_merge(out[k], v)
        else:
            out[k] = v
    return out


def _default_runner(bench: str) -> Callable[[str, Dict], Dict]:
    """The measurement entry point of one axis family. Both families
    resolve to ``series.run``; the family is the key by which
    ``LiveTuner(runners=...)`` substitutes another."""
    if bench not in ("train", "decode"):
        raise ValueError(f"unknown bench family {bench!r}")
    from deepspeed_tpu.autotuning import series

    return series.run


class LiveTuner:
    """Measured search over live tunable axes (module docstring).

    ``runners`` overrides the measurement per family (tests inject
    fakes; production uses ``series.run``). ``telemetry`` is an
    optional :class:`~deepspeed_tpu.telemetry.Telemetry` — each trial
    lands in its event stream as a ``tuning`` event, so
    ``tools/telemetry_report.py`` can render the search next to the
    compile/step-cost sections."""

    def __init__(self, base_config: Optional[Dict] = None,
                 results_dir: str = "autotuning_results",
                 runners: Optional[Dict[str, Callable]] = None,
                 telemetry=None):
        self.base_config = dict(base_config or {})
        self.results_dir = results_dir
        self._runners = dict(runners or {})
        self._telemetry = telemetry

    # ------------------------------------------------------------------
    def _runner(self, bench: str) -> Callable[[str, Dict], Dict]:
        if bench not in self._runners:
            self._runners[bench] = _default_runner(bench)
        return self._runners[bench]

    def _emit(self, axis: LiveAxis, **data):
        if self._telemetry is not None:
            self._telemetry.emit("tuning", axis.name, data=data)

    def measure(self, axis: LiveAxis, value) -> Dict:
        """One trial: run the axis's series with the candidate
        applied; returns the measurement dict (must carry the axis
        objective key)."""
        config = _deep_merge(self.base_config, axis.series_config(value))
        measurements = self._runner(axis.bench)(axis.series, config)
        if axis.objective not in measurements:
            raise KeyError(
                f"series {axis.series!r} returned no {axis.objective!r} "
                f"(keys: {sorted(measurements)}) — the axis objective and "
                "the series payload drifted apart")
        return measurements

    # ------------------------------------------------------------------
    def tune_axis(self, axis: LiveAxis) -> Dict:
        """Measure every candidate on one axis; returns the artifact
        entry (chosen value + full evidence, skips and failures
        included)."""
        trials: List[Dict] = []
        best_value, best_score = None, None
        # resolve the runner BEFORE the candidate loop: an unknown family
        # must fail the tune loudly, not become per-trial "evidence" in
        # a silently empty artifact
        self._runner(axis.bench)
        for value in axis.grid:
            ok, reason = axis.valid(value)
            if not ok:
                trials.append({"value": value, "skipped": reason})
                self._emit(axis, value=value, skipped=reason)
                continue
            try:
                m = self.measure(axis, value)
            except Exception as e:  # noqa: BLE001 — a failed candidate is
                # evidence, not a tuner crash (the reference records OOMing
                # trials as infeasible the same way)
                trials.append({"value": value, "error": str(e)[:300]})
                self._emit(axis, value=value, error=str(e)[:300])
                logger.warning(f"[tuning] {axis.name}={value!r} failed: {e}")
                continue
            trials.append({"value": value, "measurements": m})
            score = m.get(axis.objective)
            self._emit(axis, value=value, objective=axis.objective,
                       score=score)
            if score is None:
                continue
            better = (best_score is None
                      or (score < best_score if axis.minimize
                          else score > best_score))
            if better:
                best_value, best_score = value, score
        if best_value is not None:
            logger.info(f"[tuning] {axis.name}: chose {best_value!r} "
                        f"({axis.objective}={best_score})")
        else:
            logger.warning(f"[tuning] {axis.name}: no candidate measured "
                           "successfully; axis recorded without a choice")
        return {
            "target": axis.target,
            "value": best_value,
            "objective": axis.objective,
            "minimize": axis.minimize,
            "score": best_score,
            "evidence": trials,
        }

    def tune(self, axes: Optional[Sequence[LiveAxis]] = None,
             axis_names: Optional[Sequence[str]] = None,
             write: bool = True) -> Dict:
        """Tune the given axes (default: the full built-in registry) and
        write ``<results_dir>/tuned.json``. Returns the artifact."""
        if axes is None:
            axes = ([get_axis(n) for n in axis_names]
                    if axis_names else default_axes())
        entries = {}
        for axis in axes:
            entries[axis.name] = self.tune_axis(axis)
        artifact = make_artifact(entries,
                                 fingerprint=topology_fingerprint())
        if write:
            path = os.path.join(self.results_dir, TUNED_ARTIFACT_NAME)
            write_tuned_artifact(path, artifact)
            logger.info(f"[tuning] wrote {path} "
                        f"({sum(1 for a in entries.values() if a['value'] is not None)}"
                        f"/{len(entries)} axes chosen)")
        return artifact
