"""Live tunable axes: the plugin registry the measured autotuner walks.

The offline autotuner (``space.py``) enumerates *launch-time* choices —
micro-batch, ZeRO stage, remat — against a closed-form cost model. The
axes here are the knobs PRs 1–7 actually introduced, and none of them
is predictable from a roofline: Pallas tile sizes (grid overhead vs VMEM
pressure), ZeRO reduction bucket bytes (collective latency vs overlap
window — T3, arXiv:2401.16677, shows no static model ranks these),
collective wire tier (compression CPU/step cost vs wire bytes), and the
serving prefill shape (chunk size / bucket set vs TTFT). Each axis
declares:

- a **candidate grid** (JSON-able values);
- a **validity predicate** — a candidate the current runtime cannot
  measure (dp=1 for a reduction axis, no serving layer) is recorded as
  skipped with the reason, never silently dropped;
- a **measurement hook** — the series (``series.run``) that measures
  it for real, reading the PR 2 telemetry stream (step cost, wire
  bytes, retraces, TTFT) as the objective rather than wall clock alone;
- a **target** — the config path (``comm_quantization.bucket_bytes``,
  ``serving.prefill_chunk_tokens``) or kernel-registry key
  (``ops.decode_attention.block_k``) the chosen value is applied to.

Import-light by design (no jax at module level): registering axes and
reading artifacts must not touch a device.
"""

import dataclasses
from typing import Callable, Dict, Optional, Sequence, Tuple

MiB = 1024 * 1024


@dataclasses.dataclass(frozen=True)
class LiveAxis:
    """One measurable tunable (module docstring)."""

    name: str                 # artifact key, e.g. "zero.reduce_bucket_bytes"
    target: str               # config path or ops-registry key it tunes
    grid: Tuple               # candidate values (JSON-able)
    bench: str                # runner family, "train" or "decode": the
    #                           key of LiveTuner(runners={family: fn})
    series: str               # series.run name the measurement drives
    objective: str            # measurement key that ranks candidates
    minimize: bool = False
    # config overrides handed to series.run for one candidate value
    overrides: Callable[[object], Dict] = None
    # (ok, reason) — reason recorded in evidence when skipped
    validity: Optional[Callable[[object], Tuple[bool, str]]] = None

    def valid(self, value) -> Tuple[bool, str]:
        if self.validity is None:
            return True, ""
        return self.validity(value)

    def series_config(self, value) -> Dict:
        return self.overrides(value) if self.overrides else {}


# ----------------------------------------------------------------------
# registry
_REGISTRY: Dict[str, LiveAxis] = {}


def register_axis(axis: LiveAxis, replace: bool = False) -> LiveAxis:
    if axis.name in _REGISTRY and not replace:
        raise ValueError(f"live axis {axis.name!r} already registered "
                         "(pass replace=True to override)")
    _REGISTRY[axis.name] = axis
    return axis


def get_axis(name: str) -> LiveAxis:
    if name not in _REGISTRY:
        raise KeyError(
            f"unknown live axis {name!r}; registered: {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def all_axes() -> Dict[str, LiveAxis]:
    return dict(_REGISTRY)


def default_axes() -> Sequence[LiveAxis]:
    """The built-in axes, in a stable tuning order (cheap kernel
    microbenches first, engine-building series last)."""
    return tuple(_REGISTRY[n] for n in _DEFAULT_ORDER)


# ----------------------------------------------------------------------
# validity helpers (lazy jax imports)
def _needs_multichip(value) -> Tuple[bool, str]:
    import jax

    if jax.device_count() > 1:
        return True, ""
    return False, "needs >1 device (nothing crosses a wire at dp=1)"


def _tile_on_backend(value) -> Tuple[bool, str]:
    import jax

    if jax.default_backend() in ("tpu", "cpu"):
        # TPU runs the real kernel; CPU measures via interpret mode
        # (relative ranking only, but the plumbing is identical)
        return True, ""
    return False, f"no Pallas path on backend {jax.default_backend()!r}"


def _mesh_shape_valid(value):
    """(data, fsdp, tp) candidate: data = -1 (fill), fsdp*tp must divide
    the device count with at least one device left for data. tp=2 also
    needs the series model's head count divisible — the smoke GPT-2 has
    4+ heads, so any tp <= 4 power of two is head-legal."""
    import jax

    _, f, t = (int(v) for v in value)
    n = jax.device_count()
    if f * t == 1:
        return True, ""  # the pure-DP default is always measurable
    if n % (f * t) != 0 or n // (f * t) < 1:
        return False, (f"device count {n} not divisible by "
                       f"fsdp*tp = {f * t}")
    if n == 1:
        return False, "needs >1 device (nothing to factor at n=1)"
    return True, ""


# ----------------------------------------------------------------------
# built-in axes
_DEFAULT_ORDER = (
    "decode_attention.block_k",
    "flash_attention.tiles",
    "zero.reduce_bucket_bytes",
    "comm.tier",
    "mesh.shape",
    "serving.prefill_chunk_tokens",
    "serving.prompt_buckets",
    "serving.num_speculative_tokens",
)

register_axis(LiveAxis(
    name="decode_attention.block_k",
    target="ops.decode_attention.block_k",
    grid=(128, 256, 512),
    bench="decode", series="decode_attention",
    objective="per_call_ms", minimize=True,
    overrides=lambda v: {"block_k": int(v)},
    validity=_tile_on_backend,
))

register_axis(LiveAxis(
    # one axis, paired values: bq/bk trade VMEM rows against grid steps
    # together, so searching them independently measures noise
    name="flash_attention.tiles",
    target="ops.flash_attention.tiles",
    grid=((128, 128), (128, 256), (256, 256), (256, 512)),
    bench="train", series="train_step",
    objective="steps_per_sec",
    overrides=lambda v: {"tunables": {
        "ops.flash_attention.block_q": int(v[0]),
        "ops.flash_attention.block_k": int(v[1])}},
    # the dense-attention CPU path never calls the flash kernel — a CPU
    # "measurement" of this axis would tune dead code
    validity=lambda v: ((True, "") if _backend() == "tpu"
                        else (False, "flash kernel only runs on tpu")),
))

register_axis(LiveAxis(
    name="zero.reduce_bucket_bytes",
    target="comm_quantization.bucket_bytes",
    grid=(4 * MiB, 16 * MiB, 64 * MiB),
    bench="train", series="train_step",
    objective="steps_per_sec",
    overrides=lambda v: {"ds_config": {
        "comm_quantization": {"enabled": True, "dtype": "none",
                              "bucket_bytes": int(v)},
        "zero_optimization": {"stage": 2}}},
    validity=_needs_multichip,
))

register_axis(LiveAxis(
    # "off" measures the UNTUNED default (GSPMD's own reduction) so the
    # choice to switch machinery at all is itself measured — consuming
    # the artifact enables the bucketed path only when a bucketed
    # candidate actually beat the default
    name="comm.tier",
    target="comm_quantization.tier",
    grid=("off", "none", "int8"),
    bench="train", series="train_step",
    objective="steps_per_sec",
    overrides=lambda v: {"ds_config": {
        "comm_quantization": ({"enabled": False} if v == "off"
                              else {"enabled": True, "dtype": str(v)}),
        "zero_optimization": {"stage": 2}}},
    validity=_needs_multichip,
))

register_axis(LiveAxis(
    # (data, fsdp, tp) factorizations of the device count — the mesh
    # shape the SpecLayout partitions over (data = -1 fills the
    # remainder). Measured against the REAL train_step series: whether
    # trading data-parallel width for fsdp memory headroom or tp
    # latency pays is workload- and interconnect-dependent, exactly
    # what a roofline cannot rank (GSPMD, arXiv:2105.04663). The triple
    # is one choice — its consumption (artifact._expand_section_target)
    # expands it into the three mesh axis knobs as a unit, and only
    # when the user pinned no mesh axis themselves. ROADMAP: "the PR 8
    # autotuner should gain a mesh-shape axis the day this lands".
    name="mesh.shape",
    target="mesh.shape",
    grid=((-1, 1, 1), (-1, 1, 2), (-1, 2, 1), (-1, 2, 2)),
    bench="train", series="train_step",
    objective="steps_per_sec",
    overrides=lambda v: {"ds_config": {"mesh": {
        "data": int(v[0]), "fsdp": int(v[1]), "tp": int(v[2])}}},
    validity=_mesh_shape_valid,
))

register_axis(LiveAxis(
    name="serving.prefill_chunk_tokens",
    target="serving.prefill_chunk_tokens",
    grid=(16, 32, 64),
    bench="decode", series="serving_chunk",
    objective="short_ttft_ms_p95", minimize=True,
    overrides=lambda v: {"serving": {"prefill_chunk_tokens": int(v)}},
))

register_axis(LiveAxis(
    # values are explicit bucket sets; () = the power-of-two default.
    # resolve_buckets clips to max_len and always appends it, so one set
    # is meaningful across model windows
    name="serving.prompt_buckets",
    target="serving.prompt_buckets",
    grid=((), (32, 128), (64,)),
    bench="decode", series="serving_chunk",
    objective="tokens_per_sec",
    overrides=lambda v: {"serving": {"prompt_buckets": [int(b)
                                                        for b in v]}},
))


register_axis(LiveAxis(
    # k, the verify program's draft-token count: larger k buys more
    # tokens per dispatch only while the proposer's acceptance holds up
    # — a workload-dependent cliff no roofline predicts, so it is
    # measured against the real *_spec_decode series. "off" measures
    # the plain decode program, so (comm.tier convention) the choice to
    # switch speculation on AT ALL is itself measured — consuming the
    # artifact enables it only when a k beat the baseline
    name="serving.num_speculative_tokens",
    target="serving.speculative.num_speculative_tokens",
    grid=("off", 2, 4, 8),
    bench="decode", series="spec_decode",
    objective="spec_tokens_per_sec",
    overrides=lambda v: {"serving": {"speculative": (
        {"enabled": False} if v == "off"
        else {"enabled": True, "num_speculative_tokens": int(v)})}},
))


def _backend() -> str:
    import jax

    return jax.default_backend()
