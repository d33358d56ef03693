"""The device policy: which device this process runs on, whether it is the
one that was asked for, and what that device can do at its peak.

One in-process answer, no fallback. A program that measures or serves on
the accelerator calls :func:`require_device` once, before it builds
anything: it returns what JAX reports (platform, ``device_kind``, count)
or raises :class:`DeviceError`. The CPU is accepted only when it was asked
for by name (``JAX_PLATFORMS=cpu`` or ``jax.config.update("jax_platforms",
"cpu")``, which is how the tests run), never because nothing better was
found. A chip belongs to one process at a time, so this module starts no
child process: the caller's process is the one that holds the chip.
"""

import dataclasses
from typing import Dict


class DeviceError(RuntimeError):
    """The device JAX found is not the one that was asked for, or nothing
    is known about it."""


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    bf16_flops: float     # FLOP/s, dense bf16 on the MXU
    hbm_bandwidth: float  # bytes/s
    hbm_bytes: float      # device memory


# Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``
# (Google Cloud TPU documentation, the "TPU v4" / "TPU v5e" / "TPU v5p" /
# "TPU v6e" system-architecture pages). The package's one table: the
# autotuner's roofline and chip_smoke.py read it (the benchmark keeps its
# own, perfbench/peaks.json). A kind that is not here is an error, not a
# default.
PEAKS: Dict[str, DevicePeaks] = {
    "TPU v4": DevicePeaks(275e12, 1228e9, 32e9),
    "TPU v5 lite": DevicePeaks(197e12, 819e9, 16e9),
    "TPU v5": DevicePeaks(459e12, 2765e9, 95e9),
    "TPU v6 lite": DevicePeaks(918e12, 1640e9, 32e9),
}


def peaks(device_kind: str) -> DevicePeaks:
    """Peak rates of one chip of ``device_kind``."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise DeviceError(
            f"no published peaks for device_kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}. Add the kind to utils/device.py:PEAKS with "
            "its source; nothing is assumed for an unknown chip") from None


def cpu_requested() -> bool:
    """True when the CPU was asked for by name, through ``JAX_PLATFORMS``
    or ``jax_platforms`` (the config value starts out as the variable)."""
    import jax

    return (jax.config.jax_platforms or "").replace(" ", "") == "cpu"


def describe() -> Dict:
    """``{"platform", "kind", "count"}`` of the default backend, as JAX
    reports it: the object every result line names its device with."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}


def require_device(platform: str = "tpu") -> Dict:
    """:func:`describe` if the default backend is ``platform``, or is the
    CPU and the CPU was asked for by name; :class:`DeviceError` otherwise.
    A backend that fails to start raises its own error from here."""
    dev = describe()
    if dev["platform"] == platform:
        return dev
    if dev["platform"] == "cpu" and cpu_requested():
        return dev
    raise DeviceError(
        f"this program needs a {platform!r} device but JAX started on "
        f"{dev['platform']!r} ({dev['kind']}); it does not carry on on "
        "another device unasked. For a CPU smoke run set JAX_PLATFORMS=cpu")
