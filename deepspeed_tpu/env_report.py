"""Environment/compatibility report (reference ``deepspeed/env_report.py``,
surfaced as the ``ds_report`` CLI).

Instead of CUDA/torch/nvcc compatibility probes and per-op build status, the
TPU report covers: JAX/jaxlib/libtpu versions, platform + device inventory,
Pallas availability, host toolchain (for the C++ host ops), and the
framework's op registry status.
"""

import importlib
import shutil
import subprocess
import sys

GREEN = "\033[92m"
RED = "\033[91m"
YELLOW = "\033[93m"
END = "\033[0m"
OKAY = f"{GREEN}[OKAY]{END}"
WARN = f"{YELLOW}[WARNING]{END}"
FAIL = f"{RED}[FAIL]{END}"


def _version(mod_name):
    try:
        mod = importlib.import_module(mod_name)
        return getattr(mod, "__version__", "unknown")
    except ImportError:
        return None


def software_report():
    rows = []
    for mod in ("jax", "jaxlib", "flax", "optax", "numpy"):
        v = _version(mod)
        rows.append((mod, v or "not installed", OKAY if v else FAIL))
    rows.append(("python", sys.version.split()[0], OKAY))
    rows.append(("deepspeed_tpu", _version("deepspeed_tpu") or "source", OKAY))
    return rows


def hardware_report():
    """The device inventory, gathered in this process: the report is the one
    process that runs, so it may hold the chip while it prints."""
    rows = []
    try:
        import jax

        devices = jax.devices()
    except RuntimeError as e:  # no backend could start: that IS the report
        rows.append(("jax devices", f"backend unreachable: {str(e)[:120]}",
                     FAIL))
    else:
        platform = devices[0].platform
        rows.append(("platform", platform,
                     OKAY if platform == "tpu" else WARN))
        rows.append(("device count", str(len(devices)), OKAY))
        rows.append(("device kind", ", ".join(sorted(
            {d.device_kind for d in devices})), OKAY))
        rows.append(("process count", str(jax.process_count()), OKAY))
    try:
        from jax.experimental import pallas  # noqa: F401

        rows.append(("pallas", "importable", OKAY))
    except ImportError:
        rows.append(("pallas", "unavailable", WARN))
    return rows


def toolchain_report():
    """Host C++ toolchain for the native host-side ops (cpu offload tier)."""
    rows = []
    for tool in ("g++", "cmake", "ninja", "make"):
        path = shutil.which(tool)
        if path:
            try:
                out = subprocess.run([tool, "--version"], capture_output=True,
                                     text=True, timeout=10).stdout.splitlines()
                ver = out[0].strip() if out else "found"
            except Exception:
                ver = "found"
            rows.append((tool, ver[:60], OKAY))
        else:
            rows.append((tool, "not found", WARN))
    return rows


def op_report():
    rows = []
    try:
        from deepspeed_tpu.ops import op_registry

        for name, status in op_registry.report().items():
            rows.append((name, status["detail"],
                         OKAY if status["available"] else WARN))
    except ImportError:
        for name in ("flash_attention", "quantizer", "ring_attention"):
            try:
                importlib.import_module(f"deepspeed_tpu.ops.{name}")
                rows.append((name, "importable", OKAY))
            except Exception as e:
                rows.append((name, f"error: {e}", FAIL))
    return rows


def _print_table(title, rows):
    print("-" * 72)
    print(title)
    print("-" * 72)
    for name, detail, status in rows:
        print(f"{name:.<24} {status} {detail}")


def main():
    print("=" * 72)
    print("DeepSpeed-TPU environment report (ds_report equivalent)")
    print("=" * 72)
    _print_table("software", software_report())
    _print_table("hardware", hardware_report())
    _print_table("host toolchain", toolchain_report())
    _print_table("ops", op_report())
    return 0


def cli_main():
    sys.exit(main())


if __name__ == "__main__":
    main()
