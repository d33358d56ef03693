"""JAX/TPU implementation of the accelerator abstraction.

Reference analog: ``deepspeed/accelerator/cuda_accelerator.py`` (the
torch.cuda-backed implementation). Here every probe rides JAX public APIs:
device inventory from ``jax.local_devices()``, memory from PJRT
``device.memory_stats()``, profiler ranges from
``jax.profiler.TraceAnnotation`` (xprof), synchronization via a devicized
fence.

On a backend whose PJRT client reports no memory stats (the CPU, where
``memory_stats()`` returns ``None``), byte counts come from live-array
accounting: the sum
of ``nbytes`` of this process's live ``jax.Array`` shards on the device,
with a process-local high-water mark standing in for the allocator's peak
counter. That undercounts XLA scratch/temp buffers but tracks the
steady-state working set, which is what ZeRO memory verification needs.
"""

import threading

from .abstract_accelerator import Accelerator


class TpuAccelerator(Accelerator):
    _name = "tpu"

    def __init__(self):
        self._seed = 0
        self._lock = threading.Lock()
        self._live_peak = {}  # device -> high-water mark (fallback path)
        self._range_stack = []

    # --- identity -----------------------------------------------------
    def device_name(self, device_index=None) -> str:
        import jax

        platform = jax.local_devices()[0].platform
        if device_index is None:
            return platform
        return f"{platform}:{device_index}"

    def device(self, device_index=None):
        import jax

        return jax.local_devices()[device_index or 0]

    def current_device(self) -> int:
        return 0

    def device_count(self) -> int:
        import jax

        return jax.local_device_count()

    def is_available(self) -> bool:
        try:
            import jax

            return len(jax.local_devices()) > 0
        except Exception:
            return False

    # --- execution ----------------------------------------------------
    def synchronize(self, device_index=None) -> None:
        """Fence the async dispatch queue: a device runs its programs in
        the order they were enqueued, so a trivial one that has finished
        means everything dispatched before it has."""
        import jax
        import numpy as np

        d = self.device(device_index)
        (jax.device_put(np.zeros((), np.int32), d) + 1).block_until_ready()

    # --- RNG ----------------------------------------------------------
    def manual_seed(self, seed: int) -> None:
        self._seed = int(seed)

    def initial_seed(self) -> int:
        return self._seed

    # --- memory introspection ----------------------------------------
    def memory_stats(self, device_index=None) -> dict:
        import jax

        d = self.device(device_index)
        stats = d.memory_stats()
        if stats:
            return {
                "bytes_in_use": int(stats.get("bytes_in_use", 0)),
                "peak_bytes_in_use": int(stats.get("peak_bytes_in_use", 0)),
                "bytes_limit": int(stats.get("bytes_limit", 0)),
                "largest_alloc_size": int(stats.get("largest_alloc_size", 0)),
                "source": "pjrt",
            }
        # No allocator stats (CPU): live jax.Array shards on this device.
        in_use = 0
        for a in jax.live_arrays():
            for shard in getattr(a, "addressable_shards", []):
                if shard.device == d:
                    in_use += int(shard.data.nbytes)
        with self._lock:
            peak = max(self._live_peak.get(d, 0), in_use)
            self._live_peak[d] = peak
        return {"bytes_in_use": in_use, "peak_bytes_in_use": peak,
                "bytes_limit": 0, "largest_alloc_size": 0,
                "source": "live_arrays"}

    def reset_peak_memory_stats(self, device_index=None) -> None:
        d = self.device(device_index)
        with self._lock:
            self._live_peak[d] = 0
        # PJRT exposes no peak reset; callers diff successive readings.

    # --- precision probes ---------------------------------------------
    def is_bf16_supported(self) -> bool:
        return True  # native on every TPU generation; emulated on CPU

    def is_fp16_supported(self) -> bool:
        return True  # fp16 compute works; bf16 is preferred on the MXU

    # --- profiler ranges ----------------------------------------------
    def range_push(self, msg: str) -> None:
        import jax

        ann = jax.profiler.TraceAnnotation(msg)
        ann.__enter__()
        self._range_stack.append(ann)

    def range_pop(self) -> None:
        if self._range_stack:
            self._range_stack.pop().__exit__(None, None, None)
