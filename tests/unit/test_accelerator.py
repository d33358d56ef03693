"""Accelerator abstraction + memory introspection tests.

Reference capability: ``deepspeed/accelerator/abstract_accelerator.py:5``
(device seam), ``real_accelerator.py:15,33`` (get/set singleton),
``runtime/utils.py:821`` (``see_memory_usage``).
"""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.accelerator import (Accelerator, TpuAccelerator,
                                       get_accelerator, set_accelerator)
from deepspeed_tpu.utils.memory import memory_stats, see_memory_usage


def test_singleton_and_set():
    acc = get_accelerator()
    assert isinstance(acc, TpuAccelerator)
    assert get_accelerator() is acc

    class _Fake(TpuAccelerator):
        _name = "fake"

    fake = _Fake()
    set_accelerator(fake)
    try:
        assert get_accelerator() is fake
    finally:
        set_accelerator(acc)

    with pytest.raises(AssertionError):
        set_accelerator(object())  # type: ignore[arg-type]


def test_device_identity():
    acc = get_accelerator()
    assert acc.is_available()
    assert acc.device_count() >= 8  # virtual CPU mesh from conftest
    assert acc.device_name() == jax.devices()[0].platform
    assert acc.device_name(3).endswith(":3")
    assert acc.device(2) is jax.local_devices()[2]
    assert acc.current_device_name() == acc.device_name(0)


def test_synchronize_runs():
    get_accelerator().synchronize()


def test_seed_roundtrip():
    acc = get_accelerator()
    acc.manual_seed(1234)
    assert acc.initial_seed() == 1234


def test_memory_stats_tracks_live_arrays():
    import gc

    acc = get_accelerator()
    d = acc.device(0)
    # live-array accounting counts garbage that is not collected yet: were
    # an earlier test's arrays freed between the two readings, the growth
    # below would be understated
    gc.collect()
    acc.reset_peak_memory_stats(0)
    base = acc.memory_allocated(0)
    big = jax.device_put(np.ones((512, 512), np.float32), d)
    big.block_until_ready()
    grown = acc.memory_allocated(0)
    assert grown >= base + big.nbytes
    assert acc.max_memory_allocated(0) >= grown
    # memory_reserved aliases allocated on XLA (no allocator cache tier)
    assert acc.memory_reserved(0) == acc.memory_allocated(0)
    del big


def test_reset_peak_brackets_phases():
    acc = get_accelerator()
    d = acc.device(0)
    x = jax.device_put(np.ones((256, 256), np.float32), d)
    x.block_until_ready()
    acc.memory_stats(0)  # record a peak including x
    del x
    import gc

    gc.collect()
    acc.reset_peak_memory_stats(0)
    after = acc.max_memory_allocated(0)
    # after reset, the peak re-bases to the current working set
    assert after <= acc.memory_allocated(0) + 1


def test_precision_probes_and_ranges():
    acc = get_accelerator()
    assert acc.is_bf16_supported()
    assert acc.is_fp16_supported()
    acc.range_push("unit-test-range")
    (jnp.ones((8, 8)) @ jnp.ones((8, 8))).block_until_ready()
    acc.range_pop()
    acc.range_pop()  # over-pop is harmless
    assert acc.communication_backend_name() == "xla"

    called = []
    acc.lazy_call(lambda: called.append(1))
    assert called == [1]
    assert acc.pin_memory("x") == "x"


def test_memory_stats_snapshot_shape():
    s = memory_stats()
    assert set(s) == {"device", "host_rss_bytes", "host_used_bytes",
                      "host_percent"}
    assert s["host_rss_bytes"] > 0
    dev = s["device"]
    assert {"bytes_in_use", "peak_bytes_in_use", "bytes_limit"} <= set(dev)


class _Capture(logging.Handler):
    def __init__(self):
        super().__init__()
        self.messages = []

    def emit(self, record):
        self.messages.append(record.getMessage())


def _capture_framework_log():
    """The framework logger sets propagate=False, so pytest's caplog never
    sees it; attach a handler directly."""
    from deepspeed_tpu.utils.logging import logger as ds_logger

    h = _Capture()
    ds_logger.addHandler(h)
    return ds_logger, h


def test_see_memory_usage_logs():
    ds_logger, h = _capture_framework_log()
    try:
        see_memory_usage("not-forced")  # gated: no work, no log
        see_memory_usage("phase-marker", force=True)
    finally:
        ds_logger.removeHandler(h)
    assert not any("not-forced" in m for m in h.messages)
    assert any("phase-marker" in m and "host RSS" in m for m in h.messages)


def test_engine_memory_breakdown():
    """memory_breakdown config → per-print-step memory lines + accessor."""
    import deepspeed_tpu
    from tests.unit.simple_model import simple_loss_fn, simple_params

    engine, *_ = deepspeed_tpu.initialize(
        model=simple_loss_fn, model_parameters=simple_params(),
        config={"train_batch_size": 8,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "memory_breakdown": True,
                "steps_per_print": 1})
    x = np.ones((8, 8), np.float32)
    y = np.zeros((8, 8), np.float32)
    ds_logger, h = _capture_framework_log()
    try:
        loss = engine((x, y))
        engine.backward(loss)
        engine.step()
    finally:
        ds_logger.removeHandler(h)
    assert any("device MA" in m for m in h.messages)
    s = engine.memory_stats()
    assert s["device"]["bytes_in_use"] >= 0
