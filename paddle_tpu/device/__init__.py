"""paddle_tpu.device — device management namespace.

Reference: python/paddle/device/__init__.py (set_device:265, cuda/xpu
namespaces, streams/events). TPU-native: devices are PjRt devices; memory
stats come from PjRt allocator telemetry; stream/event synchronization
collapses into `block_until_ready` (XLA programs are ordered per device, so
explicit stream management is compiled away).
"""
from __future__ import annotations

import jax

from ..core.device import (  # noqa: F401
    CPUPlace, Place, TPUPlace, device_count, get_all_devices, get_device,
    get_place, is_compiled_with_tpu, set_device,
)

__all__ = ["set_device", "get_device", "get_all_devices", "device_count",
           "Place", "TPUPlace", "CPUPlace", "is_compiled_with_tpu",
           "synchronize", "memory_stats", "max_memory_allocated",
           "max_memory_reserved", "memory_allocated", "memory_reserved",
           "cuda", "Stream", "Event", "stream_guard", "current_stream"]


def _dev(device=None):
    if device is None:
        return jax.devices()[0]
    if isinstance(device, int):
        return jax.devices()[device]
    if isinstance(device, str):
        kind, _, idx = device.partition(":")
        want = "cpu" if kind == "cpu" else "tpu"
        devs = [d for d in jax.devices() if d.platform == want]
        if not devs and want == "cpu":
            devs = jax.devices("cpu")
        if not devs:
            raise RuntimeError(f"no {kind} device attached")
        i = int(idx) if idx else 0
        if i >= len(devs):
            raise RuntimeError(
                f"device index {i} out of range for {kind} "
                f"({len(devs)} attached)")
        return devs[i]
    return device


def synchronize(device=None):
    """Block until all queued work on the device finished (reference:
    paddle.device.synchronize). In jax: a tiny transfer forces a sync."""
    import jax.numpy as jnp
    jnp.zeros((), device=_dev(device)).block_until_ready()


def memory_stats(device=None):
    d = _dev(device)
    stats = d.memory_stats() if hasattr(d, "memory_stats") else None
    return stats or {}


def memory_allocated(device=None):
    return int(memory_stats(device).get("bytes_in_use", 0))


def max_memory_allocated(device=None):
    s = memory_stats(device)
    return int(s.get("peak_bytes_in_use", s.get("bytes_in_use", 0)))


def memory_reserved(device=None):
    s = memory_stats(device)
    return int(s.get("bytes_reserved", s.get("bytes_limit", 0)))


def max_memory_reserved(device=None):
    return memory_reserved(device)


class Stream:
    """Compatibility shim: XLA serializes per-device execution, so explicit
    streams are a no-op container (reference: device/cuda/streams.py).
    Device resolution is lazy — importing the module must not touch the
    backend."""

    def __init__(self, device=None, priority=2):
        self._device_arg = device

    @property
    def device(self):
        return _dev(self._device_arg)

    def synchronize(self):
        synchronize(self.device)

    def wait_stream(self, stream):
        pass

    def wait_event(self, event):
        pass

    def record_event(self, event=None):
        return event or Event()


class Event:
    def __init__(self, enable_timing=False, blocking=False, interprocess=False):
        self._recorded = False

    def record(self, stream=None):
        self._recorded = True

    def query(self):
        return True

    def synchronize(self):
        pass


_current_stream = None


def current_stream(device=None):
    global _current_stream
    if _current_stream is None:
        _current_stream = Stream()
    return _current_stream


import contextlib as _contextlib


@_contextlib.contextmanager
def stream_guard(stream):
    yield


class _CudaNamespace:
    """paddle.device.cuda compatibility surface, routed to the TPU backend
    (the reference exposes these under device/cuda/)."""

    Stream = Stream
    Event = Event

    @staticmethod
    def device_count():
        return device_count()

    @staticmethod
    def synchronize(device=None):
        return synchronize(device)

    @staticmethod
    def max_memory_allocated(device=None):
        return max_memory_allocated(device)

    @staticmethod
    def max_memory_reserved(device=None):
        return max_memory_reserved(device)

    @staticmethod
    def memory_allocated(device=None):
        return memory_allocated(device)

    @staticmethod
    def memory_reserved(device=None):
        return memory_reserved(device)

    @staticmethod
    def empty_cache():
        pass

    @staticmethod
    def get_device_properties(device=None):
        d = _dev(device)
        return {"name": d.device_kind, "platform": d.platform}


cuda = _CudaNamespace()
