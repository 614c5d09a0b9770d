"""Device routing — TPU-native equivalent of ``paddle.device`` + ``phi::Place``.

Reference: ``python/paddle/device/__init__.py:265`` (``set_device``) routes ops to a
backend via DeviceContextPool; here a device string simply selects the jax default
device, and everything downstream is XLA/PjRt. ``Place`` mirrors
``paddle/phi/common/place.h`` as a lightweight value type.
"""
from __future__ import annotations

import jax


class Place:
    """Value type mirroring phi::Place (paddle/phi/common/place.h)."""

    def __init__(self, device_type: str, device_id: int = 0):
        self.device_type = device_type
        self.device_id = device_id

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def is_tpu_place(self):
        return self.device_type == "tpu"

    def is_cpu_place(self):
        return self.device_type == "cpu"


TPUPlace = lambda idx=0: Place("tpu", idx)  # noqa: E731
CPUPlace = lambda idx=0: Place("cpu", idx)  # noqa: E731

_current_place = None


def get_all_devices():
    return jax.devices()


def set_device(device: str) -> Place:
    """paddle.set_device('tpu') / 'tpu:0' / 'cpu'. Selects the jax default
    device. Raises when the named device is not attached: a request for
    the TPU never lands on another platform."""
    global _current_place
    kind, _, idx = device.partition(":")
    idx = int(idx) if idx else 0
    if kind in ("tpu", "xla"):
        matching = [d for d in jax.devices() if d.platform == "tpu"]
    elif kind == "cpu":
        matching = jax.devices("cpu")
    else:
        raise ValueError(
            f"paddle_tpu supports 'tpu' and 'cpu' devices, got {device!r}")
    if idx >= len(matching):
        raise RuntimeError(
            f"set_device({device!r}): {len(matching)} such device(s) "
            f"attached (jax sees "
            f"{sorted({d.platform for d in jax.devices()})})")
    jax.config.update("jax_default_device", matching[idx])
    _current_place = Place(kind, idx)
    return _current_place


def get_device() -> str:
    if _current_place is None:
        d = jax.devices()[0]
        return f"{d.platform}:{d.id}"
    return f"{_current_place.device_type}:{_current_place.device_id}"


def get_place() -> Place:
    if _current_place is None:
        d = jax.devices()[0]
        return Place(d.platform, d.id)
    return _current_place


def device_count() -> int:
    return len(jax.devices())


def is_compiled_with_tpu() -> bool:
    return True
