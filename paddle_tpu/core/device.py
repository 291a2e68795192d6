"""Device / place management.

Paddle's Place hierarchy (phi/common/place.h) collapses here to jax.Device: TPU is
the first-class target, CPU is the test backend. `set_device`/`get_device` keep the
Paddle string surface ("tpu", "tpu:0", "cpu").
"""
from __future__ import annotations

import jax


class Place:
    """Lightweight place wrapper over a jax.Device (phi/common/place.h analog)."""

    __slots__ = ("device",)

    def __init__(self, device: jax.Device):
        self.device = device

    @property
    def platform(self) -> str:
        return self.device.platform

    def is_tpu_place(self) -> bool:
        return self.device.platform == "tpu"

    def is_cpu_place(self) -> bool:
        return self.device.platform == "cpu"

    def is_gpu_place(self) -> bool:
        return self.device.platform in ("gpu", "cuda")

    def __eq__(self, other):
        if isinstance(other, Place):
            return self.device == other.device
        return NotImplemented

    def __hash__(self):
        return hash(self.device)

    def __repr__(self):
        return f"Place({self.device.platform}:{self.device.id})"


_current_device = None


def _parse(device):
    if device is None:
        return None
    if isinstance(device, jax.Device):
        return device
    if isinstance(device, Place):
        return device.device
    if isinstance(device, str):
        name, _, idx = device.partition(":")
        if name in ("gpu", "xpu"):
            name = "tpu"    # ported Paddle scripts: the accelerator here is the TPU
        try:
            return jax.devices(name)[int(idx) if idx else 0]
        except (RuntimeError, IndexError) as e:
            # never a quiet CPU stand-in: a run that asked for the chip and
            # did not get it must not look like one that did
            raise RuntimeError(
                f"device {device!r} requested, but this process has no such "
                f"device (default backend: {jax.default_backend()})") from e
    raise ValueError(f"cannot parse device spec {device!r}")


def set_device(device) -> Place:
    """paddle.set_device analog (python/paddle/device/__init__.py)."""
    global _current_device
    _current_device = _parse(device)
    jax.config.update("jax_default_device", _current_device)
    return Place(_current_device)


def get_device():
    d = _current_device or jax.devices()[0]
    return f"{d.platform}:{d.id}"


def current_device() -> jax.Device:
    return _current_device or jax.devices()[0]


def current_place() -> Place:
    return Place(current_device())


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_cinn() -> bool:
    return False


def device_count() -> int:
    return len(jax.devices())
