"""paddle.device surface (reference: python/paddle/device/__init__.py)."""
from __future__ import annotations

import jax

from ..core.device import (set_device, get_device, current_place, device_count,  # noqa: F401
                           Place, is_compiled_with_cuda, is_compiled_with_xpu,
                           is_compiled_with_cinn)


def get_available_device():
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def get_all_device_type():
    return sorted({d.platform for d in jax.devices()})


def get_available_custom_device():
    return []


def is_compiled_with_rocm():
    return False


def is_compiled_with_custom_device(name):
    return name == "tpu"


def synchronize(device=None):
    """Block until all queued device work completes (stream sync analog)."""
    (jax.device_put(0) + 0).block_until_ready()


class Stream:
    """Streams are implicit on TPU (XLA manages ordering); API-compat no-op."""

    def __init__(self, device=None, priority=2):
        self.device = device

    def synchronize(self):
        synchronize()

    def wait_stream(self, stream):
        pass

    def record_event(self, event=None):
        return event or Event()

    def wait_event(self, event):
        pass


class Event:
    def __init__(self, enable_timing=False, blocking=False, interprocess=False):
        pass

    def record(self, stream=None):
        pass

    def query(self):
        return True

    def synchronize(self):
        synchronize()


def current_stream(device=None):
    return Stream(device)


def set_stream(stream):
    return stream


def stream_guard(stream):
    import contextlib
    return contextlib.nullcontext()


class cuda:
    """paddle.device.cuda compat namespace (maps to the accelerator)."""

    Stream = Stream
    Event = Event

    @staticmethod
    def device_count():
        return device_count()

    @staticmethod
    def synchronize(device=None):
        synchronize()

    @staticmethod
    def current_stream(device=None):
        return Stream(device)

    @staticmethod
    def max_memory_allocated(device=None):
        stats = jax.devices()[0].memory_stats() or {}
        return stats.get("peak_bytes_in_use", 0)

    @staticmethod
    def memory_allocated(device=None):
        stats = jax.devices()[0].memory_stats() or {}
        return stats.get("bytes_in_use", 0)

    @staticmethod
    def memory_reserved(device=None):
        stats = jax.devices()[0].memory_stats() or {}
        return stats.get("bytes_reserved", stats.get("bytes_in_use", 0))

    @staticmethod
    def empty_cache():
        pass


def get_cudnn_version():
    """Compat: no cuDNN on the TPU build (reference returns None when absent)."""
    return None


def is_compiled_with_ipu():
    return False


def is_compiled_with_distribute():
    return True


def get_all_custom_device_type():
    """No out-of-tree device plugins: TPU is first-class here."""
    return []


class XPUPlace(Place):
    """Compat: Kunlun place; resolves to the default accelerator."""

    def __init__(self, device_id=0):
        import jax
        devs = jax.devices()
        super().__init__(devs[min(device_id, len(devs) - 1)])


class IPUPlace:
    def __init__(self, *a, **k):
        raise NotImplementedError("IPU support is not part of the TPU build")
