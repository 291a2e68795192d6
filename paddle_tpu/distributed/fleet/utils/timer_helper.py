"""Throughput / interval timers (reference: fleet/utils/timer_helper.py —
_Timer/_TimerGroup powering the hybrid-parallel trainers' ips logging).

TPU note: timings bracket host-side dispatch; for device-accurate intervals
call stop(sync=True), which materializes a scalar to drain the dispatch queue.
"""
from __future__ import annotations

import time

__all__ = ["get_timers", "set_timers", "Timer", "TimerGroup"]


class Timer:
    def __init__(self, name):
        self.name = name
        self._elapsed = 0.0
        self._count = 0
        self._started = False
        self._start_t = 0.0

    def start(self):
        if self._started:
            raise RuntimeError(f"timer {self.name!r} already started")
        self._started = True
        self._start_t = time.perf_counter()

    def stop(self, sync=False):
        if not self._started:
            raise RuntimeError(f"timer {self.name!r} not started")
        if sync:
            import jax
            import numpy as np
            # drain the device queue so the interval covers execution
            np.asarray(jax.device_put(0.0) + 0)
        self._elapsed += time.perf_counter() - self._start_t
        self._count += 1
        self._started = False

    def elapsed(self, reset=True):
        if self._started:   # fold the in-flight interval, keep running
            now = time.perf_counter()
            self._elapsed += now - self._start_t
            self._start_t = now               # reference _Timer restarts
        out = self._elapsed
        if reset:
            self._elapsed = 0.0
            self._count = 0
        return out

    def mean(self, reset=True):
        out = self._elapsed / max(self._count, 1)
        if reset:
            self._elapsed = 0.0
            self._count = 0
        return out


class TimerGroup:
    def __init__(self):
        self._timers = {}

    def __call__(self, name):
        if name not in self._timers:
            self._timers[name] = Timer(name)
        return self._timers[name]

    def log(self, names=None, normalizer=1.0, reset=True):
        names = names if names is not None else list(self._timers)
        parts = []
        for n in names:
            if n in self._timers:
                ms = 1000.0 * self._timers[n].elapsed(reset) / normalizer
                parts.append(f"{n}: {ms:.2f}ms")
        msg = "time (ms) | " + " | ".join(parts)
        print(msg)  # graftlint: disable=no-adhoc-telemetry (log() prints by contract)
        return msg

    def throughput(self, name, items, reset=True):
        """items/sec over the named timer's accumulated time (the reference's
        ips metric)."""
        t = self._timers[name].elapsed(reset)
        return items / t if t > 0 else float("inf")


_timers = None


def get_timers():
    global _timers
    if _timers is None:
        _timers = TimerGroup()
    return _timers


def set_timers(timers):
    global _timers
    _timers = timers
