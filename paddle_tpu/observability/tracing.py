"""``trace_span``: the one call with which library code times a scope.

Two switches decide where a span goes; each is one flag check:

- metrics on (``observability.enable()``): the span opens a
  ``jax.profiler.TraceAnnotation(name, **attrs)`` — whenever the profiler is
  recording it lies in the xplane file, on the host plane, on the same clock
  as the device's operations, with its attributes as the event's stats — and
  observes ``span_seconds{span=name}`` at exit;
- flight recorder on (``flight.enable()``) and a trace id given or ambient:
  the same span is recorded through ``flight.record`` with ``dur``, the
  attributes and the enclosing span's name as ``parent``.  ``rid`` and
  ``trace_id`` may be parallel lists (a batched dispatch serves several
  requests): one event for each traced request.

Both off, ``trace_span`` returns one shared no-op object: no clock is read
and nothing is allocated.  ``timed=True`` is for the few callers whose own
control loop needs the duration whatever the switches say (the decode-block
auto-fit): the span then reads the clock and nothing else.  A span keeps
``.dur`` (seconds; ``None`` on the no-op) after exit, so no call site runs a
``perf_counter`` of its own beside it.

**The device watch** (metrics on).  A thread that launches programs may
register a probe, ``watch_device(probe, engine=label, waits=names)``:
``probe()`` is true while a program the thread launched is still queued or
running on the device (the engine's step loop registers its runner's).  On
such a thread every span enter and exit is a boundary, and so is
``tick()``: the probe is read once, and the wall time since the thread's
previous boundary is added to ``loop_seconds_total{engine, span, device}``
— ``span`` the innermost span open through the interval (``"none"``
outside every span: the innermost-span rule of
``bench/tools/idle_by_span.py``, applied live), ``device`` ``dry`` where
the probe read idle at the interval's start, ``busy`` where it read busy at
both ends.  Where it went from busy to idle the device finished inside the
interval: under a span named in ``waits`` (the thread blocked there until
its device finished) the interval is busy; elsewhere half of it is each.
A long host span that does its work in pieces calls ``tick()`` between
them, so that the device's finishing falls within a piece and not within
the whole span (the runner's argument fan does, an argument at a time).
Dry time is time the device waited on this thread (or, under
``replica.idle``, on nobody sending work).  ``is_ready`` turns true a
little after the device finishes (some 0.4 ms on a TPU v5e), so where the
thread waits through every finish dry time reads low by that much each
time.  What it costs: with metrics off
no probe is called, ``trace_span`` is the same shared no-op and ``tick()``
one flag check; with metrics on, one probe call (``jax.Array.is_ready``,
which never blocks) and one counter increment a boundary of a watched
thread.  An interval that began before the switch last went on is not
counted.
"""
from __future__ import annotations

import threading
import time

from . import flight as _flight
from . import registry as _registry

SPAN_SECONDS = _registry.REGISTRY.histogram(
    "span_seconds", "wall time inside trace_span scopes", ("span",))

_annotation = None                  # jax.profiler.TraceAnnotation, on first use


class _Thread(threading.local):
    """One thread's open spans (outermost first) and its device watch."""

    watch = None

    def __init__(self):
        self.names = []


_open = _Thread()


class _Watch:
    """A thread's probe, and what it read at the thread's last boundary."""

    __slots__ = ("probe", "engine", "waits", "t", "busy", "turned_on",
                 "_series")

    def __init__(self, probe, engine, waits):
        self.probe, self.engine, self.waits = probe, engine, waits
        self.t = self.busy = self.turned_on = None
        self._series = {}

    def boundary(self, now, names):
        """Close the interval since the last boundary at ``now``, under the
        innermost of ``names`` (the spans open through it)."""
        busy = bool(self.probe())
        if self.t is not None and self.turned_on == _registry._TURNED_ON:
            dt = now - self.t
            span = names[-1] if names else "none"
            if not self.busy:
                self._add(span, "dry", dt)
            elif busy or span in self.waits:
                self._add(span, "busy", dt)
            else:
                self._add(span, "busy", dt / 2)
                self._add(span, "dry", dt / 2)
        self.t, self.busy, self.turned_on = now, busy, _registry._TURNED_ON

    def _add(self, span, device, dt):
        child = self._series.get((span, device))
        if child is None:
            from . import LOOP_SECONDS
            child = self._series[span, device] = LOOP_SECONDS.labels(
                engine=self.engine, span=span, device=device)
        child.inc(dt)


def watch_device(probe, engine="", waits=()):
    """Watch the calling thread's device through ``probe`` (a callable, true
    while a program this thread launched is queued or running) under the
    label ``engine``; ``waits``: the spans in which the thread blocks until
    its device finishes.  See the module docstring.  A thread watches one
    probe: another replaces it, the same one again changes nothing;
    ``None`` stops the watch."""
    w, engine, waits = _open.watch, str(engine), frozenset(waits)
    if probe is None:
        _open.watch = None
    elif (w is None or w.probe != probe or w.engine != engine
          or w.waits != waits):
        _open.watch = _Watch(probe, engine, waits)


def tick():
    """A boundary of the calling thread's device watch that opens and
    closes no span (see the module docstring); nothing without a watch or
    with metrics off."""
    if _registry._ENABLED:
        th = _open
        if th.watch is not None:
            th.watch.boundary(time.perf_counter(), th.names)


class _NoSpan:
    """What ``trace_span`` returns while nothing listens."""

    __slots__ = ()
    dur = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        pass


_NOOP = _NoSpan()


class _Span:
    __slots__ = ("name", "rid", "trace_id", "attrs", "dur", "_ann", "_t0")

    def __init__(self, name, rid, trace_id, attrs):
        self.name = name
        self.rid = rid
        self.trace_id = trace_id
        self.attrs = attrs
        self.dur = None
        self._ann = None

    def set(self, **attrs):
        """Attributes known only inside the scope (a step's ``kind``)."""
        self.attrs.update(attrs)
        if self._ann is not None:
            self._ann.set_metadata(**attrs)

    def __enter__(self):
        global _annotation
        th = _open
        if _registry._ENABLED:
            if _annotation is None:
                from jax.profiler import TraceAnnotation as _annotation
            self._ann = _annotation(self.name, **self.attrs)
            self._ann.__enter__()
        self._t0 = now = time.perf_counter()
        if self._ann is not None and th.watch is not None:
            th.watch.boundary(now, th.names)
        th.names.append(self.name)
        return self

    def __exit__(self, *exc):
        th = _open
        now = time.perf_counter()
        self.dur = dur = now - self._t0
        names = th.names
        if self._ann is not None:
            if th.watch is not None:
                th.watch.boundary(now, names)
            self._ann.__exit__(*exc)
            SPAN_SECONDS.observe(dur, span=self.name)
        names.pop()
        tid = self.trace_id
        if tid is not None:
            parent = names[-1] if names else None
            if isinstance(tid, list):
                for rid, t in zip(self.rid, tid):
                    if t is not None:
                        _flight.record(self.name, rid=rid, trace_id=t, dur=dur,
                                       parent=parent, **self.attrs)
            else:
                _flight.record(self.name, rid=self.rid, trace_id=tid, dur=dur,
                               parent=parent, **self.attrs)
        return False


def trace_span(name, rid=None, trace_id=None, timed=False, **attrs):
    """Time a scope into the registry and the profiler's trace (metrics
    on) and into the flight recorder (recorder on, request traced); see the
    module docstring.  ``with trace_span(...) as sp`` gives ``sp.dur``."""
    if _flight._ENABLED:
        if trace_id is None:
            ctx = _flight._current.get()
            if ctx is not None:
                trace_id = ctx.trace_id
        elif isinstance(trace_id, list) and not any(trace_id):
            trace_id = None
    else:
        trace_id = None
    if trace_id is None and not timed and not _registry._ENABLED:
        return _NOOP
    return _Span(name, rid, trace_id, attrs)
