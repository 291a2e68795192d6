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
"""
from __future__ import annotations

import threading
import time

from . import flight as _flight
from . import registry as _registry

SPAN_SECONDS = _registry.REGISTRY.histogram(
    "span_seconds", "wall time inside trace_span scopes", ("span",))

_annotation = None                  # jax.profiler.TraceAnnotation, on first use
_open = threading.local()           # .names: this thread's open spans, outermost first


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
        if _registry._ENABLED:
            if _annotation is None:
                from jax.profiler import TraceAnnotation as _annotation
            self._ann = _annotation(self.name, **self.attrs)
            self._ann.__enter__()
        try:
            _open.names.append(self.name)
        except AttributeError:
            _open.names = [self.name]
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.dur = dur = time.perf_counter() - self._t0
        names = _open.names
        names.pop()
        if self._ann is not None:
            self._ann.__exit__(*exc)
            SPAN_SECONDS.observe(dur, span=self.name)
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
