"""no-adhoc-telemetry — keep runtime telemetry on the sanctioned channels.

This PR's observability layer gives library code three blessed outlets:
``logging`` (diagnostics), the metrics registry (counters/gauges/histograms)
and ``trace_span`` (timeline).  Ad-hoc instrumentation rots past them:

  * ``print(...)`` in library code is invisible to any collector, cannot be
    filtered by level, and interleaves with user stdout.  (AT101)
  * ``time.time()`` is *wall clock* — NTP steps and DST make it jump, so
    intervals measured with it are occasionally negative or wildly wrong.
    Durations belong to ``time.perf_counter()``; deadlines shared within a
    process to ``time.monotonic()``.  Wall-clock reads that genuinely need
    calendar time (timestamps persisted across processes) carry a line
    pragma stating so.  (AT102)
  * An RPC ``client.call(...)`` that omits the ``ctx`` keyword silently
    DROPS the request's trace context at the process boundary — the remote
    span events land in a fresh (orphaned) timeline and the fleet-merged
    chrome trace shows a hole exactly where the bug is.  Every call on a
    client-like receiver (``client`` / ``*_client`` / ``rpc``) must pass
    ``ctx=`` — ``wire_context()`` for request-scoped traffic, an explicit
    ``ctx=None`` for control-plane ops that genuinely have no trace.
    (AT103)
  * In ``inference/``, a ``t0 = time.perf_counter()`` whose every use is a
    ``time.perf_counter() - t0`` handed to ``flight.record(dur=...)`` or to
    an ``observe()`` is a second clock beside the one span call:
    ``with obs.trace_span(name, ...) as sp`` times the scope into the
    registry, the profiler's trace and the flight recorder at once, and
    ``sp.dur`` is there for whoever else needs it.  A duration that feeds
    the program's own control or an always-on counter as well is no such
    pair and is left alone.  (AT104)

Pure CLI front-ends (whose job *is* printing) opt out with
``# graftlint: disable-file=no-adhoc-telemetry``.
"""
from __future__ import annotations

import ast

from ..framework import AnalysisPass, Finding, register_pass

_HINTS = {
    "AT101": "use logging (module logger) for diagnostics, or the "
             "observability registry for counters; pragma user-facing "
             "console output",
    "AT102": "time.perf_counter() for durations, time.monotonic() for "
             "deadlines; pragma genuine wall-clock (calendar) reads",
    "AT103": "pass ctx=wire_context() to thread the ambient trace through "
             "the frame, or an explicit ctx=None for untraced "
             "control-plane ops",
    "AT104": "time the scope with `with obs.trace_span(name, ...) as sp` and "
             "read sp.dur; pragma a pair that must stay, with the reason",
}

# receivers treated as RPC clients: `client.call(...)`, `self.client.call`,
# `foo_client.call`, `rpc.call`.  Purely lexical — graftlint is AST-only —
# so a non-RPC object that happens to be named `client` needs a line pragma.
def _is_client_receiver(expr):
    if isinstance(expr, ast.Attribute):
        name = expr.attr
    elif isinstance(expr, ast.Name):
        name = expr.id
    else:
        return False
    name = name.lower().lstrip("_")
    return name == "rpc" or name == "client" or name.endswith("_client")


def _is_perf_counter(node):
    return (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)
            and node.func.attr == "perf_counter"
            and isinstance(node.func.value, ast.Name)
            and node.func.value.id == "time")


def _only_feeds_telemetry(name, fn, parents, seen=()):
    """Whether every read of local ``name`` in ``fn`` ends in a ``dur=`` of
    a ``.record(...)`` call or in an argument of an ``.observe(...)`` call,
    directly or through further locals it is assigned to."""
    loads = [n for n in ast.walk(fn) if isinstance(n, ast.Name)
             and n.id == name and isinstance(n.ctx, ast.Load)]
    if not loads:
        return False
    for load in loads:
        node = load
        while True:
            parent = parents[node]
            if isinstance(parent, ast.keyword):
                call = parents[parent]
                if not (parent.arg == "dur" and isinstance(
                        call.func, ast.Attribute) and call.func.attr == "record"):
                    return False
                break
            if isinstance(parent, ast.Call):
                if not (node in parent.args and isinstance(
                        parent.func, ast.Attribute)
                        and parent.func.attr == "observe"):
                    return False
                break
            if isinstance(parent, ast.Assign):
                target = parent.targets[0]
                if not (len(parent.targets) == 1 and isinstance(target, ast.Name)
                        and target.id != name and target.id not in seen
                        and _only_feeds_telemetry(target.id, fn, parents,
                                                  seen + (name,))):
                    return False
                break
            if not isinstance(parent, (ast.BinOp, ast.UnaryOp)):
                return False
            node = parent
    return True


def _adhoc_span_pairs(tree):
    """Line numbers of ``t0 = time.perf_counter()`` assignments that AT104
    describes."""
    parents = {child: node for node in ast.walk(tree)
               for child in ast.iter_child_nodes(node)}
    lines = []
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for node in ast.walk(fn):
            if (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and _is_perf_counter(node.value)
                    and _only_feeds_telemetry(node.targets[0].id, fn, parents)):
                lines.append(node.lineno)
    return sorted(set(lines))


@register_pass
class NoAdhocTelemetryPass(AnalysisPass):
    name = "no-adhoc-telemetry"
    version = 3
    codes = ("AT101", "AT102", "AT103", "AT104")
    description = ("bare print(), wall-clock time.time() timing, RPC "
                   "client.call() sites that drop the trace-context field, "
                   "and perf_counter pairs in inference/ that only feed "
                   "telemetry beside the span call")

    def check_file(self, src) -> list[Finding]:
        findings: list[Finding] = []
        if "/inference/" in str(src.path).replace("\\", "/"):
            for lineno in _adhoc_span_pairs(src.tree):
                findings.append(Finding(
                    self.name, "AT104", src.path, lineno,
                    "a perf_counter() pair that only feeds flight.record(dur=) "
                    "or observe() — a second clock beside trace_span",
                    _HINTS["AT104"]))
        # `from time import time [as t]` makes bare-name calls wall-clock too
        time_aliases = set()
        for node in ast.walk(src.tree):
            if isinstance(node, ast.ImportFrom) and node.module == "time":
                for a in node.names:
                    if a.name == "time":
                        time_aliases.add(a.asname or a.name)
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if isinstance(f, ast.Name) and f.id == "print":
                findings.append(Finding(
                    self.name, "AT101", src.path, node.lineno,
                    "bare print() in library code — uncollectable, "
                    "unfilterable telemetry", _HINTS["AT101"]))
            elif (isinstance(f, ast.Attribute) and f.attr == "time"
                  and isinstance(f.value, ast.Name) and f.value.id == "time"):
                findings.append(Finding(
                    self.name, "AT102", src.path, node.lineno,
                    "time.time() is wall clock — intervals jump on NTP "
                    "steps", _HINTS["AT102"]))
            elif isinstance(f, ast.Name) and f.id in time_aliases:
                findings.append(Finding(
                    self.name, "AT102", src.path, node.lineno,
                    f"{f.id}() (time.time) is wall clock — intervals jump "
                    "on NTP steps", _HINTS["AT102"]))
            elif (isinstance(f, ast.Attribute) and f.attr == "call"
                  and _is_client_receiver(f.value)
                  and not any(k.arg == "ctx" for k in node.keywords)):
                findings.append(Finding(
                    self.name, "AT103", src.path, node.lineno,
                    "RpcClient.call without ctx= drops the request's trace "
                    "context at the process boundary — remote spans orphan "
                    "into a fresh timeline", _HINTS["AT103"]))
        return findings
