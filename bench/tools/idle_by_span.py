"""What the host was doing while the device sat idle.  Not part of a run.

    python3 bench/tools/idle_by_span.py <file.xplane.pb> [--device /device:TPU:]

Takes the device plane's idle gaps as ``trace_reduce.reduce_plane`` finds
them (between one ``XLA Modules`` event's end and the next one's start) and
the step loop's ``TraceAnnotation`` events from the host plane (the spans of
``paddle_tpu.observability.trace_span``: the thread that ran ``engine.step``
or a ``replica.*`` span), which the profiler writes on the same clock.  Each
gap's seconds go to the innermost span open during them; what no span covers
is reported as such.  Prints one JSON object:

    {"gaps_s", "by_span": [[span, seconds], ...], "uncovered_s",
     "uncovered_share", "spans_read", "gaps_read"}

``breakdown.idle_gaps`` of a run names the two programs around a gap; this
names the host's work inside it (ROADMAP D7: moving this into the run's own
breakdown is a ``benchmark`` issue's).
"""
import argparse
import json
import os
import sys
from collections import defaultdict

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench.trace_reduce import reduce_plane  # noqa: E402

# a line of the host plane is a step loop's if it holds one of these
_LOOP_MARKS = ("engine.step", "replica.idle", "replica.lock", "replica.publish")


def device_gaps(plane):
    """``[(start_ns, end_ns), ...]``: the idle intervals whose sum
    ``reduce_plane`` reports under ``gaps``."""
    mods = [ln for ln in plane.lines if ln.name == "XLA Modules"]
    if not mods:
        return []
    iv = sorted((ev.start_ns, ev.start_ns + ev.duration_ns)
                for ev in mods[0].events)
    gaps = [(e0, s1) for (_, e0), (s1, _) in zip(iv, iv[1:]) if s1 > e0]
    want = sum(reduce_plane(plane)["gaps"].values())
    got = sum(e - s for s, e in gaps) * 1e-9
    if abs(got - want) > 1e-9 + 1e-6 * want:
        raise SystemExit(f"idle_by_span: {got} s of gaps here, {want} s in "
                         f"trace_reduce.reduce_plane: they no longer agree")
    return gaps


def loop_spans(plane):
    """``[(start_ns, end_ns, name), ...]`` of every span on the host lines
    that a step loop wrote, sorted by start."""
    spans = []
    for ln in plane.lines:
        events = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                  for ev in ln.events]
        if any(name in _LOOP_MARKS for _, _, name in events):
            spans.extend(events)
    return sorted(spans)


def attribute(gaps, spans):
    """Seconds of ``gaps`` by the innermost of ``spans`` open during them:
    ``({span: seconds}, uncovered_seconds)``.  A gap is cut at every span
    boundary inside it; a piece belongs to the span that started last among
    those that contain it."""
    by_span, uncovered = defaultdict(float), 0.0
    for g0, g1 in gaps:
        near = [(s, e, n) for s, e, n in spans if s < g1 and e > g0]
        cuts = sorted({g0, g1} | {t for s, e, _ in near for t in (s, e)
                                  if g0 < t < g1})
        for a, b in zip(cuts, cuts[1:]):
            open_ = [(s, n) for s, e, n in near if s <= a and e >= b]
            if open_:
                by_span[max(open_)[1]] += (b - a) * 1e-9
            else:
                uncovered += (b - a) * 1e-9
    return dict(by_span), uncovered


def idle_by_span(path, device_prefix="/device:TPU:", host_prefix="/host:CPU"):
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(path).planes)
    gaps = [g for p in planes if p.name.startswith(device_prefix)
            for g in device_gaps(p)]
    spans = [s for p in planes if p.name.startswith(host_prefix)
             for s in loop_spans(p)]
    by_span, uncovered = attribute(gaps, spans)
    total = sum(e - s for s, e in gaps) * 1e-9
    return {"gaps_s": total,
            "by_span": sorted(([k, v] for k, v in by_span.items()),
                              key=lambda kv: -kv[1]),
            "uncovered_s": uncovered,
            "uncovered_share": uncovered / total if total > 0 else 0.0,
            "spans_read": len(spans), "gaps_read": len(gaps)}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path")
    ap.add_argument("--device", default="/device:TPU:")
    args = ap.parse_args(argv)
    print(json.dumps(idle_by_span(args.path, args.device)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
