"""What the ``moe_gmm`` kernel's calls of the traced window need, by the
algorithm.  One expert layer makes three calls (gate, up, down); for a
layer: 2 x 3 x hidden x width operations an assignment, and in bytes the
three matrices of each TOUCHED expert once (an expert nobody chose is not
read) plus the assignments' rows in and out (the row twice in, gate and up
float32 out, the product in, the result float32 out).

How many assignments a layer's call computes and how many experts it
touches are the window's means by kind of dispatch, from the program's own
counters (``serving_moe_assignments_total``, ``serving_moe_experts_touched_total``
over ``serving_moe_calls_total``).  An expert counts as touched only if it
is one of the experts held, so the mean cannot exceed their number (40 in
the cell) and the share cannot pass 100 % by the count.  ``calls`` (the
kernel's events in the trace) is three a layer a dispatch; the spans the
benchmark puts around the runner's calls, those that began in the traced
window, say which share of them were decode steps and which prefill
chunks.  Where the program has no such counters nothing is returned.
"""
from bench.readers.registry_ratio import total
from bench.rooflines.paged_attention import spans_in_trace


def layer_needs(cfg, assignments, touched, itemsize=2):
    """(bytes, flops) of the three calls of ONE expert layer."""
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    weights = touched * 3 * h * f * itemsize
    rows = assignments * (2 * h * itemsize + 2 * f * 4 + f * itemsize + h * 4)
    return weights + rows, assignments * 2 * 3 * h * f


def means(registry, kind):
    """(assignments, experts touched) of one layer's call, window means."""
    def of(name):
        return total(registry, [{"metric": f"serving_moe_{name}_total",
                                 "labels": {"kind": kind}}])
    calls = of("calls")
    if calls <= 0:
        return None
    return of("assignments") / calls, of("experts_touched") / calls


def needed(facts, calls):
    registry = facts.get("registry") or {}
    spans = spans_in_trace(facts)
    by_kind = {k: (sum(s["kind"] == k for s in spans), means(registry, k))
               for k in ("decode", "prefill")}
    n = sum(c for c, m in by_kind.values() if m is not None)
    if not n:
        return None
    out = {"bytes": 0.0, "flops": 0.0}
    for count, m in by_kind.values():
        if m is None or not count:
            continue
        b, f = layer_needs(facts["config"], *m)
        layers = calls / 3.0 * count / n        # this kind's layer calls
        out["bytes"] += layers * b
        out["flops"] += layers * f
    return out
