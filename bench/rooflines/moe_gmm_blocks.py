"""What the ``moe_gmm`` kernel's calls of the traced window need where a
decode dispatch is a BLOCK (``sdar_moe``): as ``rooflines/moe_gmm.py``
(three calls a layer; each touched expert's matrices once, the assignments'
rows in and out; the window's mean assignments and experts touched a call
from the program's counters, by kind of dispatch), but a decode span makes
``denoising_steps + 1`` forwards of every layer and a prefill span one, so
the kernel's calls are shared out between the spans by forwards, not by
dispatches.  ``denoising_steps + 1`` is the configuration's
(``tests/test_sdar.py`` holds the program's forward counters to it).

The counters' means are over the whole run, the kernel's time over the
traced 3 s: a span with fewer rows than the mean call of its kind (slots
standing empty for a while) has its assignments and its touched experts
scaled down by its rows, never up.  Touched experts grow more slowly than
rows, so this counts too few bytes for such a span rather than too many:
the share may read low there, never past what the kernel did.
"""
from bench.readers.registry_ratio import total
from bench.rooflines.moe_gmm import layer_needs, means
from bench.rooflines.paged_attention import spans_in_trace


def forwards_of(cfg, kind):
    return cfg["generation"]["denoising_steps"] + 1 if kind == "decode" else 1


def rows_a_call(registry, kind):
    """Mean routed rows of one layer's call of this kind, over the run."""
    labels = {"kind": kind}
    calls = total(registry, [{"metric": "serving_moe_calls_total",
                              "labels": labels}])
    if calls <= 0:
        return None
    return total(registry, [{"metric": "serving_moe_rows_total",
                             "labels": labels}]) / calls


def span_rows(cfg, span):
    """Rows one forward of this span routes."""
    per = cfg["generation"]["block_length"] if span["kind"] == "decode" else 1
    return span["rows"] * per


def needed(facts, calls):
    cfg = facts["config"]
    registry = facts.get("registry") or {}
    if "generation" not in cfg:
        return None
    by_kind = {k: (means(registry, k), rows_a_call(registry, k))
               for k in ("decode", "prefill")}
    spans = [s for s in spans_in_trace(facts)
             if by_kind.get(s["kind"], (None,))[0] is not None]
    n = sum(forwards_of(cfg, s["kind"]) for s in spans)
    if not n:
        return None
    out = {"bytes": 0.0, "flops": 0.0}
    for s in spans:
        (assignments, touched), rows = by_kind[s["kind"]]
        scale = min(1.0, span_rows(cfg, s) / rows) if rows else 1.0
        b, f = layer_needs(cfg, assignments * scale, touched * scale)
        layers = calls / 3.0 * forwards_of(cfg, s["kind"]) / n
        out["bytes"] += layers * b
        out["flops"] += layers * f
    return out
