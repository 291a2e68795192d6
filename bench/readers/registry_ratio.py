"""A ratio of sums of the program's own counters (``observability``
registry, the families ``GET /metrics`` shows), taken over the window of a
--trace 1 run.

params: ``num`` and ``den``: lists of terms ``{"metric", "labels"?,
"field"? ("value", or "count"/"sum" of a histogram), "sign"? (+1)}``; a term
sums every series whose labels contain ``labels`` (so every engine of a
cell).  ``den_times_engine``: an engine setting (``max_batch``) that the
denominator is multiplied by.  ``scale``: 100 for a share in %.
"""


def total(registry, terms):
    out = 0.0
    for t in terms:
        fam = registry.get(t["metric"])
        if fam is None:
            continue
        want = t.get("labels", {})
        for s in fam["series"]:
            if all(s["labels"].get(k) == v for k, v in want.items()):
                out += t.get("sign", 1) * float(s.get(t.get("field", "value"), 0))
    return out


def read(params, facts):
    registry = facts.get("registry") or {}
    num, den = total(registry, params["num"]), total(registry, params["den"])
    if den <= 0:
        return None
    if "den_times_engine" in params:
        den *= facts["engine"][params["den_times_engine"]]
    return params.get("scale", 1.0) * num / den
