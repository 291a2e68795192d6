"""Model FLOPs of the tokens a ``zaya`` engine processed in the traced
window: for each token row 2 x the parameters it touches in every layer -
CCA's projections (q, k, the two value halves, o), its two convolutions
over time (the depthwise taps and the grouped d x d taps), the router (the
2,048 -> 256 projection and its MLP to 17 outputs), and ONE expert for each
row whose router did not choose the skip (the window's share of routed rows
not skipped, by kind of dispatch, from the program's own counters
``serving_moe_skipped_total / serving_moe_rows_total``) - plus attention over
the row's context, its own token included (4 x tokens x q heads x head
size), and the tied head on every decode row.  The embedding is a lookup.

Where the program has no skip counter (a commit before it) nothing is
returned.
"""
from bench.readers.registry_ratio import total
from bench.rooflines.paged_attention import dispatch_needs, spans_in_trace


def layer_params(cfg):
    """(what every token row touches in a layer, one expert)."""
    h, d, f = cfg["hidden_size"], cfg["head_dim"], cfg["moe_intermediate_size"]
    nq, nkv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    r, e = cfg["router_hidden_size"], cfg["num_experts"]
    groups = cfg["num_attention_heads"] + cfg["num_key_value_heads"]
    cca = (2 * h * nq + 2 * h * nkv
           + cfg["cca_time0"] * (nq + nkv) + cfg["cca_time1"] * groups * d * d)
    router = h * r + 2 * r * r + r * (e + 1)
    return cca + router, 3 * h * f


def routed_a_row(registry, kind):
    """Share of a layer's routed rows that chose an expert, not the skip."""
    labels = {"kind": kind}
    rows = total(registry, [{"metric": "serving_moe_rows_total",
                             "labels": labels}])
    skipped = total(registry, [{"metric": "serving_moe_skipped_total",
                                "labels": labels}])
    if rows <= 0 or "serving_moe_skipped_total" not in registry:
        return None
    return (rows - skipped) / rows


def span_flops(cfg, span, routed):
    always, expert = layer_params(cfg)
    layers = cfg["num_hidden_layers"]
    matmul = 2 * layers * (always + routed * expert) * span["rows"]
    if span["kind"] == "decode":
        matmul += 2 * cfg["hidden_size"] * cfg["vocab_size"] * span["rows"]
    return matmul + dispatch_needs(cfg, span)[1] * layers


def flops_and_seconds(facts):
    trace = facts.get("trace")
    spans = spans_in_trace(facts) if trace else []
    registry = facts.get("registry") or {}
    routed = {k: routed_a_row(registry, k) for k in ("decode", "prefill")}
    spans = [s for s in spans if routed.get(s["kind"]) is not None]
    if not spans:
        return None
    return (sum(span_flops(facts["config"], s, routed[s["kind"]])
                for s in spans), trace["window_s"])
