"""What the paged-attention kernel's calls of the traced window need, by
the algorithm and not by how the kernel walks its pages.

For each row: K and V of the row's VALID context (``ctx`` tokens x KV heads x
head size x 2 arrays), its query and its output.  A decode dispatch has one
row for each active slot; a prefill chunk's rows are consecutive positions
of ONE sequence, so its K and V are needed once, up to the chunk's last
position.  Operations: 4 x ctx x heads x head size a row (QK^T and PV).
Each dispatch calls the kernel once a layer.  Bytes bound it on a v5e.

The contexts come from the spans the benchmark puts around
``runner.run_prefill`` / ``run_decode`` (a --trace 1 run), those that began
inside the traced window.
"""


def row_costs(cfg, itemsize=2):
    d = cfg["head_dim"]
    kv = 2 * cfg["num_key_value_heads"] * d * itemsize     # K and V, a token
    qo = 2 * cfg["num_attention_heads"] * d * itemsize     # q and o, a row
    flops = 4 * cfg["num_attention_heads"] * d             # a row and token
    return kv, qo, flops


def dispatch_needs(cfg, span):
    """(bytes, flops) of ONE kernel call (one layer) of a dispatch."""
    kv, qo, fl = row_costs(cfg)
    if span["kind"] == "decode":
        return (span["ctx_sum"] * kv + span["rows"] * qo,
                span["ctx_sum"] * fl)
    n, start = span["rows"], span["start"]
    ctx_sum = n * start + n * (n + 1) // 2      # row i sees start + i + 1
    return (start + n) * kv + n * qo, ctx_sum * fl


def spans_in_trace(facts):
    lo, hi = facts["trace_window"]
    return [s for s in facts.get("spans", []) if lo <= s["t0"] <= hi]


def needed(facts, calls):
    cfg = facts["config"]
    spans = spans_in_trace(facts)
    if not spans:
        return None
    total_b = total_f = 0
    for s in spans:
        b, f = dispatch_needs(cfg, s)
        total_b, total_f = total_b + b, total_f + f
    layers = cfg["num_hidden_layers"]
    return {"bytes": total_b * layers, "flops": total_f * layers}
