"""Model FLOPs of the tokens a ``solar_open2`` engine processed in the
traced window: for each token 2 x the parameters it touches - the mixer of
each layer by its kind (GQA: q, k, v, gate, o; KDA: q, k, v, o, the two
low-rank gates, beta, the three 4-tap convolutions), the router, the shared
expert, and as many routed experts as were CHOSEN AND HELD here for it (the
window's mean assignments a row and layer, by kind of dispatch, from the
program's own counters ``serving_moe_assignments_total /
serving_moe_rows_total``; at most ``num_experts_per_tok``) - plus the head
where the token's logits are needed (every decode row), attention in the
GQA layers (4 x context x heads x head size) and the delta rule in the KDA
layers (2 x 3 x heads x dk x dv: the state is decayed, read by k, updated
and read by q).  The embedding is a lookup.

Where the program has no such counters (a commit before them) nothing is
returned.
"""
from bench.readers.registry_ratio import total
from bench.rooflines.paged_attention import dispatch_needs, spans_in_trace


def kinds(cfg):
    """(GQA layers, KDA layers) among the layers kept."""
    n = cfg["num_hidden_layers"]
    gqa = sum(1 for i in cfg["gqa_layers"] if i < n)
    return gqa, n - gqa


def gqa_params(cfg):
    h, d = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    return 3 * h * nq + 2 * h * nkv          # q, gate, o; k, v


def kda_params(cfg):
    h, lin = cfg["hidden_size"], cfg["linear_attn_config"]
    nl = lin["num_heads"] * lin["head_dim"]
    rank = cfg.get("kda_rank", lin["head_dim"])
    return (4 * h * nl + 2 * (h * rank + rank * nl) + h * lin["num_heads"]
            + 3 * nl * lin["short_conv_kernel_size"])


def expert_params(cfg):
    """(what every token touches in an expert layer, one routed expert)."""
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    width = cfg.get("router_width", cfg["n_routed_experts"])
    return h * width + 3 * h * f * cfg["n_shared_experts"], 3 * h * f


def delta_rule_flops(cfg):
    lin = cfg["linear_attn_config"]
    return 2 * 3 * lin["num_heads"] * lin["head_dim"] * lin["head_dim"]


def assignments_a_row(registry, kind):
    """Mean (chosen and held) experts of a routed row in one layer."""
    labels = {"kind": kind}
    rows = total(registry, [{"metric": "serving_moe_rows_total",
                             "labels": labels}])
    if rows <= 0:
        return None
    return total(registry, [{"metric": "serving_moe_assignments_total",
                             "labels": labels}]) / rows


def span_flops(cfg, span, held_a_row):
    gqa, kda = kinds(cfg)
    always, expert = expert_params(cfg)
    token = (gqa * gqa_params(cfg) + kda * kda_params(cfg)
             + (gqa + kda) * (always + held_a_row * expert))
    matmul = 2 * token * span["rows"]
    if span["kind"] == "decode":
        matmul += 2 * cfg["hidden_size"] * cfg["vocab_size"] * span["rows"]
    return (matmul + dispatch_needs(cfg, span)[1] * gqa
            + delta_rule_flops(cfg) * kda * span["rows"])


def flops_and_seconds(facts):
    trace = facts.get("trace")
    spans = spans_in_trace(facts) if trace else []
    registry = facts.get("registry") or {}
    held = {k: assignments_a_row(registry, k) for k in ("decode", "prefill")}
    spans = [s for s in spans if held.get(s["kind"]) is not None]
    if not spans:
        return None
    return (sum(span_flops(facts["config"], s, held[s["kind"]])
                for s in spans), trace["window_s"])
