"""Model FLOPs of the tokens a Mistral-block engine processed in the traced
window: for each token 2 x the parameters of the layers (and of the head,
where the token's logits are needed: every decode row), plus attention,
4 x context x heads x head size a layer.  The embedding is a lookup."""
from bench.rooflines.paged_attention import dispatch_needs, spans_in_trace


def layer_params(cfg):
    h, m, d = cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    return h * nq + 2 * h * nkv + nq * h + 3 * h * m


def head_params(cfg):
    return cfg["hidden_size"] * cfg["vocab_size"]


def span_flops(cfg, span):
    layers = cfg["num_hidden_layers"]
    matmul = 2 * layer_params(cfg) * layers * span["rows"]
    if span["kind"] == "decode":
        matmul += 2 * head_params(cfg) * span["rows"]
    return matmul + dispatch_needs(cfg, span)[1] * layers


def flops_and_seconds(facts):
    trace = facts.get("trace")
    spans = spans_in_trace(facts) if trace else []
    if not spans:
        return None
    return (sum(span_flops(facts["config"], s) for s in spans),
            trace["window_s"])
