"""Model FLOPs of what an ``sdar_moe`` engine processed in the traced
window.  A decode dispatch is ONE BLOCK of Q positions a live sequence:
``denoising_steps`` forwards that run the head and one committing forward
that does not, every one over ``rows x Q`` token rows.  For each token row
of each forward 2 x the parameters it touches - q, k, v, o, the router, and
as many routed experts as were CHOSEN AND HELD here for it (the window's
mean assignments a row and layer, by kind of dispatch, from the program's
own counters ``serving_moe_assignments_total / serving_moe_rows_total``; at
most ``num_experts_per_tok``) - in every layer, plus the head on the
denoising forwards' rows, plus attention: a block's Q rows each score the
``ctx + Q`` tokens the sequence holds with the block (4 x tokens x heads x
head size a row).  A prefill chunk's rows see to the end of their own block
and run no head.  The embedding is a lookup.

The forwards of a dispatch are ``denoising_steps + 1`` of the configuration
(the cell serves ``remasking: sequential``, which never leaves early;
``tests/test_sdar.py`` holds that to the program's counters).  Where the
program has no routing counters (a commit before them) nothing is returned.
"""
from bench.rooflines.paged_attention import spans_in_trace
from bench.rooflines.solar_open2_flops import assignments_a_row


def forwards(cfg):
    """(denoising forwards, all forwards) of one block dispatch."""
    d = cfg["generation"]["denoising_steps"]
    return d, d + 1


def layer_params(cfg):
    """(what every token row touches in a layer, one routed expert)."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    return (2 * h * nq + 2 * h * nkv + h * cfg["num_experts"],
            3 * h * cfg["moe_intermediate_size"])


def attended(cfg, span):
    """(query row, token) pairs of ONE forward (or chunk) in one layer."""
    q = cfg["generation"]["block_length"]
    if span["kind"] == "decode":
        # the spy's ctx_sum is sum(lens + 1); a block's rows see lens + Q
        return q * (span["ctx_sum"] + span["rows"] * (q - 1))
    n, start = span["rows"], span["start"]
    return sum(min((p // q + 1) * q, start + n)
               for p in range(start, start + n))


def span_flops(cfg, span, held_a_row):
    always, expert = layer_params(cfg)
    layers = cfg["num_hidden_layers"]
    token = layers * (always + held_a_row * expert)
    attn = 4 * cfg["num_attention_heads"] * cfg["head_dim"] * layers
    if span["kind"] == "decode":
        denoise, every = forwards(cfg)
        rows = span["rows"] * cfg["generation"]["block_length"]
        return (every * (2 * token * rows + attn * attended(cfg, span))
                + denoise * 2 * cfg["hidden_size"] * cfg["vocab_size"] * rows)
    return 2 * token * span["rows"] + attn * attended(cfg, span)


def flops_and_seconds(facts):
    trace = facts.get("trace")
    spans = spans_in_trace(facts) if trace else []
    registry = facts.get("registry") or {}
    held = {k: assignments_a_row(registry, k) for k in ("decode", "prefill")}
    spans = [s for s in spans if held.get(s["kind"]) is not None]
    if not spans or "generation" not in facts["config"]:
        return None
    return (sum(span_flops(facts["config"], s, held[s["kind"]])
                for s in spans), trace["window_s"])
