"""GPT-2's model FLOPs a token in training: 6 x the parameters that a
matrix product reads (forward 2, backward 4; the tied embedding counted
once, as the output head; the position table is a lookup) plus causal
attention, 6 x n_layer x seq x n_embd a token.  ISSUE 24 wrote the
attention term as 12 x n_layer x seq x n_embd, which counts the masked half
as well; the algorithm does not need it, so it is left out here.
Recomputation is not counted.  Tokens and seconds are the whole window's."""


def params(cfg):
    # a padded vocabulary's extra rows are no model FLOPs
    v = cfg.get("published", {}).get("vocab_size", cfg["vocab_size"])
    h, p, l = cfg["n_embd"], cfg["n_positions"], cfg["n_layer"]
    layer = 12 * h * h + 13 * h            # qkv, proj, fc, proj, biases, 2 LN
    return v * h + p * h + l * layer + 2 * h


def flops_per_token(cfg, seq):
    matmul_params = params(cfg) - cfg["n_positions"] * cfg["n_embd"]
    attention = 6 * cfg["n_layer"] * seq * cfg["n_embd"]
    return 6 * matmul_params + attention


def flops_and_seconds(facts):
    train = facts.get("train")
    if not train or not train.get("tokens"):
        return None
    return (flops_per_token(facts["config"], train["seq"]) * train["tokens"],
            train["elapsed_s"])
