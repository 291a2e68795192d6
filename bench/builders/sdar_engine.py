"""Builds the serving system under test for an ``sdar_moe`` configuration:
``SDARConfig -> SDARForCausalLM -> LLMEngine -> ReplicaSet ->
start_gateway``, the path a user of the front door takes.

The weights are the benchmark's (``reference.init_weights`` from ``--seed``).
The device cannot hold them twice (8.1 GiB of 15.75), so no copy is made on
the way: the model ADOPTS the reference's leaves as its parameters and
hands them over to the engine, which stacks them leaf by leaf while the
model lets go.

The configuration file keeps the published key ``num_experts`` (all 128 are
held on this chip); how the model generates is under ``generation``.
"""
import gc
import time

# at import, so that a program without this model fails at once and not
# after the weights are drawn (importing it initializes nothing)
from paddle_tpu.models import sdar as program

from bench.builders.llama_engine import ServingSystem


def sdar_config(cfg):
    gen = cfg["generation"]
    return program.SDARConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        router_width=cfg["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        norm_topk_prob=cfg["norm_topk_prob"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        block_length=gen["block_length"],
        denoising_steps=gen["denoising_steps"], remasking=gen["remasking"],
        confidence_threshold=gen["confidence_threshold"],
        mask_token_id=gen["mask_token_id"],
        attention_bias=cfg["attention_bias"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        sliding_window=cfg["sliding_window"],
        decoder_sparse_step=cfg["decoder_sparse_step"],
        mlp_only_layers=cfg["mlp_only_layers"],
        initializer_range=cfg["initializer_range"],
        max_position_embeddings=cfg["max_position_embeddings"])


def program_leaves(weights):
    """The reference's leaves (the program's names are the same), consumed
    as they go: ``weights`` is emptied."""
    layers = []
    while weights["layers"]:
        layers.append(weights["layers"].pop(0))
    return {"embed": weights.pop("embed"), "norm": weights.pop("norm"),
            "head": weights.pop("head"), "layers": layers}


def build(cfg, weights, devices, params, say):
    """One engine on the one chip of the cell.  ``params``: the cell's
    engine overrides."""
    if len(devices) != 1:
        raise SystemExit(f"bench: builder sdar_engine builds one engine on "
                         f"one chip, the cell gives {len(devices)}")
    import paddle_tpu as paddle
    from paddle_tpu.inference.frontend import ReplicaSet, start_gateway
    from paddle_tpu.inference.serving import LLMEngine

    eng = dict(cfg["engine"])
    eng.update(params.get("engine", {}))
    t0 = time.perf_counter()
    paddle.set_default_dtype(cfg["torch_dtype"])
    try:
        model = program.SDARForCausalLM(sdar_config(cfg),
                                        leaves=program_leaves(weights),
                                        hand_over=True)
    finally:
        paddle.set_default_dtype("float32")
    model.eval()
    say(f"model built around the seed's weights in "
        f"{time.perf_counter() - t0:.1f} s")
    t0 = time.perf_counter()
    engines = [LLMEngine(model, **eng)]
    del model
    gc.collect()
    say(f"engine built in {time.perf_counter() - t0:.1f} s")
    for e in engines:
        if params.get("require_kernel", True) and e.runner.use_kernel is not True:
            raise SystemExit("bench: engine.runner.use_kernel is not True: "
                             "the Pallas kernels are not on the path")
    rs = ReplicaSet(engines)
    gw = start_gateway(rs, port=0)
    return ServingSystem(engines, rs, gw)
