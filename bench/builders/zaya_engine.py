"""Builds the serving system under test for a ``zaya`` configuration:
``ZAYAConfig -> ZAYAForCausalLM -> LLMEngine -> ReplicaSet ->
start_gateway``, the path a user of the front door takes.

The weights are the benchmark's (``reference.init_weights`` from ``--seed``).
No copy is made on the way: the model ADOPTS the reference's leaves as its
parameters and hands them over to the engine, which stacks them leaf by
leaf while the model lets go. The head is the embedding's transpose: there
is no leaf of its own.
"""
import gc
import time

# at import, so that a program without this model fails at once and not
# after the weights are drawn (importing it initializes nothing)
from paddle_tpu.models import zaya as program

from bench.builders.llama_engine import ServingSystem


def zaya_config(cfg):
    return program.ZAYAConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_experts=cfg["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        router_hidden_size=cfg["router_hidden_size"],
        cca_time0=cfg["cca_time0"], cca_time1=cfg["cca_time1"],
        partial_rotary_factor=cfg["partial_rotary_factor"],
        rope_theta=cfg["rope_parameters"]["hybrid"]["rope_theta"],
        rms_norm_eps=cfg["rms_norm_eps"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        attention_bias=cfg["attention_bias"],
        lm_head_bias=cfg["lm_head_bias"],
        sliding_window=cfg["sliding_window"],
        layer_types=cfg["layer_types"],
        initializer_range=cfg["initializer_range"],
        max_position_embeddings=cfg["max_position_embeddings"])


def program_leaves(weights):
    """The reference's leaves (the program's names are the same), consumed
    as they go: ``weights`` is emptied."""
    layers = []
    while weights["layers"]:
        layers.append(weights["layers"].pop(0))
    return {"embed": weights.pop("embed"), "norm": weights.pop("norm"),
            "layers": layers}


def build(cfg, weights, devices, params, say):
    """One engine on the one chip of the cell.  ``params``: the cell's
    engine overrides."""
    if len(devices) != 1:
        raise SystemExit(f"bench: builder zaya_engine builds one engine on "
                         f"one chip, the cell gives {len(devices)}")
    import paddle_tpu as paddle
    from paddle_tpu.inference.frontend import ReplicaSet, start_gateway
    from paddle_tpu.inference.serving import LLMEngine

    eng = dict(cfg["engine"])
    eng.update(params.get("engine", {}))
    t0 = time.perf_counter()
    paddle.set_default_dtype(cfg["torch_dtype"])
    try:
        model = program.ZAYAForCausalLM(zaya_config(cfg),
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
