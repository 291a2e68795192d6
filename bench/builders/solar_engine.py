"""Builds the serving system under test for a ``solar_open2`` configuration:
``SolarOpen2Config -> SolarOpen2ForCausalLM -> LLMEngine -> ReplicaSet ->
start_gateway``, the path a user of the front door takes.

The weights are the benchmark's (``reference.init_weights`` from ``--seed``).
The device cannot hold them twice, so no copy is made on the way: the
model ADOPTS the reference's leaves as its parameters (the three KDA
projections and their three convolutions side by side, as the program
keeps them: one layer's at a time), and hands them over to the engine,
which stacks them kind by kind while the model lets go.

The configuration file counts the experts HELD under ``n_routed_experts``
and the router's published width under ``router_width``; the program's
config calls them ``experts_held`` and ``router_width``.
"""
import gc
import time

# at import, so that a program without this model fails at once and not
# after the weights are drawn (importing it initializes nothing)
from paddle_tpu.models import solar_open2 as program

from bench.builders.llama_engine import ServingSystem

_FUSED = {"wqkv": ("wq", "wk", "wv"), "conv": ("conv_q", "conv_k", "conv_v")}


def solar_config(cfg):
    lin = cfg["linear_attn_config"]
    return program.SolarOpen2Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], linear_num_heads=lin["num_heads"],
        linear_head_dim=lin["head_dim"],
        short_conv_kernel_size=lin["short_conv_kernel_size"],
        kda_rank=cfg.get("kda_rank", lin["head_dim"]),
        moe_intermediate_size=cfg["moe_intermediate_size"],
        router_width=cfg.get("router_width", cfg["n_routed_experts"]),
        experts_held=cfg["n_routed_experts"],
        expert_offset=cfg.get("expert_offset", 0),
        n_shared_experts=cfg["n_shared_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        rms_norm_eps=cfg["rms_norm_eps"], gqa_interval=cfg["gqa_interval"],
        # the published list, of which the layers kept hold the first few
        gqa_layers=[i for i in cfg["gqa_layers"]
                    if i < cfg["num_hidden_layers"]],
        use_rope=cfg["use_rope"],
        use_gqa_gate=cfg["use_gqa_gate"],
        kda_allow_neg_eigval=cfg["kda_allow_neg_eigval"],
        first_k_dense_replace=cfg["first_k_dense_replace"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        initializer_range=cfg["initializer_range"],
        max_position_embeddings=cfg["max_position_embeddings"])


def program_leaves(weights):
    """The reference's leaves under the program's names, consumed as they
    go: ``weights["layers"]`` is emptied, and of a fused leaf's parts only
    one layer's are alive beside it."""
    import jax.numpy as jnp
    layers = []
    while weights["layers"]:
        ref = weights["layers"].pop(0)
        if "conv_q" in ref:
            for fused, parts in _FUSED.items():
                ref[fused] = jnp.concatenate([ref.pop(p) for p in parts],
                                             axis=-1)
        layers.append(ref)
    return {"embed": weights.pop("embed"), "norm": weights.pop("norm"),
            "head": weights.pop("head"), "layers": layers}


def build(cfg, weights, devices, params, say):
    """One engine on the one chip of the cell.  ``params``: the cell's
    engine overrides."""
    if len(devices) != 1:
        raise SystemExit(f"bench: builder solar_engine builds one engine on "
                         f"one chip, the cell gives {len(devices)}")
    import paddle_tpu as paddle
    from paddle_tpu.inference.frontend import ReplicaSet, start_gateway
    from paddle_tpu.inference.serving import LLMEngine

    eng = dict(cfg["engine"])
    eng.update(params.get("engine", {}))
    t0 = time.perf_counter()
    paddle.set_default_dtype(cfg["torch_dtype"])
    try:
        model = program.SolarOpen2ForCausalLM(solar_config(cfg),
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
