"""Builds the serving system under test from a configuration file:
``LlamaConfig -> LlamaForCausalLM -> LLMEngine -> ReplicaSet ->
start_gateway``, the path a user of the front door takes.

The weights are the benchmark's (``reference.init_weights`` from ``--seed``)
and are put into the model's parameters before the engine stacks its own
copy; the model is then dropped so that one copy stays on the device.
"""
import gc
import time
from operator import attrgetter

# model attribute path of each reference leaf of one layer
_LAYER_PATHS = {
    "ln1": "input_layernorm", "ln2": "post_attention_layernorm",
    "wq": "self_attn.q_proj", "wk": "self_attn.k_proj",
    "wv": "self_attn.v_proj", "wo": "self_attn.o_proj",
    "wg": "mlp.gate_proj", "wu": "mlp.up_proj", "wd": "mlp.down_proj",
}


def llama_config(cfg):
    from paddle_tpu.models.llama import LlamaConfig
    return LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        initializer_range=cfg["initializer_range"])


def build_model(cfg, weights, dtype):
    """The program's model holding the benchmark's weights."""
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaForCausalLM
    paddle.seed(0)
    paddle.set_default_dtype(dtype)
    try:
        model = LlamaForCausalLM(llama_config(cfg))
    finally:
        paddle.set_default_dtype("float32")
    model.eval()

    def put(param, value):
        if tuple(param.shape) != tuple(value.shape):
            raise SystemExit(f"bench: weight shape {value.shape} does not fit "
                             f"the model's {param.shape}")
        param._data = jnp.asarray(value, param._data.dtype)

    put(model.llama.embed_tokens.weight, weights["embed"])
    put(model.llama.norm.weight, weights["norm"])
    put(model.lm_head.weight, weights["head"])
    for layer, leaves in zip(model.llama.layers, weights["layers"]):
        for name, path in _LAYER_PATHS.items():
            put(attrgetter(path)(layer).weight, leaves[name])
    return model


class ServingSystem:
    """The engine behind the gateway.  ``url`` is what the load generator
    speaks to; ``engines`` (one today) are what the readers' spans wrap."""

    def __init__(self, engines, replica_set, gateway):
        self.engines = engines
        self.replica_set = replica_set
        self.gateway = gateway
        self.url = gateway.url

    def compiled_programs(self):
        """How many programs the runners hold compiled: read before and
        after the window, the difference is what compiled inside it."""
        n = 0
        for e in self.engines:
            r = e.runner
            n += r._prefill._cache_size()
            n += sum(p._cache_size() for p in r._decode_programs.values())
            n += sum(p._cache_size() for p in r._verify_programs.values())
        return n

    def health(self):
        return [e.health() for e in self.engines]

    def close(self):
        self.gateway.close()
        self.replica_set.close()
        for e in self.engines:
            e.runner.W = None
            e.runner.cache = None
        self.engines = []
        gc.collect()


def build(cfg, weights, devices, params, say):
    """One engine on the one chip of the cell (replicas behind a router come
    with the cell that needs them).  ``params``: the cell's engine overrides."""
    if len(devices) != 1:
        raise SystemExit(f"bench: builder llama_engine builds one engine on "
                         f"one chip, the cell gives {len(devices)}")
    from paddle_tpu.inference.frontend import ReplicaSet, start_gateway
    from paddle_tpu.inference.serving import LLMEngine

    eng = dict(cfg["engine"])
    eng.update(params.get("engine", {}))
    t0 = time.perf_counter()
    model = build_model(cfg, weights, cfg["torch_dtype"])
    say(f"model built and fed the seed's weights in "
        f"{time.perf_counter() - t0:.1f} s")
    t0 = time.perf_counter()
    engines = [LLMEngine(model, **eng)]
    del model
    gc.collect()
    say(f"engine built in {time.perf_counter() - t0:.1f} s")
    for e in engines:
        if params.get("require_kernel", True) and e.runner.use_kernel is not True:
            raise SystemExit("bench: engine.runner.use_kernel is not True: "
                             "the Pallas paged-attention path is not on")
    rs = ReplicaSet(engines)
    gw = start_gateway(rs, port=0)
    return ServingSystem(engines, rs, gw)
