"""Llama-3 family (BASELINE config #2: 8B pretrain, FSDP→GSPMD; #5 MoE variant).

Architecture: RMSNorm + GQA attention with RoPE + SwiGLU MLP, tied to the
paddle_tpu.nn stack. `shard_llama` applies the hybrid placement policy
(dp/fsdp/mp/sep axes) — the fleet 4D mapping from SURVEY §2.4 as GSPMD.
"""
from __future__ import annotations

import math

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import ops
from ..core.tensor import Tensor
from ..nn.layer.layers import Layer
from ..nn.layer.common import Linear, Embedding
from ..nn.layer.norm import RMSNorm
from ..nn.layer.container import LayerList
from ..nn import functional as F
from ..nn.functional.rope import fused_rotary_position_embedding
from ..nn.initializer import Normal
from .serving_plan import LayerKind, ServingPlan


class LlamaConfig:
    def __init__(self, vocab_size=128256, hidden_size=4096, intermediate_size=14336,
                 num_hidden_layers=32, num_attention_heads=32, num_key_value_heads=8,
                 max_position_embeddings=8192, rms_norm_eps=1e-5, rope_theta=500000.0,
                 tie_word_embeddings=False, initializer_range=0.02,
                 num_experts=0, num_experts_per_tok=2, moe_intermediate_size=None,
                 sep_backend="ring"):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.max_position_embeddings = max_position_embeddings
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = rope_theta
        self.tie_word_embeddings = tie_word_embeddings
        self.initializer_range = initializer_range
        self.num_experts = num_experts
        self.sep_backend = sep_backend
        self.num_experts_per_tok = num_experts_per_tok
        self.moe_intermediate_size = moe_intermediate_size

    @classmethod
    def llama3_8b(cls, **kw):
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw):
        kw.setdefault("vocab_size", 256)
        kw.setdefault("hidden_size", 64)
        kw.setdefault("intermediate_size", 128)
        kw.setdefault("num_hidden_layers", 2)
        kw.setdefault("num_attention_heads", 4)
        kw.setdefault("num_key_value_heads", 2)
        kw.setdefault("max_position_embeddings", 128)
        kw.setdefault("rope_theta", 10000.0)
        return cls(**kw)

    @classmethod
    def tiny_moe(cls, **kw):
        kw.setdefault("num_experts", 4)
        return cls.tiny(**kw)


# ---- the dense block on raw arrays --------------------------------------
# One definition for the two callers that run on raw arrays: the serving
# engine's programs (inference/engine/runner.py) and the SPMD pipeline stage
# (make_decoder_stage). The block comes in two halves around the caller's
# attention, which is what differs between them (paged against dense). The
# eager LlamaDecoderLayer goes through the op registry and the training path
# and is pinned to these by tests/test_serving.py.

# one layer's weights, under these keys; stacked_weights() gives each a
# leading layer axis
BLOCK_KEYS = ("ln1", "wq", "wk", "wv", "wo", "ln2", "wg", "wu", "wd")


def rms_norm(x, w, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(
        x.dtype)


def rope(x, pos, theta):
    """neox-style RoPE at integer positions pos [B] (x [B, Hn, D])."""
    D = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    freqs = pos.astype(jnp.float32)[:, None] * inv[None, :]      # [B, D/2]
    emb = jnp.concatenate([freqs, freqs], axis=-1)               # [B, D]
    s, c = jnp.sin(emb)[:, None, :], jnp.cos(emb)[:, None, :]
    xf = x.astype(jnp.float32)
    half = D // 2
    rot = jnp.concatenate([-xf[..., half:], xf[..., :half]], axis=-1)
    return (xf * c + rot * s).astype(x.dtype)


def block_qkv(p, x, pos, nh, kvh, eps, theta):
    """The block up to its attention: norm, the three projections split into
    heads, RoPE on q and k. x [B, H] rows at positions pos [B]; returns
    q [B, nh, D], k and v [B, kvh, D]."""
    D = p["wq"].shape[-1] // nh
    h = rms_norm(x, p["ln1"], eps)
    q = (h @ p["wq"]).reshape(-1, nh, D)
    k = (h @ p["wk"]).reshape(-1, kvh, D)
    v = (h @ p["wv"]).reshape(-1, kvh, D)
    return rope(q, pos, theta), rope(k, pos, theta), v


def block_out(p, x, att, eps):
    """The block after its attention (att [B, nh, D]): output projection and
    residual, norm, SwiGLU (silu in float32), residual."""
    x = x + att.reshape(x.shape[0], -1) @ p["wo"]
    h = rms_norm(x, p["ln2"], eps)
    gate = h @ p["wg"]
    up = h @ p["wu"]
    return x + (jax.nn.silu(gate.astype(jnp.float32)).astype(
        up.dtype) * up) @ p["wd"]


def stacked_weight_specs(pp, mp):
    """PartitionSpecs of ``LlamaForCausalLM.stacked_weights()``'s leaves for
    mesh axes ``pp`` and ``mp`` (either may be None): the layer axis of the
    ``BLOCK_KEYS`` leaves over pp, head and ffn dims over mp."""
    col, row = P(pp, None, mp), P(pp, mp, None)
    return {"embed": P(), "norm": P(), "head": P(None, mp),
            "wq": col, "wk": col, "wv": col, "wo": row,
            "ln1": P(pp, None), "ln2": P(pp, None),
            "wg": col, "wu": col, "wd": row}


def serving_plan(cfg, weights):
    """The dense block as the serving engine runs it (``serving_plan.py``):
    ONE kind of layer with paged KV, ``block_qkv`` and ``block_out`` around
    the runner's attention, the leaves stacked ``[L, ...]`` with no period
    axis. ``weights``: () -> iterator of (key, array)."""
    nh, kvh = cfg.num_attention_heads, cfg.num_key_value_heads
    eps, theta = cfg.rms_norm_eps, cfg.rope_theta
    block = LayerKind(
        cache="pages", keys=BLOCK_KEYS,
        first=lambda wl, x, pos: block_qkv(wl, x, pos, nh, kvh, eps, theta),
        second=lambda wl, x, att, live: (block_out(wl, x, att, eps), None))
    return ServingPlan(
        kinds={"block": block}, period=(("block", cfg.num_hidden_layers),),
        periods=None, weights=weights,
        specs=lambda pp, mp, ep=None: stacked_weight_specs(pp, mp),
        nh=nh, kvh=kvh, D=cfg.hidden_size // nh)


class KVCache:
    """Per-layer dense KV cache for autoregressive decode (the serving path's
    block/paged variant is ops/pallas/paged_attention.py; reference:
    block_multi_head_attention's cache_kv tensors)."""

    def __init__(self, batch, max_len, num_kv_heads, head_dim, dtype="float32"):
        import jax.numpy as jnp
        self.k = Tensor(jnp.zeros((batch, max_len, num_kv_heads, head_dim),
                                  dtype))
        self.v = Tensor(jnp.zeros((batch, max_len, num_kv_heads, head_dim),
                                  dtype))
        # traced scalar, and caches mutate IN PLACE (property writes), so a
        # to_static-captured decode step has fixed shapes and replays as ONE
        # compiled program per token — no per-op dispatches
        self.offset = Tensor(jnp.zeros((), jnp.int32))
        self.max_len = max_len

    def update(self, k_new, v_new):
        """Write s new steps at the current offset; returns the FULL cache
        (+ new valid length) — consumers mask instead of slicing, keeping
        shapes static under jit."""
        from ..core.dispatch import apply_op
        s = k_new.shape[1]

        def f(kc, vc, kn, vn, off):
            import jax
            kc2 = jax.lax.dynamic_update_slice(
                kc, kn.astype(kc.dtype), (0, off, 0, 0))
            vc2 = jax.lax.dynamic_update_slice(
                vc, vn.astype(vc.dtype), (0, off, 0, 0))
            return kc2, vc2, off + s

        k2, v2, off2 = apply_op("kv_cache_update", f, self.k, self.v,
                                k_new, v_new, self.offset)
        self.k._data = k2._buf
        self.v._data = v2._buf
        self.offset._data = off2._buf
        return self.k, self.v


def _cached_sdpa(q, k, v, q_offset):
    """Attention of the last `s` positions (starting at traced scalar
    q_offset) against the FULL fixed-length cache; causal masking also hides
    the not-yet-written tail, so shapes never depend on the offset."""
    from ..core.dispatch import apply_op

    def f(qa, ka, va, off):
        import jax
        b, s, h, d = qa.shape
        t = ka.shape[1]
        rep = h // ka.shape[2]
        if rep > 1:
            ka2 = jnp.repeat(ka, rep, axis=2)
            va2 = jnp.repeat(va, rep, axis=2)
        else:
            ka2, va2 = ka, va
        sc = jnp.einsum("bshd,bthd->bhst", qa.astype(jnp.float32),
                        ka2.astype(jnp.float32)) / np.sqrt(d)
        rows = off + jnp.arange(s)[:, None]
        cols = jnp.arange(t)[None, :]
        sc = jnp.where((cols <= rows)[None, None], sc, -1e30)
        p = jax.nn.softmax(sc, axis=-1)
        out = jnp.einsum("bhst,bthd->bshd", p, va2.astype(jnp.float32))
        return out.astype(qa.dtype)

    return apply_op("cached_sdpa", f, q, k, v, q_offset)


class LlamaAttention(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        h = config.hidden_size
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim = h // self.num_heads
        self.rope_theta = config.rope_theta
        self.sep_backend = getattr(config, "sep_backend", "ring")
        init = Normal(std=config.initializer_range)
        self.q_proj = Linear(h, self.num_heads * self.head_dim, weight_attr=init,
                             bias_attr=False)
        self.k_proj = Linear(h, self.num_kv_heads * self.head_dim, weight_attr=init,
                             bias_attr=False)
        self.v_proj = Linear(h, self.num_kv_heads * self.head_dim, weight_attr=init,
                             bias_attr=False)
        self.o_proj = Linear(self.num_heads * self.head_dim, h, weight_attr=init,
                             bias_attr=False)

    def forward(self, x, position_ids=None, kv_cache: KVCache = None):
        b, s, h = x.shape
        q = self.q_proj(x).reshape([b, s, self.num_heads, self.head_dim])
        k = self.k_proj(x).reshape([b, s, self.num_kv_heads, self.head_dim])
        v = self.v_proj(x).reshape([b, s, self.num_kv_heads, self.head_dim])
        if kv_cache is not None and position_ids is None:
            # static arange + traced offset: shape stays [1, s] under jit
            pos = ops.arange(0, s, dtype="int64").reshape([1, s]) + \
                kv_cache.offset.astype("int64")
            position_ids = ops.tile(pos, [b, 1])
        q, k, _ = fused_rotary_position_embedding(
            q, k, None, position_ids=position_ids,
            rotary_emb_base=self.rope_theta,
            max_position=kv_cache.max_len if kv_cache is not None else None)
        if kv_cache is not None:
            q_offset = kv_cache.offset + 0   # snapshot before in-place update
            kk, vv = kv_cache.update(k, v)
            out = _cached_sdpa(q, kk, vv, q_offset)
            return self.o_proj(out.reshape([b, s, self.num_heads * self.head_dim]))
        from ..distributed.fleet.topology import get_hybrid_communicate_group
        hcg_sep = get_hybrid_communicate_group().get_sep_parallel_world_size()
        if hcg_sep > 1:
            # context parallelism: sequence sharded on 'sep'; ring attention
            # by default, Ulysses all-to-all when configured and head counts
            # divide (S >> H regime where ring's per-hop latency dominates)
            rep = self.num_heads // self.num_kv_heads
            if rep > 1:
                k = ops.repeat_interleave(k, rep, axis=2)
                v = ops.repeat_interleave(v, rep, axis=2)
            if getattr(self, "sep_backend", "ring") == "ulysses" and \
                    self.num_heads % hcg_sep == 0:
                from ..parallel.ulysses import ulysses_attention
                out = ulysses_attention(q, k, v, causal=True,
                                        axis_name="sep")
            else:
                from ..parallel.ring_attention import ring_flash_attention
                out = ring_flash_attention(q, k, v, causal=True,
                                           axis_name="sep")
        else:
            out, _ = F.flash_attention(q, k, v, causal=True)
        return self.o_proj(out.reshape([b, s, self.num_heads * self.head_dim]))


class LlamaMLP(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        h, m = config.hidden_size, config.intermediate_size
        init = Normal(std=config.initializer_range)
        self.gate_proj = Linear(h, m, weight_attr=init, bias_attr=False)
        self.up_proj = Linear(h, m, weight_attr=init, bias_attr=False)
        self.down_proj = Linear(m, h, weight_attr=init, bias_attr=False)

    def forward(self, x):
        return self.down_proj(F.swiglu(self.gate_proj(x), self.up_proj(x)))


class LlamaDecoderLayer(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.input_layernorm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                epsilon=config.rms_norm_eps)
        if config.num_experts > 0:
            from ..parallel.moe import MoELayer
            self.mlp = MoELayer(config.hidden_size, num_experts=config.num_experts,
                                d_hidden=config.moe_intermediate_size
                                or config.intermediate_size,
                                top_k=config.num_experts_per_tok)
        else:
            self.mlp = LlamaMLP(config)

    def forward(self, x, position_ids=None, kv_cache=None):
        x = x + self.self_attn(self.input_layernorm(x), position_ids,
                               kv_cache=kv_cache)
        x = x + self.mlp(self.post_attention_layernorm(x))
        return x


class LlamaModel(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(config.vocab_size, config.hidden_size,
                                      weight_attr=Normal(std=config.initializer_range))
        self.layers = LayerList([LlamaDecoderLayer(config)
                                 for _ in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def forward(self, input_ids, position_ids=None, kv_caches=None):
        x = self.embed_tokens(input_ids)
        for i, layer in enumerate(self.layers):
            x = layer(x, position_ids,
                      kv_cache=kv_caches[i] if kv_caches else None)
        return self.norm(x)


class LlamaForCausalLM(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.llama = LlamaModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = Linear(config.hidden_size, config.vocab_size,
                                  weight_attr=Normal(std=config.initializer_range),
                                  bias_attr=False)

    def stacked_weights(self):
        """The model's weights as the flat dict of host arrays that the raw
        array block takes: ``embed norm head`` and the ``BLOCK_KEYS`` leaves
        stacked ``[L, ...]`` (Linear stores weight [in, out]); ``head`` is
        ``embed.T`` when the embeddings are tied."""
        lay = self.llama.layers
        for l in lay:
            if not isinstance(l.mlp, LlamaMLP):
                raise NotImplementedError(
                    "stacked_weights() stacks the dense Llama block only: "
                    f"this model's layers hold a {type(l.mlp).__name__} "
                    f"(num_experts={self.config.num_experts})")

        def w(m):
            return np.asarray(m.weight._data)

        W = {
            "embed": w(self.llama.embed_tokens),
            "norm": w(self.llama.norm),
            "wq": np.stack([w(l.self_attn.q_proj) for l in lay]),
            "wk": np.stack([w(l.self_attn.k_proj) for l in lay]),
            "wv": np.stack([w(l.self_attn.v_proj) for l in lay]),
            "wo": np.stack([w(l.self_attn.o_proj) for l in lay]),
            "ln1": np.stack([w(l.input_layernorm) for l in lay]),
            "ln2": np.stack([w(l.post_attention_layernorm) for l in lay]),
            "wg": np.stack([w(l.mlp.gate_proj) for l in lay]),
            "wu": np.stack([w(l.mlp.up_proj) for l in lay]),
            "wd": np.stack([w(l.mlp.down_proj) for l in lay]),
        }
        W["head"] = (w(self.lm_head) if self.lm_head is not None
                     else W["embed"].T)
        return W

    def serving_plan(self, kernels=False):
        """What the serving engine needs of this model (``kernels``: the
        block has none of its own). The weights go through host numpy, all
        stacked at once."""
        return serving_plan(self.config,
                            lambda: iter(self.stacked_weights().items()))

    def new_kv_caches(self, batch, max_len, dtype="float32"):
        cfg = self.config
        return [KVCache(batch, max_len, cfg.num_key_value_heads,
                        cfg.hidden_size // cfg.num_attention_heads, dtype)
                for _ in range(cfg.num_hidden_layers)]

    def generate(self, input_ids, max_new_tokens=32, do_sample=False,
                 top_p=1.0, top_k=0, temperature=1.0, eos_token_id=None,
                 use_cache=True, seed=None, tokens_per_dispatch=None):
        """Autoregressive decoding with a per-layer KV cache (reference:
        PaddleNLP generation + phi top_p_sampling_kernel.h for the sampler).
        Greedy when do_sample=False; nucleus/top-k sampling otherwise.
        Returns [B, prompt + new] token ids.

        tokens_per_dispatch: decode steps compiled into ONE program per
        host dispatch (default 1 — async dispatch already pipelines the
        per-token calls; raise it only when per-call latency, not
        throughput, dominates). eos checking needs each token on host, so
        it forces 1."""
        from .. import ops
        from ..autograd import no_grad
        from ..jit import to_static

        with no_grad():
            b, prompt = input_ids.shape
            ids = input_ids
            finished = None
            cur = input_ids
            cached_step, caches = None, None
            gen_entry = None
            # default 1 (which K is fastest on the chip: not measured); the
            # knob remains for deployments bound by dispatch latency
            K = 1 if tokens_per_dispatch is None else tokens_per_dispatch
            K = max(1, min(int(K), max_new_tokens))
            if eos_token_id is not None:
                K = 1                      # host must see every token
            if use_cache:
                # cache length buckets to a power of two (floor 128) so
                # repeated generate() calls of similar lengths share ONE
                # compiled decode step per (batch, bucket, sampling config)
                # without paying full-context attention for short outputs;
                # entries persist on the model and reset by rewinding the
                # offset — stale tail entries are causally masked, never read
                # K>1 overshoots up to K-1 tokens past max_new before the
                # trim; the bucket must cover them or the final dispatch
                # indexes the RoPE table / cache past max_len
                need = prompt + -(-max_new_tokens // K) * K
                max_len = 1 << max(7, (need - 1).bit_length())
                gen_key = (b, max_len, do_sample, top_p, top_k, temperature,
                           seed, K)
                states = getattr(self, "_gen_states", None)
                if states is None:
                    states = self._gen_states = {}
                existing = states.get(gen_key)
                # a busy entry means a reentrant/concurrent generate: build a
                # PRIVATE state (and don't store it) so the in-flight decode
                # keeps its caches intact
                gen_entry = existing if existing is not None and \
                    not existing["busy"] else None
                if gen_entry is None:
                    caches = self.new_kv_caches(b, max_len)

                    out_dtype = str(input_ids.dtype).split(".")[-1]

                    def _one_tok(cur_tok):
                        hidden = self.llama(cur_tok, kv_caches=caches)
                        if self.lm_head is not None:
                            logits = self.lm_head(hidden[:, -1])
                        else:
                            logits = ops.matmul(
                                hidden[:, -1],
                                self.llama.embed_tokens.weight,
                                transpose_y=True)
                        nxt = self._sample(logits, do_sample, top_p, top_k,
                                           temperature, seed)
                        # cast in-graph: keeps the decode loop free of
                        # per-step eager ops (each is a device round trip)
                        return nxt.astype(out_dtype)

                    def _model_step(cur_tok):
                        # K tokens per compiled program: the kv caches are
                        # mutable captured state, so the K sequential cache
                        # updates land in ONE dispatch
                        outs = [_one_tok(cur_tok)]
                        for _ in range(K - 1):
                            outs.append(_one_tok(outs[-1]))
                        return ops.concat(outs, axis=1) if K > 1 else outs[0]

                    # one compiled program per shape signature: a prefill
                    # trace ([B, prompt]) and a decode trace ([B, 1]); every
                    # subsequent token replays the compiled decode step
                    # (cache + offset lifted as mutable program state)
                    cached_step = to_static(_model_step)
                    gen_entry = {"caches": caches, "step": cached_step,
                                 "busy": False}
                    if existing is None:
                        states[gen_key] = gen_entry
                        while len(states) > 4:  # bound retained cache memory
                            states.pop(next(iter(states)))
                else:
                    caches, cached_step = gen_entry["caches"], \
                        gen_entry["step"]
                    import jax.numpy as jnp
                    for c in caches:
                        c.offset._data = jnp.zeros((), jnp.int32)
                gen_entry["busy"] = True

            # tokens accumulate in a python list and concatenate ONCE at the
            # end: a per-step concat has a growing shape, so eager dispatch
            # would compile a fresh kernel every token (measured 15ms/token
            # vs 0.4ms for the whole compiled decode step)
            toks = [ids]
            n_dispatch = -(-max_new_tokens // K) if use_cache else \
                max_new_tokens
            try:
                for step in range(n_dispatch):
                    if use_cache:
                        blk = cached_step(cur)       # [B, K] token block
                        nxt = blk if K == 1 else blk[:, -1:]
                    else:
                        ids = ops.concat(toks, axis=1) if len(toks) > 1 \
                            else ids
                        toks = [ids]
                        hidden = self.llama(ids)
                        if self.lm_head is not None:
                            logits = self.lm_head(hidden[:, -1])
                        else:
                            logits = ops.matmul(
                                hidden[:, -1],
                                self.llama.embed_tokens.weight,
                                transpose_y=True)
                        nxt = self._sample(logits, do_sample, top_p, top_k,
                                           temperature, seed)
                    if eos_token_id is not None:
                        import jax.numpy as jnp
                        done_now = Tensor(
                            (nxt._data == eos_token_id).reshape(-1))
                        if finished is not None:
                            nxt = Tensor(jnp.where(
                                finished._data,
                                jnp.asarray(eos_token_id, nxt._data.dtype),
                                nxt._data.reshape(-1)).reshape(-1, 1))
                            done_now = Tensor(finished._data | done_now._data)
                        finished = done_now
                    nxt = nxt.astype(toks[0].dtype)
                    if use_cache and K > 1:
                        toks.append(blk.astype(toks[0].dtype))
                    else:
                        toks.append(nxt)
                    cur = nxt
                    if finished is not None and \
                            bool(np.asarray(finished._data).all()):
                        break
            finally:
                if gen_entry is not None:
                    gen_entry["busy"] = False
            out = ops.concat(toks, axis=1) if len(toks) > 1 else toks[0]
            if use_cache and K > 1:
                out = out[:, :prompt + max_new_tokens]  # trim K overshoot
            return out

    def _sample(self, logits, do_sample, top_p, top_k, temperature, seed):
        from .. import ops
        if not do_sample:
            return ops.argmax(logits, axis=-1, keepdim=True)
        if temperature and temperature != 1.0:
            logits = logits / temperature
        from ..nn import functional as F
        probs = F.softmax(logits, axis=-1)
        if top_k:
            vals, _ = ops.topk(probs, k=top_k)
            import jax.numpy as jnp
            thresh = vals[:, -1:]
            probs = Tensor(jnp.where(probs._data >= thresh._data,
                                     probs._data, 0.0))
            probs = probs / probs.sum(axis=-1, keepdim=True)
        if top_p < 1.0:
            _, ids = ops.top_p_sampling(probs, top_p,
                                        seed=-1 if seed is None else seed)
            return ids
        return ops.multinomial(probs, num_samples=1)

    def forward(self, input_ids, labels=None, position_ids=None):
        hidden = self.llama(input_ids, position_ids)
        if self.lm_head is not None:
            logits = self.lm_head(hidden)
        else:
            logits = ops.matmul(hidden, self.llama.embed_tokens.weight,
                                transpose_y=True)
        if labels is not None:
            aux = None
            for layer in self.llama.layers:
                al = getattr(layer.mlp, "aux_loss", None)
                if al is not None:
                    aux = al if aux is None else aux + al
            return logits, causal_lm_loss(logits, labels,
                                          self.config.vocab_size, aux)
        return logits


def shard_llama(model: LlamaForCausalLM, mesh, fsdp_axis="dp", mp_axis="mp"):
    """Apply the hybrid placement policy: Megatron TP on 'mp', FSDP (param
    sharding) on the fsdp axis — SURVEY §2.4 DP/sharding/TP mapping."""
    from jax.sharding import PartitionSpec as P
    from ..parallel.mp_layers import _shard_param

    def put(p, spec):
        if p is not None:
            _shard_param(p, spec)

    put(model.llama.embed_tokens.weight, P(mp_axis, None))
    if model.lm_head is not None:
        put(model.lm_head.weight, P(None, mp_axis))
    for layer in model.llama.layers:
        att, mlp = layer.self_attn, layer.mlp
        put(att.q_proj.weight, P(fsdp_axis, mp_axis))
        put(att.k_proj.weight, P(fsdp_axis, mp_axis))
        put(att.v_proj.weight, P(fsdp_axis, mp_axis))
        put(att.o_proj.weight, P(mp_axis, fsdp_axis))
        if isinstance(mlp, LlamaMLP):
            put(mlp.gate_proj.weight, P(fsdp_axis, mp_axis))
            put(mlp.up_proj.weight, P(fsdp_axis, mp_axis))
            put(mlp.down_proj.weight, P(mp_axis, fsdp_axis))
    return model


def causal_lm_loss(logits, labels, vocab_size, aux_loss=None, aux_coef=0.01):
    """Token cross-entropy (+ optional MoE load-balance aux) — the one loss
    formula shared by the dense and pipeline-partitioned models."""
    loss = F.cross_entropy(logits.reshape([-1, vocab_size]),
                           labels.reshape([-1]))
    if aux_loss is not None:
        loss = loss + aux_coef * aux_loss
    return loss


def make_decoder_stage(config: LlamaConfig):
    """The dense Llama block as (init, apply) on raw arrays — the
    homogeneous stage function for the SPMD stacked-weight pipeline
    (parallel/pipeline.py), which runs inside shard_map. The block is
    ``block_qkv`` / ``block_out`` around a dense causal attention."""
    h = config.hidden_size
    nh, nkv = config.num_attention_heads, config.num_key_value_heads
    hd = h // nh
    m = config.intermediate_size
    theta = config.rope_theta
    eps = config.rms_norm_eps
    std = config.initializer_range

    def init(key):
        ks = jax.random.split(key, 7)
        n = lambda k, shape: jax.random.normal(k, shape, jnp.float32) * std
        return {
            "ln1": jnp.ones((h,), jnp.float32),
            "wq": n(ks[0], (h, nh * hd)), "wk": n(ks[1], (h, nkv * hd)),
            "wv": n(ks[2], (h, nkv * hd)), "wo": n(ks[3], (nh * hd, h)),
            "ln2": jnp.ones((h,), jnp.float32),
            "wg": n(ks[4], (h, m)), "wu": n(ks[5], (h, m)),
            "wd": n(ks[6], (m, h)),
        }

    def apply(p, x):
        b, s, _ = x.shape
        rows = x.reshape(b * s, h)
        pos = jnp.tile(jnp.arange(s, dtype=jnp.int32), b)
        q, k, v = (a.reshape((b, s) + a.shape[1:])
                   for a in block_qkv(p, rows, pos, nh, nkv, eps, theta))
        if nh != nkv:
            k = jnp.repeat(k, nh // nkv, axis=2)
            v = jnp.repeat(v, nh // nkv, axis=2)
        scores = jnp.einsum("bsnd,btnd->bnst", q, k) / jnp.sqrt(float(hd))
        mask = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(mask[None, None], scores, -1e30)
        att = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("bnst,btnd->bsnd", att, v)
        return block_out(p, rows, o.reshape(b * s, nh, hd), eps).reshape(
            x.shape)

    return init, apply


class LlamaEmbeddingPipe(Layer):
    """Stage-0 pipe chunk: token embedding (reference PaddleNLP
    LlamaEmbeddingPipe semantics — first pp stage owns the embedding).
    For MoE configs it also seeds the carried aux-loss stream."""

    def __init__(self, config: LlamaConfig, emit_aux=False):
        super().__init__()
        self.embed_tokens = Embedding(config.vocab_size, config.hidden_size,
                                      weight_attr=Normal(std=config.initializer_range))
        self._emit_aux = emit_aux

    def forward(self, input_ids):
        h = self.embed_tokens(input_ids)
        if self._emit_aux:
            from ..core.tensor import Tensor
            import jax.numpy as jnp
            return (h, Tensor(jnp.zeros((), jnp.float32)))
        return h


class LlamaDecoderLayerPipe(LlamaDecoderLayer):
    """Decoder chunk that carries the running MoE aux loss through the stage
    boundary as a second stream member — each chunk's aux contribution stays
    inside that chunk's tape segment, so the chunked backward never crosses a
    detach boundary (the reference allreduces aux across the pp group)."""

    def forward(self, x):
        x, aux = x
        h = super().forward(x)
        al = getattr(self.mlp, "aux_loss", None)
        if al is not None:
            aux = aux + al
        return (h, aux)


class LlamaNormHeadPipe(Layer):
    """Last pipe chunk: final RMSNorm + LM head → logits. With tied embeddings
    the weight is read through a closure (not registered here) so it belongs
    to exactly one stage's parameter list."""

    def __init__(self, config: LlamaConfig, tied_weight_getter=None):
        super().__init__()
        self.config = config
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)
        if config.tie_word_embeddings:
            self.lm_head = None
            self._tied_weight_getter = tied_weight_getter
        else:
            self.lm_head = Linear(config.hidden_size, config.vocab_size,
                                  weight_attr=Normal(std=config.initializer_range),
                                  bias_attr=False)

    def forward(self, x):
        aux = None
        if isinstance(x, tuple):
            x, aux = x
        h = self.norm(x)
        if self.lm_head is not None:
            logits = self.lm_head(h)
        else:
            logits = ops.matmul(h, self._tied_weight_getter(), transpose_y=True)
        return logits if aux is None else (logits, aux)


class LlamaForCausalLMPipe:
    """Pipeline-partitioned Llama (reference: PaddleNLP LlamaForCausalLMPipe on
    fleet pp_layers.py:258). Returns a PipelineLayer whose chunks are
    [embedding | decoder blocks … | norm+head], segmented by decoder-layer
    count so embedding rides stage 0 and the head rides the last stage."""

    def __new__(cls, config: LlamaConfig, num_stages=2,
                num_virtual_pipeline_stages=None, recompute_interval=0,
                topology=None):
        from ..parallel.pipeline_layer import PipelineLayer

        moe = config.num_experts > 0
        embed = LlamaEmbeddingPipe(config, emit_aux=moe)
        dec_cls = LlamaDecoderLayerPipe if moe else LlamaDecoderLayer
        decoders = [dec_cls(config) for _ in range(config.num_hidden_layers)]
        head = LlamaNormHeadPipe(
            config, tied_weight_getter=lambda: embed.embed_tokens.weight)

        def loss_fn(out, labels):
            logits, aux = out if isinstance(out, tuple) else (out, None)
            return causal_lm_loss(logits, labels, config.vocab_size, aux)

        pipe = PipelineLayer(
            [embed] + decoders + [head],
            num_stages=num_stages, loss_fn=loss_fn,
            seg_method=f"layer:{dec_cls.__name__}",
            recompute_interval=recompute_interval,
            num_virtual_pipeline_stages=num_virtual_pipeline_stages,
            topology=topology)
        pipe.config = config
        if config.tie_word_embeddings:
            pipe._pin_exempt.add(id(embed.embed_tokens.weight))
        return pipe


def llama3_8b():
    return LlamaForCausalLM(LlamaConfig.llama3_8b())


def llama_tiny():
    return LlamaForCausalLM(LlamaConfig.tiny())


def llama_tiny_moe():
    return LlamaForCausalLM(LlamaConfig.tiny_moe())
