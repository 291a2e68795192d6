"""SDAR family (``model_type`` ``sdar_moe``): the Qwen3-MoE block - GQA with
a norm a head on q and k, RoPE (neox halves, the whole head), a sparse
expert layer with a softmax router, top-k renormalised, no shared expert -
that GENERATES BY BLOCKS (block diffusion): the mask is causal from block to
block of ``block_length`` aligned positions and open both ways inside a
block, and a block of the answer starts as ``[MASK]`` and is unmasked over
``denoising_steps`` forward passes. Serving only: a full-sequence
``forward`` under that mask for tests and the
:class:`~.serving_plan.ServingPlan` the engine runs (``plan.block`` > 0: a
decode dispatch is one block a sequence); no ``generate`` and no training
path.

Layer, every one alike::

    h = rms_norm(x);  q, k = rope(head_norm(h Wq), pos), rope(head_norm(h Wk), pos)
    x += attention(q, k, h Wv) Wo
    h2 = rms_norm(x);  s = softmax_f32(h2 Wr);  (w, e) = top_k(s);  w /= sum(w)
    x += sum_j w_j * expert_{e_j}(h2)           expert: Wd (silu(Wg h) * Wu h)

What the published ``config.json`` does not give is how the family
generates; it follows the family's published generation script, as
``bench/configs/sdar-30b-a3b-chat.json`` lists under ``assumed``: blocks
aligned at multiples of ``block_length`` from position 0; at each denoising
step the logits AT a masked position predict THAT position's token (no
shift); ``num_transfer[s] = Q // D`` (+1 on the first ``Q % D`` steps)
positions are unmasked a step by the ``remasking`` rule (:func:`unmask_rule`).

**A chip's share of the experts** is named as Solar-Open2's is
(``router_width`` / ``experts_held`` / ``expert_offset``); the sum over the
chosen experts held here is ``models/dropless.py``'s.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.rng import next_key
from ..core.tensor import Parameter, Tensor
from ..nn.layer.layers import Layer
from ..ops.pallas.moe_gmm import moe_gmm, moe_gmm_ref
from .dropless import ROUTING_COUNTS, routed_experts, routing_counts
from .llama import rms_norm, rope
from .serving_plan import LayerKind, ServingPlan, stack_leaves

__all__ = ["SDARConfig", "SDARForCausalLM", "REMASKING", "unmask_rule"]

REMASKING = ("sequential", "low_confidence_static", "low_confidence_dynamic")


class SDARConfig:
    def __init__(self, vocab_size=151936, hidden_size=2048,
                 num_hidden_layers=48, num_attention_heads=32,
                 num_key_value_heads=4, head_dim=128,
                 moe_intermediate_size=768, router_width=128,
                 experts_held=None, expert_offset=0, num_experts_per_tok=8,
                 norm_topk_prob=True, rms_norm_eps=1e-6, rope_theta=1e6,
                 block_length=4, denoising_steps=4, remasking="sequential",
                 confidence_threshold=0.9, mask_token_id=151669,
                 attention_bias=False, tie_word_embeddings=False,
                 sliding_window=None, decoder_sparse_step=1,
                 mlp_only_layers=(), initializer_range=0.02,
                 max_position_embeddings=32768):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.moe_intermediate_size = moe_intermediate_size
        self.router_width = router_width
        self.experts_held = (router_width if experts_held is None
                             else experts_held)
        self.expert_offset = expert_offset
        self.num_experts_per_tok = num_experts_per_tok
        self.norm_topk_prob = bool(norm_topk_prob)
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = rope_theta
        self.block_length = int(block_length)
        self.denoising_steps = int(denoising_steps)
        self.remasking = remasking
        self.confidence_threshold = float(confidence_threshold)
        self.mask_token_id = int(mask_token_id)
        self.initializer_range = initializer_range
        self.max_position_embeddings = max_position_embeddings
        if remasking not in REMASKING:
            raise ValueError(f"remasking={remasking!r} is not one of "
                             f"{REMASKING}")
        if self.block_length < 1 or self.denoising_steps < 1:
            raise ValueError("block_length and denoising_steps are at least 1")
        if not 0 <= self.mask_token_id < vocab_size:
            raise ValueError(f"mask_token_id={mask_token_id} is not in the "
                             f"vocabulary of {vocab_size}")
        if not 0 <= expert_offset <= expert_offset + self.experts_held \
                <= router_width:
            raise ValueError(
                f"experts [{expert_offset}, {expert_offset} + "
                f"{self.experts_held}) are not among the router's "
                f"{router_width}")
        for name, want, got in (("attention_bias", False, attention_bias),
                                ("tie_word_embeddings", False,
                                 tie_word_embeddings),
                                ("sliding_window", None, sliding_window),
                                ("decoder_sparse_step", 1,
                                 decoder_sparse_step),
                                ("mlp_only_layers", (),
                                 tuple(mlp_only_layers))):
            if got != want:
                raise NotImplementedError(
                    f"sdar_moe is implemented for {name}={want}, the "
                    f"published value; got {got}")

    @classmethod
    def tiny(cls, **kw):
        """The rehearsal size of ``bench/rehearsal/sdar-tiny.json``."""
        for k, v in dict(vocab_size=256, hidden_size=64, num_hidden_layers=2,
                         num_attention_heads=4, num_key_value_heads=2,
                         head_dim=16, moe_intermediate_size=32,
                         router_width=8, num_experts_per_tok=2,
                         mask_token_id=255,
                         max_position_embeddings=256).items():
            kw.setdefault(k, v)
        return cls(**kw)


# ---- one layer's leaves ---------------------------------------------------
# name -> (shape, how it is drawn); matrices are [in, out] as ``x @ w`` reads

def layer_leaves(c):
    h, d, f, e = (c.hidden_size, c.head_dim, c.moe_intermediate_size,
                  c.experts_held)
    nq, nkv = c.num_attention_heads * d, c.num_key_value_heads * d
    return {"ln1": ((h,), "gain"), "wq": ((h, nq), "matrix"),
            "wk": ((h, nkv), "matrix"), "wv": ((h, nkv), "matrix"),
            "q_norm": ((d,), "gain"), "k_norm": ((d,), "gain"),
            "wo": ((nq, h), "matrix"), "ln2": ((h,), "gain"),
            "router": ((h, c.router_width), "matrix"),
            "wg": ((e, h, f), "matrix"), "wu": ((e, h, f), "matrix"),
            "wd": ((e, f, h), "matrix")}


_WHOLE = ("wg", "wu", "wd")     # what moe_gmm reads: never sliced out


def _draw(key, shape, how, c):
    x = jax.random.normal(key, shape, jnp.float32)
    return 1.0 + 0.05 * x if how == "gain" else x * c.initializer_range


# ---- the layer on raw arrays ----------------------------------------------

def _mm32(x, w):
    """``x @ w`` accumulated and returned in float32 (operands as stored)."""
    return jnp.matmul(x, w, preferred_element_type=jnp.float32)


def attn_qkv(p, x, pos, c):
    """The layer up to its attention: norm, the three projections, the
    norm a head on q and k, RoPE. x [N, H] rows at positions pos [N] ->
    q [N, nh, D], k and v [N, kvh, D]."""
    nh, kvh, d = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    h = rms_norm(x, p["ln1"], c.rms_norm_eps)
    q = rms_norm((h @ p["wq"]).reshape(-1, nh, d), p["q_norm"],
                 c.rms_norm_eps)
    k = rms_norm((h @ p["wk"]).reshape(-1, kvh, d), p["k_norm"],
                 c.rms_norm_eps)
    v = (h @ p["wv"]).reshape(-1, kvh, d)
    return rope(q, pos, c.rope_theta), rope(k, pos, c.rope_theta), v


def attn_out(p, x, att):
    """After the attention (att [N, nh, D]): the output projection and the
    residual."""
    return x + att.reshape(x.shape[0], -1).astype(x.dtype) @ p["wo"]


def route(p, h, c):
    """The router on normed rows ``h [N, H]``: softmax in float32 over its
    whole width, the top-k, renormalised. -> chosen [N, k] int32, weight
    [N, k] float32."""
    score = jax.nn.softmax(_mm32(h, p["router"]), axis=-1)
    weight, chosen = jax.lax.top_k(score, c.num_experts_per_tok)
    if c.norm_topk_prob:
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    return chosen, weight


def experts(p, x, live, c, gmm=moe_gmm_ref, l=0):
    """The expert layer on rows ``x [N, H]`` (``live [N]`` > 0: the rows
    that are routed): ``x + sum over the chosen HELD experts``, and the
    routing counts (``ROUTING_COUNTS``, int32). ``l``: this layer's index
    where the experts' matrices come as the stack of all layers."""
    h = rms_norm(x, p["ln2"], c.rms_norm_eps)
    chosen, weight = route(p, h, c)
    routed, held, sizes = routed_experts(
        p, h, chosen, weight, live, c.experts_held, c.expert_offset, gmm, l,
        x.dtype)
    return x + routed.astype(x.dtype), routing_counts(live, held, sizes)


def block_causal_attention(q, k, v, block):
    """Dense softmax attention of one sequence in float32 under the
    family's mask (the engine's is paged): position p sees p' iff
    ``p' // block <= p // block``. q [T, nh, D], k and v [T, kvh, D]."""
    t, nh, d = q.shape
    group = nh // k.shape[1]
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) * d ** -0.5
    b = jnp.arange(t) // block
    s = jnp.where((b[None, :] <= b[:, None])[None], s, -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)


# ---- which positions a denoising step unmasks -----------------------------

def unmask_rule(c):
    """``(conf [B, Q] float32, masked [B, Q] bool, step) -> [B, Q] bool``:
    the masked positions whose sampled token is kept after denoising step
    ``step``, ``num_transfer[step]`` of them (``Q // D``, one more on the
    first ``Q % D`` steps; fewer where fewer are masked):

    ``sequential``              the leftmost masked
    ``low_confidence_static``   those of highest confidence
    ``low_confidence_dynamic``  every masked position whose confidence
                                passes ``confidence_threshold`` if those
                                are at least ``num_transfer[step]``, else
                                those of highest confidence

    Ties go to the left."""
    Q, D = c.block_length, c.denoising_steps
    base, extra = divmod(Q, D)
    at = jnp.arange(Q)

    def highest(score, masked, n):
        s = jnp.where(masked, score, -jnp.inf)
        ahead = ((s[:, None, :] > s[:, :, None])
                 | ((s[:, None, :] == s[:, :, None])
                    & (at[None, None, :] < at[None, :, None])))
        return masked & (jnp.sum(ahead, axis=-1) < n)

    def rule(conf, masked, step):
        n = base + (step < extra).astype(jnp.int32)
        if c.remasking == "sequential":
            return highest(jnp.broadcast_to(-at.astype(jnp.float32),
                                            masked.shape), masked, n)
        top = highest(conf, masked, n)
        if c.remasking == "low_confidence_static":
            return top
        sure = masked & (conf > c.confidence_threshold)
        return jnp.where((jnp.sum(sure, axis=-1) >= n)[:, None], sure, top)

    return rule


# ---- the model --------------------------------------------------------------

class _SDARLayer(Layer):
    """One layer's leaves as parameters."""

    def leaves(self):
        return {n: p._data for n, p in self._parameters.items()}


class SDARForCausalLM(Layer):
    """``leaves``: ``{"embed", "norm", "head", "layers": [{name: array}]}``
    to adopt as the parameters (no copy; names and shapes as
    :func:`layer_leaves` gives them); without, they are drawn from
    ``paddle.seed`` in the default dtype.

    ``hand_over``: the engine built from this model TAKES the parameters -
    each is let go of as the engine stacks it, and the model holds none
    afterwards. For a model of which the device cannot hold two copies."""

    def __init__(self, config: SDARConfig, leaves=None, hand_over=False):
        super().__init__()
        self.config = c = config
        self.hand_over = bool(hand_over)
        top = {"embed": ((c.vocab_size, c.hidden_size), "matrix"),
               "norm": ((c.hidden_size,), "gain"),
               "head": ((c.hidden_size, c.vocab_size), "matrix")}

        def param(name, shape, how, given):
            if given is None:
                return Parameter(_draw(next_key(), shape, how, c).astype(
                    self._dtype))
            if tuple(given.shape) != tuple(shape):
                raise ValueError(f"leaf {name}: shape {tuple(given.shape)} "
                                 f"does not fit the model's {tuple(shape)}")
            return Parameter(given)

        for name, (shape, how) in top.items():
            self.add_parameter(name, param(
                name, shape, how, None if leaves is None else leaves[name]))
        self.layers = []
        for i in range(c.num_hidden_layers):
            layer = _SDARLayer()
            given = None if leaves is None else leaves["layers"][i]
            for name, (shape, how) in layer_leaves(c).items():
                layer.add_parameter(name, param(
                    f"layers[{i}].{name}", shape, how,
                    None if given is None else given[name]))
            self.add_sublayer(f"layer_{i}", layer)
            self.layers.append(layer)

    def forward(self, input_ids):
        """Logits ``[B, T, vocab]`` (float32) of whole sequences under the
        block-causal mask, no cache: the layers the engine runs, with dense
        attention. The logits at a position are that position's own (no
        shift): what a denoising step reads where the input is ``[MASK]``."""
        c = self.config
        ids = input_ids._data if isinstance(input_ids, Tensor) else input_ids
        ids = jnp.asarray(ids)
        if ids.ndim == 1:
            ids = ids[None]
        live = jnp.ones((ids.shape[1],), jnp.int32)
        pos = jnp.arange(ids.shape[1], dtype=jnp.int32)

        def one(tokens):
            x = self.embed._data[tokens]
            for layer in self.layers:
                p = layer.leaves()
                att = block_causal_attention(*attn_qkv(p, x, pos, c),
                                             c.block_length)
                x, _ = experts(p, attn_out(p, x, att), live, c)
            h = rms_norm(x, self.norm._data, c.rms_norm_eps)
            return _mm32(h, self.head._data)

        return Tensor(jnp.stack([one(t) for t in ids]))

    # ---- what the serving engine takes ------------------------------------
    def serving_plan(self, kernels=False):
        """:func:`serving_plan` of this model's leaves."""
        return serving_plan(self.config, self._hand_over_weights, kernels)

    def _hand_over_weights(self):
        """(key, array) leaf by leaf: the top three, then each of a layer's
        leaves stacked ``[layers, ...]``. With ``hand_over`` a parameter is
        emptied as it is read, so at no time is more than ONE stacked leaf
        on the device twice."""
        def take(param):
            data = param._data
            if self.hand_over:
                param._data = None
            return data

        for name in ("embed", "norm", "head"):
            yield name, take(self._parameters[name])
        for name in layer_leaves(self.config):
            parts = [take(l._parameters[name]) for l in self.layers]
            stacked = stack_leaves(parts, (len(parts),) + parts[0].shape)
            del parts
            yield name, stacked


def serving_plan(c, weights, kernels=False):
    """An ``sdar_moe`` model as the serving engine runs it
    (``serving_plan.py``): one kind of layer that keeps pages, the leaves
    stacked ``[layers, ...]`` under their own names, and what says that it
    generates by blocks. ``weights``: () -> iterator of (key, array).
    ``kernels``: the grouped products go through the Pallas ``moe_gmm``
    (one device), else ``ragged_dot``."""
    gmm = moe_gmm if kernels else moe_gmm_ref
    kind = LayerKind(
        cache="pages", keys=tuple(layer_leaves(c)),
        first=lambda wl, x, pos: attn_qkv(wl, x, pos, c),
        second=lambda wl, x, att, live: experts(
            wl, attn_out(wl, x, att), live, c, gmm, wl["l"]),
        whole=_WHOLE)
    return ServingPlan(
        kinds={"block": kind}, period=(("block", c.num_hidden_layers),),
        periods=None, weights=weights,
        specs=lambda pp, mp, ep=None: _specs(c, pp, mp, ep),
        nh=c.num_attention_heads, kvh=c.num_key_value_heads, D=c.head_dim,
        counts=len(ROUTING_COUNTS), block=c.block_length,
        mask_token=c.mask_token_id, denoising_steps=c.denoising_steps,
        unmask=unmask_rule(c),
        early_exit=c.remasking == "low_confidence_dynamic")


def _specs(c, pp, mp, ep):
    """PartitionSpecs of the stacked leaves: layers over ``pp``, the
    experts over ``ep``, head and ffn dims over ``mp`` (columns of what
    fans out, rows of what comes back), the rest whole."""
    out = {"embed": P(), "norm": P(), "head": P(None, mp)}
    col, row = (None, mp), (mp, None)
    tails = {"wq": col, "wk": col, "wv": col, "wo": row,
             "wg": (ep, None, mp), "wu": (ep, None, mp), "wd": (ep, mp, None)}
    for name, (shape, _) in layer_leaves(c).items():
        out[name] = P(pp, *tails.get(name, (None,) * len(shape)))
    return out
