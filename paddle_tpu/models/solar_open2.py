"""Solar-Open2 family (``model_type`` ``solar_open2``): delta-rule linear
attention (Kimi Delta Attention, arXiv:2510.26692) in three layers of four,
a softmax GQA layer with no positional term and an output gate in the
fourth, and in every layer a sparse expert layer (DeepSeek-V3 routing:
sigmoid scores, a correction bias for the choice, top-k renormalised) beside
a shared expert. Serving only: a full-sequence ``forward`` for tests and the
:class:`~.serving_plan.ServingPlan` the engine runs; no ``generate`` and no
training path.

Block, every layer: ``x += mixer(rms_norm(x)); x += experts(rms_norm(x))``.

What the published ``config.json`` does not give follows the families whose
keys it uses, as ``bench/configs/solar-open2-250b.json`` lists under
``assumed``: the gate of the GQA layer is its own ``[hidden, heads x dim]``
projection (no q/k norm); the decay and the output gate of the KDA layer are
low-rank (``hidden -> head_dim -> heads x dim``, what ``kda_use_full_proj:
false`` names), its convolutions have no bias, q and k are L2-normalised a
head, ``beta`` is doubled (``kda_allow_neg_eigval``), the state is float32;
the shared expert is ``n_shared_experts x moe_intermediate_size`` wide.

**A chip's share of the experts.** The router keeps its published width
(``router_width``) and its top-k; this program holds ``experts_held`` of them
from ``expert_offset`` on and computes, for every token, the part of the sum
that ITS experts give plus the shared expert; what the others would add is
left out. On one device nothing is exchanged; under a mesh the expert axis
is named in the specs (``ep``).

The layers on raw arrays (``gqa_qkv`` / ``gqa_out``, ``kda_pre`` /
``kda_post``, ``experts``) are what both ``forward`` and the engine's
programs are made of.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.rng import next_key
from ..core.tensor import Parameter, Tensor
from ..nn.layer.layers import Layer
from ..ops.pallas.kda import kda_recurrence
from ..ops.pallas.moe_gmm import moe_gmm, moe_gmm_ref
from .dropless import ROUTING_COUNTS, routed_experts, routing_counts
from .llama import rms_norm
from .serving_plan import LayerKind, ServingPlan, stack_leaves

__all__ = ["SolarOpen2Config", "SolarOpen2ForCausalLM", "ROUTING_COUNTS"]


class SolarOpen2Config:
    def __init__(self, vocab_size=196608, hidden_size=4096,
                 num_hidden_layers=48, num_attention_heads=64,
                 num_key_value_heads=8, head_dim=128,
                 linear_num_heads=64, linear_head_dim=128,
                 short_conv_kernel_size=4, kda_rank=None,
                 moe_intermediate_size=1280, router_width=320,
                 experts_held=None, expert_offset=0, n_shared_experts=1,
                 num_experts_per_tok=8, norm_topk_prob=True,
                 routed_scaling_factor=1.0, rms_norm_eps=1e-5,
                 gqa_interval=3, gqa_layers=None, use_rope=False,
                 use_gqa_gate=True, kda_allow_neg_eigval=True,
                 first_k_dense_replace=0, tie_word_embeddings=False,
                 initializer_range=0.02, max_position_embeddings=1048576):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.linear_num_heads = linear_num_heads
        self.linear_head_dim = linear_head_dim
        self.short_conv_kernel_size = short_conv_kernel_size
        self.kda_rank = linear_head_dim if kda_rank is None else kda_rank
        self.moe_intermediate_size = moe_intermediate_size
        self.router_width = router_width
        self.experts_held = (router_width if experts_held is None
                             else experts_held)
        self.expert_offset = expert_offset
        self.n_shared_experts = n_shared_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.norm_topk_prob = norm_topk_prob
        self.routed_scaling_factor = routed_scaling_factor
        self.rms_norm_eps = rms_norm_eps
        self.gqa_interval = gqa_interval
        period = gqa_interval + 1
        self.gqa_layers = (list(range(0, num_hidden_layers, period))
                           if gqa_layers is None else list(gqa_layers))
        self.initializer_range = initializer_range
        self.max_position_embeddings = max_position_embeddings
        if num_hidden_layers % period:
            raise ValueError(
                f"num_hidden_layers={num_hidden_layers} is not whole periods "
                f"of one GQA layer and gqa_interval={gqa_interval} KDA layers")
        if self.gqa_layers != list(range(0, num_hidden_layers, period)):
            raise ValueError(
                f"gqa_layers={self.gqa_layers} is not every "
                f"{period}th layer from 0 (gqa_interval={gqa_interval})")
        if not 0 <= expert_offset <= expert_offset + self.experts_held \
                <= router_width:
            raise ValueError(
                f"experts [{expert_offset}, {expert_offset} + "
                f"{self.experts_held}) are not among the router's "
                f"{router_width}")
        for name, want, got in (("use_rope", False, use_rope),
                                ("use_gqa_gate", True, use_gqa_gate),
                                ("first_k_dense_replace", 0,
                                 first_k_dense_replace),
                                ("tie_word_embeddings", False,
                                 tie_word_embeddings)):
            if got != want:
                raise NotImplementedError(
                    f"solar_open2 is implemented for {name}={want}, the "
                    f"published value; got {got}")
        self.kda_allow_neg_eigval = bool(kda_allow_neg_eigval)

    @property
    def periods(self):
        return self.num_hidden_layers // (self.gqa_interval + 1)

    @property
    def conv_channels(self):
        return 3 * self.linear_num_heads * self.linear_head_dim

    @classmethod
    def tiny(cls, **kw):
        """The rehearsal size of ``bench/rehearsal/solar-tiny.json``."""
        for k, v in dict(vocab_size=256, hidden_size=64, num_hidden_layers=4,
                         num_attention_heads=4, num_key_value_heads=2,
                         head_dim=16, linear_num_heads=4, linear_head_dim=16,
                         moe_intermediate_size=32, router_width=16,
                         experts_held=4, num_experts_per_tok=2,
                         max_position_embeddings=256).items():
            kw.setdefault(k, v)
        return cls(**kw)


# ---- one layer's leaves ---------------------------------------------------
# name -> (shape, how it is drawn); matrices are [in, out] as ``x @ w`` reads

def _expert_leaves(c):
    h, f, e = c.hidden_size, c.moe_intermediate_size, c.experts_held
    fs = c.n_shared_experts * f
    return {"ln2": ((h,), "gain"),
            "router": ((h, c.router_width), "matrix"),
            "router_bias": ((c.router_width,), "bias"),
            "wg": ((e, h, f), "matrix"), "wu": ((e, h, f), "matrix"),
            "wd": ((e, f, h), "matrix"),
            "sg": ((h, fs), "matrix"), "su": ((h, fs), "matrix"),
            "sd": ((fs, h), "matrix")}


def layer_leaves(c, kind):
    """The leaves of one ``kind`` (``"gqa"`` | ``"kda"``) layer."""
    h = c.hidden_size
    if kind == "gqa":
        nq = c.num_attention_heads * c.head_dim
        nkv = c.num_key_value_heads * c.head_dim
        mixer = {"wq": ((h, nq), "matrix"), "wk": ((h, nkv), "matrix"),
                 "wv": ((h, nkv), "matrix"), "wgate": ((h, nq), "matrix"),
                 "wo": ((nq, h), "matrix")}
    else:
        hl, d, r = c.linear_num_heads, c.linear_head_dim, c.kda_rank
        mixer = {"wqkv": ((h, 3 * hl * d), "matrix"),
                 "conv": ((c.short_conv_kernel_size, 3 * hl * d), "conv"),
                 "f_down": ((h, r), "matrix"), "f_up": ((r, hl * d), "matrix"),
                 "A_log": ((hl,), "A_log"), "dt_bias": ((hl * d,), "dt_bias"),
                 "g_down": ((h, r), "matrix"), "g_up": ((r, hl * d), "matrix"),
                 "w_beta": ((h, hl), "matrix"), "o_norm": ((d,), "gain"),
                 "wo": ((hl * d, h), "matrix")}
    return {"ln1": ((h,), "gain"), **mixer, **_expert_leaves(c)}


def _draw(key, shape, how, c):
    if how == "A_log":
        return jnp.log(jax.random.uniform(key, shape, minval=1.0, maxval=16.0))
    if how == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, minval=math.log(1e-3),
                                        maxval=math.log(0.1)))
        return dt + jnp.log(-jnp.expm1(-dt))        # the inverse of softplus
    x = jax.random.normal(key, shape, jnp.float32)
    if how == "gain":
        return 1.0 + 0.05 * x
    return x * {"matrix": c.initializer_range, "bias": 0.01,
                "conv": 1.0 / math.sqrt(shape[0])}[how]


# ---- the layers on raw arrays ---------------------------------------------

def _mm32(x, w):
    """``x @ w`` accumulated and returned in float32 (operands as stored)."""
    return jnp.matmul(x, w, preferred_element_type=jnp.float32)


def gqa_qkv(p, x, c):
    """The GQA layer up to its attention: norm and the three projections;
    no positional term. x [B, H] -> q [B, nh, D], k and v [B, kvh, D]."""
    nh, kvh, d = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    h = rms_norm(x, p["ln1"], c.rms_norm_eps)
    return ((h @ p["wq"]).reshape(-1, nh, d), (h @ p["wk"]).reshape(-1, kvh, d),
            (h @ p["wv"]).reshape(-1, kvh, d))


def gqa_out(p, x, att, c):
    """After the attention (att [B, nh, D]): the sigmoid gate of the
    layer's own input over every attention output, the output projection,
    the residual."""
    h = rms_norm(x, p["ln1"], c.rms_norm_eps)
    gate = jax.nn.sigmoid(_mm32(h, p["wgate"]))
    a = att.reshape(x.shape[0], -1).astype(jnp.float32) * gate
    return x + a.astype(x.dtype) @ p["wo"]


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def kda_pre(p, x, tail, n_valid, c):
    """The KDA layer up to its recurrence, on rows ``x [..., T, H]`` at
    consecutive positions that follow ``tail [..., taps - 1, C]`` (the last
    inputs of the three convolutions, side by side). Returns ``(q, k, g
    [..., T, heads, D], v, beta [..., T, heads])`` in float32 and the tail
    after the first ``n_valid`` rows (``None``: all of them)."""
    hl, d = c.linear_num_heads, c.linear_head_dim
    taps = c.short_conv_kernel_size
    T = x.shape[-2]
    h = rms_norm(x, p["ln1"], c.rms_norm_eps)
    xin = jnp.concatenate([tail, h @ p["wqkv"]], axis=-2)   # [..., T + taps - 1, C]
    w = p["conv"].astype(jnp.float32)
    y = sum(w[j] * xin[..., j:j + T, :].astype(jnp.float32)
            for j in range(taps))
    tail = jax.lax.dynamic_slice_in_dim(
        xin, T if n_valid is None else n_valid, taps - 1, axis=-2)
    q, k, v = (a.reshape(a.shape[:-1] + (hl, d))
               for a in jnp.split(jax.nn.silu(y), 3, axis=-1))
    f = _mm32(_mm32(h, p["f_down"]).astype(x.dtype), p["f_up"])
    f = f + p["dt_bias"].astype(jnp.float32)
    g = (-jnp.exp(p["A_log"].astype(jnp.float32))[:, None]
         * jax.nn.softplus(f.reshape(f.shape[:-1] + (hl, d))))
    beta = jax.nn.sigmoid(_mm32(h, p["w_beta"]))
    if c.kda_allow_neg_eigval:
        beta = 2.0 * beta
    return (_l2norm(q) * d ** -0.5, _l2norm(k), v, g, beta), tail


def kda_post(p, x, o, c):
    """After the recurrence (``o [..., heads, D]`` float32): a norm a head,
    the low-rank sigmoid gate of the layer's input, the output projection,
    the residual."""
    h = rms_norm(x, p["ln1"], c.rms_norm_eps)
    gate = jax.nn.sigmoid(
        _mm32(_mm32(h, p["g_down"]).astype(x.dtype), p["g_up"]))
    o = (o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                           + c.rms_norm_eps)
         * p["o_norm"].astype(jnp.float32))
    return x + (o.reshape(gate.shape) * gate).astype(x.dtype) @ p["wo"]


def experts(p, x, live, c, gmm=moe_gmm_ref, l=0):
    """The expert layer on rows ``x [N, H]``; ``live [N]`` > 0 marks the
    rows that are routed (an idle slot and a chunk's padding are not).
    Returns ``x + shared(h) + sum over the chosen HELD experts`` and the
    routing counts (``ROUTING_COUNTS``, int32).

    The router is this family's (sigmoid scores, a correction bias for the
    choice only, the chosen renormalised); the sum over the chosen experts
    held here and the counts are ``models/dropless.py``'s, which every
    sparse model of the engine shares (``l``: this layer's index where the
    experts' matrices come as the whole stack of like layers)."""
    h = rms_norm(x, p["ln2"], c.rms_norm_eps)
    score = jax.nn.sigmoid(_mm32(h, p["router"]))                # [N, R]
    _, chosen = jax.lax.top_k(
        score + p["router_bias"].astype(jnp.float32),
        c.num_experts_per_tok)
    weight = jnp.take_along_axis(score, chosen, axis=1)
    if c.norm_topk_prob:
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    weight = weight * c.routed_scaling_factor
    routed, held, sizes = routed_experts(
        p, h, chosen, weight, live, c.experts_held, c.expert_offset, gmm, l,
        x.dtype)
    shared = _mm32((jax.nn.silu(_mm32(h, p["sg"]))
                    * _mm32(h, p["su"])).astype(x.dtype), p["sd"])
    counts = routing_counts(live, held, sizes)
    return x + (shared + routed).astype(x.dtype), counts


def _causal_attention(q, k, v):
    """Dense causal softmax attention of one sequence in float32 (the
    engine's is paged): q [T, nh, D], k and v [T, kvh, D]."""
    t, nh, d = q.shape
    group = nh // k.shape[1]
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) * d ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)


# ---- the model --------------------------------------------------------------

class _SolarLayer(Layer):
    """One layer's leaves as parameters."""

    def __init__(self, kind):
        super().__init__()
        self.kind = kind

    def leaves(self):
        return {n: p._data for n, p in self._parameters.items()}


class SolarOpen2ForCausalLM(Layer):
    """``leaves``: ``{"embed", "norm", "head", "layers": [{name: array}]}``
    to adopt as the parameters (no copy; names and shapes as
    :func:`layer_leaves` gives them); without, they are drawn from
    ``paddle.seed`` in the default dtype.

    ``hand_over``: the engine built from this model TAKES the parameters -
    each is let go of as the engine stacks it, and the model holds none
    afterwards. For a model of which the device cannot hold two copies."""

    def __init__(self, config: SolarOpen2Config, leaves=None,
                 hand_over=False):
        super().__init__()
        self.config = c = config
        self.hand_over = bool(hand_over)
        kinds = ["gqa" if i in c.gqa_layers else "kda"
                 for i in range(c.num_hidden_layers)]
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
        for i, kind in enumerate(kinds):
            layer = _SolarLayer(kind)
            given = None if leaves is None else leaves["layers"][i]
            for name, (shape, how) in layer_leaves(c, kind).items():
                layer.add_parameter(name, param(
                    f"layers[{i}].{name}", shape, how,
                    None if given is None else given[name]))
            self.add_sublayer(f"layer_{i}", layer)
            self.layers.append(layer)

    def forward(self, input_ids):
        """Logits ``[B, T, vocab]`` (float32) of whole sequences, no cache:
        the layers the engine runs, with dense causal attention and the
        recurrence from a zero state."""
        c = self.config
        ids = input_ids._data if isinstance(input_ids, Tensor) else input_ids
        ids = jnp.asarray(ids)
        if ids.ndim == 1:
            ids = ids[None]
        live = jnp.ones((ids.shape[1],), jnp.int32)
        tail = jnp.zeros((c.short_conv_kernel_size - 1, c.conv_channels),
                         self.embed._data.dtype)
        S0 = jnp.zeros((c.linear_num_heads, c.linear_head_dim,
                        c.linear_head_dim), jnp.float32)

        def one(tokens):
            x = self.embed._data[tokens]
            for layer in self.layers:
                p = layer.leaves()
                if layer.kind == "gqa":
                    x = gqa_out(p, x, _causal_attention(*gqa_qkv(p, x, c)), c)
                else:
                    qkvgb, _ = kda_pre(p, x, tail, None, c)
                    x = kda_post(p, x, kda_recurrence(S0, *qkvgb)[0], c)
                x, _ = experts(p, x, live, c)
            h = rms_norm(x, self.norm._data, c.rms_norm_eps)
            return _mm32(h, self.head._data)

        return Tensor(jnp.stack([one(t) for t in ids]))

    # ---- what the serving engine takes ------------------------------------
    def serving_plan(self, kernels=False):
        """:func:`serving_plan` of this model's leaves."""
        return serving_plan(self.config, self._hand_over_weights, kernels)

    def _hand_over_weights(self):
        """(key, array) leaf by leaf: the top three, then each kind's
        leaves stacked ``[periods, n, ...]``. With ``hand_over`` a parameter
        is emptied as it is read, so at no time is more than ONE stacked
        leaf on the device twice."""
        c = self.config

        def take(param):
            data = param._data
            if self.hand_over:
                param._data = None
            return data

        for name in ("embed", "norm", "head"):
            yield name, take(self._parameters[name])
        for kind, n in (("gqa", 1), ("kda", c.gqa_interval)):
            layers = [l for l in self.layers if l.kind == kind]
            for name in layer_leaves(c, kind):
                parts = [take(l._parameters[name]) for l in layers]
                stacked = stack_leaves(parts, (c.periods, n) + parts[0].shape)
                del parts
                yield f"{kind}.{name}", stacked


def serving_plan(c, weights, kernels=False):
    """A ``solar_open2`` model as the serving engine runs it
    (``serving_plan.py``): the two kinds of layer in their period (one GQA
    layer, then ``gqa_interval`` KDA layers), each followed by the expert
    layer, the leaves stacked ``[periods, n, ...]`` under ``<kind>.<leaf>``.
    ``weights``: () -> iterator of (key, array). ``kernels``: the grouped
    products go through the Pallas ``moe_gmm`` (one device), else
    ``ragged_dot``."""
    gmm = moe_gmm if kernels else moe_gmm_ref

    def leaves_of(kind, wl):
        return {k[len(kind) + 1:]: v for k, v in wl.items()
                if k.startswith(kind + ".")}

    def second(kind, mixer_out):
        def f(wl, x, a, live):
            p = leaves_of(kind, wl)
            return experts(p, mixer_out(p, x, a, c), live, c, gmm, wl["l"])
        return f

    def whole(kind):        # what moe_gmm reads: never sliced out of the stack
        return tuple(f"{kind}.{n}" for n in ("wg", "wu", "wd"))

    kinds = {
        "gqa": LayerKind(
            cache="pages", keys=_keys(c, "gqa"),
            first=lambda wl, x, pos: gqa_qkv(leaves_of("gqa", wl), x, c),
            second=second("gqa", gqa_out), whole=whole("gqa")),
        "kda": LayerKind(
            cache="state", keys=_keys(c, "kda"),
            first=lambda wl, x, tail, n_valid: kda_pre(
                leaves_of("kda", wl), x, tail, n_valid, c),
            second=second("kda", kda_post), whole=whole("kda"),
            tail=(c.short_conv_kernel_size - 1, c.conv_channels)),
    }
    return ServingPlan(
        kinds=kinds, period=(("gqa", 1), ("kda", c.gqa_interval)),
        periods=c.periods, weights=weights,
        specs=lambda pp, mp, ep=None: _specs(c, pp, mp, ep),
        nh=c.num_attention_heads, kvh=c.num_key_value_heads,
        D=c.head_dim, state_heads=c.linear_num_heads,
        state_dk=c.linear_head_dim, state_dv=c.linear_head_dim,
        counts=len(ROUTING_COUNTS))


def _keys(c, kind):
    return tuple(f"{kind}.{name}" for name in layer_leaves(c, kind))


def _specs(c, pp, mp, ep):
    """PartitionSpecs of the stacked leaves: periods over ``pp``, the
    experts over ``ep``, head and ffn dims over ``mp`` (columns of what
    fans out, rows of what comes back), the rest whole."""
    out = {"embed": P(), "norm": P(), "head": P(None, mp)}
    col, row = (None, mp), (mp, None)
    tails = {"wq": col, "wk": col, "wv": col, "wgate": col, "wo": row,
             "wqkv": col, "conv": col, "f_up": col, "g_up": col,
             "w_beta": col, "dt_bias": (mp,), "A_log": (mp,),
             "sg": col, "su": col, "sd": row,
             "wg": (ep, None, mp), "wu": (ep, None, mp), "wd": (ep, mp, None)}
    for kind in ("gqa", "kda"):
        for name, (shape, _) in layer_leaves(c, kind).items():
            tail = tails.get(name, (None,) * len(shape))
            out[f"{kind}.{name}"] = P(pp, None, *tail)
    return out
