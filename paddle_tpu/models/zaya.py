"""ZAYA1 family (``model_type`` ``zaya``): every layer an attention sublayer
of Compressed Convolutional Attention (CCA, arXiv:2510.04476) - attention in
a compressed latent, with two causal convolutions over time mixing its
queries and keys and a value half shifted by one token - and a sparse expert
sublayer behind an MLP router whose state is carried from layer to layer
(EDA), choosing one of ``num_experts`` experts or none (MoD: the skip), each
sublayer merged into a residual that is scaled too. The head is the
embedding's transpose. Serving only: a full-sequence ``forward`` for tests
and the :class:`~.serving_plan.ServingPlan` the engine runs; no
``generate`` and no training path.

Layer ``l``, for rows ``x`` at positions ``t`` (H hidden, d head size, nh q
heads, kvh KV heads, g = nh / kvh)::

    h = rms_norm(x);  qt = h Wq [nh d];  kt = h Wk [kvh d]
    v_t = [h_t Wv1 ; h_{t-1} Wv2]                value shift, h_{-1} = 0
    c1_t = a_0 * [qt;kt]_t + a_1 * [qt;kt]_{t-1}  depthwise, kernel 2
    c2_t = c1_t B_0,g + c1_{t-1} B_1,g            a d x d matrix a head g
    q = c2_q + (qt + repeat_g(kt)) / 2;  k = c2_k + (mean_g(qt) + kt) / 2
    q = sqrt(d) tau_h q / |q|;  k = sqrt(d) k / |k|;  partial RoPE (the
        first d * partial_rotary_factor channels, neox halves)
    x = s_res1 * x + s_out1 * (softmax(q k^T / sqrt(d)) v  Wo)
    h = rms_norm(x);  rh_l = h Wdown + gamma_l * rh_{l-1}      (rh_0 = 0)
    z = W3 gelu(W2 gelu(W1 rms_norm(rh_l)))                    float32
    c = argmax(z + b);  p = softmax(z)[c]                      E + 1 outputs
    x = s_res2 * x + s_out2 * (p expert_c(h) if c < E else 0)

What the published ``config.json`` does not give follows the CCA paper and
the family's conventions, as ``bench/configs/zaya1-8b.json`` lists under
``assumed``. The positions before a prompt's start are zero for both
convolutions and the value shift.

**What the engine keeps.** Each layer's K and V go to the pages like any
attention layer's. Its next token also needs, of the token before, the
latent q and k before the convolutions, their first convolution's output
and the shifted value half: the layer's TAIL, one row of ``2 (nh + kvh) d +
kvh / 2 d`` channels a slot (``LayerKind.tail``). The router state ``rh``
rides beside the residual (``plan.carry``). A choice of no expert is the
router's last output, which no expert holds (``models/dropless.py``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.rng import next_key
from ..core.tensor import Parameter, Tensor
from ..nn.layer.layers import Layer
from ..ops.pallas.moe_gmm import moe_gmm, moe_gmm_ref
from .dropless import ROUTING_COUNTS_SKIP, routed_experts, routing_counts
from .llama import rms_norm
from .serving_plan import LayerKind, ServingPlan, stack_leaves

__all__ = ["ZAYAConfig", "ZAYAForCausalLM"]

_L2_EPS = 1e-6      # under the square root of a head's squared norm


class ZAYAConfig:
    def __init__(self, vocab_size=262272, hidden_size=2048,
                 num_hidden_layers=40, num_attention_heads=8,
                 num_key_value_heads=2, head_dim=128,
                 moe_intermediate_size=2048, num_experts=16,
                 num_experts_per_tok=1, router_hidden_size=256,
                 cca_time0=2, cca_time1=2, partial_rotary_factor=0.5,
                 rope_theta=5e6, rms_norm_eps=1e-5, tie_word_embeddings=True,
                 attention_bias=False, lm_head_bias=False,
                 sliding_window=None, layer_types=None,
                 initializer_range=0.02, max_position_embeddings=131072):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.moe_intermediate_size = moe_intermediate_size
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.router_hidden_size = router_hidden_size
        self.cca_time0 = cca_time0
        self.cca_time1 = cca_time1
        self.partial_rotary_factor = partial_rotary_factor
        self.rope_theta = rope_theta
        self.rms_norm_eps = rms_norm_eps
        self.initializer_range = initializer_range
        self.max_position_embeddings = max_position_embeddings
        self.rotary_dim = int(head_dim * partial_rotary_factor)
        if num_attention_heads % num_key_value_heads or num_key_value_heads % 2:
            raise ValueError(
                f"{num_attention_heads} q heads over {num_key_value_heads} KV "
                f"heads: the q heads are whole groups a KV head, and the KV "
                f"heads split in two (the token's own, the shifted)")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= head_dim:
            raise ValueError(f"partial_rotary_factor={partial_rotary_factor} "
                             f"gives {self.rotary_dim} rotary channels of "
                             f"{head_dim}: not an even count")
        kinds = list(layer_types or ["hybrid"] * num_hidden_layers)
        for name, want, got in (
                ("num_experts_per_tok", 1, num_experts_per_tok),
                ("cca_time0", 2, cca_time0), ("cca_time1", 2, cca_time1),
                ("tie_word_embeddings", True, tie_word_embeddings),
                ("attention_bias", False, attention_bias),
                ("lm_head_bias", False, lm_head_bias),
                ("sliding_window", None, sliding_window),
                ("layer_types", ["hybrid"] * num_hidden_layers,
                 kinds[:num_hidden_layers])):
            if got != want:
                raise NotImplementedError(
                    f"zaya is implemented for {name}={want}, the published "
                    f"value; got {got}")

    @property
    def conv_channels(self):
        """Latent q and k side by side: what the convolutions mix."""
        return (self.num_attention_heads + self.num_key_value_heads) \
            * self.head_dim

    @property
    def value_half(self):
        """Channels of the value half from one token (the other is shifted)."""
        return self.num_key_value_heads // 2 * self.head_dim

    @property
    def tail_channels(self):
        """A slot's tail: the token's latent q and k, their first
        convolution's output, its shifted value half."""
        return 2 * self.conv_channels + self.value_half

    @classmethod
    def tiny(cls, **kw):
        """The rehearsal size of ``bench/rehearsal/zaya-tiny.json``."""
        for k, v in dict(vocab_size=256, hidden_size=64, num_hidden_layers=3,
                         num_attention_heads=4, num_key_value_heads=2,
                         head_dim=16, moe_intermediate_size=32,
                         num_experts=4, router_hidden_size=16,
                         max_position_embeddings=256).items():
            kw.setdefault(k, v)
        return cls(**kw)


# ---- one layer's leaves ---------------------------------------------------
# name -> (shape, how it is drawn); matrices are [in, out] as ``x @ w`` reads

def layer_leaves(c):
    h, d, f, e = (c.hidden_size, c.head_dim, c.moe_intermediate_size,
                  c.num_experts)
    nq, nkv, r = (c.num_attention_heads * d, c.num_key_value_heads * d,
                  c.router_hidden_size)
    groups = c.num_attention_heads + c.num_key_value_heads
    return {"ln1": ((h,), "gain"), "wq": ((h, nq), "matrix"),
            "wk": ((h, nkv), "matrix"), "wv1": ((h, c.value_half), "matrix"),
            "wv2": ((h, c.value_half), "matrix"),
            "conv1": ((c.cca_time0, c.conv_channels), "taps"),
            "conv2": ((c.cca_time1, groups, d, d), "taps"),
            "temp": ((c.num_attention_heads,), "gain"),
            "wo": ((nq, h), "matrix"), "res1": ((h,), "gain"),
            "out1": ((h,), "gain"), "ln2": ((h,), "gain"),
            "r_down": ((h, r), "matrix"), "eda": ((r,), "gain"),
            "r_norm": ((r,), "gain"), "r1": ((r, r), "fan_in"),
            "r2": ((r, r), "fan_in"), "r3": ((r, e + 1), "fan_in"),
            "r_bias": ((e + 1,), "bias"),
            "wg": ((e, h, f), "matrix"), "wu": ((e, h, f), "matrix"),
            "wd": ((e, f, h), "matrix"), "res2": ((h,), "gain"),
            "out2": ((h,), "gain")}


_WHOLE = ("wg", "wu", "wd")     # what moe_gmm reads: never sliced out


def _draw(key, shape, how, c):
    x = jax.random.normal(key, shape, jnp.float32)
    if how == "gain":
        return 1.0 + 0.05 * x
    fan_in = {"taps": math.prod(shape[:1] + shape[2:3]),
              "fan_in": shape[0]}.get(how)
    if fan_in:
        return x * fan_in ** -0.5
    return x * {"matrix": c.initializer_range, "bias": 0.01}[how]


# ---- the layer on raw arrays ----------------------------------------------

def _mm32(x, w):
    """``x @ w`` accumulated and returned in float32 (operands as stored)."""
    return jnp.matmul(x, w, preferred_element_type=jnp.float32)


def _unit(a):
    return a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + _L2_EPS)


def _partial_rope(x, pos, c):
    """neox halves on each head's first ``rotary_dim`` channels: x [..., T,
    heads, d] float32 at positions pos [..., T]."""
    rd = c.rotary_dim
    inv = 1.0 / c.rope_theta ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd)
    ang = pos.astype(jnp.float32)[..., None] * inv              # [..., T, rd/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[..., None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[..., None, :]
    r, rest = x[..., :rd], x[..., rd:]
    rot = jnp.concatenate([-r[..., rd // 2:], r[..., :rd // 2]], axis=-1)
    return jnp.concatenate([r * cos + rot * sin, rest], axis=-1)


def cca_qkv(p, xs, pos, tail, n_valid, c):
    """CCA up to its attention, on rows ``x [..., T, H]`` (``xs``: ``(x,
    router state)``, which is not read here) at consecutive positions ``pos
    [..., T]`` that follow ``tail [..., 1, tail_channels]``. Returns ``((q
    [..., T, nh, d], k, v [..., T, kvh, d]), tail)``: the tail after the
    first ``n_valid`` rows (``None``: all of them). The convolutions'
    inputs keep the rows' dtype, what the tail keeps, so that a chunk's
    boundary moves nothing."""
    x = xs[0]
    nh, kvh, d = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    C, T, dt = c.conv_channels, x.shape[-2], x.dtype
    f32 = jnp.float32
    h = rms_norm(x, p["ln1"], c.rms_norm_eps)
    lat = jnp.concatenate([h @ p["wq"], h @ p["wk"]], axis=-1)   # [..., T, C]
    lat_all = jnp.concatenate([tail[..., :C], lat], axis=-2)     # [..., T+1, C]
    a = p["conv1"].astype(f32)
    c1 = (a[0] * lat_all[..., 1:, :].astype(f32)
          + a[1] * lat_all[..., :-1, :].astype(f32)).astype(dt)
    c1_all = jnp.concatenate([tail[..., C:2 * C], c1], axis=-2)

    def heads(u, n):
        return u.reshape(u.shape[:-1] + (n, d)).astype(f32)
    b = p["conv2"].astype(f32)
    c2 = (jnp.einsum("...gi,gio->...go", heads(c1_all[..., 1:, :], nh + kvh),
                     b[0])
          + jnp.einsum("...gi,gio->...go", heads(c1_all[..., :-1, :], nh + kvh),
                       b[1]))
    qt, kt = heads(lat[..., :nh * d], nh), heads(lat[..., nh * d:], kvh)
    g = nh // kvh
    q = c2[..., :nh, :] + (qt + jnp.repeat(kt, g, axis=-2)) / 2
    k = c2[..., nh:, :] + (qt.reshape(qt.shape[:-2] + (kvh, g, d)).mean(-2)
                           + kt) / 2
    scale = math.sqrt(d)
    q = scale * p["temp"].astype(f32)[:, None] * _unit(q)
    k = scale * _unit(k)
    q, k = _partial_rope(q, pos, c), _partial_rope(k, pos, c)
    shifted = jnp.concatenate([tail[..., 2 * C:], h @ p["wv2"]], axis=-2)
    v = jnp.concatenate([h @ p["wv1"], shifted[..., :-1, :]], axis=-1)
    rows = jnp.concatenate([lat_all, c1_all, shifted], axis=-1)  # [..., T+1, .]
    tail = jax.lax.dynamic_slice_in_dim(
        rows, T if n_valid is None else n_valid, 1, axis=-2)
    return (q.astype(dt), k.astype(dt), heads(v, kvh).astype(dt)), tail


def _merge(res, out, x, y):
    """The scaled residual merge: ``res * x + out * y``."""
    f32 = jnp.float32
    return (res.astype(f32) * x.astype(f32) + out.astype(f32) * y).astype(
        x.dtype)


def cca_out(p, x, att):
    """After the attention (att [N, nh, D]): the output projection and the
    scaled residual merge."""
    return _merge(p["res1"], p["out1"], x,
                  _mm32(att.reshape(x.shape[0], -1).astype(x.dtype), p["wo"]))


def route(p, h, rh, c):
    """The router on normed rows ``h [N, H]`` and the router state of the
    layer before ``rh [N, R]`` float32: this layer's state, and its choice
    ``[N]`` (``num_experts``: none) with the choice's probability ``[N]``,
    all float32 but the choice."""
    f32 = jnp.float32
    rh = _mm32(h, p["r_down"]) + p["eda"].astype(f32) * rh
    u = rms_norm(rh, p["r_norm"], c.rms_norm_eps)

    def mm(u, w):               # a choice rides on it: float32 in full
        return jnp.matmul(u, p[w].astype(f32),
                          precision=jax.lax.Precision.HIGHEST)
    u = jax.nn.gelu(mm(jax.nn.gelu(mm(u, "r1"), approximate=False), "r2"),
                    approximate=False)
    z = mm(u, "r3")
    choice = jnp.argmax(z + p["r_bias"].astype(f32), axis=-1).astype(jnp.int32)
    prob = jnp.take_along_axis(jax.nn.softmax(z, axis=-1), choice[:, None],
                               axis=1)[:, 0]
    return rh, choice, prob


def experts(p, xs, live, c, gmm=moe_gmm_ref, l=0):
    """The expert sublayer on rows ``xs = (x [N, H], rh [N, R])`` (``live
    [N]`` > 0: the rows that are routed): ``((x', rh'), counts)``, x' the
    scaled merge of the chosen expert's output (nothing where the router
    chose none) and the routing counts (``ROUTING_COUNTS_SKIP``, int32).
    ``l``: this layer's index where the experts' matrices come as the stack
    of all layers."""
    x, rh = xs
    h = rms_norm(x, p["ln2"], c.rms_norm_eps)
    rh, choice, prob = route(p, h, rh, c)
    chosen = choice[:, None]
    routed, held, sizes = routed_experts(
        p, h, chosen, prob[:, None], live, c.num_experts, 0, gmm, l, x.dtype)
    counts = routing_counts(live, held, sizes, chosen == c.num_experts)
    return (_merge(p["res2"], p["out2"], x, routed), rh), counts


def causal_attention(q, k, v):
    """Dense causal softmax attention of one sequence in float32 (the
    engine's is paged): q [T, nh, D], k and v [T, kvh, D]."""
    t, nh, d = q.shape
    group = nh // k.shape[1]
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) * d ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)


def router_start(c):
    """The router state before this program's first layer, for rows ``x [N,
    H]``: zeros (a stage that follows another would get it with ``x``)."""
    return lambda x: jnp.zeros(x.shape[:-1] + (c.router_hidden_size,),
                               jnp.float32)


# ---- the model --------------------------------------------------------------

class _ZAYALayer(Layer):
    """One layer's leaves as parameters."""

    def leaves(self):
        return {n: p._data for n, p in self._parameters.items()}


class ZAYAForCausalLM(Layer):
    """``leaves``: ``{"embed", "norm", "layers": [{name: array}]}`` to adopt
    as the parameters (no copy; names and shapes as :func:`layer_leaves`
    gives them); without, they are drawn from ``paddle.seed`` in the default
    dtype. The head is ``embed``'s transpose (``tie_word_embeddings``).

    ``hand_over``: the engine built from this model TAKES the parameters -
    each is let go of as the engine stacks it, and the model holds none
    afterwards. For a model of which the device cannot hold two copies."""

    def __init__(self, config: ZAYAConfig, leaves=None, hand_over=False):
        super().__init__()
        self.config = c = config
        self.hand_over = bool(hand_over)
        top = {"embed": ((c.vocab_size, c.hidden_size), "matrix"),
               "norm": ((c.hidden_size,), "gain")}

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
            layer = _ZAYALayer()
            given = None if leaves is None else leaves["layers"][i]
            for name, (shape, how) in layer_leaves(c).items():
                layer.add_parameter(name, param(
                    f"layers[{i}].{name}", shape, how,
                    None if given is None else given[name]))
            self.add_sublayer(f"layer_{i}", layer)
            self.layers.append(layer)

    def forward(self, input_ids):
        """Logits ``[B, T, vocab]`` (float32) of whole sequences, no cache:
        the layers the engine runs, with dense causal attention, from a
        zero tail and a zero router state."""
        c = self.config
        ids = input_ids._data if isinstance(input_ids, Tensor) else input_ids
        ids = jnp.asarray(ids)
        if ids.ndim == 1:
            ids = ids[None]
        n = ids.shape[1]
        live = jnp.ones((n,), jnp.int32)
        pos = jnp.arange(n, dtype=jnp.int32)
        embed = self.embed._data
        tail = jnp.zeros((1, c.tail_channels), embed.dtype)

        def one(tokens):
            x = embed[tokens]
            xs = (x, router_start(c)(x))
            for layer in self.layers:
                p = layer.leaves()
                (q, k, v), _ = cca_qkv(p, xs, pos, tail, None, c)
                xs = (cca_out(p, xs[0], causal_attention(q, k, v)), xs[1])
                xs, _ = experts(p, xs, live, c)
            h = rms_norm(xs[0], self.norm._data, c.rms_norm_eps)
            return jnp.einsum("th,vh->tv", h.astype(jnp.float32),
                              embed.astype(jnp.float32))

        return Tensor(jnp.stack([one(t) for t in ids]))

    # ---- what the serving engine takes ------------------------------------
    def serving_plan(self, kernels=False):
        """:func:`serving_plan` of this model's leaves."""
        return serving_plan(self.config, self._hand_over_weights, kernels)

    def _hand_over_weights(self):
        """(key, array) leaf by leaf: the embedding and the final norm, then
        each of a layer's leaves stacked ``[layers, ...]``. With
        ``hand_over`` a parameter is emptied as it is read, so at no time is
        more than ONE stacked leaf on the device twice."""
        def take(param):
            data = param._data
            if self.hand_over:
                param._data = None
            return data

        for name in ("embed", "norm"):
            yield name, take(self._parameters[name])
        for name in layer_leaves(self.config):
            parts = [take(l._parameters[name]) for l in self.layers]
            stacked = stack_leaves(parts, (len(parts),) + parts[0].shape)
            del parts
            yield name, stacked


def serving_plan(c, weights, kernels=False):
    """A ``zaya`` model as the serving engine runs it (``serving_plan.py``):
    one kind of layer that keeps pages AND a tail a slot, the router state
    carried beside the residual, the head tied to the embedding; the leaves
    stacked ``[layers, ...]`` under their own names. ``weights``: () ->
    iterator of (key, array). ``kernels``: the grouped products go through
    the Pallas ``moe_gmm`` (one device), else ``ragged_dot``."""
    gmm = moe_gmm if kernels else moe_gmm_ref
    kind = LayerKind(
        cache="pages", keys=tuple(layer_leaves(c)),
        first=lambda wl, xs, pos, tail, n_valid: cca_qkv(
            wl, xs, pos, tail, n_valid, c),
        second=lambda wl, xs, att, live: experts(
            wl, (cca_out(wl, xs[0], att), xs[1]), live, c, gmm, wl["l"]),
        whole=_WHOLE, tail=(1, c.tail_channels))
    return ServingPlan(
        kinds={"cca": kind}, period=(("cca", c.num_hidden_layers),),
        periods=None, weights=weights,
        specs=lambda pp, mp, ep=None: _specs(c, pp, mp, ep),
        nh=c.num_attention_heads, kvh=c.num_key_value_heads, D=c.head_dim,
        counts=len(ROUTING_COUNTS_SKIP), carry=router_start(c),
        tied_head=True)


def _specs(c, pp, mp, ep):
    """PartitionSpecs of the stacked leaves: layers over ``pp``, the
    experts over ``ep``, the ffn dims over ``mp``, the rest whole (the
    latent attention's convolutions mix across its heads' groups)."""
    out = {"embed": P(), "norm": P()}
    tails = {"wg": (ep, None, mp), "wu": (ep, None, mp), "wd": (ep, mp, None)}
    for name, (shape, _) in layer_leaves(c).items():
        out[name] = P(pp, *tails.get(name, (None,) * len(shape)))
    return out
