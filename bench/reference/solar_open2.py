"""Plain reference of Solar-Open2-250B's layers, cut as
``bench/configs/solar-open2-250b.json`` states.

Written from the equations (``model_type`` ``solar_open2``; what its
``config.json`` does not give follows the families whose keys it uses and is
listed in the configuration file under ``assumed``): float32 ``jax.numpy`` at
``highest`` matmul precision, no kernel, no cache, no batching, nothing
imported from the program. Every layer ``l``::

    x <- x + mixer_l(rms_norm(x));  x <- x + experts(rms_norm(x))

**GQA mixer** (``l`` in ``gqa_layers``; ``use_rope`` false, so no positional
term; ``use_gqa_gate``): ``q = W_q h`` (heads x dim), ``k, v = W_k h, W_v h``
(KV heads x dim), causal softmax attention, scale dim^-1/2, q head ``i``
reading KV head ``i // group``; ``y = W_o (a * sigmoid(W_gate h))``.

**KDA mixer** (every other layer; Kimi Delta Attention, arXiv:2510.26692),
with ``c(.)`` a causal depthwise convolution of ``short_conv_kernel_size``
taps over time followed by SiLU, a head at a time::

    q_t, k_t, v_t = c(W_q h_t), c(W_k h_t), c(W_v h_t)
    q_t, k_t L2-normalised; q_t scaled by dim^-1/2
    g_t   = -exp(A) * softplus(W_f_up W_f_down h_t + b_dt)   (a key channel; <= 0)
    beta_t = 2 sigmoid(w_beta . h_t)                          (kda_allow_neg_eigval)
    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T,  S_0 = 0
    o_t = S_t^T q_t
    y_t = W_o (rms_norm_head(o_t) * sigmoid(W_g_up W_g_down h_t))

The recurrence is a plain ``lax.scan`` over positions.

**Expert layer**: ``s = sigmoid(W_r h)`` over ``router_width`` experts; the
``num_experts_per_tok`` of largest ``s + b_corr`` are chosen (the bias enters
the choice only); ``w_e = s_e / sum_chosen s`` (``norm_topk_prob``) x
``routed_scaling_factor``; ``y = shared(h) + sum_{e chosen} w_e expert_e(h)``,
every expert ``W_d (silu(W_g h) * W_u h)``. **This chip's share**: the sum
runs over the chosen experts ``expert_offset <= e < expert_offset +
n_routed_experts`` only (the key that counts experts gives what is held
here); the router keeps its width and its top-k; the shared expert is whole.
Each held expert is upcast and applied on its own, to every position, and
weighs nothing where it was not chosen.

The weights come from :func:`init_weights`, which is also what the benchmark
feeds the program: both sides get the same bfloat16 values from ``--seed``.
``precision="int8"`` is the control of the comparison that decides
``correct``: every matrix product taken on int8 operands (weights per output
channel, activations per token, int32 accumulation) - the nearest precision
below the configuration's bfloat16.
"""
import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
_Q_BLOCK = 512      # queries whose scores are alive at once in the GQA layer


def seed_key(seed):
    """A PRNG key from any whole number (``--seed`` passes 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def sizes(cfg):
    lin = cfg["linear_attn_config"]
    return dict(
        h=cfg["hidden_size"], nh=cfg["num_attention_heads"],
        nkv=cfg["num_key_value_heads"], d=cfg["head_dim"],
        hl=lin["num_heads"], dl=lin["head_dim"],
        taps=lin["short_conv_kernel_size"],
        rank=cfg.get("kda_rank", lin["head_dim"]),
        f=cfg["moe_intermediate_size"], held=cfg["n_routed_experts"],
        width=cfg.get("router_width", cfg["n_routed_experts"]),
        offset=cfg.get("expert_offset", 0),
        fs=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        topk=cfg["num_experts_per_tok"], vocab=cfg["vocab_size"])


def leaf_shapes(cfg):
    """(top, gqa layer, kda layer): name -> (shape, kind); matrices are
    [in, out] as ``x @ w`` reads, an expert's stacked [held, in, out]."""
    z = sizes(cfg)
    h, f, e = z["h"], z["f"], z["held"]
    experts = {"ln2": ((h,), "gain"), "router": ((h, z["width"]), "matrix"),
               "router_bias": ((z["width"],), "bias"),
               "wg": ((e, h, f), "matrix"), "wu": ((e, h, f), "matrix"),
               "wd": ((e, f, h), "matrix"),
               "sg": ((h, z["fs"]), "matrix"), "su": ((h, z["fs"]), "matrix"),
               "sd": ((z["fs"], h), "matrix")}
    nq, nkv, nl = z["nh"] * z["d"], z["nkv"] * z["d"], z["hl"] * z["dl"]
    gqa = {"ln1": ((h,), "gain"), "wq": ((h, nq), "matrix"),
           "wk": ((h, nkv), "matrix"), "wv": ((h, nkv), "matrix"),
           "wgate": ((h, nq), "matrix"), "wo": ((nq, h), "matrix"), **experts}
    kda = {"ln1": ((h,), "gain"), "wq": ((h, nl), "matrix"),
           "wk": ((h, nl), "matrix"), "wv": ((h, nl), "matrix"),
           "conv_q": ((z["taps"], nl), "conv"),
           "conv_k": ((z["taps"], nl), "conv"),
           "conv_v": ((z["taps"], nl), "conv"),
           "f_down": ((h, z["rank"]), "matrix"),
           "f_up": ((z["rank"], nl), "matrix"),
           "A_log": ((z["hl"],), "A_log"), "dt_bias": ((nl,), "dt_bias"),
           "g_down": ((h, z["rank"]), "matrix"),
           "g_up": ((z["rank"], nl), "matrix"),
           "w_beta": ((h, z["hl"]), "matrix"), "o_norm": ((z["dl"],), "gain"),
           "wo": ((nl, h), "matrix"), **experts}
    top = {"embed": ((z["vocab"], h), "matrix"), "norm": ((h,), "gain"),
           "head": ((h, z["vocab"]), "matrix")}
    return top, gqa, kda


def layer_kinds(cfg):
    return ["gqa" if i in cfg["gqa_layers"] else "kda"
            for i in range(cfg["num_hidden_layers"])]


def init_weights(cfg, seed):
    """Every leaf from the seed in ONE jitted call, in the served dtype.

    Returns ``{"embed", "norm", "head", "layers": [ {leaf: array} ... ]}``;
    each layer's leaves are separate arrays so that the program's model can
    take them one by one without a second copy. Matrices normal(0,
    initializer_range); gains 1 + 0.05 normal; the router's correction bias
    0.01 normal; convolution taps normal(0, taps^-1/2); ``A_log = log u``,
    ``u ~ U(1, 16)``; ``dt_bias`` the inverse softplus of ``dt ~ logU(1e-3,
    0.1)`` (the KDA family's own initialisation)."""
    top, gqa, kda = leaf_shapes(cfg)
    std = cfg["initializer_range"]
    dtype = jnp.dtype(cfg["torch_dtype"])
    kinds = layer_kinds(cfg)

    def draw(key, shape, kind):
        if kind == "A_log":
            x = jnp.log(jax.random.uniform(key, shape, minval=1.0, maxval=16.0))
        elif kind == "dt_bias":
            dt = jnp.exp(jax.random.uniform(
                key, shape, minval=math.log(1e-3), maxval=math.log(0.1)))
            x = dt + jnp.log(-jnp.expm1(-dt))
        elif len(shape) == 3:
            # a layer's experts one after another, so that the float32
            # draws of one, not of forty, stand beside the weights
            return jax.lax.map(lambda k: draw(k, shape[1:], kind),
                               jax.random.split(key, shape[0]))
        else:
            x = jax.random.normal(key, shape, jnp.float32)
            x = (1.0 + 0.05 * x if kind == "gain" else
                 x * {"matrix": std, "bias": 0.01,
                      "conv": shape[0] ** -0.5}[kind])
        return x.astype(dtype)

    @jax.jit
    def make(key):
        out = {name: draw(jax.random.fold_in(key, i), shape, kind)
               for i, (name, (shape, kind)) in enumerate(sorted(top.items()))}
        out["layers"] = []
        for li, kind in enumerate(kinds):
            lk = jax.random.fold_in(key, 1000 + li)
            leaves = gqa if kind == "gqa" else kda
            out["layers"].append({
                name: draw(jax.random.fold_in(lk, j), *leaves[name])
                for j, name in enumerate(sorted(leaves))})
        return out

    return make(seed_key(seed))


# ------------------------------------------------------------------ forward

def _int8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8), scale


def _matmul(x, w, precision):
    """``x [T, in] @ w [in, out]`` in float32, or on int8 operands."""
    if precision == "int8":
        xq, xs = _int8(x, axis=1)           # per token
        wq, ws = _int8(w, axis=0)           # per output channel
        acc = jax.lax.dot(xq, wq, preferred_element_type=jnp.int32)
        return acc.astype(jnp.float32) * xs * ws
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms_norm(x, gain, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * gain


def _f32(w, names):
    return {k: w[k].astype(jnp.float32) for k in names}


def _gqa_mixer(x, w, z, eps, precision):
    w = _f32(w, ("ln1", "wq", "wk", "wv", "wgate", "wo"))
    t = x.shape[0]
    nh, nkv, d = z["nh"], z["nkv"], z["d"]
    h = _rms_norm(x, w["ln1"], eps)
    q = _matmul(h, w["wq"], precision).reshape(t, nh, d)
    k = _matmul(h, w["wk"], precision).reshape(t, nkv, d)
    v = _matmul(h, w["wv"], precision).reshape(t, nkv, d)
    k = jnp.repeat(k, nh // nkv, axis=1)    # q head i reads KV head i // group
    v = jnp.repeat(v, nh // nkv, axis=1)
    blocks = []
    for lo in range(0, t, _Q_BLOCK):        # the scores, a block of queries
        qb = q[lo:lo + _Q_BLOCK]
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) / d ** 0.5
        causal = (jnp.arange(t)[None, :]
                  <= (lo + jnp.arange(qb.shape[0]))[:, None])
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        blocks.append(jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST))
    a = jnp.concatenate(blocks).reshape(t, nh * d)
    gate = jax.nn.sigmoid(_matmul(h, w["wgate"], precision))
    return x + _matmul(a * gate, w["wo"], precision)


def _conv_silu(u, taps_w):
    """Causal depthwise convolution over time, then SiLU: ``u [T, C]``,
    ``taps_w [taps, C]``; the last tap meets the current position."""
    taps = taps_w.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, u.shape[1]), u.dtype), u])
    y = sum(taps_w[j] * padded[j:j + u.shape[0]] for j in range(taps))
    return jax.nn.silu(y)


def _kda_mixer(x, w, z, eps, precision):
    w = _f32(w, ("ln1", "wq", "wk", "wv", "conv_q", "conv_k", "conv_v",
                 "f_down", "f_up", "A_log", "dt_bias", "g_down", "g_up",
                 "w_beta", "o_norm", "wo"))
    t = x.shape[0]
    hl, dl = z["hl"], z["dl"]
    h = _rms_norm(x, w["ln1"], eps)

    def heads(a):
        return a.reshape(t, hl, dl)

    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)

    q = unit(heads(_conv_silu(_matmul(h, w["wq"], precision), w["conv_q"])))
    k = unit(heads(_conv_silu(_matmul(h, w["wk"], precision), w["conv_k"])))
    v = heads(_conv_silu(_matmul(h, w["wv"], precision), w["conv_v"]))
    q = q * dl ** -0.5
    f = _matmul(_matmul(h, w["f_down"], precision), w["f_up"], precision)
    g = -jnp.exp(w["A_log"])[:, None] * jax.nn.softplus(
        heads(f + w["dt_bias"]))
    beta = 2.0 * jax.nn.sigmoid(_matmul(h, w["w_beta"], precision))

    def step(S, at):
        q_t, k_t, v_t, g_t, b_t = at        # [hl, dl] x 4, [hl]
        S = jnp.exp(g_t)[:, :, None] * S
        u = jnp.einsum("hk,hkv->hv", k_t, S, precision=HIGHEST)
        S = S + jnp.einsum("hk,hv->hkv", k_t, b_t[:, None] * (v_t - u),
                           precision=HIGHEST)
        return S, jnp.einsum("hk,hkv->hv", q_t, S, precision=HIGHEST)

    _, o = jax.lax.scan(step, jnp.zeros((hl, dl, dl), jnp.float32),
                        (q, k, v, g, beta))
    o = _rms_norm(o, w["o_norm"], eps)
    gate = jax.nn.sigmoid(
        _matmul(_matmul(h, w["g_down"], precision), w["g_up"], precision))
    return x + _matmul(o.reshape(t, hl * dl) * gate, w["wo"], precision)


def _experts(x, w, z, eps, norm_topk, scaling, precision):
    small = _f32(w, ("ln2", "router", "router_bias", "sg", "su", "sd"))
    h = _rms_norm(x, small["ln2"], eps)
    s = jax.nn.sigmoid(_matmul(h, small["router"], precision))   # [T, width]
    _, chosen = jax.lax.top_k(s + small["router_bias"], z["topk"])
    weight = jnp.take_along_axis(s, chosen, axis=1)
    if norm_topk:
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    weight = weight * scaling

    def swiglu(wg, wu, wd):
        return _matmul(jax.nn.silu(_matmul(h, wg, precision))
                       * _matmul(h, wu, precision), wd, precision)

    def one_expert(total, at):
        e, wg, wu, wd = at                  # upcast one expert at a time
        w_e = jnp.sum(jnp.where(chosen == e + z["offset"], weight, 0.0),
                      axis=1, keepdims=True)                     # [T, 1]
        y = swiglu(*(a.astype(jnp.float32) for a in (wg, wu, wd)))
        return total + w_e * y, None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (jnp.arange(z["held"]), w["wg"], w["wu"], w["wd"]))
    return x + swiglu(small["sg"], small["su"], small["sd"]) + routed


@functools.partial(jax.jit, static_argnames=("kind", "static"))
def _layer(x, w, *, kind, static):
    z, eps, norm_topk, scaling, precision = static
    z = dict(z)
    mixer = _gqa_mixer if kind == "gqa" else _kda_mixer
    x = mixer(x, w, z, eps, precision)
    return _experts(x, w, z, eps, norm_topk, scaling, precision)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x, norm, head, *, eps, precision):
    h = _rms_norm(x, norm.astype(jnp.float32), eps)
    return _matmul(h, head.astype(jnp.float32), precision)


def forward_logits(cfg, weights, tokens, precision="float32"):
    """Logits [T, vocab] of one sequence ``tokens [T]``, layer by layer so
    that only one layer's float32 copy of the weights (and of that, one
    expert's) lives at a time."""
    static = (tuple(sorted(sizes(cfg).items())), cfg["rms_norm_eps"],
              bool(cfg["norm_topk_prob"]),
              float(cfg["routed_scaling_factor"]), precision)
    x = weights["embed"][tokens].astype(jnp.float32)
    for kind, w in zip(layer_kinds(cfg), weights["layers"]):
        x = _layer(x, w, kind=kind, static=static)
    return _head(x, weights["norm"], weights["head"],
                 eps=cfg["rms_norm_eps"], precision=precision)


@functools.partial(jax.jit, static_argnames=("with_control",))
def _gaps(ref_logits, ctl_logits, served, mask, with_control):
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, served[:, None], axis=1)[:, 0]
    out = {"served_gap": jnp.where(mask, best - got, 0.0)}
    if with_control:
        first = jnp.argmax(ctl_logits, axis=-1)
        ctl = jnp.take_along_axis(ref_logits, first[:, None], axis=1)[:, 0]
        out["control_gap"] = jnp.where(mask, best - ctl, 0.0)
    return out


def served_token_gaps(cfg, weights, prompt, served, pad_to, control=None):
    """For one request: at each served token's position, how far the served
    token's reference logit lies below the reference's best.  With
    ``control`` (a precision) also the gap of the token that precision puts
    first, at the same positions of the same prompt and tokens.

    The sequence is padded to ``pad_to`` so that every request of a cell
    compiles one program; attention, convolution and recurrence are all
    causal, which keeps the padding out of every position that is read."""
    import numpy as np
    seq = list(prompt) + list(served[:-1])      # token i is predicted at i-1
    n_p, n_s = len(prompt), len(served)
    tokens = np.zeros((pad_to,), np.int32)
    tokens[:len(seq)] = seq
    want = np.zeros((pad_to,), np.int32)
    mask = np.zeros((pad_to,), bool)
    want[n_p - 1:n_p - 1 + n_s] = served
    mask[n_p - 1:n_p - 1 + n_s] = True
    tokens = jnp.asarray(tokens)
    ref = forward_logits(cfg, weights, tokens)
    ctl = forward_logits(cfg, weights, tokens, control) if control else ref
    out = _gaps(ref, ctl, jnp.asarray(want), jnp.asarray(mask),
                with_control=bool(control))
    return {k: np.asarray(v)[n_p - 1:n_p - 1 + n_s] for k, v in out.items()}
