"""Plain reference of the Mistral-7B block (dense GQA + SwiGLU + RoPE).

Written from the published description (Mistral 7B, arXiv:2310.06825, and
the v0.3 ``config.json``: no sliding window): float32 ``jax.numpy`` at
``highest`` matmul precision, no kernel, no cache, no batching, and nothing
imported from the program.  The weights come from :func:`init_weights`,
which is also what the benchmark feeds the program: both sides get the same
bfloat16 values from ``--seed`` and neither takes anything the other made.

``precision="int8"`` is the control of the comparison that decides
``correct``: the same forward with every matrix product taken on int8
operands (weights per output channel, activations per token, int32
accumulation) -- the nearest precision below the configuration's bfloat16.
"""
import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST

_LAYER_LEAVES = ("ln1", "wq", "wk", "wv", "wo", "ln2", "wg", "wu", "wd")


def seed_key(seed):
    """A PRNG key from any whole number (``--seed`` passes 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def leaf_shapes(cfg):
    """name -> (shape, kind); matrices are [in, out] as ``x @ w`` reads."""
    h, m = cfg["hidden_size"], cfg["intermediate_size"]
    d = cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    layer = {"ln1": ((h,), "gain"), "wq": ((h, nq), "matrix"),
             "wk": ((h, nkv), "matrix"), "wv": ((h, nkv), "matrix"),
             "wo": ((nq, h), "matrix"), "ln2": ((h,), "gain"),
             "wg": ((h, m), "matrix"), "wu": ((h, m), "matrix"),
             "wd": ((m, h), "matrix")}
    top = {"embed": ((cfg["vocab_size"], h), "matrix"),
           "norm": ((h,), "gain"),
           "head": ((h, cfg["vocab_size"]), "matrix")}
    return top, layer


def init_weights(cfg, seed):
    """Every leaf from the seed in ONE jitted call, in the served dtype.

    Returns ``{"embed", "norm", "head", "layers": [ {leaf: array} ... ]}``;
    each layer's leaves are separate arrays so that the program's model can
    take them one by one without a second copy."""
    top, layer = leaf_shapes(cfg)
    std = cfg["initializer_range"]
    n_layers = cfg["num_hidden_layers"]
    dtype = jnp.dtype(cfg["torch_dtype"])

    def draw(key, shape, kind):
        x = jax.random.normal(key, shape, jnp.float32)
        x = x * std if kind == "matrix" else 1.0 + 0.05 * x
        return x.astype(dtype)

    @jax.jit
    def make(key):
        out = {}
        for i, (name, (shape, kind)) in enumerate(sorted(top.items())):
            out[name] = draw(jax.random.fold_in(key, i), shape, kind)
        out["layers"] = []
        for li in range(n_layers):
            lk = jax.random.fold_in(key, 1000 + li)
            out["layers"].append({
                name: draw(jax.random.fold_in(lk, j), *layer[name])
                for j, name in enumerate(_LAYER_LEAVES)})
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


def _rope(x, theta):
    """Rotary embedding, rotate-half form; x [T, heads, D] at positions 0..T-1."""
    t, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


@functools.partial(jax.jit, static_argnames=("nh", "nkv", "d", "eps", "theta",
                                             "precision"))
def _layer(x, w, *, nh, nkv, d, eps, theta, precision):
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    t = x.shape[0]
    h = _rms_norm(x, w["ln1"], eps)
    q = _rope(_matmul(h, w["wq"], precision).reshape(t, nh, d), theta)
    k = _rope(_matmul(h, w["wk"], precision).reshape(t, nkv, d), theta)
    v = _matmul(h, w["wv"], precision).reshape(t, nkv, d)
    group = nh // nkv
    k = jnp.repeat(k, group, axis=1)        # grouped-query: share KV heads
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / (d ** 0.5)
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    att = jnp.einsum("hqk,khd->qhd", probs, v, precision=HIGHEST)
    x = x + _matmul(att.reshape(t, nh * d), w["wo"], precision)
    h = _rms_norm(x, w["ln2"], eps)
    gate = _matmul(h, w["wg"], precision)
    up = _matmul(h, w["wu"], precision)
    return x + _matmul(jax.nn.silu(gate) * up, w["wd"], precision)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x, norm, head, *, eps, precision):
    h = _rms_norm(x, norm.astype(jnp.float32), eps)
    return _matmul(h, head.astype(jnp.float32), precision)


def forward_logits(cfg, weights, tokens, precision="float32"):
    """Logits [T, vocab] of one sequence ``tokens [T]``, layer by layer so
    that only one layer's float32 copy of the weights lives at a time."""
    kw = dict(nh=cfg["num_attention_heads"], nkv=cfg["num_key_value_heads"],
              d=cfg["head_dim"], eps=cfg["rms_norm_eps"],
              theta=cfg["rope_theta"], precision=precision)
    x = weights["embed"][tokens].astype(jnp.float32)
    for w in weights["layers"]:
        x = _layer(x, w, **kw)
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
    compiles one program; causal attention keeps the padding out of every
    position that is read."""
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
