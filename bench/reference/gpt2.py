"""Plain reference of GPT-2's training step: loss, gradients and AdamW.

Written from the published description (Radford et al. 2019; the
``config.json`` of ``openai-community/gpt2``): float32 ``jax.numpy`` at
``highest`` matmul precision, no kernel, nothing imported from the program.
Pre-LayerNorm blocks, learned positions, tanh-GELU (``gelu_new``), the
output head tied to the token embedding, mean cross-entropy over all tokens.

The optimizer follows the configuration's recipe: global-norm clipping, then
AdamW with decoupled decay on every leaf and bias-corrected moments in
float32.  The configuration states bfloat16 parameters with no float32
master copy, so the reference keeps its parameters in that type too: each
update is computed in float32 from the stored value and stored back rounded.
(An update under half a bfloat16 step of the value is lost on both sides:
LayerNorm gains at 1.0 do not move at this learning rate.  PERF.md lists it.)

``precision="int8"`` is the control: every matrix product of the forward
AND the backward pass on int8 operands (each operand scaled along the
contracted axis' other side: weights per output channel, activations and
their gradients per token), float32 accumulation -- what an int8 training
path would compute, the nearest precision below the configuration's
bfloat16.

Norms and directions are taken over LOGICAL leaves: the fused ``qkv``
matrix and bias of a block count as three leaves each (``q_w, k_w, v_w``;
``q_b, k_b, v_b``), because a key's bias has no gradient under softmax and
would otherwise hide in a leaf that has one.
"""
import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
_LAYER = ("ln1_g", "ln1_b", "qkv_w", "qkv_b", "proj_w", "proj_b",
          "ln2_g", "ln2_b", "fc_w", "fc_b", "fc_proj_w", "fc_proj_b")


def seed_key(seed):
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def leaf_specs(cfg):
    """name -> (shape, kind); matrices are [in, out]."""
    h, v, p, n = cfg["n_embd"], cfg["vocab_size"], cfg["n_positions"], cfg["n_layer"]
    specs = {"wte": ((v, h), "matrix"), "wpe": ((p, h), "matrix"),
             "lnf_g": ((h,), "one"), "lnf_b": ((h,), "zero")}
    for i in range(n):
        layer = {"ln1_g": ((h,), "one"), "ln1_b": ((h,), "zero"),
                 "qkv_w": ((h, 3 * h), "matrix"), "qkv_b": ((3 * h,), "zero"),
                 "proj_w": ((h, h), "residual"), "proj_b": ((h,), "zero"),
                 "ln2_g": ((h,), "one"), "ln2_b": ((h,), "zero"),
                 "fc_w": ((h, 4 * h), "matrix"), "fc_b": ((4 * h,), "zero"),
                 "fc_proj_w": ((4 * h, h), "residual"), "fc_proj_b": ((h,), "zero")}
        for name in _LAYER:
            specs[f"h{i}.{name}"] = layer[name]
    return specs


def init_weights(cfg, seed):
    """Every leaf from the seed in ONE jitted call, in the dtype the recipe
    trains in: a flat dict name -> array."""
    specs = leaf_specs(cfg)
    dtype = jnp.dtype(cfg["recipe"]["dtype"])
    std = cfg["initializer_range"]
    resid = std / (2 * cfg["n_layer"]) ** 0.5

    @jax.jit
    def make(key):
        out = {}
        for i, (name, (shape, kind)) in enumerate(sorted(specs.items())):
            if kind == "one":
                out[name] = jnp.ones(shape, dtype)
            elif kind == "zero":
                out[name] = jnp.zeros(shape, dtype)
            else:
                x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
                out[name] = (x * (resid if kind == "residual" else std)).astype(dtype)
        return out

    return make(seed_key(seed))


# ------------------------------------------------------------------ forward

def _int8(x, axis):
    """``x`` rounded to 127 levels of its largest magnitude along ``axis``
    (the contracted one), still held in float32."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


@jax.custom_vjp
def _matmul_int8(x, w):
    """``x [..., in] @ w [in, out]`` with all three products of training on
    int8 operands: the forward's, and the backward's two."""
    return jnp.matmul(_int8(x, -1), _int8(w, 0), precision=HIGHEST)


def _matmul_int8_fwd(x, w):
    return _matmul_int8(x, w), (x, w)


def _matmul_int8_bwd(res, dy):
    x, w = res
    dx = jnp.matmul(_int8(dy, -1), _int8(w, 1).T, precision=HIGHEST)
    x2, dy2 = x.reshape(-1, x.shape[-1]), dy.reshape(-1, dy.shape[-1])
    dw = jnp.matmul(_int8(x2, 0).T, _int8(dy2, 0), precision=HIGHEST)
    return dx, dw


_matmul_int8.defvjp(_matmul_int8_fwd, _matmul_int8_bwd)


def _matmul(x, w, precision):
    if precision == "int8":
        return _matmul_int8(x, w)
    return jnp.matmul(x, w, precision=HIGHEST)


def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x ** 3)))


def loss_fn(params, x, y, cfg, precision="float32"):
    """Mean next-token cross-entropy of rows ``x [b, s]`` with labels ``y``."""
    n_head, eps = cfg["n_head"], cfg["layer_norm_epsilon"]
    b, s = x.shape
    h = params["wte"][x] + params["wpe"][jnp.arange(s)][None]
    hd = h.shape[-1] // n_head
    causal = jnp.tril(jnp.ones((s, s), bool))
    for i in range(cfg["n_layer"]):
        p = {k: params[f"h{i}.{k}"] for k in _LAYER}
        a = _layer_norm(h, p["ln1_g"], p["ln1_b"], eps)
        qkv = _matmul(a, p["qkv_w"], precision) + p["qkv_b"]
        q, k, v = jnp.split(qkv.reshape(b, s, 3, n_head, hd), 3, axis=2)
        q, k, v = q[:, :, 0], k[:, :, 0], v[:, :, 0]
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) / hd ** 0.5
        sc = jnp.where(causal[None, None], sc, -jnp.inf)
        att = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v,
                         precision=HIGHEST).reshape(b, s, -1)
        h = h + _matmul(att, p["proj_w"], precision) + p["proj_b"]
        a = _layer_norm(h, p["ln2_g"], p["ln2_b"], eps)
        a = _gelu_new(_matmul(a, p["fc_w"], precision) + p["fc_b"])
        h = h + _matmul(a, p["fc_proj_w"], precision) + p["fc_proj_b"]
    h = _layer_norm(h, params["lnf_g"], params["lnf_b"], eps)
    logits = _matmul(h, params["wte"].T, precision)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision"))
def _block_grad(params, x, y, cfg_key, precision):
    cfg = dict(cfg_key)
    p32 = {k: v.astype(jnp.float32) for k, v in params.items()}
    return jax.value_and_grad(loss_fn)(p32, x, y, cfg, precision)


def loss_and_grads(cfg, params, x, y, rows, precision="float32", keep=None):
    """Over the whole batch in blocks of ``rows`` rows, so that it fits;
    ``keep``: a function of the row index leaving rows out (a planted fault)."""
    cfg_key = tuple(sorted((k, v) for k, v in cfg.items()
                           if k in ("n_head", "n_layer", "layer_norm_epsilon")))
    total, grads, blocks = 0.0, None, 0
    for r in range(0, x.shape[0], rows):
        if keep is not None and not keep(r):
            continue
        l, g = _block_grad(params, x[r:r + rows], y[r:r + rows], cfg_key, precision)
        total = total + l
        grads = g if grads is None else jax.tree_util.tree_map(jnp.add, grads, g)
        blocks += 1
    return total / blocks, jax.tree_util.tree_map(lambda a: a / blocks, grads)


# ---------------------------------------------------------------- optimizer

@functools.partial(jax.jit, static_argnames=("recipe_key",), donate_argnums=(0, 1, 2))
def _adamw(params, m, v, grads, step, recipe_key):
    r = dict(recipe_key)
    sq = sum(jnp.sum(jnp.square(g)) for g in grads.values())
    clip = r["clip_global_norm"]
    scale = clip / jnp.maximum(jnp.sqrt(sq), clip)
    b1, b2 = r["beta1"], r["beta2"]
    c1, c2 = 1.0 - b1 ** step, 1.0 - b2 ** step
    out_p, out_m, out_v = {}, {}, {}
    for k, p in params.items():
        g = grads[k] * scale
        out_m[k] = b1 * m[k] + (1 - b1) * g
        out_v[k] = b2 * v[k] + (1 - b2) * jnp.square(g)
        p32 = p.astype(jnp.float32) * (1.0 - r["learning_rate"] * r["weight_decay"])
        p32 = p32 - r["learning_rate"] * (out_m[k] / c1) / (
            jnp.sqrt(out_v[k] / c2) + r["epsilon"])
        out_p[k] = p32.astype(p.dtype)      # stored as the configuration states
    return out_p, out_m, out_v


def logical_leaves(tree):
    """The fused ``qkv`` leaves cut into their three parts along the output
    axis; every other leaf as it is."""
    out = {}
    for name, a in tree.items():
        if name.endswith((".qkv_w", ".qkv_b")):
            stem, kind = name[:-len("qkv_w")], name[-1]
            for part, piece in zip("qkv", jnp.split(a, 3, axis=-1)):
                out[f"{stem}{part}_{kind}"] = piece
        else:
            out[name] = a
    return out


@jax.jit
def leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in logical_leaves(tree).items()}


@jax.jit
def change_norms(new, old):
    new, old = logical_leaves(new), logical_leaves(old)
    return {k: jnp.sqrt(jnp.sum(jnp.square(
        new[k].astype(jnp.float32) - old[k].astype(jnp.float32)))) for k in new}


@jax.jit
def _direction_gap(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    na, nb = jnp.sqrt(jnp.sum(a * a)), jnp.sqrt(jnp.sum(b * b))
    # 1 - cos, from the unit vectors' difference: no cancellation near 0
    d = a / jnp.where(na > 0, na, 1.0) - b / jnp.where(nb > 0, nb, 1.0)
    return 0.5 * jnp.sum(d * d)


def direction_gaps(got, want):
    """For each logical leaf, 1 - cos of the angle between two trees' leaves
    (``got`` may live on the host: it is moved leaf by leaf)."""
    want = logical_leaves(want)
    out = {}
    for name, a in logical_leaves({k: jnp.asarray(v) for k, v in got.items()}).items():
        out[name] = float(_direction_gap(a, want[name]))
    return out


def follow(cfg, recipe, seed, batches, rows=4, precision="float32",
           moments_after=2, keep=None, keep_moments=False):
    """Follow the first ``len(batches)`` optimizer steps from the seed's
    weights.  ``batches``: a list of ``(x [b, s], y [b, s])``.

    Returns the loss of each step, each leaf's norm of the first gradient
    (before clipping: which leaves a gradient reaches at all), each leaf's
    norm of the first moment after ``moments_after`` steps (the gradients as
    the optimizer got them), and each leaf's norm of the parameters' change
    after all the steps; with ``keep_moments`` also that first moment itself
    (``"moments"``, on the device), for :func:`direction_gaps`."""
    import numpy as np
    recipe_key = tuple(sorted((k, v) for k, v in recipe.items()
                              if isinstance(v, (int, float))))
    params = init_weights(cfg, seed)
    start = params
    m = jax.tree_util.tree_map(lambda a: jnp.zeros(a.shape, jnp.float32), params)
    v = jax.tree_util.tree_map(lambda a: jnp.zeros(a.shape, jnp.float32), params)
    out = {"loss": []}
    for t, (x, y) in enumerate(batches, start=1):
        loss, grads = loss_and_grads(cfg, params, x, y, rows, precision, keep)
        if t == 1:
            out["first_grad_norm"] = leaf_norms(grads)
            params = jax.tree_util.tree_map(jnp.copy, params)   # keep `start`
        params, m, v = _adamw(params, m, v, grads, float(t), recipe_key)
        out["loss"].append(float(loss))
        if t == moments_after:
            out["moment_norm"] = leaf_norms(m)
            if keep_moments:
                moments = jax.tree_util.tree_map(jnp.copy, m)   # m is donated
    out["change_norm"] = change_norms(params, start)
    out = {k: (val if k == "loss" else {n: float(np.asarray(a)) for n, a in val.items()})
           for k, val in out.items()}
    if keep_moments:
        out["moments"] = moments
    return out
