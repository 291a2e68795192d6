"""Plain reference of ZAYA1-8B's layers, cut as ``bench/configs/zaya1-8b.json``
states.

Written from the equations (``model_type`` ``zaya``; Compressed
Convolutional Attention as arXiv:2510.04476 describes it, and the family's
router and merge as the configuration file lists under ``assumed``):
float32 ``jax.numpy`` at ``highest`` matmul precision, no kernel, no cache,
no tail, no batching, nothing imported from the program. The convolutions
and the value shift run over the whole sequence, with zeros before its
first position. Every layer ``l``, for rows ``x [T, hidden]``::

    h   = rms_norm(x; g1);  qt = h Wq;  kt = h Wk;  u = [qt ; kt]
    c1  = a_0 u_t + a_1 u_{t-1}                       depthwise over time
    c2  = c1_t B_0 + c1_{t-1} B_1                     a matrix a head
    q   = c2_q + (qt + repeat(kt)) / 2;  k = c2_k + (mean(qt) + kt) / 2
    q   = sqrt(d) tau q / |q|;  k = sqrt(d) k / |k|;  RoPE on the first
          rotary_dim channels of a head (neox halves)
    v_t = [h_t Wv1 ; h_{t-1} Wv2]
    x   = r1 * x + o1 * (softmax(q k^T / sqrt(d) + causal) v  Wo)
    h   = rms_norm(x; g2);  rh_l = h Wdown + gamma * rh_{l-1},  rh_0 = 0
    z   = W3 gelu(W2 gelu(W1 rms_norm(rh_l; gr)))
    c   = argmax(z + b);  p = softmax(z)_c
    x   = r2 * x + o2 * (p Wd_c (silu(Wg_c h) * Wu_c h)  if c < experts, else 0)

``logits = rms_norm(x; gf) embed^T`` (the head is tied). Attention runs in
blocks of 512 queries so that 8,192 tokens fit. An expert is upcast and
applied on its own to the rows that chose it, gathered (up to twice what
even routing would send it), or to every row where more chose it.

**Departures from the published description**: ``rh`` starts at zero at
this configuration's first layer (the layers cut away would hand it over
with ``x``); the program rounds the convolutions' inputs to the weights'
dtype (what its tail keeps between tokens), this reference does not.

``precision="int8"`` is the control of the comparison that decides
``correct``: every matrix product taken on int8 operands (weights per output
channel, activations per token, int32 accumulation) - the nearest precision
below the configuration's bfloat16.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
_Q_BLOCK = 512          # queries whose scores are alive at once
_ROW_BUCKET = 256       # read rows are padded to a multiple of it
_VOCAB_BLOCK = 32768    # the head's columns alive at once
_L2_EPS = 1e-6


def seed_key(seed):
    """A PRNG key from any whole number (``--seed`` passes 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def sizes(cfg):
    d = cfg["head_dim"]
    return dict(
        h=cfg["hidden_size"], nh=cfg["num_attention_heads"],
        nkv=cfg["num_key_value_heads"], d=d,
        rd=int(d * cfg["partial_rotary_factor"]),
        f=cfg["moe_intermediate_size"], experts=cfg["num_experts"],
        r=cfg["router_hidden_size"], vocab=cfg["vocab_size"])


def leaf_shapes(cfg):
    """(top, layer): name -> (shape, kind); matrices are [in, out] as
    ``x @ w`` reads, the experts' stacked [experts, in, out]."""
    z = sizes(cfg)
    h, d, f, e, r = z["h"], z["d"], z["f"], z["experts"], z["r"]
    nq, nkv = z["nh"] * d, z["nkv"] * d
    groups = z["nh"] + z["nkv"]
    layer = {"ln1": ((h,), "gain"), "wq": ((h, nq), "matrix"),
             "wk": ((h, nkv), "matrix"), "wv1": ((h, nkv // 2), "matrix"),
             "wv2": ((h, nkv // 2), "matrix"),
             "conv1": ((cfg["cca_time0"], nq + nkv), "taps"),
             "conv2": ((cfg["cca_time1"], groups, d, d), "taps"),
             "temp": ((z["nh"],), "gain"), "wo": ((nq, h), "matrix"),
             "res1": ((h,), "gain"), "out1": ((h,), "gain"),
             "ln2": ((h,), "gain"), "r_down": ((h, r), "matrix"),
             "eda": ((r,), "gain"), "r_norm": ((r,), "gain"),
             "r1": ((r, r), "fan_in"), "r2": ((r, r), "fan_in"),
             "r3": ((r, e + 1), "fan_in"), "r_bias": ((e + 1,), "bias"),
             "wg": ((e, h, f), "matrix"), "wu": ((e, h, f), "matrix"),
             "wd": ((e, f, h), "matrix"), "res2": ((h,), "gain"),
             "out2": ((h,), "gain")}
    top = {"embed": ((z["vocab"], h), "embed"), "norm": ((h,), "gain")}
    return top, layer


def init_weights(cfg, seed):
    """Every leaf from the seed in ONE jitted call, in the served dtype.

    Returns ``{"embed", "norm", "layers": [ {leaf: array} ... ]}``; each
    layer's leaves are separate arrays so that the program's model can take
    them one by one without a second copy. Matrices normal(0,
    initializer_range), the embedding (the tied head) normal(0,
    embed_initializer_range); gains (norms, temperatures, residual scales,
    the router state's decay) 1 + 0.05 normal; the balancing bias 0.01
    normal; the router MLP and the convolutions' taps normal(0,
    fan_in^-1/2), fan_in the taps times the input channels of one output.

    Why the embedding is drawn smaller: under the tied head a token's logit
    holds ``|e|^2 / rms(x)`` of its own embedding, a lead for the token just
    read. At 0.02 greedy decoding repeats one token from its first steps,
    its best logit half a unit clear of the next, so almost no position is
    close enough for a precision to flip, and the comparison that decides
    ``correct`` cannot tell the int8 control from the program."""
    top, layer = leaf_shapes(cfg)
    std = cfg["initializer_range"]
    dtype = jnp.dtype(cfg["torch_dtype"])

    def draw(key, shape, kind):
        if kind == "embed":
            return (jax.random.normal(key, shape, jnp.float32)
                    * cfg["embed_initializer_range"]).astype(dtype)
        if kind == "matrix" and len(shape) == 3:
            # a layer's experts one after another, so that the float32
            # draws of one, not of all, stand beside the weights
            return jax.lax.map(lambda k: draw(k, shape[1:], kind),
                               jax.random.split(key, shape[0]))
        x = jax.random.normal(key, shape, jnp.float32)
        if kind == "gain":
            x = 1.0 + 0.05 * x
        elif kind in ("taps", "fan_in"):
            fan_in = (shape[0] * (shape[2] if len(shape) == 4 else 1)
                      if kind == "taps" else shape[0])
            x = x * fan_in ** -0.5
        else:
            x = x * {"matrix": std, "bias": 0.01}[kind]
        return x.astype(dtype)

    @jax.jit
    def make(key):
        out = {name: draw(jax.random.fold_in(key, i), shape, kind)
               for i, (name, (shape, kind)) in enumerate(sorted(top.items()))}
        out["layers"] = [
            {name: draw(jax.random.fold_in(jax.random.fold_in(key, 1000 + li),
                                           j), *layer[name])
             for j, name in enumerate(sorted(layer))}
            for li in range(cfg["num_hidden_layers"])]
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


def _before(u):
    """Each row's previous row; zeros before the first."""
    return jnp.concatenate([jnp.zeros_like(u[:1]), u[:-1]])


def _rope(x, pos, rd, theta):
    """neox halves over the first ``rd`` channels of ``x [T, heads, d]``."""
    inv = 1.0 / theta ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]       # [T, rd/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None, :]
    r = x[..., :rd]
    rot = jnp.concatenate([-r[..., rd // 2:], r[..., :rd // 2]], axis=-1)
    return jnp.concatenate([r * cos + rot * sin, x[..., rd:]], axis=-1)


def _unit(a):
    return a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + _L2_EPS)


def _attention(x, w, z, eps, theta, precision):
    w = {k: w[k].astype(jnp.float32)
         for k in ("ln1", "wq", "wk", "wv1", "wv2", "conv1", "conv2", "temp",
                   "wo", "res1", "out1")}
    n = x.shape[0]
    nh, nkv, d = z["nh"], z["nkv"], z["d"]
    g = nh // nkv
    h = _rms_norm(x, w["ln1"], eps)
    qt = _matmul(h, w["wq"], precision)
    kt = _matmul(h, w["wk"], precision)
    u = jnp.concatenate([qt, kt], axis=-1)
    c1 = w["conv1"][0] * u + w["conv1"][1] * _before(u)
    heads = nh + nkv

    def grouped(a, b):                      # a [T, heads * d] @ b [heads, d, d]
        return jax.vmap(lambda ag, bg: _matmul(ag, bg, precision),
                        in_axes=(1, 0), out_axes=1)(
            a.reshape(n, heads, d), b)
    c2 = grouped(c1, w["conv2"][0]) + grouped(_before(c1), w["conv2"][1])
    qt, kt = qt.reshape(n, nh, d), kt.reshape(n, nkv, d)
    q = c2[:, :nh] + (qt + jnp.repeat(kt, g, axis=1)) / 2
    k = c2[:, nh:] + (qt.reshape(n, nkv, g, d).mean(axis=2) + kt) / 2
    q = math.sqrt(d) * w["temp"][:, None] * _unit(q)
    k = math.sqrt(d) * _unit(k)
    pos = jnp.arange(n)
    q, k = _rope(q, pos, z["rd"], theta), _rope(k, pos, z["rd"], theta)
    v = jnp.concatenate([_matmul(h, w["wv1"], precision),
                         _before(_matmul(h, w["wv2"], precision))],
                        axis=-1).reshape(n, nkv, d)
    k = jnp.repeat(k, g, axis=1)            # q head i reads KV head i // g
    v = jnp.repeat(v, g, axis=1)

    def some_queries(at):                   # the scores, a block of queries
        qb, rows = at
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) / d ** 0.5
        causal = jnp.arange(n)[None, :] <= rows[:, None]
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    pad = -n % _Q_BLOCK                     # padding queries are cut
    qs = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, _Q_BLOCK, nh, d)
    rows = jnp.arange(n + pad).reshape(-1, _Q_BLOCK)
    a = jax.lax.map(some_queries, (qs, rows)).reshape(-1, nh * d)[:n]
    return w["res1"] * x + w["out1"] * _matmul(a, w["wo"], precision)


def _experts(x, rh, w, z, eps, precision):
    n, e = x.shape[0], z["experts"]
    small = {k: w[k].astype(jnp.float32)
             for k in ("ln2", "r_down", "eda", "r_norm", "r1", "r2", "r3",
                       "r_bias", "res2", "out2")}
    h = _rms_norm(x, small["ln2"], eps)
    rh = _matmul(h, small["r_down"], precision) + small["eda"] * rh
    u = _rms_norm(rh, small["r_norm"], eps)
    u = jax.nn.gelu(_matmul(u, small["r1"], precision), approximate=False)
    u = jax.nn.gelu(_matmul(u, small["r2"], precision), approximate=False)
    zz = _matmul(u, small["r3"], precision)
    choice = jnp.argmax(zz + small["r_bias"], axis=-1)
    prob = jnp.take_along_axis(jax.nn.softmax(zz, axis=-1), choice[:, None],
                               axis=1)[:, 0]
    # rows an expert takes gathered: twice what even routing would send it
    cap = max(1, 2 * n // e)

    def swiglu(rows, wg, wu, wd):
        return _matmul(jax.nn.silu(_matmul(rows, wg, precision))
                       * _matmul(rows, wu, precision), wd, precision)

    def one_expert(total, at):
        i, wg, wu, wd = at                  # upcast one expert at a time
        wg, wu, wd = (a.astype(jnp.float32) for a in (wg, wu, wd))
        w_e = jnp.where(choice == i, prob, 0.0)                  # [N]

        def gathered():
            idx = jnp.nonzero(choice == i, size=cap, fill_value=n)[0]
            rows = jnp.concatenate([h, jnp.zeros((1, h.shape[1]))])[idx]
            y = swiglu(rows, wg, wu, wd) * jnp.concatenate(
                [w_e, jnp.zeros((1,))])[idx][:, None]
            return jnp.zeros((n + 1, h.shape[1])).at[idx].add(y)[:n]

        def every_row():
            return w_e[:, None] * swiglu(h, wg, wu, wd)

        y = jax.lax.cond(jnp.sum(choice == i) > cap, every_row, gathered)
        return total + y, None

    # a row that chose the last output (the skip) gets nothing from any
    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (jnp.arange(e), w["wg"], w["wu"], w["wd"]))
    return small["res2"] * x + small["out2"] * routed, rh, choice


@functools.partial(jax.jit, static_argnames=("static",))
def _layer(x, rh, w, *, static):
    z, eps, theta, precision = static
    z = dict(z)
    with jax.default_matmul_precision("highest"):
        x = _attention(x, w, z, eps, theta, precision)
        return _experts(x, rh, w, z, eps, precision)


def _static(cfg, precision):
    return (tuple(sorted(sizes(cfg).items())), cfg["rms_norm_eps"],
            float(cfg["rope_parameters"]["hybrid"]["rope_theta"]), precision)


def hidden(cfg, weights, tokens, precision="float32"):
    """The final norm's rows ``[T, hidden]`` of one sequence, and each
    layer's choices ``[layers, T]`` (``num_experts``: none), layer by layer
    so that only one layer's float32 copy of the small weights (and of the
    experts, one) lives at a time."""
    static = _static(cfg, precision)
    x = weights["embed"][jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
    rh = jnp.zeros((x.shape[0], cfg["router_hidden_size"]), jnp.float32)
    choices = []
    for w in weights["layers"]:
        x, rh, choice = _layer(x, rh, w, static=static)
        choices.append(choice)
    return (_rms_norm(x, weights["norm"].astype(jnp.float32),
                      cfg["rms_norm_eps"]), jnp.stack(choices))


@functools.partial(jax.jit, static_argnames=("precision",))
def _head_block(h, embed_block, *, precision):
    with jax.default_matmul_precision("highest"):
        return _matmul(h, embed_block.astype(jnp.float32).T, precision)


def forward(cfg, weights, tokens, precision="float32"):
    """Logits ``[T, vocab]`` of one sequence (small sizes: the head whole)."""
    h, _ = hidden(cfg, weights, tokens, precision)
    return _head_block(h, weights["embed"], precision=precision)


def _best(cfg, weights, h, precision):
    """The head over ``h [N, hidden]`` a block of the vocabulary at a time:
    each row's largest logit and where it lies, and the logits whole only
    as functions of the blocks."""
    embed = weights["embed"]
    best = where = None
    for lo in range(0, embed.shape[0], _VOCAB_BLOCK):
        lg = _head_block(h, embed[lo:lo + _VOCAB_BLOCK], precision=precision)
        m, i = jnp.max(lg, axis=-1), jnp.argmax(lg, axis=-1) + lo
        if best is None:
            best, where = m, i
        else:
            take = m > best
            best, where = jnp.where(take, m, best), jnp.where(take, i, where)
    return best, where


@jax.jit
def _logit_at(h, embed, ids):
    with jax.default_matmul_precision("highest"):
        return jnp.einsum("nh,nh->n", h, embed[ids].astype(jnp.float32),
                          precision=HIGHEST)


def served_token_gaps(cfg, weights, prompt, served, pad_to, control=None):
    """For one request: at each served token's position, how far the served
    token's reference logit lies below the reference's best.  With
    ``control`` (a precision) also the gap of the token that precision puts
    first, at the same positions of the same prompt and tokens.

    The sequence is padded to ``pad_to`` so that every request of a cell
    compiles one program; attention, convolutions and the value shift are
    all causal, which keeps the padding out of every position that is read.
    The head runs on the read rows alone (padded to a multiple of 256), a
    block of the vocabulary at a time."""
    seq = list(prompt) + list(served[:-1])      # token i is predicted at i-1
    n_p, n_s = len(prompt), len(served)
    tokens = np.zeros((pad_to,), np.int32)
    tokens[:len(seq)] = seq
    rows = np.full(-(-n_s // _ROW_BUCKET) * _ROW_BUCKET, n_p - 1, np.int32)
    rows[:n_s] = np.arange(n_p - 1, n_p - 1 + n_s)
    want = np.zeros(len(rows), np.int32)
    want[:n_s] = served
    rows, want = jnp.asarray(rows), jnp.asarray(want)
    h, _ = hidden(cfg, weights, tokens)
    h = h[rows]
    best, _ = _best(cfg, weights, h, "float32")
    got = _logit_at(h, weights["embed"], want)
    out = {"served_gap": best - got}
    if control:
        hc, _ = hidden(cfg, weights, tokens, control)
        _, first = _best(cfg, weights, hc[rows], control)
        out["control_gap"] = best - _logit_at(h, weights["embed"], first)
    return {k: np.asarray(v)[:n_s] for k, v in out.items()}
