"""Plain reference of SDAR-30B-A3B-Chat's layers and of how it generates,
cut as ``bench/configs/sdar-30b-a3b-chat.json`` states.

Written from the equations (``model_type`` ``sdar_moe``: the Qwen3-MoE block
its keys name; how it GENERATES is not in its ``config.json`` and follows
the family's published generation script, line by line under ``assumed`` in
the configuration file): float32 ``jax.numpy`` at ``highest`` matmul
precision, no kernel, no cache, no batching, nothing imported from the
program. Every layer, for rows ``x [N, hidden]`` at positions ``pos``::

    h  = rms_norm(x; g1)
    q  = rope(rms_norm_head(h Wq; gq), pos)    k = rope(rms_norm_head(h Wk; gk), pos)
    v  = h Wv         rope: neox halves, the whole head; q head i reads KV head i // group
    x  = x + softmax(q k^T / sqrt(d) + M) v Wo
    h2 = rms_norm(x; g2);  s = softmax(h2 Wr);  (w, e) = top_k(s);  w = w / sum(w)
    x  = x + sum_j w_j * Wd[e_j] (silu(Wg[e_j] h2) * Wu[e_j] h2)

``logits = rms_norm(x; gf) W_head``; the logits AT a position are that
position's own token's (no shift). ``M``: position ``p`` sees ``p'`` iff
``p' // Q <= p // Q`` (causal from block to block, both ways inside one).

**Generation** (``cfg["generation"]``: ``block_length`` Q, ``denoising_steps``
D, ``remasking``, ``mask_token_id``): the prompt's first ``len // Q * Q``
tokens are context; then block by block: the block starts as what of the
prompt is left over followed by ``[MASK]``; denoising step ``s`` runs the
block over the clean blocks before it and unmasks ``num_transfer[s]``
positions (``Q // D``, one more on the first ``Q % D`` steps); positions
past the request's budget in its last block stay ``[MASK]``.

:func:`served_token_gaps` checks a served request against that WITHOUT a
cache: ONE forward over the served sequence clean, under ``M``, followed by
D noisy copies of its generated blocks - copy ``s`` holds each block as it
stood BEFORE step ``s`` (what was unmasked earlier known, the rest
``[MASK]``) - where a noisy block sees the clean blocks before it and
itself, and nothing sees a noisy block but itself. (A doubled sequence a
step, the D steps side by side: the clean half is the same in all.) At each
position unmasked at step ``s`` it reads, in copy ``s``, how far the served
token's logit lies below the best. Under ``remasking: sequential`` the step
of a position follows from the configuration alone (the leftmost masked go
first); for the confidence rules the caller says at which step each token
was unmasked (``steps``) and ``conf_gap`` says how far the chosen
position's confidence lay below the highest among the masked.

An expert is upcast and applied on its own: to the rows that chose it,
gathered (up to twice what even routing would send it, an eighth of all rows
at the published sizes), or to every row where more chose it (``[MASK]`` rows
route alike in the first layers).

``precision="int8"`` is the control of the comparison that decides
``correct``: every matrix product taken on int8 operands (weights per output
channel, activations per token, int32 accumulation) - the nearest precision
below the configuration's bfloat16.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
_Q_BLOCK = 256      # queries whose scores are alive at once
_NOISY_BUCKET = 1024    # a noisy copy's rows are padded to a multiple of it


def seed_key(seed):
    """A PRNG key from any whole number (``--seed`` passes 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def sizes(cfg):
    return dict(
        h=cfg["hidden_size"], nh=cfg["num_attention_heads"],
        nkv=cfg["num_key_value_heads"], d=cfg["head_dim"],
        f=cfg["moe_intermediate_size"], experts=cfg["num_experts"],
        topk=cfg["num_experts_per_tok"], vocab=cfg["vocab_size"])


def leaf_shapes(cfg):
    """(top, layer): name -> (shape, kind); matrices are [in, out] as
    ``x @ w`` reads, the experts' stacked [experts, in, out]."""
    z = sizes(cfg)
    h, f, e, d = z["h"], z["f"], z["experts"], z["d"]
    nq, nkv = z["nh"] * d, z["nkv"] * d
    layer = {"ln1": ((h,), "gain"), "wq": ((h, nq), "matrix"),
             "wk": ((h, nkv), "matrix"), "wv": ((h, nkv), "matrix"),
             "q_norm": ((d,), "gain"), "k_norm": ((d,), "gain"),
             "wo": ((nq, h), "matrix"), "ln2": ((h,), "gain"),
             "router": ((h, e), "matrix"),
             "wg": ((e, h, f), "matrix"), "wu": ((e, h, f), "matrix"),
             "wd": ((e, f, h), "matrix")}
    top = {"embed": ((z["vocab"], h), "matrix"), "norm": ((h,), "gain"),
           "head": ((h, z["vocab"]), "matrix")}
    return top, layer


def init_weights(cfg, seed):
    """Every leaf from the seed in ONE jitted call, in the served dtype.

    Returns ``{"embed", "norm", "head", "layers": [ {leaf: array} ... ]}``;
    each layer's leaves are separate arrays so that the program's model can
    take them one by one without a second copy. Matrices normal(0,
    initializer_range); gains 1 + 0.05 normal."""
    top, layer = leaf_shapes(cfg)
    std = cfg["initializer_range"]
    dtype = jnp.dtype(cfg["torch_dtype"])

    def draw(key, shape, kind):
        if len(shape) == 3:
            # a layer's experts one after another, so that the float32
            # draws of one, not of all, stand beside the weights
            return jax.lax.map(lambda k: draw(k, shape[1:], kind),
                               jax.random.split(key, shape[0]))
        x = jax.random.normal(key, shape, jnp.float32)
        return (1.0 + 0.05 * x if kind == "gain" else x * std).astype(dtype)

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


def _rope(x, pos, theta):
    """neox halves over the whole head: ``x [T, heads, d]``."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]       # [T, d/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + rot * sin


def _attention(x, w, pos, copy, blk, z, eps, theta, precision):
    """``copy [N]``: 0 for a clean row, 1.. for the noisy copy it belongs
    to; ``blk [N]``: its block. Row i sees row j iff j is clean and in an
    earlier block, or of i's own copy and block."""
    w = {k: w[k].astype(jnp.float32)
         for k in ("ln1", "wq", "wk", "wv", "q_norm", "k_norm", "wo")}
    n = x.shape[0]
    nh, nkv, d = z["nh"], z["nkv"], z["d"]
    h = _rms_norm(x, w["ln1"], eps)
    q = _matmul(h, w["wq"], precision).reshape(n, nh, d)
    k = _matmul(h, w["wk"], precision).reshape(n, nkv, d)
    v = _matmul(h, w["wv"], precision).reshape(n, nkv, d)
    q = _rope(_rms_norm(q, w["q_norm"], eps), pos, theta)
    k = _rope(_rms_norm(k, w["k_norm"], eps), pos, theta)
    k = jnp.repeat(k, nh // nkv, axis=1)    # q head i reads KV head i // group
    v = jnp.repeat(v, nh // nkv, axis=1)

    def some_queries(at):                   # the scores, a block of queries
        qb, ci, bi = (a[:, None] if a.ndim == 1 else a for a in at)
        sees = (((copy[None, :] == 0) & (blk[None, :] < bi))
                | ((copy[None, :] == ci) & (blk[None, :] == bi)))
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) / d ** 0.5
        p = jax.nn.softmax(jnp.where(sees[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    # padding queries (copy -1, a block of their own) see nothing and are cut
    pad = -n % _Q_BLOCK
    split = [jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1),
                     constant_values=-1).reshape((-1, _Q_BLOCK) + a.shape[1:])
             for a in (q, copy, blk)]
    a = jax.lax.map(some_queries, tuple(split)).reshape(-1, nh * d)[:n]
    return x + _matmul(a, w["wo"], precision)


def _experts(x, w, z, eps, norm_topk, precision):
    n = x.shape[0]
    # rows an expert takes gathered: twice what even routing would send it
    cap = max(1, 2 * n * z["topk"] // z["experts"])
    h = _rms_norm(x, w["ln2"].astype(jnp.float32), eps)
    s = jax.nn.softmax(
        _matmul(h, w["router"].astype(jnp.float32), precision), axis=-1)
    weight, chosen = jax.lax.top_k(s, z["topk"])
    if norm_topk:
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)

    def swiglu(rows, wg, wu, wd):
        return _matmul(jax.nn.silu(_matmul(rows, wg, precision))
                       * _matmul(rows, wu, precision), wd, precision)

    def one_expert(total, at):
        e, wg, wu, wd = at                  # upcast one expert at a time
        wg, wu, wd = (a.astype(jnp.float32) for a in (wg, wu, wd))
        w_e = jnp.sum(jnp.where(chosen == e, weight, 0.0), axis=1)   # [N]

        def gathered():
            idx = jnp.nonzero(w_e > 0, size=cap, fill_value=n)[0]
            rows = jnp.concatenate([h, jnp.zeros((1, h.shape[1]))])[idx]
            y = swiglu(rows, wg, wu, wd) * jnp.concatenate(
                [w_e, jnp.zeros((1,))])[idx][:, None]
            return jnp.zeros((n + 1, h.shape[1])).at[idx].add(y)[:n]

        def every_row():
            return w_e[:, None] * swiglu(h, wg, wu, wd)

        y = jax.lax.cond(jnp.sum(w_e > 0) > cap, every_row, gathered)
        return total + y, None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (jnp.arange(z["experts"]), w["wg"], w["wu"], w["wd"]))
    return x + routed


@functools.partial(jax.jit, static_argnames=("static",))
def _layer(x, w, pos, copy, blk, *, static):
    z, eps, theta, norm_topk, precision = static
    z = dict(z)
    x = _attention(x, w, pos, copy, blk, z, eps, theta, precision)
    return _experts(x, w, z, eps, norm_topk, precision)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x, norm, head, *, eps, precision):
    h = _rms_norm(x, norm.astype(jnp.float32), eps)
    return _matmul(h, head.astype(jnp.float32), precision)


def forward_rows(cfg, weights, tokens, pos, copy, want, precision="float32"):
    """Logits ``[len(want), vocab]`` at the rows ``want`` of a forward over
    rows ``tokens`` at positions ``pos`` (``copy``: see :func:`_attention`),
    layer by layer so that only one layer's float32 copy of the small
    weights (and of the experts, one) lives at a time."""
    q = cfg["generation"]["block_length"]
    static = (tuple(sorted(sizes(cfg).items())), cfg["rms_norm_eps"],
              float(cfg["rope_theta"]), bool(cfg["norm_topk_prob"]),
              precision)
    tokens, pos, copy = (jnp.asarray(a, jnp.int32)
                         for a in (tokens, pos, copy))
    x = weights["embed"][tokens].astype(jnp.float32)
    for w in weights["layers"]:
        x = _layer(x, w, pos, copy, pos // q, static=static)
    return _head(x[jnp.asarray(want, jnp.int32)], weights["norm"],
                 weights["head"], eps=cfg["rms_norm_eps"],
                 precision=precision)


def forward(cfg, weights, tokens, precision="float32"):
    """Logits ``[T, vocab]`` of one clean sequence under ``M``."""
    n = len(tokens)
    return forward_rows(cfg, weights, tokens, np.arange(n),
                        np.zeros(n, np.int32), np.arange(n), precision)


# ------------------------------------------------------- what was served

def num_transfer(cfg):
    """Positions unmasked at each denoising step."""
    g = cfg["generation"]
    base, extra = divmod(g["block_length"], g["denoising_steps"])
    return [base + (s < extra) for s in range(g["denoising_steps"])]


def sequential_steps(cfg, n_prompt, n_served):
    """The step at which ``remasking: sequential`` unmasks each served
    position: in every block the leftmost masked go first,
    ``num_transfer[s]`` of them at step ``s``."""
    g = cfg["generation"]
    if g["remasking"] != "sequential":
        raise ValueError(
            f"remasking {g['remasking']!r}: the order of unmasking does not "
            f"follow from the configuration; pass the served steps")
    q = g["block_length"]
    by_rank = [s for s, n in enumerate(num_transfer(cfg)) for _ in range(n)]
    steps, rank, block = [], 0, None
    for p in range(n_prompt, n_prompt + n_served):
        if p // q != block:
            block, rank = p // q, 0
        steps.append(by_rank[rank])
        rank += 1
    return steps


@functools.partial(jax.jit, static_argnames=("with_control",))
def _gaps(ref_logits, ctl_logits, served, with_control):
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, served[:, None], axis=1)[:, 0]
    out = {"served_gap": best - got,
           "conf": jnp.exp(best - jax.nn.logsumexp(ref_logits, axis=-1))}
    if with_control:
        first = jnp.argmax(ctl_logits, axis=-1)
        ctl = jnp.take_along_axis(ref_logits, first[:, None], axis=1)[:, 0]
        out["control_gap"] = best - ctl
    return out


def served_token_gaps(cfg, weights, prompt, served, pad_to, control=None,
                      steps=None, with_conf=False):
    """For one request: at each served token's position, in the noisy copy
    of the step it was unmasked at, how far the served token's reference
    logit lies below the reference's best (``served_gap``). With
    ``control`` (a precision) also the gap of the token that precision puts
    first at the same rows (``control_gap``); ``conf`` is the reference's
    own confidence there (its best token's probability). ``steps`` (default: what
    ``sequential`` gives): the denoising step each served token was
    unmasked at. ``with_conf``: also ``conf_gap``, how far the reference's
    confidence at the served position lay below its highest among the
    positions of the block that were masked before that step (it reads the
    logits at every masked row, so it is for small sizes).

    The clean half is padded to ``pad_to`` and each noisy copy to a
    multiple of 1,024 rows, so that a cell's requests compile few programs;
    padding rows lie in blocks of their own past everything that is read."""
    g = cfg["generation"]
    q, d, mask_id = g["block_length"], g["denoising_steps"], g["mask_token_id"]
    n_p, n_s = len(prompt), len(served)
    if steps is None:
        steps = sequential_steps(cfg, n_p, n_s)
    seq = np.asarray(list(prompt) + list(served), np.int32)
    total = n_p + n_s
    start = n_p // q * q                        # the first generated block
    end = -(-total // q) * q
    n_gen = end - start
    bucket = -(-n_gen // _NOISY_BUCKET) * _NOISY_BUCKET
    clean = max(pad_to, end)
    step_at = np.full(end, d, np.int64)         # never: stays [MASK]
    step_at[:n_p] = -1                          # known before any step
    step_at[n_p:total] = steps
    tokens = np.zeros(clean + d * bucket, np.int32)
    pos = np.arange(clean + d * bucket, dtype=np.int32)
    copy = np.zeros(clean + d * bucket, np.int32)
    tokens[:total] = seq
    gen = np.arange(start, end)
    seq_padded = np.zeros(end, np.int32)
    seq_padded[:total] = seq
    want, masked_rows = [], []
    for s in range(d):
        lo = clean + s * bucket
        known = step_at[gen] < s
        tokens[lo:lo + n_gen] = np.where(known, seq_padded[gen], mask_id)
        pos[lo:lo + n_gen] = gen
        # a copy's padding: blocks of its own past the clean half's end
        pos[lo + n_gen:lo + bucket] = clean + q * (1 + np.arange(bucket - n_gen))
        copy[lo:lo + bucket] = s + 1
        masked_rows.append({int(p): lo + i for i, p in enumerate(gen)
                            if not known[i] and n_p <= p < total})
    for p in range(n_p, total):
        want.append(masked_rows[step_at[p]][p])
    if with_conf:                               # every masked row, by copy
        extra = [r for rows in masked_rows for r in rows.values()]
        want = want + extra
    want = np.asarray(want, np.int32)
    n_want = -(-len(want) // _NOISY_BUCKET) * _NOISY_BUCKET
    rows = np.zeros(n_want, np.int32)
    rows[:len(want)] = want
    ref = forward_rows(cfg, weights, tokens, pos, copy, rows)
    ctl = (forward_rows(cfg, weights, tokens, pos, copy, rows, control)
           if control else ref)
    at = np.zeros(n_want, np.int32)
    at[:n_s] = served
    got = {k: np.asarray(v) for k, v in _gaps(
        ref, ctl, jnp.asarray(at), with_control=bool(control)).items()}
    out = {k: v[:n_s] for k, v in got.items()}
    if with_conf:
        conf = dict(zip(want[n_s:].tolist(), got["conf"][n_s:len(want)]))
        gaps = []
        for i, p in enumerate(range(n_p, total)):
            rows_s = masked_rows[step_at[p]]
            block = [r for pp, r in rows_s.items() if pp // q == p // q]
            gaps.append(max(conf[r] for r in block) - got["conf"][i])
        out["conf_gap"] = np.asarray(gaps)
    return out
