"""Flash attention Pallas TPU kernel (reference capability:
phi/kernels/gpu/flash_attn_kernel.cu:673 wrapping third_party/flashattn).

TPU-native blockwise online-softmax attention:
  forward — grid (B*H, Sq/BQ, Sk/BK); running (m, l, acc) in VMEM scratch
            persisted across the sequential k dimension; causal blocks skipped.
  backward — two kernels: dq (accumulate over k blocks) and dk/dv (accumulate
            over q blocks), recomputing P from the saved logsumexp; f32
            accumulation throughout; O(S) memory instead of O(S^2).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BQ = 128
BK = 128
LOG2E = 1.4426950408889634
LN2 = 0.6931471805599453


def _interpret() -> bool:
    # CPU has no Mosaic backend; run kernels in interpret mode (tests/CI)
    import jax
    return jax.default_backend() == "cpu"
NEG_INF = -1e30


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s, acc_s, *,
                causal, nk, bq, bk):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    run = True
    diag = False
    if causal:
        run = (j * bk) <= (i * bq + bq - 1)
        diag = (j * bk + bk - 1) > (i * bq)   # block crosses the diagonal

    def _body(masked):
        # MXU operands stay in the input dtype (bf16 native mode — f32
        # operands would force the slow multi-pass f32 MXU path); softmax
        # statistics and accumulation are f32. VPU-mindful: q is pre-scaled
        # by scale*log2(e) OUTSIDE the kernel, so scores arrive in the log2
        # domain — no (bq,bk)-wide scale multiply, and exp2 instead of exp.
        # Blocks fully below the causal diagonal skip the iota/compare/select
        # mask entirely (the hot interior is mask-free).
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT)
        if masked:
            rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev = m_s[:, 0]
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp2(s - m_new[:, None])
        corr = jnp.exp2(m_prev - m_new)
        l_new = l_s[:, 0] * corr + jnp.sum(p, axis=1)
        acc_s[:] = acc_s[:] * corr[:, None] + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT)
        m_s[:] = jnp.broadcast_to(m_new[:, None], m_s.shape)
        l_s[:] = jnp.broadcast_to(l_new[:, None], l_s.shape)

    if causal:
        @pl.when(run & diag)
        def _masked():
            _body(True)

        @pl.when(run & ~diag)
        def _interior():
            _body(False)
    else:
        _body(False)

    @pl.when(j == nk - 1)
    def _finish():
        l = l_s[:, 0]
        o_ref[0] = (acc_s[:] / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)
        # running stats live in the log2 domain; stored lse stays natural
        lse_ref[0] = ((m_s[:, 0] + jnp.log2(jnp.maximum(l, 1e-30))) * LN2
                      )[:, None] + jnp.zeros_like(lse_ref[0])


def _check_divisible(Sq, Sk, D, bq=None, bk=None):
    bq, bk = bq or BQ, bk or BK
    if Sq % bq != 0 or Sk % bk != 0:
        raise ValueError(
            f"flash attention requires seq lengths divisible by ({bq}, {bk}) "
            f"(got q {Sq}, kv {Sk}); pad or use the XLA fallback")
    if D % 64 != 0:
        raise ValueError(f"flash attention requires head_dim % 64 == 0, got {D}")


def _kv_index(nh, nhk):
    """q-head grid index -> kv row index in a [B*nhk, Sk, D] tensor (GQA:
    kv head = q head // group, computed in the BlockSpec instead of
    materializing jnp.repeat'd K/V)."""
    rep = nh // nhk

    def index(b, i, j):
        return (b // nh) * nhk + (b % nh) // rep, j, 0

    return index


def _flash_fwd(q3, k3, v3, scale, causal, nh, nhk, bq=BQ, bk=BK):
    """q3 [B*nh, Sq, D], k3/v3 [B*nhk, Sk, D] -> (o [B*nh, Sq, D],
    lse [B*nh, Sq, 128])."""
    BH, Sq, D = q3.shape
    Sk = k3.shape[1]
    _check_divisible(Sq, Sk, D, bq, bk)
    nq, nk = Sq // bq, Sk // bk
    kvix = _kv_index(nh, nhk)
    # fold softmax scale + the exp->exp2 change of base into q once (fuses
    # into the producing op); the kernel then runs scale-free in log2 domain
    q3 = (q3.astype(jnp.float32) * (scale * LOG2E)).astype(q3.dtype)
    kern = functools.partial(_fwd_kernel, causal=causal, nk=nk, bq=bq, bk=bk)
    o, lse = pl.pallas_call(
        kern,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), kvix),
            pl.BlockSpec((1, bk, D), kvix),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 128), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sq, D), q3.dtype),
            jax.ShapeDtypeStruct((BH, Sq, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_fwd",
    )(q3, k3, v3)
    return o, lse


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dq_ref, dq_s, *,
               scale, causal, nk, bq, bk):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dq_s[:] = jnp.zeros_like(dq_s)

    run = True
    diag = False
    if causal:
        run = (j * bk) <= (i * bq + bq - 1)
        diag = (j * bk + bk - 1) > (i * bq)

    def _body(masked):
        # bf16 MXU operands, f32 softmax math/accumulation. Scores go
        # through the log2 domain like the forward: q is rescaled on its
        # small (bq, D) tile, so no (bq, bk)-wide multiplies remain.
        q = (q_ref[0].astype(jnp.float32) * (scale * LOG2E)).astype(
            q_ref.dtype)
        k = k_ref[0]
        do = do_ref[0]
        lse2 = lse_ref[0][:, 0] * LOG2E
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT)
        if masked:
            rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        p = jnp.exp2(s - lse2[:, None])
        dp = jax.lax.dot_general(do, v_ref[0], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT)
        delta = jnp.sum(do.astype(jnp.float32) *
                        o_ref[0].astype(jnp.float32), axis=1)
        ds = p * (dp - delta[:, None])
        dq_s[:] = dq_s[:] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT) * scale

    if causal:
        @pl.when(run & diag)
        def _masked():
            _body(True)

        @pl.when(run & ~diag)
        def _interior():
            _body(False)
    else:
        _body(False)

    @pl.when(j == nk - 1)
    def _finish():
        dq_ref[0] = dq_s[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dk_ref, dv_ref,
                dk_s, dv_s, *, scale, causal, nq, nt, bq, bk):
    j = pl.program_id(1)  # k block
    t = pl.program_id(2)  # combined (group q-head, q block) axis, sequential —
    i = t % nq            # dk/dv accumulate across the GQA group's q heads

    @pl.when(t == 0)
    def _init():
        dk_s[:] = jnp.zeros_like(dk_s)
        dv_s[:] = jnp.zeros_like(dv_s)

    run = True
    diag = False
    if causal:
        run = (j * bk) <= (i * bq + bq - 1)
        diag = (j * bk + bk - 1) > (i * bq)

    def _body(masked):
        # bf16 MXU operands, f32 softmax math/accumulation; log2-domain
        # scores with q rescaled on its small tile (see _dq_kernel)
        q = q_ref[0]
        q2 = (q.astype(jnp.float32) * (scale * LOG2E)).astype(q_ref.dtype)
        k = k_ref[0]
        do = do_ref[0]
        lse2 = lse_ref[0][:, 0] * LOG2E
        s = jax.lax.dot_general(q2, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT)
        if masked:
            rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        p = jnp.exp2(s - lse2[:, None])
        dv_s[:] = dv_s[:] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT)
        dp = jax.lax.dot_general(do, v_ref[0], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT)
        delta = jnp.sum(do.astype(jnp.float32) *
                        o_ref[0].astype(jnp.float32), axis=1)
        ds = p * (dp - delta[:, None])
        dk_s[:] = dk_s[:] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT) * scale

    if causal:
        @pl.when(run & diag)
        def _masked():
            _body(True)

        @pl.when(run & ~diag)
        def _interior():
            _body(False)
    else:
        _body(False)

    @pl.when(t == nt - 1)
    def _finish():
        dk_ref[0] = dk_s[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[:].astype(dv_ref.dtype)


def _flash_bwd(q3, k3, v3, o3, lse, do3, scale, causal, nh, nhk, bq=BQ,
               bk=BK):
    BH, Sq, D = q3.shape
    Sk = k3.shape[1]
    _check_divisible(Sq, Sk, D, bq, bk)
    nq, nk = Sq // bq, Sk // bk
    rep = nh // nhk
    kvix = _kv_index(nh, nhk)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal, nk=nk,
                          bq=bq, bk=bk),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), kvix),
            pl.BlockSpec((1, bk, D), kvix),
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 128), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, D), q3.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=_interpret(),
        name="flash_bwd_dq",
    )(q3, k3, v3, do3, o3, lse)

    # dk/dv: grid batch is the KV row; the combined t axis walks the GQA
    # group's q heads × q blocks sequentially so dk/dv accumulate the whole
    # group in VMEM scratch — no materialized head repeat anywhere.
    BHk = k3.shape[0]
    nt = rep * nq

    def qix(b, j, t):
        return (b // nhk) * nh + (b % nhk) * rep + t // nq, t % nq, 0

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal, nq=nq,
                          nt=nt, bq=bq, bk=bk),
        grid=(BHk, nk, nt),
        in_specs=[
            pl.BlockSpec((1, bq, D), qix),
            pl.BlockSpec((1, bk, D), lambda b, j, t: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, t: (b, j, 0)),
            pl.BlockSpec((1, bq, D), qix),
            pl.BlockSpec((1, bq, D), qix),
            pl.BlockSpec((1, bq, 128), lambda b, j, t: qix(b, j, t)[:2] + (0,)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda b, j, t: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, t: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BHk, Sk, D), k3.dtype),
            jax.ShapeDtypeStruct((BHk, Sk, D), v3.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_bwd_dkv",
    )(q3, k3, v3, do3, o3, lse)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash3(q3, k3, v3, scale, causal, nh, nhk, bq, bk):
    o, _ = _flash_fwd(q3, k3, v3, scale, causal, nh, nhk, bq, bk)
    return o


def _flash3_fwd(q3, k3, v3, scale, causal, nh, nhk, bq, bk):
    o, lse = _flash_fwd(q3, k3, v3, scale, causal, nh, nhk, bq, bk)
    return o, (q3, k3, v3, o, lse)


def _flash3_bwd(scale, causal, nh, nhk, bq, bk, res, do):
    q3, k3, v3, o, lse = res
    dq, dk, dv = _flash_bwd(q3, k3, v3, o, lse, do, scale, causal, nh, nhk,
                            bq, bk)
    return dq, dk, dv


_flash3.defvjp(_flash3_fwd, _flash3_bwd)


# Ordered by preference: cands[0] (the first divisibility+VMEM-viable entry)
# is the untuned default, so large blocks lead. Measured on v5e at
# B4/S1024/H12/D64 bf16: (512,1024) runs fwd+bwd 6x faster than (128,128) —
# fewer grid steps amortize MXU pipeline startup, and causal block-skipping
# still prunes the strictly-upper-triangle k blocks.
_BLOCK_CANDIDATES = [(512, 1024), (1024, 512), (512, 512), (1024, 1024),
                     (256, 512), (512, 256), (256, 256), (128, 256),
                     (256, 128), (512, 128), (128, 512), (128, 128)]


def _block_candidates(Sq, Sk, D, dtype):
    """Valid (bq, bk) choices: divisibility + a VMEM budget estimate
    (q/o/dq blocks bq*D, k/v bk*D, lse/m/l bq*128; f32 scratch; ~2x for
    pipelining double-buffering; PLUS the bq*bk score tiles — the _dkv
    backward materializes up to ~4 of s/p/dp/ds in f32, which dominates at
    the large blocks; keep under ~12MB of the 16MB/core VMEM)."""
    out = []
    for bq, bk in _BLOCK_CANDIDATES:
        if Sq % bq or Sk % bk:
            continue
        vmem = (3 * bq * D + 2 * bk * D + 3 * bq * 128) * 4 * 2 \
            + 4 * bq * bk * 4
        if vmem <= 12 * 1024 * 1024:
            out.append((bq, bk))
    return out or [(BQ, BK)]


def _pick_blocks(q3, k3, v3, causal):
    """Autotuned (bq, bk) for this shape (reference: autotune/switch_autotune
    picking conv/matmul algos). Tunes the forward kernel only — bwd shares
    the blocking — and only on concrete arrays outside any jit trace."""
    from .. import autotune as at
    BH, Sq, D = q3.shape
    Sk = k3.shape[1]
    cands = _block_candidates(Sq, Sk, D, q3.dtype)
    if len(cands) == 1:
        return cands[0]
    key = at.cache_key("flash_fwd", BH, Sq, Sk, D, q3.dtype, causal)

    def build(cfg):
        bq, bk = cfg

        def run(q, k, v):
            nh = nhk = 1  # timing proxy: head mapping doesn't affect blocking
            return _flash_fwd(q, k, v, 1.0, causal, nh, nhk, bq, bk)[0]
        return run

    # time on single-head views so tuning cost stays low
    return tuple(at.tune(key, cands, build, (q3[:1], k3[:1], v3[:1])))


def warm_autotune(q, k, v, causal=True):
    """Tune blocks for this [B, S, H, D] geometry from CONCRETE arrays.

    Dispatch wrappers call this before entering apply_op: inside apply_op the
    kernel only ever sees jax.vjp tracers, where tuning is impossible — but
    the cache lookup in _pick_blocks keys on static shapes, so one concrete
    warm call makes every traced call use the tuned blocks."""
    from .. import autotune as at
    if not at.enabled() or isinstance(q, jax.core.Tracer):
        return
    B, Sq, H, D = q.shape
    Hk = k.shape[2]
    q3 = jnp.moveaxis(q, 2, 1).reshape(B * H, Sq, D)
    k3 = jnp.moveaxis(k, 2, 1).reshape(B * Hk, k.shape[1], D)
    v3 = jnp.moveaxis(v, 2, 1).reshape(B * Hk, v.shape[1], D)
    _pick_blocks(q3, k3, v3, causal)


# ---------------------------------------------------------------------------
# Layout-direct [B, S, H, D] kernels (MHA, nh == nhk).
#
# The 3D kernels above need [B*H, S, D] operands, which XLA materializes with
# physical layout copies around every custom call (~230us per qkv tensor per
# layer at GPT-2 b16 — profiled as the 'data formatting' bucket). These
# variants grid over (B, H/hb, Sq/bq, Sk/bk) with blocks (1, bq, hb, D) taken
# straight from the [B, S, H, D] array: the inner (hb, D) dims are contiguous
# in HBM so the DMA is dense, no transpose exists anywhere, and grid steps
# drop by hb. The head loop runs inside the kernel over VMEM slices.
# Blocks fully below the causal diagonal take a mask-free fast path (no
# iota/compare/select — pure VPU savings on the hot interior).
# ---------------------------------------------------------------------------

_B_BQ, _B_BK = 512, 512


def _bshd_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s, acc_s, *,
                     scale, causal, nk, bq, bk, hb, d):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    run = True
    diag = False
    if causal:
        run = (j * bk) <= (i * bq + bq - 1)
        diag = (j * bk + bk - 1) > (i * bq)   # block crosses the diagonal

    def compute(masked):
        qf = q_ref[0]
        kf = k_ref[0]
        vf = v_ref[0]
        if masked:
            rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            cm = rows >= cols
        for h in range(hb):
            q = qf[:, h * d:(h + 1) * d]
            k = kf[:, h * d:(h + 1) * d]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32,
                                    precision=jax.lax.Precision.DEFAULT) * scale
            if masked:
                s = jnp.where(cm, s, NEG_INF)
            m_prev = m_s[h, :, 0]
            l_prev = l_s[h, :, 0]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
            p = jnp.exp(s - m_new[:, None])
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + jnp.sum(p, axis=1)
            acc_s[h] = acc_s[h] * corr[:, None] + jax.lax.dot_general(
                p.astype(vf.dtype), vf[:, h * d:(h + 1) * d],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.DEFAULT)
            m_s[h] = jnp.broadcast_to(m_new[:, None], (bq, 128))
            l_s[h] = jnp.broadcast_to(l_new[:, None], (bq, 128))

    if causal:
        @pl.when(run & diag)
        def _masked():
            compute(True)

        @pl.when(run & ~diag)
        def _interior():
            compute(False)
    else:
        compute(False)

    @pl.when(j == nk - 1)
    def _finish():
        outs = []
        for h in range(hb):
            l = jnp.maximum(l_s[h, :, 0], 1e-30)
            outs.append((acc_s[h] / l[:, None]).astype(o_ref.dtype))
            lse_ref[h] = (m_s[h, :, 0] + jnp.log(l))[:, None] \
                + jnp.zeros_like(lse_ref[h])
        o_ref[0] = jnp.concatenate(outs, axis=1)


def _bshd_fwd(q, k, v, scale, causal, bq, bk, hb):
    """q/k/v [B, S, H, D] -> (o [B, S, H, D], lse [B*H, Sq, 128]).

    Operands are viewed as [B, S, H*D] (a free bitcast): blocks are dense
    (8,128)-tiled 2D slabs, per-head operands are static lane slices."""
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    nq, nk = Sq // bq, Sk // bk
    kern = functools.partial(_bshd_fwd_kernel, scale=scale, causal=causal,
                             nk=nk, bq=bq, bk=bk, hb=hb, d=D)
    o, lse = pl.pallas_call(
        kern,
        grid=(B, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, H * D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, H * D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, H * D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, H * D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((hb, bq, 128), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Sq, H * D), q.dtype),
            jax.ShapeDtypeStruct((B * H, Sq, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((hb, bq, 128), jnp.float32),
            pltpu.VMEM((hb, bq, 128), jnp.float32),
            pltpu.VMEM((hb, bq, D), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_fwd",
    )(q.reshape(B, Sq, H * D), k.reshape(B, Sk, H * D),
      v.reshape(B, Sk, H * D))
    return o.reshape(B, Sq, H, D), lse


def _bshd_dq_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dq_ref, dq_s,
                    *, scale, causal, nk, bq, bk, hb, d):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dq_s[:] = jnp.zeros_like(dq_s)

    run = True
    diag = False
    if causal:
        run = (j * bk) <= (i * bq + bq - 1)
        diag = (j * bk + bk - 1) > (i * bq)

    def compute(masked):
        qf, kf, vf, dof, of = q_ref[0], k_ref[0], v_ref[0], do_ref[0], o_ref[0]
        if masked:
            rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            cm = rows >= cols
        for h in range(hb):
            sl = slice(h * d, (h + 1) * d)
            q, k, do = qf[:, sl], kf[:, sl], dof[:, sl]
            lse = lse_ref[h][:, 0]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32,
                                    precision=jax.lax.Precision.DEFAULT) * scale
            if masked:
                s = jnp.where(cm, s, NEG_INF)
            p = jnp.exp(s - lse[:, None])
            dp = jax.lax.dot_general(do, vf[:, sl], (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32,
                                     precision=jax.lax.Precision.DEFAULT)
            delta = jnp.sum(do.astype(jnp.float32) *
                            of[:, sl].astype(jnp.float32), axis=1)
            ds = p * (dp - delta[:, None])
            dq_s[h] = dq_s[h] + jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.DEFAULT) * scale

    if causal:
        @pl.when(run & diag)
        def _masked():
            compute(True)

        @pl.when(run & ~diag)
        def _interior():
            compute(False)
    else:
        compute(False)

    @pl.when(j == nk - 1)
    def _finish():
        dq_ref[0] = jnp.concatenate(
            [dq_s[h].astype(dq_ref.dtype) for h in range(hb)], axis=1)


def _bshd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dk_ref,
                     dv_ref, dk_s, dv_s, *, scale, causal, nq, bq, bk, hb, d):
    j = pl.program_id(1)   # k block
    i = pl.program_id(2)   # q block (sequential accumulation axis)

    @pl.when(i == 0)
    def _init():
        dk_s[:] = jnp.zeros_like(dk_s)
        dv_s[:] = jnp.zeros_like(dv_s)

    run = True
    diag = False
    if causal:
        run = (j * bk) <= (i * bq + bq - 1)
        diag = (j * bk + bk - 1) > (i * bq)

    def compute(masked):
        qf, kf, vf, dof, of = q_ref[0], k_ref[0], v_ref[0], do_ref[0], o_ref[0]
        if masked:
            rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            cm = rows >= cols
        for h in range(hb):
            sl = slice(h * d, (h + 1) * d)
            q, k, do = qf[:, sl], kf[:, sl], dof[:, sl]
            lse = lse_ref[h][:, 0]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32,
                                    precision=jax.lax.Precision.DEFAULT) * scale
            if masked:
                s = jnp.where(cm, s, NEG_INF)
            p = jnp.exp(s - lse[:, None])
            dv_s[h] = dv_s[h] + jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.DEFAULT)
            dp = jax.lax.dot_general(do, vf[:, sl], (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32,
                                     precision=jax.lax.Precision.DEFAULT)
            delta = jnp.sum(do.astype(jnp.float32) *
                            of[:, sl].astype(jnp.float32), axis=1)
            ds = p * (dp - delta[:, None])
            dk_s[h] = dk_s[h] + jax.lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.DEFAULT) * scale

    if causal:
        @pl.when(run & diag)
        def _masked():
            compute(True)

        @pl.when(run & ~diag)
        def _interior():
            compute(False)
    else:
        compute(False)

    @pl.when(i == nq - 1)
    def _finish():
        dk_ref[0] = jnp.concatenate(
            [dk_s[h].astype(dk_ref.dtype) for h in range(hb)], axis=1)
        dv_ref[0] = jnp.concatenate(
            [dv_s[h].astype(dv_ref.dtype) for h in range(hb)], axis=1)


def _bshd_bwd(q, k, v, o, lse, do, scale, causal, bq, bk, hb):
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    nq, nk = Sq // bq, Sk // bk
    q2 = q.reshape(B, Sq, H * D)
    k2 = k.reshape(B, Sk, H * D)
    v2 = v.reshape(B, Sk, H * D)
    o2 = o.reshape(B, Sq, H * D)
    do2 = do.reshape(B, Sq, H * D)
    qspec = pl.BlockSpec((1, bq, H * D), lambda b, i, j: (b, i, 0))
    kspec = pl.BlockSpec((1, bk, H * D), lambda b, i, j: (b, j, 0))
    lspec = pl.BlockSpec((hb, bq, 128), lambda b, i, j: (b, i, 0))
    dq = pl.pallas_call(
        functools.partial(_bshd_dq_kernel, scale=scale, causal=causal, nk=nk,
                          bq=bq, bk=bk, hb=hb, d=D),
        grid=(B, nq, nk),
        in_specs=[qspec, kspec, kspec, qspec, qspec, lspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((B, Sq, H * D), q.dtype),
        scratch_shapes=[pltpu.VMEM((hb, bq, D), jnp.float32)],
        interpret=_interpret(),
        name="flash_bwd_dq",
    )(q2, k2, v2, do2, o2, lse)
    qspec_t = pl.BlockSpec((1, bq, H * D), lambda b, j, i: (b, i, 0))
    kspec_t = pl.BlockSpec((1, bk, H * D), lambda b, j, i: (b, j, 0))
    lspec_t = pl.BlockSpec((hb, bq, 128), lambda b, j, i: (b, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bshd_dkv_kernel, scale=scale, causal=causal,
                          nq=nq, bq=bq, bk=bk, hb=hb, d=D),
        grid=(B, nk, nq),
        in_specs=[qspec_t, kspec_t, kspec_t, qspec_t, qspec_t, lspec_t],
        out_specs=[kspec_t, kspec_t],
        out_shape=[
            jax.ShapeDtypeStruct((B, Sk, H * D), k.dtype),
            jax.ShapeDtypeStruct((B, Sk, H * D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((hb, bk, D), jnp.float32),
            pltpu.VMEM((hb, bk, D), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_bwd_dkv",
    )(q2, k2, v2, do2, o2, lse)
    return (dq.reshape(B, Sq, H, D), dk.reshape(B, Sk, H, D),
            dv.reshape(B, Sk, H, D))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_bshd(q, k, v, scale, causal, bq, bk, hb):
    o, _ = _bshd_fwd(q, k, v, scale, causal, bq, bk, hb)
    return o


def _flash_bshd_fwd(q, k, v, scale, causal, bq, bk, hb):
    o, lse = _bshd_fwd(q, k, v, scale, causal, bq, bk, hb)
    return o, (q, k, v, o, lse)


def _flash_bshd_bwd(scale, causal, bq, bk, hb, res, do):
    q, k, v, o, lse = res
    return _bshd_bwd(q, k, v, o, lse, do, scale, causal, bq, bk, hb)


_flash_bshd.defvjp(_flash_bshd_fwd, _flash_bshd_bwd)


def _bshd_config(B, Sq, Sk, H, D, dtype):
    """(bq, bk, hb) for the layout-direct path, or None if it doesn't apply.

    Mosaic requires the last two block dims to be (8,128)-divisible OR equal
    to the array dims, so the head axis cannot be partially blocked: hb == H
    always, and the path only applies when a whole-H block fits VMEM.
    Estimate: q/o blocks bq*H*D, k/v bk*H*D (x2 double-buffer), f32 scratch
    H*bq*(2*128+D), f32 score tiles ~3*bq*bk per live head."""
    itemsize = jnp.dtype(dtype).itemsize
    for bq, bk in ((_B_BQ, _B_BK), (256, 512), (256, 256), (128, 256),
                   (128, 128)):
        if Sq % bq or Sk % bk:
            continue
        # the unrolled per-head loop keeps ~1.5 f32 score tiles live PER HEAD
        # (measured: (256,512,H=12) hit 17.25M scoped vmem vs a 16M limit
        # when the estimate ignored this term)
        vmem = (2 * (2 * bq + 2 * bk) * H * D * itemsize
                + H * bq * (2 * 128 + D) * 4
                + int(1.5 * H * bq * bk * 4))
        if vmem <= 12 * 1024 * 1024:
            return bq, bk, H
    return None


def flash_attention_bshd(q, k, v, causal=True, scale=None):
    """[B, S, H, D] flash attention. MHA (nh == nhk) uses the layout-direct
    kernels (no transposes, dense DMA); GQA falls back to the [B*H, S, D]
    kernels whose BlockSpecs index kv-head = q-head // group — K/V are never
    repeated in HBM (at Llama-3-8B's 32q/8kv that repeat would be 4x KV
    memory). Block sizes come from the autotuner cache when
    FLAGS_use_autotune is set."""
    B, Sq, H, D = q.shape
    Hk = k.shape[2]
    if H % Hk != 0:
        raise ValueError(f"q heads ({H}) must be a multiple of kv heads ({Hk})")
    s = scale if scale is not None else 1.0 / math.sqrt(D)
    from ...core import flags as _flags
    if H == Hk and _flags.flag("flash_layout_direct"):
        # opt-in: skips the [B*H,S,D] relayout copies, but the per-head lane
        # slicing inside the kernel costs more than the copies save on v5e at
        # GPT-2 shapes (measured 1.18 vs 0.93 ms/layer fwd) — off by default
        cfg = _bshd_config(B, Sq, k.shape[1], H, D, q.dtype)
        if cfg is not None:
            bq, bk, hb = cfg
            return _flash_bshd(q, k, v, s, causal, bq, bk, hb)
    q3 = jnp.moveaxis(q, 2, 1).reshape(B * H, Sq, D)
    k3 = jnp.moveaxis(k, 2, 1).reshape(B * Hk, k.shape[1], D)
    v3 = jnp.moveaxis(v, 2, 1).reshape(B * Hk, v.shape[1], D)
    bq, bk = _pick_blocks(q3, k3, v3, causal)
    o3 = _flash3(q3, k3, v3, s, causal, H, Hk, bq, bk)
    return jnp.moveaxis(o3.reshape(B, H, Sq, D), 1, 2)


def supported(q_shape, kv_shape=None, dtype=None) -> bool:
    """Single dispatch predicate for the Pallas path ([B, S, H, D] layouts)."""
    B, S, H, D = q_shape
    ok = (S % BQ == 0) and (D % 64 == 0)
    if kv_shape is not None:
        Sk, Hk = kv_shape[1], kv_shape[2]
        ok = ok and (Sk % BK == 0) and (Hk > 0) and (H % Hk == 0)
    return ok
