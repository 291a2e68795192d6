"""Flash attention Pallas TPU kernel (reference capability:
phi/kernels/gpu/flash_attn_kernel.cu:673 wrapping third_party/flashattn).

TPU-native blockwise online-softmax attention over [B*H, S, D] operands:
  forward — grid (B*H, Sq/BQ, Sk/BK); running (m, l, acc) in VMEM scratch
            persisted across the sequential k dimension.
  backward — two kernels: dq (accumulate over k blocks) and dk/dv (accumulate
            over q blocks), recomputing P from the saved logsumexp; f32
            accumulation throughout; O(S) memory instead of O(S^2).

Under the causal mask a (bq, bk) tile wholly above the diagonal does no work,
a tile wholly below it skips the mask, and only a tile the diagonal crosses
pays for it.  What that prunes depends on the tiling: at S = 1024 the large
blocks these kernels run fastest in, (512, 1024), cross the diagonal
everywhere and compute the whole square masked.  So causal self-attention
with one kv head a q head takes the row-block kernels below: hb heads and
one b-row block a step against everything it reaches, the blocks on and
below the diagonal alone.
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


def _tile_shares(Sq, Sk, bq, bk, causal):
    """(computed, masked): the shares, in %, of the (Sq/bq) x (Sk/bk) tile
    grid that run and that take the causal mask.  Host arithmetic on the
    static tiling, at trace time."""
    if not causal:  # graftlint: disable=trace-safety
        return 100.0, 0.0
    tiles = [((j * bk) <= (i * bq + bq - 1), (j * bk + bk - 1) > (i * bq))
             for i in range(Sq // bq) for j in range(Sk // bk)]
    return (100.0 * sum(ran for ran, _ in tiles) / len(tiles),
            100.0 * sum(ran and diag for ran, diag in tiles) / len(tiles))


def _record_tiles(kernels, Sq, Sk, bq, bk, causal):
    """Sets flash_attention_tile_share for the kernels being traced."""
    from ...observability import FLASH_TILE_SHARE
    computed, masked = _tile_shares(Sq, Sk, bq, bk, causal)
    for kernel in kernels:
        FLASH_TILE_SHARE.set(computed, kernel=kernel, kind="computed")
        FLASH_TILE_SHARE.set(masked, kernel=kernel, kind="masked")


def _flash_fwd(q3, k3, v3, scale, causal, nh, nhk, bq=BQ, bk=BK):
    """q3 [B*nh, Sq, D], k3/v3 [B*nhk, Sk, D] -> (o [B*nh, Sq, D],
    lse [B*nh, Sq, 128])."""
    BH, Sq, D = q3.shape
    Sk = k3.shape[1]
    _check_divisible(Sq, Sk, D, bq, bk)
    nq, nk = Sq // bq, Sk // bk
    _record_tiles(("flash_fwd",), Sq, Sk, bq, bk, causal)
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
    _record_tiles(("flash_bwd_dq", "flash_bwd_dkv"), Sq, Sk, bq, bk, causal)
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


# ---------------------------------------------------------------------------
# Row-block kernels: causal self-attention (Sq == Sk) with one kv head a q
# head.  A grid step takes hb heads and ONE b-row block of q (or, for dk/dv,
# of k) against the whole span of the other side that the causal mask lets
# it reach: the blocks before its diagonal mask-free, the diagonal block
# masked, nothing after it.  The k side of a head block is fetched once and
# stays while the q blocks walk past it.  With every reachable column in one
# step the softmax needs no running (m, l) carried between steps; the
# branch a q block takes is static (pl.when on its index), so each has
# static shapes.  Every Python `if` below is on a static block index or
# flag; the lint pragmas on them say so, since the lint takes a body bound
# by functools.partial as traced in all its parameters.
# ---------------------------------------------------------------------------

_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def _dot(a, b, dims):
    # MXU operands stay in the input dtype; accumulation is f32
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32,
                               precision=jax.lax.Precision.DEFAULT)


def _keep(b):
    """The causal mask of a diagonal (b, b) block."""
    return (jax.lax.broadcasted_iota(jnp.int32, (b, b), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (b, b), 1))


def _each_block(nb, body):
    """body(c) under pl.when for the block index c the grid step is at, so
    each branch has static shapes."""
    i = pl.program_id(1)
    for c in range(nb):
        pl.when(i == c)(functools.partial(body, c))


def _rows_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, b, nb):
    def _row(c):
        keep = _keep(b)
        for h in range(q_ref.shape[0]):
            q = q_ref[h]
            sd = jnp.where(keep, _dot(q, k_ref[h, c * b:(c + 1) * b],
                                      _NT), NEG_INF)
            m = jnp.max(sd, axis=1)
            if c:
                si = _dot(q, k_ref[h, :c * b], _NT)
                m = jnp.maximum(m, jnp.max(si, axis=1))
            pd = jnp.exp2(sd - m[:, None])
            l = jnp.sum(pd, axis=1)
            acc = _dot(pd.astype(v_ref.dtype),
                       v_ref[h, c * b:(c + 1) * b], _NN)
            if c:
                pi = jnp.exp2(si - m[:, None])
                l = l + jnp.sum(pi, axis=1)
                acc = acc + _dot(pi.astype(v_ref.dtype), v_ref[h, :c * b],
                                 _NN)
            o_ref[h] = (acc / l[:, None]).astype(o_ref.dtype)
            lse_ref[h] = jnp.broadcast_to(
                ((m + jnp.log2(l)) * LN2)[:, None], (b, 128))

    _each_block(nb, _row)


def _rows_dq_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dq_ref, *,
                    scale, b, nb):
    def _row(c):
        keep = _keep(b)
        for h in range(q_ref.shape[0]):
            q = (q_ref[h].astype(jnp.float32) * (scale * LOG2E)).astype(
                q_ref.dtype)
            do = do_ref[h]
            lse2 = (lse_ref[h][:, 0] * LOG2E)[:, None]
            delta = jnp.sum(do.astype(jnp.float32) *
                            o_ref[h].astype(jnp.float32), axis=1)[:, None]

            def part(lo, hi, masked, h=h, q=q, do=do, lse2=lse2,
                     delta=delta):
                k = k_ref[h, lo:hi]
                s = _dot(q, k, _NT)
                if masked:
                    s = jnp.where(keep, s, NEG_INF)
                p = jnp.exp2(s - lse2)
                ds = p * (_dot(do, v_ref[h, lo:hi], _NT) - delta)
                return _dot(ds.astype(k.dtype), k, _NN)

            dq = part(c * b, (c + 1) * b, True)
            if c:  # graftlint: disable=trace-safety
                dq = dq + part(0, c * b, False)
            dq_ref[h] = (dq * scale).astype(dq_ref.dtype)

    _each_block(nb, _row)


def _rows_dkv_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dk_ref,
                     dv_ref, *, scale, b, nb):
    S = nb * b

    def _col(c):
        keep = _keep(b)
        for h in range(k_ref.shape[0]):
            k = k_ref[h]
            v = v_ref[h]

            def part(lo, hi, masked, h=h, k=k, v=v):
                q = q_ref[h, lo:hi]
                do = do_ref[h, lo:hi]
                q2 = (q.astype(jnp.float32) * (scale * LOG2E)).astype(
                    q.dtype)
                s = _dot(q2, k, _NT)
                if masked:  # graftlint: disable=trace-safety
                    s = jnp.where(keep, s, NEG_INF)
                p = jnp.exp2(s - (lse_ref[h, lo:hi][:, 0] * LOG2E)[:, None])
                dv = _dot(p.astype(do.dtype), do, _TN)
                delta = jnp.sum(do.astype(jnp.float32) *
                                o_ref[h, lo:hi].astype(jnp.float32), axis=1)
                ds = p * (_dot(do, v, _NT) - delta[:, None])
                return _dot(ds.astype(q.dtype), q, _TN), dv

            dk, dv = part(c * b, (c + 1) * b, True)
            if c < nb - 1:  # graftlint: disable=trace-safety
                dk2, dv2 = part((c + 1) * b, S, False)
                dk, dv = dk + dk2, dv + dv2
            dk_ref[h] = (dk * scale).astype(dk_ref.dtype)
            dv_ref[h] = dv.astype(dv_ref.dtype)

    _each_block(nb, _col)


@functools.partial(jax.jit,
                   static_argnames=("scale", "b", "hb"))
def _rows_fwd(q3, k3, v3, scale, b, hb):
    """Causal q3/k3/v3 [B*H, S, D] -> (o [B*H, S, D], lse [B*H, S, 128])."""
    BH, S, D = q3.shape
    nb = S // b
    _record_tiles(("flash_fwd",), S, S, b, b, True)
    blk = pl.BlockSpec((hb, b, D), lambda x, i: (x, i, 0))
    whole = pl.BlockSpec((hb, S, D), lambda x, i: (x, 0, 0))
    q3 = (q3.astype(jnp.float32) * (scale * LOG2E)).astype(q3.dtype)
    return pl.pallas_call(
        functools.partial(_rows_fwd_kernel, b=b, nb=nb),
        grid=(BH // hb, nb),
        in_specs=[blk, whole, whole],
        out_specs=[blk, pl.BlockSpec((hb, b, 128), lambda x, i: (x, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((BH, S, D), q3.dtype),
                   jax.ShapeDtypeStruct((BH, S, 128), jnp.float32)],
        interpret=_interpret(),
        name="flash_fwd",
    )(q3, k3, v3)


@functools.partial(jax.jit,
                   static_argnames=("scale", "b", "hb"))
def _rows_bwd(q3, k3, v3, o3, lse, do3, scale, b, hb):
    BH, S, D = q3.shape
    nb = S // b
    _record_tiles(("flash_bwd_dq", "flash_bwd_dkv"), S, S, b, b, True)
    blk = pl.BlockSpec((hb, b, D), lambda x, i: (x, i, 0))
    whole = pl.BlockSpec((hb, S, D), lambda x, i: (x, 0, 0))
    dq = pl.pallas_call(
        functools.partial(_rows_dq_kernel, scale=scale, b=b, nb=nb),
        grid=(BH // hb, nb),
        in_specs=[blk, whole, whole, blk, blk,
                  pl.BlockSpec((hb, b, 128), lambda x, i: (x, i, 0))],
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q3.dtype),
        interpret=_interpret(),
        name="flash_bwd_dq",
    )(q3, k3, v3, do3, o3, lse)
    dk, dv = pl.pallas_call(
        functools.partial(_rows_dkv_kernel, scale=scale, b=b, nb=nb),
        grid=(BH // hb, nb),
        in_specs=[whole, blk, blk, whole, whole,
                  pl.BlockSpec((hb, S, 128), lambda x, j: (x, 0, 0))],
        out_specs=[blk, blk],
        out_shape=[jax.ShapeDtypeStruct((BH, S, D), k3.dtype),
                   jax.ShapeDtypeStruct((BH, S, D), v3.dtype)],
        interpret=_interpret(),
        name="flash_bwd_dkv",
    )(q3, k3, v3, do3, o3, lse)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _rows3(q3, k3, v3, scale, b, hb):
    return _rows_fwd(q3, k3, v3, scale, b, hb)[0]


def _rows3_fwd(q3, k3, v3, scale, b, hb):
    o, lse = _rows_fwd(q3, k3, v3, scale, b, hb)
    return o, (q3, k3, v3, o, lse)


def _rows3_bwd(scale, b, hb, res, do):
    return _rows_bwd(*res, do, scale, b, hb)


_rows3.defvjp(_rows3_fwd, _rows3_bwd)


# (bq, bk) of the grid kernels, one head a step: every call the row-block
# kernels do not take (not causal, GQA, Sq != Sk).  Ordered by preference:
# cands[0] (the first divisibility+VMEM-viable entry) is the untuned
# default, so large blocks lead. Measured on v5e at B4/S1024/H12/D64 bf16:
# (512,1024) runs fwd+bwd 6x faster than (128,128) — fewer grid steps
# amortize MXU pipeline startup.  Causal block-skipping prunes the tiles
# above the diagonal only where Sk > bk: at S <= 1024 these blocks compute
# the whole square, masked.
_BLOCK_CANDIDATES = [(512, 1024), (1024, 512), (512, 512), (1024, 1024),
                     (256, 512), (512, 256), (256, 256), (128, 256),
                     (256, 128), (512, 128), (128, 512), (128, 128)]

# (b, hb) of the row-block kernels by preference: q blocks of b rows, hb
# heads a step.  Ordered by the three kernels' device time a layer call on
# v5e at B16/S1024/H12/D64 bf16 (ms): (512, 2) 2.30, (256, 4) 2.37,
# (512, 1) 2.44, (256, 2) 2.56, (128, 4) 2.90, (256, 1) 2.93, (128, 2)
# 3.53; the grid kernels at (512, 1024) took 3.20.  b = 512 computes 75 %
# of the square at S = 1024 against 62.5 % at b = 256, and still wins:
# fewer, larger steps.
_ROW_BLOCKS = [(512, 2), (256, 4), (512, 1), (256, 2), (128, 4), (256, 1),
               (128, 2)]
_VMEM_BUDGET = 12 * 1024 * 1024     # of the 16 MiB scoped VMEM a core


def _block_candidates(Sq, Sk, D, dtype):
    """Valid (bq, bk) choices: divisibility + a VMEM budget estimate
    (q/o/dq blocks bq*D, k/v bk*D, lse/m/l bq*128; f32 scratch; ~2x for
    pipelining double-buffering; PLUS the bq*bk score tiles — the _dkv
    backward materializes up to ~4 of s/p/dp/ds in f32, which dominates at
    the large blocks; keep under ~12MB of the 16MB/core VMEM).  Under the
    causal mask these blocks skip a tile only where it lies wholly above
    the diagonal: at Sk <= bk (one k block) none does."""
    out = []
    for bq, bk in _BLOCK_CANDIDATES:
        if Sq % bq or Sk % bk:
            continue
        vmem = (3 * bq * D + 2 * bk * D + 3 * bq * 128) * 4 * 2 \
            + 4 * bq * bk * 4
        if vmem <= _VMEM_BUDGET:
            out.append((bq, bk))
    return out or [(BQ, BK)]


def _rows_vmem(S, b, D, hb, itemsize):
    """VMEM the dk/dv row-block kernel (the largest) asks: each head's whole
    q/do/o and lse and its k/v/dk/dv blocks, double-buffered, and ~3 f32
    score tiles of one head's part below the diagonal (the compiler gave
    19.2 MiB to b = 256 with 8 heads of 64 at S = 1024, v5e)."""
    per_head = 2 * ((3 * S + 4 * b) * D * itemsize + S * 128 * 4)
    return hb * per_head + 3 * max(S - b, b) * b * 4


def _row_candidates(BH, S, D, dtype):
    """Valid (b, hb) of `_ROW_BLOCKS` for B*H heads of S rows: divisibility
    and the VMEM estimate."""
    itemsize = jnp.dtype(dtype).itemsize
    return [(b, hb) for b, hb in _ROW_BLOCKS
            if S % b == 0 and BH % hb == 0
            and _rows_vmem(S, b, D, hb, itemsize) <= _VMEM_BUDGET]


def _tuned(key, cands, build, arrays, n):
    """cands[0], or with FLAGS_use_autotune the fastest of `cands` timed on
    views of the first n rows of `arrays` (reference: autotune/
    switch_autotune picking conv/matmul algos); only on concrete arrays
    outside any jit trace."""
    from .. import autotune as at
    if len(cands) == 1:
        return cands[0]
    return tuple(at.tune(at.cache_key(*key), cands, build,
                         tuple(a[:n] for a in arrays)))


def _pick_blocks(q3, k3, v3, causal):
    """(bq, bk) of the grid kernels for this shape.  Tunes the forward
    kernel only — bwd shares the blocking — on single-head views so tuning
    cost stays low."""
    BH, Sq, D = q3.shape
    Sk = k3.shape[1]

    def build(cfg):
        def run(q, k, v):
            nh = nhk = 1  # timing proxy: head mapping doesn't affect blocking
            return _flash_fwd(q, k, v, 1.0, causal, nh, nhk, *cfg)[0]
        return run

    return _tuned(("flash_fwd", BH, Sq, Sk, D, q3.dtype, causal),
                  _block_candidates(Sq, Sk, D, q3.dtype), build,
                  (q3, k3, v3), 1)


def _pick_rows(q3):
    """(b, hb) of the row-block kernels for causal self-attention on these
    [B*H, S, D] operands, or None where no candidate fits."""
    BH, S, D = q3.shape
    cands = _row_candidates(BH, S, D, q3.dtype)
    if not cands:
        return None

    def build(cfg):
        return lambda q, k, v: _rows_fwd(q, k, v, 1.0, *cfg)[0]

    return _tuned(("flash_rows", BH, S, D, q3.dtype), cands, build,
                  (q3, q3, q3), math.lcm(*(hb for _, hb in cands)))


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
    if not (causal and _self_attention(q3, k3) and _pick_rows(q3)):
        _pick_blocks(q3, k3, v3, causal)


def _self_attention(q3, k3):
    """One kv head a q head and as many keys as queries: the row-block
    kernels' case under the causal mask."""
    return q3.shape[:2] == k3.shape[:2]


def flash_attention_bshd(q, k, v, causal=True, scale=None):
    """[B, S, H, D] flash attention over [B*H, S, D] views.  GQA runs the
    grid kernels, whose BlockSpecs index kv-head = q-head // group — K/V
    are never repeated in HBM (at Llama-3-8B's 32q/8kv that repeat would be
    4x KV memory).  Causal self-attention with one kv head a q head takes
    the row-block kernels, which compute only the blocks on and below the
    diagonal; every other call the grid kernels.  Block sizes come from the
    autotuner cache when FLAGS_use_autotune is set."""
    B, Sq, H, D = q.shape
    Hk = k.shape[2]
    if H % Hk != 0:
        raise ValueError(f"q heads ({H}) must be a multiple of kv heads ({Hk})")
    s = scale if scale is not None else 1.0 / math.sqrt(D)
    q3 = jnp.moveaxis(q, 2, 1).reshape(B * H, Sq, D)
    k3 = jnp.moveaxis(k, 2, 1).reshape(B * Hk, k.shape[1], D)
    v3 = jnp.moveaxis(v, 2, 1).reshape(B * Hk, v.shape[1], D)
    rows = causal and _self_attention(q3, k3) and _pick_rows(q3)
    if rows:
        o3 = _rows3(q3, k3, v3, s, *rows)
    else:
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
