"""Paged (block) KV-cache decode attention — the second Pallas TPU kernel
(reference capability: phi/kernels/fusion/gpu/block_multi_head_attention /
block_attn.h: paged KV blocks + per-sequence block tables).

TPU-native design: the KV cache lives in fixed-size pages
[num_pages, page_size, kv_heads, head_dim]; each sequence owns a row of the
block table. The kernel runs a (batch, page_slot) grid with the block table
scalar-prefetched, so each page's DMA address is computed *before* the body
runs (pltpu.PrefetchScalarGridSpec — the canonical TPU paged-attention
pattern). Online softmax state (m, l, acc) persists in VMEM scratch across the
sequential page_slot dimension; GQA q-head groups index their kv head directly
(no repeat materialization)."""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _kernel(bt_ref, cl_ref, q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s, *,
            scale, page_size, n_slots, kv_heads, group):
    b = pl.program_id(0)
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    cl = cl_ref[b]
    n_valid = (cl + page_size - 1) // page_size

    @pl.when(s < n_valid)
    def _compute():
        # token validity inside this page
        tok = s * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)
        valid = tok < cl                                   # [1, page_size]
        for h in range(kv_heads):
            # MXU operands stay in the input dtype (bf16 native mode);
            # softmax statistics and accumulation are f32
            q = q_ref[0, h * group:(h + 1) * group, :]
            k = k_ref[0, :, h, :]                          # [page, D]
            v = v_ref[0, :, h, :]
            sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32,
                                     precision=jax.lax.Precision.DEFAULT) * scale
            sc = jnp.where(valid, sc, NEG_INF)             # [group, page]
            row = slice(h * group, (h + 1) * group)
            m_prev = m_s[row, 0]
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1))
            p = jnp.exp(sc - m_new[:, None])
            corr = jnp.exp(m_prev - m_new)
            l_s[row, 0] = l_s[row, 0] * corr + jnp.sum(p, axis=1)
            acc_s[row, :] = acc_s[row, :] * corr[:, None] + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.DEFAULT)
            m_s[row, 0] = m_new

    @pl.when(s == n_slots - 1)
    def _finish():
        denom = jnp.maximum(l_s[:, 0:1], 1e-30)
        o_ref[0] = (acc_s[:] / denom).astype(o_ref.dtype)


def _kernel_q(bt_ref, cl_ref, st_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
              o_ref, m_s, l_s, acc_s, *, scale, page_size, n_slots, kv_heads,
              group):
    """int8-page variant (reference capability: block_multihead_attention's
    cache_k_quant_scales/cache_v_quant_scales, dynamic mode): pages carry
    int8 values + a per-(token, kv-head) f32 scale; the kernel dequantizes
    page tiles in VMEM right before the MXU dots, so HBM traffic (and page
    capacity) is ~half the bf16 cache's.

    Validation: against the dense reference in interpret mode
    (tests/test_kv_int8.py) and, on the chip, by chip_smoke.py's kernel
    comparison and its int8-page serving phase."""
    b = pl.program_id(0)
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    cl = cl_ref[b]
    n_valid = (cl + page_size - 1) // page_size

    @pl.when(s < n_valid)
    def _compute():
        tok = s * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)
        valid = tok < cl                                   # [1, page_size]
        for h in range(kv_heads):
            q = q_ref[0, h * group:(h + 1) * group, :]
            k = (k_ref[0, :, h, :].astype(jnp.float32)
                 * ks_ref[0, :, h][:, None]).astype(q.dtype)
            v = (v_ref[0, :, h, :].astype(jnp.float32)
                 * vs_ref[0, :, h][:, None]).astype(q.dtype)
            sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32,
                                     precision=jax.lax.Precision.DEFAULT) * scale
            sc = jnp.where(valid, sc, NEG_INF)             # [group, page]
            row = slice(h * group, (h + 1) * group)
            m_prev = m_s[row, 0]
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1))
            p = jnp.exp(sc - m_new[:, None])
            corr = jnp.exp(m_prev - m_new)
            l_s[row, 0] = l_s[row, 0] * corr + jnp.sum(p, axis=1)
            acc_s[row, :] = acc_s[row, :] * corr[:, None] + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.DEFAULT)
            m_s[row, 0] = m_new

    @pl.when(s == n_slots - 1)
    def _finish():
        denom = jnp.maximum(l_s[:, 0:1], 1e-30)
        o_ref[0] = (acc_s[:] / denom).astype(o_ref.dtype)


def _mq_step(q_ref, o_ref, m_s, l_s, acc_s, kv, cl, s, *, scale, page_size,
             n_slots, kv_heads, group, q_len):
    """Shared multi-query online-softmax body (speculative-decode
    verification): each sequence carries q_len query rows at consecutive
    positions, laid out kv-head-major ([B, H*q_len, D], row = qh*q_len + j)
    so every kv head's rows are one contiguous slice.  Each page is DMA'd
    ONCE per sequence and scored against all q_len rows — a per-row loop
    over the single-query kernel would stream the whole KV prefix q_len
    times.  Row j's causal horizon is ctx = cl + j (cl = context of row 0,
    itself included), enforced with a per-row position mask.  ``kv(h)``
    yields this page's (K, V) tile for kv head h, letting the bf16 and int8
    wrapper kernels differ only in how the tile is loaded."""
    @pl.when(s == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    # pages holding anything the LAST query row may attend to
    n_valid = (cl + q_len - 1 + page_size - 1) // page_size
    rows = group * q_len

    @pl.when(s < n_valid)
    def _compute():
        tok = s * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (rows, page_size), 1)
        qpos = jax.lax.broadcasted_iota(
            jnp.int32, (rows, page_size), 0) % q_len
        valid = tok < cl + qpos                            # [rows, page]
        for h in range(kv_heads):
            q = q_ref[0, h * rows:(h + 1) * rows, :]
            k, v = kv(h)
            sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32,
                                     precision=jax.lax.Precision.DEFAULT) * scale
            sc = jnp.where(valid, sc, NEG_INF)             # [rows, page]
            row = slice(h * rows, (h + 1) * rows)
            m_prev = m_s[row, 0]
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1))
            p = jnp.exp(sc - m_new[:, None])
            corr = jnp.exp(m_prev - m_new)
            l_s[row, 0] = l_s[row, 0] * corr + jnp.sum(p, axis=1)
            acc_s[row, :] = acc_s[row, :] * corr[:, None] + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.DEFAULT)
            m_s[row, 0] = m_new

    @pl.when(s == n_slots - 1)
    def _finish():
        denom = jnp.maximum(l_s[:, 0:1], 1e-30)
        o_ref[0] = (acc_s[:] / denom).astype(o_ref.dtype)


def _kernel_mq(bt_ref, cl_ref, q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s,
               *, scale, page_size, n_slots, kv_heads, group, q_len):
    b = pl.program_id(0)
    s = pl.program_id(1)
    _mq_step(q_ref, o_ref, m_s, l_s, acc_s,
             lambda h: (k_ref[0, :, h, :], v_ref[0, :, h, :]),
             cl_ref[b], s, scale=scale, page_size=page_size, n_slots=n_slots,
             kv_heads=kv_heads, group=group, q_len=q_len)


def _kernel_mq_q(bt_ref, cl_ref, st_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                 o_ref, m_s, l_s, acc_s, *, scale, page_size, n_slots,
                 kv_heads, group, q_len):
    """int8-page multi-query variant: dequantizes page tiles in VMEM right
    before the MXU dots, exactly like _kernel_q."""
    b = pl.program_id(0)
    s = pl.program_id(1)
    dt = q_ref.dtype

    def kv(h):
        k = (k_ref[0, :, h, :].astype(jnp.float32)
             * ks_ref[0, :, h][:, None]).astype(dt)
        v = (v_ref[0, :, h, :].astype(jnp.float32)
             * vs_ref[0, :, h][:, None]).astype(dt)
        return k, v

    _mq_step(q_ref, o_ref, m_s, l_s, acc_s, kv, cl_ref[b], s, scale=scale,
             page_size=page_size, n_slots=n_slots, kv_heads=kv_heads,
             group=group, q_len=q_len)


def quantize_kv(x):
    """Per-(row, kv-head) symmetric int8 quantization of K/V rows
    [..., KVH, D] -> (int8 values, f32 scales [..., KVH])."""
    xf = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1), 1e-8) / 127.0
    q = jnp.clip(jnp.round(xf / s[..., None]), -127, 127).astype(jnp.int8)
    return q, s


@functools.partial(jax.jit, static_argnames=("scale",))
def paged_attention(q, k_pages, v_pages, block_tables, context_lens,
                    *, k_scales=None, v_scales=None, scale_tables=None,
                    scale=None):
    """Decode-step attention against a paged KV cache.

    q:             [B, H, D]       current-step queries
    k_pages/v_pages: [P, page_size, KVH, D]  (int8 when *_scales given)
    k_scales/v_scales: [P', page_size, KVH] f32 per-token-per-head scales
                   (int8 KV-cache mode; reference: incubate block_multihead_
                   attention.py:47-48 cache_*_quant_scales)
    scale_tables:  [B, S] int32    page id per (sequence, slot) into the
                   SCALE arrays, where they are not indexed like the pages
                   (the engine hands the pages of every layer as one stack
                   and the scales of one layer); default block_tables
    block_tables:  [B, S] int32    physical page id per (sequence, slot)
    context_lens:  [B]   int32     tokens already in cache (incl. current)
    returns        [B, H, D]
    """
    B, H, D = q.shape
    P, page_size, KVH, _ = k_pages.shape
    S = block_tables.shape[1]
    assert H % KVH == 0, f"q heads {H} not a multiple of kv heads {KVH}"
    group = H // KVH
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    quant = k_scales is not None

    # an index map takes the grid's (b, s) and then every prefetched scalar
    page_spec = pl.BlockSpec((1, page_size, KVH, D),
                             lambda b, s, bt, *_: (bt[b, s], 0, 0, 0))
    scale_spec = pl.BlockSpec((1, page_size, KVH),
                              lambda b, s, bt, cl, st: (st[b, s], 0, 0))
    in_specs = [pl.BlockSpec((1, H, D), lambda b, s, *_: (b, 0, 0)),
                page_spec, page_spec]
    prefetch = [block_tables, context_lens]
    operands = [q, k_pages, v_pages]
    if quant:
        prefetch.append(block_tables if scale_tables is None
                        else scale_tables)
        in_specs += [scale_spec, scale_spec]
        operands += [k_scales, v_scales]
        kern = functools.partial(_kernel_q, scale=scale,
                                 page_size=page_size, n_slots=S,
                                 kv_heads=KVH, group=group)
    else:
        kern = functools.partial(_kernel, scale=scale, page_size=page_size,
                                 n_slots=S, kv_heads=KVH, group=group)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B, S),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, H, D), lambda b, s, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        interpret=_interpret(),
    )(*prefetch, *operands)


def paged_attention_ref(q, k_pages, v_pages, block_tables, context_lens,
                        *, k_scales=None, v_scales=None, scale_tables=None,
                        scale=None):
    """jnp reference (gathers pages densely) — golden for the kernel test."""
    B, H, D = q.shape
    P, page_size, KVH, _ = k_pages.shape
    S = block_tables.shape[1]
    group = H // KVH
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    out = []
    for b_i in range(B):
        pages = block_tables[b_i]                       # [S]
        k = k_pages[pages].reshape(S * page_size, KVH, D)
        v = v_pages[pages].reshape(S * page_size, KVH, D)
        if k_scales is not None:                        # int8 pages: dequant
            sp = pages if scale_tables is None else scale_tables[b_i]
            k = (k.astype(jnp.float32) *
                 k_scales[sp].reshape(S * page_size, KVH)[..., None])
            v = (v.astype(jnp.float32) *
                 v_scales[sp].reshape(S * page_size, KVH)[..., None])
        cl = context_lens[b_i]
        mask = jnp.arange(S * page_size) < cl
        qh = q[b_i].reshape(KVH, group, D).astype(jnp.float32)
        kh = jnp.moveaxis(k, 1, 0).astype(jnp.float32)  # [KVH, T, D]
        vh = jnp.moveaxis(v, 1, 0).astype(jnp.float32)
        sc = jnp.einsum("hgd,htd->hgt", qh * scale, kh)
        sc = jnp.where(mask[None, None, :], sc, NEG_INF)
        p = jax.nn.softmax(sc, axis=-1)
        out.append(jnp.einsum("hgt,htd->hgd", p, vh).reshape(H, D))
    return jnp.stack(out).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("scale",))
def paged_attention_multiquery(q, k_pages, v_pages, block_tables,
                               context_lens, *, k_scales=None, v_scales=None,
                               scale_tables=None, scale=None):
    """Verification attention: Q consecutive query positions per sequence
    against the paged KV cache (speculative decoding scores the pending
    token plus all drafts in ONE forward).

    q:             [B, Q, H, D]    row j sits at absolute position
                                   context_lens[b] - 1 + j
    context_lens:  [B] int32       cache tokens visible to row 0 (incl. its
                                   own just-written entry); row j's causal
                                   horizon is context_lens[b] + j
    k_pages/v_pages/block_tables/k_scales/v_scales/scale_tables: as
                   paged_attention
    returns        [B, Q, H, D]

    The kernel streams each page once per sequence for all Q rows (the
    single-query kernel would pay the KV DMA Q times)."""
    B, Q, H, D = q.shape
    P, page_size, KVH, _ = k_pages.shape
    S = block_tables.shape[1]
    assert H % KVH == 0, f"q heads {H} not a multiple of kv heads {KVH}"
    group = H // KVH
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    quant = k_scales is not None
    # kv-head-major row layout: rows [h*group*Q, (h+1)*group*Q) belong to kv
    # head h, query position = row % Q
    qf = jnp.transpose(q, (0, 2, 1, 3)).reshape(B, H * Q, D)

    page_spec = pl.BlockSpec((1, page_size, KVH, D),
                             lambda b, s, bt, *_: (bt[b, s], 0, 0, 0))
    scale_spec = pl.BlockSpec((1, page_size, KVH),
                              lambda b, s, bt, cl, st: (st[b, s], 0, 0))
    in_specs = [pl.BlockSpec((1, H * Q, D), lambda b, s, *_: (b, 0, 0)),
                page_spec, page_spec]
    prefetch = [block_tables, context_lens]
    operands = [qf, k_pages, v_pages]
    if quant:
        prefetch.append(block_tables if scale_tables is None
                        else scale_tables)
        in_specs += [scale_spec, scale_spec]
        operands += [k_scales, v_scales]
        kern = functools.partial(_kernel_mq_q, scale=scale,
                                 page_size=page_size, n_slots=S,
                                 kv_heads=KVH, group=group, q_len=Q)
    else:
        kern = functools.partial(_kernel_mq, scale=scale,
                                 page_size=page_size, n_slots=S,
                                 kv_heads=KVH, group=group, q_len=Q)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B, S),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, H * Q, D), lambda b, s, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H * Q, 1), jnp.float32),
            pltpu.VMEM((H * Q, 1), jnp.float32),
            pltpu.VMEM((H * Q, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H * Q, D), q.dtype),
        interpret=_interpret(),
    )(*prefetch, *operands)
    return jnp.transpose(out.reshape(B, H, Q, D), (0, 2, 1, 3))


def paged_attention_multiquery_ref(q, k_pages, v_pages, block_tables,
                                   context_lens, *, k_scales=None,
                                   v_scales=None, scale_tables=None,
                                   scale=None):
    """jnp reference for the multi-query kernel (dense gather, per-row
    causal horizon ctx + j) — golden for the kernel test and the engine's
    CPU path."""
    B, Q, H, D = q.shape
    P, page_size, KVH, _ = k_pages.shape
    S = block_tables.shape[1]
    group = H // KVH
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    out = []
    for b_i in range(B):
        pages = block_tables[b_i]
        k = k_pages[pages].reshape(S * page_size, KVH, D)
        v = v_pages[pages].reshape(S * page_size, KVH, D)
        if k_scales is not None:
            sp = pages if scale_tables is None else scale_tables[b_i]
            k = (k.astype(jnp.float32) *
                 k_scales[sp].reshape(S * page_size, KVH)[..., None])
            v = (v.astype(jnp.float32) *
                 v_scales[sp].reshape(S * page_size, KVH)[..., None])
        cl = context_lens[b_i]
        # row j attends tokens [0, cl + j)
        mask = (jnp.arange(S * page_size)[None, :]
                < cl + jnp.arange(Q)[:, None])             # [Q, T]
        qh = jnp.transpose(q[b_i], (1, 0, 2)).reshape(
            KVH, group, Q, D).astype(jnp.float32)
        kh = jnp.moveaxis(k, 1, 0).astype(jnp.float32)     # [KVH, T, D]
        vh = jnp.moveaxis(v, 1, 0).astype(jnp.float32)
        sc = jnp.einsum("hgqd,htd->hgqt", qh * scale, kh)
        sc = jnp.where(mask[None, None], sc, NEG_INF)
        p = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("hgqt,htd->hgqd", p, vh)            # [KVH, g, Q, D]
        out.append(jnp.transpose(o.reshape(H, Q, D), (1, 0, 2)))
    return jnp.stack(out).astype(q.dtype)
