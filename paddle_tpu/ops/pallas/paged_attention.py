"""Paged (block) KV-cache decode attention — the second Pallas TPU kernel
(reference capability: phi/kernels/fusion/gpu/block_multi_head_attention /
block_attn.h: paged KV blocks + per-sequence block tables).

TPU-native design: the KV cache lives in fixed-size pages
[num_pages, page_size, kv_heads, head_dim]; each sequence owns a row of the
block table. One kernel body (:func:`_walk`) serves the four entry points
(one query a row or Q consecutive ones; bf16 pages or int8 pages with their
scales). Its grid runs over the rows alone; the pools stay in HBM and the
block table and the context lengths are scalar-prefetched. For its row a
program walks ``ceil(ctx / block)`` blocks of several consecutive table
entries (about 128 tokens: :func:`_pages_per_block`) and no more: it copies
a block's pages by the table into one of two VMEM buffers while it computes
the block before, and runs the online softmax (m, l, acc in VMEM scratch,
float32) once a block over all KV heads. So a call costs what its rows'
contexts cost, not ``rows x max_len``. GQA q-head groups meet their kv head
as a mask on the score columns (no repeat materialization)."""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_BLOCK_ELEMS = 128 * 128     # a KV head's key tile a trip: see _pages_per_block


# which query of its sequence's q_len a row counts as, for its horizon: its
# own (row r is query r % q_len), or the last one's for every row of a block
_REACH = {"row": lambda r, q_len: r % q_len,
          "block": lambda r, q_len: q_len - 1}


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _pages_per_block(page_size, head_dim):
    """Pages one trip of the walk covers: a KV head's key tile is then
    ``_BLOCK_ELEMS`` elements, ``[128, 128]`` at a head size of 128 (8
    pages of 16). Fixed by measurement on the v5e (PERF.md section 6,
    PR 29); not an option of any caller."""
    return max(1, _BLOCK_ELEMS // (page_size * head_dim))


def _walk(bt_ref, cl_ref, q_ref, k_hbm, v_hbm, *rest, scale, page_size, ppb,
          n_slots, kv_heads, q_len, horizon="row"):
    """The one body of the four entry points: program ``b`` of a grid over
    rows walks row ``b``'s context in blocks of ``ppb`` consecutive table
    entries, ``ceil((ctx + q_len - 1) / block)`` of them and no more.

    The pools stay in HBM. A block's pages are copied by the prefetched
    table into one of two VMEM buffers while the block before is computed;
    a row's last trip starts the NEXT row's first block, so only the call's
    first copy is waited for with nothing to do. Table entries past the
    row's last valid page are clamped to it: what they fetch is masked, and
    a page off the row's context is never read.

    The online softmax runs once a block, over ALL KV heads at once: the
    buffer is read as it lies, ``[block * KVH, D]`` with a token's heads
    side by side, so a score column is a (token, KV head) pair and a row
    keeps the columns of its own head (``own`` below). The MXU loads the
    same 2 * KVH key and value tiles a block as a product a head would, the
    other heads' columns ride along as zeros, and no head's rows have to be
    picked out of the pages (a sublane-strided read a head: twice the time
    on the v5e, PERF.md section 6, PR 29).

    int8 pages go to the MXU as they are (an int8 is exact in bf16); their
    per-token scales multiply the scores (K) and the probabilities (V),
    which is the dequantized product with the scale taken out of the sum.
    The scales arrive as the row's own ``[n_blocks, 1, block * KVH]`` VMEM
    operand, in the columns' order (see :func:`_paged_call`).

    Rows are kv-head-major ([B, H * q_len, D], row = qh * q_len + j): every
    KV head's rows are one contiguous slice, and with one query that layout
    is [B, H, D] itself. Row j's causal horizon is ctx + j (ctx = context
    of row 0, itself included); with ``horizon="block"`` every row has the
    last row's, ctx + q_len - 1: the q_len positions are one block whose
    rows see the context and each other (generation by blocks)."""
    # int8 pages bring their two scale operands, bf16 pages none
    *scales, o_ref, kbuf, vbuf, sem, first, m_s, l_s, acc_s = rest
    ks_ref, vs_ref = scales or (None, None)
    b = pl.program_id(0)
    n_rows = pl.num_programs(0)
    block = ppb * page_size
    cols = block * kv_heads
    HQ = q_ref.shape[1]
    dt = q_ref.dtype

    def copies(row, blk, slot):
        """Block ``blk`` of ``row`` into buffer ``slot``: a copy a page.
        A copy is waited for through any descriptor of its size, so
        ``row=None`` reads no table."""
        if row is not None:
            # the last table entry with anything row's LAST query attends
            last = jnp.clip((cl_ref[row] + q_len - 2) // page_size, 0,
                            n_slots - 1)
        out = []
        for i in range(ppb):
            page = (0 if row is None else
                    bt_ref[row, jnp.minimum(blk * ppb + i, last)])
            out += [pltpu.make_async_copy(hbm.at[page], buf.at[slot, i],
                                          sem.at[slot])
                    for hbm, buf in ((k_hbm, kbuf), (v_hbm, vbuf))]
        return out

    cl = cl_ref[b]
    # never none: the row before has started this row's first block
    n_blocks = jnp.maximum((cl + q_len - 1 + block - 1) // block, 1)

    @pl.when(b == 0)
    def _first():
        first[0] = 0
        for c in copies(0, 0, 0):
            c.start()

    m_s[:] = jnp.full_like(m_s, NEG_INF)
    l_s[:] = jnp.zeros_like(l_s)
    acc_s[:] = jnp.zeros_like(acc_s)
    slot0 = first[0]

    # the same for every block: column c is token c // KVH of the block
    # under KV head c % KVH; row r is query r % q_len of a head of KV head
    # r // (HQ // KVH).  ``age`` is the token's offset less the query's,
    # and past every context where the column is another head's.
    col = jax.lax.broadcasted_iota(jnp.int32, (HQ, cols), 1)
    r = jax.lax.broadcasted_iota(jnp.int32, (HQ, cols), 0)
    own = col % kv_heads == r // (HQ // kv_heads)
    age = jnp.where(own, col // kv_heads - _REACH[horizon](r, q_len),
                    jnp.int32(2 ** 30))

    def trip(i, carry):
        slot = (slot0 + i) % 2
        more = i + 1 < n_blocks
        nxt_row = jnp.where(more, b, b + 1)

        @pl.when(nxt_row < n_rows)
        def _prefetch():
            for c in copies(nxt_row, jnp.where(more, i + 1, 0), 1 - slot):
                c.start()

        for c in copies(None, i, slot):
            c.wait()

        valid = age < cl - i * block                       # [HQ, cols]
        # MXU operands stay in the input dtype (bf16 native mode); softmax
        # statistics and accumulation are f32
        k = kbuf[slot].reshape(cols, -1)                   # [cols, D]
        v = vbuf[slot].reshape(cols, -1)
        k, v = k.astype(dt), v.astype(dt)      # int8 is exact in bf16
        sc = jax.lax.dot_general(q_ref[0], k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32,
                                 precision=jax.lax.Precision.DEFAULT) * scale
        if ks_ref is not None:
            sc = sc * ks_ref[0, i]
        sc = jnp.where(valid, sc, NEG_INF)
        m_prev = m_s[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1))
        p = jnp.exp(sc - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_s[:, 0] = l_s[:, 0] * corr + jnp.sum(p, axis=1)
        if vs_ref is not None:      # a scale past the context may be anything
            p = jnp.where(valid, p * vs_ref[0, i], 0.0)
        acc_s[:] = acc_s[:] * corr[:, None] + jax.lax.dot_general(
            p.astype(dt), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT)
        m_s[:, 0] = m_new
        return carry

    jax.lax.fori_loop(0, n_blocks, trip, 0)
    first[0] = (slot0 + n_blocks) % 2
    denom = jnp.maximum(l_s[:, 0:1], 1e-30)
    o_ref[0] = (acc_s[:] / denom).astype(o_ref.dtype)


def _paged_call(qf, k_pages, v_pages, block_tables, context_lens,
                k_scales, v_scales, scale_tables, *, scale, q_len,
                horizon="row"):
    """``pallas_call`` of :func:`_walk` on kv-head-major rows
    ``qf [B, H * q_len, D]``.

    The page pools are handed over in HBM, whole. The scale pools of int8
    pages cannot be: the TPU pads their last axis (KVH) to 128 lanes, and
    Mosaic refuses a copy of one page's ``[page, KVH]`` out of that
    (PERF.md section 6, PR 29). So the scales under each row's table are
    gathered here, by XLA, into ``[B, n_blocks, 1, block * KVH]`` - a
    block's (token, KV head) pairs on the lanes, as the scores have them -
    and reach the kernel by a ``BlockSpec``, a row a grid step."""
    B, HQ, D = qf.shape
    _, page_size, KVH, _ = k_pages.shape
    S = block_tables.shape[1]
    quant = k_scales is not None
    ppb = _pages_per_block(page_size, D)
    n_blk = -(-S // ppb)
    cols = ppb * page_size * KVH
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    row_spec = pl.BlockSpec((1, HQ, D), lambda b, *_: (b, 0, 0))
    operands = [qf, k_pages, v_pages]
    in_specs = [row_spec, hbm, hbm]
    if quant:
        st = block_tables if scale_tables is None else scale_tables

        def by_row(scales):                 # [P', page, KVH] -> by the table
            g = scales[st].reshape(B, -1)
            g = jnp.pad(g, ((0, 0), (0, n_blk * cols - g.shape[1])))
            return g.reshape(B, n_blk, 1, cols)
        operands += [by_row(k_scales), by_row(v_scales)]
        in_specs += [pl.BlockSpec((1, n_blk, 1, cols),
                                  lambda b, *_: (b, 0, 0, 0))] * 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(B,), in_specs=in_specs,
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((2, ppb, page_size, KVH, D), k_pages.dtype),
            pltpu.VMEM((2, ppb, page_size, KVH, D), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),    # the slot of the row's block 0
            pltpu.VMEM((HQ, 1), jnp.float32),
            pltpu.VMEM((HQ, 1), jnp.float32),
            pltpu.VMEM((HQ, D), jnp.float32),
        ])
    kern = functools.partial(
        _walk, scale=scale, page_size=page_size, ppb=ppb, n_slots=S,
        kv_heads=KVH, q_len=q_len, horizon=horizon)
    return pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, HQ, D), qf.dtype),
        # rows in order: a row's last trip starts the next row's first block
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
    )(block_tables, context_lens, *operands)


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
    KVH = k_pages.shape[2]
    assert H % KVH == 0, f"q heads {H} not a multiple of kv heads {KVH}"
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    # one query a row: the kv-head-major row layout is [B, H, D] itself
    return _paged_call(q, k_pages, v_pages, block_tables, context_lens,
                       k_scales, v_scales, scale_tables, scale=scale, q_len=1)


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


@functools.partial(jax.jit, static_argnames=("scale", "horizon"))
def paged_attention_multiquery(q, k_pages, v_pages, block_tables,
                               context_lens, *, k_scales=None, v_scales=None,
                               scale_tables=None, scale=None, horizon="row"):
    """Verification attention: Q consecutive query positions per sequence
    against the paged KV cache (speculative decoding scores the pending
    token plus all drafts in ONE forward).

    q:             [B, Q, H, D]    row j sits at absolute position
                                   context_lens[b] - 1 + j
    context_lens:  [B] int32       cache tokens visible to row 0 (incl. its
                                   own just-written entry); row j's causal
                                   horizon is context_lens[b] + j
    horizon:       "row" (above: verification) | "block": the Q positions
                   are ONE block and every row sees context_lens[b] + Q - 1
                   tokens, the whole block included (generation by blocks:
                   causal from block to block, both ways inside one)
    k_pages/v_pages/block_tables/k_scales/v_scales/scale_tables: as
                   paged_attention
    returns        [B, Q, H, D]

    The kernel streams each page once per sequence for all Q rows (the
    single-query kernel would pay the KV DMA Q times)."""
    B, Q, H, D = q.shape
    KVH = k_pages.shape[2]
    assert H % KVH == 0, f"q heads {H} not a multiple of kv heads {KVH}"
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    # kv-head-major row layout: rows [h*group*Q, (h+1)*group*Q) belong to kv
    # head h, query position = row % Q
    qf = jnp.transpose(q, (0, 2, 1, 3)).reshape(B, H * Q, D)
    out = _paged_call(qf, k_pages, v_pages, block_tables, context_lens,
                      k_scales, v_scales, scale_tables, scale=scale, q_len=Q,
                      horizon=horizon)
    return jnp.transpose(out.reshape(B, H, Q, D), (0, 2, 1, 3))


def _paged_attention_blocks(q, k_pages, v_pages, block_tables, context_lens,
                            *, k_scales=None, v_scales=None,
                            scale_tables=None, scale=None):
    """:func:`paged_attention_multiquery` with ``horizon="block"``: the Q
    positions a sequence are one block of a model that generates by blocks.
    A function of its own for its NAME: a kernel stands in the device trace
    under the innermost ``jit``'s, and a decode dispatch's attention is
    ``paged_attention`` there whatever the rows it takes (the benchmark's
    readers are anchored on it)."""
    return paged_attention_multiquery.__wrapped__(
        q, k_pages, v_pages, block_tables, context_lens, k_scales=k_scales,
        v_scales=v_scales, scale_tables=scale_tables, scale=scale,
        horizon="block")


_paged_attention_blocks.__name__ = "paged_attention"
paged_attention_blocks = jax.jit(_paged_attention_blocks,
                                 static_argnames=("scale",))


def paged_attention_multiquery_ref(q, k_pages, v_pages, block_tables,
                                   context_lens, *, k_scales=None,
                                   v_scales=None, scale_tables=None,
                                   scale=None, horizon="row"):
    """jnp reference for the multi-query kernel (dense gather, per-row
    causal horizon ctx + j, or ctx + Q - 1 for every row of a block) —
    golden for the kernel test and the engine's CPU path."""
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
        # row j attends tokens [0, cl + j); a block's rows all [0, cl + Q - 1)
        mask = (jnp.arange(S * page_size)[None, :]
                < cl + (jnp.arange(Q) if horizon == "row"
                        else jnp.full((Q,), Q - 1))[:, None])   # [Q, T]
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
