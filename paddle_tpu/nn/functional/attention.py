"""Attention functionals.

Reference: python/paddle/nn/functional/flash_attention.py:364 (CUDA flashattn
wrapper). Here: a fused-softmax XLA path by default; the Pallas flash-attention
kernel (paddle_tpu/ops/pallas/flash_attention.py) is used on TPU for long
sequences, matching the reference's kernel-dispatch behavior.
"""
from __future__ import annotations

import math

import numpy as np
import jax
import jax.numpy as jnp

from ...core.dispatch import apply_op, unwrap
from ...core.tensor import Tensor


def _sdpa_ref(q, k, v, mask=None, causal=False, dropout_p=0.0, scale=None, key=None):
    """[B, S, H, D] layout (paddle flash_attention convention)."""
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    # grouped-query attention without materializing repeated KV heads: fold
    # the group into a 5-D einsum (XLA keeps it a batched matmul)
    B, sq_len, hq, _ = q.shape
    hk = k.shape[2]
    rep = hq // hk
    qg = qf.reshape(B, sq_len, hk, rep, d)
    logits = jnp.einsum("bskrd,btkd->bkrst", qg, kf) * s  # [B,hk,rep,Sq,Sk]
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        cm = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(cm, logits, -jnp.inf)
    if mask is not None:
        sq, sk = logits.shape[-2], logits.shape[-1]
        m5 = jnp.broadcast_to(mask, (B, hq, sq, sk)).reshape(B, hk, rep, sq, sk)
        if mask.dtype == jnp.bool_:
            logits = jnp.where(m5, logits, -jnp.inf)
        else:
            logits = logits + m5.astype(jnp.float32)
    p = jax.nn.softmax(logits, axis=-1)
    if dropout_p > 0.0 and key is not None:
        keep = jax.random.bernoulli(key, 1.0 - dropout_p, p.shape)
        p = jnp.where(keep, p / (1.0 - dropout_p), 0.0)
    out = jnp.einsum("bkrst,btkd->bskrd", p, vf).reshape(B, sq_len, hq, d)
    return out.astype(q.dtype)


def scaled_dot_product_attention(query, key, value, attn_mask=None, dropout_p=0.0,
                                 is_causal=False, training=True, name=None):
    """paddle.nn.functional.scaled_dot_product_attention ([B, S, H, D])."""
    m = unwrap(attn_mask) if attn_mask is not None else None
    rng_key = None
    if dropout_p > 0.0 and training:
        from ...core.rng import next_key
        rng_key = next_key()
    qa, ka, va = unwrap(query), unwrap(key), unwrap(value)
    if m is None and rng_key is None and _use_pallas(qa, ka):
        from ...ops.pallas.flash_attention import warm_autotune
        warm_autotune(qa, ka, va, causal=is_causal)

    def f(q, k, v):
        if m is None and rng_key is None and _use_pallas(q, k):
            from ...ops.pallas.flash_attention import flash_attention_bshd
            return flash_attention_bshd(q, k, v, causal=is_causal)
        return _sdpa_ref(q, k, v, mask=m, causal=is_causal,
                         dropout_p=dropout_p if training else 0.0, key=rng_key)
    return apply_op("scaled_dot_product_attention", f, query, key, value)


def flash_attention(query, key, value, dropout=0.0, causal=False, return_softmax=False,
                    fixed_seed_offset=None, rng_name="", training=True, name=None):
    """Flash attention ([B, S, H, D]); dispatches to the Pallas TPU kernel when
    available, else the fused XLA path (same numerics, f32 accumulation)."""
    m = None
    rng_key = None
    if dropout > 0.0 and training:
        from ...core.rng import next_key
        rng_key = next_key()
    qa, ka, va = unwrap(query), unwrap(key), unwrap(value)
    if rng_key is None and _use_pallas(qa, ka):
        from ...ops.pallas.flash_attention import warm_autotune
        warm_autotune(qa, ka, va, causal=causal)

    def f(q, k, v):
        if rng_key is None and _use_pallas(q, k):
            from ...ops.pallas.flash_attention import flash_attention_bshd
            return flash_attention_bshd(q, k, v, causal=causal)
        return _sdpa_ref(q, k, v, mask=m, causal=causal,
                         dropout_p=dropout if training else 0.0, key=rng_key)
    out = apply_op("flash_attention", f, query, key, value)
    if return_softmax:
        return out, None
    return out, None


def _pallas_kernel_available() -> bool:
    try:
        from ...ops.pallas import flash_attention  # noqa: F401
        return True
    except ImportError:
        return False


def _split_across_devices(q) -> bool:
    """Mosaic kernels take whole operands — XLA refuses to partition them
    ("Mosaic kernels cannot be automatically partitioned. Please wrap the
    call in a shard_map").  So under a hybrid mesh of several devices, or on
    an array that already spans several, attention runs the XLA formulation:
    the training twin of the serving runner's rule (ROADMAP S6)."""
    from ...distributed.fleet.topology import get_hybrid_communicate_group
    if get_hybrid_communicate_group().get_mesh().jax_mesh().size > 1:
        return True
    return (isinstance(q, jax.Array) and not isinstance(q, jax.core.Tracer)
            and len(q.sharding.device_set) > 1)


def _use_pallas(q, k=None) -> bool:
    if not _pallas_kernel_available():
        return False
    try:
        platform = q.devices().pop().platform if hasattr(q, "devices") else \
            jax.default_backend()
    except Exception:
        platform = jax.default_backend()
    if platform != "tpu" or _split_across_devices(q):
        return False
    # single dispatch predicate lives with the kernel (ADVICE r1: _use_pallas
    # and supported() had drifted apart)
    from ...ops.pallas.flash_attention import supported
    return supported(tuple(q.shape), tuple(k.shape) if k is not None else None)


def flash_attn_unpadded(*args, **kwargs):
    raise NotImplementedError(
        "varlen flash attention: use dense [B,S,H,D] flash_attention with masking; "
        "ragged support lands with the paged-attention kernel")


def sequence_mask(x, maxlen=None, dtype="int64", name=None):
    lengths = unwrap(x)
    ml = int(maxlen) if maxlen is not None else int(jnp.max(lengths))
    from ...core.dtype import convert_dtype
    row = jnp.arange(ml)
    mask = row[None, :] < lengths[..., None]
    return Tensor(mask.astype(convert_dtype(dtype)))


def paged_attention(query, key_pages, value_pages, block_tables, context_lens,
                    scale=None, name=None):
    """Decode attention against a paged KV cache (reference:
    phi/kernels/fusion block_multi_head_attention). Tensor-level wrapper over
    the Pallas kernel (ops/pallas/paged_attention.py)."""
    from ...ops.pallas.paged_attention import paged_attention as _kern
    from ...core.dispatch import apply_op, unwrap

    bt = unwrap(block_tables)
    cl = unwrap(context_lens)

    def f(q, kp, vp):
        return _kern(q, kp, vp, bt, cl, scale=scale)

    return apply_op("paged_attention", f, query, key_pages, value_pages)
