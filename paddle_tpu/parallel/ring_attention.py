"""Ring attention for sequence/context parallelism (SURVEY §5 long-context:
the reference provides the 'sep' mesh axis + four-direction p2p
(fleet/base/topology.py:199, pp_utils/four_directions_p2p_communication.py);
ring/blockwise attention itself lives downstream in PaddleNLP. Here it is
in-core and TPU-native: shard_map over the 'sep' axis + lax.ppermute rotating
K/V blocks around the ICI ring, with online-softmax accumulation (flash style,
f32 accumulators)."""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..core.tensor import Tensor
from ..core.dispatch import apply_op
from ..distributed.collective import mesh_ppermute
from ..distributed.fleet.topology import get_hybrid_communicate_group


def _ring_attn_local(q, k, v, axis_name, causal, scale):
    """Per-shard body: q local [B, Sq, H, D]; k/v rotate around the ring.

    Online softmax: keep running (max, sum, acc) in f32 while blocks arrive.
    Causality across blocks is decided by comparing global block offsets.
    """
    n = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    B, Sq, H, D = q.shape

    def attend(carry, kv_and_src):
        m_prev, l_prev, acc = carry
        (kb, vb), src_idx = kv_and_src
        # bf16 MXU operands + f32 accumulation (native MXU mode — upcasting
        # operands to f32 forces the slow multi-pass path); the scale and all
        # softmax statistics stay in f32
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, kb,
                            preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.DEFAULT) * scale
        if causal:
            q_pos = my_idx * Sq + jnp.arange(Sq)
            k_pos = src_idx * kb.shape[1] + jnp.arange(kb.shape[1])
            mask = q_pos[:, None] >= k_pos[None, :]
            logits = jnp.where(mask[None, None], logits, -jnp.inf)
        m_cur = jnp.max(logits, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        # guard fully-masked rows
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(logits - m_safe[..., None])
        p = jnp.where(jnp.isfinite(logits), p, 0.0)
        corr = jnp.exp(jnp.where(jnp.isfinite(m_prev), m_prev - m_safe, -jnp.inf))
        corr = jnp.where(jnp.isfinite(corr), corr, 0.0)
        l_new = l_prev * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT)
        return (m_new, l_new, acc_new)

    m0 = jnp.full((B, H, Sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    acc0 = jnp.zeros((B, H, Sq, D), jnp.float32)
    carry = (m0, l0, acc0)
    kb, vb = k, v
    src = my_idx
    perm = [(i, (i + 1) % n) for i in range(n)]
    for step in range(n):
        carry = attend(carry, ((kb, vb), src))
        if step < n - 1:
            kb = mesh_ppermute(kb, axis_name, perm)
            vb = mesh_ppermute(vb, axis_name, perm)
            src = mesh_ppermute(src, axis_name, perm)
    m, l, acc = carry
    out = acc / jnp.maximum(l, 1e-20)[..., None]
    return jnp.einsum("bhqd->bqhd", out).astype(q.dtype)


def ring_flash_attention(q, k, v, causal=True, axis_name="sep", mesh=None):
    """[B, S, H, D] with S sharded over `axis_name`; returns same sharding."""
    hcg = get_hybrid_communicate_group()
    jmesh = mesh if mesh is not None else hcg.get_mesh().jax_mesh()
    if axis_name not in jmesh.axis_names or \
            jmesh.devices.shape[jmesh.axis_names.index(axis_name)] == 1:
        from ..nn.functional.attention import _sdpa_ref
        return apply_op("ring_attention",
                        lambda a, b, c: _sdpa_ref(a, b, c, causal=causal), q, k, v)
    scale = 1.0 / math.sqrt(q.shape[-1])
    spec = P(None, axis_name, None, None)
    other = tuple(a for a in jmesh.axis_names if a != axis_name)

    def f(qa, ka, va):
        body = functools.partial(_ring_attn_local, axis_name=axis_name,
                                 causal=causal, scale=scale)
        sm = shard_map(body, mesh=jmesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
        return sm(qa, ka, va)

    return apply_op("ring_attention", f, q, k, v)
