"""Ulysses (DeepSpeed-style) all-to-all sequence parallelism (SURVEY §5
long-context mechanism 2: the reference wires the 'sep' mesh axis through
topology and leaves the attention-level CP algorithms — ring attention AND
Ulysses all-to-all — to PaddleNLP; both are in-core here).

TPU-native: ONE shard_map over 'sep' whose body does
  all_to_all(seq-shard -> head-shard) -> full-sequence flash attention on
  the local head group -> all_to_all back.
The two all-to-alls ride ICI; between them every device sees the FULL
sequence for H/sep heads, so the attention itself needs no communication —
the right trade when S >> H and the ring's per-step latency would dominate.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..core.dispatch import apply_op
from ..distributed.collective import mesh_all_to_all
from ..distributed.fleet.topology import get_hybrid_communicate_group

__all__ = ["ulysses_attention"]


def _ulysses_local(q, k, v, axis_name, causal, scale):
    """Per-shard body. q/k/v local: [B, S/n, H, D] -> out [B, S/n, H, D]."""
    n = jax.lax.psum(1, axis_name)

    def seq_to_heads(x):
        # [B, s, H, D] -> [B, s*n, H/n, D]: tiled all_to_all splits the head
        # axis into n chunks (chunk i -> rank i) and concatenates received
        # seq chunks in rank order — global sequence order, rank-major heads
        return mesh_all_to_all(x, axis_name, split_axis=2, concat_axis=1)

    def heads_to_seq(x):
        # [B, S, H/n, D] -> [B, S/n, H, D]: exact inverse
        return mesh_all_to_all(x, axis_name, split_axis=1, concat_axis=2)

    qg, kg, vg = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    # full-sequence attention on the local head group, BLOCKWISE over K with
    # an online softmax — memory O(S * block), never the dense [S, S]
    # logits this mode exists to avoid at long context
    out = _blockwise_sdpa(qg, kg, vg, causal=causal, scale=scale)
    return heads_to_seq(out.astype(q.dtype))


def _blockwise_sdpa(q, k, v, causal, scale, block=1024):
    """[B, S, H, D] flash-style attention via lax.scan over K blocks."""
    B, S, H, D = q.shape
    blk = min(block, S)
    while S % blk:          # static divisor of S
        blk //= 2
    nk = S // blk
    # bf16 MXU operands + f32 accumulation (native MXU mode; see
    # ring_attention) — scale and softmax statistics stay f32
    kb = k.reshape(B, nk, blk, H, D).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nk, blk, H, D).transpose(1, 0, 2, 3, 4)
    q_pos = jnp.arange(S)

    def body(carry, xs):
        m_prev, l_prev, acc = carry
        kc, vc, j = xs
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, kc,
                            preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.DEFAULT) * scale
        if causal:
            k_pos = j * blk + jnp.arange(blk)
            mask = q_pos[:, None] >= k_pos[None, :]
            logits = jnp.where(mask[None, None], logits, -1e30)
        m_cur = jnp.max(logits, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(logits - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(vc.dtype), vc,
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT)
        return (m_new, l_new, acc), None

    init = (jnp.full((B, H, S), -jnp.inf, jnp.float32),
            jnp.zeros((B, H, S), jnp.float32),
            jnp.zeros((B, H, S, D), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(body, init,
                                  (kb, vb, jnp.arange(nk)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3)    # [B, H, S, D] -> [B, S, H, D]


def ulysses_attention(q, k, v, causal=True, axis_name="sep", mesh=None):
    """[B, S, H, D] with S sharded over `axis_name`; H must be divisible by
    the axis size. Returns the same sharding."""
    hcg = get_hybrid_communicate_group()
    jmesh = mesh if mesh is not None else hcg.get_mesh().jax_mesh()
    if axis_name not in jmesh.axis_names or \
            jmesh.devices.shape[jmesh.axis_names.index(axis_name)] == 1:
        from ..nn.functional.attention import _sdpa_ref
        return apply_op("ulysses_attention",
                        lambda a, b, c: _sdpa_ref(a, b, c, causal=causal),
                        q, k, v)
    n = jmesh.devices.shape[jmesh.axis_names.index(axis_name)]
    if q.shape[2] % n != 0:
        raise ValueError(
            f"ulysses needs heads ({q.shape[2]}) divisible by the "
            f"'{axis_name}' axis size ({n})")
    scale = 1.0 / math.sqrt(q.shape[-1])
    spec = P(None, axis_name, None, None)

    def f(qa, ka, va):
        body = functools.partial(_ulysses_local, axis_name=axis_name,
                                 causal=causal, scale=scale)
        sm = shard_map(body, mesh=jmesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
        return sm(qa, ka, va)

    return apply_op("ulysses_attention", f, q, k, v)
