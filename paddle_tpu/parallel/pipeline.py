"""SPMD pipeline schedule over the 'pp' mesh axis (SURVEY §7: "PP = stage-
partitioned program + collective_permute microbatch schedule").

Reference semantics: fleet/meta_parallel/pipeline_parallel.py (1F1B :575,
interleave :1179) built on NCCL p2p. TPU-native replacement: every stage runs
the SAME program under shard_map; stage weights are stacked on a leading [pp]
dim; activations rotate via lax.ppermute. A GPipe fill-drain over M microbatches
completes in M + P - 1 ticks; XLA overlaps the ppermute with compute on ICI.

This powers the homogeneous-transformer fast path; the generic host-driven
PipelineLayer container lives in pipeline_layer.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.tensor import Tensor
from ..core.dispatch import apply_op, unwrap
from ..distributed.collective import mesh_ppermute


def pipeline_forward(stage_fn, stacked_params, x_micro, *, mesh, axis_name="pp"):
    """Run microbatched GPipe forward.

    stage_fn(params_slice, x) -> y        (same shapes for x and y)
    stacked_params: pytree with leading [P] dim on every leaf (stage-major)
    x_micro: [M, B, ...] microbatches (already embedded — homogeneous stages)
    returns [M, B, ...] outputs from the LAST stage (replicated).
    """
    P_ = mesh.devices.shape[mesh.axis_names.index(axis_name)]

    def body(params, xs):
        # params: local stage slice (leading dim 1); xs: all microbatches
        params = jax.tree_util.tree_map(lambda a: a[0], params)
        idx = jax.lax.axis_index(axis_name)
        M = xs.shape[0]
        n_ticks = M + P_ - 1
        perm = [(i, (i + 1) % P_) for i in range(P_)]

        buf = jnp.zeros_like(xs[0])
        outs = jnp.zeros_like(xs)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (when valid)
            m_in = jnp.clip(t, 0, M - 1)
            inject = jnp.where(t < M, 1.0, 0.0).astype(xs.dtype)
            x_in = jnp.where(idx == 0,
                             xs[m_in] * inject + buf * (1 - inject) * 0.0,
                             buf)
            y = stage_fn(params, x_in)
            # last stage's output for microbatch (t - (P-1)) is ready at tick t
            m_out = t - (P_ - 1)
            valid_out = (m_out >= 0) & (m_out < M)
            outs = jax.lax.cond(
                valid_out,
                lambda o: o.at[jnp.clip(m_out, 0, M - 1)].set(y),
                lambda o: o, outs)
            buf_next = mesh_ppermute(y, axis_name, perm)
            return (buf_next, outs), None

        (buf, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(n_ticks))
        # per-stage output shard; only the last stage's slice is meaningful.
        # Returning it SHARDED (leading pp axis) instead of zero+psum avoids
        # an O(M*B*hidden) all-reduce every forward (r2 weak #8): the [P-1]
        # slice below moves just the last stage's copy, and only when a
        # consumer actually needs it elsewhere.
        return outs[None]

    pspec_params = jax.tree_util.tree_map(lambda _: P(axis_name), stacked_params)
    f = shard_map(body, mesh=mesh,
                  in_specs=(pspec_params, P()),
                  out_specs=P(axis_name), check_vma=False)
    return f(stacked_params, x_micro)[P_ - 1]


def pipeline_call(stage_fn, stacked_params, x_micro, mesh, axis_name="pp"):
    """Tensor-level wrapper with autograd through the schedule."""
    params_arrays = jax.tree_util.tree_map(
        lambda t: unwrap(t) if isinstance(t, Tensor) else t, stacked_params)
    leaves, treedef = jax.tree_util.tree_flatten(params_arrays)

    def f(x, *param_leaves):
        params = jax.tree_util.tree_unflatten(treedef, param_leaves)
        return pipeline_forward(stage_fn, params, x, mesh=mesh, axis_name=axis_name)

    tensor_leaves = jax.tree_util.tree_flatten(
        stacked_params, is_leaf=lambda x: isinstance(x, Tensor))[0]
    return apply_op("pipeline", f, x_micro, *tensor_leaves)
