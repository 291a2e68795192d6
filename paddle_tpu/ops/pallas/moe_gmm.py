"""Grouped matrix products of a sparse expert layer: the rows of ``lhs``
lie sorted by expert, ``group_sizes[e]`` consecutive rows belong to expert
``e``, and each group meets its own expert's matrix::

    out[rows of e] = lhs[rows of e] @ rhs[e]

Dropless: there is no capacity and no dropped row; an expert nobody chose
has an empty group and its matrix is never read. Rows past the groups' sum
(assignments to experts this chip does not hold are sorted there) get no
value a caller may read.

:func:`moe_gmm` is the Pallas kernel: ``megablox.gmm`` of the installed jax
(the grouped matmul MaxText serves experts with), taken without its own
``jit`` so that the call stands in the device trace under THIS name. It
walks the row tiles that hold a group's rows and streams that expert's
matrix through VMEM in ``[tk, tn]`` tiles, once for each row tile the group
touches. :func:`moe_gmm_ref` is ``jax.lax.ragged_dot``: what the CPU and a
mesh of several devices run (XLA does not partition a Mosaic kernel).
"""
from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp

# the package's ``gmm`` is the custom-vjp wrapper; the kernel's own function
# lives in the module of the same name
_megablox = importlib.import_module(
    "jax.experimental.pallas.ops.tpu.megablox.gmm")

_ROWS = 128         # rows of lhs a tile: the MXU's height; lhs is padded to it
_TILE_BYTES = 4 << 20   # one [tk, tn] tile of an expert's matrix in VMEM


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _fit(size, cap):
    """The largest multiple of 128 up to ``cap`` that divides ``size``
    (host arithmetic on a static shape); a ``size`` with none goes whole up
    to 128."""
    whole = [t for t in range(128, min(size, cap) + 1, 128)
             if size % t == 0]
    return max(whole, default=min(size, 128))


def _tiles(k, n, itemsize):
    """``(tk, tn)``: the whole of ``n`` up to 2,048 columns and as much of
    ``k`` as keeps a tile at ``_TILE_BYTES`` (two of them are in flight)."""
    tn = _fit(n, 2048)
    return _fit(k, max(128, _TILE_BYTES // (tn * itemsize))), tn


@functools.partial(jax.jit, static_argnames=("out_dtype",))
def moe_gmm(lhs, rhs, group_sizes, out_dtype=None):
    """lhs [M, K] sorted by group, rhs [G, K, N], group_sizes [G] int32 ->
    [M, N] in ``out_dtype`` (default: lhs's). float32 accumulation."""
    m = lhs.shape[0]
    pad = -m % _ROWS
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    tk, tn = _tiles(rhs.shape[1], rhs.shape[2], rhs.dtype.itemsize)
    # the repository's default matmul precision is "highest" (core/flags):
    # right for float32 products in XLA, refused by Mosaic on bf16 operands
    with jax.default_matmul_precision("default"):
        out = _megablox.gmm.__wrapped__(
            lhs, rhs, group_sizes,
            preferred_element_type=out_dtype or lhs.dtype,
            tiling=(_ROWS, tk, tn), interpret=_interpret())
    return out[:m] if pad else out


def moe_gmm_ref(lhs, rhs, group_sizes, out_dtype=None):
    """:func:`moe_gmm` by ``jax.lax.ragged_dot`` (rows past the groups' sum
    come back 0). float32 operands keep the repository's default precision
    (``highest``); bf16 operands are exact in one pass, and on the TPU, where
    ``ragged_dot`` is a Mosaic kernel itself, take no other."""
    exact = lhs.dtype == jnp.bfloat16 and rhs.dtype == jnp.bfloat16
    return jax.lax.ragged_dot(
        lhs, rhs, group_sizes,
        precision=jax.lax.Precision.DEFAULT if exact else None,
        preferred_element_type=out_dtype or lhs.dtype)
