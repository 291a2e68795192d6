"""Weight-only quantized matmul Pallas kernel (reference capability:
phi/kernels/gpu/weight_only_linear_kernel.cu + cutlass fpA_intB gemm).

Decode-time linear layers are WEIGHT-BANDWIDTH bound: y = x @ W with tiny M
streams the whole weight matrix from HBM per token. Storing W as int8/int4
halves/quarters that stream — but only if the bf16 copy is never
materialized. This kernel reads int8 (or packed int4) tiles into VMEM,
dequantizes per tile on the VPU, and feeds the MXU directly; the f32
accumulator applies the per-output-channel scale in the epilogue.

grid (N/bn, K/bk): k is the fast (sequential) axis so the f32 accumulator
lives in VMEM scratch across k steps; x [M, bk] tiles are small (decode M),
weight tiles stream once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BK = 256
BN = 256


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _kernel(x_ref, qw_ref, s_ref, o_ref, acc_s, *, nk, int4, out_dtype):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_s[:] = jnp.zeros_like(acc_s)

    q = qw_ref[...]
    if int4:
        # Mosaic legalizes no i8 vector shifts (arith.shli on vector<..xi8>):
        # widen to i32 first, then sign-extend each nibble arithmetically
        q = q.astype(jnp.int32)
        lo = (q << 28) >> 28                     # sign-extend low nibble
        hi = q >> 4                              # arithmetic shift high
        # packed rows [bk//2, bn] -> interleaved [bk, bn] (row 2i from lo,
        # row 2i+1 from hi) matching the packer in quantization/weight_only
        w = jnp.stack([lo, hi], axis=1).reshape(-1, q.shape[-1])
    else:
        w = q
    wt = w.astype(jnp.bfloat16)                  # tile-local dequant (VMEM)
    acc_s[:] = acc_s[:] + jax.lax.dot_general(
        x_ref[...], wt, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.DEFAULT)

    @pl.when(k == nk - 1)
    def _finish():
        o_ref[...] = (acc_s[:] * s_ref[0].astype(jnp.float32)[None, :]
                      ).astype(out_dtype)


def quant_matmul(x, qw, scale, *, int4=False, bk=BK, bn=BN):
    """x [M, K] float/bf16, qw int8 [K, N] (or packed [K//2, N] for int4),
    scale f32 [N] -> y [M, N] in x.dtype."""
    M, K = x.shape
    N = qw.shape[1]
    Kq = qw.shape[0] * (2 if int4 else 1)
    if Kq != K:
        raise ValueError(f"weight K {Kq} != x K {K}")
    if K % bk or N % bn:
        raise ValueError(f"shapes must divide blocks ({bk},{bn})")
    Mp = max(8, M)           # sublane-pad tiny decode batches
    if Mp != M:
        x = jnp.pad(x, ((0, Mp - M), (0, 0)))
    nk, nn = K // bk, N // bn
    wk = bk // 2 if int4 else bk
    out = pl.pallas_call(
        functools.partial(_kernel, nk=nk, int4=int4, out_dtype=x.dtype),
        grid=(nn, nk),
        in_specs=[
            pl.BlockSpec((Mp, bk), lambda n, k: (0, k)),
            pl.BlockSpec((wk, bn), lambda n, k: (k, n)),
            pl.BlockSpec((1, bn), lambda n, k: (0, n)),
        ],
        out_specs=pl.BlockSpec((Mp, bn), lambda n, k: (0, n)),
        out_shape=jax.ShapeDtypeStruct((Mp, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((Mp, bn), jnp.float32)],
        interpret=_interpret(),
    )(x, qw, scale.reshape(1, N))
    return out[:M]


def supported(M, K, N, int4=False, bk=BK, bn=BN):
    return K % bk == 0 and N % bn == 0
