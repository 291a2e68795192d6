"""Weight-only quantization for LLM serving (reference:
python/paddle/nn/quant/quantized_linear.py weight_quantize /
weight_only_linear over phi weight_only_linear_kernel).

TPU-native: int8 weights live in HBM at half/quarter the bytes; the matmul
upcasts in-register and applies the per-channel scale in the epilogue —
XLA fuses `(x @ int8.astype(bf16)) * scale` into one MXU op, halving the
weight-streaming bandwidth that dominates decode."""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..core.dispatch import apply_op, unwrap

__all__ = ["weight_quantize", "weight_dequantize", "weight_only_linear"]


def weight_quantize(x, algo="weight_only_int8", group_size=-1):
    """w [in, out] -> (qw int8 [in, out] or packed int4, scale f32 [out]).

    algo: weight_only_int8 | weight_only_int4 (packed two nibbles/byte)."""
    w = unwrap(x)
    if algo not in ("weight_only_int8", "weight_only_int4"):
        raise ValueError(f"unsupported algo {algo}")
    bits = 8 if algo.endswith("int8") else 4
    qmax = float(2 ** (bits - 1) - 1)
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=0) / qmax, 1e-9)
    q = jnp.clip(jnp.round(w / s[None, :]), -qmax, qmax).astype(jnp.int8)
    if bits == 4:
        if q.shape[0] % 2:
            raise ValueError("int4 packing needs even in_features")
        lo = q[0::2] & 0xF
        hi = (q[1::2] & 0xF) << 4
        q = (lo | hi).astype(jnp.int8)          # [in//2, out]
    return Tensor(q), Tensor(s.astype(jnp.float32))


def weight_dequantize(x, scale, algo="weight_only_int8", out_dtype="float32"):
    q, s = unwrap(x), unwrap(scale)
    if algo.endswith("int4"):
        lo = (q << 4).astype(jnp.int8) >> 4     # sign-extend low nibble
        hi = q >> 4                              # arithmetic shift: high
        q = jnp.stack([lo, hi], axis=1).reshape(-1, q.shape[-1])
    return Tensor((q.astype(jnp.float32) * s[None, :]).astype(out_dtype))


def weight_only_linear(x, weight, bias=None, weight_scale=None,
                       weight_dtype="int8", arch=None, group_size=-1):
    """y = x @ dequant(weight) + bias (reference: quantized_linear.py:33).

    On TPU, no-grad calls with block-divisible shapes run the Pallas
    quant-matmul kernel: int8/int4 tiles dequantize in VMEM and feed the
    MXU directly, so the bf16 weight copy is NEVER materialized in HBM —
    the weight stream (what bounds decode) stays at the quantized width.
    Other cases use the XLA dequant formulation."""
    is4 = str(weight_dtype) == "int4"
    import jax
    from ..core.dispatch import _requires_grad
    from ..ops.pallas import quant_matmul as qmm
    K_in = (unwrap(weight).shape[0] * (2 if is4 else 1))
    N = unwrap(weight).shape[1]
    xa = unwrap(x)
    M = int(np.prod(xa.shape[:-1])) if xa.ndim > 1 else 1
    from ..core import flags as _flags
    use_kernel = (_flags.flag("weight_only_use_kernel")
                  and jax.default_backend() == "tpu"
                  and not _requires_grad((x, weight, weight_scale))
                  and xa.shape[-1] == K_in
                  and qmm.supported(M, K_in, N, int4=is4))

    def f(a, qw, s, *b):
        lead = a.shape[:-1]
        if use_kernel:
            y2 = qmm.quant_matmul(a.reshape(-1, a.shape[-1]), qw,
                                  s.astype(jnp.float32), int4=is4)
            y = y2.reshape(*lead, qw.shape[-1])
        else:
            if is4:
                lo = (qw << 4).astype(jnp.int8) >> 4
                hi = qw >> 4
                wq = jnp.stack([lo, hi], axis=1).reshape(-1, qw.shape[-1])
            else:
                wq = qw
            y = (a @ wq.astype(a.dtype)) * s.astype(a.dtype)
        return y + b[0].astype(a.dtype) if b else y

    args = (x, weight, weight_scale) + ((bias,) if bias is not None else ())
    return apply_op("weight_only_linear", f, *args)
