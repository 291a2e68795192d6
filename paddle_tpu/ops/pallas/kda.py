"""The gated delta rule with a decay for every key channel (Kimi Delta
Attention, arXiv:2510.26692), on a pool of recurrent states.

For a head, with a state ``S [Dk, Dv]`` in float32 and a token's ``q, k
[Dk]`` (L2-normalised, q scaled), ``v [Dv]``, log-decay ``g [Dk] <= 0`` and
``beta`` (up to 2 where negative eigenvalues are allowed)::

    S <- Diag(exp g) S
    S <- S + k (beta (v - S^T k))^T        # (I - beta k k^T) S + beta k v^T
    o  = S^T q

Two callers. A DECODE step moves every live request one token on
(:func:`kda_step`, the Pallas kernel; :func:`kda_step_ref` in ``jnp``): the
states live in one pool ``[rows, heads, Dk, Dv]`` and a dispatch's rows name
theirs by index; the kernel reads each named state once, writes it once and
updates the pool in place (``input_output_aliases``) - XLA's
gather, update, scatter of 64 x 4 MiB would copy it. A PREFILL chunk and
the full forward run the rule over consecutive positions of one sequence
(:func:`kda_recurrence`, a ``lax.scan``).

TPU-native layout of the kernel: the grid is (rows, blocks of ``_HEADS``
heads); a program holds its ``[_HEADS, Dk, Dv]`` states in VMEM (1 MiB in,
1 MiB out, double-buffered by the pipeline) and works a head at a time on
the VPU - three broadcasts down the key axis (decay, beta k, k, q) and two
sums over it. What broadcasts ALONG the lanes arrives already standing on
the sublanes (``[Dk, _HEADS]`` columns, transposed by XLA in the wrapper:
2 MB a call against 512 MB of states), so the kernel transposes nothing.
Rows that are idle name the pool's idle row, which no request reads.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HEADS = 16        # heads of one row a program holds: see the module docstring


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _one_token(S, a, k, bk, bv, q):
    """The rule for one token on ``S [..., Dk, Dv]``; ``a, k, bk, q`` are
    columns ``[..., Dk, 1]`` (exp g, k, beta k, q), ``bv [..., 1, Dv]`` is
    beta v. Returns the new state and ``o [..., 1, Dv]``."""
    S = S * a
    d = bv - jnp.sum(S * bk, axis=-2, keepdims=True)
    S = S + k * d
    return S, jnp.sum(S * q, axis=-2, keepdims=True)


def _step_kernel(rows_ref, a_ref, k_ref, bk_ref, q_ref, bv_ref, s_ref,
                 o_ref, s_out):
    del rows_ref                    # the index maps have read it
    for h in range(s_ref.shape[1]):
        S, o = _one_token(s_ref[0, h], a_ref[0, 0, :, h:h + 1],
                          k_ref[0, 0, :, h:h + 1], bk_ref[0, 0, :, h:h + 1],
                          bv_ref[0, h:h + 1, :], q_ref[0, 0, :, h:h + 1])
        s_out[0, h] = S
        o_ref[0, h:h + 1, :] = o


@jax.jit
def kda_step(pool, rows, q, k, v, g, beta):
    """One token for each of ``B`` rows against the state pool.

    pool  [R, H, Dk, Dv] float32   every state there is (the engine's
                                   [layers, slots + 1] flattened)
    rows  [B] int32                the pool row of each dispatch row; idle
                                   rows name the idle row
    q, k, g [B, H, Dk], v [B, H, Dv], beta [B, H]   float32
    returns o [B, H, Dv] float32 and the pool, updated in place
    """
    B, H, Dk = q.shape
    Dv = v.shape[-1]
    hb = min(_HEADS, H)
    assert H % hb == 0, f"{H} heads are not whole blocks of {hb}"
    nb = H // hb

    def cols(x):            # [B, H, Dk] -> [B, nb, Dk, hb]: keys down the sublanes
        return jnp.swapaxes(x.reshape(B, nb, hb, Dk), 2, 3)

    col_spec = pl.BlockSpec((1, 1, Dk, hb), lambda b, j, rows: (b, j, 0, 0))
    row_spec = pl.BlockSpec((1, hb, Dv), lambda b, j, rows: (b, j, 0))
    state_spec = pl.BlockSpec((1, hb, Dk, Dv),
                              lambda b, j, rows: (rows[b], j, 0, 0))
    o, pool = pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, nb),
            in_specs=[col_spec] * 4 + [row_spec, state_spec],
            out_specs=[row_spec, state_spec]),
        out_shape=[jax.ShapeDtypeStruct((B, H, Dv), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operand 6 (counting the prefetched rows) is the pool; so is output 1
        input_output_aliases={6: 1},
        # in order: two rows may name one state (the idle row)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_interpret(), name="kda_step",
    )(rows, cols(jnp.exp(g)), cols(k), cols(k * beta[..., None]), cols(q),
      v * beta[..., None], pool)
    return o, pool


def kda_step_ref(pool, rows, q, k, v, g, beta):
    """:func:`kda_step` in ``jnp``: gather the rows' states, apply the
    rule, scatter them back."""
    col = lambda x: x[..., None]                         # noqa: E731
    S, o = _one_token(pool[rows], col(jnp.exp(g)), col(k),
                      col(k * beta[..., None]),
                      (v * beta[..., None])[..., None, :], col(q))
    return o[..., 0, :], pool.at[rows].set(S)


def kda_recurrence(S0, q, k, v, g, beta):
    """The rule over ``T`` consecutive positions of one sequence: ``S0 [H,
    Dk, Dv]``, ``q, k, g [T, H, Dk]``, ``v [T, H, Dv]``, ``beta [T, H]``, all
    float32. Returns ``o [T, H, Dv]`` and the state after the last
    position. A position with ``beta = 0`` and ``g = 0`` leaves the state as
    it was (how a chunk's padding rides along)."""
    def one(S, t):
        q, k, v, g, beta = t
        col = lambda x: x[..., None]                     # noqa: E731
        S, o = _one_token(S, col(jnp.exp(g)), col(k), col(k * beta[:, None]),
                          (v * beta[:, None])[:, None, :], col(q))
        return S, o[:, 0, :]

    S, o = jax.lax.scan(one, S0, (q, k, v, g, beta))
    return o, S
