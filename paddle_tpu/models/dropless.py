"""The dropless sparse expert layer behind its router: what every model
whose experts go through ``ops/pallas/moe_gmm.py`` shares. A model's own
function normalises, scores and chooses (``chosen`` and ``weight`` below
are its router's), then calls :func:`routed_experts` for the sum over the
chosen experts THIS program holds and :func:`routing_counts` for what the
engine's registry reads (``serving_moe_*_total``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["ROUTING_COUNTS", "ROUTING_COUNTS_SKIP", "routed_experts",
           "routing_counts"]

# what one expert layer's routing gives for one call, in this order
ROUTING_COUNTS = ("calls", "rows", "assignments", "experts_touched",
                  "max_load")
# ... and of a router that may choose NO expert (the choice past its last
# one): the live (row, choice) pairs that chose it
ROUTING_COUNTS_SKIP = ROUTING_COUNTS + ("skipped",)


def routed_experts(p, h, chosen, weight, live, e, offset, gmm, l, dtype):
    """``sum_j weight_j * expert_{chosen_j}(h)`` over the chosen experts
    held here, for rows ``h [N, H]`` (normed): ``chosen``, ``weight [N,
    k]`` from the model's router, ``live [N]`` > 0 the rows that are routed
    (an idle slot and a chunk's padding are not), ``e`` experts held from
    ``offset`` on. Returns ``(routed [N, H] float32, held [N, k] bool,
    sizes [e] int32)``.

    ``p["wg"]``, ``p["wu"]``, ``p["wd"]`` may come as one layer's ``[E, in,
    out]`` or as the whole stack of like layers ``[..., E, in, out]`` with
    ``l`` this layer's index among them: the stack goes to ``gmm`` as it
    lies, as ``layers x E`` groups of which only this layer's have rows (a
    slice of it would be a copy of a layer's experts in front of the
    kernel).

    Dropless: every (row, chosen held expert) pair is an assignment; they
    are sorted by expert, the rows gathered in that order, and the three
    grouped products (``gmm``) see ``group_sizes`` of whatever they are -
    no capacity. Pairs whose expert lives on another chip sort past the
    last group and weigh nothing, and so does a choice of NO expert (a
    router whose last output means "skip", as ZAYA's): its index lies past
    every chip's experts, so it is held nowhere, is no assignment, and its
    row costs the grouped products nothing - the compute a token gets
    varies with what its router chose."""
    n, k = chosen.shape
    local = chosen - offset
    held = (local >= 0) & (local < e) & (live > 0)[:, None]
    group = jnp.where(held, local, e).reshape(-1)                # [N * k]
    order = jnp.argsort(group, stable=True)
    sizes = jnp.sum(group[:, None] == jnp.arange(e, dtype=group.dtype),
                    axis=0, dtype=jnp.int32)
    wg, wu, wd = (p[n_].reshape((-1,) + p[n_].shape[-2:])
                  for n_ in ("wg", "wu", "wd"))
    groups = jax.lax.dynamic_update_slice(
        jnp.zeros((wg.shape[0],), jnp.int32), sizes, (l * e,))
    rows = h[order // k]
    act = (jax.nn.silu(gmm(rows, wg, groups, jnp.float32))
           * gmm(rows, wu, groups, jnp.float32)).astype(dtype)
    out = gmm(act, wd, groups, jnp.float32)                      # [N * k, H]
    # rows past the groups hold nothing a sum may see
    out = jnp.where(held.reshape(-1)[order][:, None],
                    out * weight.reshape(-1)[order][:, None], 0.0)
    routed = out[jnp.argsort(order)].reshape(n, k, -1).sum(axis=1)
    return routed, held, sizes


def routing_counts(live, held, sizes, skipped=None):
    """One call's ``ROUTING_COUNTS`` (int32) from what
    :func:`routed_experts` returned; with ``skipped [N, k]`` (the choices
    of no expert) ``ROUTING_COUNTS_SKIP``, the live ones counted last."""
    counts = [jnp.int32(1), jnp.sum(live > 0, dtype=jnp.int32),
              jnp.sum(held, dtype=jnp.int32),
              jnp.sum(sizes > 0, dtype=jnp.int32), jnp.max(sizes)]
    if skipped is not None:
        counts.append(jnp.sum(skipped & (live > 0)[:, None],
                              dtype=jnp.int32))
    return jnp.stack(counts)
