"""What a model tells the serving engine about itself.

The engine's runner (``inference/engine/runner.py``) owns the device: the
pools, the write of a step's rows into them, which attention or which
recurrence reads them, the three programs and sampling. The model owns its
layers. A :class:`ServingPlan` is the seam: the model's layers on raw
arrays, each in two halves around the one thing the runner does for it, and
the order they run in.

A layer KIND is a set of like layers: the same leaves, the same two halves,
the same cache. ``cache`` says what the runner keeps for it and does
between the halves:

``"pages"``  paged KV. ``first(wl, x, pos) -> q [B, nh, D], k, v [B, kvh,
             D]``; the runner writes k and v into the rows' pages and
             attends; ``second(wl, x, att, live) -> (x, counts)``.
``"state"``  a recurrent state and a convolution's tail for every slot.
             ``first(wl, x, tail, n_valid) -> ((q, k, v, g, beta), tail)``
             on rows ``x [..., T, H]`` that follow ``tail [..., taps - 1,
             C]``; the runner reads and writes the slot's state and tail
             and runs the delta rule; ``second(wl, x, o, live) -> (x,
             counts)``.

A kind may name some of its leaves ``whole``: those reach the halves
unsliced, ``[periods, n, ...]`` as they are stacked, beside the layer's
index among its like (``wl["l"]``), and the half picks its own part. That
is for what a Pallas kernel reads: XLA cannot fuse the scan's slice of a
layer into a custom call's operand, so a scanned-over leaf would be copied
out once a layer (0.4 GB a matrix of a layer's experts).

``live [B]`` marks the rows that carry a request's token (a sparse expert
layer routes no other); ``counts`` is ``None`` or the int32 vector of
``plan.counts`` that the layer's routing gives (the runner sums it on the
device and the engine reads the sums into the registry).

The layers run in PERIODS of ``plan.period``: ``((kind, n), ...)``, ``n``
like layers of a kind after each other (one ``lax.scan``), the whole
repeated ``plan.periods`` times. Every leaf of a kind's layers is stacked
``[periods, n, ...]`` under its own key of the flat weight dict -
``plan.periods is None`` means no period axis, ``[n, ...]`` (a model of one
kind). ``plan.weights()`` yields ``(key, array)`` leaf by leaf, so a model
that cannot afford a second copy lets go of each as the runner places it;
``plan.specs(pp, mp)`` are their ``PartitionSpec``s.

A model that GENERATES BY BLOCKS says so with ``plan.block`` = Q > 0 (0: one
token a sequence a forward, chosen at the last position under a causal
mask). Its mask is causal from block to block and open both ways inside a
block of Q aligned positions; a decode dispatch is one block a sequence:
up to ``plan.denoising_steps`` forwards over the block's Q rows, which start
as ``plan.mask_token`` wherever no token is known, each followed by
``plan.unmask(conf [B, Q], masked [B, Q], step) -> [B, Q] bool`` - the
positions (among the masked) whose sampled token is kept after that
forward, by the confidence the sampler gave each - and one more forward of
the finished block that commits its keys and values. ``plan.early_exit``:
the rule may finish a block in fewer steps, so the loop asks the device
whether any live row is still masked.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp

__all__ = ["LayerKind", "ServingPlan", "stack_leaves"]


def stack_leaves(parts, shape):
    """Like layers' leaves stacked into one of ``shape``, in ONE program,
    so that nothing but the stacked leaf is made beside its parts (eager
    ``jnp.stack`` expands each part into a copy first): what a model's
    ``plan.weights()`` yields when it hands its parameters over."""
    return jax.jit(lambda *p: jnp.stack(p).reshape(shape))(*parts)


@dataclasses.dataclass(frozen=True)
class LayerKind:
    cache: str                  # "pages" | "state"
    keys: tuple                 # one layer's leaves in the weight dict
    first: Callable
    second: Callable
    whole: tuple = ()           # the keys among ``keys`` handed over unsliced


@dataclasses.dataclass(frozen=True)
class ServingPlan:
    kinds: dict                 # name -> LayerKind
    period: tuple               # ((kind name, how many), ...)
    periods: Optional[int]      # None: the leaves have no period axis
    weights: Callable           # () -> iterator of (key, array)
    specs: Callable             # (pp, mp) -> {key: PartitionSpec}
    # paged KV: heads and head size of q, and of k and v
    nh: int = 0
    kvh: int = 0
    D: int = 0
    # recurrent state of one layer and slot: [heads, dk, dv] float32 and a
    # tail of ``conv_tail`` rows of ``conv_channels`` (0: no "state" kind)
    state_heads: int = 0
    state_dk: int = 0
    state_dv: int = 0
    conv_tail: int = 0
    conv_channels: int = 0
    counts: int = 0             # length of a layer's routing counts (0: none)
    # generation by blocks (0: one token a sequence a forward)
    block: int = 0
    mask_token: int = 0
    denoising_steps: int = 0
    unmask: Optional[Callable] = None   # (conf, masked, step) -> [B, Q] bool
    early_exit: bool = False

    def layers_of(self, cache):
        """How many layers keep a cache of this kind, over all periods."""
        n = sum(c for name, c in self.period
                if self.kinds[name].cache == cache)
        return n * (self.periods or 1)

    @property
    def recurrent(self):
        return self.layers_of("state") > 0
