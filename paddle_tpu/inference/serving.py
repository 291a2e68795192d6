"""Serving runtime for (sharded) LLMs — the role the reference fills with
the FleetExecutor actor/interceptor pipeline for multi-stage inference
(paddle/fluid/distributed/fleet_executor/carrier.cc) plus the paged
KV-cache fused ops (phi/kernels/fusion block_multi_head_attention; the
encoder/decoder split there is seq_lens_encoder vs seq_lens_decoder,
python/paddle/incubate/nn/functional/block_multihead_attention.py:33, and
sampling is in-op via phi top_p_sampling).

The implementation lives in :mod:`paddle_tpu.inference.engine` — the
monolith split into ``request`` / ``pages`` / ``runner`` / ``spec`` /
``scheduler`` / ``core`` / ``disagg`` along the scheduler–pool–runner
interfaces (see that package's docstring for the layering).  This module
is the stable import surface: everything historically imported from
``paddle_tpu.inference.serving`` keeps resolving here.

TPU-native design (details in the engine modules):
- TWO jitted programs serve the whole engine: a chunked PREFILL step and a
  token-level continuous-batching DECODE step (Orca-style); sampling is
  in-graph with per-slot parameters, matching ``model.generate``
  token-for-token at equal seed.
- KV lives in PAGES [L, n_pages, page, KVH, D] with host-managed per-slot
  page tables, on-demand growth, and youngest-slot preemption-recompute
  when the pool runs dry (vLLM-style).
- AUTOMATIC PREFIX CACHING (``prefix_cache=True``): chain-hashed full
  prompt pages, refcounted sharing, copy-on-write, LRU reclaim — cached KV
  is bit-identical to recomputation, so hits change dispatch counts, never
  tokens.
- Weights are stacked [L, ...] and placed with NamedShardings (layers over
  pp, head/ffn dims over mp); GSPMD inserts the collectives.
- DISAGGREGATED PREFILL/DECODE (:class:`DisaggEngine`): the two phases on
  separate mesh slices with KV-page handoff, so decode token cadence never
  stalls behind a prompt.
"""
from __future__ import annotations

from .engine import (  # noqa: F401
    DisaggEngine,
    LLMEngine,
    ModelRunner,
    PagePool,
    Request,
    RequestStatus,
    Scheduler,
    SpecConfig,
    prefix_page_keys,
    split_mesh,
)
from .engine.spec import _NgramProposer  # noqa: F401  (test import)

__all__ = ["LLMEngine", "DisaggEngine", "split_mesh", "Request",
           "RequestStatus", "SpecConfig", "prefix_page_keys",
           "Scheduler", "PagePool", "ModelRunner"]
