"""Engine core as a package: the serving monolith split along its natural
interfaces.

Layering (each module imports only what is below it; the import-cycle
guard in ``tests/test_analysis.py`` enforces this):

    request    Request / RequestStatus / prefix_page_keys — lifecycle types
    metrics    _EngineMetrics — per-engine registry children (labelled)
    pages      PagePool — paged-KV accounting: refcounts, prefix-cache
               chain-hash index, LRU reclaim, audit
    runner     ModelRunner — the jitted prefill/decode/verify programs and
               the KV buffers over ONE mesh (slice), plus page gather/
               scatter for cross-slice handoff
    spec       SpecConfig, the draft proposers, and the engine's
               speculative-decode orchestration mixin
    scheduler  Scheduler — admission, deadlines, continuous batching,
               preemption, slot/page-table state
    core       LLMEngine — the facade composing the above; owns step
               policy, failure isolation, and the auto-fits
    disagg     DisaggEngine — prefill and decode LLMEngines on separate
               mesh slices with KV-page handoff between their pools

``paddle_tpu.inference.serving`` re-exports the public names, so existing
imports keep working unchanged.
"""
from .request import Request, RequestStatus, prefix_page_keys
from .pages import PagePool
from .runner import ModelRunner
from .spec import SpecConfig
from .scheduler import Scheduler
from .core import LLMEngine
from .disagg import DisaggEngine, split_mesh

__all__ = [
    "LLMEngine", "DisaggEngine", "split_mesh",
    "Scheduler", "PagePool", "ModelRunner",
    "Request", "RequestStatus", "SpecConfig", "prefix_page_keys",
]
