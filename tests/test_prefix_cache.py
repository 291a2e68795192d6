"""Automatic prefix caching (ISSUE 3 tentpole): shared KV pages across
requests via chain-hash lookup, refcounted page tables, copy-on-write on
shared-page writes, LRU eviction of cached-but-unreferenced pages.
Correctness bar everywhere: byte-identical tokens vs a prefix_cache=False
engine at the same seeds.

One cache-on/cache-off engine pair is module-shared (each LLMEngine build
compiles its prefill program — per-test engines would dominate suite wall
time); tests that need special pool geometry build their own."""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.inference.serving import LLMEngine


@pytest.fixture(scope="module")
def model():
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    pt.seed(0)
    cfg = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=176,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, max_position_embeddings=128)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def _engine(model, pc, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_chunk", 8)
    return LLMEngine(model, prefix_cache=pc, **kw)


@pytest.fixture(scope="module")
def eng_off(model):
    return _engine(model, False)


@pytest.fixture(scope="module")
def eng_on(model):
    return _engine(model, True)


def _serve_one_by_one(eng, prompts, **req_kw):
    """Admit + finish each request before the next (keeps the cache warm
    between requests). Returns (results, prefill dispatch counts)."""
    outs, disp = [], []
    for p in prompts:
        rid = eng.add_request(p, **req_kw)
        eng.run_until_done()
        outs.append(eng.result(rid))
        disp.append(eng.sched.finished[rid].prefill_dispatches)
    return outs, disp


class TestPrefixCache:
    def test_shared_prefix_fewer_dispatches_and_parity(self, eng_on, eng_off):
        rng = np.random.RandomState(0)
        prefix = rng.randint(1, 128, (16,)).astype(np.int32)  # 2 full pages
        prompts = [np.concatenate([prefix,
                                   rng.randint(1, 128, (5,)).astype(np.int32)])
                   for _ in range(2)]
        ref, ref_disp = _serve_one_by_one(eng_off, prompts, max_new_tokens=6)
        got, disp = _serve_one_by_one(eng_on, prompts, max_new_tokens=6)
        assert got == ref                      # byte-identical tokens
        # the second request's 2-page shared prefix is served from cache:
        # strictly fewer prefill dispatches than the first request
        assert disp[1] < disp[0], (disp, ref_disp)
        st = eng_on.prefix_cache_stats()
        assert st["hits"] >= 2 and st["cached_pages"] >= 2, st
        # the cache-off engine must pay full prefill both times
        assert ref_disp[0] == ref_disp[1]

    def test_seeded_sampling_parity(self, eng_on, eng_off):
        rng = np.random.RandomState(1)
        prefix = rng.randint(1, 128, (16,)).astype(np.int32)
        prompts = [np.concatenate([prefix,
                                   rng.randint(1, 128, (3,)).astype(np.int32)])
                   for _ in range(2)]
        kw = dict(max_new_tokens=5, do_sample=True, temperature=0.8,
                  top_p=0.9, seed=1234)
        ref, _ = _serve_one_by_one(eng_off, prompts, **kw)
        got, _ = _serve_one_by_one(eng_on, prompts, **kw)
        assert got == ref

    def test_cow_on_shared_page(self, eng_on, eng_off):
        """A fully-cached prompt re-prefills its final token into the LAST
        shared page while the original owner still maps it — the write must
        copy, not clobber the sharer's prefix."""
        rng = np.random.RandomState(2)
        p = rng.randint(1, 128, (16,)).astype(np.int32)  # exactly 2 pages

        def serve(eng):
            r1 = eng.add_request(p, max_new_tokens=8)
            eng.step()                       # admit + first prefill chunk
            while (eng.sched.slots[0] is not None
                   and eng.sched.slots[0].pos < len(p)):
                eng.step()                   # r1 prefilled, still decoding
            r2 = eng.add_request(p, max_new_tokens=8)
            eng.run_until_done()
            return eng.result(r1), eng.result(r2)

        ref = serve(eng_off)
        cow0 = eng_on.pool.cache_cow_copies
        got = serve(eng_on)
        assert got == ref
        assert eng_on.pool.cache_cow_copies > cow0, eng_on.prefix_cache_stats()

    def test_eviction_under_pool_pressure(self, model):
        """Pool far smaller than the distinct-prompt working set: cached
        pages must be reclaimed LRU (not starve admission) and every
        request must still match the cache-off engine."""
        rng = np.random.RandomState(3)
        prompts = [rng.randint(1, 128, (24,)).astype(np.int32)
                   for _ in range(4)]
        kw = dict(max_batch=1, max_len=48)
        ref, _ = _serve_one_by_one(_engine(model, False, **kw), prompts,
                                   max_new_tokens=4)
        eng = _engine(model, True, **kw)
        got, _ = _serve_one_by_one(eng, prompts, max_new_tokens=4)
        assert got == ref
        assert eng.pool.cache_evictions >= 1, eng.prefix_cache_stats()

    def test_preemption_oversubscription_parity(self, model):
        """Concurrent slots + a pool too small for everyone's decode growth:
        preemption (recompute) must interoperate with shared/cached pages
        and still produce identical tokens."""
        rng = np.random.RandomState(4)
        prefix = rng.randint(1, 128, (16,)).astype(np.int32)
        prompts = [np.concatenate([prefix,
                                   rng.randint(1, 128, (4,)).astype(np.int32)])
                   for _ in range(3)]
        # worst case 2 slots x ceil(40/8)=10 pages; a 7-page pool runs dry
        # once both slots outgrow their prompts mid-decode
        kw = dict(max_batch=2, max_len=40, page_pool=7)

        def serve(eng):
            rids = [eng.add_request(p, max_new_tokens=12) for p in prompts]
            eng.run_until_done()
            return [eng.result(r) for r in rids]

        ref_eng = _engine(model, False, **kw)
        ref = serve(ref_eng)
        eng = _engine(model, True, **kw)
        got = serve(eng)
        assert got == ref
        # the configuration must actually exercise the oversubscribed path
        assert eng.sched.preemptions + ref_eng.sched.preemptions > 0

    def test_knob_off_is_legacy_engine(self, eng_off):
        assert len(eng_off.sched.finished) > 0      # served earlier tests
        st = eng_off.prefix_cache_stats()
        assert st["hits"] == st["misses"] == st["evictions"] == 0
        assert st["cached_pages"] == 0 and st["reclaimable_pages"] == 0
        # every page back on the free list, exactly as before the feature
        assert len(eng_off.pool.free_pages) == eng_off.n_pages - 1

    def test_stats_and_full_recycle_with_cache_on(self, eng_on):
        st = eng_on.prefix_cache_stats()
        assert st["hits"] > 0 and st["cached_pages"] > 0
        assert st["prefill_dispatches"] > 0
        # all pages accounted for: free + reclaimable == whole pool
        assert (len(eng_on.pool.free_pages) + len(eng_on.pool.lru)) \
            == eng_on.n_pages - 1
