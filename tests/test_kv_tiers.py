"""Tiered KV cache (ISSUE 17): host-RAM spill tier + peer-replica page
pulls.  The parity bar everywhere: tokens byte-identical to an engine with
no cache at all — every tier is a pure performance layer, and every fault
path (kv.spill / kv.restore / kv.peer_pull) must degrade to the tier below
(eviction / re-prefill / cold recompute), never to a wrong token."""
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.inference.serving import LLMEngine, prefix_page_keys
from paddle_tpu.testing import FAULTS, Always, FailNth, injected


@pytest.fixture(autouse=True)
def _no_leftover_faults():
    FAULTS.reset()
    yield
    FAULTS.reset()


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


def _engine(model, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_chunk", 8)
    return LLMEngine(model, **kw)


def _pressure_engine(model, host_bytes=64 << 20, **kw):
    """6-page pool, one 6-page slot: any two distinct 5-page prompts churn
    the pool, so serving A, B, A forces A's chain through the spill tier."""
    kw.setdefault("max_batch", 1)
    kw.setdefault("max_len", 48)
    kw.setdefault("page_pool", 6)
    kw.setdefault("prefix_cache", True)
    kw.setdefault("host_cache_bytes", host_bytes)
    return _engine(model, **kw)


@pytest.fixture(scope="module")
def ref_pressure(model):
    """Cache-off reference at the pressure geometry (module-shared: each
    engine build compiles a prefill program)."""
    return _engine(model, max_batch=1, max_len=48, page_pool=6,
                   prefix_cache=False)


def _churn_prompts(seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, 128, (40,)).astype(np.int32) for _ in range(2)]


def _serve_one_by_one(eng, prompts, **req_kw):
    outs, disp = [], []
    for p in prompts:
        rid = eng.add_request(p, **req_kw)
        eng.run_until_done()
        outs.append(eng.result(rid))
        disp.append(eng.sched.finished[rid].prefill_dispatches)
    return outs, disp


class TestHostTier:
    def test_spill_restore_parity_skips_reprefill(self, model, ref_pressure):
        """A fully-evicted 5-page chain comes back from the host tier: the
        re-served prompt pays exactly ONE prefill dispatch (the final
        token) instead of the full prefill — and its tokens are identical
        to the no-cache engine's."""
        a, b = _churn_prompts()
        plan = [a, b, a]
        ref, ref_disp = _serve_one_by_one(ref_pressure, plan,
                                          max_new_tokens=4)
        eng = _pressure_engine(model)
        got, disp = _serve_one_by_one(eng, plan, max_new_tokens=4)
        assert got == ref
        st = eng.kv_tier_stats()
        assert st["host_spills"] >= 5, st      # B's admission evicted A
        assert st["host_restores"] >= 5, st    # A's re-admission restored
        assert st["host_spill_drops"] == 0 and st["host_restore_failures"] == 0
        assert st["hits_host"] >= 5, st
        assert st["host_spill_bytes"] > 0 and st["host_restore_bytes"] > 0
        # the restore made re-admission as cheap as a full HBM hit: only
        # the prompt's final token re-prefills
        assert disp[2] == 1, (disp, st)
        assert disp[2] < ref_disp[2], (disp, ref_disp)
        assert eng.audit_refcounts() == []

    def test_prefix_keys_and_health_advertise_host_tier(self, model):
        """Spilled chains show up in prefix_keys() (fleet join warming and
        peer pulls read it) and health() carries the host-tier gauges."""
        a, b = _churn_prompts(seed=1)
        eng = _pressure_engine(model)
        _serve_one_by_one(eng, [a, b], max_new_tokens=4)
        keys = set(eng.prefix_keys())
        spilled = set(prefix_page_keys(a, eng.page))
        assert spilled <= keys, "host-only chains must be advertised"
        resident = set(eng.pool.key_page)
        assert not (spilled <= resident)       # A really was evicted
        h = eng.health()
        assert h["host_cached_pages"] >= 5
        assert h["host_bytes"] > 0
        assert h["host_headroom_pages"] >= 0

    def test_host_budget_evicts_oldest_chain(self, model, ref_pressure):
        """A host tier sized for 2 pages cannot hold a 5-page chain: old
        entries age out (counted), and a re-serve that misses the host
        tier falls back to plain recompute — still token-exact."""
        a, b = _churn_prompts(seed=2)
        page_bytes = ref_pressure.kv_bytes_per_page()
        eng = _pressure_engine(model, host_bytes=2 * page_bytes)
        plan = [a, b, a]
        ref, ref_disp = _serve_one_by_one(ref_pressure, plan,
                                          max_new_tokens=4)
        got, disp = _serve_one_by_one(eng, plan, max_new_tokens=4)
        assert got == ref
        st = eng.kv_tier_stats()
        assert st["host_evictions"] > 0, st
        assert st["host_cached_pages"] <= 2, st
        assert st["host_bytes"] <= 2 * page_bytes, st
        assert eng.audit_refcounts() == []

    def test_preemption_spills_decoded_pages(self, model):
        """Scheduler preemption demotes the victim's already-decoded pages
        to the host tier (registered under folded prompt+output keys), so
        its resume restores instead of re-prefilling everything."""
        rng = np.random.RandomState(3)
        # two slots, 12-page pool: both requests decoding past their
        # prompts exhausts the pool and preempts the youngest
        eng = _engine(model, max_batch=2, max_len=48, page_pool=9,
                      prefix_cache=True, host_cache_bytes=64 << 20)
        ref = _engine(model, max_batch=2, max_len=48, page_pool=9,
                      prefix_cache=False)
        prompts = [rng.randint(1, 128, (30,)).astype(np.int32)
                   for _ in range(2)]

        def serve(e):
            rids = [e.add_request(p, max_new_tokens=16) for p in prompts]
            e.run_until_done()
            return [e.result(r) for r in rids]

        want = serve(ref)
        got = serve(eng)
        assert got == want
        assert ref.sched.preemptions > 0, "geometry no longer preempts"
        st = eng.kv_tier_stats()
        assert st["host_spills"] > 0, st
        assert eng.audit_refcounts() == []


class TestHostTierChaos:
    def test_transient_spill_and_restore_retry(self, model, ref_pressure):
        """A transient firing at each tier point retries through the seeded
        backoff policy and the tier still functions — no drops, no
        fallbacks, same tokens."""
        a, b = _churn_prompts(seed=4)
        plan = [a, b, a]
        ref, _ = _serve_one_by_one(ref_pressure, plan, max_new_tokens=4)
        eng = _pressure_engine(model)
        with injected("kv.spill", FailNth(1), transient=True), \
                injected("kv.restore", FailNth(1), transient=True):
            got, disp = _serve_one_by_one(eng, plan, max_new_tokens=4)
        assert got == ref
        st = eng.kv_tier_stats()
        assert st["host_spill_drops"] == 0, st
        assert st["host_restore_failures"] == 0, st
        assert st["host_spills"] >= 5 and st["host_restores"] >= 5, st
        assert disp[2] == 1, (disp, st)
        assert eng.audit_refcounts() == []

    def test_poison_spill_degrades_to_eviction(self, model, ref_pressure):
        """Every spill poisoned: the tier degrades to plain LRU eviction —
        the re-serve pays full recompute, tokens stay exact, and no page
        accounting leaks."""
        a, b = _churn_prompts(seed=5)
        plan = [a, b, a]
        ref, ref_disp = _serve_one_by_one(ref_pressure, plan,
                                          max_new_tokens=4)
        eng = _pressure_engine(model)
        with injected("kv.spill", Always()):
            got, disp = _serve_one_by_one(eng, plan, max_new_tokens=4)
        assert got == ref
        st = eng.kv_tier_stats()
        assert st["host_spills"] == 0, st
        assert st["host_spill_drops"] > 0, st
        assert st["host_restores"] == 0, st
        assert disp[2] == ref_disp[2], (disp, ref_disp)  # full recompute
        assert eng.audit_refcounts() == []

    def test_poison_restore_falls_back_to_reprefill(self, model,
                                                    ref_pressure):
        """Spills land but every restore is poisoned: admission re-prefills
        the whole prompt (recompute fallback), token-exact, audit clean."""
        a, b = _churn_prompts(seed=6)
        plan = [a, b, a]
        ref, ref_disp = _serve_one_by_one(ref_pressure, plan,
                                          max_new_tokens=4)
        eng = _pressure_engine(model)
        with injected("kv.restore", Always()):
            got, disp = _serve_one_by_one(eng, plan, max_new_tokens=4)
        assert got == ref
        st = eng.kv_tier_stats()
        assert st["host_spills"] >= 5, st
        assert st["host_restores"] == 0, st
        assert st["host_restore_failures"] > 0, st
        assert disp[2] == ref_disp[2], (disp, ref_disp)
        assert eng.audit_refcounts() == []


def _skewed_pair(model):
    """Two replicas behind a skew-overriding affinity router with peer
    pulls on; returns (rs, engines).  The scenario every peer test drives:
    warm r0 with a prompt, block r0 with a long decode, resubmit the
    prompt — the router skew-routes it to cold r1 naming r0 as holder."""
    from paddle_tpu.inference.frontend import ReplicaSet
    from paddle_tpu.inference.frontend.router import PrefixAffinityRouter
    engines = [_engine(model, prefix_cache=True, host_cache_bytes=32 << 20)
               for _ in range(2)]
    rs = ReplicaSet(engines, peer_pull=True,
                    router=PrefixAffinityRouter(page_size=8,
                                                max_load_skew=0))
    return rs, engines


class TestPeerTier:
    def _run_skew_scenario(self, model):
        """Returns (warm_tokens, pulled_tokens, engines) — the second serve
        of the same prompt, skew-routed onto the replica that never saw
        it."""
        rs, engines = _skewed_pair(model)
        rng = np.random.RandomState(7)
        warm = rng.randint(1, 128, (27,)).astype(np.int32)  # 3 full pages
        blocker = rng.randint(1, 128, (4,)).astype(np.int32)
        try:
            h0 = rs.submit(warm, max_new_tokens=4)          # both cold: r0
            warm_toks, _ = rs.result(h0, timeout=60.0)
            hb = rs.submit(blocker, max_new_tokens=56)      # r0 now busy
            h1 = rs.submit(warm, max_new_tokens=4)          # skew -> r1
            pulled_toks, _ = rs.result(h1, timeout=60.0)
            rs.result(hb, timeout=60.0)
        finally:
            rs.close()
        return list(warm_toks), list(pulled_toks), engines

    def test_peer_pull_warms_cold_replica(self, model):
        """The skew-routed replica pulls the holder's 3-page chain before
        prefill: its admission sees 3 prefix hits it never computed, and
        the tokens match the holder's byte-for-byte."""
        warm_toks, pulled_toks, engines = self._run_skew_scenario(model)
        assert pulled_toks == warm_toks
        e0, e1 = engines
        assert e0.kv_tier_stats()["peer_exports"] >= 1, e0.kv_tier_stats()
        st1 = e1.kv_tier_stats()
        assert st1["peer_imports"] >= 1, st1
        assert st1["peer_import_pages"] >= 3, st1
        assert e1.prefix_cache_stats()["hits"] >= 3
        assert e1.audit_refcounts() == []

    def test_peer_pull_poison_recomputes_cold(self, model):
        """Every pull poisoned: the request is submitted cold and
        recomputes — same tokens, zero imports."""
        with injected("kv.peer_pull", Always()):
            warm_toks, pulled_toks, engines = self._run_skew_scenario(model)
        assert pulled_toks == warm_toks
        assert engines[1].kv_tier_stats()["peer_imports"] == 0
        assert engines[1].audit_refcounts() == []

    def test_peer_pull_transient_retries(self, model):
        """A transient first firing retries and the pull still lands."""
        with injected("kv.peer_pull", FailNth(1), transient=True):
            warm_toks, pulled_toks, engines = self._run_skew_scenario(model)
        assert pulled_toks == warm_toks
        assert engines[1].kv_tier_stats()["peer_import_pages"] >= 3


class TestPeerTierRpc:
    def test_pull_push_over_worker_rpc(self, model):
        """The peer tier's wire path: pull_pages / push_pages ops through a
        real thread-hosted WorkerServer and RemoteReplica — numpy page
        blocks survive the pickle framing and the importer's admission
        serves the spliced chain as ordinary prefix hits."""
        from paddle_tpu.distributed.store import TCPStore
        from paddle_tpu.inference.frontend.fleet import RemoteReplica
        from paddle_tpu.inference.frontend.worker import WorkerServer
        master = TCPStore(is_master=True, timeout=20)
        engines = [_engine(model, prefix_cache=True,
                           host_cache_bytes=32 << 20) for _ in range(2)]
        workers, reps = [], []
        try:
            for i, e in enumerate(engines):
                w = WorkerServer(f"w{i}", e,
                                 TCPStore(port=master.port, timeout=20),
                                 group="kvt", ttl=60.0)
                w.start(heartbeat=False)
                workers.append(w)
                reps.append(RemoteReplica(w.name, w.rpc.host, w.rpc.port))
            rng = np.random.RandomState(8)
            prompt = rng.randint(1, 128, (27,)).astype(np.int32)
            rid = reps[0].submit(list(map(int, prompt)), max_new_tokens=4)
            want, deadline = [], time.monotonic() + 60.0
            while time.monotonic() < deadline:
                toks, st = reps[0].poll(rid, timeout=1.0)
                want.extend(toks)
                if st.terminal:
                    break
            keys = prefix_page_keys(prompt, 8)
            payload = reps[0].export_pages(keys)
            assert payload is not None and len(payload["keys"]) == 3
            assert reps[1].import_pages(payload) == 3
            assert engines[1].kv_tier_stats()["peer_import_pages"] == 3
            assert set(keys) <= set(engines[1].prefix_keys())
            # a second pull of the same chain is a no-op (already cached)
            assert reps[1].import_pages(payload) == 0
            # the spliced pages serve a real request as prefix hits,
            # token-exact with the exporter's serve
            rid2 = reps[1].submit(list(map(int, prompt)), max_new_tokens=4)
            got, deadline = [], time.monotonic() + 60.0
            while time.monotonic() < deadline:
                toks, st = reps[1].poll(rid2, timeout=1.0)
                got.extend(toks)
                if st.terminal:
                    break
            assert got == want
            assert engines[1].prefix_cache_stats()["hits"] >= 3
            assert engines[1].audit_refcounts() == []
        finally:
            for r in reps:
                r.close()
            for w in workers:
                w.close(drain=False)
