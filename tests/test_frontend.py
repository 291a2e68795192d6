"""Serving front door: prefix-affinity routing, SLO admission, the threaded
ReplicaSet facade, the SSE gateway, replica-death chaos, and the trace-driven
load generator.

The routing/admission tests are pure (stub replicas, no engines, no HTTP).
The end-to-end tests run real tiny-model engines on CPU: concurrent SSE
clients must receive token streams identical to direct single-engine runs,
and a repeated-prefix workload must show affinity routing beating round-robin
on prefix-cache hits (ISSUE 8 acceptance)."""
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from paddle_tpu.inference.frontend.admission import (AdmissionDecision,
                                                     AlwaysAdmit, ShedError,
                                                     SLOAdmission)
from paddle_tpu.inference.frontend.loadgen import (http_completion,
                                                   make_trace, percentile,
                                                   run_closed_loop, summarize)
from paddle_tpu.inference.frontend.router import (PrefixAffinityRouter,
                                                  RoundRobinRouter)
from paddle_tpu.inference.serving import prefix_page_keys
from paddle_tpu.testing import FAULTS, FailNth


@pytest.fixture(autouse=True)
def _no_leftover_faults():
    FAULTS.reset()
    yield
    FAULTS.reset()


# ------------------------------------------------------------- chain hashing

class TestPrefixPageKeys:
    def test_full_pages_only(self):
        toks = list(range(20))
        assert len(prefix_page_keys(toks, 8)) == 2       # 16 of 20 tokens
        assert prefix_page_keys(toks[:7], 8) == []

    def test_chain_dependence(self):
        a = prefix_page_keys([1] * 16, 8)
        b = prefix_page_keys([1] * 8 + [2] * 8, 8)
        assert a[0] == b[0]                 # shared first page
        assert a[1] != b[1]                 # second page differs -> new chain

    def test_matches_engine_hashing_for_numpy_tokens(self):
        toks = np.arange(32, dtype=np.int32)
        assert prefix_page_keys(toks, 8) == prefix_page_keys(list(toks), 8)


# ------------------------------------------------------- router (pure units)

class _StubReplica:
    def __init__(self, name, load=0):
        self.name = name
        self.alive = True
        self._load = load

    def load(self):
        return self._load


class TestPrefixAffinityRouter:
    def _register_prefix(self, router, name, tokens, page=8):
        for k in prefix_page_keys(tokens, page):
            router.note_event(name, "register", k)

    def test_overlap_scoring_prefers_deepest_prefix(self):
        r = PrefixAffinityRouter(page_size=8)
        prompt = list(range(32))            # 4 full pages
        self._register_prefix(r, "a", prompt[:16])   # 2 pages
        self._register_prefix(r, "b", prompt[:24])   # 3 pages
        reps = [_StubReplica("a"), _StubReplica("b")]
        d = r.route(prompt, reps)
        assert d.replica.name == "b" and d.reason == "affinity"
        assert d.overlap == 3

    def test_overlap_is_contiguous_from_page_zero(self):
        # holding page 2's key without pages 0-1 is worthless (the engine
        # can only reuse a cached prefix from the start)
        r = PrefixAffinityRouter(page_size=8)
        prompt = list(range(32))
        keys = prefix_page_keys(prompt, 8)
        r.note_event("a", "register", keys[2])       # orphan tail page
        self._register_prefix(r, "b", prompt[:8])    # genuine 1-page prefix
        d = r.route(prompt, [_StubReplica("a"), _StubReplica("b")])
        assert d.replica.name == "b" and d.overlap == 1

    def test_evict_event_removes_key(self):
        r = PrefixAffinityRouter(page_size=8)
        prompt = list(range(16))
        self._register_prefix(r, "a", prompt)
        keys = prefix_page_keys(prompt, 8)
        r.note_event("a", "evict", keys[1])
        reps = [_StubReplica("a"), _StubReplica("b")]
        assert r.route(prompt, reps).overlap == 1    # page 0 still cached
        r.note_event("a", "evict", keys[0])
        d = r.route(prompt, reps)
        assert d.reason == "least_loaded"            # index fully drained

    def test_least_loaded_fallback_without_overlap(self):
        r = PrefixAffinityRouter(page_size=8)
        reps = [_StubReplica("a", load=3), _StubReplica("b", load=1)]
        d = r.route(list(range(16)), reps)
        assert d.replica.name == "b" and d.reason == "least_loaded"

    def test_load_breaks_overlap_ties(self):
        r = PrefixAffinityRouter(page_size=8)
        prompt = list(range(16))
        self._register_prefix(r, "a", prompt)
        self._register_prefix(r, "b", prompt)
        reps = [_StubReplica("a", load=2), _StubReplica("b", load=0)]
        d = r.route(prompt, reps)
        assert d.replica.name == "b" and d.reason == "affinity"

    def test_deterministic_name_tiebreak(self):
        r = PrefixAffinityRouter(page_size=8)
        reps = [_StubReplica(n) for n in ("c", "a", "b")]
        for _ in range(3):                  # same state -> same answer
            assert r.route(list(range(16)), reps).replica.name == "a"
        # list order must not matter
        assert r.route(list(range(16)), reps[::-1]).replica.name == "a"

    def test_forget_drops_whole_replica_index(self):
        r = PrefixAffinityRouter(page_size=8)
        prompt = list(range(16))
        self._register_prefix(r, "a", prompt)
        r.forget("a")
        assert r.known_keys("a") == frozenset()
        d = r.route(prompt, [_StubReplica("a"), _StubReplica("b")])
        assert d.reason == "least_loaded"

    def test_route_requires_replicas(self):
        with pytest.raises(ValueError):
            PrefixAffinityRouter(8).route([1, 2], [])

    def test_node_index_shared_across_replicas(self):
        # the radix node index maps each chain key to its holder set: one
        # walk scores every replica, and a node with no holders left is
        # dropped from the index entirely
        r = PrefixAffinityRouter(page_size=8)
        prompt = list(range(24))
        self._register_prefix(r, "a", prompt)
        self._register_prefix(r, "b", prompt[:16])
        keys = prefix_page_keys(prompt, 8)
        assert r._nodes[keys[0]] == {"a", "b"}
        assert r._nodes[keys[2]] == {"a"}
        overlaps = r._overlaps(keys, ["a", "b", "c"])
        assert overlaps == {"a": 3, "b": 2, "c": 0}
        r.note_event("b", "evict", keys[1])
        r.forget("a")
        assert keys[1] not in r._nodes and keys[2] not in r._nodes
        assert r.known_keys("b") == {keys[0]}


class TestRoundRobinRouter:
    def test_cycles_in_order(self):
        r = RoundRobinRouter()
        reps = [_StubReplica("a"), _StubReplica("b")]
        names = [r.route([1], reps).replica.name for _ in range(4)]
        assert names == ["a", "b", "a", "b"]
        assert all(d == "round_robin" for d in
                   (r.route([1], reps).reason,))


# ----------------------------------------------------- admission (pure units)

class _StubHealthReplica:
    def __init__(self, name, waiting=0, free=8, reclaimable=0, total=8):
        self.name = name
        self.alive = True
        self._h = {"waiting": waiting, "free_pages": free,
                   "reclaimable_pages": reclaimable, "total_pages": total}

    def health(self):
        return dict(self._h)


class TestSLOAdmission:
    def test_always_admit_default(self):
        assert AlwaysAdmit().decide([_StubHealthReplica("a")]).admit

    def test_queue_full_requires_every_replica_full(self):
        pol = SLOAdmission(max_queue_per_replica=2)
        full = _StubHealthReplica("a", waiting=2)
        free = _StubHealthReplica("b", waiting=1)
        assert pol.decide([full, free]).admit            # one still has room
        d = pol.decide([full, _StubHealthReplica("c", waiting=5)])
        assert not d.admit and d.reason == "queue_full"
        assert d.retry_after > 0

    def test_page_pressure_needs_backlog(self):
        pol = SLOAdmission(max_queue_per_replica=None, min_free_page_ratio=0.5)
        starved_idle = _StubHealthReplica("a", waiting=0, free=1, total=8)
        assert pol.decide([starved_idle]).admit          # idle always admits
        starved_busy = _StubHealthReplica("a", waiting=3, free=1, total=8)
        d = pol.decide([starved_busy])
        assert not d.admit and d.reason == "page_pressure"

    def test_ttft_slo_uses_observed_window(self):
        pol = SLOAdmission(max_queue_per_replica=None, ttft_slo=0.5)
        rep = _StubHealthReplica("a")
        assert pol.decide([rep]).admit                   # no data -> admit
        for _ in range(4):
            pol.observe_ttft(2.0)
        d = pol.decide([rep])
        assert not d.admit and d.reason == "ttft_slo"
        for _ in range(64):
            pol.observe_ttft(0.01)                       # window recovers
        assert pol.decide([rep]).admit

    def test_tpot_slo_uses_observed_window(self):
        pol = SLOAdmission(max_queue_per_replica=None, tpot_slo=0.05)
        rep = _StubHealthReplica("a")
        assert pol.decide([rep]).admit                   # no data -> admit
        pol.observe_tpot(None)                           # ignored
        for _ in range(4):
            pol.observe_tpot(0.2)                        # decode saturated
        d = pol.decide([rep])
        assert not d.admit and d.reason == "tpot_slo"
        for _ in range(64):
            pol.observe_tpot(0.001)                      # window recovers
        assert pol.decide([rep]).admit

    def test_ttft_slo_checked_before_tpot_slo(self):
        pol = SLOAdmission(max_queue_per_replica=None, ttft_slo=0.5,
                           tpot_slo=0.05)
        pol.observe_ttft(2.0)
        pol.observe_tpot(0.2)
        d = pol.decide([_StubHealthReplica("a")])
        assert not d.admit and d.reason == "ttft_slo"

    def test_decision_repr_and_shed_error(self):
        d = AdmissionDecision(False, "queue_full", 2.0)
        assert "queue_full" in repr(d)
        e = ShedError("queue_full", 2.0)
        assert e.reason == "queue_full" and e.retry_after == 2.0


# ------------------------------------------------------------ loadgen (pure)

class TestLoadgen:
    def test_trace_is_deterministic(self):
        a = make_trace(7, 12, groups=3)
        b = make_trace(7, 12, groups=3)
        assert a == b
        assert a != make_trace(8, 12, groups=3)

    def test_group_major_blocks_adjacent(self):
        t = make_trace(0, 8, groups=4, group_major=True)
        assert [r["group"] for r in t] == [0, 0, 1, 1, 2, 2, 3, 3]
        t = make_trace(0, 8, groups=4, group_major=False)
        assert [r["group"] for r in t] == [0, 1, 2, 3, 0, 1, 2, 3]

    def test_shared_prefix_unique_suffix(self):
        t = make_trace(1, 6, groups=2, prefix_pages=2, page_size=8,
                       suffix_tokens=4, group_major=True)
        g0 = [r["prompt"] for r in t if r["group"] == 0]
        assert all(p[:16] == g0[0][:16] for p in g0)     # shared prefix
        assert len({tuple(p) for p in g0}) == len(g0)    # distinct suffixes

    def test_percentile_nearest_rank(self):
        vals = list(range(1, 101))
        assert percentile(vals, 50) in (50, 51)
        assert percentile(vals, 95) in (95, 96)
        assert percentile([3.0], 95) == 3.0
        with pytest.raises(ValueError):
            percentile([], 50)


# ----------------------------------------------------- end-to-end (tiny CPU)

def _tiny_model():
    import paddle_tpu as pt
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    pt.seed(0)
    cfg = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=176,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, max_position_embeddings=128)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


@pytest.fixture(scope="module")
def model():
    return _tiny_model()


def _engine(model, **kw):
    from paddle_tpu.inference.serving import LLMEngine
    kw.setdefault("max_batch", 3)
    kw.setdefault("max_len", 64)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefix_cache", True)
    return LLMEngine(model, **kw)


def _replica_set(model, n=2, **kw):
    from paddle_tpu.inference.frontend import ReplicaSet
    return ReplicaSet([_engine(model) for _ in range(n)], **kw)


def _prompts(n, seed=0, lo=4, step=3):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, 128, (lo + step * i,)).astype(np.int32)
            for i in range(n)]


class TestReplicaSet:
    def test_submit_result_parity_with_direct_engine(self, model):
        prompts = _prompts(3)
        ref = _engine(model)
        rids = [ref.add_request(p, max_new_tokens=6) for p in prompts]
        ref.run_until_done()
        want = [list(ref.result(r)) for r in rids]

        rs = _replica_set(model)
        try:
            handles = [rs.submit(p, max_new_tokens=6) for p in prompts]
            got = [rs.result(h) for h in handles]
        finally:
            rs.close()
        from paddle_tpu.inference.serving import RequestStatus
        assert [list(t) for t, _ in got] == want
        assert all(s is RequestStatus.FINISHED or s is RequestStatus.EOS
                   for _, s in got)

    def test_stream_tokens_incrementally(self, model):
        prompts = _prompts(1, seed=5)
        ref = _engine(model)
        rid = ref.add_request(prompts[0], max_new_tokens=6)
        ref.run_until_done()
        rs = _replica_set(model)
        try:
            h = rs.submit(prompts[0], max_new_tokens=6)
            assert list(rs.stream(h)) == list(ref.result(rid))
        finally:
            rs.close()

    def test_cancel_mid_serve(self, model):
        from paddle_tpu.inference.serving import RequestStatus
        rs = _replica_set(model, n=1)
        try:
            h = rs.submit(_prompts(1)[0], max_new_tokens=40)
            # let it start, then cancel mid-decode
            h.replica.poll(h.rid, timeout=5.0)
            assert rs.cancel(h)
            _, status = rs.result(h, timeout=20.0)
            assert status is RequestStatus.CANCELLED
        finally:
            rs.close()

    def test_engine_level_shed_surfaces_as_shed_error(self, model):
        rs = _replica_set(model, n=1)
        try:
            rs.replicas[0].engine.sched.max_waiting = 0    # engine refuses all
            with pytest.raises(ShedError) as ei:
                rs.submit(_prompts(1)[0], max_new_tokens=4)
            assert ei.value.reason == "engine"
        finally:
            rs.close()

    def test_admission_shed_never_reaches_replicas(self, model):
        class _RefuseAll:
            def decide(self, replicas):
                return AdmissionDecision(False, "queue_full", 3.0)

            def observe_ttft(self, s):
                pass

        rs = _replica_set(model, n=1, admission=_RefuseAll())
        try:
            with pytest.raises(ShedError):
                rs.submit(_prompts(1)[0], max_new_tokens=4)
            assert rs.replicas[0].engine.health()["finished"] == 0
        finally:
            rs.close()

    def test_per_replica_health_and_metrics_labels(self, model):
        rs = _replica_set(model)
        try:
            h = rs.submit(_prompts(1)[0], max_new_tokens=4)
            rs.result(h)
            health = rs.health()
            assert set(health) == {"r0", "r1"}
            assert all(hh["replica"] == name and hh["alive"]
                       for name, hh in health.items())
            metrics = rs.metrics()
            assert set(metrics) == {"r0", "r1"}
        finally:
            rs.close()


class TestStuckStepWatchdog:
    def test_stuck_step_trips_typed_death_and_fails_over(self, model):
        """A step that wedges past ``step_wall_timeout`` is a gray failure:
        the watchdog promotes it to a typed replica death while the step
        still holds the engine condition, pollers fail over immediately,
        and the zero-streamed request requeues onto the survivor with
        byte-identical output."""
        import paddle_tpu.observability as obs
        from paddle_tpu.inference.frontend import (ReplicaSet,
                                                   StuckStepError)
        from paddle_tpu.inference.serving import RequestStatus

        prompt = _prompts(1, seed=3)[0]
        ref = _engine(model)
        rid = ref.add_request(prompt, max_new_tokens=6)
        ref.run_until_done()
        want = list(ref.result(rid))

        engines = [_engine(model) for _ in range(2)]
        for eng in engines:
            # pay each engine's JIT compilation for the exact prompt and
            # decode shapes this test submits, so the watchdog times
            # genuine step wall time, not compilation
            eng.add_request(list(prompt), max_new_tokens=6)
            eng.run_until_done()
        real_step = engines[0].step
        stalled = threading.Event()

        def wedged_step():
            if not stalled.is_set():
                stalled.set()            # wedge the FIRST step only —
                time.sleep(2.0)          #   far past step_wall_timeout
            return real_step()

        engines[0].step = wedged_step
        obs.enable()
        try:
            rs = ReplicaSet(engines, router=RoundRobinRouter(),
                            requeue=True, step_wall_timeout=0.5)
            try:
                h = rs.submit(prompt, max_new_tokens=6)  # round 1 → r0
                toks, status = rs.result(h, timeout=60.0)
                assert status in (RequestStatus.FINISHED, RequestStatus.EOS)
                assert list(toks) == want
                r0 = rs.replicas[0]
                assert not r0.alive
                assert isinstance(r0.error, StuckStepError)
                health = rs.health()
                assert health["r0"]["alive"] is False
                assert health["r1"]["alive"] is True
                text = obs.render_prometheus()
                assert 'frontend_stuck_steps_total{replica="r0"} 1' in text
                assert "frontend_requeued_total 1" in text
            finally:
                rs.close()
        finally:
            obs.disable()
            obs.reset()


class TestAffinityVsRoundRobin:
    def _run(self, model, router, trace):
        rs = _replica_set(model, n=2, router=router)
        try:
            records, wall = run_closed_loop(rs, trace, concurrency=1)
            hits = sum(r.engine.prefix_cache_stats()["hits"]
                       for r in rs.replicas)
            lookups = hits + sum(r.engine.prefix_cache_stats()["misses"]
                                 for r in rs.replicas)
        finally:
            rs.close()
        assert all(r["status"] in ("finished", "eos") for r in records)
        return records, hits, max(1, lookups)

    def test_affinity_beats_round_robin_on_prefix_hits(self, model):
        """ISSUE 8 acceptance: a repeated-prefix workload served
        group-major, closed-loop, over 2 replicas.  Round-robin alternates
        replicas, so a group's repeat lands on the replica WITHOUT its
        prefix (zero hits); affinity routes it back to the cached replica
        (>=1 page hit per repeat) — at least 2x the round-robin hit rate."""
        import paddle_tpu.observability as obs
        trace = make_trace(3, 8, groups=4, prefix_pages=2, page_size=8,
                           suffix_tokens=3, max_new_tokens=4,
                           group_major=True)
        _, rr_hits, rr_lookups = self._run(model, RoundRobinRouter(), trace)

        obs.enable()
        try:
            obs.reset()
            aff_records, aff_hits, aff_lookups = self._run(
                model, PrefixAffinityRouter(page_size=8), trace)
            snap = obs.snapshot(prefix="frontend_affinity")
            events = {s["labels"]["event"]: s["value"] for s in
                      snap["frontend_affinity_events_total"]["series"]}
        finally:
            obs.disable()

        assert aff_hits > 0, "affinity routing produced no prefix-cache hits"
        aff_rate = aff_hits / aff_lookups
        rr_rate = rr_hits / rr_lookups
        assert rr_hits == 0 or aff_rate >= 2 * rr_rate, (
            f"affinity {aff_rate:.3f} not >= 2x round-robin {rr_rate:.3f}")
        # the router's own view agrees: one miss per group's first request,
        # hits for the repeats
        assert events.get("hit", 0) >= 4
        # and every repeat went to the replica that served its group before
        by_group = {}
        for r in aff_records:
            by_group.setdefault(r["group"], set()).add(r["replica"])
        assert all(len(v) == 1 for v in by_group.values())


class TestGatewayHTTP:
    @pytest.fixture()
    def served(self, model):
        from paddle_tpu.inference.frontend import start_gateway
        rs = _replica_set(model)
        gw = start_gateway(rs)
        yield gw, rs
        gw.close()
        rs.close()

    def test_concurrent_sse_streams_byte_identical(self, model, served):
        """ISSUE 8 acceptance: >=3 concurrent streaming clients against a
        2-replica set each receive exactly the token stream a direct
        single-engine run produces."""
        gw, _ = served
        prompts = _prompts(3, seed=9)
        ref = _engine(model)
        rids = [ref.add_request(p, max_new_tokens=6) for p in prompts]
        ref.run_until_done()
        want = [[int(t) for t in ref.result(r)] for r in rids]

        results = [None] * len(prompts)
        errors = []

        def client(i):
            try:
                results[i] = http_completion(gw.url, prompts[i],
                                             max_tokens=6, stream=True,
                                             timeout=120.0)
            except Exception as e:  # surfaced via the errors list
                errors.append((i, repr(e)))

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180.0)
        assert not errors, errors
        for i, want_toks in enumerate(want):
            assert results[i]["tokens"] == want_toks, i
            assert results[i]["status"] in ("finished", "eos")
            # one event per token + final status + [DONE]
            assert results[i]["events"] == len(want_toks) + 2

    def test_non_stream_completion(self, served):
        gw, _ = served
        out = http_completion(gw.url, _prompts(1, seed=11)[0], max_tokens=5)
        assert len(out["tokens"]) == 5
        assert out["status"] in ("finished", "eos")
        assert out["replica"] in ("r0", "r1")

    def test_shed_maps_to_429_with_retry_after(self, model):
        from paddle_tpu.inference.frontend import start_gateway

        class _RefuseAll:
            def decide(self, replicas):
                return AdmissionDecision(False, "queue_full", 7.0)

            def observe_ttft(self, s):
                pass

        rs = _replica_set(model, n=1, admission=_RefuseAll())
        gw = start_gateway(rs)
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                http_completion(gw.url, [1, 2, 3], max_tokens=4)
            assert ei.value.code == 429
            assert ei.value.headers["Retry-After"] == "7"
            body = json.loads(ei.value.read().decode())
            assert body["reason"] == "queue_full"
        finally:
            gw.close()
            rs.close()

    def test_unserved_deadline_maps_to_408(self, served):
        gw, _ = served
        with pytest.raises(urllib.error.HTTPError) as ei:
            http_completion(gw.url, _prompts(1)[0], max_tokens=4,
                            deadline=1e-6)
        assert ei.value.code == 408
        # Retry-After parity with 429/503: an unserved deadline is a load
        # symptom, the client should back off before re-asking
        assert ei.value.headers["Retry-After"] == "1"

    def test_bad_request_maps_to_400(self, served):
        gw, _ = served
        req = urllib.request.Request(
            gw.url + "/v1/completions",
            data=json.dumps({"prompt": "not-token-ids"}).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=30.0)
        assert ei.value.code == 400

    def test_unknown_route_404(self, served):
        gw, _ = served
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(gw.url + "/v2/nope", timeout=30.0)
        assert ei.value.code == 404

    def test_healthz_and_metrics_endpoints(self, served):
        gw, _ = served
        with urllib.request.urlopen(gw.url + "/healthz", timeout=30.0) as r:
            health = json.loads(r.read().decode())
        assert set(health) == {"r0", "r1", "fleet"}
        assert health["fleet"]["alive"] == 2
        assert all(h["alive"] for name, h in health.items()
                   if name != "fleet")
        with urllib.request.urlopen(gw.url + "/metrics", timeout=30.0) as r:
            text = r.read().decode()
        assert "frontend_requests_total" in text
        assert "# TYPE frontend_stream_seconds histogram" in text

    def test_client_disconnect_cancels_request(self, model, served):
        import http.client
        import socket
        import struct
        from paddle_tpu.inference.serving import RequestStatus
        gw, rs = served
        # throttle decode (100ms/step via the slow-step fault point) so the
        # stream outlives the disconnect — at full speed the tiny model
        # generates and buffers all 56 tokens before the RST propagates
        from paddle_tpu.testing.faults import Always
        FAULTS.install("serving.slow_step", Always(), delay=0.1)
        body = json.dumps({"prompt": [int(t) for t in _prompts(1)[0]],
                           "max_tokens": 56, "stream": True})
        conn = http.client.HTTPConnection(gw.addr, gw.port, timeout=60.0)
        conn.request("POST", "/v1/completions", body=body,
                     headers={"Content-Type": "application/json"})
        sock = conn.sock                    # getresponse() may detach it
        resp = conn.getresponse()
        resp.read(16)                       # first bytes of the stream
        # RST on close (not a graceful FIN): the kernel would otherwise
        # buffer the server's remaining writes without erroring, and a
        # short stream could complete before the disconnect surfaces
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                        struct.pack("ii", 1, 0))
        resp.close()                        # drop makefile()'s fd reference
        sock.close()                        # ...so this really closes + RSTs
        conn.close()                        # walk away mid-stream
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            done = [r.engine.sched.finished for r in rs.replicas]
            statuses = [req.status for fin in done for req in fin.values()]
            if RequestStatus.CANCELLED in statuses:
                break
            time.sleep(0.2)
        else:
            pytest.fail("client disconnect never cancelled the request")


class TestReplicaDeathChaos:
    def test_replica_kill_mid_stream(self, model):
        """ISSUE 8 chaos acceptance: kill one replica mid-stream.  Its
        inflight requests end FAILED (typed, not hung), the router stops
        selecting it, and survivors on the other replica stay token-exact
        with a fault-free run."""
        from paddle_tpu.inference.serving import RequestStatus
        prompts = _prompts(2, seed=21)
        ref = _engine(model)
        ref_rids = [ref.add_request(p, max_new_tokens=8) for p in prompts]
        ref.run_until_done()
        want = [list(ref.result(r)) for r in ref_rids]

        rs = _replica_set(model, n=2)
        try:
            # deterministic placement: empty set routes least-loaded with
            # name tie-break -> first request r0, second (r0 now loaded) r1
            h0 = rs.submit(prompts[0], max_new_tokens=8)
            h1 = rs.submit(prompts[1], max_new_tokens=8)
            assert {h0.replica.name, h1.replica.name} == {"r0", "r1"}
            victim, survivor = h0, h1
            # kill the victim's replica a few steps in (mid-stream)
            FAULTS.install(
                "frontend.step", FailNth(3),
                match=lambda ctx: ctx.get("replica") == victim.replica.name)
            _, vstat = rs.result(victim, timeout=120.0)
            assert vstat is RequestStatus.FAILED
            assert "injected fault" in (rs.request_error(victim) or "")
            assert not victim.replica.alive
            # the dead replica's prefix index is gone from the router
            assert rs.router.known_keys(victim.replica.name) == frozenset()
            # survivor is token-exact with the fault-free run
            toks, sstat = rs.result(survivor, timeout=120.0)
            assert sstat in (RequestStatus.FINISHED, RequestStatus.EOS)
            assert list(toks) == want[1]
            # router only selects live replicas from now on
            for _ in range(3):
                h = rs.submit(prompts[0], max_new_tokens=2)
                assert h.replica.name == survivor.replica.name
                rs.result(h, timeout=120.0)
            # dead-replica health is visible to /healthz consumers
            health = rs.health()
            assert health[victim.replica.name]["alive"] is False
            assert health[victim.replica.name]["error"]
        finally:
            rs.close()

    def test_no_live_replicas_raises(self, model):
        from paddle_tpu.inference.frontend.replica import ReplicaDeadError
        rs = _replica_set(model, n=1)
        try:
            FAULTS.install("frontend.step", FailNth(1))
            h = rs.submit(_prompts(1)[0], max_new_tokens=4)
            _, status = rs.result(h, timeout=120.0)
            assert status.value == "failed"
            with pytest.raises(ReplicaDeadError):
                rs.submit(_prompts(1)[0], max_new_tokens=4)
        finally:
            rs.close()

    def test_submit_fault_point_fires(self, model):
        from paddle_tpu.testing import InjectedFault
        rs = _replica_set(model, n=1)
        try:
            FAULTS.install("frontend.route", FailNth(1))
            with pytest.raises(InjectedFault):
                rs.submit(_prompts(1)[0], max_new_tokens=4)
            FAULTS.reset()
            FAULTS.install("frontend.submit", FailNth(1),
                           match=lambda ctx: ctx.get("replica") == "r0")
            with pytest.raises(InjectedFault):
                rs.submit(_prompts(1)[0], max_new_tokens=4)
            FAULTS.reset()
            h = rs.submit(_prompts(1)[0], max_new_tokens=4)  # healthy again
            _, status = rs.result(h, timeout=120.0)
            assert status.value in ("finished", "eos")
        finally:
            rs.close()


class TestLoadgenEndToEnd:
    def test_closed_loop_summary(self, model):
        trace = make_trace(5, 6, groups=2, prefix_pages=1, page_size=8,
                           suffix_tokens=2, max_new_tokens=3)
        rs = _replica_set(model, n=2)
        try:
            records, wall = run_closed_loop(rs, trace, concurrency=3)
        finally:
            rs.close()
        assert all(r is not None for r in records)
        s = summarize(records, wall)
        assert s["requests"] == 6 and s["shed"] == 0 and s["failed"] == 0
        assert s["total_tokens"] == 18
        assert s["tokens_per_s"] > 0
        assert s["ttft_p50_s"] is not None and s["ttft_p95_s"] is not None
        assert s["ttft_p95_s"] >= s["ttft_p50_s"]
