"""End-to-end request durability: token-exact resume of partially-streamed
requests across replica death (ISSUE 14).

Layers under test, bottom-up:

- engine resume admission: ``add_request(..., resume_tokens=...)`` folds
  the already-emitted history into the prefill context, so the continued
  decode is byte-identical to the uninterrupted run — greedy AND
  fixed-seed sampling, prefix cache on AND off, at several kill offsets
  including one landing exactly on a page boundary (the parity sweep is
  driven at the engine level, where offsets are exact by construction);
- frontend recovery: a replica killed mid-stream hands its request to a
  survivor with the emitted history re-prefilled; the client's spliced
  stream is byte-identical, ``frontend_resumed_total`` ticks, and the
  survivor's page refcounts audit clean.  The single resume attempt is
  the only line of defence: poisoning it (the ``frontend.resume`` fault
  point) is the one way a partially-streamed request ends FAILED;
- supervisor quarantine (satellite S1): crash-looping into quarantine
  proactively evicts the worker's membership lease — watchers observe
  ``leave`` on their next poll, with the fake clock never advancing past
  the TTL;
- gateway keep-alive (satellite S2): an idle stream carries ``: ping``
  SSE comments, and a client that disconnects before the first token is
  detected by the failing ping write and cancelled on the replica.
"""
import http.client
import json
import socket
import struct
import time

import pytest

import paddle_tpu.observability as obs
from paddle_tpu.inference.engine.request import RequestStatus
from paddle_tpu.testing import FAULTS, Always


@pytest.fixture(autouse=True)
def _no_leftover_faults():
    FAULTS.reset()
    yield
    FAULTS.reset()


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
    kw.setdefault("debug_refcount_audit", True)
    return LLMEngine(model, **kw)


def _replica_set(model, n=2, **kw):
    from paddle_tpu.inference.frontend import ReplicaSet
    kw.setdefault("requeue", True)
    return ReplicaSet([_engine(model) for _ in range(n)], **kw)


def _run(model, prompt, max_new, seed=None, cache=True, resume=None):
    """One fresh engine, one request, all tokens out."""
    eng = _engine(model, prefix_cache=cache)
    kw = {"max_new_tokens": max_new}
    if seed is None:
        kw["do_sample"] = False
    else:
        kw["do_sample"] = True
        kw["seed"] = seed
    if resume is not None:
        kw["resume_tokens"] = resume
    rid = eng.add_request(list(prompt), **kw)
    eng.run_until_done()
    toks = list(eng.result(rid))
    assert eng.audit_refcounts() == []
    return toks, eng


PROMPT = list(range(1, 17))                  # 16 tokens = 2 full pages


# ----------------------------------------------- engine resume admission (S4)

class TestEngineResumeParity:
    """The seeded-sampling resume parity sweep: token at position p is a
    pure function of (sampling config, context), so re-prefilling
    ``prompt + emitted`` and decoding the remainder must be byte-identical
    to the uninterrupted run — at every offset, with and without the
    prefix cache, greedy and fixed-seed alike."""

    # offset 8 puts prompt(16) + emitted(8) = 24 exactly on a page
    # boundary (page_size=8): the resumed prefill ends flush with a page
    OFFSETS = (1, 8, 11)
    SEEDS = (None, 7, 1234)                  # None = greedy

    @pytest.mark.parametrize("cache", [True, False],
                             ids=["prefix-cache", "no-cache"])
    def test_resume_parity_sweep(self, model, cache):
        n = 12
        for seed in self.SEEDS:
            ref, _ = _run(model, PROMPT, n, seed=seed, cache=cache)
            assert len(ref) == n
            for k in self.OFFSETS:
                got, eng = _run(model, PROMPT, n - k, seed=seed, cache=cache,
                                resume=ref[:k])
                assert ref[:k] + got == ref, (
                    f"seed={seed} offset={k} cache={cache}: resumed tail "
                    f"diverged")
                assert eng.health()["resume_admissions"] == 1

    def test_resume_budget_accounting_respects_max_len(self, model):
        # prompt + resumed history + budget must fit max_len exactly like
        # an uninterrupted request would
        eng = _engine(model, max_len=32)
        with pytest.raises(ValueError):
            eng.add_request(PROMPT, max_new_tokens=8,
                            resume_tokens=list(range(10)), do_sample=False)

    def test_resumed_request_streams_only_new_tokens(self, model):
        # new_tokens() must never replay the resumed history — the client
        # already holds it; the splice depends on this
        ref, _ = _run(model, PROMPT, 8)
        eng = _engine(model)
        rid = eng.add_request(PROMPT, max_new_tokens=5, resume_tokens=ref[:3],
                              do_sample=False)
        out = []
        while not eng.status(rid).terminal or eng.new_tokens(rid):
            eng.step()
            out.extend(eng.new_tokens(rid))
        assert out == ref[3:]


# ------------------------------------------------- frontend resume recovery

class TestFrontendResumeChaos:
    def _kill_at(self, model, offset, seed=None, max_new=16):
        """Kill the serving replica after ``offset`` client-streamed
        tokens; returns (full client stream, handle, replica set)."""
        kw = ({"do_sample": False} if seed is None
              else {"do_sample": True, "seed": seed})
        ref, _ = _run(model, PROMPT, max_new, seed=seed)
        rs = _replica_set(model)
        try:
            # pace decode so the victim cannot finish its whole budget
            # between client pulls — the kill must land mid-request
            FAULTS.install("serving.slow_step", Always(), delay=0.05)
            h = rs.submit(PROMPT, max_new_tokens=max_new, **kw)
            victim = h.replica.name
            s = rs.stream(h)
            got = [next(s) for _ in range(offset)]
            FAULTS.install("frontend.step", Always(),
                           match=lambda ctx: ctx.get("replica") == victim)
            got += [t for t in s]
            FAULTS.reset()
            return ref, got, h, victim, rs
        except BaseException:
            rs.close()
            raise

    @pytest.mark.parametrize("offset", [1, 2, 5])
    def test_kill_mid_decode_greedy_stream_byte_identical(self, model,
                                                          offset):
        obs.enable()
        try:
            ref, got, h, victim, rs = self._kill_at(model, offset)
            try:
                assert h.resumed and not h.requeued
                assert h.replica.name != victim
                assert got == ref
                assert rs.status(h) in (RequestStatus.FINISHED,
                                        RequestStatus.EOS)
                # survivor holds no leaked pages once the request is done
                assert rs.replica(h.replica.name).engine.audit_refcounts() \
                    == []
                text = obs.render_prometheus()
                assert "frontend_resumed_total 1" in text
                assert 'reason="resume"' in text
                assert "frontend_resume_splice_seconds_count 1" in text
            finally:
                rs.close()
        finally:
            obs.disable()
            obs.reset()

    def test_kill_mid_decode_fixed_seed_stream_byte_identical(self, model):
        ref, got, h, victim, rs = self._kill_at(model, 2, seed=77)
        try:
            assert h.resumed and got == ref
            assert rs.status(h) is not RequestStatus.FAILED
        finally:
            rs.close()

    def test_resume_attempt_failure_is_the_only_failed_path(self, model):
        # the acceptance clause: a partially-streamed request only ends
        # FAILED when its single resume attempt ALSO dies
        rs = _replica_set(model)
        try:
            FAULTS.install("serving.slow_step", Always(), delay=0.05)
            h = rs.submit(PROMPT, max_new_tokens=16, do_sample=False)
            victim = h.replica.name
            s = rs.stream(h)
            got = [next(s), next(s)]
            FAULTS.install("frontend.step", Always(),
                           match=lambda ctx: ctx.get("replica") == victim)
            FAULTS.install("frontend.resume", Always())
            got += list(s)
            assert h.resumed
            assert rs.status(h) is RequestStatus.FAILED
            assert "died mid-request" in (rs.request_error(h) or "")
            # FAILED hands back no tokens (the client's stream already
            # holds the partial prefix; result() must not invent a tail)
            toks, status = rs.result(h)
            assert status is RequestStatus.FAILED and toks == []
        finally:
            FAULTS.reset()
            rs.close()

    def test_fully_buffered_victim_finishes_without_reroute(self, model):
        # death after the whole budget already streamed (an RPC batch can
        # deliver the final tokens and then the replica dies before the
        # terminal status round-trip): the dead replica owed nothing but
        # the status, which recovery pins locally — no second decode
        rs = _replica_set(model)
        try:
            ref, _ = _run(model, PROMPT, 4)
            h = rs.submit(PROMPT, max_new_tokens=4, do_sample=False)
            victim = h.replica.name
            s = rs.stream(h)
            got = [next(s) for _ in range(4)]       # full budget client-side
            status = rs._resume(h)                  # recovery path, directly
            assert status is RequestStatus.FINISHED
            assert h.resumed and h.replica.name == victim   # never re-routed
            assert got == ref
            assert rs.result(h) == (ref, RequestStatus.FINISHED)
        finally:
            rs.close()

    def test_result_after_resume_returns_full_stream(self, model):
        # result() on a resumed handle must splice too, not just stream()
        ref, _ = _run(model, PROMPT, 12)
        rs = _replica_set(model)
        try:
            FAULTS.install("serving.slow_step", Always(), delay=0.05)
            h = rs.submit(PROMPT, max_new_tokens=12, do_sample=False)
            victim = h.replica.name
            s = rs.stream(h)
            next(s), next(s)
            FAULTS.install("frontend.step", Always(),
                           match=lambda ctx: ctx.get("replica") == victim)
            list(s)
            FAULTS.reset()
            toks, status = rs.result(h)
            assert toks == ref and status.terminal
        finally:
            rs.close()


# ------------------------------------- supervisor quarantine eviction (S1)

class _CrashedHandle:
    """A process handle that is already dead."""

    def poll(self):
        return 1

    def terminate(self):
        pass

    def kill(self):
        pass

    def wait(self, timeout=None):
        return 1


class _Clock:
    def __init__(self, t=1000.0):
        self.t = float(t)

    def __call__(self):
        return self.t


class TestQuarantineEvictsLease:
    def test_quarantine_evicts_lease_within_one_poll(self, monkeypatch):
        from paddle_tpu.distributed.membership import MembershipService
        from paddle_tpu.distributed.store import TCPStore
        from paddle_tpu.inference.frontend.supervisor import (QUARANTINED,
                                                              WorkerSupervisor)
        monkeypatch.setenv("PADDLE_TPU_PURE_PY_STORE", "1")
        store = TCPStore(is_master=True, timeout=20)
        clock = _Clock()
        svc = MembershipService(store, group="q", ttl=1000.0, clock=clock)
        watcher = svc.watch()
        svc.register("w0", meta={"port": 1})
        assert [(e.kind, e.member.name)
                for e in watcher.poll()] == [("join", "w0")]

        sup = WorkerSupervisor(lambda: _CrashedHandle(), name="w0",
                               clock=clock, sleep=lambda s: None,
                               max_crashes=1, membership=svc)
        sup.start_worker()
        assert sup.tick() == QUARANTINED
        # ONE watcher poll — the fake clock never moved, so this leave can
        # only come from the supervisor's proactive evict, not TTL expiry
        assert [(e.kind, e.member.name)
                for e in watcher.poll()] == [("leave", "w0")]
        assert "w0" not in svc.members()

    def test_quarantine_without_membership_handle_still_quarantines(self):
        from paddle_tpu.inference.frontend.supervisor import (QUARANTINED,
                                                              WorkerSupervisor)
        sup = WorkerSupervisor(lambda: _CrashedHandle(), name="w1",
                               clock=_Clock(), sleep=lambda s: None,
                               max_crashes=1)
        sup.start_worker()
        assert sup.tick() == QUARANTINED


# -------------------------------------- gateway keep-alive + disconnect (S2)

class TestGatewayKeepAlive:
    def _gateway(self, model, ping_interval):
        from paddle_tpu.inference.frontend import start_gateway
        rs = _replica_set(model, n=1)
        gw = start_gateway(rs, ping_interval=ping_interval)
        return gw, rs

    def test_idle_stream_carries_ping_comments(self, model):
        gw, rs = self._gateway(model, ping_interval=0.15)
        try:
            # stall decode so the stream is silent long enough to need pings
            FAULTS.install("serving.slow_step", Always(), delay=0.4)
            body = json.dumps({"prompt": PROMPT, "max_tokens": 2,
                               "stream": True})
            conn = http.client.HTTPConnection(gw.addr, gw.port, timeout=60.0)
            conn.request("POST", "/v1/completions", body=body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            raw = b""
            while b"[DONE]" not in raw:
                chunk = resp.read(64)
                if not chunk:
                    break
                raw += chunk
                if b": ping" in raw and b"data:" not in raw:
                    FAULTS.reset()           # seen a pre-token ping; speed up
            conn.close()
            assert b": ping\n\n" in raw       # keep-alive comment frames
            assert raw.index(b": ping") < raw.index(b"data:")  # before tok 1
            assert b"[DONE]" in raw           # and the stream still completed
        finally:
            FAULTS.reset()
            gw.close()
            rs.close()

    def test_pre_first_token_disconnect_cancels_on_replica(self, model):
        gw, rs = self._gateway(model, ping_interval=0.1)
        try:
            # decode stalled: no token will be ready before the client bails
            FAULTS.install("serving.slow_step", Always(), delay=0.3)
            body = json.dumps({"prompt": PROMPT, "max_tokens": 48,
                               "stream": True})
            conn = http.client.HTTPConnection(gw.addr, gw.port, timeout=60.0)
            conn.request("POST", "/v1/completions", body=body,
                         headers={"Content-Type": "application/json"})
            sock = conn.sock
            resp = conn.getresponse()        # headers arrive before tokens
            # RST on close so the server's next ping write errors instead
            # of filling the kernel buffer
            sock.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                            struct.pack("ii", 1, 0))
            resp.close()
            sock.close()
            conn.close()                     # gone before the first token
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline:
                statuses = [req.status
                            for r in rs.replicas
                            for req in r.engine.sched.finished.values()]
                if RequestStatus.CANCELLED in statuses:
                    break
                time.sleep(0.1)
            else:
                pytest.fail("pre-first-token disconnect never cancelled "
                            "the request")
        finally:
            FAULTS.reset()
            gw.close()
            rs.close()
