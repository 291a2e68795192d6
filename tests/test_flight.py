"""Fleet-wide request tracing and metrics federation (the observability
plane's cross-process half).

Bottom-up: trace contexts + Lamport clock (pure units), the bounded flight
recorder (ring eviction, anomaly pinning, atomic chrome-trace dumps), the
merge/export path (schema-checked chrome JSON), context propagation across
the RPC frame, and the gateway surfaces — ``/v1/requests/{rid}/trace``,
the federated ``/metrics`` page, and the ``/healthz`` fleet rollup — first
against in-process replicas, then against a thread-hosted WorkerServer
fleet where one member is SIGKILL-shaped mid-scrape (RPC listener gone,
lease intact) and the scrape must skip it, not wedge."""
import json
import os
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.observability import flight
from paddle_tpu.observability.registry import REGISTRY


@pytest.fixture()
def recorder():
    """Flight recorder on, empty, default-sized; restored afterwards."""
    flight.enable()
    flight.reset()
    flight.configure(ring_size=4096)
    yield
    flight.disable()
    flight.reset()
    flight.configure(ring_size=4096)


def _assert_valid_chrome_trace(doc):
    """Minimal chrome://tracing schema check: every event names a phase the
    viewer understands, samples reference a pid announced by a preceding
    ``process_name`` metadata event, and complete events carry durations.
    Returns {pid: process label}."""
    assert isinstance(doc, dict) and isinstance(doc["traceEvents"], list)
    pids = {}
    for ev in doc["traceEvents"]:
        assert isinstance(ev["name"], str) and ev["name"]
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        if ev["ph"] == "M":
            assert ev["name"] == "process_name"
            pids[ev["pid"]] = ev["args"]["name"]
            continue
        assert ev["ph"] in ("X", "i"), ev
        assert ev["pid"] in pids, "sample before its process_name metadata"
        assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0
        if ev["ph"] == "X":
            assert ev["dur"] >= 0
        else:
            assert ev["s"] == "t"
    return pids


# ---------------------------------------------------- contexts + Lamport clock

class TestTraceContext:
    def test_mint_adopts_supplied_id(self):
        assert flight.mint("req-abc").trace_id == "req-abc"
        a, b = flight.mint(), flight.mint()
        assert a.trace_id != b.trace_id
        assert b.clock > a.clock

    def test_use_context_scopes_ambient(self):
        ctx = flight.mint("scoped")
        assert flight.current() is None
        with flight.use_context(ctx):
            assert flight.current() is ctx
            with flight.use_context(None):      # None is a passthrough
                assert flight.current() is ctx
        assert flight.current() is None

    def test_wire_round_trip_is_causally_monotone(self, recorder):
        ctx = flight.mint("wire-rt")
        with flight.use_context(ctx):
            wire = flight.wire_context()
        assert wire[0] == "wire-rt"
        adopted = flight.adopt_wire(wire)
        assert adopted.trace_id == "wire-rt"
        assert adopted.clock > wire[1]          # receive happens-after send
        assert flight.adopt_wire(None) is None

    def test_disabled_wire_is_none(self):
        flight.disable()
        with flight.use_context(flight.mint()):
            assert flight.wire_context() is None

    def test_context_pickles(self, recorder):
        import pickle
        ctx = flight.mint("pkl")
        clone = pickle.loads(pickle.dumps(ctx))
        assert (clone.trace_id, clone.clock) == (ctx.trace_id, ctx.clock)

    def test_hostile_client_id_is_sanitized(self):
        # the gateway adopts X-Request-ID verbatim as the trace id, and
        # trace ids become dump FILENAMES: path syntax must never survive
        evil = "../../etc/cron.d/evil"
        tid = flight.mint(evil).trace_id
        assert "/" not in tid and "\\" not in tid and ".." not in tid
        # hashing is stable, so retries of the same hostile id correlate
        assert flight.mint(evil).trace_id == tid
        # distinct hostile ids stay distinct
        assert flight.mint("../../other").trace_id != tid
        # conforming ids pass through untouched; overlong ones are hashed
        assert flight.mint("req_A.1-b").trace_id == "req_A.1-b"
        assert flight.mint("x" * 200).trace_id != "x" * 200


# ----------------------------------------------------------- flight recorder

class TestFlightRecorder:
    def test_disabled_record_is_noop(self):
        flight.disable()
        flight.record("phase", trace_id="off")
        flight.enable()
        try:
            assert flight.events_for("off") == []
        finally:
            flight.disable()

    def test_untraced_record_is_noop(self, recorder):
        flight.record("phase")                  # no trace_id, no ambient ctx
        assert flight.snapshot_events() == []

    def test_ring_eviction_bounds_memory(self, recorder):
        flight.configure(ring_size=16)
        for i in range(200):
            flight.record("p", rid=i, trace_id=f"t{i}")
        events = flight.snapshot_events()
        assert len(events) == 16
        # the survivors are the NEWEST 16, in causal order
        assert [e["trace_id"] for e in events] == [
            f"t{i}" for i in range(184, 200)]
        assert flight.events_for("t0") == []    # evicted

    def test_pin_survives_eviction_and_registers_reason(self, recorder):
        flight.configure(ring_size=8)
        with flight.use_context(flight.mint("victim")):
            flight.record("queued", rid=42)
            flight.record("prefill", rid=42, dur=0.01)
        assert flight.pin_rid(42, "stuck_step")
        for i in range(100):                    # churn the whole ring
            flight.record("noise", trace_id=f"n{i}")
        phases = [e["phase"] for e in flight.events_for("victim")]
        assert phases == ["queued", "prefill", "pinned"]
        assert flight.pinned() == {"victim": "stuck_step"}
        # pinned events also ride along in the full-ring snapshot (RPC pull)
        assert any(e["trace_id"] == "victim"
                   for e in flight.snapshot_events())

    def test_pin_unknown_rid_is_false(self, recorder):
        assert not flight.pin_rid(999999, "whatever")
        assert flight.pinned() == {}

    def test_pinned_store_is_bounded(self, recorder):
        # replica churn pins every resumed request: the anomaly store must
        # evict like the ring does, not grow for the life of the process
        last = flight._PINNED_MAX + 9
        for i in range(last + 1):
            flight.record("queued", trace_id=f"anom{i}", rid=i)
            assert flight.pin(f"anom{i}", "stuck_step")
        pins = flight.pinned()
        assert len(pins) == flight._PINNED_MAX
        assert "anom0" not in pins             # oldest pins fell out
        assert f"anom{last}" in pins
        # re-pinning a resident trace updates in place — no eviction
        assert flight.pin(f"anom{last}", "again")
        assert len(flight.pinned()) == flight._PINNED_MAX
        assert flight.pinned()[f"anom{last}"] == "again"

    def test_hostile_pin_cannot_escape_dump_dir(self, recorder, tmp_path,
                                                monkeypatch):
        dumps = tmp_path / "dumps"
        monkeypatch.setenv("PADDLE_TPU_TRACE_DUMP_DIR", str(dumps))
        ctx = flight.mint("../../escape")      # hostile X-Request-ID shape
        with flight.use_context(ctx):
            flight.record("queued", rid=1)
        assert flight.pin(ctx.trace_id, "quarantine")
        # the dump landed INSIDE the configured dir, nowhere else
        assert sorted(p.name for p in dumps.iterdir()) == [
            f"trace-{ctx.trace_id}.json"]
        assert not (tmp_path / "escape").exists()
        # defense in depth: the write site refuses a raw unsanitized id
        with pytest.raises(OSError):
            flight.dump_trace("../../escape", [], out_dir=str(dumps))
        with pytest.raises(OSError):
            flight.dump_trace("a/b", [], out_dir=str(dumps))

    def test_pin_dumps_valid_chrome_trace(self, recorder, tmp_path,
                                          monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_TRACE_DUMP_DIR", str(tmp_path))
        with flight.use_context(flight.mint("anomaly1")):
            flight.record("queued", rid=7)
            flight.record("decode", rid=7, dur=0.002, block=3)
        assert flight.pin("anomaly1", "quarantine")
        path = tmp_path / "trace-anomaly1.json"
        assert path.exists()
        assert not list(tmp_path.glob("*.tmp.*")), "torn dump left behind"
        doc = json.loads(path.read_text())
        _assert_valid_chrome_trace(doc)
        assert doc["metadata"] == {"trace_id": "anomaly1",
                                   "pin_reason": "quarantine"}
        names = [e["name"] for e in doc["traceEvents"] if e["ph"] != "M"]
        assert names == ["queued", "decode", "pinned"]

    def test_chaos_artifact_dump_hook(self, recorder, tmp_path,
                                      monkeypatch):
        """The conftest post-mortem hook: a failed chaos test leaves a
        metrics snapshot and every pinned trace in the artifacts dir."""
        from tests.conftest import _dump_chaos_artifacts
        monkeypatch.setenv("PADDLE_TPU_CHAOS_ARTIFACTS", str(tmp_path))
        with flight.use_context(flight.mint("chaosart")):
            flight.record("queued", rid=1)
        flight.pin("chaosart", "stuck_step")
        _dump_chaos_artifacts("tests/test_x.py::TestY::test_z[leg-11]")
        files = sorted(p.name for p in tmp_path.iterdir())
        assert "trace-chaosart.json" in files
        metrics = [f for f in files if f.startswith("metrics-")]
        assert len(metrics) == 1 and metrics[0].endswith(".json")
        json.loads((tmp_path / metrics[0]).read_text())  # valid JSON
        doc = json.loads((tmp_path / "trace-chaosart.json").read_text())
        _assert_valid_chrome_trace(doc)
        assert doc["metadata"]["pin_reason"] == "stuck_step"

    def test_trace_for_rid(self, recorder):
        flight.record("queued", rid=5, trace_id="lookup")
        assert flight.trace_for_rid(5) == "lookup"
        assert flight.trace_for_rid(6) is None


# ------------------------------------------------------------- merge / export

class TestMergeExport:
    def test_merge_dedups_and_orders_causally(self):
        a = [{"trace_id": "t", "phase": "p1", "lamport": 1, "pid": 1,
              "proc": "gw", "ts": 10.0},
             {"trace_id": "t", "phase": "p3", "lamport": 5, "pid": 1,
              "proc": "gw", "ts": 30.0}]
        b = [{"trace_id": "t", "phase": "p2", "lamport": 3, "pid": 2,
              "proc": "w0", "ts": 1.0},       # skewed wall clock: ts lies
             dict(a[1])]                       # duplicate via pinned copy
        merged = flight.merge_events(a, b, None)
        assert [e["phase"] for e in merged] == ["p1", "p2", "p3"]
        assert len(merged) == 3                # dedup by (lamport, pid, proc)

    def test_chrome_trace_schema_and_rebase(self, recorder):
        flight.set_proc_label("procA")
        flight.record("instant", trace_id="ct", rid=3)
        flight.record("span", trace_id="ct", rid=3, dur=0.5)
        doc = flight.chrome_trace(flight.events_for("ct"))
        pids = _assert_valid_chrome_trace(doc)
        assert list(pids.values()) == ["procA"]
        span = next(e for e in doc["traceEvents"] if e["name"] == "span")
        inst = next(e for e in doc["traceEvents"] if e["name"] == "instant")
        assert span["ph"] == "X" and span["dur"] == pytest.approx(5e5)
        # complete events draw from their start: recorded ts is the END of
        # the measured work, so the renderer rebases by dur
        assert span["ts"] < inst["ts"]
        assert inst["ph"] == "i"
        assert inst["tid"] == 3                # rid becomes the chrome tid

    def test_merged_multiproc_trace_round_trips_json(self, recorder):
        def in_thread(label, phase):
            def run():
                flight.set_proc_label(label)
                with flight.use_context(flight.mint("multi")):
                    flight.record(phase, rid=1)
            t = threading.Thread(target=run)
            t.start()
            t.join()
        in_thread("gateway", "queued")
        in_thread("worker:w0", "prefill")
        doc = flight.chrome_trace(flight.events_for("multi"))
        doc = json.loads(json.dumps(doc))      # must be pure-JSON types
        pids = _assert_valid_chrome_trace(doc)
        assert sorted(pids.values()) == ["gateway", "worker:w0"]


# ------------------------------------------------- RPC context propagation

class TestRpcPropagation:
    def test_ctx_crosses_the_frame_and_clock_folds_back(self, recorder):
        from paddle_tpu.inference.frontend.rpc import RpcClient, RpcServer

        def handler(op, kw):
            flight.set_proc_label("srv")
            flight.record("remote_work", rid=kw["rid"])
            return "ok"

        srv = RpcServer(handler)
        srv.start()
        try:
            c = RpcClient(srv.host, srv.port)
            with flight.use_context(flight.mint("rpc-trace")):
                flight.set_proc_label("cli")
                flight.record("send", rid=9)
                assert c.call("work", rid=9,
                              ctx=flight.wire_context()) == "ok"
                flight.record("after", rid=9)
            c.close()
        finally:
            srv.close()
        events = flight.events_for("rpc-trace")
        assert [e["phase"] for e in events] == ["send", "remote_work",
                                                "after"]
        lamports = [e["lamport"] for e in events]
        assert lamports == sorted(lamports)    # causal chain is monotone
        assert events[1]["proc"] == "srv"      # recorded server-side
        # the reply folded the server's clock back into the client's, so
        # "after" happens-after the remote work despite no shared wall clock
        assert lamports[2] > lamports[1]

    def test_ctx_none_leaves_remote_untraced(self, recorder):
        from paddle_tpu.inference.frontend.rpc import RpcClient, RpcServer
        seen = []
        srv = RpcServer(lambda op, kw: seen.append(flight.current()))
        srv.start()
        try:
            c = RpcClient(srv.host, srv.port)
            c.call("work", ctx=None)
            c.close()
        finally:
            srv.close()
        assert seen == [None]


# ------------------------------------- gateway surfaces (in-process replicas)

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
    return LLMEngine(model, **kw)


class TestSpanInTheRecorder:
    """``obs.trace_span``'s flight leg: the span a request's trace holds is
    the one the registry and the profiler hold, with ``dur``, its
    attributes and the enclosing span as ``parent``."""

    def test_traced_span_carries_dur_attributes_and_parent(self, recorder):
        with flight.use_context(flight.mint("spantrace")):
            with obs.trace_span("outer", rid=5, kind="decode"):
                with obs.trace_span("inner", rid=5, rows=3, ctx_sum=41) as sp:
                    pass
        inner, outer = flight.events_for("spantrace")
        assert (inner["phase"], outer["phase"]) == ("inner", "outer")
        assert inner["rid"] == 5 and inner["dur"] == pytest.approx(sp.dur)
        assert inner["args"] == {"parent": "outer", "rows": 3, "ctx_sum": 41}
        assert outer["args"] == {"parent": None, "kind": "decode"}
        assert outer["dur"] >= inner["dur"]

    def test_untraced_span_is_the_noop_while_metrics_are_off(self, recorder):
        from paddle_tpu.observability import tracing
        assert obs.trace_span("nobody.asked", rows=1) is tracing._NOOP
        assert obs.trace_span("nobody.asked", rid=[1, 2],
                              trace_id=[None, None]) is tracing._NOOP
        assert flight.snapshot_events() == []

    def test_batched_span_records_once_for_each_traced_request(self, recorder):
        with obs.trace_span("decode", rid=[1, 2, 3],
                            trace_id=["ta", None, "tc"], block=2) as sp:
            sp.set(late=1)
        events = flight.snapshot_events()
        assert [(e["trace_id"], e["rid"]) for e in events] == [
            ("ta", 1), ("tc", 3)]
        assert all(e["dur"] == pytest.approx(sp.dur) and e["args"] == {
            "parent": None, "block": 2, "late": 1} for e in events)

    def test_engine_phases_come_from_the_span_call(self, recorder, model):
        eng = _engine(model, prefill_chunk=8)
        with flight.use_context(flight.mint("enginetrace")):
            rid = eng.add_request(list(range(1, 12)), max_new_tokens=3)
            # stepped under the context, the step's own span is live too
            # and is the parent (a replica's loop has it from metrics)
            eng.run_until_done()
        events = {}
        for e in flight.events_for("enginetrace"):
            events.setdefault(e["phase"], []).append(e)
        # two chunks of 8 for 11 prompt tokens, then two decode steps
        assert [(e["args"]["start"], e["args"]["tokens"])
                for e in events["prefill"]] == [(0, 8), (8, 3)]
        assert len(events["decode"]) == 2
        for e in events["prefill"] + events["decode"]:
            assert e["rid"] == rid and e["dur"] > 0
            assert e["args"]["parent"] == "engine.step"
        assert all(e["args"]["block"] == 1 for e in events["decode"])


def _post(url, body, headers=None):
    req = urllib.request.Request(
        f"{url}/v1/completions", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})})
    with urllib.request.urlopen(req, timeout=60) as resp:
        return resp.status, dict(resp.headers), json.loads(resp.read())


def _get(url, path):
    with urllib.request.urlopen(f"{url}{path}", timeout=30) as resp:
        return resp.status, json.loads(resp.read())


class TestGatewayObservability:
    @pytest.fixture()
    def served(self, model, recorder):
        from paddle_tpu.inference.frontend import ReplicaSet, start_gateway
        obs.enable()
        rs = ReplicaSet([_engine(model) for _ in range(2)])
        gw = start_gateway(rs)
        yield gw, rs
        gw.close()
        rs.close()
        obs.disable()
        obs.reset()

    def test_client_request_id_becomes_the_trace(self, served):
        gw, _ = served
        status, headers, body = _post(
            gw.url, {"prompt": [1, 2, 3, 4, 5], "max_tokens": 4},
            headers={"X-Request-ID": "clienttrace01"})
        assert status == 200
        assert headers["X-Request-ID"] == "clienttrace01"
        assert body["request_id"] == "clienttrace01"
        assert len(body["tokens"]) == 4

        code, doc = _get(gw.url, "/v1/requests/clienttrace01/trace")
        assert code == 200
        pids = _assert_valid_chrome_trace(doc)
        # ISSUE acceptance: one merged trace spanning >= 2 recorder
        # processes, every event under the one trace id, causally ordered
        assert "gateway" in pids.values()
        assert any(p.startswith("replica:") for p in pids.values())
        samples = [e for e in doc["traceEvents"] if e["ph"] != "M"]
        assert all(e["args"]["trace_id"] == "clienttrace01"
                   for e in samples)
        lamports = [e["args"]["lamport"] for e in samples]
        assert lamports == sorted(lamports)
        phases = [e["name"] for e in samples]
        for must in ("gateway_accept", "queued", "routed", "prefill",
                     "first_token", "terminal", "gateway_done"):
            assert must in phases, (must, phases)
        assert phases.index("queued") < phases.index("first_token")
        assert phases.index("first_token") < phases.index("terminal")

    def test_minted_request_id_echoes_back(self, served):
        gw, _ = served
        _, headers, body = _post(
            gw.url, {"prompt": [2, 3, 4], "max_tokens": 2})
        rid = body["request_id"]
        assert headers["X-Request-ID"] == rid and len(rid) == 16
        code, doc = _get(gw.url, f"/v1/requests/{rid}/trace")
        assert code == 200 and doc["traceEvents"]

    def test_keepalive_never_echoes_a_stale_request_id(self, served):
        """handler instances persist across requests on one HTTP/1.1
        socket: a follow-up GET, or a POST that 400s before minting, must
        not inherit the previous POST's X-Request-ID."""
        import http.client
        gw, _ = served
        conn = http.client.HTTPConnection(gw.addr, gw.port, timeout=60)
        try:
            conn.request("POST", "/v1/completions",
                         body=json.dumps({"prompt": [1, 2, 3],
                                          "max_tokens": 2}).encode(),
                         headers={"Content-Type": "application/json",
                                  "X-Request-ID": "staleid01"})
            resp = conn.getresponse()
            assert resp.status == 200
            assert resp.getheader("X-Request-ID") == "staleid01"
            resp.read()
            # same socket: the health probe owns no request id
            conn.request("GET", "/healthz")
            resp = conn.getresponse()
            assert resp.status == 200
            assert resp.getheader("X-Request-ID") is None
            resp.read()
            # same socket: a 400 before mint carries no id either
            conn.request("POST", "/v1/completions", body=b"{}",
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            assert resp.status == 400
            assert resp.getheader("X-Request-ID") is None
            resp.read()
        finally:
            conn.close()

    def test_unknown_trace_is_404(self, served):
        gw, _ = served
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(gw.url, "/v1/requests/nosuchtrace/trace")
        assert ei.value.code == 404

    def test_healthz_carries_fleet_rollup(self, served):
        gw, rs = served
        code, health = _get(gw.url, "/healthz")
        assert code == 200
        fleet = health["fleet"]
        assert fleet["replicas"] == 2 and fleet["alive"] == 2
        assert fleet["draining"] == 0
        assert fleet["free_pages"] > 0         # summed across members
        assert fleet["active_slots"] == 0

    def test_metrics_page_is_valid_exposition(self, served):
        gw, _ = served
        _post(gw.url, {"prompt": [1, 2, 3], "max_tokens": 2})
        with urllib.request.urlopen(f"{gw.url}/metrics", timeout=30) as r:
            assert r.status == 200
            text = r.read().decode()
        from tests.test_observability import _assert_valid_exposition
        typed, _ = _assert_valid_exposition(text)
        assert "frontend_requests_total" in typed


# ------------------------- remote-worker federation + mid-scrape member death

class TestFleetFederation:
    @pytest.fixture()
    def fleet(self, model, recorder, monkeypatch):
        from paddle_tpu.distributed.store import TCPStore
        from paddle_tpu.inference.frontend.fleet import FleetReplicaSet
        from paddle_tpu.inference.frontend.worker import WorkerServer
        monkeypatch.setenv("PADDLE_TPU_PURE_PY_STORE", "1")
        obs.enable()
        master = TCPStore(is_master=True, timeout=20)
        workers = {}
        for name in ("w0", "w1"):
            w = WorkerServer(name, _engine(model),
                             TCPStore(port=master.port, timeout=20),
                             group="obsfed", ttl=60.0)
            w.start(heartbeat=False)
            workers[name] = w
        fs = FleetReplicaSet(TCPStore(port=master.port, timeout=20),
                             group="obsfed", ttl=60.0)
        fs.sync()
        yield fs, workers
        fs.close()
        for w in workers.values():
            w.close(drain=False)
        obs.disable()
        obs.reset()

    def _errors(self):
        snap = obs.snapshot(prefix="frontend_federation_errors_total")
        fam = snap.get("frontend_federation_errors_total", {"series": []})
        return {s["labels"]["replica"]: s["value"] for s in fam["series"]}

    def _skipped(self):
        snap = obs.snapshot(prefix="frontend_federation_skipped")
        fam = snap.get("frontend_federation_skipped", {"series": []})
        return sum(s["value"] for s in fam["series"])

    def test_metrics_federate_and_survive_member_death(self, fleet):
        from paddle_tpu.inference.frontend import start_gateway
        from tests.test_observability import _assert_valid_exposition
        fs, workers = fleet
        assert {r.name for r in fs.alive_replicas()} == {"w0", "w1"}
        gw = start_gateway(fs)
        try:
            with urllib.request.urlopen(f"{gw.url}/metrics",
                                        timeout=30) as r:
                assert r.status == 200
                text = r.read().decode()
            _assert_valid_exposition(text)
            # both members answered the scrape: their series carry their name
            assert 'replica="w0"' in text and 'replica="w1"' in text
            assert self._errors() == {}

            # SIGKILL shape: w1's RPC listener and step loop vanish, its
            # lease does not — the next scrape must skip it, not wedge
            w = workers.pop("w1")
            w.rpc.close()
            w.replica.close()
            with urllib.request.urlopen(f"{gw.url}/metrics",
                                        timeout=30) as r:
                assert r.status == 200
                text = r.read().decode()
            _assert_valid_exposition(text)
            assert 'replica="w0"' in text
            assert self._errors().get("w1", 0) >= 1
            assert ('frontend_federation_errors_total{replica="w1"}'
                    in text)
            # the failure marked w1 dead: further scrapes SKIP it without
            # re-counting (the counter's rate must mean "new failures",
            # not "a dead member still lingers in the set") — the skip
            # shows up in the gauge instead
            after_death = self._errors()["w1"]
            for _ in range(2):
                with urllib.request.urlopen(f"{gw.url}/metrics",
                                            timeout=30) as r:
                    text = r.read().decode()
            assert self._errors()["w1"] == after_death
            assert self._skipped() == 1
            assert "frontend_federation_skipped 1" in text
        finally:
            gw.close()

    def test_trace_pull_merges_worker_events(self, fleet):
        fs, workers = fleet
        with flight.use_context(flight.mint("fedtrace01")):
            h = fs.submit(list(range(1, 13)), max_new_tokens=3,
                          do_sample=False)
        toks = list(fs.stream(h))
        assert len(toks) == 3
        events = fs.trace_events_fleet("fedtrace01")
        phases = [e["phase"] for e in events]
        for must in ("routed", "queued", "prefill", "terminal"):
            assert must in phases, (must, phases)
        lamports = [e["lamport"] for e in events]
        assert lamports == sorted(lamports)
        # the engine-side spans were recorded under the worker's label
        worker_procs = {e["proc"] for e in events
                        if e["phase"] in ("queued", "prefill", "terminal")}
        assert worker_procs <= {"worker:w0", "worker:w1",
                                "replica:w0", "replica:w1"}
