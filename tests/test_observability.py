"""Unified runtime telemetry (ISSUE 4): metrics registry semantics, span
tracing, profiler scheduler/export edge cases, and the serving-engine
instrumentation — including parity between ``prefix_cache_stats()`` and the
registry after a real cached-serve run.

The registry is process-global; every test that flips the switch uses the
``metrics`` fixture so the suite always leaves telemetry disabled and the
series zeroed (reset keeps bound children valid by design).
"""
import os
import re
import threading
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import observability as obs
from paddle_tpu.observability.registry import REGISTRY


@pytest.fixture
def metrics():
    obs.reset()
    obs.enable()
    yield obs
    obs.disable()
    obs.reset()


# ------------------------------------------------------------------- registry

class TestRegistry:
    def test_disabled_mutations_are_noops(self):
        c = REGISTRY.counter("test_noop_total", "t")
        obs.disable()
        c.inc()
        c.labels().inc(5)
        assert c.labels().value == 0.0

    def test_counter_accumulates_and_rejects_negative(self, metrics):
        c = REGISTRY.counter("test_counter_total", "t")
        c.inc()
        c.inc(2)
        assert c.labels().value == 3.0
        with pytest.raises(ValueError):
            c.labels().inc(-1)

    def test_label_set_isolation(self, metrics):
        c = REGISTRY.counter("test_labels_total", "t", ("op", "kind"))
        c.inc(op="add", kind="a")
        c.inc(3, op="add", kind="b")
        c.inc(op="mul", kind="a")
        assert c.labels(op="add", kind="a").value == 1.0
        assert c.labels(op="add", kind="b").value == 3.0
        assert c.labels(op="mul", kind="a").value == 1.0
        # children are memoized: same label values -> same object
        assert c.labels(op="add", kind="a") is c.labels(op="add", kind="a")
        with pytest.raises(ValueError):
            c.labels(op="add")                      # missing label
        with pytest.raises(ValueError):
            # deliberate type conflict: asserts the registry rejects it
            REGISTRY.gauge("test_labels_total")  # graftlint: disable=contracts

    def test_gauge_set_inc_dec(self, metrics):
        g = REGISTRY.gauge("test_gauge", "t")
        g.set(7)
        g.labels().inc(2)
        g.labels().dec()
        assert g.labels().value == 8.0

    def test_histogram_bucket_boundaries_le_inclusive(self, metrics):
        h = REGISTRY.histogram("test_hist_seconds", "t", buckets=(1.0, 2.0, 5.0))
        child = h.labels()
        for v in (0.5, 1.0, 1.5, 2.0, 2.5, 100.0):
            child.observe(v)
        d = child._data()
        # exact bound values land in their own bucket (le is inclusive)
        assert d["buckets"] == {"1": 2, "2": 2, "5": 1, "+Inf": 1}
        assert d["count"] == 6
        assert d["sum"] == pytest.approx(107.5)

    def test_histogram_rejects_bad_buckets(self):
        with pytest.raises(ValueError):
            REGISTRY.histogram("test_bad_hist", "t", buckets=(1.0, 1.0))
        with pytest.raises(ValueError):
            REGISTRY.histogram("test_empty_hist", "t", buckets=())

    def test_concurrent_increments_from_threads(self, metrics):
        c = REGISTRY.counter("test_threads_total", "t")
        child = c.labels()
        N, M = 8, 2000

        def work():
            for _ in range(M):
                child.inc()

        threads = [threading.Thread(target=work) for _ in range(N)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert child.value == N * M

    def test_reset_keeps_bound_children_valid(self, metrics):
        c = REGISTRY.counter("test_reset_total", "t")
        child = c.labels()
        child.inc(4)
        obs.reset()
        assert child.value == 0.0
        child.inc()                      # the same handle still feeds the family
        assert c.labels().value == 1.0

    def test_snapshot_filters(self, metrics):
        c = REGISTRY.counter("test_snap_total", "t", ("engine",))
        c.inc(engine="0")
        c.inc(engine="1")
        snap = obs.snapshot(prefix="test_snap", labels={"engine": "1"})
        assert list(snap) == ["test_snap_total"]
        assert snap["test_snap_total"]["series"] == [
            {"labels": {"engine": "1"}, "value": 1.0}]
        assert "test_snap_total" not in obs.snapshot(prefix="serving_")


# ------------------------------------------------- Prometheus text exposition

_LABEL_VAL = r'"(?:[^"\\]|\\.)*"'                      # allows \" and \\ escapes
_METRIC_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"                       # metric name
    r"(\{[a-zA-Z_][a-zA-Z0-9_]*=" + _LABEL_VAL +       # first label
    r"(,[a-zA-Z_][a-zA-Z0-9_]*=" + _LABEL_VAL + r")*\})?"  # more labels
    r" (\+Inf|-?[0-9]+(\.[0-9]+)?(e[+-]?[0-9]+)?)$")


def _assert_valid_exposition(text):
    """Minimal 0.0.4 exposition validator: every line is a HELP/TYPE comment
    or a sample; TYPE precedes its samples; histograms are cumulative and end
    at +Inf == _count."""
    typed = {}
    samples = []
    for line in text.splitlines():
        if line.startswith("# HELP "):
            continue
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ", 3)
            assert kind in ("counter", "gauge", "histogram"), line
            typed[name] = kind
            continue
        assert _METRIC_LINE.match(line), f"bad exposition line: {line!r}"
        samples.append(line)
    for line in samples:
        name = re.split(r"[{ ]", line, 1)[0]
        base = re.sub(r"_(bucket|sum|count)$", "", name)
        assert name in typed or base in typed, f"sample without TYPE: {line!r}"
    return typed, samples


class TestPrometheus:
    def test_render_parses_as_valid_exposition(self, metrics):
        c = REGISTRY.counter("test_expo_total", "with label", ("op",))
        c.inc(op='weird"val\\ue')        # label escaping exercised
        h = REGISTRY.histogram("test_expo_seconds", "hist", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        h.observe(3.0)
        text = obs.render_prometheus()
        typed, samples = _assert_valid_exposition(text)
        assert typed["test_expo_total"] == "counter"
        assert typed["test_expo_seconds"] == "histogram"
        # histogram buckets are CUMULATIVE and close at +Inf == _count
        buckets = [l for l in samples if l.startswith("test_expo_seconds_bucket")]
        counts = [float(l.rsplit(" ", 1)[1]) for l in buckets]
        assert counts == sorted(counts) and counts[-1] == 3
        assert 'le="+Inf"' in buckets[-1]
        assert any(l.startswith("test_expo_seconds_count") and
                   l.endswith(" 3") for l in samples)

    def test_snapshot_prometheus_round_trip(self, metrics):
        c = REGISTRY.counter("test_round_total", "t", ("k",))
        c.inc(41, k="x")
        c.inc(k="x")
        snap = obs.snapshot(prefix="test_round_total")
        assert snap["test_round_total"]["series"][0]["value"] == 42.0
        assert 'test_round_total{k="x"} 42' in obs.render_prometheus()

    def test_label_value_escaping(self, metrics):
        # backslash, double quote and newline each escape per the 0.0.4 spec:
        # \ -> \\   " -> \"   LF -> \n  (two characters, not a raw newline)
        c = REGISTRY.counter("test_esc_total", "t", ("v",))
        c.inc(v='back\\slash "quote"\nline2')
        text = obs.render_prometheus()
        _assert_valid_exposition(text)
        line = next(l for l in text.splitlines()
                    if l.startswith("test_esc_total{"))
        assert line == 'test_esc_total{v="back\\\\slash \\"quote\\"\\nline2"} 1'

    def test_help_text_escaping(self, metrics):
        # HELP escapes only backslash and newline; a raw newline would split
        # the comment and leave a line the scraper rejects
        REGISTRY.counter("test_help_total", 'path C:\\tmp\nsecond line')
        text = obs.render_prometheus()
        _assert_valid_exposition(text)
        help_line = next(l for l in text.splitlines()
                         if l.startswith("# HELP test_help_total"))
        assert help_line == ("# HELP test_help_total "
                             "path C:\\\\tmp\\nsecond line")

    def test_histogram_inf_sum_count_framing(self, metrics):
        h = REGISTRY.histogram("test_frame_seconds", "h", buckets=(0.1, 1.0),
                               labelnames=("op",))
        h.observe(0.1, op="a")          # boundary lands IN the 0.1 bucket
        h.observe(7.0, op="a")          # beyond the last bound -> +Inf only
        text = obs.render_prometheus()
        typed, samples = _assert_valid_exposition(text)
        assert typed["test_frame_seconds"] == "histogram"
        frame = [l for l in samples if l.startswith("test_frame_seconds")]
        # exactly the spec framing: every bound plus +Inf, then _sum, _count
        assert [l.split(" ")[0] for l in frame] == [
            'test_frame_seconds_bucket{op="a",le="0.1"}',
            'test_frame_seconds_bucket{op="a",le="1"}',
            'test_frame_seconds_bucket{op="a",le="+Inf"}',
            'test_frame_seconds_sum{op="a"}',
            'test_frame_seconds_count{op="a"}',
        ]
        counts = {l.split(" ")[0]: l.rsplit(" ", 1)[1] for l in frame}
        assert counts['test_frame_seconds_bucket{op="a",le="0.1"}'] == "1"
        assert counts['test_frame_seconds_bucket{op="a",le="+Inf"}'] == "2"
        assert counts['test_frame_seconds_count{op="a"}'] == "2"
        assert float(counts['test_frame_seconds_sum{op="a"}']) == 7.1


# ------------------------------------------------- federated snapshot merging

class TestFederation:
    def _remote(self, value=3.0, labels=None, type="counter"):
        return {"test_fed_total": {
            "type": type, "help": "t",
            "series": [{"labels": dict(labels or {"op": "x"}),
                        "value": value}]}}

    def test_merge_relabels_remote_series(self, metrics):
        c = REGISTRY.counter("test_fed_total", "t", ("op",))
        c.inc(op="x")
        merged = obs.merge_snapshots(obs.snapshot(prefix="test_fed"),
                                     {"w0": self._remote(3.0)})
        series = merged["test_fed_total"]["series"]
        # local series untouched, remote series gains replica=<name>
        assert {"labels": {"op": "x"}, "value": 1.0} in series
        assert {"labels": {"op": "x", "replica": "w0"}, "value": 3.0} in series
        text = obs.render_snapshot(merged)
        _assert_valid_exposition(text)
        assert 'test_fed_total{op="x",replica="w0"} 3' in text

    def test_merge_keeps_existing_replica_label(self, metrics):
        # front-door families already attribute a replica; federation must
        # not overwrite the worker's own attribution
        merged = obs.merge_snapshots(
            {}, {"w0": self._remote(2.0, {"op": "x", "replica": "inner"})})
        assert merged["test_fed_total"]["series"] == [
            {"labels": {"op": "x", "replica": "inner"}, "value": 2.0}]

    def test_merge_skips_type_conflicts(self, metrics):
        c = REGISTRY.counter("test_fed_total", "t", ("op",))
        c.inc(op="x")
        merged = obs.merge_snapshots(
            obs.snapshot(prefix="test_fed"),
            {"w0": self._remote(9.0, type="gauge"),
             "w1": self._remote(5.0)})
        # w0's gauge family conflicts with the local counter and is dropped;
        # w1's matching counter merges — and the result still renders clean
        values = {s["labels"].get("replica"): s["value"]
                  for s in merged["test_fed_total"]["series"]}
        assert values == {None: 1.0, "w1": 5.0}
        _assert_valid_exposition(obs.render_snapshot(merged))

    def test_merge_of_disjoint_remote_histogram(self, metrics):
        snap = {"test_fedh_seconds": {
            "type": "histogram", "help": "h",
            "series": [{"labels": {}, "buckets": {"0.1": 1, "+Inf": 1},
                        "sum": 2.5, "count": 2}]}}
        merged = obs.merge_snapshots({}, {"w0": snap})
        text = obs.render_snapshot(merged)
        typed, samples = _assert_valid_exposition(text)
        assert typed["test_fedh_seconds"] == "histogram"
        assert ('test_fedh_seconds_bucket{replica="w0",le="+Inf"} 2'
                in samples)


# ------------------------------------------------------ pull endpoint (HTTP)

class TestMetricsServer:
    def test_scrape_returns_current_exposition(self, metrics):
        import urllib.request
        c = REGISTRY.counter("test_scrape_total", "t")
        c.inc(5)
        with obs.start_metrics_server(port=0) as server:
            assert server.url.endswith(f":{server.port}/metrics")
            with urllib.request.urlopen(server.url, timeout=5) as resp:
                assert resp.status == 200
                assert resp.headers["Content-Type"].startswith("text/plain")
                body = resp.read().decode("utf-8")
            typed, _ = _assert_valid_exposition(body)
            assert typed["test_scrape_total"] == "counter"
            assert "test_scrape_total 5" in body
            # scrapes render live state, not a startup snapshot
            c.inc(2)
            with urllib.request.urlopen(server.url, timeout=5) as resp:
                assert "test_scrape_total 7" in resp.read().decode("utf-8")

    def test_unknown_path_is_404_and_close_releases_port(self, metrics):
        import urllib.error
        import urllib.request
        server = obs.start_metrics_server(port=0)
        url = f"http://{server.addr}:{server.port}/nope"
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(url, timeout=5)
        assert e.value.code == 404
        server.close()
        with pytest.raises(OSError):
            urllib.request.urlopen(server.url, timeout=1)


# ---------------------------------------------------------- dispatch recorder

class TestDispatch:
    def test_disabled_leaves_hot_path_bare(self):
        from paddle_tpu.core import dispatch
        obs.disable()
        assert dispatch.metrics_recorder() is None
        assert dispatch._state.op_recorder is None

    def test_dispatch_counts_and_seconds(self, metrics):
        x = pt.tensor([1.0, 2.0])
        (x * 3).sum()
        snap = obs.snapshot(prefix="dispatch_ops_total")
        ops = {s["labels"]["op"]: s["value"]
               for s in snap["dispatch_ops_total"]["series"]}
        assert ops.get("multiply", 0) >= 1 and ops.get("sum", 0) >= 1
        hist = obs.snapshot(prefix="dispatch_host_seconds")
        assert hist["dispatch_host_seconds"]["series"][0]["count"] >= 2

    def test_taped_dispatches_counted(self, metrics):
        x = pt.tensor([1.0, 2.0], stop_gradient=False)
        (x * x).sum()
        snap = obs.snapshot(prefix="dispatch_taped_total")
        assert snap["dispatch_taped_total"]["series"][0]["value"] >= 2

    def test_profiler_and_metrics_recorders_compose(self, metrics):
        from paddle_tpu.core import dispatch
        from paddle_tpu import profiler
        p = profiler.Profiler(timer_only=True)
        p.start()
        try:
            assert isinstance(dispatch._state.op_recorder,
                              dispatch._FanoutRecorder)
            pt.tensor([1.0]) + 1.0
        finally:
            p.stop()
        # profiler saw the op AND the registry counted it
        assert p._op_recorder.ops
        snap = obs.snapshot(prefix="dispatch_ops_total")
        assert snap["dispatch_ops_total"]["series"]
        # stop() restored the bare metrics recorder, not None
        assert dispatch._state.op_recorder is dispatch.metrics_recorder()


# ----------------------------------------------------------------- trace_span

class TestTraceSpan:
    def test_span_counts_once_and_leaves_the_profiler_list_alone(self, metrics):
        # the span's registry leg; the old ``profiler._host_events`` leg (a
        # list nothing bounded) is gone: RecordEvent alone writes there
        from paddle_tpu.profiler import _host_events
        _host_events.pop("test.span", None)
        with obs.trace_span("test.span") as sp:
            pass
        assert "test.span" not in _host_events
        snap = obs.snapshot(prefix="span_seconds",
                            labels={"span": "test.span"})
        series = snap["span_seconds"]["series"][0]
        assert series["count"] == 1
        assert series["sum"] == pytest.approx(sp.dur)

    def test_span_disabled_is_passthrough(self):
        from paddle_tpu.profiler import _host_events
        obs.disable()
        _host_events.pop("test.span.off", None)
        with obs.trace_span("test.span.off"):
            pass
        assert "test.span.off" not in _host_events
        snap = obs.snapshot(prefix="span_seconds",
                            labels={"span": "test.span.off"})
        assert not snap.get("span_seconds", {}).get("series")

    def test_both_switches_off_is_one_shared_noop_and_reads_no_clock(
            self, monkeypatch):
        from paddle_tpu.observability import flight, tracing
        obs.disable()
        flight.disable()

        def no_clock():
            raise AssertionError("a span with nothing listening read the clock")
        monkeypatch.setattr(tracing.time, "perf_counter", no_clock)
        a = obs.trace_span("test.off.a", rows=3)
        b = obs.trace_span("test.off.b", rid=1, trace_id="t")
        assert a is b is tracing._NOOP
        with a as sp:
            sp.set(kind="decode")
        assert sp.dur is None

    def test_timed_span_keeps_dur_with_both_switches_off(self):
        from paddle_tpu.observability import flight
        obs.disable()
        flight.disable()
        with obs.trace_span("test.timed", timed=True) as sp:
            pass
        assert sp.dur is not None and sp.dur >= 0.0
        snap = obs.snapshot(prefix="span_seconds",
                            labels={"span": "test.timed"})
        assert not snap.get("span_seconds", {}).get("series")

    def test_exception_leaves_the_scope_and_is_counted(self, metrics):
        with pytest.raises(KeyError):
            with obs.trace_span("test.span.raises"):
                raise KeyError("x")
        snap = obs.snapshot(prefix="span_seconds",
                            labels={"span": "test.span.raises"})
        assert snap["span_seconds"]["series"][0]["count"] == 1
        # the thread's stack of open spans is back where it was
        with obs.trace_span("test.span.after") as sp:
            pass
        assert sp.dur is not None


# ------------------------------------------------------------ the device watch

def _loop_seconds(engine):
    """``{(span, device): seconds}`` of ``loop_seconds_total`` for one
    engine label, the series that hold time."""
    fam = obs.snapshot(prefix="loop_seconds_total",
                       labels={"engine": engine}).get("loop_seconds_total")
    return {(s["labels"]["span"], s["labels"]["device"]): s["value"]
            for s in (fam or {}).get("series", []) if s["value"]}


class TestDeviceWatch:
    """The rule, on a fake clock and a fake probe: an interval between two
    span boundaries of a watched thread goes to the innermost span open
    through it ("none" outside every span); dry where the probe read idle
    at its start, busy where it read busy at both ends, half each where it
    went from busy to idle."""

    @pytest.fixture
    def watched(self, metrics, monkeypatch):
        from paddle_tpu.observability import tracing
        clock = [100.0]
        monkeypatch.setattr(tracing.time, "perf_counter", lambda: clock[0])
        device = {"busy": False, "reads": 0}

        def probe():
            device["reads"] += 1
            return device["busy"]
        obs.watch_device(probe, engine="fake")
        device["probe"] = probe
        yield clock, device
        obs.watch_device(None)

    def test_dry_busy_and_the_split_go_to_the_innermost_span(self, watched):
        clock, device = watched
        with obs.trace_span("outer"):   # the first boundary counts nothing
            clock[0] += 1.0             # idle at the start: dry
            device["busy"] = True       # a launch inside the interval
            with obs.trace_span("inner"):
                clock[0] += 2.0         # busy at both ends: busy
            clock[0] += 4.0
            device["busy"] = False      # busy -> idle: half each
        clock[0] += 8.0                 # outside every span, idle: dry
        with obs.trace_span("last"):
            pass
        assert _loop_seconds("fake") == {
            ("outer", "dry"): 1.0 + 2.0, ("inner", "busy"): 2.0,
            ("outer", "busy"): 2.0, ("none", "dry"): 8.0}
        assert device["reads"] == 6     # once a boundary

    def test_a_wait_on_the_device_is_busy_and_a_tick_cuts_a_long_span(
            self, watched):
        """Under a span the registrant names as a wait, busy -> idle is
        busy: the wait ended because the device finished. A tick inside a
        long span narrows where the device finished to one piece of it."""
        clock, device = watched
        obs.watch_device(device["probe"], engine="fake", waits=("wait",))
        device["busy"] = True
        with obs.trace_span("wait"):
            clock[0] += 3.0
            device["busy"] = False
        device["busy"] = True           # the next launch
        with obs.trace_span("fan"):
            clock[0] += 1.0
            obs.tick()                  # busy at both ends of the piece
            clock[0] += 1.0
            device["busy"] = False
            obs.tick()                  # finished within this piece: half
            clock[0] += 1.0             # idle from the piece's end: dry
        assert _loop_seconds("fake") == {
            ("wait", "busy"): 3.0, ("fan", "busy"): 1.5, ("fan", "dry"): 1.5}

    def test_an_interval_across_the_switch_going_off_is_not_counted(
            self, watched):
        clock, _ = watched
        with obs.trace_span("a"):
            clock[0] += 1.0
        obs.disable()
        clock[0] += 50.0
        obs.enable()
        clock[0] += 1.0
        with obs.trace_span("b"):       # starts afresh: the 52 s go nowhere
            clock[0] += 2.0
        assert _loop_seconds("fake") == {("a", "dry"): 1.0, ("b", "dry"): 2.0}

    def test_a_thread_that_registered_nothing_is_untouched(self, watched):
        _, device = watched

        def elsewhere():
            with obs.trace_span("test.unwatched"):
                with obs.trace_span("test.unwatched.inner"):
                    pass
        t = threading.Thread(target=elsewhere)
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
        assert device["reads"] == 0
        fam = obs.snapshot(prefix="loop_seconds_total").get(
            "loop_seconds_total", {"series": []})
        assert not [s for s in fam["series"] if s["value"]]

    def test_metrics_off_calls_no_probe_and_spans_stay_the_shared_noop(self):
        from paddle_tpu.observability import flight, tracing
        obs.disable()
        flight.disable()
        reads = []
        obs.watch_device(lambda: reads.append(1) or True, engine="off")
        try:
            sp = obs.trace_span("test.watch.off")
            assert sp is tracing._NOOP
            with sp:
                pass
            with obs.trace_span("test.watch.timed", timed=True):
                pass                    # the clock, for its caller alone
            obs.tick()
        finally:
            obs.watch_device(None)
        assert reads == []
        assert _loop_seconds("off") == {}


# ------------------------------------------------- profiler scheduler/export

class TestScheduler:
    def test_zero_cycle_never_divides(self):
        from paddle_tpu.profiler import ProfilerState, make_scheduler
        s = make_scheduler(closed=0, ready=0, record=0)
        for step in range(4):           # cycle == 0: no ZeroDivisionError
            assert s(step) in (ProfilerState.CLOSED, ProfilerState.RECORD)

    def test_repeat_boundary_exactly_at_cycle_times_repeat(self):
        from paddle_tpu.profiler import ProfilerState, make_scheduler
        s = make_scheduler(closed=1, ready=1, record=2, repeat=2)
        cycle = 4
        assert s(cycle * 2 - 1) == ProfilerState.RECORD_AND_RETURN
        assert s(cycle * 2) == ProfilerState.CLOSED       # exact boundary
        assert s(cycle * 2 + 5) == ProfilerState.CLOSED   # stays closed

    def test_skip_first_shifts_the_whole_schedule(self):
        from paddle_tpu.profiler import ProfilerState, make_scheduler
        s = make_scheduler(closed=1, ready=1, record=1, skip_first=3)
        assert [s(i) for i in range(3)] == [ProfilerState.CLOSED] * 3
        assert s(3) == ProfilerState.CLOSED     # pos 0 of the first cycle
        assert s(4) == ProfilerState.READY
        assert s(5) == ProfilerState.RECORD_AND_RETURN
        # skip_first + repeat: the repeat window starts after the skip
        s2 = make_scheduler(closed=0, ready=0, record=2, repeat=1,
                            skip_first=2)
        assert s2(1) == ProfilerState.CLOSED
        assert s2(2) == ProfilerState.RECORD
        assert s2(3) == ProfilerState.RECORD_AND_RETURN
        assert s2(4) == ProfilerState.CLOSED

    def test_record_and_return_only_on_last_record_step(self):
        from paddle_tpu.profiler import ProfilerState, make_scheduler
        s = make_scheduler(closed=1, ready=1, record=3)
        got = [s(i) for i in range(5)]
        assert got == [ProfilerState.CLOSED, ProfilerState.READY,
                       ProfilerState.RECORD, ProfilerState.RECORD,
                       ProfilerState.RECORD_AND_RETURN]
        assert got.count(ProfilerState.RECORD_AND_RETURN) == 1


class TestExportProtobuf:
    def _fake_xplane(self, root, run, name):
        d = root / "plugins" / "profile" / run
        d.mkdir(parents=True)
        p = d / name
        p.write_bytes(b"\x00fake-xplane")
        return str(p)

    def test_handler_selects_protobuf_format(self):
        from paddle_tpu import profiler
        prof = profiler.Profiler(timer_only=True)
        profiler.export_protobuf("/tmp/ptb")(prof)
        assert prof._export_dir == "/tmp/ptb"
        assert prof._export_format == "protobuf"

    def test_export_resolves_newest_xplane(self, tmp_path):
        from paddle_tpu import profiler
        prof = profiler.Profiler(timer_only=True)
        prof._dir = str(tmp_path)
        self._fake_xplane(tmp_path, "run_a", "host.xplane.pb")
        newest = self._fake_xplane(tmp_path, "run_b", "host.xplane.pb")
        assert prof.export(format="protobuf") == newest

    def test_export_falls_back_to_json_with_warning(self, tmp_path, caplog):
        from paddle_tpu import profiler
        prof = profiler.Profiler(
            timer_only=True,
            on_trace_ready=profiler.export_protobuf(str(tmp_path)))
        prof.start()
        prof.step()
        prof.stop()                       # handler arms protobuf format
        out = str(tmp_path / "trace.json")
        with caplog.at_level("WARNING", logger="paddle_tpu.profiler"):
            path = prof.export(out)
        assert path == out and os.path.exists(out)
        assert any("falling back" in r.message for r in caplog.records)


# --------------------------------------------------------- jit capture events

class TestJitEvents:
    def _events(self, fn_name):
        snap = obs.snapshot(prefix="jit_events_total",
                            labels={"fn": fn_name})
        return {s["labels"]["event"]: s["value"]
                for s in snap.get("jit_events_total", {}).get("series", [])}

    def test_capture_then_cache_hit(self, metrics):
        from paddle_tpu.jit import to_static

        @to_static
        def double_it(x):
            return x * 2.0

        x = pt.tensor([1.0, 2.0])
        double_it(x)
        assert self._events("double_it").get("capture") == 1
        double_it(x)
        ev = self._events("double_it")
        assert ev.get("capture") == 1 and ev.get("cache_hit") == 1


# -------------------------------------------------- serving engine telemetry

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
    from paddle_tpu.inference.serving import LLMEngine
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_chunk", 8)
    return LLMEngine(model, **kw)


def _serve(eng, prompts, **req_kw):
    req_kw.setdefault("max_new_tokens", 6)
    outs = []
    for p in prompts:
        rid = eng.add_request(p, **req_kw)
        eng.run_until_done()
        outs.append(eng.result(rid))
    return outs


def _prompts(seed=0, n=2, shared=16, tail=5):
    rng = np.random.RandomState(seed)
    prefix = rng.randint(1, 128, (shared,)).astype(np.int32)
    return [np.concatenate([prefix,
                            rng.randint(1, 128, (tail,)).astype(np.int32)])
            for _ in range(n)]


class TestEngineMetrics:
    def test_metrics_view_after_real_served_batch(self, metrics, model):
        eng = _engine(model, prefix_cache=True)
        _serve(eng, _prompts(seed=3))
        m = eng.metrics()
        ttft = m["serving_ttft_seconds"]["series"]
        assert len(ttft) == 1 and ttft[0]["count"] == 2      # one per request
        assert m["serving_token_latency_seconds"]["series"][0]["count"] > 0
        kinds = {s["labels"]["kind"]: s["value"]
                 for s in m["serving_dispatches_total"]["series"]}
        assert kinds["prefill"] >= 2 and kinds["decode"] >= 1
        assert m["serving_generated_tokens_total"]["series"][0]["value"] == 12
        # gauges reflect the drained engine
        assert m["serving_queue_depth"]["series"][0]["value"] == 0
        assert m["serving_active_slots"]["series"][0]["value"] == 0
        assert m["serving_batch_occupancy_ratio"]["series"][0]["value"] == 0
        assert m["serving_free_pages"]["series"][0]["value"] > 0
        # every series carries this engine's label only
        for fam in m.values():
            for s in fam["series"]:
                assert s["labels"]["engine"] == eng._m.label

    def test_prefix_cache_stats_registry_parity(self, metrics, model):
        eng = _engine(model, prefix_cache=True)
        _serve(eng, _prompts(seed=4))
        st = eng.prefix_cache_stats()
        assert st["hits"] >= 2                   # the shared prefix was reused
        events = {s["labels"]["event"]: s["value"]
                  for s in eng.metrics()
                  ["serving_prefix_cache_events_total"]["series"]}
        assert events.get("hit", 0) == st["hits"]
        assert events.get("miss", 0) == st["misses"]
        assert events.get("eviction", 0) == st["evictions"]
        assert events.get("cow_copy", 0) == st["cow_copies"]
        m = eng.metrics()
        assert m["serving_prefix_cached_pages"]["series"][0]["value"] \
            == st["cached_pages"]
        assert m["serving_prefix_reclaimable_pages"]["series"][0]["value"] \
            == st["reclaimable_pages"]

    def test_stats_unchanged_with_metrics_disabled(self, model):
        obs.disable()
        obs.reset()
        eng = _engine(model, prefix_cache=True)
        _serve(eng, _prompts(seed=5))
        st = eng.prefix_cache_stats()
        assert st["hits"] >= 2 and st["prefill_dispatches"] > 0
        # the registry saw nothing: plain-int attrs are the always-on path
        snap = obs.snapshot(prefix="serving_",
                            labels={"engine": eng._m.label})
        for fam in snap.values():
            for s in fam["series"]:
                assert s.get("value", s.get("count", 0)) == 0

    def test_engine_render_prometheus_is_valid(self, metrics, model):
        eng = _engine(model, prefix_cache=True)
        _serve(eng, _prompts(seed=6, n=1))
        typed, samples = _assert_valid_exposition(obs.render_prometheus())
        assert typed["serving_ttft_seconds"] == "histogram"
        assert any(l.startswith("serving_dispatches_total{") for l in samples)


# --------------------------------------------- the step loop, span by span

LEAF_SPANS = ("replica.idle", "replica.lock", "engine.admit", "engine.prepare",
              "runner.dispatch", "runner.launch", "runner.wait", "engine.emit",
              "replica.publish")


def _drain(rep, rid, timeout=60.0):
    toks = []
    while True:
        got, status = rep.poll(rid, timeout=timeout)
        toks += got
        if status.terminal:
            return toks


def _loop_thread_events(trace_dir):
    """The ``TraceAnnotation`` events of the thread that ran ``engine.step``,
    from the profiler's xplane file: ``(name, start_ns, end_ns, stats)``."""
    import glob
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                     recursive=True)[0]
    host = [p for p in ProfileData.from_file(path).planes
            if p.name.startswith("/host:CPU")][0]
    for line in host.lines:
        events = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                   dict(e.stats)) for e in line.events]
        if any(name == "engine.step" for name, *_ in events):
            return sorted(events, key=lambda e: e[1])
    raise AssertionError("no thread of the trace ran engine.step")


class TestStepLoopSpans:
    @pytest.fixture
    def replica(self, metrics, model):
        from paddle_tpu.inference.frontend.replica import EngineReplica
        eng = _engine(model, max_batch=4)
        rep = EngineReplica("r0", eng, poll_interval=0.01).start()
        # compile both programs before anything is measured
        _drain(rep, rep.submit(_prompts(seed=9, n=1)[0], max_new_tokens=3))
        yield rep, eng
        rep.close()

    def test_leaf_spans_partition_the_loop_and_dispatch_carries_the_spy(
            self, replica, tmp_path):
        import jax
        from bench.traffic.open_loop_http import _spy_on_runner
        rep, eng = replica
        spied = []
        _spy_on_runner([eng], spied)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            time.sleep(0.05)            # a few whole idle waits on each side
            rids = [rep.submit(p, max_new_tokens=8)
                    for p in _prompts(seed=11, n=3, shared=10, tail=7)]
            for rid in rids:
                assert len(_drain(rep, rid)) == 8
            time.sleep(0.05)
        finally:
            jax.profiler.stop_trace()
        events = _loop_thread_events(tmp_path)
        leaves = [e for e in events if e[0] in LEAF_SPANS]
        assert {name for name, *_ in leaves} == set(LEAF_SPANS)
        # no two leaves overlap, and together they cover the thread's time
        # between the first leaf's start and the last one's end
        for (_, _, end, _), (name, start, _, _) in zip(leaves, leaves[1:]):
            assert start >= end, (name, start, end)
        # ... turn by turn of the loop (from one replica.lock to the next):
        # in the median turn the leaves hold 80 % of the wall time, and
        # 80 % of the whole. (The one sum over the trace was held to 95 %:
        # true while three quarters of an unloaded trace were the timed
        # idle waits, and false under the driver's six workers, where the
        # working part stretches, the waits do not, and a lost time slice
        # falls between two leaves as well as inside one: 91 % there. Work
        # outside every leaf would show in every turn; the median turn reads
        # 85-90 %, loaded or not, at this model's leaves of tens of
        # microseconds.)
        turns = [i for i, (name, *_) in enumerate(leaves)
                 if name == "replica.lock"]
        cover = sorted(
            sum(end - start for _, start, end, _ in leaves[lo:hi])
            / (leaves[hi][1] - leaves[lo][1])
            for lo, hi in zip(turns, turns[1:]))
        assert len(cover) > 8 and cover[len(cover) // 2] >= 0.80, cover
        wall = leaves[-1][2] - leaves[0][1]
        covered = sum(end - start for _, start, end, _ in leaves)
        assert covered >= 0.80 * wall, (covered, wall)
        # every step says what it ran
        kinds = {st.get("kind") for name, _, _, st in events
                 if name == "engine.step"}
        assert kinds <= {"prefill", "decode", "none"} and "decode" in kinds
        # runner.dispatch holds what the benchmark's spy records for the
        # same dispatches: the xplane file alone feeds the rooflines
        keys = ("kind", "rows", "ctx_sum", "start", "k")
        mine = [{k: st[k] for k in keys if k in st}
                for name, _, _, st in events if name == "runner.dispatch"]
        spy = [{k: sp[k] for k in keys if k in sp} for sp in spied]
        assert mine == spy and len(mine) > 8

    def test_span_sums_agree_with_the_dispatch_counter(self, replica):
        rep, eng = replica
        obs.reset()
        _drain(rep, rep.submit(_prompts(seed=12, n=1)[0], max_new_tokens=5))
        snap = obs.snapshot()
        count = {s["labels"]["span"]: s["count"]
                 for s in snap["span_seconds"]["series"]}
        n = sum(s["value"] for s in snap["serving_dispatches_total"]["series"])
        assert n >= 5 and count["runner.dispatch"] == n
        assert count["runner.launch"] == n
        assert count["engine.step"] >= n

    @pytest.mark.parametrize("one_sampled", [False, True],
                             ids=["all_greedy", "one_sampled_row"])
    def test_argmax_only_dispatches_are_counted_and_marked(
            self, replica, tmp_path, one_sampled):
        """What the sampler's batch-level branch took, as the host read it
        before dispatching: ``serving_argmax_dispatches_total`` beside
        ``serving_dispatches_total``, ``argmax_only`` on ``runner.dispatch``.
        Greedy traffic: every dispatch. One sampled request among greedy
        ones: its own prefill chunks and every decode step it rides in."""
        import jax
        rep, eng = replica
        obs.reset()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            prompts = _prompts(seed=15, n=3, shared=10, tail=7)
            with rep._cv:       # all three arrive between the same steps
                rids = [rep.submit(p, max_new_tokens=8) for p in prompts[:2]]
                # the longest, so that no decode step runs without it
                rids.append(rep.submit(prompts[2], max_new_tokens=24,
                                       do_sample=one_sampled, top_k=5,
                                       seed=3))
            for rid in rids:
                _drain(rep, rid)
        finally:
            jax.profiler.stop_trace()
        snap = obs.snapshot(labels={"engine": eng._m.label})

        def by_kind(name):
            return {s["labels"]["kind"]: s["value"]
                    for s in snap[name]["series"]}
        total = by_kind("serving_dispatches_total")
        argmax = by_kind("serving_argmax_dispatches_total")
        marks = [(st["kind"], st["argmax_only"])
                 for name, _, _, st in _loop_thread_events(tmp_path)
                 if name == "runner.dispatch"]
        for kind in ("prefill", "decode"):
            assert total[kind] > 0
            # the span says what the counter says
            assert (sum(a for k, a in marks if k == kind) == argmax[kind]
                    and sum(k == kind for k, _ in marks) == total[kind])
        if not one_sampled:
            assert argmax["prefill"] == total["prefill"]
            assert argmax["decode"] == total["decode"]
        else:
            assert argmax["decode"] == 0
            chunks = -(-len(prompts[2]) // eng.chunk)
            assert argmax["prefill"] == total["prefill"] - chunks

    def test_a_submitter_gets_in_between_two_steps(self, replica):
        """The loop hands the engine condition to whoever waits for it
        before it steps again: a submit that arrives while a request of 40
        paced steps is being served waits a step or two, not for the
        request's end (a lock has no fairness of its own: dropped and taken
        again at once it came back to the loop nearly every time)."""
        from paddle_tpu.testing.faults import FAULTS, Always
        rep, eng = replica
        FAULTS.install("serving.slow_step", Always(), delay=0.02)
        try:
            first = rep.submit(_prompts(seed=16, n=1)[0], max_new_tokens=40)
            deadline = 100
            while not any(s is not None for s in eng.sched.slots) and deadline:
                deadline -= 1
                time.sleep(0.01)
            t0 = time.monotonic()
            second = rep.submit(_prompts(seed=17, n=1)[0], max_new_tokens=2)
            waited = time.monotonic() - t0
            # the first request still has most of its 0.8 s of steps to go
            assert first not in eng.sched.finished
        finally:
            FAULTS.reset()
        assert waited < 0.3, waited
        assert len(_drain(rep, second)) == 2 and len(_drain(rep, first)) == 40

    def test_a_submitter_held_out_by_a_slow_step_is_counted(self, replica):
        from paddle_tpu.testing.faults import FAULTS, Always
        rep, eng = replica
        obs.reset()
        FAULTS.install("serving.slow_step", Always(), delay=0.1)
        try:
            first = rep.submit(_prompts(seed=13, n=1)[0], max_new_tokens=4)
            deadline = 50
            while not any(s is not None for s in eng.sched.slots) and deadline:
                deadline -= 1
                time.sleep(0.01)
            # the loop is now inside a step of 0.1 s at the least
            second = rep.submit(_prompts(seed=14, n=1)[0], max_new_tokens=2)
        finally:
            FAULTS.reset()
        _drain(rep, first)
        _drain(rep, second)
        snap = obs.snapshot(prefix="frontend_engine_lock_wait_seconds",
                            labels={"op": "submit", "replica": "r0"})
        series = snap["frontend_engine_lock_wait_seconds"]["series"][0]
        assert series["count"] == 2
        # the second submit sat out what was left of a 0.1 s step at least
        assert series["sum"] > 0.02
        waits = obs.snapshot(prefix="serving_queue_wait_seconds",
                             labels={"engine": eng._m.label})
        assert waits["serving_queue_wait_seconds"]["series"][0]["count"] == 2

    @pytest.fixture
    def replica_set(self, metrics, model):
        from paddle_tpu.inference.frontend.replica import ReplicaSet
        eng = _engine(model, max_batch=4)
        rs = ReplicaSet([eng], poll_interval=0.01)
        # compile both programs before anything is measured
        rs.result(rs.submit(_prompts(seed=9, n=1)[0], max_new_tokens=3),
                  timeout=60)
        yield rs, eng
        rs.close()

    @staticmethod
    def _slow_steps(rs, eng, prompts, max_new_tokens):
        """Serve ``prompts`` with every step 30 ms longer on the host
        (``serving.slow_step``), from a zeroed registry until the loop has
        stopped; ``(loop_seconds_total by (span, device), span_seconds by
        span, engine.step's count)`` of the engine."""
        from paddle_tpu.testing.faults import FAULTS, Always
        time.sleep(0.05)
        with rs.replicas[0]._cv:
            # the loop waits inside replica.idle: no boundary while the
            # registry is zeroed
            obs.reset()
        FAULTS.install("serving.slow_step", Always(), delay=0.03)
        try:
            handles = [rs.submit(p, max_new_tokens=max_new_tokens)
                       for p in prompts]
            for h in handles:
                assert len(rs.result(h, timeout=60)[0]) == max_new_tokens
        finally:
            FAULTS.reset()
        rs.close()                      # the loop's last span has ended
        spans = {s["labels"]["span"]: s for s in
                 obs.snapshot(prefix="span_seconds")["span_seconds"]["series"]}
        return (_loop_seconds(eng._m.label),
                {name: s["sum"] for name, s in spans.items()},
                spans["engine.step"]["count"])

    def test_a_slow_host_step_is_time_the_device_sat_dry(self, replica_set):
        """The 30 ms a step sleeps lie under ``engine.step`` and no span of
        its own. Prompts of one chunk and one new token each: a step's one
        program is its chunk, whose sample the step reads, so the device
        has nothing queued when the next step sleeps."""
        rs, eng = replica_set
        loop, _, steps = self._slow_steps(
            rs, eng, _prompts(seed=21, n=6, shared=2, tail=4), 1)
        assert steps >= 6
        assert loop[("engine.step", "dry")] >= 0.8 * 0.03 * steps, (loop,
                                                                    steps)

    def test_loop_seconds_add_up_to_the_loops_own_spans(self, replica_set):
        """Every second of the loop between the registry's zeroing and its
        end is counted once: under the top-level span it lies in
        (``engine.step`` holds the engine's own spans) or under "none"."""
        rs, eng = replica_set
        loop, span_sum, _ = self._slow_steps(
            rs, eng, _prompts(seed=22, n=3, shared=10, tail=7), 8)
        top = ("replica.publish", "replica.lock", "replica.idle", "none")
        by_top = dict.fromkeys(("engine.step",) + top, 0.0)
        for (span, _), seconds in loop.items():
            by_top[span if span in top else "engine.step"] += seconds
        for name in ("engine.step",) + top[:3]:
            assert by_top[name] == pytest.approx(span_sum[name], rel=0.01)
        assert sum(loop.values()) == pytest.approx(
            sum(span_sum[n] for n in ("engine.step",) + top[:3])
            + by_top["none"], rel=0.01)
        # decode steps ran one ahead: the device was busy for part of it
        assert sum(v for (_, device), v in loop.items() if device == "busy") > 0
        assert {span for span, _ in loop} >= {"engine.step", "runner.dispatch",
                                              "runner.launch", "runner.wait"}
