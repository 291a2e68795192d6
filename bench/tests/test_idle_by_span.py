"""``bench/tools/idle_by_span.py``: device idle gaps, named by the host span
open during them.  The attribution is held to hand-made planes; the reading
of the host plane to a trace recorded here, on the CPU, with the profiler
options a run uses (``bench/tests/record_trace.py`` records the device's
side of the yardstick on the chip the same way)."""
import glob
import os
import time
from types import SimpleNamespace as NS

import pytest

from bench.tools import idle_by_span as tool
from bench.trace_reduce import reduce_plane

MS = 1_000_000


def ev(name, start_ms, dur_ms):
    return NS(name=name, start_ns=start_ms * MS, duration_ns=dur_ms * MS)


def device_plane():
    # three programs; idle 10..14 and 20..30
    return NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_block(1)", 0, 10),
                                       ev("jit_prefill(2)", 14, 6),
                                       ev("jit_block(1)", 30, 5)]),
        NS(name="XLA Ops", events=[ev("%fusion.1 = f32[] fusion()", 0, 10)])])


def host_plane():
    return NS(name="/host:CPU", lines=[
        NS(name="python", events=[            # the step loop
            ev("engine.step", 9, 12),         # 9..21
            ev("engine.emit", 10, 2),         # 10..12
            ev("engine.prepare", 12, 1),      # 12..13; 13..14 the step's own
            ev("runner.wait", 15, 5),
            ev("replica.publish", 21, 3),     # 21..24
            ev("replica.idle", 26, 10)]),     # 26..36; 24..26 nobody's
        NS(name="python", events=[ev("handler", 0, 40)])])   # not a loop's


def test_gaps_are_the_ones_trace_reduce_sums():
    gaps = tool.device_gaps(device_plane())
    assert gaps == [(10 * MS, 14 * MS), (20 * MS, 30 * MS)]
    assert sum(e - s for s, e in gaps) * 1e-9 == pytest.approx(
        sum(reduce_plane(device_plane())["gaps"].values()))
    assert tool.device_gaps(NS(name="/device:TPU:1", lines=[])) == []


def test_each_piece_of_a_gap_goes_to_the_innermost_open_span():
    spans = tool.loop_spans(host_plane())
    assert "handler" not in {n for _, _, n in spans}
    by_span, uncovered = tool.attribute(tool.device_gaps(device_plane()), spans)
    assert by_span == pytest.approx({
        "engine.emit": 0.002, "engine.prepare": 0.001,
        "engine.step": 0.001 + 0.001,         # 13..14 and 20..21
        "replica.publish": 0.003, "replica.idle": 0.004})
    assert uncovered == pytest.approx(0.002)  # 24..26
    assert sum(by_span.values()) + uncovered == pytest.approx(0.014)


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    """A tiny engine behind a replica, traced on the CPU as a run traces."""
    import jax
    import paddle_tpu as pt
    from paddle_tpu import observability as obs
    from paddle_tpu.inference.frontend.replica import EngineReplica
    from paddle_tpu.inference.serving import LLMEngine
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    pt.seed(0)
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=176,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128))
    model.eval()
    rep = EngineReplica("r0", LLMEngine(model, max_batch=2, max_len=64,
                                        page_size=8, prefill_chunk=8),
                        poll_interval=0.01).start()

    def serve(n):
        rid = rep.submit(list(range(1, 14)), max_new_tokens=n)
        while not rep.poll(rid, timeout=60.0)[1].terminal:
            pass
    out = str(tmp_path_factory.mktemp("trace"))
    obs.enable()
    try:
        serve(2)                                  # compiles
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(out, profiler_options=opts)
        time.sleep(0.03)
        serve(6)
        time.sleep(0.03)
        jax.profiler.stop_trace()
    finally:
        obs.disable()
        obs.reset()
        rep.close()
    return glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)[0]


def test_host_plane_of_a_recorded_trace(cpu_trace):
    from jax.profiler import ProfileData
    host = [p for p in ProfileData.from_file(cpu_trace).planes
            if p.name.startswith("/host:CPU")][0]
    spans = tool.loop_spans(host)
    names = {n for _, _, n in spans}
    assert names >= {"engine.step", "engine.admit", "engine.prepare",
                     "runner.dispatch", "runner.launch", "runner.wait",
                     "engine.emit",
                     "replica.publish", "replica.lock", "replica.idle"}
    # the intervals of one leaf, taken as gaps, all come back under its name:
    # the leaf, not the engine.step around it, is the innermost
    gaps = [(s, e) for s, e, n in spans if n == "engine.prepare"]
    by_span, uncovered = tool.attribute(gaps, spans)
    assert set(by_span) == {"engine.prepare"} and uncovered == 0.0
    assert by_span["engine.prepare"] == pytest.approx(
        sum(e - s for s, e in gaps) * 1e-9)


def test_a_trace_with_no_device_plane_reports_no_gaps(cpu_trace, capsys):
    assert tool.main([cpu_trace]) == 0
    import json
    out = json.loads(capsys.readouterr().out)
    assert out["gaps_s"] == 0 and out["by_span"] == [] and out["gaps_read"] == 0
    assert out["spans_read"] > 20 and out["uncovered_share"] == 0.0
