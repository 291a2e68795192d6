"""The benchmark's own arithmetic: trace reduction, rooflines and model
FLOPs against hand-worked counts, the traffic generator, the harness's
refusals.  Run with ``JAX_PLATFORMS=cpu python -m pytest bench/tests``.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import trace_reduce  # noqa: E402
from bench.readers import (model_flops, registry_ratio,  # noqa: E402
                           trace_kernel_roofline, trace_module_time)
from bench.rooflines import (flash_attention, gpt2_flops,  # noqa: E402
                             mistral_flops, paged_attention)
from bench.traffic import open_loop_http as olh  # noqa: E402
from bench.traffic import train_batches  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def load(*parts):
    with open(os.path.join(ROOT, "bench", *parts)) as f:
        return json.load(f)


MISTRAL = load("configs", "mistral-7b-v0.3.json")
GPT2 = load("configs", "gpt2-124m.json")
PEAKS = load("peaks.json")["TPU v5 lite"]


# ---------------------------------------------------------- trace reduction

class _Ev:
    def __init__(self, name, start, dur):
        self.name, self.start_ns, self.duration_ns = name, start, dur
        self.stats = []


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, lines):
        self.lines = lines


def test_reduce_plane_by_hand():
    """Two runs of one program with a kernel inside a layer loop, then a run
    of another: union, per-module and per-op sums, and the gap between."""
    ms = 1_000_000
    plane = _Plane([
        _Line("XLA Modules", [_Ev("jit_block(123)", 0, 10 * ms),
                              _Ev("jit_block(123)", 12 * ms, 10 * ms),
                              _Ev("jit_prefill(9)", 30 * ms, 5 * ms)]),
        _Line("XLA Ops", [
            _Ev("%while.5 = (s32[]) while(...)", 0, 9 * ms),
            _Ev("%paged_attention.7 = bf16[16,32,128] custom-call(...)", 1 * ms, 2 * ms),
            _Ev("%fusion.161 = bf16[32,14336] fusion(...)", 3 * ms, 4 * ms),
            _Ev("%paged_attention.7 = bf16[16,32,128] custom-call(...)", 13 * ms, 2 * ms),
            _Ev("%copy.78 = bf16[12,2049] copy(...)", 30 * ms, 5 * ms)]),
    ])
    got = trace_reduce.reduce_plane(plane)
    # 0-9 (the loop spans its children), 13-15, 30-35
    assert got["busy_s"] == pytest.approx(0.016)
    assert got["modules"]["jit_block"] == [2, pytest.approx(0.020)]
    assert got["ops"]["paged_attention.7"] == [2, pytest.approx(0.004)]
    assert got["gaps"] == {"jit_block -> jit_block": pytest.approx(0.002),
                           "jit_block -> jit_prefill": pytest.approx(0.008)}
    out = trace_reduce.summarize({"/device:TPU:0": got}, window_s=0.040)
    names = [n for n, _ in out["breakdown"]["device_ops"]]
    assert "while.5" not in names and names[0] == "copy.78"
    facts = {"trace": out}
    assert trace_module_time.read({"pattern": "^jit_block$"}, facts) == pytest.approx(10.0)
    assert trace_module_time.read({"pattern": "^jit_verify$"}, facts) is None
    assert trace_kernel_roofline.kernel_seconds(
        out, r"^paged_attention(\.\d+)?$") == (2, pytest.approx(0.004))


def test_recorded_trace():
    """A small trace recorded on a TPU v5e (``record_trace.py``): a jitted
    layer loop around the paged-attention kernel, run 5 times."""
    path = os.path.join(DATA, "small_trace.xplane.pb")
    with open(os.path.join(DATA, "small_trace.json")) as f:
        want = json.load(f)
    got = trace_reduce.reduce_trace(path, want["window_s"])
    assert got["devices"] == 1
    assert 0 < got["busy_s"] <= got["window_s"]
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    mod = got["modules"][want["module"]]
    assert mod[0] == want["module_runs"]
    count, seconds = trace_kernel_roofline.kernel_seconds(
        got, r"^paged_attention(\.\d+)?$")
    assert count == want["kernel_calls"]
    assert seconds == pytest.approx(want["kernel_s"], rel=1e-9)
    assert seconds < mod[1]


# ------------------------------------------------ rooflines and model FLOPs

def test_paged_attention_needs_by_hand():
    # a token of K and V: 2 arrays x 8 KV heads x 128 x 2 bytes = 4096 bytes;
    # q and o of a row: 2 x 32 heads x 128 x 2 bytes = 16384 bytes
    kv, qo, fl = paged_attention.row_costs(MISTRAL)
    assert (kv, qo, fl) == (4096, 16384, 4 * 32 * 128)
    decode = {"kind": "decode", "rows": 16, "ctx_sum": 16 * 500}
    b, f = paged_attention.dispatch_needs(MISTRAL, decode)
    assert b == 8000 * 4096 + 16 * 16384
    assert f == 8000 * 16384
    # a prefill chunk: 32 rows of ONE sequence at 320..351: K and V once
    prefill = {"kind": "prefill", "rows": 32, "start": 320}
    b, f = paged_attention.dispatch_needs(MISTRAL, prefill)
    assert b == 352 * 4096 + 32 * 16384
    assert f == sum(320 + i + 1 for i in range(32)) * 16384
    facts = {"config": MISTRAL, "trace_window": [10.0, 13.0],
             "spans": [dict(decode, t0=11.0), dict(decode, t0=20.0)]}
    need = paged_attention.needed(facts, calls=12)
    assert need["bytes"] == 12 * (8000 * 4096 + 16 * 16384)


def test_mistral_flops_by_hand():
    # a layer: q 4096x4096, k and v 4096x1024, o 4096x4096, three 4096x14336
    assert mistral_flops.layer_params(MISTRAL) == (
        2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336) == 218_103_808
    assert mistral_flops.head_params(MISTRAL) == 4096 * 32768
    span = {"kind": "decode", "rows": 1, "ctx_sum": 100}
    want = 2 * 218_103_808 * 12 + 2 * 4096 * 32768 + 100 * 16384 * 12
    assert mistral_flops.span_flops(MISTRAL, span) == want
    facts = {"config": MISTRAL, "trace_window": [0.0, 3.0],
             "trace": {"window_s": 3.0}, "spans": [dict(span, t0=1.0)],
             "peaks": PEAKS, "chips": 1}
    assert model_flops.read({"flops": "mistral_flops"}, facts) == pytest.approx(
        100.0 * want / (3.0 * 197e12))
    assert model_flops.read({"flops": "mistral_flops"}, dict(facts, spans=[])) is None


def test_gpt2_flops_by_hand():
    # 124,439,808 parameters at the published vocabulary
    assert gpt2_flops.params(GPT2) == (
        50257 * 768 + 1024 * 768 + 12 * (12 * 768 * 768 + 13 * 768) + 2 * 768
    ) == 124_439_808
    per_token = gpt2_flops.flops_per_token(GPT2, 1024)
    assert per_token == 6 * (124_439_808 - 1024 * 768) + 6 * 12 * 1024 * 768
    facts = {"config": GPT2, "peaks": PEAKS, "chips": 1,
             "train": {"seq": 1024, "tokens": 1_000_000, "elapsed_s": 10.0}}
    assert model_flops.read({"flops": "gpt2_flops"}, facts) == pytest.approx(
        100.0 * per_token * 1e5 / 197e12)


def test_flash_attention_needs_by_hand():
    # one head: forward 4 s^2 d, backward 8 s^2 d, both halved by the mask
    b, s, h, d, layers = 16, 1024, 12, 64, 12
    assert flash_attention.step_flops(GPT2, b, s) == (
        6 * s * s * d) * b * h * layers
    facts = {"config": GPT2, "train": {"batch": b, "seq": s}}
    need = flash_attention.needed(facts, calls=3 * layers * 2)   # two steps
    assert need["flops"] == 2 * flash_attention.step_flops(GPT2, b, s)
    # compute-bound on a v5e: operations / peak is the larger time
    assert need["flops"] / 197e12 > need["bytes"] / 819e9


def test_registry_ratio_reads_every_engine():
    registry = {
        "serving_dispatches_total": {"series": [
            {"labels": {"engine": "0", "kind": "decode"}, "value": 100},
            {"labels": {"engine": "1", "kind": "decode"}, "value": 100},
            {"labels": {"engine": "0", "kind": "prefill"}, "value": 50}]},
        "serving_generated_tokens_total": {"series": [
            {"labels": {"engine": "0"}, "value": 900},
            {"labels": {"engine": "1"}, "value": 740}]},
        "serving_ttft_seconds": {"series": [
            {"labels": {"engine": "0"}, "count": 30, "sum": 1.0},
            {"labels": {"engine": "1"}, "count": 10, "sum": 1.0}]},
    }
    facts = {"registry": registry, "engine": {"max_batch": 16}}
    occ = load("metrics", "batch_occupancy.itl.json")["params"]
    # (1640 tokens - 40 first tokens, which prefill emits) / (200 x 16)
    assert registry_ratio.read(occ, facts) == pytest.approx(100 * 1600 / 3200)
    share = load("metrics", "prefill_step_share.tput.json")["params"]
    assert registry_ratio.read(share, facts) == pytest.approx(100 * 50 / 250)
    assert registry_ratio.read(occ, {"registry": {}, "engine": {"max_batch": 16}}) is None


# ------------------------------------------------------- traffic generators

STEADY = load("workloads", "mistral7b-chat-steady.json")


def _params():
    return dict(STEADY["traffic"], vocab_size=MISTRAL["vocab_size"])


def test_schedule_reproducible_and_same_work_for_every_seed():
    p = _params()
    a = olh.make_schedule(p, 3_000_000_001, 50.0)
    b = olh.make_schedule(p, 3_000_000_001, 50.0)
    c = olh.make_schedule(p, 17, 50.0)
    assert a == b
    assert [r["prompt"] for r in a] != [r["prompt"] for r in c]
    # the same multiset of lengths whatever the seed; arrivals inside the window
    for key in ("max_tokens",):
        assert sorted(r[key] for r in a) == sorted(r[key] for r in c)
    assert sorted(len(r["prompt"]) for r in a) == sorted(len(r["prompt"]) for r in c)
    assert abs(len(a) - p["rate"] * 50.0) <= 1
    assert all(0 <= r["due"] < 50.0 for r in a)
    assert [r["due"] for r in a] == sorted(r["due"] for r in a)
    lens = np.array([len(r["prompt"]) for r in a])
    outs = np.array([r["max_tokens"] for r in a])
    assert lens.min() >= 64 and lens.max() <= 1024
    assert outs.min() >= 16 and outs.max() <= 256
    assert abs(np.median(lens) - 320) < 30 and abs(np.median(outs) - 96) < 10
    ids = np.concatenate([r["prompt"] for r in a])
    assert ids.min() >= 1 and ids.max() < MISTRAL["vocab_size"]


def test_strata_put_one_of_each_slice_into_every_block():
    p = dict(_params(), rate=2.5, order={"strata": 8})
    a = olh.make_schedule(p, 2**31 + 5, 50.0)
    plain = olh.make_schedule(dict(p, order={}), 2**31 + 5, 50.0)
    assert a == olh.make_schedule(p, 2**31 + 5, 50.0)
    # the same multiset as a plain shuffle gives, in another order
    for of in (lambda r: len(r["prompt"]), lambda r: r["max_tokens"]):
        assert sorted(map(of, a)) == sorted(map(of, plain))
        assert list(map(of, a)) != list(map(of, plain))
        # every 8 consecutive requests: one from each eighth of the sorted values
        slices = np.array_split(np.sort([of(r) for r in a]), 8)
        for j in range(len(a) // 8):
            block = sorted(of(r) for r in a[8 * j:8 * j + 8])
            assert all(s[0] <= v <= s[-1] for v, s in zip(block, slices))
    values = np.arange(21)
    out = olh.seeded_order(values, np.random.default_rng(0), 4)
    assert sorted(out) == list(values) and len(out) == 21


def test_times_are_taken_from_due():
    schedule = [{"i": 0, "due": 1.0, "prompt": [1], "max_tokens": 2},
                {"i": 1, "due": 2.0, "prompt": [1], "max_tokens": 2},
                {"i": 2, "due": 3.0, "prompt": [1], "max_tokens": 2}]
    records = [
        # left 0.5 s late: the wait counts
        {"i": 0, "due": 1.0, "sent": 1.5, "status": "finished",
         "tokens": [7, 8], "times": [2.0, 2.25]},
        # shed: no token, counts as the worst
        {"i": 1, "due": 2.0, "sent": 2.0, "status": None, "tokens": [], "times": []},
    ]
    s = olh.summarize(records, schedule, seconds=4.0)
    assert s["attempted"] == 3 and s["failed"] == 2      # one shed, one never sent
    assert s["ttft_s"][0] == pytest.approx(1.0)          # 2.0 - due, not - sent
    assert s["ttft_s"][1] == pytest.approx(2.0)          # gave up at 4.0
    assert s["ttft_s"][2] == pytest.approx(1.0)
    assert s["lateness_s"] == [pytest.approx(0.5), 0.0]
    assert s["gaps_s"] == [pytest.approx(0.25)]
    assert s["tokens_in_window"] == 2
    assert olh.percentile([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 90) == 9


def test_a_preroll_request_counts_by_its_tokens_inside_the_window():
    schedule = [{"i": 0, "due": -2.0, "prompt": [1], "max_tokens": 4},
                {"i": 1, "due": 1.0, "prompt": [1], "max_tokens": 2}]
    records = [
        {"i": 0, "due": -2.0, "sent": -2.0, "status": "finished",
         "tokens": [7, 8, 9, 10], "times": [-1.0, -0.5, 0.5, 1.0]},
        {"i": 1, "due": 1.0, "sent": 1.0, "status": "cancelled_at_close",
         "tokens": [5], "times": [3.5]},
    ]
    s = olh.summarize(records, schedule, seconds=4.0)
    assert s["attempted"] == 1 and s["failed"] == 0
    assert s["tokens_in_window"] == 3                    # two before the window
    assert s["ttft_s"] == [pytest.approx(2.5)] and s["gaps_s"] == []
    assert s["lateness_s"] == [0.0]


def test_worst_leaf_gap_floor_and_nan():
    want = {"a": 1.0, "b": 1e-9, "c": 2.0}
    got = {"a": 1.1, "b": 0.5, "c": 2.0}
    # b's own norm is all but zero: measured against the median leaf's (1.0)
    assert train_batches.worst_leaf_gap(got, want) == (pytest.approx(0.5), "b")
    assert train_batches.worst_leaf_gap(got, want, skip=["b"])[1] == "a"
    nan = train_batches.worst_leaf_gap(dict(got, c=float("nan")), want)
    assert nan[1] == "c" and np.isnan(nan[0])


# ---------------------------------------------------- the harness's refusals

def _run(*extra, env=None):
    e = dict(os.environ, JAX_PLATFORMS="cpu")
    e.update(env or {})
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"), "--workload",
         "mistral7b-chat-steady", "--seed", "1", "--seconds", "1", *extra],
        capture_output=True, text=True, env=e, timeout=300)


def test_refuses_a_platform_that_is_not_a_tpu():
    r = _run()
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "no TPU" in r.stderr.strip().splitlines()[-1]


def test_refuses_a_device_kind_without_peaks(monkeypatch):
    from bench import run as bench_run

    class Dev:
        platform, device_kind = "tpu", "TPU v9 imaginary"

    import jax
    monkeypatch.setattr(jax, "devices", lambda: [Dev()])
    ctx = bench_run.Context(
        type("A", (), {"rehearsal": False})(), {"chips": 1}, {}, {})
    with pytest.raises(SystemExit, match="not in bench/peaks.json"):
        ctx.find_devices()


def test_is_correct_fails_a_nan_and_a_count():
    from bench.run import is_correct
    assert is_correct([("gap", 0.1, 0.2), ("count", 0, 0)])
    assert not is_correct([("gap", float("nan"), 0.2)])
    assert not is_correct([("gap", 0.3, 0.2)])
    assert not is_correct([("count", 1, 0)])
    assert not is_correct([("finished_requests", 0, ">=1")])


def test_refuses_a_directory_that_holds_only_the_benchmark(tmp_path):
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "mistral7b-chat-steady",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=tmp_path, timeout=300,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert r.returncode != 0 and r.stdout.strip() == ""


# ------------------------------------------- every name finds its own file

BENCHMARK = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


@pytest.mark.parametrize("metric", [m["name"] for m in BENCHMARK["per_layer"]])
def test_per_layer_metric_has_a_file_and_a_reader(metric):
    import importlib
    spec = load("metrics", metric + ".json")
    # unit, layer, source, moves and which cells report it are BENCHMARK.json's
    # alone to say: each fact stands in one place, and a later PR adds its
    # cell there and may not edit the metric's file
    assert set(spec) <= {"reader", "params"}
    reader = importlib.import_module(f"bench.readers.{spec['reader']}")
    # a reader that finds nothing to read returns nothing, never 0
    assert reader.read(spec.get("params", {}), {}) is None


@pytest.mark.parametrize("cell", [w["name"] for w in BENCHMARK["workloads"]])
def test_cell_has_a_file_a_generator_and_limits(cell):
    import importlib
    entry = next(w for w in BENCHMARK["workloads"] if w["name"] == cell)
    spec = load("workloads", cell + ".json")
    assert (spec["name"], spec["config"], spec["chips"], spec["why"]) == (
        entry["name"], entry["config"], entry["chips"], entry["why"])
    # source, reduced and assumed are the configuration's, stated there once
    assert not {"source", "reduced", "assumed"} & set(spec)
    assert spec["limits"] and spec["traffic"]["mix"]
    load("rehearsal", spec["rehearsal"]["like"] + ".json")
    cfg = load("configs", spec["config"] + ".json")
    listed = next(c for c in BENCHMARK["configs"] if c["name"] == spec["config"])
    assert cfg["reduced"] == listed["reduced"] and cfg["source"] == listed["source"]
    for kind, name in (("traffic", spec["generator"]), ("builders", cfg["builder"]),
                       ("reference", cfg["reference"])):
        importlib.import_module(f"bench.{kind}.{name}")
    if spec["generator"] == "open_loop_http":
        t = spec["traffic"]
        assert t["rate"] == pytest.approx(t["knee"] * round(t["rate"] / t["knee"], 2))
