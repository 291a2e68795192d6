"""The arithmetic of what the ``solar-open2-250b`` configuration adds to the
yardstick, against hand-worked counts: its model FLOPs, and what the two
new kernels' calls need.  (A file of its own beside ``test_yardstick.py``:
a PR that adds a configuration edits no file the benchmark already has.)
"""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench.readers import (model_flops, registry_ratio,  # noqa: E402
                           trace_kernel_roofline)
from bench.rooflines import kda_step, moe_gmm, solar_open2_flops  # noqa: E402


def load(*parts):
    with open(os.path.join(ROOT, "bench", *parts)) as f:
        return json.load(f)


SOLAR = load("configs", "solar-open2-250b.json")
PEAKS = load("peaks.json")["TPU v5 lite"]


def counters(**by_name):
    """A registry snapshot holding serving_moe_<name>_total{kind}."""
    return {f"serving_moe_{name}_total": {"series": [
        {"labels": {"engine": "0", "kind": kind}, "value": value}
        for kind, value in kinds.items()]} for name, kinds in by_name.items()}


def test_solar_flops_by_hand():
    assert solar_open2_flops.kinds(SOLAR) == (1, 3)
    # GQA: q, gate and o 4096 x 8192, k and v 4096 x 1024
    assert solar_open2_flops.gqa_params(SOLAR) == (
        3 * 4096 * 8192 + 2 * 4096 * 1024) == 109_051_904
    # KDA: q, k, v, o 4096 x 8192; two gates 4096 x 128 + 128 x 8192; beta
    # 4096 x 64; three convolutions of 4 taps over 8192 channels
    assert solar_open2_flops.kda_params(SOLAR) == (
        4 * 4096 * 8192 + 2 * (4096 * 128 + 128 * 8192) + 4096 * 64
        + 3 * 8192 * 4) == 137_723_904
    # router 4096 x 320 and the shared expert; one routed expert
    assert solar_open2_flops.expert_params(SOLAR) == (
        4096 * 320 + 3 * 4096 * 1280, 3 * 4096 * 1280)
    assert solar_open2_flops.delta_rule_flops(SOLAR) == 6 * 64 * 128 * 128
    span = {"kind": "decode", "rows": 2, "ctx_sum": 300}
    token = (109_051_904 + 3 * 137_723_904
             + 4 * (4096 * 320 + 3 * 4096 * 1280 + 1.5 * 3 * 4096 * 1280))
    want = (2 * token * 2 + 2 * 4096 * 24576 * 2      # the layers, the head
            + 300 * 4 * 64 * 128                      # attention, ONE layer
            + 6 * 64 * 128 * 128 * 3 * 2)             # the delta rule, three
    assert solar_open2_flops.span_flops(SOLAR, span, 1.5) == want
    registry = counters(rows={"decode": 800.0, "prefill": 0.0},
                        assignments={"decode": 1200.0})
    assert solar_open2_flops.assignments_a_row(registry, "decode") == 1.5
    assert solar_open2_flops.assignments_a_row(registry, "prefill") is None
    facts = {"config": SOLAR, "trace_window": [0.0, 3.0], "registry": registry,
             "trace": {"window_s": 3.0}, "peaks": PEAKS, "chips": 1,
             "spans": [dict(span, t0=1.0),
                       {"kind": "prefill", "rows": 128, "start": 0, "t0": 2.0}]}
    # the prefill span has no counters to read its experts from: left out
    assert model_flops.read({"flops": "solar_open2_flops"}, facts) == pytest.approx(
        100.0 * want / (3.0 * 197e12))
    # a program without the counters (the parent commit): nothing, no error
    assert model_flops.read({"flops": "solar_open2_flops"},
                            dict(facts, registry={})) is None


def test_kda_step_needs_by_hand():
    # a live row in one layer: the state [64, 128, 128] float32 read and
    # written; q, k, g, v, o of 64 x 128 and beta of 64, float32
    state = 64 * 128 * 128 * 4
    assert kda_step.row_needs(SOLAR) == (
        2 * state + (5 * 64 * 128 + 64) * 4, 6 * 64 * 128 * 128)
    facts = {"config": SOLAR, "trace_window": [0.0, 3.0], "spans": [
        {"kind": "decode", "rows": 64, "ctx_sum": 1, "t0": 1.0},
        {"kind": "decode", "rows": 32, "ctx_sum": 1, "t0": 2.0},
        {"kind": "prefill", "rows": 128, "start": 0, "t0": 2.5},
        {"kind": "decode", "rows": 64, "ctx_sum": 1, "t0": 9.0}]}   # outside
    need = kda_step.needed(facts, calls=6)      # two dispatches, three layers
    assert need["bytes"] == 6 * 48 * kda_step.row_needs(SOLAR)[0]
    # bound by bytes on a v5e
    assert need["bytes"] / 819e9 > need["flops"] / 197e12
    assert kda_step.needed(dict(facts, spans=[]), calls=6) is None
    # 64 live rows at the memory bound: 0.67 ms a layer call (0.655 of it
    # the states' 512 MiB)
    assert 64 * kda_step.row_needs(SOLAR)[0] / 819e9 == pytest.approx(
        0.668e-3, rel=0.01)


def test_moe_gmm_needs_by_hand():
    h, f = 4096, 1280
    b, fl = moe_gmm.layer_needs(SOLAR, assignments=64, touched=32)
    assert fl == 64 * 6 * h * f
    assert b == 32 * 3 * h * f * 2 + 64 * (2 * h * 2 + 2 * f * 4 + f * 2 + h * 4)
    registry = counters(calls={"decode": 400.0, "prefill": 8.0},
                        assignments={"decode": 400.0 * 64, "prefill": 8.0 * 128},
                        experts_touched={"decode": 400.0 * 32, "prefill": 8.0 * 39})
    assert moe_gmm.means(registry, "decode") == (64.0, 32.0)
    assert moe_gmm.means(registry, "prefill") == (128.0, 39.0)
    facts = {"config": SOLAR, "registry": registry, "trace_window": [0.0, 3.0],
             "peaks": PEAKS, "spans": [
                 {"kind": "decode", "rows": 64, "ctx_sum": 1, "t0": 0.5},
                 {"kind": "decode", "rows": 64, "ctx_sum": 1, "t0": 1.0},
                 {"kind": "decode", "rows": 64, "ctx_sum": 1, "t0": 1.5},
                 {"kind": "prefill", "rows": 128, "start": 0, "t0": 2.0}]}
    # 4 dispatches x 4 layers x 3 calls; a quarter of them a chunk's
    need = moe_gmm.needed(facts, calls=48)
    dec, pre = (moe_gmm.layer_needs(SOLAR, 64, 32),
                moe_gmm.layer_needs(SOLAR, 128, 39))
    assert need["bytes"] == pytest.approx(12 * dec[0] + 4 * pre[0])
    assert need["flops"] == pytest.approx(12 * dec[1] + 4 * pre[1])
    # touched experts cannot exceed the 40 held: a call's bytes are capped
    assert moe_gmm.layer_needs(SOLAR, 1024, 40)[0] < 40 * 3 * h * f * 2 * 1.05
    assert moe_gmm.needed(dict(facts, registry={}), calls=48) is None
    # through the reader: the share of a trace in which the kernel took
    # exactly the memory bound's time reads 100
    trace = {"ops": {"moe_gmm.17": [48, need["bytes"] / 819e9],
                     "kda_step.10": [3, 1.0]}}
    share = trace_kernel_roofline.read(
        {"pattern": r"^moe_gmm(\.\d+)?$", "roofline": "moe_gmm"},
        dict(facts, trace=trace))
    assert share == pytest.approx(100.0)


def test_touched_share_reads_percent_of_the_experts_held():
    spec = load("metrics", "moe_experts_touched_share.json")
    registry = counters(calls={"decode": 10.0, "prefill": 99.0},
                        experts_touched={"decode": 320.0, "prefill": 99.0})
    # 32 of 40 a call
    assert registry_ratio.read(spec["params"], {"registry": registry}) == 80.0
