"""The arithmetic of what the ``zaya1-8b`` configuration adds to the
yardstick, against hand-worked counts: its model FLOPs (``zaya_flops``,
which reads the skip counter) and the two new data files,
``serve_mfu.zaya`` and ``paged_attention_roofline.docs``.  (A file of its
own beside ``test_yardstick.py``: a PR that adds a configuration edits no
file the benchmark already has.)
"""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench.readers import model_flops, trace_kernel_roofline  # noqa: E402
from bench.rooflines import paged_attention, zaya_flops  # noqa: E402


def load(*parts):
    with open(os.path.join(ROOT, "bench", *parts)) as f:
        return json.load(f)


ZAYA = load("configs", "zaya1-8b.json")
PEAKS = load("peaks.json")["TPU v5 lite"]


def counters(**by_name):
    """A registry snapshot holding serving_moe_<name>_total{kind}."""
    return {f"serving_moe_{name}_total": {"series": [
        {"labels": {"engine": "0", "kind": kind}, "value": value}
        for kind, value in kinds.items()]} for name, kinds in by_name.items()}


def test_zaya_flops_by_hand():
    # CCA: q 2048 x 1024, k 2048 x 256, the two value halves 2048 x 128, o
    # 1024 x 2048; taps 2 x 1280 and 2 x 10 x 128 x 128. Router: 2048 x
    # 256, two 256 x 256, 256 x 17. One expert: three 2048 x 2048
    cca = (2048 * 1024 + 2048 * 256 + 2 * 2048 * 128 + 1024 * 2048
           + 2 * 1280 + 2 * 10 * 128 * 128)
    router = 2048 * 256 + 2 * 256 * 256 + 256 * 17
    assert zaya_flops.layer_params(ZAYA) == (cca + router, 3 * 2048 * 2048)
    assert (cca, router) == (5_573_120, 659_712)
    registry = counters(rows={"decode": 1700.0, "prefill": 100.0},
                        skipped={"decode": 100.0})
    assert zaya_flops.routed_a_row(registry, "decode") == 1600.0 / 1700.0
    assert zaya_flops.routed_a_row(registry, "prefill") == 1.0
    # a program without the skip counter (the parent commit): nothing
    assert zaya_flops.routed_a_row(counters(rows={"decode": 9.0}),
                                   "decode") is None
    dec = {"kind": "decode", "rows": 2, "ctx_sum": 300}
    token = cca + router + 0.5 * 3 * 2048 * 2048
    want = (2 * 10 * token * 2 + 2 * 2048 * 262272 * 2     # layers, the head
            + 10 * 300 * 4 * 8 * 128)                      # attention
    assert zaya_flops.span_flops(ZAYA, dec, 0.5) == want
    pre = {"kind": "prefill", "rows": 4, "start": 10}
    # no head; rows see 11, 12, 13, 14 tokens
    assert zaya_flops.span_flops(ZAYA, pre, 1.0) == (
        2 * 10 * (cca + router + 3 * 2048 * 2048) * 4
        + 10 * (11 + 12 + 13 + 14) * 4 * 8 * 128)
    facts = {"config": ZAYA, "trace_window": [0.0, 3.0], "registry": registry,
             "trace": {"window_s": 3.0}, "peaks": PEAKS, "chips": 1,
             "spans": [dict(dec, t0=1.0), dict(pre, t0=2.0),
                       dict(dec, t0=5.0)]}              # outside the window
    spec = load("metrics", "serve_mfu.zaya.json")
    got = model_flops.read(spec["params"], facts)
    assert got == pytest.approx(100.0 * (
        zaya_flops.span_flops(ZAYA, dec, 1600.0 / 1700.0)
        + zaya_flops.span_flops(ZAYA, pre, 1.0)) / (3.0 * 197e12))
    assert model_flops.read(spec["params"], dict(facts, registry={})) is None


def test_paged_attention_docs_reads_the_configs_heads():
    """The cell's file reads ``paged_attention.py`` as it is, with this
    configuration's 8 q heads and 2 KV heads of 128: 1 KiB of K and V a
    token and layer."""
    spec = load("metrics", "paged_attention_roofline.docs.json")
    assert spec["params"]["roofline"] == "paged_attention"
    kv, qo, fl = paged_attention.row_costs(ZAYA)
    assert (kv, qo, fl) == (2 * 2 * 128 * 2, 2 * 8 * 128 * 2, 4 * 8 * 128)
    spans = [{"kind": "decode", "rows": 64, "ctx_sum": 64 * 3000, "t0": 1.0},
             {"kind": "prefill", "rows": 256, "start": 2048, "t0": 2.0}]
    facts = {"config": ZAYA, "trace_window": [0.0, 3.0], "spans": spans,
             "peaks": PEAKS}
    need = paged_attention.needed(facts, calls=20)
    dec_b = 64 * 3000 * kv + 64 * qo
    pre_b = (2048 + 256) * kv + 256 * qo
    assert need["bytes"] == 10 * (dec_b + pre_b)
    # a decode step of 64 rows at 3,000 tokens: 1.9 GB over ten layers
    assert 10 * dec_b / 819e9 == pytest.approx(2.4e-3, rel=0.02)
    trace = {"ops": {"paged_attention.4": [20, need["bytes"] / 819e9]}}
    share = trace_kernel_roofline.read(spec["params"], dict(facts, trace=trace))
    assert share == pytest.approx(100.0)
