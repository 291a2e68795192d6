"""The arithmetic of what the ``sdar-30b-a3b-chat`` configuration adds to
the yardstick, against hand-worked counts: its model FLOPs, and what the
two kernels' calls need where a decode dispatch is a block of 4 positions in
5 forwards.  (A file of its own beside ``test_yardstick.py``: a PR that adds
a configuration edits no file the benchmark already has.)
"""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench.readers import (model_flops, registry_ratio,  # noqa: E402
                           trace_kernel_roofline)
from bench.rooflines import (moe_gmm, moe_gmm_blocks,  # noqa: E402
                             paged_attention_blocks, sdar_flops)


def load(*parts):
    with open(os.path.join(ROOT, "bench", *parts)) as f:
        return json.load(f)


SDAR = load("configs", "sdar-30b-a3b-chat.json")
PEAKS = load("peaks.json")["TPU v5 lite"]
H, F, V, L = 2048, 768, 151936, 6


def counters(**by_name):
    """A registry snapshot holding serving_moe_<name>_total{kind}."""
    return {f"serving_moe_{name}_total": {"series": [
        {"labels": {"engine": "0", "kind": kind}, "value": value}
        for kind, value in kinds.items()]} for name, kinds in by_name.items()}


def test_sdar_flops_by_hand():
    assert sdar_flops.forwards(SDAR) == (4, 5)
    # q and o 2048 x 4096, k and v 2048 x 512, the router 2048 x 128; an expert
    assert sdar_flops.layer_params(SDAR) == (
        2 * H * 4096 + 2 * H * 512 + H * 128, 3 * H * F) == (19_136_512, 4_718_592)
    # two live sequences with 100 and 200 tokens committed: the spy says
    # ctx_sum = 101 + 201; each of a block's 4 rows sees lens + 4 tokens
    span = {"kind": "decode", "rows": 2, "ctx_sum": 302}
    assert sdar_flops.attended(SDAR, span) == 4 * (104 + 204)
    token = L * (19_136_512 + 8 * 4_718_592)
    want = (5 * (2 * token * 8 + 4 * 32 * 128 * L * 4 * 308)   # 5 forwards
            + 4 * 2 * H * V * 8)                                # 4 with the head
    assert sdar_flops.span_flops(SDAR, span, 8.0) == want
    # a chunk of 8 rows from position 16: rows 16-19 see 20, rows 20-23 see 24
    chunk = {"kind": "prefill", "rows": 8, "start": 16}
    assert sdar_flops.attended(SDAR, chunk) == 4 * 20 + 4 * 24
    assert sdar_flops.span_flops(SDAR, chunk, 8.0) == (
        2 * token * 8 + 4 * 32 * 128 * L * 176)
    registry = counters(rows={"decode": 800.0, "prefill": 0.0},
                        assignments={"decode": 6400.0})
    facts = {"config": SDAR, "trace_window": [0.0, 3.0], "registry": registry,
             "trace": {"window_s": 3.0}, "peaks": PEAKS, "chips": 1,
             "spans": [dict(span, t0=1.0), dict(chunk, t0=2.0)]}
    # the prefill span has no counters to read its experts from: left out
    assert model_flops.read({"flops": "sdar_flops"}, facts) == pytest.approx(
        100.0 * want / (3.0 * 197e12))
    # a program without the counters (the parent commit): nothing, no error
    assert model_flops.read({"flops": "sdar_flops"},
                            dict(facts, registry={})) is None
    # the cell at full slots: 64 sequences, 5 forwards of 256 rows, in 70 ms
    full = sdar_flops.span_flops(SDAR, {"kind": "decode", "rows": 64,
                                        "ctx_sum": 64 * 1500}, 8.0)
    assert 100.0 * full / (0.070 * 197e12) == pytest.approx(12.3, abs=0.3)


def test_moe_gmm_blocks_shares_the_calls_out_by_forwards():
    registry = counters(calls={"decode": 3000.0, "prefill": 60.0},
                        rows={"decode": 3000.0 * 256, "prefill": 60.0 * 128},
                        assignments={"decode": 3000.0 * 2048, "prefill": 60.0 * 1024},
                        experts_touched={"decode": 3000.0 * 128, "prefill": 60.0 * 126})
    assert moe_gmm.means(registry, "decode") == (2048.0, 128.0)
    assert moe_gmm_blocks.rows_a_call(registry, "decode") == 256.0
    facts = {"config": SDAR, "registry": registry, "trace_window": [0.0, 3.0],
             "peaks": PEAKS, "spans": [
                 {"kind": "decode", "rows": 64, "ctx_sum": 1, "t0": 0.5},
                 {"kind": "decode", "rows": 64, "ctx_sum": 1, "t0": 1.0},
                 {"kind": "prefill", "rows": 128, "start": 0, "t0": 2.0},
                 {"kind": "decode", "rows": 64, "ctx_sum": 1, "t0": 9.0}]}  # outside
    # 2 block dispatches x 5 forwards + 1 chunk = 11 forwards x 6 layers x 3
    need = moe_gmm_blocks.needed(facts, calls=11 * 6 * 3)
    dec = moe_gmm.layer_needs(SDAR, 2048, 128)
    pre = moe_gmm.layer_needs(SDAR, 1024, 126)
    assert need["bytes"] == pytest.approx(60 * dec[0] + 6 * pre[0])
    assert need["flops"] == pytest.approx(60 * dec[1] + 6 * pre[1])
    # all 128 experts of a layer once, three matrices: 1.21 GB a layer call
    assert dec[0] == 128 * 3 * H * F * 2 + 2048 * (2 * H * 2 + 2 * F * 4 + F * 2 + H * 4)
    assert 128 * 3 * H * F * 2 == 1_207_959_552
    # a dispatch of half the mean's rows counts half its assignments and
    # half its touched experts; one of more rows counts the mean's, no more
    half = dict(facts, spans=[{"kind": "decode", "rows": 32, "ctx_sum": 1, "t0": 1.0}])
    assert moe_gmm_blocks.needed(half, calls=90)["bytes"] == pytest.approx(
        30 * moe_gmm.layer_needs(SDAR, 1024, 64)[0])
    fuller = dict(facts, registry=counters(
        calls={"decode": 10.0}, rows={"decode": 10.0 * 128},
        assignments={"decode": 10.0 * 1024}, experts_touched={"decode": 10.0 * 100}),
        spans=[{"kind": "decode", "rows": 64, "ctx_sum": 1, "t0": 1.0}])
    assert moe_gmm_blocks.needed(fuller, calls=90)["bytes"] == pytest.approx(
        30 * moe_gmm.layer_needs(SDAR, 1024, 100)[0])
    assert moe_gmm_blocks.needed(dict(facts, registry={}), calls=198) is None
    # a configuration that does not generate by blocks: nothing
    plain = {k: v for k, v in SDAR.items() if k != "generation"}
    assert moe_gmm_blocks.needed(dict(facts, config=plain), calls=198) is None
    # through the reader: a trace in which the kernel took exactly the
    # memory bound's time reads 100
    trace = {"ops": {"moe_gmm.17": [198, need["bytes"] / 819e9]}}
    share = trace_kernel_roofline.read(
        {"pattern": r"^moe_gmm(\.\d+)?$", "roofline": "moe_gmm_blocks"},
        dict(facts, trace=trace))
    assert share == pytest.approx(100.0)


def test_paged_attention_blocks_needs_by_hand():
    kv = 2 * 4 * 128 * 2        # K and V of a token: 2 KiB
    qo = 2 * 32 * 128 * 2       # q and o of a row
    span = {"kind": "decode", "rows": 2, "ctx_sum": 302}
    b, f = paged_attention_blocks.dispatch_needs(SDAR, span)
    # each forward: both sequences' 104 + 204 tokens once, 8 rows of q and o
    assert b == 5 * (308 * kv + 8 * qo)
    assert f == 5 * 4 * 308 * 4 * 32 * 128
    chunk = {"kind": "prefill", "rows": 8, "start": 16}
    assert paged_attention_blocks.dispatch_needs(SDAR, chunk) == (
        24 * kv + 8 * qo, 176 * 4 * 32 * 128)
    facts = {"config": SDAR, "trace_window": [0.0, 3.0],
             "spans": [dict(span, t0=1.0), dict(chunk, t0=2.0),
                       dict(span, t0=5.0)]}                     # outside
    need = paged_attention_blocks.needed(facts, calls=36)
    assert need["bytes"] == L * (b + 24 * kv + 8 * qo)
    assert need["bytes"] / 819e9 > need["flops"] / 197e12      # bytes bound it
    assert paged_attention_blocks.needed(dict(facts, spans=[]), 36) is None
    # the cell at full slots: 64 sequences of 1,500 tokens, 0.2 GB a forward
    full = paged_attention_blocks.dispatch_needs(
        SDAR, {"kind": "decode", "rows": 64, "ctx_sum": 64 * 1500})[0]
    assert full / 5 == pytest.approx(0.2012e9, rel=0.001)


def test_the_block_ratios_read_tokens_over_forwards_and_over_slots():
    registry = {
        "serving_generated_tokens_total": {"series": [
            {"labels": {"engine": "0"}, "value": 4000.0}]},
        "serving_block_sequence_forwards_total": {"series": [
            {"labels": {"engine": "0"}, "value": 5000.0}]},
        "serving_dispatches_total": {"series": [
            {"labels": {"engine": "0", "kind": "decode"}, "value": 20.0},
            {"labels": {"engine": "0", "kind": "prefill"}, "value": 7.0}]}}
    facts = {"registry": registry, "engine": {"max_batch": 64}}
    per_forward = load("metrics", "block_tokens_per_forward.json")
    assert registry_ratio.read(per_forward["params"], facts) == 0.8
    occupancy = load("metrics", "batch_occupancy.blocks.json")
    # 4000 tokens over 20 dispatches x 64 slots x 4 positions: 78.125 %
    assert registry_ratio.read(occupancy["params"], facts) == 78.125
    # a program without the counter (the parent commit): nothing, no error
    del registry["serving_block_sequence_forwards_total"]
    assert registry_ratio.read(per_forward["params"], facts) is None
