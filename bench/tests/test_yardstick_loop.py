"""The three shares the yardstick reads from ``loop_seconds_total{engine,
span, device}`` (the step loop's time, by the innermost span open and by
whether the device had a program of the loop's queued), against a registry
worked by hand, and against one without the family, as a program that does
not count it leaves the registry: the metric is then left out.  (A file of
its own beside ``test_yardstick.py``: a PR that adds metrics edits no file
the benchmark already has.)
"""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench.readers import registry_ratio  # noqa: E402

NAMES = ("device_dry_share.itl", "device_dry_share.tput",
         "dispatch_dry_share.tput")


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def params(name):
    spec = load(ROOT, "bench", "metrics", name + ".json")
    assert spec["reader"] == "registry_ratio"
    return spec["params"]


def loop_seconds(*rows):
    """A registry snapshot holding loop_seconds_total from
    ``(engine, span, device, seconds)`` rows."""
    return {"loop_seconds_total": {"type": "counter", "help": "", "series": [
        {"labels": {"engine": e, "span": s, "device": d}, "value": v}
        for e, s, d, v in rows]}}


# two engines' loops: 4.5 s dry with no work (replica.idle), 1.5 s dry with
# work (1.0 of it in the argument fan), 4.0 s busy
HAND = loop_seconds(
    ("0", "replica.idle", "dry", 4.0),
    ("0", "replica.idle", "busy", 0.5),
    ("0", "runner.dispatch", "dry", 0.75),
    ("0", "runner.dispatch", "busy", 0.25),
    ("0", "runner.wait", "busy", 2.5),
    ("0", "engine.step", "dry", 0.25),
    ("0", "none", "dry", 0.125),
    ("1", "replica.idle", "dry", 0.5),
    ("1", "runner.dispatch", "dry", 0.25),
    ("1", "engine.emit", "dry", 0.125),
    ("1", "engine.emit", "busy", 0.75),
)


@pytest.mark.parametrize("name", NAMES[:2])
def test_device_dry_share_by_hand(name):
    # dry outside replica.idle: 0.75 + 0.25 + 0.125 + 0.25 + 0.125 = 1.5 s
    # of the loops' 10 s
    got = registry_ratio.read(params(name), {"registry": HAND})
    assert got == pytest.approx(100 * 1.5 / 10.0) == 15.0


def test_dispatch_dry_share_by_hand():
    # the fan's dry seconds (0.75 + 0.25) of the 1.5 dry with work
    got = registry_ratio.read(params("dispatch_dry_share.tput"),
                              {"registry": HAND})
    assert got == pytest.approx(100 * 1.0 / 1.5)


def test_a_loop_that_never_waited_on_the_host():
    """Dry only while the engine stood empty: the device never waited on
    the host (0 %), and the fan's share of no waiting is left out."""
    registry = loop_seconds(("0", "replica.idle", "dry", 2.0),
                            ("0", "runner.wait", "busy", 6.0))
    assert registry_ratio.read(params("device_dry_share.tput"),
                               {"registry": registry}) == 0.0
    assert registry_ratio.read(params("dispatch_dry_share.tput"),
                               {"registry": registry}) is None


@pytest.mark.parametrize("name", NAMES)
def test_left_out_where_the_program_has_no_such_counter(name):
    other = {"span_seconds": {"type": "histogram", "help": "", "series": [
        {"labels": {"span": "engine.step"}, "sum": 3.0, "count": 9}]}}
    for registry in ({}, other):
        assert registry_ratio.read(params(name), {"registry": registry}) is None
    assert registry_ratio.read(params(name), {}) is None


def test_listed_in_the_cells_that_report_what_they_move():
    bench = load(ROOT, "BENCHMARK.json")
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    tput = ["mistral7b-chat-saturated", "solar-open2-reason-saturated",
            "sdar-30b-a3b-blocks-saturated"]
    want = {"device_dry_share.itl": ("device", "itl_p90_ms",
                                     ["mistral7b-chat-steady"]),
            "device_dry_share.tput": ("device", "serve_tokens_per_s", tput),
            "dispatch_dry_share.tput": ("runner", "serve_tokens_per_s", tput)}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for name, (layer, moves, cells) in want.items():
        m = per_layer[name]
        assert (m["layer"], m["moves"], m["workloads"]) == (layer, moves, cells)
        assert m["source"] == "program_counter" and m["better"] == "lower"
        assert m["unit"] == "%"
        assert set(cells) <= set(e2e[moves]["workloads"])
