"""``BENCHMARK.json`` against the form the driver holds it to before any
run (a PR fell on a ``why`` it would have refused): every entry has just its
keys, every name, ``why``, ``layer`` and ``source`` is of the characters
and the length allowed, every file is there, and every cell reports
``setup_s``, one more end-to-end metric and a per-layer metric.
"""
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}\Z")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}\Z")
LINE = re.compile(r"[\x20-\x7e]{1,200}\Z")      # printable ASCII, one line
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def entries(kind):
    return [pytest.param(e, id=e.get("name", "?")) for e in BENCHMARK[kind]]


def test_the_file_has_just_its_keys_and_fits():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10
    assert all(LINE.match(w) for w in BENCHMARK["command"])
    assert 1 <= len(BENCHMARK["configs"]) <= 24
    assert 1 <= len(BENCHMARK["workloads"]) <= 24
    assert 1 <= len(BENCHMARK["per_layer"]) <= 128
    for kind in ("configs", "workloads"):
        names = [e["name"] for e in BENCHMARK[kind]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]]
    assert len(metrics) == len(set(metrics))
    # a full check fits: 2 + 14 x cells runs, 2 x 90 s more a cell, 1200 spare
    cells = len(BENCHMARK["workloads"])
    assert ((2 + 14 * cells) * (BENCHMARK["run_seconds"] + 60)
            + 2 * 90 * cells + 1200) <= 43200
    four = sum(w["chips"] == 4 for w in BENCHMARK["workloads"])
    assert four <= max(1, cells // 4)


@pytest.mark.parametrize("config", entries("configs"))
def test_a_configuration_entry(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"])
    assert LINE.match(config["why"]) and LINE.match(config["source"])
    assert len(config["reduced"]) <= 16
    assert all(NAME.match(k) for k in config["reduced"])
    assert any(config["file"].startswith(p + "/") for p in BENCHMARK["paths"])
    assert re.match(r"[A-Za-z0-9_.\-/]+\Z", config["file"])
    with open(os.path.join(ROOT, config["file"])) as f:
        held = json.load(f)
    assert held["reduced"] == config["reduced"]
    assert sorted(held.get("published", {})) == sorted(config["reduced"])
    assert any(w["config"] == config["name"] for w in BENCHMARK["workloads"])
    files = [c["file"] for c in BENCHMARK["configs"]]
    assert files.count(config["file"]) == 1


@pytest.mark.parametrize("cell", entries("workloads"))
def test_a_workload_entry(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert all(NAME.match(cell[k]) for k in ("name", "config", "traffic"))
    assert LINE.match(cell["why"]) and cell["chips"] in (1, 4)
    assert cell["config"] in {c["name"] for c in BENCHMARK["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in BENCHMARK["workloads"]]
    assert pairs.count((cell["config"], cell["traffic"])) == 1
    with open(os.path.join(ROOT, "bench", "workloads", cell["name"] + ".json")) as f:
        held = json.load(f)
    assert held["config"] == cell["config"] and held["chips"] == cell["chips"]

    def reports(m):
        return "workloads" not in m or cell["name"] in m["workloads"]
    e2e = [m["name"] for m in BENCHMARK["end_to_end"] if reports(m)]
    assert "setup_s" in e2e and len(e2e) >= 2
    layers = [m for m in BENCHMARK["per_layer"] if reports(m)]
    assert layers
    # what a per-layer metric of the cell moves, the cell reports
    assert all(m["moves"] in e2e for m in layers)


@pytest.mark.parametrize("metric",
                         entries("end_to_end") + entries("per_layer"))
def test_a_metric_entry(metric):
    per_layer = "layer" in metric
    keys = ({"name", "unit", "better", "source", "layer", "moves"}
            if per_layer else {"name", "unit", "better", "bound", "source"})
    assert set(metric) - {"workloads"} == keys
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    cells = {w["name"] for w in BENCHMARK["workloads"]}
    assert set(metric.get("workloads", ())) <= cells
    if per_layer:
        assert LINE.match(metric["layer"])
        assert metric["moves"] in {m["name"] for m in BENCHMARK["end_to_end"]}
        assert os.path.exists(os.path.join(
            ROOT, "bench", "metrics", metric["name"] + ".json"))
        if "roofline" in metric["name"] or "mfu" in metric["name"]:
            assert metric["unit"] == "%"
    else:
        assert metric["source"] in ("host_clock", "device_trace")
