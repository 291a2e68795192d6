"""Every cell end to end at its tiny size on the CPU (``--rehearsal``: the
harness's look for a chip is skipped, the rest of a run is driven), sound
and with the timed path broken underneath; and the control of each
comparison, at a size a test run can hold.
"""
import importlib
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import run as harness  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
METRICS = {m["name"] for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]}


def rehearse(capsys, cell, trace=0, seconds="2", more=()):
    rc = harness.main(["--workload", cell, "--seed", str(2**31 + 11),
                       "--seconds", seconds, "--trace", str(trace), "--rehearsal",
                       *more])
    assert rc == 0
    lines = capsys.readouterr().out.strip().splitlines()
    return json.loads(lines[-1])


def break_build(monkeypatch, cell, fault):
    """The cell's builder, with ``fault(system)`` applied to what it builds."""
    cfg = harness.load_cell(cell)[1]
    builder = importlib.import_module(f"bench.builders.{cfg['builder']}")
    build = builder.build

    def broken(*a, **k):
        system = build(*a, **k)
        fault(system)
        return system
    monkeypatch.setattr(builder, "build", broken)


# ------------------------------------------------------------- sound runs

@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_at_its_tiny_size(capsys, cell, trace):
    line = rehearse(capsys, cell, trace)
    assert line["correct"] is True and line["rehearsal"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    # a CPU run's numbers never stand under a metric's name
    assert "metrics" not in line and "device" not in line
    assert line["rehearsal_counts"]
    assert not METRICS & set(line["rehearsal_counts"])
    for check in line["checks"].values():
        assert set(check) == {"value", "limit"}


# ------------------------------------------------------- the timed path broken

def alter_tokens(system):
    """A token altered where it is produced: every decode step's."""
    for engine in system.engines:
        run_decode = engine.runner.run_decode

        def altered(*a, _f=run_decode, **k):
            toks = np.asarray(_f(*a, **k))
            return np.where(toks > 1, toks - 1, toks + 1).astype(toks.dtype)
        engine.runner.run_decode = altered


def state_unchanged(system):
    """A step that returns its state unchanged: the losses come, the
    parameters and the optimizer's state stay what they were."""
    import jax.numpy as jnp
    dispatch = system.dispatch

    def stuck(ids):
        held = [(t, jnp.array(t._data, copy=True)) for t in _state(system)]
        out = dispatch(ids)
        for t, data in held:
            t._data = data
        return out
    system.dispatch = stuck


def half_batch(system):
    """Half of the batch left out, the mean taken over the rest: the second
    half of the rows is the first over again."""
    import jax.numpy as jnp
    dispatch = system.dispatch

    def halved(ids):
        h = ids.shape[1] // 2
        return dispatch(jnp.concatenate([ids[:, :h], ids[:, :h]], axis=1))
    system.dispatch = halved


def _state(system):
    tensors = list(system.leaves.values())
    for store in system.opt._accumulators.values():
        tensors += list(store.values())
    return tensors + [system.opt._global_step]


SERVING = [w["name"] for w in BENCHMARK["workloads"]
           if harness.load_cell(w["name"])[0]["generator"] == "open_loop_http"]
TRAINING = [w["name"] for w in BENCHMARK["workloads"]
            if harness.load_cell(w["name"])[0]["generator"] == "train_batches"]


@pytest.mark.parametrize("cell", SERVING)
def test_an_altered_token_is_not_correct(monkeypatch, capsys, cell):
    break_build(monkeypatch, cell, alter_tokens)
    line = rehearse(capsys, cell)
    assert line["correct"] is False
    c = line["checks"]
    assert c["served_gap_mean"]["value"] > c["served_gap_mean"]["limit"]


@pytest.mark.parametrize("cell", TRAINING)
@pytest.mark.parametrize("fault,number", [(state_unchanged, "change_norm_gap"),
                                          (half_batch, "grad_norm_gap")])
def test_a_broken_step_is_not_correct(monkeypatch, capsys, cell, fault, number):
    break_build(monkeypatch, cell, fault)
    line = rehearse(capsys, cell)
    assert line["correct"] is False
    assert line["checks"][number]["value"] > line["checks"][number]["limit"]


# ------------------------------------------------------------- the controls

@pytest.mark.parametrize("cell", SERVING)
def test_serving_control_in_the_programs_place_is_not_correct(capsys, cell):
    """A whole run with ``--control int8``: the tokens that the reference on
    int8 operands puts first stand where the served tokens stood, go through
    the run's own comparison, and ``correct`` comes out false; the same run
    without the control is correct (``test_cell_runs_at_its_tiny_size``)."""
    # at the tiny size int8 puts another token first at a few positions in a
    # hundred, so the run compares some 200 served tokens (a cell's own run
    # compares 500-1100)
    line = rehearse(capsys, cell, seconds="8",
                    more=["--control", "int8", "--set", "check_requests=24"])
    assert line["control"] == "int8" and line["correct"] is False
    c = line["checks"]
    assert any(c[n]["value"] > c[n]["limit"]
               for n in ("served_gap_mean", "served_gap_max"))
    assert line["failed"] == 0 and c["failed_requests"]["value"] == 0


def test_serving_reference_reads_nought_on_its_own_first_token():
    from bench.reference import mistral
    _, cfg = harness.load_cell(SERVING[0], rehearsal=True)
    weights = mistral.init_weights(cfg, 3)
    toks = np.random.default_rng(3).integers(1, cfg["vocab_size"], 120).tolist()
    logits = np.asarray(mistral.forward_logits(
        cfg, weights, np.asarray(toks + [0] * 8, np.int32)))
    greedy = logits[7:119].argmax(-1).tolist()       # teacher-forced on toks
    assert mistral.served_token_gaps(cfg, weights, toks[:8], greedy[:1], 128
                                     )["served_gap"].max() == 0.0


@pytest.mark.parametrize("cell", TRAINING)
def test_training_control_and_fault_are_not_correct(capsys, cell):
    """``train_limits.py`` at the tiny size: the program's steps are correct
    by the run's own comparison, the int8 control and the half-batch fault
    (the reference in the program's place) are not."""
    from bench.tools import train_limits
    assert train_limits.main(["--workload", cell, "--seeds", "9",
                              "--control-seeds", "9,10", "--rehearsal"]) == 0
    rows = [json.loads(x) for x in capsys.readouterr().out.strip().splitlines()]
    verdicts = {(r["who"], r["seed"]): r["correct"] for r in rows}
    assert verdicts == {("program", 9): True,
                        ("control_int8", 9): False, ("control_int8", 10): False,
                        ("fault_half_batch", 9): False,
                        ("fault_half_batch", 10): False}
    limits = harness.load_cell(cell, rehearsal=True)[0]["limits"]
    for r in rows:
        if r["who"] == "fault_half_batch":
            assert r["grad_norm_gap"] > limits["grad_norm_gap"]
