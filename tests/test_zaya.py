"""ZAYA1 (compressed convolutional attention, a router carried from layer to
layer, top-1 of the experts or none) through the engine, on the CPU at the
tiny size of ``bench/rehearsal/zaya-tiny.json`` (float32, seeded random
weights): the model's forward and the engine's logits against the
benchmark's plain reference, over prefill chunks whose boundaries fall
inside a prompt and the decode steps after them; a slot used again; the
skip; the router state's carry; the skip counter against a count by hand;
what a model whose attention keeps a tail refuses; and that the engine's
other programs lower to the text they lowered to before.
"""
import hashlib
import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu import observability as obs  # noqa: E402
from paddle_tpu.inference.engine.disagg import DisaggEngine  # noqa: E402
from paddle_tpu.inference.engine.runner import ModelRunner  # noqa: E402
from paddle_tpu.inference.engine.spec import SpecConfig  # noqa: E402
from paddle_tpu.inference.serving import LLMEngine  # noqa: E402
from paddle_tpu.models import sdar, zaya  # noqa: E402

from bench import run as harness  # noqa: E402
from bench.builders import zaya_engine  # noqa: E402
from bench.reference import zaya as ref  # noqa: E402

CELL = "zaya1-8b-docs-saturated"
# float32 on both sides, the same products summed in another order (paged
# attention by pages, the convolutions a chunk at a time): logits of
# magnitude 1 agree to 1e-6 ... 3e-6 (read over seeds 11-13). A bf16
# model moves them by 1e-2 and more (checked in each test that uses it),
# three orders of magnitude past it.
LOGIT_TOL = 2e-5
# the served token's reference logit below the reference's best: 0 unless
# two logits tie to rounding; a bf16 engine or the int8 control puts
# another token first at some positions, each a gap of 1e-3 ... 1e-1 here
GAP_TOL = 1e-4


def tiny(**over):
    cfg = dict(harness.load_cell(CELL, rehearsal=True)[1])
    cfg.update(over)
    return cfg


def model_of(cfg, seed, dtype=None, weights=None):
    weights = ref.init_weights(cfg, seed) if weights is None else weights
    leaves = zaya_engine.program_leaves(jax.tree_util.tree_map(
        lambda a: a, weights))
    if dtype is not None:
        leaves = jax.tree_util.tree_map(lambda a: a.astype(dtype), leaves)
    model = zaya.ZAYAForCausalLM(zaya_engine.zaya_config(cfg), leaves=leaves)
    model.eval()
    return model, weights


def engine_of(model, **kw):
    for k, v in dict(max_batch=4, max_len=128, page_size=16,
                     prefill_chunk=16).items():
        kw.setdefault(k, v)
    return LLMEngine(model, **kw)


def recording(eng):
    """The float32 logits of every dispatch, as the program computes them,
    in the order they ran: ``[V]`` a prefill chunk (its last valid row),
    ``[B, V]`` a decode step."""
    seen, logits = [], eng.runner._logits

    def record(W, h):
        out = logits(W, h)
        jax.debug.callback(lambda a: seen.append(np.asarray(a)), out)
        return out
    eng.runner._logits = record
    return seen


def serve(model, prompt, n_new, **kw):
    eng = engine_of(model, **kw)
    seen = recording(eng)
    rid = eng.add_request(list(prompt), max_new_tokens=n_new)
    eng.run_until_done()
    return eng, list(eng.result(rid)), seen


def engine_rows(seen, prompt, chunk, slot=0):
    """The logits the engine computed for one request served alone, by the
    position whose NEXT token they predict: each chunk's last row, then
    each decode step's row of the request's slot."""
    n = len(prompt)
    ends = [min(s + chunk, n) - 1 for s in range(0, n, chunk)]
    pre = [a for a in seen if a.ndim == 1]
    dec = [a[slot] for a in seen if a.ndim == 2]
    assert len(pre) == len(ends)
    return ends + list(range(n, n + len(dec))), np.stack(pre + dec)


# ------------------------------------------------------ (a) the forward pass

def test_forward_equals_the_plain_reference():
    cfg = tiny()
    model, weights = model_of(cfg, 11)
    toks = np.random.default_rng(1).integers(1, cfg["vocab_size"], 29)
    got = np.asarray(model(toks)._data[0])
    want = np.asarray(ref.forward(cfg, weights, toks))
    assert np.abs(got - want).max() < LOGIT_TOL
    # the tolerance would fail a bf16-for-float32 substitution
    low, _ = model_of(cfg, 11, dtype=jnp.bfloat16)
    assert np.abs(np.asarray(low(toks)._data[0]) - want).max() > 100 * LOGIT_TOL


# ----------------------------- (b) the engine's logits, chunk by chunk, step

@pytest.mark.parametrize("seed,n_prompt,chunk", [
    (11, 37, 16), (12, 16, 16), (13, 50, 32)],
    ids=["boundaries_inside", "one_whole_chunk", "chunk_32"])
def test_engine_logits_equal_the_reference(seed, n_prompt, chunk):
    """Prefill in chunks (37 tokens: chunks end at 15, 31, 36), then decode
    steps: at every position the engine computed, its logits are the
    reference's over the whole sequence, whose convolutions and value shift
    run over it at once with no tail."""
    cfg = tiny()
    model, weights = model_of(cfg, seed)
    prompt = np.random.default_rng(seed).integers(1, 255, n_prompt).tolist()
    _, out, seen = serve(model, prompt, 7, prefill_chunk=chunk)
    at, got = engine_rows(seen, prompt, chunk)
    want = np.asarray(ref.forward(cfg, weights, prompt + out[:-1]))[at]
    assert np.abs(got - want).max() < LOGIT_TOL
    assert ref.served_token_gaps(cfg, weights, prompt, out, 128
                                 )["served_gap"].max() < GAP_TOL


def test_a_chunk_read_as_one_sequence_computes_the_same_logits():
    """``chunk_attention="sequence"`` (the cell's): a chunk's positions go
    to the multi-query attention as one sequence, row i seeing start + i + 1
    tokens as it does as a row of its own; over chunks whose boundaries fall
    inside the prompt, and a last chunk mostly padding, every logit is the
    reference's and the served tokens are those of ``"rows"``. (The kernel
    itself against the rows' kernel: ``tests/test_paged_attention_walk.py``.)"""
    cfg = tiny()
    model, weights = model_of(cfg, 16)
    prompt = np.random.default_rng(16).integers(1, 255, 35).tolist()
    _, out, seen = serve(model, prompt, 6, chunk_attention="sequence")
    at, got = engine_rows(seen, prompt, 16)
    want = np.asarray(ref.forward(cfg, weights, prompt + out[:-1]))[at]
    assert np.abs(got - want).max() < LOGIT_TOL
    _, by_rows, _ = serve(model, prompt, 6)
    assert out == by_rows


def test_a_bf16_engine_fails_the_tolerances():
    """The same comparison of logits on a bf16 engine reads past its
    tolerance, and the int8 control's first token at the served positions
    reads past the gap's. (A bf16 engine's served tokens may all be the
    reference's at this size: that is what the logits are compared for.)"""
    cfg = tiny()
    _, weights = model_of(cfg, 11)
    low, _ = model_of(cfg, 11, dtype=jnp.bfloat16, weights=weights)
    rng = np.random.default_rng(2)
    ctl = off = 0.0
    for n in (23, 41, 30):
        prompt = rng.integers(1, 255, n).tolist()
        _, out, seen = serve(low, prompt, 16)
        at, got = engine_rows(seen, prompt, 16)
        want = np.asarray(ref.forward(cfg, weights, prompt + out[:-1]))[at]
        off = max(off, float(np.abs(got - want).max()))
        # greedy tokens settle where the top logit stands far clear; the
        # control is read where it does not, after random tokens
        after = rng.integers(1, 255, 40).tolist()
        g = ref.served_token_gaps(cfg, weights, prompt, after, 128,
                                  control="int8")
        ctl = max(ctl, g["control_gap"].max())
    assert off > 100 * LOGIT_TOL
    assert ctl > 10 * GAP_TOL


def test_a_slot_used_again_starts_from_zero_tails():
    """Request B served in the slot request A left (one slot) computes the
    logits a fresh engine computes for it: the tails of A's last token are
    not B's first token's past (``start == 0`` resets them)."""
    cfg = tiny()
    model, weights = model_of(cfg, 14)
    rng = np.random.default_rng(4)
    a, b = rng.integers(1, 255, 27).tolist(), rng.integers(1, 255, 19).tolist()
    eng = engine_of(model, max_batch=1)
    seen = recording(eng)
    ra = eng.add_request(a, max_new_tokens=5)
    eng.run_until_done()
    n_a = len(seen)
    rb = eng.add_request(b, max_new_tokens=6)
    eng.run_until_done()
    out = list(eng.result(rb))
    assert eng.sched.preemptions == 0 and len(eng.result(ra)) == 5
    at, got = engine_rows(seen[n_a:], b, 16)
    _, fresh_out, fresh = serve(model, b, 6, max_batch=1)
    assert out == fresh_out
    assert np.array_equal(got, engine_rows(fresh, b, 16)[1])
    want = np.asarray(ref.forward(cfg, weights, b + out[:-1]))[at]
    assert np.abs(got - want).max() < LOGIT_TOL
    # the pool keeps a row of tails a slot and layer, and one idle row
    assert eng.runner.state_bytes_per_slot() == (
        3 * zaya_engine.zaya_config(cfg).tail_channels * 4)


def test_four_requests_side_by_side_serve_the_references_tokens():
    """Six requests through four slots, so that two join mid-way beside
    decode rows in flight, each slot's tails its own."""
    cfg = tiny()
    model, weights = model_of(cfg, 15)
    rng = np.random.default_rng(5)
    reqs = [(rng.integers(1, 255, n).tolist(), k)
            for n, k in ((3, 5), (16, 9), (21, 7), (34, 10), (7, 1), (38, 13))]
    eng = engine_of(model)
    rids = [eng.add_request(p, max_new_tokens=k) for p, k in reqs]
    eng.run_until_done()
    for (p, k), rid in zip(reqs, rids):
        out = list(eng.result(rid))
        assert len(out) == k
        assert ref.served_token_gaps(cfg, weights, p, out, 128
                                     )["served_gap"].max() < GAP_TOL


# ------------------------------------- (c) the skip and the router's carry

def test_rows_that_choose_the_skip_add_exactly_nothing():
    """A row whose router chooses the last output gets the scaled residual
    alone, bit for bit, is no assignment, and is counted as skipped."""
    cfg = tiny()
    c = zaya_engine.zaya_config(cfg)
    w = dict(ref.init_weights(cfg, 3)["layers"][0])
    x = jax.random.normal(jax.random.PRNGKey(5), (24, c.hidden_size)) * 0.3
    rh = jax.random.normal(jax.random.PRNGKey(6), (24, c.router_hidden_size))
    live = jnp.ones((24,), jnp.int32).at[20:].set(0)
    # a bias that makes every row choose the skip ...
    w["r_bias"] = jnp.zeros((c.num_experts + 1,)).at[-1].set(1e4)
    (got, _), counts = zaya.experts(w, (x, rh), live, c)
    res = (w["res2"] * x).astype(x.dtype)
    assert np.array_equal(np.asarray(got), np.asarray(res))
    calls, rows, assigned, touched, _, skipped = np.asarray(counts).tolist()
    assert (calls, rows, assigned, touched, skipped) == (1, 20, 0, 0, 20)
    # ... and one that leaves it to the router: the rows that chose it,
    # and only those, add nothing
    w["r_bias"] = jnp.zeros((c.num_experts + 1,))
    _, choice, _ = zaya.route(w, zaya.rms_norm(x, w["ln2"], c.rms_norm_eps),
                              rh, c)
    skip = np.asarray(choice) == c.num_experts
    assert 0 < skip.sum() < 24
    (got, _), counts = zaya.experts(w, (x, rh), live, c)
    same = np.all(np.asarray(got) == np.asarray(res), axis=-1)
    # the live rows that chose it; a row that is not live is routed nowhere
    assert (same[:20] == skip[:20]).all() and same[20:].all()
    assert np.asarray(counts)[-1] == skip[:20].sum()
    assert np.asarray(counts)[2] == 20 - skip[:20].sum()


def test_the_router_state_is_carried_from_layer_to_layer():
    """The engine agrees with the reference, whose router state sums over
    the layers before; cut the carry (each layer's router from its own
    projection alone) and the reference's choices and logits move far past
    the tolerance - so an engine that dropped the carry would fail."""
    cfg = tiny()
    model, weights = model_of(cfg, 16)
    prompt = np.random.default_rng(6).integers(1, 255, 40).tolist()
    _, out, seen = serve(model, prompt, 6)
    at, got = engine_rows(seen, prompt, 16)
    seq = prompt + out[:-1]
    want = np.asarray(ref.forward(cfg, weights, seq))[at]
    assert np.abs(got - want).max() < LOGIT_TOL
    cut = dict(weights, layers=[dict(w, eda=jnp.zeros_like(w["eda"]))
                                for w in weights["layers"]])
    _, with_carry = ref.hidden(cfg, weights, seq)
    _, without = ref.hidden(cfg, cut, seq)
    assert np.array_equal(np.asarray(with_carry[0]), np.asarray(without[0]))
    assert (np.asarray(with_carry[1:]) != np.asarray(without[1:])).any()
    assert np.abs(np.asarray(ref.forward(cfg, cut, seq))[at]
                  - want).max() > 100 * LOGIT_TOL


def test_skip_counter_against_a_hand_count():
    """One request, 21 prompt tokens in chunks of 16 and 6 new tokens, 3
    layers: 2 chunks and 5 decode steps. Calls and rows follow from that;
    the skips and assignments are the reference's choices at the same 26
    positions."""
    cfg = tiny()
    model, weights = model_of(cfg, 8)
    prompt = np.random.default_rng(4).integers(1, 255, 21).tolist()
    obs.reset()
    obs.enable()
    try:
        eng = engine_of(model)
        rid = eng.add_request(prompt, max_new_tokens=6)
        eng.run_until_done()
        out = list(eng.result(rid))
        snap = obs.snapshot(prefix="serving_", labels={"engine": eng._m.label})
    finally:
        obs.disable()

    def read(name, kind):
        return sum(s["value"] for s in snap[name]["series"]
                   if s["labels"]["kind"] == kind)
    L, E = cfg["num_hidden_layers"], cfg["num_experts"]
    assert read("serving_moe_calls_total", "prefill") == 2 * L
    assert read("serving_moe_calls_total", "decode") == 5 * L
    assert read("serving_moe_rows_total", "prefill") == 21 * L
    assert read("serving_moe_rows_total", "decode") == 5 * L
    _, choices = ref.hidden(cfg, weights, prompt + out[:-1])
    skip = np.asarray(choices) == E                     # [layers, positions]
    assert 0 < skip.sum() < skip.size
    for kind, cols in (("prefill", slice(0, 21)), ("decode", slice(21, 26))):
        assert read("serving_moe_skipped_total", kind) == skip[:, cols].sum()
        assert (read("serving_moe_assignments_total", kind)
                == (~skip[:, cols]).sum())


# ------------------------------------------------- (d) what it refuses

@pytest.mark.parametrize("kw,reason", [
    ({"prefix_cache": True}, "a page boundary holds no tail"),
    ({"spec_decode": SpecConfig()}, "no rollback"),
    ({"prefix_cache": True, "host_cache_bytes": 1 << 20}, "prefix_cache"),
], ids=["prefix_cache", "spec_decode", "host_cache_bytes"])
def test_a_model_whose_attention_keeps_a_tail_refuses_by_name(kw, reason):
    model, _ = model_of(tiny(), 1)
    with pytest.raises(NotImplementedError, match="keep a tail a slot") as e:
        engine_of(model, **kw)
    assert reason in str(e.value)


def test_it_refuses_disaggregation_page_handoff_and_verification():
    model, _ = model_of(tiny(), 1)
    with pytest.raises(NotImplementedError, match="DisaggEngine.*no tail"):
        DisaggEngine(model, max_batch=2, max_len=64, page_size=8)
    eng = engine_of(model)
    with pytest.raises(NotImplementedError, match="DisaggEngine.*no tail"):
        DisaggEngine(prefill_engines=[eng], decode_engines=[eng])
    for call in (lambda: eng.export_pages([b"k"]),
                 lambda: eng.import_pages({"keys": [], "block": ()})):
        with pytest.raises(NotImplementedError, match="_pages.*no tail"):
            call()
    with pytest.raises(NotImplementedError, match="verification.*tail"):
        eng.runner._layer_fns(eng.runner.W, None, "verify")


def test_config_refuses_what_is_not_the_published_layer():
    for kw, name in (({"num_experts_per_tok": 2}, "num_experts_per_tok"),
                     ({"cca_time0": 3}, "cca_time0"),
                     ({"tie_word_embeddings": False}, "tie_word_embeddings"),
                     ({"layer_types": ["hybrid_sliding"] * 3}, "layer_types")):
        with pytest.raises(NotImplementedError, match=name):
            zaya.ZAYAConfig.tiny(**kw)
    with pytest.raises(ValueError, match="KV heads split"):
        zaya.ZAYAConfig.tiny(num_key_value_heads=1, num_attention_heads=3)


# ---------------------------------------- (e) the front door, end to end

def test_the_cell_rehearses_through_the_gateway(capsys):
    """``ZAYAForCausalLM -> LLMEngine -> ReplicaSet -> start_gateway`` and
    the load generator at the cell's tiny size, compared with the
    reference: the benchmark's own run."""
    import json
    assert harness.main(["--workload", CELL, "--seed", str(2**31 + 5),
                         "--seconds", "2", "--rehearsal"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0


# ------------- (f) the engine's other programs lower to the parent's text

# sha256 (first 16 hex) of the lowered text of the block decode program and
# the prefill of an SDARConfig.tiny() engine (4 slots, pages of 16, chunks
# of 32, 33 pages, reference attention) on the CPU, as the commit before
# this model came (d23812d) lowers them: the runner's tails now go by kind
# and its layers may carry a stream beside the residual - for a plan with
# neither, the text must be the parent's, byte for byte. (The Llama
# engine's eight programs and Solar's two are held to their hashes in
# ``tests/test_solar_open2.py`` and ``tests/test_sdar.py``.) A change of jax
# changes the text: take the hashes anew from that commit and this one.
SDAR_PROGRAMS = {"block": "5a746db3fe20cbcf", "prefill": "766ea9be87b9accc"}


def test_sdar_programs_lower_to_the_parents_text():
    paddle.seed(0)
    model = sdar.SDARForCausalLM(sdar.SDARConfig.tiny())
    model.eval()
    r = ModelRunner(model, max_batch=4, page_size=16, prefill_chunk=32,
                    n_pages=33, use_kernel=False)
    B, S, Q = 4, 8, r.plan.block
    i32, f32 = np.int32, np.float32
    dec = [np.zeros((B, Q), i32), np.zeros(B, i32), np.zeros((B, S), i32),
           np.ones(B, i32), np.ones(B, i32), np.ones(B, f32), np.ones(B, f32),
           np.zeros(B, i32), np.zeros(B, i32), np.zeros(B, i32),
           np.zeros(B, i32), np.zeros(B, i32)]
    pre = [np.zeros(32, i32), i32(0), np.zeros(S, i32), i32(5), i32(1),
           f32(1), f32(1), i32(0), i32(0), i32(0)]
    texts = {"block": r._build_decode(Q).lower(r.W, r.cache, *dec),
             "prefill": r._build_prefill().lower(r.W, r.cache, *pre)}
    for name, lowered in texts.items():
        digest = hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]
        assert digest == SDAR_PROGRAMS[name], name
