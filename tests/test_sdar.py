"""SDAR (generation by blocks) through the engine, on the CPU at the tiny
size of ``bench/rehearsal/sdar-tiny.json`` (float32, seeded random weights):
the model's forward, router and expert layer against the benchmark's plain
reference; paged attention with a block's horizon against its reference
and against dense attention under the block-causal mask; the engine, under
each of the three unmasking rules, against the reference at every (block,
step); the step loop ahead and landing every step; recompute after
preemption; what a block model refuses; and that a plan with block 0
lowers to the programs it lowered to before.
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
from paddle_tpu.inference.engine.spec import SpecConfig  # noqa: E402
from paddle_tpu.inference.serving import LLMEngine  # noqa: E402
from paddle_tpu.models import sdar  # noqa: E402
from paddle_tpu.models import solar_open2 as so  # noqa: E402
from paddle_tpu.ops.pallas import paged_attention as pa  # noqa: E402

from bench import run as harness  # noqa: E402
from bench.builders import sdar_engine  # noqa: E402
from bench.reference import sdar as ref  # noqa: E402

CELL = "sdar-30b-a3b-blocks-saturated"
# float32 on both sides, the same products summed in another order: logits
# of magnitude 0.6 agree to 2e-7 ... 4e-7 (read over seeds 11-13). bf16
# weights and activations move them by 1e-2 and more (checked below), three
# orders of magnitude past the tolerance.
LOGIT_TOL = 2e-5
# the served token's reference logit below the reference's best at the
# position and step the engine unmasked it: 0 unless two logits tie to
# rounding; an int8 or a bf16 forward puts another token first at some
# positions, each a gap of 1e-3 ... 1e-1 at this size (checked below)
GAP_TOL = 1e-4
# the reference's confidence at the position a confidence rule chose, below
# its highest among the masked: 0 unless two confidences tie to rounding
# (confidences are 0.0054 ... 0.0064 here and differ by 1e-5 ... 1e-3)
CONF_TOL = 1e-6
RULES = ("sequential", "low_confidence_static", "low_confidence_dynamic")
# between the median and the third quartile of the confidences at the tiny size, so that the dynamic rule
# takes both of its branches
THRESHOLD = 0.0058


def tiny(rule="sequential", **over):
    cfg = dict(harness.load_cell(CELL, rehearsal=True)[1])
    cfg["generation"] = dict(cfg["generation"], remasking=rule,
                             confidence_threshold=THRESHOLD)
    cfg.update(over)
    return cfg


def model_of(cfg, seed, dtype=None):
    weights = ref.init_weights(cfg, seed)
    leaves = sdar_engine.program_leaves(ref.init_weights(cfg, seed))
    if dtype is not None:
        leaves = jax.tree_util.tree_map(lambda a: a.astype(dtype), leaves)
    model = sdar.SDARForCausalLM(sdar_engine.sdar_config(cfg), leaves=leaves)
    model.eval()
    return model, weights


def engine_of(model, **kw):
    for k, v in dict(max_batch=4, max_len=128, page_size=16,
                     prefill_chunk=16).items():
        kw.setdefault(k, v)
    return LLMEngine(model, **kw)


def gaps_of(cfg, weights, prompt, out):
    return ref.served_token_gaps(cfg, weights, prompt, list(out), 128,
                                 steps=list(out.steps), with_conf=True)


def assert_served_as_the_reference_would(cfg, weights, prompt, out):
    """At every position, in the block as it stood before the step the
    engine says it unmasked it: the served token is the reference's best,
    and the position is one the rule would have chosen."""
    g = gaps_of(cfg, weights, prompt, out)
    assert g["served_gap"].max() < GAP_TOL
    rule = cfg["generation"]["remasking"]
    q = cfg["generation"]["block_length"]
    steps = np.asarray(out.steps)
    if rule == "sequential":
        assert steps.tolist() == ref.sequential_steps(cfg, len(prompt),
                                                      len(out))
    elif rule == "low_confidence_static":
        assert g["conf_gap"].max() < CONF_TOL
    else:
        # chosen: the highest among the masked, or over the threshold
        sure = g["conf"] > THRESHOLD
        assert (sure | (g["conf_gap"] < CONF_TOL)).all()
        pos = len(prompt) + np.arange(len(out))
        for b in np.unique(pos // q):
            for s in np.unique(steps[pos // q == b]):
                at = (pos // q == b) & (steps == s)
                if at.sum() > 1:        # more than num_transfer: all sure
                    assert sure[at].all()
    return g


# ------------------------------------------------------ (a) the forward pass

def test_forward_equals_the_plain_reference():
    cfg = tiny()
    model, weights = model_of(cfg, 11)
    toks = np.random.default_rng(1).integers(1, cfg["vocab_size"], 23)
    got = np.asarray(model(toks)._data[0])
    want = np.asarray(ref.forward(cfg, weights, toks))
    assert np.abs(got - want).max() < LOGIT_TOL
    # the tolerance would fail a bf16-for-float32 substitution
    low, _ = model_of(cfg, 11, dtype=jnp.bfloat16)
    assert np.abs(np.asarray(low(toks)._data[0]) - want).max() > 100 * LOGIT_TOL


def test_forward_is_block_causal():
    """A token changed at position p moves the logits of p's block and of
    later ones, and of no earlier block."""
    cfg = tiny()
    model, _ = model_of(cfg, 12)
    toks = np.random.default_rng(2).integers(1, cfg["vocab_size"], 16)
    other = toks.copy()
    other[9] = (other[9] + 1) % 255 + 1         # block 2 of 4
    a, b = (np.asarray(model(t)._data[0]) for t in (toks, other))
    moved = np.abs(a - b).max(axis=-1) > 0
    assert not moved[:8].any() and moved[8:].all()


# ------------------------------------- (b) the router and the expert layer

def test_router_and_expert_layer_equal_the_reference():
    cfg = tiny()
    c = sdar_engine.sdar_config(cfg)
    w = ref.init_weights(cfg, 3)["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(5), (24, c.hidden_size)) * 0.3
    live = jnp.ones((24,), jnp.int32)
    h = np.asarray(x, np.float64)
    h = h / np.sqrt((h * h).mean(-1, keepdims=True) + c.rms_norm_eps) \
        * np.asarray(w["ln2"], np.float64)
    s = h @ np.asarray(w["router"], np.float64)
    s = np.exp(s - s.max(-1, keepdims=True))
    s = s / s.sum(-1, keepdims=True)
    chosen, weight = sdar.route(w, jnp.asarray(h, jnp.float32), c)
    order = np.argsort(-s, axis=-1)[:, :c.num_experts_per_tok]
    assert (np.sort(np.asarray(chosen)) == np.sort(order)).all()
    top = np.take_along_axis(s, np.asarray(chosen), axis=1)
    assert np.abs(np.asarray(weight) - top / top.sum(-1, keepdims=True)
                  ).max() < 1e-6
    got, counts = sdar.experts(w, x, live, c)
    z = ref.sizes(cfg)
    want = ref._experts(x, w, z, c.rms_norm_eps, True, "float32")
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-6
    assert np.asarray(counts).tolist()[:3] == [1, 24, 24 * 2]


def test_two_shares_of_the_experts_add_up_to_the_layer():
    cfg = tiny()
    c = sdar_engine.sdar_config(cfg)
    w = ref.init_weights(cfg, 4)["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(6), (20, c.hidden_size)) * 0.3
    live = jnp.ones((20,), jnp.int32)
    whole = sdar.experts(w, x, live, c)[0] - x
    total = 0
    for offset in (0, 4):
        share = sdar.SDARConfig.tiny(experts_held=4, expert_offset=offset)
        part = dict(w, **{k: w[k][offset:offset + 4]
                          for k in ("wg", "wu", "wd")})
        total = total + (sdar.experts(part, x, live, share)[0] - x)
    assert np.abs(np.asarray(total - whole)).max() < 1e-6


# ------------------------------------ (c) attention with a block's horizon

def _pages(rng, B, S, page, kvh, d, dtype):
    n_pages = B * S + 1
    kp = rng.normal(size=(n_pages, page, kvh, d)).astype(np.float32)
    vp = rng.normal(size=(n_pages, page, kvh, d)).astype(np.float32)
    tables = rng.permutation(n_pages - 1)[:B * S].reshape(B, S).astype(np.int32)
    return jnp.asarray(kp, dtype), jnp.asarray(vp, dtype), jnp.asarray(tables)


@pytest.mark.parametrize("pages", ["bf16", "int8"])
def test_block_horizon_kernel_against_ref_and_dense_attention(pages):
    """Q = 4 rows a sequence that see ``ctx + 3`` tokens each, their own
    block included: the kernel (interpret mode) against ``_ref``, and
    ``_ref`` against dense attention under the block-causal mask."""
    rng = np.random.default_rng(7)
    B, S, page, nh, kvh, d, Q = 3, 12, 16, 4, 2, 128, 4
    dtype = jnp.bfloat16
    kp, vp, tables = _pages(rng, B, S, page, kvh, d, dtype)
    q = jnp.asarray(rng.normal(size=(B, Q, nh, d)), dtype)
    starts = np.array([0, 52, 160], np.int32)        # whole blocks before
    ctx = jnp.asarray(starts + 1)
    kw = {}
    if pages == "int8":
        kq, ks = pa.quantize_kv(kp)
        vq, vs = pa.quantize_kv(vp)
        kp, vp, kw = kq, vq, {"k_scales": ks, "v_scales": vs}
    want = np.asarray(pa.paged_attention_multiquery_ref(
        q, kp, vp, tables, ctx, horizon="block", **kw), np.float32)
    got = np.asarray(pa.paged_attention_multiquery(
        q, kp, vp, tables, ctx, horizon="block", **kw), np.float32)
    # bf16 operands on both sides, float32 statistics: the kernel's blocks
    # of 128 tokens sum in another order than the reference's one softmax
    assert np.abs(got - want).max() < 2e-2
    # the default horizon is still the row's own
    own = np.asarray(pa.paged_attention_multiquery_ref(
        q, kp, vp, tables, ctx, **kw), np.float32)
    assert np.abs(own[:, :-1] - want[:, :-1]).max() > 1e-3
    assert np.abs(own[:, -1] - want[:, -1]).max() == 0.0
    if pages == "int8":
        return
    # dense attention under M over each sequence's gathered tokens
    for b in range(B):
        n = int(starts[b]) + Q
        k = np.asarray(kp[tables[b]], np.float32).reshape(-1, kvh, d)[:n]
        v = np.asarray(vp[tables[b]], np.float32).reshape(-1, kvh, d)[:n]
        qs = np.zeros((n, nh, d), np.float32)
        qs[n - Q:] = np.asarray(q[b], np.float32)
        dense = np.asarray(sdar.block_causal_attention(
            jnp.asarray(qs), jnp.asarray(k), jnp.asarray(v), Q))[n - Q:]
        assert np.abs(dense - want[b]).max() < 2e-2


# ------------------------------------------- (d) through the engine, by rule

def _requests(cfg, seed):
    """Prompts of every ``len % 4`` (one shorter than a block), budgets
    that are no multiple of 4, one of a single token."""
    rng = np.random.default_rng(seed)
    return [(rng.integers(1, cfg["vocab_size"] - 1, n).tolist(), k)
            for n, k in ((3, 5), (16, 9), (21, 7), (34, 10), (7, 1), (38, 13))]


@pytest.mark.parametrize("rule", RULES)
def test_engine_serves_the_references_tokens_at_every_block_and_step(rule):
    """Six requests through four slots, so that two join mid-way beside
    blocks in flight."""
    cfg = tiny(rule)
    model, weights = model_of(cfg, 11)
    eng = engine_of(model)
    reqs = _requests(cfg, 0)
    rids = [eng.add_request(p, max_new_tokens=k) for p, k in reqs]
    eng.run_until_done()
    assert eng.sched.preemptions == 0
    seen = set()
    for (p, k), rid in zip(reqs, rids):
        out = eng.result(rid)
        assert len(out) == k and len(out.steps) == k
        assert_served_as_the_reference_would(cfg, weights, p, out)
        seen.update(out.steps)
    assert seen == {0, 1, 2, 3}


def test_the_gap_tolerance_fails_lower_precisions():
    """The same comparison on what a bf16 engine serves, and on the int8
    control's first tokens, reads past the tolerance."""
    cfg = tiny()
    _, weights = model_of(cfg, 11)
    low, _ = model_of(cfg, 11, dtype=jnp.bfloat16)
    eng = engine_of(low)
    reqs = [(p, 24) for p, _ in _requests(cfg, 0)[1:4]]
    rids = [eng.add_request(p, max_new_tokens=k) for p, k in reqs]
    eng.run_until_done()
    worst = ctl = 0.0
    for (p, _), rid in zip(reqs, rids):
        out = eng.result(rid)
        g = ref.served_token_gaps(cfg, weights, p, list(out), 128,
                                  control="int8")
        worst = max(worst, g["served_gap"].max())
        ctl = max(ctl, g["control_gap"].max())
    assert worst > 10 * GAP_TOL and ctl > 10 * GAP_TOL


@pytest.mark.parametrize("rule", RULES)
def test_an_eos_inside_a_block_ends_the_request_there(rule):
    cfg = tiny(rule)
    model, weights = model_of(cfg, 13)
    prompt = np.random.default_rng(3).integers(1, 254, 18).tolist()
    eng = engine_of(model)
    rid = eng.add_request(prompt, max_new_tokens=14)
    eng.run_until_done()
    free = eng.result(rid)
    # the token at the second place of the request's second block
    eos = free[3]
    cut = list(free).index(eos) + 1
    eng = engine_of(model)
    rid = eng.add_request(prompt, max_new_tokens=14, eos_token_id=eos)
    other = eng.add_request(prompt[:9], max_new_tokens=11)
    eng.run_until_done()
    out = eng.result(rid)
    assert list(out) == list(free)[:cut] and out[-1] == eos
    assert eng.status(rid).value == "eos"
    assert len(eng.result(other)) == 11
    assert not any(eng.sched.slots) and not eng.sched.in_flight.any()
    assert not eng.sched.ahead.any()


@pytest.mark.parametrize("rule", RULES)
def test_one_step_ahead_and_landing_every_step_serve_the_same(rule):
    """``decode_block="auto"`` lands every dispatch before the next is
    planned; the default launches block N+1 before block N is read."""
    cfg = tiny(rule)
    model, _ = model_of(cfg, 14)
    reqs = _requests(cfg, 4)
    outs = []
    for kw in ({}, {"decode_block": "auto"}):
        eng = engine_of(model, **kw)
        rids = [eng.add_request(p, max_new_tokens=k) for p, k in reqs]
        eng.run_until_done()
        outs.append([(list(eng.result(r)), eng.result(r).steps)
                     for r in rids])
    assert outs[0] == outs[1]


def test_recompute_after_preemption_serves_the_same_tokens():
    """B is preempted between two blocks (its prompt becomes prompt +
    output so far, whose last ``len % 4`` tokens are the known head of the
    next block it generates) and comes back into another slot."""
    cfg = tiny()
    model, weights = model_of(cfg, 15)
    rng = np.random.default_rng(5)
    a = rng.integers(1, 254, 20).tolist()
    b = rng.integers(1, 254, 19).tolist()
    eng = engine_of(model)
    want = eng.add_request(b, max_new_tokens=14)
    eng.run_until_done()
    want = eng.result(want)
    eng = engine_of(model)
    ra = eng.add_request(a, max_new_tokens=3)
    rb = eng.add_request(b, max_new_tokens=14)
    while not eng.status(ra).terminal:
        eng.step()
    eng._drain()
    req = eng.sched.slots[1]
    assert req is not None and req.rid == rb and 0 < len(req.out) < 14
    assert eng.sched.preempt_youngest(excluding=None)
    eng.step()
    assert eng.sched.slots[0] is req and eng.sched.slots[1] is None
    eng.run_until_done()
    assert eng.sched.preemptions == 1
    out = eng.result(rb)
    assert list(out) == list(want)
    assert ref.served_token_gaps(cfg, weights, b, list(out), 128
                                 )["served_gap"].max() < GAP_TOL


def test_a_request_that_ends_at_max_len_gets_its_whole_last_block():
    """prompt + max_new == max_len: the last block's commit brings the
    slot's length to ``max_len`` before its tokens are emitted, and every
    one of them is still the request's."""
    cfg = tiny()
    model, weights = model_of(cfg, 18)
    eng = engine_of(model)
    prompt = np.random.default_rng(9).integers(1, 254, 100).tolist()
    rid = eng.add_request(prompt, max_new_tokens=28)
    eng.run_until_done()
    out = eng.result(rid)
    assert len(out) == 28 and eng.status(rid).value == "finished"
    assert ref.served_token_gaps(cfg, weights, prompt, list(out), 128
                                 )["served_gap"].max() < GAP_TOL


def test_a_lost_dispatch_recalls_whole_blocks():
    cfg = tiny()
    model, _ = model_of(cfg, 16)
    eng = engine_of(model)
    prompt = np.random.default_rng(6).integers(1, 254, 10).tolist()
    rid = eng.add_request(prompt, max_new_tokens=12)
    for _ in range(3):
        eng.step()
    assert eng.sched.ahead[0] == 4 and eng.sched.in_flight[0] in (2, 4)
    before = int(eng.sched.lens[0])
    eng.sched.recall()
    assert eng.sched.lens[0] == before - 4 and eng.sched.lens[0] % 4 == 0
    del rid


# -------------------------------------------------- (e) counters and spans

def test_block_counters_and_dispatch_attributes_by_hand():
    """One request, 10 prompt tokens (2 whole blocks prefilled in one
    chunk, 2 tokens known in the first block) and 9 new ones: blocks of 2,
    4 and 3 tokens; 3 dispatches of 4 denoising forwards and a committing
    one, one live sequence each."""
    cfg = tiny()
    model, _ = model_of(cfg, 17)
    from bench.traffic.open_loop_http import _spy_on_runner
    obs.enable()
    try:
        obs.reset()
        eng = engine_of(model)
        launch, attrs, spied = eng.runner._launch, [], []

        def recording(key, prog, a, *args):
            attrs.append(dict(a))
            return launch(key, prog, a, *args)
        eng.runner._launch = recording
        _spy_on_runner([eng], spied)
        prompt = np.random.default_rng(8).integers(1, 254, 10).tolist()
        rid = eng.add_request(prompt, max_new_tokens=9)
        eng.run_until_done()
        assert len(eng.result(rid)) == 9
        snap = obs.snapshot()
    finally:
        obs.disable()
    # what the benchmark's spy records of a dispatch is what the span says
    keys = ("kind", "rows", "ctx_sum", "start", "k")
    assert ([{k: a[k] for k in keys if k in a} for a in attrs]
            == [{k: sp[k] for k in keys if k in sp} for sp in spied])
    spans = [a for a in attrs if a["kind"] == "decode"]
    assert [a["ctx_sum"] for a in spans] == [9, 13, 17]
    label = eng._m.label

    def read(name, **labels):
        return sum(s["value"] for s in snap[name]["series"]
                   if s["labels"]["engine"] == label
                   and all(s["labels"].get(k) == v for k, v in labels.items()))
    assert read("serving_blocks_total") == 3
    # the first dispatch compiles: its counts come home all the same
    assert read("serving_block_forwards_total", kind="denoise") == 12
    assert read("serving_block_forwards_total", kind="commit") == 3
    assert read("serving_block_sequence_forwards_total") == 15
    assert read("serving_generated_tokens_total") == 9
    assert read("serving_dispatches_total", kind="decode") == 3
    assert read("serving_dispatches_total", kind="prefill") == 1
    # the expert layer ran once a layer a forward: 2 layers x 15 + 2 x 1
    assert read("serving_moe_calls_total", kind="decode") == 30
    assert read("serving_moe_calls_total", kind="prefill") == 2
    # rows routed: 4 a live sequence a forward
    assert read("serving_moe_rows_total", kind="decode") == 30 * 4
    for a in spans:
        assert a["k"] == 4 and a["rows"] == 1


# ------------------------------------------------ (f) what a block model refuses

@pytest.mark.parametrize("kw,reason", [
    ({"prefix_cache": True}, "prefill yields"),
    ({"spec_decode": SpecConfig()}, "causal mask"),
    ({"host_cache_bytes": 1 << 20}, "prefix cache"),
    ({"decode_block": 2}, "one block of the model's own"),
    ({"decode_block": 4}, "one block of the model's own"),
], ids=["prefix_cache", "spec_decode", "host_cache_bytes", "decode_block_2",
        "decode_block_4"])
def test_a_block_model_refuses_by_name(kw, reason):
    model, _ = model_of(tiny(), 1)
    with pytest.raises(NotImplementedError, match="generates by blocks") as e:
        engine_of(model, **kw)
    assert reason in str(e.value) and next(iter(kw)) in str(e.value)


def test_a_block_model_refuses_disaggregation_and_page_handoff():
    model, _ = model_of(tiny(), 1)
    with pytest.raises(NotImplementedError, match="DisaggEngine.*by blocks"):
        DisaggEngine(model, max_batch=2, max_len=64, page_size=8)
    eng = engine_of(model)
    with pytest.raises(NotImplementedError, match="DisaggEngine.*by blocks"):
        DisaggEngine(prefill_engines=[eng], decode_engines=[eng])
    for call in (lambda: eng.export_pages([b"k"]),
                 lambda: eng.import_pages({"keys": [], "block": ()})):
        with pytest.raises(NotImplementedError, match="_pages.*by blocks"):
            call()
    with pytest.raises(ValueError, match="whole blocks"):
        engine_of(model, page_size=6)
    with pytest.raises(ValueError, match="one block, not 1 steps"):
        eng.runner._build_decode(1)


def test_config_refuses_what_is_not_the_published_block():
    with pytest.raises(ValueError, match="remasking"):
        sdar.SDARConfig.tiny(remasking="random")
    with pytest.raises(NotImplementedError, match="sliding_window"):
        sdar.SDARConfig.tiny(sliding_window=128)
    with pytest.raises(ValueError, match="mask_token_id"):
        sdar.SDARConfig.tiny(mask_token_id=256)


# ----------------- (g) a plan with block 0 lowers to the programs it did

# sha256 (first 16 hex) of the lowered text of the decode and prefill
# programs of a SolarOpen2Config.tiny() engine (4 slots, pages of 16, chunks
# of 32, 33 pages, reference attention) on the CPU, as the commit before
# this model came (f2a4b5f) lowers them: the plan gained its block fields,
# the runner a block program, the prefill a branch on the plan's block, the
# multi-query attention a horizon, and Solar's expert layer now calls
# ``models/dropless.py`` - for a plan with block 0 the text must be the
# parent's, byte for byte. (The Llama engine's eight programs are held to
# their hashes in ``tests/test_solar_open2.py``.) A change of jax changes
# the text: take the hashes anew from that commit and this one.
SOLAR_PROGRAMS = {"decode1": "10a50b028ebd2665", "prefill": "cf771d16093bd871"}


def test_solar_programs_lower_to_the_parents_text():
    from paddle_tpu.inference.engine.runner import ModelRunner
    paddle.seed(0)
    model = so.SolarOpen2ForCausalLM(so.SolarOpen2Config.tiny())
    model.eval()
    r = ModelRunner(model, max_batch=4, page_size=16, prefill_chunk=32,
                    n_pages=33, use_kernel=False)
    assert r.plan.block == 0
    B, S = 4, 8
    i32, f32 = np.int32, np.float32
    dec = [np.zeros(B, i32), np.zeros(B, i32), np.zeros((B, S), i32),
           np.ones(B, i32), np.ones(B, i32), np.ones(B, f32), np.ones(B, f32),
           np.zeros(B, i32), np.zeros(B, i32), np.zeros(B, i32),
           np.zeros(B, i32), np.zeros(B, i32)]
    pre = [np.zeros(32, i32), i32(0), np.zeros(S, i32), i32(5), i32(1),
           f32(1), f32(1), i32(0), i32(0), i32(0)]
    texts = {"decode1": r._build_decode(1).lower(r.W, r.cache, *dec),
             "prefill": r._build_prefill().lower(r.W, r.cache, *pre)}
    for name, lowered in texts.items():
        digest = hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]
        assert digest == SOLAR_PROGRAMS[name], name
