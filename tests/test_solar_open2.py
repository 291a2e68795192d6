"""Solar-Open2 through the engine, on the CPU at the tiny size of
``bench/rehearsal/solar-tiny.json`` (float32, seeded random weights): the
model's forward against the benchmark's plain reference, the engine through
pages AND recurrent state against the same, a chip's share of the experts
tied to the uncut layer, and what a recurrent model refuses.
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
from paddle_tpu.models import solar_open2 as so  # noqa: E402
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM  # noqa: E402

from bench import run as harness  # noqa: E402
from bench.builders import solar_engine  # noqa: E402
from bench.reference import solar_open2 as ref  # noqa: E402

CELL = "solar-open2-reason-saturated"
# float32 on both sides, the same products summed in another order: logits
# of magnitude 0.7 agree to 6e-7 ... 1.7e-6 (read over seeds 11-13 at one and
# two periods). bf16 operands move them by 2.4e-2 ... 6.8e-2 (the same
# seeds; checked below), three orders of magnitude past the tolerance.
LOGIT_TOL = 2e-5
# the served token's reference logit below the reference's best: 0 unless
# two logits tie to rounding
GAP_TOL = 1e-4


def tiny(**over):
    cfg = dict(harness.load_cell(CELL, rehearsal=True)[1])
    cfg.update(over)
    return cfg


def model_of(cfg, seed, dtype=None):
    weights = ref.init_weights(cfg, seed)
    leaves = solar_engine.program_leaves(ref.init_weights(cfg, seed))
    if dtype is not None:
        leaves = jax.tree_util.tree_map(lambda a: a.astype(dtype), leaves)
    model = so.SolarOpen2ForCausalLM(solar_engine.solar_config(cfg),
                                     leaves=leaves)
    model.eval()
    return model, weights


def ref_logits(cfg, weights, tokens):
    return np.asarray(ref.forward_logits(cfg, weights,
                                         jnp.asarray(tokens, jnp.int32)))


def served_gaps(cfg, weights, prompt, out):
    """At every served position, how far the served token's reference
    logit lies below the reference's best (the benchmark's comparison)."""
    logits = ref_logits(cfg, weights, list(prompt) + list(out[:-1]))
    at = logits[len(prompt) - 1:]
    return at.max(-1) - at[np.arange(len(out)), out]


# ------------------------------------------------------ (a) the forward pass

@pytest.mark.parametrize("layers", [4, 8], ids=["one_period", "two_periods"])
def test_forward_equals_the_plain_reference(layers):
    cfg = tiny(num_hidden_layers=layers)
    model, weights = model_of(cfg, 11)
    tokens = np.random.default_rng(layers).integers(1, cfg["vocab_size"], 48)
    want = ref_logits(cfg, weights, tokens)
    got = np.asarray(model(tokens[None])._data)[0]
    assert got.dtype == np.float32
    assert np.abs(got - want).max() < LOGIT_TOL
    # the tolerance would catch operands in the nearest precision below
    low, _ = model_of(cfg, 11, jnp.bfloat16)
    got16 = np.asarray(low(tokens[None])._data, np.float32)[0]
    assert np.abs(got16 - want).max() > 10 * LOGIT_TOL


def test_config_holds_gqa_layers_to_gqa_interval():
    so.SolarOpen2Config.tiny(num_hidden_layers=8, gqa_layers=[0, 4])
    with pytest.raises(ValueError, match="gqa_layers"):
        so.SolarOpen2Config.tiny(num_hidden_layers=8, gqa_layers=[0, 3])
    with pytest.raises(ValueError, match="whole periods"):
        so.SolarOpen2Config.tiny(num_hidden_layers=6)
    with pytest.raises(ValueError, match="router"):
        so.SolarOpen2Config.tiny(expert_offset=14)
    with pytest.raises(NotImplementedError, match="use_rope"):
        so.SolarOpen2Config.tiny(use_rope=True)


# ---------------------------------------- (b) the engine: pages and state

def engine_of(model, **kw):
    kw = {"max_batch": 3, "max_len": 96, "page_size": 8, "prefill_chunk": 16,
          **kw}
    return LLMEngine(model, **kw)


@pytest.mark.parametrize("layers", [4, 8], ids=["one_period", "two_periods"])
def test_engine_serves_the_references_tokens_at_every_position(layers):
    """Chunked prefill, then decode through the GQA layer's pages and the
    KDA layers' state pool: prompts that end inside a chunk and a page (5),
    at both (16), at a page inside the second chunk (24) and past both
    (33); four requests through three slots, so one is admitted into a
    slot another has used."""
    cfg = tiny(num_hidden_layers=layers)
    model, weights = model_of(cfg, 5)
    eng = engine_of(model)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, cfg["vocab_size"], n).tolist()
               for n in (5, 16, 24, 33)]
    rids = [eng.add_request(p, max_new_tokens=7) for p in prompts]
    eng.run_until_done()
    for p, rid in zip(prompts, rids):
        out = eng.result(rid)
        assert len(out) == 7
        assert served_gaps(cfg, weights, p, out).max() < GAP_TOL


def test_engine_after_preemption_and_readmission_into_another_slot():
    """B decodes in slot 1 and is preempted (recompute: its prompt becomes
    prompt + output so far); A has finished meanwhile, so B comes back
    into slot 0, whose state A left behind: prefill from position 0 starts
    from a zero state whatever the slot held."""
    cfg = tiny()
    model, weights = model_of(cfg, 6)
    eng = engine_of(model)
    rng = np.random.default_rng(2)
    a = rng.integers(1, cfg["vocab_size"], 20).tolist()
    b = rng.integers(1, cfg["vocab_size"], 19).tolist()
    ra = eng.add_request(a, max_new_tokens=3)
    rb = eng.add_request(b, max_new_tokens=12)
    while not eng.status(ra).terminal:
        eng.step()
    req = eng.sched.slots[1]
    assert req is not None and req.rid == rb and 0 < len(req.out) < 12
    assert eng.sched.preempt_youngest(excluding=None)
    eng.step()
    assert eng.sched.slots[0] is req and eng.sched.slots[1] is None
    eng.run_until_done()
    assert eng.sched.preemptions == 1
    out = eng.result(rb)
    assert len(out) == 12
    assert served_gaps(cfg, weights, b, out).max() < GAP_TOL


@pytest.mark.parametrize("shape,axes", [((2,), ("mp",)), ((2, 2), ("pp", "mp")),
                                        ((2, 2), ("ep", "mp"))],
                         ids=["mp2", "pp2_mp2", "ep2_mp2"])
def test_engine_under_a_mesh_serves_the_one_device_tokens(shape, axes):
    """A mesh of several devices takes the ``*_ref`` / ``ragged_dot`` path
    (M8) with the model's specs: periods over ``pp``, head and ffn dims over
    ``mp``, the experts over ``ep`` (named, nothing exchanged by hand: GSPMD
    places the collectives). Two periods, so that ``pp`` has two to split."""
    from jax.sharding import Mesh
    cfg = tiny(num_hidden_layers=8)
    model, _ = model_of(cfg, 3)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, cfg["vocab_size"], n).tolist() for n in (5, 20)]

    def serve(mesh):
        eng = LLMEngine(model, mesh=mesh, max_batch=2, max_len=64,
                        page_size=8, prefill_chunk=16)
        rids = [eng.add_request(p, max_new_tokens=5) for p in prompts]
        eng.run_until_done()
        return [eng.result(r) for r in rids]

    devices = np.array(jax.devices()[:int(np.prod(shape))]).reshape(shape)
    assert serve(Mesh(devices, axes)) == serve(None)


# ------------------------------ (f) a slot's state starts at zero, always

def test_a_used_or_dirty_slot_serves_a_fresh_engines_tokens():
    cfg = tiny()
    model, _ = model_of(cfg, 7)
    rng = np.random.default_rng(3)
    x = rng.integers(1, cfg["vocab_size"], 21).tolist()
    y = rng.integers(1, cfg["vocab_size"], 18).tolist()

    def serve(eng, prompt):
        rid = eng.add_request(prompt, max_new_tokens=6)
        eng.run_until_done()
        return eng.result(rid)

    fresh = serve(engine_of(model, max_batch=1), y)
    used = engine_of(model, max_batch=1)
    serve(used, x)
    at = used.runner._n_page_pools
    assert float(jnp.abs(used.runner.cache[at][:, 0]).max()) > 0   # x's state
    assert serve(used, y) == fresh
    dirty = engine_of(model, max_batch=1)
    cache = list(dirty.runner.cache)
    cache[at] = cache[at] + 3.0             # junk in every state and tail
    cache[at + 1] = cache[at + 1] + 3.0
    dirty.runner.cache = tuple(cache)
    assert serve(dirty, y) == fresh


# ----------------------------- (c) a chip's share, tied to the uncut layer

def expert_layer_inputs(cfg, seed):
    c = solar_engine.solar_config(cfg)
    weights = ref.init_weights(cfg, seed)
    p = weights["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(seed), (24, c.hidden_size))
    return c, p, x, jnp.ones((24,), jnp.int32)


def test_the_shares_add_up_to_the_uncut_layer():
    """16 routed experts, top-2: the four shares' routed parts (offsets 0,
    4, 8, 12; each holds 4) plus the shared expert ONCE are the uncut
    layer, in the program and in the reference."""
    whole = tiny(n_routed_experts=16, router_width=16)
    c, p, x, live = expert_layer_inputs(whole, 4)
    uncut, counts = so.experts(p, x, live, c)
    assert int(counts[2]) == 24 * 2           # every choice is held
    shared = so.experts(p, x, 0 * live, c)[0] - x       # nothing routed
    total = shared
    for offset in range(0, 16, 4):
        part = {**p, **{k: p[k][offset:offset + 4] for k in ("wg", "wu", "wd")}}
        share = so.SolarOpen2Config.tiny(experts_held=4, expert_offset=offset)
        total = total + (so.experts(part, x, live, share)[0] - x - shared)
    assert float(jnp.abs(x + total - uncut).max()) < 1e-5
    # and the reference's share is the program's
    cut = tiny(expert_offset=8)
    z = ref.sizes(cut)
    part = {**p, **{k: p[k][8:12] for k in ("wg", "wu", "wd")}}
    want = ref._experts(x, part, z, cut["rms_norm_eps"], True, 1.0, "float32")
    got = so.experts(part, x, live, solar_engine.solar_config(cut))[0]
    assert float(jnp.abs(got - want).max()) < 1e-5


# ----------------------------------------------------------- (e) dropless

def test_every_token_on_one_expert_drops_none():
    """A routing in which every row chooses experts 0 and 1 (a large
    correction bias): both groups hold all 24 rows, and the layer equals
    the dense sum by hand."""
    cfg = tiny()
    c, p, x, live = expert_layer_inputs(cfg, 9)
    bias = jnp.zeros_like(p["router_bias"]).at[:2].set(100.0)
    p = {**p, "router_bias": bias}
    got, counts = so.experts(p, x, live, c)
    assert [int(n) for n in counts] == [1, 24, 48, 2, 24]
    h = np.asarray(so.rms_norm(x, p["ln2"], c.rms_norm_eps), np.float64)
    w = {k: np.asarray(v, np.float64) for k, v in p.items()}

    def swiglu(g, u, d):
        a = h @ g
        return (a / (1 + np.exp(-a)) * (h @ u)) @ d
    s = 1 / (1 + np.exp(-(h @ w["router"])))[:, :2]
    s = s / s.sum(-1, keepdims=True)
    want = np.asarray(x, np.float64) + swiglu(w["sg"], w["su"], w["sd"])
    for e in range(2):
        want += s[:, e:e + 1] * swiglu(w["wg"][e], w["wu"][e], w["wd"][e])
    assert np.abs(np.asarray(got) - want).max() < 1e-5


# --------------------------------------------- (g) what a recurrent model refuses

@pytest.mark.parametrize("kw,reason", [
    ({"prefix_cache": True}, "skips the prefill that builds the state"),
    ({"spec_decode": SpecConfig()}, "no rollback"),
    ({"prefix_cache": True, "host_cache_bytes": 1 << 20}, "prefix_cache"),
    ({"host_cache_bytes": 1 << 20}, "carries no state"),
], ids=["prefix_cache", "spec_decode", "prefix_and_host", "host_cache_bytes"])
def test_a_recurrent_model_refuses_by_name(kw, reason):
    model, _ = model_of(tiny(), 1)
    with pytest.raises(NotImplementedError, match="recurrent-state") as e:
        engine_of(model, **kw)
    assert reason in str(e.value)


def test_a_recurrent_model_refuses_disaggregation_and_page_handoff():
    model, _ = model_of(tiny(), 1)
    with pytest.raises(NotImplementedError, match="DisaggEngine.*no state"):
        DisaggEngine(model, max_batch=2, max_len=64, page_size=8)
    eng = engine_of(model)
    with pytest.raises(NotImplementedError, match="DisaggEngine.*no state"):
        DisaggEngine(prefill_engines=[eng], decode_engines=[eng])
    for call in (lambda: eng.export_pages([b"k"]),
                 lambda: eng.import_pages({"keys": [], "block": ()})):
        with pytest.raises(NotImplementedError, match="_pages.*no state"):
            call()
    assert eng.state_bytes_per_slot() == 3 * (4 * 16 * 16 * 4 + 3 * 192 * 4)
    paddle.seed(0)
    llama = LlamaForCausalLM(LlamaConfig.tiny())
    assert LLMEngine(llama, max_len=64).state_bytes_per_slot() == 0


# ------------------------------------------- (h) the counters, by hand

def test_routing_counters_against_a_hand_count():
    """One request, 21 prompt tokens in chunks of 16 and 6 new tokens, 4
    layers: 2 chunks and 5 decode steps. Calls and rows follow from that;
    the assignments are those of the same 26 positions in the full forward
    (every layer's choices that fall on the 4 experts held)."""
    cfg = tiny()
    model, _ = model_of(cfg, 8)
    prompt = np.random.default_rng(4).integers(1, cfg["vocab_size"], 21)
    obs.reset()
    obs.enable()
    try:
        eng = engine_of(model)
        rid = eng.add_request(prompt.tolist(), max_new_tokens=6)
        eng.run_until_done()
        out = eng.result(rid)
        label = eng._m.label
        snap = obs.snapshot(prefix="serving_", labels={"engine": label})
    finally:
        obs.disable()

    def read(name, kind):
        return sum(s["value"] for s in snap[name]["series"]
                   if s["labels"]["kind"] == kind)
    L = cfg["num_hidden_layers"]
    assert read("serving_moe_calls_total", "prefill") == 2 * L
    assert read("serving_moe_calls_total", "decode") == 5 * L
    assert read("serving_moe_rows_total", "prefill") == 21 * L
    assert read("serving_moe_rows_total", "decode") == 5 * L
    # the same positions through the layers of the full forward
    c = model.config
    x = model.embed._data[jnp.asarray(list(prompt) + out[:-1])]
    live = jnp.ones((x.shape[0],), jnp.int32)
    tail = jnp.zeros((3, c.conv_channels), x.dtype)
    S0 = jnp.zeros((4, 16, 16), jnp.float32)
    held = np.zeros((L, x.shape[0]), np.int64)       # by layer and position
    for i, layer in enumerate(model.layers):
        p = layer.leaves()
        if layer.kind == "gqa":
            x = so.gqa_out(p, x, so._causal_attention(*so.gqa_qkv(p, x, c)), c)
        else:
            x = so.kda_post(p, x, so.kda_recurrence(
                S0, *so.kda_pre(p, x, tail, None, c)[0])[0], c)
        h = so.rms_norm(x, p["ln2"], c.rms_norm_eps)
        score = jax.nn.sigmoid(h @ p["router"]) + p["router_bias"]
        held[i] = np.asarray((jax.lax.top_k(score, 2)[1] < 4).sum(-1))
        x, _ = so.experts(p, x, live, c)
    assert read("serving_moe_assignments_total", "prefill") == held[:, :21].sum()
    assert read("serving_moe_assignments_total", "decode") == held[:, 21:].sum()
    touched = read("serving_moe_experts_touched_total", "decode")
    assert 0 < touched <= read("serving_moe_assignments_total", "decode")
    assert (read("serving_moe_max_load_total", "decode")
            <= read("serving_moe_rows_total", "decode"))
    gauge = snap["serving_active_slots"]["series"]
    assert len(gauge) == 1


# ------------------------- (i) the Llama engine's programs did not change

# sha256 (first 16 hex) of the lowered text of the programs of a
# LlamaConfig.tiny() engine (4 slots, pages of 16, chunks of 32, 33 pages,
# reference attention) on the CPU, as the commit before this model came
# (e0b4857) lowers them. The runner now loops over a model's kinds of
# layer; for a model of one kind it must trace to the same program, byte
# for byte. A change of jax changes the text: take the hashes anew from
# that commit and this one, and hold them equal.
#
# The four DECODE hashes were pinned anew when the decode loop went one
# step ahead (2d001c4 -> its child): the program takes two more arguments
# (``take``, ``prev``), selects each row's token between ``prev`` and the
# host's in front of the scan, and hands each row's last token back as a
# second output. A diff of the lowered text against 2d001c4's shows that
# and nothing else: the entry function's signature, one ``compare`` + one
# ``select`` before the loop, one more returned value, renumbered names;
# the scan body is the parent's line for line.
#
# All eight were pinned anew when wq, wk and wv came to be stored
# [L, out, in] (04e3a83 -> its child): a diff against 04e3a83's text shows
# that and nothing else: the two [L, in, out] leaves of another shape
# (wk, wv) in the signatures and their per-layer dynamic_slice / reshape,
# the three projections' dot_general contracting [1] x [1] where it was
# [1] x [0] (precision HIGHEST as before), renumbered private functions;
# the same count of lines in every program.
LLAMA_PROGRAMS = {
    "decode1": "ea9ed02a1b3e358e", "decode2": "89cb39884a896fe2",
    "prefill": "3a4f3a5ae74c2e05", "verify3": "c0513553d851fa35",
    "decode1.int8": "515b35dd008064c5", "decode2.int8": "9543a36bfd91bb1f",
    "prefill.int8": "16d077e15ac041ab", "verify3.int8": "50532f82ba506975",
}


@pytest.mark.parametrize("int8", [False, True], ids=["bf16_pages", "int8"])
def test_llama_programs_lower_to_the_parents_text(int8):
    from paddle_tpu.inference.engine.runner import ModelRunner
    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny())
    model.eval()
    r = ModelRunner(model, max_batch=4, page_size=16, prefill_chunk=32,
                    n_pages=33, use_kernel=False,
                    kv_cache_dtype="int8" if int8 else "auto")
    B, S = 4, 8
    i32, f32 = np.int32, np.float32
    dec = [np.zeros(B, i32), np.zeros(B, i32), np.zeros((B, S), i32),
           np.ones(B, i32), np.ones(B, i32), np.ones(B, f32), np.ones(B, f32),
           np.zeros(B, i32), np.zeros(B, i32), np.zeros(B, i32)]
    pre = [np.zeros(32, i32), i32(0), np.zeros(S, i32), i32(5), i32(1),
           f32(1), f32(1), i32(0), i32(0)]
    ver = [np.zeros((B, 3), i32)] + dec[1:]
    dec += [np.zeros(B, i32), np.zeros(B, i32)]             # take, prev
    texts = {"decode1": r._build_decode(1).lower(r.W, r.cache, *dec),
             "decode2": r._build_decode(2).lower(r.W, r.cache, *dec),
             "prefill": r._build_prefill().lower(r.W, r.cache, *pre),
             "verify3": r._build_verify(3).lower(r.W, r.cache, *ver)}
    for name, lowered in texts.items():
        digest = hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]
        assert digest == LLAMA_PROGRAMS[name + (".int8" if int8 else "")], name
