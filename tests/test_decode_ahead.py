"""The decode loop one step ahead (``LLMEngine.step``): step N+1 is planned
from counts and launched from the device's own tokens before step N's are
read home. On the CPU, tiny Llama and tiny Solar-Open2 plans: the tokens
are those of the references the parity tests use (``model.generate``, the
benchmark's plain forward) and of the same engine held in lock step; an
``eos`` found a step late; what must land the step in flight first; the
order of the recorded spans and the counter; the two hooks the benchmark's
files hang on ``runner.run_decode``.
"""
import glob
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
from paddle_tpu.inference.serving import LLMEngine, RequestStatus  # noqa: E402
from paddle_tpu.models import solar_open2 as so  # noqa: E402
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM  # noqa: E402
from paddle_tpu.testing.faults import (FAULTS, Always, FailNth,  # noqa: E402
                                       injected)

from bench import run as harness  # noqa: E402
from bench.builders import solar_engine  # noqa: E402
from bench.reference import solar_open2 as ref  # noqa: E402

# the served token's reference logit below the reference's best: 0 unless
# two logits tie to rounding (tests/test_solar_open2.py)
GAP_TOL = 1e-4
SAMPLED = dict(do_sample=True, top_p=0.8, temperature=0.9)


@pytest.fixture(scope="module")
def llama():
    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=176,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128))
    model.eval()
    return model


@pytest.fixture(scope="module")
def solar():
    cfg = dict(harness.load_cell("solar-open2-reason-saturated",
                                 rehearsal=True)[1])
    weights = ref.init_weights(cfg, 5)
    model = so.SolarOpen2ForCausalLM(
        solar_engine.solar_config(cfg),
        leaves=solar_engine.program_leaves(ref.init_weights(cfg, 5)))
    model.eval()
    return cfg, weights, model


def engine(model, **kw):
    kw = {"max_batch": 3, "max_len": 96, "page_size": 8, "prefill_chunk": 16,
          "debug_refcount_audit": True, **kw}
    return LLMEngine(model, **kw)


def generate(model, prompt, n, **kw):
    out = model.generate(paddle.to_tensor(np.asarray(prompt)[None, :]),
                         max_new_tokens=n, **kw)
    return np.asarray(out.numpy())[0, len(prompt):].tolist()


def served_gaps(cfg, weights, prompt, out):
    logits = np.asarray(ref.forward_logits(
        cfg, weights, jnp.asarray(list(prompt) + list(out[:-1]), jnp.int32)))
    at = logits[len(prompt) - 1:]
    return at.max(-1) - at[np.arange(len(out)), out]


def serve(eng, script, lock_step=False):
    """``script``: (after how many steps, prompt, keywords of add_request)
    in order. ``lock_step``: every step's tokens come home before the next
    is planned, as before the loop ran ahead. Returns the results in the
    script's order, and whether a step ever ended with one in flight."""
    script, rids, steps, flew = list(script), [], 0, False
    while script or eng.sched.waiting or any(
            s is not None for s in eng.sched.slots):
        while script and script[0][0] <= steps:
            _, prompt, kw = script.pop(0)
            rids.append(eng.add_request(prompt, **kw))
        eng.step()
        flew |= eng._flight is not None
        if lock_step:
            eng._drain()
        steps += 1
        assert steps < 500
    return [eng.result(r) for r in rids], flew


def prompts(vocab, sizes, seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, n).tolist() for n in sizes]


# ---------------- (a) the tokens are the references', whatever runs ahead

@pytest.mark.parametrize("pages", ["auto", "int8"], ids=["bf16_pages", "int8"])
@pytest.mark.parametrize("k", [1, 2], ids=["k1", "k2"])
def test_llama_serves_the_references_tokens(llama, k, pages):
    """Greedy and fixed-seed sampled requests through three slots, two of
    them joining from prefill after the first succession of decode steps
    has begun, one through a slot another has used. bf16 pages: the tokens
    of ``model.generate``; int8 pages (which ``generate`` has not): those
    of the same engine in lock step, as for every case."""
    a, b, c, d, e = prompts(128, (5, 19, 3, 11, 7), seed=k)
    script = [(0, a, dict(max_new_tokens=9)),
              (0, b, dict(max_new_tokens=14, seed=1234, **SAMPLED)),
              (0, c, dict(max_new_tokens=6)),
              (7, d, dict(max_new_tokens=8)),
              (9, e, dict(max_new_tokens=7, seed=77, **SAMPLED))]
    kw = dict(decode_block=k, kv_cache_dtype=pages)
    eng = engine(llama, **kw)
    got, flew = serve(eng, script)
    assert flew
    assert got == serve(engine(llama, **kw), script, lock_step=True)[0]
    assert [len(o) for o in got] == [9, 14, 6, 8, 7]
    if pages == "auto":
        assert got == [generate(llama, a, 9),
                       generate(llama, b, 14, seed=1234, **SAMPLED),
                       generate(llama, c, 6), generate(llama, d, 8),
                       generate(llama, e, 7, seed=77, **SAMPLED)]
    # one decode program a K, compiled once whatever the dispatch before was
    programs = eng.runner._decode_programs
    assert set(programs) <= {1, 2} and k in programs
    assert all(p._cache_size() == 1 for p in programs.values())


@pytest.mark.parametrize("k", [1, 2], ids=["k1", "k2"])
def test_solar_serves_the_references_tokens(solar, k):
    """Pages + state pool + routing counts riding behind the tokens: greedy
    requests against the plain forward at every served position, a
    fixed-seed sampled one against the engine in lock step; four requests
    through three slots, one joining mid-succession."""
    cfg, weights, model = solar
    a, b, c, d = prompts(cfg["vocab_size"], (5, 16, 24, 33), seed=10 + k)
    script = [(0, a, dict(max_new_tokens=9)),
              (0, b, dict(max_new_tokens=12, seed=5, **SAMPLED)),
              (0, c, dict(max_new_tokens=7)),
              (8, d, dict(max_new_tokens=8))]
    obs.reset()
    obs.enable()
    try:
        eng = engine(model, decode_block=k)
        got, flew = serve(eng, script)
        calls = sum(s["value"] for s in eng.metrics()[
            "serving_moe_calls_total"]["series"])
    finally:
        obs.disable()
    assert flew and [len(o) for o in got] == [9, 12, 7, 8]
    assert got == serve(engine(model, decode_block=k), script,
                        lock_step=True)[0]
    for p, out in ((a, got[0]), (c, got[2]), (d, got[3])):
        assert served_gaps(cfg, weights, p, out).max() < GAP_TOL
    # the counts came home with every dispatch, read in launch order
    assert calls > 0 and not eng.runner.take_routing_counts().any()
    assert all(p._cache_size() == 1
               for p in eng.runner._decode_programs.values())


def test_under_a_mesh_one_program_and_the_one_device_tokens(llama):
    """``prev`` goes back in where the program left it: under a mesh too a
    K compiles once, and the tokens are the one-device engine's."""
    from jax.sharding import Mesh
    script = [(0, p, dict(max_new_tokens=8))
              for p in prompts(128, (5, 12), seed=3)]
    mesh = Mesh(np.array(jax.devices()[:2]), ("mp",))
    eng = engine(llama, mesh=mesh, max_batch=2)
    got, flew = serve(eng, script)
    assert flew and got == serve(engine(llama, max_batch=2), script)[0]
    assert eng.runner._decode_programs[1]._cache_size() == 1


# ------------------------------- (b) an eos found one step after its step

def first_fresh(out, start=2):
    """Index >= start of a token that no earlier position holds."""
    return next(j for j in range(start, len(out)) if out[j] not in out[:j])


def count_decodes(eng):
    calls = []
    run_decode = eng.runner.run_decode

    def counted(*a, _f=run_decode, **k):
        calls.append(a[0])
        return _f(*a, **k)
    eng.runner.run_decode = counted
    return calls


@pytest.mark.parametrize("plan", ["llama", "solar"])
def test_an_eos_found_a_step_late(plan, llama, solar, request):
    """Step N produced the eos, step N+1 was launched before the host saw
    it: nothing after the eos is emitted, the status is EOS, the row's
    wasted write fell in pages that were its own (the audit runs after
    every step), and the slot's next tenant - pages and recurrent state
    as the wasted step left them - decodes as in a fresh engine."""
    model = llama if plan == "llama" else solar[2]
    vocab = 128 if plan == "llama" else solar[0]["vocab_size"]
    x, y = prompts(vocab, (21, 18), seed=3)
    free, = serve(engine(model, max_batch=1),
                  [(0, x, dict(max_new_tokens=12))])[0]
    fresh, = serve(engine(model, max_batch=1),
                   [(0, y, dict(max_new_tokens=6))])[0]
    j = first_fresh(free)
    eng = engine(model, max_batch=1)
    calls = count_decodes(eng)
    rid = eng.add_request(x, max_new_tokens=12, eos_token_id=free[j])
    eng.run_until_done()
    assert eng.result(rid) == free[:j + 1]
    assert eng.status(rid) is RequestStatus.EOS
    # tokens 2..j+1 took j decode steps; one more was launched ahead
    assert len(calls) == j + 1
    assert eng._flight is None and not eng.sched.in_flight.any()
    tenant = eng.add_request(y, max_new_tokens=6)
    eng.run_until_done()
    assert eng.result(tenant) == fresh
    assert eng.audit_refcounts() == []


def test_an_eos_beside_a_row_that_decodes_on(llama):
    """The eos row's slot is re-let while its neighbour is mid-succession:
    the neighbour's tokens and the new tenant's are the references'."""
    a, b, c = prompts(128, (9, 6, 13), seed=8)
    want_a = generate(llama, a, 12)
    j = first_fresh(want_a)
    eng = engine(llama, max_batch=2)
    ra = eng.add_request(a, max_new_tokens=12, eos_token_id=want_a[j])
    rb = eng.add_request(b, max_new_tokens=16)
    rc = eng.add_request(c, max_new_tokens=5)     # waits for a's slot
    eng.run_until_done()
    assert eng.result(ra) == want_a[:j + 1]
    assert eng.status(ra) is RequestStatus.EOS
    assert eng.result(rb) == generate(llama, b, 16)
    assert eng.result(rc) == generate(llama, c, 5)


# ------------ (c) what lands the step in flight before it touches a slot

def step_until_flying(eng, n=1):
    """Step until a decode step is in flight for the n-th time."""
    for _ in range(200):
        eng.step()
        if eng._flight is not None:
            n -= 1
            if n == 0:
                return
    raise AssertionError("no decode step was ever in flight")


def test_cancel_with_a_step_in_flight(llama):
    a, b, c = prompts(128, (5, 9, 14), seed=4)
    eng = engine(llama)
    ra, rb, rc = (eng.add_request(p, max_new_tokens=12) for p in (a, b, c))
    step_until_flying(eng, n=3)
    had = len(eng.sched.lookup(rb).out)
    assert eng.cancel(rb)
    # what it had been served came home before the slot went
    assert eng._flight is None
    assert eng.status(rb) is RequestStatus.CANCELLED
    assert len(eng.result(rb)) == had + 1
    assert eng.result(rb) == generate(llama, b, 12)[:had + 1]
    eng.run_until_done()
    assert eng.result(ra) == generate(llama, a, 12)
    assert eng.result(rc) == generate(llama, c, 12)


def test_a_cancel_that_comes_too_late(llama):
    """The token in flight was the request's last: it finished."""
    a, = prompts(128, (5,), seed=5)
    eng = engine(llama)
    rid = eng.add_request(a, max_new_tokens=2)
    step_until_flying(eng)
    assert not eng.cancel(rid)
    assert eng.status(rid) is RequestStatus.FINISHED
    assert eng.result(rid) == generate(llama, a, 2)


def test_growth_that_preempts_lands_the_step_first(llama):
    """A pool too small for both: growing one request's pages preempts the
    other, whose tokens in flight are emitted first - its fold (prompt +
    output so far) holds every token it was served, and both finish with
    the references' tokens in order."""
    a, b = prompts(128, (6, 6), seed=6)
    eng = engine(llama, max_batch=2, max_len=32, page_size=4, page_pool=8,
                 prefill_chunk=8)
    ra = eng.add_request(a, max_new_tokens=20)
    rb = eng.add_request(b, max_new_tokens=20)
    folds = []
    preempt = eng.sched.preempt_youngest

    def watched(excluding):
        assert eng._flight is None and not eng.sched.in_flight.any()
        folds.append(1)
        return preempt(excluding)
    eng.sched.preempt_youngest = watched
    eng.run_until_done()
    assert folds and eng.sched.preemptions == len(folds)
    assert eng.result(ra) == generate(llama, a, 20)
    assert eng.result(rb) == generate(llama, b, 20)


@pytest.mark.parametrize("transient", [True, False],
                         ids=["transient", "poison"])
def test_a_step_fault_at_decode_with_a_step_in_flight(llama, transient):
    """The fault fires while step N+1 is planned and step N is in flight:
    N is emitted first, then the retry (transient) or the sweep of one-slot
    probes (poison: the one request always fails, alone too). Survivors'
    tokens are complete and in order."""
    a, b, c = prompts(128, (5, 9, 14), seed=7)
    eng = engine(llama)
    ra, rb, rc = (eng.add_request(p, max_new_tokens=10) for p in (a, b, c))
    step_until_flying(eng, n=2)
    if transient:
        with injected("serving.step", FailNth({1, 4}), transient=True):
            eng.run_until_done()
        assert eng.step_retries >= 1 and eng.sched.quarantined == 0
        assert eng.result(rb) == generate(llama, b, 10)
    else:
        FAULTS.install("serving.step", Always(),
                       match=lambda ctx: (ctx.get("phase") == "decode"
                                          and rb in ctx.get("rids", ())))
        try:
            eng.run_until_done()
        finally:
            FAULTS.reset()
        assert eng.status(rb) is RequestStatus.FAILED
        assert eng.quarantine_probes >= 2
        # it keeps what had come home, the token in flight included
        out = eng.result(rb)
        assert len(out) >= 2 and out == generate(llama, b, 10)[:len(out)]
    assert eng.step_failures >= 1
    assert eng.result(ra) == generate(llama, a, 10)
    assert eng.result(rc) == generate(llama, c, 10)
    assert eng.audit_refcounts() == []


def test_lost_tokens_are_decoded_again(llama):
    """The wait itself fails (the device lost the dispatch): lengths go
    back to what was emitted, the sweep decodes every row again from
    there, and nobody's stream has a hole."""
    a, b = prompts(128, (5, 9), seed=9)
    eng = engine(llama, max_batch=2)
    ra, rb = (eng.add_request(p, max_new_tokens=10) for p in (a, b))
    step_until_flying(eng, n=2)

    class Lost:
        def __array__(self, *a, **k):
            raise RuntimeError("tokens lost")
    eng._flight = eng._flight._replace(toks=Lost())
    eng.run_until_done()
    assert eng.step_failures == 1 and eng.sched.quarantined == 0
    assert eng.result(ra) == generate(llama, a, 10)
    assert eng.result(rb) == generate(llama, b, 10)


def test_what_syncs_every_step_never_flies(llama):
    """``decode_block="auto"`` (the fit wants a dispatch's own wall time)
    and a speculating engine (the drafts want every token) land each
    decode in its own step; the tokens are the same."""
    from paddle_tpu.inference.engine.spec import SpecConfig
    script = [(0, p, dict(max_new_tokens=12))
              for p in prompts(128, (5, 12), seed=2)]
    want = [generate(llama, p, 12) for _, p, _ in script]
    for kw in (dict(decode_block="auto", decode_block_max=4),
               dict(spec_decode=SpecConfig(max_draft=2))):
        got, flew = serve(engine(llama, **kw), script)
        assert got == want and not flew, kw


# ---------------------------------- (d) the spans' order, and the counter

def loop_thread_events(trace_dir):
    """``(name, start_ns, stats)`` of the thread that ran ``engine.step``,
    from the profiler's xplane file, in order."""
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                     recursive=True)[0]
    host = [p for p in ProfileData.from_file(path).planes
            if p.name.startswith("/host:CPU")][0]
    for line in host.lines:
        events = [(e.name, e.start_ns, dict(e.stats)) for e in line.events]
        if any(name == "engine.step" for name, *_ in events):
            return sorted(events, key=lambda e: e[1])
    raise AssertionError("no thread of the trace ran engine.step")


def test_launch_comes_before_the_wait_and_the_counter_counts(llama, tmp_path):
    """One request, 8 tokens: the prefill chunk samples the first, seven
    decode steps the rest. By the recorded spans step N+1's
    ``runner.launch`` comes before step N's ``runner.wait``; the counter
    and ``runner.dispatch``'s ``ahead`` say 1 plain launch and 6 ahead."""
    a, = prompts(128, (5,), seed=1)
    eng = engine(llama)
    serve(eng, [(0, a, dict(max_new_tokens=3))])      # compile both programs
    obs.reset()
    obs.enable()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        got, _ = serve(eng, [(0, a, dict(max_new_tokens=8))])
    finally:
        jax.profiler.stop_trace()
        snap = eng.metrics()
        obs.disable()
    assert got == [generate(llama, a, 8)]
    events = loop_thread_events(tmp_path)
    # from the first decode launch on (the chunk's own wait lies before)
    order = [("L" if name == "runner.launch" else "W")
             for name, _, st in events
             if name == "runner.wait"
             or (name == "runner.launch" and st.get("kind") == "decode")]
    order = "".join(order[order.index("L"):])
    assert order == "L" + "LW" * 6 + "W"
    ahead = [st["ahead"] for name, _, st in events
             if name == "runner.dispatch" and st.get("kind") == "decode"]
    assert ahead == [0] + [1] * 6
    launches = {s["labels"]["ahead"]: s["value"]
                for s in snap["serving_decode_launches_total"]["series"]}
    assert launches == {"0": 1, "1": 6}
    decodes, = [s["value"] for s in snap["serving_dispatches_total"]["series"]
                if s["labels"]["kind"] == "decode"]
    assert decodes == 7


def test_the_counter_over_a_scripted_run(llama):
    """Two requests of 6 and 9 tokens and a third that joins through a
    prefill chunk: a succession breaks at the chunk and where the engine
    runs empty, and nowhere else."""
    a, b, c = prompts(128, (5, 9, 7), seed=2)
    obs.reset()
    obs.enable()
    try:
        eng = engine(llama)
        # a: 1 chunk, b: 1 chunk, then decode: steps 2, 3, 4 fly ahead...
        got, _ = serve(eng, [(0, a, dict(max_new_tokens=6)),
                             (0, b, dict(max_new_tokens=9)),
                             (5, c, dict(max_new_tokens=4))])
        snap = eng.metrics()
    finally:
        obs.disable()
    assert got == [generate(llama, a, 6), generate(llama, b, 9),
                   generate(llama, c, 4)]
    launches = {s["labels"]["ahead"]: s["value"]
                for s in snap["serving_decode_launches_total"]["series"]}
    # steps 0, 1: the chunks of a and b. Steps 2-4: decode 1 (plain), 2, 3
    # (ahead). Step 5: c's chunk, launched behind decode 3, which lands.
    # Step 6 on: decode 4 (plain: all tokens are on the host), then ahead
    # until b's last: a has 6 tokens after decode 5, c after decode 6, b
    # after decode 8
    assert launches == {"0": 2, "1": 6}


# --------------------------- (e) the two hooks the benchmark's files hang

def test_a_spy_sees_host_lengths_once_a_dispatch(llama):
    """Written like ``bench/traffic/open_loop_http.py:_spy_on_runner``:
    ``lens`` and ``active`` are host arrays at the call (a device value
    would make the spy wait for the step in flight), the lengths count the
    tokens in flight, and what the spy hands through is what is served."""
    a, b = prompts(128, (5, 9), seed=3)
    eng = engine(llama)
    seen = []
    run_decode = eng.runner.run_decode

    def decode(k, tokens, lens, tables, active, *rest, _f=run_decode):
        assert type(lens) is np.ndarray and type(active) is np.ndarray
        assert type(tokens) is np.ndarray and type(tables) is np.ndarray
        ctx = (np.asarray(lens) + 1)[np.asarray(active) > 0]
        out = _f(k, tokens, lens, tables, active, *rest)
        seen.append((int(len(ctx)), int(ctx.sum()), int(k)))
        return out
    eng.runner.run_decode = decode
    got, flew = serve(eng, [(0, a, dict(max_new_tokens=6)),
                            (0, b, dict(max_new_tokens=6))])
    assert flew and got == [generate(llama, a, 6), generate(llama, b, 6)]
    # five decode steps of two rows: each reads one more token a row
    assert seen == [(2, 5 + 9 + 2 + 2 * i, 1) for i in range(5)]


def test_tokens_altered_where_they_are_produced_are_what_is_served(llama):
    """Written like ``bench/tests/test_cells.py:alter_tokens``: the wrapper
    reads the dispatch at once and returns a plain array in its place; the
    engine emits what it was handed back."""
    a, = prompts(128, (5,), seed=4)
    want = generate(llama, a, 8)
    eng = engine(llama)
    run_decode = eng.runner.run_decode

    def altered(*args, _f=run_decode, **k):
        toks = np.asarray(_f(*args, **k))
        return np.where(toks > 1, toks - 1, toks + 1).astype(toks.dtype)
    eng.runner.run_decode = altered
    got, = serve(eng, [(0, a, dict(max_new_tokens=8))])[0]
    assert got[0] == want[0] and got[1] != want[1]
    # every decode step's token is one off what the device made of the
    # token it kept (the device decodes on from its own)
    assert got[1:] == [t - 1 if t > 1 else t + 1 for t in want[1:]]
