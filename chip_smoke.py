"""The quickest proof that the system still starts on the chip.

Drives both hot paths through the calls a user makes, in ONE process that
owns the chip, and exits non-zero at the first thing that is not so:

  kernels  every Pallas kernel against its jnp reference, on the device
  serve    Llama-3-8B widths (depth cut to fit one 16 GB chip), random
           weights from a seed, ``LLMEngine`` -> ``ReplicaSet`` ->
           ``start_gateway`` -> HTTP (as examples/serve_llama.py), with bf16
           and then int8 KV pages
  train    GPT-2 124M, bf16, batch 16 x 1,024, AdamW + global-norm clip,
           through ``paddle.jit.scan_steps`` (as examples/train_gpt2.py)

``--chips 4`` asks the four-chip questions instead, each in a child process
of a parent that never touches JAX: two launcher workers each taking one
chip, four one-chip replicas in one process, an ``mp=4`` engine, and a
dp=2 x mp=2 train step against the same step on one chip.

What it prints besides pass/fail is set-up (seconds to the first compiled
step and the first completed request — mostly compilation — and bytes on the
device), never a speed.  The last line of stdout is the result JSON.
"""
import argparse
import contextlib
import dataclasses
import gc
import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
T0 = time.perf_counter()

# bf16 has 8 mantissa bits (one ulp = 2^-8 = 0.0039 of the value).  Kernel and
# reference read the same bf16 operands; the reference then works in float32
# throughout, while the kernels feed the MXU bf16 (softmax probabilities
# included), accumulate in float32 and round the result to bf16.  That is a
# few ulps of the largest value: the bound below is 2e-2 of the reference's
# largest magnitude — the tolerance tests/test_pallas_kernels.py holds the
# same kernels to in the interpreter.
KERNEL_TOL = 2e-2

# dp=2 x mp=2 against one chip, bf16 weights: the two runs sum the same
# products in a different order (partial sums meet in an all-reduce; one
# chip runs the flash kernel, the mesh XLA's attention), so logits differ by
# bf16 rounding, and each step's update carries that forward.  Losses sit
# near ln(vocab) = 11.8; 5e-2 absolute is 0.4 %.
PARITY_TOL = 5e-2


@dataclasses.dataclass
class Sizes:
    """The run's sizes.  The defaults are the real ones; tests and CPU
    debugging shrink a copy."""
    gpt2: dict = dataclasses.field(default_factory=dict)   # gpt2_small as is
    train_batch: int = 16
    train_seq: int = 1024
    train_k: int = 2            # optimizer steps per dispatch
    train_calls: int = 4        # two eager capture passes, then compiled
    # Llama-3-8B widths; depth is what is cut: 8 layers are 5.3 GiB of bf16
    # weights, which with a 0.5 GiB page pool fit one 16 GB chip
    llama: dict = dataclasses.field(
        default_factory=lambda: {"num_hidden_layers": 8})
    slots: int = 8
    max_len: int = 2048
    prompt_len: int = 200       # 7 prefill chunks of 32, 13 pages of 16
    max_tokens: int = 24
    # the sharded train step: one layer plus the tied 128,256 x 4,096
    # embedding is 0.74 B parameters, 1.5 GB of bf16 weights
    shard_llama: dict = dataclasses.field(
        default_factory=lambda: {"num_hidden_layers": 1,
                                 "tie_word_embeddings": True})
    shard_batch: int = 4
    shard_seq: int = 128        # smallest the flash kernel accepts


def say(msg):
    print(f"[{time.perf_counter() - T0:7.1f}s] {msg}", flush=True)


def check(ok, what):
    """One line per check; the first false one ends the run."""
    say(("ok    " if ok else "FAIL  ") + what)
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def say_memory(dev, where):
    stats = dev.memory_stats()
    if not stats:
        say(f"memory {where}: backend reports none")
    else:
        say(f"memory {where}: {stats['bytes_in_use'] / 2**30:.2f} GiB in "
            f"use, {stats['peak_bytes_in_use'] / 2**30:.2f} GiB peak "
            f"(process high-water mark)")


def cache_entries(path):
    return len(os.listdir(path)) if os.path.isdir(path) else 0


# --------------------------------------------------------------------- start

def start():
    """Fail at once unless the default device is a TPU; say what is here."""
    import jax
    import jaxlib
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU: jax.devices()[0].platform == "
            f"{dev.platform!r}; this script proves the chip path and does "
            f"not run anywhere else")
    from importlib import metadata
    from paddle_tpu.core.compile_cache import enable_compile_cache
    from paddle_tpu.core.native import build
    say(f"platform {dev.platform}, device_kind {dev.device_kind!r}, "
        f"{len(jax.devices())} device(s)")
    say(f"jax {jax.__version__}, jaxlib {jaxlib.__version__}, libtpu "
        f"{metadata.version('libtpu')} ("
        + "; ".join(dev.client.platform_version.split("\n")) + ")")
    for name, src in (("pt_store", "store.cc"),
                      ("pt_dataloader", "dataloader.cc")):
        lib = build.load(name, src)
        say(f"native {src}: " + ("compiled and loaded" if lib is not None else
                                 f"PYTHON FALLBACK ({build.last_error(name)})"))
    cache = enable_compile_cache()
    n = cache_entries(cache)
    say(f"compile cache {cache}: {n} entries at start "
        f"({'warm' if n else 'cold'})")
    return dev, cache


# --------------------------------------------------------------------- train

def train_phase(sz, dev):
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu import observability as obs
    from paddle_tpu.models.gpt2 import GPT2Config, GPT2ForCausalLM
    from paddle_tpu.ops.pallas import flash_attention

    B, S, K = sz.train_batch, sz.train_seq, sz.train_k
    paddle.seed(0)
    # the loss settings of the one tuned recipe the repo has (the last parsed
    # on-chip record, GPT-2 124M at b16 x 1024): bf16 logits with a float32
    # log-sum-exp, and ONE loss chunk that backward does not recompute
    cfg = GPT2Config.gpt2_small(
        hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
        loss_logits_dtype="bfloat16", loss_chunk_size=B * S,
        loss_recompute=False, **sz.gpt2)
    model = GPT2ForCausalLM(cfg)
    model.to(dtype="bfloat16")
    opt = paddle.optimizer.AdamW(learning_rate=3e-4, weight_decay=0.01,
                                 parameters=model.parameters(),
                                 grad_clip=nn.ClipGradByGlobalNorm(1.0))
    say(f"train: GPT-2 {sum(p.size for p in model.parameters()) / 1e6:.0f}M "
        f"bf16, batch {B} x {S}, {K} steps per dispatch")

    def train_step(x, y):
        _, loss = model(x, labels=y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = paddle.jit.scan_steps(train_step)
    # one batch, revisited: the loss must fall as the model memorizes it
    ids = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (K, B, S + 1)).astype(np.int32)
    x, y = paddle.to_tensor(ids[:, :, :-1]), paddle.to_tensor(ids[:, :, 1:])

    def events():
        series = obs.snapshot(prefix="jit_events_total").get(
            "jit_events_total", {}).get("series", [])
        return {s["labels"]["event"]: int(s["value"]) for s in series
                if s["labels"]["fn"] == "train_step"}

    obs.reset()
    obs.enable()
    t0 = time.perf_counter()
    losses, setup_s = [], None
    for i in range(sz.train_calls):
        loss = step(x, y)
        losses += np.asarray(loss.numpy(), np.float32).reshape(-1).tolist()
        say(f"train call {i}: {time.perf_counter() - t0:.1f} s since start, "
            f"events {events()}")
        if setup_s is None and events().get("cache_hit"):
            setup_s = time.perf_counter() - t0
    ev = events()
    obs.disable()

    say(f"train losses {[round(v, 4) for v in losses]}")
    check(bool(np.isfinite(losses).all()), "every loss finite")
    check(losses[-1] < losses[0], "last loss below the first")
    check(ev.get("cache_hit", 0) >= 1,
          f"the step ran as one captured program (jit events {ev})")
    bad = {k: v for k, v in ev.items()
           if k in ("eager_call", "echo_mismatch", "retrace")}
    check(not bad, f"no eager_call / echo_mismatch / retrace ({bad or 'none'})")
    H, D = cfg.num_heads, cfg.hidden_size // cfg.num_heads
    check(flash_attention.supported((B, S, H, D), (B, S, H, D)),
          f"flash_attention.supported() accepts {(B, S, H, D)}")
    check("tpu_custom_call" in step.program_text(x, y),
          "the compiled step contains the Mosaic flash kernel "
          "(tpu_custom_call in its text)")
    say(f"SET-UP train: {setup_s:.1f} s to the first compiled step "
        f"(eager capture passes + compilation; not a speed)")
    say_memory(dev, "after train")


# --------------------------------------------------------------------- serve

def build_llama(overrides):
    """A bf16 Llama born in bf16 on the device: under the float32 default the
    8-layer model would be 10.6 GB before any cast."""
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    paddle.seed(0)
    paddle.set_default_dtype("bfloat16")
    try:
        model = LlamaForCausalLM(LlamaConfig.llama3_8b(**overrides))
    finally:
        paddle.set_default_dtype("float32")
    model.eval()
    return model


@contextlib.contextmanager
def front_door(engines):
    """The engines behind a ReplicaSet and the HTTP gateway; yields its URL."""
    from paddle_tpu.inference.frontend import ReplicaSet, start_gateway
    with ReplicaSet(engines) as rs:
        gw = start_gateway(rs, port=0)
        try:
            yield gw.url
        finally:
            gw.close()


def complete(url, prompt, **kw):
    """One POST /v1/completions; anything but HTTP 200 ends the run."""
    import urllib.error
    from paddle_tpu.inference.frontend import http_completion
    try:
        # the first request of a cold engine waits out its compilations
        return http_completion(url, prompt, timeout=1000.0, **kw)
    except urllib.error.HTTPError as e:
        check(False, f"HTTP {e.code}: {e.read().decode(errors='replace')}")


def together(calls):
    """Run the zero-argument ``calls`` concurrently; results in order."""
    out = [None] * len(calls)

    def run(i):
        try:
            out[i] = calls[i]()
        except BaseException as e:  # noqa: BLE001 — re-raised by the caller
            out[i] = e

    threads = [threading.Thread(target=run, args=(i,), daemon=True)
               for i in range(len(calls))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for r in out:
        if isinstance(r, BaseException):
            raise r
    return out


def check_answers(answers, max_tokens):
    for name, a in answers.items():
        check(a["status"] == "finished" and len(a["tokens"]) == max_tokens,
              f"request {name}: status {a['status']!r}, "
              f"{len(a['tokens'])} of {max_tokens} tokens")


def healthz(url):
    import urllib.request
    with urllib.request.urlopen(url + "/healthz", timeout=60) as r:
        return json.loads(r.read().decode())


def traffic(url, sz, vocab):
    """The smoke's requests: one streaming greedy request alone (it pays the
    compilations), then four at once — greedy, the first prompt again, and
    two seeded samplers.  Prompts of a few hundred tokens: several prefill
    chunks, page growth and batched decode all happen."""
    import numpy as np
    rng = np.random.RandomState(0)
    a, b, c, d = (rng.randint(1, vocab, (sz.prompt_len + 17 * i,)).tolist()
                  for i in range(4))
    n = sz.max_tokens
    t0 = time.perf_counter()
    first = complete(url, a, max_tokens=n, stream=True)
    first_s = time.perf_counter() - t0
    rest = together([
        lambda: complete(url, b, max_tokens=n),
        lambda: complete(url, a, max_tokens=n),
        lambda: complete(url, c, max_tokens=n, do_sample=True,
                         temperature=0.8, top_p=0.9, seed=7),
        lambda: complete(url, d, max_tokens=n, do_sample=True,
                         temperature=0.7, top_k=40, top_p=0.95, seed=11),
    ])
    answers = dict(zip(("stream-greedy", "greedy", "greedy-repeat",
                        "sampled-top_p", "sampled-top_k"), [first] + rest))
    check_answers(answers, n)
    check(answers["greedy-repeat"]["tokens"] == first["tokens"],
          "a greedy request repeated gives the same tokens")
    return first_s


def serve_phase(sz, dev, kv_cache_dtype):
    from paddle_tpu.inference.serving import LLMEngine

    model = build_llama(sz.llama)
    cfg = model.config
    pages = "int8" if kv_cache_dtype == "int8" else "bf16"
    say(f"serve[{pages} pages]: Llama-3-8B widths (hidden "
        f"{cfg.hidden_size}, {cfg.num_attention_heads} x "
        f"{cfg.hidden_size // cfg.num_attention_heads} heads, "
        f"{cfg.num_key_value_heads} KV heads, FFN {cfg.intermediate_size}, "
        f"vocabulary {cfg.vocab_size}, rope_theta {cfg.rope_theta:g}), "
        f"DEPTH CUT to {cfg.num_hidden_layers} of 32 layers, bf16, "
        f"{sz.slots} slots x {sz.max_len} tokens")
    engine = LLMEngine(model, max_batch=sz.slots, max_len=sz.max_len,
                       kv_cache_dtype=kv_cache_dtype)
    say_memory(dev, "with the model and the engine's copy both resident")
    # the runner holds its own stacked copy of the weights: dropping the
    # model leaves ONE bf16 copy on the device
    del model
    gc.collect()
    runner = engine.runner
    weights = sum(int(a.nbytes) for a in runner.W.values())
    pool = sum(int(a.nbytes) for a in runner.cache)
    say(f"resident: weights {weights / 2**30:.2f} GiB + page pool "
        f"{pool / 2**30:.2f} GiB")
    say_memory(dev, "with the model dropped")
    check(runner.use_kernel is True,
          "engine.runner.use_kernel is True (Pallas paged attention)")

    with front_door([engine]) as url:
        first_s = traffic(url, sz, cfg.vocab_size)
        health = healthz(url)
    say(f"SET-UP serve[{pages} pages]: {first_s:.1f} s to the first "
        f"completed request (compilation of prefill + decode; not a speed)")
    check(engine.health()["step_failures"] == 0,
          "engine.health()['step_failures'] == 0")
    check(health["fleet"]["alive"] == health["fleet"]["replicas"] == 1,
          f"/healthz: every replica alive ({health['fleet']})")
    say_memory(dev, f"after serve[{pages} pages]")


# ------------------------------------------------------------------- kernels

def close_to(name, got, want):
    import numpy as np
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    err = float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-6))
    check(bool(np.isfinite(got).all()) and err <= KERNEL_TOL,
          f"{name}: max|kernel - ref| / max|ref| = {err:.1e} "
          f"(bound {KERNEL_TOL:g})")


def kernel_phase(sz, llama_cfg, gpt2_heads=(12, 64)):
    """Compiling is not being right: each kernel against its reference, on
    the same device, at the shapes the train and serve phases run."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.nn.functional.attention import _sdpa_ref
    from paddle_tpu.ops.pallas import paged_attention as pa
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_bshd
    from paddle_tpu.ops.pallas.quant_matmul import quant_matmul

    keys = iter(jax.random.split(jax.random.PRNGKey(0), 32))

    def normal(shape, dtype=jnp.bfloat16):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(dtype)

    # flash attention, forward and gradients, GPT-2's training shape
    B, S = sz.train_batch, sz.train_seq
    q, k, v, do = (normal((B, S) + gpt2_heads) for _ in range(4))

    def fwd_bwd(attn):
        def f(q, k, v, do):
            out, vjp = jax.vjp(lambda q, k, v: attn(q, k, v, causal=True),
                               q, k, v)
            return (out,) + vjp(do)
        return jax.jit(f)(q, k, v, do)

    for name, got, want in zip(("out", "dq", "dk", "dv"),
                               fwd_bwd(flash_attention_bshd),
                               fwd_bwd(_sdpa_ref)):
        close_to(f"flash_attention_bshd {name} {q.shape}", got, want)

    # paged attention at the serving shape: every slot's pages scattered
    # over the pool; context lengths from one token to the whole table, and
    # ragged - short rows in a long table, each table padded with its last
    # page as the scheduler pads it, idle slots at one token, one full row:
    # the kernel's trip count follows each row's own context (PR 29)
    nh, kvh = llama_cfg.num_attention_heads, llama_cfg.num_key_value_heads
    D = llama_cfg.hidden_size // nh
    page, n_slots, Q = 16, sz.max_len // 16, 4
    Bq = sz.slots
    kp, vp = (normal((Bq * n_slots + 1, page, kvh, D)) for _ in range(2))
    tables = jax.random.permutation(next(keys), Bq * n_slots).reshape(
        Bq, n_slots).astype(jnp.int32)
    spread = jnp.linspace(1, sz.max_len - Q, Bq).astype(jnp.int32)
    ragged = jnp.minimum(jnp.asarray(
        ([1, 127, 128, 129, 17, 1, 300] * Bq)[:Bq - 1] + [sz.max_len],
        jnp.int32), sz.max_len - Q)
    last = jnp.take_along_axis(tables, ((ragged + Q - 2) // page)[:, None], 1)
    padded = jnp.where(jnp.arange(n_slots)[None, :] * page < ragged[:, None]
                       + Q - 1, tables, last)
    kq, ks = pa.quantize_kv(kp)
    vq, vs = pa.quantize_kv(vp)
    q1, q4 = normal((Bq, nh, D)), normal((Bq, Q, nh, D))
    for label, pages, scales in (
            ("bf16 pages", (kp, vp), {}),
            ("int8 pages", (kq, vq), {"k_scales": ks, "v_scales": vs})):
        for fn, ref, qx in (
                (pa.paged_attention, pa.paged_attention_ref, q1),
                (pa.paged_attention_multiquery,
                 pa.paged_attention_multiquery_ref, q4)):
            for how, tb, ctx in (("spread", tables, spread),
                                 ("ragged", padded, ragged)):
                close_to(f"{fn.__name__} {label} q{qx.shape} {how} contexts",
                         fn(qx, *pages, tb, ctx, **scales),
                         jax.jit(ref)(qx, *pages, tb, ctx, **scales))

    # weight-only matmul at the FFN width (off the main path; the int4
    # branch was changed in this round to lower at all)
    M, K, N = 8, llama_cfg.hidden_size, llama_cfg.intermediate_size
    x, scale = normal((M, K)), jnp.abs(normal((N,), jnp.float32)) * 0.01
    for int4 in (False, True):
        qw = jax.random.randint(next(keys), (K // 2 if int4 else K, N),
                                -128, 128, jnp.int32).astype(jnp.int8)

        def ref(x, qw, scale):
            w = qw
            if int4:    # low nibble -> row 2i, high nibble -> row 2i + 1
                w = jnp.stack([(qw << 4).astype(jnp.int8) >> 4, qw >> 4],
                              axis=1).reshape(-1, N)
            return (x.astype(jnp.float32) @ w.astype(jnp.float32)) * scale

        close_to(f"quant_matmul {'int4' if int4 else 'int8'} "
                 f"[{M},{K}]x[{K},{N}]",
                 jax.jit(lambda x, qw, s: quant_matmul(x, qw, s, int4=int4))(
                     x, qw, scale),
                 jax.jit(ref)(x, qw, scale))


# ---------------------------------------------------------------- four chips

LAUNCH_LOG = os.path.join(ROOT, "chiprun_out", "launch_log")


def worker():
    """A launcher worker: takes the chip it was given, uses it, and keeps
    hold of it until every sibling holds one too."""
    import signal
    signal.alarm(240)       # a worker that cannot get its chip must not hang
    import jax
    import jax.numpy as jnp
    devs = jax.devices()
    x = jnp.ones((256, 256), jnp.bfloat16)
    matmul = float((x @ x)[0, 0])
    rank, world = (int(os.environ[k]) for k in
                   ("PADDLE_TRAINER_ID", "PADDLE_TRAINERS_NUM"))
    open(os.path.join(LAUNCH_LOG, f"holding.{rank}"), "w").close()
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline and not all(
            os.path.exists(os.path.join(LAUNCH_LOG, f"holding.{r}"))
            for r in range(world)):
        time.sleep(0.2)
    print(json.dumps({
        "rank": rank, "TPU_VISIBLE_CHIPS": os.environ.get("TPU_VISIBLE_CHIPS"),
        "platform": devs[0].platform, "count": len(devs), "matmul": matmul,
        "held_together": time.monotonic() < deadline}), flush=True)


def launcher_phase():
    """Before this process touches JAX: can two launcher workers each take
    one chip of the host?  (A process that has initialized the backend holds
    every chip, and its children could get none.)"""
    import jax._src.xla_bridge as xb
    from paddle_tpu.distributed.launch.main import launch
    os.makedirs(LAUNCH_LOG, exist_ok=True)
    for f in os.listdir(LAUNCH_LOG):
        os.remove(os.path.join(LAUNCH_LOG, f))
    rc = launch(["--nproc_per_node", "2", "--log_dir", LAUNCH_LOG,
                 os.path.abspath(__file__), "--worker"])
    check(not xb._backends, "the launcher's process initialized no backend")
    reports = []
    for rank in range(2):
        with open(os.path.join(LAUNCH_LOG, f"workerlog.{rank}")) as f:
            text = f.read()
        lines = [ln for ln in text.splitlines() if ln.startswith("{")]
        say(f"launcher worker {rank}: {lines[-1] if lines else text[-600:]}")
        reports.append(json.loads(lines[-1]) if lines else None)
    check(rc == 0 and all(reports), f"launch() of two workers: rc {rc}")
    check(all(r["platform"] == "tpu" and r["count"] == 1 for r in reports),
          "each worker saw exactly one TPU device")
    # a chip belongs to one process at a time, so two processes that hold a
    # device at the same moment hold two chips (each is a 1x1x1 topology of
    # its own: ids and coordinates cannot tell them apart)
    check(all(r["held_together"] for r in reports),
          "both workers held their device at the same moment")


def replicas_phase(sz, devs):
    """Four one-chip replicas in ONE process behind one gateway."""
    import numpy as np
    from jax.sharding import Mesh
    from paddle_tpu.inference.serving import LLMEngine

    model = build_llama(sz.llama)
    # a one-device mesh is how an engine is pinned to a chip
    engines = [LLMEngine(model, mesh=Mesh(np.array([d]), ("mp",)),
                         max_batch=sz.slots, max_len=sz.max_len)
               for d in devs]
    vocab = model.config.vocab_size
    del model
    gc.collect()
    for i, e in enumerate(engines):
        held = {d for a in list(e.runner.W.values()) + list(e.runner.cache)
                for d in a.devices()}
        check(held == {devs[i]}, f"replica r{i}: every buffer on {devs[i]}")
        check(e.runner.use_kernel is True, f"replica r{i}: use_kernel True")
    # compile every replica before it takes traffic, all four at once: the
    # router reads a replica's load under the lock its step loop holds while
    # compiling, so cold replicas behind the gateway compile one by one
    rng = np.random.RandomState(1)
    warm = rng.randint(1, vocab, (sz.prompt_len,)).astype(np.int32)

    def warm_up(e):
        rid = e.add_request(warm, max_new_tokens=2)
        e.run_until_done()
        return e.status(rid).value
    t0 = time.perf_counter()
    check(set(together([lambda e=e: warm_up(e) for e in engines]))
          == {"finished"}, "every replica served its warm-up request")
    say(f"SET-UP four replicas: {time.perf_counter() - t0:.1f} s compiling "
        f"concurrently (not a speed)")

    prompts = [rng.randint(1, vocab, (sz.prompt_len + 11 * i,)).tolist()
               for i in range(8)]
    with front_door(engines) as url:
        answers = together([
            lambda p=p: complete(url, p, max_tokens=sz.max_tokens)
            for p in prompts])
        health = healthz(url)
    check_answers({f"#{i}": a for i, a in enumerate(answers)}, sz.max_tokens)
    served = sorted({a["replica"] for a in answers})
    check(served == ["r0", "r1", "r2", "r3"],
          f"requests reached all four replicas through the gateway {served}")
    check(health["fleet"]["alive"] == 4, f"/healthz {health['fleet']}")
    check(all(e.health()["step_failures"] == 0 for e in engines),
          "no replica had a step failure")
    for i, d in enumerate(devs):
        say_memory(d, f"chip {i} after four replicas")


def mesh_engine_phase(sz, devs):
    """One engine on an mp=4 mesh serving the same requests."""
    import numpy as np
    from jax.sharding import Mesh
    from paddle_tpu.inference.serving import LLMEngine

    model = build_llama(sz.llama)
    engine = LLMEngine(model, mesh=Mesh(np.array(devs), ("mp",)),
                       max_batch=sz.slots, max_len=sz.max_len)
    vocab = model.config.vocab_size
    del model
    gc.collect()
    runner = engine.runner
    say(f"mp=4 engine: use_kernel {runner.use_kernel} — attention path "
        + ("Pallas kernel" if runner.use_kernel else
           "paged_attention_ref, the jnp reference (ROADMAP S6)"))

    def pool():
        a = runner.cache[0]
        return (f"{a.sharding}, {a.addressable_shards[0].data.shape} a "
                f"device of {a.shape}")
    say(f"mp=4 engine: page pool at start {pool()}")
    with front_door([engine]) as url:
        first_s = traffic(url, sz, vocab)
        health = healthz(url)
    say(f"SET-UP mp=4 engine: {first_s:.1f} s to the first completed "
        f"request (not a speed)")
    # a program whose donated pool comes back under another sharding than
    # it went in with is compiled a second time on its second step
    say(f"mp=4 engine: page pool now {pool()}; prefill program compiled "
        f"{runner._prefill._cache_size()} time(s), decode "
        f"{[p._cache_size() for p in runner._decode_programs.values()]}")
    check(engine.health()["step_failures"] == 0, "mp=4 engine: no step failed")
    check(health["fleet"]["alive"] == 1, f"/healthz {health['fleet']}")


def sharded_train_phase(sz):
    """Part A of __graft_entry__.dryrun_multichip on real chips: a dense
    Llama train step under dp=2 x mp=2 with shard_llama's placements, then
    the same seed, batch and steps on one chip."""
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.distributed import Shard, shard_tensor
    from paddle_tpu.distributed.fleet.topology import (
        CommunicateTopology, HybridCommunicateGroup,
        set_hybrid_communicate_group)
    from paddle_tpu.models.llama import LlamaConfig, shard_llama

    vocab = LlamaConfig.llama3_8b(**sz.shard_llama).vocab_size
    ids = np.random.RandomState(0).randint(
        0, vocab, (sz.shard_batch, sz.shard_seq + 1)).astype(np.int32)

    def run(dp, mp):
        hcg = HybridCommunicateGroup(CommunicateTopology(
            ["dp", "pp", "sharding", "sep", "mp"], [dp, 1, 1, 1, mp]), rank=0)
        set_hybrid_communicate_group(hcg)
        mesh = hcg.get_mesh()
        model = build_llama(sz.shard_llama)
        model.train()
        shard_llama(model, mesh, fsdp_axis="dp", mp_axis="mp")
        # SGD, where part A of the dryrun has AdamW: AdamW's float32 moments
        # (5.9 GB) next to weights, grads and the eager capture pass's
        # float32 temporaries (2.1 GB apiece for the embedding) overflow ONE
        # chip — found by running it.  Forward, backward, the global-norm
        # reduction and the placements are the same.
        opt = paddle.optimizer.SGD(
            learning_rate=0.1, parameters=model.parameters(),
            grad_clip=nn.ClipGradByGlobalNorm(1.0))

        def train_step(x, y):
            if dp > 1:
                x = shard_tensor(x, mesh, [Shard(0)])       # batch on dp
            _, loss = model(x, labels=y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        step = paddle.jit.to_static(train_step)
        x, y = paddle.to_tensor(ids[:, :-1]), paddle.to_tensor(ids[:, 1:])
        # two eager capture passes (the second sees the optimizer state the
        # first created), then the compiled program
        losses = [float(np.asarray(step(x, y).numpy(), np.float32))
                  for _ in range(3)]
        text = step.program_text(x, y)
        spec = model.llama.layers[0].mlp.gate_proj.weight._data.sharding
        return losses, "tpu_custom_call" in text, spec

    sharded, mosaic, spec = run(2, 2)
    say(f"dp=2 x mp=2 losses {sharded} (gate_proj placed {spec}; Mosaic "
        f"flash kernel in the sharded program: {mosaic})")
    gc.collect()
    single, mosaic1, _ = run(1, 1)
    say(f"one-chip losses    {single} (Mosaic flash kernel: {mosaic1})")
    delta = max(abs(a - b) for a, b in zip(sharded, single))
    check(bool(np.isfinite(sharded + single).all()) and delta <= PARITY_TOL,
          f"dp=2 x mp=2 loss within {PARITY_TOL:g} of one chip at every step "
          f"(max |delta| {delta:.2e})")


# ---------------------------------------------------------------------- main

def four_chips(sz):
    """One child process per question, one after the other, from a parent
    that has not touched JAX: each child owns the chips while it runs (and
    the launcher's own workers could get none from a parent that held
    them).  A failed question does not hide the ones after it; the run
    still fails."""
    import subprocess
    failed = []
    for name in ("launcher", "replicas", "mesh-engine", "sharded-train"):
        rc = subprocess.run([sys.executable, os.path.abspath(__file__),
                             "--phase", name]).returncode
        say(f"phase {name}: exit code {rc}")
        if rc:
            failed.append(name)
    return failed


def four_chip_phase(name, sz):
    if name == "launcher":
        return launcher_phase()
    start()
    import jax
    devs = jax.devices()
    check(len(devs) == 4, f"four chips on this host ({len(devs)})")
    if name == "replicas":
        replicas_phase(sz, devs)
    elif name == "mesh-engine":
        mesh_engine_phase(sz, devs)
    else:
        sharded_train_phase(sz)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the four-chip questions instead of the "
                         "one-chip smoke")
    ap.add_argument("--phase", help=argparse.SUPPRESS)    # four_chips' child
    ap.add_argument("--worker", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    sz = Sizes()
    if args.worker:
        return worker()
    if args.phase:
        return four_chip_phase(args.phase, sz)
    failed = four_chips(sz) if args.chips == 4 else []
    dev, cache = start()
    import jax
    if args.chips == 1:
        from paddle_tpu.models.llama import LlamaConfig
        # cheapest first, and by rising memory: the peak the backend reports
        # is the process's high-water mark, so each phase's shows
        kernel_phase(sz, LlamaConfig.llama3_8b())
        gc.collect()
        for kv_cache_dtype in ("auto", "int8"):
            serve_phase(sz, dev, kv_cache_dtype)
            gc.collect()
        train_phase(sz, dev)
    say(f"compile cache {cache}: {cache_entries(cache)} entries at the end")
    if failed:
        raise SystemExit(f"chip_smoke: FAILED phases: {', '.join(failed)}")
    say("every phase passed")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
