"""Open-loop traffic over HTTP against the gateway: the one general
generator of every serving cell.  A cell is a data file of parameters
(``bench/workloads/<name>.json``, key ``traffic``):

  rate            requests a second offered (fixed in the cell, from the knee)
  prompt/output   {"median", "sigma", "min", "max"}: log-normal lengths, clipped
  order           optional {"strata": k}: how a seed orders the lengths and
                  gaps (below); without it, a plain shuffle
  preroll_s       optional: the same traffic flows for so long before the
                  window opens (part of set-up), so that a cell past the knee
                  is measured with its slots and its queue already full
  drain_s         how long past the close unfinished requests are waited for
  check_requests  how many finished requests the reference is run over
  trace           {"start_s", "seconds"}: the profiled part of a --trace 1 window

Every seed gets the SAME multiset of prompt lengths, output lengths and
arrival gaps -- the quantiles of the distributions -- in another order, so
that the seed changes which request meets which and not how much work the
window holds.  Where a window ends before all of it is served (a cell past
the knee), a plain shuffle still changes how much of the work comes first;
``order.strata`` = k then makes every k consecutive requests hold one value
from each k-th of each distribution.  Token ids are uniform over the
vocabulary.

The top of this module imports no JAX: the load generator's process imports
it for ``make_schedule``.
"""
import json
import math
import os
import subprocess
import sys
import threading
import time

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------- the schedule

def _norm_ppf(p):
    """Inverse of the standard normal CDF (Acklam's rational approximation,
    relative error 1.2e-9); numpy has none."""
    a = (-3.969683028665376e+01, 2.209460984245205e+02, -2.759285104469687e+02,
         1.383577518672690e+02, -3.066479806614716e+01, 2.506628277459239e+00)
    b = (-5.447609879822406e+01, 1.615858368580409e+02, -1.556989798598866e+02,
         6.680131188771972e+01, -1.328068155288572e+01)
    c = (-7.784894002430293e-03, -3.223964580411365e-01, -2.400758277161838e+00,
         -2.549732539343734e+00, 4.374664141464968e+00, 2.938163982698783e+00)
    d = (7.784695709041462e-03, 3.224671290700398e-01, 2.445134137142996e+00,
         3.754408661907416e+00)
    p = np.asarray(p, np.float64)
    out = np.empty_like(p)
    lo, hi = p < 0.02425, p > 1 - 0.02425
    mid = ~(lo | hi)
    q = np.sqrt(-2 * np.log(np.where(lo, p, 0.5)))
    out[lo] = ((((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q + c[5]) /
               ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q + 1))[lo]
    q = np.sqrt(-2 * np.log(np.where(hi, 1 - p, 0.5)))
    out[hi] = -((((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q + c[5]) /
                ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q + 1))[hi]
    q = p - 0.5
    r = q * q
    out[mid] = ((((((a[0] * r + a[1]) * r + a[2]) * r + a[3]) * r + a[4]) * r + a[5]) * q /
                (((((b[0] * r + b[1]) * r + b[2]) * r + b[3]) * r + b[4]) * r + 1))[mid]
    return out


def lognormal_lengths(n, spec):
    """The n mid-quantiles of a log-normal, clipped: the same multiset for
    every seed."""
    p = (np.arange(n) + 0.5) / n
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * _norm_ppf(p))
    return np.clip(np.round(x), spec["min"], spec["max"]).astype(np.int64)


def seeded_order(values, rng, strata=None):
    """``values`` in an order drawn from ``rng``: a plain shuffle, or with
    ``strata`` = k one in which every k consecutive places hold one value
    from each of the k equal slices of the sorted values."""
    if not strata:
        out = np.array(values)
        rng.shuffle(out)
        return out
    slices = np.array_split(np.sort(values), strata)
    for s in slices:
        rng.shuffle(s)
    out = []
    for j in range(len(slices[0])):
        block = [s[j] for s in slices if j < len(s)]
        rng.shuffle(block)
        out.extend(block)
    return np.array(out)


def make_schedule(params, seed, seconds):
    """The window's requests in order of ``due`` (seconds from the start of
    the window): ``{"i", "due", "prompt", "max_tokens"}``."""
    rng = np.random.default_rng([int(seed), 1])
    n = max(1, int(round(params["rate"] * seconds)))
    # Poisson arrivals: the n mid-quantiles of the exponential gap, shuffled;
    # they sum to about n / rate = seconds
    strata = params.get("order", {}).get("strata")
    gaps = -np.log(1.0 - (np.arange(n) + 0.5) / n) / params["rate"]
    gaps = seeded_order(gaps, rng, strata)
    due = np.cumsum(gaps) - gaps[0]
    due = due[due < seconds]
    n = len(due)
    prompts = seeded_order(lognormal_lengths(n, params["prompt"]), rng, strata)
    outputs = seeded_order(lognormal_lengths(n, params["output"]), rng, strata)
    vocab = int(params["vocab_size"])
    return [{"i": i, "due": float(due[i]),
             "prompt": rng.integers(1, vocab, int(prompts[i])).tolist(),
             "max_tokens": int(outputs[i])} for i in range(n)]


def warm_requests(params, seed):
    """What set-up sends before the window: enough to compile the prefill
    chunk and the decode step, on every replica, and nothing else."""
    rng = np.random.default_rng([int(seed), 2])
    vocab = int(params["vocab_size"])
    w = params.get("warm", {"requests": 2, "prompt": 80, "max_tokens": 8})
    return [{"i": -1 - k, "due": 0.0,
             "prompt": rng.integers(1, vocab, w["prompt"]).tolist(),
             "max_tokens": w["max_tokens"]}
            for k in range(w["requests"])]


# ------------------------------------------------------------- the records

def percentile(values, q):
    """Nearest-rank percentile, q in [0, 100] (copied from the program's
    ``frontend/loadgen.percentile``)."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of empty sequence")
    k = max(0, min(len(vals) - 1, round(q / 100.0 * (len(vals) - 1))))
    return vals[int(k)]


def summarize(records, schedule, seconds):
    """Client-side numbers of one window, which opens at 0.  A request that
    failed, was shed or got no token counts as the worst time to first
    token: the time from when it was due to when the generator gave up on
    it.  A request of the pre-roll is due before 0: it counts by the tokens
    it streams inside the window and by whether it failed, and its times to
    first token and its gaps are set-up's, not the window's."""
    by_i = {r["i"]: r for r in records}
    ttft, gaps, lateness = [], [], []
    tokens_in_window = attempted = failed = 0
    give_up = max([seconds] + [t for r in records for t in r["times"]])
    for req in schedule:
        r = by_i.get(req["i"])
        windowed = req["due"] >= 0
        attempted += windowed
        if r is None:                       # never sent: the generator died
            failed += 1
            if windowed:
                ttft.append(give_up - req["due"])
            continue
        finished = (r["status"] == "finished"
                    and len(r["tokens"]) == req["max_tokens"])
        if not finished and r["status"] != "cancelled_at_close":
            failed += 1
        tokens_in_window += sum(0 < t <= seconds for t in r["times"])
        if not windowed:
            continue
        lateness.append(max(0.0, r["sent"] - r["due"]))
        ttft.append((r["times"][0] if r["times"] else give_up) - r["due"])
        gaps.extend(b - a for a, b in zip(r["times"], r["times"][1:]))
    return {"attempted": attempted, "failed": failed, "ttft_s": ttft,
            "gaps_s": gaps, "lateness_s": lateness,
            "tokens_in_window": tokens_in_window}


# --------------------------------------------------------------- the driver

class LoadGenerator:
    """The child process and its line protocol."""

    def __init__(self, params, seed, seconds):
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(BENCH, "loadgen.py"),
             json.dumps(params), str(int(seed)), repr(float(seconds))],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)

    def _read(self, key, timeout):
        box = {}

        def read():
            box["line"] = self.proc.stdout.readline()
        t = threading.Thread(target=read, daemon=True)
        t.start()
        t.join(timeout)
        if t.is_alive() or not box.get("line"):
            raise SystemExit(f"bench: the load generator gave no {key!r} "
                             f"within {timeout:.0f} s")
        return json.loads(box["line"])[key]

    def ready(self):
        return self._read("ready", 120)

    def send(self, **msg):
        self.proc.stdin.write(json.dumps(msg) + "\n")
        self.proc.stdin.flush()

    def warm(self, url, timeout):
        self.send(cmd="warm", url=url)
        return self._read("warm", timeout)

    def go(self, url, t0):
        self.send(cmd="go", url=url, t0=t0)

    def done(self, timeout):
        return self._read("done", timeout)

    def close(self):
        try:
            if self.proc.poll() is None:
                self.send(cmd="quit")
                self.proc.wait(timeout=10)
        except (OSError, subprocess.TimeoutExpired):
            pass
        finally:
            if self.proc.poll() is None:
                self.proc.kill()
                self.proc.wait()


def _spy_on_runner(engines, spans):
    """Spans from the benchmark's own files, around the calls into the
    runner (a --trace 1 run only): one record for each dispatch with what the
    roofline and FLOP functions need -- rows and the valid context of each."""
    for ei, e in enumerate(engines):
        runner = e.runner
        run_prefill, run_decode = runner.run_prefill, runner.run_decode

        def prefill(tokens, start, table, n_valid, *a, _f=run_prefill, _ei=ei):
            t0 = time.time()
            out = _f(tokens, start, table, n_valid, *a)
            spans.append({"kind": "prefill", "engine": _ei, "t0": t0,
                          "t1": time.time(), "rows": int(n_valid),
                          "start": int(start)})
            return out

        def decode(k, tokens, lens, tables, active, *a, _f=run_decode, _ei=ei):
            t0 = time.time()
            ctx = (np.asarray(lens) + 1)[np.asarray(active) > 0]
            out = _f(k, tokens, lens, tables, active, *a)
            spans.append({"kind": "decode", "engine": _ei, "t0": t0,
                          "t1": time.time(), "rows": int(len(ctx)),
                          "ctx_sum": int(ctx.sum()), "k": int(k)})
            return out

        runner.run_prefill, runner.run_decode = prefill, decode


def run(ctx):
    """One run of a serving cell.  Returns what ``run.py`` prints."""
    import importlib

    import jax
    args, cell, cfg, say = ctx.args, ctx.cell, ctx.config, ctx.say
    params = dict(cell["traffic"])
    params["vocab_size"] = cfg["vocab_size"]
    preroll = float(params.get("preroll_s", 0.0))
    schedule = make_schedule(params, args.seed, preroll + args.seconds)
    # the child starts (and draws the same schedule) while the chip warms up
    gen = LoadGenerator(params, args.seed, preroll + args.seconds)
    system = None
    try:
        ref = importlib.import_module(f"bench.reference.{cfg['reference']}")
        builder = importlib.import_module(f"bench.builders.{cfg['builder']}")
        t = time.perf_counter()
        weights = ref.init_weights(cfg, args.seed)
        jax.block_until_ready(weights)
        say(f"weights drawn from the seed in {time.perf_counter() - t:.1f} s")
        system = builder.build(cfg, weights, ctx.devices, cell, say)
        del weights
        ctx.say_memory("engine(s) built, the model dropped")
        gen.ready()
        t = time.perf_counter()
        warm = gen.warm(system.url, timeout=1100)
        bad = [r for r in warm if r["status"] != "finished"]
        if bad:
            raise SystemExit(f"bench: warm-up request failed: {bad[0]}")
        say(f"warm-up: {len(warm)} requests in {time.perf_counter() - t:.1f} s")
        compiled_before = system.compiled_programs()

        from paddle_tpu import observability as obs
        spans = []
        obs.reset()
        if args.trace:
            obs.enable()
        if args.trace:
            _spy_on_runner(system.engines, spans)
        go_at = time.time() + 0.25
        gen.go(system.url, go_at)
        t0 = go_at + preroll                # the pre-roll is set-up
        ctx.window_opens(t0)
        tr = params.get("trace", {"start_s": 2.0, "seconds": 3.0})
        if args.trace:
            start = min(tr["start_s"], max(0.0, args.seconds - tr["seconds"]))
            time.sleep(max(0.0, t0 + start - time.time()))
            ctx.trace_start()
            time.sleep(min(tr["seconds"], args.seconds))
            ctx.trace_stop()
        records = gen.done(timeout=preroll + args.seconds
                           + params.get("drain_s", 60.0) + 120)
        # the generator's clock starts at the pre-roll, the window's at 0
        for r in schedule + records:
            r["due"] -= preroll
        for r in records:
            r["sent"] -= preroll
            r["times"] = [t - preroll for t in r["times"]]
        counters = obs.snapshot() if args.trace else {}
        obs.disable()
        compiled_in_window = system.compiled_programs() - compiled_before
        step_failures = sum(h.get("step_failures", 0) for h in system.health())
        ctx.read_memory_peak()
    finally:
        gen.close()
        if system is not None:
            system.close()
    say(f"window closed: {len(records)} of {len(schedule)} requests sent; "
        f"programs compiled inside the window: {compiled_in_window}; "
        f"engine step failures: {step_failures}")

    s = summarize(records, schedule, args.seconds)

    def pct(series, q):
        return 1e3 * percentile(s[series], q) if s[series] else None
    # run.py takes the cell's end-to-end metrics out of this by name; the
    # rest goes on this earlier line, judged by nothing
    client = {
        "ttft_mean_ms": 1e3 * sum(s["ttft_s"]) / len(s["ttft_s"]),
        "ttft_p50_ms": pct("ttft_s", 50), "ttft_p90_ms": pct("ttft_s", 90),
        "ttft_max_ms": 1e3 * max(s["ttft_s"]),
        "itl_p50_ms": pct("gaps_s", 50), "itl_p90_ms": pct("gaps_s", 90),
        "itl_p95_ms": pct("gaps_s", 95), "itl_p99_ms": pct("gaps_s", 99),
        "gaps": len(s["gaps_s"]),
        "serve_tokens_per_s": s["tokens_in_window"] / args.seconds,
        "finished": sum(r["status"] == "finished" for r in records),
        "unfinished_at_close": sum(r["status"] == "cancelled_at_close"
                                   for r in records),
        "lateness_p95_ms": pct("lateness_s", 95),
    }
    say("client side, judged by nothing: " + json.dumps(client))

    checks = check_served(ctx, ref, schedule, records, params)
    checks.append(("compiled_in_window", compiled_in_window, 0))
    checks.append(("step_failures", step_failures, 0))
    checks.append(("failed_requests", s["failed"], 0))
    return {
        "attempted": s["attempted"], "failed": s["failed"],
        "end_to_end": client, "checks": checks,
        "facts": {"registry": counters, "spans": spans,
                  "lateness_s": s["lateness_s"], "ttft_s": s["ttft_s"],
                  "gaps_s": s["gaps_s"],
                  "engine": {**cfg["engine"], **cell.get("engine", {})}},
    }


def check_served(ctx, ref, schedule, records, params):
    """The comparison that decides ``correct``: over a sample, drawn from
    the seed, of the requests the window finished (the longest among them),
    the reference is run once over each prompt with its served tokens, and
    the gap by which a served token's logit lies below the reference's best
    is read at every served position.  Runs after the engines are freed.
    With ``--control`` the control stands in the program's place: at the
    same positions, the gap of the token that the lower precision puts
    first is what the same limits judge, and they have to fail it."""
    args, cfg = ctx.args, ctx.config
    reqs = {r["i"]: r for r in schedule}
    done = [r for r in records if r["status"] == "finished" and r["tokens"]
            and len(r["tokens"]) == reqs[r["i"]]["max_tokens"]]
    if not done:
        return [("finished_requests", 0, ">=1")]
    n = min(int(params.get("check_requests", 6)), len(done))
    longest = max(done, key=lambda r: len(reqs[r["i"]]["prompt"]) + len(r["tokens"]))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(args.seed), 3])
    picked = [longest] + [rest[j] for j in
                          rng.choice(len(rest), n - 1, replace=False)]
    pad_to = -(-(params["prompt"]["max"] + params["output"]["max"]) // 128) * 128
    t = time.perf_counter()
    weights = ref.init_weights(cfg, args.seed)
    served_gap, control_gap = [], []
    for r in picked:
        g = ref.served_token_gaps(cfg, weights, reqs[r["i"]]["prompt"],
                                  r["tokens"], pad_to, control=args.control)
        served_gap.extend(g["served_gap"].tolist())
        control_gap.extend(g.get("control_gap", np.zeros(0)).tolist())
    del weights
    ctx.say(f"reference over {len(picked)} requests, {len(served_gap)} served "
            f"tokens, in {time.perf_counter() - t:.1f} s")
    if args.control:
        ctx.say(f"the {args.control} control stands in the program's place; "
                f"the program's own tokens read served_gap_max "
                f"{max(served_gap)}, served_gap_mean {float(np.mean(served_gap))}")
        served_gap = control_gap
    limits = ctx.cell["limits"]
    return [("served_gap_max", max(served_gap), limits["served_gap_max"]),
            ("served_gap_mean", float(np.mean(served_gap)),
             limits["served_gap_mean"])]
