"""Training traffic: fresh batches of token ids drawn from ``--seed``, fed
to the captured step for the length of the window.  A cell is a data file of
parameters (``bench/workloads/<name>.json``, key ``traffic``):

  batch, seq            rows and tokens a row of one optimizer step
  steps_per_dispatch    K optimizer steps in one call of the captured step
  in_flight             calls the host may run ahead of the device
  check_dispatches      calls that the reference follows from the seed
  reference_rows        rows a block of the reference's gradient pass
  trace                 {"start_s", "seconds"} of a --trace 1 window

Token ids are uniform over the vocabulary, every row of every step another
draw: dispatch ``i`` is ``randint(fold_in(key(seed), i))``, made on the
device by one small jitted call, so the same seed gives the same rows.
"""
import importlib
import json
import time

import numpy as np


def make_batcher(seed_key, vocab, k, batch, seq):
    import jax

    @jax.jit
    def draw(i):
        return jax.random.randint(jax.random.fold_in(seed_key, i),
                                  (k, batch, seq + 1), 0, vocab, "int32")
    return draw


def worst_leaf_gap(got, want, skip=()):
    """The widest gap between the program's norm of a leaf and the
    reference's, measured against the reference's norm of that leaf or of
    the median leaf, whichever is larger."""
    names = [n for n in want if n not in skip]
    floor = float(np.median([want[n] for n in names]))
    worst, where = 0.0, None
    for n in names:
        gap = abs(got[n] - want[n]) / max(want[n], floor)
        if not gap <= worst:            # a NaN is the worst
            worst, where = gap, n
    return worst, where


def compare(got, ref, directions=None):
    """The numbers compared, each with the leaf or step it was worst at.
    ``directions``: each logical leaf's 1 - cos between the program's first
    moment and the reference's, where they were read."""
    loss = max(abs(a - b) / abs(b) for a, b in zip(got["loss"], ref["loss"]))
    grad, grad_at = worst_leaf_gap(got["moment_norm"], ref["moment_norm"])
    # a leaf whose gradient is nought to rounding in the reference (under a
    # thousandth of the median leaf's) moves under Adam by round-off alone,
    # and its direction is round-off's too
    g1 = ref["first_grad_norm"]
    dead = [n for n in g1 if g1[n] < 1e-3 * float(np.median(list(g1.values())))]
    change, change_at = worst_leaf_gap(got["change_norm"], ref["change_norm"],
                                       skip=dead)
    out = {"loss_gap": loss, "grad_norm_gap": grad, "change_norm_gap": change,
           "at": {"grad_norm_gap": grad_at, "change_norm_gap": change_at,
                  "left_out_of_change": dead}}
    if directions:
        live = sorted((v, n) for n, v in directions.items() if n not in dead)
        # the median leaf's: one leaf's direction swings, the median is steady
        out["grad_direction_gap"], out["at"]["grad_direction_gap"] = live[len(live) // 2]
        out["at"]["grad_direction_worst"] = list(live[-1][::-1])
    return out


NUMBERS = ("loss_gap", "grad_norm_gap", "change_norm_gap", "grad_direction_gap")


def compared_steps(system, ref, cfg, draw, seed, n_check):
    """The compared steps: from the seed's state, through the compiled
    program, on the window's own feed.  Returns what the program produced:
    every step's loss, the first moment's norms (and the moment itself, on
    the host) after the first call, the parameters' change after the last."""
    weights = ref.init_weights(cfg, seed)
    system.set_state(weights)
    got = {"loss": []}
    for i in range(n_check):
        got["loss"] += np.asarray(system.dispatch(draw(i)), np.float32).tolist()
        if i == 0:
            got["moment_norm"] = {n: float(v) for n, v in
                                  system.moment_norms().items()}
            got["moments"] = system.moments_on_host()
    got["change_norm"] = {n: float(v) for n, v in
                          system.change_norms(weights).items()}
    return got


def reference_steps(ref, cfg, p, seed, draw, precision="float32", keep=None,
                    keep_moments=False):
    """The reference over the same rows as :func:`compared_steps`."""
    k = p["steps_per_dispatch"]
    batches = []
    for d in range(int(p["check_dispatches"])):
        ids = draw(d)
        batches += [(ids[j, :, :-1], ids[j, :, 1:]) for j in range(k)]
    return ref.follow(cfg, cfg["recipe"], seed, batches,
                      rows=int(p["reference_rows"]), moments_after=k,
                      precision=precision, keep=keep, keep_moments=keep_moments)


def compare_with_reference(ref, got, want):
    """``want`` from :func:`reference_steps` with ``keep_moments``."""
    return compare(got, want,
                   ref.direction_gaps(got["moments"], want["moments"]))


def capture(system, draw, say):
    """Drives the step until a call runs as the captured program (two eager
    capture passes, then the compile).  Its rows are no step's of the
    window, and the state it leaves is put back by the compared steps."""
    import jax
    from paddle_tpu import observability as obs
    obs.reset()
    obs.enable()                # jit_events_total tells a capture from a hit
    t = time.perf_counter()
    n = 0
    while not system.jit_events().get("cache_hit"):
        jax.block_until_ready(system.dispatch(draw(1_000_000 + n)))
        n += 1
        say(f"capture call {n}: {time.perf_counter() - t:.1f} s, "
            f"events {system.jit_events()}")
        if n > 4:
            raise SystemExit("bench: the step never ran as a captured program")
    obs.disable()


def run(ctx):
    """One run of a training cell.  Returns what ``run.py`` prints."""
    import jax
    args, cell, cfg, say = ctx.args, ctx.cell, ctx.config, ctx.say
    p = cell["traffic"]
    k, batch, seq = p["steps_per_dispatch"], p["batch"], p["seq"]
    ref = importlib.import_module(f"bench.reference.{cfg['reference']}")
    builder = importlib.import_module(f"bench.builders.{cfg['builder']}")
    from paddle_tpu import observability as obs

    # ids from the published vocabulary: rows a padded table adds are never read
    vocab = cfg.get("published", {}).get("vocab_size", cfg["vocab_size"])
    draw = make_batcher(ref.seed_key(args.seed), vocab, k, batch, seq)
    system = builder.build(cfg, cell, say)
    system.set_state(ref.init_weights(cfg, args.seed))
    capture(system, draw, say)
    ctx.say_memory("after the capture passes")

    # the compared steps; the window goes on from where they end
    n_check = int(p["check_dispatches"])
    got = compared_steps(system, ref, cfg, draw, args.seed, n_check)
    say(f"compared steps: losses {[round(v, 4) for v in got['loss']]}")

    obs.reset()
    if args.trace:
        obs.enable()
    in_flight = int(p.get("in_flight", 2))
    tr = p.get("trace", {"start_s": 2.0, "seconds": 3.0})
    trace_at = min(tr["start_s"], max(0.0, args.seconds - tr["seconds"]))
    tracing = None
    pending = []
    t0 = time.time()
    ctx.window_opens(t0)
    i = n_check
    while time.time() - t0 < args.seconds:
        if args.trace and tracing is None and time.time() - t0 >= trace_at:
            ctx.trace_start()
            tracing = time.time()
        if tracing and time.time() - tracing >= tr["seconds"]:
            ctx.trace_stop()
            tracing = False
        pending.append(system.dispatch(draw(i)))
        i += 1
        if len(pending) > in_flight:
            jax.block_until_ready(pending.pop(0))
    last = np.asarray(jax.block_until_ready(pending[-1]), np.float32)
    elapsed = time.time() - t0
    if tracing:
        ctx.trace_stop()
    steps = (i - n_check) * k
    events = system.jit_events() if args.trace else {}
    counters = obs.snapshot() if args.trace else {}
    obs.disable()
    ctx.read_memory_peak()
    tokens = steps * batch * seq
    say(f"window closed: {steps} steps, {tokens} tokens in {elapsed:.3f} s; "
        f"last losses {last.tolist()}; jit events in the window {events}")
    system.close()
    del pending

    t = time.perf_counter()
    want = reference_steps(ref, cfg, p, args.seed, draw, keep_moments=True)
    say(f"reference over {len(want['loss'])} steps in "
        f"{time.perf_counter() - t:.1f} s; its losses "
        f"{[round(v, 4) for v in want['loss']]}")
    c = compare_with_reference(ref, got, want)
    del got, want
    say("worst at: " + json.dumps(c["at"]))
    limits = cell["limits"]
    checks = [(n, c[n], limits[n]) for n in NUMBERS if n in limits]
    checks.append(("loss_finite", 0 if np.isfinite(last).all() else 1, 0))
    return {
        "attempted": steps, "failed": 0,
        "end_to_end": {"train_tokens_per_s": tokens / elapsed},
        "checks": checks,
        "facts": {"registry": counters,
                  "train": {"batch": batch, "seq": seq, "tokens": tokens,
                            "elapsed_s": elapsed, "steps": steps}},
    }
