"""The readings a training cell's limits are set from, in ONE process: on
every seed of ``--seeds`` the program's numbers (the capture passes are paid
once), and on every seed of ``--control-seeds`` the control's (the reference
in the program's place, on int8 operands) and the fault's that a reference
can carry (half of the batch left out, the mean taken over the rest); these
two need no program and no capture.  A state left unchanged reads 1 by the
measure and needs no run.

    python3 bench/tools/train_limits.py --workload gpt2-124m-train-b16 \\
        --seeds 1,2,3,... --control-seeds 1,2,3 [--out chiprun_out/train_limits.jsonl]

One JSON line a reading on standard output (and in ``--out``), each with the
verdict of the harness's own comparison against the cell's limits
(``correct``): true for the program, false for the control and the fault.
Not part of a benchmark run; PERF.md records what it printed.
"""
import argparse
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import run as harness  # noqa: E402
from bench.traffic import train_batches as tb  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--control", default="int8")
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = [int(s) for s in args.control_seeds.split(",") if s]

    cell, config = harness.load_cell(args.workload, args.rehearsal)
    ctx = harness.Context(args, cell, config, {})
    harness.enable_caches(cell)
    ctx.find_devices()
    say = harness.say
    p = cell["traffic"]
    k, batch, seq = p["steps_per_dispatch"], p["batch"], p["seq"]
    ref = importlib.import_module(f"bench.reference.{config['reference']}")
    builder = importlib.import_module(f"bench.builders.{config['builder']}")
    vocab = config.get("published", {}).get("vocab_size", config["vocab_size"])
    out = open(args.out, "a") if args.out else None

    def emit(**row):
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    def judged(got, want):
        """The cell's numbers with the run's own verdict on them."""
        c = tb.compare_with_reference(ref, got, want)
        checks = [(n, c[n], cell["limits"][n]) for n in tb.NUMBERS]
        return {n: c[n] for n in tb.NUMBERS} | {
            "correct": harness.is_correct(checks), "at": c["at"]}

    def batcher(seed):
        return tb.make_batcher(ref.seed_key(seed), vocab, k, batch, seq)

    if seeds:
        system = builder.build(config, cell, say)
        system.set_state(ref.init_weights(config, seeds[0]))
        tb.capture(system, batcher(seeds[0]), say)
        for seed in seeds:
            draw = batcher(seed)
            got = tb.compared_steps(system, ref, config, draw, seed,
                                    int(p["check_dispatches"]))
            want = tb.reference_steps(ref, config, p, seed, draw, keep_moments=True)
            emit(seed=seed, who="program", loss=got["loss"], **judged(got, want))
            del got, want
        system.close()
    for seed in control_seeds:
        draw = batcher(seed)
        want = tb.reference_steps(ref, config, p, seed, draw, keep_moments=True)
        ctl = tb.reference_steps(ref, config, p, seed, draw,
                                 precision=args.control, keep_moments=True)
        emit(seed=seed, who="control_" + args.control, **judged(ctl, want))
        del ctl
        half = tb.reference_steps(ref, config, p, seed, draw,
                                  keep=lambda r: r < batch // 2,
                                  keep_moments=True)
        emit(seed=seed, who="fault_half_batch", **judged(half, want))
        del half, want
    return 0


if __name__ == "__main__":
    sys.exit(main())
