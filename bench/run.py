"""One run of one cell of the benchmark.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds ``bench/workloads/<name>.json`` (which names its configuration file,
its traffic generator and parameters), checks that JAX sees the TPUs the
cell asks for and that their kind is in ``bench/peaks.json``, hands over to
the traffic generator's driver (``bench/traffic/<kind>.py``), and prints as
the last line of standard output one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` with
``--trace 1``), then ``checks``: every number compared beside its limit.
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics (each read by ``bench/readers/<reader>.py`` as
``bench/metrics/<name>.json`` says).

There is no CPU fallback.  ``--rehearsal`` (tests and debugging on the CPU,
never the driver) runs the same code at the tiny size the cell's file gives
names under ``rehearsal`` (a file of ``bench/rehearsal/``) and prints its
numbers under ``rehearsal_counts``, never under a metric's name.  ``--set
key=value`` overrides a traffic parameter for a sweep.  ``--control int8``
(serving cells) puts the control of the comparison in the program's place:
the same comparison then judges the control's tokens, and ``correct`` has to
come out false; a training cell's control and faults go through the same
verdict in ``bench/tools/train_limits.py``.
"""
import argparse
import glob
import importlib
import json
import os
import shutil
import sys
import time

T_START = time.time()
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, ".bench_out")      # traces; git-ignored, emptied


def load_json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def say(msg):
    print(f"[{time.time() - T_START:7.1f}s] {msg}", file=sys.stderr, flush=True)


def merge(base, over):
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


class Context:
    """What a driver gets from the harness: the cell, the devices, the
    clock of set-up, the profiler and the memory reading."""

    def __init__(self, args, cell, config, benchmark):
        self.args, self.cell, self.config = args, cell, config
        self.benchmark = benchmark
        self.say = say
        self.devices = []
        self.peaks = None
        self.setup_s = None
        self.memory_peak_bytes = None
        self.trace_window = None
        self.trace_dir = None

    # ---- the device
    def find_devices(self):
        import jax
        devs = jax.devices()
        chips = int(self.cell["chips"])
        if self.args.rehearsal:
            self.devices = devs[:chips]
            self.peaks = {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0,
                          "hbm_bytes": 1.0}
            return
        if devs[0].platform != "tpu":
            raise SystemExit(f"bench: no TPU: jax.devices()[0].platform == "
                             f"{devs[0].platform!r}; nothing is measured anywhere else")
        if len(devs) < chips:
            raise SystemExit(f"bench: the cell asks for {chips} chips, JAX "
                             f"sees {len(devs)}")
        peaks = load_json("peaks.json")
        kind = devs[0].device_kind
        if kind not in peaks:
            raise SystemExit(f"bench: device_kind {kind!r} is not in "
                             f"bench/peaks.json; a peak is never guessed")
        self.devices, self.peaks = devs[:chips], peaks[kind]
        say(f"platform tpu, device_kind {kind!r}, {len(devs)} device(s), "
            f"using {chips}")

    def say_memory(self, where):
        for d in self.devices:
            stats = d.memory_stats() or {}
            say(f"memory {where}: {d}: "
                f"{stats.get('bytes_in_use', 0) / 2**30:.2f} GiB in use, "
                f"{stats.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB peak")

    def read_memory_peak(self):
        self.memory_peak_bytes = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in self.devices)

    # ---- the clock
    def window_opens(self, t0):
        """Set-up ends where the first measured request or step is due."""
        self.setup_s = t0 - T_START

    # ---- the profiler (a --trace 1 run only)
    def trace_start(self):
        import jax
        shutil.rmtree(OUT, ignore_errors=True)
        self.trace_dir = os.path.join(OUT, "trace")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self.trace_window = [time.time(), None]

    def trace_stop(self):
        import jax
        self.trace_window[1] = time.time()
        jax.profiler.stop_trace()

    def reduced_trace(self):
        if self.trace_dir is None:
            return None
        from bench.trace_reduce import reduce_trace
        files = glob.glob(os.path.join(self.trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise SystemExit("bench: the profiler wrote no trace")
        # a rehearsal has no device plane: it reads the host's, to run the code
        reduced = reduce_trace(files[0],
                               self.trace_window[1] - self.trace_window[0],
                               "/host:CPU" if self.args.rehearsal else "/device:TPU:")
        shutil.rmtree(OUT, ignore_errors=True)
        return reduced


def enable_caches(cell):
    """The persistent compile cache at the program's own fixed path inside
    the checkout (or where JAX_COMPILATION_CACHE_DIR says); a cell may ask
    that even sub-second compilations are kept."""
    if cell.get("cache_every_compile"):
        os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
        os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    from paddle_tpu.core.compile_cache import enable_compile_cache
    return enable_compile_cache()


def load_cell(name, rehearsal=False):
    """The cell's file and its configuration's; a rehearsal lays over both
    the tiny sizes of the file the cell names (``rehearsal.like``, under
    ``bench/rehearsal/``) and then the cell's own few."""
    cell = load_json("workloads", name + ".json")
    config = load_json("configs", cell["config"] + ".json")
    if rehearsal:
        own = cell["rehearsal"]
        tiny = merge(load_json("rehearsal", own["like"] + ".json"), own)
        cell = merge(cell, tiny.get("cell", {}))
        config = merge(config, tiny.get("config", {}))
    return cell, config


def is_correct(checks):
    ok = True
    for name, value, limit in checks:
        if isinstance(limit, str):          # ">=1": a count that must be there
            ok &= value >= float(limit[2:])
        elif limit == 0:
            ok &= value == 0
        else:
            ok &= bool(value <= limit)      # a NaN fails
    return ok


def per_layer_metrics(ctx, facts, cell_name):
    """Every per-layer metric of BENCHMARK.json that lists this cell, read
    by its own reader.  A reader that finds nothing returns None and the
    metric is left out of the line."""
    out = {}
    for m in ctx.benchmark["per_layer"]:
        if "workloads" in m and cell_name not in m["workloads"]:
            continue
        spec = load_json("metrics", m["name"] + ".json")
        reader = importlib.import_module(f"bench.readers.{spec['reader']}")
        value = reader.read(spec.get("params", {}), facts)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--set", action="append", default=[], metavar="KEY=VALUE")
    ap.add_argument("--control", default=None)
    args = ap.parse_args(argv)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    cell, config = load_cell(args.workload, args.rehearsal)
    for item in args.set:
        key, _, value = item.partition("=")
        cell["traffic"][key] = json.loads(value)

    ctx = Context(args, cell, config, benchmark)
    cache = enable_caches(cell)
    ctx.find_devices()
    say(f"cell {args.workload}: seed {args.seed}, {args.seconds:g} s, trace "
        f"{args.trace}; compile cache {cache} "
        f"({len(os.listdir(cache)) if os.path.isdir(cache) else 0} entries)")
    driver = importlib.import_module(f"bench.traffic.{cell['generator']}")
    result = driver.run(ctx)

    checks = result["checks"]
    correct = is_correct(checks)
    facts = result.get("facts", {})
    e2e = dict(result["end_to_end"], setup_s=ctx.setup_s)
    say(f"setup_s {ctx.setup_s:.1f}; memory peak "
        f"{(ctx.memory_peak_bytes or 0) / 2**30:.2f} GiB")
    dev = ctx.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(ctx.devices),
              "memory_peak_bytes": ctx.memory_peak_bytes}
    line = {"correct": correct, "attempted": result["attempted"],
            "failed": result["failed"]}
    if args.control:
        line["control"] = args.control
    if args.trace:
        trace = ctx.reduced_trace()
        facts.update(trace=trace, trace_window=ctx.trace_window,
                     peaks=ctx.peaks, chips=len(ctx.devices), config=config,
                     cell=cell, end_to_end=e2e, seconds=args.seconds,
                     memory_peak_bytes=ctx.memory_peak_bytes)
        metrics = per_layer_metrics(ctx, facts, args.workload)
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        line["breakdown"] = trace["breakdown"]
    else:
        metrics = {}
        for m in benchmark["end_to_end"]:
            if "workloads" in m and args.workload not in m["workloads"]:
                continue
            metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                  "unit": m["unit"]}
    shown = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    for n, c in shown.items():
        say(f"compared: {n} = {c['value']} (limit {c['limit']})")
    say(f"correct: {correct}")
    if args.rehearsal:
        # a CPU run's numbers never stand under a metric's name
        print(json.dumps({"rehearsal": True, "correct": correct,
                          "control": args.control,
                          "attempted": result["attempted"],
                          "failed": result["failed"],
                          "rehearsal_counts": {"cpu_" + k: v["value"] for k, v in metrics.items()},
                          "checks": shown}), flush=True)
        return 0
    line.update(metrics=metrics, device=device, checks=shown)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
