"""Records the small device trace that ``test_recorded_trace`` reads: a
jitted loop of 3 layers around the program's paged-attention kernel, run 5
times on a TPU.  Run on the chip; writes ``small_trace.xplane.pb`` and
``small_trace.json`` (what a by-hand reading of that trace gives, taken
here with ``ProfileData`` event by event, not with ``trace_reduce``) into
the directory given.

    python3 bench/tests/record_trace.py chiprun_out/trace_data
"""
import glob
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(out):
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData
    from paddle_tpu.ops.pallas.paged_attention import paged_attention
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_trace: no TPU")
    os.makedirs(out, exist_ok=True)
    b, h, kvh, d, pages, page = 4, 8, 2, 128, 33, 16
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (b, h, d), jnp.bfloat16)
    kp = jax.random.normal(key, (pages, page, kvh, d), jnp.bfloat16)
    tables = jnp.arange(b * 8, dtype=jnp.int32).reshape(b, 8) % pages
    lens = jnp.full((b,), 100, jnp.int32)

    @jax.jit
    def block(q):
        def layer(_, x):
            return x + paged_attention(x, kp, kp, tables, lens)
        return jax.lax.fori_loop(0, 3, layer, q)

    jax.block_until_ready(block(q))
    tmp = os.path.join(out, "tmp")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    t0 = time.time()
    jax.profiler.start_trace(tmp, profiler_options=opts)
    for _ in range(5):
        jax.block_until_ready(block(q))
        time.sleep(0.002)
    jax.profiler.stop_trace()
    window = time.time() - t0
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    dst = os.path.join(out, "small_trace.xplane.pb")
    shutil.copy(src, dst)
    shutil.rmtree(tmp)

    # by hand: every event of the device's two lines
    plane = [p for p in ProfileData.from_file(dst).planes
             if p.name.startswith("/device:TPU:")][0]
    lines = {ln.name: list(ln.events) for ln in plane.lines}
    runs = [e for e in lines["XLA Modules"] if e.name.startswith("jit_block")]
    kernel = [e for e in lines["XLA Ops"] if e.name.startswith("%paged_attention")]
    spans = sorted((e.start_ns, e.start_ns + e.duration_ns) for e in lines["XLA Ops"])
    busy, end = 0, None
    for s, e in spans:
        if end is None or s > end:
            busy, end = busy + e - s, e
        elif e > end:
            busy, end = busy + e - end, e
    want = {"window_s": window, "busy_s": busy * 1e-9, "module": "jit_block",
            "module_runs": len(runs), "kernel_calls": len(kernel),
            "kernel_s": sum(e.duration_ns for e in kernel) * 1e-9,
            "op_names": sorted({e.name.split(" = ")[0] for e in lines["XLA Ops"]})}
    with open(os.path.join(out, "small_trace.json"), "w") as f:
        json.dump(want, f, indent=1)
    print(json.dumps(want), os.path.getsize(dst), "bytes")


if __name__ == "__main__":
    main(sys.argv[1])
