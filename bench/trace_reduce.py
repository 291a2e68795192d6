"""From the profiler's ``.xplane.pb`` to what the per-layer metrics read.

Reads the file with ``jax.profiler.ProfileData`` alone.  A device plane is
one whose name starts with ``/device:TPU:``; on it the line ``XLA Modules``
holds one event for each run of a compiled program (named after the jitted
function: ``jit_prefill``, ``jit_block``, ...) and the line ``XLA Ops`` one
event for each operation on the device, named by its whole HLO text
(``%paged_attention.7 = bf16[...] custom-call(...)``: a Pallas kernel shows
as the custom call XLA made of it, under the name of the jitted function
that wraps it; ``%while.5 = ...`` spans the layer loop and everything in
it).  No ``pallas_call`` of the program has a ``name=`` and no
``jax.named_scope`` is used (ROADMAP D7), so programs are told apart by
module name and kernels by the pattern in the metric's own file.

What comes out, for each device and summed over them:
  busy_s     the union of the intervals in which an operation ran
  modules    name -> [count, seconds]
  ops        name -> [count, seconds], the name cut to the HLO instruction's
             own (``paged_attention.7``, ``fusion.161``, ``while.5``)
  gaps       idle seconds between one module's end and the next one's
             start, summed by (what ran before -> what ran after)
"""
import re
from collections import defaultdict

# operations that only span others: in the union, not in a list of costs
_CONTAINERS = re.compile(r"^(while|conditional|call)(\.\d+)?$")


def _union(intervals):
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def module_name(name):
    """``jit_block(1234567890)`` -> ``jit_block``."""
    return name.split("(")[0].strip()


def op_name(name):
    """``%fusion.161 = bf16[32,14336]{...} fusion(...)`` -> ``fusion.161``."""
    return name.split(" = ")[0].lstrip("%").strip()[:64]


def reduce_plane(plane):
    lines = {ln.name: ln for ln in plane.lines}
    ops_line = lines.get("XLA Ops")
    mod_line = lines.get("XLA Modules")
    modules = defaultdict(lambda: [0, 0.0])
    ops = defaultdict(lambda: [0, 0.0])
    busy, mod_iv = [], []
    if mod_line is not None:
        for ev in mod_line.events:
            name = module_name(ev.name)
            modules[name][0] += 1
            modules[name][1] += ev.duration_ns * 1e-9
            mod_iv.append((ev.start_ns, ev.start_ns + ev.duration_ns, name))
    if ops_line is not None:
        for ev in ops_line.events:
            short = op_name(ev.name)
            ops[short][0] += 1
            ops[short][1] += ev.duration_ns * 1e-9
            busy.append((ev.start_ns, ev.start_ns + ev.duration_ns))
    if not busy:                        # a plane with modules only
        busy = [(s, e) for s, e, _ in mod_iv]
    gaps = defaultdict(float)
    mod_iv.sort()
    for (s0, e0, n0), (s1, e1, n1) in zip(mod_iv, mod_iv[1:]):
        if s1 > e0:
            gaps[f"{n0} -> {n1}"] += (s1 - e0) * 1e-9
    return {"busy_s": _union(busy) * 1e-9, "modules": dict(modules),
            "ops": dict(ops), "gaps": dict(gaps)}


def reduce_trace(path, window_s, plane_prefix="/device:TPU:"):
    """``window_s``: the length of the traced window by the host's clock
    (the device's own first and last events would hide idle ends)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices = {}
    for plane in data.planes:
        if plane.name.startswith(plane_prefix):
            devices[plane.name] = reduce_plane(plane)
    if not devices:
        raise SystemExit(f"bench: the trace holds no {plane_prefix} plane")
    return summarize(devices, window_s)


def summarize(devices, window_s):
    n = len(devices)
    modules = defaultdict(lambda: [0, 0.0])
    ops = defaultdict(lambda: [0, 0.0])
    gaps = defaultdict(float)
    for d in devices.values():
        for src, dst in ((d["modules"], modules), (d["ops"], ops)):
            for k, (c, s) in src.items():
                dst[k][0] += c
                dst[k][1] += s
        for k, s in d["gaps"].items():
            gaps[k] += s
    busy = sum(d["busy_s"] for d in devices.values()) / n
    top = lambda d, key: sorted(d.items(), key=key, reverse=True)[:10]  # noqa: E731
    return {
        "window_s": window_s, "busy_s": busy, "devices": n,
        "busy_by_device": {k: d["busy_s"] for k, d in devices.items()},
        "modules": dict(modules), "ops": dict(ops), "gaps": dict(gaps),
        "breakdown": {
            "device_ops": [[k, v[1] / n] for k, v in top(
                {k: v for k, v in ops.items() if not _CONTAINERS.match(k)},
                lambda kv: kv[1][1])],
            "idle_gaps": [[k, v / n] for k, v in top(gaps, lambda kv: kv[1])],
        },
    }
