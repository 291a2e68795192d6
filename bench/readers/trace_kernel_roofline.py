"""A kernel's share of its roofline, in %: the least time the chip could
take for the calls of the traced window (the larger of operations / peak
FLOP/s and bytes / peak bytes/s, from ``bench/rooflines/<roofline>.py`` and
``bench/peaks.json``) over the kernel's device time in the trace.

The kernel's events are the ``XLA Ops`` whose instruction name matches
``pattern``.  With ``share_of_step`` the reader gives the kernel's time as
a share of the matching module's device time.  Nothing found: nothing
returned, never 0.
"""
import importlib
import re


def kernel_seconds(trace, pattern):
    count = seconds = 0
    for name, (c, s) in trace["ops"].items():
        if re.search(pattern, name):
            count, seconds = count + c, seconds + s
    return count, seconds


def read(params, facts):
    trace = facts.get("trace")
    if not trace:
        return None
    calls, seconds = kernel_seconds(trace, params["pattern"])
    if not calls or seconds <= 0:
        return None
    if "share_of_step" in params:
        step = sum(s for name, (c, s) in trace["modules"].items()
                   if re.search(params["share_of_step"], name))
        return 100.0 * seconds / step if step > 0 else None
    roofline = importlib.import_module(f"bench.rooflines.{params['roofline']}")
    need = roofline.needed(facts, calls)
    if need is None:
        return None
    peaks = facts["peaks"]
    least = max(need["flops"] / peaks["bf16_flops_per_s"],
                need["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
