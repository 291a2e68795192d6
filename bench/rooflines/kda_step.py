"""What the ``kda_step`` kernel's calls of the traced window need, by the
algorithm: for each LIVE row of a decode dispatch the row's recurrent state
of one layer read once and written once (heads x dk x dv float32, twice),
its q, k and log-decay (heads x dk each), v (heads x dv) and beta (heads)
in, and o (heads x dv) out, all float32.  Operations: 2 x 3 x heads x dk x
dv a row.  Bytes bound it on a v5e.  An idle row's trip to the idle state
is not needed and not counted.

A decode dispatch calls the kernel once for each KDA layer, so ``calls``
(the kernel's events in the trace) is dispatches x KDA layers; the rows of
a call are the mean live rows of the decode dispatches whose spans (the
benchmark's own, around ``runner.run_decode``) began inside the traced
window.
"""
from bench.rooflines.paged_attention import spans_in_trace


def row_needs(cfg):
    """(bytes, flops) of one live row in one layer."""
    lin = cfg["linear_attn_config"]
    h, d = lin["num_heads"], lin["head_dim"]
    state = h * d * d * 4
    vectors = (3 * h * d + 2 * h * d + h) * 4       # q, k, g; v, o; beta
    return 2 * state + vectors, 2 * 3 * h * d * d


def needed(facts, calls):
    decode = [s for s in spans_in_trace(facts) if s["kind"] == "decode"]
    if not decode:
        return None
    rows = sum(s["rows"] for s in decode) / len(decode)
    b, f = row_needs(facts["config"])
    return {"bytes": calls * rows * b, "flops": calls * rows * f}
