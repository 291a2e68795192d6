"""What the paged-attention kernel's calls of the traced window need where
a decode dispatch is a BLOCK of Q positions a sequence (``sdar_moe``), by
the algorithm and not by how the kernel walks its pages.

One forward of a block needs, for each live sequence, K and V of the
``ctx + Q`` tokens it holds with the block ONCE (the Q rows share them), Q
queries and Q outputs; operations: 4 x tokens x heads x head size a row.  A
dispatch makes ``denoising_steps + 1`` forwards, each calling the kernel
once a layer.  A prefill chunk's rows are consecutive positions of ONE
sequence: K and V once up to the chunk's end, its rows each scoring up to
the end of their own block.  Bytes bound it on a v5e.

The contexts come from the spans the benchmark puts around
``runner.run_prefill`` / ``run_decode`` (a --trace 1 run), those that began
inside the traced window; a decode span's ``ctx_sum`` is ``sum(lens + 1)``
over its live sequences, ``lens`` the tokens committed before the block.
"""
from bench.rooflines.paged_attention import row_costs, spans_in_trace
from bench.rooflines.sdar_flops import attended, forwards


def dispatch_needs(cfg, span):
    """(bytes, flops) of ONE layer of a dispatch: all its forwards."""
    kv, qo, fl = row_costs(cfg)
    q = cfg["generation"]["block_length"]
    if span["kind"] == "decode":
        tokens = span["ctx_sum"] + span["rows"] * (q - 1)
        every = forwards(cfg)[1]
        return (every * (tokens * kv + span["rows"] * q * qo),
                every * attended(cfg, span) * fl)
    n, start = span["rows"], span["start"]
    return (start + n) * kv + n * qo, attended(cfg, span) * fl


def needed(facts, calls):
    cfg = facts["config"]
    spans = spans_in_trace(facts)
    if not spans or "generation" not in cfg:
        return None
    total_b = total_f = 0
    for s in spans:
        b, f = dispatch_needs(cfg, s)
        total_b, total_f = total_b + b, total_f + f
    layers = cfg["num_hidden_layers"]
    return {"bytes": total_b * layers, "flops": total_f * layers}
