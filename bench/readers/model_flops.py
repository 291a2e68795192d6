"""The whole step's share of the chip's peak (an ``mfu``), in %: the model's
FLOPs of all tokens processed in a stretch of the run and that stretch's
seconds, both from ``bench/rooflines/<flops>.py``, over peak FLOP/s x chips.
Recomputed operations are not counted."""
import importlib


def read(params, facts):
    got = importlib.import_module(
        f"bench.rooflines.{params['flops']}").flops_and_seconds(facts)
    if not got or not got[0] or got[1] <= 0:
        return None
    flops, seconds = got
    peak = facts["peaks"]["bf16_flops_per_s"] * facts["chips"]
    return 100.0 * flops / (seconds * peak)
