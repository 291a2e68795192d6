"""The walk's cases (tests/test_paged_attention_walk.py) at the calls whose
trips grow past 8 pages: ZAYA's decode call, one query of 8 q heads over 2
KV heads (32 pages a trip; int8 pages 64), and SDAR's block call, Q = 4
positions under a block's horizon over 4 KV heads (16; int8 32).  A file
of its own, so that the interpreter's compiles of the larger trips run
beside the walk's own.  int8 pages at 2 KV heads, which no model serves,
run the cases whose row ends on a trip's first and on its last page: each
new table shape costs the interpreter a compile of 2 x 64 unrolled
copies."""
import pytest

from tests.test_paged_attention_walk import CASES, Q, _check, _ppb

# (q heads, KV heads, multi-query, horizon) and the trip of bf16 pages
CALLS = {"zaya_decode_kvh2": ((8, 2, False, "row"), 32),
         "sdar_blocks_kvh4": ((8, 4, True, "block"), 16)}
INT8_64_PAGES = ("last_page_first_of_a_trip", "last_page_last_of_a_trip")

PARAMS = [pytest.param(call, int8, case,
                       id=f"{call}-{'int8' if int8 else 'bf16'}-{case}")
          for call in CALLS for int8 in (False, True) for case in CASES
          if not (int8 and call == "zaya_decode_kvh2")
          or case in INT8_64_PAGES]


@pytest.mark.parametrize("call,int8,case", PARAMS)
def test_walk_matches_reference_at_each_trip(call, int8, case):
    (h, kvh, multiquery, horizon), pages = CALLS[call]
    assert _ppb(h, kvh, Q if multiquery else 1) == pages
    if int8 and not multiquery:
        assert _ppb(h, kvh, 1, int8=True) == 2 * pages
    _check(multiquery, int8, case, h=h, kvh=kvh, horizon=horizon)
