"""The paged-attention kernel's walk (ops/pallas/paged_attention.py): each
row's context in blocks of several pages, one body for the single- and
multi-query and the bf16- and int8-page entry points.  Every case runs the
kernel (CPU: the Pallas interpreter) on a pool whose every page OFF the
row's context - past it on the table, or on no table at all - is poisoned,
and compares with the jnp reference on the same pool unpoisoned.  A trip's
pages follow the KV heads, the page dtype and the score rows: here
Mistral's 32 q heads over 8 KV heads (GQA groups of 4) take trips of 8
pages (int8: 16 for one query a row, 8 for four);
tests/test_paged_attention_trips.py runs the same cases where they grow to
16, 32 and 64."""
import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.ops.pallas import paged_attention as pa

H, KVH, D, PAGE, Q = 32, 8, 128, 16, 4
LONG = 1 << 20              # table pages: no trip is cut short by its table


def _ppb(h, kvh, q_len, int8=False):
    """Pages a trip of the walk at this shape, on a table of two trips or
    more."""
    return pa._pages_per_block(PAGE, kvh, D, 1 if int8 else 2, h * q_len,
                               LONG)


def _case(ctx, table="even", pad_with_last=False, own_scale_tables=False,
          trash=False, at_reach=False):
    """``ctx`` in tokens (``at_reach``: what the row's LAST query sees, so
    that a multi-query row's last page is the one meant); ``table`` "even"
    (two whole blocks), "odd" (not whole blocks) or a count of pages."""
    return dict(ctx=ctx, table=table, pad_with_last=pad_with_last,
                own_scale_tables=own_scale_tables, trash=trash,
                at_reach=at_reach)


def _cases(block):
    """Every case at a trip of ``block`` tokens; the keys are the ids."""
    full = 2 * block                    # the "even" table's tokens
    return {
        "one_token": _case([1]),
        "one_under_a_block": _case([block - 1]),
        "at_a_block": _case([block]),
        "one_over_a_block": _case([block + 1]),
        "full_table": _case([full]),
        "rows_of_different_lengths": _case([3, block + 5, 2 * block - 7,
                                            40]),
        # a slot nobody holds decodes one token on the trash page
        "inactive_rows_between_live_ones": _case([1, block + 9, 1, 1, 77],
                                                 trash=True),
        # the scheduler pads a slot's table with its last page
        "table_padded_with_its_last_page": _case([50, block + 1],
                                                 pad_with_last=True),
        "table_not_a_multiple_of_the_block": _case(
            [full + 3 * PAGE, 2 * block + 1, 9], table="odd"),
        "table_shorter_than_a_block": _case([PAGE * 3, 5], table=3),
        # the engine: pages of every layer as one stack, scales of one layer
        "scales_under_their_own_tables": _case([block + 3, 20],
                                               own_scale_tables=True),
        # the row's last page opens a trip (the rest of the trip copies it
        # again), or closes one
        "last_page_first_of_a_trip": _case([block + 1, block + PAGE],
                                           at_reach=True),
        "last_page_last_of_a_trip": _case([block, full, block - PAGE + 1],
                                          at_reach=True),
        # a row of one token between full ones, which start its block and
        # whose last trip starts the next row's
        "one_token_between_full_rows": _case([full, 1, full]),
    }


CASES = list(_cases(PAGE))


def _setup(case, multiquery, int8, seed=0, h=H, kvh=KVH):
    """-> (kernel args, kwargs), (reference args, kwargs) at the trip the
    kernel takes for this shape."""
    rng = np.random.RandomState(seed)
    ppb = _ppb(h, kvh, Q if multiquery else 1, int8)
    case = _cases(ppb * PAGE)[case]
    ctx = np.asarray(case["ctx"], np.int32)
    S = {"even": 2 * ppb, "odd": 2 * ppb + 3}.get(case["table"],
                                                   case["table"])
    B = len(ctx)
    if multiquery and case["at_reach"]:
        ctx = np.maximum(ctx - (Q - 1), 1)
    if multiquery:      # row j attends ctx + j tokens: keep the last on the table
        ctx = np.minimum(ctx, S * PAGE - (Q - 1))
    reach = ctx + (Q - 1 if multiquery else 0)      # tokens any query sees
    P = B * S + 3                                   # 3 pages on no table
    tables = rng.permutation(P - 3)[:B * S].reshape(B, S).astype(np.int32)
    live = np.zeros(P, bool)
    for b in range(B):
        n = -(-int(reach[b]) // PAGE)
        if case["trash"] and ctx[b] == 1:
            tables[b] = P - 4               # one page for all of them
        live[tables[b, :n]] = True
        if case["pad_with_last"]:
            tables[b, n:] = tables[b, n - 1]
    shape = (B, Q, h, D) if multiquery else (B, h, D)
    q = jnp.asarray(rng.randn(*shape), jnp.bfloat16)
    k = jnp.asarray(rng.randn(P, PAGE, kvh, D), jnp.bfloat16)
    v = jnp.asarray(rng.randn(P, PAGE, kvh, D), jnp.bfloat16)
    off = jnp.asarray(~live)[:, None, None]
    kw_clean, kw_bad = {}, {}
    if int8:
        (k, ks), (v, vs) = pa.quantize_kv(k), pa.quantize_kv(v)
        bad_k, bad_v = (jnp.where(off[..., None], jnp.int8(127), a)
                        for a in (k, v))
        kw_clean = {"k_scales": ks, "v_scales": vs}
        kw_bad = {"k_scales": jnp.where(off, jnp.nan, ks),
                  "v_scales": jnp.where(off, jnp.nan, vs)}
    else:
        bad_k, bad_v = (jnp.where(off[..., None], jnp.nan, a).astype(a.dtype)
                        for a in (k, v))
    tb, cl = jnp.asarray(tables), jnp.asarray(ctx)
    kernel_tables = tb
    if case["own_scale_tables"]:        # layer 1 of 3 holds the pages
        L, layer = 3, 1
        noise = [jnp.full_like(bad_k, 127 if int8 else jnp.nan)] * L

        def stack(a):
            return jnp.concatenate(noise[:layer] + [a] + noise[layer + 1:])
        bad_k, bad_v = stack(bad_k), stack(bad_v)
        kernel_tables = tb + layer * P
        if int8:
            kw_bad["scale_tables"] = tb
    return ((q, bad_k, bad_v, kernel_tables, cl), kw_bad), \
        ((q, k, v, tb, cl), kw_clean)


def _check(multiquery, int8, case, h=H, kvh=KVH, horizon="row"):
    (args, kw), (ref_args, ref_kw) = _setup(case, multiquery, int8, h=h,
                                            kvh=kvh)
    if not multiquery:
        kernel, ref = pa.paged_attention, pa.paged_attention_ref
    elif horizon == "block":
        kernel = pa.paged_attention_blocks

        def ref(*a, **k):
            return pa.paged_attention_multiquery_ref(*a, **k,
                                                     horizon="block")
    else:
        kernel = pa.paged_attention_multiquery
        ref = pa.paged_attention_multiquery_ref
    got = np.asarray(kernel(*args, **kw), np.float32)
    want = np.asarray(ref(*ref_args, **ref_kw), np.float32)
    assert np.isfinite(got).all(), "a page off the row's context was read"
    # bf16 operands and a bf16 result: two ulps of the largest output
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("multiquery", [False, True],
                         ids=["single_query", "multi_query_4"])
def test_walk_matches_reference(multiquery, int8, case):
    _check(multiquery, int8, case)


CHUNK = 40
CHUNK_BLOCK = _ppb(H, KVH, CHUNK) * PAGE      # the chunk's trip, in tokens


@pytest.mark.parametrize("start", [0, CHUNK_BLOCK - 5, 2 * CHUNK_BLOCK + 3],
                         ids=["prompt_start", "across_a_block", "deep"])
def test_a_chunk_as_one_sequence_is_its_rows(start):
    """``paged_attention_chunk`` (a prefill chunk: ONE sequence of C
    positions, row j seeing start + j + 1 tokens, its pages walked once)
    computes what the one-query kernel computes for each position as a row
    of its own; no page past the chunk's last position is read."""
    C, S = CHUNK, 4 * CHUNK_BLOCK // PAGE
    rng = np.random.RandomState(3)
    P = S + 3
    table = rng.permutation(P)[:S].astype(np.int32)
    q = jnp.asarray(rng.randn(1, C, H, D), jnp.bfloat16)
    k, v = (jnp.asarray(rng.randn(P, PAGE, KVH, D), jnp.bfloat16)
            for _ in range(2))
    off = np.ones(P, bool)
    off[table[:-(-(start + C) // PAGE)]] = False
    bad_k, bad_v = (jnp.where(jnp.asarray(off)[:, None, None, None], jnp.nan,
                              a).astype(a.dtype) for a in (k, v))
    got = pa.paged_attention_chunk(q, bad_k, bad_v, jnp.asarray(table)[None],
                                   jnp.asarray([start + 1], jnp.int32))[0]
    rows = pa.paged_attention(
        q[0], k, v, jnp.broadcast_to(jnp.asarray(table), (C, S)),
        jnp.asarray(start + 1 + np.arange(C), jnp.int32))
    got = np.asarray(got, np.float32)
    assert np.isfinite(got).all(), "a page past the chunk was read"
    np.testing.assert_allclose(got, np.asarray(rows, np.float32),
                               atol=2e-2, rtol=2e-2)


# (KV heads, score rows, pages a trip) at each cell's calls, bf16 pages of
# 16 tokens x 128 (the trip table of PERF.md section 6)
TRIPS = {
    "mistral_decode": (8, 32, 8),
    "mistral_chunk_rows": (8, 32, 8),
    "mistral_verification_q4": (8, 128, 8),
    "solar_gqa_decode_and_chunk_rows": (8, 64, 8),
    "zaya_decode": (2, 8, 32),
    "zaya_chunk_256": (2, 8 * 256, 8),
    "sdar_block_q4": (4, 32 * 4, 16),
    "sdar_chunk_128": (4, 32 * 128, 8),
}


@pytest.mark.parametrize("call", TRIPS)
def test_trip_pages_at_each_cell_shape(call):
    """A trip is as many pages as its K bytes (256 KiB) and its score tile
    (131,072 elements) allow on a long table, from the first block of a KV
    head's ``[128, 128]`` key tile up: a constant of the shapes, never a
    caller's option. At 8 KV heads it is the 8 pages measured best at
    Mistral's shape."""
    kvh, rows, pages = TRIPS[call]
    assert pa._pages_per_block(PAGE, kvh, D, 2, rows, LONG) == pages


def test_trip_pages_off_the_cells():
    """int8 pages at 8 KV heads grow by the bytes alone; a head of 64 starts
    at 16 pages; a page of 256 tokens is never split; a trip grows no
    longer than its table, and never shrinks below the first block."""
    assert pa._pages_per_block(16, 8, 128, 1, 32, LONG) == 16
    assert pa._pages_per_block(16, 8, 128, 1, 128, LONG) == 8  # score tile
    assert pa._pages_per_block(16, 8, 64, 2, 32, LONG) == 16
    assert pa._pages_per_block(16, 2, 64, 2, 8, LONG) == 64
    assert pa._pages_per_block(256, 8, 128, 2, 32, LONG) == 1
    assert pa._pages_per_block(16, 2, 128, 2, 8, 512) == 32     # ZAYA's table
    assert pa._pages_per_block(16, 2, 128, 2, 8, 24) == 16
    assert pa._pages_per_block(16, 2, 128, 2, 8, 3) == 8
