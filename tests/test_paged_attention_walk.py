"""The paged-attention kernel's walk (ops/pallas/paged_attention.py): each
row's context in blocks of several pages, one body for the single- and
multi-query and the bf16- and int8-page entry points.  Every case runs the
kernel (CPU: the Pallas interpreter) on a pool whose every page OFF the
row's context - past it on the table, or on no table at all - is poisoned,
and compares with the jnp reference on the same pool unpoisoned."""
import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.ops.pallas import paged_attention as pa

H, KVH, D, PAGE, Q = 4, 2, 128, 16, 4
PPB = pa._pages_per_block(PAGE, D)
BLOCK = PPB * PAGE                  # tokens a trip of the walk covers
S_EVEN, S_ODD = 2 * PPB, 2 * PPB + 3    # table lengths: whole blocks, and not


def _case(ctx, table=S_EVEN, pad_with_last=False, own_scale_tables=False,
          trash=False):
    return dict(ctx=ctx, table=table, pad_with_last=pad_with_last,
                own_scale_tables=own_scale_tables, trash=trash)


CASES = {
    "one_token": _case([1]),
    "one_under_a_block": _case([BLOCK - 1]),
    "at_a_block": _case([BLOCK]),
    "one_over_a_block": _case([BLOCK + 1]),
    "full_table": _case([S_EVEN * PAGE]),
    "rows_of_different_lengths": _case([3, BLOCK + 5, 2 * BLOCK - 7, 40]),
    # a slot nobody holds decodes one token on the trash page
    "inactive_rows_between_live_ones": _case([1, BLOCK + 9, 1, 1, 77],
                                             trash=True),
    # the scheduler pads a slot's table with its last page
    "table_padded_with_its_last_page": _case([50, BLOCK + 1],
                                             pad_with_last=True),
    "table_not_a_multiple_of_the_block": _case(
        [S_ODD * PAGE, 2 * BLOCK + 1, 9], table=S_ODD),
    "table_shorter_than_a_block": _case([PAGE * 3, 5], table=3),
    # the engine: pages of every layer as one stack, scales of one layer
    "scales_under_their_own_tables": _case([BLOCK + 3, 20],
                                           own_scale_tables=True),
}


def _setup(case, multiquery, int8, seed=0):
    """-> (kernel args, kwargs), (reference args, kwargs)."""
    rng = np.random.RandomState(seed)
    ctx = np.asarray(case["ctx"], np.int32)
    S, B = case["table"], len(case["ctx"])
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
    shape = (B, Q, H, D) if multiquery else (B, H, D)
    q = jnp.asarray(rng.randn(*shape), jnp.bfloat16)
    k = jnp.asarray(rng.randn(P, PAGE, KVH, D), jnp.bfloat16)
    v = jnp.asarray(rng.randn(P, PAGE, KVH, D), jnp.bfloat16)
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


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("multiquery", [False, True],
                         ids=["single_query", "multi_query_4"])
def test_walk_matches_reference(multiquery, int8, case):
    (args, kw), (ref_args, ref_kw) = _setup(CASES[case], multiquery, int8)
    kernel, ref = ((pa.paged_attention_multiquery,
                    pa.paged_attention_multiquery_ref) if multiquery
                   else (pa.paged_attention, pa.paged_attention_ref))
    got = np.asarray(kernel(*args, **kw), np.float32)
    want = np.asarray(ref(*ref_args, **ref_kw), np.float32)
    assert np.isfinite(got).all(), "a page off the row's context was read"
    # bf16 operands and a bf16 result: two ulps of the largest output
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)


def test_block_is_about_128_tokens_at_the_serving_shape():
    """The block is a constant of the kernel (PERF.md section 6, PR 29),
    derived from the page and head sizes and from nothing a caller says."""
    assert pa._pages_per_block(16, 128) * 16 == 128
    assert pa._pages_per_block(16, 64) * 16 == 256
    assert pa._pages_per_block(256, 128) == 1       # never less than a page
