"""The runner's in-graph sampler does what the dispatch's rows ask for
(PERF.md section 6, PR 31): a batch of greedy rows takes the arg-max alone,
behind ONE batch-level ``lax.cond`` in each serving program; a batch with
any sampled row gives every row the tokens it got when each row ran the
whole filter; the sampled path's one key-value sort is ``argsort`` + gather
bit for bit."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu.inference.engine import runner as rn

V = 512


def _row_before(logits, greedy, temp, topp, topk, seed):
    """``_sample_row`` as it stood before the batch-level branch: every row,
    greedy or not, through softmax, top-k, ``argsort`` + a gather of the
    sorted probabilities, ``cumsum`` and the Gumbel draw — the reference the
    rows' tokens are held to."""
    maxk = min(rn._MAXK, logits.shape[-1])
    amax = jnp.argmax(logits)
    l = logits / jnp.where(temp > 0, temp, 1.0)
    probs = jax.nn.softmax(l)
    kvals, _ = jax.lax.top_k(probs, maxk)
    thresh = kvals[jnp.clip(topk - 1, 0, maxk - 1)]
    probs = jnp.where((topk > 0) & (probs < thresh), 0.0, probs)
    probs = probs / jnp.sum(probs)
    sort_idx = jnp.argsort(-probs)
    sorted_p = probs[sort_idx]
    cum = jnp.cumsum(sorted_p)
    keep = jnp.where(topp < 1.0, (cum - sorted_p) < topp, sorted_p >= 0)
    filtered = jnp.where(keep, sorted_p, 0.0)
    filtered = filtered / jnp.sum(filtered)
    key = jax.random.PRNGKey(seed)
    choice = jax.random.categorical(
        key, jnp.log(jnp.maximum(filtered, 1e-30))[None, :], axis=-1)[0]
    tok = sort_idx[choice]
    return jnp.where(greedy > 0, amax, tok).astype(jnp.int32)


def _slots(mix, B=8, seed=0):
    """Per-slot sampling parameters as ``core._decode_args`` sends them: idle
    slots (the last three) greedy with neutral parameters; live slots greedy,
    seeded top-k / top-p sampled, or a mix of both."""
    rng = np.random.RandomState(seed)
    live = B - 3
    sampled = {"greedy": np.zeros(live, bool), "sampled": np.ones(live, bool),
               "mixed": np.arange(live) % 2 == 1}[mix]
    greedy = np.ones(B, np.int32)
    temp, topp = np.ones(B, np.float32), np.ones(B, np.float32)
    topk, seeds = np.zeros(B, np.int32), np.zeros(B, np.int32)
    fold = np.zeros(B, np.int32)
    greedy[:live] = ~sampled
    temp[:live] = rng.choice([0.7, 1.0, 1.3], live)
    topp[:live] = rng.choice([0.5, 0.9, 1.0], live)
    topk[:live] = rng.choice([0, 5, 40], live)
    seeds[:live] = rng.randint(1, 2 ** 30, live)
    fold[:live] = rng.randint(0, 2, live)
    return greedy, temp, topp, topk, seeds, fold


class TestRowsGetTheTokensTheyGot:
    @pytest.mark.parametrize("mix", ["greedy", "sampled", "mixed"])
    @pytest.mark.parametrize("shape", ["decode-k1", "decode-k4", "verify"])
    def test_against_the_row_by_row_sampler(self, shape, mix):
        greedy, temp, topp, topk, seeds, fold = _slots(mix)
        B = greedy.shape[0]
        rng = np.random.RandomState(7)
        if shape == "verify":
            # B x Kv flat rows, a slot's parameters repeated over its rows,
            # the seed schedule of runner._build_verify
            Kv = 4
            row_j = np.tile(np.arange(Kv, dtype=np.int32), B)
            rep = lambda a: np.repeat(a, Kv)       # noqa: E731
            args = [rep(greedy), rep(temp), rep(topp), rep(topk),
                    rep(seeds) + row_j * rep(fold)]
            steps = [args]
        else:
            # the K steps of one decode block: seeds + i * fold
            K = 1 if shape == "decode-k1" else 4
            steps = [[greedy, temp, topp, topk, seeds + i * fold]
                     for i in range(K)]
        n = steps[0][0].shape[0]
        logits = rng.randn(len(steps), n, V).astype(np.float32)
        # ties at the top: the arg-max and the stable sort both take the
        # first of equal entries
        logits[:, :, 11] = logits[:, :, 300] = logits.max(-1)

        def block(logits, stacked):
            def one(_, xs):
                return None, rn._sample_rows(xs[0], *xs[1:])
            return jax.lax.scan(one, None, (logits,) + tuple(stacked))[1]

        stacked = [np.stack(col) for col in zip(*steps)]
        got = np.asarray(jax.jit(block)(logits, stacked))
        want = np.stack([np.asarray(jax.vmap(_row_before)(lg, *step))
                         for lg, step in zip(logits, steps)])
        assert got.dtype == np.int32 and got.shape == want.shape
        np.testing.assert_array_equal(got, want)
        if mix != "sampled":
            am = logits.argmax(-1)
            g = steps[0][0] > 0
            np.testing.assert_array_equal(got[:, g], am[:, g])
        if mix != "greedy":        # the draw is live: not all the arg-max
            assert (got != logits.argmax(-1)).any()


class TestKeyValueSort:
    @pytest.mark.parametrize("case", ["ties", "zeros", "top_k_filtered"])
    def test_bit_equal_to_argsort_and_gather(self, case):
        rng = np.random.RandomState(3)
        if case == "ties":
            p = rng.choice(np.float32([0.5, 0.25, 0.125, 1e-9]), V)
        elif case == "zeros":
            p = rng.rand(V).astype(np.float32)
            p[rng.rand(V) < 0.6] = 0.0
        else:                      # what top-k leaves: 5 values, the rest 0
            p = np.zeros(V, np.float32)
            p[rng.choice(V, 5, replace=False)] = rng.rand(5)
        p = jnp.asarray(p / p.sum())
        sorted_p, sort_idx = jax.jit(rn._sort_desc)(p)
        want_idx = jnp.argsort(-p)
        assert sort_idx.dtype == want_idx.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(sort_idx),
                                      np.asarray(want_idx))
        # bit for bit, the sign of a zero included
        np.testing.assert_array_equal(
            np.asarray(sorted_p).view(np.uint32),
            np.asarray(p[want_idx]).view(np.uint32))


# --------------------------------------------- the programs hold one branch

@pytest.fixture(scope="module")
def engine():
    from paddle_tpu.inference.serving import LLMEngine
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    pt.seed(0)
    cfg = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=176,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, max_position_embeddings=128)
    model = LlamaForCausalLM(cfg)
    model.eval()
    return LLMEngine(model, max_batch=2, max_len=64, page_size=8,
                     prefill_chunk=8)


def _walk(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _walk(inner)


_FILTER = {"sort", "gather", "cumsum", "top_k", "random_bits", "threefry2x32",
           "reduce_window_sum", "exp"}


class TestOneBranchOverTheBatch:
    @pytest.mark.parametrize("kind", ["decode-k1", "decode-k4", "prefill",
                                      "verify"])
    def test_arg_max_branch_holds_no_filter(self, engine, kind):
        r = engine.runner
        B, S = r.max_batch, engine.sched.slot_tables.shape[1]
        i32, f32 = np.int32, np.float32
        per_slot = [np.ones(B, i32), np.ones(B, f32), np.ones(B, f32),
                    np.zeros(B, i32), np.zeros(B, i32), np.zeros(B, i32)]
        if kind == "prefill":
            prog = r._build_prefill()
            args = [np.zeros(r.chunk, i32), i32(0), np.zeros(S, i32), i32(3),
                    i32(1), f32(1), f32(1), i32(0), i32(0)]
        elif kind == "verify":
            prog = r._build_verify(4)
            args = [np.zeros((B, 4), i32), np.zeros(B, i32),
                    np.zeros((B, S), i32), np.ones(B, i32)] + per_slot
        else:
            prog = r._build_decode(int(kind[-1]))
            args = [np.zeros(B, i32), np.zeros(B, i32),
                    np.zeros((B, S), i32), np.ones(B, i32)] + per_slot + [
                np.zeros(B, i32), np.zeros(B, i32)]         # take, prev
        jaxpr = jax.make_jaxpr(prog)(r.W, r.cache, *args).jaxpr
        conds = [e for e in _walk(jaxpr) if e.primitive.name == "cond"]
        assert len(conds) == 1
        cond, = conds
        # over the whole batch: a scalar predicate (under vmap, a per-row
        # predicate would have become a select and left no cond at all)
        assert cond.invars[0].aval.shape == ()
        sampled, arg_max = cond.params["branches"]       # index 0: False
        in_arg_max = {e.primitive.name for e in _walk(arg_max.jaxpr)}
        in_sampled = {e.primitive.name for e in _walk(sampled.jaxpr)}
        assert not in_arg_max & _FILTER, in_arg_max
        assert {"sort", "cumsum", "top_k"} <= in_sampled
        # and no vocabulary-sized sort anywhere outside the branch
        outside = [e for e in _walk(jaxpr) if e.primitive.name == "sort"]
        inside = [e for e in _walk(sampled.jaxpr)
                  if e.primitive.name == "sort"]
        assert len(outside) == len(inside) == 1
