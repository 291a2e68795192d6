"""LLM decode/serving path tests (VERDICT #5): paged KV-cache Pallas kernel,
top-p sampling, cached generate(), predictor surface (reference:
block_multi_head_attention, top_p_sampling_kernel.h, analysis_predictor.h)."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM, KVCache


class TestPagedAttention:
    def _mk(self, B=3, H=8, KVH=2, D=128, page=16, S=4, P=32, seed=0):
        import jax.numpy as jnp
        rng = np.random.RandomState(seed)
        q = jnp.asarray(rng.randn(B, H, D).astype(np.float32))
        kp = jnp.asarray(rng.randn(P, page, KVH, D).astype(np.float32))
        vp = jnp.asarray(rng.randn(P, page, KVH, D).astype(np.float32))
        bt = jnp.asarray(rng.choice(P, (B, S), replace=False).astype(np.int32))
        return q, kp, vp, bt

    def test_kernel_matches_reference_gqa(self):
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas.paged_attention import (paged_attention,
                                                           paged_attention_ref)
        q, kp, vp, bt = self._mk()
        cl = jnp.asarray(np.array([5, 33, 64], np.int32))
        out = paged_attention(q, kp, vp, bt, cl)
        ref = paged_attention_ref(q, kp, vp, bt, cl)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_page_boundary_lengths(self):
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas.paged_attention import (paged_attention,
                                                           paged_attention_ref)
        q, kp, vp, bt = self._mk()
        for lens in ([1, 16, 17], [15, 32, 48], [64, 64, 64]):
            cl = jnp.asarray(np.array(lens, np.int32))
            out = paged_attention(q, kp, vp, bt, cl)
            ref = paged_attention_ref(q, kp, vp, bt, cl)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=2e-5, err_msg=str(lens))

    def test_functional_wrapper(self):
        import paddle_tpu.nn.functional as F
        q, kp, vp, bt = self._mk(B=2, S=2, P=8)
        import jax.numpy as jnp
        cl = jnp.asarray(np.array([7, 20], np.int32))
        out = F.paged_attention(paddle.to_tensor(np.asarray(q)),
                                paddle.to_tensor(np.asarray(kp)),
                                paddle.to_tensor(np.asarray(vp)),
                                paddle.to_tensor(np.asarray(bt)),
                                paddle.to_tensor(np.asarray(cl)))
        assert out.shape == [2, 8, 128]
        assert np.isfinite(out.numpy()).all()


class TestKVCache:
    def test_update_and_prefix(self):
        # fixed-shape contract (jit decode): update returns the FULL cache
        # and a traced scalar offset tracks the valid prefix
        cache = KVCache(2, 16, 4, 8)
        k1 = paddle.to_tensor(np.ones((2, 3, 4, 8), np.float32))
        v1 = paddle.to_tensor(np.full((2, 3, 4, 8), 2.0, np.float32))
        kk, vv = cache.update(k1, v1)
        assert int(np.asarray(cache.offset._data)) == 3
        assert kk.shape == [2, 16, 4, 8]
        k2 = paddle.to_tensor(np.full((2, 1, 4, 8), 5.0, np.float32))
        kk, vv = cache.update(k2, k2)
        assert int(np.asarray(cache.offset._data)) == 4
        np.testing.assert_allclose(kk.numpy()[:, :3], 1.0)
        np.testing.assert_allclose(kk.numpy()[:, 3], 5.0)
        np.testing.assert_allclose(kk.numpy()[:, 4:], 0.0)  # untouched tail


class TestGenerate:
    def setup_method(self, _):
        paddle.seed(0)
        self.cfg = LlamaConfig.tiny()
        self.model = LlamaForCausalLM(self.cfg)
        self.model.eval()
        rng = np.random.RandomState(0)
        self.x = paddle.to_tensor(
            rng.randint(0, self.cfg.vocab_size, (2, 8)).astype(np.int32))

    def test_greedy_cache_matches_full_recompute(self):
        """VERDICT #5 done-criterion: cached greedy decode == full-context."""
        a = self.model.generate(self.x, max_new_tokens=6, use_cache=True)
        b = self.model.generate(self.x, max_new_tokens=6, use_cache=False)
        np.testing.assert_array_equal(np.asarray(a._data), np.asarray(b._data))

    def test_gen_state_reuse_and_eviction(self):
        m = self.model
        a1 = m.generate(self.x, max_new_tokens=4)
        states = m._gen_states
        assert len(states) == 1
        key = next(iter(states))
        entry = states[key]
        assert entry["busy"] is False
        # same geometry: reuse (same entry object), identical result
        a2 = m.generate(self.x, max_new_tokens=4)
        assert states[key] is entry
        np.testing.assert_array_equal(np.asarray(a1._data),
                                      np.asarray(a2._data))
        # different batch: second entry
        m.generate(self.x[:1], max_new_tokens=4)
        assert len(m._gen_states) == 2

    def test_generate_reentrant_uses_private_state(self):
        m = self.model
        m.generate(self.x, max_new_tokens=2)
        entry = next(iter(m._gen_states.values()))
        entry["busy"] = True   # simulate an in-flight generate
        try:
            out = m.generate(self.x, max_new_tokens=2)
            assert out.shape == [2, 10]
            # in-flight entry untouched, no overwrite
            assert next(iter(m._gen_states.values())) is entry
        finally:
            entry["busy"] = False

    def test_top_p_and_top_k_decode(self):
        tp = self.model.generate(self.x, max_new_tokens=4, do_sample=True,
                                 top_p=0.8, temperature=0.9)
        tk = self.model.generate(self.x, max_new_tokens=4, do_sample=True,
                                 top_k=5)
        assert tp.shape == [2, 12] and tk.shape == [2, 12]
        v = self.cfg.vocab_size
        assert (np.asarray(tp._data) < v).all() and (np.asarray(tk._data) < v).all()

    def test_eos_early_stop(self):
        # pick eos = the first greedy token → all sequences finish instantly
        first = np.asarray(self.model.generate(
            self.x, max_new_tokens=1)._data)[:, -1]
        eos = int(first[0])
        out = self.model.generate(self.x, max_new_tokens=16, eos_token_id=eos)
        arr = np.asarray(out._data)
        # sequence 0 must have stopped right away (padded with eos if other
        # sequences continued)
        assert arr.shape[1] < 8 + 16 or (arr[0, 9:] == eos).all()


class TestTopPSampling:
    def test_mass_restricted_to_nucleus(self):
        rng = np.random.RandomState(0)
        probs = np.zeros((1, 10), np.float32)
        probs[0, :3] = [0.5, 0.3, 0.15]        # nucleus at p=0.8 = tokens {0,1}
        probs[0, 3:] = 0.05 / 7
        counts = np.zeros(10)
        for seed in range(64):
            _, ids = paddle.ops.top_p_sampling(
                paddle.to_tensor(probs), 0.8, seed=seed + 1)
            counts[int(np.asarray(ids._data)[0, 0])] += 1
        assert counts[:2].sum() == 64, counts    # never leaves the nucleus


class TestPredictor:
    def test_save_load_run(self, tmp_path):
        from paddle_tpu.jit import InputSpec
        import paddle_tpu.inference as infer
        paddle.seed(0)
        net = paddle.nn.Sequential(paddle.nn.Linear(8, 16), paddle.nn.ReLU(),
                                   paddle.nn.Linear(16, 4))
        net.eval()
        x = np.random.RandomState(0).rand(2, 8).astype(np.float32)
        ref = net(paddle.to_tensor(x)).numpy()
        prefix = str(tmp_path / "inference")
        paddle.jit.save(net, prefix, input_spec=[InputSpec([2, 8], "float32")])

        cfg = infer.Config(str(tmp_path))
        pred = infer.create_predictor(cfg)
        outs = pred.run([x])
        np.testing.assert_allclose(outs[0], ref, atol=1e-5)

        # handle-style IO (reference ZeroCopyTensor surface)
        h = pred.get_input_handle(pred.get_input_names()[0])
        h.copy_from_cpu(x)
        pred.run()
        np.testing.assert_allclose(
            pred.get_output_handle("out0").copy_to_cpu(), ref, atol=1e-5)

    def test_predictor_pool(self, tmp_path):
        from paddle_tpu.jit import InputSpec
        import paddle_tpu.inference as infer
        paddle.seed(1)
        net = paddle.nn.Linear(4, 2)
        net.eval()
        prefix = str(tmp_path / "inference")
        paddle.jit.save(net, prefix, input_spec=[InputSpec([1, 4], "float32")])
        pool = infer.PredictorPool(infer.Config(str(tmp_path)), 2)
        x = np.ones((1, 4), np.float32)
        a = pool.retrieve(0).run([x])[0]
        b = pool.retrieve(1).run([x])[0]
        np.testing.assert_allclose(a, b)


class TestLLMEngine:
    """Serving runtime (VERDICT r2 #9): continuous batching over a paged KV
    cache; parity with model.generate; runs sharded on a pp=2 x mp=2 mesh."""

    def _model(self):
        import paddle_tpu as pt
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        pt.seed(0)
        cfg = LlamaConfig(vocab_size=128, hidden_size=64,
                          intermediate_size=176, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=2,
                          max_position_embeddings=128)
        m = LlamaForCausalLM(cfg)
        m.eval()
        return m

    def test_engine_matches_model_generate(self):
        import numpy as np
        import paddle_tpu as pt
        from paddle_tpu.inference.serving import LLMEngine
        m = self._model()
        rng = np.random.RandomState(0)
        prompts = [rng.randint(1, 128, (n,)).astype(np.int32)
                   for n in (5, 9, 3)]
        ref = []
        for p in prompts:
            out = m.generate(pt.to_tensor(p[None, :]), max_new_tokens=6)
            ref.append(np.asarray(out.numpy())[0, len(p):].tolist())
        eng = LLMEngine(m, max_batch=2, max_len=64, page_size=8)
        rids = [eng.add_request(p, max_new_tokens=6) for p in prompts]
        eng.run_until_done()
        for rid, r in zip(rids, ref):
            assert eng.result(rid) == r, (rid, eng.result(rid), r)

    def test_continuous_batching_interleaves(self):
        import numpy as np
        from paddle_tpu.inference.serving import LLMEngine
        m = self._model()
        rng = np.random.RandomState(1)
        eng = LLMEngine(m, max_batch=2, max_len=32, page_size=8)
        # 4 requests through 2 slots: pages must recycle, results per-request
        rids = [eng.add_request(rng.randint(1, 128, (4 + i,)),
                                max_new_tokens=4) for i in range(4)]
        steps = eng.run_until_done()
        assert steps > 0 and len(eng.sched.finished) == 4
        assert all(len(eng.result(r)) == 4 for r in rids)
        # all pages recycled
        assert len(eng.pool.free_pages) == eng.n_pages - 1

    def test_streaming_accessor_parity(self):
        """new_tokens(rid) is incremental and lossless: concatenating every
        increment reproduces result(rid) exactly, across continuous
        batching with slot churn (the public surface the gateway streams
        from — it never reads slot state)."""
        import numpy as np
        from paddle_tpu.inference.serving import LLMEngine
        m = self._model()
        rng = np.random.RandomState(3)
        eng = LLMEngine(m, max_batch=2, max_len=32, page_size=8)
        rids = [eng.add_request(rng.randint(1, 128, (4 + i,)),
                                max_new_tokens=5) for i in range(4)]
        seen = {r: [] for r in rids}
        while eng.sched.waiting or any(
                s is not None for s in eng.sched.slots):
            eng.step()
            for r in rids:
                inc = eng.new_tokens(r)
                assert all(type(t) is int for t in inc)
                seen[r].extend(inc)
        for r in rids:
            seen[r].extend(eng.new_tokens(r))      # final drain
            assert seen[r] == list(eng.result(r))
            assert eng.new_tokens(r) == []         # cursor fully consumed

    def test_stream_generator_parity(self):
        """stream(rid) drives the engine itself and yields exactly the
        batch-path result, ending on the terminal status."""
        import numpy as np
        from paddle_tpu.inference.serving import LLMEngine
        m = self._model()
        rng = np.random.RandomState(4)
        prompt = rng.randint(1, 128, (6,))
        ref_eng = LLMEngine(m, max_batch=1, max_len=32, page_size=8)
        rid0 = ref_eng.add_request(prompt, max_new_tokens=5)
        ref_eng.run_until_done()
        eng = LLMEngine(m, max_batch=1, max_len=32, page_size=8)
        rid = eng.add_request(prompt, max_new_tokens=5)
        toks = list(eng.stream(rid))
        assert toks == list(ref_eng.result(rid0))
        assert eng.status(rid).terminal

    def test_engine_on_pp_mp_mesh(self):
        import numpy as np
        import jax
        from jax.sharding import Mesh
        from paddle_tpu.inference.serving import LLMEngine
        if len(jax.devices()) < 4:
            import pytest
            pytest.skip("needs 4 virtual devices")
        m = self._model()
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("pp", "mp"))
        rng = np.random.RandomState(2)
        prompt = rng.randint(1, 128, (6,)).astype(np.int32)
        # unsharded reference
        ref_eng = LLMEngine(m, max_batch=2, max_len=32, page_size=8)
        r0 = ref_eng.add_request(prompt, max_new_tokens=5)
        ref_eng.run_until_done()
        # sharded engine: same tokens through a pp=2,mp=2 placement
        eng = LLMEngine(m, mesh=mesh, max_batch=2, max_len=32, page_size=8)
        r1 = eng.add_request(prompt, max_new_tokens=5)
        eng.run_until_done()
        assert eng.result(r1) == ref_eng.result(r0)


def test_generate_tokens_per_dispatch_parity():
    """K decode steps per dispatched program must produce identical tokens
    to per-token dispatch (cache state threads through the K-step capture)."""
    import numpy as np
    import paddle_tpu as pt
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    pt.seed(0)
    m = LlamaForCausalLM(LlamaConfig.tiny())
    m.eval()
    rng = np.random.RandomState(0)
    x = pt.to_tensor(rng.randint(1, 256, (2, 7)).astype(np.int32))
    m._gen_states = {}
    a = np.asarray(m.generate(x, max_new_tokens=10,
                              tokens_per_dispatch=1).numpy())
    m._gen_states = {}
    b = np.asarray(m.generate(x, max_new_tokens=10,
                              tokens_per_dispatch=4).numpy())
    np.testing.assert_array_equal(a, b)
    assert b.shape == (2, 17)


class TestEngineRound4:
    """VERDICT r3 #4: chunked prefill, in-engine sampling, on-demand pages."""

    def _model(self):
        import paddle_tpu as pt
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        pt.seed(0)
        cfg = LlamaConfig(vocab_size=128, hidden_size=64,
                          intermediate_size=176, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=2,
                          max_position_embeddings=128)
        m = LlamaForCausalLM(cfg)
        m.eval()
        return m

    def test_prefill_is_chunked_not_per_token(self):
        """A P-token prompt must reach its first output token in
        ceil(P/chunk) prefill dispatches + 0 decode steps, not P steps."""
        import numpy as np
        from paddle_tpu.inference.serving import LLMEngine
        m = self._model()
        rng = np.random.RandomState(2)
        prompt = rng.randint(1, 128, (30,)).astype(np.int32)
        eng = LLMEngine(m, max_batch=2, max_len=64, page_size=8,
                        prefill_chunk=8)
        rid = eng.add_request(prompt, max_new_tokens=1)
        steps = eng.run_until_done()
        # ceil(30/8)=4 prefill dispatches; the 4th samples the only token
        assert steps == 4, steps
        assert len(eng.result(rid)) == 1
        assert eng.ttft(rid) is not None and eng.ttft(rid) > 0

    def test_chunked_prefill_matches_greedy_generate(self):
        """Prefill chunking must not change numerics: same outputs as
        model.generate for a prompt spanning several chunks AND pages."""
        import numpy as np
        import paddle_tpu as pt
        from paddle_tpu.inference.serving import LLMEngine
        m = self._model()
        rng = np.random.RandomState(3)
        prompt = rng.randint(1, 128, (21,)).astype(np.int32)
        out = m.generate(pt.to_tensor(prompt[None, :]), max_new_tokens=5)
        ref = np.asarray(out.numpy())[0, len(prompt):].tolist()
        eng = LLMEngine(m, max_batch=2, max_len=64, page_size=8,
                        prefill_chunk=4)
        rid = eng.add_request(prompt, max_new_tokens=5)
        eng.run_until_done()
        assert eng.result(rid) == ref

    def test_sampled_decode_matches_model_generate(self):
        """Seeded top-p sampling in-engine reproduces model.generate's
        draws token-for-token (same filter order, same categorical key)."""
        import numpy as np
        import paddle_tpu as pt
        from paddle_tpu.inference.serving import LLMEngine
        m = self._model()
        rng = np.random.RandomState(4)
        prompt = rng.randint(1, 128, (7,)).astype(np.int32)
        out = m.generate(pt.to_tensor(prompt[None, :]), max_new_tokens=8,
                         do_sample=True, top_p=0.8, temperature=0.9,
                         seed=1234)
        ref = np.asarray(out.numpy())[0, len(prompt):].tolist()
        eng = LLMEngine(m, max_batch=2, max_len=64, page_size=8,
                        prefill_chunk=8)
        rid = eng.add_request(prompt, max_new_tokens=8, do_sample=True,
                              top_p=0.8, temperature=0.9, seed=1234)
        eng.run_until_done()
        assert eng.result(rid) == ref, (eng.result(rid), ref)

    def test_on_demand_pages_and_early_release(self):
        """Admit reserves only prompt pages; decode grows page-by-page; a
        request ending early (eos) never claims its worst-case pages."""
        import numpy as np
        from paddle_tpu.inference.serving import LLMEngine
        m = self._model()
        rng = np.random.RandomState(5)
        prompt = rng.randint(1, 128, (8,)).astype(np.int32)
        eng = LLMEngine(m, max_batch=1, max_len=64, page_size=8,
                        prefill_chunk=8)
        rid = eng.add_request(prompt, max_new_tokens=40)
        eng.step()                       # prefill: exactly 1 page in use
        used_after_prefill = eng.n_pages - 1 - len(eng.pool.free_pages)
        assert used_after_prefill == 1   # NOT ceil((8+40)/8)=6
        # force an early finish via eos on the next emitted token
        eng.sched.slots[0].eos = None
        for _ in range(9):               # 9 decode tokens -> 17 total -> 3 pages
            eng.step()
        used = eng.n_pages - 1 - len(eng.pool.free_pages)
        assert used == 3, used
        # any token; then match it
        eng.sched.slots[0].eos = eng.sched.slots[0].out[-1]
        # run until the engine emits that token again or request completes
        eng.run_until_done()
        assert len(eng.pool.free_pages) == eng.n_pages - 1   # all freed

    def test_preemption_recovers_and_completes(self):
        """With an OVERSUBSCRIBED page_pool (smaller than worst case) the
        pool runs dry mid-decode, the youngest slot is preempted (recompute)
        and every request still completes with the right token count."""
        import numpy as np
        from paddle_tpu.inference.serving import LLMEngine
        m = self._model()
        rng = np.random.RandomState(6)
        # worst case would be 2*ceil(24/4)=12 pages; give it 7 -> must
        # preempt when both slots outgrow the pool
        eng = LLMEngine(m, max_batch=2, max_len=24, page_size=4,
                        prefill_chunk=8, page_pool=7)
        rids = [eng.add_request(rng.randint(1, 128, (8,)).astype(np.int32),
                                max_new_tokens=16) for _ in range(3)]
        eng.run_until_done()
        assert eng.sched.preemptions > 0          # oversubscription really bit
        assert len(eng.sched.finished) == 3
        for rid in rids:
            assert len(eng.result(rid)) == 16
        assert len(eng.pool.free_pages) == eng.n_pages - 1

    def test_add_request_validation(self):
        import numpy as np
        import pytest
        from paddle_tpu.inference.serving import LLMEngine
        m = self._model()
        eng = LLMEngine(m, max_batch=1, max_len=16, page_size=8)
        with pytest.raises(ValueError):   # ADVICE r3: silent truncation
            eng.add_request(np.arange(1, 9), max_new_tokens=9)
        with pytest.raises(ValueError):
            eng.add_request(np.array([], np.int32), max_new_tokens=1)
        eng.add_request(np.arange(1, 9), max_new_tokens=8)  # exactly fits

    def test_decode_block_matches_single_step(self):
        """decode_block=4 (K decode steps fused per dispatch) must emit the
        same tokens as per-step decode, greedy AND seeded-sampled, and use
        fewer dispatches."""
        import numpy as np
        from paddle_tpu.inference.serving import LLMEngine
        m = self._model()
        rng = np.random.RandomState(7)
        prompt = rng.randint(1, 128, (9,)).astype(np.int32)
        outs = {}
        steps = {}
        for blk in (1, 4):
            eng = LLMEngine(m, max_batch=2, max_len=64, page_size=8,
                            prefill_chunk=8, decode_block=blk)
            rids = [eng.add_request(prompt, max_new_tokens=7),
                    eng.add_request(prompt, max_new_tokens=7,
                                    do_sample=True, top_p=0.8, seed=99)]
            steps[blk] = eng.run_until_done()
            outs[blk] = [eng.result(r) for r in rids]
        assert outs[1] == outs[4], (outs[1], outs[4])
        assert steps[4] < steps[1]

    def test_repeated_preemption_no_prompt_double_fold(self):
        """A request preempted TWICE must re-fold original_prompt + out, not
        compound the earlier fold (which duplicated context and overflowed
        the page table)."""
        import numpy as np
        from paddle_tpu.inference.serving import LLMEngine
        m = self._model()
        rng = np.random.RandomState(8)
        eng = LLMEngine(m, max_batch=2, max_len=24, page_size=4,
                        prefill_chunk=8, page_pool=7, decode_block=4)
        rids = [eng.add_request(rng.randint(1, 128, (8,)).astype(np.int32),
                                max_new_tokens=16) for _ in range(3)]
        eng.run_until_done()
        assert eng.sched.preemptions >= 2
        for rid in rids:
            r = eng.sched.finished[rid]
            assert len(r.out) == 16
            assert r.prompt == r.prompt0 + r.out[:len(r.prompt) - 8] \
                or len(r.prompt) == 8      # never double-folded


class TestAutoDecodeBlock:
    """decode_block='auto' fits t(k) = RTT + k*c from dispatch samples and
    targets the block where RTT costs <= ~25% of device time (VERDICT r4
    weak #7: the knob previously never adapted to measured RTT)."""

    def _engine(self, **kw):
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.inference.serving import LLMEngine
        paddle.seed(0)
        cfg = LlamaConfig.tiny()
        m = LlamaForCausalLM(cfg)
        m.eval()
        return cfg, LLMEngine(m, max_batch=2, max_len=96, page_size=8,
                              prefill_chunk=8, decode_block="auto", **kw)

    def test_runs_and_adapts(self):
        cfg, eng = self._engine()
        rng = np.random.RandomState(0)
        prompt = rng.randint(1, cfg.vocab_size, (8,)).astype(np.int32)
        rid = eng.add_request(prompt, max_new_tokens=40)
        eng.run_until_done()
        assert len(eng.result(rid)) == 40
        assert eng.auto_decode_block >= 1     # solved, not stuck pre-sample

    def test_block_model_math_high_rtt(self):
        """Feed synthetic timings: RTT 100ms, c 3ms/token -> target 32 (the
        cap), the regime where dispatch latency dominates."""
        _, eng = self._engine()
        eng._record_block_sample(1, 0.103)
        assert eng._block_target == 2         # second sample size forced
        eng._record_block_sample(2, 0.106)
        assert eng._block_target == 32        # 3*RTT/c = 100 -> pow2 cap

    def test_block_model_math_low_rtt(self):
        """Local runtime: RTT ~0.2ms, c 3ms -> block stays tiny."""
        _, eng = self._engine()
        eng._record_block_sample(1, 0.0032)
        eng._record_block_sample(2, 0.0062)
        assert eng._block_target <= 2

    def test_fixed_block_unchanged(self):
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.inference.serving import LLMEngine
        paddle.seed(0)
        m = LlamaForCausalLM(LlamaConfig.tiny())
        m.eval()
        eng = LLMEngine(m, max_batch=2, max_len=64, page_size=8,
                        prefill_chunk=8, decode_block=4)
        assert eng.auto_decode_block == 4

    def test_late_samples_correct_the_fit(self):
        """Least-squares over ALL sampled block sizes (ADVICE r5: the old
        two-earliest-medians fit froze the model): a large-k sample that
        contradicts the small-k extrapolation pulls the target back down."""
        _, eng = self._engine()
        eng._record_block_sample(1, 0.103)
        eng._record_block_sample(2, 0.106)
        assert eng._block_target == 32        # small-k fit: huge RTT
        # k=32 runs now produce real timings: the per-token cost is much
        # higher than the k=1->2 delta suggested. The frozen fit would stay
        # at 32 forever; the full least-squares re-solves to a small block.
        for _ in range(8):
            eng._record_block_sample(32, 1.6)
        assert eng._block_target < 32, eng._block_target

    def test_periodic_small_k_resample(self):
        """Every 64th sample the target drops to a small k for one dispatch
        so the RTT intercept keeps getting re-measured."""
        _, eng = self._engine()
        eng._record_block_sample(1, 0.103)
        eng._record_block_sample(2, 0.106)
        assert eng._block_target == 32
        eng._block_n = 63
        eng._record_block_sample(32, 0.196)   # consistent with the fit
        assert eng._block_target == 2         # forced re-sample at small k
        eng._record_block_sample(2, 0.106)
        assert eng._block_target == 32        # model re-solved, back up


# ---------------------------------------------------------------- KV pool
# The serving programs carry the stacked pools through the layer loop and
# update them in place; a pool that is scanned over is copied whole once a
# dispatch and sliced out / written back once a layer (PERF.md section 6,
# PR 26).  These hold the programs to that, and the writes to their rows.
_POOL_L, _POOL_PAGE, _POOL_B, _POOL_KV = 4, 8, 3, 4


@pytest.fixture(scope="module", params=["auto", "int8"], ids=["float", "int8"])
def pool_runner(request):
    """``make(n_pages)``: a runner over a tiny model of 4 layers, with pages
    of the model's dtype or int8 pages.  The model is float32: the CPU
    backend widens a bfloat16 scatter (and every bfloat16 weight) to
    float32 and back, which would put a pool among the temporaries here
    that the chip never sees; tests/test_aot_tpu_compile.py holds the
    bfloat16 pool to the same on the v5e's compiler."""
    from paddle_tpu.inference.engine.runner import ModelRunner
    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=_POOL_L))
    model.eval()
    made = {}

    def make(n_pages):
        if n_pages not in made:
            made[n_pages] = ModelRunner(
                model, max_batch=_POOL_B, page_size=_POOL_PAGE,
                prefill_chunk=8, n_pages=n_pages, use_kernel=False,
                kv_cache_dtype=request.param)
        return made[n_pages]
    return make


def _program_and_args(r, kind, tables):
    """The jitted program of ``kind`` and host arguments (after W and the
    cache) that address ``tables`` [B, S]; the rows each program writes are
    spelled out in ``_written_rows``."""
    i32, f32 = np.int32, np.float32
    B = r.max_batch

    def sampling(shape):
        return (np.ones(shape, i32), np.ones(shape, f32), np.ones(shape, f32),
                np.zeros(shape, i32), np.zeros(shape, i32))
    if kind.startswith("decode"):
        k = int(kind[6:])
        return r._build_decode(k), (
            np.array([5, 9, 7], i32), np.array([3, 10, 13], i32), tables,
            np.array([1, 1, 0], i32), *sampling(B), np.zeros(B, i32),
            np.zeros(B, i32), np.zeros(B, i32))      # .., fold, take, prev
    if kind == "prefill":
        return r._prefill, (
            np.arange(1, 9, dtype=i32), i32(4), tables[0], i32(6),
            *sampling(()))
    return r._build_verify(_POOL_KV), (
        np.arange(1, 1 + B * _POOL_KV, dtype=i32).reshape(B, _POOL_KV),
        np.array([6, 13, 2], i32), tables, np.array([3, 0, 2], i32),
        *sampling(B), np.zeros(B, i32))


def _written_rows(kind, tables, trash):
    """(page, within) of every row ``_program_and_args``'s dispatch writes,
    the same in every layer: position p of a slot lands in its table's page
    p // 8 at row p % 8, an invalid or inactive row in the trash page."""
    page = _POOL_PAGE
    if kind == "decode1":       # slots 0, 1 at lens 3, 10; slot 2 inactive
        return [(tables[0][0], 3), (tables[1][1], 2), (trash, 13 % page)]
    if kind == "prefill":       # positions 4..9 valid, 10 and 11 padding
        return ([(tables[0][p // page], p % page) for p in range(4, 10)]
                + [(trash, 2), (trash, 3)])
    # verify: slot 0 rows at 6, 7, 8 (+ one padding row at 9), slot 1
    # inactive (positions 13..16), slot 2 rows at 2, 3 (+ padding at 4, 5)
    return ([(tables[0][p // page], p % page) for p in (6, 7, 8)]
            + [(tables[2][0], 2), (tables[2][0], 3)]
            + [(trash, p % page) for p in (9, 13, 14, 15, 16, 4, 5)])


class TestPoolIsCarried:
    N_PAGES = 512

    @pytest.mark.parametrize("kind",
                             ["decode1", "decode2", "prefill", "verify"])
    def test_no_program_copies_a_pool(self, pool_runner, kind):
        """Temporaries stay under one pool's bytes and no ``copy`` has a
        pool's shape, stacked, flat or one layer's."""
        import re
        import jax.numpy as jnp
        r = pool_runner(self.N_PAGES)
        tables = np.arange(_POOL_B * 4, dtype=np.int32).reshape(_POOL_B, 4)
        prog, args = _program_and_args(r, kind, tables)
        compiled = prog.lower(r.W, r.cache,
                              *[jnp.asarray(a) for a in args]).compile()
        assert (compiled.memory_analysis().temp_size_in_bytes
                < min(a.nbytes for a in r.cache[:2]))
        text = compiled.as_text()
        for a in r.cache:               # the scale pools of int8 pages too
            L, n = a.shape[:2]
            lead = "|".join((f"{L},{n}", str(L * n), str(n), f"1,{n}"))
            tail = ",".join(map(str, a.shape[2:]))
            assert not re.findall(
                rf"= \w+\[(?:{lead}),{tail}\](?:\{{[^}}]*\}})? copy\(", text)

    @pytest.mark.parametrize("kind", ["decode1", "prefill", "verify"])
    def test_a_dispatch_writes_its_rows_and_nothing_else(self, pool_runner,
                                                         kind):
        """After one dispatch on a pool of known values every row it did not
        address is bit-identical, in every layer, and its own rows changed
        in every layer.  What it reads lies in its tables alone: the same
        dispatch over NaN in every other page gives the same tokens and
        rows.  And layer ``l`` reads layer ``l``'s pages, not a neighbour's:
        other values in layer ``j``'s pages leave the rows written in
        layers up to ``j`` as they were and change those of every later
        layer (a layer's rows are made from what the layers before it
        read)."""
        import jax.numpy as jnp
        n_pages = 32
        r = pool_runner(n_pages)
        trash = r.trash_page
        # first and last page of a layer among the written ones; the trash
        # page (the layer's very last) takes the invalid rows
        tables = np.array([[0, n_pages - 2, 9], [4, n_pages - 2, 11],
                           [1, 6, 12]], np.int32)
        if kind == "decode1":
            tables[0, 1] = 5    # two slots never own one page
        rows = _written_rows(kind, tables, trash)
        live = [(p, w) for p, w in rows if p != trash]
        rng = np.random.RandomState(3)

        def pattern(a):
            if a.dtype == jnp.int8:
                return rng.randint(-127, 128, a.shape).astype(np.int8)
            return rng.uniform(0.5, 1.5, a.shape).astype(a.dtype)
        before = [pattern(a) for a in r.cache]

        prog, args = _program_and_args(r, kind, tables)   # compiled once
        args = [jnp.asarray(a) for a in args]

        def dispatch(pools):
            toks, after = prog(r.W, tuple(jnp.asarray(p) for p in pools),
                               *args)
            if kind.startswith("decode"):   # (the block's, each row's last)
                toks = toks[0]
            return np.asarray(toks), [np.asarray(a) for a in after]

        toks, after = dispatch(before)
        for b, a in zip(before, after):
            untouched = np.ones(a.shape[:3], bool)
            for p, w in rows:
                untouched[:, p, w] = False
                for l in range(_POOL_L):
                    assert not np.array_equal(a[l, p, w], b[l, p, w]), (
                        l, p, w)
            np.testing.assert_array_equal(a[untouched], b[untouched])

        poisoned = [b.copy() for b in before]
        off_table = np.setdiff1d(np.arange(n_pages), tables.ravel())
        for b in poisoned:
            if b.dtype != np.int8:          # int8 pages: the scales carry it
                b[:, off_table] = np.nan
        toks_p, after_p = dispatch(poisoned)
        np.testing.assert_array_equal(toks, toks_p)
        for a, ap in zip(after, after_p):
            for p, w in live:
                np.testing.assert_array_equal(a[:, p, w], ap[:, p, w])

        for j in range(_POOL_L):
            shaken = [b.copy() for b in before]
            for b in shaken:
                b[j] = pattern(b[j])
            _, after_j = dispatch(shaken)
            for a, aj in zip(after, after_j):
                for p, w in live:
                    for l in range(_POOL_L):
                        assert np.array_equal(a[l, p, w], aj[l, p, w]) == (
                            l <= j), (j, l, p, w)


# --- one definition of the dense Llama block on raw arrays ------------------
# models/llama.py holds the block once (block_qkv / block_out, around the
# caller's attention): the engine's programs and the SPMD pipeline stage call
# it, and the eager LlamaDecoderLayer, which runs through the op registry, is
# held to it here.

@pytest.fixture(scope="module", params=[2, 4], ids=["gqa", "mha"])
def block_case(request):
    """(cfg, model, layer 0's weights, x [2, 9, H], the eager layer's
    output) for a float32 tiny Llama with 4 query heads over 2 or 4 KV
    heads."""
    from paddle_tpu.models.llama import BLOCK_KEYS
    paddle.seed(0)
    cfg = LlamaConfig.tiny(num_key_value_heads=request.param)
    model = LlamaForCausalLM(cfg)
    model.eval()
    W = model.stacked_weights()
    p = {k: W[k][0] for k in BLOCK_KEYS}
    x = np.random.RandomState(1).randn(2, 9, cfg.hidden_size).astype(
        np.float32)
    with paddle.no_grad():
        want = model.llama.layers[0](paddle.to_tensor(x)).numpy()
    return cfg, model, p, x, want


class TestLlamaBlockOnRawArrays:
    # float32 throughout, activations of O(1): the two sides differ in the
    # order of float32 operations alone (a RoPE table against sin/cos at the
    # positions, where the attention scales), which reads as one ulp here
    # (1.2e-7). 1e-5 absolute is eighty times that, and a hundredth of what
    # rotating interleaved pairs in place of half-split lanes gives (1.5e-3)
    ATOL = 1e-5

    def test_halves_around_plain_attention_equal_the_eager_layer(
            self, block_case):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.models.llama import block_out, block_qkv
        cfg, _, p, x, want = block_case
        nh, kvh = cfg.num_attention_heads, cfg.num_key_value_heads
        b, s, H = x.shape
        rows = jnp.asarray(x).reshape(b * s, H)
        pos = jnp.tile(jnp.arange(s, dtype=jnp.int32), b)
        q, k, v = (a.reshape((b, s) + a.shape[1:]) for a in block_qkv(
            p, rows, pos, nh, kvh, cfg.rms_norm_eps, cfg.rope_theta))
        k, v = (jnp.repeat(a, nh // kvh, axis=2) for a in (k, v))
        sc = jnp.einsum("bsnd,btnd->bnst", q, k) / np.sqrt(q.shape[-1])
        sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
        att = jnp.einsum("bnst,btnd->bsnd", jax.nn.softmax(sc, axis=-1), v)
        got = block_out(p, rows, att.reshape(b * s, nh, -1),
                        cfg.rms_norm_eps).reshape(x.shape)
        np.testing.assert_allclose(np.asarray(got), want, atol=self.ATOL,
                                   rtol=0)

    def test_pipeline_stage_equals_the_eager_layer(self, block_case):
        """The stage once rotated interleaved pairs where the model rotates
        half-split lanes: another function of the same weights."""
        import jax.numpy as jnp
        from paddle_tpu.models.llama import make_decoder_stage
        cfg, _, p, x, want = block_case
        _, apply = make_decoder_stage(cfg)
        got = apply(p, jnp.asarray(x))
        np.testing.assert_allclose(np.asarray(got), want, atol=self.ATOL,
                                   rtol=0)

    def test_stacked_weights_and_their_specs(self, block_case):
        from paddle_tpu.models.llama import BLOCK_KEYS, stacked_weight_specs
        cfg, model, _, _, _ = block_case
        W = model.stacked_weights()
        specs = stacked_weight_specs("pp", "mp")
        assert set(W) == set(specs) == set(BLOCK_KEYS) | {
            "embed", "norm", "head"}
        for k, a in W.items():
            assert len(specs[k]) <= a.ndim, k
            # the layer axis leads the block's leaves, and only those
            assert (tuple(specs[k][:1]) == ("pp",)) == (k in BLOCK_KEYS), k
            if k in BLOCK_KEYS:
                assert a.shape[0] == cfg.num_hidden_layers, k
        assert W["head"].shape == (cfg.hidden_size, cfg.vocab_size)

    def test_tied_embeddings_give_the_head(self):
        paddle.seed(0)
        model = LlamaForCausalLM(LlamaConfig.tiny(tie_word_embeddings=True))
        W = model.stacked_weights()
        np.testing.assert_array_equal(W["head"], W["embed"].T)

    def test_a_moe_model_is_refused_by_name(self):
        paddle.seed(0)
        model = LlamaForCausalLM(LlamaConfig.tiny_moe())
        with pytest.raises(NotImplementedError, match="MoELayer"):
            model.stacked_weights()
