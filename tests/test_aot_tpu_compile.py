"""Every Pallas kernel must get through the real XLA:TPU and Mosaic compilers
at the shapes chip_smoke.py runs — checked here, with no chip, by compiling
ahead of time for a v5e (the installed libtpu carries the compilers).

Compiling is not being right (chip_smoke.py compares each kernel with its
reference on the device), but a kernel Mosaic refuses never gets that far.
The file name sorts first on purpose: tier-1 runs alphabetically into a
timeout, and this is the one CPU-side guard of the on-chip path.
"""
import functools
import re

import numpy as np
import pytest

pytest.importorskip("libtpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,  # noqa: E402
                          SingleDeviceSharding)

from paddle_tpu.ops.pallas import flash_attention as fa  # noqa: E402
from paddle_tpu.ops.pallas import kda  # noqa: E402
from paddle_tpu.ops.pallas import moe_gmm as mg  # noqa: E402
from paddle_tpu.ops.pallas import paged_attention as pa  # noqa: E402
from paddle_tpu.ops.pallas import quant_matmul as qm  # noqa: E402

# chip_smoke.Sizes: GPT-2 124M at b16 x 1024; Llama-3-8B widths, 8 slots x
# 2,048 tokens in pages of 16, prefill chunks of 32 rows
GPT2 = (16, 1024, 12, 64)
NH, KVH, D, PAGE, SLOTS, MAX_LEN, CHUNK = 32, 8, 128, 16, 8, 2048, 32
HIDDEN, FFN = 4096, 14336


@pytest.fixture(scope="module")
def v5e():
    """Four ``TpuDevice``s of kind 'TPU v5 lite' that exist only as a compile
    target, with every kernel's ``_interpret()`` forced off (on the CPU
    backend they would otherwise lower to the interpreter, not Mosaic)."""
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    with pytest.MonkeyPatch.context() as mp:
        for mod in (fa, pa, qm, kda, mg):
            mp.setattr(mod, "_interpret", lambda: False)
        yield topo.devices


def compile_for(sharding, fn, *shapes):
    """Trace ``fn`` on ShapeDtypeStructs placed by ``sharding`` and run the
    TPU compiler; returns the compiled program's text."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).trace(*args).lower().compile().as_text()


def fwd_bwd(attn):
    def f(q, k, v):
        out, vjp = jax.vjp(attn, q, k, v)
        return (out,) + vjp(out)
    return f


def bf16(*shape):
    return shape, jnp.bfloat16


def kernel_calls(text):
    """Instruction names of the Mosaic kernels in a compiled program."""
    return re.findall(r"^\s*%(\S+) = .*custom_call_target=\"tpu_custom_call\"",
                      text, re.M)


class TestFlashAttention:
    def test_gpt2_train_shape_forward_and_backward(self, v5e):
        """One kernel each, fwd, dq and dk/dv, in the row-block kernels the
        causal shape takes: the benchmark's roofline counts steps as
        calls / 3 a layer and finds the kernels by these names."""
        B, S, H, Dh = GPT2
        assert fa._pick_rows(jnp.zeros((B * H, S, Dh), jnp.bfloat16))
        text = compile_for(
            SingleDeviceSharding(v5e[0]),
            fwd_bwd(functools.partial(fa.flash_attention_bshd, causal=True)),
            bf16(*GPT2), bf16(*GPT2), bf16(*GPT2))
        calls = kernel_calls(text)
        assert len(calls) == 3, calls
        for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
            assert sum(re.search(name + r"[._]", c) is not None
                       for c in calls) == 1, (name, calls)

    @pytest.mark.parametrize("B,S,H,Dh,dtype", [
        (4, 2048, 16, 128, jnp.bfloat16),
        (16, 1024, 12, 64, jnp.float32),
    ], ids=["d128-s2048", "gpt2-f32"])
    def test_row_blocks_compile_where_picked(self, v5e, B, S, H, Dh, dtype):
        """Off GPT-2's shape, what the VMEM estimate admits compiles too: a
        wider head over longer rows, and float32 operands."""
        assert fa._pick_rows(jnp.zeros((B * H, S, Dh), dtype))
        shape = ((B, S, H, Dh), dtype)
        text = compile_for(
            SingleDeviceSharding(v5e[0]),
            fwd_bwd(functools.partial(fa.flash_attention_bshd, causal=True)),
            shape, shape, shape)
        assert len(kernel_calls(text)) == 3

    def test_llama_gqa_shape(self, v5e):
        """The one-chip side of chip_smoke's dp=2 x mp=2 parity step, and
        the one-chip Llama training shape: GQA keeps one head a step."""
        for S in (128, 1024):
            text = compile_for(
                SingleDeviceSharding(v5e[0]),
                fwd_bwd(functools.partial(fa.flash_attention_bshd,
                                          causal=True)),
                bf16(4, S, NH, D), bf16(4, S, KVH, D), bf16(4, S, KVH, D))
            assert len(kernel_calls(text)) == 3

    def test_split_operands_are_refused(self, v5e):
        """XLA does not partition a Mosaic kernel, which is why the dispatch
        predicate says no under a mesh (tests/test_chip_smoke.py holds the
        predicate to that).  If this stops raising, the predicate can go."""
        mesh = Mesh(np.array(v5e).reshape(2, 2), ("dp", "mp"))
        with pytest.raises(Exception, match="cannot be automatically "
                                            "partitioned"):
            compile_for(
                NamedSharding(mesh, P("dp", None, "mp", None)),
                functools.partial(fa.flash_attention_bshd, causal=True),
                bf16(4, 128, NH, D), bf16(4, 128, KVH, D),
                bf16(4, 128, KVH, D))


class TestPagedAttention:
    """The four entry points of the one walk (PERF.md section 6, PR 29), at
    the decode and the prefill row counts, on the serving table and on one
    four times as long: the table is prefetched whole into scalar memory,
    and the walk's trip count, not the grid, follows the context."""

    @staticmethod
    def shapes(rows, q_shape, int8, max_len):
        n_pages = SLOTS * max_len // PAGE + 1
        pages = ((n_pages, PAGE, KVH, D), jnp.int8 if int8 else jnp.bfloat16)
        return [bf16(*q_shape), pages, pages,
                ((rows, max_len // PAGE), jnp.int32), ((rows,), jnp.int32)] + (
            [((n_pages, PAGE, KVH), jnp.float32)] * 2 if int8 else [])

    @staticmethod
    def call(kernel):
        def f(q, kp, vp, tables, ctx, *scales):
            kw = dict(zip(("k_scales", "v_scales"), scales))
            return kernel(q, kp, vp, tables, ctx, **kw)
        return f

    @pytest.mark.parametrize("max_len", [MAX_LEN, 4 * MAX_LEN],
                             ids=["table2048", "table8192"])
    @pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
    @pytest.mark.parametrize("rows", [SLOTS, CHUNK], ids=["decode", "prefill"])
    def test_single_query(self, v5e, rows, int8, max_len):
        text = compile_for(SingleDeviceSharding(v5e[0]),
                           self.call(pa.paged_attention),
                           *self.shapes(rows, (rows, NH, D), int8, max_len))
        assert "tpu_custom_call" in text

    @pytest.mark.parametrize("max_len", [MAX_LEN, 4 * MAX_LEN],
                             ids=["table2048", "table8192"])
    @pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
    def test_multi_query(self, v5e, int8, max_len):
        text = compile_for(SingleDeviceSharding(v5e[0]),
                           self.call(pa.paged_attention_multiquery),
                           *self.shapes(SLOTS, (SLOTS, 4, NH, D), int8,
                                        max_len))
        assert "tpu_custom_call" in text

    @pytest.mark.parametrize("kernel,q_shape,kvh,max_len,int8,pages", [
        (pa.paged_attention, (64, 8, D), 2, 8192, False, 32),
        (pa.paged_attention_blocks, (64, 4, 32, D), 4, 3072, False, 16),
        (pa.paged_attention, (64, 8, D), 2, 8192, True, 64),
    ], ids=["decode_kvh2_table8192", "blocks_kvh4_table3072",
            "decode_kvh2_int8_table8192"])
    def test_grown_trips(self, v5e, kernel, q_shape, kvh, max_len, int8,
                         pages):
        """Fewer KV heads take more pages a trip (PERF.md section 6):
        ZAYA's decode rows (8 q heads over 2 KV heads) walk 32 pages a
        trip of 8,192-token tables, SDAR's block call (4 positions x 32
        heads over 4) 16 of 3,072-token ones; both must compile. int8 pages
        at 2 KV heads too: a page is copied as dense ``[page * KVH, D]``
        rows, where a ``[page, 2, D]`` slice of the pool was refused."""
        rows, score_rows = q_shape[0], int(np.prod(q_shape[1:-1]))
        dt = jnp.int8 if int8 else jnp.bfloat16
        assert pa._pages_per_block(PAGE, kvh, D, dt.dtype.itemsize,
                                   score_rows, max_len // PAGE) == pages
        n_pages = rows * max_len // PAGE + 1
        kv = ((n_pages, PAGE, kvh, D), dt)
        text = compile_for(
            SingleDeviceSharding(v5e[0]), self.call(kernel), bf16(*q_shape),
            kv, kv, ((rows, max_len // PAGE), jnp.int32), ((rows,), jnp.int32),
            *([((n_pages, PAGE, kvh), jnp.float32)] * 2 if int8 else []))
        assert "tpu_custom_call" in text


class TestQuantMatmul:
    @pytest.mark.parametrize("int4", [False, True], ids=["int8", "int4"])
    def test_ffn_width(self, v5e, int4):
        """A weight format ``supported()`` accepts must lower: int4 once
        reached ``arith.shli`` on i8 vectors, which Mosaic does not
        legalize, while ``supported()`` still said yes."""
        if not qm.supported(SLOTS, HIDDEN, FFN, int4=int4):
            return      # reported unsupported: weight_only_linear uses XLA
        text = compile_for(
            SingleDeviceSharding(v5e[0]),
            lambda x, w, s: qm.quant_matmul(x, w, s, int4=int4),
            bf16(SLOTS, HIDDEN),
            ((HIDDEN // 2 if int4 else HIDDEN, FFN), jnp.int8),
            ((FFN,), jnp.float32))
        assert "tpu_custom_call" in text


class TestServingProgramsCarryThePool:
    """The engine's whole serving programs on the v5e's compiler, at the
    attention widths above and a pool far larger than anything else they
    hold: the pools ride the layer loop and are updated in place.  A pool
    that is scanned over instead is copied whole once a dispatch and sliced
    out and written back once a layer (PERF.md section 6, PR 26);
    tests/test_serving.py holds the CPU's compiler to the same."""
    L, N_PAGES, FFN, VOCAB, KV, SLOTS = 4, 513, 1024, 1024, 4, SLOTS

    def runner(self, int8):
        """A ``ModelRunner`` that is shapes alone: there is no device here
        to hold a weight, so it is not built from a model."""
        import types
        from paddle_tpu.inference.engine.runner import ModelRunner
        from paddle_tpu.models.llama import serving_plan
        r = ModelRunner.__new__(ModelRunner)
        r.cfg = types.SimpleNamespace(
            rms_norm_eps=1e-5, rope_theta=1e6, num_attention_heads=NH,
            num_key_value_heads=KVH, hidden_size=HIDDEN,
            num_hidden_layers=self.L)
        r.plan = serving_plan(r.cfg, None)
        r.mesh = None
        r.max_batch, r.page, r.chunk = self.SLOTS, PAGE, CHUNK
        r.n_pages, r.trash_page = self.N_PAGES, self.N_PAGES - 1
        r.use_kernel, r.kv_quant = True, int8
        return r

    def arguments(self, kind, int8, sharding):
        L, F, V, B = self.L, self.FFN, self.VOCAB, self.SLOTS
        i32, f32 = jnp.int32, jnp.float32

        def sds(shape, dt=jnp.bfloat16):
            return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
        W = {"embed": sds((V, HIDDEN)), "norm": sds((HIDDEN,)),
             "head": sds((HIDDEN, V)),
             "wq": sds((L, NH * D, HIDDEN)), "wk": sds((L, KVH * D, HIDDEN)),
             "wv": sds((L, KVH * D, HIDDEN)), "wo": sds((L, NH * D, HIDDEN)),
             "ln1": sds((L, HIDDEN)), "ln2": sds((L, HIDDEN)),
             "wg": sds((L, HIDDEN, F)), "wu": sds((L, HIDDEN, F)),
             "wd": sds((L, F, HIDDEN))}
        pages = sds((L, self.N_PAGES, PAGE, KVH, D),
                    jnp.int8 if int8 else jnp.bfloat16)
        cache = (pages, pages)
        if int8:
            cache += (sds((L, self.N_PAGES, PAGE, KVH), f32),) * 2
        table = MAX_LEN // PAGE
        if kind == "prefill":
            sampling = [sds((), dt) for dt in (i32, f32, f32, i32, i32)]
            return W, cache, [sds((CHUNK,), i32), sds((), i32),
                              sds((table,), i32), sds((), i32)] + sampling
        sampling = [sds((B,), dt) for dt in (i32, f32, f32, i32, i32, i32)]
        tokens = sds((B, self.KV) if kind == "verify" else (B,), i32)
        # decode: the mask and the dispatch before's tokens (take, prev)
        ahead = [sds((B,), i32)] * 2 if kind == "decode" else []
        return W, cache, [tokens, sds((B,), i32), sds((B, table), i32),
                          sds((B,), i32)] + sampling + ahead

    @pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
    @pytest.mark.parametrize("kind", ["decode", "prefill", "verify"])
    def test_no_pool_among_the_temporaries(self, v5e, kind, int8):
        import re
        r = self.runner(int8)
        prog = {"decode": lambda: r._build_decode(1),
                "prefill": r._build_prefill,
                "verify": lambda: r._build_verify(self.KV)}[kind]()
        W, cache, rest = self.arguments(kind, int8,
                                        SingleDeviceSharding(v5e[0]))
        compiled = prog.lower(W, cache, *rest).compile()
        text = compiled.as_text()
        pool_bytes = int(np.prod(cache[0].shape)) * cache[0].dtype.itemsize
        assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes
        # the page pools; the scale pools of int8 pages are a sixteenth of
        # them and reach the kernel by the layer (runner._layer_fn says why)
        for a in cache[:2]:
            L, n = a.shape[:2]
            tail = ",".join(map(str, a.shape[2:]))
            whole = rf"\w+\[(?:{L},{n}|{L * n}),{tail}\]"
            one_layer = rf"\w+\[(?:1,)?{n},{tail}\]"
            assert not re.findall(rf"= {whole}(?:\{{[^}}]*\}})? copy\(", text)
            assert not re.findall(
                rf"= {one_layer}\S* (?:dynamic-slice|dynamic-update-slice)\(",
                text)
        # the kernel is on the path, under the name the benchmark's
        # paged_attention_roofline looks for (bench/metrics/)
        name = ("paged_attention_multiquery" if kind == "verify"
                else "paged_attention")
        assert re.search(rf"%{name}(\.\d+)? = .*custom-call\(", text)
        assert "tpu_custom_call" in text

    # the Mistral cell's (bench/configs/mistral-7b-v0.3.json): 12 layers,
    # FFN and vocabulary as published, 16 slots x 2,048 tokens in pages of 16
    MISTRAL = {"L": 12, "N_PAGES": 16 * MAX_LEN // PAGE + 1, "FFN": 14336,
               "VOCAB": 32768, "SLOTS": 16}
    # what each program held beside its arguments at those widths when the
    # stacked wq, wk and wv were still [L, in, out] (PR 35's tree, this
    # libtpu): bf16 pages
    TEMP_BYTES = {"decode": 927_744, "prefill": 709_632, "verify": 968_192}

    @pytest.mark.parametrize("kind", ["decode", "prefill", "verify"])
    def test_the_projections_are_read_where_they_lie(self, v5e, kind):
        """No layer's q, k or v projection (nor any other weight of its
        size) is copied or transposed out of its stack: stored [L, in, out],
        each layer's slice was re-laid with the contraction axis minor once a
        step (decode ``copy.71/.72/.74``, prefill ``copy.55/.56/.58``,
        PERF.md section 5), and the temporaries do not grow."""
        import math
        vars(self).update(self.MISTRAL)
        r = self.runner(False)
        prog = {"decode": lambda: r._build_decode(1),
                "prefill": r._build_prefill,
                "verify": lambda: r._build_verify(self.KV)}[kind]()
        W, cache, rest = self.arguments(kind, False,
                                        SingleDeviceSharding(v5e[0]))
        compiled = prog.lower(W, cache, *rest).compile()
        # a layer's wq, and its wk / wv, in any orientation or split
        layer = {math.prod(W[k].shape[1:]) for k in ("wq", "wk", "wv")}
        made = re.findall(r"%([\w.\-]+) = bf16\[([\d,]*)\]\{([\d,]*)"
                          r"[^}]*\} ([\w\-]+)\(", compiled.as_text())
        re_laid = []
        for name, dims, layout, op in made:
            if math.prod(int(d) for d in dims.split(",") if d) in layer:
                minor = [int(d) for d in layout.split(",")]
                if (op in ("copy", "copy-start", "transpose")
                        or minor != sorted(minor, reverse=True)):
                    re_laid.append(f"{name} = [{dims}]{{{layout}}} {op}")
        assert not re_laid, re_laid
        assert (compiled.memory_analysis().temp_size_in_bytes
                <= self.TEMP_BYTES[kind])

    @pytest.mark.parametrize("kind", ["decode", "prefill", "verify"])
    def test_a_greedy_batch_takes_a_real_conditional(self, v5e, kind):
        """The sampler's batch-level branch survives the TPU compiler as a
        ``conditional`` (not a select over both branches), the filter's
        vocabulary-wide operations are all inside its sampled branch, and
        its arg-max branch holds none of them (PERF.md section 6, PR 31)."""
        import re
        r = self.runner(False)
        prog = {"decode": lambda: r._build_decode(1),
                "prefill": r._build_prefill,
                "verify": lambda: r._build_verify(self.KV)}[kind]()
        W, cache, rest = self.arguments(kind, False,
                                        SingleDeviceSharding(v5e[0]))
        text = prog.lower(W, cache, *rest).compile().as_text()
        bodies, name = {}, None            # computation -> its lines
        for line in text.splitlines():
            head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{$", line)
            if head:
                name = head.group(1)
                bodies[name] = []
            elif name is not None:
                bodies[name].append(line)

        def reach(comp, seen):
            """``comp`` and every computation it calls, lines joined."""
            if comp in seen or comp not in bodies:
                return ""
            seen.add(comp)
            own = "\n".join(bodies[comp])
            called = re.findall(
                r"(?:calls|to_apply|body|condition)=%([\w.\-]+)", own)
            for group in re.findall(r"branch_computations=\{([^}]*)\}", own):
                called += re.findall(r"%([\w.\-]+)", group)
            return own + "".join("\n" + reach(c, seen) for c in called)

        conds = re.findall(
            r" conditional\(.*branch_computations=\{%([\w.\-]+), "
            r"%([\w.\-]+)\}", text)
        assert len(conds) == 1, conds
        sampled, arg_max = (reach(c, set()) for c in conds[0])   # 0: False
        filter_ops = r" (sort|gather|reduce-window|rng[\w\-]*|exponential)\("
        assert not re.search(filter_ops, arg_max)
        assert " sort(" in sampled and " reduce-window(" in sampled
        # nothing of the filter is hoisted out of the branch
        assert text.count(" sort(") == sampled.count(" sort(") >= 1



class TestSolarOpen2Programs:
    """The new cell's kernels and its two whole programs on the v5e's
    compiler at the cell's own shapes (bench/configs/solar-open2-250b.json:
    published widths, one period of 4 layers, 40 of 320 experts, 24,576
    rows of the vocabulary, 64 slots x 3,072 tokens, chunks of 128): what
    Mosaic refuses shows here first, and so does what the programs hold
    beside their arguments."""
    B, MAX_LEN, CHUNK, VOCAB, HELD = 64, 3072, 128, 24576, 40

    def test_kda_step_at_64_rows(self, v5e):
        f32 = jnp.float32
        rows, heads, d = self.B, 64, 128
        text = compile_for(
            SingleDeviceSharding(v5e[0]), kda.kda_step,
            ((3 * (rows + 1), heads, d, d), f32), ((rows,), jnp.int32),
            ((rows, heads, d), f32), ((rows, heads, d), f32),
            ((rows, heads, d), f32), ((rows, heads, d), f32),
            ((rows, heads), f32))
        assert re.search(r"%kda_step(\.\d+)? = .*custom-call\(", text)

    @pytest.mark.parametrize("rows", [64 * 8, 128 * 8], ids=["decode", "chunk"])
    @pytest.mark.parametrize("k,n", [(4096, 1280), (1280, 4096)],
                             ids=["up", "down"])
    def test_moe_gmm_over_a_stack_of_layers(self, v5e, rows, k, n):
        text = compile_for(
            SingleDeviceSharding(v5e[0]),
            lambda x, w, gs: mg.moe_gmm(x, w, gs, jnp.float32),
            bf16(rows, k), bf16(3 * self.HELD, k, n),
            ((3 * self.HELD,), jnp.int32))
        assert re.search(r"%moe_gmm(\.\d+)? = .*custom-call\(", text)

    def runner_and_arguments(self, sharding):
        from paddle_tpu.inference.engine.runner import _COUNT_ROWS, ModelRunner
        from paddle_tpu.models import solar_open2 as so
        cfg = so.SolarOpen2Config(vocab_size=self.VOCAB, num_hidden_layers=4,
                                  experts_held=self.HELD)
        n_pages = self.B * self.MAX_LEN // PAGE + 1
        r = ModelRunner.__new__(ModelRunner)
        r.cfg, r.mesh = cfg, None
        r.max_batch, r.page, r.chunk = self.B, PAGE, self.CHUNK
        r.n_pages, r.trash_page = n_pages, n_pages - 1
        r.use_kernel, r.kv_quant = True, False
        r.plan = plan = so.serving_plan(cfg, None, kernels=True)

        def sds(shape, dt=jnp.bfloat16):
            return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
        H = cfg.hidden_size
        W = {"embed": sds((self.VOCAB, H)), "norm": sds((H,)),
             "head": sds((H, self.VOCAB))}
        for kind, n in plan.period:
            for name, (shape, _) in so.layer_leaves(cfg, kind).items():
                W[f"{kind}.{name}"] = sds((cfg.periods, n) + tuple(shape))
        pages = sds((1, n_pages, PAGE, plan.kvh, plan.D))
        state = sds((3, self.B + 1, 64, 128, 128), jnp.float32)
        cache = (pages, pages, state,
                 sds((3, self.B + 1, 3, cfg.conv_channels)),
                 sds((len(_COUNT_ROWS), plan.counts), jnp.int32))
        i32, f32 = jnp.int32, jnp.float32
        table = self.MAX_LEN // PAGE
        decode = [sds((self.B,), i32), sds((self.B,), i32),
                  sds((self.B, table), i32), sds((self.B,), i32)] + [
            sds((self.B,), dt) for dt in (i32, f32, f32, i32, i32, i32,
                                          i32, i32)]        # .., take, prev
        prefill = [sds((self.CHUNK,), i32), sds((), i32), sds((table,), i32),
                   sds((), i32)] + [
            sds((), dt) for dt in (i32, f32, f32, i32, i32)] + [sds((), i32)]
        return r, W, cache, {"decode": decode, "prefill": prefill}

    @pytest.mark.parametrize("kind", ["decode", "prefill"])
    def test_whole_program_fits_and_copies_no_pool(self, v5e, kind):
        r, W, cache, rest = self.runner_and_arguments(
            SingleDeviceSharding(v5e[0]))
        prog = r._build_decode(1) if kind == "decode" else r._build_prefill()
        compiled = prog.lower(W, cache, *rest[kind]).compile()
        mem = compiled.memory_analysis()
        held = mem.argument_size_in_bytes + mem.temp_size_in_bytes
        print(f"solar {kind}: arguments {mem.argument_size_in_bytes / 2**30:.2f}"
              f" GiB, temporaries {mem.temp_size_in_bytes / 2**30:.3f} GiB")
        assert held < 11 * 2**30
        state_bytes = int(np.prod(cache[2].shape)) * 4
        assert mem.temp_size_in_bytes < state_bytes // 2
        text = compiled.as_text()
        # neither the state pool nor a layer's experts is copied or sliced
        # out in front of its kernel
        for shape in (r"f32\[(?:3,65|195),64,128,128\]",
                      r"bf16\[(?:1,)*40,(?:4096,1280|1280,4096)\]"):
            assert not re.findall(
                rf"= {shape}\S* (?:copy|dynamic-slice|gather)\(", text), shape
        for name in ("moe_gmm", "paged_attention") + (
                ("kda_step",) if kind == "decode" else ()):
            assert re.search(rf"%{name}(\.\d+)? = .*custom-call\(", text), name


class TestSDARPrograms:
    """The block-generation cell's two whole programs on the v5e's compiler
    at the cell's own shapes (bench/configs/sdar-30b-a3b-chat.json:
    published widths, 6 layers, all 128 experts, the whole vocabulary, 64
    slots x 3,072 tokens, chunks of 128, blocks of 4 in 4 steps): the
    multi-query paged attention with a block's horizon and ``moe_gmm`` over
    the stack of six layers' experts, inside a scan over denoising steps."""
    B, MAX_LEN, CHUNK = 64, 3072, 128

    def runner_and_arguments(self, sharding, remasking="sequential"):
        from paddle_tpu.inference.engine.runner import (
            _COUNT_ROWS, BLOCK_COUNTS, ModelRunner)
        from paddle_tpu.models import sdar
        cfg = sdar.SDARConfig(num_hidden_layers=6, remasking=remasking)
        n_pages = self.B * self.MAX_LEN // PAGE + 1
        r = ModelRunner.__new__(ModelRunner)
        r.cfg, r.mesh = cfg, None
        r.max_batch, r.page, r.chunk = self.B, PAGE, self.CHUNK
        r.n_pages, r.trash_page = n_pages, n_pages - 1
        r.use_kernel, r.kv_quant = True, False
        r.plan = plan = sdar.serving_plan(cfg, None, kernels=True)
        r._block_counts_at = 2

        def sds(shape, dt=jnp.bfloat16):
            return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
        H, V, L = cfg.hidden_size, cfg.vocab_size, cfg.num_hidden_layers
        W = {"embed": sds((V, H)), "norm": sds((H,)), "head": sds((H, V))}
        for name, (shape, _) in sdar.layer_leaves(cfg).items():
            W[name] = sds((L,) + tuple(shape))
        pages = sds((L, n_pages, PAGE, plan.kvh, plan.D))
        cache = (pages, pages, sds((len(BLOCK_COUNTS),), jnp.int32),
                 sds((len(_COUNT_ROWS), plan.counts), jnp.int32))
        i32, f32 = jnp.int32, jnp.float32
        table = self.MAX_LEN // PAGE
        decode = [sds((self.B, plan.block), i32), sds((self.B,), i32),
                  sds((self.B, table), i32), sds((self.B,), i32)] + [
            sds((self.B,), dt) for dt in (i32, f32, f32, i32, i32, i32,
                                          i32, i32)]        # .., take, prev
        prefill = [sds((self.CHUNK,), i32), sds((), i32), sds((table,), i32),
                   sds((), i32)] + [
            sds((), dt) for dt in (i32, f32, f32, i32, i32)]
        return r, W, cache, {"decode": decode, "prefill": prefill}

    @pytest.mark.parametrize("kind,remasking", [
        ("decode", "sequential"), ("decode", "low_confidence_dynamic"),
        ("prefill", "sequential")])
    def test_whole_program_fits_and_copies_no_pool(self, v5e, kind, remasking):
        import time
        r, W, cache, rest = self.runner_and_arguments(
            SingleDeviceSharding(v5e[0]), remasking)
        prog = (r._build_decode(r.plan.block) if kind == "decode"
                else r._build_prefill())
        t0 = time.perf_counter()
        compiled = prog.lower(W, cache, *rest[kind]).compile()
        seconds = time.perf_counter() - t0
        mem = compiled.memory_analysis()
        print(f"sdar {kind} ({remasking}): arguments "
              f"{mem.argument_size_in_bytes / 2**30:.2f} GiB, temporaries "
              f"{mem.temp_size_in_bytes / 2**30:.3f} GiB, compiled in "
              f"{seconds:.0f} s")
        assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                < 13 * 2**30)
        text = compiled.as_text()
        # neither a page pool nor the layers' experts is copied or sliced
        # out in front of its kernel
        for shape in (r"bf16\[(?:6,)?\d+,16,4,128\]",
                      r"bf16\[(?:6,128|768),(?:2048,768|768,2048)\]"):
            assert not re.findall(
                rf"= {shape}\S* (?:copy|dynamic-slice|gather)\(", text), shape
        for name in ("moe_gmm", "paged_attention"):
            assert re.search(rf"%{name}(\.\d+)? = .*custom-call\(", text), name
        if kind == "decode":
            # the loop over denoising steps and the layers' scans are whiles
            assert text.count(" while(") >= 2


class TestZAYAPrograms:
    """The long-input cell's two whole programs on the v5e's compiler at the
    cell's own shapes (bench/configs/zaya1-8b.json: published widths, 10
    layers, all 16 experts, the whole vocabulary, 64 slots x 8,192 tokens,
    chunks of 256): the paged attention over tables 8,192 tokens long,
    ``moe_gmm`` over the stack of the layers' experts, the tails pool and
    the router state beside the residual, the head read off the embedding."""
    B, MAX_LEN, CHUNK, L = 64, 8192, 256, 10

    def runner_and_arguments(self, sharding):
        from paddle_tpu.inference.engine.runner import _COUNT_ROWS, ModelRunner
        from paddle_tpu.models import zaya
        cfg = zaya.ZAYAConfig(num_hidden_layers=self.L)
        n_pages = self.B * self.MAX_LEN // PAGE + 1
        r = ModelRunner.__new__(ModelRunner)
        r.cfg, r.mesh = cfg, None
        r.max_batch, r.page, r.chunk = self.B, PAGE, self.CHUNK
        r.n_pages, r.trash_page = n_pages, n_pages - 1
        r.use_kernel, r.kv_quant = True, False
        r.chunk_attention = "sequence"          # the chunk's pages read once
        r.plan = plan = zaya.serving_plan(cfg, None, kernels=True)

        def sds(shape, dt=jnp.bfloat16):
            return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
        W = {"embed": sds((cfg.vocab_size, cfg.hidden_size)),
             "norm": sds((cfg.hidden_size,))}
        for name, (shape, _) in zaya.layer_leaves(cfg).items():
            W[name] = sds((self.L,) + tuple(shape))
        pages = sds((self.L, n_pages, PAGE, plan.kvh, plan.D))
        cache = (pages, pages,
                 sds((self.L, self.B + 1) + plan.kinds["cca"].tail),
                 sds((len(_COUNT_ROWS), plan.counts), jnp.int32))
        i32, f32 = jnp.int32, jnp.float32
        table = self.MAX_LEN // PAGE
        decode = [sds((self.B,), i32), sds((self.B,), i32),
                  sds((self.B, table), i32), sds((self.B,), i32)] + [
            sds((self.B,), dt) for dt in (i32, f32, f32, i32, i32, i32,
                                          i32, i32)]        # .., take, prev
        prefill = [sds((self.CHUNK,), i32), sds((), i32), sds((table,), i32),
                   sds((), i32)] + [
            sds((), dt) for dt in (i32, f32, f32, i32, i32)] + [sds((), i32)]
        return r, W, cache, {"decode": decode, "prefill": prefill}

    @pytest.mark.parametrize("kind", ["decode", "prefill"])
    def test_whole_program_fits_and_copies_nothing_big(self, v5e, kind):
        r, W, cache, rest = self.runner_and_arguments(
            SingleDeviceSharding(v5e[0]))
        prog = r._build_decode(1) if kind == "decode" else r._build_prefill()
        compiled = prog.lower(W, cache, *rest[kind]).compile()
        mem = compiled.memory_analysis()
        print(f"zaya {kind}: arguments {mem.argument_size_in_bytes / 2**30:.3f}"
              f" GiB, temporaries {mem.temp_size_in_bytes / 2**30:.4f} GiB")
        # weights 5.23 GB and two page pools of 2.68 GB, 10.6 GB in all
        assert mem.argument_size_in_bytes < 10.2 * 2**30
        embed_bytes = int(np.prod(W["embed"].shape)) * 2
        # nothing of the embedding's size (a head of its own, or the
        # embedding upcast: 2 GB in float32) and no pool is made beside the
        # arguments; what is there is the decode step's logits and the
        # sampler's sort over them, 64 x 262,272 a few times over
        assert mem.temp_size_in_bytes < embed_bytes // 2
        text = compiled.as_text()
        for shape in (r"bf16\[(?:10,)?\d+,16,2,128\]",
                      r"bf16\[(?:10,16|160|16),(?:2048,2048)\]",
                      r"(?:bf16|f32)\[(?:262272,2048|2048,262272)\]"):
            assert not re.findall(
                rf"= {shape}\S* (?:copy|dynamic-slice|gather|transpose)\(",
                text), shape
        for name in ("moe_gmm", "paged_attention"):
            assert re.search(rf"%{name}(\.\d+)? = .*custom-call\(", text), name
