"""Every Pallas kernel must get through the real XLA:TPU and Mosaic compilers
at the shapes chip_smoke.py runs — checked here, with no chip, by compiling
ahead of time for a v5e (the installed libtpu carries the compilers).

Compiling is not being right (chip_smoke.py compares each kernel with its
reference on the device), but a kernel Mosaic refuses never gets that far.
The file name sorts first on purpose: tier-1 runs alphabetically into a
timeout, and this is the one CPU-side guard of the on-chip path.
"""
import functools

import numpy as np
import pytest

pytest.importorskip("libtpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,  # noqa: E402
                          SingleDeviceSharding)

from paddle_tpu.ops.pallas import flash_attention as fa  # noqa: E402
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
        for mod in (fa, pa, qm):
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


class TestFlashAttention:
    def test_gpt2_train_shape_forward_and_backward(self, v5e):
        text = compile_for(
            SingleDeviceSharding(v5e[0]),
            fwd_bwd(functools.partial(fa.flash_attention_bshd, causal=True)),
            bf16(*GPT2), bf16(*GPT2), bf16(*GPT2))
        assert text.count("tpu_custom_call") >= 3       # fwd, dq, dk/dv

    def test_layout_direct_variant(self, v5e):
        B, S, H, Dh = GPT2
        bq, bk, hb = fa._bshd_config(B, S, S, H, Dh, jnp.bfloat16)
        compile_for(
            SingleDeviceSharding(v5e[0]),
            fwd_bwd(lambda q, k, v: fa._flash_bshd(
                q, k, v, Dh ** -0.5, True, bq, bk, hb)),
            bf16(*GPT2), bf16(*GPT2), bf16(*GPT2))

    def test_llama_gqa_shape(self, v5e):
        """The one-chip side of chip_smoke's dp=2 x mp=2 parity step."""
        compile_for(
            SingleDeviceSharding(v5e[0]),
            fwd_bwd(functools.partial(fa.flash_attention_bshd, causal=True)),
            bf16(4, 128, NH, D), bf16(4, 128, KVH, D), bf16(4, 128, KVH, D))

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
    N_PAGES = SLOTS * MAX_LEN // PAGE + 1
    TABLE = MAX_LEN // PAGE

    def shapes(self, rows, q_shape, int8):
        pages = ((self.N_PAGES, PAGE, KVH, D),
                 jnp.int8 if int8 else jnp.bfloat16)
        return [bf16(*q_shape), pages, pages,
                ((rows, self.TABLE), jnp.int32), ((rows,), jnp.int32)] + (
            [((self.N_PAGES, PAGE, KVH), jnp.float32)] * 2 if int8 else [])

    @staticmethod
    def call(kernel):
        def f(q, kp, vp, tables, ctx, *scales):
            kw = dict(zip(("k_scales", "v_scales"), scales))
            return kernel(q, kp, vp, tables, ctx, **kw)
        return f

    @pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
    @pytest.mark.parametrize("rows", [SLOTS, CHUNK], ids=["decode", "prefill"])
    def test_single_query(self, v5e, rows, int8):
        text = compile_for(SingleDeviceSharding(v5e[0]),
                           self.call(pa.paged_attention),
                           *self.shapes(rows, (rows, NH, D), int8))
        assert "tpu_custom_call" in text

    @pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
    def test_multi_query(self, v5e, int8):
        text = compile_for(SingleDeviceSharding(v5e[0]),
                           self.call(pa.paged_attention_multiquery),
                           *self.shapes(SLOTS, (SLOTS, 4, NH, D), int8))
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
