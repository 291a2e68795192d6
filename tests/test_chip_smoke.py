"""The ground PR 21 took for the on-chip path, held on the CPU: the smoke
refuses to run without a TPU, nothing initializes a backend by being
imported, the compile cache is placed from outside or under the checkout,
asking for a TPU that is not there raises, and the rules that pick a Pallas
kernel pick it for whole operands only."""
import os
import subprocess
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def test_smoke_refuses_to_run_without_a_tpu():
    r = subprocess.run([sys.executable, SMOKE], cwd=ROOT,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"},
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    reason = [ln for ln in r.stderr.splitlines() if "chip_smoke" in ln]
    assert len(reason) == 1 and "no TPU" in reason[0], r.stderr[-2000:]
    assert '"ok"' not in r.stdout           # no result line


def test_smoke_sets_no_platform():
    with open(SMOKE) as f:
        src = f.read()
    assert "JAX_PLATFORMS" not in src and "jax_platforms" not in src


def test_parents_of_workers_initialize_no_backend(tmp_path):
    """A process that has initialized the backend owns the chip.  So the
    smoke, the launcher and the front door must be importable — and the
    launcher must size a job (``--nproc_per_node`` unset: chips counted from
    /dev, and an error where there are none) — without ever asking jax."""
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "import jax._src.xla_bridge as xb\n"
        "import chip_smoke, paddle_tpu\n"
        "import paddle_tpu.inference.frontend\n"
        "from paddle_tpu.distributed.launch.main import launch\n"
        "assert not xb._backends, list(xb._backends)\n"
        "print('NO_BACKEND')\n"
        "rc = launch(['--log_dir', %r, 'nothing.py'])\n"
        "assert not xb._backends, list(xb._backends)\n"
        "print('RC', rc)" % (ROOT, str(tmp_path / "log")))
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"},
                       capture_output=True, text=True, timeout=300)
    assert "NO_BACKEND" in r.stdout, r.stderr[-2000:]
    assert "RC 2" in r.stdout and "no TPU chip" in r.stderr, r.stderr[-2000:]


class TestCompileCache:
    @pytest.fixture
    def updates(self, monkeypatch):
        """What enable_compile_cache() asks jax.config to set."""
        import jax
        import paddle_tpu  # noqa: F401  (its own import-time config first)
        seen = []
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: seen.append((k, v)))
        return seen

    def test_placed_from_outside_sets_nothing(self, updates, monkeypatch,
                                              tmp_path):
        from paddle_tpu.core.compile_cache import enable_compile_cache
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert updates == []

    def test_default_is_under_the_checkout_whatever_the_cwd(
            self, updates, monkeypatch, tmp_path):
        from paddle_tpu.core.compile_cache import enable_compile_cache
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        monkeypatch.chdir(tmp_path)
        want = os.path.join(ROOT, ".jax_cache")
        assert enable_compile_cache() == want
        assert [v for _, v in updates] == [want]
        assert updates[0][0].endswith("_cache_dir")


def test_set_device_tpu_raises_where_there_is_none():
    import paddle_tpu as paddle
    before = paddle.get_device()
    for spec in ("tpu", "tpu:0", "gpu"):        # "gpu": a ported script
        with pytest.raises(RuntimeError, match="no such device"):
            paddle.set_device(spec)
    assert paddle.get_device() == before
    assert paddle.set_device("cpu").is_cpu_place()


class _TpuDevice:
    platform = "tpu"


class TestKernelRules:
    """Both rules with the platform stubbed to a TPU."""
    TPU = _TpuDevice()

    def test_paged_attention_runner_rule(self):
        import jax
        from jax.sharding import Mesh
        from paddle_tpu.inference.engine.runner import _kernel_applies
        devs = jax.devices()
        assert _kernel_applies(self.TPU, None)
        assert _kernel_applies(self.TPU, Mesh(np.array(devs[:1]), ("mp",)))
        assert not _kernel_applies(self.TPU, Mesh(np.array(devs[:4]),
                                                  ("mp",)))
        assert not _kernel_applies(devs[0], None)           # a CPU device

    def test_flash_attention_predicate(self):
        from paddle_tpu.distributed.fleet.topology import (
            CommunicateTopology, HybridCommunicateGroup,
            set_hybrid_communicate_group)
        from paddle_tpu.nn.functional.attention import _use_pallas
        q = types.SimpleNamespace(shape=(4, 128, 32, 128),
                                  devices=lambda: {self.TPU})
        assert _use_pallas(q, q)
        try:
            set_hybrid_communicate_group(HybridCommunicateGroup(
                CommunicateTopology(dims=[2, 1, 1, 1, 2]), rank=0))
            assert not _use_pallas(q, q)
        finally:
            set_hybrid_communicate_group(HybridCommunicateGroup(
                CommunicateTopology(dims=[1, 1, 1, 1, 1]), rank=0))


def test_autotune_reads_no_disk_cache_while_off(tmp_path, monkeypatch):
    import json

    import paddle_tpu as paddle
    from paddle_tpu.ops import autotune as at
    path = tmp_path / "autotune.json"
    path.write_text(json.dumps({"op|1": [7, 7]}))
    monkeypatch.setattr(at, "_DISK", str(path))
    monkeypatch.setattr(at, "_loaded", False)
    at.clear()
    try:
        assert at.lookup("op|1") is None            # off: not even read
        assert not at._loaded
        paddle.set_flags({"FLAGS_use_autotune": True})
        assert at.lookup("op|1") == (7, 7)
    finally:
        paddle.set_flags({"FLAGS_use_autotune": False})
        at.clear()


def test_autotune_raises_when_every_candidate_fails():
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.ops import autotune as at

    def build(cfg):
        def refused(x):
            raise RuntimeError(f"Mosaic failed to compile {cfg}")
        return refused
    paddle.set_flags({"FLAGS_use_autotune": True})
    try:
        with pytest.raises(RuntimeError, match="all 2 candidates failed"):
            at.tune(at.cache_key("op5", 1), ["a", "b"], build,
                    (jnp.ones(2),))
    finally:
        paddle.set_flags({"FLAGS_use_autotune": False})
        at.clear()
