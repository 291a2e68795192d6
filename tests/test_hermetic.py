"""Child-process environments (core/hermetic.py): a CPU-bound child never
opens the chip (reference pattern: the CPU-simulation contract of
test/legacy_test/test_dist_base.py:957), and sibling workers of one host
each get a chip of their own."""
import os
import subprocess
import sys

from paddle_tpu.core.hermetic import cpu_child_env, one_chip_env


class TestChildEnvs:
    def test_cpu_child_forces_cpu_and_keeps_the_rest(self):
        env = cpu_child_env({"PATH": "/bin", "JAX_PLATFORMS": "tpu"})
        assert env == {"PATH": "/bin", "JAX_PLATFORMS": "cpu"}

    def test_extra_overrides_win(self):
        assert cpu_child_env({}, PADDLE_TRAINER_ID="3")[
            "PADDLE_TRAINER_ID"] == "3"
        assert one_chip_env(0, {}, PADDLE_TRAINER_ID="3")[
            "PADDLE_TRAINER_ID"] == "3"

    def test_one_chip_env_pins_libtpu_to_that_chip(self):
        env = one_chip_env(2, {"PATH": "/bin"})
        assert env == {"PATH": "/bin", "TPU_VISIBLE_CHIPS": "2",
                       "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
                       "TPU_PROCESS_BOUNDS": "1,1,1"}


class TestSpawnPathsAreHermetic:
    def test_launch_worker_envs(self):
        from paddle_tpu.distributed.launch.main import _parse, _worker_env
        args = _parse(["--nproc_per_node=2", "x.py"])
        assert [_worker_env(args, i)["TPU_VISIBLE_CHIPS"] for i in (0, 1)] \
            == ["0", "1"]
        assert _worker_env(args, 1)["TPU_PROCESS_BOUNDS"] == "1,1,1"
        # a lone worker drives every local chip; a CPU worker opens none
        assert "TPU_VISIBLE_CHIPS" not in _worker_env(
            _parse(["--nproc_per_node=1", "x.py"]), 0)
        cpu = _worker_env(_parse(["--nproc_per_node=2", "--backend=cpu",
                                  "x.py"]), 1)
        assert cpu["JAX_PLATFORMS"] == "cpu" and "TPU_VISIBLE_CHIPS" not in cpu

    def test_child_through_cpu_child_env_runs_cpu(self):
        """Whatever platform the parent's environment names, a child
        launched through cpu_child_env comes up on the CPU."""
        r = subprocess.run(
            [sys.executable, "-c",
             "import jax; print('BACKEND', jax.default_backend())"],
            env=cpu_child_env({**os.environ, "JAX_PLATFORMS": "tpu"}),
            capture_output=True, text=True, timeout=120)
        assert "BACKEND cpu" in r.stdout, r.stderr[-2000:]

    def test_ps_server_child_is_hermetic(self, tmp_path):
        """start_server_process ships its child a CPU environment."""
        import socket
        import numpy as np
        from paddle_tpu.distributed.ps_sparse import (start_server_process,
                                                      SparsePsClient)
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        p = start_server_process(port, str(tmp_path), ready_timeout=60)
        client = SparsePsClient([f"127.0.0.1:{port}"])
        client.create_table("t", dim=4, capacity_rows_per_server=8,
                            lr=1.0, initializer="zeros")
        out = client.pull("t", np.array([1, 2]))
        assert out.shape == (2, 4)
        client.shutdown()
        p.wait(timeout=10)


class TestLaunch:
    def test_cpu_backend_runs_the_script(self, tmp_path):
        from paddle_tpu.distributed.launch.main import launch
        script = tmp_path / "t.py"
        script.write_text("print('ran')\n")
        rc = launch(["--nproc_per_node=1", "--backend=cpu",
                     f"--log_dir={tmp_path}/log", str(script)])
        assert rc == 0
        assert "ran" in (tmp_path / "log" / "workerlog.0").read_text()
