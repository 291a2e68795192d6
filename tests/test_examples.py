"""The examples/ scripts are the user's first contact — they must run."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES = sorted(f for f in os.listdir(os.path.join(ROOT, "examples"))
                  if f.endswith(".py"))
# full training/serving loops in a fresh interpreter (~15s each): slow tier;
# export_onnx stays in tier-1 as the fast end-to-end canary
_SLOW = {"serve_llama.py", "sharded_train.py", "train_gpt2.py"}


@pytest.mark.parametrize(
    "script",
    [pytest.param(s, marks=pytest.mark.slow) if s in _SLOW else s
     for s in EXAMPLES])
def test_example_runs(script):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
    r = subprocess.run([sys.executable, os.path.join("examples", script)],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=420)
    assert r.returncode == 0, f"{script}:\n{r.stderr[-2000:]}"
