"""Export a model to ONNX and verify it with the in-tree numpy runner.

No external onnx package needed: the exporter serializes the captured jaxpr
directly against the public onnx.proto schema, and `load_and_run` re-executes
the exported graph for verification.

Run:  JAX_PLATFORMS=cpu python examples/export_onnx.py
"""
import os
import sys

# runnable from any cwd: the repo root (one level up) on sys.path
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import tempfile

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.onnx import export, load_and_run


def main():
    paddle.seed(0)
    model = nn.Sequential(nn.Linear(16, 32), nn.GELU(), nn.Linear(32, 4))
    x = paddle.to_tensor(np.random.RandomState(0).rand(
        3, 16).astype(np.float32))
    with tempfile.TemporaryDirectory() as d:
        path = export(model, d + "/mlp", input_spec=[x])
        got = load_and_run(path, {"x0": x.numpy()})["y0"]
    ref = model(x).numpy()
    np.testing.assert_allclose(got, ref, atol=1e-4, rtol=1e-4)
    print(f"exported and verified: max|Δ| = {np.abs(got - ref).max():.2e}")


if __name__ == "__main__":
    main()
