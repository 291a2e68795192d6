"""Hybrid-parallel training on a device mesh (8 virtual CPU devices here;
the same code runs on a real TPU pod slice — GSPMD inserts the collectives).

Run:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python examples/sharded_train.py
"""
import os
import sys

# runnable from any cwd: the repo root (one level up) on sys.path
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.core.compile_cache import enable_compile_cache
import paddle_tpu.nn as nn
from paddle_tpu.distributed import Shard, shard_tensor
from paddle_tpu.distributed.fleet.topology import (
    CommunicateTopology, HybridCommunicateGroup,
    set_hybrid_communicate_group)
from paddle_tpu.models.llama import (LlamaConfig, LlamaForCausalLM,
                                     shard_llama)


def main(steps=3):
    enable_compile_cache()
    topo = CommunicateTopology(["dp", "pp", "sharding", "sep", "mp"],
                               [2, 1, 1, 1, 4])        # dp=2 x mp=4
    hcg = HybridCommunicateGroup(topo, rank=0)
    set_hybrid_communicate_group(hcg)
    mesh = hcg.get_mesh()

    paddle.seed(0)
    cfg = LlamaConfig.tiny(num_key_value_heads=4)
    model = LlamaForCausalLM(cfg)
    shard_llama(model, mesh, fsdp_axis="dp", mp_axis="mp")
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())

    def train_step(x, y):
        xs = shard_tensor(x, mesh, [Shard(0)])          # batch on dp
        _, loss = model(xs, labels=y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = paddle.jit.to_static(train_step)
    rng = np.random.RandomState(0)
    for i in range(steps):
        ids = rng.randint(0, cfg.vocab_size, (4, 33)).astype(np.int32)
        loss = step(paddle.to_tensor(ids[:, :-1]),
                    paddle.to_tensor(ids[:, 1:]))
        print(f"step {i}: loss {float(loss.numpy()):.4f} (dp=2 x mp=4 mesh)")


if __name__ == "__main__":
    main()
