"""Pretrain a (tiny) GPT-2 with the compiled train step.

The pattern scales to the real chip unchanged: `jit.scan_steps` fuses K
optimizer steps into one dispatch (one dispatch latency buys K updates).
Losses come back STACKED on the leading [K] axis and are read on the host
after the dispatch — scan_steps raises a permanent MissedCapture on any
in-step scalar event, so a `float(loss)` inside the step would silently
pin the whole example eager (stitched breaks are a `to_static` feature).

Run:  JAX_PLATFORMS=cpu python examples/train_gpt2.py
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
from paddle_tpu.models.gpt2 import GPT2Config, GPT2ForCausalLM


def main(steps=4, k=2, batch=2, seqlen=64):
    enable_compile_cache()
    paddle.seed(0)
    cfg = GPT2Config.tiny(hidden_dropout_prob=0.0,
                          attention_dropout_prob=0.0,
                          max_position_embeddings=seqlen)
    model = GPT2ForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=3e-4, weight_decay=0.01,
                                 parameters=model.parameters(),
                                 grad_clip=nn.ClipGradByGlobalNorm(1.0))
    losses = []

    def train_step(x, y):
        _, loss = model(x, labels=y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss                     # host read happens AFTER dispatch

    step = paddle.jit.scan_steps(train_step) if k > 1 \
        else paddle.jit.to_static(train_step)
    rng = np.random.RandomState(0)
    # one fixed batch, revisited every step: loss must fall as the model
    # memorizes it (fresh random ids each step would just bounce around)
    ids = rng.randint(0, cfg.vocab_size,
                      (k, batch, seqlen + 1)).astype(np.int32)
    x = paddle.to_tensor(ids[:, :, :-1] if k > 1 else ids[0, :, :-1])
    y = paddle.to_tensor(ids[:, :, 1:] if k > 1 else ids[0, :, 1:])
    for i in range(steps):
        loss = step(x, y)               # [k] stacked under scan_steps
        losses.extend(np.asarray(loss.numpy()).reshape(-1).tolist())
    print(f"losses (k={k} updates/dispatch): "
          f"{[round(v, 3) for v in losses]}")
    assert losses[-1] < losses[0]
    return losses


if __name__ == "__main__":
    main()
