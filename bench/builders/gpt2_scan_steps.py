"""Builds the training system under test from a configuration file: GPT-2
through ``GPT2ForCausalLM`` + ``paddle.optimizer.AdamW`` +
``paddle.jit.scan_steps``, the recipe ``chip_smoke.train_phase`` proved on
the chip, holding the benchmark's weights (``reference.init_weights``).

ONE object is built: the captured step with its state.  Set-up drives it,
puts its state back to the seed's, drives the compared steps through the
compiled program, and hands the same object to the window.
"""
from operator import attrgetter

import jax.numpy as jnp
import numpy as np

from bench.reference import gpt2 as yardstick   # norms over logical leaves

# reference leaf -> attribute path on a GPT2Block
_BLOCK = {
    "ln1_g": "ln1.weight", "ln1_b": "ln1.bias",
    "qkv_w": "attn.qkv.weight", "qkv_b": "attn.qkv.bias",
    "proj_w": "attn.proj.weight", "proj_b": "attn.proj.bias",
    "ln2_g": "ln2.weight", "ln2_b": "ln2.bias",
    "fc_w": "mlp.fc.weight", "fc_b": "mlp.fc.bias",
    "fc_proj_w": "mlp.proj.weight", "fc_proj_b": "mlp.proj.bias",
}


class TrainingSystem:
    def __init__(self, cfg, steps_per_dispatch):
        import paddle_tpu as paddle
        import paddle_tpu.nn as nn
        from paddle_tpu.models.gpt2 import GPT2Config, GPT2ForCausalLM
        r = cfg["recipe"]
        self.cfg, self.k = cfg, int(steps_per_dispatch)
        paddle.seed(0)
        gcfg = GPT2Config(
            vocab_size=cfg["vocab_size"], hidden_size=cfg["n_embd"],
            num_layers=cfg["n_layer"], num_heads=cfg["n_head"],
            max_position_embeddings=cfg["n_positions"],
            hidden_dropout_prob=cfg["resid_pdrop"],
            attention_dropout_prob=cfg["attn_pdrop"],
            layer_norm_epsilon=cfg["layer_norm_epsilon"],
            initializer_range=cfg["initializer_range"],
            loss_logits_dtype=r["loss_logits_dtype"],
            loss_chunk_size=r["loss_chunk_tokens"],
            loss_recompute=r["loss_recompute"])
        self.model = GPT2ForCausalLM(gcfg)
        self.model.to(dtype=r["dtype"])
        self.opt = paddle.optimizer.AdamW(
            learning_rate=r["learning_rate"], beta1=r["beta1"], beta2=r["beta2"],
            epsilon=r["epsilon"], weight_decay=r["weight_decay"],
            parameters=self.model.parameters(),
            grad_clip=nn.ClipGradByGlobalNorm(r["clip_global_norm"]))
        model, opt = self.model, self.opt

        def train_step(x, y):
            _, loss = model(x, labels=y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        self.fn_name = "train_step"
        self.step = paddle.jit.scan_steps(train_step)
        g = self.model.gpt2
        self.leaves = {"wte": g.wte.weight, "wpe": g.wpe.weight,
                       "lnf_g": g.ln_f.weight, "lnf_b": g.ln_f.bias}
        for i, block in enumerate(g.blocks):
            for name, path in _BLOCK.items():
                self.leaves[f"h{i}.{name}"] = attrgetter(path)(block)

    def dispatch(self, ids):
        """One call of the captured step on ``ids [K, B, S + 1]``: K
        optimizer steps.  Returns the K losses, still on the device."""
        import paddle_tpu as paddle
        return self.step(paddle.to_tensor(ids[:, :, :-1]),
                         paddle.to_tensor(ids[:, :, 1:]))._data

    def set_state(self, weights):
        """The seed's weights in, the optimizer's state back to nought:
        what a fresh object would hold after its first capture."""
        for name, p in self.leaves.items():
            w = weights[name]
            if tuple(p.shape) != tuple(w.shape):
                raise SystemExit(f"bench: weight {name} {w.shape} does not "
                                 f"fit the model's {tuple(p.shape)}")
            # a copy: the captured step donates its state's buffers
            p._data = jnp.array(w, p._data.dtype, copy=True)
        for name, store in self.opt._accumulators.items():
            for t in store.values():
                fill = jnp.ones if name.endswith("_pow") else jnp.zeros
                t._data = fill(t._data.shape, t._data.dtype)
        self.opt._global_step._data = jnp.zeros((), jnp.int32)

    def _moments(self):
        m = self.opt._accumulators["moment1"]
        return {n: m[id(p)]._data for n, p in self.leaves.items()}

    def moment_norms(self):
        return yardstick.leaf_norms(self._moments())

    def moments_on_host(self):
        """The optimizer's first moment, copied off the device: what the
        direction of the gradients is read from once the state is freed."""
        return {n: np.asarray(a) for n, a in self._moments().items()}

    def change_norms(self, weights):
        return yardstick.change_norms(
            {n: p._data for n, p in self.leaves.items()},
            {n: weights[n] for n in self.leaves})

    def jit_events(self):
        from paddle_tpu import observability as obs
        series = obs.snapshot(prefix="jit_events_total").get(
            "jit_events_total", {}).get("series", [])
        return {s["labels"]["event"]: int(s["value"]) for s in series
                if s["labels"]["fn"] == self.fn_name}

    def close(self):
        for p in self.leaves.values():
            p._data = jnp.zeros((), p._data.dtype)
        for store in self.opt._accumulators.values():
            for t in store.values():
                t._data = jnp.zeros((), t._data.dtype)
        self.step = self.model = self.opt = None
        self.leaves = {}


def build(cfg, cell, say):
    return TrainingSystem(cfg, cell["traffic"]["steps_per_dispatch"])
