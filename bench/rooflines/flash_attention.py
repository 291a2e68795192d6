"""What the flash-attention kernels of the training steps in the trace
need: causal attention's FLOPs.  For one head, forward is QK^T and PV
(4 s^2 d), backward dV, dP, dQ and dK (8 s^2 d); the causal mask halves
both.  The scores that the two backward kernels compute again are NOT
counted: recomputation is no model FLOP.  Compute bounds it on a v5e.

``calls`` is the number of kernel events found; the steps they belong to
are ``calls / (3 kernels a layer x layers)``: forward, dq, dkv.
"""


def step_flops(cfg, batch, seq):
    h, d = cfg["n_head"], cfg["n_embd"] // cfg["n_head"]
    full = batch * h * seq * seq * d
    fwd = 4 * full / 2          # QK^T and PV, causal half
    bwd = 8 * full / 2          # dV, dP, dQ, dK, causal half
    return (fwd + bwd) * cfg["n_layer"]


def step_bytes(cfg, batch, seq, itemsize=2):
    # q, k, v, o read or written once forward; q, k, v, o, do read and
    # dq, dk, dv written backward
    return 12 * batch * seq * cfg["n_embd"] * itemsize * cfg["n_layer"]


def needed(facts, calls):
    cfg, train = facts["config"], facts["train"]
    kernels_a_step = 3 * cfg["n_layer"]         # forward, dq, dkv a layer
    steps = calls / kernels_a_step
    return {"flops": steps * step_flops(cfg, train["batch"], train["seq"]),
            "bytes": steps * step_bytes(cfg, train["batch"], train["seq"])}
