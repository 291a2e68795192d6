"""Runtime flag registry.

Analog of the reference's FLAGS_* system (common/flags.cc, ~185 flags; python surface
paddle.set_flags/get_flags in python/paddle/base/framework.py:132). Flags are a plain
registry with env-var override (`FLAGS_<name>`), typed defaults, and change hooks so
subsystems can react (e.g. nan/inf checking toggling the debug dispatch path).
"""
from __future__ import annotations

import os
import threading
from typing import Any, Callable

_lock = threading.Lock()
_registry: dict[str, dict] = {}
_hooks: dict[str, list[Callable[[Any], None]]] = {}


def define_flag(name: str, default, help: str = ""):
    typ = type(default)
    value = default
    env = os.environ.get(f"FLAGS_{name}")
    if env is not None:
        value = _coerce(env, typ)
    _registry[name] = {"value": value, "default": default, "type": typ, "help": help}
    return value


def _coerce(v, typ):
    if typ is bool:
        return str(v).lower() in ("1", "true", "yes", "on")
    return typ(v)


def set_flags(flags: dict):
    with _lock:
        for name, value in flags.items():
            key = name[6:] if name.startswith("FLAGS_") else name
            if key not in _registry:
                raise KeyError(f"unknown flag {name!r}")
            entry = _registry[key]
            entry["value"] = _coerce(value, entry["type"])
            for hook in _hooks.get(key, ()):
                hook(entry["value"])


def get_flags(flags=None) -> dict:
    if flags is None:
        names = list(_registry)
    elif isinstance(flags, str):
        names = [flags]
    else:
        names = list(flags)
    out = {}
    for name in names:
        key = name[6:] if name.startswith("FLAGS_") else name
        out[f"FLAGS_{key}"] = _registry[key]["value"]
    return out


def flag(name: str):
    return _registry[name]["value"]


def on_change(name: str, hook: Callable[[Any], None]):
    _hooks.setdefault(name, []).append(hook)


# Core flags (subset of common/flags.cc relevant on TPU)
define_flag("check_nan_inf", False, "scan op outputs for nan/inf (debug dispatch path)")
define_flag("use_autotune", False,
            "time Pallas launch-config candidates and cache the best "
            "(ops/autotune.py)")
define_flag("check_nan_inf_level", 0, "0: error on nan/inf; >=1: log only")
define_flag("low_precision_op_list", 0, "audit ops running in low precision")
define_flag("use_stride_kernel", True, "allow view/stride shortcuts where possible")
define_flag("eager_delete_tensor_gb", 0.0, "GC threshold (no-op: XLA manages memory)")
define_flag("tpu_matmul_precision", "highest",
            "jax matmul precision: default|high|highest. 'highest' makes fp32 "
            "matmuls true fp32 on the MXU (multi-pass bf16); bf16 inputs are "
            "unaffected, so bf16 training keeps full MXU throughput")
define_flag("log_level", 0, "VLOG-style verbosity for framework logging")
define_flag("flash_layout_direct", False,
            "flash attention reads [B,S,H,D] operands directly (no relayout "
            "copies) via in-kernel per-head lane slicing; measured slower on "
            "v5e at GPT-2 shapes, may win at other geometries")
define_flag("weight_only_use_kernel", True,
            "route weight_only_linear through the Pallas in-kernel-dequant "
            "matmul on TPU no-grad calls; False uses the XLA dequant "
            "formulation (which of the two is faster on the chip: not "
            "measured)")
define_flag("eager_recompute_grad", False,
            "eager autograd stores op inputs only and recomputes each vjp at "
            "backward time (2x forward FLOPs, far lower peak memory); the "
            "to_static spy pass always runs in this mode")


def _apply_matmul_precision(value):
    """Wire tpu_matmul_precision to XLA. Without this, fp32 matmul/einsum
    silently run at bf16 precision on the TPU backend (one MXU pass)."""
    import jax

    jax.config.update("jax_default_matmul_precision",
                      None if value == "default" else value)


_apply_matmul_precision(flag("tpu_matmul_precision"))
on_change("tpu_matmul_precision", _apply_matmul_precision)
