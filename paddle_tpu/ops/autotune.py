"""Kernel autotuner (reference: paddle/phi/kernels/autotune/ — cache.h
AutoTuneCache keyed by algorithm+shape hash, switch_autotune.h controlling
when tuning runs).

TPU-native: candidates are Pallas launch configs (block sizes), timed with
real compiled executions on the live device and memoized per
(op, static-shape/dtype) key, with optional on-disk persistence so a
relaunched job skips re-tuning (the reference persists via its cache
serialization). Tuning only ever happens on CONCRETE arrays — under a jit
trace the cached (or default) config is used, so autotuning never bakes
timing side effects into a compiled program."""
from __future__ import annotations

import json
import logging
import os
import threading
import time

import jax

from ..core import flags

if "use_autotune" not in flags._registry:   # normally defined in core/flags
    flags.define_flag("use_autotune", False,
                      "time Pallas launch-config candidates and cache the "
                      "best")

logger = logging.getLogger(__name__)

_lock = threading.Lock()
_cache: dict[str, dict] = {}
_loaded = False
_DISK = os.environ.get("PADDLE_TPU_AUTOTUNE_CACHE",
                       os.path.expanduser("~/.cache/paddle_tpu/autotune.json"))


def _load_disk():
    global _loaded
    if _loaded:
        return
    _loaded = True
    try:
        with open(_DISK) as f:
            _cache.update(json.load(f))
    except (OSError, ValueError):
        pass


def _save_disk():
    try:
        os.makedirs(os.path.dirname(_DISK), exist_ok=True)
        with open(_DISK, "w") as f:
            json.dump(_cache, f)
    except OSError:
        pass


def cache_key(op: str, *parts) -> str:
    return f"{op}|" + "|".join(str(p) for p in parts)


def lookup(key: str):
    """The tuned config for ``key``, or None.  With autotuning off nothing
    is consulted: a file left on disk by another run — another jax, another
    chip — must not pick a kernel's blocks."""
    if not enabled():
        return None
    _load_disk()
    with _lock:
        hit = _cache.get(key)
    return tuple(hit) if isinstance(hit, list) else hit


def enabled() -> bool:
    return bool(flags.flag("use_autotune"))


def _concrete(args) -> bool:
    return not any(isinstance(a, jax.core.Tracer) for a in args)


def tune(key: str, candidates, build, args, iters=3):
    """Pick the fastest candidate config for `key`.

    build(cfg) -> callable(*args). With autotune off: candidates[0]. With it
    on: the cached config when present, else candidates[0] inside a jit
    trace, else the fastest candidate timed on the concrete args.  A
    candidate that fails to compile or run is skipped, but never quietly:
    the failures are counted and logged, and if EVERY candidate failed the
    last error is raised — on the chip a refused kernel is a finding."""
    hit = lookup(key)
    if hit is not None:
        return hit
    if not enabled() or not _concrete(args):
        return candidates[0]
    best, best_t = None, float("inf")
    failed = []
    for cfg in candidates:
        try:
            fn = build(cfg)
            jax.block_until_ready(fn(*args))       # compile + warm
            t0 = time.perf_counter()
            for _ in range(iters):
                out = fn(*args)
            jax.block_until_ready(out)
            dt = (time.perf_counter() - t0) / iters
        except Exception as e:  # noqa: BLE001 — any refusal is recorded
            failed.append((cfg, e))
            continue
        if dt < best_t:
            best, best_t = cfg, dt
    if failed:
        cfg, e = failed[0]
        logger.warning(
            "autotune %s: %d of %d candidates failed to compile/run (first: "
            "%s -> %s: %s)", key, len(failed), len(candidates), cfg,
            type(e).__name__, str(e)[:200])
    if best is None:
        raise RuntimeError(
            f"autotune {key}: all {len(candidates)} candidates failed"
        ) from failed[-1][1]
    with _lock:
        _cache[key] = list(best) if isinstance(best, tuple) else best
        _save_disk()
    return best


def clear():
    with _lock:
        _cache.clear()
