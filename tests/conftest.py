"""Test env: CPU XLA with 8 virtual devices (SURVEY §4 — the reference simulates
multi-node as multi-process on one host; we simulate a TPU mesh as 8 CPU devices).

``JAX_PLATFORMS=cpu`` is set here before anything imports jax, so the chip is
never opened and every child a test spawns inherits it.  Tests hard-assert the
8-device CPU mesh up front so a mis-forced platform fails loudly instead of
silently testing less.  An autouse fixture reaps any child process a test
leaks (timeouts in ``communicate()`` kill nothing).
"""
import os
import signal
import tempfile
import time

# hermetic autotune cache: don't read/write the user's on-disk cache
os.environ["PADDLE_TPU_AUTOTUNE_CACHE"] = os.path.join(
    tempfile.gettempdir(), f"paddle_tpu_autotune_test_{os.getpid()}.json")

flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

# XLA executable cache, keyed by HLO hash: serving/spec tests build many
# LLMEngine instances whose per-instance jit closures lower to identical
# programs — the on-disk cache dedups those compiles within a run (and
# across runs / subprocess children, which inherit the env var).  Unlike
# the autotune cache this never changes behavior, only compile latency.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(tempfile.gettempdir(), "paddle_tpu_xla_cache"))
os.environ.setdefault("JAX_ENABLE_X64", "0")
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

assert jax.devices()[0].platform == "cpu", (
    f"test suite requires the CPU platform, got {jax.devices()[0].platform}"
)
assert len(jax.devices()) == 8, (
    f"test suite requires 8 virtual CPU devices, got {len(jax.devices())}"
)

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running tests excluded from the tier-1 gate (-m 'not slow')")


# ------------------------------------------------- chaos post-mortem capture

def _dump_chaos_artifacts(nodeid):
    """When ``PADDLE_TPU_CHAOS_ARTIFACTS`` names a directory, drop a metrics
    registry snapshot plus every pinned flight-recorder trace there — the
    evidence a red chaos-matrix leg needs for a post-mortem without a rerun.
    CI uploads the directory on failure; unset (the default) this is free."""
    d = os.environ.get("PADDLE_TPU_CHAOS_ARTIFACTS")
    if not d:
        return
    import json

    from paddle_tpu import observability as obs
    try:
        os.makedirs(d, exist_ok=True)
        safe = "".join(c if c.isalnum() or c in "._-" else "_"
                       for c in nodeid)[-120:]
        with open(os.path.join(d, f"metrics-{safe}.json"), "w",
                  encoding="utf-8") as f:
            json.dump(obs.snapshot(), f, indent=1, sort_keys=True)
    except Exception:
        pass                    # capture must never mask the real failure
    for tid, reason in obs.flight.pinned().items():
        try:
            obs.flight.dump_trace(tid, obs.flight.events_for(tid),
                                  reason=reason, out_dir=d)
        except OSError:
            pass


def pytest_runtest_logreport(report):
    if report.failed:
        _dump_chaos_artifacts(report.nodeid)


def _live_children():
    """pid -> state for direct children of this process (via /proc)."""
    me = os.getpid()
    out = {}
    for d in os.listdir("/proc"):
        if not d.isdigit():
            continue
        try:
            with open(f"/proc/{d}/stat") as f:
                stat = f.read()
            rest = stat[stat.rindex(")") + 2:].split()
            if int(rest[1]) == me:
                out[int(d)] = rest[0]
        except (OSError, ValueError):
            continue
    return out


def _cmdline(pid):
    try:
        with open(f"/proc/{pid}/cmdline", "rb") as f:
            return f.read().replace(b"\0", b" ").decode(errors="replace")
    except OSError:
        return ""


# multiprocessing helper daemons legitimately persist across tests
_KEEP_CHILDREN = ("multiprocessing.resource_tracker",
                  "multiprocessing.forkserver")


@pytest.fixture(autouse=True)
def _reap_leaked_children():
    """A child process that outlives its test is a leak (RPC pairs and PS
    servers survive ``communicate(timeout=...)`` expiry, which kills nothing):
    terminate it and reap the zombie so later tests don't inherit port
    collisions or CPU contention."""
    before = set(_live_children())
    yield
    after = _live_children()
    leaked = {p: st for p, st in after.items() if p not in before}
    live = [p for p, st in leaked.items()
            if st != "Z" and not any(k in _cmdline(p) for k in _KEEP_CHILDREN)]
    for p in live:
        try:
            os.kill(p, signal.SIGTERM)
        except OSError:
            pass
    deadline = time.time() + 5.0
    while live and time.time() < deadline:
        live = [p for p, st in _live_children().items()
                if p in live and st != "Z"]
        if live:
            time.sleep(0.05)
    for p in live:
        try:
            os.kill(p, signal.SIGKILL)
        except OSError:
            pass
    # reap every zombie child (leaked or pre-existing) without blocking
    for p, st in _live_children().items():
        if st == "Z":
            try:
                os.waitpid(p, os.WNOHANG)
            except (OSError, ChildProcessError):
                pass
