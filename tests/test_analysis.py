"""graftlint (paddle_tpu.analysis) — per-pass fixture tests + repo self-check.

Each pass gets a known-bad fixture (seeded violations it must catch) and a
known-clean fixture (idioms it must NOT flag).  The repo self-check at the
bottom is the tier-1 CI gate: the analyzer must exit clean on the tree.
"""
import importlib
import json
import pathlib
import textwrap

import pytest

from paddle_tpu.analysis import PASSES, run
from paddle_tpu.analysis import cli
from paddle_tpu.analysis.baseline import Baseline
from paddle_tpu.analysis.cache import FileCache
from paddle_tpu.analysis.framework import Finding, SourceFile

REPO = pathlib.Path(__file__).resolve().parents[1]
FIXTURES = pathlib.Path(__file__).resolve().parent / "graftlint_fixtures"


def _lint(tmp_path, source, select=None, name="fixture.py"):
    p = tmp_path / name
    p.write_text(textwrap.dedent(source))
    return run([str(p)], select=select)


def _codes(result):
    return {f.code for f in result.findings}


# ---------------------------------------------------------------- trace-safety

TS_BAD = """
    import jax
    import numpy as np

    _STEP = 0

    @jax.jit
    def bad(x, y):
        global _STEP
        if x > 0:                  # TS101: data-dependent branch
            y = y + 1
        v = float(x)               # TS102: host escape builtin
        h = y.numpy()              # TS103: host escape method
        w = np.tanh(x)             # TS104: numpy on a tracer
        _STEP = _STEP + 1          # TS105: trace-time side effect
        return helper(y) + v + h + w

    def helper(z):
        while z.sum() > 0:         # TS101 via interprocedural taint
            z = z - 1
        return z
"""

TS_CLEAN = """
    import jax
    import numpy as np

    TABLE = np.arange(8)           # numpy on host constants is fine

    @jax.jit
    def clean(x, mask=None):
        if mask is None:           # identity compare is static
            mask = x * 0
        if len(x.shape) == 2:      # shape metadata is host-known
            x = x + 1
        for dim in range(x.ndim):  # ndim is static
            x = x * 1
        vals = [x, x + 1]
        out = 0
        for v, keep in zip(vals, [True, False]):   # static mask: no taint
            if keep:
                out = out + v
        return out
"""


def test_trace_safety_catches_seeded_violations(tmp_path):
    res = _lint(tmp_path, TS_BAD, select=["trace-safety"])
    assert {"TS101", "TS102", "TS103", "TS104", "TS105"} <= _codes(res)
    # the interprocedural edge reaches helper()'s while loop
    lines = {f.line for f in res.findings if f.code == "TS101"}
    assert len(lines) >= 2


def test_trace_safety_clean_idioms_not_flagged(tmp_path):
    res = _lint(tmp_path, TS_CLEAN, select=["trace-safety"])
    assert res.findings == []


def test_trace_safety_respects_static_argnames(tmp_path):
    src = """
        import jax
        from functools import partial

        @partial(jax.jit, static_argnames=("mode",))
        def f(x, mode):
            if mode == "train":    # static arg: host branch is fine
                return x * 2
            return x
    """
    res = _lint(tmp_path, src, select=["trace-safety"])
    assert res.findings == []


def test_trace_safety_every_finding_has_hint(tmp_path):
    res = _lint(tmp_path, TS_BAD, select=["trace-safety"])
    assert res.findings and all(f.hint for f in res.findings)


# ------------------------------------------------------------- registry-parity

RP_STATIC_BAD = """
    REGISTRY = {}

    def u(name, ref, cat="math", **kw):
        REGISTRY[name] = (ref, cat, kw)

    u("tanh", None)                 # RP003: golden without np_ref/check
    u("tanh", abs)                  # RP001: duplicate registration
    u("warp", abs, cat="astral")    # RP002: unknown category
"""

RP_RUNTIME_PKG = """
    REGISTRY = {}
    CATEGORIES = frozenset({"math"})
    DUPLICATE_REGISTRATIONS = []

    class OpSpec:
        def __init__(self, name, op, np_ref=None, sample=None, kwargs=(),
                     kind="golden", category="math", check=None,
                     alias_of=None):
            self.name, self.op, self.np_ref = name, op, np_ref
            self.sample, self.kwargs, self.kind = sample, kwargs, kind
            self.category, self.check, self.alias_of = category, check, alias_of

        def resolve(self):
            if self.op is None:
                raise AttributeError(f"no resolver for {self.name}")
            return self.op

    def _one(x):
        return x

    def u(name, ref, cat="math", **kw):
        REGISTRY[name] = OpSpec(name, kw.pop("op", None), np_ref=ref,
                                category=cat, **kw)

    u("good", abs, op=_one, sample=lambda: [1.0])
    u("two_into_one", abs, op=_one, sample=lambda: [1.0, 2.0])  # RP007
    u("ghost", abs, op=None, sample=lambda: [1.0])              # RP006
"""


def test_registry_parity_static_checks(tmp_path):
    res = _lint(tmp_path, RP_STATIC_BAD, select=["registry-parity"])
    assert {"RP001", "RP002", "RP003"} <= _codes(res)


def test_registry_parity_runtime_checks(tmp_path, monkeypatch):
    pkg = tmp_path / "graftlint_fixture_pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "registry.py").write_text(textwrap.dedent(RP_RUNTIME_PKG))
    monkeypatch.syspath_prepend(str(tmp_path))
    importlib.invalidate_caches()
    res = run([str(pkg)], select=["registry-parity"])
    codes = _codes(res)
    assert "RP007" in codes     # resolver arity vs sample builder
    assert "RP006" in codes     # missing resolver
    flagged = {f.message.split("'")[1] for f in res.findings}
    assert "good" not in flagged


def test_registry_parity_clean_on_non_registry_files(tmp_path):
    res = _lint(tmp_path, "def u(x):\n    return x\nu(3)\n",
                select=["registry-parity"])
    assert res.findings == []


# ------------------------------------------------------------ namespace-parity

NS_BAD = """
    __all__ = ["real", "ghost", "real"]    # NS001 ghost, NS002 dup

    def real():
        return 1
"""

NS_CLEAN = """
    import os as _os

    __all__ = ["real", "CONST", "_os"]

    CONST = 3

    def real():
        return 1
"""


def test_namespace_parity_catches_stale_and_duplicate(tmp_path):
    res = _lint(tmp_path, NS_BAD, select=["namespace-parity"])
    assert _codes(res) == {"NS001", "NS002"}
    msgs = " ".join(f.message for f in res.findings)
    assert "ghost" in msgs


def test_namespace_parity_clean(tmp_path):
    res = _lint(tmp_path, NS_CLEAN, select=["namespace-parity"])
    assert res.findings == []


def test_namespace_parity_skips_star_import_files(tmp_path):
    src = """
        from os.path import *

        __all__ = ["join", "whatever"]
    """
    res = _lint(tmp_path, src, select=["namespace-parity"])
    assert not any(f.code == "NS001" for f in res.findings)


# ----------------------------------------------------------- jit-cache-hygiene

JH_BAD = """
    import jax
    import jax.numpy as jnp
    from functools import partial

    @jax.jit
    def f(x, scale=jnp.ones(3), opts=[1, 2]):   # JH002, JH001
        return x * scale

    @partial(jax.jit, static_argnames=("cfg",))
    def g(x, cfg={"a": 1}):                      # JH004
        return x

    def caller(x):
        return g(x, cfg={"b": 2})                # JH003
"""

JH_CLEAN = """
    import jax

    @jax.jit
    def f(x, scale=None, shape=(3, 3)):          # None/tuple defaults hash fine
        return x

    def plain(x, opts=[1]):                      # not a jit entry: no finding
        return x
"""


def test_jit_cache_hygiene_catches_seeded_violations(tmp_path):
    res = _lint(tmp_path, JH_BAD, select=["jit-cache-hygiene"])
    assert _codes(res) == {"JH001", "JH002", "JH003", "JH004"}


def test_jit_cache_hygiene_clean(tmp_path):
    res = _lint(tmp_path, JH_CLEAN, select=["jit-cache-hygiene"])
    assert res.findings == []


# ---------------------------------------------------------- no-adhoc-telemetry

AT_BAD = """
    import time
    from time import time as walltime


    def work():
        t0 = time.time()
        print("starting work")
        elapsed = time.time() - t0
        return elapsed + walltime()
"""

AT_CLEAN = """
    import logging
    import time

    logger = logging.getLogger(__name__)


    def work(timer=None):
        t0 = time.perf_counter()
        logger.info("starting work")
        deadline = time.monotonic() + 5.0
        timer.time()          # method named `time` on another object: fine
        return time.perf_counter() - t0, deadline
"""


def test_no_adhoc_telemetry_catches_seeded_violations(tmp_path):
    res = _lint(tmp_path, AT_BAD, select=["no-adhoc-telemetry"])
    assert _codes(res) == {"AT101", "AT102"}
    # three wall-clock reads: two time.time() plus the renamed from-import
    assert sum(f.code == "AT102" for f in res.findings) == 3
    assert sum(f.code == "AT101" for f in res.findings) == 1


def test_no_adhoc_telemetry_clean_idioms_not_flagged(tmp_path):
    res = _lint(tmp_path, AT_CLEAN, select=["no-adhoc-telemetry"])
    assert res.findings == []


AT103_BAD = """
    class Tier:
        def submit(self, prompt):
            return self.client.call("submit", prompt_ids=prompt)

    def pull(rpc, rid):
        return rpc.call("handoff_pull", rid=rid)

    def scrape(metrics_client, deadline):
        return metrics_client.call("metrics_snapshot", deadline=deadline)
"""

AT103_CLEAN = """
    def traced(self, prompt, ctx):
        return self.client.call("submit", ctx=ctx, prompt_ids=prompt)

    def control_plane(self):
        return self.client.call("ping", ctx=None)   # explicit: untraced

    def not_rpc(self):
        return self._exported.call(self._params)    # jit export, not RPC

    def also_not_rpc(callback):
        return callback.call()                       # no client-ish name
"""


def test_no_adhoc_telemetry_at103_ctx_dropped(tmp_path):
    res = _lint(tmp_path, AT103_BAD, select=["no-adhoc-telemetry"])
    assert _codes(res) == {"AT103"}
    # all three client-like receivers: self.client, bare rpc, *_client
    assert len(res.findings) == 3
    assert all("trace context" in f.message for f in res.findings)


def test_no_adhoc_telemetry_at103_clean_idioms(tmp_path):
    res = _lint(tmp_path, AT103_CLEAN, select=["no-adhoc-telemetry"])
    assert res.findings == []


def test_no_adhoc_telemetry_line_pragma(tmp_path):
    src = """
        import time


        def show():
            print("hi")  # graftlint: disable=no-adhoc-telemetry
            return time.time()  # graftlint: disable=no-adhoc-telemetry
    """
    res = _lint(tmp_path, src, select=["no-adhoc-telemetry"])
    assert res.findings == [] and res.suppressed == 2


AT104_BAD = """
    import time


    def prefill(runner, flight, r):
        t0 = time.perf_counter()
        out = runner.run_prefill(r)
        if r.trace_id is not None:
            flight.record("prefill", rid=r.rid, dur=time.perf_counter() - t0)
        return out


    def append(hist, fh, payload):
        t0 = time.perf_counter()
        fh.write(payload)
        dt = time.perf_counter() - t0
        hist.observe(dt / 2)
"""

AT104_CLEAN = """
    import time


    def decode(self, obs, k):
        with obs.trace_span("decode", block=k) as sp:
            toks = self.runner.run_decode(k)
        self.hist.observe(sp.dur / k)
        return toks


    def stage(self, flight, h):
        t0 = time.perf_counter()
        block = self.dispatch(h)
        dispatch_s = time.perf_counter() - t0
        flight.record("handoff_dispatch", dur=dispatch_s)
        self.transfer_s += dispatch_s     # an always-on counter reads it too
        return block, t0


    def stamp(self, hist, r):
        hist.observe(time.perf_counter() - r.t_submit)   # no pair: a stamp
"""


def _lint_inference(tmp_path, source):
    d = tmp_path / "inference"
    d.mkdir()
    return _lint(d, source, select=["no-adhoc-telemetry"])


def test_no_adhoc_telemetry_at104_pair_beside_the_span_call(tmp_path):
    res = _lint_inference(tmp_path, AT104_BAD)
    assert [f.code for f in res.findings] == ["AT104", "AT104"]
    assert all("trace_span" in f.message for f in res.findings)


def test_no_adhoc_telemetry_at104_clean_idioms(tmp_path):
    assert _lint_inference(tmp_path, AT104_CLEAN).findings == []


def test_no_adhoc_telemetry_at104_only_in_inference(tmp_path):
    res = _lint(tmp_path, AT104_BAD, select=["no-adhoc-telemetry"])
    assert res.findings == []


# ----------------------------------------------- sharding-spec-coverage

def _sharding(paths):
    return run([str(p) for p in paths], select=["sharding-spec-coverage"])


def test_sharding_spec_catches_seeded_violations():
    res = _sharding([FIXTURES / "sharding_bad.py"])
    assert _codes(res) == {"SS101", "SS102", "SS103", "SS104", "SS105",
                           "SS106"}
    by_code = {}
    for f in res.findings:
        by_code.setdefault(f.code, []).append(f)
    assert "2 positional argument(s)" in by_code["SS101"][0].message
    assert "'ep'" in by_code["SS102"][0].message
    assert "'sep'" in by_code["SS103"][0].message
    assert by_code["SS104"][0].severity == "warning"    # divergence risk
    assert "3-tuple" in by_code["SS105"][0].message
    # SS106 fires at BOTH spec-vs-mesh sites: the NamedSharding ctor and
    # the bare PartitionSpec inside jit's in_shardings keyword
    ss106 = " | ".join(f.message for f in by_code["SS106"])
    assert "'tp'" in ss106 and "'fsdp'" in ss106
    assert any("in_shardings" in f.message for f in by_code["SS106"])
    assert all(f.severity == "error" for f in res.findings
               if f.code != "SS104")
    assert all(f.hint for f in res.findings)


def test_sharding_spec_clean_fixture_not_flagged():
    res = _sharding([FIXTURES / "sharding_clean.py"])
    assert res.findings == []


def test_sharding_spec_resolves_body_across_files():
    res = _sharding([FIXTURES / "sharding_xfile_def.py",
                     FIXTURES / "sharding_xfile_use.py"])
    assert _codes(res) == {"SS101"}
    (f,) = res.findings
    assert f.path.endswith("sharding_xfile_use.py")
    assert "3 positional argument(s)" in f.message


def test_jit_shardings_use_mesh_spelling(tmp_path):
    src = """
        import jax
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(jax.devices(), ("dp",))

        def f(fn, x):
            with jax.sharding.use_mesh(mesh):
                g = jax.jit(fn, out_shardings=P("mp"))
                return g(x)
    """
    res = _lint(tmp_path, src, select=["sharding-spec-coverage"])
    assert _codes(res) == {"SS106"}
    (f,) = res.findings
    assert "'mp'" in f.message and "out_shardings" in f.message


# --------------------------------------------------------------- robustness

def test_robustness_flags_swallowed_exceptions():
    res = run([str(FIXTURES / "robustness_bad.py")], select=["robustness"])
    assert _codes(res) == {"RB101", "RB102", "RB104"}
    by_code = {}
    for f in res.findings:
        by_code.setdefault(f.code, []).append(f)
    assert len(by_code["RB101"]) == 5
    assert len(by_code["RB102"]) == 4        # continue, break, return, None
    assert len(by_code["RB104"]) == 2        # while retry, for retry
    assert all(f.severity == "warning" for f in res.findings)
    msgs = " | ".join(f.message for f in res.findings)
    assert "bare except" in msgs and "except BaseException" in msgs
    rb102 = " | ".join(f.message for f in by_code["RB102"])
    assert "continue" in rb102 and "break" in rb102 and "return" in rb102
    rb104 = " | ".join(f.message for f in by_code["RB104"])
    assert "while retry loop" in rb104 and "for retry loop" in rb104
    assert all("RetryPolicy" in f.message for f in by_code["RB104"])
    assert all(f.hint for f in res.findings)


def test_robustness_clean_fixture_not_flagged():
    res = run([str(FIXTURES / "robustness_clean.py")], select=["robustness"])
    assert res.findings == []
    assert res.suppressed == 2          # pragma'd swallow + pragma'd retry


def test_robustness_rb104_wait_loop_vs_retry_loop(tmp_path):
    # the discriminator is an attempt under try/except in the SAME loop:
    # a sleeping poll loop is waiting, not retrying
    src = """
        import time

        def poll(ready):
            while not ready():
                time.sleep(0.1)

        def reconnect(connect):
            while True:
                try:
                    return connect()
                except OSError:
                    time.sleep(0.1)
    """
    res = _lint(tmp_path, src, select=["robustness"])
    assert _codes(res) == {"RB104"}
    (f,) = res.findings
    assert "time.sleep" in f.message and "core.retry" in f.message


def test_robustness_rb104_ignores_injected_sleep(tmp_path):
    # core.retry's own loop sleeps through an injectable callable — only
    # the literal time.sleep spelling is a policy bypass
    src = """
        def retry(fn, sleep, delays):
            for d in delays:
                try:
                    return fn()
                except OSError:
                    sleep(d)
            return fn()
    """
    res = _lint(tmp_path, src, select=["robustness"])
    assert res.findings == []


def test_robustness_rb105_flags_torn_writes_in_persistence_modules():
    res = run([str(FIXTURES / "persistence_bad.py")], select=["robustness"])
    assert _codes(res) == {"RB105"}
    assert len(res.findings) == 4            # w, wb, mode="w", marker
    assert all(f.severity == "warning" for f in res.findings)
    assert all("os.replace" in f.hint for f in res.findings)
    modes = " | ".join(f.message for f in res.findings)
    assert "'w'" in modes and "'wb'" in modes


def test_robustness_rb105_clean_fixtures_not_flagged():
    # tmp-staged / append / read / dynamic-mode writes inside a qualifying
    # module, and ANY write inside a module with no os.replace/os.fsync
    for name in ("persistence_clean.py", "persistence_clean_nodisc.py"):
        res = run([str(FIXTURES / name)], select=["robustness"])
        assert res.findings == [], name


def test_robustness_rb105_journal_compaction_is_clean():
    # the request journal IS the in-tree model of the idiom RB105 enforces:
    # its own truncating writes are all tmp-staged or append-mode
    res = run([str(REPO / "paddle_tpu" / "inference" / "frontend"
                   / "journal.py")], select=["robustness"])
    assert not [f for f in res.findings if f.code == "RB105"]


def test_sharding_spec_repo_parallel_tree_is_clean():
    res = _sharding([REPO / "paddle_tpu" / "parallel",
                     REPO / "paddle_tpu" / "distributed"])
    assert res.findings == [], "\n" + "\n".join(
        f.render() for f in res.findings)


def test_sharding_spec_skips_dynamic_specs(tmp_path):
    # non-literal specs / meshes must be skipped, never guessed
    src = """
        from jax.experimental.shard_map import shard_map

        def apply(fn, mesh, in_specs, out_specs, x):
            f = shard_map(fn, mesh=mesh, in_specs=in_specs,
                          out_specs=out_specs)
            return f(x)
    """
    res = _lint(tmp_path, src, select=["sharding-spec-coverage"])
    assert res.findings == []


def test_named_sharding_axis_checked_outside_shard_map(tmp_path):
    # SS106 fires at bare NamedSharding construction sites too (device_put,
    # jit sharding args, ...), not only under with_sharding_constraint
    src = """
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(jax.devices(), ("dp",))

        def place(x):
            return jax.device_put(x, NamedSharding(mesh, P("model")))
    """
    res = _lint(tmp_path, src, select=["sharding-spec-coverage"])
    assert _codes(res) == {"SS106"}
    (f,) = res.findings
    assert "'model'" in f.message and "(dp)" in f.message


# --------------------------------------------------------------- dtype-rules

def test_dtype_rules_catches_seeded_violations(monkeypatch):
    monkeypatch.syspath_prepend(str(FIXTURES))
    importlib.invalidate_caches()
    res = run([str(FIXTURES / "dtype_bad_pkg")], select=["dtype-rules"])
    codes = _codes(res)
    assert codes == {"DT101", "DT102", "DT103"}
    flagged = {f.message.split("'")[1] for f in res.findings}
    assert flagged == {"bad_index", "bad_sample", "bad_grad", "f64_golden"}
    by_op = {f.message.split("'")[1]: f for f in res.findings}
    assert by_op["bad_index"].severity == "error"
    assert by_op["f64_golden"].severity == "warning"
    # findings land on the registration line of the offending op
    assert by_op["bad_index"].line != by_op["bad_grad"].line


def test_dtype_rules_warning_not_in_errors(monkeypatch):
    monkeypatch.syspath_prepend(str(FIXTURES))
    importlib.invalidate_caches()
    res = run([str(FIXTURES / "dtype_bad_pkg")], select=["dtype-rules"])
    assert all(f.code != "DT102" for f in res.errors())
    assert any(f.code == "DT102" for f in res.findings)


def test_dtype_rules_skips_non_registry_files(tmp_path):
    res = _lint(tmp_path, "import numpy as np\nx = np.array([1])\n",
                select=["dtype-rules"])
    assert res.findings == []


# ----------------------------------------------------------- concurrency

def _cc(paths):
    return run([str(p) for p in paths], select=["concurrency"])


def test_concurrency_cc101_bad_fixture():
    res = _cc([FIXTURES / "concurrency_cc101_bad.py"])
    assert _codes(res) == {"CC101"}
    # one finding per (attr, method): the naked read AND the naked write
    assert len(res.findings) == 2
    assert all(f.severity == "warning" for f in res.findings)
    assert any("read with no lock held in read()" in f.message
               for f in res.findings)


def test_concurrency_cc101_clean_fixture():
    # the clean fixture routes writes through a caller-holds-the-lock
    # helper: inherited lock context must keep it silent
    res = _cc([FIXTURES / "concurrency_cc101_clean.py"])
    assert res.findings == []


def test_concurrency_cc102_bad_fixture():
    res = _cc([FIXTURES / "concurrency_cc102_bad.py"])
    assert _codes(res) == {"CC102"}
    msgs = "\n".join(f.message for f in res.findings)
    assert "time.sleep()" in msgs
    assert "injectable sleep" in msgs          # self.sleep = sleep param
    assert "which does os.fsync()" in msgs     # one call-hop into _sync()


def test_concurrency_cc102_clean_fixture():
    res = _cc([FIXTURES / "concurrency_cc102_clean.py"])
    assert res.findings == []


def test_concurrency_cc103_bad_fixture():
    res = _cc([FIXTURES / "concurrency_cc103_bad.py"])
    assert _codes(res) == {"CC103"}
    assert all(f.severity == "error" for f in res.findings)
    msgs = "\n".join(f.message for f in res.findings)
    assert "not inside a while loop" in msgs
    assert "notify_all() in put() outside" in msgs


def test_concurrency_cc103_clean_fixture():
    # while-predicate waits, notify under the cv, and a wait_for lambda
    # predicate (which runs WITH the lock held — no CC101 either)
    res = _cc([FIXTURES / "concurrency_cc103_clean.py"])
    assert res.findings == []


CC_HOLDER = """
    import contextlib
    import threading


    class Replica:
        def __init__(self):
            self._cv = threading.Condition(threading.RLock())
            self.queue = []

        @contextlib.contextmanager
        def _engine_lock(self, op):
            self._cv.acquire()
            try:
                yield
            finally:
                self._cv.release()

        def submit(self, item):
            with self._engine_lock("submit"):
                self.queue.append(item)
                self._cv.notify_all()

        def take(self):
            with self._cv:
                while not self.queue:
                    self._cv.wait()
                return self.queue.pop()
%s
"""

CC_HOLDER_BARE = """
        def peek(self):
            self._cv.notify_all()          # CC103: nothing held here
"""


def test_concurrency_lock_held_through_a_contextmanager_helper(tmp_path):
    """``with self._engine_lock(...)`` holds the condition its helper
    acquires: the notify inside is owned, the queue is guarded."""
    res = _lint(tmp_path, CC_HOLDER % "", select=["concurrency"])
    assert res.findings == []
    res = _lint(tmp_path, CC_HOLDER % CC_HOLDER_BARE, select=["concurrency"],
                name="bare.py")
    assert _codes(res) == {"CC103"}


def test_concurrency_cc104_bad_fixture():
    res = _cc([FIXTURES / "concurrency_cc104_bad.py"])
    assert _codes(res) == {"CC104"}
    (f,) = res.findings
    assert f.severity == "error"
    # both sites cited by method name (messages stay line-free so the
    # baseline fingerprint survives reformatting)
    assert "transfer()" in f.message and "reconcile()" in f.message
    assert "lock-order inversion" in f.message


def test_concurrency_cc104_clean_fixture():
    res = _cc([FIXTURES / "concurrency_cc104_clean.py"])
    assert res.findings == []


def test_concurrency_cc105_bad_fixture():
    res = _cc([FIXTURES / "concurrency_cc105_bad.py"])
    assert _codes(res) == {"CC105"}
    msgs = "\n".join(f.message for f in res.findings)
    assert "calls self._bump(), which acquires it again" in msgs
    assert "re-acquired in a nested with" in msgs


def test_concurrency_cc105_clean_fixture():
    res = _cc([FIXTURES / "concurrency_cc105_clean.py"])
    assert res.findings == []


def test_concurrency_inherited_lock_context(tmp_path):
    # a helper is only "caller holds the lock" when EVERY non-init call
    # site holds it: one naked call site revokes the inheritance
    res = _lint(tmp_path, """
        import threading

        class Box:
            def __init__(self):
                self._mu = threading.Lock()
                self.n = 0

            def locked_path(self):
                with self._mu:
                    self.n += 1
                    self._bump()

            def naked_path(self):
                self._bump()

            def _bump(self):
                self.n += 1
        """, select=["concurrency"])
    assert _codes(res) == {"CC101"}
    assert any("in _bump()" in f.message for f in res.findings)


def test_concurrency_module_level_lock_order(tmp_path):
    res = _lint(tmp_path, """
        import threading

        _a = threading.Lock()
        _b = threading.Lock()

        def forward():
            with _a:
                with _b:
                    pass

        def backward():
            with _b:
                with _a:
                    pass
        """, select=["concurrency"])
    assert _codes(res) == {"CC104"}


def test_concurrency_init_is_exempt(tmp_path):
    # __init__ populates guarded attrs before the object is shared
    res = _lint(tmp_path, """
        import threading

        class Box:
            def __init__(self):
                self._mu = threading.Lock()
                self.n = 0
                self.n += 1

            def bump(self):
                with self._mu:
                    self.n += 1
        """, select=["concurrency"])
    assert res.findings == []


def test_concurrency_nested_def_holds_nothing(tmp_path):
    # a closure defined under the lock runs later (possibly on another
    # thread): the sleep inside it is NOT "blocking while holding"
    res = _lint(tmp_path, """
        import threading
        import time

        class Box:
            def __init__(self):
                self._mu = threading.Lock()

            def arm(self):
                with self._mu:
                    def later():
                        time.sleep(1.0)
                    return later
        """, select=["concurrency"])
    assert res.findings == []


def test_concurrency_pragma_and_baseline(tmp_path):
    src = """
        import threading

        class Box:
            def __init__(self):
                self._mu = threading.Lock()
                self.n = 0

            def bump(self):
                with self._mu:
                    self.n += 1

            def peek(self):
                return self.n{pragma}
        """
    flagged = _lint(tmp_path, src.format(pragma=""))
    assert _codes(flagged) == {"CC101"}
    quiet = _lint(tmp_path,
                  src.format(pragma="  # graftlint: disable=concurrency"),
                  name="quiet.py")
    assert quiet.findings == []
    assert quiet.suppressed == 1
    base = Baseline(frozenset(f.fingerprint() for f in flagged.findings))
    absorbed = run([str(tmp_path / "fixture.py")], select=["concurrency"],
                   baseline=base)
    assert absorbed.findings == [] and absorbed.baselined == 1


# ---------------------------------------- contracts (summary-scope) fixtures

def _ct(paths):
    return run([str(p) for p in paths], select=["contracts"])


def _rl(paths):
    return run([str(p) for p in paths], select=["resource_lifecycle"])


def test_contracts_ct101_bad_fixture():
    res = _ct([FIXTURES / "contracts_ct101_bad.py"])
    assert _codes(res) == {"CT101"}
    sev = {f.severity for f in res.findings}
    assert sev == {"error", "warning"}      # unhandled op + dead arm
    msgs = "\n".join(f.message for f in res.findings)
    assert "'cancel' has no registered server handler" in msgs
    assert "'audit' has no call site anywhere" in msgs


def test_contracts_ct101_clean_fixture():
    # parity both ways, with one op site resolved through a forwarder
    # method (Remote._call) and a client bound to a plain local name
    res = _ct([FIXTURES / "contracts_ct101_clean.py"])
    assert res.findings == []


def test_contracts_ct102_bad_fixture():
    res = _ct([FIXTURES / "contracts_ct102_bad.py"])
    assert _codes(res) == {"CT102"}
    assert "QuotaError" in res.findings[0].message
    assert res.findings[0].severity == "warning"


def test_contracts_ct102_clean_fixture():
    # verbatim-forwarding __init__, explicit __reduce__, and no __init__
    # at all are the three pickle-safe shapes
    res = _ct([FIXTURES / "contracts_ct102_clean.py"])
    assert res.findings == []


def test_contracts_ct103_bad_fixture():
    res = _ct([FIXTURES / "contracts_ct103_bad.py",
               FIXTURES / "contracts_ct103_decl.py"])
    assert _codes(res) == {"CT103"}
    msgs = "\n".join(f.message for f in res.findings)
    assert "'engine.stray' is fired but not declared" in msgs
    assert "non-literal point name" in msgs
    assert "'engine.retire' is never fired" in msgs
    assert "'engine.flush' has no injected(...) chaos coverage" in msgs
    errors = [f for f in res.findings if f.severity == "error"]
    assert len(errors) == 1                 # only the undeclared fire


def test_contracts_ct103_clean_fixture():
    res = _ct([FIXTURES / "contracts_ct103_clean.py",
               FIXTURES / "contracts_ct103_decl_ok.py"])
    assert res.findings == []


def test_contracts_ct103_self_armed_adhoc_point_ok(tmp_path):
    # a file that both arms a point (injected/install) and fires it is the
    # injector's own unit test — no parity error even with a KNOWN_POINTS
    # table elsewhere in the project
    adhoc = """
        from paddle_tpu.testing.faults import FAULTS, FailNth, injected

        def test_probe():
            with injected("p", FailNth(1)):
                FAULTS.fire("p", rid=1)
    """
    decl = 'KNOWN_POINTS = frozenset({"engine.step"})\n'
    a = tmp_path / "test_adhoc.py"
    a.write_text(textwrap.dedent(adhoc))
    d = tmp_path / "decl.py"
    d.write_text(decl)
    res = run([str(a), str(d)], select=["contracts"])
    assert not [f for f in res.findings if f.severity == "error"]


def test_contracts_ct104_bad_fixture():
    res = _ct([FIXTURES / "contracts_ct104_bad.py"])
    assert _codes(res) == {"CT104"}
    msgs = "\n".join(f.message for f in res.findings)
    assert "not a valid Prometheus name" in msgs
    assert "non-literal name" in msgs
    assert "redeclared as gauge but first declared as counter" in msgs


def test_contracts_ct104_clean_fixture():
    res = _ct([FIXTURES / "contracts_ct104_clean.py"])
    assert res.findings == []


# ------------------------------------------------- resource_lifecycle fixtures

def test_resource_rl101_bad_fixture():
    res = _rl([FIXTURES / "resource_rl101_bad.py"])
    assert _codes(res) == {"RL101"}
    msgs = "\n".join(f.message for f in res.findings)
    assert "socket 'sock' can leak" in msgs
    assert "constructor raises after acquiring" in msgs


def test_resource_rl101_clean_fixture():
    # closing except, guarded ctor, with-block, daemon thread, joined thread
    res = _rl([FIXTURES / "resource_rl101_clean.py"])
    assert res.findings == []


def test_resource_rl102_bad_fixture():
    res = _rl([FIXTURES / "resource_rl102_bad.py"])
    assert _codes(res) == {"RL102"}
    assert "alloc_page() ref can strand" in res.findings[0].message


def test_resource_rl102_clean_fixture():
    # rollback-guarded risky call and ownership transfer via return
    res = _rl([FIXTURES / "resource_rl102_clean.py"])
    assert res.findings == []


def test_resource_rl103_bad_fixture():
    res = _rl([FIXTURES / "resource_rl103_bad.py"])
    assert _codes(res) == {"RL103"}
    assert "membership lease 'self.lease'" in res.findings[0].message


def test_resource_rl103_clean_fixture():
    # release reachable from close() through an intra-class call
    res = _rl([FIXTURES / "resource_rl103_clean.py"])
    assert res.findings == []


def test_resource_lifecycle_skips_test_files(tmp_path):
    tdir = tmp_path / "tests"
    tdir.mkdir()
    leaky = (FIXTURES / "resource_rl101_bad.py").read_text()
    p = tdir / "test_sockets.py"
    p.write_text(leaky)
    res = run([str(p)], select=["resource_lifecycle"])
    assert res.findings == []


# ------------------------------------- summary cache: cross-file invalidation

CT_CLIENT = """
    from paddle_tpu.inference.frontend.rpc import RpcClient


    def gateway(host, port):
        client = RpcClient(host, port)
        return client.call("resume", rid=1)
"""

CT_WORKER = """
    from paddle_tpu.inference.frontend.rpc import RpcServer


    class Worker:
        def serve(self):
            self.srv = RpcServer(self._handle)
            return self.srv

        def _handle(self, op, kw):
            if op == "submit":
                return kw["rid"]
            raise ValueError(f"unknown worker op {op!r}")
"""


def test_summary_cache_cross_file_invalidation(tmp_path):
    """Editing the dispatcher must re-lint the (unchanged) client file —
    the whole point of the per-domain digest deps, proven WITHOUT
    --no-cache."""
    client = tmp_path / "client.py"
    worker = tmp_path / "worker.py"
    client.write_text(textwrap.dedent(CT_CLIENT))
    worker.write_text(textwrap.dedent(CT_WORKER))
    cpath = str(tmp_path / "cache.json")
    r1 = run([str(client), str(worker)], select=["contracts"],
             cache=FileCache(cpath))
    errs = [f for f in r1.findings if f.severity == "error"]
    assert len(errs) == 1 and "'resume'" in errs[0].message
    assert errs[0].path == str(client)
    # add the missing arm to worker.py ONLY; client.py is byte-identical
    worker.write_text(textwrap.dedent(CT_WORKER).replace(
        'if op == "submit":', 'if op in ("submit", "resume"):'))
    r2 = run([str(client), str(worker)], select=["contracts"],
             cache=FileCache(cpath))
    assert not [f for f in r2.findings if f.severity == "error"]
    assert r2.cache_hits == 0            # rpc digest changed: both re-lint
    # replay: nothing changed, both files served from cache
    r3 = run([str(client), str(worker)], select=["contracts"],
             cache=FileCache(cpath))
    assert r3.cache_hits == 2
    assert [f.to_dict() for f in r3.findings] == \
           [f.to_dict() for f in r2.findings]


def test_summary_cache_unrelated_edit_replays(tmp_path):
    """Editing a file with no rpc/fault/metric facts must NOT re-lint the
    others: only its own entry invalidates."""
    client = tmp_path / "client.py"
    worker = tmp_path / "worker.py"
    other = tmp_path / "mathutil.py"
    client.write_text(textwrap.dedent(CT_CLIENT))
    worker.write_text(textwrap.dedent(CT_WORKER))
    other.write_text("def double(x):\n    return 2 * x\n")
    cpath = str(tmp_path / "cache.json")
    run([str(client), str(worker), str(other)], select=["contracts"],
        cache=FileCache(cpath))
    other.write_text("def double(x):\n    return x + x\n")
    r2 = run([str(client), str(worker), str(other)], select=["contracts"],
             cache=FileCache(cpath))
    assert r2.cache_hits == 2            # client+worker replay, other re-lints


def test_cli_version_lists_rule_ids(capsys):
    assert cli.main(["--version"]) == 0
    out = capsys.readouterr().out
    assert "concurrency" in out
    assert "CC101, CC102, CC103, CC104, CC105" in out


def test_every_pass_declares_rule_codes():
    for name, p in PASSES.items():
        assert p.codes, f"pass {name} declares no rule codes"
        assert all(c.isalnum() for c in p.codes)


# ------------------------------------------------------- baseline workflow

def test_baseline_absorbs_recorded_findings(tmp_path):
    res = _sharding([FIXTURES / "sharding_bad.py"])
    assert res.findings
    bpath = str(tmp_path / "base.json")
    assert Baseline.write(bpath, res.findings) == len(res.findings)
    res2 = run([str(FIXTURES / "sharding_bad.py")],
               select=["sharding-spec-coverage"],
               baseline=Baseline.load(bpath))
    assert res2.findings == [] and res2.baselined == len(res.findings)


def test_baseline_missing_file_is_empty():
    assert len(Baseline.load("/nonexistent/base.json")) == 0


def test_fingerprint_is_path_and_line_independent():
    a = Finding("p", "C1", "/abs/elsewhere/paddle_tpu/ops/x.py", 3, "m")
    b = Finding("p", "C1", "paddle_tpu/ops/x.py", 99, "m")
    c = Finding("p", "C1", "paddle_tpu/ops/x.py", 99, "other message")
    assert a.fingerprint() == b.fingerprint() != c.fingerprint()


def test_cli_baseline_workflow(tmp_path, capsys, monkeypatch):
    monkeypatch.syspath_prepend(str(FIXTURES))
    importlib.invalidate_caches()
    bpath = str(tmp_path / "base.json")
    assert cli.main([str(FIXTURES), "--no-cache",
                     "--write-baseline", bpath]) == 0
    capsys.readouterr()
    assert cli.main([str(FIXTURES), "--no-cache", "--baseline", bpath]) == 0
    assert "baselined" in capsys.readouterr().out


def test_cli_fail_on_warning(tmp_path, capsys, monkeypatch):
    # a registry whose only finding is the DT102 warning
    pkg = tmp_path / "warnonly_pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text(textwrap.dedent("""
        # graftlint: disable-file=registry-parity
        import numpy as np

        class OpSpec:
            def __init__(self, name, np_ref, sample):
                self.name, self.np_ref, self.sample = name, np_ref, sample
                self.kwargs, self.grad, self.kind = {}, False, "golden"
                self.category, self.check, self.alias_of = "math", None, None

            def resolve(self):
                return self.np_ref

        REGISTRY = {}

        def g(name, ref, sample, cat):
            REGISTRY[name] = OpSpec(name, ref, sample)

        g("wide", lambda x: np.vander(x), lambda: [np.ones(3, np.float32)],
          "math")
    """))
    monkeypatch.syspath_prepend(str(tmp_path))
    importlib.invalidate_caches()
    assert cli.main([str(pkg), "--no-cache"]) == 0
    capsys.readouterr()
    assert cli.main([str(pkg), "--no-cache", "--fail-on", "warning"]) == 1


# ----------------------------------------------------- framework: pragmas etc.

def test_line_pragma_suppresses(tmp_path):
    src = """
        import jax

        @jax.jit
        def f(x):
            if x > 0:  # graftlint: disable=trace-safety
                return x
            return -x
    """
    res = _lint(tmp_path, src, select=["trace-safety"])
    assert res.findings == [] and res.suppressed == 1


def test_file_pragma_suppresses_all(tmp_path):
    res = _lint(tmp_path, "# graftlint: disable-file=all\n"
                + textwrap.dedent(TS_BAD), select=["trace-safety"])
    assert res.findings == [] and res.suppressed >= 5


def test_pragma_is_pass_specific(tmp_path):
    src = """
        import jax

        @jax.jit
        def f(x, opts=[1]):  # graftlint: disable=trace-safety
            return x
    """
    res = _lint(tmp_path, src, select=["jit-cache-hygiene"])
    assert _codes(res) == {"JH001"}     # wrong pass name: not suppressed


def test_syntax_error_is_a_finding(tmp_path):
    res = _lint(tmp_path, "def broken(:\n")
    assert _codes(res) == {"GL000"}


def test_cache_replay_matches_fresh_run(tmp_path):
    p = tmp_path / "bad.py"
    p.write_text(textwrap.dedent(TS_BAD))
    cpath = str(tmp_path / "cache.json")
    r1 = run([str(p)], select=["trace-safety"], cache=FileCache(cpath))
    r2 = run([str(p)], select=["trace-safety"], cache=FileCache(cpath))
    assert r1.cache_hits == 0 and r2.cache_hits == 1
    assert [f.to_dict() for f in r1.findings] == \
           [f.to_dict() for f in r2.findings]
    # editing the file invalidates the entry
    p.write_text(textwrap.dedent(TS_BAD) + "\n# touched\n")
    r3 = run([str(p)], select=["trace-safety"], cache=FileCache(cpath))
    assert r3.cache_hits == 0


def test_cache_invalidated_on_pass_version_bump(tmp_path, monkeypatch):
    p = tmp_path / "bad.py"
    p.write_text(textwrap.dedent(TS_BAD))
    cpath = str(tmp_path / "cache.json")
    run([str(p)], select=["trace-safety"], cache=FileCache(cpath))
    r2 = run([str(p)], select=["trace-safety"], cache=FileCache(cpath))
    assert r2.cache_hits == 1
    ts = PASSES["trace-safety"]
    monkeypatch.setattr(ts, "version", ts.version + 1)
    r3 = run([str(p)], select=["trace-safety"], cache=FileCache(cpath))
    assert r3.cache_hits == 0
    assert [f.to_dict() for f in r3.findings] == \
           [f.to_dict() for f in r2.findings]


def test_finding_dict_round_trip():
    f = Finding("trace-safety", "TS101", "a.py", 3, "msg", "hint", "warning")
    assert Finding.from_dict(f.to_dict()) == f
    # pre-severity cache records default to error
    d = f.to_dict()
    del d["severity"]
    assert Finding.from_dict(d).severity == "error"


def test_builtin_passes_registered():
    assert {"trace-safety", "registry-parity", "namespace-parity",
            "jit-cache-hygiene", "no-adhoc-telemetry",
            "sharding-spec-coverage", "dtype-rules", "robustness",
            "concurrency", "contracts", "resource_lifecycle"} <= set(PASSES)


def test_unknown_pass_rejected(tmp_path):
    with pytest.raises(KeyError):
        _lint(tmp_path, "x = 1\n", select=["no-such-pass"])


# ----------------------------------------------------------------------- CLI

def test_cli_json_schema_and_exit_code(tmp_path, capsys):
    p = tmp_path / "bad.py"
    p.write_text(textwrap.dedent(TS_BAD))
    rc = cli.main([str(p), "--format", "json", "--no-cache"])
    data = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert data["graftlint"] == 1
    assert data["files"] == 1
    assert {f["code"] for f in data["findings"]} >= {"TS101", "TS105"}
    assert all({"pass", "code", "path", "line", "message", "hint"}
               <= set(f) for f in data["findings"])


def test_cli_clean_exit_zero(tmp_path, capsys):
    p = tmp_path / "ok.py"
    p.write_text("x = 1\n")
    assert cli.main([str(p), "--no-cache"]) == 0
    assert "OK:" in capsys.readouterr().out


def test_cli_unknown_pass_is_usage_error(tmp_path, capsys):
    p = tmp_path / "ok.py"
    p.write_text("x = 1\n")
    assert cli.main([str(p), "--select", "bogus", "--no-cache"]) == 2


def test_cli_list_passes(capsys):
    assert cli.main(["--list-passes"]) == 0
    out = capsys.readouterr().out
    assert "trace-safety" in out and "registry-parity" in out
    assert "sharding-spec-coverage" in out and "dtype-rules" in out
    assert "contracts" in out and "resource_lifecycle" in out
    assert "[summary]" in out            # summary-scope passes are tagged
    assert "CT101 CT102 CT103 CT104" in out
    assert "RL101 RL102 RL103" in out


def test_cli_explain_rule(capsys):
    assert cli.main(["--explain", "ct101"]) == 0    # case-insensitive
    out = capsys.readouterr().out
    assert "CT101 [contracts v" in out
    assert "severity:" in out
    assert "RPC op parity" in out
    # the committed fixture pair renders as the example
    assert "bad example" in out and "contracts_ct101_bad.py" in out
    assert "clean example" in out and "contracts_ct101_clean.py" in out


def test_cli_explain_every_declared_code(capsys):
    for p in PASSES.values():
        for code in p.codes:
            assert cli.main(["--explain", code]) == 0
    capsys.readouterr()


def test_cli_explain_unknown_code(capsys):
    assert cli.main(["--explain", "XX999"]) == 2
    assert "unknown rule code" in capsys.readouterr().err


def test_cli_sarif_output_valid(capsys, monkeypatch):
    monkeypatch.syspath_prepend(str(FIXTURES))
    importlib.invalidate_caches()
    rc = cli.main([str(FIXTURES), "--no-cache", "--format", "sarif"])
    data = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert data["version"] == "2.1.0"
    assert "sarif-schema-2.1.0" in data["$schema"]
    (sarif_run,) = data["runs"]
    driver = sarif_run["tool"]["driver"]
    assert driver["name"] == "graftlint"
    rule_ids = [r["id"] for r in driver["rules"]]
    assert rule_ids == sorted(rule_ids)
    # findings from the newer passes are present
    assert {"SS101", "SS104", "DT101", "DT102"} <= set(rule_ids)
    # every concurrency rule fires on its bad fixture
    assert {"CC101", "CC102", "CC103", "CC104", "CC105"} <= set(rule_ids)
    levels = set()
    for r in sarif_run["results"]:
        assert r["ruleId"] == rule_ids[r["ruleIndex"]]
        levels.add(r["level"])
        (loc,) = r["locations"]
        phys = loc["physicalLocation"]
        assert phys["artifactLocation"]["uri"]
        assert phys["region"]["startLine"] >= 1
        assert r["fingerprints"]["graftlint/v1"]
    assert {"error", "warning"} <= levels


def test_cli_json_reports_severity_and_baseline(capsys):
    rc = cli.main([str(FIXTURES / "sharding_bad.py"), "--no-cache",
                   "--format", "json"])
    data = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert "baselined" in data
    severities = {f["severity"] for f in data["findings"]}
    assert severities == {"error", "warning"}


# ------------------------------------------------------- repo self-check gate

def test_repo_tree_is_clean(tmp_path):
    """The tier-1 CI gate: every pass (the sharding/dtype ones included) must
    exit clean on paddle_tpu/ at error severity; the accepted warnings live
    in the committed baseline."""
    res = run([str(REPO / "paddle_tpu")],
              cache=FileCache(str(tmp_path / "cache.json")),
              baseline=Baseline.load(str(REPO / ".graftlint-baseline.json")))
    assert res.files > 100
    assert {"sharding-spec-coverage", "dtype-rules"} <= set(res.passes)
    assert not res.findings, "\n" + "\n".join(
        f.render() for f in res.findings)


def test_repo_cross_process_contracts_clean(tmp_path):
    """PR-20 gate: the contracts and resource-lifecycle passes must run
    clean — warnings included — over the package AND the top-level test
    files, because CT101/CT103 need both halves of each protocol (op sites
    and dispatcher arms, fault fires and injected(...) coverage) in view.
    Fixture files stay out: tests/*.py does not recurse."""
    paths = [str(REPO / "paddle_tpu")] + sorted(
        str(p) for p in (REPO / "tests").glob("*.py"))
    res = run(paths, select=["contracts", "resource_lifecycle"],
              cache=FileCache(str(tmp_path / "cache.json")))
    assert res.files > 200
    assert not res.findings, "\n" + "\n".join(
        f.render() for f in res.findings)
    # CT103's decl-side checks actually engaged: the declared table is
    # non-empty and chaos coverage exists in the analyzed tree
    from paddle_tpu.analysis.summaries import SummaryIndex
    from paddle_tpu.analysis.framework import (Project, SourceFile,
                                               iter_python_files)
    idx = SummaryIndex(Project(
        [SourceFile(p) for p in iter_python_files(paths)]))
    assert len(idx.declared_points) >= 19
    assert idx.declared_points <= idx.fault_coverage, (
        "declared fault points without injected(...) coverage: "
        f"{sorted(idx.declared_points - idx.fault_coverage)}")


# ------------------------------------------- engine package layering guard

def test_engine_package_has_no_import_cycles():
    """The engine package's layering (request < pages/runner/spec <
    scheduler < core < disagg) must stay acyclic, and ``request`` must stay
    at the bottom importing no siblings — a cycle here means the interface
    split regressed back toward the monolith."""
    import ast

    pkg = REPO / "paddle_tpu" / "inference" / "engine"
    deps = {}
    for path in sorted(pkg.glob("*.py")):
        mod = path.stem
        tree = ast.parse(path.read_text())
        sibs = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom) and node.level == 1:
                if node.module:                      # "from .x import y"
                    sibs.add(node.module.split(".")[0])
                else:                                # "from . import x"
                    sibs.update(a.name for a in node.names)
        deps[mod] = sibs - {mod}

    assert deps.get("request") == set(), (
        "engine.request must import no siblings (it is the layering floor)")

    state = {}   # mod -> "visiting" | "done"

    def visit(mod, stack):
        if state.get(mod) == "done" or mod not in deps:
            return
        assert state.get(mod) != "visiting", (
            f"import cycle in inference.engine: {' -> '.join(stack + [mod])}")
        state[mod] = "visiting"
        for dep in sorted(deps[mod]):
            visit(dep, stack + [mod])
        state[mod] = "done"

    for mod in sorted(deps):
        visit(mod, [])
