"""concurrency — lock-discipline analysis for the threaded serving fleet.

The serving front door is deeply multithreaded: per-replica step-loop
threads, RPC server threads, membership heartbeats, supervisor respawn
loops, journal pump threads, a ThreadingHTTPServer gateway.  Seventeen
modules hold ``threading.Lock``/``RLock``/``Condition`` objects, and the
bug classes that machinery breeds — a field guarded in one method and
naked in another, blocking I/O under a held lock, a missed ``notify``
ownership rule, two locks taken in opposite orders — are exactly the ones
unit tests miss until a chaos run hangs.  This pass infers each class's
lock discipline from the AST and enforces it:

  * **CC101** guarded-attribute race: an instance attribute written under
    ``with self._lock`` in one method but read/written with no lock held
    in another (``__init__``/``__new__`` exempt — the object is not shared
    yet).  Warning: lock-free reads of monotonic flags are sometimes
    deliberate; such sites carry a pragma saying why they are safe.
  * **CC102** blocking call while holding a lock: ``time.sleep`` (or an
    injectable ``sleep=time.sleep`` attribute), socket
    send/recv/accept/connect, ``os.fsync``, ``subprocess.*``,
    ``Thread.join`` on a thread attribute, and ``retry_call`` — resolved
    one call-hop deep through same-class helper methods, so ``with
    self._mu: self._flush()`` is caught when ``_flush`` fsyncs.  Warning:
    a lock whose express purpose is serializing the blocking channel
    (one-socket RPC clients, fsync-before-ack journals) is deliberate and
    carries a pragma.
  * **CC103** condition misuse: ``cv.wait()`` not inside a ``while`` loop
    re-checking its predicate (spurious wakeups and barging make an
    ``if``-guarded wait a race), or ``notify``/``notify_all`` outside the
    owning ``with cv`` (raises RuntimeError at runtime).  Error.
  * **CC104** lock-order inversion: a per-module acquisition graph (lock
    held while acquiring another → edge) with a cycle — A then B on one
    path, B then A on another — citing both sites.  Error.
  * **CC105** self-deadlock: a non-reentrant ``threading.Lock`` (or a
    ``Condition`` wrapping one) re-acquired along an intra-class call
    chain: ``with self._mu: self._helper()`` where ``_helper`` takes
    ``self._mu`` again.  Error.

Inference is class-scoped (the ISSUE's "which lock guards what" is a
per-object protocol) with two resolution aids shared by the rules: a
method whose every intra-class call site holds lock L is analyzed as if
it held L itself (private helpers documented "caller holds the lock"),
and call sites in ``__init__`` neither grant nor revoke that inheritance.
A ``@contextmanager`` method that acquires ``self.L`` before its ``yield``
and releases it after (a helper that counts the wait for the lock, say)
holds L for the body of every ``with self.helper(...)``.
Module-level locks (``_lock = threading.Lock()`` guarding a global
registry) participate in CC102/CC103/CC104.  Nested ``def``/``lambda``
bodies run later, possibly on another thread, so they never inherit the
lexically-enclosing held set.
"""
from __future__ import annotations

import ast

from ..framework import AnalysisPass, Finding, register_pass
from ..resolve import Imports

_CC101_HINT = ("take the guarding lock around this access, or mark a "
               "deliberately lock-free access (monotonic flag, "
               "snapshot-staleness-tolerant read) with a pragma saying why "
               "it is safe")

_CC102_HINT = ("move the blocking call outside the with block (snapshot "
               "state under the lock, do I/O after); a lock that exists to "
               "serialize the blocking channel carries a pragma saying so")

_CC103_WAIT_HINT = ("wrap the wait in `while not <predicate>:` — spurious "
                    "wakeups and lock barging mean one wakeup does not "
                    "imply the predicate holds")

_CC103_NOTIFY_HINT = ("notify only while holding the condition's lock "
                      "(inside `with cv:`); outside it raises RuntimeError")

_CC104_HINT = ("pick one global order for the two locks and acquire them "
               "in that order on every path (document it where the locks "
               "are constructed)")

_CC105_HINT = ("use threading.RLock when a lock must be re-entered on an "
               "intra-class call chain, or hoist the inner acquisition to "
               "the callers")

# threading constructors, by canonical dotted path (resolve.Imports sees
# through `import threading` / `from threading import Lock` / aliases)
_LOCK_CTORS = {"threading.Lock": False, "threading.RLock": True}
_CONDITION_CTOR = "threading.Condition"
_THREAD_CTOR = "threading.Thread"

# blocking socket operations, matched by method name on any receiver
_SOCK_METHODS = {"sendall", "recv", "recv_into", "accept", "connect"}

# container mutators: a call to one of these on `self.X` writes X's state
_MUTATORS = {"append", "appendleft", "extend", "add", "insert", "remove",
             "discard", "pop", "popleft", "clear", "update", "setdefault"}

_INIT_METHODS = ("__init__", "__new__")


class _Lock:
    """One inferred lock object: a class attribute or module global."""

    def __init__(self, key, display, reentrant, condition):
        self.key = key                # unique per module: "Cls.attr" / name
        self.display = display        # "self._mu" / "_lock"
        self.reentrant = reentrant
        self.condition = condition


def _lock_of_ctor(call, imports):
    """(reentrant, is_condition) when ``call`` constructs a lock, else
    None.  ``Condition()`` defaults to an RLock; ``Condition(Lock())`` is
    non-reentrant; a non-literal lock argument gets the benefit of the
    doubt (reentrant)."""
    canon = imports.canonical(call.func)
    if canon in _LOCK_CTORS:
        return _LOCK_CTORS[canon], False
    if canon == _CONDITION_CTOR:
        reentrant = True
        if call.args and isinstance(call.args[0], ast.Call):
            inner = imports.canonical(call.args[0].func)
            if inner in _LOCK_CTORS:
                reentrant = _LOCK_CTORS[inner]
        return reentrant, True
    return None


def _self_attr(node, selfname):
    """X when ``node`` is ``self.X`` (for this method's self name)."""
    if (selfname and isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name)
            and node.value.id == selfname):
        return node.attr
    return None


class _Method:
    def __init__(self, name, node, selfname):
        self.name = name
        self.node = node
        self.selfname = selfname
        # every descendant node -> (frozenset of held lock keys, nested?)
        self.ctx: dict[ast.AST, tuple[frozenset, bool]] = {}
        # lexical acquisitions: (lock key, line, held-before, nested?)
        self.acquisitions: list[tuple] = []
        self.inherited: frozenset = frozenset()

    def held(self, node):
        lex, _ = self.ctx.get(node, (frozenset(), False))
        return lex | self.inherited

    def nested(self, node):
        return self.ctx.get(node, (frozenset(), False))[1]


def _lock_holders(defs, class_locks):
    """{method name: lock attr} for the ``@contextmanager`` methods that
    take ``self.<lock>.acquire()`` and give it back with ``.release()``:
    ``with self.<method>(...)`` holds that lock for its body."""
    holders = {}
    for d in defs:
        if not any(getattr(x, "attr", getattr(x, "id", None))
                   == "contextmanager" for x in d.decorator_list):
            continue
        args = d.args.posonlyargs + d.args.args
        selfname = args[0].arg if args else None
        taken = {"acquire": set(), "release": set()}
        for node in ast.walk(d):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in taken):
                attr = _self_attr(node.func.value, selfname)
                if attr in class_locks:
                    taken[node.func.attr].add(attr)
        both = taken["acquire"] & taken["release"]
        if len(both) == 1:
            holders[d.name] = both.pop()
    return holders


def _collect(method, class_locks, module_locks, holders=None):
    """Populate ``method.ctx``/``method.acquisitions`` by walking the body
    with the lexically-held lock set threaded through ``with`` blocks."""
    selfname = method.selfname

    def lock_key(expr):
        if holders and isinstance(expr, ast.Call):
            helper = _self_attr(expr.func, selfname)
            if helper in holders:
                return class_locks[holders[helper]].key
        attr = _self_attr(expr, selfname)
        if attr is not None and attr in class_locks:
            return class_locks[attr].key
        if isinstance(expr, ast.Name) and expr.id in module_locks:
            return module_locks[expr.id].key
        return None

    def walk(node, held, nested):
        method.ctx[node] = (held, nested)
        if isinstance(node, (ast.With, ast.AsyncWith)):
            for item in node.items:
                walk(item.context_expr, held, nested)
                if item.optional_vars is not None:
                    walk(item.optional_vars, held, nested)
                key = lock_key(item.context_expr)
                if key is not None:
                    method.acquisitions.append(
                        (key, node.lineno, held, nested))
                    held = held | {key}
            for stmt in node.body:
                walk(stmt, held, nested)
            return
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "wait_for"):
            # a cv.wait_for(lambda: ...) predicate is the exception to the
            # nested-lambda rule: the condition re-acquires its lock around
            # every evaluation, so the predicate body runs with it held
            for child in ast.iter_child_nodes(node):
                if isinstance(child, ast.Lambda):
                    for sub in ast.walk(child):
                        method.ctx[sub] = (held, nested)
                else:
                    walk(child, held, nested)
            return
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            # a nested def/lambda body runs later, possibly on another
            # thread: it holds nothing, whatever encloses it lexically
            body = node.body if isinstance(node.body, list) else [node.body]
            for child in ast.iter_child_nodes(node):
                if child in body:
                    walk(child, frozenset(), True)
                else:
                    walk(child, held, nested)   # decorators/defaults: now
            return
        for child in ast.iter_child_nodes(node):
            walk(child, held, nested)

    for stmt in method.node.body:
        walk(stmt, frozenset(), False)


def _intra_calls(method, methods):
    """(callee name, call node) for every ``self.m(...)`` where ``m`` is a
    sibling method."""
    out = []
    for node in method.ctx:
        if isinstance(node, ast.Call):
            attr = _self_attr(node.func, method.selfname)
            if attr is not None and attr in methods:
                out.append((attr, node))
    return out


def _infer_inherited(methods, all_keys):
    """Greatest-fixpoint lock inheritance: a method whose every non-init
    intra-class call site holds L is analyzed as holding L ("caller holds
    the lock" helpers).  Methods with no such call sites inherit nothing —
    they are public entry points."""
    sites: dict[str, list] = {m: [] for m in methods}
    for caller in methods.values():
        if caller.name in _INIT_METHODS:
            continue
        for callee, node in _intra_calls(caller, methods):
            lex, nested = caller.ctx[node]
            if not nested:
                sites[callee].append((caller.name, lex))
    for m in methods.values():
        m.inherited = frozenset(all_keys) if sites[m.name] else frozenset()
    for _ in range(len(methods) + 1):
        changed = False
        for m in methods.values():
            if not sites[m.name]:
                continue
            new = frozenset(all_keys)
            for caller_name, lex in sites[m.name]:
                new &= lex | methods[caller_name].inherited
            if new != m.inherited:
                m.inherited = new
                changed = True
        if not changed:
            break
    return sites


def _in_loop(node, parents):
    """Is ``node`` lexically inside a while/for loop of its own def?"""
    cur = parents.get(node)
    while cur is not None:
        if isinstance(cur, (ast.While, ast.For, ast.AsyncFor)):
            return True
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.Lambda)):
            return False
        cur = parents.get(cur)
    return False


def _access_kind(node, parents):
    """'write' / 'read' for a ``self.X`` attribute node: stores, augmented
    assigns, subscript stores (``self.X[k] = v``) and container-mutator
    calls (``self.X.append(v)``) write; everything else reads."""
    if isinstance(node.ctx, (ast.Store, ast.Del)):
        return "write"
    parent = parents.get(node)
    if (isinstance(parent, ast.Subscript) and parent.value is node
            and isinstance(parent.ctx, (ast.Store, ast.Del))):
        return "write"
    if (isinstance(parent, ast.Attribute) and parent.value is node
            and parent.attr in _MUTATORS):
        grand = parents.get(parent)
        if isinstance(grand, ast.Call) and grand.func is parent:
            return "write"
    return "read"


def _sleep_attrs(cls_methods, imports):
    """Attributes bound from a parameter whose default is ``time.sleep``
    (the injectable-sleep idiom): calls through them block like
    ``time.sleep`` itself."""
    out = set()
    for m in cls_methods.values():
        args = m.node.args
        named = args.posonlyargs + args.args + args.kwonlyargs
        defaults = ([None] * (len(args.posonlyargs + args.args)
                              - len(args.defaults))
                    + list(args.defaults) + list(args.kw_defaults))
        sleepy = {a.arg for a, d in zip(named, defaults)
                  if d is not None and imports.canonical(d) == "time.sleep"}
        if not sleepy:
            continue
        for node in ast.walk(m.node):
            if (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.value, ast.Name)
                    and node.value.id in sleepy):
                attr = _self_attr(node.targets[0], m.selfname)
                if attr is not None:
                    out.add(attr)
    return out


def _blocking_desc(call, imports, selfname, sleep_attrs, thread_attrs):
    """Human-readable description when ``call`` is a known blocking
    operation, else None."""
    canon = imports.canonical(call.func)
    if canon == "time.sleep":
        return "time.sleep()"
    if canon == "os.fsync":
        return "os.fsync()"
    if canon == "socket.create_connection":
        return "socket.create_connection()"
    if canon and (canon == "subprocess" or canon.startswith("subprocess.")):
        return canon + "()"
    if canon and (canon == "retry_call" or canon.endswith(".retry_call")):
        return "retry_call() (sleeps through its backoff policy)"
    f = call.func
    if isinstance(f, ast.Attribute):
        if f.attr in _SOCK_METHODS:
            # module-level .connect()/.accept() of an imported non-socket
            # module (sqlite3.connect, ...) is an API call, not socket I/O
            recv = imports.canonical(f.value)
            if not (recv and recv != "socket"
                    and recv in set(imports.aliases.values())):
                return f"socket .{f.attr}()"
        if _self_attr(f, selfname) in sleep_attrs:
            return f"self.{f.attr}() (injectable sleep)"
        if f.attr == "join" and _self_attr(f.value, selfname) in thread_attrs:
            return f"self.{f.value.attr}.join()"
    return None


def _find_cycles(edges):
    """Cycles in the acquisition graph as node tuples, deduped by node
    set.  Graphs here are tiny (a handful of locks per module), so a plain
    DFS per node is fine."""
    adj: dict[str, list[str]] = {}
    for (a, b) in edges:
        adj.setdefault(a, []).append(b)
    cycles, seen = [], set()

    def dfs(start, node, path):
        for nxt in adj.get(node, ()):
            if nxt == start:
                key = frozenset(path)
                if key not in seen:
                    seen.add(key)
                    cycles.append(tuple(path))
            elif nxt not in path and nxt > start:
                # only walk nodes ordered after start: each cycle is
                # discovered exactly once, from its smallest node
                dfs(start, nxt, path + [nxt])

    for start in sorted(adj):
        dfs(start, start, [start])
    return cycles


@register_pass
class ConcurrencyPass(AnalysisPass):
    name = "concurrency"
    version = 2
    codes = ("CC101", "CC102", "CC103", "CC104", "CC105")
    description = ("lock discipline: guarded-attribute races (CC101), "
                   "blocking calls under a held lock (CC102), condition "
                   "wait/notify misuse (CC103), lock-order inversion "
                   "(CC104), non-reentrant self-deadlock (CC105)")

    def check_file(self, src) -> list[Finding]:
        from ..framework import Project
        imports = Imports(src.tree, Project.module_name(src.path))
        parents = {}
        for node in ast.walk(src.tree):
            for child in ast.iter_child_nodes(node):
                parents[child] = node

        # module-level locks: NAME = threading.Lock()/RLock()/Condition()
        module_locks: dict[str, _Lock] = {}
        for stmt in src.tree.body:
            if (isinstance(stmt, ast.Assign) and len(stmt.targets) == 1
                    and isinstance(stmt.targets[0], ast.Name)
                    and isinstance(stmt.value, ast.Call)):
                kind = _lock_of_ctor(stmt.value, imports)
                if kind is not None:
                    name = stmt.targets[0].id
                    module_locks[name] = _Lock(name, name, *kind)

        findings: list[Finding] = []
        edges: dict[tuple, tuple] = {}   # (a, b) -> (line, where)
        locks_by_key: dict[str, _Lock] = {l.key: l
                                          for l in module_locks.values()}

        for node in ast.walk(src.tree):
            if isinstance(node, ast.ClassDef):
                self._check_class(src, node, imports, parents, module_locks,
                                  locks_by_key, edges, findings)
        # module-level functions participate in CC102/CC103/CC104
        for stmt in src.tree.body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                m = _Method(stmt.name, stmt, None)
                _collect(m, {}, module_locks)
                self._check_blocking(src, m, {}, imports, set(), set(),
                                     locks_by_key, findings)
                self._check_conditions(src, m, {}, module_locks, parents,
                                       locks_by_key, findings)
                for key, line, held, nested in m.acquisitions:
                    if nested:
                        continue
                    for h in held:
                        edges.setdefault((h, key), (line, stmt.name))

        for cyc in _find_cycles(set(edges)):
            cites = []
            for i, a in enumerate(cyc):
                b = cyc[(i + 1) % len(cyc)]
                line, where = edges[(a, b)]
                cites.append((line, where, a, b))
            first = min(cites)
            order = " -> ".join(locks_by_key[k].display for k in cyc)
            sites = "; ".join(
                f"{locks_by_key[a].display} then {locks_by_key[b].display} "
                f"in {where}()" for line, where, a, b in cites)
            findings.append(Finding(
                self.name, "CC104", src.path, first[0],
                f"lock-order inversion: cycle {order} -> "
                f"{locks_by_key[cyc[0]].display} ({sites}) — two threads "
                f"taking these paths concurrently deadlock",
                _CC104_HINT, severity="error"))
        findings.sort(key=lambda f: (f.line, f.code))
        return findings

    # ---- per-class analysis --------------------------------------------------
    def _check_class(self, src, cls, imports, parents, module_locks,
                     locks_by_key, edges, findings):
        defs = [n for n in cls.body
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
        methods: dict[str, _Method] = {}
        class_locks: dict[str, _Lock] = {}
        thread_attrs: set[str] = set()

        # class-body lock attributes: _lock = threading.Lock()
        for stmt in cls.body:
            if (isinstance(stmt, ast.Assign) and len(stmt.targets) == 1
                    and isinstance(stmt.targets[0], ast.Name)
                    and isinstance(stmt.value, ast.Call)):
                kind = _lock_of_ctor(stmt.value, imports)
                if kind is not None:
                    attr = stmt.targets[0].id
                    class_locks[attr] = _Lock(f"{cls.name}.{attr}",
                                              f"self.{attr}", *kind)
        for d in defs:
            deco = {getattr(x, "id", None) for x in d.decorator_list}
            args = d.args.posonlyargs + d.args.args
            selfname = (args[0].arg if args and "staticmethod" not in deco
                        else None)
            methods[d.name] = _Method(d.name, d, selfname)
            for node in ast.walk(d):
                if (isinstance(node, ast.Assign) and len(node.targets) == 1
                        and isinstance(node.value, ast.Call)):
                    attr = _self_attr(node.targets[0], selfname)
                    if attr is None:
                        continue
                    kind = _lock_of_ctor(node.value, imports)
                    if kind is not None:
                        class_locks[attr] = _Lock(f"{cls.name}.{attr}",
                                                  f"self.{attr}", *kind)
                    elif imports.canonical(node.value.func) == _THREAD_CTOR:
                        thread_attrs.add(attr)
        if not class_locks:
            return
        locks_by_key.update({l.key: l for l in class_locks.values()})
        class_keys = {l.key for l in class_locks.values()}
        holders = _lock_holders(defs, class_locks)
        for m in methods.values():
            _collect(m, class_locks, module_locks, holders)
        _infer_inherited(methods, class_keys)
        sleep_attrs = _sleep_attrs(methods, imports)

        self._check_guarded_attrs(src, cls, methods, class_locks, class_keys,
                                  parents, locks_by_key, findings)
        for m in methods.values():
            self._check_blocking(src, m, methods, imports, sleep_attrs,
                                 thread_attrs, locks_by_key, findings)
            self._check_conditions(src, m, class_locks, module_locks,
                                   parents, locks_by_key, findings)
        self._check_self_deadlock(src, cls, methods, class_locks,
                                  locks_by_key, findings)
        for m in methods.values():
            for key, line, held, nested in m.acquisitions:
                if nested:
                    continue
                for h in held | (m.inherited - {key}):
                    if h != key:
                        edges.setdefault((h, key), (line, m.name))
            # one hop: holding L while calling a sibling that acquires K
            for callee, node in _intra_calls(m, methods):
                held = m.held(node)
                if not held or m.nested(node):
                    continue
                for key, line, _, nested in methods[callee].acquisitions:
                    if nested:
                        continue
                    for h in held:
                        if h != key:
                            edges.setdefault((h, key), (node.lineno, m.name))

    # ---- CC101 ---------------------------------------------------------------
    def _check_guarded_attrs(self, src, cls, methods, class_locks,
                             class_keys, parents, locks_by_key, findings):
        guarded: dict[str, set] = {}     # attr -> guarding lock keys
        accesses = []                    # (attr, method, kind, line, locked)
        for m in methods.values():
            if m.name in _INIT_METHODS or m.selfname is None:
                continue
            for node in m.ctx:
                attr = _self_attr(node, m.selfname)
                if attr is None or attr in class_locks:
                    continue
                kind = _access_kind(node, parents)
                locked = m.held(node) & class_keys
                if kind == "write" and locked:
                    guarded.setdefault(attr, set()).update(locked)
                accesses.append((attr, m.name, kind, node.lineno,
                                 bool(locked)))
        reported = set()
        for attr, mname, kind, line, locked in sorted(
                accesses, key=lambda a: a[3]):
            if locked or attr not in guarded or (attr, mname) in reported:
                continue
            reported.add((attr, mname))
            guards = ", ".join(sorted(locks_by_key[k].display
                                      for k in guarded[attr]))
            verb = "written" if kind == "write" else "read"
            findings.append(Finding(
                self.name, "CC101", src.path, line,
                f"{cls.name}.{attr} is written under {guards} elsewhere "
                f"but {verb} with no lock held in {mname}()",
                _CC101_HINT, severity="warning"))

    # ---- CC102 ---------------------------------------------------------------
    def _check_blocking(self, src, m, methods, imports, sleep_attrs,
                        thread_attrs, locks_by_key, findings):
        def direct_sites(method):
            out = []
            for node in method.ctx:
                if isinstance(node, ast.Call) and not method.nested(node):
                    desc = _blocking_desc(node, imports, method.selfname,
                                          sleep_attrs, thread_attrs)
                    if desc is not None:
                        out.append(desc)
            return out

        for node in m.ctx:
            if not isinstance(node, ast.Call) or m.nested(node):
                continue
            held, _ = m.ctx[node]          # lexical only: helpers called
            if not held:                   # under a lock are flagged at
                continue                   # their call site, one hop deep
            locks = ", ".join(sorted(locks_by_key[k].display for k in held))
            desc = _blocking_desc(node, imports, m.selfname, sleep_attrs,
                                  thread_attrs)
            callee = _self_attr(node.func, m.selfname)
            if desc is None and callee in methods and callee != m.name:
                inner = direct_sites(methods[callee])
                if inner:
                    desc = f"self.{callee}() which does {inner[0]}"
            if desc is not None:
                findings.append(Finding(
                    self.name, "CC102", src.path, node.lineno,
                    f"blocking {desc} while holding {locks} in {m.name}() "
                    f"— every thread contending on the lock stalls behind "
                    f"this call",
                    _CC102_HINT, severity="warning"))

    # ---- CC103 ---------------------------------------------------------------
    def _check_conditions(self, src, m, class_locks, module_locks, parents,
                          locks_by_key, findings):
        conds = {l.key: l for l in class_locks.values() if l.condition}
        conds.update({l.key: l for l in module_locks.values()
                      if l.condition})

        def cond_key(expr):
            attr = _self_attr(expr, m.selfname)
            if attr is not None and attr in class_locks \
                    and class_locks[attr].condition:
                return class_locks[attr].key
            if (isinstance(expr, ast.Name) and expr.id in module_locks
                    and module_locks[expr.id].condition):
                return module_locks[expr.id].key
            return None

        for node in m.ctx:
            if not isinstance(node, ast.Call) \
                    or not isinstance(node.func, ast.Attribute):
                continue
            key = cond_key(node.func.value)
            if key is None:
                continue
            disp = locks_by_key[key].display
            if node.func.attr == "wait" and not _in_loop(node, parents):
                findings.append(Finding(
                    self.name, "CC103", src.path, node.lineno,
                    f"{disp}.wait() in {m.name}() is not inside a while "
                    f"loop re-checking its predicate — spurious wakeups "
                    f"and lock barging make a single wakeup meaningless",
                    _CC103_WAIT_HINT, severity="error"))
            elif node.func.attr in ("notify", "notify_all") \
                    and key not in m.held(node):
                findings.append(Finding(
                    self.name, "CC103", src.path, node.lineno,
                    f"{disp}.{node.func.attr}() in {m.name}() outside "
                    f"`with {disp}:` — notifying without owning the "
                    f"condition's lock raises RuntimeError",
                    _CC103_NOTIFY_HINT, severity="error"))

    # ---- CC105 ---------------------------------------------------------------
    def _check_self_deadlock(self, src, cls, methods, class_locks,
                             locks_by_key, findings):
        nonreentrant = {l.key for l in class_locks.values()
                        if not l.reentrant}
        if not nonreentrant:
            return
        acq: dict[str, frozenset] = {
            name: frozenset(k for k, _, _, nested in m.acquisitions
                            if not nested)
            for name, m in methods.items()}
        for _ in range(len(methods) + 1):     # transitive closure
            changed = False
            for m in methods.values():
                new = acq[m.name]
                for callee, node in _intra_calls(m, methods):
                    if not m.nested(node):
                        new = new | acq[callee]
                if new != acq[m.name]:
                    acq[m.name] = new
                    changed = True
            if not changed:
                break
        for m in methods.values():
            for key, line, held, nested in m.acquisitions:
                if not nested and key in held and key in nonreentrant:
                    findings.append(Finding(
                        self.name, "CC105", src.path, line,
                        f"non-reentrant {locks_by_key[key].display} "
                        f"re-acquired in a nested with in {m.name}() — "
                        f"deadlocks immediately",
                        _CC105_HINT, severity="error"))
            for callee, node in _intra_calls(m, methods):
                if m.nested(node):
                    continue
                again = m.held(node) & nonreentrant & acq[callee]
                for key in sorted(again):
                    findings.append(Finding(
                        self.name, "CC105", src.path, node.lineno,
                        f"self-deadlock: {m.name}() holds non-reentrant "
                        f"{locks_by_key[key].display} and calls "
                        f"self.{callee}(), which acquires it again",
                        _CC105_HINT, severity="error"))
