"""lock-order & blocking-under-lock checker.

Builds the static lock graph across every module that constructs a
``threading.Lock``/``RLock``/``Condition`` and reports:

  (a) inconsistent acquisition order — lock A taken while holding B in
      one place and B taken while holding A in another (the classic ABBA
      deadlock shape).  Edges come from lexical ``with``-nesting, plus
      one level of intra-module calls (a call under lock L to a local
      function that acquires M contributes L->M) and a small table of
      known cross-module acquirers (the shuffle counters);
  (b) re-acquisition of a non-reentrant Lock already held on the same
      lexical path (self-deadlock);
  (c) blocking calls while holding a lock: socket IO, subprocess spawn,
      sleeps, file-system IO, device syncs, future waits.  One thread
      stalled in IO under a hot lock (the connection pool, the file
      cache, the spill framework) stalls every other thread that needs
      it — the exact failure mode the reference avoids by keeping its
      send/receive bounce-buffer work outside the transport locks.

``cond.wait()`` on the condition currently held is exempt (wait releases
the lock); so is everything under an explicit
``# tpu-lint: allow-lock-order(reason)``.
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from tools.tpulint.core import ScopedVisitor, SourceFile, Violation, dotted

RULE = "lock-order"

LOCK_CTORS = {"Lock", "RLock", "Condition", "BoundedSemaphore", "Semaphore",
              "MaterializeLock"}
REENTRANT_CTORS = {"RLock"}
#: semaphores bound concurrency rather than guard invariants: they appear
#: as graph nodes but blocking calls under them are expected (that's their
#: job) and are not reported
THROTTLE_CTORS = {"BoundedSemaphore", "Semaphore"}
#: blocking calls under these are not reported.  plan/execs/base.py's
#: materialise-once lock is held across a child's whole execution by
#: design and its waiters hold no device permit; order edges and
#: self-deadlock still apply to it
BLOCKING_EXPECTED_CTORS = THROTTLE_CTORS | {"MaterializeLock"}

#: dotted-suffix -> blocking category
BLOCKING_SUFFIXES = {
    "socket.create_connection": "socket connect",
    ".sendall": "socket send",
    ".recv": "socket recv",
    ".recv_into": "socket recv",
    ".accept": "socket accept",
    ".connect": "socket connect",
    "subprocess.Popen": "subprocess spawn",
    "subprocess.run": "subprocess spawn",
    "subprocess.check_output": "subprocess spawn",
    "subprocess.check_call": "subprocess spawn",
    "time.sleep": "sleep",
    "os.stat": "filesystem IO",
    "os.listdir": "filesystem IO",
    "os.remove": "filesystem IO",
    "os.replace": "filesystem IO",
    "os.utime": "filesystem IO",
    "os.makedirs": "filesystem IO",
    "os.path.exists": "filesystem IO",
    "shutil.copyfile": "filesystem IO",
    "shutil.rmtree": "filesystem IO",
    ".get_file": "remote IO",
    "jax.device_get": "device sync",
    ".block_until_ready": "device sync",
    ".result": "future wait",
}

#: calls that acquire a lock in ANOTHER module (dotted suffix -> lock id)
EXTERNAL_ACQUIRERS = {
    "SHUFFLE_COUNTERS.add": "shuffle/stats.ShuffleCounters._lock",
    "SHUFFLE_COUNTERS.snapshot": "shuffle/stats.ShuffleCounters._lock",
    "SHUFFLE_COUNTERS.reset": "shuffle/stats.ShuffleCounters._lock",
}


def _modbase(path: str) -> str:
    # spark_rapids_tpu/shuffle/net.py -> shuffle/net
    p = path
    if p.startswith("spark_rapids_tpu/"):
        p = p[len("spark_rapids_tpu/"):]
    return p[:-3] if p.endswith(".py") else p


class _LockTable(ScopedVisitor):
    """First pass: find lock constructions -> (lock id, ctor kind)."""

    def __init__(self, src: SourceFile):
        super().__init__()
        self.src = src
        self.mod = _modbase(src.path)
        #: bare attr/var name -> (lock_id, ctor)
        self.module_locks: Dict[str, Tuple[str, str]] = {}
        self.class_locks: Dict[Tuple[str, str], Tuple[str, str]] = {}

    def visit_Assign(self, node: ast.Assign):
        value = node.value
        if isinstance(value, ast.Call):
            name = dotted(value.func)
            ctor = name.rsplit(".", 1)[-1]
            if ctor in LOCK_CTORS and (
                    name.startswith("threading.") or "." not in name
                    or name.startswith("_threading.")):
                for tgt in node.targets:
                    self._bind(tgt, ctor)
        self.generic_visit(node)

    def _bind(self, tgt: ast.AST, ctor: str) -> None:
        if isinstance(tgt, ast.Name):
            scope = self.scope
            if scope == "<module>":
                self.module_locks[tgt.id] = (
                    f"{self.mod}.{tgt.id}", ctor)
            else:
                # function-local lock (e.g. the fetch iterator's cv)
                self.module_locks.setdefault(
                    tgt.id, (f"{self.mod}.{scope}.{tgt.id}", ctor))
        elif isinstance(tgt, ast.Attribute) and \
                isinstance(tgt.value, ast.Name) and tgt.value.id == "self":
            cls = self.scope.split(".")[0] if self.scope != "<module>" \
                else "<module>"
            self.class_locks[(cls, tgt.attr)] = (
                f"{self.mod}.{cls}.{tgt.attr}", ctor)


class _Analyzer(ScopedVisitor):
    """Second pass: walk with a held-locks stack; collect order edges and
    blocking-call sites."""

    def __init__(self, src: SourceFile, table: _LockTable,
                 fn_acquires: Dict[str, Set[Tuple[str, str]]],
                 fn_blocking: Optional[Dict[str, list]] = None):
        super().__init__()
        self.src = src
        self.table = table
        self.fn_acquires = fn_acquires
        self.fn_blocking = fn_blocking or {}
        self.held: List[Tuple[str, str]] = []   # (lock_id, ctor)
        #: parameter names of the enclosing defs (callback detection)
        self.param_stack: List[Set[str]] = []
        # (outer_id, inner_id) -> (file, line)
        self.edges: Dict[Tuple[str, str], Tuple[str, int]] = {}
        self.out: List[Violation] = []

    def _visit_def(self, node):
        args = node.args
        params = {a.arg for a in args.args + args.kwonlyargs
                  + args.posonlyargs}
        self.param_stack.append(params)
        held = len(self.held)
        ScopedVisitor._visit_def(self, node)
        # an explicit .acquire() holds to the end of ITS function only
        del self.held[held:]
        self.param_stack.pop()

    visit_FunctionDef = _visit_def
    visit_AsyncFunctionDef = _visit_def

    # -- lock resolution -----------------------------------------------------

    def resolve(self, expr: ast.AST) -> Optional[Tuple[str, str]]:
        if isinstance(expr, ast.Name):
            hit = self.table.module_locks.get(expr.id)
            return hit
        if isinstance(expr, ast.Attribute) and \
                isinstance(expr.value, ast.Name) and expr.value.id == "self":
            cls = self.scope.split(".")[0] if self.scope != "<module>" \
                else "<module>"
            hit = self.table.class_locks.get((cls, expr.attr))
            if hit is None:
                # self._lock defined in another class of this module (or a
                # base class): fall back to any class defining that attr
                for (c, a), v in self.table.class_locks.items():
                    if a == expr.attr:
                        return v
            return hit
        return None

    # -- traversal -----------------------------------------------------------

    def visit_With(self, node: ast.With):
        acquired: List[Tuple[str, str]] = []
        for item in node.items:
            ctx = item.context_expr
            # `with lock:` or `with lock.acquire_timeout(..)`-style wrappers
            target = ctx
            if isinstance(ctx, ast.Call):
                target = ctx.func
                if isinstance(target, ast.Attribute):
                    target = target.value
            hit = self.resolve(target)
            if hit is not None:
                self._acquire(hit, node.lineno)
                acquired.append(hit)
            self.visit(ctx)
        for stmt in node.body:
            self.visit(stmt)
        for _ in acquired:
            self.held.pop()

    def _acquire(self, lock: Tuple[str, str], line: int) -> None:
        lock_id, ctor = lock
        for held_id, held_ctor in self.held:
            if held_id == lock_id and ctor not in REENTRANT_CTORS \
                    and ctor not in THROTTLE_CTORS:
                self.out.append(Violation(
                    RULE, self.src.path, line, self.scope,
                    f"non-reentrant lock {lock_id} re-acquired while "
                    f"already held (self-deadlock)"))
            elif held_id != lock_id:
                self.edges.setdefault((held_id, lock_id),
                                      (self.src.path, line))
        self.held.append(lock)

    def visit_Call(self, node: ast.Call):
        name = dotted(node.func)
        bare = name.rsplit(".", 1)[-1]
        # explicit .acquire() counts as taking the lock for the rest of
        # the function (approximate: we don't track release())
        # (a try-lock, blocking=False, never waits: not an acquisition)
        if bare == "acquire" and isinstance(node.func, ast.Attribute) \
                and not any(k.arg == "blocking"
                            and getattr(k.value, "value", None) is False
                            for k in node.keywords):
            hit = self.resolve(node.func.value)
            if hit is not None:
                self._acquire(hit, node.lineno)
        if self.held:
            self._check_blocking(node, name)
            self._check_external(node, name)
            self._check_local_calls(node, name)
            self._check_callback(node, name)
        self.generic_visit(node)

    def _check_callback(self, node: ast.Call, name: str) -> None:
        """A function-valued PARAMETER invoked under a lock: the callee is
        opaque to this analysis and, in practice (PooledConnection's
        send/recv thunks), it's the blocking IO itself."""
        held = self._innermost_real_lock()
        if held is None or "." in name:
            return
        if self.param_stack and name in self.param_stack[-1]:
            self.out.append(Violation(
                RULE, self.src.path, node.lineno, self.scope,
                f"callback parameter '{name}' invoked while holding "
                f"{held[0]}; an opaque callback under a lock can block "
                f"every other holder"))

    def _innermost_real_lock(self) -> Optional[Tuple[str, str]]:
        for lock_id, ctor in reversed(self.held):
            if ctor not in BLOCKING_EXPECTED_CTORS:
                return lock_id, ctor
        return None

    def _check_blocking(self, node: ast.Call, name: str) -> None:
        held = self._innermost_real_lock()
        if held is None:
            return
        held_id, held_ctor = held
        category = None
        for suffix, cat in BLOCKING_SUFFIXES.items():
            if name == suffix or name.endswith(suffix):
                category = cat
                break
        if name == "open" or name.endswith(".open"):
            category = "filesystem IO"
        if category is None:
            return
        # cond.wait() on the held condition releases it — exempt; same
        # for wait() in general, which is only meaningful on conditions
        if name.endswith(".wait"):
            return
        self.out.append(Violation(
            RULE, self.src.path, node.lineno, self.scope,
            f"{category} ({name}) while holding {held_id}; move the "
            f"blocking call outside the critical section"))

    def _check_external(self, node: ast.Call, name: str) -> None:
        for suffix, lock_id in EXTERNAL_ACQUIRERS.items():
            if name == suffix or name.endswith("." + suffix):
                for held_id, ctor in self.held:
                    if ctor in THROTTLE_CTORS or held_id == lock_id:
                        continue
                    self.edges.setdefault((held_id, lock_id),
                                          (self.src.path, node.lineno))

    def _check_local_calls(self, node: ast.Call, name: str) -> None:
        # only `self.x()` and bare-name calls resolve to module-local
        # functions; `anything.get()` matching dict.get by bare name was
        # the checker's worst false-positive source
        if "." in name and not name.startswith("self."):
            return
        if name.startswith("self.") and name.count(".") > 1:
            return
        bare = name.rsplit(".", 1)[-1]
        for lock in self.fn_acquires.get(bare, ()):
            for held_id, ctor in self.held:
                if ctor in THROTTLE_CTORS or held_id == lock[0]:
                    continue
                self.edges.setdefault((held_id, lock[0]),
                                      (self.src.path, node.lineno))
        held = self._innermost_real_lock()
        if held is not None:
            for line, category, blocked in self.fn_blocking.get(bare, ()):
                self.out.append(Violation(
                    RULE, self.src.path, node.lineno, self.scope,
                    f"{category} ({blocked}, via {bare}) while holding "
                    f"{held[0]}; move the blocking call outside the "
                    f"critical section"))


def _function_acquisitions(src: SourceFile, table: _LockTable) -> \
        Dict[str, Set[Tuple[str, str]]]:
    """bare function name -> set of locks its body acquires lexically."""
    out: Dict[str, Set[Tuple[str, str]]] = {}

    class V(ScopedVisitor):
        def _visit_def(self, node):
            locks: Set[Tuple[str, str]] = set()
            resolver = _Analyzer(src, table, {})
            resolver._names = list(self._names) + [node.name]
            for sub in ast.walk(node):
                if isinstance(sub, ast.With):
                    for item in sub.items:
                        hit = resolver.resolve(item.context_expr)
                        if hit is not None:
                            locks.add(hit)
            if locks:
                out.setdefault(node.name, set()).update(locks)
            ScopedVisitor._visit_def(self, node)

        visit_FunctionDef = _visit_def
        visit_AsyncFunctionDef = _visit_def

    V().visit(src.tree)
    return out


def _function_blocking(src: SourceFile) -> Dict[str, list]:
    """bare def name -> [(line, category, dotted name)] — one
    representative blocking call per callee, for one-level
    interprocedural 'blocking via self.x()' reporting."""
    out: Dict[str, list] = {}

    class V(ast.NodeVisitor):
        def _visit_def(self, node):
            for sub in ast.walk(node):
                if not isinstance(sub, ast.Call):
                    continue
                name = dotted(sub.func)
                for suffix, cat in BLOCKING_SUFFIXES.items():
                    if name == suffix or name.endswith(suffix):
                        out.setdefault(node.name, []).append(
                            (sub.lineno, cat, name))
                        break
                if node.name in out:
                    break
            self.generic_visit(node)

        visit_FunctionDef = _visit_def
        visit_AsyncFunctionDef = _visit_def

    V().visit(src.tree)
    return out


def check(sources: List[SourceFile]) -> List[Violation]:
    out: List[Violation] = []
    all_edges: Dict[Tuple[str, str], Tuple[str, int]] = {}
    for src in sources:
        if not src.path.startswith("spark_rapids_tpu/"):
            continue
        table = _LockTable(src)
        table.visit(src.tree)
        if not table.module_locks and not table.class_locks:
            continue
        fn_acquires = _function_acquisitions(src, table)
        analyzer = _Analyzer(src, table, fn_acquires,
                             _function_blocking(src))
        analyzer.visit(src.tree)
        out.extend(analyzer.out)
        for edge, site in analyzer.edges.items():
            all_edges.setdefault(edge, site)

    reported: Set[frozenset] = set()
    for (a, b), (path, line) in sorted(all_edges.items()):
        if (b, a) in all_edges:
            pair = frozenset((a, b))
            if pair in reported:
                continue
            reported.add(pair)
            other_path, _other_line = all_edges[(b, a)]
            first, second = sorted((a, b))
            # no line numbers in the message: it feeds the baseline
            # fingerprint and must survive unrelated edits
            out.append(Violation(
                RULE, path, line, "<module>",
                f"inconsistent lock order between {first} and {second}: "
                f"{a} -> {b} here, {b} -> {a} in {other_path}"))
    return out
