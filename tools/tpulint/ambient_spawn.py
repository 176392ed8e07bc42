"""ambient-propagation checker (flow-sensitive).

A worker thread spawned on behalf of a running query must inherit the
thread-ambient context -- tenant scope, task priority, CancelToken
(utils/ambient.py docstring; PR 10's hand-plumbed producer ambients are
the motivating defect).  The blessed spawn points are
``utils/ambient.spawn_with_ambients`` / ``submit_with_ambients`` (or an
explicit ``Ambients.capture()`` + ``bind``).

Flagged: any bare ``threading.Thread(target=...)`` or thread-pool
``.submit(fn, ...)`` whose target can TRANSITIVELY reach
engine/shuffle/memory code, judged over the same-module call summaries
(cfg.build_module_info):

  * the target resolves to a same-module def/lambda (dynamic targets
    like ``server.serve_forever`` are outside the rule's reach);
  * reachability walks same-module calls from the target; a function is
    engine-reaching when it references a name imported from the engine
    packages (plan/shuffle/memory/kernels/parallel/io/serving/cluster/
    expressions/columnar/planner/api) or calls an opaque function-typed
    PARAMETER (a callback the rule cannot see through -- assumed
    engine-reaching, the same conservatism the lock rule applies to
    callbacks under a lock);
  * pool receivers are recognized by provenance, not just name: locals
    and ``self.<attr>`` assigned from ``ThreadPoolExecutor(...)``
    anywhere in the module, results of same-module helpers that return
    one, and receivers whose name mentions pool/executor.

Maintenance daemons that deliberately run ambient-free (the watchdog
scanner, the profiler sampler) either never reach engine code or carry
a reasoned inline suppression.
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set

from tools.tpulint.cfg import ModuleInfo, cached_module_info
from tools.tpulint.core import ScopedVisitor, SourceFile, Violation, dotted

RULE = "ambient-propagation"

ENGINE_PKGS = {
    "plan", "shuffle", "memory", "kernels", "expressions", "parallel",
    "serving", "cluster", "io", "planner", "columnar", "api",
}

#: the blessed implementation itself.  Calls to spawn_with_ambients /
#: submit_with_ambients are inherently unflagged: they are neither a
#: Thread construction nor a pool .submit.
EXEMPT_FILES = {"spark_rapids_tpu/utils/ambient.py"}


def _engine_module(mod: str) -> bool:
    parts = mod.split(".")
    if parts[0] == "spark_rapids_tpu":
        parts = parts[1:]
    return bool(parts) and parts[0] in ENGINE_PKGS


def _engine_imported_names(info: ModuleInfo) -> Set[str]:
    return {name for name, mod in info.imports.items()
            if _engine_module(mod)}


def _engine_reaching(info: ModuleInfo, root_qual: str,
                     engine_names: Set[str]) -> Optional[str]:
    """Why the function (or a same-module callee) reaches engine code:
    a short reason string, or None when provably infra-only."""
    seen: Set[str] = set()
    work = [root_qual]
    while work:
        q = work.pop()
        if q in seen:
            continue
        seen.add(q)
        fi = info.functions.get(q)
        if fi is None:
            continue
        hit = fi.refs & engine_names
        if hit:
            return f"references engine import {sorted(hit)[0]!r}"
        if fi.calls_param:
            return "invokes an opaque callback parameter"
        # follow same-module calls: bare names and self-method attrs
        for name in fi.refs | fi.called_attrs:
            for callee in info.defs_by_name.get(name, ()):
                if callee not in seen:
                    work.append(callee)
    return None


def _pool_provenance(info: ModuleInfo, tree: ast.AST) -> Set[str]:
    """Receiver texts known to hold a ThreadPoolExecutor: assignment
    targets of ``ThreadPoolExecutor(...)`` (locals and self attrs, plus
    ``with ThreadPoolExecutor(...) as p``) and same-module functions
    that return one."""
    pools: Set[str] = set()
    pool_returning_defs: Set[str] = set()

    def is_pool_ctor(v) -> bool:
        return isinstance(v, ast.Call) and \
            dotted(v.func).rsplit(".", 1)[-1] == "ThreadPoolExecutor"

    for sub in ast.walk(tree):
        if isinstance(sub, ast.Assign) and is_pool_ctor(sub.value):
            for t in sub.targets:
                name = dotted(t)
                if name:
                    pools.add(name)
        elif isinstance(sub, (ast.With, ast.AsyncWith)):
            for item in sub.items:
                if is_pool_ctor(item.context_expr) and \
                        item.optional_vars is not None:
                    name = dotted(item.optional_vars)
                    if name:
                        pools.add(name)
        elif isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for s2 in ast.walk(sub):
                if isinstance(s2, ast.Return) and s2.value is not None:
                    rname = dotted(s2.value)
                    if is_pool_ctor(s2.value) or \
                            (rname and rname in pools) or \
                            (rname and rname.startswith("_POOL")):
                        pool_returning_defs.add(sub.name)
    return pools | {f"{d}()" for d in pool_returning_defs}


class _SpawnIndex(ScopedVisitor):
    """Collect Thread(...) constructions and pool .submit(...) calls."""

    def __init__(self, pools: Set[str]):
        super().__init__()
        self.pools = pools
        self.hits: List[dict] = []

    def _target_expr(self, call: ast.Call, kind: str):
        if kind == "thread":
            for kw in call.keywords:
                if kw.arg == "target":
                    return kw.value
            return call.args[0] if call.args else None
        return call.args[0] if call.args else None

    def visit_Call(self, node: ast.Call):
        name = dotted(node.func)
        bare = name.rsplit(".", 1)[-1]
        if bare == "Thread" and ("threading" in name or name == "Thread"):
            self.hits.append({"node": node, "kind": "thread",
                              "scope": self.scope, "line": node.lineno,
                              "target": self._target_expr(node, "thread")})
        elif isinstance(node.func, ast.Attribute) and \
                node.func.attr == "submit":
            recv = dotted(node.func.value)
            recv_l = recv.lower()
            is_pool = (recv in self.pools
                       or "pool" in recv_l or "executor" in recv_l)
            if isinstance(node.func.value, ast.Call):
                callee = dotted(node.func.value.func)
                if f"{callee.rsplit('.', 1)[-1]}()" in self.pools:
                    is_pool = True
            if is_pool:
                self.hits.append({
                    "node": node, "kind": "submit", "scope": self.scope,
                    "line": node.lineno,
                    "target": self._target_expr(node, "submit")})
        self.generic_visit(node)


def _resolve_target(info: ModuleInfo, scope: str, target) -> Optional[str]:
    """Qualname of the spawn target when it is a same-module def/lambda
    (preferring the definition nested in the spawning scope)."""
    if target is None:
        return None
    if isinstance(target, ast.Lambda):
        for q, fi in info.functions.items():
            if fi.node is target:
                return q
        return None
    name = dotted(target)
    if not name:
        return None
    bare = name.rsplit(".", 1)[-1]
    cands = info.defs_by_name.get(bare, [])
    if not cands:
        return None
    for q in cands:
        if q.startswith(scope + ".") or q == f"{scope}.{bare}":
            return q
    return cands[0]


def check(sources: List[SourceFile]) -> List[Violation]:
    out: List[Violation] = []
    for src in sources:
        if src.path in EXEMPT_FILES:
            continue
        info = cached_module_info(src)
        engine_names = _engine_imported_names(info)
        pools = _pool_provenance(info, src.tree)
        idx = _SpawnIndex(pools)
        idx.visit(src.tree)
        for hit in idx.hits:
            target_qual = _resolve_target(info, hit["scope"],
                                          hit["target"])
            if target_qual is None:
                continue      # dynamic target: outside the rule's reach
            reason = _engine_reaching(info, target_qual, engine_names)
            if reason is None:
                continue
            what = ("threading.Thread" if hit["kind"] == "thread"
                    else "pool submit")
            tname = target_qual.rsplit(".", 1)[-1]
            out.append(Violation(
                RULE, src.path, hit["line"], hit["scope"],
                f"bare {what} target '{tname}' reaches engine code "
                f"({reason}) without inheriting the task ambients "
                f"(tenant scope, task_priority, CancelToken) — spawn "
                f"through utils/ambient."
                f"spawn_with_ambients / submit_with_ambients"))
    return out
