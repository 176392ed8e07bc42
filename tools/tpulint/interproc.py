"""Interprocedural tier: the flow rules re-grounded on summaries.

Four passes ride the EXISTING rule names (pin-balance,
ambient-propagation, counter-discipline, lock-order) so suppressions,
docs sections, and the baseline workflow apply unchanged; each pass
reports the class of defect the intraprocedural rule is blind to —
a leak through a helper, a wrapper that transfers a pin, a
pool-submitted closure that reaches engine code two modules away, a
lock inversion assembled across call boundaries — at the CALL SITE,
with the interprocedural path in the finding.

Whole-program discipline: the call graph is global even when only one
file is being linted, so when the passed sources are a real on-disk
subset (the ``--changed`` mode), the remaining package files are loaded
from disk to complete the program — but violations are reported ONLY
for the files actually passed.  A source set that does not match the
on-disk tree (test fixtures) is treated as its own closed world.
"""
from __future__ import annotations

import ast
import os
from typing import Dict, List, Optional, Set, Tuple

from tools.tpulint import summaries as S
from tools.tpulint.ambient_spawn import (EXEMPT_FILES as AMBIENT_EXEMPT,
                                         _SpawnIndex,
                                         _engine_imported_names,
                                         _engine_reaching,
                                         _pool_provenance,
                                         _resolve_target)
from tools.tpulint.callgraph import FnRecord
from tools.tpulint.cfg import cached_module_info
from tools.tpulint.core import (REPO, SourceFile, Violation, dotted,
                                iter_py_files, load_source)
from tools.tpulint.counter_discipline import (
    EXEMPT_FILES as COUNTER_EXEMPT, _retry_body_quals)
from tools.tpulint.locks import _Analyzer
from tools.tpulint.pin_balance import (ACQUIRE_METHODS, CLOSE_METHODS,
                                       RELEASE_METHODS, _recv_of,
                                       in_scope as pin_in_scope)

# -- whole-program source augmentation ---------------------------------------

_AUGMENT_CACHE: Dict[tuple, List[SourceFile]] = {}


def _whole_program(sources: List[SourceFile],
                   repo_root: str = REPO) -> List[SourceFile]:
    """The full program the given sources belong to: the sources
    themselves, plus (when they are a faithful on-disk subset) the rest
    of the package loaded from disk."""
    pkg = [s for s in sources if s.path.startswith("spark_rapids_tpu/")]
    paths = {s.path for s in pkg}
    if not pkg or "spark_rapids_tpu/__init__.py" in paths:
        return sources
    for s in pkg:
        abs_path = os.path.join(repo_root, s.path)
        try:
            with open(abs_path, encoding="utf-8") as f:
                if f.read() != s.text:
                    return sources      # fixture world: closed as given
        except OSError:
            return sources
    key = tuple(sorted((s.path, id(s.tree)) for s in pkg))
    full = _AUGMENT_CACHE.get(key)
    if full is None:
        full = list(sources)
        for rel in iter_py_files(repo_root):
            if rel in paths:
                continue
            src = load_source(repo_root, rel)
            if src is not None:
                full.append(src)
        if len(_AUGMENT_CACHE) > 4:
            _AUGMENT_CACHE.clear()
        _AUGMENT_CACHE[key] = full
    return full


def _engine_for(sources: List[SourceFile]) -> S.SummaryEngine:
    return S.build_engine(_whole_program(sources))


def _bare(fid: str) -> str:
    return fid.rsplit(":", 1)[-1].rsplit(".", 1)[-1]


# -- pin-balance: leaks through returns-pinned callees -----------------------

def check_pins(sources: List[SourceFile]) -> List[Violation]:
    eng = _engine_for(sources)
    out: List[Violation] = []
    reported_ann: Set[tuple] = set()
    for path, line, msg in eng.annotation_problems:
        key = (path, msg)
        if any(s.path == path for s in sources) and key not in \
                reported_ann:
            reported_ann.add(key)
            out.append(Violation("bad-suppression", path, line,
                                 "<module>", msg))
    for src in sources:
        if not pin_in_scope(src.path):
            continue
        mod = eng.index.modules.get(src.path)
        if mod is None:
            continue
        for rec in mod.functions.values():
            bare = rec.qualname.rsplit(".", 1)[-1]
            if bare in RELEASE_METHODS | CLOSE_METHODS | ACQUIRE_METHODS:
                continue        # release/transfer APIs themselves
            out.extend(_pin_leaks_in(eng, src, rec))
    return out


def _pin_leaks_in(eng: S.SummaryEngine, src: SourceFile,
                  rec: FnRecord) -> List[Violation]:
    out: List[Violation] = []
    for callee_fid, site in eng.edges.get(rec.fid, ()):
        if site.kind != "call":
            continue
        cs = eng.summaries.get(callee_fid)
        if cs is None or not cs.returns_pinned:
            continue
        callee_bare = _bare(callee_fid)
        if callee_bare in ACQUIRE_METHODS:
            continue    # direct acquire calls are the intra rule's job
        usage = _result_usage(rec, site.node, eng)
        if usage is None:
            continue
        how, detail = usage
        out.append(Violation(
            "pin-balance", src.path, site.line, rec.qualname,
            f"call to '{callee_bare}' returns a pinned handle "
            f"(interprocedural path: {cs.pin_path}) and the result is "
            f"{detail} — the pin leaks until process exit; unpin the "
            f"result (or hand it off) on every path" if how == "bound"
            else
            f"call to '{callee_bare}' returns a pinned handle "
            f"(interprocedural path: {cs.pin_path}) and the result is "
            f"discarded — the pin leaks until process exit; bind the "
            f"result and unpin it (or hand it off) on every path"))
    return out


def _result_usage(rec: FnRecord, call: ast.Call,
                  eng: S.SummaryEngine) -> Optional[Tuple[str, str]]:
    """("discarded", _) when the call is a bare expression statement;
    ("bound", why) when bound to a local that is never released and
    never escapes.  None = released/escaped/too-dynamic (not flagged)."""
    var = None
    for n in S._shallow_walk(rec.node):
        if isinstance(n, ast.Expr) and n.value is call:
            return ("discarded", "discarded")
        if isinstance(n, ast.Assign) and len(n.targets) == 1 and \
                isinstance(n.targets[0], ast.Name) and n.value is call:
            var = n.targets[0].id
    if var is None:
        return None         # tuple-unpacked / nested expression: skip
    released = escaped = False
    for n in S._shallow_walk(rec.node):
        if isinstance(n, ast.Call):
            rm = _recv_of(n)
            if rm and rm[0] == var and \
                    rm[1] in RELEASE_METHODS | CLOSE_METHODS:
                released = True
                continue
            for j, arg in enumerate(n.args):
                if isinstance(arg, ast.Name) and arg.id == var:
                    # passed along: released if the callee releases this
                    # positional, otherwise ownership escapes our view
                    rel = False
                    for fid in eng.index.resolve(rec, dotted(n.func)):
                        cs2 = eng.summaries.get(fid)
                        if cs2 is not None and j in cs2.releases_params:
                            rel = True
                    released = released or rel
                    escaped = escaped or not rel
            for kw in n.keywords:
                if isinstance(kw.value, ast.Name) and kw.value.id == var:
                    escaped = True
        elif isinstance(n, (ast.Return, ast.Yield, ast.YieldFrom)) and \
                n.value is not None:
            if any(isinstance(x, ast.Name) and x.id == var
                   for x in ast.walk(n.value)):
                escaped = True
        elif isinstance(n, ast.Assign) and n.value is not call:
            if any(isinstance(x, ast.Name) and x.id == var
                   for x in ast.walk(n.value)):
                escaped = True
        elif isinstance(n, (ast.With, ast.AsyncWith)):
            for item in n.items:
                if isinstance(item.context_expr, ast.Name) and \
                        item.context_expr.id == var:
                    released = True     # context manager owns cleanup
        elif isinstance(n, (ast.For, ast.AsyncFor)) and \
                isinstance(n.iter, ast.Name) and n.iter.id == var:
            for sub in ast.walk(n):
                if isinstance(sub, ast.Call):
                    rm = _recv_of(sub)
                    if rm and isinstance(n.target, ast.Name) and \
                            rm[0] == n.target.id and \
                            rm[1] in RELEASE_METHODS | CLOSE_METHODS:
                        released = True
    if released or escaped:
        return None
    return ("bound", f"bound to '{var}' which is never unpinned and "
                     f"never leaves this function")


# -- ambient-propagation: engine reach across modules ------------------------

def check_ambients(sources: List[SourceFile]) -> List[Violation]:
    eng = _engine_for(sources)
    out: List[Violation] = []
    for src in sources:
        if src.path in AMBIENT_EXEMPT or \
                not src.path.startswith("spark_rapids_tpu/"):
            continue
        mod = eng.index.modules.get(src.path)
        if mod is None:
            continue
        info = cached_module_info(src)
        engine_names = _engine_imported_names(info)
        pools = _pool_provenance(info, src.tree)
        idx = _SpawnIndex(pools)
        idx.visit(src.tree)
        for hit in idx.hits:
            target_qual = _resolve_target(info, hit["scope"],
                                          hit["target"])
            if target_qual is not None and _engine_reaching(
                    info, target_qual, engine_names) is not None:
                continue        # the intraprocedural rule already fires
            fid = _target_fid(eng, mod, info, hit, target_qual)
            if fid is None:
                continue
            summ = eng.summaries.get(fid)
            if summ is None or summ.engine is None:
                continue
            what = ("threading.Thread" if hit["kind"] == "thread"
                    else "pool submit")
            out.append(Violation(
                "ambient-propagation", src.path, hit["line"],
                hit["scope"],
                f"bare {what} target '{_bare(fid)}' reaches engine code "
                f"only visible interprocedurally ({summ.engine}) "
                f"without inheriting the task ambients (tenant scope, "
                f"task_priority, CancelToken) — spawn "
                f"through utils/ambient.spawn_with_ambients / "
                f"submit_with_ambients"))
    return out


def _target_fid(eng: S.SummaryEngine, mod, info, hit,
                target_qual: Optional[str]) -> Optional[str]:
    if target_qual is not None:
        fi = info.functions.get(target_qual)
        if fi is not None:
            return eng.index.by_node.get(id(fi.node))
        return None
    # cross-module target (imported name / module attribute)
    scope = hit["scope"]
    caller = mod.functions_by_qual().get(scope)
    if caller is None:
        caller = FnRecord(fid="", path=mod.path, qualname="",
                          node=None, line=0)
    return eng.index.resolve_expr(caller, hit["target"])


# -- counter-discipline: counter mutation through helpers --------------------

def check_counters(sources: List[SourceFile]) -> List[Violation]:
    eng = _engine_for(sources)
    out: List[Violation] = []
    for src in sources:
        if not src.path.startswith("spark_rapids_tpu/") or \
                src.path in COUNTER_EXEMPT:
            continue
        info = cached_module_info(src)
        for qual in sorted(_retry_body_quals(info)):
            fi = info.functions.get(qual)
            if fi is None:
                continue
            fid = eng.index.by_node.get(id(fi.node))
            if fid is None:
                continue
            rec = eng.index.functions[fid]
            out.extend(_counter_calls_in(eng, src, rec))
    return out


def _counter_calls_in(eng: S.SummaryEngine, src: SourceFile,
                      rec: FnRecord) -> List[Violation]:
    out: List[Violation] = []
    for callee_fid, site in eng.edges.get(rec.fid, ()):
        if site.kind != "call":
            continue
        cs = eng.summaries.get(callee_fid)
        if cs is None or not cs.counters:
            continue
        if cs.counters_tail and S._sites_are_tail(
                eng.cfg_of(rec), [site.node]):
            continue    # nothing fallible after the count, either side
        fields = ", ".join(sorted(cs.counters)[:4])
        via = cs.counters[sorted(cs.counters)[0]]
        out.append(Violation(
            "counter-discipline", src.path, site.line, rec.qualname,
            f"helper '{_bare(callee_fid)}' mutates shuffle counters "
            f"({fields}) and runs inside a retry-attempt body "
            f"(interprocedural path: {via}) — an OOM retry "
            f"double-counts; move the helper call outside the retry, "
            f"make the count the helper's last fallible-free step, or "
            f"suppress with a reason if it deliberately counts "
            f"attempts"))
    return out


# -- lock-order: inversions assembled across call boundaries -----------------

_EDGE_CACHE: Dict[tuple, tuple] = {}


def _lock_edge_sets(sources: List[SourceFile]):
    """(intra edges, interproc edges, blocking-under-lock findings) for
    the whole program the given sources belong to, cached per program."""
    eng = _engine_for(sources)
    full = _whole_program(sources)
    key = tuple(sorted((s.path, id(s.tree)) for s in full))
    hit = _EDGE_CACHE.get(key)
    if hit is None:
        inter, blocking = _interproc_lock_edges(eng, full)
        hit = (_intra_lock_edges(eng, full), inter, blocking)
        if len(_EDGE_CACHE) > 4:
            _EDGE_CACHE.clear()
        _EDGE_CACHE[key] = hit
    return hit


def check_locks(sources: List[SourceFile]) -> List[Violation]:
    intra, inter, blocking = _lock_edge_sets(sources)
    out: List[Violation] = []
    lint_paths0 = {s.path for s in sources}
    for (path, line, scope, held_id, callee_bare, why) in blocking:
        if path not in lint_paths0:
            continue
        out.append(Violation(
            "lock-order", path, line, scope,
            f"call to '{callee_bare}' can block ({why}) while holding "
            f"{held_id} — visible only interprocedurally; hoist the "
            f"blocking work out of the critical section, or suppress "
            f"with a reason if this is a deliberate init-once"))
    all_edges: Dict[Tuple[str, str], Tuple[str, int, str]] = {}
    for edge, (path, line) in intra.items():
        all_edges[edge] = (path, line, "held directly")
    for edge, (path, line, via) in inter.items():
        all_edges.setdefault(edge, (path, line, via))
    lint_paths = {s.path for s in sources}
    reported: Set[frozenset] = set()
    for (a, b), (path, line, via) in sorted(all_edges.items()):
        if (b, a) not in all_edges:
            continue
        pair = frozenset((a, b))
        if pair in reported:
            continue
        reported.add(pair)
        if (a, b) in intra and (b, a) in intra:
            continue        # locks.py's one-level analysis reports it
        # report at whichever side of the inversion is being linted
        other_path, _ol, other_via = all_edges[(b, a)]
        site_path, site_line, site_via = path, line, via
        if site_path not in lint_paths and other_path in lint_paths:
            site_path, site_line, site_via = other_path, _ol, other_via
            a, b = b, a
            other_path, other_via = path, via
        if site_path not in lint_paths:
            continue
        first, second = sorted((a, b))
        out.append(Violation(
            "lock-order", site_path, site_line, "<module>",
            f"inconsistent lock order between {first} and {second}, "
            f"visible only interprocedurally: {a} -> {b} here "
            f"({site_via}), {b} -> {a} in {other_path} ({other_via})"))
    return out


def _intra_lock_edges(eng: S.SummaryEngine, full: List[SourceFile]
                      ) -> Dict[Tuple[str, str], Tuple[str, int]]:
    """locks.py's edge set, recomputed from the callgraph inventories so
    only lock-touching function bodies are traversed (the full-module
    _Analyzer walk is the single most expensive part of a --changed
    run).  Must mirror locks.check's edges: it is the dedup oracle that
    keeps this pass from double-reporting inversions the one-level
    analysis already covers."""
    edges: Dict[Tuple[str, str], Tuple[str, int]] = {}
    for src in full:
        if not src.path.startswith("spark_rapids_tpu/"):
            continue
        mod = eng.index.modules.get(src.path)
        if mod is None:
            continue
        table = eng._lock_table(mod)
        if not table.module_locks and not table.class_locks:
            continue
        # bare name -> lexically acquired locks, from the with-item
        # inventories (locks.py walks every def body for the same map)
        fn_acquires: Dict[str, set] = {}
        candidates = []
        for rec in (mod.functions.values() if mod else ()):
            if not isinstance(rec.node, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                continue
            acquiry = bool(rec.with_items) or any(
                cs.name == "acquire" or cs.name.endswith(".acquire")
                for cs in rec.call_sites)
            if not acquiry:
                continue
            candidates.append(rec)
            if rec.with_items:
                resolver = _resolver_for(src, table, rec)
                got = {hit for expr in rec.with_items
                       for hit in [resolver.resolve(expr)]
                       if hit is not None}
                if got:
                    bare = rec.qualname.rsplit(".", 1)[-1]
                    fn_acquires.setdefault(bare, set()).update(got)
        for rec in candidates:
            analyzer = _Analyzer(src, table, fn_acquires)
            qual = [p for p in rec.qualname.split(".")
                    if not p.startswith("<lambda")]
            analyzer._names = qual[:-1]
            analyzer.visit(rec.node)
            for edge, site in analyzer.edges.items():
                edges.setdefault(edge, site)
        toplevel = [stmt for stmt in src.tree.body
                    if isinstance(stmt, (ast.With, ast.AsyncWith))]
        if toplevel:
            analyzer = _Analyzer(src, table, fn_acquires)
            for stmt in toplevel:
                analyzer.visit(stmt)
            for edge, site in analyzer.edges.items():
                edges.setdefault(edge, site)
    return edges


def _resolver_for(src: SourceFile, table, rec) -> _Analyzer:
    resolver = _Analyzer(src, table, {})
    resolver._names = [p for p in rec.qualname.split(".")
                       if not p.startswith("<lambda")]
    return resolver


def _interproc_lock_edges(eng: S.SummaryEngine, full: List[SourceFile]):
    """Two products of one walk over lexically-held lock regions:

      * (outer lock, inner lock) -> (file, line, via) for lock
        acquisitions reached through resolved CALLS while another lock
        is lexically held;
      * blocking-under-lock findings: (file, line, scope, held lock,
        callee bare name, why) for calls whose summary says a blocking
        category is reachable (``may_block``) while a real (non-
        throttle) lock is held.  Condition-variable waits are exempt
        (``wait`` releases the lock), as is ``cancellable_wait`` — the
        blessed bounded wait whose contract is to be handed the held
        condition.  One-level same-module bare/self calls whose callee
        blocks DIRECTLY are the intra rule's job (locks.py
        fn_blocking) and are skipped here."""
    edges: Dict[Tuple[str, str], Tuple[str, int, str]] = {}
    blocking: List[tuple] = []
    for src in full:
        if not src.path.startswith("spark_rapids_tpu/"):
            continue
        mod = eng.index.modules.get(src.path)
        if mod is None:
            continue
        table = eng._lock_table(mod)
        if not table.module_locks and not table.class_locks:
            continue        # nothing can be lexically held here
        locky = _locky_bares(eng)
        for rec in mod.functions.values():
            if not isinstance(rec.node, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                continue
            if not rec.with_items:
                continue        # nothing can be lexically held
            resolver = _resolver_for(src, table, rec)
            _walk_held(eng, src, rec, rec.node.body, [], resolver,
                       edges, blocking, locky)
    return edges, blocking


def _locky_bares(eng: S.SummaryEngine) -> Set[str]:
    """Bare names of functions whose summary acquires any lock or may
    block — the cheap prefilter that keeps _walk_held from resolving
    every call under every held lock."""
    locky = getattr(eng, "_locky_bares", None)
    if locky is None:
        locky = set()
        for fid, s in eng.summaries.items():
            if not s.locks and s.may_block is None:
                continue
            qual = fid.rsplit(":", 1)[-1].split(".")
            locky.add(qual[-1])
            if qual[-1] == "__init__" and len(qual) > 1:
                locky.add(qual[-2])     # Class() resolves to __init__
        eng._locky_bares = locky
    return locky


#: leaf call names whose block RELEASES the lock it runs under (cv
#: waits) or is the blessed bounded wait built exactly for that pattern
_BLOCK_EXEMPT_LEAVES = ("wait", "cancellable_wait")


def _block_leaf(why: str) -> str:
    """The leaf call name out of a may_block path like
    ``"future wait (fut.result) in shuffle/net.py:Fetcher._get"`` or a
    chained ``"helper() in a.py:f -> device sync (jax.device_get) in
    b.py:g"`` — the last parenthesized name decides exemption."""
    tail = why.rsplit("(", 1)
    if len(tail) < 2:
        return ""
    return tail[1].split(")", 1)[0].rsplit(".", 1)[-1]


def _walk_held(eng: S.SummaryEngine, src: SourceFile, rec: FnRecord,
               body, held: List[tuple], resolver, edges,
               blocking: List[tuple], locky: Set[str]) -> None:
    from tools.tpulint.locks import BLOCKING_EXPECTED_CTORS
    for stmt in body:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if isinstance(stmt, (ast.With, ast.AsyncWith)):
            got: List[tuple] = []
            for item in stmt.items:
                hit = resolver.resolve(item.context_expr)
                if hit is not None:
                    got.append(hit)
            _walk_held(eng, src, rec, stmt.body, held + got, resolver,
                       edges, blocking, locky)
            continue
        if held:
            real_held = [h for h in held
                         if h[1] not in BLOCKING_EXPECTED_CTORS]
            for sub in ast.walk(stmt):
                if isinstance(sub, (ast.FunctionDef,
                                    ast.AsyncFunctionDef, ast.Lambda)):
                    continue
                if not isinstance(sub, ast.Call):
                    continue
                name = dotted(sub.func)
                bare_name = name.rsplit(".", 1)[-1]
                if bare_name not in locky:
                    continue
                for fid in eng.index.resolve(rec, name):
                    cs = eng.summaries.get(fid)
                    if cs is None:
                        continue
                    for inner, path in cs.locks.items():
                        for outer, _ctor in held:
                            if inner != outer:
                                edges.setdefault(
                                    (outer, inner),
                                    (src.path, sub.lineno,
                                     f"via {_bare(fid)}(): {path}"))
                    if cs.may_block is None or not real_held:
                        continue
                    if _bare(fid) in _BLOCK_EXEMPT_LEAVES or \
                            _block_leaf(cs.may_block) in \
                            _BLOCK_EXEMPT_LEAVES:
                        continue
                    same_module = fid.startswith(src.path + ":")
                    one_level = "->" not in cs.may_block
                    intra_visible = ("." not in name
                                     or (name.startswith("self.")
                                         and name.count(".") == 1))
                    if same_module and one_level and intra_visible:
                        continue    # locks.py fn_blocking reports it
                    # one finding per (site, callee): multiple resolve
                    # candidates (e.g. several __init__ fids) must not
                    # fan out into near-duplicate reports
                    key = (src.path, sub.lineno, rec.qualname,
                           real_held[-1][0], _bare(fid))
                    if all(b[:5] != key for b in blocking):
                        blocking.append(key + (cs.may_block,))
        for child_body in _sub_bodies(stmt):
            _walk_held(eng, src, rec, child_body, held, resolver, edges,
                       blocking, locky)


def _sub_bodies(stmt):
    for attr in ("body", "orelse", "finalbody"):
        b = getattr(stmt, attr, None)
        if b:
            yield b
    for h in getattr(stmt, "handlers", ()):
        yield h.body


def static_lock_graph(sources: Optional[List[SourceFile]] = None,
                      repo_root: str = REPO) -> Set[Tuple[str, str]]:
    """Every (outer, inner) lock-order edge the static analysis knows —
    one-level lexical plus summary-propagated.  The runtime sanitizer's
    witnessed edges are checked against this set (a witnessed edge the
    static graph missed is a candidate fixture)."""
    if sources is None:
        sources = [s for s in (load_source(repo_root, rel)
                               for rel in iter_py_files(repo_root))
                   if s is not None]
    intra, inter, _blocking = _lock_edge_sets(sources)
    return set(intra) | set(inter)
