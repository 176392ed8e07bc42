"""registry/doc/API drift checker.

The reference generates docs from code (TypeChecks -> supported_ops.md,
RapidsConf -> configs.md) and validates its API surface against shims
(ApiValidation) precisely so the three can never silently diverge.  This
checker wires the same guarantees into tier-1:

  * docs/supported_ops.md and docs/configs.md must byte-match what
    tools/generate_docs.py emits from the live registries;
  * every expression class registered in planner/overrides.py
    (_SUPPORTED_EXPRS) must have a planner/typesig.py signature row —
    an op the tagging pass accepts but the TypeSig table doesn't know is
    exactly the drift TypeChecks exists to prevent;
  * tools/api_check.py must be clean against its committed
    api_surface.json snapshot.

This checker imports the live package (unlike the AST checkers), so it
forces the CPU backend first — lint must never wait on a TPU runtime.
"""
from __future__ import annotations

import json
import os
from typing import List

from tools.tpulint.core import Violation

RULE = "drift"


def _force_cpu() -> None:
    import jax
    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass   # already initialized by the host process (tests do this)


def check(repo_root: str, sources=None) -> List[Violation]:
    """``sources`` (the framework's already-parsed SourceFile list, when
    the caller has a FULL package scan in hand) lets the trace-ranges
    walk reuse those ASTs instead of re-reading every module."""
    _force_cpu()
    out: List[Violation] = []
    out.extend(_check_generated_docs(repo_root))
    out.extend(_check_typesig_rows())
    out.extend(_check_api_surface(repo_root))
    out.extend(_check_lint_doc(repo_root))
    out.extend(_check_trace_ranges(repo_root, sources))
    out.extend(_check_metrics_doc(repo_root))
    out.extend(_check_knob_wiring(repo_root, sources))
    out.extend(_check_unused_counters(repo_root, sources))
    return out


#: registered keys that legitimately have no in-package reader, with the
#: reason they stay registered.  Keep EMPTY unless a knob truly cannot
#: wire (every entry here is a doc'd key users can set to no effect).
_KNOB_ALLOW: dict = {}


def _package_trees(repo_root: str, sources):
    """(relpath, tree) for every spark_rapids_tpu module, reusing the
    framework's parsed ASTs when the caller has a full scan in hand."""
    import ast as _ast
    if sources is not None:
        return [(s.path, s.tree) for s in sources
                if s.path.startswith("spark_rapids_tpu/")]
    parsed = []
    pkg = os.path.join(repo_root, "spark_rapids_tpu")
    for dirpath, _dirs, files in os.walk(pkg):
        for fn in sorted(files):
            if not fn.endswith(".py"):
                continue
            fpath = os.path.join(dirpath, fn)
            with open(fpath, encoding="utf-8") as f:
                try:
                    tree = _ast.parse(f.read())
                except SyntaxError:
                    continue
            parsed.append((os.path.relpath(fpath, repo_root), tree))
    return parsed


def _check_knob_wiring(repo_root: str, sources=None) -> List[Violation]:
    """Dead-knob drift, both directions (the RapidsConf analog of
    documented-but-dead flags):

      * every ``conf("spark.rapids.*")`` entry registered in config.py
        must be READ somewhere in the package — via its constant
        (``C.MAX_READER_BATCH_SIZE_ROWS``), its accessor property
        (``conf.reader_batch_size_rows``, including ``getattr`` by
        string), or its raw key string.  A registered-but-never-read key
        is documentation for behavior that does not exist (this check
        found spark.rapids.sql.reader.batchSizeRows, sql.batchSizeBytes
        and shuffle.multiThreaded.reader.threads all silently ignored);
      * every ``spark.rapids.*`` key string READ in the package must be
        registered in config.py — an unregistered read is an
        undocumented knob (found spark.rapids.serving.query.tenant).

    Purely syntactic: an accessor whose name collides with an unrelated
    attribute reads as "wired", so the check errs toward silence."""
    import ast as _ast
    import re as _re

    cfg_rel = "spark_rapids_tpu/config.py"
    trees = _package_trees(repo_root, sources)
    cfg_tree = next((t for p, t in trees if p == cfg_rel), None)
    if cfg_tree is None:
        with open(os.path.join(repo_root, cfg_rel), encoding="utf-8") as f:
            cfg_tree = _ast.parse(f.read())

    def entry_key(call):
        node = call
        while isinstance(node, _ast.Call):
            f = node.func
            if isinstance(f, _ast.Name) and f.id == "conf":
                if node.args and isinstance(node.args[0], _ast.Constant):
                    return node.args[0].value
                return None
            if isinstance(f, _ast.Attribute):
                node = f.value
            else:
                return None
        return None

    entries = {}          # const name -> (key, lineno)
    for node in cfg_tree.body:
        if (isinstance(node, _ast.Assign)
                and isinstance(node.value, _ast.Call)
                and len(node.targets) == 1
                and isinstance(node.targets[0], _ast.Name)):
            key = entry_key(node.value)
            if key:
                entries[node.targets[0].id] = (key, node.lineno)

    accessors = {}        # const name -> {property/method names}
    for node in _ast.walk(cfg_tree):
        if isinstance(node, _ast.FunctionDef):
            for sub in _ast.walk(node):
                if (isinstance(sub, _ast.Call)
                        and isinstance(sub.func, _ast.Attribute)
                        and sub.func.attr == "get" and sub.args
                        and isinstance(sub.args[0], _ast.Name)
                        and sub.args[0].id in entries):
                    accessors.setdefault(
                        sub.args[0].id, set()).add(node.name)

    ext_names, ext_attrs, ext_strs = set(), set(), {}
    for path, tree in trees:
        if path == cfg_rel:
            continue
        for node in _ast.walk(tree):
            if isinstance(node, _ast.Name):
                ext_names.add(node.id)
            elif isinstance(node, _ast.Attribute):
                ext_attrs.add(node.attr)
            elif (isinstance(node, _ast.Constant)
                    and isinstance(node.value, str)):
                ext_strs.setdefault(node.value, (path, node.lineno))
            elif isinstance(node, _ast.ImportFrom):
                for a in node.names:
                    ext_names.add(a.name)

    out: List[Violation] = []
    keys = set()
    for const, (key, lineno) in sorted(entries.items()):
        keys.add(key)
        if key in _KNOB_ALLOW:
            continue
        accs = accessors.get(const, set())
        wired = (const in ext_names or const in ext_attrs
                 or key in ext_strs
                 or any(a in ext_attrs or a in ext_strs for a in accs))
        if not wired:
            out.append(Violation(
                RULE, cfg_rel, lineno, "<knobs>",
                f"conf key {key!r} ({const}) is registered but never "
                f"read in the package — wire it to behavior, or "
                f"allowlist it in tools/tpulint/drift.py _KNOB_ALLOW "
                f"with a reason"))
    key_pat = _re.compile(r"^spark\.rapids\.[A-Za-z0-9_.]+$")
    for val, (path, lineno) in sorted(ext_strs.items()):
        if key_pat.match(val) and val not in keys \
                and val not in _KNOB_ALLOW:
            out.append(Violation(
                RULE, path, lineno, "<knobs>",
                f"key string {val!r} is read/written in the package but "
                f"not registered in config.py — register it (docs are "
                f"generated from the registry)"))
    return out


def _check_unused_counters(repo_root: str,
                           sources=None) -> List[Violation]:
    """Counter-registry drift: every field in shuffle/stats.py
    ``_FIELDS`` must be mutated somewhere in the package (a kwarg to a
    ``.add(...)``/``.set_max(...)`` call, including ``**{...}`` splat
    keys).  The snapshot/scrape plumbing iterates ``_FIELDS``
    generically, so a never-incremented field shows up in artifacts as a
    permanently-zero series — dashboard noise that reads as signal."""
    import ast as _ast

    stats_rel = "spark_rapids_tpu/shuffle/stats.py"
    trees = _package_trees(repo_root, sources)
    stats_tree = next((t for p, t in trees if p == stats_rel), None)
    if stats_tree is None:
        with open(os.path.join(repo_root, stats_rel),
                  encoding="utf-8") as f:
            stats_tree = _ast.parse(f.read())

    fields = {}           # field name -> lineno
    for node in stats_tree.body:
        if (isinstance(node, _ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], _ast.Name)
                and node.targets[0].id == "_FIELDS"
                and isinstance(node.value, (_ast.Tuple, _ast.List))):
            for elt in node.value.elts:
                if (isinstance(elt, _ast.Constant)
                        and isinstance(elt.value, str)):
                    fields[elt.value] = elt.lineno

    mutated = set()
    for _path, tree in trees:
        for node in _ast.walk(tree):
            if not (isinstance(node, _ast.Call)
                    and isinstance(node.func, _ast.Attribute)
                    and node.func.attr in ("add", "set_max")):
                continue
            for kw in node.keywords:
                if kw.arg is not None:
                    mutated.add(kw.arg)
                elif isinstance(kw.value, _ast.Dict):
                    for k in kw.value.keys:
                        if (isinstance(k, _ast.Constant)
                                and isinstance(k.value, str)):
                            mutated.add(k.value)

    return [Violation(
        RULE, stats_rel, lineno, "<counters>",
        f"counter field {name!r} is registered in _FIELDS but never "
        f"incremented (no .add()/.set_max() kwarg anywhere in the "
        f"package) — remove it or wire the increment")
        for name, lineno in sorted(fields.items())
        if name not in mutated]


def _check_metrics_doc(repo_root: str) -> List[Violation]:
    """Metric-name registry drift (utils/telemetry.py): docs/metrics.md
    must byte-match ``telemetry.generate_metrics_doc()`` — the same
    docs-from-code contract as trace_ranges.md.  The scrape tool
    (tools/metrics_scrape.py) independently refuses to RENDER a name
    absent from the registry, so a series can neither appear
    undocumented nor survive a rename silently."""
    from spark_rapids_tpu.utils.telemetry import generate_metrics_doc
    rel = "docs/metrics.md"
    path = os.path.join(repo_root, rel)
    want = generate_metrics_doc()
    have = None
    if os.path.exists(path):
        with open(path, encoding="utf-8") as f:
            have = f.read()
    if have != want:
        return [Violation(
            RULE, rel, 1, "<generated>",
            f"{rel} does not match telemetry.generate_metrics_doc(); "
            f"run `python tools/generate_docs.py`")]
    return []


def _check_trace_ranges(repo_root: str,
                        sources=None) -> List[Violation]:
    """Trace-range registry drift (the NvtxRangeWithDoc discipline):

      * docs/trace_ranges.md must byte-match
        ``tracing.generate_ranges_doc()`` over the statically registered
        table (same docs-from-code contract as configs.md);
      * every LITERAL span name used with ``trace_range(...)``,
        ``obs.span(...)`` or ``timed(metric, name)`` (the range paired
        with an exec's metric, plan/execs/base.py) in the package must be
        registered — an unregistered range is invisible to the generated
        doc and to anyone navigating a Perfetto timeline.
    """
    import ast as _ast

    from spark_rapids_tpu.utils import tracing

    out: List[Violation] = []
    want = tracing.generate_ranges_doc()
    rel = "docs/trace_ranges.md"
    path = os.path.join(repo_root, rel)
    have = None
    if os.path.exists(path):
        with open(path, encoding="utf-8") as f:
            have = f.read()
    if have != want:
        out.append(Violation(
            RULE, rel, 1, "<generated>",
            f"{rel} does not match tracing.generate_ranges_doc(); "
            f"run `python tools/generate_docs.py`"))

    registered = set(tracing.static_ranges())
    if sources is not None:
        # reuse the framework's parsed ASTs (same file set:
        # core.iter_py_files walks exactly spark_rapids_tpu/)
        parsed = [(s.path, s.tree) for s in sources
                  if s.path.startswith("spark_rapids_tpu/")]
    else:
        parsed = []
        pkg = os.path.join(repo_root, "spark_rapids_tpu")
        for dirpath, _dirs, files in os.walk(pkg):
            for fn in sorted(files):
                if not fn.endswith(".py"):
                    continue
                fpath = os.path.join(dirpath, fn)
                with open(fpath, encoding="utf-8") as f:
                    try:
                        tree = _ast.parse(f.read())
                    except SyntaxError:
                        continue
                parsed.append((os.path.relpath(fpath, repo_root), tree))
    for relf, tree in parsed:
        for node in _ast.walk(tree):
            if not isinstance(node, _ast.Call):
                continue
            func = node.func
            name = (func.attr if isinstance(func, _ast.Attribute)
                    else func.id if isinstance(func, _ast.Name)
                    else "")
            if name in ("trace_range", "span"):
                arg = node.args[0] if node.args else None
            elif name == "timed":
                arg = (node.args[1] if len(node.args) > 1 else next(
                    (k.value for k in node.keywords if k.arg == "span"),
                    None))
            else:
                continue
            if not isinstance(arg, _ast.Constant) or not isinstance(
                    arg.value, str):
                continue
            rng = arg.value
            if rng not in registered:
                out.append(Violation(
                    RULE, relf, node.lineno, "<trace-ranges>",
                    f"span name {rng!r} is not registered in "
                    f"utils/tracing.py _STATIC_RANGES — register "
                    f"it (with a doc) and regenerate "
                    f"docs/trace_ranges.md"))
    return out


def _check_lint_doc(repo_root: str) -> List[Violation]:
    """docs/linting.md must carry a section per registered rule — a new
    rule without documentation (or a renamed rule leaving its section
    behind) is doc drift like any other."""
    from tools.tpulint.core import ALL_RULES
    path = os.path.join(repo_root, "docs", "linting.md")
    if not os.path.exists(path):
        return [Violation(RULE, "docs/linting.md", 1, "<generated>",
                          "docs/linting.md missing")]
    with open(path, encoding="utf-8") as f:
        text = f.read()
    out: List[Violation] = []
    for rule in ALL_RULES:
        if f"### `{rule}`" not in text:
            out.append(Violation(
                RULE, "docs/linting.md", 1, "<rules>",
                f"registered rule {rule!r} has no \"### `{rule}`\" "
                f"section in docs/linting.md"))
    return out


def _check_generated_docs(repo_root: str) -> List[Violation]:
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "tpulint_generate_docs",
        os.path.join(repo_root, "tools", "generate_docs.py"))
    gd = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gd)

    from spark_rapids_tpu.config import generate_config_docs

    out: List[Violation] = []
    for rel, want in (("docs/supported_ops.md", gd.generate_supported_ops()),
                      ("docs/configs.md", generate_config_docs())):
        path = os.path.join(repo_root, rel)
        have = None
        if os.path.exists(path):
            with open(path, encoding="utf-8") as f:
                have = f.read()
        if have != want:
            out.append(Violation(
                RULE, rel, 1, "<generated>",
                f"{rel} does not match tools/generate_docs.py output; "
                f"run `python tools/generate_docs.py`"))
    return out


def _check_typesig_rows() -> List[Violation]:
    from spark_rapids_tpu.planner import overrides as O
    from spark_rapids_tpu.planner import typesig

    out: List[Violation] = []
    for cls in sorted(O._SUPPORTED_EXPRS, key=lambda c: c.__name__):
        if typesig.sig_for(cls) is None:
            out.append(Violation(
                RULE, "spark_rapids_tpu/planner/typesig.py", 1,
                "_build_registry",
                f"{cls.__name__} is registered in planner/overrides.py "
                f"but has no typesig row"))
    return out


def _check_api_surface(repo_root: str) -> List[Violation]:
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "tpulint_api_check",
        os.path.join(repo_root, "tools", "api_check.py"))
    ac = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ac)

    snapshot = os.path.join(repo_root, "tools", "generated_files",
                            "api_surface.json")
    if not os.path.exists(snapshot):
        return [Violation(RULE, "tools/generated_files/api_surface.json", 1,
                          "<generated>",
                          "api surface snapshot missing; run "
                          "`python tools/api_check.py --update`")]
    with open(snapshot, encoding="utf-8") as f:
        recorded = json.load(f)
    problems = ac.diff_surface(recorded, ac.current_surface())
    return [Violation(RULE, "tools/generated_files/api_surface.json", 1,
                      "<api>", f"api surface drift: {p}")
            for p in problems]
