"""Physical exec base: the TPU analog of GpuExec.

Reference: GpuExec.scala:107 — a columnar plan node producing an
RDD[ColumnarBatch] per partition, with standard metrics (op time, output
rows/batches) and semaphore acquisition before device work.

Execution model: ``num_partitions()`` partitions, each computed by
``execute_partition(idx)`` yielding device ColumnarBatches.  The local task
runner (plan/engine.py) maps partitions onto a thread pool with the TPU
semaphore gating device concurrency (GpuSemaphore.scala:240 analog).

Jit discipline: each exec builds its device computation as pure functions of
batch pytrees and jits them once per (schema, capacity-bucket); capacities
are bucketed to powers of two (columnar/column.py round_up_pow2) so XLA
recompiles stay bounded while batch sizes vary.
"""
from __future__ import annotations

import collections
import functools
import hashlib
import sys
import threading
import time
from typing import Callable, Dict, Iterator, List, Optional, Tuple

from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema
from spark_rapids_tpu.utils.tracing import trace_range


class MaterializeLock:
    """The lock of a materialise-once block (``with self._lock: if
    self._x is None: ...``), which a task holds across its child's whole
    execution.  A task that finds it taken waits for it under
    ``tpu_semaphore().released()``: blocked on a sibling's materialisation
    it holds no device permit, so the sibling's scan, which gave its own
    up to wait for a decoded chunk, can always take one back.
    Uncontended, it costs one non-blocking ``acquire``."""

    def __init__(self):
        self._lock = threading.Lock()

    def __enter__(self):
        if not self._lock.acquire(blocking=False):
            from spark_rapids_tpu.memory.semaphore import tpu_semaphore
            got = False
            try:
                with tpu_semaphore().released():
                    got = self._lock.acquire()
            except BaseException:
                # taking the permit back is a cancellation point, and
                # __exit__ does not run when __enter__ raises
                if got:
                    self._lock.release()
                raise

    def __exit__(self, *exc):
        self._lock.release()


# -- cross-query jit sharing --------------------------------------------------
#
# jax.jit functions created per exec INSTANCE recompile on every new query
# even when the plan is identical (the reference pays codegen once per plan
# shape via Spark's codegen cache; we get the analog by keying jitted step
# functions on a canonical plan signature).  The cache holds the jit wrapper
# (and therefore its XLA executables); an LRU bound keeps memory in check.

_JIT_CACHE: "collections.OrderedDict[str, object]" = collections.OrderedDict()
_JIT_CACHE_MAX = 512
_JIT_CACHE_LOCK = threading.Lock()


# On the CPU backend an executable is JIT-compiled code in anonymous memory
# mappings, 130 to 300 of them a program with a sort in it, and the kernel
# gives a process vm.max_map_count of them (65,530 by default): some 300
# cached programs, fewer than _JIT_CACHE_MAX.  Past it mmap fails inside the
# compiler and the process dies there (SIGSEGV or abort in
# backend_compile_and_load; a tier-1 worker at 64,894 mappings, PR 34).  So
# the cache is also bounded by what it can observe of the process: past half
# the allowance the least recently used half goes.  A TPU executable maps
# nothing like it, and a process that cannot read /proc is not bounded here.

@functools.lru_cache(maxsize=None)
def _mappings_budget() -> int:
    try:
        with open("/proc/sys/vm/max_map_count") as f:
            return int(f.read()) // 2
    except (OSError, ValueError):
        return sys.maxsize


def _mappings_in_use() -> int:
    try:
        with open("/proc/self/maps", "rb") as f:
            return f.read().count(b"\n")
    except OSError:
        return 0


def _trim_jit_cache_locked() -> None:
    """The LRU bound, and the mappings bound after a miss (the caller holds
    ``_JIT_CACHE_LOCK``; a miss is a compile, beside which reading
    /proc/self/maps is nothing)."""
    while len(_JIT_CACHE) > _JIT_CACHE_MAX:
        _JIT_CACHE.popitem(last=False)
    if _mappings_in_use() > _mappings_budget():
        for _ in range(len(_JIT_CACHE) // 2):
            _JIT_CACHE.popitem(last=False)


class _LaunchStats:
    """Process-wide program-launch accounting: how many XLA programs a
    query dispatches, and how many of each.  Every launch is a host
    dispatch; what one costs on the device is read from the profiler's
    trace, where each program appears under the same name as here.
    Counts every shared_jit dispatch, never blocks;
    reset/read from bench.py around each timed run.  Lock-guarded: tasks
    dispatch from a thread pool and `+=` is not atomic bytecode."""
    lock = threading.Lock()
    count = 0
    by_program: Dict[str, int] = {}     # program name -> launches since reset
    discarded: Dict[str, int] = {}      # reason -> launches whose output a
                                        # fused segment threw away


#: runtime-sanitizer compile-budget seam (utils/sanitizer.py): called
#: with the program key on every shared_jit cache MISS.  None when the
#: sanitizer is off.
_COMPILE_HOOK = None


def set_compile_hook(fn) -> None:
    global _COMPILE_HOOK
    _COMPILE_HOOK = fn


def reset_launch_stats() -> None:
    with _LaunchStats.lock:
        _LaunchStats.count = 0
        _LaunchStats.by_program = {}
        _LaunchStats.discarded = {}


def launch_stats() -> dict:
    """``by_program``: launches per program NAME — the name the jitted
    function carries (``program_name``), so the same string names the
    program in the device trace's ``XLA Modules`` line.

    ``discarded``: of ``launches``, those whose output a fused segment
    threw away and ran again larger (``plan/fused.py`` ``_converge``), by
    what had been speculated too small: ``bucket`` (the string byte
    window), ``join_cap`` (a join's output rows or gather bytes),
    ``group_cap`` (a grouped partial aggregate's output rows).  A launch
    short of two of them counts under both.  Empty once a plan's
    capacities have converged."""
    with _LaunchStats.lock:
        return {"launches": _LaunchStats.count,
                "programs": len(_LaunchStats.by_program),
                "by_program": dict(_LaunchStats.by_program),
                "discarded": dict(_LaunchStats.discarded)}


def count_discarded_launch(*reasons: str) -> None:
    with _LaunchStats.lock:
        for reason in reasons:
            _LaunchStats.discarded[reason] = \
                _LaunchStats.discarded.get(reason, 0) + 1


def program_name(kind: str, key: str) -> str:
    """``<kind>_<8 hex digits of a digest of the cache key>``: what a
    shared_jit program is called in the device trace and in
    ``launch_stats()["by_program"]``.  A ``hashlib`` digest and not
    ``hash()``, which is salted per process: the name is part of the
    persistent compile cache's key (JAX hashes the module, and the module
    is named after the function), so a name that changed between
    processes would make every run compile cold."""
    digest = hashlib.blake2s(key.encode("utf-8"), digest_size=4)
    return f"{kind}_{digest.hexdigest()}"


def _counted(name: str, fn):
    def wrapper(*a, **k):
        with _LaunchStats.lock:
            _LaunchStats.count += 1
            _LaunchStats.by_program[name] = \
                _LaunchStats.by_program.get(name, 0) + 1
        return fn(*a, **k)
    wrapper.__wrapped__ = fn
    return wrapper


def _named(fn: Callable, name: str) -> Callable:
    """``fn`` under ``name``, for ``jax.jit`` to call the program after.
    A wrapper and not ``fn.__name__ = name``: ``make_fn`` may hand back a
    module-level function or a ``partial`` that other keys share."""
    @functools.wraps(fn)
    def named(*a, **k):
        return fn(*a, **k)
    named.__name__ = named.__qualname__ = name
    return named


def shared_jit(key: str, make_fn: Callable[[], Callable], *, kind: str,
               **jit_kwargs):
    """Return a jitted function shared by all execs with the same plan key.

    ``make_fn`` is only called on a cache miss; the key must fully determine
    the computation (expression tree incl. dtypes, schemas, static params).
    ``kind`` is a short identifier of what the program does
    (``agg_partial``, ``sort_local``, ...): with a digest of the key it
    names the program in the device trace (``program_name``).

    CONTRACT: the function ``make_fn`` returns must NOT close over an exec
    instance (``self``) — cached entries outlive queries, and an exec pins
    its children chain down to the scan's input batches.  Close over the
    plan parameters (exprs, schemas) only.
    """
    from spark_rapids_tpu.config import current_session_timezone
    # session timezone is an ambient input of datetime extraction programs
    # (the tz table bakes in as a trace-time constant); key on it so a
    # tz change never reuses another zone's compiled program
    key = f"{key}|tz={current_session_timezone()}"
    with _JIT_CACHE_LOCK:
        fn = _JIT_CACHE.get(key)
        if fn is not None:
            _JIT_CACHE.move_to_end(key)
            return fn
    import jax
    from spark_rapids_tpu.memory.arena import translate_device_oom
    if _COMPILE_HOOK is not None:
        _COMPILE_HOOK(key)   # may raise: compile budget exceeded
    # a REAL XLA RESOURCE_EXHAUSTED from any cached program enters the
    # retry/spill machinery as TpuRetryOOM (DeviceMemoryEventHandler analog)
    name = program_name(kind, key)
    made = _counted(name, translate_device_oom(
        jax.jit(_named(make_fn(), name), **jit_kwargs)))
    with _JIT_CACHE_LOCK:
        fn = _JIT_CACHE.setdefault(key, made)   # racer may have won; reuse
        _JIT_CACHE.move_to_end(key)
        _trim_jit_cache_locked()
    return fn


def alias_shared_jit(key_from: str, key_to: str) -> None:
    """Register the program cached under ``key_from`` under ``key_to`` too.

    The fused-segment path compiles under a pre-trace capacity key (the
    defaults are only seeded during tracing) but looks up subsequent
    batches under the converged-caps key — without the alias every segment
    would XLA-compile a byte-identical program twice."""
    from spark_rapids_tpu.config import current_session_timezone
    tz = f"|tz={current_session_timezone()}"
    with _JIT_CACHE_LOCK:
        fn = _JIT_CACHE.get(key_from + tz)
        if fn is not None and (key_to + tz) not in _JIT_CACHE:
            _JIT_CACHE[key_to + tz] = fn
            while len(_JIT_CACHE) > _JIT_CACHE_MAX:   # keep the LRU bound
                _JIT_CACHE.popitem(last=False)


def expr_cache_key(e) -> str:
    """Canonical signature of a bound expression tree for shared_jit keys.

    repr() alone is unsafe (lit(5) INT vs LONG print the same), so walk the
    tree recording class names, dtypes, and scalar attributes."""
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.expressions.core import Expression
    atoms: List[str] = []

    def walk(x):
        atoms.append(type(x).__name__)
        try:
            atoms.append(repr(x.dtype))
        except Exception:
            atoms.append("?")
        for k in sorted(vars(x)):
            if k == "children" or k.startswith("_"):
                # private attrs are derived caches (e.g. a compiled DFA);
                # the public fields (pattern, dtype, ...) determine them
                continue
            v = vars(x)[k]
            if isinstance(v, Expression) or (
                    isinstance(v, tuple) and v
                    and all(isinstance(t, Expression) for t in v)):
                continue  # reached via children
            if isinstance(v, (str, int, float, bool, bytes, type(None),
                              T.DataType)):
                atoms.append(f"{k}={v!r}")
            else:
                atoms.append(f"{k}~{type(v).__name__}:{v!r}")
        atoms.append("(")
        for c in x.children:
            walk(c)
        atoms.append(")")

    walk(e)
    return "|".join(atoms)


def exprs_cache_key(exprs) -> str:
    return ";".join(expr_cache_key(e) for e in exprs)


def schema_cache_key(s: Schema) -> str:
    return repr(s)


class Metric:
    def __init__(self, name: str, level: str = "MODERATE"):
        self.name = name
        self.level = level
        self.value = 0
        self._lazy: list = []

    def add(self, v) -> None:
        """Accepts ints or device scalars.  Device scalars are accumulated
        unresolved and only synced at snapshot time — a metric must never
        force a device round-trip on the hot path (the analog of the
        reference keeping metrics off the kernel path, GpuMetrics.scala)."""
        if isinstance(v, (int, float)):
            self.value += v
        else:
            self._lazy.append(v)

    def resolve(self) -> int:
        if self._lazy:
            self.value += sum(int(x) for x in self._lazy)
            self._lazy.clear()
        return self.value


_METRIC_LEVELS = {"ESSENTIAL": 0, "MODERATE": 1, "DEBUG": 2}


class MetricSet:
    """Per-exec metrics registry (GpuMetrics.scala:89 analog)."""

    def __init__(self):
        self._metrics: Dict[str, Metric] = {}

    def metric(self, name: str, level: str = "MODERATE") -> Metric:
        if name not in self._metrics:
            self._metrics[name] = Metric(name, level)
        return self._metrics[name]

    def snapshot(self, level: str = "DEBUG") -> Dict[str, int]:
        """Metrics at or below the requested verbosity
        (spark.rapids.sql.metrics.level: ESSENTIAL < MODERATE < DEBUG)."""
        cut = _METRIC_LEVELS.get(level.upper(), 2)
        return {k: m.resolve() for k, m in self._metrics.items()
                if _METRIC_LEVELS.get(m.level, 1) <= cut}


class TpuExec:
    """Base physical operator."""

    def __init__(self, children: Tuple["TpuExec", ...], schema: Schema):
        self.children = children
        self._schema = schema
        self.metrics = MetricSet()
        # standard metric names (GpuExec.scala:196-206)
        self.op_time = self.metrics.metric("opTime", "ESSENTIAL")
        self.output_rows = self.metrics.metric("numOutputRows", "ESSENTIAL")
        self.output_batches = self.metrics.metric("numOutputBatches")

    @property
    def schema(self) -> Schema:
        return self._schema

    def num_partitions(self) -> int:
        if self.children:
            return self.children[0].num_partitions()
        return 1

    def execute_partition(self, idx: int) -> Iterator[ColumnarBatch]:
        raise NotImplementedError(type(self).__name__)

    def node_name(self) -> str:
        return type(self).__name__

    def describe(self) -> str:
        return self.node_name()

    def tree_string(self, indent: int = 0) -> str:
        lines = ["  " * indent + self.describe()]
        for c in self.children:
            lines.append(c.tree_string(indent + 1))
        return "\n".join(lines)

    def _count_out(self, batch: ColumnarBatch) -> ColumnarBatch:
        self.output_batches.add(1)
        return batch

    def cleanup(self) -> None:
        """Release retained resources (shuffle catalogs, broadcast builds)
        after the query finishes — the ShuffleCleanupManager analog
        (Plugin.scala:497-521).  Recurses the exec tree."""
        for c in self.children:
            c.cleanup()


class timed:
    """Context manager adding wall time to a metric and, given a
    registered span name, recording the same interval as a trace range
    (NvtxWithMetrics analog: the range paired with the metric)."""

    def __init__(self, metric: Metric, span: Optional[str] = None):
        self.metric = metric
        self.span = trace_range(span) if span is not None else None

    def __enter__(self):
        if self.span is not None:
            self.span.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.metric.add(time.perf_counter_ns() - self.t0)
        if self.span is not None:
            self.span.__exit__(*exc)
        return False


def collect_trace_consts(exprs):
    """Gather per-expression device constants (e.g. compiled DFA tables)
    from an expression tree, in deterministic walk order.

    These must enter jitted step functions as ARGUMENTS, not closed-over
    concrete arrays: a closed-over array becomes a hoisted executable
    parameter, which trips jax-0.9 dispatch when equivalent computations
    are traced under more than one jit wrapper (kernels/cast_strings.py
    note).  Returns a flat list of arrays; bind_trace_consts() re-attaches
    them inside the trace by repeating the same walk.
    """
    out = []

    def walk(e):
        tc = getattr(e, "trace_consts", None)
        if tc is not None:
            out.extend(tc())
        for c in e.children:
            walk(c)
    for e in exprs:
        walk(e)
    return out


def bind_trace_consts(exprs, arrays):
    """exprs + flat (possibly traced) array list -> {id(expr): [arrays]}."""
    mapping = {}
    it = iter(arrays)

    def walk(e):
        tc = getattr(e, "trace_consts", None)
        if tc is not None:
            n = len(tc())
            mapping[id(e)] = [next(it) for _ in range(n)]
        for c in e.children:
            walk(c)
    for e in exprs:
        walk(e)
    return mapping


def tree_uses_string_bucket(exprs) -> bool:
    """Does any expression subtree contain a byte-window (regex/DFA) node
    that needs a static string bucket threaded through EvalContext?"""
    def walk(e) -> bool:
        if getattr(e, "uses_string_bucket", False):
            return True
        return any(walk(c) for c in e.children)
    return any(walk(e) for e in exprs)


def regex_bucket(batch, exprs) -> int:
    """STATIC byte bound for the regex/byte-window expressions in `exprs`:
    the max live string length over the batch's string columns, maxed with
    any string-literal byte length in the trees (a CASE branch returning a
    literal longer than every column value must still fit the window).
    Safe for the non-growing string children the planner admits under
    regex nodes.  Returns 0 when no subtree needs one (no device sync)."""
    if not tree_uses_string_bucket(exprs):
        return 0
    from spark_rapids_tpu.expressions.core import BoundReference, Literal
    from spark_rapids_tpu.kernels import strings as SK

    # only the string columns/literals referenced UNDER bucket-consuming
    # nodes matter: syncing every string column would inflate the window
    # (and the jit variant count) with unrelated long columns
    ordinals = set()
    lit_len = [0]

    def collect(e):
        if isinstance(e, BoundReference) and getattr(
                e.dtype, "variable_width", False):
            ordinals.add(e.ordinal)
        if isinstance(e, Literal) and isinstance(e.value, str):
            lit_len[0] = max(lit_len[0], len(e.value.encode("utf-8")))
        for c in e.children:
            collect(c)

    def walk(e):
        if getattr(e, "uses_string_bucket", False):
            collect(e)
            return
        for c in e.children:
            walk(c)
    for e in exprs:
        walk(e)
    # ONE device sync over every referenced string column (the previous
    # per-column int() loop stalled dispatch once per column)
    m = max(lit_len[0], SK.max_live_bytes_multi(
        (batch.columns[ci], batch.num_rows) for ci in ordinals))
    return SK.bucket_for(m)


def jit_bucketed_step(key: str, exprs, make_call, *, kind: str):
    """Shared project/filter wiring: collect trace consts once, then per
    batch compute the static regex bucket, key the shared_jit cache on it,
    and invoke with (batch, consts).  ``make_call(string_bucket)`` returns
    the traceable fn(batch, consts); ``kind`` names the program
    (shared_jit)."""
    import jax.numpy as _jnp
    from spark_rapids_tpu.expressions.bridge import tree_has_bridge
    exprs = tuple(exprs)
    consts = tuple(_jnp.asarray(a) for a in collect_trace_consts(exprs))

    if tree_has_bridge(exprs):
        # CPU-bridged steps run EAGERLY: the host round-trip inside
        # CpuBridgeExpression cannot live under jax.jit; surrounding
        # device expressions still execute as (op-by-op) XLA
        return lambda batch: make_call(regex_bucket(batch, exprs))(
            batch, consts)

    def call(batch):
        bkt = regex_bucket(batch, exprs)
        fn = shared_jit(f"{key}|{bkt}", lambda: make_call(bkt), kind=kind)
        return fn(batch, consts)
    return call


def string_key_bucket(batch, exprs) -> int:
    """Shared max-bytes bucket over BoundReference string key expressions
    (one tiny device sync per string key; 0 when no string keys).  The
    planner restricts string keys to plain column refs so the bucket is
    computable before the jitted kernel runs."""
    from spark_rapids_tpu.expressions.core import Alias, BoundReference
    from spark_rapids_tpu.kernels import strings as SK
    pairs = []
    for e in exprs:
        while isinstance(e, Alias):
            e = e.child
        if isinstance(e, BoundReference) and e.dtype.variable_width:
            pairs.append((batch.columns[e.ordinal], batch.num_rows))
    if not pairs:
        return 0
    # ONE device sync across every string key column
    return SK.bucket_for(SK.max_live_bytes_multi(pairs))
