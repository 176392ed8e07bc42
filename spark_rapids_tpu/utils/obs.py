"""Query-scoped observability plane: one trace context per query.

The repo grew every observability primitive in isolation — per-exec
``MetricSet`` (plan/execs/base.py), ``SpanLog``/``trace_range``
(utils/tracing.py), ``QueryProfiler`` flamegraphs, process-global
``ShuffleCounters`` (shuffle/stats.py) and per-program launch
attribution — but none of them were correlated per QUERY or across
processes: two concurrent serving queries interleave one global counter
set, and an executor's stall is a number on the wrong machine.  The
reference stays debuggable because every metric is tagged with the
Spark stage/task that produced it; ``QueryTrace`` is that correlation
point for the TPU stack:

  * a thread-ambient trace context (carried beside the tenant scope,
    task priority and CancelToken by utils/ambient.py, re-entered by
    every engine task thread and blessed worker spawn) holding the
    query id, a bounded SPAN buffer, and a PER-QUERY COUNTER SCOPE —
    ``ShuffleCounters.add``/``set_max`` tee each delta into the ambient
    scope, so concurrent queries get attributed counters instead of
    interleaved globals;
  * ``span(name)`` / ``tracing.trace_range`` record into the ambient
    trace automatically (epoch timestamps, so spans from different
    processes align on one timeline) and maintain a per-thread OPEN-SPAN
    stack the stall watchdog reads to name *which query, where* a
    wedged thread sits;
  * cross-process propagation: the cluster task proto ships the trace
    context, executors return their task spans + per-exec ``MetricSet``
    snapshots + scoped counter deltas in ``task_result``, and the
    driver merges them under the originating query's trace with
    rank/attempt tags (cluster/driver.py / cluster/executor.py);
  * consumption: ``session.explain_analyze`` and
    ``driver.query_report`` render the physical plan annotated with the
    merged metrics, and tools/trace_export.py emits one Perfetto/
    Chrome-trace JSON timeline per query.

Everything here is OFF-hot-path by construction: with no ambient trace
the tee is one ``threading.local`` read, and span recording is a dict
append under the trace's lock (no device sync, no I/O).
"""
from __future__ import annotations

import itertools
import threading
import time
from contextlib import contextmanager
from typing import Dict, List, Optional, Tuple

#: default span-buffer bound (spark.rapids.trace.maxSpans overrides):
#: a long query must never grow an unbounded list on the serving path
DEFAULT_MAX_SPANS = 4096

#: reserved headroom past max_spans for ANCHOR spans — the control-plane
#: spans recorded at query END (serving.submit, driver.query, each
#: rank's executor.task) that give the exported timeline its structure.
#: A span-heavy query fills the buffer with data-plane ranges long
#: before the anchors record; without the reserve the Perfetto export
#: would lose exactly the serving/driver/rank tracks it exists to show.
ANCHOR_HEADROOM = 64

class _Ambient(threading.local):
    """Class-level defaults: reading an attribute a thread never set
    raises inside ``getattr`` before the default is returned, a
    microsecond on every span and counter add."""
    trace: Optional["QueryTrace"] = None
    #: the span open on the thread that spawned this one
    parent_span: Optional[int] = None


class _Open(threading.local):
    stack: Optional[list] = None    # [(name, since_monotonic, id)]


_AMBIENT = _Ambient()
_OPEN = _Open()
_SPAN_IDS = itertools.count(1)      # next() is atomic under the GIL


class QueryTrace:
    """One query's trace context: query id + span buffer + counter scope.

    Thread-safe: engine task threads, pipeline producers and fetch
    workers all record concurrently.  Spans use EPOCH seconds
    (``time.time``) so spans merged from other processes land on the
    same timeline; elapsed math inside one process stays monotonic at
    the recording sites."""

    def __init__(self, query_id, enabled: bool = True,
                 max_spans: Optional[int] = None,
                 default_track: str = "local"):
        self.query_id = str(query_id)
        self.enabled = bool(enabled)
        self.default_track = default_track
        self.max_spans = int(max_spans if max_spans is not None
                             else DEFAULT_MAX_SPANS)
        self.t_submit = time.time()
        self.duration_s: Optional[float] = None
        self.dropped_spans = 0
        self._spans: List[dict] = []
        self._counters: Dict[str, int] = {}
        self._gauges: Dict[str, int] = {}
        #: rank-tagged remote records merged by the driver
        self._remote: List[dict] = []
        self._lock = threading.Lock()

    # -- recording (hot-ish path: bounded, no sync, no I/O) ------------------

    def record_span(self, name: str, t0: float, t1: float,
                    track: Optional[str] = None,
                    tags: Optional[dict] = None,
                    anchor: bool = False,
                    thread: Optional[str] = None,
                    span_id: Optional[int] = None,
                    parent: Optional[int] = None) -> None:
        """``span_id``/``parent``: this span's process-unique id and the
        id of the span that caused it — the innermost span open on the
        recording thread or, for a worker thread's outermost span, the
        one open on the thread that spawned it (``current_span_id``).
        ``parent`` is None for a root.

        ``anchor=True`` marks a control-plane span the timeline's
        STRUCTURE depends on (serving.submit, driver.query, a rank's
        executor.task): anchors may spend the ANCHOR_HEADROOM reserve
        past max_spans, so a query whose data-plane ranges filled the
        buffer still exports with its tracks intact."""
        if not self.enabled:
            return
        span = {"name": name, "t0": t0, "t1": t1,
                "track": track or self.default_track,
                "thread": thread or threading.current_thread().name}
        if span_id is not None:
            span["id"] = span_id
            span["parent"] = parent
        if tags:
            span["tags"] = dict(tags)
        cap = self.max_spans + (ANCHOR_HEADROOM if anchor else 0)
        with self._lock:
            if len(self._spans) >= cap:
                self.dropped_spans += 1
                return
            self._spans.append(span)

    def counter_add(self, deltas: Dict[str, int]) -> None:
        """The scoped TEE target of ``ShuffleCounters.add`` — per-query
        attribution of exactly the deltas the global counters saw."""
        with self._lock:
            for k, v in deltas.items():
                self._counters[k] = self._counters.get(k, 0) + int(v)

    def counter_set_max(self, values: Dict[str, int]) -> None:
        with self._lock:
            for k, v in values.items():
                self._gauges[k] = max(self._gauges.get(k, 0), int(v))

    # -- cross-process merge (driver side) -----------------------------------

    def merge_remote(self, telemetry: dict, rank: int, attempt: int,
                     eid: str) -> None:
        """Fold one executor task's telemetry under this trace: spans
        land on a per-rank track tagged with rank/attempt/executor, and
        counter deltas accumulate into the query scope (remote work is
        still THIS query's work)."""
        track = f"rank{rank}"
        base_tags = {"rank": rank, "attempt": attempt, "eid": eid}
        for s in telemetry.get("spans", ()):
            tags = dict(base_tags)
            tags.update(s.get("tags") or {})
            # each rank's whole-task span is an anchor: the merge runs
            # AFTER the query resolved, when a span-heavy query already
            # filled the buffer — the rank track must still appear.
            # The EXECUTOR-side thread name rides along: the exporter
            # keys tids on it, and restamping the driver's merge thread
            # would collapse a rank's concurrent spans onto one tid
            # (overlapping X events — invalid Chrome trace)
            self.record_span(s["name"], s["t0"], s["t1"], track=track,
                             tags=tags,
                             anchor=(s["name"] == "executor.task"),
                             thread=s.get("thread"))
        deltas = telemetry.get("counters") or {}
        if deltas:
            self.counter_add(deltas)
        with self._lock:
            self.dropped_spans += int(telemetry.get("dropped_spans", 0))
            self._remote.append({
                "rank": rank, "attempt": attempt, "eid": eid,
                "metrics": telemetry.get("metrics") or [],
                "counters": deltas})

    # -- reading -------------------------------------------------------------

    def finish(self) -> None:
        if self.duration_s is None:
            self.duration_s = time.time() - self.t_submit

    def counters_snapshot(self) -> Dict[str, int]:
        with self._lock:
            out = dict(self._counters)
            for k, v in self._gauges.items():
                out[k] = max(out.get(k, 0), v)
            return out

    def spans_snapshot(self) -> List[dict]:
        with self._lock:
            return [dict(s) for s in self._spans]

    def remote_records(self) -> List[dict]:
        with self._lock:
            return [dict(r) for r in self._remote]

    def snapshot(self) -> dict:
        """The export shape tools/trace_export.py and the bench artifact
        consume; JSON-safe by construction."""
        return {"query_id": self.query_id,
                "t_submit": self.t_submit,
                "duration_s": self.duration_s,
                "dropped_spans": self.dropped_spans,
                "spans": self.spans_snapshot(),
                "counters": self.counters_snapshot(),
                "remote": self.remote_records()}


# -- the ambient ---------------------------------------------------------------

def current_query_trace() -> Optional[QueryTrace]:
    return _AMBIENT.trace


@contextmanager
def trace_scope(trace: Optional[QueryTrace],
                parent_span: Optional[int] = None):
    """Make ``trace`` the thread's ambient query trace for the block —
    the exact shape of cancel_scope/tenant scope, and carried by
    utils/ambient.py to every blessed worker spawn.  ``parent_span`` is
    what the spawning thread's ``current_span_id()`` was: the parent of
    this thread's outermost spans."""
    prev = (_AMBIENT.trace, _AMBIENT.parent_span)
    _AMBIENT.trace, _AMBIENT.parent_span = trace, parent_span
    try:
        yield trace
    finally:
        _AMBIENT.trace, _AMBIENT.parent_span = prev


@contextmanager
def task_metrics_tee(trace: Optional[QueryTrace]):
    """Tee this thread's TaskMetrics DELTA over the block into ``trace``
    as ``task_*`` counter keys (semaphore wait, retries, OOM counts).
    Task/worker threads are REUSED across queries and TaskMetrics is
    per-thread cumulative, so only the before/after delta belongs to the
    current task.  The tee lands in the finally — a failed or cancelled
    task still attributes the work it did.  No-op when ``trace`` is
    None; the one shared seam for engine.run_one and executor.run_task."""
    if trace is None:
        yield
        return
    from spark_rapids_tpu.memory import metrics as task_metrics
    before = task_metrics.get().as_dict()
    try:
        yield
    finally:
        after = task_metrics.get().as_dict()
        trace.counter_add({f"task_{k}": after[k] - before[k]
                           for k in after if after[k] != before[k]})


# -- open-span stack (the watchdog's "which query, where" source) --------------

def _open_stack() -> list:
    st = _OPEN.stack
    if st is None:
        st = _OPEN.stack = []
    return st


def new_span_id() -> int:
    """A process-unique span id (for a span recorded when it ends)."""
    return next(_SPAN_IDS)


def push_open_span(name: str) -> int:
    """Open a span on this thread; returns its process-unique id."""
    span_id = new_span_id()
    _open_stack().append((name, time.monotonic(), span_id))
    return span_id


def pop_open_span() -> None:
    st = _open_stack()
    if st:
        st.pop()


def innermost_open_span() -> Optional[Tuple[str, float]]:
    """(name, since_monotonic) of the CURRENT thread's innermost open
    trace range, or None.  The stall watchdog captures this at
    begin_wait so a stall report names the wedged site's enclosing
    span, not just the wait primitive."""
    st = _OPEN.stack
    return st[-1][:2] if st else None


def current_span_id() -> Optional[int]:
    """Id of the span a span opened NOW on this thread descends from:
    the innermost one open here, else the one that was open on the thread
    that spawned this one (``trace_scope``'s ``parent_span``)."""
    st = _OPEN.stack
    return st[-1][2] if st else _AMBIENT.parent_span


@contextmanager
def span(name: str, track: Optional[str] = None,
         tags: Optional[dict] = None, anchor: bool = False,
         log: bool = False):
    """Lightweight named span: records into the ambient QueryTrace (if
    any) and maintains the open-span stack.  Unlike
    ``tracing.trace_range`` it never touches the XLA profiler — this is
    the serving/driver/control-plane span primitive.  Every name used
    with it must be registered in utils/tracing.py's static range table
    (the trace-ranges drift lint pins the discipline).  ``anchor=True``
    for the spans the exported timeline's structure depends on (see
    QueryTrace.record_span).  ``log=True``: the interval also goes to
    ``tracing.span_log`` while that is enabled, as a ``trace_range``'s
    does (the serving spans, which the benchmark's readers read)."""
    t0 = time.time()
    t0_log = time.perf_counter() if log else 0.0
    parent = current_span_id()
    span_id = push_open_span(name)
    try:
        yield
    finally:
        pop_open_span()
        if log:
            from spark_rapids_tpu.utils.tracing import span_log
            span_log.record(name, t0_log, time.perf_counter())
        tr = current_query_trace()
        if tr is not None:
            tr.record_span(name, t0, time.time(), track=track, tags=tags,
                           anchor=anchor, span_id=span_id, parent=parent)


# -- plan instrumentation + metric trees (EXPLAIN ANALYZE machinery) -----------

def metrics_tree(physical, level: str = "DEBUG") -> List[tuple]:
    """[(describe, depth, metric snapshot), ...] over a physical tree at
    the requested metric verbosity, tolerating duck-typed wrapper nodes
    without a MetricSet (the executor's _RankFilteredScan).  The ONE
    tree-to-rows walk — TpuEngine._metrics_report delegates here, so
    explain_analyze's two sources (engine.last_metrics / a fresh walk)
    can never drift in shape."""
    out: List[tuple] = []

    def walk(n, depth):
        ms = getattr(n, "metrics", None)
        snap = ms.snapshot(level) if ms is not None else {}
        out.append((n.describe(), depth, snap))
        for c in n.children:
            walk(c, depth + 1)
    walk(physical, 0)
    return out


def instrument_plan(physical) -> None:
    """Wrap every node's batch seams with row/batch/time accounting so
    EXPLAIN ANALYZE (and traced cluster tasks) report non-zero merged
    metrics for every exec that ran — independent of how much metric
    discipline the exec itself has.  Instruments both
    ``execute_partition`` (the per-op path) and ``stream_pieces`` (the
    fused-across-shuffle path, where an exchange's batches never flow
    through execute_partition).  The analyzer's numbers live under
    DISTINCT metric names (``anRows``/``anBatches``/``anTimeNs``): the
    wrapped time is INCLUSIVE pull-model iterate time (it contains the
    children's compute), which must never pollute the execs' own
    self-time ``opTime``.  Row counts ride ``Metric``'s lazy
    device-scalar accumulation: no sync on the hot path."""
    from spark_rapids_tpu.plan.execs.base import MetricSet
    seen = set()

    def wrap(node):
        if id(node) in seen:
            return
        seen.add(id(node))
        if getattr(node, "metrics", None) is None:
            node.metrics = MetricSet()
        rows = node.metrics.metric("anRows", "ESSENTIAL")
        batches = node.metrics.metric("anBatches")
        an_time = node.metrics.metric("anTimeNs", "ESSENTIAL")
        ep = node.execute_partition

        def timed_exec(idx, _ep=ep, _rows=rows, _batches=batches,
                       _t=an_time):
            it = iter(_ep(idx))
            while True:
                t0 = time.perf_counter_ns()
                try:
                    b = next(it)
                except StopIteration:
                    _t.add(time.perf_counter_ns() - t0)
                    return
                _t.add(time.perf_counter_ns() - t0)
                _batches.add(1)
                _rows.add(b.num_rows)   # device scalar: resolved lazily
                yield b
        node.execute_partition = timed_exec
        sp = getattr(node, "stream_pieces", None)
        if sp is not None:
            def timed_pieces(idx, _sp=sp, _rows=rows, _batches=batches,
                             _t=an_time):
                it = iter(_sp(idx))
                while True:
                    t0 = time.perf_counter_ns()
                    try:
                        piece = next(it)
                    except StopIteration:
                        _t.add(time.perf_counter_ns() - t0)
                        return
                    _t.add(time.perf_counter_ns() - t0)
                    _batches.add(1)
                    _rows.add(piece.rows)
                    yield piece
            node.stream_pieces = timed_pieces
        for c in node.children:
            wrap(c)
    wrap(physical)


def merge_metric_trees(trees: List[List[tuple]]) -> List[tuple]:
    """Sum per-node metric snapshots across ranks.  Plans are identical
    across ranks (the driver's fingerprint guard pins it), so trees
    merge positionally; a shape mismatch (legacy harness, partial
    telemetry) keeps the first tree's row rather than mis-summing."""
    if not trees:
        return []
    base = [(d, depth, dict(snap)) for d, depth, snap in trees[0]]
    for tree in trees[1:]:
        if len(tree) != len(base):
            continue
        for i, (d, depth, snap) in enumerate(tree):
            bd, bdepth, bsnap = base[i]
            if (bd, bdepth) != (d, depth):
                continue
            for k, v in snap.items():
                bsnap[k] = bsnap.get(k, 0) + int(v)
    return base


def render_metrics_tree(tree: List[tuple],
                        footer: Optional[dict] = None) -> str:
    """The EXPLAIN ANALYZE rendering: plan tree, one line per exec,
    annotated with its merged metrics; optional footer of query-scoped
    attribution (launches, counters, wall time).  ``rows=`` prefers the
    exec's own numOutputRows and falls back to the analyzer seam count
    (anRows); ``opTime=`` is the exec's SELF time, falling back to the
    analyzer's inclusive iterate time when the exec recorded none — so
    every node that ran renders non-zero rows and time."""
    _HANDLED = ("numOutputRows", "numOutputBatches", "opTime",
                "anRows", "anBatches", "anTimeNs")
    lines: List[str] = []
    for describe, depth, snap in tree:
        parts = []
        rows = snap.get("numOutputRows") or snap.get("anRows")
        if rows is not None:
            parts.append(f"rows={rows}")
        nb = snap.get("numOutputBatches") or snap.get("anBatches")
        if nb is not None:
            parts.append(f"batches={nb}")
        t = snap.get("opTime") or snap.get("anTimeNs")
        if t is not None:
            # sub-0.1ms self-times must not round down to a zero that
            # reads as "never measured" — drop to microseconds instead
            parts.append(f"opTime={t / 1e6:.1f}ms" if t >= 100_000
                         else f"opTime={t / 1e3:.3f}us")
        for k in sorted(snap):
            if k in _HANDLED:
                continue
            parts.append(f"{k}={snap[k]}")
        annot = f"  [{', '.join(parts)}]" if parts else ""
        lines.append("  " * depth + describe + annot)
    if footer:
        lines.append("")
        for k in sorted(footer):
            v = footer[k]
            if isinstance(v, dict):
                nz = {kk: vv for kk, vv in sorted(v.items()) if vv}
                lines.append(f"{k}: {nz}")
            else:
                lines.append(f"{k}: {v}")
    return "\n".join(lines)


# -- export bridge (spark.rapids.trace.dir) ------------------------------------

def export_trace_file(trace: "QueryTrace", trace_dir: str) -> Optional[str]:
    """Write ``<trace_dir>/query_<id>.trace.json`` via the Perfetto
    exporter (tools/trace_export.py).  Diagnostics must never fail the
    query: any exporter/IO failure is logged and swallowed.  Returns
    the written path or None."""
    if not trace_dir:
        return None
    try:
        from tools.trace_export import export_trace
        safe = "".join(c if c.isalnum() or c in "-_." else "_"
                       for c in str(trace.query_id))
        import os
        return export_trace(trace, os.path.join(
            trace_dir, f"query_{safe}.trace.json"))
    except Exception:  # noqa: BLE001 — diagnostics never fail the query
        import logging
        logging.getLogger(__name__).warning(
            "trace export to %r failed", trace_dir, exc_info=True)
        return None


# -- executor-side telemetry (cluster/executor.py) -----------------------------

def collect_task_telemetry(trace: Optional[QueryTrace],
                           physical=None) -> Optional[dict]:
    """One task's contribution to the originating query's trace:
    task-side spans, the scoped counter deltas, and the per-exec
    MetricSet snapshots — JSON-safe (it rides the task_result header),
    bounded by the trace's span cap."""
    if trace is None or not trace.enabled:
        return None
    out = {"spans": trace.spans_snapshot(),
           "dropped_spans": trace.dropped_spans,
           "counters": {k: v for k, v in
                        trace.counters_snapshot().items() if v}}
    if physical is not None:
        out["metrics"] = [[d, depth, snap]
                          for d, depth, snap in metrics_tree(physical)]
    return out
