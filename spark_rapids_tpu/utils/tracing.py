"""Named trace ranges with a documented registry.

Reference: NvtxRangeWithDoc.scala (911 LoC) — every profiling range has a
registered name + docstring, emitted into docs so traces are navigable
(docs/dev/nvtx_profiling.md).  The TPU twin emits
jax.profiler.TraceAnnotation ranges (visible in XLA/Perfetto traces) plus a
lightweight in-process span log usable without a profiler attached.

Usage:
    with trace_range("agg.partial", "per-batch update aggregation"):
        ...
Registered names + docs are dumped by tools/generate_docs.py.
"""
from __future__ import annotations

import collections
import os
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

from spark_rapids_tpu.utils import obs

_registry: Dict[str, str] = {}
_lock = threading.Lock()

#: spans the log keeps: the newest ones, so a process that leaves the log
#: on cannot grow without bound (a traced slice of the benchmark records
#: some thousands)
SPAN_LOG_CAPACITY = 1 << 18


class SpanLog:
    """In-process span collector (enable() to start; snapshot() to read):
    a ring of the newest ``SPAN_LOG_CAPACITY`` spans on the
    ``time.perf_counter`` clock."""

    def __init__(self, capacity: int = SPAN_LOG_CAPACITY):
        self.enabled = False
        self._spans: "collections.deque[Tuple[str, float, float]]" = \
            collections.deque(maxlen=capacity)
        self._lock = threading.Lock()

    def record(self, name: str, t0: float, t1: float) -> None:
        if self.enabled:
            with self._lock:
                self._spans.append((name, t0, t1))

    def snapshot(self) -> List[Tuple[str, float, float]]:
        with self._lock:
            return list(self._spans)

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()

    def summary(self) -> Dict[str, Tuple[int, float]]:
        """name -> (count, total seconds)."""
        out: Dict[str, Tuple[int, float]] = {}
        for name, t0, t1 in self.snapshot():
            c, t = out.get(name, (0, 0.0))
            out[name] = (c + 1, t + (t1 - t0))
        return out


span_log = SpanLog()

#: the sampler asks for the interpreter lock this often: the interpreter's
#: own switch interval, so under plain bytecode contention a waiter has the
#: lock within one of them and more lateness means a C call that kept it
SAMPLER_INTERVAL_S = 0.005
#: a tick that ran this long after it was due is a ``host.lock_wait`` span
LOCK_WAIT_MIN_S = 0.001
#: and one this late keeps the other threads' frames
LATE_TICK_S = 0.020
LATE_TICKS_KEPT = 256
LATE_TICK_FRAMES = 8
SAMPLER_THREAD_NAME = "tpu-stack-sampler"


def _collapse(frame) -> str:
    """A thread's whole stack as one collapsed-stack line, outermost first
    (the flamegraph toolchain's interchange format)."""
    parts: List[str] = []
    while frame is not None:
        code = frame.f_code
        parts.append(f"{os.path.basename(code.co_filename)}:{code.co_name}")
        frame = frame.f_back
    return ";".join(reversed(parts))


def _innermost(frame) -> List[str]:
    """The innermost ``LATE_TICK_FRAMES`` frames of a thread, innermost
    first, each as ``file:line:function``."""
    out: List[str] = []
    while frame is not None and len(out) < LATE_TICK_FRAMES:
        code = frame.f_code
        out.append(f"{os.path.basename(code.co_filename)}:{frame.f_lineno}:"
                   f"{code.co_name}")
        frame = frame.f_back
    return out


class StackProfile:
    """Collapsed stacks ("frame;frame;frame count" lines, what
    flamegraph.pl and speedscope ingest) of every thread of the process,
    one sample a tick of the sampler while the profile is held."""

    def __init__(self):
        self.counts: collections.Counter = collections.Counter()
        self.samples = 0

    def collapsed_stacks(self) -> List[str]:
        return [f"{stack} {n}" for stack, n in self.counts.most_common()]

    def write(self, path: str) -> None:
        with open(path, "w") as f:
            f.write("\n".join(self.collapsed_stacks()) + "\n")


LateTick = Tuple[float, float, Dict[str, List[str]]]


class StackSampler:
    """The process's one sampler: a daemon thread that asks for the
    interpreter lock every ``SAMPLER_INTERVAL_S`` and knows how late it got
    it.  A tick over ``LOCK_WAIT_MIN_S`` late is a ``host.lock_wait`` span
    in ``span_log`` (from when it was due to when it ran; written then, so
    not in the profiler's trace).  A tick over ``LATE_TICK_S`` late also
    keeps, in a ring of the newest ``LATE_TICKS_KEPT``, the innermost
    frames of every other thread as they stand at that moment: the thread
    that held the lock is the one standing at the line of its C call.

    It runs while ``span_log.enabled`` is true or a profile is held
    (``hold`` / ``release``: ``utils/profiler.QueryProfiler``), and its
    loop ends by itself within two intervals of neither being so.  For a
    held profile it also collapses every thread's whole stack each tick
    (every thread of the process: two profiled queries running at once
    each see the union of both)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self._profiles: List[StackProfile] = []
        self._late: "collections.deque[LateTick]" = collections.deque(
            maxlen=LATE_TICKS_KEPT)

    def ensure_running(self) -> None:
        """Start the thread unless it runs (a second ``collect()``, or a
        concurrent one, finds it running)."""
        with self._lock:
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._run, daemon=True, name=SAMPLER_THREAD_NAME)
                self._thread.start()

    def hold(self) -> StackProfile:
        """A new profile, sampled from now until ``release``."""
        profile = StackProfile()
        with self._lock:
            self._profiles.append(profile)
        self.ensure_running()
        return profile

    def release(self, profile: StackProfile) -> None:
        with self._lock:
            self._profiles = [p for p in self._profiles if p is not profile]

    def late_ticks(self) -> List[LateTick]:
        """The newest ticks over ``LATE_TICK_S`` late: ``(due, ran, {thread
        name: innermost frames, as file:line:function})`` on the
        ``time.perf_counter`` clock."""
        with self._lock:
            return list(self._late)

    def clear(self) -> None:
        with self._lock:
            self._late.clear()

    def _run(self) -> None:
        own = threading.get_ident()
        while True:
            due = time.perf_counter() + SAMPLER_INTERVAL_S
            time.sleep(SAMPLER_INTERVAL_S)      # gives the lock up
            ran = time.perf_counter()
            # the whole tick under the sampler's lock: a profile that
            # release() has returned for is written to no more
            with self._lock:
                if not self._profiles and not span_log.enabled:
                    self._thread = None
                    return
                late = ran - due
                if late > LOCK_WAIT_MIN_S:
                    span_log.record("host.lock_wait", due, ran)
                if self._profiles or late > LATE_TICK_S:
                    self._read_frames(
                        own, (due, ran) if late > LATE_TICK_S else None)

    def _read_frames(self, own: int,
                     late_tick: Optional[Tuple[float, float]]) -> None:
        """One look at every other thread's frames: the late tick's ring
        entry and each held profile's sample.  A method of its own so that
        the frames, which keep their threads' locals alive, are let go
        before the next sleep."""
        frames = {ident: frame
                  for ident, frame in sys._current_frames().items()
                  if ident != own}
        if late_tick is not None:
            stacks: Dict[str, List[str]] = {}
            names = {t.ident: t.name for t in threading.enumerate()}
            for ident, frame in frames.items():
                name = names.get(ident, str(ident))
                if name in stacks:
                    name = f"{name}#{ident}"
                stacks[name] = _innermost(frame)
            self._late.append((*late_tick, stacks))
        for profile in self._profiles:
            for frame in frames.values():
                profile.counts[_collapse(frame)] += 1
            profile.samples += 1


sampler = StackSampler()


def late_ticks() -> List[LateTick]:
    """The sampler's ring of ticks over ``LATE_TICK_S`` late."""
    return sampler.late_ticks()


def register_range(name: str, doc: str) -> None:
    with _lock:
        if name in _registry and _registry[name] != doc:
            raise ValueError(f"trace range {name!r} re-registered with a "
                             "different doc")
        _registry[name] = doc


def registered_ranges() -> Dict[str, str]:
    with _lock:
        return dict(_registry)


_TraceAnnotation = None     # jax.profiler's, imported at the first range


class trace_range:
    """Named range: registers (once), annotates the XLA trace, logs a
    span — and records into the ambient per-query trace (utils/obs.py)
    with the span that caused it as ``parent``, so a range that ran on
    behalf of a query lands on that query's timeline, with the open-span
    stack maintained for the stall watchdog's "which query, where"
    reports.

    A class and not a generator: a range with every sink off is two clock
    reads, a push and a pop."""

    __slots__ = ("name", "t0", "t0_epoch", "span_id", "parent", "_ann")

    def __init__(self, name: str, doc: Optional[str] = None):
        if doc is not None and name not in _registry:
            register_range(name, doc)
        self.name = name

    def __enter__(self):
        global _TraceAnnotation
        if _TraceAnnotation is None:
            from jax.profiler import TraceAnnotation
            _TraceAnnotation = TraceAnnotation
        self.t0 = time.perf_counter()
        self.t0_epoch = time.time()
        self.parent = obs.current_span_id()
        self.span_id = obs.push_open_span(self.name)
        self._ann = _TraceAnnotation(self.name)
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        # recorded on every exit (matching obs.span): a range a query
        # FAILED or was cancelled inside is exactly the one its timeline
        # needs
        self._ann.__exit__(*exc)
        obs.pop_open_span()
        span_log.record(self.name, self.t0, time.perf_counter())
        tr = obs.current_query_trace()
        if tr is not None:
            tr.record_span(self.name, self.t0_epoch, time.time(),
                           span_id=self.span_id, parent=self.parent)
        return False


def record_range(name: str, t0: float, t0_epoch: float) -> None:
    """A range that is known to be one only when it ends (a launch is a
    ``fused.discard`` once its feedback has condemned it): from ``t0``
    (``time.perf_counter``; ``t0_epoch`` the same instant on
    ``time.time``) to now, into the span log and the ambient per-query
    trace, a child of the span open on this thread.  The profiler's trace
    cannot be written after the fact and does not get it."""
    span_log.record(name, t0, time.perf_counter())
    tr = obs.current_query_trace()
    if tr is not None:
        tr.record_span(name, t0_epoch, time.time(),
                       span_id=obs.new_span_id(),
                       parent=obs.current_span_id())


def generate_ranges_doc() -> str:
    """docs/trace_ranges.md content, emitted from the STATIC range
    table below — deterministic regardless of which modules ran (a
    lazily trace_range-registered name would make the byte-matched doc
    depend on import order; the drift lint instead requires every call
    site's literal name to appear in the static table)."""
    names = static_ranges()
    lines = [
        "# Trace range registry",
        "",
        "Generated from spark_rapids_tpu.utils.tracing (the "
        "NvtxRangeWithDoc analog: every named range documents itself).",
        "",
        "| Range | What it covers |",
        "|---|---|",
    ]
    for name in sorted(names):
        lines.append(f"| `{name}` | {names[name]} |")
    return "\n".join(lines) + "\n"


def static_ranges() -> Dict[str, str]:
    """The statically registered range table (name -> doc)."""
    return dict(_STATIC_RANGES)


# -- static range registry -----------------------------------------------------
#
# Every span name used with trace_range(), timed(metric, name) or
# obs.span() anywhere in the package is registered HERE at import time,
# so docs/trace_ranges.md can be generated deterministically
# (tools/generate_docs.py) and the tpu-lint drift rule can byte-match it —
# the same docs-from-code discipline configs.md pins.  Call sites may still pass doc= lazily,
# but the doc string must match this table (register_range raises on a
# conflicting re-registration).
_STATIC_RANGES = (
    # one collect() (api/session.py, plan/engine.py)
    ("query.collect", "one collect(): plan, execute, fetch; the root "
                      "every other span of the query descends from"),
    ("query.plan", "logical plan -> physical plan: optimizer, tagging, "
                   "CBO, conversion, fusion, LORE"),
    ("query.finish", "after the last batch of execute(): the per-exec "
                     "metric report (one device -> host transfer per "
                     "lazily kept row count) and plan cleanup"),
    ("query.fetch", "final device -> host transfer and row building, "
                    "after execute() returned"),
    # io / scan (plan/execs/scan.py + io/reader_pool.py + io/parquet.py)
    ("scan.open", "file open on the reader pool up to the first row "
                  "group being ready: footer parse, row-group pruning, "
                  "coalesced open (inside the file's first scan.decode)"),
    ("scan.decode", "host-side decode of ONE chunk on the reader pool "
                    "(no device semaphore held; the wait on a full "
                    "prefetch queue is outside it)"),
    ("scan.wait", "task waiting for a decoded chunk "
                  "(semaphore released)"),
    ("scan.upload", "Arrow host chunk -> HBM batch upload "
                    "(semaphore held)"),
    # host -> device hand-over (columnar/column.py put_plane)
    ("upload.put", "one host plane (a column's data, validity or offsets) "
                   "handed to the runtime: what jnp.asarray costs the "
                   "calling thread, not the transfer's completion "
                   "(nested in scan.upload where a scan uploads)"),
    # the sampler thread (utils/tracing.py StackSampler)
    ("host.lock_wait", "a tick of the sampler that got the interpreter "
                       "lock more than 1 ms late: from when the tick was "
                       "due to when it ran (written then: in the span "
                       "log, not the profiler's trace or a query's)"),
    # fused segments (plan/fused.py)
    ("fused.batch", "host work for one fused-program call: key "
                    "building, shared_jit lookup, dispatch, retry loop, "
                    "feedback"),
    ("fused.feedback", "task thread blocked on the device for the "
                       "capacity feedback of one fused-program call"),
    ("fused.discard", "one launch of a fused program that was thrown away "
                      "and run again larger: from its dispatch to the "
                      "feedback that condemned it (written then: in the "
                      "span log and the query trace, not the profiler's)"),
    # final aggregate + HAVING (plan/execs/aggregate.py, coalesce.py)
    ("agg.final", "the final aggregate's own work for one reduce group: "
                  "pull the group's pieces from the exchange's read side, "
                  "dispatch the combine (or, out of core, one bucket's "
                  "merge and finalize)"),
    ("agg.out_of_core", "one per reduce group that outgrew the in-core "
                        "bound: its partials sub-partitioned into "
                        "spillable buckets (keyless: the tree merge)"),
    ("batch.shrink", "maybe_shrink of a batch over the floor capacity: "
                     "the host sync on its row count and, where it is "
                     "sparse, the regather dispatch"),
    # joins (plan/execs/join.py)
    ("join.build", "a join's own work to have one reduce group's (or the "
                   "broadcast) build side ready: pull its pieces from the "
                   "exchange's read side, or coalesce its batches"),
    ("join.probe", "one probe group against its build: the pull of its "
                   "pieces, the probe launch (which folds both sides' "
                   "pieces) and the condition or expansion launches with "
                   "their host syncs, and the output's shrink"),
    ("join.retry", "one launch of a join's expansion or condition program "
                   "that was run again at a larger capacity: from its "
                   "dispatch to the status that condemned it (written "
                   "then: in the span log and the query trace, not the "
                   "profiler's)"),
    ("join.decide", "an adaptive join's own work once its build side is "
                    "pulled: the count of its rows (a host sync) and the "
                    "building of the inner plan"),
    ("join.out_of_core", "one per join partition past the in-core bound: "
                         "both sides sub-partitioned into spillable "
                         "co-buckets"),
    # exchange + range sort (plan/execs/exchange.py, range_sort.py)
    ("exchange.write", "the exchange's own map-side work for one map "
                       "batch: slice dispatch, counts sync or download, "
                       "transport write (not the child's compute)"),
    ("exchange.read", "reduce side: one pull from the transport's "
                      "reader, or the coalescing concat"),
    ("sort.range", "the ORDER BY's own work after its child is drained: "
                   "coalesce or bound sampling and routing, and each "
                   "partition's local sort dispatch"),
    # serving control plane (serving/admission.py; obs.span; admission,
    # follow and run also go to the span log)
    ("serving.submit", "one serving submission end-to-end: cache "
                       "lookup, admission, execution (also in the span "
                       "log)"),
    ("serving.admission", "admission wait: slots + byte-budget "
                          "semaphores (priority-then-FIFO; also in the "
                          "span log)"),
    ("serving.follow", "a single-flight follower waiting for the leader "
                       "that runs the same plan, up to its answer or its "
                       "failure (also in the span log)"),
    ("serving.run", "admitted query executing under its tenant scope "
                    "(LocalSessionRunner or ClusterDriverRunner; also in "
                    "the span log)"),
    # driver control plane (cluster/driver.py; obs.span)
    ("driver.query", "one cluster submission attempt: dispatch through "
                     "last rank result"),
    ("driver.dispatch", "driver queueing the per-rank task protos"),
    # executor task path (cluster/executor.py; obs.span)
    ("executor.task", "one rank's whole task: plan, map sides, output "
                      "partitions"),
    ("executor.plan", "executor-local planning of the shipped logical "
                      "plan"),
    ("executor.output", "executor output loop: this rank's share of "
                        "root partitions"),
    # shuffle data plane (shuffle/pipeline.py; obs.span)
    ("shuffle.pipeline.produce", "pipelined exchange producer running "
                                 "on its hand-off thread"),
    # elasticity control loop (cluster/autoscaler.py; obs.span)
    ("autoscale.decide", "one autoscaler policy tick: read signals, "
                         "apply hysteresis/cooldowns, emit a decision"),
    ("autoscale.scale_out", "executor launch requested by a scale-out "
                            "decision (pending until the join lands)"),
    ("autoscale.scale_in", "graceful drain of a sustained-idle rank "
                           "requested by a scale-in decision"),
)
for _n, _d in _STATIC_RANGES:
    register_range(_n, _d)
del _n, _d
