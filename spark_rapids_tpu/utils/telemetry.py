"""Continuous resource-plane telemetry: sampler ring, event log, flight
recorder, and the metric-name registry.

PR 13's query-scoped plane (utils/obs.py) answers "what did THIS query
do"; this module is its complement — "what was the SYSTEM doing at
t=42s": arena occupancy, pinned/spilled bytes, admission queue depth,
semaphore slots, fetch/pipeline in-flight bytes, sampled continuously
into a bounded ring.  The reference ships the same numbers as
executor-plugin metrics a Prometheus scraper polls; Theseus and
Presto-on-GPU (PAPERS.md) both treat this resource timeline as the
substrate for disaggregated scheduling — it is the signal layer ROADMAP
item 5's autoscaler reads (queue depth, admission waits).

Three pieces:

  * ``TelemetrySampler`` (the ``TELEMETRY`` singleton) — a daemon
    configured via ``initialize_memory`` (knobs
    ``spark.rapids.metrics.{enabled,intervalMs,ringSeconds}``) that
    every interval snapshots the resource GAUGES plus the cumulative
    counters/histograms into a ring bounded to ``ringSeconds`` worth of
    samples.  ``sample_now()`` only READS live state (it never
    constructs the spill framework or a serving queue as a side
    effect); disabled, no daemon samples and the cost is zero.
  * cluster collection — executors piggyback their latest sample on the
    existing heartbeat (no new RPC; legacy peers that send none stay
    compatible), the driver's ``HeartbeatRegistry`` keeps per-rank
    rings, and the block server answers a ``metrics`` wire op that
    ``tools/metrics_scrape.py`` renders as Prometheus text exposition.
  * flight recorder — an ALWAYS-ON bounded recent-events log (spills,
    OOM retries, admissions/rejections, cancels, executor join/leave)
    plus the ring, dumped as a JSON post-mortem through the existing
    ``utils/crashdump.py`` path on watchdog stall, OOM-retry
    exhaustion, and executor loss — stamped with the active query ids
    so a post-mortem correlates with the PR 13 trace exports.

Every metric name this plane emits is registered in the static tables
below; ``docs/metrics.md`` is generated from them
(tools/generate_docs.py) and byte-matched by the tpu-lint drift rule,
and the scrape tool refuses to render an unregistered name — the same
docs-from-code discipline as configs.md and trace_ranges.md.

Module import is stdlib-only (the counter/arena/spill imports are lazy
inside the sampling functions), so low-level modules — cancel, spill,
net — can import this one without cycles.
"""
from __future__ import annotations

import logging
import threading
import time
import weakref
from collections import deque
from typing import Dict, List, Optional

log = logging.getLogger(__name__)


# -- metric-name registry (docs/metrics.md; drift-linted) ----------------------
#
# GAUGES are instantaneous readings the sampler takes; COUNTERS are the
# cumulative families it snapshots beside them (shuffle/stats.py _FIELDS
# plus the spill byte totals); HISTOGRAMS are shuffle/stats.py
# HISTOGRAMS.  tools/metrics_scrape.py refuses any name absent here.

_STATIC_GAUGES = (
    ("arena_used_bytes",
     "device arena bytes currently reserved (memory/arena.py bookkept "
     "residency)"),
    ("arena_budget_bytes",
     "device arena byte budget (0 = unlimited bookkeeping mode)"),
    ("arena_peak_bytes",
     "high watermark of arena_used_bytes since process start"),
    ("spill_device_resident_bytes",
     "bytes of spillable handles currently device-resident "
     "(memory/spill.py)"),
    ("spill_pinned_bytes",
     "bytes of device-resident handles currently PINNED (a consumer "
     "holds the materialized batch; no spill can reclaim them)"),
    ("spill_host_bytes",
     "bytes of handles spilled to host memory"),
    ("spill_disk_bytes",
     "bytes of handles spilled through to disk files"),
    ("spill_handles",
     "live (unclosed) spillable handles registered with the framework"),
    ("semaphore_slots_total",
     "device-semaphore permits (spark.rapids.sql.concurrentTpuTasks)"),
    ("semaphore_slots_in_use",
     "device-semaphore permits currently held by tasks"),
    ("semaphore_waiters",
     "threads queued on the device semaphore"),
    ("admission_slots_total",
     "serving admission slots (spark.rapids.serving."
     "maxConcurrentQueries, summed over live QueryQueues)"),
    ("admission_slots_in_use",
     "admission slots held by admitted queries"),
    ("admission_queue_depth",
     "queries WAITING for admission (the autoscaler's primary signal)"),
    ("admission_bytes_total",
     "byte-weighted admission budget (0 until the arena is budgeted)"),
    ("admission_bytes_in_use",
     "admission bytes reserved by admitted queries"),
    ("fetch_inflight_bytes",
     "reduce-fetch bytes in flight (fetched but unconsumed, summed "
     "over live BlockFetchIterators; shuffle/net.py flow window)"),
    ("pipeline_inflight_bytes",
     "bytes parked in pipelined-exchange hand-off queues "
     "(shuffle/pipeline.py)"),
    ("tenant_used_bytes",
     "per-tenant device bytes in use (labeled tenant=<name>; "
     "memory/tenant.py ledger)"),
    ("tenant_peak_bytes",
     "per-tenant high watermark of tenant_used_bytes (labeled "
     "tenant=<name>)"),
)

#: cumulative spill byte totals sampled beside the ShuffleCounters
#: snapshot (SpillMetrics fields; prometheus type: counter)
_SPILL_COUNTERS = (
    ("spill_to_host_bytes", "cumulative device->host spill bytes"),
    ("spill_to_disk_bytes", "cumulative host->disk spill bytes"),
    ("read_spill_bytes", "cumulative bytes reloaded from spill files"),
)


def _counter_names() -> List[str]:
    from spark_rapids_tpu.shuffle.stats import _FIELDS
    return list(_FIELDS) + [n for n, _ in _SPILL_COUNTERS]


def _histogram_names() -> List[str]:
    from spark_rapids_tpu.shuffle.stats import HISTOGRAMS
    return sorted(HISTOGRAMS)


def registered_metrics() -> Dict[str, str]:
    """name -> kind (gauge|counter|histogram) over every registered
    metric — the scrape tool's validation table."""
    out = {n: "gauge" for n, _ in _STATIC_GAUGES}
    for n in _counter_names():
        out[n] = "counter"
    for n in _histogram_names():
        out[n] = "histogram"
    return out


def generate_metrics_doc() -> str:
    """docs/metrics.md content, emitted from the static tables (the
    configs.md/trace_ranges.md docs-from-code discipline: the tpu-lint
    drift rule byte-matches the committed file against this)."""
    from spark_rapids_tpu.shuffle.stats import _FIELDS
    lines = [
        "# Metric-name registry",
        "",
        "Generated by tools/generate_docs.py from "
        "spark_rapids_tpu.utils.telemetry.  Every series the resource-"
        "plane sampler emits (and tools/metrics_scrape.py renders as "
        "Prometheus text) is registered here; the scrape tool refuses "
        "unregistered names and the tpu-lint drift rule byte-matches "
        "this file.",
        "",
        "## Gauges (sampled every spark.rapids.metrics.intervalMs)",
        "",
        "| Name | What it reads |",
        "|---|---|",
    ]
    for name, doc in _STATIC_GAUGES:
        lines.append(f"| `{name}` | {doc} |")
    lines += [
        "",
        "## Counters",
        "",
        "The cumulative shuffle/serving data-plane counters "
        "(shuffle/stats.py `_FIELDS`; see that table for per-counter "
        "semantics) snapshotted with every sample, plus the spill byte "
        "totals:",
        "",
        "| Name | What it counts |",
        "|---|---|",
    ]
    for name in _FIELDS:
        lines.append(f"| `{name}` | shuffle/stats.py `_FIELDS` entry "
                     f"(process-wide cumulative) |")
    for name, doc in _SPILL_COUNTERS:
        lines.append(f"| `{name}` | {doc} |")
    lines += [
        "",
        "## Histograms",
        "",
        "Fixed-bucket latency histograms (shuffle/stats.py "
        "`HISTOGRAMS`), rendered as native Prometheus histograms "
        "(cluster-aggregated bucket-wise via `Histogram.merge`):",
        "",
        "| Name | What it measures |",
        "|---|---|",
        "| `admission_wait_s` | time one serving submission spent in "
        "admission (QueryQueue._admit) — the autoscaler/shedder SLO "
        "signal |",
        "| `fetch_wait_s` | reduce consumer blocked on an empty "
        "prefetch queue |",
        "| `serving_submit_s` | serving submit()->rows wall time per "
        "submission |",
        "| `stage_drain_s` | pipelined-exchange consumer blocked on an "
        "empty hand-off |",
        "",
    ]
    return "\n".join(lines)


# -- live in-flight gauges (updated by the shuffle data plane) -----------------

class LiveGauge:
    """Lock-guarded running total the data plane adjusts as bytes enter
    and leave flight (one add per fetch batch / hand-off item — far off
    the per-block hot path)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._value = 0

    def add(self, delta: int) -> None:
        with self._lock:
            self._value += int(delta)

    def value(self) -> int:
        with self._lock:
            return self._value

    def reset(self) -> None:
        with self._lock:
            self._value = 0


#: reduce-fetch bytes in flight (shuffle/net.py BlockFetchIterator)
FETCH_INFLIGHT = LiveGauge()
#: pipelined-exchange hand-off bytes (shuffle/pipeline.py _Pipe)
PIPELINE_INFLIGHT = LiveGauge()

#: live serving QueryQueues (weak: a closed/dropped queue must not keep
#: reporting phantom admission capacity)
_QUERY_QUEUES: "weakref.WeakSet" = weakref.WeakSet()


def register_query_queue(queue) -> None:
    _QUERY_QUEUES.add(queue)


# -- sampling ------------------------------------------------------------------

def _spill_gauges() -> Dict[str, int]:
    """Read the spill store WITHOUT constructing it (a sampler must
    never create the singleton framework as a side effect)."""
    from spark_rapids_tpu.memory import spill as _spill
    fw = _spill._FRAMEWORK
    out = {"spill_device_resident_bytes": 0, "spill_pinned_bytes": 0,
           "spill_host_bytes": 0, "spill_disk_bytes": 0,
           "spill_handles": 0}
    if fw is None:
        return out
    g = fw.gauges()
    out.update(g)
    return out


def sample_now() -> dict:
    """One JSON-safe snapshot of every resource gauge + the cumulative
    counters/histograms.  Read-only: no framework construction, no
    device sync, no I/O."""
    from spark_rapids_tpu.memory.arena import device_arena
    from spark_rapids_tpu.memory.semaphore import tpu_semaphore
    from spark_rapids_tpu.memory.tenant import TENANTS
    from spark_rapids_tpu.memory import spill as _spill
    from spark_rapids_tpu.shuffle.stats import histograms, shuffle_counters
    arena = device_arena()
    gauges = {
        "arena_used_bytes": int(arena.used_bytes),
        "arena_budget_bytes": int(arena.budget_bytes),
        "arena_peak_bytes": int(arena.peak_bytes),
        "fetch_inflight_bytes": FETCH_INFLIGHT.value(),
        "pipeline_inflight_bytes": PIPELINE_INFLIGHT.value(),
    }
    gauges.update(_spill_gauges())
    gauges.update(tpu_semaphore().occupancy())
    adm = {"admission_slots_total": 0, "admission_slots_in_use": 0,
           "admission_queue_depth": 0, "admission_bytes_total": 0,
           "admission_bytes_in_use": 0}
    for q in list(_QUERY_QUEUES):
        try:
            for k, v in q.admission_gauges().items():
                adm[k] += int(v)
        except Exception:  # noqa: BLE001
            # a queue mid-teardown must not fail the sample; the series
            # simply misses its contribution for this tick
            log.debug("admission gauge read failed", exc_info=True)
    gauges.update(adm)
    counters = shuffle_counters()
    fw = _spill._FRAMEWORK
    if fw is not None:
        counters["spill_to_host_bytes"] = int(fw.metrics.spill_to_host_bytes)
        counters["spill_to_disk_bytes"] = int(fw.metrics.spill_to_disk_bytes)
        counters["read_spill_bytes"] = int(fw.metrics.read_spill_bytes)
    else:
        counters["spill_to_host_bytes"] = 0
        counters["spill_to_disk_bytes"] = 0
        counters["read_spill_bytes"] = 0
    tenants = {name: {"used_bytes": snap["used_bytes"],
                      "peak_bytes": snap["peak_bytes"]}
               for name, snap in TENANTS.snapshot().items()}
    return {"t": time.time(), "gauges": gauges, "tenants": tenants,
            "counters": counters, "histograms": histograms()}


class TelemetrySampler:
    """The ``TELEMETRY`` singleton: sampler daemon + ring + event log +
    flight recorder."""

    #: bound on the always-on recent-events log
    EVENTS_MAX = 256

    def __init__(self):
        self._lock = threading.Lock()
        self.enabled = False
        self.interval_ms = 250
        self.ring_seconds = 60
        self._ring: deque = deque(maxlen=240)
        self._events: deque = deque(maxlen=self.EVENTS_MAX)
        self._thread: Optional[threading.Thread] = None
        self._wake = threading.Event()
        #: most recent flight_record() post-mortem (in-memory twin of
        #: the crashdump artifact, for tests and in-process inspection)
        self.last_postmortem: Optional[dict] = None

    # -- configuration -------------------------------------------------------

    def configure(self, enabled: bool, interval_ms: int = 250,
                  ring_seconds: int = 60) -> None:
        """Apply the metrics conf (initialize_memory path).  Enabling
        starts the daemon; the ring is re-bounded (existing samples kept
        up to the new bound).  Repeated calls with the same values are
        no-ops for the ring."""
        with self._lock:
            self.enabled = bool(enabled)
            self.interval_ms = max(int(interval_ms), 10)
            self.ring_seconds = max(int(ring_seconds), 1)
            maxlen = max(self.ring_seconds * 1000 // self.interval_ms, 1)
            if self._ring.maxlen != maxlen:
                self._ring = deque(self._ring, maxlen=maxlen)
            if self.enabled:
                self._ensure_thread_locked()
        self._wake.set()

    def _ensure_thread_locked(self) -> None:
        if self._thread is not None and self._thread.is_alive():
            return
        # tpu-lint: allow-ambient-propagation(the sampler is a process-wide daemon reading EVERY query's shared resource gauges; binding it to one query's ambients would be wrong by construction)
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="tpu-telemetry")
        self._thread.start()

    def _loop(self) -> None:
        while True:
            with self._lock:
                enabled = self.enabled
                interval = self.interval_ms / 1000.0
            self._wake.wait(interval if enabled else 2.0)
            self._wake.clear()
            if not self.enabled:    # re-read: configure() wakes this wait
                continue
            try:
                self.sample(only_if_enabled=True)
            except Exception:  # noqa: BLE001
                # the sampler must never die to a transient read race;
                # one missing tick beats a silent telemetry blackout
                log.warning("telemetry sample failed", exc_info=True)

    # -- ring ----------------------------------------------------------------

    def sample(self, only_if_enabled: bool = False) -> dict:
        """Take one sample into the ring (also the deterministic test
        entry point — callable regardless of the daemon, whose own ticks
        land ``only_if_enabled``: none after configure() turned it
        off)."""
        s = sample_now()
        with self._lock:
            if self.enabled or not only_if_enabled:
                self._ring.append(s)
        return s

    def latest(self) -> Optional[dict]:
        with self._lock:
            return self._ring[-1] if self._ring else None

    def ring(self) -> List[dict]:
        with self._lock:
            return list(self._ring)

    def reset_ring(self) -> None:
        with self._lock:
            self._ring.clear()

    def timeline_summary(self) -> dict:
        """Peaks/totals over the current ring — the per-query resource
        context bench.py embeds beside its rows/s numbers."""
        ring = self.ring()
        if not ring:
            return {"samples": 0}
        peak = {k: max(s["gauges"].get(k, 0) for s in ring)
                for k in ("arena_used_bytes", "spill_pinned_bytes",
                          "admission_queue_depth", "fetch_inflight_bytes",
                          "pipeline_inflight_bytes")}
        def delta(key: str) -> int:
            # the ring's FIRST sample is the window baseline: callers
            # that want an exact delta sample() right after reset_ring()
            # so spill before the first timer tick is never missed
            return int(ring[-1]["counters"].get(key, 0)
                       - ring[0]["counters"].get(key, 0))
        return {
            "samples": len(ring),
            "span_s": round(ring[-1]["t"] - ring[0]["t"], 3),
            "peak_arena_used_bytes": peak["arena_used_bytes"],
            "peak_pinned_bytes": peak["spill_pinned_bytes"],
            "peak_queue_depth": peak["admission_queue_depth"],
            "peak_fetch_inflight_bytes": peak["fetch_inflight_bytes"],
            "peak_pipeline_inflight_bytes":
                peak["pipeline_inflight_bytes"],
            "total_spill_bytes": delta("spill_to_host_bytes"),
            "total_spill_disk_bytes": delta("spill_to_disk_bytes"),
        }

    # -- event log (always on) -----------------------------------------------

    def record_event(self, kind: str, **fields) -> None:
        """Append one bounded flight-recorder event (spill, oom_retry,
        admission, rejection, cancel, executor_join/leave...).  Always
        on: the deque append is the whole cost, and the recent-events
        window is exactly what a post-mortem needs."""
        ev = {"t": time.time(), "kind": kind}
        ev.update(fields)
        with self._lock:
            self._events.append(ev)

    def events(self) -> List[dict]:
        with self._lock:
            return list(self._events)

    def reset_events(self) -> None:
        with self._lock:
            self._events.clear()

    # -- flight recorder -----------------------------------------------------

    def flight_record(self, reason: str, query_ids=None,
                      extra: Optional[dict] = None,
                      sample: Optional[dict] = None) -> Optional[dict]:
        """Assemble and dump one post-mortem: the ring, the event log, a
        sample (the caller's, or a fresh one), and the ACTIVE query ids
        (explicit + the calling thread's ambient trace + every id
        registered in the CANCELS registry) so the artifact correlates
        with the PR 13 trace exports.  Dumped through utils/crashdump.py
        (reason ``flight_recorder:<reason>``); kept in
        ``last_postmortem`` either way.  Diagnostics NEVER raise out of
        here.  Callers on degraded paths (the watchdog) pass ``sample``
        so the gauge sweep — which takes data-plane locks — runs at
        most once, and not at all when a ring sample already exists."""
        try:
            from spark_rapids_tpu.utils.cancel import CANCELS
            from spark_rapids_tpu.utils.obs import current_query_trace
            ids = {str(q) for q in (query_ids or ()) if q is not None}
            tr = current_query_trace()
            if tr is not None:
                ids.add(str(tr.query_id))
            ids.update(str(k) for k in CANCELS.active_ids())
            postmortem = {
                "reason": reason,
                "t": time.time(),
                "active_query_ids": sorted(ids),
                "sample": sample if sample is not None else sample_now(),
                "ring": self.ring(),
                "events": self.events(),
                "extra": extra or {},
            }
            from spark_rapids_tpu.utils import crashdump
            path = crashdump.dump_now(f"flight_recorder:{reason}",
                                      extra=postmortem)
            if path:
                postmortem["dump_path"] = path
            with self._lock:
                self.last_postmortem = postmortem
            return postmortem
        except Exception:  # noqa: BLE001
            # the flight recorder runs on failure paths (OOM exhaustion,
            # stall, executor loss) — it must never compound them
            log.warning("flight_record(%s) failed", reason, exc_info=True)
            return None

    # -- wire payload (the `metrics` op; shuffle/net.py serves it) -----------

    def local_metrics(self) -> dict:
        """This process's scrape payload: a fresh sample plus the ring
        (JSON-safe; the block server sends it as the `metrics` reply)."""
        return {"sample": sample_now(), "ring": self.ring(),
                "enabled": self.enabled}

    def reset(self) -> None:
        """Tests: drop ring, events and the last post-mortem."""
        with self._lock:
            self._ring.clear()
            self._events.clear()
            self.last_postmortem = None


TELEMETRY = TelemetrySampler()


def record_event(kind: str, **fields) -> None:
    """Module-level convenience for data-plane call sites."""
    TELEMETRY.record_event(kind, **fields)
