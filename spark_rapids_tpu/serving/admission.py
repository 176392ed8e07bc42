"""Admission control + tenant-budgeted scheduling: the serving tier.

``QueryQueue`` is the driver-side front door that turns the engine from
a batch runner into a server: concurrent ``submit()`` calls tagged with
a tenant and priority are

  1. served from the plan-fingerprint result cache when possible (a hit
     never consumes admission or dispatches work — serving/cache.py);
  2. gated by a MEMORY-AWARE admission policy: a slots semaphore
     (``spark.rapids.serving.maxConcurrentQueries``) and, when the
     device arena has a byte budget, a byte-weighted semaphore sized at
     ``admission.memoryFraction`` of it — both are
     ``WeightedPrioritySemaphore``s (memory/semaphore.py), so waiters
     drain in priority-then-FIFO order, the discipline the device
     semaphore pins (reference: GpuSemaphore/PrioritySemaphore,
     GpuSemaphore.scala:183,512);
  3. queued with timeout/backpressure: more than ``queue.maxDepth``
     waiting queries rejects immediately, an admission wait past
     ``queue.timeout`` rejects with ``AdmissionRejected`` — overload is
     surfaced, never silently buffered without bound;
  4. executed under the tenant's ambient scope (memory/tenant.py): the
     query's device residency charges the tenant's budget, its spill
     order follows the tenant's weight, and a budget breach self-spills
     and self-retries instead of OOM-killing a neighbor.

Execution itself is pluggable: ``LocalSessionRunner`` runs plans
in-process under the device semaphore (one serving process = one chip),
``ClusterDriverRunner`` dispatches through ``TpuClusterDriver.submit``
(whose per-executor task queues interleave independent queries across
executors).  Counters: queries_admitted/queued/rejected plus the cache
and tenant families (shuffle/stats.py) ride the cluster-stats snapshot
and the bench artifact.
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Callable, Dict, Optional

import itertools

from spark_rapids_tpu.memory.semaphore import WeightedPrioritySemaphore
from spark_rapids_tpu.memory.tenant import TENANT_CONF_KEY, TENANTS
from spark_rapids_tpu.shuffle.stats import HISTOGRAMS, SHUFFLE_COUNTERS
from spark_rapids_tpu.testing.chaos import CHAOS
from spark_rapids_tpu.utils.cancel import (
    CANCELS, CancelToken, QueryCancelled, cancellable_wait)
from spark_rapids_tpu.utils.obs import span
from spark_rapids_tpu.utils.telemetry import record_event

from spark_rapids_tpu.serving.cache import (
    ResultCache, UncacheableError, plan_fingerprint)
from spark_rapids_tpu.serving.overload import OverloadController


class AdmissionRejected(RuntimeError):
    """Admission control refused the query.  ``reason`` is
    ``"queue_full"`` (backpressure: too many queries already waiting),
    ``"timeout"`` (waited past the queue timeout without being
    admitted), or — with overload protection armed
    (serving/overload.py) — ``"shed"`` (priority-aware load shedding
    under SLO pressure), ``"ratelimited"`` (tenant over its token-
    bucket rate), or ``"breaker"`` (this plan fingerprint's circuit
    breaker is open)."""

    def __init__(self, message: str, reason: str, tenant: str):
        super().__init__(message)
        self.reason = reason
        self.tenant = tenant


class QueryContext:
    """What a runner gets alongside the plan."""

    def __init__(self, tenant: str, priority: int, conf_overrides: dict,
                 cancel_token: Optional[CancelToken] = None):
        self.tenant = tenant
        self.priority = priority
        self.conf_overrides = dict(conf_overrides)
        self.cancel_token = cancel_token


class LocalSessionRunner:
    """In-process execution: one serving process drives one device.
    Device gating stays where it lives — the engine acquires the device
    semaphore per partition task — but at THIS query's priority via the
    ``task_priority`` ambient, so concurrent admitted queries time-share
    the chip in serving-priority order, inside the tenant scope the
    QueryQueue already established."""

    def __init__(self, conf: Optional[dict] = None):
        from spark_rapids_tpu.api.session import TpuSession
        self.session = TpuSession(dict(conf or {}))

    def __call__(self, plan, ctx: QueryContext) -> list:
        import copy

        from spark_rapids_tpu.api.session import DataFrame
        from spark_rapids_tpu.memory.semaphore import task_priority
        sess = self.session
        if ctx.conf_overrides:
            sess = copy.copy(self.session)
            sess.conf = self.session.conf.with_overrides(
                **ctx.conf_overrides)
        with task_priority(ctx.priority):
            return DataFrame(plan, sess).collect()


class ClusterDriverRunner:
    """Cluster execution through TpuClusterDriver.submit (thread-safe:
    concurrent queries queue per executor and interleave).  The tenant
    rides the per-query conf overrides so executors run the task under
    the tenant's scope."""

    def __init__(self, driver, timeout_s: float = 300.0):
        self.driver = driver
        self.timeout_s = timeout_s

    def __call__(self, plan, ctx: QueryContext) -> list:
        conf = dict(ctx.conf_overrides)
        conf[TENANT_CONF_KEY] = ctx.tenant
        # the serving token IS the cluster query's cancel handle: the
        # driver's polling loop observes it, broadcasts cancel_query to
        # executors and tears the query down (cluster/driver.py)
        return self.driver.submit(plan, timeout_s=self.timeout_s,
                                  conf=conf,
                                  cancel_token=ctx.cancel_token)


class QueryQueue:
    """Admission controller + serving facade (see module doc).

    ``runner(plan, ctx)`` executes one admitted query and returns rows;
    priority is LOWER-FIRST (the PrioritySemaphore convention)."""

    def __init__(self, runner: Callable, conf=None,
                 cache: Optional[ResultCache] = None):
        from spark_rapids_tpu.config import RapidsConf
        if conf is None or isinstance(conf, dict):
            conf = RapidsConf(conf or {})
        self.conf = conf
        self.runner = runner
        self.max_concurrent = max(conf.serving_max_concurrent, 1)
        self.queue_max_depth = max(conf.serving_queue_max_depth, 0)
        self.queue_timeout_s = conf.serving_queue_timeout
        self._slots = WeightedPrioritySemaphore(self.max_concurrent)
        #: atomic admission-queue depth: the maxDepth bound must hold
        #: under a stampede, so the count-and-enter is one locked step
        #: (reading the semaphore's waiting() then enqueueing would let
        #: every racer pass the same snapshot)
        self._depth = 0
        self._depth_lock = threading.Lock()
        # memory-aware admission: only meaningful when the arena has a
        # byte budget (unbudgeted arenas admit on slots alone).  Sized
        # LAZILY on first admission, not at construction: a cluster-side
        # QueryQueue is often built before initialize_memory configures
        # the arena, and a constructor-time snapshot of budget 0 would
        # silently disable the byte bound forever
        self.admission_bytes = 0
        self._bytes: Optional[WeightedPrioritySemaphore] = None
        self._bytes_init = threading.Lock()
        self.default_query_bytes = conf.serving_admission_query_bytes
        self.cache = cache if cache is not None else (
            ResultCache(conf.serving_cache_max_bytes,
                        conf.serving_cache_ttl)
            if conf.serving_cache_enabled else None)
        TENANTS.configure(conf.serving_tenant_default_budget,
                          conf.serving_tenant_default_weight,
                          conf.serving_tenants_spec)
        #: overload protections (serving/overload.py): None unless
        #: spark.rapids.serving.overload.enabled — with the knob off no
        #: overload state exists and the submit path is byte-identical
        #: to the pre-overload tier (pinned by test)
        self.overload: Optional[OverloadController] = (
            OverloadController(conf)
            if conf.serving_overload_enabled else None)
        self._pool: Optional[ThreadPoolExecutor] = None
        self._pool_lock = threading.Lock()
        #: single-flight: fingerprint -> the LEADER's completion future.
        #: Concurrent identical submissions (a dashboard miss-storm)
        #: wait for the leader instead of each executing the same plan
        self._inflight: Dict[str, Future] = {}
        self._inflight_lock = threading.Lock()
        #: per-query execution deadline (0 = none): every submission's
        #: CancelToken derives from it, so a runaway query self-cancels
        #: instead of holding slots/bytes forever
        self.query_deadline_s = conf.serving_query_deadline
        #: how long a single-flight follower with no execution deadline
        #: waits for a leader that is still running: the stall watchdog's
        #: threshold (a wait that long is a stall), or queue.timeout with
        #: the watchdog off.  A leader still running then is taken as
        #: wedged, and the follower runs the query itself.
        self.follow_limit_s = (conf.watchdog_stall_seconds
                               or self.queue_timeout_s)
        #: query_id -> live CancelToken — the public cancel() handle
        self._active: Dict[str, CancelToken] = {}
        self._active_lock = threading.Lock()
        self._qid_seq = itertools.count(1)
        #: query-scoped observability (utils/obs.py): every submission
        #: runs under a QueryTrace ambient when enabled — spans +
        #: attributed counters per query instead of interleaved globals;
        #: finished snapshots are kept (bounded) for query_trace()
        self.trace_enabled = conf.trace_enabled
        self.trace_dir = conf.trace_dir
        self.trace_max_spans = conf.trace_max_spans
        import collections
        self._traces: "collections.OrderedDict[str, dict]" = \
            collections.OrderedDict()
        self._traces_max = 32
        self._traces_lock = threading.Lock()
        # resource-plane telemetry (utils/telemetry.py): the sampler
        # reads this queue's slot/byte/depth occupancy every tick —
        # queue depth and admission waits are the autoscaler's signals
        from spark_rapids_tpu.utils.telemetry import register_query_queue
        register_query_queue(self)

    # -- admission -----------------------------------------------------------

    def admission_gauges(self) -> dict:
        """Instantaneous admission occupancy (telemetry sampler): slots
        total/in-use, waiting depth, and the byte budget when sized."""
        g = {"admission_slots_total": self.max_concurrent,
             "admission_slots_in_use": max(
                 self.max_concurrent - self._slots.available(), 0),
             "admission_queue_depth": self._depth,
             "admission_bytes_total": 0, "admission_bytes_in_use": 0}
        bytes_sem = self._bytes
        if bytes_sem is not None:
            g["admission_bytes_total"] = self.admission_bytes
            g["admission_bytes_in_use"] = max(
                self.admission_bytes - bytes_sem.available(), 0)
        return g

    def _ensure_bytes_sem(self) -> None:
        """Size the byte-admission semaphore from the arena's CURRENT
        budget on first use (one-shot: later arena reconfiguration keeps
        the first sizing — outstanding reservations couldn't survive a
        resize)."""
        if self._bytes is not None:
            return
        from spark_rapids_tpu.memory.arena import device_arena
        with self._bytes_init:
            if self._bytes is not None:
                return
            budget = device_arena().budget_bytes
            if not budget:
                return      # unbudgeted arena: slots-only (retry later)
            frac = self.conf.serving_admission_memory_fraction
            self.admission_bytes = max(int(budget * frac), 1)
            self._bytes = WeightedPrioritySemaphore(self.admission_bytes)

    def _admit(self, tenant: str, priority: int, est_bytes: int,
               timeout_s: float) -> int:
        """Take (slot, bytes) or raise AdmissionRejected.  Returns the
        byte cost actually reserved (release must match).  The wall
        time spent here — admitted, rejected or cancelled alike — is
        the admission-wait distribution: it feeds the admission_wait_s
        histogram (whose ring-sampled bucket counts the autoscaler
        diffs for its windowed p99) and the overload shedder's sliding
        window."""
        t0 = time.monotonic()
        try:
            cost = self._admit_inner(tenant, priority, est_bytes,
                                     timeout_s)
        finally:
            waited = time.monotonic() - t0
            HISTOGRAMS["admission_wait_s"].record(waited)
            if self.overload is not None:
                self.overload.record_wait(waited)
        if self.overload is not None:
            # anti-starvation bookkeeping: the shed exemption reads the
            # tenant's last ADMITTED time
            self.overload.note_admitted(tenant)
        return cost

    def _admit_inner(self, tenant: str, priority: int, est_bytes: int,
                     timeout_s: float) -> int:
        self._ensure_bytes_sem()
        # ONE capture: cost computation and the acquire/release pair
        # must see the same semaphore — racing the lazy init could
        # otherwise compute cost 0 then "acquire" from the now-created
        # semaphore, bypassing the byte bound
        bytes_sem = self._bytes
        now = time.monotonic()
        cost = 0
        if bytes_sem is not None:
            # a query estimated over the whole admission budget runs
            # alone (full budget) instead of never admitting
            cost = min(max(int(est_bytes), 1), self.admission_bytes)
        instant = self._slots.acquire(priority, deadline=now)
        if not instant:
            with self._depth_lock:
                if self._depth >= self.queue_max_depth:
                    full = True
                else:
                    full = False
                    self._depth += 1
            if full:
                SHUFFLE_COUNTERS.add(queries_rejected=1)
                record_event("rejection", tenant=tenant,
                             reason="queue_full")
                raise AdmissionRejected(
                    f"admission queue full ({self.queue_max_depth} "
                    f"waiting): tenant {tenant!r} rejected",
                    reason="queue_full", tenant=tenant)
            SHUFFLE_COUNTERS.add(queries_queued=1)
            try:
                ok = self._slots.acquire(priority,
                                         deadline=now + timeout_s)
            finally:
                with self._depth_lock:
                    self._depth -= 1
            if not ok:
                SHUFFLE_COUNTERS.add(queries_rejected=1)
                record_event("rejection", tenant=tenant,
                             reason="timeout")
                raise AdmissionRejected(
                    f"admission wait exceeded {timeout_s:.1f}s: tenant "
                    f"{tenant!r} rejected", reason="timeout",
                    tenant=tenant)
        if bytes_sem is not None:
            try:
                ok = bytes_sem.acquire(priority, cost=cost,
                                       deadline=now + timeout_s)
            except BaseException:
                # the byte wait is a CANCELLATION POINT: a cancel (or
                # token deadline) raising out of it must give back the
                # slot already held, or every such cancel leaks one
                # admission slot permanently
                self._slots.release()
                raise
            if not ok:
                self._slots.release()
                SHUFFLE_COUNTERS.add(queries_rejected=1)
                record_event("rejection", tenant=tenant,
                             reason="timeout")
                raise AdmissionRejected(
                    f"admission byte budget wait exceeded "
                    f"{timeout_s:.1f}s ({cost}b of "
                    f"{self.admission_bytes}b): tenant {tenant!r} "
                    "rejected", reason="timeout", tenant=tenant)
        SHUFFLE_COUNTERS.add(queries_admitted=1)
        record_event("admission", tenant=tenant, cost_bytes=cost)
        return cost

    def _release(self, cost: int) -> None:
        # cost > 0 implies the byte semaphore existed at admission (it
        # is created once and never replaced, so this is the same one)
        if cost and self._bytes is not None:
            self._bytes.release(cost)
        self._slots.release()

    # -- submission ----------------------------------------------------------

    def cancel(self, query_id: str,
               reason: str = "cancelled by caller") -> bool:
        """Cancel an in-flight submission by its ``query_id``: the id
        passed to submit(), the ``query_id`` attribute of the Future
        submit_async() returned (auto-assigned ids are pre-minted
        there), or one from ``active_queries()``.  Cooperative: the
        query's token flips, every blessed wait and batch boundary
        under it raises ``QueryCancelled``, and cleanup (admission
        release, tenant refund, shuffle drop) runs on the submitting
        thread's unwind.  Returns False for an unknown/finished id (an
        async submission registers at submit entry on its worker
        thread — a cancel racing that hand-off can simply retry)."""
        with self._active_lock:
            token = self._active.get(query_id)
        if token is None:
            return False
        return token.cancel(reason)

    def active_queries(self) -> list:
        """Ids of submissions currently in flight (cancel() handles)."""
        with self._active_lock:
            return sorted(self._active)

    def query_trace(self, query_id: str) -> Optional[dict]:
        """Finished submission's trace snapshot (spans, per-query
        attributed counters, duration, merged executor telemetry) —
        None when tracing was off or the id aged out."""
        with self._traces_lock:
            snap = self._traces.get(query_id)
            return dict(snap) if snap is not None else None

    def _finish_trace(self, trace, query_id: str) -> None:
        """Seal + stash + export one submission's trace (never fails
        the submission: export IO errors are logged and swallowed by
        obs.export_trace_file)."""
        from spark_rapids_tpu.utils.obs import export_trace_file
        trace.finish()
        snap = trace.snapshot()
        path = (export_trace_file(trace, self.trace_dir)
                if self.trace_dir else None)
        if path:
            snap["export_path"] = path
        with self._traces_lock:
            self._traces[query_id] = snap
            while len(self._traces) > self._traces_max:
                self._traces.popitem(last=False)

    def _mint_query_id(self) -> str:
        """Fresh auto id, dodging caller-supplied ids (caller holds
        ``_active_lock``)."""
        qid = f"q{next(self._qid_seq)}"
        while qid in self._active:
            qid = f"q{next(self._qid_seq)}"
        return qid

    def submit(self, plan, tenant: str = "default", priority: int = 0,
               est_bytes: Optional[int] = None,
               timeout_s: Optional[float] = None,
               conf: Optional[dict] = None,
               cacheable: bool = True,
               query_id: Optional[str] = None,
               deadline_s: Optional[float] = None) -> list:
        """Run one logical plan for ``tenant`` and return its rows.
        Blocks through admission (bounded by ``timeout_s`` or the
        queue.timeout conf) and runs the query on THIS thread.  Cache
        hits return without consuming admission or dispatching work.

        Every submission runs under a deadline-derived ``CancelToken``
        (``deadline_s`` or spark.rapids.serving.query.deadline; 0 =
        no deadline), registered under ``query_id`` (auto-assigned when
        None) so ``cancel(query_id)`` stops it mid-flight with a typed
        ``QueryCancelled`` — releasing its admission slot/bytes and
        tenant bytes on the way out instead of running to completion."""
        CHAOS.delay("serving.admit.delay")
        overrides = dict(conf or {})
        # the admission deadline; a single-flight follower's wait for a
        # running leader is not admission and moves it on
        # (_submit_under_token)
        budget_s = self.queue_timeout_s if timeout_s is None else timeout_s
        deadline = time.monotonic() + budget_s
        exec_deadline = (self.query_deadline_s if deadline_s is None
                         else deadline_s)
        # a follower's wait: its own execution deadline bounds it where
        # there is one (the token), else follow_limit_s
        follow_s = None if exec_deadline else self.follow_limit_s
        token = CancelToken(label="serving query",
                            deadline_s=exec_deadline or None)
        with self._active_lock:
            if query_id is None:
                query_id = self._mint_query_id()
            elif query_id in self._active:
                # overwriting would orphan the in-flight submission's
                # token — it could never be cancelled again, the exact
                # leak this layer exists to prevent
                raise ValueError(
                    f"query_id {query_id!r} is already in flight; "
                    "cancel it first or choose a distinct id")
            self._active[query_id] = token
        token.label = f"serving query {query_id!r}"
        # the PROCESS-WIDE active-query registry (utils/cancel.py): the
        # flight recorder stamps post-mortems from CANCELS.active_ids(),
        # so a serving submission must be visible there even with
        # tracing off (cluster tasks register executor-side already)
        CANCELS.register(query_id, token)
        #: single-flight state shared with the except/finally clauses
        #: (the helper fills it in as it learns the key/role)
        sf = {"key": None, "leader": None}
        # query-scoped observability: the submission runs under a
        # QueryTrace ambient (utils/obs.py) — engine task threads,
        # pipeline producers and fetch workers inherit it, so spans and
        # counter deltas attribute to THIS query; the cluster runner
        # ships the same context to executors and merges their task
        # telemetry back under it
        from contextlib import nullcontext

        from spark_rapids_tpu.utils.obs import QueryTrace, trace_scope
        trace = (QueryTrace(query_id, enabled=True,
                            max_spans=self.trace_max_spans,
                            default_track="serving")
                 if self.trace_enabled else None)
        t_sub = time.monotonic()
        # the token is ambient for the WHOLE submission — admission
        # waits, the single-flight follower wait, and the runner (whose
        # engine threads inherit it) are all cancellation points
        with token.scope(), \
                (trace_scope(trace) if trace is not None
                 else nullcontext()):
            try:
                with span("serving.submit", anchor=True, log=True,
                          tags={"tenant": tenant, "priority": priority}):
                    return self._submit_under_token(
                        plan, tenant, priority, est_bytes, overrides,
                        cacheable, deadline, budget_s, follow_s, token, sf)
            except QueryCancelled as e:
                # count THIS submission only when ITS OWN token was
                # cancelled: a single-flight follower unwinding with the
                # leader's QueryCancelled is collateral, not a second
                # cancelled query (and the cluster driver skips counting
                # for a serving-owned token — one cancel, one count)
                if token.cancelled():
                    SHUFFLE_COUNTERS.add(queries_cancelled=1)
                if sf["leader"] is not None:
                    sf["leader"].set_exception(e)
                raise
            except BaseException as e:
                if sf["leader"] is not None:
                    sf["leader"].set_exception(e)
                raise
            finally:
                # submit->done latency distribution: every submission
                # (hits, rejections, cancels included — the latency the
                # CALLER saw), p50/p90/p99 in cluster stats and the
                # --concurrent bench artifact
                HISTOGRAMS["serving_submit_s"].record(
                    time.monotonic() - t_sub)
                if trace is not None:
                    self._finish_trace(trace, query_id)
                CANCELS.unregister(query_id, token)
                with self._active_lock:
                    if self._active.get(query_id) is token:
                        del self._active[query_id]
                if sf["leader"] is not None:
                    with self._inflight_lock:
                        if self._inflight.get(sf["key"]) is sf["leader"]:
                            del self._inflight[sf["key"]]

    def _submit_under_token(self, plan, tenant, priority, est_bytes,
                            overrides, cacheable, deadline, budget_s,
                            follow_s, token, sf) -> list:
        """Cache lookup + single-flight + admission + execution of one
        submission (submit()'s body; the caller owns token registration
        and leader-future completion on the error paths)."""
        key = sources = None
        if self.cache is not None and cacheable:
            try:
                key, sources = plan_fingerprint(plan, overrides)
            except UncacheableError:
                key = None
            if key is not None:
                hit = self.cache.get(key, tenant=tenant)
                if hit is not None:
                    return hit
                # single-flight: the FIRST miss becomes the leader; the
                # concurrent rest wait for it and serve from the entry
                # it stores — a dashboard miss-storm executes once
                with self._inflight_lock:
                    existing = self._inflight.get(key)
                    if existing is None:
                        sf["key"] = key
                        sf["leader"] = Future()
                        self._inflight[key] = sf["leader"]
                if sf["leader"] is None and existing is not None:
                    # follower: waits for the leader while it runs, not
                    # by the admission deadline the two share, up to
                    # follow_s (None: its own deadline, by its token); a
                    # CANCELLED leader unblocks its followers with the
                    # QueryCancelled itself (the fingerprint's one
                    # execution was deliberately stopped — re-running it
                    # would defeat the cancel); any other failure, or a
                    # leader still running past follow_s, falls through
                    # to a normal execution of our own, whose admission
                    # gets the whole budget from then on
                    try:
                        with span("serving.follow", log=True,
                                  tags={"tenant": tenant}):
                            cancellable_wait(
                                existing, timeout=follow_s, token=token,
                                site="serving.single_flight")
                    except QueryCancelled:
                        raise
                    except Exception:  # noqa: BLE001  # tpu-lint: allow-swallow(the leader raises its own failure to its own caller; a follower deliberately falls through to execute the query itself, which surfaces any real error)
                        pass
                    else:
                        hit = self.cache.get(key, tenant=tenant)
                        if hit is not None:
                            return hit
                    deadline = time.monotonic() + budget_s
        # overload gate (serving/overload.py): rate limit -> breaker ->
        # shed, each a typed rejection BEFORE any slot is queued for.
        # The breaker keys on the plan fingerprint even when the result
        # cache is off/uncacheable-for-caching reasons didn't fire —
        # an unfingerprintable plan simply has no breaker.
        fp = key
        if self.overload is not None:
            if fp is None:
                try:
                    fp, _ = plan_fingerprint(plan, overrides)
                except UncacheableError:
                    fp = None
            self.overload.check(tenant, priority, fp)
        with span("serving.admission", anchor=True, log=True,
                  tags={"tenant": tenant}):
            cost = self._admit(
                tenant, priority,
                self.default_query_bytes if est_bytes is None
                else est_bytes,
                max(deadline - time.monotonic(), 0.001))
        try:
            # chaos serving.runner.stall: the runner wedges in a
            # REGISTERED wait (the stall the watchdog must catch;
            # cancelOnStall then frees this very submission)
            hit = CHAOS.fire("serving.runner.stall")
            if hit is not None:
                cancellable_wait(
                    threading.Event(),
                    timeout=float(hit.get("seconds", 30.0)),
                    token=token, site="serving.runner.stall")
            ctx = QueryContext(tenant, priority, overrides,
                               cancel_token=token)
            with TENANTS.scope(tenant), \
                    span("serving.run", anchor=True, log=True,
                         tags={"tenant": tenant}):
                rows = self.runner(plan, ctx)
            token.check()   # a cancel that raced completion wins
        except QueryCancelled:
            # a deliberate stop says nothing about the plan: the
            # breaker must not trip toward open on cancels
            raise
        except BaseException:
            if self.overload is not None:
                self.overload.record_outcome(fp, ok=False)
            raise
        finally:
            self._release(cost)
        if self.overload is not None:
            self.overload.record_outcome(fp, ok=True)
        if key is not None:
            self.cache.put(key, rows, sources, tenant=tenant)
        if sf["leader"] is not None:
            sf["leader"].set_result(True)
        return rows

    def submit_async(self, plan, **kw):
        """``submit`` on a worker thread; returns a Future carrying the
        submission's ``query_id`` attribute (auto ids are pre-minted
        HERE so async callers have a cancel() handle — submit's return
        value is the rows, so an id minted inside it would be
        unreachable).  The pool is sized past the admission bound so
        queued queries can WAIT in the admission queue (where priority
        ordering lives) rather than in the pool's FIFO."""
        if kw.get("query_id") is None:
            with self._active_lock:
                kw["query_id"] = self._mint_query_id()
        with self._pool_lock:
            if self._pool is None:
                self._pool = ThreadPoolExecutor(
                    max_workers=self.max_concurrent
                    + self.queue_max_depth,
                    thread_name_prefix="serving")
        # tpu-lint: allow-ambient-propagation(submit() establishes its OWN token/tenant/priority scopes per submission; inheriting the async caller's ambients would leak one query's context into another's execution)
        fut = self._pool.submit(self.submit, plan, **kw)
        fut.query_id = kw["query_id"]
        return fut

    def invalidate_source(self, source: str) -> int:
        """Explicit cache invalidation for one source (file path, table
        path, or ResultCache.source_token of an in-memory relation)."""
        if self.cache is None:
            return 0
        return self.cache.invalidate_source(source)

    def close(self) -> None:
        with self._pool_lock:
            pool, self._pool = self._pool, None
        if pool is not None:
            pool.shutdown(wait=True)
