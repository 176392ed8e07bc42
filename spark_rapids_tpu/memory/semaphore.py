"""Device semaphore: gate how many tasks use the chip concurrently.

Reference analog: GpuSemaphore/PrioritySemaphore
(GpuSemaphore.scala:183,512; PrioritySemaphore.scala:26) gated by
``spark.rapids.sql.concurrentGpuTasks``.  Tasks acquire before device work
and may release while doing host-side work (e.g. Parquet footer parsing or
Python UDFs), maximizing chip occupancy without oversubscribing HBM.

Priority: lower task-attempt id first (matches the reference's TaskPriority
— older tasks win so progress is monotonic); ties FIFO.
"""
from __future__ import annotations

import heapq
import itertools
import threading
import time
from contextlib import contextmanager
from typing import Optional

from spark_rapids_tpu.memory import metrics as task_metrics


class PrioritySemaphore:
    #: charge waits to the task metric semaphore_wait_ns — DEVICE
    #: semaphores only; admission semaphores (WeightedPrioritySemaphore)
    #: must not pollute a metric that means chip contention
    _record_wait_metric = True

    def __init__(self, permits: int):
        self._permits = permits
        self._size = permits            # configured total (occupancy gauge)
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._waiters = []  # heap of (priority, seq)
        self._dead = set()  # timed-out tickets, lazily popped
        self._seq = itertools.count()

    def _drop_dead_locked(self) -> None:
        while self._waiters and tuple(self._waiters[0]) in self._dead:
            self._dead.discard(tuple(heapq.heappop(self._waiters)))

    def acquire(self, priority: int = 0, cost: int = 1,
                deadline: Optional[float] = None) -> bool:
        """Block until this ticket is at the head of the priority-then-
        FIFO queue AND ``cost`` permits are free, then take them.  With a
        ``deadline`` (time.monotonic() instant) returns False instead of
        blocking past it (the ticket is withdrawn).  cost > 1 is the
        weighted form the serving admission controller builds on — a
        head-of-line ticket holds its place until its full cost fits
        (no starvation of big requests by a stream of small ones).

        CANCELLATION POINT: the wait IS a blessed ``cancellable_wait``
        (utils/cancel.py) — bounded slices, ambient CancelToken checks
        between slices (a cancelled query waiting for the device wakes
        with QueryCancelled, its ticket withdrawn, instead of blocking
        forever), watchdog-registered while actually waiting."""
        from spark_rapids_tpu.utils.cancel import cancellable_wait
        start = time.monotonic_ns()
        acquired = True
        with self._cv:
            ticket = (priority, next(self._seq))
            heapq.heappush(self._waiters, ticket)

            def ready() -> bool:
                self._drop_dead_locked()
                return bool(self._waiters and self._waiters[0] == ticket
                            and self._permits >= cost)
            try:
                if not ready():
                    acquired = cancellable_wait(
                        self._cv, predicate=ready,
                        timeout=(None if deadline is None else
                                 max(deadline - time.monotonic(), 0.0)),
                        site="semaphore.acquire")
                if acquired:
                    heapq.heappop(self._waiters)
                    self._permits -= cost
                    if self._permits > 0 and self._waiters:
                        # wake the next head: it may have re-slept while
                        # we were still queued even though a permit is
                        # free
                        self._cv.notify_all()
            except BaseException:
                # withdrawn ticket (cancel/interrupt): unblock the next
                # head exactly like a deadline withdrawal
                self._dead.add(ticket)
                self._drop_dead_locked()
                self._cv.notify_all()
                raise
            finally:
                if not acquired:
                    self._dead.add(ticket)
                    self._drop_dead_locked()
                    # a withdrawn head unblocks whoever is next
                    self._cv.notify_all()
        if self._record_wait_metric:
            task_metrics.get().semaphore_wait_ns += \
                time.monotonic_ns() - start
        return acquired

    def release(self, cost: int = 1) -> None:
        with self._cv:
            self._permits += cost
            self._cv.notify_all()

    def available(self) -> int:
        with self._cv:
            return self._permits

    def waiting(self) -> int:
        with self._cv:
            return len(self._waiters) - len(self._dead)


class WeightedPrioritySemaphore(PrioritySemaphore):
    """Byte-weighted admission form of the device semaphore: permits are
    a RESOURCE QUANTITY (admission bytes, queue slots), each acquire
    names its cost, and waiters drain in priority-then-FIFO order with a
    deadline.  The serving layer's admission controller
    (serving/admission.py) gates concurrent queries through two of
    these — the same wake discipline the device semaphore pins, grown to
    weighted costs.  Waits here are ADMISSION time, not chip contention:
    they stay out of the semaphore_wait_ns task metric."""

    _record_wait_metric = False


class TpuSemaphore:
    """Per-process singleton gating concurrent device tasks."""

    def __init__(self, concurrent_tasks: int = 2):
        self._sem = PrioritySemaphore(concurrent_tasks)
        self._tls = threading.local()

    def held_count(self) -> int:
        """This thread's reentrant hold count (0 for non-task threads)."""
        return getattr(self._tls, "held", 0)

    def occupancy(self) -> dict:
        """Slot occupancy for the resource-plane sampler
        (utils/telemetry.py): total/in-use permits + queued waiters."""
        total = self._sem._size
        return {"semaphore_slots_total": total,
                "semaphore_slots_in_use": max(
                    total - self._sem.available(), 0),
                "semaphore_waiters": self._sem.waiting()}

    def acquire_if_necessary(self, priority: int = 0) -> None:
        """Where a thread's hold begins (plan/engine.py, the only caller:
        ``run_one`` for a task, ``execute`` for the caller's thread while
        it sizes the plan): no other thread takes a permit.  A worker
        thread doing device work for a task that waits for its output (a
        pipeline's producer) works under that task's permit and takes
        none."""
        if self.held_count() == 0:
            self._sem.acquire(priority)
            self._tls.priority = priority
        self._tls.held = self.held_count() + 1

    def release_if_necessary(self) -> None:
        held = self.held_count()
        if held <= 0:
            return
        self._tls.held = held - 1
        if self._tls.held == 0:
            self._sem.release()

    @contextmanager
    def released(self):
        """THE way to wait for something other than the device: give back
        this thread's whole hold for the block and take it back on exit,
        on the same thread, in the same frame (the block never spans a
        ``yield``, so no generator ``finally`` touches the semaphore), at
        the priority the hold began with.  Nothing is taken that was not
        given: a thread that holds nothing passes straight through.  The
        re-acquire is a cancellation point: when it raises, the thread
        holds nothing and ``release_if_necessary`` finds nothing to give
        back."""
        held = self.held_count()
        if held == 0:
            yield
            return
        self._tls.held = 0
        self._sem.release()
        try:
            yield
        finally:
            self._sem.acquire(self._tls.priority)
            self._tls.held = held


#: thread-ambient device priority: the serving layer sets it around a
#: query's execution; the engine captures it at execute() entry and
#: acquires the semaphore for every partition task at that priority
#: (lower value = earlier wake, the PrioritySemaphore convention)
_PRIORITY = threading.local()


def current_task_priority() -> int:
    return getattr(_PRIORITY, "value", 0)


@contextmanager
def task_priority(priority: int):
    prev = getattr(_PRIORITY, "value", 0)
    _PRIORITY.value = int(priority)
    try:
        yield
    finally:
        _PRIORITY.value = prev


_SEMAPHORE_SIZE = 2
_SEMAPHORE = TpuSemaphore(_SEMAPHORE_SIZE)


def tpu_semaphore() -> TpuSemaphore:
    return _SEMAPHORE


def configure(concurrent_tasks: int) -> None:
    """Resize the process semaphore.  No-op when the size is unchanged —
    session init calls this (Plugin.scala:657 analog) and must not drop
    permits held by a query running on another thread."""
    global _SEMAPHORE, _SEMAPHORE_SIZE
    if concurrent_tasks == _SEMAPHORE_SIZE:
        return
    _SEMAPHORE = TpuSemaphore(concurrent_tasks)
    _SEMAPHORE_SIZE = concurrent_tasks
