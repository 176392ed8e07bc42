"""Shared host-decode thread pool + prefetching iterator for scans.

Reference: GpuMultiFileReader.scala / MultiFileCloudParquetPartitionReader
(GpuParquetScan.scala:3134) — CPU threads parse footers and decode pages
into host memory with NO device semaphore held; the task only takes the
semaphore at device entry (GpuSemaphore.acquireIfNecessary,
GpuSemaphore.scala:240).  Here the pool runs pyarrow decode producing host
Arrow tables; the consuming task releases the TPU semaphore while it
waits and re-acquires it for the HBM upload, so decode of batch N+1
overlaps device compute on batch N (visible in the span log as
scan.decode / scan.upload overlap).

Pool size: spark.rapids.sql.multiThreadedRead.numThreads.
"""
from __future__ import annotations

import queue
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Iterator, Optional

from spark_rapids_tpu.utils.tracing import trace_range

_POOL: Optional[ThreadPoolExecutor] = None
_POOL_SIZE = 0
_LOCK = threading.Lock()


def reader_pool(num_threads: int) -> ThreadPoolExecutor:
    """Process-wide decode pool (grown, never shrunk, on config change)."""
    global _POOL, _POOL_SIZE
    with _LOCK:
        if _POOL is None or num_threads > _POOL_SIZE:
            if _POOL is not None:
                _POOL.shutdown(wait=False)
            _POOL_SIZE = max(num_threads, 1)
            _POOL = ThreadPoolExecutor(
                max_workers=_POOL_SIZE,
                thread_name_prefix="tpu-reader")
        return _POOL


_SENTINEL = object()


def prefetched(host_iter_fn: Callable[[], Iterator], num_threads: int,
               capacity: int = 4) -> Iterator:
    """Run ``host_iter_fn()`` on the reader pool, buffering up to
    ``capacity`` decoded items ahead of the consumer.

    The producer runs the WHOLE iterator on one pool thread (pyarrow
    readers are not thread-safe per file); parallelism across files/tasks
    comes from the pool width.  Errors re-raise at the consumer.  If the
    consumer abandons the iterator early (LIMIT short-circuit, error), the
    generator's close sets ``cancelled`` and the producer exits instead of
    blocking on the full queue forever — a stuck producer would pin one
    thread of the process-wide pool per abandoned scan.
    """
    from spark_rapids_tpu.utils.cancel import (cancellable_wait,
                                               current_cancel_token)
    q: "queue.Queue" = queue.Queue(maxsize=capacity)
    cancelled = threading.Event()
    # the consuming task's cancel token: the producer polls it directly
    # (NOT via token.on_cancel — a long query opens many scans and
    # per-scan registrations would accumulate on the token for its
    # whole lifetime); the consumer's unwind also sets ``cancelled``,
    # so both exit signals converge on the same loop conditions
    token = current_cancel_token()

    def _stop() -> bool:
        return cancelled.is_set() or \
            (token is not None and token.cancelled())

    def produce():
        try:
            it = iter(host_iter_fn())
            while True:
                # one chunk's decode; the wait on a full queue below is
                # the consumer's pace, not decode time
                with trace_range("scan.decode"):
                    item = next(it, _SENTINEL)
                if item is _SENTINEL:
                    break
                while not _stop():
                    try:
                        q.put(item, timeout=0.2)
                        break
                    except queue.Full:
                        continue
                if _stop():
                    return
        except BaseException as e:   # noqa: BLE001 — relayed to consumer
            while not _stop():
                try:
                    q.put(("__error__", e), timeout=0.2)
                    break
                except queue.Full:
                    continue
        finally:
            while not _stop():
                try:
                    q.put(_SENTINEL, timeout=0.2)
                    break
                except queue.Full:
                    continue

    # the decode runs for the consuming scan task: inherit its tenant/
    # priority/token (host-side decode NEVER takes the device semaphore
    # — that is the point of the pool — so no cover)
    from spark_rapids_tpu.utils.ambient import submit_with_ambients
    submit_with_ambients(reader_pool(num_threads), produce)
    # belt-and-braces: the task-completion hook cancels the producer even
    # when the abandoning caller never closes the generator (GC-delayed
    # iterators under the engine's task scope;
    # memory/task_completion.py, ScalableTaskCompletion analog)
    from spark_rapids_tpu.memory.task_completion import on_task_completion
    on_task_completion(cancelled.set)

    try:
        while True:
            item = cancellable_wait(q, token=token, site="scan.prefetch")
            if item is _SENTINEL:
                return
            if isinstance(item, tuple) and len(item) == 2 and \
                    item[0] == "__error__":
                raise item[1]
            yield item
    finally:
        cancelled.set()
