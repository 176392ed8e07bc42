"""Throttled async write-behind: encode/write overlapped with the device loop.

Reference: io/async/AsyncOutputStream.scala + ThrottlingExecutor.scala —
writes queue onto a background pool, bounded by an in-flight byte budget so
a slow sink applies backpressure instead of buffering the whole output in
host memory.  Errors surface at the NEXT submit or at close (the async
stream's error-propagation contract)."""
from __future__ import annotations

import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, List, Optional


class ThrottlingExecutor:
    """Bounded in-flight-bytes task runner.

    submit(nbytes, fn) blocks while the budget is exhausted (backpressure),
    runs fn on the pool, and re-raises the first task error on the next
    submit or at wait()."""

    def __init__(self, max_in_flight_bytes: int, num_threads: int = 2):
        self.budget = max(int(max_in_flight_bytes), 1)
        self._in_flight = 0
        self._cv = threading.Condition()
        self._pool = ThreadPoolExecutor(
            max_workers=max(num_threads, 1),
            thread_name_prefix="tpu-async-write")
        self._error: Optional[BaseException] = None

    def _raise_pending(self) -> None:
        if self._error is not None:
            err, self._error = self._error, None
            raise err

    def submit(self, nbytes: int, fn: Callable[[], None]) -> None:
        from spark_rapids_tpu.utils.ambient import submit_with_ambients
        from spark_rapids_tpu.utils.cancel import cancellable_wait
        nbytes = min(max(int(nbytes), 0), self.budget)
        with self._cv:
            self._raise_pending()
            cancellable_wait(
                self._cv,
                predicate=lambda: not (self._in_flight + nbytes
                                       > self.budget and self._in_flight),
                site="io.write.throttle")
            self._in_flight += nbytes

        def run():
            try:
                fn()
            except BaseException as e:  # noqa: BLE001 — surfaced at submit/wait
                with self._cv:
                    if self._error is None:
                        self._error = e
            finally:
                with self._cv:
                    self._in_flight -= nbytes
                    self._cv.notify_all()
        # write-behind work runs under the SUBMITTER's tenant/priority/
        # token (a cancelled query's queued encodes stop at their next
        # blessed wait and surface here as the pending error)
        submit_with_ambients(self._pool, run)

    def wait(self) -> None:
        """Drain all in-flight work; re-raise the first error."""
        from spark_rapids_tpu.utils.cancel import cancellable_wait
        with self._cv:
            cancellable_wait(self._cv,
                             predicate=lambda: not self._in_flight,
                             site="io.write.drain")
            self._raise_pending()

    def shutdown(self) -> None:
        self._pool.shutdown(wait=True)
