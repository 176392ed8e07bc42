"""Producer-thread pipelining for consecutive exchange stages.

The task engine's lazy materialization runs stage k+1's map side as one
serial loop over stage k's reduce output: while the map side frames and
serializes a batch, the reduce fetch plane sits idle, and vice versa —
the pipeline drains at every hand-off (ROADMAP open item 1; Theseus's
thesis in PAPERS.md is that distributed query speed is won on exactly
this data-movement overlap).

``pipelined(gen)`` moves the PRODUCER side of such a hand-off onto a
background thread with a byte-bounded hand-off queue (the shuffle fetch
in-flight window bounds residency, shuffle/transport.py), so:

  * map framing/serialize of stage k+1 overlaps stage k's reduce fetch
    and compute (exchange._materialize wraps its map generator);
  * the fused reduce path prefetches the NEXT coalesced group's pieces
    while the current group's program runs (plan/fused.py).

Counters make the overlap checkable (shuffle/stats.py):
  * ``pipeline_overlap_ns`` — production time of items that were already
    waiting when the consumer asked (work that genuinely ran under the
    consumer's own processing);
  * ``stage_drain_ns`` — time the consumer blocked on an empty queue
    AFTER the first item (pipeline-fill excluded): ≈0 means the producer
    kept ahead and the stage hand-off never drained.
"""
from __future__ import annotations

import threading
import time
from typing import Callable, Iterable, Iterator, Optional

from spark_rapids_tpu.shuffle.stats import SHUFFLE_COUNTERS
from spark_rapids_tpu.testing.chaos import CHAOS
from spark_rapids_tpu.utils.cancel import cancellable_wait
from spark_rapids_tpu.utils.telemetry import PIPELINE_INFLIGHT

_SENTINEL = object()


class _Pipe:
    """Byte-bounded single-producer/single-consumer hand-off.

    Both waits are blessed ``cancellable_wait``s observing ``token``
    (the consumer task's cancel token, shared by the producer thread it
    spawned): a cancelled query's hand-off unblocks BOTH sides with
    ``QueryCancelled`` — the producer's surfaces at the consumer through
    ``finish(error)``, the consumer's propagates directly."""

    def __init__(self, max_bytes: int, token=None):
        self.max_bytes = max(int(max_bytes), 1)
        self.token = token
        self._cv = threading.Condition()
        self._items = []           # (item, nbytes, produce_ns)
        self._bytes = 0
        self._done = False
        self._error: Optional[BaseException] = None
        self._closed = False       # consumer abandoned the stream

    # -- producer side ------------------------------------------------------

    def put(self, item, nbytes: int, produce_ns: int) -> bool:
        with self._cv:
            cancellable_wait(
                self._cv,
                predicate=lambda: not (self._bytes >= self.max_bytes
                                       and self._items
                                       and not self._closed),
                token=self.token, site="shuffle.pipeline.put")
            if self._closed:
                return False
            self._items.append((item, nbytes, produce_ns))
            self._bytes += nbytes
            # resource-plane gauge (utils/telemetry.py): hand-off bytes
            # parked between producer and consumer, one add per item
            PIPELINE_INFLIGHT.add(nbytes)
            self._cv.notify_all()
            return True

    def finish(self, error: Optional[BaseException] = None) -> None:
        with self._cv:
            self._error = error
            self._done = True
            self._cv.notify_all()

    # -- consumer side ------------------------------------------------------

    def get(self):
        """(item, produce_ns, waited_ns) or (_SENTINEL, 0, waited_ns)."""
        t0 = time.perf_counter_ns()
        with self._cv:
            cancellable_wait(
                self._cv,
                predicate=lambda: self._items or self._done,
                token=self.token, site="shuffle.pipeline.handoff")
            waited = time.perf_counter_ns() - t0
            if self._items:
                item, nbytes, produce_ns = self._items.pop(0)
                self._bytes -= nbytes
                PIPELINE_INFLIGHT.add(-nbytes)
                self._cv.notify_all()
                return item, produce_ns, waited
            if self._error is not None:
                raise self._error
            return _SENTINEL, 0, waited

    def close(self) -> None:
        with self._cv:
            self._closed = True
            # an abandoned stream's parked bytes leave flight here (the
            # producer's post-close put() never adds to the gauge)
            PIPELINE_INFLIGHT.add(-self._bytes)
            self._bytes = 0
            self._items.clear()
            self._cv.notify_all()


def pipelined(source: Iterable, nbytes_of: Callable[[object], int],
              max_inflight_bytes: int,
              name: str = "shuffle-pipeline") -> Iterator:
    """Yield ``source``'s items, produced ahead on a background thread.

    The producer works ON BEHALF of the calling task, so it runs under
    the caller's full ambient snapshot (utils/ambient.py): tenant scope
    (its device allocations charge the submitting query), task priority,
    the cancel token (a cancelled query's producer exits its loop at the
    next token check or hand-off wait instead of producing into a dead
    hand-off).  It takes no device permit: the consumer blocks on this
    queue while holding its own, and a producer-side acquire would
    deadlock once every permit is held by such blocked consumers (the
    reference's shuffle writer threads skip the GPU semaphore for the
    same reason).  Exceptions from the source re-raise at the consumer's
    next pull; an abandoned consumer (generator closed early) stops the
    producer at its next hand-off.
    """
    from spark_rapids_tpu.utils.ambient import spawn_with_ambients
    from spark_rapids_tpu.utils.cancel import current_cancel_token

    token = current_cancel_token()
    pipe = _Pipe(max_inflight_bytes, token=token)

    def produce():
        from spark_rapids_tpu.utils.obs import span
        try:
            # the producer span lands on the query's timeline (the
            # ambient trace rides the spawn snapshot): a pipelined
            # exchange's drain shows as a GAP between producer spans
            # and consumer work instead of a counter to guess at
            with span("shuffle.pipeline.produce", tags={"name": name}):
                it = iter(source)
                while True:
                    if token is not None:
                        token.check()
                    # chaos shuffle.pipeline.producer.fail: the producer
                    # thread dies mid-stream — the error must surface at
                    # the consumer's next pull, never hang the hand-off
                    CHAOS.raise_if("shuffle.pipeline.producer.fail")
                    t0 = time.perf_counter_ns()
                    try:
                        item = next(it)
                    except StopIteration:
                        break
                    dt = time.perf_counter_ns() - t0
                    if not pipe.put(item, max(nbytes_of(item), 1), dt):
                        break      # consumer gone: stop producing
        except BaseException as e:  # noqa: BLE001 — re-raised at consumer
            pipe.finish(e)
        else:
            pipe.finish()

    spawn_with_ambients(produce, name=name)
    first = True
    try:
        while True:
            # tpu-lint: allow-unbounded-wait(_Pipe.get waits through a blessed cancellable_wait internally — watchdog-registered, cancel-aware)
            item, produce_ns, waited_ns = pipe.get()
            if item is _SENTINEL:
                return
            if first:
                first = False   # pipeline fill, not a stage drain
            elif waited_ns > produce_ns:
                # the producer could not keep ahead: the hand-off drained
                # for the part of the wait its own production can't cover
                drain_ns = waited_ns - produce_ns
                SHUFFLE_COUNTERS.add(stage_drain_ns=drain_ns)
                from spark_rapids_tpu.shuffle.stats import HISTOGRAMS
                HISTOGRAMS["stage_drain_s"].record(drain_ns / 1e9)
            if waited_ns < produce_ns:
                # this item's production ran (at least partly) while the
                # consumer was busy with earlier items — true overlap
                SHUFFLE_COUNTERS.add(
                    pipeline_overlap_ns=produce_ns - waited_ns)
            yield item
    finally:
        pipe.close()
