"""The one blessed way to put engine work on another thread.

A worker thread spawned on behalf of a running query must observe the
SAME thread-ambient context as its spawner, or the system silently
mis-attributes or deadlocks its work:

  * the TENANT scope (memory/tenant.py) -- device allocations on the
    worker must charge the submitting query's tenant, or budget
    enforcement spills a neighbor;
  * the TASK PRIORITY (memory/semaphore.py) -- a worker acquiring the
    device semaphore at default priority jumps the serving queue;
  * the CANCEL TOKEN (utils/cancel.py) -- a cancelled query's workers
    must stop at their next blessed wait instead of producing into a
    dead hand-off;
  * the SEMAPHORE COVER -- a worker doing device work on behalf of a
    task that already holds a semaphore slot (and is blocked waiting on
    this worker's output) must RIDE that slot, not take a second one:
    once every slot is held by such blocked consumers, a worker-side
    acquire deadlocks (the PR 9 pipelined-producer/device-semaphore
    deadlock; the reference's shuffle writer threads skip the GPU
    semaphore for exactly this reason).

``Ambients.capture()`` snapshots all four on the spawning thread;
``spawn_with_ambients`` / ``submit_with_ambients`` re-enter them around
the target on the worker.  tpu-lint's ``ambient-propagation`` rule flags
any bare ``threading.Thread`` / pool ``submit`` whose target can reach
engine/shuffle/memory code without coming through here, so the PR 9/10
bug class (hand-plumbed or forgotten ambients) is a lint error, not a
review catch.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager, nullcontext
from typing import Callable, Optional


#: runtime-sanitizer ambient-integrity seam (utils/sanitizer.py): called
#: with the Ambients snapshot on the WORKER thread, inside the
#: re-entered scope, before the target runs.  None when the sanitizer is
#: off.
_AMBIENT_HOOK = None


def set_ambient_hook(fn) -> None:
    global _AMBIENT_HOOK
    _AMBIENT_HOOK = fn


class Ambients:
    """Immutable snapshot of the spawning thread's ambient context."""

    __slots__ = ("tenant", "priority", "token", "covered", "trace",
                 "parent_span")

    def __init__(self, tenant, priority: int, token, covered: bool,
                 trace=None, parent_span=None):
        self.tenant = tenant
        self.priority = priority
        self.token = token
        self.covered = covered
        #: the per-query trace context (utils/obs.py QueryTrace): a
        #: worker's counter deltas and spans must attribute to the
        #: spawning query, or concurrent queries interleave again
        self.trace = trace
        #: id of the span open on the spawning thread at capture: the
        #: parent of the worker's outermost spans
        self.parent_span = parent_span

    @classmethod
    def capture(cls, inherit_semaphore_cover: bool = True) -> "Ambients":
        """Snapshot the CURRENT thread's ambients.  ``covered`` is true
        only when the capturing thread actually holds (or rides) a
        device-semaphore slot AND the caller opted in -- a worker that
        outlives its spawner's slot must not claim cover it no longer
        has, so pass ``inherit_semaphore_cover=False`` for workers the
        spawner does not block on."""
        from spark_rapids_tpu.memory.semaphore import (
            current_task_priority, tpu_semaphore)
        from spark_rapids_tpu.memory.tenant import TENANTS
        from spark_rapids_tpu.utils.cancel import current_cancel_token
        from spark_rapids_tpu.utils.obs import (current_query_trace,
                                                current_span_id)
        covered = (inherit_semaphore_cover
                   and tpu_semaphore().held_count() > 0)
        return cls(TENANTS.current(), current_task_priority(),
                   current_cancel_token(), covered,
                   trace=current_query_trace(),
                   parent_span=current_span_id())

    @contextmanager
    def scope(self):
        """Re-enter the snapshot on the current (worker) thread."""
        from spark_rapids_tpu.memory.semaphore import (task_priority,
                                                       tpu_semaphore)
        from spark_rapids_tpu.memory.tenant import TENANTS
        from spark_rapids_tpu.utils.cancel import cancel_scope
        from spark_rapids_tpu.utils.obs import trace_scope
        cover = (tpu_semaphore().borrowed_cover() if self.covered
                 else nullcontext())
        with TENANTS.scope(self.tenant), task_priority(self.priority), \
                cancel_scope(self.token), \
                trace_scope(self.trace, self.parent_span), cover:
            yield self

    def bind(self, fn: Callable) -> Callable:
        """``fn`` wrapped to run under this snapshot."""
        def run(*args, **kwargs):
            with self.scope():
                if _AMBIENT_HOOK is not None:
                    _AMBIENT_HOOK(self)
                return fn(*args, **kwargs)
        run.__name__ = getattr(fn, "__name__", "ambient_bound")
        return run


def spawn_with_ambients(target: Callable, *args,
                        name: Optional[str] = None,
                        daemon: bool = True,
                        start: bool = True,
                        inherit_semaphore_cover: bool = True,
                        ambients: Optional[Ambients] = None,
                        **kwargs) -> threading.Thread:
    """``threading.Thread`` that runs ``target`` under the spawner's
    ambients (captured NOW, on the spawning thread -- not at thread
    start, which races the spawner leaving its scopes)."""
    amb = ambients if ambients is not None else Ambients.capture(
        inherit_semaphore_cover=inherit_semaphore_cover)
    t = threading.Thread(target=amb.bind(target), args=args,
                         kwargs=kwargs, name=name, daemon=daemon)
    if start:
        t.start()
    return t


def submit_with_ambients(pool, fn: Callable, *args,
                         inherit_semaphore_cover: bool = False,
                         ambients: Optional[Ambients] = None, **kwargs):
    """``pool.submit`` with the submitter's ambients re-entered around
    ``fn`` on the pool thread.  Cover inheritance defaults OFF here:
    pool tasks routinely outlive the submitting call (write-behind), and
    a borrowed cover is only sound while the spawner blocks holding its
    slot -- opt in per call site when that contract holds."""
    amb = ambients if ambients is not None else Ambients.capture(
        inherit_semaphore_cover=inherit_semaphore_cover)
    return pool.submit(amb.bind(fn), *args, **kwargs)
