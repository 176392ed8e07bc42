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
    dead hand-off.

The DEVICE PERMIT is NOT inherited and a worker never takes one: only
``plan/engine.py`` acquires the device semaphore.  A worker
doing device work for a task that waits for its output (a pipeline's
producer) works under that task's permit; once every permit is held by
such waiting consumers, a worker-side acquire would deadlock (the PR 9
pipelined-producer deadlock; the reference's shuffle writer threads skip
the GPU semaphore for exactly this reason).

``Ambients.capture()`` snapshots all three on the spawning thread;
``spawn_with_ambients`` / ``submit_with_ambients`` re-enter them around
the target on the worker.  tpu-lint's ``ambient-propagation`` rule flags
any bare ``threading.Thread`` / pool ``submit`` whose target can reach
engine/shuffle/memory code without coming through here, so the PR 9/10
bug class (hand-plumbed or forgotten ambients) is a lint error, not a
review catch.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
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

    __slots__ = ("tenant", "priority", "token", "trace", "parent_span")

    def __init__(self, tenant, priority: int, token,
                 trace=None, parent_span=None):
        self.tenant = tenant
        self.priority = priority
        self.token = token
        #: the per-query trace context (utils/obs.py QueryTrace): a
        #: worker's counter deltas and spans must attribute to the
        #: spawning query, or concurrent queries interleave again
        self.trace = trace
        #: id of the span open on the spawning thread at capture: the
        #: parent of the worker's outermost spans
        self.parent_span = parent_span

    @classmethod
    def capture(cls) -> "Ambients":
        """Snapshot the CURRENT thread's ambients."""
        from spark_rapids_tpu.memory.semaphore import current_task_priority
        from spark_rapids_tpu.memory.tenant import TENANTS
        from spark_rapids_tpu.utils.cancel import current_cancel_token
        from spark_rapids_tpu.utils.obs import (current_query_trace,
                                                current_span_id)
        return cls(TENANTS.current(), current_task_priority(),
                   current_cancel_token(),
                   trace=current_query_trace(),
                   parent_span=current_span_id())

    @contextmanager
    def scope(self):
        """Re-enter the snapshot on the current (worker) thread."""
        from spark_rapids_tpu.memory.semaphore import task_priority
        from spark_rapids_tpu.memory.tenant import TENANTS
        from spark_rapids_tpu.utils.cancel import cancel_scope
        from spark_rapids_tpu.utils.obs import trace_scope
        with TENANTS.scope(self.tenant), task_priority(self.priority), \
                cancel_scope(self.token), \
                trace_scope(self.trace, self.parent_span):
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
                        ambients: Optional[Ambients] = None,
                        **kwargs) -> threading.Thread:
    """``threading.Thread`` that runs ``target`` under the spawner's
    ambients (captured NOW, on the spawning thread -- not at thread
    start, which races the spawner leaving its scopes)."""
    amb = ambients if ambients is not None else Ambients.capture()
    t = threading.Thread(target=amb.bind(target), args=args,
                         kwargs=kwargs, name=name, daemon=daemon)
    if start:
        t.start()
    return t


def submit_with_ambients(pool, fn: Callable, *args,
                         ambients: Optional[Ambients] = None, **kwargs):
    """``pool.submit`` with the submitter's ambients re-entered around
    ``fn`` on the pool thread."""
    amb = ambients if ambients is not None else Ambients.capture()
    return pool.submit(amb.bind(fn), *args, **kwargs)
