"""utils/ambient.py: the blessed ambient-inheriting spawn helpers.

The contract tpu-lint's ambient-propagation rule points every spawn
site at: a worker spawned through spawn_with_ambients /
submit_with_ambients observes the SPAWNER's tenant scope, task
priority and cancel token (never a device permit) — and the
snapshot is taken at spawn time on the spawning thread, so the worker
keeps the ambients even after the spawner leaves its scopes.
"""
import os
import sys
import threading
from concurrent.futures import ThreadPoolExecutor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from spark_rapids_tpu.memory.semaphore import (current_task_priority,
                                               task_priority,
                                               tpu_semaphore)
from spark_rapids_tpu.memory.tenant import TENANTS
from spark_rapids_tpu.utils.ambient import (Ambients, spawn_with_ambients,
                                            submit_with_ambients)
from spark_rapids_tpu.utils.cancel import (CancelToken, cancel_scope,
                                           current_cancel_token)
from tests.test_memory import task_hold


def _observe(out: dict, done: threading.Event):
    out["tenant"] = TENANTS.current()
    out["priority"] = current_task_priority()
    out["token"] = current_cancel_token()
    out["held"] = tpu_semaphore().held_count()
    done.set()


def test_spawn_inherits_tenant_priority_token():
    token = CancelToken(label="t")
    out, done = {}, threading.Event()
    with TENANTS.scope("acme"), task_priority(7), cancel_scope(token):
        spawn_with_ambients(_observe, out, done)
        assert done.wait(5.0)
    assert out["tenant"] == "acme"
    assert out["priority"] == 7
    assert out["token"] is token


def test_spawn_captures_at_spawn_time_not_thread_start():
    """The snapshot happens on the SPAWNING thread at call time: a
    worker started (start=False) and run after the spawner left its
    scopes still sees them."""
    out, done = {}, threading.Event()
    with TENANTS.scope("late"), task_priority(3):
        t = spawn_with_ambients(_observe, out, done, start=False)
    # spawner's scopes are gone now
    assert TENANTS.current() is None
    t.start()
    assert done.wait(5.0)
    assert out["tenant"] == "late"
    assert out["priority"] == 3


def test_spawned_worker_holds_no_permit_whether_or_not_the_spawner_does():
    """The device permit is not an ambient: a worker works under the
    permit of the task that waits for it and has no hold of its own."""
    out, done = {}, threading.Event()
    with task_hold(tpu_semaphore()):
        spawn_with_ambients(_observe, out, done)
        assert done.wait(5.0)
    assert out["held"] == 0

    out2, done2 = {}, threading.Event()
    spawn_with_ambients(_observe, out2, done2)
    assert done2.wait(5.0)
    assert out2["held"] == 0


def test_worker_waiting_off_the_device_cannot_free_spawners_permit():
    """A worker's released() (a scan under a pipelined exchange waiting
    for a decoded chunk) and release_if_necessary() give back nothing:
    the permit belongs to the spawning task (the PR 9 lesson)."""
    sem = tpu_semaphore()
    base = sem._sem.available()
    done = threading.Event()
    seen = []

    def worker():
        sem.release_if_necessary()    # must NOT free the spawner's slot
        with sem.released():
            seen.append(sem._sem.available())
        done.set()

    with task_hold(sem):
        avail_held = sem._sem.available()
        spawn_with_ambients(worker)
        assert done.wait(5.0)
        assert seen == [avail_held]
        assert sem._sem.available() == avail_held
    assert sem._sem.available() == base


def test_submit_with_ambients_inherits_on_pool_thread():
    token = CancelToken(label="pool")
    with ThreadPoolExecutor(max_workers=1) as pool:
        with TENANTS.scope("poolco"), task_priority(2), \
                cancel_scope(token):
            fut = submit_with_ambients(
                pool, lambda: (TENANTS.current(), current_task_priority(),
                               current_cancel_token()))
        tenant, prio, tok = fut.result(timeout=5.0)
    assert tenant == "poolco"
    assert prio == 2
    assert tok is token


def test_pool_task_holds_no_permit():
    """Pool tasks routinely outlive the submitting call: like every
    worker they hold nothing of the submitter's."""
    with ThreadPoolExecutor(max_workers=1) as pool:
        with task_hold(tpu_semaphore()):
            fut = submit_with_ambients(
                pool, lambda: tpu_semaphore().held_count())
            assert fut.result(timeout=5.0) == 0


def test_ambients_scope_restores_previous_context():
    amb = Ambients(tenant="x", priority=9, token=None)
    with TENANTS.scope("outer"), task_priority(1):
        with amb.scope():
            assert TENANTS.current() == "x"
            assert current_task_priority() == 9
        assert TENANTS.current() == "outer"
        assert current_task_priority() == 1
