"""Memory runtime tests: arena budget, spill tiers, retry/split, injection.

Models the reference's RmmSparkRetrySuiteBase-style units
(tests/src/test/scala/.../RmmRapidsRetryIteratorSuite.scala in the
reference) against the TPU arena/spill/retry stack.
"""
import time
from contextlib import contextmanager

import numpy as np
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema
from spark_rapids_tpu.memory import (
    TpuRetryOOM,
    TpuSplitAndRetryOOM,
    device_arena,
    make_spillable,
    spill_framework,
    with_capacity_retry,
    with_retry,
    with_retry_no_split,
)
from spark_rapids_tpu.memory import retry as retry_mod


SCHEMA = Schema.of(a=T.LONG, b=T.DOUBLE)


def mk_batch(n=100):
    return ColumnarBatch.from_pydict(
        {"a": list(range(n)), "b": [float(i) * 0.5 for i in range(n)]}, SCHEMA)


@pytest.fixture(autouse=True)
def _clean_arena():
    arena = device_arena()
    arena.budget_bytes = 0
    arena.used_bytes = 0
    arena.peak_bytes = 0
    yield
    spill_framework().close()
    arena.clear_injection()
    arena.budget_bytes = 0
    arena.used_bytes = 0


def test_spill_roundtrip_device_host_disk():
    b = mk_batch(64)
    expected = b.to_pydict()
    h = make_spillable(b)
    assert h.on_device()
    used_before = device_arena().used_bytes
    assert used_before > 0

    freed = h.spill_to_host()
    assert freed == h.size_bytes
    assert not h.on_device()
    assert device_arena().used_bytes == used_before - freed

    assert h.spill_to_disk() > 0
    out = h.materialize()
    assert out.to_pydict() == expected
    h.close()
    assert device_arena().used_bytes == 0


def test_arena_pressure_triggers_spill():
    b1 = mk_batch(256)
    h1 = make_spillable(b1)
    # budget only fits one batch; reserving a second must spill the first
    device_arena().budget_bytes = int(h1.size_bytes * 1.5)
    b2 = mk_batch(256)
    h2 = make_spillable(b2)
    assert not h1.on_device()
    assert h2.on_device()
    h1.close()
    h2.close()


def test_with_retry_no_split_retries_after_oom():
    calls = {"n": 0}

    def fn():
        calls["n"] += 1
        if calls["n"] == 1:
            raise TpuRetryOOM("synthetic")
        return 42

    assert with_retry_no_split(fn) == 42
    assert calls["n"] == 2


def test_with_retry_split_policy():
    def fn(item):
        if len(item) > 2:
            raise TpuSplitAndRetryOOM("too big")
        return sum(item)

    def split(item):
        mid = len(item) // 2
        return [item[:mid], item[mid:]]

    out = with_retry([[1, 2, 3, 4, 5, 6]], fn, split_policy=split)
    assert sum(out) == 21
    assert len(out) > 1


def test_with_retry_split_exhausted_raises():
    def fn(item):
        raise TpuSplitAndRetryOOM("always")

    with pytest.raises(TpuSplitAndRetryOOM):
        with_retry([[1]], fn, split_policy=lambda x: [x])


def test_capacity_retry_grows():
    seen = []

    def run(cap):
        seen.append(cap)
        return cap

    def check(result):
        return 100 if result < 100 else None

    assert with_capacity_retry(run, check, initial_capacity=16) == 128
    assert seen == [16, 128]


def test_capacity_retry_ceiling_raises_split():
    with pytest.raises(TpuSplitAndRetryOOM):
        with_capacity_retry(lambda c: c, lambda r: 10**9, initial_capacity=16,
                            max_capacity=1024)


@pytest.mark.inject_oom
def test_injected_oom_is_retried_transparently():
    """@inject_oom marker arms one synthetic retry-OOM; the retry framework
    must absorb it and still produce the right answer (the differential
    oracle contract, reference conftest.py:177)."""
    b = mk_batch(32)
    h = make_spillable(b)

    def fn(handle):
        with handle.borrowed() as batch:
            return batch.to_pydict()["a"]

    (vals,) = with_retry([h], fn)
    assert vals == list(range(32))
    h.close()


def test_injection_kind_split():
    retry_mod.enable_oom_injection(num_ooms=1, kind="split")
    try:
        calls = {"n": 0}

        def fn(item):
            calls["n"] += 1
            return item * 2

        out = with_retry([3], fn, split_policy=lambda x: [x, x])
        # one injected split -> item replaced by two copies
        assert out == [6, 6]
    finally:
        retry_mod.disable_oom_injection()


def test_pinned_handle_refuses_to_spill():
    """While a caller borrows the materialized batch, a pressure spill must
    not release the arena accounting out from under it."""
    b = mk_batch(64)
    h = make_spillable(b)
    with h.borrowed():
        assert h.spill_to_host() == 0
        assert h.on_device()
    assert h.spill_to_host() == h.size_bytes  # unpinned: spillable again
    h.close()


def test_device_manager_probe_and_budget():
    """GpuDeviceManager analog: probe the chip, size the arena budget from
    allocFraction when HBM stats exist (CPU backend exposes none ->
    bookkeeping mode)."""
    from spark_rapids_tpu.config import RapidsConf
    from spark_rapids_tpu.memory.device_manager import (
        DeviceInfo, initialize_device, probe_device)
    info = probe_device()
    assert info.platform
    # fake a chip with 16GiB to check the sizing math
    import spark_rapids_tpu.memory.device_manager as DM
    real = DM.probe_device
    try:
        DM.probe_device = lambda: DeviceInfo(None, 16 << 30, "tpu")
        from spark_rapids_tpu.memory import device_arena
        before = device_arena().budget_bytes
        initialize_device(RapidsConf(
            {"spark.rapids.memory.tpu.allocFraction": "0.5"}))
        assert device_arena().budget_bytes == 8 << 30
    finally:
        DM.probe_device = real
        device_arena().budget_bytes = before


# -- real XLA RESOURCE_EXHAUSTED translation ---------------------------------
# (reference contract: DeviceMemoryEventHandler.scala turns a real RMM
# allocator failure into GpuRetryOOM; here jaxlib's XlaRuntimeError with a
# RESOURCE_EXHAUSTED status must enter the same retry/spill machinery)

class XlaRuntimeError(RuntimeError):
    """Stand-in matching jaxlib's class BY NAME (is_device_oom matches the
    MRO class name so it survives jaxlib module-layout changes)."""


def test_is_device_oom_matches_resource_exhausted():
    from spark_rapids_tpu.memory.arena import is_device_oom
    assert is_device_oom(XlaRuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
        "1073741824 bytes."))
    assert not is_device_oom(XlaRuntimeError("INVALID_ARGUMENT: bad shape"))
    assert not is_device_oom(RuntimeError("RESOURCE_EXHAUSTED: not xla"))


def test_real_oom_translates_to_retry_with_spill():
    """A raw XlaRuntimeError(RESOURCE_EXHAUSTED) inside with_retry must
    spill and re-run, not kill the task."""
    h = make_spillable(mk_batch())
    calls = {"n": 0}

    def fn(_):
        calls["n"] += 1
        if calls["n"] == 1:
            raise XlaRuntimeError(
                "RESOURCE_EXHAUSTED: Out of memory allocating 8589934592 "
                "bytes (fragmentation outside the bookkept arena)")
        return calls["n"]

    assert with_retry([None], fn) == [2]
    # the emergency spill evicted the (unpinned) device handle
    assert not h.on_device()


def test_real_oom_translates_in_no_split_path():
    calls = {"n": 0}

    def fn():
        calls["n"] += 1
        if calls["n"] == 1:
            raise XlaRuntimeError("RESOURCE_EXHAUSTED: Out of memory")
        return "ok"

    assert with_retry_no_split(fn) == "ok"
    assert calls["n"] == 2


def test_translate_device_oom_wrapper():
    """shared_jit wraps every cached program with translate_device_oom; the
    wrapper converts only RESOURCE_EXHAUSTED and passes others through."""
    from spark_rapids_tpu.memory.arena import translate_device_oom

    @translate_device_oom
    def boom():
        raise XlaRuntimeError("RESOURCE_EXHAUSTED: Out of memory")

    with pytest.raises(TpuRetryOOM):
        boom()

    @translate_device_oom
    def other():
        raise XlaRuntimeError("INTERNAL: something else")

    with pytest.raises(XlaRuntimeError):
        other()


def test_non_oom_exceptions_propagate_unchanged():
    def fn(_):
        raise ValueError("regular bug")

    with pytest.raises(ValueError):
        with_retry([None], fn)


def test_leak_audit_tracks_and_asserts():
    """spark.rapids.memory.debug.leakAudit: creation stacks + the
    assert_no_leaks surface (the MemoryCleaner refcount-audit analog)."""
    from spark_rapids_tpu.memory.spill import (
        make_spillable, set_leak_audit, spill_framework)
    fw = spill_framework()
    set_leak_audit(True)
    try:
        b = ColumnarBatch.from_pydict({"v": [1.0, 2.0]},
                                      Schema.of(v=T.DOUBLE))
        h = make_spillable(b)
        assert h.creation_site is not None
        assert "test_leak_audit_tracks_and_asserts" in h.creation_site
        leaks = [x for x in fw.leaked_handles() if x is h]
        assert leaks, "open handle must be reported"
        # assert_no_leaks must raise while OUR handle is open, regardless
        # of ambient fixtures (they only add to the leak list)
        with pytest.raises(AssertionError, match="leaked"):
            fw.assert_no_leaks("unit test")
        h.close()
        assert not [x for x in fw.leaked_handles() if x is h]
    finally:
        set_leak_audit(False)


def test_leak_audit_off_by_default_no_stack_capture():
    from spark_rapids_tpu.memory.spill import make_spillable
    b = ColumnarBatch.from_pydict({"v": [1.0]}, Schema.of(v=T.DOUBLE))
    h = make_spillable(b)
    try:
        assert h.creation_site is None
    finally:
        h.close()


def test_query_leaves_no_leaked_handles():
    """End-to-end audit: a shuffle+agg query closes every handle it made."""
    from spark_rapids_tpu.memory.spill import (
        set_leak_audit, spill_framework)
    from spark_rapids_tpu.expressions import col, count, sum_
    from spark_rapids_tpu.expressions.core import Alias
    fw = spill_framework()
    before = set(id(h) for h in fw.leaked_handles())
    set_leak_audit(True)
    try:
        from spark_rapids_tpu.api.session import TpuSession
        s = TpuSession({"spark.rapids.sql.enabled": "true",
                        "spark.rapids.memory.debug.leakAudit": "true"})
        df = s.create_dataframe(
            {"k": [i % 5 for i in range(200)],
             "v": list(range(200))},
            Schema.of(k=T.INT, v=T.LONG), num_partitions=2)
        rows = df.group_by("k").agg(Alias(sum_(col("v")), "s"),
                                    Alias(count(), "n")).collect()
        assert len(rows) == 5
        new = [h for h in fw.leaked_handles() if id(h) not in before]
        assert not new, f"query leaked {len(new)} handles"
    finally:
        set_leak_audit(False)


# -- the device permit: TpuSemaphore.released() and MaterializeLock ----------

def _free(sem) -> int:
    return sem._sem.available()


@contextmanager
def task_hold(sem):
    """A task's hold, as plan/engine.py run_one takes and gives it."""
    sem.acquire_if_necessary()
    try:
        yield
    finally:
        sem.release_if_necessary()


@pytest.mark.parametrize("holds", [0, 1, 3])
def test_released_gives_back_the_whole_hold_and_takes_back_only_that(holds):
    """Nothing held: nothing given, nothing taken (the leak of
    python_exec.py's hand-written pair at PR 28).  Held once or
    re-entrantly: one permit goes back for the block and the hold count
    is what it was afterwards."""
    from spark_rapids_tpu.memory.semaphore import TpuSemaphore
    sem = TpuSemaphore(2)
    for _ in range(holds):
        sem.acquire_if_necessary()
    before = _free(sem)
    assert before == (2 if holds == 0 else 1)
    with sem.released():
        assert _free(sem) == 2 and sem.held_count() == 0
    assert _free(sem) == before and sem.held_count() == holds
    for _ in range(holds):
        sem.release_if_necessary()
    assert _free(sem) == 2 and sem.held_count() == 0


def test_released_on_a_tasks_worker_thread_leaves_the_tasks_permit():
    """A pipeline's producer works under its consumer task's permit and
    holds nothing itself: its scan's released() gives nothing back."""
    import threading

    from spark_rapids_tpu.memory.semaphore import TpuSemaphore
    sem = TpuSemaphore(2)
    seen = []

    def producer():
        with sem.released():
            seen.append((_free(sem), sem.held_count()))
    with task_hold(sem):
        worker = threading.Thread(target=producer)
        worker.start()
        worker.join(30)
        assert seen == [(1, 0)] and _free(sem) == 1
    assert _free(sem) == 2


def test_released_takes_the_permit_back_when_the_block_raises():
    from spark_rapids_tpu.memory.semaphore import TpuSemaphore
    sem = TpuSemaphore(2)
    with task_hold(sem):
        with pytest.raises(KeyError):
            with sem.released():
                raise KeyError("inside")
        assert _free(sem) == 1 and sem.held_count() == 1
    assert _free(sem) == 2


def test_generator_using_released_closed_from_another_thread():
    """The block never spans a yield, so closing the generator on a thread
    that holds nothing touches no permit (scan.py's `restore` loops, which
    ran in a generator's finally on whichever thread closed it, are gone)."""
    import threading

    from spark_rapids_tpu.memory.semaphore import TpuSemaphore
    sem = TpuSemaphore(2)

    def batches():
        for i in range(3):
            with sem.released():
                item = i
            yield item

    gen = batches()
    with task_hold(sem):
        assert next(gen) == 0 and _free(sem) == 1
        closer = threading.Thread(target=gen.close)
        closer.start()
        closer.join(30)
        assert _free(sem) == 1 and sem.held_count() == 1
    assert _free(sem) == 2


def test_scan_on_a_thread_without_a_hold_uploads_without_a_permit(tmp_path):
    """A scan on a thread that holds nothing (a pipeline's producer; a
    caller driving execute_partition itself, as here) takes no permit for
    its uploads and leaves the count whole, abandoned half-way too."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.memory.semaphore import tpu_semaphore
    path = str(tmp_path / "t.parquet")
    pq.write_table(pa.table({"a": list(range(4000))}), path,
                   row_group_size=1000)
    sess = TpuSession({"spark.rapids.sql.reader.batchSizeRows": "1000"})
    scan = sess.read_parquet(path).physical_plan()
    sem = tpu_semaphore()
    total = _free(sem)
    batches = scan.execute_partition(0)
    assert next(batches).num_rows == 1000
    assert _free(sem) == total and sem.held_count() == 0
    batches.close()
    assert _free(sem) == total and sem.held_count() == 0
    assert sum(b.num_rows for b in scan.execute_partition(0)) == 4000
    assert _free(sem) == total and sem.held_count() == 0
    scan.cleanup()


@pytest.mark.parametrize("contended", [False, True])
def test_materialize_lock_waiter_holds_no_permit(contended, monkeypatch):
    """Uncontended the lock never touches the semaphore; a task that finds
    it taken gives its permit up until it has the lock."""
    import threading

    from spark_rapids_tpu.memory import semaphore as semaphore_mod
    from spark_rapids_tpu.plan.execs.base import MaterializeLock
    sem = semaphore_mod.TpuSemaphore(2)
    monkeypatch.setattr(semaphore_mod, "_SEMAPHORE", sem)
    lock = MaterializeLock()
    inside, leave = threading.Event(), threading.Event()

    def sibling():
        with task_hold(sem), lock:
            inside.set()
            leave.wait(30)
    other = threading.Thread(target=sibling)
    if contended:
        other.start()
        assert inside.wait(30)
    seen, has_permit = [], threading.Event()

    def task():
        with task_hold(sem):
            has_permit.set()
            with lock:
                seen.append(_free(sem))
    waiter = threading.Thread(target=task)
    waiter.start()
    assert has_permit.wait(30)      # both permits are out now
    if contended:
        deadline = time.monotonic() + 30
        while _free(sem) != 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert _free(sem) == 1, "the waiter kept its permit"
        leave.set()
        other.join(30)
    waiter.join(30)
    assert seen == [1] and _free(sem) == 2


def test_materialize_lock_is_free_after_a_cancelled_reacquire(monkeypatch):
    """Taking the permit back is a cancellation point.  A waiter that gets
    the lock but not its permit (both held elsewhere, its query cancelled)
    raises out of __enter__, where no __exit__ follows: the lock must be
    free afterwards, or the query's own cleanup() blocks on it for good."""
    import threading

    from spark_rapids_tpu.memory import semaphore as semaphore_mod
    from spark_rapids_tpu.plan.execs.base import MaterializeLock
    from spark_rapids_tpu.utils.cancel import (CancelToken, QueryCancelled,
                                               cancel_scope)
    sem = semaphore_mod.TpuSemaphore(2)
    monkeypatch.setattr(semaphore_mod, "_SEMAPHORE", sem)
    lock = MaterializeLock()
    token = CancelToken("waiter")
    raised, entered = [], []

    def task():
        try:
            with cancel_scope(token), task_hold(sem):
                with lock:
                    entered.append(True)
        except QueryCancelled as exc:
            raised.append(exc)
            raised.append(sem.held_count())

    with lock:                          # the sibling's materialisation
        waiter = threading.Thread(target=task)
        waiter.start()
        deadline = time.monotonic() + 30
        while sem._sem.waiting() or _free(sem) != 2:    # took one, gave it
            assert time.monotonic() < deadline
            time.sleep(0.01)
        time.sleep(0.05)                # ...and now stands at the lock
        sem._sem.acquire()              # both permits held elsewhere
        sem._sem.acquire()
        token.cancel()
    waiter.join(30)
    assert not waiter.is_alive() and not entered
    assert len(raised) == 2 and raised[1] == 0
    assert lock._lock.acquire(blocking=False), "the lock leaked"
    lock._lock.release()
    sem._sem.release()
    sem._sem.release()
    assert _free(sem) == 2 and sem._sem.waiting() == 0


def test_released_takes_the_permit_back_at_the_holds_priority():
    """A serving query's task re-enters the device queue where it stood,
    not at the default priority (which would jump or lose the queue)."""
    from spark_rapids_tpu.memory.semaphore import TpuSemaphore
    sem = TpuSemaphore(2)
    asked = []
    acquire = sem._sem.acquire
    sem._sem.acquire = lambda priority=0, **kw: (asked.append(priority),
                                                 acquire(priority, **kw))[1]
    sem.acquire_if_necessary(-7)
    sem.acquire_if_necessary(3)         # re-entrant: the hold's own stays
    with sem.released():
        pass
    sem.release_if_necessary()
    sem.release_if_necessary()
    assert asked == [-7, -7] and _free(sem) == 2


def test_sizing_the_plan_is_metered_like_a_task(monkeypatch):
    """Device work inside num_partitions() (an exchange's map side, an AQE
    reader, an adaptive join's build side) runs on the caller's thread
    before any task: engine.execute holds a permit for it, so N queries
    at once put no more than the semaphore's size on the device."""
    import threading

    from spark_rapids_tpu.columnar.batch import Schema
    from spark_rapids_tpu.memory import semaphore as semaphore_mod
    from spark_rapids_tpu.plan import engine as engine_mod
    from spark_rapids_tpu.plan.execs.base import TpuExec
    sem = semaphore_mod.TpuSemaphore(2)
    monkeypatch.setattr(semaphore_mod, "_SEMAPHORE", sem)
    monkeypatch.setattr(engine_mod, "tpu_semaphore", lambda: sem)
    gauge = threading.Lock()
    inside, peak, held = [0], [0], []

    class Sizing(TpuExec):
        def num_partitions(self):
            with gauge:
                inside[0] += 1
                peak[0] = max(peak[0], inside[0])
                held.append(sem.held_count())
            time.sleep(0.1)
            with gauge:
                inside[0] -= 1
            return 1

        def execute_partition(self, idx):
            return iter(())

    def query():
        engine_mod.TpuEngine().execute(Sizing((), Schema((), ())))
    queries = [threading.Thread(target=query) for _ in range(5)]
    for q in queries:
        q.start()
    for q in queries:
        q.join(60)
    assert not any(q.is_alive() for q in queries)
    assert held == [1] * 5 and peak[0] == 2
    assert _free(sem) == 2 and sem.held_count() == 0
