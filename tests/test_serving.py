"""Serving-layer tests: admission control, priority-then-FIFO wake
order, tenant-budget isolation, the fingerprint result cache, and
concurrent driver submission (ROADMAP open item 3 / ISSUE 8).

All tier-1: in-process, seeded, CPU backend.  The two acceptance tests
are ``test_tenant_isolation_concurrent_queries`` (N parallel queries
across 2 tenants, isolation proven by counters, oracle-correct rows)
and ``test_result_cache_repeat_and_source_invalidation`` (second
submission of an identical plan served from cache with NO task
dispatched; a changed source invalidates)."""
import os
import threading
import time

import numpy as np
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema
from spark_rapids_tpu.expressions import col, count, sum_
from spark_rapids_tpu.expressions.core import Alias
from spark_rapids_tpu.memory.semaphore import (
    PrioritySemaphore, WeightedPrioritySemaphore)
from spark_rapids_tpu.memory.spill import make_spillable, spill_framework
from spark_rapids_tpu.memory.tenant import TENANTS, TenantBudgetExceeded
from spark_rapids_tpu.serving import (
    AdmissionRejected, ClusterDriverRunner, LocalSessionRunner, QueryQueue,
    ResultCache, UncacheableError, plan_fingerprint)
from spark_rapids_tpu.shuffle.stats import (
    reset_shuffle_counters, shuffle_counters)
from spark_rapids_tpu.testing.chaos import CHAOS


@pytest.fixture(autouse=True)
def _clean():
    CHAOS.clear()
    reset_shuffle_counters()
    TENANTS.reset()
    yield
    CHAOS.clear()
    TENANTS.reset()


# -- semaphore semantics (satellite: pin before the scheduler builds on

# them) -----------------------------------------------------------------------

def _start_waiter(sem, priority, label, order, started_at):
    def run():
        started_at.append(label)
        sem.acquire(priority)
        order.append(label)
        sem.release()
    t = threading.Thread(target=run, daemon=True)
    t.start()
    return t


def _wait_for(cond, timeout_s=10.0):
    deadline = time.monotonic() + timeout_s
    while not cond():
        assert time.monotonic() < deadline, "condition never held"
        time.sleep(0.005)


def test_priority_semaphore_wakes_priority_then_fifo():
    """REGRESSION PIN: under contention, waiters wake lowest-priority-
    value first, FIFO within equal priority — the contract the serving
    scheduler builds on (reference: PrioritySemaphore.scala:26)."""
    sem = PrioritySemaphore(1)
    sem.acquire(0)                      # hold the only permit
    order, started = [], []
    threads = []
    # start waiters one at a time so their FIFO seq order is exactly
    # submission order: A(pri 5), B(pri 1), C(pri 1), D(pri 0)
    for i, (label, pri) in enumerate(
            [("A", 5), ("B", 1), ("C", 1), ("D", 0)]):
        threads.append(_start_waiter(sem, pri, label, order, started))
        _wait_for(lambda i=i: sem.waiting() == i + 1)
    sem.release()
    for t in threads:
        t.join(timeout=10)
    assert order == ["D", "B", "C", "A"], order


def test_priority_semaphore_timeout_withdraws_ticket():
    sem = PrioritySemaphore(1)
    sem.acquire(0)
    # a timed-out waiter must not wedge the queue for the next one
    assert sem.acquire(0, deadline=time.monotonic() + 0.05) is False
    assert sem.waiting() == 0
    sem.release()
    assert sem.acquire(0, deadline=time.monotonic() + 1.0) is True


def test_weighted_semaphore_cost_and_head_of_line():
    sem = WeightedPrioritySemaphore(10)
    assert sem.acquire(0, cost=6)
    assert sem.available() == 4
    order = []

    def big():
        sem.acquire(0, cost=6)          # head of line: needs a release
        order.append("big")
        sem.release(6)

    t = threading.Thread(target=big, daemon=True)
    t.start()
    _wait_for(lambda: sem.waiting() == 1)
    # a later, smaller request must NOT overtake the waiting head even
    # though its cost currently fits (no starvation of big queries)
    def small():
        sem.acquire(0, cost=2)
        order.append("small")
        sem.release(2)
    t2 = threading.Thread(target=small, daemon=True)
    t2.start()
    _wait_for(lambda: sem.waiting() == 2)
    sem.release(6)
    t.join(timeout=10)
    t2.join(timeout=10)
    assert order == ["big", "small"], order
    assert sem.available() == 10


# -- admission control --------------------------------------------------------

def _counting_runner(active, high_water, hold_s=0.05):
    lock = threading.Lock()

    def run(plan, ctx):
        with lock:
            active[0] += 1
            high_water[0] = max(high_water[0], active[0])
        time.sleep(hold_s)
        with lock:
            active[0] -= 1
        return [("ok", ctx.tenant)]
    return run


def test_admission_bounds_concurrency_and_counts():
    active, high = [0], [0]
    q = QueryQueue(_counting_runner(active, high), conf={
        "spark.rapids.serving.maxConcurrentQueries": "2",
        "spark.rapids.serving.cache.enabled": "false"})
    futs = [q.submit_async({"p": i}, tenant="t%d" % (i % 2), cacheable=False)
            for i in range(6)]
    rows = [f.result(timeout=30) for f in futs]
    q.close()
    assert len(rows) == 6
    assert high[0] <= 2, f"admission bound breached: {high[0]} concurrent"
    c = shuffle_counters()
    assert c["queries_admitted"] == 6
    assert c["queries_queued"] >= 1       # some had to wait
    assert c["queries_rejected"] == 0


def test_admission_queue_full_and_timeout_reject():
    gate = threading.Event()

    def blocking_runner(plan, ctx):
        gate.wait(30)
        return []
    q = QueryQueue(blocking_runner, conf={
        "spark.rapids.serving.maxConcurrentQueries": "1",
        "spark.rapids.serving.queue.maxDepth": "1",
        "spark.rapids.serving.cache.enabled": "false"})
    f1 = q.submit_async({"p": 1}, cacheable=False)          # runs, blocked
    _wait_for(lambda: shuffle_counters()["queries_admitted"] == 1)
    f2 = q.submit_async({"p": 2}, cacheable=False)          # waits
    _wait_for(lambda: q._slots.waiting() == 1)
    with pytest.raises(AdmissionRejected) as e3:            # queue full
        q.submit({"p": 3}, cacheable=False)
    assert e3.value.reason == "queue_full"
    # timeout while waiting: use a direct submit with a tiny timeout —
    # it would be waiter #2 but the depth check fires first, so drain
    # one slot to test the timeout path in isolation
    gate.set()
    f1.result(timeout=30)
    f2.result(timeout=30)
    gate.clear()
    f4 = q.submit_async({"p": 4}, cacheable=False)          # blocks again
    _wait_for(lambda: shuffle_counters()["queries_admitted"] == 3)
    with pytest.raises(AdmissionRejected) as e5:
        q.submit({"p": 5}, timeout_s=0.1, cacheable=False)
    assert e5.value.reason == "timeout"
    gate.set()
    f4.result(timeout=30)
    q.close()
    c = shuffle_counters()
    assert c["queries_rejected"] == 2
    assert c["queries_queued"] >= 2


def test_admission_byte_bound_engages_after_arena_config():
    """Review finding: the byte-weighted bound must size itself from the
    arena's budget at FIRST admission, not at construction — a cluster
    QueryQueue is often built before initialize_memory runs."""
    from spark_rapids_tpu.memory.arena import configure, device_arena
    old = device_arena().budget_bytes
    q = QueryQueue(lambda p, c: ["ok"], conf={
        "spark.rapids.serving.admission.memoryFraction": "0.5",
        "spark.rapids.serving.cache.enabled": "false"})
    assert q._bytes is None                  # arena unbudgeted so far
    configure(1 << 20)
    try:
        q.submit({"p": 1}, est_bytes=1000, cacheable=False)
        assert q.admission_bytes == 1 << 19  # fraction of the budget
        assert q._bytes is not None
        assert q._bytes.available() == q.admission_bytes  # fully released
    finally:
        configure(old)
        q.close()


def test_chaos_admit_delay_site():
    CHAOS.install("serving.admit.delay", count=1, seconds=0.3)
    q = QueryQueue(lambda plan, ctx: ["x"], conf={
        "spark.rapids.serving.cache.enabled": "false"})
    before = CHAOS.delayed_seconds("serving.admit.delay")
    t0 = time.monotonic()
    q.submit({"p": 1}, cacheable=False)
    wall = time.monotonic() - t0
    assert CHAOS.delayed_seconds("serving.admit.delay") - before \
        == pytest.approx(0.3)
    assert wall >= 0.3


# -- tenant budgets (memory/tenant.py) ---------------------------------------

def _batch(nrows=20_000, seed=0):
    rng = np.random.RandomState(seed)
    return ColumnarBatch.from_pydict(
        {"k": rng.randint(0, 7, nrows).tolist(),
         "v": rng.randint(-100, 100, nrows).tolist()},
        Schema.of(k=T.INT, v=T.LONG))


def test_tenant_budget_denial_and_self_spill():
    """Deterministic ledger semantics: a pinned working set over budget
    DENIES (budget_denials, TenantBudgetExceeded names the tenant);
    after unpinning, the charge self-spills the tenant's OWN handle
    (tenant_spills) and succeeds.  A neighbor's residency is untouched."""
    b = _batch()
    one = b.device_size_bytes()
    TENANTS.set_budget("small", int(one * 1.5))
    with TENANTS.scope("big"):
        neighbor = make_spillable(_batch(seed=1))
    with TENANTS.scope("small"):
        h1 = make_spillable(_batch(seed=2))
        h1.materialize()                 # pinned: cannot self-spill
        with pytest.raises(TenantBudgetExceeded) as exc:
            make_spillable(_batch(seed=3))
        assert exc.value.tenant == "small"
        h1.unpin()
        h2 = make_spillable(_batch(seed=3))   # self-spills h1, fits
    assert not h1.on_device() and h2.on_device()
    assert neighbor.on_device(), "neighbor tenant was evicted"
    snap = TENANTS.snapshot()
    assert snap["small"]["budget_denials"] == 1
    assert snap["small"]["spills"] >= 1
    assert snap["big"]["spills"] == 0
    c = shuffle_counters()
    assert c["budget_denials"] == 1 and c["tenant_spills"] >= 1
    for h in (h1, h2, neighbor):
        h.close()


def test_global_pressure_spills_lightest_tenant_first():
    from spark_rapids_tpu.memory.arena import device_arena
    TENANTS.set_budget("light", 0, weight=1.0)
    TENANTS.set_budget("heavy", 0, weight=4.0)
    with TENANTS.scope("light"):
        hl = make_spillable(_batch(seed=4))
    with TENANTS.scope("heavy"):
        hh = make_spillable(_batch(seed=5))
    freed = spill_framework().spill_device(1)   # need 1 byte: one victim
    assert freed > 0
    assert not hl.on_device(), "lighter tenant should spill first"
    assert hh.on_device()
    assert device_arena().used_bytes >= 0
    hl.close()
    hh.close()


# -- the tier-1 concurrency acceptance test ----------------------------------

def _mkplan(sess, batches, parts=2):
    df = sess.create_dataframe(list(batches), num_partitions=parts)
    return df.group_by("k").agg(Alias(sum_(col("v")), "sv"),
                                Alias(count(), "n")).plan


def _wide_batch(nrows=30_000, seed=0):
    # HIGH-cardinality keys: the partial aggregate stays ~row-sized, so
    # the CACHE_ONLY shuffle slices carry real bytes and the query has a
    # spillable working set worth budgeting
    rng = np.random.RandomState(seed)
    return ColumnarBatch.from_pydict(
        {"k": rng.randint(0, nrows, nrows).tolist(),
         "v": rng.randint(-100, 100, nrows).tolist()},
        Schema.of(k=T.INT, v=T.LONG))


def test_tenant_isolation_concurrent_queries():
    """ACCEPTANCE: N=4 queries in parallel across 2 tenants; the
    over-budget tenant spills/retries ITSELF (budget_denials +
    tenant_spills name it; the neighbor tenant records zero of both),
    no cross-query OOM kill, and every query returns oracle-correct
    rows."""
    batches = [_wide_batch(seed=10), _wide_batch(seed=11)]
    runner = LocalSessionRunner({})
    plan = _mkplan(runner.session, batches)
    oracle = sorted(
        TpuSession({"spark.rapids.sql.enabled": "false"})
        .create_dataframe(list(batches), num_partitions=2)
        .group_by("k").agg(Alias(sum_(col("v")), "sv"),
                           Alias(count(), "n")).collect())

    q = QueryQueue(runner, conf={
        "spark.rapids.serving.maxConcurrentQueries": "4",
        "spark.rapids.serving.cache.enabled": "false"})
    # calibrate: one probe run records the query's device high-water
    q.submit(plan, tenant="probe", cacheable=False)
    peak = TENANTS.get("probe").peak_bytes
    assert peak > 0, "CACHE_ONLY shuffle slices should be tenant-tagged"
    # 'small' starts with a resident BALLAST handle and a budget that
    # fits the query alone but NOT ballast + query: its own charges must
    # evict its own ballast (deterministic self-spill), while 'big' is
    # unlimited and must feel nothing
    with TENANTS.scope("small"):
        ballast = make_spillable(_wide_batch(seed=99))
    with TENANTS.scope("big"):
        big_ballast = make_spillable(_wide_batch(seed=98))
    TENANTS.set_budget(
        "small", peak + ballast.size_bytes // 2, weight=1.0)
    TENANTS.set_budget("big", 0, weight=2.0)

    # one budgeted query + three unlimited neighbors in parallel (two
    # smalls would legitimately exceed the budget TOGETHER — each
    # tenant budget covers one working set + the ballast's slack)
    futs = [q.submit_async(plan, tenant=t, cacheable=False)
            for t in ("small", "big", "big", "big")]
    rows = [f.result(timeout=120) for f in futs]
    q.close()
    for r in rows:
        assert sorted(r) == oracle      # every query correct, no kill
    assert not ballast.on_device(), \
        "small's budget breach must spill small's OWN residency"
    assert big_ballast.on_device(), \
        "a neighbor tenant's residency was evicted"
    snap = TENANTS.snapshot()
    pressure = snap["small"]["spills"] + snap["small"]["budget_denials"]
    assert pressure > 0, f"small tenant never felt its budget: {snap}"
    assert snap["big"]["spills"] == 0 and \
        snap["big"]["budget_denials"] == 0, f"pressure leaked: {snap}"
    c = shuffle_counters()
    assert c["queries_admitted"] >= 5
    assert c["tenant_spills"] + c["budget_denials"] == pressure
    ballast.close()
    big_ballast.close()


def test_concurrent_order_by_queries_from_parquet_complete(tmp_path,
                                                          monkeypatch):
    """Two ORDER BY queries over Parquet at once in one process: each
    query's first task holds its range sort's lock and gives its permit
    up in the scan; the scans here deliver nothing until both have, and
    until the two other tasks have had time to take the permits.  At PR 28
    those two kept them while they waited for the locks and no scan could
    ever upload.  A task now waits for a materialise-once lock off the
    semaphore."""
    from concurrent.futures import TimeoutError as FutureTimeout

    from spark_rapids_tpu.memory.semaphore import tpu_semaphore
    from spark_rapids_tpu.plan.execs.scan import TpuParquetScanExec
    from tests.test_range_sort import ordered_agg, write_two_parquet_files
    paths = write_two_parquet_files(tmp_path)
    both_in_scan = threading.Barrier(2)
    host_iter = TpuParquetScanExec._host_iter

    def gated_host_iter(self, idx):
        if idx == 0:            # once a query: its first file's decode
            both_in_scan.wait(60)
            deadline = time.monotonic() + 1.0
            while tpu_semaphore()._sem.available() \
                    and time.monotonic() < deadline:
                time.sleep(0.02)
        return host_iter(self, idx)
    monkeypatch.setattr(TpuParquetScanExec, "_host_iter", gated_host_iter)

    runner = LocalSessionRunner(
        {"spark.rapids.sql.reader.batchSizeRows": "1500"})
    plan = ordered_agg(runner.session, paths).plan
    q = QueryQueue(runner, conf={
        "spark.rapids.serving.maxConcurrentQueries": "2",
        "spark.rapids.serving.cache.enabled": "false"})
    futs = [q.submit_async(plan, tenant=t, cacheable=False)
            for t in ("a", "b")]
    try:
        rows = [f.result(timeout=180) for f in futs]
    except FutureTimeout:
        from tests.test_range_sort import thread_stacks
        stacks = thread_stacks()
        for f in futs:          # a cancelled wait for a permit wakes
            q.cancel(f.query_id)
        pytest.fail("concurrent queries deadlocked on the device permit:\n"
                    + stacks)
    finally:
        q.close()
    expected = ordered_agg(
        TpuSession({"spark.rapids.sql.enabled": "false"}), paths).collect()
    assert rows == [expected, expected] and len(expected) == 40


# -- result cache -------------------------------------------------------------

def _write_parquet(path, seed=0, n=500):
    import pyarrow as pa
    import pyarrow.parquet as pq
    rng = np.random.RandomState(seed)
    pq.write_table(pa.table({
        "k": rng.randint(0, 5, n).astype(np.int64),
        "v": rng.randint(-50, 50, n).astype(np.int64)}), path)


def test_plan_fingerprint_stability_and_sources(tmp_path):
    p = os.path.join(str(tmp_path), "t.parquet")
    _write_parquet(p)
    s = TpuSession({})

    def mk():
        return s.read_parquet(p).group_by("k").agg(
            Alias(count(), "n")).plan
    k1, src1 = plan_fingerprint(mk())
    k2, _ = plan_fingerprint(mk())
    assert k1 == k2 and p in src1
    k3, _ = plan_fingerprint(mk(), {"x": "1"})    # conf folds in
    assert k3 != k1
    time.sleep(0.05)
    _write_parquet(p, seed=9)                     # rewrite: key changes
    k4, _ = plan_fingerprint(mk())
    assert k4 != k1
    with pytest.raises(UncacheableError):
        plan_fingerprint(
            s.create_dataframe({"a": [1]}, Schema.of(a=T.INT))
            .map_batches(lambda b: b, Schema.of(a=T.INT)).plan)


def test_plan_fingerprint_rejects_opaque_udfs():
    """Review finding: UDF reprs are NAME-based ('pyudf:<lambda>(..)'),
    so two different lambdas would alias one cache key and serve each
    other's rows — any plan carrying an opaque callable is uncacheable."""
    from spark_rapids_tpu.expressions.udf import tpu_udf
    s = TpuSession({})
    df = s.create_dataframe({"k": [1, 2, 3]}, Schema.of(k=T.INT))
    f1 = tpu_udf(lambda x: x + 1 if x % 3 == 0 else x - 1,
                 return_type=T.LONG)
    plan = df.select(Alias(f1(col("k")), "u")).plan
    with pytest.raises(UncacheableError):
        plan_fingerprint(plan)
    # and the serving layer just bypasses the cache for it
    runs = [0]

    def counting(pl, ctx):
        runs[0] += 1
        return [("x",)]
    q = QueryQueue(counting, conf={})
    q.submit(plan)
    q.submit(plan)
    assert runs[0] == 2         # never served from cache
    q.close()


def test_single_flight_follower_honors_timeout():
    """A single-flight follower's wait for a leader that is still running
    is bounded by the follower's own deadline (its cancel token), not by
    the admission timeout the two share: past its deadline it unblocks
    with a typed ``QueryCancelled`` instead of hanging, and it does not
    run the query itself."""
    from spark_rapids_tpu.utils.cancel import QueryCancelled
    gate = threading.Event()
    started = threading.Event()
    runs = [0]

    def stuck(pl, ctx):
        runs[0] += 1
        started.set()
        gate.wait(30)
        return [("late",)]
    q = QueryQueue(stuck, conf={
        "spark.rapids.serving.maxConcurrentQueries": "1"})
    s = TpuSession({})
    plan = s.create_dataframe({"k": [1]}, Schema.of(k=T.INT)) \
        .group_by("k").agg(Alias(count(), "n")).plan
    leader = q.submit_async(plan)
    assert started.wait(10)
    t0 = time.monotonic()
    with pytest.raises(QueryCancelled):
        q.submit(plan, timeout_s=0.3, deadline_s=0.6)
    assert 0.5 < time.monotonic() - t0 < 5.0
    gate.set()
    assert leader.result(timeout=30) == [("late",)]
    assert runs[0] == 1
    q.close()


def test_single_flight_follower_without_deadline_hands_over_to_itself():
    """With no query deadline (the default) a follower still does not wait
    for a wedged leader forever: past the stall watchdog's threshold it
    runs the query itself and answers; with the watchdog off the bound is
    queue.timeout."""
    assert QueryQueue(lambda pl, ctx: [], conf={}).follow_limit_s == 300.0
    assert QueryQueue(lambda pl, ctx: [], conf={
        "spark.rapids.watchdog.stallSeconds": "0",
        "spark.rapids.serving.queue.timeout": "7"}).follow_limit_s == 7.0
    gate = threading.Event()
    started = threading.Event()
    runs = [0]

    def first_wedges(pl, ctx):
        runs[0] += 1
        if runs[0] == 1:
            started.set()
            gate.wait(30)
            return [("late",)]
        return [("own",)]
    q = QueryQueue(first_wedges, conf={
        "spark.rapids.serving.maxConcurrentQueries": "2",
        "spark.rapids.watchdog.stallSeconds": "0.5"})
    s = TpuSession({})
    plan = s.create_dataframe({"k": [1]}, Schema.of(k=T.INT)) \
        .group_by("k").agg(Alias(count(), "n")).plan
    leader = q.submit_async(plan)
    assert started.wait(10)
    t0 = time.monotonic()
    assert q.submit(plan) == [("own",)]
    assert 0.4 < time.monotonic() - t0 < 5.0
    assert runs[0] == 2
    gate.set()
    assert leader.result(timeout=30) == [("late",)]
    q.close()


def test_result_cache_repeat_and_source_invalidation(tmp_path):
    """ACCEPTANCE: the second submission of an identical plan serves
    from cache (cache_hits >= 1, the runner is NOT invoked again — no
    work dispatched), and a changed source invalidates it."""
    p = os.path.join(str(tmp_path), "t.parquet")
    _write_parquet(p)
    s = TpuSession({})
    plan = s.read_parquet(p).group_by("k").agg(Alias(count(), "n")).plan
    runs = [0]
    inner = LocalSessionRunner({})

    def counting(pl, ctx):
        runs[0] += 1
        return inner(pl, ctx)
    q = QueryQueue(counting, conf={})
    r1 = q.submit(plan, tenant="alice")
    r2 = q.submit(plan, tenant="alice")
    assert sorted(r1) == sorted(r2)
    assert runs[0] == 1, "cache hit must not dispatch work"
    c = shuffle_counters()
    assert c["cache_hits"] == 1 and c["cache_misses"] == 1
    assert c["queries_admitted"] == 1
    assert q.cache.stats()["per_tenant"]["alice"]["hits"] == 1

    # changed source data: the rewritten file's (mtime, size) folds
    # into the key -> miss -> recompute with fresh rows
    time.sleep(0.05)
    _write_parquet(p, seed=9)
    plan2 = s.read_parquet(p).group_by("k").agg(Alias(count(), "n")).plan
    r3 = q.submit(plan2, tenant="alice")
    assert runs[0] == 2
    # explicit invalidation drops every entry reading the path
    assert q.invalidate_source(p) >= 1
    r4 = q.submit(plan2, tenant="alice")
    assert runs[0] == 3 and sorted(r4) == sorted(r3)
    assert shuffle_counters()["cache_invalidations"] >= 1
    q.close()


def test_cache_corruption_detected_and_recomputed(tmp_path):
    """Chaos site serving.cache.corrupt: a flipped bit in the cached
    payload fails CRC verify -> entry dropped, query recomputed, rows
    correct; corrupt rows are NEVER served."""
    p = os.path.join(str(tmp_path), "t.parquet")
    _write_parquet(p)
    s = TpuSession({})
    plan = s.read_parquet(p).group_by("k").agg(Alias(count(), "n")).plan
    runs = [0]
    inner = LocalSessionRunner({})

    def counting(pl, ctx):
        runs[0] += 1
        return inner(pl, ctx)
    q = QueryQueue(counting, conf={})
    r1 = q.submit(plan)
    CHAOS.install("serving.cache.corrupt", count=1, seed=7)
    r2 = q.submit(plan)                 # corrupt hit -> recompute
    assert runs[0] == 2
    assert sorted(r2) == sorted(r1)
    c = shuffle_counters()
    assert c["cache_invalidations"] == 1
    r3 = q.submit(plan)                 # re-stored entry serves again
    assert runs[0] == 2 and sorted(r3) == sorted(r1)
    assert shuffle_counters()["cache_hits"] == 1
    q.close()


def test_result_cache_lru_eviction_and_ttl():
    import pickle
    big = list(range(100))
    bound = int(len(pickle.dumps(big)) * 2.5)   # fits 2 entries, not 3
    cache = ResultCache(max_bytes=bound, ttl_s=0.0)
    assert cache.put("k1", big, frozenset(["s1"]), tenant="owner")
    assert cache.put("k2", big, frozenset(["s2"]), tenant="owner")
    assert cache.put("k3", big, frozenset(["s3"]), tenant="other")
    stats = cache.stats()
    assert stats["used_bytes"] <= bound
    assert shuffle_counters()["cache_evictions"] >= 1
    # the eviction charges the evicted entry's OWNER, not the inserter
    assert stats["per_tenant"]["owner"]["evictions"] >= 1
    assert stats["per_tenant"].get("other", {}).get("evictions", 0) == 0
    # LRU: k1 was oldest -> gone; the newest stays
    assert cache.get("k3", tenant="other") == big
    assert cache.get("k1", tenant="owner") is None
    ttl = ResultCache(max_bytes=1 << 20, ttl_s=0.05)
    ttl.put("k", [1], frozenset(), tenant="t")
    assert ttl.get("k", tenant="t") == [1]
    time.sleep(0.08)
    assert ttl.get("k", tenant="t") is None    # expired


def test_single_flight_coalesces_concurrent_identical_plans(tmp_path):
    """A miss-STORM of identical plans executes ONCE: the first miss
    leads, concurrent submissions wait for it and serve from the entry
    it stores (found by the end-to-end verify drive: without
    single-flight, N concurrent dashboards each executed the query)."""
    p = os.path.join(str(tmp_path), "t.parquet")
    _write_parquet(p)
    s = TpuSession({})
    plan = s.read_parquet(p).group_by("k").agg(Alias(count(), "n")).plan
    runs = [0]
    started = threading.Event()
    gate = threading.Event()
    inner = LocalSessionRunner({})

    def gated(pl, ctx):
        runs[0] += 1
        started.set()
        gate.wait(30)
        return inner(pl, ctx)
    q = QueryQueue(gated, conf={})
    leader = q.submit_async(plan, tenant="t0")
    assert started.wait(10)
    followers = [q.submit_async(plan, tenant="t%d" % i)
                 for i in (1, 2, 3)]
    time.sleep(0.2)          # followers reach the single-flight wait
    gate.set()
    rows = [f.result(timeout=60) for f in [leader] + followers]
    q.close()
    assert all(sorted(r) == sorted(rows[0]) for r in rows)
    assert runs[0] == 1, "identical concurrent plans must execute once"
    c = shuffle_counters()
    assert c["queries_admitted"] == 1
    assert c["cache_hits"] >= 3


def test_cache_oversized_payload_not_cached():
    cache = ResultCache(max_bytes=64)
    assert not cache.put("k", list(range(1000)), frozenset())
    assert cache.get("k") is None


# -- concurrent driver submission (protocol-level fake executors) ------------

def test_driver_concurrent_submissions_queue_per_executor():
    """Concurrent TpuClusterDriver.submit: three queries dispatched
    while the executors are gated QUEUE per executor (a second dispatch
    never clobbers an undelivered first — the pre-r8 one-slot regression)
    and all three complete with their own rows."""
    import pickle

    from spark_rapids_tpu.cluster.driver import TpuClusterDriver
    from spark_rapids_tpu.shuffle.net import (
        PeerClient, ShuffleExecutor, _request)

    class GatedExecutor:
        def __init__(self, driver, name, gate):
            self.driver, self.name, self.gate = driver, name, gate
            self.node = ShuffleExecutor(
                name, driver_addr=driver.shuffle.server.addr)
            self.stop = threading.Event()
            self.tasks_run = []
            self.t = threading.Thread(target=self._run, daemon=True)
            self.t.start()

        def _run(self):
            while not self.stop.is_set():
                try:
                    PeerClient(
                        self.driver.shuffle.server.addr).heartbeat(
                        self.name)
                except OSError:
                    time.sleep(0.02)
                    continue
                if not self.gate.is_set():
                    time.sleep(0.02)
                    continue
                try:
                    h, _ = _request(
                        self.driver.rpc_addr,
                        {"op": "get_task", "executor_id": self.name},
                        retriable=False)
                except OSError:
                    time.sleep(0.02)
                    continue
                task = h.get("task")
                if task is None:
                    time.sleep(0.02)
                    continue
                self.tasks_run.append(task["query_id"])
                rank, world = task["rank"], task["world"]
                out = [(p, [[p, task["query_id"]]])
                       for p in range(4) if p % world == rank]
                _request(self.driver.rpc_addr,
                         {"op": "task_result",
                          "query_id": task["query_id"],
                          "executor_id": self.name, "rank": rank,
                          "attempt": task.get("attempt", 0)},
                         pickle.dumps(out))

        def close(self):
            self.stop.set()
            self.t.join(timeout=5)
            self.node.close()

    gate = threading.Event()
    driver = TpuClusterDriver(conf={}, heartbeat_timeout_s=30.0)
    ws = [GatedExecutor(driver, f"w{i}", gate) for i in range(2)]
    try:
        driver.wait_for_executors(2, timeout_s=30)
        res, threads = {}, []
        for tag in (1, 2, 3):
            t = threading.Thread(
                target=lambda tag=tag: res.__setitem__(
                    tag, driver.submit({"plan": tag}, timeout_s=60)),
                daemon=True)
            t.start()
            threads.append(t)
        # all three queries must be IN FLIGHT with their tasks queued
        # per executor before anything runs
        _wait_for(lambda: len(driver._expected) == 3)
        with driver._lock:
            queued = {e: [t["query_id"] for t in q]
                      for e, q in driver._tasks.items()}
        assert all(len(v) == 3 for v in queued.values()), queued
        gate.set()
        for t in threads:
            t.join(timeout=60)
        # each query got its OWN rows back (tagged with its qid), and
        # three distinct queries ran
        qids_seen = set()
        for tag in (1, 2, 3):
            rows = sorted(tuple(r) for r in res[tag])
            qid = rows[0][1]
            assert rows == [(p, qid) for p in range(4)], rows
            qids_seen.add(qid)
        assert len(qids_seen) == 3
    finally:
        for w in ws:
            w.close()
        driver.close()


def test_driver_serving_cache_skips_task_dispatch(tmp_path):
    """Cluster form of the cache acceptance: the repeated plan through
    QueryQueue(ClusterDriverRunner) dispatches ZERO executor tasks on
    the second submission."""
    import pickle

    from spark_rapids_tpu.cluster.driver import TpuClusterDriver
    from spark_rapids_tpu.shuffle.net import (
        PeerClient, ShuffleExecutor, _request)

    p = os.path.join(str(tmp_path), "t.parquet")
    _write_parquet(p)
    s = TpuSession({})
    plan = s.read_parquet(p).group_by("k").agg(Alias(count(), "n")).plan

    tasks_run = []

    class Echo:
        def __init__(self, driver, name):
            self.driver, self.name = driver, name
            self.node = ShuffleExecutor(
                name, driver_addr=driver.shuffle.server.addr)
            self.stop = threading.Event()
            self.t = threading.Thread(target=self._run, daemon=True)
            self.t.start()

        def _run(self):
            while not self.stop.is_set():
                try:
                    PeerClient(
                        self.driver.shuffle.server.addr).heartbeat(
                        self.name)
                    h, _ = _request(
                        self.driver.rpc_addr,
                        {"op": "get_task", "executor_id": self.name},
                        retriable=False)
                except OSError:
                    time.sleep(0.02)
                    continue
                task = h.get("task")
                if task is None:
                    time.sleep(0.02)
                    continue
                tasks_run.append((self.name, task["query_id"]))
                rank, world = task["rank"], task["world"]
                out = [(pp, [[pp, 1]])
                       for pp in range(2) if pp % world == rank]
                _request(self.driver.rpc_addr,
                         {"op": "task_result",
                          "query_id": task["query_id"],
                          "executor_id": self.name, "rank": rank,
                          "attempt": task.get("attempt", 0)},
                         pickle.dumps(out))

        def close(self):
            self.stop.set()
            self.t.join(timeout=5)
            self.node.close()

    driver = TpuClusterDriver(conf={}, heartbeat_timeout_s=30.0)
    ws = [Echo(driver, f"w{i}") for i in range(2)]
    try:
        driver.wait_for_executors(2, timeout_s=30)
        q = QueryQueue(ClusterDriverRunner(driver, timeout_s=60),
                       conf={})
        r1 = q.submit(plan, tenant="dash")
        n_after_first = len(tasks_run)
        assert n_after_first == 2       # one task per executor
        r2 = q.submit(plan, tenant="dash")
        assert r2 == r1
        assert len(tasks_run) == n_after_first, \
            "cache hit dispatched executor tasks"
        c = shuffle_counters()
        assert c["cache_hits"] == 1
        # changed source -> new key -> real dispatch again
        time.sleep(0.05)
        _write_parquet(p, seed=3)
        plan2 = s.read_parquet(p).group_by("k").agg(
            Alias(count(), "n")).plan
        q.submit(plan2, tenant="dash")
        assert len(tasks_run) == n_after_first + 2
        q.close()
    finally:
        for w in ws:
            w.close()
        driver.close()
