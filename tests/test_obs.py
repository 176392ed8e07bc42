"""Query-scoped observability plane (utils/obs.py + tools/trace_export).

Covers the PR-13 acceptance surface:
  * the ShuffleCounters tee: concurrent queries get ATTRIBUTED counter
    scopes whose per-query sums reconcile with the global deltas;
  * EXPLAIN ANALYZE on a shuffled-join query: every exec node renders
    non-zero measured rows/time, launches + attributed counters in the
    footer;
  * cross-process span round-trip: a 2-rank protocol-level cluster
    query returns executor task spans/metrics merged under the driver's
    trace with rank+attempt tags;
  * Perfetto export: one cluster query's trace JSON loads with serving,
    driver and >=2 executor-rank tracks (structural validation);
  * the stall watchdog names the wedged thread's query id + innermost
    open span;
  * fixed-bucket latency histograms (serving submit->done) in cluster
    stats and their percentiles.
"""
import json
import os
import pickle
import re
import threading
import time

import numpy as np
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import Schema
from spark_rapids_tpu.expressions.aggregates import count, sum_
from spark_rapids_tpu.expressions.core import Alias, col
from spark_rapids_tpu.shuffle.stats import (
    HISTOGRAMS, SHUFFLE_COUNTERS, Histogram, histograms,
    reset_shuffle_counters, shuffle_counters)
from spark_rapids_tpu.utils import obs
from spark_rapids_tpu.utils.tracing import trace_range


# -- Histogram ----------------------------------------------------------------

def test_histogram_percentiles_and_reset():
    h = Histogram(lowest_s=0.001, n_buckets=20)
    for v in (0.001, 0.002, 0.002, 0.004, 0.1):
        h.record(v)
    snap = h.snapshot()
    assert snap["count"] == 5
    assert snap["max_s"] == pytest.approx(0.1)
    assert snap["sum_s"] == pytest.approx(0.109)
    # bucket upper bounds: conservative (reported >= true), capped at max
    assert snap["p50"] >= 0.002 and snap["p50"] <= 0.004
    assert snap["p99"] == pytest.approx(0.1)
    h.reset()
    assert h.snapshot()["count"] == 0
    assert h.percentile(0.5) == 0.0


def test_histograms_ride_cluster_stats_and_reset():
    from spark_rapids_tpu.cluster.stats import (
        local_histograms, reset_local_shuffle_counters)
    reset_local_shuffle_counters()
    HISTOGRAMS["serving_submit_s"].record(0.25)
    snap = local_histograms()
    assert snap["serving_submit_s"]["count"] == 1
    assert set(snap) >= {"serving_submit_s", "fetch_wait_s",
                         "stage_drain_s"}
    reset_local_shuffle_counters()    # one epoch: counters + histograms
    assert local_histograms()["serving_submit_s"]["count"] == 0


# -- counter tee + span recording ---------------------------------------------

def test_counter_tee_attributes_per_query_and_reconciles():
    """Two threads under two traces: each scope sees exactly its own
    deltas, their sums equal the global accumulation, and set_max tees
    as a per-query gauge."""
    reset_shuffle_counters()
    ta, tb = obs.QueryTrace("qa"), obs.QueryTrace("qb")

    def work(tr, n):
        with obs.trace_scope(tr):
            for _ in range(n):
                SHUFFLE_COUNTERS.add(merges=1, blocks_fetched=2)
            SHUFFLE_COUNTERS.set_max(heartbeat_failure_streak=n)
    th = [threading.Thread(target=work, args=(ta, 3)),
          threading.Thread(target=work, args=(tb, 5))]
    for t in th:
        t.start()
    for t in th:
        t.join()
    sa, sb = ta.counters_snapshot(), tb.counters_snapshot()
    assert sa["merges"] == 3 and sa["blocks_fetched"] == 6
    assert sb["merges"] == 5 and sb["blocks_fetched"] == 10
    assert sa["heartbeat_failure_streak"] == 3
    g = shuffle_counters()
    assert g["merges"] == sa["merges"] + sb["merges"]
    assert g["blocks_fetched"] == sa["blocks_fetched"] + \
        sb["blocks_fetched"]
    # no ambient trace: adds still count globally, scope untouched
    SHUFFLE_COUNTERS.add(merges=1)
    assert shuffle_counters()["merges"] == 9
    assert ta.counters_snapshot()["merges"] == 3


def test_trace_range_records_into_ambient_trace_and_span_cap():
    tr = obs.QueryTrace("q", max_spans=2)
    with obs.trace_scope(tr):
        with trace_range("scan.wait"):
            pass
        with obs.span("serving.run", tags={"tenant": "t0"}):
            pass
        with obs.span("serving.run"):    # over the cap: dropped, counted
            pass
    spans = tr.spans_snapshot()
    assert [s["name"] for s in spans] == ["scan.wait", "serving.run"]
    assert spans[1]["tags"] == {"tenant": "t0"}
    assert tr.dropped_spans == 1
    assert all(s["t1"] >= s["t0"] for s in spans)
    # outside any scope: no recording, no error
    with trace_range("scan.wait"):
        pass
    assert len(tr.spans_snapshot()) == 2


def test_anchor_spans_survive_a_full_buffer():
    """The control-plane anchors recorded at query END (serving.submit,
    driver.query, merged executor.task) must survive a span buffer that
    data-plane ranges already filled — they give the exported timeline
    its serving/driver/rank tracks."""
    tr = obs.QueryTrace("busy", max_spans=2)
    with obs.trace_scope(tr):
        for _ in range(4):                      # data plane fills + drops
            with obs.span("scan.wait"):
                pass
        with obs.span("serving.submit", anchor=True):
            pass
    tr.merge_remote({"spans": [
        {"name": "executor.task", "t0": 1.0, "t1": 2.0},
        {"name": "scan.wait", "t0": 1.1, "t1": 1.2}]},
        rank=0, attempt=0, eid="w1")
    tr.record_span("driver.query", 0.0, 3.0, track="driver", anchor=True)
    names = [s["name"] for s in tr.spans_snapshot()]
    assert names.count("scan.wait") == 2        # cap held for data plane
    assert "serving.submit" in names
    assert "executor.task" in names             # rank track preserved
    assert "driver.query" in names
    assert tr.dropped_spans == 3                # 2 local + 1 remote


def test_ambient_spawn_carries_the_trace():
    from spark_rapids_tpu.utils.ambient import spawn_with_ambients
    tr = obs.QueryTrace("spawned")
    seen = []
    with obs.trace_scope(tr):
        t = spawn_with_ambients(
            lambda: seen.append(obs.current_query_trace()))
    t.join(timeout=10)
    assert seen == [tr]


def test_watchdog_report_names_query_and_innermost_open_span():
    """Satellite: a stall report carries the wedged thread's ambient
    query_id and its innermost OPEN span (site + elapsed)."""
    from spark_rapids_tpu.utils.watchdog import WATCHDOG
    tr = obs.QueryTrace("stalled-query")
    entered = threading.Event()
    release = threading.Event()

    def wedge():
        with obs.trace_scope(tr), obs.span("serving.run"):
            wid = WATCHDOG.begin_wait("test.obs.wedge")
            entered.set()
            release.wait(30)
            WATCHDOG.end_wait(wid)
    th = threading.Thread(target=wedge, daemon=True)
    th.start()
    assert entered.wait(10)
    try:
        WATCHDOG.reset()
        old = WATCHDOG.stall_seconds
        WATCHDOG.configure(5.0)
        flagged = WATCHDOG.scan(now=time.monotonic() + 60)
        ours = [f for f in flagged if f["site"] == "test.obs.wedge"]
        assert ours, flagged
        assert ours[0]["query_id"] == "stalled-query"
        assert ours[0]["open_span"]["site"] == "serving.run"
        assert ours[0]["open_span"]["elapsed_s"] >= 59.0
    finally:
        WATCHDOG.configure(old if old else 0.0)
        WATCHDOG.reset()
        release.set()
        th.join(timeout=10)


# -- EXPLAIN ANALYZE ----------------------------------------------------------

def test_explain_analyze_shuffled_join_every_node_measured():
    """ACCEPTANCE: explain_analyze on a shuffled-join query renders the
    plan tree with non-zero measured metrics (rows + time) for every
    exec node, and the footer carries non-zero launches plus the
    query-attributed counter snapshot."""
    from spark_rapids_tpu.api.session import TpuSession
    sess = TpuSession({
        "spark.rapids.sql.enabled": "true",
        "spark.rapids.sql.join.broadcastRowThreshold": "0",
        "spark.rapids.sql.join.adaptive.enabled": "false",
        "spark.sql.shuffle.partitions": "2"})
    rng = np.random.RandomState(0)
    n = 4000
    left = sess.create_dataframe(
        {"k": rng.randint(0, 50, n).tolist(),
         "v": rng.randint(0, 100, n).tolist()},
        Schema.of(k=T.LONG, v=T.LONG), num_partitions=2)
    right = sess.create_dataframe(
        {"k": list(range(50)), "w": list(range(50))},
        Schema.of(k=T.LONG, w=T.LONG), num_partitions=2)
    df = left.join(right, on="k").group_by("k").agg(
        Alias(sum_(col("v") + col("w")), "sv"))
    text = sess.explain_analyze(df)
    tree_lines = text.split("\n\n")[0].splitlines()
    assert len(tree_lines) >= 5      # join + exchanges + scans
    assert any("ShuffleExchange" in ln for ln in tree_lines)
    for ln in tree_lines:
        m = re.search(r"rows=(\d+)", ln)
        assert m and int(m.group(1)) > 0, f"no measured rows: {ln!r}"
        t = re.search(r"opTime=([\d.]+)(ms|us)", ln)
        assert t and float(t.group(1)) > 0.0, f"no measured time: {ln!r}"
    m = re.search(r"launches: (\d+)", text)
    assert m and int(m.group(1)) > 0
    assert "counters:" in text and "exchange_stages" in text


# -- concurrent serving attribution (ACCEPTANCE) ------------------------------

def test_concurrent_serving_queries_get_attributed_counters():
    """ACCEPTANCE: two concurrent serving submissions produce per-query
    attributed counter/latency snapshots that are NON-interleaved (the
    exchange-free query's scope holds no shuffle counters) and whose
    per-query sums reconcile with the global counters."""
    from spark_rapids_tpu.serving import LocalSessionRunner, QueryQueue
    runner = LocalSessionRunner({})
    sess = runner.session
    rng = np.random.RandomState(1)
    n = 6000
    data = {"k": rng.randint(0, 16, n).tolist(),
            "v": rng.randint(0, 100, n).tolist()}
    # qa: group-by through a real exchange (shuffle counters move);
    # qb: a scan+filter with NO exchange (its scope must hold none)
    plan_a = (sess.create_dataframe(data, Schema.of(k=T.LONG, v=T.LONG),
                                    num_partitions=2)
              .group_by("k").agg(Alias(sum_(col("v")), "sv"),
                                 Alias(count(), "n")).plan)
    plan_b = (sess.create_dataframe(data, Schema.of(k=T.LONG, v=T.LONG),
                                    num_partitions=2)
              .filter(col("v") > 50).select(col("v")).plan)
    q = QueryQueue(runner, conf={
        "spark.rapids.serving.maxConcurrentQueries": "2",
        "spark.rapids.serving.cache.enabled": "false",
        "spark.rapids.trace.enabled": "true"})
    # warm the compile cache so the traced pass measures execution, not
    # XLA compiles (counters are reset after)
    q.submit(plan_a, tenant="warm", query_id="warm_a")
    q.submit(plan_b, tenant="warm", query_id="warm_b")
    reset_shuffle_counters()
    errs = []

    def run(plan, qid):
        try:
            q.submit(plan, tenant=qid, query_id=qid)
        except BaseException as e:  # noqa: BLE001 — asserted below
            errs.append(e)
    th = [threading.Thread(target=run, args=(plan_a, "qa")),
          threading.Thread(target=run, args=(plan_b, "qb"))]
    for t in th:
        t.start()
    for t in th:
        t.join(timeout=120)
    assert not errs, errs
    ta, tb = q.query_trace("qa"), q.query_trace("qb")
    assert ta is not None and tb is not None
    assert ta["duration_s"] > 0 and tb["duration_s"] > 0
    ca, cb = ta["counters"], tb["counters"]
    # non-interleaved attribution: the exchange ran in qa's scope ONLY
    assert ca.get("exchange_stages", 0) >= 1
    assert cb.get("exchange_stages", 0) == 0
    assert cb.get("merges", 0) == 0 and cb.get("map_range_batches",
                                               0) == 0
    # reconciliation: per-query sums == the global deltas for every
    # ADDITIVE key either scope touched (gauges tee as max, not sums;
    # task_* keys are per-task TaskMetrics attribution — memory-side
    # deltas teed at the engine task seam — with no ShuffleCounters
    # counterpart to reconcile against)
    g = shuffle_counters()
    gauges = {"heartbeat_failure_streak"}
    for k in sorted(set(ca) | set(cb)):
        if k in gauges or k.startswith("task_"):
            continue
        assert ca.get(k, 0) + cb.get(k, 0) == g[k], (
            k, ca.get(k, 0), cb.get(k, 0), g[k])
    # the task seam teed each query's OWN memory-side attribution
    # (every partition task waits on the device semaphore)
    assert ca.get("task_semaphore_wait_ns", 0) > 0
    assert cb.get("task_semaphore_wait_ns", 0) > 0
    # latency histogram saw both submissions
    assert HISTOGRAMS["serving_submit_s"].snapshot()["count"] == 2
    # spans attributed per query: qa's trace carries serving + engine
    names_a = {s["name"] for s in ta["spans"]}
    assert {"serving.submit", "serving.admission",
            "serving.run"} <= names_a


def test_tracing_disabled_is_free_and_traceless():
    from spark_rapids_tpu.serving import QueryQueue
    q = QueryQueue(lambda plan, ctx: ["ok"], conf={
        "spark.rapids.serving.cache.enabled": "false"})
    assert q.submit({"any": "plan"}, query_id="plain") == ["ok"]
    assert q.query_trace("plain") is None      # no trace was created


# -- cross-process round-trip (protocol-level, 2 ranks) -----------------------

class _TracedFakeExecutor:
    """FakeExecutor (tests/test_chaos.py lineage) whose task behavior
    builds telemetry through the REAL executor-side helpers: a
    QueryTrace from the SHIPPED task trace context, spans via obs.span,
    counter deltas through the blessed tee, shipped back in the
    task_result header like cluster/executor.py does."""

    def __init__(self, driver, name):
        from spark_rapids_tpu.shuffle.net import ShuffleExecutor
        self.driver = driver
        self.name = name
        self.node = ShuffleExecutor(
            name, driver_addr=driver.shuffle.server.addr)
        self.stop_ev = threading.Event()
        self.thread = threading.Thread(target=self._run, daemon=True)
        self.thread.start()

    def _behave(self, task):
        tctx = task.get("trace")
        assert tctx, "driver did not ship the trace context"
        assert tctx.get("max_spans", 0) > 0
        trace = obs.QueryTrace(tctx["qid"], enabled=True,
                               max_spans=tctx.get("max_spans"),
                               default_track="executor")
        with obs.trace_scope(trace):
            with obs.span("executor.task",
                          tags={"rank": task["rank"],
                                "attempt": task.get("attempt", 0),
                                "eid": self.name}):
                SHUFFLE_COUNTERS.add(blocks_fetched=2)
        tel = obs.collect_task_telemetry(trace)
        tel["metrics"] = [["FakeScan", 0, {"anRows": 10,
                                           "anTimeNs": 1000}]]
        rank, world = task["rank"], task["world"]
        rows = [(p, [[p, 10 * p]]) for p in range(4)
                if p % world == rank]
        return rows, tel

    def _run(self):
        from spark_rapids_tpu.shuffle.net import PeerClient, _request
        while not self.stop_ev.is_set():
            try:
                PeerClient(self.driver.shuffle.server.addr).heartbeat(
                    self.name)
                header, _payload = _request(
                    self.driver.rpc_addr,
                    {"op": "get_task", "executor_id": self.name},
                    retriable=False)
            except OSError:
                time.sleep(0.02)
                continue
            task = header.get("task")
            if task is None:
                time.sleep(0.02)
                continue
            rows, tel = self._behave(task)
            _request(self.driver.rpc_addr,
                     {"op": "task_result", "query_id": task["query_id"],
                      "executor_id": self.name,
                      "rank": task.get("rank"),
                      "attempt": task.get("attempt", 0),
                      "telemetry": tel},
                     pickle.dumps(rows))

    def close(self):
        self.stop_ev.set()
        self.thread.join(timeout=5)
        self.node.close()


def test_rank_filtered_scan_describe_is_rank_invariant():
    """REGRESSION (review): merge_metric_trees guards positional merges
    on (describe, depth) equality, so a rank-embedded describe string
    silently kept only rank 0's scan metrics — every other rank's tree
    row failed the guard.  _RankFilteredScan.describe() must therefore
    be IDENTICAL across ranks, and the merge must sum through it."""
    from spark_rapids_tpu.cluster.executor import _RankFilteredScan

    class _Leaf:
        children = ()

        def describe(self):
            return "FakeScan"
    d0 = _RankFilteredScan(_Leaf(), 0, 2).describe()
    d1 = _RankFilteredScan(_Leaf(), 1, 2).describe()
    assert d0 == d1
    merged = obs.merge_metric_trees([
        [(d0, 0, {"anRows": 7})],
        [(d1, 0, {"anRows": 13})]])
    assert merged == [(d0, 0, {"anRows": 20})]


def test_merge_remote_preserves_executor_thread_identity():
    """REGRESSION (review): record_span restamped the DRIVER's merging
    thread onto remote spans, collapsing a rank's concurrent spans onto
    one exporter tid (overlapping X events — invalid Chrome trace).
    The shipped executor-side thread name must survive the merge."""
    tr = obs.QueryTrace("q", enabled=True)
    tr.merge_remote({"spans": [
        {"name": "executor.task", "t0": 1.0, "t1": 2.0,
         "thread": "exec-worker-3"},
        {"name": "shuffle.pipeline.produce", "t0": 1.2, "t1": 1.8,
         "thread": "producer-1"}]}, rank=1, attempt=0, eid="w1")
    threads = {s["name"]: s["thread"] for s in tr.snapshot()["spans"]}
    assert threads["executor.task"] == "exec-worker-3"
    assert threads["shuffle.pipeline.produce"] == "producer-1"


def test_cluster_span_roundtrip_merges_with_rank_attempt_tags():
    """ACCEPTANCE (satellite): a 2-rank protocol-level cluster query
    returns executor task spans/metrics merged under the driver's trace
    with rank+attempt tags — query_report carries both ranks' records,
    the positionally-merged metric tree, and the merged counter
    attribution."""
    from spark_rapids_tpu.cluster.driver import TpuClusterDriver
    reset_shuffle_counters()
    driver = TpuClusterDriver(conf={"spark.rapids.trace.enabled": "true"},
                              heartbeat_timeout_s=5.0)
    w1 = w2 = None
    try:
        w1 = _TracedFakeExecutor(driver, "w1")
        w2 = _TracedFakeExecutor(driver, "w2")
        driver.wait_for_executors(2, timeout_s=30)
        rows = driver.submit({"fake": "plan"}, timeout_s=60)
        assert sorted(tuple(r) for r in rows) == [
            (p, 10 * p) for p in range(4)]
        rep = driver.query_report(1)
        assert rep is not None
        assert rep["world"] == 2 and rep["ranks"] == [0, 1]
        recs = {r["rank"]: r for r in rep["records"]}
        assert set(recs) == {0, 1}
        for rank, rec in recs.items():
            assert rec["attempt"] == 0
            assert rec["spans"] >= 1
            assert rec["counters"].get("blocks_fetched") == 2
        # metric trees sum positionally across the winning attempts
        assert rep["merged_metrics"] == [("FakeScan", 0,
                                          {"anRows": 20,
                                           "anTimeNs": 2000})]
        # merged counter attribution covers both ranks' deltas
        assert rep["counters"].get("blocks_fetched") == 4
        assert "FakeScan" in rep["text"] and "rows=20" in rep["text"]
    finally:
        for w in (w1, w2):
            if w is not None:
                w.close()
        driver.close()


def test_perfetto_export_has_serving_driver_and_rank_tracks(tmp_path):
    """ACCEPTANCE: one cluster query submitted through the SERVING
    layer exports a Perfetto/Chrome trace JSON that loads with serving,
    driver, and >=2 executor-rank tracks; rank-track span events carry
    rank+attempt tags."""
    from spark_rapids_tpu.cluster.driver import TpuClusterDriver
    from spark_rapids_tpu.serving import ClusterDriverRunner, QueryQueue
    tdir = str(tmp_path / "traces")
    driver = TpuClusterDriver(conf={}, heartbeat_timeout_s=5.0)
    w1 = w2 = None
    try:
        w1 = _TracedFakeExecutor(driver, "w1")
        w2 = _TracedFakeExecutor(driver, "w2")
        driver.wait_for_executors(2, timeout_s=30)
        q = QueryQueue(ClusterDriverRunner(driver, timeout_s=60), conf={
            "spark.rapids.serving.cache.enabled": "false",
            "spark.rapids.trace.enabled": "true",
            "spark.rapids.trace.dir": tdir})
        rows = q.submit({"fake": "plan"}, query_id="dash1")
        assert len(rows) == 4
        snap = q.query_trace("dash1")
        assert snap is not None
        path = snap.get("export_path")
        assert path and os.path.exists(path)
        doc = json.load(open(path))
        events = doc["traceEvents"]
        tracks = {e["args"]["name"]: e["pid"] for e in events
                  if e.get("name") == "process_name"}
        named = {t.split(" ")[0] for t in tracks}
        assert {"serving", "driver", "rank0", "rank1"} <= named, named
        # every track has at least one real span event
        by_pid = {}
        for e in events:
            if e.get("ph") == "X":
                by_pid.setdefault(e["pid"], []).append(e)
        for tname, pid in tracks.items():
            assert by_pid.get(pid), f"track {tname} has no span events"
        # rank spans carry the rank/attempt tags the driver merged
        rank_pids = {pid for t, pid in tracks.items()
                     if t.startswith("rank")}
        for pid in rank_pids:
            tagged = [e for e in by_pid[pid]
                      if e.get("args", {}).get("rank") is not None]
            assert tagged and all("attempt" in e["args"]
                                  for e in tagged)
        # the summary event carries the attributed counters
        summaries = [e for e in events if e.get("cat") == "summary"]
        assert summaries and \
            summaries[0]["args"]["counters"].get("blocks_fetched") == 4
    finally:
        for w in (w1, w2):
            if w is not None:
                w.close()
        driver.close()


def test_real_executor_traced_roundtrip(tmp_path):
    """The REAL executor path (executor_main worker, real engine, real
    group-by plan through a shuffle): the shipped trace context makes
    run_task record executor.task/plan/output spans and per-exec
    instrumented metrics, merged under the driver-owned trace, stored
    in query_report, and exported to a Perfetto JSON with driver +
    rank0 tracks."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.cluster.driver import TpuClusterDriver
    from spark_rapids_tpu.cluster.executor import executor_main
    rng = np.random.RandomState(7)
    path = os.path.join(str(tmp_path), "in.parquet")
    pq.write_table(pa.table({
        "k": rng.randint(0, 9, 400).astype(np.int64),
        "v": rng.randint(-50, 50, 400).astype(np.int64)}), path)
    tdir = str(tmp_path / "traces")
    driver = TpuClusterDriver(conf={
        "spark.sql.shuffle.partitions": "2",
        "spark.rapids.trace.enabled": "true",
        "spark.rapids.trace.dir": tdir})
    stop_ev = threading.Event()
    worker = threading.Thread(
        target=executor_main, args=(driver.rpc_addr,),
        kwargs={"executor_id": "ow1", "stop_check": stop_ev.is_set},
        daemon=True)
    worker.start()
    try:
        driver.wait_for_executors(1, timeout_s=60)
        s = TpuSession({})
        df = s.read_parquet(path).group_by("k").agg(
            Alias(sum_(col("v")), "sv"))
        rows = driver.submit(df.plan, timeout_s=120)
        oracle = sorted(
            tuple(r) for r in
            TpuSession({"spark.rapids.sql.enabled": "false"})
            .read_parquet(path).group_by("k").agg(
                Alias(sum_(col("v")), "sv")).collect())
        assert sorted(tuple(r) for r in rows) == oracle
        rep = driver.query_report(1)
        assert rep is not None and rep["ranks"] == [0]
        rec = rep["records"][0]
        assert rec["rank"] == 0 and rec["attempt"] == 0
        assert rec["spans"] >= 3     # task + plan + output at least
        # instrument_plan measured every node that ran: the merged tree
        # is non-empty and carries real row counts
        assert rep["merged_metrics"]
        assert any(snap.get("anRows", 0) > 0
                   for _d, _depth, snap in rep["merged_metrics"])
        assert "rows=" in rep["text"]
        # the exported timeline carries the real executor spans on the
        # rank0 track the driver merged them onto
        p = os.path.join(tdir, "query_1.trace.json")
        assert os.path.exists(p)
        events = json.load(open(p))["traceEvents"]
        tracks = {e["args"]["name"]: e["pid"] for e in events
                  if e.get("name") == "process_name"}
        named = {t.split(" ")[0] for t in tracks}
        assert {"driver", "rank0"} <= named, named
        rank_pid = next(pid for t, pid in tracks.items()
                        if t.startswith("rank0"))
        rank_names = {e["name"] for e in events
                      if e.get("ph") == "X" and e["pid"] == rank_pid}
        assert {"executor.task", "executor.plan",
                "executor.output"} <= rank_names, rank_names
    finally:
        stop_ev.set()
        worker.join(timeout=10)
        # executor_main installed its node process-wide (as
        # tests/test_cancel.py resets it)
        from spark_rapids_tpu.shuffle.transport import (
            set_process_shuffle_executor)
        set_process_shuffle_executor(None)
        driver.close()


def test_legacy_task_result_without_telemetry_merges_nothing():
    """A protocol peer that omits the telemetry header (every pre-PR-13
    harness) must still work — the report simply has no records."""
    from spark_rapids_tpu.cluster.driver import TpuClusterDriver
    from tests.test_chaos import FakeExecutor, _normal
    driver = TpuClusterDriver(conf={"spark.rapids.trace.enabled": "true"},
                              heartbeat_timeout_s=5.0)
    w1 = None
    try:
        w1 = FakeExecutor(driver, "w1", _normal)
        driver.wait_for_executors(1, timeout_s=30)
        rows = driver.submit({"fake": "plan"}, timeout_s=60)
        assert len(rows) == 4
        rep = driver.query_report(1)
        assert rep is not None
        assert rep["records"] == [] and rep["merged_metrics"] == []
    finally:
        if w1 is not None:
            w1.close()
        driver.close()


# -- exporter unit ------------------------------------------------------------

def test_trace_export_snapshot_shape_and_cli(tmp_path):
    from tools.trace_export import export_trace, trace_events
    tr = obs.QueryTrace("unit")
    with obs.trace_scope(tr):
        with obs.span("serving.submit", track="serving"):
            pass
    tr.merge_remote({"spans": [{"name": "executor.task", "t0": 1.0,
                                "t1": 2.0}],
                     "counters": {"blocks_fetched": 1}},
                    rank=0, attempt=1, eid="w9")
    tr.finish()
    events = trace_events(tr)
    xs = [e for e in events if e.get("ph") == "X"]
    assert any(e["name"] == "executor.task" and
               e["args"]["rank"] == 0 and e["args"]["attempt"] == 1
               for e in xs)
    p = export_trace(tr.snapshot(), str(tmp_path / "t.trace.json"))
    doc = json.load(open(p))
    assert doc["traceEvents"]
    # round-trips through the CLI path (snapshot json -> trace json)
    sp = tmp_path / "snap.json"
    sp.write_text(json.dumps(tr.snapshot()))
    from tools.trace_export import main as export_main
    out = tmp_path / "cli.trace.json"
    assert export_main([str(sp), str(out)]) == 0
    assert json.load(open(out))["traceEvents"]
