"""Benchmark harness: TPC-H/TPC-DS queries through the full engine path.

    python bench.py                 q6 q1 q3 q25 q72, one JSON line
    python bench.py --concurrent    serving layer, concurrent vs serialized
    python bench.py --load          open-loop load against a mini cluster

Everything runs in THIS process on the device JAX finds; the result names
it (platform, device_kind, device count).  There is no probe, no child
process and no CPU re-run: a query that fails, or whose rows differ from
the CPU oracle engine's, raises and the exit code is non-zero.  A run in
the CPU sandbox therefore says "platform": "cpu" and its seconds are
host-backend seconds, never a device number.  Cells, medians and bounds are
ROADMAP S1's; this file only measures what it is asked, once.

Per query: one warm-up collect() (compile + correctness), then one timed
collect() under a QueryTrace; ``engine_s`` is the host wall-clock of that
timed collect(), ``oracle_s`` the CPU oracle engine's on the same data.

With SPARK_RAPIDS_TPU_BENCH_PROFILE=<dir> one EXTRA run per query is wrapped
in jax.profiler.trace and digested (tools/profile_digest.py).

Each query's result carries ``by_program``: launches per program NAME
(plan/execs/base ``launch_stats``), the name the program has in the device
trace, so the trace's ``XLA Modules`` line says what each one cost.
"""
from __future__ import annotations

import json
import os
import sys
import time

# 1M-row batches: with stage fusion one batch is one program launch, so
# batch size directly divides the per-query launch count.  Override with
# SPARK_RAPIDS_TPU_BENCH_BATCH_ROWS.
BATCH_ROWS = int(os.environ.get("SPARK_RAPIDS_TPU_BENCH_BATCH_ROWS",
                                1 << 20))
N_ROWS = int(os.environ.get("SPARK_RAPIDS_TPU_BENCH_ROWS", 2_000_000))
QUERIES = ("q6", "q1", "q3", "q25", "q72")
#: ceiling on one concurrent-bench future (a hang must end the run)
CONCURRENT_TIMEOUT_S = 600


def _device() -> dict:
    """The device this process runs on, as JAX reports it."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _batch_bytes(batches) -> int:
    """Device bytes of the input batch pytrees (what the kernels read)."""
    import jax
    return int(sum(getattr(x, "nbytes", 0)
                   for b in batches
                   for x in jax.tree_util.tree_leaves(b)))


def _build_query(qname: str, n_rows: int):
    """Build ONE query's (runner, input_bytes) — datasets generated lazily
    per query so a child process never pays for data it won't run."""
    from spark_rapids_tpu.testing import tpcds, tpch
    if qname in ("q6", "q1"):
        batches = tpch.gen_lineitem(n_rows, batch_rows=BATCH_ROWS)
        qfn = {"q6": tpch.q6, "q1": tpch.q1}[qname]

        def run(sess):
            df = qfn(sess.create_dataframe(list(batches), num_partitions=2))
            return df.collect()
        return run, _batch_bytes(batches)
    if qname == "q3":
        fact = tpcds.gen_store_sales(n_rows, batch_rows=BATCH_ROWS)
        date_dim = tpcds.gen_date_dim()
        item = tpcds.gen_item()

        def _q3(sess):
            df = tpcds.q3(
                sess.create_dataframe(list(fact), num_partitions=2),
                sess.create_dataframe([date_dim], num_partitions=1),
                sess.create_dataframe([item], num_partitions=1))
            return df.collect()
        return _q3, _batch_bytes(fact + [date_dim, item])
    if qname == "q25":
        # 3-fact chain (VERDICT r4 next #2: join-heavy breadth in bench):
        # returns reference real sale tickets, catalog purchases correlate
        # on (customer, item) — referential integrity like the real spec
        ss = tpcds.gen_store_sales(n_rows, batch_rows=BATCH_ROWS)
        sr = tpcds.gen_store_returns(n_rows // 4, sales=ss,
                                     match_frac=0.9,
                                     batch_rows=BATCH_ROWS)
        pool = tpcds.host_pool(sr, ["sr_customer_sk", "sr_item_sk"])
        cs = tpcds.gen_catalog_sales(n_rows // 2, pair_pool=pool,
                                     match_frac=0.7,
                                     batch_rows=BATCH_ROWS)
        dims = (tpcds.gen_date_dim(), tpcds.gen_store(), tpcds.gen_item())

        def _q25(sess):
            df = tpcds.q25(
                sess.create_dataframe(list(ss), num_partitions=2),
                sess.create_dataframe(list(sr), num_partitions=2),
                sess.create_dataframe(list(cs), num_partitions=2),
                *[sess.create_dataframe([d], num_partitions=1)
                  for d in dims])
            return df.collect()
        return _q25, _batch_bytes(ss + sr + cs + list(dims))
    assert qname == "q72", qname
    # inventory stress: conditional (non-equi) join against the biggest
    # fact + two left joins, demographic filters, tri-date-dim.  Sized at
    # n/4 facts: the ORACLE's conditional-join pass is the bench's wall
    # (its cost grows with candidate pairs, and the cpu fallback child
    # must finish inside its timeout)
    cs = tpcds.gen_catalog_sales(n_rows // 8, batch_rows=BATCH_ROWS)
    opool = tpcds.host_pool(cs, ["cs_item_sk", "cs_order_number"])
    cr = tpcds.gen_catalog_returns(n_rows // 32, order_pool=opool,
                                   match_frac=0.6, batch_rows=BATCH_ROWS)
    inv = tpcds.gen_inventory(n_rows // 4, batch_rows=BATCH_ROWS)
    dims = (tpcds.gen_warehouse(), tpcds.gen_item(),
            tpcds.gen_customer_demographics(),
            tpcds.gen_household_demographics(), tpcds.gen_date_dim(),
            tpcds.gen_promotion())

    def _q72(sess):
        wh, item, cd, hd, dd, promo = [
            sess.create_dataframe([d], num_partitions=1) for d in dims]
        df = tpcds.q72(
            sess.create_dataframe(list(cs), num_partitions=2),
            sess.create_dataframe(list(inv), num_partitions=2),
            wh, item, cd, hd, dd, promo,
            sess.create_dataframe(list(cr), num_partitions=1))
        return df.collect()
    return _q72, _batch_bytes(cs + cr + inv + list(dims))


def _check_rows(name, tpu_rows, cpu_rows):
    """Type-aware cross-check mirroring the differential suite: exact for
    non-floats, relative tolerance only for float aggregates."""
    assert len(tpu_rows) == len(cpu_rows), (name, len(tpu_rows), len(cpu_rows))
    for tr, cr in zip(sorted(map(tuple, tpu_rows)),
                      sorted(map(tuple, cpu_rows))):
        for a, b in zip(tr, cr):
            if isinstance(a, float):
                assert b == b and abs(a - b) <= 1e-6 * max(1.0, abs(b)), \
                    (name, tr, cr)
            else:
                assert a == b, (name, tr, cr)


def _run_query(qname: str, n_rows: int) -> dict:
    """Warm up, time, cross-check against the CPU oracle engine; raises on
    any failure or row mismatch."""
    import jax

    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.plan.execs.base import (
        launch_stats, reset_launch_stats)
    from spark_rapids_tpu.cluster.stats import (
        local_shuffle_counters, reset_local_shuffle_counters)
    run, input_bytes = _build_query(qname, n_rows)
    tpu_sess = TpuSession({"spark.rapids.sql.enabled": "true"})
    cpu_sess = TpuSession({"spark.rapids.sql.enabled": "false"})

    tpu_rows = run(tpu_sess)        # warmup: compile + correctness

    reset_launch_stats()
    reset_local_shuffle_counters()
    # the timed run executes under a QueryTrace ambient (utils/obs.py):
    # the artifact then carries the per-query ATTRIBUTED counter scope
    # (exactly this query's deltas — meaningful even when other work
    # shares the process) beside the global snapshot, plus a Perfetto
    # trace export of the run's spans.  The tee is a dict update per
    # counter add — well under measurement noise per query.
    from spark_rapids_tpu.utils.obs import (
        QueryTrace, export_trace_file, trace_scope)
    # resource-plane timeline (utils/telemetry.py): the ring is reset so
    # the timed run's samples alone feed the per-query timeline summary
    # (peak arena/pinned/queue-depth + total spill) in the artifact —
    # perf numbers carry their resource context
    from spark_rapids_tpu.utils.telemetry import TELEMETRY
    TELEMETRY.reset_ring()
    TELEMETRY.sample()      # baseline tick: spill deltas measure from 0
    trace = QueryTrace(f"bench_{qname}", enabled=True)
    t0 = time.perf_counter()
    with trace_scope(trace):
        tpu_rows = run(tpu_sess)
    engine_time = time.perf_counter() - t0
    trace.finish()
    TELEMETRY.sample()      # >=1 sample even under a sub-interval run
    timeline = TELEMETRY.timeline_summary()
    stats = launch_stats()          # exact program-dispatch counts
    shuffle = local_shuffle_counters()  # data-plane behavior per query
    trace_counters = {k: v for k, v in trace.counters_snapshot().items()
                      if v}
    # the trace FILE is opt-in like the other bench_profile artifacts:
    # a plain bench run must not litter the cwd — export only into an
    # explicit dir
    trace_dir = os.environ.get("SPARK_RAPIDS_TPU_BENCH_TRACE_DIR")
    trace_export = export_trace_file(trace, trace_dir) if trace_dir else None

    util = None
    profile_dir = os.environ.get("SPARK_RAPIDS_TPU_BENCH_PROFILE")
    if profile_dir:
        # profile a SEPARATE run so trace overhead never leaks into the
        # timed measurement above; digest busy/idle + HBM floor from it
        with jax.profiler.trace(profile_dir):
            run(tpu_sess)
        from tools.profile_digest import digest
        util = digest(profile_dir, input_bytes=input_bytes,
                      device_kind=jax.devices()[0].device_kind)

    # the CPU ORACLE pass rides the differential-oracle result cache
    # (testing/oracle_cache.py): it is deterministic for (query, rows,
    # batch) and — on q72 — the bench wall (its conditional-join pass
    # dwarfs OUR execution).  The measured oracle wall is cached WITH the
    # rows so a cache hit still reports the honest first-run speedup
    # instead of the cache-read time.  TPU_ORACLE_CACHE=0 disables.
    from spark_rapids_tpu.testing import tpcds as _tpcds, tpch as _tpch
    from spark_rapids_tpu.testing.oracle_cache import (
        get_or_compute, source_fingerprint)

    def _oracle():
        t0 = time.perf_counter()
        rows = run(cpu_sess)
        return {"rows": rows, "oracle_s": time.perf_counter() - t0}

    payload = get_or_compute(
        ("bench", qname, n_rows, BATCH_ROWS,
         source_fingerprint(_tpcds, _tpch)), _oracle)
    cpu_rows, cpu_time = payload["rows"], payload["oracle_s"]
    _check_rows(qname, tpu_rows, cpu_rows)

    return {
        "query": qname, "device": _device(),
        "rows_per_sec": round(n_rows / engine_time),
        "engine_s": round(engine_time, 4), "oracle_s": round(cpu_time, 4),
        "launches": stats["launches"], "programs": stats["programs"],
        "by_program": stats["by_program"],
        "launches_per_stage": round(
            stats["launches"] / max(shuffle.get("exchange_stages", 0), 1),
            1),
        "shuffle": shuffle,
        "timeline": timeline,
        "trace_counters": trace_counters,
        **({"trace_export": trace_export} if trace_export else {}),
        "input_bytes": input_bytes,
        **({"util": util} if util else {}),
        **({"profile_dir": profile_dir} if profile_dir else {}),
    }


# -- concurrent serving bench (bench.py --concurrent) ------------------------
#
# Measures the serving layer (serving/admission.py) under N parallel
# queries mixed across tenants: aggregate rows/s of concurrent submission
# vs the SERIALIZED baseline over the same query mix, plus per-tenant
# latency percentiles and the serving counters.  The comparison is
# relative: same process, same device, warm programs for both passes.

CONCURRENT_QUERIES = int(os.environ.get(
    "SPARK_RAPIDS_TPU_BENCH_CONCURRENT_QUERIES", 8))
CONCURRENT_ROWS = int(os.environ.get(
    "SPARK_RAPIDS_TPU_BENCH_CONCURRENT_ROWS", 1 << 19))


def _percentiles(xs):
    xs = sorted(xs)

    def pick(q):
        return round(xs[min(int(len(xs) * q), len(xs) - 1)], 4)
    return {"p50": pick(0.50), "p90": pick(0.90), "p99": pick(0.99)}


def _concurrent_bench() -> None:
    from spark_rapids_tpu.serving import LocalSessionRunner, QueryQueue
    from spark_rapids_tpu.cluster.stats import (
        local_shuffle_counters, reset_local_shuffle_counters)
    from spark_rapids_tpu.testing import tpch

    n_rows = CONCURRENT_ROWS
    batches = tpch.gen_lineitem(n_rows, batch_rows=min(BATCH_ROWS, n_rows))
    runner = LocalSessionRunner({})
    session = runner.session

    def make_plan(qname):
        df = session.create_dataframe(list(batches), num_partitions=2)
        return {"q6": tpch.q6, "q1": tpch.q1}[qname](df).plan

    # the MIX: alternating q6/q1 across two tenants
    mix = [("q6" if i % 2 == 0 else "q1",
            "tenant%d" % (i % 2)) for i in range(CONCURRENT_QUERIES)]
    plans = [(make_plan(q), q, t) for q, t in mix]

    ctxless = QueryQueue(runner, conf={
        "spark.rapids.serving.cache.enabled": "false"})
    # warm the compile cache so both timed passes run warm (one plan of
    # each shape)
    ctxless.submit(plans[0][0], tenant="warm")
    ctxless.submit(plans[1][0], tenant="warm")

    # serialized baseline: the same mix, one query at a time
    t0 = time.perf_counter()
    for plan, _q, tenant in plans:
        ctxless.submit(plan, tenant=tenant)
    serialized_s = time.perf_counter() - t0

    # concurrent: all queries submitted at once through admission
    import threading
    from concurrent.futures import ThreadPoolExecutor
    from spark_rapids_tpu.utils.telemetry import TELEMETRY
    reset_local_shuffle_counters()
    TELEMETRY.reset_ring()
    TELEMETRY.sample()      # baseline tick: spill deltas measure from 0
    lat = {}
    lat_lock = threading.Lock()

    def timed_submit(plan, tenant):
        s = time.perf_counter()
        rows = ctxless.submit(plan, tenant=tenant)
        with lat_lock:
            lat.setdefault(tenant, []).append(time.perf_counter() - s)
        return rows

    t0 = time.perf_counter()
    with ThreadPoolExecutor(max_workers=len(plans),
                            thread_name_prefix="bench-serving") as pool:
        futs = [pool.submit(timed_submit, plan, tenant)
                for plan, _q, tenant in plans]
        for f in futs:
            f.result(timeout=CONCURRENT_TIMEOUT_S)
    concurrent_s = time.perf_counter() - t0
    TELEMETRY.sample()      # >=1 sample even under a sub-interval run
    timeline = TELEMETRY.timeline_summary()
    counters = local_shuffle_counters()
    from spark_rapids_tpu.cluster.stats import local_histograms
    hists = local_histograms()
    total_rows = n_rows * len(plans)
    out = {
        "metric": "serving_concurrent_rows_per_sec",
        "value": round(total_rows / concurrent_s),
        "unit": "rows/s",
        "serialized_rows_per_sec": round(total_rows / serialized_s),
        "speedup_vs_serialized": round(serialized_s / concurrent_s, 3),
        "device": _device(),
        "n_queries": len(plans),
        "rows_per_query": n_rows,
        "mix": sorted({q for _p, q, _t in plans}),
        "per_tenant_latency_s": {t: _percentiles(v)
                                 for t, v in sorted(lat.items())},
        # the product-side latency histogram (shuffle/stats.py), as a
        # serving process would report it: submit->done p50/p90/p99 over
        # the concurrent pass, plus the fetch-wait/stage-drain tails
        "latency_histogram": hists["serving_submit_s"],
        "fetch_wait_histogram": hists["fetch_wait_s"],
        # the concurrent pass's resource context (peak arena/pinned/
        # queue depth from the telemetry ring — the continuous plane)
        "timeline": timeline,
        "serving_counters": {k: v for k, v in counters.items()
                             if k.startswith(("queries_", "cache_",
                                              "tenant_", "budget_"))},
    }
    print(json.dumps(out))


# -- open-loop load bench (bench.py --load) -----------------------------------
#
# Drives a real in-process mini cluster (TpuClusterDriver + executor
# threads behind QueryQueue(ClusterDriverRunner)) with the open-loop
# Poisson generator (tools/loadgen.py), overload protections and the
# autoscaler armed, on the device JAX finds.  The artifact is the serving-SLO story: offered vs
# achieved rate, ok-latency p50/p99, the outcome taxonomy, and the
# autoscale/shed/ratelimit/breaker event timeline from the telemetry
# ring — written to BENCH_load_<ts>.json AND printed as the JSON line.

LOAD_RATE = float(os.environ.get("SPARK_RAPIDS_TPU_BENCH_LOAD_RATE", 12.0))
LOAD_DURATION_S = float(os.environ.get(
    "SPARK_RAPIDS_TPU_BENCH_LOAD_DURATION", 15.0))
LOAD_ROWS = int(os.environ.get("SPARK_RAPIDS_TPU_BENCH_LOAD_ROWS", 1 << 14))

#: flight-recorder kinds that narrate the load story (the elasticity +
#: overload decisions; see docs/fault_tolerance.md)
LOAD_EVENT_KINDS = ("autoscale", "shed", "ratelimit", "breaker_trip",
                    "breaker_fast_fail", "executor_join",
                    "executor_leave", "executor_loss")


def _load_bench() -> None:
    import threading

    from tools import loadgen
    from spark_rapids_tpu.cluster.autoscaler import attach_autoscaler
    from spark_rapids_tpu.cluster.driver import TpuClusterDriver
    from spark_rapids_tpu.cluster.executor import executor_main
    from spark_rapids_tpu.cluster.stats import (
        local_shuffle_counters, reset_local_shuffle_counters)
    from spark_rapids_tpu.serving import ClusterDriverRunner, QueryQueue
    from spark_rapids_tpu.testing import tpch
    from spark_rapids_tpu.utils.telemetry import TELEMETRY

    conf = {
        # cache off: an open-loop benchmark of IDENTICAL plans would
        # otherwise measure the cache, not the serving tier
        "spark.rapids.serving.cache.enabled": "false",
        "spark.rapids.serving.maxConcurrent": "2",
        "spark.rapids.serving.overload.enabled": "true",
        "spark.rapids.serving.overload.sloP99Seconds": "2.0",
        "spark.rapids.serving.overload.ratelimitQps": "8.0",
        "spark.rapids.autoscale.enabled": "true",
        "spark.rapids.autoscale.maxExecutors": "4",
        "spark.rapids.autoscale.queueDepthHigh": "3",
        "spark.rapids.autoscale.upCooldownSeconds": "2.0",
        "spark.rapids.shuffle.replication.factor": "2",
    }
    stop = threading.Event()
    driver = TpuClusterDriver(conf=conf, heartbeat_timeout_s=10.0)
    seeds = []
    for i in range(2):
        t = threading.Thread(
            target=executor_main, args=(driver.rpc_addr,),
            kwargs={"executor_id": f"seed-{i}",
                    "stop_check": stop.is_set, "poll_s": 0.05},
            daemon=True, name=f"bench-exec-{i}")
        t.start()
        seeds.append(t)
    driver.wait_for_executors(2, timeout_s=30)
    TELEMETRY.configure(True, interval_ms=100, ring_seconds=120)
    TELEMETRY.reset_events()
    reset_local_shuffle_counters()

    q = QueryQueue(ClusterDriverRunner(driver, timeout_s=60), conf=conf)
    scaler = attach_autoscaler(driver, conf=conf, stop_event=stop)
    batches = list(tpch.gen_lineitem(LOAD_ROWS,
                                     batch_rows=max(LOAD_ROWS // 2, 1)))
    from spark_rapids_tpu.expressions import col, lit
    from spark_rapids_tpu.serving import LocalSessionRunner
    session = LocalSessionRunner({}).session

    def submit(i, tenant, priority):
        # map-only shape (filter + projection): executor ranks split the
        # scan and return rows with NO exchange stage — the launched
        # ranks here are threads of ONE process, and the process-wide
        # shuffle transport cannot serve two exchanging ranks at once
        # (real multi-rank shuffles run process-split: tests/
        # test_cluster.py).  The load story is the serving control
        # plane, which this shape exercises fully.
        df = session.create_dataframe(list(batches), num_partitions=2)
        plan = df.filter(col("l_linenumber") < lit(5)).select(
            "l_orderkey", "l_linenumber").plan
        return q.submit(plan, tenant=tenant, priority=priority,
                        timeout_s=45.0)

    t0 = time.time()
    summary = loadgen.run_load(
        submit, LOAD_RATE, LOAD_DURATION_S,
        seed=int(os.environ.get("SPARK_RAPIDS_TPU_BENCH_LOAD_SEED", 0)),
        mix=[("dash", 0), ("etl", 2), ("adhoc", 3)])
    TELEMETRY.sample()
    timeline = [e for e in TELEMETRY.events()
                if e.get("kind") in LOAD_EVENT_KINDS]
    counters = local_shuffle_counters()
    rows_ok = LOAD_ROWS * summary["outcomes"]["ok"]
    out = {
        "metric": "serving_load_rows_per_sec",
        "value": round(rows_ok / summary["wall_s"]) if summary["wall_s"]
        else 0,
        "unit": "rows/s",
        "device": _device(),
        "offered_qps": summary["offered_qps"],
        "achieved_qps": summary["achieved_qps"],
        "rows_per_query": LOAD_ROWS,
        "ok_latency_s": summary["ok_latency_s"],
        "outcomes": summary["outcomes"],
        "per_tenant": summary["per_tenant"],
        "elasticity_counters": {
            k: counters[k] for k in
            ("autoscale_up", "autoscale_down", "queries_shed",
             "ratelimit_rejections", "breaker_trips",
             "breaker_fast_fails", "scoped_resubmits")},
        "event_timeline": [
            {**{k: v for k, v in e.items() if k != "t"},
             "t_s": round(e["t"] - t0, 3)} for e in timeline],
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        f"BENCH_load_{int(t0)}.json")
    with open(path, "w") as f:
        json.dump(dict(out, records=summary["records"]), f, indent=1)
    out["artifact"] = path
    q.close()
    if scaler is not None:
        scaler.stop()
    stop.set()
    driver.close()
    print(json.dumps(out))


def main() -> None:
    # a failing query raises out of here: non-zero exit, no result line
    results = {q: _run_query(q, N_ROWS) for q in QUERIES}
    print(json.dumps({"unit": "rows/s", "device": _device(),
                      "n_rows": N_ROWS, "queries": results}))


if __name__ == "__main__":
    if "--load" in sys.argv:
        _load_bench()
    elif "--concurrent" in sys.argv:
        _concurrent_bench()
    else:
        main()
