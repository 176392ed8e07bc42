"""Chip smoke: the engine's main path, once, on the accelerator JAX finds.

    python chip_smoke.py              one chip, 16,777,216 fact rows
    python chip_smoke.py --chips 4    only the four-chip ICI exchange path

One process, no children.  Phases, one JSON line each on stdout:

  device   what JAX found, its HBM limit, the arena budget derived from it
  data     lineitem / store_sales / date_dim / item made from --seed with
           the repo's generators and written to Parquet in a scratch dir
  q6 q1    TpuSession.read_parquet -> planner -> fused execs -> exchange ->
  (q3)     collect(), default conf, twice each (cold, warm); rows compared
           with a plain pandas computation over the same files (and, at
           rehearsal sizes, with the repo's CPU oracle engine too).  q3 is
           behind --queries: see DEFAULT_QUERIES
  serve    serving.QueryQueue over LocalSessionRunner, eight async
           submissions of those queries' plans from two tenants
  ici      (--chips 4 only) q3 as one SPMD program over a four-device mesh
           with the shuffle as an in-program all-to-all, on the same files

The last stdout line is the result the driver reads:
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}} — and
it is printed only after every phase passed on an accelerator.  Any failed
phase raises (non-zero exit, no result line).  Without an accelerator the
script never exits 0: at the default size it refuses at once, and with
--rows it runs every phase as a CPU REHEARSAL of the control flow, says
platform "cpu" on every line, and still exits non-zero with no result line.

Seconds printed here are one run on one machine, not a benchmark.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

DEFAULT_ROWS = 1 << 24          # 16,777,216 fact rows: 16 scan batches
BATCH_ROWS = 1 << 20            # the engine's default batch capacity
# q3 (two dimension joins, two-phase aggregate, global sort) runs with
# --queries q6,q1,q3 but is not in the default set: compiled cold for the
# v5e its three programs took 2,304 s in rehearsal (c) and 892 s on the
# chip's host, its cold collect() 978 s of the script's 1,200 s, and its
# serve phase did not finish (CHANGES.md, PR 22).
DEFAULT_QUERIES = ("q6", "q1")
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


class CompileWatch:
    """JAX's own compile events (jax.monitoring): every XLA backend compile
    request of the process, and how many the persistent cache answered."""

    def __init__(self):
        import jax.monitoring as M
        self.requests = 0
        self.seconds = 0.0
        self.cache_hits = 0
        M.register_event_duration_secs_listener(self._on_duration)
        M.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **kw):
        if event == BACKEND_COMPILE_EVENT:
            self.requests += 1
            self.seconds += secs

    def _on_event(self, event, **kw):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def snapshot(self):
        return (self.requests, self.seconds, self.cache_hits)


def cache_entries() -> dict:
    import jax
    d = jax.config.jax_compilation_cache_dir
    n = 0
    if d and os.path.isdir(d):
        n = sum(1 for f in os.listdir(d) if not f.endswith("-atime"))
    return {"dir": d, "entries": n}


# -- data ---------------------------------------------------------------------

def write_tables(root: str, rows: int, seed: int, queries) -> dict:
    """Generate from the seed the tables ``queries`` read and write them as
    Parquet; two files per fact table (two scan partitions), row groups of
    BATCH_ROWS so every scan batch arrives at one capacity."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar.arrow import (
        batch_to_arrow, decimal_array_from_unscaled, sql_type_to_arrow)
    from spark_rapids_tpu.testing import tpcds, tpch

    chunk = min(BATCH_ROWS, rows)

    def column(vals, dt, valid=None):
        if isinstance(dt, T.DecimalType):
            return decimal_array_from_unscaled(vals, dt.precision, dt.scale,
                                               valid)
        mask = None if valid is None else ~valid
        if isinstance(dt, T.DateType):
            return pa.array(vals, type=pa.int32(), mask=mask).cast(
                sql_type_to_arrow(dt))
        return pa.array(vals, type=sql_type_to_arrow(dt), mask=mask)

    def write_fact(name, schema, chunks):
        chunks = list(enumerate(chunks))
        half = (len(chunks) + 1) // 2
        paths, nbytes = [], 0
        for part, sel in enumerate((chunks[:half], chunks[half:])):
            if not sel:
                continue
            path = os.path.join(root, f"{name}-{part}.parquet")
            writer = None
            for _, (data, validity) in sel:
                table = pa.table(
                    {n: column(data[n], dt, validity.get(n))
                     for n, dt in zip(schema.names, schema.dtypes)})
                if writer is None:
                    writer = pq.ParquetWriter(path, table.schema)
                writer.write_table(table, row_group_size=chunk)
            writer.close()
            paths.append(path)
            nbytes += os.path.getsize(path)
        return paths, nbytes

    def write_dim(name, batch):
        path = os.path.join(root, f"{name}.parquet")
        pq.write_table(batch_to_arrow(batch), path)
        return path

    files = {"parquet_bytes": {}}
    if {"q6", "q1"} & set(queries):
        files["lineitem"], files["parquet_bytes"]["lineitem"] = write_fact(
            "lineitem", tpch.LINEITEM_SCHEMA,
            ((cols, {}) for cols in tpch.lineitem_host_chunks(
                rows, seed=seed, batch_rows=chunk)))
    if "q3" in queries:
        files["store_sales"], files["parquet_bytes"]["store_sales"] = \
            write_fact("store_sales", tpcds.STORE_SALES_SCHEMA,
                       tpcds.store_sales_host_chunks(
                           rows, seed=seed + 1, batch_rows=chunk))
        files["date_dim"] = write_dim("date_dim", tpcds.gen_date_dim())
        files["item"] = write_dim("item", tpcds.gen_item(seed=seed + 2))
    return files


# -- the three queries, on the engine and in plain pandas ---------------------

def build_query(sess, qname: str, files: dict):
    from spark_rapids_tpu.testing import tpcds, tpch
    if qname == "q6":
        return tpch.q6(sess.read_parquet(*files["lineitem"]))
    if qname == "q1":
        return tpch.q1(sess.read_parquet(*files["lineitem"]))
    assert qname == "q3", qname
    return tpcds.q3(sess.read_parquet(*files["store_sales"]),
                    sess.read_parquet(files["date_dim"]),
                    sess.read_parquet(files["item"]))


def pandas_reference(qname: str, files: dict) -> list:
    """The same query semantics written directly against the files with
    pyarrow + pandas, independent of the engine and of its CPU oracle."""
    import datetime

    import pandas as pd
    import pyarrow as pa
    import pyarrow.parquet as pq

    def read(paths, columns):
        paths = [paths] if isinstance(paths, str) else paths
        t = pa.concat_tables(pq.read_table(p, columns=columns)
                             for p in paths)
        # decimal(12,2) -> double and date -> days since epoch IN ARROW:
        # to_pandas() would box every value in a Python object
        cols = {}
        for name, col in zip(t.column_names, t.columns):
            if pa.types.is_decimal(col.type):
                col = col.cast(pa.float64())
            elif pa.types.is_date32(col.type):
                col = col.cast(pa.int32())
            cols[name] = col
        return pa.table(cols).to_pandas()

    def days(y, m, d):
        return (datetime.date(y, m, d) - datetime.date(1970, 1, 1)).days

    if qname == "q6":
        li = read(files["lineitem"], ["l_quantity", "l_extendedprice",
                                      "l_discount", "l_shipdate"])
        sel = li[(li["l_shipdate"] >= days(1994, 1, 1))
                 & (li["l_shipdate"] < days(1995, 1, 1))
                 & (li["l_discount"] >= 0.05) & (li["l_discount"] <= 0.07)
                 & (li["l_quantity"] < 24)]
        if not len(sel):
            return [(None,)]
        return [(float((sel["l_extendedprice"] * sel["l_discount"]).sum()),)]
    if qname == "q1":
        li = read(files["lineitem"], ["l_linenumber", "l_quantity",
                                      "l_extendedprice", "l_discount",
                                      "l_tax", "l_shipdate"])
        sel = li[li["l_shipdate"] <= days(1998, 9, 2)].copy()
        sel["disc_price"] = sel["l_extendedprice"] * (1.0 - sel["l_discount"])
        sel["charge"] = sel["disc_price"] * (1.0 + sel["l_tax"])
        g = sel.groupby("l_linenumber", sort=True)
        out = pd.DataFrame({
            "sum_qty": g["l_quantity"].sum(),
            "sum_base_price": g["l_extendedprice"].sum(),
            "sum_disc_price": g["disc_price"].sum(),
            "sum_charge": g["charge"].sum(),
            "avg_qty": g["l_quantity"].mean(),
            "avg_price": g["l_extendedprice"].mean(),
            "avg_disc": g["l_discount"].mean(),
            "count_order": g.size()}).reset_index()
        return [(int(r[0]), *map(float, r[1:8]), int(r[8]))
                for r in out.itertuples(index=False)]
    assert qname == "q3", qname
    ss = read(files["store_sales"],
              ["ss_sold_date_sk", "ss_item_sk", "ss_ext_sales_price"])
    dd = read(files["date_dim"], ["d_date_sk", "d_year", "d_moy"])
    it = read(files["item"],
              ["i_item_sk", "i_brand_id", "i_brand", "i_manufact_id"])
    j = (ss.merge(dd[dd["d_moy"] == 11], left_on="ss_sold_date_sk",
                  right_on="d_date_sk")
         .merge(it[it["i_manufact_id"] == 28], left_on="ss_item_sk",
                right_on="i_item_sk"))
    out = (j.groupby(["d_year", "i_brand_id", "i_brand"], sort=False)
           ["ss_ext_sales_price"].sum().reset_index(name="sum_agg")
           .sort_values(["d_year", "sum_agg", "i_brand_id"],
                        ascending=[True, False, True]))
    return [(int(r[0]), int(r[1]), str(r[2]), float(r[3]))
            for r in out.itertuples(index=False)]


def check_rows(name: str, got: list, want: list) -> None:
    """bench.py's rule: same multiset of rows, exact except float
    aggregates at 1e-6 relative."""
    from bench import _check_rows
    _check_rows(name, got, want)


def check_q3_order(rows: list) -> None:
    """ORDER BY d_year, sum_agg DESC, i_brand_id — on the engine's own
    values, so float tolerance cannot reorder the comparison."""
    keys = [(r[0], -r[3], r[1]) for r in rows]
    assert keys == sorted(keys), "q3 result is not in ORDER BY order"


def fallback_nodes(exec_plan) -> list:
    """Every CPU-fallback island or CPU expression bridge in a physical
    plan, found by walking exec nodes (fused chains included) and every
    expression they hold."""
    from spark_rapids_tpu.expressions.bridge import CpuBridgeExpression
    from spark_rapids_tpu.expressions.core import Expression
    from spark_rapids_tpu.expressions.parity import _BridgeExpr
    from spark_rapids_tpu.plan.execs.base import TpuExec
    from spark_rapids_tpu.plan.execs.fallback import TpuCpuFallbackExec

    found, seen = [], set()

    def walk(x):
        if id(x) in seen:
            return
        if isinstance(x, TpuExec):
            seen.add(id(x))
            if isinstance(x, TpuCpuFallbackExec):
                found.append(x.node_name())
            for v in vars(x).values():
                walk(v)
        elif isinstance(x, Expression):
            seen.add(id(x))
            if isinstance(x, (CpuBridgeExpression, _BridgeExpr)):
                found.append(type(x).__name__)
            for c in x.children:
                walk(c)
            for v in vars(x).values():
                walk(v)
        elif isinstance(x, (list, tuple)):
            for v in x:
                walk(v)
        elif isinstance(x, dict):
            for v in x.values():
                walk(v)

    walk(exec_plan)
    return found


def run_query_phase(sess, qname, files, reference, watch, platform) -> list:
    """Cold then warm collect() of one query; returns the warm rows."""
    import jax

    from spark_rapids_tpu.memory import arena
    from spark_rapids_tpu.plan.execs.base import launch_stats
    from spark_rapids_tpu.utils.obs import QueryTrace, trace_scope

    df = build_query(sess, qname, files)
    plan = df.physical_plan()
    bad = fallback_nodes(plan)
    assert not bad, (qname, "CPU fallback in the physical plan", bad)

    runs = {}
    rows = None
    for label in ("cold", "warm"):
        c0, l0, oom0 = watch.snapshot(), launch_stats(), \
            arena.GLOBAL_DEVICE_OOM_COUNT
        trace = QueryTrace(f"smoke_{qname}_{label}", enabled=True)
        t0 = time.perf_counter()
        with trace_scope(trace):
            rows = build_query(sess, qname, files).collect()
        secs = time.perf_counter() - t0
        trace.finish()
        c1, l1 = watch.snapshot(), launch_stats()
        counters = trace.counters_snapshot()
        runs[label] = {
            "seconds": round(secs, 3),
            "jax_compile_requests": c1[0] - c0[0],
            "jax_compile_seconds": round(c1[1] - c0[1], 3),
            "persistent_cache_hits": c1[2] - c0[2],
            "programs_compiled": l1["programs"] - l0["programs"],
            "launches": l1["launches"] - l0["launches"],
            "device_oom": arena.GLOBAL_DEVICE_OOM_COUNT - oom0,
            "retries": counters.get("task_retry_count", 0),
            "split_retries": counters.get("task_split_retry_count", 0),
        }
        check_rows(qname, rows, reference)
        if qname == "q3":
            check_q3_order(rows)
    assert runs["warm"]["jax_compile_requests"] == 0, \
        (qname, "the warm run compiled", runs["warm"])
    assert runs["warm"]["programs_compiled"] == 0, (qname, runs["warm"])
    emit(qname, platform=platform, rows_returned=len(rows),
         rows_equal_reference=True, fallback_nodes=0,
         plan=[ln.strip().split("[")[0]
               for ln in plan.tree_string().splitlines()],
         cold=runs["cold"], warm=runs["warm"],
         peak_bytes_in_use=(jax.devices()[0].memory_stats() or {}).get(
             "peak_bytes_in_use"))
    return rows


def serve_phase(files, expected, watch, platform) -> None:
    from spark_rapids_tpu.cluster.stats import (
        local_shuffle_counters, reset_local_shuffle_counters)
    from spark_rapids_tpu.serving import LocalSessionRunner, QueryQueue

    runner = LocalSessionRunner({})
    queue = QueryQueue(runner)
    reset_local_shuffle_counters()
    c0 = watch.snapshot()
    t0 = time.perf_counter()
    futs = []
    served = list(expected)
    for i in range(8):
        qname = served[i % len(served)]
        plan = build_query(runner.session, qname, files).plan
        futs.append((qname, queue.submit_async(
            plan, tenant=f"tenant{(i // 2) % 2}")))
    for qname, fut in futs:
        check_rows(f"serve:{qname}", fut.result(timeout=900),
                   expected[qname])
    secs = time.perf_counter() - t0
    queue.close()
    counters = local_shuffle_counters()
    emit("serve", platform=platform, plans=served, submitted=len(futs),
         completed=len(futs), rows_equal_query_phase=True,
         seconds=round(secs, 3),
         jax_compile_requests=watch.snapshot()[0] - c0[0],
         counters={k: counters.get(k, 0) for k in (
             "queries_admitted", "queries_queued", "queries_rejected",
             "cache_hits", "cache_misses")})


def ici_phase(files, reference, watch, platform) -> None:
    """q3 as ONE SPMD program over four devices (parallel/stage.py), the
    shuffle an in-program all-to-all."""
    import jax

    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.parallel import stage
    from spark_rapids_tpu.parallel.distributed import make_mesh

    mesh = make_mesh(4)
    sess = TpuSession({"spark.rapids.sql.enabled": "true",
                       "spark.rapids.shuffle.mode": "ICI"}, mesh=mesh)
    bad = fallback_nodes(build_query(sess, "q3", files).physical_plan())
    assert not bad, ("q3", "CPU fallback in the physical plan", bad)
    runs = {}
    for label in ("cold", "warm"):
        c0 = watch.snapshot()
        t0 = time.perf_counter()
        rows = build_query(sess, "q3", files).collect()
        runs[label] = {"seconds": round(time.perf_counter() - t0, 3),
                       "jax_compile_requests": watch.snapshot()[0] - c0[0]}
        check_rows("ici:q3", rows, reference)
        check_q3_order(rows)
    # collect() switches to the task engine when the SPMD compiler
    # declines a plan; that would pass the row check on one device
    programs = len({id(fn) for fn, _ in stage._SPMD_PROGRAMS.values()})
    assert programs > 0, \
        "q3 did not run as an SPMD program (fell back to the task engine)"
    per_dev = [{"id": d.id,
                **{k: (d.memory_stats() or {}).get(k) for k in
                   ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}}
               for d in jax.devices()]
    emit("ici", platform=platform, mesh_devices=int(mesh.devices.size),
         rows_returned=len(rows), rows_equal_reference=True,
         spmd_programs=programs,
         cold=runs["cold"], warm=runs["warm"], per_device_memory=per_dev)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=None,
                    help=f"fact-table rows (default {DEFAULT_ROWS}); on a "
                    "machine without an accelerator this is required and "
                    "the run is a rehearsal that exits non-zero")
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--queries", default=",".join(DEFAULT_QUERIES),
                    help="one-chip query phases to run, of q6,q1,q3")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run ONLY the four-chip ICI exchange phase "
                    "(not proven on chips: its one-program compile for the "
                    "v5e did not finish in rehearsal, see README)")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    on_chip = platform != "cpu"
    if not on_chip and args.rows is None:
        sys.exit("chip_smoke: JAX found no accelerator (platform cpu). "
                 "Pass --rows N for a CPU rehearsal of the control flow; "
                 "it too exits non-zero and prints no result.")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices, JAX found {len(devices)}")

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    watch = CompileWatch()
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.memory import device_arena

    # the four-chip phase holds four chips while its host-only reference
    # runs, so it takes a quarter of the one-chip size unless told
    rows = args.rows or (DEFAULT_ROWS if args.chips == 1
                         else DEFAULT_ROWS // 4)
    sess = TpuSession({"spark.rapids.sql.enabled": "true"})
    stats = devices[0].memory_stats() or {}
    if on_chip:
        assert stats.get("bytes_limit"), ("no bytes_limit", stats)
        assert device_arena().budget_bytes, "arena budget not derived"
    emit("device", **device, bytes_limit=stats.get("bytes_limit"),
         arena_budget_bytes=device_arena().budget_bytes,
         fact_rows=rows, default_fact_rows=DEFAULT_ROWS,
         cut=(None if rows == DEFAULT_ROWS else
              f"{rows} of {DEFAULT_ROWS} fact rows"),
         compile_cache=cache_entries())

    scratch = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        queries = (tuple(args.queries.split(",")) if args.chips == 1
                   else ("q3",))
        t0 = time.perf_counter()
        files = write_tables(scratch, rows, args.seed, queries)
        emit("data", platform=platform, seed=args.seed, fact_rows=rows,
             parquet_bytes=files["parquet_bytes"],
             seconds=round(time.perf_counter() - t0, 3))

        t0 = time.perf_counter()
        reference = {q: pandas_reference(q, files) for q in queries}
        used = "pandas"
        if rows <= BATCH_ROWS:
            # small enough for the repo's own CPU oracle engine as well
            oracle = TpuSession({"spark.rapids.sql.enabled": "false"})
            for q in queries:
                check_rows(f"oracle:{q}",
                           build_query(oracle, q, files).collect(),
                           reference[q])
            used = "pandas, and equal to the CPU oracle engine"
        emit("reference", platform=platform, computed_on="host", used=used,
             rows={q: len(r) for q, r in reference.items()},
             seconds=round(time.perf_counter() - t0, 3))

        if args.chips == 4:
            ici_phase(files, reference["q3"], watch, platform)
        else:
            got = {q: run_query_phase(sess, q, files, reference[q], watch,
                                      platform) for q in queries}
            serve_phase(files, got, watch, platform)
        emit("cache", platform=platform, compile_cache=cache_entries(),
             jax_compile_requests=watch.requests,
             jax_compile_seconds=round(watch.seconds, 3),
             persistent_cache_hits=watch.cache_hits)
    finally:
        shutil.rmtree(scratch, ignore_errors=True)

    if not on_chip:
        sys.exit("chip_smoke: CPU rehearsal finished, every phase ran; "
                 "no accelerator, so no result.")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
