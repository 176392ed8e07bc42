"""User-facing session + DataFrame API.

The standalone framework's equivalent of a SparkSession with the plugin
installed: the same query runs on the TPU engine when
``spark.rapids.sql.enabled`` is true and on the CPU oracle engine when
false — which is exactly how the reference's differential harness flips
engines (reference: integration_tests/src/main/python/spark_session.py:
145-158 with_cpu_session/with_gpu_session).
"""
from __future__ import annotations

import itertools
from contextlib import contextmanager, nullcontext
from typing import Dict, List, Optional, Sequence, Tuple, Union

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema
from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.expressions.core import Col, Expression, col, lit
from spark_rapids_tpu.kernels.sort import SortOrder
from spark_rapids_tpu.plan import logical as L
from spark_rapids_tpu.plan.cpu_engine import CpuEngine
from spark_rapids_tpu.plan.engine import TpuEngine
from spark_rapids_tpu.planner.overrides import explain_query, plan_query
from spark_rapids_tpu.utils import obs, tracing

_COLLECT_IDS = itertools.count(1)   # query ids of traces collect() opens


def _to_expr(e) -> Expression:
    if isinstance(e, Expression):
        return e
    if isinstance(e, str):
        return col(e)
    return lit(e)


def _extract_windows(exprs, plan):
    """Pull every WindowExpression anywhere inside a projection list into
    Window node(s) beneath a final Project — Spark's
    ExtractWindowExpressions analyzer rule as mirrored by GpuWindowExec
    planning (reference sql-plugin/.../window/GpuWindowExec.scala:145).

    Windows nested inside scalar expressions (``over(...) + 1``) and
    multiple distinct (partition_by, order_by) specs in one select are
    supported: specs sharing partitioning/ordering land in one Window node
    (frames may differ per expression — the exec reads them individually);
    differing specs chain as stacked Window nodes.  Returns the rewritten
    projection list (window occurrences replaced by column refs) and the
    new child plan.
    """
    from spark_rapids_tpu.expressions.window import WindowExpression

    found: List[Expression] = []

    def scan(e):
        if isinstance(e, WindowExpression):
            found.append(e)
            return
        for c in e.children:
            scan(c)

    for e in exprs:
        scan(e)
    if not found:
        return exprs, plan

    # structural dedupe (identical window exprs share one computed column)
    names: Dict[str, str] = {}
    uniq: List[Tuple[str, Expression]] = []
    for w in found:
        k = repr(w)
        if k not in names:
            names[k] = f"__w{len(uniq)}"
            uniq.append((k, w))

    # one Window node per shared (partition_by, order_by)
    groups: Dict[Tuple[str, str], List[Tuple[str, Expression]]] = {}
    order: List[Tuple[str, str]] = []
    for k, w in uniq:
        gk = (repr(w.spec.partition_by), repr(w.spec.order_by))
        if gk not in groups:
            groups[gk] = []
            order.append(gk)
        groups[gk].append((k, w))
    for gk in order:
        plan = L.Window([w.alias(names[k]) for k, w in groups[gk]], plan)

    def rewrite(e):
        if isinstance(e, WindowExpression):
            return col(names[repr(e)])
        kids = tuple(rewrite(c) for c in e.children)
        if all(n is o for n, o in zip(kids, e.children)):
            return e
        return e.with_children(kids)

    return [rewrite(e) for e in exprs], plan


class TpuSession:
    def __init__(self, conf: Optional[Dict[str, str]] = None, mesh=None):
        """mesh: optional jax.sharding.Mesh.  With
        spark.rapids.shuffle.mode=ICI, supported queries execute SPMD over
        the mesh as one XLA program with all-to-all shuffle collectives
        (parallel/stage.py); unsupported plan shapes fall back to the
        task-parallel single-device engine, mirroring the reference's
        shuffle-manager mode switch."""
        self.conf = RapidsConf(conf or {})
        self.mesh = mesh
        # executor-init analog (Plugin.scala:657-690): apply memory/
        # semaphore/injection settings from this session's conf
        from spark_rapids_tpu.memory import initialize_memory
        initialize_memory(self.conf)
        from spark_rapids_tpu.shuffle.transport import (
            set_completeness_timeout, set_fetch_window)
        set_completeness_timeout(self.conf.shuffle_completeness_timeout)
        set_fetch_window(self.conf.shuffle_fetch_max_inflight,
                         self.conf.shuffle_fetch_threads,
                         self.conf.shuffle_fetch_merge_bytes,
                         self.conf.shuffle_fetch_request_bytes)
        from spark_rapids_tpu.shuffle.serializer import set_reader_threads
        set_reader_threads(self.conf.shuffle_reader_threads)
        if self.conf.diag_dump_dir:
            from spark_rapids_tpu.utils import crashdump
            crashdump.install(self.conf.diag_dump_dir,
                              context={"session": "standalone"})
        self.last_query_metrics = None
        #: the QueryTrace the last action opened for itself (a sink was
        #: on and no trace was ambient); None where it opened none
        self.last_query_trace = None

    def set_conf(self, key: str, value) -> None:
        self.conf = self.conf.with_overrides(**{key: value})

    # -- data sources -------------------------------------------------------

    def create_dataframe(self, data, schema: Optional[Schema] = None,
                         num_partitions: int = 1) -> "DataFrame":
        """data: dict of lists, pyarrow Table, or list of ColumnarBatches."""
        if isinstance(data, dict):
            assert schema is not None, "dict data needs a Schema"
            batch = ColumnarBatch.from_pydict(data, schema)
            batches = [batch]
        elif isinstance(data, list) and data and isinstance(data[0], ColumnarBatch):
            batches = data
            schema = batches[0].schema
        else:  # pyarrow
            batch = ColumnarBatch.from_arrow(data)
            batches = [batch]
            schema = batch.schema
        # split into partitions round-robin by batch
        parts: List[List[ColumnarBatch]] = [[] for _ in range(num_partitions)]
        for i, b in enumerate(batches):
            parts[i % num_partitions].append(b)
        return DataFrame(L.InMemoryRelation(parts, schema), self)

    def read_parquet(self, *paths: str,
                     columns: Optional[Sequence[str]] = None) -> "DataFrame":
        from spark_rapids_tpu.io.parquet import parquet_schema
        schema = parquet_schema(paths[0], columns)
        return DataFrame(
            L.ParquetRelation(paths, schema,
                              tuple(columns) if columns else None), self)

    def _read_file(self, paths, fmt, columns, schema, **options):
        from spark_rapids_tpu.io.formats import infer_schema
        sch = infer_schema(paths[0], fmt, columns, schema, **options)
        return DataFrame(
            L.FileRelation(paths, fmt, sch,
                           tuple(columns) if columns else None, options),
            self)

    def read_csv(self, *paths: str, columns=None, schema=None,
                 **options) -> "DataFrame":
        return self._read_file(paths, "csv", columns, schema, **options)

    def read_json(self, *paths: str, columns=None, schema=None,
                  **options) -> "DataFrame":
        return self._read_file(paths, "json", columns, schema, **options)

    def read_orc(self, *paths: str, columns=None, schema=None,
                 **options) -> "DataFrame":
        return self._read_file(paths, "orc", columns, schema, **options)

    def range(self, start: int, end: Optional[int] = None, step: int = 1,
              num_partitions: int = 1) -> "DataFrame":
        """spark.range analog: device-generated LONG ids (GpuRangeExec)."""
        if end is None:
            start, end = 0, start
        return DataFrame(L.Range(start, end, step, num_partitions), self)

    def read_iceberg(self, table_path: str,
                     snapshot_id: Optional[int] = None,
                     as_of_ms: Optional[int] = None,
                     prune: Optional[Dict] = None) -> "DataFrame":
        """Iceberg snapshot read with optional time travel and file-level
        min/max pruning ({col: (lo, hi)} conjunctive ranges)."""
        from spark_rapids_tpu.io.iceberg import (
            IcebergTable, _current_struct, field_ids, prune_files)
        table = IcebergTable.load(table_path)
        snap = table.snapshot(snapshot_id=snapshot_id, as_of_ms=as_of_ms)
        files = snap.data_files()
        deletes = snap.delete_files()
        if prune:
            files = prune_files(files, snap.schema, prune,
                                ids=field_ids(_current_struct(snap.meta)))
        return DataFrame(
            L.IcebergRelation(table_path, snap, files, deletes=deletes),
            self)

    def iceberg_delete(self, table_path: str, predicate) -> int:
        """DELETE FROM an Iceberg table via v2 position delete files
        (merge-on-read): matching row ordinals per data file are written
        as one position-delete parquet + delete manifest in a new
        snapshot (io/iceberg.py commit_position_deletes).  Returns the
        new snapshot id, or the current one when nothing matched."""
        import numpy as np
        import pyarrow.parquet as pq

        from spark_rapids_tpu.columnar.arrow import arrow_to_batch
        from spark_rapids_tpu.expressions.core import EvalContext
        from spark_rapids_tpu.io.iceberg import (
            DeleteFilter, IcebergTable, _current_struct,
            commit_position_deletes)

        table = IcebergTable.load(table_path)
        snap = table.snapshot()
        struct = _current_struct(snap.meta)
        id_to_name = {f["id"]: f["name"] for f in struct["fields"]}
        existing = DeleteFilter(snap.schema, id_to_name,
                                snap.delete_files(), positions_only=True)
        bound = _to_expr(predicate).bind(snap.schema)
        per_file = {}
        for df in snap.data_files():
            # evaluate against PHYSICAL rows so ordinals stay stable
            # even when earlier delete files already cover some of them
            at = pq.read_table(df["file_path"],
                               columns=list(snap.schema.names))
            batch = arrow_to_batch(at)
            n = batch.host_num_rows()
            colv = bound.eval(EvalContext(batch))
            vals, valid = colv.to_numpy(n)
            hits = np.nonzero(np.asarray(vals, np.bool_) & valid)[0] \
                .astype(np.int64)
            # drop ordinals an applicable position delete already covers,
            # so re-running the same DELETE is a true no-op
            covered = existing.positions_for(df["file_path"],
                                             df.get("_seq") or 0)
            if len(covered):
                hits = np.setdiff1d(hits, covered)
            if len(hits):
                per_file[df["file_path"]] = hits
        if not per_file:
            return snap.snapshot_id
        return commit_position_deletes(table_path, per_file)

    def iceberg_optimize(self, table_path: str) -> int:
        """Compact an Iceberg table: read the current snapshot (applying
        any v2 merge-on-read delete files), rewrite the surviving rows
        as fresh data files, and commit an overwrite snapshot — dropping
        both the fragmented data files and the delete files (the
        rewrite-data-files action the reference accelerates as
        copy-on-write compaction).  Returns rows written; 0 when the
        table is already compact (single data file, no delete files —
        no snapshot is committed).  Partitioned tables are rejected:
        the writer's overwrite path emits unpartitioned manifests, which
        would silently discard the declared partition spec."""
        from spark_rapids_tpu.io.iceberg import IcebergTable
        table = IcebergTable.load(table_path)
        specs = list(table.meta.get("partition-specs") or [])
        # v1 metadata can declare partitioning ONLY via the singular
        # 'partition-spec' field (ADVICE r4 #2: a legacy table slipping
        # past the v2-only check would be rewritten unpartitioned —
        # exactly the silent layout loss this guard exists to prevent)
        v1_fields = table.meta.get("partition-spec") or []
        if v1_fields:
            specs.append({"fields": v1_fields})
        if any(s.get("fields") for s in specs):
            raise NotImplementedError(
                "iceberg_optimize over identity-partitioned tables: the "
                "overwrite writer emits unpartitioned manifests and would "
                "drop the partition layout")
        snap = table.snapshot()
        if not snap.delete_files() and len(snap.data_files()) <= 1:
            return 0            # already compact: no-op, no new snapshot
        df = self.read_iceberg(table_path)
        return df.write_iceberg(table_path, mode="overwrite")

    def read_avro(self, *paths: str, columns=None) -> "DataFrame":
        """Avro container scan (reference GpuAvroScan analog): records
        decode host-side through io/avro.py and upload as one batch per
        file."""
        from spark_rapids_tpu.io import avro as A
        batches = []
        for p in paths:
            _, records, sch = A.read_container(p)
            table = A.records_to_arrow(records, sch)
            if columns:
                table = table.select(list(columns))
            batches.append(ColumnarBatch.from_arrow(table))
        return self.create_dataframe(batches,
                                     num_partitions=max(len(batches), 1))

    def read_delta(self, table_path: str,
                   version: Optional[int] = None) -> "DataFrame":
        from spark_rapids_tpu.io.delta import load_snapshot
        snapshot = load_snapshot(table_path, version)
        return DataFrame(L.DeltaRelation(table_path, snapshot), self)

    def delta_delete(self, table_path: str, predicate) -> int:
        """DELETE FROM delta table via deletion vectors (io/delta_write)."""
        from spark_rapids_tpu.io.delta_write import delete_from
        return delete_from(self, table_path, _to_expr(predicate))

    def delta_optimize(self, table_path: str,
                       zorder_by: Sequence[str] = ()) -> int:
        """OPTIMIZE [ZORDER BY] a delta table (io/delta_write)."""
        from spark_rapids_tpu.io.delta_write import optimize
        return optimize(self, table_path, zorder_by=zorder_by)

    def explain_analyze(self, plan) -> str:
        """EXPLAIN ANALYZE: execute the plan (a DataFrame or logical
        plan) under a query-scoped trace with every exec node's batch
        seams instrumented, and render the physical plan tree annotated
        with the MEASURED metrics — rows/batches/time per node (an exec's
        own opTime where it keeps one, the analyzer's seam time where it
        doesn't), plus a footer with the query-attributed launch counts
        and counter deltas (spill/pin bytes, fetch stall, admission
        wait...).  The distributed twin is ``driver.query_report(qid)``,
        which renders the same tree from executor-merged telemetry.

        The run is a REAL execution (the analyzer seam adds iterate
        timing only, no device syncs); rows are discarded."""
        import time as _time

        from spark_rapids_tpu.plan.execs.base import launch_stats
        from spark_rapids_tpu.utils.obs import (
            QueryTrace, instrument_plan, metrics_tree,
            render_metrics_tree, trace_scope)
        df = plan if isinstance(plan, DataFrame) else DataFrame(plan, self)
        trace = QueryTrace("explain_analyze", enabled=True,
                           max_spans=self.conf.trace_max_spans,
                           default_track="local")
        with df._session_tz_scope():
            exec_plan, _ = plan_query(df.plan, self.conf)
            instrument_plan(exec_plan)
            engine = TpuEngine(self.conf)
            before = launch_stats()
            t0 = _time.perf_counter()
            with trace_scope(trace):
                # execute, not collect: the rows are discarded, so the
                # per-row CpuTable host conversion (which can dwarf the
                # query itself on a wide result) is pure waste
                engine.execute(exec_plan)
            wall_s = _time.perf_counter() - t0
            after = launch_stats()
        trace.finish()
        # the engine snapshots metrics at cleanup (last_metrics); the
        # tree re-walk here picks up the SAME MetricSet objects, now
        # holding both the execs' own metrics and the analyzer's seams
        tree = (engine.last_metrics
                if engine.last_metrics is not None
                else metrics_tree(exec_plan))
        footer = {
            "wall_s": round(wall_s, 4),
            "launches": after["launches"] - before["launches"],
            # newly-compiled during THIS run (0 = fully warm cache);
            # the cumulative process count would misattribute prior
            # queries' programs to this report
            "programs_compiled": after["programs"] - before["programs"],
            "counters": trace.counters_snapshot(),
        }
        return render_metrics_tree(tree, footer=footer)


class GroupedData:
    def __init__(self, df: "DataFrame", keys: Sequence[Expression],
                 grouping_sets=None):
        self.df = df
        self.keys = [_to_expr(k) for k in keys]
        #: None = plain group-by; else list of frozensets of included key
        #: ordinals (rollup/cube/grouping sets)
        self.grouping_sets = grouping_sets

    def agg(self, *aggs) -> "DataFrame":
        if self.grouping_sets is None:
            return DataFrame(
                L.Aggregate(self.keys, [_to_expr(a) for a in aggs],
                            self.df.plan), self.df.session)
        return self._grouping_sets_agg([_to_expr(a) for a in aggs])

    def pivot(self, pivot_col, values) -> "PivotedGroupedData":
        """Spark's df.groupBy(..).pivot(col, values).agg(..), lowered to
        conditional aggregates: each (pivot value, aggregate) pair becomes
        agg(IF(pivot == value, input, NULL)).  The reference plans this
        via PivotFirst (aggregateFunctions.scala); conditional aggregation
        is the TPU-first equivalent — one fused device pass, no per-value
        buffer shuffling, identical results.  ``values`` must be given
        explicitly (Spark's implicit-values form runs a distinct query
        first; callers can do the same with .select().distinct())."""
        return PivotedGroupedData(self, _to_expr(pivot_col), list(values))

    def _grouping_sets_agg(self, aggs) -> "DataFrame":
        """rollup/cube: Expand (one projection per grouping set, excluded
        keys nulled + a grouping-id column) -> Aggregate on keys+gid ->
        project the gid away.  Spark's ExpandExec+Aggregate plan shape
        (reference GpuExpandExec.scala).  grouping_id() markers in the
        aggregate outputs resolve to the internal gid column (Spark's
        spark_grouping_id bit encoding: bit set = key NOT grouped)."""
        from spark_rapids_tpu.expressions.core import Col, Literal
        from spark_rapids_tpu.expressions.grouping import GroupingId
        child = self.df.plan
        key_names = []
        for k in self.keys:
            assert isinstance(k, Col), "rollup/cube keys must be columns"
            key_names.append(k.name)
        nkeys = len(key_names)
        # Spark's ExpandExec keeps the original attributes (aggregate
        # inputs read them un-nulled) and adds SEPARATE per-set nulled
        # grouping copies + the grouping id
        names = (list(child.schema.names)
                 + [f"_gk{i}" for i in range(nkeys)] + ["_gid"])
        projections = []
        for included in self.grouping_sets:
            gid = 0
            for i in range(nkeys):
                if i not in included:
                    gid |= 1 << (nkeys - 1 - i)
            proj = [col(n) for n in child.schema.names]
            for i, kn in enumerate(key_names):
                if i in included:
                    proj.append(col(kn))
                else:
                    proj.append(Literal(None, child.schema.dtype_of(kn)))
            proj.append(Literal(gid, T.LONG))
            projections.append(proj)
        expanded = L.Expand(projections, names, child)
        # group on the nulled copies + _gid
        from spark_rapids_tpu.expressions.core import Alias, output_name
        group_keys = [Alias(col(f"_gk{i}"), key_names[i])
                      for i in range(nkeys)] + [col("_gid")]
        # grouping_id() outputs read the gid GROUP KEY column through the
        # final projection (grouping refs cannot ride in the aggregate
        # outputs); any expression OVER grouping_id with no aggregate
        # calls moves wholesale to the projection
        from spark_rapids_tpu.expressions.aggregates import find_aggregates
        from spark_rapids_tpu.expressions.grouping import (
            _contains_grouping_id, substitute_grouping_id)
        real_aggs = []
        gid_slots = []   # (position in agg list, projection expr)
        for i, a in enumerate(aggs):
            if not _contains_grouping_id(a):
                real_aggs.append(a)
                continue
            if find_aggregates(a):
                raise NotImplementedError(
                    "grouping_id() mixed with aggregate calls in one "
                    "output expression; compute them as separate outputs "
                    "and combine with a select() afterwards")
            out_name = output_name(a, i)
            expr = substitute_grouping_id(
                a.child if isinstance(a, Alias) else a)
            gid_slots.append((i, Alias(expr, out_name)))
        agg = L.Aggregate(group_keys, real_aggs, expanded)
        # _gid is dropped from the output unless grouping_id() asked for it
        # (Spark drops spark_grouping_id unless selected explicitly)
        keep = [col(n) for n in agg.schema.names if n != "_gid"]
        for pos, proj_expr in gid_slots:
            keep.insert(nkeys + pos, proj_expr)
        return DataFrame(L.Project(keep, agg), self.df.session)

    def apply_in_pandas(self, fn, schema: Schema) -> "DataFrame":
        assert self.grouping_sets is None, \
            "rollup/cube support agg() only (Spark parity)"
        """pyspark applyInPandas analog (grouped map): repartition on the
        grouping keys, then fn(pandas.DataFrame) per key group.
        Reference: GpuFlatMapGroupsInPandasExec."""
        import pyarrow as pa
        from spark_rapids_tpu.expressions.core import Col

        key_names = []
        for k in self.keys:
            assert isinstance(k, Col), \
                "apply_in_pandas keys must be plain columns"
            key_names.append(k.name)

        def _wrapper(table):
            pdf = table.to_pandas()
            outs = []
            for _, group in pdf.groupby(key_names, dropna=False,
                                        sort=True):
                res = fn(group)
                if len(res):
                    outs.append(res)
            import pandas as pd
            merged = (pd.concat(outs, ignore_index=True) if outs
                      else pd.DataFrame(
                          {n: pd.Series(dtype=object)
                           for n in schema.names}))
            return pa.Table.from_pandas(merged, preserve_index=False)
        _wrapper.__name__ = getattr(fn, "__name__", "apply_in_pandas")

        nparts = self.df.session.conf.shuffle_partitions
        repart = L.Repartition(nparts, list(self.keys), self.df.plan)
        return DataFrame(
            L.MapBatches(_wrapper, schema, repart, whole_partition=True),
            self.df.session)


class PivotedGroupedData:
    """groupBy(..).pivot(col, values) staging: agg() expands per value."""

    def __init__(self, grouped: GroupedData, pivot_expr, values):
        self.grouped = grouped
        self.pivot_expr = pivot_expr
        self.values = values

    def agg(self, *aggs) -> "DataFrame":
        from spark_rapids_tpu.expressions.aggregates import (
            AggregateFunction, find_aggregates)
        from spark_rapids_tpu.expressions.conditional import If
        from spark_rapids_tpu.expressions.core import (
            Alias, Literal, output_name)
        out = []
        for pv in self.values:
            for a in aggs:
                a = _to_expr(a)
                name = (a.name if isinstance(a, Alias)
                        else output_name(a, 0))

                def matched_count():
                    # rows of the group matching this pivot value — the
                    # per-value guard for zero-input aggregates AND the
                    # any-row-matches indicator below
                    from spark_rapids_tpu.expressions.aggregates import (
                        Count)
                    return Count(If(self.pivot_expr == Literal(pv),
                                    Literal(True), Literal(None)))

                def rewrite(e):
                    if isinstance(e, AggregateFunction):
                        if not e.children:
                            # zero-input aggregates (count(*)): guard by
                            # counting the pivot predicate itself — a
                            # bare pass-through would count ALL group
                            # rows for every pivot column
                            from spark_rapids_tpu.expressions.aggregates \
                                import Count
                            assert isinstance(e, Count), \
                                f"pivot cannot rewrite zero-input {e!r}"
                            return matched_count()
                        # untyped NULL literal: columns are unbound here,
                        # If takes its dtype from the then-branch
                        kids = tuple(
                            If(self.pivot_expr == Literal(pv),
                               c, Literal(None))
                            for c in e.children)
                        return e.with_children(kids)
                    if not e.children:
                        return e
                    return e.with_children(
                        tuple(rewrite(c) for c in e.children))

                def null_when_absent(e):
                    # Spark/PivotFirst semantics: a group×pivot-value
                    # combination with NO matching rows is NULL, not 0 —
                    # count-family rewrites alone would emit 0 (ADVICE r5
                    # medium).  0 still appears when rows match but every
                    # input is null.
                    from spark_rapids_tpu.expressions.aggregates import (
                        Count)
                    has_count = [False]

                    def walk(x):
                        if isinstance(x, Count):
                            has_count[0] = True
                        for c in x.children:
                            walk(c)
                    walk(e)
                    if not has_count[0]:
                        return e    # sum/min/... are NULL-on-absent already
                    return If(matched_count() > Literal(0), e,
                              Literal(None))
                col_name = (str(pv) if len(aggs) == 1
                            else f"{pv}_{name}")
                rewritten = rewrite(a.child if isinstance(a, Alias) else a)
                out.append(Alias(null_when_absent(rewritten), col_name))
        return self.grouped.agg(*out)


class DataFrame:
    def __init__(self, plan: L.LogicalPlan, session: TpuSession):
        self.plan = plan
        self.session = session

    @property
    def schema(self) -> Schema:
        return self.plan.schema

    # -- transformations ----------------------------------------------------

    def select(self, *exprs) -> "DataFrame":
        projections, plan = _extract_windows(
            [_to_expr(e) for e in exprs], self.plan)
        return DataFrame(L.Project(projections, plan), self.session)

    def filter(self, condition) -> "DataFrame":
        return DataFrame(L.Filter(_to_expr(condition), self.plan), self.session)

    where = filter

    def with_column(self, name: str, expr) -> "DataFrame":
        e = _to_expr(expr)
        exprs = [col(n) for n in self.schema.names if n != name]
        exprs.append(e.alias(name))
        return self.select(*exprs)

    def rollup(self, *keys) -> GroupedData:
        """Hierarchical grouping sets: (k1..kn), (k1..kn-1), ..., ()."""
        n = len(keys)
        sets = [frozenset(range(i)) for i in range(n, -1, -1)]
        return GroupedData(self, [_to_expr(k) for k in keys],
                           grouping_sets=sets)

    def cube(self, *keys) -> GroupedData:
        """All 2^n grouping-set combinations of the keys."""
        import itertools
        n = len(keys)
        sets = [frozenset(c) for r in range(n, -1, -1)
                for c in itertools.combinations(range(n), r)]
        return GroupedData(self, [_to_expr(k) for k in keys],
                           grouping_sets=sets)

    def expand(self, projections, names) -> "DataFrame":
        """Raw Expand node (one output row per projection per input row)."""
        return DataFrame(
            L.Expand([[_to_expr(e) for e in p] for p in projections],
                     list(names), self.plan), self.session)

    def sample(self, fraction: float, seed: int = 42) -> "DataFrame":
        return DataFrame(L.Sample(fraction, seed, self.plan), self.session)

    def build_bloom(self, expr, expected_items: int, fpp: float = 0.03):
        """Build a Spark-wire-compatible bloom filter over a LONG column —
        the build half of the runtime-filter pair (BloomFilterAggregate;
        reference GpuBloomFilter.scala).  Probe with
        expressions.hashing.BloomFilterMightContain(value_expr, bloom)."""
        import numpy as np
        from spark_rapids_tpu.expressions.core import Alias
        from spark_rapids_tpu.kernels import bloom as BK
        num_bits = BK.optimal_num_bits(expected_items, fpp)
        k = BK.optimal_num_hashes(expected_items, num_bits)
        parts = self.select(Alias(_to_expr(expr), "_b")).collect_partitions()
        bits = None
        for part in parts:
            for b in part:
                bits = BK.build_bits(b.columns[0], b.num_rows, num_bits, k,
                                     bits)
        host = (np.asarray(bits) if bits is not None
                else np.zeros((num_bits,), np.bool_))
        return BK.PyBloomFilter(num_bits, k, np.array(host, copy=True))

    def persist(self, serializer: str = "device") -> "DataFrame":
        """Materialize once and reuse (the InMemoryTableScan / cached
        batch analog: reference GpuInMemoryTableScanExec.scala).

        serializer='device' keeps live batches (fast rescan, full HBM
        cost); serializer='parquet' stores each partition as compressed
        in-memory parquet blobs (the ParquetCachedBatchSerializer analog,
        reference parquet/ParquetCachedBatchSerializer.scala:266) —
        ~10x smaller resident cache, decode on each rescan."""
        parts = self.collect_partitions()
        if serializer == "device":
            return DataFrame(L.InMemoryRelation(
                [list(p) for p in parts], self.schema), self.session)
        if serializer != "parquet":
            raise ValueError(f"unknown cache serializer {serializer!r} "
                             "(device/parquet)")
        import io as _io

        import pyarrow.parquet as pq
        blobs = []
        for p in parts:
            bl = []
            for b in p:
                sink = _io.BytesIO()
                pq.write_table(b.to_arrow(), sink, compression="zstd")
                bl.append(sink.getvalue())
            blobs.append(bl)
        return DataFrame(L.CachedParquetRelation(blobs, self.schema),
                         self.session)

    def group_by(self, *keys) -> GroupedData:
        return GroupedData(self, [_to_expr(k) for k in keys])

    def agg(self, *aggs) -> "DataFrame":
        return DataFrame(L.Aggregate([], [_to_expr(a) for a in aggs],
                                     self.plan), self.session)

    def order_by(self, *orders) -> "DataFrame":
        parsed: List[Tuple[Expression, SortOrder]] = []
        for o in orders:
            if isinstance(o, tuple):
                e, so = o
                parsed.append((_to_expr(e), so))
            else:
                parsed.append((_to_expr(o), SortOrder(True)))
        return DataFrame(L.Sort(parsed, self.plan), self.session)

    sort = order_by

    def limit(self, n: int) -> "DataFrame":
        return DataFrame(L.Limit(n, self.plan), self.session)

    def union(self, other: "DataFrame") -> "DataFrame":
        return DataFrame(L.Union([self.plan, other.plan]), self.session)

    def repartition(self, num_partitions: int, *keys) -> "DataFrame":
        return DataFrame(
            L.Repartition(num_partitions, [_to_expr(k) for k in keys],
                          self.plan), self.session)

    def explode(self, expr, alias: str = "col", outer: bool = False,
                pos: bool = False, pos_alias: str = "pos") -> "DataFrame":
        """Append explode(expr) rows: child columns + [pos] + element column
        (Spark's select('*', explode(c)); GenerateExec)."""
        from spark_rapids_tpu.expressions.collections import Explode, PosExplode
        gen = (PosExplode if pos else Explode)(_to_expr(expr))
        return DataFrame(
            L.Generate(gen, self.plan, outer=outer, alias=alias,
                       pos_alias=pos_alias), self.session)

    def map_batches(self, fn, schema: Schema) -> "DataFrame":
        """Arrow-batch python transform: fn(pyarrow.Table) -> pyarrow.Table
        producing `schema` (pandas interop: use table.to_pandas() inside)."""
        return DataFrame(L.MapBatches(fn, schema, self.plan), self.session)

    def map_in_pandas(self, fn, schema: Schema) -> "DataFrame":
        """pyspark mapInPandas analog: fn(pandas.DataFrame) ->
        pandas.DataFrame producing `schema`; rides the Arrow bridge with
        the device semaphore released while Python runs
        (GpuArrowEvalPythonExec/PythonWorkerSemaphore analog)."""
        import pyarrow as pa

        def _wrapper(table):
            result = fn(table.to_pandas())
            return pa.Table.from_pandas(result, preserve_index=False)
        _wrapper.__name__ = getattr(fn, "__name__", "map_in_pandas")
        return DataFrame(L.MapBatches(_wrapper, schema, self.plan),
                         self.session)

    def join(self, other: "DataFrame", on=None, how: str = "inner",
             condition=None) -> "DataFrame":
        """Equi-join on `on` keys plus optional residual `condition` (an
        expression over left-then-right columns, Spark's non-equi join
        predicate).  `on=None` with a condition is a nested-loop/cartesian
        join; `how="existence"` appends a boolean `exists` column instead
        of right columns."""
        if on is None:
            lkeys, rkeys = [], []
        elif isinstance(on, str):
            lkeys = rkeys = [col(on)]
        elif isinstance(on, (list, tuple)) and on and isinstance(on[0], str):
            lkeys = [col(k) for k in on]
            rkeys = [col(k) for k in on]
        else:
            lkeys, rkeys = on
        return DataFrame(
            L.Join(self.plan, other.plan, lkeys, rkeys, join_type=how,
                   condition=condition),
            self.session)

    # -- actions ------------------------------------------------------------

    def collect(self) -> List[tuple]:
        with self._session_tz_scope():
            return self._collect_impl()

    def _collect_impl(self) -> List[tuple]:
        with self._query_root():
            return self._collect_traced()

    @contextmanager
    def _query_root(self):
        """The root of one action's span tree: ``query.collect``, under a
        ``QueryTrace`` of this action's own when none is ambient (outside
        serving) and a sink is on, so its spans share a query id and each
        names its parent.  With no sink on no trace is made."""
        conf = self.session.conf
        own = None
        if tracing.span_log.enabled:
            # host.lock_wait and the late ticks: the one sampler thread,
            # which ends by itself once the log is off again
            tracing.sampler.ensure_running()
        if obs.current_query_trace() is None and (
                tracing.span_log.enabled or conf.trace_enabled):
            own = obs.QueryTrace(f"collect-{next(_COLLECT_IDS)}",
                                 max_spans=conf.trace_max_spans)
        try:
            with obs.trace_scope(own) if own is not None else nullcontext(), \
                    tracing.trace_range("query.collect"):
                yield
        finally:
            self.session.last_query_trace = own
            if own is not None:
                own.finish()
                if conf.trace_dir:
                    obs.export_trace_file(own, conf.trace_dir)

    def _plan_query(self):
        with tracing.trace_range("query.plan"):
            exec_plan, _ = plan_query(self.plan, self.session.conf)
        return exec_plan

    def _collect_traced(self) -> List[tuple]:
        if self.session.conf.sql_enabled:
            exec_plan = self._plan_query()
            from spark_rapids_tpu.plan.execs.fallback import (
                TpuCpuFallbackExec)
            if isinstance(exec_plan, TpuCpuFallbackExec):
                # the WHOLE plan is a CPU island: collect its oracle rows
                # directly — a device round-trip would be pure overhead
                # and device columns cannot even represent some bridged
                # output types (array<string>)
                self.session.last_query_metrics = None  # no device run
                return exec_plan.collect_rows()
            if (self.session.conf.shuffle_mode == "ICI"
                    and self.session.mesh is not None):
                from spark_rapids_tpu.parallel.stage import (
                    IciQueryExecutor, UnsupportedSpmd)
                from spark_rapids_tpu.plan.cpu_engine import CpuTable
                try:
                    shards = IciQueryExecutor(
                        self.session.mesh).execute(exec_plan)
                    rows: List[tuple] = []
                    for b in shards:
                        rows.extend(CpuTable.from_batch(b).rows())
                    return rows
                except UnsupportedSpmd:
                    pass   # mode switch: fall back to the task engine
            engine = TpuEngine(self.session.conf)
            if self.session.conf.profile_enabled:
                # per-query flamegraph + late ticks (asyncProfiler analog,
                # utils/profiler.py).  Diagnostics must never fail the
                # query: artifact I/O errors are swallowed (unwritable
                # dir, full disk).
                from spark_rapids_tpu.utils.profiler import QueryProfiler
                qp = None
                try:
                    qp = QueryProfiler(
                        self.session.conf.profile_dir).__enter__()
                except OSError:
                    pass
                try:
                    out = engine.collect(exec_plan)
                finally:
                    if qp is not None:
                        try:
                            qp.finish()
                        except Exception:  # noqa: BLE001 — diagnostics
                            qp.__exit__()  # must never fail the query
            else:
                out = engine.collect(exec_plan)
            self.session.last_query_metrics = engine.last_metrics
            return out
        return CpuEngine(self.session.conf.shuffle_partitions).collect(self.plan)

    def explain(self) -> str:
        return explain_query(self.plan, self.session.conf)

    def physical_plan(self):
        exec_plan, meta = plan_query(self.plan, self.session.conf)
        return exec_plan

    def _session_tz_scope(self):
        """Every plan-executing action runs under the session timezone
        ambient — written output must agree with collect() output."""
        from spark_rapids_tpu.config import session_timezone
        return session_timezone(self.session.conf.raw(
            "spark.sql.session.timeZone", "UTC"))

    def _collect_batches(self):
        """Materialize as device batches (the ColumnarRdd analog: zero-copy
        handoff to ML frameworks, reference sql-plugin-api ColumnarRdd.scala)."""
        with self._session_tz_scope(), self._query_root():
            exec_plan = self._plan_query()
            engine = TpuEngine(self.session.conf)
            out = engine.execute(exec_plan)
        self.session.last_query_metrics = engine.last_metrics
        return out

    def collect_partitions(self):
        """Device batches per partition on either engine (the writer's
        input seam; CPU-oracle results upload through Arrow)."""
        if self.session.conf.sql_enabled:
            return self._collect_batches()
        from spark_rapids_tpu.columnar.batch import ColumnarBatch
        with self._session_tz_scope():
            tables = CpuEngine(
                self.session.conf.shuffle_partitions).execute(self.plan)
        out = []
        for t in tables:
            data = {}
            for (vals, valid), name in zip(t.cols, t.schema.names):
                data[name] = [v if m else None
                              for v, m in zip(vals.tolist(), valid.tolist())]
            out.append([ColumnarBatch.from_pydict(data, t.schema)])
        return out

    def write(self, path: str, fmt: str = "parquet",
              partition_by=(), mode: str = "error"):
        """Write with dynamic partitioning + the commit protocol
        (GpuFileFormatDataWriter.scala analog)."""
        from spark_rapids_tpu.io.writer import write_dataframe
        return write_dataframe(self, path, fmt=fmt,
                               partition_by=partition_by, mode=mode)

    def write_delta(self, path: str, mode: str = "error",
                    partition_by=()):
        """Write this DataFrame as a Delta table commit (create or append)."""
        from spark_rapids_tpu.io.delta_write import write_delta
        return write_delta(self, path, mode=mode, partition_by=partition_by)

    def write_iceberg(self, path: str, mode: str = "error") -> int:
        """Commit this DataFrame to an Iceberg table (create/append/
        overwrite, copy-on-write).  Returns rows written."""
        from spark_rapids_tpu.io.iceberg import IcebergWriter
        writer = IcebergWriter(path, self.schema)
        return writer.commit(self.collect_partitions(), mode=mode)

    def write_parquet(self, path: str) -> int:
        from spark_rapids_tpu.io.parquet import write_parquet
        batches = [b for part in self._collect_batches() for b in part]
        return write_parquet(batches, path, schema=self.schema)

    def write_file(self, path: str, fmt: str) -> int:
        from spark_rapids_tpu.io.formats import write_file
        batches = [b for part in self._collect_batches() for b in part]
        return write_file(batches, path, fmt, schema=self.schema)

    def count(self) -> int:
        from spark_rapids_tpu.expressions.aggregates import count
        rows = self.agg(count()).collect()
        return rows[0][0]
