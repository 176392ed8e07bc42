"""TPC-H Q21's three LINEITEM instances (a left semi-join and a left
anti-join on ``l_orderkey``, each with a residual ``<>`` on the supplier,
then a count by supplier and an ORDER BY) through the engine's normal path,
against the plain reference the benchmark keeps
(``benchmark/queries/q21_lineitem.py``), on the benchmark's own LINEITEM at a
small batch capacity: several batches from two files, so orders straddle
batch, file and shuffle-partition boundaries.  And the one way a join reads
an exchange on its reduce side: a group that is one program's work arrives
as raw pieces which the probe program folds (``plan/execs/join.py``
``PieceSide``); only a partition past ``reduce_group_in_core`` takes the
merged read and the sub-partitioned out-of-core path.

On the CPU backend: rows and counts, never times.
"""
import numpy as np
import pytest

from benchmark import datagen
from benchmark.queries import q21_lineitem
from spark_rapids_tpu import types as T
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema
from spark_rapids_tpu.expressions import col
from spark_rapids_tpu.plan.execs.base import (launch_stats,
                                              reset_launch_stats)
from spark_rapids_tpu.plan.execs.join import (
    PieceSide, TpuAdaptiveJoinExec, TpuBroadcastHashJoinExec,
    TpuShuffledHashJoinExec, _JoinKernel)
from spark_rapids_tpu.shuffle.stats import SHUFFLE_COUNTERS
from spark_rapids_tpu.utils import tracing
from tests.test_q18_inner import (  # noqa: F401 — the fixture is used by name
    fresh_program_caches, nodes, write_lineitem)

ROWS, BATCH = 40_000, 8_192
SEEDS = (7, 2**31 + 11, 19)
JOINS = (TpuShuffledHashJoinExec, TpuBroadcastHashJoinExec,
         TpuAdaptiveJoinExec)


def session(threshold, fuse=True, batch_rows=BATCH):
    return TpuSession({
        "spark.rapids.sql.enabled": "true",
        "spark.rapids.sql.tpu.fuseStages": str(fuse).lower(),
        "spark.rapids.sql.batchSizeRows": str(batch_rows),
        "spark.rapids.sql.reader.batchSizeRows": str(batch_rows),
        "spark.rapids.sql.join.broadcastRowThreshold": str(threshold)})


def replace_column(chunks, name, kind, fn):
    """``chunks`` (one Arrow table a row group) with column ``name``
    computed by ``fn(chunk)`` (numpy)."""
    out = []
    for c in chunks:
        i = c.schema.get_field_index(name)
        out.append(c.set_column(i, name,
                                datagen._arrow_column(fn(c), kind)))
    return out


def a_third_of_the_orders_on_time(chunks):
    """No line of an order whose key is a multiple of 3 is late: the late
    lines are then 42% of the table, under the planner's estimate for a
    filter (half), so an adaptive anti-join can find its build side under
    a threshold its estimate was over."""
    def receipt(c):
        key = c["l_orderkey"].to_numpy()
        got = c["l_receiptdate"].cast("int32").to_numpy()
        due = c["l_commitdate"].cast("int32").to_numpy()
        return np.where(key % 3 == 0, np.minimum(got, due), got)
    return replace_column(chunks, "l_receiptdate", "date32", receipt)


def one_supplier_an_order(chunks):
    return replace_column(chunks, "l_suppkey", "int64",
                          lambda c: c["l_orderkey"].to_numpy() % 100 + 1)


def no_line_late(chunks):
    return replace_column(
        chunks, "l_receiptdate", "date32",
        lambda c: c["l_commitdate"].cast("int32").to_numpy())


def reference(paths):
    return q21_lineitem.reference(
        datagen.read_frame(paths, q21_lineitem.COLUMNS))


def collect_counted(df):
    """(rows, launch_stats, counters' growth, span counts) of one
    ``collect()``."""
    reset_launch_stats()
    before = SHUFFLE_COUNTERS.snapshot()
    tracing.span_log.clear()
    tracing.span_log.enabled = True
    try:
        rows = df.collect()
    finally:
        tracing.span_log.enabled = False
    after = SHUFFLE_COUNTERS.snapshot()
    spans = {k: c for k, (c, _) in tracing.span_log.summary().items()}
    tracing.span_log.clear()
    return (rows, launch_stats(),
            {k: after[k] - before[k] for k in after}, spans)


def kinds(stats):
    """Launches by program kind (``join_probe_1a2b3c4d`` -> ``join_probe``)."""
    out = {}
    for name, n in stats["by_program"].items():
        k = name.rsplit("_", 1)[0]
        out[k] = out.get(k, 0) + n
    return out


# -- the engine against the benchmark's reference ----------------------------

# threshold -> the joins the plan holds and what an adaptive one chose.
# 40,000 rows: l2 is estimated at 40,000, l3 (a filter) at 20,000; the late
# lines are some 25,300 (16,900 with a third of the orders on time)
STRATEGIES = {
    "both_broadcast": (1_000_000, None, ["TpuBroadcastHashJoinExec"] * 2,
                       {}),
    "semi_shuffled_anti_adaptive_shuffled": (
        3_000, None, ["TpuAdaptiveJoinExec", "TpuShuffledHashJoinExec"],
        {"left_anti": "shuffled"}),
    "anti_adaptive_broadcast": (
        18_000, a_third_of_the_orders_on_time,
        ["TpuAdaptiveJoinExec"] * 2,
        {"left_anti": "broadcast", "left_semi": "shuffled"}),
}


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("fuse", [True, False], ids=["fused", "unfused"])
@pytest.mark.parametrize("strategy", list(STRATEGIES))
def test_engine_equals_the_benchmarks_reference(tmp_path, strategy, fuse,
                                                seed, monkeypatch):
    threshold, edit, want_joins, want_chosen = STRATEGIES[strategy]
    chosen, plan_inner = {}, TpuAdaptiveJoinExec._plan_inner

    def spy(self, right_parts):
        inner = plan_inner(self, right_parts)
        chosen[self.join_type] = self.chosen
        return inner
    monkeypatch.setattr(TpuAdaptiveJoinExec, "_plan_inner", spy)
    paths = write_lineitem(tmp_path, ROWS, BATCH, seed, edit=edit)
    want = reference(paths)
    assert len(want) > 50 and want[0][1] > want[-1][1] >= 1
    s = session(threshold, fuse)
    df = q21_lineitem.build(s.read_parquet(*paths))
    plan = df.physical_plan()
    assert sorted(type(n).__name__ for n in nodes(plan, JOINS)) == \
        sorted(want_joins), plan.tree_string()
    rows, stats, counters, spans = collect_counted(df)
    assert [tuple(r) for r in rows] == want
    assert all(type(a) is int and type(b) is int for a, b in rows)
    assert chosen == want_chosen
    assert spans.get("join.decide", 0) == len(want_chosen)
    assert spans["join.probe"] >= 2 and spans["join.build"] >= 2
    assert counters["join_candidate_pairs"] > counters["join_output_rows"] \
        > 0
    assert "join.out_of_core" not in spans


@pytest.mark.parametrize("edit,kept", [
    (one_supplier_an_order, "the semi-join keeps nothing"),
    (no_line_late, "an empty probe side")],
    ids=["one_supplier_an_order", "no_line_late"])
@pytest.mark.parametrize("threshold", [1_000_000, 3_000],
                         ids=["broadcast", "shuffled"])
def test_a_table_that_empties_a_join(tmp_path, edit, kept, threshold):
    paths = write_lineitem(tmp_path, ROWS, BATCH, 23, edit=edit)
    assert reference(paths) == [], kept
    df = q21_lineitem.build(session(threshold).read_parquet(*paths))
    assert df.collect() == []


# -- one way to read an exchange on the reduce side --------------------------

def test_a_shuffled_q21_folds_every_view_inside_its_probe_programs(
        tmp_path, fresh_program_caches):
    """1.5 batches a side: the warm query's programs hold no
    ``range_view_slice`` and no ``concat``; a reduce group costs the joins
    one ``join_probe`` and one ``join_cond``."""
    rows = BATCH + BATCH // 2
    paths = write_lineitem(tmp_path, rows, BATCH, 29)
    s = session(200)       # 12,288 rows > 8 x 200: both planned shuffled
    df = q21_lineitem.build(s.read_parquet(*paths))
    assert [type(n) for n in nodes(df.physical_plan(), JOINS)] == \
        [TpuShuffledHashJoinExec] * 2
    assert [tuple(r) for r in df.collect()] == reference(paths)   # cold
    got, stats, counters, spans = collect_counted(
        q21_lineitem.build(s.read_parquet(*paths)))
    assert [tuple(r) for r in got] == reference(paths)
    by_kind = kinds(stats)
    assert "range_view_slice" not in by_kind and "concat" not in by_kind, \
        stats["by_program"]
    groups = spans["join.probe"]
    assert by_kind["join_probe"] == by_kind["join_cond"] == groups
    assert spans["join.build"] == groups and "join.retry" not in spans
    assert counters["range_view_folds"] > 0
    assert counters["range_view_materializes"] == 0
    assert counters["reduce_concats"] == 0
    # two scans of two batches and one of... three exchanges' map sides (6
    # fused launches), 16 exchange_slice over the semi-join's output at the
    # most, a probe and a condition a group, the partial aggregate a group
    # of the anti-join, a shrink a group at the most, one combine, one sort
    assert stats["launches"] <= 6 + 16 + 4 * groups + 2, stats["by_program"]


def test_an_unconditional_shuffled_join_per_op_reads_the_same_way(
        fresh_program_caches):
    """``fuseStages=false``: a plain shuffled inner join is a per-op
    consumer of its two exchanges and folds their views too."""
    s = TpuSession({"spark.rapids.sql.enabled": "true",
                    "spark.rapids.sql.tpu.fuseStages": "false",
                    "spark.rapids.sql.join.broadcastRowThreshold": "1",
                    "spark.rapids.sql.join.adaptive.enabled": "false"})
    rng = np.random.default_rng(31)

    def frame(n, name):
        return s.create_dataframe(
            [ColumnarBatch.from_pydict(
                {"k": rng.integers(0, 500, n).tolist(),
                 name: rng.integers(0, 10**6, n).tolist()},
                Schema.of(k=T.LONG, **{name: T.LONG}))
             for _ in range(2)], num_partitions=2)

    left, right = frame(3_000, "a"), frame(700, "b")
    df = left.join(right.select(col("k").alias("rk"), col("b")),
                   on=([col("k")], [col("rk")])).select("k", "a", "b")
    assert [type(n) for n in nodes(df.physical_plan(), JOINS)] == \
        [TpuShuffledHashJoinExec]
    cold = sorted(map(tuple, df.collect()))
    got, stats, counters, spans = collect_counted(df)
    assert sorted(map(tuple, got)) == cold and len(cold) > 3_000
    by_kind = kinds(stats)
    assert "range_view_slice" not in by_kind and "concat" not in by_kind, \
        stats["by_program"]
    assert by_kind["join_probe"] == by_kind["join_expand"] == \
        spans["join.probe"]
    assert counters["range_view_materializes"] == 0
    assert counters["join_output_rows"] == len(cold)


# -- the kernel over pieces: pins, the retry at a larger capacity ------------

KV = Schema.of(k=T.LONG, v=T.LONG)


def _kv(keys, values):
    return ColumnarBatch.from_pydict(
        {"k": [int(x) for x in keys], "v": [int(x) for x in values]}, KV)


def _view_store(batch, counts):
    from spark_rapids_tpu.shuffle.transport import CacheOnlyTransport
    t = CacheOnlyTransport(len(counts))
    t.write_partitioned([(batch, np.asarray(counts, np.int64))])
    return t


def _semi_kernel(condition=True):
    from spark_rapids_tpu.expressions.core import BoundReference
    cond = (BoundReference(3, T.LONG, "rv") != BoundReference(1, T.LONG, "v")
            ) if condition else None
    return _JoinKernel([0], [0], "left_semi", KV, left_schema=KV,
                       right_schema=KV, condition=cond)


def test_a_retry_in_the_middle_of_a_join_over_pieces_leaves_no_pin():
    """An OOM raised by the probe launch, with both sides' views pinned:
    the attempt's unwind returns every pin (``retry_over_stream_pieces``
    over two lists), the second attempt pins each backing once again, and
    afterwards both backings are unpinned and spillable."""
    from spark_rapids_tpu.memory.arena import TpuRetryOOM
    lkeys = [1, 1, 2, 3, 3, 3, 4, 5]
    lt = _view_store(_kv(lkeys, [10, 11, 10, 10, 11, 12, 10, 10]), [3, 5])
    rt = _view_store(_kv([1, 1, 2, 3, 5, 9], [10, 11, 10, 10, 10, 10]),
                     [2, 4])
    lb, rb = lt._backings[0], rt._backings[0]
    for b in (lb, rb):
        b.unpin()
    base = lb._pins, rb._pins
    lp = [p for part in range(2) for p in lt.read_pieces(part)]
    rp = [p for part in range(2) for p in rt.read_pieces(part)]
    kernel = _semi_kernel()
    launch, seen = kernel._jitted_probe, []

    def failing_once(*a):
        fn = launch(*a)

        def run(l, r):
            seen.append((lb._pins, rb._pins))
            if len(seen) == 1:
                raise TpuRetryOOM("injected mid-attempt")
            return fn(l, r)
        return run
    kernel._jitted_probe = failing_once
    out = kernel(PieceSide(lp, 8), PieceSide(rp, 6))
    assert seen == [(base[0] + 1, base[1] + 1)] * 2
    assert (lb._pins, rb._pins) == base, "pin leak"
    # key 1: (10 finds 11, 11 finds 10); key 3: only v=10 on the right
    assert sorted(zip(*out.to_pydict().values())) == \
        [(1, 10), (1, 11), (3, 11), (3, 12)]
    assert lb.spill_to_host() > 0 and rb.spill_to_host() > 0
    lt.cleanup()
    rt.cleanup()


def test_pairs_past_the_first_guess_rerun_the_condition_once():
    """One key repeated on both sides of an inner join: 64 x 64 candidate
    pairs fit the pair region (sized from the probe's exact count), the
    2,016 that pass ``lv < rv`` do not fit the first output guess (the
    larger side's capacity): ``join_cond`` runs again once at the size its
    status asked for, is right, and that launch is one ``join.retry``."""
    from spark_rapids_tpu.expressions.core import BoundReference
    out_schema = Schema.of(k=T.LONG, v=T.LONG, rk=T.LONG, rv=T.LONG)
    kernel = _JoinKernel(
        [0], [0], "inner", out_schema, left_schema=KV, right_schema=KV,
        condition=BoundReference(1, T.LONG, "v")
        < BoundReference(3, T.LONG, "rv"))
    l, r = _kv([7] * 64, range(64)), _kv([7] * 64, range(64))
    reset_launch_stats()
    before = SHUFFLE_COUNTERS.snapshot()
    tracing.span_log.clear()
    tracing.span_log.enabled = True
    try:
        out = kernel(l, r)
    finally:
        tracing.span_log.enabled = False
    spans = {k: c for k, (c, _) in tracing.span_log.summary().items()}
    tracing.span_log.clear()
    after = SHUFFLE_COUNTERS.snapshot()
    assert out.host_num_rows() == 64 * 63 // 2
    rows = set(zip(*out.to_pydict().values()))
    assert rows == {(7, a, 7, b) for a in range(64) for b in range(64)
                    if a < b}
    assert kinds(launch_stats()) == {"join_probe": 1, "join_cond": 2}
    assert spans == {"join.retry": 1}
    assert after["join_candidate_pairs"] - before["join_candidate_pairs"] \
        == 64 * 64
    assert after["join_output_rows"] - before["join_output_rows"] == 2_016


# -- past the in-core bound --------------------------------------------------

def test_a_partition_over_the_bound_still_joins_out_of_core(tmp_path):
    """One shuffle partition and a batch capacity of 1,024 rows: the
    reduce partition's two sides pass ``reduce_group_in_core``, so the
    joins leave the pieces, read merged batches and sub-partition them;
    the answer is the reference's and each join records its
    ``join.out_of_core`` spans."""
    rows = 6_000
    paths = write_lineitem(tmp_path, rows, 1_024, 37)
    s = session(1, batch_rows=1_024)
    s.set_conf("spark.rapids.sql.join.adaptive.enabled", "false")
    s.set_conf("spark.sql.shuffle.partitions", 1)
    df = q21_lineitem.build(s.read_parquet(*paths))
    assert [type(n) for n in nodes(df.physical_plan(), JOINS)] == \
        [TpuShuffledHashJoinExec] * 2
    got, stats, counters, spans = collect_counted(df)
    assert [tuple(r) for r in got] == reference(paths)
    assert spans["join.out_of_core"] == 2
    assert counters["range_view_materializes"] > 0
    assert spans["join.probe"] > 2     # one a co-bucket
