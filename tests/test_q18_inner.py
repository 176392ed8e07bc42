"""TPC-H Q18's subquery (GROUP BY ``l_orderkey`` HAVING ``sum(l_quantity) >
QUANTITY``) through the engine's normal path, against the plain reference the
benchmark keeps (``benchmark/queries/q18_inner.py``), on the benchmark's own
LINEITEM at small batch capacities: several batches from two files, so orders
straddle batch and file boundaries.  And the one size rule of the reduce side
(``plan/execs/exchange.py`` ``reduce_group_in_core``): the groups the
coalescing reader builds are the groups the final aggregate takes as one
program; only a single oversized partition goes out of core.

On the CPU backend: rows and counts, never times.
"""
import collections
import os
import types

import numpy as np
import pyarrow.parquet as pq
import pytest

from benchmark import datagen
from benchmark.queries import q18_inner
from benchmark.tables import lineitem
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.columnar.column import round_up_pow2
from spark_rapids_tpu.plan.execs.aggregate import TpuHashAggregateExec
from spark_rapids_tpu.plan.execs.base import (launch_stats,
                                              reset_launch_stats)
from spark_rapids_tpu.plan.execs.exchange import (
    SharedCoalesceSpec, TpuCoalescedShuffleReaderExec, reduce_group_in_core)
from spark_rapids_tpu.shuffle.stats import SHUFFLE_COUNTERS
from spark_rapids_tpu.utils import tracing

SEEDS = (7, 2**31 + 11, 19)
COUNTERS = ("agg_partial_rows_in", "agg_partial_groups_out",
            "exchange_rows_written", "reduce_groups",
            "reduce_groups_out_of_core")


@pytest.fixture
def fresh_program_caches(monkeypatch):
    """Converged capacities and programs are remembered per signature for
    the life of the process: a test that counts launches starts clean."""
    from spark_rapids_tpu.plan import fused
    from spark_rapids_tpu.plan.execs import base
    monkeypatch.setattr(fused, "_FUSED_CAPS", collections.OrderedDict())
    monkeypatch.setattr(fused, "_FUSED_BUCKET", collections.OrderedDict())
    monkeypatch.setattr(base, "_JIT_CACHE", collections.OrderedDict())


def write_lineitem(root, rows, batch_rows, seed, edit=None):
    """The benchmark's LINEITEM as two Parquet files with row groups of
    ``batch_rows`` (one scan batch each); ``edit(chunks)`` may rewrite the
    list of per-row-group Arrow tables before they are written."""
    prepared = lineitem.prepare(seed, rows, rows / 6_001_215)
    chunks = [datagen._chunk_table(lineitem, seed, cid,
                                   min(batch_rows, rows - lo), batch_rows,
                                   prepared)
              for cid, lo in enumerate(range(0, rows, batch_rows))]
    if edit is not None:
        chunks = edit(chunks)
    half = -(-len(chunks) // 2)
    paths = []
    for i, part in enumerate((chunks[:half], chunks[half:])):
        paths.append(os.path.join(str(root), f"lineitem-{i}.parquet"))
        with pq.ParquetWriter(paths[-1], part[0].schema) as w:
            for t in part:
                w.write_table(t, row_group_size=len(t))
    return paths


def session(batch_rows, fuse=True):
    return TpuSession({"spark.rapids.sql.enabled": "true",
                       "spark.rapids.sql.tpu.fuseStages": str(fuse).lower(),
                       "spark.rapids.sql.batchSizeRows": str(batch_rows),
                       "spark.rapids.sql.reader.batchSizeRows":
                       str(batch_rows)})


def reference(paths, quantity):
    return sorted(q18_inner.reference(
        datagen.read_frame(paths, q18_inner.COLUMNS), quantity))


def nodes(plan, kind):
    out, stack = [], [plan]
    while stack:
        n = stack.pop()
        if isinstance(n, kind):
            out.append(n)
        stack.extend(n.children)
        stack.extend(getattr(n, "chain", ()))
    return out


def collect_counted(df):
    """(sorted rows, launch_stats, the five counters, span counts) of one
    ``collect()``."""
    reset_launch_stats()
    before = SHUFFLE_COUNTERS.snapshot()
    tracing.span_log.clear()
    tracing.span_log.enabled = True
    try:
        rows = sorted(map(tuple, df.collect()))
    finally:
        tracing.span_log.enabled = False
    after = SHUFFLE_COUNTERS.snapshot()
    spans = {k: c for k, (c, _) in tracing.span_log.summary().items()}
    tracing.span_log.clear()
    return (rows, launch_stats(),
            {k: after[k] - before[k] for k in COUNTERS}, spans)


def groups_a_batch(paths):
    keys = datagen.read_frame(paths, ("l_orderkey",))["l_orderkey"]
    sizes = [md.num_rows for p in paths
             for md in [pq.ParquetFile(p).metadata.row_group(i)
                        for i in range(pq.ParquetFile(p).metadata
                                       .num_row_groups)]]
    out, lo = [], 0
    for n in sizes:
        out.append(int(keys.iloc[lo:lo + n].nunique()))
        lo += n
    return out


# -- the engine against the benchmark's reference ----------------------------

def order_sums_of_exactly_300_and_301(chunks):
    """Two orders of seven lines get the quantities 50 x 5 + 25 + 25 (300,
    which ``> 300`` leaves out) and 50 x 5 + 25 + 26 (301, kept); the first
    of them is one that a row-group boundary cuts where there is one."""
    keys = np.concatenate([c["l_orderkey"].to_numpy() for c in chunks])
    qty = np.concatenate([
        np.asarray(c["l_quantity"].cast("float64").to_numpy() * 100
                   ).round().astype(np.int64) for c in chunks])
    bounds = np.cumsum([len(c) for c in chunks])[:-1]
    uniq, first, counts = np.unique(keys, return_index=True,
                                    return_counts=True)
    seven = [(int(f), int(k)) for k, f, n in zip(uniq, first, counts)
             if n == 7]
    cut = [fk for fk in seven
           if any(fk[0] < b < fk[0] + 7 for b in bounds)]
    chosen = (cut + seven)[:1] + [fk for fk in seven
                                  if fk not in cut][-1:]
    assert len(chosen) == 2 and chosen[0] != chosen[1]
    for (f, _), last in zip(chosen, (2500, 2600)):
        qty[f:f + 7] = [5000] * 5 + [2500, last]
    out, lo = [], 0
    for c in chunks:
        i = c.schema.get_field_index("l_quantity")
        out.append(c.set_column(i, "l_quantity", datagen._arrow_column(
            qty[lo:lo + len(c)], "decimal(12,2)")))
        lo += len(c)
    order_sums_of_exactly_300_and_301.keys = [k for _, k in chosen]
    return out


@pytest.mark.parametrize("fuse", [True, False], ids=["fused", "unfused"])
@pytest.mark.parametrize("quantity", [0.0, 300.0])
@pytest.mark.parametrize("batch_rows,rows", [
    # some 2,050 groups a batch: under GROUP_CAP_DEFAULT, nothing discarded
    (8_192, 40_000),
    # some 4,100 a batch, just over it (the test reads the counts from
    # the data)
    (16_384, 70_000),
    # some 8,200: over it, one discarded launch a process
    (32_768, 140_000)])
def test_engine_equals_the_benchmarks_reference(tmp_path, batch_rows, rows,
                                                quantity, fuse,
                                                fresh_program_caches):
    from spark_rapids_tpu.plan.fused import GROUP_CAP_DEFAULT
    paths = write_lineitem(tmp_path, rows, batch_rows, 2**31 + 3,
                           edit=order_sums_of_exactly_300_and_301)
    k300, k301 = order_sums_of_exactly_300_and_301.keys
    want = reference(paths, quantity)
    df = q18_inner.build(session(batch_rows, fuse).read_parquet(*paths),
                         quantity)
    got, stats, counters, spans = collect_counted(df)
    assert got == want
    assert all(type(k) is int and type(v) is float for k, v in got)
    if quantity == 300.0:
        assert (k301, 301.0) in got and k300 not in dict(got)
    else:
        assert dict(got)[k300] == 300.0 and len(got) > rows // 5
    per_batch = groups_a_batch(paths)
    assert counters["exchange_rows_written"] == sum(per_batch)
    assert counters["reduce_groups_out_of_core"] == 0
    assert counters["reduce_groups"] == spans["agg.final"] >= 1
    assert "agg.out_of_core" not in spans
    if fuse:
        assert counters["agg_partial_rows_in"] == rows
        assert counters["agg_partial_groups_out"] == sum(per_batch)
        # the first batch that outgrows the default costs one launch, and
        # every later one starts at the input's capacity
        over = max(per_batch) > GROUP_CAP_DEFAULT
        assert stats["discarded"] == ({"group_cap": 1} if over else {})
        assert spans.get("fused.discard", 0) == int(over)
        assert over == (batch_rows > 8_192), per_batch


# -- the one rule -------------------------------------------------------------

class _Exchange:
    """What ``SharedCoalesceSpec.groups`` reads of an exchange."""
    _epoch = 0

    def __init__(self, counts):
        self.counts = list(counts)

    def _materialize(self):
        return self

    def partition_row_counts(self):
        return list(self.counts)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("sides", [1, 2], ids=["aggregate", "join"])
def test_no_group_the_reader_builds_is_refused_unless_a_single_partition(
        seed, sides):
    """Seeded random per-partition counts (two co-partitioned sides for a
    join's shared spec): every group keeps ``reduce_group_in_core`` or is
    one partition, is as large as the rule lets it be, and together they
    are all partitions in order."""
    rng = np.random.default_rng(seed)
    for _ in range(200):
        target = int(rng.integers(1, 5000))
        n = int(rng.integers(1, 64))
        scale = int(rng.choice([1, target // 8 + 1, target, 3 * target]))
        spec = SharedCoalesceSpec(target)
        exchanges = [_Exchange(rng.integers(0, scale + 1, n))
                     for _ in range(sides)]
        for ex in exchanges:
            spec.register(ex)
        counts = np.sum([ex.counts for ex in exchanges], axis=0)
        groups = spec.groups()
        assert [p for g in groups for p in g] == list(range(n))
        for g, nxt in zip(groups, groups[1:] + [None]):
            rows = int(counts[g].sum())
            assert reduce_group_in_core(rows, target) or len(g) == 1, \
                (target, counts.tolist(), groups)
            if nxt is not None:     # greedy: the next partition did not fit
                assert not reduce_group_in_core(
                    rows + int(counts[nxt[0]]), target)


def _run_plan(plan):
    """Every row of a physical plan executed partition by partition, as
    sorted ``(int, float)`` pairs; the plan is cleaned up."""
    try:
        return sorted((int(k), float(v))
                      for p in range(plan.num_partitions())
                      for b in plan.execute_partition(p)
                      for k, v in zip(*(c.to_pylist(b.host_num_rows())
                                        for c in b.columns)))
    finally:
        plan.cleanup()


def _planned(tmp_path, seed, batch_rows, rows):
    paths = write_lineitem(tmp_path, rows, batch_rows, seed)
    sess = session(batch_rows)
    df = q18_inner.build(sess.read_parquet(*paths), 0.0)
    return paths, df


@pytest.mark.parametrize("seed", SEEDS)
def test_one_and_a_half_batches_of_partial_rows_take_one_program_a_group(
        tmp_path, seed):
    """The cell's shape in small: six scan batches whose partial aggregates
    hand on 1.5 batch capacities of rows.  Every reduce group is one
    ``agg_combine``; nothing is sliced by a launch of its own, nothing
    sub-partitioned."""
    batch_rows = 8_192
    paths, df = _planned(tmp_path, seed, batch_rows, 6 * batch_rows - 700)
    want = reference(paths, 0.0)
    assert 1.4 * batch_rows < len(want) < 1.6 * batch_rows
    collect_counted(df)                       # a process's first query
    got, stats, counters, spans = collect_counted(df)
    assert got == want
    kinds = collections.Counter(name.rsplit("_", 1)[0]
                                for name, n in stats["by_program"].items()
                                for _ in range(n))
    assert not [k for k in kinds if k.startswith("ooc")
                or k in ("range_view_slice", "concat", "agg_merge",
                         "agg_finalize")], kinds
    groups = counters["reduce_groups"]
    assert groups == 2 and counters["reduce_groups_out_of_core"] == 0
    assert kinds["agg_combine"] == groups == spans["agg.final"]
    assert kinds["fused_agg_slice"] == 6 and stats["discarded"] == {}
    # a combine and a filter a group; at threshold 0 the HAVING keeps every
    # row, so its ``maybe_shrink`` waits for the count and regathers nothing
    assert stats["launches"] == 6 + 2 * groups
    assert spans["batch.shrink"] == groups


@pytest.mark.parametrize("where", ["under", "equal", "just_over"])
def test_partial_rows_at_the_in_core_bound(tmp_path, where):
    """The bound set so that the first three partitions' rows are under it,
    equal to it, or one over it: the reader closes the first group after
    three partitions, or after two, and every group it builds is one
    program."""
    batch_rows = 8_192
    paths, df = _planned(tmp_path, 23, batch_rows, 30_000)
    plan = df.physical_plan()
    reader, = nodes(plan, TpuCoalescedShuffleReaderExec)
    agg, = [a for a in nodes(plan, TpuHashAggregateExec)
            if a.mode == "final"]
    counts = reader.children[0].partition_row_counts()
    three = sum(counts[:3])
    bound = {"under": three + 1, "equal": three, "just_over": three - 1}[where]
    assert max(counts) < bound
    reader.spec.target_rows = agg.target_capacity = bound
    reader.spec._groups = None
    groups = reader.spec.groups()
    assert len(groups[0]) == (2 if where == "just_over" else 3)
    before = SHUFFLE_COUNTERS.snapshot()
    got = _run_plan(plan)
    after = SHUFFLE_COUNTERS.snapshot()
    assert got == reference(paths, 0.0)
    assert after["reduce_groups"] - before["reduce_groups"] == len(groups)
    assert after["reduce_groups_out_of_core"] == \
        before["reduce_groups_out_of_core"]


def test_a_single_partition_over_the_bound_still_merges_out_of_core(
        tmp_path):
    """A bound under every partition's rows: each partition is a group of
    its own, refused by the rule, and takes the sub-partition merge, one
    ``agg.out_of_core`` span a group; the answers are the reference's."""
    batch_rows = 8_192
    paths, df = _planned(tmp_path, 29, batch_rows, 30_000)
    plan = df.physical_plan()
    reader, = nodes(plan, TpuCoalescedShuffleReaderExec)
    agg, = [a for a in nodes(plan, TpuHashAggregateExec)
            if a.mode == "final"]
    counts = reader.children[0].partition_row_counts()
    bound = min(counts) // 2
    assert bound > 16
    reader.spec.target_rows = agg.target_capacity = bound
    reader.spec._groups = None
    assert reader.spec.groups() == [[p] for p in range(16)]
    before = SHUFFLE_COUNTERS.snapshot()
    tracing.span_log.clear()
    tracing.span_log.enabled = True
    try:
        got = _run_plan(plan)
    finally:
        tracing.span_log.enabled = False
    after = SHUFFLE_COUNTERS.snapshot()
    spans = {k: c for k, (c, _) in tracing.span_log.summary().items()}
    tracing.span_log.clear()
    assert got == reference(paths, 0.0)
    assert after["reduce_groups_out_of_core"] \
        - before["reduce_groups_out_of_core"] == 16 == spans["agg.out_of_core"]
    assert after["reduce_groups"] - before["reduce_groups"] == 16
    # each bucket's merge and finalize is the final aggregate's own work
    assert spans["agg.final"] >= 2 * 16


# -- the discarded launch ------------------------------------------------------

def test_the_discarded_launch_is_in_a_processs_first_query_only(
        tmp_path, fresh_program_caches):
    batch_rows = 32_768
    paths, df = _planned(tmp_path, 31, batch_rows, 100_000)
    _, first, _, first_spans = collect_counted(df)
    got, second, _, second_spans = collect_counted(df)
    assert got == reference(paths, 0.0)
    assert first["discarded"] == {"group_cap": 1}
    assert first_spans["fused.discard"] == 1
    assert second["discarded"] == {} and "fused.discard" not in second_spans
    assert first["launches"] == second["launches"] + 1


# -- views of one program at one capacity -------------------------------------

def test_views_of_one_program_are_sliced_at_one_capacity():
    """Partition sizes a little under and a little over a power of two
    (16,412 rows a view in the cell) are one program, not one a data set."""
    from spark_rapids_tpu.shuffle.transport import (RangeView,
                                                    views_at_one_capacity)
    backing = types.SimpleNamespace()
    views = [RangeView(backing, 0, n, round_up_pow2(n))
             for n in (120, 130, 127, 129)]
    assert {v.capacity for v in views} == {128, 256}
    same = views_at_one_capacity(views + ["a batch"])
    assert [v.capacity for v in same[:-1]] == [256] * 4
    assert same[-1] == "a batch" and same[1] is views[1]
    assert [(v.start, v.count) for v in same[:-1]] == \
        [(0, 120), (0, 130), (0, 127), (0, 129)]
