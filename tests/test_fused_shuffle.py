"""Fusion THROUGH shuffled joins + pipelined exchanges (plan/fused.py
across-shuffle path; ROADMAP open item 1).

Differential discipline: every fused-across-shuffle result is checked
against the per-op engine (fuseStages=false, under which a final
aggregate still folds its exchange's pieces) and against the CPU oracle.  The
counter-pinned tests prove the perf CLAIM: one fused program per
coalesced reduce partition group (merge + probe + aggregate + the next
exchange's partition step), and a stage hand-off that never drains.
"""
import numpy as np
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema
from spark_rapids_tpu.expressions import col, count, lit, sum_
from tests.test_queries import assert_tpu_cpu_equal

FACT = Schema.of(k=T.INT, sk=T.STRING, v=T.DOUBLE, tag=T.STRING)
DIM2 = Schema.of(dk=T.INT, dsk=T.STRING, w=T.DOUBLE)


def _fact(n=6000, seed=11, nkeys=40, skew_frac=0.0, null_frac=0.15):
    """Skew/null/string-key fact: ``skew_frac`` of the rows pile onto ONE
    hot key; ``null_frac`` of the join keys are NULL (must never match)."""
    rng = np.random.RandomState(seed)
    k = 1 + rng.randint(0, nkeys, n)
    if skew_frac:
        k[rng.uniform(size=n) < skew_frac] = 7
    nulls = rng.uniform(size=n) < null_frac
    ks = [None if dead else int(x) for x, dead in zip(k, nulls)]
    return ColumnarBatch.from_pydict(
        {"k": ks,
         "sk": [None if dead else f"key-{int(x) % nkeys}-{'x' * (x % 9)}"
                for x, dead in zip(k, nulls)],
         "v": np.round(rng.uniform(-10, 10, n), 3).tolist(),
         "tag": [f"t{int(x) % 5}" for x in rng.randint(0, 1000, n)]}, FACT)


def _dim(n=3000, seed=5, nkeys=40, null_frac=0.1):
    rng = np.random.RandomState(seed)
    k = 1 + rng.randint(0, nkeys, n)
    nulls = rng.uniform(size=n) < null_frac
    return ColumnarBatch.from_pydict(
        {"dk": [None if dead else int(x) for x, dead in zip(k, nulls)],
         "dsk": [None if dead else f"key-{int(x) % nkeys}-{'x' * (x % 9)}"
                 for x, dead in zip(k, nulls)],
         "w": np.round(rng.uniform(0, 4, n), 3).tolist()}, DIM2)


#: broadcastRowThreshold=1 forces every join SHUFFLED — the shape under
#: test; adaptive off so the plan is deterministic at this tiny scale
SHUFFLED = {"spark.rapids.sql.enabled": "true",
            "spark.rapids.sql.join.broadcastRowThreshold": "1",
            "spark.rapids.sql.join.adaptive.enabled": "false"}


def _sessions():
    """(fused, per-op) engines over the same forced-shuffle conf."""
    return (
        TpuSession(dict(SHUFFLED)),
        TpuSession(dict(SHUFFLED,
                        **{"spark.rapids.sql.tpu.fuseStages": "false"})),
    )


def _join_agg_query(s, fact_batches, dim_batches, key="k", how="inner"):
    fact = s.create_dataframe(fact_batches, num_partitions=2)
    dim = s.create_dataframe(dim_batches, num_partitions=2)
    on = ([col(key)], [col("dk" if key == "k" else "dsk")])
    df = fact.join(dim, on=on, how=how)
    cols = ["tag", "v"] + ([] if how in ("left_semi", "left_anti")
                           else ["w"])
    df = df.select(*cols)
    aggs = [sum_("v").alias("sv"), count().alias("n")]
    if how not in ("left_semi", "left_anti"):
        aggs.append(sum_("w").alias("sw"))
    return df.group_by("tag").agg(*aggs).order_by("tag")


def _norm(rows):
    return [tuple(round(v, 6) if isinstance(v, float) else v for v in r)
            for r in rows]


@pytest.mark.parametrize("key", [
    pytest.param("k", marks=pytest.mark.slow),   # tier-1 keeps the string
    "sk",                                        # variant (richer path)
])
def test_shuffled_join_agg_differential(key):
    """Fused-across-shuffle vs per-op vs oracle, over null-heavy int and
    STRING join keys."""
    fact = [_fact(seed=1), _fact(seed=2, n=3000)]
    dim = [_dim(seed=3)]
    fused_s, perop_s = _sessions()
    rows_f = _join_agg_query(fused_s, fact, dim, key=key).collect()
    rows_p = _join_agg_query(perop_s, fact, dim, key=key).collect()
    assert _norm(rows_f) == _norm(rows_p)
    assert rows_f
    assert_tpu_cpu_equal(
        lambda s: _join_agg_query(s, fact, dim, key=key),
        ignore_order=False)


def test_shuffled_join_skew_differential():
    """A hot build-side key (skew) through the fused path."""
    fact = [_fact(seed=21, skew_frac=0.5)]
    dim = [_dim(seed=22)]
    fused_s, perop_s = _sessions()
    rows_f = _join_agg_query(fused_s, fact, dim).collect()
    rows_p = _join_agg_query(perop_s, fact, dim).collect()
    assert _norm(rows_f) == _norm(rows_p)
    assert rows_f


@pytest.mark.parametrize("how", ["left", "left_semi", "left_anti"])
def test_shuffled_join_types_across_shuffle(how):
    fact = [_fact(seed=31, n=2500)]
    dim = [_dim(seed=32, n=900)]
    fused_s, perop_s = _sessions()
    rows_f = _join_agg_query(fused_s, fact, dim, how=how).collect()
    rows_p = _join_agg_query(perop_s, fact, dim, how=how).collect()
    assert _norm(rows_f) == _norm(rows_p)
    assert rows_f
    assert_tpu_cpu_equal(
        lambda s: _join_agg_query(s, fact, dim, how=how),
        ignore_order=False)


def test_plan_fuses_shuffled_join():
    fused_s, _perop_s = _sessions()
    fact = [_fact(seed=41)]
    dim = [_dim(seed=42)]
    plan_f = _join_agg_query(fused_s, fact, dim).physical_plan()
    tree_f = plan_f.tree_string()
    assert "TpuFusedSegment" in tree_f
    # the shuffled join is INSIDE a segment (a chain "* ..." member)
    assert "* TpuShuffledHashJoin" in tree_f


def test_q25_shape_one_program_per_reduce_partition():
    """The acceptance pin: on the q25 shape (fact x fact chain into a
    grouped final aggregate), every coalesced reduce partition runs ONE
    fused program — merge + probe + partial agg + the next exchange's
    partition step — and the final aggregate folds its merge the same
    way.  Launches collapse versus the per-op plan."""
    from spark_rapids_tpu.cluster.stats import (
        local_shuffle_counters, reset_local_shuffle_counters)
    from spark_rapids_tpu.plan.execs.base import (
        launch_stats, reset_launch_stats)
    fact = [_fact(seed=51, n=5000, null_frac=0.0),
            _fact(seed=52, n=5000, null_frac=0.0)]
    dim = [_dim(seed=53, n=4000, null_frac=0.0)]

    stats = {}
    for name, s in zip(("fused", "perop"), _sessions()):
        q = _join_agg_query(s, fact, dim)
        q.collect()                    # warm: compile + converge caps
        reset_launch_stats()
        reset_local_shuffle_counters()
        q.collect()
        stats[name] = (launch_stats(), local_shuffle_counters())

    fused_launch, fused_sc = stats["fused"]
    perop_launch, _ = stats["perop"]
    # ONE fused program per coalesced reduce group: at this scale the
    # shared spec coalesces all 16 partitions into one group per stage —
    # one program for the join stage, one for the final-agg merge fold
    assert fused_sc["fused_reduce_programs"] == 2, fused_sc
    assert fused_sc["fused_reduce_fallbacks"] == 0
    # the per-op reduce side pays merge + probe + expand + agg programs
    # per partition; fused must collapse well below half of it
    assert fused_launch["launches"] * 2 <= perop_launch["launches"], stats
    assert fused_launch["programs"] < perop_launch["programs"], stats


@pytest.mark.slow
def test_oversized_build_falls_back_out_of_core():
    """A co-partition build side beyond the fuse limit (single hot build
    key + tiny batch target) must take the per-op out-of-core fallback —
    counter-proven — and still match the per-op engine."""
    from spark_rapids_tpu.cluster.stats import (
        local_shuffle_counters, reset_local_shuffle_counters)
    conf = dict(SHUFFLED, **{"spark.rapids.sql.batchSizeRows": "512",
                             "spark.sql.shuffle.partitions": "4"})
    fact = [_fact(seed=61, n=2000, skew_frac=1.0, null_frac=0.0)]
    dim = [_dim(seed=62, n=2000, null_frac=0.0)]
    # every dim row onto the hot key too: ONE build partition >> target
    hot = ColumnarBatch.from_pydict(
        {"dk": [7] * 1500,
         "dsk": ["key-7-xxxxxxx"] * 1500,
         "w": np.round(np.random.RandomState(63).uniform(0, 4, 1500),
                       3).tolist()}, DIM2)
    reset_local_shuffle_counters()
    fused_s = TpuSession(conf)
    rows_f = _join_agg_query(fused_s, fact, [hot]).collect()
    sc = local_shuffle_counters()
    assert sc["fused_reduce_fallbacks"] >= 1, sc
    perop_s = TpuSession(dict(
        conf, **{"spark.rapids.sql.tpu.fuseStages": "false"}))
    rows_p = _join_agg_query(perop_s, fact, [hot]).collect()
    assert _norm(rows_f) == _norm(rows_p)
    assert rows_f


def test_map_side_single_op_chain_fuses_under_exchange():
    """Satellite: a single project/filter between a scan and an exchange
    becomes a segment, so the exchange's fused map path runs op +
    key-append + partition as ONE program per map batch."""
    s = _sessions()[0]
    fact = s.create_dataframe([_fact(seed=71)], num_partitions=2)
    df = (fact.select("k", "v", "tag")
          .group_by("tag").agg(sum_("v").alias("sv")).order_by("tag"))
    tree = df.physical_plan().tree_string()
    lines = tree.splitlines()
    ix = next(i for i, ln in enumerate(lines)
              if "TpuShuffleExchange" in ln and "keys=" in ln)
    assert "TpuFusedSegment" in lines[ix + 1], tree
    assert_tpu_cpu_equal(
        lambda sess: (sess.create_dataframe([_fact(seed=71)],
                                            num_partitions=2)
                      .select("k", "v", "tag")
                      .group_by("tag").agg(sum_("v").alias("sv"))
                      .order_by("tag")),
        ignore_order=False)


@pytest.mark.slow
def test_pipelined_exchange_overlap_counters():
    """Two consecutive exchanges on the WIRE transport: the map side of
    stage k+1 must overlap stage k's reduce (pipeline_overlap_ns > 0)
    and the stage hand-off must not drain beyond pipeline fill
    (stage_drain_ns ≈ 0: items flow the moment they are produced)."""
    from spark_rapids_tpu.cluster.stats import (
        local_shuffle_counters, reset_local_shuffle_counters)
    conf = dict(SHUFFLED, **{"spark.rapids.shuffle.mode": "MULTITHREADED"})
    fact = [_fact(seed=81, n=20000, null_frac=0.0),
            _fact(seed=82, n=20000, null_frac=0.0)]
    dim = [_dim(seed=83, n=8000, null_frac=0.0)]
    s = TpuSession(conf)
    q = _join_agg_query(s, fact, dim)
    q.collect()                       # warm compiles out of the window
    reset_local_shuffle_counters()
    rows = q.collect()
    sc = local_shuffle_counters()
    assert rows
    assert sc["exchange_stages"] >= 3, sc          # two join sides + agg
    assert sc["pipeline_overlap_ns"] > 0, sc
    # ≈0: an order of magnitude under the proven overlap (scheduling
    # jitter allowance; a barriered hand-off would dwarf the overlap)
    assert sc["stage_drain_ns"] < max(sc["pipeline_overlap_ns"], 10**7), sc


def test_adaptive_join_runtime_decision_fuses():
    """An ambiguous-zone join that decides SHUFFLED at runtime re-applies
    coalescing + fusion over the tree it builds (the plan-time passes
    never saw it) — counter-proven."""
    from spark_rapids_tpu.cluster.stats import (
        local_shuffle_counters, reset_local_shuffle_counters)
    fact = [_fact(seed=95, n=6000, null_frac=0.0)]
    dim = [_dim(seed=96, n=3000, null_frac=0.0)]
    # dim (3000 rows) sits in (threshold, 8x threshold]: adaptive plans,
    # runtime build count 3000 > 1000 decides shuffled
    conf = {"spark.rapids.sql.enabled": "true",
            "spark.rapids.sql.join.broadcastRowThreshold": "1000"}
    s = TpuSession(conf)
    q = _join_agg_query(s, fact, dim)
    tree = q.physical_plan().tree_string()
    assert "TpuAdaptiveJoin" in tree
    reset_local_shuffle_counters()
    rows = q.collect()
    sc = local_shuffle_counters()
    assert sc["fused_reduce_programs"] >= 1, sc
    assert rows
    assert_tpu_cpu_equal(lambda sess: _join_agg_query(sess, fact, dim),
                         ignore_order=False)


def test_pipelined_parquet_scan_does_not_deadlock(tmp_path):
    """Regression (found by the end-to-end verify drive): a pipelined
    wire-mode exchange whose producer thread reaches a PARQUET scan used
    to acquire a SECOND device-semaphore slot — with every slot held by
    engine tasks blocked on the producer's own queue, the query
    deadlocked.  A producer works under the spawning task's permit and
    takes none: only plan/engine.py acquires (PR 29)."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    rng = np.random.RandomState(7)
    for side, nrows, cols in (
            ("fact", 4000, lambda i: {"k": int(1 + i % 50),
                                      "v": float(i % 97) / 7.0,
                                      "tag": f"t{i % 5}"}),
            ("dim", 800, lambda i: {"dk": int(1 + i % 50),
                                    "w": float(i % 13)})):
        rows = [cols(int(x)) for x in rng.permutation(nrows)]
        for part in range(2):
            pq.write_table(
                pa.Table.from_pylist(rows[part::2]),
                str(tmp_path / f"{side}{part}.parquet"))

    conf = dict(SHUFFLED, **{"spark.rapids.shuffle.mode": "MULTITHREADED"})
    s = TpuSession(conf)
    f = s.read_parquet(str(tmp_path / "fact0.parquet"),
                       str(tmp_path / "fact1.parquet"))
    d = s.read_parquet(str(tmp_path / "dim0.parquet"),
                       str(tmp_path / "dim1.parquet"))
    df = (f.join(d, on=([col("k")], [col("dk")]))
          .group_by("tag").agg(sum_("v").alias("sv"), count().alias("n"))
          .order_by("tag"))
    rows = df.collect()   # used to hang here
    assert len(rows) == 5
    cpu = TpuSession({"spark.rapids.sql.enabled": "false"})
    fc = cpu.read_parquet(str(tmp_path / "fact0.parquet"),
                          str(tmp_path / "fact1.parquet"))
    dc = cpu.read_parquet(str(tmp_path / "dim0.parquet"),
                          str(tmp_path / "dim1.parquet"))
    exp = (fc.join(dc, on=([col("k")], [col("dk")]))
           .group_by("tag").agg(sum_("v").alias("sv"), count().alias("n"))
           .order_by("tag")).collect()
    assert _norm(rows) == _norm(exp)


def test_shared_coalesce_spec_memoizes_per_epoch():
    """Satellite: groups() computes once per exchange epoch — repeated
    reader calls reuse the memo, and a cleanup (epoch bump) recomputes
    from the fresh map statistics instead of serving stale groups."""
    from spark_rapids_tpu.plan.execs.exchange import SharedCoalesceSpec

    class FakeExchange:
        def __init__(self, counts):
            self.counts = counts
            self._epoch = 0
            self.calls = 0

        def _materialize(self):
            pass

        def partition_row_counts(self):
            self.calls += 1
            return list(self.counts)

    ex = FakeExchange([10, 10, 10, 10])
    spec = SharedCoalesceSpec(target_rows=20)
    spec.register(ex)
    g1 = spec.groups()
    assert g1 == [[0, 1], [2, 3]]
    assert spec.groups() is g1          # memoized: no re-plan per reader
    assert ex.calls == 1
    # new epoch, new statistics: the memo must NOT survive
    ex.counts = [40, 1, 1, 1]
    ex._epoch += 1
    g2 = spec.groups()
    assert ex.calls == 2
    assert g2 == [[0], [1, 2, 3]]


def test_dim_build_fold_gated_by_raw_build_size():
    """Review pin (r11): the broadcast planner sizes builds by their
    POST-chain estimate, so a raw build far larger than its filtered
    output can still plan as broadcast — folding its filter in-trace
    would re-filter the raw build on every program call.  Past the
    consumer join's batch target the chain applies EAGERLY once (a
    standalone 'buildchain' program); small raw builds keep the
    in-trace fold (no such program).  Rows match per-op either way."""
    from spark_rapids_tpu.expressions import col as _col, lit as _lit
    from spark_rapids_tpu.plan.execs.base import (
        launch_stats, reset_launch_stats)

    def q(s, dim_rows):
        f = s.create_dataframe([_fact(seed=81, n=2000, null_frac=0.0)],
                               num_partitions=2)
        d = s.create_dataframe([_dim(seed=82, n=dim_rows, null_frac=0.0)],
                               num_partitions=1)
        return (f.join(d.filter(_col("w") < _lit(2.0)),
                       on=([_col("k")], [_col("dk")]))
                .group_by("tag").agg(sum_("v").alias("sv"),
                                     count().alias("n"))
                .order_by("tag"))

    conf = {"spark.rapids.sql.enabled": "true",
            "spark.rapids.sql.batchSizeRows": "1024"}

    def profiled_collect(s, dim_rows):
        df = q(s, dim_rows)
        reset_launch_stats()
        rows = df.collect()
        return rows, launch_stats()["by_program"]

    # raw build 3000 rows (cap 4096) > 1024 target: eager one-shot chain
    rows_big, prof_big = profiled_collect(TpuSession(dict(conf)), 3000)
    assert any(k.startswith("buildchain_") for k in prof_big), \
        sorted(prof_big)[:6]
    # raw build 600 rows (cap <= 1024): in-trace fold, no standalone run
    rows_small, prof_small = profiled_collect(TpuSession(dict(conf)), 600)
    assert not any(k.startswith("buildchain_") for k in prof_small), \
        sorted(k for k in prof_small if k.startswith("buildchain"))
    perop = TpuSession(dict(
        conf, **{"spark.rapids.sql.tpu.fuseStages": "false"}))
    assert _norm(rows_big) == _norm(q(perop, 3000).collect())
    assert _norm(rows_small) == _norm(q(perop, 600).collect())
    assert rows_big and rows_small
