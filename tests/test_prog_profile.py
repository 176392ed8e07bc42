"""Per-program wall-clock/rows attribution (plan/execs/base
enable_launch_profile — the engine mode behind `bench.py --profile`).

The profiler must (1) attribute execution to the program that ran it
(dispatches block through block_until_ready while armed), (2) record
launches and output row capacities per program key, (3) cost nothing
when disarmed (the default), and (4) surface through bench.py as
a `prog_profile` artifact entry.
"""
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema
from spark_rapids_tpu.expressions import count, sum_
from spark_rapids_tpu.plan.execs.base import (
    _LaunchStats,
    _out_row_capacity,
    disable_launch_profile,
    enable_launch_profile,
    launch_stats,
    reset_launch_stats,
)

SCHEMA = Schema.of(k=T.INT, v=T.DOUBLE)


def _batch(n=4096, seed=3):
    rng = np.random.RandomState(seed)
    return ColumnarBatch.from_pydict(
        {"k": (1 + rng.randint(0, 17, n)).tolist(),
         "v": np.round(rng.uniform(-5, 5, n), 3).tolist()}, SCHEMA)


def _query(s):
    df = s.create_dataframe([_batch()], num_partitions=2)
    return (df.group_by("k").agg(sum_("v").alias("sv"),
                                 count().alias("n"))
            .order_by("k"))


def test_attribution_records_launches_ns_and_rows():
    s = TpuSession({"spark.rapids.sql.enabled": "true"})
    q = _query(s)
    q.collect()                      # warm: compile once
    enable_launch_profile()
    try:
        rows = q.collect()
    finally:
        prof = disable_launch_profile()
    assert rows
    assert prof, "no programs attributed"
    for k, v in prof.items():
        assert v["launches"] >= 1, (k, v)
        assert v["ns"] >= 0, (k, v)
        assert v["rows"] >= 0, (k, v)
    # the aggregate's program keys are attributable by name
    assert any("agg" in k or "fused" in k for k in prof), list(prof)
    # a second disable returns empty (armed state cleared)
    assert disable_launch_profile() == {}


def test_disarmed_by_default_and_counting_unaffected():
    assert _LaunchStats.profile is None
    s = TpuSession({"spark.rapids.sql.enabled": "true"})
    q = _query(s)
    q.collect()
    reset_launch_stats()
    q.collect()
    stats = launch_stats()
    assert stats["launches"] >= 1 and stats["programs"] >= 1
    assert _LaunchStats.profile is None


def test_out_row_capacity_walks_result_pytrees():
    b = _batch(64)
    cap = b.capacity
    assert _out_row_capacity(b) == cap
    assert _out_row_capacity((b, b)) == 2 * cap
    assert _out_row_capacity({"x": b, "y": (b, None)}) == 2 * cap
    assert _out_row_capacity(None) == 0
    assert _out_row_capacity(123) == 0


def test_bench_query_emits_prog_profile(monkeypatch):
    """bench.py's --profile plumbing: with the env flag set, a query's
    result carries a prog_profile list sorted by wall time, and names the
    device it ran on."""
    import bench

    monkeypatch.setenv("SPARK_RAPIDS_TPU_BENCH_PROGPROF", "1")
    monkeypatch.setenv("TPU_ORACLE_CACHE", "0")
    out = bench._run_query("q6", 65536)
    assert out["query"] == "q6"
    assert out["device"]["platform"] == "cpu" and "engine_s" in out
    prof = out.get("prog_profile")
    assert prof, out.keys()
    assert all({"program", "launches", "ns", "rows"} <= set(e)
               for e in prof)
    ns = [e["ns"] for e in prof]
    assert ns == sorted(ns, reverse=True), "not sorted by wall time"
