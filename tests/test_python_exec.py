"""Arrow-batch Python transform tests (pandas-UDF exec analog)."""
import pyarrow as pa

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import Schema
from spark_rapids_tpu.expressions import col, lit, sum_
from tests.test_queries import assert_tpu_cpu_equal, source


OUT_SCHEMA = Schema.of(k=T.INT, doubled=T.LONG)


def double_v(table: pa.Table) -> pa.Table:
    import pyarrow.compute as pc
    return pa.table({
        "k": table.column("k"),
        "doubled": pc.multiply(table.column("v"), pa.scalar(2, pa.int64())),
    })


def test_map_batches_differential():
    assert_tpu_cpu_equal(
        lambda s: source(s).map_batches(double_v, OUT_SCHEMA))


def test_map_batches_composes_with_tpu_ops():
    assert_tpu_cpu_equal(
        lambda s: source(s)
        .filter(col("v").is_not_null())
        .map_batches(double_v, OUT_SCHEMA)
        .group_by("k").agg(sum_("doubled").alias("sd")))


def test_map_batches_with_pandas():
    def via_pandas(table: pa.Table) -> pa.Table:
        df = table.to_pandas()
        out = df[["k"]].copy()
        out["doubled"] = (df["v"] * 2).astype("Int64")
        return pa.Table.from_pandas(out, preserve_index=False)

    assert_tpu_cpu_equal(
        lambda s: source(s).map_batches(via_pandas, OUT_SCHEMA))


def test_udf_worker_never_opens_the_parents_chip(monkeypatch):
    """One process per chip: whatever platform the parent runs on, a UDF
    worker is spawned with JAX_PLATFORMS=cpu, and the parent's own
    setting is restored after the spawn."""
    import os

    from spark_rapids_tpu.plan.execs.python_worker import PythonWorkerPool

    def worker_platform(table: pa.Table) -> pa.Table:
        import os
        return pa.table({"p": [os.environ.get("JAX_PLATFORMS")]})

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")   # a parent on the chip
    pool = PythonWorkerPool(1)                   # spawns its worker now
    try:
        assert os.environ["JAX_PLATFORMS"] == "tpu"
        out = pool.run(worker_platform, pa.table({"x": [1]}))
        assert out.column("p").to_pylist() == ["cpu"]
    finally:
        for w in pool._free:
            if w is not None:
                w.close()
