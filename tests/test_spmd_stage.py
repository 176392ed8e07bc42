"""SPMD whole-query execution on the 8-virtual-device CPU mesh.

VERDICT round-1 item 2's "done" bar: a real multi-stage PLANNED query
(TPC-DS q3) runs through the planner + SPMD stage compiler on the mesh and
agrees with the single-chip engine / CPU oracle — not a bespoke demo step.
"""
import jax
import numpy as np
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema
from spark_rapids_tpu.parallel import distributed as D
from spark_rapids_tpu.parallel.stage import IciQueryExecutor
from spark_rapids_tpu.planner.overrides import plan_query
from spark_rapids_tpu.plan.cpu_engine import CpuTable
from spark_rapids_tpu.testing import tpcds

N_DEV = 8


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) >= N_DEV
    return D.make_mesh(N_DEV)


def _spmd_rows(mesh, df):
    exec_plan, _meta = plan_query(df.plan, df.session.conf)
    out = IciQueryExecutor(mesh).execute(exec_plan)
    rows = []
    for b in out:
        rows.extend(CpuTable.from_batch(b).rows())
    return rows


def _q3_frames(sess, n_rows=20_000):
    ss = sess.create_dataframe(
        tpcds.gen_store_sales(n_rows, batch_rows=4096), num_partitions=4)
    dd = sess.create_dataframe([tpcds.gen_date_dim()], num_partitions=1)
    it = sess.create_dataframe([tpcds.gen_item()], num_partitions=1)
    return ss, dd, it


def test_spmd_q3_matches_cpu_oracle(mesh):
    tpu = TpuSession({"spark.rapids.sql.enabled": "true"})
    cpu = TpuSession({"spark.rapids.sql.enabled": "false"})

    got = _spmd_rows(mesh, tpcds.q3(*_q3_frames(tpu)))
    expect = tpcds.q3(*_q3_frames(cpu)).collect()
    assert len(got) == len(expect) and len(got) > 0
    # q3 ends in a global sort with a full tiebreaker -> order must match;
    # columns: d_year, i_brand_id, i_brand (string), sum_agg
    for g, e in zip(got, expect):
        assert g[:3] == e[:3], (g, e)
        assert abs(g[3] - e[3]) < 1e-6 * max(abs(e[3]), 1.0), (g, e)


def test_spmd_q3_matches_single_chip_engine(mesh):
    tpu = TpuSession({"spark.rapids.sql.enabled": "true"})
    got = _spmd_rows(mesh, tpcds.q3(*_q3_frames(tpu)))
    single = tpcds.q3(*_q3_frames(tpu)).collect()
    assert [tuple(r[:2]) for r in got] == [tuple(r[:2]) for r in single]


def test_spmd_groupby_with_strings(mesh):
    """Multi-stage group-by over string keys: partial agg -> hash exchange
    (string byte redistribution) -> final agg, all inside one program."""
    from spark_rapids_tpu.expressions import count, sum_
    tpu = TpuSession({"spark.rapids.sql.enabled": "true"})
    cpu = TpuSession({"spark.rapids.sql.enabled": "false"})
    rng = np.random.RandomState(5)
    words = ["alpha", "beta", "gamma", "delta", "Ω-utf8", ""]
    n = 3000
    data = {"w": [words[i % len(words)] for i in rng.randint(0, 1000, n)],
            "v": rng.randint(-100, 100, n).tolist()}
    schema = Schema.of(w=T.STRING, v=T.LONG)

    def q(s):
        df = s.create_dataframe(data, schema, num_partitions=4)
        return df.group_by("w").agg(sum_("v").alias("s"),
                                    count().alias("n"))
    got = sorted(_spmd_rows(mesh, q(tpu)), key=repr)
    expect = sorted(q(cpu).collect(), key=repr)
    assert got == expect


def test_spmd_complete_agg_single_partition(mesh):
    """mode='complete' agg (planner: single-partition child) must return ONE
    result, not one per device, even though SPMD shards the scan."""
    from spark_rapids_tpu.expressions import count, sum_
    tpu = TpuSession({"spark.rapids.sql.enabled": "true"})
    cpu = TpuSession({"spark.rapids.sql.enabled": "false"})
    data = {"k": [i % 3 for i in range(300)], "v": list(range(300))}
    schema = Schema.of(k=T.INT, v=T.LONG)

    def q(s):
        df = s.create_dataframe(data, schema, num_partitions=1)
        return df.group_by("k").agg(sum_("v").alias("s"),
                                    count().alias("n"))
    got = sorted(_spmd_rows(mesh, q(tpu)))
    expect = sorted(q(cpu).collect())
    assert got == expect


def test_spmd_exchange_over_replicated_no_duplication(mesh):
    """Sort (replicates in SPMD v1) below a grouped agg: the planner's hash
    exchange over the replicated data must not multiply rows by n_dev."""
    from spark_rapids_tpu.expressions import count, sum_
    from spark_rapids_tpu.kernels.sort import SortOrder
    tpu = TpuSession({"spark.rapids.sql.enabled": "true"})
    cpu = TpuSession({"spark.rapids.sql.enabled": "false"})
    data = {"k": [i % 5 for i in range(400)], "v": list(range(400))}
    schema = Schema.of(k=T.INT, v=T.LONG)

    def q(s):
        df = s.create_dataframe(data, schema, num_partitions=4)
        return (df.order_by(("v", SortOrder(True)))
                .group_by("k").agg(sum_("v").alias("s"),
                                   count().alias("n")))
    got = sorted(_spmd_rows(mesh, q(tpu)))
    expect = sorted(q(cpu).collect())
    assert got == expect


def test_spmd_repartition_root_not_dropped(mesh):
    """A root exchange above a replicated subtree must surface EVERY row
    (a kind mismatch here silently keeps only device 0's shard)."""
    from spark_rapids_tpu.kernels.sort import SortOrder
    tpu = TpuSession({"spark.rapids.sql.enabled": "true"})
    cpu = TpuSession({"spark.rapids.sql.enabled": "false"})
    data = {"k": [i % 7 for i in range(350)], "v": list(range(350))}
    schema = Schema.of(k=T.INT, v=T.LONG)

    def q(s):
        df = s.create_dataframe(data, schema, num_partitions=4)
        return df.order_by(("v", SortOrder(False))).repartition(8, "k")
    got = sorted(_spmd_rows(mesh, q(tpu)), key=repr)
    expect = sorted(q(cpu).collect(), key=repr)
    assert got == expect


def test_spmd_join_without_exchanges(mesh):
    """Single-partition shuffled join plans WITHOUT exchanges; SPMD still
    round-robins the scans, so the compiler must gather the sides (local
    shard x local shard would silently drop cross-shard matches)."""
    from spark_rapids_tpu.expressions import col
    tpu = TpuSession({"spark.rapids.sql.enabled": "true",
                      "spark.rapids.sql.join.broadcastRowThreshold": "1"})
    cpu = TpuSession({"spark.rapids.sql.enabled": "false"})
    ldata = {"k": list(range(100)), "a": [i * 2 for i in range(100)]}
    rdata = {"k": list(range(50, 150)), "b": [i * 3 for i in range(100)]}
    ls = Schema.of(k=T.INT, a=T.LONG)
    rs = Schema.of(k=T.INT, b=T.LONG)

    def q(s):
        l = s.create_dataframe(ldata, ls, num_partitions=1)
        r = s.create_dataframe(rdata, rs, num_partitions=1)
        return l.join(r, on=([col("k")], [col("k")]))
    got = sorted(_spmd_rows(mesh, q(tpu)), key=repr)
    expect = sorted(q(cpu).collect(), key=repr)
    assert got == expect


def test_spmd_global_agg(mesh):
    from spark_rapids_tpu.expressions import avg, count, sum_
    tpu = TpuSession({"spark.rapids.sql.enabled": "true"})
    cpu = TpuSession({"spark.rapids.sql.enabled": "false"})
    data = {"v": list(range(1000))}
    schema = Schema.of(v=T.LONG)

    def q(s):
        df = s.create_dataframe(data, schema, num_partitions=4)
        return df.agg(sum_("v").alias("s"), count().alias("n"),
                      avg("v").alias("a"))
    got = _spmd_rows(mesh, q(tpu))
    expect = q(cpu).collect()
    assert len(got) == 1
    assert got[0][0] == expect[0][0] and got[0][1] == expect[0][1]
    assert abs(got[0][2] - expect[0][2]) < 1e-9


def test_spmd_takes_parquet_scans_and_compiles_once(mesh, tmp_path):
    """A file-backed plan runs on the mesh — it used to be declined
    (UnsupportedSpmd), which collect() answers by falling back to the
    one-device engine without a word — and its program is built once:
    the second run finds it under the capacities the first one seeded."""
    from spark_rapids_tpu.parallel import stage

    tpu = TpuSession({"spark.rapids.sql.enabled": "true"})
    k = [i % 7 for i in range(4000)]
    v = [float(i) for i in range(4000)]
    paths = []
    for p in range(2):
        path = str(tmp_path / f"t{p}.parquet")
        tpu.create_dataframe(
            {"k": k[p::2], "v": v[p::2]},
            Schema.of(k=T.INT, v=T.DOUBLE)).write_parquet(path)
        paths.append(path)

    def q(sess):
        from spark_rapids_tpu.expressions import sum_
        return sess.read_parquet(*paths).group_by("k").agg(
            sum_("v").alias("s"))

    expect = sorted(q(TpuSession({"spark.rapids.sql.enabled": "false"}))
                    .collect())
    built = []
    real_compile = IciQueryExecutor._compile

    def counting_compile(self, *a, **kw):
        built.append(1)
        return real_compile(self, *a, **kw)

    IciQueryExecutor._compile = counting_compile
    try:
        for _ in range(2):
            assert sorted(_spmd_rows(mesh, q(tpu))) == expect
    finally:
        IciQueryExecutor._compile = real_compile
    assert len(built) == 1, built
    assert stage._SPMD_PROGRAMS
