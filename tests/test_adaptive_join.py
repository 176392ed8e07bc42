"""Adaptive join: runtime broadcast-vs-shuffled choice from the
materialized build-side size (GpuShuffledSizedHashJoinExec.scala:829 /
AQE analog).  The key test: the static estimate is WRONG and the runtime
choice fixes it."""
import numpy as np
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema
from spark_rapids_tpu.expressions import col, lit
from spark_rapids_tpu.plan.execs.join import TpuAdaptiveJoinExec
from spark_rapids_tpu.planner.overrides import plan_query

from test_queries import assert_tpu_cpu_equal

SCHEMA = Schema.of(k=T.INT, v=T.LONG)


def _df(sess, n, seed, parts=3):
    rng = np.random.RandomState(seed)
    return sess.create_dataframe(
        [ColumnarBatch.from_pydict(
            {"k": rng.randint(0, 50, n).tolist(),
             "v": rng.randint(0, 10**6, n).tolist()}, SCHEMA)],
        num_partitions=parts)


def _adaptive_of(plan):
    """Find the adaptive exec in a physical tree."""
    if isinstance(plan, TpuAdaptiveJoinExec):
        return plan
    for c in plan.children:
        found = _adaptive_of(c)
        if found is not None:
            return found
    return None


def _build(sess, n_right, filtered=True):
    left = _df(sess, 400, seed=1)
    right = _df(sess, n_right, seed=2, parts=1)
    r = right.select(col("k").alias("rk"), col("v").alias("rv"))
    if filtered:
        # the filter makes the static estimate (rows // 2) WRONG in both
        # directions: a selective filter keeps ~2% (estimate 8x too big),
        # a pass-through filter keeps ~100% (estimate 2x too small)
        r = r.filter(col("rv") >= lit(0))
    return left.join(r, on=([col("k")], [col("rk")]), how="inner")


@pytest.mark.parametrize("keep,chosen", [
    # a selective filter keeps ~2% (~6 rows): the estimate was 8x too big
    (lambda rv: rv < lit(20_000), "broadcast"),
    # a pass-through filter keeps all 300: the estimate was 2x too small
    (lambda rv: rv >= lit(0), "shuffled")],
    ids=["runtime_broadcasts", "runtime_shuffles"])
def test_static_estimate_wrong_runtime_decides(keep, chosen):
    """The estimate (300 // 2 = 150 > 64) lands in the ambiguous zone; the
    materialized build side's actual row count picks the strategy, and the
    rows are the oracle's either way.  (The same choice on a conditional
    semi- and anti-join over an exchange: tests/test_q21_lineitem.py.)"""
    sess = TpuSession({"spark.rapids.sql.enabled": "true",
                       "spark.rapids.sql.join.broadcastRowThreshold": "64"})
    left = _df(sess, 400, seed=1)
    right = _df(sess, 300, seed=2, parts=1)
    r = right.select(col("k").alias("rk"), col("v").alias("rv"))
    df = left.join(r.filter(keep(col("rv"))),
                   on=([col("k")], [col("rk")]), how="inner")
    plan, _ = plan_query(df.plan, sess.conf)
    ad = _adaptive_of(plan)
    assert ad is not None, plan.tree_string()
    ad.num_partitions()    # forces the decision
    assert ad.chosen == chosen, ad.describe()
    ad.cleanup()
    df.collect()


@pytest.mark.parametrize("n_right", [40, 2000])
def test_adaptive_join_differential(n_right):
    """Both runtime outcomes produce oracle-identical results."""
    def build(s):
        # TPU session uses a threshold landing n_right in the ambiguous
        # zone; the CPU oracle ignores the rapids keys entirely
        return _build(s, n_right)
    cpu = TpuSession({"spark.rapids.sql.enabled": "false"})
    tpu = TpuSession({"spark.rapids.sql.enabled": "true",
                      "spark.rapids.sql.join.broadcastRowThreshold": "256"})
    from test_queries import _normalize
    assert _normalize(build(tpu).collect()) == _normalize(build(cpu).collect())


def test_concurrent_queries_decide_their_build_sides_under_a_permit(
        monkeypatch):
    """_decide materialises the build side on the device, as a rule from
    num_partitions() on the caller's thread before any task has started:
    engine.execute holds a permit there (PR 29), so four queries at once
    put no more build sides on the device than the semaphore has
    permits."""
    import threading
    import time

    from spark_rapids_tpu.memory.semaphore import tpu_semaphore
    from test_queries import _normalize
    gauge = threading.Lock()
    deciding, peak, held = [0], [0], []
    decide = TpuAdaptiveJoinExec._decide

    def watched(self):
        if self._inner is not None:
            return decide(self)
        with gauge:
            deciding[0] += 1
            peak[0] = max(peak[0], deciding[0])
            held.append(tpu_semaphore().held_count())
        try:
            time.sleep(0.05)
            return decide(self)
        finally:
            with gauge:
                deciding[0] -= 1
    monkeypatch.setattr(TpuAdaptiveJoinExec, "_decide", watched)
    cpu = TpuSession({"spark.rapids.sql.enabled": "false"})
    want = _normalize(_build(cpu, 2000).collect())
    got = [None] * 4

    def query(i):
        tpu = TpuSession({
            "spark.rapids.sql.enabled": "true",
            "spark.rapids.sql.join.broadcastRowThreshold": "256"})
        got[i] = _normalize(_build(tpu, 2000).collect())
    queries = [threading.Thread(target=query, args=(i,)) for i in range(4)]
    for q in queries:
        q.start()
    for q in queries:
        q.join(300)
    assert not any(q.is_alive() for q in queries)
    assert got == [want] * 4
    assert held == [1] * 4, held
    assert peak[0] <= tpu_semaphore()._sem._size, peak


@pytest.mark.inject_oom
def test_adaptive_join_inject_oom():
    cpu = TpuSession({"spark.rapids.sql.enabled": "false"})
    tpu = TpuSession({"spark.rapids.sql.enabled": "true",
                      "spark.rapids.sql.join.broadcastRowThreshold": "256"})
    from test_queries import _normalize
    assert _normalize(_build(tpu, 500).collect()) == \
        _normalize(_build(cpu, 500).collect())


def test_skew_join_hot_key_split():
    """One key 100x the others: hash sub-partitioning alone can't shrink
    the hot bucket (all its rows share a hash), so the probe side splits
    by row ranges — AQE's skew-join split (OptimizeSkewedJoin /
    GpuCustomShuffleReaderExec.scala:39).  Results must stay differential
    green, and the engine must never materialize the hot bucket's join in
    one batch."""
    import numpy as np

    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar.batch import Schema
    from spark_rapids_tpu.expressions import col, count, sum_
    from spark_rapids_tpu.expressions.core import Alias
    from tests.test_queries import assert_tpu_cpu_equal

    ls = Schema.of(k=T.INT, lv=T.LONG)
    rs = Schema.of(k=T.INT, rv=T.LONG)

    def q(s, how):
        s.set_conf("spark.rapids.sql.batchSizeRows", 1 << 8)
        rng = np.random.RandomState(3)
        n_hot, n_cold = 2000, 20
        l = s.create_dataframe(
            {"k": [7] * n_hot + [int(x) for x in rng.randint(100, 120, n_cold)],
             "lv": list(range(n_hot + n_cold))}, ls, num_partitions=2)
        r = s.create_dataframe(
            {"k": [7, 7, 101, 105, 119],
             "rv": [1, 2, 3, 4, 5]}, rs, num_partitions=2)
        j = l.join(r, "k", how=how)
        if how in ("inner", "left"):
            return j.agg(Alias(sum_(col("lv")), "s1"),
                         Alias(sum_(col("rv")), "s2"), Alias(count(), "n"))
        return j.agg(Alias(sum_(col("lv")), "s1"), Alias(count(), "n"))

    for how in ("inner", "left", "left_semi", "left_anti"):
        assert_tpu_cpu_equal(lambda s, h=how: q(s, h))
