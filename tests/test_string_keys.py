"""String key support: group-by, sort, repartition, join, window partition
keys on string columns (max-bytes bucket threading)."""
import pytest

from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.expressions import (
    DenseRank, RowNumber, col, count, max_, min_, over, sum_)
from spark_rapids_tpu.kernels.sort import SortOrder
from tests.test_queries import assert_tpu_cpu_equal
from tests.test_strings import strings_df


def test_group_by_string_key():
    assert_tpu_cpu_equal(
        lambda s: strings_df(s).group_by("s").agg(
            count().alias("n"), sum_("n").alias("sn")))


def test_group_by_string_runs_on_tpu():
    s = TpuSession({"spark.rapids.sql.enabled": "true"})
    e = strings_df(s).group_by("s").agg(count().alias("n")).explain()
    assert "will NOT" not in e, e


def test_sort_by_string_key():
    assert_tpu_cpu_equal(
        lambda s: strings_df(s).order_by(
            ("s", SortOrder(True)), ("t", SortOrder(False)),
            ("n", SortOrder(True))),
        ignore_order=False)


def test_repartition_by_string_key():
    assert_tpu_cpu_equal(lambda s: strings_df(s).repartition(4, col("s")))


def test_join_on_string_key():
    def build(s):
        left = strings_df(s)
        right = (strings_df(s).group_by("t")
                 .agg(count().alias("cnt")))
        return left.join(right, on=([col("s")], [col("t")]))
    assert_tpu_cpu_equal(build)


def test_join_on_string_key_runs_on_tpu():
    s = TpuSession({"spark.rapids.sql.enabled": "true"})
    left = strings_df(s)
    right = strings_df(s).group_by("t").agg(count().alias("cnt"))
    e = left.join(right, on=([col("s")], [col("t")])).explain()
    assert "will NOT" not in e, e


def test_window_partition_by_string():
    assert_tpu_cpu_equal(
        lambda s: strings_df(s).with_column(
            "rn", over(RowNumber(), partition_by=["s"], order_by=["n"])))


@pytest.mark.parametrize("ascending", [True, False], ids=["asc", "desc"])
def test_window_ranked_by_string_within_string_partitions(ascending):
    """Peers are runs of equal string order keys: the chunk keys that the
    sort read, compared through the order, nulls a peer group of their own."""
    assert_tpu_cpu_equal(
        lambda s: strings_df(s).with_column(
            "rk", over(DenseRank(), partition_by=["s"],
                       order_by=[("t", SortOrder(ascending))])))


def test_window_totals_over_string_partitions():
    """The unbounded-frame path groups the batch by the partition keys and
    reads its columns in sorted order (``layout.sorted_batch``)."""
    assert_tpu_cpu_equal(
        lambda s: strings_df(s).with_column(
            "total", over(sum_("n"), partition_by=["s", "t"])))


def test_merge_of_string_keyed_partials_with_string_buffers():
    """``_merge_step`` over two string keys: keys from the unsorted partial
    batches through the order, min/max string buffers in sorted order."""
    assert_tpu_cpu_equal(
        lambda s: strings_df(s, parts=3).group_by("s", "t").agg(
            min_("t").alias("lo"), max_("s").alias("hi"),
            sum_("n").alias("sn"), count().alias("c")))
