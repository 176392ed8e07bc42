"""Range-partitioned global sort tests (GpuRangePartitioner analog)."""
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema
from spark_rapids_tpu.kernels.sort import SortOrder
from tests.test_queries import assert_tpu_cpu_equal, source
from tests.test_strings import strings_df


def test_global_sort_is_range_partitioned():
    s = TpuSession({"spark.rapids.sql.enabled": "true"})
    plan = source(s).order_by(("v", SortOrder(True))).physical_plan()
    assert "TpuRangeSort" in plan.tree_string()


def test_range_sort_correct_asc_desc():
    assert_tpu_cpu_equal(
        lambda s: source(s).order_by(("v", SortOrder(False)),
                                     ("k", SortOrder(True))),
        ignore_order=False)


def test_range_sort_nulls_last():
    assert_tpu_cpu_equal(
        lambda s: source(s).order_by(
            ("x", SortOrder(True, nulls_first=False)),
            ("v", SortOrder(True))),
        ignore_order=False)


def test_range_sort_string_keys():
    assert_tpu_cpu_equal(
        lambda s: strings_df(s, parts=3).order_by(
            ("s", SortOrder(True)), ("n", SortOrder(True)),
            ("t", SortOrder(True))),
        ignore_order=False)


def test_range_sort_skewed_distribution():
    def build(s):
        rng = np.random.RandomState(1)
        n = 900
        vals = np.where(rng.rand(n) < 0.8, 7, rng.randint(0, 1000, n))
        batches = [ColumnarBatch.from_pydict(
            {"v": vals[o:o + 300].tolist()}, Schema.of(v=T.LONG))
            for o in range(0, n, 300)]
        return s.create_dataframe(batches, num_partitions=3).order_by(
            ("v", SortOrder(True)))
    assert_tpu_cpu_equal(build, ignore_order=False)


# -- a task blocked on a sibling's materialisation holds no device permit ----

def write_two_parquet_files(root) -> list:
    import os

    import pyarrow as pa
    import pyarrow.parquet as pq
    rng = np.random.RandomState(3)
    paths = []
    for i in range(2):
        paths.append(os.path.join(str(root), f"t{i}.parquet"))
        pq.write_table(pa.table({
            "k": rng.randint(0, 40, 6000).astype(np.int64),
            "v": rng.randint(-50, 50, 6000).astype(np.int64)}),
            paths[-1], row_group_size=1500)
    return paths


def ordered_agg(sess, paths):
    """ORDER BY over an aggregate over a two-file Parquet scan: two tasks
    meet at the range sort's materialise-once lock, and the one inside it
    gives its permit up in the scan to wait for each decoded chunk."""
    from spark_rapids_tpu.expressions import count, sum_
    return (sess.read_parquet(*paths).group_by("k")
            .agg(sum_("v").alias("sv"), count().alias("n")).order_by("k"))


def thread_stacks() -> str:
    import sys
    import traceback
    return "\n".join(
        f"--- thread {ident}\n" + "".join(traceback.format_stack(frame))
        for ident, frame in sys._current_frames().items())


def test_order_by_over_parquet_completes_with_one_permit_taken(tmp_path):
    """With one of the two permits held elsewhere in the process (another
    query's task; at PR 28 a permit that test_python_exec had leaked),
    task A took the range sort's lock, gave its permit up in the scan, and
    task B took that permit and kept it while it waited for the lock: A
    could never take one back.  B now waits for the lock off the
    semaphore (plan/execs/base.py MaterializeLock)."""
    import threading

    from spark_rapids_tpu.memory.semaphore import tpu_semaphore
    from tests.test_memory import task_hold
    paths = write_two_parquet_files(tmp_path)
    conf = {"spark.rapids.sql.enabled": "true",
            "spark.rapids.sql.reader.batchSizeRows": "1500"}
    plan = ordered_agg(TpuSession(conf), paths)
    assert "TpuRangeSort[" in plan.physical_plan().tree_string()
    sem = tpu_semaphore()
    holding, let_go = threading.Event(), threading.Event()

    def hold_one_permit():
        with task_hold(sem):
            holding.set()
            let_go.wait()
    holder = threading.Thread(target=hold_one_permit, daemon=True)
    holder.start()
    assert holding.wait(30)
    got = {}
    runner = threading.Thread(
        target=lambda: got.update(rows=plan.collect()), daemon=True)
    runner.start()
    try:
        runner.join(timeout=180)
        stacks = thread_stacks() if runner.is_alive() else ""
    finally:
        let_go.set()        # a deadlocked query ends once a permit is free
        holder.join(30)
        runner.join(120)
    assert not stacks, "the query deadlocked on the device permit:\n" + stacks
    expected = ordered_agg(
        TpuSession({"spark.rapids.sql.enabled": "false"}), paths).collect()
    assert got["rows"] == expected and len(expected) == 40
