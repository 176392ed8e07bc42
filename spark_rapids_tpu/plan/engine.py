"""TPU engine driver: runs a physical exec tree.

The local stand-in for Spark's task scheduler: partitions are tasks; the
TPU semaphore (memory/semaphore.py, GpuSemaphore.scala:240 analog) gates
device concurrency when tasks run on a thread pool.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import List, Optional

from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.memory.semaphore import tpu_semaphore
from spark_rapids_tpu.plan.execs.base import TpuExec


class TpuEngine:
    def __init__(self, conf: Optional[RapidsConf] = None):
        self.conf = conf or RapidsConf()
        self.last_metrics = None

    def execute(self, plan: TpuExec) -> List[List[ColumnarBatch]]:
        """Materialize all partitions (list of batches per partition)."""
        # partition tasks are PART of the submitting query: pool threads
        # must inherit its tenant ambient or their allocations would
        # escape the tenant's budget/spill accounting (memory/tenant.py),
        # and its CANCEL TOKEN or a cancelled query's tasks would run to
        # completion holding semaphore slots (utils/cancel.py)
        from spark_rapids_tpu.memory.semaphore import current_task_priority
        from spark_rapids_tpu.memory.tenant import TENANTS
        from spark_rapids_tpu.utils.cancel import (
            QueryCancelled, cancel_scope, current_cancel_token)
        from spark_rapids_tpu.utils.obs import (
            current_query_trace, current_span_id, trace_scope)
        from spark_rapids_tpu.utils.sanitizer import (hot_section,
                                                      query_scope)
        from spark_rapids_tpu.utils.tracing import trace_range
        tenant = TENANTS.current()
        priority = current_task_priority()
        token = current_cancel_token()
        # the per-query trace rides along like the other ambients: a
        # task thread's counter deltas and trace ranges must attribute
        # to the submitting query (utils/obs.py)
        trace = current_query_trace()
        parent_span = current_span_id()

        # sizing the plan can be device work (an exchange, an AQE reader
        # or an adaptive join materialises its child inside
        # num_partitions()): this thread is a task meanwhile, with a
        # permit like any other
        sem = tpu_semaphore()
        sem.acquire_if_necessary(priority)
        try:
            nparts = plan.num_partitions()
        finally:
            sem.release_if_necessary()

        def run_one(p: int) -> List[ColumnarBatch]:
            from spark_rapids_tpu.memory.task_completion import task_scope
            from spark_rapids_tpu.utils.obs import task_metrics_tee
            # task_metrics_tee: this task's per-thread TaskMetrics
            # DELTA (semaphore wait below included) lands in the
            # per-query counter scope as task_* keys
            with task_metrics_tee(trace):
                sem.acquire_if_necessary(priority)
                try:
                    with TENANTS.scope(tenant), cancel_scope(token), \
                            trace_scope(trace, parent_span), \
                            task_scope():
                        try:
                            out: List[ColumnarBatch] = []
                            # sanitizer hot section: a task's batch loop
                            # must dispatch device programs, never
                            # implicitly sync (utils/sanitizer.py)
                            with hot_section(f"task-partition[{p}]"):
                                for batch in plan.execute_partition(p):
                                    # batch-boundary cancellation point
                                    # (the task analog of Spark's
                                    # cooperative interruption)
                                    if token is not None:
                                        token.check()
                                    out.append(batch)
                            return out
                        except QueryCancelled:
                            # counted INSIDE the trace scope so the
                            # delta tees into the query's attribution
                            # (scope sums must equal global deltas even
                            # for a run containing a cancel)
                            from spark_rapids_tpu.shuffle.stats import (
                                SHUFFLE_COUNTERS)
                            SHUFFLE_COUNTERS.add(tasks_cancelled=1)
                            raise
                finally:
                    sem.release_if_necessary()

        threads = min(nparts, max(self.conf.concurrent_tpu_tasks, 1))
        # sanitizer query scope: zero pin balance + zero tenant residue
        # asserted at teardown (cleanup() runs INSIDE the scope -- execs
        # release their handles there, so a leak is a real leak)
        with query_scope("engine.execute"):
            try:
                if threads <= 1 or nparts <= 1:
                    return [run_one(p) for p in range(nparts)]
                with ThreadPoolExecutor(max_workers=threads) as pool:
                    return list(pool.map(run_one, range(nparts)))
            finally:
                # the report resolves every lazily kept device scalar (a
                # row count per batch per exec) with a transfer of its own
                with trace_range("query.finish"):
                    self.last_metrics = self._metrics_report(plan)
                    plan.cleanup()

    def _metrics_report(self, plan: TpuExec):
        """Per-exec metric snapshots at the configured verbosity
        (spark.rapids.sql.metrics.level; GpuMetrics levels analog)."""
        from spark_rapids_tpu.utils.obs import metrics_tree
        return metrics_tree(plan, level=self.conf.metrics_level)

    def collect(self, plan: TpuExec) -> List[tuple]:
        from spark_rapids_tpu.plan.cpu_engine import CpuTable
        from spark_rapids_tpu.utils.tracing import trace_range
        rows: List[tuple] = []
        parts = self.execute(plan)
        with trace_range("query.fetch"):
            for part in parts:
                for batch in part:
                    rows.extend(CpuTable.from_batch(batch).rows())
        return rows
