"""Arrow-bridged Python transforms on device batches.

Reference: org/apache/spark/sql/rapids/execution/python/ — GpuArrowEval
PythonExec (BatchProducer at :223), map/flatMap-in-pandas variants, and
PythonWorkerSemaphore (the device semaphore is released while Python runs
so other tasks can use the chip).
"""
from __future__ import annotations

from typing import Iterator

from spark_rapids_tpu.columnar.arrow import arrow_to_batch
from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema
from spark_rapids_tpu.memory.semaphore import tpu_semaphore
from spark_rapids_tpu.plan.execs.base import TpuExec, timed


class TpuMapBatchesExec(TpuExec):
    def __init__(self, fn, child: TpuExec, schema: Schema,
                 whole_partition: bool = False, worker_conf=None):
        super().__init__((child,), schema)
        self.fn = fn
        self.whole_partition = whole_partition
        #: optional (pool size, mem limit): UDFs run out-of-process with
        #: crash isolation + memory rlimit (python_worker.py).  The pool
        #: is created LAZILY on first execution — planning/explain must
        #: never spawn processes — then cached on the exec.
        self.worker_conf = worker_conf
        self._pool = None

    @property
    def worker_pool(self):
        if self.worker_conf is None:
            return None
        if self._pool is None:
            from spark_rapids_tpu.plan.execs.python_worker import (
                PythonWorkerPool)
            self._pool = PythonWorkerPool.shared(*self.worker_conf)
        return self._pool

    def _input_batches(self, idx: int):
        if not self.whole_partition:
            yield from self.children[0].execute_partition(idx)
            return
        # grouped-map: one Arrow table per partition (host-side concat —
        # cheaper than a device coalesce we would immediately download)
        import pyarrow as pa
        tables = [b.to_arrow()
                  for b in self.children[0].execute_partition(idx)]
        if not tables:
            return
        merged = pa.concat_tables(tables)
        from spark_rapids_tpu.columnar.arrow import arrow_to_batch
        yield arrow_to_batch(merged)

    def execute_partition(self, idx: int) -> Iterator[ColumnarBatch]:
        for batch in self._input_batches(idx):
            with timed(self.op_time):
                table = batch.to_arrow()     # device -> host Arrow
                # release the device while Python crunches host data
                # (PythonWorkerSemaphore.scala analog)
                with tpu_semaphore().released():
                    if self.worker_pool is not None:
                        result = self.worker_pool.run(self.fn, table)
                    else:
                        result = self.fn(table)
                out = arrow_to_batch(result)  # host Arrow -> device
            self.output_rows.add(out.num_rows)
            yield self._count_out(out)

    def describe(self):
        name = getattr(self.fn, "__name__", "fn")
        return f"TpuMapBatches[{name}]"
