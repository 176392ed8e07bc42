"""Sort + limit execs.

Reference: GpuSortExec.scala (:44 one-batch sort; out-of-core merge at :137
is the follow-on once spillable pending queues land here), limit.scala.
"""
from __future__ import annotations

from typing import Iterator, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar.batch import (ColumnarBatch, Schema,
                                              host_scalar)
from spark_rapids_tpu.columnar.column import round_up_pow2
from spark_rapids_tpu.expressions.core import EvalContext, Expression
from spark_rapids_tpu.kernels.selection import concat_batches_device, gather_batch
from spark_rapids_tpu.kernels.sort import SortOrder, sort_indices
from spark_rapids_tpu.memory.retry import with_capacity_retry, with_retry_no_split
from spark_rapids_tpu.plan.execs.base import TpuExec, string_key_bucket, timed


def sort_step(orders, batch: ColumnarBatch, bucket: int) -> ColumnarBatch:
    """Pure device sort of one batch by `orders` (shared by the task-engine
    exec and the SPMD stage compiler — one body, two engines)."""
    ctx = EvalContext(batch)
    key_cols = tuple(e.eval(ctx) for e, _ in orders)
    work = ColumnarBatch(
        tuple(batch.columns) + key_cols, batch.num_rows,
        Schema(tuple(batch.schema.names) +
               tuple(f"_sk{i}" for i in range(len(key_cols))),
               tuple(batch.schema.dtypes) +
               tuple(c.dtype for c in key_cols)))
    nbase = len(batch.schema)
    idx = sort_indices(
        work, list(range(nbase, nbase + len(key_cols))),
        [o for _, o in orders], string_max_bytes=bucket)
    sorted_work = gather_batch(work, idx, batch.num_rows)
    return ColumnarBatch(sorted_work.columns[:nbase],
                         batch.num_rows, batch.schema)


class TpuSortExec(TpuExec):
    """Sorts each partition (planner puts a single-partition exchange below
    for global sorts).

    Out-of-core: when a partition's rows exceed ``target_rows``, the input
    is range-bucketed with sampled splitters (the same machinery as the
    range exchange) into spillable buckets that are sorted one at a time
    and emitted in order — the TPU distribution-sort answer to the
    reference's spillable-pending-queue merge sort (GpuSortExec.scala:137,
    OutOfCoreBatch:241).  Ties never split across buckets, so the output
    equals a stable sort of the concatenated input.
    """

    def __init__(self, orders: Sequence[Tuple[Expression, SortOrder]],
                 child: TpuExec, target_rows: int = 1 << 20):
        super().__init__((child,), child.schema)
        self.orders = tuple(orders)
        self.target_rows = max(int(target_rows), 1)
        from spark_rapids_tpu.plan.execs.base import (
            exprs_cache_key, schema_cache_key, shared_jit)

        orders = self.orders   # no self-capture (cache pins the exec tree)

        def make_run(bucket: int):
            def run(batch: ColumnarBatch) -> ColumnarBatch:
                return sort_step(orders, batch, bucket)
            return run

        key = (f"sort|{schema_cache_key(child.schema)}|"
               f"{exprs_cache_key(e for e, _ in self.orders)}|"
               f"{','.join(f'{o.ascending}:{o.nulls_first}' for _, o in self.orders)}")
        self._run = lambda b, _k=key: shared_jit(
            f"{_k}|{(bkt := string_key_bucket(b, [e for e, _ in self.orders]))}",
            lambda: make_run(bkt), kind="sort_local")(b)

    def execute_partition(self, idx: int) -> Iterator[ColumnarBatch]:
        batches = list(self.children[0].execute_partition(idx))
        if not batches:
            return
        total = sum(b.capacity for b in batches)
        if total > self.target_rows:
            yield from self._execute_out_of_core(batches, total)
            return
        with timed(self.op_time):
            if len(batches) == 1:
                out = with_retry_no_split(lambda: self._run(batches[0]))
            else:
                cap = round_up_pow2(max(total, 1))
                # concat INSIDE the retry body: on OOM the discarded
                # concat result is re-run after the spill instead of
                # sitting unspillably in the closure
                out = with_retry_no_split(lambda: self._run(
                    concat_batches_device(batches, cap)[0]))
        self.output_rows.add(out.num_rows)
        yield self._count_out(out)

    def _execute_out_of_core(self, batches: List[ColumnarBatch],
                             total: int) -> Iterator[ColumnarBatch]:
        from spark_rapids_tpu.plan.execs.coalesce import (
            coalesce_to_one, retry_over_spillable)
        from spark_rapids_tpu.plan.execs.out_of_core import (
            close_all, num_sub_buckets)
        from spark_rapids_tpu.plan.execs.range_sort import (
            range_bucket_spillable)

        n_out = num_sub_buckets(total, self.target_rows)
        with timed(self.op_time):
            buckets = range_bucket_spillable(
                iter(batches), self.orders, self.schema, n_out, batches)
            del batches  # queued data now lives in spillable handles
        try:
            for q in buckets:
                if not q:
                    continue
                with timed(self.op_time):
                    # pin-balanced retry (retry_over_spillable): each
                    # attempt re-materializes the handles and unpins
                    # before it ends, so an OOM's spill can free exactly
                    # these inputs before the re-run
                    out = retry_over_spillable(q, self._run)
                    for h in q:
                        h.close()
                    q.clear()
                self.output_rows.add(out.num_rows)
                yield self._count_out(out)
        finally:
            close_all(buckets)

    def describe(self):
        inner = ", ".join(f"{e!r} {'ASC' if o.ascending else 'DESC'}"
                          for e, o in self.orders)
        return f"TpuSort[{inner}]"


class TpuLimitExec(TpuExec):
    """Global limit: take the first n rows across partitions in order."""

    def __init__(self, n: int, child: TpuExec):
        super().__init__((child,), child.schema)
        self.n = n

    def num_partitions(self) -> int:
        return 1

    def execute_partition(self, idx: int) -> Iterator[ColumnarBatch]:
        remaining = self.n
        child = self.children[0]
        for p in range(child.num_partitions()):
            if remaining <= 0:
                return
            for batch in child.execute_partition(p):
                if remaining <= 0:
                    return
                nrows = batch.host_num_rows()
                if nrows <= remaining:
                    remaining -= nrows
                    self.output_rows.add(nrows)
                    yield self._count_out(batch)
                else:
                    take = remaining
                    remaining = 0
                    idx_arr = jnp.arange(batch.capacity, dtype=jnp.int32)
                    out = gather_batch(batch, idx_arr, host_scalar(take))
                    self.output_rows.add(take)
                    yield self._count_out(out)
                    return

    def describe(self):
        return f"TpuLimit[{self.n}]"
