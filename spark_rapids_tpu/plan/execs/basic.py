"""Project and Filter execs.

Reference: basicPhysicalOperators.scala — GpuProjectExec (:834, tiered
project with retry at :890) and GpuFilterExec (:1334).

The whole per-batch computation (expression eval + compaction gather) is one
jitted function, so XLA fuses expression work into the gather — the TPU
equivalent of the reference fusing filter into its kernels via AST.
jax.jit's shape-keyed tracing cache gives per-capacity-bucket compilation
for free.
"""
from __future__ import annotations

from typing import Iterator, Sequence, Tuple

import jax

from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema
from spark_rapids_tpu.expressions.core import EvalContext, Expression
from spark_rapids_tpu.kernels.selection import compaction_map, gather_batch
from spark_rapids_tpu.memory.retry import with_retry_no_split
from spark_rapids_tpu.plan.execs.base import (
    TpuExec,
    schema_cache_key,
    shared_jit,
    timed,
)


class TpuProjectExec(TpuExec):
    def __init__(self, exprs: Sequence[Expression], child: TpuExec,
                 schema: Schema):
        super().__init__((child,), schema)
        self.exprs = tuple(exprs)
        exprs_t, out_schema = self.exprs, schema   # no self-capture (cache pins)

        from functools import partial as _p
        from spark_rapids_tpu.plan.execs.base import (
            bind_trace_consts, jit_bucketed_step)

        def run(batch: ColumnarBatch, consts, string_bucket: int = 0
                ) -> ColumnarBatch:
            ctx = EvalContext(batch, string_bucket=string_bucket,
                              trace_consts=bind_trace_consts(exprs_t, consts))
            cols = tuple(e.eval(ctx) for e in exprs_t)
            return ColumnarBatch(cols, batch.num_rows, out_schema)

        self._run = jit_bucketed_step(
            f"project|{schema_cache_key(child.schema)}", self.exprs,
            lambda bkt: _p(run, string_bucket=bkt), kind="project")

    def execute_partition(self, idx: int) -> Iterator[ColumnarBatch]:
        for batch in self.children[0].execute_partition(idx):
            with timed(self.op_time):
                out = with_retry_no_split(lambda: self._run(batch))
            self.output_rows.add(out.num_rows)
            yield self._count_out(out)

    def describe(self):
        return f"TpuProject[{', '.join(map(repr, self.exprs))}]"


class TpuFilterExec(TpuExec):
    def __init__(self, condition: Expression, child: TpuExec):
        super().__init__((child,), child.schema)
        self.condition = condition

        cond = condition   # no self-capture (cache pins)
        from functools import partial as _p
        from spark_rapids_tpu.plan.execs.base import (
            bind_trace_consts, jit_bucketed_step)

        def run(batch: ColumnarBatch, consts, string_bucket: int = 0
                ) -> ColumnarBatch:
            ctx = EvalContext(batch, string_bucket=string_bucket,
                              trace_consts=bind_trace_consts([cond], consts))
            pred = cond.eval(ctx)
            mask = pred.data & pred.validity & batch.live_mask()
            indices, count = compaction_map(mask)
            # output capacity = input capacity: a filter never grows, so
            # there is no overflow path here
            return gather_batch(batch, indices, count)

        self._run = jit_bucketed_step(
            f"filter|{schema_cache_key(child.schema)}", [condition],
            lambda bkt: _p(run, string_bucket=bkt), kind="filter")

    def execute_partition(self, idx: int) -> Iterator[ColumnarBatch]:
        from spark_rapids_tpu.plan.execs.coalesce import maybe_shrink
        for batch in self.children[0].execute_partition(idx):
            with timed(self.op_time):
                out = with_retry_no_split(lambda: self._run(batch))
                # selective filters leave capacity >> rows; re-bucket so
                # downstream kernels stop paying the static-shape tax
                out = maybe_shrink(out)
            self.output_rows.add(out.num_rows)
            yield self._count_out(out)

    def describe(self):
        return f"TpuFilter[{self.condition!r}]"


class TpuUnionExec(TpuExec):
    """Concatenation of children's partitions (GpuUnionExec)."""

    def __init__(self, children: Tuple[TpuExec, ...], schema: Schema):
        super().__init__(children, schema)

    def num_partitions(self) -> int:
        return sum(c.num_partitions() for c in self.children)

    def execute_partition(self, idx: int) -> Iterator[ColumnarBatch]:
        for c in self.children:
            n = c.num_partitions()
            if idx < n:
                for batch in c.execute_partition(idx):
                    # re-schema: union output names come from the first child
                    out = ColumnarBatch(batch.columns, batch.num_rows, self.schema)
                    self.output_rows.add(out.num_rows)
                    yield self._count_out(out)
                return
            idx -= n

    def describe(self):
        return f"TpuUnion[{len(self.children)}]"
