"""Expand, Range, and Sample execs.

Reference: GpuExpandExec.scala (projection fan-out for rollup/cube),
GpuRangeExec (basicPhysicalOperators.scala:526 — device-side iota id
generation), GpuSampleExec (device-side Bernoulli sampling).

TPU designs:
  * Expand emits one projected batch per projection per input batch — no
    row interleave kernel is needed; downstream aggregation is order-free
    (the oracle mirrors this projection-major order).
  * Range builds batches from a jitted iota at a static batch capacity.
  * Sample derives a per-row uniform from a splitmix64 hash of
    (seed, partition, global row offset) — identical integer math on
    device and oracle, so results agree bit-for-bit.
"""
from __future__ import annotations

from typing import Iterator, Sequence

import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import (ColumnarBatch, Schema,
                                              host_scalar)
from spark_rapids_tpu.columnar.column import DeviceColumn, round_up_pow2
from spark_rapids_tpu.expressions.core import EvalContext, Expression
from spark_rapids_tpu.kernels.selection import compaction_map, gather_batch
from spark_rapids_tpu.memory.retry import with_retry_no_split
from spark_rapids_tpu.plan.execs.base import (
    TpuExec, exprs_cache_key, schema_cache_key, shared_jit, timed)


class TpuExpandExec(TpuExec):
    def __init__(self, projections: Sequence[Sequence[Expression]],
                 child: TpuExec, schema: Schema):
        super().__init__((child,), schema)
        self.projections = tuple(tuple(p) for p in projections)
        out_schema = schema
        self._runs = []
        from functools import partial as _p
        from spark_rapids_tpu.plan.execs.base import (
            bind_trace_consts, jit_bucketed_step)
        for pi, proj in enumerate(self.projections):
            proj_t = proj

            def run(batch: ColumnarBatch, consts, string_bucket: int = 0,
                    _proj=proj_t) -> ColumnarBatch:
                ctx = EvalContext(batch, string_bucket=string_bucket,
                                  trace_consts=bind_trace_consts(_proj, consts))
                cols = tuple(_coerce(e.eval(ctx), dt)
                             for e, dt in zip(_proj, out_schema.dtypes))
                return ColumnarBatch(cols, batch.num_rows, out_schema)

            key = (f"expand{pi}|{schema_cache_key(child.schema)}|"
                   f"{exprs_cache_key(proj)}")
            self._runs.append(jit_bucketed_step(
                key, proj, lambda bkt, _r=run: _p(_r, string_bucket=bkt),
                kind="expand"))

    def execute_partition(self, idx: int) -> Iterator[ColumnarBatch]:
        for batch in self.children[0].execute_partition(idx):
            for run in self._runs:
                with timed(self.op_time):
                    out = with_retry_no_split(lambda: run(batch))
                self.output_rows.add(out.num_rows)
                yield self._count_out(out)

    def describe(self):
        return f"TpuExpand[{len(self.projections)} projections]"


def _coerce(col: DeviceColumn, dt) -> DeviceColumn:
    """Null-literal projection slots arrive as NullType; re-type the buffer
    to the expand output dtype (all-invalid, so values are irrelevant)."""
    if isinstance(col.dtype, T.NullType) and not isinstance(dt, T.NullType):
        if dt.variable_width:
            cap = col.capacity
            return DeviceColumn.empty(dt, cap, byte_capacity=1)
        return DeviceColumn(jnp.zeros((col.capacity,), dt.jnp_dtype),
                            jnp.zeros((col.capacity,), jnp.bool_), dt)
    return col


class TpuRangeExec(TpuExec):
    def __init__(self, start: int, end: int, step: int, num_partitions: int,
                 schema: Schema, batch_rows: int = 1 << 20):
        super().__init__((), schema)
        self.start, self.end, self.step = start, end, step
        self.n_parts = num_partitions
        self.batch_rows = batch_rows
        total = max(0, -(-(end - start) // step))
        per = -(-total // num_partitions)
        self._bounds = [(start + p * per * step,
                         min(per, max(0, total - p * per)))
                        for p in range(num_partitions)]

    def num_partitions(self) -> int:
        return self.n_parts

    def execute_partition(self, idx: int) -> Iterator[ColumnarBatch]:
        lo, count = self._bounds[idx]
        step = self.step
        emitted = 0
        while emitted < count:
            n = min(self.batch_rows, count - emitted)
            cap = round_up_pow2(max(n, 1))

            def make(lo_=lo, emitted_=emitted, n_=n, cap_=cap):
                fn = shared_jit(f"range|{cap_}",
                                lambda: _partial(_range_kernel, cap=cap_),
                                kind="range")
                return fn(host_scalar(lo_ + emitted_ * step, np.int64),
                          host_scalar(step, np.int64), host_scalar(n_))
            with timed(self.op_time):
                out_col, live = make()
            batch = ColumnarBatch((DeviceColumn(out_col, live, T.LONG),),
                                  host_scalar(n), self.schema)
            emitted += n
            self.output_rows.add(batch.num_rows)
            yield self._count_out(batch)

    def describe(self):
        return f"TpuRange[{self.start}, {self.end}, {self.step}]"


from functools import partial as _partial


def _range_kernel(lo, step, n, cap):
    idx = jnp.arange(cap, dtype=jnp.int64)
    live = (idx < n.astype(jnp.int64))
    vals = jnp.where(live, lo + idx * step, 0)
    return vals, live


def sample_mask_uniform(seed: int, partition: int, offset, cap: int, xp):
    """Shared device/oracle uniform in [0,1): splitmix64 of
    (seed, partition, global row index).  xp is jnp or np."""
    M = 1 << 64
    seed_mix = (int(seed) * 0x9E3779B97F4A7C15) % M
    part_mix = ((int(partition) + 1) * 0xBF58476D1CE4E5B9) % M \
        if not hasattr(partition, "dtype") else None
    idx = xp.arange(cap, dtype=xp.uint64) + xp.uint64(offset)
    if part_mix is None:   # traced device scalar
        pm = (partition + xp.uint64(1)) * xp.uint64(0xBF58476D1CE4E5B9)
    else:
        pm = xp.uint64(part_mix)
    z = idx + xp.uint64(seed_mix) + pm
    z = (z ^ (z >> xp.uint64(30))) * xp.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> xp.uint64(27))) * xp.uint64(0x94D049BB133111EB)
    z = z ^ (z >> xp.uint64(31))
    return (z >> xp.uint64(11)).astype(xp.float64) * (2.0 ** -53)


class TpuSampleExec(TpuExec):
    def __init__(self, fraction: float, seed: int, child: TpuExec):
        super().__init__((child,), child.schema)
        self.fraction = fraction
        self.seed = seed

        frac, sd = fraction, seed

        def step(batch: ColumnarBatch, part_s, off_s):
            # partition/offset are traced scalars: one compile per capacity
            u = sample_mask_uniform(sd, part_s, off_s, batch.capacity, jnp)
            mask = (u < frac) & batch.live_mask()
            indices, count = compaction_map(mask)
            return gather_batch(batch, indices, count)

        key = f"sample|{fraction}|{seed}|{schema_cache_key(child.schema)}"
        self._step = lambda b, p, o: shared_jit(
            key, lambda: step, kind="sample")(
            b, jnp.uint64(p), jnp.uint64(o))

    def execute_partition(self, idx: int) -> Iterator[ColumnarBatch]:
        offset = 0
        for batch in self.children[0].execute_partition(idx):
            n = batch.host_num_rows()
            with timed(self.op_time):
                out = with_retry_no_split(
                    lambda: self._step(batch, idx, offset))
            offset += n
            self.output_rows.add(out.num_rows)
            yield self._count_out(out)

    def describe(self):
        return f"TpuSample[{self.fraction}, seed={self.seed}]"
