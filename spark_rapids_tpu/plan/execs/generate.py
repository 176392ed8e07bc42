"""Generate (explode/posexplode) exec.

Reference: GpuGenerateExec.scala:33 — generator row production with
lazy-array optimizations.  TPU design: one jitted kernel builds, from the
array column's offsets, a row gather-map (for the child's other columns) and
an element gather-map (for the generated column), both at a static output
capacity; the capacity-escalation retry loop re-runs on overflow (the analog
of GpuGenerateExec's batch splitting on OOM).
"""
from __future__ import annotations

from typing import Iterator

import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema
from spark_rapids_tpu.columnar.column import DeviceColumn, round_up_pow2
from spark_rapids_tpu.expressions.core import EvalContext
from spark_rapids_tpu.kernels import collections as CK
from spark_rapids_tpu.kernels.selection import (
    OverflowStatus, gather_column, required_gather_bytes)
from spark_rapids_tpu.memory.retry import with_retry_no_split
from spark_rapids_tpu.plan.execs.base import (
    TpuExec, expr_cache_key, schema_cache_key, shared_jit, timed)


class TpuGenerateExec(TpuExec):
    def __init__(self, generator, outer: bool, child: TpuExec,
                 schema: Schema):
        super().__init__((child,), schema)
        self.generator = generator      # collections.Explode / PosExplode
        self.outer = outer
        arr_expr = generator.child
        pos = generator.POS
        child_schema = child.schema
        out_schema = schema

        base_key = (f"generate|{'outer' if outer else ''}|{int(pos)}|"
                    f"{schema_cache_key(child_schema)}|"
                    f"{expr_cache_key(arr_expr)}")
        from spark_rapids_tpu.expressions.bridge import tree_has_bridge
        eager = tree_has_bridge([arr_expr])

        def jitted(out_cap: int, byte_caps: tuple):
            def run(batch: ColumnarBatch):
                ctx = EvalContext(batch)
                arr = arr_expr.eval(ctx)
                row_map, elem_map, posv, count = CK.explode_maps(
                    arr, batch.num_rows, outer, out_cap)
                bcaps = dict(byte_caps)
                cols = []
                req_bytes = []
                for i, c in enumerate(batch.columns):
                    bc = bcaps.get(i)
                    cols.append(gather_column(
                        c, row_map, count, out_capacity=out_cap,
                        out_byte_capacity=bc))
                    if c.offsets is not None:
                        req_bytes.append(
                            required_gather_bytes(c, row_map, count))
                if pos:
                    live = jnp.arange(out_cap, dtype=jnp.int32) < count
                    # outer-generated rows (null/empty arrays) have no
                    # element (elem_map is the OOB sentinel): pos is NULL
                    # there, matching Spark/oracle
                    pvalid = (live & (elem_map >= 0)
                              & (elem_map < arr.byte_capacity))
                    cols.append(DeviceColumn(
                        jnp.where(pvalid, posv, 0), pvalid, T.INT))
                cols.append(CK.gather_elements(arr, elem_map, count))
                out = ColumnarBatch(tuple(cols), count.astype(jnp.int32),
                                    out_schema)
                return out, OverflowStatus(count.astype(jnp.int64), req_bytes)
            if eager:   # CPU-bridged array input: host round-trip, no jit
                return run
            return shared_jit(f"{base_key}|{out_cap}|{byte_caps}",
                              lambda: run, kind="generate")

        def step(batch: ColumnarBatch):
            # initial output capacity: the element buffer bound (+rows for
            # outer's empty-array rows)
            arr_ord = _array_ordinal(arr_expr, batch)
            ecap = (batch.columns[arr_ord].byte_capacity
                    if arr_ord is not None else batch.capacity * 4)
            init_cap = round_up_pow2(max(
                ecap + (batch.capacity if outer else 0), 1))
            string_ords = [i for i, c in enumerate(batch.columns)
                           if c.offsets is not None]

            # capacity-escalation loop over BOTH row capacity and per-column
            # byte capacities (GpuSplitAndRetryOOM analog)
            cap = init_cap
            bcaps = {i: round_up_pow2(max(batch.columns[i].byte_capacity, 1))
                     for i in string_ords}
            from spark_rapids_tpu.memory.retry import TpuSplitAndRetryOOM
            while True:
                if cap > (1 << 28):
                    raise TpuSplitAndRetryOOM(
                        f"generate output needs capacity {cap}")
                out, status = jitted(cap, tuple(sorted(bcaps.items())))(batch)
                need_rows = int(status.required_rows)
                grow = False
                if need_rows > cap:
                    cap = round_up_pow2(need_rows)
                    grow = True
                for req, i in zip(status.required_bytes, string_ords):
                    if int(req) > bcaps[i]:
                        bcaps[i] = round_up_pow2(int(req))
                        grow = True
                if not grow:
                    return out
        self._step = step

    def execute_partition(self, idx: int) -> Iterator[ColumnarBatch]:
        for batch in self.children[0].execute_partition(idx):
            with timed(self.op_time):
                out = with_retry_no_split(lambda: self._step(batch))
            self.output_rows.add(out.num_rows)
            yield self._count_out(out)

    def describe(self):
        kind = "posexplode" if self.generator.POS else "explode"
        return (f"TpuGenerate[{'outer ' if self.outer else ''}{kind}"
                f"({self.generator.child!r})]")


def _array_ordinal(arr_expr, batch):
    """Ordinal of the array column when the generator input is a plain
    (possibly aliased) column reference; None for computed arrays."""
    from spark_rapids_tpu.expressions import core as E
    e = arr_expr
    while isinstance(e, E.Alias):
        e = e.child
    if isinstance(e, E.BoundReference):
        return e.ordinal
    return None
