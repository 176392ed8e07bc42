"""Window exec: partition-sorted segmented-scan evaluation.

Reference: window/GpuWindowExec.scala:145 (sorted window calc),
GpuRunningWindowExec (running frames).  The planner co-locates window
partitions via a hash exchange on the partition keys (as Spark plans
Window) so each task sees whole partitions; one lexsort + segmented scans
(kernels/window.py) produce every window column in a single jitted step.
"""
from __future__ import annotations

from typing import Iterator, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu.expressions.core import Alias, EvalContext, Expression
from spark_rapids_tpu.expressions.aggregates import (
    Average, Count, Max, Min, Sum)
from spark_rapids_tpu.expressions.window import (
    CumeDist, DenseRank, FirstValue, Lag, LastValue, Lead, NthValue, Ntile,
    PercentRank, Rank, RowNumber, WindowExpression, WindowFrame)
from spark_rapids_tpu.kernels import window as WK
from spark_rapids_tpu.kernels.groupby import (
    _rows_equal_prev, _string_rows_equal_prev, normalize_key_column)
from spark_rapids_tpu.kernels.selection import gather_batch
from spark_rapids_tpu.kernels.sort import (
    SortOrder, sort_indices, string_key_planes)
from spark_rapids_tpu.memory.retry import with_retry_no_split
from spark_rapids_tpu.plan.execs.base import TpuExec, string_key_bucket, timed
from spark_rapids_tpu.plan.execs.coalesce import (
    coalesce_to_one, retry_over_spillable)


def _unwrap(e: Expression) -> WindowExpression:
    return e.child if isinstance(e, Alias) else e


class _WindowDeviceSpec:
    """Device-step parameters + pure step functions, detached from the exec
    so shared_jit-cached steps never pin the exec tree (see base.shared_jit)."""

    def __init__(self, window_exprs, spec, schema):
        self.window_exprs = window_exprs
        self.spec = spec
        self.schema = schema

    def _step(self, batch: ColumnarBatch,
              string_bucket: int = 0) -> ColumnarBatch:
        ctx = EvalContext(batch)
        spec = self.spec
        pcols = [normalize_key_column(e.eval(ctx)) for e in spec.partition_by]
        ocols = [normalize_key_column(e.eval(ctx)) for e, _ in spec.order_by]
        nbase = len(batch.schema)
        work_cols = tuple(batch.columns) + tuple(pcols) + tuple(ocols)
        work = ColumnarBatch(
            work_cols, batch.num_rows,
            Schema(tuple(batch.schema.names)
                   + tuple(f"_p{i}" for i in range(len(pcols)))
                   + tuple(f"_o{i}" for i in range(len(ocols))),
                   tuple(c.dtype for c in work_cols)))
        key_idx = list(range(nbase, nbase + len(pcols) + len(ocols)))
        orders = ([SortOrder(True, True)] * len(pcols)
                  + [o for _, o in spec.order_by])
        planes = string_key_planes(work, key_idx, string_bucket)
        idx = sort_indices(work, key_idx, orders,
                           string_max_bytes=string_bucket,
                           string_planes=planes)
        # a string key's sorted copy is read by nobody and is compiled away
        sw = gather_batch(work, idx, work.num_rows)
        live = sw.live_mask()
        first = jnp.arange(sw.capacity, dtype=jnp.int32) == 0

        def eq_prev(ci):
            if ci in planes:
                return _string_rows_equal_prev(work.columns[ci], *planes[ci],
                                               idx)
            return _rows_equal_prev(sw.columns[ci])

        part_eq = jnp.ones((sw.capacity,), jnp.bool_)
        for i in range(len(pcols)):
            part_eq = part_eq & eq_prev(nbase + i)
        peer_eq = part_eq
        for i in range(len(ocols)):
            peer_eq = peer_eq & eq_prev(nbase + len(pcols) + i)
        part_boundary = live & (first | ~part_eq)
        peer_boundary = live & (first | ~peer_eq)
        layout = WK.window_layout(part_boundary, peer_boundary, live)

        sorted_input = ColumnarBatch(sw.columns[:nbase], sw.num_rows,
                                     batch.schema)
        sctx = EvalContext(sorted_input)
        out_cols: List[DeviceColumn] = list(sorted_input.columns)
        for e in self.window_exprs:
            out_cols.append(self._window_column(_unwrap(e), layout, sctx))
        return ColumnarBatch(tuple(out_cols), sw.num_rows, self.schema)

    def _positional_value(self, fn, frame, we, layout, sctx):
        """first/last/nth value: gather at the frame-boundary position.

        Frame bounds come from the same machinery the bounded aggregates
        use; nulls are respected (Spark default)."""
        c = fn.child.eval(sctx)
        cap = layout.pos.shape[0]
        if frame.is_unbounded_both():
            lower, upper = layout.seg_start, layout.seg_end - 1
        elif frame.kind == "range" and frame.is_unbounded_to_current():
            lower, upper = layout.seg_start, layout.run_last
        elif frame.kind == "rows":
            lower, upper = WK.frame_bounds_rows(
                layout, None if frame.start is None else -frame.start,
                frame.end)
        else:
            okey = we.spec.order_by[0][0].eval(sctx)
            lower, upper = WK.frame_bounds_range(
                okey.data, layout,
                None if frame.start is None else -frame.start, frame.end)
        if isinstance(fn, NthValue):
            at = lower + jnp.int32(fn.k - 1)
        elif isinstance(fn, LastValue):
            at = upper
        else:
            at = lower
        in_frame = (at >= lower) & (at <= upper) & layout.live
        safe = jnp.clip(at, 0, cap - 1)
        valid = in_frame & c.validity[safe]
        vals = jnp.where(valid, c.data[safe], jnp.zeros((), c.data.dtype))
        return DeviceColumn(vals, valid, fn.dtype)

    def _window_column(self, we: WindowExpression, layout: WK.WindowLayout,
                       sctx: EvalContext) -> DeviceColumn:
        fn = we.function
        frame = we.spec.frame
        if isinstance(fn, RowNumber):
            return DeviceColumn(WK.row_number(layout), layout.live, T.INT)
        if isinstance(fn, DenseRank):
            return DeviceColumn(WK.dense_rank(layout), layout.live, T.INT)
        if isinstance(fn, Rank):
            return DeviceColumn(WK.rank(layout), layout.live, T.INT)
        if isinstance(fn, (Lead, Lag)):
            c = fn.child.eval(sctx)
            off = fn.offset if not isinstance(fn, Lag) else -fn.offset
            vals, valid = WK.shift(c.data, c.validity, layout, off)
            return DeviceColumn(
                jnp.where(valid, vals, jnp.zeros((), vals.dtype)),
                valid, fn.dtype)
        if isinstance(fn, PercentRank):
            cnt = (layout.seg_end - layout.seg_start).astype(jnp.float64)
            rk = (layout.run_first - layout.seg_start).astype(jnp.float64)
            v = jnp.where(cnt > 1, rk / jnp.maximum(cnt - 1.0, 1.0), 0.0)
            return DeviceColumn(jnp.where(layout.live, v, 0.0),
                                layout.live, T.DOUBLE)
        if isinstance(fn, CumeDist):
            cnt = (layout.seg_end - layout.seg_start).astype(jnp.float64)
            le = (layout.run_last + 1 - layout.seg_start).astype(jnp.float64)
            v = le / jnp.maximum(cnt, 1.0)
            return DeviceColumn(jnp.where(layout.live, v, 0.0),
                                layout.live, T.DOUBLE)
        if isinstance(fn, Ntile):
            n_t = jnp.int32(fn.n)
            cnt = layout.seg_end - layout.seg_start
            r = layout.pos - layout.seg_start
            bs = cnt // n_t
            rem = cnt % n_t
            thr = rem * (bs + 1)
            big = r // jnp.maximum(bs + 1, 1) + 1
            small = rem + (r - thr) // jnp.maximum(bs, 1) + 1
            v = jnp.where(bs == 0, r + 1, jnp.where(r < thr, big, small))
            return DeviceColumn(
                jnp.where(layout.live, v.astype(jnp.int32), 0),
                layout.live, T.INT)
        if isinstance(fn, (FirstValue, LastValue, NthValue)):
            return self._positional_value(fn, frame, we, layout, sctx)

        # aggregate window functions
        out_dt = fn.dtype
        if fn.input is not None:
            c = fn.input.eval(sctx)
            vals, valid = c.data, c.validity
        else:
            vals = jnp.zeros((layout.pos.shape[0],), jnp.int64)
            valid = jnp.ones((layout.pos.shape[0],), jnp.bool_)

        def from_sum_count(s, n):
            if isinstance(fn, Count):
                return DeviceColumn(n.astype(jnp.int64), layout.live, T.LONG)
            if isinstance(fn, Average):
                ok = (n > 0) & layout.live
                avg = s.astype(jnp.float64) / jnp.where(n > 0, n, 1)
                return DeviceColumn(jnp.where(ok, avg, 0.0), ok, T.DOUBLE)
            ok = (n > 0) & layout.live
            sv = s.astype(out_dt.jnp_dtype)
            return DeviceColumn(jnp.where(ok, sv, jnp.zeros((), sv.dtype)),
                                ok, out_dt)

        def bounded(lower, upper):
            """Any [lower, upper]-position frame: sum/count via prefix
            sums, min/max via the sparse-table kernel."""
            if isinstance(fn, (Min, Max)):
                is_min = isinstance(fn, Min)
                v_in = vals
                nonnan_valid = valid
                if jnp.issubdtype(vals.dtype, jnp.floating):
                    isnan = jnp.isnan(vals)
                    nonnan_valid = valid & ~isnan
                    if is_min:
                        # Spark: NaN is the LARGEST value — min ignores it
                        # unless the frame is all-NaN
                        v_in = jnp.where(isnan, jnp.inf, vals)
                v, _ = WK.bounded_min_max(v_in, valid if not is_min
                                          else nonnan_valid,
                                          layout, lower, upper, is_min)
                _, n = WK.bounded_sum_count(vals, valid, layout, lower,
                                            upper, sum_dt)
                ok = (n > 0) & layout.live
                if jnp.issubdtype(vals.dtype, jnp.floating) and is_min:
                    _, n_nonnan = WK.bounded_sum_count(
                        vals, nonnan_valid, layout, lower, upper, sum_dt)
                    v = jnp.where((n > 0) & (n_nonnan == 0),
                                  jnp.asarray(jnp.nan, v.dtype), v)
                if jnp.issubdtype(vals.dtype, jnp.floating) and not is_min:
                    # any NaN in frame -> NaN: maximum() propagates only
                    # when NaN is scanned; the sparse table uses maximum
                    # so propagation already holds
                    pass
                v = v.astype(out_dt.jnp_dtype)
                return DeviceColumn(
                    jnp.where(ok, v, jnp.zeros((), v.dtype)), ok, out_dt)
            s, n = WK.bounded_sum_count(vals, valid, layout, lower, upper,
                                        sum_dt)
            return from_sum_count(s, n)

        sum_dt = (jnp.float64 if out_dt.is_floating or isinstance(fn, Average)
                  else jnp.int64)
        if frame.kind == "range" and not (
                frame.is_unbounded_both()
                or frame.is_unbounded_to_current()):
            # bounded RANGE frame over the single numeric order key
            # (planner guarantees one ascending fixed-width key)
            okey = we.spec.order_by[0][0].eval(sctx)
            lower, upper = WK.frame_bounds_range(
                okey.data, layout,
                None if frame.start is None else -frame.start, frame.end)
            return bounded(lower, upper)
        if frame.kind == "rows" and isinstance(fn, (Min, Max)):
            lower, upper = WK.frame_bounds_rows(
                layout,
                None if frame.start is None else -frame.start, frame.end)
            return bounded(lower, upper)
        if frame.is_unbounded_both():
            if isinstance(fn, (Min, Max)):
                op = "min" if isinstance(fn, Min) else "max"
                v, n = WK.whole_partition_agg(vals, valid, layout, op, sum_dt)
                ok = (n > 0) & layout.live
                return DeviceColumn(jnp.where(ok, v, jnp.zeros((), v.dtype)),
                                    ok, out_dt)
            op = "count" if isinstance(fn, Count) else "sum"
            s, n = WK.whole_partition_agg(vals, valid, layout, "sum", sum_dt)
            return from_sum_count(s, n)
        if frame.kind == "range" and frame.is_unbounded_to_current():
            if isinstance(fn, Min):
                ident = jnp.asarray(jnp.inf, vals.dtype) \
                    if jnp.issubdtype(vals.dtype, jnp.floating) \
                    else jnp.iinfo(vals.dtype).max
                v = WK.running_min_range(vals, valid, layout, ident)
                _, n = WK.running_sum_range(vals, valid, layout, sum_dt)
                ok = (n > 0) & layout.live
                return DeviceColumn(jnp.where(ok, v, jnp.zeros((), v.dtype)),
                                    ok, out_dt)
            if isinstance(fn, Max):
                ident = jnp.asarray(-jnp.inf, vals.dtype) \
                    if jnp.issubdtype(vals.dtype, jnp.floating) \
                    else jnp.iinfo(vals.dtype).min
                v = WK.running_max_range(vals, valid, layout, ident)
                _, n = WK.running_sum_range(vals, valid, layout, sum_dt)
                ok = (n > 0) & layout.live
                return DeviceColumn(jnp.where(ok, v, jnp.zeros((), v.dtype)),
                                    ok, out_dt)
            s, n = WK.running_sum_range(vals, valid, layout, sum_dt)
            return from_sum_count(s, n)
        # ROWS frame
        s, n = WK.rows_frame_sum(
            vals, valid, layout,
            None if frame.start is None else -frame.start,
            frame.end, sum_dt)
        return from_sum_count(s, n)


#: two-pass unbounded-agg fallback threshold: beyond this many distinct
#: partition keys the host merge loop dominates and key-batching wins
_TWO_PASS_MAX_KEYS = 65536


def _extreme_merge(x, y, is_min: bool):
    """Merge two per-batch (value, valid) extremes with Spark's total
    order (NaN greatest; MIN prefers non-NaN, MAX prefers NaN)."""
    import math
    (vx, okx), (vy, oky) = x, y
    if not okx:
        return y
    if not oky:
        return x
    x_nan = isinstance(vx, float) and math.isnan(vx)
    y_nan = isinstance(vy, float) and math.isnan(vy)
    if is_min:
        if x_nan:
            return y
        if y_nan:
            return x
        return x if vx <= vy else y
    if x_nan:
        return x
    if y_nan:
        return y
    return x if vx >= vy else y


def _merge_slots(a, b, specs):
    """Combine two hosts' per-key partial states (pass-1 merge)."""
    out = []
    i = 0
    for kind, _inp, _dt in specs:
        if kind == "count":
            out.append((a[i][0] + b[i][0], True))
            i += 1
        elif kind in ("sum", "average"):
            (sa, va), (sb, vb) = a[i], b[i]
            s = (sa + sb) if (va and vb) else (sa if va else sb)
            out.append((s, va or vb))
            out.append((a[i + 1][0] + b[i + 1][0], True))
            i += 2
        else:
            out.append(_extreme_merge(a[i], b[i], kind == "min"))
            i += 1
    return out


def _finalize_slots(slots, specs):
    """Per-key merged state -> final (value, valid) per window expr."""
    out = []
    i = 0
    for kind, _inp, _dt in specs:
        if kind == "count":
            out.append((slots[i][0], True))
            i += 1
        elif kind == "sum":
            s, v = slots[i]
            n = slots[i + 1][0]
            ok = bool(n > 0 and v)
            out.append((s if ok else None, ok))
            i += 2
        elif kind == "average":
            s, v = slots[i]
            n = slots[i + 1][0]
            ok = bool(n > 0 and v)
            out.append(((s / n) if ok else None, ok))
            i += 2
        else:
            out.append(slots[i])
            i += 1
    return out


class TpuWindowExec(TpuExec):
    def __init__(self, window_exprs: Sequence[Expression], child: TpuExec,
                 schema: Schema, target_rows: int = 1 << 20):
        super().__init__((child,), schema)
        self.window_exprs = tuple(window_exprs)
        self.spec = _unwrap(self.window_exprs[0]).spec
        self.target_rows = max(int(target_rows), 1)
        dspec = _WindowDeviceSpec(self.window_exprs, self.spec, schema)
        from functools import partial as _p
        from spark_rapids_tpu.plan.execs.base import (
            exprs_cache_key, schema_cache_key, shared_jit)
        key = (f"window|{schema_cache_key(child.schema)}|"
               f"{schema_cache_key(schema)}|"
               f"{exprs_cache_key(self.window_exprs)}")
        self._run = lambda b, _k=key: shared_jit(
            f"{_k}|{(bkt := string_key_bucket(b, list(self.spec.partition_by) + [e for e, _ in self.spec.order_by]))}",
            lambda: _p(dspec._step, string_bucket=bkt), kind="window")(b)

    def execute_partition(self, idx: int) -> Iterator[ColumnarBatch]:
        batches = list(self.children[0].execute_partition(idx))
        if not batches:
            return
        total = sum(b.capacity for b in batches)
        if total > self.target_rows:
            if self._two_pass_capable():
                # unbounded-agg state machine: handles ONE partition key
                # larger than any batch (key-batching can't split it)
                yield from self._execute_two_pass(batches)
                return
            if self._partition_ordinals() is not None:
                yield from self._execute_out_of_core(batches, total)
                return
        with timed(self.op_time):
            # coalesce INSIDE the retry body: a discarded concat result
            # re-runs after the spill instead of pinning HBM from the
            # closure
            out = with_retry_no_split(
                lambda: self._run(coalesce_to_one(batches)))
        self.output_rows.add(out.num_rows)
        yield self._count_out(out)

    # -- two-pass UNBOUNDED-to-UNBOUNDED agg windows -------------------------
    # (reference: window/GpuUnboundedToUnboundedAggWindowExec.scala — the
    # state machine for partitions larger than any batch: the answer per
    # row is the PARTITION-constant aggregate, so pass 1 streams batches
    # through a per-batch grouped partial agg and merges the tiny per-key
    # states on the host; pass 2 maps them back per batch with an
    # order-preserving left join.  Memory: O(batch + distinct keys),
    # independent of partition size.)

    def _two_pass_capable(self) -> bool:
        if self.spec.partition_by and self._partition_ordinals() is None:
            return False
        child_schema = self.children[0].schema
        for o in (self._partition_ordinals() or []):
            dt = child_schema.dtypes[o]
            if dt.variable_width or isinstance(
                    dt, (T.ArrayType, T.StructType, T.MapType)):
                return False
        for e in self.window_exprs:
            we = _unwrap(e)
            if not isinstance(we, WindowExpression):
                return False
            if not we.spec.frame.is_unbounded_both():
                return False
            fn = we.function
            if not isinstance(fn, (Sum, Count, Min, Max, Average)):
                return False
            if fn.input is not None:
                dt = fn.input.dtype
                if (dt.variable_width or isinstance(
                        dt, (T.DecimalType, T.ArrayType, T.StructType,
                             T.MapType))):
                    return False
        return True

    def _fn_specs(self):
        """(kind, input_expr, out_dtype) per window expression."""
        out = []
        for e in self.window_exprs:
            fn = _unwrap(e).function
            kind = type(fn).__name__.lower()
            out.append((kind, fn.input, fn.dtype))
        return out

    def _totals_step(self, key_ords, specs):
        """Jitted per-batch partial: keys + per-fn merge buffers."""
        def step(batch: ColumnarBatch, string_bucket: int = 0):
            import spark_rapids_tpu.kernels.groupby as G
            layout = G.group_rows(batch, list(key_ords),
                                  string_max_bytes=string_bucket)
            cols: List[jax.Array] = []
            for c in G.group_keys_output(layout, list(key_ords)):
                cols.append((c.data, c.validity))
            sctx = EvalContext(layout.sorted_batch)
            for kind, inp, out_dt in specs:
                if inp is None:           # count(*)
                    n, _ = G.seg_count_star(layout)
                    cols.append((n.astype(jnp.int64),
                                 jnp.ones(n.shape, jnp.bool_)))
                    continue
                c = inp.eval(sctx)
                if kind == "count":
                    n, _ = G.seg_count_valid(c, layout)
                    cols.append((n.astype(jnp.int64),
                                 jnp.ones(n.shape, jnp.bool_)))
                elif kind in ("sum", "average"):
                    sdt = (jnp.float64 if out_dt.is_floating
                           or kind == "average" else jnp.int64)
                    sv, svalid = G.seg_sum(c, layout, sdt)
                    n, _ = G.seg_count_valid(c, layout)
                    cols.append((sv, svalid))
                    cols.append((n.astype(jnp.int64),
                                 jnp.ones(n.shape, jnp.bool_)))
                elif kind == "min":
                    v, valid = G.seg_min(c, layout)
                    cols.append((v, valid))
                else:
                    v, valid = G.seg_max(c, layout)
                    cols.append((v, valid))
            return tuple(cols), layout.num_groups
        return step

    def _execute_two_pass(self, batches) -> Iterator[ColumnarBatch]:
        import numpy as np

        from spark_rapids_tpu.memory.spill import make_spillable
        from spark_rapids_tpu.plan.execs.base import (
            exprs_cache_key, schema_cache_key, shared_jit)

        key_ords = self._partition_ordinals() or []
        specs = self._fn_specs()
        child_schema = self.children[0].schema
        base_key = (f"window2p|{schema_cache_key(child_schema)}|"
                    f"{exprs_cache_key(self.window_exprs)}")
        step = self._totals_step(key_ords, specs)
        handles = [make_spillable(b) for b in batches]
        del batches

        # pass 1: stream, host-merge tiny per-key states.  Key identity
        # uses Spark normalization (NaN is ONE group; -0.0 == 0.0) —
        # python dict identity on raw floats splits NaN groups per batch,
        # and the device join (which canonicalizes NaN) would then fan
        # out duplicate rows.
        import math

        def canon(v):
            if isinstance(v, float):
                if math.isnan(v):
                    return "\0nan"
                if v == 0.0:
                    return 0.0
            return v

        state = {}      # canonical key tuple -> per-slot merge values
        originals = {}  # canonical key tuple -> representative raw key
        for h in handles:
            b = h.materialize()
            try:
                with timed(self.op_time):
                    cols, ngroups = with_retry_no_split(
                        lambda: shared_jit(
                            f"{base_key}|p1|{b.capacity}",
                            lambda: step, kind="window_ooc")(b))
            finally:
                # a retry-exhausted OOM must not leave this batch's pin
                # held — the handle would refuse to spill for the rest
                # of the query
                h.unpin()
            ng = int(ngroups)
            if ng > _TWO_PASS_MAX_KEYS:
                # a single batch already exceeds the key budget: bail
                # BEFORE paying the O(groups) host loop below.  (ng alone,
                # not len(state)+ng — groups repeat across batches, and
                # double-counting them would spuriously evict workloads
                # the two-pass path handles; the post-merge check below
                # remains the authoritative cumulative bound.)
                rebatched = [hh.release_device_copy() for hh in handles]
                total = sum(bb.capacity for bb in rebatched)
                yield from self._execute_out_of_core(rebatched, total)
                return
            host = [(np.asarray(d)[:ng], np.asarray(v)[:ng])
                    for d, v in cols]
            nk = len(key_ords)
            for g in range(ng):
                raw = tuple(
                    (None if not host[i][1][g] else host[i][0][g].item())
                    for i in range(nk))
                key = tuple(canon(v) for v in raw)
                slots = [(host[i][0][g].item(), bool(host[i][1][g]))
                         for i in range(nk, len(host))]
                cur = state.get(key)
                if cur is None:
                    originals[key] = raw
                state[key] = slots if cur is None else \
                    _merge_slots(cur, slots, specs)
            if len(state) > _TWO_PASS_MAX_KEYS:
                # cumulative distinct keys blew the budget: the host
                # merge would dominate — reroute to key-batching.
                rebatched = [hh.release_device_copy() for hh in handles]
                total = sum(bb.capacity for bb in rebatched)
                yield from self._execute_out_of_core(rebatched, total)
                return

        # finalize per-key window values (keyed by the REPRESENTATIVE raw
        # key so NaN re-materializes as a float in the build table)
        values = {originals[k]: _finalize_slots(sl, specs)
                  for k, sl in state.items()}

        # pass 2: map values back per batch, order-preserving
        if not key_ords:
            (vals,) = [values.get((), [(None, False)] * len(specs))]
            for h in handles:
                b = h.materialize()
                try:
                    out = self._broadcast_constants(b, vals)
                finally:
                    h.unpin()
                h.close()
                self.output_rows.add(out.num_rows)
                yield self._count_out(out)
            return

        build = self._build_values_batch(key_ords, child_schema, values)
        joiner = self._two_pass_joiner(key_ords, child_schema)
        for h in handles:
            b = h.materialize()
            try:
                with timed(self.op_time):
                    out = self._join_values(b, build, joiner, key_ords)
            finally:
                h.unpin()
            h.close()
            self.output_rows.add(out.num_rows)
            yield self._count_out(out)

    def _broadcast_constants(self, b: ColumnarBatch, vals):
        """Empty PARTITION BY: one global group — append constants."""
        cols = list(b.columns)
        live = b.live_mask()
        for (v, valid), (_k, _i, out_dt) in zip(vals, self._fn_specs()):
            data = jnp.full((b.capacity,),
                            v if valid and v is not None else 0,
                            out_dt.jnp_dtype)
            cols.append(DeviceColumn(
                jnp.where(live & valid, data,
                          jnp.zeros((), out_dt.jnp_dtype)),
                live & bool(valid), out_dt))
        return ColumnarBatch(tuple(cols), b.num_rows, self.schema)

    def _build_values_batch(self, key_ords, child_schema, values):
        """Small device table: normalized keys + null flags + values."""
        import numpy as np
        keys = list(values.keys())
        data = {}
        names = []
        dtypes = []
        for i, o in enumerate(key_ords):
            dt = child_schema.dtypes[o]
            data[f"_k{i}"] = [0 if k[i] is None else k[i] for k in keys]
            data[f"_kn{i}"] = [k[i] is None for k in keys]
            names += [f"_k{i}", f"_kn{i}"]
            dtypes += [dt, T.BOOLEAN]
        for j, (_kind, _inp, out_dt) in enumerate(self._fn_specs()):
            col_vals = []
            for k in keys:
                v, valid = values[k][j]
                col_vals.append(v if valid and v is not None else None)
            data[f"_w{j}"] = col_vals
            names.append(f"_w{j}")
            dtypes.append(out_dt)
        sch = Schema(tuple(names), tuple(dtypes))
        return ColumnarBatch.from_pydict(data, sch)

    def _probe_schema(self, key_ords, child_schema) -> Schema:
        """Input batch + normalized keys + null flags (single source of
        truth for the probe layout — the joiner and per-batch prep must
        agree on these ordinals)."""
        nk = len(key_ords)
        names = (tuple(child_schema.names)
                 + tuple(f"_lk{i}" for i in range(nk))
                 + tuple(f"_lkn{i}" for i in range(nk)))
        dtypes = (tuple(child_schema.dtypes)
                  + tuple(child_schema.dtypes[o] for o in key_ords)
                  + tuple(T.BOOLEAN for _ in key_ords))
        return Schema(names, dtypes)

    def _two_pass_joiner(self, key_ords, child_schema):
        from spark_rapids_tpu.plan.execs.join import _JoinKernel
        nk = len(key_ords)
        left = self._probe_schema(key_ords, child_schema)
        right = self._build_values_schema(key_ords, child_schema)
        join_schema = Schema(tuple(left.names) + tuple(right.names),
                             tuple(left.dtypes) + tuple(right.dtypes))
        n = len(child_schema)
        left_keys = [n + i for i in range(nk)] + \
            [n + nk + i for i in range(nk)]
        right_keys = list(range(0, 2 * nk, 2)) + \
            list(range(1, 2 * nk, 2))
        return _JoinKernel(left_keys, right_keys, "left", join_schema)

    def _build_values_schema(self, key_ords, child_schema):
        names = []
        dtypes = []
        for i, o in enumerate(key_ords):
            names += [f"_k{i}", f"_kn{i}"]
            dtypes += [child_schema.dtypes[o], T.BOOLEAN]
        for j, (_k, _i, out_dt) in enumerate(self._fn_specs()):
            names.append(f"_w{j}")
            dtypes.append(out_dt)
        return Schema(tuple(names), tuple(dtypes))

    def _join_values(self, b: ColumnarBatch, build, joiner, key_ords):
        live = b.live_mask()
        cols = list(b.columns)
        for o in key_ords:
            c = b.columns[o]
            cols.append(DeviceColumn(
                jnp.where(c.validity, c.data,
                          jnp.zeros((), c.data.dtype)),
                live, c.dtype))
        for o in key_ords:
            c = b.columns[o]
            cols.append(DeviceColumn(~c.validity & live, live, T.BOOLEAN))
        probe = ColumnarBatch(tuple(cols), b.num_rows,
                              self._probe_schema(key_ords, b.schema))
        joined = joiner(probe, build)
        n = len(b.schema)
        nfn = len(self.window_exprs)
        out_cols = joined.columns[:n] + joined.columns[-nfn:]
        return ColumnarBatch(tuple(out_cols), joined.num_rows, self.schema)

    def _partition_ordinals(self):
        """Column ordinals of the PARTITION BY keys, or None if any key is
        not a plain reference (then the key-batched path can't route)."""
        from spark_rapids_tpu.expressions.core import Alias, BoundReference
        if not self.spec.partition_by:
            return None
        out = []
        for e in self.spec.partition_by:
            while isinstance(e, Alias):
                e = e.child
            if not isinstance(e, BoundReference):
                return None
            out.append(e.ordinal)
        return out

    def _execute_out_of_core(self, batches, total) -> Iterator[ColumnarBatch]:
        """Key-batched windows (GpuKeyBatchingIterator.scala:37 analog):
        hash-repartition the input on the PARTITION BY keys into spillable
        key-disjoint buckets and window each bucket independently — frames
        never cross partition values, so the union of bucket outputs is
        exactly the single-batch answer."""
        from spark_rapids_tpu.plan.execs.out_of_core import (
            close_all, num_sub_buckets, sub_partition_spillable)
        n_b = num_sub_buckets(total, self.target_rows)
        with timed(self.op_time):
            buckets = sub_partition_spillable(
                iter(batches), self._partition_ordinals(), n_b,
                self.children[0].schema)
            del batches
        try:
            for q in buckets:
                if not q:
                    continue
                with timed(self.op_time):
                    # pin-balanced retry: each attempt re-materializes
                    # the handles and unpins before it ends (see
                    # coalesce.retry_over_spillable)
                    out = retry_over_spillable(q, self._run)
                    for h in q:
                        h.close()
                    q.clear()
                self.output_rows.add(out.num_rows)
                yield self._count_out(out)
        finally:
            close_all(buckets)

    def describe(self):
        return f"TpuWindow[{', '.join(map(repr, self.window_exprs))}]"
