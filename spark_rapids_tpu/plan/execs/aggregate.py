"""Hash-aggregate exec: two-phase (partial/final) columnar aggregation.

Reference: GpuAggregateExec.scala — AggHelper's update/merge split (:360),
first-pass iterator (:730), merge-on-concat (:130-147).  The TPU lowering
replaces cuDF's hash groupby with sort-based segmented reduction
(kernels/groupby.py) — a shape-static pipeline XLA maps onto sorts and
scatter-reduces.

Modes (matching Spark's physical agg modes the reference plans):
  * partial:  raw rows -> (keys..., buffer slots...) partial batches
  * final:    partial batches -> finalized output (after a key shuffle)
  * complete: both fused (single-partition plans)

The per-batch partial step and the merge step are each one jitted function;
group count is dynamic, capacities static.
"""
from __future__ import annotations

import itertools
from typing import Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import (ColumnarBatch, Schema,
                                              host_scalar)
from spark_rapids_tpu.columnar.column import DeviceColumn, round_up_pow2
from spark_rapids_tpu.expressions.core import (
    EvalContext,
    Expression,
)
from spark_rapids_tpu.expressions.aggregates import (
    BIT_OPS,
    COLLECT,
    COLLECT_MERGE,
    COUNT_STAR,
    COUNT_VALID,
    HLL_MERGE,
    HLL_UPDATE,
    M2,
    M2_MERGE,
    MAX,
    MAX128,
    MAXBY_VAL,
    MIN,
    MIN128,
    MINBY_VAL,
    PICK_OPS,
    SUM,
    SUM128,
    TD_MEANS,
    TD_MEANS_MERGE,
    TD_WEIGHTS,
    TD_WEIGHTS_MERGE,
    AggregateFunction,
)
from spark_rapids_tpu.kernels import groupby as G
from spark_rapids_tpu.kernels.selection import concat_batches_device
from spark_rapids_tpu.memory.retry import with_capacity_retry, with_retry_no_split
from spark_rapids_tpu.plan.execs.base import TpuExec, string_key_bucket, timed


class _DeviceAggResult(Expression):
    """Internal: finalized aggregate column injected into output-expression
    eval (the device twin of the CPU oracle's substitution)."""

    def __init__(self, column: DeviceColumn):
        self.column = column
        self.children = ()

    @property
    def dtype(self):
        return self.column.dtype

    def eval(self, ctx):
        return self.column

    def __repr__(self):
        return "<agg-result>"


def _substitute(e: Expression, mapping) -> Expression:
    if isinstance(e, AggregateFunction):
        return _DeviceAggResult(mapping[id(e)])
    if not e.children:
        return e
    return e.with_children(tuple(_substitute(c, mapping) for c in e.children))


def _seg_update(op: str, col: Optional[DeviceColumn], layout: G.GroupedLayout,
                out_dtype: T.DataType):
    if op == COUNT_STAR:
        return G.seg_count_star(layout)
    assert col is not None
    if op == COUNT_VALID:
        return G.seg_count_valid(col, layout)
    if op == SUM:
        return G.seg_sum(col, layout, out_dtype.jnp_dtype)
    if op == M2:
        return G.seg_m2_update(col, layout)
    if op == MIN:
        return G.seg_min(col, layout)
    if op == MAX:
        return G.seg_max(col, layout)
    raise NotImplementedError(op)


def _seg_sum128(col: DeviceColumn, count_col: Optional[DeviceColumn],
                layout: G.GroupedLayout,
                out_dtype: T.DataType) -> DeviceColumn:
    """Exact int128 segmented sum of decimal values (update) or partial
    sums (merge, count_col given).  A NULL partial sum with a non-zero
    count is an overflow marker and poisons its group (SPARK-28067
    semantics); fresh overflow beyond the buffer precision nulls too."""
    from spark_rapids_tpu.kernels import decimal as DK
    live = layout.live_mask()
    valid = col.validity & live
    cap = col.capacity
    hi, lo = DK.limbs_of(col, col.dtype)
    h, l, ov = DK.segment_sum128(hi, lo, valid, layout.segment_ids, cap)
    nvalid = jax.ops.segment_sum(valid.astype(jnp.int32),
                                 layout.segment_ids, num_segments=cap)
    out_valid = (nvalid > 0) & ~ov
    if count_col is not None:
        poison = jax.ops.segment_max(
            (live & ~col.validity
             & (count_col.data > 0)).astype(jnp.int32),
            layout.segment_ids, num_segments=cap) > 0
        out_valid = out_valid & ~poison
    out_valid = out_valid & ~DK.overflow(h, l, out_dtype.precision)
    group_live = jnp.arange(cap, dtype=jnp.int32) < layout.num_groups
    return DK.make_column128(h, l, out_valid & group_live, out_dtype)


def _seg_extreme128(col: DeviceColumn, layout: G.GroupedLayout,
                    out_dtype: T.DataType, is_min: bool) -> DeviceColumn:
    """Segmented min/max over two-limb decimal columns (update AND merge:
    min of mins is min).  Null inputs/partials are simply excluded."""
    from spark_rapids_tpu.kernels import decimal as DK
    live = layout.live_mask()
    valid = col.validity & live
    cap = col.capacity
    hi, lo = DK.limbs_of(col, col.dtype)
    h, l, ok = DK.segment_extreme128(hi, lo, valid, layout.segment_ids,
                                     cap, is_min)
    group_live = jnp.arange(cap, dtype=jnp.int32) < layout.num_groups
    return DK.make_column128(h, l, ok & group_live, out_dtype)


def _global_extreme128(col: DeviceColumn, live, out_dtype: T.DataType,
                       is_min: bool) -> DeviceColumn:
    from spark_rapids_tpu.kernels import decimal as DK
    valid = col.validity & live
    hi, lo = DK.limbs_of(col, col.dtype)
    seg = jnp.zeros(hi.shape, jnp.int32)
    h, l, ok = DK.segment_extreme128(hi, lo, valid, seg, 1, is_min)
    return DK.make_column128(h, l, ok, out_dtype)


def _global_sum128(col: DeviceColumn, count_col: Optional[DeviceColumn],
                   live, out_dtype: T.DataType) -> DeviceColumn:
    from spark_rapids_tpu.kernels import decimal as DK
    valid = col.validity & live
    hi, lo = DK.limbs_of(col, col.dtype)
    h, l, ov = DK.sum128(hi, lo, valid)
    nvalid = jnp.sum(valid.astype(jnp.int32))
    out_valid = (nvalid > 0) & ~ov
    if count_col is not None:
        poison = jnp.any(live & ~col.validity & (count_col.data > 0))
        out_valid = out_valid & ~poison
    out_valid = out_valid & ~DK.overflow(h, l, out_dtype.precision)
    return DK.make_column128(jnp.reshape(h, (1,)), jnp.reshape(l, (1,)),
                             jnp.reshape(out_valid, (1,)), out_dtype)


def _collect_update(col: DeviceColumn, layout: Optional[G.GroupedLayout],
                    live, num_groups) -> DeviceColumn:
    """COLLECT buffer update: the group's valid values as one array row
    (values already contiguous per group in the sorted layout; stable
    compaction preserves that grouping)."""
    from spark_rapids_tpu.kernels.selection import compaction_map
    cap = col.capacity
    valid = col.validity & live
    idx, total = compaction_map(valid)
    ecap = cap
    vals = col.data.astype(jnp.float64)[jnp.clip(idx, 0, cap - 1)]
    epos = jnp.arange(ecap, dtype=jnp.int32)
    cvalid = epos < total
    data = jnp.where(cvalid, vals, 0.0)
    if layout is None:
        offsets = jnp.minimum(
            jnp.arange(cap + 1, dtype=jnp.int32),
            1) * total.astype(jnp.int32)
        validity = jnp.arange(cap, dtype=jnp.int32) < 1
        ng = 1
    else:
        counts = jax.ops.segment_sum(valid.astype(jnp.int32),
                                     layout.segment_ids, num_segments=cap)
        csum = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                jnp.cumsum(counts).astype(jnp.int32)])
        gidx = jnp.minimum(jnp.arange(cap + 1, dtype=jnp.int32), num_groups)
        offsets = csum[gidx]
        validity = jnp.arange(cap, dtype=jnp.int32) < num_groups
    return DeviceColumn(data, validity,
                        T.ArrayType(T.DoubleType(), contains_null=False),
                        offsets, cvalid)


def _collect_merge(col: DeviceColumn, layout: Optional[G.GroupedLayout],
                   live, num_groups) -> DeviceColumn:
    """COLLECT merge: concatenate partial array rows per group.  Entries
    of the key-sorted rows are already in segment order; compact away
    entries of dead rows and rebuild offsets from per-group entry sums."""
    from spark_rapids_tpu.kernels.collections import (
        element_live_mask, element_row_ids)
    cap = col.capacity
    ecap = col.byte_capacity
    row_valid = col.validity & live
    lengths = col.offsets[1:] - col.offsets[:-1]
    keep_len = jnp.where(row_valid, lengths, 0)
    erows = element_row_ids(col)
    nrows = jnp.sum(live.astype(jnp.int32))
    elive = element_live_mask(col, nrows) & row_valid[erows] \
        & (col.child_validity
           if col.child_validity is not None
           else jnp.ones((ecap,), jnp.bool_))
    from spark_rapids_tpu.kernels.selection import compaction_map
    eidx, etotal = compaction_map(elive)
    data = jnp.where(jnp.arange(ecap, dtype=jnp.int32) < etotal,
                     col.data[jnp.clip(eidx, 0, ecap - 1)], 0.0)
    cvalid = jnp.arange(ecap, dtype=jnp.int32) < etotal
    if layout is None:
        offsets = jnp.minimum(
            jnp.arange(cap + 1, dtype=jnp.int32),
            1) * etotal.astype(jnp.int32)
        validity = jnp.arange(cap, dtype=jnp.int32) < 1
    else:
        gcounts = jax.ops.segment_sum(keep_len.astype(jnp.int32),
                                      layout.segment_ids, num_segments=cap)
        csum = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                jnp.cumsum(gcounts).astype(jnp.int32)])
        gidx = jnp.minimum(jnp.arange(cap + 1, dtype=jnp.int32), num_groups)
        offsets = csum[gidx]
        validity = jnp.arange(cap, dtype=jnp.int32) < num_groups
    return DeviceColumn(data, validity,
                        T.ArrayType(T.DoubleType(), contains_null=False),
                        offsets, cvalid)


def _hll_array_col(regs2d, num_groups, cap: int, m: int) -> DeviceColumn:
    """Pack [cap, m] registers into a canonical fixed-length array column."""
    from spark_rapids_tpu import types as T
    ng = num_groups.astype(jnp.int32) if hasattr(num_groups, "astype") \
        else jnp.int32(num_groups)
    offs = jnp.minimum(jnp.arange(cap + 1, dtype=jnp.int32), ng) * m
    elem_live = jnp.arange(cap * m, dtype=jnp.int32) < ng * m
    data = jnp.where(elem_live, regs2d.reshape(-1), jnp.int8(0))
    validity = jnp.arange(cap, dtype=jnp.int32) < ng
    return DeviceColumn(data, validity,
                        T.ArrayType(T.ByteType(), contains_null=False),
                        offs, elem_live)


def _hll_regs2d(col: DeviceColumn, cap: int, m: int):
    """Array-column rows (fixed length m, packed) -> [cap, m] registers."""
    need = cap * m
    data = col.data
    if data.shape[0] < need:
        data = jnp.concatenate(
            [data, jnp.zeros((need - data.shape[0],), data.dtype)])
    return data[:need].reshape(cap, m)


def _global_update(op: str, col: Optional[DeviceColumn], live, out_dtype):
    """Whole-batch reduction to one group (no keys)."""
    if op == COUNT_STAR:
        return jnp.sum(live.astype(jnp.int64)), jnp.bool_(True)
    assert col is not None
    valid = col.validity & live
    if op == COUNT_VALID:
        return jnp.sum(valid.astype(jnp.int64)), jnp.bool_(True)
    nvalid = jnp.sum(valid.astype(jnp.int32))
    if op == SUM:
        vals = col.data.astype(out_dtype.jnp_dtype)
        return jnp.sum(jnp.where(valid, vals, 0)), nvalid > 0
    if op == M2:
        x = col.data.astype(jnp.float64)
        nf = jnp.sum(valid.astype(jnp.float64))
        mean = jnp.sum(jnp.where(valid, x, 0.0)) / jnp.maximum(nf, 1.0)
        d = x - mean
        return jnp.sum(jnp.where(valid, d * d, 0.0)), nvalid > 0
    if op in (MIN, MAX):
        dt = col.data.dtype
        is_min = op == MIN
        if jnp.issubdtype(dt, jnp.floating):
            isnan = jnp.isnan(col.data)
            ident = G._extreme(dt, is_min)
            contrib = jnp.where(valid & ~isnan, col.data, ident)
            red = jnp.min(contrib) if is_min else jnp.max(contrib)
            if is_min:
                any_nonnan = jnp.sum((valid & ~isnan).astype(jnp.int32)) > 0
                red = jnp.where(any_nonnan, red, jnp.full((), jnp.nan, dt))
            else:
                any_nan = jnp.sum((valid & isnan).astype(jnp.int32)) > 0
                red = jnp.where(any_nan, jnp.full((), jnp.nan, dt), red)
            return red, nvalid > 0
        ident = G._extreme(dt if dt != jnp.bool_ else jnp.bool_, is_min)
        contrib = jnp.where(valid, col.data, ident)
        if dt == jnp.bool_:
            contrib = contrib.astype(jnp.int8)
        red = jnp.min(contrib) if is_min else jnp.max(contrib)
        if dt == jnp.bool_:
            red = red.astype(jnp.bool_)
        return red, nvalid > 0
    raise NotImplementedError(op)


def _global_m2_merge(m2col: DeviceColumn, scol: DeviceColumn,
                     ncol: DeviceColumn, live):
    """Chan's merge over all partial rows, one output group (no keys)."""
    valid = m2col.validity & live
    n_i = jnp.where(valid, ncol.data.astype(jnp.float64), 0.0)
    s_i = jnp.where(valid, scol.data.astype(jnp.float64), 0.0)
    m2_i = jnp.where(valid, m2col.data.astype(jnp.float64), 0.0)
    n = jnp.sum(n_i)
    mean = jnp.sum(s_i) / jnp.maximum(n, 1.0)
    mean_i = s_i / jnp.maximum(n_i, 1.0)
    delta = mean_i - mean
    m2 = jnp.sum(jnp.where(valid, m2_i + n_i * delta * delta, 0.0))
    return m2, n > 0


def _head_rows(col: DeviceColumn, capacity: int,
               max_bytes: int) -> DeviceColumn:
    """The first ``capacity`` rows of a buffer-slot column whose live rows
    are a prefix (one a group): slices, no gather.  A string column keeps
    ``capacity × max_bytes`` bytes where the caller knows that none of its
    rows is longer; with ``max_bytes`` 0, and for an array column (collect,
    HLL, t-digest), whose rows nothing bounds, the byte or element plane
    stays the source's."""
    return col.with_capacity(
        capacity, G.string_plane_capacity(col, capacity, max_bytes))


# update ops whose keyless form (the nkeys == 0 branch of _partial_step)
# reads ``live`` as a boolean mask and nowhere as "the first num_rows rows":
# each masks values to an identity, counts the mask, or takes the first/last
# set position of it, so rows dropped in the middle of a batch read like
# padding at its end.  An op not listed here keeps the filter's compaction.
_MASK_UPDATE_OPS = frozenset(
    (COUNT_STAR, COUNT_VALID, SUM, MIN, MAX, M2, SUM128, MIN128, MAX128,
     HLL_UPDATE, COLLECT, TD_MEANS, TD_WEIGHTS, MAXBY_VAL, MINBY_VAL)
    + PICK_OPS + BIT_OPS)


class _AggDeviceSpec:
    """The aggregate's device-step parameters + pure step functions,
    detached from the exec so shared_jit-cached steps never pin the exec
    tree (and its scan input data) in the global cache."""

    def __init__(self, group_exprs, agg_exprs, aggregates, slot_specs,
                 slot_pos, partial_schema, out_schema):
        self.group_exprs = group_exprs
        self.agg_exprs = agg_exprs
        self.aggregates = aggregates
        self.slot_specs = slot_specs
        self._slot_pos = slot_pos
        self.partial_schema = partial_schema
        self.schema = out_schema
        # string columns that get ORDER-compared (min/max over strings,
        # max_by/min_by string ordering keys): the max-bytes bucket must
        # cover them too, not just the group keys — a truncated rank
        # would silently mis-order long strings
        self.string_order_exprs = tuple(self._string_order_exprs())

    def _string_order_exprs(self):
        from spark_rapids_tpu.expressions import aggregates as A
        out = []
        for agg in self.aggregates:
            try:
                if isinstance(agg, (A.Min, A.Max)) and \
                        agg.children[0].dtype.variable_width:
                    out.append(agg.children[0])
                elif isinstance(agg, (A.MaxBy, A.MinBy)) and \
                        agg.children[1].dtype.variable_width:
                    out.append(agg.children[1])
            except (TypeError, ValueError, NotImplementedError):
                pass
        return out

    def _string_order_slots(self):
        """Slot indices whose PARTIAL buffer column is a string that gets
        order-compared at merge time (the min/max string buffers)."""
        return [si for si, (_, slot) in enumerate(self.slot_specs)
                if slot.merge_op in (MIN, MAX) and slot.dtype.variable_width]

    def _m2_companions(self, ai: int):
        """Slot indices of the M2 buffer's sum and count companions,
        resolved by op kind (not position) so a buffer-layout change in the
        aggregate fails loudly here instead of merging the wrong columns."""
        s_si = n_si = None
        for si in self._slot_pos[ai]:
            _, slot = self.slot_specs[si]
            if slot.update_op == SUM:
                s_si = si
            elif slot.update_op == COUNT_VALID:
                n_si = si
        if s_si is None or n_si is None:
            raise AssertionError(
                f"M2_MERGE needs SUM and COUNT_VALID companion buffers "
                f"on aggregate {self.aggregates[ai]!r}")
        return s_si, n_si

    def _count_companion(self, ai: int) -> int:
        """Slot index of this aggregate's COUNT_VALID companion buffer."""
        for si in self._slot_pos[ai]:
            _, slot = self.slot_specs[si]
            if slot.update_op == COUNT_VALID:
                return si
        raise AssertionError(
            f"SUM128 needs a COUNT_VALID companion buffer on "
            f"{self.aggregates[ai]!r}")

    def _td_companion(self, ai: int, update_op: str) -> int:
        """Slot index of this aggregate's other t-digest plane (means <->
        weights): the merge re-clustering needs both."""
        for si in self._slot_pos[ai]:
            _, slot = self.slot_specs[si]
            if slot.update_op == update_op:
                return si
        raise AssertionError(
            f"t-digest merge needs a {update_op} companion buffer on "
            f"{self.aggregates[ai]!r}")

    def _by_companion(self, ai: int) -> int:
        """Slot index of max_by/min_by's ordering-key buffer."""
        for si in self._slot_pos[ai]:
            _, slot = self.slot_specs[si]
            if slot.input_index == 1 and slot.update_op in (MIN, MAX):
                return si
        raise AssertionError(
            f"max_by/min_by needs a MIN/MAX ordering companion buffer on "
            f"{self.aggregates[ai]!r}")

    def _merge_bucket(self, partial: ColumnarBatch) -> int:
        from spark_rapids_tpu.kernels import strings as SK
        nkeys = len(self.group_exprs)
        pairs = [(partial.columns[i], partial.num_rows)
                 for i in range(nkeys)]
        # min/max STRING buffer columns are order-compared again at merge
        pairs += [(partial.columns[nkeys + si], partial.num_rows)
                  for si in self._string_order_slots()]
        if not any(c.is_string_like for c, _ in pairs):
            return 0
        return SK.bucket_for(SK.max_live_bytes_multi(pairs))

    def reduces_under_mask(self) -> bool:
        """True when ``_partial_step`` takes the rows that count as a
        boolean ``live`` mask, so that a fused filter below the aggregate
        hands over its mask instead of compacting (plan/fused.py).

        Keyless, every slot's update op has to be one of
        ``_MASK_UPDATE_OPS``: each op reads the mask itself.  Grouped,
        always: the grouping sort takes the mask as its liveness key and
        leaves the rows that count as a prefix in their own order
        (``group_rows``), and everything after the sort is the step a
        compacted batch runs, whatever the ops, so no per-op list is needed
        there."""
        return bool(self.group_exprs) or all(
            slot.update_op in _MASK_UPDATE_OPS
            for _, slot in self.slot_specs)

    def _partial_step(self, batch: ColumnarBatch, string_bucket: int = 0,
                      live: Optional[jax.Array] = None,
                      group_capacity: Optional[int] = None) -> ColumnarBatch:
        """Raw rows -> one partial batch (keys + buffers), grouped in-batch.

        The partial batch has one row a group.  Keyless, its capacity is 1.
        Grouped, its capacity is the input's (every batch fits its own
        groups: ``_jit_partial``, ``parallel/stage.py``) unless
        ``group_capacity`` (static, at most the input's capacity) is given:
        the batch then has that many rows, string keys and min/max string
        buffers ``group_capacity × string_bucket`` bytes (they were
        compared under the bucket; every other string or array buffer
        keeps its source plane), and its ``num_rows`` is still the true
        group count.  Nothing in here validates it: a caller
        that passes a ``group_capacity`` reads ``num_rows`` back and
        discards a batch whose ``num_rows`` exceeds its capacity, as
        ``plan/fused.py`` does through its ``g<pos>`` feedback.

        ``live``: the rows that count, where they are not the prefix
        ``batch.live_mask()`` (``reduces_under_mask``): keyless, every
        reduction reads it; grouped, the grouping sort does."""
        ctx = EvalContext(batch)
        key_cols = tuple(e.eval(ctx) for e in self.group_exprs)
        agg_in = {}
        for agg in self.aggregates:
            for ii, inp in enumerate(agg.inputs):
                if (id(agg), ii) not in agg_in:
                    agg_in[(id(agg), ii)] = inp.eval(ctx)
        nkeys = len(key_cols)

        if nkeys == 0:
            if live is None:
                live = batch.live_mask()
            cols = []
            for ai, slot in self.slot_specs:
                agg = self.aggregates[ai]
                col = agg_in.get((id(agg), slot.input_index))
                if slot.update_op == HLL_UPDATE:
                    from spark_rapids_tpu.kernels import hll as HLL
                    regs = HLL.global_update(col, live, agg.p)
                    cols.append(_hll_array_col(
                        regs.reshape(1, agg.m), 1, 1, agg.m))
                    continue
                if slot.update_op == SUM128:
                    cols.append(_global_sum128(col, None, live, slot.dtype))
                    continue
                if slot.update_op in (MIN128, MAX128):
                    cols.append(_global_extreme128(
                        col, live, slot.dtype, slot.update_op == MIN128))
                    continue
                if slot.update_op == COLLECT:
                    cols.append(_collect_update(col, None, live, 1))
                    continue
                if slot.update_op in (TD_MEANS, TD_WEIGHTS):
                    from spark_rapids_tpu.kernels import tdigest as TDK
                    agg_ = self.aggregates[ai]
                    cols.append(TDK.global_update(
                        col, live, agg_.delta,
                        "means" if slot.update_op == TD_MEANS
                        else "weights"))
                    continue
                if slot.update_op in PICK_OPS:
                    cols.append(G.global_pick(
                        col, live, "valid" in slot.update_op,
                        slot.update_op.startswith("last")))
                    continue
                if slot.update_op in (MAXBY_VAL, MINBY_VAL):
                    ycol = agg_in[(id(agg), 1)]
                    cols.append(G.global_pick_by(
                        col, ycol, live, slot.update_op == MINBY_VAL,
                        string_max_bytes=string_bucket))
                    continue
                if slot.update_op in (MIN, MAX) and col.is_string_like:
                    cols.append(G.global_extreme_string(
                        col, live, slot.update_op == MIN, string_bucket))
                    continue
                if slot.update_op in BIT_OPS:
                    v, valid = G.global_bitwise(col, live, slot.update_op,
                                                slot.dtype.jnp_dtype)
                    cols.append(DeviceColumn(
                        jnp.where(valid, v, jnp.zeros((), v.dtype)),
                        valid, slot.dtype))
                    continue
                v, valid = _global_update(slot.update_op, col, live, slot.dtype)
                data = jnp.where(valid, v, jnp.zeros((), v.dtype))
                cols.append(DeviceColumn(
                    jnp.reshape(data.astype(slot.dtype.jnp_dtype), (1,)),
                    jnp.reshape(valid, (1,)), slot.dtype))
            return ColumnarBatch(tuple(cols), host_scalar(1), self.partial_schema)

        # grouped: pack keys + inputs into a work batch, sort-group, reduce
        work_cols = list(key_cols)
        col_of_agg = {}
        for agg in self.aggregates:
            for ii in range(len(agg.inputs)):
                col_of_agg[(id(agg), ii)] = len(work_cols)
                work_cols.append(agg_in[(id(agg), ii)])
        work_names = tuple(f"c{i}" for i in range(len(work_cols)))
        work = ColumnarBatch(tuple(work_cols), batch.num_rows,
                             Schema(work_names, tuple(c.dtype for c in work_cols)))
        # split-tolerant fast grouping: the partial step's per-batch
        # groups merge again at the final/merge step, so string keys sort
        # by one hashed pass each (a collision splits a group — exactly
        # what a batch boundary does anyway); boundaries stay byte-exact
        layout = G.group_rows(work, list(range(nkeys)),
                              string_max_bytes=string_bucket,
                              allow_split_groups=True, live=live)
        # keys are born at the group capacity; a buffer slot is reduced
        # over the input's capacity and keeps its first rows (_head_rows)
        out_keys = G.group_keys_output(layout, list(range(nkeys)),
                                       out_capacity=group_capacity,
                                       string_max_bytes=string_bucket)
        cols = list(out_keys)
        for ai, slot in self.slot_specs:
            agg = self.aggregates[ai]
            col = (layout.sorted_column(
                       col_of_agg[(id(agg), slot.input_index)])
                   if agg.inputs else None)
            if slot.update_op == HLL_UPDATE:
                from spark_rapids_tpu.kernels import hll as HLL
                regs2d = HLL.seg_update(col, layout, agg.p)
                cols.append(_hll_array_col(regs2d, layout.num_groups,
                                           col.capacity, agg.m))
                continue
            if slot.update_op == SUM128:
                cols.append(_seg_sum128(col, None, layout, slot.dtype))
                continue
            if slot.update_op in (MIN128, MAX128):
                cols.append(_seg_extreme128(col, layout, slot.dtype,
                                            slot.update_op == MIN128))
                continue
            if slot.update_op == COLLECT:
                live2 = layout.live_mask()
                cols.append(_collect_update(col, layout, live2,
                                            layout.num_groups))
                continue
            if slot.update_op in (TD_MEANS, TD_WEIGHTS):
                from spark_rapids_tpu.kernels import tdigest as TDK
                cols.append(TDK.seg_update(
                    col, layout, agg.delta,
                    "means" if slot.update_op == TD_MEANS else "weights"))
                continue
            if slot.update_op in PICK_OPS:
                cols.append(G.seg_pick(col, layout,
                                       "valid" in slot.update_op,
                                       slot.update_op.startswith("last")))
                continue
            if slot.update_op in (MAXBY_VAL, MINBY_VAL):
                ycol = layout.sorted_column(col_of_agg[(id(agg), 1)])
                cols.append(G.seg_pick_by(col, ycol, layout,
                                          slot.update_op == MINBY_VAL,
                                          string_max_bytes=string_bucket))
                continue
            if slot.update_op in (MIN, MAX) and col.is_string_like:
                cols.append(G.seg_extreme_string(
                    col, layout, slot.update_op == MIN, string_bucket))
                continue
            if slot.update_op in BIT_OPS:
                v, valid = G.seg_bitwise(col, layout, slot.update_op,
                                         slot.dtype.jnp_dtype)
                cols.append(G.finalize_agg_column(
                    v.astype(slot.dtype.jnp_dtype), valid,
                    layout.num_groups, slot.dtype))
                continue
            v, valid = _seg_update(slot.update_op, col, layout, slot.dtype)
            cols.append(G.finalize_agg_column(
                v.astype(slot.dtype.jnp_dtype), valid, layout.num_groups,
                slot.dtype))
        if group_capacity is not None:
            # the bucket bounds a string buffer only where its input was
            # order-compared under it (min/max, a max_by/min_by ordering
            # key: plain column references, like the keys); a picked value
            # (first/last, max_by/min_by of any expression) can be longer
            ordered = set(self._string_order_slots())
            cols[nkeys:] = [
                _head_rows(c, group_capacity,
                           string_bucket if si in ordered else 0)
                for si, c in enumerate(cols[nkeys:])]
        return ColumnarBatch(tuple(cols), layout.num_groups, self.partial_schema)

    def _merge_step(self, partial: ColumnarBatch,
                    string_bucket: int = 0) -> ColumnarBatch:
        """Concatenated partial batches -> merged partial batch."""
        nkeys = len(self.group_exprs)
        if nkeys == 0:
            live = partial.live_mask()
            cols = []
            for si, (ai, slot) in enumerate(self.slot_specs):
                col = partial.columns[nkeys + si]
                if slot.merge_op == HLL_MERGE:
                    agg = self.aggregates[ai]
                    regs2d = _hll_regs2d(col, partial.capacity, agg.m)
                    keep = (col.validity & live)[:, None]
                    merged = jnp.max(jnp.where(keep, regs2d, jnp.int8(0)),
                                     axis=0)
                    cols.append(_hll_array_col(
                        merged.reshape(1, agg.m), 1, 1, agg.m))
                    continue
                if slot.merge_op == SUM128:
                    ncol = partial.columns[nkeys + self._count_companion(ai)]
                    cols.append(_global_sum128(col, ncol, live, slot.dtype))
                    continue
                if slot.merge_op in (MIN128, MAX128):
                    cols.append(_global_extreme128(
                        col, live, slot.dtype, slot.merge_op == MIN128))
                    continue
                if slot.merge_op == COLLECT_MERGE:
                    cols.append(_collect_merge(col, None, live, 1))
                    continue
                if slot.merge_op in (TD_MEANS_MERGE, TD_WEIGHTS_MERGE):
                    from spark_rapids_tpu.kernels import tdigest as TDK
                    m_si = self._td_companion(ai, TD_MEANS)
                    w_si = self._td_companion(ai, TD_WEIGHTS)
                    mc = partial.columns[nkeys + m_si]
                    wc = partial.columns[nkeys + w_si]
                    cols.append(TDK.global_merge(
                        mc, wc, live, self.aggregates[ai].delta,
                        "means" if slot.merge_op == TD_MEANS_MERGE
                        else "weights"))
                    continue
                if slot.merge_op in PICK_OPS:
                    cols.append(G.global_pick(
                        col, live, "valid" in slot.merge_op,
                        slot.merge_op.startswith("last")))
                    continue
                if slot.merge_op in (MAXBY_VAL, MINBY_VAL):
                    ycol = partial.columns[nkeys + self._by_companion(ai)]
                    cols.append(G.global_pick_by(
                        col, ycol, live, slot.merge_op == MINBY_VAL,
                        string_max_bytes=string_bucket))
                    continue
                if slot.merge_op in (MIN, MAX) and col.is_string_like:
                    cols.append(G.global_extreme_string(
                        col, live, slot.merge_op == MIN, string_bucket))
                    continue
                if slot.merge_op in BIT_OPS:
                    v, valid = G.global_bitwise(col, live, slot.merge_op,
                                                slot.dtype.jnp_dtype)
                    cols.append(DeviceColumn(
                        jnp.where(valid, v, jnp.zeros((), v.dtype)),
                        valid, slot.dtype))
                    continue
                if slot.merge_op == M2_MERGE:
                    s_si, n_si = self._m2_companions(ai)
                    v, valid = _global_m2_merge(
                        col, partial.columns[nkeys + s_si],
                        partial.columns[nkeys + n_si], live)
                else:
                    v, valid = _global_update(slot.merge_op, col, live,
                                              slot.dtype)
                data = jnp.where(valid, v, jnp.zeros((), v.dtype))
                cols.append(DeviceColumn(
                    jnp.reshape(data.astype(slot.dtype.jnp_dtype), (1,)),
                    jnp.reshape(valid, (1,)), slot.dtype))
            return ColumnarBatch(tuple(cols), host_scalar(1), self.partial_schema)
        layout = G.group_rows(partial, list(range(nkeys)),
                              string_max_bytes=string_bucket)
        out_keys = G.group_keys_output(layout, list(range(nkeys)))
        cols = list(out_keys)
        for si, (ai, slot) in enumerate(self.slot_specs):
            col = layout.sorted_column(nkeys + si)
            if slot.merge_op == HLL_MERGE:
                agg = self.aggregates[ai]
                cap = col.capacity
                regs2d = _hll_regs2d(col, cap, agg.m)
                live2 = layout.live_mask()
                keep = (col.validity & live2)[:, None]
                r = jnp.where(keep, regs2d, jnp.int8(0))
                merged = jax.ops.segment_max(
                    r, layout.segment_ids, num_segments=cap)
                merged = jnp.maximum(merged, 0).astype(jnp.int8)
                cols.append(_hll_array_col(merged, layout.num_groups,
                                           cap, agg.m))
                continue
            if slot.merge_op == SUM128:
                ncol = layout.sorted_column(nkeys + self._count_companion(ai))
                cols.append(_seg_sum128(col, ncol, layout, slot.dtype))
                continue
            if slot.merge_op in (MIN128, MAX128):
                cols.append(_seg_extreme128(col, layout, slot.dtype,
                                            slot.merge_op == MIN128))
                continue
            if slot.merge_op == COLLECT_MERGE:
                live2 = layout.live_mask()
                cols.append(_collect_merge(col, layout, live2,
                                           layout.num_groups))
                continue
            if slot.merge_op in (TD_MEANS_MERGE, TD_WEIGHTS_MERGE):
                from spark_rapids_tpu.kernels import tdigest as TDK
                m_si = self._td_companion(ai, TD_MEANS)
                w_si = self._td_companion(ai, TD_WEIGHTS)
                mc = layout.sorted_column(nkeys + m_si)
                wc = layout.sorted_column(nkeys + w_si)
                cols.append(TDK.seg_merge(
                    mc, wc, layout, self.aggregates[ai].delta,
                    "means" if slot.merge_op == TD_MEANS_MERGE
                    else "weights"))
                continue
            if slot.merge_op in PICK_OPS:
                cols.append(G.seg_pick(col, layout,
                                       "valid" in slot.merge_op,
                                       slot.merge_op.startswith("last")))
                continue
            if slot.merge_op in (MAXBY_VAL, MINBY_VAL):
                ycol = layout.sorted_column(nkeys + self._by_companion(ai))
                cols.append(G.seg_pick_by(col, ycol, layout,
                                          slot.merge_op == MINBY_VAL,
                                          string_max_bytes=string_bucket))
                continue
            if slot.merge_op in (MIN, MAX) and col.is_string_like:
                cols.append(G.seg_extreme_string(
                    col, layout, slot.merge_op == MIN, string_bucket))
                continue
            if slot.merge_op in BIT_OPS:
                v, valid = G.seg_bitwise(col, layout, slot.merge_op,
                                         slot.dtype.jnp_dtype)
                cols.append(G.finalize_agg_column(
                    v.astype(slot.dtype.jnp_dtype), valid,
                    layout.num_groups, slot.dtype))
                continue
            if slot.merge_op == M2_MERGE:
                s_si, n_si = self._m2_companions(ai)
                v, valid = G.seg_m2_merge(
                    col, layout.sorted_column(nkeys + s_si),
                    layout.sorted_column(nkeys + n_si), layout)
            else:
                v, valid = _seg_update(slot.merge_op, col, layout, slot.dtype)
            cols.append(G.finalize_agg_column(
                v.astype(slot.dtype.jnp_dtype), valid, layout.num_groups,
                slot.dtype))
        return ColumnarBatch(tuple(cols), layout.num_groups, self.partial_schema)

    def _finalize(self, merged: ColumnarBatch) -> ColumnarBatch:
        """Merged partials -> final output batch (keys + output exprs)."""
        nkeys = len(self.group_exprs)
        mapping = {}
        si = 0
        for agg in self.aggregates:
            bufs = []
            for slot in agg.buffers:
                c = merged.columns[nkeys + si]
                if slot.update_op == HLL_UPDATE:
                    bufs.append((_hll_regs2d(c, merged.capacity, agg.m),
                                 c.validity))
                elif (slot.update_op in (COLLECT, TD_MEANS,
                                         TD_WEIGHTS)
                      or c.children is not None
                      or c.offsets is not None):
                    # holistic/limb columns, and var-width pick buffers
                    # (first/last/max_by over strings)
                    bufs.append((c, c.validity))
                else:
                    bufs.append((c.data, c.validity))
                si += 1
            v, valid = agg.finalize_jnp(bufs)
            live = merged.live_mask()
            valid = valid & live
            if isinstance(v, DeviceColumn) and v.offsets is not None:
                # array-valued result (approx_percentile with array
                # percentages): finalize built the segmented column
                mapping[id(agg)] = DeviceColumn(
                    v.data, valid, v.dtype, v.offsets, v.child_validity)
            elif isinstance(v, DeviceColumn):
                from spark_rapids_tpu.kernels import decimal as DK
                mapping[id(agg)] = DK.make_column128(
                    v.children[0].data, v.children[1].data, valid,
                    agg.dtype)
            else:
                v = jnp.where(valid, v.astype(agg.dtype.jnp_dtype),
                              jnp.zeros((), agg.dtype.jnp_dtype))
                mapping[id(agg)] = DeviceColumn(v, valid, agg.dtype)
        out_cols = list(merged.columns[:nkeys])
        ctx = EvalContext(merged)
        for e in self.agg_exprs:
            sub = _substitute(e, mapping)
            out_cols.append(sub.eval(ctx))
        return ColumnarBatch(tuple(out_cols), merged.num_rows, self.schema)


class TpuHashAggregateExec(TpuExec):
    def __init__(self, group_exprs: Sequence[Expression],
                 agg_exprs: Sequence[Expression],
                 aggregates: List[AggregateFunction],
                 child: TpuExec, schema: Schema, mode: str = "complete",
                 target_capacity: int = 1 << 20):
        self.group_exprs = tuple(group_exprs)
        self.agg_exprs = tuple(agg_exprs)
        self.aggregates = list(aggregates)
        self.mode = mode
        self.target_capacity = target_capacity
        # buffer layout: per aggregate, per slot -> one partial column
        self.slot_specs = []   # (agg_index, slot)
        slot_pos = {}          # agg_index -> [slot indices into slot_specs]
        for ai, agg in enumerate(self.aggregates):
            for slot in agg.buffers:
                slot_pos.setdefault(ai, []).append(len(self.slot_specs))
                self.slot_specs.append((ai, slot))
        nkeys = len(self.group_exprs)
        partial_names = tuple(f"_k{i}" for i in range(nkeys)) + tuple(
            f"_buf{i}" for i in range(len(self.slot_specs)))
        partial_dtypes = tuple(e.dtype for e in self.group_exprs) + tuple(
            s.dtype for _, s in self.slot_specs)
        self.partial_schema = Schema(partial_names, partial_dtypes)
        out_schema = self.partial_schema if mode == "partial" else schema
        super().__init__((child,), out_schema)
        spec = _AggDeviceSpec(self.group_exprs, self.agg_exprs,
                              self.aggregates, self.slot_specs, slot_pos,
                              self.partial_schema, out_schema)
        self._spec = spec
        from functools import partial as _partial
        from spark_rapids_tpu.plan.execs.base import (
            exprs_cache_key, schema_cache_key, shared_jit)
        key = ("agg|" + mode
               + "|" + schema_cache_key(child.schema)
               + "|" + schema_cache_key(self.partial_schema)
               + "|" + schema_cache_key(out_schema)
               + "|" + exprs_cache_key(self.group_exprs)
               + "|" + exprs_cache_key(self.agg_exprs))
        # the bucket covers every ORDER-compared string column: group
        # keys plus min/max string inputs and max_by/min_by string
        # ordering keys (plain column refs by the planner gate)
        bucket_exprs = tuple(spec.group_exprs) + spec.string_order_exprs
        self._jit_partial = lambda b, _k=key: shared_jit(
            f"{_k}|partial|{(bkt := string_key_bucket(b, bucket_exprs))}",
            lambda: _partial(spec._partial_step, string_bucket=bkt),
            kind="agg_partial")(b)
        self._jit_merge = lambda b, _k=key: shared_jit(
            f"{_k}|merge|{(bkt := spec._merge_bucket(b))}",
            lambda: _partial(spec._merge_step, string_bucket=bkt),
            kind="agg_merge")(b)
        self._jit_finalize = lambda b, _k=key: shared_jit(
            f"{_k}|finalize", lambda: spec._finalize,
            kind="agg_finalize")(b)

        # in-core reduce path as ONE program: concat + merge + finalize.
        # The per-op path pays three launches per reduce partition (what
        # a launch costs on a directly attached chip is not measured).
        # OOC paths keep the split functions (they need merge sans
        # finalize).
        def combine(partials, out_capacity=None, string_bucket: int = 0):
            # partials may be CACHE_ONLY RangeViews (the final-fused
            # reduce path): the map-side slice folds into THIS program.
            # ``out_capacity`` (static): the rows the caller knows the
            # partials to hold, rounded up; without it the sum of their
            # capacities, which bounds them
            from spark_rapids_tpu.shuffle.transport import (
                fold_pieces_in_trace)
            return spec._finalize(spec._merge_step(
                fold_pieces_in_trace(partials, out_capacity),
                string_bucket=string_bucket))

        def _combine_bucket(partials) -> int:
            from spark_rapids_tpu.kernels import strings as SK
            nkeys = len(spec.group_exprs)
            pairs = [(p.columns[i], p.num_rows) for p in partials
                     for i in range(nkeys)]
            pairs += [(p.columns[nkeys + si], p.num_rows) for p in partials
                      for si in spec._string_order_slots()]
            if not any(c.is_string_like for c, _ in pairs):
                return 0
            return SK.bucket_for(SK.max_live_bytes_multi(pairs))

        self._jit_combine = lambda ps, out_capacity=None, _k=key: shared_jit(
            f"{_k}|combine|{len(ps)}|{(bkt := _combine_bucket(ps))}",
            lambda: _partial(combine, string_bucket=bkt),
            kind="agg_combine", static_argnums=(1,))(
                tuple(ps), out_capacity)

    # -- host-side orchestration -------------------------------------------

    def _identity_partial(self) -> ColumnarBatch:
        """The empty-input global-agg row: count 0, null value slots
        (Spark: global agg over empty input yields one row)."""
        cols = []
        for ai, slot in self.slot_specs:
            from spark_rapids_tpu import types as TT
            if (isinstance(slot.dtype, (TT.ArrayType, TT.StructType,
                                        TT.MapType))
                    or slot.dtype.variable_width
                    or (isinstance(slot.dtype, TT.DecimalType)
                        and slot.dtype.uses_two_limbs)):
                cols.append(DeviceColumn.empty(slot.dtype, 1,
                                               byte_capacity=1))
                continue
            data = jnp.zeros((1,), slot.dtype.jnp_dtype)
            valid = jnp.zeros((1,), jnp.bool_)
            if slot.update_op == COUNT_STAR or slot.update_op == COUNT_VALID:
                valid = jnp.ones((1,), jnp.bool_)
            cols.append(DeviceColumn(data, valid, slot.dtype))
        return ColumnarBatch(tuple(cols), host_scalar(1), self.partial_schema)

    def _partials_for(self, idx: int) -> List[ColumnarBatch]:
        out = []
        for batch in self.children[0].execute_partition(idx):
            if self.mode in ("partial", "complete"):
                out.append(with_retry_no_split(lambda: self._jit_partial(batch)))
            else:
                out.append(batch)   # already partial-format
        return out

    def _merge_partials(self, partials: List[ColumnarBatch]) -> ColumnarBatch:
        if len(partials) == 1:
            return with_retry_no_split(
                lambda: self._jit_merge(partials[0]))
        from spark_rapids_tpu.plan.execs.coalesce import concat_batches_jit
        cap = round_up_pow2(max(sum(p.capacity for p in partials), 1))
        # concat INSIDE the retry body: the discarded concat result
        # re-runs after a spill instead of pinning HBM from the closure
        return with_retry_no_split(
            lambda: self._jit_merge(concat_batches_jit(partials, cap)))

    def _execute_final_fused(self, idx: int) -> Iterator[ColumnarBatch]:
        """Final mode over a shuffle: ONE program per reduce group — the
        group's raw wire/cache pieces concat + merge + finalize inside
        _jit_combine, pin-balanced per attempt
        (coalesce.retry_over_stream_pieces), instead of the exchange
        merging groups first and the combine concatenating them again.
        What is one program's work is ``reduce_group_in_core``'s to say,
        the rule the coalescing reader built the group by: a group that
        breaks it (a single oversized partition) takes the default path's
        out-of-core sub-partition merge."""
        from spark_rapids_tpu.plan.execs.coalesce import (
            pull_group_in_core, retry_over_stream_pieces)
        from spark_rapids_tpu.shuffle.stats import SHUFFLE_COUNTERS
        from spark_rapids_tpu.shuffle.transport import (
            views_at_one_capacity, views_over_memory_budget)
        stream = iter(self.children[0].stream_pieces(idx))
        # the first pull runs the exchange's map side where nothing has
        # yet: the child's work, outside this layer's span
        first = list(itertools.islice(stream, 1))
        out = None
        with timed(self.op_time, "agg.final"):
            # pulled with an INCREMENTAL size check: a group that passes
            # the in-core bound is dropped at once and the default path's
            # out-of-core merge re-reads the partition
            pieces, rows = pull_group_in_core(
                itertools.chain(first, stream), self.target_capacity)
            # range-view residency guard: one attempt pins each view's
            # FULL backing batch (deduped), which no spill can reclaim
            # mid-attempt — near the arena's byte budget the default path
            # (its reads slice views pin-balanced and release the
            # backing) must run instead of the fold
            in_core = (pieces is not None
                       and not views_over_memory_budget([pieces]))
            if not in_core:
                pieces = first = None
            elif pieces:
                n_views = sum(1 for p in pieces if p.is_range_view)
                if n_views:
                    # CACHE_ONLY range views sliced INSIDE _jit_combine
                    SHUFFLE_COUNTERS.add(range_view_folds=n_views)
                cap = round_up_pow2(max(rows, 1))
                out = retry_over_stream_pieces(
                    [pieces], lambda mats: self._jit_combine(
                        views_at_one_capacity(mats[0]), cap))
        if not in_core:
            yield from self._execute_default(idx)
        elif out is not None:
            SHUFFLE_COUNTERS.add(fused_reduce_programs=1, reduce_groups=1)
            self.output_rows.add(out.num_rows)
            yield self._count_out(out)

    def execute_partition(self, idx: int) -> Iterator[ColumnarBatch]:
        if (self.mode == "final"
                and hasattr(self.children[0], "stream_pieces")):
            # over an exchange/reader: consume RAW shuffle pieces and run
            # concat + merge + finalize as ONE program per reduce
            # partition (the reduce-side merge joins the aggregate program)
            yield from self._execute_final_fused(idx)
            return
        yield from self._execute_default(idx)

    def _execute_default(self, idx: int) -> Iterator[ColumnarBatch]:
        with timed(self.op_time):
            partials = self._partials_for(idx)
            if self.mode == "partial":
                # Spark emits one initial-buffer row per empty partition for
                # global aggregates, so the final phase always sees input
                if not partials and len(self.group_exprs) == 0:
                    partials = [self._identity_partial()]
                for p in partials:
                    # device scalar: Metric.add defers the sync (a per-batch
                    # host_num_rows here cost one round trip per batch)
                    self.output_rows.add(p.num_rows)
                    yield self._count_out(p)
                return
            if not partials:
                if len(self.group_exprs) == 0:
                    partials = [self._identity_partial()]
                else:
                    return
        from spark_rapids_tpu.plan.execs.exchange import reduce_group_in_core
        from spark_rapids_tpu.shuffle.stats import SHUFFLE_COUNTERS
        SHUFFLE_COUNTERS.add(reduce_groups=1)
        # batches and not pieces: their capacities are what the host knows
        # of their rows (StreamPiece.rows says the same of an uploaded one)
        total = sum(p.capacity for p in partials)
        if not reduce_group_in_core(total, self.target_capacity):
            yield from self._execute_out_of_core(partials, total)
            return
        with timed(self.op_time, "agg.final"):
            out = with_retry_no_split(lambda: self._jit_combine(partials))
        self.output_rows.add(out.num_rows)
        yield self._count_out(out)

    def _execute_out_of_core(self, partials: List[ColumnarBatch],
                             total: int) -> Iterator[ColumnarBatch]:
        """Merge a partial set larger than one capacity bucket.

        Grouped: hash-repartition the partials on the grouping keys (with
        the sub-partition seed, NOT the shuffle seed) into spillable
        buckets and merge+finalize each bucket independently — key-disjoint
        buckets make the union of bucket outputs exactly the in-core
        answer.  Reference: repartition-based aggregation on oversized
        merge sets, GpuAggregateExec.scala:290.

        Global (no keys): tree-merge in chunks of target_capacity rows.
        """
        from spark_rapids_tpu.memory.spill import make_spillable
        from spark_rapids_tpu.plan.execs.exchange import reduce_group_in_core
        from spark_rapids_tpu.plan.execs.out_of_core import (
            close_all, num_sub_buckets, sub_partition_spillable)
        from spark_rapids_tpu.shuffle.stats import SHUFFLE_COUNTERS

        SHUFFLE_COUNTERS.add(reduce_groups_out_of_core=1)
        nkeys = len(self.group_exprs)
        if nkeys == 0:
            # chunks bounded by accumulated ROW capacity, not batch count:
            # each merge's concat stays within one capacity bucket
            with timed(self.op_time, "agg.out_of_core"):
                while len(partials) > 1:
                    nxt, group, acc = [], [], 0
                    for p in partials + [None]:
                        if p is not None and (
                                not group or reduce_group_in_core(
                                    acc + p.capacity, self.target_capacity)):
                            group.append(p)
                            acc += p.capacity
                            continue
                        nxt.append(self._merge_partials(group))
                        if p is not None:
                            group, acc = [p], p.capacity
                    partials = nxt
                out = with_retry_no_split(
                    lambda: self._jit_finalize(partials[0]))
            self.output_rows.add(out.num_rows)
            yield self._count_out(out)
            return

        # one ``agg.out_of_core`` span a group, around what only this path
        # does before it hands anything on: the sub-partition.  A span
        # never stays open across a yield (the parent's work would be in
        # it), so each bucket's merge and finalize is an ``agg.final``
        n_b = num_sub_buckets(total, self.target_capacity)
        with timed(self.op_time, "agg.out_of_core"):
            handles = [make_spillable(p) for p in partials]
            del partials
            buckets = sub_partition_spillable(
                (h.release_device_copy() for h in handles),
                list(range(nkeys)), n_b, self.partial_schema)
        try:
            for q in buckets:
                if not q:
                    continue
                with timed(self.op_time, "agg.final"):
                    # pinned-ledger unwind: a raise in materialize or
                    # the merge must still unpin what WAS materialized,
                    # or the handles stay unspillable until close
                    batches = []
                    pinned = []
                    try:
                        for h in q:
                            batches.append(h.materialize())
                            pinned.append(h)
                        merged = self._merge_partials(batches)
                    finally:
                        for h in pinned:
                            h.unpin()
                    for h in q:
                        h.close()
                    out = with_retry_no_split(
                        lambda: self._jit_finalize(merged))
                self.output_rows.add(out.num_rows)
                yield self._count_out(out)
        finally:
            close_all(buckets)

    def describe(self):
        keys = ", ".join(map(repr, self.group_exprs))
        aggs = ", ".join(map(repr, self.agg_exprs))
        return f"TpuHashAggregate[{self.mode}, keys=[{keys}], aggs=[{aggs}]]"
