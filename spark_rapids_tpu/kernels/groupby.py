"""Group-by kernels: sort-based segmented aggregation.

TPU replacement for cuDF's hash groupby (`Table.groupBy`, reference
consumption: GpuAggregateExec.scala:360 `AggHelper`).  On TPU a sort +
segmented-reduce maps better onto XLA's fixed-shape world than an
open-addressing hash table: `jnp.lexsort` is a single fused variadic sort,
and `jax.ops.segment_*` are native scatter-reduces.

Spark grouping semantics honored here:
  * null keys form their own group (null == null for grouping);
  * -0.0 and 0.0 group together; all NaNs group together
    (keys are normalized before comparison);
  * output group order is unspecified (ours: key sort order) — the
    differential oracle sorts before comparing, as the reference's
    integration tests do via ignore_order.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu.kernels.selection import OOB, compaction_map, gather_column
from spark_rapids_tpu.kernels.sort import (
    BYTES_PER_CHUNK, SortOrder, sort_indices, string_key_planes)


def normalize_key_column(col: DeviceColumn) -> DeviceColumn:
    """Normalize float keys so bit-compare == Spark group equality."""
    if col.is_struct:
        return DeviceColumn(col.data, col.validity, col.dtype,
                            children=tuple(normalize_key_column(c)
                                           for c in col.children))
    if isinstance(col.dtype, (T.FloatType, T.DoubleType)):
        d = col.data
        d = jnp.where(d == 0.0, jnp.zeros((), d.dtype), d)      # -0.0 -> 0.0
        d = jnp.where(jnp.isnan(d), jnp.full((), jnp.nan, d.dtype), d)  # canonical NaN
        return DeviceColumn(d, col.validity, col.dtype, col.offsets)
    return col


def _rows_equal_prev(col: DeviceColumn) -> jax.Array:
    """[capacity] bool: row i equals row i-1 in this column (null==null).
    Relies on canonical padding (null data slots are zero) and on float keys
    being normalized, so a bit/data comparison is exact."""
    assert not col.is_string_like, "use _string_rows_equal_prev"
    if col.is_struct:
        # struct equality: same presence, and (both null OR all fields
        # equal) — nested nulls compare equal, like Spark grouping
        same_null = col.validity == jnp.roll(col.validity, 1)
        both_valid = col.validity & jnp.roll(col.validity, 1)
        kid_eq = jnp.ones_like(col.validity)
        for c in col.children:
            kid_eq = kid_eq & _rows_equal_prev(c)
        return same_null & (kid_eq | ~both_valid)
    if isinstance(col.dtype, (T.FloatType, T.DoubleType)):
        if col.data.dtype == jnp.float64:
            from spark_rapids_tpu.kernels.sort import f64_injective_u64
            bits = f64_injective_u64(col.data)
        else:
            bits = jax.lax.bitcast_convert_type(col.data, jnp.uint32)
        eq = bits == jnp.roll(bits, 1)
    else:
        eq = col.data == jnp.roll(col.data, 1)
    same_null = col.validity == jnp.roll(col.validity, 1)
    return eq & same_null


def _string_rows_equal_prev(col: DeviceColumn, planes: jax.Array,
                            steps: jax.Array, idx: jax.Array) -> jax.Array:
    """[capacity] bool: in the order ``idx``, row i holds the string of row
    i-1 (null==null).  ``planes``, ``steps``: the column's chunk keys and
    the byte steps they took (``_string_chunk_planes``), in input order.
    Chunk sequences are injective for strings within the bucket, so equal
    chunks are equal bytes; the lengths are compared beside them, so a key
    longer than the bucket merges only with one of its own length.  A chunk
    past the longest string is zero on every row and is not gathered."""
    lengths = col.offsets[1:] - col.offsets[:-1]
    tag = jnp.where(col.validity, lengths, -1)[idx]     # -1: null

    def step(c, eq):
        chunk = planes[c][idx]
        return eq & (chunk == jnp.roll(chunk, 1))

    return jax.lax.fori_loop(0, -(-steps // BYTES_PER_CHUNK), step,
                             tag == jnp.roll(tag, 1))


@dataclasses.dataclass
class GroupedLayout:
    """Result of the grouping phase: the order of the rows by key plus
    segment structure; no column is moved but the fixed-width keys, which
    the boundaries compare in sorted order.  Aggregations are segment
    reductions over the columns a caller asks for in that order
    (``sorted_column``): what it does not read is not gathered."""

    source: ColumnarBatch        # rows in input order, keys normalized
    indices: jax.Array           # int32 [capacity]: sorted position -> source row
    num_rows: jax.Array          # scalar int32: the rows that count, a prefix of the order
    segment_ids: jax.Array       # int32 [capacity], 0-based; padding rows -> last
    num_groups: jax.Array        # scalar int32
    boundary: jax.Array          # bool [capacity], True at first row of group
    _sorted: Dict[int, DeviceColumn]

    @property
    def capacity(self) -> int:
        return self.indices.shape[0]

    def live_mask(self) -> jax.Array:
        return jnp.arange(self.capacity, dtype=jnp.int32) < self.num_rows

    def sorted_column(self, ci: int) -> DeviceColumn:
        """Column ``ci`` of the source in sorted order, rows past
        ``num_rows`` canonical padding; gathered once."""
        if ci not in self._sorted:
            with jax.named_scope("gather_sorted"):
                self._sorted[ci] = gather_column(
                    self.source.columns[ci], self.indices, self.num_rows)
        return self._sorted[ci]

    @property
    def sorted_batch(self) -> ColumnarBatch:
        """Every column in sorted order, for a caller that reads them all."""
        return ColumnarBatch(
            tuple(self.sorted_column(ci)
                  for ci in range(len(self.source.columns))),
            self.num_rows, self.source.schema)


@jax.named_scope("group_rows")
def group_rows(
    batch: ColumnarBatch,
    key_cols: Sequence[int],
    string_max_bytes: Optional[int] = None,
    allow_split_groups: bool = False,
    live: Optional[jax.Array] = None,
) -> GroupedLayout:
    """Order rows by keys and delimit groups.

    ``live``: bool [capacity], the rows that count where they are not the
    prefix ``batch.live_mask()`` (a fused filter's mask, taken in the place
    of its compaction).  The sort sinks every other row to the end and is
    stable, so the order holds the rows that count as a prefix of
    ``sum(live)`` rows, in the order a compaction before the sort would
    have left them in: everything that reads the layout reads what it
    would have read of a compacted batch.

    string_max_bytes must cover the longest live string key or distinct
    groups silently merge; None derives it from the data (host sync).
    A string key's chunk keys are built once, on the rows as given: the
    sort reads them, and the boundaries read them through the order.

    ``allow_split_groups``: sort string keys by ONE hashed key each
    instead of their full chunk sequence — ceil(max_bytes/7) sort passes
    per string column collapse to one (the q25 partial-agg wall: 4 string
    group keys × 128-byte bucket was ~130 lexsort passes per batch).
    Group BOUNDARIES still compare the actual bytes, so distinct keys
    can never merge; a rare hash collision interleaves two keys in one
    hash run and SPLITS a group into several segments instead.  Valid
    ONLY for consumers whose downstream re-merges equal keys — the
    partial aggregate step, whose per-batch partials meet the final/merge
    step exactly like partials of different batches always have.
    """
    if string_max_bytes is None:
        from spark_rapids_tpu.kernels import strings as strkern
        string_max_bytes = strkern.live_string_bucket_for_batch(batch, key_cols)
    # normalize keys (in a copy of the batch) before sorting/comparison
    cols = list(batch.columns)
    for ci in key_cols:
        cols[ci] = normalize_key_column(cols[ci])
    nb = ColumnarBatch(tuple(cols), batch.num_rows, batch.schema)

    planes = string_key_planes(nb, key_cols, string_max_bytes)
    orders = [SortOrder(True, True) for _ in key_cols]
    idx = sort_indices(nb, key_cols, orders, string_max_bytes,
                       hash_string_keys=allow_split_groups, live=live,
                       string_planes=planes)
    count = (nb.num_rows if live is None
             else jnp.sum(live.astype(jnp.int32))).astype(jnp.int32)

    # from here on the rows that count are a prefix of the order
    cap = idx.shape[0]
    live = jnp.arange(cap, dtype=jnp.int32) < count
    sorted_keys = {}
    eq = jnp.ones((cap,), dtype=jnp.bool_)
    for ci in key_cols:
        if ci in planes:
            eq = eq & _string_rows_equal_prev(nb.columns[ci], *planes[ci], idx)
        else:
            sorted_keys[ci] = gather_column(nb.columns[ci], idx, count)
            eq = eq & _rows_equal_prev(sorted_keys[ci])
    first_row = jnp.arange(cap, dtype=jnp.int32) == 0
    boundary = live & (first_row | ~eq)
    segment_ids = jnp.cumsum(boundary.astype(jnp.int32)) - 1
    segment_ids = jnp.where(live, segment_ids, cap - 1)
    num_groups = jnp.sum(boundary.astype(jnp.int32))
    return GroupedLayout(nb, idx, count, segment_ids.astype(jnp.int32),
                         num_groups, boundary, sorted_keys)


# -- segment reductions -----------------------------------------------------

def seg_count_valid(col: DeviceColumn, layout: GroupedLayout) -> Tuple[jax.Array, jax.Array]:
    """COUNT(col): number of non-null values per group -> (int64, validity)."""
    live = layout.live_mask()
    contrib = (col.validity & live).astype(jnp.int64)
    out = jax.ops.segment_sum(contrib, layout.segment_ids, num_segments=col.capacity)
    return out, jnp.ones((col.capacity,), jnp.bool_)


def seg_count_star(layout: GroupedLayout) -> Tuple[jax.Array, jax.Array]:
    cap = layout.capacity
    live = layout.live_mask()
    out = jax.ops.segment_sum(live.astype(jnp.int64), layout.segment_ids, num_segments=cap)
    return out, jnp.ones((cap,), jnp.bool_)


def seg_sum(col: DeviceColumn, layout: GroupedLayout, out_dtype) -> Tuple[jax.Array, jax.Array]:
    """SUM: nulls ignored; all-null group -> null; int64 overflow wraps
    (non-ANSI Spark)."""
    live = layout.live_mask()
    valid = col.validity & live
    vals = col.data.astype(out_dtype)
    contrib = jnp.where(valid, vals, jnp.zeros((), out_dtype))
    out = jax.ops.segment_sum(contrib, layout.segment_ids, num_segments=col.capacity)
    nvalid = jax.ops.segment_sum(valid.astype(jnp.int32), layout.segment_ids,
                                 num_segments=col.capacity)
    return out, nvalid > 0


def seg_m2_update(col: DeviceColumn, layout: GroupedLayout) -> Tuple[jax.Array, jax.Array]:
    """M2 = sum((x - group_mean)^2) per group, two-pass segmented.

    The two-pass form avoids the sum-of-squares cancellation the textbook
    identity suffers when mean >> stddev (reference: Welford/Chan numerics
    in aggregateFunctions.scala GpuStddevSamp)."""
    live = layout.live_mask()
    valid = col.validity & live
    x = col.data.astype(jnp.float64)
    cap = col.capacity
    n = jax.ops.segment_sum(valid.astype(jnp.float64), layout.segment_ids,
                            num_segments=cap)
    s = jax.ops.segment_sum(jnp.where(valid, x, 0.0), layout.segment_ids,
                            num_segments=cap)
    mean = s / jnp.maximum(n, 1.0)
    d = x - mean[layout.segment_ids]
    m2 = jax.ops.segment_sum(jnp.where(valid, d * d, 0.0),
                             layout.segment_ids, num_segments=cap)
    return m2, n > 0


def seg_m2_merge(m2col: DeviceColumn, scol: DeviceColumn, ncol: DeviceColumn,
                 layout: GroupedLayout) -> Tuple[jax.Array, jax.Array]:
    """Chan's parallel merge: M2 = sum_i M2_i + n_i*(mean_i - mean)^2."""
    live = layout.live_mask()
    valid = m2col.validity & live
    n_i = jnp.where(valid, ncol.data.astype(jnp.float64), 0.0)
    s_i = jnp.where(valid, scol.data.astype(jnp.float64), 0.0)
    m2_i = jnp.where(valid, m2col.data.astype(jnp.float64), 0.0)
    cap = m2col.capacity
    n = jax.ops.segment_sum(n_i, layout.segment_ids, num_segments=cap)
    s = jax.ops.segment_sum(s_i, layout.segment_ids, num_segments=cap)
    mean = s / jnp.maximum(n, 1.0)
    mean_i = s_i / jnp.maximum(n_i, 1.0)
    delta = mean_i - mean[layout.segment_ids]
    contrib = jnp.where(valid, m2_i + n_i * delta * delta, 0.0)
    m2 = jax.ops.segment_sum(contrib, layout.segment_ids, num_segments=cap)
    return m2, n > 0


def _extreme(dtype, is_min: bool):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(jnp.inf if is_min else -jnp.inf, dtype=dtype)
    if dtype == jnp.bool_:
        return jnp.array(True if is_min else False, dtype=dtype)
    info = jnp.iinfo(dtype)
    return jnp.array(info.max if is_min else info.min, dtype=dtype)


def seg_min(col: DeviceColumn, layout: GroupedLayout) -> Tuple[jax.Array, jax.Array]:
    """Spark MIN: NaN sorts greater than everything (Spark's total order), so
    MIN returns the smallest non-NaN value and is NaN only for all-NaN
    groups.  segment_min's native NaN propagation would be wrong here."""
    live = layout.live_mask()
    valid = col.validity & live
    ident = _extreme(col.data.dtype, is_min=True)
    if jnp.issubdtype(col.data.dtype, jnp.floating):
        nonnan = valid & ~jnp.isnan(col.data)
        contrib = jnp.where(nonnan, col.data, ident)
        out = jax.ops.segment_min(contrib, layout.segment_ids,
                                  num_segments=col.capacity)
        any_nonnan = jax.ops.segment_sum(
            nonnan.astype(jnp.int32), layout.segment_ids,
            num_segments=col.capacity) > 0
        out = jnp.where(any_nonnan, out, jnp.full((), jnp.nan, col.data.dtype))
    elif col.data.dtype == jnp.bool_:
        contrib = jnp.where(valid, col.data, ident)
        out = jax.ops.segment_min(contrib.astype(jnp.int8), layout.segment_ids,
                                  num_segments=col.capacity).astype(jnp.bool_)
    else:
        contrib = jnp.where(valid, col.data, ident)
        out = jax.ops.segment_min(contrib, layout.segment_ids, num_segments=col.capacity)
    nvalid = jax.ops.segment_sum(valid.astype(jnp.int32), layout.segment_ids,
                                 num_segments=col.capacity)
    return out, nvalid > 0


def seg_max(col: DeviceColumn, layout: GroupedLayout) -> Tuple[jax.Array, jax.Array]:
    """Spark MAX: NaN is the greatest value, so any valid NaN in the group
    makes the result NaN (explicitly, not via float-max propagation, whose
    NaN behavior XLA does not guarantee)."""
    live = layout.live_mask()
    valid = col.validity & live
    ident = _extreme(col.data.dtype, is_min=False)
    if jnp.issubdtype(col.data.dtype, jnp.floating):
        isnan = jnp.isnan(col.data)
        contrib = jnp.where(valid & ~isnan, col.data, ident)
        out = jax.ops.segment_max(contrib, layout.segment_ids,
                                  num_segments=col.capacity)
        any_nan = jax.ops.segment_sum(
            (valid & isnan).astype(jnp.int32), layout.segment_ids,
            num_segments=col.capacity) > 0
        out = jnp.where(any_nan, jnp.full((), jnp.nan, col.data.dtype), out)
    elif col.data.dtype == jnp.bool_:
        contrib = jnp.where(valid, col.data, ident)
        out = jax.ops.segment_max(contrib.astype(jnp.int8), layout.segment_ids,
                                  num_segments=col.capacity).astype(jnp.bool_)
    else:
        contrib = jnp.where(valid, col.data, ident)
        out = jax.ops.segment_max(contrib, layout.segment_ids, num_segments=col.capacity)
    nvalid = jax.ops.segment_sum(valid.astype(jnp.int32), layout.segment_ids,
                                 num_segments=col.capacity)
    return out, nvalid > 0


def string_plane_capacity(col: DeviceColumn, rows: int,
                          max_bytes: int) -> Optional[int]:
    """Bytes that ``rows`` rows of a string column need when none is longer
    than ``max_bytes`` (never more than the column has); None, "as the
    source", for any other column and where no bound is known."""
    if not (max_bytes and col.is_string_like):
        return None
    return min(col.byte_capacity, rows * max_bytes)


def group_keys_output(layout: GroupedLayout, key_cols: Sequence[int],
                      out_capacity: Optional[int] = None,
                      string_max_bytes: int = 0) -> List[DeviceColumn]:
    """Gather the first row of each group for the key output columns.

    A fixed-width key is read from its sorted copy, which the boundaries
    compared; a string key has none and is read from the source through the
    order at the group starts, so its gather runs over as many rows and bytes
    as the output has and not over the batch's byte plane.

    The columns have the layout's capacity, or ``out_capacity`` rows
    where one is given: the gather then reads the first ``out_capacity``
    group starts only, and a string key's byte plane holds ``out_capacity
    × string_max_bytes`` bytes (a string gather costs by its output byte
    plane).  ``string_max_bytes`` is the bucket that ``group_rows`` sorted
    and compared under, so no live key is longer.  Nobody here checks that
    ``layout.num_groups`` fits ``out_capacity``: the caller reports the
    group count to whoever chose the capacity and discards an output that
    overflowed (``plan/fused.py``'s ``g<pos>`` feedback)."""
    starts, count = compaction_map(layout.boundary)
    starts = starts[:out_capacity]
    cap = layout.capacity
    source_rows = jnp.where(
        starts < cap, layout.indices[jnp.minimum(starts, cap - 1)], OOB)
    out = []
    for ci in key_cols:
        col = layout.source.columns[ci]
        if col.is_string_like:
            out.append(gather_column(
                col, source_rows, count, out_capacity=out_capacity,
                out_byte_capacity=(
                    None if out_capacity is None else
                    string_plane_capacity(col, out_capacity,
                                          string_max_bytes))))
        else:
            out.append(gather_column(layout.sorted_column(ci), starts, count,
                                     out_capacity=out_capacity))
    return out


def finalize_agg_column(values: jax.Array, validity: jax.Array,
                        num_groups: jax.Array, dtype: T.DataType) -> DeviceColumn:
    """Trim a [capacity] segment-reduce result to canonical form."""
    cap = values.shape[0]
    live = jnp.arange(cap, dtype=jnp.int32) < num_groups
    valid = validity & live
    data = jnp.where(valid, values, jnp.zeros((), values.dtype))
    return DeviceColumn(data, valid, dtype)


# -- string ordering surrogate ------------------------------------------------
#
# Aggregations that ORDER by a string column (min/max over strings, the
# max_by/min_by ordering key) reduce over a dense int32 rank instead of
# the byte planes: one stable lexsort of the string chunk keys assigns
# every row the ordinal of its distinct value (equal strings share a
# rank), and segment extremes of the rank ARE extremes of the string.
# The reference compares UTF8 bytes directly in libcudf; on TPU the rank
# surrogate keeps the reduction a plain fixed-width segment_min/max.

def string_order_rank(col: DeviceColumn, max_bytes: int) -> jax.Array:
    """int32 [capacity] dense rank of each row's string value in
    lexicographic byte order (Spark UTF8String.binaryCompare); equal
    strings share a rank.  max_bytes must cover the longest live string
    or ordering truncates (same contract as sort_indices).  Null rows
    rank arbitrarily — callers gate on validity."""
    from spark_rapids_tpu.kernels.sort import _string_data_keys
    cap = col.capacity
    chunks = _string_data_keys(col, SortOrder(True), max_bytes)
    # jnp.lexsort: LAST key is primary -> feed least-significant first
    order = jnp.lexsort(tuple(reversed(chunks)))
    eq = jnp.ones((cap,), dtype=jnp.bool_)
    for c in chunks:
        sc = c[order]
        eq = eq & (sc == jnp.roll(sc, 1))
    boundary = (jnp.arange(cap, dtype=jnp.int32) == 0) | ~eq
    ranks_sorted = jnp.cumsum(boundary.astype(jnp.int32)) - 1
    return jnp.zeros((cap,), jnp.int32).at[order].set(ranks_sorted)


def _string_rank_column(col: DeviceColumn, max_bytes: int) -> DeviceColumn:
    """Fixed-width surrogate for a string ordering column: the rank with
    the original validity, so the fixed-width pick/extreme kernels apply
    unchanged."""
    return DeviceColumn(string_order_rank(col, max_bytes), col.validity,
                        T.INT)


def seg_extreme_string(col: DeviceColumn, layout: GroupedLayout,
                       is_min: bool, max_bytes: int) -> DeviceColumn:
    """Per-group MIN/MAX over a string column as a gather: the extreme
    RANK per segment selects the first row (input order) holding the
    extreme value; all-null groups yield null."""
    from spark_rapids_tpu.kernels.selection import OOB, gather_column
    live = layout.live_mask()
    cap = col.capacity
    rank = string_order_rank(col, max_bytes)
    valid = col.validity & live
    ident = jnp.int32(cap) if is_min else jnp.int32(-1)
    contrib = jnp.where(valid, rank, ident)
    reduce = jax.ops.segment_min if is_min else jax.ops.segment_max
    m = reduce(contrib, layout.segment_ids, num_segments=cap)
    has = (m < cap) if is_min else (m >= 0)
    eligible = valid & (rank == m[layout.segment_ids])
    arg, has2 = _seg_arg(eligible, layout, last=False)
    idx = jnp.where(has & has2, arg, jnp.int32(OOB))
    return gather_column(col, idx, layout.num_groups,
                         out_capacity=cap)


def global_extreme_string(col: DeviceColumn, live: jax.Array,
                          is_min: bool, max_bytes: int) -> DeviceColumn:
    """Whole-batch MIN/MAX over a string column -> one-row string column."""
    from spark_rapids_tpu.kernels.selection import OOB, gather_column
    cap = col.capacity
    rank = string_order_rank(col, max_bytes)
    valid = live & col.validity
    ident = jnp.int32(cap) if is_min else jnp.int32(-1)
    contrib = jnp.where(valid, rank, ident)
    m = jnp.min(contrib) if is_min else jnp.max(contrib)
    eligible = valid & (rank == m)
    pos = jnp.arange(cap, dtype=jnp.int32)
    arg = jnp.min(jnp.where(eligible, pos, jnp.int32(cap)))
    has = (arg < cap) & jnp.any(valid)
    idx = jnp.where(has, jnp.clip(arg, 0, cap - 1).astype(jnp.int32)[None],
                    jnp.full((1,), OOB, jnp.int32))
    return gather_column(col, idx, jnp.int32(1), out_capacity=1)


# -- positional picks (first/last/max_by/min_by) -----------------------------
#
# group_rows' stable lexsort preserves input order within each segment, so
# "first live row of the segment" IS Spark's first-in-row-order semantics
# (reference: GpuFirst/GpuLast/GpuMaxBy in aggregateFunctions.scala).  The
# same kernels implement the MERGE ops: partial batches concatenate in
# batch order, so first-partial == global first.

def _seg_arg(eligible: jax.Array, layout: GroupedLayout, last: bool
             ) -> Tuple[jax.Array, jax.Array]:
    """(row index of the first/last eligible row per segment, has-any)."""
    cap = eligible.shape[0]
    pos = jnp.arange(cap, dtype=jnp.int32)
    if last:
        p = jnp.where(eligible, pos, jnp.int32(-1))
        arg = jax.ops.segment_max(p, layout.segment_ids, num_segments=cap)
        has = arg >= 0
    else:
        p = jnp.where(eligible, pos, jnp.int32(cap))
        arg = jax.ops.segment_min(p, layout.segment_ids, num_segments=cap)
        has = arg < cap
    return jnp.clip(arg, 0, cap - 1).astype(jnp.int32), has


def seg_pick(col: DeviceColumn, layout: GroupedLayout, ignore_nulls: bool,
             last: bool) -> DeviceColumn:
    """FIRST/LAST as a gather: works for every device dtype incl. strings
    (the picked subset can never exceed the source byte planes)."""
    from spark_rapids_tpu.kernels.selection import OOB, gather_column
    live = layout.live_mask()
    eligible = live & col.validity if ignore_nulls else live
    arg, has = _seg_arg(eligible, layout, last)
    idx = jnp.where(has, arg, jnp.int32(OOB))
    return gather_column(col, idx, layout.num_groups,
                         out_capacity=col.capacity)


def seg_pick_by(xcol: DeviceColumn, ycol: DeviceColumn,
                layout: GroupedLayout, is_min: bool,
                string_max_bytes: int = 0) -> DeviceColumn:
    """max_by/min_by value: x at the extreme of y; ties take the FIRST row
    in input order (Spark's update keeps the incumbent on equal keys).
    Null y rows never win; all-null-y groups yield null.  y is normalized
    (-0.0 == 0.0; NaN greatest in Spark's total order) like sort keys.
    String ordering keys reduce over their rank surrogate
    (string_order_rank; string_max_bytes must cover the longest live y)."""
    from spark_rapids_tpu.kernels.selection import OOB, gather_column
    live = layout.live_mask()
    if ycol.is_string_like:
        ycol = _string_rank_column(ycol, string_max_bytes)
    ycol = normalize_key_column(ycol)
    m, has = (seg_min if is_min else seg_max)(ycol, layout)
    yv = ycol.data
    eq = yv == m[layout.segment_ids]
    if jnp.issubdtype(yv.dtype, jnp.floating):
        eq = eq | (jnp.isnan(yv) & jnp.isnan(m[layout.segment_ids]))
    eligible = live & ycol.validity & eq
    arg, has2 = _seg_arg(eligible, layout, last=False)
    idx = jnp.where(has & has2, arg, jnp.int32(OOB))
    return gather_column(xcol, idx, layout.num_groups,
                         out_capacity=xcol.capacity)


_BIT_IDENT = {"bit_and": -1, "bit_or": 0, "bit_xor": 0}


def seg_bitwise(col: DeviceColumn, layout: GroupedLayout, op: str,
                out_dtype) -> Tuple[jax.Array, jax.Array]:
    """bit_and / bit_or / bit_xor over integral groups via a segmented
    inclusive scan (flag-resetting combine), reading the running value at
    each segment's last live row."""
    live = layout.live_mask()
    valid = col.validity & live
    ident = jnp.asarray(_BIT_IDENT[op], out_dtype)
    x = jnp.where(valid, col.data.astype(out_dtype), ident)
    flag = layout.boundary

    bop = {"bit_and": jnp.bitwise_and, "bit_or": jnp.bitwise_or,
           "bit_xor": jnp.bitwise_xor}[op]

    def comb(a, b):
        fa, va = a
        fb, vb = b
        return fa | fb, jnp.where(fb, vb, bop(va, vb))

    _f, v = jax.lax.associative_scan(comb, (flag, x))
    arg, has = _seg_arg(live, layout, last=True)
    out = v[arg]
    nvalid = jax.ops.segment_sum(valid.astype(jnp.int32),
                                 layout.segment_ids,
                                 num_segments=col.capacity)
    return out, has & (nvalid > 0)


# -- whole-batch (global, no grouping keys) variants --------------------------

def global_pick(col: DeviceColumn, live: jax.Array, ignore_nulls: bool,
                last: bool) -> DeviceColumn:
    from spark_rapids_tpu.kernels.selection import OOB, gather_column
    cap = col.capacity
    eligible = live & col.validity if ignore_nulls else live
    pos = jnp.arange(cap, dtype=jnp.int32)
    if last:
        arg = jnp.max(jnp.where(eligible, pos, jnp.int32(-1)))
        has = arg >= 0
    else:
        arg = jnp.min(jnp.where(eligible, pos, jnp.int32(cap)))
        has = arg < cap
    idx = jnp.full((1,), OOB, jnp.int32)
    idx = jnp.where(has, jnp.clip(arg, 0, cap - 1).astype(jnp.int32)[None],
                    idx)
    return gather_column(col, idx, jnp.int32(1), out_capacity=1)


def global_pick_by(xcol: DeviceColumn, ycol: DeviceColumn, live: jax.Array,
                   is_min: bool, string_max_bytes: int = 0) -> DeviceColumn:
    from spark_rapids_tpu.kernels.selection import OOB, gather_column
    cap = xcol.capacity
    if ycol.is_string_like:
        ycol = _string_rank_column(ycol, string_max_bytes)
    ycol = normalize_key_column(ycol)
    valid = live & ycol.validity
    yv = ycol.data
    if jnp.issubdtype(yv.dtype, jnp.floating):
        # Spark total order: NaN greatest — never the min; always the max
        key = jnp.where(jnp.isnan(yv), jnp.inf, yv)
        ident = jnp.asarray(jnp.inf if is_min else -jnp.inf, yv.dtype)
        k = jnp.where(valid, key, ident)
    else:
        info = jnp.iinfo(yv.dtype) if yv.dtype != jnp.bool_ else None
        if info is None:
            ident = jnp.asarray(is_min, yv.dtype)
            k = jnp.where(valid, yv, ident)
        else:
            ident = jnp.asarray(info.max if is_min else info.min, yv.dtype)
            k = jnp.where(valid, yv, ident)
    m = jnp.min(k) if is_min else jnp.max(k)
    eligible = valid & (k == m)
    pos = jnp.arange(cap, dtype=jnp.int32)
    arg = jnp.min(jnp.where(eligible, pos, jnp.int32(cap)))
    has = (arg < cap) & jnp.any(valid)
    idx = jnp.where(has, jnp.clip(arg, 0, cap - 1).astype(jnp.int32)[None],
                    jnp.full((1,), OOB, jnp.int32))
    return gather_column(xcol, idx, jnp.int32(1), out_capacity=1)


def global_bitwise(col: DeviceColumn, live: jax.Array, op: str, out_dtype
                   ) -> Tuple[jax.Array, jax.Array]:
    valid = col.validity & live
    ident = jnp.asarray(_BIT_IDENT[op], out_dtype)
    x = jnp.where(valid, col.data.astype(out_dtype), ident)
    red = {"bit_and": lambda a: jnp.bitwise_and.reduce(a),
           "bit_or": lambda a: jnp.bitwise_or.reduce(a),
           "bit_xor": lambda a: jnp.bitwise_xor.reduce(a)}
    out = red[op](x)
    return jnp.reshape(out, (1,)), jnp.reshape(jnp.any(valid), (1,))
