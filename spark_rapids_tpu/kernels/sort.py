"""Sort kernels: multi-key lexicographic argsort with Spark ordering rules.

TPU replacement for cuDF's `Table.orderBy` (reference consumption:
GpuSortExec.scala:87).  Ordering semantics match Spark's SortExec:

  * ASC NULLS FIRST is Spark's default (NULLS LAST for DESC); all four null
    orderings supported, and NULLS FIRST/LAST is absolute (not affected by
    the direction of the data ordering).
  * Floats use Java Double.compare's total order: -0.0 < 0.0 and NaN sorts
    greater than +Inf.
  * Stable (ties keep input order), so partial sorts compose.

Strategy: each key column contributes (null_key, data_key...) integer keys to
one stable jnp.lexsort (XLA variadic sort); a liveness key sinks padding rows
to the end.  Strings are ranked by byte chunks packed 7-bytes-per-uint64 in
9-bit lanes (byte+1, 0 = past-end) so 'ab' < 'ab\\x00' orders correctly;
max_bytes is a static bucket — the planner falls back for longer sort keys.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu.kernels.selection import gather_batch


class SortOrder:
    """Direction + null placement of one sort key."""

    def __init__(self, ascending: bool = True, nulls_first: Optional[bool] = None):
        self.ascending = ascending
        # Spark default: NULLS FIRST for ASC, NULLS LAST for DESC
        self.nulls_first = nulls_first if nulls_first is not None else ascending

    def __repr__(self):
        return (f"{'ASC' if self.ascending else 'DESC'} "
                f"NULLS {'FIRST' if self.nulls_first else 'LAST'}")


def _f32_total_order_bits(x: jax.Array) -> jax.Array:
    """float32 -> uint32 preserving Java Float.compare total order."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    sign = jnp.uint32(1) << 31
    return jnp.where((bits & sign) != 0, ~bits, bits | sign)


def f64_total_order_u64(x: jax.Array) -> jax.Array:
    """float64 -> uint64 total-order key (-0.0 < 0.0, NaN above +Inf).

    TPU has no native float64: X64 values are emulated (float32 pairs)
    and the X64-rewrite pass cannot implement a f64->u64 bitcast (raw
    IEEE-754 double bits do not exist on chip).  There the key is built
    from the double-double split — hi = f32(x), lo = f32(x - hi) — each
    totalized through the SUPPORTED f32->u32 bitcast and packed with u64
    arithmetic (which IS emulated).  The split is lossless for every
    value representable under the emulation, so ordering matches; on
    CPU/GPU the exact bitcast path keeps true f64 tie-breaking."""
    if jax.default_backend() == "tpu":
        hi = x.astype(jnp.float32)
        lo = (x - hi.astype(jnp.float64)).astype(jnp.float32)
        hk = _f32_total_order_bits(hi).astype(jnp.uint64)
        lk = _f32_total_order_bits(lo).astype(jnp.uint64)
        return (hk << jnp.uint64(32)) | lk
    bits = jax.lax.bitcast_convert_type(x, jnp.uint64)
    sign = jnp.uint64(1) << 63
    return jnp.where((bits & sign) != 0, ~bits, bits | sign)


def f64_injective_u64(x: jax.Array) -> jax.Array:
    """float64 -> uint64 INJECTIVE bit key (equality/identity uses, not
    ordering).  Raw IEEE bits on CPU/GPU; the double-double split's f32
    bit patterns packed with u64 arithmetic on TPU (see
    f64_total_order_u64 for why the direct bitcast cannot exist there)."""
    if jax.default_backend() == "tpu":
        hi = x.astype(jnp.float32)
        lo = (x - hi.astype(jnp.float64)).astype(jnp.float32)
        return (jax.lax.bitcast_convert_type(hi, jnp.uint32)
                .astype(jnp.uint64) << jnp.uint64(32)) | \
            jax.lax.bitcast_convert_type(lo, jnp.uint32).astype(jnp.uint64)
    return jax.lax.bitcast_convert_type(x, jnp.uint64)


def _float_total_order_bits(x: jax.Array) -> jax.Array:
    """Map float32/float64 to same-width uint preserving Java's
    Float/Double.compare total order (-0.0 < 0.0, NaN above +Inf)."""
    if x.dtype == jnp.float64:
        return f64_total_order_u64(x)
    return _f32_total_order_bits(x)


def _signed_to_unsigned(x: jax.Array) -> jax.Array:
    """Order-preserving signed→unsigned (offset by flipping the sign bit)."""
    return x.astype(jnp.int64).astype(jnp.uint64) ^ (jnp.uint64(1) << 63)


def _data_key_fixed(col: DeviceColumn, order: SortOrder) -> jax.Array:
    dt = col.dtype
    if isinstance(dt, T.BooleanType):
        k = col.data.astype(jnp.uint64)
    elif isinstance(dt, T.FloatType):
        k = _float_total_order_bits(col.data).astype(jnp.uint64)
    elif isinstance(dt, T.DoubleType):
        k = _float_total_order_bits(col.data)
    else:
        k = _signed_to_unsigned(col.data)
    if not order.ascending:
        k = ~k
    # null rows get a constant so they never perturb less-significant keys
    return jnp.where(col.validity, k, jnp.uint64(0))


def _null_key(col: DeviceColumn, order: SortOrder) -> jax.Array:
    if order.nulls_first:
        return jnp.where(col.validity, jnp.uint8(1), jnp.uint8(0))
    return jnp.where(col.validity, jnp.uint8(0), jnp.uint8(1))


def _decimal128_data_keys(col: DeviceColumn,
                          order: SortOrder) -> List[jax.Array]:
    """Two-limb decimal order keys: signed hi limb then unsigned lo limb
    (int128 comparison order), most significant first."""
    hi, lo = col.children
    k_hi = _signed_to_unsigned(hi.data)
    k_lo = lo.data.astype(jnp.int64).astype(jnp.uint64)
    if not order.ascending:
        k_hi = ~k_hi
        k_lo = ~k_lo
    return [jnp.where(col.validity, k, jnp.uint64(0))
            for k in (k_hi, k_lo)]


def _struct_data_keys(col: DeviceColumn, order: SortOrder) -> List[jax.Array]:
    """Flatten a struct key column into uint64 leaf keys, most significant
    first: per field a null-flag key (null field sorts smallest ascending,
    flipped with the direction like Spark's struct comparator) then the
    field's data key.  Keys are masked to zero on null STRUCT rows so the
    lexsort stays stable among them (the struct's own null key has already
    grouped those rows)."""
    keys: List[jax.Array] = []
    for i, f in enumerate(col.dtype.fields):
        fc = col.children[i]
        flag = DeviceColumn(fc.validity, jnp.ones_like(col.validity),
                            T.BOOLEAN)
        keys.append(_data_key_fixed(flag, order))
        if fc.is_struct:
            keys.extend(_struct_data_keys(fc, order))
        else:
            keys.append(_data_key_fixed(fc, order))
    return [jnp.where(col.validity, k, jnp.uint64(0)) for k in keys]


BYTES_PER_CHUNK = 7  # 9-bit lanes (byte value + 1; 0 = past end) in a uint64


def _string_chunk_planes(col: DeviceColumn, max_bytes: int):
    """(uint64 [n_chunks, capacity] ascending chunk keys, most-significant
    chunk first and zero on null rows; int32 scalar: the byte steps run).

    The shapes follow the static bucket, ``ceil(max_bytes / 7)`` chunks; the
    work follows the data: one u8 gather a byte position up to the longest
    string of the column (padding and dead rows included: never fewer steps
    than a live key needs), a device scalar, so a char(1) column under a
    16-byte bucket costs one gather and not 21.  A position no step visits
    leaves lane 0, which is what "past the end" is."""
    starts = col.offsets[:-1]
    lengths = col.offsets[1:] - starts
    n_chunks = max(1, -(-max_bytes // BYTES_PER_CHUNK))
    steps = jnp.minimum(jnp.max(lengths, initial=0),
                        n_chunks * BYTES_PER_CHUNK).astype(jnp.int32)
    last = col.data.shape[0] - 1

    def step(pos, planes):
        byte = col.data[jnp.clip(starts + pos, 0, last)]
        lane = jnp.where(pos < lengths, byte.astype(jnp.uint64) + 1,
                         jnp.uint64(0))
        c = pos // BYTES_PER_CHUNK
        shift = 9 * (BYTES_PER_CHUNK - 1 - pos % BYTES_PER_CHUNK)
        return planes.at[c].set(planes[c] | (lane << shift.astype(jnp.uint64)))

    planes = jax.lax.fori_loop(
        0, steps, step, jnp.zeros((n_chunks, col.capacity), jnp.uint64))
    return jnp.where(col.validity[None, :], planes, jnp.uint64(0)), steps


def string_key_planes(batch: ColumnarBatch, key_cols: Sequence[int],
                      max_bytes: int) -> Dict[int, tuple]:
    """{ordinal: ``_string_chunk_planes``} of the string columns among
    ``key_cols``: for a caller that sorts by them (``sort_indices``,
    ``string_planes=``) and reads them again to delimit runs of equal keys,
    so that they are built once."""
    return {ci: _string_chunk_planes(batch.columns[ci], max_bytes)
            for ci in key_cols if batch.columns[ci].is_string_like}


def _string_data_keys(col: DeviceColumn, order: SortOrder, max_bytes: int,
                      planes: Optional[jax.Array] = None) -> List[jax.Array]:
    """uint64 chunk keys, most-significant chunk first.  Lexicographic byte
    order == unsigned comparison of the chunk sequence (Spark
    UTF8String.binaryCompare).  ``planes``: the column's ascending planes
    where the caller holds them already."""
    if planes is None:
        planes, _ = _string_chunk_planes(col, max_bytes)
    if not order.ascending:
        planes = jnp.where(col.validity[None, :], ~planes, jnp.uint64(0))
    return list(planes)


def _string_hash_key(col: DeviceColumn, chunks: Sequence[jax.Array]) -> jax.Array:
    """ONE uint64 GROUPING key per string column: an FNV-1a-style fold of
    its ascending chunk keys.  Equal strings always hash equal;
    distinct strings may collide — so this key is ONLY valid for callers
    that need EQUAL-KEYS-CONTIGUOUS rather than byte order, and whose
    group boundaries re-verify the actual bytes (groupby's exact
    adjacent-row compare).  A collision then SPLITS a group (stable sort
    interleaves the colliding values), it can never merge two groups —
    split-tolerant consumers (partial aggregation, whose per-batch
    partials merge again downstream) trade that for sorting 1 key pass
    per string column instead of ceil(max_bytes/7) passes."""
    h = jnp.full((col.capacity,), jnp.uint64(14695981039346656037))
    for chunk in chunks:
        h = (h ^ chunk) * jnp.uint64(1099511628211)
    return jnp.where(col.validity, h, jnp.uint64(0))


@jax.named_scope("sort_indices")
def sort_indices(
    batch: ColumnarBatch,
    key_cols: Sequence[int],
    orders: Sequence[SortOrder],
    string_max_bytes: Optional[int] = None,
    hash_string_keys: bool = False,
    live: Optional[jax.Array] = None,
    string_planes: Optional[Dict[int, tuple]] = None,
) -> jax.Array:
    """Stable argsort of live rows by the given keys; padding rows at end.
    Returns int32 [capacity] gather indices.

    ``live``: bool [capacity], the rows that count where they are not the
    prefix ``batch.live_mask()`` (a fused filter's mask): every other row
    sinks to the end like padding, and the rows that count keep their
    order among equal keys.

    string_max_bytes must cover the longest live string key or ordering
    truncates; None derives it from the data (host sync).

    ``hash_string_keys``: sort strings by ONE hashed key each instead of
    their chunk sequence — equal-keys-contiguous (up to rare collision
    SPLITS), not byte order; see _string_hash_key for the contract.

    ``string_planes``: ``string_key_planes`` of the batch under
    ``string_max_bytes``, from a caller that reads them again; a string key
    that is not in it gets its chunks built here."""
    if string_max_bytes is None:
        from spark_rapids_tpu.kernels import strings as strkern
        string_max_bytes = strkern.live_string_bucket_for_batch(batch, key_cols)
    keys = []  # least significant first (jnp.lexsort: last key is primary)
    for ci, order in zip(reversed(list(key_cols)), reversed(list(orders))):
        col = batch.columns[ci]
        if col.is_string_like:
            planes, _ = (string_planes or {}).get(ci, (None, None))
            chunks = _string_data_keys(
                col, SortOrder(True) if hash_string_keys else order,
                string_max_bytes, planes)
            if hash_string_keys:
                keys.append(_string_hash_key(col, chunks))
            else:
                keys.extend(reversed(chunks))
        elif col.is_struct and isinstance(col.dtype, T.DecimalType):
            for k in reversed(_decimal128_data_keys(col, order)):
                keys.append(k)
        elif col.is_struct:
            for k in reversed(_struct_data_keys(col, order)):
                keys.append(k)
        else:
            keys.append(_data_key_fixed(col, order))
        keys.append(_null_key(col, order))
    if live is None:
        live = batch.live_mask()
    keys.append(jnp.where(live, jnp.uint8(0), jnp.uint8(1)))
    return jnp.lexsort(tuple(keys)).astype(jnp.int32)


def sort_batch(
    batch: ColumnarBatch,
    key_cols: Sequence[int],
    orders: Sequence[SortOrder],
    string_max_bytes: Optional[int] = None,
) -> ColumnarBatch:
    idx = sort_indices(batch, key_cols, orders, string_max_bytes)
    return gather_batch(batch, idx, batch.num_rows)
