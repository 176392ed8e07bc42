"""pyarrow ⇄ device-batch interop.

The host staging layer: file readers (io/) decode Parquet/ORC/CSV/JSON into
Arrow on host CPU threads (the TPU analog of the reference's HostMemoryBuffer
assembly in MultiFileCloudParquetPartitionReader, GpuParquetScan.scala:3134),
and this module uploads Arrow buffers into canonical DeviceColumns; writers
run the reverse.
"""
from __future__ import annotations

from typing import Optional

import numpy as np
import pyarrow as pa

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import (ColumnarBatch, Schema,
                                              host_scalar)
from spark_rapids_tpu.columnar.column import (DeviceColumn, put_plane,
                                              round_up_pow2)

_ARROW_TO_SQL = {
    pa.bool_(): T.BOOLEAN,
    pa.int8(): T.BYTE,
    pa.int16(): T.SHORT,
    pa.int32(): T.INT,
    pa.int64(): T.LONG,
    pa.float32(): T.FLOAT,
    pa.float64(): T.DOUBLE,
    pa.string(): T.STRING,
    pa.large_string(): T.STRING,
    pa.binary(): T.BINARY,
    pa.date32(): T.DATE,
}


def arrow_type_to_sql(at: pa.DataType) -> T.DataType:
    if at in _ARROW_TO_SQL:
        return _ARROW_TO_SQL[at]
    if pa.types.is_timestamp(at):
        return T.TIMESTAMP
    if pa.types.is_decimal(at):
        return T.DecimalType(at.precision, at.scale)
    if pa.types.is_dictionary(at):
        return arrow_type_to_sql(at.value_type)
    if pa.types.is_list(at) or pa.types.is_large_list(at):
        return T.ArrayType(arrow_type_to_sql(at.value_type))
    if pa.types.is_struct(at):
        return T.StructType(tuple(
            T.StructField(at.field(i).name,
                          arrow_type_to_sql(at.field(i).type),
                          at.field(i).nullable)
            for i in range(at.num_fields)))
    if pa.types.is_map(at):
        return T.MapType(arrow_type_to_sql(at.key_type),
                         arrow_type_to_sql(at.item_type))
    raise NotImplementedError(f"unsupported arrow type: {at}")


def sql_type_to_arrow(dt: T.DataType) -> pa.DataType:
    if isinstance(dt, T.BooleanType):
        return pa.bool_()
    if isinstance(dt, T.ByteType):
        return pa.int8()
    if isinstance(dt, T.ShortType):
        return pa.int16()
    if isinstance(dt, T.IntegerType):
        return pa.int32()
    if isinstance(dt, T.LongType):
        return pa.int64()
    if isinstance(dt, T.FloatType):
        return pa.float32()
    if isinstance(dt, T.DoubleType):
        return pa.float64()
    if isinstance(dt, T.StringType):
        return pa.string()
    if isinstance(dt, T.BinaryType):
        return pa.binary()
    if isinstance(dt, T.DateType):
        return pa.date32()
    if isinstance(dt, T.TimestampType):
        return pa.timestamp("us", tz="UTC")
    if isinstance(dt, T.DecimalType):
        return pa.decimal128(dt.precision, dt.scale)
    if isinstance(dt, T.ArrayType):
        return pa.list_(sql_type_to_arrow(dt.element_type))
    if isinstance(dt, T.StructType):
        return pa.struct([pa.field(f.name, sql_type_to_arrow(f.dtype),
                                   f.nullable)
                          for f in dt.fields])
    if isinstance(dt, T.MapType):
        return pa.map_(sql_type_to_arrow(dt.key_type),
                       sql_type_to_arrow(dt.value_type))
    raise NotImplementedError(f"unsupported sql type: {dt}")


def decimal_unscaled_int64(arr: pa.Array) -> np.ndarray:
    """Unscaled int64 values of a decimal(p<=18, s) Arrow array, nulls as 0.

    Reads the low 64-bit limb of each little-endian two's-complement
    decimal128 value straight from the data buffer: |unscaled| < 10^18 <
    2^63, so the low limb IS the value.  (A per-row Decimal round trip
    costs microseconds a value — minutes on a fact-table scan.)"""
    if not pa.types.is_decimal128(arr.type):
        arr = arr.cast(pa.decimal128(arr.type.precision, arr.type.scale))
    n = len(arr)
    limbs = np.frombuffer(arr.buffers()[1], dtype=np.int64)
    vals = limbs[2 * arr.offset: 2 * (arr.offset + n): 2].copy()
    if arr.null_count:
        vals[~np.asarray(arr.is_valid())] = 0
    return vals


def decimal_array_from_unscaled(unscaled, precision: int, scale: int,
                                validity=None) -> pa.Array:
    """decimal128(precision<=18, scale) Arrow array from unscaled int64s
    (the inverse of ``decimal_unscaled_int64``), no per-row objects."""
    lo = np.ascontiguousarray(unscaled, dtype=np.int64)
    limbs = np.empty((len(lo), 2), dtype=np.int64)
    limbs[:, 0] = lo
    limbs[:, 1] = lo >> 63          # sign extension into the high limb
    vbuf = None
    if validity is not None and not np.all(validity):
        vbuf = pa.array(np.asarray(validity, np.bool_)).buffers()[1]
    return pa.Array.from_buffers(
        pa.decimal128(precision, scale), len(lo),
        [vbuf, pa.py_buffer(limbs.tobytes())])


def _chunked_to_array(col) -> pa.Array:
    if isinstance(col, pa.ChunkedArray):
        return col.combine_chunks()
    return col


def arrow_column_to_device(arr: pa.Array, dtype: T.DataType,
                           capacity: int) -> DeviceColumn:
    arr = _chunked_to_array(arr)
    n = len(arr)
    if pa.types.is_dictionary(arr.type):
        arr = arr.dictionary_decode()
    if isinstance(dtype, T.ArrayType):
        # List<elem> upload via python objects (list columns are cold-path
        # inputs; the hot scan columns are primitives/strings)
        return DeviceColumn.from_arrays(arr.to_pylist(), dtype, capacity=capacity)
    if isinstance(dtype, T.StructType):
        rows = [None if v is None else tuple(v[f.name] for f in dtype.fields)
                for v in arr.to_pylist()]
        return DeviceColumn.from_structs(rows, dtype, capacity=capacity)
    if isinstance(dtype, T.MapType):
        # arrow MapArray rows arrive as lists of (key, value) tuples
        return DeviceColumn.from_maps(arr.to_pylist(), dtype,
                                      capacity=capacity)
    if dtype.variable_width:
        if pa.types.is_large_string(arr.type) or pa.types.is_large_binary(arr.type):
            arr = arr.cast(pa.string() if pa.types.is_large_string(arr.type) else pa.binary())
        # Fast path: Arrow string arrays already hold the exact
        # int32-offsets + bytes layout DeviceColumn wants; slice the raw
        # buffers into numpy views instead of round-tripping Python objects.
        bufs = arr.buffers()
        off_view = np.frombuffer(bufs[1], dtype=np.int32)[arr.offset : arr.offset + n + 1]
        base = off_view[0] if n > 0 else 0
        data_all = np.frombuffer(bufs[2], dtype=np.uint8) if bufs[2] is not None else np.zeros(0, np.uint8)
        total = int(off_view[n] - base) if n > 0 else 0
        if arr.null_count:
            validity = np.asarray(arr.is_valid())
        else:
            validity = np.ones((n,), dtype=np.bool_)
        cap = capacity
        bcap = round_up_pow2(max(total, 1))
        offsets = np.zeros((cap + 1,), dtype=np.int32)
        offsets[: n + 1] = off_view - base
        offsets[n + 1 :] = offsets[n]
        datab = np.zeros((bcap,), dtype=np.uint8)
        if total:
            datab[:total] = data_all[base : base + total]
        validity_full = np.zeros((cap,), dtype=np.bool_)
        validity_full[:n] = validity
        return DeviceColumn(
            data=put_plane(datab),
            validity=put_plane(validity_full),
            dtype=dtype,
            offsets=put_plane(offsets),
        )
    if isinstance(dtype, T.TimestampType):
        arr = arr.cast(pa.timestamp("us"))
        # fill nulls BEFORE to_numpy: a null-carrying conversion degrades
        # to float64, silently corrupting |micros| > 2^53 (pre-1684 dates)
        np_vals = arr.cast(pa.int64()).fill_null(0).to_numpy(
            zero_copy_only=False)
    elif isinstance(dtype, T.DateType):
        np_vals = arr.cast(pa.int32()).fill_null(0).to_numpy(
            zero_copy_only=False)
    elif isinstance(dtype, T.DecimalType):
        if dtype.uses_two_limbs:
            raise NotImplementedError("decimal precision > 18 upload")
        np_vals = decimal_unscaled_int64(arr)
    else:
        # fill_null keeps nulls from surfacing as NaN/garbage in to_numpy;
        # DeviceColumn.from_numpy re-zeroes null slots for canonical padding.
        null_fill = False if pa.types.is_boolean(arr.type) else 0
        filled = arr.fill_null(null_fill) if arr.null_count else arr
        np_vals = filled.to_numpy(zero_copy_only=False)
        if np_vals.dtype != dtype.np_dtype:
            np_vals = np_vals.astype(dtype.np_dtype)
    if arr.null_count:
        validity = np.asarray(arr.is_valid())
    else:
        validity = np.ones((n,), dtype=np.bool_)
    return DeviceColumn.from_numpy(np_vals, dtype, validity, capacity=capacity)


def arrow_to_batch(table, capacity: Optional[int] = None) -> ColumnarBatch:
    if isinstance(table, pa.RecordBatch):
        table = pa.Table.from_batches([table])
    n = table.num_rows
    cap = capacity if capacity is not None else round_up_pow2(max(n, 1))
    names, dtypes, cols = [], [], []
    for field, col in zip(table.schema, table.columns):
        dt = arrow_type_to_sql(field.type)
        names.append(field.name)
        dtypes.append(dt)
        cols.append(arrow_column_to_device(col, dt, cap))
    return ColumnarBatch(
        tuple(cols), host_scalar(n), Schema(tuple(names), tuple(dtypes))
    )


def batch_to_arrow(batch: ColumnarBatch) -> pa.Table:
    n = batch.host_num_rows()
    arrays = []
    fields = []
    for name, dtype, col in zip(batch.schema.names, batch.schema.dtypes, batch.columns):
        at = sql_type_to_arrow(dtype)
        if isinstance(dtype, (T.ArrayType, T.MapType)):
            arrays.append(pa.array(col.to_pylist(n), type=at))
        elif isinstance(dtype, T.StructType):
            rows = [None if v is None
                    else {f.name: v[i] for i, f in enumerate(dtype.fields)}
                    for v in col.to_pylist(n)]
            arrays.append(pa.array(rows, type=at))
        elif dtype.variable_width:
            # Build from raw buffers: offsets/data download straight into an
            # Arrow StringArray without Python-object round-trips.
            offsets = np.asarray(col.offsets)[: n + 1]
            nbytes = int(offsets[n]) if n > 0 else 0
            data = np.asarray(col.data)[:nbytes]
            valid = np.asarray(col.validity)[:n]
            validity_buf = pa.array(valid).buffers()[1]
            arr = pa.Array.from_buffers(
                pa.string() if isinstance(dtype, T.StringType) else pa.binary(),
                n,
                [validity_buf, pa.py_buffer(offsets.tobytes()), pa.py_buffer(data.tobytes())],
            )
            # Null rows may carry nonzero extents after gathers; normalize to
            # empty so results match the CPU oracle exactly.
            if not valid.all():
                arr = pa.compute.if_else(pa.array(valid), arr, pa.scalar(None, type=arr.type))
            arrays.append(arr.cast(at) if arr.type != at else arr)
        else:
            data, valid = col.to_numpy(n)
            # force OWNING host copies: np.asarray over a jax CPU array is
            # a zero-copy view, and pa.array wraps primitive numpy arrays
            # zero-copy too — an Arrow table silently referencing jax
            # buffer memory corrupts the heap if the buffer is reclaimed
            # while the table is alive (intermittent segfaults under the
            # engine thread pool)
            data = np.array(data, copy=True)
            valid = np.array(valid, copy=True)
            if isinstance(dtype, T.DecimalType):
                arrays.append(decimal_array_from_unscaled(
                    data, dtype.precision, dtype.scale, valid))
            elif isinstance(dtype, (T.DateType, T.TimestampType)):
                base = pa.array(np.asarray(data), type=pa.int32() if isinstance(dtype, T.DateType) else pa.int64())
                casted = base.cast(at)
                mask = pa.array(np.asarray(valid))
                arrays.append(pa.compute.if_else(mask, casted, pa.scalar(None, type=at)))
            else:
                arrays.append(pa.array(np.asarray(data), type=at,
                                       mask=~np.asarray(valid)))
        fields.append(pa.field(name, at))
    return pa.Table.from_arrays(arrays, schema=pa.schema(fields))
