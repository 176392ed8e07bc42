"""Parquet read/write for the TPU engine.

Reference: parquet/GpuParquetScan.scala — PERFILE reader (:3631), footer
parse + row-group pruning, chunked batching (:3409);
GpuParquetFileFormat.scala for writes.

TPU lowering per SURVEY.md §2.1: host decode (Arrow C++ via pyarrow — a
native columnar decoder, not a Python loop) feeding HBM upload; the decode
runs OFF the device semaphore, only the upload path touches the device.
Row-group pruning by min/max statistics mirrors the reference's footer
filter; a Pallas page-decoder is the north-star follow-on.
"""
from __future__ import annotations

from typing import Iterator, List, Optional, Sequence, Tuple

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.arrow import arrow_to_batch, batch_to_arrow, arrow_type_to_sql
from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema


def _open_parquet(path: str) -> pq.ParquetFile:
    """Local paths open directly; URLs (s3://, gs://, memory://, ...) open
    through the fsspec ranged source with the footer prefetched — the
    object-store entry point (S3InputFile.scala analog)."""
    from spark_rapids_tpu.io.rangeio import (
        is_remote_path, open_footer, open_source)
    if is_remote_path(path):
        return pq.ParquetFile(open_footer(open_source(path)))
    return pq.ParquetFile(path)


def parquet_schema(path: str, columns: Optional[Sequence[str]] = None) -> Schema:
    pf = _open_parquet(path)
    arrow_schema = pf.schema_arrow
    names = []
    dtypes = []
    for field in arrow_schema:
        if columns and field.name not in columns:
            continue
        names.append(field.name)
        dtypes.append(arrow_type_to_sql(field.type))
    if columns:
        order = {n: i for i, n in enumerate(columns)}
        pairs = sorted(zip(names, dtypes), key=lambda p: order[p[0]])
        names = [p[0] for p in pairs]
        dtypes = [p[1] for p in pairs]
    return Schema(tuple(names), tuple(dtypes))


def _stats_allow(row_group, col_index: int, lo, hi) -> bool:
    """Can this row group contain values in [lo, hi]?  (min/max pruning)"""
    col = row_group.column(col_index)
    stats = col.statistics
    if stats is None or not stats.has_min_max:
        return True
    if hi is not None and stats.min is not None and stats.min > hi:
        return False
    if lo is not None and stats.max is not None and stats.max < lo:
        return False
    return True


def iter_parquet_arrow(
    path: str,
    columns: Optional[Sequence[str]] = None,
    batch_size_rows: int = 1 << 20,
    range_filters: Optional[dict] = None,
    batch_size_bytes: int = 0,
    coalesce_ranges: bool = False,
) -> Iterator[pa.Table]:
    """HOST side of the scan: footer parse, row-group pruning, page decode
    to Arrow tables — safe to run on the reader pool with no semaphore.
    Everything up to the first row group being ready to read is one
    ``scan.open`` span.

    range_filters: {column: (lo, hi)} predicate-pushdown hints used for
    row-group pruning only (exact filtering stays in the Filter exec —
    same contract as the reference's footer filter).

    batch_size_bytes > 0 bounds decoded bytes per batch (the CHUNKED
    reader, GpuParquetScan.scala:2523): rows-per-batch derives from the
    file's own rows/bytes ratio so a scan's device footprint is
    independent of file size.  coalesce_ranges reads the pruned column
    chunks as few merged I/O requests (io/rangeio.py).
    """
    from spark_rapids_tpu.utils.tracing import trace_range
    with trace_range("scan.open"):
        from spark_rapids_tpu.io.rangeio import is_remote_path
        remote = is_remote_path(path)
        if remote:
            # object-store scans ALWAYS take the coalesced multithreaded
            # tier: per-page seeks against an object store are latency
            # death (the reference routes cloud paths to the
            # MULTITHREADED reader, GpuParquetScan.scala:3134)
            coalesce_ranges = True
        pf = _open_parquet(path)
        groups: List[int] = []
        meta = pf.metadata
        name_to_idx = {meta.schema.column(i).name: i
                       for i in range(len(meta.schema))}
        for rg in range(meta.num_row_groups):
            row_group = meta.row_group(rg)
            keep = True
            if range_filters:
                for cname, (lo, hi) in range_filters.items():
                    ci = name_to_idx.get(cname)
                    if ci is not None and not _stats_allow(
                            row_group, ci, lo, hi):
                        keep = False
                        break
            if keep:
                groups.append(rg)
        if not groups:
            return
        rows_per_batch = batch_size_rows
        if batch_size_bytes > 0 and meta.num_rows:
            total_bytes = sum(meta.row_group(rg).total_byte_size
                              for rg in range(meta.num_row_groups))
            bytes_per_row = max(total_bytes / max(meta.num_rows, 1), 1.0)
            rows_per_batch = max(min(
                batch_size_rows, int(batch_size_bytes / bytes_per_row)), 1)
        if coalesce_ranges:
            from spark_rapids_tpu.io.rangeio import open_coalesced_parquet
            src, _ = open_coalesced_parquet(path, groups, columns)
            pf = pq.ParquetFile(src)
        # LEGACY-calendar files (org.apache.spark.legacyDateTime footer
        # tag) carry hybrid Julian dates/timestamps: rebase to proleptic
        # Gregorian on the host path (datetimeRebaseUtils.scala:53-58;
        # VERDICT r3 #4 — without this, pre-1582 values are silently wrong)
        from spark_rapids_tpu.io.rebase import (needs_rebase,
                                                rebase_arrow_table)
        legacy = needs_rebase(meta)
    for record_batch in pf.iter_batches(batch_size=rows_per_batch,
                                        row_groups=groups,
                                        columns=list(columns) if columns else None):
        table = pa.Table.from_batches([record_batch])
        if legacy:
            table = rebase_arrow_table(table)
        yield table


def read_parquet_batches(
    path: str,
    columns: Optional[Sequence[str]] = None,
    batch_size_rows: int = 1 << 20,
    range_filters: Optional[dict] = None,
) -> Iterator[ColumnarBatch]:
    """Stream one file as DEVICE batches (host decode + upload, serial)."""
    for table in iter_parquet_arrow(path, columns, batch_size_rows,
                                    range_filters):
        yield arrow_to_batch(table)


def write_parquet(batches, path: str, schema: Optional[Schema] = None) -> int:
    """Device batches -> one parquet file; returns rows written.

    (ColumnarOutputWriter.scala analog: download + host encode.)
    """
    writer = None
    rows = 0
    try:
        for batch in batches:
            table = batch_to_arrow(batch)
            if writer is None:
                writer = pq.ParquetWriter(path, table.schema)
            writer.write_table(table)
            rows += batch.host_num_rows()
        if writer is None and schema is not None:
            from spark_rapids_tpu.columnar.arrow import sql_type_to_arrow
            empty = pa.table({n: pa.array([], type=sql_type_to_arrow(d))
                              for n, d in zip(schema.names, schema.dtypes)})
            writer = pq.ParquetWriter(path, empty.schema)
            writer.write_table(empty)
    finally:
        if writer is not None:
            writer.close()
    return rows
