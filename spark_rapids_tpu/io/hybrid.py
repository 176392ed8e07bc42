"""Hybrid scan: Arrow Dataset (Acero) streaming decode.

Reference: the hybrid/ module (velox-backed GpuHybridParquetScan) — an
ALTERNATIVE native CPU decode engine plugged in behind the same scan exec
when spark.rapids.sql.hybrid.parquet.enabled is set.  Here the alternative
engine is pyarrow.dataset's C++ streaming scanner: fragment-level
readahead, dictionary/late materialization and thread-pool decode inside
Arrow, yielding record batches that upload through the normal path.
"""
from __future__ import annotations

from typing import Iterator, Optional, Sequence


def iter_hybrid_parquet(path: str,
                        columns: Optional[Sequence[str]] = None,
                        batch_size_rows: int = 1 << 20) -> Iterator:
    """Yield pyarrow RecordBatches via the dataset scanner."""
    import pyarrow.dataset as ds

    from spark_rapids_tpu.utils.tracing import trace_range
    with trace_range("scan.open"):
        dataset = ds.dataset(path, format="parquet")
        scanner = dataset.scanner(
            columns=list(columns) if columns else None,
            batch_size=batch_size_rows,
            use_threads=True)
    for rb in scanner.to_batches():
        if rb.num_rows:
            yield rb
