"""Scan execs: in-memory and Parquet.

Reference: GpuFileSourceScanExec + parquet/GpuParquetScan.scala.  The
PERFILE/COALESCING/MULTITHREADED reader architecture is mirrored in
io/parquet.py; this exec is the plan node gluing a relation to the engine.
Host decode (pyarrow) happens OFF the device semaphore; only the HBM upload
holds it — same discipline as the reference's multi-file readers, which
assemble host buffers in CPU threads and only take the GPU semaphore for
the device decode (GpuMultiFileReader.scala, GpuSemaphore.scala:240).
"""
from __future__ import annotations

from typing import Iterator, List, Optional, Sequence

from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema
from spark_rapids_tpu.plan.execs.base import TpuExec, timed


class TpuInMemoryScanExec(TpuExec):
    def __init__(self, partitions: List[List[ColumnarBatch]], schema: Schema):
        super().__init__((), schema)
        self.partitions = partitions

    def num_partitions(self) -> int:
        return max(len(self.partitions), 1)

    def execute_partition(self, idx: int) -> Iterator[ColumnarBatch]:
        if idx >= len(self.partitions):
            return
        for batch in self.partitions[idx]:
            self.output_rows.add(batch.num_rows)
            yield self._count_out(batch)

    def describe(self):
        return f"TpuInMemoryScan{self.schema!r}"


class _PooledScanExec(TpuExec):
    """Shared scan body: host decode on the reader thread pool, device
    upload under the semaphore.

    While the task waits for the next decoded Arrow chunk it RELEASES the
    TPU semaphore (the engine acquires one count per task) so another
    task's device work can proceed — the reference's discipline of
    acquiring only at device entry (GpuSemaphore.scala:240,
    MultiFileCloudParquetPartitionReader).  Decode of chunk N+1 overlaps
    the consumer's device compute on chunk N via the prefetch queue.
    """

    def _host_iter(self, idx: int):
        raise NotImplementedError

    def _scan_batches(self, idx: int,
                      reader_threads: int) -> Iterator[ColumnarBatch]:
        from spark_rapids_tpu.columnar.arrow import arrow_to_batch
        from spark_rapids_tpu.io.reader_pool import prefetched
        from spark_rapids_tpu.memory.semaphore import tpu_semaphore
        from spark_rapids_tpu.utils.tracing import trace_range

        it = prefetched(lambda: self._host_iter(idx), reader_threads)

        def uploads():
            while True:
                # wait for decode OFF the semaphore; a thread that holds
                # no permit (a pipeline's producer, under its consumer's)
                # takes none for the upload
                with tpu_semaphore().released(), \
                        trace_range("scan.wait",
                                    "task waiting for a decoded chunk "
                                    "(semaphore released)"):
                    table = next(it, None)
                if table is None:
                    return
                # the contexts must CLOSE before the yield: a generator
                # suspends inside an open with-block, which would charge
                # the consumer's whole per-batch compute to scan opTime
                with timed(self.op_time), \
                        trace_range("scan.upload",
                                    "Arrow host chunk -> HBM batch upload "
                                    "(semaphore held)"):
                    batch = arrow_to_batch(table)
                yield batch

        # one-deep upload lookahead (VERDICT r4 #9, the pinned-host
        # double-buffer analog): the NEXT chunk's upload is DISPATCHED
        # before the current batch is yielded — jax transfers are
        # async, so upload(n+1) streams into HBM while the consumer
        # computes on batch n.  Resident bound: two batches.
        up = uploads()
        prev = next(up, None)
        while prev is not None:
            nxt = next(up, None)
            self.output_rows.add(prev.num_rows)
            yield self._count_out(prev)
            prev = nxt


class TpuCachedParquetScanExec(_PooledScanExec):
    """Scan of .persist(serializer='parquet') blobs: decode each
    partition's in-memory parquet back to device batches (reference
    GpuInMemoryTableScanExec over ParquetCachedBatchSerializer data).
    Runs on the pooled-scan body so blob decompression happens OFF the
    device semaphore with prefetch overlap, like every other scan."""

    def __init__(self, partitions, schema: Schema,
                 projection=None, reader_threads: int = 2):
        super().__init__((), schema)
        self.partitions = partitions   # List[List[bytes]]
        self.projection = list(projection) if projection else None
        self.reader_threads = reader_threads

    def num_partitions(self) -> int:
        return max(len(self.partitions), 1)

    def _host_iter(self, idx: int):
        import pyarrow as pa
        import pyarrow.parquet as pq
        for blob in self.partitions[idx]:
            yield pq.read_table(pa.BufferReader(blob),
                                columns=self.projection)

    def execute_partition(self, idx: int) -> Iterator[ColumnarBatch]:
        if idx >= len(self.partitions):
            return
        yield from self._scan_batches(idx, self.reader_threads)

    def describe(self):
        total = sum(len(b) for p in self.partitions for b in p)
        return f"TpuCachedParquetScan{self.schema!r} [{total} bytes]"



class TpuParquetScanExec(_PooledScanExec):
    """One partition per file; host decode runs MULTITHREADED-style on the
    shared reader pool (GpuParquetScan.scala:3134 analog)."""

    def __init__(self, paths: Sequence[str], schema: Schema,
                 column_pruning=None, batch_size_rows: int = 1 << 20,
                 reader_threads: int = 8, conf=None):
        super().__init__((), schema)
        self.paths = list(paths)
        self.column_pruning = column_pruning
        self.batch_size_rows = batch_size_rows
        self.reader_threads = reader_threads
        self.conf = conf

    def num_partitions(self) -> int:
        return max(len(self.paths), 1)

    def _host_iter(self, idx: int):
        path = self.paths[idx]
        if self.conf is not None:
            from spark_rapids_tpu.io.filecache import cached_path
            path = cached_path(path, self.conf)
        cols = list(self.column_pruning) if self.column_pruning else None
        if self.conf is not None and self.conf.hybrid_parquet_enabled:
            from spark_rapids_tpu.io.hybrid import iter_hybrid_parquet
            return iter_hybrid_parquet(
                path, columns=cols, batch_size_rows=self.batch_size_rows)
        from spark_rapids_tpu.io.parquet import iter_parquet_arrow
        return iter_parquet_arrow(
            path, columns=cols, batch_size_rows=self.batch_size_rows,
            batch_size_bytes=(self.conf.reader_batch_size_bytes
                              if self.conf is not None else 0),
            coalesce_ranges=(self.conf is not None
                             and self.conf.parquet_coalesce_ranges))

    def execute_partition(self, idx: int) -> Iterator[ColumnarBatch]:
        if idx >= len(self.paths):
            return
        yield from self._scan_batches(idx, self.reader_threads)

    def describe(self):
        return f"TpuParquetScan[{len(self.paths)} files]"


class TpuFileScanExec(_PooledScanExec):
    """csv/json/orc scan: one partition per file, host-native Arrow decode
    on the reader pool feeding device upload (GpuCSVScan/GpuOrcScan/
    GpuJsonReadCommon analog)."""

    def __init__(self, paths: Sequence[str], fmt: str, schema: Schema,
                 column_pruning=None, options=None,
                 batch_size_rows: int = 1 << 20, reader_threads: int = 8):
        super().__init__((), schema)
        self.paths = list(paths)
        self.fmt = fmt
        self.column_pruning = column_pruning
        self.options = dict(options or {})
        self.batch_size_rows = batch_size_rows
        self.reader_threads = reader_threads

    def num_partitions(self) -> int:
        return max(len(self.paths), 1)

    def _host_iter(self, idx: int):
        from spark_rapids_tpu.io import formats as F
        return F.iter_arrow(
            self.paths[idx], self.fmt,
            columns=self.column_pruning, schema=self.schema,
            batch_size_rows=self.batch_size_rows, **self.options)

    def execute_partition(self, idx: int) -> Iterator[ColumnarBatch]:
        if idx >= len(self.paths):
            return
        yield from self._scan_batches(idx, self.reader_threads)

    def describe(self):
        return f"TpuFileScan[{self.fmt}, {len(self.paths)} files]"
