"""Shuffle exchange exec (v1: in-process, host-staged-optional).

Reference: GpuShuffleExchangeExecBase.scala:174 (device-side partition/slice
then hand off to the shuffle manager) + RapidsShuffleInternalManagerBase.
This v1 is the CACHE_ONLY-mode analog (RapidsCachingWriter:1618): map tasks
partition batches on device and park each partition-ordered batch in the
shuffle catalog as ONE *spillable* handle; reduce tasks read their
partition's row ranges of it.  The transport SPI seam for ICI/multi-host
lives in shuffle/ and plugs in here without changing this exec.

Partition routing is bit-exact Spark murmur3/pmod (kernels/partition.py), so
results agree with the CPU oracle row-for-row.
"""
from __future__ import annotations

from typing import Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.columnar.batch import (ColumnarBatch, Schema,
                                              host_scalar)
from spark_rapids_tpu.columnar.column import round_up_pow2
from spark_rapids_tpu.expressions.core import EvalContext, Expression
from spark_rapids_tpu.kernels.partition import hash_partition, round_robin_partition
from spark_rapids_tpu.kernels.selection import (
    concat_batches_device,
    gather_batch,
)
from spark_rapids_tpu.memory.retry import with_capacity_retry, with_retry_no_split
from spark_rapids_tpu.plan.execs.base import (
    MaterializeLock, TpuExec, string_key_bucket, timed)
from spark_rapids_tpu.utils.tracing import trace_range


def _chain_none(it):
    """Yield everything from ``it`` then a final None flush marker."""
    yield from it
    yield None


def append_key_columns(batch: ColumnarBatch, keys):
    """Evaluate partition-key expressions and append them as columns;
    returns (work_batch, key ordinals).  Shared by the task-engine slice
    step and the SPMD stage compiler."""
    ctx = EvalContext(batch)
    key_cols = tuple(k.eval(ctx) for k in keys)
    work = ColumnarBatch(
        tuple(batch.columns) + key_cols, batch.num_rows,
        Schema(tuple(batch.schema.names) +
               tuple(f"_pk{i}" for i in range(len(key_cols))),
               tuple(batch.schema.dtypes) +
               tuple(c.dtype for c in key_cols)))
    return work, list(range(len(batch.schema), len(work.schema)))


class TpuShuffleExchangeExec(TpuExec):
    """Two shuffle manager modes, mirroring the reference's mode switch
    (RapidsShuffleInternalManagerBase.scala:1751):

      * CACHE_ONLY: partition-ordered map batches stay device-resident
        as spillable handles in the in-process catalog, each reduce
        partition's block a range view (RapidsCachingWriter analog);
      * MULTITHREADED: row ranges are serialized to the tpu-kudo host wire
        format on a writer thread pool and merged back on read
        (RapidsShuffleThreadedWriterBase/ReaderBase analog) — the mode
        that generalizes to multi-host transports.
    """

    def __init__(self, num_partitions: int, keys: Sequence[Expression],
                 child: TpuExec, schema: Optional[Schema] = None,
                 mode: str = "CACHE_ONLY", writer_threads: int = 4,
                 codec: str = "none", target_rows: int = 1 << 20):
        super().__init__((child,), schema or child.schema)
        self.out_partitions = num_partitions
        self.keys = tuple(keys)
        from spark_rapids_tpu.shuffle.serializer import wire_supported
        if mode == "MULTITHREADED" and not all(
                wire_supported(d) for d in self.schema.dtypes):
            # the kudo wire format carries fixed-width + string columns;
            # nested payloads stay device-resident (CACHE_ONLY slices).
            # Downgrading is safe only because MULTITHREADED is an
            # in-process transport; MULTIPROCESS must NOT silently fall
            # back (a remote reduce task would see partial data) — the
            # transport factory raises instead (ADVICE r2 #1).
            mode = "CACHE_ONLY"
        self.mode = mode
        self.writer_threads = writer_threads
        self.codec = codec
        self.target_rows = max(int(target_rows), 1)
        self._lock = MaterializeLock()
        self._transport = None   # built lazily per query (the SPI seam)
        #: materialization generation: bumped on cleanup so epoch-keyed
        #: consumers (SharedCoalesceSpec) never serve groups computed from
        #: a previous execution's map statistics
        self._epoch = 0
        # per-partition row stats cost a host sync per piece: collected
        # only when an AQE coalescing spec registered interest
        self._want_part_stats = False

        keys_t, n_out = self.keys, self.out_partitions  # no self-capture

        def slice_step(batch: ColumnarBatch, rr_start, string_bucket: int = 0):
            """Device: append key columns, partition, return reordered batch
            + per-partition counts.  ``rr_start`` is the round-robin start
            partition — a DYNAMIC scalar rotated across batches (reference
            GpuRoundRobinPartitioning rotates per task) so every batch's
            remainder rows don't pile into partition 0; keyed routing
            ignores it."""
            if not keys_t:
                return round_robin_partition(batch, n_out,
                                             start_partition=rr_start)
            work, key_idx = append_key_columns(batch, keys_t)
            reordered, counts = hash_partition(
                work, key_idx, n_out, string_max_bytes=string_bucket)
            # drop the key columns again
            out = ColumnarBatch(reordered.columns[:len(batch.schema)],
                                reordered.num_rows, batch.schema)
            return out, counts

        from functools import partial as _p
        from spark_rapids_tpu.plan.execs.base import (
            exprs_cache_key, schema_cache_key, shared_jit)
        key = (f"exchange|{num_partitions}|{schema_cache_key(child.schema)}|"
               f"{exprs_cache_key(self.keys)}")
        self._jit_slice = lambda b, rr, _k=key: shared_jit(
            f"{_k}|{(bkt := string_key_bucket(b, self.keys))}",
            lambda: _p(slice_step, string_bucket=bkt),
            kind="exchange_slice")(b, rr)

    def num_partitions(self) -> int:
        return self.out_partitions

    # -- map side -----------------------------------------------------------

    def _partitioned(self):
        """Device-side partition of every input batch ->
        (reordered_batch, counts).  ``counts`` is a DEVICE array on the
        task-engine path (consumers choose how to sync it) and already-
        host numpy on the fused path (the fused program ships counts
        with its feedback fetch — one launch and one device round trip
        per batch for the whole map side, VERDICT r4 #1)."""
        from spark_rapids_tpu.expressions.bridge import tree_has_bridge
        from spark_rapids_tpu.plan.execs.base import (
            collect_trace_consts, exprs_cache_key, tree_uses_string_bucket)
        from spark_rapids_tpu.plan.fused import TpuFusedSegmentExec
        child = self.children[0]
        self._part_rows = [0] * self.out_partitions
        fused = (isinstance(child, TpuFusedSegmentExec)
                 and not tree_has_bridge(self.keys)
                 and not tree_uses_string_bucket(self.keys)
                 and not collect_trace_consts(self.keys))
        if fused:
            ex_sig = f"{self.out_partitions}|{exprs_cache_key(self.keys)}"
            for in_part in range(child.num_partitions()):
                yield from child.execute_partition_sliced(
                    in_part, self.keys, self.out_partitions, ex_sig)
            return
        ordinal = 0    # rotates the round-robin start across batches
        for in_part in range(child.num_partitions()):
            for batch in child.execute_partition(in_part):
                # keep the slice dispatch (the dominant map-side cost)
                # inside opTime, as before the fused path
                with timed(self.op_time, "exchange.write"):
                    rr = host_scalar(ordinal % self.out_partitions)
                    reordered, counts = with_retry_no_split(
                        lambda: self._jit_slice(batch, rr))
                ordinal += 1
                yield reordered, counts

    def _record_part_rows(self, host_counts) -> None:
        from spark_rapids_tpu.shuffle.stats import SHUFFLE_COUNTERS
        SHUFFLE_COUNTERS.add(exchange_rows_written=int(host_counts.sum()))
        if self._want_part_stats:
            # host_counts is already on host; a per-piece host_num_rows
            # would re-sync per partition
            for p in range(self.out_partitions):
                self._part_rows[p] += int(host_counts[p])

    def _slices(self):
        """Device-slice write path of NESTED schemas on wire transports
        (the range writer frames flat layouts only): (partition, device
        piece) per non-empty partition of every input batch.  Per-
        partition row counts are recorded as they stream past — the
        MapStatus sizes AQE coalescing plans from."""
        from spark_rapids_tpu.plan.execs.out_of_core import slice_by_counts
        for reordered, counts in self._partitioned():
            with timed(self.op_time, "exchange.write"):
                host_counts = np.asarray(counts)  # ONE sync per batch
                pieces = slice_by_counts(reordered, host_counts,
                                         self.out_partitions,
                                         count_stat=True)
                self._record_part_rows(host_counts)
            # yielded with the span CLOSED: the transport's write of each
            # piece is a span of its own (_spanned_writes)
            for p, piece in enumerate(pieces):
                if piece is not None:
                    yield p, piece

    def _range_views(self):
        """Range-view write path (CACHE_ONLY): (partition-reordered
        batch, host counts) per map batch — NO slicing at all.  The
        transport stores the batch as ONE spillable backing handle and
        each partition's block becomes a (backing, start, count) range
        view that fused consumers slice inside their own program (the
        device twin of _range_stream's wire-range framing)."""
        for reordered, counts in self._partitioned():
            with timed(self.op_time, "exchange.write"):
                host_counts = np.asarray(counts)  # ONE sync per batch
            self._record_part_rows(host_counts)
            yield reordered, host_counts

    def _range_stream(self):
        """Range-serialization write path: (host batch, host counts) per
        map batch, downloaded in ONE batched device_get — no per-
        partition gather launches, no per-column syncs, no pow2-padded
        piece staging.  The transport frames each partition's wire block
        from host row ranges (GpuPartitioning.scala:66 contiguous_split
        + Kudo row-range serialization analog)."""
        from spark_rapids_tpu.shuffle.serializer import download_partitioned
        for reordered, counts in self._partitioned():
            with timed(self.op_time, "exchange.write"):
                host_batch, host_counts = download_partitioned(
                    reordered, counts)
            self._record_part_rows(host_counts)
            yield host_batch, host_counts

    def partition_row_counts(self) -> List[int]:
        """Materialize the map side and return rows per reduce partition
        (the runtime statistics AQE coalescing reads)."""
        self._materialize()
        return list(getattr(self, "_part_rows",
                            [0] * self.out_partitions))

    def _materialize(self):
        """Run the map side once, writing through the transport SPI
        (RapidsShuffleTransport.scala:303 analog — the data plane is
        pluggable; this exec never touches its storage).  The write shape
        is the map side's one decision, made here from the transport's
        type and the schema:

          * CACHE_ONLY -> range views (``_range_views``);
          * wire, flat schema -> range stream (``_range_stream``);
          * wire, nested schema -> device slices (``_slices``).

        On wire transports the map generator (child compute + device
        partition + download — which includes the UPSTREAM exchange's
        reduce fetch when stages are consecutive) runs on a producer
        thread bounded by the fetch in-flight byte window, so this
        exchange's host framing/serialize overlaps the previous stage's
        reduce instead of draining the pipeline at every hand-off
        (shuffle/pipeline.py; counter-proven by stage_drain_ns)."""
        import jax as _jax

        from spark_rapids_tpu.shuffle.pipeline import pipelined
        from spark_rapids_tpu.shuffle.serializer import range_supported
        from spark_rapids_tpu.shuffle.stats import SHUFFLE_COUNTERS
        from spark_rapids_tpu.shuffle.transport import (
            CacheOnlyTransport, fetch_window_bytes, make_transport)

        with self._lock:
            if self._transport is None:
                def nbytes(item) -> int:
                    return sum(getattr(x, "nbytes", 0)
                               for x in _jax.tree_util.tree_leaves(item))

                SHUFFLE_COUNTERS.add(exchange_stages=1)
                t = make_transport(self.mode, self.out_partitions,
                                   self.schema, self.writer_threads,
                                   self.codec)
                if isinstance(t, CacheOnlyTransport):
                    # device twin of the wire range path: one spillable
                    # backing per map batch, per-partition range views —
                    # zero slice/gather programs on the map side
                    t.write_partitioned(
                        _spanned_writes(self._range_views()))
                elif t.supports_range_write and range_supported(self.schema):
                    t.write_batches(_spanned_writes(pipelined(
                        self._range_stream(), nbytes, fetch_window_bytes(),
                        name="exchange-map-range")))
                else:
                    t.write(_spanned_writes(pipelined(
                        self._slices(), nbytes, fetch_window_bytes(),
                        name="exchange-map-slices")))
                self._transport = t
            return self._transport

    # -- reduce side --------------------------------------------------------

    @property
    def coalesce_target_rows(self) -> int:
        return self.target_rows

    def stream_pieces(self, idx: int):
        """Raw reduce pieces: StreamPiece items (shuffle/transport.py)
        with NO merge/concat.  THE read of a consumer that can take a
        reduce group as one program's work — a fused segment
        (plan/fused.py), the final aggregate, a shuffled join — which
        folds them INSIDE its own program
        (transport.fold_pieces_in_trace), pin-balanced via
        coalesce.retry_over_stream_pieces.  execute_partition() remains
        the merged read for consumers that need batches."""
        transport = self._materialize()
        it = iter(transport.read_pieces(idx, target_rows=self.target_rows))
        while True:
            with timed(self.op_time, "exchange.read"):
                try:
                    piece = next(it)
                except StopIteration:
                    return
            yield piece

    def execute_partition(self, idx: int) -> Iterator[ColumnarBatch]:
        """Reduce side, the MERGED read: every piece a batch of its own (a
        CACHE_ONLY range view sliced by a launch of its own), coalesced up
        to the batch target.  Who still reads it: consumers that need
        batches (sort, window, a broadcast build, the per-op aggregate of
        an unfused plan's partial side), every consumer of a wire
        transport's already merged batches, and a reduce partition that
        outgrew ``reduce_group_in_core`` (the join's streamed probe and
        sub-partitioned out-of-core path, the aggregate's out-of-core
        merge).  A reduce group that is one program's work is read through
        stream_pieces() instead.

        Coalesces fetched slices up to the batch target and
        streams them (GpuShuffleCoalesceExec.scala:72's target-size goal) —
        an oversized reduce partition arrives as several batches so the
        downstream operator's out-of-core path can engage instead of one
        unbounded concat.  Consumption is STREAMING (transport.read_iter):
        with the flow-controlled TCP plane at most fetch-window + merge-
        chunk + one coalesce group of memory is resident, never the whole
        partition (VERDICT r4 #7).  The transport receives this exec's
        coalesce target so its merge flushes land ON the target — the
        common case then yields single-batch groups below and the extra
        concat_batches_jit pass never runs (concat-once)."""
        transport = self._materialize()

        def batches():
            with timed(self.op_time, "exchange.read"):
                it = iter(transport.read_iter(
                    idx, target_rows=self.target_rows))
            while True:
                with timed(self.op_time, "exchange.read"):
                    try:
                        b = next(it)
                    except StopIteration:
                        return
                yield b

        group: List[ColumnarBatch] = []
        acc = 0
        for b in _chain_none(batches()):
            if b is not None and (not group or acc + b.capacity <= self.target_rows):
                group.append(b)
                acc += b.capacity
                continue
            if not group:          # empty partition: nothing to flush
                continue
            with timed(self.op_time, "exchange.read"):
                if len(group) == 1:
                    out = group[0]
                else:
                    from spark_rapids_tpu.plan.execs.coalesce import (
                        concat_batches_jit)
                    from spark_rapids_tpu.shuffle.stats import (
                        SHUFFLE_COUNTERS)
                    SHUFFLE_COUNTERS.add(reduce_concats=1)
                    cap = round_up_pow2(max(acc, 1))
                    out = with_retry_no_split(
                        lambda: concat_batches_jit(group, cap))
            self.output_rows.add(out.num_rows)
            yield self._count_out(out)
            if b is not None:
                group = [b]
                acc = b.capacity

    def cleanup(self) -> None:
        with self._lock:
            if self._transport is not None:
                self._transport.cleanup()
                self._transport = None
                self._epoch += 1
        super().cleanup()

    def describe(self):
        keys = ", ".join(map(repr, self.keys))
        return f"TpuShuffleExchange[{self.out_partitions}, keys=[{keys}]]"


def _spanned_writes(items):
    """``items`` as handed to a transport's write: what the transport does
    with each one is an ``exchange.write`` span.  The span is opened when
    the transport has the item and closed when it asks for the next, so
    producing the items (the child's compute) is outside it."""
    for item in items:
        with trace_range("exchange.write"):
            yield item


def _estimated_row_bytes(schema: Schema) -> int:
    """Static per-row byte estimate for the byte-based coalesce goal:
    fixed-width columns contribute their itemsize, variable-width ones a
    flat 32-byte estimate (offset word + typical short payload), plus one
    validity byte each.  An estimate is enough — the goal only has to
    stop a WIDE schema from merging to target_rows-sized monsters."""
    total = 0
    for dt in schema.dtypes:
        if dt.variable_width or dt.np_dtype is None:
            total += 32
        else:
            total += int(np.dtype(dt.np_dtype).itemsize)
        total += 1
    return total


def reduce_group_in_core(rows: int, bound: int) -> bool:
    """THE size rule of the reduce side, read by both of its ends: a group
    of reduce partitions is one program's work while the ROWS its pieces
    hold do not pass ``bound`` (the batch capacity the consumer was planned
    with).  ``SharedCoalesceSpec.groups`` closes a group before the next
    partition would break it; ``TpuHashAggregateExec._execute_final_fused``
    takes a group that keeps it as one program and sends any other, which
    the reader can only have built from a single oversized partition, to
    the out-of-core merge.

    Rows and not the pieces' capacities: the map side's statistics are row
    counts (summed over map batches, over ranks in a cluster), a range
    view's count is exact on the host, and the combine's concat compacts
    live rows, so its capacity has to cover the rows alone.  A view's
    capacity is its count rounded up to a power of two, piece by piece:
    the same partition would read as up to twice its size, and as another
    size from one data set to the next."""
    return rows <= bound


class SharedCoalesceSpec:
    """ONE contiguous-partition grouping computed from the COMBINED
    materialized sizes of every exchange feeding a consumer.

    Spark AQE's CoalesceShufflePartitions contract (reference:
    GpuCustomShuffleReaderExec.scala:82 reading CoalescedPartitionSpec):
    co-partitioned join sides must merge with the same spec, or partition
    i on the left no longer holds the same key space as partition i on
    the right.  Greedy merge of adjacent partitions for as long as the
    combined row count stays in core (``reduce_group_in_core``): a group
    is closed BEFORE the partition that would take it past the target, so
    only a single partition can be a group that is larger."""

    def __init__(self, target_rows: int, target_bytes: int = 0):
        self.target_rows = max(int(target_rows), 1)
        # byte-based coalesce goal (spark.rapids.sql.batchSizeBytes, the
        # reference's TargetSize): converted to a row cap from the
        # estimated schema row width once exchanges register, so a wide
        # schema stops merging before target_rows would
        self.target_bytes = max(int(target_bytes), 0)
        self.exchanges: List[TpuShuffleExchangeExec] = []
        self._groups: Optional[List[List[int]]] = None
        self._epoch_key: Optional[tuple] = None
        self._lock = MaterializeLock()

    def register(self, ex: "TpuShuffleExchangeExec") -> None:
        ex._want_part_stats = True    # before any materialization (plan
        self.exchanges.append(ex)     # post-pass runs pre-execution)

    def groups(self) -> List[List[int]]:
        # materialize OUTSIDE the spec lock: each exchange's own lock
        # makes this idempotent, and concurrent readers (serving-layer
        # submissions, engine partition tasks) must not serialize behind
        # one reader holding the spec lock across the whole map side
        for ex in self.exchanges:
            ex._materialize()
        # groups are memoized PER EXCHANGE EPOCH: a re-executed plan
        # (cleanup bumped the epochs) re-plans from the fresh map
        # statistics instead of serving the previous run's grouping
        key = tuple(ex._epoch for ex in self.exchanges)
        with self._lock:
            if self._groups is not None and self._epoch_key == key:
                return self._groups
            counts = None
            for ex in self.exchanges:
                c = ex.partition_row_counts()
                counts = c if counts is None else \
                    [a + b for a, b in zip(counts, c)]
            assert counts is not None, "spec with no registered exchange"
            from spark_rapids_tpu.cluster.stats import cluster_stats
            client = cluster_stats()
            if client is not None:
                # distributed AQE (VERDICT r4 #8): local map-output counts
                # are this rank's share; group boundaries must come from
                # the GLOBAL per-partition sums or co-partitioned join
                # sides would merge differently across ranks.  The key is
                # derived from the exchanges' deterministic shuffle ids,
                # so every rank names this spec identically without any
                # call-order assumption.
                sids = sorted(ex._transport.shuffle_id
                              for ex in self.exchanges)
                stats_key = "aqe:" + "-".join(map(str, sids))
                client.publish(stats_key, counts)
                counts = client.fetch_global(stats_key)
            target = self.target_rows
            if self.target_bytes:
                row_bytes = max(_estimated_row_bytes(
                    self.exchanges[0].schema), 1)
                target = min(target,
                             max(self.target_bytes // row_bytes, 1))
            groups: List[List[int]] = []
            cur: List[int] = []
            acc = 0
            for p, n in enumerate(counts):
                if cur and not reduce_group_in_core(acc + n, target):
                    groups.append(cur)
                    cur, acc = [], 0
                cur.append(p)
                acc += n
            if cur:
                groups.append(cur)
            if not groups:
                groups = [[p] for p in range(len(counts))]
            self._groups = groups
            self._epoch_key = key
            return groups


class TpuCoalescedShuffleReaderExec(TpuExec):
    """Reduce-side adaptive reader: presents the exchange's partitions
    re-grouped by a SharedCoalesceSpec, so many undersized reduce tasks
    become few full ones (reference: GpuCustomShuffleReaderExec.scala:26).
    num_partitions() materializes the map side — exactly the AQE staging
    point where runtime statistics become available."""

    def __init__(self, exchange: TpuShuffleExchangeExec,
                 spec: SharedCoalesceSpec):
        super().__init__((exchange,), exchange.schema)
        self.spec = spec
        spec.register(exchange)

    def num_partitions(self) -> int:
        return len(self.spec.groups())

    @property
    def coalesce_target_rows(self) -> int:
        return self.children[0].coalesce_target_rows

    def stream_pieces(self, idx: int):
        """Raw pieces of every member partition of coalesced group
        ``idx`` (fused-across-shuffle path; see the exchange's
        stream_pieces)."""
        for p in self.spec.groups()[idx]:
            yield from self.children[0].stream_pieces(p)

    def execute_partition(self, idx: int) -> Iterator[ColumnarBatch]:
        for p in self.spec.groups()[idx]:
            for batch in self.children[0].execute_partition(p):
                self.output_rows.add(batch.num_rows)
                yield self._count_out(batch)

    def describe(self):
        n = len(self.spec._groups) if self.spec._groups else "?"
        return (f"TpuCoalescedShuffleReader[{n} of "
                f"{self.children[0].num_partitions()} partitions]")


class TpuSinglePartitionExec(TpuExec):
    """Gather all child partitions into one (SinglePartition exchange)."""

    def __init__(self, child: TpuExec):
        super().__init__((child,), child.schema)

    def num_partitions(self) -> int:
        return 1

    def execute_partition(self, idx: int) -> Iterator[ColumnarBatch]:
        child = self.children[0]
        for p in range(child.num_partitions()):
            for batch in child.execute_partition(p):
                self.output_rows.add(batch.num_rows)
                yield self._count_out(batch)

    def describe(self):
        return "TpuSinglePartition"
