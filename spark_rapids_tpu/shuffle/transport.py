"""Shuffle transport SPI: the pluggable data plane behind the exchange exec.

Reference seam: `RapidsShuffleTransport` (sql-plugin/.../shuffle/
RapidsShuffleTransport.scala:303, makeClient/makeServer) — the interface the
UCX plugin implements so the shuffle manager can swap data planes without
touching exec code (mode switch RapidsShuffleInternalManagerBase.scala:1714,
1751).  The TPU analogs:

  * CacheOnlyTransport  — device-resident spillable handles in an in-process
    catalog (RapidsCachingWriter:1618 shape); the fast path when map and
    reduce tasks share a process/device.
  * KudoWireTransport   — host-staged tpu-kudo wire bytes with a writer
    thread pool and optional codec (MULTITHREADED mode,
    RapidsShuffleThreadedWriterBase:298); the mode that generalizes to
    multi-host block servers.
  * IciTransport        — gang-scheduled `lax.all_to_all` over the mesh
    (parallel/ici.py).  Unlike the store-and-forward transports it moves
    all shards in ONE collective step; the SPMD stage compiler
    (parallel/stage.py) goes further and inlines that collective into the
    whole-query XLA program, so this class is the standalone/elastic-mode
    form of the same data plane.

`TpuShuffleExchangeExec` consumes only this interface; adding a transport
(e.g. a DCN/multi-host fetcher) never touches exec code — the property the
reference's SPI exists to provide.
"""
from __future__ import annotations

import abc
import threading
from typing import Iterable, List, Optional, Sequence, Tuple

import jax

from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema


@jax.tree_util.register_pytree_node_class
class RangeView:
    """A row range [start, start+count) of a BACKING batch, deliverable
    into a traced program WITHOUT a standalone gather.

    The device twin of the wire path's row-range framing (PR 5,
    serializer.serialize_batch_ranges): the CACHE_ONLY map side stores ONE
    partition-reordered batch per map batch, and each reduce partition's
    "block" is a view over it.  A fused consumer receives the view as a
    program ARGUMENT — ``batch`` + dynamic ``start``/``count`` scalars with
    the pow2 row ``capacity`` static in the treedef aux — and slices it
    in-trace (``slice_in_trace``), so the per-partition gather launches of
    the old ``slice_by_counts`` path fold into the consumer's one program.

    Host-side accessors (columns/num_rows/schema) delegate to the backing
    batch: bucket derivations over a view (string byte maxima) are then
    computed over the backing's live rows — a superset of the view's, so
    the derived bucket is always sufficient."""

    __slots__ = ("batch", "start", "count", "capacity")

    def __init__(self, batch: ColumnarBatch, start, count, capacity: int):
        self.batch = batch      # backing batch (dynamic pytree)
        self.start = start      # dynamic scalar: first backing row
        self.count = count      # dynamic scalar: live rows in the view
        self.capacity = int(capacity)   # static pow2 row capacity

    def tree_flatten(self):
        return (self.batch, self.start, self.count), self.capacity

    @classmethod
    def tree_unflatten(cls, capacity, children):
        batch, start, count = children
        return cls(batch, start, count, capacity)

    # host-side accessors (backing superset; see class doc)
    @property
    def columns(self):
        return self.batch.columns

    @property
    def num_rows(self):
        return self.batch.num_rows

    @property
    def schema(self):
        return self.batch.schema

    def slice_in_trace(self) -> ColumnarBatch:
        """Gather the view's rows INSIDE the current trace (the fold that
        replaces the map side's standalone piece-gather program)."""
        import jax.numpy as jnp

        from spark_rapids_tpu.kernels.selection import gather_batch
        idx = jnp.arange(self.capacity, dtype=jnp.int32) + \
            jnp.asarray(self.start, jnp.int32)
        return gather_batch(self.batch, idx,
                            jnp.asarray(self.count, jnp.int32),
                            out_capacity=self.capacity)


def views_at_one_capacity(mats: list) -> list:
    """``mats`` (what ``materialize_pinned`` returned for the pieces of ONE
    program call) with every RangeView at the largest capacity among them.

    A view's capacity is static in the program: a program that kept each
    view's own power of two would be another program for every multiset of
    partition sizes, compiled anew for every data set whose counts sit
    near a power of two (16 partitions of 262,594 groups hold 16,412 rows
    each, 28 over one).  The concat pads every input to the largest
    capacity anyway (``concat_batches_device``), so the larger slices add
    gathered rows and no buffer."""
    cap = max((m.capacity for m in mats if isinstance(m, RangeView)),
              default=0)
    return [RangeView(m.batch, m.start, m.count, cap)
            if isinstance(m, RangeView) and m.capacity != cap else m
            for m in mats]


def fold_pieces_in_trace(pieces, out_capacity: Optional[int] = None
                         ) -> ColumnarBatch:
    """One reduce group's pieces as ONE batch, inside the traced program
    that consumes them: the reduce-side merge as the first step of the
    consumer's own program (the final aggregate's combine, a fused
    segment's stream group and co-partition build, a join's probe), so no
    view costs a launch and no side a concat launch.  A piece is a batch or
    a RangeView of a shared CACHE_ONLY backing batch; views slice in-trace
    first (the map-side piece gather folded in as well).

    ``out_capacity`` (static): the rows the caller knows the pieces to
    hold, rounded up (the concat compacts live rows, so it has to cover
    the rows alone); without it the sum of the pieces' capacities, which
    bounds them.  Either way the concat cannot overflow and needs no
    feedback."""
    from spark_rapids_tpu.columnar.column import round_up_pow2
    from spark_rapids_tpu.kernels.selection import concat_batches_device
    batches = tuple(p.slice_in_trace() if isinstance(p, RangeView) else p
                    for p in pieces)
    if len(batches) == 1:
        return batches[0]
    cap = out_capacity or round_up_pow2(
        max(sum(b.capacity for b in batches), 1))
    # tpu-lint: allow-retry-discipline(traced body of the consumer's program; every call site dispatches it under with_retry_no_split or retry_over_stream_pieces)
    out, _ = concat_batches_device(list(batches), cap)
    return out


class StreamPiece:
    """One reduce-partition shuffle piece deliverable WITHOUT merging.

    The fused-across-shuffle reduce path (plan/fused.py) concats pieces
    INSIDE its one program per coalesced partition, so the transport's own
    merge/concat pass never runs.  A piece is an already-device batch
    (wire transports pay their host->device upload in read_iter
    regardless) or a RANGE VIEW of a shared spillable backing batch (the
    CACHE_ONLY store — the backing stays spillable between uses;
    consumers materialize pin-balanced via
    coalesce.retry_over_stream_pieces): materialize_pinned then returns a
    RangeView the consumer's program slices in-trace, and pin balancing
    dedupes by ``backing_key`` so a backing batch shared by several views
    pins exactly once per attempt."""

    __slots__ = ("capacity", "rows", "nbytes", "_handle", "_batch",
                 "_range")

    def __init__(self, capacity: int, nbytes: int, handle=None, batch=None,
                 range_: Optional[Tuple[int, int]] = None):
        # a device batch, or a backing handle with the view's row range
        assert (batch is None) == (handle is not None
                                   and range_ is not None)
        self.capacity = int(capacity)   # static row capacity (grouping)
        #: rows the piece holds, as the host knows them without a sync: a
        #: view's count, which is exact (the map side's statistics are
        #: sums of these); an uploaded batch's capacity, the bound on its
        #: device-resident row count.  What a reduce group is sized by
        #: (plan/execs/exchange.py reduce_group_in_core)
        self.rows = int(range_[1]) if range_ is not None else int(capacity)
        self.nbytes = int(nbytes)       # in-flight byte accounting
        self._handle = handle
        self._batch = batch
        self._range = range_            # (start_row, row_count) or None

    @classmethod
    def of_batch(cls, batch: ColumnarBatch) -> "StreamPiece":
        return cls(batch.capacity, batch.device_size_bytes(), batch=batch)

    @classmethod
    def of_range_view(cls, handle, start: int, count: int,
                      nbytes: int) -> "StreamPiece":
        from spark_rapids_tpu.columnar.column import round_up_pow2
        return cls(round_up_pow2(max(int(count), 1)), nbytes,
                   handle=handle, range_=(int(start), int(count)))

    @property
    def is_range_view(self) -> bool:
        return self._range is not None

    def backing_key(self):
        """Identity of the shared backing handle (pin-dedup key), or None
        when this piece owns its materialization alone."""
        return id(self._handle) if self._range is not None else None

    def resident_nbytes(self, seen: set) -> int:
        """Bytes this piece ADDS to an attempt's pinned device residency.

        A range view pins its FULL backing batch — once per backing,
        however many views share it — so a group's true pinned residency
        is the deduped sum of backing sizes, not the per-view byte
        shares.  ``seen`` carries backing keys across a group; non-view
        pieces contribute their own nbytes."""
        bk = self.backing_key()
        if bk is None:
            return self.nbytes
        if bk in seen:
            return 0
        seen.add(bk)
        return self._handle.size_bytes

    def materialize_pinned(self):
        """Device data for this piece; a view's backing handle gains a
        pin the caller MUST return via unpin() before its retry attempt
        ends.  Range-view pieces return a RangeView (slice folds into the
        consumer's program); others return the device batch."""
        if self._handle is None:
            return self._batch
        batch = self._handle.materialize()
        try:
            return self.as_view(batch)
        except BaseException:
            # the caller only owns the pin once the view is RETURNED: a
            # raise in view construction must give the materialize pin
            # back or the backing stays unspillable with no owner to
            # unpin it
            self._handle.unpin()
            raise

    def as_view(self, backing: ColumnarBatch):
        """The same value materialize_pinned would return, built from an
        ALREADY-materialized backing batch — no extra pin (the shared-
        backing dedup path of retry_over_stream_pieces)."""
        import jax.numpy as jnp
        import numpy as np
        start, count = self._range
        # commit the dynamic scalars explicitly HERE: np scalar leaves
        # would be committed implicitly at every jit dispatch that takes
        # the view as an argument (the sanitizer's transfer guard flags
        # exactly that in hot sections)
        return RangeView(backing,
                         jnp.asarray(np.asarray(start, np.int32)),
                         jnp.asarray(np.asarray(count, np.int32)),
                         self.capacity)

    @staticmethod
    def backing_of(mat):
        """The backing batch inside a materialize_pinned result."""
        return mat.batch if isinstance(mat, RangeView) else mat

    def materialize_batch_pinned(self) -> ColumnarBatch:
        """Device BATCH for this piece — the materialize fallback for
        consumers that cannot fold a RangeView into their own program
        (the fused OOC fallback, per-op reads): a view runs its slice as
        a standalone gather here (counted: range_view_materializes).  The
        backing pin is retained until unpin() like every other piece; the
        gather itself retries under with_retry_no_split (idempotent over
        the pinned backing — a mid-gather OOM spills OTHER handles)."""
        mat = self.materialize_pinned()
        if isinstance(mat, RangeView):
            try:
                from spark_rapids_tpu.memory.retry import (
                    with_retry_no_split)
                from spark_rapids_tpu.shuffle.stats import SHUFFLE_COUNTERS
                SHUFFLE_COUNTERS.add(range_view_materializes=1)
                return with_retry_no_split(lambda: _slice_view(mat))
            except BaseException:
                # the caller only learns it holds a pin when this call
                # RETURNS (its unwind lists pieces appended after
                # success) — ANY raise past the acquire (the failed
                # fallback gather, even the import/counter) must release
                # its own pin or the backing stays unspillable until
                # cleanup
                self.unpin()
                raise
        return mat

    def unpin(self) -> None:
        if self._handle is not None:
            self._handle.unpin()


def views_over_memory_budget(piece_lists) -> bool:
    """True when materializing ``piece_lists`` in ONE attempt would pin
    backing batches past HALF the device arena's byte budget.

    The range-view residency guard: an attempt pins each view's FULL
    backing (deduped across shared backings) and pinned handles cannot
    spill, so a group approaching the budget must take the materialize
    fallback (slices release their backing pin) instead of the in-trace
    fold — summing per-view shares would undercount by ~num_partitions x
    and bypass the fallback exactly when memory is tightest.  Budget 0
    (bookkeeping mode — no HBM stats) never trips: residency is then not
    the binding constraint and the fold stays on."""
    from spark_rapids_tpu.memory.arena import device_arena
    budget = device_arena().budget_bytes
    if not budget:
        return False
    seen: set = set()
    total = 0
    for lst in piece_lists:
        for p in lst:
            total += (p.resident_nbytes(seen)
                      if hasattr(p, "resident_nbytes") else p.nbytes)
    return total > budget // 2


def materialize_view_batch(piece: StreamPiece) -> ColumnarBatch:
    """Pin-balanced standalone slice of a piece into an INDEPENDENT
    batch: the materialize fallback (counted range_view_materializes for
    views).  The backing pin is taken and returned inside each retry
    attempt, so a mid-attempt OOM can spill the backing itself."""
    from spark_rapids_tpu.memory.retry import with_retry_no_split
    if piece.is_range_view:
        from spark_rapids_tpu.shuffle.stats import SHUFFLE_COUNTERS
        SHUFFLE_COUNTERS.add(range_view_materializes=1)

    def attempt():
        # unpin only covers a SUCCESSFUL materialize: a raise inside
        # materialize_pinned means no pin was taken, and an unmatched
        # unpin would steal a concurrent consumer's pin
        mat = piece.materialize_pinned()
        try:
            return (_slice_view(mat) if isinstance(mat, RangeView)
                    else mat)
        finally:
            piece.unpin()
    return with_retry_no_split(attempt)


def _slice_view(view: RangeView) -> ColumnarBatch:
    """Standalone (jitted) gather of a RangeView — the materialize
    fallback only; the fused path slices in-trace instead."""
    from spark_rapids_tpu.plan.execs.base import schema_cache_key, shared_jit
    bcaps = ",".join(str(c.byte_capacity) for c in view.batch.columns
                     if c.offsets is not None)
    key = (f"rvslice|{schema_cache_key(view.batch.schema)}|"
           f"{view.batch.capacity}|{bcaps}|{view.capacity}")
    return shared_jit(key, lambda: _rv_slice_step,
                      kind="range_view_slice")(view)


def _rv_slice_step(view: RangeView) -> ColumnarBatch:
    return view.slice_in_trace()


class ShuffleTransport(abc.ABC):
    """Store-and-forward data plane: map side writes (partition, batch)
    pieces; reduce side reads every piece for one partition."""

    #: True when the transport implements write_batches — the range-
    #: serialization write path (one download per map batch, partition
    #: blocks framed from host row ranges).  CacheOnlyTransport stays
    #: False: its backings must remain device-resident and spillable, so
    #: it is written by write_partitioned instead.
    supports_range_write = False

    @abc.abstractmethod
    def write(self, pieces: Iterable[Tuple[int, ColumnarBatch]]) -> None:
        """Consume the map side's partition slices (called once)."""

    def write_batches(self, batches) -> None:
        """Range-serialization write path (called once, instead of
        write()): consume (partition-ordered host batch, host
        per-partition counts) pairs — the exchange hands each map batch
        over WITHOUT slicing and the transport frames every partition's
        wire block from row ranges (serializer.serialize_batch_ranges).
        Only called when ``supports_range_write``."""
        raise NotImplementedError(type(self).__name__)

    def read_iter(self, partition: int, target_rows: Optional[int] = None):
        """Streaming read: yield a partition's batches incrementally so
        the consumer's coalesce window — not the whole partition — bounds
        resident memory.  ``target_rows`` is the consumer's coalesce
        target: a transport that merges wire blocks aligns its flush
        boundaries to it so the consumer never re-concats (concat-once).
        Default delegates to read(); flow-controlled transports override
        with true incremental merge."""
        yield from self.read(partition)

    def read_pieces(self, partition: int,
                    target_rows: Optional[int] = None):
        """Unmerged piece stream for the fused reduce path: StreamPiece
        items the consumer concats INSIDE its own program.  Default wraps
        read_iter's (already merged/uploaded) batches; CACHE_ONLY
        overrides with range views of its spillable backings so nothing
        merges or pins ahead of the consumer's pin-balanced attempt."""
        for b in self.read_iter(partition, target_rows=target_rows):
            yield StreamPiece.of_batch(b)

    @abc.abstractmethod
    def read(self, partition: int) -> List[ColumnarBatch]:
        """All pieces routed to `partition`, as device batches."""

    @abc.abstractmethod
    def cleanup(self) -> None:
        """Drop shuffle state (query-end, ShuffleCleanupManager analog)."""


class CacheOnlyTransport(ShuffleTransport):
    """Device-resident spillable range views (CACHE_ONLY mode).

    Written by ``write_partitioned`` only: ONE spillable handle per map
    batch (the partition-reordered batch, exactly what the device
    partition step already produced) plus host counts; each partition's
    block is a (backing, start, count) view.  No gather programs run on
    the map side at all — fused consumers slice the view inside their own
    program (StreamPiece/RangeView), and non-fused consumers get a
    standalone slice at read time (the materialize fallback, counted
    range_view_materializes).  ``write`` raises: per-partition device
    pieces are the wire transports' shape, and a second store for them
    would have no writer.

    A backing handle is shared by every partition's view over its map
    batch (partial handle reuse across partitions): the store owns it
    exactly once (``_backings``) and cleanup closes it exactly once, no
    matter how many views were consumed, pinned, or never read."""

    def __init__(self, num_partitions: int):
        #: per partition: (backing handle, start row, row count, nbytes)
        self._views: List[List] = [[] for _ in range(num_partitions)]
        #: backing handles owned by the view store, one per map batch
        self._backings: List = []

    def write(self, pieces):
        raise NotImplementedError(
            "CacheOnlyTransport stores range views: use write_partitioned")

    def write_partitioned(self, batches) -> None:
        """Range-view write path (instead of write()): consume
        (partition-reordered batch, host per-partition counts) pairs —
        the exchange's device partition output WITHOUT slicing."""
        from spark_rapids_tpu.memory.spill import make_spillable
        from spark_rapids_tpu.shuffle.stats import SHUFFLE_COUNTERS
        n_parts = len(self._views)
        for reordered, host_counts in batches:
            total = int(host_counts.sum())
            if total == 0:
                # no live rows: store nothing (a backing handle nobody
                # views would hold dead spillable residency until
                # cleanup)
                continue
            h = make_spillable(reordered)
            self._backings.append(h)
            start = 0
            nblocks = 0
            for p in range(n_parts):
                cnt = int(host_counts[p])
                if cnt:
                    nbytes = max(h.size_bytes * cnt // total, 1)
                    self._views[p].append((h, start, cnt, nbytes))
                    nblocks += 1
                start += cnt
            SHUFFLE_COUNTERS.add(range_view_blocks=nblocks)

    def read(self, partition: int) -> List[ColumnarBatch]:
        # each view is sliced into an INDEPENDENT batch, its backing pin
        # taken and returned inside the slice's own retry attempt
        return [materialize_view_batch(p)
                for p in self.read_pieces(partition)]

    def read_pieces(self, partition: int,
                    target_rows: Optional[int] = None):
        for h, start, cnt, nbytes in self._views[partition]:
            yield StreamPiece.of_range_view(h, start, cnt, nbytes)

    def cleanup(self) -> None:
        for h in self._backings:
            h.close()
        self._backings.clear()
        for views in self._views:
            views.clear()


class KudoWireTransport(ShuffleTransport):
    """Host-staged kudo wire bytes, threaded serialize (MULTITHREADED)."""

    supports_range_write = True

    def __init__(self, num_partitions: int, schema: Schema,
                 writer_threads: int = 4, codec: str = "none"):
        self._buckets: List[List[bytes]] = [[] for _ in range(num_partitions)]
        self.schema = schema
        self.writer_threads = writer_threads
        self.codec = codec

    def write(self, pieces):
        from concurrent.futures import ThreadPoolExecutor
        from spark_rapids_tpu.shuffle.serializer import serialize_batch
        from spark_rapids_tpu.utils.ambient import (Ambients,
                                                    submit_with_ambients)
        from spark_rapids_tpu.utils.cancel import cancellable_wait
        # writer threads serialize for the map task: same tenant/
        # priority/token (a cancelled query's framing stops at the next
        # blessed wait); captured once for the whole batch of submits
        amb = Ambients.capture()
        with ThreadPoolExecutor(max_workers=self.writer_threads) as pool:
            futures = [(p, submit_with_ambients(pool, serialize_batch,
                                                piece, self.codec,
                                                ambients=amb))
                       for p, piece in pieces]
            for p, fut in futures:
                self._buckets[p].append(cancellable_wait(
                    fut, site="shuffle.serialize.drain"))

    def write_batches(self, batches):
        """Range write: each map batch arrives host-resident with its
        partition counts (ONE download upstream); framing is pure host
        work and parallelizes across batches on the writer pool.  In-
        flight submissions are bounded to ~2x the pool so a large map
        side holds O(writer_threads) uncompressed host batches, not all
        of them, while the framed blocks still land in batch order."""
        from collections import deque
        from concurrent.futures import ThreadPoolExecutor
        from spark_rapids_tpu.shuffle.serializer import serialize_batch_ranges
        from spark_rapids_tpu.utils.ambient import (Ambients,
                                                    submit_with_ambients)
        from spark_rapids_tpu.utils.cancel import cancellable_wait

        def drain(fut):
            blocks = cancellable_wait(fut, site="shuffle.serialize.drain")
            for p, block in enumerate(blocks):
                if block is not None:
                    self._buckets[p].append(block)

        amb = Ambients.capture()
        pending = deque()
        with ThreadPoolExecutor(max_workers=self.writer_threads) as pool:
            for hb, counts in batches:
                pending.append(submit_with_ambients(
                    pool, serialize_batch_ranges, hb, counts, self.codec,
                    ambients=amb))
                if len(pending) >= 2 * self.writer_threads:
                    drain(pending.popleft())
            while pending:
                drain(pending.popleft())

    def read_iter(self, partition: int, target_rows: Optional[int] = None):
        """Streaming read: merge wire blocks in chunks aligned to the
        consumer's coalesce target (wire_row_count reads rows without
        decompressing), so an oversized reduce partition streams like
        the TCP plane instead of materializing in ONE merge.  A codec
        that hides the header falls back to the whole-partition merge."""
        from spark_rapids_tpu.memory.retry import with_retry_no_split
        from spark_rapids_tpu.shuffle.serializer import (
            merge_batches, wire_row_count)
        buffers = self._buckets[partition]
        if not buffers:
            return
        if not target_rows:
            yield from self.read(partition)
            return
        chunk: List[bytes] = []
        rows = 0
        for raw in buffers:
            rc = wire_row_count(raw)
            if rc is None:
                yield from self.read(partition)
                return
            chunk.append(raw)
            rows += rc
            if rows >= target_rows:
                # under retry: inputs are host wire bytes (idempotent),
                # the merge is this chunk's one HBM materialization
                out = with_retry_no_split(
                    lambda c=chunk: merge_batches(c, self.schema))
                chunk, rows = [], 0
                if out is not None:
                    yield out
        if chunk:
            out = with_retry_no_split(
                lambda: merge_batches(chunk, self.schema))
            if out is not None:
                yield out

    def read(self, partition: int) -> List[ColumnarBatch]:
        from spark_rapids_tpu.memory.retry import with_retry_no_split
        from spark_rapids_tpu.shuffle.serializer import merge_batches
        buffers = self._buckets[partition]
        if not buffers:
            return []
        # under retry: inputs are host wire bytes (idempotent to re-merge),
        # and the merge is the read side's one big HBM materialization
        return [with_retry_no_split(
            lambda: merge_batches(buffers, self.schema))]

    def cleanup(self) -> None:
        for b in self._buckets:
            b.clear()


class IciTransport:
    """Collective data plane: one all-to-all moves every shard at once.

    Not a store-and-forward `ShuffleTransport` — the exchange is a single
    gang-scheduled step over per-device shards (UCX peer-to-peer replaced by
    the interconnect collective).  Offered standalone for elastic/multi-host
    composition; the SPMD compiler inlines the same kernel into whole-query
    programs instead."""

    def __init__(self, mesh, axis_name: Optional[str] = None):
        self.mesh = mesh
        self.axis_name = axis_name

    def exchange(self, shards: Sequence[ColumnarBatch],
                 key_idx: Sequence[int]) -> List[ColumnarBatch]:
        from spark_rapids_tpu.parallel.ici import ici_exchange
        return ici_exchange(self.mesh, shards, key_idx, self.axis_name)


_default_executor = None
_default_executor_lock = threading.Lock()


def process_shuffle_executor():
    """Lazy process-wide ShuffleExecutor node (MULTIPROCESS mode).  In a
    real multi-host deployment each worker constructs one with the
    driver's registry address; standalone it self-registers."""
    global _default_executor
    with _default_executor_lock:
        if _default_executor is None:
            from spark_rapids_tpu.shuffle.net import ShuffleExecutor
            # tpu-lint: allow-lock-order(canonical once-per-process init: double-checked executor construction; its persist-dir makedirs runs exactly once)
            _default_executor = ShuffleExecutor(serve_registry=True)
        return _default_executor


_cluster_participants = None
_cluster_shuffle_seq = None   # [query_id, next_exchange_ordinal]
_cluster_attempt = 0          # task attempt id (speculation/re-dispatch)
_cluster_logical = None       # logical participant id this task runs AS


def set_cluster_query(query_id, attempt: int = 0) -> None:
    """Enter (or leave, with None) a cluster task: exchanges then take
    DETERMINISTIC shuffle ids (query_id << 16 | ordinal-of-materialization)
    so every rank names the same exchange identically — a driver-counter
    allocation would hand each requesting rank a different id and reduce
    reads would wait on a shuffle nobody else knows (the role of Spark's
    driver-assigned shuffleId in the reference's heartbeat registry).

    ``attempt`` tags this task attempt's map-output blocks (speculative
    copies and rank re-dispatches run the SAME shuffle ids under a higher
    attempt; first-commit-wins at the registry decides which attempt's
    blocks serve, and the loser's are dropped by this tag)."""
    global _cluster_shuffle_seq, _cluster_attempt
    _cluster_shuffle_seq = [int(query_id), 0] if query_id is not None \
        else None
    _cluster_attempt = int(attempt)


def set_cluster_identity(logical_id) -> None:
    """The logical participant slot this task fills (defaults to the
    executor's own id).  A speculative attempt or a post-loss rank
    re-dispatch runs AS the original assignee: its map completions commit
    against that logical slot, so readers' completeness waits and server
    resolution see one consistent participant set whoever physically ran
    the work."""
    global _cluster_logical
    _cluster_logical = logical_id


def set_cluster_participants(participants) -> None:
    """Full worker set for the current cluster task: transports declare it
    so a reduce read waits for EVERY participant's map completion, even
    one that hasn't constructed its transport yet (the coordinator-known-
    membership case in TcpShuffleTransport's contract)."""
    global _cluster_participants
    _cluster_participants = list(participants) if participants else None


#: reduce-read completeness wait (seconds); cluster executors set it from
#: the broadcast conf (spark.rapids.shuffle.completenessTimeout).  The
#: wait itself runs as a named RetryBudget deadline (net.py
#: _await_and_resolve_peers), so a lost participant surfaces as a
#: RetryBudgetExhausted naming the shuffle and the pending executors —
#: never an anonymous fixed-timeout hang.
_completeness_timeout_s: float = 120.0


def set_completeness_timeout(seconds: float) -> None:
    global _completeness_timeout_s
    _completeness_timeout_s = float(seconds)


def fetch_window_bytes() -> int:
    return _fetch_window[0]


#: map-output durability (spark.rapids.shuffle.replication.* +
#: spark.rapids.cluster.drain.timeout): (replication factor k, persist
#: dir, drain timeout seconds).  k>1: after a map commit the blocks
#: replicate asynchronously to k-1 rendezvous-chosen peers and reduce
#: reads fail over to replicas on peer loss; persist dir is the
#: spill-backed fallback when k=1 (blocks also land on local disk and a
#: restarted executor re-serves them); the drain timeout bounds a
#: graceful leave's re-replication pass.
_replication = (1, "", 30.0)


def set_replication(factor: int, persist_dir: str = "",
                    drain_timeout_s: float = 30.0) -> None:
    global _replication
    _replication = (max(int(factor), 1), str(persist_dir or ""),
                    max(float(drain_timeout_s), 0.0))


def replication_config():
    return _replication


#: receive-side flow-control window (spark.rapids.shuffle.fetch.*):
#: (max in-flight bytes, fetch threads, streaming merge chunk bytes)
_fetch_window = (64 << 20, 4, 32 << 20)

#: byte budget per fetch_many round-trip (spark.rapids.shuffle.fetch
#: .requestBytes): how many blocks the prefetcher batches per request
_fetch_request_bytes = 4 << 20


def set_fetch_window(max_inflight_bytes: int, threads: int,
                     merge_chunk_bytes: int,
                     request_bytes: Optional[int] = None) -> None:
    global _fetch_window, _fetch_request_bytes
    _fetch_window = (int(max_inflight_bytes), int(threads),
                     int(merge_chunk_bytes))
    if request_bytes is not None:
        _fetch_request_bytes = int(request_bytes)


def set_process_shuffle_executor(executor) -> None:
    """Install the process-wide shuffle node (cluster executor bootstrap:
    the node registered with the DRIVER's registry must be the one the
    engine's exchanges write through — RapidsExecutorPlugin init analog,
    Plugin.scala:599)."""
    global _default_executor
    with _default_executor_lock:
        _default_executor = executor


def make_transport(mode: str, num_partitions: int, schema: Schema,
                   writer_threads: int = 4,
                   codec: str = "none") -> ShuffleTransport:
    if mode == "MULTITHREADED":
        return KudoWireTransport(num_partitions, schema, writer_threads, codec)
    if mode == "MULTIPROCESS":
        from spark_rapids_tpu.shuffle.serializer import wire_supported
        unsupported = [str(d) for d in schema.dtypes
                       if not wire_supported(d)]
        if unsupported:
            # never silently downgrade a cross-process transport: a remote
            # reduce task would read only its local slices and return
            # partial results (ADVICE r2 #1)
            raise NotImplementedError(
                "MULTIPROCESS shuffle cannot serialize column types "
                f"{unsupported} on the kudo wire")
        from spark_rapids_tpu.shuffle.net import TcpShuffleTransport
        sid = None
        if _cluster_shuffle_seq is not None:
            qid, ordinal = _cluster_shuffle_seq
            _cluster_shuffle_seq[1] += 1
            sid = (qid << 16) | ordinal
        mi, ft, mc = _fetch_window
        repl, persist, _drain = _replication
        return TcpShuffleTransport(process_shuffle_executor(),
                                   num_partitions, schema, codec,
                                   max_inflight_bytes=mi,
                                   fetch_threads=ft,
                                   merge_chunk_bytes=mc,
                                   shuffle_id=sid,
                                   completeness_timeout_s=(
                                       _completeness_timeout_s),
                                   participants=_cluster_participants,
                                   request_bytes=_fetch_request_bytes,
                                   attempt=_cluster_attempt,
                                   logical_id=_cluster_logical,
                                   replication=repl,
                                   persist_dir=persist)
    return CacheOnlyTransport(num_partitions)
