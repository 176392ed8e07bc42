"""Out-of-core substrate: process partitions larger than one capacity bucket.

The reference's "any input size on fixed memory" property (SURVEY §5.7)
comes from three operator-level mechanisms, each reproduced here in TPU
terms on top of the spill/retry substrate:

  * aggregate bucket-overflow repartition (GpuAggregateExec.scala:290):
    when the merge set is too big, hash-repartition it into sub-buckets
    with a DIFFERENT hash seed and merge each bucket independently;
  * sub-partitioned hash join (GpuSubPartitionHashJoin.scala): partition
    both sides on the join keys into co-buckets and join pairwise;
  * out-of-core sort (GpuSortExec.scala:137): the reference merge-sorts
    spillable sorted runs; the TPU-first equivalent is a range-bucketed
    distribution sort (sampled splitters, the same machinery as the range
    exchange) — buckets are statically shaped, spillable, and sorted one
    at a time, which maps onto XLA better than an N-way streaming merge.

Every helper here keeps at most O(bucket) rows on device at a time; queued
data lives in SpillableBatchHandles so the arena pressure callback can push
it to host/disk.
"""
from __future__ import annotations

from typing import Callable, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.columnar.batch import (ColumnarBatch, Schema,
                                              host_scalar)
from spark_rapids_tpu.columnar.column import round_up_pow2
from spark_rapids_tpu.kernels.partition import hash_partition
from spark_rapids_tpu.kernels.selection import gather_batch
from spark_rapids_tpu.memory.retry import with_retry_no_split
from spark_rapids_tpu.memory.spill import SpillableBatchHandle, make_spillable

# Sub-partitioning must NOT reuse the shuffle's routing seed (42): data on
# one shuffle partition all has hash%P equal, so a same-seed repartition
# would be degenerate.  The reference picks a new hash level per recursion;
# one alternate seed suffices here because sub-partitioning never recurses
# onto its own output with the same seed and bucket count.
SUB_PARTITION_SEED = 0x5F3759DF


def num_sub_buckets(total_rows: int, target_rows: int, cap: int = 256) -> int:
    """Power-of-two bucket count so each bucket lands near target_rows."""
    if target_rows <= 0:
        return 1
    need = (total_rows + target_rows - 1) // target_rows
    return min(round_up_pow2(max(need, 1)), cap)


def slice_by_counts(
    reordered: ColumnarBatch, counts: jax.Array, num_buckets: int,
    count_stat: bool = False,
) -> List[Optional[ColumnarBatch]]:
    """Slice a partition-ordered batch into per-bucket batches.

    One host sync of `num_buckets` scalars decides each slice's static
    capacity (pow2-bucketed so the gather kernels stay cached).  Empty
    buckets yield None.

    ``count_stat``: record the gather program dispatches in the
    slice_gather_programs shuffle counter — set by the exchange's
    device-slice map path (the CACHE_ONLY range-view store runs none:
    its views fold the slice into the consumer's program).
    OOC sub-partitioning keeps its own slicing uncounted: that path is
    not a map-side piece gather.
    """
    from spark_rapids_tpu.plan.execs.base import schema_cache_key, shared_jit

    def _stat(n: int) -> None:
        if count_stat and n:
            from spark_rapids_tpu.shuffle.stats import SHUFFLE_COUNTERS
            SHUFFLE_COUNTERS.add(slice_gather_programs=n)
    host_counts = np.asarray(counts)
    offsets = np.zeros(num_buckets + 1, np.int64)
    np.cumsum(host_counts, out=offsets[1:])
    bcaps = ",".join(str(c.byte_capacity) for c in reordered.columns
                     if c.offsets is not None)
    max_cnt = int(host_counts.max()) if num_buckets else 0
    if max_cnt == 0:
        return [None] * num_buckets
    ucap = round_up_pow2(max_cnt)
    if num_buckets > 1 and ucap * num_buckets <= 4 * reordered.capacity:
        # balanced pieces (the hash-partition common case): gather ALL
        # buckets at one uniform capacity in ONE program — the per-piece
        # loop costs one launch per bucket per batch (a host dispatch
        # each; per-launch cost on the chip is not measured).  Offsets
        # and counts enter as dynamic args so re-slicing never recompiles;
        # the 4x capacity guard routes skewed splits to the per-piece path.
        def slice_all(rb, offs, cnts):
            pieces = []
            for p in range(num_buckets):
                idx = jnp.arange(ucap, dtype=jnp.int32) + offs[p]
                pieces.append(gather_batch(rb, idx, cnts[p],
                                           out_capacity=ucap))
            return tuple(pieces)
        key = (f"oocsliceall|{schema_cache_key(reordered.schema)}|"
               f"{reordered.capacity}|{bcaps}|{ucap}|{num_buckets}")
        _stat(1)
        pieces = shared_jit(key, lambda: slice_all, kind="ooc_slice_all")(
            reordered,
            jnp.asarray(offsets[:num_buckets].astype(np.int32)),
            jnp.asarray(host_counts.astype(np.int32)))
        return [pieces[p] if int(host_counts[p]) else None
                for p in range(num_buckets)]
    _stat(int(np.count_nonzero(host_counts)))
    out: List[Optional[ColumnarBatch]] = []
    for p in range(num_buckets):
        cnt = int(host_counts[p])
        if cnt == 0:
            out.append(None)
            continue
        cap = round_up_pow2(cnt)

        def slice_piece(rb, off, n, _cap=cap):
            idx = jnp.arange(_cap, dtype=jnp.int32) + off
            return gather_batch(rb, idx, n, out_capacity=_cap)
        key = (f"oocslice|{schema_cache_key(reordered.schema)}|"
               f"{reordered.capacity}|{bcaps}|{cap}")
        out.append(shared_jit(key, lambda: slice_piece, kind="ooc_slice")(
            reordered, host_scalar(int(offsets[p])), host_scalar(cnt)))
    return out


def _partition_step(schema: Schema, key_idx: Tuple[int, ...],
                    num_buckets: int, string_bucket: int):
    def run(batch: ColumnarBatch):
        return hash_partition(
            batch, list(key_idx), num_buckets,
            string_max_bytes=string_bucket if string_bucket else 64,
            seed=SUB_PARTITION_SEED)
    return run


def sub_partition_spillable(
    batches: Iterator[ColumnarBatch],
    key_idx: Sequence[int],
    num_buckets: int,
    schema: Schema,
) -> List[List[SpillableBatchHandle]]:
    """Hash-repartition a stream of batches into spillable bucket queues.

    Processes one input batch at a time (device residency = one batch +
    its reordering); slices go straight into spillable handles so queued
    buckets can leave HBM under pressure.
    """
    from spark_rapids_tpu.kernels import strings as SK
    from spark_rapids_tpu.plan.execs.base import schema_cache_key, shared_jit

    key_idx = tuple(key_idx)
    buckets: List[List[SpillableBatchHandle]] = [[] for _ in range(num_buckets)]
    for batch in batches:
        has_string = any(batch.columns[ci].is_string_like
                         for ci in key_idx)
        # ONE device sync per batch across all string key columns
        string_bucket = SK.bucket_for(SK.max_live_bytes_multi(
            (batch.columns[ci], batch.num_rows) for ci in key_idx)) \
            if has_string else 0
        fn = shared_jit(
            f"subpart|{schema_cache_key(schema)}|{key_idx}|{num_buckets}"
            f"|{string_bucket}",
            lambda: _partition_step(schema, key_idx, num_buckets,
                                    string_bucket),
            kind="ooc_subpartition")
        reordered, counts = with_retry_no_split(lambda: fn(batch))
        for p, piece in enumerate(slice_by_counts(reordered, counts,
                                                  num_buckets)):
            if piece is not None:
                buckets[p].append(make_spillable(piece))
    return buckets


def close_all(buckets: List[List[SpillableBatchHandle]]) -> None:
    for q in buckets:
        for h in q:
            h.close()
        q.clear()
