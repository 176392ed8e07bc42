"""Hash-partition kernel: slice a batch into per-partition contiguous runs.

TPU replacement for cuDF's `Table.partition` (reference consumption:
GpuPartitioning.scala:66 `sliceInternalOnGpuAndClose`).  The output is
ordered by partition id — the reference's MT shuffle v2 design depends on
exactly this property (docs/design/rapids_shuffle_manager_v2_phase1_design.md)
and so does our ICI all-to-all layout.

Implementation: murmur3(keys) -> pmod -> stable sort by partition id (one
lexsort), plus per-partition row counts from a segment sum.  The partition
offsets let the shuffle writer slice each partition's rows without further
device work.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.kernels import hash as hash_kernels
from spark_rapids_tpu.kernels import strings as strkern
from spark_rapids_tpu.kernels.selection import gather_batch


@jax.named_scope("hash_partition")
def hash_partition(
    batch: ColumnarBatch,
    key_cols: Sequence[int],
    num_partitions: int,
    string_max_bytes: Optional[int] = None,
    seed: int = hash_kernels.DEFAULT_SEED,
) -> Tuple[ColumnarBatch, jax.Array]:
    """Returns (reordered_batch, partition_row_counts[int32 num_partitions]).

    Rows are stably reordered so partition p occupies rows
    [offsets[p], offsets[p+1]) where offsets = exclusive cumsum of counts.
    With the default seed it matches Spark HashPartitioning routing
    bit-for-bit (murmur3 seed 42, pmod), which is required for CPU/TPU
    shuffle interop and the differential oracle.  Out-of-core operators
    sub-partition with a DIFFERENT seed so re-partitioning data that already
    arrived through a seed-42 exchange still spreads across buckets
    (the reference's repartition level discipline,
    GpuAggregateExec.scala:290 / GpuSubPartitionHashJoin.scala).

    string_max_bytes=None derives the bucket from the data (host sync);
    routing is bit-exactness-critical so an undersized bucket is never
    acceptable here.
    """
    if string_max_bytes is None:
        string_max_bytes = strkern.live_string_bucket_for_batch(batch, key_cols)
    live = batch.live_mask()
    h = hash_kernels.murmur3_hash(
        [batch.columns[ci] for ci in key_cols], seed=seed,
        string_max_bytes=string_max_bytes
    )
    part = hash_kernels.pmod(h, num_partitions)
    part = jnp.where(live, part, jnp.int32(num_partitions))  # padding last
    order = jnp.lexsort((part,)).astype(jnp.int32)
    out = gather_batch(batch, order, batch.num_rows)
    counts = jax.ops.segment_sum(
        live.astype(jnp.int32), part, num_segments=num_partitions + 1
    )[:num_partitions]
    return out, counts


def round_robin_partition(
    batch: ColumnarBatch, num_partitions: int, start_partition: int = 0
) -> Tuple[ColumnarBatch, jax.Array]:
    """GpuRoundRobinPartitioning analog: row i -> (i + start) % n."""
    live = batch.live_mask()
    idx = jnp.arange(batch.capacity, dtype=jnp.int32)
    part = (idx + jnp.int32(start_partition)) % jnp.int32(num_partitions)
    part = jnp.where(live, part, jnp.int32(num_partitions))
    order = jnp.lexsort((part,)).astype(jnp.int32)
    out = gather_batch(batch, order, batch.num_rows)
    counts = jax.ops.segment_sum(
        live.astype(jnp.int32), part, num_segments=num_partitions + 1
    )[:num_partitions]
    return out, counts
