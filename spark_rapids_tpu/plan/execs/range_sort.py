"""Range-partitioned global sort + the shared range-bucketing machinery.

Reference: GpuRangePartitioner.scala + GpuSortExec — sample the sort keys,
pick range boundaries, exchange rows so partition i holds keys < partition
i+1's, sort each partition locally; the concatenation of partitions in
order IS the global order, and no single device ever holds the whole
dataset (the scalable path the single-partition sort lacks).

Key encoding: every fixed-width sort key maps to a uint64 whose unsigned
order equals Spark's column order including direction (kernels/sort.py
`_data_key_fixed`), with a separate null rank honoring NULLS FIRST/LAST;
string keys contribute packed byte-chunk keys.  Row destinations come from
lexicographic comparison against the (static, small) boundary list — B-1
vectorized compares, no searchsorted-over-tuples needed.

The module-level helpers (make_encoder / make_router / sample_boundaries)
are shared with the out-of-core single-partition sort (plan/execs/sort.py),
which uses the same bucketing as a distribution sort within one partition
(the TPU answer to GpuSortExec.scala:137's merge sort).
"""
from __future__ import annotations

from typing import Callable, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema
from spark_rapids_tpu.columnar.column import round_up_pow2
from spark_rapids_tpu.expressions.core import EvalContext, Expression
from spark_rapids_tpu.kernels.selection import gather_batch
from spark_rapids_tpu.kernels.sort import SortOrder, _data_key_fixed, _null_key, _string_data_keys
from spark_rapids_tpu.kernels.groupby import normalize_key_column
from spark_rapids_tpu.memory.retry import with_retry_no_split
from spark_rapids_tpu.memory.spill import SpillableBatchHandle, make_spillable
from spark_rapids_tpu.plan.execs.base import (
    MaterializeLock, TpuExec, string_key_bucket, timed)
from spark_rapids_tpu.plan.execs.coalesce import (
    coalesce_to_one, retry_over_spillable)
from spark_rapids_tpu.plan.execs.sort import TpuSortExec

SAMPLE_PER_PARTITION = 64


def _encode_fn(orders: Tuple[Tuple[Expression, SortOrder], ...]):
    def encode(batch: ColumnarBatch, bucket: int):
        """Per-row encoded key arrays (most-significant first)."""
        ctx = EvalContext(batch)
        keys = []
        for e, o in orders:
            c = normalize_key_column(e.eval(ctx))
            keys.append(_null_key(c, o).astype(jnp.uint64))
            if c.is_string_like:
                keys.extend(_string_data_keys(c, o, bucket))
            else:
                keys.append(_data_key_fixed(c, o))
        return tuple(keys)
    return encode


def _plan_key(orders, schema: Schema, n_out: int) -> str:
    from spark_rapids_tpu.plan.execs.base import (
        exprs_cache_key, schema_cache_key)
    return (f"rangesort|{n_out}|{schema_cache_key(schema)}|"
            f"{exprs_cache_key(e for e, _ in orders)}|"
            f"{','.join(f'{o.ascending}:{o.nulls_first}' for _, o in orders)}")


def make_encoder(orders, schema: Schema):
    """bucket -> jitted fn(batch) -> tuple of uint64 key arrays."""
    from functools import partial as _p
    from spark_rapids_tpu.plan.execs.base import shared_jit
    orders = tuple(orders)
    pk = _plan_key(orders, schema, 0)
    encode = _encode_fn(orders)
    return lambda b: shared_jit(f"{pk}|encode|{b}",
                                lambda: _p(encode, bucket=b),
                                kind="sort_encode")


def make_router(orders, schema: Schema, n_out: int):
    """(bucket, boundaries) -> fn(batch) -> (reordered_batch, counts).

    boundaries is a tuple of per-boundary uint64 tuples; it enters the
    jitted function as a DYNAMIC array input so re-sampling never
    recompiles.  Rows compare lexicographically against every boundary at
    once; equal keys always land in the same bucket (ties never split),
    which is what makes bucket-at-a-time sorting equivalent to a stable
    sort of the whole input.
    """
    from functools import partial as _p
    from spark_rapids_tpu.plan.execs.base import shared_jit
    orders = tuple(orders)
    pk = _plan_key(orders, schema, n_out)
    encode = _encode_fn(orders)

    def route(batch: ColumnarBatch, bounds: jax.Array, bucket: int):
        keys = encode(batch, bucket)
        K = jnp.stack(keys, axis=1)               # [cap, nk]
        lt = K[:, None, :] < bounds[None]         # [cap, nb, nk]
        eq = K[:, None, :] == bounds[None]
        # prefix_eq[..., k] = all positions before k equal
        prefix_eq = jnp.cumprod(
            jnp.concatenate([jnp.ones_like(eq[..., :1]), eq[..., :-1]],
                            axis=-1), axis=-1).astype(jnp.bool_)
        lt_lex = jnp.any(prefix_eq & lt, axis=-1)  # [cap, nb]
        dest = jnp.sum((~lt_lex).astype(jnp.int32), axis=1)
        live = batch.live_mask()
        dest = jnp.where(live, dest, jnp.int32(n_out))
        order = jnp.lexsort((dest,)).astype(jnp.int32)
        out = gather_batch(batch, order, batch.num_rows)
        counts = jax.ops.segment_sum(
            live.astype(jnp.int32), dest,
            num_segments=n_out + 1)[:n_out]
        return out, counts

    def routed(bucket: int, boundaries: tuple):
        n_keys = len(boundaries[0]) if boundaries else 1
        bounds = jnp.asarray(
            np.array(boundaries, np.uint64).reshape(-1, n_keys))
        fn = shared_jit(f"{pk}|route|{bucket}|{bounds.shape}",
                        lambda: _p(route, bucket=bucket),
                        kind="sort_route")
        return lambda b: fn(b, bounds)

    return routed


def sample_boundaries(batches: List[ColumnarBatch], orders, encoder,
                      n_out: int, bucket: Optional[int] = None):
    """Sample encoded keys from every batch and pick n_out-1 splitters.
    Returns (string_bucket, boundaries tuple).  ``bucket`` overrides the
    sample-derived string bucket (the cluster path must encode with the
    globally agreed DATA-wide bucket, not the local samples')."""
    if bucket is None:
        bucket = 0
        for b in batches:
            bucket = max(bucket,
                         string_key_bucket(b, [e for e, _ in orders]))
    samples: List[np.ndarray] = []
    n_keys = None
    for b in batches:
        keys = encoder(bucket)(b)
        n_keys = len(keys)
        cap = keys[0].shape[0]
        stride = max(cap // SAMPLE_PER_PARTITION, 1)
        idx = np.arange(0, cap, stride)
        live = np.asarray(b.live_mask())[idx]
        rows = np.stack([np.asarray(k)[idx] for k in keys], axis=1)
        samples.append(rows[live])
    if n_keys is None:
        return bucket, ()
    all_rows = (np.concatenate(samples) if samples
                else np.zeros((0, n_keys), np.uint64))
    if len(all_rows) == 0 or n_out == 1:
        return bucket, ()
    order = np.lexsort(tuple(all_rows[:, i]
                             for i in range(n_keys - 1, -1, -1)))
    sorted_rows = all_rows[order]
    boundaries = []
    for p in range(1, n_out):
        pos = min(len(sorted_rows) - 1, (p * len(sorted_rows)) // n_out)
        boundaries.append(tuple(int(x) for x in sorted_rows[pos]))
    # dedupe (equal boundaries collapse partitions, still correct)
    return bucket, tuple(dict.fromkeys(boundaries))


def range_bucket_spillable(batches: Iterator[ColumnarBatch], orders,
                           schema: Schema, n_out: int,
                           sample_batches: List[ColumnarBatch],
                           ) -> List[List[SpillableBatchHandle]]:
    """Route a stream of batches into n_out spillable range buckets."""
    encoder = make_encoder(orders, schema)
    bucket, boundaries = sample_boundaries(sample_batches, orders, encoder,
                                           n_out)
    route = make_router(orders, schema, n_out)(bucket, boundaries)
    from spark_rapids_tpu.plan.execs.out_of_core import slice_by_counts
    buckets: List[List[SpillableBatchHandle]] = [[] for _ in range(n_out)]
    for b in batches:
        reordered, counts = with_retry_no_split(lambda: route(b))
        for p, piece in enumerate(slice_by_counts(reordered, counts, n_out)):
            if piece is not None:
                buckets[p].append(make_spillable(piece))
    return buckets


class TpuRangeSortExec(TpuExec):
    """Global sort over N output partitions (range exchange + local sort)."""

    def __init__(self, orders: Sequence[Tuple[Expression, SortOrder]],
                 child: TpuExec, num_partitions: int,
                 small_sort_rows: int = 1 << 20):
        super().__init__((child,), child.schema)
        self.orders = tuple(orders)
        self.out_partitions = max(num_partitions, 1)
        #: inputs at or under this (spark.rapids.sql.batchSizeRows) skip
        #: sampling/routing and sort as ONE local partition
        self.small_sort_rows = max(int(small_sort_rows), 1)
        self._lock = MaterializeLock()
        self._buckets: Optional[List[List[SpillableBatchHandle]]] = None
        self._local_sort = TpuSortExec(self.orders, child)  # reuse its jit
        #: (rank, world) when distributed — set by the cluster executor;
        #: switches materialization to the cross-rank exchange path
        self.cluster: Optional[Tuple[int, int]] = None
        self._cluster_transport = None
        self._cluster_sample_transport = None

    def ensure_cluster_mapside(self) -> None:
        """Run the cross-rank map side (sample publish + routed shard
        writes) NOW.  Every rank must do this even when it owns zero
        output partitions (world > out_partitions): peers' completeness
        waits count this rank as a declared participant."""
        if self.cluster is None:
            return
        with self._lock:
            if self._cluster_transport is None:
                self._cluster_transport = \
                    self._materialize_cluster(*self.cluster)

    def num_partitions(self) -> int:
        return self.out_partitions

    def _materialize(self) -> List[List[SpillableBatchHandle]]:
        with self._lock:
            if self._buckets is not None:
                return self._buckets
            child = self.children[0]
            batches: List[ColumnarBatch] = []
            for p in range(child.num_partitions()):
                batches.extend(child.execute_partition(p))
            # the child is drained: from here on the work is the sort's own
            with timed(self.op_time, "sort.range"):
                if not batches:
                    buckets = [[] for _ in range(self.out_partitions)]
                elif (sum(b.capacity for b in batches)
                        <= self.small_sort_rows):
                    # small input: one local sort IS the global sort.  The
                    # sampling + routing machinery costs ~2 launches and a
                    # host sync per batch plus a per-partition sort — for
                    # a sub-batch-target input (the common
                    # post-aggregation shape) that is pure launch overhead
                    # on the TPU.  All rows land in partition 0; empty
                    # partitions follow, so partition-order concatenation
                    # is still the global order.
                    merged = with_retry_no_split(
                        lambda: coalesce_to_one(batches))
                    buckets = [[make_spillable(merged)]] + \
                        [[] for _ in range(self.out_partitions - 1)]
                else:
                    buckets = range_bucket_spillable(
                        iter(batches), self.orders, child.schema,
                        self.out_partitions, batches)
            self._buckets = buckets
            return buckets

    def execute_partition(self, idx: int) -> Iterator[ColumnarBatch]:
        if self.cluster is not None:
            with self._lock:
                if self._cluster_transport is None:
                    self._cluster_transport = \
                        self._materialize_cluster(*self.cluster)
                transport = self._cluster_transport
            with timed(self.op_time):
                batches = transport.read(idx)
            if not batches:
                return
            with timed(self.op_time, "sort.range"):
                # coalesce INSIDE the retry body (discard-and-rerun on
                # OOM instead of an unspillable closure capture)
                out = with_retry_no_split(
                    lambda: self._local_sort._run(
                        coalesce_to_one(batches)))
            self.output_rows.add(out.num_rows)
            yield self._count_out(out)
            return
        handles = self._materialize()[idx]
        if not handles:
            return
        with timed(self.op_time, "sort.range"):
            # pin-balanced retry: each attempt re-materializes the
            # handles and unpins before it ends (see
            # coalesce.retry_over_spillable); handles close in cleanup()
            out = retry_over_spillable(handles, self._local_sort._run)
        self.output_rows.add(out.num_rows)
        yield self._count_out(out)

    def cleanup(self) -> None:
        with self._lock:
            if self._buckets is not None:
                for bucket in self._buckets:
                    for h in bucket:
                        h.close()
                self._buckets = None
            if self._cluster_transport is not None:
                self._cluster_transport.cleanup()
                self._cluster_transport = None
            if self._cluster_sample_transport is not None:
                self._cluster_sample_transport.cleanup()
                self._cluster_sample_transport = None
        super().cleanup()

    def describe(self):
        inner = ", ".join(f"{e!r} {'ASC' if o.ascending else 'DESC'}"
                          for e, o in self.orders)
        return f"TpuRangeSort[{self.out_partitions}, {inner}]"


# -- cluster (multi-rank) path ------------------------------------------------

def _sample_value_batch(batches: List[ColumnarBatch], orders,
                        local_bucket: int) -> Optional[ColumnarBatch]:
    """Evaluate the sort-key expressions and gather a strided sample of
    their VALUES into one small host-built batch (+ a constant column
    carrying this rank's string-key bucket).  Raw values — not encoded
    keys — cross the wire so every rank can re-encode the union with one
    agreed bucket."""
    names = tuple([f"k{i}" for i in range(len(orders))] + ["_bucket"])
    from spark_rapids_tpu import types as _T
    dtypes = tuple([e.dtype for e, _ in orders] + [_T.INT])
    schema = Schema(names, dtypes)
    data = {n: [] for n in names}
    for b in batches:
        ctx = EvalContext(b)
        cols = [e.eval(ctx) for e, _ in orders]
        n = b.host_num_rows()
        if n == 0:
            continue
        stride = max(n // SAMPLE_PER_PARTITION, 1)
        idx = list(range(0, n, stride))
        # tpu-lint: allow-host-sync(driver-side range-bound sampling: a few rows per partition, off the hot path)
        col_lists = [c.to_pylist(n) for c in cols]
        for i in idx:
            for ci, n_ in enumerate(names[:-1]):
                data[n_].append(col_lists[ci][i])
            data["_bucket"].append(local_bucket)
    if not data[names[0]]:
        return None
    return ColumnarBatch.from_pydict(data, schema)


class ClusterRangeSortMixin:
    """Cross-rank global sort: exchanged samples -> identical boundaries
    on every rank -> range exchange over the TCP block plane -> each
    OWNER rank (p % world == rank) locally sorts its partitions.

    The cluster analog of Spark's RangePartitioner + per-partition sort
    (reference GpuRangePartitioner.scala; the executor's worker loop
    already assigns output partition p to rank p % world, and the driver
    reassembles partition-major, so the concatenation across ranks IS
    the global order)."""

    def _materialize_cluster(self, rank: int, world: int):
        from spark_rapids_tpu.shuffle.serializer import wire_supported
        from spark_rapids_tpu.shuffle.transport import make_transport
        child = self.children[0]
        bad = [str(d) for d in child.schema.dtypes
               if not wire_supported(d)]
        if bad:
            raise NotImplementedError(
                f"cluster range sort cannot serialize {bad} on the wire")
        local: List[ColumnarBatch] = []
        for p in range(child.num_partitions()):
            local.extend(child.execute_partition(p))

        # 1. sample exchange (broadcast pattern: every rank writes
        #    partition 0, every rank reads it from all participants)
        local_bucket = 0
        for b in local:
            local_bucket = max(local_bucket, string_key_bucket(
                b, [e for e, _ in self.orders]))
        sample = _sample_value_batch(local, self.orders, local_bucket)
        sschema = (sample.schema if sample is not None else None)
        if sschema is None:
            # still must participate: build an empty-shaped schema
            from spark_rapids_tpu import types as _T
            sschema = Schema(
                tuple([f"k{i}" for i in range(len(self.orders))]
                      + ["_bucket"]),
                tuple([e.dtype for e, _ in self.orders] + [_T.INT]))
        t_samples = make_transport("MULTIPROCESS", 1, sschema)
        t_samples.write(iter([(0, sample)] if sample is not None
                             else []))
        gathered = t_samples.read(0)

        # 2. identical boundaries on every rank: re-encode the union of
        #    raw sampled values with ONE agreed bucket (max of every
        #    rank's data-wide bucket, carried in the _bucket column)
        from spark_rapids_tpu.expressions.core import BoundReference
        bound_orders = tuple(
            (BoundReference(i, e.dtype), o)
            for i, (e, o) in enumerate(self.orders))
        union: List[ColumnarBatch] = []
        agreed_bucket = local_bucket
        for b in gathered:
            vals = b.to_pydict()
            agreed_bucket = max(agreed_bucket,
                                *(x for x in vals["_bucket"] if x
                                  is not None), 0)
            union.append(b)
        key_schema = Schema(sschema.names[:-1], sschema.dtypes[:-1])
        key_batches = [ColumnarBatch(b.columns[:-1], b.num_rows,
                                     key_schema) for b in union]
        encoder = make_encoder(bound_orders, key_schema)
        _bkt, boundaries = sample_boundaries(
            key_batches, bound_orders, encoder, self.out_partitions,
            bucket=agreed_bucket)

        # 3. range exchange: route local batches, write slices, owners
        #    read complete partitions from every rank
        t_data = make_transport("MULTIPROCESS", self.out_partitions,
                                child.schema)
        route = make_router(self.orders, child.schema,
                            self.out_partitions)(agreed_bucket, boundaries)
        from spark_rapids_tpu.plan.execs.out_of_core import slice_by_counts

        def slices():
            for b in local:
                reordered, counts = with_retry_no_split(lambda: route(b))
                for p, piece in enumerate(slice_by_counts(
                        reordered, counts, self.out_partitions)):
                    if piece is not None:
                        yield p, piece
        t_data.write(slices())
        self._cluster_sample_transport = t_samples
        return t_data


TpuRangeSortExec._materialize_cluster = \
    ClusterRangeSortMixin._materialize_cluster
