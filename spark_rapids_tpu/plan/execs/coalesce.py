"""Batch coalescing helper + exec.

Reference: GpuCoalesceBatches.scala:260 (concat to target size goals with
retry) and GpuShuffleCoalesceExec.scala:72.  The capacity-retry loop is the
static-shape analog of the reference's concat-with-retry.
"""
from __future__ import annotations

from functools import partial
from typing import List, Optional

from spark_rapids_tpu.columnar.batch import ColumnarBatch, host_scalar
from spark_rapids_tpu.columnar.column import round_up_pow2
from spark_rapids_tpu.kernels.selection import concat_batches_device
from spark_rapids_tpu.utils.tracing import trace_range


def _shape_key(batches: List[ColumnarBatch]) -> str:
    return ";".join(
        f"{b.capacity}," + ",".join(
            str(c.byte_capacity) for c in b.columns if c.offsets is not None)
        for b in batches)


def concat_batches_jit(batches: List[ColumnarBatch],
                       out_capacity: int) -> ColumnarBatch:
    """One jitted XLA program for the whole concat, cached by
    (schema, input shapes, output capacity).  Eager `concat_batches_device`
    dispatches ~80 primitives per call with per-shape compiles — measured
    at ~0.5s/call on the CPU backend for what is a sub-ms program."""
    from spark_rapids_tpu.plan.execs.base import schema_cache_key, shared_jit
    key = (f"concat|{schema_cache_key(batches[0].schema)}|"
           f"{_shape_key(batches)}|{out_capacity}")
    fn = shared_jit(key, lambda: partial(
        concat_batches_device, out_capacity=out_capacity), kind="concat")
    out, _ = fn(batches)
    return out


def maybe_shrink(batch: ColumnarBatch,
                 min_capacity: int = 4096) -> ColumnarBatch:
    """Re-bucket a sparse batch (live rows << capacity) to a small capacity.

    Selective filters and joins leave live rows far below the static
    capacity; every downstream kernel's cost scales with CAPACITY, not
    rows (the static-shape tax).  The reference's coalesce-insertion pass
    plays this role on dynamic-shape batches; here it is a conditional
    pow2 re-bucket.  Costs one host sync of num_rows per batch.
    """
    cap = batch.capacity
    if cap <= min_capacity:
        return batch
    with trace_range("batch.shrink"):
        return _shrink_over_floor(batch, cap, min_capacity)


def _shrink_over_floor(batch: ColumnarBatch, cap: int,
                       min_capacity: int) -> ColumnarBatch:
    """``maybe_shrink`` of a batch over the floor capacity: the wait on its
    row count and the regather, one ``batch.shrink`` span."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_tpu.columnar.batch import ColumnarBatch as _CB
    from spark_rapids_tpu.kernels.selection import gather_column
    from spark_rapids_tpu.plan.execs.base import schema_cache_key, shared_jit

    # ONE device->host transfer for num_rows + every string column's live
    # byte count (per-scalar syncs would stall the dispatch pipeline once
    # per column on the filter hot path)
    # tpu-lint: allow-host-sync(documented ONE batched transfer for num_rows + live byte counts)
    scalars = jax.device_get(
        (batch.num_rows,
         [c.offsets[batch.num_rows] for c in batch.columns
          if c.offsets is not None]))
    n = int(scalars[0])
    live_bytes = [int(x) for x in scalars[1]]
    target = round_up_pow2(max(n, min_capacity))
    if target * 4 > cap:
        return batch   # not sparse enough to pay the regather

    # live rows sit compacted at the front (canonical form), so the
    # shrink is a prefix gather; child buffers re-bucket to the live size
    out_bcaps = []
    bi = 0
    for c in batch.columns:
        if c.offsets is not None:
            out_bcaps.append(round_up_pow2(max(live_bytes[bi], 1)))
            bi += 1
        else:
            out_bcaps.append(None)

    def shrink(b, n_scalar, _cap=target, _bcaps=tuple(out_bcaps)):
        idx = jnp.arange(_cap, dtype=jnp.int32)
        cols = tuple(
            gather_column(c, idx, n_scalar, out_capacity=_cap,
                          out_byte_capacity=bc)
            for c, bc in zip(b.columns, _bcaps))
        return _CB(cols, n_scalar, b.schema)
    bcaps = ",".join(str(c.byte_capacity) for c in batch.columns
                     if c.offsets is not None)
    key = (f"shrink|{schema_cache_key(batch.schema)}|{cap}|{bcaps}|"
           f"{target}|{out_bcaps}")
    return shared_jit(key, lambda: shrink, kind="shrink")(
        batch, host_scalar(n))


def retry_over_spillable(handles, body):
    """Run ``body(coalesce_to_one(materialized handles))`` under
    with_retry_no_split with PIN-BALANCED attempts.

    Every attempt re-materializes the handles (pin +1 each) and ALWAYS
    unpins its own pins before the attempt ends — after ``body`` returns
    on success, before the retry's spill on failure.  That makes the
    re-materialize contract real: a mid-attempt OOM leaves the handles
    unpinned and therefore spillable, so the spill can free exactly the
    inputs the next attempt will bring back (the reference's
    withRetry-over-SpillableColumnarBatch discipline).  Materializing
    inside a retry body WITHOUT this balancing leaks one pin per extra
    attempt and permanently unspills the handles.

    ``body`` must not keep the coalesced batch (or the materialized
    inputs) alive past its return; callers still own close().
    """
    from spark_rapids_tpu.memory.retry import with_retry_no_split
    from spark_rapids_tpu.utils.cancel import check_cancelled

    handles = list(handles)   # attempts re-iterate: a generator would be
                              # exhausted by attempt 1 and retry nothing

    def attempt():
        # cancellation point per ATTEMPT: a cancelled query must not
        # spill-and-rerun its way through the remaining retries
        check_cancelled()
        pinned = []
        try:
            mats = []
            for h in handles:
                mats.append(h.materialize())
                pinned.append(h)
            return body(coalesce_to_one(mats))
        finally:
            for h in pinned:
                h.unpin()

    return with_retry_no_split(attempt)


def retry_over_stream_pieces(piece_lists, body):
    """``body(lists of materialized batches)`` under with_retry_no_split
    with PIN-BALANCED attempts over shuffle StreamPieces
    (shuffle/transport.py).

    The fused-across-shuffle reduce path concats its stream group and its
    per-partition build pieces INSIDE one program, so the pieces must be
    device-resident for exactly the attempt: every attempt materializes
    each piece (pin +1 on spillable handles) and ALWAYS unpins its own
    pins before the attempt ends — the retry_over_spillable discipline
    generalized to piece lists with the coalesce moved into the caller's
    program.  A mid-attempt OOM therefore leaves every piece spillable,
    so the spill can free exactly the inputs the next attempt will bring
    back.

    Range-view pieces (CACHE_ONLY range-view store) share one BACKING
    handle across several views: the backing pins EXACTLY ONCE per
    attempt — later views of a backing already materialized this attempt
    reuse its batch through as_view() with no extra pin, so the unwind
    leaves the backing's pin count exactly where the attempt found it
    (N pins would still balance, but the dedup also collapses N
    materialize calls on the shared handle to one).

    ``body`` must not keep the materialized batches alive past its
    return; piece ownership (close) stays with the transport.
    """
    from spark_rapids_tpu.memory.retry import with_retry_no_split
    from spark_rapids_tpu.utils.cancel import check_cancelled

    piece_lists = [list(lst) for lst in piece_lists]

    def attempt():
        # cancellation point per attempt (see retry_over_spillable)
        check_cancelled()
        pinned = []
        backings = {}   # backing_key -> materialized backing batch
        try:
            mats = []
            for lst in piece_lists:
                cur = []
                for p in lst:
                    bk = p.backing_key()
                    if bk is not None and bk in backings:
                        cur.append(p.as_view(backings[bk]))
                        continue
                    m = p.materialize_pinned()
                    pinned.append(p)
                    if bk is not None:
                        backings[bk] = p.backing_of(m)
                    cur.append(m)
                mats.append(cur)
            return body(mats)
        finally:
            for p in pinned:
                p.unpin()

    return with_retry_no_split(attempt)


def pull_group_in_core(pieces, bound: int, rows: int = 0):
    """The pieces ``pieces`` hands out (one side of one reduce group, raw
    from ``stream_pieces``) for as long as the group stays one program's
    work by ``exchange.reduce_group_in_core``: ``(list, rows)`` with
    ``rows`` the rows held so far (``rows`` in: what the group's other side
    holds).  The moment the bound is passed the pull STOPS and what was
    pulled is DROPPED: ``(None, rows)``.  Wire pieces hold real device
    batches, and keeping them across the merged path's re-read would double
    residency on exactly the oversized path the fallback protects."""
    from spark_rapids_tpu.plan.execs.exchange import reduce_group_in_core
    out = []
    for p in pieces:
        out.append(p)
        rows += p.rows
        if not reduce_group_in_core(rows, bound):
            return None, rows
    return out, rows


def coalesce_to_one(batches: List[ColumnarBatch]) -> Optional[ColumnarBatch]:
    """Concat same-schema batches into one (None for empty input)."""
    if not batches:
        return None
    if len(batches) == 1:
        return batches[0]
    # size by the sum of static capacities: an upper bound on live rows, so
    # the concat can never overflow and needs no device sync or retry
    cap = round_up_pow2(max(sum(b.capacity for b in batches), 1))
    return concat_batches_jit(batches, cap)
