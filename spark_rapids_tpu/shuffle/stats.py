"""Process-wide shuffle data-plane counters.

The reduce-side fast path (pooled connections, pipelined fetch_many,
concat-once merge) is a perf claim: these counters make it checkable —
in tests (connection reuse, one merge per reduce partition), in the
cluster stats snapshot (cluster/stats.py) and in the bench artifact
(bench.py emits them per query).  The reference keeps the same numbers
as shuffle-manager metrics (RapidsShuffleInternalManagerBase metrics /
UCX transport counters).

Counting is lock-guarded: fetch threads, writer pools and reduce tasks
all touch these concurrently and ``+=`` is not atomic bytecode.
"""
from __future__ import annotations

import threading

# module-level on purpose: add() runs per fetched block/batch on the
# data plane, and obs.py's module imports are stdlib-only (no cycle)
from spark_rapids_tpu.utils.obs import current_query_trace

_FIELDS = (
    # transport
    "connections_opened",     # TCP connects (reuse keeps this ~1/peer)
    "fetch_requests",         # fetch round-trips (fetch_many = 1)
    "blocks_fetched",         # wire blocks received over the network
    "bytes_fetched",          # wire bytes received over the network
    # overlap
    "prefetch_stall_ns",      # consumer blocked on an empty prefetch queue
    # pipelined exchanges + reduce-side fusion (shuffle/pipeline.py +
    # plan/fused.py; ROADMAP open item 1)
    "pipeline_overlap_ns",    # producer work that ran WHILE the consumer
                              # of a stage hand-off was busy (true overlap
                              # of map compute/serialize with reduce fetch)
    "stage_drain_ns",         # consumer blocked on an empty stage hand-off
                              # after pipeline fill (≈0 = never drained)
    "fused_reduce_programs",  # fused-across-shuffle program executions
                              # (merge + probe + agg + next-map-slice as
                              # ONE program per coalesced partition group)
    "fused_reduce_fallbacks", # partitions that fell back to the per-op
                              # join path (build side over the fuse limit)
    "exchange_stages",        # exchanges materialized (launches-per-stage
                              # = launches / exchange_stages in bench)
    # CACHE_ONLY range-view store (transport.py RangeView; the device
    # twin of the wire range path — ROADMAP open item 1)
    "range_view_blocks",      # per-partition range views written (one
                              # spillable BACKING batch per map batch;
                              # blocks are (backing, start, count) views)
    "range_view_folds",       # views whose slice ran INSIDE a consumer's
                              # fused program (no standalone gather)
    "slice_gather_programs",  # standalone map-side piece-gather program
                              # dispatches (slice_by_counts on the
                              # exchange's device-slice path: nested
                              # schemas on wire transports; 0 on
                              # CACHE_ONLY, which stores range views)
    "range_view_materializes",  # views sliced by a standalone gather for
                              # a non-fused consumer (the materialize
                              # fallback: OOC joins, sort, per-op reads)
    # the grouped aggregate across an exchange (plan/fused.py _converge,
    # plan/execs/exchange.py, plan/execs/aggregate.py): numbers the host
    # holds anyway, no sync of their own
    "agg_partial_rows_in",    # rows into fused programs that hold a
                              # grouped partial aggregate (the stream
                              # batch's, before the chain's filters)
    "agg_partial_groups_out",  # rows those programs' aggregates handed on:
                              # their groups, batch by batch (the feedback
                              # _converge fetches)
    "exchange_rows_written",  # rows the map side put into its reduce
                              # partitions (the per-batch counts)
    "reduce_groups",          # reduce groups a final or complete aggregate
                              # merged
    "reduce_groups_out_of_core",  # of them, those that took the out-of-core
                              # sub-partition merge (one agg.out_of_core
                              # span each): past reduce_group_in_core
    # joins (plan/execs/join.py): numbers the kernel's capacity loop has
    # on the host anyway, no sync of their own
    "join_candidate_pairs",   # pairs the equi-key probes found: what a
                              # condition is evaluated over, or an
                              # unconditional join's expansion needs
    "join_output_rows",       # rows the joins' accepted launches handed on
    # map side (range-serialization write path; serializer.py)
    "map_range_batches",      # map batches written via range framing
    "map_range_blocks",       # partition wire blocks framed from row ranges
    "map_d2h_syncs",          # serializer device->host downloads (range
                              # path: exactly 1 per map batch)
    "map_serialize_bytes",    # wire bytes produced by the map serializer
    "map_serialize_ns",       # wall time in map-side wire framing
    # merge
    "merges",                 # merge_batches materializations (HBM uploads)
    "merge_input_blocks",     # wire blocks consumed by those merges
    "reduce_concats",         # exchange-side concat passes over already-
                              # merged batches (0 when concat-once holds)
    # integrity (checksummed frames; docs/fault_tolerance.md)
    "checksums_computed",     # map-side frame checksums stored at put()
    "checksums_verified",     # reduce-side frames verified on receive
    "checksum_failures",      # mismatches detected (BlockCorruptionError)
    # recovery
    "fetch_retries",          # reconnect/retry round-trips beyond the first
    "blocks_refetched",       # blocks re-fetched after a corrupt/failed read
    "peer_failures_reported", # budget-exhausted peers reported upstream
    "peers_excluded",         # peers the heartbeat registry excluded
    # durability (map-output replication + spill-backed persistence;
    # docs/fault_tolerance.md durable-shuffle rows)
    "blocks_replicated",      # map blocks pushed to replica holders
    "bytes_replicated",       # wire bytes pushed to replica holders
    "replica_announces",      # (shuffle, source)->holder records announced
    "blocks_refetched_replica",  # blocks served from a replica after the
                              # primary was lost/corrupt (re-fetch, NOT
                              # re-execution — the acceptance counter)
    "replica_failovers",      # fetch paths that switched primary->replica
    "blocks_persisted",       # map blocks also written to the persist dir
    "blocks_recovered_disk",  # blocks reloaded from the persist dir after
                              # a restart emptied the in-memory store
    # elasticity (dynamic membership)
    "executors_joined",       # workers registered into a live registry
    "executors_left",         # workers that gracefully left (drained)
    "blocks_drained",         # primary blocks re-replicated by a drain
    "catalog_syncs",          # joiners that pulled the shuffle/replica
                              # catalog at registration
    # speculation + first-commit-wins
    "speculative_launches",   # straggler tasks given a second attempt
    "speculative_wins",       # ranks whose speculative attempt finished
                              # first
    "map_commits_won",        # map-output commits that won their logical
                              # slot at the registry
    "map_commits_lost",       # commits that lost the race (the loser's
                              # blocks are dropped by attempt)
    "rank_redispatches",      # single-rank re-dispatches after executor
                              # loss (the durable path: survivors re-fetch
                              # instead of re-executing the whole query)
    # executor liveness
    "heartbeat_failures",     # failed liveness beats (cumulative)
    "heartbeat_failure_streak",  # max consecutive failed beats (gauge)
    # driver-side scoped recovery
    "scoped_resubmits",       # query re-dispatches after executor loss
    "task_retries",           # query re-dispatches after a retryable task
                              # failure (no executor lost)
    "executors_excluded",     # lost executors excluded from resubmission
    "shuffle_invalidations",  # shuffles dropped from peers' block stores
                              # when a query attempt was torn down
    # serving layer (admission / tenant budgets / result cache;
    # serving/admission.py + serving/cache.py + memory/tenant.py)
    "queries_admitted",       # queries that passed admission control
    "queries_queued",         # queries that had to WAIT for admission
    "queries_rejected",       # queries rejected (queue full / admission
                              # timeout) — backpressure made visible
    "cache_hits",             # result-cache hits (served without running)
    "cache_misses",           # result-cache misses (executed + stored)
    "cache_evictions",        # entries evicted by the LRU size bound/TTL
    "cache_invalidations",    # entries dropped by explicit source
                              # invalidation or corruption detection
    "tenant_spills",          # spills of tenant-tagged handles (pressure
                              # attributed to the tenant that held data)
    "budget_denials",         # tenant-budget breaches surfaced as
                              # self-retry OOMs (never a neighbor kill)
    # cooperative cancellation + stall watchdog (utils/cancel.py +
    # utils/watchdog.py; docs/fault_tolerance.md cancellation section)
    "queries_cancelled",      # queries stopped by an explicit cancel, a
                              # deadline, or the watchdog (driver/serving)
    "tasks_cancelled",        # partition/executor tasks that observed the
                              # cancel and stopped early (typed abort, not
                              # run-to-completion)
    "cancel_broadcasts",      # cancel_query fan-outs to executor peers
    "watchdog_stalls",        # registered waits flagged past the stall
                              # threshold (stall report written each time)
    "drop_query_failures",    # drop_query broadcasts that failed on a peer
                              # even after the retry (residual stale state
                              # surfaced, not silently swallowed)
    # elasticity control loop (cluster/autoscaler.py) + overload
    # protection (serving/overload.py); docs/fault_tolerance.md
    # "overload & elasticity"
    "autoscale_up",           # scale-out decisions (executor launches
                              # requested by the policy)
    "autoscale_down",         # scale-in decisions (graceful drains
                              # requested by the policy)
    "queries_shed",           # submissions rejected by priority-aware
                              # load shedding (admission-wait p99 over
                              # the SLO target; lowest priority first)
    "ratelimit_rejections",   # submissions rejected by a tenant's
                              # token-bucket rate limit
    "breaker_trips",          # plan-fingerprint circuit breakers that
                              # opened (repeated failures of one plan)
    "breaker_fast_fails",     # submissions failed fast by an OPEN
                              # breaker (capacity NOT re-burned)
)


class ShuffleCounters:
    """add()/set_max() are the ONE blessed mutation entry point: beside
    the global accumulation they TEE every delta into the thread-ambient
    per-query counter scope (utils/obs.py QueryTrace), so two concurrent
    serving queries get ATTRIBUTED counters instead of interleaved
    globals.  tpu-lint's counter-discipline rule flags raw attribute
    mutation that would bypass the tee."""

    def __init__(self):
        self._lock = threading.Lock()
        for f in _FIELDS:
            setattr(self, f, 0)

    def add(self, **deltas: int) -> None:
        with self._lock:
            for k, v in deltas.items():
                setattr(self, k, getattr(self, k) + int(v))
        # per-query tee OUTSIDE the counters lock (the trace has its own
        # lock; never nest them).  No ambient trace = one thread-local
        # read — the ~0-overhead disabled path.
        tr = current_query_trace()
        if tr is not None:
            tr.counter_add(deltas)

    def set_max(self, **values: int) -> None:
        """High-watermark gauges (e.g. heartbeat failure streak)."""
        with self._lock:
            for k, v in values.items():
                setattr(self, k, max(getattr(self, k), int(v)))
        tr = current_query_trace()
        if tr is not None:
            tr.counter_set_max(values)

    def snapshot(self) -> dict:
        with self._lock:
            return {f: getattr(self, f) for f in _FIELDS}

    def reset(self) -> None:
        with self._lock:
            for f in _FIELDS:
                setattr(self, f, 0)


SHUFFLE_COUNTERS = ShuffleCounters()


class Histogram:
    """Fixed-bucket latency histogram: exponential (x2) bucket bounds
    from ``lowest_s`` up, with exact count/sum/max.  Counters answer
    "how much"; serving needs "how long at the tail" — submit→done
    latency and per-stage fetch wait p50/p90/p99 for the fleet-scale
    SLO story (ROADMAP item 5), without storing every sample.

    Percentiles report the UPPER bound of the bucket holding the
    quantile (conservative: a reported p99 is >= the true p99), capped
    at the observed max."""

    def __init__(self, lowest_s: float = 0.0005, n_buckets: int = 28):
        self.lowest_s = float(lowest_s)
        self.bounds = [self.lowest_s * (2.0 ** i)
                       for i in range(n_buckets)]
        self._lock = threading.Lock()
        self._counts = [0] * (n_buckets + 1)   # +1: overflow bucket
        self.count = 0
        self.sum_s = 0.0
        self.max_s = 0.0

    def _bucket(self, v: float) -> int:
        import bisect
        return bisect.bisect_left(self.bounds, v)

    def record(self, seconds: float) -> None:
        v = max(float(seconds), 0.0)
        i = self._bucket(v)
        with self._lock:
            self._counts[i] += 1
            self.count += 1
            self.sum_s += v
            if v > self.max_s:
                self.max_s = v

    def percentile(self, q: float) -> float:
        with self._lock:
            return self._percentile_locked(q)

    def _percentile_locked(self, q: float) -> float:
        if not self.count:
            return 0.0
        target = max(min(q, 1.0), 0.0) * self.count
        cum = 0
        for i, c in enumerate(self._counts):
            cum += c
            if cum >= target and c:
                if i >= len(self.bounds):
                    return self.max_s
                return min(self.bounds[i], self.max_s)
        return self.max_s

    def snapshot(self) -> dict:
        # ONE critical section: count/sum/max and the percentiles must
        # come from the same sample set, or a concurrent record() tears
        # the snapshot (count=N over N-1-sample percentiles).  The raw
        # bucket counts ride along so remote snapshots can be merged
        # bucket-wise (Histogram.merge) and rendered as native
        # Prometheus histograms (tools/metrics_scrape.py).
        with self._lock:
            return {"count": self.count,
                    "sum_s": round(self.sum_s, 6),
                    "max_s": round(self.max_s, 6),
                    "p50": round(self._percentile_locked(0.50), 6),
                    "p90": round(self._percentile_locked(0.90), 6),
                    "p99": round(self._percentile_locked(0.99), 6),
                    "counts": list(self._counts)}

    def merge(self, other) -> "Histogram":
        """Fold another histogram (or a wire SNAPSHOT of one) into this
        one bucket-wise: the driver aggregates rank-local latency
        histograms into cluster stats instead of reporting only its own.
        Requires the same bucket layout (every histogram in the fleet is
        built with the defaults); count/sum/max reconcile as sums/max."""
        if isinstance(other, Histogram):
            other = other.snapshot()
        counts = other.get("counts")
        if counts is None:
            raise ValueError(
                "Histogram.merge needs a snapshot with bucket counts "
                "(a pre-merge-era peer sent a percentile-only snapshot)")
        with self._lock:
            if len(counts) != len(self._counts):
                raise ValueError(
                    f"bucket layout mismatch: {len(counts)} buckets vs "
                    f"{len(self._counts)} (histograms must share "
                    "lowest_s/n_buckets to merge)")
            for i, c in enumerate(counts):
                self._counts[i] += int(c)
            self.count += int(other["count"])
            self.sum_s += float(other["sum_s"])
            self.max_s = max(self.max_s, float(other["max_s"]))
        return self

    def reset(self) -> None:
        with self._lock:
            self._counts = [0] * len(self._counts)
            self.count = 0
            self.sum_s = 0.0
            self.max_s = 0.0


#: process-wide latency histograms, beside the counters in the cluster
#: stats snapshot and the bench artifacts
HISTOGRAMS = {
    # serving submit()->rows wall time per submission (admission wait,
    # execution, cache hits included — the user-visible latency)
    "serving_submit_s": Histogram(),
    # reduce-side fetch stalls: consumer blocked on an empty prefetch
    # queue (each stall occurrence, seconds)
    "fetch_wait_s": Histogram(),
    # pipelined-exchange drains: consumer blocked on an empty stage
    # hand-off after pipeline fill
    "stage_drain_s": Histogram(),
    # admission wait alone (inside serving_submit_s): time one
    # submission spent in QueryQueue._admit — the autoscaler's and the
    # load shedder's SLO signal (its p99 rides every telemetry sample,
    # so windowed tails come from ring bucket-count deltas)
    "admission_wait_s": Histogram(),
}


def histograms() -> dict:
    """{name: percentile snapshot} over the process-wide histograms."""
    return {k: h.snapshot() for k, h in HISTOGRAMS.items()}


def shuffle_counters() -> dict:
    """Snapshot of the process-wide counters (bench/test accessor)."""
    return SHUFFLE_COUNTERS.snapshot()


def reset_shuffle_counters() -> None:
    SHUFFLE_COUNTERS.reset()
    for h in HISTOGRAMS.values():
        h.reset()
