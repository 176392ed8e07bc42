"""Config/flag system.

Re-creates the reference's `RapidsConf` builder DSL (reference:
sql-plugin/src/main/scala/com/nvidia/spark/rapids/RapidsConf.scala:263
`ConfBuilder` / `ConfEntry:124`): every key is registered with a doc string,
a type, and a default; typed accessors hang off a `RapidsConf` snapshot; the
registry generates `docs/configs.md`.  Keys keep the `spark.rapids.*`
namespace for drop-in familiarity, with TPU-specific keys under
`spark.rapids.tpu.*`.

Configs are re-read at plan time per query (reference: GpuOverrides.scala:4990)
so toggles take effect without restarting the session.
"""
from __future__ import annotations

import threading
from typing import Any, Callable, Dict, Generic, List, Optional, TypeVar

T = TypeVar("T")

_REGISTRY: Dict[str, "ConfEntry"] = {}
_REGISTRY_LOCK = threading.Lock()


class ConfEntry(Generic[T]):
    def __init__(self, key: str, doc: str, default: T, converter: Callable[[str], T],
                 internal: bool = False, startup_only: bool = False):
        self.key = key
        self.doc = doc
        self.default = default
        self.converter = converter
        self.internal = internal
        self.startup_only = startup_only

    def get(self, conf_map: Dict[str, str]) -> T:
        raw = conf_map.get(self.key)
        if raw is None:
            return self.default
        if isinstance(raw, str):
            return self.converter(raw)
        return raw  # already typed (programmatic set)

    def __repr__(self):
        return f"ConfEntry({self.key}, default={self.default!r})"


def _to_bool(s: str) -> bool:
    return s.strip().lower() in ("true", "1", "yes")


def _to_int(s: str) -> int:
    return int(s)


def _to_float(s: str) -> float:
    return float(s)


def _to_bytes(s: str) -> int:
    """Parse '512m', '512mb', '4g', '1024' into bytes (Spark byte-string
    syntax, JavaUtils.byteStringAs)."""
    s = s.strip().lower()
    mult = 1
    for suffix, m in (
        ("kb", 1 << 10), ("mb", 1 << 20), ("gb", 1 << 30), ("tb", 1 << 40),
        ("k", 1 << 10), ("m", 1 << 20), ("g", 1 << 30), ("t", 1 << 40),
        ("b", 1),
    ):
        if s.endswith(suffix):
            s = s[: -len(suffix)]
            mult = m
            break
    return int(float(s) * mult)


class ConfBuilder:
    def __init__(self, key: str):
        self._key = key
        self._doc = ""
        self._internal = False
        self._startup_only = False

    def doc(self, text: str) -> "ConfBuilder":
        self._doc = text
        return self

    def internal(self) -> "ConfBuilder":
        self._internal = True
        return self

    def startup_only(self) -> "ConfBuilder":
        self._startup_only = True
        return self

    def _register(self, default, converter) -> ConfEntry:
        entry = ConfEntry(self._key, self._doc, default, converter,
                          self._internal, self._startup_only)
        with _REGISTRY_LOCK:
            if self._key in _REGISTRY:
                raise ValueError(f"duplicate conf key: {self._key}")
            _REGISTRY[self._key] = entry
        return entry

    def boolean_conf(self, default: bool) -> ConfEntry:
        return self._register(default, _to_bool)

    def int_conf(self, default: int) -> ConfEntry:
        return self._register(default, _to_int)

    def double_conf(self, default: float) -> ConfEntry:
        return self._register(default, _to_float)

    def string_conf(self, default: Optional[str]) -> ConfEntry:
        return self._register(default, lambda s: s)

    def bytes_conf(self, default: int) -> ConfEntry:
        return self._register(default, _to_bytes)


def conf(key: str) -> ConfBuilder:
    return ConfBuilder(key)


# ---------------------------------------------------------------------------
# Registered keys (subset mirroring the reference's most load-bearing flags;
# reference key names preserved where the concept carries over 1:1).
# ---------------------------------------------------------------------------

SQL_ENABLED = conf("spark.rapids.sql.enabled").doc(
    "Enable or disable the TPU acceleration of SQL plans. When false every "
    "operator runs on CPU and the differential-test oracle uses this to get "
    "reference results."
).boolean_conf(True)

EXPLAIN = conf("spark.rapids.sql.explain").doc(
    "Explain why parts of a query were or were not placed on the TPU. "
    "Values: NONE, NOT_ON_GPU, ALL."
).string_conf("NOT_ON_GPU")

BATCH_SIZE_BYTES = conf("spark.rapids.sql.batchSizeBytes").doc(
    "Target size in bytes of output columnar batches. Mirrors the reference's "
    "coalesce goal machinery (GpuExec.scala:129-144)."
).bytes_conf(1 << 28)

BATCH_SIZE_ROWS = conf("spark.rapids.sql.batchSizeRows").doc(
    "Target row count of output columnar batches; row capacities are rounded "
    "up to a power of two so XLA re-compiles at most log2(n) variants."
).int_conf(1 << 20)

STAGE_FUSION = conf("spark.rapids.sql.tpu.fuseStages").doc(
    "Fuse exchange-free operator chains (project/filter/broadcast-join/"
    "partial-agg) into one XLA program per batch, eliminating per-operator "
    "program launches and host round trips (the reference keeps per-batch "
    "operator chains device-side, GpuExec.scala:393; what a launch costs "
    "on a directly attached chip is not measured)."
).boolean_conf(True)

CONCURRENT_TPU_TASKS = conf("spark.rapids.sql.concurrentGpuTasks").doc(
    "Number of tasks that can hold the device semaphore concurrently "
    "(reference: RapidsConf.scala:637, GpuSemaphore)."
).int_conf(2)

SHUFFLE_PARTITIONS = conf("spark.sql.shuffle.partitions").doc(
    "Number of reduce-side partitions for shuffle exchanges."
).int_conf(16)

PROFILE_ENABLED = conf("spark.rapids.profile.enabled").doc(
    "Per-query profiling: a sampled flamegraph (collapsed stacks, "
    "flamegraph.pl/speedscope format) plus the sampler's ticks that got "
    "the interpreter lock more than 20 ms late, each with the frames of "
    "every other thread (reference: asyncProfiler.scala per-stage "
    "flamegraphs)."
).boolean_conf(False)

PROFILE_DIR = conf("spark.rapids.profile.dir").doc(
    "Directory for profiling artifacts (query<N>_flame.txt / "
    "query<N>_late_ticks.json)."
).string_conf("tpu_profile")

AQE_COALESCE_PARTITIONS = conf(
    "spark.rapids.sql.adaptive.coalescePartitions.enabled").doc(
    "Merge undersized reduce partitions at exchange read time using the "
    "materialized map-output row counts (AQE partition coalescing; "
    "reference: GpuCustomShuffleReaderExec.scala:82 reading Spark's "
    "CoalescedPartitionSpec).  Co-partitioned join sides always merge "
    "with one shared spec so co-partitioning is preserved."
).boolean_conf(True)

SHUFFLE_MODE = conf("spark.rapids.shuffle.mode").doc(
    "CACHE_ONLY: partition slices stay device-resident as spillable handles "
    "(reference CACHE_ONLY / RapidsCachingWriter shape — the fast in-process "
    "path). MULTITHREADED: host-staged threaded shuffle over the tpu-kudo "
    "wire format (reference MT mode, RapidsShuffleInternalManagerBase"
    ".scala). ICI: gang-scheduled device-to-device all-to-all over the TPU "
    "interconnect (replaces the reference's UCX mode). MULTIPROCESS: "
    "TCP block-server data plane with heartbeat peer discovery and a "
    "flow-controlled fetch iterator (shuffle/net.py — the DCN analog of "
    "the reference's UCX transport for multi-host clusters)."
).string_conf("CACHE_ONLY")

SHUFFLE_WRITER_THREADS = conf("spark.rapids.shuffle.multiThreaded.writer.threads").doc(
    "Serializer/writer thread-pool size for the multithreaded shuffle."
).int_conf(4)

SHUFFLE_READER_THREADS = conf("spark.rapids.shuffle.multiThreaded.reader.threads").doc(
    "Deserializer/reader thread-pool size for the multithreaded shuffle."
).int_conf(4)

SHUFFLE_COMPRESSION_CODEC = conf("spark.rapids.shuffle.compression.codec").doc(
    "Compression for shuffle wire buffers: none, zstd, lz4 (reference: "
    "TableCompressionCodec.scala; device nvcomp is N/A on TPU so compression "
    "runs on host in the native library)."
).string_conf("none")

BROADCAST_ROW_THRESHOLD = conf("spark.rapids.sql.join.broadcastRowThreshold").doc(
    "Estimated build-side row count below which a join plans as a broadcast "
    "hash join instead of a shuffled hash join (the role of Spark's "
    "autoBroadcastJoinThreshold for the reference's "
    "GpuBroadcastHashJoinExec)."
).int_conf(500_000)

JOIN_ADAPTIVE_ENABLED = conf("spark.rapids.sql.join.adaptive.enabled").doc(
    "Allow the runtime broadcast-vs-shuffled choice for joins whose "
    "static estimate sits in the ambiguous zone (reference: "
    "GpuShuffledSizedHashJoinExec.scala:829).  Cluster mode forces this "
    "off: the choice is made from the LOCAL build-side row count, so two "
    "ranks could pick different physical shapes for the same plan."
).boolean_conf(True)

SHUFFLE_CHECKSUM_ENABLED = conf("spark.rapids.shuffle.checksum.enabled").doc(
    "Verify every fetched shuffle frame against the CRC computed when its "
    "map output was stored (utils/checksum.py: CRC32C when available, CRC32 "
    "otherwise). A mismatch raises a typed BlockCorruptionError and the "
    "block is re-fetched from the serving peer under the network retry "
    "budget before the error escalates. Frames always carry a checksum "
    "slot on the wire (0 = unchecksummed), so toggling this never desyncs "
    "framing."
).boolean_conf(True)

SPILL_CHECKSUM_ENABLED = conf(
    "spark.rapids.memory.spill.checksum.enabled").doc(
    "Checksum spill files at write time and verify on reload; a mismatch "
    "raises SpillCorruptionError instead of resurrecting corrupt data as "
    "wrong query results."
).boolean_conf(True)

NETWORK_RETRY_MAX_ATTEMPTS = conf(
    "spark.rapids.network.retry.maxAttempts").doc(
    "Retries of one RPC/fetch against one peer before the shared "
    "RetryBudget raises RetryBudgetExhausted (bounded exponential backoff; "
    "utils/retry_budget.py). Applies to pooled-connection reconnects and "
    "corrupt-block refetches."
).int_conf(4)

NETWORK_RETRY_BASE_DELAY = conf(
    "spark.rapids.network.retry.baseDelay").doc(
    "First backoff delay in seconds for network retry budgets; doubles per "
    "retry up to spark.rapids.network.retry.maxDelay."
).double_conf(0.05)

NETWORK_RETRY_MAX_DELAY = conf(
    "spark.rapids.network.retry.maxDelay").doc(
    "Upper bound in seconds on one network-retry backoff sleep."
).double_conf(2.0)

PEER_EXCLUDE_AFTER_FAILURES = conf(
    "spark.rapids.shuffle.peer.excludeAfterFailures").doc(
    "Budget-exhausted fetch failures reported against one peer before the "
    "heartbeat registry excludes it from the live view (a fresh register() "
    "clears the record and re-admits a genuinely restarted executor)."
).int_conf(3)

SHUFFLE_REPLICATION_FACTOR = conf(
    "spark.rapids.shuffle.replication.factor").doc(
    "Copies of each map-output block kept across the cluster (1 = primary "
    "only, no replication). After a map task commits its blocks, they are "
    "asynchronously pushed to factor-1 peers chosen by a rendezvous hash "
    "and announced to the heartbeat registry's replica catalog; reduce "
    "reads fail over to a replica on peer loss or persistent corruption, "
    "so losing an executor costs a re-fetch instead of a re-execution "
    "(the reference's shuffle data surviving its producer, "
    "RapidsShuffleManager block catalog)."
).int_conf(1)

SHUFFLE_PERSIST_DIR = conf(
    "spark.rapids.shuffle.replication.persistDir").doc(
    "Spill-backed map-output persistence: when set, every block put into "
    "the local BlockStore is also written under this directory (with its "
    "CRC), and a restarted executor with the same directory re-serves "
    "them from disk. The durability fallback when replication.factor is "
    "1 (no peers to replicate to). Empty disables persistence."
).string_conf("")

CLUSTER_DRAIN_TIMEOUT = conf("spark.rapids.cluster.drain.timeout").doc(
    "Seconds a graceful executor leave may spend draining: waiting for "
    "pending replications and re-replicating its primary map-output "
    "blocks to surviving peers before deregistering. Exceeding the bound "
    "leaves anyway (the scoped-recovery path then covers any reads its "
    "departure orphaned)."
).double_conf(30.0)

CLUSTER_SPECULATION_ENABLED = conf(
    "spark.rapids.cluster.speculation.enabled").doc(
    "Speculative re-dispatch of straggler tasks: the driver compares each "
    "running task's elapsed time against a quantile of completed-task "
    "durations and launches ONE speculative copy on an idle executor past "
    "the threshold; whichever attempt's map outputs commit first wins "
    "(first-commit-wins at the registry; the loser's blocks are dropped "
    "by attempt id)."
).boolean_conf(False)

CLUSTER_SPECULATION_QUANTILE = conf(
    "spark.rapids.cluster.speculation.quantile").doc(
    "Quantile of completed-task durations used as the speculation "
    "baseline (0.5 = median, like Spark's speculation.quantile role)."
).double_conf(0.5)

CLUSTER_SPECULATION_MULTIPLIER = conf(
    "spark.rapids.cluster.speculation.multiplier").doc(
    "A running task is a straggler when its elapsed time exceeds "
    "multiplier x the baseline quantile of completed-task durations."
).double_conf(2.0)

CLUSTER_SPECULATION_MIN_TASKS = conf(
    "spark.rapids.cluster.speculation.minTasks").doc(
    "Completed tasks required before the duration baseline is considered "
    "meaningful; no speculation happens below this count."
).int_conf(2)

CLUSTER_QUERY_DEADLINE = conf("spark.rapids.cluster.query.deadline").doc(
    "Per-query wall-clock deadline in seconds across ALL driver "
    "resubmission attempts (executor loss, retryable task failures). "
    "Exhaustion raises RetryBudgetExhausted naming the query's budget "
    "instead of hanging."
).double_conf(600.0)

SHUFFLE_COMPLETENESS_TIMEOUT = conf(
    "spark.rapids.shuffle.completenessTimeout").doc(
    "Seconds a cross-process reduce read waits for every declared map "
    "participant before failing (the MapOutputTracker wait bound; lost "
    "executors surface as this timeout on surviving ranks)."
).double_conf(120.0)

SHUFFLE_FETCH_MAX_INFLIGHT = conf(
    "spark.rapids.shuffle.fetch.maxInflightBytes").doc(
    "Receive-side flow-control window: at most this many bytes of "
    "requested-but-unconsumed shuffle blocks are outstanding per reduce "
    "read (the BufferSendState/WindowedBlockIterator bounce-buffer bound "
    "in the reference, shuffle/BufferSendState.scala); together with the "
    "streaming merge it keeps reduce-side memory bounded at any fan-in."
).bytes_conf(64 << 20)

SHUFFLE_FETCH_THREADS = conf(
    "spark.rapids.shuffle.fetch.threads").doc(
    "Concurrent fetch round-trips per reduce read ACROSS peers: the "
    "pipelined fetch runs one prefetch thread per peer, each serialized "
    "on its pooled connection (per-peer parallelism comes from batching "
    "many blocks per requestBytes round-trip, not parallel sockets); "
    "this caps how many of those round-trips run at once."
).int_conf(4)

SHUFFLE_FETCH_REQUEST_BYTES = conf(
    "spark.rapids.shuffle.fetch.requestBytes").doc(
    "Byte budget per fetch_many round-trip on the binary hot path: the "
    "per-peer prefetcher batches this many bytes of blocks into ONE "
    "request so small map-side slices amortize the network round-trip "
    "(the reference's BufferSendState packs bounce buffers the same way)."
).bytes_conf(4 << 20)

SHUFFLE_FETCH_MERGE_BYTES = conf(
    "spark.rapids.shuffle.fetch.mergeChunkBytes").doc(
    "Streaming reduce reads deserialize+merge fetched wire blocks into "
    "device batches once this many bytes accumulate, releasing the wire "
    "buffers — bounding resident reduce memory to window + chunk instead "
    "of the whole partition."
).bytes_conf(32 << 20)

DIAG_DUMP_DIR = conf("spark.rapids.diagnostics.dumpDir").doc(
    "Directory for crash/diagnostic bundles (the GpuCoreDumpHandler "
    "analog, reference GpuCoreDumpHandler.scala:38): fatal executor "
    "errors write a compressed bundle of thread stacks, device state, "
    "config and recent trace ranges here.  Empty disables capture."
).string_conf("")

MEMORY_LEAK_AUDIT = conf("spark.rapids.memory.debug.leakAudit").doc(
    "Track every spillable handle's creation stack and expose "
    "SpillFramework.assert_no_leaks() / leaked_handles(); unclosed "
    "handles also warn at interpreter exit.  The reference's leak "
    "tracking analog (cuDF MemoryCleaner refcount discipline, "
    "docs/dev/mem_debug.md; spark.rapids.memory.gpu.debug "
    "RapidsConf.scala:393).  Debug-only: stack capture costs ~us per "
    "handle."
).boolean_conf(False)

SANITIZER_ENABLED = conf("spark.rapids.sanitizer.enabled").doc(
    "Arm the runtime contract sanitizer (utils/sanitizer.py), the "
    "dynamic twin of tpulint's static rules: a per-query pin ledger "
    "asserting zero balance and zero tenant-ledger residue at query "
    "teardown (naming the acquiring stack), lock-acquisition-order "
    "witnessing checked against the static lock graph, ambient "
    "integrity asserts at every blessed-spawn target entry, and "
    "jax.transfer_guard around hot-path sections.  The environment "
    "variable SPARK_RAPIDS_TPU_SANITIZE=1 forces this on regardless of "
    "the conf (how tools/run_suites.py arms whole suites).  Debug-only: "
    "stack capture per pin and wrapped locks cost real time."
).boolean_conf(False)

SANITIZER_COMPILE_BUDGET = conf("spark.rapids.sanitizer.compileBudget").doc(
    "With the sanitizer armed: maximum DISTINCT XLA programs "
    "(shared_jit cache misses, the launch-profile 'programs' metric) "
    "the process may compile; exceeding it raises naming the newest "
    "program key.  Catches plan-key regressions that recompile per "
    "query (an id() or timestamp leaking into a key).  0 = unlimited.  "
    "The environment variable SPARK_RAPIDS_TPU_SANITIZE_COMPILE_BUDGET "
    "overrides (per-suite budgets in tools/run_suites.py)."
).int_conf(0)

PYTHON_WORKER_ENABLED = conf("spark.rapids.python.worker.enabled").doc(
    "Run pandas/Arrow UDFs in separate reusable worker processes (the "
    "GPU-aware PySpark worker analog, reference python/rapids/daemon.py): "
    "crash isolation + per-worker memory rlimit; functions ship via "
    "cloudpickle, data as Arrow IPC.  Off = in-process evaluation."
).boolean_conf(False)

PYTHON_WORKER_COUNT = conf(
    "spark.rapids.python.concurrentPythonWorkers").doc(
    "Size of the Python UDF worker pool (same key as the reference's "
    "gate on concurrent Python workers)."
).int_conf(2)

PYTHON_WORKER_MEM = conf("spark.rapids.python.memory.maxBytes").doc(
    "Address-space rlimit applied in each Python UDF worker before user "
    "code runs (the memory.gpu.allocFraction analog for host memory; "
    "0 = unlimited)."
).bytes_conf(0)

TEST_INJECT_RETRY_OOM = conf("spark.rapids.sql.test.injectRetryOOM").doc(
    "Fault injection: make the allocator throw synthetic retry OOMs "
    "(reference: RapidsConf.scala:3041-3083, used by the @inject_oom pytest "
    "marker). Format: true|false or 'count:N' to throw on the Nth allocation."
).string_conf("false")

HYBRID_PARQUET_ENABLED = conf("spark.rapids.sql.hybrid.parquet.enabled").doc(
    "Decode parquet through the Arrow Dataset (Acero) streaming scanner "
    "instead of the per-row-group reader — the analog of the reference's "
    "velox-backed hybrid CPU scan (hybrid/ module): a different native "
    "decode engine behind the same scan exec."
).boolean_conf(False)

FILECACHE_ENABLED = conf("spark.rapids.filecache.enabled").doc(
    "Cache scan input files on local disk, keyed by path+mtime+size with "
    "LRU eviction (reference: filecache/FileCache.scala — remote scan "
    "bytes land once per host; repeat scans hit local storage)."
).boolean_conf(False)

FILECACHE_DIR = conf("spark.rapids.filecache.dir").doc(
    "Directory for cached scan files."
).string_conf("/tmp/spark_rapids_tpu_filecache")

FILECACHE_MAX_BYTES = conf("spark.rapids.filecache.maxBytes").doc(
    "LRU size bound for the file cache."
).bytes_conf(8 << 30)

OPTIMIZER_ENABLED = conf("spark.rapids.sql.optimizer.enabled").doc(
    "Enable the cost-based optimizer: device-capable plan sections fall "
    "back to CPU when estimated device cost (incl. transitions) exceeds "
    "the CPU cost (reference: CostBasedOptimizer.scala)."
).boolean_conf(False)

OPTIMIZER_CPU_ROW_COST = conf(
    "spark.rapids.sql.optimizer.cpu.rowCost").doc(
    "CBO: cost units per row for a CPU operator."
).double_conf(1.0)

OPTIMIZER_TPU_ROW_COST = conf(
    "spark.rapids.sql.optimizer.tpu.rowCost").doc(
    "CBO: cost units per row for a device operator."
).double_conf(0.05)

OPTIMIZER_TPU_FIXED_COST = conf(
    "spark.rapids.sql.optimizer.tpu.fixedCost").doc(
    "CBO: fixed per-operator device cost (jit dispatch overhead)."
).double_conf(5000.0)

OPTIMIZER_TRANSITION_ROW_COST = conf(
    "spark.rapids.sql.optimizer.transition.rowCost").doc(
    "CBO: cost units per row crossing a CPU<->device boundary."
).double_conf(0.5)

DEVICE_MEMORY_LIMIT = conf("spark.rapids.memory.tpu.allocFraction").doc(
    "Fraction of HBM the arena may use (reference: GpuDeviceManager RMM pool "
    "sizing)."
).double_conf(0.85)

HOST_SPILL_STORAGE_SIZE = conf("spark.rapids.memory.host.spillStorageSize").doc(
    "Max host memory for spilled device buffers before cascading to disk "
    "(reference: SpillableHostStore limit, SpillFramework.scala:1482)."
).bytes_conf(1 << 30)

RETRY_MAX_ATTEMPTS = conf("spark.rapids.sql.retry.maxAttempts").doc(
    "Upper bound on OOM/capacity retries before the task fails."
).int_conf(8)

METRICS_LEVEL = conf("spark.rapids.sql.metrics.level").doc(
    "ESSENTIAL, MODERATE or DEBUG (reference: GpuMetrics.scala:89)."
).string_conf("MODERATE")

CPU_BRIDGE_ENABLED = conf("spark.rapids.sql.expression.cpuBridge.enabled").doc(
    "Allow unsupported expressions to run on CPU inside a TPU plan via the "
    "row bridge (reference: GpuCpuBridgeExpression.scala)."
).boolean_conf(True)

IMPROVED_FLOAT_OPS = conf("spark.rapids.sql.variableFloatAgg.enabled").doc(
    "Permit float/double aggregations whose result can differ from CPU Spark "
    "in last-bit rounding due to parallel reduction order."
).boolean_conf(True)

MAX_READER_BATCH_SIZE_ROWS = conf("spark.rapids.sql.reader.batchSizeRows").doc(
    "Soft cap on rows per batch produced by file readers.  Applied as "
    "min() with spark.rapids.sql.batchSizeRows at scan planning, so a "
    "reader-specific cap can shrink scan batches without touching the "
    "pipeline-wide batch size (reference: GpuParquetScan maxReadBatch"
    "SizeRows)."
).int_conf(1 << 20)

MULTITHREAD_READ_NUM_THREADS = conf("spark.rapids.sql.multiThreadedRead.numThreads").doc(
    "Thread pool size for the multi-file cloud reader (reference: "
    "GpuMultiFileReader.scala)."
).int_conf(8)

READER_BATCH_SIZE_BYTES = conf("spark.rapids.sql.reader.batchSizeBytes").doc(
    "Soft cap on decoded bytes per scan batch: the chunked-reader bound "
    "that keeps one scan's device footprint independent of file size "
    "(reference: GpuParquetScan.scala:2523 chunked reader)."
).int_conf(128 << 20)

PARQUET_COALESCE_RANGES = conf(
    "spark.rapids.sql.format.parquet.rangeCoalescing.enabled").doc(
    "Plan the pruned row groups' column-chunk byte ranges from the footer "
    "and read them as few merged I/O requests (the object-store range "
    "coalescing of S3InputFile.readVectored / fileio/hadoop)."
).boolean_conf(False)

ASYNC_WRITE_MAX_INFLIGHT = conf(
    "spark.rapids.sql.asyncWrite.maxInFlightBytes").doc(
    "Byte budget of encode/write work allowed in flight behind the device "
    "loop; 0 writes synchronously (reference: io/async/AsyncOutputStream"
    ".scala + ThrottlingExecutor.scala)."
).int_conf(256 << 20)

LORE_DUMP_IDS = conf("spark.rapids.sql.lore.idsToDump").doc(
    "LORE-style debug replay: comma-separated exec ids (see explain() "
    "output, [loreId=N]) whose OUTPUT batches are dumped as parquet for "
    "offline replay via tools/lore_replay.py (reference: lore/)."
).string_conf(None)

LORE_DUMP_PATH = conf("spark.rapids.sql.lore.dumpPath").doc(
    "Directory receiving LORE batch dumps (one subdir per exec id)."
).string_conf("/tmp/spark_rapids_tpu_lore")

SERVING_MAX_CONCURRENT = conf("spark.rapids.serving.maxConcurrentQueries").doc(
    "Queries allowed past admission control at once (the serving-layer "
    "slot bound; serving/admission.py QueryQueue). Waiters queue in "
    "priority-then-FIFO order behind a WeightedPrioritySemaphore — the "
    "same wake discipline as the device semaphore."
).int_conf(4)

SERVING_QUEUE_MAX_DEPTH = conf("spark.rapids.serving.queue.maxDepth").doc(
    "Queries allowed to WAIT for admission; one more is rejected "
    "immediately with AdmissionRejected(queue_full) — bounded "
    "backpressure instead of unbounded buffering under overload."
).int_conf(32)

SERVING_QUEUE_TIMEOUT = conf("spark.rapids.serving.queue.timeout").doc(
    "Seconds one query may wait for admission before it is rejected "
    "with AdmissionRejected(timeout)."
).double_conf(30.0)

SERVING_ADMISSION_MEMORY_FRACTION = conf(
    "spark.rapids.serving.admission.memoryFraction").doc(
    "Memory-aware admission: fraction of the device arena's byte budget "
    "admitted queries may collectively claim (each query reserves its "
    "estimated bytes, spark.rapids.serving.admission.queryBytes by "
    "default). With an unbudgeted arena, admission is slot-only."
).double_conf(0.6)

SERVING_ADMISSION_QUERY_BYTES = conf(
    "spark.rapids.serving.admission.queryBytes").doc(
    "Default per-query device-byte estimate the admission controller "
    "reserves when submit() does not declare one; estimates above the "
    "admission budget clamp to it (the query runs alone)."
).bytes_conf(64 << 20)

SERVING_CACHE_ENABLED = conf("spark.rapids.serving.cache.enabled").doc(
    "Serve repeated identical plans from the fingerprint-keyed result "
    "cache (serving/cache.py): a hit returns without admission or task "
    "dispatch; file sources fold (mtime, size) into the key so changed "
    "data misses, and invalidate_source() drops entries explicitly."
).boolean_conf(True)

SERVING_CACHE_MAX_BYTES = conf("spark.rapids.serving.cache.maxBytes").doc(
    "LRU size bound of the serving result cache (pickled payload "
    "bytes)."
).bytes_conf(256 << 20)

SERVING_CACHE_TTL = conf("spark.rapids.serving.cache.ttl").doc(
    "Seconds a cached result stays servable; 0 disables expiry (source "
    "invalidation still applies)."
).double_conf(0.0)

SERVING_TENANT_DEFAULT_BUDGET = conf(
    "spark.rapids.serving.tenant.defaultBudgetBytes").doc(
    "Device-byte budget for tenants not named in "
    "spark.rapids.serving.tenants; 0 = unlimited. Exceeding a tenant "
    "budget spills that tenant's own handles then raises a retryable "
    "TenantBudgetExceeded into its own task — never a neighbor's "
    "(memory/tenant.py)."
).bytes_conf(0)

SERVING_TENANT_DEFAULT_WEIGHT = conf(
    "spark.rapids.serving.tenant.defaultWeight").doc(
    "Spill weight for tenants not named in spark.rapids.serving.tenants "
    "(and for untagged allocations): under GLOBAL arena pressure, "
    "lighter tenants' handles spill before heavier ones."
).double_conf(1.0)

SERVING_QUERY_DEADLINE = conf("spark.rapids.serving.query.deadline").doc(
    "Per-query EXECUTION deadline in seconds for serving submissions "
    "(0 = none): QueryQueue.submit derives each query's CancelToken "
    "from it, so a runaway query self-cancels at its next batch "
    "boundary or blessed wait with a typed QueryCancelled instead of "
    "running to completion holding admission slots and tenant bytes "
    "(utils/cancel.py)."
).double_conf(0.0)

SERVING_QUERY_TENANT = conf("spark.rapids.serving.query.tenant").doc(
    "Per-query tenant tag carried from serving admission to cluster "
    "executors.  Set automatically by serving/admission.py "
    "ClusterDriverRunner on each submitted query's conf and read by "
    "cluster/executor.run_task to scope device-byte accounting; may "
    "also be set by hand to tag a standalone query.  The key string is "
    "mirrored as memory/tenant.py TENANT_CONF_KEY so the executor "
    "never imports the serving tier just for a string."
).string_conf(None)

WATCHDOG_STALL_SECONDS = conf("spark.rapids.watchdog.stallSeconds").doc(
    "Stall watchdog threshold in seconds (0 disables): every blessed "
    "blocking site registers its wait (utils/cancel.cancellable_wait), "
    "and a wait older than this bumps watchdog_stalls and writes a "
    "crashdump-style stall report of all registered waits + thread "
    "stacks (utils/watchdog.py) — a silent hang becomes an actionable, "
    "typed artifact.  It also bounds how long a serving single-flight "
    "follower with no spark.rapids.serving.query.deadline waits for a "
    "leader still running (spark.rapids.serving.queue.timeout when 0): "
    "past it the follower runs the query itself."
).double_conf(300.0)

WATCHDOG_CANCEL_ON_STALL = conf("spark.rapids.watchdog.cancelOnStall").doc(
    "When the stall watchdog flags a wait, also CANCEL the stalled "
    "query's token: the wedged query dies with QueryCancelled naming "
    "the stalled site and the server frees its slots, instead of "
    "wedging until operator intervention."
).boolean_conf(False)

SERVING_TENANTS = conf("spark.rapids.serving.tenants").doc(
    "Per-tenant budget/weight spec: "
    "'name:weight=2:budget=64m,name2:weight=1'. Unnamed tenants use the "
    "defaultBudgetBytes/defaultWeight knobs."
).string_conf("")

SERVING_OVERLOAD_ENABLED = conf("spark.rapids.serving.overload.enabled").doc(
    "Arm the serving-layer overload protections (serving/overload.py): "
    "priority-aware load shedding when admission-wait p99 exceeds the "
    "SLO target, per-tenant token-bucket rate limits, and the per-plan-"
    "fingerprint circuit breaker.  Off (the default) no overload state "
    "is constructed and the submit path is byte-identical to the "
    "pre-overload behavior."
).boolean_conf(False)

SERVING_OVERLOAD_SLO_P99 = conf(
    "spark.rapids.serving.overload.sloP99Seconds").doc(
    "Admission-wait p99 SLO target in seconds: when the windowed p99 "
    "of admission_wait_s exceeds it, the shedder starts rejecting "
    "shed-eligible submissions with AdmissionRejected(shed) instead of "
    "letting every tenant's tail latency grow unboundedly."
).double_conf(2.0)

SERVING_OVERLOAD_SHED_WINDOW = conf(
    "spark.rapids.serving.overload.shedWindowSeconds").doc(
    "Sliding window in seconds over which the shedder computes the "
    "admission-wait p99 it compares against sloP99Seconds."
).double_conf(30.0)

SERVING_OVERLOAD_SHED_PRIORITY_FLOOR = conf(
    "spark.rapids.serving.overload.shedPriorityFloor").doc(
    "Only submissions at this priority or WORSE (priority is lower-"
    "first, so numerically >= floor) are shed-eligible: latency-"
    "critical work above the floor rides through an overload un-shed."
).int_conf(1)

SERVING_OVERLOAD_SHED_GUARANTEE = conf(
    "spark.rapids.serving.overload.shedGuaranteeSeconds").doc(
    "Anti-starvation bound: a tenant that has had no admitted "
    "submission within this many seconds is exempt from shedding — "
    "under sustained overload every tenant still makes progress at a "
    "trickle instead of the lowest-priority tenant starving to zero."
).double_conf(10.0)

SERVING_OVERLOAD_RATELIMIT_QPS = conf(
    "spark.rapids.serving.overload.ratelimitQps").doc(
    "Per-tenant token-bucket refill rate in submissions/second (0 = "
    "no rate limit).  A tenant submitting faster than its bucket "
    "refills is rejected with AdmissionRejected(ratelimited) before "
    "admission — abusive arrival rates never reach the queue."
).double_conf(0.0)

SERVING_OVERLOAD_RATELIMIT_BURST = conf(
    "spark.rapids.serving.overload.ratelimitBurst").doc(
    "Token-bucket capacity per tenant: bursts up to this many "
    "submissions pass before the ratelimitQps refill rate governs."
).int_conf(10)

SERVING_OVERLOAD_BREAKER_FAILURES = conf(
    "spark.rapids.serving.overload.breakerFailures").doc(
    "Consecutive failures of one plan fingerprint after which its "
    "circuit breaker OPENS: further identical submissions fail fast "
    "with AdmissionRejected(breaker) instead of re-burning cluster "
    "capacity on a query that keeps crashing."
).int_conf(3)

SERVING_OVERLOAD_BREAKER_RESET = conf(
    "spark.rapids.serving.overload.breakerResetSeconds").doc(
    "Seconds an OPEN breaker waits before HALF-OPEN: one probe "
    "submission is let through — success closes the breaker, failure "
    "re-opens it for another reset interval."
).double_conf(30.0)

AUTOSCALE_ENABLED = conf("spark.rapids.autoscale.enabled").doc(
    "Arm the elasticity control loop (cluster/autoscaler.py): a policy "
    "daemon consumes the telemetry rings (admission queue depth, "
    "admission-wait p99, arena pressure) and drives executor launches "
    "and graceful drains within [minExecutors, maxExecutors].  Off "
    "(the default) no daemon runs and cluster behavior is byte-"
    "identical to the pre-autoscaler loop."
).boolean_conf(False)

AUTOSCALE_MIN_EXECUTORS = conf("spark.rapids.autoscale.minExecutors").doc(
    "Lower capacity bound: scale-in never drains below this many "
    "available executors."
).int_conf(1)

AUTOSCALE_MAX_EXECUTORS = conf("spark.rapids.autoscale.maxExecutors").doc(
    "Upper capacity bound: scale-out never launches past this many "
    "executors counting available AND pending (launched, not yet "
    "joined) ranks."
).int_conf(8)

AUTOSCALE_INTERVAL_MS = conf("spark.rapids.autoscale.intervalMs").doc(
    "Autoscaler policy tick period in milliseconds (min 50)."
).int_conf(500)

AUTOSCALE_QUEUE_DEPTH_HIGH = conf(
    "spark.rapids.autoscale.queueDepthHigh").doc(
    "Scale-out trigger: admission queue depth (queries WAITING for a "
    "slot, from the telemetry ring) at or above this breaches the "
    "policy's pressure threshold."
).int_conf(4)

AUTOSCALE_WAIT_P99_HIGH = conf(
    "spark.rapids.autoscale.admissionWaitP99High").doc(
    "Scale-out trigger: windowed admission-wait p99 in seconds (from "
    "the admission_wait_s histogram bucket deltas across the telemetry "
    "ring) above this breaches the policy's pressure threshold."
).double_conf(1.0)

AUTOSCALE_ARENA_PRESSURE_HIGH = conf(
    "spark.rapids.autoscale.arenaPressureHigh").doc(
    "Scale-out trigger: arena_used_bytes/arena_budget_bytes above this "
    "fraction (on a budgeted arena) breaches the policy's pressure "
    "threshold — memory pressure scales out before queue depth shows "
    "it."
).double_conf(0.9)

AUTOSCALE_SCALE_OUT_STEP = conf("spark.rapids.autoscale.scaleOutStep").doc(
    "Executors launched per scale-out decision (bounded by "
    "maxExecutors minus available+pending capacity)."
).int_conf(1)

AUTOSCALE_UP_COOLDOWN = conf(
    "spark.rapids.autoscale.upCooldownSeconds").doc(
    "Minimum seconds between scale-out decisions: launched capacity "
    "gets time to join and absorb load before the policy re-evaluates "
    "(hysteresis against launch stampedes)."
).double_conf(10.0)

AUTOSCALE_DOWN_COOLDOWN = conf(
    "spark.rapids.autoscale.downCooldownSeconds").doc(
    "Minimum seconds between scale-in decisions (drains are deliberate "
    "and rare: each one re-replicates the rank's blocks)."
).double_conf(30.0)

AUTOSCALE_IDLE_SECONDS = conf("spark.rapids.autoscale.idleSeconds").doc(
    "Scale-in trigger: the cluster must show ZERO admission pressure "
    "(empty queue, no breach) continuously for this many seconds "
    "before one rank is drained — momentary idleness never scales in."
).double_conf(20.0)

AUTOSCALE_FLAP_SECONDS = conf("spark.rapids.autoscale.flapSeconds").doc(
    "Flap suppression: minimum seconds between OPPOSITE-direction "
    "decisions (a scale-out forbids any scale-in for this long and "
    "vice versa), so oscillating load can't thrash launch/drain "
    "cycles."
).double_conf(60.0)

AUTOSCALE_JOIN_TIMEOUT = conf(
    "spark.rapids.autoscale.joinTimeoutSeconds").doc(
    "Seconds a launched executor may take to register before its "
    "PENDING capacity expires: a slow join holds its slot (no second "
    "redundant scale-out, chaos site cluster.join.delay) until this "
    "bound, after which the policy may launch a replacement."
).double_conf(30.0)

AUTOSCALE_JOIN_RETRIES = conf("spark.rapids.autoscale.joinRetries").doc(
    "Launch attempts per scale-out decision under the named "
    "cluster.join RetryBudget (chaos site cluster.join.fail): a failed "
    "spawn retries with backoff instead of silently shrinking the "
    "decision."
).int_conf(3)

TRACE_ENABLED = conf("spark.rapids.trace.enabled").doc(
    "Arm the query-scoped observability plane (utils/obs.py): every "
    "serving/cluster submission runs under a QueryTrace ambient that "
    "collects named spans (trace ranges), tees ShuffleCounters deltas "
    "into a per-query counter scope, and — on the cluster path — ships "
    "the trace context with each task so executors return task-side "
    "spans and per-exec metric snapshots the driver merges under the "
    "originating query with rank/attempt tags.  Off (the default) the "
    "tee is a single thread-local read per counter add: ~zero overhead."
).boolean_conf(False)

TRACE_DIR = conf("spark.rapids.trace.dir").doc(
    "Directory for per-query Perfetto/Chrome-trace JSON exports "
    "(tools/trace_export.py): when set (and tracing is enabled), each "
    "serving/driver submission writes <dir>/query_<id>.trace.json — a "
    "timeline spanning serving admission, driver dispatch, per-rank "
    "task spans and shuffle fetch/pipeline producer spans, loadable in "
    "ui.perfetto.dev or chrome://tracing.  Empty disables export."
).string_conf("")

TRACE_MAX_SPANS = conf("spark.rapids.trace.maxSpans").doc(
    "Per-query span-buffer bound: spans past it are dropped (and "
    "counted in the trace's dropped_spans) so a long query can never "
    "grow an unbounded buffer on the serving path.  Executor task "
    "traces use the same bound, shipped with the trace context."
).int_conf(4096)

METRICS_ENABLED = conf("spark.rapids.metrics.enabled").doc(
    "Arm the continuous resource-plane sampler (utils/telemetry.py): a "
    "daemon snapshots arena/spill/semaphore/admission/in-flight gauges "
    "plus the cumulative counters into a bounded ring every intervalMs, "
    "executors piggyback their latest sample on the heartbeat for the "
    "driver's per-rank rings, and tools/metrics_scrape.py renders the "
    "cluster state as Prometheus text.  Off, no daemon samples and the "
    "cost is zero (the flight recorder's event log stays on either "
    "way)."
).boolean_conf(True)

METRICS_INTERVAL_MS = conf("spark.rapids.metrics.intervalMs").doc(
    "Resource-plane sampling period in milliseconds (min 10).  One "
    "sample is a handful of lock-guarded gauge reads — no device sync, "
    "no I/O — measured within noise on the reduce-fetch micro-bench at "
    "the default."
).int_conf(250)

METRICS_RING_SECONDS = conf("spark.rapids.metrics.ringSeconds").doc(
    "Seconds of samples the telemetry ring retains (bounds the ring at "
    "ringSeconds*1000/intervalMs samples).  The ring is what flight-"
    "recorder post-mortems dump and bench timeline summaries read."
).int_conf(60)

TEST_RETRY_CONTEXT_CHECK = conf("spark.rapids.sql.test.retryContextCheck.enabled").doc(
    "Assert that every device allocation site is covered by a retry block "
    "(reference: AllocationRetryCoverageTracker.scala)."
).boolean_conf(False)


class RapidsConf:
    """Immutable snapshot of the conf map, with typed accessors."""

    def __init__(self, conf_map: Optional[Dict[str, Any]] = None):
        self._map: Dict[str, Any] = dict(conf_map or {})

    def get(self, entry: ConfEntry[T]) -> T:
        return entry.get(self._map)

    def raw(self, key: str, default: Optional[str] = None):
        return self._map.get(key, default)

    # Convenience accessors used throughout the engine.
    @property
    def sql_enabled(self) -> bool:
        return self.get(SQL_ENABLED)

    @property
    def explain(self) -> str:
        return (self.get(EXPLAIN) or "NONE").upper()

    @property
    def batch_size_rows(self) -> int:
        return self.get(BATCH_SIZE_ROWS)

    @property
    def batch_size_bytes(self) -> int:
        return self.get(BATCH_SIZE_BYTES)

    @property
    def shuffle_partitions(self) -> int:
        return self.get(SHUFFLE_PARTITIONS)

    @property
    def profile_enabled(self) -> bool:
        return self.get(PROFILE_ENABLED)

    @property
    def profile_dir(self) -> str:
        return self.get(PROFILE_DIR)

    @property
    def aqe_coalesce_partitions(self) -> bool:
        return self.get(AQE_COALESCE_PARTITIONS)

    @property
    def shuffle_mode(self) -> str:
        return (self.get(SHUFFLE_MODE) or "MULTITHREADED").upper()

    @property
    def broadcast_row_threshold(self) -> int:
        return self.get(BROADCAST_ROW_THRESHOLD)

    @property
    def join_adaptive_enabled(self) -> bool:
        return self.get(JOIN_ADAPTIVE_ENABLED)

    @property
    def shuffle_completeness_timeout(self) -> float:
        return self.get(SHUFFLE_COMPLETENESS_TIMEOUT)

    @property
    def shuffle_checksum_enabled(self) -> bool:
        return self.get(SHUFFLE_CHECKSUM_ENABLED)

    @property
    def spill_checksum_enabled(self) -> bool:
        return self.get(SPILL_CHECKSUM_ENABLED)

    @property
    def sanitizer_enabled(self) -> bool:
        return self.get(SANITIZER_ENABLED)

    @property
    def sanitizer_compile_budget(self) -> int:
        return self.get(SANITIZER_COMPILE_BUDGET)

    @property
    def network_retry_max_attempts(self) -> int:
        return self.get(NETWORK_RETRY_MAX_ATTEMPTS)

    @property
    def network_retry_base_delay(self) -> float:
        return self.get(NETWORK_RETRY_BASE_DELAY)

    @property
    def network_retry_max_delay(self) -> float:
        return self.get(NETWORK_RETRY_MAX_DELAY)

    @property
    def peer_exclude_after_failures(self) -> int:
        return self.get(PEER_EXCLUDE_AFTER_FAILURES)

    @property
    def cluster_query_deadline(self) -> float:
        return self.get(CLUSTER_QUERY_DEADLINE)

    @property
    def shuffle_replication_factor(self) -> int:
        return self.get(SHUFFLE_REPLICATION_FACTOR)

    @property
    def shuffle_persist_dir(self) -> str:
        return self.get(SHUFFLE_PERSIST_DIR) or ""

    @property
    def cluster_drain_timeout(self) -> float:
        return self.get(CLUSTER_DRAIN_TIMEOUT)

    @property
    def speculation_enabled(self) -> bool:
        return self.get(CLUSTER_SPECULATION_ENABLED)

    @property
    def speculation_quantile(self) -> float:
        return self.get(CLUSTER_SPECULATION_QUANTILE)

    @property
    def speculation_multiplier(self) -> float:
        return self.get(CLUSTER_SPECULATION_MULTIPLIER)

    @property
    def speculation_min_tasks(self) -> int:
        return self.get(CLUSTER_SPECULATION_MIN_TASKS)

    @property
    def shuffle_fetch_max_inflight(self) -> int:
        return self.get(SHUFFLE_FETCH_MAX_INFLIGHT)

    @property
    def shuffle_fetch_threads(self) -> int:
        return self.get(SHUFFLE_FETCH_THREADS)

    @property
    def shuffle_fetch_merge_bytes(self) -> int:
        return self.get(SHUFFLE_FETCH_MERGE_BYTES)

    @property
    def shuffle_fetch_request_bytes(self) -> int:
        return self.get(SHUFFLE_FETCH_REQUEST_BYTES)

    @property
    def diag_dump_dir(self) -> str:
        return self.get(DIAG_DUMP_DIR) or ""

    @property
    def python_worker_enabled(self) -> bool:
        return self.get(PYTHON_WORKER_ENABLED)

    @property
    def python_worker_count(self) -> int:
        return self.get(PYTHON_WORKER_COUNT)

    @property
    def python_worker_mem(self) -> int:
        return self.get(PYTHON_WORKER_MEM)

    @property
    def shuffle_writer_threads(self) -> int:
        return self.get(SHUFFLE_WRITER_THREADS)

    @property
    def shuffle_reader_threads(self) -> int:
        return self.get(SHUFFLE_READER_THREADS)

    @property
    def shuffle_codec(self) -> str:
        return (self.get(SHUFFLE_COMPRESSION_CODEC) or "none").lower()

    @property
    def concurrent_tpu_tasks(self) -> int:
        return self.get(CONCURRENT_TPU_TASKS)

    @property
    def fuse_stages(self) -> bool:
        return self.get(STAGE_FUSION)

    @property
    def multithreaded_read_threads(self) -> int:
        return self.get(MULTITHREAD_READ_NUM_THREADS)

    @property
    def metrics_level(self) -> str:
        return (self.get(METRICS_LEVEL) or "MODERATE").upper()

    @property
    def hybrid_parquet_enabled(self) -> bool:
        return self.get(HYBRID_PARQUET_ENABLED)

    @property
    def filecache_enabled(self) -> bool:
        return self.get(FILECACHE_ENABLED)

    @property
    def filecache_dir(self) -> str:
        return self.get(FILECACHE_DIR)

    @property
    def filecache_max_bytes(self) -> int:
        return self.get(FILECACHE_MAX_BYTES)

    @property
    def optimizer_enabled(self) -> bool:
        return self.get(OPTIMIZER_ENABLED)

    @property
    def optimizer_cpu_row_cost(self) -> float:
        return self.get(OPTIMIZER_CPU_ROW_COST)

    @property
    def optimizer_tpu_row_cost(self) -> float:
        return self.get(OPTIMIZER_TPU_ROW_COST)

    @property
    def optimizer_tpu_fixed_cost(self) -> float:
        return self.get(OPTIMIZER_TPU_FIXED_COST)

    @property
    def optimizer_transition_row_cost(self) -> float:
        return self.get(OPTIMIZER_TRANSITION_ROW_COST)

    @property
    def variable_float_agg_enabled(self) -> bool:
        return self.get(IMPROVED_FLOAT_OPS)

    @property
    def lore_dump_ids(self):
        raw = self.get(LORE_DUMP_IDS)
        if not raw:
            return set()
        return {int(x) for x in str(raw).split(",") if x.strip()}

    @property
    def lore_dump_path(self) -> str:
        return self.get(LORE_DUMP_PATH)

    @property
    def retry_context_check(self) -> bool:
        return self.get(TEST_RETRY_CONTEXT_CHECK)

    @property
    def reader_batch_size_rows(self) -> int:
        return self.get(MAX_READER_BATCH_SIZE_ROWS)

    @property
    def reader_batch_size_bytes(self) -> int:
        return self.get(READER_BATCH_SIZE_BYTES)

    @property
    def parquet_coalesce_ranges(self) -> bool:
        return self.get(PARQUET_COALESCE_RANGES)

    @property
    def async_write_max_inflight(self) -> int:
        return self.get(ASYNC_WRITE_MAX_INFLIGHT)

    @property
    def retry_max_attempts(self) -> int:
        return self.get(RETRY_MAX_ATTEMPTS)

    @property
    def test_inject_retry_oom(self) -> str:
        v = self.get(TEST_INJECT_RETRY_OOM)
        return str(v) if v is not None else "false"

    @property
    def cpu_bridge_enabled(self) -> bool:
        return self.get(CPU_BRIDGE_ENABLED)

    @property
    def serving_max_concurrent(self) -> int:
        return self.get(SERVING_MAX_CONCURRENT)

    @property
    def serving_queue_max_depth(self) -> int:
        return self.get(SERVING_QUEUE_MAX_DEPTH)

    @property
    def serving_queue_timeout(self) -> float:
        return self.get(SERVING_QUEUE_TIMEOUT)

    @property
    def serving_admission_memory_fraction(self) -> float:
        return self.get(SERVING_ADMISSION_MEMORY_FRACTION)

    @property
    def serving_admission_query_bytes(self) -> int:
        return self.get(SERVING_ADMISSION_QUERY_BYTES)

    @property
    def serving_cache_enabled(self) -> bool:
        return self.get(SERVING_CACHE_ENABLED)

    @property
    def serving_cache_max_bytes(self) -> int:
        return self.get(SERVING_CACHE_MAX_BYTES)

    @property
    def serving_cache_ttl(self) -> float:
        return self.get(SERVING_CACHE_TTL)

    @property
    def serving_tenant_default_budget(self) -> int:
        return self.get(SERVING_TENANT_DEFAULT_BUDGET)

    @property
    def serving_tenant_default_weight(self) -> float:
        return self.get(SERVING_TENANT_DEFAULT_WEIGHT)

    @property
    def serving_tenants_spec(self) -> str:
        return self.get(SERVING_TENANTS) or ""

    @property
    def serving_query_deadline(self) -> float:
        return self.get(SERVING_QUERY_DEADLINE)

    @property
    def watchdog_stall_seconds(self) -> float:
        return self.get(WATCHDOG_STALL_SECONDS)

    @property
    def watchdog_cancel_on_stall(self) -> bool:
        return self.get(WATCHDOG_CANCEL_ON_STALL)

    @property
    def trace_enabled(self) -> bool:
        return self.get(TRACE_ENABLED)

    @property
    def trace_dir(self) -> str:
        return self.get(TRACE_DIR)

    @property
    def trace_max_spans(self) -> int:
        return self.get(TRACE_MAX_SPANS)

    @property
    def metrics_enabled(self) -> bool:
        return self.get(METRICS_ENABLED)

    @property
    def metrics_interval_ms(self) -> int:
        return self.get(METRICS_INTERVAL_MS)

    @property
    def metrics_ring_seconds(self) -> int:
        return self.get(METRICS_RING_SECONDS)

    @property
    def serving_overload_enabled(self) -> bool:
        return self.get(SERVING_OVERLOAD_ENABLED)

    @property
    def serving_overload_slo_p99(self) -> float:
        return self.get(SERVING_OVERLOAD_SLO_P99)

    @property
    def serving_overload_shed_window(self) -> float:
        return self.get(SERVING_OVERLOAD_SHED_WINDOW)

    @property
    def serving_overload_shed_priority_floor(self) -> int:
        return self.get(SERVING_OVERLOAD_SHED_PRIORITY_FLOOR)

    @property
    def serving_overload_shed_guarantee(self) -> float:
        return self.get(SERVING_OVERLOAD_SHED_GUARANTEE)

    @property
    def serving_overload_ratelimit_qps(self) -> float:
        return self.get(SERVING_OVERLOAD_RATELIMIT_QPS)

    @property
    def serving_overload_ratelimit_burst(self) -> int:
        return self.get(SERVING_OVERLOAD_RATELIMIT_BURST)

    @property
    def serving_overload_breaker_failures(self) -> int:
        return self.get(SERVING_OVERLOAD_BREAKER_FAILURES)

    @property
    def serving_overload_breaker_reset(self) -> float:
        return self.get(SERVING_OVERLOAD_BREAKER_RESET)

    @property
    def autoscale_enabled(self) -> bool:
        return self.get(AUTOSCALE_ENABLED)

    @property
    def autoscale_min_executors(self) -> int:
        return self.get(AUTOSCALE_MIN_EXECUTORS)

    @property
    def autoscale_max_executors(self) -> int:
        return self.get(AUTOSCALE_MAX_EXECUTORS)

    @property
    def autoscale_interval_ms(self) -> int:
        return self.get(AUTOSCALE_INTERVAL_MS)

    @property
    def autoscale_queue_depth_high(self) -> int:
        return self.get(AUTOSCALE_QUEUE_DEPTH_HIGH)

    @property
    def autoscale_wait_p99_high(self) -> float:
        return self.get(AUTOSCALE_WAIT_P99_HIGH)

    @property
    def autoscale_arena_pressure_high(self) -> float:
        return self.get(AUTOSCALE_ARENA_PRESSURE_HIGH)

    @property
    def autoscale_scale_out_step(self) -> int:
        return self.get(AUTOSCALE_SCALE_OUT_STEP)

    @property
    def autoscale_up_cooldown(self) -> float:
        return self.get(AUTOSCALE_UP_COOLDOWN)

    @property
    def autoscale_down_cooldown(self) -> float:
        return self.get(AUTOSCALE_DOWN_COOLDOWN)

    @property
    def autoscale_idle_seconds(self) -> float:
        return self.get(AUTOSCALE_IDLE_SECONDS)

    @property
    def autoscale_flap_seconds(self) -> float:
        return self.get(AUTOSCALE_FLAP_SECONDS)

    @property
    def autoscale_join_timeout(self) -> float:
        return self.get(AUTOSCALE_JOIN_TIMEOUT)

    @property
    def autoscale_join_retries(self) -> int:
        return self.get(AUTOSCALE_JOIN_RETRIES)

    def with_overrides(self, **kv) -> "RapidsConf":
        m = dict(self._map)
        m.update(kv)
        return RapidsConf(m)


def all_entries() -> List[ConfEntry]:
    return sorted(_REGISTRY.values(), key=lambda e: e.key)


def generate_config_docs() -> str:
    """Emit docs/configs.md the way the reference's RapidsConf markdown
    emitters do (reference: RapidsConf.scala doc generation)."""
    lines = [
        "# Configuration",
        "",
        "| Name | Description | Default |",
        "|------|-------------|---------|",
    ]
    for e in all_entries():
        if e.internal:
            continue
        default = "(none)" if e.default is None else str(e.default)
        doc = e.doc.replace("\n", " ")
        lines.append(f"| `{e.key}` | {doc} | {default} |")
    lines += ["", "## Query parameters and compiled programs", "",
              _LITERAL_RULE_DOC]
    return "\n".join(lines) + "\n"


_LITERAL_RULE_DOC = (
    "No key selects it: a numeric, date, timestamp or boolean literal of a "
    "projection or a filter, at the root of an expression or under a "
    "comparison, arithmetic, boolean logic or an alias, is an argument of "
    "the program that evaluates it (`expressions/core.py` "
    "`literal_is_argument`), and the program's key holds its type and "
    "position, not its value. The same query with other values (another "
    "year, discount or DELTA of a dashboard's query) runs the programs its "
    "first values compiled. A string, a null, a two-limb decimal, a literal "
    "under any other expression (which may read its value while the program "
    "is built: a scale, a count, a pattern, a sort direction), a LIMIT (the "
    "host's), and the literals of aggregate, join, sort and window "
    "expressions stay constants: another value there is another program. "
    "The serving result cache keys every value (`serving/cache.py` "
    "`plan_fingerprint`), so two values never share an answer.")


# -- session timezone ambient -------------------------------------------------
# Spark's spark.sql.session.timeZone: datetime field extraction and
# timestamp->date casts interpret instants in this zone.  Exposed as a
# process ambient (set around query execution by DataFrame.collect) because
# expression eval has no conf channel — the same shape as Spark's
# SQLConf.get session-local lookups.  shared_jit keys on it so compiled
# programs never leak across zones.

_SESSION_TZ = "UTC"


def current_session_timezone() -> str:
    return _SESSION_TZ


class session_timezone:
    """Context manager scoping the ambient session timezone."""

    def __init__(self, tz: str):
        self.tz = tz or "UTC"

    def __enter__(self):
        global _SESSION_TZ
        self._saved = _SESSION_TZ
        _SESSION_TZ = self.tz
        return self

    def __exit__(self, *exc):
        global _SESSION_TZ
        _SESSION_TZ = self._saved
        return False
