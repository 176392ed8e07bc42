"""Memory runtime: arena accounting, spill, retry-on-OOM, task gating.

The TPU analog of the reference's L1 device/memory runtime
(GpuDeviceManager, GpuSemaphore, SpillFramework, RmmRapidsRetryIterator —
see SURVEY.md §1 L1 and §3.5).
"""
from spark_rapids_tpu.memory.arena import (  # noqa: F401
    CpuRetryOOM,
    DeviceArena,
    TpuOOM,
    TpuRetryOOM,
    TpuSplitAndRetryOOM,
    device_arena,
)
from spark_rapids_tpu.memory.retry import (  # noqa: F401
    disable_oom_injection,
    enable_oom_injection,
    with_capacity_retry,
    with_retry,
    with_retry_no_split,
)
from spark_rapids_tpu.memory.semaphore import tpu_semaphore  # noqa: F401
from spark_rapids_tpu.memory.spill import (  # noqa: F401
    SpillableBatchHandle,
    SpillFramework,
    make_spillable,
    spill_framework,
)


def initialize_memory(conf) -> None:
    """Apply a RapidsConf snapshot to the memory runtime.

    Analog of the executor-plugin memory init (reference: Plugin.scala:657-690
    -> GpuDeviceManager.initializeGpuAndMemory): retry attempts, concurrent
    device tasks, host spill limit, and test OOM injection.
    """
    from spark_rapids_tpu import config as C
    from spark_rapids_tpu.memory import retry as _retry, semaphore as _sem

    _retry.MAX_RETRIES = conf.retry_max_attempts
    _sem.configure(conf.concurrent_tpu_tasks)
    spill_framework().host_limit_bytes = conf.get(C.HOST_SPILL_STORAGE_SIZE)
    from spark_rapids_tpu.memory.spill import set_leak_audit, \
        set_spill_checksum
    set_leak_audit(conf.get(C.MEMORY_LEAK_AUDIT))
    set_spill_checksum(conf.spill_checksum_enabled)
    # the runtime contract sanitizer rides the same conf snapshot as the
    # checksum knobs (utils/sanitizer.py; SPARK_RAPIDS_TPU_SANITIZE=1
    # forces it on regardless of the conf)
    from spark_rapids_tpu.utils.sanitizer import configure_sanitizer
    configure_sanitizer(conf.sanitizer_enabled,
                        conf.sanitizer_compile_budget)
    # integrity/recovery knobs of the shuffle data plane ride the same
    # conf snapshot (both the session path and the cluster executor's
    # broadcast-conf path run through here)
    from spark_rapids_tpu.shuffle.net import (set_checksum_enabled,
                                              set_network_retry)
    set_checksum_enabled(conf.shuffle_checksum_enabled)
    set_network_retry(conf.network_retry_max_attempts,
                      conf.network_retry_base_delay,
                      conf.network_retry_max_delay)
    from spark_rapids_tpu.shuffle.transport import set_replication
    set_replication(conf.shuffle_replication_factor,
                    conf.shuffle_persist_dir,
                    conf.cluster_drain_timeout)
    device_arena().check_retry_context = conf.retry_context_check
    # the stall watchdog rides the same conf snapshot: any blessed
    # blocking site (utils/cancel.cancellable_wait) past the threshold
    # becomes a typed stall report instead of a silent hang
    from spark_rapids_tpu.utils.watchdog import WATCHDOG
    WATCHDOG.configure(conf.watchdog_stall_seconds,
                       conf.watchdog_cancel_on_stall)
    # the continuous resource-plane sampler rides the same conf
    # snapshot: every intervalMs a daemon snapshots the arena/spill/
    # semaphore/admission/in-flight gauges into a bounded ring —
    # heartbeats piggyback the latest sample, the flight recorder dumps
    # the ring on stall/OOM-exhaustion/executor loss (utils/telemetry)
    from spark_rapids_tpu.utils.telemetry import TELEMETRY
    TELEMETRY.configure(conf.metrics_enabled,
                        conf.metrics_interval_ms,
                        conf.metrics_ring_seconds)
    # HBM-budget sizing from the chip's memory stats (GpuDeviceManager):
    # always on, like the reference's default-fraction pool sizing —
    # backends with no memory stats (CPU tests) stay in bookkeeping mode
    from spark_rapids_tpu.memory.device_manager import initialize_device
    initialize_device(conf)
    # injectRetryOOM accepts: false | true | retry[:num[:skip]] | split[:num[:skip]]
    # (reference parse: RapidsConf.scala:3041-3083).  Only an EXPLICIT key
    # touches the injection state: the @inject_oom test marker arms it
    # directly and a later session init must not disarm it.
    if conf.raw(C.TEST_INJECT_RETRY_OOM.key) is None:
        return
    spec = conf.test_inject_retry_oom.strip().lower()
    if spec in ("", "false", "0", "no"):
        device_arena().clear_injection()
    else:
        kind, num, skip = "retry", 1, 0
        if spec not in ("true", "1", "yes"):
            parts = spec.split(":")
            kind = parts[0]
            if len(parts) > 1:
                num = int(parts[1])
            if len(parts) > 2:
                skip = int(parts[2])
        if kind not in ("retry", "split"):
            raise ValueError(
                "spark.rapids.sql.test.injectRetryOOM: unknown kind "
                f"{kind!r} (expected retry|split|true|false, optionally "
                "kind:num:skip)")
        device_arena().inject_ooms(num, skip=skip, kind=kind)
