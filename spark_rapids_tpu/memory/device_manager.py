"""Device manager: chip discovery and HBM budget sizing.

Reference: GpuDeviceManager.scala (:473-480 pool sizing from
spark.rapids.memory.gpu.allocFraction over the device's total memory,
device selection/pinning, init-time validation).  The TPU analog reads the
PJRT device's memory stats and sizes the arena budget as
allocFraction x HBM bytes.  The CPU backend exposes no stats, and there
(tests) the arena stays in unlimited bookkeeping mode; on a TPU missing
stats are an error — a chip run with no HBM budget would look healthy
until the device itself ran out.
"""
from __future__ import annotations

from typing import Optional


class DeviceInfo:
    def __init__(self, device, hbm_bytes: Optional[int], platform: str):
        self.device = device
        self.hbm_bytes = hbm_bytes
        self.platform = platform

    def __repr__(self):
        size = (f"{self.hbm_bytes / (1 << 30):.1f}GiB"
                if self.hbm_bytes else "unknown")
        return f"DeviceInfo({self.device}, hbm={size})"


def probe_device() -> DeviceInfo:
    """Discover the executor's device (one chip == one executor, the
    reference's one-GPU-per-executor model)."""
    import jax
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    hbm = int(stats.get("bytes_limit")
              or stats.get("bytes_reservable_limit") or 0) or None
    if hbm is None and dev.platform == "tpu":
        raise RuntimeError(
            f"{dev} reports no memory limit (memory_stats()={stats!r}): "
            "cannot size the HBM arena budget")
    return DeviceInfo(dev, hbm, dev.platform)


def initialize_device(conf) -> DeviceInfo:
    """Size the arena budget from the chip's HBM and the allocFraction
    conf (GpuDeviceManager.initializeMemory analog).  Called from session
    init; safe to call repeatedly (last conf wins)."""
    from spark_rapids_tpu import config as C
    from spark_rapids_tpu.memory import device_arena

    info = probe_device()
    frac = conf.get(C.DEVICE_MEMORY_LIMIT)
    arena = device_arena()
    if info.hbm_bytes and 0.0 < frac <= 1.0:
        budget = int(info.hbm_bytes * frac)
        # never SHRINK below what is already resident (a later session with
        # a smaller fraction must not instantly OOM live handles)
        arena.budget_bytes = max(budget, arena.used_bytes)
    return info
