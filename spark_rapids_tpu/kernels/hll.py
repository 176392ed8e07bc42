"""HyperLogLog++ register kernels.

Reference: aggregate/GpuHyperLogLogPlusPlus.scala (cuDF HLL sketch agg).
TPU design: a group's sketch is m = 2^p int8 registers stored as one
fixed-length array<tinyint> row in the aggregation-buffer batch; the update
computes (register index, rho) from xxhash64 per row and segment-maxes into
a [groups*m] flattened register plane; merge is an elementwise segment max
over the same plane.  The estimate formula is shared (verbatim math) with
the numpy oracle so both engines agree exactly.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.kernels import hash as HK


def p_from_rsd(rsd: float) -> int:
    """Spark HyperLogLogPlusPlus: p = ceil(2 * log2(1.106 / rsd))."""
    p = int(math.ceil(2.0 * math.log(1.106 / rsd) / math.log(2.0)))
    return max(4, p)


def row_idx_rho(values_u64, validity, p: int):
    """Device per-row (register index, rho) from xxhash64(long, seed 42)."""
    seed = jnp.full(values_u64.shape, np.uint64(HK.XXHASH64_DEFAULT_SEED),
                    jnp.uint64)
    h = HK._xx_hash_long(values_u64, seed)
    idx = (h >> (64 - p)).astype(jnp.int32)
    rest = h << p
    nz = jax.lax.clz(rest.astype(jnp.uint64)).astype(jnp.int32)
    rho = jnp.minimum(nz + 1, 64 - p + 1)
    rho = jnp.where(validity, rho, 0)
    idx = jnp.where(validity, idx, 0)
    return idx, rho


def _alpha(m: int) -> float:
    """HLL++ paper (Heule et al. 2013) alpha constants, as used by Spark's
    HyperLogLogPlusPlusHelper: exact values for small m, asymptotic
    formula otherwise."""
    if m == 16:
        return 0.673
    if m == 32:
        return 0.697
    if m == 64:
        return 0.709
    return 0.7213 / (1.0 + 1.079 / m)


def estimate_np(registers: np.ndarray) -> int:
    """HLL estimate + linear-counting small-range correction (shared).

    Spark additionally subtracts an interpolated empirical bias
    (RAW_ESTIMATE_DATA/BIAS_DATA, ~2000 doubles) for estimates under 5m and
    switches to linear counting below per-p THRESHOLDS; those tables are
    not reproduced here, so the classic 2.5m linear-counting rule is used
    instead (the paper thresholds assume the bias correction and degrade
    accuracy without it).  Mid-cardinality estimates can therefore differ
    slightly from CPU Spark (documented divergence; engine and oracle
    share this exact function so differential tests are unaffected)."""
    m = registers.shape[0]
    inv = np.power(2.0, -registers.astype(np.float64))
    est = _alpha(m) * m * m / inv.sum()
    zeros = int((registers == 0).sum())
    if est <= 2.5 * m and zeros != 0:
        est = m * np.log(m / float(zeros))
    return int(round(est))


def update_np(values, validity, p: int, registers=None) -> np.ndarray:
    """Numpy oracle register update."""
    m = 1 << p
    if registers is None:
        registers = np.zeros((m,), np.int8)
    for v, ok in zip(values, validity):
        if not ok:
            continue
        h = HK.py_xxhash64_long(int(v), HK.XXHASH64_DEFAULT_SEED)
        idx = h >> (64 - p)
        rest = (h << p) & ((1 << 64) - 1)
        rho = 1
        for _ in range(64 - p):
            if rest & (1 << 63):
                break
            rho += 1
            rest = (rest << 1) & ((1 << 64) - 1)
        registers[idx] = max(registers[idx], min(rho, 64 - p + 1))
    return registers


def global_update(col, live, p: int) -> jax.Array:
    """Whole-batch registers int8[m] for the no-keys aggregation path."""
    m = 1 << p
    valid = col.validity & live
    v = col.data.astype(jnp.int64).astype(jnp.uint64)
    idx, rho = row_idx_rho(v, valid, p)
    regs = jax.ops.segment_max(rho, idx, num_segments=m)
    return jnp.maximum(regs, 0).astype(jnp.int8)


def seg_update(col, layout, p: int) -> jax.Array:
    """Grouped registers [capacity, m] int8 over a GroupedLayout."""
    m = 1 << p
    cap = col.capacity
    live = layout.live_mask()
    valid = col.validity & live
    v = col.data.astype(jnp.int64).astype(jnp.uint64)
    idx, rho = row_idx_rho(v, valid, p)
    flat = layout.segment_ids * m + idx
    regs = jax.ops.segment_max(rho, flat, num_segments=cap * m)
    return jnp.maximum(regs, 0).astype(jnp.int8).reshape(cap, m)


def merge_rows(regs_2d, seg_or_none, cap: int, m: int):
    """Merge register rows: [rows, m] -> per-segment elementwise max.

    seg_or_none None = global merge (one output row)."""
    if seg_or_none is None:
        return jnp.max(regs_2d, axis=0, keepdims=True)
    out = jax.ops.segment_max(regs_2d, seg_or_none, num_segments=cap)
    return jnp.maximum(out, 0).astype(jnp.int8)
