"""t-digest kernels for approx_percentile.

Reference: GpuApproximatePercentile.scala:58-74 — the reference offloads
Spark's ApproximatePercentile to cuDF's t-digest (documented divergence
from Spark CPU's Greenwald-Khanna summaries: results agree within the
accuracy tolerance, not bitwise).  This module is the TPU lowering of the
same design.

Digest representation (TPU-shaped): per group, a VAR-LENGTH centroid list
(mean, weight) carried as two parallel ``array<double>`` columns plus
scalar min/max buffers.  A group with n <= delta values keeps every value
as its own centroid; larger groups compress onto the k1 scale function
(centroids tighten at the tails, where quantile queries need precision):

    k(q) = delta * (asin(2q - 1) / pi + 1/2),   cluster = floor(k(q_mid))

Everything is segment machinery over ONE lexsort per phase — total
centroid elements are bounded by the input row count, so the element plane
never exceeds the batch capacity (no groups x delta blowup).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.column import DeviceColumn

DEFAULT_DELTA = 100


def _orderable_f64(x: jax.Array) -> jax.Array:
    """float64 -> uint64 monotone sort key (TPU-safe: no f64 bitcast —
    kernels/sort.py f64_total_order_u64)."""
    from spark_rapids_tpu.kernels.sort import f64_total_order_u64
    return f64_total_order_u64(x.astype(jnp.float64))


def _cluster_of(q: jax.Array, delta: int) -> jax.Array:
    k = delta * (jnp.arcsin(jnp.clip(2.0 * q - 1.0, -1.0, 1.0)) / math.pi
                 + 0.5)
    return jnp.clip(jnp.floor(k).astype(jnp.int32), 0, delta - 1)


def _runs_to_array_column(run_live, run_seg, run_data, cap, num_groups):
    """Compress per-run values (contiguous, segment-ascending) into a
    var-length array<double> column with one row per group."""
    from spark_rapids_tpu.kernels.selection import compaction_map
    ecap = run_live.shape[0]
    idx, total = compaction_map(run_live)
    epos = jnp.arange(ecap, dtype=jnp.int32)
    data = jnp.where(epos < total,
                     run_data[jnp.clip(idx, 0, ecap - 1)], 0.0)
    seg_of_run = jnp.where(run_live, run_seg, cap)
    counts = jax.ops.segment_sum(run_live.astype(jnp.int32), seg_of_run,
                                 num_segments=cap + 1)[:cap]
    csum = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(counts).astype(jnp.int32)])
    gidx = jnp.minimum(jnp.arange(cap + 1, dtype=jnp.int32), num_groups)
    offsets = csum[gidx]
    validity = jnp.arange(cap, dtype=jnp.int32) < num_groups
    return DeviceColumn(data, validity, T.ArrayType(T.DOUBLE,
                                                    contains_null=False),
                        offsets=offsets)


def _digest_from_weighted(values, weights, seg, valid, cap, num_groups,
                          delta: int, want: str) -> DeviceColumn:
    """Shared core: weighted (value, weight) points per segment ->
    clustered centroid arrays.  `want` is 'means' or 'weights'."""
    n = values.shape[0]
    seg_safe = jnp.where(valid, seg, cap)
    order = jnp.lexsort((_orderable_f64(values), seg_safe)).astype(jnp.int32)
    v_s = values[order]
    w_s = jnp.where(valid[order], weights[order], 0.0)
    seg_s = seg_safe[order]
    valid_s = valid[order]

    # cumulative weight before each point, within its segment
    cw = jnp.cumsum(w_s)
    seg_tot = jax.ops.segment_sum(w_s, seg_s, num_segments=cap + 1)
    seg_cw_start = jnp.concatenate([jnp.zeros((1,), jnp.float64),
                                    jnp.cumsum(seg_tot)])[:-1]
    before = cw - w_s - seg_cw_start[jnp.clip(seg_s, 0, cap)]
    total = jnp.maximum(seg_tot[jnp.clip(seg_s, 0, cap)], 1e-300)
    q_mid = (before + w_s * 0.5) / total
    cluster = _cluster_of(q_mid, delta)

    pos = jnp.arange(n, dtype=jnp.int32)
    prev_seg = jnp.roll(seg_s, 1)
    prev_cluster = jnp.roll(cluster, 1)
    boundary = valid_s & ((pos == 0) | (seg_s != prev_seg)
                          | (cluster != prev_cluster)
                          | ~jnp.roll(valid_s, 1))
    run = jnp.cumsum(boundary.astype(jnp.int32)) - 1
    run = jnp.where(valid_s, run, n - 1)

    run_w = jax.ops.segment_sum(w_s, run, num_segments=n)
    run_wm = jax.ops.segment_sum(w_s * v_s, run, num_segments=n)
    run_seg = jax.ops.segment_min(jnp.where(valid_s, seg_s, cap), run,
                                  num_segments=n)
    n_runs = jnp.sum(boundary.astype(jnp.int32))
    run_live = jnp.arange(n, dtype=jnp.int32) < n_runs
    run_data = (run_wm / jnp.maximum(run_w, 1e-300)
                if want == "means" else run_w)
    return _runs_to_array_column(run_live, run_seg, run_data, cap,
                                 num_groups)


def seg_update(col: DeviceColumn, layout, delta: int,
               want: str) -> DeviceColumn:
    """Raw grouped rows -> centroid arrays (update phase)."""
    live = layout.live_mask()
    valid = col.validity & live
    cap = col.capacity
    return _digest_from_weighted(
        col.data.astype(jnp.float64), jnp.ones((cap,), jnp.float64),
        layout.segment_ids, valid, cap, layout.num_groups, delta, want)


def global_update(col: DeviceColumn, live, delta: int,
                  want: str) -> DeviceColumn:
    valid = col.validity & live
    cap = col.capacity
    return _digest_from_weighted(
        col.data.astype(jnp.float64), jnp.ones((cap,), jnp.float64),
        jnp.zeros((cap,), jnp.int32), valid, cap, jnp.int32(1), delta,
        want)


def _element_points(means_col, weights_col, seg_ids, row_valid):
    """Flatten partial-digest array rows into per-element (value, weight,
    segment, valid) planes."""
    from spark_rapids_tpu.kernels.collections import (
        element_live_mask, element_row_ids)
    ecap = means_col.byte_capacity
    erows = element_row_ids(means_col)
    nrows = jnp.sum(row_valid.astype(jnp.int32))
    elive = (element_live_mask(means_col, nrows)
             & row_valid[jnp.clip(erows, 0, row_valid.shape[0] - 1)])
    eseg = seg_ids[jnp.clip(erows, 0, seg_ids.shape[0] - 1)]
    ew = weights_col.data.astype(jnp.float64)
    elive = elive & (ew > 0)
    return means_col.data.astype(jnp.float64), ew, eseg, elive, ecap


def seg_merge(means_col: DeviceColumn, weights_col: DeviceColumn, layout,
              delta: int, want: str) -> DeviceColumn:
    """Partial digests (array rows) -> merged digests per group: pool all
    centroids of a group, re-cluster by cumulative weight."""
    live = layout.live_mask()
    row_valid = means_col.validity & live
    cap = means_col.capacity
    ev, ew, eseg, elive, _ = _element_points(
        means_col, weights_col, layout.segment_ids, row_valid)
    return _digest_from_weighted(ev, ew, eseg, elive, cap,
                                 layout.num_groups, delta, want)


def global_merge(means_col: DeviceColumn, weights_col: DeviceColumn, live,
                 delta: int, want: str) -> DeviceColumn:
    row_valid = means_col.validity & live
    cap = means_col.capacity
    seg = jnp.zeros((cap,), jnp.int32)
    ev, ew, eseg, elive, _ = _element_points(
        means_col, weights_col, seg, row_valid)
    return _digest_from_weighted(ev, ew, eseg, elive, cap, jnp.int32(1),
                                 delta, want)


def interpolate(means_col: DeviceColumn, weights_col: DeviceColumn,
                mins, maxs, percentage: float
                ) -> Tuple[jax.Array, jax.Array]:
    """Per-group percentile from merged digests: centroid cumulative
    midpoints, linear interpolation, clamped to [min, max].  Returns
    (values[cap], valid[cap])."""
    cap = means_col.capacity
    ecap = means_col.byte_capacity
    offsets = means_col.offsets
    lengths = (offsets[1:] - offsets[:-1]).astype(jnp.int32)
    m = means_col.data.astype(jnp.float64)
    w = weights_col.data.astype(jnp.float64)

    epos = jnp.arange(ecap, dtype=jnp.int32)
    # group id per element (offsets ascending): rightmost offset <= e
    eg = jnp.clip(jnp.searchsorted(offsets, epos, side="right") - 1,
                  0, cap - 1).astype(jnp.int32)
    elive = epos < offsets[cap]
    eg_safe = jnp.where(elive, eg, cap)
    wsum = jax.ops.segment_sum(jnp.where(elive, w, 0.0), eg_safe,
                               num_segments=cap + 1)[:cap]
    cw = jnp.cumsum(jnp.where(elive, w, 0.0))
    gstart = cw[jnp.clip(offsets[:cap], 0, ecap - 1)] - \
        w[jnp.clip(offsets[:cap], 0, ecap - 1)]
    cm = cw - w * 0.5 - gstart[jnp.clip(eg, 0, cap - 1)]   # cum midpoint

    t = percentage * wsum                                   # target rank
    below = elive & (cm <= t[jnp.clip(eg, 0, cap - 1)])
    j_count = jax.ops.segment_sum(below.astype(jnp.int32), eg_safe,
                                  num_segments=cap + 1)[:cap]
    base = offsets[:cap]
    jlo = jnp.clip(j_count - 1, 0, jnp.maximum(lengths - 1, 0))
    jhi = jnp.clip(j_count, 0, jnp.maximum(lengths - 1, 0))
    elo = jnp.clip(base + jlo, 0, ecap - 1)
    ehi = jnp.clip(base + jhi, 0, ecap - 1)
    cm_lo, cm_hi = cm[elo], cm[ehi]
    m_lo, m_hi = m[elo], m[ehi]
    denom = jnp.where(cm_hi > cm_lo, cm_hi - cm_lo, 1.0)
    frac = jnp.clip((t - cm_lo) / denom, 0.0, 1.0)
    val = m_lo + (m_hi - m_lo) * frac
    # tails: t beyond the first/last midpoint clamps toward min/max
    val = jnp.clip(val, mins, maxs)
    valid = (lengths > 0) & (wsum > 0)
    return jnp.where(valid, val, 0.0), valid


# -- numpy twin (CPU oracle; same math, single-pass) -------------------------

def np_digest(values, delta: int):
    """Exact numpy replica of the update clustering for the oracle:
    sorted values -> (means, weights) lists."""
    import numpy as np
    v = np.sort(np.asarray(values, np.float64))
    n = len(v)
    if n == 0:
        return [], []
    q = (np.arange(n) + 0.5) / n
    k = delta * (np.arcsin(np.clip(2 * q - 1, -1, 1)) / math.pi + 0.5)
    cluster = np.clip(np.floor(k).astype(np.int64), 0, delta - 1)
    boundary = np.concatenate([[True], cluster[1:] != cluster[:-1]])
    run = np.cumsum(boundary) - 1
    wsum = np.bincount(run, minlength=run[-1] + 1).astype(np.float64)
    msum = np.bincount(run, weights=v, minlength=run[-1] + 1)
    return (msum / wsum).tolist(), wsum.tolist()


def np_interpolate(means, weights, vmin, vmax, percentage: float):
    import numpy as np
    m = np.asarray(means, np.float64)
    w = np.asarray(weights, np.float64)
    if len(m) == 0 or w.sum() <= 0:
        return None
    cm = np.cumsum(w) - w * 0.5
    t = percentage * w.sum()
    j = int(np.searchsorted(cm, t, side="right")) - 1
    jlo = max(min(j, len(m) - 1), 0)
    jhi = max(min(j + 1, len(m) - 1), 0)
    if cm[jhi] > cm[jlo]:
        frac = min(max((t - cm[jlo]) / (cm[jhi] - cm[jlo]), 0.0), 1.0)
    else:
        frac = 0.0
    val = m[jlo] + (m[jhi] - m[jlo]) * frac
    return float(min(max(val, vmin), vmax))
