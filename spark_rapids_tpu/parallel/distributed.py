"""Multi-chip SPMD execution: mesh-sharded query steps.

The TPU-native replacement for the reference's multi-executor + UCX data
plane (SURVEY.md §5.8): instead of per-executor processes exchanging batches
over RDMA, a query stage is one SPMD program over a jax.sharding.Mesh —
rows are sharded over the 'data' axis, aggregations finish with XLA
collectives (psum) that ride ICI, and the shuffle between stages is an
all-to-all (jax.lax.all_to_all) routed by the same bit-exact murmur3/pmod
partitioner the single-chip shuffle uses (kernels/partition.py).

This module is deliberately mesh-shape agnostic: tests and the driver's
dryrun run it over N virtual CPU devices
(xla_force_host_platform_device_count), production runs it over a pod
slice's real chips.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu.kernels import hash as hash_kernels


def make_mesh(n_devices: int) -> Mesh:
    devices = np.array(jax.devices()[:n_devices])
    return Mesh(devices, ("data",))


def shard_batch(batch: ColumnarBatch, mesh: Mesh) -> ColumnarBatch:
    """Place a batch row-sharded over the mesh's data axis.

    Fixed-width columns shard on their row axis; the dynamic num_rows scalar
    is replicated.  (String columns would shard offsets/validity but need a
    byte redistribution — they stay replicated until the string shuffle
    lands.)
    """
    row_sharded = NamedSharding(mesh, P("data"))
    replicated = NamedSharding(mesh, P())
    cols = []
    for c in batch.columns:
        if c.is_string_like:
            cols.append(DeviceColumn(
                jax.device_put(c.data, replicated),
                jax.device_put(c.validity, replicated), c.dtype,
                jax.device_put(c.offsets, replicated)))
        else:
            cols.append(DeviceColumn(
                jax.device_put(c.data, row_sharded),
                jax.device_put(c.validity, row_sharded), c.dtype))
    return ColumnarBatch(tuple(cols), jax.device_put(batch.num_rows, replicated),
                         batch.schema)


# ---------------------------------------------------------------------------
# distributed filter+aggregate (the q6 shape): pure sharding annotations —
# XLA inserts the psum; no manual collectives needed.


def distributed_filter_sum(mesh: Mesh, predicate_fn, value_fn):
    """Build a jitted SPMD step computing sum(value) over rows passing
    predicate.  predicate_fn/value_fn: (batch) -> (values, validity) arrays.

    Returns fn(batch sharded over 'data') -> (sum f64, count i64), both
    replicated.  The cross-chip reduction is XLA's: outputs demand
    replication, so the compiler emits the ICI all-reduce itself.
    """
    out_sharding = NamedSharding(mesh, P())

    @partial(jax.jit, out_shardings=(out_sharding, out_sharding))
    def step(batch: ColumnarBatch):
        keep, kvalid = predicate_fn(batch)
        vals, vvalid = value_fn(batch)
        live = batch.live_mask()
        mask = keep & kvalid & vvalid & live
        s = jnp.sum(jnp.where(mask, vals.astype(jnp.float64), 0.0))
        n = jnp.sum(mask.astype(jnp.int64))
        return s, n

    return step


# ---------------------------------------------------------------------------
# all-to-all hash exchange: the ICI shuffle primitive.


def make_all_to_all_exchange(mesh: Mesh, schema: Schema, key_cols: Sequence[int],
                             per_dest_capacity: int):
    """Build a jitted SPMD step that redistributes rows so equal keys land on
    the same device: murmur3(keys) pmod n_dev -> all_to_all over ICI.

    Each device scatters its rows into an [n_dev, per_dest_capacity] send
    buffer (padded, canonical), then one jax.lax.all_to_all moves bucket i
    of every device to device i.  Returns fn(local column arrays dict) ->
    (received arrays [n_dev, cap], received validity).  Overflow of
    per_dest_capacity reports via the returned required-counts vector, for
    the capacity-retry loop (memory/retry.py).
    """
    n_dev = mesh.devices.size
    names = schema.names
    fixed = [i for i in range(len(names))]

    def local_step(cols: Dict[str, jax.Array], validity: Dict[str, jax.Array],
                   num_rows: jax.Array):
        # cols: per-device local shard [rows_local]
        rows_local = cols[names[0]].shape[0]
        live = jnp.arange(rows_local, dtype=jnp.int32) < num_rows
        key_device_cols = [
            DeviceColumn(cols[names[ci]], validity[names[ci]], schema.dtypes[ci])
            for ci in key_cols]
        h = hash_kernels.murmur3_hash(key_device_cols, string_max_bytes=0)
        dest = hash_kernels.pmod(h, n_dev)
        dest = jnp.where(live, dest, jnp.int32(n_dev))  # padding -> dropped
        # slot within destination bucket = running count of rows to that dest
        one_hot = (dest[:, None] == jnp.arange(n_dev, dtype=jnp.int32)[None, :])
        slot = jnp.cumsum(one_hot.astype(jnp.int32), axis=0) - one_hot.astype(jnp.int32)
        slot_of_row = jnp.sum(slot * one_hot, axis=1)
        required = jnp.sum(one_hot.astype(jnp.int32), axis=0)  # per-dest counts

        sent = {}
        sent_valid = {}
        flat_idx = dest * per_dest_capacity + jnp.minimum(
            slot_of_row, per_dest_capacity - 1)
        drop = (dest >= n_dev) | (slot_of_row >= per_dest_capacity)
        flat_idx = jnp.where(drop, n_dev * per_dest_capacity, flat_idx)
        for name, arr in cols.items():
            buf = jnp.zeros((n_dev * per_dest_capacity + 1,), arr.dtype)
            buf = buf.at[flat_idx].set(jnp.where(live, arr, jnp.zeros((), arr.dtype)),
                                       mode="drop")
            vbuf = jnp.zeros((n_dev * per_dest_capacity + 1,), jnp.bool_)
            vbuf = vbuf.at[flat_idx].set(validity[name] & live, mode="drop")
            sent[name] = buf[:-1].reshape(n_dev, per_dest_capacity)
            sent_valid[name] = vbuf[:-1].reshape(n_dev, per_dest_capacity)
        occupied = jnp.zeros((n_dev * per_dest_capacity + 1,), jnp.bool_)
        occupied = occupied.at[flat_idx].set(live, mode="drop")
        occupied = occupied[:-1].reshape(n_dev, per_dest_capacity)

        # the ICI hop: bucket d of every device -> device d
        recv = {name: jax.lax.all_to_all(buf, "data", 0, 0, tiled=False)
                for name, buf in sent.items()}
        recv_valid = {name: jax.lax.all_to_all(buf, "data", 0, 0, tiled=False)
                      for name, buf in sent_valid.items()}
        recv_occupied = jax.lax.all_to_all(occupied, "data", 0, 0, tiled=False)
        return recv, recv_valid, recv_occupied, required

    in_spec = (
        {n: P("data") for n in names},
        {n: P("data") for n in names},
        P(),
    )
    out_spec = (
        {n: P("data", None) for n in names},
        {n: P("data", None) for n in names},
        P("data", None),
        P("data"),
    )
    step = jax.shard_map(local_step, mesh=mesh, in_specs=in_spec,
                         out_specs=out_spec)
    return jax.jit(step)


# ---------------------------------------------------------------------------
# distributed grouped aggregation = exchange + local segmented reduce


def distributed_group_sum(mesh: Mesh, schema: Schema, key_col: str,
                          value_col: str, per_dest_capacity: int,
                          max_groups: int):
    """Full distributed group-by-sum step: all-to-all exchange on the key,
    then a local sort-based segmented sum per device.  The one-step SPMD
    equivalent of partial-agg -> shuffle -> final-agg."""
    exchange = make_all_to_all_exchange(
        mesh, schema, [schema.index_of(key_col)], per_dest_capacity)

    ki = schema.index_of(key_col)
    n_dev = mesh.devices.size

    def local_agg(recv_keys, recv_vals, recv_kvalid, recv_vvalid, occupied):
        # flatten [n_dev, cap] -> [n_dev*cap] local rows
        keys = recv_keys.reshape(-1)
        vals = recv_vals.reshape(-1)
        kval = recv_kvalid.reshape(-1)
        vval = recv_vvalid.reshape(-1)
        occ = occupied.reshape(-1)
        order = jnp.lexsort((jnp.where(occ, keys, jnp.iinfo(keys.dtype).max),
                             (~occ).astype(jnp.int32)))
        keys_s = keys[order]
        vals_s = vals[order]
        occ_s = occ[order]
        vval_s = (vval & occ)[order]
        first = jnp.arange(keys_s.shape[0]) == 0
        boundary = occ_s & (first | (keys_s != jnp.roll(keys_s, 1)))
        seg = jnp.cumsum(boundary.astype(jnp.int32)) - 1
        seg = jnp.where(occ_s, seg, keys_s.shape[0] - 1)
        sums = jax.ops.segment_sum(
            jnp.where(vval_s, vals_s.astype(jnp.float64), 0.0), seg,
            num_segments=max_groups)
        group_keys = jnp.zeros((max_groups,), keys_s.dtype).at[
            jnp.minimum(seg, max_groups - 1)].set(
                jnp.where(occ_s, keys_s, 0), mode="drop")
        n_groups = jnp.sum(boundary.astype(jnp.int32)).reshape(1)
        return group_keys, sums, n_groups

    local_agg_sm = jax.shard_map(
        local_agg, mesh=mesh,
        in_specs=(P("data", None),) * 5,
        out_specs=(P("data"), P("data"), P("data")))

    names = schema.names

    @jax.jit
    def step(cols, validity, num_rows):
        recv, recv_valid, occupied, required = exchange(cols, validity, num_rows)
        gk, gs, ng = local_agg_sm(
            recv[key_col], recv[value_col],
            recv_valid[key_col], recv_valid[value_col], occupied)
        return gk, gs, ng, required

    return step
