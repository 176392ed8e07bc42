"""Map-side range-serialization wire path (PR: contiguous-split framing).

The reference never materializes per-partition sub-tables on the map side
(GpuPartitioning.scala:66 contiguous_split; the Kudo serializer writes a
row range of the packed table).  These tests pin the TPU analog:

  * differential: range-framed wire blocks merge to batches row-equal to
    the per-piece serializer's output (fixed, string, null-heavy,
    empty-partition and skewed-counts cases), on BOTH the native and
    numpy writers — and are byte-identical to each other;
  * counters: exactly ONE device-to-host sync and zero extra gather
    launches per map batch on the MULTITHREADED and MULTIPROCESS write
    paths (shuffle/stats.py map_* counters + launch_stats);
  * the map side's one decision (transport type x schema), as a table;
  * round-robin start rotation spreads remainder rows across batches;
  * KudoWireTransport.read_iter chunks oversized reduce partitions by
    target_rows (whole-merge fallback when a codec hides the header).
"""
import numpy as np
import pytest

from spark_rapids_tpu import native
from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema
from spark_rapids_tpu.expressions.core import BoundReference
from spark_rapids_tpu.kernels.partition import hash_partition
from spark_rapids_tpu.plan.execs.base import (launch_stats,
                                              reset_launch_stats)
from spark_rapids_tpu.plan.execs.exchange import TpuShuffleExchangeExec
from spark_rapids_tpu.plan.execs.out_of_core import slice_by_counts
from spark_rapids_tpu.plan.execs.scan import TpuInMemoryScanExec
from spark_rapids_tpu.shuffle import serializer as ser
from spark_rapids_tpu.shuffle.stats import (reset_shuffle_counters,
                                            shuffle_counters)
from spark_rapids_tpu.shuffle.transport import KudoWireTransport

SCHEMA = Schema.of(k=T.INT, v=T.LONG, s=T.STRING)
FIXED_SCHEMA = Schema.of(k=T.INT, v=T.DOUBLE)


def _batch(lo, hi, key_mod=5):
    words = ["alpha", "", "beta gamma", None, "δέλτα"]
    return ColumnarBatch.from_pydict(
        {"k": [i % key_mod if i % 7 else None for i in range(lo, hi)],
         "v": list(range(lo, hi)),
         "s": [words[i % 5] for i in range(lo, hi)]}, SCHEMA)


def _rows(batch):
    d = batch.to_pydict()
    return sorted(zip(*[d[n] for n in batch.schema.names]),
                  key=lambda r: (r is None, str(r)))


CASES = {
    # name -> (batch, key ordinal, partitions)
    "fixed": (ColumnarBatch.from_pydict(
        {"k": [i % 3 for i in range(41)],
         "v": [float(i) if i % 4 else None for i in range(41)]},
        FIXED_SCHEMA), 0, 4),
    "strings": (_batch(0, 63), 0, 4),
    "null_heavy": (ColumnarBatch.from_pydict(
        {"k": [None if i % 2 else i % 4 for i in range(50)],
         "v": [None] * 50,
         "s": [None if i % 3 else f"s{i}" for i in range(50)]},
        SCHEMA), 0, 4),
    # more partitions than key values: empty partitions must frame as None
    "empty_parts": (_batch(0, 30, key_mod=2), 0, 8),
    # one key value: everything lands in a single partition
    "skewed": (_batch(0, 40, key_mod=1), 0, 4),
}


@pytest.mark.parametrize("writer", ["native", "numpy"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_range_blocks_match_piece_serializer(case, writer, monkeypatch):
    if writer == "numpy":
        monkeypatch.setenv("SPARK_RAPIDS_TPU_NO_NATIVE", "1")
    elif not native.available():
        pytest.skip("native toolchain unavailable")
    batch, key, nparts = CASES[case]
    reordered, counts = hash_partition(batch, [key], nparts)
    hc = np.asarray(counts)
    blocks = ser.serialize_batch_ranges(reordered, hc)
    pieces = slice_by_counts(reordered, hc, nparts)
    assert len(blocks) == nparts
    for p in range(nparts):
        if pieces[p] is None:
            assert blocks[p] is None
            continue
        piece_block = ser.serialize_batch(pieces[p])
        got = ser.merge_batches([blocks[p]], batch.schema)
        want = ser.merge_batches([piece_block], batch.schema)
        assert got.host_num_rows() == int(hc[p])
        assert _rows(got) == _rows(want), (case, p)
    # the whole partition set reassembles the input exactly
    merged = ser.merge_batches([b for b in blocks if b is not None],
                               batch.schema)
    assert _rows(merged) == _rows(batch)


def test_range_writers_byte_identical():
    """The numpy range writer is the native writer's differential oracle:
    same blocks byte-for-byte, which are ALSO the per-piece serializer's
    bytes (one wire format, three producers)."""
    if not native.available():
        pytest.skip("native toolchain unavailable")
    batch, key, nparts = CASES["strings"]
    reordered, counts = hash_partition(batch, [key], nparts)
    hc = np.asarray(counts)
    hb, hc = ser.download_partitioned(reordered, hc)
    bounds = np.zeros(nparts + 1, np.int64)
    np.cumsum(hc, out=bounds[1:])
    cols = []
    for c in hb.columns:
        valid = np.asarray(c.validity)
        if c.is_string_like:
            cols.append((valid, np.asarray(c.offsets), np.asarray(c.data)))
        else:
            cols.append((valid, None, np.ascontiguousarray(c.data)))
    native_raw = native.kudo_serialize_ranges(cols, bounds)
    py_parts = ser._py_serialize_ranges(cols, bounds)
    pieces = slice_by_counts(reordered, hc, nparts)
    for p in range(nparts):
        if native_raw[p] is None:
            assert py_parts[p] is None
            continue
        py_raw = b"".join(bytes(x) for x in py_parts[p])
        assert py_raw == native_raw[p], p
        assert ser.serialize_batch(pieces[p]) == b"N" + native_raw[p], p


def test_empty_batch_ranges():
    batch = ColumnarBatch.empty(SCHEMA, capacity=4)
    blocks = ser.serialize_batch_ranges(batch, np.zeros(3, np.int64))
    assert blocks == [None, None, None]


@pytest.mark.parametrize("mode", ["MULTITHREADED", "MULTIPROCESS"])
def test_map_side_one_sync_zero_gathers(mode):
    """Acceptance pin: on the wire write paths each map batch costs ONE
    serializer D2H sync and ONE program launch (the partition program) —
    no per-partition gather launches, no per-column downloads."""
    batches = [_batch(0, 40), _batch(40, 100), _batch(100, 130)]
    scan = TpuInMemoryScanExec([[b] for b in batches], SCHEMA)
    ex = TpuShuffleExchangeExec(4, [BoundReference(0, T.INT, "k")], scan,
                                mode=mode)
    try:
        # warm the jit cache so launch accounting isn't polluted by
        # bucket-convergence re-dispatches on a cold process
        ex._jit_slice(batches[0], __import__("jax").numpy.int32(0))
        reset_shuffle_counters()
        reset_launch_stats()
        transport = ex._materialize()
        c = shuffle_counters()
        s = launch_stats()
        assert c["map_d2h_syncs"] == len(batches), c
        assert c["map_range_batches"] == len(batches), c
        assert c["map_range_blocks"] >= len(batches)
        assert c["map_serialize_bytes"] > 0
        assert s["launches"] == len(batches), s   # partition program only
        rows = []
        for p in range(4):
            for b in (transport.read_iter(p) if mode == "MULTIPROCESS"
                      else ex.execute_partition(p)):
                rows += b.to_pydict()["v"]
        assert sorted(rows) == list(range(130))
    finally:
        ex.cleanup()


NESTED_SCHEMA = Schema.of(
    st=T.StructType((T.StructField("a", T.INT),
                     T.StructField("b", T.STRING))),
    ar=T.ArrayType(T.LONG))


def _nested_batch(lo, hi):
    return ColumnarBatch.from_pydict(
        {"st": [{"a": i, "b": f"x{i}"} if i % 3 else None
                for i in range(lo, hi)],
         "ar": [list(range(i % 4)) if i % 5 else None
                for i in range(lo, hi)]}, NESTED_SCHEMA)


@pytest.mark.parametrize("mode,nested,written_as", [
    ("CACHE_ONLY", False, "range_views"),
    ("MULTITHREADED", False, "range_stream"),
    # why _slices stays: the range writer frames flat layouts only
    ("MULTITHREADED", True, "slices")])
def test_map_side_write_shape_by_transport_and_schema(mode, nested,
                                                      written_as):
    """The map side's one decision reads the transport's type and
    range_supported(schema): CACHE_ONLY stores range views, a wire
    transport is written by ranges where the schema is flat and by
    device slices where it is nested; the rows are the input's."""
    if nested:
        schema, keys = NESTED_SCHEMA, []          # round-robin routing
        batches = [_nested_batch(0, 20), _nested_batch(20, 50)]
    else:
        schema, keys = SCHEMA, [BoundReference(0, T.INT, "k")]
        batches = [_batch(0, 40), _batch(40, 80)]
    assert ser.range_supported(schema) == (not nested)
    scan = TpuInMemoryScanExec([[b] for b in batches], schema)
    ex = TpuShuffleExchangeExec(4, keys, scan, mode=mode)
    assert ex.mode == mode
    try:
        reset_shuffle_counters()
        got = []
        for p in range(4):
            for b in ex.execute_partition(p):
                got += _rows(b)
        c = shuffle_counters()
    finally:
        ex.cleanup()
    assert sorted(got, key=str) == sorted(
        (r for b in batches for r in _rows(b)), key=str)
    assert (c["range_view_blocks"] > 0) == (written_as == "range_views"), c
    assert (c["map_range_batches"] > 0) == (written_as == "range_stream"), c
    # per-piece serializer downloads: more syncs than map batches
    assert (c["map_d2h_syncs"] > len(batches)) == (written_as == "slices"), c


def test_round_robin_start_rotates_across_batches():
    """GpuRoundRobinPartitioning rotates the start partition; without
    rotation partition 0 collects every batch's remainder rows.  3
    batches x 10 rows over 4 partitions: unrotated totals are [9,9,6,6],
    rotated [7,8,8,7]."""
    schema = Schema.of(v=T.LONG)
    batches = [ColumnarBatch.from_pydict(
        {"v": list(range(i * 10, i * 10 + 10))}, schema) for i in range(3)]
    scan = TpuInMemoryScanExec([[b] for b in batches], schema)
    ex = TpuShuffleExchangeExec(4, [], scan, mode="CACHE_ONLY")
    try:
        ex._want_part_stats = True
        counts = ex.partition_row_counts()
        assert sum(counts) == 30
        assert max(counts) - min(counts) <= 1, counts
        rows = []
        for p in range(4):
            for b in ex.execute_partition(p):
                rows += b.to_pydict()["v"]
        assert sorted(rows) == list(range(30))
    finally:
        ex.cleanup()


def test_kudo_read_iter_chunks_by_target_rows():
    """Satellite: an oversized reduce partition streams in chunks aligned
    to the consumer's row target instead of ONE whole-partition merge."""
    t = KudoWireTransport(2, SCHEMA)
    t.write_batches(
        ser.download_partitioned(*_partitioned(_batch(i * 20, i * 20 + 20)))
        for i in range(6))
    batches = list(t.read_iter(0, target_rows=25))
    assert len(batches) > 1
    whole = list(t.read_iter(0, target_rows=None))
    assert len(whole) == 1
    assert sorted(r for b in batches for r in b.to_pydict()["v"]) == \
        sorted(whole[0].to_pydict()["v"])
    # each flush lands at the first block boundary past the target
    # (chunk < 25 rows before its last block, one block adds <= 20)
    assert all(b.host_num_rows() <= 44 for b in batches)
    t.cleanup()


def test_kudo_read_iter_whole_merge_when_header_hidden(monkeypatch):
    """A codec that hides the wire header falls back to whole-merge."""
    t = KudoWireTransport(2, SCHEMA)
    t.write_batches(
        ser.download_partitioned(*_partitioned(_batch(i * 20, i * 20 + 20)))
        for i in range(4))
    monkeypatch.setattr(
        "spark_rapids_tpu.shuffle.serializer.wire_row_count",
        lambda raw: None)
    batches = list(t.read_iter(0, target_rows=10))
    assert len(batches) == 1
    t.cleanup()


def _partitioned(batch, nparts=2):
    reordered, counts = hash_partition(batch, [0], nparts)
    return reordered, np.asarray(counts)


def test_nested_serializer_single_download():
    """Satellite: the nested wire path (which the range writer doesn't
    take) downloads each piece in ONE batched device_get."""
    schema, batch = NESTED_SCHEMA, _nested_batch(0, 20)
    assert not ser.range_supported(schema)
    reset_shuffle_counters()
    block = ser.serialize_batch(batch)
    assert shuffle_counters()["map_d2h_syncs"] == 1
    merged = ser.merge_batches([block], schema)
    assert merged.to_pydict() == batch.to_pydict()
