"""The generator: the same seed gives the same bytes, whatever the threads
do; a seed over 2**31 works; files and row groups are as the configuration
says."""
import hashlib

import pyarrow.parquet as pq

from benchmark import datagen
from benchmark.tables import lineitem


def _digest(paths):
    h = hashlib.sha256()
    for p in paths:
        with open(p, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def _write(tmp_path, sub, seed, rows=40_000, files=2, group=8_192):
    d = tmp_path / sub
    d.mkdir()
    return datagen.write_table(str(d), lineitem, "lineitem", rows, files,
                               group, seed, rows / 6_001_215)


def test_same_seed_same_bytes_other_seed_other_bytes(tmp_path):
    big = 2**31 + 11
    a, b = _write(tmp_path, "a", big), _write(tmp_path, "b", big)
    c = _write(tmp_path, "c", big + 1)
    assert _digest(a) == _digest(b)
    assert _digest(a) != _digest(c)


def test_files_row_groups_and_schema(tmp_path):
    paths = _write(tmp_path, "a", 5)
    assert len(paths) == 2
    metas = [pq.ParquetFile(p).metadata for p in paths]
    groups = [m.row_group(i).num_rows for m in metas
              for i in range(m.num_row_groups)]
    assert groups == [8192, 8192, 8192, 8192, 7232]
    assert [m.num_row_groups for m in metas] == [3, 2]
    table = pq.read_table(paths[0])
    assert table.column_names == list(lineitem.SCHEMA)
    assert str(table.schema.field("l_discount").type) == "decimal128(12, 2)"
    assert str(table.schema.field("l_shipdate").type) == "date32[day]"
    frame = datagen.read_frame(paths, ["l_discount", "l_quantity",
                                       "l_shipdate", "l_tax"])
    assert frame["l_discount"].between(0.0, 0.10).all()
    assert frame["l_quantity"].between(1, 50).all()
    assert frame["l_tax"].between(0.0, 0.08).all()
    assert frame["l_shipdate"].between(lineitem.days(1992, 1, 2),
                                       lineitem.days(1998, 12, 1)).all()


def test_columns_are_derived_as_the_spec_says(tmp_path):
    paths = _write(tmp_path, "a", 2**31 + 7, rows=60_012, group=16_384)
    f = pq.read_table(paths).to_pandas()
    assert len(f) == 60_012 and list(f.columns) == list(lineitem.SCHEMA)
    # lines of an order: numbered from 1, 1 to 7 of them, one key, sparse
    by_order = f.groupby("l_orderkey")["l_linenumber"]
    assert (by_order.max() == by_order.size()).all()
    assert by_order.size().between(1, 7).all()
    assert ((f["l_orderkey"] - 1) % 32 < 8).all()
    assert f["l_orderkey"].is_monotonic_increasing
    # SF 0.01: 2,000 parts, 100 suppliers
    assert f["l_partkey"].between(1, 2_000).all()
    assert f["l_suppkey"].between(1, 100).all()
    part = f["l_partkey"]
    retail = (90_000 + (part // 10) % 20_001 + 100 * (part % 1_000)) / 100
    price = f["l_extendedprice"].astype(float)
    assert (abs(price - f["l_quantity"].astype(float) * retail)
            < 0.005).all()
    days = (f["l_receiptdate"] - f["l_shipdate"]).map(lambda d: d.days)
    assert days.between(1, 30).all()
    now = lineitem.datetime.date(1995, 6, 17)
    assert ((f["l_linestatus"] == "O") == (f["l_shipdate"] > now)).all()
    assert ((f["l_returnflag"] == "N") == (f["l_receiptdate"] > now)).all()
    assert set(f["l_returnflag"]) == {"R", "A", "N"}
    assert set(f["l_shipinstruct"]) == set(lineitem.INSTRUCTIONS)
    assert set(f["l_shipmode"]) == set(lineitem.MODES)
    assert f["l_comment"].str.len().between(10, 43).all()
    assert f["l_comment"].nunique() > 59_000


def test_decimal_buffer_round_trips_negative_values():
    import numpy as np
    vals = np.array([-12345, 0, 1, 10_500_000], dtype=np.int64)
    arr = datagen._arrow_column(vals, "decimal(12,2)")
    assert [int(v.as_py() * 100) for v in arr] == vals.tolist()
