"""Deterministic TPC-H-style data generation + query definitions.

The analog of the reference's datagen module (datagen/.../bigDataGen.scala:
deterministic, seed-stable, skew-controllable data for scale tests) plus
the mortgage/scaletest benchmark harness role
(integration_tests/.../mortgage/MortgageSpark.scala).

Column value distributions follow the TPC-H spec shapes (uniform discounts
0.00-0.10, quantities 1-50, shipdate 1992-1998) so selectivities match the
official queries; this is generation from the spec, not a copy of any
generator code.
"""
from __future__ import annotations

import datetime
from typing import Dict, List, Optional

import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import (ColumnarBatch, Schema,
                                              host_scalar)

EPOCH = datetime.date(1970, 1, 1)


def _days(y, m, d) -> int:
    return (datetime.date(y, m, d) - EPOCH).days


# Money/quantity columns are decimal(12,2) — the official TPC-H schema.
# This matters doubly on TPU: the chip emulates float64 (double-double
# over f32 pairs) and is NOT bit-exact, so predicate boundaries like
# `l_discount >= 0.05` can flip whole value buckets under f64; decimal64
# columns are int64 on device, making filters/joins/group-bys exact.  Sums
# of products still run in f64 (within differential tolerance).
DEC12_2 = T.DecimalType(12, 2)

LINEITEM_SCHEMA = Schema.of(
    l_orderkey=T.LONG,
    l_partkey=T.LONG,
    l_suppkey=T.LONG,
    l_linenumber=T.INT,
    l_quantity=DEC12_2,
    l_extendedprice=DEC12_2,
    l_discount=DEC12_2,
    l_tax=DEC12_2,
    l_shipdate=T.DATE,
    l_commitdate=T.DATE,
    l_receiptdate=T.DATE,
)

# TPC-H SF1 lineitem is ~6M rows; rows_per_sf lets tests dial size down
ROWS_PER_SF = 6_001_215


def lineitem_host_chunks(num_rows: int, seed: int = 42,
                         batch_rows: int = 1 << 20):
    """Yield lineitem as host numpy column dicts, ``batch_rows`` rows at a
    time (TPC-H value distributions).  ``gen_lineitem`` uploads these;
    file-backed callers (chip_smoke.py) write them to Parquet instead."""
    remaining = num_rows
    chunk_id = 0
    while remaining > 0:
        n = min(batch_rows, remaining)
        rng = np.random.RandomState(seed + chunk_id * 7919)
        orderkey = rng.randint(1, max(num_rows // 4, 2), n).astype(np.int64)
        partkey = rng.randint(1, 200_000, n).astype(np.int64)
        suppkey = rng.randint(1, 10_000, n).astype(np.int64)
        linenumber = rng.randint(1, 8, n).astype(np.int32)
        # unscaled decimal(12,2) ints: value = unscaled / 100
        quantity = (rng.randint(1, 51, n) * 100).astype(np.int64)
        extendedprice = np.round(
            rng.uniform(900.0, 105_000.0, n) * 100).astype(np.int64)
        discount = rng.randint(0, 11, n).astype(np.int64)
        tax = rng.randint(0, 9, n).astype(np.int64)
        ship_lo, ship_hi = _days(1992, 1, 2), _days(1998, 12, 1)
        shipdate = rng.randint(ship_lo, ship_hi, n).astype(np.int32)
        commitdate = shipdate + rng.randint(-30, 31, n).astype(np.int32)
        receiptdate = shipdate + rng.randint(1, 31, n).astype(np.int32)
        yield {
            "l_orderkey": orderkey,
            "l_partkey": partkey,
            "l_suppkey": suppkey,
            "l_linenumber": linenumber,
            "l_quantity": quantity,
            "l_extendedprice": extendedprice,
            "l_discount": discount,
            "l_tax": tax,
            "l_shipdate": shipdate,
            "l_commitdate": commitdate,
            "l_receiptdate": receiptdate,
        }
        remaining -= n
        chunk_id += 1


def gen_lineitem(num_rows: int, seed: int = 42,
                 batch_rows: int = 1 << 20) -> List[ColumnarBatch]:
    """Generate lineitem device batches with TPC-H value distributions."""
    from spark_rapids_tpu.columnar.column import DeviceColumn, round_up_pow2
    out = []
    for cols in lineitem_host_chunks(num_rows, seed, batch_rows):
        n = len(cols["l_orderkey"])
        cap = round_up_pow2(n)
        device_cols = tuple(
            DeviceColumn.from_numpy(cols[name], dt, capacity=cap)
            for name, dt in zip(LINEITEM_SCHEMA.names, LINEITEM_SCHEMA.dtypes))
        out.append(ColumnarBatch(device_cols, host_scalar(n),
                                 LINEITEM_SCHEMA))
    return out


def q6(df):
    """TPC-H Q6: forecast revenue change.

    select sum(l_extendedprice * l_discount) as revenue from lineitem
    where l_shipdate >= '1994-01-01' and l_shipdate < '1995-01-01'
      and l_discount between 0.05 and 0.07 and l_quantity < 24
    """
    from spark_rapids_tpu.expressions import Cast, col, lit, sum_
    d94 = _days(1994, 1, 1)
    d95 = _days(1995, 1, 1)
    # decimal predicates compare unscaled int64 on device (exact on TPU);
    # the product runs in f64 (decimal(12,2)^2 would need decimal128)
    price = Cast(col("l_extendedprice"), T.DOUBLE)
    disc = Cast(col("l_discount"), T.DOUBLE)
    return (df.filter(
                (col("l_shipdate") >= lit(d94, T.DATE))
                & (col("l_shipdate") < lit(d95, T.DATE))
                & (col("l_discount") >= lit(5, DEC12_2))
                & (col("l_discount") <= lit(7, DEC12_2))
                & (col("l_quantity") < lit(2400, DEC12_2)))
            .agg((sum_(price * disc)).alias("revenue")))


def q1(df):
    """TPC-H Q1: pricing summary report (scan + filter + wide group-agg)."""
    from spark_rapids_tpu.expressions import Cast, avg, col, count, lit, sum_
    cutoff = _days(1998, 9, 2)
    qty = Cast(col("l_quantity"), T.DOUBLE)
    price = Cast(col("l_extendedprice"), T.DOUBLE)
    disc = Cast(col("l_discount"), T.DOUBLE)
    tax = Cast(col("l_tax"), T.DOUBLE)
    disc_price = price * (lit(1.0) - disc)
    charge = disc_price * (lit(1.0) + tax)
    return (df.filter(col("l_shipdate") <= lit(cutoff, T.DATE))
            .group_by("l_linenumber")     # stand-in flags until strings land
            .agg(sum_(qty).alias("sum_qty"),
                 sum_(price).alias("sum_base_price"),
                 sum_(disc_price).alias("sum_disc_price"),
                 sum_(charge).alias("sum_charge"),
                 avg(qty).alias("avg_qty"),
                 avg(price).alias("avg_price"),
                 avg(disc).alias("avg_disc"),
                 count().alias("count_order")))
