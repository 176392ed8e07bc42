"""TPC-H Q6 (spec 2.4.6) with the validation substitutions: DATE 1994-01-01,
DISCOUNT 0.06, QUANTITY 24.

    select sum(l_extendedprice * l_discount) as revenue from lineitem
    where l_shipdate >= '1994-01-01' and l_shipdate < '1995-01-01'
      and l_discount between 0.05 and 0.07 and l_quantity < 24

A query file gives: ``TABLE``, ``COLUMNS`` (what the query reads, for the
least-bytes count), ``build(df)`` over the program's DataFrame, and
``reference(frame)``: the same semantics in plain pandas over a frame of
``COLUMNS`` (decimals as floats, dates as days), which imports nothing of
the program.  ``build`` is copied from ``spark_rapids_tpu/testing/tpch.py``.
"""
from benchmark.tables.lineitem import days

TABLE = "lineitem"
COLUMNS = ("l_shipdate", "l_discount", "l_quantity", "l_extendedprice")


def build(df):
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.expressions import Cast, col, lit, sum_
    dec = T.DecimalType(12, 2)
    price = Cast(col("l_extendedprice"), T.DOUBLE)
    disc = Cast(col("l_discount"), T.DOUBLE)
    return (df.filter(
                (col("l_shipdate") >= lit(days(1994, 1, 1), T.DATE))
                & (col("l_shipdate") < lit(days(1995, 1, 1), T.DATE))
                & (col("l_discount") >= lit(5, dec))
                & (col("l_discount") <= lit(7, dec))
                & (col("l_quantity") < lit(2400, dec)))
            .agg((sum_(price * disc)).alias("revenue")))


def reference(li) -> list:
    # 0.045 / 0.075 / 23.5 sit between the two-decimal values, so the
    # predicate is the same in float64 and in the control's float32
    sel = li[(li["l_shipdate"] >= days(1994, 1, 1))
             & (li["l_shipdate"] < days(1995, 1, 1))
             & (li["l_discount"] > 0.045) & (li["l_discount"] < 0.075)
             & (li["l_quantity"] < 23.5)]
    if not len(sel):
        return [(None,)]
    return [(float((sel["l_extendedprice"] * sel["l_discount"]).sum()),)]
