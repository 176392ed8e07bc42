"""TPC-H Q6 (spec 2.4.6), by default with the validation substitutions: DATE
1994-01-01, DISCOUNT 0.06, QUANTITY 24.

    select sum(l_extendedprice * l_discount) as revenue from lineitem
    where l_shipdate >= date 'DATE'
      and l_shipdate < date 'DATE' + interval '1' year
      and l_discount between DISCOUNT - 0.01 and DISCOUNT + 0.01
      and l_quantity < QUANTITY

A query file gives: ``TABLE``, ``COLUMNS`` (what the query reads, for the
least-bytes count), ``build(df, **sub)`` over the program's DataFrame, and
``reference(frame, **sub)``: the same semantics in plain pandas over a frame
of ``COLUMNS`` (decimals as floats, dates as days), which imports nothing of
the program.  ``SUBSTITUTIONS``, where a file has it, gives the spec's range
of each substitution parameter; both functions take them as keywords, with
the validation values as defaults.  ``build`` is copied from
``spark_rapids_tpu/testing/tpch.py``.
"""
from benchmark.tables.lineitem import days

TABLE = "lineitem"
COLUMNS = ("l_shipdate", "l_discount", "l_quantity", "l_extendedprice")
# spec 2.4.6.3: DATE the first of January of a year in [1993, 1997];
# DISCOUNT in [0.02, 0.09], here in hundredths; QUANTITY in [24, 25]
SUBSTITUTIONS = {"year": list(range(1993, 1998)),
                 "discount": list(range(2, 10)),
                 "quantity": [24, 25]}


def build(df, year=1994, discount=6, quantity=24):
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.expressions import Cast, col, lit, sum_
    dec = T.DecimalType(12, 2)
    price = Cast(col("l_extendedprice"), T.DOUBLE)
    disc = Cast(col("l_discount"), T.DOUBLE)
    return (df.filter(
                (col("l_shipdate") >= lit(days(year, 1, 1), T.DATE))
                & (col("l_shipdate") < lit(days(year + 1, 1, 1), T.DATE))
                & (col("l_discount") >= lit(discount - 1, dec))
                & (col("l_discount") <= lit(discount + 1, dec))
                & (col("l_quantity") < lit(100 * quantity, dec)))
            .agg((sum_(price * disc)).alias("revenue")))


def reference(li, year=1994, discount=6, quantity=24) -> list:
    # the bounds sit half a step between the two-decimal values (0.045 /
    # 0.075 / 23.5 by default), so the predicate is the same in float64 and
    # in the control's float32
    sel = li[(li["l_shipdate"] >= days(year, 1, 1))
             & (li["l_shipdate"] < days(year + 1, 1, 1))
             & (li["l_discount"] > (discount - 1.5) / 100)
             & (li["l_discount"] < (discount + 1.5) / 100)
             & (li["l_quantity"] < quantity - 0.5)]
    if not len(sel):
        return [(None,)]
    return [(float((sel["l_extendedprice"] * sel["l_discount"]).sum()),)]
