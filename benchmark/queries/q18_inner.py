"""The subquery of TPC-H Q18 (spec 2.4.18, "Large Volume Customer"), with
the validation substitution QUANTITY 300 (2.4.18.3):

    select l_orderkey, sum(l_quantity) as sum_qty from lineitem
    group by l_orderkey having sum(l_quantity) > 300

A GROUP BY on a nearly unique key: 1,500,000 orders at scale factor 1, of
which the HAVING keeps some tens.  The outer query (CUSTOMER and ORDERS
joined to it, ORDER BY, LIMIT) is left out: the harness gives a query one
table.  The file's shape is described in ``q6.py``.  There is no ORDER BY,
so the rows compare as a multiset.  ``l_quantity`` is a whole number from 1
to 50 and an order has at most seven lines, so every sum is exact in
float64 (and in float32: the configuration says what that means for
``correct``) and ``> 300`` has no rounding to hide behind.
"""
TABLE = "lineitem"
COLUMNS = ("l_orderkey", "l_quantity")
QUANTITY = 300.0


def build(df, quantity: float = QUANTITY):
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.expressions import Cast, col, lit, sum_
    return (df.group_by("l_orderkey")
            .agg(sum_(Cast(col("l_quantity"), T.DOUBLE)).alias("sum_qty"))
            .filter(col("sum_qty") > lit(float(quantity))))


def reference(li, quantity: float = QUANTITY) -> list:
    sums = li.groupby("l_orderkey", sort=False)["l_quantity"].sum()
    kept = sums[sums > sums.dtype.type(quantity)]
    return [(int(k), float(v)) for k, v in kept.items()]
