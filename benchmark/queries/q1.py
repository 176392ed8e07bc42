"""TPC-H Q1 (spec 2.4.1), the pricing summary report, by default with the
validation substitution DELTA 90:

    select l_returnflag, l_linestatus, sum(l_quantity),
           sum(l_extendedprice), sum(l_extendedprice * (1 - l_discount)),
           sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)),
           avg(l_quantity), avg(l_extendedprice), avg(l_discount), count(*)
    from lineitem where l_shipdate <= date '1998-12-01' - interval 'DELTA' day
    group by l_returnflag, l_linestatus
    order by l_returnflag, l_linestatus

The file's shape is described in ``q6.py``.  ``ORDERED`` says that the rows
are compared in the order in which they come.  Sums and means are float64
over the decimal columns cast to double, as the configuration states.
"""
import pandas as pd

from benchmark.tables.lineitem import days

TABLE = "lineitem"
COLUMNS = ("l_shipdate", "l_discount", "l_quantity", "l_extendedprice",
           "l_tax", "l_returnflag", "l_linestatus")
ORDERED = True
# spec 2.4.1.3: DELTA in [60, 120] days
SUBSTITUTIONS = {"delta": list(range(60, 121))}


def build(df, delta=90):
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.expressions import Cast, avg, col, count, lit, sum_
    qty = Cast(col("l_quantity"), T.DOUBLE)
    price = Cast(col("l_extendedprice"), T.DOUBLE)
    disc = Cast(col("l_discount"), T.DOUBLE)
    tax = Cast(col("l_tax"), T.DOUBLE)
    disc_price = price * (lit(1.0) - disc)
    charge = disc_price * (lit(1.0) + tax)
    return (df.filter(col("l_shipdate")
                      <= lit(days(1998, 12, 1) - delta, T.DATE))
            .group_by("l_returnflag", "l_linestatus")
            .agg(sum_(qty).alias("sum_qty"),
                 sum_(price).alias("sum_base_price"),
                 sum_(disc_price).alias("sum_disc_price"),
                 sum_(charge).alias("sum_charge"),
                 avg(qty).alias("avg_qty"),
                 avg(price).alias("avg_price"),
                 avg(disc).alias("avg_disc"),
                 count().alias("count_order"))
            .order_by("l_returnflag", "l_linestatus"))


def reference(li, delta=90) -> list:
    sel = li[li["l_shipdate"] <= days(1998, 12, 1) - delta].copy()
    one = sel["l_discount"].dtype.type(1.0)
    sel["disc_price"] = sel["l_extendedprice"] * (one - sel["l_discount"])
    sel["charge"] = sel["disc_price"] * (one + sel["l_tax"])
    g = sel.groupby(["l_returnflag", "l_linestatus"], sort=True,
                    observed=True)
    out = pd.DataFrame({
        "sum_qty": g["l_quantity"].sum(),
        "sum_base_price": g["l_extendedprice"].sum(),
        "sum_disc_price": g["disc_price"].sum(),
        "sum_charge": g["charge"].sum(),
        "avg_qty": g["l_quantity"].mean(),
        "avg_price": g["l_extendedprice"].mean(),
        "avg_disc": g["l_discount"].mean(),
        "count_order": g.size()}).reset_index()
    return [(str(r[0]), str(r[1]), *map(float, r[2:9]), int(r[9]))
            for r in out.itertuples(index=False)]
