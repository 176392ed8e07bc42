"""The three LINEITEM instances of TPC-H Q21 (spec 2.4.21, "Suppliers Who
Kept Orders Waiting"):

    select s_name, count(*) as numwait
    from supplier, lineitem l1, orders, nation
    where s_suppkey = l1.l_suppkey and o_orderkey = l1.l_orderkey
      and o_orderstatus = 'F' and l1.l_receiptdate > l1.l_commitdate
      and exists (select * from lineitem l2
                  where l2.l_orderkey = l1.l_orderkey
                    and l2.l_suppkey <> l1.l_suppkey)
      and not exists (select * from lineitem l3
                      where l3.l_orderkey = l1.l_orderkey
                        and l3.l_suppkey <> l1.l_suppkey
                        and l3.l_receiptdate > l3.l_commitdate)
      and s_nationkey = n_nationkey and n_name = '[NATION]'
    group by s_name order by numwait desc, s_name  -- first 100 rows

What is run: ``l1`` = the late lines (receipt after commit), kept where
another supplier has a line in the same order (EXISTS: a left semi-join on
``l_orderkey`` with the residual ``<>``) and no other supplier has a LATE
line in it (NOT EXISTS: a left anti-join with the same residual), counted by
supplier, ordered by ``numwait`` descending, then supplier.

What is left out: SUPPLIER, ORDERS and NATION are not written (the harness
gives a query one table), so ``o_orderstatus = 'F'`` and the NATION filter
do not thin ``l1`` (the joins' probe side is larger than the spec's, not
smaller) and ``l_suppkey`` stands for ``s_name`` (one to one); the spec's
"first 100 rows" is left out, so that every supplier's count is held to the
reference.  The file's shape is described in ``q6.py``.  Every field is an
integer: nothing here rounds.
"""
TABLE = "lineitem"
COLUMNS = ("l_orderkey", "l_suppkey", "l_commitdate", "l_receiptdate")
ORDERED = True


def build(df):
    from spark_rapids_tpu.expressions import col, count
    from spark_rapids_tpu.kernels.sort import SortOrder
    late = df.filter(col("l_receiptdate") > col("l_commitdate"))
    l1 = late.select(col("l_orderkey"), col("l_suppkey"))
    l2 = df.select(col("l_orderkey").alias("o2"),
                   col("l_suppkey").alias("s2"))
    l3 = late.select(col("l_orderkey").alias("o3"),
                     col("l_suppkey").alias("s3"))
    return (l1.join(l2, on=([col("l_orderkey")], [col("o2")]),
                    how="left_semi", condition=col("s2") != col("l_suppkey"))
            .join(l3, on=([col("l_orderkey")], [col("o3")]),
                  how="left_anti", condition=col("s3") != col("l_suppkey"))
            .group_by("l_suppkey").agg(count().alias("numwait"))
            .order_by(("numwait", SortOrder(False)),
                      ("l_suppkey", SortOrder(True))))


def reference(li) -> list:
    """Not two joins: a late line is kept when its order has more than one
    distinct supplier and exactly one distinct supplier with a late line
    (its own)."""
    late = li["l_receiptdate"] > li["l_commitdate"]
    suppliers = li.groupby("l_orderkey", sort=False)["l_suppkey"].nunique()
    late_suppliers = (li[late].groupby("l_orderkey", sort=False)["l_suppkey"]
                      .nunique())
    orders = late_suppliers.index[
        (late_suppliers == 1)
        & (suppliers.reindex(late_suppliers.index) > 1)]
    kept = li[late & li["l_orderkey"].isin(orders)]
    numwait = kept.groupby("l_suppkey", sort=False).size()
    out = numwait.reset_index(name="numwait").sort_values(
        ["numwait", "l_suppkey"], ascending=[False, True], kind="stable")
    return [(int(s), int(n)) for s, n in
            zip(out["l_suppkey"].to_numpy(), out["numwait"].to_numpy())]
