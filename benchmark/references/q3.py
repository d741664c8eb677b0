"""TPC-H Q3 (spec cl. 2.4.3), shipping priority: segment BUILDING, date
1995-03-15, the ten orders of largest revenue. Columns in the order the
program's query gives them (the group-by keys, then ``revenue``); rows by
``revenue`` descending, then ``o_orderdate`` ascending, as the query sorts
them."""
from . import day, days


def reference(tables, float_dtype):
    one = float_dtype(1.0)
    cust, orders, li = (tables[t] for t in ("customer", "orders", "lineitem"))
    cust = cust[cust.c_mktsegment == "BUILDING"]
    orders = orders[days(orders.o_orderdate) < day("1995-03-15")]
    li = li[days(li.l_shipdate) > day("1995-03-15")]
    joined = cust.merge(orders, left_on="c_custkey", right_on="o_custkey") \
                 .merge(li, left_on="o_orderkey", right_on="l_orderkey")
    joined = joined.assign(
        revenue=joined.l_extendedprice * (one - joined.l_discount))
    # an order has one date and one priority: l_orderkey alone is the group
    out = joined.groupby("l_orderkey", as_index=False).agg(
        o_orderdate=("o_orderdate", "first"),
        o_shippriority=("o_shippriority", "first"),
        revenue=("revenue", "sum"))
    return out.sort_values(["revenue", "o_orderdate"],
                           ascending=[False, True], kind="stable") \
              .head(10).reset_index(drop=True)
