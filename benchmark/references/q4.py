"""TPC-H Q4 (spec cl. 2.4.4), order priority checking, DATE 1993-07-01: the
orders of one quarter that have at least one line item received after its
commit date, counted by priority. The EXISTS subquery is read as what it
says: an order is kept where its key is among the keys of the late lines,
however many of them it has. Columns and rows in the query's order
(``o_orderpriority`` ascending). The answer holds no float column, so
``float_dtype`` changes nothing here."""
from . import day, days


def reference(tables, float_dtype):
    orders, li = tables["orders"], tables["lineitem"]
    od = days(orders.o_orderdate)
    orders = orders[(od >= day("1993-07-01")) & (od < day("1993-10-01"))]
    late = li[days(li.l_commitdate) < days(li.l_receiptdate)]
    kept = orders[orders.o_orderkey.isin(late.l_orderkey.unique())]
    out = kept.groupby("o_orderpriority", as_index=False).agg(
        order_count=("o_orderkey", "size"))
    return out.sort_values("o_orderpriority").reset_index(drop=True)
