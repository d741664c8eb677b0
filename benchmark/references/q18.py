"""TPC-H Q18 (spec cl. 2.4.18), large volume customer, QUANTITY 300: the
orders whose lines sum to more than 300 in ``l_quantity``, each with its
customer and that sum, the hundred of largest ``o_totalprice``. HAVING and
IN are read as what they say: an order is kept where the sum over ALL its
lines exceeds 300, and it appears once. Columns in the order the program's
query gives them (the group-by keys, then ``sum_qty``); rows by
``o_totalprice`` descending, then ``o_orderdate`` and ``o_orderkey``
ascending, as the query sorts them."""

COLUMNS = ["c_name", "c_custkey", "o_orderkey", "o_orderdate", "o_totalprice",
           "sum_qty"]


def reference(tables, float_dtype):
    cust, orders, li = (tables[t] for t in ("customer", "orders", "lineitem"))
    qty = li.groupby("l_orderkey", as_index=False).agg(
        sum_qty=("l_quantity", "sum"))
    big = qty[qty.sum_qty > float_dtype(300.0)]
    kept = orders[orders.o_orderkey.isin(big.l_orderkey)]
    out = cust.merge(kept, left_on="c_custkey", right_on="o_custkey") \
              .merge(big, left_on="o_orderkey", right_on="l_orderkey")
    return out.sort_values(["o_totalprice", "o_orderdate", "o_orderkey"],
                           ascending=[False, True, True], kind="stable") \
              .head(100)[COLUMNS].reset_index(drop=True)
