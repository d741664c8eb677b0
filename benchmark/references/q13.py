"""TPC-H Q13 (spec cl. 2.4.13), customer distribution, WORD1 = special and
WORD2 = requests: every customer with the number of its orders whose comment
is NOT LIKE '%special%requests%', then the number of customers with each such
count. The LEFT OUTER JOIN is read as what it says: a customer with no kept
order is kept once with a count of 0, and ``count(o_orderkey)`` counts only
the orders it matched. A null comment is neither LIKE nor NOT LIKE, so its
order is dropped. Columns ``c_count``, ``custdist``; rows by ``custdist``
descending, then ``c_count`` descending. The answer holds no float column,
so ``float_dtype`` changes nothing here."""
import re


def reference(tables, float_dtype):
    cust, orders = tables["customer"], tables["orders"]
    like = orders.o_comment.str.contains("special.*requests", regex=True,
                                         flags=re.DOTALL, na=True)
    kept = orders[~like.astype(bool)]
    joined = cust.merge(kept, how="left", left_on="c_custkey",
                        right_on="o_custkey")
    per_cust = joined.groupby("c_custkey", as_index=False).agg(
        c_count=("o_orderkey", "count"))
    out = per_cust.groupby("c_count", as_index=False).agg(
        custdist=("c_custkey", "size"))
    return out.sort_values(["custdist", "c_count"], ascending=[False, False],
                           kind="stable").reset_index(drop=True)
