"""TPC-H Q1 (spec cl. 2.4.1), pricing summary report, delta = 90 days."""
from . import day, days


def reference(tables, float_dtype):
    one = float_dtype(1.0)
    li = tables["lineitem"]
    li = li[days(li.l_shipdate) <= day("1998-09-02")]
    li = li.assign(disc_price=li.l_extendedprice * (one - li.l_discount))
    li = li.assign(charge=li.disc_price * (one + li.l_tax))
    out = li.groupby(["l_returnflag", "l_linestatus"], as_index=False).agg(
        sum_qty=("l_quantity", "sum"),
        sum_base_price=("l_extendedprice", "sum"),
        sum_disc_price=("disc_price", "sum"),
        sum_charge=("charge", "sum"),
        avg_qty=("l_quantity", "mean"),
        avg_price=("l_extendedprice", "mean"),
        avg_disc=("l_discount", "mean"),
        count_order=("l_quantity", "size"))
    return out.sort_values(["l_returnflag", "l_linestatus"]) \
              .reset_index(drop=True)
