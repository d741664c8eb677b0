"""TPC-H Q6 (spec cl. 2.4.6), forecasting revenue change: 1994, discount
0.06 +- 0.01, quantity < 24."""
import pandas as pd

from . import day, days


def reference(tables, float_dtype):
    li = tables["lineitem"]
    sd = days(li.l_shipdate)
    m = ((sd >= day("1994-01-01")) & (sd < day("1995-01-01"))
         & (li.l_discount >= float_dtype(0.05))
         & (li.l_discount <= float_dtype(0.07))
         & (li.l_quantity < float_dtype(24.0)))
    product = (li.l_extendedprice[m] * li.l_discount[m]).to_numpy()
    # the sum is taken, and kept, in the precision asked for
    return pd.DataFrame({"revenue": [product.sum(dtype=float_dtype)]})
