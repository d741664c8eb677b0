"""ORDERS, all 9 columns (spec cl. 1.4.1). One order per key that
``lineitem.py`` can draw (``l_orderkey`` is 4 x a number in 1..1,500,000 x
sf), so every line item finds its order; ``o_custkey`` skips every third
customer key, as cl. 4.2.3 has it, and never leaves 1..150,000 x sf, so
every order finds its customer. ``o_orderdate`` is uniform over STARTDATE ..
ENDDATE - 151 days: Q3's ``o_orderdate < 1995-03-15`` keeps 48.6 %. The
order status is drawn, not derived from the order's line items.

Not dbgen's: there ``l_shipdate`` is the order's date + 1..121 days, so an
order before 1995-03-15 has a line shipped after it only where the two dates
straddle the day, and Q3's join keeps 0.5 % of ``lineitem`` in ~11 k groups.
``lineitem.py`` draws ``l_shipdate`` on its own, uniform over seven years, so
no order date here could restore that: the join keeps 5 % (~130 k groups at
SF 1), ten times the spec's join output and group-by width. The repair is
``lineitem.py``'s (ship date from the order's date), a `benchmark` change
(PERF.md section 7)."""
import numpy as np
import pyarrow as pa

from . import DATE_RANGE, EPOCH_1992, choice, dates, sentences, strings

STREAM = 0x6F7264   # "ord": this table's own stream of the seed


def generate(sf: float, seed: int) -> pa.Table:
    n = max(int(1_500_000 * sf), 1)
    rng = np.random.default_rng([seed, STREAM])
    n_cust, n_clerk = max(int(150_000 * sf), 1), max(int(1_000 * sf), 1)
    # 1, 2, 4, 5, 7, 8, ...: two thirds of the customers have orders
    k = rng.integers(0, max(n_cust - n_cust // 3, 1), size=n)
    custkey = np.minimum(k + k // 2 + 1, n_cust)
    return pa.table({
        "o_orderkey": pa.array(np.arange(1, n + 1, dtype=np.int64) * 4),
        "o_custkey": pa.array(custkey.astype(np.int64)),
        "o_orderstatus": choice(rng, ["F", "O", "P"], n),
        "o_totalprice": pa.array(
            np.round(rng.uniform(850.0, 560_000.0, size=n), 2)),
        "o_orderdate": dates(
            EPOCH_1992 + rng.integers(0, DATE_RANGE - 151, size=n)),
        "o_orderpriority": choice(rng, [
            "1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"], n),
        "o_clerk": strings([f"Clerk#{i:09d}" for i in range(1, n_clerk + 1)],
                           rng.integers(0, n_clerk, size=n)),
        "o_shippriority": pa.array(np.zeros(n, dtype=np.int32)),
        "o_comment": sentences(rng, n, words=8, width=78),
    })
