"""LINEITEM, all 16 columns (spec cl. 1.4.1). ``l_linestatus`` and
``l_returnflag`` follow dbgen's rule (cl. 4.2.3): 'O' where the ship date is
after CURRENTDATE, else 'F'; 'R' or 'A' where the receipt date is on or
before it, else 'N' — so Q1 has the spec's four groups."""
import numpy as np
import pyarrow as pa

from . import CURRENT_DATE, DATE_RANGE, EPOCH_1992, choice, dates, sentences
from . import strings


def generate(sf: float, seed: int) -> pa.Table:
    n = int(6_000_000 * sf)
    rng = np.random.default_rng(seed)
    n_ord, n_part, n_supp = (max(int(1_500_000 * sf), 1),
                             max(int(200_000 * sf), 1),
                             max(int(10_000 * sf), 1))
    shipdate = EPOCH_1992 + rng.integers(0, DATE_RANGE, size=n)
    orderkey = rng.integers(1, n_ord + 1, size=n) * 4
    partkey = rng.integers(1, n_part + 1, size=n)
    suppkey = rng.integers(1, n_supp + 1, size=n)
    linenumber = rng.integers(1, 8, size=n).astype(np.int32)
    quantity = rng.integers(1, 51, size=n).astype(np.float64)
    extendedprice = np.round(rng.uniform(900.0, 105_000.0, size=n), 2)
    discount = np.round(rng.integers(0, 11, size=n) * 0.01, 2)
    tax = np.round(rng.integers(0, 9, size=n) * 0.01, 2)
    returned = rng.integers(0, 2, size=n)            # 0 'A', 1 'R'
    commitdate = shipdate + rng.integers(-30, 31, size=n)
    receiptdate = shipdate + rng.integers(1, 31, size=n)
    return pa.table({
        "l_orderkey": pa.array(orderkey),
        "l_partkey": pa.array(partkey),
        "l_suppkey": pa.array(suppkey),
        "l_linenumber": pa.array(linenumber),
        "l_quantity": pa.array(quantity),
        "l_extendedprice": pa.array(extendedprice),
        "l_discount": pa.array(discount),
        "l_tax": pa.array(tax),
        "l_returnflag": strings(
            ["A", "R", "N"], np.where(receiptdate <= CURRENT_DATE, returned, 2)),
        "l_linestatus": strings(["F", "O"], shipdate > CURRENT_DATE),
        "l_shipdate": dates(shipdate),
        "l_commitdate": dates(commitdate),
        "l_receiptdate": dates(receiptdate),
        "l_shipinstruct": choice(rng, [
            "DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN"], n),
        "l_shipmode": choice(rng, [
            "AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK"], n),
        "l_comment": sentences(rng, n, words=4, width=43),
    })
