"""CUSTOMER, all 8 columns (spec cl. 1.4.1). Keys 1..150,000 x sf, every
one of them; ``c_mktsegment`` uniform over the spec's five segments, so Q3's
``c_mktsegment = 'BUILDING'`` keeps a fifth; the phone's country code is the
nation key + 10 (cl. 4.2.3)."""
import numpy as np
import pyarrow as pa

from . import choice, sentences

STREAM = 0x637573   # "cus": this table's own stream of the seed
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY"]


def generate(sf: float, seed: int) -> pa.Table:
    n = max(int(150_000 * sf), 1)
    rng = np.random.default_rng([seed, STREAM])
    custkey = np.arange(1, n + 1, dtype=np.int64)
    nationkey = rng.integers(0, 25, size=n).astype(np.int64)
    phone = (nationkey + 10).astype("U2")
    for part in ("-", rng.integers(100, 1000, size=n).astype("U3"),
                 "-", rng.integers(100, 1000, size=n).astype("U3"),
                 "-", rng.integers(1000, 10000, size=n).astype("U4")):
        phone = np.char.add(phone, part)
    return pa.table({
        "c_custkey": pa.array(custkey),
        "c_name": pa.array(np.char.add(
            "Customer#", np.char.zfill(custkey.astype("U9"), 9))),
        "c_address": sentences(rng, n, words=4, width=40),
        "c_nationkey": pa.array(nationkey),
        "c_phone": pa.array(phone),
        "c_acctbal": pa.array(
            np.round(rng.uniform(-999.99, 9999.99, size=n), 2)),
        "c_mktsegment": choice(rng, SEGMENTS, n),
        "c_comment": sentences(rng, n, words=12, width=117),
    })
