"""The whole query's share of the HBM roofline, in %: the least time the
chips could take to read the query's input once (``input_bytes.py``) over
``device_busy_s_per_query``, the busiest chip's busy time per traced query.
Bandwidth-bound by construction: these queries do a few operations a byte.

Only for cells in which the device sees the query's whole input. Where the
host takes part of the query (Q6's filter runs in the host Parquet reader,
and 2 % of the rows reach the chip) the input counted never passes HBM and
the share means nothing: such a cell is left out of the metric's
``workloads`` in BENCHMARK.json."""


def read(run):
    t = run["trace"]
    if not t.get("busy_s_busiest") or not run.get("input_bytes") \
            or not run.get("peaks"):
        return None
    least_s = run["input_bytes"] / (
        run["peaks"]["hbm_bytes_per_s"] * run["chips"])
    return 100.0 * least_s / (t["busy_s_busiest"] / t["queries"])
