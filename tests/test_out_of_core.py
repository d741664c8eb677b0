"""Out-of-core execution tests (reference: GpuSortExec.scala OutOfCoreSort,
aggregate.scala merge passes, AbstractGpuJoinIterator sub-partitioning):
operators must complete correctly when the device pool is smaller than the
data, with buffers migrating through the spill tiers."""
import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.device import DeviceTable
from spark_rapids_tpu.columnar.host import HostColumn, HostTable
from spark_rapids_tpu.memory.catalog import BufferCatalog, set_catalog
from spark_rapids_tpu.plan.schema import Field, Schema


@pytest.fixture
def small_catalog():
    """Device pool far below the test data size -> forced spills."""
    cat = BufferCatalog(device_limit=60_000, host_limit=40_000)
    set_catalog(cat)
    yield cat
    set_catalog(None)


class _Source:
    def __init__(self, batches, schema):
        self.batches = batches
        self.schema = schema
        self.num_partitions = 1
        self.children = ()

    def execute_columnar(self, pidx):
        yield from self.batches


def _num_batches(n_rows, n_batches, seed=0, extra_cols=0):
    rng = np.random.default_rng(seed)
    per = n_rows // n_batches
    batches, all_a, all_b = [], [], []
    for i in range(n_batches):
        a = rng.integers(-500, 500, per).astype(np.int64)
        b = rng.uniform(-5, 5, per)
        all_a.append(a)
        all_b.append(b)
        cols = [HostColumn(dt.LONG, a), HostColumn(dt.DOUBLE, b)]
        names = ["a", "b"]
        t = HostTable(names, cols)
        batches.append(DeviceTable.from_host(t, min_bucket=8))
    schema = Schema([Field("a", dt.LONG, True), Field("b", dt.DOUBLE, True)])
    return batches, schema, np.concatenate(all_a), np.concatenate(all_b)


def test_out_of_core_sort_spills(small_catalog):
    from spark_rapids_tpu.exec.sort import TpuSortExec
    from spark_rapids_tpu.expr.functions import SortOrder, col
    batches, schema, a, b = _num_batches(6000, 10)
    src = _Source(batches, schema)
    orders = [SortOrder(col("a").expr, True), SortOrder(col("b").expr, True)]
    s = TpuSortExec(src, orders, min_bucket=8, batch_bytes=20_000)
    frames = [HostTable.to_arrow(x.to_host()).to_pandas()
              for x in s.execute_columnar(0)]
    got = pd.concat(frames, ignore_index=True)
    exp = pd.DataFrame({"a": a, "b": b}).sort_values(
        ["a", "b"], kind="stable").reset_index(drop=True)
    assert len(got) == len(exp)
    assert (got["a"].values == exp["a"].values).all()
    assert np.allclose(got["b"].values, exp["b"].values)
    spills = small_catalog.stats()["spill_count"]
    assert sum(spills.values()) > 0, spills


def test_out_of_core_grace_join(small_catalog):
    from spark_rapids_tpu.exec.joins import TpuShuffledHashJoinExec
    rng = np.random.default_rng(1)
    nl, nr = 3000, 2000
    lk = rng.integers(0, 200, nl).astype(np.int64)
    lv = rng.uniform(0, 1, nl)
    rk = rng.integers(0, 200, nr).astype(np.int64)
    rv = rng.uniform(0, 1, nr)
    lbatches = [DeviceTable.from_host(HostTable(
        ["k", "lv"], [HostColumn(dt.LONG, lk[i::3]),
                      HostColumn(dt.DOUBLE, lv[i::3])]), min_bucket=8)
        for i in range(3)]
    rbatches = [DeviceTable.from_host(HostTable(
        ["k", "rv"], [HostColumn(dt.LONG, rk[i::2]),
                      HostColumn(dt.DOUBLE, rv[i::2])]), min_bucket=8)
        for i in range(2)]
    lschema = Schema([Field("k", dt.LONG, True), Field("lv", dt.DOUBLE, True)])
    rschema = Schema([Field("k", dt.LONG, True), Field("rv", dt.DOUBLE, True)])
    left = _Source(lbatches, lschema)
    right = _Source(rbatches, rschema)
    # batch_bytes below the build size -> grace sub-partitioned join
    j = TpuShuffledHashJoinExec(left, right, ["k"], ["k"], "inner", None,
                                merge_keys=True, min_bucket=8,
                                batch_bytes=8_000)
    frames = [HostTable.to_arrow(x.to_host()).to_pandas()
              for x in j.execute_columnar(0)]
    got = pd.concat(frames, ignore_index=True).sort_values(
        ["k", "lv", "rv"]).reset_index(drop=True)
    exp = pd.merge(pd.DataFrame({"k": lk, "lv": lv}),
                   pd.DataFrame({"k": rk, "rv": rv}), on="k").sort_values(
        ["k", "lv", "rv"]).reset_index(drop=True)
    assert len(got) == len(exp)
    assert np.allclose(got["lv"].values, exp["lv"].values)
    assert np.allclose(got["rv"].values, exp["rv"].values)


def test_out_of_core_left_join_grace(small_catalog):
    from spark_rapids_tpu.exec.joins import TpuShuffledHashJoinExec
    rng = np.random.default_rng(5)
    nl, nr = 2000, 1500
    lk = rng.integers(0, 400, nl).astype(np.int64)  # some keys unmatched
    rk = rng.integers(0, 200, nr).astype(np.int64)
    lv = rng.uniform(0, 1, nl)
    rv = rng.uniform(0, 1, nr)
    lschema = Schema([Field("k", dt.LONG, True), Field("lv", dt.DOUBLE, True)])
    rschema = Schema([Field("k", dt.LONG, True), Field("rv", dt.DOUBLE, True)])
    left = _Source([DeviceTable.from_host(HostTable(
        ["k", "lv"], [HostColumn(dt.LONG, lk), HostColumn(dt.DOUBLE, lv)]),
        min_bucket=8)], lschema)
    right = _Source([DeviceTable.from_host(HostTable(
        ["k", "rv"], [HostColumn(dt.LONG, rk), HostColumn(dt.DOUBLE, rv)]),
        min_bucket=8)], rschema)
    j = TpuShuffledHashJoinExec(left, right, ["k"], ["k"], "left", None,
                                merge_keys=True, min_bucket=8,
                                batch_bytes=6_000)
    frames = [HostTable.to_arrow(x.to_host()).to_pandas()
              for x in j.execute_columnar(0)]
    got = pd.concat(frames, ignore_index=True)
    exp = pd.merge(pd.DataFrame({"k": lk, "lv": lv}),
                   pd.DataFrame({"k": rk, "rv": rv}), on="k", how="left")
    assert len(got) == len(exp)
    assert np.isclose(got["lv"].sum(), exp["lv"].sum())
    assert np.isclose(got["rv"].fillna(0).sum(), exp["rv"].fillna(0).sum())


def test_windowed_expand_bounds_output(small_catalog):
    """High-multiplicity join: gather output exceeds the budget and must be
    emitted in probe windows rather than one oversized batch."""
    from spark_rapids_tpu.exec.joins import TpuShuffledHashJoinExec
    nl, nr = 600, 400
    lk = np.zeros(nl, dtype=np.int64)  # every pair matches: 240k rows out
    rk = np.zeros(nr, dtype=np.int64)
    lv = np.arange(nl, dtype=np.float64)
    rv = np.arange(nr, dtype=np.float64)
    lschema = Schema([Field("k", dt.LONG, True), Field("lv", dt.DOUBLE, True)])
    rschema = Schema([Field("k", dt.LONG, True), Field("rv", dt.DOUBLE, True)])
    left = _Source([DeviceTable.from_host(HostTable(
        ["k", "lv"], [HostColumn(dt.LONG, lk), HostColumn(dt.DOUBLE, lv)]),
        min_bucket=8)], lschema)
    right = _Source([DeviceTable.from_host(HostTable(
        ["k", "rv"], [HostColumn(dt.LONG, rk), HostColumn(dt.DOUBLE, rv)]),
        min_bucket=8)], rschema)
    j = TpuShuffledHashJoinExec(left, right, ["k"], ["k"], "inner", None,
                                merge_keys=True, min_bucket=8,
                                batch_bytes=500_000)
    max_out = j._max_out_rows()
    assert max_out < nl * nr
    total = 0
    nbatches = 0
    for x in j.execute_columnar(0):
        n = int(x.num_rows)
        assert x.capacity <= max(max_out * 2, 8), \
            f"batch capacity {x.capacity} blew past budget {max_out}"
        total += n
        nbatches += 1
    assert total == nl * nr
    assert nbatches > 1


def test_aggregate_merge_state_bounded(small_catalog):
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.expr.aggregates import Sum
    from spark_rapids_tpu.expr.base import AttributeReference
    from spark_rapids_tpu.plan.physical import AggSpec
    rng = np.random.default_rng(2)
    batches = []
    per = 512
    nb = 12
    ks, vs = [], []
    for i in range(nb):
        k = rng.integers(0, 40, per).astype(np.int64)
        v = rng.uniform(0, 1, per)
        ks.append(k)
        vs.append(v)
        batches.append(DeviceTable.from_host(HostTable(
            ["k", "_agg0_in0"], [HostColumn(dt.LONG, k),
                                 HostColumn(dt.DOUBLE, v)]), min_bucket=8))
    schema = Schema([Field("k", dt.LONG, True),
                     Field("_agg0_in0", dt.DOUBLE, True)])
    src = _Source(batches, schema)
    spec = AggSpec("_agg0", Sum(AttributeReference("_agg0_in0", dt.DOUBLE)))
    agg = TpuHashAggregateExec(src, ["k"], [spec], "partial")
    outs = list(agg.execute_columnar(0))
    assert len(outs) == 1
    out = outs[0]
    # running state shrank to the group bucket, not sum of batch capacities
    assert out.capacity < per * nb
    h = out.to_host()
    got = pd.DataFrame({"k": h.column("k").values,
                        "s": h.column("_agg0_sum").values}) \
        .sort_values("k").reset_index(drop=True)
    exp = pd.DataFrame({"k": np.concatenate(ks),
                        "v": np.concatenate(vs)}).groupby("k")["v"].sum() \
        .reset_index().rename(columns={"v": "s"})
    assert np.allclose(got["s"].values, exp["s"].values)


def test_tpch_query_under_memory_pressure(small_catalog):
    """End-to-end: a TPC-H query completes with the pool below data size."""
    from spark_rapids_tpu.session import TpuSession
    from spark_rapids_tpu.tools import tpch
    sess = TpuSession({"spark.rapids.tpu.batchRowsMinBucket": 8,
                       "spark.rapids.sql.batchSizeBytes": 50_000})
    lineitem = tpch.gen_lineitem(0, seed=0, rows=4000)
    df = sess.create_dataframe(lineitem, num_partitions=4)
    t = {"lineitem": df}
    got = tpch.q1(t).collect(device=True).to_pandas()
    exp = tpch.q1(t).collect(device=False).to_pandas()
    assert len(got) == len(exp)
    for c in got.columns:
        if got[c].dtype.kind in "fi":
            assert np.allclose(got[c].values.astype(float),
                               exp[c].values.astype(float)), c
        else:
            assert (got[c].values == exp[c].values).all(), c


# ---------------------------------------------------------------------------
# Runtime OOM -> spill -> retry (reference: DeviceMemoryEventHandler.scala:33)
# ---------------------------------------------------------------------------
def _spillable_tables(cat, n=4, rows=512):
    rng = np.random.default_rng(0)
    handles = []
    for i in range(n):
        ht = HostTable(["a"], [HostColumn(dt.DOUBLE, rng.normal(size=rows))])
        handles.append(cat.register(DeviceTable.from_host(ht, 64)))
    return handles


def test_runtime_oom_spills_and_retries():
    """A RESOURCE_EXHAUSTED from the runtime triggers one synchronous
    spill + retry at the jit chokepoint — the query completes."""
    from spark_rapids_tpu.memory.catalog import BufferCatalog, set_catalog
    from spark_rapids_tpu.memory.retry import wrap_jit
    cat = BufferCatalog(device_limit=10**9, host_limit=10**9)
    set_catalog(cat)
    try:
        handles = _spillable_tables(cat)
        calls = {"n": 0}

        def flaky(x):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError(
                    "RESOURCE_EXHAUSTED: Out of memory while trying to "
                    "allocate 123456 bytes.")
            return x + 1

        out = wrap_jit(flaky)(41)
        assert out == 42 and calls["n"] == 2
        assert cat.oom_events == 1
        assert sum(cat.spill_count.values()) > 0, cat.spill_count
        # spilled buffers restore transparently on next access
        assert handles[0].get().num_rows == 512
    finally:
        set_catalog(None)


def test_runtime_oom_second_failure_dumps_diagnostics():
    from spark_rapids_tpu.memory.catalog import BufferCatalog, set_catalog
    from spark_rapids_tpu.memory.retry import wrap_jit
    cat = BufferCatalog(device_limit=10**9, host_limit=10**9)
    set_catalog(cat)
    try:
        _spillable_tables(cat, n=2)

        def always_oom(_):
            raise RuntimeError("RESOURCE_EXHAUSTED: Out of memory")

        with pytest.raises(RuntimeError, match="catalog state"):
            wrap_jit(always_oom)(0)
        # non-OOM errors pass through untouched
        def boom(_):
            raise ValueError("unrelated")
        with pytest.raises(ValueError, match="unrelated"):
            wrap_jit(boom)(0)
    finally:
        set_catalog(None)
