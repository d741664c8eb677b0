"""Expand (rollup/cube/grouping sets), TakeOrderedAndProject, CollectLimit,
and Sample exec nodes (reference: GpuExpandExec.scala, limit.scala,
GpuPoissonSampler; exec rules in GpuOverrides.scala:3481ff)."""
import numpy as np
import pyarrow as pa
import pytest

import spark_rapids_tpu.expr.functions as F
from spark_rapids_tpu.expr.functions import col, lit
from harness import assert_tpu_cpu_equal, data_gen


def _has_node(plan, cls_name: str) -> bool:
    from spark_rapids_tpu.plan.aqe import AdaptiveExec
    if isinstance(plan, AdaptiveExec):
        plan = plan.final_plan()
    if type(plan).__name__ == cls_name:
        return True
    kids = list(plan.children)
    for attr in ("inner", "stage"):  # AQE stage leaves/readers hide subtrees
        sub = getattr(plan, attr, None)
        if sub is not None:
            kids.append(sub)
    kids.extend(getattr(plan, "chain", ()))  # whole-stage fused nodes
    return any(_has_node(c, cls_name) for c in kids)


@pytest.fixture
def gdata(session, rng):
    t = data_gen(rng, 300, {"a": ("int32", 0, 4), "b": ("int64", 0, 3),
                            "v": "float64", "s": "string"})
    return session.create_dataframe(t, num_partitions=2)


def test_rollup_device(session, gdata):
    q = gdata.rollup("a", "b").agg(F.sum(col("v")).alias("s"),
                                   F.count_star().alias("c"))
    plan = session._physical(q.logical, True)
    assert _has_node(plan, "TpuExpandExec") \
        or "Expand" in plan.tree_string(), plan.tree_string()
    assert_tpu_cpu_equal(q)


def test_cube_device(session, gdata):
    assert_tpu_cpu_equal(
        gdata.cube("a", "b").agg(F.avg(col("v")).alias("m")))


def test_grouping_sets(session, gdata):
    q = gdata.grouping_sets([["a"], ["b"], []], "a", "b") \
        .agg(F.min(col("v")).alias("lo"), F.max(col("v")).alias("hi"))
    out = assert_tpu_cpu_equal(q)
    # one row per distinct a (NULL data included) + same for b + grand total
    import pyarrow.compute as pc
    base = gdata.collect(device=False)
    n_a = len(pc.unique(base.column("a")))
    n_b = len(pc.unique(base.column("b")))
    assert out.num_rows == n_a + n_b + 1


def test_rollup_string_grouping_null_literal(session, gdata):
    # rollup over a string column exercises device null string literals
    assert_tpu_cpu_equal(
        gdata.rollup("s", "a").agg(F.count_star().alias("c")))


def test_rollup_distinguishes_real_nulls(session):
    # a NULL data value groups separately from the aggregated-away marker
    t = pa.table({"a": [1, None, 1, None], "v": [1.0, 2.0, 3.0, 4.0]})
    df = session.create_dataframe(t)
    out = assert_tpu_cpu_equal(df.rollup("a").agg(F.sum(col("v")).alias("s")))
    rows = sorted(out.to_pylist(), key=lambda r: (r["a"] is None, r["a"] or 0,
                                                  r["s"]))
    # groups: a=1 (4.0), a=NULL (6.0), total (10.0)
    assert [r["s"] for r in rows] == [4.0, 6.0, 10.0]


def test_take_ordered_device(session, rng):
    t = data_gen(rng, 400, {"k": "int64", "v": "float64", "s": "string"})
    df = session.create_dataframe(t, num_partitions=3)
    q = df.sort(col("v")).limit(7)
    plan = session._physical(q.logical, True)
    assert _has_node(plan, "TpuTakeOrderedExec"), plan.tree_string()
    assert not _has_node(plan, "TpuSortExec")
    assert_tpu_cpu_equal(q, ignore_order=False)
    # descending, string key, nulls present
    assert_tpu_cpu_equal(df.sort(col("s"), ascending=False).limit(9),
                         ignore_order=False)


def test_take_ordered_wide_batch_tournament(session, rng):
    # one batch wider than a top-n chunk (40k rows -> 64k capacity) takes
    # the chunk-winners reduction; heavy ties on the leading key and nulls
    # must come out exactly as the host engine's full sort orders them
    from spark_rapids_tpu.exec.sort import _TOPN_CHUNK
    n = 5 * _TOPN_CHUNK - 960
    k = rng.integers(0, 6, n).astype(np.float64)
    k[rng.random(n) < 0.01] = np.nan
    t = pa.table({"k": pa.array(k, from_pandas=False),
                  "d": pa.array(rng.integers(0, 50, n), mask=rng.random(n) < 0.02),
                  "i": np.arange(n)})
    df = session.create_dataframe(t)
    for q in (df.sort(col("k").desc(), col("d").asc(), col("i").asc()).limit(37),
              df.filter(col("i") % lit(7) == lit(3))
                .sort(col("d").desc(), col("k").asc(), col("i").desc()).limit(5)):
        assert _has_node(session._physical(q.logical, True),
                         "TpuTakeOrderedExec")
        assert_tpu_cpu_equal(q, ignore_order=False)


def test_take_ordered_n_larger_than_data(session, rng):
    t = data_gen(rng, 30, {"v": "float64"})
    df = session.create_dataframe(t, num_partitions=2)
    out = assert_tpu_cpu_equal(df.sort(col("v")).limit(1000),
                               ignore_order=False)
    assert out.num_rows == 30


def test_collect_limit_device(session, rng):
    t = data_gen(rng, 200, {"v": "float64"})
    df = session.create_dataframe(t, num_partitions=3)
    q = df.limit(17)
    plan = session._physical(q.logical, True)
    assert _has_node(plan, "CpuCollectLimitExec") \
        or _has_node(plan, "TpuLocalLimitExec"), plan.tree_string()
    assert q.collect(device=True).num_rows == 17
    assert q.collect(device=False).num_rows == 17


def test_sample_deterministic_and_differential(session, rng):
    t = pa.table({"k": np.arange(1500, dtype=np.int64)})
    df = session.create_dataframe(t, num_partitions=3)
    q = df.sample(0.25, seed=11)
    plan = session._physical(q.logical, True)
    assert _has_node(plan, "TpuSampleExec"), plan.tree_string()
    out = assert_tpu_cpu_equal(q)  # bit-for-bit: same rows both engines
    frac = out.num_rows / 1500
    assert 0.18 < frac < 0.32
    # same seed -> same rows; different seed -> (almost surely) different
    again = df.sample(0.25, seed=11).collect(device=True)
    assert sorted(again.column("k").to_pylist()) \
        == sorted(out.column("k").to_pylist())
    other = df.sample(0.25, seed=12).collect(device=True)
    assert sorted(other.column("k").to_pylist()) \
        != sorted(out.column("k").to_pylist())


def test_sample_after_filter_positions_agree(session, rng):
    t = data_gen(rng, 800, {"k": "int64", "v": "float64"}, null_prob=0.1)
    df = session.create_dataframe(t, num_partitions=2)
    assert_tpu_cpu_equal(df.filter(col("v") > lit(0.0)).sample(0.5, seed=3))


def test_sample_fraction_bounds(session):
    df = session.create_dataframe(pa.table({"a": [1, 2]}))
    with pytest.raises(ValueError):
        df.sample(1.5, seed=1)
    assert df.sample(0.0, seed=1).collect().num_rows == 0
    assert df.sample(1.0, seed=1).collect().num_rows == 2


def test_rollup_aggregates_grouping_column(session):
    """Spark: rollup('a').agg(sum('a')) sums REAL values even in rows where
    'a' is aggregated away — the Expand keeps an un-nulled input copy."""
    df = session.create_dataframe(pa.table({"a": [1, 2, 3]}))
    q = df.rollup("a").agg(F.sum(col("a")).alias("s"),
                           F.count(col("a")).alias("c"))
    out = assert_tpu_cpu_equal(q)
    rows = sorted(out.to_pylist(),
                  key=lambda r: (r["a"] is None, r["a"] or 0))
    assert rows[-1] == {"a": None, "s": 6, "c": 3}
