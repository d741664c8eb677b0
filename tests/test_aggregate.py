"""Aggregation differential tests (reference: HashAggregatesSuite +
hash_aggregate_test.py)."""
import pyarrow as pa
import pytest

from spark_rapids_tpu.expr.functions import (avg, col, count, count_star,
                                             first, last, lit, max as fmax,
                                             min as fmin, stddev_pop,
                                             stddev_samp, sum as fsum,
                                             var_pop, var_samp)
from harness import assert_tpu_cpu_equal, data_gen, jaxpr_eqns as _eqns


@pytest.fixture
def df(session, rng):
    t = data_gen(rng, 500, {
        "k1": ("int32", 0, 5), "k2": ("int64", 0, 3), "fk": "float64",
        "i": "int64", "f": "float64", "b": "bool",
    })
    return session.create_dataframe(t, num_partitions=3)


def test_grand_aggregate(df):
    assert_tpu_cpu_equal(df.agg(
        fsum(col("i")).alias("s"), count(col("i")).alias("c"),
        count_star().alias("n"), fmin(col("i")).alias("mn"),
        fmax(col("i")).alias("mx"), avg(col("i")).alias("av"),
    ), rel_tol=1e-6)


def test_grouped_single_key(df):
    assert_tpu_cpu_equal(df.group_by("k1").agg(
        fsum(col("i")).alias("s"), count(col("i")).alias("c"),
        fmin(col("f")).alias("mn"), fmax(col("f")).alias("mx"),
        avg(col("f")).alias("av"),
    ), rel_tol=1e-6)


def test_grouped_multi_key(df):
    assert_tpu_cpu_equal(df.group_by("k1", "k2").agg(
        fsum(col("i")).alias("s"), count_star().alias("n"),
    ))


def test_grouped_float_key_nan_zero(df):
    # float keys: NaN==NaN grouping, -0.0 == 0.0 normalization
    assert_tpu_cpu_equal(df.group_by("fk").agg(count_star().alias("n")))


def test_group_by_expression(df, session):
    assert_tpu_cpu_equal(
        df.group_by((col("k1") % lit(2)).alias("parity"))
          .agg(fsum(col("i")).alias("s")))


def test_sum_empty_and_all_null(session):
    t = pa.table({"k": pa.array([], type=pa.int32()),
                  "v": pa.array([], type=pa.int64())})
    df = session.create_dataframe(t)
    assert_tpu_cpu_equal(df.agg(fsum(col("v")).alias("s"),
                                count_star().alias("n")))
    t2 = pa.table({"k": [1, 1, 2], "v": pa.array([None, None, None],
                                                 type=pa.int64())})
    df2 = session.create_dataframe(t2)
    assert_tpu_cpu_equal(df2.group_by("k").agg(fsum(col("v")).alias("s"),
                                               count(col("v")).alias("c")))


def test_null_group_key(session):
    t = pa.table({"k": [1, None, 1, None, 2], "v": [1, 2, 3, 4, 5]})
    df = session.create_dataframe(t)
    assert_tpu_cpu_equal(df.group_by("k").agg(fsum(col("v")).alias("s")))


def test_first_last(df):
    # first/last need deterministic order per group: use single partition input
    assert_tpu_cpu_equal(df.group_by("k1").agg(
        count_star().alias("n")))


def test_variance_stddev(df):
    assert_tpu_cpu_equal(df.group_by("k1").agg(
        var_pop(col("f")).alias("vp"), var_samp(col("f")).alias("vs"),
        stddev_pop(col("f")).alias("sp"), stddev_samp(col("f")).alias("ss"),
    ), rel_tol=1e-5)


def test_avg_over_filter(df):
    assert_tpu_cpu_equal(
        df.filter(col("i") > lit(0)).group_by("k2")
          .agg(avg(col("i")).alias("av"), fsum(col("f")).alias("s")),
        rel_tol=1e-6)


@pytest.mark.parametrize("strategy", ["sort", "hash"])
def test_groupby_strategy_differential(strategy):
    """The sort-free hash grouping (bucket-resolve rounds, no lax.sort —
    spark.rapids.tpu.groupby.strategy) matches the sort path and the host
    engine exactly, incl. null/NaN keys and string keys."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import pyarrow as pa
    import spark_rapids_tpu.expr.functions as F
    from spark_rapids_tpu.expr.functions import col
    from spark_rapids_tpu.session import TpuSession
    rng = np.random.default_rng(11)
    n = 5000
    fv = rng.normal(size=n).round(2)
    fv[::17] = np.nan
    fmask = np.ones(n, bool)
    fmask[::23] = False
    t = pa.table({
        "k1": rng.integers(0, 40, n),
        "k2": rng.choice(["aa", "bb", None, "ab\x00"], n),
        "f": pa.array(fv, mask=~fmask),
        "v": rng.normal(size=n),
    })
    sess = TpuSession({"spark.rapids.tpu.batchRowsMinBucket": 512,
                       "spark.rapids.tpu.groupby.strategy": strategy})
    df = sess.create_dataframe(t, num_partitions=2)
    q = df.group_by("k1", "k2", "f").agg(
        F.sum(col("v")).alias("sv"), F.count(col("v")).alias("c"),
        F.min(col("v")).alias("mn"), F.first(col("v")).alias("fst"))
    dev = sorted(map(str, q.collect(device=True).to_pylist()))
    cpu = sorted(map(str, q.collect(device=False).to_pylist()))
    assert dev == cpu


def _branch_case(case: str, few: int):
    """(table, query builder) of one few-groups differential case; ``few``
    is the engine's own FEW_GROUPS, which the boundary cases straddle."""
    import numpy as np
    import spark_rapids_tpu.expr.functions as F
    rng = np.random.default_rng([31, sum(map(ord, case))])
    n = {"one_row": 1}.get(case, 700)
    v = rng.normal(size=n).round(3) * 100
    vmask = rng.random(n) < 0.15                 # a nullable input column
    if case == "mixed_keys":
        fk = rng.choice(np.array([np.nan, -0.0, 0.0, 1.5, -2.25]), n)
        keys = {"fk": pa.array(fk, mask=rng.random(n) < 0.1),
                "sk": pa.array(rng.choice(
                    np.array(["ab", "ab\x00", "", "b", None], dtype=object),
                    n).tolist(), type=pa.string())}
    else:
        groups = {"few_minus_1": few - 1, "few": few,
                  "few_plus_1": few + 1}.get(case, 3)
        keys = {"k": pa.array(rng.integers(0, groups, n).astype(np.int32))}
        if case.startswith("few"):
            # every group present, whatever the draw
            keys["k"] = pa.array((np.arange(n) % groups).astype(np.int32))
    t = pa.table({**keys,
                  "v": pa.array(v, mask=vmask),
                  "i": pa.array(rng.integers(-50, 50, n), mask=vmask),
                  "nul": pa.array([None] * n, type=pa.float64())})

    def query(df):
        if case == "all_masked":
            df = df.filter(col("i") > lit(1000))
        return df.group_by(*keys).agg(
            fsum(col("v")).alias("s"), fsum(col("i")).alias("si"),
            count(col("v")).alias("c"), count_star().alias("n"),
            fmin(col("v")).alias("mn"), fmax(col("i")).alias("mx"),
            first(col("v")).alias("fst"), last(col("i")).alias("lst"),
            avg(col("v")).alias("av"), var_samp(col("v")).alias("var"),
            fsum(col("nul")).alias("snul"), fmax(col("nul")).alias("mnul"),
            F.count(col("nul")).alias("cnul"))
    return t, query


@pytest.mark.parametrize("case", ["mixed_keys", "few_minus_1", "few",
                                  "few_plus_1", "all_masked", "one_row"])
@pytest.mark.parametrize("strategy", ["sort", "hash"])
@pytest.mark.parametrize("branch", ["scatter", "dense", "picked"])
def test_grouped_branches_differential(branch, strategy, case, monkeypatch):
    """The same batches through the scatter branch of ``grouped``
    (FEW_GROUPS patched to 0), its dense branch (patched past any batch)
    and the branch the device picks, each against the host engine:
    integers, keys and nulls equal, floats within the harness's rel_tol.
    The ``agg.dense`` / ``agg.scatter`` spans say the forced branch ran."""
    import spark_rapids_tpu.exec.aggregate as A
    from spark_rapids_tpu.session import TpuSession
    from spark_rapids_tpu.utils.compile_cache import clear_cache
    from harness import assert_tables_equal
    few = A.FEW_GROUPS
    t, query = _branch_case(case, few)
    if branch != "picked":
        monkeypatch.setattr(A, "FEW_GROUPS", 0 if branch == "scatter"
                            else 1 << 30)
    clear_cache()        # programs are cached by plan, not by FEW_GROUPS
    sess = TpuSession({"spark.rapids.tpu.batchRowsMinBucket": 64,
                       "spark.rapids.tpu.groupby.strategy": strategy})
    try:
        q = query(sess.create_dataframe(t, num_partitions=1))
        dev = q.collect(device=True)
        phases = sess.last_query_phases()["phases"]
        cpu = q.collect(device=False)
    finally:
        sess.close()
        clear_cache()
    assert_tables_equal(dev, cpu)
    groups = dev.num_rows
    took_dense = {"scatter": groups == 0, "dense": True,
                  "picked": groups <= few}[branch]
    assert ("agg.scatter" if took_dense else "agg.dense") not in phases
    if t.num_rows > 64:      # a batch at the minimum bucket syncs no count
        assert phases["agg.dense" if took_dense else "agg.scatter"]["calls"]


# ---------------------------------------------------------------------------
# what the grouped aggregate's program may hold (a jaxpr guard of the kind
# tests/test_shrink_to_fit.py has): gathers and scatters cost by their index
# count on the chip (PERF.md section 6, PR 29 and PR 31)
# ---------------------------------------------------------------------------
_GUARD_CAP = 1 << 14


def _identity_gathers(jaxpr, iota_invars=()):
    """Gathers whose indices are an ``iota`` (through the index
    normalisation ``jnp.take`` wraps them in), branch bodies included."""
    import jax
    from jax.extend.core import Var
    derived = set(iota_invars)
    found = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        ins = [v for v in eqn.invars if isinstance(v, Var)]
        if name == "gather":
            if eqn.invars[1] in derived:
                found.append(eqn)
        elif name in ("cond", "pjit", "jit"):
            # operands map one to one onto each body's inputs (after the
            # cond's predicate)
            args = eqn.invars[1:] if name == "cond" else eqn.invars
            for body in jax.core.jaxprs_in_params(eqn.params):
                found += _identity_gathers(
                    body, [b for b, v in zip(body.invars, args)
                           if isinstance(v, Var) and v in derived])
            if name != "cond" and ins and all(v in derived for v in ins):
                derived.update(eqn.outvars)     # e.g. jnp.take's _where
        elif name == "iota" or (ins and all(v in derived for v in ins)
                                and not list(jax.core.jaxprs_in_params(
                                    eqn.params))):
            derived.update(eqn.outvars)
    return found


@pytest.fixture(scope="module")
def q1_shaped_partial():
    """(node, batch): a Q1-shaped partial aggregate (2 string keys, 11
    buffers) and an input batch of capacity 2^14 for its ``batch_fn``,
    inside a session under the hash strategy."""
    import numpy as np
    from spark_rapids_tpu.exec.wholestage import TpuWholeStageExec
    from spark_rapids_tpu.session import TpuSession
    sess = TpuSession({"spark.rapids.tpu.batchRowsMinBucket": _GUARD_CAP,
                       "spark.rapids.tpu.groupby.strategy": "hash",
                       "spark.rapids.tpu.aqe.enabled": False})
    try:
        rng = np.random.default_rng(0)
        n = _GUARD_CAP - 3
        df = sess.create_dataframe(pa.table({
            "rf": rng.choice(np.array(["A", "N", "R"]), n),
            "ls": rng.choice(np.array(["F", "O"]), n),
            "q": rng.uniform(0, 50, n), "p": rng.uniform(0, 1e5, n),
            "d": rng.uniform(0, .1, n), "x": rng.uniform(0, .08, n)}))
        disc = col("p") * (lit(1.0) - col("d"))
        q = df.group_by("rf", "ls").agg(
            fsum(col("q")).alias("a"), fsum(col("p")).alias("b"),
            fsum(disc).alias("c"),
            fsum(disc * (lit(1.0) + col("x"))).alias("e"),
            avg(col("q")).alias("f"), avg(col("p")).alias("g"),
            avg(col("d")).alias("h"), count_star().alias("i"))

        def find(plan):
            if isinstance(plan, TpuWholeStageExec):
                return plan
            return next(filter(None, map(find, plan.children)), None)

        stage = find(sess._physical(q.logical, device=True))
        partial = stage.chain[-1]
        assert partial.mode == "partial" and len(partial._columns_ops()) == 11
        batch = next(stage.source.execute_columnar(0))
        for node in stage.chain[:-1]:
            batch = node.batch_fn()(batch)
        assert batch.capacity == _GUARD_CAP
        yield partial, batch
    finally:
        sess.close()


@pytest.fixture(scope="module")
def q1_shaped_jaxpr(q1_shaped_partial):
    """``batch_fn()`` of that aggregate, traced: the form a fused stage
    holds."""
    import jax
    partial, batch = q1_shaped_partial
    return jax.make_jaxpr(partial.batch_fn())(batch).jaxpr


def test_returning_the_resolve_loops_trips_adds_no_equation(
        q1_shaped_partial, q1_shaped_jaxpr):
    """The aggregate's program of its own returns (table, trips): the same
    equations as the fused form, and one more output, which is the first
    value of the bucket-resolve loop's carry as the loop leaves it (the
    carry keeps its order: XLA's memory-space assignment follows it)."""
    import jax
    partial, batch = q1_shaped_partial
    alone = jax.make_jaxpr(partial.batch_fn(with_rounds=True))(batch).jaxpr
    fused = q1_shaped_jaxpr
    assert [str(e.primitive) for e in alone.eqns] \
        == [str(e.primitive) for e in fused.eqns]
    assert len(alone.outvars) == len(fused.outvars) + 1
    loops = [e for e in alone.eqns if e.primitive.name == "while"]
    assert len(loops) == 1 and alone.outvars[-1] is loops[0].outvars[0]
    assert [v.aval for v in loops[0].outvars] == [
        v.aval for v in next(e for e in fused.eqns
                             if e.primitive.name == "while").outvars]


@pytest.mark.parametrize("strategy,trips", [
    ("hash", lambda r: r >= 2), ("sort", lambda r: r == 0)])
def test_agg_scatter_books_rows_groups_and_rounds(strategy, trips):
    """500 distinct keys in one 512-row batch: under the hash strategy keys
    share buckets, so the resolve loop takes a second trip; a sort resolves
    no bucket and books 0."""
    import numpy as np
    from spark_rapids_tpu.session import TpuSession
    from spark_rapids_tpu.utils.tracing import get_tracer
    tracer = get_tracer()
    was, tracer.enabled = tracer.enabled, True
    tracer.clear()
    sess = TpuSession({"spark.rapids.tpu.batchRowsMinBucket": 64,
                       "spark.rapids.tpu.groupby.strategy": strategy})
    try:
        keys = np.arange(500, dtype=np.int64) * 4
        df = sess.create_dataframe(
            pa.table({"k": keys, "v": np.ones(500)}), num_partitions=1)
        got = df.group_by("k").agg(fsum(col("v")).alias("s")).collect()
        booked = [e.args for e in tracer.events() if e.name == "agg.scatter"]
    finally:
        sess.close()
        tracer.enabled = was
        tracer.clear()
    assert got.num_rows == 500
    alone = [a for a in booked if "rounds" in a]
    assert alone and all(a["rows"] == 512 and a["groups"] == 500
                         for a in booked)
    assert all(trips(a["rounds"]) for a in alone)


def test_hash_grouping_gathers_by_no_identity_permutation(q1_shaped_jaxpr):
    """``_hash_group_ids`` has no permutation to give, so no column is
    gathered by one (the parent took every column by an ``iota``)."""
    assert not _identity_gathers(q1_shaped_jaxpr)


def test_grouped_holds_one_cond_with_a_dense_and_a_scatter_branch(
        q1_shaped_jaxpr):
    from spark_rapids_tpu.exec.aggregate import FEW_GROUPS
    conds = [e for e in _eqns(q1_shaped_jaxpr) if e.primitive.name == "cond"]
    assert len(conds) == 1
    scatter, dense = (list(_eqns(b.jaxpr))
                      for b in conds[0].params["branches"])
    # the dense branch: no scatter, no gather longer than FEW_GROUPS
    assert not [e for e in dense if e.primitive.name.startswith("scatter")]
    gathers = [e for e in dense if e.primitive.name == "gather"]
    assert gathers
    for e in gathers:
        assert e.invars[1].aval.shape[0] <= FEW_GROUPS, e
        assert e.outvars[0].aval.shape[0] <= FEW_GROUPS, e
    # the scatter branch: a value and a count scatter-add for each of the 7
    # sums, a count for each of the 4 counts (ROADMAP A1(b): buffers over
    # one input can share a count), and the representative row's scatter-min
    names = [e.primitive.name for e in scatter]
    assert names.count("scatter-add") <= 18, names.count("scatter-add")
    assert [n for n in names if n.startswith("scatter")
            and n != "scatter-add"] == ["scatter-min"]
    # outside the branches only the bucket-resolve loop scatters
    outside = [e.primitive.name for e in q1_shaped_jaxpr.eqns
               if e.primitive.name.startswith("scatter")]
    assert not outside, outside
