"""Aggregation differential tests (reference: HashAggregatesSuite +
hash_aggregate_test.py)."""
import numpy as np
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


#: case -> (aggregates, whether the final merges the rows passed through
#: as one-row states, so that it reduces many states a group)
_SCATTER_CASES = {
    "count": (lambda: [count(col("v")).alias("a"),
                       count_star().alias("b")], False),
    "merge_sum_of_counts": (lambda: [count(col("v")).alias("a"),
                                     count_star().alias("b")], True),
    "float64_sum_with_nulls": (lambda: [fsum(col("v")).alias("a"),
                                        fsum(col("nul")).alias("b")], True),
    "first_last_with_nulls": (lambda: [first(col("i")).alias("a"),
                                       last(col("v")).alias("b")], True),
    "min_max_with_nan": (lambda: [fmin(col("v")).alias("a"),
                                  fmax(col("v")).alias("b")], True),
}


def _scatter_reference(case, k, v, i):
    """-> (keys in first-appearance order, {output: (values, validity)}):
    the case's aggregates group by group in numpy, rows in table order."""
    keys = list(dict.fromkeys(k.tolist()))
    vv, iv = ~v.mask, ~i.mask
    out = {"a": [], "b": []}
    for key in keys:
        rows = np.flatnonzero(k == key)
        vr, ir = v.data[rows][vv[rows]], i.data[rows][iv[rows]]
        if case in ("count", "merge_sum_of_counts"):
            got = [(np.int64(len(vr)), True), (np.int64(len(rows)), True)]
        elif case == "float64_sum_with_nulls":
            acc = np.zeros(1)
            np.add.at(acc, np.zeros(len(vr), np.int64), vr)
            got = [(acc[0], len(vr) > 0), (0.0, False)]
        elif case == "first_last_with_nulls":
            got = [(ir[0] if len(ir) else 0, len(ir) > 0),
                   (vr[-1] if len(vr) else 0.0, len(vr) > 0)]
        else:
            # Spark's total order: NaN is the largest double
            nan = np.isnan(vr)
            lo = vr[~nan].min() if (~nan).any() else np.nan
            hi = np.nan if nan.any() else vr.max() if len(vr) else 0.0
            got = [(lo, len(vr) > 0), (hi, len(vr) > 0)]
        for name, g in zip("ab", got):
            out[name].append(g)
    return keys, {n: (np.array([x for x, _ in g]), np.array([h for _, h in g]))
                  for n, g in out.items()}


@pytest.mark.parametrize("case", sorted(_SCATTER_CASES))
@pytest.mark.parametrize("strategy", ["sort", "hash"])
def test_scatter_branch_against_numpy(strategy, case, monkeypatch):
    """The scatter branch (``FEW_GROUPS`` patched to 0) of a partial
    aggregate and of the final one over its states, or over the rows
    passed through as one-row states, against numpy group by group: the
    same bits, the same validity, ``count`` as int64, and the groups in
    the grouping's order (first appearance under the hash grouping, key
    order under the sorted one). Row counts and positions are reduced in
    32 bits into ``cap`` segments; the answer may not show it."""
    import spark_rapids_tpu.exec.aggregate as A
    from spark_rapids_tpu.exec.wholestage import TpuWholeStageExec
    from spark_rapids_tpu.session import TpuSession
    from spark_rapids_tpu.utils.compile_cache import clear_cache
    aggs, passed = _SCATTER_CASES[case]
    rng = np.random.default_rng([43, sum(map(ord, case))])
    n = 700
    k = rng.integers(-30, 30, n) * 7
    k[rng.random(n) < 0.45] = 0      # a group of ~300 rows
    v = np.ma.masked_array(rng.normal(size=n) * 1e3,
                           mask=rng.random(n) < 0.2)
    v.data[rng.random(n) < 0.1] = np.nan
    v.data[k == 14] = np.nan                 # a group whose values are NaN
    v.mask[k == 21] = True                   # and one whose are null
    i = np.ma.masked_array(rng.integers(-1 << 40, 1 << 40, n),
                           mask=rng.random(n) < 0.3)
    t = pa.table({"k": k, "v": pa.array(v.data, mask=v.mask),
                  "i": pa.array(i.data, mask=i.mask),
                  "nul": pa.array([None] * n, type=pa.float64())})
    monkeypatch.setattr(A, "FEW_GROUPS", 0)
    clear_cache()        # programs are cached by plan, not by FEW_GROUPS
    sess = TpuSession({"spark.rapids.tpu.batchRowsMinBucket": 1024,
                       "spark.rapids.tpu.groupby.strategy": strategy,
                       "spark.rapids.tpu.aqe.enabled": False})
    try:
        q = sess.create_dataframe(t).group_by("k").agg(*aggs())
        plan = sess._physical(q.logical, device=True)

        def find(p, cls):
            if isinstance(p, cls):
                return p
            return next(filter(None, (find(c, cls) for c in p.children)),
                        None)
        stage = find(plan, TpuWholeStageExec)
        final = find(plan, A.TpuHashAggregateExec)
        assert final.mode == "final" and stage.chain[-1].mode == "partial"
        batch = next(stage.source.execute_columnar(0))
        states = (stage.passthrough_fn() if passed else stage.batch_fn())(
            batch)
        out = final.batch_fn()(states)
        groups = int(out.num_rows)
        # the columns: the key, then the aggregates in the query's order
        got = {name: (np.asarray(c.data)[:groups],
                      np.asarray(c.validity)[:groups])
               for name, c in zip("kab", out.columns)}
    finally:
        sess.close()
        clear_cache()
    keys, want = _scatter_reference(case, k, v, i)
    if strategy == "sort":
        order = np.argsort(keys, kind="stable")
        keys = [keys[j] for j in order]
        want = {nm: (x[order], h[order]) for nm, (x, h) in want.items()}
    assert groups == len(keys)
    np.testing.assert_array_equal(got["k"][0], keys)
    for name, (x, has) in want.items():
        data, valid = got[name]
        np.testing.assert_array_equal(valid, has, err_msg=name)
        if case in ("count", "merge_sum_of_counts"):
            assert data.dtype == np.int64, (name, data.dtype)
        # the same bits where the value is valid
        assert data[has].dtype.itemsize == 8
        np.testing.assert_array_equal(
            data[has].view(np.int64),
            x[has].astype(data.dtype).view(np.int64), err_msg=name)


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


def _tail_conds(jaxpr):
    """The ``cond``s of a jaxpr with a branch that does nothing and one that
    loops: the bucket resolve's compaction, tail rounds and write-back."""
    def loops(branch):
        return any(q.primitive.name == "while" for q in branch.jaxpr.eqns)
    return [e for e in jaxpr.eqns if e.primitive.name == "cond"
            and sorted((len(b.jaxpr.eqns) > 0, loops(b))
                       for b in e.params["branches"])
            == [(False, False), (True, True)]]


def test_returning_the_resolve_loops_trips_adds_no_equation(
        q1_shaped_partial, q1_shaped_jaxpr):
    """The aggregate's program of its own returns (table, (rounds,
    full_rounds)): the same equations as the fused form, and two more
    outputs — the counter as the tail's ``cond`` hands it on, and the
    first value of the full rounds' carry as that loop leaves it (the
    carry keeps its order: XLA's memory-space assignment follows it)."""
    import jax
    partial, batch = q1_shaped_partial
    alone = jax.make_jaxpr(partial.batch_fn(with_rounds=True))(batch).jaxpr
    fused = q1_shaped_jaxpr
    assert [str(e.primitive) for e in alone.eqns] \
        == [str(e.primitive) for e in fused.eqns]
    assert len(alone.outvars) == len(fused.outvars) + 2
    rounds, full_rounds = alone.outvars[-2:]
    loops = [e for e in alone.eqns if e.primitive.name == "while"]
    assert len(loops) == 1 and full_rounds is loops[0].outvars[0]
    (tail,) = _tail_conds(alone)
    assert rounds is tail.outvars[0]
    assert [v.aval for v in loops[0].outvars] == [
        v.aval for v in next(e for e in fused.eqns
                             if e.primitive.name == "while").outvars]
    r, winner, unresolved = (v.aval for v in loops[0].outvars)
    assert (r.shape, winner.dtype.name, unresolved.dtype.name) \
        == ((), "int32", "bool")


def test_the_tail_of_the_resolve_loop_is_its_share_of_the_batch(
        q1_shaped_jaxpr):
    """Under the tail's ``cond``: one compaction (a prefix sum, no
    ``cumsum``; one index scatter of the batch's length), gathers of no
    more than ``cap // _TAIL_SHARE`` indices inside and outside its loop,
    and a write-back of as many updates; the other branch holds
    nothing."""
    from spark_rapids_tpu.exec.aggregate import _TAIL_SHARE
    tail_cap = _GUARD_CAP // _TAIL_SHARE
    (cond,) = _tail_conds(q1_shaped_jaxpr)
    skip, tail = (b.jaxpr for b in cond.params["branches"])
    assert not skip.eqns
    eqns = list(_eqns(tail))
    names = [e.primitive.name for e in eqns]
    assert names.count("while") == 1 and "cumsum" not in names
    assert "sort" not in names
    gathers = [e for e in eqns if e.primitive.name == "gather"]
    assert gathers and all(
        e.invars[1].aval.shape[0] == tail_cap for e in gathers)
    updates = sorted(e.invars[2].aval.shape[0] for e in eqns
                     if e.primitive.name.startswith("scatter"))
    # compaction's index scatter; write-back; the tail rounds' scatter-min
    assert updates == [tail_cap, tail_cap, _GUARD_CAP]


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
    # the other one is the bucket resolve's tail
    assert len(conds) == 2 and conds[0] in _tail_conds(q1_shaped_jaxpr)
    scatter, dense = (list(_eqns(b.jaxpr))
                      for b in conds[1].params["branches"])
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

    def updates(eqns, name):
        """dtype names of the updates of a jaxpr's ``name`` equations"""
        return [e.invars[2].aval.dtype.name for e in eqns
                if e.primitive.name == name]

    def outputs(eqns, name):
        return [e.outvars[0].aval.dtype.name for e in eqns
                if e.primitive.name == name]
    # ... the representative row's scatter-min over int32 positions
    assert updates(scatter, "scatter-min") == ["int32"]
    # ... a 64-bit scatter-add for each of the 7 value buffers (the sums)
    # and none else: every count scatters in int32
    adds = updates(scatter, "scatter-add")
    assert [d for d in adds if d != "int32"] == ["float64"] * 7, adds
    assert "int32" in adds
    # the dense branch reduces as at the parent: counts and the
    # representative's position in int64
    sums = outputs(dense, "reduce_sum")
    assert sorted(set(sums)) == ["float64", "int64"], sums
    assert outputs(dense, "reduce_min") == ["int64"]
    # outside the branches only the bucket-resolve loops scatter
    outside = [e.primitive.name for e in q1_shaped_jaxpr.eqns
               if e.primitive.name.startswith("scatter")]
    assert not outside, outside


# ---------------------------------------------------------------------------
# the hash grouping itself: full rounds while over 1/_TAIL_SHARE of the batch
# is open, one compaction, tail rounds over the rest — against a grouping
# defined in numpy
# ---------------------------------------------------------------------------
def _first_round_buckets(table, key_names):
    """The bucket each row falls in on the resolve loop's first round, from
    the module's own key hash: used to BUILD a case that leaves a chosen
    number of rows open, never to judge one."""
    import numpy as np
    import spark_rapids_tpu.exec.aggregate as A
    from spark_rapids_tpu.shuffle.manager import _fmix_device
    return np.asarray(_fmix_device(A._hashed_key_words(table, key_names)[0]))


def _open_after_one_round(cap, n_open):
    """``cap`` int64 keys of which exactly ``n_open`` rows are open after
    the first full round: a key that owns its bucket (its first row is the
    bucket's lowest) resolves with all its duplicates; ``n_open`` other
    keys, one row each, sit behind an owner of their bucket."""
    import numpy as np
    from spark_rapids_tpu.columnar.device import DeviceTable
    from spark_rapids_tpu.columnar.host import HostTable
    pool = np.arange(4 * cap, dtype=np.int64) * 7 + 3
    bucket = _first_round_buckets(DeviceTable.from_host(
        HostTable.from_arrow(pa.table({"k": pool})), capacity=len(pool)),
        ["k"]) % cap
    owners, losers, seen = [], [], set()
    for key, b in zip(pool, bucket):
        if b not in seen:
            seen.add(b)
            owners.append(key)
        elif len(losers) < n_open:
            losers.append(key)
    owners = owners[:cap // 2]
    assert len(losers) == n_open
    own = set(bucket[np.isin(pool, owners)])
    assert all(b in own for b in bucket[np.isin(pool, losers)])
    fill = np.resize(owners, cap - len(owners) - n_open)
    return np.concatenate([owners, fill, losers])


def _grouping_case(case):
    """-> (key columns, rows in the batch, capacity, rows switched off,
    what the trip counts must satisfy)."""
    import numpy as np
    from spark_rapids_tpu.exec.aggregate import _TAIL_SHARE as TAIL_SHARE
    rng = np.random.default_rng([35, sum(map(ord, case))])
    off = None
    cap = 1024
    if case == "one_key":
        keys = {"k": np.full(1000, 5, np.int64)}
        trips = lambda r, f: (r, f) == (1, 1)
    elif case == "all_distinct_full_batch":
        cap = 4096
        keys = {"k": rng.permutation(cap).astype(np.int64) * 4}
        trips = lambda r, f: 1 <= f and r - f >= 2
    elif case in ("open_equals_the_tail", "open_one_over_the_tail"):
        over = case == "open_one_over_the_tail"
        keys = {"k": _open_after_one_round(cap, cap // TAIL_SHARE + over)}
        trips = lambda r, f: f == 1 + over and r > f
    elif case == "few_live_rows_in_a_large_batch":
        cap = 4096
        keys = {"k": rng.integers(0, 120, cap // TAIL_SHARE - 1) * 4}
        trips = lambda r, f: f == 0 and r >= 1
    elif case == "inactive_rows_interleaved":
        keys = {"k": rng.integers(0, 300, cap).astype(np.int64)}
        off = rng.random(cap) < 0.4
        trips = lambda r, f: 1 <= f <= r
    elif case == "null_nan_and_negative_zero":
        fk = rng.choice(np.array([np.nan, -0.0, 0.0, 1.5, -2.25]), 900)
        keys = {"f": pa.array(fk, mask=rng.random(900) < 0.1),
                "i": pa.array(rng.integers(0, 40, 900),
                              mask=rng.random(900) < 0.1)}
        trips = lambda r, f: 1 <= f <= r
    elif case == "int_and_wide_string":
        names = np.array([f"Customer#{i:09d}" for i in range(150)]
                         + ["Customer#000000001\x00", "", None], dtype=object)
        keys = {"i": rng.integers(0, 3, cap).astype(np.int64),
                "s": pa.array(rng.choice(names, cap).tolist(),
                              type=pa.string())}
        trips = lambda r, f: 1 <= f <= r
    elif case == "minimum_bucket":
        # 200 rows in the default 1,024-row bucket: over an eighth, so one
        # full round at least, then the tail
        keys = {"k": np.arange(200, dtype=np.int64) * 4}
        trips = lambda r, f: 1 <= f <= r
    elif case == "empty":
        keys = {"k": np.zeros(0, np.int64)}
        trips = lambda r, f: (r, f) == (0, 0)
    return keys, cap, off, trips


def _key_classes(table: pa.Table):
    """One hashable value a row: equal exactly where Spark's grouping holds
    the keys equal (null == null, NaN == NaN, -0.0 == 0.0)."""
    import math

    def norm(v):
        if isinstance(v, float):
            return "nan" if math.isnan(v) else v + 0.0
        return v
    return list(zip(*[[norm(v) for v in table.column(n).to_pylist()]
                      for n in table.column_names]))


@pytest.mark.parametrize("case", [
    "one_key", "all_distinct_full_batch", "open_equals_the_tail",
    "open_one_over_the_tail", "few_live_rows_in_a_large_batch",
    "inactive_rows_interleaved", "null_nan_and_negative_zero",
    "int_and_wide_string", "minimum_bucket", "empty"])
def test_hash_grouping_against_numpy(case):
    """``_hash_group_ids`` gives the grouping defined here in numpy:
    a class's representative is its lowest active row, a group's id the
    rank of its representative among the representatives, whichever of
    the loop's phases resolved the class."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import spark_rapids_tpu.exec.aggregate as A
    from spark_rapids_tpu.columnar.device import DeviceTable
    from spark_rapids_tpu.columnar.host import HostTable
    keys, cap, off, trips = _grouping_case(case)
    t = pa.table(keys)
    table = DeviceTable.from_host(HostTable.from_arrow(t), capacity=cap)
    if off is not None:
        table = table.filter_mask(jnp.asarray(~off))
    cap = table.capacity
    active = np.arange(cap) < t.num_rows
    if off is not None:
        active &= ~off
    first, winner = {}, np.arange(cap)
    for i, key in enumerate(_key_classes(t)):
        if active[i]:
            winner[i] = first.setdefault(key, i)
    is_rep = active & (winner == np.arange(cap))
    want_gid = (np.cumsum(is_rep) - 1)[winner]

    order, got_active, gid, boundary, num_groups, (rounds, full_rounds) = \
        jax.jit(lambda tb: A._hash_group_ids(tb, list(t.column_names)))(table)
    assert order is None
    np.testing.assert_array_equal(np.asarray(got_active), active)
    np.testing.assert_array_equal(np.asarray(boundary), is_rep)
    assert int(num_groups) == is_rep.sum() == len(first)
    np.testing.assert_array_equal(np.asarray(gid)[active], want_gid[active])
    assert trips(int(rounds), int(full_rounds)), (rounds, full_rounds)


# ---- a partial that does not reduce passes its batches through --------------
def _skip_run(table, query, batch_rows=128, parts=2, **conf):
    """(answer, ``agg.skip`` spans, ``agg.dense`` + ``agg.scatter`` spans)
    of ``query`` over ``table`` in ``parts`` partitions of ``batch_rows``-row
    batches, each batch one the partial decides on."""
    from spark_rapids_tpu.io.memory import InMemorySource
    from spark_rapids_tpu.plan.logical import LogicalScan
    from spark_rapids_tpu.session import DataFrame, TpuSession
    from spark_rapids_tpu.utils.tracing import get_tracer
    tracer = get_tracer()
    was = tracer.enabled
    tracer.enabled = True
    tracer.clear()
    sess = TpuSession({"spark.rapids.tpu.batchRowsMinBucket": 64,
                       "spark.rapids.sql.test.enabled": True, **conf})
    try:
        df = DataFrame(sess, LogicalScan(
            InMemorySource(table, parts, batch_rows=batch_rows)))
        got = query(df).collect(device=True)
        names = [e.name for e in tracer.events()]
    finally:
        tracer.enabled = was
        tracer.clear()
        sess.close()
    return (got, names.count("agg.skip"),
            names.count("agg.dense") + names.count("agg.scatter"))


def _wide_table(rng, n=1024, keys=600):
    """``n`` rows of ~``keys`` int64 keys: a 128-row batch holds ~115
    groups, so the partial keeps nine rows of ten and is skipped."""
    from harness import data_gen
    t = data_gen(rng, n, {"i": ("int64", -1000, 1000), "f": "float64"})
    return t.append_column("k", pa.array(rng.integers(0, keys, n)))


SKIP_OPS = {
    "sum": lambda: fsum(col("i")),
    "sumsq": lambda: var_pop(col("i")),     # sum, sumsq and count states
    "count": lambda: count(col("f")),
    "count_star": lambda: count_star(),
    "min_nan_null": lambda: fmin(col("f")),
    "max_nan_null": lambda: fmax(col("f")),
    "avg": lambda: avg(col("f")),
    "first": lambda: first(col("i")),
    "last": lambda: last(col("i")),
}


@pytest.mark.parametrize("op", sorted(SKIP_OPS))
def test_a_skipped_partial_gives_the_kept_partials_answer(op, rng,
                                                          monkeypatch):
    """Two partitions of four batches: each partition's first batch is
    aggregated and shows ~115 groups in 128 rows, so its three others pass
    through as one-row states, and the final aggregate merges them to the
    answer of the plan whose partial reduces every batch (``SKIP_SHARE``
    0: no batch ever skips). Integers are exact, floats a few ulps off at
    most (the sums add in another order)."""
    from harness import assert_tables_equal
    from spark_rapids_tpu.exec import aggregate
    t = _wide_table(rng)

    def query(df):
        return df.group_by("k").agg(SKIP_OPS[op]().alias("a"))
    got, skipped, reduced = _skip_run(t, query)
    assert skipped == 6, (skipped, reduced)
    monkeypatch.setattr(aggregate, "SKIP_SHARE", 0)
    want, kept, _ = _skip_run(t, query)
    assert kept == 0
    assert got.num_rows == len(set(t.column("k").to_pylist()))
    assert_tables_equal(got, want, rel_tol=1e-12)


def test_a_float_key_of_both_zeros_groups_as_one_when_skipped(rng):
    """-0.0 and +0.0 in every batch, among ~115 other keys a batch: the
    rows pass through with their keys as they are, and the final
    aggregate's normalised grouping still makes them one group."""
    n = 1024
    k = rng.integers(1, 600, n).astype(np.float64)
    k[::16] = 0.0
    k[8::16] = -0.0
    i = rng.integers(0, 50, n)
    got, skipped, _ = _skip_run(
        pa.table({"k": k, "i": i}), lambda df: df.group_by("k").agg(
            fsum(col("i")).alias("s"), count_star().alias("n")))
    assert skipped == 6
    got = got.to_pandas().sort_values("k")
    want = pd_groupby_sum_count(k, i)
    np.testing.assert_array_equal(got.k, want.index)
    np.testing.assert_array_equal(got.s, want.s)
    np.testing.assert_array_equal(got.n, want.n)
    assert (got.k == 0).sum() == 1 and int(got.n[got.k == 0].iloc[0]) == n // 8


def pd_groupby_sum_count(k, i):
    """pandas' group-by, where -0.0 and +0.0 are one key."""
    import pandas as pd
    return pd.DataFrame({"k": k + 0.0, "i": i}).groupby("k").i.agg(
        s="sum", n="count")


def test_a_batch_of_four_groups_keeps_the_partial(rng):
    """Q1's shape: four groups a 128-row batch, every batch reduced."""
    t = pa.table({"k": rng.integers(0, 4, 1024),
                  "v": rng.uniform(0, 1, 1024)})
    got, skipped, reduced = _skip_run(
        t, lambda df: df.group_by("k").agg(fsum(col("v")).alias("s")))
    assert got.num_rows == 4 and skipped == 0 and reduced == 9


def test_an_aggregate_with_a_collect_list_never_skips(rng):
    """``collect_list``'s state is a list, no row-wise projection: the
    plan's partial never passes through, whatever its groups."""
    from spark_rapids_tpu.expr.functions import collect_list
    t = _wide_table(rng)
    got, skipped, _ = _skip_run(t, lambda df: df.group_by("k").agg(
        fsum(col("i")).alias("s"), collect_list(col("i")).alias("l")))
    assert skipped == 0
    assert got.num_rows == len(set(t.column("k").to_pylist()))
    lens = {r["k"]: len(r["l"]) for r in got.to_pylist()}
    i = t.column("i").to_pylist()
    for key, n in lens.items():
        assert n == sum(1 for kk, v in zip(t.column("k").to_pylist(), i)
                        if kk == key and v is not None)
