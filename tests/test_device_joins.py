"""Device join kernel tests (reference analogues: join_test.py +
HashJoinSuite). Verifies the Tpu join node is actually in the plan, then
differentials device vs CPU engine across join types and edge cases."""
import numpy as np
import pyarrow as pa
import pytest

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
    return any(_has_node(c, cls_name) for c in kids)


@pytest.fixture
def sides(session, rng):
    lt = data_gen(rng, 200, {"k": ("int32", 0, 30), "k2": ("int64", 0, 4),
                             "a": "int64", "fa": "float64"})
    rt = data_gen(rng, 150, {"k": ("int32", 0, 30), "k2": ("int64", 0, 4),
                             "b": "float64"})
    return (session.create_dataframe(lt, num_partitions=2),
            session.create_dataframe(rt, num_partitions=2))


def test_device_join_in_plan(session, sides):
    l, r = sides
    q = l.join(r.select("k", "b"), on="k")
    plan = session._physical(q.logical, True)
    assert _has_node(plan, "TpuBroadcastHashJoinExec") \
        or _has_node(plan, "TpuShuffledHashJoinExec"), plan.tree_string()


@pytest.mark.parametrize("how", ["inner", "left", "right", "full",
                                 "left_semi", "left_anti"])
def test_device_join_types(sides, how):
    l, r = sides
    assert_tpu_cpu_equal(l.join(r.select("k", "b"), on="k", how=how))


def test_device_join_multi_key(sides):
    l, r = sides
    assert_tpu_cpu_equal(l.join(r, on=["k", "k2"]))


def test_device_join_null_keys(session):
    lt = pa.table({"k": [1, None, 2, None, 3], "a": [1, 2, 3, 4, 5]})
    rt = pa.table({"k": [1, None, 3, 4], "b": [10.0, 20.0, 30.0, 40.0]})
    l = session.create_dataframe(lt)
    r = session.create_dataframe(rt)
    for how in ["inner", "left", "right", "full", "left_semi", "left_anti"]:
        assert_tpu_cpu_equal(l.join(r, on="k", how=how))
    out = l.join(r, on="k").collect(device=True)
    assert sorted(out.column("k").to_pylist()) == [1, 3]  # nulls never match
    # full outer: null keys from BOTH sides appear as unmatched rows
    out = l.join(r, on="k", how="full").collect(device=True)
    # 2 matches (1,3) + 3 unmatched left (None,2,None) + 2 unmatched right
    assert out.num_rows == 7


def test_device_join_float_keys_nan_zero(session):
    lt = pa.table({"k": [1.0, float("nan"), -0.0, 2.5],
                   "a": [1, 2, 3, 4]})
    rt = pa.table({"k": [float("nan"), 0.0, 2.5],
                   "b": [10, 20, 30]})
    l = session.create_dataframe(lt)
    r = session.create_dataframe(rt)
    out = assert_tpu_cpu_equal(l.join(r, on="k"))
    # NaN matches NaN, -0.0 matches 0.0
    assert out.num_rows == 3


def test_device_join_duplicate_expansion(session, rng):
    # heavy duplicates: expansion >> probe rows exercises the bucketed
    # out_cap path (the reference's oversized-gather handling)
    lt = pa.table({"k": np.repeat([1, 2], 50), "a": np.arange(100)})
    rt = pa.table({"k": np.repeat([1, 2, 3], 40), "b": np.arange(120)})
    l = session.create_dataframe(lt)
    r = session.create_dataframe(rt)
    out = assert_tpu_cpu_equal(l.join(r, on="k"))
    assert out.num_rows == 2 * 50 * 40


def test_device_join_empty_sides(session):
    l = session.create_dataframe(pa.table({"k": pa.array([], type=pa.int64()),
                                           "a": pa.array([], type=pa.int64())}))
    r = session.create_dataframe(pa.table({"k": [1, 2], "b": [1.0, 2.0]}))
    assert_tpu_cpu_equal(l.join(r, on="k"))
    assert_tpu_cpu_equal(r.join(l, on="k", how="left"))
    assert_tpu_cpu_equal(r.join(l, on="k", how="left_anti"))


def test_device_join_residual_condition(session, rng):
    lt = data_gen(rng, 80, {"lk": ("int32", 0, 10), "a": "int64"})
    rt = data_gen(rng, 60, {"rk": ("int32", 0, 10), "b": "float64"})
    l = session.create_dataframe(lt)
    r = session.create_dataframe(rt)
    q = l.join(r, condition=(col("lk") == col("rk"))
               & (col("a").cast(__import__("spark_rapids_tpu.columnar.dtypes",
                                           fromlist=["DOUBLE"]).DOUBLE)
                  > col("b")))
    assert_tpu_cpu_equal(q)


def test_shuffled_path_forced(session, rng):
    # disable broadcast -> shuffled hash join with exchanges
    s2 = type(session)(session.conf.set(
        "spark.rapids.tpu.autoBroadcastJoinThreshold", -1).set(
        "spark.rapids.tpu.aqe.autoBroadcastJoinThreshold", -1))
    lt = data_gen(rng, 100, {"k": ("int32", 0, 10), "a": "int64"})
    rt = data_gen(rng, 80, {"k": ("int32", 0, 10), "b": "float64"})
    l = s2.create_dataframe(lt, num_partitions=2)
    r = s2.create_dataframe(rt, num_partitions=2)
    q = l.join(r, on="k")
    plan = s2._physical(q.logical, True)
    assert _has_node(plan, "TpuShuffledHashJoinExec"), plan.tree_string()
    assert_tpu_cpu_equal(q)


def test_string_join_keys_on_device(session, rng):
    """String join keys run on device via packed-word join codes (the
    reference gets native string keys from cudf hash join)."""
    lt = pa.table({"k": ["a", "b", None, "longer-key-aaaa", "b"],
                   "v": [1, 2, 3, 4, 5]})
    rt = pa.table({"k": ["b", "c", None, "longer-key-aaaa"],
                   "w": [3, 4, 5, 6]})
    l = session.create_dataframe(lt)
    r = session.create_dataframe(rt)
    q = l.join(r, on="k")
    plan = session._physical(q.logical, True)
    assert _has_node(plan, "TpuBroadcastHashJoinExec") \
        or _has_node(plan, "TpuShuffledHashJoinExec"), plan.tree_string()
    for how in ["inner", "left", "right", "full", "left_semi", "left_anti"]:
        assert_tpu_cpu_equal(l.join(r, on="k", how=how))
    out = q.collect(device=True)
    assert sorted(out.column("k").to_pylist()) == ["b", "b",
                                                   "longer-key-aaaa"]


@pytest.mark.parametrize("how", ["inner", "left", "right", "full",
                                 "left_semi", "left_anti"])
def test_outer_residual_condition(session, rng, how):
    """Residual conditions on outer joins: a probe row whose every candidate
    fails the condition must still appear null-padded (matched-flag fixup,
    reference GpuHashJoin.scala:507)."""
    from spark_rapids_tpu.columnar import dtypes as dt
    lt = data_gen(rng, 120, {"lk": ("int32", 0, 12), "a": "int64"})
    rt = data_gen(rng, 90, {"rk": ("int32", 0, 12), "b": "float64"})
    l = session.create_dataframe(lt, num_partitions=2)
    r = session.create_dataframe(rt, num_partitions=2)
    q = l.join(r, how=how,
               condition=(col("lk") == col("rk"))
               & (col("a").cast(dt.DOUBLE) > col("b")))
    assert_tpu_cpu_equal(q)


@pytest.mark.parametrize("how", ["inner", "cross", "left", "right", "full",
                                 "left_semi", "left_anti"])
def test_device_bnlj(session, rng, how):
    """Non-equi conditions lower to the device nested-loop join."""
    lt = data_gen(rng, 60, {"a": ("int64", 0, 40)})
    rt = data_gen(rng, 25, {"b": ("int64", 0, 40)})
    l = session.create_dataframe(lt, num_partitions=2)
    r = session.create_dataframe(rt)
    cond = None if how == "cross" else col("a") > col("b")
    q = l.join(r, how=how, condition=cond)
    plan = session._physical(q.logical, True)
    assert _has_node(plan, "TpuBroadcastNestedLoopJoinExec"), \
        plan.tree_string()
    assert_tpu_cpu_equal(q)


@pytest.mark.slow
def test_bnlj_unmatched_broadcast_rows_once(session, rng):
    """right/full BNLJ: unmatched broadcast rows appear exactly once even
    with multiple stream partitions and batches. Slow tier: compiles the
    BNLJ kernel for two join kinds (~27s); tier-1 keeps the hash-join
    unmatched-once guard (test_right_outer_not_broadcast_with_partitions)."""
    lt = data_gen(rng, 50, {"a": ("int64", 0, 10)}, null_prob=0.0)
    rt = pa.table({"b": [5, 1000]})
    l = session.create_dataframe(lt, num_partitions=3)
    r = session.create_dataframe(rt)
    for how in ("right", "full"):
        q = l.join(r, how=how, condition=col("a") > col("b"))
        out = assert_tpu_cpu_equal(q)
        assert out.column("b").to_pylist().count(1000) == 1


def test_right_outer_not_broadcast_with_partitions(session, rng):
    # regression: broadcast-right must not be used for right/full outer joins
    lt = data_gen(rng, 40, {"k": ("int32", 0, 5), "a": "int64"})
    rt = pa.table({"k": [1, 99], "b": [1.0, 2.0]})
    l = session.create_dataframe(lt, num_partitions=2)
    r = session.create_dataframe(rt)
    for how in ("right", "full"):
        out = l.join(r.select("k", "b"), on="k", how=how).collect()
        # unmatched right row (k=99) must appear exactly once
        assert out.column("k").to_pylist().count(99) == 1


def test_broadcast_threshold_string_conf(session, rng):
    # regression: late-registered conf keys set as strings must be converted
    s2 = type(session)({"spark.rapids.tpu.autoBroadcastJoinThreshold": "-1",
                        "spark.rapids.tpu.batchRowsMinBucket": 8})
    lt = data_gen(rng, 20, {"k": ("int32", 0, 5), "a": "int64"})
    rt = data_gen(rng, 10, {"k": ("int32", 0, 5), "b": "float64"})
    out = s2.create_dataframe(lt).join(
        s2.create_dataframe(rt), on="k").collect()
    assert out.num_rows > 0


def test_bnlj_build_side_windowing(session, rng):
    """A broadcast side bigger than the pair-slot budget splits into build
    windows; results stay identical incl. right/full leftover emission."""
    s2 = type(session)({"spark.rapids.sql.batchSizeBytes": 64 * 1024,
                        "spark.rapids.tpu.batchRowsMinBucket": 8,
                        "spark.rapids.tpu.autoBroadcastJoinThreshold": -1})
    lt = data_gen(rng, 150, {"a": ("int64", 0, 60)}, null_prob=0.05)
    rt = data_gen(rng, 400, {"b": ("int64", 0, 60)}, null_prob=0.05)
    l = s2.create_dataframe(lt, num_partitions=2)
    r = s2.create_dataframe(rt)
    from spark_rapids_tpu.expr.functions import col as _c
    for how in ("inner", "left", "right", "full", "left_semi", "left_anti"):
        q = l.join(r, how=how, condition=_c("a") == _c("b") + 1)
        dev = q.collect(device=True)
        cpu = q.collect(device=False)
        import pyarrow.compute as pc
        assert dev.num_rows == cpu.num_rows, (how, dev.num_rows, cpu.num_rows)
        d = dev.to_pandas().sort_values(list(dev.column_names)).reset_index(drop=True)
        c = cpu.to_pandas().sort_values(list(cpu.column_names)).reset_index(drop=True)
        import pandas.testing as pdt
        pdt.assert_frame_equal(d, c, check_dtype=False)


def test_mixed_type_join_keys_coerce(session):
    """int64 vs float64 join keys must hash to the same partitions (Spark
    inserts implicit casts): USING joins output the COMMON type, semi/anti
    keep the left side's ORIGINAL type (hidden-key coercion)."""
    import pandas as pd
    s2 = type(session)(session.conf.set(
        "spark.rapids.tpu.autoBroadcastJoinThreshold", -1))
    fact = s2.create_dataframe(pa.table({
        "k": pa.array(np.arange(40, dtype=np.int64) % 10),
        "v": pa.array(np.ones(40))}), num_partitions=3)
    dim = s2.create_dataframe(pa.table({
        "k": pa.array(np.arange(0, 10, 2, dtype=np.float64)),
        "w": pa.array(np.arange(5, dtype=np.float64))}), num_partitions=2)
    # USING inner join: every k in {0,2,4,6,8} matches (4 rows each)
    j = fact.join(dim, on="k")
    assert str(j.schema.field("k").dtype) == "double"  # common type
    out = assert_tpu_cpu_equal(j)
    assert out.num_rows == 20
    # full join: 20 matches + 20 unmatched fact rows
    jf = fact.join(dim, on="k", how="full")
    assert assert_tpu_cpu_equal(jf).num_rows == 40
    # semi/anti: left types preserved, matching still works
    js = fact.join(dim, on="k", how="left_semi")
    assert str(js.schema.field("k").dtype) == "bigint"
    out_s = assert_tpu_cpu_equal(js)
    assert out_s.num_rows == 20
    assert str(out_s.schema.field("k").type) == "int64"
    ja = fact.join(dim, on="k", how="left_anti")
    assert assert_tpu_cpu_equal(ja).num_rows == 20


@pytest.mark.parametrize("strategy", ["sort", "hash"])
def test_join_strategy_differential(strategy):
    """The sort-free hash slot-table join (spark.rapids.tpu.join.strategy)
    matches the sorted searchsorted path and the host engine, including
    duplicate-key builds (which fall back to the general count path) and
    null keys."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import pyarrow as pa
    from spark_rapids_tpu.expr.functions import col, lit
    from spark_rapids_tpu.session import TpuSession
    rng = np.random.default_rng(13)
    n = 20_000
    kv = rng.integers(0, 3000, n)
    kmask = np.ones(n, bool)
    kmask[::37] = False
    fact = pa.table({"k": pa.array(kv, mask=~kmask),
                     "v": rng.normal(size=n)})
    dim = pa.table({"k": np.arange(3000, dtype=np.int64),
                    "w": rng.normal(size=3000)})
    dup = pa.table({"k": np.repeat(np.arange(50, dtype=np.int64), 2),
                    "w": rng.normal(size=100)})
    sess = TpuSession({"spark.rapids.tpu.batchRowsMinBucket": 2048,
                       "spark.rapids.tpu.join.strategy": strategy,
                       "spark.rapids.tpu.autoBroadcastJoinThreshold": -1})
    f = sess.create_dataframe(fact, num_partitions=2)
    for build in (dim, dup):
        d = sess.create_dataframe(build, num_partitions=2)
        for how in ("inner", "left", "left_semi", "left_anti"):
            q = f.join(d.filter(col("k") < lit(1500)), on="k", how=how)
            dev = sorted(map(str, q.collect(device=True).to_pylist()))
            cpu = sorted(map(str, q.collect(device=False).to_pylist()))
            assert dev == cpu, (strategy, how, build.num_rows)


# -- the expand's slot map ----------------------------------------------------

def _slot_reference(mask, counts, starts, b_order, out_cap, outer):
    """The expand's slot map by a binary search of every slot in the running
    slot counts: -> (probe row, build row, valid, build-matched, total)."""
    slot_counts = np.where(mask, np.maximum(counts, 1) if outer else counts, 0)
    cum = np.cumsum(slot_counts)
    j = np.arange(out_cap)
    pi = np.clip(np.searchsorted(cum, j, side="right"), 0, len(mask) - 1)
    k = j - (cum - slot_counts)[pi]
    bi = b_order[np.clip(starts[pi] + k, 0, len(b_order) - 1)]
    valid = j < cum[-1]
    return pi, bi, valid, valid & (counts[pi] > 0), int(cum[-1])


def _slot_case(case, rng):
    """-> (probe row mask, counts, build capacity, out_cap or None for the
    total's size) of one probe batch shape."""
    cap = 64
    mask = np.arange(cap) < 50
    counts = rng.integers(0, 5, cap)
    out_cap = None
    if case.startswith("masked"):
        mask = rng.random(cap) < 0.6
    elif case.startswith("zero-ends"):
        counts = rng.integers(1, 5, cap)
        counts[:5] = counts[20:27] = counts[45:] = 0
        mask = np.ones(cap, bool)
    elif case.startswith("all-empty"):
        counts[:] = 0
    elif case == "exact-fit":
        counts = np.full(cap, 2)                     # total == out_cap
        mask = np.ones(cap, bool)
        out_cap = 2 * cap
    elif case == "roomy":
        out_cap = 1024                               # total < out_cap
    elif case == "one-owner":
        counts[:] = 0
        counts[17] = 256
        out_cap = 256
    elif case == "q13-batch":
        # a 2^17-row batch of 75,000 customers, a third with no orders,
        # the rest 1-36 orders each, into a 2^20-slot output
        cap = 1 << 17
        mask = np.arange(cap) < 75_000
        counts = np.where(rng.random(cap) < 1 / 3, 0,
                          rng.integers(1, 37, cap))
        out_cap = 1 << 20
        return mask, counts, 1 << 21, out_cap
    return mask, counts, 256, out_cap


@pytest.mark.parametrize("case,outer", [
    ("inner", False), ("outer", True), ("masked-inner", False),
    ("masked-outer", True), ("zero-ends-inner", False),
    ("zero-ends-outer", True), ("all-empty", False),
    ("all-empty-outer-masked", True), ("exact-fit", False),
    ("roomy", True), ("one-owner", False), ("q13-batch", True)])
def test_expand_slot_map(case, outer):
    """``_slots``' scatter-and-prefix-sum map of output slots to probe rows
    against a binary search of the running counts: the probe and build row
    and the matched flag of every valid slot, the valid slots and the
    total."""
    import types
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.exec.joins import _JoinKernels
    rng = np.random.default_rng(41)
    mask, counts, bcap, out_cap = _slot_case(case, rng)
    if case == "all-empty-outer-masked":
        mask[:] = False
    counts = counts.astype(np.int64)
    starts = rng.integers(0, bcap - counts.max(initial=0) + 1,
                          len(counts)).astype(np.int64)
    b_order = rng.permutation(bcap).astype(np.int32)
    slot_counts = np.where(mask, np.maximum(counts, 1) if outer else counts, 0)
    if out_cap is None:
        out_cap = max(int(slot_counts.sum()), 1)
    assert slot_counts.sum() <= out_cap

    def slots(mask, b_order, starts, counts):
        probe = types.SimpleNamespace(row_mask=mask, capacity=mask.shape[0])
        build = types.SimpleNamespace(capacity=b_order.shape[0])
        return _JoinKernels(None)._slots(build, probe, b_order, starts,
                                         counts, out_cap, outer)
    got = jax.jit(slots)(jnp.asarray(mask), jnp.asarray(b_order),
                         jnp.asarray(starts), jnp.asarray(counts))
    pi, bi, valid, matched, total = (np.asarray(x) for x in got)
    rpi, rbi, rvalid, rmatched, rtotal = _slot_reference(
        mask, counts, starts, b_order, out_cap, outer)
    assert int(total) == rtotal
    np.testing.assert_array_equal(valid, rvalid)
    np.testing.assert_array_equal(pi[rvalid], rpi[rvalid])
    np.testing.assert_array_equal(bi[rvalid], rbi[rvalid])
    np.testing.assert_array_equal(matched[rvalid], rmatched[rvalid])


def _expand_node(how, condition):
    """A hash join of a probe (k, pv) against a build (rk, bv) on k = rk,
    its children standing in by their schemas alone."""
    import types
    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.exec.joins import TpuShuffledHashJoinExec
    from spark_rapids_tpu.plan.schema import Schema
    left = types.SimpleNamespace(
        schema=Schema.of(("k", dt.LONG), ("pv", dt.LONG)), num_partitions=1)
    right = types.SimpleNamespace(
        schema=Schema.of(("rk", dt.LONG), ("bv", dt.LONG)), num_partitions=1)
    return TpuShuffledHashJoinExec(left, right, ["k"], ["rk"], how,
                                   condition, False, min_bucket=8)


@pytest.mark.parametrize("program,how", [
    ("expand", "left"), ("expand", "inner"), ("expand", "full"),
    ("cond", "left_semi"), ("cond", "left_anti"), ("cond", "left")])
def test_expand_programs_follow_the_slot_map(program, how):
    """Whole expand programs, with and without a residual condition, over a
    duplicate-keyed build and a probe with masked rows and null payloads:
    the rows they emit, in order, are those the binary-search slot map
    gives."""
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar.device import DeviceTable
    from spark_rapids_tpu.columnar.host import HostTable
    rng = np.random.default_rng(42)
    pk, bk = rng.integers(0, 12, 40), rng.integers(0, 9, 30)
    pv = rng.integers(0, 100, 40)
    pv_null = rng.random(40) < 0.15
    bv = rng.integers(0, 100, 30)
    probe = DeviceTable.from_host(HostTable.from_arrow(pa.table({
        "k": pa.array(pk), "pv": pa.array(pv, mask=pv_null)})), capacity=64)
    live = np.arange(64) < 40
    live[[3, 11, 12, 39]] = False
    probe = probe.filter_mask(jnp.asarray(live))
    build = DeviceTable.from_host(HostTable.from_arrow(pa.table({
        "rk": pa.array(bk), "bv": pa.array(bv)})), capacity=32)
    b_order = np.argsort(np.concatenate([bk, np.full(2, 99)]),
                         kind="stable").astype(np.int32)
    sk = bk[b_order[:30]]
    pkeys = np.concatenate([pk, np.zeros(24, np.int64)])
    starts = np.searchsorted(sk, pkeys, side="left").astype(np.int64)
    counts = np.searchsorted(sk, pkeys, side="right") - starts
    # dead rows still carry counts: the map itself must drop them
    counts[40:] = 0
    condition = (col("pv") < col("bv")).expr if program == "cond" else None
    node = _expand_node(how, condition)
    outer = program == "expand" and how in ("left", "full")
    pslots = np.where(live, np.maximum(counts, 1) if outer else counts, 0)
    out_cap = int(pslots.sum())
    args = (build, probe, jnp.asarray(b_order), jnp.asarray(starts),
            jnp.asarray(counts))
    rpi, rbi, rvalid, rmatched, _ = _slot_reference(
        live, counts, starts, b_order, out_cap, outer)
    pv_l = [None if n else int(v) for v, n in zip(pv, pv_null)] + [None] * 24
    bv_l = [int(v) for v in bv] + [None] * 2
    pairs = [(int(pkeys[p]), pv_l[p]) + ((int(bk[b]), bv_l[b]) if m
                                         else (None, None))
             for p, b, m in zip(rpi[rvalid], rbi[rvalid], rmatched[rvalid])]

    def rows(t):
        return [tuple(r.values()) for r in t.to_host().to_arrow().to_pylist()]
    if program == "expand":
        out = node._kernels.expand_fn(out_cap, how)(*args)
        assert rows(out) == pairs
        return
    passes = [p[1] is not None and p[1] < p[3] for p in pairs]
    hit = set(int(p) for p, ok in zip(rpi[rvalid], passes) if ok)
    res = node._kernels.expand_cond_fn(out_cap, how)(*args)
    probe_rows = [(int(pkeys[i]), pv_l[i]) for i in range(64) if live[i]]
    if how == "left_semi":
        assert rows(res) == [r for i, r in zip(np.flatnonzero(live),
                                               probe_rows) if i in hit]
    elif how == "left_anti":
        assert rows(res) == [r for i, r in zip(np.flatnonzero(live),
                                               probe_rows) if i not in hit]
    else:
        kept, pad = res
        assert rows(kept) == [p for p, ok in zip(pairs, passes) if ok]
        assert rows(pad) == [r + (None, None) for i, r in zip(
            np.flatnonzero(live), probe_rows) if i not in hit]
