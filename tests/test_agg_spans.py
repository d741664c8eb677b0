"""The grouped aggregate as the tracer sees it (``exec/aggregate.py``):
``agg.dense`` / ``agg.scatter`` a batch whose group count the host reads
(``rows`` = the batch's capacity, ``groups``; ``agg.scatter`` with ``rounds``
and ``full_rounds``, the trips of the bucket-resolve loops and those of them
that ran over the whole batch, where the aggregate ran as a program of its
own), ``agg.merge`` a concat-and-merge step of the final aggregate's
cascade (``rows`` = the concat's capacity, ``groups`` = the state it
leaves), ``join.probe.pk`` with ``rows_out`` beside ``rows`` — and no
program and no blocking read that the code without them did not make;
``agg.skip`` a batch a grouped partial passed through, whose decision is
the one read a partition that PR 39 adds."""
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.exec import aggregate
from spark_rapids_tpu.expr.functions import col, sum as fsum
from spark_rapids_tpu.session import TpuSession
from spark_rapids_tpu.tools import tpch
from spark_rapids_tpu.utils.tracing import get_tracer

MIN_BUCKET = 64


@pytest.fixture
def traced():
    """(session factory, events reader): the ring is on for the test."""
    tracer = get_tracer()
    was = tracer.enabled
    tracer.enabled = True
    tracer.clear()
    opened = []

    def session(**extra):
        opened.append(TpuSession({
            "spark.rapids.tpu.batchRowsMinBucket": MIN_BUCKET,
            "spark.rapids.sql.test.enabled": True, **extra}))
        return opened[-1]

    def events(prefix):
        return [e for e in tracer.events() if e.name.startswith(prefix)]
    yield session, events
    tracer.enabled = was
    tracer.clear()
    for s in opened:
        s.close()


def crossings(events):
    """(programs dispatched, blocking syncs + downloads): what
    ``programs_per_query`` and ``host_syncs_per_query`` count. The reads of
    a stage's statistics (one a handle: ``plan/aqe.py``
    ``_device_shard_stats``), which the commits the numbers were read from
    made under no span, are held apart."""
    syncs = events("sync")
    stats = [e for e in syncs if e.args.get("parent") == "stage.stats"]
    assert len(stats) == sum(e.args["handles"] for e in events("stage.stats"))
    return (len(events("dispatch")),
            len(syncs) - len(stats) + len(events("d2h")))


# ---- three plans in the shape of the benchmark's cells ----------------------
def q18_tables():
    """Q18's three tables by hand: 400 orders of 60 customers, 3,000 lines
    drawn so that some orders hold a dozen lines and pass HAVING."""
    rng = np.random.default_rng(18)
    n_ord, n_cust = 400, 60
    okey = np.arange(1, n_ord + 1, dtype=np.int64) * 4
    heavy = rng.choice(n_ord, 40, replace=False)
    l_ord = np.concatenate([rng.integers(0, n_ord, 2400),
                            rng.choice(heavy, 600)])
    return {
        "customer": pa.table({
            "c_custkey": np.arange(1, n_cust + 1, dtype=np.int64),
            "c_name": [f"Customer#{i:09d}" for i in range(1, n_cust + 1)]}),
        "orders": pa.table({
            "o_orderkey": okey,
            "o_custkey": rng.integers(1, n_cust + 1, n_ord).astype(np.int64),
            "o_orderdate": pa.array(
                rng.integers(8035, 10000, n_ord).astype(np.int32),
                pa.int32()).cast(pa.date32()),
            "o_totalprice": np.round(rng.uniform(900, 5e5, n_ord), 2)}),
        "lineitem": pa.table({
            "l_orderkey": okey[l_ord],
            "l_quantity": rng.integers(1, 51, len(l_ord)).astype(np.float64)}),
    }


def q18_by_pandas(t):
    cust, orders, li = (t[n].to_pandas()
                        for n in ("customer", "orders", "lineitem"))
    qty = li.groupby("l_orderkey", as_index=False).agg(
        sum_qty=("l_quantity", "sum"))
    big = qty[qty.sum_qty > 300.0]
    out = cust.merge(orders, left_on="c_custkey", right_on="o_custkey") \
              .merge(big, left_on="o_orderkey", right_on="l_orderkey")
    return out.sort_values(["o_totalprice", "o_orderdate", "o_orderkey"],
                           ascending=[False, True, True]).head(100)


def distinct_keys():
    """3,000 rows of 2,900 keys in one batch: the final aggregate's resolve
    loop leaves over an eighth of its 4,096 rows open after a round, so
    full rounds, a compaction and tail rounds all run."""
    rng = np.random.default_rng(35)
    return {"t": pa.table({
        "k": rng.permutation(np.arange(3000) % 2900).astype(np.int64) * 4,
        "v": rng.integers(1, 51, 3000).astype(np.float64)})}


PLANS = {
    # name: (tables, partitions a table)
    "q1": (lambda: {"lineitem": tpch.gen_lineitem(0, seed=1, rows=3000)}, 3),
    "q3": (lambda: tpch.gen_all(0, tiny=True, seed=3), 2),
    "q18": (q18_tables, 3),
    "distinct": (distinct_keys, 1),
}
QUERIES = {**tpch.QUERIES, "distinct": lambda f: f["t"].group_by("k").agg(
    fsum(col("v")).alias("s"))}
#: read from commit 228df1e (PR 33) with this file's plans; "distinct" from
#: commit 5e4fda7 (PR 34)
PARENT_CROSSINGS = {"q1": (11, 6), "q3": (29, 13), "q18": (48, 24),
                    "distinct": (3, 2)}


def run_plan(session, name):
    make, parts = PLANS[name]
    tables = make()
    frames = tpch.build_dataframes(session(), tables, num_partitions=parts)
    return tables, QUERIES[name](frames).collect().to_pandas()


@pytest.mark.parametrize("name", sorted(PLANS))
def test_the_span_arguments_add_no_program_and_no_sync(traced, name):
    session, events = traced
    tables, got = run_plan(session, name)
    assert len(got) > 0
    assert crossings(events) == PARENT_CROSSINGS[name]
    booked = events("agg.dense") + events("agg.scatter")
    assert booked, "no aggregate batch was booked"
    for e in booked:
        assert e.args["rows"] >= MIN_BUCKET and e.args["rows"] & \
            (e.args["rows"] - 1) == 0, e.args
        assert 0 <= e.args["groups"] <= e.args["rows"]
        assert "rounds" not in e.args or e.name == "agg.scatter"
        assert ("full_rounds" in e.args) == ("rounds" in e.args)
        if "rounds" in e.args:
            assert 0 <= e.args["full_rounds"] <= e.args["rounds"]
    if name == "distinct":
        # both phases of the resolve loop ran, and the answer is right
        (wide,) = [e for e in booked if e.args["groups"] == 2900]
        assert 1 <= wide.args["full_rounds"] < wide.args["rounds"]
        want = tables["t"].to_pandas().groupby("k").v.sum()
        np.testing.assert_array_equal(got.sort_values("k").s, want)
    if name == "q18":
        want = q18_by_pandas(tables)
        assert len(want) >= 5
        assert list(got.o_orderkey) == list(want.o_orderkey)
        assert list(got.c_name) == list(want.c_name)
        np.testing.assert_array_equal(got.sum_qty, want.sum_qty)


def test_q18s_wide_group_by_books_rounds_and_its_probes_match_rates(traced):
    session, events = traced
    tables, _ = run_plan(session, "q18")
    orders_with_lines = tables["lineitem"]["l_orderkey"].to_pandas().nunique()
    # the final aggregate of `big` runs as a program of its own, which
    # returns the resolve loop's trips; at this size its three partial
    # batches are one chunk, so one batch holds every order with a line
    # and nothing is merged (the next test merges)
    wide = [e for e in events("agg.scatter")
            if e.args["groups"] == orders_with_lines]
    assert len(wide) == 1 and wide[0].args["rounds"] >= 2
    assert wide[0].args["rows"] >= sum(
        e.args["groups"] for e in events("agg.scatter")
        if "rounds" not in e.args and e.args["rows"] == 1024)
    assert not events("agg.merge")
    # the fused partials leave their stage as tables alone: no trips
    assert [e for e in events("agg.scatter") if "rounds" not in e.args]
    # the probes that read their output's count carry it beside `rows`
    probes = events("join.probe.pk")
    assert probes
    for e in probes:
        assert ("rows_out" in e.args) == ("rounds" in e.args)
        if "rows_out" in e.args:
            assert 0 <= e.args["rows_out"] <= e.args["rows"]
    kept = [e.args["rows_out"] for e in probes if "rows_out" in e.args]
    assert kept and min(kept) < max(e.args["rows"] for e in probes)


def test_the_phase_totals_hold_the_sums_of_the_spans_arguments(traced):
    """``last_query_phases()`` after a Q18-shaped query: every counted
    argument of every span summed per phase, ``rounds`` / ``full_rounds``
    of the wide group-by and of the PK probes and ``rows`` of the builds
    among them, beside ``calls`` / ``self_s`` / ``bytes``."""
    from spark_rapids_tpu.utils.tracing import COUNTED_ARGS
    session, events = traced
    make, parts = PLANS["q18"]
    sess = session()
    frames = tpch.build_dataframes(sess, make(), num_partitions=parts)
    QUERIES["q18"](frames).collect()
    phases = sess.last_query_phases()["phases"]
    sums = {}
    for e in events(""):
        for k in COUNTED_ARGS & set(e.args):
            by = sums.setdefault(e.name, {})
            by[k] = by.get(k, 0) + e.args[k]
    for name, by in sums.items():
        got = {k: v for k, v in phases[name].items()
               if k not in ("calls", "self_s", "bytes")}
        assert got == {k: v for k, v in by.items() if v}, name
    for name, fields in {"agg.scatter": ("rows", "groups", "rounds",
                                         "full_rounds"),
                         "join.probe.pk": ("rows", "rows_out", "rounds",
                                           "full_rounds"),
                         "join.prep": ("rows", "unique", "rounds"),
                         "join.build": ("rows",), "sync": ("scalars",),
                         "stage.stats": ("shards", "handles")}.items():
        assert all(phases[name][f] > 0 for f in fields), (name, phases[name])
    assert phases["join.build"]["rows"] == sum(
        e.args["rows"] for e in events("join.build"))
    assert "rounds" not in phases["dispatch"]


# ---- a partial that does not reduce passes its batches through --------------
#: the plans above with ``lineitem`` read in 250-row batches, four a
#: partition: (programs, syncs) read from commit 1920b46 (PR 38)
BATCHED_PARENT_CROSSINGS = {"q1": (29, 6), "q18": (56, 24)}


def run_batched(session, name):
    from spark_rapids_tpu.io.memory import InMemorySource
    from spark_rapids_tpu.plan.logical import LogicalScan
    from spark_rapids_tpu.session import DataFrame
    make, parts = PLANS[name]
    tables = make()
    sess = session()
    frames = {n: DataFrame(sess, LogicalScan(InMemorySource(
        t, parts, batch_rows=250 if n == "lineitem" else 1 << 20)))
        for n, t in tables.items()}
    return tables, parts, QUERIES[name](frames).collect().to_pandas()


def test_q18s_partial_passes_through_every_batch_after_a_partitions_first(
        traced):
    """``big``'s partial keeps ~180 groups of a 250-row batch: each of the
    three partitions aggregates its first batch, reads its groups and rows
    in one sync, and passes its other three through (``agg.skip``, no
    branch booked); the final aggregate merges them to pandas' answer.
    One sync a partition more than the parent; two programs fewer: the
    third partition's last two batches, which the parent's partial reduced
    to 40 groups, no longer shrink to the 64-row bucket."""
    session, events = traced
    tables, parts, got = run_batched(session, "q18")
    want = q18_by_pandas(tables)
    assert list(got.o_orderkey) == list(want.o_orderkey)
    np.testing.assert_array_equal(got.sum_qty, want.sum_qty)
    skips = events("agg.skip")
    assert len(skips) == 3 * parts
    assert all(e.args["rows"] == 256 for e in skips)
    firsts = [e for e in events("agg.scatter") if "rounds" not in e.args
              and e.args["rows"] == 256]
    assert len(firsts) == parts and all(
        2 * e.args["groups"] > 250 for e in firsts)
    programs, syncs = BATCHED_PARENT_CROSSINGS["q18"]
    assert crossings(events) == (programs - 2, syncs + parts)


def test_q1s_partial_keeps_reducing_and_its_programs(traced):
    """Six groups a 250-row batch: no batch passes through, every one is
    ``agg.dense``, the parent's programs run, and the only new crossing is
    the read of each partition's first counts."""
    session, events = traced
    _, parts, got = run_batched(session, "q1")
    assert len(got) > 0 and not events("agg.skip")
    assert len(events("agg.dense")) == 4 * parts + 1
    programs, syncs = BATCHED_PARENT_CROSSINGS["q1"]
    assert crossings(events) == (programs, syncs + parts)


# ---- a state that outgrows one batch's bucket --------------------------------
def test_a_state_that_outgrows_a_batch_merges_through_two_capacities(
        traced, monkeypatch):
    """Three batches of 200 distinct keys each (one batch fits the 256-row
    bucket): the running state is 400 keys after the first merge and 600
    after the second, through the 512- and 1,024-row buckets."""
    session, events = traced
    # a chunk is one batch here, as a 2^20-row batch is at SF 1
    monkeypatch.setattr(aggregate, "_CHUNK_ROWS", 256)
    rng = np.random.default_rng(7)
    keys = np.concatenate([np.arange(b * 200, (b + 1) * 200).repeat(2)
                           for b in range(3)]).astype(np.int64) * 4
    vals = rng.integers(1, 51, len(keys)).astype(np.float64)
    df = session(**{"spark.rapids.tpu.shuffle.partitions": 1}) \
        .create_dataframe(pa.table({"k": keys, "v": vals}), num_partitions=3)
    got = df.group_by("k").agg(fsum(col("v")).alias("s")).collect() \
        .to_pandas().sort_values("k")
    want = np.bincount(keys // 4, weights=vals)
    np.testing.assert_array_equal(got.k, np.arange(600) * 4)
    np.testing.assert_array_equal(got.s, want)
    merges = events("agg.merge")
    assert [e.args["groups"] for e in merges] == [400, 600]
    assert [e.args["rows"] for e in merges] == [512, 1024]
    # every step's aggregate is a scatter batch at the concat's capacity
    steps = [e for e in events("agg.scatter")
             if e.args["groups"] in (400, 600)]
    assert [e.args["rows"] for e in steps] == [512, 1024]
    assert all(e.args["rounds"] >= 1 for e in steps)


def test_rounds_count_the_trips_of_the_resolve_loop():
    """The program of its own returns the loops' trip counts, all of them
    and the full rounds among them: one full trip for keys that all land
    in buckets of their own, more where keys share a bucket (300 keys in
    512 buckets cannot all be alone: the first round resolves one key a
    bucket and leaves the rest)."""
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar.device import DeviceTable
    from spark_rapids_tpu.columnar.host import HostTable

    def trips(keys):
        t = DeviceTable.from_host(HostTable.from_arrow(
            pa.table({"k": np.asarray(keys, np.int64)})), min_bucket=512)
        *_, groups, (rounds, full_rounds) = aggregate._hash_group_ids(
            t, ["k"])
        return int(groups), int(rounds), int(full_rounds)

    assert trips([5] * 100) == (1, 1, 1)
    groups, rounds, full_rounds = trips(np.arange(300) * 4)
    assert groups == 300 and rounds >= 2 and 1 <= full_rounds <= rounds
    assert trips([]) == (0, 0, 0)


def test_the_aggregate_spans_name_their_device_on_a_mesh(traced):
    """On a four-device mesh each partition's final aggregate runs on the
    device the all-to-all sent its keys to: ``agg.scatter`` / ``agg.dense``
    (and ``agg.merge``, a step of one partition's cascade) say which, as
    ``sync`` does, so ``tools.trace gaps`` can put the aggregate's seconds
    on each chip."""
    from spark_rapids_tpu.parallel.mesh import data_parallel_mesh
    session, events = traced
    # AQE off: it would coalesce four small partitions into one
    sess = session(**{"spark.rapids.tpu.shuffle.partitions": 4,
                      "spark.rapids.tpu.aqe.enabled": False})
    sess.attach_mesh(data_parallel_mesh(4))
    rng = np.random.default_rng(9)
    t = pa.table({"k": rng.integers(0, 5000, 20000),
                  "v": rng.uniform(0, 1, 20000)})
    got = sess.create_dataframe(t, num_partitions=2).group_by("k").agg(
        fsum(col("v")).alias("s")).collect()
    assert got.num_rows == len(set(t.column("k").to_pylist()))
    aggs = events("agg.")
    assert aggs and all("device" in e.args for e in aggs)
    devices = {e.args["device"] for e in aggs}
    assert devices <= {0, 1, 2, 3} and len(devices) == 4, devices
