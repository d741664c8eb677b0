"""The join path as the tracer sees it (``exec/joins.py``): ``join.build`` a
build table, ``join.prep`` (``unique``, ``rounds``, ``full_rounds``) a miss
of the node's prep cache, one ``join.probe.pk`` / ``.semi`` / ``.expand`` /
``.cross`` a probe batch by the branch it took (``.pk`` with the chain
walk's ``rounds`` / ``full_rounds`` where the output's row count is read),
``join.grace`` where the build is over the batch budget — and no sync or
download that the code without the spans did not make. Both broadcast
thresholds are off, so an equi-join is the ``TpuShuffledHashJoinExec`` the
chip plans for ``sf1.q4``, whose build side (``lineitem``) holds every key
several times. The prep cache itself: one entry a live build table of a
node, each under its own lock, closed with its build. And the slot
table that prep builds: one row a distinct key, whatever the duplicates; and
the probe's walk of it, full rounds and tail rounds, against a numpy walk."""
import functools

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from spark_rapids_tpu.expr.functions import col
from spark_rapids_tpu.session import TpuSession
from spark_rapids_tpu.utils.tracing import get_tracer

#: rows a build key is held by; the 64 rows of one key contend for one slot
MULTIPLICITIES = {1: 40, 2: 20, 15: 5, 64: 1}
PROBE_BATCHES = 3


def duplicate_build():
    keys, base = [], 100
    for m, n in MULTIPLICITIES.items():
        keys.append(np.repeat(np.arange(base, base + n) * 4, m))
        base += 100
    keys = np.concatenate(keys).astype(np.int64)
    rng = np.random.default_rng(3)
    rng.shuffle(keys)
    return pa.table({"bk": keys, "w": rng.random(len(keys))})


def unique_build():
    return pa.table({"bk": np.arange(100, 400, dtype=np.int64) * 4,
                     "w": np.random.default_rng(5).random(300)})


def probe():
    rng = np.random.default_rng(4)
    return pa.table({"pk": (rng.integers(90, 450, 500) * 4).astype(np.int64),
                     "v": rng.random(500)})


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
            "spark.rapids.tpu.batchRowsMinBucket": 64,
            "spark.rapids.tpu.shuffle.partitions": 1,
            "spark.rapids.sql.test.enabled": True,
            "spark.rapids.tpu.autoBroadcastJoinThreshold": -1,
            "spark.rapids.tpu.aqe.autoBroadcastJoinThreshold": -1,
            **extra}))
        return opened[-1]

    def events(prefix):
        return [e for e in tracer.events() if e.name.startswith(prefix)]
    yield session, events
    tracer.enabled = was
    tracer.clear()
    for s in opened:
        s.close()


def join(sess, build, how, condition=None):
    b = sess.create_dataframe(build, num_partitions=2)
    p = sess.create_dataframe(probe(), num_partitions=PROBE_BATCHES)
    q = p.join(b, how=how, condition=col("pk") == col("bk")
               if condition is None else condition)
    return q.collect().to_pandas()


class Source:
    """A one-batch child plan over an arrow table, for a join node driven
    directly; ``live`` masks rows of the batch out."""
    num_partitions, children = 1, ()

    def __init__(self, table, min_bucket=64, live=None):
        from spark_rapids_tpu.columnar.device import DeviceTable
        from spark_rapids_tpu.columnar.host import HostTable
        from spark_rapids_tpu.plan.schema import Field, Schema
        import jax.numpy as jnp
        host = HostTable.from_arrow(table)
        self.schema = Schema([Field(n, c.dtype, True) for n, c in
                              zip(host.names, host.columns)])
        self.batch = DeviceTable.from_host(host, min_bucket=min_bucket)
        if live is not None:
            self.batch = self.batch.filter_mask(jnp.asarray(np.pad(
                live, (0, self.batch.capacity - len(live)))))

    def execute_columnar(self, pidx):
        yield self.batch


def crossings(events):
    """(blocking syncs + downloads, programs dispatched): what
    ``host_syncs_per_query`` and ``programs_per_query`` count. The numbers
    the tests hold them to were read from the code before it had the spans
    and threw ``rounds`` away (commit d917b1e, the same plans), so the
    reads of a stage's statistics, which that code made under no span
    (one a handle: ``plan/aqe.py`` ``_device_shard_stats``), are held
    apart."""
    syncs = events("sync")
    stats = [e for e in syncs if e.args.get("parent") == "stage.stats"]
    assert len(stats) == sum(e.args["handles"] for e in events("stage.stats"))
    return (len(syncs) - len(stats) + len(events("d2h")),
            len(events("dispatch")))


def names(events):
    return sorted({e.name for e in events("join.")})


@pytest.mark.parametrize("how", ["left_semi", "left_anti"])
def test_existence_over_a_duplicate_keyed_build(traced, how):
    """The hash tier probes a duplicate-keyed slot table for existence: one
    prep a build table, whose rounds do not follow the longest run of equal
    keys (64 rows here), and the fused program on every probe batch."""
    session, events = traced
    got = join(session(), duplicate_build(), how)
    p, b = probe().to_pandas(), duplicate_build().to_pandas()
    keep = p.pk.isin(b.bk)
    want = p[keep if how == "left_semi" else ~keep]
    assert sorted(got.pk) == sorted(want.pk) and len(got) == len(want)
    assert np.isclose(got.v.sum(), want.v.sum())
    assert 0 < keep.sum() < len(p)

    assert names(events) == ["join.build", "join.prep", "join.probe.pk"]
    (build,) = events("join.build")
    assert build.args["rows"] == 256 and build.args["bytes"] > 0
    # once a build table: the second and third probe batch hit the cache
    (prep,) = events("join.prep")
    assert prep.args["rows"] == 256 and prep.args["unique"] is False
    assert 1 <= prep.args["full_rounds"] <= prep.args["rounds"] < 8
    assert [e.args["rows"] for e in events("join.probe.pk")] \
        == [256] * PROBE_BATCHES
    # every batch's row count is read for the shrink, the walk's trip counts
    # in the same transfer
    assert all(0 <= e.args["full_rounds"] <= e.args["rounds"] < 8
               for e in events("join.probe.pk"))
    # the semi join keeps a few rows a batch and shrinks them, the anti
    # join keeps most and does not
    assert crossings(events) == (10, 8 if how == "left_semi" else 5)


@pytest.mark.parametrize("strategy", ["hash", "sort"])
def test_a_unique_build_takes_the_fused_single_match_program(traced,
                                                             strategy):
    session, events = traced
    got = join(session(**{"spark.rapids.tpu.join.strategy": strategy}),
               unique_build(), "inner")
    want = probe().to_pandas().merge(unique_build().to_pandas(),
                                     left_on="pk", right_on="bk")
    assert len(got) == len(want) and np.isclose(got.w.sum(), want.w.sum())
    assert names(events) == ["join.build", "join.prep", "join.probe.pk"]
    (prep,) = events("join.prep")
    assert prep.args["unique"] is True
    probes = events("join.probe.pk")
    assert len(probes) == PROBE_BATCHES
    if strategy == "hash":
        assert 1 <= prep.args["rounds"] < 8
        assert all(1 <= e.args["full_rounds"] <= e.args["rounds"] < 8
                   for e in probes)
    else:
        # the sorted tier walks no chain
        assert not any("rounds" in e.args for e in [prep] + probes)
    assert crossings(events) == (10, 5)


def test_an_inner_join_over_duplicate_keys_counts_and_expands(traced):
    """Every match is wanted, so the slot table (``unique=False``) is not
    probed: counts, the ``total`` sync, one expand a batch."""
    session, events = traced
    got = join(session(), duplicate_build(), "inner")
    want = probe().to_pandas().merge(duplicate_build().to_pandas(),
                                     left_on="pk", right_on="bk")
    assert len(got) == len(want) and np.isclose(got.w.sum(), want.w.sum())
    assert names(events) == ["join.build", "join.prep", "join.probe.expand"]
    # the hash prep that said no, then the sorted prep the counts use
    assert [(e.args["unique"], "rounds" in e.args)
            for e in events("join.prep")] == [(False, True), (False, False)]
    assert len(events("join.probe.expand")) == PROBE_BATCHES
    assert crossings(events) == (11, 9)


def test_the_sort_tier_counts_and_masks_a_semi_join(traced):
    session, events = traced
    got = join(session(**{"spark.rapids.tpu.join.strategy": "sort"}),
                  duplicate_build(), "left_semi")
    p = probe().to_pandas()
    assert sorted(got.pk) == sorted(
        p.pk[p.pk.isin(duplicate_build().to_pandas().bk)])
    assert names(events) == ["join.build", "join.prep", "join.probe.semi"]
    (prep,) = events("join.prep")
    assert prep.args["unique"] is False and "rounds" not in prep.args
    assert len(events("join.probe.semi")) == PROBE_BATCHES
    assert crossings(events) == (7, 8)


def test_a_cross_join_books_a_span_a_window(traced):
    session, events = traced
    got = join(session(), unique_build().slice(0, 7), "inner",
                  condition=col("pk") < col("bk"))
    p, b = probe().to_pandas(), unique_build().to_pandas()[:7]
    assert len(got) == sum(int((pk < b.bk).sum()) for pk in p.pk)
    assert names(events) == ["join.probe.cross"]
    assert len(events("join.probe.cross")) >= 1
    assert crossings(events) == (5, 11)


def test_a_build_over_the_batch_budget_goes_grace(traced):
    """Driven on the node (no conf reaches a join's ``batch_bytes``): both
    sides split into ``parts`` buckets under ``join.grace``, then every
    bucket is a join of its own with its own prep."""
    from spark_rapids_tpu.columnar.host import HostTable
    from spark_rapids_tpu.exec.joins import TpuShuffledHashJoinExec
    _, events = traced

    node = TpuShuffledHashJoinExec(
        Source(probe()), Source(duplicate_build()), ["pk"], ["bk"],
        "left_semi", None, merge_keys=False, min_bucket=64, batch_bytes=2_000)
    got = pd.concat([HostTable.to_arrow(t.to_host()).to_pandas()
                     for t in node.execute_columnar(0)])
    p = probe().to_pandas()
    assert sorted(got.pk) == sorted(
        p.pk[p.pk.isin(duplicate_build().to_pandas().bk)])
    (grace,) = events("join.grace")
    parts = grace.args["parts"]
    assert parts == 3
    (build,) = events("join.build")
    assert build.args["bytes"] > node.batch_bytes
    assert len(events("join.prep")) == len(events("join.probe.pk")) == parts
    assert not any(e.args["unique"] for e in events("join.prep"))
    assert crossings(events) == (16, 15)


# ---- the prep cache: one entry a live build table -----------------------------
def other_build():
    """A second duplicate-keyed build table of the shape of the first."""
    t = duplicate_build()
    return pa.table({"bk": np.asarray(t["bk"]) + 4, "w": np.asarray(t["w"])})


def registered(table):
    """(spill handle of the build table, its device table)."""
    from spark_rapids_tpu.memory.catalog import SpillPriorities, get_catalog
    batch = Source(table).batch
    return get_catalog().register(batch, SpillPriorities.ACTIVE_ON_DECK), batch


def live(handle):
    return handle.buffer_id in handle.catalog._buffers


def prep_handles(node, owner):
    slot = node._preps[id(owner)]
    return [hit[1][0] for hit in (slot.hash, slot.dense) if hit is not None]


@pytest.mark.parametrize("how,strategy,preps_a_table", [
    ("left_semi", "hash", 1),   # the slot table answers existence
    ("inner", "hash", 2),       # the hash prep says no, the sorted prep counts
    ("left_semi", "sort", 1),
], ids=["hash-existence", "hash-then-sorted", "sorted"])
def test_two_build_tables_of_one_node_keep_a_prep_each(
        traced, how, strategy, preps_a_table):
    """The partitions of a shuffled join run together under the mesh
    exchange's map side, each with its own build table: probed alternately,
    a single-entry cache would prep A, B, A, B."""
    from spark_rapids_tpu.exec.joins import TpuShuffledHashJoinExec
    session, events = traced
    session(**{"spark.rapids.tpu.join.strategy": strategy})
    node = TpuShuffledHashJoinExec(
        Source(probe()), Source(duplicate_build()), ["pk"], ["bk"], how, None,
        merge_keys=False, min_bucket=64)
    (ha, _), (hb, _) = registered(duplicate_build()), registered(other_build())
    batch = Source(probe()).batch
    p = probe().to_pandas()
    rows = {}
    for h, build in ((ha, duplicate_build()), (hb, other_build())) * 2:
        got = sum(int(t.num_rows) for t in node._probe_join(h, [batch]))
        b = build.to_pandas()
        want = p.pk.isin(b.bk).sum() if how == "left_semi" \
            else len(p.merge(b, left_on="pk", right_on="bk"))
        assert got == want
        rows.setdefault(id(h), []).append(got)
    assert len(events("join.prep")) == 2 * preps_a_table
    assert len(node._preps) == 2

    # closing A's build closes A's preps and leaves B's
    of_a, of_b = prep_handles(node, ha), prep_handles(node, hb)
    assert len(of_a) == len(of_b) == preps_a_table
    node._close_preps(ha)
    assert not any(live(h) for h in of_a) and all(live(h) for h in of_b)
    assert list(node._preps) == [id(hb)]
    list(node._probe_join(hb, [batch]))
    assert len(events("join.prep")) == 2 * preps_a_table     # B: a hit
    list(node._probe_join(ha, [batch]))
    assert len(events("join.prep")) == 3 * preps_a_table     # A: prepped anew
    for h in (ha, hb):
        node._close_preps(h)
        node._close_preps(h)       # closing twice is a no-op
        h.close()
    assert not node._preps


def test_a_build_table_back_from_a_spill_replaces_its_entry(traced):
    """The hit test is the table's identity: the same handle handing out
    another table is a miss, and the entry's old prep is closed."""
    from spark_rapids_tpu.exec.joins import TpuShuffledHashJoinExec
    session, events = traced
    session()
    node = TpuShuffledHashJoinExec(
        Source(probe()), Source(duplicate_build()), ["pk"], ["bk"],
        "left_semi", None, merge_keys=False, min_bucket=64)
    handle, table = registered(duplicate_build())
    node._get_prep_hash(table, handle)
    node._get_prep_hash(table, handle)
    (first,) = prep_handles(node, handle)
    assert len(events("join.prep")) == 1
    restored = Source(duplicate_build()).batch    # equal rows, another table
    node._get_prep_hash(restored, handle)
    (second,) = prep_handles(node, handle)
    assert len(events("join.prep")) == 2
    assert not live(first) and live(second) and len(node._preps) == 1
    node._close_preps(handle)
    handle.close()


class Partitions(Source):
    """``n`` partitions, each the one batch."""

    def __init__(self, table, n):
        super().__init__(table)
        self.num_partitions = n


@pytest.mark.parametrize("how,preps", [("left_semi", 1), ("inner", 2)])
def test_a_broadcast_join_preps_its_build_once_for_all_partitions(
        traced, how, preps):
    from spark_rapids_tpu.exec.joins import TpuBroadcastHashJoinExec
    session, events = traced
    session()
    node = TpuBroadcastHashJoinExec(
        Partitions(probe(), PROBE_BATCHES), Source(duplicate_build()),
        ["pk"], ["bk"], how, None, merge_keys=False, min_bucket=64)
    for pidx in range(PROBE_BATCHES):
        assert sum(int(t.num_rows) for t in node.execute_columnar(pidx)) > 0
    assert len(events("join.build")) == 1
    assert len(events("join.prep")) == preps
    # the broadcast lives as long as the node, and its preps with it
    assert list(node._preps) == [id(node._bc_handle)]
    node.release_spill_handles()


def test_preps_of_two_build_tables_do_not_wait_on_each_other(
        traced, monkeypatch):
    """Both threads must be inside ``resolve_scalars`` at once: each waits
    there for the other. Under one lock a node, held across the read, the
    second never gets that far and the barrier breaks."""
    import threading
    from spark_rapids_tpu.exec import joins
    session, events = traced
    session()
    node = joins.TpuShuffledHashJoinExec(
        Source(probe()), Source(duplicate_build()), ["pk"], ["bk"],
        "left_semi", None, merge_keys=False, min_bucket=64)
    builds = [registered(duplicate_build()), registered(other_build())]
    # warm: the prep program is compiled before the threads meet
    node._get_prep_hash(builds[0][1], builds[0][0])
    node._close_preps(builds[0][0])
    barrier = threading.Barrier(2, timeout=30)
    real = joins.resolve_scalars

    def meeting(*values):
        barrier.wait()
        return real(*values)
    monkeypatch.setattr(joins, "resolve_scalars", meeting)
    results = {}

    def prep(i):
        handle, table = builds[i]
        try:
            results[i] = node._get_prep_hash(table, handle)[2]
        except BaseException as e:      # the assertion below shows it
            results[i] = e
    threads = [threading.Thread(target=prep, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive()
    assert results == {0: False, 1: False}, results
    assert not barrier.broken
    assert len(node._preps) == 2
    for handle, _ in builds:
        node._close_preps(handle)
        handle.close()


def test_prep_entries_under_many_threads(traced):
    """More threads than cores, a short switch interval: every thread preps
    its own build table, hits it, closes it, again and again. A lost entry
    shows as a second prep of a table that is still open, a leaked one as
    an entry left behind."""
    import sys
    import threading
    from spark_rapids_tpu.exec.joins import TpuShuffledHashJoinExec
    session, events = traced
    session()
    node = TpuShuffledHashJoinExec(
        Source(probe()), Source(duplicate_build()), ["pk"], ["bk"],
        "left_semi", None, merge_keys=False, min_bucket=64)
    workers, rounds = 16, 4
    builds = [registered(duplicate_build() if i % 2 else other_build())
              for i in range(workers)]
    failures = []

    def work(i):
        handle, table = builds[i]
        try:
            for _ in range(rounds):
                first = node._get_prep_hash(table, handle)
                again = node._get_prep_hash(table, handle)
                assert first[0] is again[0]     # the second is a hit
                node._close_preps(handle)
        except BaseException as e:
            failures.append(e)
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(was)
    assert not failures, failures
    assert len(events("join.prep")) == workers * rounds
    assert not node._preps
    for handle, _ in builds:
        handle.close()


# ---- the slot table itself ---------------------------------------------------
def keys_table(keys, valid=None, live=None):
    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.columnar.device import (DeviceColumn, DeviceTable,
                                                  canonical_names)
    import jax.numpy as jnp
    n = len(keys)
    valid = np.ones(n, bool) if valid is None else valid
    live = np.ones(n, bool) if live is None else live
    col = DeviceColumn(jnp.asarray(keys, jnp.int64), jnp.asarray(valid),
                       dt.LongType(), None)
    return DeviceTable((col,), jnp.asarray(live),
                       jnp.asarray(int(live.sum()), jnp.int32),
                       canonical_names(1))


def chain_hashes(keys):
    """(first bucket hash, step) of int64 keys as uint64, computed apart
    from ``exec/joins.py`` but for the murmur finalizer."""
    from spark_rapids_tpu.shuffle.manager import _fmix_device
    import jax.numpy as jnp
    u = np.asarray(keys, np.int64).astype(np.uint64)
    lo = jnp.asarray((u & np.uint64(0xFFFFFFFF)).astype(np.uint32))
    hi = jnp.asarray((u >> np.uint64(32)).astype(np.uint32))
    h1 = _fmix_device(lo ^ _fmix_device(hi))
    step = _fmix_device(h1 ^ jnp.uint32(0x9E3779B9)) | jnp.uint32(1)
    return (np.asarray(h1).astype(np.uint64),
            np.asarray(step).astype(np.uint64))


def slot_table_cases():
    rng = np.random.default_rng(32)
    cap = 1 << 12
    yield "unique-full", np.arange(cap) * 4, None, None
    yield "one-key", np.full(cap, 7), None, None
    yield "poisson-4", rng.integers(0, cap // 4, cap) * 4, None, None
    yield "long-chains", np.repeat(rng.integers(0, 1 << 40, 16), cap // 16), \
        None, None
    yield "nulls-and-dead-rows", rng.integers(0, 600, cap), \
        rng.random(cap) < 0.8, rng.random(cap) < 0.7
    yield "unique-among-the-live", np.arange(cap) % (cap // 2), None, \
        np.arange(cap) < cap // 2
    yield "nothing-live", np.arange(cap), None, np.zeros(cap, bool)
    yield "tiny", np.array([5, 5, 9, 5, 1, 9, 2, 2]), None, None


@pytest.mark.parametrize("name,keys,valid,live",
                         [pytest.param(*c, id=c[0])
                          for c in slot_table_cases()])
def test_the_slot_table_holds_one_row_a_distinct_key(name, keys, valid, live):
    """What the probe's chain walk relies on, for any build: every distinct
    usable key sits in the table once, no empty slot lies before it on its
    chain, ``unique`` says whether a usable key repeats, and the rounds stay
    far below the longest run of equal keys."""
    from spark_rapids_tpu.exec.joins import _TAIL_SHARE
    keys = np.asarray(keys, np.int64)
    cap = len(keys)
    usable = (np.ones(cap, bool) if valid is None else valid) \
        & (np.ones(cap, bool) if live is None else live)
    slot_row, bv, unique, rounds, full_rounds = walk_programs()[0](
        keys_table(keys, valid, live))
    slot_row = np.asarray(slot_row)
    assert len(slot_row) == 2 * cap and (np.asarray(bv) == keys).all()
    held = slot_row[slot_row >= 0]
    distinct = np.unique(keys[usable])
    assert usable[held].all()
    assert sorted(keys[held]) == sorted(distinct)       # once each
    assert bool(unique) == (len(distinct) == usable.sum())
    # the walk of every usable key finds it before it finds an empty slot
    h1, step = chain_hashes(keys)
    found = ~usable
    for r in range(int(rounds) + 1):
        row = slot_row[((h1 + np.uint64(r) * step)
                        & np.uint64(2 * cap - 1)).astype(np.int64)]
        assert (found | (row >= 0)).all(), (name, r)
        found |= (row >= 0) & (keys[np.clip(row, 0, cap - 1)] == keys)
    assert found.all()
    assert 0 <= int(full_rounds) <= int(rounds) <= 24
    if usable.any():
        assert int(full_rounds) >= (usable.sum() > cap // _TAIL_SHARE)


# ---- the probe's walk of it --------------------------------------------------
BUILD_CAP, PROBE_CAP, MIN_BUCKET, ABSENT = 512, 1024, 64, 200_000


def numpy_walk(slot_row, bv, keys, usable):
    """Every usable row down its chain until its key or an empty slot ->
    (found, bi, slots visited a row)."""
    h1, step = chain_hashes(keys)
    n, slots = len(keys), len(slot_row)
    found, bi = np.zeros(n, bool), np.zeros(n, np.int32)
    depth, open_rows, r = np.zeros(n, np.int64), usable.copy(), 0
    while open_rows.any() and r < slots:
        row = slot_row[((h1 + np.uint64(r) * step)
                        & np.uint64(slots - 1)).astype(np.int64)]
        eq = (row >= 0) & (bv[np.clip(row, 0, len(bv) - 1)] == keys)
        hit = open_rows & eq
        found |= hit
        bi[hit] = row[hit]
        depth[open_rows] += 1
        open_rows &= ~((row < 0) | eq)
        r += 1
    return found, bi, depth


def trip_counts(depth, cap, tail_share):
    """(rounds, full_rounds) a walk of these depths must take: full rounds
    while more than cap / tail_share rows are open, then until none is."""
    tail_cap, full = max(cap // tail_share, 1), 0
    while (depth > full).sum() > tail_cap:
        full += 1
    return max(full, int(depth.max(initial=0))), full


@functools.cache
def walk_programs():
    """(the build's prep, the probe's walk alone), jitted once."""
    import jax
    from spark_rapids_tpu.exec.joins import _JoinKernels
    kernels = _JoinKernels(None)
    return (jax.jit(kernels.build_prep_hash_fn()),
            jax.jit(kernels.probe_slots_fn()))


@functools.cache
def slot_table(duplicates):
    """-> (build keys, their slot table, its key array, a pool of candidate
    probe keys — absent ones, then the build's own — and the depth of each
    candidate's chain by a numpy walk of this very table)."""
    rng = np.random.default_rng(33)
    distinct = rng.choice(1 << 20, BUILD_CAP // (2 if duplicates else 1),
                          replace=False).astype(np.int64) * 4
    bkeys = np.repeat(distinct, 2) if duplicates else distinct
    rng.shuffle(bkeys)
    slot_row, bv, unique, _, _ = walk_programs()[0](keys_table(bkeys))
    assert bool(unique) != duplicates
    slot_row, bv = np.asarray(slot_row), np.asarray(bv)
    pool = np.concatenate([rng.integers(0, 1 << 22, ABSENT) * 4 + 1,
                           np.resize(bv, 20_000)]).astype(np.int64)
    _, _, depth = numpy_walk(slot_row, bv, pool, np.ones(len(pool), bool))
    return bkeys, slot_row, bv, pool, depth


def probe_keys(shape, bv, pool, depth):
    """-> (keys, live) of a probe batch of one of ``PROBE_SHAPES``."""
    rng = np.random.default_rng(34)
    n = MIN_BUCKET - 4 if shape == "minimum-bucket" else PROBE_CAP - 24
    live = np.ones(n, bool)
    if shape in ("mostly-absent", "minimum-bucket"):
        keys = np.concatenate([rng.choice(bv, n // 10),
                               rng.choice(pool[:ABSENT], n - n // 10)])
    elif shape == "all-in-round-0":
        keys = rng.choice(pool[depth == 1], n)
    elif shape == "nothing-live":
        keys, live = rng.choice(pool, n), np.zeros(n, bool)
    else:
        assert shape == "one-long-chain", shape
        # forty rows down the longest chain of an absent key, the rest done
        # in round 0: the tail phase starts at once and runs long
        longest = pool[np.argmax(depth[:ABSENT])]
        keys = np.concatenate([np.full(40, longest),
                               rng.choice(pool[depth == 1], n - 40)])
    rng.shuffle(keys)
    return keys.astype(np.int64), live


PROBE_SHAPES = ["mostly-absent", "all-in-round-0", "nothing-live",
                "one-long-chain", "minimum-bucket"]


@pytest.mark.parametrize("shape", PROBE_SHAPES)
@pytest.mark.parametrize("how,duplicates", [
    ("inner", False), ("left", False), ("left_semi", False),
    ("left_anti", False), ("left_semi", True), ("left_anti", True)])
def test_the_probe_walks_the_rows_still_open(traced, how, duplicates, shape):
    """``probe_slots_fn`` against a numpy walk of the same slot table
    (``found``, ``bi`` and both trip counts to the round), then the join
    node over the same batches against pandas; ``join.probe.pk`` carries the
    trip counts wherever the output's row count is read."""
    from spark_rapids_tpu.columnar.host import HostTable
    from spark_rapids_tpu.exec.joins import (_TAIL_SHARE,
                                             TpuShuffledHashJoinExec)
    import jax.numpy as jnp
    _, events = traced
    bkeys, slot_row, bv, pool, depth = slot_table(duplicates)
    keys, live = probe_keys(shape, bv, pool, depth)

    # the program alone, over the padded batch the node will see
    cap = MIN_BUCKET if shape == "minimum-bucket" else PROBE_CAP
    pv = np.pad(keys, (0, cap - len(keys)))
    usable = np.pad(live, (0, cap - len(live)))
    found, bi, rounds, full_rounds = walk_programs()[1](
        slot_row, bv, jnp.asarray(pv), jnp.asarray(usable))
    want_found, want_bi, depth = numpy_walk(slot_row, bv, pv, usable)
    assert (np.asarray(found) == want_found).all()
    assert (np.asarray(bi) == want_bi).all()
    trips = trip_counts(depth, cap, _TAIL_SHARE)
    assert (int(rounds), int(full_rounds)) == trips
    if shape == "mostly-absent":
        assert trips[1] >= 2 and trips[0] - trips[1] >= 2, trips
    elif shape == "all-in-round-0":
        assert trips == (1, 1)
    elif shape == "nothing-live":
        assert trips == (0, 0) and not want_found.any()
    elif shape == "one-long-chain":
        assert trips[1] == 1 and trips[0] >= 8, trips

    # the join, against pandas
    p = pd.DataFrame({"pk": keys, "v": np.arange(len(keys)) * 0.5})
    b = pd.DataFrame({"bk": bkeys, "w": np.arange(len(bkeys)) * 0.25})
    node = TpuShuffledHashJoinExec(
        Source(pa.Table.from_pandas(p), MIN_BUCKET, live),
        Source(pa.Table.from_pandas(b), MIN_BUCKET), ["pk"], ["bk"], how,
        None, merge_keys=False, min_bucket=MIN_BUCKET)
    got = pd.concat([HostTable.to_arrow(t.to_host()).to_pandas()
                     for t in node.execute_columnar(0)])
    p = p[live]
    if how in ("inner", "left"):
        want = p.merge(b, how=how, left_on="pk", right_on="bk")
    else:
        keep = p.pk.isin(b.bk)
        want = p[keep if how == "left_semi" else ~keep]
    pd.testing.assert_frame_equal(      # ``v`` names the probe row
        got.sort_values("v").reset_index(drop=True),
        want.sort_values("v").reset_index(drop=True), check_dtype=False)

    (span,) = events("join.probe.pk")
    assert span.args["rows"] == cap
    if how == "left" or shape == "minimum-bucket":
        # no row count is read, so no trip count is either
        assert "rounds" not in span.args and "full_rounds" not in span.args
        assert len(events("sync")) == 1         # the prep's ``unique``
    else:
        assert (span.args["rounds"], span.args["full_rounds"]) == trips
        assert len(events("sync")) == 2         # and the row count


# ---- what leaves the expand: ``rows_out``, and ``unmatched`` of an outer join ---
def numpy_counts():
    """Build rows a probe row matches, in the probe's row order."""
    held = duplicate_build().to_pandas().bk.value_counts()
    return probe().to_pandas().pk.map(held).fillna(0).astype(int).to_numpy()


def expand_node(how):
    """The join node driven directly on one probe batch of 500 rows."""
    from spark_rapids_tpu.exec.joins import TpuShuffledHashJoinExec
    return TpuShuffledHashJoinExec(
        Source(probe()), Source(duplicate_build()), ["pk"], ["bk"], how, None,
        merge_keys=False, min_bucket=64)


#: (blocking syncs + downloads, programs) of each drive, read from the code
#: before ``rows_out`` and ``unmatched`` were booked (the parent of the change
#: that added them, the same drives): the counts ride in the transfer that
#: read the output's total, so neither moves
EXPAND_CROSSINGS = {("left", False): (11, 9), ("inner", False): (11, 9),
                    ("left", True): (20, 28), ("inner", True): (20, 28)}


@pytest.mark.parametrize("windowed", [False, True],
                         ids=["one-batch-a-partition", "windowed"])
@pytest.mark.parametrize("how", ["left", "inner"])
def test_the_expand_books_the_rows_it_emits(traced, monkeypatch, how,
                                            windowed):
    """``join.probe.expand`` books ``rows_out`` = the rows the expand
    emitted (numpy's count: every match, and for a left join one
    null-extended row a probe row with none) and, for the outer join alone,
    ``unmatched`` = the live probe rows with no match. A probe over the
    output budget goes in windows: each window books its own, the span that
    only sized them books neither, so the sums hold either way."""
    from spark_rapids_tpu.columnar.host import HostTable
    from spark_rapids_tpu.exec.joins import TpuShuffledHashJoinExec
    session, events = traced
    counts = numpy_counts()
    unmatched = int((counts == 0).sum())
    emitted = int(counts.sum()) + (unmatched if how == "left" else 0)
    assert 0 < unmatched < len(counts) and counts.max() == 15
    if windowed:
        monkeypatch.setattr(TpuShuffledHashJoinExec, "_max_out_rows",
                            lambda self: 64)
        got = pd.concat([HostTable.to_arrow(t.to_host()).to_pandas()
                         for t in expand_node(how).execute_columnar(0)])
    else:
        got = join(session(), duplicate_build(), how)
    assert len(got) == emitted
    assert int(got.bk.isna().sum()) == (unmatched if how == "left" else 0)
    spans = events("join.probe.expand")
    booked = [e for e in spans if "rows_out" in e.args]
    assert sum(e.args["rows_out"] for e in booked) == emitted
    if how == "left":
        assert all("unmatched" in e.args for e in booked)
        assert sum(e.args["unmatched"] for e in booked) == unmatched
    else:
        assert not any("unmatched" in e.args for e in spans)
    if windowed:
        # the whole batch's span sized the windows and emitted nothing
        assert len(spans) == len(booked) + 1 and len(booked) >= 5
        assert spans[0].args["rows"] == 512
        assert all(e.args["rows_out"] <= 2 * 64 or e.args["rows"] <= 64
                   for e in booked)
    else:
        assert len(spans) == len(booked) == PROBE_BATCHES
    assert crossings(events) == EXPAND_CROSSINGS[how, windowed]


def test_phase_totals_sum_rows_out_and_unmatched(traced):
    """The per-query phase totals carry both sums flat, as every counted
    argument: what ``benchmark/readers/query_phases.py`` reads with
    ``field "rows_out"`` or ``field "unmatched"``."""
    from spark_rapids_tpu.utils.tracing import COUNTED_ARGS
    session, _ = traced
    sess = session()
    b = sess.create_dataframe(duplicate_build(), num_partitions=2)
    p = sess.create_dataframe(probe(), num_partitions=PROBE_BATCHES)
    p.join(b, how="left", condition=col("pk") == col("bk")).collect()
    phase = sess.last_query_phases()["phases"]["join.probe.expand"]
    counts = numpy_counts()
    assert "unmatched" in COUNTED_ARGS
    assert phase["calls"] == PROBE_BATCHES
    assert phase["unmatched"] == int((counts == 0).sum())
    assert phase["rows_out"] == int(counts.sum() + (counts == 0).sum())
