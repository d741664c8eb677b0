"""The exchange path as the tracer sees it: spans ``exchange.count`` /
``.shard`` / ``.split`` / ``.gather`` with their bytes, the exchange's
downloads under ``sync`` / ``d2h``, a ``dispatch`` span for each of the three
mesh programs (the all-to-all's carries the bytes handed over), the
partition-id program traced once per shape, and the single-partition gather
kept on the device under a mesh."""
import jax
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.expr.functions import col, sum as fsum
from spark_rapids_tpu.session import TpuSession
from spark_rapids_tpu.utils.tracing import get_tracer

N = 4   # of conftest's 8 virtual devices


def session(mesh=True, **extra):
    from spark_rapids_tpu.parallel.mesh import data_parallel_mesh
    if len(jax.devices()) < N:
        pytest.skip(f"needs {N} virtual devices")
    sess = TpuSession({
        "spark.rapids.tpu.batchRowsMinBucket": 8,
        "spark.rapids.tpu.shuffle.partitions": N,
        "spark.rapids.sql.test.enabled": True,
        # the static lowering: with AQE every exchange is a stage of its
        # own and no mesh stage is planned (tests/benchmark has that plan)
        "spark.rapids.tpu.aqe.enabled": False,
        **extra})
    if mesh:
        sess.attach_mesh(data_parallel_mesh(N))
    return sess


def table(seed=0, rows=600):
    rng = np.random.default_rng(seed)
    return pa.table({"k": rng.integers(0, 40, rows),
                     "v": rng.uniform(0, 10, rows)})


def group_by(sess, t):
    df = sess.create_dataframe(t, num_partitions=3)
    return df.group_by("k").agg(fsum(col("v")).alias("s"))


def count_chunks(monkeypatch):
    from spark_rapids_tpu.exec.exchange import TpuShuffleExchangeExec
    chunks = []
    real = TpuShuffleExchangeExec._exchange_chunk

    def counted(self, batches, shards):
        chunks.append(self)
        return real(self, batches, shards)
    monkeypatch.setattr(TpuShuffleExchangeExec, "_exchange_chunk", counted)
    return chunks


@pytest.mark.parametrize("mesh_stage", [True, False],
                         ids=["kept-sharded", "split"])
def test_an_exchanged_chunk_books_its_spans_and_its_downloads(
        monkeypatch, mesh_stage):
    sess = session(**{
        "spark.rapids.tpu.mesh.stageExecution.enabled": mesh_stage})
    chunks = count_chunks(monkeypatch)
    t = table()
    got = group_by(sess, t).collect().to_pandas()
    want = t.to_pandas().groupby("k", as_index=False).agg(s=("v", "sum"))
    got = got.sort_values("k").reset_index(drop=True)
    assert got.k.tolist() == want.k.tolist()
    assert np.allclose(got.s, want.s, rtol=1e-12)
    n = len(chunks)
    assert n >= 1
    phases = sess.last_query_phases()["phases"]
    for name in ("exchange.count", "exchange.shard"):
        assert phases[name]["calls"] == n, (name, phases[name])
        assert phases[name]["bytes"] > 0, name
    # the all-to-all's dispatch is the one dispatch that carries bytes: the
    # padded slots handed over, n x quota a shard
    assert phases["dispatch"]["bytes"] > 0
    # kept sharded for a mesh stage, a chunk is not split
    assert phases.get("exchange.split", {"calls": 0})["calls"] \
        == (0 if mesh_stage else n)
    # the partition-id download of every chunk is a d2h of 4 bytes a row,
    # its row-count sync a sync: what host_syncs_per_query counts
    assert phases["exchange.count"]["bytes"] % 4 == 0
    assert phases["d2h"]["bytes"] >= phases["exchange.count"]["bytes"]
    assert phases["d2h"]["calls"] >= n + 1      # + the answer's download
    assert phases["sync"]["calls"] >= n
    shuffle_bytes = sum(x.metrics.snapshot()["shuffleBytes"]
                        for x in set(chunks))
    assert phases["exchange.shard"]["bytes"] == shuffle_bytes
    sess.close()


def test_each_mesh_program_is_a_dispatch_span_under_its_name():
    sess = session()
    tracer = get_tracer()
    was = tracer.enabled
    tracer.enabled = True
    tracer.clear()
    try:
        group_by(sess, table(1)).collect()
        programs = [e.args.get("program") for e in tracer.events()
                    if e.name == "dispatch"]
    finally:
        tracer.enabled = was
        tracer.clear()
        sess.close()
    assert {"srt_exchange_pid", "srt_ici_all_to_all",
            "srt_mesh_stage"} <= set(programs), sorted(set(programs))
    calls = sess.last_query_phases()["phases"]["dispatch"]["calls"]
    assert calls == len(programs)


def test_two_exchanges_of_one_shape_trace_the_partition_id_program_once(
        monkeypatch):
    """The count pass used to wrap a fresh lambda in a fresh jax.jit for
    every chunk: a re-trace and a compile request (served from XLA's cache,
    but asked) per chunk of every query."""
    from spark_rapids_tpu.shuffle import manager
    from spark_rapids_tpu.utils.compile_cache import clear_cache
    clear_cache()
    jax.clear_caches()
    traces = []
    real = manager.device_partition_ids

    def traced(t, keys, n):
        traces.append((tuple(keys), n, t.capacity))
        return real(t, keys, n)
    # shuffle/ici.py holds its own reference: only the count pass's program
    # looks the function up when it is built
    monkeypatch.setattr(manager, "device_partition_ids", traced)
    sess = session()
    chunks = count_chunks(monkeypatch)
    t = table(2)
    # a cold and a warm query: the chunk shapes a query of this table has
    # (the entry's first call is lowered once more for its cost analysis)
    for _ in range(2):
        group_by(sess, t).collect()
    assert 1 <= len(traces) <= 3, traces
    warm = len(traces)
    for _ in range(3):
        group_by(sess, t).collect()
    sess.close()
    assert len(chunks) == 5 and len(set(chunks)) == 5   # five exchanges
    assert len(traces) == warm, traces


def top_n(sess, t):
    df = sess.create_dataframe(t, num_partitions=3)
    return (df.group_by("k").agg(fsum(col("v")).alias("s"))
              .sort(col("s").desc()).limit(5))


def nodes(plan):
    return [ln.split("[")[0].split()[0]
            for ln in plan.tree_string().splitlines() if ln.strip()]


@pytest.mark.parametrize("aqe", [False, True], ids=["static", "aqe"])
def test_the_single_partition_gather_stays_on_the_device_under_a_mesh(
        monkeypatch, aqe):
    from spark_rapids_tpu.exec.exchange import TpuLocalExchangeExec
    t = table(5)
    alone = session(mesh=False)
    want = top_n(alone, t).collect().to_pandas()
    alone.close()
    registered = []     # the devices of every batch the gather registers
    own = TpuLocalExchangeExec._own_spill_handle
    monkeypatch.setattr(
        TpuLocalExchangeExec, "_own_spill_handle",
        lambda self, h: (registered.append(h.get().row_mask.devices()),
                         own(self, h))[1])

    sess = session(**{"spark.rapids.tpu.aqe.enabled": aqe})
    executed = []
    physical = sess._physical
    sess._physical = lambda *a, **k: (executed.append(physical(*a, **k)),
                                      executed[-1])[1]
    got = top_n(sess, t).collect().to_pandas()
    plan = executed[-1]
    if hasattr(plan, "final_plan"):
        plan = plan.final_plan()
    names = nodes(plan)
    assert "ShuffleExchangeExec" not in names, plan.tree_string()
    assert "TpuLocalExchangeExec" in names and "TpuShuffleExchangeExec" in names

    def find(node):
        if isinstance(node, TpuLocalExchangeExec):
            return node
        kids = list(node.children) + [getattr(node, a) for a in
                                      ("inner", "stage") if hasattr(node, a)]
        return next((f for f in map(find, kids) if f is not None), None)
    gather = find(plan)
    first = sess.shuffle_mesh().devices.flat[0]
    assert gather.gather_device == first
    assert registered and set().union(*registered) == {first}
    phases = sess.last_query_phases()["phases"]
    assert phases["exchange.gather"]["calls"] == len(registered)
    assert phases["exchange.gather"]["bytes"] \
        == gather.metrics.snapshot()["shuffleBytes"]
    sess.close()
    assert got.k.tolist() == want.k.tolist()
    assert np.allclose(got.s, want.s, rtol=1e-12)


def test_range_partitioning_still_stays_on_the_host_tier_under_a_mesh():
    sess = session(**{"spark.rapids.sql.test.enabled": False})
    df = sess.create_dataframe(table(6), num_partitions=3)
    q = df.sort(col("v").asc())
    plan = sess._physical(q.logical, device=True)
    names = nodes(plan)
    sess.close()
    if "ShuffleExchangeExec" not in names:
        pytest.skip("the planner sorted without a range exchange")
    assert "TpuLocalExchangeExec" not in names


# ---- the staged path (AQE): its planning, its statistics, its reads -----------
#: (programs, syncs, downloads) of the plan below at commit ec800b2 (PR 35),
#: which read every handle's row count under no span
PARENT_Q3_STAGED = (83, 37, 6)


def test_a_staged_q3_books_its_planning_its_statistics_and_their_reads(
        monkeypatch):
    """Q3 as the mesh cell plans it — both joins shuffled, every exchange a
    stage of its own — on four devices: planning between stages is
    ``plan.aqe``, the statistics loop ``stage.stats``, each read of a
    handle's row count a ``sync`` that says which device it read; no
    program and no transfer that commit ec800b2 did not make."""
    from spark_rapids_tpu.plan import aqe
    from spark_rapids_tpu.tools import tpch
    sess = session(**{
        "spark.rapids.tpu.aqe.enabled": True,
        "spark.rapids.tpu.batchRowsMinBucket": 64,
        "spark.rapids.tpu.autoBroadcastJoinThreshold": -1,
        "spark.rapids.tpu.aqe.autoBroadcastJoinThreshold": -1})
    reads = []      # what the statistics loop read, and from which device
    real = aqe._device_shard_stats

    def spied(handles):
        reads.extend(next(iter(h.get().num_rows.devices())).id
                     for h in handles)
        return real(handles)
    monkeypatch.setattr(aqe, "_device_shard_stats", spied)
    frames = {n: sess.create_dataframe(t, num_partitions=2)
              for n, t in tpch.gen_all(0, tiny=True, seed=3).items()}
    q3 = tpch.QUERIES["q3"](frames)
    q3.collect()                 # compile
    tracer = get_tracer()
    was = tracer.enabled
    tracer.enabled = True
    tracer.clear()
    del reads[:]
    try:
        got = q3.collect()
        events = tracer.events()
    finally:
        tracer.enabled = was
        tracer.clear()
    phases = sess.last_query_phases()["phases"]
    sess.close()
    assert got.num_rows > 0
    stages = phases["stage"]["calls"]
    assert stages == 6          # five hash exchanges and the top-n's gather
    # one stage.stats a stage; plan.aqe: the loop's pick and the segment's
    # overrides a stage, and the final segment
    stats = [e for e in events if e.name == "stage.stats"]
    assert [e.args["stage"] for e in stats] == list(range(stages))
    assert phases["stage.stats"]["calls"] == stages
    assert phases["plan.aqe"]["calls"] == 2 * stages + 1
    assert sorted({e.args["stage"] for e in events if e.name == "plan.aqe"}) \
        == list(range(stages + 1))
    assert phases["plan"]["calls"] == 1
    # every read of the statistics loop is a sync of one scalar, on the
    # device the spy saw; the counters say how many
    under = [e for e in events if e.name == "sync"
             and e.args.get("parent") == "stage.stats"]
    assert [e.args["device"] for e in under] == reads and len(reads) > stages
    assert all(e.args["scalars"] == 1 for e in under)
    assert set(reads) == {0, 1, 2, 3}
    assert phases["stage.stats"]["handles"] == len(reads)
    assert phases["stage.stats"]["shards"] == 4 * (stages - 1) + 1
    # the parent's programs and transfers, and the reads it did not count
    assert (phases["dispatch"]["calls"],
            phases["sync"]["calls"] - len(reads),
            phases["d2h"]["calls"]) == PARENT_Q3_STAGED
    # a span that reads or runs on the mesh says -1
    a2a = [e for e in events if e.name == "dispatch"
           and e.args["program"] == "srt_ici_all_to_all"]
    assert len(a2a) == stages - 1 and {e.args["device"] for e in a2a} == {-1}
    # the per-partition joins run, and are read, on every device in turn
    preps = [e.args["device"] for e in events if e.name == "dispatch"
             and e.args["program"] == "srt_join_prep_hash"]
    assert sorted(preps) == [0, 0, 1, 1, 2, 2, 3, 3]


# ---- the map side: one producer an input partition, drained in order ----------
def staged_q3(monkeypatch, pipelined):
    """Q3 as above, warm, with the ring on: (answer, events, phase totals,
    what reached ``_exchange_chunk``: a fingerprint a batch, in order)."""
    import hashlib
    from spark_rapids_tpu.exec.exchange import TpuShuffleExchangeExec
    from spark_rapids_tpu.tools import tpch
    sess = session(**{
        "spark.rapids.tpu.aqe.enabled": True,
        "spark.rapids.tpu.batchRowsMinBucket": 64,
        "spark.rapids.tpu.autoBroadcastJoinThreshold": -1,
        "spark.rapids.tpu.aqe.autoBroadcastJoinThreshold": -1,
        "spark.rapids.tpu.pipeline.enabled": pipelined})
    fed = []
    real = TpuShuffleExchangeExec._exchange_chunk
    real = getattr(real, "real", real)      # a second call in one test

    def spied(self, batches, shards):
        for b in batches:
            digest = hashlib.sha1(np.asarray(b.row_mask).tobytes())
            for c in b.columns:
                digest.update(np.asarray(c.data).tobytes())
            fed.append((b.capacity, next(iter(b.row_mask.devices())).id,
                        digest.hexdigest()))
        return real(self, batches, shards)
    spied.real = real
    monkeypatch.setattr(TpuShuffleExchangeExec, "_exchange_chunk", spied)
    frames = {n: sess.create_dataframe(t, num_partitions=2)
              for n, t in tpch.gen_all(0, tiny=True, seed=3).items()}
    q3 = tpch.QUERIES["q3"](frames)
    q3.collect()                 # compile
    tracer = get_tracer()
    was = tracer.enabled
    tracer.enabled = True
    tracer.clear()
    del fed[:]
    try:
        got = q3.collect()
        events = tracer.events()
    finally:
        tracer.enabled = was
        tracer.clear()
    phases = sess.last_query_phases()["phases"]
    sess.close()
    TpuSession({"spark.rapids.tpu.pipeline.enabled": True}).close()
    return got, events, phases, fed


@pytest.mark.parametrize("pipelined", [True, False],
                         ids=["pipelined", "sequential"])
def test_the_map_side_books_its_producers(monkeypatch, pipelined):
    """One ``exchange.map`` a materialisation of a mesh exchange, with
    ``producers`` = the partitions of its child, all started together: two a
    scan of two files, four a shuffled join (1 with pipelining off: the
    serial drain). The phase totals carry the sum flat."""
    got, events, phases, _ = staged_q3(monkeypatch, pipelined)
    assert got.num_rows > 0
    maps = [e for e in events if e.name == "exchange.map"]
    want = [2, 2, 2, 4, 4] if pipelined else [1] * 5
    assert sorted(e.args["producers"] for e in maps) == want
    assert phases["exchange.map"]["calls"] == 5
    assert phases["exchange.map"]["producers"] == sum(want)
    # the chunks' spans nest in it, on the collective's one thread
    tids = {e.tid for e in maps}
    assert len(tids) == 1
    for name in ("exchange.count", "exchange.shard", "exchange.split"):
        inside = [e for e in events if e.name == name]
        assert inside and {e.tid for e in inside} == tids
        assert {e.args["parent"] for e in inside} == {"exchange.map"}
    # no build table is prepped twice: two joins x four partitions x (the
    # hash prep that says no + the sorted prep), under four threads or one
    assert phases["join.prep"]["calls"] == 16
    assert phases["join.build"]["calls"] == 8
    threads = {e.tid for e in events if e.name == "join.prep"}
    assert len(threads) >= 4 if pipelined else threads == tids


def test_the_map_side_feeds_the_exchange_what_the_serial_drain_feeds_it(
        monkeypatch):
    """Bit for bit: the same batches, from the same devices, in the same
    order reach ``_exchange_chunk``, so chunking, quotas, float summation
    order and the answer are those of ``pipeline.enabled=false``; and the
    same programs and transfers."""
    got, _, phases, fed = staged_q3(monkeypatch, True)
    want, _, serial, fed_serial = staged_q3(monkeypatch, False)
    assert fed == fed_serial and len(fed) >= 10
    assert {device for _, device, _ in fed} == {0, 1, 2, 3}
    assert got.equals(want)
    for name in ("dispatch", "sync", "d2h", "join.prep", "join.probe.expand",
                 "exchange.count", "exchange.shard", "exchange.split"):
        assert phases[name]["calls"] == serial[name]["calls"], name


def test_exchange_count_notes_the_rows_and_slots_of_the_all_to_all(
        monkeypatch):
    """``exchange.count`` a chunk: ``rows`` its live rows, ``quota`` the
    slots a source-destination pair handed to the all-to-all, ``slots`` = n
    x n x quota, what the collective carries padding included; the phase
    totals hold their sums a query (1 - rows / slots: the padding share)."""
    from spark_rapids_tpu.shuffle import ici
    handed = []
    real = ici.ici_all_to_all_exchange

    def seen(table, keys, mesh, axis="dp", quota=None, **kw):
        handed.append((int(np.asarray(table.row_mask).sum()), quota))
        return real(table, keys, mesh, axis, quota=quota, **kw)
    monkeypatch.setattr(ici, "ici_all_to_all_exchange", seen)
    tracer = get_tracer()
    was = tracer.enabled
    tracer.enabled = True
    tracer.clear()
    sess = session(**{"spark.rapids.tpu.mesh.stageExecution.enabled": False})
    try:
        t = table(seed=7, rows=900)
        got = group_by(sess, t).collect().to_pandas()
        phases = sess.last_query_phases()["phases"]
        counts = [e.args for e in tracer.events()
                  if e.name == "exchange.count"]
    finally:
        tracer.enabled = was
        tracer.clear()
        sess.close()
    assert len(got) == len(set(t.column("k").to_pylist()))
    assert len(counts) == len(handed) >= 1
    for args, (live, quota) in zip(counts, handed):
        assert (args["rows"], args["quota"], args["slots"]) \
            == (live, quota, N * N * quota)
        assert args["rows"] <= args["slots"]
    for field, i in (("rows", 0), ("quota", 1)):
        assert phases["exchange.count"][field] == sum(h[i] for h in handed)
    assert phases["exchange.count"]["slots"] \
        == N * N * sum(q for _, q in handed)
    # the partial states cross, one a key a map partition
    assert 0 < sum(h[0] for h in handed) <= 3 * 40
