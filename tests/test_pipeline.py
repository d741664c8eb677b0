"""Pipelined multi-partition execution engine (parallel/pipeline.py).

Covers the PR 3 acceptance contract:
- pipelined and sequential modes return identical results (TPC-H smoke
  queries + shuffle/broadcast paths),
- an injected mid-stream operator exception surfaces as the SAME
  exception (never a hang) with the originating stage context attached,
- no leaked worker threads / bounded-queue shutdown after
  ``session.close()``,
- the tier-1 queue lint: every prefetch queue in the package is bounded,
- pipelineWait / prefetchQueueDepth metrics flow into the event log and
  are ranked by tools/diagnose.py,
- input donation (donate_argnums) and the byte-based coalesce goal.
"""
import json
import threading
import time
import warnings

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.parallel import pipeline as P
from spark_rapids_tpu.session import TpuSession
from spark_rapids_tpu.tools import tpch

ROWS = 8_000


@pytest.fixture(scope="module")
def lineitem():
    return tpch.gen_lineitem(0, seed=11, rows=ROWS)


@pytest.fixture(scope="module")
def orders():
    return tpch.gen_orders(0, seed=12, rows=2_000)


@pytest.fixture(scope="module")
def customer():
    return tpch.gen_customer(0, seed=13, rows=500)


def _session(pipelined: bool, **extra):
    # TpuSession.__init__ applies the pipeline conf process-wide
    # (configure_pipeline), so build the session right before collecting
    return TpuSession({
        "spark.rapids.tpu.batchRowsMinBucket": 8,
        "spark.rapids.tpu.shuffle.partitions": 4,
        "spark.rapids.tpu.pipeline.enabled": pipelined,
        **extra,
    })


def _sorted_pandas(tbl: pa.Table):
    df = tbl.to_pandas()
    return df.sort_values(list(df.columns)).reset_index(drop=True)


# ---------------------------------------------------------------------------
# correctness parity: pipelined == sequential (rows + ordering semantics)
# ---------------------------------------------------------------------------
def _run_mode(build_query, pipelined: bool, device: bool):
    sess = _session(pipelined)
    try:
        return build_query(sess).collect(device=device)
    finally:
        sess.close()


@pytest.mark.parametrize("qname", ["q1", "q6"])
@pytest.mark.parametrize("device", [True, False])
def test_tpch_smoke_parity(qname, device, lineitem):
    def build(sess):
        df = sess.create_dataframe(lineitem, num_partitions=4)
        return getattr(tpch, qname)({"lineitem": df})

    pipe = _run_mode(build, True, device)
    seq = _run_mode(build, False, device)
    # q1 is ordered (sort by returnflag/linestatus): compare positionally
    assert pipe.num_rows == seq.num_rows
    pd_pipe = pipe.to_pandas().reset_index(drop=True)
    pd_seq = seq.to_pandas().reset_index(drop=True)
    for col in pd_seq.columns:
        if pd_seq[col].dtype.kind in "fc":
            np.testing.assert_allclose(pd_pipe[col], pd_seq[col], rtol=1e-9)
        else:
            assert (pd_pipe[col].astype(str) == pd_seq[col].astype(str)).all()


@pytest.mark.parametrize("device", [True, False])
def test_shuffle_and_broadcast_parity(device, lineitem, orders, customer):
    """q3 exercises the broadcast + shuffled join paths and a sorted
    limit; a plain group-by exercises the exchange tiers."""
    def q3(sess):
        return tpch.q3({
            "lineitem": sess.create_dataframe(lineitem, num_partitions=4),
            "orders": sess.create_dataframe(orders, num_partitions=2),
            "customer": sess.create_dataframe(customer)})

    pipe = _run_mode(q3, True, device)
    seq = _run_mode(q3, False, device)
    np.testing.assert_allclose(
        np.sort(pipe.column("revenue").to_numpy(zero_copy_only=False)),
        np.sort(seq.column("revenue").to_numpy(zero_copy_only=False)),
        rtol=1e-9)

    from spark_rapids_tpu.expr.functions import col, sum as s_

    def grouped(sess):
        df = sess.create_dataframe(lineitem, num_partitions=4)
        return df.group_by("l_returnflag").agg(
            s_(col("l_quantity")).alias("q"))

    gp = _sorted_pandas(_run_mode(grouped, True, device))
    gs = _sorted_pandas(_run_mode(grouped, False, device))
    np.testing.assert_allclose(gp["q"], gs["q"], rtol=1e-9)
    assert (gp["l_returnflag"] == gs["l_returnflag"]).all()


# ---------------------------------------------------------------------------
# failure propagation: same exception, no hang, stage context attached
# ---------------------------------------------------------------------------
class _Injected(ValueError):
    pass


def test_midstream_exception_surfaces_not_hangs(lineitem):
    from spark_rapids_tpu.columnar import dtypes as dt

    sess = _session(True)
    try:
        df = sess.create_dataframe(lineitem, num_partitions=4)

        def bad(it):
            for i, pdf in enumerate(it):
                raise _Injected("boom from operator")
                yield pdf  # pragma: no cover

        q = df.map_in_pandas(bad, {"l_orderkey": dt.LONG})
        t0 = time.monotonic()
        with pytest.raises(_Injected, match="boom from operator"):
            q.collect()
        assert time.monotonic() - t0 < 60, "error took hang-like time"
    finally:
        sess.close()
    assert P.active_workers() == 0


def test_prefetched_propagates_original_exception_with_context():
    def make_iter():
        yield 1
        raise _Injected("stage blew up")

    it = P.prefetched(make_iter, stage="unit:test")
    assert next(it) == 1
    with pytest.raises(_Injected, match="stage blew up") as ei:
        next(it)
    assert "unit:test" in getattr(ei.value, "pipeline_context", ())


def test_prefetched_carries_input_file_holder_across_threads():
    from spark_rapids_tpu.io.file_block import (clear_input_file,
                                                current_input_file,
                                                set_input_file)

    def make_iter():
        for i in range(3):
            set_input_file(f"file{i}.parquet", i, 10)
            yield i

    clear_input_file()
    seen = []
    for item in P.prefetched(make_iter, stage="unit:file"):
        seen.append((item, current_input_file()[0]))
    assert seen == [(0, "file0.parquet"), (1, "file1.parquet"),
                    (2, "file2.parquet")]


# ---------------------------------------------------------------------------
# shutdown: no leaked threads, queues drained, abandoned iterators reaped
# ---------------------------------------------------------------------------
def test_no_leaked_threads_after_close(lineitem):
    from spark_rapids_tpu.expr.functions import col, sum as s_

    before = {t.name for t in threading.enumerate()}
    sess = _session(True)
    df = sess.create_dataframe(lineitem, num_partitions=4)
    df.group_by("l_returnflag").agg(
        s_(col("l_quantity")).alias("q")).collect(device=True)

    # abandon a prefetched iterator mid-stream: close() must reap it
    it = P.prefetched(iter, stage="unit:abandoned", depth=1)  # type: ignore[arg-type]

    def slow():
        for i in range(100):
            time.sleep(0.01)
            yield i

    it = P.prefetched(slow, stage="unit:abandoned", depth=1)
    assert next(it) == 0
    del it
    sess.close()
    deadline = time.monotonic() + 10
    while P.active_workers() and time.monotonic() < deadline:
        time.sleep(0.05)
    assert P.active_workers() == 0
    lingering = {t.name for t in threading.enumerate()} - before
    assert not [n for n in lingering if n.startswith("tpu-prefetch")
                or n.startswith("tpu-pipeline")], lingering


# ---------------------------------------------------------------------------
# tier-1 lint: every prefetch queue in the package must be bounded
# ---------------------------------------------------------------------------
def test_lint_no_unbounded_queues():
    """Migrated into the srtpu-analyze framework (PR 6): the AST-based
    thread checker subsumes the old regex lint. The queue-bound contract
    stays ABSOLUTE — no baseline allowance, no suppressions: an unbounded
    queue at a stage boundary silently re-materializes whole partitions
    in memory."""
    import pathlib

    import spark_rapids_tpu
    from spark_rapids_tpu.tools.analyze import analyze_paths

    pkg = pathlib.Path(spark_rapids_tpu.__file__).parent
    report = analyze_paths([str(pkg)], checks=["thread"])
    offenders = [f.render() for f in report.findings + report.suppressed
                 if f.rule == "thread-unbounded-queue"]
    assert not offenders, offenders
    # the lint is live: a seeded unbounded queue must be caught
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        bad = pathlib.Path(d) / "bad.py"
        bad.write_text("import queue\nq = queue.Queue()\n")
        seeded = analyze_paths([str(bad)], checks=["thread"])
        assert any(f.rule == "thread-unbounded-queue"
                   for f in seeded.findings)
    assert "maxsize" in (pkg / "parallel" / "pipeline.py").read_text()


# ---------------------------------------------------------------------------
# observability: metrics land in the event log; diagnose ranks stalls
# ---------------------------------------------------------------------------
def test_pipeline_metrics_in_event_log_and_trace(tmp_path, lineitem):
    from spark_rapids_tpu.expr.functions import col, sum as s_
    from spark_rapids_tpu.utils.tracing import get_tracer

    sess = _session(True, **{
        "spark.rapids.tpu.eventLog.dir": str(tmp_path),
        "spark.rapids.tpu.trace.enabled": True,
    })
    try:
        get_tracer().clear()
        df = sess.create_dataframe(lineitem, num_partitions=4)
        df.group_by("l_returnflag").agg(
            s_(col("l_quantity")).alias("q")).collect(device=True)
        events = get_tracer().events()
    finally:
        sess.close()
        get_tracer().enabled = False

    # pipelineWait / prefetchQueueDepth on at least one node record
    logs = list(tmp_path.glob("*.jsonl"))
    assert logs
    waits, depths = [], []
    for line in logs[0].read_text().splitlines():
        rec = json.loads(line)
        if rec.get("event") == "node":
            m = rec.get("metrics") or {}
            if "pipelineWait" in m:
                waits.append(rec["name"])
            if "prefetchQueueDepth" in m:
                depths.append(rec["name"])
    assert waits, "no node recorded pipelineWait"
    assert depths, "no node recorded prefetchQueueDepth"

    # trace shows pipeline spans AND genuinely overlapped work: two spans
    # on different threads whose time windows intersect
    assert any(e.cat == "pipeline" for e in events)
    spans = [e for e in events if e.ph == "X" and e.dur > 0]
    overlapped = any(
        a.tid != b.tid and a.ts < b.ts + b.dur and b.ts < a.ts + a.dur
        for i, a in enumerate(spans) for b in spans[i + 1:i + 60])
    assert overlapped, "no overlapping spans across threads in the trace"


def test_diagnose_ranks_pipeline_stalls(tmp_path):
    from spark_rapids_tpu.tools.diagnose import diagnose_path

    records = [
        {"event": "app_start", "app_id": "a", "schema_version": 3,
         "ts": 0.0, "conf": {}},
        {"event": "query_start", "query_id": 1, "ts": 0.0, "plan": "p"},
        {"event": "node", "query_id": 1, "node_id": 0, "parent_id": -1,
         "name": "TpuWholeStage[Project+Filter]", "desc": "", "depth": 0,
         "wall_s": 0.9, "rows": 1000, "batches": 4, "t_first": 0.0,
         "t_last": 0.9, "metrics": {
             "pipelineWait": 0.5,
             "prefetchQueueDepth": {"count": 4, "sum": 0.0, "min": 0.0,
                                    "max": 0.0, "p50": 0.0, "p90": 0.0,
                                    "p99": 0.0}}},
        {"event": "query_end", "query_id": 1, "ts": 1.0, "wall_s": 1.0,
         "final_plan": "p", "aqe_events": [], "spill_count": {},
         "semaphore_wait_s": 0.0, "stats": {}},
        {"event": "app_end", "ts": 1.0},
    ]
    path = tmp_path / "stall.jsonl"
    path.write_text("\n".join(json.dumps(r) for r in records) + "\n")
    rep = diagnose_path(str(path))
    finds = rep.queries[0].findings
    stall = [f for f in finds if f.metric == "pipelineWait"]
    assert stall, [f.metric for f in finds]
    assert "prefetchDepth" in stall[0].suggestion
    assert "queue depth p50=0" in stall[0].detail


# ---------------------------------------------------------------------------
# input donation + byte-based coalesce goal
# ---------------------------------------------------------------------------
def test_donation_entry_point_and_metric(lineitem):
    from spark_rapids_tpu.exec.wholestage import TpuWholeStageExec
    from spark_rapids_tpu.expr.functions import col

    with warnings.catch_warnings():
        # XLA:CPU ignores the donation request with a warning; forcing it
        # here exercises the donating entry point end to end
        warnings.simplefilter("ignore")
        sess = _session(True, **{
            "spark.rapids.tpu.donation.force": True,
            "spark.rapids.tpu.scan.deviceCache.enabled": False,
        })
        try:
            df = sess.create_dataframe(lineitem, num_partitions=2)
            q = df.filter(col("l_quantity") > 10.0).select(
                (col("l_extendedprice") * 0.5).alias("half"))
            plan = sess._physical(q.logical, True)
            ws = [n for n in _walk(plan) if isinstance(n, TpuWholeStageExec)]
            assert ws and all(w.donate_inputs for w in ws)
            out = [b for p in range(plan.num_partitions)
                   for b in plan.execute(p)]
            donated = sum(w.metrics.snapshot().get("donatedBytes", 0)
                          for w in ws)
            assert donated > 0
            # parity against the non-donating run
            seq = _run_mode(
                lambda s: s.create_dataframe(lineitem, num_partitions=2)
                .filter(col("l_quantity") > 10.0)
                .select((col("l_extendedprice") * 0.5).alias("half")),
                False, True)
            import pyarrow as _pa
            got = _pa.concat_tables([t.to_arrow() for t in out])
            np.testing.assert_allclose(
                np.sort(got.column("half").to_numpy(zero_copy_only=False)),
                np.sort(seq.column("half").to_numpy(zero_copy_only=False)),
                rtol=1e-7)
        finally:
            sess.close()


def test_cached_uploads_are_never_donated(lineitem):
    """The scan device cache retains uploads; donating them would corrupt
    the next execution. Exclusive marks must only appear when caching is
    off / declined."""
    from spark_rapids_tpu.columnar.host import HostTable
    from spark_rapids_tpu.exec.transitions import (HostToDeviceExec,
                                                   take_exclusive)
    from spark_rapids_tpu.plan.physical import CpuScanExec
    from spark_rapids_tpu.io.memory import InMemorySource

    src = CpuScanExec(InMemorySource(lineitem.select(["l_quantity"]), 1))
    cached = HostToDeviceExec(src, min_bucket=8, cache_max_bytes=1 << 30)
    for b in cached.execute_columnar(0):
        assert not take_exclusive(b), "cached upload marked exclusive"
    uncached = HostToDeviceExec(src, min_bucket=8, cache_max_bytes=0)
    for b in uncached.execute_columnar(0):
        assert take_exclusive(b), "uncached upload must be exclusive"
        assert not take_exclusive(b), "exclusivity must be consumed once"


def test_cache_retained_batches_are_not_donated(lineitem):
    """df.cache() retains the very DeviceTable objects it yields; the
    cache node must consume the exclusive mark so a donating fused stage
    above it never frees buffers the cache re-serves."""
    from spark_rapids_tpu.exec.wholestage import TpuWholeStageExec
    from spark_rapids_tpu.expr.functions import col

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        sess = _session(True, **{
            "spark.rapids.tpu.donation.force": True,
            "spark.rapids.tpu.scan.deviceCache.enabled": False,
        })
        try:
            df = sess.create_dataframe(lineitem, num_partitions=2).cache()
            q = df.select((col("l_extendedprice") * 0.5).alias("half"))
            first = q.collect(device=True)
            plan = sess._physical(q.logical, True)
            ws = [n for n in _walk(plan) if isinstance(n, TpuWholeStageExec)]
            out = [b for p in range(plan.num_partitions)
                   for b in plan.execute(p)]
            assert sum(int(t.num_rows) for t in out) == ROWS
            donated = sum(w.metrics.snapshot().get("donatedBytes", 0)
                          for w in ws)
            assert donated == 0, "donated a cache-retained batch"
            # the cached second execution must still serve intact data
            second = q.collect(device=True)
            np.testing.assert_allclose(
                np.sort(first.column("half").to_numpy(zero_copy_only=False)),
                np.sort(second.column("half").to_numpy(zero_copy_only=False)),
                rtol=0)
        finally:
            sess.close()


def test_coalesce_bytes_target():
    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.columnar.device import DeviceTable
    from spark_rapids_tpu.columnar.host import HostColumn, HostTable
    from spark_rapids_tpu.exec.transitions import TpuCoalesceBatchesExec
    from spark_rapids_tpu.plan.schema import Field, Schema

    tables = []
    for i in range(6):
        vals = np.arange(64, dtype=np.float64) + 100 * i
        ht = HostTable(["x"], [HostColumn(dt.DOUBLE, vals)])
        tables.append(DeviceTable.from_host(ht, 8))
    per_batch = tables[0].nbytes()

    class _Src:
        children = ()
        schema = Schema([Field("x", dt.DOUBLE, False)])
        num_partitions = 1

        def execute_columnar(self, pidx):
            yield from tables

    # rows goal alone would coalesce everything into one flush; the byte
    # goal forces flushes of ~2 batches each (wide-schema OOM guard)
    node = TpuCoalesceBatchesExec(_Src(), target_rows=1 << 30,
                                  min_bucket=8,
                                  target_bytes=2 * per_batch)
    out = list(node.execute_columnar(0))
    assert 2 <= len(out) < 6, [int(t.num_rows) for t in out]
    assert sum(int(t.num_rows) for t in out) == 6 * 64
    snap = node.metrics.snapshot()
    assert snap.get("coalescedBytes", 0) > 0
    assert "bytes=" in node.node_desc()

    # without the byte goal: single flush (row goal never reached)
    node2 = TpuCoalesceBatchesExec(_Src(), target_rows=1 << 30, min_bucket=8)
    assert len(list(node2.execute_columnar(0))) == 1


def test_coalesce_after_upload_conf_wiring(lineitem):
    from spark_rapids_tpu.exec.transitions import TpuCoalesceBatchesExec
    from spark_rapids_tpu.expr.functions import col

    sess = _session(True, **{
        "spark.rapids.tpu.coalesce.afterUpload.enabled": True,
        "spark.rapids.tpu.coalesce.targetBytes": 1 << 20,
    })
    try:
        df = sess.create_dataframe(lineitem, num_partitions=2)
        q = df.select((col("l_quantity") + 1.0).alias("qq"))
        plan = sess._physical(q.logical, True)
        nodes = [n for n in _walk(plan)
                 if isinstance(n, TpuCoalesceBatchesExec)]
        assert nodes, "coalesce.afterUpload did not insert the exec"
        assert all(n.target_bytes == 1 << 20 for n in nodes)
        got = q.collect(device=True)
        assert got.num_rows == ROWS
    finally:
        sess.close()


def _walk(plan):
    yield plan
    for c in plan.children:
        yield from _walk(c)


# ---------------------------------------------------------------------------
# conf plumbing / sequential fallback
# ---------------------------------------------------------------------------
def test_pipeline_conf_snapshot():
    sess = _session(False, **{
        "spark.rapids.tpu.pipeline.prefetchDepth": 7,
        "spark.rapids.tpu.pipeline.taskPool": 3,
    })
    try:
        assert not P.pipeline_enabled()
        assert P.prefetch_depth() == 7
        assert P.task_pool_size() == 3
        # maybe_prefetched degrades to the plain iterator when off
        it = P.maybe_prefetched(lambda: iter([1, 2]), stage="unit:off")
        assert list(it) == [1, 2]
        assert P.active_workers() == 0
    finally:
        sess.close()
        TpuSession({"spark.rapids.tpu.pipeline.enabled": True}).close()


# ---------------------------------------------------------------------------
# OrderedFanIn: many producers, one ordered consumer (the mesh exchange's
# map side). Nothing here is timed by sleeping: producers meet at barriers
# and events, and a test ends by joining the worker threads.
# ---------------------------------------------------------------------------
FAN_STAGE = "unit:fan"


@pytest.fixture
def pipelining(monkeypatch):
    """Set the process-wide switch for one test: ``pipelining(False)``."""
    def switch(enabled: bool):
        monkeypatch.setitem(P._SETTINGS, "enabled", enabled)
    switch(True)
    return switch


def _fan_threads():
    return [t for t in threading.enumerate()
            if t.name == f"tpu-prefetch:{FAN_STAGE}"]


def _join_fan_threads():
    for t in _fan_threads():
        t.join(timeout=20)
        assert not t.is_alive(), t.name


def test_fan_in_starts_every_producer_before_the_first_item(pipelining):
    """Each producer waits for all the others before it yields: a drain
    that starts them one after another (``prefetched`` made in a loop
    starts at its first ``next()``) breaks the barrier."""
    n, depth = 4, 2
    baseline = P.active_workers()
    barrier = threading.Barrier(n, timeout=20)
    produced = [0] * n

    def maker(p):
        def make():
            barrier.wait()
            for i in range(100):
                produced[p] += 1
                yield (p, i)
        return make

    fan = P.OrderedFanIn([maker(p) for p in range(n)], stage=FAN_STAGE,
                         depth=depth)
    assert fan.producers == n
    assert not _fan_threads()           # nothing runs before the first next()
    assert next(fan) == (0, 0)
    assert len(_fan_threads()) == n
    assert not barrier.broken
    # the bound: a producer is at most its queue and the item in its hand
    # ahead of the consumer
    assert produced[0] <= 1 + depth + 1
    assert all(c <= depth + 1 for c in produced[1:]), produced
    fan.close()
    _join_fan_threads()
    assert P.active_workers() == baseline


@pytest.mark.parametrize("finish", ["last-first", "first-first", "middle-out"])
def test_fan_in_yields_in_producer_order_whoever_finishes_first(
        pipelining, finish):
    n, items = 4, 2
    order = {"last-first": [3, 2, 1, 0], "first-first": [0, 1, 2, 3],
             "middle-out": [2, 1, 3, 0]}[finish]
    finished = [threading.Event() for _ in range(n)]
    finish_log = []

    def maker(p):
        # a producer runs once the one before it in ``order`` has produced
        # everything it has (the queue holds all of it, so it can)
        before = order[order.index(p) - 1] if order.index(p) else None

        def make():
            if before is not None:
                assert finished[before].wait(timeout=20)
            for i in range(items):
                yield (p, i)
            finish_log.append(p)
            finished[p].set()
        return make

    fan = P.OrderedFanIn([maker(p) for p in range(n)], stage=FAN_STAGE,
                         depth=items)
    got = list(fan)
    assert got == [(p, i) for p in range(n) for i in range(items)]
    assert finish_log == order
    _join_fan_threads()


def test_fan_in_failure_reraises_with_context_and_cancels_the_rest(
        pipelining):
    n = 4
    baseline = P.active_workers()
    closed = [False] * n

    def maker(p):
        def make():
            try:
                if p == 1:
                    yield (p, 0)
                    raise _Injected("partition 1 blew up")
                i = 0
                while p > 1 or i < 2:   # partitions 2 and 3 never end
                    yield (p, i)
                    i += 1
            finally:
                closed[p] = True
        return make

    fan = P.OrderedFanIn([maker(p) for p in range(n)], stage=FAN_STAGE,
                         depth=1)
    assert [next(fan) for _ in range(3)] == [(0, 0), (0, 1), (1, 0)]
    with pytest.raises(_Injected, match="partition 1 blew up") as ei:
        next(fan)
    assert FAN_STAGE in getattr(ei.value, "pipeline_context", ())
    _join_fan_threads()     # the endless producers were cancelled
    assert closed == [True] * n
    assert P.active_workers() == baseline
    with pytest.raises(StopIteration):
        next(fan)


def test_fan_in_closed_early_cancels_every_producer(pipelining):
    n = 3
    baseline = P.active_workers()
    snap_before = len(P.pipeline_snapshot()["queues"])
    closed = [False] * n

    def maker(p):
        def make():
            try:
                i = 0
                while True:
                    yield (p, i)
                    i += 1
            finally:
                closed[p] = True
        return make

    fan = P.OrderedFanIn([maker(p) for p in range(n)], stage=FAN_STAGE,
                         depth=2)
    assert next(fan) == (0, 0)
    assert len(P.pipeline_snapshot()["queues"]) == snap_before + n
    fan.close()
    _join_fan_threads()
    assert closed == [True] * n
    assert P.active_workers() == baseline
    assert len(P.pipeline_snapshot()["queues"]) == snap_before


@pytest.mark.parametrize("enabled,makers,producers,threads", [
    (False, 3, 1, 0),       # pipelining off: the serial chain, no worker
    (True, 1, 1, 1),        # one input partition: ``prefetched``
    (True, 0, 0, 0),
], ids=["disabled", "one-partition", "no-partition"])
def test_fan_in_serial_paths(pipelining, enabled, makers, producers,
                             threads):
    pipelining(enabled)
    started = P.pipeline_stats()["workers_started"]
    calling = threading.get_ident()
    ran_on = []

    def maker(p):
        def make():
            ran_on.append(threading.get_ident())
            yield from ((p, i) for i in range(3))
        return make

    fan = P.OrderedFanIn([maker(p) for p in range(makers)], stage=FAN_STAGE)
    assert fan.producers == producers
    assert list(fan) == [(p, i) for p in range(makers) for i in range(3)]
    assert P.pipeline_stats()["workers_started"] - started == threads
    if not enabled:
        assert ran_on == [calling] * makers
    _join_fan_threads()
