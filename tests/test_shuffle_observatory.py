"""Shuffle & collective observatory: per-tier transfer telemetry (ISSUE 19).

Covers the acceptance contract:
- zero overhead when off: every hook compiles down to a single
  module-constant check (bytecode pin, the utils/movement.py pattern)
  and the v12 record's payload is null,
- forensics ring is bounded while the per-(query, shuffle, tier)
  aggregation stays exact,
- sender/receiver stitching over real TCP: the SRTC traced wire header
  pairs the client's recv wall with the server's serve wall for the
  same block,
- straggler attribution: slowest-partition wall vs p50 with the worst
  (shuffle, partition, tier) triple,
- TPC-H end to end (q3/q5): every query's event log carries a v12
  ``shuffle_summary`` whose tier enqueue bytes reconcile EXACTLY with
  the summed ``shuffleBytes`` operator metric,
- the surfacing round-trips: health_check straggler/backpressure
  warnings, diagnose.py findings, compare.py's shuffle-wall/wire-bytes
  gate and the history sentinel's shuffle-wall gate.

Process-wide observatory state is drained between modules by the
conftest ``_drain_shuffle_observatory_per_module`` fixture.
"""
import glob
import json
import os

import pytest

from spark_rapids_tpu.conf import RapidsConf
from spark_rapids_tpu.shuffle import telemetry


@pytest.fixture
def observatory():
    """A fresh process-wide observatory; cleared afterwards so the
    module leaves the default (off) state behind."""
    obs = telemetry.configure_shuffle_telemetry(RapidsConf(
        {"spark.rapids.tpu.shuffle.telemetry.enabled": True}))
    yield obs
    telemetry.reset_shuffle_telemetry()


# ---------------------------------------------------------------------------
# zero overhead when off
# ---------------------------------------------------------------------------
def test_zero_overhead_when_off_bytecode_pin():
    """Off is the default; every hook's FIRST action must be the
    module-constant is-None check — co_names[0] pins that no other
    global (let alone a conf lookup) is touched before the early
    return (the utils/movement.py cost-model pattern)."""
    telemetry.reset_shuffle_telemetry()
    for fn in (telemetry.clock, telemetry.note_transfer):
        assert fn.__code__.co_names[0] == "_OBSERVATORY", fn.__name__
    assert telemetry.active() is None
    # and the disabled path records nothing / returns the null payload
    telemetry.note_transfer("ici", "dispatch", shuffle_id=0,
                            logical_bytes=lambda: 1 / 0)  # never called
    assert telemetry.clock() == 0.0
    assert telemetry.drain_ring() == []
    assert telemetry.query_summary(0) is None


def test_conf_off_means_no_observatory():
    assert telemetry.configure_shuffle_telemetry(RapidsConf({})) is None
    assert telemetry.active() is None


# ---------------------------------------------------------------------------
# ring bound vs exact aggregation
# ---------------------------------------------------------------------------
def test_ring_bounded_aggregation_exact():
    obs = telemetry.configure_shuffle_telemetry(RapidsConf({
        "spark.rapids.tpu.shuffle.telemetry.enabled": True,
        "spark.rapids.tpu.shuffle.telemetry.ringSize": 16,
    }))
    try:
        for i in range(100):
            obs.note("local", "enqueue", shuffle_id=1, partition=i % 4,
                     logical_bytes=10, query_id=7)
        ring = obs.drain_ring()
        assert len(ring) == 16          # oldest dropped
        t = obs.totals()
        assert t["transfers"] == 100    # aggregation exact regardless
        assert t["logical_bytes"] == 1000
        s = obs.query_summary(7)
        assert s["totals"]["transfers"] == 100
        (tier,) = s["tiers"]
        assert tier["tier"] == "local" and tier["count"] == 100
    finally:
        telemetry.reset_shuffle_telemetry()


# ---------------------------------------------------------------------------
# TCP sender/receiver stitching (real sockets, SRTC traced header)
# ---------------------------------------------------------------------------
def test_tcp_stitches_sender_and_receiver_halves(observatory):
    from spark_rapids_tpu.shuffle.serializer import serialize_table
    from spark_rapids_tpu.shuffle.tcp import TcpShuffleTransport
    from spark_rapids_tpu.shuffle.transport import BlockId
    from spark_rapids_tpu.utils.tracing import (TraceContext,
                                                activate_trace_context)
    from spark_rapids_tpu.columnar.host import HostColumn, HostTable
    from spark_rapids_tpu.columnar import dtypes as dt
    import numpy as np

    table = HostTable(["v"], [
        HostColumn(dt.LONG, np.arange(32, dtype=np.int64))])
    a = TcpShuffleTransport()
    b = TcpShuffleTransport()
    try:
        b.add_peer(*a.address)
        a.publish(BlockId(3, 1, 2), serialize_table(table))
        ctx = TraceContext("0123456789abcdef", 1, query_id=42)
        with activate_trace_context(ctx):
            got = dict(b.fetch([BlockId(3, 1, 2)]))
        assert BlockId(3, 1, 2) in got
        # the server thread notes its half AFTER the last sendall: give it
        # a moment before reading the pairs
        import time
        deadline = time.monotonic() + 10
        stitched = observatory.stitched()
        while not stitched and time.monotonic() < deadline:
            time.sleep(0.01)
            stitched = observatory.stitched()
        assert stitched, "no sender/receiver pair stitched"
        (pair,) = [s for s in stitched if s["shuffle_id"] == 3]
        assert pair["trace_id"] == "0123456789abcdef"
        assert pair["map_id"] == 1 and pair["partition"] == 2
        assert pair["send_bytes"] > 0 and pair["recv_bytes"] > 0
        assert pair["send_wall_s"] >= 0 and pair["recv_wall_s"] >= 0
        # both halves attribute to the traced query
        assert observatory.query_summary(42)["totals"]["stitched"] >= 1
    finally:
        a.close()
        b.close()


# ---------------------------------------------------------------------------
# straggler attribution
# ---------------------------------------------------------------------------
def test_straggler_slowest_partition_vs_p50(observatory):
    import time as _time

    def note_wall(partition, wall):
        observatory.note("transport", "fetch", shuffle_id=9,
                         partition=partition,
                         t0=_time.perf_counter() - wall, query_id=5)

    for p, wall in ((0, 0.01), (1, 0.01), (2, 0.012), (3, 0.1)):
        note_wall(p, wall)
    st = observatory.query_summary(5)["straggler"]
    assert st is not None
    assert st["worst"] == {"shuffle_id": 9, "partition": 3,
                           "tier": "transport",
                           "wall_s": pytest.approx(st["slowest_wall_s"])}
    assert st["slowest_wall_s"] == pytest.approx(0.1, rel=0.3)
    assert st["skew"] == pytest.approx(
        st["slowest_wall_s"] / st["p50_wall_s"])
    assert st["skew"] > 4


# ---------------------------------------------------------------------------
# TPC-H end to end: v12 records + metric reconciliation
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tpch_app(tmp_path_factory):
    """q3/q5 under the observatory + event log, replayed."""
    from spark_rapids_tpu.session import TpuSession
    from spark_rapids_tpu.tools import tpch
    from spark_rapids_tpu.tools.eventlog import load_event_log
    logdir = str(tmp_path_factory.mktemp("shuffle_evl"))
    sess = TpuSession({
        "spark.rapids.tpu.eventLog.dir": logdir,
        "spark.rapids.tpu.shuffle.telemetry.enabled": True,
        "spark.rapids.tpu.batchRowsMinBucket": 8,
        "spark.rapids.tpu.shuffle.partitions": 4,
    })
    tables = tpch.gen_all(0, tiny=True)
    dfs = tpch.build_dataframes(sess, tables, num_partitions=2)
    for name in ("q3", "q5"):
        getattr(tpch, name)(dfs).collect(device=True)
    sess.close()
    telemetry.reset_shuffle_telemetry()
    (path,) = glob.glob(os.path.join(logdir, "*.jsonl"))
    records = [json.loads(line) for line in open(path, encoding="utf-8")]
    return load_event_log(path), records


def test_tpch_every_query_carries_v12_shuffle_summary(tpch_app):
    app, _records = tpch_app
    assert len(app.queries) == 2
    for q in app.queries.values():
        sh = q.shuffle_summary
        assert sh is not None, f"q{q.query_id} shuffle_summary missing"
        t = sh["totals"]
        assert t["transfers"] > 0 and t["logical_bytes"] > 0
        assert sh["tiers"] and sh["shuffles"]
        for tier in sh["tiers"]:
            assert tier["tier"] in telemetry.TIERS, tier["tier"]


def test_tpch_tier_bytes_reconcile_with_shuffle_bytes_metric(tpch_app):
    """The acceptance pin: each query's shuffle_summary tier logical
    bytes sum EXACTLY to the summed ``shuffleBytes`` operator metric —
    the observatory's enqueue notes mirror every metrics.add() at the
    exchange chokepoints, so the two ledgers cannot drift."""
    app, _records = tpch_app
    for q in app.queries.values():
        metric = sum(n.get("metrics", {}).get("shuffleBytes", 0)
                     for n in q.nodes)
        assert metric > 0, f"q{q.query_id} moved no shuffle bytes"
        tier_bytes = sum(t["logical_bytes"]
                         for t in q.shuffle_summary["tiers"])
        assert tier_bytes == metric, (
            f"q{q.query_id}: observatory {tier_bytes}B != "
            f"shuffleBytes metric {metric}B")


def test_v12_record_shape(tpch_app):
    """Record-shape pin: ONE shuffle_summary per query with the stable
    key set; the payload's totals carry exactly the documented keys."""
    _app, records = tpch_app
    recs = [r for r in records if r["event"] == "shuffle_summary"]
    assert len(recs) == 2
    for r in recs:
        assert set(r) == {"event", "query_id", "ts", "shuffle"}
        sh = r["shuffle"]
        assert set(sh) == {"totals", "tiers", "shuffles", "straggler"}
        assert set(sh["totals"]) == set(telemetry.TOTAL_KEYS) \
            | {"wall_s", "max_queue_depth"}
        for tier in sh["tiers"]:
            assert {"tier", "count", "logical_bytes", "wire_bytes",
                    "wall_s", "retries", "max_queue_depth",
                    "phases"} <= set(tier)


def test_diagnose_carries_shuffle_summary(tpch_app):
    from spark_rapids_tpu.tools.diagnose import diagnose_app
    app, _records = tpch_app
    report = diagnose_app(app)
    for qd in report.queries:
        assert qd.shuffle is not None
        assert qd.shuffle["totals"]["transfers"] > 0


# ---------------------------------------------------------------------------
# surfacing round-trips on synthetic v12 logs
# ---------------------------------------------------------------------------
def _summary(wall=0.2, wire=4 << 20, retries=0, skew=1.0, depth=0):
    slowest = 0.1 * skew
    return {
        "totals": {"transfers": 8, "logical_bytes": wire,
                   "wire_bytes": wire, "retries": retries, "stitched": 0,
                   "wall_s": wall, "max_queue_depth": depth},
        "tiers": [{"tier": "transport", "count": 8,
                   "logical_bytes": wire, "wire_bytes": wire,
                   "wall_s": wall, "retries": retries,
                   "max_queue_depth": depth,
                   "phases": {"fetch": wall}}],
        "shuffles": [{"shuffle_id": 1, "tier": "transport", "count": 8,
                      "logical_bytes": wire, "wire_bytes": wire,
                      "wall_s": wall, "retries": retries,
                      "max_queue_depth": depth}],
        "straggler": {"slowest_wall_s": slowest, "p50_wall_s": 0.1,
                      "skew": skew,
                      "worst": {"shuffle_id": 1, "partition": 3,
                                "tier": "transport",
                                "wall_s": slowest}} if skew > 1 else None,
    }


def _v12_log(path, app_id, shuffle, stats=None):
    recs = [
        {"event": "app_start", "app_id": app_id, "schema_version": 12,
         "ts": 0.0, "conf": {}},
        {"event": "query_start", "query_id": 0, "ts": 1.0, "plan": "p",
         "trace_id": "t"},
        {"event": "shuffle_summary", "query_id": 0, "ts": 2.0,
         "shuffle": shuffle},
        {"event": "query_end", "query_id": 0, "ts": 2.0, "wall_s": 1.0,
         "final_plan": "p", "aqe_events": [], "spill_count": {},
         "semaphore_wait_s": 0.0, "stats": stats or {}, "trace_id": "t",
         "critical_path": None},
        {"event": "app_end", "ts": 3.0},
    ]
    path.write_text("\n".join(json.dumps(r) for r in recs) + "\n")
    return str(path)


def test_health_check_warns_on_straggler_and_retries(tmp_path):
    from spark_rapids_tpu.tools.eventlog import load_event_log
    app = load_event_log(_v12_log(
        tmp_path / "sh.jsonl", "sh",
        _summary(retries=3, skew=8.0, depth=6)))
    warnings = app.health_check()
    assert any("shuffle straggler" in w and "partition 3" in w
               and "transport tier" in w for w in warnings), warnings
    assert any("retrie" in w and "backpressure" in w
               for w in warnings), warnings
    # balanced + retry-free: no shuffle warnings
    app = load_event_log(_v12_log(tmp_path / "ok.jsonl", "ok", _summary()))
    assert not [w for w in app.health_check()
                if "shuffle" in w.lower()]


def test_diagnose_straggler_and_backpressure_findings(tmp_path):
    from spark_rapids_tpu.tools.diagnose import diagnose_path
    report = diagnose_path(_v12_log(
        tmp_path / "sh.jsonl", "sh",
        _summary(retries=2, skew=8.0, depth=4)))
    (qd,) = report.queries
    metrics = {f.metric: f for f in qd.findings}
    assert "shuffleStraggler" in metrics
    assert "repartition" in metrics["shuffleStraggler"].suggestion
    assert "shuffleBackpressure" in metrics
    assert "backpressure" in metrics["shuffleBackpressure"].suggestion


def test_compare_shuffle_gate(tmp_path):
    from spark_rapids_tpu.tools.compare import compare_apps, shuffle_delta
    # unit: +5% is clean, +50% past the floors flags both keys
    base = {"shuffle_wall_s": 1.0, "wire_bytes": 10 << 20}
    _d, flagged = shuffle_delta(base, {"shuffle_wall_s": 1.04,
                                       "wire_bytes": 10 << 20})
    assert not flagged
    deltas, flagged = shuffle_delta(base, {"shuffle_wall_s": 1.5,
                                           "wire_bytes": 15 << 20})
    assert set(flagged) == {"shuffle_wall_s", "wire_bytes"}
    assert deltas["wire_bytes"] == 5 << 20
    assert shuffle_delta(None, base) == ({}, [])
    # end to end: a regressed run flags in compare_apps + the summary
    a = _v12_log(tmp_path / "a.jsonl", "a", _summary(wall=0.2))
    b = _v12_log(tmp_path / "b.jsonl", "b",
                 _summary(wall=0.5, wire=12 << 20))
    from spark_rapids_tpu.tools.eventlog import load_event_log
    report = compare_apps(load_event_log(a), load_event_log(b))
    assert report.shuffle_regressions()
    assert "SHUFFLE REGRESSION" in report.summary()
    clean = compare_apps(load_event_log(a), load_event_log(a))
    assert not clean.shuffle_regressions()


def test_sentinel_shuffle_wall_gate(tmp_path):
    """Two synthetic runs whose only difference is shuffle-wall growth
    past the 10% + 50ms gate: the sentinel flags shuffle_wall."""
    from spark_rapids_tpu.tools.history import (HistoryStore,
                                                SHUFFLE_WALL_KEY,
                                                run_sentinel)

    def _run(name, wall):
        return _v12_log(tmp_path / f"{name}.jsonl", name,
                        _summary(wall=wall),
                        stats={SHUFFLE_WALL_KEY: wall})

    store = HistoryStore(str(tmp_path / "store"))
    store.append_run(_run("run_a", 1.0), app_id="run_a")
    store.append_run(_run("run_b", 2.0), app_id="run_b")
    verdict = run_sentinel(store, candidate="run_b", baseline="run_a")
    assert not verdict["ok"]
    assert "shuffle_wall" in verdict["flags"]
    assert verdict["shuffle_wall_regressions"][0]["delta"] \
        == pytest.approx(1.0)
    # +4% under the relative gate: clean
    store.append_run(_run("run_c", 1.04), app_id="run_c")
    verdict = run_sentinel(store, candidate="run_c", baseline="run_a")
    assert verdict["ok"] and "shuffle_wall" not in verdict["flags"]


# ---------------------------------------------------------------------------
# 8-virtual-device mesh: the ICI collective tier (heavy -> slow tier)
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_mesh_q3_ici_tier_observed_and_reconciles(tmp_path):
    """q3 on the 8-device virtual mesh: the exchange lowers to the ICI
    all-to-all and the observatory's ici-tier enqueue bytes reconcile
    exactly with the shuffleBytes metric while the dispatch wall is
    real (the MULTICHIP trajectory measurement, in miniature)."""
    from spark_rapids_tpu.parallel.mesh import virtual_cpu_mesh
    from spark_rapids_tpu.session import TpuSession
    from spark_rapids_tpu.tools import tpch
    from spark_rapids_tpu.tools.eventlog import load_event_log
    logdir = str(tmp_path / "evl")
    sess = TpuSession({
        "spark.rapids.tpu.eventLog.dir": logdir,
        "spark.rapids.tpu.shuffle.telemetry.enabled": True,
        "spark.rapids.tpu.batchRowsMinBucket": 8,
        "spark.rapids.tpu.shuffle.partitions": 4,
        "spark.rapids.tpu.aqe.enabled": False,
        "spark.rapids.tpu.autoBroadcastJoinThreshold": -1,
    })
    sess.attach_mesh(virtual_cpu_mesh(8))
    tables = tpch.gen_all(0, tiny=True)
    dfs = tpch.build_dataframes(sess, tables, num_partitions=2)
    out = tpch.q3(dfs).collect(device=True)
    assert out.num_rows > 0
    sess.close()
    telemetry.reset_shuffle_telemetry()
    (path,) = glob.glob(os.path.join(logdir, "*.jsonl"))
    (q,) = load_event_log(path).queries.values()
    sh = q.shuffle_summary
    ici = [t for t in sh["tiers"] if t["tier"] == "ici"]
    assert ici, f"no ici tier in {[t['tier'] for t in sh['tiers']]}"
    assert ici[0]["phases"].get("dispatch", 0.0) > 0
    assert ici[0]["wire_bytes"] > 0
    metric = sum(n.get("metrics", {}).get("shuffleBytes", 0)
                 for n in q.nodes)
    assert metric > 0
    assert sum(t["logical_bytes"] for t in sh["tiers"]) == metric
