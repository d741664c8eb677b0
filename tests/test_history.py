"""History store + regression sentinel + history-server UI
(tools/history.py, tools/historyd.py).

Synthetic event logs are hand-written record dicts (the
test_health.py idiom) so verdicts are deterministic; one integration
test drives a real session with ``spark.rapids.tpu.history.dir`` set to
pin the close()-appends contract end to end.
"""
import json
import os
import threading
import urllib.error
import urllib.request

import numpy as np
import pandas as pd
import pytest

from spark_rapids_tpu.tools.history import (COMPILE_COUNT_KEY,
                                            SYNC_COUNT_KEY, HistoryStore,
                                            run_sentinel)
from spark_rapids_tpu.tools.history import main as history_main


def _write_log(path, app_id, wall=1.0, stats=None, skew_rows=None,
               n_queries=2, error_qid=None, fault_qids=()):
    """One synthetic schema-v7 event log: ``n_queries`` queries of
    ``wall`` seconds each, a two-node plan, optional per-query counter
    stats, and an optional shuffle_skew record built from an explicit
    per-partition row list. Queries in ``fault_qids`` additionally carry
    schema-v8 ``fault`` + ``recovery`` records (an injected-chaos run)."""
    recs = [{"event": "app_start", "app_id": app_id, "schema_version": 7,
             "ts": 1000.0, "conf": {}}]
    for qid in range(1, n_queries + 1):
        t0 = 1000.0 + qid * 10
        recs.append({"event": "query_start", "query_id": qid, "ts": t0,
                     "plan": "TpuHashAggregateExec\n  TpuScanExec"})
        recs.append({"event": "node", "query_id": qid, "node_id": 0,
                     "parent_id": -1, "name": "TpuHashAggregateExec",
                     "desc": "keys=[g]", "depth": 0, "wall_s": wall,
                     "rows": 100, "batches": 1, "t_first": 0.0,
                     "t_last": wall, "peak_device_bytes": 1 << 20,
                     "metrics": {}})
        recs.append({"event": "node", "query_id": qid, "node_id": 1,
                     "parent_id": 0, "name": "TpuScanExec",
                     "desc": "table", "depth": 1, "wall_s": wall * 0.4,
                     "rows": 400, "batches": 2, "t_first": 0.0,
                     "t_last": wall * 0.4, "peak_device_bytes": 1 << 18,
                     "metrics": {}})
        if skew_rows is not None:
            mean = sum(skew_rows) / len(skew_rows)
            recs.append({
                "event": "shuffle_skew", "query_id": qid, "node_id": 2,
                "name": "ShuffleExchangeExec",
                "partitions": len(skew_rows),
                "rows": {"min": min(skew_rows),
                         "p50": sorted(skew_rows)[len(skew_rows) // 2],
                         "max": max(skew_rows), "mean": mean,
                         "imbalance": max(skew_rows) / mean},
                "bytes": {"min": 8 * min(skew_rows),
                          "p50": 8 * sorted(skew_rows)[len(skew_rows) // 2],
                          "max": 8 * max(skew_rows), "mean": 8 * mean,
                          "imbalance": max(skew_rows) / mean},
                "per_partition_rows": list(skew_rows)})
        if qid in fault_qids:
            recs.append({"event": "fault", "query_id": qid, "ts": t0,
                         "point": "worker.task", "action": "kill",
                         "fire": 1, "evaluation": 2})
            recs.append({"event": "recovery", "query_id": qid,
                         "ts": t0 + wall,
                         "recovery": {"worker_deaths": 1,
                                      "task_resubmissions": 1}})
        end = {"event": "query_end", "query_id": qid, "ts": t0 + wall,
               "wall_s": wall, "stats": dict(stats or {})}
        if qid == error_qid:
            end["error"] = "RuntimeError: boom"
        recs.append(end)
    recs.append({"event": "app_end", "ts": 2000.0})
    with open(path, "w", encoding="utf-8") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
    return path


_BASE_STATS = {SYNC_COUNT_KEY: 5, COMPILE_COUNT_KEY: 3}


def test_store_round_trip_and_headline(tmp_path):
    log = _write_log(str(tmp_path / "a.jsonl"), "app-a",
                     stats=_BASE_STATS, skew_rows=[10, 10, 300, 10])
    art = tmp_path / "trace.json"
    art.write_text("{}")
    store = HistoryStore(str(tmp_path / "store"))
    app_id = store.append_run(log, artifacts=[str(art)])
    assert app_id == "app-a"

    h = store.index()["app-a"]
    assert h["schema_version"] == 7
    assert h["n_queries"] == 2 and h["n_errors"] == 0
    assert h["total_wall_s"] == pytest.approx(2.0)
    q1 = h["queries"]["1"] if "1" in h["queries"] else h["queries"][1]
    assert q1["wall_s"] == pytest.approx(1.0)
    assert q1["sync_count"] == 5 and q1["compile_count"] == 3
    # the headline surfaces the run's worst rows-imbalance
    assert q1["skew_imbalance"] == pytest.approx(300 / 82.5)

    # a FRESH store object over the same directory (new-process analogue)
    # lists the run and replays the copied event log + artifact
    fresh = HistoryStore(str(tmp_path / "store"))
    assert [a["app_id"] for a in fresh.apps()] == ["app-a"]
    app = fresh.load("app-a")
    assert app.schema_version == 7
    assert len(app.query(1).shuffle_skew) == 1
    assert os.path.exists(os.path.join(
        fresh.app_dir("app-a"), "artifacts", "trace.json"))


def test_index_survives_concurrent_writers(tmp_path):
    """Racing appends must converge on a complete, never-torn index:
    every writer rebuilds by rescanning app dirs and atomically replaces
    index.json, so the last replace wins with the full superset."""
    store_dir = str(tmp_path / "store")
    n = 8
    logs = [_write_log(str(tmp_path / f"l{i}.jsonl"), f"app-{i:02d}",
                       stats=_BASE_STATS) for i in range(n)]
    errors = []

    def _append(i):
        try:
            HistoryStore(store_dir).append_run(logs[i])
        except Exception as e:  # pragma: no cover — the failure signal
            errors.append(e)

    threads = [threading.Thread(target=_append, args=(i,))
               for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    store = HistoryStore(store_dir)
    # index.json parses (atomic replace: no torn writes) and, after a
    # rebuild by any reader, covers every app dir on disk
    store.rebuild_index()
    assert sorted(store.index()) == [f"app-{i:02d}" for i in range(n)]


def test_sentinel_clean_then_regressed(tmp_path):
    store = HistoryStore(str(tmp_path / "store"))
    store.append_run(_write_log(str(tmp_path / "b.jsonl"), "base",
                                wall=1.0, stats=_BASE_STATS))
    store.append_run(_write_log(str(tmp_path / "c.jsonl"), "clean",
                                wall=1.0, stats=_BASE_STATS))
    store.pin_baseline("base")
    assert store.baseline_app_id() == "base"

    v = run_sentinel(store, candidate="clean")
    assert v["ok"] is True and v["status"] == "clean"
    assert v["baseline"] == "base" and v["flags"] == []
    # the verdict persists into the store and folds into the index
    assert store.verdict("clean")["ok"] is True
    assert store.index()["clean"]["verdict"]["ok"] is True

    # regressed run: 10x wall plus sync/compile counter explosions well
    # past the 10%/abs-2 count gates
    store.append_run(_write_log(
        str(tmp_path / "r.jsonl"), "regressed", wall=10.0,
        stats={SYNC_COUNT_KEY: 60, COMPILE_COUNT_KEY: 58}))
    v = run_sentinel(store, candidate="regressed")
    assert v["ok"] is False and v["status"] == "regressed"
    assert "wall_time" in v["flags"]
    assert "sync_count" in v["flags"]
    assert "compile_count" in v["flags"]
    assert v["sync_count_regressions"] and v["compile_count_regressions"]
    assert store.index()["regressed"]["verdict"]["ok"] is False


def test_sentinel_treats_recovered_chaos_run_as_clean(tmp_path):
    """A candidate whose queries carry schema-v8 fault records but no
    errors (an injected-chaos run that recovered to the right answer,
    e.g. a chaos drive) is exempt from every gate — its recovery
    overhead is paid on purpose. A query that regressed WITHOUT
    injection in the same run still flags."""
    store = HistoryStore(str(tmp_path / "store"))
    store.append_run(_write_log(str(tmp_path / "b.jsonl"), "base",
                                wall=1.0, stats=_BASE_STATS))
    store.pin_baseline("base")

    # every query slower + counter explosions, but all injected+recovered
    store.append_run(_write_log(
        str(tmp_path / "ch.jsonl"), "chaos", wall=10.0,
        stats={SYNC_COUNT_KEY: 60, COMPILE_COUNT_KEY: 58},
        fault_qids=(1, 2)))
    v = run_sentinel(store, candidate="chaos")
    assert v["ok"] is True and v["status"] == "clean"
    assert v["flags"] == []
    assert v["chaos_recovered_queries"] == [1, 2]

    # same slowdown but only query 2 was injected: query 1's regression
    # is real and still gates
    store.append_run(_write_log(
        str(tmp_path / "m.jsonl"), "mixed", wall=10.0,
        stats=_BASE_STATS, fault_qids=(2,)))
    v = run_sentinel(store, candidate="mixed", baseline="base")
    assert v["ok"] is False and "wall_time" in v["flags"]
    assert v["wall_regressed_queries"] == [1]
    assert v["chaos_recovered_queries"] == [2]

    # an injected query that ERRORED is not exempt — recovery failed
    store.append_run(_write_log(
        str(tmp_path / "e.jsonl"), "chaos-err", wall=10.0,
        stats=_BASE_STATS, fault_qids=(1, 2), error_qid=1))
    v = run_sentinel(store, candidate="chaos-err", baseline="base")
    assert v["chaos_recovered_queries"] == [2]


def test_sentinel_total_wall_gate(tmp_path):
    """The v13 aggregate gate (the MULTICHIP trajectory number): summed
    wall over the queries present in both runs flags past the relative
    threshold AND the 2s absolute floor — a material fleet-wide slowdown
    trips it, while the same relative growth on a tiny run doesn't
    flap the sentinel."""
    store = HistoryStore(str(tmp_path / "store"))
    store.append_run(_write_log(str(tmp_path / "b.jsonl"), "base",
                                wall=1.0, stats=_BASE_STATS))
    store.pin_baseline("base")

    # 2 queries x (1.0s -> 3.0s): total 2s -> 6s, past 20% and the floor
    store.append_run(_write_log(str(tmp_path / "s.jsonl"), "slow",
                                wall=3.0, stats=_BASE_STATS))
    v = run_sentinel(store, candidate="slow")
    assert "total_wall" in v["flags"]
    assert v["total_wall"]["baseline_s"] == pytest.approx(2.0)
    assert v["total_wall"]["candidate_s"] == pytest.approx(6.0)
    assert v["total_wall"]["n_queries"] == 2

    # 50% relative growth but only +1s aggregate: under the 2s floor,
    # the per-query wall gate still owns this one
    store.append_run(_write_log(str(tmp_path / "j.jsonl"), "jitter",
                                wall=1.5, stats=_BASE_STATS))
    v = run_sentinel(store, candidate="jitter", baseline="base")
    assert "total_wall" not in v["flags"]
    assert v["total_wall"]["candidate_s"] == pytest.approx(3.0)


def test_sentinel_no_baseline_and_cli_exit_codes(tmp_path):
    store_dir = str(tmp_path / "store")
    store = HistoryStore(store_dir)
    store.append_run(_write_log(str(tmp_path / "one.jsonl"), "only",
                                stats=_BASE_STATS))
    v = run_sentinel(store)
    assert v["ok"] is True and v["status"] == "no-baseline"

    # second run regresses against the implicit prior-run baseline —
    # the CLI contract: exit 1 on regression, 0 on clean
    store.append_run(_write_log(
        str(tmp_path / "two.jsonl"), "slow", wall=9.0,
        stats={SYNC_COUNT_KEY: 90, COMPILE_COUNT_KEY: 80}))
    assert history_main(["sentinel", "--dir", store_dir,
                         "--candidate", "slow"]) == 1
    store.append_run(_write_log(str(tmp_path / "three.jsonl"), "ok-run",
                                stats=_BASE_STATS))
    assert history_main(["sentinel", "--dir", store_dir,
                         "--candidate", "ok-run",
                         "--baseline", "only"]) == 0
    assert history_main(["list", "--dir", store_dir]) == 0


def _get(url):
    try:
        with urllib.request.urlopen(url, timeout=10) as r:
            return r.status, r.read().decode("utf-8")
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode("utf-8")


def test_history_server_ui_smoke(tmp_path):
    from spark_rapids_tpu.tools.historyd import HistoryServer
    store = HistoryStore(str(tmp_path / "store"))
    store.append_run(_write_log(str(tmp_path / "a.jsonl"), "run-a",
                                wall=1.0, stats=_BASE_STATS,
                                skew_rows=[5, 5, 200, 5]))
    store.append_run(_write_log(str(tmp_path / "b.jsonl"), "run-b",
                                wall=2.0, stats=_BASE_STATS,
                                skew_rows=[5, 5, 200, 5]))
    run_sentinel(store, candidate="run-b", baseline="run-a")

    srv = HistoryServer(store, port=0).start()
    try:
        assert srv.port > 0  # ephemeral bind
        st, body = _get(srv.url + "/")
        assert st == 200 and "run-a" in body and "run-b" in body
        assert "<svg" in body  # trend sparkline (two runs)

        st, body = _get(srv.url + "/app/run-a")
        assert st == 200 and "/app/run-a/query/1" in body

        st, body = _get(srv.url + "/app/run-a/query/1")
        assert st == 200
        assert "TpuHashAggregateExec" in body and "self-time" in body
        assert "shuffle skew" in body  # the v7 table renders

        st, body = _get(srv.url + "/diff?a=run-a&b=run-b")
        assert st == 200

        st, body = _get(srv.url + "/healthz")
        assert st == 200 and json.loads(body)["runs_indexed"] == 2

        st, body = _get(srv.url + "/metrics")
        assert st == 200
        assert "spark_rapids_tpu_history_runs_indexed 2" in body
        assert "spark_rapids_tpu_history_store_bytes" in body
        assert 'outcome="regressed"' in body

        st, _body = _get(srv.url + "/app/no-such-run")
        assert st == 404
        st, _body = _get(srv.url + "/nope")
        assert st == 404
    finally:
        srv.stop()


def test_shuffle_skew_record_schema_v7_pin():
    """The skew pin: shuffle_skew is registered at exactly schema 7
    (the writer has since moved on — v8 fault/recovery, v9 oom_retry), and
    the summary math the exchanges feed from (utils/metrics.py)
    produces the pinned stat keys."""
    from spark_rapids_tpu.tools.eventlog import (RECORD_TYPES,
                                                 SCHEMA_VERSION)
    from spark_rapids_tpu.utils.metrics import (build_skew_record,
                                                skew_summary)
    assert SCHEMA_VERSION == 12
    assert RECORD_TYPES["shuffle_skew"] == 7
    assert max(RECORD_TYPES.values()) == SCHEMA_VERSION

    s = skew_summary([10, 10, 300, 10])
    assert set(s) == {"min", "p50", "max", "mean", "imbalance"}
    assert s["min"] == 10 and s["max"] == 300
    assert s["imbalance"] == pytest.approx(300 / 82.5)
    rec = build_skew_record([10, 10, 300, 10], [80, 80, 2400, 80])
    assert set(rec) == {"partitions", "rows", "bytes",
                        "per_partition_rows"}
    assert rec["partitions"] == 4
    assert rec["per_partition_rows"] == [10, 10, 300, 10]
    # degenerate inputs stay well-formed (imbalance 1.0 = balanced)
    assert skew_summary([])["imbalance"] == 1.0


def test_session_close_appends_run(tmp_path):
    """Integration: a session with spark.rapids.tpu.history.dir appends
    its run on close; a fresh store over the same directory lists it and
    replays per-query detail including v7 skew records."""
    from spark_rapids_tpu.expr.functions import col, sum as f_sum
    from spark_rapids_tpu.session import TpuSession
    store_dir = str(tmp_path / "store")
    sess = TpuSession({
        "spark.rapids.tpu.eventLog.dir": str(tmp_path / "logs"),
        "spark.rapids.tpu.history.dir": store_dir,
        "spark.rapids.tpu.batchRowsMinBucket": 8,
        "spark.rapids.tpu.shuffle.partitions": 2,
        "spark.rapids.tpu.shuffle.mode": "host",
    })
    rng = np.random.default_rng(5)
    df = sess.create_dataframe(pd.DataFrame({
        "g": rng.integers(0, 5, 300).astype(np.int64),
        "x": rng.normal(size=300)}), num_partitions=2)
    df.group_by("g").agg(f_sum(col("x")).alias("sx")).collect(device=True)
    sess.close()

    store = HistoryStore(store_dir)
    apps = store.apps()
    assert len(apps) == 1
    h = apps[0]
    assert h["n_queries"] == 1 and h["schema_version"] == 12
    app = store.load(h["app_id"])
    (q,) = app.queries.values()
    assert q.nodes  # plan replays
    assert q.shuffle_skew  # the host group-by shuffle emitted v7 records


def test_memory_gate_needs_relative_and_absolute_growth():
    """The sentinel's peak-memory gate: >10% AND >=1MiB. Tiny queries
    jitter past 10% run-to-run, so the relative gate alone would flag
    clean back-to-back runs."""
    from spark_rapids_tpu.tools.compare import (
        MEM_PEAK_FLAG_MIN_BYTES, memory_delta)
    # 20% growth but only bytes: noise, must not flag
    _, flagged = memory_delta({"peak_bytes": 20_000, "spill_bytes": 0},
                              {"peak_bytes": 24_000, "spill_bytes": 0})
    assert flagged == []
    # 20% growth and past the absolute floor: flags
    base = 100 * MEM_PEAK_FLAG_MIN_BYTES
    _, flagged = memory_delta({"peak_bytes": base, "spill_bytes": 0},
                              {"peak_bytes": int(base * 1.2),
                               "spill_bytes": 0})
    assert flagged == ["peak_bytes"]
    # big absolute delta but under 10% relative: must not flag either
    _, flagged = memory_delta({"peak_bytes": base, "spill_bytes": 0},
                              {"peak_bytes": int(base * 1.05),
                               "spill_bytes": 0})
    assert flagged == []
