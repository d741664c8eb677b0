"""Persistent cross-run history store + regression sentinel.

Reference: the Spark History Server plus the plugin's qualification and
profiling tools turn per-run event logs into cross-run, browsable
evidence (PAPER.md §1 tooling layer). Our per-run signals — event-log
schema v7 with critical paths, memory summaries and shuffle-skew
records, ``tools/compare.py``, ``tools/diagnose.py`` — evaporate when
the process exits; this module makes them durable:

- ``HistoryStore`` (``spark.rapids.tpu.history.dir``): one directory per
  application holding the event log (``eventlog.jsonl``), any bench or
  trace artifacts, an ``app.json`` headline record, and the sentinel's
  ``verdict.json``. A store-level ``index.json`` (per-query headline
  stats for every run) is DERIVED from the per-app records and replaced
  atomically (tmp + ``os.replace``), so concurrent writers — several
  sessions closing at once — can only ever race to publish a complete
  index, never tear one. Every ``TpuSession`` appends its run on close
  when the conf is set; ``tools/historyd.py`` serves the browsable UI
  over the same store.
- The **regression sentinel** (``python -m spark_rapids_tpu.tools.history
  sentinel --dir <store>``; exit 1 on regression) compares the candidate
  run (default: newest) against the pinned baseline (default: the run
  before it) using the existing compare.py gates — per-operator wall
  time, per-operator peak memory > 10 %, critical-path share > 5 pp —
  plus three gates of its own over the per-query counter deltas the
  event log already carries: **sync count** (``host_sync_d2h_count``,
  the deliberate-D2H funnel counter in columnar/device.py), **compile
  count** (``compile_cache_compiles``), — when the movement ledger
  is on — **D2H bytes** (``movement_d2h_bytes``, floor
  ``BYTES_FLAG_MIN``), and — when shuffle telemetry is on — **shuffle
  wall** (``shuffle_telemetry_wall_s``, floor
  ``SHUFFLE_WALL_FLAG_MIN_S``: time measurably spent inside shuffle
  transfer phases, which a fast machine can hide inside flat wall
  time). Any growing past ``COUNT_FLAG_FRAC`` (10 %,
  absolute floor ``COUNT_FLAG_MIN`` for counts) flags a regression
  wall-time comparison alone would miss: the run got slower
  *structurally* (more host round trips, wider downloads,
  compile-cache churn) even if this machine absorbed it. The verdict is
  written into the store next to the candidate's event log.

CLI::

    python -m spark_rapids_tpu.tools.history list --dir DIR
    python -m spark_rapids_tpu.tools.history append --dir DIR LOG [ART...]
    python -m spark_rapids_tpu.tools.history pin --dir DIR APP_ID
    python -m spark_rapids_tpu.tools.history sentinel --dir DIR \
        [--candidate APP] [--baseline APP] [--threshold 0.2]
"""
from __future__ import annotations

import json
import os
import shutil
import threading
import time
from typing import Dict, List, Optional, Sequence

from ..conf import register_conf

__all__ = ["HistoryStore", "run_sentinel", "HISTORY_DIR",
           "COUNT_FLAG_FRAC", "COUNT_FLAG_MIN", "SYNC_COUNT_KEY",
           "COMPILE_COUNT_KEY", "D2H_BYTES_KEY", "BYTES_FLAG_MIN",
           "SHUFFLE_WALL_KEY", "SHUFFLE_WALL_FLAG_MIN_S"]

HISTORY_DIR = register_conf(
    "spark.rapids.tpu.history.dir",
    "Root directory of the persistent query-history store (one directory "
    "per application: event log, artifacts, headline stats, sentinel "
    "verdict; plus an atomic store-level index.json). Empty disables the "
    "store. Every session appends its run on close; browse with "
    "tools/historyd.py, gate with 'python -m spark_rapids_tpu.tools."
    "history sentinel'. The Spark History Server log-dir analogue.", "")

HISTORY_BASELINE = register_conf(
    "spark.rapids.tpu.history.baseline",
    "Application id of the pinned regression-sentinel baseline in the "
    "history store. Empty uses the store's pinned baseline (the 'pin' "
    "subcommand) or, failing that, the run immediately before the "
    "candidate.", "")

#: relative growth of a sentinel-gated counter (sync count, compile
#: count) that flags a regression: 10%
COUNT_FLAG_FRAC = 0.10
#: absolute growth floor for the counter gates, so one extra sync on a
#: tiny run doesn't flap the sentinel
COUNT_FLAG_MIN = 2

#: per-query stats key for the sync-count gate (columnar/device.py
#: deliberate-D2H funnel counter, via the host_sync stats source)
SYNC_COUNT_KEY = "host_sync_d2h_count"
#: per-query stats key for the compile-count gate (XLA programs compiled
#: by the query, utils/compile_cache.py)
COMPILE_COUNT_KEY = "compile_cache_compiles"

#: per-query stats key for the D2H transfer-BYTES gate (movement-ledger
#: totals via the movement stats source, utils/movement.py). Where the
#: sync-count gate catches new host round trips, this one catches the
#: same number of syncs moving structurally more data — a widened
#: download that wall time on a fast link absorbs. Requires
#: spark.rapids.tpu.movement.enabled on both runs; absent stats gate
#: nothing.
D2H_BYTES_KEY = "movement_d2h_bytes"
#: absolute growth floor for the byte gate (1 MiB), so per-run row-count
#: jitter on small queries doesn't flap the sentinel
BYTES_FLAG_MIN = 1 << 20

#: per-query stats key for the shuffle-wall gate (shuffle-observatory
#: totals via the shuffle_telemetry stats source, shuffle/telemetry.py):
#: wall measurably spent inside transfer phases (serialize/publish/
#: fetch/deserialize/dispatch). Catches a shuffle tier getting slower
#: even when overlap keeps query wall flat. Requires
#: spark.rapids.tpu.shuffle.telemetry.enabled on both runs; absent
#: stats gate nothing.
SHUFFLE_WALL_KEY = "shuffle_telemetry_wall_s"
#: absolute growth floor for the shuffle-wall gate (50 ms), so
#: scheduler jitter on tiny transfers doesn't flap the sentinel
SHUFFLE_WALL_FLAG_MIN_S = 0.05

#: absolute growth floor for the aggregate total-wall gate (2 s): the
#: trajectory gate sums per-query walls across the run, so a
#: fleet-wide slowdown spread thinly over every query (each one under
#: the per-query threshold) still flags, while compile-cache jitter on
#: a single tiny query doesn't
TOTAL_WALL_FLAG_MIN_S = 2.0

_EVENTLOG_NAME = "eventlog.jsonl"
_APP_JSON = "app.json"
_VERDICT_JSON = "verdict.json"
_INDEX_JSON = "index.json"
_BASELINE_JSON = "baseline.json"
_ARTIFACT_DIR = "artifacts"


def _atomic_write_json(path: str, obj) -> None:
    """tmp + os.replace so readers never observe a torn file; the tmp
    name is writer-unique so concurrent writers can't clobber each
    other's half-written staging file."""
    tmp = f"{path}.tmp.{os.getpid()}.{threading.get_ident()}"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(obj, f, indent=1, sort_keys=True)
    os.replace(tmp, path)


def _read_json(path: str) -> Optional[Dict]:
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return None


class HistoryStore:
    """One directory per application + a derived, atomically-replaced
    store index. Safe for concurrent appenders: per-app records are
    written before the index rebuild, and every rebuild re-scans the
    app directories, so racing writers converge on a complete index
    (last replace wins; both candidates are supersets of what either
    writer alone knew)."""

    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        os.makedirs(self.root, exist_ok=True)

    # -- paths ----------------------------------------------------------------
    def app_dir(self, app_id: str) -> str:
        return os.path.join(self.root, app_id)

    def event_log_path(self, app_id: str) -> str:
        return os.path.join(self.app_dir(app_id), _EVENTLOG_NAME)

    def index_path(self) -> str:
        return os.path.join(self.root, _INDEX_JSON)

    # -- append ---------------------------------------------------------------
    def append_run(self, eventlog_path: str,
                   artifacts: Sequence[str] = (),
                   app_id: Optional[str] = None) -> str:
        """Ingest one finished event log (plus optional artifact files)
        as a new application directory and refresh the index. Returns
        the app id the run is stored under."""
        from .eventlog import load_event_log
        app = load_event_log(eventlog_path)
        app_id = app_id or app.app_id \
            or os.path.splitext(os.path.basename(eventlog_path))[0]
        d = self.app_dir(app_id)
        os.makedirs(d, exist_ok=True)
        shutil.copyfile(eventlog_path, os.path.join(d, _EVENTLOG_NAME))
        if artifacts:
            art_dir = os.path.join(d, _ARTIFACT_DIR)
            os.makedirs(art_dir, exist_ok=True)
            for src in artifacts:
                if os.path.isfile(src):
                    shutil.copyfile(
                        src, os.path.join(art_dir, os.path.basename(src)))
        headline = self._headline(app_id, app, eventlog_path)
        _atomic_write_json(os.path.join(d, _APP_JSON), headline)
        self.rebuild_index()
        return app_id

    @staticmethod
    def _headline(app_id: str, app, eventlog_path: str) -> Dict:
        """Per-query headline stats — everything the index/UI list view
        and the sentinel's trend sparkline need without replaying the
        full log."""
        queries: Dict[str, Dict] = {}
        ts = 0.0
        for q in app.queries.values():
            ts = ts or q.ts_start
            ms = q.memory_summary or {}
            skew = max((r.get("rows", {}).get("imbalance", 1.0)
                        for r in q.shuffle_skew), default=None)
            queries[str(q.query_id)] = {
                "wall_s": round(q.wall_s, 6),
                "error": q.error,
                "rows": sum(n.get("rows", 0) for n in q.nodes
                            if (n.get("parent_id") is None
                                or n["parent_id"] < 0)),
                "peak_bytes": int(ms.get("peak_bytes") or 0),
                "sync_count": int(q.stats.get(SYNC_COUNT_KEY, 0) or 0),
                "compile_count": int(
                    q.stats.get(COMPILE_COUNT_KEY, 0) or 0),
                "d2h_bytes": int(q.stats.get(D2H_BYTES_KEY, 0) or 0),
                "skew_imbalance": skew,
            }
        if not ts:
            try:
                ts = os.path.getmtime(eventlog_path)
            except OSError:
                ts = time.time()
        return {
            "app_id": app_id,
            "ts": ts,
            "schema_version": app.schema_version,
            "n_queries": len(app.queries),
            "n_errors": sum(1 for q in app.queries.values() if q.error),
            "total_wall_s": round(
                sum(q.wall_s for q in app.queries.values()), 6),
            "queries": queries,
        }

    # -- index ----------------------------------------------------------------
    def rebuild_index(self) -> Dict:
        """Re-derive index.json from the per-app records and replace it
        atomically. Returns the new index (app_id -> headline, verdict
        folded in when present)."""
        index: Dict[str, Dict] = {}
        try:
            entries = sorted(os.listdir(self.root))
        except OSError:
            entries = []
        for name in entries:
            headline = _read_json(os.path.join(self.root, name, _APP_JSON))
            if not headline:
                continue
            verdict = _read_json(
                os.path.join(self.root, name, _VERDICT_JSON))
            if verdict is not None:
                headline["verdict"] = {
                    "ok": verdict.get("ok"),
                    "baseline": verdict.get("baseline"),
                    "flags": verdict.get("flags", []),
                }
            index[name] = headline
        _atomic_write_json(self.index_path(), index)
        return index

    def index(self) -> Dict:
        idx = _read_json(self.index_path())
        return idx if idx is not None else self.rebuild_index()

    def apps(self) -> List[Dict]:
        """Headlines, oldest first (the trend/sparkline order)."""
        return sorted(self.index().values(),
                      key=lambda h: (h.get("ts", 0.0), h.get("app_id", "")))

    def load(self, app_id: str):
        """Full replay of one stored run (tools/eventlog.py AppReplay)."""
        from .eventlog import load_event_log
        return load_event_log(self.event_log_path(app_id))

    # -- baseline + verdict ---------------------------------------------------
    def pin_baseline(self, app_id: str) -> None:
        if not os.path.isdir(self.app_dir(app_id)):
            raise FileNotFoundError(f"no such run in the store: {app_id}")
        _atomic_write_json(os.path.join(self.root, _BASELINE_JSON),
                           {"app_id": app_id})

    def baseline_app_id(self) -> Optional[str]:
        rec = _read_json(os.path.join(self.root, _BASELINE_JSON))
        return rec.get("app_id") if rec else None

    def write_verdict(self, app_id: str, verdict: Dict) -> None:
        d = self.app_dir(app_id)
        os.makedirs(d, exist_ok=True)
        _atomic_write_json(os.path.join(d, _VERDICT_JSON), verdict)
        self.rebuild_index()

    def verdict(self, app_id: str) -> Optional[Dict]:
        return _read_json(os.path.join(self.app_dir(app_id),
                                       _VERDICT_JSON))

    def store_size_bytes(self) -> int:
        total = 0
        for dirpath, _dirnames, filenames in os.walk(self.root):
            for name in filenames:
                try:
                    total += os.path.getsize(os.path.join(dirpath, name))
                except OSError:
                    pass
        return total


# ---------------------------------------------------------------------------
# Regression sentinel
# ---------------------------------------------------------------------------
def _count_gate(report, key: str,
                flag_min: int = COUNT_FLAG_MIN) -> List[Dict]:
    """Queries whose per-query counter ``key`` grew past the sentinel's
    count gate (relative COUNT_FLAG_FRAC with absolute floor
    ``flag_min`` — COUNT_FLAG_MIN for sync/compile counts,
    BYTES_FLAG_MIN for the transfer-byte gate). Works off
    QueryDelta.metric_deltas, which compare.py already computes as
    candidate minus baseline."""
    flagged = []
    for q in report.queries:
        delta = q.metric_deltas.get(key)
        if not delta or delta <= 0:
            continue
        # reconstruct the baseline's absolute count: compare.py keeps
        # only the delta, so look it up through the ops-independent
        # stats the report retained; fall back to treating the delta as
        # 100% growth when the baseline count is unknown/zero
        base = getattr(q, "_stats_base", {}).get(key, 0)
        grew_enough = delta >= flag_min and (
            base <= 0 or delta > base * COUNT_FLAG_FRAC)
        if grew_enough:
            flagged.append({"query_id": q.query_id, "key": key,
                            "delta": delta, "baseline": base})
    return flagged


def run_sentinel(store: HistoryStore,
                 candidate: Optional[str] = None,
                 baseline: Optional[str] = None,
                 threshold: float = 0.2,
                 min_seconds: float = 0.001) -> Dict:
    """Compare the candidate run (default newest) against the baseline
    (explicit > pinned > previous run), write the verdict record into
    the store under the candidate, and return it. ``verdict["ok"]`` is
    False on any regression — wall time, critical-path share, peak
    memory, sync count, or compile count."""
    from .compare import compare_apps
    runs = store.apps()
    if not runs:
        raise FileNotFoundError(f"history store {store.root} has no runs")
    cand_id = candidate or runs[-1]["app_id"]
    base_id = baseline or store.baseline_app_id()
    if base_id is None:
        prior = [h["app_id"] for h in runs if h["app_id"] != cand_id
                 and h.get("ts", 0.0) <= next(
                     h2.get("ts", 0.0) for h2 in runs
                     if h2["app_id"] == cand_id)]
        base_id = prior[-1] if prior else None
    if base_id is None or base_id == cand_id:
        verdict = {"ok": True, "status": "no-baseline",
                   "candidate": cand_id, "baseline": None,
                   "ts": time.time(), "flags": [], "summary":
                   "no baseline run to compare against; recorded only"}
        store.write_verdict(cand_id, verdict)
        return verdict
    app_base = store.load(base_id)
    app_cand = store.load(cand_id)
    report = compare_apps(app_base, app_cand, threshold, min_seconds)
    # stash each query's BASELINE counters on the deltas so the count
    # gates can apply their relative threshold
    for q in report.queries:
        qb = app_base.queries.get(q.query_id)
        q._stats_base = dict(qb.stats) if qb is not None else {}
    # chaos-awareness (event-log v8): a candidate query that recovered
    # from INJECTED faults and still answered correctly pays its
    # recovery overhead on purpose — exempt it from every gate instead
    # of flagging the slowdown as a regression. Uninjected recovery
    # (fault records absent) still gates: that slowdown is real.
    # v9: same exemption for queries run on purpose under a
    # shrunken HBM pool — their oom_retry records (spills, retries,
    # splits) are deliberate pressure, not a regression.
    # v10: ditto for queries that recovered via host fallback — the
    # download/host-execute/upload round trips are the degradation
    # working as designed, not a device-path slowdown.
    chaos_ok = {q.query_id for q in app_cand.queries.values()
                if (getattr(q, "faults", None)
                    or getattr(q, "oom_retries", None)
                    or getattr(q, "fallbacks", None))
                and q.error is None}
    sync_flags = [f for f in _count_gate(report, SYNC_COUNT_KEY)
                  if f["query_id"] not in chaos_ok]
    compile_flags = [f for f in _count_gate(report, COMPILE_COUNT_KEY)
                     if f["query_id"] not in chaos_ok]
    # v11: movement-ledger D2H byte growth — same relative threshold as
    # the count gates, but floored at BYTES_FLAG_MIN so only material
    # transfer growth flags
    d2h_flags = [f for f in _count_gate(report, D2H_BYTES_KEY,
                                        BYTES_FLAG_MIN)
                 if f["query_id"] not in chaos_ok]
    # v12: shuffle-observatory transfer-wall growth — time spent inside
    # shuffle phases regressing past 10% and the 50ms floor flags even
    # when pipeline overlap keeps end-to-end wall flat
    shuffle_flags = [f for f in _count_gate(report, SHUFFLE_WALL_KEY,
                                            SHUFFLE_WALL_FLAG_MIN_S)
                     if f["query_id"] not in chaos_ok]
    # v13: aggregate total-wall gate (the multi-device trajectory number) —
    # per-query wall gates can miss a fleet-wide slowdown spread thinly
    # across the run; sum walls over the query ids present in BOTH runs
    # (chaos-exempt ones excluded, like every other gate) and flag
    # material aggregate growth past the relative threshold + 2s floor
    shared_q = [k for k in set(app_base.queries) & set(app_cand.queries)
                if k not in chaos_ok]
    base_total = sum(app_base.queries[k].wall_s for k in shared_q)
    cand_total = sum(app_cand.queries[k].wall_s for k in shared_q)
    total_wall = {"baseline_s": round(base_total, 4),
                  "candidate_s": round(cand_total, 4),
                  "n_queries": len(shared_q)} if shared_q else None
    total_wall_flagged = bool(
        shared_q
        and cand_total - base_total > TOTAL_WALL_FLAG_MIN_S
        and cand_total > base_total * (1.0 + threshold))
    wall_q = [q.query_id for q in report.regressed_queries()
              if q.query_id not in chaos_ok]
    wall_ops = [(op.query_id, op.name) for op in report.regressions()
                if op.query_id not in chaos_ok]
    cp_q = [q.query_id for q in report.critical_path_regressions()
            if q.query_id not in chaos_ok]
    mem_q = [q.query_id for q in report.memory_regressions()
             if q.query_id not in chaos_ok]
    flags: List[str] = []
    if wall_q or wall_ops:
        flags.append("wall_time")
    if cp_q:
        flags.append("critical_path")
    if mem_q:
        flags.append("memory")
    if sync_flags:
        flags.append("sync_count")
    if compile_flags:
        flags.append("compile_count")
    if d2h_flags:
        flags.append("d2h_bytes")
    if shuffle_flags:
        flags.append("shuffle_wall")
    if total_wall_flagged:
        flags.append("total_wall")
    verdict = {
        "ok": not flags,
        "status": "regressed" if flags else "clean",
        "candidate": cand_id,
        "baseline": base_id,
        "ts": time.time(),
        "threshold": threshold,
        "flags": flags,
        "wall_regressed_queries": wall_q,
        "wall_regressed_ops": [
            {"query_id": qid, "name": name} for qid, name in wall_ops],
        "critical_path_regressed_queries": cp_q,
        "memory_regressed_queries": mem_q,
        "sync_count_regressions": sync_flags,
        "compile_count_regressions": compile_flags,
        "d2h_bytes_regressions": d2h_flags,
        "shuffle_wall_regressions": shuffle_flags,
        "total_wall": total_wall,
        "chaos_recovered_queries": sorted(chaos_ok),
        "summary": report.summary(),
    }
    store.write_verdict(cand_id, verdict)
    return verdict


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------
def main(argv: Optional[List[str]] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        prog="python -m spark_rapids_tpu.tools.history",
        description="Query-history store: list runs, append event logs, "
                    "pin a baseline, run the regression sentinel")
    sub = ap.add_subparsers(dest="cmd")
    p_list = sub.add_parser("list", help="list stored runs")
    p_list.add_argument("--dir", required=True)
    p_append = sub.add_parser("append", help="ingest an event log")
    p_append.add_argument("--dir", required=True)
    p_append.add_argument("eventlog")
    p_append.add_argument("artifacts", nargs="*")
    p_pin = sub.add_parser("pin", help="pin the sentinel baseline run")
    p_pin.add_argument("--dir", required=True)
    p_pin.add_argument("app_id")
    p_sent = sub.add_parser(
        "sentinel",
        help="compare the newest (or --candidate) run against the "
             "baseline; exit 1 on regression")
    p_sent.add_argument("--dir", required=True)
    p_sent.add_argument("--candidate", default=None)
    p_sent.add_argument("--baseline", default=None)
    p_sent.add_argument("--threshold", type=float, default=0.2)
    p_sent.add_argument("--min-seconds", type=float, default=0.001)
    args = ap.parse_args(argv)
    if args.cmd is None:
        ap.print_help()
        return 2
    store = HistoryStore(args.dir)
    if args.cmd == "list":
        for h in store.apps():
            verdict = h.get("verdict") or {}
            mark = {True: "clean", False: "REGRESSED"}.get(
                verdict.get("ok"), "-")
            print(f"{h['app_id']:<40} queries={h['n_queries']:<3} "
                  f"wall={h['total_wall_s']:.4f}s errors={h['n_errors']} "
                  f"sentinel={mark}")
        return 0
    if args.cmd == "append":
        app_id = store.append_run(args.eventlog, args.artifacts)
        print(f"appended {app_id} -> {store.app_dir(app_id)}")
        return 0
    if args.cmd == "pin":
        store.pin_baseline(args.app_id)
        print(f"pinned baseline {args.app_id}")
        return 0
    # sentinel
    verdict = run_sentinel(store, args.candidate, args.baseline,
                           args.threshold, args.min_seconds)
    print(f"sentinel: candidate={verdict['candidate']} "
          f"baseline={verdict['baseline']} status={verdict['status']}"
          + (f" flags={','.join(verdict['flags'])}"
             if verdict["flags"] else ""))
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":  # pragma: no cover
    raise SystemExit(main())
