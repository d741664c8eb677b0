"""Event-log persistence + post-hoc replay.

Reference: the plugin tools replay *Spark event logs* into profiling and
qualification reports (tools/.../profiling/Profiler.scala:32,436 and
EventLogPathProcessor) — the whole point is analyzing a run after the fact.
This framework owns its runtime, so it writes its own event log: one JSONL
file per session (``spark.rapids.tpu.eventLog.dir``), one record per event:

- ``app_start``: conf snapshot
- ``query_start``: query id + plan tree
- ``node``: one per physical operator — name/desc/depth/parent, wall time,
  rows/batches, first/last activity offsets, operator metrics snapshot
  (schema v3: the snapshot carries the per-node byte/compile/spill
  attribution — upload/download bytes, shuffle bytes, xla cache hits and
  misses, compile seconds, spill bytes)
- ``kernel`` (schema v3): one per XLA program the query touched — plan
  signature, owning node, compile wall, HLO cost / memory analysis
  (utils/compile_cache.py kernel table)
- ``heartbeat`` (schema v4): periodic live-engine sample from the health
  monitor (utils/health.py) — HBM used/peak/limit, semaphore
  holders/waiters, pipeline queue depths + in-flight tasks, progress age
  and the watchdog's stalled verdict; written from the monitor thread
  (the writer is locked), so ``tools/diagnose.py`` can rank stall
  windows and flag queries that heartbeated into OOM territory
- ``query_end``: wall time, spill/semaphore deltas, AQE events, per-query
  process-counter deltas; schema v5 adds ``trace_id`` (the distributed
  TraceContext minted for the query, also on ``query_start``) and
  ``critical_path`` (the per-category wall-time attribution computed
  from this process's tracer spans — tools/trace.py)
- ``memory_summary`` (schema v6): one per query (success AND error
  paths) — the memory flight recorder's per-operator peak/live HBM
  aggregation, peak-holder attribution and retained-buffer leak scan
  (utils/memprof.py ``query_end``); ``summary`` is null when profiling
  is off. v6 also adds ``peak_device_bytes`` to ``node`` records.
- ``oom_postmortem`` (schema v6): one per OOM the catalog hit during the
  query — context, ranked holders-by-operator, live/peak bytes and the
  path of the full ``oom-<ts>.txt`` report (the record omits the report
  text; the file carries it)
- ``shuffle_skew`` (schema v7): one per exchange node that materialized
  during the query — the per-output-partition row/byte distribution
  (min/p50/max/mean, imbalance ratio = max/mean, per-partition row
  counts) computed from counts the exchange tiers already gather in
  bulk; the partition-level telemetry ROADMAP items 3–4 consume and
  the history server's regression sentinel watches
- ``fault`` (schema v8): one per injected-fault fire drained from the
  fault-injection framework (utils/faults.py) — point, action and the
  per-point fire/evaluation ordinals; absent entirely when injection is
  off (the common case)
- ``recovery`` (schema v8): ONE per query (success AND error paths) —
  the per-query delta of the recovery ledger (worker deaths/respawns,
  task resubmissions, transport retries, shuffle recomputes, spill
  corruptions...); the ``recovery`` payload is null when the query saw
  no recovery activity, so the record set per query is stable whether
  or not faults fired
- ``movement_summary`` (schema v11): ONE per query (success AND error
  paths) — the data-movement ledger's per-query aggregation
  (utils/movement.py): total D2H/H2D bytes and counts, blocking vs.
  deferred syncs, detected round trips (downloaded then re-uploaded
  within the query), and the per-(site, operator) breakdown keyed by the
  same funnel names srtpu-analyze's sync baseline tracks; the
  ``movement`` payload is null when the ledger is off (the default), so
  the per-query record set is stable either way
- ``app_end``

``load_event_log`` replays a file into ``AppReplay``: per-query summaries,
aggregated operator hot list, HealthCheck warnings, a timeline SVG, and a
plan DOT graph — the Profiler.scala report set, rebuilt from our log.
``tools/diagnose.py`` consumes the same replay for the ranked bottleneck
report (the AutoTuner analogue).
"""
from __future__ import annotations

import json
import os
import threading
import time
from typing import Dict, List, Optional

from ..conf import register_conf

__all__ = ["EventLogWriter", "load_event_log", "AppReplay", "QueryReplay",
           "EVENT_LOG_DIR", "SCHEMA_VERSION", "RECORD_TYPES"]

# Event-record schema version. Bump ONLY with a migration note in
# docs/observability.md; tests/test_observability.py pins the current value
# and the per-record required-key sets so replay/compare tooling can rely
# on old logs staying loadable. v12: shuffle_summary records — ONE per
# query, the shuffle observatory's per-query aggregation of every
# transfer on every shuffle tier (shuffle/telemetry.py): per-tier and
# per-(shuffle, tier) bytes/wall/phase breakdowns, stitched TCP
# sender/receiver counts and straggler attribution (slowest-partition
# wall vs p50); null payload when the observatory is off.
# (v11 added movement_summary records — ONE per
# query, the data-movement ledger's per-query aggregation of every
# host<->device crossing (utils/movement.py): per-site and per-operator
# bytes/wall/counts plus round-trip detections; null payload when the
# ledger is off; v10 added fallback records — one per batch a
# device operator re-executed through the host engine after a terminal
# device failure (exec/fallback.py): operator + failure class + bytes
# moved each way + host wall time; v9 added oom_retry records — one per
# retry scope that engaged the device-OOM escalation ladder
# (memory/retry.py): spill → retry → split-and-retry, with the
# attempt/split/spilled-bytes counts and the recovered/failed outcome;
# v8 added fault/recovery records — per-fire injection telemetry plus an
# always-written per-query recovery-ledger delta; v7 added shuffle_skew
# records; v6 added memory_summary/oom_postmortem records and
# peak_device_bytes on node records.)
SCHEMA_VERSION = 12

# The event-record schema registry: every record type a writer may emit,
# mapped to the schema version that introduced it. srtpu-analyze's
# ``eventlog`` checker statically verifies that each
# ``write({"event": ...})`` call site across the package names a
# registered type, and that no registered type claims a version above
# SCHEMA_VERSION — adding a record type without bumping the version (and
# the docs/observability.md migration note) is flagged at analyze time.
RECORD_TYPES: Dict[str, int] = {
    "app_start": 1,
    "query_start": 1,
    "node": 1,
    "query_end": 1,
    "app_end": 1,
    "kernel": 3,
    "heartbeat": 4,
    "memory_summary": 6,
    "oom_postmortem": 6,
    "shuffle_skew": 7,
    "fault": 8,
    "recovery": 8,
    "oom_retry": 9,
    "fallback": 10,
    "movement_summary": 11,
    "shuffle_summary": 12,
}

#: health_check flags a query whose critical-path ``sync_wait`` fraction
#: exceeds this (v11) — past it, host<->device synchronization is the
#: dominant cost and the movement ledger's site ranking is the worklist
SYNC_WAIT_WARN_FRAC = 0.4

#: health_check flags a shuffle straggler when the slowest partition's
#: measured transfer wall exceeds the p50 by this factor (v12) AND the
#: absolute wall clears ``SHUFFLE_STRAGGLER_WARN_WALL_S`` — tiny queries
#: have noisy ratios, so both gates must fire
SHUFFLE_STRAGGLER_WARN_SKEW = 4.0
SHUFFLE_STRAGGLER_WARN_WALL_S = 0.05

EVENT_LOG_DIR = register_conf(
    "spark.rapids.tpu.eventLog.dir",
    "Directory for the session event log (JSONL; one file per session). "
    "Empty disables logging. Spark's spark.eventLog.dir analogue — feeds "
    "the replay tools (tools/eventlog.py load_event_log and "
    "tools/compare.py).", "")


class EventLogWriter:
    """Append-only JSONL writer; one per session."""

    def __init__(self, directory: str, app_id: str, conf_snapshot: Dict):
        os.makedirs(directory, exist_ok=True)
        self.path = os.path.join(directory, f"{app_id}.jsonl")
        self._f = open(self.path, "a", encoding="utf-8")
        self._query_seq = 0
        #: qid -> query_end record awaiting its root span's close (end_query)
        self._pending_end: Dict[int, Dict] = {}
        # v4: the health monitor thread appends heartbeats while the query
        # thread writes node/query records — serialize whole lines
        self._lock = threading.Lock()
        self.write({"event": "app_start", "app_id": app_id,
                    "schema_version": SCHEMA_VERSION,
                    "ts": time.time(), "conf": conf_snapshot})

    def write(self, record: Dict) -> None:
        line = json.dumps(record) + "\n"
        with self._lock:
            self._f.write(line)
            self._f.flush()

    def write_heartbeat(self, record: Dict) -> None:
        """One schema-v4 heartbeat record (utils/health.py supplies the
        flat sample dict; event type + wall-clock stamp added here)."""
        self.write({"event": "heartbeat", "ts": time.time(), **record})  # srtpu: eventlog-ok(health.py sample dicts are flat metric counters and never carry an event key)

    def next_query_id(self) -> int:
        self._query_seq += 1
        return self._query_seq

    def begin_query(self):
        """``(query id, TraceContext)`` of the next query. v5: one
        TraceContext per query — the identity every process boundary
        (ProcessCluster envelope, shuffle wire header) carries so worker
        spans merge under this query's timeline. The caller opens the
        root span with both (``get_tracer().query(tctx, query_id=qid)``)."""
        from ..utils.tracing import mint_trace_context
        qid = self.next_query_id()
        return qid, mint_trace_context(query_id=qid)

    def run_query(self, plan, collect_fn):
        """Instrument ``plan``, run ``collect_fn()`` under a root
        ``query`` span of its own, persist the events — for a plan driven
        without ``DataFrame.collect`` (which opens the root itself, around
        planning too, and calls the two halves)."""
        from ..utils.tracing import get_tracer
        qid, tctx = self.begin_query()
        try:
            with get_tracer().query(tctx, query_id=qid):
                return self.log_query(plan, collect_fn, qid, tctx)
        finally:
            self.end_query(qid, tctx)

    def end_query(self, qid: int, tctx) -> None:
        """Write the ``query_end`` record ``log_query`` prepared — called
        once the root ``query`` span has closed, because the record's
        critical path is computed from the tracer's ring and the root
        lands there on exit. No-op after a failed query (its record is
        written where it failed)."""
        record = self._pending_end.pop(qid, None)
        if record is not None:
            record["critical_path"] = _query_critical_path(tctx.trace_id)
            self.write(record)

    def log_query(self, plan, collect_fn, qid: int, tctx):
        """Inside the root span: instrument ``plan``, run
        ``collect_fn()``, persist everything but ``query_end``."""
        from ..memory.catalog import get_catalog
        from ..memory.semaphore import get_semaphore
        from ..utils.compile_cache import kernel_seq, kernels_since
        from ..utils.metrics import StatsRegistry, get_stats
        from .profiler import instrument_plan

        epoch = time.perf_counter()
        stats: List = []
        from ..plan.aqe import AdaptiveExec
        if isinstance(plan, AdaptiveExec):
            # AQE finalizes lazily: each stage segment + the final segment
            # get instrumented as the adaptive loop creates them
            plan._instrument_hook = \
                lambda p: instrument_plan(p, epoch, into=stats,
                                          query_id=qid)
        else:
            instrument_plan(plan, epoch, into=stats, query_id=qid)
        cat = get_catalog()
        sem = get_semaphore()
        registry = get_stats()
        spill_before = dict(cat.spill_count)
        wait_before = sem.total_wait_time
        counters_before = registry.collect()
        kseq_before = kernel_seq()
        from ..utils import faults
        recovery_before = faults.recovery_counters()
        self.write({"event": "query_start", "query_id": qid,
                    "ts": time.time(), "trace_id": tctx.trace_id,
                    "plan": plan.tree_string()})
        t0 = time.perf_counter()
        try:
            result = collect_fn()
        except Exception as e:
            # v6: the OOM that killed the query (if any) queued a
            # postmortem in the flight recorder — persist it, and the leak
            # scan, before the error record propagates
            self._write_memory_records(qid)
            # v8: whatever recovery the runtime managed BEFORE giving up
            # (retries, recomputes, respawns) is exactly the forensics a
            # failed query needs — write it on the error path too
            self._write_fault_records(qid, recovery_before)
            # v9: ditto for the OOM-retry ladder — the scopes that
            # retried/split before the query died are the postmortem trail
            self._write_oom_retry_records(qid)
            # v10: host fallbacks completed before the query died anyway
            self._write_fallback_records(qid)
            # v11: whatever the query moved across the PCI boundary before
            # failing is exactly where a timeout/OOM forensics starts
            self._write_movement_records(qid)
            # v12: ditto for shuffle transfers — a query that died mid
            # exchange leaves the straggler/backpressure trail here
            self._write_shuffle_records(qid)
            self.write({"event": "query_end", "query_id": qid,
                        "ts": time.time(), "trace_id": tctx.trace_id,
                        "wall_s": time.perf_counter() - t0,
                        "error": f"{type(e).__name__}: {e}"})
            raise
        wall = time.perf_counter() - t0
        # close plan-owned spill handles (shuffle/broadcast outputs)
        # BEFORE the leak scan in _write_memory_records below: the plan is
        # single-use and its outputs release at query end by design — only
        # what remains after this is a real leak
        plan.release_spill_handles()
        # v6: per-node peak HBM from the flight recorder (keys match the
        # node ids instrument_plan assigned; {} when profiling is off)
        from ..utils.memprof import active as memprof_active
        mp = memprof_active()
        node_peaks = mp.node_peaks(qid) if mp is not None else {}
        for ns in stats:
            self.write({"event": "node", "query_id": qid,
                        "node_id": ns.node_id, "parent_id": ns.parent_id,
                        "name": ns.name, "desc": ns.desc, "depth": ns.depth,
                        "wall_s": ns.wall_s, "rows": ns.rows,
                        "batches": ns.batches, "t_first": ns.t_first,
                        "t_last": ns.t_last,
                        "peak_device_bytes": node_peaks.get(ns.node_id, 0),
                        "metrics": _node_metrics(ns)})
        # schema v7: per-exchange output-partition row/byte distribution.
        # Exchange nodes (both tiers + the host fallback) accumulate the
        # per-partition counts they already gather during materialize and
        # expose them via shuffle_skew(); one record per exchange that
        # actually materialized in this query.
        for ns in stats:
            skew = _node_shuffle_skew(ns)
            if skew is not None:
                self.write({**skew, "event": "shuffle_skew",
                            "query_id": qid, "node_id": ns.node_id,
                            "name": ns.name})
        # schema v3: one kernel record per XLA program this query touched
        # (compile wall + cost/memory analysis keyed back to node ids)
        for entry in kernels_since(kseq_before):
            entry.pop("last_touch", None)
            # the record's query_id is THIS query (the entry's own
            # query_id field records where the program first compiled)
            self.write({**entry, "event": "kernel", "query_id": qid,
                        "first_query_id": entry.get("query_id")})
        self._write_memory_records(qid)
        self._write_fault_records(qid, recovery_before)
        self._write_oom_retry_records(qid)
        self._write_fallback_records(qid)
        self._write_movement_records(qid)
        self._write_shuffle_records(qid)
        aqe_events: List[str] = list(getattr(plan, "events", []))
        self._pending_end[qid] = {
            "event": "query_end", "query_id": qid, "ts": time.time(),
            "trace_id": tctx.trace_id,
            "wall_s": wall, "final_plan": plan.tree_string(),
            "aqe_events": aqe_events,
            "spill_count": {str(k): v - spill_before.get(k, 0)
                            for k, v in cat.spill_count.items()},
            "semaphore_wait_s": sem.total_wait_time - wait_before,
            # per-query deltas of every process-wide counter: compile cache,
            # upload cache, shuffle tiers, catalog spills/OOM, semaphore —
            # the attribution BENCH needs (VERDICT layer-11 gap)
            "stats": StatsRegistry.delta(registry.collect(),
                                         counters_before),
        }
        return result

    def _write_memory_records(self, qid: int) -> None:
        """v6: drain queued oom_postmortem records, then run the flight
        recorder's query-end leak scan and write ONE memory_summary
        (``summary`` is null when profiling is off, so the record set per
        query is stable either way)."""
        from ..utils.memprof import active as memprof_active
        mp = memprof_active()
        summary = None
        if mp is not None:
            for pm in mp.drain_postmortems():
                rec = {k: v for k, v in pm.items() if k != "report"}
                self.write({**rec, "event": "oom_postmortem",
                            "query_id": qid})
            summary = mp.query_end(qid)
        self.write({"event": "memory_summary", "query_id": qid,
                    "ts": time.time(), "summary": summary})

    def _write_fault_records(self, qid: int,
                             before: Dict[str, int]) -> None:
        """v8: drain the injector's fire records (one ``fault`` record
        each; none when injection is off — the common case) and write
        ONE ``recovery`` record whose payload is the per-query delta of
        the recovery ledger. ``recovery`` is null when the query saw no
        recovery activity, so the per-query record set is identical
        whether or not faults are enabled."""
        from ..utils import faults
        for fr in faults.drain_fault_records():
            self.write({**fr, "event": "fault", "query_id": qid,
                        "ts": time.time()})
        after = faults.recovery_counters()
        delta = {k: after.get(k, 0) - before.get(k, 0)
                 for k in after if after.get(k, 0) != before.get(k, 0)}
        self.write({"event": "recovery", "query_id": qid,
                    "ts": time.time(), "recovery": delta or None})

    def _write_oom_retry_records(self, qid: int) -> None:
        """v9: drain the OOM-retry ladder's per-scope records (one
        ``oom_retry`` record per retry scope that saw at least one retry
        or split; none in the common no-pressure case)."""
        from ..memory.retry import drain_oom_retry_records
        for rr in drain_oom_retry_records():
            self.write({**rr, "event": "oom_retry", "query_id": qid})

    def _write_movement_records(self, qid: int) -> None:
        """v11: write ONE ``movement_summary`` record — the data-movement
        ledger's per-query aggregation of every host<->device crossing
        (utils/movement.py). ``movement`` is null when the ledger is off
        (the default), so the per-query record set is stable either way."""
        from ..utils import movement
        self.write({"event": "movement_summary", "query_id": qid,
                    "ts": time.time(),
                    "movement": movement.query_summary(qid)})

    def _write_shuffle_records(self, qid: int) -> None:
        """v12: write ONE ``shuffle_summary`` record — the shuffle
        observatory's per-query aggregation of every transfer on every
        shuffle tier (shuffle/telemetry.py), with straggler attribution.
        ``shuffle`` is null when the observatory is off (the default),
        so the per-query record set is stable either way."""
        from ..shuffle import telemetry
        self.write({"event": "shuffle_summary", "query_id": qid,
                    "ts": time.time(),
                    "shuffle": telemetry.query_summary(qid)})

    def _write_fallback_records(self, qid: int) -> None:
        """v10: drain the degradation layer's completed-fallback records
        (one ``fallback`` record per batch re-executed through the host
        engine; none in the healthy-device common case)."""
        from ..exec.fallback import drain_fallback_records
        for fr in drain_fallback_records():
            self.write({**fr, "event": "fallback", "query_id": qid})

    def close(self) -> None:
        self.write({"event": "app_end", "ts": time.time()})
        self._f.close()


def _query_critical_path(trace_id: str) -> Optional[Dict]:
    """The per-category wall-time breakdown of the query just run,
    computed from THIS process's tracer spans (v5 query_end payload).
    None when tracing is off or the query span was dropped from the
    ring — never raises (trace math must not fail a query)."""
    from ..utils.tracing import get_tracer
    tracer = get_tracer()
    if not tracer.enabled:
        return None
    try:
        from .trace import critical_path_from_tracer
        cp = critical_path_from_tracer(tracer, trace_id)
        return None if cp is None else cp.to_dict()
    except Exception:  # pragma: no cover — defensive
        return None


def _node_metrics(ns) -> Dict:
    """Snapshot the live node's operator metrics (TpuExec registries) —
    the same rule QueryProfile uses (tools/profiler.py)."""
    from .profiler import registry_snapshot
    return registry_snapshot(getattr(ns, "_node", None))


def _node_shuffle_skew(ns) -> Optional[Dict]:
    """The live node's accumulated per-partition distribution (v7), or
    None for non-exchange nodes / exchanges that never materialized.
    Never raises — skew telemetry must not fail a query."""
    node = getattr(ns, "_node", None)
    fn = getattr(node, "shuffle_skew", None)
    if fn is None:
        return None
    try:
        return fn()
    except Exception:  # pragma: no cover — defensive
        return None


# ---------------------------------------------------------------------------
# Replay
# ---------------------------------------------------------------------------
class QueryReplay:
    def __init__(self, qid: int):
        self.query_id = qid
        self.plan: str = ""
        self.final_plan: str = ""
        self.wall_s: float = 0.0
        self.error: Optional[str] = None
        self.nodes: List[Dict] = []
        self.kernels: List[Dict] = []  # v3: per-XLA-program records
        self.aqe_events: List[str] = []
        self.spill_count: Dict = {}
        self.semaphore_wait_s: float = 0.0
        self.stats: Dict = {}  # per-query process-counter deltas
        # v4: wall-clock window (query_start.ts .. query_end.ts) so
        # app-level heartbeats can be attributed to the running query
        self.ts_start: float = 0.0
        self.ts_end: float = 0.0
        # v5: distributed-trace identity + critical-path attribution
        self.trace_id: str = ""
        self.critical_path: Optional[Dict] = None
        # v6: memory flight recorder — per-operator HBM attribution +
        # leak scan (None for pre-v6 logs or profiling off), and any OOM
        # postmortems the query hit
        self.memory_summary: Optional[Dict] = None
        self.oom_postmortems: List[Dict] = []
        # v7: per-exchange output-partition row/byte distribution records
        # (empty for pre-v7 logs or queries with no materialized exchange)
        self.shuffle_skew: List[Dict] = []
        # v8: fault-injection + recovery telemetry — ``recovery`` is the
        # per-query recovery-ledger delta (None for pre-v8 logs AND for
        # queries that needed no recovery), ``faults`` the injected-fire
        # records (empty when injection is off)
        self.recovery: Optional[Dict] = None
        self.faults: List[Dict] = []
        # v9: device-OOM retry-ladder records — one per retry scope that
        # retried or split (empty for pre-v9 logs and unpressured queries)
        self.oom_retries: List[Dict] = []
        # v10: host-fallback records — one per batch re-executed through
        # the host engine (empty for pre-v10 logs and healthy devices)
        self.fallbacks: List[Dict] = []
        # v11: data-movement ledger aggregation — per-site/per-operator
        # host<->device bytes, wall, blocking counts and round trips
        # (None for pre-v11 logs AND when the ledger is off)
        self.movement_summary: Optional[Dict] = None
        # v12: shuffle observatory aggregation — per-tier/per-shuffle
        # transfer bytes, walls, retries and straggler attribution
        # (None for pre-v12 logs AND when shuffle telemetry is off)
        self.shuffle_summary: Optional[Dict] = None

    def heartbeats_in_window(self, heartbeats: List[Dict]) -> List[Dict]:
        """App heartbeats whose timestamp falls inside this query's run
        (v4; empty for pre-v4 logs — ts_start is 0)."""
        if not self.ts_start:
            return []
        end = self.ts_end or float("inf")
        return [h for h in heartbeats
                if self.ts_start <= h.get("ts", 0.0) <= end]

    def summary(self) -> str:
        lines = [f"query {self.query_id}: wall={self.wall_s:.4f}s"
                 + (f" ERROR {self.error}" if self.error else ""),
                 f"{'op':<44}{'time_s':>9}{'rows':>12}{'batches':>9}"]
        for n in self.nodes:
            label = ("  " * n["depth"] + n["name"])[:43]
            lines.append(f"{label:<44}{n['wall_s']:>9.4f}{n['rows']:>12}"
                         f"{n['batches']:>9}")
        if self.aqe_events:
            lines.append("aqe: " + "; ".join(self.aqe_events))
        return "\n".join(lines)

    def timeline_svg(self) -> str:
        """One bar per operator from first to last activity — the
        reference profiler's generateTimeline analogue."""
        nodes = [n for n in self.nodes if n["batches"] > 0]
        if not nodes:
            return "<svg xmlns='http://www.w3.org/2000/svg'/>"
        t_max = max(max(n["t_last"] for n in nodes), self.wall_s, 1e-9)
        row_h, label_w, width = 22, 260, 900
        height = row_h * (len(nodes) + 1) + 10
        scale = (width - label_w - 20) / t_max
        parts = [f"<svg xmlns='http://www.w3.org/2000/svg' "
                 f"width='{width}' height='{height}' "
                 f"font-family='monospace' font-size='11'>"]
        for i, n in enumerate(sorted(nodes, key=lambda x: x["t_first"])):
            y = 5 + i * row_h
            x0 = label_w + n["t_first"] * scale
            w = max(1.0, (n["t_last"] - n["t_first"]) * scale)
            label = ("  " * n["depth"] + n["name"])[:38]
            parts.append(f"<text x='4' y='{y + 14}'>{label}</text>")
            parts.append(
                f"<rect x='{x0:.1f}' y='{y + 3}' width='{w:.1f}' "
                f"height='{row_h - 8}' fill='#4C78A8'>"
                f"<title>{n['name']}: {n['wall_s']:.4f}s, "
                f"{n['rows']} rows</title></rect>")
        axis_y = 5 + len(nodes) * row_h + 12
        parts.append(f"<text x='{label_w}' y='{axis_y}'>0s</text>")
        parts.append(f"<text x='{width - 60}' y='{axis_y}'>"
                     f"{t_max:.3f}s</text>")
        parts.append("</svg>")
        return "".join(parts)

    def to_dot(self) -> str:
        """Graphviz DOT of the executed plan with per-node metrics
        (reference: GenerateDot.scala)."""
        lines = ["digraph plan {", "  node [shape=box fontname=monospace];"]
        for n in self.nodes:
            label = (f"{n['name']}\\n{n['desc'][:40]}\\n"
                     f"{n['wall_s']:.4f}s  {n['rows']} rows")
            lines.append(f"  n{n['node_id']} [label=\"{label}\"];")
        for n in self.nodes:
            if n["parent_id"] >= 0:
                lines.append(f"  n{n['node_id']} -> n{n['parent_id']};")
        lines.append("}")
        return "\n".join(lines)


class AppReplay:
    def __init__(self, path: str):
        self.path = path
        self.app_id: str = ""
        self.schema_version: int = 1  # logs predating the field
        self.conf: Dict = {}
        self.queries: Dict[int, QueryReplay] = {}
        self.heartbeats: List[Dict] = []  # v4: app-level monitor samples

    def query(self, qid: int) -> QueryReplay:
        return self.queries[qid]

    def summary(self) -> str:
        lines = [f"app {self.app_id}: {len(self.queries)} queries"]
        for q in self.queries.values():
            lines.append(f"  q{q.query_id}: {q.wall_s:.4f}s"
                         + (" ERROR" if q.error else ""))
        hot: Dict[str, float] = {}
        for q in self.queries.values():
            for n in q.nodes:
                hot[n["name"]] = hot.get(n["name"], 0.0) + n["wall_s"]
        lines.append("hottest operators:")
        for name, t in sorted(hot.items(), key=lambda kv: -kv[1])[:10]:
            lines.append(f"  {name:<40}{t:>9.4f}s")
        return "\n".join(lines)

    def health_check(self) -> List[str]:
        warnings = []
        for q in self.queries.values():
            if q.error:
                warnings.append(f"q{q.query_id} failed: {q.error}")
            if any(q.spill_count.values()):
                warnings.append(
                    f"q{q.query_id}: device memory pressure "
                    f"(spills {q.spill_count})")
            if q.wall_s > 0 and q.semaphore_wait_s > 0.25 * q.wall_s:
                warnings.append(
                    f"q{q.query_id}: semaphore wait is "
                    f"{q.semaphore_wait_s / q.wall_s:.0%} of wall time")
            compile_s = q.stats.get("compile_cache_compile_seconds", 0.0)
            if q.wall_s > 0 and compile_s > 0.5 * q.wall_s:
                warnings.append(
                    f"q{q.query_id}: XLA compile is "
                    f"{compile_s / q.wall_s:.0%} of wall time — cold compile "
                    "cache (warm up or enable the persistent cache)")
            if q.stats.get("catalog_oom_callback_errors", 0):
                warnings.append(
                    f"q{q.query_id}: OOM cache-drop callbacks raised "
                    "(see catalog diagnostics)")
            ms = q.memory_summary or {}
            if ms.get("leaked_bytes"):
                warnings.append(
                    f"q{q.query_id}: {len(ms.get('leaked_buffers', []))} "
                    f"buffer(s) still registered after query end "
                    f"({ms['leaked_bytes']} bytes leaked — top holder: "
                    f"{ms['leaked_buffers'][0]['operator']})")
            for pm in q.oom_postmortems:
                warnings.append(
                    f"q{q.query_id}: OOM postmortem — {pm.get('context')}"
                    + (f" (report: {pm['path']})" if pm.get("path")
                       else ""))
            if q.recovery:
                detail = ", ".join(f"{k}={v}"
                                   for k, v in sorted(q.recovery.items()))
                warnings.append(
                    f"q{q.query_id}: recovered from failures ({detail})"
                    + (" — faults were injected" if q.faults else ""))
            # v9: a scope that had to split repeatedly is running batches
            # far above what HBM can hold — a split storm
            storm = [r for r in q.oom_retries if r.get("splits", 0) >= 2]
            if storm:
                worst = max(storm, key=lambda r: r.get("splits", 0))
                warnings.append(
                    f"q{q.query_id}: OOM split storm — scope "
                    f"'{worst.get('scope')}' split {worst['splits']}x "
                    "(lower spark.rapids.sql.batchSizeBytes so batches "
                    "fit HBM without retry-time splitting)")
            # v10: batches that had to re-execute on the host engine —
            # correct results, but the device path is failing for that
            # operator and each batch pays a download/upload round trip
            if q.fallbacks:
                ops = sorted({f.get("operator", "?") for f in q.fallbacks})
                down = sum(f.get("bytes_down", 0) for f in q.fallbacks)
                warnings.append(
                    f"q{q.query_id}: {len(q.fallbacks)} batch(es) fell "
                    f"back to the host engine ({', '.join(ops)}; "
                    f"{down} bytes downloaded) — repeated failures "
                    "quarantine the operator to host at plan time")
            # v11: the query spent most of its wall blocked on host<->
            # device synchronization — the data-movement observatory's
            # per-site ranking says which funnel to make non-blocking
            cp = q.critical_path or {}
            sync_frac = cp.get("sync_wait_frac", 0.0) or 0.0
            if sync_frac > SYNC_WAIT_WARN_FRAC:
                msg = (f"q{q.query_id}: sync wait is {sync_frac:.0%} of "
                       "wall time — host<->device crossings dominate")
                mv = q.movement_summary or {}
                sites = mv.get("sites") or []
                if sites:
                    top = sites[0]
                    msg += (f" (heaviest site: {top.get('site')} — "
                            f"{top.get('bytes', 0)} bytes, "
                            f"{top.get('count', 0)} crossings)")
                else:
                    msg += (" (enable spark.rapids.tpu.movement.enabled "
                            "for per-site attribution)")
                warnings.append(msg)
            mvt = (q.movement_summary or {}).get("totals") or {}
            if mvt.get("round_trips"):
                warnings.append(
                    f"q{q.query_id}: {mvt['round_trips']} batch(es) made a "
                    "host round trip (downloaded then re-uploaded within "
                    "the query) — keep them device-resident or cache the "
                    "shuffle on device")
            # v12: shuffle observatory — measured per-partition transfer
            # walls expose stragglers that row-count skew records can't
            # (a balanced partition on a slow link still stalls the stage)
            sh = q.shuffle_summary or {}
            st = sh.get("straggler") or {}
            if ((st.get("skew") or 0.0) >= SHUFFLE_STRAGGLER_WARN_SKEW
                    and (st.get("slowest_wall_s") or 0.0)
                    >= SHUFFLE_STRAGGLER_WARN_WALL_S):
                worst = st.get("worst") or {}
                warnings.append(
                    f"q{q.query_id}: shuffle straggler — slowest partition "
                    f"wall {st['slowest_wall_s']:.3f}s vs p50 "
                    f"{st['p50_wall_s']:.3f}s ({st['skew']:.1f}x; shuffle "
                    f"{worst.get('shuffle_id')} partition "
                    f"{worst.get('partition')} on the {worst.get('tier')} "
                    "tier) — repartition or salt the hot keys")
            sht = sh.get("totals") or {}
            if sht.get("retries"):
                warnings.append(
                    f"q{q.query_id}: {sht['retries']} shuffle transfer "
                    "retrie(s) — peers answered late or died; check "
                    "transport-tier backpressure (max publish-queue depth "
                    f"{sht.get('max_queue_depth', 0)})")
        stalled = [h for h in self.heartbeats if h.get("stalled")]
        if stalled:
            age = max(h.get("last_progress_age_s", 0.0) for h in stalled)
            warnings.append(
                f"watchdog: {len(stalled)} heartbeat(s) reported a stalled "
                f"engine (max no-progress age {age:.1f}s) — see the "
                "stall-<ts>.txt forensics reports")
        return warnings


def load_event_log(path: str) -> AppReplay:
    app = AppReplay(path)
    with open(path, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            rec = json.loads(line)
            ev = rec.get("event")
            if ev == "app_start":
                app.app_id = rec.get("app_id", "")
                app.schema_version = rec.get("schema_version", 1)
                app.conf = rec.get("conf", {})
            elif ev == "query_start":
                q = app.queries.setdefault(rec["query_id"],
                                           QueryReplay(rec["query_id"]))
                q.plan = rec.get("plan", "")
                q.ts_start = rec.get("ts", 0.0)
                q.trace_id = rec.get("trace_id", "")
            elif ev == "heartbeat":
                app.heartbeats.append(rec)
            elif ev == "node":
                q = app.queries.setdefault(rec["query_id"],
                                           QueryReplay(rec["query_id"]))
                q.nodes.append(rec)
            elif ev == "kernel":
                q = app.queries.setdefault(rec["query_id"],
                                           QueryReplay(rec["query_id"]))
                q.kernels.append(rec)
            elif ev == "memory_summary":
                q = app.queries.setdefault(rec["query_id"],
                                           QueryReplay(rec["query_id"]))
                q.memory_summary = rec.get("summary")
            elif ev == "oom_postmortem":
                q = app.queries.setdefault(rec["query_id"],
                                           QueryReplay(rec["query_id"]))
                q.oom_postmortems.append(rec)
            elif ev == "shuffle_skew":
                q = app.queries.setdefault(rec["query_id"],
                                           QueryReplay(rec["query_id"]))
                q.shuffle_skew.append(rec)
            elif ev == "fault":
                q = app.queries.setdefault(rec["query_id"],
                                           QueryReplay(rec["query_id"]))
                q.faults.append(rec)
            elif ev == "recovery":
                q = app.queries.setdefault(rec["query_id"],
                                           QueryReplay(rec["query_id"]))
                q.recovery = rec.get("recovery")
            elif ev == "oom_retry":
                q = app.queries.setdefault(rec["query_id"],
                                           QueryReplay(rec["query_id"]))
                q.oom_retries.append(rec)
            elif ev == "fallback":
                q = app.queries.setdefault(rec["query_id"],
                                           QueryReplay(rec["query_id"]))
                q.fallbacks.append(rec)
            elif ev == "movement_summary":
                q = app.queries.setdefault(rec["query_id"],
                                           QueryReplay(rec["query_id"]))
                q.movement_summary = rec.get("movement")
            elif ev == "shuffle_summary":
                q = app.queries.setdefault(rec["query_id"],
                                           QueryReplay(rec["query_id"]))
                q.shuffle_summary = rec.get("shuffle")
            elif ev == "query_end":
                q = app.queries.setdefault(rec["query_id"],
                                           QueryReplay(rec["query_id"]))
                q.wall_s = rec.get("wall_s", 0.0)
                q.error = rec.get("error")
                q.ts_end = rec.get("ts", 0.0)
                q.trace_id = rec.get("trace_id", q.trace_id)
                q.critical_path = rec.get("critical_path")
                q.final_plan = rec.get("final_plan", "")
                q.aqe_events = rec.get("aqe_events", [])
                q.spill_count = rec.get("spill_count", {})
                q.semaphore_wait_s = rec.get("semaphore_wait_s", 0.0)
                q.stats = rec.get("stats", {})
    return app
