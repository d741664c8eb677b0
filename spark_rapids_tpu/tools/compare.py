"""Compare two runs: event logs or bench result JSONs.

Reference: the plugin tools' CompareApplications
(tools/.../profiling/CompareApplications.scala) lines up several Spark
event logs and reports matching SQL IDs / stage durations side by side so
a regression can be localized to an operator, not just a query. Same job
here, over our own JSONL event logs (tools/eventlog.py) or two bench
result JSONs (the per-query files of the pre-chip bench, deleted in PR 30:
nothing writes them any more, ROADMAP C2c):

- queries align by query id (the workloads are assumed to be the same
  script run twice);
- operators align by (name, occurrence-index) within a query, which is
  stable across runs of the same plan even when node ids shift;
- per-operator wall/rows deltas plus per-query counter deltas (compile
  cache, upload cache, shuffle tiers, spill, semaphore) with regression
  flags: candidate slower than baseline by more than ``threshold``
  (relative) AND ``min_seconds`` (absolute floor, so microsecond noise on
  trivial operators doesn't flag);
- critical-path category deltas when both runs carry a breakdown
  (schema-v5 event logs / traced bench JSONs): a query whose sync-wait
  fraction grew by more than 5 percentage points flags even when its
  total wall time did NOT regress — the composition shifted toward the
  ROADMAP-item-1 bottleneck and the next scale-up will pay for it;
- per-query memory deltas when both runs carry the flight recorder's
  numbers (schema-v6 ``memory_summary`` / bench ``peak_hbm_bytes``):
  peak HBM and spilled bytes diff side by side, and a candidate whose
  peak grew by more than ``MEM_PEAK_FLAG_FRAC`` (10%) flags a
  peak-memory regression — also independent of wall time, since a run
  can get faster by holding more HBM and pay later in spills/OOM;
- per-query transfer-byte deltas when both runs carry the data-movement
  ledger's numbers (schema-v11 ``movement_summary`` / bench
  ``d2h_bytes``+``h2d_bytes``): D2H/H2D bytes and round trips diff side
  by side, and a candidate whose transfer bytes grew past
  ``MOVE_BYTES_FLAG_FRAC`` (10%) and ``MOVE_BYTES_FLAG_MIN`` flags a
  transfer-byte regression — the same wall-orthogonal logic: a plan
  change that bounces batches through the host can hide inside an
  unchanged total on a fast PCI link and still sink the scale-up;
- per-query shuffle deltas when both runs carry the shuffle
  observatory's numbers (schema-v12 ``shuffle_summary`` totals / bench
  ``shuffle_wall_s``+``wire_bytes``): wall measurably spent inside
  transfer phases and bytes actually crossing the wire diff side by
  side, and a candidate whose shuffle wall grew past
  ``SHUFFLE_WALL_FLAG_FRAC`` (+ the 50 ms floor) or whose wire bytes
  grew past the byte gate flags a shuffle regression — pipeline
  overlap hides a slower tier inside flat query wall, and serializer
  changes inflate wire bytes without touching logical bytes.

CLI: ``python -m spark_rapids_tpu.tools.compare A B [--threshold 0.2]``
where A/B are event-log JSONL paths or bench summary JSONs.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Dict, List, Optional, Tuple

__all__ = ["OpDelta", "QueryDelta", "CompareReport", "compare_event_logs",
           "compare_bench_results", "compare_apps",
           "critical_path_fractions", "critical_path_delta",
           "memory_delta", "movement_delta", "shuffle_delta",
           "CP_FRAC_FLAG_PP",
           "MEM_PEAK_FLAG_FRAC", "MEM_PEAK_FLAG_MIN_BYTES",
           "MOVE_BYTES_FLAG_FRAC", "MOVE_BYTES_FLAG_MIN",
           "SHUFFLE_WALL_FLAG_FRAC", "SHUFFLE_WALL_FLAG_MIN_S",
           "SYNC_WAIT_GATE_FRAC"]

#: category-fraction growth (candidate minus baseline) that flags a
#: critical-path regression: 5 percentage points
CP_FRAC_FLAG_PP = 0.05

#: relative peak-HBM growth (candidate over baseline) that flags a
#: memory regression: 10%
MEM_PEAK_FLAG_FRAC = 0.10

#: absolute peak-HBM growth floor for the memory gate: tiny queries jitter
#: past 10% run-to-run (bucket rounding, warm-cache layout), so a relative
#: gate alone makes the history sentinel cry wolf on clean back-to-back
#: runs — both conditions must hold, like the sentinel's count gates
MEM_PEAK_FLAG_MIN_BYTES = 1 << 20


#: relative transfer-byte growth (candidate over baseline) that flags a
#: movement regression: 10%, same shape as the peak-HBM gate
MOVE_BYTES_FLAG_FRAC = 0.10

#: absolute transfer-byte growth floor for the movement gate — shape
#: buckets round batch capacities, so tiny queries jitter in bytes
#: run-to-run; both conditions must hold, like the memory gate
MOVE_BYTES_FLAG_MIN = 1 << 20

#: relative shuffle-transfer-wall growth (candidate over baseline) that
#: flags a shuffle regression: 10%, same shape as the byte gates
SHUFFLE_WALL_FLAG_FRAC = 0.10

#: absolute shuffle-wall growth floor (50 ms) — tiny transfers jitter
#: with scheduler noise, so both conditions must hold
SHUFFLE_WALL_FLAG_MIN_S = 0.05

#: ABSOLUTE sync-wait ceiling for the candidate run: a query spending
#: more than 10% of its wall blocked on device->host syncs fails the
#: async-first budget regardless of how the baseline did — this is a
#: gate on the candidate, not a delta, so a regression that was already
#: present in the baseline still flags. The violation names the
#: heaviest movement-ledger funnel (bench "sync_top_site") so the fix
#: starts at a file:symbol, not a number.
SYNC_WAIT_GATE_FRAC = 0.10


def movement_delta(mv_a: Optional[Dict], mv_b: Optional[Dict],
                   flag_frac: float = MOVE_BYTES_FLAG_FRAC,
                   flag_min_bytes: int = MOVE_BYTES_FLAG_MIN
                   ) -> Tuple[Dict[str, float], List[str]]:
    """(deltas B - A, flagged keys) from two per-query movement dicts
    ({"d2h_bytes", "h2d_bytes", "round_trips"}, from a v11 event log's
    movement_summary totals or a bench JSON's movement fields). Empty
    when either run lacks the numbers — ledger off must not flag. A
    byte direction growing past ``flag_frac`` AND ``flag_min_bytes``
    flags; new round trips (baseline had none) always flag."""
    if not mv_a or not mv_b:
        return {}, []
    keys = ("d2h_bytes", "h2d_bytes", "round_trips")
    deltas = {k: float(mv_b.get(k) or 0) - float(mv_a.get(k) or 0)
              for k in keys}
    flagged = []
    for k in ("d2h_bytes", "h2d_bytes"):
        a = float(mv_a.get(k) or 0)
        b = float(mv_b.get(k) or 0)
        if a > 0 and b > a * (1.0 + flag_frac) and b - a >= flag_min_bytes:
            flagged.append(k)
    if not float(mv_a.get("round_trips") or 0) \
            and float(mv_b.get("round_trips") or 0):
        flagged.append("round_trips")
    return deltas, flagged


def shuffle_delta(sh_a: Optional[Dict], sh_b: Optional[Dict],
                  flag_frac: float = SHUFFLE_WALL_FLAG_FRAC,
                  flag_min_s: float = SHUFFLE_WALL_FLAG_MIN_S,
                  flag_min_bytes: int = MOVE_BYTES_FLAG_MIN
                  ) -> Tuple[Dict[str, float], List[str]]:
    """(deltas B - A, flagged keys) from two per-query shuffle dicts
    ({"shuffle_wall_s", "wire_bytes"}, from a v12 event log's
    shuffle_summary totals or a bench JSON's shuffle fields). Empty
    when either run lacks the numbers — telemetry off must not flag.
    Shuffle wall growing past ``flag_frac`` AND ``flag_min_s`` flags
    "shuffle_wall_s"; wire bytes growing past ``flag_frac`` AND
    ``flag_min_bytes`` flags "wire_bytes"."""
    if not sh_a or not sh_b:
        return {}, []
    keys = ("shuffle_wall_s", "wire_bytes")
    deltas = {k: float(sh_b.get(k) or 0) - float(sh_a.get(k) or 0)
              for k in keys}
    flagged = []
    floors = {"shuffle_wall_s": flag_min_s, "wire_bytes": flag_min_bytes}
    for k in keys:
        a = float(sh_a.get(k) or 0)
        b = float(sh_b.get(k) or 0)
        if a > 0 and b > a * (1.0 + flag_frac) and b - a >= floors[k]:
            flagged.append(k)
    return deltas, flagged


def memory_delta(mem_a: Optional[Dict], mem_b: Optional[Dict],
                 flag_frac: float = MEM_PEAK_FLAG_FRAC,
                 flag_min_bytes: int = MEM_PEAK_FLAG_MIN_BYTES
                 ) -> Tuple[Dict[str, float], List[str]]:
    """(byte deltas B - A, flagged keys) from two per-query memory dicts
    ({"peak_bytes", "spill_bytes"}, from a v6 event log's memory_summary
    or a bench JSON's per-query fields). Empty when either run lacks the
    numbers — profiling off must not flag. Peak HBM growing past
    ``flag_frac`` AND ``flag_min_bytes`` flags "peak_bytes" (the
    >10%%-and-≥1MiB peak-memory gate)."""
    if not mem_a or not mem_b:
        return {}, []
    deltas = {k: float(mem_b.get(k) or 0) - float(mem_a.get(k) or 0)
              for k in ("peak_bytes", "spill_bytes")}
    flagged = []
    peak_a = float(mem_a.get("peak_bytes") or 0)
    peak_b = float(mem_b.get("peak_bytes") or 0)
    if (peak_a > 0 and peak_b > peak_a * (1.0 + flag_frac)
            and peak_b - peak_a >= flag_min_bytes):
        flagged.append("peak_bytes")
    return deltas, flagged


def critical_path_fractions(cp: Optional[Dict]) -> Optional[Dict]:
    """Category -> fraction-of-wall from a critical-path dict
    (tools/trace.py ``CriticalPath.to_dict()`` or the trimmed bench form
    with only ``categories_s`` + ``total_s``)."""
    if not cp:
        return None
    if cp.get("fractions"):
        return dict(cp["fractions"])
    total = float(cp.get("total_s", 0.0))
    if total <= 0:
        return None
    return {k: float(v) / total
            for k, v in cp.get("categories_s", {}).items()}


def critical_path_delta(cp_a: Optional[Dict], cp_b: Optional[Dict],
                        flag_pp: float = CP_FRAC_FLAG_PP
                        ) -> Tuple[Dict[str, float], List[str]]:
    """(fraction deltas B - A, categories whose share grew > flag_pp).
    Empty when either run lacks a breakdown — absence of tracing must
    not flag."""
    fa = critical_path_fractions(cp_a)
    fb = critical_path_fractions(cp_b)
    if fa is None or fb is None:
        return {}, []
    deltas = {k: round(fb.get(k, 0.0) - fa.get(k, 0.0), 4)
              for k in sorted(set(fa) | set(fb))}
    flagged = sorted(k for k, v in deltas.items() if v > flag_pp)
    return deltas, flagged


@dataclasses.dataclass
class OpDelta:
    """One aligned operator's baseline-vs-candidate numbers. ``query_id``
    is an int for event logs, a "phase:qN" label for bench comparisons."""
    query_id: "int | str"
    name: str
    occurrence: int
    wall_a: float
    wall_b: float
    rows_a: int
    rows_b: int
    regressed: bool = False
    only_in: str = ""  # "a"/"b" when the op exists in one run only

    @property
    def delta_s(self) -> float:
        return self.wall_b - self.wall_a

    @property
    def ratio(self) -> float:
        return self.wall_b / self.wall_a if self.wall_a > 0 else float("inf")


@dataclasses.dataclass
class QueryDelta:
    query_id: "int | str"
    wall_a: float
    wall_b: float
    regressed: bool
    ops: List[OpDelta]
    metric_deltas: Dict[str, float]  # candidate minus baseline counters
    #: critical-path fraction deltas (B - A) per category, when both
    #: runs carried a breakdown
    cp_deltas: Dict[str, float] = dataclasses.field(default_factory=dict)
    #: categories whose share of the query wall grew > CP_FRAC_FLAG_PP
    cp_flagged: List[str] = dataclasses.field(default_factory=list)
    #: memory byte deltas (B - A): peak_bytes + spill_bytes, when both
    #: runs carried the memory flight recorder's numbers
    mem_deltas: Dict[str, float] = dataclasses.field(default_factory=dict)
    #: ["peak_bytes"] when the candidate's peak HBM grew past
    #: MEM_PEAK_FLAG_FRAC — the memory-regression gate
    mem_flagged: List[str] = dataclasses.field(default_factory=list)
    #: the baseline's absolute memory numbers (for % rendering)
    mem_base: Dict[str, float] = dataclasses.field(default_factory=dict)
    #: movement deltas (B - A): d2h/h2d bytes + round trips, when both
    #: runs carried the data-movement ledger's numbers (schema v11)
    move_deltas: Dict[str, float] = dataclasses.field(default_factory=dict)
    #: byte directions grown past MOVE_BYTES_FLAG_FRAC (+ floor), or
    #: "round_trips" when the candidate bounces batches the baseline kept
    #: device-resident — the transfer-byte regression gate
    move_flagged: List[str] = dataclasses.field(default_factory=list)
    #: the baseline's absolute movement numbers (for % rendering)
    move_base: Dict[str, float] = dataclasses.field(default_factory=dict)
    #: candidate sync-wait fraction when it exceeds SYNC_WAIT_GATE_FRAC
    #: (None otherwise) — the absolute async-first budget gate
    sync_gate_frac: Optional[float] = None
    #: the heaviest movement-ledger funnel during the candidate run
    #: (bench "sync_top_site"); where a sync_gate violation points
    sync_top_site: str = ""
    #: shuffle deltas (B - A): transfer wall + wire bytes, when both
    #: runs carried the shuffle observatory's numbers (schema v12)
    shuffle_deltas: Dict[str, float] = dataclasses.field(
        default_factory=dict)
    #: keys grown past SHUFFLE_WALL_FLAG_FRAC (+ their floors) — the
    #: shuffle-regression gate
    shuffle_flagged: List[str] = dataclasses.field(default_factory=list)
    #: the baseline's absolute shuffle numbers (for % rendering)
    shuffle_base: Dict[str, float] = dataclasses.field(
        default_factory=dict)

    @property
    def delta_s(self) -> float:
        return self.wall_b - self.wall_a

    @property
    def ratio(self) -> float:
        return self.wall_b / self.wall_a if self.wall_a > 0 else float("inf")


@dataclasses.dataclass
class CompareReport:
    label_a: str
    label_b: str
    queries: List[QueryDelta]
    threshold: float
    only_in_a: List[int] = dataclasses.field(default_factory=list)
    only_in_b: List[int] = dataclasses.field(default_factory=list)

    def regressions(self) -> List[OpDelta]:
        return [op for q in self.queries for op in q.ops if op.regressed]

    def regressed_queries(self) -> List[QueryDelta]:
        return [q for q in self.queries if q.regressed]

    def critical_path_regressions(self) -> List[QueryDelta]:
        """Queries whose critical-path COMPOSITION regressed (a category's
        share grew past the flag threshold) — orthogonal to wall-time
        regressions; a query can flag here while getting faster."""
        return [q for q in self.queries if q.cp_flagged]

    def memory_regressions(self) -> List[QueryDelta]:
        """Queries whose peak HBM grew past MEM_PEAK_FLAG_FRAC — also
        orthogonal to wall time: a query can get faster by holding more
        memory, and the next scale-up pays in spills/OOM."""
        return [q for q in self.queries if q.mem_flagged]

    def movement_regressions(self) -> List[QueryDelta]:
        """Queries whose host<->device transfer bytes grew past
        MOVE_BYTES_FLAG_FRAC (or that started round-tripping batches) —
        orthogonal to wall time like the memory gate: extra transfers
        hide on a fast link and sink the scale-up."""
        return [q for q in self.queries if q.move_flagged]

    def shuffle_regressions(self) -> List[QueryDelta]:
        """Queries whose shuffle transfer wall or wire bytes grew past
        SHUFFLE_WALL_FLAG_FRAC (+ floors) — orthogonal to wall time:
        pipeline overlap hides a slower shuffle tier inside flat query
        wall until the tier saturates at scale."""
        return [q for q in self.queries if q.shuffle_flagged]

    def sync_wait_violations(self) -> List[QueryDelta]:
        """Queries whose CANDIDATE run spent more than
        SYNC_WAIT_GATE_FRAC of wall blocked on device->host syncs — an
        absolute budget, not a delta, so debt the baseline already
        carried still fails; each violation names the heaviest
        movement-ledger funnel to fix first."""
        return [q for q in self.queries if q.sync_gate_frac is not None]

    def summary(self) -> str:
        lines = [f"compare: A={self.label_a}  B={self.label_b}  "
                 f"(threshold {self.threshold:.0%}; positive delta = "
                 "B slower)"]
        for q in self.queries:
            flag = "  ** REGRESSED" if q.regressed else ""
            lines.append(f"query {q.query_id}: "
                         f"A={q.wall_a:.4f}s B={q.wall_b:.4f}s "
                         f"delta={q.delta_s:+.4f}s "
                         f"({q.ratio:.2f}x){flag}")
            lines.append(f"  {'op':<40}{'A_s':>9}{'B_s':>9}"
                         f"{'delta_s':>10}{'rows_B':>12}")
            for op in q.ops:
                mark = " **" if op.regressed else \
                    (f" [only {op.only_in}]" if op.only_in else "")
                lines.append(f"  {op.name[:39]:<40}{op.wall_a:>9.4f}"
                             f"{op.wall_b:>9.4f}{op.delta_s:>+10.4f}"
                             f"{op.rows_b:>12}{mark}")
            hot = sorted((k for k, v in q.metric_deltas.items() if v),
                         key=lambda k: -abs(q.metric_deltas[k]))[:8]
            if hot:
                lines.append("  counter deltas (B - A): " + ", ".join(
                    f"{k}={q.metric_deltas[k]:+g}" for k in hot))
            if q.cp_deltas:
                moved = sorted((k for k, v in q.cp_deltas.items() if v),
                               key=lambda k: -abs(q.cp_deltas[k]))[:6]
                if moved:
                    lines.append(
                        "  critical-path share deltas (B - A): " + ", ".join(
                            f"{k}={q.cp_deltas[k]:+.1%}" for k in moved))
                if q.cp_flagged:
                    lines.append(
                        "  ** CRITICAL-PATH REGRESSION: "
                        + ", ".join(f"{k} share +{q.cp_deltas[k]:.1%}"
                                    for k in q.cp_flagged))
            if q.mem_deltas:
                parts = []
                for k in sorted(q.mem_deltas):
                    v = q.mem_deltas[k]
                    base = q.mem_base.get(k, 0.0)
                    pct = f" ({v / base:+.1%})" if base > 0 else ""
                    parts.append(f"{k}={v:+.0f}B{pct}")
                lines.append("  memory deltas (B - A): " + ", ".join(parts))
                if q.mem_flagged:
                    lines.append(
                        "  ** PEAK-MEMORY REGRESSION: "
                        + ", ".join(
                            f"{k} +{q.mem_deltas[k] / q.mem_base[k]:.1%}"
                            if q.mem_base.get(k) else f"{k} grew"
                            for k in q.mem_flagged)
                        + f" (gate {MEM_PEAK_FLAG_FRAC:.0%})")
            if q.move_deltas:
                parts = []
                for k in sorted(q.move_deltas):
                    v = q.move_deltas[k]
                    base = q.move_base.get(k, 0.0)
                    pct = f" ({v / base:+.1%})" if base > 0 else ""
                    unit = "" if k == "round_trips" else "B"
                    parts.append(f"{k}={v:+.0f}{unit}{pct}")
                lines.append("  movement deltas (B - A): "
                             + ", ".join(parts))
                if q.move_flagged:
                    lines.append(
                        "  ** TRANSFER-BYTE REGRESSION: "
                        + ", ".join(
                            f"{k} +{q.move_deltas[k] / q.move_base[k]:.1%}"
                            if q.move_base.get(k) else f"{k} grew"
                            for k in q.move_flagged)
                        + f" (gate {MOVE_BYTES_FLAG_FRAC:.0%})")
            if q.shuffle_deltas:
                parts = []
                for k in sorted(q.shuffle_deltas):
                    v = q.shuffle_deltas[k]
                    base = q.shuffle_base.get(k, 0.0)
                    pct = f" ({v / base:+.1%})" if base > 0 else ""
                    unit = "s" if k.endswith("_s") else "B"
                    parts.append(f"{k}={v:+.4g}{unit}{pct}")
                lines.append("  shuffle deltas (B - A): "
                             + ", ".join(parts))
                if q.shuffle_flagged:
                    lines.append(
                        "  ** SHUFFLE REGRESSION: "
                        + ", ".join(
                            f"{k} +{q.shuffle_deltas[k] / q.shuffle_base[k]:.1%}"
                            if q.shuffle_base.get(k) else f"{k} grew"
                            for k in q.shuffle_flagged)
                        + f" (gate {SHUFFLE_WALL_FLAG_FRAC:.0%})")
            if q.sync_gate_frac is not None:
                site = q.sync_top_site or "(no ledger attribution)"
                lines.append(
                    f"  ** SYNC-WAIT GATE: {q.sync_gate_frac:.1%} of "
                    f"wall blocked on device->host syncs (budget "
                    f"{SYNC_WAIT_GATE_FRAC:.0%}) — heaviest funnel: "
                    f"{site}")
        if self.only_in_a:
            lines.append(f"queries only in A: {self.only_in_a}")
        if self.only_in_b:
            lines.append(f"queries only in B: {self.only_in_b}")
        n_reg = len(self.regressions())
        lines.append(f"{n_reg} regressed operator(s), "
                     f"{len(self.regressed_queries())} regressed query(ies), "
                     f"{len(self.critical_path_regressions())} "
                     "critical-path regression(s), "
                     f"{len(self.memory_regressions())} "
                     "peak-memory regression(s), "
                     f"{len(self.movement_regressions())} "
                     "transfer-byte regression(s), "
                     f"{len(self.shuffle_regressions())} "
                     "shuffle regression(s), "
                     f"{len(self.sync_wait_violations())} "
                     "sync-wait gate violation(s)")
        return "\n".join(lines)


def _op_key_counts(nodes: List[Dict]) -> List[Tuple[Tuple[str, int], Dict]]:
    """Stable (name, occurrence) keys in node order."""
    seen: Dict[str, int] = {}
    out = []
    for n in nodes:
        idx = seen.get(n["name"], 0)
        seen[n["name"]] = idx + 1
        out.append(((n["name"], idx), n))
    return out


def _query_memory(q) -> Optional[Dict]:
    """Per-query memory numbers from a replay's v6 ``memory_summary``:
    peak HBM bytes + total bytes its operators spilled. None pre-v6 or
    with profiling off."""
    ms = getattr(q, "memory_summary", None)
    if not ms:
        return None
    per_op = ms.get("per_operator") or {}
    return {"peak_bytes": int(ms.get("peak_bytes") or 0),
            "spill_bytes": sum(int(d.get("spilled_bytes") or 0)
                               for d in per_op.values())}


def _query_movement(q) -> Optional[Dict]:
    """Per-query transfer numbers from a replay's v11 ``movement_summary``
    totals. None pre-v11 or with the ledger off."""
    mv = getattr(q, "movement_summary", None)
    if not mv:
        return None
    t = mv.get("totals") or {}
    return {"d2h_bytes": int(t.get("d2h_bytes") or 0),
            "h2d_bytes": int(t.get("h2d_bytes") or 0),
            "round_trips": int(t.get("round_trips") or 0)}


def _query_shuffle(q) -> Optional[Dict]:
    """Per-query shuffle numbers from a replay's v12 ``shuffle_summary``
    totals. None pre-v12 or with telemetry off."""
    sh = getattr(q, "shuffle_summary", None)
    if not sh:
        return None
    t = sh.get("totals") or {}
    return {"shuffle_wall_s": float(t.get("wall_s") or 0.0),
            "wire_bytes": int(t.get("wire_bytes") or 0)}


def compare_apps(app_a, app_b, threshold: float = 0.2,
                 min_seconds: float = 0.001) -> CompareReport:
    """Compare two loaded ``AppReplay``s (tools/eventlog.py)."""
    qids_a, qids_b = set(app_a.queries), set(app_b.queries)
    queries: List[QueryDelta] = []
    for qid in sorted(qids_a & qids_b):
        qa, qb = app_a.queries[qid], app_b.queries[qid]
        ops_a = dict(_op_key_counts(qa.nodes))
        ops_b = dict(_op_key_counts(qb.nodes))
        ops: List[OpDelta] = []
        for key in list(ops_a) + [k for k in ops_b if k not in ops_a]:
            na, nb = ops_a.get(key), ops_b.get(key)
            wall_a = na["wall_s"] if na else 0.0
            wall_b = nb["wall_s"] if nb else 0.0
            regressed = (na is not None and nb is not None
                         and wall_b > wall_a * (1.0 + threshold)
                         and wall_b - wall_a >= min_seconds)
            ops.append(OpDelta(
                qid, key[0], key[1], wall_a, wall_b,
                na["rows"] if na else 0, nb["rows"] if nb else 0,
                regressed=regressed,
                only_in="a" if nb is None else ("b" if na is None else "")))
        stats_delta = {k: qb.stats.get(k, 0) - qa.stats.get(k, 0)
                       for k in set(qa.stats) | set(qb.stats)
                       if isinstance(qa.stats.get(k, 0), (int, float))
                       and isinstance(qb.stats.get(k, 0), (int, float))}
        q_regressed = (qb.wall_s > qa.wall_s * (1.0 + threshold)
                       and qb.wall_s - qa.wall_s >= min_seconds)
        cp_deltas, cp_flagged = critical_path_delta(
            getattr(qa, "critical_path", None),
            getattr(qb, "critical_path", None))
        mem_a, mem_b = _query_memory(qa), _query_memory(qb)
        mem_deltas, mem_flagged = memory_delta(mem_a, mem_b)
        mv_a, mv_b = _query_movement(qa), _query_movement(qb)
        move_deltas, move_flagged = movement_delta(mv_a, mv_b)
        sh_a, sh_b = _query_shuffle(qa), _query_shuffle(qb)
        sh_deltas, sh_flagged = shuffle_delta(sh_a, sh_b)
        queries.append(QueryDelta(qid, qa.wall_s, qb.wall_s,
                                  q_regressed, ops, stats_delta,
                                  cp_deltas, cp_flagged,
                                  mem_deltas, mem_flagged,
                                  {k: float(v) for k, v in
                                   (mem_a or {}).items()},
                                  move_deltas, move_flagged,
                                  {k: float(v) for k, v in
                                   (mv_a or {}).items()},
                                  shuffle_deltas=sh_deltas,
                                  shuffle_flagged=sh_flagged,
                                  shuffle_base={k: float(v) for k, v in
                                                (sh_a or {}).items()}))
    return CompareReport(app_a.app_id or app_a.path,
                         app_b.app_id or app_b.path, queries, threshold,
                         sorted(qids_a - qids_b), sorted(qids_b - qids_a))


def compare_event_logs(path_a: str, path_b: str, threshold: float = 0.2,
                       min_seconds: float = 0.001) -> CompareReport:
    """Load two JSONL event logs and align them (A = baseline,
    B = candidate)."""
    from .eventlog import load_event_log
    return compare_apps(load_event_log(path_a), load_event_log(path_b),
                        threshold, min_seconds)


def _bench_memory(entry: Dict) -> Optional[Dict]:
    """Per-query memory numbers from a bench JSON entry (peak_hbm_bytes +
    spill_bytes, written when the memory profiler was on)."""
    if "peak_hbm_bytes" not in entry:
        return None
    return {"peak_bytes": int(entry.get("peak_hbm_bytes") or 0),
            "spill_bytes": int(entry.get("spill_bytes") or 0)}


def _bench_movement(entry: Dict) -> Optional[Dict]:
    """Per-query transfer numbers from a bench JSON entry
    (d2h_bytes/h2d_bytes/round_trips, written when the movement ledger
    was on)."""
    if "d2h_bytes" not in entry:
        return None
    return {"d2h_bytes": int(entry.get("d2h_bytes") or 0),
            "h2d_bytes": int(entry.get("h2d_bytes") or 0),
            "round_trips": int(entry.get("round_trips") or 0)}


def _bench_shuffle(entry: Dict) -> Optional[Dict]:
    """Per-query shuffle numbers from a bench JSON entry
    (shuffle_wall_s/shuffle_wall_frac/wire_bytes, written when shuffle
    telemetry was on)."""
    if "shuffle_wall_s" not in entry:
        return None
    return {"shuffle_wall_s": float(entry.get("shuffle_wall_s") or 0.0),
            "wire_bytes": int(entry.get("wire_bytes") or 0)}


def compare_bench_results(path_a: str, path_b: str, threshold: float = 0.2,
                          min_seconds: float = 0.001) -> CompareReport:
    """Compare two per-query bench result JSONs (the
    BENCH_partial.json shape, with smoke/tpch sections): device seconds as
    single-op queries so the same report/flagging machinery applies."""
    with open(path_a, encoding="utf-8") as f:
        a = json.load(f)
    with open(path_b, encoding="utf-8") as f:
        b = json.load(f)
    # phases compare separately: smoke and tpch both name queries q1/q6
    # but run at different scale factors — merging would shadow the smoke
    # entries (or diff incomparable numbers when one run lacks a phase)
    queries: List[QueryDelta] = []
    only_a: List = []
    only_b: List = []
    for phase in ("smoke", "tpch"):
        qs_a = a.get(phase, {})
        qs_b = b.get(phase, {})
        names = sorted(set(qs_a) & set(qs_b),
                       key=lambda n: int(n.lstrip("q"))
                       if n.lstrip("q").isdigit() else 0)
        only_a.extend(f"{phase}:{n}" for n in sorted(set(qs_a) - set(qs_b)))
        only_b.extend(f"{phase}:{n}" for n in sorted(set(qs_b) - set(qs_a)))
        for name in names:
            label = f"{phase}:{name}"
            wall_a = float(qs_a[name].get("dev_s", 0.0))
            wall_b = float(qs_b[name].get("dev_s", 0.0))
            regressed = (wall_a > 0 and wall_b > wall_a * (1.0 + threshold)
                         and wall_b - wall_a >= min_seconds)
            deltas = {k: float(qs_b[name].get(k, 0))
                      - float(qs_a[name].get(k, 0))
                      for k in ("dev_s", "cpu_s", "compile_s", "speedup",
                                "sync_wait_frac")
                      if k in qs_a[name] or k in qs_b[name]}
            cp_deltas, cp_flagged = critical_path_delta(
                qs_a[name].get("critical_path"),
                qs_b[name].get("critical_path"))
            mem_a = _bench_memory(qs_a[name])
            mem_b = _bench_memory(qs_b[name])
            mem_deltas, mem_flagged = memory_delta(mem_a, mem_b)
            mv_a = _bench_movement(qs_a[name])
            mv_b = _bench_movement(qs_b[name])
            move_deltas, move_flagged = movement_delta(mv_a, mv_b)
            sh_a = _bench_shuffle(qs_a[name])
            sh_b = _bench_shuffle(qs_b[name])
            sh_deltas, sh_flagged = shuffle_delta(sh_a, sh_b)
            # absolute sync-wait budget on the CANDIDATE run: > 10% of
            # wall blocked on syncs fails even if the baseline was just
            # as bad; the heaviest ledger funnel gives the fix a target
            frac_b = qs_b[name].get("sync_wait_frac")
            gate_frac = (float(frac_b)
                         if frac_b is not None
                         and float(frac_b) > SYNC_WAIT_GATE_FRAC
                         else None)
            queries.append(QueryDelta(
                label, wall_a, wall_b, regressed,
                [OpDelta(label, name, 0, wall_a, wall_b, 0, 0,
                         regressed=regressed)], deltas,
                cp_deltas, cp_flagged,
                mem_deltas, mem_flagged,
                {k: float(v) for k, v in (mem_a or {}).items()},
                move_deltas, move_flagged,
                {k: float(v) for k, v in (mv_a or {}).items()},
                sync_gate_frac=gate_frac,
                sync_top_site=str(qs_b[name].get("sync_top_site") or ""),
                shuffle_deltas=sh_deltas, shuffle_flagged=sh_flagged,
                shuffle_base={k: float(v) for k, v in
                              (sh_a or {}).items()}))
    return CompareReport(path_a, path_b, queries, threshold,
                         only_a, only_b)


def _sniff(path: str) -> str:
    """Classify an input file: "bench" (one JSON object with smoke/tpch
    per-query sections, i.e. BENCH_partial.json shape), "eventlog" (JSONL
    from tools/eventlog.py), or "unknown". Note the round driver's
    BENCH_rNN.json wrappers hold only the summary metric — no per-query
    data to compare — so they classify as unknown."""
    try:
        with open(path, encoding="utf-8") as f:
            obj = json.load(f)
    except json.JSONDecodeError:
        # multi-line JSONL fails a full-file parse; check the first record
        try:
            with open(path, encoding="utf-8") as f:
                first = json.loads(f.readline())
            return "eventlog" if isinstance(first, dict) and "event" in first \
                else "unknown"
        except (json.JSONDecodeError, OSError):
            return "unknown"
    except OSError:
        return "unknown"
    if isinstance(obj, dict):
        if "tpch" in obj or "smoke" in obj:
            return "bench"
        if "event" in obj:
            return "eventlog"  # degenerate single-record log
    return "unknown"


def main(argv: Optional[List[str]] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        description="Compare two event logs or bench result JSONs "
                    "(A = baseline, B = candidate)")
    ap.add_argument("log_a")
    ap.add_argument("log_b")
    ap.add_argument("--threshold", type=float, default=0.2,
                    help="relative slowdown that flags a regression")
    ap.add_argument("--min-seconds", type=float, default=0.001,
                    help="absolute slowdown floor for flagging")
    args = ap.parse_args(argv)
    kinds = {_sniff(args.log_a), _sniff(args.log_b)}
    if "unknown" in kinds:
        ap.error(
            "inputs must both be event logs (JSONL from "
            "spark.rapids.tpu.eventLog.dir) or both bench summaries with "
            "per-query sections (BENCH_partial.json / bench event sink); "
            "round wrapper files like BENCH_rNN.json carry only the "
            "summary metric and cannot be compared per operator")
    if len(kinds) > 1:
        ap.error("cannot compare an event log against a bench summary")
    if kinds == {"bench"}:
        report = compare_bench_results(args.log_a, args.log_b,
                                       args.threshold, args.min_seconds)
    else:
        report = compare_event_logs(args.log_a, args.log_b, args.threshold,
                                    args.min_seconds)
    print(report.summary())
    return 1 if report.regressions() \
        or report.critical_path_regressions() \
        or report.memory_regressions() \
        or report.movement_regressions() \
        or report.shuffle_regressions() else 0


if __name__ == "__main__":  # pragma: no cover
    raise SystemExit(main())
