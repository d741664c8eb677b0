"""Distributed-trace collector: merge per-process Chrome traces onto one
timeline + per-query critical-path attribution.

Each process (driver and every ProcessCluster worker) records spans into
its own tracer ring (utils/tracing.py) against its own
``time.perf_counter()`` epoch. Cross-process identity travels as a
TraceContext (trace_id, parent span id, query_id) inside task envelopes
and the SRTC shuffle wire header, so a worker span's ``parent_span_id``
points at a driver span — but the TIMESTAMPS live in per-process clock
domains. This module puts them on one timeline:

1. every tracer snapshots ``epoch_unix = time.time()`` at the same
   instant as its perf_counter epoch, anchoring relative timestamps to
   that process's wall clock;
2. ProcessCluster estimates each worker's wall-clock offset against the
   driver with an NTP-style min-RTT handshake (the "clock" envelope
   kind) and stamps ``clock_offset_s`` into collected traces;
3. ``merge_process_traces`` shifts every event by
   ``(epoch_unix - clock_offset_s) - driver_epoch`` so all spans share
   the driver's timebase, assigns deterministic pids, and emits one
   Perfetto-loadable Chrome trace with per-process metadata rows.

Critical-path attribution walks the merged span DAG for one trace_id:
each span's SELF time (its duration minus the union of its children's
intervals, clipped to the parent) is attributed to a category —
device compute, sync wait, shuffle transfer, compile, semaphore wait,
pipeline-queue idle, ... — so the categories sum to exactly the root
query span's wall time. The ranked path is the greedy longest chain
root -> leaf, the place an optimiser should look first (reference: the
qualification/profiling tool ranks stages by task time the same way,
tools/qualification in the plugin repo).

CLI::

    python -m spark_rapids_tpu.tools.trace merge <dir-or-files...> \
        [-o merged.json] [--trace-id HEX]
    python -m spark_rapids_tpu.tools.trace critical-path <merged.json> \
        [--trace-id HEX]
    python -m spark_rapids_tpu.tools.trace gaps <capture.xplane.pb> [--json]

``gaps`` reads a ``jax.profiler`` capture instead of the engine's own
ring: every ``Tracer.span`` is also a ``TraceAnnotation("srt.<name>")``, so
under any profiler session the engine's spans lie in the xplane's host
plane on the clock the device planes are synchronised to. The command
books the busiest device's idle time inside each query to the phase span
the host was in and to a cause — the module another device was running,
or that host span while every device was idle — and prints every
device's busy seconds by module and the longest gaps.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Iterable, List, Optional, Tuple

__all__ = ["merge_process_traces", "load_process_traces",
           "critical_path", "critical_path_from_tracer", "CriticalPath",
           "CATEGORY_BY_CAT", "span_category", "idle_by_phase"]

# ---------------------------------------------------------------------------
# category attribution: tracer cat -> critical-path bucket
# ---------------------------------------------------------------------------
#: tracer category -> critical-path cost bucket. Structural spans
#: (query/stage/task) fall through to "other": their SELF time is
#: scheduling/driver overhead not owned by any subsystem.
CATEGORY_BY_CAT: Dict[str, str] = {
    "operator": "device_compute",
    "compile": "compile",
    "semaphore": "semaphore_wait",
    "shuffle": "shuffle_transfer",
    "pipeline": "pipeline_queue_idle",
    "download": "sync_wait",      # blocking D2H sync (ROADMAP item 1)
    "upload": "h2d_upload",
    "plan": "planning",
    "scan": "host_scan",
    "dispatch": "dispatch",
    "spill": "spill",
    # spill-restore + OOM-recovery spans (memory/catalog.py) — time the
    # query lost to HBM pressure, distinct from proactive spill writes
    "memory": "memory_pressure",
}


def span_category(cat: str) -> str:
    return CATEGORY_BY_CAT.get(cat, "other")


# ---------------------------------------------------------------------------
# loading + merging
# ---------------------------------------------------------------------------
def load_process_traces(sources: Iterable[str]) -> List[dict]:
    """Load per-process Chrome trace dicts from files and/or directories
    (directories contribute every ``trace-*.json`` inside, sorted)."""
    paths: List[str] = []
    for src in sources:
        if os.path.isdir(src):
            paths.extend(sorted(
                os.path.join(src, n) for n in os.listdir(src)
                if n.startswith("trace-") and n.endswith(".json")))
        else:
            paths.append(src)
    traces = []
    for p in paths:
        with open(p, "r", encoding="utf-8") as f:
            traces.append(json.load(f))
    return traces


def _proc_key(trace: dict) -> Tuple[int, str]:
    od = trace.get("otherData", {})
    role = od.get("role", "")
    # driver first, then workers by name — deterministic merge order no
    # matter what order the files were read in
    return (0 if role == "driver" else 1, str(od.get("process_name", "")))


def merge_process_traces(traces: List[dict],
                         trace_id: Optional[str] = None) -> dict:
    """Merge per-process Chrome traces into ONE Perfetto-loadable trace.

    - clock alignment: every event is shifted into the driver's wall
      clock using the process's ``epoch_unix`` anchor and its
      ``clock_offset_s`` handshake estimate (driver offset = 0);
    - deterministic pids: processes are sorted (driver first, then by
      process name) and numbered 1..N, with ``process_name`` /
      ``process_sort_index`` metadata rows;
    - drop accounting: a process whose window dropped events gets a
      ``trace_truncated`` instant at the front of its row and a
      ``truncated`` flag in ``otherData.processes`` — a merged timeline
      never silently hides a wrapped ring;
    - ``trace_id`` filters to one query's span DAG (metadata rows are
      kept only for processes that still contribute events).
    """
    ordered = sorted(traces, key=_proc_key)
    ref_epoch = None
    for t in ordered:
        od = t.get("otherData", {})
        if od.get("role") == "driver" and "epoch_unix" in od:
            ref_epoch = float(od["epoch_unix"])
            break
    if ref_epoch is None and ordered:
        ref_epoch = float(
            ordered[0].get("otherData", {}).get("epoch_unix", 0.0))

    events: List[dict] = []
    processes: List[dict] = []
    for idx, t in enumerate(ordered):
        od = t.get("otherData", {})
        pid = idx + 1
        name = str(od.get("process_name", f"process-{pid}"))
        offset = float(od.get("clock_offset_s", 0.0))
        epoch = float(od.get("epoch_unix", ref_epoch or 0.0))
        # worker wall = epoch + ts; driver-clock equivalent subtracts the
        # estimated (worker_wall - driver_wall) offset
        shift_us = ((epoch - offset) - (ref_epoch or 0.0)) * 1e6
        dropped = int(od.get("dropped_events", 0))
        kept: List[dict] = []
        for ev in t.get("traceEvents", []):
            if ev.get("ph") == "M":
                continue  # re-emitted below with merged pids
            if trace_id is not None \
                    and ev.get("args", {}).get("trace_id") != trace_id:
                continue
            out = dict(ev)
            out["pid"] = pid
            out["ts"] = round(float(ev.get("ts", 0.0)) + shift_us, 3)
            kept.append(out)
        if trace_id is not None and not kept:
            continue  # process contributed nothing to this query
        first_ts = min((e["ts"] for e in kept), default=0.0)
        if dropped > 0:
            kept.append({
                "name": "trace_truncated", "cat": "health", "ph": "i",
                "ts": round(first_ts, 3), "pid": pid, "tid": 0, "s": "p",
                "args": {"dropped_events": dropped,
                         "process_name": name}})
        events.extend(kept)
        processes.append({
            "pid": pid, "process_name": name,
            "role": od.get("role", "unknown"),
            "clock_offset_s": offset, "epoch_unix": epoch,
            "dropped_events": dropped, "truncated": dropped > 0,
            "events": len(kept)})
        events.append({"name": "process_name", "ph": "M", "pid": pid,
                       "tid": 0, "args": {"name": name}})
        events.append({"name": "process_sort_index", "ph": "M", "pid": pid,
                       "tid": 0, "args": {"sort_index": idx}})

    events.sort(key=lambda e: (e.get("ph") != "M", e.get("pid", 0),
                               e.get("ts", 0.0), e.get("name", "")))
    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {
            "producer": "spark-rapids-tpu",
            "merged": True,
            "clock_aligned": True,
            "reference_epoch_unix": ref_epoch or 0.0,
            "trace_id_filter": trace_id,
            "processes": processes,
            "truncated_processes": [p["process_name"] for p in processes
                                    if p["truncated"]],
        },
    }


# ---------------------------------------------------------------------------
# critical-path attribution
# ---------------------------------------------------------------------------
class _Span:
    __slots__ = ("name", "cat", "ts", "dur", "span_id", "parent_id", "pid")

    def __init__(self, name, cat, ts, dur, span_id, parent_id, pid):
        self.name = name
        self.cat = cat
        self.ts = ts
        self.dur = dur
        self.span_id = span_id
        self.parent_id = parent_id
        self.pid = pid

    @property
    def end(self):
        return self.ts + self.dur


class CriticalPath:
    """Per-query wall-time attribution over the merged span DAG.

    ``categories`` maps cost bucket -> seconds of SELF time summed over
    the tree (children clipped to parents). With serial children the
    buckets sum to the root span's wall time exactly (coverage 1.0);
    concurrent children (parallel partition drains) overlap in wall
    time, so coverage reads as parallel busy time and can exceed 1.0 —
    either way the acceptance bar is coverage >= 0.95. ``ranked_path``
    is the greedy longest chain from the query root to a leaf."""

    def __init__(self, trace_id: str, total_s: float,
                 categories: Dict[str, float],
                 ranked_path: List[Dict],
                 span_count: int):
        self.trace_id = trace_id
        self.total_s = total_s
        self.categories = categories
        self.ranked_path = ranked_path
        self.span_count = span_count

    @property
    def sync_wait_frac(self) -> float:
        if self.total_s <= 0:
            return 0.0
        return self.categories.get("sync_wait", 0.0) / self.total_s

    @property
    def coverage(self) -> float:
        """Fraction of the root wall time the categories account for:
        1.0 by construction for serial children, above 1.0 when sibling
        spans ran concurrently (parallel busy time). The acceptance bar
        is >= 0.95."""
        if self.total_s <= 0:
            return 0.0
        return sum(self.categories.values()) / self.total_s

    def to_dict(self) -> Dict:
        total = self.total_s
        fractions = {k: (v / total if total > 0 else 0.0)
                     for k, v in self.categories.items()}
        return {
            "trace_id": self.trace_id,
            "total_s": round(total, 6),
            "span_count": self.span_count,
            "categories_s": {k: round(v, 6)
                             for k, v in sorted(self.categories.items())},
            "fractions": {k: round(v, 4)
                          for k, v in sorted(fractions.items())},
            "sync_wait_frac": round(self.sync_wait_frac, 4),
            "coverage": round(self.coverage, 4),
            "ranked_path": self.ranked_path,
        }

    def render(self) -> str:
        lines = [f"critical path for trace {self.trace_id} "
                 f"({self.total_s * 1e3:.2f} ms wall, "
                 f"{self.span_count} spans)"]
        total = self.total_s or 1.0
        for cat, sec in sorted(self.categories.items(),
                               key=lambda kv: -kv[1]):
            lines.append(f"  {cat:<22} {sec * 1e3:10.3f} ms "
                         f"{100.0 * sec / total:6.2f}%")
        lines.append("  ranked path (longest chain):")
        for i, hop in enumerate(self.ranked_path):
            lines.append(f"    {'  ' * i}{hop['name']} "
                         f"[{hop['category']}] "
                         f"{hop['dur_s'] * 1e3:.3f} ms")
        return "\n".join(lines)


def _extract_spans(events: Iterable[dict],
                   trace_id: Optional[str]) -> List[_Span]:
    spans = []
    for ev in events:
        if ev.get("ph") != "X":
            continue
        args = ev.get("args", {})
        sid = args.get("span_id")
        if sid is None:
            continue
        if trace_id is not None and args.get("trace_id") != trace_id:
            continue
        spans.append(_Span(ev.get("name", "?"), ev.get("cat", "misc"),
                           float(ev.get("ts", 0.0)),
                           float(ev.get("dur", 0.0)),
                           int(sid), args.get("parent_span_id"),
                           ev.get("pid", 0)))
    return spans


def _union_len(ivals: List[Tuple[float, float]]) -> float:
    if not ivals:
        return 0.0
    ivals.sort()
    total = 0.0
    cur_lo, cur_hi = ivals[0]
    for lo, hi in ivals[1:]:
        if lo > cur_hi:
            total += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        else:
            cur_hi = max(cur_hi, hi)
    total += cur_hi - cur_lo
    return total


def critical_path(events: Iterable[dict],
                  trace_id: Optional[str] = None) -> Optional[CriticalPath]:
    """Attribute one query's wall time over its span DAG.

    ``events`` are Chrome-format event dicts (merged or single-process).
    The root is the ``query`` span of the trace (falling back to the
    longest parentless span); spans whose parent never made it into the
    ring (dropped, or a process that wasn't collected) attach under the
    root so their time is still attributed rather than lost."""
    spans = _extract_spans(events, trace_id)
    if not spans:
        return None
    trace_label = trace_id if trace_id is not None else "(all)"
    ids = {s.span_id for s in spans}
    root_candidates = [s for s in spans if s.parent_id not in ids]
    by_id: Dict[int, _Span] = {s.span_id: s for s in spans}
    children: Dict[int, List[_Span]] = {}
    for s in spans:
        if s.parent_id in by_id and s.parent_id != s.span_id:
            children.setdefault(s.parent_id, []).append(s)
    if not root_candidates:
        # cycles / all-parented (shouldn't happen): longest span wins
        root_candidates = [max(spans, key=lambda s: s.dur)]
    roots_q = [s for s in root_candidates if s.cat == "query"]
    root = max(roots_q or root_candidates, key=lambda s: s.dur)
    # orphans: parentless spans other than the root adopt the root, so
    # their cost is attributed instead of silently dropped
    orphans = [s for s in root_candidates if s.span_id != root.span_id]
    if orphans:
        children.setdefault(root.span_id, []).extend(orphans)

    categories: Dict[str, float] = {}
    visited = set()

    def attribute(span: _Span, lo: float, hi: float) -> None:
        if span.span_id in visited:
            return
        visited.add(span.span_id)
        lo = max(lo, span.ts)
        hi = min(hi, span.end)
        if hi <= lo:
            return
        kids = children.get(span.span_id, [])
        clipped = []
        for k in kids:
            klo, khi = max(lo, k.ts), min(hi, k.end)
            if khi > klo:
                clipped.append((klo, khi))
        self_time = (hi - lo) - _union_len(clipped)
        if self_time > 0:
            cat = span_category(span.cat)
            categories[cat] = categories.get(cat, 0.0) + self_time
        for k in kids:
            attribute(k, lo, hi)

    attribute(root, root.ts, root.end)

    # greedy longest chain root -> leaf (each hop: the child covering the
    # most of its parent's window)
    ranked: List[Dict] = []
    node, lo, hi = root, root.ts, root.end
    chain_seen = set()
    while node is not None and node.span_id not in chain_seen:
        chain_seen.add(node.span_id)
        lo, hi = max(lo, node.ts), min(hi, node.end)
        ranked.append({"name": node.name, "cat": node.cat,
                       "category": span_category(node.cat),
                       "dur_s": round(max(hi - lo, 0.0) / 1e6, 6),
                       "pid": node.pid})
        best, best_len = None, 0.0
        for k in children.get(node.span_id, []):
            klen = min(hi, k.end) - max(lo, k.ts)
            if klen > best_len:
                best, best_len = k, klen
        node = best
    # µs -> seconds
    categories_s = {k: v / 1e6 for k, v in categories.items()}
    return CriticalPath(trace_label, root.dur / 1e6, categories_s,
                        ranked, len(spans))


def critical_path_from_tracer(tracer,
                              trace_id: str) -> Optional[CriticalPath]:
    """Critical path over the LIVE in-process tracer ring (driver side;
    the eventlog's query_end hook) — no export round trip."""
    events = [e.to_chrome(pid=os.getpid()) for e in tracer.events()]
    return critical_path(events, trace_id)


def query_trace_ids(events: Iterable[dict]) -> List[Tuple[str, float]]:
    """(trace_id, query-span duration seconds) for every query span
    present, longest first — the pick list for critical-path reports."""
    out = []
    for ev in events:
        if ev.get("ph") == "X" and ev.get("cat") == "query":
            tid = ev.get("args", {}).get("trace_id")
            if tid:
                out.append((tid, float(ev.get("dur", 0.0)) / 1e6))
    out.sort(key=lambda kv: -kv[1])
    return out


# ---------------------------------------------------------------------------
# device idle time by host phase and by cause, from a jax.profiler capture
# ---------------------------------------------------------------------------
_HOST_PLANE = "/host:CPU"
_DEVICE_PLANE_PREFIX = "/device:TPU:"
_DEVICE_OPS_LINE = "XLA Ops"
_DEVICE_MODULES_LINE = "XLA Modules"
_NO_SPAN = "(no srt span open)"
_NO_MODULE = "(no module)"
_PROGRAM_MODULE_PREFIX = "jit_srt_"
#: how many of the busiest device's gaps ``longest_gaps`` describes
_LONGEST_GAPS = 8
#: how many causes a gap's row lists, and how many rows of the table of
#: causes the CLI prints (``--json`` has them all)
_GAP_CAUSES = 4
_CAUSE_ROWS = 24
#: what of a host span's arguments a gap's row carries
_GAP_ARGS = ("program", "device", "partition", "scalars")


def _span_rank(name: str, structural) -> int:
    """Which open span a gap is booked to: a phase that does work beats a
    wait, and a wait beats the spans that only group others or wait on
    the engine's own producer thread (``structural``: ``wait.pipeline`` on
    the consumer says nothing while the producer is in ``scan.read``)."""
    if name in structural:
        return 0
    return 1 if name.startswith("wait.") else 2


def _merged(ivals) -> List[List[float]]:
    out: List[List[float]] = []
    for s, e in sorted(ivals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _inside(ivals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in ivals
            if min(e, hi) > max(s, lo)]


def _module_name(name: str) -> str:
    """``jit_srt_stage(123)`` -> ``jit_srt_stage``."""
    return name[:name.rindex("(")] if name.endswith(")") and "(" in name \
        else name


def _cause_label(device: Optional[str], what: str) -> str:
    return f"{device}: {what}" if device else f"host: {what}"


def idle_by_phase(profile) -> Optional[Dict]:
    """Where the busiest device's idle time inside ``srt.query`` spans went,
    from a ``jax.profiler`` capture. ``profile`` is a
    ``jax.profiler.ProfileData`` or anything shaped like it (planes ->
    lines -> events with name, start_ns, duration_ns, stats). None when
    the capture has no device plane or no query span. Reuses nothing of
    ``benchmark/``.

    Every elementary interval of the busiest device's gaps (between two
    neighbouring boundaries of any span, gap or other device's work) is
    booked twice:

    - ``idle_by_phase_s``: to the innermost ``srt.*`` span open on any
      host thread (ranked by ``_span_rank``, then the latest opened), the
      remainder to ``(no srt span open)``;
    - ``idle_by_cause_s``: to ONE cause, with that host span beside it.
      While another device is busy the cause is that device and the module
      it runs (``XLA Modules`` line; the busiest such device where several
      are); while every device is idle it is the host span.

    ``devices`` has every device plane's busy and idle seconds inside the
    queries and its seconds by module; ``longest_gaps`` the busiest
    device's longest gaps, each with its offset in its query, its causes
    and the arguments of the host span that held most of it.
    ``named_share`` is the share of the idle time whose cause has a name:
    a ``jit_srt_*`` module of another device, or a host span that is not
    structural."""
    from ..utils.tracing import ANNOTATION_PREFIX, STRUCTURAL_SPANS
    spans: List[Tuple[float, float, str, Dict]] = []
    busy: Dict[str, List[Tuple[float, float]]] = {}
    modules: Dict[str, List[Tuple[float, float, str]]] = {}
    for plane in profile.planes:
        if plane.name == _HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(ANNOTATION_PREFIX):
                        spans.append((e.start_ns,
                                      e.start_ns + e.duration_ns,
                                      e.name[len(ANNOTATION_PREFIX):],
                                      dict(getattr(e, "stats", None) or ())))
        elif plane.name.startswith(_DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name == _DEVICE_OPS_LINE:
                    busy.setdefault(plane.name, []).extend(
                        (e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events)
                elif line.name == _DEVICE_MODULES_LINE:
                    modules.setdefault(plane.name, []).extend(
                        (e.start_ns, e.start_ns + e.duration_ns,
                         _module_name(e.name)) for e in line.events)
    query_spans = [(s, e) for s, e, n, _ in spans if n == "query"]
    queries = _merged(query_spans)
    if not busy or not queries:
        return None

    def in_queries(ivals):
        return sum(e - s for qs, qe in queries
                   for s, e in _inside(ivals, qs, qe))

    ops = {d: _merged(iv) for d, iv in busy.items()}
    busy_ns = {d: in_queries(iv) for d, iv in ops.items()}
    device = max(busy_ns, key=busy_ns.get)
    # the other devices, busiest first: who a shared gap is booked to
    others = sorted((d for d in ops if d != device),
                    key=lambda d: -busy_ns[d])
    gaps: List[Tuple[float, float, float]] = []     # start, end, query start
    for qs, qe in queries:
        edge = qs
        for s, e in _inside(ops[device], qs, qe):
            if s > edge:
                gaps.append((edge, s, qs))
            edge = max(edge, e)
        if qe > edge:
            gaps.append((edge, qe, qs))
    longest = sorted(range(len(gaps)),
                     key=lambda i: gaps[i][0] - gaps[i][1])[:_LONGEST_GAPS]
    in_longest: Dict[int, Dict] = {i: {} for i in longest}

    # one sweep over every boundary: between two neighbours the set of
    # open spans and of busy devices is constant, so the elementary
    # interval has one owner. Closings sort before openings at one instant
    rank = {n: _span_rank(n, STRUCTURAL_SPANS) for _, _, n, _ in spans}
    points = []
    for i, (s, e, n, _) in enumerate(spans):
        points.append((s, 1, (s, n, i)))    # latest opened, name, which
        points.append((e, 0, (s, n, i)))
    for i, (s, e, _) in enumerate(gaps):
        points.append((s, 3, i))
        points.append((e, 2, i))
    for d in others:
        for s, e in ops[d]:
            points.append((s, 5, d))
            points.append((e, 4, d))
        for s, e, n in modules.get(d, ()):
            points.append((s, 7, (d, n)))
            points.append((e, 6, (d, n)))
    points.sort(key=lambda p: (p[0], p[1]))
    open_spans: set = set()
    busy_now = dict.fromkeys(others, False)
    running: Dict[str, List[str]] = {d: [] for d in others}
    gap = None
    by_phase: Dict[str, float] = {}
    by_cause: Dict[Tuple, float] = {}
    prev = None
    for t, kind, key in points:
        if gap is not None and prev is not None and t > prev:
            owner = max(open_spans, default=None,
                        key=lambda k: (rank[k[1]], k[0]))
            name = _NO_SPAN if owner is None else owner[1]
            by_phase[name] = by_phase.get(name, 0.0) + (t - prev)
            at = next((d for d in others if busy_now[d]), None)
            what = name if at is None else \
                (running[at][-1] if running[at] else _NO_MODULE)
            on = None if owner is None else spans[owner[2]][3].get("device")
            cause = (at, what, name, on)
            by_cause[cause] = by_cause.get(cause, 0.0) + (t - prev)
            if gap in in_longest:
                held = in_longest[gap]
                k = (cause, None if owner is None else owner[2])
                held[k] = held.get(k, 0.0) + (t - prev)
        prev = t
        if kind == 1:
            open_spans.add(key)
        elif kind == 0:
            open_spans.discard(key)
        elif kind in (2, 3):
            gap = key if kind == 3 else None
        elif kind in (4, 5):
            busy_now[key] = kind == 5
        elif kind == 7:
            running[key[0]].append(key[1])
        elif key[1] in running[key[0]]:
            running[key[0]].remove(key[1])

    ns = 1e-9

    def cause_row(cause, sec):
        at, what, name, on = cause
        return {"cause": _cause_label(at, what), "device": at,
                "module": what if at else None, "host_span": name,
                "host_device": on, "s": sec * ns}

    def has_name(cause) -> bool:
        at, what, name, _ = cause
        if at is not None:
            return what.startswith(_PROGRAM_MODULE_PREFIX)
        return name != _NO_SPAN and rank[name] > 0

    def device_row(d):
        secs: Dict[str, float] = {}
        for s, e, n in modules.get(d, ()):
            secs[n] = secs.get(n, 0.0) + in_queries([(s, e)])
        return {"busy_s": busy_ns[d] * ns,
                "idle_s": (query_ns - busy_ns[d]) * ns,
                "modules_s": {n: v * ns for n, v in sorted(
                    secs.items(), key=lambda kv: -kv[1]) if v}}

    def gap_row(i):
        s, e, qs = gaps[i]
        causes: Dict[Tuple, float] = {}     # (device, module or host span)
        for (cause, _), sec in in_longest[i].items():
            causes[cause[:2]] = causes.get(cause[:2], 0.0) + sec
        top = max(causes, key=causes.get)
        # the host span that held most of the gap's leading cause
        (cause, span), _ = max(
            (kv for kv in in_longest[i].items() if kv[0][0][:2] == top),
            key=lambda kv: kv[1])
        args = {} if span is None else spans[span][3]
        return {"offset_s": (s - qs) * ns, "length_s": (e - s) * ns,
                "cause": _cause_label(*top),
                "cause_share": causes[top] / (e - s),
                "host_span": cause[2],
                "args": {k: args[k] for k in _GAP_ARGS if k in args},
                "causes": {_cause_label(*c): v * ns for c, v in sorted(
                    causes.items(), key=lambda kv: -kv[1])[:_GAP_CAUSES]}}

    query_ns = sum(e - s for s, e in queries)
    idle = sum(e - s for s, e, _ in gaps)
    named = sum(v for c, v in by_cause.items() if has_name(c))
    return {
        "device": device,
        "queries": len(query_spans),
        "query_s": sum(e - s for s, e in query_spans) * ns,
        "busy_s": busy_ns[device] * ns,
        "idle_s": idle * ns,
        "idle_by_phase_s": {n: v * ns for n, v in sorted(
            by_phase.items(), key=lambda kv: -kv[1])},
        "named_share": named / idle if idle else 0.0,
        "devices": {d: device_row(d) for d in sorted(ops)},
        "idle_by_cause_s": [cause_row(c, v) for c, v in sorted(
            by_cause.items(), key=lambda kv: -kv[1])],
        "longest_gaps": [gap_row(i) for i in longest],
    }


def _cmd_gaps(argv: List[str]) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        prog="spark_rapids_tpu.tools.trace gaps",
        description="The busiest device's idle time inside each query, by "
                    "the engine span the host was in and by cause: another "
                    "device's module, or the host span while every device "
                    "is idle (from a jax.profiler capture).")
    ap.add_argument("xplane", help="a .xplane.pb file of a jax.profiler "
                                   "capture taken while queries ran")
    ap.add_argument("--json", action="store_true",
                    help="emit machine-readable JSON")
    ns = ap.parse_args(argv)
    from jax.profiler import ProfileData
    got = idle_by_phase(ProfileData.from_file(ns.xplane))
    if got is None:
        print("no device plane with XLA ops, or no srt.query span, in "
              "this capture")
        return 1
    if ns.json:
        print(json.dumps(got, indent=2))
        return 0
    idle, n = got["idle_s"], got["queries"]

    def share(sec):
        return 100 * sec / idle if idle else 0.0

    print(f"{got['device']}: {n} queries, "
          f"{got['query_s']:.6f} s inside srt.query; device busy "
          f"{got['busy_s']:.6f} s, idle {got['idle_s']:.6f} s")
    print(f"idle seconds by the span the host was in "
          f"({100 * got['named_share']:.1f}% of them have a named cause):")
    for name, sec in got["idle_by_phase_s"].items():
        print(f"  {name:<22} {sec:12.6f} s {share(sec):6.2f}%"
              f" {sec / n:12.6f} s/query")
    print("devices (seconds inside srt.query; largest modules):")
    for d, row in got["devices"].items():
        top = ", ".join(f"{m} {sec:.6f}" for m, sec in
                        list(row["modules_s"].items())[:6])
        print(f"  {d:<16} busy {row['busy_s']:12.6f} s idle "
              f"{row['idle_s']:12.6f} s  {top}")
    print(f"idle seconds of {got['device']} by cause (another device's "
          f"module, else the host span), and the host span beside it:")
    rows = got["idle_by_cause_s"]
    for row in rows[:_CAUSE_ROWS]:
        host = row["host_span"] + ("" if row["host_device"] is None
                                   else f" [device {row['host_device']}]")
        print(f"  {row['cause']:<46} host in {host:<28} "
              f"{row['s']:12.6f} s {share(row['s']):6.2f}%")
    if len(rows) > _CAUSE_ROWS:
        rest = sum(r["s"] for r in rows[_CAUSE_ROWS:])
        print(f"  {len(rows) - _CAUSE_ROWS} more rows (--json has them)"
              f"{'':<52} {rest:12.6f} s {share(rest):6.2f}%")
    print(f"the {len(got['longest_gaps'])} longest gaps (offset in the "
          f"query, length, causes by share, the host span of the first):")
    for g in got["longest_gaps"]:
        args = " ".join(f"{k}={v}" for k, v in g["args"].items())
        causes = ", ".join(f"{c} {100 * v / g['length_s']:.0f}%"
                           for c, v in g["causes"].items())
        print(f"  at {g['offset_s']:10.6f} s  {g['length_s']:10.6f} s  "
              f"{causes}; host in {g['host_span']} {args}")
    return 0


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------
def _cmd_merge(argv: List[str]) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        prog="spark_rapids_tpu.tools.trace merge",
        description="Merge per-process Chrome traces into one "
                    "Perfetto-loadable timeline.")
    ap.add_argument("sources", nargs="+",
                    help="trace-*.json files and/or directories of them")
    ap.add_argument("-o", "--output", default="merged-trace.json")
    ap.add_argument("--trace-id", default=None,
                    help="keep only one query's spans")
    ns = ap.parse_args(argv)
    traces = load_process_traces(ns.sources)
    if not traces:
        print("no input traces found")
        return 1
    merged = merge_process_traces(traces, trace_id=ns.trace_id)
    with open(ns.output, "w", encoding="utf-8") as f:
        json.dump(merged, f)
    procs = merged["otherData"]["processes"]
    trunc = merged["otherData"]["truncated_processes"]
    print(f"merged {len(procs)} process traces "
          f"({sum(p['events'] for p in procs)} events) -> {ns.output}")
    for p in procs:
        flag = "  [TRUNCATED: %d spans dropped]" % p["dropped_events"] \
            if p["truncated"] else ""
        print(f"  pid {p['pid']}: {p['process_name']:<16} "
              f"role={p['role']:<10} offset={p['clock_offset_s']:+.6f}s "
              f"events={p['events']}{flag}")
    return 0


def _cmd_critical_path(argv: List[str]) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        prog="spark_rapids_tpu.tools.trace critical-path",
        description="Per-query critical-path attribution over a "
                    "(merged) Chrome trace.")
    ap.add_argument("trace", help="merged trace JSON")
    ap.add_argument("--trace-id", default=None,
                    help="query to attribute (default: every query span)")
    ap.add_argument("--json", action="store_true",
                    help="emit machine-readable JSON")
    ns = ap.parse_args(argv)
    with open(ns.trace, "r", encoding="utf-8") as f:
        doc = json.load(f)
    events = doc.get("traceEvents", [])
    tids = [ns.trace_id] if ns.trace_id else \
        [t for t, _ in query_trace_ids(events)]
    if not tids:
        print("no query spans with a trace_id found")
        return 1
    out = []
    for tid in tids:
        cp = critical_path(events, tid)
        if cp is None:
            continue
        out.append(cp)
    if ns.json:
        print(json.dumps([cp.to_dict() for cp in out], indent=2))
    else:
        for cp in out:
            print(cp.render())
            print()
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    import sys
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help"):
        print("usage: python -m spark_rapids_tpu.tools.trace "
              "{merge,critical-path,gaps} ...")
        return 0 if argv else 1
    cmd, rest = argv[0], argv[1:]
    if cmd == "merge":
        return _cmd_merge(rest)
    if cmd in ("critical-path", "critical_path"):
        return _cmd_critical_path(rest)
    if cmd == "gaps":
        return _cmd_gaps(rest)
    print(f"unknown subcommand: {cmd!r} "
          f"(expected merge | critical-path | gaps)")
    return 1


if __name__ == "__main__":
    raise SystemExit(main())
