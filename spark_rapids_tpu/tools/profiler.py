"""Profiling tool: per-operator wall time + runtime health report.

Reference: tools/ ProfileMain / Profiler (tools/.../profiling/Profiler.scala:
32,436) — replays Spark event logs into executor/app/SQL-metric reports plus
a HealthCheck. Standalone equivalent: wrap a live plan execution, time every
physical node, and fold in the runtime's own health signals (spill counts,
semaphore waits) — the data the reference mines from event logs, captured at
the source instead.
"""
from __future__ import annotations

import dataclasses
import json
import time
from typing import Dict, List, Optional

from ..conf import RapidsConf

__all__ = ["profile_query", "QueryProfile", "NodeStats", "instrument_plan",
           "registry_snapshot", "snapshot_node_metrics",
           "compute_self_times", "finalize_self_times"]


@dataclasses.dataclass
class NodeStats:
    name: str
    desc: str
    depth: int
    node_id: int = 0
    parent_id: int = -1
    wall_s: float = 0.0
    rows: int = 0
    batches: int = 0
    t_first: float = 0.0   # offset of first activity from query start
    t_last: float = 0.0    # offset of last activity
    # operator-metric snapshot (the node's MetricRegistry), captured after
    # the run by snapshot_node_metrics(); lands in event-log node records
    metrics: Dict = dataclasses.field(default_factory=dict)

    @property
    def self_s(self) -> float:
        """Wall time minus child production (set by finalize_self_times)."""
        return getattr(self, "_self_s", self.wall_s)


@dataclasses.dataclass
class QueryProfile:
    nodes: List[NodeStats]
    total_s: float
    spill: Dict
    semaphore: Dict
    # per-query deltas of the process-wide StatsRegistry counters: compile
    # cache, upload cache, shuffle tiers, catalog spills/OOM, semaphore —
    # one report with every subsystem's signal
    stats: Dict = dataclasses.field(default_factory=dict)
    # kernel-table entries this query touched (utils/compile_cache.py):
    # per-program compile wall + XLA cost/memory analysis, node-attributed
    kernels: List[Dict] = dataclasses.field(default_factory=list)

    TIMELINE_WIDTH = 20

    def _timeline(self, n: NodeStats) -> str:
        """Activity window of one operator as an ASCII bar over the query
        wall — column-aligned bars make operator overlap (pipelining vs
        serialization) visible at a glance."""
        w = self.TIMELINE_WIDTH
        if self.total_s <= 0 or n.batches == 0 or n.t_last < n.t_first:
            return " " * w
        lo = int(round(min(n.t_first, self.total_s) / self.total_s * w))
        hi = int(round(min(n.t_last, self.total_s) / self.total_s * w))
        lo = min(lo, w - 1)
        hi = max(hi, lo + 1)
        return "." * lo + "=" * (hi - lo) + "." * (w - hi)

    def summary(self) -> str:
        lines = [f"total wall time: {self.total_s:.4f}s", "",
                 f"{'op':<44}{'time_s':>9}{'rows':>12}{'batches':>9}"
                 f"  {'timeline':<{self.TIMELINE_WIDTH}}"]
        for n in self.nodes:
            label = ("  " * n.depth + n.name)[:43]
            lines.append(f"{label:<44}{n.wall_s:>9.4f}{n.rows:>12}"
                         f"{n.batches:>9}  {self._timeline(n)}")
        lines.append("")
        lines.append(f"spill: {self.spill}")
        lines.append(f"semaphore: {self.semaphore}")
        if self.stats:
            lines.append("counters (this query):")
            for k in sorted(self.stats):
                v = self.stats[k]
                if v:
                    lines.append(f"  {k:<44}{v}")
        return "\n".join(lines)

    def to_json(self) -> str:
        return json.dumps({
            "total_s": self.total_s,
            "nodes": [dataclasses.asdict(n) for n in self.nodes],
            "spill": self.spill,
            "semaphore": self.semaphore,
            "stats": self.stats,
            "kernels": self.kernels,
        })

    def health_check(self) -> List[str]:
        """Reference: HealthCheck — flag suspicious signals."""
        warnings = []
        if self.spill.get("spill_count"):
            sc = self.spill["spill_count"]
            if any(sc.values()):
                warnings.append(
                    f"device memory pressure: spills occurred ({sc}) — "
                    "consider a larger pool or smaller batch size")
        wait = self.semaphore.get("total_wait_time", 0.0)
        if self.total_s > 0 and wait > 0.25 * self.total_s:
            warnings.append(
                f"semaphore wait is {wait / self.total_s:.0%} of wall time — "
                "tasks are serialized on the chip; lower parallelism or raise "
                "concurrentGpuTasks")
        slowest = max(self.nodes, key=lambda n: n.wall_s, default=None)
        if slowest and self.total_s > 0 and slowest.wall_s > 0.8 * self.total_s:
            warnings.append(
                f"{slowest.name} dominates ({slowest.wall_s:.2f}s) — "
                "check its explain tagging for fallback reasons")
        compile_s = self.stats.get("compile_cache_compile_seconds", 0.0)
        if self.total_s > 0 and compile_s > 0.5 * self.total_s:
            warnings.append(
                f"XLA compile is {compile_s / self.total_s:.0%} of wall "
                "time — cold compile cache (warm up, or check for shape-"
                "bucket churn recompiling per batch)")
        if self.stats.get("catalog_oom_callback_errors", 0):
            warnings.append(
                "OOM cache-drop callbacks raised during this query — "
                "cached device bytes may not have been released "
                "(see catalog diagnostics)")
        return warnings


def instrument_plan(plan, epoch: Optional[float] = None,
                    annotate: bool = False,
                    into: Optional[List[NodeStats]] = None,
                    query_id: Optional[int] = None) -> List[NodeStats]:
    """Wrap every physical node's ``execute``/``execute_columnar`` in timers
    (shared by the live profiler and the event-log writer). ``annotate``
    additionally scopes each node's work in a
    ``jax.profiler.TraceAnnotation`` so XLA trace captures show query
    operators by name — the NvtxWithMetrics analogue (reference:
    NvtxWithMetrics.scala). ``into`` appends to an existing stats list with
    continuing node ids (AQE instruments each stage segment as it forms).
    ``query_id`` flows into the node-context scopes so process services
    (the compile-cache kernel table) can record which query first drove
    them."""
    stats: List[NodeStats] = [] if into is None else into
    if epoch is None:
        epoch = time.perf_counter()

    def wrap(node, depth: int, parent: int):
        ns = NodeStats(type(node).__name__,
                       getattr(node, "node_desc", lambda: "")(), depth,
                       node_id=len(stats), parent_id=parent)
        ns._node = node  # live reference for metric snapshots (not serialized)
        stats.append(ns)
        # wrap exactly one method per node: device execs route execute()
        # through execute_columnar(), so wrapping both would double-count
        from ..exec.base import TpuExec
        attrs = ("execute_columnar",) if isinstance(node, TpuExec) \
            else ("execute",)
        for attr in attrs:
            fn = getattr(node, attr, None)
            if fn is None:
                continue

            def timed(pidx, _fn=fn, _ns=ns, _node=node):
                import contextlib

                from ..utils import metrics as M
                from ..utils.node_context import node_scope
                from ..utils.tracing import get_tracer
                tracer = get_tracer()
                reg = getattr(_node, "metrics", None)
                scope = contextlib.nullcontext()
                if annotate:
                    import jax.profiler
                    scope = jax.profiler.TraceAnnotation(
                        f"{_ns.name}[{pidx}]")
                it = _fn(pidx)
                t0 = time.perf_counter()
                if not _ns.batches:
                    _ns.t_first = t0 - epoch
                try:
                    with scope:
                        while True:
                            # the node-context scope brackets each RESUME of
                            # the node's generator frame: process services
                            # (compile cache, spill path) attribute work to
                            # the innermost node driving them. A child
                            # resumed within pushes itself deeper, so the
                            # top of stack is always the executing node.
                            with node_scope(_ns.node_id, _ns.name, reg,
                                            query_id=query_id):
                                try:
                                    batch = next(it)
                                except StopIteration:
                                    break
                            now = time.perf_counter()
                            _ns.wall_s += now - t0
                            _ns.t_last = now - epoch
                            _ns.batches += 1
                            rows = int(batch.num_rows)
                            _ns.rows += rows
                            # operator-batch span: one complete event per
                            # batch produced (the query->stage->task->
                            # operator level of the span hierarchy)
                            tracer.complete(_ns.name, "operator", t0,
                                            now - t0, partition=pidx,
                                            rows=rows)
                            if reg is not None and hasattr(reg, "observe"):
                                reg.observe(M.BATCH_ROWS_HISTOGRAM, rows)
                            yield batch
                            t0 = time.perf_counter()
                finally:
                    now = time.perf_counter()
                    _ns.wall_s += now - t0
                    _ns.t_last = now - epoch

            setattr(node, attr, timed)

        # materializing nodes (exchanges) may be driven directly via
        # _materialize() by the AQE loop (plan/aqe.py materialize_stage)
        # instead of through their generator — time that path too, but
        # skip when re-entered from this node's own instrumented generator
        # (the generator timer already covers it)
        mat = getattr(node, "_materialize", None)
        if callable(mat):
            def timed_mat(_fn=mat, _ns=ns, _node=node):
                from ..utils.node_context import current, node_scope
                ctx = current()
                if ctx is not None and ctx.node_id == _ns.node_id:
                    return _fn()  # inside our own timed generator
                reg = getattr(_node, "metrics", None)
                t0 = time.perf_counter()
                if not _ns.batches and not _ns.wall_s:
                    _ns.t_first = t0 - epoch
                try:
                    with node_scope(_ns.node_id, _ns.name, reg,
                                    query_id=query_id):
                        return _fn()
                finally:
                    now = time.perf_counter()
                    _ns.wall_s += now - t0
                    _ns.t_last = now - epoch

            setattr(node, "_materialize", timed_mat)
        me = ns.node_id
        for c in node.children:
            wrap(c, depth + 1, me)

    wrap(plan, 0, -1)
    return stats


def registry_snapshot(node) -> Dict:
    """A node's operator-metric snapshot with zero values dropped — the
    ONE filtering rule shared by the event-log node records and
    QueryProfile, so both report identical metrics for the same query."""
    reg = getattr(node, "metrics", None)
    if reg is None or not hasattr(reg, "snapshot"):
        return {}
    return {k: v for k, v in reg.snapshot().items() if v}


def snapshot_node_metrics(stats: List[NodeStats]) -> None:
    """Fold each live node's MetricRegistry into its NodeStats (call after
    the run)."""
    for ns in stats:
        ns.metrics = registry_snapshot(getattr(ns, "_node", None))


def compute_self_times(nodes) -> Dict[int, float]:
    """Per-node SELF time (wall minus direct children's wall), keyed by
    node_id. ``nodes`` are NodeStats or event-log node dicts.

    An operator's timed window includes pulling from its children (the
    generators nest), so wall_s alone over-attributes upstream cost; self
    time is the ONE attribution rule EXPLAIN ANALYZE percentages and the
    diagnose tool both rank by."""
    def get(n, k, default=0.0):
        # dicts may come from old event logs with keys missing
        return n.get(k, default) if isinstance(n, dict) else getattr(n, k)

    child_wall: Dict[int, float] = {}
    for n in nodes:
        parent = get(n, "parent_id", -1)
        if parent >= 0:
            child_wall[parent] = child_wall.get(parent, 0.0) \
                + get(n, "wall_s")
    return {get(n, "node_id"):
            max(0.0, get(n, "wall_s") - child_wall.get(get(n, "node_id"),
                                                       0.0))
            for n in nodes}


def finalize_self_times(stats: List[NodeStats]) -> None:
    """Attach ``self_s`` to each NodeStats (see compute_self_times)."""
    self_s = compute_self_times(stats)
    for ns in stats:
        ns._self_s = self_s[ns.node_id]


def profile_query(df, device: Optional[bool] = None,
                  xla_trace_dir: Optional[str] = None) -> QueryProfile:
    """Execute ``df.collect(device=...)`` with every physical node's
    ``execute``/``execute_columnar`` wrapped in timers. With
    ``xla_trace_dir`` the whole execution also runs under
    ``jax.profiler.trace`` with per-operator TraceAnnotations, producing a
    TensorBoard-loadable XLA trace."""
    from ..memory.catalog import get_catalog
    from ..memory.semaphore import get_semaphore
    from ..utils.compile_cache import kernel_seq, kernels_since
    from ..utils.memprof import active as memprof_active
    from ..utils.metrics import StatsRegistry, get_stats
    from ..utils.tracing import get_tracer

    plan = df.session._physical(df.logical, device)
    annotate = xla_trace_dir is not None
    stats: List[NodeStats] = []
    epoch = time.perf_counter()
    from ..plan.aqe import AdaptiveExec
    if isinstance(plan, AdaptiveExec):
        # AQE finalizes lazily: instrument each stage segment + the final
        # segment as the adaptive loop creates them
        plan._instrument_hook = \
            lambda p: instrument_plan(p, epoch, annotate, into=stats)
    else:
        instrument_plan(plan, epoch, annotate, into=stats)
    # snapshot the process-global counters so the report shows THIS query's
    # deltas, not lifetime totals
    cat = get_catalog()
    sem = get_semaphore()
    registry = get_stats()
    spill_before = dict(cat.spill_count)
    bytes_before = dict(cat.spilled_bytes)
    wait_before = sem.total_wait_time
    acq_before = sem.acquire_count
    counters_before = registry.collect()
    kseq_before = kernel_seq()
    # profiled runs share query_id=None in the node contexts — drop any
    # stale per-operator memory aggregation from a previous profile so
    # node_peaks() below reflects only THIS run
    mp = memprof_active()
    if mp is not None:
        mp.begin_query(None)

    if xla_trace_dir is not None:
        import jax.profiler
        t0 = time.perf_counter()
        with jax.profiler.trace(xla_trace_dir), \
                get_tracer().query(profiled=True):
            plan.collect()
        total = time.perf_counter() - t0
    else:
        t0 = time.perf_counter()
        with get_tracer().query(profiled=True):
            plan.collect()
        total = time.perf_counter() - t0

    spill = {
        "spill_count": {str(k): v - spill_before.get(k, 0)
                        for k, v in cat.spill_count.items()},
        "spilled_bytes": {str(k): v - bytes_before.get(k, 0)
                          for k, v in cat.spilled_bytes.items()},
    }
    # single-use profiled plan: close its spill-registered outputs now
    # (same query-end release the session collect path performs)
    plan.release_spill_handles()
    semaphore = {"total_wait_time": sem.total_wait_time - wait_before,
                 "acquire_count": sem.acquire_count - acq_before}
    counters = StatsRegistry.delta(registry.collect(), counters_before)
    snapshot_node_metrics(stats)
    # fold per-node peak HBM from the memory flight recorder into the
    # metric snapshots: EXPLAIN ANALYZE renders it as the peakDevMemory
    # column (plan/meta.py render order)
    if mp is not None:
        from ..utils.metrics import PEAK_DEVICE_MEMORY
        peaks = mp.node_peaks(None)
        for ns in stats:
            if peaks.get(ns.node_id):
                ns.metrics[PEAK_DEVICE_MEMORY] = peaks[ns.node_id]
    finalize_self_times(stats)
    return QueryProfile(stats, total, spill, semaphore, counters,
                        kernels=kernels_since(kseq_before))
