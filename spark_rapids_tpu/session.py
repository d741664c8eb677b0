"""TpuSession + DataFrame — the user entry point.

Plays the combined role of SparkSession + the plugin lifecycle
(reference: Plugin.scala RapidsDriverPlugin/RapidsExecutorPlugin): holds the
RapidsConf, initializes the device runtime (semaphore, memory), and drives
logical -> physical -> overrides -> execution.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Union

import pyarrow as pa

from .conf import RapidsConf
from .columnar.host import HostTable
from .expr.base import Expression
from .expr.functions import Column, SortOrder, _to_expr
from .plan.logical import (LogicalAggregate, LogicalFilter, LogicalJoin,
                           LogicalLimit, LogicalPlan, LogicalProject,
                           LogicalRange, LogicalScan, LogicalSort,
                           LogicalUnion)
from .plan.overrides import apply_overrides, explain_plan
from .plan.physical import PhysicalPlan
from .plan.planner import plan_physical
from .plan.schema import Schema

__all__ = ["TpuSession", "DataFrame"]

# per-process sequence for trace dump filenames (pid+timestamp alone can
# collide when two sessions close within the same millisecond)
import itertools as _itertools

_TRACE_DUMP_SEQ = _itertools.count()


class TpuSession:
    _active: "Optional[TpuSession]" = None

    def __init__(self, conf: Optional[Union[RapidsConf, Dict]] = None):
        if isinstance(conf, dict):
            conf = RapidsConf(conf)
        self.conf = conf or RapidsConf()
        self._mesh = None
        # apply spark.rapids.tpu.trace.* to the process tracer (spans from
        # every subsystem land in one ring buffer; close() can export it)
        from .utils.tracing import configure_tracer
        configure_tracer(self.conf)
        # apply spark.rapids.tpu.metrics.* to the compile cache's kernel
        # table (XLA cost/memory introspection depth)
        from .utils.compile_cache import configure_introspection
        configure_introspection(self.conf)
        # canonical shape-bucket ladder (spark.rapids.tpu.shapeBuckets.*):
        # one process-wide policy instead of per-node bucket defaults, so
        # repeated queries land on repeatable XLA shapes
        from .columnar.device import configure_buckets
        configure_buckets(self.conf)
        # persistent compilation tier (spark.rapids.tpu.compile.*): where
        # XLA's disk cache lives
        from .utils.compile_cache import configure_compile_cache
        configure_compile_cache(self.conf)
        # apply spark.rapids.tpu.pipeline.* to the pipelined executor
        # (prefetch depth / task pool; parallel/pipeline.py)
        from .parallel.pipeline import configure_pipeline
        configure_pipeline(self.conf)
        # apply spark.rapids.tpu.debug.* to the columnar layer
        # (gather all-valid guard; columnar/device.py)
        from .columnar.device import configure_debug
        configure_debug(self.conf)
        # async-first execution (spark.rapids.tpu.async.enabled): deferred
        # scalar resolution + bulk per-drain downloads, or the sync-forcing
        # debug mode (columnar/device.py DeferredScalar/to_host_batched)
        from .columnar.device import configure_async
        configure_async(self.conf)
        # memory flight recorder (spark.rapids.tpu.memory.profile.*):
        # buffer-lifecycle attribution, leak scans and OOM postmortems
        # (utils/memprof.py; the catalog emits into it)
        from .utils.memprof import configure_memprof
        configure_memprof(self.conf)
        # fault injection (spark.rapids.tpu.faults.*): install or clear
        # the process-wide injector behind the named fault points
        # (utils/faults.py); None/no-op unless faults.enabled
        from .utils.faults import configure_faults
        configure_faults(self.conf)
        # data-movement observatory (spark.rapids.tpu.movement.*): install
        # or clear the process-wide host<->device transfer ledger behind the
        # engine's D2H/H2D funnels (utils/movement.py); None/no-op unless
        # movement.enabled
        from .utils.movement import configure_movement
        configure_movement(self.conf)
        # shuffle & collective observatory (spark.rapids.tpu.shuffle.
        # telemetry.*): install or clear the process-wide per-tier
        # transfer ledger behind the shuffle chokepoints
        # (shuffle/telemetry.py); None/no-op unless telemetry.enabled
        from .shuffle.telemetry import configure_shuffle_telemetry
        configure_shuffle_telemetry(self.conf)
        # structured OOM retry (spark.rapids.tpu.oom.*): escalation-ladder
        # bounds + HBM pressure arbitration (memory/retry.py)
        from .memory.retry import configure_oom_retry
        configure_oom_retry(self.conf)
        # runtime degradation (spark.rapids.tpu.fallback.*): host-fallback
        # boundary + operator quarantine store (exec/fallback.py); loads
        # the persisted quarantine.json so past failures route at plan time
        from .exec.fallback import configure_fallback
        configure_fallback(self.conf)
        # live health subsystem: watchdog monitor thread + optional HTTP
        # status endpoints (utils/health.py + tools/statusd.py); None when
        # health.enabled is false and health.port < 0 (the default)
        from .utils.health import configure_health
        self._health = configure_health(
            self.conf, eventlog_fn=lambda: getattr(self, "_eventlog", None))
        TpuSession._active = self

    # -- device mesh (accelerated shuffle tier) ------------------------------
    def attach_mesh(self, mesh) -> "TpuSession":
        """Attach a jax.sharding.Mesh; hash exchanges then run as on-device
        ICI all-to-all (exec/exchange.py) instead of the host-staged tier."""
        self._mesh = mesh
        return self

    def shuffle_mesh(self):
        """The mesh the planner may exchange over, or None for host shuffle.

        Mode 'host' disables the device tier; 'ici' builds a 1-D mesh over
        all addressable devices on first use; 'auto' uses whatever mesh the
        user attached (reference: choosing RapidsShuffleManager vs default
        Spark shuffle is likewise an explicit deployment decision)."""
        from .exec.exchange import SHUFFLE_MODE
        mode = self.conf.get(SHUFFLE_MODE)
        if mode == "host":
            return None
        if self._mesh is None and mode == "ici":
            from .parallel.mesh import data_parallel_mesh
            self._mesh = data_parallel_mesh()
        if self._mesh is not None and self._mesh.size < 2:
            return None
        return self._mesh

    # -- data sources --------------------------------------------------------
    def create_dataframe(self, data, schema=None, num_partitions: int = 1
                         ) -> "DataFrame":
        from .io.memory import InMemorySource
        if isinstance(data, pa.Table):
            table = data
        elif isinstance(data, dict):
            table = pa.table(data)
        elif isinstance(data, HostTable):
            table = data.to_arrow()
        else:  # pandas
            table = pa.Table.from_pandas(data, preserve_index=False)
        return DataFrame(self, LogicalScan(InMemorySource(table, num_partitions)))

    def read_parquet(self, paths, num_partitions: Optional[int] = None
                     ) -> "DataFrame":
        from .io.parquet import ParquetSource
        return DataFrame(self, LogicalScan(
            ParquetSource(paths, self.conf, num_partitions)))

    def read_csv(self, paths, schema=None, header: bool = True, sep: str = ",",
                 num_partitions: Optional[int] = None) -> "DataFrame":
        from .io.csv import CsvSource
        return DataFrame(self, LogicalScan(
            CsvSource(paths, self.conf, schema=schema, header=header, sep=sep,
                      num_partitions=num_partitions)))

    def read_json(self, paths, num_partitions: Optional[int] = None
                  ) -> "DataFrame":
        from .io.json import JsonSource
        return DataFrame(self, LogicalScan(
            JsonSource(paths, self.conf, num_partitions=num_partitions)))

    def read_orc(self, paths, num_partitions: Optional[int] = None
                 ) -> "DataFrame":
        from .io.orc import OrcSource
        return DataFrame(self, LogicalScan(
            OrcSource(paths, self.conf, num_partitions=num_partitions)))

    def range(self, start: int, end: Optional[int] = None, step: int = 1,
              num_partitions: int = 1) -> "DataFrame":
        if end is None:
            start, end = 0, start
        return DataFrame(self, LogicalRange(start, end, step, num_partitions))

    # -- execution -----------------------------------------------------------
    def _physical(self, logical: LogicalPlan,
                  device: Optional[bool] = None) -> PhysicalPlan:
        from .utils.tracing import get_tracer
        with get_tracer().span("plan", "plan"):
            return self._plan_physical(logical, device)

    def _plan_physical(self, logical: LogicalPlan,
                       device: Optional[bool]) -> PhysicalPlan:
        # the executing session is the active one (mesh discovery); conf-
        # sensitive expressions are BOUND at plan time below so lazily
        # consumed iterators keep this session's semantics even if another
        # session plans meanwhile
        TpuSession._active = self
        cpu = plan_physical(logical, self.conf)
        use_device = self.conf.is_sql_enabled if device is None else device
        if self.conf.is_explain_only:
            # reference: spark.rapids.sql.mode=explainOnly (RapidsConf.scala:515)
            # — tag & report what would run on device, execute on the host
            # engine only (ExplainPlan.explainPotentialGpuPlan). Printed
            # BEFORE the bind pass: binding executes scalar subqueries, and
            # the explain output must not wait on (or be blamed for) that.
            if self.conf.explain != "NONE":
                print(explain_plan(cpu, self.conf))
            use_device = False
        _bind_conf_exprs(cpu, self.conf, self, device)
        if not use_device:
            # UDF compilation is engine-independent (the compiled expression
            # tree also runs on the host engine) — apply it here too so the
            # CPU path matches the reference's resolution-rule placement
            from .udf import UDF_COMPILER_ENABLED, compile_plan_udfs
            if self.conf.get(UDF_COMPILER_ENABLED):
                compile_plan_udfs(cpu)
            return cpu
        from .plan.aqe import AQE_ENABLED, AdaptiveExec
        from .plan.physical import ShuffleExchangeExec
        if self.conf.get(AQE_ENABLED) \
                and any(isinstance(n, ShuffleExchangeExec)
                        for n in _walk_plan(cpu)):
            # adaptive: stages materialize + re-plan at exchange boundaries
            # (reference: GpuQueryStagePrepOverrides on AdaptiveSparkPlanExec)
            return AdaptiveExec(cpu, self.conf, use_device=True)
        return apply_overrides(cpu, self.conf)

    def last_query_phases(self) -> Optional[Dict]:
        """Where the host time of this session's last ``collect()`` went:
        its wall, and per phase span (``plan``, ``scan.read``, ``h2d``,
        ``dispatch``, ``sync``, ``d2h``, ``result``, ...) calls, seconds
        of self time and bytes — docs/observability.md "Tracer". None
        before the first query."""
        query = getattr(self, "_last_query", None)
        return None if query is None else query.to_dict()

    def set_conf(self, key: str, value) -> "TpuSession":
        self.conf = self.conf.set(key, value)
        return self

    # -- event log (reference: Spark event logs consumed by the plugin's
    # profiling tools; here the session writes its own JSONL log that
    # tools/eventlog.py replays) ------------------------------------------
    def _event_logger(self):
        from .tools.eventlog import EVENT_LOG_DIR, EventLogWriter
        directory = self.conf.get(EVENT_LOG_DIR)
        if not directory:
            return None
        if getattr(self, "_eventlog", None) is None:
            import os
            import time as _time
            app_id = f"app-{os.getpid()}-{int(_time.time() * 1000)}"
            snap = {k: repr(v) for k, v in self.conf._values.items()}
            self._eventlog = EventLogWriter(directory, app_id, snap)
        return self._eventlog

    def health_status(self) -> Dict:
        """The live /status snapshot as a dict (works whether or not the
        monitor thread / HTTP server are running)."""
        health = getattr(self, "_health", None)
        if health is not None:
            return health.monitor.snapshot()
        from .utils.health import HealthMonitor
        return HealthMonitor(self.conf).snapshot()

    def close(self) -> None:
        # stop the health subsystem FIRST: its monitor thread writes
        # heartbeats into the event log closed below, and its HTTP server
        # snapshots the runtime being shut down
        health = getattr(self, "_health", None)
        if health is not None:
            health.close()
            self._health = None
        # flush the operator-quarantine store beside the compile cache so
        # the NEXT session plans known-bad operators on host
        from .exec.fallback import persist_quarantine
        persist_quarantine()
        # cancel + join any straggling pipeline prefetch workers (queries
        # that drained fully already left none; this is the abandoned-
        # iterator backstop, and the no-leaked-threads test contract)
        from .parallel.pipeline import shutdown_workers
        shutdown_workers()
        log = getattr(self, "_eventlog", None)
        log_path = log.path if log is not None else None
        if log is not None:
            log.close()
            self._eventlog = None
        from .utils.tracing import (TRACE_DIR, TRACE_DISTRIBUTED_DIR,
                                    get_tracer)
        dist_dir = self.conf.get(TRACE_DISTRIBUTED_DIR)
        if dist_dir and get_tracer().enabled:
            # one trace-<process_name>.json per process (workers dump
            # theirs in _worker_main) — the input set for
            # `python -m spark_rapids_tpu.tools.trace merge`
            import os
            tracer = get_tracer()
            tracer.dump(os.path.join(
                dist_dir, f"trace-{tracer.process_name}.json"))
        trace_artifacts = []
        trace_dir = self.conf.get(TRACE_DIR)
        if trace_dir:
            import os
            tracer = get_tracer()
            if not tracer.enabled and not tracer.events():
                import warnings
                warnings.warn(
                    "spark.rapids.tpu.trace.dir is set but tracing never "
                    "ran — set spark.rapids.tpu.trace.enabled=true",
                    RuntimeWarning)
            else:
                seq = next(_TRACE_DUMP_SEQ)
                path = os.path.join(
                    trace_dir, f"trace-{os.getpid()}-{seq}.json")
                tracer.dump(path)
                trace_artifacts.append(path)
        # persistent history: append this run LAST — the event log is
        # flushed and the trace artifact (if any) exists, so the stored
        # run is complete. Opt-in via spark.rapids.tpu.history.dir.
        self._history_append(log_path, trace_artifacts)

    def _history_append(self, log_path, artifacts) -> None:
        from .tools.history import HISTORY_DIR
        root = self.conf.get(HISTORY_DIR)
        if not root or not log_path:
            return
        try:
            from .tools.history import HistoryStore
            HistoryStore(root).append_run(log_path, artifacts=artifacts)
        except Exception as e:  # history must never fail close
            import warnings
            warnings.warn(f"history store append failed: {e}",
                          RuntimeWarning)


class DataFrame:
    def __init__(self, session: TpuSession, logical: LogicalPlan):
        self.session = session
        self.logical = logical

    @property
    def schema(self) -> Schema:
        return self.logical.schema

    @property
    def columns(self) -> List[str]:
        return self.schema.names

    # -- transformations -----------------------------------------------------
    def select(self, *cols) -> "DataFrame":
        exprs = [self._col_expr(c) for c in cols]
        gen = self._split_generator(exprs)
        if gen is not None:
            return gen
        return self._project_with_windows(exprs)

    def _split_generator(self, exprs: List[Expression]):
        """select(..., explode(arr).alias(x), ...) -> Generate + project
        (Spark allows one generator per select clause)."""
        from .expr.base import Alias, AttributeReference
        from .expr.collections import Explode
        from .plan.logical import LogicalGenerate

        def top_gen(e):
            if isinstance(e, Explode):
                return e, None
            if isinstance(e, Alias) and isinstance(e.child, Explode):
                return e.child, e.name
            return None, None

        hits = [(i, *top_gen(e)) for i, e in enumerate(exprs)]
        hits = [(i, g, a) for i, g, a in hits if g is not None]
        if not hits:
            return None
        if len(hits) > 1:
            raise ValueError("only one generator (explode/posexplode) is "
                             "allowed per select clause")
        i, gen, alias = hits[0]
        # generate under INTERNAL names so a user alias may legally shadow a
        # source column (the final projection drops the original)
        probe = LogicalGenerate(self.logical, gen, outer=False)
        defaults = [n for n, _, _ in probe.gen_fields]
        if alias is not None and len(defaults) != 1:
            raise ValueError(
                f"generator yields {len(defaults)} columns "
                f"({defaults}); a single alias cannot name them")
        internals = [f"__gen{j}_{n}" for j, n in enumerate(defaults)]
        base = LogicalGenerate(self.logical, gen, outer=False,
                               aliases=internals)
        out = [Alias(AttributeReference(int_n),
                     alias if alias is not None and len(defaults) == 1 else n)
               for int_n, n in zip(internals, defaults)]
        final: List[Expression] = list(exprs)
        final[i:i + 1] = out
        # remaining exprs may contain window expressions — route through the
        # same splitter plain select uses
        return DataFrame(self.session, base)._project_with_windows(final)

    def _project_with_windows(self, exprs: List[Expression]) -> "DataFrame":
        """Pull top-level window expressions into stacked LogicalWindow nodes
        (reference: GpuWindowExec meta splitting pre/post projections)."""
        from .expr.base import Alias, AttributeReference
        from .expr.window import WindowExpression
        from .plan.logical import LogicalWindow

        def top_window(e):
            if isinstance(e, WindowExpression):
                return e
            if isinstance(e, Alias) and isinstance(e.child, WindowExpression):
                return e.child
            return None

        win_items = []
        final_exprs: List[Expression] = []
        for i, e in enumerate(exprs):
            w = top_window(e)
            if w is None:
                if any(isinstance(x, WindowExpression)
                       for x in _walk_expr(e)):
                    raise NotImplementedError(
                        "window expressions nested inside other expressions "
                        "are not supported yet; alias the window column first")
                final_exprs.append(e)
            else:
                # internal name avoids collisions when the window column
                # overwrites an existing column (with_column("x", ...over(w)))
                target = e.name if isinstance(e, Alias) else f"_w{i}"
                internal = f"__win{i}_{target}"
                win_items.append((internal, w))
                final_exprs.append(Alias(AttributeReference(internal), target))
        if not win_items:
            return DataFrame(self.session,
                             LogicalProject(self.logical, exprs))
        # group by identical (partition, order) spec to share one sort each
        base = self.logical
        groups = {}
        for name, w in win_items:
            key = (tuple(repr(p) for p in w.spec.partition_exprs),
                   tuple((repr(o.expr), o.ascending, o.nulls_first)
                         for o in w.spec.orders))
            groups.setdefault(key, []).append((name, w))
        for _, items in groups.items():
            base = LogicalWindow(base, items)
        return DataFrame(self.session, LogicalProject(base, final_exprs))

    def with_column(self, name: str, c) -> "DataFrame":
        from .expr.base import Alias, AttributeReference
        exprs: List[Expression] = [
            AttributeReference(n) for n in self.schema.names if n != name]
        exprs.append(Alias(_to_expr(c), name))
        return self._project_with_windows(exprs)

    def filter(self, cond) -> "DataFrame":
        return DataFrame(self.session,
                         LogicalFilter(self.logical, _to_expr(cond)))

    where = filter

    def group_by(self, *cols) -> "GroupedData":
        return GroupedData(self, [self._col_expr(c) for c in cols])

    groupBy = group_by

    def rollup(self, *cols) -> "GroupedData":
        """Hierarchical grouping sets: rollup(a, b) aggregates by (a, b),
        (a), and () — lowered through an Expand node (reference:
        GpuExpandExec.scala; ExpandExec rule in GpuOverrides.scala)."""
        names = self._grouping_names(cols)
        sets = [names[:i] for i in range(len(names), -1, -1)]
        return GroupedData(self, [self._col_expr(c) for c in cols],
                           grouping_sets=sets)

    def cube(self, *cols) -> "GroupedData":
        """All 2^k grouping-set combinations of the given columns."""
        import itertools
        names = self._grouping_names(cols)
        sets = []
        for r in range(len(names), -1, -1):
            sets.extend(list(c) for c in itertools.combinations(names, r))
        return GroupedData(self, [self._col_expr(c) for c in cols],
                           grouping_sets=sets)

    def grouping_sets(self, sets, *cols) -> "GroupedData":
        """Explicit GROUPING SETS over ``cols``; each entry of ``sets`` is a
        list of column names drawn from ``cols``."""
        names = self._grouping_names(cols)
        for s in sets:
            unknown = set(s) - set(names)
            if unknown:
                raise ValueError(f"grouping set references {unknown} "
                                 f"not in grouping columns {names}")
        return GroupedData(self, [self._col_expr(c) for c in cols],
                           grouping_sets=[list(s) for s in sets])

    def _grouping_names(self, cols):
        names = []
        for c in cols:
            e = self._col_expr(c)
            from .expr.base import AttributeReference
            if isinstance(e, AttributeReference):
                names.append(e.column_name)
            else:
                raise TypeError(
                    "rollup/cube/grouping_sets take column references, "
                    f"got {e!r} (pre-project expressions with select())")
        return names

    def agg(self, *aggs) -> "DataFrame":
        return GroupedData(self, []).agg(*aggs)

    def sample(self, fraction: float, seed=None) -> "DataFrame":
        """Deterministic Bernoulli row sample (reference: SampleExec /
        GpuPoissonSampler). Same seed -> same rows on device and host."""
        from .plan.logical import LogicalSample
        if seed is None:
            import random as _random
            seed = _random.randrange(2 ** 31)
        return DataFrame(self.session,
                         LogicalSample(self.logical, fraction, seed))

    def sort(self, *orders, ascending: bool = True) -> "DataFrame":
        sos = []
        for o in orders:
            if isinstance(o, SortOrder):
                sos.append(o)
            elif isinstance(o, Column):
                sos.append(SortOrder(o.expr, ascending))
            else:
                sos.append(SortOrder(_to_expr(_as_col(o)), ascending))
        return DataFrame(self.session, LogicalSort(self.logical, sos, True))

    order_by = sort
    orderBy = sort

    def cache(self) -> "DataFrame":
        from .plan.logical import LogicalCache
        return DataFrame(self.session, LogicalCache(self.logical))

    def limit(self, n: int) -> "DataFrame":
        return DataFrame(self.session, LogicalLimit(self.logical, n))

    def map_in_pandas(self, fn, schema) -> "DataFrame":
        """Apply ``fn(iterator_of_pandas_DataFrames) -> iterator of pandas
        DataFrames`` per batch with a declared output schema (PySpark
        mapInPandas; reference: GpuMapInPandasExec keeps the surrounding
        plan columnar around the Python bridge). ``schema`` is a dict of
        column name -> DataType."""
        from .plan.logical import LogicalMapInPandas
        from .plan.schema import Field, Schema
        out = Schema([Field(n, d, True) for n, d in schema.items()])
        return DataFrame(self.session,
                         LogicalMapInPandas(self.logical, fn, out))

    mapInPandas = map_in_pandas

    def explode(self, c, *aliases, outer: bool = False,
                pos: bool = False) -> "DataFrame":
        """Append explode/posexplode output columns (reference:
        GpuGenerateExec). ``outer=True`` keeps rows with null/empty input."""
        from .expr.collections import Explode, PosExplode
        from .plan.logical import LogicalGenerate
        e = self._col_expr(c)
        gen = PosExplode(e) if pos else Explode(e)
        return DataFrame(self.session,
                         LogicalGenerate(self.logical, gen, outer,
                                         list(aliases) or None))

    def distinct(self) -> "DataFrame":
        """Row dedup = zero-aggregate group-by over all columns (the planner
        lowers it to the grouped-aggregate exec's key dedup)."""
        return DataFrame(self.session,
                         LogicalAggregate(self.logical,
                                          [self._col_expr(n) for n in self.columns],
                                          []))

    def union(self, other: "DataFrame") -> "DataFrame":
        return DataFrame(self.session, LogicalUnion([self.logical, other.logical]))

    union_all = union

    def join(self, other: "DataFrame", on=None, how: str = "inner",
             condition=None) -> "DataFrame":
        if isinstance(on, str):
            on = [on]
        cond = _to_expr(condition) if condition is not None else None
        return DataFrame(self.session,
                         LogicalJoin(self.logical, other.logical, on, cond, how))

    def cross_join(self, other: "DataFrame") -> "DataFrame":
        return DataFrame(self.session,
                         LogicalJoin(self.logical, other.logical, None, None,
                                     "cross"))

    def _col_expr(self, c) -> Expression:
        return _to_expr(_as_col(c))

    # -- actions -------------------------------------------------------------
    def collect(self, device: Optional[bool] = None) -> pa.Table:
        from .utils.tracing import get_tracer
        logger = self.session._event_logger()
        # the root span of the query: every span of this collect() carries
        # its query id, and its phase totals (plan / scan / h2d / dispatch
        # / sync / d2h / result) land in tracer.recent_queries(). With an
        # event log the query also gets the log's id and a TraceContext
        # that crosses process boundaries.
        if logger is None:
            with get_tracer().query() as root:
                return self._collect_in(root, device, None, None, None)
        qid, tctx = logger.begin_query()
        try:
            with get_tracer().query(tctx, query_id=qid) as root:
                return self._collect_in(root, device, logger, qid, tctx)
        finally:
            # after the root closed: the record's critical path reads the
            # root span from the tracer's ring
            logger.end_query(qid, tctx)

    def _collect_in(self, root, device, logger, qid, tctx) -> pa.Table:
        from .parallel.pipeline import pipelined_collect
        from .utils.deadline import QUERY_TIMEOUT, deadline_scope
        from .utils.health import HEALTH_REPORT_DIR
        from .utils.tracing import get_tracer

        session = self.session
        session._last_query = root.summary
        plan = session._physical(self.logical, device)

        # pipelined executor: partitions drain concurrently under
        # TpuSemaphore admission (parallel/pipeline.py); sequential
        # PhysicalPlan.collect when pipeline.enabled=false or 1 partition
        def run():
            return pipelined_collect(plan, session.conf)

        try:
            # query deadline (spark.rapids.tpu.query.timeoutSeconds):
            # cooperative cancellation checkpoints across the retry
            # ladder, the arbitration gate and the pipeline raise a
            # structured QueryTimeoutError past the deadline (no-op scope
            # when the timeout is 0)
            with deadline_scope(
                    session.conf.get(QUERY_TIMEOUT),
                    report_dir=session.conf.get(HEALTH_REPORT_DIR)):
                table = run() if logger is None \
                    else logger.log_query(plan, run, qid, tctx)
            with get_tracer().span("result", "result"):
                return table.to_arrow()
        finally:
            # the plan is single-use (re-planned per collect): close its
            # spill-registered outputs now instead of waiting on GC — the
            # compile cache can pin plan nodes in kernel closures, which
            # would hold shuffle/broadcast HBM across queries (flagged by
            # the memory flight recorder's leak gate)
            plan.release_spill_handles()

    def to_pandas(self, device: Optional[bool] = None):
        return self.collect(device).to_pandas()

    # -- ML-framework handoff (reference: ColumnarRdd.scala:42,51 +
    # InternalColumnarRddConverter — zero-copy DataFrame -> device tables
    # for XGBoost-style consumers; here DataFrame -> jax.Array) ------------
    def _batches_from_plan(self, plan, pidx: int):
        from .exec.transitions import DeviceToHostExec
        from .columnar.device import DeviceTable as _DT
        from .plan.aqe import AdaptiveExec
        if isinstance(plan, AdaptiveExec):
            plan = plan.final_plan()
        if isinstance(plan, DeviceToHostExec):
            yield from plan.child.execute_columnar(pidx)
            return
        # plan fell back to host: upload each host batch
        mb = self.session.conf.min_bucket_rows
        for ht in plan.execute(pidx):
            yield _DT.from_host(ht, mb)

    def _device_plan(self):
        """Physical device plan, cached per conf snapshot (planning is
        pure given logical+conf, so iterating partitions must not re-plan)."""
        cached = getattr(self, "_dev_plan_cache", None)
        if cached is not None and cached[0] is self.session.conf:
            return cached[1]
        plan = self.session._physical(self.logical, True)
        self._dev_plan_cache = (self.session.conf, plan)
        return plan

    def to_device_batches(self, pidx: int):
        """Iterator of DeviceTable batches for one partition — the
        ColumnarRdd analogue: results stay on device, no host round trip."""
        yield from self._batches_from_plan(self._device_plan(), pidx)

    def num_partitions(self) -> int:
        return self._device_plan().num_partitions

    def to_jax(self, columns=None, allow_nulls: bool = False):
        """Materialize as a dict of ``jax.Array``s sliced to the exact row
        count (device-resident; feeds jax ML training directly).

        Numeric/bool/date/timestamp columns map to one array each; decimal
        columns unscale to float64; string columns map to
        ``(bytes_matrix, lengths)``. Raises on null values unless
        ``allow_nulls`` (then a ``<name>__validity`` mask is added).
        """
        from .columnar import dtypes as dt_
        from .columnar.device import concat_device_tables, shrink_to_fit
        plan = self.session._physical(self.logical, True)   # plan ONCE
        batches = []
        for p in range(plan.num_partitions):
            batches.extend(self._batches_from_plan(plan, p))
        if not batches:
            raise ValueError("empty DataFrame")
        table = concat_device_tables(batches) if len(batches) > 1 \
            else batches[0].compact()
        table = shrink_to_fit(table, self.session.conf.min_bucket_rows)
        n = int(table.num_rows)
        import numpy as _np
        out = {}
        for name, c in zip(table.names, table.columns):
            if columns is not None and name not in columns:
                continue
            valid = _np.asarray(c.validity[:n])
            if not valid.all():
                if not allow_nulls:
                    raise ValueError(
                        f"column {name!r} contains nulls; pass "
                        "allow_nulls=True to receive a validity mask")
                out[f"{name}__validity"] = c.validity[:n]
            if isinstance(c.dtype, (dt_.StringType, dt_.BinaryType)):
                out[name] = (c.data[:n], c.lengths[:n])
            elif isinstance(c.dtype, dt_.DecimalType):
                # device decimals are scale-shifted int64; hand ML consumers
                # the real values
                import jax.numpy as _jnp
                out[name] = c.data[:n].astype(_jnp.float64) \
                    / (10.0 ** c.dtype.scale)
            else:
                out[name] = c.data[:n]
        return out

    def count(self) -> int:
        from .expr.functions import count_star
        t = self.agg(count_star().alias("n")).collect()
        return t.column("n")[0].as_py()

    def explain(self, mode: str = "plan") -> str:
        if mode == "analyze":
            # EXPLAIN ANALYZE: EXECUTE the query under instrumentation and
            # render the post-override plan annotated with each node's
            # runtime metrics and % of query wall (reference: tagging-only
            # ExplainPlan; the measured analogue is ours to provide)
            from .plan.meta import render_analyzed_plan
            from .tools.profiler import profile_query
            prof = profile_query(self)
            text = render_analyzed_plan(prof.nodes, prof.total_s,
                                        kernels=prof.kernels)
            print(text)
            return text
        cpu = plan_physical(self.logical, self.session.conf)
        if mode == "tpu":
            text = explain_plan(cpu, self.session.conf)
        else:
            plan = self.session._physical(self.logical)
            text = plan.tree_string()
        print(text)
        return text

    def write_parquet(self, path, **kw):
        from .io.writer import write_parquet
        write_parquet(self, path, **kw)

    def write_csv(self, path, **kw):
        from .io.writer import write_csv
        write_csv(self, path, **kw)

    def write_orc(self, path, **kw):
        from .io.writer import write_orc
        write_orc(self, path, **kw)


class GroupedData:
    def __init__(self, df: DataFrame, groupings: Sequence[Expression],
                 grouping_sets=None):
        self.df = df
        self.groupings = list(groupings)
        self.grouping_sets = grouping_sets

    def agg(self, *aggs) -> DataFrame:
        exprs = [_to_expr(a) for a in aggs]
        if self.grouping_sets is not None:
            return self._agg_grouping_sets(exprs)
        return DataFrame(self.df.session,
                         LogicalAggregate(self.df.logical, self.groupings, exprs))

    def _agg_grouping_sets(self, aggs) -> DataFrame:
        """rollup/cube/grouping sets: Expand (one projection per set, absent
        grouping columns nulled, plus a grouping id so (a=null) data rows
        stay distinct from aggregated-away rows) -> aggregate -> drop the id
        (Spark's Aggregate-over-Expand lowering; reference GpuExpandExec).

        Aggregates that read a grouping column get a separate UN-nulled
        passthrough copy, matching Spark: rollup('a').agg(sum('a')) sums the
        real values even in rows where 'a' is aggregated away."""
        from .expr.base import AttributeReference, Literal
        from .expr.functions import col
        from .plan.logical import LogicalExpand, LogicalProject
        child = self.df.logical
        cs = child.schema
        gnames = [g.column_name for g in self.groupings]
        refs = {r for a in aggs for r in a.references()}
        others = sorted(refs - set(gnames))
        # grouping columns read by aggregates: alias an un-nulled copy and
        # rewrite the aggregate expressions to read it
        copied = sorted(refs & set(gnames))
        copy_name = {g: f"__gset_input_{g}__" for g in copied}
        aggs = [_replace_refs(a, copy_name) for a in aggs]
        gid_name = "__grouping_id__"
        k = len(gnames)
        projections = []
        for s in self.grouping_sets:
            # Spark grouping id: bit (k-1-i) set when column i is aggregated
            # away in this set
            gid = sum(1 << (k - 1 - i) for i, g in enumerate(gnames)
                      if g not in s)
            proj = [AttributeReference(g, cs.field(g).dtype) if g in s
                    else Literal(None, cs.field(g).dtype) for g in gnames]
            proj += [AttributeReference(o, cs.field(o).dtype) for o in others]
            proj += [AttributeReference(g, cs.field(g).dtype) for g in copied]
            proj.append(Literal(gid))
            projections.append(proj)
        expand = LogicalExpand(
            child, projections,
            gnames + others + [copy_name[g] for g in copied] + [gid_name])
        agg = LogicalAggregate(
            expand, [col(g).expr for g in gnames] + [col(gid_name).expr],
            aggs)
        out_names = [n for n in agg.schema.names if n != gid_name]
        proj = LogicalProject(agg, [col(n).expr for n in out_names])
        return DataFrame(self.df.session, proj)

    def count(self) -> DataFrame:
        from .expr.functions import count_star
        return self.agg(count_star().alias("count"))

    def apply_in_pandas(self, fn, schema) -> DataFrame:
        """``fn(pandas.DataFrame) -> pandas.DataFrame`` once per key group
        (PySpark applyInPandas; reference: GpuFlatMapGroupsInPandasExec).
        ``schema`` is a dict of output column name -> DataType."""
        from .plan.logical import LogicalGroupedMapPandas
        from .plan.schema import Field, Schema
        keys = self._key_names("applyInPandas")
        out = Schema([Field(n, d, True) for n, d in schema.items()])
        return DataFrame(self.df.session, LogicalGroupedMapPandas(
            self.df.logical, keys, fn, out))

    applyInPandas = apply_in_pandas

    def cogroup(self, other: "GroupedData") -> "CoGroupedData":
        """Pair this grouping with another DataFrame's grouping (PySpark
        cogroup; reference: GpuFlatMapCoGroupsInPandasExec)."""
        return CoGroupedData(self, other)

    def _key_names(self, what: str = "cogroup"):
        from .expr.base import AttributeReference
        keys = []
        for g in self.groupings:
            if not isinstance(g, AttributeReference):
                raise TypeError(f"{what} grouping must be plain column "
                                f"references, got {g!r}")
            keys.append(g.column_name)
        return keys


class CoGroupedData:
    def __init__(self, left: "GroupedData", right: "GroupedData"):
        self.left = left
        self.right = right

    def apply_in_pandas(self, fn, schema) -> "DataFrame":
        """``fn(left_frame, right_frame) -> pandas.DataFrame`` once per key
        present on either side (missing side passes an empty frame)."""
        from .plan.logical import LogicalCoGroupedMapPandas
        from .plan.schema import Field, Schema
        out = Schema([Field(n, d, True) for n, d in schema.items()])
        return DataFrame(self.left.df.session, LogicalCoGroupedMapPandas(
            self.left.df.logical, self.right.df.logical,
            self.left._key_names(), self.right._key_names(), fn, out))

    applyInPandas = apply_in_pandas


def _walk_expr(e):
    yield e
    for c in e.children:
        yield from _walk_expr(c)


def _bind_conf_exprs(plan, conf, session=None, device=None) -> None:
    """Freeze conf-dependent expression semantics into the plan at planning
    time (spark.sql.mapKeyDedupPolicy today): evaluation must not re-read
    the active session, which can change before a lazy iterator drains.
    Scalar subqueries execute here too (driver-side, before the main
    query — reference: ExecSubqueryExpression / GpuScalarSubquery)."""
    from .expr.collections import MAP_KEY_DEDUP_POLICY, CreateMap
    from .expr.subquery import ScalarSubquery

    policy = str(conf.get(MAP_KEY_DEDUP_POLICY)).upper()

    def bind(e):
        if not isinstance(e, Expression):
            return e
        if isinstance(e, ScalarSubquery):
            if session is None:
                raise RuntimeError("scalar subquery outside a session")
            return e.to_literal(session, device)
        if e.children:
            new = [bind(c) for c in e.children]
            if any(n is not o for n, o in zip(new, e.children)):
                e = e.with_children(new)
        if isinstance(e, CreateMap) and e._dedup_policy is None:
            e = CreateMap(*e.children, dedup_policy=policy)
        return e

    def bind_any(v):
        """Bind expressions wherever they sit in a node attribute: bare,
        lists (possibly nested), SortOrders, (name, expr) pairs,
        WindowExpressions."""
        if isinstance(v, Expression):
            return bind(v)
        if isinstance(v, list):
            return [bind_any(x) for x in v]
        if isinstance(v, tuple) and len(v) == 2 \
                and isinstance(v[1], Expression):
            return (v[0], bind(v[1]))
        from .expr.functions import SortOrder
        if isinstance(v, SortOrder):
            v.expr = bind(v.expr)
            return v
        return v

    from .plan.physical import PLAN_EXPR_ATTRS
    for node in _walk_plan(plan):
        for attr in PLAN_EXPR_ATTRS:
            v = getattr(node, attr, None)
            if v is not None:
                setattr(node, attr, bind_any(v))


def _walk_plan(plan):
    yield plan
    for c in plan.children:
        yield from _walk_plan(c)


def _replace_refs(e, mapping):
    """Rename AttributeReferences per ``mapping`` throughout a tree."""
    from .expr.base import AttributeReference
    if isinstance(e, AttributeReference):
        if e.column_name in mapping:
            return AttributeReference(mapping[e.column_name], e._dtype,
                                      e._nullable)
        return e
    if not e.children:
        return e
    return e.with_children([_replace_refs(c, mapping) for c in e.children])


def _as_col(c):
    from .expr.functions import col as _col
    if isinstance(c, str):
        return _col(c)
    return c
