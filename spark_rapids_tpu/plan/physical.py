"""Physical plan nodes — CPU engine + common infrastructure.

In the reference, Spark provides CPU physical operators and the plugin swaps
them for ``Gpu*Exec`` nodes. This framework is standalone, so it carries its
own CPU operator set (numpy/pandas based) which serves two purposes:

1. the fallback path for anything tagged not-runnable on TPU (same role as
   Spark falling back to CPU in the reference), and
2. the differential-testing baseline (tests run device vs CPU and compare,
   like the reference's SparkQueryCompareTestSuite / integration harness).

Execution model: a plan node exposes ``num_partitions`` and
``execute(pidx) -> Iterator[HostTable]``. Device nodes (exec/) additionally
expose ``execute_columnar(pidx) -> Iterator[DeviceTable]``.
"""
from __future__ import annotations

import math
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np
import pandas as pd

from ..columnar import dtypes as dt
from ..columnar.host import HostColumn, HostTable
from ..expr.aggregates import AggregateFunction
from ..expr.base import EvalContext, Expression
from ..expr.functions import SortOrder
from ..expr.hashing import float_key_bits
from .schema import Field, Schema

__all__ = [
    "PhysicalPlan", "CpuScanExec", "CpuProjectExec", "CpuFilterExec",
    "CpuHashAggregateExec", "CpuSortExec", "CpuLocalLimitExec",
    "CpuGlobalLimitExec", "CpuUnionExec", "CpuRangeExec",
    "ShuffleExchangeExec", "Partitioning", "SinglePartitioning",
    "HashPartitioning", "RoundRobinPartitioning", "RangePartitioning",
    "AggSpec", "host_eval_exprs", "murmur_hash_columns",
]

DEFAULT_BATCH_ROWS = 1 << 20

#: every attribute a physical node may hold expressions in — the single
#: source of truth for expression walkers (planner InputFileBlockRule,
#: session conf-binding); extend HERE when adding a new expression slot
PLAN_EXPR_ATTRS = ("exprs", "condition", "projections", "orders",
                   "window_cols", "aggregates")


def _close_handle_quietly(handle):
    try:
        handle.close()
    except Exception:
        pass  # srtpu: net-ok(best-effort release at plan teardown; a failed close cannot affect the already-collected result)


class PhysicalPlan:
    children: Tuple["PhysicalPlan", ...] = ()
    schema: Schema

    @property
    def num_partitions(self) -> int:
        return self.children[0].num_partitions if self.children else 1

    def _own_spill_handle(self, handle) -> None:
        """Track a catalog spill handle this node registered on behalf of
        its output (shuffle partitions, broadcast builds). The handle is
        closed deterministically by ``release_spill_handles()`` when the
        owning query's collect finishes — relying on plan GC alone leaks:
        compile-cache entries capture plan nodes in kernel closures, so a
        finished plan can stay reachable indefinitely while its buffers
        hold HBM (found by the memory flight recorder's leak gate). The
        GC-time finalizer stays as a fallback for plans that never go
        through an explicit release (to_device_batches / to_jax); a
        finalizer runs at most once, so the two paths cannot double-close.
        """
        import weakref
        fins = self.__dict__.setdefault("_spill_finalizers", [])
        fins.append(weakref.finalize(self, _close_handle_quietly, handle))

    def release_spill_handles(self) -> int:
        """Close every spill handle owned by this (finished) plan tree.

        Walks ``children`` plus the wrapper edges the tree hides from it
        (AQE stage/reader nodes keep ``children = ()`` and reference the
        materialized subtree via ``inner``/``stage``/``_final``). Safe to
        call more than once. Returns the number of handles closed."""
        closed = 0
        seen = set()
        stack: List[PhysicalPlan] = [self]
        while stack:
            node = stack.pop()
            if id(node) in seen:
                continue
            seen.add(id(node))
            for fin in node.__dict__.get("_spill_finalizers", ()):
                if fin.alive:
                    fin()
                    closed += 1
            stack.extend(getattr(node, "children", ()))
            for attr in ("inner", "stage", "_final", "child"):
                v = getattr(node, attr, None)
                if isinstance(v, PhysicalPlan):
                    stack.append(v)
        return closed

    def execute(self, pidx: int) -> Iterator[HostTable]:
        raise NotImplementedError(type(self).__name__)

    def node_name(self) -> str:
        return type(self).__name__

    def node_desc(self) -> str:
        return ""

    def tree_string(self, indent: int = 0) -> str:
        pad = "  " * indent
        desc = self.node_desc()
        line = f"{pad}{self.node_name()}" + (f" [{desc}]" if desc else "")
        return "\n".join([line] + [c.tree_string(indent + 1) for c in self.children])

    def collect(self) -> HostTable:
        from ..utils.tracing import get_tracer
        tracer = get_tracer()
        batches: List[HostTable] = []
        for p in range(self.num_partitions):
            # one "task" span per partition drain (the Spark-task level of
            # the query -> stage -> task -> operator span hierarchy)
            with tracer.span("task", "task", partition=p):
                batches.extend(self.execute(p))
        if not batches:
            return empty_result_table(self.schema)
        return HostTable.concat(batches)


def empty_result_table(schema: Schema) -> HostTable:
    """Typed zero-row result — the ONE construction shared by sequential
    collect and the pipelined executor (they are correctness-oracle pairs
    and must agree on empty results)."""
    return HostTable(schema.names, [
        HostColumn(f.dtype, _empty_values(f.dtype)) for f in schema])


def _empty_values(d: dt.DataType) -> np.ndarray:
    if isinstance(d, (dt.StringType, dt.BinaryType, dt.ArrayType,
                      dt.StructType, dt.MapType)):
        return np.empty(0, dtype=object)
    return np.empty(0, dtype=d.np_dtype())


def host_eval_exprs(table: HostTable, exprs: Sequence[Expression],
                    names: Sequence[str], partition_id: int = 0,
                    batch_row_offset: int = 0) -> HostTable:
    ctx = EvalContext.for_host(table, partition_id=partition_id,
                               batch_row_offset=batch_row_offset)
    cols = []
    for e in exprs:
        c = e.eval(ctx)
        values = c.values
        if not isinstance(values, np.ndarray):
            values = np.asarray(values)  # srtpu: sync-ok(host engine path over host tables)
        if isinstance(c.dtype, dt.BooleanType) and values.dtype != np.bool_:
            values = values.astype(np.bool_)
        elif isinstance(c.dtype, (dt.ArrayType, dt.StructType, dt.MapType)):
            pass  # nested values stay python objects host-side
        elif values.dtype != c.dtype.np_dtype() and values.dtype != object:
            values = values.astype(c.dtype.np_dtype())
        cols.append(HostColumn(c.dtype, values, c.validity))
    return HostTable(list(names), cols)


# ---------------------------------------------------------------------------
# Leaf / basic operators
# ---------------------------------------------------------------------------
class CpuScanExec(PhysicalPlan):
    def __init__(self, source, columns: Optional[List[str]] = None):
        self.source = source
        self.columns = columns
        self.children = ()
        full = source.schema()
        self.schema = full.select(columns) if columns else full

    @property
    def num_partitions(self) -> int:
        return self.source.partitions()

    def execute(self, pidx: int) -> Iterator[HostTable]:
        conf = getattr(self.source, "conf", None)
        dump_dir = ""
        if conf is not None:
            from ..io.dump import DEBUG_DUMP_PATH
            dump_dir = conf.get(DEBUG_DUMP_PATH)
        for i, batch in enumerate(
                self.source.read_partition(pidx, self.columns)):
            if dump_dir:
                from ..io.dump import dump_scan_batch
                dump_scan_batch(dump_dir, self.source.name(), pidx, i, batch)
            yield batch

    def node_desc(self):
        return f"{self.source.name()} cols={self.columns or '*'}"


class CpuProjectExec(PhysicalPlan):
    def __init__(self, child: PhysicalPlan, exprs: Sequence[Expression],
                 names: Sequence[str]):
        self.child = child
        self.children = (child,)
        self.exprs = list(exprs)
        self.names = list(names)
        self.schema = Schema([Field(n, e.data_type, e.nullable)
                              for n, e in zip(names, exprs)])

    def execute(self, pidx: int) -> Iterator[HostTable]:
        offset = 0
        for batch in self.child.execute(pidx):
            yield host_eval_exprs(batch, self.exprs, self.names,
                                  partition_id=pidx, batch_row_offset=offset)
            offset += batch.num_rows

    def node_desc(self):
        return ", ".join(self.names)


class CpuFilterExec(PhysicalPlan):
    def __init__(self, child: PhysicalPlan, condition: Expression):
        self.child = child
        self.children = (child,)
        self.condition = condition
        self.schema = child.schema

    def execute(self, pidx: int) -> Iterator[HostTable]:
        offset = 0
        for batch in self.child.execute(pidx):
            ctx = EvalContext.for_host(batch, partition_id=pidx,
                                       batch_row_offset=offset)
            offset += batch.num_rows
            c = self.condition.eval(ctx)
            keep = np.asarray(c.values, dtype=np.bool_)  # srtpu: sync-ok(host engine path over host tables)
            if c.validity is not None:
                keep = keep & c.validity
            yield batch.take(np.nonzero(keep)[0])

    def node_desc(self):
        return repr(self.condition)


class CpuSampleExec(CpuFilterExec):
    """Deterministic Bernoulli sample (reference: SampleExec rule +
    GpuPoissonSampler; here a seeded position-hash filter so device and host
    agree row-for-row)."""

    def __init__(self, child: PhysicalPlan, fraction: float, seed: int):
        from ..expr.hashing import SampleMask
        super().__init__(child, SampleMask(fraction, seed))
        self.fraction = fraction
        self.seed = seed

    def node_desc(self):
        return f"fraction={self.fraction} seed={self.seed}"


class CpuRangeExec(PhysicalPlan):
    def __init__(self, start: int, end: int, step: int, num_partitions: int = 1):
        self.start, self.end, self.step = start, end, step
        self._parts = num_partitions
        self.children = ()
        self.schema = Schema([Field("id", dt.LONG, False)])

    @property
    def num_partitions(self) -> int:
        return self._parts

    def execute(self, pidx: int) -> Iterator[HostTable]:
        from ..io.file_block import clear_input_file
        clear_input_file()  # generated rows have no source file
        total = max(0, math.ceil((self.end - self.start) / self.step))
        per = math.ceil(total / self._parts) if total else 0
        lo = pidx * per
        hi = min(total, (pidx + 1) * per)
        vals = self.start + self.step * np.arange(lo, hi, dtype=np.int64)
        yield HostTable(["id"], [HostColumn(dt.LONG, vals)])


class CpuUnionExec(PhysicalPlan):
    def __init__(self, children: Sequence[PhysicalPlan]):
        self.children = tuple(children)
        self.schema = children[0].schema

    @property
    def num_partitions(self) -> int:
        return sum(c.num_partitions for c in self.children)

    def execute(self, pidx: int) -> Iterator[HostTable]:
        for c in self.children:
            if pidx < c.num_partitions:
                for b in c.execute(pidx):
                    # normalize column names to union output schema
                    yield HostTable(self.schema.names, b.columns)
                return
            pidx -= c.num_partitions
        raise IndexError(pidx)


class CpuExpandExec(PhysicalPlan):
    """Each input row -> one output row per projection (grouping sets
    substrate; reference GpuExpandExec.scala)."""

    def __init__(self, child: PhysicalPlan, projections, names, schema):
        self.child = child
        self.children = (child,)
        self.projections = projections
        self.names = list(names)
        self.schema = schema

    def execute(self, pidx: int) -> Iterator[HostTable]:
        offset = 0
        for batch in self.child.execute(pidx):
            for proj in self.projections:
                yield host_eval_exprs(batch, proj, self.names,
                                      partition_id=pidx,
                                      batch_row_offset=offset)
            offset += batch.num_rows

    def node_desc(self):
        return f"{len(self.projections)} projections"


class CpuLocalLimitExec(PhysicalPlan):
    def __init__(self, child: PhysicalPlan, n: int):
        self.child = child
        self.children = (child,)
        self.n = n
        self.schema = child.schema

    def execute(self, pidx: int) -> Iterator[HostTable]:
        remaining = self.n
        for batch in self.child.execute(pidx):
            if remaining <= 0:
                return
            if batch.num_rows > remaining:
                yield batch.slice(0, remaining)
                return
            remaining -= batch.num_rows
            yield batch


class CpuGlobalLimitExec(PhysicalPlan):
    """Must sit above a single-partition child."""

    def __init__(self, child: PhysicalPlan, n: int):
        self.child = child
        self.children = (child,)
        self.n = n
        self.schema = child.schema

    @property
    def num_partitions(self) -> int:
        return 1

    def execute(self, pidx: int) -> Iterator[HostTable]:
        yield from CpuLocalLimitExec(self.child, self.n).execute(0)


class CpuCollectLimitExec(CpuGlobalLimitExec):
    """limit-for-collect: local limit per partition feeds a single-partition
    exchange feeding this (reference: CollectLimitExec rule, limit.scala)."""


class CpuTakeOrderedExec(PhysicalPlan):
    """Top-n: sort each partition's batches and keep the first n rows
    (reference: GpuTakeOrderedAndProjectExec in limit.scala — local top-n,
    single-partition exchange, final top-n; the planner stacks two of
    these around an exchange)."""

    def __init__(self, child: PhysicalPlan, orders, n: int):
        self.child = child
        self.children = (child,)
        self.orders = list(orders)
        self.n = n
        self.schema = child.schema

    def execute(self, pidx: int) -> Iterator[HostTable]:
        batches = list(self.child.execute(pidx))
        if not batches:
            return
        t = HostTable.concat(batches) if len(batches) > 1 else batches[0]
        idx = _sort_indices(t, self.orders)[:self.n]
        yield t.take(idx)

    def node_desc(self):
        return f"n={self.n} orders={len(self.orders)}"


# ---------------------------------------------------------------------------
# Sort
# ---------------------------------------------------------------------------
def _sort_indices(table: HostTable, orders: Sequence[SortOrder]) -> np.ndarray:
    """Stable multi-key sort with Spark null ordering."""
    keys = []
    ctx = EvalContext.for_host(table)
    for o in reversed(list(orders)):  # lexsort: last key is primary
        c = o.expr.eval(ctx)
        vals = np.asarray(c.values)  # srtpu: sync-ok(host engine path over host tables)
        valid = c.validity if c.validity is not None \
            else np.ones(len(vals), dtype=bool)
        if vals.dtype == object:
            codes = pd.factorize(vals, sort=True)[0].astype(np.int64) + 1
        elif vals.dtype.kind == "f":
            # DENSE codes: equal values MUST share a code, or a tied float
            # key never defers to the later sort keys (argsort ranks are
            # unique per row — a fuzzer caught multi-key sorts ignoring
            # every key after a tied float). NaN sorts last (Spark);
            # -0.0 == 0.0.
            v = vals.copy()
            v[v == 0] = 0.0
            nan = np.isnan(v)
            _, inv = np.unique(np.where(nan, np.inf, v),
                               return_inverse=True)
            codes = inv.reshape(-1).astype(np.int64)
            codes = np.where(nan, np.int64(2**62), codes)
        else:
            codes = vals.astype(np.int64) if vals.dtype != np.int64 else vals
        if not o.ascending:
            codes = -codes
        # null sentinel strictly beyond the NaN code EVEN AFTER negation:
        # desc+nulls_first used to collide (-(2**62) == negated NaN code),
        # interleaving NULL and NaN rows (Spark: NULL strictly outside)
        null_code = np.int64(-(2**62) - 2) if o.nulls_first \
            else np.int64(2**62 + 2)
        codes = np.where(valid, codes, null_code)
        keys.append(codes)
    return np.lexsort(keys) if keys else np.arange(table.num_rows)


class CpuSortExec(PhysicalPlan):
    def __init__(self, child: PhysicalPlan, orders: Sequence[SortOrder]):
        self.child = child
        self.children = (child,)
        self.orders = list(orders)
        self.schema = child.schema

    def execute(self, pidx: int) -> Iterator[HostTable]:
        batches = list(self.child.execute(pidx))
        if not batches:
            return
        table = HostTable.concat(batches)
        yield table.take(_sort_indices(table, self.orders))

    def node_desc(self):
        return ", ".join(
            f"{o.expr!r} {'ASC' if o.ascending else 'DESC'}" for o in self.orders)


# ---------------------------------------------------------------------------
# Aggregation
# ---------------------------------------------------------------------------
class AggSpec:
    """Physical aggregate: prefix + function, aligned input/state col names."""

    def __init__(self, prefix: str, fn: AggregateFunction):
        self.prefix = prefix
        self.fn = fn
        self.input_cols = [f"{prefix}_in{k}" for k in range(len(fn.update_ops()))]
        self.state_fields = fn.state_fields(prefix)
        self.update_ops = fn.update_ops()
        self.merge_ops = fn.merge_ops()


class CpuHashAggregateExec(PhysicalPlan):
    """Group-by aggregate over pre-projected input (mode partial|final).

    Partial input: key cols + per-spec ``{prefix}_in{k}`` columns.
    Partial output/final input: key cols + per-spec state columns.
    Final output: key cols + state columns merged (post-projection is a
    separate CpuProjectExec inserted by the planner).
    """

    def __init__(self, child: PhysicalPlan, key_names: List[str],
                 specs: List[AggSpec], mode: str):
        assert mode in ("partial", "final")
        self.child = child
        self.children = (child,)
        self.key_names = list(key_names)
        self.specs = specs
        self.mode = mode
        key_fields = [child.schema.field(k) for k in key_names]
        state_fields = [Field(n, d, nb) for s in specs
                        for (n, d, nb) in s.state_fields]
        self.schema = Schema(key_fields + state_fields)

    @property
    def num_partitions(self) -> int:
        return self.child.num_partitions

    def _columns_ops(self) -> List[Tuple[str, str, str, dt.DataType]]:
        """(input_col, op, out_col, out_dtype) per state column."""
        out = []
        for s in self.specs:
            ops = s.update_ops if self.mode == "partial" else s.merge_ops
            in_cols = s.input_cols if self.mode == "partial" \
                else [n for (n, _, _) in s.state_fields]
            for (in_col, op, (out_col, out_dt, _)) in zip(in_cols, ops, s.state_fields):
                out.append((in_col, op, out_col, out_dt))
        return out

    def execute(self, pidx: int) -> Iterator[HostTable]:
        from .host_groupby import group_codes, host_group_reduce
        batches = list(self.child.execute(pidx))
        table = HostTable.concat(batches) if batches else None
        cols_ops = self._columns_ops()
        if table is None or table.num_rows == 0:
            if self.key_names:
                yield HostTable(self.schema.names,
                                [HostColumn(f.dtype, _empty_values(f.dtype))
                                 for f in self.schema])
                return
            # grand aggregate over empty input: one null/zero row
            table = HostTable(
                [c for c, _, _, _ in cols_ops],
                [HostColumn(self.child.schema.field(c).dtype,
                            _empty_values(self.child.schema.field(c).dtype))
                 for c, _, _, _ in cols_ops])
        gid, ngroups, rep = group_codes(table, self.key_names)
        out_cols: List[HostColumn] = []
        for k in self.key_names:
            out_cols.append(table.column(k).take(rep))
        for in_col, op, out_col, out_dt in cols_ops:
            vals, validity = host_group_reduce(op, table.column(in_col), gid,
                                               ngroups, out_dt)
            if not isinstance(out_dt, (dt.StringType, dt.BinaryType,
                                       dt.ArrayType, dt.StructType,
                                       dt.MapType)) \
                    and not dt.is_d128(out_dt) \
                    and vals.dtype != out_dt.np_dtype():
                with np.errstate(invalid="ignore"):
                    vals = vals.astype(out_dt.np_dtype())
            if validity is not None and validity.all():
                validity = None
            out_cols.append(HostColumn(out_dt, vals, validity))
        yield HostTable(self.schema.names, out_cols)

    def node_desc(self):
        return f"mode={self.mode} keys={self.key_names}"


# ---------------------------------------------------------------------------
# Exchange / partitioning
# ---------------------------------------------------------------------------
def murmur_hash_columns(table: HostTable, key_names: Sequence[str],
                        seed: int = 42) -> np.ndarray:
    """32-bit Murmur3-style hash of key columns (matches the device kernel in
    exec/hashing; reference: HashFunctions.scala / GpuHashPartitioningBase)."""
    h = np.full(table.num_rows, seed, dtype=np.uint32)
    for name in key_names:
        col = table.column(name)
        if col.values.dtype == object:
            k = np.asarray([_murmur_bytes(str(v).encode()) for v in col.values],  # srtpu: sync-ok(host partitioner over host tables)
                           dtype=np.uint32)
        else:
            k = _murmur_fmix(col.values)
        k = np.where(col.valid_mask(), k, np.uint32(0))
        h = _murmur_combine(h, k)
    return h


def _murmur_fmix(vals: np.ndarray) -> np.ndarray:
    if vals.dtype == np.bool_:
        x = vals.astype(np.uint32)
    elif vals.dtype.kind == "f":
        with np.errstate(over="ignore", invalid="ignore"):
            x = float_key_bits(np, vals)
    else:
        x64 = vals.astype(np.int64).view(np.uint64)
        x = (x64 & np.uint64(0xFFFFFFFF)).astype(np.uint32) ^ (x64 >> np.uint64(32)).astype(np.uint32)
    x ^= x >> np.uint32(16)
    x = (x * np.uint32(0x85EBCA6B)) & np.uint32(0xFFFFFFFF)
    x ^= x >> np.uint32(13)
    x = (x * np.uint32(0xC2B2AE35)) & np.uint32(0xFFFFFFFF)
    x ^= x >> np.uint32(16)
    return x


def _murmur_bytes(b: bytes) -> int:
    h = 0
    for byte in b:
        h = (h * 31 + byte) & 0xFFFFFFFF
    return h


def _murmur_combine(h: np.ndarray, k: np.ndarray) -> np.ndarray:
    h = h ^ k
    h = (h * np.uint32(5) + np.uint32(0xE6546B64)) & np.uint32(0xFFFFFFFF)
    return h


class Partitioning:
    num_parts: int = 1

    def partition_indices(self, table: HostTable) -> np.ndarray:
        raise NotImplementedError


class SinglePartitioning(Partitioning):
    num_parts = 1

    def partition_indices(self, table: HostTable) -> np.ndarray:
        return np.zeros(table.num_rows, dtype=np.int32)


class HashPartitioning(Partitioning):
    def __init__(self, key_names: Sequence[str], num_parts: int):
        self.key_names = list(key_names)
        self.num_parts = num_parts

    def partition_indices(self, table: HostTable) -> np.ndarray:
        h = murmur_hash_columns(table, self.key_names)
        return (h % np.uint32(self.num_parts)).astype(np.int32)


class RoundRobinPartitioning(Partitioning):
    def __init__(self, num_parts: int):
        self.num_parts = num_parts

    def partition_indices(self, table: HostTable) -> np.ndarray:
        return (np.arange(table.num_rows, dtype=np.int64) % self.num_parts
                ).astype(np.int32)


class RangePartitioning(Partitioning):
    """Sampled-bounds range partitioning (reference: GpuRangePartitioner)."""

    def __init__(self, orders: Sequence[SortOrder], num_parts: int):
        self.orders = list(orders)
        self.num_parts = num_parts
        self._bounds: Optional[HostTable] = None

    def set_bounds_from_sample(self, sample: HostTable):
        idx = _sort_indices(sample, self.orders)
        n = len(idx)
        if n == 0 or self.num_parts <= 1:
            self._bounds = None
            return
        picks = [idx[int(n * (i + 1) / self.num_parts) - 1]
                 for i in range(self.num_parts - 1)]
        self._bounds = sample.take(np.asarray(picks, dtype=np.int64))  # srtpu: sync-ok(driver-side range-bounds sampling, once per exchange)

    def partition_indices(self, table: HostTable) -> np.ndarray:
        if self._bounds is None or table.num_rows == 0:
            return np.zeros(table.num_rows, dtype=np.int32)
        merged = HostTable.concat([table, self._bounds])
        order = _sort_indices(merged, self.orders)
        rank = np.empty(len(order), dtype=np.int64)
        rank[order] = np.arange(len(order))
        bound_ranks = np.sort(rank[table.num_rows:])
        row_ranks = rank[:table.num_rows]
        return np.searchsorted(bound_ranks, row_ranks, side="left").astype(np.int32)


class ShuffleExchangeExec(PhysicalPlan):
    """Materializing exchange (host-side baseline path).

    Equivalent to the reference's default-Spark-shuffle mode
    (GpuColumnarBatchSerializer path, SURVEY §2.7 mode 1). The accelerated
    mesh-collective path lives in shuffle/ and is swapped in by the planner
    when running under a device mesh.
    """

    def __init__(self, child: PhysicalPlan, partitioning: Partitioning):
        import threading

        from ..utils.metrics import MetricRegistry
        self.child = child
        self.children = (child,)
        self.partitioning = partitioning
        self.schema = child.schema
        self._materialized: Optional[List[List[HostTable]]] = None
        # v7 skew telemetry: per-output-partition rows/bytes, summed once
        # at the end of materialize (tools/eventlog.py shuffle_skew)
        self._skew_rows: Optional[List[int]] = None
        self._skew_bytes: Optional[List[int]] = None
        self._mat_lock = threading.Lock()
        # host-tier shuffles are the single largest single-chip overhead
        # (download-partition-upload); the registry makes that visible to
        # EXPLAIN ANALYZE / the diagnose tool per node
        self.metrics = MetricRegistry()
        # process-unique shuffle id for observatory attribution (shared
        # counter with the device-tier exchanges in exec/exchange.py)
        from ..exec.exchange import _EXCHANGE_IDS
        self.telemetry_sid = next(_EXCHANGE_IDS)

    @property
    def num_partitions(self) -> int:
        return self.partitioning.num_parts

    def _materialize(self):
        # pipelined partition drains race to materialize; exactly one wins.
        # The winner must never block on the TpuSemaphore while holding
        # this lock (pipeline.exempt_admission invariant)
        with self._mat_lock:
            if self._materialized is not None:
                return
            from ..parallel.pipeline import exempt_admission
            with exempt_admission():
                self._materialize_locked()

    def _materialize_locked(self):
        if isinstance(self.partitioning, RangePartitioning) \
                and self.partitioning._bounds is None:
            samples = []
            for p in range(self.child.num_partitions):
                for b in self.child.execute(p):
                    samples.append(b)
            allb = HostTable.concat(samples) if samples else None
            if allb is not None:
                self.partitioning.set_bounds_from_sample(allb)
            inputs = samples
        else:
            inputs = None
        out: List[List[HostTable]] = [[] for _ in range(self.num_partitions)]
        from ..shuffle import telemetry as shuffle_telemetry
        from ..utils import metrics as M
        # node context is thread-local; feed() runs on the parallel_map
        # pool workers below, so capture the query identity here (the
        # materializing thread holds the instrumented node scope) and
        # attribute notes explicitly
        from ..utils import node_context
        _ctx = node_context.current()
        _qid = _ctx.query_id if _ctx is not None else None

        def feed(batch: HostTable) -> List:
            with self.metrics.timed(M.SHUFFLE_PARTITION_TIME):
                nb = batch.nbytes()
                self.metrics.add(M.SHUFFLE_BYTES, nb)
                # mirrors the shuffleBytes metric add exactly so the
                # shuffle_summary tier bytes reconcile with it
                shuffle_telemetry.note_transfer(
                    "local", "enqueue", shuffle_id=self.telemetry_sid,
                    logical_bytes=nb, query_id=_qid)
                self.metrics.add(M.NUM_OUTPUT_ROWS, batch.num_rows)
                pids = self.partitioning.partition_indices(batch)
                slices = []
                for p in range(self.num_partitions):
                    sel = np.nonzero(pids == p)[0]
                    if len(sel):
                        slices.append((p, batch.take(sel)))
                        self.metrics.add(M.NUM_OUTPUT_BATCHES, 1)
                return slices

        if inputs is not None:
            for b in inputs:
                for p, sl in feed(b):
                    out[p].append(sl)
        else:
            # parallel map-side writes: each input partition decodes,
            # hashes and slices on the bounded task pool; results merge in
            # partition order so output batch order stays deterministic
            from ..parallel.pipeline import parallel_map

            def map_side(p: int) -> List:
                return [s for b in self.child.execute(p) for s in feed(b)]

            for part in parallel_map(map_side,
                                     range(self.child.num_partitions),
                                     stage="shuffle_map_write"):
                for p, sl in part:
                    out[p].append(sl)
        self._materialized = out
        # v7 skew telemetry: summed here at the end rather than inside
        # feed() so the parallel map-side writers need no extra locking
        self._skew_rows = [sum(t.num_rows for t in part) for part in out]
        self._skew_bytes = [sum(t.nbytes() for t in part) for part in out]

    def shuffle_skew(self) -> Optional[Dict]:
        """v7 event-log payload: per-output-partition row/byte
        distribution (None until the exchange materialized)."""
        if self._skew_rows is None:
            return None
        from ..utils.metrics import build_skew_record
        return build_skew_record(self._skew_rows, self._skew_bytes)

    def execute(self, pidx: int) -> Iterator[HostTable]:
        self._materialize()
        # rows of a shuffled partition come from many input files: file
        # attribution ends here (Spark: input_file_name() is "" post-shuffle)
        from ..io.file_block import clear_input_file
        clear_input_file()
        yield from self._materialized[pidx]

    def node_desc(self):
        return f"{type(self.partitioning).__name__}({self.num_partitions})"
