"""Basic device operators: Project / Filter / Range / Union / Limit
(reference: basicPhysicalOperators.scala:115,313,540 and limit.scala).

Project and Filter are pure per-batch functions — Filter only ANDs the
selection mask (no gather!), so a filter+project chain fuses into one XLA
computation with zero intermediate materialization.
"""
from __future__ import annotations

from typing import Callable, Iterator, List, Optional, Sequence

import jax.numpy as jnp

from ..columnar import dtypes as dt
from ..columnar.device import (DeviceColumn, DeviceTable,
                               resolve_min_bucket)
from ..expr.base import EvalContext, Expression
from ..plan.physical import PhysicalPlan
from ..plan.schema import Field, Schema
from ..utils import metrics as M
from ..utils.compile_cache import named_program
from .base import TpuExec

__all__ = ["TpuProjectExec", "TpuFilterExec", "TpuRangeExec", "TpuUnionExec",
           "TpuLocalLimitExec", "TpuExpandExec", "TpuSampleExec",
           "eval_exprs_device"]


def eval_exprs_device(table: DeviceTable, exprs: Sequence[Expression],
                      names: Sequence[str], partition_id: int = 0,
                      batch_row_offset: int = 0) -> DeviceTable:
    ctx = EvalContext.for_device(table, partition_id=partition_id,
                                 batch_row_offset=batch_row_offset)
    cols: List[DeviceColumn] = []
    for e in exprs:
        c = e.eval(ctx)
        validity = c.validity
        if validity is None:
            validity = jnp.ones(table.capacity, dtype=bool)
        values = c.values
        if not isinstance(c.dtype, (dt.StringType, dt.BinaryType,
                                    dt.ArrayType, dt.StructType,
                                    dt.MapType)):
            want = c.dtype.np_dtype()
            if values.dtype != want:
                values = values.astype(want)
        kids = None if c.children is None \
            else tuple(ctx.to_device_column(k) for k in c.children)
        cols.append(DeviceColumn(values, validity, c.dtype, c.lengths,
                                 c.elem_validity, kids))
    return DeviceTable(tuple(cols), table.row_mask, table.num_rows, tuple(names))


class TpuProjectExec(TpuExec):
    def __init__(self, child: PhysicalPlan, exprs: Sequence[Expression],
                 names: Sequence[str]):
        super().__init__()
        self.child = child
        self.children = (child,)
        self.exprs = list(exprs)
        self.names = list(names)
        self.schema = Schema([Field(n, e.data_type, e.nullable)
                              for n, e in zip(names, exprs)])

    def batch_fn(self) -> Callable[[DeviceTable], DeviceTable]:
        exprs, names = self.exprs, self.names

        def fn(table: DeviceTable) -> DeviceTable:
            return eval_exprs_device(table, exprs, names)
        return fn

    def host_batch_fn(self):
        # the host-engine projection over one downloaded batch
        # (plan/physical.py CpuProjectExec's per-batch body); context-
        # dependent exprs need the real task context and cannot fall back
        if any(e.tree_context_dependent() for e in self.exprs):
            return None
        exprs, names = self.exprs, self.names

        def fn(table):
            from ..plan.physical import host_eval_exprs
            return host_eval_exprs(table, exprs, names)
        return fn

    def plan_signature(self) -> str:
        child_schema = repr(self.children[0].schema) if self.children else ""
        return f"Project|{[repr(e) for e in self.exprs]}|{self.names}|{child_schema}"

    @property
    def fusible(self) -> bool:
        # context-dependent exprs (partition id / monotonic id / rand) need a
        # per-partition context, so they stay out of whole-stage fusion
        return not any(e.tree_context_dependent() for e in self.exprs)

    def execute_columnar(self, pidx: int) -> Iterator[DeviceTable]:
        from ..utils.compile_cache import cached_jit
        if not self.fusible:
            # eager device evaluation with an explicit task context
            offset = 0
            for batch in self.child_device_batches(pidx):
                with self.metrics.timed(M.OP_TIME):
                    out = eval_exprs_device(batch, self.exprs, self.names,
                                            partition_id=pidx,
                                            batch_row_offset=offset)
                offset += batch.capacity
                self.account_batch()
                yield out
            return
        from ..memory.retry import split_device_rows, with_retry_split
        from .fallback import with_host_fallback
        fn = cached_jit(self.plan_signature(), self.batch_fn,
                        name="op_project")
        # degradation boundary: ladder inside (spill → retry → split),
        # host fallback outside — a terminal device failure re-runs the
        # batch through the host projection instead of failing the query
        run = with_host_fallback(
            self,
            lambda b: with_retry_split(fn, b, splitter=split_device_rows,
                                       scope="project",
                                       context=self.node_desc()),
            self.host_batch_fn())
        for batch in self.child_device_batches(pidx):
            with self.metrics.timed(M.OP_TIME):
                # row-wise: halves concat back into the same projection
                out = run(batch)
            self.account_batch()
            yield out

    def node_desc(self):
        return ", ".join(self.names)


class TpuFilterExec(TpuExec):
    def __init__(self, child: PhysicalPlan, condition: Expression):
        super().__init__()
        self.child = child
        self.children = (child,)
        self.condition = condition
        self.schema = child.schema

    def batch_fn(self) -> Callable[[DeviceTable], DeviceTable]:
        cond = self.condition

        def fn(table: DeviceTable) -> DeviceTable:
            ctx = EvalContext.for_device(table)
            c = cond.eval(ctx)
            keep = c.values
            if c.validity is not None:
                keep = jnp.logical_and(keep, c.validity)
            return table.filter_mask(keep)
        return fn

    def host_batch_fn(self):
        # the host-engine filter over one downloaded batch
        # (plan/physical.py CpuFilterExec's per-batch body)
        if self.condition.tree_context_dependent():
            return None
        cond = self.condition

        def fn(table):
            import numpy as np
            from ..expr.base import EvalContext as _Ctx
            ctx = _Ctx.for_host(table)
            c = cond.eval(ctx)
            keep = np.asarray(c.values, dtype=np.bool_)  # srtpu: sync-ok(host fallback path over a downloaded host table)
            if c.validity is not None:
                keep = keep & c.validity
            return table.take(np.nonzero(keep)[0])
        return fn

    def plan_signature(self) -> str:
        child_schema = repr(self.children[0].schema) if self.children else ""
        return f"Filter|{self.condition!r}|{child_schema}"

    @property
    def fusible(self) -> bool:
        return not self.condition.tree_context_dependent()

    def execute_columnar(self, pidx: int) -> Iterator[DeviceTable]:
        from ..utils.compile_cache import cached_jit
        if not self.fusible:
            cond = self.condition
            offset = 0
            for batch in self.child_device_batches(pidx):
                with self.metrics.timed(M.OP_TIME):
                    ctx = EvalContext.for_device(batch, partition_id=pidx,
                                                 batch_row_offset=offset)
                    c = cond.eval(ctx)
                    keep = c.values
                    if c.validity is not None:
                        keep = jnp.logical_and(keep, c.validity)
                    out = batch.filter_mask(keep)
                offset += batch.capacity
                self.account_batch()
                yield out
            return
        from ..memory.retry import split_device_rows, with_retry_split
        from .fallback import with_host_fallback
        fn = cached_jit(self.plan_signature(), self.batch_fn,
                        name="op_filter")
        # degradation boundary (see TpuProjectExec): ladder inside,
        # host fallback outside
        run = with_host_fallback(
            self,
            lambda b: with_retry_split(fn, b, splitter=split_device_rows,
                                       scope="filter",
                                       context=self.node_desc()),
            self.host_batch_fn())
        for batch in self.child_device_batches(pidx):
            with self.metrics.timed(M.OP_TIME):
                # row-wise: filtering halves and concatenating preserves
                # the partition's surviving rows and their order
                out = run(batch)
            self.account_batch()
            yield out

    def node_desc(self):
        return repr(self.condition)


class TpuSampleExec(TpuExec):
    """Device Bernoulli sample (reference: GpuPartitionwiseSampledRDD /
    GpuPoissonSampler). Batches are compacted and the running row offset is
    tracked by TRUE row count so the position-hash decisions match the host
    engine row-for-row."""

    def __init__(self, child: PhysicalPlan, fraction: float, seed: int):
        super().__init__()
        from ..expr.hashing import SampleMask
        self.child = child
        self.children = (child,)
        self.fraction = fraction
        self.seed = seed
        self.mask_expr = SampleMask(fraction, seed)
        self.schema = child.schema

    def plan_signature(self) -> str:
        return f"Sample|{self.fraction}|{self.seed}|{self.schema!r}"

    def execute_columnar(self, pidx: int) -> Iterator[DeviceTable]:
        from ..utils.compile_cache import cached_jit
        mask_expr = self.mask_expr

        def make():
            def fn(table: DeviceTable, offset) -> DeviceTable:
                ctx = EvalContext.for_device(table, partition_id=pidx,
                                             batch_row_offset=offset)
                c = mask_expr.eval(ctx)
                return table.filter_mask(c.values)
            return fn
        from ..memory.retry import with_retry
        fn = cached_jit(self.plan_signature() + f"|p{pidx}", make,
                        name="op_sample")
        # device-resident row offset: the accumulation rides async
        # dispatch, so sampling never blocks the host between batches
        offset = jnp.zeros((), dtype=jnp.int64)
        for batch in self.child_device_batches(pidx):
            with self.metrics.timed(M.OP_TIME):
                batch = batch.compact()
                # spill-only retry: the sample mask hashes ABSOLUTE row
                # positions, so row-axis halves (which renumber rows from
                # 0) would sample different rows — unsplittable
                out = with_retry(fn, batch, offset,
                                 scope="sample", context=self.node_desc())
            offset = offset + batch.num_rows.astype(jnp.int64)
            self.account_batch()
            yield out

    def node_desc(self):
        return f"fraction={self.fraction} seed={self.seed}"


class TpuExpandExec(TpuExec):
    """Device Expand: the P projections evaluate in ONE traced kernel and
    stack into a (P * capacity)-row batch — fully static shapes (reference:
    GpuExpandExec.scala emits per-projection batches; stacking suits XLA
    better than P small launches)."""

    def __init__(self, child: PhysicalPlan, projections, names, schema):
        super().__init__()
        self.child = child
        self.children = (child,)
        self.projections = projections
        self.names = list(names)
        self.schema = schema

    def batch_fn(self) -> Callable[[DeviceTable], DeviceTable]:
        projections, names = self.projections, self.names

        def fn(table: DeviceTable) -> DeviceTable:
            from ..columnar.device import concat_device_tables
            parts = [eval_exprs_device(table, proj, names)
                     for proj in projections]
            if len(parts) == 1:
                return parts[0]
            return concat_device_tables(parts)
        return fn

    def plan_signature(self) -> str:
        child_schema = repr(self.children[0].schema) if self.children else ""
        return ("Expand|"
                f"{[[repr(e) for e in p] for p in self.projections]}|"
                f"{self.names}|{child_schema}")

    @property
    def fusible(self) -> bool:
        return not any(e.tree_context_dependent()
                       for p in self.projections for e in p)

    def execute_columnar(self, pidx: int) -> Iterator[DeviceTable]:
        from ..columnar.device import concat_device_tables
        from ..utils.compile_cache import cached_jit
        if not self.fusible:
            # context-dependent projections need the real task context
            offset = 0
            for batch in self.child_device_batches(pidx):
                with self.metrics.timed(M.OP_TIME):
                    parts = [eval_exprs_device(batch, proj, self.names,
                                               partition_id=pidx,
                                               batch_row_offset=offset)
                             for proj in self.projections]
                    out = parts[0] if len(parts) == 1 \
                        else concat_device_tables(parts)
                offset += batch.capacity
                self.account_batch()
                yield out
            return
        from ..memory.retry import with_retry
        fn = cached_jit(self.plan_signature(), self.batch_fn,
                        name="op_expand")
        for batch in self.child_device_batches(pidx):
            with self.metrics.timed(M.OP_TIME):
                # spill-only retry: expand interleaves P projections per
                # batch, so half-outputs would reorder rows across the
                # projection boundary — unsplittable
                out = with_retry(fn, batch, scope="expand",
                                 context=self.node_desc())
            self.account_batch()
            yield out

    def node_desc(self):
        return f"{len(self.projections)} projections"


class TpuRangeExec(TpuExec):
    def __init__(self, start: int, end: int, step: int, num_partitions: int = 1,
                 min_bucket: Optional[int] = None, max_batch_rows: int = 1 << 22):
        super().__init__()
        import math
        self.start, self.end, self.step = start, end, step
        self._parts = num_partitions
        self.min_bucket = resolve_min_bucket(min_bucket)
        self.max_batch_rows = max_batch_rows
        self.children = ()
        self.schema = Schema([Field("id", dt.LONG, False)])
        self._total = max(0, math.ceil((end - start) / step))

    @property
    def num_partitions(self) -> int:
        return self._parts

    def execute_columnar(self, pidx: int) -> Iterator[DeviceTable]:
        import math
        per = math.ceil(self._total / self._parts) if self._total else 0
        lo = min(self._total, pidx * per)
        hi = min(self._total, (pidx + 1) * per)
        pos = lo
        while pos < hi:
            n = min(self.max_batch_rows, hi - pos)
            from ..columnar.device import bucket_rows
            with self.metrics.timed(M.OP_TIME):
                cap = bucket_rows(max(n, 1), self.min_bucket)
                iota = jnp.arange(cap, dtype=jnp.int64)
                values = jnp.asarray(self.start, jnp.int64) \
                    + jnp.asarray(self.step, jnp.int64) * (iota + pos)
                mask = iota < n
                col = DeviceColumn(values, mask, dt.LONG, None)
            self.account_batch(rows=n)
            yield DeviceTable((col,), mask, jnp.asarray(n, jnp.int32), ("id",))
            pos += n


class TpuUnionExec(TpuExec):
    def __init__(self, children: Sequence[PhysicalPlan]):
        super().__init__()
        self.children = tuple(children)
        self.schema = children[0].schema

    @property
    def num_partitions(self) -> int:
        return sum(c.num_partitions for c in self.children)

    def execute_columnar(self, pidx: int) -> Iterator[DeviceTable]:
        for c in self.children:
            if pidx < c.num_partitions:
                for b in c.execute_columnar(pidx):
                    self.account_batch()
                    yield DeviceTable(b.columns, b.row_mask, b.num_rows,
                                      tuple(self.schema.names))
                return
            pidx -= c.num_partitions
        raise IndexError(pidx)


def _limit_take_impl(table: DeviceTable, k) -> DeviceTable:
    t = table.compact()
    iota = jnp.arange(t.capacity, dtype=jnp.int32)
    nr = jnp.minimum(t.num_rows, k).astype(jnp.int32)
    mask = iota < nr
    return DeviceTable(t.columns, mask, nr, t.names)


_limit_take = named_program(_limit_take_impl, "op_limit")


class TpuLocalLimitExec(TpuExec):
    """Per-partition limit: compacts then masks the first n rows."""

    def __init__(self, child: PhysicalPlan, n: int):
        super().__init__()
        self.child = child
        self.children = (child,)
        self.n = n
        self.schema = child.schema

    def execute_columnar(self, pidx: int) -> Iterator[DeviceTable]:
        remaining = self.n

        from ..columnar.device import resolve_scalars
        for batch in self.child_device_batches(pidx):
            if remaining <= 0:
                return
            with self.metrics.timed(M.OP_TIME):
                out = _limit_take(batch,
                                  jnp.asarray(remaining, jnp.int32))
            # early-exit decision: one batched-funnel transfer per batch
            (emitted,) = resolve_scalars(out.num_rows)
            emitted = int(emitted)
            remaining -= emitted
            self.account_batch(rows=emitted)
            yield out
