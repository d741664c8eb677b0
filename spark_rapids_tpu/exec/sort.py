"""Device sort (reference: GpuSortExec.scala — FullSortSingleBatch /
OutOfCoreSort / SortEachBatch modes at :39-41,69).

TPU shape: one lexsort over transformed key arrays inside one jitted program
(FullSortSingleBatch). When the input exceeds the batch-size budget, the
OutOfCoreSort path sorts each batch into a spillable run (registered with the
BufferCatalog so memory pressure migrates runs to host/disk), then merges
runs with a sentinel-sort: each round pulls a fixed-size chunk per run plus
each run's next unconsumed row flagged as a sentinel, sorts the union, and
emits exactly the prefix before the first sentinel — rows provably <= every
unseen row. All comparisons happen on device; only the emitted-count scalar
syncs to host.

Spark ordering semantics: nulls first/last per order, NaN greater than all
numbers, -0.0 == 0.0.
"""
from __future__ import annotations

from typing import Iterator, List, Optional, Sequence

import jax
import jax.numpy as jnp

from ..columnar import dtypes as dt
from ..columnar.device import (DeviceColumn, DeviceTable, append_column,
                               resolve_min_bucket, resolve_scalars,
                               bucket_rows, concat_device_tables, drop_column,
                               shrink_to_fit, slice_rows)
from ..expr.base import EvalContext
from ..expr.functions import SortOrder
from ..plan.physical import PhysicalPlan
from ..utils import metrics as M
from .base import TpuExec

__all__ = ["TpuSortExec", "device_sort_table"]

_SENT = "__ooc_sentinel"


def _order_keys(table: DeviceTable, orders: Sequence[SortOrder]) -> List[jax.Array]:
    """lexsort key list (minor..major) implementing Spark ordering."""
    ctx = EvalContext.for_device(table)
    keys: List[jax.Array] = []
    for o in reversed(list(orders)):
        c = o.expr.eval(ctx)
        v = c.values
        if jnp.issubdtype(v.dtype, jnp.floating):
            nan = jnp.isnan(v)
            v = jnp.where(v == 0, jnp.zeros_like(v), v)       # -0.0 -> 0.0
            v = jnp.where(nan, jnp.full_like(v, jnp.inf), v)  # NaN sorts high
            nan_key = nan  # among +inf ties, NaN after true inf
            if not o.ascending:
                v = -v
                nan_key = jnp.logical_not(nan)
            keys.append(nan_key)
            keys.append(v)
        elif dt.is_d128(c.dtype):  # two-limb decimal: biased uint64 words
            from ..expr.decimal128 import d128_key_words
            words = d128_key_words(v)
            if not o.ascending:  # bit inversion reverses unsigned order
                words = [~w for w in words]
            for wd in reversed(words):
                keys.append(wd)
        elif v.ndim == 2:  # string/binary: packed uint64 surrogate words
            from ..columnar.device import pack_string_key_words
            words = pack_string_key_words(v, c.lengths)
            if not o.ascending:  # bit inversion reverses unsigned order
                words = [~w for w in words]
            for wd in reversed(words):  # append LSW first; MSW nearest null key
                keys.append(wd)
        elif v.dtype == jnp.bool_:
            keys.append(v != o.ascending)
        else:
            keys.append(v if o.ascending else -v)
        valid = c.validity
        if valid is None:
            valid = jnp.ones(table.capacity, dtype=bool)
        null = jnp.logical_not(valid)
        # nulls_first: null sorts as 0 (before valid=1); else after
        null_key = jnp.logical_not(null) if o.nulls_first else null
        keys.append(null_key)
    # primary: active rows first
    keys.append(jnp.logical_not(table.row_mask))
    return keys


def device_sort_table(table: DeviceTable, orders: Sequence[SortOrder]) -> DeviceTable:
    keys = _order_keys(table, orders)
    order = jnp.lexsort(tuple(keys))
    # sort permutation parks masked-off rows past num_rows; the dense
    # prefix mask below exposes only real rows (all_valid survives)
    cols = tuple(c.gather(order, keep_all_valid=True)
                 for c in table.columns)
    iota = jnp.arange(table.capacity, dtype=jnp.int32)
    mask = iota < table.num_rows
    return DeviceTable(cols, mask, table.num_rows, table.names)


#: rows one top-n tournament sort spans. For v5e, XLA compiles the
#: 8-operand lexsort of TPC-H Q3's top-n in 1 s at this width inside a
#: ``lax.map`` body, in 101 s at 2^14 rows, 532 s at 2^16 and (a batched
#: sort along one axis of a 2-D array) 129 s at (64, 1024) — ROADMAP A1.
_TOPN_CHUNK = 2048


def _chunk_winners(table: DeviceTable, orders: Sequence[SortOrder],
                   k: int) -> DeviceTable:
    """The first ``k`` rows in sort order of every ``_TOPN_CHUNK``-row
    chunk, as one table of capacity chunks*k. Exact for top-n with n <= k:
    a row leaves only when k rows of its own chunk sort before it, and
    those precede it globally too. The sort is stable and chunks stay in
    input order, so fully tied rows keep their input order exactly as
    under the full sort. Chunks are sorted one after another by ONE
    compiled 1-D sort (``lax.map``)."""
    chunks = table.capacity // _TOPN_CHUNK
    keys = tuple(x.reshape(chunks, _TOPN_CHUNK)
                 for x in _order_keys(table, orders))
    order = jax.lax.map(lambda ks: jnp.lexsort(ks)[:k].astype(jnp.int32),
                        keys)
    base = jnp.arange(chunks, dtype=jnp.int32) * _TOPN_CHUNK
    idx = (order + base[:, None]).reshape(-1)
    # the row mask travels with the rows: only real rows stay exposed
    cols = tuple(c.gather(idx, keep_all_valid=True) for c in table.columns)
    mask = jnp.take(table.row_mask, idx)
    return DeviceTable(cols, mask, jnp.sum(mask, dtype=jnp.int32),
                       table.names)


def _topn_reduce(table: DeviceTable, orders: Sequence[SortOrder], n: int,
                 cap: int) -> DeviceTable:
    """Cut a wide batch down to chunk winners until one chunk-wide sort
    finishes the top-n; the result never drops below the ``cap``-row state
    capacity. Batches the chunk width does not divide, and n above half a
    chunk, keep the full sort."""
    kn = 1 << max(n - 1, 0).bit_length()
    while table.capacity > _TOPN_CHUNK \
            and table.capacity % _TOPN_CHUNK == 0:
        chunks = table.capacity // _TOPN_CHUNK
        k = max(kn, -(-cap // chunks))
        if k > _TOPN_CHUNK // 2:
            break
        table = _chunk_winners(table, orders, k)
    return table


class TpuTakeOrderedExec(TpuExec):
    """Device top-n (reference: GpuTakeOrderedAndProjectExec, limit.scala).

    Folds batches through a running top-n: sort batch, truncate to n,
    concat with state, sort, truncate — state stays at a bucketed n-row
    capacity so the kernel shapes are stable across batches. A batch wider
    than ``_TOPN_CHUNK`` is first reduced to its chunks' winners
    (``_topn_reduce``), so no sort in the program is wider than a chunk."""

    EXTRA_METRICS = (M.SORT_TIME,)

    def __init__(self, child, orders: Sequence[SortOrder], n: int,
                 min_bucket: Optional[int] = None):
        super().__init__()
        self.child = child
        self.children = (child,)
        self.orders = list(orders)
        self.n = n
        self.schema = child.schema
        self.min_bucket = resolve_min_bucket(min_bucket)

    def plan_signature(self) -> str:
        return (f"TakeOrdered|{self.n}|"
                f"{[(repr(o.expr), o.ascending, o.nulls_first) for o in self.orders]}|"
                f"{self.schema!r}")

    def _topn_fn(self, cap_key: str):
        from ..utils.compile_cache import cached_jit
        orders, n = self.orders, self.n
        cap = bucket_rows(max(n, 1), self.min_bucket)

        def make():
            def fn(table: DeviceTable) -> DeviceTable:
                s = device_sort_table(
                    _topn_reduce(table, orders, n, cap), orders)
                iota = jnp.arange(s.capacity, dtype=jnp.int32)
                keep = jnp.minimum(s.num_rows, jnp.int32(n))
                mask = iota < keep
                cols = tuple(
                    DeviceColumn(c.data[:cap], jnp.logical_and(
                        c.validity[:cap], mask[:cap]), c.dtype,
                        None if c.lengths is None else c.lengths[:cap])
                    for c in s.columns) if s.capacity > cap else tuple(
                    DeviceColumn(c.data, jnp.logical_and(c.validity, mask),
                                 c.dtype, c.lengths) for c in s.columns)
                out_mask = mask[:cap] if s.capacity > cap else mask
                return DeviceTable(cols, out_mask, keep, s.names)
            return fn
        return cached_jit(self.plan_signature() + cap_key, make,
                          name="sort_topn")

    def execute_columnar(self, pidx: int) -> Iterator[DeviceTable]:
        from ..memory.retry import (split_device_rows, with_retry,
                                    with_retry_split)

        def topn_combine(outs):
            """Half top-n's are each sorted-and-truncated; re-running
            top-n over their concat restores the global order + bound."""
            merged = concat_device_tables(outs)
            return self._topn_fn(f"|cap{merged.capacity}")(merged)

        from .fallback import quarantine_on_failure
        state = None
        for batch in self.child_device_batches(pidx):
            # note-only boundary: top-n state spans batches, so a terminal
            # failure can't fall back mid-stream — but it quarantines
            with quarantine_on_failure(self), \
                    self.metrics.timed(M.SORT_TIME):
                top = with_retry_split(
                    lambda b: self._topn_fn(f"|cap{b.capacity}")(b), batch,
                    splitter=split_device_rows, combiner=topn_combine,
                    scope="topn", context=self.node_desc())
                if state is None:
                    state = top
                else:
                    merged = concat_device_tables([state, top])
                    # spill-only: the running state is already bounded at
                    # the bucketed n-row capacity
                    state = with_retry(
                        self._topn_fn(f"|cap{merged.capacity}"), merged,
                        scope="topn-merge", context=self.node_desc())
        if state is not None:
            self.account_batch()
            yield state

    def node_desc(self):
        return f"n={self.n}"


class TpuSortExec(TpuExec):
    EXTRA_METRICS = (M.SORT_TIME,)

    def __init__(self, child: PhysicalPlan, orders: Sequence[SortOrder],
                 min_bucket: Optional[int] = None,
                 batch_bytes: int = 512 * 1024 * 1024):
        super().__init__()
        self.child = child
        self.children = (child,)
        self.orders = list(orders)
        self.schema = child.schema
        self.min_bucket = resolve_min_bucket(min_bucket)
        self.batch_bytes = batch_bytes

    def _sort_fn(self, cap_key: str):
        from ..utils.compile_cache import cached_jit
        orders = self.orders
        return cached_jit(self.plan_signature() + cap_key,
                          lambda: (lambda t: device_sort_table(t, orders)),
                          name="sort")

    def _sort_combine(self, outs):
        """Split-and-retry combiner: half-sorts are only locally ordered,
        so re-sort their concat — by combine time the ladder has spilled
        everything else, leaving the merged sort the whole HBM."""
        merged = concat_device_tables(outs)
        return self._sort_fn(f"|cap{merged.capacity}")(merged)

    def execute_columnar(self, pidx: int) -> Iterator[DeviceTable]:
        from ..memory.retry import split_device_rows, with_retry_split
        batches = list(self.child_device_batches(pidx))
        if not batches:
            return
        total_bytes = sum(b.nbytes() for b in batches)
        if len(batches) == 1 or total_bytes <= self.batch_bytes:
            # FullSortSingleBatch mode
            from .fallback import quarantine_on_failure
            table = concat_device_tables(batches) if len(batches) > 1 \
                else batches[0]
            with quarantine_on_failure(self), \
                    self.metrics.timed(M.SORT_TIME):
                out = with_retry_split(
                    lambda t: self._sort_fn(f"|cap{t.capacity}")(t), table,
                    splitter=split_device_rows, combiner=self._sort_combine,
                    scope="sort", context=self.node_desc())
            self.account_batch()
            yield out
            return
        yield from self._out_of_core(batches)

    # -- OutOfCoreSort mode ---------------------------------------------------
    def _out_of_core(self, batches: List[DeviceTable]
                     ) -> Iterator[DeviceTable]:
        from ..memory.catalog import SpillPriorities, get_catalog
        from ..memory.retry import split_device_rows, with_retry_split
        from .fallback import quarantine_on_failure
        catalog = get_catalog()
        runs = []  # (SpillableDeviceTable, active_rows)
        try:
            with quarantine_on_failure(self), \
                    self.metrics.timed(M.SORT_TIME):
                sorted_bs = [with_retry_split(
                    lambda t: self._sort_fn(f"|cap{t.capacity}")(t), b,
                    splitter=split_device_rows,
                    combiner=self._sort_combine,
                    scope="sort", context=self.node_desc())
                    for b in batches]
                # every run's sort dispatches before the host blocks:
                # one batched-funnel transfer resolves all run counts
                counts = resolve_scalars(
                    *[b.num_rows for b in sorted_bs])
                for sorted_b, n in zip(sorted_bs, counts):
                    n = int(n)
                    if n:
                        runs.append((catalog.register(
                            sorted_b, SpillPriorities.INPUT), n))
            yield from self._merge_runs(runs)
        finally:
            for run, _ in runs:
                run.close()

    def _merge_runs(self, runs) -> Iterator[DeviceTable]:
        if not runs:
            return
        k = len(runs)
        target_rows = max(r for _, r in runs)
        chunk = bucket_rows(max(self.min_bucket, target_rows // k),
                            self.min_bucket)
        cursors = [0] * k
        carry: Optional[DeviceTable] = None
        while carry is not None or any(c < n for c, (_, n) in
                                       zip(cursors, runs)):
            inputs: List[DeviceTable] = []
            flags: List[bool] = []
            if carry is not None:
                inputs.append(carry)
                flags.append(False)
            for i, (run, nrows) in enumerate(runs):
                if cursors[i] >= nrows:
                    continue
                with run as t:
                    inputs.append(slice_rows(t, cursors[i], chunk))
                    flags.append(False)
                    cursors[i] = min(cursors[i] + chunk, nrows)
                    if cursors[i] < nrows:  # next unseen row = sentinel
                        inputs.append(slice_rows(t, cursors[i], 1))
                        flags.append(True)
            tagged = [append_column(
                t, _SENT, DeviceColumn(
                    jnp.full(t.capacity, f, dtype=bool),
                    jnp.ones(t.capacity, dtype=bool), dt.BOOLEAN, None))
                for t, f in zip(inputs, flags)]
            merged = concat_device_tables(tagged, self.min_bucket)
            with self.metrics.timed(M.SORT_TIME):
                # spill-only: merge inputs are fixed-size chunks already
                # bounded by the out-of-core chunking policy
                from ..memory.retry import with_retry
                sorted_m = with_retry(
                    self._sort_fn(f"|merge{merged.capacity}"), merged,
                    scope="sort-merge", context=self.node_desc())
            sent = jnp.logical_and(sorted_m.column(_SENT).data,
                                   sorted_m.row_mask)
            # the emitted-count decision stays on device; ONE batched
            # transfer then resolves both loop controls (emit count and
            # carry count) instead of three scalar syncs per round
            emit_dev = jnp.where(jnp.any(sent),
                                 jnp.argmax(sent).astype(jnp.int32),
                                 sorted_m.num_rows)
            iota = jnp.arange(sorted_m.capacity, dtype=jnp.int32)
            rest_mask = jnp.logical_and(
                iota >= emit_dev,
                jnp.logical_not(sorted_m.column(_SENT).data))
            rest = drop_column(sorted_m.filter_mask(rest_mask), _SENT)
            emit_n, rest_n = resolve_scalars(emit_dev, rest.num_rows)
            emit_n, rest_n = int(emit_n), int(rest_n)
            if emit_n > 0:
                out = drop_column(
                    sorted_m.filter_mask(iota < emit_n), _SENT)
                self.account_batch(rows=emit_n)
                yield shrink_to_fit(out, self.min_bucket, num_rows=emit_n)
            carry = shrink_to_fit(rest, self.min_bucket, num_rows=rest_n) \
                if rest_n else None

    def node_desc(self):
        return ", ".join(f"{o.expr!r} {'ASC' if o.ascending else 'DESC'}"
                         for o in self.orders)
