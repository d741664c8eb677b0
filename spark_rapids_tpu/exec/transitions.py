"""Host<->device transitions and batch coalescing.

Reference equivalents:
- ``HostToDeviceExec``   ~ GpuRowToColumnarExec / HostColumnarToGpu
- ``DeviceToHostExec``   ~ GpuColumnarToRowExec / GpuBringBackToHost
- ``TpuCoalesceBatchesExec`` ~ GpuCoalesceBatches (GpuCoalesceBatches.scala:528)

The transition inserter (plan/transitions.py) places these where device
sections start/end, exactly like GpuTransitionOverrides.scala:37.
"""
from __future__ import annotations

import threading
import weakref
from typing import Iterator, List, Optional

from ..columnar.device import (DeviceTable, bucket_rows,
                               concat_device_tables, resolve_min_bucket)
from ..columnar.host import HostTable
from ..conf import register_conf
from ..plan.physical import PhysicalPlan
from ..utils import faults
from ..utils import metrics as M
from ..utils import movement
from ..utils.tracing import get_tracer
from .base import TpuExec

__all__ = ["HostToDeviceExec", "DeviceToHostExec", "TpuCoalesceBatchesExec",
           "clear_upload_cache", "upload_cache_stats", "mark_exclusive",
           "take_exclusive"]

SCAN_DEVICE_CACHE = register_conf(
    "spark.rapids.tpu.scan.deviceCache.enabled",
    "Keep scanned batches device-resident across executions. Sources that "
    "re-yield identical host batches (in-memory tables, cached scans) skip "
    "the host->device re-upload entirely; entries die with their source "
    "batch and a device OOM drops the whole cache. (reference: "
    "ParquetCachedBatchSerializer keeps Spark-cached data as device "
    "batches, com/nvidia/spark/rapids/shims/ParquetCachedBatchSerializer)",
    True)

SCAN_DEVICE_CACHE_MAX_BYTES = register_conf(
    "spark.rapids.tpu.scan.deviceCache.maxBytes",
    "Device-byte budget for the scan upload cache; uploads past the budget "
    "are not cached (data still flows, uncached). 0 disables caching.",
    2 << 30)

COALESCE_AFTER_UPLOAD = register_conf(
    "spark.rapids.tpu.coalesce.afterUpload.enabled",
    "Insert a TpuCoalesceBatchesExec above every host->device upload so "
    "many small scanned batches stitch into full-size device batches "
    "before compute (reference: GpuCoalesceBatches above GpuRowToColumnar "
    "via childrenCoalesceGoal).", False)

COALESCE_TARGET_BYTES = register_conf(
    "spark.rapids.tpu.coalesce.targetBytes",
    "Byte-based flush target for TpuCoalesceBatchesExec, alongside the "
    "row goal: a pending set flushes once its device bytes reach this "
    "bound even when the row target is far away, so wide schemas cannot "
    "accumulate an OOM-sized concat (reference: the TargetSize coalesce "
    "goal is byte-denominated, GpuCoalesceBatches.scala:93-200). "
    "0 disables the byte bound.", 512 * 1024 * 1024,
    checker=lambda v: None if int(v) >= 0 else "must be >= 0")


# ---------------------------------------------------------------------------
# donation-safe hand-off: an uploaded batch that is NOT retained by the
# upload cache is exclusively owned by its consumer, so a fused stage may
# donate its buffers to XLA (exec/wholestage.py donate_argnums) — cutting
# peak HBM per batch. Cached uploads are shared across executions and must
# never be donated. The mark rides the DeviceTable instance (plain
# dataclass) and is consumed exactly once.
# ---------------------------------------------------------------------------
def mark_exclusive(table: DeviceTable, origin: Optional[HostTable] = None,
                   min_bucket: Optional[int] = None) -> DeviceTable:
    table._tpu_exclusive = True
    if origin is not None:
        # donated-input OOM recovery (memory/retry.py wrap_jit_donating):
        # a failed donating dispatch may have consumed the buffers, so the
        # ladder re-materializes from the retained host-side origin — the
        # host batch is alive for the duration of the consumer's dispatch
        # anyway, so this pins no extra memory
        table._tpu_remat = lambda: DeviceTable.from_host(origin, min_bucket)  # srtpu: retry-ok(this lambda IS the ladder's recovery hook — wrap_jit_donating invokes it from inside the retry scope after spilling) srtpu: memtrack-ok(the fresh table replaces a donated batch inside the consuming dispatch and dies with it — never long-lived HBM)
    return table


def take_exclusive(table: DeviceTable) -> bool:
    """True once per exclusively-owned batch (clears the mark: after the
    consumer donates — or declines to — the buffers are no longer safely
    donatable by anyone else)."""
    if getattr(table, "_tpu_exclusive", False):
        table._tpu_exclusive = False
        return True
    return False

# Upload memoization keyed by host-batch IDENTITY (HostTable is mutable-ish
# and unhashable; identity is the right equivalence anyway — sources that
# cache decoded batches re-yield the same objects). A weakref death-callback
# removes the entry the moment its source batch is collected, so a recycled
# id() can never alias a stale upload.
#
# All cache state is guarded by _UPLOAD_LOCK. It must be an RLock: the
# weakref death-callback can fire from a GC pass triggered at any
# allocation, including while this thread already holds the lock. Lock
# order is catalog lock -> _UPLOAD_LOCK (the catalog reads cached bytes
# under its own lock); nothing here calls into the catalog while holding
# _UPLOAD_LOCK.
_UPLOAD_LOCK = threading.RLock()
_UPLOAD_CACHE: dict = {}   # id(batch) -> (weakref, {min_bucket: DeviceTable})
_CACHED_BYTES = 0          # running device-byte total of cached uploads
_CACHE_HITS = 0
_CACHE_INSERTS = 0
_CACHE_EVICTIONS = 0
_OOM_HOOKED = False


def _cached_bytes() -> int:
    with _UPLOAD_LOCK:
        return _CACHED_BYTES


def _drop_entry(key: int) -> None:
    """Weakref death-callback: remove a dead batch's uploads and keep the
    running byte counter consistent."""
    global _CACHED_BYTES, _CACHE_EVICTIONS
    with _UPLOAD_LOCK:
        entry = _UPLOAD_CACHE.pop(key, None)
        if entry is not None:
            _CACHED_BYTES -= sum(dt.nbytes() for dt in entry[1].values())
            _CACHE_EVICTIONS += 1


def clear_upload_cache() -> int:
    """Drop all device-resident scan uploads; returns bytes released."""
    global _CACHED_BYTES
    with _UPLOAD_LOCK:
        freed = _CACHED_BYTES
        _UPLOAD_CACHE.clear()
        _CACHED_BYTES = 0
    return freed


def upload_cache_stats() -> dict:
    """Process-wide upload-cache counters (feeds utils.metrics.StatsRegistry
    and per-query event-log deltas)."""
    with _UPLOAD_LOCK:
        return {"entries": len(_UPLOAD_CACHE), "bytes": _CACHED_BYTES,
                "hits": _CACHE_HITS, "inserts": _CACHE_INSERTS,
                "evictions": _CACHE_EVICTIONS}


def _hook_oom() -> None:
    """Register the cache with the buffer catalog: droppable on device OOM,
    and its device bytes visible to the catalog's peak/OOM accounting."""
    global _OOM_HOOKED
    if _OOM_HOOKED:
        return
    from ..memory.catalog import get_catalog
    cat = get_catalog()
    cat.register_oom_callback(clear_upload_cache)
    cat.register_external_bytes("upload_cache", _cached_bytes)
    _OOM_HOOKED = True


# movement-observatory site identity (utils/movement.py SITES)
_MOVE_UPLOAD = ("spark_rapids_tpu/exec/transitions.py"
                "::HostToDeviceExec._upload_retryable")


class HostToDeviceExec(TpuExec):
    EXTRA_METRICS = (M.UPLOAD_TIME, M.UPLOAD_BYTES, M.UPLOAD_CACHE_HITS,
                     M.PIPELINE_WAIT)

    def __init__(self, child: PhysicalPlan, min_bucket: Optional[int] = None,
                 cache_max_bytes: int = 0):
        super().__init__()
        self.child = child
        self.children = (child,)
        self.schema = child.schema
        self.min_bucket = resolve_min_bucket(min_bucket)
        self.cache_max_bytes = cache_max_bytes

    def _upload_retryable(self, batch: HostTable) -> DeviceTable:
        """One H2D upload under the full OOM ladder (memory/retry.py):
        spill → retry → split the HOST batch and upload the halves (each
        half needs half the device allocation) → structured failure."""
        from ..memory.retry import split_host_rows, with_retry_split
        min_bucket = self.min_bucket

        def upload(hb: HostTable) -> DeviceTable:
            action = faults.fire("h2d.upload")
            if action is not None and action != "delay":
                raise faults.FaultInjectedError("h2d.upload", action)
            return DeviceTable.from_host(hb, min_bucket)  # srtpu: memtrack-ok(upload-cache bytes are accounted via register_external_bytes + clear_upload_cache OOM hook; uncached uploads are consumed/donated by the fused chain)

        def combine(outs):
            return concat_device_tables(outs, min_bucket)

        t0 = movement.clock()
        with get_tracer().span("h2d", "upload",
                               rows=int(batch.num_rows)) as span:  # srtpu: sync-ok(HostTable.num_rows is a host int on the upload side)
            dtb = with_retry_split(upload, batch, splitter=split_host_rows,
                                   combiner=combine, scope="h2d-upload",
                                   context=f"rows={int(batch.num_rows)}",  # srtpu: sync-ok(HostTable.num_rows is a host int on the upload side)
                                   fault_point="alloc.upload")
            span.note(bytes=dtb.nbytes())
        movement.note_h2d(_MOVE_UPLOAD, dtb.nbytes, t0, origin=batch)
        return dtb

    def _upload(self, batch: HostTable) -> DeviceTable:
        global _CACHED_BYTES, _CACHE_HITS, _CACHE_INSERTS
        if not self.cache_max_bytes:
            dtb = self._upload_retryable(batch)
            self.metrics.add(M.UPLOAD_BYTES, dtb.nbytes())
            return mark_exclusive(dtb, origin=batch,
                                  min_bucket=self.min_bucket)
        key = id(batch)
        with _UPLOAD_LOCK:
            entry = _UPLOAD_CACHE.get(key)
            hit = None
            if entry is not None and entry[0]() is batch:
                hit = entry[1].get(self.min_bucket)
                if hit is not None:
                    _CACHE_HITS += 1
        if hit is not None:
            self.metrics.add(M.UPLOAD_CACHE_HITS, 1)
            return hit
        dtb = self._upload_retryable(batch)
        nbytes = dtb.nbytes()
        self.metrics.add(M.UPLOAD_BYTES, nbytes)
        cached = False
        with _UPLOAD_LOCK:
            if _CACHED_BYTES + nbytes <= self.cache_max_bytes:
                entry = _UPLOAD_CACHE.get(key)
                try:
                    if entry is None or entry[0]() is not batch:
                        if entry is not None:  # stale id-aliased entry
                            _CACHED_BYTES -= sum(
                                dt.nbytes() for dt in entry[1].values())
                        ref = weakref.ref(
                            batch, lambda _r, k=key: _drop_entry(k))
                        entry = _UPLOAD_CACHE[key] = (ref, {})
                    if self.min_bucket not in entry[1]:
                        entry[1][self.min_bucket] = dtb
                        _CACHED_BYTES += nbytes
                        _CACHE_INSERTS += 1
                        cached = True
                except TypeError:
                    pass  # un-weakref-able batch type: serve uncached
        if cached:
            # outside _UPLOAD_LOCK: these take the catalog lock (lock order
            # is catalog -> upload, never the reverse)
            _hook_oom()
            from ..memory.catalog import peek_catalog
            cat = peek_catalog()
            if cat is not None:
                cat.note_external_change()
        else:
            # not retained by the cache: the consumer owns the only
            # reference, so fused stages may donate it (wholestage.py)
            mark_exclusive(dtb, origin=batch, min_bucket=self.min_bucket)
        return dtb

    def execute_columnar(self, pidx: int) -> Iterator[DeviceTable]:
        # stage boundary: host decode/IO runs on a prefetch worker so the
        # NEXT batch decodes while THIS one uploads (double-buffered via
        # the bounded queue; parallel/pipeline.py)
        from ..parallel.pipeline import maybe_prefetched, stage_name
        child = maybe_prefetched(
            lambda: self.child.execute(pidx),
            stage=f"decode:{stage_name(self.child)}", registry=self.metrics)
        for batch in child:
            with self.metrics.timed(M.UPLOAD_TIME):
                dtb = self._upload(batch)
            self.metrics.add(M.NUM_OUTPUT_BATCHES, 1)
            self.metrics.add(M.NUM_OUTPUT_ROWS, batch.num_rows)
            # batchRows histograms are observed by instrument_plan (once per
            # node) — observing here too would double-count under profiling
            yield dtb


class DeviceToHostExec(PhysicalPlan):
    def __init__(self, child: TpuExec):
        self.child = child
        self.children = (child,)
        self.schema = child.schema
        self.metrics = M.MetricRegistry()

    @property
    def num_partitions(self) -> int:
        return self.child.num_partitions

    def device_batches(self, pidx: int) -> List[DeviceTable]:
        """Drain the child's device batches WITHOUT materializing — the
        accumulate half of the deferred-D2H contract. Dispatch of later
        batches overlaps device execution of earlier ones (JAX async
        dispatch); nothing here blocks on device state."""
        # stage boundary: jitted compute (async dispatch) keeps running on
        # the prefetch worker while this thread accumulates/downloads
        from ..parallel.pipeline import maybe_prefetched, stage_name
        child = maybe_prefetched(
            lambda: self.child.execute_columnar(pidx),
            stage=f"compute:{stage_name(self.child)}", registry=self.metrics)
        return list(child)

    def download(self, batches: List[DeviceTable]) -> List[HostTable]:
        """Materialize accumulated device batches in ONE bulk device_get
        (columnar/device.py to_host_batched) — the other half of the
        deferred-D2H contract; pipelined_collect calls this once per
        output drain across every partition's batches."""
        from ..columnar.device import to_host_batched
        if not batches:
            return []
        with self.metrics.timed(M.DOWNLOAD_TIME):
            hts = to_host_batched(batches)     # the "d2h" span is inside
        for batch, ht in zip(batches, hts):
            self.metrics.add(M.DOWNLOAD_BYTES, batch.nbytes())
            self.metrics.add(M.NUM_OUTPUT_BATCHES, 1)
            self.metrics.add(M.NUM_OUTPUT_ROWS, ht.num_rows)
        return hts

    def execute(self, pidx: int) -> Iterator[HostTable]:
        from ..columnar.device import async_enabled
        if async_enabled():
            # deferred D2H: accumulate the partition's device batches,
            # then one bulk transfer for the whole drain
            yield from self.download(self.device_batches(pidx))
            return
        # sync-forcing debug mode (spark.rapids.tpu.async.enabled=false):
        # one blocking to_host per batch, so each download blocks at its
        # own site in the ledger/trace
        from ..parallel.pipeline import maybe_prefetched, stage_name
        child = maybe_prefetched(
            lambda: self.child.execute_columnar(pidx),
            stage=f"compute:{stage_name(self.child)}", registry=self.metrics)
        for batch in child:
            with self.metrics.timed(M.DOWNLOAD_TIME):
                ht = batch.to_host()           # the "d2h" span is inside
            self.metrics.add(M.DOWNLOAD_BYTES, batch.nbytes())
            self.metrics.add(M.NUM_OUTPUT_BATCHES, 1)
            self.metrics.add(M.NUM_OUTPUT_ROWS, ht.num_rows)
            yield ht


class TpuCoalesceBatchesExec(TpuExec):
    """Concatenate small device batches up to a target row and/or byte goal.

    The reference distinguishes TargetSize vs RequireSingleBatch goals
    (CoalesceGoal lattice, GpuCoalesceBatches.scala:93-200); here the goal
    is expressed in rows (``target_rows``), bytes (``target_bytes`` — the
    TargetSize analogue, so wide schemas cannot accumulate an OOM-sized
    flush long before the row goal fills), or single-batch
    (``require_single``).
    """

    EXTRA_METRICS = (M.COALESCED_BYTES,)

    def __init__(self, child: PhysicalPlan, target_rows: int = 1 << 20,
                 require_single: bool = False, min_bucket: Optional[int] = None,
                 target_bytes: int = 0):
        super().__init__()
        self.child = child
        self.children = (child,)
        self.schema = child.schema
        self.target_rows = target_rows
        self.target_bytes = int(target_bytes)
        self.require_single = require_single
        self.min_bucket = resolve_min_bucket(min_bucket)

    def node_desc(self) -> str:
        if self.require_single:
            return "goal=single"
        goal = f"rows={self.target_rows}"
        if self.target_bytes:
            goal += f" bytes={self.target_bytes}"
        return goal

    def _over_bytes(self, pending_bytes: int, extra: int = 0) -> bool:
        return bool(self.target_bytes) \
            and pending_bytes + extra > self.target_bytes

    def execute_columnar(self, pidx: int) -> Iterator[DeviceTable]:
        pending: List[DeviceTable] = []
        pending_rows = 0
        pending_bytes = 0
        for batch in self.child_device_batches(pidx):
            # capacity, not num_rows: the goal accounting stays sync-free
            # (capacity >= num_rows, so the row/byte goals flush
            # conservatively — never an over-sized concat)
            n = batch.capacity
            nb = batch.nbytes()
            if self.require_single:
                pending.append(batch)
                continue
            if pending and (pending_rows + n > self.target_rows
                            or self._over_bytes(pending_bytes, nb)):
                yield self._flush(pending)
                pending, pending_rows, pending_bytes = [], 0, 0
            pending.append(batch)
            pending_rows += n
            pending_bytes += nb
            if pending_rows >= self.target_rows \
                    or self._over_bytes(pending_bytes):
                yield self._flush(pending)
                pending, pending_rows, pending_bytes = [], 0, 0
        if pending:
            yield self._flush(pending)

    def _flush(self, pending: List[DeviceTable]) -> DeviceTable:
        from ..memory.retry import with_retry
        with self.metrics.timed(M.OP_TIME):
            # spill-only retry: a half-concat is not the requested
            # coalesce (and under require_single would be wrong) — the
            # byte-goal bound already caps the flush size
            out = with_retry(concat_device_tables, pending, self.min_bucket,
                             scope="coalesce", context=self.node_desc())
        self.metrics.add(M.NUM_OUTPUT_BATCHES, 1)
        self.metrics.add(M.COALESCED_BYTES, out.nbytes())
        return out
