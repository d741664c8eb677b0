"""Buffer catalog: global registry of spillable device tables.

Reference mapping (SURVEY §2.2):
- ``BufferCatalog``        ~ RapidsBufferCatalog.scala:40,156
- ``SpillableDeviceTable`` ~ SpillableColumnarBatch.scala (operator-facing
  handle: register once, re-acquire on access, migrates tiers underneath)
- ``synchronous_spill``    ~ RapidsBufferStore.synchronousSpill +
  DeviceMemoryEventHandler.scala:33 (OOM callback -> spill)
- spill priorities         ~ SpillPriorities.scala
"""
from __future__ import annotations

import itertools
import threading
import warnings
from collections import deque
from typing import Callable, Dict, List, Optional

import jax

from .. import native
from ..columnar.device import DeviceTable
from ..conf import RapidsConf, register_conf
from ..utils.memprof import active as _memprof
from .stores import (DeviceStore, DiskStore, HostStore, StorageTier,
                     StoredTable, _host_arrays_to_table)

DEVICE_POOL_BYTES = register_conf(
    "spark.rapids.tpu.memory.pool.size",
    "Logical HBM budget in bytes for spillable buffers (reference: RMM pool "
    "sizing, GpuDeviceManager.scala:176-222). 0 = derive from device.",
    0)

DEVICE_POOL_MODE = register_conf(
    "spark.rapids.tpu.memory.pool.mode",
    "Buffer-pool accounting mode (reference: the RMM DEFAULT/POOL/ARENA/"
    "ASYNC selection, GpuDeviceManager.scala:224): 'logical' enforces the "
    "budget by spilling lowest-priority buffers; 'none' disables budget "
    "accounting (XLA's own allocator arbitrates, like RMM DEFAULT); "
    "'strict' raises when a registration cannot fit even after spilling "
    "(surface OOM early instead of overcommitting).", "logical",
    checker=lambda v: None if v in ("logical", "none", "strict")
    else f"must be one of logical/none/strict, got {v!r}")

OOM_SPILL_ENABLED = register_conf(
    "spark.rapids.memory.gpu.oomSpill.enabled",
    "Spill lowest-priority buffers when the device budget is exceeded "
    "(reference: DeviceMemoryEventHandler).", True)

DISK_SPILL_DIRECT = register_conf(
    "spark.rapids.tpu.memory.disk.direct",
    "Restore disk-spilled buffers through read-only memory maps so the "
    "device upload streams straight from the file (the GPUDirect-Storage "
    "analogue; reference: RapidsGdsStore). false uses compact npz files.",
    True)

DISK_SPILL_CHECKSUM = register_conf(
    "spark.rapids.tpu.memory.disk.checksum",
    "CRC32-checksum disk-spilled buffers on write and verify them on "
    "restore; a mismatch raises SpillCorruptionError, which the shuffle "
    "read path converts to fetch-failed -> recompute instead of serving "
    "silently corrupt rows.", True)

DEVICE_POOL_MAX_FRACTION = register_conf(
    "spark.rapids.memory.gpu.maxAllocFraction",
    "Upper bound on the fraction of device HBM the spillable pool may "
    "claim (reference: RapidsConf RMM_ALLOC_MAX_FRACTION).", 1.0,
    conf_type=float)

MEMORY_DEBUG = register_conf(
    "spark.rapids.tpu.memory.debug",
    "Sanitizer mode for the buffer catalog (reference: RMM debug allocator / "
    "spark.rapids.memory.gpu.debug): double-free and release-underflow "
    "raise, freed host buffers are poisoned (0xDD), buffer creation sites "
    "are recorded, and accounting invariants are checked after every "
    "operation.", False)

__all__ = ["SpillPriorities", "BufferCatalog", "SpillableDeviceTable",
           "DebugMemoryError", "get_catalog", "set_catalog", "peek_catalog"]


class DebugMemoryError(RuntimeError):
    """Raised by the debug allocator on misuse (double free, underflow,
    use-after-close, accounting drift)."""


class SpillPriorities:
    """Lower value spills first (reference: SpillPriorities.scala)."""
    INPUT = 0
    OUTPUT_FOR_SHUFFLE = 10
    BROADCAST = 50
    ACTIVE_ON_DECK = 100


class BufferCatalog:
    def __init__(self, conf: Optional[RapidsConf] = None,
                 device_limit: Optional[int] = None,
                 host_limit: Optional[int] = None,
                 disk_dir: Optional[str] = None):
        conf = conf or RapidsConf()
        if device_limit is None:
            device_limit = conf.get(DEVICE_POOL_BYTES)
            if not device_limit:
                # pool = allocFraction of detected HBM, capped by
                # maxAllocFraction (reference: GpuDeviceManager pool sizing)
                from ..conf import DEVICE_POOL_FRACTION
                frac = float(conf.get(DEVICE_POOL_FRACTION))
                frac = min(frac, float(conf.get(DEVICE_POOL_MAX_FRACTION)))
                device_limit = int(_device_memory_bytes() * frac)
        from ..conf import HOST_SPILL_STORAGE_SIZE
        if host_limit is None:
            host_limit = conf.get(HOST_SPILL_STORAGE_SIZE)
        self.device = DeviceStore(device_limit)
        self.host = HostStore(host_limit)
        self.disk = DiskStore(disk_dir,
                              direct=bool(conf.get(DISK_SPILL_DIRECT)),
                              checksum=bool(conf.get(DISK_SPILL_CHECKSUM)))
        self._buffers: Dict[int, StoredTable] = {}
        # persistent device-tier spill queue (reference: RapidsBufferStore's
        # HashedPriorityQueue — O(log n) membership updates instead of
        # rebuilding a heap per spill pass); native C++ when built
        self._spill_pq = native.HashedPriorityQueue()
        self._pq_handles: Dict[int, int] = {}  # buffer_id -> pq handle
        self._ids = itertools.count()
        self._lock = threading.RLock()
        self._oom_callbacks: List = []
        self._oom_spill = conf.get(OOM_SPILL_ENABLED)
        self._pool_mode = conf.get(DEVICE_POOL_MODE)
        self.oom_events = 0  # runtime RESOURCE_EXHAUSTED recoveries
        self.spill_count = {StorageTier.HOST: 0, StorageTier.DISK: 0}
        self.spilled_bytes = {StorageTier.HOST: 0, StorageTier.DISK: 0}
        # device memory held OUTSIDE the spill framework but accountable to
        # this process (e.g. the scan upload cache): name -> byte-count fn,
        # plus a cached last-known value per source so the allocation hot
        # path (register/acquire -> _note_peak_locked) never calls out
        # through a foreign lock; sources push updates via
        # note_external_change(), cold paths (stats/oom_dump) refresh
        self._external_bytes: Dict[str, Callable[[], int]] = {}
        self._external_cache: Dict[str, int] = {}
        self.peak_device_bytes = 0
        self.oom_callback_errors = 0
        self.diagnostics: deque = deque(maxlen=64)
        self._debug = bool(conf.get(MEMORY_DEBUG))
        self._sites: Dict[int, str] = {}    # buffer_id -> creation site
        self._closed_ids: set = set()       # debug: double-free detection

    # -- registration ---------------------------------------------------------
    def register(self, table: DeviceTable,
                 priority: int = SpillPriorities.INPUT
                 ) -> "SpillableDeviceTable":
        # a catalog-registered table is shared/spillable by definition —
        # strip any exclusive-ownership mark so no downstream fused stage
        # donates buffers this handle re-serves (exec/transitions.py)
        if getattr(table, "_tpu_exclusive", False):
            table._tpu_exclusive = False
        nbytes = table.nbytes()
        with self._lock:
            if self._pool_mode != "none" and not self.device.fits(nbytes) \
                    and self._oom_spill:
                self.synchronous_spill(
                    nbytes - (self.device.limit_bytes - self.device.used_bytes))
            if self._pool_mode == "strict" and not self.device.fits(nbytes):
                msg = (f"strict pool mode: {nbytes} bytes cannot fit "
                       f"(used={self.device.used_bytes}, "
                       f"limit={self.device.limit_bytes})")
                mp = _memprof()
                if mp is not None:
                    # attributed dump BEFORE the exception propagates
                    # (reference: oomDumpDir state dumps)
                    mp.oom_postmortem(f"allocation failure: {msg}",
                                      catalog=self)
                raise MemoryError(msg)
            bid = next(self._ids)
            stored = StoredTable(bid, table, priority, nbytes)
            self._buffers[bid] = stored
            self.device.used_bytes += nbytes
            self._note_peak_locked()
            self._pq_handles[bid] = self._spill_pq.push(priority, bid)
            mp = _memprof()
            if mp is not None:
                mp.record("register", bid, nbytes, tier="DEVICE",
                          ext_bytes=sum(self._external_cache.values()))
            if self._debug:
                import traceback
                frame = traceback.extract_stack(limit=4)[0]
                self._sites[bid] = f"{frame.filename}:{frame.lineno}"
                self._check_invariants()
        return SpillableDeviceTable(self, bid)

    # -- spill machinery ------------------------------------------------------
    def synchronous_spill(self, target_bytes: int) -> int:
        """Move lowest-priority device buffers down-tier until target freed
        (reference: RapidsBufferStore.synchronousSpill)."""
        freed = 0
        with self._lock:
            pinned = []  # (priority, bid) popped but in use; re-pushed after
            try:
                while freed < target_bytes:
                    entry = self._spill_pq.pop()
                    if entry is None:
                        break
                    priority, bid = entry
                    stored = self._buffers.get(bid)
                    if stored is None or stored.tier != StorageTier.DEVICE:
                        self._pq_handles.pop(bid, None)
                        continue
                    if stored.refcount > 0:
                        # pop the handle too: the entry left the queue, so
                        # a map entry pointing at the popped handle is
                        # stale — a later remove() on it would corrupt the
                        # pq once handles recycle. The finally block
                        # re-pushes under a fresh handle.
                        self._pq_handles.pop(bid, None)
                        pinned.append((priority, bid))
                        continue
                    self._pq_handles.pop(bid, None)
                    try:
                        self._spill_one(stored)
                    except Exception:
                        # spill target failed (e.g. disk full): keep the
                        # buffer spillable for a later pass
                        pinned.append((priority, bid))
                        raise
                    freed += stored.size_bytes
            finally:
                for priority, bid in pinned:
                    self._pq_handles[bid] = self._spill_pq.push(priority, bid)
        return freed

    def _spill_one(self, stored: StoredTable):
        from ..utils.tracing import get_tracer
        # attribute the spilled bytes to whichever operator is executing
        # (instrumented runs only): the spill fires on behalf of that node's
        # allocation even though its victim may belong to another node
        from ..utils.node_context import current_registry
        reg = current_registry()
        if reg is not None:
            from ..utils.metrics import SPILL_BYTES
            reg.add(SPILL_BYTES, stored.size_bytes)
        with get_tracer().span("spill", "spill", bytes=stored.size_bytes,
                               buffer=stored.buffer_id):
            self._spill_one_inner(stored)

    def _spill_one_inner(self, stored: StoredTable):
        # device -> host; if host full, push host's lowest priority to disk
        if not self.host.fits(stored.size_bytes):
            self._spill_host_to_disk(stored.size_bytes)
        if self.host.fits(stored.size_bytes):
            self.host.put(stored)
            self.device.used_bytes -= stored.size_bytes
            self.spill_count[StorageTier.HOST] += 1
            self.spilled_bytes[StorageTier.HOST] += stored.size_bytes
            mp = _memprof()
            if mp is not None:
                mp.record("spill", stored.buffer_id, stored.size_bytes,
                          tier="HOST",
                          ext_bytes=sum(self._external_cache.values()))
            if self._debug and stored.host_arrays is not None:
                # jax-backed views are read-only; debug mode owns writable
                # copies so close can poison them (use-after-free detection)
                import numpy as _np
                stored.host_arrays = {k: _np.array(v)
                                      for k, v in stored.host_arrays.items()}
        else:  # straight to disk (host tier full even after its own spills)
            from .stores import _table_to_host_arrays
            arrays, meta = _table_to_host_arrays(stored.device_table)
            stored.host_arrays = arrays
            stored.meta = meta
            stored.device_table = None
            self.disk.put(stored)
            self.device.used_bytes -= stored.size_bytes
            self.spill_count[StorageTier.DISK] += 1
            self.spilled_bytes[StorageTier.DISK] += stored.size_bytes
            mp = _memprof()
            if mp is not None:
                mp.record("spill", stored.buffer_id, stored.size_bytes,
                          tier="DISK",
                          ext_bytes=sum(self._external_cache.values()))

    def _spill_host_to_disk(self, need_bytes: int):
        victims = sorted((s for s in self._buffers.values()
                          if s.tier == StorageTier.HOST and s.refcount == 0),
                         key=lambda s: s.priority)
        freed = 0
        for s in victims:
            if self.host.fits(need_bytes):
                break
            self.disk.put(s)
            self.host.used_bytes -= s.size_bytes
            self.spill_count[StorageTier.DISK] += 1
            self.spilled_bytes[StorageTier.DISK] += s.size_bytes
            freed += s.size_bytes

    # -- access ---------------------------------------------------------------
    def acquire(self, buffer_id: int) -> DeviceTable:
        with self._lock:
            if self._debug and buffer_id in self._closed_ids:
                raise DebugMemoryError(
                    f"use-after-close of buffer {buffer_id} "
                    f"(created at {self._sites.get(buffer_id, '?')})")
            stored = self._buffers[buffer_id]
            assert not stored.closed, "buffer already closed"
            # pin first so spill passes triggered below can't victimize the
            # buffer being restored
            stored.refcount += 1
            if stored.tier == StorageTier.DISK:
                arrays = self.disk.load(stored)
                stored.host_arrays = arrays
                self.disk.drop(stored)
                stored.tier = StorageTier.HOST
                self.host.used_bytes += stored.size_bytes
                mp = _memprof()
                if mp is not None:
                    mp.record("disk_load", buffer_id, stored.size_bytes,
                              tier="HOST")
            if stored.tier == StorageTier.HOST:
                if not self.device.fits(stored.size_bytes) and self._oom_spill:
                    self.synchronous_spill(stored.size_bytes)
                from ..utils.tracing import get_tracer
                # cat="memory": restore time is memory pressure the
                # critical path should see (tools/trace.py
                # memory_pressure bucket), unlike the spill span above
                with get_tracer().span("restore", "memory",
                                       bytes=stored.size_bytes,
                                       buffer=buffer_id):
                    table = _host_arrays_to_table(stored.host_arrays,
                                                  stored.meta)
                self.host.drop(stored)
                stored.device_table = table
                stored.tier = StorageTier.DEVICE
                self.device.used_bytes += stored.size_bytes
                self._note_peak_locked()
                if buffer_id not in self._pq_handles:
                    self._pq_handles[buffer_id] = \
                        self._spill_pq.push(stored.priority, buffer_id)
                mp = _memprof()
                if mp is not None:
                    mp.record("restore", buffer_id, stored.size_bytes,
                              tier="DEVICE",
                              ext_bytes=sum(self._external_cache.values()))
            return stored.device_table

    def release(self, buffer_id: int):
        with self._lock:
            stored = self._buffers.get(buffer_id)
            if stored is None:
                if self._debug:
                    raise DebugMemoryError(
                        f"release of unknown/closed buffer {buffer_id}")
                return
            if self._debug and stored.refcount <= 0:
                raise DebugMemoryError(
                    f"refcount underflow on buffer {buffer_id} "
                    f"(created at {self._sites.get(buffer_id, '?')})")
            stored.refcount = max(0, stored.refcount - 1)

    def close_buffer(self, buffer_id: int):
        with self._lock:
            stored = self._buffers.pop(buffer_id, None)
            if stored is None:
                if self._debug and buffer_id in self._closed_ids:
                    raise DebugMemoryError(
                        f"double free of buffer {buffer_id} "
                        f"(created at {self._sites.get(buffer_id, '?')})")
                return
            stored.closed = True
            if self._debug:
                self._closed_ids.add(buffer_id)
                # poison freed host-tier memory so use-after-free reads are
                # deterministic garbage (RMM debug allocator 0xDD pattern)
                if stored.host_arrays is not None:
                    for arr in stored.host_arrays.values():
                        try:
                            arr.view("uint8").fill(0xDD)
                        except (ValueError, AttributeError):
                            pass  # read-only views can't be poisoned
            handle = self._pq_handles.pop(buffer_id, None)
            if handle is not None:
                self._spill_pq.remove(handle)
            tier_name = StorageTier.NAMES[stored.tier]
            if stored.tier == StorageTier.DEVICE:
                self.device.used_bytes -= stored.size_bytes
            elif stored.tier == StorageTier.HOST:
                self.host.drop(stored)
            else:
                self.disk.drop(stored)
            mp = _memprof()
            if mp is not None:
                mp.record("free", buffer_id, stored.size_bytes,
                          tier=tier_name,
                          ext_bytes=sum(self._external_cache.values()))
            if self._debug:
                self._check_invariants()

    def tier_of(self, buffer_id: int) -> int:
        return self._buffers[buffer_id].tier

    # -- sanitizers (debug allocator mode) ------------------------------------
    def _check_invariants(self):
        """Accounting drift check: per-tier used_bytes must equal the sum of
        resident buffer sizes (called after mutations in debug mode)."""
        dev = sum(s.size_bytes for s in self._buffers.values()
                  if s.tier == StorageTier.DEVICE)
        host = sum(s.size_bytes for s in self._buffers.values()
                   if s.tier == StorageTier.HOST)
        if dev != self.device.used_bytes:
            raise DebugMemoryError(
                f"device accounting drift: tracked {self.device.used_bytes} "
                f"!= resident {dev}")
        if host != self.host.used_bytes:
            raise DebugMemoryError(
                f"host accounting drift: tracked {self.host.used_bytes} "
                f"!= resident {host}")

    def assert_no_leaks(self):
        """End-of-scope leak check: every registered buffer must have been
        closed and no pins outstanding (reference: RMM debug allocator's
        outstanding-allocations report)."""
        with self._lock:
            leaks = [(bid, s.refcount, self._sites.get(bid, "?"))
                     for bid, s in self._buffers.items()]
            if leaks:
                detail = "; ".join(
                    f"buffer {bid} refcount={rc} created at {site}"
                    for bid, rc, site in leaks[:10])
                raise DebugMemoryError(
                    f"{len(leaks)} leaked buffer(s): {detail}")

    def register_oom_callback(self, cb) -> None:
        """Register a zero-arg callable invoked on device OOM before the
        catalog spill; it returns bytes it released (droppable device
        caches — e.g. the scan upload cache — hook in here)."""
        with self._lock:
            if cb not in self._oom_callbacks:
                self._oom_callbacks.append(cb)

    # -- external device-memory accounting ------------------------------------
    def register_external_bytes(self, name: str,
                                fn: Callable[[], int]) -> None:
        """Make device memory held outside the spill framework (e.g. the
        scan upload cache) visible to peak/used accounting and OOM dumps.
        ``fn`` returns the source's current device bytes; it may take its
        own lock (lock order: catalog lock -> source lock)."""
        with self._lock:
            self._refresh_external_locked()
            self._external_bytes[name] = fn
            try:
                self._external_cache[name] = int(fn() or 0)
            except Exception:
                self._external_cache[name] = 0
            self._note_peak_locked()
            mp = _memprof()
            if mp is not None:
                mp.record("external", -1, self._external_cache[name],
                          ext_bytes=sum(self._external_cache.values()))

    def _refresh_external_locked(self) -> Dict[str, int]:
        for name, fn in self._external_bytes.items():
            try:
                self._external_cache[name] = int(fn() or 0)
            except Exception:
                self._external_cache[name] = 0
        return dict(self._external_cache)

    def external_device_bytes(self) -> int:
        with self._lock:
            return sum(self._refresh_external_locked().values())

    def device_in_use_bytes(self) -> int:
        """Catalog-resident + externally-cached device bytes — the number
        OOM diagnostics should reason about."""
        with self._lock:
            return self.device.used_bytes \
                + sum(self._refresh_external_locked().values())

    def _note_peak_locked(self) -> None:
        # hot path (every register/unspill): cached ints only, no calls
        # out through external sources' locks
        used = self.device.used_bytes + sum(self._external_cache.values())
        if used > self.peak_device_bytes:
            self.peak_device_bytes = used

    def note_external_change(self) -> None:
        """External sources call this after growing their device footprint
        so peak accounting reflects it (refreshes the cached counts)."""
        with self._lock:
            self._refresh_external_locked()
            self._note_peak_locked()
            mp = _memprof()
            if mp is not None:
                # keep the flight recorder's external total (and thus peak
                # attribution) in step with _note_peak_locked
                mp.record("external", -1, 0,
                          ext_bytes=sum(self._external_cache.values()))

    def handle_device_oom(self, context: str = "") -> int:
        """Runtime-OOM callback (reference: DeviceMemoryEventHandler.scala:33
        — RMM allocation failure -> synchronous spill -> retry alloc).

        XLA/PJRT exposes no alloc hook, so callers invoke this when a
        device computation raises RESOURCE_EXHAUSTED and retry once. The
        needed allocation size is unknown, so everything spillable moves
        down-tier. Returns bytes freed (0 = nothing left to spill)."""
        from ..utils.tracing import get_tracer
        get_tracer().instant("device_oom", "spill", context=context[:200])
        cb_freed = 0
        with self._lock:
            callbacks = list(self._oom_callbacks)
        for cb in callbacks:
            try:
                cb_freed += int(cb() or 0)
            except Exception as e:
                # a broken cache-dropper must not abort OOM recovery, but it
                # must not fail silently either: the callback's bytes stay
                # resident, so diagnostics have to show why
                name = getattr(cb, "__qualname__",
                               getattr(cb, "__name__", repr(cb)))
                msg = (f"OOM callback {name} failed: "
                       f"{type(e).__name__}: {e}")
                with self._lock:
                    self.oom_callback_errors += 1
                    self.diagnostics.append(msg)
                warnings.warn(msg, RuntimeWarning)
        with self._lock:
            target = self.device.used_bytes
        # cat="memory": OOM-recovery spilling is memory-pressure time on
        # the query's critical path (tools/trace.py)
        with get_tracer().span("oom_recovery", "memory",
                               context=context[:200]):
            freed = self.synchronous_spill(max(target, 1))
        self.oom_events += 1
        if freed + cb_freed == 0:
            # nothing left to spill or drop: the caller's retry will fail
            # and raise — dump the attributed postmortem first
            mp = _memprof()
            if mp is not None:
                mp.oom_postmortem(
                    f"device OOM with nothing left to spill: {context}"
                    [:500], catalog=self)
        return freed + cb_freed

    def oom_dump(self) -> str:
        """Diagnostic snapshot for a spill-couldn't-save-it failure
        (reference: spark.rapids.memory.gpu.oomDumpDir state dumps)."""
        s = self.stats()
        with self._lock:
            top = sorted(self._buffers.values(),
                         key=lambda b: -b.size_bytes)[:10]
            rows = [f"  buffer {b.buffer_id} tier="
                    f"{StorageTier.NAMES[b.tier]} bytes={b.size_bytes} "
                    f"refcount={b.refcount} priority={b.priority} "
                    f"site={self._sites.get(b.buffer_id, '?')}"
                    for b in top]
            ext = self._refresh_external_locked()
            notes = list(self.diagnostics)
        report = ("device OOM after spill retry; catalog state: "
                  f"{s}\nlargest buffers:\n" + "\n".join(rows))
        mp = _memprof()
        if mp is not None:
            holders = mp.holders_by_operator()[:10]
            if holders:
                report += ("\nholders by operator (live device bytes):\n"
                           + "\n".join(f"  {k}={v}" for k, v in holders))
        if ext:
            report += "\nexternal device bytes: " + ", ".join(
                f"{k}={v}" for k, v in sorted(ext.items()))
        if notes:
            report += "\nrecent diagnostics:\n" + "\n".join(
                f"  {n}" for n in notes[-10:])
        return report

    def watermarks(self, timeout_s: Optional[float] = None
                   ) -> Optional[dict]:
        """O(1) HBM used/peak snapshot for the health monitor's per-tick
        sampling (utils/health.py). Uses the CACHED external byte counts —
        a once-a-second tick must not call out through foreign locks the
        way the cold stats()/oom_dump() paths may. With ``timeout_s``,
        returns None instead of blocking when the catalog lock is held
        past the timeout: the wedged lock-holder the watchdog reports on
        must never wedge the watchdog itself."""
        if timeout_s is None:
            self._lock.acquire()
        elif not self._lock.acquire(timeout=timeout_s):
            return None
        try:
            ext = sum(self._external_cache.values())
            return {
                "device_used_bytes": self.device.used_bytes + ext,
                "device_peak_bytes": self.peak_device_bytes,
                "device_limit_bytes": self.device.limit_bytes,
                "host_used_bytes": self.host.used_bytes,
                "host_limit_bytes": self.host.limit_bytes,
                "disk_used_bytes": self.disk.used_bytes,
                "external_device_bytes": ext,
                "buffers": len(self._buffers),
            }
        finally:
            self._lock.release()

    def watchdog_dump(self, timeout_s: float = 1.0) -> Optional[dict]:
        """Stall-forensics snapshot that can never hang: bounded lock
        acquire and NO calls out through external sources' locks (cached
        bytes only) — unlike stats()/oom_dump(), which may block exactly
        when the engine is wedged. None = lock unavailable (and that fact
        itself belongs in the report)."""
        if not self._lock.acquire(timeout=timeout_s):
            return None
        try:
            tiers: Dict[str, int] = {}
            for s in self._buffers.values():
                name = StorageTier.NAMES[s.tier]
                tiers[name] = tiers.get(name, 0) + 1
            wm = self.watermarks()  # RLock: re-entrant, still bounded
            return {**wm, "tiers": tiers,
                    "spill_count": dict(self.spill_count),
                    "spilled_bytes": dict(self.spilled_bytes),
                    "oom_events": self.oom_events,
                    "oom_callback_errors": self.oom_callback_errors}
        finally:
            self._lock.release()

    def stats(self) -> dict:
        with self._lock:
            tiers = {}
            for s in self._buffers.values():
                name = StorageTier.NAMES[s.tier]
                tiers[name] = tiers.get(name, 0) + 1
            return {
                "buffers": len(self._buffers),
                "tiers": tiers,
                "device_used": self.device.used_bytes,
                "host_used": self.host.used_bytes,
                "disk_used": self.disk.used_bytes,
                "external_bytes": self._refresh_external_locked(),
                "peak_device_bytes": self.peak_device_bytes,
                "spill_count": dict(self.spill_count),
                "spilled_bytes": dict(self.spilled_bytes),
                "oom_events": self.oom_events,
                "oom_callback_errors": self.oom_callback_errors,
            }

    def counters(self) -> dict:
        """Flat, stable-named counters for the process StatsRegistry /
        Prometheus exposition (spill tiers by name, not enum value)."""
        with self._lock:
            ext = self._refresh_external_locked()
            return {
                "buffers": len(self._buffers),
                "device_used_bytes": self.device.used_bytes,
                "host_used_bytes": self.host.used_bytes,
                "disk_used_bytes": self.disk.used_bytes,
                "external_device_bytes": sum(ext.values()),
                "peak_device_bytes": self.peak_device_bytes,
                "spills_to_host": self.spill_count[StorageTier.HOST],
                "spills_to_disk": self.spill_count[StorageTier.DISK],
                "spilled_bytes_host": self.spilled_bytes[StorageTier.HOST],
                "spilled_bytes_disk": self.spilled_bytes[StorageTier.DISK],
                "oom_events": self.oom_events,
                "oom_callback_errors": self.oom_callback_errors,
            }


class SpillableDeviceTable:
    """Operator-facing handle (reference: SpillableColumnarBatch)."""

    def __init__(self, catalog: BufferCatalog, buffer_id: int):
        self.catalog = catalog
        self.buffer_id = buffer_id

    def get(self) -> DeviceTable:
        """Acquire the table on device (restoring from lower tiers).

        The acquire/release pair runs under ONE catalog-lock hold: as two
        separate acquisitions, a spill pass could interleave between them
        and race the restore's tier flip, double-counting the buffer's
        bytes in the device store (regression test:
        tests/test_memprof.py two-thread spill-vs-get stress)."""
        with self.catalog._lock:
            table = self.catalog.acquire(self.buffer_id)
            self.catalog.release(self.buffer_id)
        return table

    def __enter__(self) -> DeviceTable:
        return self.catalog.acquire(self.buffer_id)

    def __exit__(self, *exc):
        self.catalog.release(self.buffer_id)

    @property
    def tier(self) -> int:
        return self.catalog.tier_of(self.buffer_id)

    def close(self):
        self.catalog.close_buffer(self.buffer_id)


#: what the CPU backend's "device" is taken to hold: XLA:CPU has no
#: memory_stats(), and the tests size their pools against this default
CPU_BACKEND_DEVICE_BYTES = 8 * 1024 * 1024 * 1024


def _device_memory_bytes() -> int:
    """HBM the pool may plan with, as the device reports it. An accelerator
    that reports none is an error: a guessed size either wastes half the
    chip or plans past its end."""
    d = jax.devices()[0]
    ms = d.memory_stats()
    if ms and "bytes_limit" in ms:
        return int(ms["bytes_limit"])
    if d.platform == "cpu":
        return CPU_BACKEND_DEVICE_BYTES
    raise RuntimeError(
        f"{d.platform} device {d.device_kind!r} reports no bytes_limit "
        f"(memory_stats() = {ms!r}); set spark.rapids.tpu.memory.pool.size "
        f"explicitly")


_GLOBAL: Optional[BufferCatalog] = None
_GLOBAL_LOCK = threading.Lock()


def get_catalog(conf: Optional[RapidsConf] = None) -> BufferCatalog:
    global _GLOBAL
    with _GLOBAL_LOCK:
        if _GLOBAL is None:
            _GLOBAL = BufferCatalog(conf)
        return _GLOBAL


def set_catalog(catalog: Optional[BufferCatalog]):
    global _GLOBAL
    with _GLOBAL_LOCK:
        _GLOBAL = catalog


def peek_catalog() -> Optional[BufferCatalog]:
    """The global catalog if one exists — never creates one (stats sources
    must not side-effect a default catalog into existence)."""
    with _GLOBAL_LOCK:
        return _GLOBAL
