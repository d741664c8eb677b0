"""Shuffle manager: device-side partition slicing + transport-backed exchange
(reference: RapidsShuffleInternalManagerBase.scala — RapidsCachingWriter at
:92-155, RapidsShuffleIterator / RapidsShuffleClient on the read side; and
GpuPartitioning.sliceInternalOnGpu, GpuPartitioning.scala:49,130).

Write path per map partition:
  device batch -> device hash kernel assigns reduce partition per row
  -> one compact-by-partition sort -> slice per reduce partition (host loop
     over bucketed slices) -> serialize (+codec) -> transport.publish
Read path per reduce partition:
  transport.fetch -> deserialize -> host-concat (GpuShuffleCoalesceExec
  analogue) -> upload as one device batch.

A heartbeat registry stands in for the executor discovery control plane
(reference: RapidsShuffleHeartbeatManager.scala).
"""
from __future__ import annotations

import itertools
import threading
import time
from typing import Dict, Iterator, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..columnar.device import DeviceTable, stable_counting_order
from ..columnar.host import HostTable
from ..conf import RapidsConf, SHUFFLE_COMPRESSION_CODEC, register_conf
from ..expr.hashing import float_key_bits
from ..memory.stores import SpillCorruptionError
from ..utils import faults, movement
from ..utils.tracing import get_tracer
from . import telemetry
from .serializer import deserialize_table, serialize_table
from .transport import BlockId, ShuffleTransport, load_transport

__all__ = ["ShuffleManager", "HeartbeatManager", "device_partition_ids",
           "shuffle_stats"]

# process-wide shuffle counters (all ShuffleManager instances fold in here;
# feeds utils.metrics.StatsRegistry and the per-query event-log deltas)
_STATS_LOCK = threading.Lock()
_STATS = {
    "blocks_published": 0, "bytes_published": 0,
    "blocks_fetched": 0, "bytes_fetched": 0,
    "writes_cached_tier": 0, "writes_transport_tier": 0,
    "reads_cached_tier": 0, "reads_transport_tier": 0,
}


def _bump(**kv) -> None:
    with _STATS_LOCK:
        for k, v in kv.items():
            _STATS[k] += v


def shuffle_stats() -> Dict[str, int]:
    """Blocks/bytes written+fetched and which tier served them (cached
    device-resident vs transport)."""
    with _STATS_LOCK:
        return dict(_STATS)


SHUFFLE_CACHE_WRITES = register_conf(
    "spark.rapids.tpu.shuffle.cacheWrites",
    "Cache written shuffle partitions in the device store as spillable "
    "buffers (reference: RapidsCachingWriter + ShuffleBufferCatalog): same-"
    "process readers consume them with no serialize/upload round trip. "
    "'auto' enables it for the in-process transport only; 'on'/'off' force.",
    "auto",
    checker=lambda v: None if v in ("auto", "on", "off")
    else f"must be one of auto/on/off, got {v!r}")


# movement-ledger funnel names (see utils/movement.py SITES)
_MOVE_WRITE_TRANSPORT = ("spark_rapids_tpu/shuffle/manager.py"
                         "::ShuffleManager._write_partition_transport")
_MOVE_WRITE_CACHED = ("spark_rapids_tpu/shuffle/manager.py"
                      "::ShuffleManager._write_partition_cached")
_MOVE_READ_CACHED = ("spark_rapids_tpu/shuffle/manager.py"
                     "::ShuffleManager._read_partition_cached")
_MOVE_READ_UPLOAD = ("spark_rapids_tpu/shuffle/manager.py"
                     "::ShuffleManager.read_partition")


def _partition_order(pids, num_parts: int):
    """Stable group-by-partition permutation. The sort-free counting
    order materializes an O(rows x parts) one-hot, so it only pays off
    for small partition counts; larger fan-outs keep the argsort (same
    memory as before the sort-free rework)."""
    if num_parts + 1 <= 32:
        return stable_counting_order(pids, num_parts + 1)
    return jnp.argsort(pids, stable=True)


_MURMUR_C1 = np.uint32(0x85EBCA6B)
_MURMUR_C2 = np.uint32(0xC2B2AE35)


def _fmix_device(x: jax.Array) -> jax.Array:
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(_MURMUR_C1)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(_MURMUR_C2)
    x = x ^ (x >> 16)
    return x


def _string_key_hash(col) -> jax.Array:
    """Width-independent hash of a fixed-width string column.

    Bytes past each row's length are zero-padded by construction
    (columnar/device.py from_host), and words fully past the length are
    masked out, so the result does not depend on the batch's padded width —
    the same key hashes identically across batches (required for shuffle
    write/read agreement, like cudf's string murmur in the reference)."""
    data, lengths = col.data, col.lengths
    cap, w = data.shape
    k = jnp.zeros(cap, dtype=jnp.uint32)
    for start in range(0, w, 8):
        chunk = data[:, start:start + 8]
        word = jnp.zeros((cap,), dtype=jnp.uint64)
        for j in range(chunk.shape[1]):
            word = word | (chunk[:, j].astype(jnp.uint64)
                           << jnp.uint64(8 * (7 - j)))
        kw = _fmix_device((word & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
                          ^ (word >> jnp.uint64(32)).astype(jnp.uint32)
                          ^ jnp.uint32(start + 1))
        overlaps = lengths > start
        k = k ^ jnp.where(overlaps, kw, jnp.uint32(0))
    return k ^ _fmix_device(lengths.astype(jnp.uint32))


def device_partition_ids(table: DeviceTable, key_names: List[str],
                         num_parts: int, seed: int = 42) -> jax.Array:
    """Per-row reduce-partition ids; bitwise-identical to the host
    murmur-style partitioner (plan/physical.py murmur_hash_columns) for
    fixed-width types so host and device paths agree on placement. String
    keys use a device-only width-independent hash (consistent across the
    all-device shuffle write/read paths; host/device placement agreement is
    not required for strings because placement never crosses engines)."""
    h = jnp.full(table.capacity, jnp.uint32(seed), dtype=jnp.uint32)
    for name in key_names:
        k = _column_key_hash(table.column(name))
        h = h ^ k
        h = h * jnp.uint32(5) + jnp.uint32(0xE6546B64)
    return (h % jnp.uint32(num_parts)).astype(jnp.int32)


def _column_key_hash(col) -> jax.Array:
    """Per-row u32 hash of one key column; struct keys fold their field
    hashes (recursively), null rows/fields hash to 0."""
    from ..columnar import dtypes as _dt
    if isinstance(col.dtype, _dt.StructType):
        k = jnp.zeros(col.capacity, dtype=jnp.uint32)
        for i, child in enumerate(col.children):
            ck = _column_key_hash(child)
            k = k ^ _fmix_device(ck ^ jnp.uint32(i + 1))
            k = k * jnp.uint32(5) + jnp.uint32(0xE6546B64)
        return jnp.where(col.validity, k, jnp.uint32(0))
    v = col.data
    if col.lengths is not None:  # string/binary
        k = _string_key_hash(col)
    elif v.ndim == 2:  # decimal128 two-limb columns: fold both limbs
        hi = v[:, 0].view(jnp.uint64)
        lo = v[:, 1].view(jnp.uint64)
        bits = hi ^ (lo * jnp.uint64(0x9E3779B97F4A7C15))
        k = (bits & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32) \
            ^ (bits >> jnp.uint64(32)).astype(jnp.uint32)
    elif v.dtype == jnp.bool_:
        k = v.astype(jnp.uint32)
    elif jnp.issubdtype(v.dtype, jnp.floating):
        k = float_key_bits(jnp, v)
    else:
        bits = v.astype(jnp.int64).view(jnp.uint64)
        k = (bits & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32) \
            ^ (bits >> jnp.uint64(32)).astype(jnp.uint32)
    k = _fmix_device(k)
    return jnp.where(col.validity, k, jnp.uint32(0))


class HeartbeatManager:
    """Executor registration/heartbeat control plane (reference:
    Plugin.scala:149-161 + RapidsShuffleHeartbeatManager.scala)."""

    def __init__(self, timeout_s: float = 60.0):
        self._peers: Dict[int, float] = {}
        self._lock = threading.Lock()
        self.timeout_s = timeout_s

    def register(self, executor_id: int):
        self.heartbeat(executor_id)

    def heartbeat(self, executor_id: int):
        with self._lock:
            self._peers[executor_id] = time.monotonic()

    def live_peers(self) -> List[int]:
        now = time.monotonic()
        with self._lock:
            return sorted(e for e, t in self._peers.items()
                          if now - t < self.timeout_s)

    def expire(self):
        now = time.monotonic()
        with self._lock:
            for e in [e for e, t in self._peers.items()
                      if now - t >= self.timeout_s]:
                del self._peers[e]


class ShuffleManager:
    def __init__(self, conf: Optional[RapidsConf] = None,
                 transport: Optional[ShuffleTransport] = None):
        self.conf = conf or RapidsConf()
        self.transport = transport or load_transport(self.conf)
        from .serializer import default_codec
        self.codec = self.conf.get(SHUFFLE_COMPRESSION_CODEC)
        if self.codec not in ("none", "zlib"):
            # lz4 needs the native library; zstd isn't shipped — both degrade
            # to the best available codec
            self.codec = default_codec()
        self._ids = itertools.count()
        # v7 skew telemetry: per-shuffle reduce-partition row/byte
        # distribution, accumulated across map tasks on both write tiers
        # from counts the write paths already compute (bounds diff +
        # published block sizes). Instance state: shuffle ids are
        # per-manager, so a process-wide map would alias id 0 across
        # managers with different partition counts.
        self._skew_lock = threading.Lock()
        self._skew: Dict[int, Dict[str, List[int]]] = {}
        self.heartbeats = HeartbeatManager()
        from .buffer_catalog import ShuffleBufferCatalog
        self.buffer_catalog = ShuffleBufferCatalog()
        mode = self.conf.get(SHUFFLE_CACHE_WRITES)
        if mode == "auto":
            from .transport import LocalShuffleTransport
            self.cache_writes = isinstance(self.transport,
                                           LocalShuffleTransport)
        else:
            self.cache_writes = mode == "on"

    def new_shuffle_id(self) -> int:
        return next(self._ids)

    def _bump_skew(self, shuffle_id: int, part_rows, part_bytes) -> None:
        with self._skew_lock:
            entry = self._skew.setdefault(
                shuffle_id, {"rows": [0] * len(part_rows),
                             "bytes": [0] * len(part_bytes)})
            for p, r in enumerate(part_rows):
                entry["rows"][p] += int(r)
            for p, b in enumerate(part_bytes):
                entry["bytes"][p] += int(b)

    def shuffle_skew_stats(self, shuffle_id: int) -> Optional[Dict]:
        """The v7 ``shuffle_skew`` payload for one shuffle's write-side
        distribution (min/p50/max/imbalance over reduce partitions), or
        None for an unknown/unwritten shuffle id."""
        with self._skew_lock:
            entry = self._skew.get(shuffle_id)
            if entry is None:
                return None
            from ..utils.metrics import build_skew_record
            return build_skew_record(entry["rows"], entry["bytes"])

    def unregister_shuffle(self, shuffle_id: int) -> None:
        """Free a finished shuffle's blocks in BOTH stores — device-resident
        catalog buffers and transport payloads (reference:
        unregisterShuffle releasing the ShuffleBufferCatalog's buffers).
        Callers own the shuffle lifecycle: invoke when the consuming stage
        has fully drained the reduce partitions."""
        self.buffer_catalog.remove_shuffle(shuffle_id)
        with self._skew_lock:
            self._skew.pop(shuffle_id, None)
        try:
            self.transport.remove_shuffle(shuffle_id)
        except NotImplementedError:
            pass

    def unregister_all(self) -> None:
        """Executor shutdown: free every cached shuffle block."""
        for sid in self.buffer_catalog.shuffle_ids():
            self.buffer_catalog.remove_shuffle(sid)

    # -- write side -----------------------------------------------------------
    def write_partition(self, shuffle_id: int, map_id: int,
                        batches: Iterator[DeviceTable], key_names: List[str],
                        num_parts: int) -> List[int]:
        """Slice + publish one map task's output; returns bytes per block.

        EVERY (map, reduce) block is published, including empty ones — the
        reader treats a missing block as a fetch failure (reference: Spark's
        MapStatus records every block; RapidsShuffleIterator fails loudly on
        a miss rather than guessing it was empty).

        With ``cache_writes`` the slices stay DEVICE-resident in the shuffle
        buffer catalog (RapidsCachingWriter): no download, no serialization;
        same-process readers concat the device blocks directly and the spill
        framework owns the memory."""
        action = faults.fire("shuffle.publish")
        if action is not None and action != "delay":
            raise faults.FaultInjectedError("shuffle.publish", action)
        if self.cache_writes:
            with get_tracer().span("shuffle.write", "shuffle", tier="cached",
                                   shuffle=shuffle_id, map=map_id):
                return self._write_partition_cached(
                    shuffle_id, map_id, batches, key_names, num_parts)
        with get_tracer().span("shuffle.write", "shuffle", tier="transport",
                               shuffle=shuffle_id, map=map_id):
            return self._write_partition_transport(
                shuffle_id, map_id, batches, key_names, num_parts)

    def _write_partition_transport(self, shuffle_id: int, map_id: int,
                                   batches: Iterator[DeviceTable],
                                   key_names: List[str],
                                   num_parts: int) -> List[int]:
        merged: List[List[HostTable]] = [[] for _ in range(num_parts)]
        part_rows = np.zeros(num_parts, dtype=np.int64)
        schema_host: Optional[HostTable] = None
        for batch in batches:
            pids = device_partition_ids(batch, key_names, num_parts)
            pids = jnp.where(batch.row_mask, pids, num_parts)  # park inactive
            order = _partition_order(pids, num_parts)
            sorted_tbl = DeviceTable(
                tuple(c.gather(order, keep_all_valid=True)
                      for c in batch.columns),
                jnp.take(batch.row_mask, order), batch.num_rows, batch.names)
            t0 = movement.clock()
            sorted_pids = np.asarray(jnp.take(pids, order))  # srtpu: sync-ok(count pass: partition-id vector only, 4B/row, before the bulk download)
            movement.note_d2h(_MOVE_WRITE_TRANSPORT, sorted_pids.nbytes, t0)
            bounds = np.searchsorted(sorted_pids, np.arange(num_parts + 1))
            part_rows += np.diff(bounds)
            host = sorted_tbl.to_host()  # single download, dense prefix
            schema_host = host
            for p in range(num_parts):
                lo, hi = int(bounds[p]), int(bounds[p + 1])
                if hi > lo:
                    merged[p].append(host.slice(lo, hi - lo))
        def publish(p: int) -> int:
            if merged[p]:
                table = HostTable.concat(merged[p])
            elif schema_host is not None:
                table = schema_host.slice(0, 0)
            else:  # map task saw no batches at all: typed-empty marker
                table = HostTable([], [])
            t0 = telemetry.clock()
            payload = serialize_table(table, self.codec)
            telemetry.note_transfer(
                "transport", "serialize", shuffle_id=shuffle_id,
                map_id=map_id, partition=p, t0=t0,
                logical_bytes=lambda: table.nbytes(),
                wire_bytes=len(payload))
            t1 = telemetry.clock()
            self.transport.publish(BlockId(shuffle_id, map_id, p), payload)
            telemetry.note_transfer(
                "transport", "publish", shuffle_id=shuffle_id,
                map_id=map_id, partition=p, t0=t1,
                wire_bytes=len(payload))
            return len(payload)

        # parallel map-side writes: per-block concat+serialize (+codec) is
        # pure CPU work; the transport guards its own store
        from ..parallel.pipeline import parallel_map
        sizes = parallel_map(publish, range(num_parts),
                             stage="shuffle_serialize")
        _bump(blocks_published=num_parts, bytes_published=sum(sizes),
              writes_transport_tier=1)
        self._bump_skew(shuffle_id, part_rows, sizes)
        return sizes

    def _write_partition_cached(self, shuffle_id: int, map_id: int,
                                batches: Iterator[DeviceTable],
                                key_names: List[str],
                                num_parts: int) -> List[int]:
        """Device-resident write path (RapidsCachingWriter analogue)."""
        from ..columnar.device import bucket_rows, concat_device_tables

        def gather_window(tbl: DeviceTable, lo: int, hi: int) -> DeviceTable:
            # explicit gather (NOT slice_rows: its start clamp would shift
            # windows whose bucketed length overruns the capacity)
            length = bucket_rows(max(hi - lo, 1), 256)  # srtpu: bucket-ok(cached-block slice quantum: 256 keys the window kernels independently of the session ladder, so reader and writer agree on stored shard shapes)
            idx = jnp.clip(lo + jnp.arange(length, dtype=jnp.int32),
                           0, tbl.capacity - 1)
            mask = jnp.arange(length, dtype=jnp.int32) < (hi - lo)
            cols = tuple(c.gather(idx, keep_all_valid=True).with_validity(
                jnp.take(c.validity, idx) & mask) for c in tbl.columns)
            return DeviceTable(cols, mask, jnp.int32(hi - lo), tbl.names)

        per_part: List[List[DeviceTable]] = [[] for _ in range(num_parts)]
        part_rows = np.zeros(num_parts, dtype=np.int64)
        schema_tbl: Optional[DeviceTable] = None
        for batch in batches:
            pids = device_partition_ids(batch, key_names, num_parts)
            pids = jnp.where(batch.row_mask, pids, num_parts)
            order = _partition_order(pids, num_parts)
            sorted_tbl = DeviceTable(
                tuple(c.gather(order, keep_all_valid=True)
                      for c in batch.columns),
                jnp.take(batch.row_mask, order), batch.num_rows, batch.names)
            schema_tbl = sorted_tbl
            # count download only (4B/row), like the ICI exchange count pass
            t0 = movement.clock()
            sorted_pids = np.asarray(jnp.take(pids, order))  # srtpu: sync-ok(count pass: partition-id vector only, 4B/row; slices stay on device)
            movement.note_d2h(_MOVE_WRITE_CACHED, sorted_pids.nbytes, t0)
            bounds = np.searchsorted(sorted_pids, np.arange(num_parts + 1))
            part_rows += np.diff(bounds)
            for p in range(num_parts):
                lo, hi = int(bounds[p]), int(bounds[p + 1])
                if hi > lo:
                    per_part[p].append(gather_window(sorted_tbl, lo, hi))
        sizes = [0] * num_parts
        for p in range(num_parts):
            if per_part[p]:
                table = concat_device_tables(per_part[p], 256)  # srtpu: bucket-ok(stored cached-tier blocks share the 256-row write quantum above; readers re-bucket to their own ladder)
            elif schema_tbl is not None:
                table = gather_window(schema_tbl, 0, 0)
            else:  # map task saw no batches at all
                table = DeviceTable((), jnp.zeros(0, dtype=bool),
                                    jnp.int32(0), ())
            t0 = telemetry.clock()
            self.buffer_catalog.put((shuffle_id, map_id, p), table)
            sizes[p] = table.nbytes()
            telemetry.note_transfer(
                "cached", "publish", shuffle_id=shuffle_id,
                map_id=map_id, partition=p, t0=t0,
                logical_bytes=sizes[p], wire_bytes=sizes[p])
        _bump(blocks_published=num_parts, bytes_published=sum(sizes),
              writes_cached_tier=1)
        self._bump_skew(shuffle_id, part_rows, sizes)
        return sizes

    # -- read side ------------------------------------------------------------
    def read_partition(self, shuffle_id: int, num_maps: int, reduce_id: int,
                       min_bucket: Optional[int] = None,
                       recompute=None) -> Iterator[DeviceTable]:
        """Fetch + coalesce + upload one reduce partition.

        A missing block raises ShuffleFetchFailedException. When a
        ``recompute(map_id)`` hook is provided (the stage-retry analogue —
        reference: RapidsShuffleFetchFailedException -> Spark recomputes the
        map task from lineage), it is invoked once for the failed map and the
        fetch retried before giving up."""
        from .transport import ShuffleFetchFailedException
        if self.cache_writes:
            yield from self._read_partition_cached(
                shuffle_id, num_maps, reduce_id, min_bucket, recompute)
            return
        blocks = [BlockId(shuffle_id, m, reduce_id) for m in range(num_maps)]
        tables: List[HostTable] = []
        fetched_bytes = 0
        pending = list(blocks)
        retried = set()
        with get_tracer().span("shuffle.fetch", "shuffle", tier="transport",
                               shuffle=shuffle_id, reduce=reduce_id,
                               maps=num_maps):
            while pending:
                try:
                    if faults.fire("shuffle.fetch") not in (None, "delay"):
                        # injected through the REAL failure type so the
                        # recompute-once machinery below recovers it
                        raise ShuffleFetchFailedException(
                            pending[0], "injected fault 'shuffle.fetch'")
                    t_fetch = telemetry.clock()
                    for bid, payload in self.transport.fetch(pending):
                        telemetry.note_transfer(
                            "transport", "fetch", shuffle_id=shuffle_id,
                            map_id=bid[1], partition=reduce_id,
                            wire_bytes=len(payload), t0=t_fetch,
                            retries=1 if bid[1] in retried else 0,
                            queue_depth=len(pending))
                        t_des = telemetry.clock()
                        host = deserialize_table(payload)
                        telemetry.note_transfer(
                            "transport", "deserialize",
                            shuffle_id=shuffle_id, map_id=bid[1],
                            partition=reduce_id, t0=t_des,
                            logical_bytes=lambda: host.nbytes())
                        tables.append(host)
                        fetched_bytes += len(payload)
                        pending = pending[pending.index(bid) + 1:]
                        t_fetch = telemetry.clock()
                    break
                except ShuffleFetchFailedException as e:
                    map_id = e.block[1]
                    get_tracer().instant(
                        "shuffle_fetch_failed", "shuffle",
                        shuffle=shuffle_id, map=map_id, reduce=reduce_id,
                        retry=recompute is not None and map_id not in retried)
                    if recompute is None or map_id in retried:
                        raise
                    retried.add(map_id)
                    faults.note_recovery("shuffle_recomputes")
                    with get_tracer().span("shuffle.recompute", "shuffle",
                                           shuffle=shuffle_id, map=map_id):
                        recompute(map_id)
                    pending = pending[pending.index(e.block):]
        _bump(blocks_fetched=len(tables), bytes_fetched=fetched_bytes,
              reads_transport_tier=1)
        non_empty = [t for t in tables if t.num_columns and t.num_rows]
        if not non_empty:
            # all blocks empty: match the cached tier — yield a zero-row
            # table with the schema when any schema-bearing block exists
            schema_t = next((t for t in tables if t.num_columns), None)
            if schema_t is not None:
                yield DeviceTable.from_host(schema_t.slice(0, 0), min_bucket)
            return
        # host-side coalesce then single upload (GpuShuffleCoalesceExec)
        merged = HostTable.concat(non_empty)
        t0 = movement.clock()
        dtb = DeviceTable.from_host(merged, min_bucket)
        movement.note_h2d(_MOVE_READ_UPLOAD, dtb.nbytes, t0, origin=merged)
        yield dtb

    def _read_partition_cached(self, shuffle_id: int, num_maps: int,
                               reduce_id: int, min_bucket: int,
                               recompute=None) -> Iterator[DeviceTable]:
        """Catalog-backed read: blocks never left the device (or come back
        via the spill framework); a miss is a fetch failure with the same
        recompute-once semantics as the transport path."""
        from ..columnar.device import concat_device_tables
        from .transport import ShuffleFetchFailedException
        parts: List[DeviceTable] = []
        schema_holder: Optional[DeviceTable] = None
        fetched_bytes = 0
        with get_tracer().span("shuffle.fetch", "shuffle", tier="cached",
                               shuffle=shuffle_id, reduce=reduce_id,
                               maps=num_maps):
            tables: List[DeviceTable] = []
            for m in range(num_maps):
                key = (shuffle_id, m, reduce_id)
                handle = self.buffer_catalog.get(key)
                if handle is not None and \
                        faults.fire("shuffle.fetch") not in (None, "delay"):
                    handle = None  # injected miss: exercises the same
                    # recompute path a genuinely lost block takes
                if handle is None and recompute is not None:
                    get_tracer().instant(
                        "shuffle_fetch_failed", "shuffle",
                        shuffle=shuffle_id, map=m, reduce=reduce_id,
                        retry=True)
                    faults.note_recovery("shuffle_recomputes")
                    with get_tracer().span("shuffle.recompute", "shuffle",
                                           shuffle=shuffle_id, map=m):
                        recompute(m)
                    handle = self.buffer_catalog.get(key)
                if handle is None:
                    raise ShuffleFetchFailedException(
                        BlockId(shuffle_id, m, reduce_id),
                        "block not in the shuffle buffer catalog")
                try:
                    t = handle.get()
                except SpillCorruptionError as e:
                    # a corrupt disk-spilled block is recoverable the same
                    # way a lost remote block is: recompute the map output
                    # (put() overwrites and closes the corrupt handle)
                    get_tracer().instant(
                        "shuffle_fetch_failed", "shuffle",
                        shuffle=shuffle_id, map=m, reduce=reduce_id,
                        retry=recompute is not None)
                    if recompute is None:
                        raise ShuffleFetchFailedException(
                            BlockId(shuffle_id, m, reduce_id),
                            f"spilled block corrupt: {e}")
                    faults.note_recovery("shuffle_recomputes")
                    with get_tracer().span("shuffle.recompute", "shuffle",
                                           shuffle=shuffle_id, map=m):
                        recompute(m)
                    fresh = self.buffer_catalog.get(key)
                    if fresh is None:
                        raise ShuffleFetchFailedException(
                            BlockId(shuffle_id, m, reduce_id),
                            "block missing after corruption recompute")
                    try:
                        t = fresh.get()
                    except SpillCorruptionError as e2:
                        raise ShuffleFetchFailedException(
                            BlockId(shuffle_id, m, reduce_id),
                            f"spilled block corrupt after recompute: {e2}")
                nb = t.nbytes()
                telemetry.note_transfer(
                    "cached", "fetch", shuffle_id=shuffle_id,
                    map_id=m, partition=reduce_id,
                    logical_bytes=nb, wire_bytes=nb)
                fetched_bytes += nb
                if t.num_columns:
                    tables.append(t)
            # ONE bulk D2H of all block row counts instead of a blocking
            # 4-byte round trip per map block (ROADMAP item 1)
            t0 = movement.clock()
            counts = jax.device_get(  # srtpu: sync-ok(batched count sync, 4B per block once per reduce partition)
                [t.num_rows for t in tables])
            movement.note_d2h(_MOVE_READ_CACHED, 4 * len(tables), t0)
            for t, cnt in zip(tables, counts):
                if int(cnt):
                    parts.append(t)
                elif schema_holder is None:
                    schema_holder = t
        _bump(blocks_fetched=num_maps, bytes_fetched=fetched_bytes,
              reads_cached_tier=1)
        if parts:
            yield concat_device_tables(parts, min_bucket)
        elif schema_holder is not None:
            # all blocks empty: yield a zero-row table with the schema so
            # this tier matches the transport tier's empty-partition shape;
            # re-bucket to the READER's min_bucket (the stored block keeps
            # the map-side write capacity, a one-off shape downstream)
            yield DeviceTable.from_host(
                schema_holder.to_host().slice(0, 0), min_bucket)
