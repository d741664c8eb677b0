"""DCN (cross-host) accelerated shuffle tier — design + mocked transport.

Reference mapping: the UCX shuffle plugin (shuffle-plugin/.../UCX.scala:69,
UCXShuffleTransport.scala:47) moves shuffle blocks executor-to-executor
device-to-device over NVLink/IB/RoCE with bounce-buffer pools and a TCP
management handshake. On TPU pods the equivalent fabric story has three
tiers:

1. **ICI** (intra-slice): already first-class — the planner-reachable
   all-to-all exchange (shuffle/ici.py + exec/exchange.py) runs as XLA
   collectives inside one jitted program. No transport code at all; the
   compiler owns the links. This replaces UCX for everything inside a
   slice, which is where the reference's NVLink tier lived.
2. **DCN** (cross-slice, same pod network): multi-slice jax meshes expose
   DCN to XLA through the SAME collectives — a mesh axis that crosses
   slices makes `all_to_all`/`ppermute` ride DCN automatically. The
   production path is therefore *mesh shape*, not a socket transport:
   `Mesh(devices.reshape(n_slices, chips_per_slice), ("dcn", "ici"))`
   with the exchange partitioned over both axes. `dryrun_multichip`
   exercises exactly this program shape on virtual devices.
3. **Fallback / task-parallel tier** (this module's SPI): when executors
   run as independent processes (ProcessCluster — the Spark-task model),
   cross-host blocks must move through an explicit transport. The TCP
   tier (shuffle/tcp.py) ships host bytes; THIS module is the
   accelerated analogue, keeping payloads as device arrays end to end
   and staging device->device (host memory never holds a serialized
   copy). Real hardware would back `_link_transfer` with
   jax.device_put over DCN-visible devices or a PJRT cross-host copy;
   the in-process mock preserves the exact SPI surface, device
   residency, and accounting so the planner/manager integration and the
   failure semantics are testable without a pod
   (the reference tests its UCX protocol with mocked transports the
   same way, RapidsShuffleTestHelper.scala:53-132).

Mock semantics:
- every `MockDcnFabric` is a registry of named "hosts"; each host owns a
  `DcnShuffleTransport` bound to a jax device.
- `publish` keeps the DeviceTable resident on the owner's device (via
  the catalog at shuffle priority, so it stays spillable).
- `fetch` locates the block on a peer host and moves it with
  `jax.device_put` onto the consumer's device — a device-to-device copy
  path with per-link byte accounting (`fabric.link_bytes`) and an
  injectable failure hook for fetch-failed testing.
"""
from __future__ import annotations

import threading
from typing import Callable, Dict, Iterator, List, Optional, Tuple

import jax

from ..columnar.device import DeviceTable
from ..utils import faults
from ..utils.tracing import current_trace_context, get_tracer
from . import telemetry
from .transport import BlockId, ShuffleFetchFailedException

__all__ = ["MockDcnFabric", "DcnShuffleTransport",
           "TcpDcnShuffleTransport"]


class MockDcnFabric:
    """In-process stand-in for the cross-slice network: a registry of
    hosts plus per-link transfer accounting."""

    def __init__(self):
        self.hosts: Dict[str, "DcnShuffleTransport"] = {}
        self.link_bytes: Dict[Tuple[str, str], int] = {}
        self.transfers = 0
        self._lock = threading.Lock()
        #: test hook: raise/drop on specific transfers (failure injection)
        self.fault: Optional[Callable[[str, str, BlockId], None]] = None

    def attach(self, name: str, transport: "DcnShuffleTransport"):
        with self._lock:
            self.hosts[name] = transport

    def transfer(self, src: str, dst: str, block: BlockId,
                 table: DeviceTable, device) -> DeviceTable:
        if self.fault is not None:
            self.fault(src, dst, block)
        with get_tracer().span("shuffle.dcn_transfer", "shuffle", src=src, dst=dst,
                               shuffle=block[0], map=block[1]):
            moved = jax.device_put(table, device)
        nbytes = table.nbytes()
        with self._lock:
            self.link_bytes[(src, dst)] = \
                self.link_bytes.get((src, dst), 0) + nbytes
            self.transfers += 1
        return moved


class DcnShuffleTransport:
    """Device-resident shuffle transport over a (mock) DCN fabric.

    Unlike the byte-oriented ShuffleTransport SPI, blocks here are
    DeviceTables: publish keeps them on-device (catalog-registered,
    spillable), fetch lands them on the consumer's device without a host
    serialization round trip."""

    def __init__(self, fabric: MockDcnFabric, host_name: str,
                 device=None, catalog=None):
        self.fabric = fabric
        self.host_name = host_name
        self.device = device if device is not None else jax.devices()[0]
        self.catalog = catalog
        self._blocks: Dict[BlockId, object] = {}   # handle or table
        self._lock = threading.Lock()
        fabric.attach(host_name, self)

    # -- publish/lookup -------------------------------------------------------
    def publish_table(self, block: BlockId, table: DeviceTable) -> None:
        entry: object = table
        if self.catalog is not None:
            from ..memory.catalog import SpillPriorities
            entry = self.catalog.register(
                table, SpillPriorities.OUTPUT_FOR_SHUFFLE)
        with self._lock:
            self._blocks[block] = entry

    def _local(self, block: BlockId) -> Optional[DeviceTable]:
        with self._lock:
            entry = self._blocks.get(block)
        if entry is None:
            return None
        return entry.get() if hasattr(entry, "get") else entry

    # -- fetch ----------------------------------------------------------------
    def fetch_tables(self, blocks: List[BlockId]
                     ) -> Iterator[Tuple[BlockId, DeviceTable]]:
        for b in blocks:
            local = self._local(b)
            if local is not None:
                yield b, local
                continue
            found = False
            for name, host in list(self.fabric.hosts.items()):
                if name == self.host_name:
                    continue
                remote = host._local(b)
                if remote is None:
                    continue
                yield b, self.fabric.transfer(  # srtpu: shuffle-ok(in-process mock fabric hop with its own link_bytes accounting; the real DCN tier TcpDcnShuffleTransport notes the observatory)
                    name, self.host_name, b, remote, self.device)
                found = True
                break
            if not found:
                raise ShuffleFetchFailedException(
                    b, f"block not on any of {len(self.fabric.hosts)} "
                       "DCN hosts")

    def remove_shuffle(self, shuffle_id: int) -> None:
        with self._lock:
            doomed = [b for b in self._blocks if b[0] == shuffle_id]
            entries = [self._blocks.pop(b) for b in doomed]
        for e in entries:
            close = getattr(e, "close", None)
            if close is not None:
                try:
                    close()
                except Exception:
                    pass  # srtpu: net-ok(best-effort handle release while dropping a finished shuffle; the blocks are dead either way)

    def close(self) -> None:
        self.remove_all()

    def remove_all(self) -> None:
        with self._lock:
            sids = {b[0] for b in self._blocks}
        for sid in sids:
            self.remove_shuffle(sid)


class TcpDcnShuffleTransport:
    """REAL cross-process DCN-tier transport (round-4 VERDICT item 9):
    device-resident at both ends, host-staged only at the wire.

    Same surface as DcnShuffleTransport but peers are other PROCESSES
    (ProcessCluster workers — the Spark-task model), reached through the
    chunked spill-backed TCP fabric (shuffle/tcp.py) exactly as the
    reference's UCX transport pairs device tables with a TCP/active-message
    wire (UCXShuffleTransport.scala:47). Serialization is LAZY: a published
    block stays a spillable device table until some peer actually requests
    it, then it downloads + serializes once into the TCP block store."""

    def __init__(self, conf=None, device=None, catalog=None,
                 codec: str = "lz4"):
        from ..conf import RapidsConf
        from .tcp import TcpShuffleTransport
        conf = conf or RapidsConf()
        self.tcp = TcpShuffleTransport(conf)
        self.device = device if device is not None else jax.devices()[0]
        self.catalog = catalog
        self.codec = codec
        self._blocks: Dict[BlockId, object] = {}
        self._lock = threading.Lock()
        self.bytes_wired = 0

    # -- wiring ---------------------------------------------------------------
    @property
    def address(self) -> Tuple[str, int]:
        return self.tcp.address

    def add_peer(self, host: str, port: int) -> None:
        self.tcp.add_peer(host, port)

    # -- publish/fetch --------------------------------------------------------
    def publish_table(self, block: BlockId, table: DeviceTable) -> None:
        action = faults.fire("dcn.publish")
        if action is not None and action != "delay":
            raise faults.FaultInjectedError("dcn.publish", action)
        entry: object = table
        if self.catalog is not None:
            from ..memory.catalog import SpillPriorities
            entry = self.catalog.register(
                table, SpillPriorities.OUTPUT_FOR_SHUFFLE)
        with self._lock:
            self._blocks[block] = entry
        t0 = telemetry.clock()
        self.tcp.store.put_lazy(block, lambda: self._serialize(block))
        telemetry.note_transfer(
            "dcn", "enqueue", shuffle_id=block[0], map_id=block[1],
            partition=block[2], t0=t0,
            logical_bytes=lambda: table.nbytes(),
            queue_depth=self.tcp.store.lazy_depth())

    def _serialize(self, block: BlockId) -> bytes:
        from .serializer import serialize_table
        table = self._local(block)
        if table is None:
            raise ShuffleFetchFailedException(
                block, "published table vanished before serialization")
        # runs on the TCP server thread under the REQUESTING query's
        # TraceContext (the SRTC wire header activated it), so this span
        # parents under the remote query span in the merged timeline
        t0 = telemetry.clock()
        with get_tracer().span("shuffle.dcn_serialize", "shuffle",
                               shuffle=block[0], map=block[1]):
            payload = serialize_table(table.to_host(), codec=self.codec)
        tctx = current_trace_context()
        telemetry.note_transfer(
            "dcn", "serialize", shuffle_id=block[0], map_id=block[1],
            partition=block[2], t0=t0,
            logical_bytes=lambda: table.nbytes(),
            wire_bytes=len(payload),
            queue_depth=self.tcp.store.lazy_depth(),
            query_id=tctx.query_id if tctx is not None else None)
        with self._lock:
            self.bytes_wired += len(payload)
        return payload

    def _local(self, block: BlockId) -> Optional[DeviceTable]:
        with self._lock:
            entry = self._blocks.get(block)
        if entry is None:
            return None
        return entry.get() if hasattr(entry, "get") else entry

    def fetch_tables(self, blocks: List[BlockId]
                     ) -> Iterator[Tuple[BlockId, DeviceTable]]:
        from .serializer import deserialize_table

        from ..columnar.device import DeviceTable as _DT
        local = [b for b in blocks if self._local(b) is not None]
        remote = [b for b in blocks if b not in set(local)]
        for b in local:
            yield b, self._local(b)
        if not remote:
            return
        action = faults.fire("dcn.fetch")
        if action is not None and action != "delay":
            raise faults.FaultInjectedError("dcn.fetch", action)
        t_fetch = telemetry.clock()
        for b, payload in self.tcp.fetch(remote):
            telemetry.note_transfer(
                "dcn", "fetch", shuffle_id=b[0], map_id=b[1],
                partition=b[2], wire_bytes=len(payload), t0=t_fetch,
                queue_depth=len(remote))
            t_des = telemetry.clock()
            with get_tracer().span("shuffle.dcn_fetch", "shuffle",
                                   shuffle=b[0], map=b[1],
                                   bytes=len(payload)):
                host = deserialize_table(payload)
                table = _DT.from_host(host)
                if self.device is not None:
                    table = jax.device_put(table, self.device)
            telemetry.note_transfer(
                "dcn", "deserialize", shuffle_id=b[0], map_id=b[1],
                partition=b[2], t0=t_des,
                logical_bytes=lambda: table.nbytes())
            yield b, table
            t_fetch = telemetry.clock()

    def remove_shuffle(self, shuffle_id: int) -> None:
        with self._lock:
            doomed = [b for b in self._blocks if b[0] == shuffle_id]
            entries = [self._blocks.pop(b) for b in doomed]
        for e in entries:
            close = getattr(e, "close", None)
            if close is not None:
                try:
                    close()
                except Exception:
                    pass  # srtpu: net-ok(best-effort handle release while dropping a finished shuffle; the blocks are dead either way)
        self.tcp.remove_shuffle(shuffle_id)

    def close(self) -> None:
        with self._lock:
            sids = {b[0] for b in self._blocks}
        for sid in sids:
            self.remove_shuffle(sid)
        self.tcp.close()
