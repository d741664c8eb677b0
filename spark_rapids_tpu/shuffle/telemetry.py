"""Shuffle & collective observatory: per-tier transfer telemetry.

ROADMAP item 3 (shuffle and scale-out) had zero measurement: none of
the four shuffle tiers — ICI collectives (shuffle/ici.py), the cached
device-resident tier, host-TCP transport (shuffle/tcp.py) and DCN
(shuffle/dcn.py) — recorded per-transfer phase walls, wire bytes or
queue/backpressure state, so a multi-device timeout was an opaque rc=124.
Theseus (PAPERS.md) argues data movement is *the* bottleneck of a
distributed columnar engine and Thallus specifies exactly the
per-transfer protocol telemetry this module records: every transfer at
the existing chokepoints (manager serialize/publish/fetch/deserialize,
TCP connect/send/recv framing, DCN publish/fetch, the per-device
collective dispatch wall around ``shard_map``) reports into a
process-wide **ShuffleObservatory**.

Cost model mirrors utils/movement.py and utils/faults.py: a module
global ``_OBSERVATORY`` that is ``None`` when disabled, so every hook
pays exactly one global load + is-None check when the observatory is
off (the zero-overhead pin tests/test_shuffle_observatory.py asserts
on). Byte counts may be callables so nothing is computed on the
disabled path.

Each transfer records (shuffle_id, map/reduce partition, tier, phase,
logical vs wire bytes, wall, retries, publish-queue depth) into a
bounded forensics ring plus exact aggregation:

- per-(query, tier) and per-(query, shuffle, tier) rollups with phase
  wall breakdowns — the ``shuffle_summary`` event-log payload;
- **straggler attribution**: per-(shuffle, partition, tier) walls give
  slowest-partition wall vs p50 and the worst triple, extending the v7
  ``shuffle_skew`` rows-based view with measured time;
- **sender/receiver stitching**: the SRTC traced wire header already
  carries a per-query trace id; both halves of one TCP transfer note
  it with the block identity, so ``stitched()`` pairs the client fetch
  wall with the server serve wall for the same block.

Surfacing follows the movement-ledger convention: tools/eventlog.py
writes ONE schema-v12 ``shuffle_summary`` record per query (null when
off) on success AND error paths; ``shuffle_telemetry_stats()`` feeds
the stats registry so statusd ``/metrics`` gauges, per-query event-log
stats deltas and the history sentinel's shuffle-wall gate come free.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

from ..conf import register_conf

__all__ = [
    "ShuffleObservatory",
    "TIERS",
    "configure_shuffle_telemetry",
    "reset_shuffle_telemetry",
    "active",
    "clock",
    "note_transfer",
    "drain_ring",
    "query_summary",
    "shuffle_telemetry_stats",
]

SHUFFLE_TELEMETRY_ENABLED = register_conf(
    "spark.rapids.tpu.shuffle.telemetry.enabled",
    "Enable the shuffle & collective observatory "
    "(shuffle/telemetry.py): every transfer on every shuffle tier "
    "(ici/local/cached/transport/dcn) is recorded with phase walls, "
    "logical vs wire bytes, retries and publish-queue depth; TCP "
    "sender/receiver halves are stitched via the SRTC trace header and "
    "each query's event log carries a shuffle_summary record with "
    "straggler attribution. When false (the default) every hook "
    "compiles down to a single module-constant check and nothing is "
    "recorded.",
    False)

SHUFFLE_TELEMETRY_RING_SIZE = register_conf(
    "spark.rapids.tpu.shuffle.telemetry.ringSize",
    "Bounded capacity of the shuffle observatory's raw-event forensics "
    "ring. Oldest events drop first; the per-(query, shuffle, tier) "
    "aggregation is exact regardless of ring occupancy.",
    4096,
    checker=lambda v: None if int(v) > 0 else "must be positive")


#: the transfer fabrics a note may attribute to — "ici" collective
#: all-to-all, "local" single-device exchange, "cached" device-resident
#: catalog blocks, "transport" host-TCP (incl. in-process transports),
#: "dcn" cross-slice data-center network
TIERS = ("ici", "local", "cached", "transport", "dcn")

#: keys of the per-query / process-wide totals dict — one place so the
#: event-log record, the stats source and the tests agree on the shape
TOTAL_KEYS = ("transfers", "logical_bytes", "wire_bytes", "retries",
              "stitched")


def _zero_totals() -> Dict[str, Any]:
    t: Dict[str, Any] = {k: 0 for k in TOTAL_KEYS}
    t["wall_s"] = 0.0
    t["max_queue_depth"] = 0
    return t


def _zero_agg() -> Dict[str, Any]:
    return {"count": 0, "logical_bytes": 0, "wire_bytes": 0,
            "wall_s": 0.0, "retries": 0, "max_queue_depth": 0,
            "phases": {}}


class ShuffleObservatory:
    """Process-wide ledger of shuffle/collective transfers.

    Raw events land in a bounded ring (forensics: the exact transfer
    sequence, for timeout diagnostics); exact
    aggregation is kept per (query, tier) and per (query, shuffle,
    tier), with per-(shuffle, partition, tier) walls for straggler
    attribution. All state is lock-guarded — hooks fire from pipeline
    workers, the TCP server thread, fetch pools and the query thread
    concurrently."""

    def __init__(self, ring_size: int = 4096):
        self.ring_size = int(ring_size)
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=self.ring_size)
        self._totals = _zero_totals()
        # (tier, phase) -> agg, process-wide
        self._agg: Dict[Tuple[str, str], Dict[str, Any]] = {}
        # query_id -> {"totals", "tiers", "shuffles", "partitions"}
        self._queries: Dict[Any, Dict[str, Any]] = {}
        # (trace_id, shuffle, map, partition) -> {"send": e, "recv": e}
        self._stitch: Dict[Tuple, Dict[str, Dict[str, Any]]] = {}
        self._stitched: List[Dict[str, Any]] = []

    # -- recording --------------------------------------------------------
    def note(self, tier: str, phase: str,
             shuffle_id: Any = None, map_id: Any = None,
             partition: Any = None,
             logical_bytes: Union[int, Callable[[], int]] = 0,
             wire_bytes: Union[int, Callable[[], int]] = 0,
             t0: float = 0.0, retries: int = 0, queue_depth: int = 0,
             trace_id: Any = None, side: Optional[str] = None,
             query_id: Any = None) -> None:
        """Record one transfer (or one phase of one). ``query_id``
        overrides node-context attribution for hooks running off the
        query thread (the TCP server half passes the traced header's
        qid). ``side`` ("send"/"recv") + ``trace_id`` + block identity
        stitch the two halves of one wire transfer."""
        wall = (time.perf_counter() - t0) if t0 else 0.0
        logical = int(logical_bytes() if callable(logical_bytes)
                      else logical_bytes)
        wire = int(wire_bytes() if callable(wire_bytes) else wire_bytes)
        operator = None
        if query_id is None:
            from ..utils import node_context
            ctx = node_context.current()
            operator = ctx.name if ctx is not None else None
            query_id = ctx.query_id if ctx is not None else None
        entry = {
            "ts": time.time(),
            "tier": tier,
            "phase": phase,
            "shuffle_id": shuffle_id,
            "map_id": map_id,
            "partition": partition,
            "logical_bytes": logical,
            "wire_bytes": wire,
            "wall_s": wall,
            "retries": int(retries),
            "queue_depth": int(queue_depth),
            "query_id": query_id,
            "operator": operator,
            "trace_id": trace_id,
            "side": side,
        }
        with self._lock:
            self._ring.append(entry)
            self._fold_totals(self._totals, entry)
            self._fold_agg(self._agg.setdefault((tier, phase),
                                                _zero_agg()), entry)
            q = self._queries.get(query_id)
            if q is None:
                q = self._queries[query_id] = {
                    "totals": _zero_totals(), "tiers": {},
                    "shuffles": {}, "partitions": {}}
            self._fold_totals(q["totals"], entry)
            self._fold_agg(q["tiers"].setdefault(tier, _zero_agg()),
                           entry)
            if shuffle_id is not None:
                self._fold_agg(
                    q["shuffles"].setdefault((shuffle_id, tier),
                                             _zero_agg()), entry)
            if shuffle_id is not None and partition is not None \
                    and wall > 0.0:
                pk = (shuffle_id, partition, tier)
                q["partitions"][pk] = \
                    q["partitions"].get(pk, 0.0) + wall
            if trace_id is not None and side in ("send", "recv"):
                self._fold_stitch(entry)

    @staticmethod
    def _fold_totals(totals: Dict[str, Any], entry: Dict) -> None:
        totals["transfers"] += 1
        totals["logical_bytes"] += entry["logical_bytes"]
        totals["wire_bytes"] += entry["wire_bytes"]
        totals["retries"] += entry["retries"]
        totals["wall_s"] += entry["wall_s"]
        if entry["queue_depth"] > totals["max_queue_depth"]:
            totals["max_queue_depth"] = entry["queue_depth"]

    @staticmethod
    def _fold_agg(a: Dict[str, Any], entry: Dict) -> None:
        a["count"] += 1
        a["logical_bytes"] += entry["logical_bytes"]
        a["wire_bytes"] += entry["wire_bytes"]
        a["wall_s"] += entry["wall_s"]
        a["retries"] += entry["retries"]
        if entry["queue_depth"] > a["max_queue_depth"]:
            a["max_queue_depth"] = entry["queue_depth"]
        ph = a["phases"]
        ph[entry["phase"]] = ph.get(entry["phase"], 0.0) \
            + entry["wall_s"]

    def _fold_stitch(self, entry: Dict) -> None:
        """Pair the two halves of one wire transfer on (trace id, block
        identity). Caller holds the lock."""
        key = (entry["trace_id"], entry["shuffle_id"],
               entry["map_id"], entry["partition"])
        halves = self._stitch.setdefault(key, {})
        halves[entry["side"]] = entry
        if "send" in halves and "recv" in halves:
            send, recv = halves["send"], halves["recv"]
            self._stitched.append({
                "trace_id": entry["trace_id"],
                "shuffle_id": entry["shuffle_id"],
                "map_id": entry["map_id"],
                "partition": entry["partition"],
                "send_tier": send["tier"],
                "send_wall_s": send["wall_s"],
                "send_bytes": send["wire_bytes"],
                "recv_wall_s": recv["wall_s"],
                "recv_bytes": recv["wire_bytes"],
            })
            del self._stitch[key]
            self._totals["stitched"] += 1
            q = self._queries.get(entry["query_id"])
            if q is not None:
                q["totals"]["stitched"] += 1

    # -- reads ------------------------------------------------------------
    def drain_ring(self) -> List[Dict[str, Any]]:
        with self._lock:
            out = list(self._ring)
            self._ring.clear()
            return out

    def totals(self) -> Dict[str, Any]:
        with self._lock:
            return dict(self._totals)

    def stitched(self) -> List[Dict[str, Any]]:
        """Completed sender/receiver pairs (both halves seen)."""
        with self._lock:
            return list(self._stitched)

    def tier_aggregate(self) -> List[Dict[str, Any]]:
        """Process-wide per-(tier, phase) rows, heaviest wall first."""
        with self._lock:
            rows = [{"tier": tier, "phase": phase,
                     **{k: v for k, v in a.items() if k != "phases"}}
                    for (tier, phase), a in self._agg.items()]
        rows.sort(key=lambda r: (-r["wall_s"], -r["wire_bytes"],
                                 r["tier"], r["phase"]))
        return rows

    @staticmethod
    def _straggler(partitions: Dict[Tuple, float]) -> Optional[Dict]:
        """Slowest-partition wall vs p50 over the per-(shuffle,
        partition, tier) walls — the measured-time extension of the v7
        rows-based ``shuffle_skew`` view."""
        if not partitions:
            return None
        walls = sorted(partitions.values())
        p50 = walls[len(walls) // 2]
        worst_key = max(partitions, key=lambda k: partitions[k])
        slowest = partitions[worst_key]
        return {
            "slowest_wall_s": slowest,
            "p50_wall_s": p50,
            "skew": (slowest / p50) if p50 > 0 else 0.0,
            "worst": {"shuffle_id": worst_key[0],
                      "partition": worst_key[1],
                      "tier": worst_key[2],
                      "wall_s": slowest},
        }

    def query_summary(self, query_id: Any,
                      drain: bool = True) -> Dict[str, Any]:
        """The per-query ``shuffle_summary`` payload: totals plus
        per-tier and per-(shuffle, tier) breakdowns (wall-heavy first)
        and straggler attribution. A query that shuffled nothing gets a
        zero summary — the event-log record set stays stable whether or
        not data moved."""
        with self._lock:
            q = (self._queries.pop(query_id, None) if drain
                 else self._queries.get(query_id))
        if q is None:
            return {"totals": _zero_totals(), "tiers": [],
                    "shuffles": [], "straggler": None}
        tiers = [{"tier": tier, **a, "phases": dict(a["phases"])}
                 for tier, a in q["tiers"].items()]
        tiers.sort(key=lambda r: (-r["wall_s"], -r["wire_bytes"],
                                  r["tier"]))
        shuffles = [{"shuffle_id": sid, "tier": tier,
                     **{k: v for k, v in a.items() if k != "phases"}}
                    for (sid, tier), a in q["shuffles"].items()]
        shuffles.sort(key=lambda r: (-r["wall_s"], -r["wire_bytes"],
                                     str(r["shuffle_id"]), r["tier"]))
        return {"totals": dict(q["totals"]), "tiers": tiers,
                "shuffles": shuffles,
                "straggler": self._straggler(q["partitions"])}


# ---------------------------------------------------------------------------
# module-level observatory: None when disabled (the zero-overhead pin)
# ---------------------------------------------------------------------------
_OBSERVATORY: Optional[ShuffleObservatory] = None


def clock() -> float:
    """Hook-side timestamp: perf_counter when the observatory is on,
    0.0 (= "don't time") when off. One global load + is-None check on
    the disabled path."""
    if _OBSERVATORY is None:
        return 0.0
    return time.perf_counter()


def note_transfer(tier: str, phase: str,
                  shuffle_id: Any = None, map_id: Any = None,
                  partition: Any = None,
                  logical_bytes: Union[int, Callable[[], int]] = 0,
                  wire_bytes: Union[int, Callable[[], int]] = 0,
                  t0: float = 0.0, retries: int = 0,
                  queue_depth: int = 0, trace_id: Any = None,
                  side: Optional[str] = None,
                  query_id: Any = None) -> None:
    """Hot-path transfer hook. Disabled: one global load + is-None
    check (the zero-overhead pin)."""
    if _OBSERVATORY is None:
        return
    _OBSERVATORY.note(tier, phase, shuffle_id=shuffle_id, map_id=map_id,
                      partition=partition, logical_bytes=logical_bytes,
                      wire_bytes=wire_bytes, t0=t0, retries=retries,
                      queue_depth=queue_depth, trace_id=trace_id,
                      side=side, query_id=query_id)


def configure_shuffle_telemetry(conf) -> Optional[ShuffleObservatory]:
    """Install (or clear) the process-wide observatory from a
    RapidsConf (TpuSession.__init__ chokepoint — the most recent
    session wins)."""
    global _OBSERVATORY
    if not conf.get(SHUFFLE_TELEMETRY_ENABLED):
        _OBSERVATORY = None
        return None
    _OBSERVATORY = ShuffleObservatory(
        int(conf.get(SHUFFLE_TELEMETRY_RING_SIZE)))
    return _OBSERVATORY


def reset_shuffle_telemetry() -> None:
    global _OBSERVATORY
    _OBSERVATORY = None


def active() -> Optional[ShuffleObservatory]:
    return _OBSERVATORY


def drain_ring() -> List[Dict[str, Any]]:
    obs = _OBSERVATORY
    return obs.drain_ring() if obs is not None else []


def query_summary(query_id: Any,
                  drain: bool = True) -> Optional[Dict[str, Any]]:
    """Per-query shuffle summary for the event log; None when the
    observatory is off (the v12 record's null-payload convention)."""
    obs = _OBSERVATORY
    if obs is None:
        return None
    return obs.query_summary(query_id, drain=drain)


def shuffle_telemetry_stats() -> Dict[str, Any]:
    """Stats-registry source: process-wide transfer totals, flattened
    as ``shuffle_telemetry_*`` gauges on /metrics and per-query
    event-log stats deltas (the history sentinel's shuffle-wall gate
    reads ``shuffle_telemetry_wall_s``). Empty when the observatory is
    off."""
    obs = _OBSERVATORY
    if obs is None:
        return {}
    t = obs.totals()
    t["wall_s"] = round(t["wall_s"], 6)
    return t
