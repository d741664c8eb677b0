"""Process-wide span tracer + Chrome trace-event exporter.

Reference: the plugin scopes device work in NVTX ranges
(NvtxWithMetrics.scala) and relies on Nsight for timeline analysis; the
TPU runtime owns its execution loop, so it records its own spans instead:
query -> AQE stage -> partition task -> operator batch, plus subsystem
spans (shuffle write/fetch, XLA compile, host->device upload, spill,
semaphore wait) and instant events (device OOM).

Design constraints:
- thread-safe: operators run on executor worker threads; one global ring
  buffer collects events from all of them.
- bounded: a ring buffer (``spark.rapids.tpu.trace.bufferSize`` events)
  caps memory no matter how long the session runs; overflow drops the
  OLDEST events and counts the drops.
- one span API, three sinks. ``Tracer.span()`` always (1) opens a
  ``jax.profiler.TraceAnnotation("srt.<name>")`` when a profiler session
  is capturing, so the engine's spans lie in the xplane's host plane on
  the clock the device planes are synchronised to, and (2) books its self
  time, its bytes and its counted arguments (``COUNTED_ARGS``) to the
  running query's phase totals (``recent_queries``); only (3), the ring
  buffer + Chrome export, is gated by ``spark.rapids.tpu.trace.enabled``.
  With no profiler session and the ring off a span costs two clock reads
  and a dict update; ``device`` (``span(on=...)``) is worked out only
  while one of the two is on.

The export format is the Chrome trace-event JSON (``ph: "X"`` complete
events with microsecond timestamps), loadable in Perfetto / chrome://tracing
and in TensorBoard's trace viewer.
"""
from __future__ import annotations

import itertools
import json
import os
import struct
import threading
import time
import uuid
from collections import deque
from contextlib import contextmanager
from typing import Callable, Dict, List, Optional

from jax.profiler import TraceAnnotation

from ..conf import register_conf

__all__ = ["TraceEvent", "Tracer", "TraceContext", "QuerySummary",
           "get_tracer", "ANNOTATION_PREFIX", "STRUCTURAL_SPANS",
           "COUNTED_ARGS", "device_of",
           "set_tracer", "configure_tracer", "tracer_stats",
           "mint_trace_context", "current_trace_context",
           "activate_trace_context", "new_span_id",
           "TRACE_ENABLED", "TRACE_BUFFER_SIZE", "TRACE_DIR",
           "TRACE_DISTRIBUTED", "TRACE_DISTRIBUTED_DIR",
           "TRACE_CLOCK_PROBES"]

TRACE_ENABLED = register_conf(
    "spark.rapids.tpu.trace.enabled",
    "Keep runtime spans (query/plan/scan/h2d/dispatch/sync/d2h, stage/task/"
    "operator, shuffle, compile, spill and semaphore-wait events) in the "
    "process-wide tracer's ring buffer for the Chrome-trace export (the "
    "NVTX-range analogue; reference: NvtxWithMetrics.scala). The spans "
    "themselves are always on: they appear in any jax.profiler capture as "
    "srt.<name> and feed TpuSession.last_query_phases() whatever this is "
    "set to. Export with Tracer.to_chrome_trace() or "
    "spark.rapids.tpu.trace.dir.", False)

TRACE_BUFFER_SIZE = register_conf(
    "spark.rapids.tpu.trace.bufferSize",
    "Ring-buffer capacity of the tracer in events; overflow drops the "
    "oldest events (drop count is reported in the exported trace metadata).",
    65536, checker=lambda v: None if v > 0 else f"must be positive, got {v}")

TRACE_DIR = register_conf(
    "spark.rapids.tpu.trace.dir",
    "Directory to dump the Chrome trace-event JSON into on session close "
    "(one file per session, loadable in Perfetto / chrome://tracing). "
    "Empty disables the dump.", "")

TRACE_DISTRIBUTED = register_conf(
    "spark.rapids.tpu.trace.distributed.enabled",
    "Propagate the per-query TraceContext (trace_id, parent span id, "
    "query_id) across process boundaries: ProcessCluster task envelopes "
    "and the TCP/DCN shuffle wire headers. Worker-side spans then parent "
    "under the driver's query span in the merged timeline "
    "(tools/trace.py merge). Near-zero cost; only disable to bisect "
    "wire-protocol issues.", True)

TRACE_DISTRIBUTED_DIR = register_conf(
    "spark.rapids.tpu.trace.distributed.dir",
    "Directory where each PROCESS (driver and every ProcessCluster "
    "worker) dumps its own Chrome trace on shutdown/flush, named "
    "trace-<process_name>.json — the input set for "
    "`python -m spark_rapids_tpu.tools.trace merge`. Empty disables.", "")

TRACE_CLOCK_PROBES = register_conf(
    "spark.rapids.tpu.trace.distributed.clockProbes",
    "Number of clock-handshake probes per ProcessCluster worker used to "
    "estimate the worker->driver wall-clock offset (the probe with the "
    "smallest round trip wins, NTP-style); the estimate aligns worker "
    "span timestamps in the merged timeline.", 5,
    checker=lambda v: None if v > 0 else f"must be positive, got {v}")


# ---------------------------------------------------------------------------
# trace context: the cross-process identity of one query's timeline
# ---------------------------------------------------------------------------
_SPAN_SEQ = itertools.count(1)


def new_span_id() -> int:
    """Process-unique span id: pid in the high bits, a monotonic counter
    in the low bits — two processes can never mint the same id, so the
    merged span DAG needs no renumbering."""
    return ((os.getpid() & 0xFFFF) << 40) | (next(_SPAN_SEQ) & 0xFFFFFFFFFF)


class TraceContext:
    """Identity carried across every process boundary a query touches:
    which trace (query execution) an event belongs to and which span it
    parents under. Immutable; ``child()`` derives the context a nested
    span propagates."""

    __slots__ = ("trace_id", "span_id", "query_id")

    #: wire encoding for the TCP shuffle header: 16 ascii-hex chars of
    #: trace_id, u64 parent span id, i64 query id (-1 = none)
    WIRE = struct.Struct("<16sQq")

    def __init__(self, trace_id: str, span_id: int,
                 query_id: Optional[int] = None):
        self.trace_id = trace_id
        self.span_id = span_id
        self.query_id = query_id

    def child(self, span_id: int) -> "TraceContext":
        return TraceContext(self.trace_id, span_id, self.query_id)

    # -- serialization (task envelopes use the dict form; the TCP wire
    #    uses the fixed-size pack) --------------------------------------
    def to_wire(self) -> Dict:
        return {"trace_id": self.trace_id, "span_id": self.span_id,
                "query_id": self.query_id}

    @classmethod
    def from_wire(cls, d: Optional[Dict]) -> Optional["TraceContext"]:
        if not d:
            return None
        return cls(d["trace_id"], d["span_id"], d.get("query_id"))

    def pack(self) -> bytes:
        return self.WIRE.pack(
            self.trace_id[:16].ljust(16, "0").encode("ascii"),
            self.span_id,
            -1 if self.query_id is None else int(self.query_id))

    @classmethod
    def unpack(cls, raw: bytes) -> "TraceContext":
        tid, span_id, qid = cls.WIRE.unpack(raw)
        return cls(tid.decode("ascii"), span_id,
                   None if qid < 0 else qid)

    def __repr__(self):
        return (f"TraceContext({self.trace_id!r}, span={self.span_id}, "
                f"query={self.query_id})")


_CTX_TLS = threading.local()


def current_trace_context() -> Optional[TraceContext]:
    """The TraceContext active on THIS thread (None outside a query)."""
    stack = getattr(_CTX_TLS, "stack", None)
    return stack[-1] if stack else None


@contextmanager
def activate_trace_context(ctx: Optional[TraceContext]):
    """Make ``ctx`` the current context for the with-block (no-op on
    None, so call sites need no conditionals)."""
    if ctx is None:
        yield None
        return
    stack = getattr(_CTX_TLS, "stack", None)
    if stack is None:
        stack = _CTX_TLS.stack = []
    stack.append(ctx)
    try:
        yield ctx
    finally:
        stack.pop()


def mint_trace_context(query_id: Optional[int] = None) -> TraceContext:
    """A fresh trace root (driver side, one per query)."""
    return TraceContext(uuid.uuid4().hex[:16], new_span_id(), query_id)


class TraceEvent:
    """One recorded event. ``ts``/``dur`` are microseconds relative to the
    tracer's epoch; ``ph`` is the Chrome trace phase ("X" complete span,
    "i" instant)."""

    __slots__ = ("name", "cat", "ph", "ts", "dur", "tid", "depth", "args")

    def __init__(self, name: str, cat: str, ph: str, ts: float, dur: float,
                 tid: int, depth: int, args: Optional[Dict] = None):
        self.name = name
        self.cat = cat
        self.ph = ph
        self.ts = ts
        self.dur = dur
        self.tid = tid
        self.depth = depth
        self.args = args or {}

    def to_chrome(self, pid: int = 1) -> Dict:
        ev: Dict = {"name": self.name, "cat": self.cat, "ph": self.ph,
                    "ts": round(self.ts, 3), "pid": pid, "tid": self.tid}
        if self.ph == "X":
            ev["dur"] = round(self.dur, 3)
        if self.ph == "i":
            ev["s"] = "t"  # instant scope: thread
        args = dict(self.args)
        args["depth"] = self.depth
        ev["args"] = args
        return ev

    def __repr__(self):
        return (f"TraceEvent({self.name!r}, cat={self.cat!r}, ph={self.ph!r}, "
                f"ts={self.ts:.1f}us, dur={self.dur:.1f}us, "
                f"depth={self.depth})")


#: every engine span's name in a ``jax.profiler`` capture starts with this
ANNOTATION_PREFIX = "srt."

#: spans whose time is really other spans': those that only group (the
#: query root, a partition task, an AQE stage), the consumer's wait on
#: the engine's own producer thread, and a join's ``join.build`` /
#: ``join.grace``, which hold the drain of a child plan. Their self time is
#: booked like any phase's, but they do not count as COVERED wall: a
#: ``task`` span tiles a whole drain and ``wait.pipeline`` the whole of a
#: producer's work, and counting them would hide exactly the time no phase
#: span claims (``host_unattributed_share``).
STRUCTURAL_SPANS = frozenset({"query", "task", "stage", "wait.pipeline",
                              "join.build", "join.grace"})

#: span arguments that are summed per phase beside ``calls`` / ``self_s`` /
#: ``bytes`` (a boolean counts 0 / 1): row and group counts, the trip counts
#: of the hash loops, scalars a ``sync`` read, what ``stage.stats`` walked,
#: the producers an ``exchange.map`` started together, the slot quota and
#: the slots of an ``exchange.count``, the probe rows an outer join
#: emitted null-extended (``join.probe.expand``).
#: ``to_dict`` emits them flat where non-zero, so a reader that takes
#: ``phases[name].get(field, 0)`` reads ``field="rounds"`` as it reads
#: ``"calls"``
COUNTED_ARGS = frozenset({"rows", "rows_out", "groups", "rounds",
                          "full_rounds", "parts", "scalars", "unique",
                          "shards", "handles", "producers", "quota",
                          "slots", "unmatched"})

#: queries whose phase totals ``Tracer.recent_queries`` remembers
RECENT_QUERIES = 256
#: phase-span intervals remembered per query for the covered-wall union;
#: past it the totals keep counting and ``spans_dropped`` says how many
#: intervals the union lacks
QUERY_SPAN_CAP = 2048

_QUERY_SEQ = itertools.count(1)


def _union_s(intervals: List) -> float:
    total, hi = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if s > hi:
            total += e - s
            hi = e
        elif e > hi:
            total += e - hi
            hi = e
    return total


class QuerySummary:
    """Per-phase totals of ONE ``collect()`` — the counter half of the
    spans, always on. Filled by every span that closes while the query
    is the thread's current one (pool threads get it from
    ``Tracer.bind_query``), sealed when the ``query`` span closes.

    ``phases[name]`` is ``[calls, self_s, bytes]``: thread-seconds of
    SELF time (duration minus what child spans on the same thread
    cover), so phases sum to at most ``wall_s`` x ``threads``;
    ``counts[name]`` holds the sums of the spans' ``COUNTED_ARGS``.
    ``covered_s`` is the union over all threads of the non-structural
    spans' intervals, clipped to the query span."""

    __slots__ = ("query_id", "t0", "wall_s", "phases", "counts",
                 "covered_s", "spans_dropped", "_intervals", "_threads",
                 "_lock")

    def __init__(self, query_id: int, t0: float):
        self.query_id = query_id
        self.t0 = t0
        self.wall_s: Optional[float] = None     # None while the query runs
        self.phases: Dict[str, List] = {}
        self.counts: Dict[str, Dict[str, float]] = {}
        self.covered_s = 0.0
        self.spans_dropped = 0
        self._intervals: List = []
        self._threads = set()
        self._lock = threading.Lock()

    def _book(self, name: str, t0: float, t1: float, self_s: float,
              nbytes: int, counted: Optional[Dict] = None) -> None:
        with self._lock:
            if self.wall_s is not None:
                return      # a straggler of a query that already returned
            p = self.phases.get(name)
            if p is None:
                p = self.phases[name] = [0, 0.0, 0]
            p[0] += 1
            p[1] += self_s
            p[2] += nbytes
            if counted:
                sums = self.counts.setdefault(name, {})
                for k, v in counted.items():
                    sums[k] = sums.get(k, 0) + v
            self._threads.add(threading.get_ident())
            if name not in STRUCTURAL_SPANS:
                if len(self._intervals) < QUERY_SPAN_CAP:
                    self._intervals.append((t0, t1))
                else:
                    self.spans_dropped += 1

    def _seal(self, t1: float) -> None:
        with self._lock:
            self.wall_s = t1 - self.t0
            self.covered_s = _union_s(
                [(max(s, self.t0), min(e, t1)) for s, e in self._intervals
                 if min(e, t1) > max(s, self.t0)])
            self._intervals = []

    def to_dict(self) -> Dict:
        with self._lock:
            return {
                "query_id": self.query_id, "wall_s": self.wall_s,
                "covered_s": self.covered_s,
                "threads": len(self._threads),
                "spans_dropped": self.spans_dropped,
                "phases": {n: {"calls": p[0], "self_s": p[1], "bytes": p[2],
                               **{k: v for k, v in
                                  self.counts.get(n, {}).items() if v}}
                           for n, p in self.phases.items()}}


def _small_args(args: Dict) -> Dict:
    """What of a span's args rides on its TraceAnnotation: numbers and
    short strings (an xplane event's stats are not the place for a plan
    signature)."""
    return {k: v for k, v in args.items()
            if isinstance(v, (bool, int, float))
            or (isinstance(v, str) and len(v) <= 64)}


def device_of(values) -> int:
    """The id of the ONE device every ``jax.Array`` leaf of ``values`` lives
    on; -1 where there is no such device: an array sharded over a mesh,
    arrays on several devices, or nothing that lives on a device. Called by
    a span given ``on=`` and only while a profiler session captures or the
    ring is on."""
    import jax
    found = -1
    for leaf in jax.tree_util.tree_leaves(values):
        if not isinstance(leaf, jax.Array) \
                or isinstance(leaf, jax.core.Tracer):
            continue
        devices = leaf.devices()
        if len(devices) != 1:
            return -1
        (d,) = devices
        if found not in (-1, d.id):
            return -1
        found = d.id
    return found


class _Span:
    """One open span (``Tracer.span`` / ``Tracer.query``): a small class
    with ``__enter__``/``__exit__``, not a generator — a disabled
    ``@contextmanager`` cost 1.2 us a span, this costs the two clock
    reads. ``note(bytes=...)`` adds args known only once the work ran."""

    __slots__ = ("_tracer", "name", "cat", "args", "_t0", "_child_s",
                 "_parent", "_query", "_ann", "_ring", "_ctx", "_span_id",
                 "_root", "_pushed", "_on")

    def __init__(self, tracer: "Tracer", name: str, cat: str, args: Dict,
                 root: bool = False, ctx: Optional[TraceContext] = None,
                 on=None):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self._on = on
        self._root = root
        self._ctx = ctx
        self._child_s = 0.0
        self._ann = None
        self._span_id = None

    def note(self, **args) -> None:
        self.args.update(args)

    def __enter__(self) -> "_Span":
        tracer = self._tracer
        tls = tracer._tls
        stack = getattr(tls, "stack", None)
        if stack is None:
            stack = tls.stack = []
        self._parent = stack[-1] if stack else None
        query = getattr(tls, "query", None)
        if self._root and query is not None:
            self._root = False      # a collect() inside a collect(): nested
        if self._root:
            query = QuerySummary(next(_QUERY_SEQ), 0.0)
            tls.query = query
            self.args.setdefault("query_id", query.query_id)
        self._query = query
        self._ring = tracer.enabled
        # a query root activates the TraceContext it was given; with the
        # ring on, every span under a context gets a span id and
        # re-parents the context for its block (the cross-process DAG)
        given = self._ctx
        ctx = given if given is not None or not self._ring \
            else current_trace_context()
        self._ctx = ctx
        self._pushed = 0
        if ctx is not None:
            cstack = getattr(_CTX_TLS, "stack", None)
            if cstack is None:
                cstack = _CTX_TLS.stack = []
            if given is not None:
                cstack.append(ctx)
                self._pushed += 1
            if self._ring:
                self._span_id = new_span_id()
                cstack.append(ctx.child(self._span_id))
                self._pushed += 1
        capturing = TraceAnnotation.is_enabled()
        if self._on is not None:
            if capturing or self._ring:
                self.args["device"] = device_of(self._on)
            self._on = None     # hold no array past the block's start
        if capturing:
            small = _small_args(self.args)
            if query is not None:
                small["query_id"] = query.query_id
            self._ann = TraceAnnotation(ANNOTATION_PREFIX + self.name,
                                        **small)
            self._ann.__enter__()
        stack.append(self)
        self._t0 = time.perf_counter()
        if self._root:
            query.t0 = self._t0
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        t1 = time.perf_counter()
        tracer = self._tracer
        tls = tracer._tls
        stack = tracer._stack()
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:     # closed out of order (a generator's span)
            stack.remove(self)
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        dur = t1 - self._t0
        if self._parent is not None:
            self._parent._child_s += dur
        query = self._query
        if query is not None:
            args = self.args
            counted = None if COUNTED_ARGS.isdisjoint(args) else \
                {k: v for k, v in args.items()
                 if k in COUNTED_ARGS and isinstance(v, (int, float))}
            query._book(self.name, self._t0, t1,
                        max(0.0, dur - self._child_s),
                        int(args.get("bytes", 0) or 0), counted)
            if self._root:
                query._seal(t1)
                tls.query = None
                tracer._remember(query)
        if self._pushed:
            del _CTX_TLS.stack[-self._pushed:]
        if self._ring:
            args = self.args
            if self._parent is not None:
                args = dict(args, parent=self._parent.name)
            if query is not None and self._ctx is None \
                    and "query_id" not in args:
                args = dict(args, query_id=query.query_id)
            tracer._record(TraceEvent(
                self.name, self.cat, "X", (self._t0 - tracer.epoch) * 1e6,
                dur * 1e6, threading.get_ident(), len(stack),
                tracer._ctx_args(args, self._ctx, self._span_id)))
        return False

    @property
    def summary(self) -> Optional[QuerySummary]:
        return self._query


class _BoundQuery:
    """``with`` scope that makes a query the current one of a pool
    thread for the length of one task."""

    __slots__ = ("_tls", "_query", "_prev")

    def __init__(self, tls, query: Optional[QuerySummary]):
        self._tls = tls
        self._query = query

    def __enter__(self):
        self._prev = getattr(self._tls, "query", None)
        self._tls.query = self._query
        return self._query

    def __exit__(self, *exc) -> bool:
        self._tls.query = self._prev
        return False


class Tracer:
    """Thread-safe span recorder: the one span API of the engine."""

    def __init__(self, capacity: int = 65536, enabled: bool = False,
                 process_name: Optional[str] = None):
        self.enabled = enabled
        self.capacity = capacity
        self._events: deque = deque(maxlen=capacity)
        self._recent: deque = deque(maxlen=RECENT_QUERIES)
        self._lock = threading.Lock()
        self._tls = threading.local()
        # epoch (perf_counter domain) and its wall-clock anchor are taken
        # at the SAME instant: merged timelines place this process's
        # events at epoch_unix + ts, then correct by the handshake offset
        self.epoch = time.perf_counter()
        self.epoch_unix = time.time()
        self.process_name = process_name or f"pid-{os.getpid()}"
        self.dropped = 0
        self._drop_warned = False

    # -- recording ------------------------------------------------------------
    def _stack(self) -> List[_Span]:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def _record(self, ev: TraceEvent) -> None:
        warn = False
        with self._lock:
            if len(self._events) == self.capacity:
                self.dropped += 1
                if not self._drop_warned:
                    self._drop_warned = True
                    warn = True
            self._events.append(ev)
        if warn:
            # once per session of drops: a wrapped ring buffer means the
            # exported Chrome trace is silently truncated at the front
            import warnings
            warnings.warn(
                "tracer ring buffer wrapped — oldest spans are being "
                "dropped and the exported trace will be truncated; raise "
                "spark.rapids.tpu.trace.bufferSize "
                f"(currently {self.capacity})", RuntimeWarning)

    @staticmethod
    def _ctx_args(args: Dict,
                  ctx: Optional[TraceContext] = None,
                  span_id: Optional[int] = None) -> Dict:
        """Fold the active TraceContext into event args: trace_id +
        query_id tie the event to one query's timeline, span_id /
        parent_span_id link the cross-process span DAG. No context
        active -> args unchanged (process-local tracing stays lean)."""
        ctx = ctx if ctx is not None else current_trace_context()
        if ctx is None:
            return args
        out = dict(args)
        out["trace_id"] = ctx.trace_id
        out["span_id"] = span_id if span_id is not None else new_span_id()
        out["parent_span_id"] = ctx.span_id
        if ctx.query_id is not None:
            out["query_id"] = out.get("query_id", ctx.query_id)
        return out

    def span(self, name: str, cat: str = "misc", on=None, **args) -> _Span:
        """A span around the with-block, into all three sinks (module
        docstring). Nesting is tracked per thread: a span's self time is
        its duration minus its children's. Under an active TraceContext
        (ring on) the span gets its own span id and re-parents the context
        for the block, so nested spans (this thread or a remote process
        the block talks to) chain under it. ``on`` is the arrays (any
        pytree) the block reads or runs on: while a profiler session
        captures or the ring is on the span carries ``device``
        (``device_of(on)``); otherwise ``on`` is not looked at."""
        return _Span(self, name, cat, args, on=on)

    def complete(self, name: str, cat: str, start_s: float, dur_s: float,
                 **args) -> None:
        """Record a ring-buffer event with caller-measured times
        (``time.perf_counter()`` domain) — for the per-batch operator
        instrumentation (tools/profiler.py), which owns its timers."""
        if not self.enabled:
            return
        self._record(TraceEvent(
            name, cat, "X", (start_s - self.epoch) * 1e6, dur_s * 1e6,
            threading.get_ident(), len(self._stack()),
            self._ctx_args(args)))

    def query(self, ctx: Optional[TraceContext] = None, **args) -> _Span:
        """The root span ``query`` of one ``collect()``: mints the query
        id every span of the query carries, starts its phase totals, and
        files them under ``recent_queries`` when the block ends. ``ctx``
        (the event log's TraceContext) is activated for the block. Inside
        another query on the same thread it is a plain nested span."""
        return _Span(self, "query", "query", args, root=True, ctx=ctx)

    # -- per-query phase totals ---------------------------------------------
    def current_query(self) -> Optional[QuerySummary]:
        """The query whose spans THIS thread is recording, if any."""
        return getattr(self._tls, "query", None)

    def bind_query(self, fn: Callable) -> Callable:
        """``fn`` bound to the calling thread's current query, for
        handing to a pool or a worker thread: its spans are then booked to
        that query — carried in the task, never through a process global,
        so concurrent queries do not mix."""
        query = self.current_query()
        if query is None:
            return fn

        def bound(*args, **kwargs):
            with _BoundQuery(self._tls, query):
                return fn(*args, **kwargs)
        return bound

    def _remember(self, query: QuerySummary) -> None:
        with self._lock:
            self._recent.append(query)

    def recent_queries(self, n: int = RECENT_QUERIES) -> List[Dict]:
        """Phase totals of the newest ``n`` finished queries, oldest
        first (at most the last 256)."""
        with self._lock:
            recent = list(self._recent)
        return [q.to_dict() for q in recent[-n:]] if n > 0 else []

    def instant(self, name: str, cat: str = "misc", **args) -> None:
        if not self.enabled:
            return
        self._record(TraceEvent(
            name, cat, "i", (time.perf_counter() - self.epoch) * 1e6, 0.0,
            threading.get_ident(), len(self._stack()),
            self._ctx_args(args)))

    # -- inspection / export --------------------------------------------------
    def events(self) -> List[TraceEvent]:
        with self._lock:
            return list(self._events)

    def categories(self) -> set:
        return {e.cat for e in self.events()}

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self.dropped = 0
            self._drop_warned = False

    def drain(self) -> Dict:
        """Atomically snapshot-and-reset: returns a Chrome trace of
        everything recorded since the last drain, with the drop count
        scoped to THAT window (per-process, per-flush accounting — a
        worker's per-query flush attributes its drops to the query that
        overflowed the ring, and the counter starts clean for the next
        one). The epoch is NOT reset: timestamps across drains stay in
        one timebase."""
        with self._lock:
            evs = list(self._events)
            dropped = self.dropped
            self._events.clear()
            self.dropped = 0
            self._drop_warned = False
        return self._chrome(evs, dropped)

    def _chrome(self, evs: List[TraceEvent], dropped: int) -> Dict:
        return {
            "traceEvents": [e.to_chrome(pid=os.getpid()) for e in evs],
            "displayTimeUnit": "ms",
            "otherData": {
                "producer": "spark-rapids-tpu",
                "dropped_events": dropped,
                "pid": os.getpid(),
                "process_name": self.process_name,
                "epoch_unix": self.epoch_unix,
            },
        }

    def to_chrome_trace(self) -> Dict:
        """Chrome trace-event JSON object ({"traceEvents": [...]}), loadable
        in Perfetto/chrome://tracing. ``otherData`` carries the process
        identity + wall-clock anchor tools/trace.py needs to merge traces
        from several processes onto one timeline."""
        return self._chrome(self.events(), self.dropped)

    def dump(self, path: str) -> str:
        """Write the Chrome trace JSON to ``path``; returns the path."""
        import os
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w", encoding="utf-8") as f:
            json.dump(self.to_chrome_trace(), f)
        return path


def tracer_stats() -> Dict:
    """Flat tracer counters for the process StatsRegistry (utils/metrics.py)
    — ``spans_dropped`` > 0 flags a truncated Perfetto trace that would
    otherwise silently mislead."""
    t = get_tracer()
    with t._lock:
        return {"enabled": t.enabled, "capacity": t.capacity,
                "events_buffered": len(t._events),
                "spans_dropped": t.dropped}


_GLOBAL = Tracer()
_GLOBAL_LOCK = threading.Lock()


def get_tracer() -> Tracer:
    return _GLOBAL


def set_tracer(tracer: Tracer) -> None:
    global _GLOBAL
    with _GLOBAL_LOCK:
        _GLOBAL = tracer


def configure_tracer(conf) -> Tracer:
    """Apply conf to the global tracer (session init chokepoint).

    Sticky semantics: the tracer is process-wide and sessions come and go,
    so a session whose conf leaves tracing at the default must NOT disable
    a tracer another session enabled (nor shrink its buffer, dropping
    already-recorded events). Enabling turns it on; turning it off again is
    an explicit act: ``get_tracer().enabled = False``. The buffer resizes
    only when this conf sets a non-default size; resizing preserves the
    newest events."""
    tracer = _GLOBAL
    with _GLOBAL_LOCK:
        if bool(conf.get(TRACE_ENABLED)):
            tracer.enabled = True
        capacity = int(conf.get(TRACE_BUFFER_SIZE))
        if capacity != tracer.capacity \
                and capacity != TRACE_BUFFER_SIZE.default:
            with tracer._lock:
                tracer.dropped += max(0, len(tracer._events) - capacity)
                tracer.capacity = capacity
                tracer._events = deque(tracer._events, maxlen=capacity)
    return tracer
