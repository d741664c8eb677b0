"""Pipelined multi-partition execution engine.

The reference plugin gets its throughput from running many Spark tasks
concurrently against one device, gated by ``GpuSemaphore``, so host-side
decode/serialization overlaps device kernels (Plugin.scala +
GpuSemaphore.scala). The sequential port executed partitions one at a time
through synchronous iterators, leaving the TPU idle during every host
decode, H2D upload and shuffle write. This module supplies the three
overlap mechanisms:

- ``pipelined_collect(plan, conf)``: drains multiple partitions
  concurrently from a bounded task pool, each task holding the
  ``TpuSemaphore`` while it drives device work (the ExecutorContext /
  concurrent-GPU-tasks analogue). Host-side stages of one partition
  overlap device stages of another.
- ``prefetched(make_iter, ...)``: stage-decouples an iterator chain with a
  SMALL BOUNDED queue fed by a background worker, so host decode/IO,
  ``HostToDeviceExec`` upload, jitted compute (riding JAX async dispatch)
  and downloads/shuffle writes run double-buffered within one partition.
  Exec nodes opt in at their stage boundaries (exec/transitions.py,
  exec/wholestage.py).
- ``OrderedFanIn(makers, ...)``: many producers, one ordered consumer —
  one such bounded queue and worker a maker, all started together, drained
  maker 0 to its end, then 1, and so on. The mesh exchange's map side
  (exec/exchange.py) produces its child's partitions through it, so each
  device runs its partition while the collective's thread consumes them in
  partition order.

Design rules:

- Every queue is BOUNDED (``prefetchDepth``); an unbounded queue would
  re-materialize whole partitions in memory and is rejected by the tier-1
  lint test (tests/test_pipeline.py).
- Failure propagation: a worker exception crosses the queue as a poison
  pill carrying the originating stage context, the queues drain, and the
  ORIGINAL exception re-raises in the consumer — an error must surface,
  never hang.
- The input-file holder (io/file_block.py) is thread-local; each queue
  item carries the producer's holder state and the consumer restores it
  before yielding, so ``input_file_name()`` attribution survives the
  thread hop.
- ``pipelineWait`` (seconds the consumer blocked on an empty queue) and
  ``prefetchQueueDepth`` (occupancy histogram) are accounted on the
  consuming node's ``MetricRegistry`` and mirrored as ``pipeline`` trace
  spans, so ``tools/diagnose.py`` can rank pipeline stalls.

Sequential mode (``spark.rapids.tpu.pipeline.enabled=false``) keeps the
old synchronous behavior and is the correctness oracle.
"""
from __future__ import annotations

import queue
import threading
import time
from contextlib import contextmanager
from typing import Callable, Iterator, List, Optional, Sequence, TypeVar

from ..conf import register_conf

__all__ = ["PIPELINE_ENABLED", "PIPELINE_PREFETCH_DEPTH",
           "PIPELINE_TASK_POOL", "configure_pipeline", "pipeline_enabled",
           "prefetch_depth", "task_pool_size", "prefetched",
           "maybe_prefetched", "OrderedFanIn", "pipelined_collect",
           "parallel_map", "active_workers", "shutdown_workers",
           "pipeline_stats", "pipeline_snapshot", "note_progress",
           "stage_name"]


def stage_name(node) -> str:
    """Display name of a plan node for span/metric labels (tolerates test
    stubs without the PhysicalPlan surface)."""
    fn = getattr(node, "node_name", None)
    try:
        return fn() if callable(fn) else type(node).__name__
    except Exception:
        return type(node).__name__


# ---------------------------------------------------------------------------
# semaphore exemption for pipeline worker threads.
#
# Admission is TASK-scoped: the partition's task thread holds the
# TpuSemaphore; the prefetch/map workers it spawns run UNDER that
# admission. A worker must therefore never acquire a permit of its own —
# with concurrentGpuTasks=1 a task blocked on its own worker while the
# worker blocks acquiring the permit the task holds is a deadlock
# (observed with the python-UDF exec's release-reacquire pattern,
# udf/python_exec.py). TpuSemaphore.acquire_if_necessary consults
# ``semaphore_exempt()``; ``pipelined_collect`` clears the flag in its
# drain (the pool thread IS the task there and must take admission).
# ---------------------------------------------------------------------------
_WORKER_TLS = threading.local()


def semaphore_exempt() -> bool:
    """True on pipeline worker threads — device admission was already
    granted to the owning task (memory/semaphore.py consults this)."""
    return getattr(_WORKER_TLS, "exempt", False)


@contextmanager
def _worker_scope():
    prev = getattr(_WORKER_TLS, "exempt", False)
    _WORKER_TLS.exempt = True
    try:
        yield
    finally:
        _WORKER_TLS.exempt = prev


#: public name for the same scope, used by nodes whose SHARED materialize
#: lock may be held while operators (python-UDF exec) release/reacquire
#: the semaphore. Invariant: a thread must never BLOCK on the TpuSemaphore
#: while holding a materialize lock another admitted task may want —
#: permit-holder A (in the lock, reacquiring) and lock-waiter B (holding
#: the permit) would deadlock at concurrentGpuTasks=1. Inside this scope
#: acquires no-op; admission is advisory there.
exempt_admission = _worker_scope


@contextmanager
def task_admission():
    """The inverse scope: this thread is a TASK and takes real admission
    (used by pipelined_collect's drains and the write path's map tasks —
    anything that is a top-level unit of device work, not a stage worker
    under an already-admitted task)."""
    prev = getattr(_WORKER_TLS, "exempt", False)
    _WORKER_TLS.exempt = False
    try:
        yield
    finally:
        _WORKER_TLS.exempt = prev


_task_admission = task_admission  # internal alias

PIPELINE_ENABLED = register_conf(
    "spark.rapids.tpu.pipeline.enabled",
    "Overlap host decode, host->device upload, XLA compute and "
    "shuffle/download work: partitions drain concurrently from a bounded "
    "task pool under TpuSemaphore admission, and stage boundaries inside a "
    "partition hand batches through small bounded prefetch queues "
    "(reference: concurrent Spark tasks gated by GpuSemaphore, "
    "Plugin.scala + GpuSemaphore.scala). 'false' restores strictly "
    "sequential execution (the correctness oracle).", True)

PIPELINE_PREFETCH_DEPTH = register_conf(
    "spark.rapids.tpu.pipeline.prefetchDepth",
    "Bound of each inter-stage prefetch queue, in batches. 2 double-"
    "buffers every stage boundary; larger values absorb burstier stages "
    "at the cost of more resident batches.", 2,
    checker=lambda v: None if int(v) > 0 else "must be positive")

PIPELINE_TASK_POOL = register_conf(
    "spark.rapids.tpu.pipeline.taskPool",
    "Maximum partitions drained concurrently by the pipelined executor "
    "(the Spark-task-parallelism analogue). Each task holds the "
    "TpuSemaphore for its drain, so CROSS-partition concurrency is "
    "bounded by spark.rapids.sql.concurrentGpuTasks (raise it to overlap "
    "partitions); the decode/upload/compute/download overlap WITHIN a "
    "partition runs on admission-free prefetch workers regardless.", 4,
    checker=lambda v: None if int(v) > 0 else "must be positive")

# process-wide settings snapshot (session-init chokepoint, like
# utils/tracing.configure_tracer: exec nodes have no conf at execute time)
_SETTINGS_LOCK = threading.Lock()
_SETTINGS = {
    "enabled": bool(PIPELINE_ENABLED.default),
    "depth": int(PIPELINE_PREFETCH_DEPTH.default),
    "task_pool": int(PIPELINE_TASK_POOL.default),
}

# live prefetch workers (for the shutdown/no-leak contract); counters feed
# pipeline_stats() and the StatsRegistry
_WORKERS_LOCK = threading.Lock()
_WORKERS: dict = {}            # thread -> cancel Event
_STATS = {"workers_started": 0, "items_queued": 0, "stage_errors": 0,
          "tasks_run": 0}

# live introspection for the health watchdog (utils/health.py): every
# bounded prefetch queue and every in-flight pooled task registers here so
# a stalled engine can report WHICH stage is wedged and for how long, and
# a monotonically increasing progress marker distinguishes "slow" from
# "stuck" (the stall detector compares tokens across ticks).
import itertools as _it

_QUEUE_IDS = _it.count()
_QUEUES: dict = {}             # qid -> {"stage", "queue", "created"}
_INFLIGHT_IDS = _it.count()
_INFLIGHT: dict = {}           # token -> {"stage", "thread", "started"}
_PROGRESS = {"counter": 0, "ts": time.monotonic()}


def note_progress() -> None:
    """Bump the engine-wide progress marker (an operator accounted a
    batch, a batch crossed a stage boundary, or a task finished). The
    stall detector treats an unchanged marker with work in flight as a
    hang candidate.

    Deliberately LOCK-FREE: this runs on the hottest per-batch paths
    (exec/base.py account_batch, every queue hop), and the detector only
    needs "did it move" — a racing increment that loses an update still
    moves the counter, so taking _WORKERS_LOCK here would buy nothing
    but cross-operator contention."""
    _PROGRESS["counter"] += 1
    _PROGRESS["ts"] = time.monotonic()


def pipeline_snapshot() -> dict:
    """Live pipeline state for /status and the watchdog report: per-queue
    stage/depth/bound/age, in-flight pooled tasks with ages, worker count,
    and the progress marker + its age."""
    now = time.monotonic()
    with _WORKERS_LOCK:
        queues = [{"stage": info["stage"],
                   "depth": info["queue"].qsize(),
                   "bound": info["queue"].maxsize,
                   "age_s": round(now - info["created"], 3)}
                  for info in _QUEUES.values()]
        in_flight = [{"stage": e["stage"], "thread": e["thread"],
                      "age_s": round(now - e["started"], 3)}
                     for e in _INFLIGHT.values()]
        return {"queues": queues, "in_flight": in_flight,
                "active_workers": sum(1 for t in _WORKERS if t.is_alive()),
                "stats": dict(_STATS),
                "progress_counter": _PROGRESS["counter"],
                "last_progress_age_s": round(now - _PROGRESS["ts"], 3)}


def configure_pipeline(conf) -> None:
    """Apply spark.rapids.tpu.pipeline.* to the process settings (called
    from TpuSession.__init__; the most recent session wins)."""
    with _SETTINGS_LOCK:
        _SETTINGS["enabled"] = bool(conf.get(PIPELINE_ENABLED))
        _SETTINGS["depth"] = int(conf.get(PIPELINE_PREFETCH_DEPTH))
        _SETTINGS["task_pool"] = int(conf.get(PIPELINE_TASK_POOL))


def pipeline_enabled() -> bool:
    with _SETTINGS_LOCK:
        return _SETTINGS["enabled"]


def prefetch_depth() -> int:
    with _SETTINGS_LOCK:
        return _SETTINGS["depth"]


def task_pool_size() -> int:
    with _SETTINGS_LOCK:
        return _SETTINGS["task_pool"]


def pipeline_stats() -> dict:
    """Process-wide pipeline counters (a StatsRegistry source)."""
    with _WORKERS_LOCK:
        out = dict(_STATS)
        out["active_workers"] = sum(1 for t in _WORKERS if t.is_alive())
    return out


def active_workers() -> int:
    """Live prefetch worker threads (0 after queries drain / shutdown)."""
    with _WORKERS_LOCK:
        return sum(1 for t in _WORKERS if t.is_alive())


def shutdown_workers(timeout_s: float = 5.0) -> int:
    """Cancel and join any straggling prefetch workers (session.close()).

    Workers exit on their own when their iterator drains; this is the
    backstop for consumers abandoned mid-stream. PROCESS-GLOBAL, like the
    tracer and the pipeline settings: closing a session while another
    session's query is mid-collect cancels that query's workers too (its
    consumer receives a 'pipeline stage cancelled' error, never a hang) —
    the runtime assumes one active session per process, matching the
    sticky conf semantics in configure_pipeline. Returns the number of
    workers that were still alive when called."""
    with _WORKERS_LOCK:
        items = [(t, ev) for t, ev in _WORKERS.items() if t.is_alive()]
    for _t, ev in items:
        ev.set()
    deadline = time.monotonic() + timeout_s
    for t, _ev in items:
        t.join(timeout=max(0.0, deadline - time.monotonic()))
    with _WORKERS_LOCK:
        for t in [t for t in _WORKERS if not t.is_alive()]:
            _WORKERS.pop(t, None)
    return len(items)


# ---------------------------------------------------------------------------
# stage-decoupling prefetch queue
# ---------------------------------------------------------------------------
class _Done:
    """Poison pill: producer finished cleanly."""


class _Failure:
    """Poison pill: producer raised. Carries the original exception with
    the originating stage context already attached."""

    __slots__ = ("exc",)

    def __init__(self, exc: BaseException):
        self.exc = exc


def _attach_context(exc: BaseException, stage: str) -> BaseException:
    """Tag an exception with the pipeline stage that raised it without
    changing its type (callers must see the SAME exception)."""
    note = f"raised in pipeline stage {stage!r}"
    add_note = getattr(exc, "add_note", None)
    if add_note is not None:
        try:
            add_note(f"[spark-rapids-tpu] {note}")
        except Exception:
            pass  # srtpu: net-ok(annotating a propagating error is cosmetic; the original exception still raises either way)
    try:
        ctx = getattr(exc, "pipeline_context", ())
        exc.pipeline_context = tuple(ctx) + (stage,)
    except Exception:
        pass  # srtpu: net-ok(exceptions with slots reject new attributes; the note or type is all we get and the error still raises)
    return exc


class _Producer:
    """One bounded producer: ``make_iter()`` runs on a worker thread of its
    own, STARTED BY THE CONSTRUCTOR, and hands its items through a queue of
    ``depth``. ``drain`` yields them on the calling thread; ``cancel`` stops
    the worker and empties the queue. The worker's spans belong to the query
    of the thread that made the producer."""

    def __init__(self, make_iter: Callable[[], Iterator], stage: str,
                 depth: Optional[int] = None):
        from ..utils.tracing import get_tracer
        depth = prefetch_depth() if depth is None else max(1, int(depth))
        self.stage = stage
        self._make_iter = make_iter
        self._q: "queue.Queue" = queue.Queue(maxsize=depth)
        self._cancel = threading.Event()
        self._abandoned = False     # the CONSUMER cancelled: nobody reads
        t = threading.Thread(target=get_tracer().bind_query(self._produce),
                             daemon=True, name=f"tpu-prefetch:{stage}")
        self._qid = next(_QUEUE_IDS)
        with _WORKERS_LOCK:
            _WORKERS[t] = self._cancel
            _STATS["workers_started"] += 1
            _QUEUES[self._qid] = {"stage": stage, "queue": self._q,
                                  "created": time.monotonic()}
            # opportunistic GC of finished workers so the registry stays small
            for dead in [w for w in _WORKERS
                         if not w.is_alive() and w is not t]:
                _WORKERS.pop(dead, None)
        t.start()

    def _put(self, item) -> bool:
        """put that never blocks forever: gives up when cancelled."""
        while not self._cancel.is_set():
            try:
                self._q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _put_final(self, item) -> None:
        """Best-effort sentinel delivery AFTER cancellation: a consumer
        still blocked in get() must never hang just because its producer
        was shut down. A consumer that cancelled the producer itself reads
        no more, and is owed nothing."""
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and not self._abandoned:
            try:
                self._q.put(item, timeout=0.05)
                return
            except queue.Full:
                continue

    def _produce(self) -> None:
        from ..io.file_block import current_input_file
        stage = self.stage
        _WORKER_TLS.exempt = True  # runs under the owning task's admission
        try:
            it = self._make_iter()
            try:
                for item in it:
                    with _WORKERS_LOCK:
                        _STATS["items_queued"] += 1
                    note_progress()
                    # carry the thread-local input-file holder across the
                    # thread hop (io/file_block.py contract)
                    if not self._put((item, current_input_file())):
                        self._put_final(_Failure(_attach_context(
                            RuntimeError("pipeline stage cancelled "
                                         "(shutdown)"), stage)))
                        return
            finally:
                close = getattr(it, "close", None)
                if close is not None:
                    close()
            if not self._put(_Done):
                self._put_final(_Done)
        except BaseException as e:  # noqa: BLE001 — crosses the queue  # srtpu: degrade-ok(the failure is forwarded through the queue and re-raised in the consumer)
            with _WORKERS_LOCK:
                _STATS["stage_errors"] += 1
            if not self._put(_Failure(_attach_context(e, stage))):
                self._put_final(_Failure(_attach_context(e, stage)))

    def _get(self):
        # cooperative deadline: the consumer must not block forever on a
        # producer that wedged after the query's deadline passed — poll
        # with a short timeout only while a deadline is armed (the plain
        # blocking get stays on the hot path otherwise)
        from ..utils.deadline import check_deadline, deadline_active
        if not deadline_active():
            return self._q.get()
        while True:
            check_deadline()
            try:
                return self._q.get(timeout=0.25)
            except queue.Empty:
                continue

    def drain(self, registry=None) -> Iterator:
        """The producer's items in order, on the calling thread; cancels the
        producer when it ends, fails or is closed early."""
        from ..io.file_block import set_input_file
        from ..utils import metrics as M
        from ..utils.tracing import get_tracer
        tracer = get_tracer()
        try:
            while True:
                t0 = time.perf_counter()
                with tracer.span("wait.pipeline", "pipeline",
                                 stage=self.stage):
                    item = self._get()
                if registry is not None:
                    registry.add(M.PIPELINE_WAIT, time.perf_counter() - t0)
                    registry.observe(M.PREFETCH_QUEUE_DEPTH, self._q.qsize())
                if item is _Done:
                    return
                if isinstance(item, _Failure):
                    raise item.exc
                batch, file_info = item
                note_progress()
                set_input_file(*file_info)
                yield batch
        finally:
            self.cancel()

    def cancel(self) -> None:
        with _WORKERS_LOCK:
            _QUEUES.pop(self._qid, None)
        self._abandoned = True
        self._cancel.set()
        # unblock a producer stuck in put()
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass


def prefetched(make_iter: Callable[[], Iterator], *, stage: str,
               depth: Optional[int] = None, registry=None) -> Iterator:
    """Run ``make_iter()`` on a worker thread, handing items through a
    BOUNDED queue; yields them in order on the calling thread.

    Consumer-side blocked time accounts to ``pipelineWait`` and queue
    occupancy to the ``prefetchQueueDepth`` histogram on ``registry``; the
    same wait is a ``pipeline`` trace span so overlapped stages show up in
    the Chrome trace. Early consumer exit (close/throw) cancels the worker
    and drains the queue; a producer exception re-raises here with the
    stage context attached. A generator: the worker starts at the first
    ``next()``."""
    yield from _Producer(make_iter, stage, depth).drain(registry)


def maybe_prefetched(make_iter: Callable[[], Iterator], *, stage: str,
                     registry=None, depth: Optional[int] = None) -> Iterator:
    """``prefetched`` when pipelining is on, else the plain iterator —
    the one switch every stage boundary goes through so
    ``pipeline.enabled=false`` restores strictly sequential execution."""
    if not pipeline_enabled():
        return make_iter()
    return prefetched(make_iter, stage=stage, registry=registry, depth=depth)


class OrderedFanIn:
    """Many producers, one ordered consumer: ``makers[p]()`` each runs on a
    bounded producer of its own (``prefetched``'s worker and queue), ALL
    STARTED TOGETHER at the first ``next()``, and the items are yielded
    producer 0 to its end, then 1, and so on: the order of the plain serial
    chain, whichever producer finishes first. A producer runs at most its
    queue's ``depth`` (plus the item in its hand) ahead of the consumer. The
    first failure the consumer reaches re-raises with the stage context, and
    ending, failing or closing early cancels every producer. With pipelining
    off it IS the serial chain on the calling thread; a single maker is
    ``prefetched``. ``producers`` says how many were started together (1 for
    the serial chain)."""

    def __init__(self, makers: Sequence[Callable[[], Iterator]], *,
                 stage: str, depth: Optional[int] = None, registry=None):
        makers = list(makers)
        serial = not pipeline_enabled()
        self.producers = 1 if serial else len(makers)
        self._it = self._serial(makers) if serial \
            else self._fan_in(makers, stage, depth, registry)

    @staticmethod
    def _serial(makers) -> Iterator:
        for make in makers:
            yield from make()

    @staticmethod
    def _fan_in(makers, stage, depth, registry) -> Iterator:
        started = [_Producer(make, stage, depth) for make in makers]
        try:
            for producer in started:
                yield from producer.drain(registry)
        finally:
            for producer in started:
                producer.cancel()

    def __iter__(self) -> "OrderedFanIn":
        return self

    def __next__(self):
        return next(self._it)

    def close(self) -> None:
        self._it.close()


# ---------------------------------------------------------------------------
# bounded task pool helpers
# ---------------------------------------------------------------------------
T = TypeVar("T")
R = TypeVar("R")


def parallel_map(fn: Callable[[T], R], items: Sequence[T],
                 max_workers: Optional[int] = None,
                 stage: str = "map") -> List[R]:
    """Apply ``fn`` to every item on a bounded pool; results in input
    order. The FIRST exception re-raises (with stage context) after the
    in-flight work settles — no orphaned workers. Falls back to a plain
    loop when pipelining is off, one item, or one worker."""
    items = list(items)
    workers = task_pool_size() if max_workers is None else int(max_workers)
    workers = min(max(1, workers), len(items)) if items else 1
    if not pipeline_enabled() or workers <= 1 or len(items) <= 1:
        return [fn(x) for x in items]
    import concurrent.futures as cf
    from ..utils.tracing import get_tracer
    with _WORKERS_LOCK:
        _STATS["tasks_run"] += len(items)

    @get_tracer().bind_query    # pool threads book spans to the submitter's query
    def run_exempt(x):
        # pool threads run under the submitting task's admission (see
        # semaphore_exempt); pipelined_collect re-opts into admission.
        # Register the task in the in-flight table (watchdog forensics:
        # a wedged task shows its stage + age) and mark progress when it
        # completes — either way — so the stall detector sees liveness.
        token = next(_INFLIGHT_IDS)
        with _WORKERS_LOCK:
            _INFLIGHT[token] = {
                "stage": stage,
                "thread": threading.current_thread().name,
                "started": time.monotonic()}
        try:
            from ..utils.deadline import check_deadline
            check_deadline()  # expired deadline: fail fast, don't start
            with _worker_scope():
                return fn(x)
        finally:
            with _WORKERS_LOCK:
                _INFLIGHT.pop(token, None)
            note_progress()

    with cf.ThreadPoolExecutor(
            max_workers=workers,
            thread_name_prefix=f"tpu-pipeline:{stage}") as pool:
        futs = [pool.submit(run_exempt, x) for x in items]
        try:
            return [f.result() for f in futs]
        except BaseException as e:
            for f in futs:
                f.cancel()
            raise _attach_context(e, stage)


def pipelined_collect(plan, conf=None):
    """Drain every partition of ``plan`` concurrently (bounded by
    ``taskPool``) and concatenate in partition order — the pipelined
    replacement for ``PhysicalPlan.collect``.

    Each task holds the TpuSemaphore while it drives its partition
    (admission control: only ``concurrentGpuTasks`` tasks dispatch device
    work at once; the rest overlap host-side stages). Materializing nodes
    (exchanges, AQE, broadcast builds) serialize internally behind their
    own locks, so whichever task arrives first runs the shared work while
    the others wait — exactly one materialization, same as sequential
    mode."""
    from ..columnar.host import HostTable
    from ..memory.semaphore import get_semaphore
    from ..utils.tracing import get_tracer

    n = plan.num_partitions
    if not pipeline_enabled() or n <= 1:
        return plan.collect()
    sem = get_semaphore(conf)
    tracer = get_tracer()
    # async-first drain (ROADMAP item 1): when the plan root is a
    # DeviceToHostExec and async execution is on, tasks accumulate DEVICE
    # batches — no task ever blocks in to_host, so partition P+1's
    # dispatch overlaps partition P's device execution — and the whole
    # query materializes in ONE bulk device_get after every partition
    # drains (exec/transitions.py download -> device.py to_host_batched).
    from ..columnar.device import async_enabled
    deferred = (async_enabled()
                and hasattr(plan, "device_batches")
                and hasattr(plan, "download"))
    # num_partitions above may have run AQE stage materialization on THIS
    # thread; operators (python-UDF exec) end that work re-holding the
    # semaphore for the "task" to release. This thread's task is done —
    # shed every hold, or the drains below starve while we block in
    # result() (single-permit deadlock)
    sem.release_all()

    def drain(p: int):
        with tracer.span("task", "task", partition=p, pipelined=True), \
                _task_admission():
            if deferred:
                # no iterator to close: device_batches drains eagerly
                with sem.task_scope():
                    return plan.device_batches(p)
            it = plan.execute(p)
            try:
                # task_scope, not held(): operators (python-UDF exec) may
                # end a batch re-holding the semaphore, relying on task
                # completion to release — a pooled thread must shed every
                # hold before its next task
                with sem.task_scope():
                    return list(it)
            finally:
                close = getattr(it, "close", None)
                if close is not None:
                    close()

    try:
        per_part = parallel_map(drain, range(n),
                                max_workers=min(task_pool_size(), n),
                                stage="collect")
    finally:
        sem.release_all()  # holds a failed/partial run left on this thread
    batches = [b for part in per_part for b in part]
    if deferred:
        # one bulk transfer for the whole output drain (the ≤1-device_get
        # pin in tests/test_async_exec.py holds across partitions too)
        batches = plan.download(batches)
    if not batches:
        from ..plan.physical import empty_result_table
        return empty_result_table(plan.schema)
    return HostTable.concat(batches)
