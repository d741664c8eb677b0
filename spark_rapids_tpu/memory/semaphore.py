"""TpuSemaphore — per-chip task admission control (reference:
GpuSemaphore.scala:27,58,74 + spark.rapids.sql.concurrentGpuTasks).

On GPU, over-admission causes OOM; on TPU it is worse — a chip runs one
program at a time, so concurrent dispatch only adds queueing (SURVEY §7 hard
part (d): the semaphore is mandatory, not advisory). Tasks acquire before
their first device dispatch and release when blocked on host work (the
python-worker pattern, GpuArrowEvalPythonExec.scala:306-332) or done.

Every permit hold is attributed: the holder's thread name and acquire
timestamp are recorded per task, final releases feed a held-duration
histogram, and ``dump()`` snapshots holders + the wait queue — the health
watchdog's stall forensics (utils/health.py) name the stuck thread instead
of reporting an anonymous missing permit.
"""
from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import Dict, Optional, Tuple

from ..conf import RapidsConf
from ..utils.metrics import Histogram
from .retry import oom_admission_gate

__all__ = ["TpuSemaphore", "get_semaphore", "peek_semaphore"]


class _Hold:
    """One task's live permit hold (reentrant depth + attribution)."""

    __slots__ = ("depth", "thread_name", "thread_id", "acquired_at")

    def __init__(self, thread_name: str, thread_id: int, acquired_at: float):
        self.depth = 1
        self.thread_name = thread_name
        self.thread_id = thread_id
        self.acquired_at = acquired_at


class TpuSemaphore:
    def __init__(self, permits: int = 1):
        self.permits = permits
        self._sem = threading.BoundedSemaphore(permits)
        self._holders: Dict[int, _Hold] = {}  # task/thread id -> hold
        self._waiters: Dict[int, Tuple[str, float]] = {}  # id -> (name, t0)
        self._lock = threading.Lock()
        self.total_wait_time = 0.0
        self.acquire_count = 0
        #: distribution of full-hold durations (acquire -> final release);
        #: a fat tail here is the first hint of a permit-hogging operator
        self.held_histogram = Histogram("semaphoreHeldSeconds")

    def acquire_if_necessary(self, task_id: Optional[int] = None):
        """Reentrant per task (reference: acquireIfNecessary semantics).

        Pipeline worker threads are exempt: they run under their owning
        task's admission, and a worker blocking on the permit its task
        holds (while the task waits on the worker's queue) would deadlock
        at concurrentGpuTasks=1 (parallel/pipeline.py semaphore_exempt)."""
        from ..parallel.pipeline import semaphore_exempt
        if semaphore_exempt():
            return
        tid = task_id if task_id is not None else threading.get_ident()
        with self._lock:
            hold = self._holders.get(tid)
            if hold is not None:
                hold.depth += 1
                return
        # HBM pressure arbitration (memory/retry.py): while a thread is
        # retrying after device OOM, NEW admissions park here so the
        # retrier's final attempts get the chip's HBM to themselves.
        # One module-global check when no retrier is engaged.
        oom_admission_gate()
        from ..utils.tracing import get_tracer
        thread = threading.current_thread()
        t0 = time.perf_counter()
        with self._lock:
            self._waiters[tid] = (thread.name, time.monotonic())
        try:
            with get_tracer().span("wait.semaphore", "semaphore", task=tid):
                self._sem.acquire()
        finally:
            with self._lock:
                self._waiters.pop(tid, None)
        with self._lock:
            self.total_wait_time += time.perf_counter() - t0
            self.acquire_count += 1
            self._holders[tid] = _Hold(thread.name, thread.ident or 0,
                                       time.monotonic())

    def release_if_held(self, task_id: Optional[int] = None):
        # symmetric with acquire_if_necessary: inside an exempt scope a
        # release/reacquire pair (python-UDF exec) must not really drop
        # the owning task's permit — the reacquire would no-op and the
        # task would finish its drain unadmitted
        from ..parallel.pipeline import semaphore_exempt
        if semaphore_exempt():
            return
        tid = task_id if task_id is not None else threading.get_ident()
        with self._lock:
            hold = self._holders.get(tid)
            if hold is None:
                return
            if hold.depth > 1:
                hold.depth -= 1
                return
            del self._holders[tid]
            held_s = time.monotonic() - hold.acquired_at
        self.held_histogram.observe(held_s)
        self._sem.release()

    def release_all(self, task_id: Optional[int] = None):
        """Task-completion release: drop EVERY hold this task accumulated
        (reference: GpuSemaphore's task-completion listener releases the
        whole hold, GpuSemaphore.scala). Operators like the python-UDF
        exec legitimately end a batch with acquire_if_necessary and rely
        on task end to release; a pooled task thread must not carry that
        hold into the next task — the permit would leak forever."""
        tid = task_id if task_id is not None else threading.get_ident()
        with self._lock:
            hold = self._holders.pop(tid, None)
        if hold is not None:
            self.held_histogram.observe(time.monotonic() - hold.acquired_at)
            self._sem.release()

    @contextmanager
    def held(self, task_id: Optional[int] = None):
        self.acquire_if_necessary(task_id)
        try:
            yield
        finally:
            self.release_if_held(task_id)

    @contextmanager
    def task_scope(self, task_id: Optional[int] = None):
        """One task's admission window: acquire on entry, release ALL
        holds on exit (see release_all)."""
        self.acquire_if_necessary(task_id)
        try:
            yield
        finally:
            self.release_all(task_id)

    # -- introspection (health watchdog / stats registry) ---------------------
    def holder_count(self) -> int:
        with self._lock:
            return len(self._holders)

    def waiter_count(self) -> int:
        with self._lock:
            return len(self._waiters)

    def dump(self) -> Dict:
        """Live admission state: per-holder thread name/depth/held-duration
        and the wait queue — the watchdog report's semaphore section."""
        now = time.monotonic()
        with self._lock:
            holders = [{"task_id": tid, "thread": h.thread_name,
                        "thread_id": h.thread_id, "depth": h.depth,
                        "held_s": round(now - h.acquired_at, 3)}
                       for tid, h in self._holders.items()]
            waiters = [{"task_id": tid, "thread": name,
                        "waiting_s": round(now - since, 3)}
                       for tid, (name, since) in self._waiters.items()]
            out = {"permits": self.permits,
                   "available": max(0, self.permits - len(holders)),
                   "holders": holders, "waiters": waiters,
                   "total_wait_s": round(self.total_wait_time, 6),
                   "acquires": self.acquire_count}
        out["held_seconds"] = self.held_histogram.snapshot()
        return out


_GLOBAL: Optional[TpuSemaphore] = None
_LOCK = threading.Lock()


def get_semaphore(conf: Optional[RapidsConf] = None) -> TpuSemaphore:
    global _GLOBAL
    with _LOCK:
        if _GLOBAL is None:
            permits = (conf or RapidsConf()).concurrent_tpu_tasks
            _GLOBAL = TpuSemaphore(permits)
        return _GLOBAL


def peek_semaphore() -> Optional[TpuSemaphore]:
    """The global semaphore if one exists — never creates one (stats
    sources must not conjure a default-permit semaphore)."""
    with _LOCK:
        return _GLOBAL
