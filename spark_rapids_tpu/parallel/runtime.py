"""Distributed runtime: driver control plane + local-cluster simulation.

Reference mapping:
- ``DriverRuntime``  ~ RapidsDriverPlugin (Plugin.scala:146-178): owns the
  heartbeat manager/failure detector, hands out executor ids, wires the
  shared transport.
- ``LocalCluster``   ~ Spark ``local-cluster[N, cores, mem]`` mode, the
  reference's no-real-cluster distribution test vehicle
  (integration_tests/README.md:66-86): N executor contexts in one process,
  each running its partitions on a worker thread, exchanging shuffle blocks
  through the shared transport. Device work is serialized per chip by each
  executor's TpuSemaphore (SURVEY §7 hard part (d)).

The GSPMD path (one jitted program over a Mesh, collectives over ICI) lives
in shuffle/ici.py + __graft_entry__.dryrun_multichip; this module is the
*task-parallel* path that mirrors the reference's executor model, used when
partitions outnumber chips or when running multi-host without a shared
program.
"""
from __future__ import annotations

import collections
import itertools
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, List, Optional, Tuple

import pyarrow as pa

from ..columnar.host import HostTable
from ..conf import RapidsConf, _positive, register_conf
from ..shuffle.transport import LocalShuffleTransport, ShuffleTransport
from ..utils import faults
from .executor import ExecutorContext, FailureDetector

__all__ = ["DriverRuntime", "LocalCluster", "ProcessCluster",
           "TaskFailedError", "TaskTimeoutError"]

TASK_TIMEOUT = register_conf(
    "spark.rapids.tpu.task.timeout",
    "Default seconds a ProcessCluster task may run before the driver gives "
    "up and raises TaskTimeoutError with worker forensics (last heartbeat "
    "age, pending-queue depth). Per-call override via run_on(timeout_s=...).",
    300.0, checker=_positive("task timeout"))

TASK_MAX_FAILURES = register_conf(
    "spark.rapids.tpu.task.maxFailures",
    "Times a task may be attempted across worker deaths before the driver "
    "fails it with TaskFailedError (the spark.task.maxFailures analogue; "
    "tasks are only re-attempted on worker loss, never on application "
    "errors, which fail fast).",
    4, checker=_positive("max failures"))

TASK_RESPAWN_WORKERS = register_conf(
    "spark.rapids.tpu.task.respawnWorkers",
    "Replace a worker process that died on its own (crash, injected kill, "
    "heartbeat wedge) with a fresh one on the same slot. Deliberate "
    "ProcessCluster.kill() always excludes the slot instead.",
    True)

TASK_MAX_WORKER_RESPAWNS = register_conf(
    "spark.rapids.tpu.task.maxWorkerRespawns",
    "Respawns allowed per worker slot before the slot is excluded from "
    "the cluster (the executor-exclusion analogue).",
    2)

TASK_HEARTBEAT_INTERVAL = register_conf(
    "spark.rapids.tpu.task.heartbeatInterval",
    "Seconds between worker heartbeat records on the result queue.",
    2.0, checker=_positive("heartbeat interval"))

TASK_HEARTBEAT_TIMEOUT = register_conf(
    "spark.rapids.tpu.task.heartbeatTimeout",
    "Seconds of heartbeat silence (measured only while the driver is "
    "actively waiting on a task) before a live-looking worker process is "
    "declared wedged, recycled, and its tasks resubmitted.",
    60.0, checker=_positive("heartbeat timeout"))


class TaskFailedError(RuntimeError):
    """A ProcessCluster task failed terminally: its worker(s) died and the
    task exhausted resubmission, or no live workers remain. Carries the
    forensics the old silent 300s hang threw away."""

    def __init__(self, message: str, *, task_id: Optional[int] = None,
                 worker: Optional[int] = None, attempts: int = 0,
                 history: Tuple[str, ...] = (),
                 fault: Optional[str] = None,
                 last_heartbeat_age_s: Optional[float] = None,
                 pending_tasks: Optional[int] = None,
                 exitcode: Optional[int] = None):
        super().__init__(message)
        self.task_id = task_id
        self.worker = worker
        self.attempts = attempts
        self.history = tuple(history)
        self.fault = fault
        self.last_heartbeat_age_s = last_heartbeat_age_s
        self.pending_tasks = pending_tasks
        self.exitcode = exitcode


class TaskTimeoutError(TaskFailedError):
    """The task.timeout deadline expired while a task was in flight."""


class DriverRuntime:
    """Driver-side control plane."""

    def __init__(self, conf: Optional[RapidsConf] = None,
                 heartbeat_timeout_s: float = 60.0):
        self.conf = conf or RapidsConf()
        self.detector = FailureDetector(heartbeat_timeout_s)
        self._ids = itertools.count()
        self._lock = threading.Lock()
        self.executors: Dict[int, ExecutorContext] = {}

    def register_executor(self, ctx: ExecutorContext) -> int:
        with self._lock:
            self.executors[ctx.executor_id] = ctx
        self.detector.heartbeat(ctx.executor_id)
        return ctx.executor_id

    def next_executor_id(self) -> int:
        return next(self._ids)

    def heartbeat(self, executor_id: int):
        self.detector.heartbeat(executor_id)

    def live_executors(self) -> List[int]:
        self.detector.check()
        return self.detector.live()


class LocalCluster:
    """N executors in-process sharing one transport; partitions of a
    DataFrame run round-robin across executors on worker threads."""

    def __init__(self, n_executors: int, conf: Optional[RapidsConf] = None,
                 device: bool = True):
        self.conf = conf or RapidsConf()
        self.device = device
        self.driver = DriverRuntime(self.conf)
        self.transport: ShuffleTransport = LocalShuffleTransport(self.conf)
        self.executors: List[ExecutorContext] = []
        for _ in range(n_executors):
            eid = self.driver.next_executor_id()
            ctx = ExecutorContext(eid, self.conf, transport=self.transport)
            ctx.initialize()
            self.driver.register_executor(ctx)
            self.executors.append(ctx)
        self._pool = ThreadPoolExecutor(max_workers=n_executors,
                                        thread_name_prefix="srtpu-exec")

    def __enter__(self) -> "LocalCluster":
        return self

    def __exit__(self, *exc):
        self.close()

    def close(self):
        self._pool.shutdown(wait=True)
        for ctx in self.executors:
            ctx.shutdown()
        self.transport.close()

    # -- execution ------------------------------------------------------------
    def run(self, df) -> pa.Table:
        """Execute a DataFrame's physical plan with partitions spread across
        the executors (reference: one Spark task per partition, tasks pinned
        to an executor's GPU via GpuSemaphore)."""
        plan = df.session._physical(df.logical, device=self.device)
        n_parts = plan.num_partitions

        def run_partition(pidx: int) -> List[HostTable]:
            from ..utils.tracing import get_tracer
            ctx = self.executors[pidx % len(self.executors)]
            ctx.heartbeat()
            out: List[HostTable] = []
            with get_tracer().span("task", "task", partition=pidx,
                                   executor=ctx.executor_id):
                if self.device:
                    # the device plan root (DeviceToHostExec) downloads
                    # batches; the chip is held for the whole partition like
                    # a Spark task holds GpuSemaphore
                    with ctx.semaphore.held():
                        out.extend(plan.execute(pidx))
                else:
                    out.extend(plan.execute(pidx))
            return out

        futures = [self._pool.submit(run_partition, p) for p in range(n_parts)]
        tables: List[HostTable] = []
        for f in futures:
            tables.extend(f.result())
        if not tables:
            from ..columnar.host import HostColumn
            from ..plan.physical import _empty_values
            empty = HostTable(plan.schema.names,
                              [HostColumn(f.dtype, _empty_values(f.dtype))
                               for f in plan.schema])
            return empty.to_arrow()
        merged = HostTable.concat(tables)
        return merged.to_arrow()

    def map_executors(self, fn: Callable[[ExecutorContext], object]
                      ) -> List[object]:
        futures = [self._pool.submit(fn, ctx) for ctx in self.executors]
        return [f.result() for f in futures]


# ---------------------------------------------------------------------------
# Multi-process cluster: executors as OS processes over the TCP transport
# (reference: real Spark executors + RapidsShuffleServer/Client crossing
# process/host boundaries; LocalCluster above is the threads-only analogue of
# local-cluster mode)
# ---------------------------------------------------------------------------
def _worker_main(worker_id: int, conf_values: dict, addr_q, task_q, result_q):
    # Workers are pinned to the CPU backend ON PURPOSE: a chip belongs to
    # one process at a time, and that process is the driver. A worker that
    # initialized the TPU backend while the driver holds the chip would
    # fail or hang, so the platform is fixed before anything can touch a
    # device — with it, jax never loads libtpu here
    # (worker_backend_task reports both; tests/test_process_cluster.py).
    import os
    import time

    import jax
    jax.config.update("jax_platforms", "cpu")
    from ..conf import RapidsConf
    from ..shuffle.tcp import TcpShuffleTransport
    from ..utils import faults as wfaults
    from ..utils.tracing import (TRACE_DISTRIBUTED_DIR, TraceContext,
                                 activate_trace_context, configure_tracer,
                                 get_tracer)
    from .executor import ExecutorContext

    conf = RapidsConf(conf_values)
    # per-worker seed offset decorrelates probabilistic chaos streams
    # across workers while keeping every process deterministic
    wfaults.configure_faults(conf, seed_offset=worker_id)
    tracer = configure_tracer(conf)
    tracer.process_name = f"worker-{worker_id}"
    transport = TcpShuffleTransport(conf)
    addr_q.put((worker_id, transport.address))

    # heartbeat publisher: the driver's FailureDetector distinguishes a
    # busy worker from a wedged one only through these records
    hb_stop = threading.Event()
    hb_interval = float(conf.get(TASK_HEARTBEAT_INTERVAL))

    def _heartbeat_loop():
        while not hb_stop.is_set():
            try:
                result_q.put((-1, "hb", (worker_id, time.time())))
            except Exception:  # queue torn down mid-shutdown
                return
            hb_stop.wait(hb_interval)

    threading.Thread(target=_heartbeat_loop, daemon=True,
                     name=f"srtpu-worker-hb-{worker_id}").start()
    ctx = None
    try:
        while True:
            task = task_q.get()
            if task is None:
                return
            tid, kind, payload, ctx_wire = task
            if kind == "peers":
                for host, port in payload:
                    transport.add_peer(host, port)
                ctx = ExecutorContext(worker_id, conf,
                                      transport=transport).initialize()
                result_q.put((tid, "ok", None))
                continue
            if kind == "addpeer":
                # a respawned worker announcing its replacement address;
                # the stale address stays in the peer list and simply
                # fails fast on the next fetch attempt
                host, port = payload
                transport.add_peer(host, port)
                result_q.put((tid, "ok", None))
                continue
            if kind == "clock":
                # clock handshake: the driver brackets this round trip and
                # estimates our wall-clock offset NTP-style from the reply
                result_q.put((tid, "ok",
                              (time.time(), tracer.epoch_unix)))
                continue
            fn, args = payload
            try:
                action = wfaults.fire("worker.task")
                if action == "kill":
                    # simulate abrupt worker loss, but first tell the
                    # driver which fault did it so TaskFailedError can
                    # name it; flush the queue feeder thread before the
                    # no-cleanup exit or the notice can be lost
                    result_q.put((tid, "dying",
                                  "injected fault 'worker.task' "
                                  "(action=kill)"))
                    result_q.close()
                    result_q.join_thread()
                    os._exit(13)
                tctx = TraceContext.from_wire(ctx_wire)
                with activate_trace_context(tctx), \
                        get_tracer().span("task", "task", worker=worker_id,
                                          fn=getattr(fn, "__name__", "?")):
                    if action is not None:
                        raise wfaults.FaultInjectedError("worker.task",
                                                         action)
                    out = fn(ctx, *args)
                result_q.put((tid, "ok", out))
            except Exception as e:  # surface to the driver, keep serving
                result_q.put((tid, "err", f"{type(e).__name__}: {e}"))
    finally:
        hb_stop.set()
        if ctx is not None:
            ctx.shutdown()
        transport.close()
        dump_dir = str(conf.get(TRACE_DISTRIBUTED_DIR))
        if dump_dir and tracer.enabled:
            tracer.dump(os.path.join(
                dump_dir, f"trace-{tracer.process_name}.json"))


class ProcessCluster:
    """N executor processes, each owning a TcpShuffleTransport server, all
    peered with each other. Task functions must be module-level (pickled by
    reference) and take the worker's ExecutorContext as first argument.

    Every task envelope carries the submitting thread's TraceContext
    (``spark.rapids.tpu.trace.distributed.enabled``), so worker-side spans
    parent under the driver's query span; a per-worker clock handshake at
    startup estimates each worker's wall-clock offset for the merged
    timeline (tools/trace.py)."""

    def __init__(self, n_executors: int, conf: Optional[dict] = None,
                 start_timeout_s: float = 120.0):
        import multiprocessing as mp

        from ..utils.tracing import TRACE_CLOCK_PROBES, TRACE_DISTRIBUTED
        self._mp = mp.get_context("spawn")
        self._addr_q = self._mp.Queue()
        # one result queue a worker, never one for all: a queue's write
        # lock is shared by its writers and dies with a holder. A worker
        # terminated right after the driver read its answer still holds it
        # four times in ten on a loaded box (the feeder thread releases it
        # after the bytes are out), and every other worker's heartbeats
        # and answers would then block behind it for good
        self._result_qs = [self._mp.Queue() for _ in range(n_executors)]
        self._results: collections.deque = collections.deque()
        self._task_qs = [self._mp.Queue() for _ in range(n_executors)]
        self._conf_values = dict(conf or {})
        rconf = RapidsConf(self._conf_values)
        self._propagate = bool(rconf.get(TRACE_DISTRIBUTED))
        self._clock_probes = int(rconf.get(TRACE_CLOCK_PROBES))
        self._task_timeout = float(rconf.get(TASK_TIMEOUT))
        self._max_failures = int(rconf.get(TASK_MAX_FAILURES))
        self._respawn_enabled = bool(rconf.get(TASK_RESPAWN_WORKERS))
        self._max_respawns = int(rconf.get(TASK_MAX_WORKER_RESPAWNS))
        self._hb_timeout = float(rconf.get(TASK_HEARTBEAT_TIMEOUT))
        self._start_timeout = float(start_timeout_s)
        #: wedge detection over worker heartbeat records (reference:
        #: heartbeat-driven executor exclusion, Plugin.scala:149-161)
        self.detector = FailureDetector(self._hb_timeout)
        self._inflight: Dict[int, dict] = {}
        self._excluded: set = set()
        self._respawns: Dict[int, int] = {}
        self._last_hb: Dict[int, float] = {}
        self._closing = False
        self._recovering = False
        self.procs = [self._spawn_process(i) for i in range(n_executors)]
        for p in self.procs:
            p.start()
        addrs: Dict[int, tuple] = {}
        for _ in range(n_executors):
            wid, addr = self._addr_q.get(timeout=start_timeout_s)
            addrs[wid] = addr
        self.addresses = [addrs[i] for i in range(n_executors)]
        self._tids = itertools.count()
        self._done: Dict[int, tuple] = {}
        # peer everyone with everyone else
        for i in range(n_executors):
            peers = [a for j, a in enumerate(self.addresses) if j != i]
            self._wait(self._submit(i, "peers", peers))
        #: worker id -> estimated (worker_wall - driver_wall) seconds
        self.clock_offsets: Dict[int, float] = {
            i: self._estimate_clock_offset(i) for i in range(n_executors)}
        #: worker id -> the worker tracer's epoch_unix (merge anchor)
        self.worker_epochs: Dict[int, float] = dict(self._epochs)

    def _spawn_process(self, worker: int):
        return self._mp.Process(
            target=_worker_main,
            args=(worker, self._conf_values, self._addr_q,
                  self._task_qs[worker], self._result_qs[worker]),
            daemon=True)

    def _next_result(self, timeout_s: float):
        """The next record any live worker put on its result queue;
        ``queue.Empty`` when none comes within ``timeout_s``. The queue of
        an excluded worker is never read again."""
        import queue as _queue
        from multiprocessing.connection import wait
        if not self._results:
            readers = {q._reader: q for i, q in enumerate(self._result_qs)
                       if i not in self._excluded}
            for r in wait(list(readers), timeout_s):
                self._results.append(readers[r].get())
        if not self._results:
            raise _queue.Empty
        return self._results.popleft()

    def live_workers(self) -> List[int]:
        return [i for i, p in enumerate(self.procs)
                if i not in self._excluded and p.is_alive()]

    def _estimate_clock_offset(self, worker: int) -> float:
        """NTP-style offset estimate: bracket N clock round trips and keep
        the probe with the smallest RTT — queue latency inflates RTT
        symmetrically, so the tightest bracket bounds the offset best."""
        import time
        best_rtt, offset, epoch = float("inf"), 0.0, 0.0
        for _ in range(max(1, self._clock_probes)):
            t0 = time.time()
            t1, worker_epoch = self._wait(self._submit(worker, "clock", None))
            t2 = time.time()
            rtt = t2 - t0
            if rtt < best_rtt:
                best_rtt = rtt
                offset = t1 - (t0 + t2) / 2.0
                epoch = worker_epoch
        if not hasattr(self, "_epochs"):
            self._epochs: Dict[int, float] = {}
        self._epochs[worker] = epoch
        return offset

    def _submit(self, worker: int, kind: str, payload) -> int:
        from ..utils.tracing import current_trace_context
        tid = next(self._tids)
        ctx = current_trace_context() if self._propagate else None
        wire = None if ctx is None else ctx.to_wire()
        self._inflight[tid] = {"worker": worker, "kind": kind,
                               "payload": payload, "wire": wire,
                               "attempts": 1, "history": [], "fault": None}
        self._task_qs[worker].put((tid, kind, payload, wire))
        return tid

    def submit(self, worker: int, fn, *args) -> int:
        """Run ``fn(ctx, *args)`` on a worker; returns a task id."""
        return self._submit(worker, "call", (fn, args))

    def _wait(self, tid: int, timeout_s: Optional[float] = None):
        import queue as _queue
        import time
        budget = self._task_timeout if timeout_s is None else float(timeout_s)
        deadline = time.monotonic() + budget
        # baseline the detector: wedge detection measures heartbeat
        # silence during THIS wait — nobody drains the result queue while
        # the driver is idle, so stale stamps would be false positives
        for w in self.live_workers():
            self.detector.heartbeat(w)
        while tid not in self._done:
            try:
                got_tid, status, value = self._next_result(0.2)
            except _queue.Empty:
                self._check_workers()
                if time.monotonic() >= deadline:
                    self._raise_timeout(tid, budget)
                continue
            if status == "hb":
                wid, _ts = value
                self.detector.heartbeat(wid)
                self._last_hb[wid] = time.monotonic()
                continue
            if status == "dying":
                # a worker's last words before an injected kill: remember
                # the fault name for the task's forensics
                rec = self._inflight.get(got_tid)
                if rec is not None:
                    rec["fault"] = value
                continue
            if got_tid not in self._inflight:
                # stale duplicate: the task was already resubmitted after
                # its first worker died mid-answer, or already failed
                continue
            self._inflight.pop(got_tid, None)
            self._done[got_tid] = (status, value)
        status, value = self._done.pop(tid)
        if status == "failed":
            raise value
        if status == "err":
            raise RuntimeError(f"task {tid} failed on worker: {value}")
        return value

    def _raise_timeout(self, tid: int, budget: float):
        import time
        rec = self._inflight.pop(tid, None)
        faults.note_recovery("task_timeouts")
        worker = rec["worker"] if rec else None
        hb_age = None
        depth = None
        if worker is not None:
            last = self._last_hb.get(worker)
            hb_age = None if last is None else time.monotonic() - last
            try:
                depth = self._task_qs[worker].qsize()
            except (NotImplementedError, OSError):
                depth = None
        age_txt = "never seen" if hb_age is None else f"{hb_age:.1f}s ago"
        depth_txt = "?" if depth is None else str(depth)
        raise TaskTimeoutError(
            f"task {tid} timed out after {budget:.1f}s on worker {worker} "
            f"(last heartbeat {age_txt}, ~{depth_txt} pending tasks); "
            f"raise spark.rapids.tpu.task.timeout if the task is legitimately "
            f"slow",
            task_id=tid, worker=worker,
            attempts=rec["attempts"] if rec else 0,
            history=tuple(rec["history"]) if rec else (),
            fault=rec.get("fault") if rec else None,
            last_heartbeat_age_s=hb_age, pending_tasks=depth)

    # -- worker supervision ---------------------------------------------------
    def _check_workers(self):
        """Detect dead or wedged workers and run recovery. Called from
        inside _wait's poll loop; re-entrancy (recovery itself waits on
        control tasks) is cut off with the _recovering latch."""
        if self._closing or self._recovering:
            return
        self._recovering = True
        try:
            for i, p in enumerate(self.procs):
                if i in self._excluded:
                    continue
                if not p.is_alive():
                    self._on_worker_death(
                        i, f"worker {i} process exited "
                           f"(exitcode={p.exitcode})")
            for wid in self.detector.check():
                if wid in self._excluded or wid >= len(self.procs):
                    continue
                p = self.procs[wid]
                if p.is_alive():
                    # alive but silent past heartbeatTimeout: wedged
                    p.terminate()
                    p.join(timeout=10)
                    self._on_worker_death(
                        wid, f"worker {wid} wedged (no heartbeat for "
                             f"{self._hb_timeout:.0f}s)")
        finally:
            self._recovering = False

    def _on_worker_death(self, worker: int, reason: str,
                         allow_respawn: bool = True):
        faults.note_recovery("worker_deaths")
        orphans = [t for t, r in self._inflight.items()
                   if r["worker"] == worker]
        respawned = False
        if (allow_respawn and self._respawn_enabled and not self._closing
                and self._respawns.get(worker, 0) < self._max_respawns):
            try:
                self._respawn_worker(worker)
                respawned = True
                faults.note_recovery("worker_respawns")
            except Exception:
                respawned = False
        if not respawned:
            self._excluded.add(worker)
            faults.note_recovery("worker_exclusions")
        for t in orphans:
            self._resubmit_or_fail(t, reason)

    def _respawn_worker(self, worker: int):
        """Replace a dead worker with a fresh process on the same slot:
        fresh task and result queues (the old ones may hold stale
        envelopes, or a write lock the dead process took with it), new
        transport address announced to every surviving peer, clock offset
        re-estimated."""
        self._respawns[worker] = self._respawns.get(worker, 0) + 1
        old_q = self._task_qs[worker]
        self._task_qs[worker] = self._mp.Queue()
        self._result_qs[worker] = self._mp.Queue()
        p = self._spawn_process(worker)
        self.procs[worker] = p
        p.start()
        while True:
            wid, addr = self._addr_q.get(timeout=self._start_timeout)
            if wid == worker:
                break
        self.addresses[worker] = addr
        peers = [a for j, a in enumerate(self.addresses)
                 if j != worker and j not in self._excluded
                 and self.procs[j].is_alive()]
        self._wait(self._submit(worker, "peers", peers),
                   timeout_s=self._start_timeout)
        for j in self.live_workers():
            if j != worker:
                self._wait(self._submit(j, "addpeer", addr),
                           timeout_s=self._start_timeout)
        self.clock_offsets[worker] = self._estimate_clock_offset(worker)
        self.worker_epochs[worker] = self._epochs[worker]
        old_q.close()

    def _resubmit_or_fail(self, tid: int, reason: str):
        """Bounded task re-attempt after worker loss. Control tasks and
        exhausted tasks become terminal TaskFailedError results that the
        owning _wait raises."""
        rec = self._inflight.get(tid)
        if rec is None:
            return
        rec["history"].append(reason)
        live = self.live_workers()
        terminal = None
        if rec["kind"] != "call":
            terminal = "control task cannot be resubmitted"
        elif rec["attempts"] >= self._max_failures:
            terminal = (f"exhausted spark.rapids.tpu.task.maxFailures="
                        f"{self._max_failures}")
        elif not live:
            terminal = "no live workers remain"
        if terminal is not None:
            self._inflight.pop(tid, None)
            faults.note_recovery("task_failures")
            fault = rec.get("fault")
            msg = (f"task {tid} failed after {rec['attempts']} attempt(s): "
                   f"{terminal}; failures: {'; '.join(rec['history'])}")
            if fault:
                msg += f"; fault: {fault}"
            self._done[tid] = ("failed", TaskFailedError(
                msg, task_id=tid, worker=rec["worker"],
                attempts=rec["attempts"], history=tuple(rec["history"]),
                fault=fault))
            return
        rec["attempts"] += 1
        target = live[rec["attempts"] % len(live)]
        rec["worker"] = target
        faults.note_recovery("task_resubmissions")
        self._task_qs[target].put((tid, rec["kind"], rec["payload"], rec["wire"]))  # srtpu: trace-ok(resubmission replays the original envelope whose context was captured at _submit)

    def run_on(self, worker: int, fn, *args,
               timeout_s: Optional[float] = None):
        return self._wait(self.submit(worker, fn, *args), timeout_s)

    def run_tpch_query(self, query: str, sf: float = 0.01,
                       tiny: bool = True, num_partitions: int = 4,
                       timeout_s: Optional[float] = None) -> pa.Table:
        """Fan the partitions of one TPC-H query across the live workers
        and merge the results — the chaos-parity vehicle: a mid-query
        worker kill must yield exactly the sequential answer via
        supervision + resubmission."""
        from ..shuffle.serializer import deserialize_table
        live = self.live_workers()
        if not live:
            raise TaskFailedError("no live workers to plan the query on")
        n_parts = self.run_on(live[0], query_num_partitions_task, query,
                              sf, tiny, num_partitions, self._conf_values,
                              timeout_s=timeout_s)
        tids = []
        for pidx in range(n_parts):
            live = self.live_workers()
            if not live:
                raise TaskFailedError(
                    f"no live workers remain for partition {pidx}")
            w = live[pidx % len(live)]
            tids.append(self.submit(w, run_query_task, query, sf, tiny,
                                    num_partitions, pidx,
                                    self._conf_values))
        parts: List[HostTable] = []
        for tid in tids:
            payload = self._wait(tid, timeout_s)
            if payload is not None:
                parts.append(deserialize_table(payload))
        if not parts:
            return pa.table({})
        return HostTable.concat(parts).to_arrow()

    # -- distributed trace collection -----------------------------------------
    def collect_traces(self, drain: bool = False) -> List[dict]:
        """One Chrome-trace dict per process (driver first, then every
        live worker), each annotated with its clock-offset estimate —
        the input set for tools/trace.py merge_process_traces. With
        ``drain`` the worker rings are flushed (snapshot-and-reset), so
        per-query collection attributes ring drops to the right query."""
        from ..utils.tracing import get_tracer
        tracer = get_tracer()
        driver = tracer.drain() if drain else tracer.to_chrome_trace()
        driver["otherData"]["process_name"] = tracer.process_name
        driver["otherData"]["clock_offset_s"] = 0.0
        driver["otherData"]["role"] = "driver"
        traces = [driver]
        for w, p in enumerate(self.procs):
            if not p.is_alive():
                continue
            t = self.run_on(w, trace_flush_task, drain)
            t["otherData"]["clock_offset_s"] = self.clock_offsets.get(w, 0.0)
            t["otherData"]["role"] = f"worker-{w}"
            traces.append(t)
        return traces

    def dump_traces(self, directory: str, drain: bool = False) -> List[str]:
        """Write one trace-<process_name>.json per process into
        ``directory`` (the file set ``python -m spark_rapids_tpu.tools.trace
        merge <directory>`` consumes); returns the paths."""
        import json
        import os
        os.makedirs(directory, exist_ok=True)
        paths = []
        for t in self.collect_traces(drain=drain):
            name = t["otherData"].get("process_name", "unknown")
            path = os.path.join(directory, f"trace-{name}.json")
            with open(path, "w", encoding="utf-8") as f:
                json.dump(t, f)
            paths.append(path)
        return paths

    def kill(self, worker: int):
        """Hard-kill one executor process (deliberate failure injection).
        The slot is excluded — never respawned — and any of its in-flight
        tasks are resubmitted to surviving workers."""
        self.procs[worker].terminate()
        self.procs[worker].join(timeout=30)
        self._on_worker_death(worker, f"worker {worker} killed by driver",
                              allow_respawn=False)

    def close(self):
        self._closing = True
        for i, p in enumerate(self.procs):
            if p.is_alive():
                try:
                    self._task_qs[i].put(None)  # srtpu: trace-ok(shutdown sentinel, not a task envelope — no context to inject)
                except Exception:
                    pass  # srtpu: net-ok(a full queue or dead worker during shutdown is fine; terminate below is the backstop)
        for p in self.procs:
            p.join(timeout=30)
            if p.is_alive():
                p.terminate()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


# -- reusable cross-process task functions (module-level => picklable) -------
def trace_flush_task(ctx: ExecutorContext, drain: bool = False) -> dict:
    """Export this worker's tracer ring as a Chrome-trace dict (with the
    process identity + wall-clock anchor in otherData). ``drain`` resets
    the ring so the NEXT flush starts clean — per-process drop counts then
    attribute to the window that overflowed."""
    from ..utils.tracing import get_tracer
    tracer = get_tracer()
    return tracer.drain() if drain else tracer.to_chrome_trace()


def metrics_text_task(ctx: ExecutorContext) -> str:
    """This worker's StatsRegistry as Prometheus text — the scrape body
    the driver's MetricsFederation (tools/statusd.py) pulls through the
    task queue (workers run no HTTP server; the queue IS the scrape
    transport)."""
    from ..utils.metrics import get_stats
    return get_stats().prometheus_text()


def trace_probe_task(ctx: ExecutorContext, depth: int = 0) -> Optional[dict]:
    """Record one probe span and report the TraceContext active inside it
    — the round-trip test for envelope propagation (None when no context
    arrived)."""
    from ..utils.tracing import current_trace_context, get_tracer
    with get_tracer().span("trace_probe", "task", depth=depth):
        ctx_now = current_trace_context()
        return None if ctx_now is None else ctx_now.to_wire()


def worker_backend_task(ctx: ExecutorContext) -> dict:
    """What this worker process runs on: its jax backend, and whether the
    TPU runtime library is mapped into it (it must not be — the driver
    process alone owns the chip)."""
    import jax
    with open("/proc/self/maps") as f:
        maps = f.read()
    return {"backend": jax.default_backend(),
            "libtpu_loaded": "libtpu" in maps}


def shuffle_write_task(ctx: ExecutorContext, shuffle_id: int, map_id: int,
                       payload: bytes, key_names: List[str],
                       num_parts: int) -> List[int]:
    from ..columnar.device import DeviceTable
    from ..shuffle.serializer import deserialize_table
    # srtpu: bucket-ok(cross-process wire protocol: payloads re-bucket at the tiny fixed floor so worker shard shapes never depend on the driver's session ladder)
    table = DeviceTable.from_host(deserialize_table(payload), min_bucket=8)
    return ctx.shuffle.write_partition(shuffle_id, map_id, iter([table]),
                                       key_names, num_parts)


def dcn_address_task(ctx: ExecutorContext) -> tuple:
    """Start (if needed) the worker's DCN-tier transport; -> (host, port)."""
    return ctx.dcn_transport().address


def dcn_add_peer_task(ctx: ExecutorContext, host: str, port: int) -> None:
    ctx.dcn_transport().add_peer(host, port)


def dcn_publish_task(ctx: ExecutorContext, shuffle_id: int, map_id: int,
                     reduce_id: int, payload: bytes) -> int:
    """Upload the payload table and publish it DEVICE-RESIDENT on this
    worker's DCN transport (serialization to the wire is lazy)."""
    from ..columnar.device import DeviceTable
    from ..shuffle.serializer import deserialize_table
    from ..shuffle.transport import BlockId
    # srtpu: bucket-ok(cross-process wire protocol: fixed floor keeps published block shapes driver-independent)
    table = DeviceTable.from_host(deserialize_table(payload), min_bucket=8)
    ctx.dcn_transport().publish_table(
        BlockId(shuffle_id, map_id, reduce_id), table)
    return int(table.num_rows)  # srtpu: sync-ok(cross-process DCN publish requires host bytes)


def dcn_fetch_task(ctx: ExecutorContext, shuffle_id: int, map_id: int,
                   reduce_id: int) -> bytes:
    """Fetch one block over the DCN tier; returns its serialized rows (for
    test verification — the table itself lands device-resident)."""
    from ..shuffle.serializer import serialize_table
    from ..shuffle.transport import BlockId
    blocks = dict(ctx.dcn_transport().fetch_tables(
        [BlockId(shuffle_id, map_id, reduce_id)]))
    table = blocks[BlockId(shuffle_id, map_id, reduce_id)]
    return serialize_table(table.to_host())


def shuffle_read_task(ctx: ExecutorContext, shuffle_id: int, num_maps: int,
                      reduce_id: int) -> Optional[bytes]:
    from ..shuffle.serializer import serialize_table
    # srtpu: bucket-ok(cross-process wire protocol: result is serialized back to exact rows, bucket only pads transient upload)
    out = list(ctx.shuffle.read_partition(shuffle_id, num_maps, reduce_id,
                                          min_bucket=8))
    if not out:
        return None
    return serialize_table(out[0].to_host())


def shuffle_read_recompute_task(ctx: ExecutorContext, shuffle_id: int,
                                num_maps: int, reduce_id: int,
                                map_payloads: Dict[int, bytes],
                                key_names: List[str],
                                num_parts: int) -> Optional[bytes]:
    """Read with a recompute hook: a fetch-failed map task is re-run locally
    from its input (the lineage-recompute analogue of Spark stage retry)."""
    def recompute(map_id: int):
        shuffle_write_task(ctx, shuffle_id, map_id, map_payloads[map_id],
                           key_names, num_parts)

    from ..shuffle.serializer import serialize_table
    # srtpu: bucket-ok(cross-process wire protocol: result is serialized back to exact rows, bucket only pads transient upload)
    out = list(ctx.shuffle.read_partition(shuffle_id, num_maps, reduce_id,
                                          min_bucket=8, recompute=recompute))
    if not out:
        return None
    return serialize_table(out[0].to_host())


def broadcast_build_task(ctx: ExecutorContext, bcast_id: int,
                         payload: bytes) -> Tuple[int, int]:
    """Designated-builder side of a cross-process broadcast (reference:
    the driver-side relationFuture, GpuBroadcastExchangeExec.scala:336)."""
    from ..columnar.device import DeviceTable
    from ..shuffle.serializer import deserialize_table

    def build():
        # srtpu: bucket-ok(cross-process wire protocol: broadcast build shape must match across workers regardless of session ladder)
        return DeviceTable.from_host(deserialize_table(payload),
                                     min_bucket=8)
    ctx.broadcast.build_and_publish(bcast_id, build)
    return ctx.broadcast.builds, ctx.broadcast.fetches


#: (query, sf, tiny, partitions, conf) -> (TpuSession, physical plan);
#: per-worker plan cache so every partition task reuses one build
_QUERY_PLANS: Dict[tuple, tuple] = {}


def _query_plan(query: str, sf: float, tiny: bool, num_partitions: int,
                conf_overrides: Optional[dict]):
    from ..session import TpuSession
    from ..tools import tpch
    key = (query, sf, tiny, num_partitions,
           tuple(sorted((conf_overrides or {}).items())))
    cached = _QUERY_PLANS.get(key)
    if cached is None:
        # a worker-side TpuSession re-runs configure_faults with the
        # plain conf seed — preserve this worker's seed-offset injector
        prev_injector = faults.active()
        sess = TpuSession(dict(conf_overrides or {}))
        faults.install(prev_injector)
        tables = tpch.gen_all(sf, tiny=tiny)
        dfs = tpch.build_dataframes(sess, tables,
                                    num_partitions=num_partitions)
        df = tpch.QUERIES[query](dfs)
        cached = (sess, sess._physical(df.logical, device=False))
        _QUERY_PLANS[key] = cached
    return cached


def query_num_partitions_task(ctx: ExecutorContext, query: str, sf: float,
                              tiny: bool, num_partitions: int,
                              conf_overrides: Optional[dict] = None) -> int:
    """Build (and cache) the query plan worker-side; -> its output
    partition count, which the driver fans run_query_task over."""
    _sess, plan = _query_plan(query, sf, tiny, num_partitions,
                              conf_overrides)
    return int(plan.num_partitions)


def run_query_task(ctx: ExecutorContext, query: str, sf: float, tiny: bool,
                   num_partitions: int, pidx: int,
                   conf_overrides: Optional[dict] = None
                   ) -> Optional[bytes]:
    """Execute one output partition of a TPC-H query inside the worker.
    Every worker regenerates the seeded TPC-H tables and materializes its
    own exchanges — duplicated work, but each partition's rows are exactly
    the sequential run's, which is what the chaos-parity tests pin."""
    from ..shuffle.serializer import serialize_table
    _sess, plan = _query_plan(query, sf, tiny, num_partitions,
                              conf_overrides)
    out = list(plan.execute(pidx))
    if not out:
        return None
    return serialize_table(HostTable.concat(out))


def broadcast_probe_task(ctx: ExecutorContext, bcast_id: int,
                         probe_payload: bytes, key: str
                         ) -> Tuple[bytes, int, int]:
    """Probe side: re-materialize the broadcast build table from the
    transport (never re-executing the build) and hash-join the local probe
    partition against it on ``key``."""
    import numpy as np

    from ..shuffle.serializer import deserialize_table, serialize_table
    build = ctx.broadcast.get(bcast_id).to_host()
    probe = deserialize_table(probe_payload)
    bk = np.sort(build.column(key).values)
    pk = probe.column(key).values
    if len(bk):
        pos = np.clip(np.searchsorted(bk, pk), 0, len(bk) - 1)
        hit = bk[pos] == pk
    else:
        hit = np.zeros(len(pk), dtype=bool)
    joined = probe.take(np.nonzero(hit)[0])
    return (serialize_table(joined), ctx.broadcast.builds,
            ctx.broadcast.fetches)
