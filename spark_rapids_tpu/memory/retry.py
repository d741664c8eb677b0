"""Structured OOM retry: retry scopes, split-and-retry, HBM arbitration.

The reference engine routes every device allocation failure through
``DeviceMemoryEventHandler`` (spill → retry) and gives operators
split-and-retry semantics (``RmmRapidsRetryIterator``: halve the input
batch on the row axis, run the halves sequentially, stitch the results)
so a query degrades gracefully under memory pressure instead of dying.
This module is that ladder for the TPU runtime — one framework that
every device-work site runs under:

**Retry scopes.** ``with_retry(fn, *args)`` wraps a device-invoking
callable with classify → spill → retry; ``with_retry_split(fn, batch,
splitter=...)`` adds the split rung: when retries are exhausted and the
operator declared a splitter, the input batch is halved on the row
axis, the halves execute sequentially (recursively retryable) and the
results are recombined. Both bound their rungs with
``spark.rapids.tpu.oom.maxRetries`` / ``oom.maxSplits`` and terminate
in a structured :class:`DeviceOomError` carrying attempts, splits,
spilled bytes and the memprof postmortem path.

**Classification.** ``is_retryable_oom()`` is the single process-wide
OOM classifier (moved out of utils/compile_cache.py): runtime
``RESOURCE_EXHAUSTED`` strings, allocator "out of memory" variants and
the strict-pool "cannot fit" MemoryError all count; a
:class:`DeviceOomError` from a nested (jit-level) ladder counts too, so
an operator-level scope can catch the inner failure and escalate
straight to splitting.

**HBM pressure arbitration.** On first OOM the retrying thread engages
a process-wide arbiter. While any retrier is engaged, NEW task
admissions through ``TpuSemaphore.acquire_if_necessary`` park on
``oom_admission_gate()`` (one module-global is-None-style check when
idle — the tracer/faults zero-overhead pattern), and the retrier's
final attempts run under an exclusive token that serializes retriers,
so two concurrent pipeline tasks cannot starve each other into a
mutual-OOM livelock: one finishes with the chip's HBM to itself, then
the other.

**Donated inputs.** A failed donating dispatch may already have
consumed its input buffers, so re-calling is unsound. Upload sites
attach a rematerializer to the device table (the retained host-side
origin, exec/transitions.py ``mark_exclusive``); the donating ladder
re-materializes a fresh table from it and retries, and when it gives up
the :class:`DeviceOomError` carries the rematerializer so an enclosing
split scope can resurrect the batch and halve it.
"""
from __future__ import annotations

import functools
import sys
import threading
import time
from contextlib import contextmanager, nullcontext
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from ..conf import register_conf

__all__ = [
    "DeviceOomError",
    "is_retryable_oom",
    "with_retry",
    "with_retry_split",
    "wrap_jit",
    "wrap_jit_donating",
    "split_device_rows",
    "split_host_rows",
    "configure_oom_retry",
    "oom_admission_gate",
    "arbiter_snapshot",
    "retry_stats",
    "drain_oom_retry_records",
    "reset_retry_state",
]


def _non_negative(what: str):
    def check(v):
        return None if v >= 0 else f"{what} must be >= 0, got {v}"
    return check


OOM_MAX_RETRIES = register_conf(
    "spark.rapids.tpu.oom.maxRetries",
    "Maximum spill-and-retry attempts per retry scope before the ladder "
    "escalates to split-and-retry (or fails with a structured "
    "DeviceOomError). 0 disables plain retries.",
    2, checker=_non_negative("oom.maxRetries"))

OOM_MAX_SPLITS = register_conf(
    "spark.rapids.tpu.oom.maxSplits",
    "Maximum row-axis input halvings per retry scope for operators that "
    "declare a splitter (split-and-retry). 0 disables splitting. Each "
    "split halves the failing batch and runs the halves sequentially, "
    "so N splits bound the smallest retried piece at 1/2^N of the "
    "original batch.",
    4, checker=_non_negative("oom.maxSplits"))

OOM_ARBITRATION = register_conf(
    "spark.rapids.tpu.oom.arbitration.enabled",
    "Pause new TpuSemaphore admissions while a thread is retrying after "
    "device OOM and serialize retriers' final attempts, giving the "
    "retrier effectively exclusive HBM (prevents concurrent pipeline "
    "tasks from spilling each other into a mutual-OOM livelock).",
    True)

OOM_GATE_MAX_WAIT = register_conf(
    "spark.rapids.tpu.oom.arbitration.maxWaitSeconds",
    "Upper bound on how long a new admission parks on the OOM "
    "arbitration gate before proceeding anyway (the gate is a pressure "
    "valve, not a correctness lock — a bounded wait can never deadlock "
    "the task pool).",
    30.0, conf_type=float,
    checker=lambda v: None if v > 0 else f"maxWaitSeconds must be > 0, got {v}")

# sticky module config (configure_oom_retry; defaults match the conf
# registrations so bare unit tests get the production ladder)
_MAX_RETRIES = 2
_MAX_SPLITS = 4
_ARBITRATION = True
_GATE_WAIT_S = 30.0


def configure_oom_retry(conf) -> None:
    """Apply spark.rapids.tpu.oom.* (TpuSession chokepoint; sticky, like
    configure_memprof — worker processes inherit via their own session)."""
    global _MAX_RETRIES, _MAX_SPLITS, _ARBITRATION, _GATE_WAIT_S
    _MAX_RETRIES = int(conf.get(OOM_MAX_RETRIES))
    _MAX_SPLITS = int(conf.get(OOM_MAX_SPLITS))
    _ARBITRATION = bool(conf.get(OOM_ARBITRATION))
    _GATE_WAIT_S = float(conf.get(OOM_GATE_MAX_WAIT))


# ---------------------------------------------------------------------------
# classification: the single process-wide device-OOM test
# ---------------------------------------------------------------------------
#: Runtime/allocator substrings that mark an exception as device OOM.
#: "cannot fit" is the strict-pool MemoryError from BufferCatalog.register
#: — without it a pinned-HBM-limit run could never retry.
#: "Failed to allocate" covers the XLA allocator variants surfaced under
#: an INTERNAL status ("INTERNAL: Failed to allocate 123B ...") — those
#: are memory pressure, not engine bugs, and must walk the ladder before
#: ever reaching the host-fallback boundary (exec/fallback.py classifies
#: INTERNAL as non-retryable, so misclassifying here would skip the
#: spill/split rungs entirely).
_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "RESOURCE EXHAUSTED", "Out of memory",
                "out of memory", "OOM", "cannot fit", "Failed to allocate",
                "failed to allocate")


class DeviceOomError(RuntimeError):
    """Device OOM that survived the full escalation ladder. Carries the
    ladder's forensics; the message embeds the catalog's OOM dump so
    operators and tests see live memory state without re-querying."""

    def __init__(self, message: str, *, scope: str = "device",
                 attempts: int = 0, splits: int = 0, spilled_bytes: int = 0,
                 postmortem_path: Optional[str] = None,
                 rematerialize: Optional[Callable[[], Any]] = None):
        super().__init__(message)
        self.scope = scope
        self.attempts = attempts
        self.splits = splits
        self.spilled_bytes = spilled_bytes
        self.postmortem_path = postmortem_path
        #: donated-input resurrection hook: an enclosing split scope can
        #: rebuild the (consumed) batch from its host origin and halve it
        self.rematerialize = rematerialize


def is_retryable_oom(e: BaseException) -> bool:
    """True when ``e`` is a device OOM the ladder can act on. A nested
    ladder's DeviceOomError is retryable at the ENCLOSING scope (the
    outer scope skips plain retries — the inner ladder exhausted them —
    and escalates straight to split)."""
    if isinstance(e, DeviceOomError):
        return True
    if not isinstance(e, (RuntimeError, MemoryError)):
        return False
    msg = str(e)
    return any(m in msg for m in _OOM_MARKERS)


# ---------------------------------------------------------------------------
# HBM pressure arbitration: process-wide OOM state machine
# ---------------------------------------------------------------------------
class _OomArbiter:
    """Cooperates with TpuSemaphore: while >= 1 retrier is engaged, new
    admissions park on :func:`oom_admission_gate` and retriers' final
    attempts serialize on a reentrant exclusive token."""

    def __init__(self):
        self._cond = threading.Condition(threading.Lock())
        self._retriers: Dict[int, int] = {}   # thread ident -> engage depth
        self._token_holder: Optional[int] = None
        self._token_depth = 0

    def engage(self) -> None:
        global _GATE_ACTIVE
        me = threading.get_ident()
        with self._cond:
            self._retriers[me] = self._retriers.get(me, 0) + 1
            _GATE_ACTIVE = True

    def disengage(self) -> None:
        global _GATE_ACTIVE
        me = threading.get_ident()
        with self._cond:
            depth = self._retriers.get(me, 0) - 1
            if depth <= 0:
                self._retriers.pop(me, None)
            else:
                self._retriers[me] = depth
            if not self._retriers:
                _GATE_ACTIVE = False
                self._cond.notify_all()

    def wait_admission(self) -> None:
        """Park the calling (non-retrier) thread until no retrier is
        engaged, bounded by oom.arbitration.maxWaitSeconds."""
        from ..utils.deadline import check_deadline
        me = threading.get_ident()
        deadline = time.monotonic() + _GATE_WAIT_S
        waited = False
        with self._cond:
            if me in self._retriers:
                return  # a retrier must never gate itself (deadlock)
            while self._retriers:
                check_deadline()  # a parked admission must honor the query deadline
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break  # pressure valve, not a correctness lock
                waited = True
                self._cond.wait(min(remaining, 0.25))
        if waited:
            _bump("gate_waits")

    @contextmanager
    def exclusive(self):
        """Reentrant exclusive token serializing retriers' attempts."""
        me = threading.get_ident()
        with self._cond:
            while self._token_holder is not None and self._token_holder != me:
                self._cond.wait(0.25)
            self._token_holder = me
            self._token_depth += 1
        try:
            yield
        finally:
            with self._cond:
                self._token_depth -= 1
                if self._token_depth <= 0:
                    self._token_depth = 0
                    self._token_holder = None
                    self._cond.notify_all()

    def snapshot(self) -> Dict[str, Any]:
        with self._cond:
            return {"active_retriers": len(self._retriers),
                    "gate_active": bool(self._retriers),
                    "token_held": self._token_holder is not None}

    def reset(self) -> None:
        global _GATE_ACTIVE
        with self._cond:
            self._retriers.clear()
            self._token_holder = None
            self._token_depth = 0
            _GATE_ACTIVE = False
            self._cond.notify_all()


_ARBITER = _OomArbiter()

#: Zero-overhead gate flag: False whenever no retrier is engaged, so
#: TpuSemaphore's admission path pays one global load + truthiness check
#: (the tracer/faults/memprof hot-path pattern).
_GATE_ACTIVE = False


def oom_admission_gate() -> None:
    """Called by TpuSemaphore.acquire_if_necessary before a NEW admission
    queues on the permit. No-op unless a retrier is engaged or a query
    deadline is armed (both are one module-global truthiness check)."""
    from ..utils.deadline import check_deadline
    check_deadline()
    if not _GATE_ACTIVE:
        return
    _ARBITER.wait_admission()


def arbiter_snapshot() -> Dict[str, Any]:
    return _ARBITER.snapshot()


# ---------------------------------------------------------------------------
# telemetry: counters (stats registry), drainable records (event log v9)
# ---------------------------------------------------------------------------
_STATS_LOCK = threading.Lock()
_COUNTS: Dict[str, int] = {
    "oom_retries": 0,        # plain spill-and-retry attempts
    "oom_splits": 0,         # row-axis input halvings
    "oom_rematerializations": 0,  # donated inputs rebuilt from host origin
    "oom_recoveries": 0,     # scopes that saw >=1 OOM and still succeeded
    "oom_failures": 0,       # scopes that exhausted the ladder
    "oom_spilled_bytes": 0,  # bytes freed by ladder-triggered spills
    "arbitrations": 0,       # scopes that engaged the arbiter
    "gate_waits": 0,         # admissions that parked on the gate
}
_RECORDS: List[Dict[str, Any]] = []


def _bump(key: str, n: int = 1) -> None:
    with _STATS_LOCK:
        _COUNTS[key] = _COUNTS.get(key, 0) + n


def retry_stats() -> Dict[str, Any]:
    """Stats-registry source (/metrics gauges under the retry_ prefix)."""
    with _STATS_LOCK:
        out: Dict[str, Any] = dict(_COUNTS)
    snap = _ARBITER.snapshot()
    out["active_retriers"] = snap["active_retriers"]
    out["gate_active"] = int(snap["gate_active"])
    return out


def drain_oom_retry_records() -> List[Dict[str, Any]]:
    """Pop completed-ladder records (the event-log writer turns each into
    one schema-v9 ``oom_retry`` record on the owning query)."""
    global _RECORDS
    with _STATS_LOCK:
        out, _RECORDS = _RECORDS, []
    return out


def reset_retry_state() -> None:
    """Test hook: zero counters, drop pending records, reset the arbiter."""
    global _RECORDS
    with _STATS_LOCK:
        for k in list(_COUNTS):
            _COUNTS[k] = 0
        _RECORDS = []
    _ARBITER.reset()


def _memprof_event(kind: str, nbytes: int = 0) -> None:
    try:
        from ..utils import memprof
        mp = memprof.active()
        if mp is not None:
            mp.record(kind, -1, max(int(nbytes), 0))
    except Exception:  # srtpu: degrade-ok(best-effort telemetry inside the ladder itself — nothing structured can originate here)
        pass  # srtpu: net-ok(best-effort telemetry — a memprof failure must never break the OOM recovery path it is narrating)


# ---------------------------------------------------------------------------
# fault chokepoint: alloc.jit / alloc.upload with action=oom
# ---------------------------------------------------------------------------
def _maybe_inject(point: Optional[str]) -> None:
    """Deterministic synthetic OOM inside the retry scope (utils/faults
    ``alloc.jit`` / ``alloc.upload``, ``action=oom``): raises the same
    RESOURCE_EXHAUSTED string the runtime produces, so the ladder under
    test is the production ladder."""
    if point is None:
        return
    from ..utils import faults
    action = faults.fire(point)
    if action is None or action == "delay":
        return
    if action == "oom":
        raise RuntimeError(
            f"RESOURCE_EXHAUSTED: injected device OOM at {point} "
            f"(faults action=oom)")
    if action == "fatal":
        # the NON-retryable twin of action=oom: the same INTERNAL status
        # string a wedged XLA runtime produces, with no OOM marker — the
        # ladder re-raises it and the host-fallback boundary
        # (exec/fallback.py) classifies it as xla_internal
        raise RuntimeError(
            f"INTERNAL: injected non-retryable XLA failure at {point} "
            f"(faults action=fatal)")
    raise faults.FaultInjectedError(point, action)


# ---------------------------------------------------------------------------
# the escalation ladder
# ---------------------------------------------------------------------------
class _Ladder:
    """Per-scope mutable ladder state: OOM attempts seen, splits spent,
    bytes spilled, arbiter engagement. One _Ladder spans a whole
    with_retry/with_retry_split call including recursive half-runs, so
    the split budget is global to the scope, not per level."""

    __slots__ = ("scope", "context", "fault_point", "attempts", "splits",
                 "spilled_bytes", "remats", "engaged", "closed", "last_error")

    def __init__(self, scope: str, context: Optional[str],
                 fault_point: Optional[str]):
        self.scope = scope
        self.context = context or scope
        self.fault_point = fault_point
        self.attempts = 0
        self.splits = 0
        self.spilled_bytes = 0
        self.remats = 0
        self.engaged = False
        self.closed = False
        self.last_error: Optional[BaseException] = None

    def note_oom(self, e: BaseException) -> None:
        self.attempts += 1
        self.last_error = e
        if _ARBITRATION and not self.engaged:
            self.engaged = True
            _ARBITER.engage()
            _bump("arbitrations")

    def spill(self) -> int:
        """One synchronous-spill rung: catalog OOM callbacks + spill."""
        from .catalog import get_catalog
        catalog = get_catalog()
        freed = catalog.handle_device_oom(
            context=f"oom-retry[{self.scope}]: "
                    f"{repr(self.last_error)[:160]}")
        if freed > 0:
            self.spilled_bytes += freed
            _bump("oom_spilled_bytes", freed)
        return freed

    def note_retry(self) -> None:
        _bump("oom_retries")
        from ..utils import faults
        faults.note_recovery("oom_retries")
        _memprof_event("oom_retry")
        print(f"# device OOM in {self.scope}: spilled, retrying "
              f"(attempt {self.attempts})", file=sys.stderr)

    def note_split(self, batch: Any) -> None:
        self.splits += 1
        _bump("oom_splits")
        from ..utils import faults
        faults.note_recovery("oom_splits")
        try:
            nbytes = batch.nbytes()
        except Exception:  # srtpu: degrade-ok(size probe for telemetry; the split itself proceeds either way)
            nbytes = 0
        _memprof_event("oom_split", nbytes)
        print(f"# device OOM in {self.scope}: splitting input on the row "
              f"axis (split {self.splits}/{_MAX_SPLITS})", file=sys.stderr)

    def note_remat(self) -> None:
        self.remats += 1
        _bump("oom_rematerializations")

    def exclusive(self):
        """Exclusive-HBM token for post-OOM attempts; no-op before the
        first OOM or with arbitration disabled."""
        if self.engaged:
            return _ARBITER.exclusive()
        return nullcontext()

    def structured_error(self, rematerialize: Optional[Callable] = None
                         ) -> DeviceOomError:
        from .catalog import get_catalog
        catalog = get_catalog()
        pm_path = None
        try:
            from ..utils import memprof
            mp = memprof.active()
            if mp is not None:
                pm_path = mp.oom_postmortem(
                    f"oom-retry exhausted [{self.scope}]: {self.context}",
                    catalog).get("path")
        except Exception:  # srtpu: degrade-ok(postmortem capture while BUILDING the structured error — the DeviceOomError is raised regardless)
            pm_path = None
        msg = (f"device OOM in scope {self.scope!r} survived the retry "
               f"ladder: {self.attempts} attempt(s), {self.splits} "
               f"split(s), {self.spilled_bytes} bytes spilled"
               + (f"; postmortem: {pm_path}" if pm_path else "")
               + "; " + catalog.oom_dump())
        return DeviceOomError(msg, scope=self.scope, attempts=self.attempts,
                              splits=self.splits,
                              spilled_bytes=self.spilled_bytes,
                              postmortem_path=pm_path,
                              rematerialize=rematerialize)

    def close(self, ok: bool) -> None:
        if self.closed:
            return
        self.closed = True
        if self.engaged:
            _ARBITER.disengage()
        if self.attempts == 0 and self.splits == 0:
            return
        _bump("oom_recoveries" if ok else "oom_failures")
        rec = {"ts": time.time(), "scope": self.scope,
               "context": (self.context or "")[:200],
               "attempts": self.attempts, "splits": self.splits,
               "rematerializations": self.remats,
               "spilled_bytes": self.spilled_bytes,
               "outcome": "recovered" if ok else "failed"}
        with _STATS_LOCK:
            _RECORDS.append(rec)


def _invoke(lad: _Ladder, fn: Callable, args: tuple, kwargs: dict):
    # cooperative cancellation checkpoint: every ladder-protected device
    # dispatch passes here, so a query past its deadline stops BEFORE its
    # next device call instead of thrashing the spill/retry rungs
    from ..utils.deadline import check_deadline
    check_deadline()
    with lad.exclusive():
        _maybe_inject(lad.fault_point)
        return fn(*args, **kwargs)


def with_retry(fn: Callable, *args, scope: str = "device",
               context: Optional[str] = None,
               fault_point: Optional[str] = None,
               max_retries: Optional[int] = None, **kwargs):
    """Run ``fn(*args, **kwargs)`` under the spill-and-retry ladder (no
    split rung — for unsplittable work: broadcast build sides, device
    concat, jit dispatch). Raises :class:`DeviceOomError` on exhaustion;
    non-OOM exceptions pass through untouched."""
    lad = _Ladder(scope, context, fault_point)
    retries = _MAX_RETRIES if max_retries is None else max_retries
    try:
        while True:
            try:
                out = _invoke(lad, fn, args, kwargs)
            except Exception as e:
                if not is_retryable_oom(e):
                    raise
                lad.note_oom(e)
                freed = lad.spill()
                # a nested ladder already exhausted ITS retries; retrying
                # identical work after a zero-byte spill cannot succeed
                if (isinstance(e, DeviceOomError) or freed <= 0
                        or lad.attempts > retries):
                    raise lad.structured_error() from e
                lad.note_retry()
                continue
            lad.close(True)
            return out
    except BaseException:
        lad.close(False)
        raise


def with_retry_split(fn: Callable, batch, *, splitter: Optional[Callable],
                     combiner: Optional[Callable] = None,
                     scope: str = "device", context: Optional[str] = None,
                     fault_point: Optional[str] = None,
                     max_retries: Optional[int] = None,
                     max_splits: Optional[int] = None):
    """Run ``fn(batch)`` under the full ladder: spill → retry →
    split-and-retry. ``splitter(batch)`` returns two row-axis halves (or
    None when the batch is too small to split); halves run sequentially
    through the same ladder and ``combiner(outputs)`` recombines them
    (default: ``concat_device_tables``). Operators whose output is not
    row-concatenable (partial aggregates, sorted runs) pass a combiner
    that re-applies their merge."""
    lad = _Ladder(scope, context, fault_point)
    retries = _MAX_RETRIES if max_retries is None else max_retries
    msplits = _MAX_SPLITS if max_splits is None else max_splits
    comb = combiner if combiner is not None else _concat_combine
    try:
        out = _run_split(lad, fn, batch, splitter, comb, retries, msplits)
        lad.close(True)
        return out
    except BaseException:
        lad.close(False)
        raise


def _run_split(lad: _Ladder, fn: Callable, batch, splitter, comb,
               retries: int, msplits: int):
    attempts_here = 0
    while True:
        try:
            return _invoke(lad, fn, (batch,), {})
        except Exception as e:
            if not is_retryable_oom(e):
                raise
            structured = isinstance(e, DeviceOomError)
            lad.note_oom(e)
            freed = lad.spill()
            if not structured and freed > 0 and attempts_here < retries:
                attempts_here += 1
                lad.note_retry()
                continue
            # escalate: split-and-retry. A donated batch was consumed by
            # the failed dispatch — resurrect it from the host origin the
            # inner ladder handed back before slicing.
            live = batch
            if structured and e.rematerialize is not None:
                live = e.rematerialize()
                lad.note_remat()
            halves = None
            if splitter is not None and lad.splits < msplits:
                halves = splitter(live)
            if halves is None:
                raise lad.structured_error() from e
            lad.note_split(live)
            outs = [_run_split(lad, fn, half, splitter, comb,
                               retries, msplits) for half in halves]
            return comb(outs)


# ---------------------------------------------------------------------------
# splitters / combiners
# ---------------------------------------------------------------------------
def split_device_rows(table):
    """Row-axis halving for DeviceTable inputs: two static-shape slices
    on the (pow2-bucketed) capacity axis, so the halves land back on the
    canonical bucket ladder and reuse compiled entries. Returns None for
    capacity-1 tables (cannot shrink further)."""
    cap = getattr(table, "capacity", 0)
    if cap <= 1:
        return None
    from ..columnar.device import slice_rows
    # slice_rows masks off rows past the active count, which assumes the
    # active rows are contiguous from row 0 — compact scattered masks first
    table = table.compact()
    half = cap // 2
    return (slice_rows(table, 0, half),
            slice_rows(table, half, cap - half))


def split_host_rows(table):
    """Row-axis halving for HostTable inputs (the H2D upload scope —
    splitting BEFORE upload halves the transfer's device footprint)."""
    n = getattr(table, "num_rows", 0)
    if n <= 1:
        return None
    half = n // 2
    return (table.slice(0, half), table.slice(half, n - half))


def _concat_combine(outs: Sequence[Any]):
    """Default combiner: row-concat the half outputs back into one
    device table (valid for row-wise operators — project/filter/
    wholestage chains — where f(a ++ b) == f(a) ++ f(b))."""
    outs = [o for o in outs if o is not None]
    if len(outs) == 1:
        return outs[0]
    from ..columnar.device import concat_device_tables
    return concat_device_tables(outs)


# ---------------------------------------------------------------------------
# jit chokepoint wrappers (utils/compile_cache.py)
# ---------------------------------------------------------------------------
def wrap_jit(fn: Callable, context: Optional[str] = None) -> Callable:
    """Spill-and-retry OOM recovery around a jitted callable (reference:
    DeviceMemoryEventHandler.scala:33).
    Splitting stays at the operator layer — this wrapper raises a
    retryable :class:`DeviceOomError` on exhaustion, which an enclosing
    with_retry_split scope escalates to split-and-retry."""
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        return with_retry(fn, *args, scope="jit",
                          context=context or getattr(fn, "__name__", "jit"),
                          fault_point="alloc.jit", **kwargs)
    return wrapped


def wrap_jit_donating(fn: Callable, context: Optional[str] = None) -> Callable:
    """OOM recovery for DONATING jit entries (donate_argnums): a failed
    dispatch may already have invalidated the donated input, so instead
    of re-calling with the same (dead) buffers the ladder re-materializes
    a fresh table from the host origin retained by the upload site
    (``table._tpu_remat``, exec/transitions.py) and retries with that.
    Without a rematerializer: spill for later batches, then structured
    failure (the old spill-and-raise, now a DeviceOomError)."""
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        remat = getattr(args[0], "_tpu_remat", None) if args else None
        lad = _Ladder("jit-donate",
                      context or getattr(fn, "__name__", "jit-donate"),
                      "alloc.jit")
        try:
            out = _run_donating(lad, fn, args, kwargs, remat)
            lad.close(True)
            return out
        except BaseException:
            lad.close(False)
            raise
    return wrapped


def _run_donating(lad: _Ladder, fn: Callable, args: tuple, kwargs: dict,
                  remat: Optional[Callable]):
    cur = args
    while True:
        try:
            return _invoke(lad, fn, cur, kwargs)
        except Exception as e:
            if not is_retryable_oom(e):
                raise
            lad.note_oom(e)
            freed = lad.spill()
            if remat is None:
                # input buffers are gone and cannot be rebuilt: spill
                # relieved pressure for SUBSEQUENT batches, but this one
                # is unrecoverable at this layer
                print("# device OOM in donating dispatch: input was "
                      "donated and no host origin was retained — "
                      "structured failure after spill", file=sys.stderr)
                raise lad.structured_error() from e
            if freed <= 0 or lad.attempts > _MAX_RETRIES:
                raise lad.structured_error(rematerialize=remat) from e
            fresh = remat()
            lad.note_remat()
            lad.note_retry()
            cur = (fresh,) + tuple(args[1:])
