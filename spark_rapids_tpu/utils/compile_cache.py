"""Global XLA compile cache (+ the runtime-OOM recovery chokepoint).

Plans are rebuilt per query execution, but the traced computations repeat
(same operator chains over the same shape buckets). jax.jit caches on the
wrapped callable's identity, so per-plan ``jax.jit(fn)`` wrappers would
recompile every run (~1s each). This cache keys jitted callables by a
canonical plan signature so repeated queries hit steady-state dispatch
(~0.1ms). The reference relies on cuDF's precompiled kernels; on TPU the
compile-once-run-many discipline is ours to enforce.

The cache is THREE tiers (ROADMAP item 2 — compile dominates bench wall):

1. the in-process table above (``_CACHE``),
2. XLA's own persistent compilation cache. Where the environment places
   it (``JAX_COMPILATION_CACHE_DIR``) it lives exactly there and the
   engine never touches ``jax_compilation_cache_dir``; otherwise it is
   wired under ``spark.rapids.tpu.compile.cacheDir``, scoped by a machine
   fingerprint + jax version so foreign XLA:CPU executables never load,
3. the engine's OWN manifest persisted alongside it: per plan signature,
   cumulative hit counts plus a serialized ``jax.export`` of the traced
   program at its first-call shapes. A fresh process replays the hottest
   exports on background threads at session start (the warm pool,
   ``spark.rapids.tpu.compile.warmPool.*``) and installs ready-to-dispatch
   executables into ``_CACHE`` — the second run of a query in a NEW
   process then executes with zero XLA compiles (``cache_stats()``).

Every load path is corruption-tolerant: a bad manifest, entry, or export
file is dropped (and counted), never fatal.

Every jitted device computation flows through here, which makes it the
TPU-native stand-in for RMM's allocation-failure callback (reference:
DeviceMemoryEventHandler.scala:33): a RESOURCE_EXHAUSTED from the runtime
triggers a synchronous catalog spill and ONE retry; a second failure
re-raises with the catalog's OOM dump attached.
"""
from __future__ import annotations

import functools
import hashlib
import json
import os
import sys
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax

from ..conf import register_conf

__all__ = ["cached_jit", "named_jit", "named_program", "PROGRAM_NAMES",
           "cache_stats", "clear_cache", "oom_retry",
           "configure_introspection", "kernel_table", "kernel_seq",
           "kernels_since", "XLA_INTROSPECTION", "KERNEL_TABLE_SIZE",
           "configure_compile_cache", "persist_compile_cache",
           "machine_fingerprint", "warm_pool_wait", "stop_warm_pool",
           "persistent_cache_dir", "COMPILE_CACHE_DIR",
           "COMPILE_CACHE_ENABLED", "WARM_POOL_ENABLED",
           "WARM_POOL_MAX_SIGNATURES", "WARM_POOL_MAX_SECONDS"]

#: Every device program the engine compiles, by its stable name. XLA names
#: a module after the jitted function (``jit_<__name__>``), so ``cached_jit``
#: / ``named_jit`` rename the function to ``srt_<name>`` first: the module is
#: ``jit_srt_<name>`` in every profile, HLO dump and compile log, whatever the
#: inner function of the builder happens to be called. A name never holds a
#: shape, a key or a partition number (those are the cache KEY's job).
#: FROZEN: the module name is part of XLA's persistent-cache key, so renaming
#: an entry costs every user one cold compile of that program. Add, never
#: rename.
PROGRAM_NAMES: Dict[str, str] = {
    "pq_decode_fixed": "Parquet fixed-width column decode (io/parquet_device.py)",
    "pq_decode_bytes": "Parquet BYTE_ARRAY column decode (io/parquet_device.py)",
    "csv_decode": "CSV field split + typed parse (exec/scan.py)",
    "json_decode": "JSON-lines field extract + typed parse (exec/scan.py)",
    "stage": "whole-stage fused operator chain (exec/wholestage.py)",
    "op_project": "TpuProjectExec run alone (exec/basic.py)",
    "op_filter": "TpuFilterExec run alone (exec/basic.py)",
    "op_sample": "TpuSampleExec (exec/basic.py)",
    "op_expand": "TpuExpandExec run alone (exec/basic.py)",
    "op_limit": "TpuLocalLimitExec: compact and keep the first n rows (exec/basic.py)",
    "agg_grouped": "hash group-by aggregate, one batch (exec/aggregate.py)",
    "agg_ungrouped": "aggregate without keys, one batch (exec/aggregate.py)",
    "agg_sizes": "collect_list/set width probe (exec/aggregate.py)",
    "compact": "DeviceTable.compact (columnar/device.py)",
    "compact_shrink": "shrink_to_fit: compact into the row count's bucket (columnar/device.py)",
    "concat": "concat_device_tables (columnar/device.py)",
    "slice_rows": "slice_rows (columnar/device.py)",
    "sort": "full sort of one batch (exec/sort.py)",
    "sort_topn": "top-n reduce + sort (exec/sort.py)",
    "window": "window functions over one sorted partition (exec/window.py)",
    "join_prep_hash": "hash-join build-side prep (exec/joins.py)",
    "join_prep_dense": "sorted-join build-side prep (exec/joins.py)",
    "join_counts": "probe match counts (exec/joins.py)",
    "join_probe_count": "dense probe match counts (exec/joins.py)",
    "join_matched": "build rows matched so far (exec/joins.py)",
    "join_pk_hash": "fused primary-key hash join (exec/joins.py)",
    "join_pk": "fused primary-key sorted join (exec/joins.py)",
    "join_semi": "semi/anti probe mask (exec/joins.py)",
    "join_expand": "join output expansion (exec/joins.py)",
    "join_expand_cond": "join expansion with a condition (exec/joins.py)",
    "join_cond": "post-join condition filter (exec/joins.py)",
    "join_leftover": "unmatched build rows of an outer join (exec/joins.py)",
    "join_cross": "nested-loop / cross join slice (exec/joins.py)",
    "join_cross_pairs": "nested-loop join pairs of one slice (exec/joins.py)",
    "exchange_pid": "partition ids of one exchange chunk (exec/exchange.py)",
    "mesh_stage": "operator chain over a device mesh (exec/mesh.py)",
    "ici_all_to_all": "hash exchange as one all-to-all (shuffle/ici.py)",
    "warm_replay": "a persisted export replayed by the warm pool",
}
_PROGRAM_PREFIX = "srt_"


def _named(fn: Callable, name: str) -> Callable:
    """``fn`` under the fixed program name ``srt_<name>``: what
    ``jax.jit`` reads the XLA module name from. A wrapper, because a
    builder may return a bound method or a shared function whose
    ``__name__`` is not ours to set; it exists only while tracing."""
    if name not in PROGRAM_NAMES:
        raise ValueError(
            f"device program name {name!r} is not in "
            f"compile_cache.PROGRAM_NAMES: add it there (names are frozen "
            f"once released, so choose it for good)")

    def program(*args, **kwargs):
        return fn(*args, **kwargs)
    program.__name__ = program.__qualname__ = _PROGRAM_PREFIX + name
    program.__wrapped__ = fn
    return program


def named_jit(fn: Callable, name: str, **jit_kwargs) -> Callable:
    """``jax.jit(fn)`` compiled as module ``jit_srt_<name>`` — for the few
    programs jitted outside ``cached_jit`` (module-level utilities, mesh
    programs with caches of their own)."""
    return jax.jit(_named(fn, name), **jit_kwargs)


def named_program(fn: Callable, name: str, **jit_kwargs) -> Callable:
    """``named_jit`` for a program that is called directly (the
    module-level table utilities of columnar/device.py): each call is a
    ``dispatch`` span, as through a ``cached_jit`` entry."""
    from .tracing import get_tracer
    jitted = named_jit(fn, name, **jit_kwargs)
    program = _PROGRAM_PREFIX + name

    @functools.wraps(fn)
    def dispatch(*args, **kwargs):
        with get_tracer().span("dispatch", "dispatch", program=program):
            return jitted(*args, **kwargs)
    return dispatch


_CACHE: Dict[str, Callable] = {}
_LOCK = threading.Lock()
_HITS = 0
_MISSES = 0
_COMPILES = 0
_COMPILE_SECONDS = 0.0

# ---------------------------------------------------------------------------
# Kernel table: one row per cache entry (= per XLA program), keyed by the
# plan signature and attributed back to the exec node that requested it
# (utils/node_context.py — pushed by the profiler/event-log
# instrumentation). Flushed into event-log schema v3 ``kernel`` records and
# mined by tools/diagnose.py ("q6 dominated by recompiles: N unique
# signatures for 1 operator"). Flare's lesson applies: inspect what the
# compiler actually generated instead of guessing.
# ---------------------------------------------------------------------------
XLA_INTROSPECTION = register_conf(
    "spark.rapids.tpu.metrics.xlaIntrospection",
    "What the compile cache captures about each XLA program into the "
    "kernel table: 'off' records only compile wall/hit counts; 'lowered' "
    "(default) additionally runs HLO cost analysis on the lowered module "
    "(flops / bytes accessed — one cheap retrace per unique program, no "
    "extra XLA compile); 'compiled' also AOT-compiles the captured input "
    "shapes for memory_analysis() (argument/output/temp bytes) — one "
    "EXTRA compile per unique program, meant for offline analysis runs.",
    "lowered",
    checker=lambda v: None if str(v).lower() in ("off", "lowered",
                                                 "compiled")
    else f"must be one of off/lowered/compiled, got {v!r}")

KERNEL_TABLE_SIZE = register_conf(
    "spark.rapids.tpu.metrics.kernelTableSize",
    "Max kernel-table entries kept in memory; least-recently-touched "
    "entries are dropped past the bound (the jitted callables themselves "
    "stay cached).", 4096,
    checker=lambda v: None if int(v) > 0 else "must be positive")

_INTROSPECT_MODE = "lowered"
_KERNEL_TABLE_MAX = 4096
_KERNELS: "Dict[str, Dict]" = {}   # signature -> kernel entry (mutable dict)
_KERNEL_SEQ = 0                    # bumps on every entry touch


def configure_introspection(conf) -> None:
    """Apply spark.rapids.tpu.metrics.* to the process kernel table
    (called from TpuSession.__init__, like configure_tracer)."""
    global _INTROSPECT_MODE, _KERNEL_TABLE_MAX
    _INTROSPECT_MODE = str(conf.get(XLA_INTROSPECTION)).lower()
    _KERNEL_TABLE_MAX = int(conf.get(KERNEL_TABLE_SIZE))


# ---------------------------------------------------------------------------
# persistent compilation tier (spark.rapids.tpu.compile.*)
# ---------------------------------------------------------------------------
COMPILE_CACHE_ENABLED = register_conf(
    "spark.rapids.tpu.compile.enabled",
    "Master switch for the persistent compilation tier: when true AND a "
    "cache directory is known (the JAX_COMPILATION_CACHE_DIR environment "
    "variable, else spark.rapids.tpu.compile.cacheDir), XLA executables "
    "persist across process restarts and the engine's plan-signature "
    "manifest + program exports are saved on session close.",
    True)

COMPILE_CACHE_DIR = register_conf(
    "spark.rapids.tpu.compile.cacheDir",
    "Base directory of the persistent compilation tier when the "
    "JAX_COMPILATION_CACHE_DIR environment variable is unset; '' "
    "(default) then disables the tier. The engine scopes everything under "
    "a <machine-fingerprint>-jax<version> subdirectory, so a shared "
    "filesystem can hold caches for a fleet and no host ever loads "
    "executables compiled for different CPU features or a different jax. "
    "Where the environment variable is set it wins: XLA's cache stays "
    "exactly there, untouched by the engine, and the engine's manifest "
    "and exports go under its 'srtpu' subdirectory.",
    "")

#: JAX reads this itself at import. A cache placed from outside has to be
#: found again from another machine, so nothing machine-specific may enter
#: the path and the engine must not re-point jax_compilation_cache_dir.
_JAX_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
#: the engine's own files (manifest, exports, quarantine) under that dir
_ENGINE_SUBDIR = "srtpu"

WARM_POOL_ENABLED = register_conf(
    "spark.rapids.tpu.compile.warmPool.enabled",
    "Precompile the hottest persisted plan signatures on background "
    "threads at session start (under the pipeline task pool), so even the "
    "FIRST run of a repeated workload in a fresh process hits steady-state "
    "dispatch. Requires compile.cacheDir.", True)

WARM_POOL_MAX_SIGNATURES = register_conf(
    "spark.rapids.tpu.compile.warmPool.maxSignatures",
    "How many persisted plan signatures the warm pool precompiles, "
    "hottest (by cumulative cross-process hits) first. Also caps how many "
    "program exports are written per session close.", 32,
    checker=lambda v: None if int(v) > 0 else "must be positive")

WARM_POOL_MAX_SECONDS = register_conf(
    "spark.rapids.tpu.compile.warmPool.maxSeconds",
    "Wall-clock budget for warm-pool precompilation; signatures not "
    "reached by the deadline stay cold (they compile on first dispatch as "
    "usual).", 30.0, conf_type=float,
    checker=lambda v: None if float(v) > 0 else "must be positive")

#: refuse to persist a single program export larger than this — a giant
#: export means a builder closed over baked-in data, which the in-process
#: cache contract already forbids; never let one entry bloat the tier
_EXPORT_MAX_BYTES = 32 * 1024 * 1024

# persistent-tier process state. _PERSIST is reconfigured per session
# (most recent wins, like the tracer/pipeline chokepoints); _EXPORTABLE
# retains (builder, aval-skeleton) per signature compiled THIS process so
# session close can export the traced programs. All under _LOCK.
_PERSIST: Dict = {"dir": None, "base": {}, "wired_xla": False,
                  "warm_enabled": True,
                  "warm_max": int(WARM_POOL_MAX_SIGNATURES.default),
                  "warm_seconds": float(WARM_POOL_MAX_SECONDS.default)}
_EXPORTABLE: Dict[str, Tuple[Callable, tuple]] = {}
_PSTATS = {"manifest_entries": 0, "warmed_entries": 0, "hits": 0,
           "misses": 0, "warm_compiles": 0, "warm_errors": 0,
           "exports_written": 0, "dropped_entries": 0}
_WARM_STOP = threading.Event()
_WARM_THREAD: Optional[threading.Thread] = None


def machine_fingerprint() -> str:
    """Stable id for 'programs compiled here run here' (XLA:CPU bakes host
    CPU features into generated code; a foreign cache recompiles or
    SIGILLs — bench.py learned this across rounds)."""
    import platform
    parts = [platform.system(), platform.machine()]
    try:
        want = ("flags", "features", "model name", "cpu model")
        seen = set()
        with open("/proc/cpuinfo") as f:
            for line in f:
                key = line.split(":", 1)[0].strip().lower()
                if key in want and key not in seen:
                    seen.add(key)
                    parts.append(
                        " ".join(sorted(line.split(":", 1)[1].split())))
                if len(seen) == len(want):
                    break
    except OSError:
        pass
    return hashlib.sha256("|".join(parts).encode()).hexdigest()[:12]


def _touch_locked(entry: Dict) -> None:
    global _KERNEL_SEQ
    _KERNEL_SEQ += 1
    entry["last_touch"] = _KERNEL_SEQ


def _kernel_entry_locked(key: str) -> Dict:
    entry = _KERNELS.get(key)
    if entry is None:
        from .node_context import current
        ctx = current()
        entry = _KERNELS[key] = {
            "signature": key,
            "node_name": ctx.name if ctx is not None else None,
            "node_id": ctx.node_id if ctx is not None else None,
            "query_id": ctx.query_id if ctx is not None else None,
            "hits": 0, "misses": 0, "compiles": 0, "compile_s": 0.0,
            "cost": {}, "memory": {}, "last_touch": 0,
        }
        # touch BEFORE choosing an eviction victim: a fresh entry holds
        # last_touch=0 (the global minimum) and would otherwise evict
        # itself, freezing the table with stale entries at capacity
        _touch_locked(entry)
        if len(_KERNELS) > _KERNEL_TABLE_MAX:
            victim = min(_KERNELS, key=lambda k: _KERNELS[k]["last_touch"])
            del _KERNELS[victim]
    else:
        _touch_locked(entry)
    return entry


def kernel_seq() -> int:
    """Monotonic touch counter — snapshot before a query, pass to
    ``kernels_since`` after it to get the programs that query exercised."""
    with _LOCK:
        return _KERNEL_SEQ


def kernels_since(seq: int) -> List[Dict]:
    """Kernel entries touched (hit, compiled, or created) after ``seq``."""
    with _LOCK:
        return [dict(e) for e in _KERNELS.values() if e["last_touch"] > seq]


def kernel_table() -> List[Dict]:
    """The full kernel table, hottest compile first."""
    with _LOCK:
        rows = [dict(e) for e in _KERNELS.values()]
    return sorted(rows, key=lambda e: -e["compile_s"])


def _aval_of(x):
    """Shape/dtype skeleton of one pytree leaf (weak types collapse — fine
    for cost analysis)."""
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)
    return x


def _introspect(key: str, builder: Callable[[], Callable],
                args, kwargs) -> None:
    """Capture cost/memory analysis for the program behind ``key``.

    Re-lowers the builder against shape skeletons of the first call's
    arguments (jit.lower accepts ShapeDtypeStruct pytrees, so nothing is
    kept resident). Failures are recorded, never raised — introspection
    must not break execution."""
    mode = _INTROSPECT_MODE
    if mode == "off":
        return
    entry_update: Dict = {}
    try:
        avals = jax.tree_util.tree_map(_aval_of, (args, kwargs))
        lowered = jax.jit(builder()).lower(*avals[0], **avals[1])
        cost = lowered.cost_analysis()
        if mode == "compiled":
            compiled = lowered.compile()
            cca = compiled.cost_analysis()
            if cca:
                cost = cca[0] if isinstance(cca, list) else cca
            mem = compiled.memory_analysis()
            if mem is not None:
                entry_update["memory"] = {
                    "argument_bytes": int(mem.argument_size_in_bytes),
                    "output_bytes": int(mem.output_size_in_bytes),
                    "temp_bytes": int(mem.temp_size_in_bytes),
                    "code_bytes": int(mem.generated_code_size_in_bytes),
                }
        if isinstance(cost, list):
            cost = cost[0] if cost else {}
        if cost:
            # keep the totals; the per-operand breakdown keys ("bytes
            # accessed0{}") would bloat every event log
            entry_update["cost"] = {
                k: float(v) for k, v in cost.items() if "{" not in k}
    except Exception as e:  # pragma: no cover - backend-dependent
        entry_update["introspection_error"] = repr(e)[:200]
    with _LOCK:
        entry = _KERNELS.get(key)
        if entry is not None:
            entry.update(entry_update)

def oom_retry(fn: Callable) -> Callable:
    """Spill-and-retry OOM recovery at the jit chokepoint. The
    classification and the escalation ladder live in memory/retry.py
    (wrap_jit) — this name survives as the cache's chokepoint so every
    existing call site (and test) keeps working."""
    from ..memory.retry import wrap_jit
    return wrap_jit(fn)


def oom_spill_noretry(fn: Callable) -> Callable:
    """OOM handling for DONATING entries (donate_argnums): a failed
    dispatch may already have invalidated the donated input buffers, so
    re-calling with the same arguments is unsound. memory/retry.py's
    wrap_jit_donating re-materializes the input from the host origin
    retained by the upload site and retries; with no origin it spills
    for SUBSEQUENT batches and raises a structured DeviceOomError."""
    from ..memory.retry import wrap_jit_donating
    return wrap_jit_donating(fn)


_EXEC_MISMATCH_MARKERS = ("but got buffer with incompatible size",
                          "buffers but compiled program expected")


def _rebuild_on_mismatch(builder: Callable[[], Callable],
                         fn: Callable) -> Callable:
    """jax 0.9 workaround: a jit wrapper's dispatch cache can resolve to a
    stale executable for inputs whose treedef+avals are IDENTICAL to a
    previously successful call (observed with (n, 2) two-limb decimal128
    columns — no-lengths 2-D data planes). A fresh jax.jit of the same
    builder always works, so on that specific INVALID_ARGUMENT signature
    the jitted callable is rebuilt and the call retried. The rebuild
    happens INSIDE this wrapper, so the cache entry (and the ``dispatch``
    span around it) stays the one every caller already holds."""
    current = [fn]

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        try:
            return current[0](*args, **kwargs)
        except ValueError as e:
            msg = str(e)
            if not any(m in msg for m in _EXEC_MISMATCH_MARKERS):
                raise
            current[0] = oom_retry(jax.jit(builder()))
            return current[0](*args, **kwargs)
    return wrapped


def _time_first_call(key: str, fn: Callable,
                     builder: Optional[Callable[[], Callable]] = None,
                     name: str = "") -> Callable:
    """The wrapper every cache entry is called through: a ``dispatch``
    span (the host side of one call into the compiled program ``name``)
    around each call, and the first call attributed to XLA compile time.

    jax.jit compiles lazily on first dispatch, so the first call through a
    fresh entry is (compile + run); later calls are steady-state dispatch.
    Timing the first call is the standard approximation for per-plan
    compile seconds (the run part is dwarfed by the ~1s trace+compile),
    and it scopes the call in a "compile" trace span so Perfetto shows
    compile stalls on the query timeline. The first call also feeds the
    kernel table: compile wall + (when introspection is on) the program's
    HLO cost/memory analysis, attributed to the executing node."""
    from .tracing import get_tracer
    state = {"done": False}
    program = _PROGRAM_PREFIX + name

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        global _COMPILES, _COMPILE_SECONDS
        if state["done"]:
            with get_tracer().span("dispatch", "dispatch", program=program):
                return fn(*args, **kwargs)
        # shape/dtype skeleton BEFORE dispatch: donated input buffers may
        # be dead afterwards; the skeleton is what session close exports
        # for the persistent tier (cheap — aval metadata only)
        skeleton = None
        if builder is not None and _PERSIST["dir"] is not None:
            try:
                skeleton = jax.tree_util.tree_map(_aval_of, (args, kwargs))
            except Exception:
                skeleton = None
        t0 = time.perf_counter()
        with get_tracer().span("dispatch", "dispatch", program=program), \
                get_tracer().span("compile", "compile", program=program,
                                  key=key[:160]):
            out = fn(*args, **kwargs)
        dt = time.perf_counter() - t0
        first = False
        with _LOCK:
            # check-and-set under the lock: concurrent first dispatches of
            # one entry must attribute the compile exactly once
            if not state["done"]:
                state["done"] = True
                first = True
                _COMPILES += 1
                _COMPILE_SECONDS += dt
                if skeleton is not None:
                    if len(_EXPORTABLE) >= 512 and key not in _EXPORTABLE:
                        # bound builder-closure retention: beyond any
                        # plausible warm set, drop the oldest capture
                        _EXPORTABLE.pop(next(iter(_EXPORTABLE)))
                    _EXPORTABLE[key] = (builder, skeleton)
                entry = _KERNELS.get(key)
                if entry is not None:
                    entry["compiles"] += 1
                    entry["compile_s"] += dt
                    _touch_locked(entry)
        if first:
            # a finished compile IS engine progress: without this, a
            # compile-heavy warm-up phase (many first dispatches, no
            # batches accounted yet) looks frozen to the health watchdog
            from ..parallel.pipeline import note_progress
            note_progress()
            from .node_context import current_registry
            reg = current_registry()
            if reg is not None:
                from . import metrics as M
                reg.add(M.COMPILE_TIME, dt)
            if builder is not None:
                _introspect(key, builder, args, kwargs)
        return out
    return wrapped


def _attribute(metric_name: str) -> None:
    """Count a cache hit/miss on the executing node's registry (no-op when
    uninstrumented — process-global counters still track)."""
    from .node_context import current_registry
    reg = current_registry()
    if reg is not None:
        reg.add(metric_name, 1)


def cached_jit(key: str, builder: Callable[[], Callable], *, name: str,
               donate_argnums=None) -> Callable:
    """Return a jitted callable for ``key``, building it on first use.

    ``name`` is the program's fixed name from ``PROGRAM_NAMES``: the
    built function is compiled as XLA module ``jit_srt_<name>`` and every
    call is a ``dispatch`` span carrying it. Many keys share one name
    (every fused chain of every query is ``stage``).

    ``donate_argnums`` requests XLA input-buffer donation for the jitted
    entry (exec/wholestage.py input donation — callers MUST key donating
    and non-donating variants differently: the option is baked into the
    compiled executable)."""
    global _HITS, _MISSES
    from . import metrics as M
    with _LOCK:
        fn = _CACHE.get(key)
        if fn is not None:
            _HITS += 1
            entry = _KERNELS.get(key)
            if entry is not None:
                entry["hits"] += 1
                _touch_locked(entry)
        else:
            _MISSES += 1
            entry = _kernel_entry_locked(key)
            entry["misses"] += 1
            entry["program"] = _PROGRAM_PREFIX + name

    def named_builder():
        return _named(builder(), name)

    if fn is not None:
        if isinstance(fn, _WarmedEntry):
            # warm-pool entries need the builder for output-pytree
            # reconstruction and as the unexpected-shape fallback
            fn.attach_builder(named_builder, donate_argnums, name)
        _attribute(M.COMPILE_CACHE_HITS)
        return fn
    _attribute(M.COMPILE_CACHE_MISSES)
    built = _build_entry(key, named_builder, donate_argnums, name)
    with _LOCK:
        fn = _CACHE.setdefault(key, built)
    if fn is not built and isinstance(fn, _WarmedEntry):
        # the warm pool installed this key between our miss check and the
        # setdefault — the warmed entry has never seen a cached_jit() hit,
        # so it still needs the builder for out-tree/fallback dispatch
        fn.attach_builder(named_builder, donate_argnums, name)
    return fn


def _build_entry(key: str, builder: Callable[[], Callable], donate_argnums,
                 name: str) -> Callable:
    """A fresh cache entry over ``builder`` (already named). The OOM
    wrapper sits directly on the jitted callable and ``_time_first_call``
    outermost, so the ``dispatch`` span covers recovery too."""
    if donate_argnums is None:
        return _time_first_call(key, _rebuild_on_mismatch(
            builder, oom_retry(jax.jit(builder()))), builder, name)
    # donating entries get NO call-again recovery with the SAME args (the
    # failed dispatch may have consumed the donated input); the donating
    # ladder re-materializes from the retained host origin instead, or
    # spills-and-raises structured when there is none
    return _time_first_call(key, oom_spill_noretry(
        jax.jit(builder(), donate_argnums=donate_argnums)), builder, name)


def cache_stats() -> Dict[str, float]:
    # snapshot under _LOCK: the pipeline task pool compiles concurrently,
    # and a lock-free multi-field read can tear (hits from one moment,
    # compiles from another) — stats consumers diff these across queries
    with _LOCK:
        out = {"entries": len(_CACHE), "hits": _HITS, "misses": _MISSES,
               "compiles": _COMPILES,
               "compile_seconds": round(_COMPILE_SECONDS, 6)}
        out.update({f"persist_{k}": v for k, v in _PSTATS.items()})
    return out


def clear_cache():
    global _HITS, _MISSES, _COMPILES, _COMPILE_SECONDS
    with _LOCK:
        _CACHE.clear()
        _KERNELS.clear()
        _EXPORTABLE.clear()
        # flushed deltas track _KERNELS totals; clearing one without the
        # other would produce negative deltas at the next persist
        _PERSIST.pop("flushed", None)
        for k in _PSTATS:
            _PSTATS[k] = 0
        _HITS = _MISSES = 0
        _COMPILES = 0
        _COMPILE_SECONDS = 0.0


# ---------------------------------------------------------------------------
# persistent tier: manifest + program exports + warm pool
# ---------------------------------------------------------------------------
def persistent_cache_dir() -> Optional[str]:
    """The active tier directory (fingerprint+jax scoped), or None."""
    with _LOCK:
        return _PERSIST["dir"]


def _aval_signature(treedef, leaves) -> str:
    """Stable id of a call's input pytree: structure + leaf shape/dtype.
    Identical across processes for identical plans over identical bucket
    ladders — the key that matches a live dispatch to a persisted export."""
    parts = [str(treedef)]
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            parts.append(f"{dtype}{tuple(shape)}")
        else:
            parts.append(f"py:{type(leaf).__name__}")
    return hashlib.sha256("|".join(parts).encode()).hexdigest()[:16]


class _WarmedEntry:
    """A ``_CACHE`` entry installed by the warm pool BEFORE any builder
    exists in this process: per input-shape signature, an AOT-compiled
    executable replayed from a persisted ``jax.export``.

    Dispatch flattens the call's args, matches the aval signature, runs the
    flat executable and unflattens through the output pytree learned from
    ONE abstract trace of the builder (``jax.eval_shape`` — no XLA compile).
    Any mismatch (unexpected shapes, incompatible arguments) falls back to
    the normal build path, which counts a real compile."""

    def __init__(self, key: str):
        self.key = key
        self._records: Dict[str, Callable] = {}   # aval_sig -> flat dispatch
        self._out_trees: Dict[str, object] = {}   # aval_sig -> out treedef
        self._builder: Optional[Callable] = None
        self._donate = None
        self._name = "warm_replay"
        self._fallback: Optional[Callable] = None
        self._elock = threading.Lock()

    def add_record(self, aval_sig: str, dispatch: Callable) -> None:
        self._records[aval_sig] = dispatch

    def attach_builder(self, builder: Callable, donate_argnums,
                       name: str) -> None:
        if self._builder is None:
            self._builder = builder
            self._donate = donate_argnums
            self._name = name

    def _fallback_fn(self) -> Callable:
        fb = self._fallback
        if fb is not None:
            return fb
        with self._elock:
            if self._fallback is None:
                builder = self._builder
                if builder is None:
                    raise RuntimeError(
                        f"warmed compile-cache entry {self.key!r} dispatched "
                        f"before any cached_jit() call attached its builder")
                self._fallback = _build_entry(self.key, builder,
                                              self._donate, self._name)
            return self._fallback

    def _out_tree_for(self, aval_sig: str, args, kwargs, n_out: int):
        tree = self._out_trees.get(aval_sig)
        if tree is not None:
            return tree
        builder = self._builder
        if builder is None:
            return None
        # one abstract trace to learn the output pytree (cheap: no XLA)
        out_shape = jax.eval_shape(builder(), *args, **kwargs)
        leaves, tree = jax.tree_util.tree_flatten(out_shape)
        if len(leaves) != n_out:
            return None
        with self._elock:
            self._out_trees.setdefault(aval_sig, tree)
        return tree

    def __call__(self, *args, **kwargs):
        leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
        aval_sig = _aval_signature(treedef, leaves)
        dispatch = self._records.get(aval_sig)
        if dispatch is None:
            with _LOCK:
                _PSTATS["misses"] += 1
            return self._fallback_fn()(*args, **kwargs)
        from .tracing import get_tracer
        try:
            with get_tracer().span("dispatch", "dispatch",
                                   program=_PROGRAM_PREFIX + self._name):
                flat_out = dispatch(*leaves)
            tree = self._out_tree_for(aval_sig, args, kwargs, len(flat_out))
            if tree is None:
                raise TypeError("output arity mismatch")
            out = jax.tree_util.tree_unflatten(tree, flat_out)
        except (TypeError, ValueError) as e:
            # incompatible-argument class of errors only: device OOM
            # (RuntimeError) propagates through the oom_retry wrapper
            with _LOCK:
                self._records.pop(aval_sig, None)
                _PSTATS["warm_errors"] += 1
                _PSTATS["misses"] += 1
            print(f"# warmed entry {self.key[:80]!r} fell back to a live "
                  f"compile: {type(e).__name__}", file=sys.stderr)
            return self._fallback_fn()(*args, **kwargs)
        with _LOCK:
            _PSTATS["hits"] += 1
        return out


def _manifest_path(tier_dir: str) -> str:
    return os.path.join(tier_dir, "manifest.json")


def _load_manifest(path: str) -> Tuple[Dict[str, Dict], int]:
    """Read the persisted plan-signature manifest. Corruption-tolerant by
    contract: a bad file or a bad entry is dropped (counted), never
    raised — a wedged cache must not take the engine down."""
    try:
        with open(path, encoding="utf-8") as f:
            data = json.load(f)
    except FileNotFoundError:
        return {}, 0
    except (OSError, ValueError):
        return {}, 1
    raw = data.get("entries") if isinstance(data, dict) else None
    if not isinstance(raw, dict):
        return {}, 1
    entries: Dict[str, Dict] = {}
    dropped = 0
    for sig, e in raw.items():
        if not isinstance(e, dict) \
                or not isinstance(e.get("hits", 0), (int, float)) \
                or not isinstance(e.get("compiles", 0), (int, float)):
            dropped += 1
            continue
        exports = e.get("exports", [])
        if not isinstance(exports, list):
            dropped += 1
            continue
        good_exports = [x for x in exports
                        if isinstance(x, dict)
                        and isinstance(x.get("file"), str)
                        and isinstance(x.get("aval_sig"), str)]
        entry = {"hits": int(e.get("hits", 0)),
                 "compiles": int(e.get("compiles", 0)),
                 "compile_s": float(e.get("compile_s", 0.0) or 0.0),
                 "node_name": e.get("node_name"),
                 "program": e.get("program"),
                 "exports": good_exports}
        entries[sig] = entry
    return entries, dropped


def configure_compile_cache(conf) -> Optional[str]:
    """Apply spark.rapids.tpu.compile.* (called from TpuSession.__init__,
    most recent session wins). Finds the tier directory (module docstring,
    tier 2), loads the engine manifest, and starts the warm pool. Returns
    the directory of the engine's own files, or None when the tier is
    off."""
    stop_warm_pool()
    enabled = bool(conf.get(COMPILE_CACHE_ENABLED))
    external = os.environ.get(_JAX_CACHE_ENV, "").strip()
    base = str(conf.get(COMPILE_CACHE_DIR) or "").strip()
    if enabled and external:
        tier, xla_dir = os.path.join(os.path.abspath(external),
                                     _ENGINE_SUBDIR), None
    elif enabled and base:
        tier = os.path.join(os.path.abspath(base),
                            f"{machine_fingerprint()}-jax{jax.__version__}")
        xla_dir = os.path.join(tier, "xla")
    else:
        _persist_off()
        return None
    try:
        os.makedirs(os.path.join(tier, "exports"), exist_ok=True)
        if xla_dir is not None:
            os.makedirs(xla_dir, exist_ok=True)
    except OSError as e:
        import warnings
        warnings.warn(f"persistent compile cache disabled: cannot create "
                      f"{tier!r} ({e})", RuntimeWarning)
        _persist_off()
        return None
    # tier 2: XLA executables survive restarts. min_compile_time 0 — a
    # cache dir was asked for, so persist everything
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if xla_dir is not None:
        jax.config.update("jax_compilation_cache_dir", xla_dir)
    entries, dropped = _load_manifest(_manifest_path(tier))
    with _LOCK:
        _PERSIST["dir"] = tier
        _PERSIST["wired_xla"] = xla_dir is not None
        _PERSIST["base"] = entries
        _PERSIST["warm_enabled"] = bool(conf.get(WARM_POOL_ENABLED))
        _PERSIST["warm_max"] = int(conf.get(WARM_POOL_MAX_SIGNATURES))
        _PERSIST["warm_seconds"] = float(conf.get(WARM_POOL_MAX_SECONDS))
        _PSTATS["manifest_entries"] = len(entries)
        _PSTATS["dropped_entries"] += dropped
        warm = _PERSIST["warm_enabled"]
    if warm and entries:
        _start_warm_pool()
    return tier


def _persist_off() -> None:
    """Tier off for the most recent session: it owns the chokepoint, so
    un-wire the XLA disk cache an earlier session wired — never one the
    environment placed."""
    with _LOCK:
        unwire = _PERSIST["wired_xla"]
        _PERSIST["dir"] = None
        _PERSIST["wired_xla"] = False
        _PERSIST["base"] = {}
    if unwire:
        jax.config.update("jax_compilation_cache_dir", None)


def _warm_items_locked() -> List[Tuple[str, str, str, Optional[str]]]:
    """(signature, export file, aval_sig, program name) for the hottest
    manifest signatures, bounded by warmPool.maxSignatures."""
    ranked = sorted(_PERSIST["base"].items(),
                    key=lambda kv: -(kv[1]["hits"] + kv[1]["compiles"]))
    items: List[Tuple[str, str, str, Optional[str]]] = []
    for sig, entry in ranked[:_PERSIST["warm_max"]]:
        for ex in entry["exports"]:
            items.append((sig, ex["file"], ex["aval_sig"],
                          entry.get("program")))
    return items


def _start_warm_pool() -> None:
    global _WARM_THREAD
    if _WARM_THREAD is not None and _WARM_THREAD.is_alive():
        # a previous pool outlived its stop request (mid-AOT-compile);
        # clearing _WARM_STOP under it would un-cancel it — skip warming
        print("# warm pool not started: previous pool still draining",
              file=sys.stderr)
        return
    with _LOCK:
        tier = _PERSIST["dir"]
        items = _warm_items_locked()
        deadline = time.monotonic() + _PERSIST["warm_seconds"]
    if not items or tier is None:
        return
    _WARM_STOP.clear()

    def main():
        from ..parallel.pipeline import parallel_map
        try:
            parallel_map(lambda it: _warm_one(tier, deadline, *it), items,
                         stage="warm-pool")
        except Exception as e:  # never let warming break a session
            print(f"# warm pool aborted: {type(e).__name__}: {e}",
                  file=sys.stderr)

    _WARM_THREAD = threading.Thread(target=main, daemon=True,
                                    name="tpu-warm-pool")
    _WARM_THREAD.start()


def _warm_one(tier_dir: str, deadline: float, sig: str, fname: str,
              aval_sig: str, program: Optional[str] = None) -> None:
    """Replay one persisted export: deserialize, AOT-compile (an XLA
    disk-cache hit when tier 2 already holds the executable), and install
    a dispatchable entry under the plan signature. It compiles under the
    program name the manifest recorded (``warm_replay`` for a manifest
    written before programs had names)."""
    name = (program or "")[len(_PROGRAM_PREFIX):]
    if name not in PROGRAM_NAMES:
        name = "warm_replay"
    if _WARM_STOP.is_set() or time.monotonic() > deadline:
        return
    try:
        from jax import export as jax_export
        path = os.path.join(tier_dir, "exports", os.path.basename(fname))
        with open(path, "rb") as f:
            data = f.read()
        exported = jax_export.deserialize(bytearray(data))
        sds = [jax.ShapeDtypeStruct(a.shape, a.dtype)
               for a in exported.in_avals]
        compiled = named_jit(exported.call, name).lower(*sds).compile()
        dispatch = oom_retry(compiled)
    except Exception as e:
        with _LOCK:
            _PSTATS["warm_errors"] += 1
        print(f"# warm pool skipped {sig[:80]!r}: "
              f"{type(e).__name__}: {str(e)[:120]}", file=sys.stderr)
        return
    with _LOCK:
        cur = _CACHE.get(sig)
        if cur is None:
            cur = _CACHE[sig] = _WarmedEntry(sig)
            _PSTATS["warmed_entries"] += 1
            entry = _kernel_entry_locked(sig)
            entry["warmed"] = True
        if isinstance(cur, _WarmedEntry):
            cur.add_record(aval_sig, dispatch)
            _PSTATS["warm_compiles"] += 1
        # else: a live compile beat us to the key — keep the live entry


def warm_pool_wait(timeout: Optional[float] = None) -> bool:
    """Block until warm-pool precompilation settles (bench/tests call this
    before measuring). True when the pool is idle."""
    t = _WARM_THREAD
    if t is None or not t.is_alive():
        return True
    with _LOCK:
        budget = _PERSIST["warm_seconds"] + 10.0
    t.join(timeout if timeout is not None else budget)
    return not t.is_alive()


def stop_warm_pool(timeout: float = 10.0) -> None:
    """Cancel + join the warm pool (session close / reconfigure); part of
    the no-leaked-threads contract."""
    global _WARM_THREAD
    t = _WARM_THREAD
    if t is None:
        return
    _WARM_STOP.set()
    t.join(timeout)
    if t.is_alive():
        # join timed out mid-AOT-compile: keep the handle so the leak is
        # VISIBLE (warm_pool_wait / thread checks still see it) and so
        # _start_warm_pool refuses to race a second pool against it
        print("# warm pool still busy after stop request; it will exit "
              "after the in-flight compile", file=sys.stderr)
        return
    _WARM_THREAD = None


def _export_one(key: str, builder: Callable, skeleton, exports_dir: str
                ) -> Optional[Dict[str, str]]:
    """Serialize the traced program behind ``key`` at its captured input
    shapes. The export wraps the computation in a FLAT (leaves-in,
    leaves-out) function so no custom pytree type needs a serializer;
    dispatch re-learns the output tree from one eval_shape."""
    from jax import export as jax_export
    leaves, treedef = jax.tree_util.tree_flatten(skeleton)

    def flat_fn(*flat):
        a, kw = jax.tree_util.tree_unflatten(treedef, flat)
        out = builder()(*a, **kw)
        return tuple(jax.tree_util.tree_flatten(out)[0])

    exported = jax_export.export(jax.jit(flat_fn))(*leaves)
    data = exported.serialize()
    if len(data) > _EXPORT_MAX_BYTES:
        raise ValueError(f"export too large ({len(data)} bytes) — builder "
                         f"likely closed over concrete data")
    aval_sig = _aval_signature(treedef, leaves)
    fname = hashlib.sha256(
        (key + "|" + aval_sig).encode()).hexdigest()[:24] + ".jaxexport"
    path = os.path.join(exports_dir, fname)
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(bytes(data))
    os.replace(tmp, path)
    return {"file": fname, "aval_sig": aval_sig}


def persist_compile_cache() -> int:
    """Flush the engine manifest (+ new program exports) to the tier
    directory — called from TpuSession.close(). Merges this process's
    hit/compile counts into the cumulative cross-process totals, exports
    the hottest newly-compiled programs (bounded by
    warmPool.maxSignatures), and atomically replaces manifest.json.
    Returns the number of exports written; never raises."""
    with _LOCK:
        tier = _PERSIST["dir"]
        if tier is None:
            return 0
        entries: Dict[str, Dict] = {
            sig: dict(e, exports=list(e["exports"]))
            for sig, e in _PERSIST["base"].items()}
        # merge DELTAS vs the last flush, not raw process totals: a
        # process cycling several sessions (or a double close()) must not
        # re-merge counts it already persisted
        flushed = _PERSIST.setdefault("flushed", {})
        kernels, totals = {}, {}
        for sig, e in _KERNELS.items():
            cur = (int(e.get("hits", 0)), int(e.get("compiles", 0)),
                   float(e.get("compile_s", 0.0)))
            prev = flushed.get(sig, (0, 0, 0.0))
            totals[sig] = cur
            kernels[sig] = {"hits": cur[0] - prev[0],
                            "compiles": cur[1] - prev[1],
                            "compile_s": cur[2] - prev[2],
                            "node_name": e.get("node_name"),
                            "program": e.get("program")}
        exportable = dict(_EXPORTABLE)
        cap = _PERSIST["warm_max"]
    for sig, k in kernels.items():
        e = entries.setdefault(
            sig, {"hits": 0, "compiles": 0, "compile_s": 0.0,
                  "node_name": None, "program": None, "exports": []})
        e["hits"] += int(k["hits"])
        e["compiles"] += int(k["compiles"])
        e["compile_s"] = round(e["compile_s"] + float(k["compile_s"]), 6)
        e["node_name"] = e["node_name"] or k["node_name"]
        e["program"] = e.get("program") or k["program"]
    # export the hottest signatures compiled this process whose captured
    # shapes are not persisted yet
    exports_dir = os.path.join(tier, "exports")
    candidates = sorted(
        exportable, key=lambda s: -(entries.get(s, {}).get("hits", 0)
                                    + entries.get(s, {}).get("compiles", 0)))
    written = 0
    exported_keys = []       # captures persisted (or already on disk) —
    stale_files = []         # release the builder closures afterwards
    for sig in candidates:
        if written >= cap:
            break
        builder, skeleton = exportable[sig]
        entry = entries.setdefault(
            sig, {"hits": 0, "compiles": 0, "compile_s": 0.0,
                  "node_name": None, "exports": []})
        try:
            leaves, treedef = jax.tree_util.tree_flatten(skeleton)
            aval_sig = _aval_signature(treedef, leaves)
            if any(x["aval_sig"] == aval_sig for x in entry["exports"]):
                exported_keys.append(sig)
                continue
            rec = _export_one(sig, builder, skeleton, exports_dir)
        except Exception as e:
            print(f"# compile-cache export skipped {sig[:80]!r}: "
                  f"{type(e).__name__}: {str(e)[:120]}", file=sys.stderr)
            continue
        if rec is not None:
            # newest first; bound the per-signature shape fanout, and
            # reclaim the files of records falling off the end
            kept = [rec] + entry["exports"][:3]
            stale_files.extend(x["file"] for x in entry["exports"][3:])
            entry["exports"] = kept
            written += 1
            exported_keys.append(sig)
    try:
        path = _manifest_path(tier)
        tmp = path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump({"version": 1, "tool": "spark-rapids-tpu",
                       "jax": jax.__version__,
                       "fingerprint": machine_fingerprint(),
                       "entries": entries}, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError as e:
        print(f"# compile-cache manifest not written: {e}", file=sys.stderr)
        return written
    for fname in stale_files:   # only after the manifest dropped them
        try:
            os.unlink(os.path.join(exports_dir, os.path.basename(fname)))
        except OSError:
            pass
    with _LOCK:
        _PERSIST["base"] = entries
        _PERSIST["flushed"] = dict(flushed, **totals)
        for sig in exported_keys:
            _EXPORTABLE.pop(sig, None)
        _PSTATS["manifest_entries"] = len(entries)
        _PSTATS["exports_written"] += written
    return written
