"""Global XLA compile cache (+ the runtime-OOM recovery chokepoint).

Plans are rebuilt per query execution, but the traced computations repeat
(same operator chains over the same shape buckets). jax.jit caches on the
wrapped callable's identity, so per-plan ``jax.jit(fn)`` wrappers would
recompile every run (~1s each). This cache keys jitted callables by a
canonical plan signature so repeated queries hit steady-state dispatch
(~0.1ms). The reference relies on cuDF's precompiled kernels; on TPU the
compile-once-run-many discipline is ours to enforce.

The cache is TWO tiers (docs/compilation.md):

1. the in-process tables: ``_CACHE`` (jitted callables by plan signature)
   and ``_AOT`` (the mesh programs' AOT executables, ``aot_program``),
2. XLA's own persistent compilation cache. Where the environment places
   it (``JAX_COMPILATION_CACHE_DIR``) it lives exactly there and the
   engine never touches ``jax_compilation_cache_dir``; otherwise it is
   wired under ``spark.rapids.tpu.compile.cacheDir``, scoped by a machine
   fingerprint + jax version so foreign XLA:CPU executables never load.

There is no third: an engine-side replay of exported programs lost to
tier 2 on the chip (PR 22: its executables changed every downstream HLO,
so a second process hit XLA's cache 48 of 120 times against 112 of 112
without it).

Every jitted device computation flows through here, which makes it the
TPU-native stand-in for RMM's allocation-failure callback (reference:
DeviceMemoryEventHandler.scala:33): a RESOURCE_EXHAUSTED from the runtime
triggers a synchronous catalog spill and ONE retry; a second failure
re-raises with the catalog's OOM dump attached.
"""
from __future__ import annotations

import functools
import hashlib
import os
import threading
import time
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Tuple

import jax

from ..conf import register_conf

__all__ = ["cached_jit", "named_jit", "named_program", "aot_program",
           "PROGRAM_NAMES", "cache_stats", "clear_cache",
           "configure_introspection", "kernel_table", "kernel_seq",
           "kernels_since", "XLA_INTROSPECTION", "KERNEL_TABLE_SIZE",
           "configure_compile_cache", "machine_fingerprint",
           "persistent_cache_dir", "COMPILE_CACHE_DIR",
           "COMPILE_CACHE_ENABLED"]

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
    "agg_passthrough": "a skipped partial aggregate: every row its own state (exec/aggregate.py)",
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
    programs jitted outside ``cached_jit`` (module-level utilities, the
    mesh programs kept by ``aot_program``)."""
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
        with get_tracer().span("dispatch", "dispatch", on=(args, kwargs),
                               program=program):
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
    "persist across process restarts.",
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
    "exactly there, untouched by the engine, and the engine's own file "
    "(quarantine.json) goes under its 'srtpu' subdirectory.",
    "")

#: JAX reads this itself at import. A cache placed from outside has to be
#: found again from another machine, so nothing machine-specific may enter
#: the path and the engine must not re-point jax_compilation_cache_dir.
_JAX_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
#: the engine's own file (exec/fallback.py's quarantine.json) under that dir
_ENGINE_SUBDIR = "srtpu"

# persistent-tier process state, reconfigured per session (most recent
# wins, like the tracer/pipeline chokepoints). Under _LOCK.
_PERSIST: Dict = {"dir": None, "wired_xla": False}


def machine_fingerprint() -> str:
    """Stable id for 'programs compiled here run here' (XLA:CPU bakes host
    CPU features into generated code; a foreign cache recompiles or
    SIGILLs)."""
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
            from ..memory.retry import wrap_jit
            current[0] = wrap_jit(jax.jit(builder()))
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
            with get_tracer().span("dispatch", "dispatch",
                                   on=(args, kwargs), program=program):
                return fn(*args, **kwargs)
        t0 = time.perf_counter()
        with get_tracer().span("dispatch", "dispatch", on=(args, kwargs),
                               program=program), \
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
        _attribute(M.COMPILE_CACHE_HITS)
        return fn
    _attribute(M.COMPILE_CACHE_MISSES)
    built = _build_entry(key, named_builder, donate_argnums, name)
    with _LOCK:
        return _CACHE.setdefault(key, built)


def _build_entry(key: str, builder: Callable[[], Callable], donate_argnums,
                 name: str) -> Callable:
    """A fresh cache entry over ``builder`` (already named). The OOM
    wrapper sits directly on the jitted callable and ``_time_first_call``
    outermost, so the ``dispatch`` span covers recovery too."""
    from ..memory.retry import wrap_jit, wrap_jit_donating
    if donate_argnums is None:
        return _time_first_call(key, _rebuild_on_mismatch(
            builder, wrap_jit(jax.jit(builder()))), builder, name)
    # donating entries get NO call-again recovery with the SAME args (the
    # failed dispatch may have consumed the donated input); the donating
    # ladder re-materializes from the retained host origin instead, or
    # spills-and-raises structured when there is none
    return _time_first_call(key, wrap_jit_donating(
        jax.jit(builder(), donate_argnums=donate_argnums)), builder, name)


#: AOT executables of the mesh programs (exec/mesh.py's stage,
#: shuffle/ici.py's all-to-all), by (program name, the caller's semantic
#: key). They are lowered and compiled ahead of the call so that the
#: one-time XLA build is its own ``compile`` span and never reads as
#: collective wall. Bounded LRU: shapes are bucketed upstream (quota
#: bucketing, exec/exchange.py), so a handful of entries covers a run.
_AOT: "OrderedDict[tuple, object]" = OrderedDict()
_AOT_MAX = 64


def aot_program(key: tuple, build: Callable[[], Callable], args: tuple, *,
                name: str) -> Tuple[object, bool]:
    """The compiled executable of program ``name`` for ``key``, and whether
    it was compiled by this call. On a miss ``build()`` returns the
    ``named_jit`` function, which is lowered at ``args`` and compiled under
    a ``compile`` span; the caller wraps its own ``dispatch`` span around
    each call of the executable (``cached_jit`` would nest the one inside
    the other)."""
    from .tracing import get_tracer
    key = (name, key)
    with _LOCK:
        prog = _AOT.get(key)
        if prog is not None:
            _AOT.move_to_end(key)
            return prog, False
    with get_tracer().span("compile", "compile",
                           program=_PROGRAM_PREFIX + name):
        prog = build().lower(*args).compile()
    with _LOCK:
        _AOT[key] = prog
        while len(_AOT) > _AOT_MAX:
            _AOT.popitem(last=False)
    return prog, True


def cache_stats() -> Dict[str, float]:
    # snapshot under _LOCK: the pipeline task pool compiles concurrently,
    # and a lock-free multi-field read can tear (hits from one moment,
    # compiles from another) — stats consumers diff these across queries
    with _LOCK:
        return {"entries": len(_CACHE), "hits": _HITS, "misses": _MISSES,
                "compiles": _COMPILES,
                "compile_seconds": round(_COMPILE_SECONDS, 6)}


def clear_cache():
    global _HITS, _MISSES, _COMPILES, _COMPILE_SECONDS
    with _LOCK:
        _CACHE.clear()
        _AOT.clear()
        _KERNELS.clear()
        _HITS = _MISSES = 0
        _COMPILES = 0
        _COMPILE_SECONDS = 0.0


# ---------------------------------------------------------------------------
# persistent tier: where XLA's cache lives
# ---------------------------------------------------------------------------
def persistent_cache_dir() -> Optional[str]:
    """The directory of the engine's own files beside XLA's cache
    (exec/fallback.py keeps quarantine.json there), or None."""
    with _LOCK:
        return _PERSIST["dir"]


def configure_compile_cache(conf) -> Optional[str]:
    """Apply spark.rapids.tpu.compile.* (called from TpuSession.__init__,
    most recent session wins): find the cache directory (module docstring,
    tier 2) and point XLA's persistent cache at it. Returns the directory
    of the engine's own files, or None when the tier is off. Whatever an
    older version left there (its manifest, exports/) is neither read
    nor deleted."""
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
        os.makedirs(tier, exist_ok=True)
        if xla_dir is not None:
            os.makedirs(xla_dir, exist_ok=True)
    except OSError as e:
        import warnings
        warnings.warn(f"persistent compile cache disabled: cannot create "
                      f"{tier!r} ({e})", RuntimeWarning)
        _persist_off()
        return None
    # min_compile_time 0 — a cache dir was asked for, so persist everything
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if xla_dir is not None:
        jax.config.update("jax_compilation_cache_dir", xla_dir)
    with _LOCK:
        _PERSIST["dir"] = tier
        _PERSIST["wired_xla"] = xla_dir is not None
    return tier


def _persist_off() -> None:
    """Tier off for the most recent session: it owns the chokepoint, so
    un-wire the XLA disk cache an earlier session wired — never one the
    environment placed."""
    with _LOCK:
        unwire = _PERSIST["wired_xla"]
        _PERSIST["dir"] = None
        _PERSIST["wired_xla"] = False
    if unwire:
        jax.config.update("jax_compilation_cache_dir", None)
