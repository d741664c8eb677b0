#!/usr/bin/env python
"""Benchmark driver (BASELINE.md ladder) — parent/worker edition.

An XLA compile can hang *indefinitely* with the GIL held, so no signal
handler in that process can ever run. Hence:

  * The PARENT process never imports jax. It orchestrates killable worker
    subprocesses and is therefore always able to emit the summary line.
    It also never holds the chip: the probe child has exited before the
    first worker starts, and one worker runs at a time.
  * Each phase runs in a WORKER subprocess that appends one JSON line per
    event (query start / done / error) to a shared JSONL file. The parent
    applies a per-query watchdog: a worker that makes no progress for
    BENCH_QUERY_TIMEOUT_S is killed and the hung query is skipped on the
    next worker attempt.
  * No chip, no result: without a TPU and without an explicit
    BENCH_PLATFORM=cpu the bench exits non-zero, and it never moves to
    the CPU after a failure. Every metric names the backend it ran on.
  * EXACTLY ONE summary JSON line lands on stdout no matter what — normal
    return, exception, SIGTERM, or internal alarm all funnel into _emit().
  * The persistent XLA compile cache makes warm-cache runs cheap: it lives
    where JAX_COMPILATION_CACHE_DIR says, else in the fixed
    <checkout>/.jax_compile_cache.

Phases (budget permitting, results accumulate):
  1. smoke  — Q1+Q6 vs a raw pandas baseline (ladder step 1).
  2. tpch22 — all 22 TPC-H queries, device engine vs the host engine,
     correctness asserted (ladder step 2). Q6,Q1 first, then the rest.
  3. ablation — Q1+Q6 under feature flags for attribution.

Summary line: {"metric": ..., "value": geomean_speedup_x, "unit": "x",
"vs_baseline": ...}; vs_baseline = speedup / 4.0 (reference's "4x typical"
claim, reference docs/FAQ.md:100-106).

Env knobs: BENCH_MODE (auto|tpch22|q1q6), BENCH_SF, BENCH_SMOKE_SF,
BENCH_PARTITIONS, BENCH_BUDGET_S, BENCH_PROBE_BUDGET_S, BENCH_PLATFORM
(cpu runs on the CPU backend, by request only), BENCH_QUERY_TIMEOUT_S,
BENCH_ABLATION, BENCH_PIPELINE (on|off A/B knob for the pipelined
executor, spark.rapids.tpu.pipeline.enabled; recorded in the bench JSON),
BENCH_HEALTH (1|0: live health monitor per phase — /status snapshot +
peak HBM watermark into the bench JSON, stall forensics appended to
diagnose.txt), BENCH_STALL_TIMEOUT_S (watchdog threshold),
BENCH_WARM=restart (cold-process re-run phase: after smoke populates the
persistent compile tier, a FRESH worker process replays Q6+Q1 through the
warm pool and records its second-run compile count — the zero-compiles
trajectory metric, "restart" + per-phase "compile_cache" in the JSON),
BENCH_TRACE (1|0: span tracer per timed phase — each query's res gains a
"critical_path" category breakdown + "sync_wait_frac", the measured
ROADMAP-item-1 trajectory number), BENCH_MEMPROF (1|0, default on: the
memory flight recorder per phase — each query's res gains
"peak_hbm_bytes" + "spill_bytes" and the phase gains a "memory" summary
with peak holders-by-operator / leak / postmortem counts in the bench
JSON; tools/compare.py diffs the per-query numbers across rounds and
gates >10% peak-HBM growth), BENCH_HISTORY (1|0, default on: each
phase's run lands in the persistent history store (.bench_history/,
override with BENCH_HISTORY_DIR) and the regression sentinel
(tools/history.py) compares it against the previous round's pinned
baseline — wall/critical-path/memory plus the sync-count and
compile-count gates — writing a "history" verdict per phase into the
bench JSON and pinning this run as the next round's baseline),
BENCH_CHAOS (1 opt-in: recovery-parity phase — each query runs twice on
a 2-worker ProcessCluster, clean then under a deterministic worker-kill
fault spec; the chaos answer must match the clean answer and the
driver's recovery ledger must show the kill actually landed, recorded
as "chaos" in the bench JSON with the recovery overhead;
BENCH_CHAOS_SF scales the data), and the history sentinel treats a
recovered-but-correct chaos run as clean (run_sentinel exempts queries
whose event log carries fault records and no error).
BENCH_OOM (1 opt-in: pressure-parity phase — each query first runs
clean to record its reference answer and the clean-run peak-HBM
watermark, then re-runs in a fresh session whose device pool is capped
at BENCH_OOM_FRAC (default 0.40) of that peak in strict mode, plus a
deterministic times-bounded alloc.jit OOM fault spec; the pressured
answer must match the clean answer and the memory/retry.py ladder
counters must show nonzero oom_retries + oom_splits, recorded as "oom"
in the bench JSON with per-query retry/split/spill deltas;
BENCH_OOM_SF scales the data, and the history sentinel treats a
recovered run as clean — run_sentinel exempts queries whose event log
carries oom_retry records and no error).
BENCH_FALLBACK (1 opt-in: degradation-parity phase — each query first
runs clean to record its reference answer, then re-runs in a fresh
session under a deterministic times-bounded alloc.jit:action=fatal
spec (a NON-retryable XLA failure the ladder refuses to retry); the
degraded answer must match the clean answer and the exec/fallback.py
counters must show nonzero host_fallbacks, recorded as "fallback" in
the bench JSON with per-query fallback counts, transfer bytes and
overhead; BENCH_FALLBACK_SF scales the data, and the history sentinel
treats a fallback-recovered run as clean — run_sentinel exempts
queries whose event log carries schema-v10 fallback records and no
error).
BENCH_SHUFFLE (1|0, default on: the shuffle observatory
(shuffle/telemetry.py) per phase — each query's res gains
"shuffle_wall_s" + "shuffle_wall_frac" + "wire_bytes" and the event
log gets real v12 shuffle_summary payloads; tools/compare.py diffs the
per-query numbers across rounds and gates >10% shuffle-wall / wire-byte
growth).
`bench.py --multichip [out.json]` is a separate parent mode, a CPU
rehearsal of the mesh path (its JSON says "backend": "cpu-virtual"; the
default file is MULTICHIP_cpu-virtual.json): it runs q3/q5/q7 on an
BENCH_MULTICHIP_DEVICES (default 8) virtual-device CPU mesh — the ICI
all-to-all shuffle tier — and writes per-query wall, shuffle wall,
per-tier transfer breakdown, wire bytes and straggler stats to the
JSON. On a per-query timeout (BENCH_MULTICHIP_QUERY_TIMEOUT_S,
in-worker alarm) or worker death the JSON carries the partial per-query
results plus the observatory's forensics ring for the failed query —
never an opaque {rc, tail} stub. BENCH_MESH=on|off (default on) sets
mesh-parallel stage execution (exec/mesh.py) for the headline arm, and
after the headline runs a second eventlog-free session measures each
query with the mesh stage OFF then ON (warm collect, then timed) — the
A/B lands in each query's "mesh_ab".
"""
import atexit
import json
import math
import os
import signal
import subprocess
import sys
import time

_T_START = time.monotonic()
_WALL_START = time.time()  # for filtering files produced by THIS run
_REPO = os.path.dirname(os.path.abspath(__file__))
_PARTIAL_PATH = os.path.join(_REPO, "BENCH_partial.json")

_STATE = {
    "emitted": False,
    "backend": None,
    "smoke": {},
    "tpch": {},
    "errors": {},
    "ablation": {},
    "restart": {},
    "chaos": {},      # query -> clean-vs-injected parity + recovery ledger
    "multichip": {},  # query -> mesh wall + shuffle tier breakdown
    "multichip_forensics": {},  # query -> timeout/crash observatory dump
    "oom": {},        # query -> pressure-vs-clean parity + retry ladder deltas
    "fallback": {},   # query -> degraded-vs-clean parity + fallback counters
    "compile_cache": {},   # phase -> cache_stats() snapshot
    "sf": None,
    "rows": None,
    "eventlog": {},   # phase -> event-log directory
    "health": {},     # phase -> /status snapshot + peak HBM watermark
    "memory": {},     # phase -> memory flight-recorder summary
    "history": {},    # phase -> history-store sentinel verdict
    "pipeline": os.environ.get("BENCH_PIPELINE", "on"),  # A/B knob
    "analyze": {},    # srtpu-analyze baseline summary (sync-site debt)
    "notes": [],
}


def _load_analyze_summary():
    """The committed srtpu-analyze baseline summary, read as plain JSON
    (the parent process must never import jax, so no tools.analyze
    import). Sync-site count lands in the bench JSON as a tracked
    trajectory metric next to the measured sync waits."""
    path = os.path.join(_REPO, "spark_rapids_tpu", "tools", "analyze",
                        "baseline.json")
    try:
        with open(path) as f:
            data = json.load(f)
        return {"initial_inventory": data.get("initial_inventory", {}),
                "summary": data.get("summary", {})}
    except (OSError, ValueError):
        return {}


def _log(msg):
    print(f"# {msg}", file=sys.stderr, flush=True)


def _budget_s() -> float:
    return float(os.environ.get("BENCH_BUDGET_S", "840"))


def _remaining() -> float:
    return _budget_s() - (time.monotonic() - _T_START)


def _write_partial():
    tmp = _PARTIAL_PATH + ".tmp"
    with open(tmp, "w") as f:
        json.dump({k: _STATE[k] for k in
                   ("backend", "sf", "rows", "smoke", "tpch",
                    "ablation", "restart", "chaos", "oom", "fallback",
                    "compile_cache", "errors", "eventlog",
                    "health", "memory", "history", "pipeline", "analyze",
                    "notes")}
                  | {"elapsed_s": round(time.monotonic() - _T_START, 2)},
                  f, indent=1)
    os.replace(tmp, _PARTIAL_PATH)


def _geomean(d):
    vals = [v["speedup"] for v in d.values() if v.get("speedup", 0) > 0]
    if not vals:
        return 0.0
    return math.exp(sum(math.log(v) for v in vals) / len(vals))


def _emit(reason=""):
    if _STATE["emitted"]:
        return
    try:
        old_mask = signal.pthread_sigmask(
            signal.SIG_BLOCK, {signal.SIGTERM, signal.SIGALRM})
    except (AttributeError, ValueError):
        old_mask = None
    try:
        if _STATE["emitted"]:
            return
        _STATE["emitted"] = True
        _emit_locked(reason)
    finally:
        if old_mask is not None:
            signal.pthread_sigmask(signal.SIG_SETMASK, old_mask)


def _emit_locked(reason):
    # a number is only ever reported under the backend that produced it
    suffix = f"_{_STATE['backend'] or 'nobackend'}"
    if _STATE["tpch"]:
        geo = _geomean(_STATE["tpch"])
        n = len(_STATE["tpch"])
        partial = "" if n == 22 else f"_partial{n}"
        sf = _STATE["sf"] or 0
        metric = (f"tpch22_sf{sf:g}_rows{_STATE['rows']}"
                  f"_geomean_speedup_vs_hostengine{partial}{suffix}")
    elif _STATE["smoke"]:
        geo = _geomean(_STATE["smoke"])
        metric = f"tpch_q1_q6_smoke_geomean_speedup_vs_pandas{suffix}"
    else:
        geo = 0.0
        metric = "bench_no_queries_completed" + suffix
        if reason:
            metric += f"_{reason}"
    print(json.dumps({
        "metric": metric,
        "value": round(geo, 4),
        "unit": "x",
        "vs_baseline": round(geo / 4.0, 4),
    }), flush=True)
    if reason:
        _log(f"summary emitted ({reason}) at t={time.monotonic()-_T_START:.0f}s")
    try:
        _write_partial()
    except Exception:
        pass


_ACTIVE_WORKER = []          # parent-side: Popen of the worker in flight


def _on_signal(signum, frame):
    _log(f"caught signal {signum}; emitting summary from partial results")
    for proc in _ACTIVE_WORKER:  # don't leak a jax process holding the
        try:                     # chip: it belongs to one process at a time
            proc.kill()
        except Exception:
            pass
    _emit(reason=f"sig{signum}")
    os._exit(0)


def _install_emit_guards():
    atexit.register(_emit)
    signal.signal(signal.SIGTERM, _on_signal)
    signal.signal(signal.SIGALRM, _on_signal)


# ---------------------------------------------------------------------------
# parent: orchestration
# ---------------------------------------------------------------------------

_TPCH_ORDER = [6, 1] + [i for i in range(1, 23) if i not in (1, 6)]


def _probe_tpu(timeout_s: float) -> bool:
    """One patient probe in a killable subprocess: init + tiny matmul.

    The matmul matters: backend init can succeed while the first real
    dispatch hangs. subprocess.run returns only after the child exited,
    so the chip is free again before any worker starts."""
    try:
        r = subprocess.run(
            [sys.executable, "-u", "-c",
             "import jax, jax.numpy as jnp;"
             "x = (jnp.ones((128,128)) @ jnp.ones((128,128)))"
             ".block_until_ready();"
             "print('PROBE_OK', jax.devices()[0].platform)"],
            capture_output=True, text=True, timeout=timeout_s,
            cwd=_REPO)
        out = r.stdout.strip()
        ok = r.returncode == 0 and out.endswith("PROBE_OK tpu")
        if not ok:
            _log(f"tpu probe rc={r.returncode} out={out!r} "
                 f"err_tail={r.stderr[-200:]!r}")
        return ok
    except subprocess.TimeoutExpired:
        _log(f"tpu probe timed out after {timeout_s:.0f}s")
        return False


class _Worker:
    """One phase-worker subprocess + its event-line stream."""

    def __init__(self, phase: str, platform: str, extra_env=None):
        self.phase = phase
        self.out_path = os.path.join(
            _REPO, f".bench_worker_{phase}_{int(time.time()*1000)}.jsonl")
        env = dict(os.environ)
        env["BENCH_WORKER_OUT"] = self.out_path
        env["BENCH_PLATFORM"] = platform
        env.update(extra_env or {})
        self.proc = subprocess.Popen(
            [sys.executable, "-u", __file__, "--worker", phase],
            env=env, cwd=_REPO, stdout=subprocess.DEVNULL)
        _ACTIVE_WORKER.append(self.proc)
        self._pos = 0

    def poll_events(self):
        """New JSONL events since last poll."""
        events = []
        try:
            with open(self.out_path) as f:
                f.seek(self._pos)
                for line in f:
                    if not line.endswith("\n"):
                        break  # partial write; re-read next poll
                    self._pos += len(line)
                    try:
                        events.append(json.loads(line))
                    except json.JSONDecodeError:
                        pass
        except FileNotFoundError:
            pass
        return events

    def kill(self):
        try:
            self.proc.kill()
            self.proc.wait(timeout=10)
        except Exception:
            pass

    def cleanup(self):
        try:
            _ACTIVE_WORKER.remove(self.proc)
        except ValueError:
            pass
        try:
            os.unlink(self.out_path)
        except OSError:
            pass


def _consume(ev):
    """Fold a worker event into _STATE."""
    kind = ev.get("ev")
    if kind == "done":
        _STATE[ev["phase"]][ev["name"]] = ev["res"]
    elif kind == "error":
        _STATE["errors"][ev["name"]] = ev["msg"]
    elif kind == "meta":
        for k in ("sf", "rows"):
            if k in ev:
                _STATE[k] = ev[k]
        if "compile_cache" in ev:
            # phase-keyed cache_stats snapshots (incl. the persistent-tier
            # persist_* hit/miss counters)
            _STATE["compile_cache"].update(ev["compile_cache"])
        if "eventlog" in ev:
            _STATE["eventlog"].update(ev["eventlog"])
        if "health" in ev:
            _STATE["health"].update(ev["health"])
        if "memory" in ev:
            _STATE["memory"].update(ev["memory"])
        if "history" in ev:
            _STATE["history"].update(ev["history"])
        if "multichip_forensics" in ev:
            _STATE["multichip_forensics"].update(ev["multichip_forensics"])
    elif kind == "ablation":
        _STATE["ablation"][ev["name"]] = ev["res"]
    _write_partial()


def _run_phase(phase: str, platform: str, queries, query_timeout: float,
               extra_env=None):
    """Run one phase worker under the per-query watchdog.

    Returns (status, current) — status one of "clean" (rc=0), "crashed"
    (nonzero exit), "hung" (watchdog kill; current = query in flight or
    None for a startup hang), "budget" (global budget kill)."""
    env = dict(extra_env or {})
    if queries is not None:
        env["BENCH_WORKER_QUERIES"] = ",".join(str(q) for q in queries)
    w = _Worker(phase, platform, env)
    current = None          # query in flight
    last_progress = time.monotonic()
    try:
        while True:
            events = w.poll_events()
            for ev in events:
                if ev.get("ev") == "start":
                    current = ev["name"]
                else:
                    _consume(ev)
                    if ev.get("ev") in ("done", "error"):
                        current = None
            if events:
                last_progress = time.monotonic()
            rc = w.proc.poll()
            if rc is not None:
                for ev in w.poll_events():
                    if ev.get("ev") == "start":
                        current = ev["name"]
                    else:
                        _consume(ev)
                        if ev.get("ev") in ("done", "error"):
                            current = None
                if rc == 0:
                    return "clean", None
                _log(f"{phase}: worker died rc={rc} on "
                     f"{current or 'startup'}")
                _STATE["notes"].append(f"worker_crash_{phase}_rc{rc}")
                if current:
                    _STATE["errors"].setdefault(
                        current, f"worker crashed rc={rc}")
                return "crashed", current
            if _remaining() < 30:
                _log(f"{phase}: budget exhausted, killing worker")
                _STATE["notes"].append(f"budget_kill_{phase}")
                w.kill()
                return "budget", current
            if time.monotonic() - last_progress > query_timeout:
                _log(f"{phase}: watchdog fired on {current or 'startup'} "
                     f"after {query_timeout:.0f}s; killing worker")
                _STATE["notes"].append(
                    f"watchdog_{phase}_{current or 'startup'}")
                if current:
                    _STATE["errors"][current] = \
                        f"hung > {query_timeout:.0f}s (watchdog kill)"
                w.kill()
                return "hung", current
            time.sleep(0.5)
    finally:
        w.cleanup()


def main():
    _install_emit_guards()
    signal.alarm(max(int(_budget_s()) + 20, 30))
    _silence_xla_cpu_noise()  # probes/workers inherit the env

    def refuse(code, msg):
        _log(msg)
        _STATE["emitted"] = True   # nothing ran: no summary line
        sys.exit(code)

    platform = os.environ.get("BENCH_PLATFORM", "")
    if platform not in ("", "cpu", "tpu"):
        refuse(2, f"BENCH_PLATFORM={platform!r}: must be cpu or tpu")
    if platform != "cpu":
        probe_budget = float(os.environ.get(
            "BENCH_PROBE_BUDGET_S", str(min(300.0, _budget_s() * 0.35))))
        if not _probe_tpu(timeout_s=max(probe_budget, 30.0)):
            refuse(3, "no TPU answered the probe (no chip, no result); set "
                      "BENCH_PLATFORM=cpu to run on the CPU backend on "
                      "purpose")
        platform = "tpu"
    _STATE["backend"] = platform
    _STATE["analyze"] = _load_analyze_summary()
    _log(f"backend={platform} budget={_budget_s():.0f}s")
    _write_partial()

    qt = float(os.environ.get(
        "BENCH_QUERY_TIMEOUT_S", "300" if platform == "tpu" else "180"))
    mode = os.environ.get("BENCH_MODE", "auto")

    def _drop_through(remaining, name):
        """Remove queries up to and including the one the worker reported
        as ``name`` ("q6" -> 6); already-completed ones were consumed via
        their done/error events, so dropping the prefix is lossless."""
        if remaining is None or name is None:
            return remaining
        try:
            qid = int(str(name).lstrip("q"))
        except ValueError:
            return remaining
        if qid not in remaining:
            return remaining
        return remaining[remaining.index(qid) + 1:]

    def phase_with_retries(phase, queries):
        """Run a phase, skipping hung/crashing queries; gives up after
        three failed workers. Never changes backend."""
        remaining = list(queries) if queries is not None else None
        failures = 0
        while _remaining() > 60 and failures < 3:
            status, current = _run_phase(phase, platform, remaining, qt)
            if status in ("clean", "budget"):
                return
            failures += 1
            remaining = _drop_through(remaining, current)
            if remaining is not None and not remaining:
                return

    if mode in ("auto", "q1q6"):
        phase_with_retries("smoke", [6, 1])
        if os.environ.get("BENCH_WARM", "") == "restart" \
                and _remaining() > 60:
            # cold-process re-run: the smoke worker exited, so this phase
            # measures second-run compiles across a real process boundary
            phase_with_retries("restart", [6, 1])
    if mode in ("auto", "tpch22") and _remaining() > 60:
        phase_with_retries("tpch", _TPCH_ORDER)
    if os.environ.get("BENCH_ABLATION", "1") != "0" and _remaining() > 120:
        phase_with_retries("ablation", None)
    if os.environ.get("BENCH_CHAOS", "0") == "1" and _remaining() > 120:
        phase_with_retries("chaos", [1, 3])
    if os.environ.get("BENCH_OOM", "0") == "1" and _remaining() > 120:
        phase_with_retries("oom", [1, 6])
    if os.environ.get("BENCH_FALLBACK", "0") == "1" and _remaining() > 120:
        phase_with_retries("fallback", [1, 6])
    _emit(reason="done")


# ---------------------------------------------------------------------------
# worker: actual query execution (imports jax; may hang; parent kills us)
# ---------------------------------------------------------------------------

class _EventSink:
    def __init__(self):
        self.path = os.environ["BENCH_WORKER_OUT"]

    def emit(self, **ev):
        with open(self.path, "a") as f:
            f.write(json.dumps(ev) + "\n")
            f.flush()
            os.fsync(f.fileno())


def _silence_xla_cpu_noise():
    """Silence the XLA:CPU machine-feature-mismatch warning (persistent
    compile-cache entries built on a different host spam one line per
    load) via the logging flag, not log scraping. Must run BEFORE jax
    initializes its C++ logging: worker processes call it ahead of their
    jax import, and the parent (which never imports jax) calls it so
    probe/worker subprocesses inherit the env. BENCH_XLA_LOG overrides."""
    os.environ.setdefault(
        "TF_CPP_MIN_LOG_LEVEL", os.environ.get("BENCH_XLA_LOG", "2"))
    import logging
    logging.getLogger("jax._src.compilation_cache").setLevel(logging.ERROR)


def _worker_setup_jax():
    _silence_xla_cpu_noise()
    import jax
    plat = os.environ.get("BENCH_PLATFORM")
    if plat == "cpu":
        jax.config.update("jax_platforms", "cpu")
    return jax


def _compile_cache_conf() -> dict:
    """Persistent-compile-tier session conf: XLA's cache, the
    plan-signature manifest and the warm pool live under the fixed,
    git-ignored <checkout>/.jax_compile_cache unless
    JAX_COMPILATION_CACHE_DIR places them (then the engine keeps
    everything there and ignores this; utils/compile_cache.py)."""
    return {"spark.rapids.tpu.compile.cacheDir":
            os.path.join(_REPO, ".jax_compile_cache")}


def _write_diagnose_report(phase: str):
    """Run the auto-diagnosis tool over this phase's event logs and write
    the ranked bottleneck report next to them
    (.bench_eventlogs/<phase>/diagnose.txt) — every BENCH round carries its
    own per-query (node, metric) attribution, not just timings. Any
    watchdog stall forensics (stall-<ts>.txt, written by the health
    monitor into the same directory) are appended so a hung round
    explains itself."""
    d = os.path.join(
        os.environ.get("BENCH_EVENTLOG_DIR",
                       os.path.join(_REPO, ".bench_eventlogs")), phase)
    try:
        import glob as _glob

        chunks = []
        if os.environ.get("BENCH_EVENTLOG", "1") != "0":
            from spark_rapids_tpu.tools.diagnose import diagnose_path
            logs = sorted(_glob.glob(os.path.join(d, "*.jsonl")))
            chunks = [diagnose_path(p).summary() for p in logs]
        # stall forensics come from the health monitor (BENCH_HEALTH),
        # which runs independently of the event-log knob; mtime filter
        # keeps a previous round's stall files out of THIS round's report
        if os.environ.get("BENCH_HEALTH", "1") != "0":
            for sp in sorted(_glob.glob(os.path.join(d, "stall-*.txt"))):
                if os.path.getmtime(sp) < _WALL_START:
                    continue
                with open(sp, encoding="utf-8") as f:
                    chunks.append(f"== stall forensics: "
                                  f"{os.path.basename(sp)} ==\n" + f.read())
        if not chunks:
            return
        out = os.path.join(d, "diagnose.txt")
        with open(out, "w", encoding="utf-8") as f:
            f.write("\n\n".join(chunks) + "\n")
        _log(f"{phase}: diagnose report -> {out}")
    except Exception as e:  # report generation must never fail the bench
        _log(f"{phase}: diagnose report failed: {type(e).__name__}: {e}")


def _eventlog_conf(phase: str, sink=None) -> dict:
    """Per-run event log (BENCH trajectory gains per-operator attribution:
    replay with tools/eventlog.py, diff rounds with tools/compare.py).
    BENCH_EVENTLOG=0 disables; BENCH_EVENTLOG_DIR overrides the location."""
    if os.environ.get("BENCH_EVENTLOG", "1") == "0":
        return {}
    d = os.path.join(
        os.environ.get("BENCH_EVENTLOG_DIR",
                       os.path.join(_REPO, ".bench_eventlogs")), phase)
    if sink is not None:
        sink.emit(ev="meta", eventlog={phase: d})
    return {"spark.rapids.tpu.eventLog.dir": d}


def _history_conf(phase: str) -> dict:
    """Persistent cross-run history store (tools/history.py): with this
    conf set, the phase's session appends its run to the store when it
    closes; _bench_sentinel then gates it against the previous round.
    Per-phase subdirectories keep smoke rounds comparing against smoke
    rounds. BENCH_HISTORY=0 disables; BENCH_HISTORY_DIR relocates."""
    if os.environ.get("BENCH_HISTORY", "1") == "0":
        return {}
    d = os.environ.get("BENCH_HISTORY_DIR",
                       os.path.join(_REPO, ".bench_history"))
    return {"spark.rapids.tpu.history.dir": os.path.join(d, phase)}


def _bench_sentinel(sink: "_EventSink", phase: str) -> None:
    """Regression sentinel over the history store: compare the run the
    session just appended on close against the previous round's pinned
    baseline (first round verdict: 'no-baseline'), emit the verdict into
    the bench JSON, and pin this run as the next round's baseline.
    Never fails the bench."""
    if os.environ.get("BENCH_HISTORY", "1") == "0":
        return
    try:
        from spark_rapids_tpu.tools.history import (HistoryStore,
                                                    run_sentinel)
        d = os.environ.get("BENCH_HISTORY_DIR",
                           os.path.join(_REPO, ".bench_history"))
        store = HistoryStore(os.path.join(d, phase))
        if not store.apps():  # BENCH_EVENTLOG=0: session had no log
            return
        verdict = run_sentinel(store)
        cand = verdict.get("candidate")
        store.pin_baseline(cand)
        sink.emit(ev="meta", history={phase: {
            "store": store.root, "candidate": cand,
            "baseline": verdict.get("baseline"),
            "status": verdict.get("status"), "ok": verdict.get("ok"),
            "flags": verdict.get("flags", [])}})
        _log(f"{phase}: sentinel {verdict.get('status')}"
             + (f" vs {verdict['baseline']}" if verdict.get("baseline")
                else ""))
    except Exception as e:  # the sentinel must never fail the bench
        _log(f"{phase}: history sentinel failed: {type(e).__name__}: {e}")


def _pipeline_conf() -> dict:
    """BENCH_PIPELINE=on|off A/B knob -> session conf (default on)."""
    return {"spark.rapids.tpu.pipeline.enabled":
            os.environ.get("BENCH_PIPELINE", "on") != "off"}


def _trace_conf() -> dict:
    """Enable the span tracer so every timed query carries a
    critical-path breakdown (sync_wait_frac is a tracked trajectory
    number — ROADMAP item 1). BENCH_TRACE=0 disables."""
    if os.environ.get("BENCH_TRACE", "1") == "0":
        return {}
    return {"spark.rapids.tpu.trace.enabled": True}


def _movement_conf() -> dict:
    """Enable the data-movement observatory so every timed query's res
    carries its transfer totals (D2H/H2D bytes, blocking syncs, round
    trips) and the event log gets real v11 movement_summary payloads.
    BENCH_MOVEMENT=0 disables."""
    if os.environ.get("BENCH_MOVEMENT", "1") == "0":
        return {}
    return {"spark.rapids.tpu.movement.enabled": True}


def _movement_probe() -> dict:
    """Snapshot of the process-wide movement-ledger totals ({} when the
    observatory is off) — diff two around a timed run for that run's
    transfer cost. Carries a per-site wall snapshot under "_site_wall"
    so the res can name the heaviest ledger funnel (the sync-wait
    gate's attribution). Never fails the bench."""
    try:
        from spark_rapids_tpu.utils.movement import active, movement_stats
        stats = dict(movement_stats())
        led = active()
        if stats and led is not None:
            stats["_site_wall"] = {r["site"]: float(r["wall_s"])
                                   for r in led.site_aggregate()}
        return stats
    except Exception:
        return {}


def _movement_res(before: dict) -> dict:
    """Movement-total deltas across one timed run, keyed the way
    tools/compare.py's bench transfer-byte gate reads them; {} when the
    observatory is off. "sync_top_site" names the ledger funnel that
    held the most wall during the run — the site tools/compare.py's
    sync-wait gate points at when sync_wait_frac trips it."""
    after = _movement_probe()
    if not after or not before:
        return {}
    sites_a = before.get("_site_wall") or {}
    sites_b = after.get("_site_wall") or {}
    deltas = {s: w - sites_a.get(s, 0.0) for s, w in sites_b.items()
              if w - sites_a.get(s, 0.0) > 0.0}
    top = max(deltas.items(), key=lambda kv: kv[1])[0] if deltas else ""
    res = {"d2h_bytes": int(after.get("d2h_bytes", 0)
                            - before.get("d2h_bytes", 0)),
           "h2d_bytes": int(after.get("h2d_bytes", 0)
                            - before.get("h2d_bytes", 0)),
           "blocking_syncs": int(after.get("blocking_count", 0)
                                 - before.get("blocking_count", 0)),
           "round_trips": int(after.get("round_trips", 0)
                              - before.get("round_trips", 0))}
    if top:
        res["sync_top_site"] = top
    return res


def _shuffle_conf() -> dict:
    """Enable the shuffle observatory so every timed query's res carries
    its shuffle cost (shuffle wall, wire bytes) and the event log gets
    real v12 shuffle_summary payloads. BENCH_SHUFFLE=0 disables."""
    if os.environ.get("BENCH_SHUFFLE", "1") == "0":
        return {}
    return {"spark.rapids.tpu.shuffle.telemetry.enabled": True}


def _shuffle_probe() -> dict:
    """Snapshot of the process-wide shuffle-observatory totals ({} when
    the observatory is off) — diff two around a timed run for that run's
    shuffle cost. Never fails the bench."""
    try:
        from spark_rapids_tpu.shuffle.telemetry import active
        obs = active()
        return dict(obs.totals()) if obs is not None else {}
    except Exception:
        return {}


def _shuffle_res(before: dict, wall_s: float) -> dict:
    """Shuffle-total deltas across one timed run, keyed the way
    tools/compare.py's bench shuffle gate reads them ("shuffle_wall_s" +
    "wire_bytes"); {} when the observatory is off. "shuffle_wall_frac"
    is the run's shuffle wall over its total wall — the ROADMAP item 3
    trajectory number."""
    after = _shuffle_probe()
    if not after or not before:
        return {}
    sh_wall = float(after.get("wall_s", 0.0) - before.get("wall_s", 0.0))
    return {
        "shuffle_wall_s": round(sh_wall, 4),
        "shuffle_wall_frac": round(sh_wall / wall_s, 4)
        if wall_s > 0 else 0.0,
        "wire_bytes": int(after.get("wire_bytes", 0)
                          - before.get("wire_bytes", 0)),
    }


def _bench_critical_path():
    """Critical-path breakdown of the NEWEST query span in the live
    tracer ring (the query the caller just timed): category seconds +
    sync_wait_frac, or None when tracing is off. Never fails the bench."""
    try:
        from spark_rapids_tpu.tools.trace import critical_path_from_tracer
        from spark_rapids_tpu.utils.tracing import get_tracer
        tracer = get_tracer()
        if not tracer.enabled:
            return None
        tid = None
        for e in tracer.events():
            if e.cat == "query" and "trace_id" in e.args:
                tid = e.args["trace_id"]
        if tid is None:
            return None
        cp = critical_path_from_tracer(tracer, tid)
        if cp is None:
            return None
        d = cp.to_dict()
        return {"sync_wait_frac": d["sync_wait_frac"],
                "categories_s": d["categories_s"],
                "coverage": d["coverage"],
                "total_s": d["total_s"]}
    except Exception:
        return None


def _health_conf(phase: str) -> dict:
    """Enable the live health monitor per phase: heartbeats land in the
    phase event log, stall forensics land next to it (appended to
    diagnose.txt), and the end-of-phase /status snapshot + peak HBM
    watermark land in the bench JSON. BENCH_HEALTH=0 disables."""
    if os.environ.get("BENCH_HEALTH", "1") == "0":
        return {}
    d = os.path.join(
        os.environ.get("BENCH_EVENTLOG_DIR",
                       os.path.join(_REPO, ".bench_eventlogs")), phase)
    return {"spark.rapids.tpu.health.enabled": True,
            "spark.rapids.tpu.health.intervalMs": 500,
            "spark.rapids.tpu.health.stallTimeout": float(os.environ.get(
                "BENCH_STALL_TIMEOUT_S", "120")),
            "spark.rapids.tpu.health.reportDir": d}


def _emit_health_snapshot(sink: "_EventSink", phase: str, sess) -> None:
    """Capture the live /status snapshot + peak HBM watermark for the
    bench JSON (never fails the bench)."""
    if os.environ.get("BENCH_HEALTH", "1") == "0":
        return
    try:
        snap = sess.health_status()
        cat = snap.get("catalog") or {}
        sink.emit(ev="meta", health={phase: {
            "peak_device_bytes": cat.get("device_peak_bytes", 0),
            "device_limit_bytes": cat.get("device_limit_bytes", 0),
            "stalls_detected": snap.get("stalls_detected", 0),
            "status": snap}})
    except Exception as e:
        _log(f"{phase}: health snapshot failed: {type(e).__name__}: {e}")


def _memprof_conf() -> dict:
    """BENCH_MEMPROF=1|0 -> memory flight recorder session conf (default
    on; the recorder's engine default is also on, so =0 is the explicit
    overhead-measurement off-switch)."""
    return {"spark.rapids.tpu.memory.profile.enabled":
            os.environ.get("BENCH_MEMPROF", "1") != "0"}


def _mem_probe():
    """Cumulative catalog memory counters (process-wide, monotonic) for
    per-query deltas. None when profiling is off or the engine has no
    catalog yet — memory probing must never fail the bench."""
    if os.environ.get("BENCH_MEMPROF", "1") == "0":
        return None
    try:
        from spark_rapids_tpu.memory.catalog import peek_catalog
        cat = peek_catalog()
        if cat is None:
            return None
        return {"peak": cat.peak_device_bytes,
                "spilled": sum(cat.spilled_bytes.values())}
    except Exception:
        return None


def _mem_res(before) -> dict:
    """Per-query memory fields for the bench JSON: the process peak-HBM
    watermark after this query and the bytes spilled while it ran.
    tools/compare.py diffs these across rounds and fails its gate on
    >10% peak growth."""
    after = _mem_probe()
    if after is None:
        return {}
    res = {"peak_hbm_bytes": after["peak"]}
    if before is not None:
        res["spill_bytes"] = after["spilled"] - before["spilled"]
    return res


def _emit_memory_snapshot(sink: "_EventSink", phase: str, sess) -> None:
    """End-of-phase memory flight-recorder summary for the bench JSON:
    peak watermark + holders-by-operator attribution, leak and
    postmortem counts (never fails the bench)."""
    if os.environ.get("BENCH_MEMPROF", "1") == "0":
        return
    try:
        from spark_rapids_tpu.utils.memprof import active
        mp = active()
        if mp is None:
            return
        snap = mp.snapshot()
        sink.emit(ev="meta", memory={phase: {
            "peak_bytes": snap.get("peak_bytes", 0),
            "peak_holders": snap.get("peak_holders", {}),
            "leaks_detected": snap.get("leaks_detected", 0),
            "postmortems": snap.get("postmortems", 0),
            "external_bytes": snap.get("external_bytes", 0),
            "events_recorded": snap.get("events_recorded", 0)}})
    except Exception as e:
        _log(f"{phase}: memory snapshot failed: {type(e).__name__}: {e}")


def _rel_tol() -> float:
    """TPU computes float64 at f32 precision; loosen device-vs-host float
    comparisons there (the reference marks such queries approximate_float)."""
    return 1e-6 if os.environ.get("BENCH_PLATFORM") == "cpu" else 5e-3


def _tables_equal(dev, cpu) -> float:
    import numpy as np
    import pandas as pd
    d = dev.to_pandas()
    c = cpu.to_pandas()
    if len(d) != len(c):
        return float("inf")
    if len(d) == 0:
        return 0.0
    cols = list(d.columns)
    d = d.sort_values(cols).reset_index(drop=True)
    c = c.sort_values(cols).reset_index(drop=True)
    worst = 0.0
    for col in cols:
        dv, cv = d[col], c[col]
        if pd.api.types.is_numeric_dtype(dv) \
                and pd.api.types.is_numeric_dtype(cv):
            dn = dv.to_numpy(dtype=float, na_value=np.nan)
            cn = cv.to_numpy(dtype=float, na_value=np.nan)
            both_nan = np.isnan(dn) & np.isnan(cn)
            denom = np.maximum(np.abs(cn), 1e-9)
            rel = np.where(both_nan, 0.0, np.abs(dn - cn) / denom)
            if np.isnan(rel).any():
                return float("inf")
            worst = max(worst, float(rel.max()) if len(rel) else 0.0)
        else:
            if not (dv.astype(str).values == cv.astype(str).values).all():
                return float("inf")
    return worst


def _worker_smoke(sink: _EventSink):
    import numpy as np
    import pyarrow as pa
    _worker_setup_jax()
    on_cpu = os.environ.get("BENCH_PLATFORM") == "cpu"
    from spark_rapids_tpu.session import TpuSession
    from spark_rapids_tpu.tools import tpch
    default_sf = "0.05" if on_cpu else "0.25"
    sf = float(os.environ.get("BENCH_SMOKE_SF", default_sf))
    rows = int(6_000_000 * sf)
    lineitem = tpch.gen_lineitem(sf, seed=0, rows=rows)
    sess = TpuSession({"spark.rapids.tpu.batchRowsMinBucket": 1 << 18,
                       **_pipeline_conf(),
                       **_compile_cache_conf(),
                       **_eventlog_conf("smoke", sink),
                       **_history_conf("smoke"),
                       **_health_conf("smoke"),
                       **_memprof_conf(),
                       **_movement_conf(),
                       **_shuffle_conf(),
                       **_trace_conf()})
    df = sess.create_dataframe(lineitem, num_partitions=1).cache()
    t = {"lineitem": df}

    pdf = lineitem.to_pandas()
    sd_all = np.asarray(
        lineitem.column("l_shipdate").combine_chunks().cast(pa.int32()))

    def pandas_q6():
        m = ((sd_all >= 8766) & (sd_all < 9131)
             & (pdf["l_discount"] >= 0.05) & (pdf["l_discount"] <= 0.07)
             & (pdf["l_quantity"] < 24.0))
        return (pdf["l_extendedprice"][m] * pdf["l_discount"][m]).sum()

    def pandas_q1():
        sub = pdf[sd_all <= 10471]
        disc_price = sub["l_extendedprice"] * (1.0 - sub["l_discount"])
        charge = disc_price * (1.0 + sub["l_tax"])
        g = sub.assign(disc_price=disc_price, charge=charge) \
            .groupby(["l_returnflag", "l_linestatus"])
        return g.agg(sum_qty=("l_quantity", "sum"),
                     sum_base=("l_extendedprice", "sum"),
                     sum_disc=("disc_price", "sum"),
                     sum_charge=("charge", "sum"),
                     avg_qty=("l_quantity", "mean"),
                     avg_price=("l_extendedprice", "mean"),
                     avg_disc=("l_discount", "mean"),
                     n=("l_quantity", "size")).sort_index()

    queries = os.environ.get("BENCH_WORKER_QUERIES", "6,1").split(",")
    for qn in queries:
        name = f"q{qn}"
        pandas_fn = pandas_q6 if qn == "6" else pandas_q1
        sink.emit(ev="start", name=name)
        try:
            q = getattr(tpch, name)(t)
            t0 = time.perf_counter()
            q.collect(device=True)
            warm = time.perf_counter() - t0
            mb = _mem_probe()
            mv = _movement_probe()
            sh = _shuffle_probe()
            t0 = time.perf_counter()
            dev_res = q.collect(device=True)
            dev_t = time.perf_counter() - t0
            mv_res = _movement_res(mv)
            sh_res = _shuffle_res(sh, dev_t)
            t0 = time.perf_counter()
            exp = pandas_fn()
            cpu_t = time.perf_counter() - t0
            # correctness before reporting
            ok, err = _smoke_check(name, dev_res, exp)
            if not ok:
                sink.emit(ev="error", name=name,
                          msg=f"mismatch rel_err={err:.2e}")
                continue
            cp = _bench_critical_path()
            sink.emit(ev="done", phase="smoke", name=name, res={
                "dev_s": round(dev_t, 4), "cpu_s": round(cpu_t, 4),
                "compile_s": round(warm, 2),
                "speedup": cpu_t / max(dev_t, 1e-9),
                **_mem_res(mb),
                **mv_res,
                **sh_res,
                **({"critical_path": cp,
                    "sync_wait_frac": cp["sync_wait_frac"]}
                   if cp else {})})
            _log(f"smoke {name}: dev={dev_t:.4f}s cpu={cpu_t:.4f}s "
                 f"compile={warm:.1f}s x{cpu_t/dev_t:.2f} rel_err={err:.1e}")
        except Exception as e:
            sink.emit(ev="error", name=name,
                      msg=f"{type(e).__name__}: {e}"[:300])
            _log(f"smoke {name} FAILED: {e}")
    from spark_rapids_tpu.utils.compile_cache import cache_stats
    sink.emit(ev="meta", compile_cache={"smoke": dict(cache_stats())})
    _emit_health_snapshot(sink, "smoke", sess)
    _emit_memory_snapshot(sink, "smoke", sess)
    sess.close()  # flush the event log + persist the compile tier
    _write_diagnose_report("smoke")
    _bench_sentinel(sink, "smoke")


def _smoke_check(name, dev_res, exp):
    import numpy as np
    if name == "q6":
        got = dev_res.column("revenue")[0].as_py()
        rel = abs(got - exp) / max(abs(exp), 1e-9)
        return rel <= _rel_tol(), rel
    dev = dev_res.to_pandas() \
        .sort_values(["l_returnflag", "l_linestatus"]).reset_index(drop=True)
    expdf = exp.reset_index()
    dev_num = dev[["sum_qty", "sum_base_price", "sum_disc_price",
                   "sum_charge", "avg_qty", "avg_price", "avg_disc",
                   "count_order"]].to_numpy(dtype=float)
    exp_num = expdf[["sum_qty", "sum_base", "sum_disc", "sum_charge",
                     "avg_qty", "avg_price", "avg_disc", "n"]] \
        .to_numpy(dtype=float)
    if dev_num.shape != exp_num.shape:
        return False, float("inf")
    rel = np.abs(dev_num - exp_num) / np.maximum(np.abs(exp_num), 1e-9)
    err = float(rel.max()) if rel.size else float("inf")
    return err <= _rel_tol(), err


def _worker_tpch(sink: _EventSink):
    _worker_setup_jax()
    on_cpu = os.environ.get("BENCH_PLATFORM") == "cpu"
    from spark_rapids_tpu.session import TpuSession
    from spark_rapids_tpu.tools import tpch
    from spark_rapids_tpu.utils.compile_cache import cache_stats

    sf = float(os.environ.get("BENCH_SF", "0.2" if on_cpu else "1.0"))
    nparts = int(os.environ.get("BENCH_PARTITIONS", "4"))
    tables = tpch.gen_all(sf)
    sink.emit(ev="meta", sf=sf, rows=tables["lineitem"].num_rows)
    sess = TpuSession({
        "spark.rapids.tpu.batchRowsMinBucket": 8192,
        "spark.rapids.tpu.shuffle.partitions": nparts,
        **_pipeline_conf(),
        **_compile_cache_conf(),
        **_eventlog_conf("tpch", sink),
        **_history_conf("tpch"),
        **_health_conf("tpch"),
        **_memprof_conf(),
        **_movement_conf(),
        **_shuffle_conf(),
        **_trace_conf(),
    })
    dfs = tpch.build_dataframes(sess, tables, num_partitions=nparts)

    queries = [int(q) for q in
               os.environ.get("BENCH_WORKER_QUERIES", "").split(",") if q]
    if not queries:
        queries = _TPCH_ORDER
    for i in queries:
        name = f"q{i}"
        sink.emit(ev="start", name=name)
        try:
            q = getattr(tpch, name)(dfs)
            t0 = time.perf_counter()
            dev_tbl = q.collect(device=True)
            warm = time.perf_counter() - t0
            mb = _mem_probe()
            mv = _movement_probe()
            sh = _shuffle_probe()
            t0 = time.perf_counter()
            dev_tbl = q.collect(device=True)
            dev_t = time.perf_counter() - t0
            mv_res = _movement_res(mv)
            sh_res = _shuffle_res(sh, dev_t)
            t0 = time.perf_counter()
            cpu_tbl = q.collect(device=False)
            cpu_t = time.perf_counter() - t0
            err = _tables_equal(dev_tbl, cpu_tbl)
            if err > _rel_tol():
                sink.emit(ev="error", name=name,
                          msg=f"device != host (rel err {err})")
                _log(f"{name} MISMATCH rel_err={err}")
            else:
                cp = _bench_critical_path()
                sink.emit(ev="done", phase="tpch", name=name, res={
                    "dev_s": round(dev_t, 4), "cpu_s": round(cpu_t, 4),
                    "compile_s": round(warm, 2),
                    "speedup": cpu_t / max(dev_t, 1e-9),
                    **_mem_res(mb),
                    **mv_res,
                    **sh_res,
                    **({"critical_path": cp,
                        "sync_wait_frac": cp["sync_wait_frac"]}
                       if cp else {})})
                _log(f"{name}: dev={dev_t:.3f}s cpu={cpu_t:.3f}s "
                     f"compile={warm:.1f}s x{cpu_t/dev_t:.2f}")
        except Exception as e:
            sink.emit(ev="error", name=name,
                      msg=f"{type(e).__name__}: {e}"[:300])
            _log(f"{name} FAILED: {e}")
    sink.emit(ev="meta", compile_cache={"tpch": dict(cache_stats())})
    _emit_health_snapshot(sink, "tpch", sess)
    _emit_memory_snapshot(sink, "tpch", sess)
    sess.close()  # flush the event log + persist the compile tier
    _write_diagnose_report("tpch")
    _bench_sentinel(sink, "tpch")


def _worker_ablation(sink: _EventSink):
    _worker_setup_jax()
    on_cpu = os.environ.get("BENCH_PLATFORM") == "cpu"
    from spark_rapids_tpu.session import TpuSession
    from spark_rapids_tpu.tools import tpch
    sf = float(os.environ.get("BENCH_ABLATION_SF",
                              "0.1" if on_cpu else "0.5"))
    tables = {"lineitem": tpch.gen_lineitem(sf, seed=0,
                                            rows=int(6_000_000 * sf))}
    configs = {
        "baseline": {},
        "host_shuffle_tier": {"spark.rapids.tpu.shuffle.mode": "host"},
        "aqe_off": {"spark.rapids.tpu.aqe.enabled": False},
        "pipeline_off": {"spark.rapids.tpu.pipeline.enabled": False},
        "sql_off_hostengine": {"spark.rapids.sql.enabled": False},
    }
    for name, extra in configs.items():
        sink.emit(ev="start", name=f"ablation_{name}")
        try:
            sess = TpuSession({
                "spark.rapids.tpu.batchRowsMinBucket": 8192,
                "spark.rapids.tpu.shuffle.partitions": 2,
                **_pipeline_conf(), **_compile_cache_conf(), **extra})
            dfs = {"lineitem": sess.create_dataframe(
                tables["lineitem"], num_partitions=2)}
            times = {}
            for qname in ("q6", "q1"):
                q = getattr(tpch, qname)(dfs)
                q.collect()
                t0 = time.perf_counter()
                q.collect()
                times[qname] = round(time.perf_counter() - t0, 4)
            sink.emit(ev="ablation", name=name, res=times)
            _log(f"ablation {name}: {times}")
        except Exception as e:
            sink.emit(ev="ablation", name=name,
                      res={"error": f"{type(e).__name__}: {e}"[:200]})
            _log(f"ablation {name} FAILED: {e}")
    from spark_rapids_tpu.utils.compile_cache import cache_stats
    sink.emit(ev="meta", compile_cache={"ablation": dict(cache_stats())})


def _worker_restart(sink: _EventSink):
    """BENCH_WARM=restart: the zero-compiles acceptance phase. A FRESH
    process (the smoke worker that populated the persistent tier is gone)
    builds the same session/data, waits for the warm pool to replay the
    persisted exports, runs each query ONCE and records how many XLA
    compiles that first-in-process run needed — the tracked trajectory
    number (target: 0)."""
    _worker_setup_jax()
    on_cpu = os.environ.get("BENCH_PLATFORM") == "cpu"
    from spark_rapids_tpu.session import TpuSession
    from spark_rapids_tpu.tools import tpch
    from spark_rapids_tpu.utils.compile_cache import (cache_stats,
                                                      warm_pool_wait)
    default_sf = "0.05" if on_cpu else "0.25"
    sf = float(os.environ.get("BENCH_SMOKE_SF", default_sf))
    rows = int(6_000_000 * sf)
    lineitem = tpch.gen_lineitem(sf, seed=0, rows=rows)
    # conf MUST mirror the smoke phase: same bucket ladder -> same plan
    # signatures + shapes -> warmed executables match
    sess = TpuSession({"spark.rapids.tpu.batchRowsMinBucket": 1 << 18,
                       **_pipeline_conf(),
                       **_compile_cache_conf(),
                       **_eventlog_conf("restart", sink),
                       **_history_conf("restart"),
                       **_health_conf("restart"),
                       **_memprof_conf(),
                       **_movement_conf(),
                       **_trace_conf()})
    warmed = warm_pool_wait()
    df = sess.create_dataframe(lineitem, num_partitions=1).cache()
    t = {"lineitem": df}
    queries = os.environ.get("BENCH_WORKER_QUERIES", "6,1").split(",")
    for qn in queries:
        name = f"q{qn}"
        sink.emit(ev="start", name=name)
        try:
            before = cache_stats()
            mb = _mem_probe()
            mv = _movement_probe()
            q = getattr(tpch, name)(t)
            t0 = time.perf_counter()
            q.collect(device=True)
            run_s = time.perf_counter() - t0
            after = cache_stats()
            cp = _bench_critical_path()
            res = {"run_s": round(run_s, 4),
                   **_mem_res(mb),
                   **_movement_res(mv),
                   "compiles": after["compiles"] - before["compiles"],
                   "persist_hits": after["persist_hits"]
                   - before["persist_hits"],
                   "warm_pool_settled": warmed,
                   **({"critical_path": cp,
                       "sync_wait_frac": cp["sync_wait_frac"]}
                      if cp else {})}
            sink.emit(ev="done", phase="restart", name=name, res=res)
            _log(f"restart {name}: run={run_s:.4f}s "
                 f"second_run_compiles={res['compiles']} "
                 f"persist_hits={res['persist_hits']}")
        except Exception as e:
            sink.emit(ev="error", name=name,
                      msg=f"{type(e).__name__}: {e}"[:300])
            _log(f"restart {name} FAILED: {e}")
    sink.emit(ev="meta", compile_cache={"restart": dict(cache_stats())})
    _emit_health_snapshot(sink, "restart", sess)
    _emit_memory_snapshot(sink, "restart", sess)
    sess.close()
    _write_diagnose_report("restart")
    _bench_sentinel(sink, "restart")


def _worker_chaos(sink: _EventSink):
    """BENCH_CHAOS=1: the recovery-parity phase. Each query runs twice
    on a 2-worker ProcessCluster — clean, then under a deterministic
    worker-kill spec — and passes only if the chaos answer matches the
    clean answer AND the driver's recovery ledger proves a worker
    actually died and its tasks were resubmitted. shuffle.partitions is
    pinned to 2 so each worker process evaluates the worker.task fault
    point exactly once per query and after=1:times=1 kills exactly one
    worker mid-query. The recovery overhead lands in the bench JSON;
    the history sentinel never flags it because run_sentinel exempts
    queries whose event log shows fault records and no error."""
    _worker_setup_jax()
    from spark_rapids_tpu.parallel.runtime import ProcessCluster
    from spark_rapids_tpu.utils import faults
    sf = float(os.environ.get("BENCH_CHAOS_SF", "0.01"))
    queries = os.environ.get("BENCH_WORKER_QUERIES", "1,3").split(",")
    base = {"spark.rapids.tpu.shuffle.partitions": "2"}
    chaos = {**base,
             "spark.rapids.tpu.faults.enabled": "true",
             "spark.rapids.tpu.faults.seed": "7",
             "spark.rapids.tpu.faults.spec":
                 "worker.task:after=1:times=1:action=kill",
             "spark.rapids.tpu.task.heartbeatInterval": "0.5"}

    def _cluster_run(name, conf):
        cl = ProcessCluster(2, conf=conf)
        try:
            t0 = time.perf_counter()
            table = cl.run_tpch_query(name, sf=sf, tiny=True,
                                      num_partitions=2, timeout_s=180)
            return table, time.perf_counter() - t0
        finally:
            cl.close()

    for qn in queries:
        name = f"q{qn}"
        sink.emit(ev="start", name=name)
        try:
            ref, base_s = _cluster_run(name, base)
            faults.reset_recovery()
            got, chaos_s = _cluster_run(name, chaos)
            rec = {k: v for k, v in faults.recovery_counters().items()
                   if v}
            err = _tables_equal(got, ref)
            if not (err <= _rel_tol()):
                raise AssertionError(
                    f"chaos run diverged from clean run: rel_err={err}")
            if not rec.get("worker_deaths"):
                raise AssertionError(
                    "fault spec fired no worker kill; nothing recovered")
            res = {"base_s": round(base_s, 4),
                   "chaos_s": round(chaos_s, 4),
                   "overhead": round(chaos_s / base_s, 3)
                   if base_s > 0 else None,
                   "rel_err": err, "rows": got.num_rows,
                   "recovery": rec}
            sink.emit(ev="done", phase="chaos", name=name, res=res)
            _log(f"chaos {name}: clean={base_s:.3f}s "
                 f"injected={chaos_s:.3f}s deaths="
                 f"{rec.get('worker_deaths')} resubmits="
                 f"{rec.get('task_resubmissions')} rel_err={err:.2e}")
        except Exception as e:
            sink.emit(ev="error", name=name,
                      msg=f"{type(e).__name__}: {e}"[:300])
            _log(f"chaos {name} FAILED: {e}")


def _worker_oom(sink: _EventSink):
    """BENCH_OOM=1: the pressure-parity phase. Each query runs twice in
    one worker process — clean (recording the reference answer and the
    clean-run peak-HBM watermark), then in a FRESH session whose device
    pool is capped at BENCH_OOM_FRAC (default 0.40) of that peak in
    strict mode, with a deterministic times-bounded alloc.jit OOM spec
    layered on top so the ladder's plain-retry rung fires even when
    spilling alone absorbs the pool pressure. Passes only if the
    pressured answer matches the clean answer AND the memory/retry.py
    ladder counters moved (nonzero oom_retries + oom_splits across the
    phase). The history sentinel never flags it because run_sentinel
    exempts queries whose event log carries oom_retry records and no
    error."""
    _worker_setup_jax()
    from spark_rapids_tpu.memory.catalog import peek_catalog
    from spark_rapids_tpu.memory.retry import reset_retry_state, retry_stats
    from spark_rapids_tpu.session import TpuSession
    from spark_rapids_tpu.tools import tpch

    sf = float(os.environ.get("BENCH_OOM_SF", "0.05"))
    frac = float(os.environ.get("BENCH_OOM_FRAC", "0.40"))
    nparts = 2
    tables = tpch.gen_all(sf)
    queries = [int(q) for q in
               os.environ.get("BENCH_WORKER_QUERIES", "1,6").split(",")
               if q]
    base_conf = {
        "spark.rapids.tpu.batchRowsMinBucket": 4096,
        "spark.rapids.tpu.shuffle.partitions": nparts,
    }

    # pass 1: clean run — reference answers (host path) + the device
    # peak-HBM watermark the pressure pool is derived from
    sess = TpuSession(base_conf)
    dfs = tpch.build_dataframes(sess, tables, num_partitions=nparts)
    refs, clean_s = {}, {}
    for i in queries:
        name = f"q{i}"
        try:
            q = getattr(tpch, name)(dfs)
            t0 = time.perf_counter()
            q.collect(device=True)          # drive the device watermark
            clean_s[name] = time.perf_counter() - t0
            refs[name] = q.collect(device=False)
        except Exception as e:
            sink.emit(ev="error", name=name,
                      msg=f"clean pass: {type(e).__name__}: {e}"[:300])
            _log(f"oom {name} clean pass FAILED: {e}")
    cat = peek_catalog()
    peak = cat.peak_device_bytes if cat is not None else 0
    sess.close()
    if not refs or peak <= 0:
        sink.emit(ev="error", name="setup",
                  msg=f"no clean references (peak={peak})")
        return
    pool = max(int(peak * frac), 1 << 20)
    _log(f"oom: clean peak={peak} -> strict pool={pool} ({frac:.0%})")

    # pass 2: fresh session under pressure — strict pool + injected OOMs
    reset_retry_state()
    sess = TpuSession({
        **base_conf,
        "spark.rapids.tpu.memory.pool.size": pool,
        "spark.rapids.tpu.memory.pool.mode": "strict",
        "spark.rapids.tpu.faults.enabled": True,
        "spark.rapids.tpu.faults.seed": 11,
        # times <= oom.maxRetries so a spill-only scope can absorb the
        # injected failures via plain retries; splits come from the pool
        "spark.rapids.tpu.faults.spec":
            "alloc.jit:after=3:times=2:action=oom",
        **_eventlog_conf("oom", sink),
        **_history_conf("oom"),
        **_memprof_conf(),
    })
    dfs = tpch.build_dataframes(sess, tables, num_partitions=nparts)
    for i in queries:
        name = f"q{i}"
        if name not in refs:
            continue
        sink.emit(ev="start", name=name)
        try:
            before = retry_stats()
            mb = _mem_probe()
            t0 = time.perf_counter()
            got = getattr(tpch, name)(dfs).collect(device=True)
            oom_s = time.perf_counter() - t0
            after = retry_stats()
            err = _tables_equal(got, refs[name])
            if not (err <= _rel_tol()):
                raise AssertionError(
                    f"pressured run diverged from clean run: rel_err={err}")
            delta = {k: after[k] - before[k]
                     for k in ("oom_retries", "oom_splits",
                               "oom_rematerializations", "oom_recoveries",
                               "oom_spilled_bytes")
                     if after[k] - before[k]}
            res = {"clean_s": round(clean_s[name], 4),
                   "oom_s": round(oom_s, 4),
                   "overhead": round(oom_s / clean_s[name], 3)
                   if clean_s.get(name) else None,
                   "rel_err": err, "pool_bytes": pool,
                   "retry": delta, **_mem_res(mb)}
            sink.emit(ev="done", phase="oom", name=name, res=res)
            _log(f"oom {name}: clean={clean_s[name]:.3f}s "
                 f"pressured={oom_s:.3f}s retries="
                 f"{delta.get('oom_retries', 0)} splits="
                 f"{delta.get('oom_splits', 0)} rel_err={err:.2e}")
        except Exception as e:
            sink.emit(ev="error", name=name,
                      msg=f"{type(e).__name__}: {e}"[:300])
            _log(f"oom {name} FAILED: {e}")
    totals = retry_stats()
    if not (totals["oom_retries"] and totals["oom_splits"]):
        sink.emit(ev="error", name="counters",
                  msg="pressure phase exercised no ladder: "
                      f"retries={totals['oom_retries']} "
                      f"splits={totals['oom_splits']}")
        _log(f"oom: LADDER IDLE retries={totals['oom_retries']} "
             f"splits={totals['oom_splits']}")
    _emit_memory_snapshot(sink, "oom", sess)
    sess.close()  # flush the event log (oom_retry records) + history run
    _write_diagnose_report("oom")
    _bench_sentinel(sink, "oom")


def _worker_fallback(sink: _EventSink):
    """BENCH_FALLBACK=1: the degradation-parity phase. Each query runs
    twice in one worker process — clean (recording the reference
    answer), then in a FRESH session under a deterministic
    times-bounded alloc.jit:action=fatal spec: a NON-retryable INTERNAL
    XLA failure the retry ladder refuses to touch, so recovery can only
    come from the exec/fallback.py host-fallback boundary. Passes only
    if the degraded answer matches the clean answer AND the fallback
    counters moved (nonzero host_fallbacks across the phase). The
    history sentinel never flags it because run_sentinel exempts
    queries whose event log carries schema-v10 fallback records and no
    error."""
    _worker_setup_jax()
    from spark_rapids_tpu.exec.fallback import (fallback_stats,
                                                reset_fallback_state)
    from spark_rapids_tpu.session import TpuSession
    from spark_rapids_tpu.tools import tpch

    sf = float(os.environ.get("BENCH_FALLBACK_SF", "0.05"))
    nparts = 2
    tables = tpch.gen_all(sf)
    queries = [int(q) for q in
               os.environ.get("BENCH_WORKER_QUERIES", "1,6").split(",")
               if q]
    base_conf = {
        "spark.rapids.tpu.batchRowsMinBucket": 4096,
        "spark.rapids.tpu.shuffle.partitions": nparts,
    }

    # pass 1: clean run — reference answers
    sess = TpuSession(base_conf)
    dfs = tpch.build_dataframes(sess, tables, num_partitions=nparts)
    refs, clean_s = {}, {}
    for i in queries:
        name = f"q{i}"
        try:
            q = getattr(tpch, name)(dfs)
            t0 = time.perf_counter()
            refs[name] = q.collect(device=True)
            clean_s[name] = time.perf_counter() - t0
        except Exception as e:
            sink.emit(ev="error", name=name,
                      msg=f"clean pass: {type(e).__name__}: {e}"[:300])
            _log(f"fallback {name} clean pass FAILED: {e}")
    sess.close()
    if not refs:
        sink.emit(ev="error", name="setup", msg="no clean references")
        return

    # pass 2: fresh session under injected non-retryable failures — the
    # quarantine threshold is raised past what the phase can accumulate
    # so every injection exercises the RUNTIME boundary, not the planner
    reset_fallback_state()
    sess = TpuSession({
        **base_conf,
        "spark.rapids.tpu.faults.enabled": True,
        "spark.rapids.tpu.faults.seed": 11,
        # no after-offset: the first alloc.jit dispatches sit inside the
        # fallback-capable whole-stage boundary; later evaluations can
        # land in note-only merge scopes where fatal is terminal
        "spark.rapids.tpu.faults.spec":
            "alloc.jit:times=2:action=fatal",
        "spark.rapids.tpu.fallback.quarantine.threshold": 1000,
        **_eventlog_conf("fallback", sink),
        **_history_conf("fallback"),
        **_memprof_conf(),
    })
    dfs = tpch.build_dataframes(sess, tables, num_partitions=nparts)
    for i in queries:
        name = f"q{i}"
        if name not in refs:
            continue
        sink.emit(ev="start", name=name)
        try:
            before = fallback_stats()
            mb = _mem_probe()
            t0 = time.perf_counter()
            got = getattr(tpch, name)(dfs).collect(device=True)
            fb_s = time.perf_counter() - t0
            after = fallback_stats()
            err = _tables_equal(got, refs[name])
            if not (err <= _rel_tol()):
                raise AssertionError(
                    f"degraded run diverged from clean run: rel_err={err}")
            delta = {k: after[k] - before[k]
                     for k in ("host_fallbacks", "fallback_bytes_down",
                               "fallback_bytes_up", "fallback_failures",
                               "quarantine_notes")
                     if after[k] - before[k]}
            res = {"clean_s": round(clean_s[name], 4),
                   "fallback_s": round(fb_s, 4),
                   "overhead": round(fb_s / clean_s[name], 3)
                   if clean_s.get(name) else None,
                   "rel_err": err, "degrade": delta, **_mem_res(mb)}
            sink.emit(ev="done", phase="fallback", name=name, res=res)
            _log(f"fallback {name}: clean={clean_s[name]:.3f}s "
                 f"degraded={fb_s:.3f}s host_fallbacks="
                 f"{delta.get('host_fallbacks', 0)} bytes_down="
                 f"{delta.get('fallback_bytes_down', 0)} rel_err={err:.2e}")
        except Exception as e:
            sink.emit(ev="error", name=name,
                      msg=f"{type(e).__name__}: {e}"[:300])
            _log(f"fallback {name} FAILED: {e}")
    totals = fallback_stats()
    if not totals["host_fallbacks"]:
        sink.emit(ev="error", name="counters",
                  msg="degradation phase exercised no host fallback: "
                      f"host_fallbacks={totals['host_fallbacks']} "
                      f"failures={totals['fallback_failures']}")
        _log(f"fallback: BOUNDARY IDLE "
             f"host_fallbacks={totals['host_fallbacks']}")
    _emit_memory_snapshot(sink, "fallback", sess)
    sess.close()  # flush the event log (fallback records) + history run
    _write_diagnose_report("fallback")
    _bench_sentinel(sink, "fallback")


def _worker_multichip(sink: _EventSink):
    """MULTICHIP trajectory phase: q3/q5/q7 on an n-virtual-device CPU
    mesh — the hash exchanges lower to the on-device ICI all-to-all tier
    (shuffle/ici.py) and the shuffle observatory attributes every
    transfer. Each query runs under an in-worker alarm: on timeout the
    res that lands in the JSON is the partial shuffle delta plus the
    observatory's forensics ring for THAT query, and the phase moves on
    — an rc=124 wall-of-silence can't happen at this layer (the parent
    watchdog above still catches a GIL-held native hang)."""
    import __graft_entry__
    n = int(os.environ.get("BENCH_MULTICHIP_DEVICES", "8"))
    __graft_entry__._force_cpu_devices(n)
    _silence_xla_cpu_noise()
    from spark_rapids_tpu.parallel.mesh import virtual_cpu_mesh
    from spark_rapids_tpu.session import TpuSession
    from spark_rapids_tpu.shuffle import telemetry as shuffle_telemetry
    from spark_rapids_tpu.tools import tpch

    sf = float(os.environ.get("BENCH_MULTICHIP_SF", "0"))
    tables = tpch.gen_all(sf) if sf > 0 else tpch.gen_all(0, tiny=True)
    sink.emit(ev="meta", sf=sf, rows=tables["lineitem"].num_rows)
    mesh_on = os.environ.get("BENCH_MESH", "on") != "off"
    base_conf = {
        "spark.rapids.tpu.batchRowsMinBucket": 8192 if sf > 0 else 8,
        "spark.rapids.tpu.shuffle.partitions":
            int(os.environ.get("BENCH_PARTITIONS", "4")),
        # static ICI lowering (the shape tests/test_exchange.py pins):
        # AQE re-plans exchanges into materialized stages and a broadcast
        # join would route the probe side around the device exchange
        "spark.rapids.tpu.aqe.enabled": False,
        "spark.rapids.tpu.autoBroadcastJoinThreshold": -1,
    }
    sess = TpuSession({
        **base_conf,
        # the headline arm's mesh-parallel stage execution knob; the
        # post-headline A/B below measures both settings either way
        "spark.rapids.tpu.mesh.stageExecution.enabled": mesh_on,
        **_shuffle_conf(),
        **_movement_conf(),
        **_eventlog_conf("multichip", sink),
        **_history_conf("multichip"),
    })
    sess.attach_mesh(virtual_cpu_mesh(n))
    dfs = tpch.build_dataframes(sess, tables, num_partitions=2)

    per_q_timeout = float(
        os.environ.get("BENCH_MULTICHIP_QUERY_TIMEOUT_S", "180"))

    class _QueryTimeout(Exception):
        pass

    def _on_alarm(signum, frame):
        raise _QueryTimeout()

    signal.signal(signal.SIGALRM, _on_alarm)

    queries = [q for q in
               os.environ.get("BENCH_WORKER_QUERIES", "").split(",") if q]
    queries = queries or ["3", "5", "7"]
    exec_log = []   # collect order -> name (maps event-log qids back)
    results = {}
    for qn in queries:
        name = f"q{qn}"
        sink.emit(ev="start", name=name)
        shuffle_telemetry.drain_ring()  # scope the forensics ring to THIS query
        sh = _shuffle_probe()
        signal.alarm(int(per_q_timeout))
        try:
            q = getattr(tpch, name)(dfs)
            t0 = time.perf_counter()
            out = q.collect(device=True)
            wall = time.perf_counter() - t0
            signal.alarm(0)
            exec_log.append(name)
            res = {"wall_s": round(wall, 4), "rows": out.num_rows,
                   **_shuffle_res(sh, wall)}
            results[name] = res
            sink.emit(ev="done", phase="multichip", name=name, res=res)
            _log(f"multichip {name}: wall={wall:.3f}s "
                 f"shuffle={res.get('shuffle_wall_s', 0):.3f}s "
                 f"wire={res.get('wire_bytes', 0)}B")
        except _QueryTimeout:
            signal.alarm(0)
            exec_log.append(name)  # the error path still logs the query
            sink.emit(ev="error", name=name,
                      msg=f"query timeout > {per_q_timeout:.0f}s "
                          f"(in-worker alarm)")
            sink.emit(ev="meta", multichip_forensics={name: {
                "kind": "timeout", "timeout_s": per_q_timeout,
                "partial": _shuffle_res(sh, per_q_timeout),
                "ring": shuffle_telemetry.drain_ring()[-64:]}})
            _log(f"multichip {name} TIMEOUT after {per_q_timeout:.0f}s")
        except Exception as e:
            signal.alarm(0)
            exec_log.append(name)
            sink.emit(ev="error", name=name,
                      msg=f"{type(e).__name__}: {e}"[:300])
            sink.emit(ev="meta", multichip_forensics={name: {
                "kind": type(e).__name__,
                "partial": _shuffle_res(sh, 0.0),
                "ring": shuffle_telemetry.drain_ring()[-64:]}})
            _log(f"multichip {name} FAILED: {e}")
    sess.close()  # flush the event log (shuffle_summary records)
    _enrich_multichip(sink, exec_log, results)
    _mesh_ab(sink, tables, results, base_conf, n, per_q_timeout, queries)
    _write_diagnose_report("multichip")
    _bench_sentinel(sink, "multichip")


def _mesh_ab(sink: _EventSink, tables, results, base_conf, n,
             per_q_timeout, queries):
    """Mesh-stage execution A/B (exec/mesh.py): re-measure each headline
    query with mesh-parallel stage execution OFF then ON, in fresh
    sessions WITHOUT eventlog/history conf — the A/B collects never
    pollute the trajectory store or the sentinel's baseline chain. Each
    arm warms a query (build + XLA compile land in the process-global
    caches) before its timed collect, so the A/B compares steady-state
    dispatch, not compilation order. Folds {off,on}_wall_s/_rows into
    each query's res as "mesh_ab"; never fails the bench."""
    from spark_rapids_tpu.parallel.mesh import virtual_cpu_mesh
    from spark_rapids_tpu.session import TpuSession
    from spark_rapids_tpu.tools import tpch

    class _ABTimeout(Exception):
        pass

    def _on_alarm(signum, frame):
        raise _ABTimeout()

    signal.signal(signal.SIGALRM, _on_alarm)
    ab = {name: {} for name in results}
    for arm, enabled in (("off", False), ("on", True)):
        try:
            sess = TpuSession({
                **base_conf,
                "spark.rapids.tpu.mesh.stageExecution.enabled": enabled})
            sess.attach_mesh(virtual_cpu_mesh(n))
            dfs = tpch.build_dataframes(sess, tables, num_partitions=2)
        except Exception as e:
            _log(f"multichip mesh A/B arm={arm}: setup failed: {e}")
            continue
        for qn in queries:
            name = f"q{qn}"
            if name not in ab:
                continue  # headline run never finished this query
            signal.alarm(int(per_q_timeout))
            try:
                q = getattr(tpch, name)(dfs)
                q.collect(device=True)  # warm: plan + compile
                t0 = time.perf_counter()
                out = q.collect(device=True)
                wall = time.perf_counter() - t0
                signal.alarm(0)
                ab[name][f"{arm}_wall_s"] = round(wall, 4)
                ab[name][f"{arm}_rows"] = out.num_rows
                _log(f"multichip mesh A/B {name} {arm}: {wall:.3f}s "
                     f"rows={out.num_rows}")
            except _ABTimeout:
                signal.alarm(0)
                ab[name][f"{arm}_error"] = \
                    f"timeout > {per_q_timeout:.0f}s"
                _log(f"multichip mesh A/B {name} {arm}: TIMEOUT")
            except Exception as e:
                signal.alarm(0)
                ab[name][f"{arm}_error"] = \
                    f"{type(e).__name__}: {e}"[:200]
                _log(f"multichip mesh A/B {name} {arm} FAILED: {e}")
        try:
            sess.close()
        except Exception:
            pass
    for name, res in results.items():
        if ab.get(name):
            res["mesh_ab"] = ab[name]
            sink.emit(ev="done", phase="multichip", name=name, res=res)


def _enrich_multichip(sink: _EventSink, exec_log, results):
    """Re-emit each multichip query's res enriched with the event log's
    v12 shuffle_summary (per-tier breakdown, straggler attribution,
    stitched count) — the log is only guaranteed flushed after
    sess.close(), so the per-query "done" events carry the scalar deltas
    first and the full breakdown lands here. Never fails the bench."""
    d = os.path.join(
        os.environ.get("BENCH_EVENTLOG_DIR",
                       os.path.join(_REPO, ".bench_eventlogs")),
        "multichip")
    try:
        import glob as _glob
        from spark_rapids_tpu.tools.eventlog import load_event_log
        logs = [p for p in _glob.glob(os.path.join(d, "*.jsonl"))
                if os.path.getmtime(p) >= _WALL_START]
        if not logs:
            return
        app = load_event_log(sorted(logs, key=os.path.getmtime)[-1])
        for i, qid in enumerate(sorted(app.queries)):
            if i >= len(exec_log):
                break
            name = exec_log[i]
            sh = getattr(app.queries[qid], "shuffle_summary", None)
            if not sh or name not in results:
                continue
            res = results[name]
            res["shuffle"] = {"totals": sh["totals"],
                              "tiers": sh["tiers"],
                              "straggler": sh["straggler"]}
            sink.emit(ev="done", phase="multichip", name=name, res=res)
    except Exception as e:
        _log(f"multichip: enrich failed: {type(e).__name__}: {e}")


def multichip_main(out_path: str):
    """Parent mode (``bench.py --multichip [out.json]``): run the
    multichip phase worker under the watchdog and write the MULTICHIP
    trajectory JSON — per-query wall, shuffle wall, per-tier transfer
    breakdown, wire bytes and straggler stats, with per-query forensics
    (partial results + observatory ring) on timeout or worker death."""
    _silence_xla_cpu_noise()
    n = int(os.environ.get("BENCH_MULTICHIP_DEVICES", "8"))
    # budget covers the headline queries PLUS the mesh A/B's two extra
    # warm+timed collects per query (warm arms reuse compiled programs)
    timeout = float(os.environ.get("BENCH_MULTICHIP_TIMEOUT_S", "480"))
    status, current = _run_phase("multichip", "cpu", None, timeout)
    queries = _STATE["multichip"]
    out = {
        # forced virtual CPU devices: a rehearsal of meshes and sharding
        # rules, never a multi-chip measurement
        "backend": "cpu-virtual",
        "n_devices": n,
        "mesh": os.environ.get("BENCH_MESH", "on"),
        "status": status,
        "ok": status == "clean" and not _STATE["errors"],
        "queries": queries,
        "errors": _STATE["errors"],
        "forensics": _STATE["multichip_forensics"],
        "eventlog": _STATE["eventlog"].get("multichip"),
        "history": _STATE["history"].get("multichip"),
        "notes": _STATE["notes"],
    }
    if current:
        out["killed_on"] = current
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2, default=str)
        f.write("\n")
    _log(f"multichip -> {out_path} status={status} "
         f"queries={sorted(queries)} errors={sorted(_STATE['errors'])}")


def worker_main(phase: str):
    sink = _EventSink()
    if phase == "smoke":
        _worker_smoke(sink)
    elif phase == "tpch":
        _worker_tpch(sink)
    elif phase == "ablation":
        _worker_ablation(sink)
    elif phase == "restart":
        _worker_restart(sink)
    elif phase == "chaos":
        _worker_chaos(sink)
    elif phase == "oom":
        _worker_oom(sink)
    elif phase == "fallback":
        _worker_fallback(sink)
    elif phase == "multichip":
        _worker_multichip(sink)
    else:
        raise SystemExit(f"unknown worker phase {phase!r}")


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--worker":
        worker_main(sys.argv[2])
        sys.exit(0)
    if len(sys.argv) >= 2 and sys.argv[1] == "--multichip":
        multichip_main(sys.argv[2] if len(sys.argv) > 2
                       else os.path.join(_REPO,
                                         "MULTICHIP_cpu-virtual.json"))
        sys.exit(0)
    try:
        main()
    except Exception:
        import traceback
        traceback.print_exc()
        _emit(reason="exception")
        sys.exit(0)
