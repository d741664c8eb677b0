#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, no subprocess. Set-up (all of it is ``setup_s``): build the
native library if absent, write or find the seeded Parquet, open the
configuration's session, ``collect()`` the cell's query until one
``collect()`` asks XLA for no compile. Then the window: the query back to
back from one client for ``--seconds``; with ``--trace 1`` a short window
under ``jax.profiler`` instead. After the window: device memory is read, the
session is closed, and every answer the window returned is compared with the
plain reference over the same files. The last line of standard output is the
result; the numbers compared stand beside their limits in it and as the last
lines of standard error.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result. ``--rehearse-cpu`` runs the control flow on whatever
backend there is at ``--sf`` (default 0.01): it prints what it found to
standard error only, and exits 2.
"""
import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import types  # noqa: E402

import jax  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import cells, compare, data, engine, references  # noqa: E402
from benchmark import input_bytes, reduce_trace, window  # noqa: E402
from benchmark.compile_clock import XlaCompileClock  # noqa: E402

TRACE_DIR = os.path.join(cells.HERE, ".trace")
#: a traced window holds one whole query where a query takes longer than
#: this, else as many whole queries as start within it
TRACED_WINDOW_S = 5.0
MAX_WARMUP_COLLECTS = 5


def log(msg: str):
    print(f"[bench {time.perf_counter() - T_PROCESS_START:8.2f}s] {msg}",
          file=sys.stderr, flush=True)


class StallWatch(threading.Thread):
    """Says where a stalled query hangs. A daemon thread that wakes four
    times a second; once the ``collect()`` in flight has run for over
    ``after_s`` it writes every thread's Python stack to standard error,
    once per query. It touches nothing of the query. (Two Q6 queries in
    ~30,000 stalled 3.4 s on the chip's host in PR 24, cause not found: this
    is how the next one is read.)"""

    def __init__(self):
        super().__init__(daemon=True)
        self.after_s = float("inf")   # set once a warm wall is known
        self.started = None           # clock at the start of the collect()
        self.stalls = 0
        self.start()

    def run(self):
        seen = None
        while True:
            time.sleep(0.25)
            t0 = self.started
            if t0 is not None and t0 != seen \
                    and time.perf_counter() - t0 > self.after_s:
                seen = t0
                self.stalls += 1
                log(f"STALL: a collect() has run for over {self.after_s:.2f} "
                    f"s; every thread's stack follows")
                faulthandler.dump_traceback(file=sys.stderr, all_threads=True)


def make_query(sess, df, annotate=False, watch=None):
    """The window's unit of work: one ``collect()`` and nothing else inside
    the timed wall. Returns ``(query, plans)``: ``query()`` gives ``(Arrow
    table, None)`` and appends the plan it executed to ``plans``, which
    ``plan_faults`` reads once the window has closed."""
    plans = []
    watch = watch or types.SimpleNamespace()   # nobody watches

    def query():
        watch.started = time.perf_counter()
        if annotate:
            with jax.profiler.TraceAnnotation(reduce_trace.SPAN):
                table = df.collect()
        else:
            table = df.collect()
        watch.started = None
        plans.append(sess.executed_plan)
        return table, None
    return query, plans


def plan_faults(plans, plan_rules) -> list:
    """Per executed plan: what is wrong with it, as one string, or None."""
    return ["; ".join(f) or None for f in (
        engine.plan_faults(engine.executed_nodes(p), plan_rules)
        for p in plans)]


def warm_up(query, plans, plan_rules, clock):
    """collect() until one compiles nothing: every compile request it makes,
    if it makes any, is served from XLA's persistent cache, so every program
    of the query is loaded and has run once. With the cache kept that is the
    first; in a checkout's first run, the second. Returns the walls of the
    warm-up queries; raises where the fifth still compiled. (Requests that
    hit the cache are not compiles, but they are work: the traced run counts
    them inside its window as ``window_xla_compiles``.)"""
    walls = []
    fell = engine.host_fallbacks()
    for i in range(MAX_WARMUP_COLLECTS):
        before = clock.snapshot()
        t0 = time.perf_counter()
        query()
        walls.append(time.perf_counter() - t0)
        requests = clock.compiles - before["xla_compiles"]
        missed = requests - (clock.cache_hits
                             - before["xla_persistent_cache_hits"])
        fault = plan_faults(plans[-1:], plan_rules)[0]
        if engine.host_fallbacks() != fell:
            fault = "; ".join(filter(None, [fault, "host fallback"]))
        log(f"warm-up collect {i}: {walls[-1]:.3f} s, {requests} XLA compile "
            f"requests, {missed} compiled"
            + (f", FAULT {fault}" if fault else ""))
        if fault:
            raise RuntimeError(f"warm-up query failed: {fault}")
        if missed == 0:
            return walls
    raise RuntimeError(f"collect() number {MAX_WARMUP_COLLECTS} still "
                       f"compiled {missed} programs: set-up cannot end")


def traced_window(query, warm_wall_s: float, seconds: float):
    """A short window under the profiler (Python tracer off)."""
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    try:
        w = window.run_window(
            query, min(seconds, TRACED_WINDOW_S),
            max_queries=1 if warm_wall_s > TRACED_WINDOW_S else None)
    finally:
        jax.profiler.stop_trace()
    return w


def drive(cell, seed: int, seconds: float, trace: bool, scale=None):
    """Everything of a run after the look for a chip; returns the result
    object. ``scale`` is for rehearsals and tests."""
    clock = XlaCompileClock()
    devices = jax.devices()[:cell.chips]

    # ---- set-up ----------------------------------------------------------
    native = engine.build_native_library()
    root = data.ensure_data(cell.config, list(cell.traffic["columns"]), seed,
                            scale)
    log(f"data at {os.path.relpath(root, cells.REPO)} "
        f"(native library {'loaded' if native else 'absent'})")
    sess = engine.open_session(cell.config)
    try:
        df = engine.build_query(sess, root, cell.config, cell.traffic)
        watch = StallWatch()
        query, plans = make_query(sess, df, annotate=trace, watch=watch)
        warm_walls = warm_up(query, plans, cell.config["plan"], clock)
        # a stall: a query of twice the warm-up's wall, and a second at least
        watch.after_s = max(1.0, 2 * warm_walls[-1])
        del plans[:]
        fell = engine.host_fallbacks()
        setup = clock.snapshot()
        setup_s = time.perf_counter() - T_PROCESS_START
        log(f"set-up done: {setup_s:.3f} s")

        # ---- the window --------------------------------------------------
        if trace:
            w = traced_window(query, warm_walls[-1], seconds)
        else:
            w = window.run_window(query, seconds)
        window_compiles = clock.compiles - setup["xla_compiles"]
        fell = engine.host_fallbacks() - fell
        window.add_faults(w, plan_faults(plans, cell.config["plan"]), fell)
        log(f"window: {w.attempted} queries in {w.length_s:.3f} s, "
            f"{w.failed} failed, {window_compiles} XLA compile requests; "
            f"walls first {w.walls[0]:.4f} median "
            f"{window.percentile(w.walls, 0.5):.4f} max {max(w.walls):.4f} s "
            f"(query {w.walls.index(max(w.walls))}); {watch.stalls} stalls")
        memory = [d.memory_stats() for d in devices]
    finally:
        sess.close()
    for fault in sorted({f for f in w.faults if f}):
        log(f"query fault: {fault}")

    # ---- after the window: reference and comparison ----------------------
    t_ref = time.perf_counter()
    ref = references.compute(cell.traffic["reference"], root,
                             cell.traffic["columns"])
    answers = [a.to_pandas() for a in w.answers if a is not None]
    verdict = compare.judge(answers, ref, w.failed,
                             cell.traffic["limits"])
    log(f"reference and comparison: {time.perf_counter() - t_ref:.3f} s")

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": max(
                  (m or {}).get("peak_bytes_in_use", 0) for m in memory)}
    if trace:
        reduced = reduce_trace.reduce_trace(
            reduce_trace.load(reduce_trace.find_xplane(TRACE_DIR)))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        run = {
            "trace": reduced, "memory": memory, "chips": cell.chips,
            "peaks": cells.peaks(devices[0].device_kind)
            if devices[0].platform == "tpu" else None,
            "input_bytes": input_bytes.query_input_bytes(
                root, cell.traffic["columns"]),
            "counters": {
                "window_xla_compiles": window_compiles,
                "setup_xla_compiles": setup["xla_compiles"],
                "setup_xla_compile_s": setup["xla_compile_seconds"],
                "setup_xla_cache_hits": setup["xla_persistent_cache_hits"],
                "first_collect_s": warm_walls[0]}}
        values = {}
        for name in cell.per_layer:
            read, args = cells.load_reader(name)
            values[name] = read(run, **args)
        if "busy_s_mean" in reduced:
            device["busy_s"] = reduced["busy_s_mean"]
        if "window_s" in reduced:
            device["window_s"] = reduced["window_s"]
    else:
        values = {"query_s": window.query_s(w),
                  "query_p95_s": window.query_p95_s(w), "setup_s": setup_s}
        values = {k: values[k] for k in cell.end_to_end}
    result = {
        "correct": verdict["correct"],
        "attempted": w.attempted,
        "failed": w.failed,
        "metrics": {k: {"value": v, "unit": cells.metric_unit(k)}
                    for k, v in values.items() if v is not None},
        "device": device,
    }
    if trace:
        result["breakdown"] = reduce_trace.breakdown(reduced)
    result["workload"] = cell.name
    result["seed"] = seed
    result["answers_compared"] = verdict["answers_compared"]
    result["compared"] = verdict["compared"]
    return result


def print_compared(result):
    for name, v in result["compared"].items():
        print(f"compared {name}: value {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    if "correct" in result:
        print(f"correct: {result['correct']}", file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="control flow only, on any backend, at --sf; "
                         "prints no result and exits 2")
    ap.add_argument("--sf", type=float, default=0.01,
                    help="scale factor of a rehearsal")
    args = ap.parse_args(argv)

    cell = cells.load_cell(args.workload)
    found = jax.devices()
    if not args.rehearse_cpu and found[0].platform != "tpu":
        print(f"benchmark: JAX found no TPU (platform "
              f"{found[0].platform!r}); nothing was run", file=sys.stderr)
        return 2
    if len(found) < cell.chips:
        print(f"benchmark: cell {cell.name} needs {cell.chips} chips, JAX "
              f"found {len(found)}; nothing was run", file=sys.stderr)
        return 2
    if found[0].platform == "tpu":
        cells.peaks(found[0].device_kind)   # an unknown kind is an error

    result = drive(cell, args.seed, args.seconds, bool(args.trace),
                   scale=args.sf if args.rehearse_cpu else None)
    if args.rehearse_cpu:
        # a rehearsal is no measurement and no proof: it has no "correct"
        result["rehearsal_agrees"] = result.pop("correct")
        print_compared(result)
        print(json.dumps(result), file=sys.stderr)
        print("benchmark: rehearsal finished; no result is printed",
              file=sys.stderr)
        return 2
    print_compared(result)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
