"""The one span API (utils/tracing.py): per-query phase totals, spans on
the profiler's clock, stable device-program names, and the ``jitname``
lint. CPU, SF 0.01; nothing here asserts a time."""
import glob
import os
import threading

import jax
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu.session import TpuSession
from spark_rapids_tpu.tools import tpch
from spark_rapids_tpu.tools.analyze import analyze_paths
from spark_rapids_tpu.tools.trace import idle_by_phase
from spark_rapids_tpu.utils import tracing
from spark_rapids_tpu.utils.compile_cache import (PROGRAM_NAMES, cached_jit,
                                                  named_jit)
from spark_rapids_tpu.utils.tracing import (RECENT_QUERIES, STRUCTURAL_SPANS,
                                            Tracer, get_tracer)

PKG = os.path.dirname(os.path.abspath(tracing.__file__ + "/.."))
CONF = {"spark.rapids.tpu.batchRowsMinBucket": 64,
        "spark.rapids.sql.test.enabled": True,
        "spark.rapids.tpu.compile.warmPool.enabled": False}


@pytest.fixture(scope="module")
def lineitem_dir(tmp_path_factory):
    """TPC-H lineitem at SF 0.01 as two Parquet files (two partitions)."""
    root = tmp_path_factory.mktemp("lineitem")
    table = tpch.gen_lineitem(0.01)
    half = table.num_rows // 2
    pq.write_table(table.slice(0, half), str(root / "part-0.parquet"))
    pq.write_table(table.slice(half), str(root / "part-1.parquet"))
    return str(root)


@pytest.fixture(scope="module")
def sess():
    s = TpuSession(CONF)
    yield s
    s.close()


def run_query(sess, lineitem_dir, name):
    df = tpch.QUERIES[name]({"lineitem": sess.read_parquet(lineitem_dir)})
    df.collect()                      # compile
    df.collect()
    return sess.last_query_phases()


#: what a Q6-shaped query (pushed-down host scan, upload, one stage, one
#: sum) and a Q1-shaped one (device decode, group-by) must book a phase for
Q6_PHASES = ("plan", "scan.read", "scan.parse", "h2d", "dispatch", "d2h",
             "result", "query", "task")
Q1_PHASES = ("plan", "scan.read", "scan.parse", "h2d", "dispatch", "sync",
             "d2h", "result", "query", "task")


@pytest.mark.parametrize("query,phases", [("q6", Q6_PHASES),
                                          ("q1", Q1_PHASES)])
def test_a_collect_books_every_phase_of_its_host_path(sess, lineitem_dir,
                                                      query, phases):
    got = run_query(sess, lineitem_dir, query)
    assert got == get_tracer().recent_queries(1)[0]
    for name in phases:
        assert got["phases"][name]["calls"] > 0, name
    assert got["phases"]["query"]["calls"] == 1
    assert got["phases"]["plan"]["calls"] == 1
    for name in ("scan.read", "h2d", "d2h"):
        assert got["phases"][name]["bytes"] > 0, name
    # self time never counts an interval twice on one thread, and the
    # covered wall is a union inside the query span
    total_self = sum(p["self_s"] for p in got["phases"].values())
    assert 0 < total_self <= got["wall_s"] * got["threads"] * 1.001
    assert 0 < got["covered_s"] <= got["wall_s"]
    assert got["spans_dropped"] == 0 and got["threads"] >= 1


def test_device_decode_parses_pages_on_the_host_and_names_its_programs(
        sess, lineitem_dir):
    got = run_query(sess, lineitem_dir, "q1")
    # two host steps a decoded column chunk: the pages, the kernel inputs
    assert got["phases"]["scan.parse"]["calls"] >= 2
    assert got["phases"]["dispatch"]["calls"] > got["phases"]["d2h"]["calls"]


def test_two_queries_from_two_threads_keep_their_summaries_apart(
        lineitem_dir):
    """A span is booked to the query its thread is in, pool threads to the
    query that submitted their task: never to a process global."""
    s = TpuSession(CONF)
    li = s.read_parquet(lineitem_dir)
    frames = {"q6": tpch.QUERIES["q6"]({"lineitem": li}),
              "q1": tpch.QUERIES["q1"]({"lineitem": li})}
    for df in frames.values():
        df.collect()
    alone = {}
    for name, df in frames.items():
        df.collect()
        alone[name] = s.last_query_phases()
    tracer = get_tracer()
    before = {q["query_id"] for q in tracer.recent_queries()}
    start = threading.Barrier(2)
    errors = []

    def client(df):
        try:
            start.wait(timeout=60)
            for _ in range(3):
                df.collect()
        except Exception as e:      # surfaced below
            errors.append(e)

    threads = [threading.Thread(target=client, args=(df,))
               for df in frames.values()]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
        assert not t.is_alive()
    assert not errors
    new = [q for q in tracer.recent_queries() if q["query_id"] not in before]
    assert len(new) == 6 and len({q["query_id"] for q in new}) == 6

    def shape(q):       # what a query's plan fixes, whatever ran beside it
        return {p: q["phases"].get(p, {}).get("calls", 0)
                for p in ("plan", "scan.read", "scan.parse", "h2d", "d2h",
                          "dispatch", "result", "query")}
    shapes = [shape(q) for q in new]
    assert sorted(map(str, shapes)) == sorted(
        [str(shape(alone["q6"]))] * 3 + [str(shape(alone["q1"]))] * 3)
    s.close()


def test_the_ring_of_recent_queries_holds_256():
    tracer = Tracer()
    for _ in range(RECENT_QUERIES + 44):
        with tracer.query():
            with tracer.span("plan", "plan"):
                pass
    recent = tracer.recent_queries()
    assert len(recent) == RECENT_QUERIES == 256
    ids = [q["query_id"] for q in recent]
    assert ids == sorted(ids) and ids[-1] - ids[0] == 255
    assert [q["query_id"] for q in tracer.recent_queries(3)] == ids[-3:]
    assert tracer.recent_queries(0) == []


def test_self_time_leaves_out_children_and_past_the_cap_totals_keep_counting(
        monkeypatch):
    monkeypatch.setattr(tracing, "QUERY_SPAN_CAP", 4)
    tracer = Tracer()
    with tracer.query() as root:
        with tracer.span("task", "task"):
            for _ in range(10):
                with tracer.span("scan.read", "scan", bytes=100):
                    with tracer.span("scan.parse", "scan"):
                        pass
    got = root.summary.to_dict()
    assert got["phases"]["scan.read"] == {
        "calls": 10, "self_s": got["phases"]["scan.read"]["self_s"],
        "bytes": 1000}
    assert got["phases"]["scan.parse"]["calls"] == 10
    assert got["spans_dropped"] == 16          # 20 phase spans, 4 kept
    # structural spans hold no covered wall of their own
    assert STRUCTURAL_SPANS >= {"query", "task", "stage"}
    assert got["covered_s"] <= got["phases"]["scan.read"]["self_s"] \
        + got["phases"]["scan.parse"]["self_s"] + 1e-9
    total = sum(p["self_s"] for p in got["phases"].values())
    assert total <= got["wall_s"] * 1.001


def test_a_collect_inside_a_collect_is_one_query():
    tracer = Tracer()
    with tracer.query() as outer:
        with tracer.query() as inner:
            assert inner.summary is outer.summary
    (only,) = tracer.recent_queries()
    assert only["phases"]["query"]["calls"] == 2


def test_pool_threads_book_to_the_query_that_submitted_the_task():
    tracer = Tracer()

    def work():
        with tracer.span("scan.read", "scan", bytes=7):
            pass

    with tracer.query():
        bound = tracer.bind_query(work)
    unbound = tracer.bind_query(work)       # no query: the function itself
    assert unbound is work
    with tracer.query():
        t = threading.Thread(target=bound)
        t.start()
        t.join(timeout=30)
    first, second = tracer.recent_queries()
    # the first query had returned when its straggler ran: nothing is
    # booked to a sealed summary, and nothing to the query running beside
    assert "scan.read" not in first["phases"]
    assert "scan.read" not in second["phases"]
    with tracer.query() as q:
        t = threading.Thread(target=tracer.bind_query(work))
        t.start()
        t.join(timeout=30)
    assert q.summary.to_dict()["phases"]["scan.read"]["bytes"] == 7


def test_with_the_ring_off_no_trace_event_is_built(sess, lineitem_dir,
                                                   monkeypatch):
    tracer = get_tracer()
    assert not tracer.enabled
    tracer.clear()      # what an earlier test file left in this process
    built = []

    class Counting(tracing.TraceEvent):
        def __init__(self, *a, **k):
            built.append(a[0])
            super().__init__(*a, **k)
    monkeypatch.setattr(tracing, "TraceEvent", Counting)
    run_query(sess, lineitem_dir, "q6")
    assert built == [] and tracer.events() == []
    tracer.enabled = True
    try:
        run_query(sess, lineitem_dir, "q6")
    finally:
        tracer.enabled = False
        events = tracer.events()
        tracer.clear()
    assert "query" in built and "dispatch" in built
    by_name = {e.name: e for e in events}
    assert by_name["plan"].args["parent"] == "query"
    assert by_name["plan"].args["query_id"] \
        == by_name["query"].args["query_id"]


def test_under_a_profiler_session_the_spans_are_in_the_xplane(
        sess, lineitem_dir, tmp_path):
    df = tpch.QUERIES["q6"]({"lineitem": sess.read_parquet(lineitem_dir)})
    df.collect()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        df.collect()
        df.collect()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    profile = jax.profiler.ProfileData.from_file(path)
    (host,) = [p for p in profile.planes if p.name == "/host:CPU"]
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
              dict(e.stats).get("query_id"))
             for line in host.lines for e in line.events
             if e.name.startswith("srt.")]
    queries = [s for s in spans if s[0] == "srt.query"]
    assert len(queries) == 2
    ids = {q[3] for q in queries}
    assert len(ids) == 2 and None not in ids
    for _, lo, hi, qid in queries:
        inside = [s for s in spans if s[3] == qid]
        names = {s[0] for s in inside}
        assert {"srt.plan", "srt.scan.read", "srt.h2d", "srt.dispatch",
                "srt.d2h", "srt.result"} <= names
        # one query id on all of them, all nested in the query's span
        assert all(lo <= s[1] and s[2] <= hi for s in inside)
    # every span of the capture belongs to one of the two queries
    assert {s[3] for s in spans} == ids
    # no device plane on the CPU backend: nothing to book gaps on
    assert idle_by_phase(profile) is None


def ev(name, start, dur):
    from types import SimpleNamespace as NS
    return NS(name=name, start_ns=start, duration_ns=dur, stats=())


def test_gaps_books_device_idle_time_to_the_innermost_working_span():
    from types import SimpleNamespace as NS
    host = NS(name="/host:CPU", lines=[
        NS(name="main", events=[
            ev("srt.query", 0, 1000), ev("srt.plan", 0, 100),
            ev("srt.task", 100, 800), ev("srt.wait.pipeline", 110, 490),
            ev("srt.d2h", 700, 200), ev("other", 0, 5000)]),
        NS(name="pool", events=[ev("srt.scan.read", 150, 350),
                                ev("srt.h2d", 500, 50)])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[ev("fusion.1", 600, 50),
                                   ev("fusion.2", 700, 100)]),
        NS(name="XLA Modules", events=[ev("jit_srt_stage(1)", 600, 50)])])
    got = idle_by_phase(NS(planes=[host, dev]))
    assert got["device"] == "/device:TPU:0" and got["queries"] == 1
    ns = 1e-9
    assert got["busy_s"] == pytest.approx(150 * ns)
    assert got["idle_s"] == pytest.approx(850 * ns)
    by = {k: round(v / ns) for k, v in got["idle_by_phase_s"].items()}
    # 0-100 plan; 100-110 task; 110-150 task + wait.pipeline open (the
    # later opened of two grouping spans); 150-500 the pool's scan.read
    # beats the consumer's wait; 500-550 h2d; 550-600 wait.pipeline;
    # 650-700 task; 800-900 d2h (the device finished, the host still
    # downloads); 900-1000 query
    assert by == {"plan": 100, "scan.read": 350, "h2d": 50, "d2h": 100,
                  "wait.pipeline": 90, "task": 60, "query": 100}
    assert got["named_share"] == pytest.approx(600 / 850)


# -- stable program names ---------------------------------------------------

def test_every_lowered_module_of_the_two_queries_is_named(sess, lineitem_dir,
                                                          monkeypatch):
    """After the Q6- and the Q1-shaped query, every program the engine
    compiled through its cache is ``jit_srt_<a name of the table>``."""
    from spark_rapids_tpu.utils import compile_cache
    compile_cache.clear_cache()
    jax.clear_caches()
    seen = []
    real_jit = jax.jit

    def recording_jit(fn, *a, **k):
        seen.append(getattr(fn, "__name__", repr(fn)))
        return real_jit(fn, *a, **k)
    monkeypatch.setattr(compile_cache.jax, "jit", recording_jit)
    run_query(sess, lineitem_dir, "q6")
    run_query(sess, lineitem_dir, "q1")
    assert seen, "nothing was compiled"
    assert all(n.startswith("srt_") and n[4:] in PROGRAM_NAMES
               for n in seen), seen
    assert {"srt_stage", "srt_pq_decode_fixed", "srt_pq_decode_bytes",
            "srt_agg_grouped", "srt_agg_ungrouped"} <= set(seen)
    lowered = named_jit(lambda x: x + 1, "stage").lower(1.0)
    assert "jit_srt_stage" in lowered.as_text()[:200]


def test_a_name_outside_the_table_is_refused():
    with pytest.raises(ValueError, match="PROGRAM_NAMES"):
        cached_jit("test|unnamed", lambda: (lambda x: x), name="my_kernel")
    with pytest.raises(TypeError):
        cached_jit("test|unnamed", lambda: (lambda x: x))


def test_a_name_holds_no_shape_key_or_partition_number():
    for name in PROGRAM_NAMES:
        assert name.replace("_", "").isalpha(), name


def test_the_package_passes_the_jitname_lint():
    report = analyze_paths([PKG], checks=["jitname"])
    assert [f.render() for f in report.findings] == []


@pytest.mark.parametrize("source,rule", [
    ("from spark_rapids_tpu.utils.compile_cache import cached_jit\n"
     "fn = cached_jit('k', build)\n", "jitname-missing"),
    ("from spark_rapids_tpu.utils.compile_cache import cached_jit\n"
     "fn = cached_jit('k', build, name='fn')\n", "jitname-unknown"),
    ("from spark_rapids_tpu.utils.compile_cache import cached_jit\n"
     "fn = cached_jit('k', build, name='stage' if a else 'run')\n",
     "jitname-unknown"),
    ("from spark_rapids_tpu.utils.compile_cache import cached_jit\n"
     "fn = cached_jit('k', build, name=some_variable)\n", "jitname-unknown"),
    ("from spark_rapids_tpu.utils.compile_cache import named_jit\n"
     "fn = named_jit(f, 'jit_fn')\n", "jitname-unknown"),
    ("import jax\nfn = jax.jit(lambda t: t)\n", "jitname-bare-jit"),
    ("import jax\n@jax.jit\ndef run(t):\n    return t\n", "jitname-bare-jit"),
    ("from spark_rapids_tpu.utils.compile_cache import cached_jit\n"
     "fn = cached_jit('k', build, name='stage' if a else 'sort')\n"
     "# srtpu: jitname-ok(csv_decode or json_decode, from the caller)\n"
     "g = cached_jit('k', build, name=program)\n", None),
])
def test_the_jitname_lint(tmp_path, source, rule):
    path = tmp_path / "mod.py"
    path.write_text(source)
    rules = [f.rule for f in analyze_paths([str(path)],
                                           checks=["jitname"]).findings]
    assert rules == ([rule] if rule else [])
