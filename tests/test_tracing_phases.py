"""The one span API (utils/tracing.py): per-query phase totals, spans on
the profiler's clock, stable device-program names, and the ``jitname``
lint. CPU, SF 0.01; nothing here asserts a time."""
import glob
import os
import threading

import jax
import numpy as np
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


# -- which device: sync / d2h / dispatch ---------------------------------------
#: the spans given the arrays they wait for or work on (``span(on=...)``)
ON_A_DEVICE = ("sync", "d2h", "dispatch", "agg.dense", "agg.scatter",
               "agg.merge")


def test_the_device_is_worked_out_only_while_someone_records_it(
        sess, lineitem_dir, monkeypatch, tmp_path):
    tracer = get_tracer()
    assert not tracer.enabled
    tracer.clear()
    asked = []
    real = tracing.device_of
    monkeypatch.setattr(tracing, "device_of",
                        lambda on: (asked.append(on), real(on))[1])
    phases = run_query(sess, lineitem_dir, "q1")["phases"]
    spans = sum(phases[n]["calls"] for n in ON_A_DEVICE if n in phases)
    # profiler off, ring off: nobody looks at what a span is ``on``
    assert asked == [] and spans > 10
    df = tpch.QUERIES["q1"]({"lineitem": sess.read_parquet(lineitem_dir)})
    tracer.enabled = True
    try:
        df.collect()
    finally:
        tracer.enabled = False
        events = tracer.events()
        tracer.clear()
    said = [e for e in events if e.name in ON_A_DEVICE]
    assert len(said) == len(asked) == spans
    # one CPU device holds every array of the query; a program whose
    # arguments hold no array says -1, as one over a mesh would
    assert {e.args["device"] for e in said} <= {0, -1}
    assert all(e.args["device"] == 0 for e in said if e.name != "dispatch")
    assert "device" not in {k for e in events if e not in said
                            for k in e.args}
    # under a profiler session the device rides on the annotation
    del asked[:]
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        df.collect()
    finally:
        jax.profiler.stop_trace()
    assert len(asked) == spans and tracer.events() == []
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    profile = jax.profiler.ProfileData.from_file(path)
    stats = [dict(e.stats) for plane in profile.planes
             if plane.name == "/host:CPU" for line in plane.lines
             for e in line.events
             if e.name[len("srt."):] in ON_A_DEVICE]
    assert len(stats) == spans and all("device" in st for st in stats)


@pytest.mark.parametrize("values,want", [
    (lambda d: [jax.device_put(1, d[2]), 7, "x"], 2),
    (lambda d: {"a": jax.device_put(1, d[1]), "b": jax.device_put(2, d[1])}, 1),
    (lambda d: [jax.device_put(1, d[0]), jax.device_put(1, d[3])], -1),
    (lambda d: jax.device_put(
        np.arange(8), jax.sharding.NamedSharding(
            jax.sharding.Mesh(np.array(d[:4]), ("dp",)),
            jax.sharding.PartitionSpec("dp"))), -1),
    (lambda d: (3, np.arange(4)), -1),
], ids=["one", "same", "several", "sharded", "none"])
def test_device_of_names_the_one_device_or_none(values, want):
    devices = jax.devices()
    if len(devices) < 4:
        pytest.skip("needs 4 virtual devices")
    assert tracing.device_of(values(devices)) == want


# -- span arguments as per-query counters ---------------------------------------
def test_counted_arguments_are_summed_per_phase_and_read_by_field(
        monkeypatch):
    tracer = Tracer()
    for rounds in (4, 6):
        with tracer.query():
            with tracer.span("agg.scatter", "agg", rows=1024, groups=7) as sp:
                sp.note(rounds=rounds, full_rounds=1)   # known after the run
            with tracer.span("agg.scatter", "agg", rows=2048, groups=0):
                pass
            with tracer.span("join.prep", "join", rows=512, unique=True):
                pass
            with tracer.span("join.prep", "join", rows=512, unique=False,
                             rounds=0):
                pass
            with tracer.span("dispatch", "dispatch", program="srt_x",
                             rows="many"):
                pass
            with tracer.span("sync", "download", scalars=3):
                pass
    first, last = tracer.recent_queries()
    phases = last["phases"]
    assert phases["agg.scatter"] == {
        "calls": 2, "self_s": phases["agg.scatter"]["self_s"], "bytes": 0,
        "rows": 3072, "groups": 7, "rounds": 6, "full_rounds": 1}
    # a boolean counts 0 / 1; a sum of zero is left out, as is what is not
    # a number or not in COUNTED_ARGS
    assert phases["join.prep"] == {
        "calls": 2, "self_s": phases["join.prep"]["self_s"], "bytes": 0,
        "rows": 1024, "unique": 1}
    assert set(phases["dispatch"]) == {"calls", "self_s", "bytes"}
    assert phases["sync"]["scalars"] == 3
    assert tracing.COUNTED_ARGS >= {"rows", "rows_out", "groups", "rounds",
                                    "full_rounds", "parts", "scalars"}
    # the benchmark's reader, as it stands, reads a counter through ``field``
    from benchmark.readers import query_phases
    monkeypatch.setattr(tracing, "get_tracer", lambda: tracer)
    run = {"trace": {"queries": 2,
                     "span_s": first["wall_s"] + last["wall_s"]}}
    read = query_phases.read
    assert read(run, phases=["agg.scatter"], field="rounds") == (4 + 6) / 2
    assert read(run, phases=["agg.scatter", "join.prep"], field="rows") \
        == 3072 + 1024
    assert read(run, phases=["join.prep"], field="rounds") == 0
    assert read(run, phases=["sync"], field="calls") == 1


def ev(name, start, dur, **stats):
    from types import SimpleNamespace as NS
    return NS(name=name, start_ns=start, duration_ns=dur,
              stats=tuple(stats.items()))


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


def mesh_profile():
    """Four device planes. Device 0 is the busiest (300 ns) and idles over
    100-600 and 800-1000 of a 1000-ns query; meanwhile the host sits in a
    ``join.prep`` whose ``sync`` waits first for device 1, then for device
    2, each in a prep program, and later under nothing but a ``stage``."""
    from types import SimpleNamespace as NS

    def dev(n, ops, mods):
        return NS(name=f"/device:TPU:{n}", lines=[
            NS(name="XLA Ops", events=[ev(f"fusion.{i}", s, d)
                                       for i, (s, d) in enumerate(ops)]),
            NS(name="XLA Modules", events=[ev(m, s, d) for m, s, d in mods])])
    host = NS(name="/host:CPU", lines=[NS(name="main", events=[
        ev("srt.query", 0, 1000),
        ev("srt.join.prep", 120, 330, rows=512),
        ev("srt.sync", 140, 160, scalars=3, device=1),
        ev("srt.sync", 300, 120, scalars=1, device=2),
        ev("srt.stage", 450, 550), ev("srt.stage.stats", 460, 40)])])
    return NS(planes=[
        host,
        dev(0, [(0, 100), (600, 200)],
            [("jit_srt_stage(1)", 0, 100), ("jit_srt_concat(2)", 600, 200)]),
        dev(1, [(150, 150)], [("jit_srt_join_prep_hash(3)", 150, 150)]),
        dev(2, [(250, 170), (850, 50)],
            [("jit_srt_join_prep_hash(4)", 250, 170),
             ("jit_convert_element_type(9)", 850, 50)]),
        dev(3, [], [])])


def test_gaps_books_a_mesh_gap_to_the_device_that_was_busy():
    got = idle_by_phase(mesh_profile())
    ns = 1e-9
    assert got["device"] == "/device:TPU:0"
    assert (round(got["busy_s"] / ns), round(got["idle_s"] / ns)) == (300, 700)
    # the host's view is the one-device one: every gap to a host span
    assert {k: round(v / ns) for k, v in got["idle_by_phase_s"].items()} \
        == {"stage": 310, "sync": 280, "join.prep": 50, "stage.stats": 40,
            "query": 20}
    by_cause = {(r["cause"], r["host_span"], r["host_device"]):
                round(r["s"] / ns) for r in got["idle_by_cause_s"]}
    prep = "jit_srt_join_prep_hash"
    assert by_cause == {
        # another device busy: that device and the module it ran; where two
        # are (250-300), the busier of them; the host span stands beside it
        ("/device:TPU:1: " + prep, "sync", 1): 100,
        ("/device:TPU:2: " + prep, "sync", 1): 50,
        ("/device:TPU:2: " + prep, "sync", 2): 120,
        ("/device:TPU:2: jit_convert_element_type", "stage", None): 50,
        # every device idle: the host span, ranked as on one device
        ("host: query", "query", None): 20,
        ("host: join.prep", "join.prep", None): 50,
        ("host: sync", "sync", 1): 10,
        ("host: stage", "stage", None): 260,
        ("host: stage.stats", "stage.stats", None): 40}
    assert [r["s"] for r in got["idle_by_cause_s"]] \
        == sorted((r["s"] for r in got["idle_by_cause_s"]), reverse=True)
    # named: a jit_srt_* module of another device, or a working host span
    assert got["named_share"] == pytest.approx(370 / 700)
    devices = {d: (round(r["busy_s"] / ns), round(r["idle_s"] / ns),
                   {m: round(v / ns) for m, v in r["modules_s"].items()})
               for d, r in got["devices"].items()}
    assert devices == {
        "/device:TPU:0": (300, 700, {"jit_srt_concat": 200,
                                     "jit_srt_stage": 100}),
        "/device:TPU:1": (150, 850, {prep: 150}),
        "/device:TPU:2": (220, 780, {prep: 170,
                                     "jit_convert_element_type": 50}),
        "/device:TPU:3": (0, 1000, {})}
    first, second = got["longest_gaps"]
    assert (round(first["offset_s"] / ns), round(first["length_s"] / ns)) \
        == (100, 500)
    assert first["cause"] == "/device:TPU:2: " + prep
    assert first["cause_share"] == pytest.approx(170 / 500)
    assert (first["host_span"], first["args"]) \
        == ("sync", {"device": 2, "scalars": 1})
    assert (round(second["offset_s"] / ns), second["cause"],
            second["host_span"], second["args"]) \
        == (800, "host: stage", "stage", {})


def test_on_one_device_every_gap_has_a_host_span_for_its_cause():
    """What ``gaps`` added for a mesh says nothing new on one device: the
    causes are the host spans of ``idle_by_phase_s``, second for second."""
    from types import SimpleNamespace as NS
    host = NS(name="/host:CPU", lines=[NS(name="main", events=[
        ev("srt.query", 0, 1000), ev("srt.plan", 0, 100),
        ev("srt.task", 100, 800), ev("srt.d2h", 700, 250, device=0)])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[ev("fusion.1", 600, 50),
                                   ev("fusion.2", 700, 100)]),
        NS(name="XLA Modules", events=[ev("jit_srt_stage(1)", 600, 50)])])
    got = idle_by_phase(NS(planes=[host, dev]))
    assert {r["cause"]: r["s"] for r in got["idle_by_cause_s"]} \
        == {"host: " + k: v for k, v in got["idle_by_phase_s"].items()}
    assert all(r["device"] is None for r in got["idle_by_cause_s"])
    assert list(got["devices"]) == ["/device:TPU:0"]
    assert got["devices"]["/device:TPU:0"]["busy_s"] == got["busy_s"]
    # 0-600 (plan, then task), 800-1000 (d2h, then query), 650-700
    assert [(g["cause"], round(g["length_s"] / 1e-9))
            for g in got["longest_gaps"]] \
        == [("host: task", 600), ("host: d2h", 200), ("host: task", 50)]
    assert got["longest_gaps"][1]["args"] == {"device": 0}


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
