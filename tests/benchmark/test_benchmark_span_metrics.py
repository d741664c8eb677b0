"""The per-layer metrics that read the program's stable program names and
its per-query phase totals: the three readers on hand-made input, on the
recorded v5e fixture, and through a whole traced rehearsal (CPU, SF 0.01;
no value of a rehearsal is ever recorded)."""
import os
import re
from types import SimpleNamespace as NS

import pytest

from benchmark import cells, data, reduce_trace, run
from benchmark.readers import query_phases

HERE = os.path.dirname(os.path.abspath(__file__))
NEW = ("decode_s_per_query", "stage_s_per_query", "compact_s_per_query",
       "unnamed_program_share", "plan_s_per_query", "scan_host_s_per_query",
       "h2d_s_per_query", "device_wait_s_per_query",
       "host_unattributed_share", "programs_per_query",
       "host_syncs_per_query")


def read(metric, run_):
    fn, args = cells.load_reader(metric)
    return fn(run_, **args)


# ---- seconds by stable program name ---------------------------------------
MODULES = [("jit_srt_pq_decode_fixed", 8.0), ("jit_srt_stage", 6.0),
           ("jit_srt_pq_decode_bytes", 4.0), ("jit_srt_compact", 1.5),
           ("jit_convert_element_type", 0.25), ("jit_srt_compaction", 0.25)]


def trace_run(modules=MODULES, queries=2):
    return {"trace": {"queries": queries, "module_s": modules}}


@pytest.mark.parametrize("metric,want", [
    ("decode_s_per_query", (8.0 + 4.0) / 2),     # both decoders, one prefix
    ("stage_s_per_query", 6.0 / 2),
    ("compact_s_per_query", (1.5 + 0.25) / 2),   # a prefix, not a name
    ("unnamed_program_share", 100 * 0.25 / 20.0),
])
def test_module_seconds_sum_a_prefix_per_traced_query(metric, want):
    assert read(metric, trace_run()) == pytest.approx(want)


@pytest.mark.parametrize("metric", ["decode_s_per_query", "stage_s_per_query",
                                    "compact_s_per_query"])
def test_no_module_under_the_prefix_is_nothing_to_read(metric):
    assert read(metric, trace_run([("jit_fn", 12.0), ("jit_run", 8.0)])) \
        is None
    assert read(metric, {"trace": {"queries": 2}}) is None


def test_unnamed_share_of_a_program_without_the_names_is_all_of_it():
    assert read("unnamed_program_share",
                trace_run([("jit_fn", 12.0), ("jit_run", 8.0)])) == 100.0
    assert read("unnamed_program_share", {"trace": {"queries": 2}}) is None
    assert read("unnamed_program_share", trace_run([("jit_fn", 0.0)])) is None


# ---- the recorded fixture predates the names ------------------------------
def fixture_text():
    with open(os.path.join(HERE, "fixtures", "sf1.q6.v5e.xplane.txt")) as f:
        return f.read()


def reduced(text):
    from jax.profiler import ProfileData
    return reduce_trace.reduce_trace(ProfileData.from_text_proto(text))


def test_the_recorded_trace_reads_all_unnamed_and_a_renamed_copy_what_is_left():
    old = {"trace": reduced(fixture_text())}
    assert read("unnamed_program_share", old) == 100.0
    assert read("stage_s_per_query", old) is None
    # the same trace as the program names its modules now
    renamed = fixture_text()
    for was, now in (("jit_run", "jit_srt_stage"),
                     ("jit__concat_impl", "jit_srt_concat"),
                     ("jit_ungrouped", "jit_srt_agg_ungrouped"),
                     ("jit_fn", "jit_srt_op_project")):
        renamed, n = re.subn(rf'name: "{was}\(', f'name: "{now}(', renamed)
        assert n == 1, was
    new = {"trace": reduced(renamed)}
    mods = dict(new["trace"]["module_s"])
    left = sum(s for n, s in mods.items() if not n.startswith("jit_srt_"))
    assert 0 < left < sum(mods.values())
    assert set(n for n in mods if not n.startswith("jit_srt_")) \
        == {"jit_convert_element_type"}
    assert read("unnamed_program_share", new) \
        == pytest.approx(100 * left / sum(mods.values()))
    assert read("stage_s_per_query", new) \
        == pytest.approx(dict(old["trace"]["module_s"])["jit_run"] / 3)


# ---- the phase reader ------------------------------------------------------
def summary(wall, covered, **phases):
    return {"query_id": 1, "wall_s": wall, "covered_s": covered, "threads": 2,
            "spans_dropped": 0,
            "phases": {n.replace("_", "."): {"calls": c, "self_s": s,
                                             "bytes": 0}
                       for n, (c, s) in phases.items()}}


RECENT = [summary(9.0, 9.0, plan=(1, 9.0)),                 # a warm-up query
          summary(0.10, 0.08, plan=(1, 0.002), scan_read=(2, 0.06),
                  scan_parse=(2, 0.01), h2d=(2, 0.004), sync=(2, 0.001),
                  d2h=(1, 0.003), dispatch=(5, 0.001)),
          summary(0.12, 0.09, plan=(1, 0.004), scan_read=(2, 0.08),
                  scan_parse=(2, 0.01), h2d=(2, 0.006), sync=(4, 0.003),
                  d2h=(1, 0.003), dispatch=(7, 0.003))]


@pytest.fixture
def tracer_with(monkeypatch):
    """The program's tracer answering ``recent_queries`` from a list."""
    from spark_rapids_tpu.utils import tracing

    def install(recent):
        fake = NS(recent_queries=lambda n=256: recent[-n:] if n else [])
        monkeypatch.setattr(tracing, "get_tracer", lambda: fake)
    return install


def phase_run(span_s=0.22, queries=2):
    return {"trace": {"queries": queries, "span_s": span_s}}


def test_phase_metrics_are_means_over_the_windows_own_summaries(tracer_with):
    tracer_with(RECENT)
    got = {m: read(m, phase_run()) for m in NEW[4:]}
    assert got == pytest.approx({
        "plan_s_per_query": 0.003,
        "scan_host_s_per_query": (0.06 + 0.01 + 0.08 + 0.01) / 2,
        "h2d_s_per_query": 0.005,
        "device_wait_s_per_query": (0.001 + 0.003 + 0.003 + 0.003) / 2,
        "host_unattributed_share": 100 * (1 - 0.17 / 0.22),
        "programs_per_query": 6.0,
        "host_syncs_per_query": 4.0})


def test_a_phase_that_never_ran_counts_zero_not_nothing(tracer_with):
    tracer_with([summary(0.1, 0.05, plan=(1, 0.01))])
    assert read("host_syncs_per_query", phase_run(0.1, 1)) == 0
    assert read("h2d_s_per_query", phase_run(0.1, 1)) == 0


@pytest.mark.parametrize("recent,span_s,queries", [
    (RECENT[1:], 0.22, 3),          # fewer summaries than traced queries
    (RECENT, 0.22 * 1.03, 2),       # walls 3 % under the spans' length
    (RECENT, 0.22 / 1.03, 2),       # and 3 % over
    (RECENT, 0.22, 3),              # the warm-up's summary among them
    ([], 0.22, 2),
    (RECENT[:2] + [dict(RECENT[2], wall_s=None)], 0.22, 2),   # unfinished
], ids=["fewer", "under", "over", "not-the-windows", "none", "unfinished"])
def test_the_phase_reader_refuses_summaries_that_are_not_the_windows(
        tracer_with, recent, span_s, queries):
    tracer_with(recent)
    for metric in NEW[4:]:
        assert read(metric, phase_run(span_s, queries)) is None


def test_walls_within_two_percent_of_the_spans_are_the_windows(tracer_with):
    tracer_with(RECENT)
    assert read("programs_per_query", phase_run(0.22 * 1.019)) == 6.0
    assert read("programs_per_query", phase_run(0.22 / 1.019)) == 6.0
    assert query_phases.WALL_TOLERANCE == 0.02


def test_a_program_without_phase_totals_has_nothing_to_read(tracer_with,
                                                            monkeypatch):
    from spark_rapids_tpu.utils import tracing
    monkeypatch.setattr(tracing, "get_tracer", lambda: NS())   # the parent's
    for metric in NEW[4:]:
        assert read(metric, phase_run()) is None
    assert read("plan_s_per_query", {"trace": {}}) is None


# ---- manifest: eleven added entries, nothing else touched ------------------
def test_the_new_metrics_are_the_manifests_last_eleven_and_move_query_s():
    per_layer = cells.manifest()["per_layer"]
    assert tuple(m["name"] for m in per_layer[-11:]) == NEW
    for m in per_layer[-11:]:
        assert m["moves"] == "query_s" and m["better"] == "lower"
    by_name = {m["name"]: m for m in per_layer}
    assert by_name["decode_s_per_query"]["workloads"] == ["sf1.q1"]
    assert by_name["compact_s_per_query"]["workloads"] == ["sf1.q1"]
    assert {m["layer"] for m in per_layer[-11:-7]} == {"device programs"}
    assert {m["layer"] for m in per_layer[-7:]} \
        == {"session, planner, host scan"}
    assert {m["source"] for m in per_layer[-7:]} \
        == {"program_span", "program_counter"}


# ---- a whole traced rehearsal ----------------------------------------------
def with_a_device_plane(profile):
    """The CPU backend traces no device plane. Give the real profile one in
    which each ``srt.dispatch`` span of the host plane is the program it
    names, busy for the span's length: what the module readers then find is
    what the program's spans said."""
    planes = list(profile.planes)
    (host,) = [p for p in planes if p.name == reduce_trace.HOST_PLANE]
    calls = [(dict(e.stats)["program"], e.start_ns, e.duration_ns)
             for line in host.lines for e in line.events
             if e.name == "srt.dispatch"]
    mods = [NS(name=f"jit_{prog}({i})", start_ns=s, duration_ns=d)
            for i, (prog, s, d) in enumerate(calls)]
    ops = [NS(name=f"%fusion.{i} = f64[8]{{0}} fusion()", start_ns=s,
              duration_ns=d) for i, (_, s, d) in enumerate(calls)]
    device = NS(name="/device:TPU:0", lines=[
        NS(name=reduce_trace.OPS_LINE, events=ops),
        NS(name=reduce_trace.MODULES_LINE, events=mods)])
    return NS(planes=planes + [device])


@pytest.mark.parametrize("cell_name", ["sf1.q1", "sf1.q6"])
def test_a_traced_rehearsal_prints_every_new_metric_of_the_cell(
        tmp_path, monkeypatch, cell_name):
    monkeypatch.setattr(data, "DATA_DIR", str(tmp_path / "data"))
    monkeypatch.setattr(run, "TRACE_DIR", str(tmp_path / "trace"))
    load = reduce_trace.load
    monkeypatch.setattr(reduce_trace, "load",
                        lambda path: with_a_device_plane(load(path)))
    cell = cells.load_cell(cell_name)
    r = run.drive(cell, 5, 0.3, True, scale=0.01)
    assert r["correct"] is True and r["attempted"] >= 1
    mine = [m for m in NEW if m in cell.per_layer]
    assert len(mine) == (11 if cell_name == "sf1.q1" else 9)
    for name in mine:
        assert r["metrics"].get(name, {}).get("value") is not None, name
    values = {m: r["metrics"][m]["value"] for m in mine}
    assert values["programs_per_query"] >= 3
    assert values["host_syncs_per_query"] >= 1
    assert 0 <= values["host_unattributed_share"] < 100
    assert 0 <= values["unnamed_program_share"] <= 100
    assert all(v >= 0 for v in values.values())
