"""reduce_trace.py and the readers over it, on a hand-made trace whose
numbers can be checked by eye, and on the recorded v5e fixture."""
import json
import os
from types import SimpleNamespace as NS

import pytest

from benchmark import cells, reduce_trace

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000   # ns


def ev(name, start_ms, dur_ms):
    return NS(name=name, start_ns=start_ms * MS, duration_ns=dur_ms * MS)


def device(n, ops, mods, async_ops=()):
    return NS(name=f"/device:TPU:{n}", lines=[
        NS(name="XLA Ops", events=[ev(*o) for o in ops]),
        NS(name="Async XLA Ops", events=[ev(*o) for o in async_ops]),
        NS(name="XLA Modules", events=[ev(*m) for m in mods]),
        NS(name="Steps", events=[ev("ignored", 0, 10_000)])])


@pytest.fixture
def profile():
    """Two collects, 0-1000 ms and 1200-2000 ms, on two devices. Device 0:
    ops 100-400 (with a nested op and an overlapping async copy), 600-900,
    1300-1500, and one op outside the window; device 1: 100-200 only."""
    host = NS(name="/host:CPU", lines=[
        NS(name="main", events=[ev("bench.collect", 0, 1000),
                                ev("other", 0, 5000),
                                ev("bench.collect", 1200, 800)])])
    dev0 = device(0, ops=[
        ("while.1", 100, 300), ("fusion.2", 150, 50),       # nested
        ("copy-start.3", 350, 100),                          # overlaps: -> 450
        ("fusion.4", 600, 300), ("all-to-all.5", 1300, 200),
        ("fusion.6", 2500, 100)],                            # after the window
        mods=[("jit_fn(11)", 100, 350), ("jit_run(12)", 600, 300),
              ("jit_run(13)", 1300, 200), ("jit_late(14)", 2500, 100)],
        # an async collective, start to done: counted as collective time
        # (overlapping the all-to-all by 50 ms), never as busy time
        async_ops=[("%all-gather-start.7 = (f32[8]) all-gather-start(...)",
                    1450, 150)])
    dev1 = device(1, ops=[("fusion.9", 100, 100)], mods=[("jit_fn(21)", 100, 100)])
    return NS(planes=[host, dev0, dev1,
                      NS(name="/device:TPU:0 extra", lines=[])])


def test_window_is_first_span_start_to_last_span_end(profile):
    r = reduce_trace.reduce_trace(profile)
    assert r["queries"] == 2 and r["devices"] == 2
    assert r["window_s"] == pytest.approx(2.0)
    assert r["span_s"] == pytest.approx(1.8)


def test_busy_is_the_union_of_op_intervals_clipped_to_the_window(profile):
    r = reduce_trace.reduce_trace(profile)
    # device 0: 100-450, 600-900, 1300-1500 = 850 ms; the op at 2500 is out
    assert r["busy_s_by_device"] == pytest.approx([0.85, 0.1])
    assert r["busiest_device"] == 0
    assert r["busy_s_busiest"] == pytest.approx(0.85)
    assert r["busy_s_mean"] == pytest.approx(0.475)
    assert r["busy_in_spans_s"] == pytest.approx(0.85)
    assert r["collective_s"] == pytest.approx(0.3)    # 1300-1600


def test_seconds_by_module_strip_the_run_id(profile):
    r = reduce_trace.reduce_trace(profile)
    assert dict(r["module_s"]) == pytest.approx(
        {"jit_run": 0.5, "jit_fn": 0.35, "jit_late": 0.0})
    assert r["op_s"][0] == ("while.1", pytest.approx(0.3))


def test_idle_gaps_are_labelled_by_the_collect_in_flight(profile):
    r = reduce_trace.reduce_trace(profile)
    # inside: 0-100, 450-600, 900-1000 (cut at the span end by the midpoint
    # rule: the gap 900-1300 has its midpoint between collects), 1500-2000
    assert sum(r["idle_s"].values()) == pytest.approx(2.0 - 0.85)
    gaps = {(round(g, 3), where, b, a) for g, where, b, a in r["longest_gaps"]}
    assert (0.5, "inside collect", "jit_run", "jit_late") in gaps
    assert (0.4, "between collects", "jit_run", "jit_run") in gaps
    assert (0.1, "inside collect", "window start", "jit_fn") in gaps
    b = reduce_trace.breakdown(r)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0] == ["module jit_run", pytest.approx(0.5)]


def run_of(profile, **more):
    return {"trace": reduce_trace.reduce_trace(profile), "counters": {},
            "memory": [], "chips": 2, **more}


@pytest.mark.parametrize("metric,want", [
    ("device_busy_s_per_query", 0.425),
    ("host_s_per_query", (1.8 - 0.85) / 2),
    ("device_idle_share", 100 * (1 - 0.85 / 2.0)),
    # 1 GB over two chips at 819 GB/s each, against 0.425 s busy a query
    ("query_hbm_roofline", 100 * (1e9 / (2 * 819e9)) / 0.425),
])
def test_readers_over_the_reduction(profile, metric, want):
    read, args = cells.load_reader(metric)
    run = run_of(profile, input_bytes=1e9, peaks=cells.peaks("TPU v5 lite"))
    assert read(run, **args) == pytest.approx(want)


@pytest.mark.parametrize("metric", [
    "device_busy_s_per_query", "host_s_per_query", "device_idle_share",
    "query_hbm_roofline",
    "setup_cache_hit_share", "peak_hbm_share", "window_xla_compiles"])
def test_a_reader_with_nothing_to_read_returns_nothing(metric):
    host_only = NS(planes=[NS(name="/host:CPU", lines=[
        NS(name="main", events=[ev("bench.collect", 0, 1000)])])])
    read, args = cells.load_reader(metric)
    run = run_of(host_only, input_bytes=1e9, peaks=None)
    assert read(run, **args) is None


def test_counter_readers():
    run = {"trace": {}, "memory": [
        {"peak_bytes_in_use": 1e9, "bytes_limit": 16e9},
        {"peak_bytes_in_use": 4e9, "bytes_limit": 16e9}, None],
        "counters": {"window_xla_compiles": 0, "setup_xla_compiles": 40,
                     "setup_xla_cache_hits": 30, "setup_xla_compile_s": 2.5,
                     "first_collect_s": 29.7}}
    got = {m: cells.load_reader(m)[0](run, **cells.load_reader(m)[1])
           for m in ("window_xla_compiles", "setup_xla_compile_s",
                     "setup_cache_hit_share", "first_collect_s",
                     "peak_hbm_share")}
    assert got == {"window_xla_compiles": 0, "setup_xla_compile_s": 2.5,
                   "setup_cache_hit_share": 75.0, "first_collect_s": 29.7,
                   "peak_hbm_share": 25.0}


# ---- the recorded fixture: three collect() of sf1.q6 on one v5e ----------
@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData
    with open(os.path.join(HERE, "fixtures", "sf1.q6.v5e.xplane.txt")) as f:
        return reduce_trace.reduce_trace(ProfileData.from_text_proto(f.read()))


def test_recorded_trace_reads_through_profile_data(recorded):
    r = recorded
    assert r["queries"] == 3 and r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.284742, abs=1e-6)
    assert r["span_s"] == pytest.approx(0.284530, abs=1e-6)
    # host and device share a clock: all the device work lies inside spans
    assert r["busy_in_spans_s"] == pytest.approx(r["busy_s_busiest"])
    assert r["busy_s_busiest"] == pytest.approx(0.000353186, abs=1e-9)
    assert "collective_s" not in r


def test_recorded_trace_names_modules_ops_and_gaps(recorded):
    r = recorded
    mods = dict(r["module_s"])
    assert list(mods)[:2] == ["jit_run", "jit__concat_impl"]
    assert mods["jit_run"] == pytest.approx(0.000233321, abs=1e-9)
    # the device ran nothing outside its programs
    assert sum(mods.values()) >= r["busy_s_busiest"]
    assert r["op_s"][0][0] == "%fusion.3"
    assert any(n.endswith("X64SplitHigh") for n, _ in r["op_s"])
    assert r["idle_s"]["between collects"] == pytest.approx(0, abs=1e-3)
    assert sum(r["idle_s"].values()) + r["busy_s_busiest"] \
        == pytest.approx(r["window_s"])
    # the long gaps are the host's scan at the head of each collect()
    assert [(w, a) for _, w, _, a in r["longest_gaps"][:3]] \
        == [("inside collect", "jit_convert_element_type")] * 3
    assert {b for _, _, b, _ in r["longest_gaps"][:3]} \
        == {"window start", "jit_ungrouped"}


def test_recorded_trace_gives_a_host_bound_query(recorded):
    run = {"trace": recorded, "counters": {}, "memory": [], "chips": 1,
           "input_bytes": 6_000_000 * 28, "peaks": cells.peaks("TPU v5 lite")}
    got = {m: cells.load_reader(m)[0](run, **cells.load_reader(m)[1])
           for m in ("host_s_per_query", "device_busy_s_per_query",
                     "device_idle_share", "query_hbm_roofline")}
    assert got["host_s_per_query"] == pytest.approx(0.0947, abs=1e-4)
    assert got["device_busy_s_per_query"] == pytest.approx(1.177e-4, abs=1e-7)
    assert got["device_idle_share"] == pytest.approx(99.876, abs=1e-3)
    # Q6's filter runs in the host reader: the input counted never reaches
    # the chip, and a share of it by busy time passes 100 %. The cell is
    # therefore not among the metric's workloads.
    assert got["query_hbm_roofline"] > 100
    assert "query_hbm_roofline" not in cells.load_cell("sf1.q6").per_layer
    assert "query_hbm_roofline" in cells.load_cell("sf1.q1").per_layer
