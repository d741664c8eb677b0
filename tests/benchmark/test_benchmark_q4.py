"""The cell ``sf1.q4`` on the CPU backend at SF 0.02: the program's Q4 and the
plain reference agree exactly, a sound whole run is ``correct``, and whole
runs with a fault driven through them are not. Q4's answer holds no float
column, so the precision control has nothing to fail on (pinned below): the
faults that guard this cell are semantic ones — the join run as another
join, a predicate of the build side dropped, input left out, a host
fallback. The chip readings at SF 1 are in PERF.md."""
import numpy as np
import pytest

from benchmark import cells, compare, data, engine, references, run, tables

SCALE = 0.02
SEED = 2**31 + 32
CELL = "sf1.q4"
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]


@pytest.fixture(scope="module")
def data_dir(tmp_path_factory):
    mp = pytest.MonkeyPatch()
    tmp = tmp_path_factory.mktemp("q4")
    mp.setattr(data, "DATA_DIR", str(tmp / "data"))
    mp.setattr(run, "TRACE_DIR", str(tmp / "trace"))
    yield tmp
    mp.undo()


@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(CELL)


# ---- the configuration the cell runs ------------------------------------------
def test_the_cell_runs_a_configuration_of_its_own(cell):
    """A ``model_config`` PR brings a configuration that a cell runs: its
    own name, source and file, the strict session, both tables' rows, and
    a plan rule that demands a hash join on the device (the shuffled one at
    SF 1, the broadcast one where AQE demotes it, as at this file's scale)."""
    configs = cells.manifest()["configs"]
    entry = next(c for c in configs if c["name"] == cell.config_name)
    assert cell.config_name == cell.config["name"] == "tpch-sf1-q4-1chip"
    assert entry["file"] == "benchmark/configs/tpch-sf1-q4-1chip.json"
    for other in configs:
        if other is not entry:
            assert other["source"] != entry["source"]
            assert other["file"] != entry["file"]
    assert "Q4" in entry["source"] and "cl. 2.4.4" in entry["source"]
    assert [w["name"] for w in cells.manifest()["workloads"]
            if w["config"] == cell.config_name] == [CELL]
    assert cell.config["rows"] == {"orders": 1_500_000, "lineitem": 6_000_000}
    assert set(cell.config["rows"]) == set(cell.traffic["columns"])
    assert cell.config["scale_factor"] == 1 and cell.config["mesh"] is None
    assert cell.config["session_conf"] == {
        "spark.rapids.sql.test.enabled": True,
        "spark.rapids.tpu.fallback.enabled": False,
        "spark.rapids.tpu.fallback.quarantine.enabled": False}
    assert set(cell.config["reduced"]) == {"tables"} == set(entry["reduced"])
    rules = cell.config["plan"]
    assert engine.plan_faults(
        ["TpuHashAggregateExec", "TpuParquetScanExec"], rules) == [
        "none of TpuShuffledHashJoinExec/TpuBroadcastHashJoinExec planned"]
    for join in ("TpuShuffledHashJoinExec", "TpuBroadcastHashJoinExec"):
        assert engine.plan_faults(["TpuHashAggregateExec", join], rules) == []
    assert engine.plan_faults(
        ["TpuBroadcastNestedLoopJoinExec"], rules) != []


# ---- the program against the reference --------------------------------------
@pytest.mark.parametrize("seed", [SEED, 11, 77])
def test_q4_equals_the_reference_exactly(data_dir, cell, seed):
    root = data.ensure_data(cell.config, list(cell.traffic["columns"]), seed,
                            SCALE)
    sess = engine.open_session(cell.config)
    try:
        df = engine.build_query(sess, root, cell.config, cell.traffic)
        got = df.collect().to_pandas()
        nodes = engine.executed_nodes(sess.executed_plan)
        phases = sess.last_query_phases()["phases"]
    finally:
        sess.close()
    ref = references.compute(cell.traffic["reference"], root,
                             cell.traffic["columns"])
    assert list(got.columns) == list(ref.columns) \
        == ["o_orderpriority", "order_count"]
    assert list(ref.o_orderpriority) == PRIORITIES
    assert compare.answer_gap(got, ref) == (0.0, 0)
    assert ref.order_count.sum() > 0.9 * 0.03 * 1_500_000 * SCALE
    # a left-semi hash join on the device, and nothing off it
    # (AQE demotes it to broadcast at this scale; the chip plans the
    # shuffled one, PERF.md section 4)
    assert [n for n in nodes if "Join" in n] in (
        ["TpuShuffledHashJoinExec"], ["TpuBroadcastHashJoinExec"])
    assert engine.plan_faults(nodes, cell.config["plan"]) == []
    for name in ("join.build", "join.prep", "join.probe.pk", "agg.dense"):
        assert phases[name]["calls"] >= 1, (name, sorted(phases))


# ---- the generator against the traffic file's stated shares -----------------
def test_the_generator_gives_the_shares_the_traffic_file_states(cell):
    """``what`` states them for SF 1; every seed and scale draws from the
    same distributions (the multiplicity tail is shorter at a smaller
    scale: 15 at SF 1, 10 or more here)."""
    what = cell.traffic["what"]
    for stated in ("3.8 %", "74.6 %", "94.8 %", "up to ~15", "1,500,000",
                   "6,000,000", "6,001,215", "2 of the 8 tables"):
        assert stated in what, stated
    orders = tables.generate("orders", SCALE, SEED).to_pandas()
    li = tables.generate("lineitem", SCALE, SEED).to_pandas()
    od = references.days(orders.o_orderdate)
    quarter = orders[(od >= references.day("1993-07-01"))
                     & (od < references.day("1993-10-01"))]
    assert 0.034 < len(quarter) / len(orders) < 0.042
    late = li[references.days(li.l_commitdate)
              < references.days(li.l_receiptdate)]
    assert 0.74 < len(late) / len(li) < 0.75
    # an order's date is drawn apart from its lines, so the share of orders
    # with a late line is read over all of them (the quarter's 1,200 rows
    # here would spread it by +-1.3 %)
    assert 0.94 < orders.o_orderkey.isin(late.l_orderkey).mean() < 0.96
    assert 0.92 < quarter.o_orderkey.isin(late.l_orderkey).mean() < 0.98
    held = late.l_orderkey.value_counts()
    assert held.max() >= 8 and 3.0 < held.mean() < 3.3
    assert set(cell.traffic["columns"]) == {"orders", "lineitem"}


# ---- the control that is none ------------------------------------------------
def test_the_float32_control_reads_nothing_in_this_mix(data_dir, cell):
    """Every other mix's control (the reference in float32) fails its
    ``max_rel_err``. Q4 counts orders by a string: there is no float to
    round, the control is the reference itself, and a limit of 0 passes it.
    That is why this file drives semantic faults through whole runs."""
    root = data.ensure_data(cell.config, list(cell.traffic["columns"]), SEED,
                            SCALE)
    args = (cell.traffic["reference"], root, cell.traffic["columns"])
    ref = references.compute(*args)
    assert not [c for c in ref.columns if ref[c].dtype.kind == "f"]
    assert compare.answer_gap(references.compute(*args, np.float32), ref) \
        == (0.0, 0)
    assert cell.traffic["limits"] == {"max_rel_err": 0, "exact_mismatches": 0,
                                      "failed_queries": 0}


# ---- whole runs, sound and with a fault driven through them -------------------
def drive(cell):
    return run.drive(cell, SEED, 0.2, False, scale=SCALE)


def test_a_sound_run_is_correct(data_dir, cell):
    r = drive(cell)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 1
    assert set(r["metrics"]) == {"query_s", "setup_s"} == set(cell.end_to_end)
    assert r["workload"] == CELL and list(r)[-1] == "compared"
    assert all(v["value"] == 0 for v in r["compared"].values())


def join_as_inner(monkeypatch):
    """The EXISTS planned as an inner join: an order counts once for every
    late line it has."""
    from spark_rapids_tpu.session import DataFrame
    real = DataFrame.join
    monkeypatch.setattr(
        DataFrame, "join", lambda self, other, *a, how="inner", **k: real(
            self, other, *a, how="inner" if how == "left_semi" else how, **k))


def drop_the_late_filter(monkeypatch):
    """The build side's predicate dropped: any line keeps its order."""
    from spark_rapids_tpu.session import DataFrame
    real = DataFrame.filter
    monkeypatch.setattr(
        DataFrame, "filter", lambda self, cond: self
        if repr(cond) == "Column(col('late'))" else real(self, cond))


def leave_out_half_of_lineitem(monkeypatch):
    """The second of ``lineitem``'s two scan partitions reads no file."""
    from spark_rapids_tpu.io.parquet import ParquetSource
    init = ParquetSource.__init__

    def halved(self, path, *a, **k):
        init(self, path, *a, **k)
        if str(path).rstrip("/").endswith("lineitem"):
            self._file_parts[1] = []
    monkeypatch.setattr(ParquetSource, "__init__", halved)


def fall_back_to_the_host(monkeypatch):
    """The program answers, but its counter of host fallbacks goes up
    between any two readings; broken once set-up is over (set-up refuses a
    warm-up query that failed)."""
    n = iter(range(1, 1 << 30))
    real = run.warm_up
    monkeypatch.setattr(run, "warm_up", lambda *a: (
        real(*a),
        monkeypatch.setattr(engine, "host_fallbacks", lambda: next(n)))[0])


@pytest.mark.parametrize("fault,number", [
    (join_as_inner, "exact_mismatches"),
    (drop_the_late_filter, "exact_mismatches"),
    (leave_out_half_of_lineitem, "exact_mismatches"),
    (fall_back_to_the_host, "failed_queries"),
], ids=["semi-join-run-as-inner", "late-filter-dropped",
        "half-of-lineitem-left-out", "host-fallback"])
def test_a_run_with_a_fault_driven_through_it_is_not_correct(
        data_dir, cell, monkeypatch, fault, number):
    fault(monkeypatch)
    r = drive(cell)
    assert r["correct"] is False and r["attempted"] >= 1
    c = r["compared"]
    assert c[number]["value"] > c[number]["limit"]
    assert c["max_rel_err"]["value"] == 0      # nothing there to read
    if number == "exact_mismatches":
        assert r["failed"] == 0
        # the priorities are still right: the counts are what differs
        assert c[number]["value"] <= len(PRIORITIES) * r["answers_compared"]
