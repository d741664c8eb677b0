"""The cell ``sf1.q13`` on the CPU backend at SF 0.02: the program's Q13 and
the plain reference agree exactly, the generator gives the shares the
traffic file states, a sound whole run is ``correct``, and whole runs with a
fault driven through them are not. Q13's answer holds no float column, so,
as for Q4, the faults that guard this cell are semantic ones — the outer
join run as an inner one, the wrong count, input left out, a host fallback.
The chip readings at SF 1 are in PERF.md."""
import numpy as np
import pytest

from benchmark import cells, compare, data, engine, references, run, tables

SCALE = 0.02
SEED = 2**31 + 41
CELL = "sf1.q13"


@pytest.fixture(scope="module")
def data_dir(tmp_path_factory):
    mp = pytest.MonkeyPatch()
    tmp = tmp_path_factory.mktemp("q13")
    mp.setattr(data, "DATA_DIR", str(tmp / "data"))
    mp.setattr(run, "TRACE_DIR", str(tmp / "trace"))
    yield tmp
    mp.undo()


@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(CELL)


# ---- the configuration the cell runs ------------------------------------------
def test_the_cell_runs_a_configuration_of_its_own(cell):
    """Its own name, source and file, the strict session, both tables'
    rows, and a plan rule that demands a hash join on the device (the
    shuffled one at SF 1, the broadcast one AQE demotes it to at this
    file's scale)."""
    configs = cells.manifest()["configs"]
    entry = next(c for c in configs if c["name"] == cell.config_name)
    assert cell.config_name == cell.config["name"] == "tpch-sf1-q13-1chip"
    assert entry["file"] == "benchmark/configs/tpch-sf1-q13-1chip.json"
    for other in configs:
        if other is not entry:
            assert other["source"] != entry["source"]
            assert other["file"] != entry["file"]
    assert "Q13" in entry["source"] and "cl. 2.4.13" in entry["source"]
    assert [w["name"] for w in cells.manifest()["workloads"]
            if w["config"] == cell.config_name] == [CELL]
    assert cell.chips == 1
    assert cell.config["rows"] == {"customer": 150_000, "orders": 1_500_000}
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
    assert engine.plan_faults(["CpuFilterExec"], rules) != []


# ---- the program against the reference --------------------------------------
@pytest.mark.parametrize("seed", [SEED, 13])
def test_q13_equals_the_reference_exactly(data_dir, cell, seed):
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
    assert list(got.columns) == list(ref.columns) == ["c_count", "custdist"]
    assert compare.answer_gap(got, ref) == (0.0, 0)
    customers = int(150_000 * SCALE)
    assert ref.custdist.sum() == customers
    # one third of the customers have no order: the c_count 0 row
    assert int(ref.custdist[ref.c_count == 0].sum()) == customers // 3
    # a left outer hash join on the device, and nothing off it
    assert [n for n in nodes if "Join" in n] in (
        ["TpuShuffledHashJoinExec"], ["TpuBroadcastHashJoinExec"])
    assert engine.plan_faults(nodes, cell.config["plan"]) == []
    for name in ("join.build", "join.prep", "join.probe.expand"):
        assert phases[name]["calls"] >= 1, (name, sorted(phases))
    assert phases["join.probe.expand"]["unmatched"] == customers // 3
    assert phases["join.probe.expand"]["rows_out"] \
        == int(1_500_000 * SCALE) + customers // 3


# ---- the generator against the traffic file's stated shares -----------------
def test_the_generator_gives_the_shares_the_traffic_file_states(cell):
    """``what`` states them for SF 1; every seed and scale draws from the
    same distributions (the longest run of one customer's orders is shorter
    at a smaller scale: ~36 at SF 1, 25 or more here)."""
    what = cell.traffic["what"]
    for stated in ("one third", "~36", "~15", "no comment matches",
                   "1,500,000", "150,000", "2 of the 8 tables",
                   "at most 78 bytes"):
        assert stated in what, stated
    cust = tables.generate("customer", SCALE, SEED).to_pandas()
    orders = tables.generate("orders", SCALE, SEED).to_pandas()
    held = orders.o_custkey.value_counts()
    assert len(cust) - len(held) == len(cust) // 3
    assert held.max() >= 25 and 14.5 < held.mean() < 15.5
    assert not orders.o_comment.str.contains("special").any()
    widths = orders.o_comment.str.len()
    assert widths.max() <= 78 and 60 < widths.mean() < 66
    assert set(cell.traffic["columns"]) == {"customer", "orders"}


# ---- the control that is none ------------------------------------------------
def test_the_float32_control_reads_nothing_in_this_mix(data_dir, cell):
    """Q13 counts customers by a count: there is no float to round, the
    control is the reference itself, and a limit of 0 passes it."""
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


def outer_join_as_inner(monkeypatch):
    """The LEFT OUTER JOIN planned as an inner join: the customers with no
    order, the c_count 0 row, vanish."""
    from spark_rapids_tpu.session import DataFrame
    real = DataFrame.join
    monkeypatch.setattr(
        DataFrame, "join", lambda self, other, *a, how="inner", **k: real(
            self, other, *a, how="inner" if how == "left" else how, **k))


def count_star_for_count(monkeypatch):
    """``count(*)`` in place of ``count(o_orderkey)``: the null-extended row
    of a customer with no order counts 1."""
    from spark_rapids_tpu.expr import functions
    monkeypatch.setattr(functions, "count", lambda c: functions.count_star())


def leave_out_half_of_orders(monkeypatch):
    """The second of ``orders``' two scan partitions reads no file."""
    from spark_rapids_tpu.io.parquet import ParquetSource
    init = ParquetSource.__init__

    def halved(self, path, *a, **k):
        init(self, path, *a, **k)
        if str(path).rstrip("/").endswith("orders"):
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
    (outer_join_as_inner, "exact_mismatches"),
    (count_star_for_count, "exact_mismatches"),
    (leave_out_half_of_orders, "exact_mismatches"),
    (fall_back_to_the_host, "failed_queries"),
], ids=["outer-join-run-as-inner", "count-star-for-count",
        "half-of-orders-left-out", "host-fallback"])
def test_a_run_with_a_fault_driven_through_it_is_not_correct(
        data_dir, cell, monkeypatch, fault, number):
    fault(monkeypatch)
    r = drive(cell)
    assert r["correct"] is False and r["attempted"] >= 1
    c = r["compared"]
    assert c[number]["value"] > c[number]["limit"]
    assert c["max_rel_err"]["value"] in (0, float("inf"))
    if number == "exact_mismatches":
        assert r["failed"] == 0
