"""The cell ``sf1.q18`` on the CPU backend at SF 0.05: the plain reference
against a hand-made table, the program's Q18 against the reference, a sound
whole run ``correct``, and whole runs with a fault driven through them not.
``sum_qty`` adds a few integers below 51 and is exact in any float type, so
the precision control can fail only through the cast of ``o_totalprice``
(pinned below): it guards the types, and the semantic faults — HAVING
dropped, input left out, the merge of the wide group-by lost, the top-n's
keys reversed, a host fallback — guard the arithmetic and the order. The
chip readings at SF 1 are in PERF.md."""
import numpy as np
import pandas as pd
import pytest

from benchmark import cells, compare, data, engine, references, run, tables
from benchmark.references import q18

SCALE = 0.05
SEED = 2**31 + 34
CELL = "sf1.q18"
COLUMNS = ["c_name", "c_custkey", "o_orderkey", "o_orderdate", "o_totalprice",
           "sum_qty"]


@pytest.fixture(scope="module")
def data_dir(tmp_path_factory):
    mp = pytest.MonkeyPatch()
    tmp = tmp_path_factory.mktemp("q18")
    mp.setattr(data, "DATA_DIR", str(tmp / "data"))
    mp.setattr(run, "TRACE_DIR", str(tmp / "trace"))
    yield tmp
    mp.undo()


@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(CELL)


# ---- the configuration the cell runs ------------------------------------------
def test_the_cell_runs_a_configuration_of_its_own(cell):
    configs = cells.manifest()["configs"]
    entry = next(c for c in configs if c["name"] == cell.config_name)
    assert cell.config_name == cell.config["name"] == "tpch-sf1-q18-1chip"
    assert entry["file"] == "benchmark/configs/tpch-sf1-q18-1chip.json"
    assert entry["source"] == cell.config["source"]
    for other in configs:
        if other is not entry:
            assert other["source"] != entry["source"]
            assert other["file"] != entry["file"]
    assert "Q18" in entry["source"] and "cl. 2.4.18" in entry["source"]
    assert [w["name"] for w in cells.manifest()["workloads"]
            if w["config"] == cell.config_name] == [CELL]
    assert cell.chips == 1 and cell.traffic["query"] == "q18"
    assert cell.config["rows"] == {"customer": 150_000, "orders": 1_500_000,
                                   "lineitem": 6_000_000}
    assert list(cell.config["rows"]) == list(cell.traffic["columns"])
    assert cell.config["scale_factor"] == 1 and cell.config["mesh"] is None
    assert cell.config["files_per_table"] == 2
    assert cell.config["session_conf"] == {
        "spark.rapids.sql.test.enabled": True,
        "spark.rapids.tpu.fallback.enabled": False,
        "spark.rapids.tpu.fallback.quarantine.enabled": False}
    assert set(cell.config["reduced"]) == {"tables"} == set(entry["reduced"])
    assert set(cell.config) == set(cells.load_cell("sf1.q4").config)
    rules = cell.config["plan"]
    assert engine.plan_faults(
        ["TpuHashAggregateExec", "TpuParquetScanExec"], rules) == [
        "none of TpuShuffledHashJoinExec/TpuBroadcastHashJoinExec planned"]
    for join in ("TpuShuffledHashJoinExec", "TpuBroadcastHashJoinExec"):
        assert engine.plan_faults(["TpuHashAggregateExec", join], rules) == []
    assert engine.plan_faults(["CpuHashAggregateExec"], rules) != []
    # the cell reports what every one-chip cell reports and nothing new
    assert set(cell.end_to_end) == {"query_s", "setup_s"}
    assert set(cell.per_layer) == set(cells.load_cell("sf1.q4").per_layer)


# ---- the reference against a table made by hand -------------------------------
def hand_made():
    """Five customers, seven orders. Order 8's lines sum to exactly 300
    (out), order 12's to 301 (in); customer 2 holds two big orders (12, 16);
    orders 20 and 24 tie on ``o_totalprice`` and fall by date, orders 24 and
    28 tie on price and date and fall by key; order 4 is small."""
    date = lambda s: np.datetime64(s, "D")  # noqa: E731
    cust = pd.DataFrame({"c_custkey": [1, 2, 3, 4, 5],
                         "c_name": [f"Customer#{i:09d}" for i in range(1, 6)]})
    orders = pd.DataFrame({
        "o_orderkey": [4, 8, 12, 16, 20, 24, 28],
        "o_custkey": [1, 1, 2, 2, 3, 4, 5],
        "o_orderdate": [date("1995-01-01"), date("1995-01-02"),
                        date("1995-01-03"), date("1995-01-04"),
                        date("1996-06-01"), date("1996-05-01"),
                        date("1996-05-01")],
        "o_totalprice": [900.0, 500_000.0, 1000.0, 4000.0, 2000.0, 2000.0,
                         2000.0]})
    lines = {4: [50.0, 50.0], 8: [50.0] * 6, 12: [50.0] * 6 + [1.0],
             16: [50.0] * 7, 20: [44.0] * 7, 24: [45.0] * 7, 28: [46.0] * 7}
    li = pd.DataFrame({
        "l_orderkey": [k for k, v in lines.items() for _ in v],
        "l_quantity": [q for v in lines.values() for q in v]})
    # the lines of an order are not adjacent in the table
    li = li.sample(frac=1.0, random_state=18).reset_index(drop=True)
    return {"customer": cust, "orders": orders, "lineitem": li}


@pytest.mark.parametrize("float_dtype", [np.float64, np.float32])
def test_the_reference_on_a_hand_made_table(float_dtype):
    t = hand_made()
    t["lineitem"]["l_quantity"] = t["lineitem"].l_quantity.astype(float_dtype)
    t["orders"]["o_totalprice"] = t["orders"].o_totalprice.astype(float_dtype)
    out = q18.reference(t, float_dtype)
    assert list(out.columns) == COLUMNS
    # price descending, then date, then key; 300 is out and 301 is in;
    # customer 2 appears twice
    assert list(out.o_orderkey) == [16, 24, 28, 20, 12]
    assert list(out.c_custkey) == [2, 4, 5, 3, 2]
    assert list(out.c_name) == [f"Customer#{i:09d}" for i in (2, 4, 5, 3, 2)]
    assert list(out.sum_qty) == [350.0, 315.0, 322.0, 308.0, 301.0]
    assert list(out.o_totalprice) == [4000.0, 2000.0, 2000.0, 2000.0, 1000.0]
    assert 8 not in set(out.o_orderkey) and 4 not in set(out.o_orderkey)


def test_the_reference_cuts_more_than_a_hundred_big_orders_to_a_hundred():
    n = 130
    keys = np.arange(1, n + 1) * 4
    rng = np.random.default_rng(18)
    t = {"customer": pd.DataFrame({"c_custkey": [1], "c_name": ["C"]}),
         "orders": pd.DataFrame({
             "o_orderkey": keys, "o_custkey": 1,
             "o_orderdate": np.datetime64("1995-01-01", "D"),
             "o_totalprice": rng.permutation(n).astype(np.float64)}),
         "lineitem": pd.DataFrame({"l_orderkey": np.repeat(keys, 7),
                                   "l_quantity": 50.0})}
    out = q18.reference(t, np.float64)
    assert len(out) == 100
    assert list(out.o_totalprice) == sorted(range(30, n), reverse=True)
    assert set(out.sum_qty) == {350.0}


# ---- the program against the reference --------------------------------------
@pytest.mark.parametrize("seed", [SEED, 11, 77])
def test_q18_equals_the_reference(data_dir, cell, seed):
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
    assert list(got.columns) == list(ref.columns) == COLUMNS
    # ~0.3 % of 75,000 orders pass HAVING: over a hundred, cut to it
    assert len(ref) == 100
    assert (ref.sum_qty > 300).all()
    # sum_qty adds integers: exact; o_totalprice is copied through
    assert compare.answer_gap(got, ref) == (0.0, 0)
    # three hash joins on the device, and nothing off it (AQE plans every
    # one broadcast, here as on the chip: PERF.md section 4)
    joins = [n for n in nodes if "Join" in n]
    assert len(joins) == 3 and set(joins) <= {
        "TpuShuffledHashJoinExec", "TpuBroadcastHashJoinExec"}
    assert engine.plan_faults(nodes, cell.config["plan"]) == []
    for name in ("join.build", "join.prep", "agg.scatter", "decode.dense"):
        assert phases[name]["calls"] >= 1, (name, sorted(phases))


# ---- the generator against the traffic file's stated shares -----------------
def test_the_generator_gives_the_shares_the_traffic_file_states(cell):
    """``what`` states them for SF 1; every seed and scale draws from the
    same distributions."""
    what = cell.traffic["what"]
    for stated in ("150,000", "1,500,000", "6,000,000", "6,001,215",
                   "3 of the 8 tables", "Poisson(4)", "0.3 %", "passes 57"):
        assert stated in what, stated
    li = tables.generate("lineitem", SCALE, SEED).to_pandas()
    n_orders = int(1_500_000 * SCALE)
    held = li.l_orderkey.value_counts()
    # one group an order with a line: 98.2 % of the orders (1 - e^-4)
    assert 0.975 < len(held) / n_orders < 0.988
    assert 3.9 < held[held > 0].mean() < 4.2 and held.max() >= 12
    sums = li.groupby("l_orderkey").l_quantity.sum()
    assert 0.002 < (sums > 300).sum() / n_orders < 0.004
    assert list(cell.traffic["columns"]) == ["customer", "orders", "lineitem"]


# ---- the control: it guards the types, not the arithmetic ---------------------
def test_the_float32_control_fails_by_the_cast_of_o_totalprice_alone(
        data_dir, cell):
    root = data.ensure_data(cell.config, list(cell.traffic["columns"]), SEED,
                            SCALE)
    args = (cell.traffic["reference"], root, cell.traffic["columns"])
    ref = references.compute(*args)
    low = references.compute(*args, np.float32)
    assert low.sum_qty.dtype == np.float32
    # sums of at most ~17 integers below 51: exact in float32 too
    assert (low.sum_qty.astype(np.float64) == ref.sum_qty).all()
    err, wrong = compare.answer_gap(low, ref)
    limits = cell.traffic["limits"]
    assert wrong == 0 <= limits["exact_mismatches"]
    assert 1e-9 < err < 1e-6 and err > 100 * limits["max_rel_err"]
    assert compare.judge([low], ref, 0, limits)["correct"] is False
    assert limits["max_rel_err"] > 0 and limits["failed_queries"] == 0


# ---- whole runs, sound and with a fault driven through them -------------------
def drive(cell, seed=SEED):
    return run.drive(cell, seed, 0.2, False, scale=SCALE)


@pytest.mark.parametrize("seed", [SEED, 3, 2**31 + 1234])
def test_a_sound_run_is_correct(data_dir, cell, seed):
    r = drive(cell, seed)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 1
    assert set(r["metrics"]) == {"query_s", "setup_s"} == set(cell.end_to_end)
    assert r["workload"] == CELL and list(r)[-1] == "compared"
    assert all(v["value"] <= v["limit"] for v in r["compared"].values())
    assert r["compared"]["exact_mismatches"]["value"] == 0


def drop_having(monkeypatch):
    """HAVING dropped: every order with a line is a big one."""
    from spark_rapids_tpu.session import DataFrame
    real = DataFrame.filter
    monkeypatch.setattr(
        DataFrame, "filter", lambda self, cond: self
        if "sum_qty" in repr(cond) else real(self, cond))


def leave_out_half_of_lineitem(monkeypatch):
    """The second of ``lineitem``'s two scan partitions reads no file, in
    the subquery and in the outer join."""
    from spark_rapids_tpu.io.parquet import ParquetSource
    init = ParquetSource.__init__

    def halved(self, path, *a, **k):
        init(self, path, *a, **k)
        if str(path).rstrip("/").endswith("lineitem"):
            self._file_parts[1] = []
    monkeypatch.setattr(ParquetSource, "__init__", halved)


def lose_the_merge(monkeypatch):
    """A final aggregate that sees only the first of its partial batches:
    the states of the other scan partition are never merged in, so an
    order's sum is the sum over some of its lines."""
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
    real = TpuHashAggregateExec.child_device_batches

    def first_only(self, pidx):
        batches = list(real(self, pidx))
        return iter(batches[:1] if self.mode == "final" else batches)
    monkeypatch.setattr(TpuHashAggregateExec, "child_device_batches",
                        first_only)


def reverse_the_sort_keys(monkeypatch):
    """The top-n's keys in reverse: by key, then date, then price."""
    from spark_rapids_tpu.session import DataFrame
    real = DataFrame.sort
    monkeypatch.setattr(
        DataFrame, "sort", lambda self, *orders, **k: real(
            self, *reversed(orders), **k))


def fall_back_to_the_host(monkeypatch):
    """The program answers, but its counter of host fallbacks goes up
    between any two readings; broken once set-up is over (set-up refuses a
    warm-up query that failed)."""
    n = iter(range(1, 1 << 30))
    real = run.warm_up
    monkeypatch.setattr(run, "warm_up", lambda *a: (
        real(*a),
        monkeypatch.setattr(engine, "host_fallbacks", lambda: next(n)))[0])


@pytest.mark.parametrize("fault,numbers", [
    (drop_having, ("exact_mismatches", "max_rel_err")),
    (leave_out_half_of_lineitem, ("exact_mismatches", "max_rel_err")),
    (lose_the_merge, ("exact_mismatches", "max_rel_err")),
    (reverse_the_sort_keys, ("exact_mismatches", "max_rel_err")),
    (fall_back_to_the_host, ("failed_queries",)),
], ids=["having-dropped", "half-of-lineitem-left-out", "merge-lost",
        "sort-keys-reversed", "host-fallback"])
def test_a_run_with_a_fault_driven_through_it_is_not_correct(
        data_dir, cell, monkeypatch, fault, numbers):
    fault(monkeypatch)
    r = drive(cell)
    assert r["correct"] is False and r["attempted"] >= 1
    c = r["compared"]
    over = [n for n in c if c[n]["value"] > c[n]["limit"]]
    # other rows than the reference's hundred: their keys differ, and with
    # them the two float columns; a fallback changes no answer
    assert numbers[0] in over and set(over) <= set(numbers), (over, c)
    if "failed_queries" not in numbers:
        assert r["failed"] == 0
