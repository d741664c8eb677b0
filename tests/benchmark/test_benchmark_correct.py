"""What decides ``correct``: the comparison itself, the control in the
precision below (which has to fail it), and a whole run driven with the timed
path broken underneath (which has to come out as not correct). CPU backend,
SF 0.01; the chip readings at SF 1 are in PERF.md."""
import numpy as np
import pandas as pd
import pytest

from benchmark import cells, compare, data, references, run

SCALE = 0.01
LIMITS = {"max_rel_err": 1e-10, "exact_mismatches": 0, "failed_queries": 0}


@pytest.fixture
def data_dir(tmp_path, monkeypatch):
    monkeypatch.setattr(data, "DATA_DIR", str(tmp_path / "data"))
    monkeypatch.setattr(run, "TRACE_DIR", str(tmp_path / "trace"))


# ---- the comparison ------------------------------------------------------
REF = pd.DataFrame({"k": ["A", "B"], "n": [3, 4], "x": [100.0, 200.0],
                    "d": pd.to_datetime(["1995-01-01", "1995-01-02"])})


def test_equal_answers_are_correct():
    v = compare.judge([REF.copy(), REF.copy()], REF, 0, LIMITS)
    assert v["correct"] and v["answers_compared"] == 2
    assert v["compared"]["max_rel_err"]["value"] == 0.0


@pytest.mark.parametrize("column,value,number", [
    ("x", 100.0 * (1 + 1e-9), "max_rel_err"),
    ("x", float("nan"), "max_rel_err"),
    ("n", 5, "exact_mismatches"),
    ("k", "C", "exact_mismatches"),
    ("d", pd.Timestamp("1995-01-03"), "exact_mismatches"),
])
def test_one_altered_value_fails_the_number_that_watches_it(
        column, value, number):
    got = REF.copy()
    got.loc[0, column] = value
    v = compare.judge([REF.copy(), got], REF, 0, LIMITS)
    assert not v["correct"]
    c = v["compared"][number]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("got", [REF.iloc[:1], REF.drop(columns="n"),
                                 REF.iloc[::-1].reset_index(drop=True)],
                         ids=["row-missing", "column-missing", "order"])
def test_a_wrong_shape_or_order_is_not_correct(got):
    assert not compare.judge([got], REF, 0, LIMITS)["correct"]


def test_a_failed_query_or_no_answer_is_not_correct():
    assert not compare.judge([REF.copy()], REF, 1, LIMITS)["correct"]
    assert not compare.judge([], REF, 0, LIMITS)["correct"]


# ---- the control: the reference in float32 has to fail -------------------
@pytest.mark.parametrize("cell_name", ["sf1.q1", "sf1.q6"])
@pytest.mark.parametrize("seed", [11, 2**31 + 5, 77])
def test_float32_control_fails_the_cells_own_limit(data_dir, cell_name, seed):
    cell = cells.load_cell(cell_name)
    root = data.ensure_data(cell.config, list(cell.traffic["columns"]), seed,
                            SCALE)
    args = (cell.traffic["reference"], root, cell.traffic["columns"])
    ref = references.compute(*args)
    low = references.compute(*args, np.float32)
    v = compare.judge([low], ref, 0, cell.traffic["limits"])
    assert not v["correct"]
    # by the float columns: the keys and counts of a float32 path are right
    assert v["compared"]["max_rel_err"]["value"] \
        > v["compared"]["max_rel_err"]["limit"]
    # and the float64 reference agrees with itself
    assert compare.judge([references.compute(*args)], ref, 0,
                         cell.traffic["limits"])["correct"]


# ---- a whole run with the timed path broken underneath -------------------
def drive(cell_name, seed=3):
    return run.drive(cells.load_cell(cell_name), seed, 0.2, False, scale=SCALE)


def alter_answers(monkeypatch):
    """An answer altered where it is produced: collect() returns its table
    with the first float value off by one part in a million."""
    import pyarrow as pa
    from spark_rapids_tpu.session import DataFrame
    collect = DataFrame.collect

    def altered(self, *a, **k):
        t = collect(self, *a, **k)
        i = next(i for i, f in enumerate(t.schema)
                 if pa.types.is_floating(f.type))
        col = t.column(i).to_numpy().copy()
        col[0] *= 1.0 + 1e-6
        return t.set_column(i, t.schema[i].name, pa.array(col))
    monkeypatch.setattr(DataFrame, "collect", altered)


def drop_half_the_input(monkeypatch):
    """Half of the input left out: the second of the two scan partitions of
    every table reads no file; the aggregates are taken over the rest."""
    from spark_rapids_tpu.io.parquet import ParquetSource
    init = ParquetSource.__init__

    def halved(self, *a, **k):
        init(self, *a, **k)
        self._file_parts[1] = []
    monkeypatch.setattr(ParquetSource, "__init__", halved)


def fall_back_to_the_host(monkeypatch):
    """The device path left out: the program answers, but its counter of
    host fallbacks goes up between any two readings."""
    from benchmark import engine
    n = {"n": 0}

    def counted():
        n["n"] += 1
        return n["n"]
    monkeypatch.setattr(engine, "host_fallbacks", counted)


def plan_a_host_operator(monkeypatch):
    """An operator planned off the device: the device-to-host transition no
    longer counts as part of a device plan."""
    from benchmark import engine
    monkeypatch.setattr(engine, "NON_TPU_NODES",
                        engine.NON_TPU_NODES - {"DeviceToHostExec"})


@pytest.mark.parametrize("cell_name", ["sf1.q1", "sf1.q6"])
def test_a_sound_run_is_correct(data_dir, cell_name):
    r = drive(cell_name)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 1
    assert set(r["metrics"]) == set(cells.load_cell(cell_name).end_to_end)
    assert list(r)[-1] == "compared"
    assert all(v["value"] <= v["limit"] for v in r["compared"].values())


@pytest.mark.parametrize("cell_name", ["sf1.q1", "sf1.q6"])
@pytest.mark.parametrize("fault,number", [
    (alter_answers, "max_rel_err"),
    (drop_half_the_input, "max_rel_err"),
    (fall_back_to_the_host, "failed_queries"),
    (plan_a_host_operator, "failed_queries"),
], ids=["answer-altered", "half-the-input-left-out", "host-fallback",
        "host-operator-planned"])
def test_a_run_on_a_broken_timed_path_is_not_correct(
        data_dir, monkeypatch, cell_name, fault, number):
    if number == "failed_queries":
        # set-up refuses a warm-up query that failed: break only later
        r = drive(cell_name)
        assert r["correct"]
        real = run.warm_up
        monkeypatch.setattr(
            run, "warm_up",
            lambda *a: (real(*a), fault(monkeypatch))[0])
    else:
        fault(monkeypatch)
    r = drive(cell_name)
    assert r["correct"] is False
    c = r["compared"][number]
    assert c["value"] > c["limit"]


def test_a_traced_run_reports_the_per_layer_metrics_it_can_read(data_dir):
    cell = cells.load_cell("sf1.q6")
    r = run.drive(cell, 3, 0.2, True, scale=SCALE)
    assert r["correct"] is True
    # no device plane on the CPU backend: trace-read metrics are left out,
    # never reported as 0; the counters are there
    assert {"window_xla_compiles", "first_collect_s"} <= set(r["metrics"])
    assert not {"device_busy_s_per_query", "query_hbm_roofline",
                "device_idle_share"} & set(r["metrics"])
    assert r["metrics"]["window_xla_compiles"]["value"] == 0
    assert "window_s" in r["device"] and "busy_s" not in r["device"]


def test_a_stalled_query_gets_every_threads_stack_logged_once(capfd):
    import time
    watch = run.StallWatch()
    watch.after_s = 0.3
    for wall in (0.05, 0.9, 0.05):
        watch.started = time.perf_counter()
        time.sleep(wall)
        watch.started = None
    assert watch.stalls == 1
    err = capfd.readouterr().err
    assert err.count("STALL") == 1 and "most recent call first" in err
