"""The two Q3 cells at SF 0.01 on the CPU backend, 4 of conftest's 8 virtual
devices for the mesh: the mesh session, the one-device session and the plain
reference agree; the mesh plan holds no host exchange and its exchanges show
in the phase totals; and whole runs with a fault driven through them come
out as not ``correct``. The chip readings at SF 1 are in PERF.md."""
import numpy as np
import pytest

from benchmark import cells, compare, data, engine, references, run

SCALE = 0.01
SEED = 2**31 + 28
MESH, ONE = "sf1-mesh4.q3", "sf1.q3"
#: ``revenue`` is a sum of at most seven float64 products a group; the two
#: engines and pandas add them in different orders, which costs a few ulps
#: (2.2e-16 each). float32 anywhere in the path would cost 1e-8 or more.
REVENUE_RTOL = 1e-13


@pytest.fixture(scope="module")
def data_dir(tmp_path_factory):
    mp = pytest.MonkeyPatch()
    tmp = tmp_path_factory.mktemp("q3")
    mp.setattr(data, "DATA_DIR", str(tmp / "data"))
    mp.setattr(run, "TRACE_DIR", str(tmp / "trace"))
    yield tmp
    mp.undo()


def collect_once(cell, root):
    """One ``collect()`` of the cell's query through the cell's session:
    (answer frame, executed node names, phase totals, exchanged chunks)."""
    from spark_rapids_tpu.exec.exchange import TpuShuffleExchangeExec
    mp = pytest.MonkeyPatch()
    chunks = []
    real = TpuShuffleExchangeExec._exchange_chunk
    mp.setattr(TpuShuffleExchangeExec, "_exchange_chunk",
               lambda self, *a: (chunks.append(self), real(self, *a))[1])
    sess = engine.open_session(cell.config)
    try:
        df = engine.build_query(sess, root, cell.config, cell.traffic)
        frame = df.collect().to_pandas()
        nodes = engine.executed_nodes(sess.executed_plan)
        phases = sess.last_query_phases()["phases"]
    finally:
        sess.close()
        mp.undo()
    return frame, nodes, phases, chunks


@pytest.fixture(scope="module")
def answers(data_dir):
    out = {}
    for name in (MESH, ONE):
        cell = cells.load_cell(name)
        root = data.ensure_data(cell.config, list(cell.traffic["columns"]),
                                SEED, SCALE)
        out[name] = collect_once(cell, root)
    out["reference"] = references.compute(
        cell.traffic["reference"], root, cell.traffic["columns"])
    return out


# ---- (a) mesh, one device and reference agree ------------------------------
@pytest.mark.parametrize("cell_name", [MESH, ONE])
def test_q3_agrees_with_the_reference(answers, cell_name):
    got, ref = answers[cell_name][0], answers["reference"]
    assert list(got.columns) == list(ref.columns) == [
        "l_orderkey", "o_orderdate", "o_shippriority", "revenue"]
    assert len(got) == len(ref) == 10
    worst, wrong = compare.answer_gap(got, ref)
    assert wrong == 0           # keys, dates, priorities: exact, in order
    assert worst <= REVENUE_RTOL
    assert (np.diff(ref.revenue) <= 0).all()


def test_the_mesh_and_the_one_device_answer_are_the_same_rows(answers):
    mesh, one = answers[MESH][0], answers[ONE][0]
    assert compare.answer_gap(mesh, one)[1] == 0
    assert np.allclose(mesh.revenue, one.revenue, rtol=REVENUE_RTOL, atol=0)


# ---- (e) no host exchange under the mesh ------------------------------------
def test_the_mesh_plan_holds_ici_exchanges_and_no_host_exchange(answers):
    nodes = answers[MESH][1]
    assert "ShuffleExchangeExec" not in nodes, nodes
    assert nodes.count("TpuShuffleExchangeExec") >= 2
    assert "TpuLocalExchangeExec" in nodes          # the top-n gather
    cell = cells.load_cell(MESH)
    assert engine.plan_faults(nodes, cell.config["plan"]) == []
    assert "ShuffleExchangeExec" not in cell.config["plan"]["also_allowed"]
    assert cell.config["guarantees"] \
        == cells.load_cell(ONE).config["guarantees"]


def test_the_mesh_cell_as_committed_shuffles_both_joins(answers):
    """A broadcast join reads its build side whole and does not care how its
    probe side was routed: it would hide a broken exchange. AQE demotes a
    join under its own threshold whatever the planner's says, so the
    configuration switches both off and its plan rules demand the result."""
    nodes = answers[MESH][1]
    assert nodes.count("TpuShuffledHashJoinExec") == 2
    assert not [n for n in nodes if "Broadcast" in n]
    assert nodes.count("TpuShuffleExchangeExec") == 5
    config = cells.load_cell(MESH).config
    for conf in ("spark.rapids.tpu.autoBroadcastJoinThreshold",
                 "spark.rapids.tpu.aqe.autoBroadcastJoinThreshold"):
        assert config["session_conf"][conf] == -1 and conf in config["assumed"]
    demoted = [n.replace("Shuffled", "Broadcast") for n in nodes]
    assert engine.plan_faults(demoted, config["plan"]) \
        == ["none of TpuShuffledHashJoinExec planned"]


def test_the_one_device_plan_exchanges_locally(answers):
    nodes = answers[ONE][1]
    assert "TpuShuffleExchangeExec" not in nodes
    assert "TpuLocalExchangeExec" in nodes
    assert engine.plan_faults(nodes, cells.load_cell(ONE).config["plan"]) \
        == []
    assert not answers[ONE][3]


# ---- (d) the exchanges in the phase totals ----------------------------------
def test_the_mesh_querys_phase_totals_hold_its_exchanges(answers):
    _, _, phases, chunks = answers[MESH]
    n = len(chunks)
    assert n >= 2
    for name in ("exchange.count", "exchange.shard", "exchange.split"):
        assert phases[name]["calls"] == n, (name, phases[name])
    # "dispatch": the all-to-all's is the one dispatch that carries bytes
    for name in ("exchange.count", "exchange.shard", "exchange.gather",
                 "dispatch"):
        assert phases[name]["bytes"] > 0, name
    # every chunk downloads its partition ids (d2h) and its shards' row
    # counts (sync): host_syncs_per_query counts them
    assert phases["d2h"]["calls"] >= n + 1
    assert phases["sync"]["calls"] >= n
    assert phases["d2h"]["bytes"] >= phases["exchange.count"]["bytes"]
    shuffled = sum(x.metrics.snapshot()["shuffleBytes"] for x in set(chunks))
    assert phases["exchange.shard"]["bytes"] == shuffled
    one = answers[ONE][2]
    assert not [p for p in one if p.startswith("exchange.")]
    assert phases["sync"]["calls"] + phases["d2h"]["calls"] \
        >= one["sync"]["calls"] + one["d2h"]["calls"] + 2 * n - 2


# ---- (b) whole runs, sound and with a fault driven through them -------------
def drive(cell, seed=SEED):
    return run.drive(cell, seed, 0.2, False, scale=SCALE)


@pytest.mark.parametrize("cell_name", [MESH, ONE])
def test_a_sound_run_is_correct(data_dir, cell_name):
    cell = cells.load_cell(cell_name)
    r = drive(cell)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 1
    assert set(r["metrics"]) == {"query_s", "setup_s"} == set(cell.end_to_end)
    assert r["workload"] == cell_name and list(r)[-1] == "compared"


def test_an_exchange_that_moves_no_row_is_not_correct(data_dir, monkeypatch):
    """The fault driven through the mesh cell exactly as committed: its
    joins are shuffled, so each reads only the rows its device was sent."""
    from spark_rapids_tpu.shuffle import ici
    monkeypatch.setattr(ici, "ici_all_to_all_exchange",
                        lambda table, *a, **k: table)
    r = drive(cells.load_cell(MESH))
    assert r["correct"] is False and r["failed"] == 0
    c = r["compared"]
    assert c["max_rel_err"]["value"] > c["max_rel_err"]["limit"] \
        or c["exact_mismatches"]["value"] > 0


@pytest.mark.parametrize("cell_name", [MESH, ONE])
def test_a_host_operator_in_the_plan_fails_failed_queries(
        data_dir, monkeypatch, cell_name):
    """The gather put back on the host tier, once set-up is over (set-up
    refuses a warm-up query that failed)."""
    real = run.warm_up
    monkeypatch.setattr(run, "warm_up", lambda *a: (
        real(*a), monkeypatch.setattr(
            engine, "NON_TPU_NODES",
            engine.NON_TPU_NODES - {"DeviceToHostExec"}))[0])
    r = drive(cells.load_cell(cell_name))
    assert r["correct"] is False
    c = r["compared"]["failed_queries"]
    assert c["value"] == r["failed"] == r["attempted"] > c["limit"]


def test_a_host_exchange_in_the_mesh_plan_is_a_fault():
    plan = cells.load_cell(MESH).config["plan"]
    nodes = ["DeviceToHostExec", "TpuTakeOrderedExec", "ShuffleExchangeExec",
             "TpuShuffledHashJoinExec", "TpuShuffleExchangeExec"]
    assert engine.plan_faults(nodes, plan) \
        == ["host operator ShuffleExchangeExec"]
    assert engine.plan_faults(
        ["TpuLocalExchangeExec", "TpuShuffledHashJoinExec"], plan) \
        == ["none of TpuShuffleExchangeExec/TpuMeshStageExec planned"]


@pytest.mark.parametrize("seed", [11, 2**31 + 5, 77])
def test_float32_control_fails_the_traffics_own_limit(data_dir, seed):
    cell = cells.load_cell(ONE)
    assert cell.traffic == cells.load_cell(MESH).traffic   # one mix, one limit
    root = data.ensure_data(cell.config, list(cell.traffic["columns"]), seed,
                            SCALE)
    args = (cell.traffic["reference"], root, cell.traffic["columns"])
    ref = references.compute(*args)
    v = compare.judge([references.compute(*args, np.float32)], ref, 0,
                      cell.traffic["limits"])
    assert not v["correct"]
    assert v["compared"]["max_rel_err"]["value"] \
        > v["compared"]["max_rel_err"]["limit"]
    assert compare.judge([references.compute(*args)], ref, 0,
                         cell.traffic["limits"])["correct"]
