"""The cell ``sf1-mesh4.q18`` on the CPU backend at SF 0.05, 4 of conftest's
8 virtual devices for the mesh: its configuration, its answer against the
reference and against the one-chip cell's, the executed plan (ICI exchanges
under both aggregates, shuffled hash joins, no host exchange), the exchange's
slot counters, and whole runs — sound, and with a fault driven through them.
The chip readings at SF 1 are in PERF.md."""
import pytest

from benchmark import cells, compare, data, engine, references, run

SCALE = 0.05
SEED = 2**31 + 38
MESH, ONE = "sf1-mesh4.q18", "sf1.q18"


@pytest.fixture(scope="module")
def data_dir(tmp_path_factory):
    mp = pytest.MonkeyPatch()
    tmp = tmp_path_factory.mktemp("q18mesh")
    mp.setattr(data, "DATA_DIR", str(tmp / "data"))
    mp.setattr(run, "TRACE_DIR", str(tmp / "trace"))
    yield tmp
    mp.undo()


@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(MESH)


# ---- the configuration the cell runs ------------------------------------------
def test_the_cell_runs_a_configuration_of_its_own(cell):
    configs = cells.manifest()["configs"]
    entry = next(c for c in configs if c["name"] == cell.config_name)
    assert cell.config_name == cell.config["name"] == "tpch-sf1-q18-mesh4"
    assert entry["file"] == "benchmark/configs/tpch-sf1-q18-mesh4.json"
    assert entry["source"] == cell.config["source"]
    for other in configs:
        if other is not entry:
            assert other["source"] != entry["source"]
            assert other["file"] != entry["file"]
    assert [w["name"] for w in cells.manifest()["workloads"]
            if w["config"] == cell.config_name] == [MESH]
    one, q3 = cells.load_cell(ONE), cells.load_cell("sf1-mesh4.q3")
    # Q18 at the one-chip cell's scale and files, with the traffic it runs
    assert cell.chips == 4 and cell.traffic == one.traffic
    for key in ("scale_factor", "rows", "files_per_table", "guarantees"):
        assert cell.config[key] == one.config[key], key
    # laid out as the Q3 mesh cell: its mesh and its seven session keys
    assert cell.config["mesh"] == q3.config["mesh"] \
        == {"kind": "data_parallel", "devices": 4}
    assert cell.config["session_conf"] == q3.config["session_conf"]
    assert len(cell.config["session_conf"]) == 7
    assert set(cell.config["reduced"]) == {"tables", "executors"} \
        == set(entry["reduced"])
    assert set(cell.config) == set(q3.config)
    assert set(cell.end_to_end) == {"query_s", "setup_s"}
    assert set(cell.per_layer) == set(q3.per_layer)


def test_the_plan_rules_demand_the_partitioned_aggregate(cell):
    rules = cell.config["plan"]
    whole = ["TpuShuffleExchangeExec", "TpuShuffledHashJoinExec",
             "TpuHashAggregateExec"]
    assert engine.plan_faults(whole, rules) == []
    assert engine.plan_faults(["ShuffleStageExec"] + whole, rules) == []
    assert engine.plan_faults(["ShuffleExchangeExec"] + whole, rules) \
        == ["host operator ShuffleExchangeExec"]
    assert engine.plan_faults(
        ["TpuLocalExchangeExec"] + whole[1:], rules) \
        == ["none of TpuShuffleExchangeExec/TpuMeshStageExec planned"]
    assert engine.plan_faults(
        [n.replace("Shuffled", "Broadcast") for n in whole], rules) \
        == ["none of TpuShuffledHashJoinExec planned"]


# ---- mesh, one device and reference agree -------------------------------------
def collect_once(c, root):
    """One ``collect()`` through the cell's session: (answer, executed
    plan, phase totals)."""
    sess = engine.open_session(c.config)
    try:
        df = engine.build_query(sess, root, c.config, c.traffic)
        frame = df.collect().to_pandas()
        plan = sess.executed_plan
        plan = plan.final_plan() if hasattr(plan, "final_plan") else plan
        phases = sess.last_query_phases()["phases"]
    finally:
        sess.close()
    return frame, plan, phases


@pytest.fixture(scope="module")
def answers(data_dir, cell):
    root = data.ensure_data(cell.config, list(cell.traffic["columns"]), SEED,
                            SCALE)
    out = {name: collect_once(cells.load_cell(name), root)
           for name in (MESH, ONE)}
    out["reference"] = references.compute(
        cell.traffic["reference"], root, cell.traffic["columns"])
    return out


@pytest.mark.parametrize("cell_name", [MESH, ONE])
def test_q18_equals_the_reference(answers, cell_name):
    got, ref = answers[cell_name][0], answers["reference"]
    assert list(got.columns) == list(ref.columns)
    assert len(ref) == 100 and (ref.sum_qty > 300).all()
    # sum_qty adds integers: exact; o_totalprice is copied through
    assert compare.answer_gap(got, ref) == (0.0, 0)


def test_the_mesh_and_the_one_chip_answer_are_the_same_rows(answers):
    assert compare.answer_gap(answers[MESH][0], answers[ONE][0]) == (0.0, 0)


#: AQE's wrappers of a materialised exchange: the node they read is below
_READERS = ("TpuStageReaderExec", "ShuffleStageExec")


def _kids(node):
    """A node's children as ``tree_string`` shows them: through a stage
    reader to the stage's exchange."""
    name = type(node).__name__
    if name == "TpuStageReaderExec":
        return [node.stage.inner]
    if name == "ShuffleStageExec":
        return [node.inner]
    return list(node.children)


def _walk(node):
    yield node
    for k in _kids(node):
        yield from _walk(k)


def _unwrap(node):
    while type(node).__name__ in _READERS:
        node = _kids(node)[0]
    return node


def _below(node):
    """The first node under ``node`` that is no stage wrapper."""
    return _unwrap(_kids(node)[0])


def _sides(join):
    return [_unwrap(k) for k in _kids(join)]


def test_ici_exchanges_sit_under_both_aggregates_and_every_join(answers):
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.exec.exchange import TpuShuffleExchangeExec
    from spark_rapids_tpu.exec.joins import TpuShuffledHashJoinExec
    plan = answers[MESH][1]
    finals = [n for n in _walk(plan)
              if isinstance(n, TpuHashAggregateExec) and n.mode == "final"]
    # the subquery's l_orderkey group-by and the last five-key one
    assert sorted(len(n.key_names) for n in finals) == [1, 5]
    for node in finals:
        under = _below(node)
        assert isinstance(under, TpuShuffleExchangeExec), type(under)
        assert under.partitioning.key_names == list(node.key_names)
    joins = [n for n in _walk(plan) if isinstance(n, TpuShuffledHashJoinExec)]
    assert sorted(j.how for j in joins) == ["inner", "inner", "left_semi"]
    for join in joins:
        assert all(isinstance(side, TpuShuffleExchangeExec)
                   for side in _sides(join))
    names = engine.executed_nodes(plan)
    assert "ShuffleExchangeExec" not in names
    assert not [n for n in names if "Broadcast" in n]
    assert engine.plan_faults(names, cells.load_cell(MESH).config["plan"]) \
        == []


def test_the_exchanges_count_their_rows_and_slots(answers):
    """``exchange.count``: ``rows`` the live rows exchanged, ``quota`` the
    slots a source-destination pair, ``slots`` = n x n x quota a chunk —
    what the all-to-all carries, so 1 - rows / slots is its padding."""
    phases = answers[MESH][2]
    count = phases["exchange.count"]
    assert 0 < count["rows"] <= count["slots"]
    assert count["slots"] == 4 * 4 * count["quota"]
    assert phases["agg.scatter"]["calls"] >= 2
    assert not [p for p in answers[ONE][2] if p.startswith("exchange.")]


# ---- whole runs, sound and with a fault driven through them -------------------
def drive(c, seed=SEED):
    return run.drive(c, seed, 0.2, False, scale=SCALE)


def test_a_sound_run_is_correct(data_dir, cell):
    r = drive(cell)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 1
    assert set(r["metrics"]) == {"query_s", "setup_s"}
    assert r["workload"] == MESH and list(r)[-1] == "compared"


def test_an_exchange_that_moves_no_row_is_not_correct(data_dir, cell,
                                                      monkeypatch):
    """Every join is shuffled and both aggregates are partitioned: with the
    all-to-all a no-op, co-partitioned join sides miss their matches and a
    final aggregate sees only its own shard's partials."""
    from spark_rapids_tpu.shuffle import ici
    monkeypatch.setattr(ici, "ici_all_to_all_exchange",
                        lambda table, *a, **k: table)
    r = drive(cell)
    assert r["correct"] is False and r["failed"] == 0
    c = r["compared"]
    assert c["exact_mismatches"]["value"] > 0


def test_a_host_operator_in_the_plan_fails_failed_queries(
        data_dir, cell, monkeypatch):
    """The gather put back on the host tier once set-up is over (set-up
    refuses a warm-up query that failed)."""
    real = run.warm_up
    monkeypatch.setattr(run, "warm_up", lambda *a: (
        real(*a), monkeypatch.setattr(
            engine, "NON_TPU_NODES",
            engine.NON_TPU_NODES - {"DeviceToHostExec"}))[0])
    r = drive(cell)
    assert r["correct"] is False
    c = r["compared"]["failed_queries"]
    assert c["value"] == r["failed"] == r["attempted"] > c["limit"]
