"""TPC-H Q13 (customer distribution) through a strict ``TpuSession`` from
Parquet, two files a table, against numpy counts of the same files: the
NOT LIKE '%special%requests%' filter on the device's regex NFA, the left
outer join that keeps customers with no order as one null-extended row, the
two counts. Two data sets: ``tools/tpch.py``'s, whose comments embed
'special ... requests' in 5 % of the orders (so the filter removes rows),
and the benchmark's own generators', where a third of the customers have no
order (so the join emits null-extended rows). Both exact."""
import os
import re

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from benchmark import engine, references
from benchmark import tables as bench_tables
from spark_rapids_tpu.tools import tpch

STRICT = {"spark.rapids.sql.test.enabled": True,
          "spark.rapids.tpu.fallback.enabled": False,
          "spark.rapids.tpu.fallback.quarantine.enabled": False}
COLUMNS = {"customer": ["c_custkey"],
           "orders": ["o_orderkey", "o_custkey", "o_comment"]}


def write_two_files(root, name, table: pa.Table):
    d = os.path.join(root, name)
    os.makedirs(d)
    half = -(-table.num_rows // 2)
    for i in range(2):
        pq.write_table(table.slice(i * half, half),
                       os.path.join(d, f"part-{i}.parquet"))


def tools_data(root):
    """``tools/tpch.py`` at SF 0.01: 1,500 customers, 15,000 orders, 5 % of
    the comments 'special <word> requests'."""
    write_two_files(root, "customer", tpch.gen_customer(0.01))
    write_two_files(root, "orders", tpch.gen_orders(0.01))


def bench_data(root):
    """The benchmark's generators at SF 0.02: 3,000 customers, 30,000
    orders over two thirds of the customer keys."""
    for name in COLUMNS:
        write_two_files(root, name, bench_tables.generate(name, 0.02, 13))


def numpy_q13(root):
    """(c_count, custdist) rows in the query's order, from numpy counts."""
    cust = pq.read_table(os.path.join(root, "customer")).to_pandas()
    orders = pq.read_table(os.path.join(root, "orders")).to_pandas()
    rx = re.compile("special.*requests", re.DOTALL)
    kept = np.array([c is not None and rx.search(c) is None
                     for c in orders.o_comment])
    index = {k: i for i, k in enumerate(cust.c_custkey)}
    per_cust = np.bincount([index[k] for k in orders.o_custkey[kept]],
                           minlength=len(cust))
    c_count, custdist = np.unique(per_cust, return_counts=True)
    rows = sorted(zip(custdist.tolist(), c_count.tolist()), reverse=True)
    return ([c for _, c in rows], [d for d, _ in rows],
            int((~kept).sum()), int((per_cust == 0).sum()))


@pytest.fixture(scope="module", params=["tools-sf0.01", "benchmark-sf0.02"])
def q13_run(request, tmp_path_factory):
    """One strict run of Q13 over a data set: (data root, answer, executed
    plan's node names, the query's phases)."""
    from spark_rapids_tpu.utils.tracing import get_tracer
    root = str(tmp_path_factory.mktemp("q13"))
    (tools_data if request.param.startswith("tools") else bench_data)(root)
    sess = engine.open_session({"session_conf": STRICT, "mesh": None})
    tracer = get_tracer()
    was = tracer.enabled
    tracer.enabled = True
    tracer.clear()
    try:
        frames = {n: sess.read_parquet(os.path.join(root, n))
                  for n in COLUMNS}
        assert all(f.num_partitions() == 2 for f in frames.values())
        got = tpch.q13(frames).collect().to_pandas()
        nodes = engine.executed_nodes(sess.executed_plan)
        phases = sess.last_query_phases()["phases"]
        expands = [e.args for e in tracer.events()
                   if e.name == "join.probe.expand"]
    finally:
        tracer.enabled = was
        tracer.clear()
        sess.close()
    return request.param, root, got, nodes, phases, expands


def test_q13_equals_numpy_counts_exactly(q13_run):
    name, root, got, _, _, _ = q13_run
    c_count, custdist, dropped, unmatched = numpy_q13(root)
    assert list(got.columns) == ["c_count", "custdist"]
    assert got.c_count.tolist() == c_count
    assert got.custdist.tolist() == custdist
    if name.startswith("tools"):
        # the filter removes the 5 % whose comment matches
        assert 0.04 < dropped / 15_000 < 0.06
    else:
        # no comment of the benchmark's pool matches; a third of the
        # customers have no order at all
        assert dropped == 0 and unmatched == 1_000
        assert custdist[c_count.index(0)] == unmatched


def test_the_benchmark_reference_agrees(q13_run):
    _, root, got, _, _, _ = q13_run
    ref = references.compute("q13", root, COLUMNS)
    assert ref.c_count.tolist() == got.c_count.tolist()
    assert ref.custdist.tolist() == got.custdist.tolist()


def test_every_operator_ran_on_the_device(q13_run):
    _, _, _, nodes, phases, _ = q13_run
    assert engine.plan_faults(nodes, {"must_hold": [[
        "TpuShuffledHashJoinExec", "TpuBroadcastHashJoinExec"]]}) == []
    assert "TpuParquetScanExec" in nodes
    assert phases["decode.dense"]["calls"] >= 1


def test_the_outer_join_books_its_unmatched_customers(q13_run):
    """A customer's key appears once in ``customer``, so each with no kept
    order is one probe row with no match: ``unmatched`` over the expand
    spans is numpy's count, and ``rows_out`` is the kept orders plus it."""
    _, root, _, _, phases, expands = q13_run
    _, _, dropped, unmatched = numpy_q13(root)
    kept = pq.read_table(os.path.join(root, "orders")).num_rows - dropped
    assert expands and all("rows_out" in e for e in expands)
    assert sum(e.get("unmatched", 0) for e in expands) == unmatched
    assert phases["join.probe.expand"].get("unmatched", 0) == unmatched
    assert phases["join.probe.expand"]["rows_out"] == kept + unmatched
