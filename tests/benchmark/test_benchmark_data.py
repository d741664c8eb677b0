"""The benchmark's data: the generated table against the spec's schema and
dbgen's rules, and the files a run writes, reuses and deletes."""
import os

import numpy as np
import pyarrow.compute as pc
import pyarrow.parquet as pq
import pytest

from benchmark import cells, data, tables

SF = 0.002
LINEITEM = ["l_orderkey", "l_partkey", "l_suppkey", "l_linenumber",
            "l_quantity", "l_extendedprice", "l_discount", "l_tax",
            "l_returnflag", "l_linestatus", "l_shipdate", "l_commitdate",
            "l_receiptdate", "l_shipinstruct", "l_shipmode", "l_comment"]
CONFIG = cells.load_cell("sf1.q1").config


@pytest.fixture(scope="module")
def lineitem():
    return tables.generate("lineitem", SF, 7)


def test_lineitem_has_every_column_of_the_spec_and_the_configs_rows(lineitem):
    assert lineitem.column_names == LINEITEM
    assert lineitem.num_rows == int(CONFIG["rows"]["lineitem"] * SF)


def test_comments_fit_varchar_44(lineitem):
    lengths = pc.utf8_length(lineitem["l_comment"])
    assert 10 <= pc.min(lengths).as_py() and pc.max(lengths).as_py() <= 44


def test_flags_follow_dbgens_rule_so_q1_has_four_groups(lineitem):
    df = lineitem.select(["l_returnflag", "l_linestatus", "l_shipdate",
                          "l_receiptdate"]).to_pandas()
    current = np.datetime64("1995-06-17")
    ship = df.l_shipdate.to_numpy().astype("datetime64[D]")
    receipt = df.l_receiptdate.to_numpy().astype("datetime64[D]")
    assert ((df.l_linestatus == "O") == (ship > current)).all()
    assert ((df.l_returnflag == "N") == (receipt > current)).all()
    assert set(df.l_returnflag[receipt <= current]) == {"A", "R"}
    groups = set(zip(df.l_returnflag, df.l_linestatus))
    assert groups == {("A", "F"), ("N", "F"), ("N", "O"), ("R", "F")}


@pytest.mark.parametrize("other,same", [(7, True), (8, False)])
def test_a_seed_gives_the_same_table_and_every_seed_the_same_size(
        lineitem, other, same):
    again = tables.generate("lineitem", SF, other)
    assert again.num_rows == lineitem.num_rows
    assert again.schema == lineitem.schema
    assert again.equals(lineitem) == same


def test_a_seed_past_32_bits_is_taken():
    assert tables.generate("lineitem", SF, 2**31 + 11).num_rows > 0


def test_an_unknown_table_has_no_generator():
    with pytest.raises(ModuleNotFoundError):
        tables.generate("nation", SF, 1)


@pytest.fixture
def data_dir(tmp_path, monkeypatch):
    monkeypatch.setattr(data, "DATA_DIR", str(tmp_path / "data"))
    return tmp_path / "data"


def test_only_the_tables_asked_for_are_written_two_files_each(data_dir):
    root = data.ensure_data(CONFIG, ["lineitem"], 5, SF)
    assert os.listdir(root) == ["lineitem"]
    assert sorted(os.listdir(os.path.join(root, "lineitem"))) == [
        data.DONE, "part-0.parquet", "part-1.parquet"]
    t = pq.read_table(os.path.join(root, "lineitem"))
    assert t.column_names == LINEITEM
    assert t.equals(tables.generate("lineitem", SF, 5))


def test_a_seeds_data_is_reused_and_another_seed_deletes_it(data_dir):
    root = data.ensure_data(CONFIG, ["lineitem"], 5, SF)
    part = os.path.join(root, "lineitem", "part-0.parquet")
    written = os.stat(part).st_mtime_ns
    assert data.ensure_data(CONFIG, ["lineitem"], 5, SF) == root
    assert os.stat(part).st_mtime_ns == written
    # a table whose writing did not reach its end is written again
    os.remove(os.path.join(root, "lineitem", data.DONE))
    data.ensure_data(CONFIG, ["lineitem"], 5, SF)
    assert os.stat(part).st_mtime_ns > written
    other = data.ensure_data(CONFIG, ["lineitem"], 6, SF)
    assert os.listdir(data_dir) == [os.path.basename(other)]


def test_the_writer_is_a_process_that_never_loads_jax(data_dir, monkeypatch):
    # a child that imported jax would look for the chip its parent holds
    monkeypatch.setenv("PYTHONPATH", str(data_dir.parent / "poison"))
    os.makedirs(data_dir.parent / "poison" / "jax")
    with open(data_dir.parent / "poison" / "jax" / "__init__.py", "w") as f:
        f.write("raise ImportError('the data writer imported jax')\n")
    root = data.ensure_data(CONFIG, ["lineitem"], 9, SF)
    assert os.path.exists(os.path.join(root, "lineitem", data.DONE))
