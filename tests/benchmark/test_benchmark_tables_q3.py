"""The generators Q3 brought: ORDERS and CUSTOMER against the spec's schema,
their keys against ``lineitem.py``'s, and their streams against each other."""
import numpy as np
import pyarrow.compute as pc
import pytest

from benchmark import cells, tables

SF = 0.002
SEED = 7
ORDERS = ["o_orderkey", "o_custkey", "o_orderstatus", "o_totalprice",
          "o_orderdate", "o_orderpriority", "o_clerk", "o_shippriority",
          "o_comment"]
CUSTOMER = ["c_custkey", "c_name", "c_address", "c_nationkey", "c_phone",
            "c_acctbal", "c_mktsegment", "c_comment"]
ROWS = cells.load_cell("sf1-mesh4.q3").config["rows"]


@pytest.fixture(scope="module")
def three():
    return {t: tables.generate(t, SF, SEED)
            for t in ("lineitem", "orders", "customer")}


@pytest.mark.parametrize("table,columns", [("orders", ORDERS),
                                           ("customer", CUSTOMER)])
def test_every_column_of_the_spec_and_the_configurations_rows(
        three, table, columns):
    assert three[table].column_names == columns
    assert three[table].num_rows == int(ROWS[table] * SF)


def test_the_one_chip_configuration_names_the_same_lineitem_rows(three):
    assert three["lineitem"].num_rows == int(ROWS["lineitem"] * SF)
    assert cells.load_cell("sf1.q3").config["rows"]["lineitem"] \
        == ROWS["lineitem"]


def test_every_line_item_finds_its_order_and_every_order_its_customer(three):
    orderkeys = three["orders"]["o_orderkey"].to_numpy()
    assert len(np.unique(orderkeys)) == len(orderkeys)
    assert np.isin(three["lineitem"]["l_orderkey"].to_numpy(),
                   orderkeys).all()
    custkeys = three["customer"]["c_custkey"].to_numpy()
    assert (custkeys == np.arange(1, len(custkeys) + 1)).all()
    o_cust = three["orders"]["o_custkey"].to_numpy()
    assert np.isin(o_cust, custkeys).all()
    # cl. 4.2.3: a third of the customers have no order
    assert (o_cust % 3 != 0).all()


def test_q3s_filters_keep_a_fifth_a_half_and_a_half(three):
    cut = np.datetime64("1995-03-15")
    seg = three["customer"]["c_mktsegment"].to_pandas()
    assert set(seg) == {"AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD",
                        "MACHINERY"}
    assert 0.12 < (seg == "BUILDING").mean() < 0.28
    odate = three["orders"]["o_orderdate"].to_numpy().astype("datetime64[D]")
    assert odate.min() >= np.datetime64("1992-01-01")
    assert odate.max() <= np.datetime64("1998-08-02")
    assert 0.44 < (odate < cut).mean() < 0.54
    ship = three["lineitem"]["l_shipdate"].to_numpy().astype("datetime64[D]")
    assert 0.50 < (ship > cut).mean() < 0.59


def test_text_columns_fit_the_specs_widths(three):
    for table, column, width in (("orders", "o_comment", 79),
                                 ("orders", "o_clerk", 15),
                                 ("customer", "c_address", 40),
                                 ("customer", "c_comment", 117),
                                 ("customer", "c_name", 25),
                                 ("customer", "c_phone", 15)):
        lengths = pc.utf8_length(three[table][column])
        assert 1 <= pc.min(lengths).as_py() \
            and pc.max(lengths).as_py() <= width, column
    assert three["customer"]["c_name"][0].as_py() == "Customer#000000001"
    phone = three["customer"].slice(0, 50).to_pandas()
    assert (phone.c_phone.str[:2].astype(int) == phone.c_nationkey + 10).all()


@pytest.mark.parametrize("table", ["orders", "customer"])
@pytest.mark.parametrize("other,same", [(SEED, True), (SEED + 1, False),
                                        (2**31 + 11, False)])
def test_a_seed_reproduces_and_seeds_differ(three, table, other, same):
    again = tables.generate(table, SF, other)
    assert again.num_rows == three[table].num_rows
    assert again.schema == three[table].schema
    assert again.equals(three[table]) == same


def test_no_two_tables_share_a_stream(three):
    """Were the tables drawn from one stream of the seed, their first draws
    would be the same numbers: orders' customer keys, customer's nation keys
    and lineitem's ship dates would rank alike."""
    n = three["customer"].num_rows
    first = {
        "lineitem": three["lineitem"]["l_shipdate"].to_numpy()
        .astype("datetime64[D]").astype(np.int64)[:n],
        "orders": three["orders"]["o_custkey"].to_numpy()[:n],
        "customer": three["customer"]["c_nationkey"].to_numpy()[:n]}
    names = sorted(first)
    for i, a in enumerate(names):
        for b in names[i + 1:]:
            r = np.corrcoef(first[a], first[b])[0, 1]
            assert abs(r) < 0.2, (a, b, r)
    from benchmark.tables import customer, orders
    assert orders.STREAM != customer.STREAM
