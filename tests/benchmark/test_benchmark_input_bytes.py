"""The bytes function, on a tiny Parquet directory."""
import os

import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from benchmark import input_bytes


@pytest.fixture
def root(tmp_path):
    d = tmp_path / "t"
    os.makedirs(d)
    for i in range(2):      # two files: the count is over the directory
        pq.write_table(pa.table({
            "k": pa.array([1, 2, 3], type=pa.int64()),
            "d": pa.array([1, 2, 3], type=pa.int32()).cast(pa.date32()),
            "x": pa.array([1.0, 2.0, 3.0]),
            "s": pa.array(["a", "bcd", None]),
            "b": pa.array([True, False, True]),
            "unused": pa.array([0.0, 0.0, 0.0]),
        }), d / f"part-{i}.parquet")
    return str(tmp_path)


@pytest.mark.parametrize("cols,want", [
    (["k"], 6 * 8),
    (["d"], 6 * 4),
    (["x", "k"], 6 * 16),
    (["s"], 2 * 4),            # byte lengths; a null is no bytes
    (["b"], 1),                # six bits
])
def test_logical_bytes_of_the_listed_columns(root, cols, want):
    assert input_bytes.query_input_bytes(root, {"t": cols}) == want


def test_columns_not_listed_are_not_counted(root):
    listed = input_bytes.query_input_bytes(root, {"t": ["k", "x"]})
    every = input_bytes.query_input_bytes(
        root, {"t": ["k", "d", "x", "s", "b", "unused"]})
    assert listed < every
