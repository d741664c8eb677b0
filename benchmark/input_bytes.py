"""The least bytes a query has to read: the logical size of the Parquet
columns it references. Counted from the files and the traffic file's column
list, never from the plan, so that it is the same work whatever implements
it: rows x fixed width, strings by their byte lengths (no offsets, no
validity, no compression)."""
import os

import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq


def column_bytes(column: pa.ChunkedArray) -> int:
    t = column.type
    if pa.types.is_string(t) or pa.types.is_large_string(t) \
            or pa.types.is_binary(t) or pa.types.is_large_binary(t):
        return int(pc.sum(pc.binary_length(column)).as_py() or 0)
    if pa.types.is_boolean(t):
        return -(-len(column) // 8)
    return len(column) * t.bit_width // 8


def query_input_bytes(root: str, columns: dict) -> int:
    """``columns``: table name -> column names, as a traffic file lists."""
    n = 0
    for table, cols in columns.items():
        t = pq.read_table(os.path.join(root, table), columns=list(cols))
        n += sum(column_bytes(t.column(c)) for c in cols)
    return n
