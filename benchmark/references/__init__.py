"""Plain references: pandas / numpy over the same Parquet files, one module
per query, found by the name the traffic file gives. They import nothing of
the program and take nothing it has made.

Each module has ``reference(tables, float_dtype)``: ``tables`` maps a table
name to a pandas frame of the columns the traffic file lists, with every
float column already cast to ``float_dtype``. float64 is what the
configurations state; float32 is the control in the precision below, where
every product, sum and mean is then taken in float32.
"""
import importlib
import os

import numpy as np
import pyarrow.parquet as pq


def read_tables(root: str, columns: dict, float_dtype=np.float64) -> dict:
    out = {}
    for table, cols in columns.items():
        df = pq.read_table(os.path.join(root, table),
                           columns=list(cols)).to_pandas()
        if float_dtype != np.float64:
            floats = [c for c in df.columns if df[c].dtype.kind == "f"]
            df = df.astype({c: float_dtype for c in floats})
        out[table] = df
    return out


def days(series) -> np.ndarray:
    """A date column as days since the epoch."""
    return series.to_numpy().astype("datetime64[D]").astype(np.int64)


def day(iso: str) -> int:
    return int(np.datetime64(iso, "D").astype(np.int64))


def compute(name: str, root: str, columns: dict, float_dtype=np.float64):
    """The reference answer of query ``name`` over the files under ``root``."""
    module = importlib.import_module(f"benchmark.references.{name}")
    return module.reference(read_tables(root, columns, float_dtype),
                            float_dtype)
