"""Differential test harness (reference: SparkQueryCompareTestSuite /
integration_tests asserts.py:499 assert_gpu_and_cpu_are_equal_collect).

Runs the same DataFrame on the device path and the CPU fallback path and
asserts row-equality with float tolerance.
"""
from __future__ import annotations

import math

import numpy as np
import pyarrow as pa

__all__ = ["assert_tpu_cpu_equal", "assert_tables_equal", "data_gen",
           "jaxpr_eqns"]


def _sort_table(t: pa.Table) -> pa.Table:
    if t.num_rows <= 1 or t.num_columns == 0:
        return t
    # nested columns aren't sortable; order by the scalar columns only
    keys = [(f.name, "ascending") for f in t.schema
            if not pa.types.is_nested(f.type)]
    if not keys:
        return t
    try:
        return t.sort_by(keys)
    except (pa.ArrowInvalid, pa.ArrowTypeError):
        return t


def jaxpr_eqns(jaxpr):
    """Every equation of a jaxpr, loop, call and branch bodies included."""
    import jax
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from jaxpr_eqns(sub)


def assert_tables_equal(actual: pa.Table, expected: pa.Table,
                        ignore_order: bool = True, rel_tol: float = 1e-9):
    assert actual.column_names == expected.column_names, \
        f"column names differ: {actual.column_names} vs {expected.column_names}"
    assert actual.num_rows == expected.num_rows, \
        f"row count differs: {actual.num_rows} vs {expected.num_rows}"
    if ignore_order:
        actual = _sort_table(actual)
        expected = _sort_table(expected)
    for name in actual.column_names:
        a = actual.column(name).to_pylist()
        e = expected.column(name).to_pylist()
        for i, (av, ev) in enumerate(zip(a, e)):
            if av is None or ev is None:
                assert av is None and ev is None, \
                    f"{name}[{i}]: {av!r} vs {ev!r}"
            elif isinstance(av, float) and isinstance(ev, float):
                if math.isnan(av) or math.isnan(ev):
                    assert math.isnan(av) and math.isnan(ev), \
                        f"{name}[{i}]: {av!r} vs {ev!r}"
                else:
                    assert math.isclose(av, ev, rel_tol=rel_tol, abs_tol=1e-9), \
                        f"{name}[{i}]: {av!r} vs {ev!r}"
            else:
                assert av == ev, f"{name}[{i}]: {av!r} vs {ev!r}"


def assert_tpu_cpu_equal(df, ignore_order: bool = True, rel_tol: float = 1e-9):
    device = df.collect(device=True)
    cpu = df.collect(device=False)
    assert_tables_equal(device, cpu, ignore_order, rel_tol)
    return device


# ---------------------------------------------------------------------------
# Random data generation (reference: integration_tests data_gen.py)
# ---------------------------------------------------------------------------
def data_gen(rng, n: int, spec: dict, null_prob: float = 0.15) -> pa.Table:
    """spec: name -> one of int8,int16,int32,int64,float32,float64,bool,string,
    date,timestamp or ('int64', lo, hi) tuples."""
    cols = {}
    for name, kind in spec.items():
        lo, hi = None, None
        if isinstance(kind, tuple):
            kind, lo, hi = kind
        if kind.startswith("int"):
            bits = int(kind[3:])
            lo = lo if lo is not None else -(2 ** (bits - 2))
            hi = hi if hi is not None else 2 ** (bits - 2)
            vals = rng.integers(lo, hi, size=n, dtype=np.int64).astype(f"int{bits}")
            arr = pa.array(vals)
        elif kind == "float32" or kind == "float64":
            vals = rng.normal(0, 100, size=n)
            # sprinkle special values like the reference's generators
            special = rng.random(n)
            vals = np.where(special < 0.02, np.inf, vals)
            vals = np.where((special >= 0.02) & (special < 0.04), -np.inf, vals)
            vals = np.where((special >= 0.04) & (special < 0.06), np.nan, vals)
            vals = np.where((special >= 0.06) & (special < 0.08), -0.0, vals)
            arr = pa.array(vals.astype(kind))
        elif kind == "bool":
            arr = pa.array(rng.integers(0, 2, size=n).astype(bool))
        elif kind == "string":
            words = ["", "a", "ab", "abc", "tpu", "Spark", "RAPIDS", "xyzzy",
                     "longer string value", "ünïcode"]
            arr = pa.array([words[i] for i in rng.integers(0, len(words), size=n)])
        elif kind == "date":
            arr = pa.array(rng.integers(0, 20000, size=n).astype("int32"),
                           type=pa.int32()).cast(pa.date32())
        elif kind == "timestamp":
            arr = pa.array(rng.integers(0, 2 ** 48, size=n),
                           type=pa.int64()).cast(pa.timestamp("us"))
        else:
            raise ValueError(kind)
        if null_prob > 0:
            mask = rng.random(n) < null_prob
            arr = pa.array(arr.to_pylist(), type=arr.type,
                           mask=mask)
        cols[name] = arr
    return pa.table(cols)
