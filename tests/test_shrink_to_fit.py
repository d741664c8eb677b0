"""``shrink_to_fit`` as one ``compact_shrink`` program: the rows it keeps
against a numpy boolean-index reference, what the program may and may not
hold (no ``cumsum``, no gather longer than the output bucket), and the
``shrink`` / ``shrink.skip`` spans of a small query."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.device import (DeviceColumn, DeviceTable,
                                              _compact_impl,
                                              _compact_shrink_impl,
                                              prefix_sum, shrink_to_fit,
                                              stable_partition_order)

from harness import jaxpr_eqns as _eqns

MIN_BUCKET = 1024


def _mask(kind: str, cap: int, rng) -> np.ndarray:
    """Masks by shape; all but ``full`` keep few enough rows to shrink a
    4,096-row table into its 1,024-row bucket."""
    iota = np.arange(cap)
    if kind == "prefix":
        return iota < min(700, cap // 2)
    if kind == "scattered":
        return rng.random(cap) < 0.2
    if kind == "empty":
        return np.zeros(cap, bool)
    if kind == "full":
        return np.ones(cap, bool)
    assert kind == "last_row"
    return iota == cap - 1


def _column(kind: str, cap: int, rng) -> DeviceColumn:
    validity = rng.random(cap) < 0.8
    if kind == "int32":
        return DeviceColumn(
            jnp.asarray(rng.integers(-2**31, 2**31 - 1, cap, dtype=np.int32)),
            jnp.asarray(validity), dt.INT)
    if kind == "float64":
        return DeviceColumn(jnp.asarray(rng.standard_normal(cap)),
                            jnp.asarray(validity), dt.DOUBLE)
    if kind == "int64":
        return DeviceColumn(
            jnp.asarray(rng.integers(-2**62, 2**62, cap, dtype=np.int64)),
            jnp.asarray(validity), dt.LONG)
    if kind == "string":
        return DeviceColumn(
            jnp.asarray(rng.integers(0, 256, (cap, 16), dtype=np.uint8)),
            jnp.asarray(validity), dt.STRING,
            lengths=jnp.asarray(rng.integers(0, 17, cap, dtype=np.int32)))
    if kind == "struct":
        fields = (dt.StructField("a", dt.INT),
                  dt.StructField("s", dt.STRING))
        return DeviceColumn(
            jnp.zeros(cap, jnp.uint8), jnp.asarray(validity),
            dt.StructType(fields),
            children=(_column("int32", cap, rng),
                      _column("string", cap, rng)))
    assert kind == "all_valid"
    return DeviceColumn(
        jnp.asarray(rng.integers(0, 1000, cap, dtype=np.int32)),
        jnp.ones(cap, bool), dt.INT, all_valid=True)


def _planes(c: DeviceColumn):
    """Every per-row array of a column except its validity, children's
    validity planes included."""
    out = [c.data]
    if c.lengths is not None:
        out.append(c.lengths)
    if c.elem_validity is not None:
        out.append(c.elem_validity)
    for k in c.children or ():
        out.append(k.validity)
        out.extend(_planes(k))
    return [np.asarray(a) for a in out]


def _table(col_kind: str, mask: np.ndarray, rng) -> DeviceTable:
    cap = mask.shape[0]
    return DeviceTable((_column(col_kind, cap, rng),
                        _column("int32", cap, rng)),
                       jnp.asarray(mask),
                       jnp.asarray(mask.sum(), dtype=jnp.int32),
                       ("c", "k"))


@pytest.mark.parametrize("pass_num_rows", [True, False],
                         ids=["host_count", "synced_count"])
@pytest.mark.parametrize("cap,want_cap", [(4096, 1024), (1024, 1024),
                                          (512, 512)],
                         ids=["4096to1024", "equal", "below_min_bucket"])
@pytest.mark.parametrize("col_kind", ["int32", "float64", "int64", "string",
                                      "struct", "all_valid"])
@pytest.mark.parametrize("mask_kind", ["prefix", "scattered", "empty", "full",
                                       "last_row"])
def test_shrink_to_fit_keeps_the_live_rows(mask_kind, col_kind, cap, want_cap,
                                           pass_num_rows):
    rng = np.random.default_rng(
        [29, cap, sum(map(ord, mask_kind + col_kind))])
    mask = _mask(mask_kind, cap, rng)
    n = int(mask.sum())
    table = _table(col_kind, mask, rng)
    out = shrink_to_fit(table, MIN_BUCKET,
                        num_rows=n if pass_num_rows else None)
    if mask_kind == "full":
        want_cap = cap       # nothing to drop: the input comes back
    if want_cap == cap:
        assert out is table
        return
    assert out.capacity == want_cap
    assert int(out.num_rows) == n
    assert out.names == table.names
    np.testing.assert_array_equal(np.asarray(out.row_mask),
                                  np.arange(want_cap) < n)
    for got, src in zip(out.columns, table.columns):
        assert got.dtype == src.dtype and got.all_valid == src.all_valid
        validity = np.asarray(got.validity)
        assert validity.shape == (want_cap,)
        np.testing.assert_array_equal(validity[:n],
                                      np.asarray(src.validity)[mask])
        assert not validity[n:].any()
        for g, s in zip(_planes(got), _planes(src)):
            assert g.shape == (want_cap,) + s.shape[1:]
            np.testing.assert_array_equal(g[:n], s[mask])


@pytest.mark.parametrize("mask_kind", ["prefix", "scattered", "empty", "full",
                                       "last_row"])
def test_compact_keeps_its_contract_on_the_blocked_prefix_sum(mask_kind):
    """``DeviceTable.compact()``: same capacity out, live rows first in
    their order, the permutation that of a stable argsort."""
    rng = np.random.default_rng(7)
    mask = _mask(mask_kind, 4096, rng)
    order = np.asarray(stable_partition_order(jnp.asarray(mask)))
    np.testing.assert_array_equal(order, np.argsort(~mask, kind="stable"))
    table = _table("string", mask, rng)
    out = table.compact()
    n = int(mask.sum())
    assert out.capacity == 4096 and int(out.num_rows) == n
    np.testing.assert_array_equal(np.asarray(out.row_mask),
                                  np.arange(4096) < n)
    np.testing.assert_array_equal(np.asarray(out.columns[0].data)[:n],
                                  np.asarray(table.columns[0].data)[mask])


@pytest.mark.parametrize("n", [1, 5, 1024, 1025, 5000])
def test_prefix_sum_is_cumsum(n):
    x = np.random.default_rng(n).integers(0, 3, (2, n)).astype(np.int32)
    np.testing.assert_array_equal(np.asarray(prefix_sum(jnp.asarray(x))),
                                  np.cumsum(x, axis=-1))


def _wide_table() -> DeviceTable:
    rng = np.random.default_rng(3)
    mask = rng.random(1 << 14) < 0.01
    cols = tuple(_column(k, mask.shape[0], rng)
                 for k in ("int32", "float64", "int64", "string", "struct",
                           "all_valid"))
    return DeviceTable(cols, jnp.asarray(mask),
                       jnp.asarray(mask.sum(), dtype=jnp.int32),
                       tuple(f"c{i}" for i in range(len(cols))))


@pytest.mark.parametrize("program", ["compact_shrink", "compact"])
def test_compaction_programs_hold_no_cumsum(program):
    """``jnp.cumsum`` over a row-capacity vector costs the TPU compiler
    17-31 s a program (PERF.md, PR 27)."""
    table = _wide_table()
    jaxpr = jax.make_jaxpr(
        (lambda t: _compact_shrink_impl(t, 1024))
        if program == "compact_shrink" else _compact_impl)(table)
    names = {e.primitive.name for e in _eqns(jaxpr.jaxpr)}
    assert not {n for n in names if n.startswith("cum")}, names
    assert "sort" not in names and "while" not in names, names
    assert "gather" in names and "scatter" in names


def test_compact_shrink_gathers_no_more_than_the_output_bucket():
    table = _wide_table()
    out_cap = 1024
    jaxpr = jax.make_jaxpr(lambda t: _compact_shrink_impl(t, out_cap))(table)
    gathers = [e for e in _eqns(jaxpr.jaxpr) if e.primitive.name == "gather"]
    # one per array of the table: data, validity, lengths, children
    n_arrays = len(jax.tree_util.tree_leaves(table.columns))
    assert len(gathers) == n_arrays
    for e in gathers:
        assert e.invars[1].aval.shape[0] <= out_cap, e
        assert e.outvars[0].aval.shape[0] <= out_cap, e
    scatters = [e for e in _eqns(jaxpr.jaxpr)
                if e.primitive.name.startswith("scatter")]
    assert len(scatters) == 1
    assert scatters[0].outvars[0].aval.shape == (out_cap,)


@pytest.mark.parametrize("rows,expect", [(4000, "ran"), (100, "skipped")],
                         ids=["slack_to_drop", "one_bucket_batches"])
def test_a_small_query_books_its_shrinks_by_span(rows, expect, monkeypatch):
    """A filtered group-by over two partitions: every ``shrink_to_fit`` of
    the query is a ``shrink`` span (the program ran: one ``dispatch`` of
    ``srt_compact_shrink`` inside) or a ``shrink.skip`` (the input came
    back)."""
    import pyarrow as pa
    from spark_rapids_tpu.columnar import device as D
    from spark_rapids_tpu.expr.functions import col, lit, sum as fsum
    from spark_rapids_tpu.session import TpuSession

    calls = []
    real = D.shrink_to_fit

    def recording(table, *args, **kwargs):
        out = real(table, *args, **kwargs)
        calls.append((table.capacity, out.capacity, out is table))
        return out

    # the exchange and the aggregate import it when they run
    monkeypatch.setattr(D, "shrink_to_fit", recording)
    sess = TpuSession({"spark.rapids.tpu.batchRowsMinBucket": 64})
    try:
        rng = np.random.default_rng(11)
        df = sess.create_dataframe(pa.table({
            "k": rng.integers(0, 5, rows).astype(np.int32),
            "v": rng.random(rows)}), num_partitions=2)
        got = df.filter(col("v") > lit(0.5)).group_by("k") \
            .agg(fsum(col("v")).alias("s")).collect(device=True)
        assert got.num_rows == 5
        phases = sess.last_query_phases()["phases"]
    finally:
        sess.close()
    ran = [c for c in calls if not c[2]]
    skipped = [c for c in calls if c[2]]
    assert {"ran": ran, "skipped": skipped}[expect], calls
    assert all(out < cap for cap, out, _ in ran)
    assert phases.get("shrink", {"calls": 0})["calls"] == len(ran)
    assert phases.get("shrink.skip", {"calls": 0})["calls"] == len(skipped)


@pytest.mark.parametrize("groups,span,dispatches",
                         [(3, "agg.dense", 8), (5000, "agg.scatter", 7)],
                         ids=["few_groups", "many_groups"])
def test_a_grouped_query_books_the_branch_its_batches_took(groups, span,
                                                           dispatches):
    """Every aggregate batch of a filtered group-by over two partitions —
    the two fused partials, whose count the exchange resolves, and the final
    merge, whose count its ``shrink_to_fit`` syncs — books ``agg.dense``
    (at most FEW_GROUPS groups) or ``agg.scatter``, from the count the host
    already held: programs and blocking syncs are the parent's (PR 30: 8 / 7
    dispatches — the 5,000-group final state skips one shrink — and 3 syncs
    + 1 download either way; the reads of the stage's statistics, one a
    handle, which PR 30's code made under no span, are held apart)."""
    import pyarrow as pa
    from spark_rapids_tpu.expr.functions import col, lit, sum as fsum
    from spark_rapids_tpu.session import TpuSession

    sess = TpuSession({"spark.rapids.tpu.batchRowsMinBucket": 64})
    try:
        rng = np.random.default_rng(11)
        rows = 20000
        df = sess.create_dataframe(pa.table({
            "k": rng.integers(0, groups, rows).astype(np.int32),
            "v": rng.random(rows)}), num_partitions=2)
        got = df.filter(col("v") > lit(0.5)).group_by("k") \
            .agg(fsum(col("v")).alias("s")).collect(device=True)
        assert got.num_rows == (3 if groups == 3 else 4275)
        phases = sess.last_query_phases()["phases"]
    finally:
        sess.close()
    other = ({"agg.dense", "agg.scatter"} - {span}).pop()
    assert phases[span]["calls"] == 3 and other not in phases, phases
    assert phases["dispatch"]["calls"] == dispatches
    assert (phases["sync"]["calls"] - phases["stage.stats"]["handles"],
            phases["d2h"]["calls"]) == (3, 1)
