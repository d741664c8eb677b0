"""Device parquet decode tests (reference: GpuParquetScanBase.scala:995,1194
device decode; this path is io/parquet_thrift.py + io/parquet_device.py +
exec/scan.py TpuParquetScanExec)."""
import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu.session import TpuSession
from spark_rapids_tpu.expr.functions import col, sum as f_sum

from harness import assert_tables_equal, assert_tpu_cpu_equal


def _write(tmp_path, n=4000, codec="snappy", use_dictionary=True,
           row_group_size=1500, nulls=True, with_strings=True):
    rng = np.random.default_rng(7)
    data = {
        "i64": pa.array(rng.integers(-10**12, 10**12, n), type=pa.int64()),
        "i32": pa.array(rng.integers(-2**30, 2**30, n).astype(np.int32)),
        "f64": pa.array(rng.normal(size=n)),
        "f32": pa.array(rng.normal(size=n).astype(np.float32)),
        "b": pa.array(rng.integers(0, 2, n).astype(bool)),
        "lowcard": pa.array(rng.integers(0, 40, n), type=pa.int64()),
        "date": pa.array(rng.integers(0, 20000, n).astype(np.int32)).cast(
            pa.date32()),
        "ts": pa.array(rng.integers(0, 2**48, n), type=pa.int64()).cast(
            pa.timestamp("us")),
    }
    if with_strings:
        data["s"] = pa.array([f"str{i % 11}" for i in range(n)])
    t = pa.table(data)
    if nulls:
        cols = {}
        for name in t.column_names:
            mask = rng.random(n) < 0.12
            arr = t.column(name).combine_chunks()
            cols[name] = pa.array(arr.to_pylist(), type=arr.type, mask=mask)
        t = pa.table(cols)
    p = str(tmp_path / "data.parquet")
    pq.write_table(t, p, row_group_size=row_group_size, compression=codec,
                   use_dictionary=use_dictionary)
    return p, t


@pytest.fixture
def sess():
    return TpuSession({"spark.rapids.tpu.shuffle.mode": "host",
                       "spark.rapids.tpu.batchRowsMinBucket": 64})


@pytest.mark.parametrize("codec,use_dict", [("snappy", True),
                                            ("none", False),
                                            ("zstd", True),
                                            ("gzip", False)])
def test_device_scan_differential(sess, tmp_path, codec, use_dict):
    p, t = _write(tmp_path, codec=codec, use_dictionary=use_dict)
    df = sess.read_parquet(p)
    dev = df.collect(device=True)
    cpu = df.collect(device=False)
    assert_tables_equal(dev, cpu, ignore_order=False)
    assert_tables_equal(dev, t, ignore_order=False)


def test_device_scan_in_plan_and_kill_switch(sess, tmp_path):
    p, _ = _write(tmp_path)
    df = sess.read_parquet(p)
    plan = sess._physical(df.logical, True)
    assert "TpuParquetScanExec" in plan.tree_string(), plan.tree_string()
    off = TpuSession({
        "spark.rapids.tpu.shuffle.mode": "host",
        "spark.rapids.tpu.parquet.deviceDecode.enabled": False,
    })
    plan2 = off._physical(off.read_parquet(p).logical, True)
    assert "TpuParquetScanExec" not in plan2.tree_string()
    assert_tables_equal(off.read_parquet(p).collect(device=True),
                        off.read_parquet(p).collect(device=False),
                        ignore_order=False)


def test_pushed_filter_keeps_host_reader(sess, tmp_path):
    """Row-group statistics pruning lives in the host reader; a pushed
    filter therefore keeps the scan there (and stays correct)."""
    p, _ = _write(tmp_path, with_strings=False, nulls=False)
    df = sess.read_parquet(p)
    q = df.filter(col("i64") > 0)
    plan = sess._physical(q.logical, True)
    text = plan.tree_string()
    assert "TpuParquetScanExec" not in text, text
    assert_tpu_cpu_equal(q)


def test_device_scan_feeds_aggregate(sess, tmp_path):
    p, t = _write(tmp_path)
    df = sess.read_parquet(p)
    q = df.group_by("lowcard").agg(f_sum(col("f64")).alias("sf"))
    out = assert_tpu_cpu_equal(q, rel_tol=1e-9)
    pdf = t.to_pandas()
    exp = pdf.groupby("lowcard", dropna=False).f64.sum()
    assert out.num_rows == len(exp)


def _find_scan(plan):
    from spark_rapids_tpu.exec.scan import TpuParquetScanExec

    def find(n):
        if isinstance(n, TpuParquetScanExec):
            return n
        for c in n.children:
            r = find(c)
            if r is not None:
                return r
        return None
    return find(plan)


def test_string_columns_decode_on_device(sess, tmp_path):
    """BYTE_ARRAY columns decode on device too (round-2 missing #1;
    reference: GpuParquetScanBase.scala:995,1194) — every column of the
    scan lands in the device-decoded metric, none ride the fallback."""
    p, t = _write(tmp_path)
    df = sess.read_parquet(p)
    plan = sess._physical(df.logical, True)
    scan = _find_scan(plan)
    assert scan is not None
    batches = list(scan.execute_columnar(0))
    assert batches
    snap = scan.metrics.snapshot()
    # ALL 9 columns (incl. the string one) decode on device per row group
    assert snap.get("deviceDecodedColumns", 0) == 9 * len(batches)
    got = pa.concat_tables([b.to_host().to_arrow() for b in batches])
    assert got.column("s").to_pylist() == \
        t.column("s").to_pylist()[:got.num_rows]


def test_column_pruning_through_device_scan(sess, tmp_path):
    p, t = _write(tmp_path)
    df = sess.read_parquet(p).select("i64", "f64")
    dev = df.collect(device=True)
    assert dev.column_names == ["i64", "f64"]
    assert_tables_equal(dev, df.collect(device=False), ignore_order=False)


def test_mixed_width_dictionary_pages(sess, tmp_path):
    """A growing dictionary makes successive pages bit-pack at DIFFERENT
    widths; the run table records width per run (a single chunk-wide width
    silently corrupted 60%+ of values)."""
    rng = np.random.default_rng(11)
    n = 200_000
    # values appear progressively so the dictionary (and index width) grows
    vals = np.minimum(rng.integers(0, 200, n).cumsum() % 120,
                      np.arange(n) // 500)
    t = pa.table({"v": pa.array(vals, type=pa.int64())})
    p = str(tmp_path / "growdict.parquet")
    pq.write_table(t, p, row_group_size=n, data_page_size=8 * 1024,
                   compression="snappy")
    df = sess.read_parquet(p)
    plan = sess._physical(df.logical, True)
    assert "TpuParquetScanExec" in plan.tree_string()
    dev = df.collect(device=True)
    assert dev.column("v").to_pylist() == t.column("v").to_pylist()


def test_unsupported_codec_falls_back_to_host(sess, tmp_path):
    """Hadoop-framed LZ4 is unreadable by pa.decompress; the device decoder
    must fall back per column, never crash (host pyarrow reads it fine)."""
    t = pa.table({"a": pa.array(np.arange(5000, dtype=np.int64)),
                  "b": pa.array(np.random.default_rng(1).normal(size=5000))})
    p = str(tmp_path / "lz4.parquet")
    pq.write_table(t, p, compression="lz4")
    df = sess.read_parquet(p)
    dev = df.collect(device=True)
    cpu = df.collect(device=False)
    assert_tables_equal(dev, cpu, ignore_order=False)
    assert_tables_equal(dev, t, ignore_order=False)


def test_empty_and_single_row_groups(sess, tmp_path):
    t = pa.table({"a": pa.array([], type=pa.int64()),
                  "b": pa.array([], type=pa.float64())})
    p = str(tmp_path / "empty.parquet")
    pq.write_table(t, p)
    df = sess.read_parquet(p)
    assert df.collect(device=True).num_rows == 0
    t2 = pa.table({"a": pa.array([42], type=pa.int64())})
    p2 = str(tmp_path / "one.parquet")
    pq.write_table(t2, p2)
    out = sess.read_parquet(p2).collect(device=True)
    assert out.column("a").to_pylist() == [42]


@pytest.mark.parametrize("label,kw", [
    ("plain-v1", dict(use_dictionary=False)),
    ("mixed-v1", dict(use_dictionary=True,
                      dictionary_pagesize_limit=4096, data_page_size=2048)),
    ("dict-v2", dict(data_page_version="2.0")),
    ("plain-v2", dict(use_dictionary=False, data_page_version="2.0")),
    ("mixed-v2", dict(use_dictionary=True, dictionary_pagesize_limit=4096,
                      data_page_size=2048, data_page_version="2.0")),
])
def test_string_and_v2_page_matrix(sess, tmp_path, label, kw):
    """Strings + numerics across PLAIN / dictionary-overflow-mixed chunks
    and data-page v1/v2 — all decode on DEVICE, bit-identical to host
    (reference: GpuParquetScanBase.scala:995 handles the same page matrix)."""
    import io as _io
    from spark_rapids_tpu.io.parquet_device import decode_row_group
    rng = np.random.default_rng(5)
    n = 4000
    raw_s = ["s" + str(rng.integers(0, 10**9)) * rng.integers(1, 4)
             for _ in range(n)]
    mask = rng.random(n) < 0.1
    t = pa.table({
        "s": pa.array(raw_s, type=pa.string(), mask=mask),
        "i": pa.array(rng.integers(-2**40, 2**40, n), type=pa.int64()),
        "f": pa.array(rng.normal(size=n)),
    })
    buf = _io.BytesIO()
    pq.write_table(t, buf, row_group_size=n, compression="snappy", **kw)
    raw = buf.getvalue()
    pf = pq.ParquetFile(_io.BytesIO(raw))
    dt_, ndev = decode_row_group(raw, pf.metadata, 0, pf.schema_arrow,
                                 ["s", "i", "f"], 64)
    assert ndev == 3, f"{label}: only {ndev}/3 columns decoded on device"
    got = dt_.to_host().to_arrow()
    host = pf.read_row_group(0)
    for c in ("s", "i", "f"):
        assert got.column(c).to_pylist() == host.column(c).to_pylist(), \
            f"{label}: column {c} diverged"


def test_tpch_lineitem_orders_full_device_decode(sess, tmp_path):
    """The round-2 'done' criterion: every column of TPC-H lineitem and
    orders (strings included) decodes on device, differential vs host."""
    import io as _io
    from spark_rapids_tpu.io.parquet_device import decode_row_group
    from spark_rapids_tpu.tools import tpch
    tables = tpch.gen_all(0.01)
    for tname in ("lineitem", "orders"):
        t = tables[tname]
        buf = _io.BytesIO()
        pq.write_table(t, buf, row_group_size=t.num_rows,
                       compression="snappy")
        raw = buf.getvalue()
        pf = pq.ParquetFile(_io.BytesIO(raw))
        names = list(t.column_names)
        dt_, ndev = decode_row_group(raw, pf.metadata, 0, pf.schema_arrow,
                                     names, 64)
        assert ndev == len(names), \
            f"{tname}: {ndev}/{len(names)} columns on device"
        got = dt_.to_host().to_arrow()
        host = pf.read_row_group(0)
        for c in names:
            assert got.column(c).to_pylist() == host.column(c).to_pylist(), \
                f"{tname}.{c} diverged"


def test_per_type_device_decode_gates(sess, tmp_path):
    """Per-type kill switches (reference: per-type read enables,
    RapidsConf.scala:877-917): strings/booleans can be forced back to the
    host column decode independently."""
    from spark_rapids_tpu.conf import RapidsConf
    import io as _io
    from spark_rapids_tpu.io.parquet_device import decode_row_group
    t = pa.table({"s": pa.array(["a", "bb", "ccc"] * 10),
                  "b": pa.array([True, False, True] * 10),
                  "i": pa.array(np.arange(30, dtype=np.int64))})
    buf = _io.BytesIO()
    pq.write_table(t, buf, compression="none")
    raw = buf.getvalue()
    pf = pq.ParquetFile(_io.BytesIO(raw))
    base = RapidsConf()
    dt_, nd = decode_row_group(raw, pf.metadata, 0, pf.schema_arrow,
                               ["s", "b", "i"], 8, conf=base)
    assert nd == 3
    off = RapidsConf({
        "spark.rapids.tpu.parquet.deviceDecode.strings.enabled": False,
        "spark.rapids.tpu.parquet.deviceDecode.booleans.enabled": False})
    dt2, nd2 = decode_row_group(raw, pf.metadata, 0, pf.schema_arrow,
                                ["s", "b", "i"], 8, conf=off)
    assert nd2 == 1  # only the int column stayed on device
    assert dt2.to_host().to_arrow().column("s").to_pylist() == \
        t.column("s").to_pylist()


# ---------------------------------------------------------------------------
# The decode programs expand run tables by a scatter of run-start deltas and
# one prefix sum, and unpack bit fields densely: every case below is checked
# bit for bit, the streams against a plain numpy expander of the SAME run
# table and against the values that were encoded, the files against the host
# reader.
# ---------------------------------------------------------------------------
def _varint(v):
    out = bytearray()
    while True:
        if v < 0x80:
            out.append(v)
            return bytes(out)
        out.append((v & 0x7F) | 0x80)
        v >>= 7


def _hybrid(parts, width):
    """Encode one RLE/bit-packed hybrid stream (parquet format spec):
    ``("rle", value, count)`` or ``("bp", values)``, a short last group of
    a bit-packed run zero-padded to 8 values."""
    out = bytearray()
    for part in parts:
        if part[0] == "rle":
            _, value, count = part
            out += _varint(count << 1)
            out += int(value).to_bytes((width + 7) // 8, "little")
        else:
            vals = np.asarray(part[1], np.int64)
            vals = np.pad(vals, (0, -len(vals) % 8))
            out += _varint((len(vals) // 8) << 1 | 1)
            bits = ((vals[:, None] >> np.arange(width)) & 1).astype(np.uint8)
            out += np.packbits(bits.reshape(-1), bitorder="little").tobytes()
    return bytes(out)


def _pages_to_run_table(pages):
    """pages: [(width, parts)] -> (_RunTable, the values encoded)."""
    from spark_rapids_tpu.io.parquet_device import _RunTable
    rt = _RunTable()
    truth = []
    for width, parts in pages:
        vals = np.concatenate(
            [np.full(p[2], p[1], np.int64) if p[0] == "rle"
             else np.asarray(p[1], np.int64) for p in parts])
        buf = _hybrid(parts, width)
        rt.parse_hybrid(buf, 0, len(buf), width, len(vals))
        truth.append(vals)
    return rt, np.concatenate(truth)


def _expand_numpy(rt):
    """The plain expander: run by run, bit by bit, from the run table."""
    out = np.zeros(rt.total, np.int64)
    for s, c, w, v, fb in zip(rt.out_start, rt.count, rt.width,
                              rt.rle_value, rt.field_base):
        if w == 0:
            out[s:s + c] = v
            continue
        bits = np.unpackbits(np.frombuffer(bytes(rt.packed[w]), np.uint8),
                             bitorder="little")
        fields = bits[fb * w:(fb + c) * w].reshape(c, w).astype(np.int64)
        out[s:s + c] = (fields << np.arange(w)).sum(axis=1)
    return out


def _expand_device(rt):
    import jax
    from spark_rapids_tpu.io import parquet_device as pd
    cap = max(8, pd._pow2(rt.total))
    widths = rt.widths()
    got = jax.jit(lambda *a: pd._expand_runs(*a, widths, cap))(
        *rt.device_inputs(cap))
    assert str(got.dtype) == "int32"
    return np.asarray(got)[:rt.total].astype(np.int64)


def _width_pages(width, seed=3, pages=5):
    """Pages of one width, each a bit-packed run, an RLE run, and a second
    bit-packed run whose last group is short (only a page's last run may
    be: a reader cannot tell padding from values anywhere else)."""
    rng = np.random.default_rng(seed + width)
    hi = 1 << width
    out = []
    for p in range(pages):
        out.append((width, [
            ("bp", rng.integers(0, hi, 8 * (3 + p))),
            ("rle", int(rng.integers(0, hi)), 9 + 7 * p),
            ("bp", rng.integers(0, hi, 11 + p))]))
    return out


def _stream_cases():
    rng = np.random.default_rng(17)
    cases = {
        "rle-only": [(3, [("rle", 3, 1000), ("rle", 0, 17)]),
                     (3, [("rle", 5, 9)])],
        "bit-packed-only": [(6, [("bp", rng.integers(0, 64, 4096))])],
        "interleaved": [
            (2, [("bp", rng.integers(0, 4, 8 * (1 + p % 3))),
                 ("rle", p % 4, 8 + p),
                 ("bp", rng.integers(0, 4, 13 + p))]) for p in range(20)],
        "width-0": [(0, [("rle", 0, 777)])],
        "pages-grow-15-to-18": [
            (w, [("rle", (1 << w) - 1, 10),
                 ("bp", rng.integers(0, 1 << w, 1000 + w))])
            for w in (15, 16, 17, 18)],
        "short-last-group-every-page": [
            (6, [("bp", rng.integers(0, 64, 13))]) for _ in range(300)],
        "n-not-multiple-of-8": [(12, [("bp", rng.integers(0, 4096, 1003))])],
        "one-row-bit-packed": [(6, [("bp", [41])])],
        "one-row-rle": [(6, [("rle", 41, 1)])],
    }
    for w in (1, 2, 6, 12, 24):
        cases[f"width-{w}"] = _width_pages(w)
    return cases


def _file_cases():
    def table(null_share, n=5000, seed=23):
        rng = np.random.default_rng(seed)
        mask = rng.random(n) < null_share if 0 < null_share < 1 \
            else np.full(n, bool(null_share))
        return pa.table({
            "lowcard": pa.array(rng.integers(0, 40, n), type=pa.int64(),
                                mask=mask),
            "f": pa.array(np.round(rng.normal(size=n), 1), mask=mask),
            "i": pa.array(rng.integers(-2**40, 2**40, n), type=pa.int64(),
                          mask=mask),
            "d": pa.array(rng.integers(0, 2500, n).astype(np.int32),
                          mask=mask).cast(pa.date32()),
            "b": pa.array(rng.integers(0, 2, n).astype(bool), mask=mask),
            "s": pa.array([f"s{i % 7}" * (1 + i % 3) for i in range(n)],
                          type=pa.string(), mask=mask),
        })
    overflow = dict(dictionary_pagesize_limit=4096, data_page_size=2048)
    return {
        "nulls-0pct": (table(0.0), {}),
        "nulls-0.6pct": (table(0.006), {}),
        "nulls-50pct": (table(0.5), {}),
        "nulls-100pct": (table(1.0), {}),
        "dict-to-plain-overflow": (table(0.0), overflow),
        "dict-to-plain-overflow-nulls": (table(0.1), overflow),
        # v2 writes BOOLEAN values RLE-encoded: host decode, by column
        "data-page-v2": (table(0.0).drop_columns(["b"]),
                         dict(data_page_version="2.0")),
        "data-page-v2-nulls": (table(0.2).drop_columns(["b"]),
                               dict(data_page_version="2.0")),
        "strings-large-dictionary": (pa.table({"s": pa.array(
            [f"key-{i % 3000:05d}" for i in range(20000)])}), {}),
        "many-small-pages": (table(0.006, n=20000),
                             dict(data_page_size=512)),
    }


_STREAM_CASES = _stream_cases()
_FILE_CASES = _file_cases()


@pytest.mark.parametrize(
    "case", [f"stream:{k}" for k in _STREAM_CASES]
    + [f"file:{k}" for k in _FILE_CASES])
def test_decode_bit_identical(case):
    import io as _io
    from spark_rapids_tpu.io import parquet_device as pd
    kind, name = case.split(":", 1)
    if kind == "stream":
        rt, truth = _pages_to_run_table(_STREAM_CASES[name])
        assert np.array_equal(_expand_numpy(rt), truth)
        assert np.array_equal(_expand_device(rt), truth)
        return
    t, kw = _FILE_CASES[name]
    buf = _io.BytesIO()
    pq.write_table(t, buf, row_group_size=t.num_rows, compression="snappy",
                   **kw)
    raw = buf.getvalue()
    pf = pq.ParquetFile(_io.BytesIO(raw))
    names = list(t.column_names)
    rg = pf.metadata.row_group(0)
    for ci, cname in enumerate(names):
        ch = pd._parse_chunk(raw, rg.column(ci),
                             pf.schema_arrow.field(cname).nullable)
        col = t.column(cname)
        assert ch.n_defined == len(col) - col.null_count
        for rt in (ch.defs, ch.idx):
            if rt.total:
                assert np.array_equal(_expand_device(rt), _expand_numpy(rt))
    dt_, ndev = pd.decode_row_group(raw, pf.metadata, 0, pf.schema_arrow,
                                    names, 64)
    assert ndev == len(names), f"{ndev}/{len(names)} columns on device"
    got = dt_.to_host().to_arrow()
    host = pf.read_row_group(0)
    for c in names:
        assert got.column(c).to_pylist() == host.column(c).to_pylist(), c


def _lowered_decode(decoder, segments, def_widths, idx_widths,
                    cap=1 << 20):
    """StableHLO text (with scopes) of one decode variant at the shapes
    the benchmark's SF 1 chunks have: 2^20 rows, 53 definition-level runs
    (bucket 256), 265-3,593 index runs (bucket 4,096)."""
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.io import parquet_device as pd
    S = jax.ShapeDtypeStruct

    def stream(widths, rb):
        if widths is None:
            return ()
        return (S((rb,), jnp.int32),
                S((3 if len(widths) > 1 else 2, rb), jnp.int32),
                S((sum(widths), cap // 8 + rb), jnp.uint8))
    defs = stream(def_widths, 256)
    idx = stream(idx_widths if segments != "plain" else None, 4096)
    n = S((), jnp.int32)
    has_dict, has_plain = segments != "plain", segments != "dict"
    if decoder == "fixed":
        fn = pd._fixed_kernel_builder("<f8", cap, segments, def_widths,
                                      idx_widths)()
        args = (defs, idx, S((4096,), jnp.float64) if has_dict else (),
                S((cap,), jnp.float64) if has_plain else (), n, n)
    else:
        fn = pd._bytes_kernel_builder(cap, segments, def_widths,
                                      idx_widths)()
        args = (defs, idx,
                S((4, 8), jnp.uint8) if has_dict else (),
                S((4,), jnp.int32) if has_dict else (),
                S((cap, 8), jnp.uint8) if has_plain else (),
                S((cap,), jnp.int32) if has_plain else (), n, n)
    return jax.jit(fn).lower(*args).as_text(debug_info=True)


# The gathers a decode program may hold, settled in PR 27. All-defined
# chunk: the short-last-group shift of the index fields + the dictionary
# gather (fixed: 2; strings gather rows and lengths: 3); a PLAIN chunk
# none. Chunk with nulls: + the definition-level fields and the spread of
# the dense stream over the rows (fixed: 4); strings spread the indices,
# then gather rows and lengths from the dictionary and, where the chunk
# overflowed its dictionary, from the PLAIN matrix as well (5 / 7).
@pytest.mark.parametrize("decoder,segments,def_widths,idx_widths,gathers", [
    ("fixed", "dict", None, (6,), 2),       # l_quantity
    ("fixed", "dict", None, (4,), 2),       # l_discount, l_tax
    ("fixed", "dict", None, (12,), 2),      # l_shipdate
    ("fixed", "mixed", None, (15, 16, 17, 18), 2),   # l_extendedprice
    ("fixed", "plain", None, (), 0),
    ("bytes", "dict", None, (2,), 3),       # l_returnflag
    ("bytes", "dict", None, (1,), 3),       # l_linestatus
    ("bytes", "mixed", None, (10, 11), 3),
    ("fixed", "dict", (), (6,), 3),         # nulls in long RLE runs only
    ("fixed", "dict", (1,), (6,), 4),
    ("fixed", "mixed", (1,), (15, 16, 17, 18), 4),
    ("fixed", "plain", (1,), (), 2),
    ("bytes", "dict", (1,), (2,), 5),
    ("bytes", "mixed", (1,), (10, 11), 7),
])
def test_decode_programs_hold_no_loop_and_few_gathers(
        decoder, segments, def_widths, idx_widths, gathers):
    import re
    text = _lowered_decode(decoder, segments, def_widths, idx_widths)
    assert "stablehlo.while" not in text
    assert "stablehlo.sort" not in text
    assert len(re.findall(r'= "?stablehlo\.gather', text)) <= gathers
    assert not re.search(r"tensor<(\d+x)*\d{3,}(x\d+)*xi64>", text), \
        "an int64 vector in the decode"
    if segments != "plain":
        assert "pq_run_prefix_sum" in text and "pq_bit_unpack" in text
    # the all-defined variant traces nothing of the definition levels
    assert ("pq_def_levels" in text) == (def_widths is not None)


@pytest.mark.parametrize("null_share,span", [(0.0, "decode.dense"),
                                             (0.1, "decode.general")])
def test_decode_path_is_booked_by_span(sess, tmp_path, null_share, span):
    """A chunk without nulls takes the all-defined program and books
    ``decode.dense``; one with nulls books ``decode.general`` — the phase
    totals count chunks by path (what the benchmark's
    ``decode_general_chunks_per_query`` reads)."""
    from spark_rapids_tpu.utils.tracing import get_tracer
    n = 3000
    rng = np.random.default_rng(2)
    mask = rng.random(n) < null_share
    t = pa.table({"k": pa.array(rng.integers(0, 9, n), type=pa.int64(),
                                mask=mask),
                  "s": pa.array([f"v{i % 5}" for i in range(n)], mask=mask)})
    p = str(tmp_path / "t.parquet")
    pq.write_table(t, p, row_group_size=1000)
    out = sess.read_parquet(p).collect(device=True)
    assert out.column("k").to_pylist() == t.column("k").to_pylist()
    phases = get_tracer().recent_queries(1)[-1]["phases"]
    other = ({"decode.dense", "decode.general"} - {span}).pop()
    assert phases[span]["calls"] == 2 * 3      # columns x row groups
    assert other not in phases
