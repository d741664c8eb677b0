"""Device-side parquet decode.

Reference: the GPU plugin's biggest IO win is decoding parquet ON the
accelerator — raw column chunks go to the device and cuDF kernels expand
them (GpuParquetScanBase.scala:995,1194). The TPU-native shape of that
design, mapped onto XLA's static-shape world:

- HOST does the byte plumbing: file reads, page-header parsing
  (io/parquet_thrift.py), page decompression, and a one-pass scan of the
  RLE/bit-packed hybrid streams into *run tables* (a few entries per run,
  NOT per value — the classic GPU decoder split).
- DEVICE does the per-value work, one fused jit per column chunk:
  run-table expansion (searchsorted over run starts), bit-field extraction
  of dictionary indices from the packed blob, dictionary gather, and
  null-scatter of the dense non-null values into row slots via a validity
  cumsum.

Supported (everything else falls back per COLUMN to pyarrow + upload):
flat columns (no repetition), physical BOOLEAN/INT32/INT64/FLOAT/DOUBLE/
BYTE_ARRAY (strings/binary via the bucketed byte-matrix layout), data-page
v1 AND v2, PLAIN or RLE_DICTIONARY values including chunks whose pages
switch dictionary->plain mid-chunk (the pyarrow dictionary-overflow
fallback), any pyarrow-decompressible codec. Output is bit-identical to
the host path (DeviceTable.from_host of the pyarrow read).
"""
from __future__ import annotations

from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np

from ..columnar import dtypes as dt
from ..columnar.host import _arrow_to_dtype
from ..conf import register_conf
from .parquet_thrift import Encoding, PageType, read_page_header

__all__ = ["PARQUET_DEVICE_DECODE", "chunk_supported", "decode_row_group",
           "UnsupportedChunk"]

PARQUET_DEVICE_DECODE = register_conf(
    "spark.rapids.tpu.parquet.deviceDecode.enabled",
    "Decode supported parquet columns on the device (run-table expansion + "
    "dictionary gather kernels; reference: GpuParquetScanBase device "
    "decode). Unsupported columns fall back to host decode per column.",
    True)

# per-type device-decode gates (reference: the per-type read enables of
# RapidsConf.scala:877-917 — risky parses get their own kill switch)
PARQUET_DEVICE_DECODE_STRINGS = register_conf(
    "spark.rapids.tpu.parquet.deviceDecode.strings.enabled",
    "Decode BYTE_ARRAY (string/binary) parquet columns on device; false "
    "keeps strings on the per-column host decode.", True)

PARQUET_DEVICE_DECODE_BOOLEANS = register_conf(
    "spark.rapids.tpu.parquet.deviceDecode.booleans.enabled",
    "Decode BOOLEAN parquet columns on device; false keeps booleans on "
    "the per-column host decode.", True)

_PHYS_OK = {"BOOLEAN", "INT32", "INT64", "FLOAT", "DOUBLE", "BYTE_ARRAY"}
_ENC_OK = {"PLAIN", "RLE", "RLE_DICTIONARY", "PLAIN_DICTIONARY",
           "BIT_PACKED"}


class UnsupportedChunk(Exception):
    """Column chunk outside the device decoder's subset."""


def chunk_supported(col_meta, arrow_field, conf=None) -> bool:
    """Static (metadata-only) eligibility of one column chunk."""
    import pyarrow as pa
    if col_meta.physical_type not in _PHYS_OK:
        return False
    if conf is not None:
        if col_meta.physical_type == "BYTE_ARRAY" \
                and not conf.get(PARQUET_DEVICE_DECODE_STRINGS):
            return False
        if col_meta.physical_type == "BOOLEAN" \
                and not conf.get(PARQUET_DEVICE_DECODE_BOOLEANS):
            return False
    if any(e not in _ENC_OK for e in col_meta.encodings):
        return False
    t = arrow_field.type
    if pa.types.is_nested(t) or pa.types.is_dictionary(t):
        return False
    try:
        d = _arrow_to_dtype(t)
    except Exception:
        return False
    if isinstance(d, dt.DecimalType):
        return False
    if isinstance(d, (dt.StringType, dt.BinaryType)):
        return col_meta.physical_type == "BYTE_ARRAY"
    return col_meta.physical_type != "BYTE_ARRAY"


# ---------------------------------------------------------------------------
# Host side: pages -> merged run tables
# ---------------------------------------------------------------------------
def _decompress(buf: bytes, codec: str, uncompressed_size: int) -> bytes:
    if codec in ("UNCOMPRESSED", None):
        return buf
    import pyarrow as pa
    return pa.decompress(buf, decompressed_size=uncompressed_size,
                         codec=codec.lower()).to_pybytes()


class _RunTable:
    """Accumulated RLE/bit-packed runs across a chunk's pages."""

    def __init__(self):
        self.out_start: List[int] = []
        self.count: List[int] = []
        self.is_rle: List[bool] = []
        self.rle_value: List[int] = []
        self.bit_base: List[int] = []   # absolute first-bit into self.packed
        self.width: List[int] = []      # PER-RUN bit width (pages with a
        # growing dictionary are written at increasing widths!)
        self.packed = bytearray()
        self.total = 0

    def parse_hybrid(self, buf: bytes, pos: int, end: int, width: int,
                     max_count: int) -> None:
        """One RLE-hybrid stream (parquet format spec): header varint LSB
        selects bit-packed groups vs RLE run."""
        if width == 0:
            # zero-width stream: max_count zeros, no bytes
            self._push_rle(max_count, 0)
            return
        produced = 0
        vbytes = (width + 7) // 8
        while pos < end and produced < max_count:
            header = 0
            shift = 0
            while True:
                b = buf[pos]
                pos += 1
                header |= (b & 0x7F) << shift
                if not b & 0x80:
                    break
                shift += 7
            if header & 1:  # bit-packed groups
                groups = header >> 1
                nvals = min(groups * 8, max_count - produced)
                nbytes = groups * width  # groups*8 values * width/8 bits
                self.out_start.append(self.total)
                self.count.append(nvals)
                self.is_rle.append(False)
                self.rle_value.append(0)
                self.bit_base.append(len(self.packed) * 8)
                self.width.append(width)
                self.packed.extend(buf[pos:pos + nbytes])
                pos += nbytes
                self.total += nvals
                produced += nvals
            else:           # RLE run
                run = min(header >> 1, max_count - produced)
                v = int.from_bytes(buf[pos:pos + vbytes], "little")
                pos += vbytes
                self._push_rle(run, v)
                produced += run

    def _push_rle(self, run: int, v: int) -> None:
        if run <= 0:
            return
        self.out_start.append(self.total)
        self.count.append(run)
        self.is_rle.append(True)
        self.rle_value.append(v)
        self.bit_base.append(0)
        self.width.append(0)
        self.total += run

    def arrays(self) -> Tuple[np.ndarray, ...]:
        # pow2-pad entry count and packed blob so XLA sees a bounded shape
        # set across chunks (padding runs have out_start == total -> the
        # searchsorted expansion never selects them)
        n = _pow2(max(1, len(self.out_start)))
        pad = n - len(self.out_start)
        out_start = np.asarray(self.out_start + [self.total] * pad, np.int64)
        packed = np.frombuffer(bytes(self.packed) or b"\0", np.uint8)
        packed = np.pad(packed, (0, _pow2(len(packed)) - len(packed)))
        return (out_start,
                np.asarray(self.is_rle + [True] * pad, np.bool_),
                np.asarray(self.rle_value + [0] * pad, np.int64),
                np.asarray(self.bit_base + [0] * pad, np.int64),
                np.asarray(self.width + [0] * pad, np.int64),
                packed)


class _Chunk:
    """Parsed column chunk: run tables + dense plain values + dictionary.

    The dense non-null value stream of a chunk is [dictionary-encoded
    pages' values] ++ [plain pages' values] — parquet writers that
    overflow their dictionary (pyarrow's 1MB default) switch to PLAIN for
    the REST of the chunk, never back, so segment order is statically
    dict-then-plain."""

    def __init__(self):
        self.defs = _RunTable()      # definition levels (width 1)
        self.idx = _RunTable()       # dictionary indices (width per page)
        self.idx_width: int = 0
        self.plain_parts: List[bytes] = []
        self.dictionary: Optional[np.ndarray] = None
        # BYTE_ARRAY: dictionary entries + per-page plain streams, kept as
        # (starts, lengths, blob) triples until the matrix assembly
        self.ba_dict: Optional[Tuple[np.ndarray, np.ndarray, np.ndarray]] = None
        self.ba_plain: List[Tuple[np.ndarray, np.ndarray, np.ndarray]] = []
        self.num_rows = 0
        self.nullable = False
        self.bool_plain: List[Tuple[bytes, int]] = []  # packed bits, count
        self.uses_dict = False
        self.uses_plain = False


def _parse_byte_array_stream(buf: bytes, n: int
                             ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Walk a PLAIN BYTE_ARRAY stream (u32 length prefix per value) ->
    (starts, lengths, blob) without copying the value bytes. The walk is
    sequential; the native C helper does it at memory speed, with a
    Python loop as the compiler-less fallback."""
    from .. import native
    walked = native.ba_walk(buf, n)
    if walked is not None:
        starts, lens, pos = walked
        return starts, lens, np.frombuffer(buf, np.uint8, pos)
    import struct as _struct
    starts = np.empty(n, np.int64)
    lens = np.empty(n, np.int64)
    pos = 0
    unpack = _struct.unpack_from
    for i in range(n):
        (ln,) = unpack("<I", buf, pos)
        pos += 4
        starts[i] = pos
        lens[i] = ln
        pos += ln
    return starts, lens, np.frombuffer(buf, np.uint8, pos)


def _ba_matrix(parts: List[Tuple[np.ndarray, np.ndarray, np.ndarray]],
               width: int) -> Tuple[np.ndarray, np.ndarray]:
    """(starts, lens, blob) segments -> dense (n, width) matrix + lengths
    via one vectorized scatter (same trick as _encode_string_matrix)."""
    n = sum(len(p[1]) for p in parts)
    mat = np.zeros((max(n, 1), width), dtype=np.uint8)
    out_lens = np.zeros(max(n, 1), dtype=np.int32)
    row0 = 0
    for starts, lens, blob in parts:
        k = len(lens)
        total = int(lens.sum())
        if total:
            rows = row0 + np.repeat(np.arange(k, dtype=np.int64), lens)
            prefix = np.cumsum(lens) - lens
            cols = np.arange(total, dtype=np.int64) - np.repeat(prefix, lens)
            mat[rows, cols] = blob[np.repeat(starts, lens) + cols]
        out_lens[row0:row0 + k] = lens
        row0 += k
    return mat, out_lens


def _parse_chunk(raw: bytes, col_meta, nullable: bool) -> _Chunk:
    ch = _Chunk()
    ch.nullable = nullable
    phys = col_meta.physical_type
    codec = col_meta.compression
    off = col_meta.dictionary_page_offset
    if off is None:
        off = col_meta.data_page_offset
    end = off + col_meta.total_compressed_size
    pos = off
    while pos < end:
        hdr = read_page_header(raw, pos)
        data_start = pos + hdr.header_bytes
        page = raw[data_start:data_start + hdr.compressed_size]
        pos = data_start + hdr.compressed_size
        if hdr.page_type == PageType.DICTIONARY_PAGE:
            page = _decompress(page, codec, hdr.uncompressed_size)
            if phys == "BYTE_ARRAY":
                ch.ba_dict = _parse_byte_array_stream(page, hdr.num_values)
            else:
                ch.dictionary = _plain_values(page, phys, hdr.num_values)
            continue
        nvals = hdr.num_values
        if hdr.page_type == PageType.DATA_PAGE:
            page = _decompress(page, codec, hdr.uncompressed_size)
            p = 0
            # flat columns: no repetition levels; definition levels only
            # when the column is nullable (length-prefixed RLE, width 1)
            n_nonnull = nvals
            if nullable:
                if hdr.def_level_encoding != Encoding.RLE:
                    # legacy BIT_PACKED levels have no length prefix;
                    # parsing them as RLE would read garbage "plausibly"
                    raise UnsupportedChunk(
                        f"definition-level encoding {hdr.def_level_encoding}")
                (dl_len,) = np.frombuffer(page, np.uint32, 1, p)
                p += 4
                before = ch.defs.total
                ch.defs.parse_hybrid(page, p, p + int(dl_len), 1, nvals)
                if ch.defs.total - before < nvals:  # stream may omit tail
                    ch.defs._push_rle(nvals - (ch.defs.total - before), 1)
                p += int(dl_len)
                n_nonnull = _count_defined(ch.defs, before)
            else:
                ch.defs._push_rle(nvals, 1)
        elif hdr.page_type == PageType.DATA_PAGE_V2:
            # v2 layout: [rep levels][def levels] UNCOMPRESSED, then the
            # values section (compressed iff is_compressed); levels are
            # RLE with NO length prefix (lengths live in the header)
            if hdr.rep_levels_byte_length:
                raise UnsupportedChunk("v2 repetition levels on flat column")
            dl = hdr.def_levels_byte_length
            levels = page[:dl]
            vals = page[dl:]
            if hdr.v2_is_compressed:
                vals = _decompress(vals, codec,
                                   hdr.uncompressed_size - dl)
            n_nonnull = nvals - hdr.num_nulls
            before = ch.defs.total
            if dl:
                ch.defs.parse_hybrid(levels, 0, dl, 1, nvals)
            if ch.defs.total - before < nvals:
                ch.defs._push_rle(nvals - (ch.defs.total - before), 1)
            page = vals
            p = 0
        else:
            raise UnsupportedChunk(f"page type {hdr.page_type}")
        if hdr.encoding in (Encoding.RLE_DICTIONARY,
                            Encoding.PLAIN_DICTIONARY):
            if ch.uses_plain:
                # dense-stream order would break (plain segment sits last)
                raise UnsupportedChunk("dictionary page after plain page")
            width = page[p]
            p += 1
            if width > 24:
                raise UnsupportedChunk(f"dict index width {width}")
            ch.idx_width = max(ch.idx_width, width)
            ch.idx.parse_hybrid(page, p, len(page), width, n_nonnull)
            ch.uses_dict = True
        elif hdr.encoding == Encoding.PLAIN:
            if phys == "BOOLEAN":
                ch.bool_plain.append((page[p:], n_nonnull))
            elif phys == "BYTE_ARRAY":
                ch.ba_plain.append(
                    _parse_byte_array_stream(page[p:], n_nonnull))
            else:
                ch.plain_parts.append(page[p:])
            ch.uses_plain = True
        else:
            raise UnsupportedChunk(f"encoding {hdr.encoding}")
        ch.num_rows += nvals
    if ch.uses_dict and ch.bool_plain:
        raise UnsupportedChunk("mixed dict+plain boolean pages")
    return ch


def _count_defined(rt: _RunTable, from_entry_total: int) -> int:
    """Non-null count contributed by def-level entries after a checkpoint —
    needed because dictionary index streams hold only non-null values."""
    # walk entries added since the checkpoint
    total = 0
    acc = 0
    for i in range(len(rt.out_start)):
        if rt.out_start[i] < from_entry_total:
            continue
        if rt.is_rle[i]:
            total += rt.count[i] * (1 if rt.rle_value[i] else 0)
        else:
            # bit-packed def levels at width 1: count set bits in the run
            base = rt.bit_base[i] // 8
            nbits = rt.count[i]
            blob = bytes(rt.packed[base:base + (nbits + 7) // 8])
            bits = np.unpackbits(np.frombuffer(blob, np.uint8),
                                 bitorder="little")[:nbits]
            total += int(bits.sum())
        acc += rt.count[i]
    return total


_NP_BY_PHYS = {"INT32": np.int32, "INT64": np.int64,
               "FLOAT": np.float32, "DOUBLE": np.float64}


def _plain_values(buf: bytes, phys: str, n: int) -> np.ndarray:
    if phys == "BOOLEAN":
        bits = np.unpackbits(np.frombuffer(buf, np.uint8, (n + 7) // 8),
                             bitorder="little")[:n]
        return bits.astype(np.bool_)
    npdt = _NP_BY_PHYS[phys]
    return np.frombuffer(buf, npdt, n)


# ---------------------------------------------------------------------------
# Device side: one fused kernel per chunk
# ---------------------------------------------------------------------------
def _pow2(n: int) -> int:
    c = 1
    while c < n:
        c *= 2
    return c


def _expand_hybrid_device(out_start, is_rle, rle_value, bit_base, widths,
                          packed, iota):
    """values[i] for each output position in ``iota``: expand the run table
    on device (searchsorted for run id + LSB-first bit-field extraction for
    bit-packed runs). ``widths`` is PER RUN — successive pages of one chunk
    may bit-pack at different widths as the dictionary grows."""
    import jax
    import jax.numpy as jnp
    i = iota.astype(jnp.int64)
    with jax.named_scope("pq_run_searchsorted"):
        run = jnp.clip(jnp.searchsorted(out_start, i, side="right") - 1,
                       0, out_start.shape[0] - 1)
    within = i - out_start[run]
    w = widths[run]
    bit = bit_base[run] + within * w
    byte0 = bit >> 3
    shift = (bit & 7).astype(jnp.uint32)
    nb = packed.shape[0]
    g = lambda k: packed[jnp.clip(byte0 + k, 0, nb - 1)].astype(jnp.uint32)
    dword = g(0) | (g(1) << 8) | (g(2) << 16) | (g(3) << 24)
    # width <= 24 enforced at parse time, so 4 gathered bytes always cover
    mask = (jnp.uint32(1) << w.astype(jnp.uint32)) - jnp.uint32(1)
    bp_val = (dword >> shift) & mask
    return jnp.where(is_rle[run], rle_value[run].astype(jnp.int64),
                     bp_val.astype(jnp.int64))


def _mixed_kernel_builder(npdt_str: str):
    """Fixed-width decode: dense stream = dict segment ++ plain segment.

    Row r's dense position ``pos[r]`` reads from the dictionary gather
    while pos < n_dict (the count of dictionary-encoded non-null values)
    and from the host-parsed plain array after — one kernel covers
    dict-only (plain is a 1-slot dummy), plain-only (n_dict = 0), and the
    pyarrow dictionary-overflow mixed chunk."""
    def fn(v_start, v_rle, v_val, v_bit, v_width, v_packed,
           d_start, d_rle, d_val, d_bit, d_width, d_packed, dvals,
           plain, n_dict, n, iota_cap, iota_nv):
        import jax
        import jax.numpy as jnp
        with jax.named_scope("pq_def_levels"):
            validity = _expand_hybrid_device(
                v_start, v_rle, v_val, v_bit, v_width, v_packed,
                iota_cap) > 0
            validity = jnp.logical_and(validity, iota_cap < n)
            pos = (jnp.cumsum(validity.astype(jnp.int32)) - 1) \
                .astype(jnp.int64)
        with jax.named_scope("pq_dict_indices"):
            idx = _expand_hybrid_device(d_start, d_rle, d_val, d_bit,
                                        d_width, d_packed, iota_nv)
        with jax.named_scope("pq_value_gather"):
            dense_dict = dvals[jnp.clip(idx, 0, dvals.shape[0] - 1)]
            from_dict = pos < n_dict
            v_dict = dense_dict[jnp.clip(pos, 0, dense_dict.shape[0] - 1)]
            v_plain = plain[jnp.clip(pos - n_dict, 0, plain.shape[0] - 1)]
            vals = jnp.where(from_dict, v_dict, v_plain)
            vals = jnp.where(validity, vals, jnp.zeros((), vals.dtype))
        return vals.astype(jnp.dtype(npdt_str)), validity
    return lambda: fn


def _ba_kernel_builder():
    """BYTE_ARRAY decode into the bucketed (rows, width) byte-matrix +
    lengths layout — dictionary rows gather as whole matrix rows (an
    MXU-friendly 2D gather), plain rows come from the host-assembled
    matrix, segment choice as in _mixed_kernel_builder."""
    def fn(v_start, v_rle, v_val, v_bit, v_width, v_packed,
           d_start, d_rle, d_val, d_bit, d_width, d_packed,
           dict_mat, dict_lens, plain_mat, plain_lens,
           n_dict, n, iota_cap, iota_nv):
        import jax
        import jax.numpy as jnp
        with jax.named_scope("pq_def_levels"):
            validity = _expand_hybrid_device(
                v_start, v_rle, v_val, v_bit, v_width, v_packed,
                iota_cap) > 0
            validity = jnp.logical_and(validity, iota_cap < n)
            pos = (jnp.cumsum(validity.astype(jnp.int32)) - 1) \
                .astype(jnp.int64)
        with jax.named_scope("pq_dict_indices"):
            idx = _expand_hybrid_device(d_start, d_rle, d_val, d_bit,
                                        d_width, d_packed, iota_nv)
        with jax.named_scope("pq_value_gather"):
            from_dict = pos < n_dict
            didx = idx[jnp.clip(pos, 0, idx.shape[0] - 1)]
            row_dict = dict_mat[jnp.clip(didx, 0, dict_mat.shape[0] - 1)]
            len_dict = dict_lens[jnp.clip(didx, 0, dict_lens.shape[0] - 1)]
            ppos = jnp.clip(pos - n_dict, 0, plain_mat.shape[0] - 1)
            row_plain = plain_mat[ppos]
            len_plain = plain_lens[ppos]
            data = jnp.where(from_dict[:, None], row_dict, row_plain)
            lengths = jnp.where(from_dict, len_dict, len_plain)
            ok = validity[:, None]
            data = jnp.where(ok, data, jnp.zeros((), jnp.uint8))
            lengths = jnp.where(validity, lengths, 0).astype(jnp.int32)
        return data, lengths, validity
    return lambda: fn


def _empty_run_tables() -> Tuple[np.ndarray, ...]:
    return _RunTable().arrays()


def _run_table_inputs(ch: _Chunk, cap: int):
    """The run tables and iotas both decode kernels start with, and the
    count of dictionary-encoded values."""
    v_tables = ch.defs.arrays()
    iota_cap = np.arange(cap, dtype=np.int64)
    d_tables = ch.idx.arrays() if ch.uses_dict else _empty_run_tables()
    n_dict = ch.idx.total if ch.uses_dict else 0
    iota_nv = np.arange(_pow2(max(1, n_dict)), dtype=np.int64)
    return v_tables, d_tables, n_dict, iota_cap, iota_nv


def _pad_rows_pow2(mat: np.ndarray, lens: np.ndarray):
    pad_to = _pow2(mat.shape[0])
    return (np.pad(mat, ((0, pad_to - mat.shape[0]), (0, 0))),
            np.pad(lens, (0, pad_to - len(lens))).astype(np.int32))


def _bytes_inputs(ch: _Chunk, cap: int) -> tuple:
    """Host arrays the BYTE_ARRAY kernel takes, shapes pow2-bucketed."""
    from ..columnar.device import bucket_width
    v_tables, d_tables, n_dict, iota_cap, iota_nv = _run_table_inputs(ch, cap)
    max_len = 1
    if ch.ba_dict is not None and len(ch.ba_dict[1]):
        max_len = max(max_len, int(ch.ba_dict[1].max()))
    for _, lens, _b in ch.ba_plain:
        if len(lens):
            max_len = max(max_len, int(lens.max()))
    width = bucket_width(max_len)
    if ch.uses_dict:
        if ch.ba_dict is None:
            raise UnsupportedChunk("dict-encoded pages, no dict page")
        dm, dlens = _pad_rows_pow2(*_ba_matrix([ch.ba_dict], width))
    else:
        dm, dlens = np.zeros((1, width), np.uint8), np.zeros(1, np.int32)
    if ch.ba_plain:
        pm, plens = _pad_rows_pow2(*_ba_matrix(ch.ba_plain, width))
    else:
        pm, plens = np.zeros((1, width), np.uint8), np.zeros(1, np.int32)
    return (*v_tables, *d_tables, dm, dlens, pm, plens,
            np.int64(n_dict), np.int64(ch.num_rows), iota_cap, iota_nv)


def _fixed_inputs(ch: _Chunk, npdt, cap: int) -> tuple:
    """Host arrays the fixed-width kernel takes, shapes pow2-bucketed."""
    v_tables, d_tables, n_dict, iota_cap, iota_nv = _run_table_inputs(ch, cap)
    if ch.bool_plain and not ch.uses_dict:
        parts = [_plain_values(b, "BOOLEAN", c) for b, c in ch.bool_plain]
        plain = np.concatenate(parts) if parts else np.zeros(0, np.bool_)
    elif ch.plain_parts:
        blob = b"".join(ch.plain_parts)
        d_ = np.dtype(npdt)
        if d_.kind == "f":
            phys = "FLOAT" if d_.itemsize == 4 else "DOUBLE"
        else:  # ints + date32/timestamp storage types
            phys = "INT32" if d_.itemsize == 4 else "INT64"
        count = len(blob) // np.dtype(_NP_BY_PHYS[phys]).itemsize
        plain = _plain_values(blob, phys, count)
    else:
        plain = np.zeros(0, npdt)
    plain = np.asarray(plain, npdt)
    plain = np.pad(plain, (0, _pow2(max(1, len(plain))) - len(plain)))
    if ch.uses_dict:
        dict_vals = np.asarray(ch.dictionary, npdt)
    else:
        dict_vals = np.zeros(1, npdt)
    dv = np.pad(dict_vals,
                (0, _pow2(max(1, len(dict_vals))) - len(dict_vals)))
    return (*v_tables, *d_tables, dv, plain,
            np.int64(n_dict), np.int64(ch.num_rows), iota_cap, iota_nv)


def _decode_column_device(ch: _Chunk, out_dtype: dt.DataType, cap: int):
    """-> DeviceColumn with row capacity ``cap`` (device kernels; compiled
    callables shared via the global compile cache, shapes pow2-bucketed).
    Three phases, each a span: the host builds the kernel's inputs
    (``scan.parse``), uploads them (``h2d``), and dispatches the decode."""
    import jax

    from ..columnar.device import DeviceColumn
    from ..utils.compile_cache import cached_jit
    from ..utils.tracing import get_tracer

    tracer = get_tracer()
    is_bytes = isinstance(out_dtype, (dt.StringType, dt.BinaryType))
    npdt = None if is_bytes else out_dtype.np_dtype()
    with tracer.span("scan.parse", "scan", step="kernel_inputs"):
        args = _bytes_inputs(ch, cap) if is_bytes \
            else _fixed_inputs(ch, npdt, cap)
    with tracer.span("h2d", "upload",
                     bytes=sum(int(a.nbytes) for a in args)):
        args = jax.device_put(args)
    if is_bytes:
        fn = cached_jit("pq_ba", _ba_kernel_builder(), name="pq_decode_bytes")
        data, lengths, validity = fn(*args)
        return DeviceColumn(data, validity, out_dtype, lengths)
    npdt_str = np.dtype(npdt).str
    fn = cached_jit(f"pq_mix|{npdt_str}", _mixed_kernel_builder(npdt_str),
                    name="pq_decode_fixed")
    data, validity = fn(*args)
    return DeviceColumn(data, validity, out_dtype, None)


def decode_row_group(raw: bytes, pf_metadata, rg: int, arrow_schema,
                     columns: List[str], min_bucket: int, conf=None):
    """Decode one row group into a DeviceTable; per-column fallback to
    pyarrow host decode + upload for unsupported chunks. Returns
    (DeviceTable, n_device_decoded_columns)."""
    from ..columnar.device import DeviceTable, bucket_rows
    from ..utils.tracing import get_tracer
    rg_meta = pf_metadata.row_group(rg)
    n = rg_meta.num_rows
    cap = bucket_rows(max(n, 1), min_bucket)
    name_to_ci = {pf_metadata.schema.column(i).path: i
                  for i in range(pf_metadata.num_columns)}
    cols = {}
    fallback: List[str] = []
    n_device = 0
    for name in columns:
        ci = name_to_ci.get(name)
        field = arrow_schema.field(name)
        col_meta = rg_meta.column(ci) if ci is not None else None
        if col_meta is None or not chunk_supported(col_meta, field, conf):
            fallback.append(name)
            continue
        try:
            with get_tracer().span("scan.parse", "scan", step="pages"):
                ch = _parse_chunk(raw, col_meta, field.nullable)
            if ch.num_rows != n:
                raise UnsupportedChunk("row count mismatch")
            cols[name] = _decode_column_device(
                ch, _arrow_to_dtype(field.type), cap)
            n_device += 1
        except Exception:
            # ANY decode problem (unsupported feature, codec pa.decompress
            # can't handle — e.g. hadoop-framed LZ4 — or a parse error)
            # falls back to the per-column host decode, never crashes the
            # query: the host reader is the always-correct tier
            fallback.append(name)
    if fallback:
        # per-column host decode for the leftovers (reference: the plugin
        # likewise keeps unsupported columns on the CPU decode path)
        import io as _io

        import pyarrow.parquet as pq

        from ..columnar.host import HostTable
        t = pq.ParquetFile(_io.BytesIO(raw)).read_row_group(
            rg, columns=fallback)
        ht = HostTable.from_arrow(t)
        host_dt = DeviceTable.from_host(ht, min_bucket, capacity=cap)
        for cname, c in zip(host_dt.names, host_dt.columns):
            cols[cname] = c
    import jax.numpy as jnp
    iota = jnp.arange(cap, dtype=jnp.int32)
    mask = iota < n
    ordered = tuple(cols[c] for c in columns)
    return (DeviceTable(ordered, mask, jnp.int32(n), tuple(columns)),
            n_device)
