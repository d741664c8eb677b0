"""Device-side parquet decode.

Reference: the GPU plugin's biggest IO win is decoding parquet ON the
accelerator — raw column chunks go to the device and cuDF kernels expand
them (GpuParquetScanBase.scala:995,1194). The TPU-native shape of that
design, mapped onto XLA's static-shape world:

- HOST does the byte plumbing: file reads, page-header parsing
  (io/parquet_thrift.py), page decompression, and a one-pass scan of the
  RLE/bit-packed hybrid streams into *run tables* (a few entries per run,
  NOT per value — the classic GPU decoder split).
- DEVICE does the per-value work, one fused jit per column chunk, with no
  search and no loop: each run's attributes are scattered as DELTAS at the
  run's first position and one int32 prefix sum spreads them over the
  rows; bit-packed fields unpack densely (eight values of width w are w
  bytes, so the host hands the blob over byte-column-major and every field
  is static shifts of whole vectors); what is left is one gather a stream
  to close the short last group of each page, the dictionary gather, and
  for chunks with nulls the validity prefix sum and the gather spreading the
  dense non-null stream over the row slots.
- A chunk whose definition levels are all set (the parser knows before it
  uploads anything) takes a program of its own in which validity is
  ``iota < n`` and the definition-level stream is not traced at all
  (span ``decode.dense``; chunks with nulls: ``decode.general``).

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


#: Run-table lengths the decode programs are compiled for. With no search
#: over the table its length sets no loop count, only the size of a
#: scatter, so two sizes cover most chunks (a page is a few runs; a
#: million rows of a bit-packed dictionary column a few thousand) and
#: longer tables take the next power of two.
_RUN_BUCKETS = (256, 4096)


def _bucket_runs(n_runs: int) -> int:
    for b in _RUN_BUCKETS:
        if n_runs <= b:
            return b
    return _pow2(n_runs)


class _RunTable:
    """Accumulated RLE/bit-packed runs across a chunk's pages."""

    def __init__(self):
        self.out_start: List[int] = []
        self.count: List[int] = []
        self.rle_value: List[int] = []
        self.width: List[int] = []      # PER-RUN bit width (pages with a
        # growing dictionary are written at increasing widths!); 0 = RLE run
        self.field_base: List[int] = []  # first field of a bit-packed run
        # in its width's blob, counted in values of that width
        self.packed: Dict[int, bytearray] = {}  # width -> its runs' bytes;
        # every run is whole groups of 8 values = w bytes, so field j of
        # the blob sits at bit j x w
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
                if nvals > 0:
                    # keep only the groups that hold values, whole (a
                    # truncated last group is zero-filled): the blob stays
                    # a multiple of w bytes
                    keep = (nvals + 7) // 8 * width
                    blob = self.packed.setdefault(width, bytearray())
                    self.out_start.append(self.total)
                    self.count.append(nvals)
                    self.rle_value.append(0)
                    self.width.append(width)
                    self.field_base.append(len(blob) // width * 8)
                    got = buf[pos:pos + keep]
                    blob.extend(got)
                    blob.extend(bytes(keep - len(got)))
                    self.total += nvals
                    produced += nvals
                pos += nbytes
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
        self.rle_value.append(v)
        self.width.append(0)
        self.field_base.append(0)
        self.total += run

    def widths(self) -> Tuple[int, ...]:
        """The bit widths of the chunk's bit-packed runs, sorted: part of
        the decode program's key (the unpack is static per width)."""
        return tuple(sorted(self.packed))

    def device_inputs(self, cap: int) -> Tuple[np.ndarray, ...]:
        """What ``_expand_runs`` takes, all int32 / uint8:

        - ``starts`` (R,): each run's first output position, strictly
          increasing; padding entries lie past ``cap`` (the scatter drops
          them) and stay unique and sorted;
        - ``deltas`` (k, R): per attribute the CHANGE from the run before,
          so a prefix sum over the scattered deltas leaves every position
          holding its own run's attribute. Row 0: ``field_base -
          out_start`` (position + it = the field to read); row 1: an RLE
          run's value + 1, 0 for a bit-packed run; row 2, only with
          several widths: where the run's width starts in the unpacked
          field table;
        - ``packed_t`` (sum of widths, G): each width's blob as w byte
          columns of its G groups (byte c of every group contiguous).

        R is bucketed coarsely and G = cap/8 + R follows from it (a run
        wastes less than one group), so neither adds a shape of its own."""
        n_runs = len(self.out_start)
        if self.total > cap:
            raise UnsupportedChunk("more values than rows")
        rb = _bucket_runs(n_runs)
        groups = cap // 8 + rb
        widths = self.widths()
        if len(widths) * 8 * groups + cap + rb >= 2 ** 31:
            raise UnsupportedChunk("field offsets past int32")
        starts = np.arange(cap, cap + rb, dtype=np.int32)
        starts[:n_runs] = self.out_start
        width = np.asarray(self.width, np.int64)
        packed_run = width > 0
        attrs = np.zeros((3 if len(widths) > 1 else 2, n_runs), np.int64)
        attrs[0] = np.where(packed_run, np.asarray(self.field_base, np.int64)
                            - starts[:n_runs], 0)
        attrs[1] = np.where(packed_run, 0,
                            np.asarray(self.rle_value, np.int64) + 1)
        if len(widths) > 1:
            attrs[2] = np.where(
                packed_run, np.searchsorted(widths, width) * (8 * groups), 0)
        deltas = np.zeros((len(attrs), rb), np.int32)
        deltas[:, :n_runs] = np.diff(attrs, axis=1, prepend=0)
        packed_t = np.zeros((sum(widths), groups), np.uint8)
        row = 0
        for w in widths:
            blob = np.frombuffer(self.packed[w], np.uint8).reshape(-1, w)
            if len(blob) > groups:
                raise UnsupportedChunk("more bit-packed groups than rows")
            packed_t[row:row + w, :len(blob)] = blob.T
            row += w
        return starts, deltas, packed_t


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
        self.n_defined = 0           # rows whose definition level is set
        self.nullable = False
        self.bool_plain: List[Tuple[bytes, int]] = []  # packed bits, count
        self.uses_dict = False
        self.uses_plain = False

    @property
    def all_defined(self) -> bool:
        """Every definition level is set: the chunk has no null."""
        return self.n_defined == self.num_rows

    @property
    def segments(self) -> str:
        """Which value segments the chunk has: "dict", "plain" (also a
        chunk with no page at all) or "mixed" (dictionary, then PLAIN)."""
        if self.uses_dict:
            return "mixed" if self.uses_plain else "dict"
        return "plain"


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
        ch.n_defined += n_nonnull
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
        if rt.width[i] == 0:
            total += rt.count[i] * (1 if rt.rle_value[i] else 0)
        else:
            # bit-packed def levels at width 1: count set bits in the run
            base = rt.field_base[i] // 8
            nbits = rt.count[i]
            blob = bytes(rt.packed[1][base:base + (nbits + 7) // 8])
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


def _unpack_fields(packed_t, widths: Tuple[int, ...]):
    """Every bit field of the blobs, densely: for each width w the w byte
    columns of G groups give the 8 fields of every group by static shifts
    of whole vectors. -> (len(widths) * 8 * G,) int32, width-major, then
    field-in-group-major: field f of width number q is at
    ``q * 8G + (f & 7) * G + (f >> 3)``."""
    import jax.numpy as jnp
    fields = []
    row = 0
    for w in widths:
        cols = packed_t[row:row + w].astype(jnp.uint32)
        row += w
        for k in range(8):
            c0, shift = (k * w) >> 3, (k * w) & 7
            # width <= 24 enforced at parse time: shift + w <= 31 bits
            word = cols[c0]
            for b in range(1, (shift + w + 7) >> 3):
                word = word | (cols[c0 + b] << (8 * b))
            fields.append((word >> shift) & jnp.uint32((1 << w) - 1))
    return jnp.concatenate(fields).astype(jnp.int32)


def _expand_runs(starts, deltas, packed_t, widths: Tuple[int, ...],
                 cap: int):
    """values[i] for output positions 0..cap of one RLE/bit-packed hybrid
    stream (``_RunTable.device_inputs``), in int32 throughout. The run
    table is expanded without a search: its per-run deltas are scattered
    at the run starts (a few thousand elements) and ONE prefix sum leaves
    each position with its run's attributes. A bit-packed value is then
    one gather from the densely unpacked fields — position + offset, a
    piecewise shift of the identity that skips each page's short last
    group. Positions past the stream's total hold garbage; callers mask."""
    import jax
    import jax.numpy as jnp
    from ..columnar.device import prefix_sum
    with jax.named_scope("pq_run_prefix_sum"):
        scattered = jnp.zeros((deltas.shape[0], cap), jnp.int32) \
            .at[:, starts].add(deltas, mode="drop", indices_are_sorted=True,
                               unique_indices=True)
        attrs = prefix_sum(scattered)
    rle = attrs[1] - 1          # an RLE run's value, -1 in bit-packed runs
    if not widths:
        return rle
    with jax.named_scope("pq_bit_unpack"):
        groups = packed_t.shape[1]
        field = jax.lax.iota(jnp.int32, cap) + attrs[0]
        at = (field & 7) * groups + (field >> 3)
        if len(widths) > 1:
            at = at + attrs[2]
        unpacked = jnp.take(_unpack_fields(packed_t, widths), at,
                            mode="clip")
    return jnp.where(rle >= 0, rle, unpacked)


def _validity_and_pos(defs, def_widths: Optional[Tuple[int, ...]], n,
                      cap: int):
    """(validity, pos): row r is non-null and reads entry ``pos[r]`` of the
    chunk's dense non-null stream. ``def_widths`` None = the parser found
    every definition level set: validity is ``iota < n``, pos the identity
    (returned as None), and no definition-level op is traced."""
    import jax
    import jax.numpy as jnp
    from ..columnar.device import prefix_sum
    in_rows = jax.lax.iota(jnp.int32, cap) < n
    if def_widths is None:
        return in_rows, None
    with jax.named_scope("pq_def_levels"):
        validity = jnp.logical_and(
            _expand_runs(*defs, def_widths, cap) > 0, in_rows)
        return validity, prefix_sum(validity.astype(jnp.int32)) - 1


def _after_dict(plain, n_dict, cap: int):
    """``plain`` (cap rows, entry j = the j-th PLAIN value) moved down by
    ``n_dict`` rows, so that it lines up with the dense stream it follows
    the dictionary-encoded values in: a slice, not a gather."""
    import jax
    import jax.numpy as jnp
    return jax.lax.dynamic_slice_in_dim(
        jnp.concatenate([jnp.zeros_like(plain), plain]), cap - n_dict, cap)


def _fixed_kernel_builder(npdt_str: str, cap: int, segments: str,
                          def_widths: Optional[Tuple[int, ...]],
                          idx_widths: Tuple[int, ...]):
    """Fixed-width decode: dense stream = dict segment ++ plain segment.

    ``segments`` says which of the two the chunk has ("dict", "plain",
    "mixed" — the pyarrow dictionary-overflow chunk, whose first
    ``n_dict`` non-null values are dictionary-encoded and the rest PLAIN);
    an absent segment's inputs are ``()`` and nothing of it is traced."""
    def fn(defs, idx, dvals, plain, n_dict, n):
        import jax
        import jax.numpy as jnp
        validity, pos = _validity_and_pos(defs, def_widths, n, cap)
        if segments != "plain":
            with jax.named_scope("pq_dict_indices"):
                indices = _expand_runs(*idx, idx_widths, cap)
        with jax.named_scope("pq_value_gather"):
            if segments == "plain":
                stream = plain
            else:
                stream = jnp.take(dvals, indices, mode="clip")
                if segments == "mixed":
                    stream = jnp.where(
                        jax.lax.iota(jnp.int32, cap) < n_dict, stream,
                        _after_dict(plain, n_dict, cap))
            vals = stream if pos is None \
                else jnp.take(stream, pos, mode="clip")
            vals = jnp.where(validity, vals, jnp.zeros((), vals.dtype))
        return vals.astype(jnp.dtype(npdt_str)), validity
    return lambda: fn


def _bytes_kernel_builder(cap: int, segments: str,
                          def_widths: Optional[Tuple[int, ...]],
                          idx_widths: Tuple[int, ...]):
    """BYTE_ARRAY decode into the bucketed (rows, width) byte-matrix +
    lengths layout — dictionary rows gather as whole matrix rows (an
    MXU-friendly 2D gather), plain rows come from the host-assembled
    matrix, segments as in _fixed_kernel_builder."""
    def fn(defs, idx, dict_mat, dict_lens, plain_mat, plain_lens, n_dict, n):
        import jax
        import jax.numpy as jnp
        validity, pos = _validity_and_pos(defs, def_widths, n, cap)
        if segments != "plain":
            with jax.named_scope("pq_dict_indices"):
                indices = _expand_runs(*idx, idx_widths, cap)
        with jax.named_scope("pq_value_gather"):
            if segments != "plain":
                if pos is not None:
                    indices = jnp.take(indices, pos, mode="clip")
                data = jnp.take(dict_mat, indices, axis=0, mode="clip")
                lengths = jnp.take(dict_lens, indices, mode="clip")
            if segments != "dict":
                if pos is None:
                    row_plain = _after_dict(plain_mat, n_dict, cap)
                    len_plain = _after_dict(plain_lens, n_dict, cap)
                else:
                    row_plain = jnp.take(plain_mat, pos - n_dict, axis=0,
                                         mode="clip")
                    len_plain = jnp.take(plain_lens, pos - n_dict,
                                         mode="clip")
                if segments == "plain":
                    data, lengths = row_plain, len_plain
                else:
                    dense_pos = jax.lax.iota(jnp.int32, cap) if pos is None \
                        else pos
                    from_dict = dense_pos < n_dict
                    data = jnp.where(from_dict[:, None], data, row_plain)
                    lengths = jnp.where(from_dict, lengths, len_plain)
            data = jnp.where(validity[:, None], data,
                             jnp.zeros((), jnp.uint8))
            lengths = jnp.where(validity, lengths, 0).astype(jnp.int32)
        return data, lengths, validity
    return lambda: fn


def _pad_rows(a: np.ndarray, rows: int) -> np.ndarray:
    if a.shape[0] > rows:
        raise UnsupportedChunk("more values than rows")
    return np.pad(a, ((0, rows - a.shape[0]),) + ((0, 0),) * (a.ndim - 1))


def _stream_inputs(ch: _Chunk, cap: int):
    """The definition-level and dictionary-index streams as the kernels
    take them: ``()`` for a stream its program does not trace."""
    defs = () if ch.all_defined else ch.defs.device_inputs(cap)
    idx = ch.idx.device_inputs(cap) if ch.uses_dict else ()
    return defs, idx, np.int32(ch.idx.total), np.int32(ch.num_rows)


def _bytes_inputs(ch: _Chunk, cap: int) -> tuple:
    """Host arrays the BYTE_ARRAY kernel takes."""
    from ..columnar.device import bucket_width
    defs, idx, n_dict, n = _stream_inputs(ch, cap)
    max_len = 1
    if ch.ba_dict is not None and len(ch.ba_dict[1]):
        max_len = max(max_len, int(ch.ba_dict[1].max()))
    for _, lens, _b in ch.ba_plain:
        if len(lens):
            max_len = max(max_len, int(lens.max()))
    width = bucket_width(max_len)
    dict_part = plain_part = ((), ())
    if ch.uses_dict:
        if ch.ba_dict is None:
            raise UnsupportedChunk("dict-encoded pages, no dict page")
        dm, dlens = _ba_matrix([ch.ba_dict], width)
        rows = _pow2(dm.shape[0])
        dict_part = (_pad_rows(dm, rows), _pad_rows(dlens, rows))
    if ch.segments != "dict":
        pm, plens = _ba_matrix(ch.ba_plain, width)
        plain_part = (_pad_rows(pm, cap), _pad_rows(plens, cap))
    return (defs, idx, *dict_part, *plain_part, n_dict, n)


def _fixed_inputs(ch: _Chunk, npdt, cap: int) -> tuple:
    """Host arrays the fixed-width kernel takes."""
    defs, idx, n_dict, n = _stream_inputs(ch, cap)
    dv = plain = ()
    if ch.uses_dict:
        dv = np.asarray(ch.dictionary, npdt)
        dv = _pad_rows(dv, _pow2(max(1, len(dv))))
    if ch.segments != "dict":
        if ch.bool_plain:
            plain = np.concatenate(
                [_plain_values(b, "BOOLEAN", c) for b, c in ch.bool_plain])
        else:
            blob = b"".join(ch.plain_parts)
            d_ = np.dtype(npdt)
            if d_.kind == "f":
                phys = "FLOAT" if d_.itemsize == 4 else "DOUBLE"
            else:  # ints + date32/timestamp storage types
                phys = "INT32" if d_.itemsize == 4 else "INT64"
            count = len(blob) // np.dtype(_NP_BY_PHYS[phys]).itemsize
            plain = _plain_values(blob, phys, count)
        plain = _pad_rows(np.asarray(plain, npdt), cap)
    return (defs, idx, dv, plain, n_dict, n)


def _decode_column_device(ch: _Chunk, out_dtype: dt.DataType, cap: int):
    """-> DeviceColumn with row capacity ``cap`` (device kernels; compiled
    callables shared via the global compile cache). The program is chosen
    by what the parser saw in the chunk — all rows defined or not, which
    value segments it has, the bit widths of each stream — never by a
    conf. Three phases, each a span: the host builds the kernel's inputs
    (``scan.parse``), uploads them (``h2d``), and dispatches the decode
    (``decode.dense`` for an all-defined chunk, else ``decode.general``)."""
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
                     bytes=sum(int(a.nbytes)
                               for a in jax.tree_util.tree_leaves(args))):
        args = jax.device_put(args)
    segments = ch.segments
    def_widths = None if ch.all_defined else ch.defs.widths()
    idx_widths = ch.idx.widths() if ch.uses_dict else ()
    shape_key = f"{cap}|{segments}|{def_widths}|{idx_widths}"
    with tracer.span("decode.dense" if ch.all_defined else "decode.general",
                     "decode",
                     runs=len(ch.defs.out_start) + len(ch.idx.out_start),
                     widths=",".join(map(str, idx_widths))):
        if is_bytes:
            fn = cached_jit(
                f"pq_ba|{shape_key}",
                _bytes_kernel_builder(cap, segments, def_widths, idx_widths),
                name="pq_decode_bytes")
            data, lengths, validity = fn(*args)
            return DeviceColumn(data, validity, out_dtype, lengths)
        npdt_str = np.dtype(npdt).str
        fn = cached_jit(
            f"pq_mix|{npdt_str}|{shape_key}",
            _fixed_kernel_builder(npdt_str, cap, segments, def_widths,
                                  idx_widths),
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
