"""Hash expressions (reference: sql-plugin/.../HashFunctions.scala).

Spark-bit-exact Murmur3 (seed 42) and XxHash64 (seed 42), vectorized:
fixed-width types are pure elementwise uint32/uint64 arithmetic; strings run a
``lax.scan`` over the padded byte matrix's 4-byte blocks + tail bytes with
per-row length masking — one fused device program, no per-row control flow.

Exactness matters here: ``hash()`` output is user-visible and is also the
partitioning function, so host and device must agree bit-for-bit with each
other (and with Spark) or differential tests and shuffle placement break.
"""
from __future__ import annotations

import numpy as np

from ..columnar import dtypes as dt
from .base import EvalCol, EvalContext, Expression

__all__ = ["Murmur3Hash", "XxHash64", "SparkPartitionID",
           "MonotonicallyIncreasingID", "Rand"]

_U32 = 0xFFFFFFFF
_C1 = 0xCC9E2D51
_C2 = 0x1B873593


def _u32(xp, x):
    return x.astype(xp.uint32) if hasattr(x, "astype") else xp.uint32(x)


def _rotl32(xp, x, r):
    return (x << xp.uint32(r)) | (x >> xp.uint32(32 - r))


def _mix_k1(xp, k1):
    k1 = k1 * xp.uint32(_C1)
    k1 = _rotl32(xp, k1, 15)
    return k1 * xp.uint32(_C2)


def _mix_h1(xp, h1, k1):
    h1 = h1 ^ k1
    h1 = _rotl32(xp, h1, 13)
    return h1 * xp.uint32(5) + xp.uint32(0xE6546B64)


def _fmix(xp, h1, length):
    h1 = h1 ^ xp.uint32(length) if np.isscalar(length) else h1 ^ length
    h1 = h1 ^ (h1 >> xp.uint32(16))
    h1 = h1 * xp.uint32(0x85EBCA6B)
    h1 = h1 ^ (h1 >> xp.uint32(13))
    h1 = h1 * xp.uint32(0xC2B2AE35)
    return h1 ^ (h1 >> xp.uint32(16))


def _hash_int(xp, k, seed):
    """Murmur3_x86_32.hashInt(k, seed) — k: uint32 array, seed: uint32 array."""
    h1 = _mix_h1(xp, seed, _mix_k1(xp, k))
    return _fmix(xp, h1, xp.uint32(4))


def _hash_long(xp, v, seed):
    """hashLong: low word then high word, fmix with length 8."""
    v = v.astype(xp.int64).view(xp.uint64) if hasattr(v, "view") else v
    low = (v & xp.uint64(_U32)).astype(xp.uint32)
    high = (v >> xp.uint64(32)).astype(xp.uint32)
    h1 = _mix_h1(xp, seed, _mix_k1(xp, low))
    h1 = _mix_h1(xp, h1, _mix_k1(xp, high))
    return _fmix(xp, h1, xp.uint32(8))


def _normalize_float(xp, vals):
    """Spark normalizes -0.0 -> 0.0 and NaN -> canonical NaN before hashing."""
    vals = xp.where(vals == 0.0, xp.zeros_like(vals), vals)
    return xp.where(vals != vals, xp.full_like(vals, float("nan")), vals)


def float_word_bits(xp, w):
    """Float word -> u32 hash contribution, the same function under numpy
    and ``jax.numpy``: equal values give equal bits. A float64 goes by the
    bits of its float32 rounding and of the float32 rounding of what that
    leaves (both functions of the value alone): the TPU holds a float64 as
    a pair of float32 and its compiler has no bitcast of one to 64 integer
    bits (a float64 group-by key — TPC-H Q18's ``o_totalprice`` — failed to
    compile there, and so did its hash exchange on the mesh). Callers under
    numpy silence the overflow of a cast past float32's range."""
    hi = w.astype(xp.float32)
    u = hi.view(xp.uint32)
    if w.dtype == xp.float32:
        return u
    lo = xp.where(xp.isfinite(hi), w - hi.astype(w.dtype),
                  xp.zeros_like(w)).astype(xp.float32)
    return u ^ (lo.view(xp.uint32) * xp.uint32(0x9E3779B1))


def float_key_bits(xp, v):
    """A float key's u32 by the group-by's equality (``exec/aggregate.py``
    ``_key_code_words``): -0.0 hashes as +0.0 and every NaN alike, so a
    hash partitioner sends all rows of one group to one partition.

    Magnitudes whose split would hold a float32 subnormal hash as 0 (below
    2^-126 for a float32 key, 2^-73 for a float64 one, whose remainder
    keeps at most 29 bits): XLA flushes subnormals in a conversion, numpy
    does not, and host and device must place a key alike. Equal keys still
    hash alike; tiny distinct ones share a partition."""
    tiny = 2.0 ** -126 if v.dtype == xp.float32 else 2.0 ** -73
    nan = xp.isnan(v)
    v = xp.where(xp.logical_or(nan, xp.abs(v) < tiny), xp.zeros_like(v), v)
    return xp.where(nan, xp.uint32(0x7FC00000), float_word_bits(xp, v))


def _view_u64(xp, x):
    if xp is np:
        return x.view(np.uint64)
    import jax.numpy as jnp
    return jnp.asarray(x).view(jnp.uint64)


def _murmur3_fixed(xp, col: EvalCol, seed):
    d = col.dtype
    v = col.values
    if isinstance(d, dt.BooleanType):
        return _hash_int(xp, v.astype(xp.uint32), seed)
    if isinstance(d, (dt.ByteType, dt.ShortType, dt.IntegerType, dt.DateType)):
        return _hash_int(xp, v.astype(xp.int32).view(xp.uint32)
                         if xp is np else v.astype(xp.int32).astype(xp.uint32),
                         seed)
    if isinstance(d, (dt.LongType, dt.TimestampType, dt.DecimalType)):
        return _hash_long(xp, _view_u64(xp, v.astype(xp.int64)), seed)
    if isinstance(d, dt.FloatType):
        f = _normalize_float(xp, v.astype(xp.float32))
        bits = f.view(xp.uint32) if xp is np else f.view(xp.int32).astype(xp.uint32)
        return _hash_int(xp, bits, seed)
    if isinstance(d, dt.DoubleType):
        f = _normalize_float(xp, v.astype(xp.float64))
        return _hash_long(xp, _view_u64(xp, f), seed)
    raise TypeError(f"murmur3 of {d!r} not supported")


def _sext_byte(xp, b):
    """sign-extend a uint8 byte to uint32 (Java byte semantics)."""
    b32 = b.astype(xp.uint32)
    return xp.where(b32 >= 128, b32 | xp.uint32(0xFFFFFF00), b32)


def _murmur3_string_device(xp, col: EvalCol, seed):
    from jax import lax
    v, lengths = col.values, col.lengths
    n, w = v.shape
    aligned = (lengths - lengths % 4).astype(xp.int32)
    nblocks = w // 4

    def block_step(h1, bi):
        off = bi * 4
        b0 = v[:, off].astype(xp.uint32)
        b1 = v[:, off + 1].astype(xp.uint32)
        b2 = v[:, off + 2].astype(xp.uint32)
        b3 = v[:, off + 3].astype(xp.uint32)
        k = b0 | (b1 << 8) | (b2 << 16) | (b3 << 24)
        nh = _mix_h1(xp, h1, _mix_k1(xp, k))
        return xp.where(off + 4 <= aligned, nh, h1), None

    h1 = seed
    if nblocks:
        h1, _ = lax.scan(block_step, h1, xp.arange(nblocks, dtype=xp.int32))

    def tail_step(h1, j):
        k = _mix_k1(xp, _sext_byte(xp, xp.take(v, j, axis=1)))
        nh = _mix_h1(xp, h1, k)
        use = xp.logical_and(j >= aligned, j < lengths)
        return xp.where(use, nh, h1), None

    h1, _ = lax.scan(tail_step, h1, xp.arange(w, dtype=xp.int32))
    return _fmix(xp, h1, lengths.astype(xp.uint32))


def _murmur3_string_host(col: EvalCol, seed):
    out = np.empty(len(col.values), dtype=np.uint32)
    for i, s in enumerate(col.values):
        b = s.encode() if isinstance(s, str) else bytes(s)
        h1 = int(seed[i])
        la = len(b) - len(b) % 4
        for off in range(0, la, 4):
            k = int.from_bytes(b[off:off + 4], "little")
            k = (k * _C1) & _U32
            k = ((k << 15) | (k >> 17)) & _U32
            k = (k * _C2) & _U32
            h1 ^= k
            h1 = ((h1 << 13) | (h1 >> 19)) & _U32
            h1 = (h1 * 5 + 0xE6546B64) & _U32
        for off in range(la, len(b)):
            byte = b[off]
            k = byte | 0xFFFFFF00 if byte >= 128 else byte
            k = (k * _C1) & _U32
            k = ((k << 15) | (k >> 17)) & _U32
            k = (k * _C2) & _U32
            h1 ^= k
            h1 = ((h1 << 13) | (h1 >> 19)) & _U32
            h1 = (h1 * 5 + 0xE6546B64) & _U32
        h1 ^= len(b)
        h1 ^= h1 >> 16
        h1 = (h1 * 0x85EBCA6B) & _U32
        h1 ^= h1 >> 13
        h1 = (h1 * 0xC2B2AE35) & _U32
        h1 ^= h1 >> 16
        out[i] = h1
    return out


class Murmur3Hash(Expression):
    """hash(...) — Spark's Murmur3, folding seed 42 across columns."""

    def __init__(self, *children: Expression, seed: int = 42):
        self.children = tuple(children)
        self.seed = seed

    def with_children(self, children):
        return Murmur3Hash(*children, seed=self.seed)

    @property
    def data_type(self):
        return dt.INT

    @property
    def nullable(self):
        return False

    def eval(self, ctx: EvalContext) -> EvalCol:
        xp = ctx.xp
        n = ctx.num_rows
        h = xp.full((n,), self.seed, dtype=xp.uint32)
        for child in self.children:
            c = child.eval(ctx)
            if isinstance(c.dtype, (dt.StringType, dt.BinaryType)):
                if ctx.is_device:
                    nh = _murmur3_string_device(xp, c, h)
                else:
                    nh = _murmur3_string_host(c, h)
            else:
                nh = _murmur3_fixed(xp, c, h)
            # null input leaves the running hash unchanged (Spark semantics)
            h = xp.where(c.valid_mask(ctx), nh, h)
        return EvalCol(h.view(xp.int32), None, dt.INT)


# ---------------------------------------------------------------------------
# XxHash64
# ---------------------------------------------------------------------------

_XXP1 = 0x9E3779B185EBCA87
_XXP2 = 0xC2B2AE3D27D4EB4F
_XXP3 = 0x165667B19E3779F9
_XXP5 = 0x27D4EB2F165667C5
_U64 = 0xFFFFFFFFFFFFFFFF


def _rotl64(xp, x, r):
    return (x << xp.uint64(r)) | (x >> xp.uint64(64 - r))


def _xx_fmix(xp, h):
    h = h ^ (h >> xp.uint64(33))
    h = h * xp.uint64(_XXP2)
    h = h ^ (h >> xp.uint64(29))
    h = h * xp.uint64(_XXP3)
    return h ^ (h >> xp.uint64(32))


def _xx_long(xp, v, seed):
    """XXH64.hashLong(l, seed) — Spark's XxHash64 for 8-byte values."""
    hash_ = seed + xp.uint64(_XXP5) + xp.uint64(8)
    k1 = _rotl64(xp, v * xp.uint64(_XXP2), 31) * xp.uint64(_XXP1)
    hash_ = hash_ ^ k1
    hash_ = _rotl64(xp, hash_, 27) * xp.uint64(_XXP1) + xp.uint64(_XXP4)
    return _xx_fmix(xp, hash_)


_XXP4 = 0x85EBCA77C2B2AE63


def _xx_int(xp, v, seed):
    """XXH64.hashInt(i, seed): 4-byte values."""
    hash_ = seed + xp.uint64(_XXP5) + xp.uint64(4)
    hash_ = hash_ ^ (v.astype(xp.uint64) * xp.uint64(_XXP1))
    hash_ = _rotl64(xp, hash_, 23) * xp.uint64(_XXP2) + xp.uint64(_XXP3)
    return _xx_fmix(xp, hash_)


class XxHash64(Expression):
    """xxhash64(...) — Spark's XxHash64, seed 42, folding across columns."""

    def __init__(self, *children: Expression, seed: int = 42):
        self.children = tuple(children)
        self.seed = seed

    def with_children(self, children):
        return XxHash64(*children, seed=self.seed)

    @property
    def data_type(self):
        return dt.LONG

    @property
    def nullable(self):
        return False

    def eval(self, ctx: EvalContext) -> EvalCol:
        xp = ctx.xp
        n = ctx.num_rows
        h = xp.full((n,), self.seed, dtype=xp.uint64)
        for child in self.children:
            c = child.eval(ctx)
            d = c.dtype
            if isinstance(d, (dt.StringType, dt.BinaryType)):
                if ctx.is_device:
                    # vectorized device kernel over the byte matrix
                    nh = _xx_bytes_device(c.values, c.lengths, h)
                else:
                    nh = np.asarray([_xx_bytes_host(  # srtpu: sync-ok(host-eval branch — ctx.is_device is false; inputs are host values)
                        s.encode() if isinstance(s, str) else bytes(s),
                        int(sd))
                        for s, sd in zip(c.values, np.asarray(h))],  # srtpu: sync-ok(host-eval branch — ctx.is_device is false; inputs are host values)
                        dtype=np.uint64)
            elif isinstance(d, dt.BooleanType):
                nh = _xx_int(xp, c.values.astype(xp.uint32), h)
            elif isinstance(d, (dt.ByteType, dt.ShortType, dt.IntegerType,
                                dt.DateType)):
                v32 = c.values.astype(xp.int32)
                nh = _xx_int(xp, v32.view(xp.uint32) if xp is np
                             else v32.astype(xp.uint32), h)
            elif isinstance(d, (dt.LongType, dt.TimestampType, dt.DecimalType)):
                nh = _xx_long(xp, _view_u64(xp, c.values.astype(xp.int64)), h)
            elif isinstance(d, dt.FloatType):
                f = _normalize_float(xp, c.values.astype(xp.float32))
                bits = f.view(np.uint32) if xp is np \
                    else f.view(xp.int32).astype(xp.uint32)
                nh = _xx_int(xp, bits, h)
            elif isinstance(d, dt.DoubleType):
                f = _normalize_float(xp, c.values.astype(xp.float64))
                nh = _xx_long(xp, _view_u64(xp, f), h)
            else:
                raise TypeError(f"xxhash64 of {d!r} not supported")
            h = xp.where(c.valid_mask(ctx), nh, h)
        return EvalCol(h.view(xp.int64), None, dt.LONG)


def _xx_bytes_device(data, lengths, seeds):
    """Vectorized XXH64.hashUnsafeBytes over a (cap, w) uint8 byte matrix
    with per-row lengths — bit-identical to ``_xx_bytes_host`` (asserted by
    tests). Every loop below is STATIC over the padded width; per-row
    participation is masked, so one jit handles all lengths in the batch:

    - stripe phase: 32-byte stripes = 4 consecutive u64 words; stripe t is
      active for rows with t < len//32
    - 8-byte phase: word j participates when 32*(len//32) <= 8j and
      8j+8 <= len
    - 4-byte chunk at 8*(len//8) when len%8 >= 4 (word-aligned: the low
      half of word len//8)
    - <=3 tail bytes, gathered per row by dynamic index
    """
    import jax.numpy as jnp
    cap, w = data.shape
    n = lengths.astype(jnp.uint64)
    u = jnp.uint64
    seeds = seeds.astype(jnp.uint64)

    def rotl(x, r):
        return _rotl64(jnp, x, r)

    # little-endian u64 words; zero padding beyond each row's length is
    # masked out by the phase conditions below
    nwords = max(1, (w + 7) // 8)
    padded = jnp.pad(data, ((0, 0), (0, nwords * 8 - w)))
    words = jnp.zeros((cap, nwords), dtype=jnp.uint64)
    for byte in range(8):
        words = words | (padded[:, byte::8].astype(jnp.uint64)
                         << u(8 * byte))

    # stripe phase as lax.scan (O(1) graph in the padded width, like
    # _murmur3_string_device — unrolled loops would trace hundreds of ops
    # for wide buckets and recompile per width)
    import jax as _jax
    nstripes = (n // u(32)).astype(jnp.uint64)
    nstripe_max = max(1, (nwords + 3) // 4)
    words4 = jnp.pad(words, ((0, 0), (0, nstripe_max * 4 - nwords)))
    # (nstripe_max, 4, cap): scan consumes one stripe of 4 lanes per step
    stripes = jnp.moveaxis(words4.reshape(cap, nstripe_max, 4), 0, -1)

    def stripe_step(carry, xs):
        v1, v2, v3, v4 = carry
        t, ks = xs
        active = t < nstripes

        def lane(v, k):
            upd = rotl(v + k * u(_XXP2), 31) * u(_XXP1)
            return jnp.where(active, upd, v)
        return (lane(v1, ks[0]), lane(v2, ks[1]),
                lane(v3, ks[2]), lane(v4, ks[3])), None

    init = (seeds + u(_XXP1) + u(_XXP2), seeds + u(_XXP2),
            seeds, seeds - u(_XXP1))
    (v1, v2, v3, v4), _ = _jax.lax.scan(
        stripe_step, init,
        (jnp.arange(nstripe_max, dtype=jnp.uint64), stripes))
    merged = rotl(v1, 1) + rotl(v2, 7) + rotl(v3, 12) + rotl(v4, 18)
    for v in (v1, v2, v3, v4):
        merged = merged ^ (rotl(v * u(_XXP2), 31) * u(_XXP1))
        merged = merged * u(_XXP1) + u(_XXP4)
    h = jnp.where(n >= u(32), merged, seeds + u(_XXP5))
    h = h + n

    # 8-byte phase: words past the stripes, fully inside the length
    base_word = u(4) * nstripes

    def word_step(h, xs):
        j, k1 = xs
        active = (j >= base_word) & (u(8) * j + u(8) <= n)
        upd = h ^ (rotl(k1 * u(_XXP2), 31) * u(_XXP1))
        upd = rotl(upd, 27) * u(_XXP1) + u(_XXP4)
        return jnp.where(active, upd, h), None

    h, _ = _jax.lax.scan(
        word_step, h,
        (jnp.arange(nwords, dtype=jnp.uint64), jnp.moveaxis(words, 0, -1)))

    # 4-byte chunk (word-aligned low half of word len//8)
    has4 = (n % u(8)) >= u(4)
    jj = jnp.clip(n // u(8), 0, nwords - 1).astype(jnp.int32)
    word_jj = jnp.take_along_axis(words, jj[:, None], axis=1)[:, 0]
    k32 = word_jj & u(0xFFFFFFFF)
    upd = h ^ (k32 * u(_XXP1))
    upd = rotl(upd, 23) * u(_XXP2) + u(_XXP3)
    h = jnp.where(has4, upd, h)

    # tail bytes (at most 3)
    tail_start = u(8) * (n // u(8)) + jnp.where(has4, u(4), u(0))
    for t in range(3):
        p = tail_start + u(t)
        active = p < n
        idx = jnp.clip(p, 0, max(w - 1, 0)).astype(jnp.int32)
        byte = jnp.take_along_axis(data, idx[:, None], axis=1)[:, 0] \
            .astype(jnp.uint64) if w else jnp.zeros(cap, jnp.uint64)
        upd = rotl(h ^ (byte * u(_XXP5)), 11) * u(_XXP1)
        h = jnp.where(active, upd, h)

    return _xx_fmix(jnp, h)


def _xx_bytes_host(b: bytes, seed: int) -> int:
    """Spark XXH64.hashUnsafeBytes (scalar reference implementation)."""
    def rotl(x, r):
        return ((x << r) | (x >> (64 - r))) & _U64

    length = len(b)
    off = 0
    if length >= 32:
        v1 = (seed + _XXP1 + _XXP2) & _U64
        v2 = (seed + _XXP2) & _U64
        v3 = seed & _U64
        v4 = (seed - _XXP1) & _U64
        while off + 32 <= length:
            for _ in range(1):
                k1 = int.from_bytes(b[off:off + 8], "little")
                v1 = (rotl((v1 + k1 * _XXP2) & _U64, 31) * _XXP1) & _U64
                k2 = int.from_bytes(b[off + 8:off + 16], "little")
                v2 = (rotl((v2 + k2 * _XXP2) & _U64, 31) * _XXP1) & _U64
                k3 = int.from_bytes(b[off + 16:off + 24], "little")
                v3 = (rotl((v3 + k3 * _XXP2) & _U64, 31) * _XXP1) & _U64
                k4 = int.from_bytes(b[off + 24:off + 32], "little")
                v4 = (rotl((v4 + k4 * _XXP2) & _U64, 31) * _XXP1) & _U64
            off += 32
        h = (rotl(v1, 1) + rotl(v2, 7) + rotl(v3, 12) + rotl(v4, 18)) & _U64
        for v in (v1, v2, v3, v4):
            h ^= (rotl((v * _XXP2) & _U64, 31) * _XXP1) & _U64
            h = ((h * _XXP1) + _XXP4) & _U64
    else:
        h = (seed + _XXP5) & _U64
    h = (h + length) & _U64
    while off + 8 <= length:
        k1 = int.from_bytes(b[off:off + 8], "little")
        h ^= (rotl((k1 * _XXP2) & _U64, 31) * _XXP1) & _U64
        h = ((rotl(h, 27) * _XXP1) + _XXP4) & _U64
        off += 8
    if off + 4 <= length:
        k1 = int.from_bytes(b[off:off + 4], "little")
        h ^= (k1 * _XXP1) & _U64
        h = ((rotl(h, 23) * _XXP2) + _XXP3) & _U64
        off += 4
    while off < length:
        h ^= ((b[off] & 0xFF) * _XXP5) & _U64
        h = (rotl(h, 11) * _XXP1) & _U64
        off += 1
    h ^= h >> 33
    h = (h * _XXP2) & _U64
    h ^= h >> 29
    h = (h * _XXP3) & _U64
    h ^= h >> 32
    return h


# ---------------------------------------------------------------------------
# id / random expressions
# ---------------------------------------------------------------------------

class InputFileName(Expression):
    """input_file_name() (reference: GpuInputFileName + Spark's
    InputFileBlockHolder; sources populate io/file_block.py's holder right
    before yielding each batch). Empty string when the batch has no single
    source file (in-memory data, coalesced multi-file batches)."""

    context_dependent = True

    @property
    def data_type(self):
        return dt.STRING

    @property
    def nullable(self):
        return False

    def eval(self, ctx: EvalContext) -> EvalCol:
        import numpy as np

        from ..io.file_block import current_input_file
        name, _, _ = current_input_file()
        vals = np.empty(ctx.num_rows, dtype=object)
        vals[:] = name
        return EvalCol(vals, None, dt.STRING)


class _InputFileBlockField(Expression):
    context_dependent = True
    _field = 1

    @property
    def data_type(self):
        return dt.LONG

    @property
    def nullable(self):
        return False

    def eval(self, ctx: EvalContext) -> EvalCol:
        import numpy as np

        from ..io.file_block import current_input_file
        info = current_input_file()
        vals = np.full(ctx.num_rows, info[self._field], dtype=np.int64)
        return EvalCol(vals, None, dt.LONG)


class InputFileBlockStart(_InputFileBlockField):
    """input_file_block_start() (reference: GpuInputFileBlockStart)."""
    _field = 1


class InputFileBlockLength(_InputFileBlockField):
    """input_file_block_length() (reference: GpuInputFileBlockLength)."""
    _field = 2


class SparkPartitionID(Expression):
    """spark_partition_id() (reference: GpuSparkPartitionID.scala)."""

    context_dependent = True

    @property
    def data_type(self):
        return dt.INT

    @property
    def nullable(self):
        return False

    def eval(self, ctx: EvalContext) -> EvalCol:
        xp = ctx.xp
        vals = xp.full((ctx.num_rows,), ctx.partition_id, dtype=xp.int32)
        return EvalCol(vals, None, dt.INT)


class MonotonicallyIncreasingID(Expression):
    """monotonically_increasing_id(): (partition_id << 33) + row offset
    (reference: GpuMonotonicallyIncreasingID.scala)."""

    context_dependent = True

    @property
    def data_type(self):
        return dt.LONG

    @property
    def nullable(self):
        return False

    def eval(self, ctx: EvalContext) -> EvalCol:
        xp = ctx.xp
        base = xp.int64(ctx.partition_id) << 33 if xp is np \
            else (xp.asarray(ctx.partition_id, dtype=xp.int64) << 33)
        offs = xp.arange(ctx.num_rows, dtype=xp.int64) + ctx.batch_row_offset
        return EvalCol(base + offs, None, dt.LONG)


class SampleMask(Expression):
    """Deterministic Bernoulli-sample predicate: keep a row iff
    splitmix64(seed, partition, absolute row position) maps below
    ``fraction``. Unlike Rand, the device and host engines produce the SAME
    decisions, so sampling differential-tests bit-for-bit (the reference's
    GpuPoissonSampler is likewise deterministic per seed/partition)."""

    context_dependent = True

    def __init__(self, fraction: float, seed: int):
        assert 0.0 <= fraction <= 1.0, fraction
        self.fraction = float(fraction)
        self.seed = int(seed)
        self.children = ()

    def with_children(self, children):
        return self

    def __repr__(self):
        return f"SampleMask({self.fraction}, seed={self.seed})"

    @property
    def data_type(self):
        return dt.BOOLEAN

    @property
    def nullable(self):
        return False

    def eval(self, ctx: EvalContext) -> EvalCol:
        xp = ctx.xp
        n = ctx.num_rows
        pos = xp.arange(n, dtype=xp.int64) + ctx.batch_row_offset
        x = pos.astype(xp.uint64)
        x = x + xp.uint64((self.seed * 0x632BE59BD9B4E019
                           + ctx.partition_id * 0x9E3779B97F4A7C15)
                          & 0xFFFFFFFFFFFFFFFF)
        # splitmix64 finalizer (wrapping uint64 arithmetic on both backends)
        z = (x + xp.uint64(0x9E3779B97F4A7C15))
        z = (z ^ (z >> xp.uint64(30))) * xp.uint64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> xp.uint64(27))) * xp.uint64(0x94D049BB133111EB)
        z = z ^ (z >> xp.uint64(31))
        u = (z >> xp.uint64(11)).astype(xp.float64) * (2.0 ** -53)
        return EvalCol(u < self.fraction, None, dt.BOOLEAN)


class Rand(Expression):
    """rand([seed]) — per-partition-seeded uniform [0,1). Like the reference's
    GpuRand, values differ from Spark's XORShiftRandom sequence (marked
    incompat); device uses jax PRNG, host numpy PCG64."""

    context_dependent = True

    def __init__(self, seed=None):
        if seed is None:
            import random as _random
            seed = _random.randrange(2 ** 31)   # fresh stream per rand() call
        self.seed = seed
        self.children = ()

    def with_children(self, children):
        return self

    @property
    def data_type(self):
        return dt.DOUBLE

    @property
    def nullable(self):
        return False

    def eval(self, ctx: EvalContext) -> EvalCol:
        n = ctx.num_rows
        if ctx.is_device:
            import jax
            key = jax.random.PRNGKey(self.seed + ctx.partition_id * 7919
                                     + int(ctx.batch_row_offset))
            vals = jax.random.uniform(key, (n,), dtype=ctx.xp.float64)
            return EvalCol(vals, None, dt.DOUBLE)
        rng = np.random.default_rng(self.seed + ctx.partition_id * 7919
                                    + int(ctx.batch_row_offset))
        return EvalCol(rng.random(n), None, dt.DOUBLE)
