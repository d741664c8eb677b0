"""String functions (reference: sql-plugin/.../stringFunctions.scala, 1381 LoC).

TPU-first design: device strings are fixed-width padded uint8 matrices
``(capacity, width)`` plus int32 byte ``lengths`` (columnar/device.py). Every
string kernel below is a dense 2-D vector op over that matrix so XLA can fuse
and tile it onto the VPU:

- character-aware ops (length/substring/reverse) derive a per-byte *character
  index* from the UTF-8 continuation-bit mask ``(b & 0xC0) != 0x80`` — exact
  for all of UTF-8, no host round-trip;
- variable-length outputs (substring/trim/concat) are produced by *stable
  left-compaction*: select the surviving bytes, stable-argsort the inverted
  selection mask per row, gather — O(w log w) per row, fully vectorized;
- search ops (contains/instr/locate) gather sliding windows against literal
  patterns (pattern length is static at trace time).

Case mapping on device is ASCII-only (tagged with a ps-note, like the
reference's incompat annotations); the host fallback engine is full Unicode.
"""
from __future__ import annotations

import numpy as np

from ..columnar import dtypes as dt
from .arithmetic import _combine_validity
from .base import EvalCol, EvalContext, Expression, Literal

__all__ = [
    "Upper", "Lower", "Length", "OctetLength", "BitLength", "Substring",
    "StartsWith", "EndsWith", "Contains", "StringLocate", "Concat",
    "ConcatWs", "StringTrim", "StringTrimLeft", "StringTrimRight",
    "StringLpad", "StringRpad", "StringRepeat", "StringReplace",
    "SubstringIndex", "StringReverse", "InitCap", "Ascii", "Chr",
    "Like", "RLike", "RegExpExtract", "RegExpReplace", "literal_value",
]


# ---------------------------------------------------------------------------
# device helpers (all take xp = jax.numpy)
# ---------------------------------------------------------------------------

def _pos_mask(xp, w: int, lengths):
    """(n, w) bool — byte position is inside the string."""
    return xp.arange(w, dtype=xp.int32)[None, :] < lengths[:, None]


def _char_starts(xp, vals, lengths):
    """(n, w) bool — byte begins a UTF-8 character and is inside the string."""
    starts = (vals & 0xC0) != 0x80
    return xp.logical_and(starts, _pos_mask(xp, vals.shape[1], lengths))


def _stable_argsort(xp, a, axis=-1):
    if xp is np:
        return np.argsort(a, axis=axis, kind="stable")
    return xp.argsort(a, axis=axis, stable=True)


def _compact(xp, vals, sel):
    """Stable left-compaction of selected bytes. Returns (data, lengths)."""
    order = _stable_argsort(xp, xp.logical_not(sel), axis=1)
    data = xp.take_along_axis(vals, order, axis=1)
    lengths = sel.sum(axis=1).astype(xp.int32)
    w = vals.shape[1]
    data = xp.where(_pos_mask(xp, w, lengths), data, 0)
    return data, lengths


def _zero_tail(xp, vals, lengths):
    return xp.where(_pos_mask(xp, vals.shape[1], lengths), vals, 0)


def _pad_to(xp, m, w):
    if m.shape[1] >= w:
        return m
    return xp.pad(m, ((0, 0), (0, w - m.shape[1])))


def literal_value(e: Expression):
    """The python value if ``e`` is a (possibly aliased) literal, else None."""
    from .base import Alias
    while isinstance(e, Alias):
        e = e.child
    if isinstance(e, Literal):
        return e.value
    return None


def _utf8_len(s) -> int:
    return len(s.encode() if isinstance(s, str) else s)


# ---------------------------------------------------------------------------
# unary string ops
# ---------------------------------------------------------------------------

class UnaryString(Expression):
    def __init__(self, child: Expression):
        self.child = child
        self.children = (child,)

    @property
    def data_type(self):
        return dt.STRING

    @property
    def nullable(self) -> bool:
        return self.child.nullable

    def eval(self, ctx: EvalContext) -> EvalCol:
        c = self.child.eval(ctx)
        if ctx.is_device:
            return self._eval_device(ctx, c)
        vals = np.asarray([self._host_one(s) for s in c.values], dtype=object)  # srtpu: sync-ok(host-eval path builds an object array from Python strings — no device transfer)
        return EvalCol(vals, c.validity, self.data_type)

    def _host_one(self, s: str):
        raise NotImplementedError

    def _eval_device(self, ctx, c: EvalCol) -> EvalCol:
        raise NotImplementedError


class Upper(UnaryString):
    """upper() — device path is ASCII-only (ps-note), host is full Unicode."""

    def _host_one(self, s):
        return s.upper()

    def _eval_device(self, ctx, c):
        xp = ctx.xp
        v = c.values
        is_lower = xp.logical_and(v >= 97, v <= 122)
        return EvalCol(xp.where(is_lower, v - 32, v), c.validity, dt.STRING,
                       c.lengths)


class Lower(UnaryString):
    def _host_one(self, s):
        return s.lower()

    def _eval_device(self, ctx, c):
        xp = ctx.xp
        v = c.values
        is_upper = xp.logical_and(v >= 65, v <= 90)
        return EvalCol(xp.where(is_upper, v + 32, v), c.validity, dt.STRING,
                       c.lengths)


class InitCap(UnaryString):
    """initcap() — device is ASCII-only; word boundary = space (Spark semantics)."""

    def _host_one(self, s):
        return " ".join(w[:1].upper() + w[1:].lower() for w in s.split(" "))

    def _eval_device(self, ctx, c):
        xp = ctx.xp
        v = c.values
        lo = xp.where(xp.logical_and(v >= 65, v <= 90), v + 32, v)
        prev = xp.concatenate(
            [xp.full((v.shape[0], 1), 32, dtype=v.dtype), lo[:, :-1]], axis=1)
        first = prev == 32
        up = xp.where(xp.logical_and(lo >= 97, lo <= 122) & first, lo - 32, lo)
        return EvalCol(up, c.validity, dt.STRING, c.lengths)


class StringReverse(UnaryString):
    """reverse() — UTF-8 character-exact on device: bytes are re-ordered by
    (reversed character index, byte offset within character)."""

    def _host_one(self, s):
        return s[::-1]

    def _eval_device(self, ctx, c):
        xp = ctx.xp
        v, lengths = c.values, c.lengths
        w = v.shape[1]
        pos = xp.arange(w, dtype=xp.int32)[None, :]
        starts = _char_starts(xp, v, lengths)
        cidx = xp.cumsum(starts.astype(xp.int32), axis=1) - 1
        nchars = starts.sum(axis=1).astype(xp.int32)
        # byte offset of the character this byte belongs to
        from jax import lax
        start_pos = lax.cummax(xp.where(starts, pos, -1), axis=1)
        in_char = pos - start_pos
        valid = _pos_mask(xp, w, lengths)
        key = xp.where(valid, (nchars[:, None] - 1 - cidx) * w + in_char,
                       2 * w * w)
        order = _stable_argsort(xp, key, axis=1)
        data = xp.take_along_axis(v, order, axis=1)
        return EvalCol(_zero_tail(xp, data, lengths), c.validity, dt.STRING,
                       lengths)


class Length(Expression):
    """length() — number of characters (UTF-8-aware on both paths)."""

    def __init__(self, child: Expression):
        self.child = child
        self.children = (child,)

    @property
    def data_type(self):
        return dt.INT

    def eval(self, ctx: EvalContext) -> EvalCol:
        c = self.child.eval(ctx)
        if ctx.is_device:
            xp = ctx.xp
            n = _char_starts(xp, c.values, c.lengths).sum(axis=1)
            return EvalCol(n.astype(xp.int32), c.validity, dt.INT)
        vals = np.asarray([len(s) for s in c.values], dtype=np.int32)  # srtpu: sync-ok(host-eval path builds an object array from Python strings — no device transfer)
        return EvalCol(vals, c.validity, dt.INT)


class OctetLength(Expression):
    def __init__(self, child: Expression):
        self.child = child
        self.children = (child,)

    @property
    def data_type(self):
        return dt.INT

    def eval(self, ctx: EvalContext) -> EvalCol:
        c = self.child.eval(ctx)
        if ctx.is_device:
            return EvalCol(c.lengths.astype(ctx.xp.int32), c.validity, dt.INT)
        vals = np.asarray([_utf8_len(s) for s in c.values], dtype=np.int32)  # srtpu: sync-ok(host-eval path builds an object array from Python strings — no device transfer)
        return EvalCol(vals, c.validity, dt.INT)


class BitLength(OctetLength):
    def eval(self, ctx):
        r = super().eval(ctx)
        return EvalCol(r.values * 8, r.validity, dt.INT)


class Ascii(Expression):
    """ascii() — codepoint of the first character (ASCII-exact on device)."""

    def __init__(self, child: Expression):
        self.child = child
        self.children = (child,)

    @property
    def data_type(self):
        return dt.INT

    def eval(self, ctx: EvalContext) -> EvalCol:
        c = self.child.eval(ctx)
        if ctx.is_device:
            xp = ctx.xp
            first = c.values[:, 0].astype(xp.int32)
            return EvalCol(xp.where(c.lengths > 0, first, 0), c.validity, dt.INT)
        vals = np.asarray([ord(s[0]) if len(s) else 0 for s in c.values],  # srtpu: sync-ok(host-eval path builds an object array from Python strings — no device transfer)
                          dtype=np.int32)
        return EvalCol(vals, c.validity, dt.INT)


class Chr(Expression):
    """chr(n): the character for n & 0xFF (empty for n < 0).

    Device: the output is at most 2 UTF-8 bytes (codepoints 0-255), so the
    "dynamic" width is a static 2-byte matrix with computed lengths."""

    def __init__(self, child: Expression):
        self.child = child
        self.children = (child,)

    @property
    def data_type(self):
        return dt.STRING

    def eval(self, ctx: EvalContext) -> EvalCol:
        c = self.child.eval(ctx)
        if ctx.is_device:
            xp = ctx.xp
            from ..columnar.device import bucket_width
            iv = c.values.astype(xp.int64)   # sign check BEFORE narrowing
            b = (iv & 0xFF).astype(xp.int32)
            one = b < 0x80
            byte0 = xp.where(one, b, 0xC0 | (b >> 6)).astype(xp.uint8)
            byte1 = xp.where(one, 0, 0x80 | (b & 0x3F)).astype(xp.uint8)
            data = _pad_to(xp, xp.stack([byte0, byte1], axis=1),
                           bucket_width(2))
            lengths = xp.where(iv < 0, 0, xp.where(one, 1, 2)) \
                .astype(xp.int32)
            return EvalCol(_zero_tail(xp, data, lengths), c.validity,
                           dt.STRING, lengths)
        vals = np.asarray([chr(int(v) & 0xFF) if int(v) >= 0 else ""  # srtpu: sync-ok(host-eval path builds an object array from Python strings — no device transfer)
                           for v in c.values], dtype=object)
        return EvalCol(vals, c.validity, dt.STRING)


# ---------------------------------------------------------------------------
# substring family
# ---------------------------------------------------------------------------

class Substring(Expression):
    """substring(str, pos, len) — Spark 1-based, negative pos from the end.

    Device path is UTF-8 character-exact: byte selected iff its character index
    falls in [start, start+len); survivors stable-compact left.
    """

    def __init__(self, child: Expression, pos: Expression, length: Expression):
        self.child, self.pos, self.length = child, pos, length
        self.children = (child, pos, length)

    @property
    def data_type(self):
        return dt.STRING

    def eval(self, ctx: EvalContext) -> EvalCol:
        c = self.child.eval(ctx)
        p = self.pos.eval(ctx)
        l = self.length.eval(ctx)
        validity = _combine_validity(ctx, c, p, l)
        if not ctx.is_device:
            out = []
            for s, pos, ln in zip(c.values, p.values, l.values):
                out.append(_host_substr(s, int(pos), int(ln)))
            return EvalCol(np.asarray(out, dtype=object), validity, dt.STRING)  # srtpu: sync-ok(host-eval path builds an object array from Python strings — no device transfer)
        xp = ctx.xp
        v, lengths = c.values, c.lengths
        w = v.shape[1]
        starts = _char_starts(xp, v, lengths)
        cidx = xp.cumsum(starts.astype(xp.int32), axis=1) - 1
        nchars = starts.sum(axis=1).astype(xp.int32)
        pos = p.values.astype(xp.int32)
        ln = xp.maximum(l.values.astype(xp.int32), 0)
        # 0-based start char: pos>0 -> pos-1; pos==0 -> 0; pos<0 -> nchars+pos
        start0 = xp.where(pos > 0, pos - 1, xp.where(pos == 0, 0, nchars + pos))
        # negative start beyond beginning shortens the result (Spark semantics)
        ln = xp.where(start0 < 0, xp.maximum(ln + start0, 0), ln)
        start0 = xp.maximum(start0, 0)
        sel = xp.logical_and(cidx >= start0[:, None],
                             cidx < (start0 + ln)[:, None])
        sel = xp.logical_and(sel, _pos_mask(xp, w, lengths))
        data, out_len = _compact(xp, v, sel)
        return EvalCol(data, validity, dt.STRING, out_len)


def _host_substr(s: str, pos: int, ln: int) -> str:
    if ln <= 0:
        return ""
    n = len(s)
    start = pos - 1 if pos > 0 else (0 if pos == 0 else n + pos)
    if start < 0:
        ln = max(ln + start, 0)
        start = 0
    return s[start:start + ln]


class SubstringIndex(Expression):
    """substring_index(str, delim, count) with literal delim/count.

    Device: delimiter occurrences found by unrolled shifted-byte compares
    (UTF-8 is self-synchronizing, so byte matching is character-exact);
    multi-byte delimiters resolve overlaps with a left-to-right lax.scan;
    count>0 keeps a prefix (tail zeroed), count<0 a suffix (left-shift
    gather). Reference: GpuSubstringIndex in stringFunctions.scala."""

    def __init__(self, child: Expression, delim: Expression, count: Expression):
        self.child, self.delim, self.count = child, delim, count
        self.children = (child, delim, count)

    @property
    def data_type(self):
        return dt.STRING

    def eval(self, ctx: EvalContext) -> EvalCol:
        c = self.child.eval(ctx)
        delim = literal_value(self.delim)
        cnt = int(literal_value(self.count))
        if ctx.is_device:
            return self._eval_device(ctx, c, delim, cnt)
        out = []
        for s in c.values:
            out.append(_substring_index(s, delim, cnt))
        return EvalCol(np.asarray(out, dtype=object), c.validity, dt.STRING)  # srtpu: sync-ok(host-eval path builds an object array from Python strings — no device transfer)

    def _eval_device(self, ctx, c, delim: str, cnt: int) -> EvalCol:
        xp = ctx.xp
        v, lengths = c.values, c.lengths
        n, w = v.shape
        db = delim.encode() if delim else b""
        dlen = len(db)
        if dlen == 0 or cnt == 0 or dlen > w:
            empty_ok = dlen == 0 or cnt == 0  # no-delim/0-count -> ""
            out_len = xp.zeros(n, xp.int32) if empty_ok else lengths
            data = _zero_tail(xp, v, out_len)
            return EvalCol(data, c.validity, dt.STRING, out_len)
        j = xp.arange(w, dtype=xp.int32)[None, :]
        # occ[r, j]: delim bytes match starting at byte j (unrolled: dlen is
        # a host literal, typically 1-3)
        occ = xp.ones((n, w), dtype=bool)
        for k, bk in enumerate(db):
            shifted = xp.roll(v, -k, axis=1) if k else v
            # roll wraps; positions past w-k are invalidated by the length
            # bound below (j + dlen <= len <= w)
            occ = xp.logical_and(occ, shifted == xp.uint8(bk))
        occ = xp.logical_and(occ, (j + dlen) <= lengths[:, None])
        if dlen == 1:
            keep = occ
        else:
            from jax import lax

            def step(next_ok, col):
                o = occ[:, col]
                k_ = xp.logical_and(o, col >= next_ok)
                next_ok = xp.where(k_, col + dlen, next_ok)
                return next_ok, k_

            _, keep_t = lax.scan(step, xp.zeros(n, xp.int32),
                                 xp.arange(w, dtype=xp.int32))
            keep = keep_t.T  # scan stacks per-column results on axis 0
        kcum = xp.cumsum(keep.astype(xp.int32), axis=1)
        total = kcum[:, -1]
        if cnt > 0:
            found = total >= cnt
            hit = xp.logical_and(keep, kcum == cnt)
            cut = xp.argmax(hit, axis=1).astype(xp.int32)
            out_len = xp.where(found, cut, lengths).astype(xp.int32)
            data = _zero_tail(xp, v, out_len)
        else:
            kneg = -cnt
            found = total >= kneg
            target = (total - kneg + 1)[:, None]
            hit = xp.logical_and(keep, kcum == target)
            start = xp.where(found,
                             xp.argmax(hit, axis=1).astype(xp.int32) + dlen,
                             0).astype(xp.int32)
            src = xp.clip(j + start[:, None], 0, w - 1)
            data = xp.take_along_axis(v, src, axis=1)
            out_len = (lengths - start).astype(xp.int32)
            data = _zero_tail(xp, data, out_len)
        return EvalCol(data, c.validity, dt.STRING, out_len)


def _substring_index(s: str, delim: str, count: int) -> str:
    if not delim or count == 0:
        return ""
    if count > 0:
        parts = s.split(delim)
        return delim.join(parts[:count])
    parts = s.split(delim)
    return delim.join(parts[count:])


# ---------------------------------------------------------------------------
# search family
# ---------------------------------------------------------------------------

class BinaryStringPredicate(Expression):
    """Base for startswith/endswith/contains: boolean, null-propagating."""

    def __init__(self, left: Expression, right: Expression):
        self.left, self.right = left, right
        self.children = (left, right)

    @property
    def data_type(self):
        return dt.BOOLEAN

    def eval(self, ctx: EvalContext) -> EvalCol:
        l = self.left.eval(ctx)
        r = self.right.eval(ctx)
        validity = _combine_validity(ctx, l, r)
        if not ctx.is_device:
            vals = np.asarray([self._host_one(a, b)  # srtpu: sync-ok(host-eval path builds an object array from Python strings — no device transfer)
                               for a, b in zip(l.values, r.values)])
            return EvalCol(vals, validity, dt.BOOLEAN)
        return EvalCol(self._eval_device(ctx, l, r), validity, dt.BOOLEAN)


class StartsWith(BinaryStringPredicate):
    def _host_one(self, a, b):
        return a.startswith(b)

    def _eval_device(self, ctx, l, r):
        xp = ctx.xp
        w = max(l.values.shape[1], r.values.shape[1])
        lv = _pad_to(xp, l.values, w)
        rv = _pad_to(xp, r.values, w)
        inside_r = _pos_mask(xp, w, r.lengths)
        match = xp.logical_or(lv == rv, xp.logical_not(inside_r))
        return xp.logical_and(xp.all(match, axis=1), l.lengths >= r.lengths)


class EndsWith(BinaryStringPredicate):
    def _host_one(self, a, b):
        return a.endswith(b)

    def _eval_device(self, ctx, l, r):
        xp = ctx.xp
        w = max(l.values.shape[1], r.values.shape[1])
        lv = _pad_to(xp, l.values, w)
        rv = _pad_to(xp, r.values, w)
        shift = (l.lengths - r.lengths)[:, None]
        idx = xp.arange(w, dtype=xp.int32)[None, :] + shift
        tail = xp.take_along_axis(lv, xp.clip(idx, 0, w - 1), axis=1)
        inside_r = _pos_mask(xp, w, r.lengths)
        match = xp.logical_or(tail == rv, xp.logical_not(inside_r))
        return xp.logical_and(xp.all(match, axis=1), l.lengths >= r.lengths)


def _device_find(ctx, l: EvalCol, pattern: bytes):
    """First byte offset of literal ``pattern`` in each row, -1 if absent."""
    return _device_find_from(ctx, l, pattern, 0)


class Contains(BinaryStringPredicate):
    """contains — device requires a literal pattern (reference: GpuContains)."""

    def _host_one(self, a, b):
        return b in a

    def _eval_device(self, ctx, l, r):
        pat = literal_value(self.right)
        assert pat is not None, "device contains requires literal pattern"
        return _device_find(ctx, l, pat.encode()) >= 0


class StringLocate(Expression):
    """locate/instr(substr, str[, start]) — 1-based char position, 0 = absent.

    Device path returns byte-derived char positions via the char-index of the
    matched byte offset (UTF-8 exact)."""

    def __init__(self, substr: Expression, string: Expression,
                 start: Expression = None):
        self.substr, self.string = substr, string
        self.start = start if start is not None else Literal(1)
        self.children = (substr, string, self.start)

    @property
    def data_type(self):
        return dt.INT

    def eval(self, ctx: EvalContext) -> EvalCol:
        sub = self.substr.eval(ctx)
        s = self.string.eval(ctx)
        st = self.start.eval(ctx)
        validity = _combine_validity(ctx, sub, s)
        if not ctx.is_device:
            out = []
            for a, b, k in zip(s.values, sub.values, st.values):
                k = int(k)
                if k <= 0:
                    out.append(0)
                else:
                    out.append(a.find(b, k - 1) + 1)
            return EvalCol(np.asarray(out, dtype=np.int32), validity, dt.INT)  # srtpu: sync-ok(host-eval path builds an object array from Python strings — no device transfer)
        xp = ctx.xp
        pat = literal_value(self.substr)
        start = int(literal_value(self.start) or 1)
        assert pat is not None, "device locate requires literal pattern"
        # byte offset of first match at/after byte(start-1) (ASCII start col)
        off = _device_find_from(ctx, s, pat.encode(), start - 1)
        starts = _char_starts(xp, s.values, s.lengths)
        cidx = xp.cumsum(starts.astype(xp.int32), axis=1) - 1
        w = s.values.shape[1]
        char_of = xp.take_along_axis(
            cidx, xp.clip(off, 0, w - 1)[:, None], axis=1)[:, 0]
        found = xp.where(off >= 0, char_of + 1, 0)
        return EvalCol(xp.where(start <= 0, 0, found).astype(xp.int32),
                       validity, dt.INT)


def _device_find_from(ctx, l: EvalCol, pattern: bytes, from_byte: int):
    xp = ctx.xp
    v, lengths = l.values, l.lengths
    w = v.shape[1]
    p = len(pattern)
    if p == 0:
        return xp.full(v.shape[0], max(from_byte, 0), dtype=xp.int32)
    if p > w:
        return xp.full(v.shape[0], -1, dtype=xp.int32)
    pat = xp.asarray(np.frombuffer(pattern, dtype=np.uint8))
    hit = xp.ones(v.shape, dtype=bool)
    for k in range(p):
        shifted = v[:, k:] if k else v
        shifted = _pad_to(xp, shifted, w)
        hit = xp.logical_and(hit, shifted == pat[k])
    pos = xp.arange(w, dtype=xp.int32)[None, :]
    ok = xp.logical_and(pos <= (lengths - p)[:, None], pos >= from_byte)
    hit = xp.logical_and(hit, ok)
    any_hit = xp.any(hit, axis=1)
    first = xp.argmax(hit, axis=1).astype(xp.int32)
    return xp.where(any_hit, first, -1)


# ---------------------------------------------------------------------------
# concatenation / padding
# ---------------------------------------------------------------------------

class Concat(Expression):
    """concat(s1, s2, ...) — null if any input null. Device: pairwise fold of
    an index-select merge (out[j] = left[j] if j < len_l else right[j-len_l])."""

    def __init__(self, *children: Expression):
        self.children = tuple(children)

    @property
    def data_type(self):
        return dt.STRING

    def eval(self, ctx: EvalContext) -> EvalCol:
        cols = [c.eval(ctx) for c in self.children]
        validity = cols[0].validity
        for c in cols[1:]:
            validity = _combine_validity(
                ctx, EvalCol(None, validity, dt.STRING), c)
        if not ctx.is_device:
            vals = np.asarray(["".join(parts) for parts in  # srtpu: sync-ok(host-eval path builds an object array from Python strings — no device transfer)
                               zip(*[c.values for c in cols])], dtype=object)
            return EvalCol(vals, validity, dt.STRING)
        acc = cols[0]
        for c in cols[1:]:
            acc = _device_concat2(ctx, acc, c)
        return EvalCol(acc.values, validity, dt.STRING, acc.lengths)


def _device_concat2(ctx, l: EvalCol, r: EvalCol) -> EvalCol:
    xp = ctx.xp
    from ..columnar.device import bucket_width
    out_w = bucket_width(l.values.shape[1] + r.values.shape[1])
    lv = _pad_to(xp, l.values, out_w)
    rv = _pad_to(xp, r.values, out_w)
    j = xp.arange(out_w, dtype=xp.int32)[None, :]
    ll = l.lengths[:, None]
    from_l = j < ll
    r_idx = xp.clip(j - ll, 0, out_w - 1)
    r_sel = xp.take_along_axis(rv, r_idx, axis=1)
    data = xp.where(from_l, lv, r_sel)
    lengths = xp.minimum(l.lengths + r.lengths, out_w).astype(xp.int32)
    return EvalCol(_zero_tail(xp, data, lengths), None, dt.STRING, lengths)


class ConcatWs(Expression):
    """concat_ws(sep, ...) — skips null inputs; null only when sep is null.

    Device: fold of the Concat index-select merge, with per-row effective
    lengths zeroed for null inputs and for separators that precede the
    first non-null part — the output width is statically bounded by the
    sum of input widths, so "dynamic" width is just length arithmetic
    (reference: GpuConcatWs in stringFunctions.scala)."""

    def __init__(self, sep: Expression, *children: Expression):
        self.sep = sep
        self.children = (sep,) + tuple(children)

    @property
    def data_type(self):
        return dt.STRING

    @property
    def nullable(self):
        return self.sep.nullable

    def eval(self, ctx: EvalContext) -> EvalCol:
        sep = self.sep.eval(ctx)
        cols = [c.eval(ctx) for c in self.children[1:]]
        if ctx.is_device:
            return self._eval_device(ctx, sep, cols)
        out = []
        n = ctx.num_rows
        masks = [c.valid_mask(ctx) for c in cols]
        for i in range(n):
            parts = [c.values[i] for c, m in zip(cols, masks) if m[i]]
            out.append(sep.values[i].join(parts))
        return EvalCol(np.asarray(out, dtype=object), sep.validity, dt.STRING)  # srtpu: sync-ok(host-eval path builds an object array from Python strings — no device transfer)

    def _eval_device(self, ctx, sep, cols) -> EvalCol:
        xp = ctx.xp
        n = sep.shape0(ctx)
        acc = EvalCol(xp.zeros((n, 1), dtype=xp.uint8), None, dt.STRING,
                      xp.zeros(n, dtype=xp.int32))
        started = xp.zeros(n, dtype=bool)
        for c in cols:
            valid = c.valid_mask(ctx)
            need_sep = xp.logical_and(started, valid)
            sep_eff = EvalCol(
                sep.values, None, dt.STRING,
                xp.where(need_sep, sep.lengths, 0).astype(xp.int32))
            part = EvalCol(
                c.values, None, dt.STRING,
                xp.where(valid, c.lengths, 0).astype(xp.int32))
            acc = _device_concat2(ctx, acc, sep_eff)
            acc = _device_concat2(ctx, acc, part)
            started = xp.logical_or(started, valid)
        return EvalCol(acc.values, sep.validity, dt.STRING, acc.lengths)


class StringRpad(Expression):
    """rpad(str, len, pad) — ASCII-exact on device (len counts bytes there)."""

    pad_left = False

    def __init__(self, child: Expression, length: Expression, pad: Expression):
        self.child, self.length, self.pad = child, length, pad
        self.children = (child, length, pad)

    @property
    def data_type(self):
        return dt.STRING

    def eval(self, ctx: EvalContext) -> EvalCol:
        c = self.child.eval(ctx)
        ln = self.length.eval(ctx)
        pd = self.pad.eval(ctx)
        validity = _combine_validity(ctx, c, ln, pd)
        if not ctx.is_device:
            out = []
            for s, k, p in zip(c.values, ln.values, pd.values):
                out.append(_host_pad(s, int(k), p, self.pad_left))
            return EvalCol(np.asarray(out, dtype=object), validity, dt.STRING)  # srtpu: sync-ok(host-eval path builds an object array from Python strings — no device transfer)
        xp = ctx.xp
        pad = literal_value(self.pad)
        tgt = int(literal_value(self.length))
        assert pad is not None and tgt is not None, \
            "device pad requires literal length/pad"
        tgt = max(tgt, 0)
        pb = pad.encode() or b" "
        from ..columnar.device import bucket_width
        out_w = bucket_width(max(tgt, c.values.shape[1], 1))
        v = _pad_to(xp, c.values, out_w)
        slen = c.lengths
        out_len = xp.full_like(slen, tgt)
        j = xp.arange(out_w, dtype=xp.int32)[None, :]
        patv = xp.asarray(np.frombuffer(pb, dtype=np.uint8))
        if self.pad_left:
            shift = xp.maximum(tgt - slen, 0)[:, None]
            src = xp.take_along_axis(
                v, xp.clip(j - shift, 0, out_w - 1), axis=1)
            fill = patv[(j % len(pb)).astype(xp.int32)]
            data = xp.where(j < shift, fill, src)
        else:
            fill = patv[((j - slen[:, None]) % len(pb)).astype(xp.int32)]
            data = xp.where(j < slen[:, None], v, fill)
        # truncation when tgt < len
        data = _zero_tail(xp, data, out_len)
        return EvalCol(data, validity, dt.STRING, out_len.astype(xp.int32))


class StringLpad(StringRpad):
    pad_left = True


def _host_pad(s: str, k: int, p: str, left: bool) -> str:
    if k <= 0:
        return ""
    if k <= len(s):
        return s[:k]
    if not p:
        return s
    fill = (p * ((k - len(s)) // len(p) + 1))[:k - len(s)]
    return fill + s if left else s + fill


class StringRepeat(Expression):
    """repeat(str, n) — device requires literal n (output width is static)."""

    def __init__(self, child: Expression, times: Expression):
        self.child, self.times = child, times
        self.children = (child, times)

    @property
    def data_type(self):
        return dt.STRING

    def eval(self, ctx: EvalContext) -> EvalCol:
        c = self.child.eval(ctx)
        t = self.times.eval(ctx)
        validity = _combine_validity(ctx, c, t)
        if not ctx.is_device:
            vals = np.asarray([s * max(int(k), 0)  # srtpu: sync-ok(host-eval path builds an object array from Python strings — no device transfer)
                               for s, k in zip(c.values, t.values)], dtype=object)
            return EvalCol(vals, validity, dt.STRING)
        xp = ctx.xp
        n_rep = int(literal_value(self.times))
        if n_rep <= 0:
            z = xp.zeros_like(c.values)
            return EvalCol(z, validity, dt.STRING,
                           xp.zeros_like(c.lengths))
        from ..columnar.device import bucket_width
        out_w = bucket_width(c.values.shape[1] * n_rep)
        v = _pad_to(xp, c.values, out_w)
        j = xp.arange(out_w, dtype=xp.int32)[None, :]
        slen = xp.maximum(c.lengths, 1)[:, None]
        data = xp.take_along_axis(v, (j % slen).astype(xp.int32), axis=1)
        lengths = xp.minimum(c.lengths * n_rep, out_w).astype(xp.int32)
        return EvalCol(_zero_tail(xp, data, lengths), validity, dt.STRING,
                       lengths)


# ---------------------------------------------------------------------------
# trim family
# ---------------------------------------------------------------------------

class StringTrim(Expression):
    """trim / ltrim / rtrim (space trimming, Spark default)."""

    trim_left = True
    trim_right = True

    def __init__(self, child: Expression):
        self.child = child
        self.children = (child,)

    @property
    def data_type(self):
        return dt.STRING

    def eval(self, ctx: EvalContext) -> EvalCol:
        c = self.child.eval(ctx)
        if not ctx.is_device:
            if self.trim_left and self.trim_right:
                f = lambda s: s.strip(" ")
            elif self.trim_left:
                f = lambda s: s.lstrip(" ")
            else:
                f = lambda s: s.rstrip(" ")
            vals = np.asarray([f(s) for s in c.values], dtype=object)  # srtpu: sync-ok(host-eval path builds an object array from Python strings — no device transfer)
            return EvalCol(vals, c.validity, dt.STRING)
        xp = ctx.xp
        v, lengths = c.values, c.lengths
        w = v.shape[1]
        pos = xp.arange(w, dtype=xp.int32)[None, :]
        inside = _pos_mask(xp, w, lengths)
        nonspace = xp.logical_and(v != 32, inside)
        any_ns = xp.any(nonspace, axis=1)
        first_ns = xp.argmax(nonspace, axis=1).astype(xp.int32)
        last_ns = (w - 1 - xp.argmax(nonspace[:, ::-1], axis=1)).astype(xp.int32)
        lo = first_ns if self.trim_left else xp.zeros_like(first_ns)
        hi = (last_ns + 1) if self.trim_right else lengths
        lo = xp.where(any_ns, lo, 0)
        hi = xp.where(any_ns, hi, 0)
        sel = xp.logical_and(pos >= lo[:, None], pos < hi[:, None])
        sel = xp.logical_and(sel, inside)
        data, out_len = _compact(xp, v, sel)
        return EvalCol(data, c.validity, dt.STRING, out_len)


class StringTrimLeft(StringTrim):
    trim_right = False


class StringTrimRight(StringTrim):
    trim_left = False


# ---------------------------------------------------------------------------
# replace (host-only) and LIKE
# ---------------------------------------------------------------------------

class StringReplace(Expression):
    """replace(str, search, replace). Device path: literal-span emission
    kernel (reference: GpuStringReplace in stringFunctions.scala delegates
    to cudf replace; here regex.py replace_by_spans)."""

    def __init__(self, child: Expression, search: Expression,
                 replace: Expression):
        self.child, self.search, self.replace = child, search, replace
        self.children = (child, search, replace)

    @property
    def data_type(self):
        return dt.STRING

    def eval(self, ctx: EvalContext) -> EvalCol:
        c = self.child.eval(ctx)
        if ctx.is_device:
            search = literal_value(self.search)
            repl = literal_value(self.replace)
            if search is None or repl is None:
                raise TypeError("device replace requires literal "
                                "search/replacement (tag_fn gates this)")
            return _device_replace_spans(ctx, c, search.encode(),
                                         repl.encode(), literal_search=True)
        s = self.search.eval(ctx)
        r = self.replace.eval(ctx)
        validity = _combine_validity(ctx, c, s, r)
        out = []
        for a, b, rep in zip(c.values, s.values, r.values):
            out.append(a.replace(b, rep) if b else a)
        return EvalCol(np.asarray(out, dtype=object), validity, dt.STRING)  # srtpu: sync-ok(host-eval path builds an object array from Python strings — no device transfer)


class Like(Expression):
    """LIKE with literal pattern (reference: GpuLike requires literal too).

    Device strategy mirrors the reference's like→cuDF transpile: simple
    patterns (equality / prefix / suffix / contains, no ``_``) lower to the
    vectorized search kernels above; everything else transpiles to the regex
    NFA engine (expr/regex.py) or falls back to host at tag time.
    """

    def __init__(self, child: Expression, pattern: Expression,
                 escape: str = "\\"):
        self.child, self.pattern, self.escape = child, pattern, escape
        self.children = (child, pattern)

    def with_children(self, children):
        return Like(children[0], children[1], self.escape)

    @property
    def data_type(self):
        return dt.BOOLEAN

    # -- pattern analysis (used by tagging AND execution) --------------------
    def simple_kind(self):
        """('equals'|'prefix'|'suffix'|'contains', needle) or None."""
        pat = literal_value(self.pattern)
        if pat is None:
            return None
        body = pat
        lead = body.startswith("%")
        trail = body.endswith("%") and not body.endswith(self.escape + "%")
        core = body[1 if lead else 0: len(body) - 1 if trail else len(body)]
        # no remaining wildcards/escapes allowed in the core
        if any(ch in core for ch in ("%", "_", self.escape)):
            return None
        if lead and trail:
            return ("contains", core)
        if lead:
            return ("suffix", core)
        if trail:
            return ("prefix", core)
        return ("equals", core)

    def to_regex(self):
        pat = literal_value(self.pattern)
        if pat is None:
            return None
        import re as _re
        out = []
        i = 0
        while i < len(pat):
            ch = pat[i]
            if ch == self.escape and i + 1 < len(pat):
                out.append(_re.escape(pat[i + 1]))
                i += 2
                continue
            if ch == "%":
                out.append(".*")
            elif ch == "_":
                out.append(".")
            else:
                out.append(_re.escape(ch))
            i += 1
        return "^" + "".join(out) + "$"

    def eval(self, ctx: EvalContext) -> EvalCol:
        c = self.child.eval(ctx)
        kind = self.simple_kind()
        if not ctx.is_device:
            import re as _re
            rx = _re.compile(self.to_regex(), _re.DOTALL)
            vals = np.asarray([rx.match(s) is not None for s in c.values])  # srtpu: sync-ok(host-eval path builds an object array from Python strings — no device transfer)
            return EvalCol(vals, c.validity, dt.BOOLEAN)
        xp = ctx.xp
        if kind is not None:
            import jax
            op, needle = kind
            nb = needle.encode()
            # the search kernels' ops under one name in the program (the
            # general pattern's are under the NFA's ``like_nfa``)
            with jax.named_scope("like_search"):
                if op == "contains":
                    vals = _device_find(ctx, c, nb) >= 0
                elif op == "prefix":
                    vals = _device_startswith(ctx, c, nb)
                elif op == "suffix":
                    vals = _device_endswith(ctx, c, nb)
                else:  # equals
                    vals = xp.logical_and(_device_startswith(ctx, c, nb),
                                          c.lengths == len(nb))
            return EvalCol(vals, c.validity, dt.BOOLEAN)
        # general pattern: device regex NFA
        from .regex import compile_device_nfa
        nfa = compile_device_nfa(self.to_regex())
        assert nfa is not None, "device LIKE on un-transpilable pattern"
        return EvalCol(nfa.matches(ctx, c), c.validity, dt.BOOLEAN)


class RLike(Expression):
    """rlike — Java find() semantics. Device path runs the bitmask NFA
    (expr/regex.py); tagging falls back to host when the pattern is outside
    the NFA subset (reference: CudfRegexTranspiler rejection path)."""

    def __init__(self, child: Expression, pattern: Expression):
        self.child, self.pattern = child, pattern
        self.children = (child, pattern)

    @property
    def data_type(self):
        return dt.BOOLEAN

    def eval(self, ctx: EvalContext) -> EvalCol:
        c = self.child.eval(ctx)
        pat = literal_value(self.pattern)
        if not ctx.is_device:
            import re as _re
            rx = _re.compile(pat)
            vals = np.asarray([rx.search(s) is not None for s in c.values])  # srtpu: sync-ok(host-eval path builds an object array from Python strings — no device transfer)
            return EvalCol(vals, c.validity, dt.BOOLEAN)
        from .regex import compile_device_nfa
        nfa = compile_device_nfa(pat)
        assert nfa is not None, "device rlike on un-transpilable pattern"
        return EvalCol(nfa.matches(ctx, c), c.validity, dt.BOOLEAN)


class RegExpExtract(Expression):
    """regexp_extract(str, pattern, idx) — host-only (capture groups)."""

    def __init__(self, child: Expression, pattern: Expression,
                 idx: Expression = None):
        self.child, self.pattern = child, pattern
        self.idx = idx if idx is not None else Literal(1)
        self.children = (child, pattern, self.idx)

    @property
    def data_type(self):
        return dt.STRING

    def eval(self, ctx: EvalContext) -> EvalCol:
        import re as _re
        c = self.child.eval(ctx)
        if ctx.is_device:
            from .regex import (compile_device_nfa, compile_group_plan,
                                extract_first_span, extract_group_span)
            nfa = compile_device_nfa(literal_value(self.pattern))
            gi = int(literal_value(self.idx))
            if nfa is None or not nfa.spans_supported:
                raise TypeError("device regexp_extract outside the span "
                                "subset (tag_fn gates this)")
            xp = ctx.xp
            ends = nfa.match_ends(xp, c.values, c.lengths)
            if gi == 0:
                out, out_len = extract_first_span(
                    xp, c.values, c.lengths, ends)
            else:
                plan = compile_group_plan(literal_value(self.pattern))
                if plan is None or gi > plan.ngroups:
                    raise TypeError("device regexp_extract: capture group "
                                    "outside the plan subset (tag_fn gates)")
                out, out_len = extract_group_span(
                    xp, c.values, c.lengths, ends, plan, gi)
            return EvalCol(out, c.validity, dt.STRING, out_len)
        rx = _re.compile(literal_value(self.pattern))
        gi = int(literal_value(self.idx))
        out = []
        for s in c.values:
            m = rx.search(s)
            out.append(m.group(gi) if m and m.group(gi) is not None else "")
        return EvalCol(np.asarray(out, dtype=object), c.validity, dt.STRING)  # srtpu: sync-ok(host-eval path builds an object array from Python strings — no device transfer)


class RegExpReplace(Expression):
    """regexp_replace(str, pattern, replacement) — host-only."""

    def __init__(self, child: Expression, pattern: Expression,
                 replacement: Expression):
        self.child, self.pattern, self.replacement = child, pattern, replacement
        self.children = (child, pattern, replacement)

    @property
    def data_type(self):
        return dt.STRING

    def eval(self, ctx: EvalContext) -> EvalCol:
        import re as _re
        c = self.child.eval(ctx)
        if ctx.is_device:
            repl = literal_value(self.replacement)
            if repl is None:
                raise TypeError("device regexp_replace: null replacement "
                                "stays on host (tag_fn gates this)")
            if _re.search(r"\$\d", repl):
                # $n group references: template re-emission over the
                # deterministic group-plan subset (reference:
                # GpuRegExpReplace, stringFunctions.scala:895)
                return _device_replace_template(
                    ctx, c, literal_value(self.pattern), repl)
            return _device_replace_spans(
                ctx, c, literal_value(self.pattern).encode(), repl.encode(),
                literal_search=False)
        rx = _re.compile(literal_value(self.pattern))
        rep = _java_repl_to_python(literal_value(self.replacement))
        out = [rx.sub(rep, s) for s in c.values]
        return EvalCol(np.asarray(out, dtype=object), c.validity, dt.STRING)  # srtpu: sync-ok(host-eval path builds an object array from Python strings — no device transfer)


def _java_repl_to_python(repl: str) -> str:
    """Java Matcher replacement -> python re template: ``$n`` becomes
    ``\\n``, ``\\x`` escapes stay literal, lone python-special backslashes
    get escaped."""
    out = []
    i = 0
    while i < len(repl):
        ch = repl[i]
        if ch == "\\" and i + 1 < len(repl):
            nxt = repl[i + 1]
            out.append("\\\\" if nxt == "\\" else _re_escape_lit(nxt))
            i += 2
            continue
        if ch == "$" and i + 1 < len(repl) and repl[i + 1].isdigit():
            j = i + 1
            while j < len(repl) and repl[j].isdigit():
                j += 1
            # \g<n> form: unambiguous for $0 and when digits follow
            out.append("\\g<" + repl[i + 1:j] + ">")
            i = j
            continue
        out.append("\\\\" if ch == "\\" else ch)
        i += 1
    return "".join(out)


def _re_escape_lit(ch: str) -> str:
    return "\\\\" if ch == "\\" else ch


def _device_replace_template(ctx, c: EvalCol, pattern: str,
                             repl: str) -> EvalCol:
    """Device regexp_replace with ``$n`` group references: NFA match
    spans + all-starts group-bounds walk + template re-emission."""
    from ..columnar.device import bucket_width
    from .regex import (compile_device_nfa, compile_group_plan,
                        group_bounds_all_starts, parse_replacement_template,
                        replace_by_template, select_leftmost_spans)
    xp = ctx.xp
    nfa = compile_device_nfa(pattern)
    plan = compile_group_plan(pattern)
    if nfa is None or not nfa.spans_supported or plan is None:
        raise TypeError("device regexp_replace with group refs outside the "
                        "group-plan subset (tag_fn gates this)")
    segments = parse_replacement_template(repl, plan.ngroups)
    if segments is None:
        raise TypeError("device regexp_replace: un-parsable replacement "
                        "template (tag_fn gates this)")
    w = c.values.shape[1]
    ends = nfa.match_ends(xp, c.values, c.lengths)
    starts, in_match = select_leftmost_spans(xp, ends, c.lengths)
    bounds = group_bounds_all_starts(xp, c.values, c.lengths, plan)
    lit_total = sum(len(p) for k, p in segments if k == "lit")
    n_refs = sum(1 for k, _ in segments if k == "grp")
    # worst case: every non-match byte copies (<= w), each group ref's
    # emissions total <= w across all matches ('$1$1' doubles), plus one
    # literal block per match (<= w // min_len matches)
    out_w = bucket_width(w * (1 + n_refs)
                         + (w // max(nfa.min_len, 1)) * lit_total
                         + lit_total)
    out, out_len = replace_by_template(xp, c.values, c.lengths, starts,
                                       in_match, ends, segments, bounds,
                                       out_w)
    return EvalCol(out, c.validity, dt.STRING, out_len)


def _device_replace_spans(ctx, c: EvalCol, search: bytes, repl: bytes,
                          literal_search: bool) -> EvalCol:
    """Shared device replace: literal or NFA match spans -> re-emission."""
    from ..columnar.device import bucket_width
    from .regex import (compile_device_nfa, literal_match_ends,
                        replace_by_spans, select_leftmost_spans)
    xp = ctx.xp
    if literal_search and not search:
        return c          # Spark replace('', x) is the identity
    w = c.values.shape[1]
    if literal_search:
        ends = literal_match_ends(xp, c.values, c.lengths, search)
        min_len = len(search)
    else:
        nfa = compile_device_nfa(search.decode())
        if nfa is None or not nfa.spans_supported:
            raise TypeError("device regexp_replace outside the span subset "
                            "(tag_fn gates this)")
        ends = nfa.match_ends(xp, c.values, c.lengths)
        min_len = nfa.min_len
    starts, in_match = select_leftmost_spans(xp, ends, c.lengths)
    grow = max(len(repl) - min_len, 0)
    out_w = bucket_width(w + (w // max(min_len, 1)) * grow)
    out, out_len = replace_by_spans(xp, c.values, c.lengths, starts,
                                    in_match, repl, out_w)
    return EvalCol(out, c.validity, dt.STRING, out_len)


def _device_startswith(ctx, c: EvalCol, nb: bytes):
    xp = ctx.xp
    w = c.values.shape[1]
    if len(nb) > w:
        return xp.zeros(c.values.shape[0], dtype=bool)
    pat = xp.asarray(np.frombuffer(nb, dtype=np.uint8))
    head = c.values[:, :len(nb)]
    return xp.logical_and(xp.all(head == pat[None, :], axis=1),
                          c.lengths >= len(nb))


def _device_endswith(ctx, c: EvalCol, nb: bytes):
    xp = ctx.xp
    w = c.values.shape[1]
    if len(nb) == 0:
        return xp.ones(c.values.shape[0], dtype=bool)
    if len(nb) > w:
        return xp.zeros(c.values.shape[0], dtype=bool)
    pat = xp.asarray(np.frombuffer(nb, dtype=np.uint8))
    j = xp.arange(len(nb), dtype=xp.int32)[None, :]
    idx = xp.clip((c.lengths - len(nb))[:, None] + j, 0, w - 1)
    tail = xp.take_along_axis(c.values, idx, axis=1)
    return xp.logical_and(xp.all(tail == pat[None, :], axis=1),
                          c.lengths >= len(nb))


class GetJsonObject(Expression):
    """get_json_object(json, path) with the $.a.b[0] JSONPath subset
    (reference: GpuGetJsonObject.scala; host evaluation here)."""

    def __init__(self, json: Expression, path: Expression):
        self.json, self.path = json, path
        self.children = (json, path)

    @property
    def data_type(self):
        return dt.STRING

    def with_children(self, children):
        return GetJsonObject(children[0], children[1])

    @staticmethod
    def _parse_path(path):
        """Validate + tokenize ONCE (the path is a literal; per-row
        re-parsing was pure waste). -> token list or None for malformed
        paths (Spark returns null rather than best-effort parsing)."""
        import re as _re
        if not isinstance(path, str) \
                or not _re.fullmatch(r"\$(?:\.[A-Za-z0-9_]+|\[\d+\])*", path):
            return None
        return [(key if key else None, int(idx) if idx else None)
                for key, idx in
                _re.findall(r"\.([A-Za-z0-9_]+)|\[(\d+)\]", path)]

    @staticmethod
    def _extract(doc, tokens):
        import json as _json
        if not isinstance(doc, str):
            return None
        try:
            cur = _json.loads(doc)
        except Exception:
            return None
        for key, idx in tokens:
            if key is not None:
                if not isinstance(cur, dict) or key not in cur:
                    return None
                cur = cur[key]
            else:
                if not isinstance(cur, list) or idx >= len(cur):
                    return None
                cur = cur[idx]
        if cur is None:
            return None
        if isinstance(cur, str):
            return cur
        return _json.dumps(cur, separators=(",", ":"))

    def eval(self, ctx):
        import numpy as np
        jc = self.json.eval(ctx)
        tokens = self._parse_path(literal_value(self.path))
        n = len(jc.values)
        out = np.empty(n, dtype=object)
        validity = np.ones(n, dtype=bool)
        jvalid = jc.validity if jc.validity is not None \
            else np.ones(n, dtype=bool)
        for i in range(n):
            r = self._extract(jc.values[i], tokens) \
                if jvalid[i] and tokens is not None else None
            if r is None:
                validity[i] = False
                out[i] = ""
            else:
                out[i] = r
        return EvalCol(out, validity, dt.STRING)

    def __repr__(self):
        return f"get_json_object({self.json!r}, {self.path!r})"
