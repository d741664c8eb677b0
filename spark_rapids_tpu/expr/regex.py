"""Regex subsystem (reference: RegexParser.scala:41 + CudfRegexTranspiler:414).

The reference parses Java regex into an AST and either transpiles it to the
device engine's dialect (cuDF) or rejects it so the expression falls back to
CPU. This module keeps that exact shape, TPU-first:

- ``RegexParser``    — Java-style regex → AST, rejecting constructs Spark's
  semantics or our engines can't honor (backrefs, lookaround, \\p classes...).
- ``transpile``      — AST → Python ``re`` pattern for the host fallback
  engine (the supported subset is dialect-identical).
- ``compile_device_nfa`` — AST → byte-class **bitmask NFA** executed as a
  dense XLA program: states are bits of a uint32, the 256-byte alphabet is
  compressed to equivalence classes, and one ``lax.scan`` step per byte column
  computes ``next[t] = any(active & mask[class, t])`` for all rows at once.
  This is how a backtracking-free regex lands on the VPU: no per-row control
  flow, just (rows × states) integer ops per character position.

Match semantics follow Java ``Matcher.find()`` (unanchored unless ^/$).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Set, Tuple

import numpy as np

__all__ = ["RegexUnsupported", "RegexParser", "transpile",
           "compile_device_nfa", "DeviceNfa"]

MAX_STATES = 32          # state set must fit a uint32 bitmask
# The device NFA is run per *character*: continuation bytes (0x80-0xBF) are
# skipped by the scan, so a symbol is an ASCII byte or a UTF-8 lead byte.
# "any char" classes therefore include the lead-byte range — this keeps `.`,
# negated classes and \D/\W/\S character-exact for all UTF-8 input. Literal
# non-ASCII characters in a *pattern* are rejected from the device subset
# (lead bytes don't identify a character uniquely); host handles those.
_LEAD_BYTES = frozenset(range(0xC2, 0xF5))
_ALL_BYTES = frozenset(range(1, 128)) | _LEAD_BYTES   # NUL excluded (padding)


class RegexUnsupported(Exception):
    """Pattern uses a construct outside the supported subset."""


# ---------------------------------------------------------------------------
# AST
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RNode:
    pass


@dataclasses.dataclass
class RChars(RNode):
    """A one-byte matcher: set of accepted byte values."""
    bytes_: frozenset


@dataclasses.dataclass
class RSeq(RNode):
    items: List[RNode]


@dataclasses.dataclass
class RAlt(RNode):
    options: List[RNode]


@dataclasses.dataclass
class RRepeat(RNode):
    child: RNode
    lo: int
    hi: Optional[int]       # None = unbounded


@dataclasses.dataclass
class RGroup(RNode):
    """Capturing group (index is 1-based, Java numbering)."""
    child: RNode
    index: int


@dataclasses.dataclass
class RStartAnchor(RNode):
    pass


@dataclasses.dataclass
class REndAnchor(RNode):
    pass


_CLASS_D = frozenset(range(48, 58))
_CLASS_W = _CLASS_D | frozenset(range(65, 91)) | frozenset(range(97, 123)) | {95}
_CLASS_S = frozenset(b" \t\n\x0b\f\r")


class RegexParser:
    """Recursive-descent parser for the supported Java-regex subset."""

    def __init__(self, pattern: str):
        self.p = pattern
        self.i = 0
        #: lazy quantifiers seen — harmless for boolean matching, but they
        #: change SPAN lengths, so span-based ops must stay on host
        self.saw_lazy = False
        #: capturing groups seen (Java numbering)
        self.ngroups = 0

    def parse(self) -> RNode:
        node = self._alt()
        if self.i != len(self.p):
            raise RegexUnsupported(f"unexpected {self.p[self.i]!r} at {self.i}")
        return node

    # alt := seq ('|' seq)*
    def _alt(self) -> RNode:
        opts = [self._seq()]
        while self._peek() == "|":
            self.i += 1
            opts.append(self._seq())
        return opts[0] if len(opts) == 1 else RAlt(opts)

    def _seq(self) -> RNode:
        items: List[RNode] = []
        while True:
            ch = self._peek()
            if ch is None or ch in "|)":
                break
            items.append(self._quantified())
        return RSeq(items)

    def _quantified(self) -> RNode:
        atom = self._atom()
        while True:
            ch = self._peek()
            if ch == "*":
                self.i += 1
                atom = RRepeat(atom, 0, None)
            elif ch == "+":
                self.i += 1
                atom = RRepeat(atom, 1, None)
            elif ch == "?":
                self.i += 1
                atom = RRepeat(atom, 0, 1)
            elif ch == "{":
                atom = RRepeat(atom, *self._braces())
            else:
                break
            nxt = self._peek()
            if nxt in ("+",):   # possessive quantifiers: Java-only semantics
                raise RegexUnsupported("possessive quantifier")
            if nxt == "?":      # lazy: irrelevant for pure matching, consume
                self.saw_lazy = True
                self.i += 1
        return atom

    def _braces(self) -> Tuple[int, Optional[int]]:
        try:
            j = self.p.index("}", self.i)
            body = self.p[self.i + 1:j]
            self.i = j + 1
            if "," not in body:
                n = int(body)
                return n, n
            lo_s, hi_s = body.split(",", 1)
            lo = int(lo_s) if lo_s else 0
            hi = int(hi_s) if hi_s else None
            return lo, hi
        except ValueError as e:
            raise RegexUnsupported(f"malformed {{m,n}} quantifier: {e}")

    def _atom(self) -> RNode:
        ch = self._next()
        if ch == "(":
            capturing = True
            if self._peek() == "?":
                # (?:...) ok; lookaround/named groups unsupported
                if self.p[self.i:self.i + 2] == "?:":
                    self.i += 2
                    capturing = False
                else:
                    raise RegexUnsupported("special group")
            if capturing:
                self.ngroups += 1
                gidx = self.ngroups
            node = self._alt()
            if self._next() != ")":
                raise RegexUnsupported("unbalanced group")
            return RGroup(node, gidx) if capturing else node
        if ch == "[":
            return self._char_class()
        if ch == ".":
            return RChars(frozenset(_ALL_BYTES - {10, 13}))
        if ch == "^":
            return RStartAnchor()
        if ch == "$":
            return REndAnchor()
        if ch == "\\":
            return self._escape()
        if ch in "*+?{":
            raise RegexUnsupported(f"dangling quantifier {ch!r}")
        b = ch.encode()
        if len(b) == 1:
            return RChars(frozenset(b))
        # non-ASCII literal: a lead byte doesn't identify the character
        # uniquely under the per-character scan, so reject (host handles it)
        raise RegexUnsupported("non-ASCII literal in pattern")

    def _escape(self) -> RNode:
        ch = self._next()
        if ch is None:
            raise RegexUnsupported("trailing backslash")
        simple = {"d": _CLASS_D, "D": _ALL_BYTES - _CLASS_D,
                  "w": _CLASS_W, "W": _ALL_BYTES - _CLASS_W,
                  "s": _CLASS_S, "S": _ALL_BYTES - _CLASS_S}
        if ch in simple:
            return RChars(frozenset(simple[ch]))
        if ch == "n":
            return RChars(frozenset({10}))
        if ch == "t":
            return RChars(frozenset({9}))
        if ch == "r":
            return RChars(frozenset({13}))
        if ch == "0":
            raise RegexUnsupported("octal escape")
        if ch.isdigit():
            raise RegexUnsupported("backreference")
        if ch in ("p", "P"):
            raise RegexUnsupported("\\p class")
        if ch in ("b", "B", "A", "Z", "z", "G"):
            raise RegexUnsupported(f"\\{ch} boundary")
        b = ch.encode()
        if len(b) != 1:
            raise RegexUnsupported("non-ASCII escape")
        return RChars(frozenset(b))

    def _char_class(self) -> RNode:
        neg = False
        if self._peek() == "^":
            neg = True
            self.i += 1
        accepted: Set[int] = set()
        first = True
        while True:
            ch = self._next()
            if ch is None:
                raise RegexUnsupported("unterminated class")
            if ch == "]" and not first:
                break
            first = False
            if ch == "\\":
                sub = self._escape()
                if not isinstance(sub, RChars):
                    raise RegexUnsupported("class escape")
                accepted |= set(sub.bytes_)
                continue
            b = ch.encode()
            if len(b) != 1:
                raise RegexUnsupported("non-ASCII in class")
            lo = b[0]
            if self._peek() == "-" and self.p[self.i + 1:self.i + 2] not in ("]", ""):
                self.i += 1
                hi_ch = self._next()
                if hi_ch == "\\":
                    hi_node = self._escape()
                    if not isinstance(hi_node, RChars) or len(hi_node.bytes_) != 1:
                        raise RegexUnsupported("bad range end")
                    hi = next(iter(hi_node.bytes_))
                else:
                    hb = hi_ch.encode()
                    if len(hb) != 1:
                        raise RegexUnsupported("non-ASCII range")
                    hi = hb[0]
                accepted |= set(range(lo, hi + 1))
            else:
                accepted.add(lo)
        if neg:
            accepted = set(_ALL_BYTES) - accepted
        return RChars(frozenset(accepted))

    def _peek(self) -> Optional[str]:
        return self.p[self.i] if self.i < len(self.p) else None

    def _next(self) -> Optional[str]:
        ch = self._peek()
        if ch is not None:
            self.i += 1
        return ch


# ---------------------------------------------------------------------------
# host transpile
# ---------------------------------------------------------------------------

def transpile(pattern: str) -> str:
    """Validate ``pattern`` against the supported subset; return a Python
    ``re``-compatible pattern (identical dialect for the subset) or raise
    ``RegexUnsupported`` so tagging falls the expression back."""
    RegexParser(pattern).parse()
    return pattern


# ---------------------------------------------------------------------------
# device NFA
# ---------------------------------------------------------------------------

class _NfaBuilder:
    """Glushkov-style position automaton: one state per RChars occurrence
    (+ start). No epsilon states to eliminate; state count = #char positions."""

    def __init__(self):
        self.accept_sets: List[frozenset] = []   # byte set per state (1-based)

    def new_state(self, bytes_: frozenset) -> int:
        self.accept_sets.append(bytes_)
        return len(self.accept_sets)             # state 0 is start


@dataclasses.dataclass
class _Frag:
    first: Set[int]          # states reachable on first char
    last: Set[int]           # states that can end the match
    nullable: bool
    pairs: Set[Tuple[int, int]]   # follow pairs (a, b): after a comes b


def _build(node: RNode, nb: _NfaBuilder) -> _Frag:
    if isinstance(node, RGroup):    # transparent for matching
        return _build(node.child, nb)
    if isinstance(node, RChars):
        if not node.bytes_:
            raise RegexUnsupported("empty char class")
        s = nb.new_state(node.bytes_)
        return _Frag({s}, {s}, False, set())
    if isinstance(node, RSeq):
        frag = _Frag(set(), set(), True, set())
        for it in node.items:
            if isinstance(it, (RStartAnchor, REndAnchor)):
                raise RegexUnsupported("inner anchor")  # handled at top level
            f = _build(it, nb)
            frag.pairs |= f.pairs
            frag.pairs |= {(a, b) for a in frag.last for b in f.first}
            if frag.nullable:
                frag.first |= f.first
            if f.nullable:
                frag.last |= f.last
            else:
                frag.last = set(f.last)
            frag.nullable = frag.nullable and f.nullable
        return frag
    if isinstance(node, RAlt):
        frags = [_build(o, nb) for o in node.options]
        return _Frag(set().union(*[f.first for f in frags]),
                     set().union(*[f.last for f in frags]),
                     any(f.nullable for f in frags),
                     set().union(*[f.pairs for f in frags]))
    if isinstance(node, RRepeat):
        lo, hi = node.lo, node.hi
        if hi is None:
            if lo == 0:      # e*
                f = _build(node.child, nb)
                f.pairs |= {(a, b) for a in f.last for b in f.first}
                f.nullable = True
                return f
            # e{lo,} = e^(lo-1) e+
            seq = RSeq([node.child] * (lo - 1) + [RRepeat(node.child, 1, None)])
            if lo == 1:       # e+
                f = _build(node.child, nb)
                f.pairs |= {(a, b) for a in f.last for b in f.first}
                return f
            return _build(seq, nb)
        # bounded: expand (keeps state count explicit; guarded by MAX_STATES)
        items: List[RNode] = [node.child] * lo
        items += [RRepeat(node.child, 0, 1)] * (hi - lo)
        if not items:
            return _Frag(set(), set(), True, set())
        if hi == lo and lo == 1:
            return _build(node.child, nb)
        if node.lo == 0 and node.hi == 1:
            f = _build(node.child, nb)
            f.nullable = True
            return f
        return _build(RSeq(items), nb)
    raise RegexUnsupported(f"unsupported node {type(node).__name__}")


class DeviceNfa:
    """Byte-class bitmask NFA runnable on device over (n, w) uint8 matrices."""

    def __init__(self, class_of_byte: np.ndarray, masks: np.ndarray,
                 start_bits: int, accept_bits: int, anchored_start: bool,
                 anchored_end: bool, nullable: bool):
        self.class_of_byte = class_of_byte   # (256,) int32
        self.masks = masks                   # (n_classes, n_states) uint32
        self.start_bits = start_bits
        self.accept_bits = accept_bits
        self.anchored_start = anchored_start
        self.anchored_end = anchored_end
        self.nullable = nullable
        #: every matchable byte < 0x80 — match spans are then char-aligned
        #: on any UTF-8 subject, enabling span extraction/replacement
        self.ascii_only = False
        #: alternation present: NFA longest-match may diverge from Java's
        #: first-alternative backtracking order, so spans stay host-only
        self.has_alt = True
        #: shortest non-empty accepted length (bounds replace output growth)
        self.min_len = 0

    @property
    def spans_supported(self) -> bool:
        """Span extraction (regexp_replace/extract) supported: ASCII-only
        byte classes (char-aligned spans), no alternation (NFA longest ==
        Java greedy order for the remaining subset), non-nullable (no
        empty-match insertion semantics)."""
        return self.ascii_only and not self.has_alt and not self.nullable

    def match_ends(self, xp, values, lengths):
        """Per (row, start byte): longest match END (exclusive), or -1.

        Byte-level stepping — requires ``ascii_only`` so spans cannot split
        a UTF-8 character. O(w^2 * states) work, the static-shape price of
        dynamic match spans (the reference pays the same inside cuDF)."""
        from jax import lax
        v, w = values, values.shape[1]
        n = v.shape[0]
        cls = xp.asarray(self.class_of_byte)[v.astype(xp.int32)]   # (n, w)
        masks = xp.asarray(self.masks)                             # (c, S)
        S = self.masks.shape[1]
        bit = (xp.uint32(1) << xp.arange(S, dtype=xp.uint32))
        accept = xp.uint32(self.accept_bits)
        pos = xp.arange(w, dtype=xp.int32)
        in_str = pos[None, :] < lengths[:, None]

        def step(carry, j):
            states, ends = carry               # (n, w) uint32 / int32
            # open a new match at start position j (column j)
            can_start = in_str[:, j] & ((not self.anchored_start) | (j == 0))
            states = states.at[:, j].set(
                xp.where(can_start, xp.uint32(self.start_bits),
                         xp.uint32(0)))
            m = masks[cls[:, j]]                                 # (n, S)
            hits = (states[:, :, None] & m[:, None, :]) != 0     # (n, w, S)
            nxt = (hits.astype(xp.uint32)
                   * bit[None, None, :]).sum(axis=2, dtype=xp.uint32)
            states = xp.where(in_str[:, j][:, None], nxt, xp.uint32(0))
            done = (states & accept) != 0
            if self.anchored_end:
                done = done & (j == (lengths - 1))[:, None]
            ends = xp.where(done & in_str[:, j][:, None], j + 1, ends)
            return (states, ends), None

        init = (xp.zeros((n, w), dtype=xp.uint32),
                xp.full((n, w), -1, dtype=xp.int32))
        (_, ends), _ = lax.scan(step, init, pos)
        return ends

    def matches(self, ctx, col):
        """col: device EvalCol (string). Returns (n,) bool of find() matches.
        Its ops sit under ``jax.named_scope("like_nfa")`` in the program
        that evaluates it (a fused stage's, for a filter)."""
        import jax
        with jax.named_scope("like_nfa"):
            return self._scan_matches(ctx, col)

    def _factored(self):
        """The transition table as two small tables. Every byte class that
        enters state t comes from the same source states (t's
        predecessors), so ``masks[c, t]`` is either 0 or that set, and one
        step is ``follow(active) & label(byte)``: ``follow`` ORs together
        the successors of the active states, ``label`` is the set of states
        whose byte set holds the byte. -> (successors: (S,) uint32, one
        (states, inclusive byte ranges) a distinct byte set)."""
        masks = self.masks
        S = masks.shape[1]
        preds = np.bitwise_or.reduce(masks, axis=0)
        succ = np.array([sum(1 << t for t in range(S)
                             if (int(preds[t]) >> s) & 1) for s in range(S)],
                        dtype=np.uint32)
        member = masks[self.class_of_byte] != 0              # (256, S)
        sets = {}
        for t in np.flatnonzero(member.any(axis=0)):
            held = member[:, t]
            sets.setdefault(held.tobytes(), [held, 0])[1] |= 1 << int(t)
        groups = []
        for held, states in sets.values():
            edge = np.flatnonzero(np.diff(np.concatenate(
                ([0], held.astype(np.int8), [0]))))
            groups.append((states, list(zip(edge[::2].tolist(),
                                            (edge[1::2] - 1).tolist()))))
        return succ, groups

    def _scan_matches(self, ctx, col):
        """One ``lax.scan`` step a byte column over the transposed (w, n)
        byte matrix, each row's state a uint32 bitmask: the byte's label
        set is computed for the whole matrix up front by range compares
        (no gather), and a step is S masked ORs over (n,) vectors."""
        xp = ctx.xp
        from jax import lax
        v, lengths = col.values, col.lengths
        n, w = v.shape
        succ, groups = self._factored()
        start = xp.uint32(self.start_bits)
        accept = xp.uint32(self.accept_bits)
        vt = v.T                                                   # (w, n)
        labels = xp.zeros((w, n), dtype=xp.uint32)
        for states, ranges in groups:
            held = True if ranges == [(0, 255)] else functools.reduce(
                xp.logical_or, [vt == lo if lo == hi
                                else xp.logical_and(vt >= lo, vt <= hi)
                                for lo, hi in ranges])
            labels = labels | xp.where(held, xp.uint32(states), xp.uint32(0))
        follows = [(xp.uint32(1 << s), xp.uint32(int(to)))
                   for s, to in enumerate(succ) if to]
        pos_in = xp.arange(w, dtype=xp.int32)[:, None]

        # per-character stepping: continuation bytes leave the state untouched
        lead_in = xp.logical_and((vt & 0xC0) != 0x80,
                                 pos_in < lengths[None, :])
        # position of the final character's lead byte (for $ anchoring)
        any_lead = xp.any(lead_in, axis=0)
        last_lead = w - 1 - xp.argmax(lead_in[::-1, :], axis=0)
        is_last_char = xp.logical_and(
            lead_in, pos_in == last_lead[None, :])
        is_last_char = xp.logical_and(is_last_char, any_lead[None, :])

        def step(carry, x):
            active, matched = carry
            label, inside, last = x
            follow = xp.zeros_like(active)
            for bit, to in follows:
                follow = follow | xp.where((active & bit) != 0, to,
                                           xp.uint32(0))
            nxt = follow & label
            if not self.anchored_start:
                nxt = nxt | start                 # restart a match anywhere
            active = xp.where(inside, nxt, active)
            done = (active & accept) != 0
            # anchored: the match must consume through the final character
            at = last if self.anchored_end else inside
            matched = xp.where(at, xp.logical_or(matched, done), matched)
            return (active, matched), None

        empty_match = xp.full((n,), self.nullable, dtype=bool)
        if self.anchored_end and not self.nullable:
            empty_match = xp.zeros((n,), dtype=bool)
        matched0 = xp.where(lengths == 0, empty_match,
                            xp.full((n,), self.nullable and not self.anchored_end,
                                    dtype=bool))
        init = (xp.full((n,), self.start_bits, dtype=xp.uint32), matched0)
        (active, matched), _ = lax.scan(step, init,
                                        (labels, lead_in, is_last_char))
        if self.anchored_end:
            matched = xp.logical_or(
                matched, xp.logical_and(lengths == 0,
                                        xp.full((n,), self.nullable, dtype=bool)))
        return matched


def compile_device_nfa(pattern: str) -> Optional[DeviceNfa]:
    """Compile ``pattern`` to a DeviceNfa, or None when outside the subset."""
    try:
        parser = RegexParser(pattern)
        ast = parser.parse()
    except RegexUnsupported:
        return None
    # peel top-level anchors
    anchored_start = anchored_end = False
    if isinstance(ast, RSeq):
        items = list(ast.items)
        if items and isinstance(items[0], RStartAnchor):
            anchored_start = True
            items = items[1:]
        if items and isinstance(items[-1], REndAnchor):
            anchored_end = True
            items = items[:-1]
        ast = RSeq(items)
    try:
        nb = _NfaBuilder()
        frag = _build(ast, nb)
    except RegexUnsupported:
        return None
    n_states = len(nb.accept_sets) + 1          # + start state 0
    if n_states > MAX_STATES:
        return None
    # byte equivalence classes
    sets = nb.accept_sets
    sig = np.zeros((256, len(sets)), dtype=bool)
    for si, bs in enumerate(sets):
        for b in bs:
            sig[b, si] = True
    from ..shims import get_shims
    _, _, class_of_byte = get_shims().unique_rows(sig)
    n_classes = class_of_byte.max() + 1
    # transition masks: masks[c, t] = bitmask of source states from which we
    # reach state t on a byte of class c
    follow = {}
    for (a, b) in frag.pairs:
        follow.setdefault(b, set()).add(a)
    for b in frag.first:
        follow.setdefault(b, set()).add(0)
    masks = np.zeros((n_classes, n_states), dtype=np.uint32)
    rep_byte_of_class = {}
    for byte in range(256):
        rep_byte_of_class.setdefault(class_of_byte[byte], byte)
    for c in range(n_classes):
        byte = rep_byte_of_class[c]
        for t in range(1, n_states):
            if byte in sets[t - 1]:
                srcs = follow.get(t, set())
                m = 0
                for s in srcs:
                    m |= (1 << s)
                masks[c, t] = m
    accept_bits = 0
    for s in frag.last:
        accept_bits |= (1 << s)
    nfa = DeviceNfa(class_of_byte.astype(np.int32), masks,
                    start_bits=1, accept_bits=accept_bits,
                    anchored_start=anchored_start, anchored_end=anchored_end,
                    nullable=frag.nullable)
    nfa.ascii_only = all(max(bs, default=0) < 0x80 for bs in sets)
    nfa.has_alt = _contains_alt(ast) or parser.saw_lazy
    nfa.min_len = _nfa_min_len(frag, len(sets))
    return nfa


def _contains_alt(node: RNode) -> bool:
    if isinstance(node, RAlt):
        return True
    if isinstance(node, RSeq):
        return any(_contains_alt(i) for i in node.items)
    if isinstance(node, RRepeat):
        return _contains_alt(node.child)
    if isinstance(node, RGroup):
        return _contains_alt(node.child)
    return False


def _nfa_min_len(frag: _Frag, n_positions: int) -> int:
    """Shortest accepted string length (Bellman-Ford over follow pairs)."""
    if frag.nullable:
        return 0
    INF = n_positions + 2
    dist = [INF] * (n_positions + 1)
    for s in frag.first:
        dist[s] = 1
    for _ in range(n_positions):
        changed = False
        for (a, b) in frag.pairs:
            if dist[a] + 1 < dist[b]:
                dist[b] = dist[a] + 1
                changed = True
        if not changed:
            break
    best = min((dist[s] for s in frag.last), default=INF)
    return max(1, best if best < INF else 1)


# ---------------------------------------------------------------------------
# Match-span machinery (device regexp_replace / regexp_extract / replace):
# select leftmost non-overlapping spans, then re-emit bytes around them.
# ---------------------------------------------------------------------------
def select_leftmost_spans(xp, ends, lengths):
    """ends: (n, w) longest-match end per start (or -1). Returns
    (start_mask, in_match): leftmost non-overlapping selection, the order
    Java Matcher.find() visits matches."""
    from jax import lax
    n, w = ends.shape
    pos = xp.arange(w, dtype=xp.int32)

    def step(carry, j):
        next_allowed = carry
        start = xp.logical_and(ends[:, j] >= 0, j >= next_allowed)
        next_allowed = xp.where(start, ends[:, j], next_allowed)
        in_match = j < next_allowed
        return next_allowed, (start, in_match)

    _, (starts, in_match) = lax.scan(
        step, xp.zeros(n, dtype=xp.int32), pos)
    return starts.T, in_match.T        # scan stacks along axis 0


def replace_by_spans(xp, values, lengths, start_mask, in_match,
                     repl: bytes, out_w: int):
    """Emit input bytes with each selected span replaced by ``repl``.
    -> (out (n, out_w) uint8, out_lengths). Spans must be non-empty."""
    from jax import lax
    n, w = values.shape
    rows = xp.arange(n)
    pos = xp.arange(w, dtype=xp.int32)
    in_str = pos[None, :] < lengths[:, None]
    L = len(repl)

    def step(carry, j):
        out, cursor = carry
        start = start_mask[:, j]
        # replacement emission: writes land at >= cursor, which is beyond
        # any finalized content, so non-start rows' dummy writes are
        # overwritten by their later real writes (or stay as padding)
        for k in range(L):
            idx = xp.clip(cursor + k, 0, out_w - 1)
            byte = xp.where(start, xp.uint8(repl[k]), out[rows, idx])
            out = out.at[rows, idx].set(byte)
        cursor = xp.where(start, cursor + L, cursor)
        copy = xp.logical_and(in_str[:, j],
                              xp.logical_not(in_match[:, j]))
        idx = xp.clip(cursor, 0, out_w - 1)
        byte = xp.where(copy, values[:, j], out[rows, idx])
        out = out.at[rows, idx].set(byte)
        cursor = xp.where(copy, cursor + 1, cursor)
        return (out, cursor), None

    init = (xp.zeros((n, out_w), dtype=xp.uint8),
            xp.zeros(n, dtype=xp.int32))
    (out, cursor), _ = lax.scan(step, init, pos)
    return out, cursor


def extract_first_span(xp, values, lengths, ends):
    """First (leftmost) match span copied to column 0; no match -> ''.
    -> (out (n, w) uint8, out_lengths)."""
    n, w = values.shape
    valid = ends >= 0
    found = xp.any(valid, axis=1)
    s = xp.argmax(valid, axis=1).astype(xp.int32)
    e = xp.take_along_axis(ends, s[:, None], axis=1)[:, 0]
    out_len = xp.where(found, e - s, 0)
    k = xp.arange(w, dtype=xp.int32)
    idx = xp.clip(s[:, None] + k[None, :], 0, w - 1)
    out = xp.take_along_axis(values, idx, axis=1)
    out = xp.where(k[None, :] < out_len[:, None], out, 0).astype(xp.uint8)
    return out, out_len


def literal_match_ends(xp, values, lengths, search: bytes):
    """ends matrix for a literal byte-string search (StringReplace)."""
    n, w = values.shape
    L = len(search)
    pos = xp.arange(w, dtype=xp.int32)
    match = xp.ones((n, w), dtype=bool)
    for k in range(L):
        idx = xp.clip(pos[None, :] + k, 0, w - 1)
        byte = xp.take_along_axis(values, xp.broadcast_to(idx, (n, w)),
                                  axis=1)
        match = xp.logical_and(match, byte == search[k])
    fits = (pos[None, :] + L) <= lengths[:, None]
    match = xp.logical_and(match, fits)
    return xp.where(match, pos[None, :] + L, -1).astype(xp.int32)


# ---------------------------------------------------------------------------
# Capture groups (reference: CudfRegexTranspiler keeps capture groups in the
# transpiled pattern, RegexParser.scala:414; cuDF extracts them natively).
# The TPU-native equivalent: for the deterministic no-alternation subset the
# pattern linearizes into charset items; after the NFA finds the match span,
# a vectorized greedy walk over the items recovers every group boundary —
# no per-row control flow, one (rows x width) pass per item.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class GroupPlan:
    """Linearized pattern: items are (charset, lo, hi); groups maps group
    index -> [first_item, end_item) ranges over ``items``."""
    items: List[Tuple[frozenset, int, Optional[int]]]
    groups: dict
    ngroups: int


def _linearize(node: RNode, items: List, groups: dict,
               in_group: Optional[int]) -> None:
    if isinstance(node, RSeq):
        for it in node.items:
            _linearize(it, items, groups, in_group)
        return
    if isinstance(node, RGroup):
        if in_group is not None:
            raise RegexUnsupported("nested capture group")
        start = len(items)
        _linearize(node.child, items, groups, node.index)
        groups[node.index] = (start, len(items))
        return
    if isinstance(node, RChars):
        items.append((node.bytes_, 1, 1))
        return
    if isinstance(node, RRepeat):
        if not isinstance(node.child, RChars):
            raise RegexUnsupported("repeat over a non-class in group plan")
        items.append((node.child.bytes_, node.lo, node.hi))
        return
    raise RegexUnsupported(f"group plan: {type(node).__name__}")


def compile_group_plan(pattern: str) -> Optional[GroupPlan]:
    """Linearize ``pattern`` for device capture-group extraction, or None.

    Subset: no alternation/lazy, ASCII-only classes (char-aligned spans),
    non-nullable, groups flat (not nested, not repeated), and greedy
    consumption DETERMINISTIC: every variable-length item's charset is
    disjoint from the first-sets of the items that may follow it up to and
    including the next mandatory item — under that condition the greedy
    left-to-right walk reproduces Java's backtracking parse exactly."""
    try:
        parser = RegexParser(pattern)
        ast = parser.parse()
    except RegexUnsupported:
        return None
    if parser.saw_lazy or parser.ngroups == 0 or _contains_alt(ast):
        return None
    if isinstance(ast, RSeq):
        its = list(ast.items)
        if its and isinstance(its[0], RStartAnchor):
            its = its[1:]
        if its and isinstance(its[-1], REndAnchor):
            its = its[:-1]
        ast = RSeq(its)
    items: List[Tuple[frozenset, int, Optional[int]]] = []
    groups: dict = {}
    try:
        _linearize(ast, items, groups, None)
    except RegexUnsupported:
        return None
    if not items or all(lo == 0 for _, lo, _ in items):
        return None                       # nullable: empty-match semantics
    for cs, _, _ in items:
        if not cs or max(cs) >= 0x80:
            return None                   # spans must stay char-aligned
    # determinism of greedy consumption
    for i, (cs, lo, hi) in enumerate(items):
        if hi is not None and hi == lo:
            continue                      # fixed width: nothing to choose
        for cs2, lo2, _ in items[i + 1:]:
            if cs & cs2:
                return None
            if lo2 >= 1:
                break                     # first mandatory follower reached
    return GroupPlan(items, groups, parser.ngroups)


def parse_replacement_template(repl: str, ngroups: int):
    """Java Matcher.appendReplacement template -> segment list
    [('lit', bytes) | ('grp', int)], or None if un-parsable.

    ``$`` followed by digits is a group reference (digits consumed
    greedily while the number still names an existing group, Java
    semantics); ``\\`` escapes the next character (``\\$`` is a literal
    dollar). Group 0 is the whole match. (reference:
    GpuRegExpReplace with group refs, stringFunctions.scala:895.)"""
    segs = []
    lit = bytearray()
    i = 0
    while i < len(repl):
        ch = repl[i]
        if ch == "\\":
            if i + 1 >= len(repl):
                return None
            lit += repl[i + 1].encode()
            i += 2
            continue
        if ch == "$":
            j = i + 1
            if j >= len(repl) or not repl[j].isdigit():
                return None               # bare $: Java throws
            g = 0
            k = j
            while k < len(repl) and repl[k].isdigit():
                cand = g * 10 + int(repl[k])
                if cand > ngroups and k > j:
                    break
                if cand > ngroups:
                    return None           # first digit already invalid
                g = cand
                k += 1
            if lit:
                segs.append(("lit", bytes(lit)))
                lit = bytearray()
            segs.append(("grp", g))
            i = k
            continue
        lit += ch.encode()
        i += 1
    if lit:
        segs.append(("lit", bytes(lit)))
    return segs


def _greedy_walk_bounds(xp, values, lengths, plan: GroupPlan, pos):
    """Vectorized greedy item walk from start positions ``pos`` (n, k).
    Returns the bounds list: bounds[i] is the position after item i-1.
    The ONE implementation of the deterministic greedy consumption —
    extract_group_span (k=1) and the all-starts replace path (k=w) both
    run through it."""
    from jax import lax
    n, w = values.shape
    idxs = xp.arange(w, dtype=xp.int32)
    vi = values.astype(xp.int32)
    in_str = idxs[None, :] < lengths[:, None]
    bounds = [pos]
    for cs, lo, hi in plan.items:
        lut = np.zeros(256, dtype=bool)
        lut[list(cs)] = True
        member = xp.logical_and(xp.asarray(lut)[vi], in_str)
        bad_at = xp.where(member, w, idxs[None, :])
        nb = lax.associative_scan(xp.minimum, bad_at[:, ::-1],
                                  axis=1)[:, ::-1]
        next_bad = xp.take_along_axis(nb, xp.clip(pos, 0, w - 1), axis=1)
        avail = xp.maximum(next_bad - pos, 0)
        take = avail if hi is None else xp.minimum(avail, hi)
        pos = (pos + take).astype(xp.int32)
        bounds.append(pos)
    return bounds


def group_bounds_all_starts(xp, values, lengths, plan: GroupPlan):
    """Greedy-walk group bounds for EVERY potential match start j.
    -> {g: (GS, GE)} with (n, w) int32 matrices: the bounds of group g
    for a match beginning at column j. Only meaningful where the NFA
    reported a match at j (same deterministic-subset contract as
    extract_group_span)."""
    n, w = values.shape
    idxs = xp.arange(w, dtype=xp.int32)
    pos = xp.broadcast_to(idxs[None, :], (n, w))
    bounds = _greedy_walk_bounds(xp, values, lengths, plan, pos)
    return {g: (bounds[lo_i], bounds[hi_i])
            for g, (lo_i, hi_i) in plan.groups.items()}


def replace_by_template(xp, values, lengths, start_mask, in_match, ends,
                        segments, group_bounds, out_w: int):
    """replace_by_spans generalized to a segment template: literals are
    emitted verbatim, group segments copy that match's captured span from
    the input. -> (out (n, out_w) uint8, out_lengths)."""
    from jax import lax
    n, w = values.shape
    rows = xp.arange(n)
    pos = xp.arange(w, dtype=xp.int32)
    in_str = pos[None, :] < lengths[:, None]

    def emit_group(out, cursor, start, gs, ge):
        glen = xp.where(start, xp.maximum(ge - gs, 0), 0)

        def body(k, out_):
            src = xp.clip(gs + k, 0, w - 1)
            byte = values[rows, src]
            idx = xp.clip(cursor + k, 0, out_w - 1)
            keep = xp.logical_and(start, k < glen)
            return out_.at[rows, idx].set(
                xp.where(keep, byte, out_[rows, idx]))
        out = lax.fori_loop(0, w, body, out)
        return out, cursor + glen

    def step(carry, j):
        out, cursor = carry
        start = start_mask[:, j]
        for kind, payload in segments:
            if kind == "lit":
                for k in range(len(payload)):
                    idx = xp.clip(cursor + k, 0, out_w - 1)
                    byte = xp.where(start, xp.uint8(payload[k]),
                                    out[rows, idx])
                    out = out.at[rows, idx].set(byte)
                cursor = xp.where(start, cursor + len(payload), cursor)
            else:
                g = payload
                if g == 0:                 # whole match: [j, ends[:, j])
                    gs = xp.broadcast_to(j, (n,)).astype(xp.int32)
                    ge = xp.maximum(ends[:, j], 0)
                else:
                    gs = group_bounds[g][0][:, j]
                    ge = group_bounds[g][1][:, j]
                out, cursor = emit_group(out, cursor, start, gs, ge)
        copy = xp.logical_and(in_str[:, j],
                              xp.logical_not(in_match[:, j]))
        idx = xp.clip(cursor, 0, out_w - 1)
        byte = xp.where(copy, values[:, j], out[rows, idx])
        out = out.at[rows, idx].set(byte)
        cursor = xp.where(copy, cursor + 1, cursor)
        return (out, cursor), None

    init = (xp.zeros((n, out_w), dtype=xp.uint8),
            xp.zeros(n, dtype=xp.int32))
    (out, cursor), _ = lax.scan(step, init, pos)
    return out, cursor


def extract_group_span(xp, values, lengths, ends, plan: GroupPlan,
                       gidx: int):
    """Extract capture group ``gidx`` of the leftmost match per row.
    -> (out (n, w) uint8, out_lengths). No match -> ''."""
    n, w = values.shape
    valid = ends >= 0
    found = xp.any(valid, axis=1)
    start = xp.argmax(valid, axis=1).astype(xp.int32)
    bounds = _greedy_walk_bounds(xp, values, lengths, plan,
                                 start[:, None])
    lo_i, hi_i = plan.groups[gidx]
    gs = bounds[lo_i][:, 0]
    ge = bounds[hi_i][:, 0]
    out_len = xp.where(found, xp.maximum(ge - gs, 0), 0).astype(xp.int32)
    k = xp.arange(w, dtype=xp.int32)
    src = xp.clip(gs[:, None] + k[None, :], 0, w - 1)
    out = xp.take_along_axis(values, src, axis=1)
    out = xp.where(k[None, :] < out_len[:, None], out, 0).astype(xp.uint8)
    return out, out_len
