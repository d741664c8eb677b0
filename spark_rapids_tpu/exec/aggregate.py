"""Device hash aggregate (reference: aggregate.scala — GpuHashAggregateIterator
at :181, partial/final projections at :193-208, GpuHashAggregateExec at :1319).

TPU-first re-design: cuDF's hash-based groupby assumes dynamic output sizes;
XLA wants static shapes. We use a **sort-based groupby** entirely inside one
jitted computation:

    lexsort rows by (active, key nulls, key values)   -> equal keys adjacent
    boundary flags -> segment ids (cumsum)            -> static capacity
    jax.ops.segment_{sum,min,max} reductions          -> per-group states
    representative-row gather                         -> group key columns

Output capacity == input capacity (groups <= rows), so the whole kernel is one
static-shape XLA program that fuses with upstream project/filter. Grouped
float keys are normalized (-0.0 -> +0.0, NaNs equal) matching Spark's
NormalizeFloatingNumbers pass.

Per-batch partial aggregation emits one aggregated batch per input batch; the
exchange + final merge reduce across batches/partitions exactly like the
reference's merge passes.
"""
from __future__ import annotations

from typing import Callable, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..columnar import dtypes as dt
from ..columnar.device import DeviceColumn, DeviceTable
from ..conf import register_conf
from ..expr.hashing import float_word_bits
from ..plan.physical import AggSpec, PhysicalPlan
from ..plan.schema import Field, Schema
from ..utils import metrics as M
from ..utils.tracing import get_tracer
from .base import TpuExec

__all__ = ["TpuHashAggregateExec", "fused_grouped_aggregate",
           "passed_through", "SKIP_SHARE"]

_BIG = np.int64(2**62)
_BIG32 = np.int32(2**31 - 1)


def _minmax_identity(xp_dtype, for_min: bool):
    if xp_dtype == jnp.bool_:
        return True if for_min else False
    info = jnp.finfo(xp_dtype) if jnp.issubdtype(xp_dtype, jnp.floating) \
        else jnp.iinfo(xp_dtype)
    return info.max if for_min else info.min


def _normalize_float_key(v: jax.Array) -> jax.Array:
    if jnp.issubdtype(v.dtype, jnp.floating):
        v = jnp.where(v == 0, jnp.zeros_like(v), v)
    return v


def _key_code_words(kc) -> "Tuple[List[jax.Array], Optional[jax.Array]]":
    """Column -> (1-D surrogate sort/equality words most-significant first,
    optional NaN flag).

    Strings/binary pack 8 bytes per uint64 word big-endian, plus the length
    as the final tiebreak word — zero padding would otherwise conflate
    "ab" with "ab\\x00". Word-wise unsigned order == lexicographic byte
    order, so device groupby/sort accept string keys of ANY width without a
    dictionary pass (the reference relies on cudf's native string keys;
    SURVEY §7 hard part (b))."""
    from ..columnar.device import pack_string_key_words
    if isinstance(kc.dtype, (dt.StringType, dt.BinaryType)):
        return pack_string_key_words(kc.data, kc.lengths), None
    if isinstance(kc.dtype, dt.StructType):
        # struct keys: concatenate each field's surrogate words, folding
        # the per-field null and NaN flags in as words of their own —
        # equality over the flattened word list == struct equality
        # (reference: struct group-by keys, TypeChecks.scala:166 nesting)
        words: "List[jax.Array]" = []
        for child in kc.children:
            words.append(jnp.logical_not(child.validity))
            cw, nan = _key_code_words(child)
            # zero the value words of null fields so all null-field rows
            # group together regardless of the plane's stale contents
            words.extend(jnp.where(child.validity, w,
                                   jnp.zeros_like(w)) for w in cw)
            if nan is not None:
                words.append(jnp.logical_and(nan, child.validity))
        return words, None
    if dt.is_d128(kc.dtype):
        from ..expr.decimal128 import d128_key_words
        return d128_key_words(kc.data), None
    v = _normalize_float_key(kc.data)
    if jnp.issubdtype(v.dtype, jnp.floating):
        nan = jnp.isnan(v)
        return [jnp.where(nan, jnp.full_like(v, jnp.inf), v)], nan
    return [v], None


def _key_small_fields(kc):
    """Column -> (value words, [(small_field, nbits), ...]) where the small
    fields (string lengths, null/NaN flags) are equality-relevant but only
    need a few bits each — the caller bit-packs them into shared meta
    words so the lexsort runs over FAR fewer operands (sort cost scales
    with operand count; Q1's 2 string keys drop from 7 operands to 3).
    Value words are zeroed on null rows so null-key groups can't split on
    stale plane contents."""
    from ..columnar.device import pack_string_key_words
    valid = kc.validity
    smalls = [(jnp.logical_not(valid).astype(jnp.uint64), 1)]

    def z(w):
        return jnp.where(valid, w, jnp.zeros_like(w))

    if isinstance(kc.dtype, (dt.StringType, dt.BinaryType)):
        w = kc.data.shape[1]
        words = [z(x) for x in
                 pack_string_key_words(kc.data, kc.lengths)[:-1]]
        lbits = max(int(w).bit_length(), 1)
        smalls.append((z(kc.lengths.astype(jnp.uint64)), lbits))
        return words, smalls
    words, nan = _key_code_words(kc)
    words = [z(x) for x in words]
    if nan is not None:
        smalls.append((jnp.logical_and(nan, valid).astype(jnp.uint64), 1))
    return words, smalls


def _pack_meta_words(bit_fields) -> "List[jax.Array]":
    """[(u64 field, nbits), ...] -> u64 words, most-significant field
    first; a new word starts when 64 bits fill up. Equality over the words
    == equality over the fields, and the FIRST field occupies the top bits
    of word 0 (so making it the not-active flag keeps active rows sorted
    first)."""
    words: "List[jax.Array]" = []
    acc = None
    used = 0
    for field, nbits in bit_fields:
        if acc is None or used + nbits > 64:
            if acc is not None:
                words.append(acc << jnp.uint64(64 - used))
            acc = field
            used = nbits
        else:
            acc = (acc << jnp.uint64(nbits)) | field
            used += nbits
    if acc is not None:
        words.append(acc << jnp.uint64(64 - used))
    return words


def _keys_equal_prev(sv: jax.Array) -> jax.Array:
    """eq[i] = sv[i] == sv[i-1] (with NaN==NaN); eq[0] = False."""
    prev = jnp.roll(sv, 1, axis=0)
    eq = sv == prev
    if jnp.issubdtype(sv.dtype, jnp.floating):
        eq = jnp.logical_or(eq, jnp.logical_and(jnp.isnan(sv), jnp.isnan(prev)))
    return eq.at[0].set(False) if eq.ndim == 1 else eq


#: Most groups a batch may have for ``grouped`` to reduce it group by group
#: with dense masked reductions instead of scatters into ``capacity``
#: segments; the device picks the branch from the batch's own group count.
#: The dense branch costs one streaming pass over the batch per live group,
#: a 64-bit scatter 78-122 ms per buffer per 2^20 rows whatever the groups
#: (an int32 one 7 ms: the scatter branch's counts and positions, PERF.md
#: section 6, PR 43); fixed from the chip readings in PERF.md section 6
#: (PR 31), where the dense branch at its worst (this many groups, 2^20
#: rows, Q1's 11 buffers) takes under a quarter of the scatter branch's
#: time, as the branch was then: all 19 of its reductions over Q1's batch
#: scattered in 64 bits, where the 7 sums alone still do.
FEW_GROUPS = 16


def _seg_sum(x, gid, cap):
    """segment_sum that lowers to a plain reduce when there is one segment
    (a scatter-add over a single bucket is a serial loop on XLA:CPU and
    wasted scatter traffic everywhere; the ungrouped aggregate hits this
    on every batch)."""
    if cap == 1:
        return jnp.sum(x, axis=0, keepdims=True)
    return jax.ops.segment_sum(x, gid, num_segments=cap)


def _seg_min(x, gid, cap):
    if cap == 1:
        return jnp.min(x, axis=0, keepdims=True)
    return jax.ops.segment_min(x, gid, num_segments=cap)


def _seg_max(x, gid, cap):
    if cap == 1:
        return jnp.max(x, axis=0, keepdims=True)
    return jax.ops.segment_max(x, gid, num_segments=cap)


def _chosen_row(op: str, contrib: jax.Array, gid: jax.Array, cap: int,
                pos: jax.Array, rows: int) -> jax.Array:
    """Each segment's first (``first``) or last (``last``) contributing
    row, as int32 indices into ``rows`` rows; a segment with none gives an
    index in range whose value its validity hides."""
    big = _BIG if cap == 1 else _BIG32
    p = jnp.where(contrib, -pos if op == "last" else pos,
                  jnp.full_like(pos, big))
    best = _seg_min(p, gid, cap)
    idx = -best if op == "last" else best
    return jnp.clip(idx, 0, rows - 1).astype(jnp.int32)


def _reduce_segment(op: str, vals: jax.Array, contrib: jax.Array,
                    gid: jax.Array, cap: int, pos: jax.Array,
                    out_dt: dt.DataType) -> Tuple[jax.Array, jax.Array]:
    """Per-group reduction -> (values[cap], validity[cap]).

    Into ``cap > 1`` segments the row counts and the ``first`` / ``last``
    positions are reduced in int32: both are integers below ``cap``, exact
    in 32 bits, and a 32-bit scatter is the chip's native one where a
    64-bit scatter lowers to a variadic scatter over pairs of 32-bit words
    (see ``FEW_GROUPS`` for the costs). ``pos`` is then int32 too. The
    values keep their own dtype: their sums, minima and maxima are the
    aggregate's answer at the precision the plan asked for. Into one
    segment every reduction is a plain reduce and counts in int64."""
    out_dtype = jnp.dtype(np.bool_ if isinstance(out_dt, dt.BooleanType)
                          else out_dt.np_dtype())
    counts = _seg_sum(contrib.astype(jnp.int64 if cap == 1 else jnp.int32),
                      gid, cap)
    has = counts > 0
    if op == "count":
        return counts.astype(out_dtype), jnp.ones(cap, dtype=bool)
    if dt.is_d128(out_dt):
        from ..expr.decimal128 import d128_from_i64, d128_segment_sum
        if op == "sum":
            limbs = vals if vals.ndim == 2 else d128_from_i64(vals)
            out, over = d128_segment_sum(limbs, contrib, gid, cap,
                                         out_dt.precision)
            return out, jnp.logical_and(has, jnp.logical_not(over))
        if op in ("first", "last"):
            idx = _chosen_row(op, contrib, gid, cap, pos, vals.shape[0])
            return jnp.take(vals, idx, axis=0), has
        raise TypeError(f"decimal128 aggregate op {op!r} is host-only")
    if op in ("sum", "sumsq"):
        x = vals.astype(out_dtype)
        if op == "sumsq":
            x = x * x
        x = jnp.where(contrib, x, jnp.zeros_like(x))
        return _seg_sum(x, gid, cap), has
    if op == "min" or op == "max":
        ident = _minmax_identity(vals.dtype, op == "min")
        x = vals
        isfloat = jnp.issubdtype(vals.dtype, jnp.floating)
        if isfloat:
            # Spark total order: NaN is the largest double
            nan = jnp.isnan(vals)
            x = jnp.where(nan, jnp.full_like(vals, jnp.inf if op == "min"
                                             else -jnp.inf), vals)
        x = jnp.where(contrib, x, jnp.full_like(x, ident))
        red = _seg_min if op == "min" else _seg_max
        out = red(x, gid, cap)
        if isfloat:
            nan_contrib = jnp.logical_and(contrib, nan)
            nan_counts = _seg_sum(nan_contrib.astype(jnp.int32), gid, cap)
            if op == "min":
                nonnan = _seg_sum(
                    jnp.logical_and(contrib, jnp.logical_not(nan)).astype(jnp.int32),
                    gid, cap)
                out = jnp.where(jnp.logical_and(has, nonnan == 0),
                                jnp.full_like(out, jnp.nan), out)
            else:
                out = jnp.where(nan_counts > 0, jnp.full_like(out, jnp.nan), out)
        return out.astype(out_dtype), has
    if op in ("first", "last"):
        idx = _chosen_row(op, contrib, gid, cap, pos, vals.shape[0])
        return jnp.take(vals, idx, axis=0).astype(out_dtype), has
    if op == "any":
        x = jnp.where(contrib, vals, jnp.zeros_like(vals))
        return _seg_max(x.astype(jnp.int32), gid, cap).astype(bool), has
    if op == "all":
        x = jnp.where(contrib, vals, jnp.ones_like(vals))
        return _seg_min(x.astype(jnp.int32), gid, cap).astype(bool), has
    raise ValueError(op)


_COLLECT_OPS = frozenset(
    {"collect_list", "collect_set", "merge_lists", "merge_sets"})
#: capacity at which ``execute_columnar`` aggregates the child batches it
#: has staged as one chunk; chunks beyond the first merge into the running
#: state one by one (span ``agg.merge``). A merge step re-aggregates the
#: whole running state, so a state that barely reduces pays for every step
#: at the state's capacity: with 2^20-row chunks TPC-H Q18's 4.4 M partial
#: rows (1.47 M groups) took six chunk aggregates and five merge steps,
#: 24 x 2^20 rows of aggregate for 16.0 s a query on one v5e; at 2^22 two
#: chunks and one step, 10 x 2^20 (PERF.md section 6, PR 34)
_CHUNK_ROWS = 1 << 22

#: a grouped partial aggregate is skipped for the rest of a partition when
#: the first batch it reduced kept more than 1/``SKIP_SHARE`` of that
#: batch's live rows as groups (``skips``; Spark's skipPartialAggregate).
#: The partial costs a whole grouped aggregate at its input's capacity, and
#: what it buys is a smaller state downstream, where every program (the
#: exchange's compaction and count, the final aggregate's chunks and merge
#: steps) costs by the power-of-two bucket of the live rows. Keeping more
#: than half of the rows leaves the state's bucket at least half the
#: input's, and at a full batch the same one: TPC-H Q18's ``l_orderkey``
#: keeps 754 k of 1,048,576 rows, and its partial, 3.0 s a query on one
#: v5e, bought no smaller chunk in the final aggregate (PERF.md sections 5
#: and 6, PR 38 and PR 39). Derived, not tuned: a knob would stand for a
#: property of the data, which the first batch shows.
SKIP_SHARE = 2
#: update ops whose partial state of ONE row is a row-wise projection of
#: the row (``passthrough_fn``): the value, its square or the row's
#: contribution, valid where the row contributes
_PASSTHROUGH_OPS = frozenset(
    {"sum", "sumsq", "count", "min", "max", "first", "last"})


def _word_bits_u32(w: jax.Array) -> jax.Array:
    """Equality word -> u32 hash contribution: equal values give equal
    bits (a float word by ``expr/hashing.py`` ``float_word_bits``, which
    the hash partitioner shares)."""
    if jnp.issubdtype(w.dtype, jnp.floating):
        return float_word_bits(jnp, w)
    if w.dtype == jnp.bool_:
        return w.astype(jnp.uint32)
    u = w.astype(jnp.uint64)
    return (u & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32) \
        ^ (u >> jnp.uint64(32)).astype(jnp.uint32)


#: the bucket-resolve loop runs full rounds while more than this share of
#: the capacity is open, then one compaction and tail rounds that long. The
#: joins' walks switch at a sixteenth (``exec/joins.py``): they take ~10
#: tail rounds, a slot a round, so a short tail pays. This loop takes 2-3 (a
#: round resolves a key a BUCKET), so a tail twice as long costs it little
#: and a full round saved is worth 4-20 tail rounds: a final aggregate's
#: 2^22-row chunk — a concat of batches, two thirds live, a third as many
#: keys as buckets — leaves a tenth of its capacity open after round 1 and
#: runs one full round at 8 where 16 runs two (536 against 870 ms on the
#: chip; over a query of ``sf1.q18`` 8 beats 16 by 16-19 % of the loop:
#: PERF.md section 6, PR 35)
_TAIL_SHARE = 8
#: buckets a tail row: a quarter of the load saves one tail round of three
#: on every measured shape (PERF.md section 6, PR 35)
_TAIL_BUCKETS = 4


def _hashed_key_words(table: "DeviceTable", key_names: List[str]
                      ) -> "Tuple[jax.Array, List[jax.Array]]":
    """-> (u32 hash a row, the equality words it was taken over: every key
    column's value words, then the null / NaN / length flags bit-packed
    into shared meta words)."""
    from ..shuffle.manager import _fmix_device
    bit_fields = []
    value_words: List[jax.Array] = []
    for k in key_names:
        words, smalls = _key_small_fields(table.column(k))
        value_words.extend(words)
        bit_fields.extend(smalls)
    words = value_words + _pack_meta_words(bit_fields)
    with jax.named_scope("groupby_key_hash"):
        h = jnp.zeros(table.capacity, dtype=jnp.uint32)
        for i, w in enumerate(words):
            h = h ^ _fmix_device(_word_bits_u32(w) ^ jnp.uint32(i + 1))
            h = h * jnp.uint32(5) + jnp.uint32(0xE6546B64)
    return h, words


def _resolve_buckets(h: jax.Array, words: List[jax.Array],
                     active: jax.Array):
    """The bucket-resolve loops of ``_hash_group_ids`` alone: rows of key
    hashes ``h`` and key words ``words`` -> (winner = each active row's
    representative, the lowest active row of its key class; rounds;
    full_rounds). The tail loop is not free to a batch that skips it: on
    the chip its buffers take fast memory from the full rounds' fusions in
    the same program (Q1's one round a batch reads +14 %: PERF.md section
    6, PR 35)."""
    from ..columnar.device import open_rows_by_rank
    from ..shuffle.manager import _fmix_device
    cap = h.shape[0]
    tail_cap = max(cap // _TAIL_SHARE, 1)
    iota = jnp.arange(cap, dtype=jnp.int32)

    def resolve(h, words, iota, buckets):
        """The loop body over the rows whose hashes and key words these
        are, hashed to ``buckets`` buckets; ``winner`` holds positions
        among the rows."""
        n = iota.shape[0]

        def body(state):
            r, winner, unresolved = state
            hr = _fmix_device(h ^ (r.astype(jnp.uint32)
                                   * jnp.uint32(2654435761)))
            bucket = (hr % jnp.uint32(buckets)).astype(jnp.int32)
            cand_src = jnp.where(unresolved, iota, n)
            cand = jax.ops.segment_min(cand_src, bucket,
                                       num_segments=buckets)
            w = jnp.take(cand, bucket)
            w_safe = jnp.clip(w, 0, n - 1)
            eq = jnp.logical_and(unresolved, w < n)
            for word in words:
                eq = jnp.logical_and(
                    eq, word == jnp.take(word, w_safe, axis=0))
            winner = jnp.where(eq, w_safe, winner)
            unresolved = jnp.logical_and(unresolved, jnp.logical_not(eq))
            return r + 1, winner, unresolved
        return body

    def many_open(state):
        r, _, unresolved = state
        return jnp.logical_and(
            jnp.sum(unresolved, dtype=jnp.int32) > tail_cap, r < cap)

    def any_open(state):
        r, _, unresolved = state
        return jnp.logical_and(jnp.any(unresolved), r < cap)

    def tail(full_rounds, winner, unresolved):
        rows, live = open_rows_by_rank(unresolved, iota, tail_cap)
        tail_iota = jnp.arange(tail_cap, dtype=jnp.int32)
        rounds, tail_winner, _ = jax.lax.while_loop(
            any_open,
            resolve(jnp.take(h, rows),
                    [jnp.take(w, rows, axis=0) for w in words], tail_iota,
                    _TAIL_BUCKETS * tail_cap),
            (full_rounds, tail_iota, live))
        return rounds, winner.at[jnp.where(live, rows, cap)].set(
            jnp.take(rows, tail_winner), mode="drop")

    # the scopes tie the HLO's %while / gather ops to this code in a
    # device profile (free at run time)
    with jax.named_scope("groupby_bucket_resolve"):
        full_rounds, winner, unresolved = jax.lax.while_loop(
            many_open, resolve(h, words, iota, cap),
            (jnp.int32(0), iota, active))
    with jax.named_scope("groupby_tail_resolve"):
        rounds, winner = jax.lax.cond(
            jnp.any(unresolved), tail, lambda r, w, _: (r, w),
            full_rounds, winner, unresolved)
    return winner, rounds, full_rounds


def _hash_group_ids(table: "DeviceTable", key_names: List[str]):
    """SORT-FREE exact grouping: hash keys into buckets, resolve each
    bucket's minimum-index candidate's whole key-class per round, and
    rehash unresolved rows until none remain (``lax.while_loop``s — compile
    cost is a body a loop regardless of rounds; expected 2-4 rounds).

    A round costs by the rows it is run over, not by the rows still open,
    and a round resolves one key a bucket: the last rounds are run for a
    few percent of the rows. So a *full round* — every row hashed to one
    of ``cap`` buckets, each bucket's lowest open row taken as candidate,
    every key word of the candidate gathered by every row — runs only
    while more than 1/``_TAIL_SHARE`` of the capacity is open. The rows
    then still open are compacted once by rank (``open_rows_by_rank``),
    their hash and key words gathered by those ``cap // _TAIL_SHARE``
    indices, and *tail rounds* that long (the same step, over
    ``_TAIL_BUCKETS`` buckets a tail row) finish them; their winners are
    written back by row index in one scatter. A round resolves a
    candidate's whole class, so the open rows are whole classes, and
    compaction keeps row order, so a class's lowest row is its
    representative whichever phase resolves it: the grouping is the same
    for every input. Compaction, tail rounds and write-back sit under one
    ``lax.cond`` on "any row still open": a batch the full rounds finish
    (Q1's four groups: one round) pays one scalar test, and a batch with
    no more than ``cap // _TAIL_SHARE`` live rows runs no full round at
    all.

    Returns the same contract as _sorted_group_ids but with NO order
    (``None``): rows stay where they are, so a consumer reads the input
    columns as they stand and gathers nothing by a permutation; the last
    value is (rounds, full_rounds): all trips of the resolve loops, full
    and tail, and the full rounds among them (span ``agg.scatter`` carries
    both). Every consumer (group reductions, representative gather) is
    order-agnostic, so the GROUPING contributes no lax.sort to the program
    — the escape hatch for toolchains where sort compilation is
    pathological (see spark.rapids.tpu.groupby.strategy), and the closest
    analogue of the reference's cuDF HASH groupby."""
    cap = table.capacity
    active = table.row_mask
    h, words = _hashed_key_words(table, key_names)
    winner, rounds, full_rounds = _resolve_buckets(h, words, active)
    with jax.named_scope("groupby_group_ids"):
        iota = jnp.arange(cap, dtype=jnp.int32)
        is_rep = jnp.logical_and(active, winner == iota)
        rep_rank = jnp.cumsum(is_rep.astype(jnp.int32)) - 1
        gid = jnp.clip(jnp.take(rep_rank, winner), 0, cap - 1)
        num_groups = jnp.sum(is_rep.astype(jnp.int32))
    boundary = is_rep
    return None, active, gid, boundary, num_groups, (rounds, full_rounds)


GROUPBY_STRATEGY = register_conf(
    "spark.rapids.tpu.groupby.strategy",
    "Device group-by algorithm: 'sort' (lexsort + boundaries — the "
    "static-shape default on CPU), 'hash' (bucket-resolve rounds; no "
    "lax.sort in the GROUPING — collect_set dedup still sorts), or "
    "'auto' (= hash: faster on every measured backend, and immune to "
    "the pathologically slow sort compilation seen on some TPU "
    "toolchains; reference analogue: cuDF hash groupby vs sort "
    "groupby).", "auto",
    checker=lambda v: None if str(v).lower() in ("auto", "sort", "hash")
    else "must be auto|sort|hash")


def _resolve_groupby_strategy() -> str:
    """sort|hash from the active session conf; AUTO = hash (measured
    faster than the lexsort path on CPU — TPC-H Q1 2.55x vs 0.82x — and
    sort compilation is the pathological op for some TPU toolchains)."""
    from ..session import TpuSession
    sess = TpuSession._active
    v = "auto"
    if sess is not None and GROUPBY_STRATEGY is not None:
        v = str(sess.conf.get(GROUPBY_STRATEGY)).lower()
    return "hash" if v == "auto" else v


def _sorted_group_ids(table: "DeviceTable", key_names: List[str]):
    """Lexsort rows so equal keys are adjacent (active first) and label
    groups. -> (order, active_s, gid, boundary, num_groups, (rounds,
    full_rounds)), both 0: a sort resolves no bucket.

    The per-key null/NaN/length flags bit-pack into shared "meta" uint64
    words (the not-active flag in the top bits of meta word 0, so active
    rows sort first) — only group EQUALITY must survive the packing, not
    any particular inter-group order, so the lexsort runs over the value
    words + one or two meta words instead of ~3 operands per key."""
    cap = table.capacity
    active = table.row_mask
    key_cols = [table.column(k) for k in key_names]
    bit_fields = [(jnp.logical_not(active).astype(jnp.uint64), 1)]
    value_words: List[jax.Array] = []
    for kc in key_cols:
        words, smalls = _key_small_fields(kc)
        value_words.extend(words)
        bit_fields.extend(smalls)
    meta = _pack_meta_words(bit_fields)
    # lexsort: LAST entry is most significant -> meta[0] (active bit) is
    # primary, remaining meta words next, value words after
    sort_keys = list(reversed(value_words)) + list(reversed(meta))
    with jax.named_scope("groupby_lexsort"):
        order = jnp.lexsort(tuple(sort_keys))
    active_s = jnp.take(active, order)
    same = jnp.ones(cap, dtype=bool)
    for wd in value_words + meta:
        same = jnp.logical_and(same,
                               _keys_equal_prev(jnp.take(wd, order)))
    boundary = jnp.logical_and(jnp.logical_not(same), active_s)
    boundary = boundary.at[0].set(active_s[0])
    gid = jnp.cumsum(boundary.astype(jnp.int32)) - 1
    gid = jnp.clip(gid, 0, cap - 1)
    num_groups = jnp.sum(boundary.astype(jnp.int32))
    return order, active_s, gid, boundary, num_groups, \
        (jnp.int32(0), jnp.int32(0))


def _first_occurrence_in_group(sv: jax.Array, gid: jax.Array,
                               contrib: jax.Array) -> jax.Array:
    """True for the first contributing row of each (group, value) pair —
    collect_set dedup that preserves first-insertion row order."""
    v = sv
    if v.dtype == jnp.bool_:
        v = v.astype(jnp.int32)
    if jnp.issubdtype(v.dtype, jnp.floating):
        # total order for grouping equal values adjacently
        v = _normalize_float_key(v)
    order2 = jnp.lexsort((v, gid, jnp.logical_not(contrib)))
    v2 = jnp.take(v, order2)
    g2 = jnp.take(gid, order2)
    c2 = jnp.take(contrib, order2)
    dup = jnp.logical_and(v2 == jnp.roll(v2, 1), g2 == jnp.roll(g2, 1))
    dup = jnp.logical_and(dup, jnp.logical_and(c2, jnp.roll(c2, 1)))
    dup = dup.at[0].set(False)
    first2 = jnp.logical_and(c2, jnp.logical_not(dup))
    return jnp.zeros_like(contrib).at[order2].set(first2)


def _row_dedup_sorted(mat: jax.Array, lens: jax.Array):
    """Per-row: sort elements, drop adjacent duplicates, compact left
    (merge_sets — partial states may repeat values across map sides).

    Sorting happens on an integer surrogate key (floats via the monotone
    bit trick, NaN greatest) with an int64-max pad sentinel, and the
    ORIGINAL values are gathered by that order — so NaN dedups against
    NaN, no pad value can leak into the data, and bool/float dtypes come
    back unchanged."""
    W = mat.shape[1]
    j = jnp.arange(W, dtype=jnp.int32)
    in_len = j[None, :] < lens[:, None]
    is_float = jnp.issubdtype(mat.dtype, jnp.floating)
    if is_float:
        # monotone bit surrogate (IEEE trick): order-preserving injection
        # into uint64, with -0.0 normalized so it dedups against +0.0
        v = jnp.where(mat == 0, jnp.zeros_like(mat), mat)
        if mat.dtype == jnp.float32:
            u = jax.lax.bitcast_convert_type(v, jnp.uint32)
            top = jnp.uint32(1) << jnp.uint32(31)
        else:
            u = jax.lax.bitcast_convert_type(v, jnp.uint64)
            top = jnp.uint64(1) << jnp.uint64(63)
        key = jnp.where((u & top) != 0, ~u, u | top).astype(jnp.uint64)
    elif mat.dtype == jnp.bool_:
        key = mat.astype(jnp.int64)
    else:
        key = mat.astype(jnp.int64)
    # exact pads-last ordering: stable sort by key, then stable sort by
    # the pad flag — composition = lexsort((key, is_pad)) per row, with
    # no sentinel that could collide with a real extreme value
    pad_flag = jnp.logical_not(in_len)
    order1 = jnp.argsort(key, axis=1, stable=True)
    p1 = jnp.take_along_axis(pad_flag, order1, axis=1)
    order2 = jnp.argsort(p1, axis=1, stable=True)
    order = jnp.take_along_axis(order1, order2, axis=1)
    sk = jnp.take_along_axis(key, order, axis=1)
    spad = jnp.take_along_axis(pad_flag, order, axis=1)
    sv = jnp.take_along_axis(mat, order, axis=1)
    dup = jnp.logical_and(sk == jnp.roll(sk, 1, axis=1),
                          jnp.logical_not(
                              jnp.logical_or(spad,
                                             jnp.roll(spad, 1, axis=1))))
    if is_float:
        # `==` dedup semantics (the host engine's): NaN never equals NaN,
        # so same-bit NaNs must NOT merge at the merge pass either
        nan_s = jnp.isnan(sv)
        dup = jnp.logical_and(dup, jnp.logical_not(
            jnp.logical_or(nan_s, jnp.roll(nan_s, 1, axis=1))))
    dup = dup.at[:, 0].set(False)
    # pads sort strictly last, so the first ``lens`` slots are the reals
    keep = jnp.logical_and(j[None, :] < lens[:, None],
                           jnp.logical_not(dup))
    order2 = jnp.argsort(jnp.logical_not(keep), axis=1, stable=True)
    out = jnp.take_along_axis(sv, order2, axis=1)
    newlens = keep.sum(axis=1).astype(jnp.int32)
    out = jnp.where(j[None, :] < newlens[:, None], out,
                    jnp.zeros((), out.dtype))
    return out, newlens


def _collect_segment(op: str, sv: jax.Array, slen, contrib: jax.Array,
                     gid: jax.Array, cap: int, width: int):
    """Per-group collect into a (cap, width) list matrix + lengths.

    Update ops scatter scalar rows by within-group rank; merge ops scatter
    whole element runs by within-group element offset. Callers size
    ``width`` from a host-synced size pass (the dynamic-width escape
    hatch; reference: cuDF list columns size their child dynamically)."""
    if op == "collect_set":
        contrib = jnp.logical_and(
            contrib, _first_occurrence_in_group(sv, gid, contrib))
        op = "collect_list"
    if op == "collect_list":
        c32 = contrib.astype(jnp.int32)
        prefix = jnp.cumsum(c32) - c32      # contributing rows before this
        base = jax.ops.segment_min(
            jnp.where(contrib, prefix, _BIG32), gid, num_segments=cap)
        within = jnp.where(contrib, prefix - base[gid], 0)
        r_idx = jnp.where(contrib, gid, cap)        # trash row for skips
        c_idx = jnp.where(contrib, jnp.clip(within, 0, width), width)
        out = jnp.zeros((cap + 1, width + 1), sv.dtype)
        out = out.at[r_idx, c_idx].set(sv)
        lens = jax.ops.segment_sum(c32, gid, num_segments=cap) \
            .astype(jnp.int32)
        return out[:cap, :width], jnp.minimum(lens, width)
    # merge_lists / merge_sets: sv is (n, Win) + per-row lengths
    lens_eff = jnp.where(contrib, slen.astype(jnp.int32), 0)
    prefix = jnp.cumsum(lens_eff) - lens_eff
    base = jax.ops.segment_min(
        jnp.where(contrib, prefix, _BIG32), gid, num_segments=cap)
    elem_base = prefix - base[gid]
    win = sv.shape[1]
    j = jnp.arange(win, dtype=jnp.int32)[None, :]
    valid_e = j < lens_eff[:, None]
    r_idx = jnp.where(valid_e, gid[:, None], cap)
    c_idx = jnp.where(valid_e,
                      jnp.clip(elem_base[:, None] + j, 0, width), width)
    out = jnp.zeros((cap + 1, width + 1), sv.dtype)
    out = out.at[r_idx, c_idx].set(sv)
    lens = jnp.minimum(
        jax.ops.segment_sum(lens_eff, gid, num_segments=cap), width) \
        .astype(jnp.int32)
    out = out[:cap, :width]
    if op == "merge_sets":
        return _row_dedup_sorted(out, lens)
    return out, lens


class TpuHashAggregateExec(TpuExec):
    """Same pre-projected input contract as CpuHashAggregateExec."""

    EXTRA_METRICS = (M.AGG_TIME,)

    def __init__(self, child: PhysicalPlan, key_names: List[str],
                 specs: List[AggSpec], mode: str):
        super().__init__()
        assert mode in ("partial", "final")
        self.child = child
        self.children = (child,)
        self.key_names = list(key_names)
        self.specs = specs
        self.mode = mode
        key_fields = [child.schema.field(k) for k in key_names]
        state_fields = [Field(n, d, nb) for s in specs
                        for (n, d, nb) in s.state_fields]
        self.schema = Schema(key_fields + state_fields)

    @property
    def fusible(self) -> bool:
        # partial mode may emit one state-batch per input batch (downstream
        # merge reduces them); final mode must merge across batches itself.
        # collect_* needs a per-batch host-synced width pass, so it cannot
        # join a whole-stage program
        return self.mode == "partial" and not self._has_collect()

    def _columns_ops(self) -> List[Tuple[str, str, str, dt.DataType]]:
        out = []
        for s in self.specs:
            ops = s.update_ops if self.mode == "partial" else s.merge_ops
            in_cols = s.input_cols if self.mode == "partial" \
                else [n for (n, _, _) in s.state_fields]
            for (in_col, op, (out_col, out_dt, _)) in zip(in_cols, ops, s.state_fields):
                out.append((in_col, op, out_col, out_dt))
        return out

    def _has_collect(self) -> bool:
        return any(op in _COLLECT_OPS
                   for (_, op, _, _) in self._columns_ops())

    def _dense_ok(self) -> bool:
        """Whether ``grouped`` holds the few-groups branch: static, from
        the plan. Every op ``_reduce_segment`` reduces through ``_seg_*``
        has the one-segment (plain reduce) form the branch runs; a collect
        has none, nor a decimal128 state (its limb sums carry their own
        scatter)."""
        return not any(op in _COLLECT_OPS or dt.is_d128(out_dt)
                       for (_, op, _, out_dt) in self._columns_ops())

    def can_pass_through(self) -> bool:
        """Whether this grouped partial may pass batches through as
        one-row states (``passthrough_fn``): static, from the plan, like
        ``_dense_ok``. Every op's state must be a row-wise projection of a
        fixed-width value: no collect, no decimal128 state (its overflow
        flag), no string or nested ``min`` / ``max``."""
        return self.mode == "partial" and bool(self.key_names) and all(
            op in _PASSTHROUGH_OPS and not dt.is_d128(out_dt)
            and not isinstance(out_dt, (dt.StringType, dt.BinaryType,
                                        dt.ArrayType, dt.StructType,
                                        dt.MapType))
            for (_, op, _, out_dt) in self._columns_ops())

    @staticmethod
    def skips(groups: int, rows: int) -> bool:
        """The rule: a first batch of ``rows`` live rows reduced to
        ``groups`` groups says the partial does not reduce (``SKIP_SHARE``)."""
        return groups * SKIP_SHARE > rows

    def passthrough_fn(self) -> Callable[[DeviceTable], DeviceTable]:
        """Every row its own partial state, at the input's capacity: keys
        as they are, ``sum`` / ``sumsq`` the value (squared) in the state's
        type, ``count`` the row's contribution, ``min`` / ``max`` /
        ``first`` / ``last`` the value, each valid where the row
        contributes; ``row_mask`` and ``num_rows`` the input's. The merge
        ops of the final aggregate read these rows as they read the states
        of the partial (NaN order included)."""
        cols_ops = self._columns_ops()
        key_names = self.key_names
        out_names = tuple(self.schema.names)

        def run(table: DeviceTable) -> DeviceTable:
            mask = table.row_mask
            out_cols = [table.column(k) for k in key_names]
            for in_col, op, _, out_dt in cols_ops:
                col = table.column(in_col)
                contrib = mask if col.all_valid \
                    else jnp.logical_and(col.validity, mask)
                out_dtype = jnp.dtype(
                    np.bool_ if isinstance(out_dt, dt.BooleanType)
                    else out_dt.np_dtype())
                if op == "count":
                    out_cols.append(DeviceColumn(
                        contrib.astype(out_dtype), mask, out_dt, None))
                    continue
                x = col.data.astype(out_dtype)
                if op in ("sum", "sumsq"):
                    x = jnp.where(contrib, x * x if op == "sumsq" else x,
                                  jnp.zeros_like(x))
                out_cols.append(DeviceColumn(x, contrib, out_dt, None))
            return DeviceTable(tuple(out_cols), mask, table.num_rows,
                               out_names)
        return run

    def book_skip(self, table: DeviceTable) -> DeviceTable:
        """Span ``agg.skip`` (``rows`` = the capacity) for a batch that
        passed through, booked where the skip was decided, with no sync:
        the batch is marked so that no consumer books a branch of
        ``grouped`` for it (``passed_through``)."""
        table._tpu_passed_through = True
        with get_tracer().span("agg.skip", "agg", on=table.row_mask,
                               rows=table.capacity):
            pass
        return table

    def book_branch(self, num_groups: int, rows: int,
                    trips: Optional[Sequence[int]] = None,
                    on=None) -> None:
        """Span ``agg.dense`` / ``agg.scatter`` (``rows`` = the batch's
        capacity, ``groups``): the branch of ``grouped`` that reduced a
        batch of ``num_groups`` groups. Booked where the host already
        holds the batch's group count (the row-count sync of the
        ``shrink_to_fit`` that follows, or the count an exchange
        resolved): the device picked the branch from the same number, so
        this adds no sync and no program. ``agg.scatter`` carries
        ``rounds`` and ``full_rounds`` (``trips``: all trips of the
        bucket-resolve loops, and those of them that ran over the whole
        batch) where the aggregate ran as a program of its own, which
        returns them; a fused stage returns its table alone. A batch
        whose count the host never reads books neither. ``on`` (the batch)
        gives the span its ``device``, as ``sync`` has it."""
        if not self.key_names:
            return
        dense = self._dense_ok() and num_groups <= FEW_GROUPS
        args = {"rows": rows, "groups": num_groups}
        if trips is not None and not dense:
            args["rounds"], args["full_rounds"] = trips
        with get_tracer().span("agg.dense" if dense else "agg.scatter",
                               "agg", on=on, **args):
            pass

    def shrink_booked(self, fn, out: DeviceTable, rows: int,
                      live_in=None
                      ) -> Tuple[DeviceTable, Optional[int], bool]:
        """``shrink_to_fit`` of a batch ``fn`` (a ``_canon_fn`` of this
        node) aggregated from ``rows`` rows of capacity, its branch
        booked: the resolve loops' trip counts ride in the transfer that
        reads the group count for the shrink, and so does ``live_in``,
        the input's live row count, where given. -> (the shrunk batch,
        its group count or None where none is read: no keys, or a batch
        already at the minimum bucket; whether ``skips`` says the rest
        of the partition passes through, False without ``live_in``)."""
        from ..columnar.device import (resolve_min_bucket, resolve_scalars,
                                       shrink_to_fit)
        if not self.key_names or out.capacity <= resolve_min_bucket(None):
            return shrink_to_fit(out), None, False
        extra = () if live_in is None else (live_in,)
        n, *trips = resolve_scalars(out.num_rows, *extra, *fn.trips)
        skip = live_in is not None and self.skips(n, trips.pop(0))
        self.book_branch(n, rows, trips, on=out.row_mask)
        return shrink_to_fit(out, num_rows=n), n, skip

    def host_batch_fn(self):
        # host-engine partial aggregation over one downloaded batch — the
        # per-table body of CpuHashAggregateExec.execute. Only the
        # fusible (partial, no collect_*) form gets a fallback path: its
        # per-batch state outputs merge downstream exactly like the
        # device partial's would
        if not self.fusible:
            return None
        key_names = list(self.key_names)
        cols_ops = self._columns_ops()
        out_names = list(self.schema.names)
        schema = self.schema
        child_schema = self.child.schema

        def fn(table):
            import numpy as np
            from ..columnar.host import HostColumn, HostTable
            from ..plan.host_groupby import group_codes, host_group_reduce
            from ..plan.physical import _empty_values
            if table.num_rows == 0:
                if key_names:
                    return HostTable(
                        out_names,
                        [HostColumn(f.dtype, _empty_values(f.dtype))
                         for f in schema])
                # grand aggregate over an empty batch: one null/zero row
                table = HostTable(
                    [c for c, _, _, _ in cols_ops],
                    [HostColumn(child_schema.field(c).dtype,
                                _empty_values(child_schema.field(c).dtype))
                     for c, _, _, _ in cols_ops])
            gid, ngroups, rep = group_codes(table, key_names)
            out_cols = []
            for k in key_names:
                out_cols.append(table.column(k).take(rep))
            for in_col, op, out_col, out_dt in cols_ops:
                vals, validity = host_group_reduce(
                    op, table.column(in_col), gid, ngroups, out_dt)
                if not isinstance(out_dt, (dt.StringType, dt.BinaryType,
                                           dt.ArrayType, dt.StructType,
                                           dt.MapType)) \
                        and not dt.is_d128(out_dt) \
                        and vals.dtype != out_dt.np_dtype():
                    with np.errstate(invalid="ignore"):
                        vals = vals.astype(out_dt.np_dtype())
                if validity is not None and validity.all():
                    validity = None
                out_cols.append(HostColumn(out_dt, vals, validity))
            return HostTable(out_names, out_cols)
        return fn

    # -- kernels -------------------------------------------------------------
    def batch_fn(self, list_width: int = 0, with_rounds: bool = False
                 ) -> Callable[[DeviceTable], DeviceTable]:
        """The aggregate of one batch. ``with_rounds``: a grouped
        aggregate returns (table, (rounds, full_rounds) of the
        bucket-resolve loops), the form the program of its own
        (``_canon_fn``) compiles; inside a fused stage the table alone
        leaves the program."""
        cols_ops = self._columns_ops()
        key_names = self.key_names
        out_names = tuple(self.schema.names)

        def ungrouped(table: DeviceTable) -> DeviceTable:
            cap_out = 8  # tiny fixed capacity for the single state row
            out_cols = []
            pos = jnp.arange(table.capacity, dtype=jnp.int64)
            for in_col, op, out_col, out_dt in cols_ops:
                col = table.column(in_col)
                contrib = table.row_mask if col.all_valid \
                    else jnp.logical_and(col.validity, table.row_mask)
                gid = jnp.zeros(table.capacity, dtype=jnp.int32)
                if op in _COLLECT_OPS:
                    data1, lens1 = _collect_segment(
                        op, col.data, col.lengths, contrib, gid, 1,
                        list_width)
                    data = jnp.zeros((cap_out, list_width), data1.dtype) \
                        .at[0].set(data1[0])
                    lens = jnp.zeros(cap_out, jnp.int32).at[0].set(lens1[0])
                    validity = jnp.zeros(cap_out, bool).at[0].set(True)
                    out_cols.append(
                        DeviceColumn(data, validity, out_dt, lens))
                    continue
                vals1, has1 = _reduce_segment(
                    op, col.data, contrib, gid, 1, pos, out_dt)
                vals = jnp.zeros((cap_out,) + vals1.shape[1:],
                                 dtype=vals1.dtype).at[0].set(vals1[0])
                validity = jnp.zeros(cap_out, dtype=bool).at[0].set(has1[0])
                out_cols.append(DeviceColumn(vals, validity, out_dt, None))
            iota = jnp.arange(cap_out, dtype=jnp.int32)
            return DeviceTable(tuple(out_cols), iota < 1,
                               jnp.asarray(1, jnp.int32), out_names)

        # collect ops need CONTIGUOUS groups: their within-group ranks
        # come from global prefix sums, which only equal within-group
        # ranks when equal keys are adjacent — so collects force the
        # sorted grouping regardless of strategy
        has_collect = any(op in _COLLECT_OPS for (_, op, _, _) in cols_ops)
        group_ids = _hash_group_ids \
            if (_resolve_groupby_strategy() == "hash" and not has_collect) \
            else _sorted_group_ids

        # where an op has no dense form the program holds only the
        # scatter branch
        dense_ok = self._dense_ok()

        def grouped(table: DeviceTable) -> DeviceTable:
            cap = table.capacity
            order, active_s, gid, boundary, num_groups, trips = \
                group_ids(table, key_names)

            def in_order(a):
                # only a sorted grouping permutes the rows
                return a if order is None or a is None \
                    else jnp.take(a, order, axis=0)

            key_cols = [table.column(k) for k in key_names]
            pos = jnp.arange(cap, dtype=jnp.int64)
            inputs = []
            for in_col, op, out_col, out_dt in cols_ops:
                col = table.column(in_col)
                contrib = active_s if col.all_valid else jnp.logical_and(
                    in_order(col.validity), active_s)
                inputs.append((in_order(col.data), contrib,
                               in_order(col.lengths)
                               if op in _COLLECT_OPS else None))

            def key_rows(rep):
                """Key columns gathered at each group's representative
                row (``rep``: positions in grouping order)."""
                rep = jnp.clip(rep, 0, cap - 1).astype(jnp.int32)
                if order is not None:
                    rep = jnp.take(order, rep)
                # DeviceColumn.gather recurses into struct children and
                # the element-validity plane
                return [kc.gather(rep, keep_all_valid=True)
                        for kc in key_cols]

            def scatter():
                """Every row scattered into its group's segment: flat in
                the group count, ``cap`` segments and ``cap`` key rows."""
                # positions in 32 bits, as ``_reduce_segment`` takes them
                # into ``cap`` segments
                pos32 = jnp.arange(cap, dtype=jnp.int32)
                with jax.named_scope("groupby_key_gather"):
                    rep_src = jnp.where(active_s, pos32,
                                        jnp.full_like(pos32, _BIG32))
                    keys = key_rows(_seg_min(rep_src, gid, cap))
                states = []
                with jax.named_scope("agg_scatter"):
                    for (_, op, _, out_dt), (sv, contrib, slen) in zip(
                            cols_ops, inputs):
                        states.append(
                            _collect_segment(op, sv, slen, contrib, gid, cap,
                                             list_width)
                            if op in _COLLECT_OPS else
                            _reduce_segment(op, sv, contrib, gid, cap, pos32,
                                            out_dt))
                return keys, states

            def dense():
                """Group by group, over the live groups only: each is the
                one-segment (plain reduce) form of the same reductions over
                ``gid == g``, so no row is scattered and a pass costs what
                an ungrouped aggregate of the batch costs. Results land in
                ``FEW_GROUPS`` slots, keys and ``first`` / ``last`` values
                are gathered by as many indices, and all is padded to
                ``cap`` rows: the same pytree as ``scatter``."""
                # at least one slot, so the program traces with the
                # branch switched off (FEW_GROUPS 0: only an empty batch)
                n = max(1, min(FEW_GROUPS, cap))

                def of_group(g):
                    member = gid == g
                    rep = _seg_min(
                        jnp.where(jnp.logical_and(active_s, member), pos,
                                  jnp.full_like(pos, _BIG)), gid, 1)
                    return rep, [
                        _reduce_segment(op, sv,
                                        jnp.logical_and(contrib, member),
                                        gid, 1, pos, out_dt)
                        for (_, op, _, out_dt), (sv, contrib, _)
                        in zip(cols_ops, inputs)]

                def body(g, slots):
                    return jax.tree_util.tree_map(
                        lambda a, v: jax.lax.dynamic_update_slice_in_dim(
                            a, v, g, 0), slots, of_group(g))

                slots = jax.tree_util.tree_map(
                    lambda v: jnp.zeros((n,) + v.shape[1:], v.dtype),
                    jax.eval_shape(of_group, num_groups))
                with jax.named_scope("agg_dense"):
                    rep, states = jax.lax.fori_loop(0, num_groups, body,
                                                    slots)
                with jax.named_scope("groupby_key_gather"):
                    keys = key_rows(rep)
                return jax.tree_util.tree_map(
                    lambda a: jnp.pad(a, [(0, cap - n)]
                                      + [(0, 0)] * (a.ndim - 1)),
                    (keys, states))

            # the group ids, and so the order of the output rows, are the
            # same whichever branch reduces them
            keys, states = jax.lax.cond(
                num_groups <= FEW_GROUPS, dense, scatter) if dense_ok \
                else scatter()
            iota = jnp.arange(cap, dtype=jnp.int32)
            group_mask = iota < num_groups
            out_cols: List[DeviceColumn] = [
                g.with_validity(jnp.logical_and(g.validity, group_mask))
                for g in keys]
            for (_, op, _, out_dt), state in zip(cols_ops, states):
                if op in _COLLECT_OPS:
                    data, lens = state
                    out_cols.append(DeviceColumn(
                        data, group_mask, out_dt,
                        jnp.where(group_mask, lens, 0)))
                    continue
                vals, has = state
                validity = jnp.logical_and(has, group_mask) if op != "count" \
                    else group_mask
                out_cols.append(DeviceColumn(vals, validity, out_dt, None))
            out = DeviceTable(tuple(out_cols), group_mask,
                              num_groups.astype(jnp.int32), out_names)
            return (out, trips) if with_rounds else out

        return ungrouped if not key_names else grouped

    def plan_signature(self) -> str:
        child_schema = repr(self.children[0].schema) \
            if hasattr(self.children[0], "schema") else ""
        return (f"HashAgg|{self.mode}|{self.key_names}|"
                f"{self._columns_ops()!r}|{child_schema}")

    def _canon_exec(self) -> Tuple["TpuHashAggregateExec", str]:
        """Schema-erased clone + cache key: column names become positional
        (c0..cN in, o0..oM out) so structurally identical aggregations in
        DIFFERENT queries share one compiled program. Shapes/dtypes that
        remain distinct retrace inside the shared jax.jit wrapper — the key
        only needs what the *builder closure* captures (mode, positions,
        ops, output dtypes)."""
        child_fields = list(self.child.schema.fields)
        pos = {f.name: i for i, f in enumerate(child_fields)}
        ops = self._columns_ops()
        nk = len(self.key_names)
        canon_ops = [(f"c{pos[in_col]}", op, f"o{nk + j}", out_dt)
                     for j, (in_col, op, _, out_dt) in enumerate(ops)]
        clone = TpuHashAggregateExec.__new__(TpuHashAggregateExec)
        TpuExec.__init__(clone)
        clone.mode = self.mode
        clone.key_names = [f"c{pos[k]}" for k in self.key_names]
        clone.specs = []
        clone._columns_ops = lambda: canon_ops      # instance-level override
        clone.schema = Schema([Field(f"o{j}", f.dtype, f.nullable)
                               for j, f in enumerate(self.schema.fields)])
        clone.child = _SchemaOnly(Schema(
            [Field(f"c{i}", f.dtype, f.nullable)
             for i, f in enumerate(child_fields)]))
        clone.children = (clone.child,)
        has_collect = any(op in _COLLECT_OPS for (_, op, _, _) in ops)
        eff_strategy = "sort" if has_collect \
            else _resolve_groupby_strategy()
        key = (f"HashAggC|{self.mode}|k{[pos[k] for k in self.key_names]}|"
               f"{[(pos[i], op, repr(odt)) for (i, op, _, odt) in ops]}|"
               f"g={eff_strategy}")
        return clone, key

    def _sizes_fn(self) -> Callable[[DeviceTable], jax.Array]:
        """Max list width any collect op needs for one batch (the host
        syncs this one int to pick a bucketed static width)."""
        cols_ops = [co for co in self._columns_ops() if co[1] in _COLLECT_OPS]
        key_names = self.key_names

        # sizes exist only for collect ops, which force sorted grouping
        group_ids = _sorted_group_ids

        def sizes(table: DeviceTable) -> jax.Array:
            cap = table.capacity
            if key_names:
                order, active_s, gid, _, _, _ = group_ids(
                    table, key_names)
            else:
                order = jnp.arange(cap, dtype=jnp.int32)
                active_s = table.row_mask
                gid = jnp.zeros(cap, dtype=jnp.int32)
            w = jnp.asarray(1, jnp.int32)
            for in_col, op, _, _ in cols_ops:
                col = table.column(in_col)
                contrib = active_s if col.all_valid else jnp.logical_and(
                    jnp.take(col.validity, order), active_s)
                if op in ("collect_list", "collect_set"):
                    per = jax.ops.segment_sum(
                        contrib.astype(jnp.int32), gid, num_segments=cap)
                else:
                    lens = jnp.take(col.lengths, order).astype(jnp.int32)
                    per = jax.ops.segment_sum(
                        jnp.where(contrib, lens, 0), gid, num_segments=cap)
                w = jnp.maximum(w, per.max())
            return w
        return sizes

    def _collect_width(self, table: DeviceTable, key: str) -> int:
        from ..columnar.device import bucket_width
        from ..utils.compile_cache import cached_jit
        sizes = cached_jit(key + "|sizes", self._sizes_fn, name="agg_sizes")
        return bucket_width(max(int(sizes(table)), 1), min_width=4)

    def _canon_fn(self) -> Callable[[DeviceTable], DeviceTable]:
        """Schema-erased cached aggregate callable: canonical-rename in,
        run the shared program, rename out."""
        from ..utils.compile_cache import cached_jit
        canon, ckey = self._canon_exec()
        out_names = tuple(self.schema.names)
        grouped = bool(canon.key_names)

        def named(out) -> DeviceTable:
            # a grouped program returns (table, trips of its resolve
            # loops): the trips wait on ``fn.trips`` for whoever reads
            # the batch's group count (``shrink_booked``)
            if grouped:
                out, fn.trips = out
            return out.with_names(out_names)

        if not self._has_collect():
            base = cached_jit(
                ckey, lambda: canon.batch_fn(with_rounds=grouped),
                name="agg_grouped" if grouped else "agg_ungrouped")

            def fn(batch: DeviceTable) -> DeviceTable:
                return named(base(batch.canonical()))
        else:
            def fn(batch: DeviceTable) -> DeviceTable:
                # per-batch static width, cached per bucket
                bc = batch.canonical()
                w = canon._collect_width(bc, ckey)
                return named(cached_jit(
                    ckey + f"|W{w}",
                    lambda: canon.batch_fn(list_width=w, with_rounds=grouped),
                    name="agg_grouped" if grouped else "agg_ungrouped")(bc))
        fn.trips = None
        return fn

    def _canon_passthrough_fn(self) -> Callable[[DeviceTable], DeviceTable]:
        """``passthrough_fn`` as a program of its own, schema-erased as
        ``_canon_fn`` is: the batches an unfused partial passes through."""
        from ..utils.compile_cache import cached_jit
        canon, ckey = self._canon_exec()
        out_names = tuple(self.schema.names)
        base = cached_jit(ckey + "|pass", canon.passthrough_fn,
                          name="agg_passthrough")
        # srtpu: retry-ok(run only by _passed_through, under with_retry_split) srtpu: degrade-ok(run only by _passed_through, under quarantine_on_failure as the chunks are)
        return lambda batch: base(batch.canonical()).with_names(out_names)

    def execute_columnar(self, pidx: int) -> Iterator[DeviceTable]:
        from ..columnar.device import concat_device_tables
        from ..memory.catalog import SpillPriorities, get_catalog
        from ..memory.retry import (split_device_rows, with_retry,
                                    with_retry_split)
        fn = self._canon_fn()
        merged = self._merged_exec()
        merge_fn = None  # built lazily, loop-invariant
        catalog = get_catalog()
        pending = None  # SpillableDeviceTable holding the running merge state

        def agg_combine(outs):
            """Split-and-retry combiner: half-outputs are PARTIAL states
            with overlapping keys, so plain row-concat would double-count
            groups — re-aggregate the concat through the merge exec."""
            nonlocal merge_fn
            both = concat_device_tables(outs)
            if merge_fn is None:
                merge_fn = merged._canon_fn()
            return merge_fn(both)

        # only the partial pass is splittable: its half-outputs are
        # mergeable states. A final-mode aggregate emits finished values
        # (e.g. avg = sum/count), which no merge pass can recombine.
        splitter = split_device_rows if self.mode == "partial" else None
        # a grouped partial decides from its first chunk whether the rest
        # of the partition passes through (``skips``), as a fused one does
        # from its first batch (exec/wholestage.py)
        deciding = self.can_pass_through()
        child_batches = iter(self.child_device_batches(pidx))

        def chunked_inputs():
            """Stage child batches and aggregate one CONCAT per
            ``_CHUNK_ROWS`` of capacity: one groupby over the chunk
            replaces a per-batch aggregate + pairwise merge cascade (4
            batches would otherwise cost 7 groupbys; chunking costs 1).
            The chunk bound keeps the concat out-of-core-safe; anything
            beyond one chunk still reduces through the pairwise merge
            below."""
            staged: List[DeviceTable] = []
            cap = 0
            for b in child_batches:
                staged.append(b)
                cap += b.capacity
                if cap >= _CHUNK_ROWS:
                    yield staged[0] if len(staged) == 1 \
                        else concat_device_tables(staged)
                    staged, cap = [], 0
            if staged:
                yield staged[0] if len(staged) == 1 \
                    else concat_device_tables(staged)

        from .fallback import quarantine_on_failure
        try:
            for batch in chunked_inputs():
                # note-only boundary: aggregate state spans batches, so a
                # terminal failure can't fall back mid-stream — but it
                # feeds the quarantine store for plan-time routing
                with quarantine_on_failure(self), \
                        self.metrics.timed(M.AGG_TIME):
                    # shrink to the group bucket: the running state must
                    # not scale with input capacity (out-of-core bound)
                    out, _, skip = self.shrink_booked(fn, with_retry_split(
                        fn, batch, splitter=splitter, combiner=agg_combine,
                        scope="partial-agg", context=self.node_desc()),
                        batch.capacity,
                        batch.num_rows if deciding else None)
                deciding = False
                if skip:
                    # the first chunk's state, then every batch left of
                    # the partition as one-row states: the downstream
                    # merge reduces them as it reduces partial states
                    self.account_batch()
                    yield out
                    yield from self._passed_through(child_batches)
                    return
                if pending is None:
                    pending = catalog.register(
                        out, SpillPriorities.ACTIVE_ON_DECK)
                else:
                    # merge-as-you-go keeps one running aggregated batch;
                    # shrink-to-groups stops its capacity growing with the
                    # batch count, and the catalog registration lets memory
                    # pressure spill it between input batches (reference:
                    # aggregate.scala merge passes under targetSize).
                    # concat pads to a pow2 bucket, so the merge program
                    # compiles for one or two capacities, not per sum.
                    # span agg.merge: one step of the cascade, by the
                    # capacity it ran at and the state it leaves
                    with get_tracer().span("agg.merge", "agg",
                                           on=out.row_mask) as span:
                        with pending as prev:
                            both = concat_device_tables([prev, out])
                        span.note(rows=both.capacity)
                        if merge_fn is None:
                            merge_fn = merged._canon_fn()
                        # spill-only retry: the concat'd pair is already
                        # at the group bucket — there is nothing useful
                        # to halve
                        state, groups, _ = merged.shrink_booked(
                            merge_fn, with_retry(
                                merge_fn, both, scope="agg-merge",
                                context=self.node_desc()), both.capacity)
                        if groups is not None:
                            span.note(groups=groups)
                    pending.close()
                    pending = catalog.register(
                        state, SpillPriorities.ACTIVE_ON_DECK)
            if pending is None:
                if not self.key_names:
                    empty = _empty_device_table(self.child.schema, 8)
                    self.account_batch()
                    yield fn(empty)
                return
            self.account_batch()
            yield pending.get()
        finally:
            if pending is not None:
                pending.close()

    def _passed_through(self, batches) -> Iterator[DeviceTable]:
        """The batches left of a partition once the skip is decided, each
        through the pass-through program under the partial's OOM ladder
        (its halves concatenate back into the same rows)."""
        from ..memory.retry import split_device_rows, with_retry_split
        from .fallback import quarantine_on_failure
        pass_fn = self._canon_passthrough_fn()
        for b in batches:
            with quarantine_on_failure(self), \
                    self.metrics.timed(M.AGG_TIME):
                out = with_retry_split(pass_fn, b, splitter=split_device_rows,
                                       scope="partial-agg",
                                       context=self.node_desc())
            self.account_batch()
            yield self.book_skip(out)

    def _merged_exec(self) -> "TpuHashAggregateExec":
        """Exec that re-aggregates concatenated partial outputs."""
        merged = TpuHashAggregateExec.__new__(TpuHashAggregateExec)
        TpuExec.__init__(merged)
        merged.key_names = self.key_names
        merged.mode = "final"
        # after the partial pass the state columns are inputs to merge ops
        specs = []
        for s in self.specs:
            ms = AggSpec(s.prefix, s.fn)
            specs.append(ms)
        merged.specs = specs
        merged.child = _SchemaOnly(self.schema)
        merged.children = (merged.child,)
        merged.schema = self.schema
        return merged

    def _merge_batch_fn(self):
        """Re-aggregate concatenated partial outputs (merge semantics)."""
        return self._merged_exec().batch_fn()

    def node_desc(self):
        return f"mode={self.mode} keys={self.key_names}"


class _SchemaOnly:
    def __init__(self, schema: Schema):
        self.schema = schema


def fused_grouped_aggregate(node) -> "Optional[TpuHashAggregateExec]":
    """The grouped aggregate that ends ``node``'s fused chain, if one
    does. Its batches leave the stage at input capacity with no host
    count read yet, so the consumer that reads the count (the exchange)
    books their ``agg.dense`` / ``agg.scatter``."""
    chain = getattr(node, "chain", None)
    top = chain[-1] if chain else None
    if isinstance(top, TpuHashAggregateExec) and top.key_names:
        return top
    return None


def passed_through(table: DeviceTable) -> bool:
    """Whether a grouped partial passed ``table`` through as one-row states
    (``TpuHashAggregateExec.book_skip``): no branch of ``grouped`` ran."""
    return getattr(table, "_tpu_passed_through", False)


def _empty_device_table(schema: Schema, cap: int) -> DeviceTable:
    def empty_col(d: dt.DataType) -> DeviceColumn:
        kids = None
        if isinstance(d, (dt.StringType, dt.BinaryType)):
            data = jnp.zeros((cap, 8), dtype=jnp.uint8)
            lengths = jnp.zeros(cap, dtype=jnp.int32)
        elif dt.is_d128(d):
            data = jnp.zeros((cap, 2), dtype=jnp.int64)
            lengths = None
        elif isinstance(d, dt.ArrayType):
            np_dt = jnp.bool_ if isinstance(d.element_type, dt.BooleanType) \
                else d.element_type.np_dtype()
            data = jnp.zeros((cap, 4), dtype=np_dt)
            lengths = jnp.zeros(cap, dtype=jnp.int32)
        elif isinstance(d, dt.StructType):
            data = jnp.zeros(cap, dtype=jnp.uint8)
            lengths = None
            kids = tuple(empty_col(f.data_type) for f in d.fields)
        elif isinstance(d, dt.MapType):
            data = jnp.zeros(cap, dtype=jnp.uint8)
            lengths = None
            kids = (empty_col(dt.ArrayType(d.key_type, False)),
                    empty_col(dt.ArrayType(d.value_type, True)))
        else:
            data = jnp.zeros(cap, dtype=d.np_dtype())
            lengths = None
        return DeviceColumn(data, jnp.zeros(cap, dtype=bool), d, lengths,
                            None, kids)

    cols = [empty_col(f.dtype) for f in schema]
    return DeviceTable(tuple(cols), jnp.zeros(cap, dtype=bool),
                       jnp.asarray(0, jnp.int32), tuple(schema.names))
