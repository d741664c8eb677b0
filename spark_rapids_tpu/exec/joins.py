"""Device equi-joins (reference: GpuHashJoin.scala:507 + JoinGatherer.scala +
AbstractGpuJoinIterator.scala out-of-core gather sub-partitioning;
GpuShuffledHashJoinExec / GpuBroadcastHashJoinExec wrappers).

TPU-first re-design — cuDF's hash join produces dynamically-sized gather maps;
XLA needs static shapes. Three-kernel pipeline per probe batch:

1. **Join codes** (exact, no hash collisions): concatenate build+probe key
   columns, one lexsort over (null flags, normalized values), boundary flags →
   dense group ids. Equal key tuples on either side get equal codes; null keys
   get per-row sentinel codes so they never match (Spark semantics); NaN keys
   match NaN; -0.0 == 0.0.
2. **Count kernel**: sort build codes once; per probe row,
   ``searchsorted(left/right)`` gives match count + start. One scalar
   (total pairs) syncs to host.
3. **Expand kernel**: compiled per *bucketed* output capacity chosen from the
   true total — the static-shape answer to cuDF's dynamic gather map.

Out-of-core (reference: AbstractGpuJoinIterator + the big-join
sub-partitioning): the build side registers with the BufferCatalog as a
spillable; a build side over the batch budget triggers a grace-style hash
sub-partition of BOTH sides (same key hash, independent seed) into spillable
buckets joined pairwise; an oversized gather output is produced in probe row
windows so no expand exceeds the budget.
"""
from __future__ import annotations

import math
import threading
from typing import Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..columnar import dtypes as dt
from ..columnar.device import (DeviceColumn, DeviceTable, bucket_rows,
                               resolve_min_bucket, resolve_scalars,
                               concat_device_tables, open_rows_by_rank,
                               prefix_sum, shrink_to_fit, slice_rows)
from ..expr.base import EvalContext, Expression
from ..plan.logical import _join_schema
from ..plan.physical import PhysicalPlan
from ..plan.schema import Field, Schema
from ..utils import metrics as M
from ..utils.compile_cache import cached_jit
from ..utils.tracing import get_tracer
from .base import TpuExec

# grace sub-partitioning uses its own hash seed: the upstream exchange
# already partitioned rows by these keys with the default seed, so reusing
# it would send every row of one shard to a single grace bucket
_GRACE_SEED = 9001

__all__ = ["TpuShuffledHashJoinExec", "TpuBroadcastHashJoinExec",
           "TpuBroadcastNestedLoopJoinExec"]


def _concat_key_col(bc: DeviceColumn, pc: DeviceColumn) -> DeviceColumn:
    """Concatenate a build/probe key column pair (strings pad to a common
    width so the byte matrices stack)."""
    bdat, pdat = bc.data, pc.data
    lengths = None
    if bc.is_string_like:
        w = max(bdat.shape[1], pdat.shape[1])
        if bdat.shape[1] < w:
            bdat = jnp.pad(bdat, ((0, 0), (0, w - bdat.shape[1])))
        if pdat.shape[1] < w:
            pdat = jnp.pad(pdat, ((0, 0), (0, w - pdat.shape[1])))
        lengths = jnp.concatenate([bc.lengths, pc.lengths])
    data = jnp.concatenate([bdat, pdat])
    validity = jnp.concatenate([bc.validity, pc.validity])
    return DeviceColumn(data, validity, bc.dtype, lengths)


def _column_code_arrays(col: DeviceColumn) -> List[jax.Array]:
    """1-D arrays whose tuple-equality equals Spark key-equality for this
    column (NaN == NaN, -0.0 == 0.0, strings by bytes+length); lexsorting by
    them (minor..major over the returned order) groups equal keys."""
    from ..columnar.device import pack_string_key_words
    v = col.data
    if col.is_string_like:
        return pack_string_key_words(v, col.lengths)
    if dt.is_d128(col.dtype):
        from ..expr.decimal128 import d128_key_words
        return d128_key_words(v)
    if jnp.issubdtype(v.dtype, jnp.floating):
        nan = jnp.isnan(v)
        v = jnp.where(v == 0, jnp.zeros_like(v), v)
        # NaN -> +inf for a total order; the nan flag keeps real +inf distinct
        v = jnp.where(nan, jnp.full_like(v, jnp.inf), v)
        return [v, nan]
    return [v]


def _join_codes(bcols: List[DeviceColumn], bactive: jax.Array,
                pcols: List[DeviceColumn], pactive: jax.Array
                ) -> Tuple[jax.Array, jax.Array]:
    """Dense int64 codes: equal key tuples <-> equal codes across both sides.

    Inactive/null-key rows get unique negative sentinels (never match).
    """
    nb = bactive.shape[0]
    npr = pactive.shape[0]
    code_arrays: List[jax.Array] = []   # major..minor
    anynull = jnp.zeros(nb + npr, dtype=bool)
    for bc, pc in zip(bcols, pcols):
        cat = _concat_key_col(bc, pc)
        code_arrays.extend(_column_code_arrays(cat))
        anynull = jnp.logical_or(anynull, jnp.logical_not(cat.validity))
    active = jnp.concatenate([bactive, pactive])
    usable = jnp.logical_and(active, jnp.logical_not(anynull))
    # lexsort takes minor..major; prepend reversed codes, usable-first primary
    keys = list(reversed(code_arrays))
    keys.append(jnp.logical_not(usable))
    order = jnp.lexsort(tuple(keys))
    usable_s = jnp.take(usable, order)
    # boundary among sorted usable rows (same logic as aggregate kernel)
    same = jnp.ones(nb + npr, dtype=bool)
    for arr in code_arrays:
        sv = jnp.take(arr, order)
        eq = sv == jnp.roll(sv, 1)
        eq = eq.at[0].set(False)
        same = jnp.logical_and(same, eq)
    boundary = jnp.logical_and(jnp.logical_not(same), usable_s)
    boundary = boundary.at[0].set(usable_s[0])
    gid_sorted = jnp.cumsum(boundary.astype(jnp.int64)) - 1
    # scatter back to original positions
    gid = jnp.zeros(nb + npr, dtype=jnp.int64).at[order].set(gid_sorted)
    iota = jnp.arange(nb + npr, dtype=jnp.int64)
    gid = jnp.where(usable, gid, -(iota + 2))  # unique non-matching sentinels
    return gid[:nb], gid[nb:]


def _co_locate(table: DeviceTable, ref: DeviceTable) -> DeviceTable:
    """Move ``table`` to ``ref``'s device when they differ (probe shards of
    an ICI exchange live one-per-chip; a jit cannot mix devices)."""
    try:
        td = next(iter(table.row_mask.devices()))
        rd = next(iter(ref.row_mask.devices()))
    except (AttributeError, TypeError):
        return table
    if td == rd:
        return table
    return jax.device_put(table, rd)


def _count_matches(bgid: jax.Array, pgid: jax.Array):
    """-> (b_order, b_sorted, starts, counts) for probe rows."""
    b_order = jnp.argsort(bgid)
    b_sorted = jnp.take(bgid, b_order)
    # sentinels are negative and unique so they contribute zero matches;
    # clamp probe sentinels to a value absent from build (-1)
    p = jnp.where(pgid < 0, jnp.full_like(pgid, -1), pgid)
    starts = jnp.searchsorted(b_sorted, p, side="left")
    ends = jnp.searchsorted(b_sorted, p, side="right")
    # build sentinels: strip them from matches (they sit < 0 in sorted order)
    counts = jnp.where(pgid < 0, 0, ends - starts)
    return b_order, starts.astype(jnp.int64), counts.astype(jnp.int64)


def _build_matched(bgid: jax.Array, pgid: jax.Array) -> jax.Array:
    """Per-build-row: does any probe row share its key? (right/full outer)."""
    p_sorted = jnp.sort(jnp.where(pgid < 0, jnp.full_like(pgid, -1), pgid))
    b = jnp.where(bgid < 0, jnp.full_like(bgid, -2), bgid)
    lo = jnp.searchsorted(p_sorted, b, side="left")
    hi = jnp.searchsorted(p_sorted, b, side="right")
    return jnp.logical_and(hi > lo, bgid >= 0)


def _gather_columns(table: DeviceTable, idx: jax.Array, matched: jax.Array
                    ) -> List[DeviceColumn]:
    cols = []
    for c in table.columns:
        g = c.gather(idx, keep_all_valid=True)
        cols.append(g.with_validity(jnp.logical_and(g.validity, matched)))
    return cols


def _null_device_column(dtype: dt.DataType, capacity: int) -> DeviceColumn:
    """All-null column of ``dtype`` (outer-join padding)."""
    from ..columnar.device import bucket_width
    if isinstance(dtype, (dt.StringType, dt.BinaryType)):
        return DeviceColumn(
            jnp.zeros((capacity, bucket_width(1)), dtype=jnp.uint8),
            jnp.zeros(capacity, dtype=bool), dtype,
            jnp.zeros(capacity, dtype=jnp.int32))
    if dt.is_d128(dtype):
        return DeviceColumn(jnp.zeros((capacity, 2), dtype=jnp.int64),
                            jnp.zeros(capacity, dtype=bool), dtype, None)
    np_dt = dtype.np_dtype()
    return DeviceColumn(jnp.zeros(capacity, dtype=np_dt),
                        jnp.zeros(capacity, dtype=bool), dtype, None)


_I64_MAX = np.int64(2**63 - 1)

#: both chain walks of the hash tier (the build's insertion loop, the
#: probe) run full rounds while more than this share of the capacity is
#: open, then one compaction and rounds that long: at a load of 0.5 a
#: quarter of the rows are open after the first round and a sixteenth
#: after the second. On the chip 16 beats 8 and 32 over Q3-shaped probes
#: of 2^19-2^20 rows (PERF.md section 6, PR 33)
_TAIL_SHARE = 16


def _chain_hashes(keys: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """-> (first bucket hash, odd step: a full cycle over a power-of-two
    table) of monotone-int64 keys, the same for build and probe."""
    from ..shuffle.manager import _fmix_device
    u = jax.lax.bitcast_convert_type(keys, jnp.uint64)
    lo = (u & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
    hi = (u >> jnp.uint64(32)).astype(jnp.uint32)
    h1 = _fmix_device(lo ^ _fmix_device(hi))
    return h1, _fmix_device(h1 ^ jnp.uint32(0x9E3779B9)) | jnp.uint32(1)


from ..conf import register_conf  # noqa: E402  (grouped with sibling confs)

JOIN_STRATEGY = register_conf(
    "spark.rapids.tpu.join.strategy",
    "Unique-build-key (FK->PK) join algorithm: 'sort' (sorted build keys "
    "+ searchsorted), 'hash' (open-addressing slot table; no lax.sort in "
    "build prep or probe), or 'auto' (hash off-CPU, where sort "
    "compilation can be pathologically slow). Multi-key and non-unique "
    "builds always use the sorted count path; 'auto' = hash (measured "
    "faster on CPU and sort-compile-free for TPU; reference analogue: "
    "cuDF hash join vs sort-merge).", "auto",
    checker=lambda v: None if str(v).lower() in ("auto", "sort", "hash")
    else "must be auto|sort|hash")


def _resolve_join_strategy() -> str:
    from ..session import TpuSession
    sess = TpuSession._active
    v = str(sess.conf.get(JOIN_STRATEGY)).lower() if sess is not None \
        else "auto"
    return "hash" if v == "auto" else v


def _monotone_i64(v: jax.Array) -> jax.Array:
    """Order- and equality-preserving map of a key column into int64
    (Spark key semantics: NaN == NaN, -0.0 == 0.0). Integers/bool/date/
    timestamp widen; floats use the IEEE monotone bit trick after
    canonicalizing -0.0 and NaN."""
    if v.dtype == jnp.bool_ or jnp.issubdtype(v.dtype, jnp.integer):
        return v.astype(jnp.int64)
    if v.dtype == jnp.float32:
        v = v.astype(jnp.float64)  # lossless widen
    v = jnp.where(v == 0, jnp.zeros_like(v), v)          # -0.0 -> +0.0
    v = jnp.where(jnp.isnan(v), jnp.full_like(v, jnp.nan), v)  # one NaN
    u = jax.lax.bitcast_convert_type(v, jnp.uint64)
    top = jnp.uint64(1) << jnp.uint64(63)
    mono = jnp.where((u & top) != 0, ~u, u | top)        # monotone uint64
    return jax.lax.bitcast_convert_type(mono ^ top, jnp.int64)


def _key_view(table: DeviceTable, keys: Sequence[str]) -> DeviceTable:
    """Table of only the join-key columns under canonical names — the
    schema-erased input of the shared count kernel."""
    from ..columnar.device import canonical_names
    cols = tuple(table.column(k) for k in keys)
    return DeviceTable(cols, table.row_mask, table.num_rows,
                       canonical_names(len(cols)))


class _JoinSchemaOnly:
    def __init__(self, schema: Schema):
        self.schema = schema


def _condition_mask(condition: Expression, table: DeviceTable) -> jax.Array:
    """Residual-condition boolean mask over an assembled pair table."""
    ctx = EvalContext.for_device(table)
    c = condition.eval(ctx)
    keep = c.values
    if c.validity is not None:
        keep = jnp.logical_and(keep, c.validity)
    return jnp.logical_and(keep, table.row_mask)


class _JoinKernels:
    """Builds the jitted count + expand kernels for a (schema, how) combo."""

    def __init__(self, exec_node: "TpuShuffledHashJoinExec"):
        self.node = exec_node

    def counts_fn(self):
        """Key-view based: takes tables holding ONLY the join-key columns
        (canonical names), so one compiled count program serves every join
        with the same key layout, regardless of payload schema."""
        def fn(build_keys: DeviceTable, probe_keys: DeviceTable):
            bgid, pgid = _join_codes(
                list(build_keys.columns), build_keys.row_mask,
                list(probe_keys.columns), probe_keys.row_mask)
            b_order, starts, counts = _count_matches(bgid, pgid)
            return b_order, starts, counts, bgid, pgid
        return fn

    def matched_fn(self):
        """No-condition right/full general path: this probe batch's
        per-build-row key-match mask (ORed into the running seen mask by
        the caller)."""
        def fn(bgid, pgid):
            return _build_matched(bgid, pgid)
        return fn

    def build_prep_fn(self):
        """Direct single-key fast path, build half: map keys into the
        monotone int64 domain and sort ONCE per build table
        (invalid/masked rows pushed to a +max tail). Probe batches then
        only pay searchsorted — no build+probe concat, no per-batch
        build re-sort, exact (no hash)."""
        def fn(build_keys: DeviceTable):
            bc = build_keys.columns[0]
            bmask = jnp.logical_and(bc.validity, build_keys.row_mask)
            bv = _monotone_i64(bc.data)
            inv_b = jnp.logical_not(bmask)
            b_order = jnp.lexsort((bv, inv_b))
            sv = jnp.where(jnp.take(inv_b, b_order), _I64_MAX,
                           jnp.take(bv, b_order))
            nvalid = jnp.sum(bmask.astype(jnp.int64))
            # PK detection: no adjacent duplicates among the valid prefix
            # -> every probe row matches at most one build row, unlocking
            # the sync-free fixed-capacity join path (pk_join_fn)
            iota = jnp.arange(sv.shape[0], dtype=jnp.int64)
            dup = jnp.logical_and(sv[1:] == sv[:-1], (iota[1:] < nvalid))
            unique = jnp.logical_not(jnp.any(dup))
            return b_order, sv, nvalid, unique
        return fn

    def build_prep_hash_fn(self):
        """SORT-FREE build prep: vectorized open-addressing insertion into
        a 2x-capacity slot table (double hashing; each while_loop round
        claims empty slots by minimum row index). No lax.sort anywhere
        (spark.rapids.tpu.join.strategy; reference analogue: cuDF's hash
        join build).

        The table holds ONE row a distinct key. Equal keys share their
        hash and step, so they contend for the same slot in the same round:
        when one of them claims it, the others see their own key in the
        winner and retire (``twin``) — which is also how duplicates are
        detected: ``unique`` is "no row ever retired as a twin". The PK fast
        path needs a unique build; semi / anti joins only ask existence and
        probe any build. (A duplicate that claimed a slot of its own would
        cost a round a row of the longest chain: 22-30 rounds over
        ``sf1.q4``'s 2^23 rows, moving with the seed.)

        A round costs by the rows it is run over, not by the rows still
        unplaced, and the last rounds place a handful. So full-capacity
        rounds run only while more than 1/``_TAIL_SHARE`` of the
        capacity is unplaced; the rest are compacted once and finish in
        rounds that long (``open_rows_by_rank``; the probe's walk retires
        and compacts its rows the same way). -> (slot_row, keys, unique,
        rounds, full_rounds): the trip counts ride on span ``join.prep``."""
        def fn(build_keys: DeviceTable):
            bc = build_keys.columns[0]
            bmask = jnp.logical_and(bc.validity, build_keys.row_mask)
            bv = _monotone_i64(bc.data)
            cap = bv.shape[0]
            T = 2 * cap                       # pow2 (capacity is pow2)
            tail_cap = max(cap // _TAIL_SHARE, 1)
            mask = jnp.uint32(T - 1)
            h1, step = _chain_hashes(bv)
            iota = jnp.arange(cap, dtype=jnp.int32)
            big = jnp.int32(cap)

            def insert(rows, keys, h, s):
                """The loop body over build rows ``rows`` (their keys,
                hashes and steps beside them)."""
                def body(state):
                    r, slot_row, active, dup = state
                    bucket = ((h + r.astype(jnp.uint32) * s) & mask) \
                        .astype(jnp.int32)
                    want = jnp.logical_and(active,
                                           jnp.take(slot_row, bucket) < 0)
                    claim = jax.ops.segment_min(jnp.where(want, rows, big),
                                                bucket, num_segments=T)
                    winner = jnp.take(claim, bucket)
                    lost = jnp.logical_and(want, winner != rows)
                    twin = jnp.logical_and(
                        lost,
                        jnp.take(bv, jnp.clip(winner, 0, cap - 1)) == keys)
                    slot_row = jnp.where(
                        jnp.logical_and(slot_row < 0, claim < big),
                        claim, slot_row)
                    # still to place: lost to another key, or slot taken
                    active = jnp.logical_and(
                        active, jnp.logical_or(
                            jnp.logical_not(want),
                            jnp.logical_and(lost, jnp.logical_not(twin))))
                    return (r + 1, slot_row, active,
                            jnp.logical_or(dup, jnp.any(twin)))
                return body

            def many_left(state):
                r, _, active, _ = state
                return jnp.logical_and(
                    jnp.sum(active, dtype=jnp.int32) > tail_cap, r < T)

            def any_left(state):
                r, _, active, _ = state
                return jnp.logical_and(jnp.any(active), r < T)

            full_rounds, slot_row, active, dup = jax.lax.while_loop(
                many_left, insert(iota, bv, h1, step),
                (jnp.int32(0), jnp.full(T, -1, jnp.int32), bmask,
                 jnp.zeros((), dtype=bool)))
            rows, live = open_rows_by_rank(active, iota, tail_cap)
            rounds, slot_row, _, dup = jax.lax.while_loop(
                any_left,
                insert(rows, jnp.take(bv, rows), jnp.take(h1, rows),
                       jnp.take(step, rows)),
                (full_rounds, slot_row, live, dup))
            return slot_row, bv, jnp.logical_not(dup), rounds, full_rounds
        return fn

    def probe_slots_fn(self):
        """The probe's chain walk alone: each usable probe row visits the
        slots of its double-hash chain until it meets its key (found) or
        an empty slot (absent). A *full round* visits one more slot for
        every row of the probe batch and runs only while more than
        1/``_TAIL_SHARE`` of the probe capacity is unresolved; the rows
        then still open are compacted once (``open_rows_by_rank``) and
        finish in *tail rounds* that long, whose hits are written back by
        row index: the walk costs by the rows that have anything left to
        do. A table built over duplicate keys holds one row a distinct
        key (``build_prep_hash_fn``), which answers existence. -> (found,
        bi = the build row found or 0, rounds, full_rounds): the trip
        counts ride on span ``join.probe.pk``."""
        def fn(slot_row, bv, pv, pmask):
            cap, cap_b, T = pv.shape[0], bv.shape[0], slot_row.shape[0]
            tail_cap = max(cap // _TAIL_SHARE, 1)
            mask = jnp.uint32(T - 1)
            h1, step = _chain_hashes(pv)

            def look(keys, h, s):
                """The loop body over the probe rows whose keys, hashes
                and steps these are."""
                def body(state):
                    r, unresolved, hit_row = state
                    bucket = ((h + r.astype(jnp.uint32) * s) & mask) \
                        .astype(jnp.int32)
                    row = jnp.take(slot_row, bucket)
                    empty = row < 0
                    eq = jnp.logical_and(
                        jnp.logical_not(empty),
                        jnp.take(bv, jnp.clip(row, 0, cap_b - 1)) == keys)
                    hit_row = jnp.where(jnp.logical_and(unresolved, eq),
                                        row, hit_row)
                    unresolved = jnp.logical_and(
                        unresolved,
                        jnp.logical_not(jnp.logical_or(empty, eq)))
                    return r + 1, unresolved, hit_row
                return body

            def many_open(state):
                r, unresolved, _ = state
                return jnp.logical_and(
                    jnp.sum(unresolved, dtype=jnp.int32) > tail_cap, r < T)

            def any_open(state):
                r, unresolved, _ = state
                return jnp.logical_and(jnp.any(unresolved), r < T)

            full_rounds, unresolved, hit_row = jax.lax.while_loop(
                many_open, look(pv, h1, step),
                (jnp.int32(0), pmask, jnp.full(cap, -1, jnp.int32)))
            rows, live = open_rows_by_rank(
                unresolved, jnp.arange(cap, dtype=jnp.int32), tail_cap)
            rounds, _, tail_hit = jax.lax.while_loop(
                any_open,
                look(jnp.take(pv, rows), jnp.take(h1, rows),
                     jnp.take(step, rows)),
                (full_rounds, live, jnp.take(hit_row, rows)))
            hit_row = hit_row.at[jnp.where(live, rows, cap)].set(
                tail_hit, mode="drop")
            return (hit_row >= 0, jnp.maximum(hit_row, 0), rounds,
                    full_rounds)
        return fn

    def pk_hash_join_fn(self, how: str):
        """Unique-build-key join via the hash slot table: each probe row
        walks its double-hash chain until an empty slot (absent) or a key
        match (``probe_slots_fn``: full rounds while many rows are open,
        tail rounds over the rest). Counts are 0/1; output capacity ==
        probe capacity; NO lax.sort in the program. -> (joined table,
        (rounds, full_rounds))."""
        node = self.node
        probe_slots = self.probe_slots_fn()

        def fn(build: DeviceTable, probe: DeviceTable,
               probe_keys: DeviceTable, slot_row, bv):
            pc = probe_keys.columns[0]
            pmask = jnp.logical_and(pc.validity, probe.row_mask)
            found, bi, rounds, full_rounds = probe_slots(
                slot_row, bv, _monotone_i64(pc.data), pmask)
            trips = (rounds, full_rounds)
            if how == "left_semi":
                return probe.filter_mask(found), trips
            if how == "left_anti":
                return probe.filter_mask(jnp.logical_not(found)), trips
            keep = found if how == "inner" else probe.row_mask
            pcols = [c.with_validity(jnp.logical_and(c.validity, keep))
                     for c in probe.columns]
            bcols = _gather_columns(build, bi, found)
            out_cols, names = node.assemble(pcols, bcols, found)
            out_mask = jnp.logical_and(keep, probe.row_mask)
            return DeviceTable(tuple(out_cols), out_mask,
                               jnp.sum(out_mask, dtype=jnp.int32),
                               tuple(names)), trips
        return fn

    def pk_join_fn(self, how: str):
        """Unique-build-key (FK->PK) join in ONE program: searchsorted
        lookup + gather, output capacity == probe capacity (counts are 0/1
        so no count sync, no windowing, no per-size expand recompiles —
        the hot TPC-H join shape; reference: GpuHashJoin's single-match
        gather specialization). -> (joined table, () — no trip counts)."""
        node = self.node

        def fn(build: DeviceTable, probe: DeviceTable,
               probe_keys: DeviceTable, b_order, sv, nvalid):
            pc = probe_keys.columns[0]
            pmask = jnp.logical_and(pc.validity, probe.row_mask)
            pv = _monotone_i64(pc.data)
            pos = jnp.searchsorted(sv, pv, side="left")
            safe = jnp.clip(pos, 0, sv.shape[0] - 1)
            found = jnp.logical_and(
                jnp.logical_and(pos < nvalid,
                                jnp.take(sv, safe) == pv), pmask)
            if how == "left_semi":
                return probe.filter_mask(found), ()
            if how == "left_anti":
                return probe.filter_mask(jnp.logical_not(found)), ()
            bi = jnp.take(b_order, safe).astype(jnp.int32)
            keep = found if how == "inner" else probe.row_mask
            pcols = [c.with_validity(jnp.logical_and(c.validity, keep))
                     for c in probe.columns]
            bcols = _gather_columns(build, bi, found)
            out_cols, names = node.assemble(pcols, bcols, found)
            mask = jnp.logical_and(keep, probe.row_mask)
            return DeviceTable(tuple(out_cols), mask,
                               jnp.sum(mask, dtype=jnp.int32),
                               tuple(names)), ()
        return fn

    def probe_count_fn(self, track: bool):
        """Direct path, probe half: two searchsorted passes clamped to the
        valid build prefix. Clamping makes sentinel collisions exact: for
        a probe key equal to the +max sentinel, the count still equals the
        number of VALID build rows holding that key (the tie region's
        valid entries all sit below ``nvalid``). ``track`` adds the
        per-build-row matched mask (right/full) from a probe-side sort."""
        def fn(b_order, sv, nvalid, probe_keys: DeviceTable):
            pc = probe_keys.columns[0]
            pmask = jnp.logical_and(pc.validity, probe_keys.row_mask)
            pv = _monotone_i64(pc.data)
            starts = jnp.minimum(
                jnp.searchsorted(sv, pv, side="left"), nvalid)
            ends = jnp.minimum(
                jnp.searchsorted(sv, pv, side="right"), nvalid)
            counts = jnp.where(pmask, ends - starts, 0)
            if track:
                pinv = jnp.logical_not(pmask)
                ps = jnp.sort(jnp.where(pinv, _I64_MAX, pv))
                pn = jnp.sum(pmask.astype(jnp.int64))
                lo = jnp.minimum(jnp.searchsorted(ps, sv, side="left"), pn)
                hi = jnp.minimum(jnp.searchsorted(ps, sv, side="right"), pn)
                iota = jnp.arange(sv.shape[0], dtype=jnp.int64)
                matched_s = jnp.logical_and(hi > lo, iota < nvalid)
                matched = jnp.zeros(sv.shape[0], dtype=bool) \
                    .at[b_order].set(matched_s)
            else:
                matched = jnp.zeros(sv.shape[0], dtype=bool)
            return starts.astype(jnp.int64), counts.astype(jnp.int64), \
                matched
        return fn

    def _slots(self, build, probe, b_order, starts, counts, out_cap, outer):
        """Common slot math: per-output-slot probe index, build index,
        valid/matched flags. Each non-empty probe row owns the run of slots
        from its offset: a 1 scattered at every run's start, prefix-summed
        over the slots, is the owner's rank among the non-empty rows, and
        ``open_rows_by_rank`` turns the rank into the row. O(out_cap), where
        a ``searchsorted`` of the slots in the running counts gathers the
        whole output once per level of a log2(probe rows) binary search.
        Slots at or past ``total`` map to some row and are masked."""
        slot_counts = jnp.maximum(counts, 1) if outer else counts
        slot_counts = jnp.where(probe.row_mask, slot_counts, 0)
        cum = prefix_sum(slot_counts)
        total = cum[-1]
        offsets = cum - slot_counts
        nonempty = slot_counts > 0
        iota = jnp.arange(probe.capacity, dtype=jnp.int32)
        rows, _ = open_rows_by_rank(nonempty, iota, probe.capacity)
        # offsets of non-empty rows are distinct and below total <= out_cap
        heads = jnp.where(nonempty, offsets, out_cap).astype(jnp.int32)
        flags = jnp.zeros(out_cap, jnp.int32).at[heads].set(1, mode="drop")
        rank = jnp.clip(prefix_sum(flags) - 1, 0, probe.capacity - 1)
        pi = jnp.take(rows, rank)
        j = jnp.arange(out_cap, dtype=jnp.int64)
        k = j - jnp.take(offsets, pi)
        has_match = jnp.take(counts, pi) > 0
        b_sorted_pos = jnp.take(starts, pi) + k
        b_sorted_pos = jnp.clip(b_sorted_pos, 0, build.capacity - 1)
        bi = jnp.take(b_order, b_sorted_pos)
        valid_slot = j < total
        build_matched = jnp.logical_and(valid_slot, has_match)
        return pi.astype(jnp.int32), bi.astype(jnp.int32), valid_slot, \
            build_matched, total

    def expand_fn(self, out_cap: int, how: str):
        """Expand without a residual condition. ``left``/``full`` keep
        unmatched probe rows inline; ``right`` behaves as inner here (its
        unmatched build rows are emitted by leftover_fn at the end)."""
        node = self.node

        def fn(build: DeviceTable, probe: DeviceTable, b_order, starts,
               counts):
            outer = how in ("left", "full")
            pi, bi, valid_slot, build_matched, total = self._slots(
                build, probe, b_order, starts, counts, out_cap, outer)
            pcols = _gather_columns(probe, pi, valid_slot)
            bcols = _gather_columns(build, bi, build_matched)
            out_cols, names = node.assemble(pcols, bcols, build_matched)
            return DeviceTable(tuple(out_cols), valid_slot,
                               total.astype(jnp.int32), tuple(names))
        return fn

    def expand_cond_fn(self, out_cap: int, how: str):
        """Expand WITH a residual condition, outer-correct: candidate pairs
        are inner-expanded, the condition filters pairs, and probe rows
        whose every candidate failed are re-emitted null-padded (left/full)
        — the matched-flag fixup of reference GpuHashJoin.scala:507. Returns
        (pairs_table[, pad_table][, seen_update]) depending on ``how``."""
        node = self.node
        condition = node.condition

        def fn(build: DeviceTable, probe: DeviceTable, b_order, starts,
               counts):
            pi, bi, valid_slot, _, total = self._slots(
                build, probe, b_order, starts, counts, out_cap, outer=False)
            if how in ("left_semi", "left_anti"):
                # pair evaluation only needs the CONDITION's referenced
                # columns — never assemble the full pair table (q21's
                # semi/anti pairs would otherwise gather every payload
                # column per candidate match)
                refs = condition.references()
                lnames = [n for n in node.left.schema.names if n in refs]
                rnames = [n for n in node.right.schema.names if n in refs]
                cols = tuple(
                    [probe.column(n).gather(pi, keep_all_valid=True)
                     .with_validity(
                        jnp.logical_and(
                            jnp.take(probe.column(n).validity, pi),
                            valid_slot)) for n in lnames]
                    + [build.column(n).gather(bi, keep_all_valid=True)
                       .with_validity(
                        jnp.logical_and(
                            jnp.take(build.column(n).validity, bi),
                            valid_slot)) for n in rnames])
                pairs = DeviceTable(cols, valid_slot,
                                    total.astype(jnp.int32),
                                    tuple(lnames + rnames))
                keep = _condition_mask(condition, pairs)
                keep = jnp.logical_and(keep, valid_slot)
                any_pass = jnp.zeros(probe.capacity, dtype=bool) \
                    .at[pi].max(keep, mode="drop")
                keep_rows = jnp.logical_not(any_pass) \
                    if how == "left_anti" else any_pass
                return probe.filter_mask(keep_rows)
            pcols = _gather_columns(probe, pi, valid_slot)
            bcols = _gather_columns(build, bi, valid_slot)
            out_cols, names = node.assemble(pcols, bcols, valid_slot)
            pairs = DeviceTable(tuple(out_cols), valid_slot,
                                total.astype(jnp.int32), tuple(names))
            keep = _condition_mask(condition, pairs)
            pairs = pairs.filter_mask(keep)
            keep = jnp.logical_and(keep, valid_slot)
            any_pass = jnp.zeros(probe.capacity, dtype=bool).at[pi].max(
                keep, mode="drop")
            outs = [pairs]
            if how in ("left", "full"):
                unmatched = jnp.logical_and(probe.row_mask,
                                            jnp.logical_not(any_pass))
                outs.append(node.pad_probe(probe, unmatched))
            if how in ("right", "full"):
                seen_upd = jnp.zeros(build.capacity, dtype=bool).at[bi].max(
                    keep, mode="drop")
                outs.append(seen_upd)
            return tuple(outs)
        return fn

    def semi_mask_fn(self, anti: bool):
        def fn(probe: DeviceTable, counts):
            keep = counts == 0 if anti else counts > 0
            return probe.filter_mask(keep)
        return fn

    def leftover_fn(self):
        """Final right/full emission: build rows no probe row matched,
        null-padded on the probe side."""
        node = self.node

        def fn(build: DeviceTable, seen: jax.Array):
            emit = jnp.logical_and(build.row_mask, jnp.logical_not(seen))
            return node.pad_build(build, emit)
        return fn


class TpuShuffledHashJoinExec(TpuExec):
    """Equi-join: build side = right child, probe side = left child.

    right/full outer track a per-build-row ``seen`` mask across probe
    batches and emit never-matched build rows null-padded at the end —
    sound per partition because the upstream hash exchange gives each
    partition disjoint key ranges (reference GpuHashJoin.scala:507
    HashedExistenceJoinIterator / buildSideTrackerOpt)."""

    SUPPORTED = ("inner", "left", "right", "full", "left_semi", "left_anti")
    EXTRA_METRICS = (M.JOIN_TIME,)

    def __init__(self, left: PhysicalPlan, right: PhysicalPlan,
                 left_keys: Sequence[str], right_keys: Sequence[str],
                 how: str, condition: Optional[Expression], merge_keys: bool,
                 min_bucket: Optional[int] = None,
                 batch_bytes: int = 512 * 1024 * 1024):
        super().__init__()
        assert how in self.SUPPORTED, how
        self.left, self.right = left, right
        self.children = (left, right)
        self.left_keys = list(left_keys)
        self.right_keys = list(right_keys)
        self.how = how
        self.condition = condition
        self.merge_keys = merge_keys
        self.min_bucket = resolve_min_bucket(min_bucket)
        self.batch_bytes = batch_bytes
        on = self.left_keys if merge_keys else None
        self.schema = _join_schema(left.schema, right.schema, on, how)
        self._kernels = _JoinKernels(self)
        # prep entries, one a live build table (``_prep_slot``); the lock
        # guards the dict alone
        self._preps: dict = {}
        self._prep_lock = threading.Lock()

    @property
    def num_partitions(self) -> int:
        return self.left.num_partitions

    def node_desc(self):
        return f"{self.how} lkeys={self.left_keys} rkeys={self.right_keys}"

    def plan_signature(self) -> str:
        return (f"Join|{self.how}|{self.left_keys}|{self.right_keys}|"
                f"{self.merge_keys}|{self.condition!r}|"
                f"{self.left.schema!r}|{self.right.schema!r}")

    def _canon(self) -> Tuple["TpuShuffledHashJoinExec", str]:
        """Schema-erased clone + cache key (see aggregate._canon_exec):
        left columns a0..aN, right b0..bM, keys by position. Gather/assemble
        kernels built from the clone are shared by every join with the same
        (how, key positions, merge, column counts); dtype/shape differences
        retrace inside the shared jax.jit wrapper. Residual-condition
        kernels keep name-based keys (conditions reference real names)."""
        if getattr(self, "_canon_cache", None) is not None:
            return self._canon_cache
        lf = list(self.left.schema.fields)
        rf = list(self.right.schema.fields)
        lpos = {f.name: i for i, f in enumerate(lf)}
        rpos = {f.name: i for i, f in enumerate(rf)}
        clone = TpuShuffledHashJoinExec.__new__(TpuShuffledHashJoinExec)
        TpuExec.__init__(clone)
        clone.left = _JoinSchemaOnly(Schema(
            [Field(f"a{i}", f.dtype, f.nullable) for i, f in enumerate(lf)]))
        clone.right = _JoinSchemaOnly(Schema(
            [Field(f"b{i}", f.dtype, f.nullable) for i, f in enumerate(rf)]))
        clone.children = (clone.left, clone.right)
        clone.left_keys = [f"a{lpos[k]}" for k in self.left_keys]
        clone.right_keys = [f"b{rpos[k]}" for k in self.right_keys]
        clone.how = self.how
        clone.condition = None
        clone.merge_keys = self.merge_keys
        clone.min_bucket = self.min_bucket
        clone.batch_bytes = self.batch_bytes
        clone.schema = self.schema
        clone._kernels = _JoinKernels(clone)
        key = (f"JoinC|{self.how}|{[lpos[k] for k in self.left_keys]}|"
               f"{[rpos[k] for k in self.right_keys]}|{self.merge_keys}|"
               f"nl{len(lf)}|nr{len(rf)}")
        self._canon_cache = (clone, key)
        return self._canon_cache

    # -- column assembly (traced inside expand kernel) ------------------------
    def assemble(self, pcols: List[DeviceColumn], bcols: List[DeviceColumn],
                 build_matched: jax.Array, key_from_build: bool = False):
        """``key_from_build`` routes merged ``on=`` key columns from the
        build side — used for right/full leftover rows whose probe side is
        all-null (the coalesce step of the reference's full-outer key
        handling)."""
        lnames = list(self.left.schema.names)
        rnames = list(self.right.schema.names)
        names: List[str] = []
        cols: List[DeviceColumn] = []
        if self.merge_keys:
            for lk, rk in zip(self.left_keys, self.right_keys):
                src = bcols[rnames.index(rk)] if key_from_build \
                    else pcols[lnames.index(lk)]
                cols.append(src)
                names.append(lk)
            skip_l = set(self.left_keys)
            skip_r = set(self.right_keys)
        else:
            skip_l = set()
            skip_r = set()
        for n, c in zip(lnames, pcols):
            if n not in skip_l:
                names.append(n)
                cols.append(c)
        for n, c in zip(rnames, bcols):
            if n not in skip_r:
                names.append(n)
                cols.append(c)
        return cols, names

    # -- null-padded emission (outer-join fixup rows) -------------------------
    def pad_probe(self, probe: DeviceTable, emit: jax.Array) -> DeviceTable:
        """Probe rows with an all-null build side (left/full unmatched)."""
        bcols = [_null_device_column(f.dtype, probe.capacity)
                 for f in self.right.schema]
        pcols = [c.with_validity(jnp.logical_and(c.validity, emit))
                 for c in probe.columns]
        out_cols, names = self.assemble(pcols, bcols,
                                        jnp.zeros(probe.capacity, dtype=bool))
        return DeviceTable(tuple(out_cols), emit,
                           jnp.sum(emit, dtype=jnp.int32), tuple(names))

    def pad_build(self, build: DeviceTable, emit: jax.Array) -> DeviceTable:
        """Build rows with an all-null probe side (right/full leftover)."""
        pcols = [_null_device_column(f.dtype, build.capacity)
                 for f in self.left.schema]
        bcols = [c.with_validity(jnp.logical_and(c.validity, emit))
                 for c in build.columns]
        out_cols, names = self.assemble(pcols, bcols, emit,
                                        key_from_build=True)
        return DeviceTable(tuple(out_cols), emit,
                           jnp.sum(emit, dtype=jnp.int32), tuple(names))

    # -- execution ------------------------------------------------------------
    def _concat_build(self, batches: List[DeviceTable]) -> DeviceTable:
        from ..memory.retry import with_retry
        if not batches:
            from .aggregate import _empty_device_table
            return _empty_device_table(self.right.schema, self.min_bucket)
        if len(batches) == 1:
            return batches[0]
        # build sides are unsplittable (the probe needs the WHOLE build
        # table in one piece) — spill-only retry, no split escalation
        return with_retry(concat_device_tables, batches,
                          scope="join-build", context=self.node_desc())

    def _max_out_rows(self) -> int:
        """Gather-output row budget derived from the byte budget."""
        row_bytes = 0
        for f in self.schema:
            if isinstance(f.dtype, (dt.StringType, dt.BinaryType)):
                row_bytes += 32  # width varies; assume a modest string
            else:
                row_bytes += f.dtype.np_dtype().itemsize
            row_bytes += 1  # validity
        return max(self.min_bucket, self.batch_bytes // max(row_bytes, 1))

    def execute_columnar(self, pidx: int) -> Iterator[DeviceTable]:
        from .fallback import quarantine_on_failure
        # note-only boundary: the probe needs the whole build table, so a
        # terminal failure can't fall back per-batch — but it quarantines
        with quarantine_on_failure(self):
            for out in self._join_batches(pidx):
                self.account_batch()
                yield out

    def _open_build(self, pidx: int):
        """-> (build table, its SpillableDeviceTable or None where the
        table is over the batch budget and the join goes grace,
        close_when_done). Span ``join.build`` (``rows`` = capacity,
        ``bytes``), one a build table: the child's drain and the
        ``srt_concat`` dispatch nest inside it."""
        from ..memory.catalog import SpillPriorities, get_catalog
        with get_tracer().span("join.build", "join") as span:
            build = self._concat_build(
                list(_device_batches(self.right, pidx)))
            span.note(rows=build.capacity, bytes=build.nbytes())
            if build.nbytes() > self.batch_bytes:
                return build, None, False
            return build, get_catalog().register(
                build, SpillPriorities.ACTIVE_ON_DECK), True

    def _join_batches(self, pidx: int) -> Iterator[DeviceTable]:
        build, handle, own = self._open_build(pidx)
        if handle is None:
            yield from self._grace_join(build, pidx)
            return
        build_cap = build.capacity
        del build  # the catalog handle is the owner from here on
        track = self.how in ("right", "full")
        seen_box = [jnp.zeros(build_cap, dtype=bool)] if track else None
        try:
            yield from self._probe_join(
                handle, _device_batches(self.left, pidx), seen_box)
            if track:
                leftover = self._leftover_fn()
                with handle as build:
                    yield leftover(build, seen_box[0])
        finally:
            if own:
                handle.close()
                self._close_preps(handle)

    def _leftover_fn(self):
        """Cached canonical leftover kernel (right/full build-side rows).
        Left-side dtypes go in the key: the null probe columns are built
        from them at trace time."""
        clone, ckey = self._canon()
        lkey = (ckey + "|leftover|"
                + ",".join(repr(f.dtype) for f in self.left.schema.fields))
        fn = cached_jit(lkey, clone._kernels.leftover_fn,
                        name="join_leftover")
        out_names = tuple(self.schema.names)

        def run(build: DeviceTable, seen) -> DeviceTable:
            return fn(build.canonical(), seen).with_names(out_names)
        return run

    def _direct_key_ok(self) -> bool:
        """Single-key joins on identical non-nested, non-string dtypes use
        the sort-build-once searchsorted count path."""
        if len(self.left_keys) != 1:
            return False
        lt = self.left.schema.field(self.left_keys[0]).dtype
        rt = self.right.schema.field(self.right_keys[0]).dtype
        bad = (dt.StringType, dt.BinaryType, dt.ArrayType)
        return lt == rt and not isinstance(lt, bad) and not dt.is_d128(lt)

    def _counts_fn(self, owner, track: bool = False):
        """Shared count kernel over key views -> (b_order, starts, counts,
        matched_or_None). One program per key LAYOUT (count of keys +
        direct/general + track), retraced per dtype/capacity inside the
        shared jit. ``owner`` is the spill handle of the build table the
        returned callable will be handed (its prep entry's key)."""
        lkeys, rkeys = self.left_keys, self.right_keys
        if self._direct_key_ok():
            cnt = cached_jit(f"JoinC|probeD|t{int(track)}",
                             lambda: self._kernels.probe_count_fn(track),
                             name="join_probe_count")

            def run(build: DeviceTable, probe: DeviceTable):
                b_order, sv, nvalid, _uniq = self._get_prep(build, owner)
                starts, counts, matched = cnt(b_order, sv, nvalid,
                                              _key_view(probe, lkeys))
                return b_order, starts, counts, (matched if track else None)
            return run
        fn = cached_jit(f"JoinC|counts|k{len(lkeys)}",
                        self._kernels.counts_fn, name="join_counts")
        matched_fn = cached_jit("JoinC|matched", self._kernels.matched_fn,
                                name="join_matched") \
            if track else None

        def run(build: DeviceTable, probe: DeviceTable):
            b_order, starts, counts, bgid, pgid = fn(
                _key_view(build, rkeys), _key_view(probe, lkeys))
            matched = matched_fn(bgid, pgid) if track else None
            return b_order, starts, counts, matched
        return run

    def _prep_slot(self, owner) -> "_PrepSlot":
        """The prep entry of the build table behind spill handle ``owner``:
        one entry a live build table of this node (a shuffled join has one
        build table a partition, and the mesh exchange's map side runs the
        partitions together; a broadcast join has the one every probe
        partition shares). The node's lock guards the dict alone: what is
        held across a prep's dispatch and its ``resolve_scalars`` is the
        ENTRY's lock, which only probes of the same build table wait on."""
        with self._prep_lock:
            slot = self._preps.get(id(owner))
            if slot is None:
                slot = self._preps[id(owner)] = _PrepSlot(owner)
            return slot

    def _close_preps(self, owner) -> None:
        """Close the preps of a build table with it (its owner calls this
        where it closes the build's handle); a no-op for a table that was
        never prepped."""
        with self._prep_lock:
            slot = self._preps.pop(id(owner), None)
        if slot is not None:
            for hit in (slot.hash, slot.dense):
                if hit is not None:
                    _close_quietly(hit[1][0])

    def _get_prep_hash(self, build: DeviceTable, owner):
        """Per-build-table HASH prep (slot table + key array + uniqueness),
        cached like the sorted prep; no lax.sort in the prep program. A
        miss books span ``join.prep`` (``rows``, ``unique``, ``rounds``,
        ``full_rounds``), a hit nothing."""
        prep = cached_jit("JoinC|prepH", self._kernels.build_prep_hash_fn,
                          name="join_prep_hash")
        slot = self._prep_slot(owner)
        with slot.lock:
            hit = slot.hash
            if hit is None or hit[0] is not build.row_mask:
                with get_tracer().span("join.prep", "join",
                                       rows=build.capacity) as span:
                    slot_row, bv, unique, rounds, full_rounds = prep(
                        _key_view(build, self.right_keys))
                    handle = self._register_prep_hash(slot_row, bv)
                    # uniqueness gates the PK fast path: one batched-funnel
                    # transfer per build table (cached across probe
                    # batches/partitions); the insertion loop's trip counts
                    # ride in it
                    uniq, rounds, full_rounds = resolve_scalars(
                        unique, rounds, full_rounds)
                    span.note(unique=bool(uniq), rounds=int(rounds),
                              full_rounds=int(full_rounds))
                if hit is not None:     # the table came back from a spill
                    _close_quietly(hit[1][0])
                hit = slot.hash = (build.row_mask, (handle, bool(uniq)))
        handle, unique = hit[1]
        pt = handle.get()
        cap = pt.capacity // 2
        return pt.columns[0].data, pt.columns[1].data[:cap], unique

    def _register_prep_hash(self, slot_row, bv):
        from ..columnar.device import canonical_names
        from ..memory.catalog import SpillPriorities, get_catalog
        T = slot_row.shape[0]
        bv_padded = jnp.pad(bv, (0, T - bv.shape[0]))
        ones = jnp.ones(T, dtype=bool)
        cols = (DeviceColumn(slot_row, ones, dt.IntegerType(), None),
                DeviceColumn(bv_padded, ones, dt.LongType(), None))
        t = DeviceTable(cols, ones, jnp.asarray(T, jnp.int32),
                        canonical_names(2))
        h = get_catalog().register(t, SpillPriorities.ACTIVE_ON_DECK)
        self._own_spill_handle(h)
        return h

    def _get_prep(self, build: DeviceTable, owner):
        """Per-build-table sorted-key prep: (b_order, sv, nvalid, unique).

        Cached on the node, one entry a live build table, keyed by the
        build's spill handle ``owner`` (``_prep_slot``): a broadcast join
        re-enters _probe_join once per probe partition with the SAME build
        table, and the prep must survive across those entries; the
        partitions of a shuffled join each have a build table of their own
        and may run at once. A hit is the entry's table being this one
        (``row_mask`` identity: a table restored from a spill is prepped
        again and replaces the entry); the entry is closed with its build
        (``_close_preps``). The sorted-key arrays live in a catalog-
        registered spillable so memory pressure can evict them. ``unique``
        is host-synced once per build (it gates the PK fast path). A miss
        books span ``join.prep`` (``rows``, ``unique``)."""
        prep = cached_jit("JoinC|prepD", self._kernels.build_prep_fn,
                          name="join_prep_dense")
        slot = self._prep_slot(owner)
        with slot.lock:
            hit = slot.dense
            if hit is None or hit[0] is not build.row_mask:
                with get_tracer().span("join.prep", "join",
                                       rows=build.capacity) as span:
                    pr = self._register_prep(
                        prep(_key_view(build, self.right_keys)))
                    span.note(unique=pr[2])
                if hit is not None:     # the table came back from a spill
                    _close_quietly(hit[1][0])
                hit = slot.dense = (build.row_mask, pr)
        handle, nvalid, unique = hit[1]
        pt = handle.get()
        return pt.columns[0].data, pt.columns[1].data, nvalid, unique

    def _register_prep(self, pr):
        """(b_order, sv, nvalid, unique) -> (spill handle, nvalid,
        unique_bool): the sorted build-key arrays go through the
        BufferCatalog so memory pressure can evict them like any other
        device buffer; the uniqueness flag syncs to a host bool here (one
        tiny transfer per build table)."""
        from ..columnar.device import canonical_names
        from ..memory.catalog import SpillPriorities, get_catalog
        b_order, sv, nvalid, unique = pr
        cap = sv.shape[0]
        ones = jnp.ones(cap, dtype=bool)
        cols = (DeviceColumn(b_order, ones, dt.LongType(), None),
                DeviceColumn(sv, ones, dt.LongType(), None))
        t = DeviceTable(cols, ones, jnp.asarray(cap, jnp.int32),
                        canonical_names(2))
        h = get_catalog().register(t, SpillPriorities.ACTIVE_ON_DECK)
        self._own_spill_handle(h)
        (uniq,) = resolve_scalars(unique)
        return (h, nvalid, bool(uniq))

    def _probe_join(self, build_handle, probe_batches, seen_box=None
                    ) -> Iterator[DeviceTable]:
        """Join probe batches against one spillable build table.

        ``seen_box`` (right/full) is a one-element list holding the running
        per-build-row matched mask, updated in place across batches.
        """
        has_cond = self.condition is not None
        track = seen_box is not None and not has_cond
        counts_fn = self._counts_fn(build_handle, track=track)
        pk_eligible = (not has_cond and self._direct_key_ok()
                       and self.how in ("inner", "left", "left_semi",
                                        "left_anti"))
        tracer = get_tracer()
        for probe in probe_batches:
            with self.metrics.timed(M.JOIN_TIME), build_handle as build:
                probe = _co_locate(probe, build)
                pk = self._pk_program(build, build_handle) \
                    if pk_eligible else None
                if pk is not None:
                    # FK->PK or existence: counts are 0/1, output fits the
                    # probe capacity — one fused program, no count sync
                    fused, prep = pk
                    # selective joins keep the probe CAPACITY with a mask;
                    # shrink (one int sync) so downstream sorts/groupbys
                    # don't run over dead padding
                    shrinks = self.how in ("inner", "left_semi", "left_anti")
                    n = None
                    with tracer.span("join.probe.pk", "join",
                                     rows=probe.capacity) as span:
                        out, trips = fused(
                            build.canonical(), probe.canonical(),
                            _key_view(probe, self.left_keys), *prep)
                        out = out.with_names(
                            self.schema.names if self.how in ("inner", "left")
                            else probe.names)
                        if shrinks and out.capacity > self.min_bucket:
                            # the hash walk's trip counts ride in the
                            # transfer that reads the shrink's row count
                            # (``rows_out``: beside ``rows``, the share
                            # of the probe batch that matched)
                            n, *trips = resolve_scalars(out.num_rows, *trips)
                            span.note(rows_out=int(n))
                            if trips:
                                span.note(rounds=int(trips[0]),
                                          full_rounds=int(trips[1]))
                    if shrinks:
                        out = shrink_to_fit(out, self.min_bucket, num_rows=n)
                    yield out
                    continue
                if seen_box is not None and hasattr(seen_box[0], "devices") \
                        and hasattr(build.row_mask, "devices") \
                        and seen_box[0].devices() != build.row_mask.devices():
                    seen_box[0] = jax.device_put(
                        seen_box[0], next(iter(build.row_mask.devices())))
                if self.how in ("left_semi", "left_anti") and not has_cond:
                    with tracer.span("join.probe.semi", "join",
                                     rows=probe.capacity):
                        _, _, counts, _ = counts_fn(build, probe)
                        anti = self.how == "left_anti"
                        fn = cached_jit(
                            f"JoinC|semi|{anti}",
                            lambda: self._kernels.semi_mask_fn(anti),
                            name="join_semi")
                        out = fn(probe.canonical(), counts) \
                            .with_names(probe.names)
                    yield out
                    continue
                yield from self._probe_expand(build, probe, counts_fn,
                                              seen_box)

    def _pk_program(self, build: DeviceTable, owner):
        """-> (the fused single-match / existence program, the prepared
        arrays of this build table it takes last), or None where the
        build's keys repeat and the join needs every match (the counts +
        expand path). The program returns (joined table, trip counts):
        (rounds, full_rounds) of the hash tier's chain walk, none of the
        sorted tier's. Runs the build's prep on its first call."""
        clone, ckey = self._canon()
        if _resolve_join_strategy() == "hash":
            # sort-free tier: open-addressing slot table. semi/anti only
            # ask EXISTENCE, so duplicate build keys are fine (the chain
            # walk finds any representative); inner/left need uniqueness
            # for the single-match gather
            slot_row, bv, unique = self._get_prep_hash(build, owner)
            if not (unique or self.how in ("left_semi", "left_anti")):
                return None
            fused = cached_jit(
                ckey + f"|pkh|{self.how}",
                lambda: clone._kernels.pk_hash_join_fn(self.how),
                name="join_pk_hash")
            return fused, (slot_row, bv)
        b_order, sv, nvalid, unique = self._get_prep(build, owner)
        if not unique:
            return None
        fused = cached_jit(
            ckey + f"|pk|{self.how}",
            lambda: clone._kernels.pk_join_fn(self.how),
            name="join_pk")
        return fused, (b_order, sv, nvalid)

    def _expand_total(self, probe: DeviceTable, counts) -> dict:
        """The output's slot total of a probe batch or window, read in one
        batched-funnel transfer (the decision boundary that sizes the
        expand), as ``{"rows_out": total}``. An outer join without a
        condition gives each live probe row with no match one null-extended
        slot; their count rides in the same transfer as ``unmatched``."""
        live = jnp.sum(jnp.where(probe.row_mask, counts, 0))
        if not (self.how in ("left", "full") and self.condition is None):
            (total,) = resolve_scalars(live)
            return {"rows_out": int(total)}
        unmatched = jnp.sum(jnp.logical_and(probe.row_mask, counts == 0))
        total, unmatched = resolve_scalars(live + unmatched, unmatched)
        return {"rows_out": int(total), "unmatched": int(unmatched)}

    def _probe_expand(self, build: DeviceTable, probe: DeviceTable,
                      counts_fn, seen_box) -> Iterator[DeviceTable]:
        """Counts, the ``total`` sync that sizes the output, and the expand
        of one probe batch or window: span ``join.probe.expand``, closed
        before anything is yielded, with ``rows_out`` = the rows the expand
        emits (and ``unmatched`` for an outer join). An oversized gather
        goes on in probe row windows, each a span of its own that books
        them instead."""
        with get_tracer().span("join.probe.expand", "join",
                               rows=probe.capacity) as span:
            b_order, starts, counts, matched = counts_fn(build, probe)
            if matched is not None:
                seen_box[0] = jnp.logical_or(seen_box[0], matched)
            out_rows = self._expand_total(probe, counts)
            total = out_rows["rows_out"]
            max_out = self._max_out_rows()
            outs = None
            if total <= max_out:
                span.note(**out_rows)
                out_cap = bucket_rows(max(total, 1), self.min_bucket)
                outs = self._expand_one(build, probe, b_order, starts,
                                        counts, out_cap, seen_box)
        if outs is None:
            # oversized gather: emit in probe row windows (reference:
            # AbstractGpuJoinIterator sub-partitions the gather)
            outs = self._windowed_expand(build, probe, total, max_out,
                                         counts_fn, seen_box)
        yield from outs

    def _expand_one(self, build, probe, b_order, starts, counts, out_cap,
                    seen_box) -> List[DeviceTable]:
        """One expand call on a probe batch/window (post-count)."""
        how = self.how
        out_names = tuple(self.schema.names)
        if self.condition is None:
            # right behaves as inner here; leftover_fn emits its outer rows
            eff = {"right": "inner", "full": "left"}.get(how, how)
            clone, ckey = self._canon()
            expand = cached_jit(
                ckey + f"|expand{out_cap}|{eff}",
                lambda: clone._kernels.expand_fn(out_cap, eff),
                name="join_expand")
            return [expand(build.canonical(), probe.canonical(), b_order,
                           starts, counts).with_names(out_names)]
        if how == "inner":
            clone, ckey = self._canon()
            expand = cached_jit(
                ckey + f"|expand{out_cap}|inner",
                lambda: clone._kernels.expand_fn(out_cap, "inner"),
                name="join_expand")
            out = expand(build.canonical(), probe.canonical(), b_order,
                         starts, counts).with_names(out_names)
            cond_fn = cached_jit(self.plan_signature() + "|cond",
                                 lambda: _condition_filter_fn(self.condition),
                                 name="join_cond")
            return [cond_fn(out)]
        fn = cached_jit(self.plan_signature() + f"|condexpand{out_cap}",
                        lambda: self._kernels.expand_cond_fn(out_cap, how),
                        name="join_expand_cond")
        res = fn(build, probe, b_order, starts, counts)
        if how in ("left_semi", "left_anti"):
            return [res]
        outs = list(res) if isinstance(res, tuple) else [res]
        if how in ("right", "full"):
            seen_upd = outs.pop()  # last element by expand_cond_fn contract
            seen_box[0] = jnp.logical_or(seen_box[0], seen_upd)
        return outs

    def _windowed_expand(self, build: DeviceTable, probe: DeviceTable,
                         total: int, max_out: int, counts_fn, seen_box=None
                         ) -> Iterator[DeviceTable]:
        probe = probe.compact()
        (nrows,) = resolve_scalars(probe.num_rows)
        nrows = max(1, int(nrows))
        # size windows by average multiplicity; skewed windows re-split below
        avg_mult = max(1.0, total / nrows)
        wsize = bucket_rows(max(self.min_bucket, int(max_out / avg_mult)),
                            self.min_bucket)
        outer_slots = self.how in ("left", "full") and self.condition is None
        start = 0
        while start < nrows:
            window = slice_rows(probe, start, wsize)
            start += wsize
            outs = None
            with get_tracer().span("join.probe.expand", "join",
                                   rows=window.capacity) as span:
                b_order, starts, counts, _ = counts_fn(build, window)
                out_rows = self._expand_total(window, counts)
                wtotal = out_rows["rows_out"]
                if wtotal == 0 and not outer_slots \
                        and self.condition is None \
                        and self.how not in ("left_semi", "left_anti"):
                    continue
                if wtotal <= 2 * max_out or wsize <= self.min_bucket:
                    span.note(**out_rows)
                    out_cap = bucket_rows(max(wtotal, 1), self.min_bucket)
                    outs = self._expand_one(build, window, b_order, starts,
                                            counts, out_cap, seen_box)
            if outs is None:
                # skewed window: recurse with smaller windows
                outs = self._windowed_expand(build, window, wtotal, max_out,
                                             counts_fn, seen_box)
            yield from outs

    # -- grace-style sub-partitioned join (build side over budget) -----------
    def _grace_split(self, table: DeviceTable, keys: List[str], n_sub: int
                     ) -> List[DeviceTable]:
        from ..shuffle.manager import device_partition_ids
        pid = device_partition_ids(table, keys, n_sub, seed=_GRACE_SEED)
        return [shrink_to_fit(table.filter_mask(pid == s), self.min_bucket)
                for s in range(n_sub)]

    def _grace_build_parts(self, build: DeviceTable, n_sub: int):
        """-> (list of build-part spill handles, close_when_done)."""
        from ..memory.catalog import SpillPriorities, get_catalog
        catalog = get_catalog()
        return [catalog.register(t, SpillPriorities.INPUT)
                for t in self._grace_split(build, self.right_keys, n_sub)], \
            True

    def _grace_join(self, build: DeviceTable, pidx: int
                    ) -> Iterator[DeviceTable]:
        from ..memory.catalog import SpillPriorities, get_catalog
        catalog = get_catalog()
        n_sub = min(64, max(2, math.ceil(build.nbytes() / self.batch_bytes)))
        track = self.how in ("right", "full")
        build_parts, own_build = [], False
        probe_parts: List[List] = [[] for _ in range(n_sub)]
        try:
            # span ``join.grace``: both sides split into ``parts`` buckets
            # (closed before the pairwise joins yield anything)
            with get_tracer().span("join.grace", "join", parts=n_sub):
                build_parts, own_build = self._grace_build_parts(build,
                                                                 n_sub)
                del build
                for probe in _device_batches(self.left, pidx):
                    parts = self._grace_split(probe, self.left_keys, n_sub)
                    # one batched-funnel transfer resolves every bucket's
                    # count instead of n_sub per-bucket syncs
                    ns = resolve_scalars(*[t.num_rows for t in parts])
                    for s, (t, tn) in enumerate(zip(parts, ns)):
                        if int(tn):
                            probe_parts[s].append(
                                catalog.register(t, SpillPriorities.INPUT))
            for s in range(n_sub):
                def sub_batches():
                    for h in probe_parts[s]:
                        with h as t:
                            yield t
                seen_box = None
                if track:
                    with build_parts[s] as bt:
                        seen_box = [jnp.zeros(bt.capacity, dtype=bool)]
                if probe_parts[s] or track:
                    yield from self._probe_join(build_parts[s],
                                                sub_batches(), seen_box)
                if track:
                    # never-probed buckets still owe all their build rows
                    leftover = self._leftover_fn()
                    with build_parts[s] as bt:
                        yield leftover(bt, seen_box[0])
                if own_build:   # one part's prep alive at a time
                    self._close_preps(build_parts[s])
        finally:
            if own_build:
                for h in build_parts:
                    h.close()
                    self._close_preps(h)
            for hs in probe_parts:
                for h in hs:
                    h.close()


class TpuBroadcastHashJoinExec(TpuShuffledHashJoinExec):
    """Build side materialized once across partitions (reference:
    GpuBroadcastHashJoinExec + SerializeConcatHostBuffersDeserializeBatch)."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        # broadcasting the build side is unsound when its unmatched rows
        # appear in the output (duplicated per probe partition)
        assert self.how not in ("right", "full"), \
            f"{self.how} join cannot broadcast the right side"
        self._bc_handle = None
        self._bc_grace_parts = None
        self._bc_lock = __import__("threading").Lock()

    def _broadcast_handle(self):
        """Broadcast batch registered once with the BufferCatalog at
        BROADCAST priority — accounted and spillable rather than pinned to
        the exec node for the plan's lifetime. The catalog entry releases
        at query end (release_spill_handles), with a GC-time finalizer
        fallback for plans never explicitly released. The lock keeps
        concurrent (pipelined) probe partitions from double-building.
        Never block on the semaphore while holding it
        (pipeline.exempt_admission invariant)."""
        with self._bc_lock:
            from ..parallel.pipeline import exempt_admission
            with exempt_admission():
                return self._broadcast_handle_locked()

    def _broadcast_handle_locked(self):
        if self._bc_handle is None:
            from ..memory.catalog import SpillPriorities, get_catalog
            with get_tracer().span("join.build", "join") as span:
                batches = []
                for p in range(self.right.num_partitions):  # srtpu: mesh-ok(build-side INPUT drain: collecting the broadcast table's partitions, not per-shard compute)
                    batches.extend(_device_batches(self.right, p))
                table = self._concat_build(batches)
                span.note(rows=table.capacity, bytes=table.nbytes())
                self._bc_handle = get_catalog().register(
                    table, SpillPriorities.BROADCAST)
            self._own_spill_handle(self._bc_handle)
        return self._bc_handle

    def _open_build(self, pidx: int):
        """The one broadcast table, built (and ``join.build`` booked) by
        the first partition that asks."""
        handle = self._broadcast_handle()
        build = handle.get()
        if build.nbytes() > self.batch_bytes:
            return build, None, False
        return build, handle, False

    def _grace_build_parts(self, build: DeviceTable, n_sub: int):
        """Split the broadcast once; reuse the parts for every partition."""
        with self._bc_lock:
            if self._bc_grace_parts is None:
                from ..parallel.pipeline import exempt_admission
                with exempt_admission():
                    parts, _ = super()._grace_build_parts(build, n_sub)
                self._bc_grace_parts = parts
                for h in parts:
                    self._own_spill_handle(h)
            return self._bc_grace_parts, False


class _PrepSlot:
    """The prep entry of one live build table (``_prep_slot``): per tier
    ``(the table's row_mask, the prep's payload)`` or None, under a lock of
    its own. It pins ``owner``, so the ``id`` it is filed under stays the
    owner's for as long as the entry lives."""

    __slots__ = ("owner", "lock", "hash", "dense")

    def __init__(self, owner):
        self.owner = owner
        self.lock = threading.Lock()
        self.hash = None
        self.dense = None


def _close_quietly(handle):
    try:
        handle.close()
    except Exception:
        pass  # srtpu: net-ok(best-effort release of an already-consumed spill handle; the data was read before this)


class TpuBroadcastNestedLoopJoinExec(TpuExec):
    """Non-equi / cross join: the right side is broadcast once, the stream
    (left) side crosses it in windows sized so window_rows x build_capacity
    stays under the batch budget (reference:
    GpuBroadcastNestedLoopJoinExec.scala + GpuCartesianProductExec.scala;
    conditions compile into the traced kernel like the reference's AST
    conditions).

    right/full outer consume ALL stream partitions inside partition 0 so
    unmatched build rows are emitted exactly once (the reference instead
    requires the build side opposite the outer side; with a single
    broadcast side this serialization is the sound equivalent).
    """

    SUPPORTED = ("inner", "cross", "left", "right", "full", "left_semi",
                 "left_anti")
    EXTRA_METRICS = (M.JOIN_TIME,)

    def __init__(self, left: PhysicalPlan, right: PhysicalPlan, how: str,
                 condition: Optional[Expression], min_bucket: Optional[int] = None,
                 batch_bytes: int = 512 * 1024 * 1024):
        super().__init__()
        assert how in self.SUPPORTED, how
        self.left, self.right = left, right
        self.children = (left, right)
        self.how = how
        self.condition = condition
        self.min_bucket = resolve_min_bucket(min_bucket)
        self.batch_bytes = batch_bytes
        self.schema = _join_schema(left.schema, right.schema, None, how)
        self._bc_handle = None

    @property
    def num_partitions(self) -> int:
        return self.left.num_partitions

    def node_desc(self):
        return f"{self.how} condition={self.condition!r}"

    def plan_signature(self) -> str:
        return (f"BNLJ|{self.how}|{self.condition!r}|"
                f"{self.left.schema!r}|{self.right.schema!r}")

    def _broadcast_handle(self):
        if self._bc_handle is None:
            from ..memory.catalog import SpillPriorities, get_catalog
            batches = []
            for p in range(self.right.num_partitions):  # srtpu: mesh-ok(build-side INPUT drain: collecting the broadcast table's partitions, not per-shard compute)
                batches.extend(_device_batches(self.right, p))
            if not batches:
                from .aggregate import _empty_device_table
                table = _empty_device_table(self.right.schema,
                                            self.min_bucket)
            elif len(batches) == 1:
                table = batches[0]
            else:
                # broadcast build tables are unsplittable: every stream
                # window crosses the whole table — spill-only retry
                from ..memory.retry import with_retry
                table = with_retry(concat_device_tables, batches,
                                   scope="join-build",
                                   context=self.node_desc())
            table = shrink_to_fit(table, self.min_bucket)
            self._bc_handle = get_catalog().register(
                table, SpillPriorities.BROADCAST)
            self._own_spill_handle(self._bc_handle)
        return self._bc_handle

    # -- assembly & padding (stream side plays the probe role) ---------------
    def assemble(self, scols: List[DeviceColumn], bcols: List[DeviceColumn]):
        names = list(self.left.schema.names) + list(self.right.schema.names)
        return list(scols) + list(bcols), names

    def pad_stream(self, stream: DeviceTable, emit: jax.Array) -> DeviceTable:
        bcols = [_null_device_column(f.dtype, stream.capacity)
                 for f in self.right.schema]
        scols = [c.with_validity(jnp.logical_and(c.validity, emit))
                 for c in stream.columns]
        cols, names = self.assemble(scols, bcols)
        return DeviceTable(tuple(cols), emit, jnp.sum(emit, dtype=jnp.int32),
                           tuple(names))

    def pad_build(self, build: DeviceTable, emit: jax.Array) -> DeviceTable:
        scols = [_null_device_column(f.dtype, build.capacity)
                 for f in self.left.schema]
        bcols = [c.with_validity(jnp.logical_and(c.validity, emit))
                 for c in build.columns]
        cols, names = self.assemble(scols, bcols)
        return DeviceTable(tuple(cols), emit, jnp.sum(emit, dtype=jnp.int32),
                           tuple(names))

    # -- kernels --------------------------------------------------------------
    def cross_fn(self, ws: int, how: str):
        """One stream-window x build cross product with traced condition."""
        node = self

        def fn(window: DeviceTable, build: DeviceTable, seen):
            nb = build.capacity
            j = jnp.arange(ws * nb, dtype=jnp.int64)
            si = (j // nb).astype(jnp.int32)
            bi = (j % nb).astype(jnp.int32)
            valid = jnp.logical_and(jnp.take(window.row_mask, si),
                                    jnp.take(build.row_mask, bi))
            scols = _gather_columns(window, si, valid)
            bcols = _gather_columns(build, bi, valid)
            cols, names = node.assemble(scols, bcols)
            pairs = DeviceTable(tuple(cols), valid,
                                jnp.sum(valid, dtype=jnp.int32), tuple(names))
            if node.condition is not None:
                keep = _condition_mask(node.condition, pairs)
            else:
                keep = valid
            pairs = pairs.filter_mask(keep)
            any_pass = jnp.zeros(window.capacity, dtype=bool).at[si].max(
                keep, mode="drop")
            outs = []
            if how in ("inner", "cross", "left", "right", "full"):
                outs.append(pairs)
            if how in ("left", "full"):
                unmatched = jnp.logical_and(window.row_mask,
                                            jnp.logical_not(any_pass))
                outs.append(node.pad_stream(window, unmatched))
            if how == "left_semi":
                outs.append(window.filter_mask(any_pass))
            if how == "left_anti":
                outs.append(window.filter_mask(jnp.logical_not(any_pass)))
            if how in ("right", "full"):
                seen = jnp.logical_or(
                    seen,
                    jnp.zeros(nb, dtype=bool).at[bi].max(keep, mode="drop"))
            return tuple(outs), seen
        return fn

    def leftover_fn(self):
        node = self

        def fn(build: DeviceTable, seen):
            emit = jnp.logical_and(build.row_mask, jnp.logical_not(seen))
            return node.pad_build(build, emit)
        return fn

    # -- execution ------------------------------------------------------------
    def _budget_rows(self) -> int:
        """Cross-product pair-slot budget derived from the byte budget."""
        row_bytes = 0
        for f in self.schema:
            if isinstance(f.dtype, (dt.StringType, dt.BinaryType)):
                row_bytes += 32
            else:
                row_bytes += f.dtype.np_dtype().itemsize
            row_bytes += 1
        return max(self.min_bucket, self.batch_bytes // max(row_bytes, 1))

    def _window_shape(self, build_cap: int):
        """(stream_window_rows, build_window_rows): both sides window so
        stream_ws x build_ws pair slots stay under the budget even when the
        broadcast side alone exceeds it (fixes the reference-scale case
        where GpuBroadcastNestedLoopJoinExec streams the build side too)."""
        budget = self._budget_rows()
        build_ws = bucket_rows(
            min(build_cap, max(self.min_bucket, budget // self.min_bucket)),
            self.min_bucket)
        stream_ws = bucket_rows(max(1, budget // build_ws), self.min_bucket)
        return stream_ws, min(build_ws, bucket_rows(build_cap,
                                                    self.min_bucket))

    def execute_columnar(self, pidx: int) -> Iterator[DeviceTable]:
        from .fallback import quarantine_on_failure
        # note-only boundary: the probe needs the whole build table, so a
        # terminal failure can't fall back per-batch — but it quarantines
        with quarantine_on_failure(self):
            for out in self._join_batches(pidx):
                self.account_batch()
                yield out

    def _join_batches(self, pidx: int) -> Iterator[DeviceTable]:
        track = self.how in ("right", "full")
        if track and pidx != 0:
            return
        handle = self._broadcast_handle()
        with handle as build:
            build_cap = build.capacity
        ws, bws = self._window_shape(build_cap)
        n_bslices = max(1, math.ceil(build_cap / bws))
        semi_like = self.how in ("left_semi", "left_anti")
        # per-build-slice semantics: pairs emit per slice; stream-side
        # outer/semi decisions need the OR across slices, so single-slice
        # keeps the fast path and multi-slice accumulates per window
        fn = cached_jit(self.plan_signature() + f"|cross{ws}x{bws}",
                        lambda: self.cross_fn(ws, self.how),
                        name="join_cross")
        # multi-slice variant: pairs only ("right" also threads the seen
        # update for right/full; stream-side fixup happens after all slices)
        pairs_how = "right" if track else (
            "cross" if self.how == "cross" else "inner")
        pairs_fn = cached_jit(
            self.plan_signature() + f"|crosspairs{pairs_how}{ws}x{bws}",
            lambda: self.cross_fn(ws, pairs_how), name="join_cross_pairs")
        seen_slices = [jnp.zeros(min(bws, build_cap), dtype=bool)
                       for _ in range(n_bslices)] if track else None
        parts = range(self.left.num_partitions) if track else [pidx]
        for sp in parts:
            for batch in _device_batches(self.left, sp):
                batch = batch.compact()
                (nrows,) = resolve_scalars(batch.num_rows)
                nrows = max(0, int(nrows))
                start = 0
                while start < nrows:
                    window = slice_rows(batch, start, ws)
                    start += ws
                    yield from self._cross_window(
                        window, handle, n_bslices, bws, fn, pairs_fn,
                        seen_slices, semi_like)
        if track:
            leftover = cached_jit(self.plan_signature() + "|bnlj_leftover",
                                  self.leftover_fn, name="join_leftover")
            for bi in range(n_bslices):
                with handle as build:
                    bslice = slice_rows(build, bi * bws, min(bws, build_cap))
                    yield leftover(bslice, seen_slices[bi])

    def _cross_window(self, window, handle, n_bslices, bws, fn, pairs_fn,
                      seen_slices, semi_like) -> Iterator[DeviceTable]:
        """One stream window against the build table: span
        ``join.probe.cross`` (``rows`` = window capacity) around each cross
        program, one a window and build slice."""
        track = seen_slices is not None
        tracer = get_tracer()
        if n_bslices == 1:
            with self.metrics.timed(M.JOIN_TIME), handle as build, \
                    tracer.span("join.probe.cross", "join",
                                rows=window.capacity):
                window = _co_locate(window, build)
                outs, seen = fn(window, build, seen_slices[0] if track
                                else jnp.zeros(build.capacity, dtype=bool))
            if track:
                seen_slices[0] = seen
            yield from outs
            return
        # multi-slice: emit inner pairs per slice; accumulate per-stream-row
        # any_pass across slices for outer/semi fixup at the end
        any_pass = jnp.zeros(window.capacity, dtype=bool)
        for bi in range(n_bslices):
            with self.metrics.timed(M.JOIN_TIME), handle as build, \
                    tracer.span("join.probe.cross", "join",
                                rows=window.capacity):
                window = _co_locate(window, build)
                bslice = slice_rows(build, bi * bws,
                                    min(bws, build.capacity))
                outs, seen = pairs_fn(
                    window, bslice,
                    seen_slices[bi] if track
                    else jnp.zeros(bslice.capacity, dtype=bool))
                pairs = outs[0]
                matched = jnp.zeros(window.capacity, dtype=bool)
                if self.how not in ("inner", "cross"):
                    # recompute stream-row matches from the pair mask
                    nb = bslice.capacity
                    si = (jnp.arange(pairs.capacity, dtype=jnp.int32) // nb)
                    matched = jnp.zeros(window.capacity, dtype=bool).at[
                        si].max(pairs.row_mask, mode="drop")
            if track:
                seen_slices[bi] = seen
            any_pass = jnp.logical_or(any_pass, matched)
            if self.how in ("inner", "cross", "left", "right", "full"):
                yield pairs
        if self.how in ("left", "full"):
            unmatched = jnp.logical_and(window.row_mask,
                                        jnp.logical_not(any_pass))
            yield self.pad_stream(window, unmatched)
        elif self.how == "left_semi":
            yield window.filter_mask(any_pass)
        elif self.how == "left_anti":
            yield window.filter_mask(jnp.logical_not(any_pass))


def _condition_filter_fn(condition: Expression):
    def fn(table: DeviceTable) -> DeviceTable:
        ctx = EvalContext.for_device(table)
        c = condition.eval(ctx)
        keep = c.values
        if c.validity is not None:
            keep = jnp.logical_and(keep, c.validity)
        return table.filter_mask(keep)
    return fn


def _device_batches(child: PhysicalPlan, pidx: int) -> Iterator[DeviceTable]:
    assert hasattr(child, "execute_columnar"), \
        f"join child {type(child).__name__} is not columnar (missing transition)"
    return child.execute_columnar(pidx)
