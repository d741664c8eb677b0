"""Device window exec (reference: GpuWindowExec.scala — running-window
optimization at :161,1346; frame -> rolling/scan mapping in
GpuWindowExpression.scala).

TPU-first: one lexsort puts rows in (partition, order) layout; every window
function is then a data-parallel kernel over that layout inside a single jit:

- segment flags + ``lax.associative_scan`` give segmented cumulative ops
  (the running-window scan path)
- entire-partition aggregates are segment reductions gathered back per row
- bounded ROWS frames use clamped prefix-sum differences (sum/count/avg)
- ranking functions are index arithmetic over segment starts / peer flags

All static shapes; no per-partition loops.
"""
from __future__ import annotations

from typing import Iterator, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..columnar import dtypes as dt
from ..columnar.device import DeviceColumn, DeviceTable, concat_device_tables
from ..expr.aggregates import (AggregateFunction, Average, Count, CountStar,
                               Max, Min, Sum)
from ..expr.base import EvalContext
from ..expr.functions import SortOrder
from ..expr.window import (DenseRank, Lag, Lead, NTile, Rank, RowNumber,
                           WindowExpression)
from ..plan.physical import PhysicalPlan
from ..plan.schema import Field, Schema
from ..utils import metrics as M
from ..utils.compile_cache import cached_jit
from .base import TpuExec
from .sort import _order_keys

__all__ = ["TpuWindowExec"]


def _segmented_scan(vals: jax.Array, new_seg: jax.Array, op) -> jax.Array:
    """Inclusive segmented scan: resets at rows where new_seg is True."""
    def combine(a, b):
        fa, va = a
        fb, vb = b
        return jnp.logical_or(fa, fb), jnp.where(fb, vb, op(va, vb))
    _, out = jax.lax.associative_scan(combine, (new_seg, vals))
    return out


def _eq_prev_values(values, lengths=None) -> jax.Array:
    """Per-row equality with the previous row (Spark grouping semantics:
    NaN == NaN, -0.0 == 0.0); string columns compare the full byte row +
    length so zero padding can't conflate "ab" with "ab\x00"."""
    v = values
    if v.ndim == 2:  # string/binary byte matrix
        eq = jnp.all(v == jnp.roll(v, 1, axis=0), axis=1)
        if lengths is not None:
            eq = jnp.logical_and(eq, lengths == jnp.roll(lengths, 1))
        return eq
    if jnp.issubdtype(v.dtype, jnp.floating):
        v = jnp.where(v == 0, jnp.zeros_like(v), v)
        return (v == jnp.roll(v, 1)) \
            | (jnp.isnan(v) & jnp.isnan(jnp.roll(v, 1)))
    return v == jnp.roll(v, 1)


def _seg_info(table: DeviceTable, part_names: List[str]):
    """Assumes rows already sorted by partition keys: returns
    (new_seg flags, seg_start index per row, pos, pos_in_seg)."""
    cap = table.capacity
    pos = jnp.arange(cap, dtype=jnp.int64)
    active = table.row_mask
    new_seg = jnp.zeros(cap, dtype=bool).at[0].set(True)
    for k in part_names:
        c = table.column(k)
        eq = _eq_prev_values(c.data, c.lengths)
        null = jnp.logical_not(c.validity)
        eq = jnp.where(null | jnp.roll(null, 1), null & jnp.roll(null, 1), eq)
        new_seg = jnp.logical_or(new_seg, jnp.logical_not(eq).at[0].set(True))
    # inactive rows are at the end after compact-sort; give them their own seg
    new_seg = jnp.logical_or(new_seg, jnp.logical_not(active)
                             != jnp.logical_not(jnp.roll(active, 1)))
    new_seg = new_seg.at[0].set(True)
    seg_start = _segmented_scan(jnp.where(new_seg, pos, 0), new_seg,
                                lambda a, b: jnp.maximum(a, b))
    # simpler: seg_start via scan of "carry start"
    seg_start = _segmented_scan(pos * new_seg, new_seg, jnp.maximum)
    return new_seg, seg_start, pos, pos - seg_start


def _peer_flags(table: DeviceTable, orders: Sequence[SortOrder],
                new_seg: jax.Array) -> jax.Array:
    """True where a new peer group (distinct order keys) starts."""
    if not orders:
        return new_seg
    ctx = EvalContext.for_device(table)
    neq = jnp.zeros(table.capacity, dtype=bool)
    for o in orders:
        c = o.expr.eval(ctx)
        eq = _eq_prev_values(c.values, getattr(c, "lengths", None))
        valid = c.validity if c.validity is not None \
            else jnp.ones(table.capacity, dtype=bool)
        null = jnp.logical_not(valid)
        eq = jnp.where(null | jnp.roll(null, 1), null & jnp.roll(null, 1), eq)
        neq = jnp.logical_or(neq, jnp.logical_not(eq))
    return jnp.logical_or(new_seg, neq).at[0].set(True)


class TpuWindowExec(TpuExec):
    def __init__(self, child: PhysicalPlan,
                 window_cols: Sequence[Tuple[str, WindowExpression]],
                 child_names: Sequence[str]):
        super().__init__()
        self.child = child
        self.children = (child,)
        self.window_cols = list(window_cols)
        self.child_names = list(child_names)
        fields = list(child.schema.fields)
        for name, w in self.window_cols:
            fields.append(Field(name, w.data_type, w.nullable))
        self.schema = Schema(fields)

    def node_desc(self):
        return ", ".join(n for n, _ in self.window_cols)

    def plan_signature(self) -> str:
        descs = [f"{n}={w!r}" for n, w in self.window_cols]
        return f"Window|{descs}|{self.child.schema!r}"

    @property
    def fusible(self) -> bool:
        return False  # needs whole-partition batches

    def _kernel(self):
        window_cols = self.window_cols
        spec0 = window_cols[0][1].spec
        out_names = tuple(self.schema.names)

        def fn(table: DeviceTable) -> DeviceTable:
            # sort by (partition keys, order keys); actives first
            part_orders = [SortOrder(e, True) for e in spec0.partition_exprs]
            orders = part_orders + list(spec0.orders)
            keys = _order_keys(table, orders) if orders else \
                [jnp.logical_not(table.row_mask)]
            order = jnp.lexsort(tuple(keys))
            cols = tuple(c.gather(order, keep_all_valid=True)
                         for c in table.columns)
            iota = jnp.arange(table.capacity, dtype=jnp.int32)
            mask = iota < table.num_rows
            sorted_t = DeviceTable(cols, mask, table.num_rows, table.names)
            # partition segments: evaluate partition exprs on sorted table
            ctx = EvalContext.for_device(sorted_t)
            part_cols = []
            part_names = []
            scratch = sorted_t
            for i, e in enumerate(spec0.partition_exprs):
                c = e.eval(ctx)
                validity = c.validity if c.validity is not None \
                    else jnp.ones(sorted_t.capacity, dtype=bool)
                part_cols.append(DeviceColumn(c.values, validity, c.dtype,
                                              c.lengths))
                part_names.append(f"_wp{i}")
            scratch = DeviceTable(tuple(sorted_t.columns) + tuple(part_cols),
                                  mask, sorted_t.num_rows,
                                  tuple(sorted_t.names) + tuple(part_names))
            new_seg, seg_start, pos, pos_in_seg = _seg_info(scratch, part_names)
            out_cols = list(sorted_t.columns)
            for name, w in window_cols:
                out_cols.append(_window_column(scratch, w, new_seg, seg_start,
                                               pos, pos_in_seg, mask))
            return DeviceTable(tuple(out_cols), mask, sorted_t.num_rows,
                               out_names)
        return fn

    def execute_columnar(self, pidx: int) -> Iterator[DeviceTable]:
        batches = list(self.child_device_batches(pidx))
        if not batches:
            return
        table = concat_device_tables(batches) if len(batches) > 1 else batches[0]
        fn = cached_jit(self.plan_signature(), self._kernel, name="window")
        with self.metrics.timed(M.OP_TIME):
            out = fn(table)
        self.account_batch()
        yield out


def _window_column(scratch: DeviceTable, w: WindowExpression,
                   new_seg, seg_start, pos, pos_in_seg, mask) -> DeviceColumn:
    cap = scratch.capacity
    fn = w.fn
    all_valid = jnp.ones(cap, dtype=bool)
    if isinstance(fn, RowNumber):
        return DeviceColumn((pos_in_seg + 1).astype(jnp.int32), all_valid,
                            dt.INT, None)
    if isinstance(fn, NTile):
        seg_len = _seg_len(new_seg, seg_start, pos, cap)
        k = fn.n
        base = seg_len // k
        rem = seg_len % k
        cut = rem * (base + 1)
        tile = jnp.where(pos_in_seg < cut,
                         pos_in_seg // jnp.maximum(base + 1, 1),
                         rem + (pos_in_seg - cut) // jnp.maximum(base, 1))
        return DeviceColumn((tile + 1).astype(jnp.int32), all_valid, dt.INT,
                            None)
    if isinstance(fn, (Rank, DenseRank)):
        peers = _peer_flags(scratch, w.spec.orders, new_seg)
        if isinstance(fn, DenseRank):
            dr = _segmented_scan(peers.astype(jnp.int64), new_seg,
                                 lambda a, b: a + b)
            return DeviceColumn(dr.astype(jnp.int32), all_valid, dt.INT, None)
        first_of_peer = _segmented_scan(jnp.where(peers, pos, 0), new_seg,
                                        jnp.maximum)
        return DeviceColumn((first_of_peer - seg_start + 1).astype(jnp.int32),
                            all_valid, dt.INT, None)
    if isinstance(fn, (Lag, Lead)):
        off = fn.offset if isinstance(fn, Lead) else -fn.offset
        ctx = EvalContext.for_device(scratch)
        c = fn.child.eval(ctx)
        src = jnp.clip(pos + off, 0, cap - 1).astype(jnp.int32)
        seg_len = _seg_len(new_seg, seg_start, pos, cap)
        in_seg = jnp.logical_and(pos_in_seg + off >= 0,
                                 pos_in_seg + off < seg_len)
        vals = jnp.take(c.values, src, axis=0)
        valid = jnp.take(c.valid_mask(ctx), src) & in_seg
        if fn.default is not None:
            vals = jnp.where(in_seg, vals,
                             jnp.full_like(vals, fn.default))
            valid = jnp.logical_or(valid, jnp.logical_not(in_seg))
        lengths = None if c.lengths is None else jnp.take(c.lengths, src)
        return DeviceColumn(vals, valid & mask, c.dtype, lengths)
    if isinstance(fn, AggregateFunction):
        return _agg_window_device(scratch, w, new_seg, seg_start, pos,
                                  pos_in_seg, mask)
    raise NotImplementedError(type(fn).__name__)


def _seg_len(new_seg, seg_start, pos, cap):
    # segment end: next segment's start (propagated backwards)
    rev_new = jnp.flip(new_seg)
    rev_pos = jnp.flip(pos)
    # for each row (reversed), the minimum pos of the NEXT segment start at or
    # after it == first new_seg position after current row + 1 ... compute via
    # reverse segmented scan of "start of my segment" on flipped array:
    # flipped segments are delimited one off; easier: seg_end = seg_start of
    # next seg. seg_end[i] = min over j>i of (pos[j] where new_seg[j]) else cap
    nxt = jnp.where(new_seg, pos, cap)
    rev_min = jnp.flip(jax.lax.associative_scan(jnp.minimum, jnp.flip(nxt)))
    # rev_min[i] = min(nxt[i:]) -> next boundary at or after i; but boundary at
    # own segment start should not count: use strictly-after by shifting
    after = jnp.concatenate([rev_min[1:], jnp.asarray([cap], rev_min.dtype)])
    seg_end = after
    return seg_end - seg_start


def _agg_window_device(scratch, w, new_seg, seg_start, pos, pos_in_seg, mask
                       ) -> DeviceColumn:
    fn = w.fn
    frame = w.spec.frame
    cap = scratch.capacity
    ctx = EvalContext.for_device(scratch)
    if isinstance(fn, CountStar):
        vals = jnp.ones(cap, dtype=jnp.int64)
        valid = mask
        in_dt = dt.LONG
    else:
        c = fn.children[0].eval(ctx)
        vals = c.values
        valid = (c.validity if c.validity is not None
                 else jnp.ones(cap, dtype=bool)) & mask
        in_dt = c.dtype
    out_dt = fn.data_type
    np_out = jnp.dtype(out_dt.np_dtype())

    _is_float = jnp.issubdtype(vals.dtype, jnp.floating)

    def prefix_pair():
        x = jnp.where(valid, vals, jnp.zeros_like(vals)).astype(
            jnp.float64 if _is_float else jnp.int64)
        # non-finite-aware prefix sums: a NaN/±inf in the running sum would
        # poison every LATER frame (csum[hi]-csum[lo] = nan-nan or inf-inf)
        # even when the frame excludes that row; sum zeros instead and
        # re-derive the float-sum result per frame from non-finite counts
        if _is_float:
            def ccount(m):
                return jnp.concatenate([jnp.zeros(1, jnp.int64),
                                        jnp.cumsum(m.astype(jnp.int64))])
            nanm = valid & jnp.isnan(vals)
            posm = valid & (vals == jnp.inf)
            negm = valid & (vals == -jnp.inf)
            x = jnp.where(nanm | posm | negm, jnp.float64(0), x)
            specials = (ccount(nanm), ccount(posm), ccount(negm))
        else:
            specials = None
        csum = jnp.concatenate([jnp.zeros(1, x.dtype), jnp.cumsum(x)])
        ccnt = jnp.concatenate([jnp.zeros(1, jnp.int64),
                                jnp.cumsum(valid.astype(jnp.int64))])
        return csum, ccnt, specials

    def reduce_frame(lo, hi):
        csum, ccnt, specials = prefix_pair()
        s = csum[hi] - csum[lo]
        if specials is not None:
            cnan, cpos, cneg = specials
            nn = cnan[hi] - cnan[lo]
            pp = cpos[hi] - cpos[lo]
            gg = cneg[hi] - cneg[lo]
            s = jnp.where((nn > 0) | ((pp > 0) & (gg > 0)),
                          jnp.float64(jnp.nan),
                          jnp.where(pp > 0, jnp.float64(jnp.inf),
                                    jnp.where(gg > 0, jnp.float64(-jnp.inf),
                                              s)))
        return finish(s, ccnt[hi] - ccnt[lo])

    def finish(s, cnt):
        if isinstance(fn, (Count, CountStar)):
            return DeviceColumn(cnt.astype(jnp.int64),
                                jnp.ones(cap, dtype=bool), dt.LONG, None)
        if isinstance(fn, Sum):
            return DeviceColumn(s.astype(np_out), cnt > 0, out_dt, None)
        avg = s.astype(jnp.float64) / jnp.maximum(cnt, 1)
        return DeviceColumn(avg, cnt > 0, dt.DOUBLE, None)

    seg_len = _seg_len(new_seg, seg_start, pos, cap)
    if frame.is_unbounded_entire or (not w.spec.orders and frame.is_running):
        if isinstance(fn, (Sum, Count, CountStar, Average)):
            return reduce_frame(seg_start, seg_start + seg_len)
        # min/max entire partition: forward + effectively segment reduce;
        # do running scan then take value at segment end
        col = _running_minmax(fn, vals, valid, new_seg)
        end_idx = jnp.clip(seg_start + seg_len - 1, 0, cap - 1).astype(jnp.int32)
        v = jnp.take(col[0], end_idx)
        has = jnp.take(col[1], end_idx)
        return DeviceColumn(v.astype(np_out), has & mask, out_dt, None)
    if frame.is_running:
        if frame.kind == "range" and w.spec.orders:
            peers = _peer_flags(scratch, w.spec.orders, new_seg)
            # hi = end of my peer group: next peer boundary after me
            nxt = jnp.where(peers, pos, cap)
            rev_min = jnp.flip(jax.lax.associative_scan(
                jnp.minimum, jnp.flip(nxt)))
            after = jnp.concatenate([rev_min[1:],
                                     jnp.asarray([cap], rev_min.dtype)])
            hi = jnp.minimum(after, seg_start + seg_len)
        else:
            hi = pos + 1
        if isinstance(fn, (Sum, Count, CountStar, Average)):
            return reduce_frame(seg_start, hi)
        run_v, run_has = _running_minmax(fn, vals, valid, new_seg)
        idx = jnp.clip(hi - 1, 0, cap - 1).astype(jnp.int32)
        return DeviceColumn(jnp.take(run_v, idx).astype(np_out),
                            jnp.take(run_has, idx) & mask, out_dt, None)
    seg_end = seg_start + seg_len
    if frame.kind == "rows":
        s = seg_start if frame.start is None else jnp.maximum(
            pos + frame.start, seg_start)
        e = seg_end if frame.end is None else jnp.minimum(
            pos + frame.end + 1, seg_end)
    elif frame.kind == "range" and len(w.spec.orders) == 1:
        sk, null_mask, scale = _device_range_sort_key(scratch,
                                                      w.spec.orders[0])

        def tgt(offset):
            t = sk + offset
            return t if null_mask is None else jnp.where(null_mask, sk, t)

        s = seg_start if frame.start is None else _device_bsearch(
            sk, tgt(frame.start * scale), seg_start, seg_end, strict=False)
        e = seg_end if frame.end is None else _device_bsearch(
            sk, tgt(frame.end * scale), seg_start, seg_end, strict=True)
    else:
        raise NotImplementedError(
            f"{type(fn).__name__} over {frame.describe()} on device")
    e = jnp.maximum(e, s)
    if isinstance(fn, (Sum, Count, CountStar, Average)):
        return reduce_frame(s, e)
    if isinstance(fn, (Min, Max)):
        return _device_range_minmax(isinstance(fn, Min), vals, valid,
                                    s, e, out_dt, cap)
    raise NotImplementedError(
        f"{type(fn).__name__} over {frame.describe()} on device")


def _device_range_sort_key(scratch: DeviceTable, order: SortOrder):
    """Sort-axis key for bounded RANGE frames -> (sk, null_mask, scale);
    identical rules to the host engine's _range_sort_key: integral/date/
    decimal keys stay int64 (decimal offsets scale to value units), float
    keys use float64 with NaN at the top; DESC negates; null keys collapse
    to a +-extreme sentinel peer window."""
    ctx = EvalContext.for_device(scratch)
    c = order.expr.eval(ctx)
    scale = 1
    if isinstance(c.dtype, dt.DecimalType):
        scale = 10 ** c.dtype.scale
    if jnp.issubdtype(c.values.dtype, jnp.floating):
        sk = c.values.astype(jnp.float64)
        sk = jnp.where(jnp.isnan(sk), jnp.inf, sk)
        lo_sent, hi_sent = -jnp.inf, jnp.inf
    else:
        sk = c.values.astype(jnp.int64)
        lo_sent = jnp.iinfo(jnp.int64).min
        hi_sent = jnp.iinfo(jnp.int64).max
    if not order.ascending:
        sk = -sk
    null_mask = None
    if c.validity is not None:
        null_mask = jnp.logical_not(c.validity)
        sent = lo_sent if order.nulls_first else hi_sent
        sk = jnp.where(null_mask, jnp.asarray(sent, sk.dtype), sk)
    return sk, null_mask, scale


def _device_bsearch(sk, target, lo0, hi0, strict: bool):
    """First pos in [lo0, hi0) with sk[pos] >= target (> when strict);
    fixed-depth vectorized binary search (static log2(cap) iterations)."""
    cap = sk.shape[0]
    lo = lo0.astype(jnp.int64)
    hi = hi0.astype(jnp.int64)
    for _ in range(max(1, cap.bit_length())):
        active = lo < hi
        mid = (lo + hi) // 2
        mv = jnp.take(sk, jnp.clip(mid, 0, cap - 1))
        go_right = (mv <= target) if strict else (mv < target)
        lo = jnp.where(jnp.logical_and(active, go_right), mid + 1, lo)
        hi = jnp.where(jnp.logical_and(active,
                                       jnp.logical_not(go_right)), mid, hi)
    return lo


def _device_range_minmax(is_min: bool, vals, valid, lo, hi, out_dt, cap
                         ) -> DeviceColumn:
    """Per-row [lo, hi) min/max via a power-of-two sparse table (the device
    mirror of the host engine's _range_minmax), Spark NaN total order."""
    np_out = jnp.dtype(out_dt.np_dtype())
    isfloat = jnp.issubdtype(vals.dtype, jnp.floating)
    if isfloat:
        nan_mask = jnp.isnan(vals)
        work = jnp.where(nan_mask, jnp.inf if is_min else -jnp.inf, vals)
        ident = jnp.asarray(jnp.inf if is_min else -jnp.inf, work.dtype)
    else:
        nan_mask = jnp.zeros(cap, dtype=bool)
        work = vals.astype(jnp.int64)
        ident = jnp.asarray(jnp.iinfo(jnp.int64).max if is_min
                            else jnp.iinfo(jnp.int64).min, jnp.int64)
    work = jnp.where(valid, work, ident)
    op = jnp.minimum if is_min else jnp.maximum
    tables = [work]
    k = 1
    while (1 << k) <= cap:
        prev = tables[-1]
        half = 1 << (k - 1)
        shifted = jnp.concatenate(
            [prev[half:], jnp.full(half, ident, prev.dtype)])
        tables.append(op(prev, shifted))
        k += 1
    T = jnp.stack(tables)                                # (levels, cap)
    wlen = jnp.maximum(hi - lo, 0)
    kk = jnp.where(wlen > 0,
                   jnp.floor(jnp.log2(jnp.maximum(wlen, 1))), 0
                   ).astype(jnp.int32)
    a = T[kk, jnp.clip(lo, 0, cap - 1).astype(jnp.int32)]
    b_idx = hi - jnp.left_shift(jnp.int64(1), kk.astype(jnp.int64))
    b = T[kk, jnp.clip(b_idx, 0, cap - 1).astype(jnp.int32)]
    out = op(a, b)
    ccnt = jnp.concatenate([jnp.zeros(1, jnp.int64),
                            jnp.cumsum(valid.astype(jnp.int64))])
    cnt = ccnt[jnp.clip(hi, 0, cap)] - ccnt[jnp.clip(lo, 0, cap)]
    has = cnt > 0
    if isfloat:
        cnan = jnp.concatenate([
            jnp.zeros(1, jnp.int64),
            jnp.cumsum(jnp.logical_and(valid, nan_mask).astype(jnp.int64))])
        nnan = cnan[jnp.clip(hi, 0, cap)] - cnan[jnp.clip(lo, 0, cap)]
        if is_min:
            out = jnp.where(jnp.logical_and(has, cnt == nnan), jnp.nan, out)
        else:
            out = jnp.where(nnan > 0, jnp.nan, out)
    return DeviceColumn(out.astype(np_out), has, out_dt, None)


def _running_minmax(fn, vals, valid, new_seg):
    """Segmented running min/max with Spark NaN ordering; returns (vals, has)."""
    is_min = isinstance(fn, Min)
    isfloat = jnp.issubdtype(vals.dtype, jnp.floating)
    x = vals
    if isfloat:
        nan = jnp.isnan(vals)
        x = jnp.where(nan, jnp.full_like(vals, jnp.inf if is_min else -jnp.inf),
                      vals)
        # NaN counts tracked separately for Spark total order
    ident = (jnp.finfo(x.dtype).max if jnp.issubdtype(x.dtype, jnp.floating)
             else jnp.iinfo(x.dtype).max) if is_min else \
        (jnp.finfo(x.dtype).min if jnp.issubdtype(x.dtype, jnp.floating)
         else jnp.iinfo(x.dtype).min)
    x = jnp.where(valid, x, jnp.full_like(x, ident))
    op = jnp.minimum if is_min else jnp.maximum
    run = _segmented_scan(x, new_seg, op)
    has = _segmented_scan(valid.astype(jnp.int64), new_seg,
                          lambda a, b: a + b) > 0
    if isfloat:
        nan_run = _segmented_scan((valid & jnp.isnan(vals)).astype(jnp.int64),
                                  new_seg, lambda a, b: a + b)
        nonnan_run = _segmented_scan(
            (valid & jnp.logical_not(jnp.isnan(vals))).astype(jnp.int64),
            new_seg, lambda a, b: a + b)
        if is_min:
            run = jnp.where(has & (nonnan_run == 0),
                            jnp.full_like(run, jnp.nan), run)
        else:
            run = jnp.where(nan_run > 0, jnp.full_like(run, jnp.nan), run)
    return run, has
