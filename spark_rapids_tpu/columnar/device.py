"""Device-side columnar batches as JAX pytrees — the ``GpuColumnVector`` /
``cudf.Table`` replacement (reference: sql-plugin/src/main/java/.../GpuColumnVector.java).

TPU-first design decisions (this is where we deliberately diverge from cuDF):

1. **Static shapes via bucketing.** XLA compiles per shape. Every device batch
   has a row *capacity* that is a power-of-two multiple of a minimum bucket, so
   a pipeline sees a small bounded set of shapes regardless of actual row
   counts. cuDF's dynamically-sized columns have no analogue here.

2. **Selection masks instead of compaction.** A filter does not gather
   survivors into a smaller buffer (dynamic output size!); it ANDs a per-table
   ``row_mask``. Downstream kernels treat masked-off rows as nonexistent.
   Physical compaction (a stable argsort of the mask + gather) happens only at
   operator boundaries that need dense data: sort, join build, shuffle slice,
   and host download. This is vectorized-engine "late materialization" mapped
   onto XLA's static-shape world.

3. **Validity as bool vectors** (not bitmasks): the VPU operates on 8x128
   lanes; bool vectors fuse into elementwise ops for free.

4. **Strings as fixed-width padded uint8 matrices** (capacity, width) +
   int32 lengths, width bucketed per batch. Wasteful for long tails but keeps
   every string op a dense 2-D vector op that XLA can fuse and tile.

The pytree registration makes DeviceTable a first-class jit/shard_map citizen:
whole operator pipelines take and return DeviceTables inside one jit.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import dtypes as dt
from ..utils import movement
from ..utils.compile_cache import named_program
from ..utils.tracing import get_tracer
from .host import HostColumn, HostTable

__all__ = ["BucketPolicy", "DeferredScalar", "DeviceColumn", "DeviceTable",
           "async_enabled", "bucket_rows",
           "bucket_width", "bulk_download_stats", "canonical_names",
           "configure_async", "configure_buckets",
           "configure_debug", "current_bucket_policy",
           "debug_assertions_enabled", "host_sync_stats",
           "resolve_min_bucket", "resolve_scalars", "shard_row_counts",
           "to_host_batched"]

# process-wide count of deliberate D2H materializations (to_host calls —
# the funnel every blocking download converges on per the srtpu-analyze
# sync rules). Feeds utils/metrics.StatsRegistry as the ``host_sync``
# source, so per-query event-log deltas carry it and the history
# sentinel's sync-count gate can flag a run that started syncing more.
_HOST_SYNC_LOCK = __import__("threading").Lock()
_HOST_SYNC = {"d2h_count": 0}


def host_sync_stats() -> Dict[str, int]:
    with _HOST_SYNC_LOCK:
        return dict(_HOST_SYNC)


def _note_host_sync() -> None:
    with _HOST_SYNC_LOCK:
        _HOST_SYNC["d2h_count"] += 1

# movement-observatory site identities (utils/movement.py SITES): the
# ``path::symbol`` names the ledger aggregates these funnels under and
# joins onto the srtpu-analyze baseline keys
_MOVE_TO_HOST = "spark_rapids_tpu/columnar/device.py::DeviceTable.to_host"
_MOVE_SHRINK = "spark_rapids_tpu/columnar/device.py::shrink_to_fit"
_MOVE_RESOLVE = "spark_rapids_tpu/columnar/device.py::resolve_scalars"
_MOVE_BULK = "spark_rapids_tpu/columnar/device.py::to_host_batched"

# spark.rapids.tpu.async.enabled snapshot (session-init chokepoint, same
# contract as configure_debug below). True = deferred scalars stay async
# until a fusible boundary and downloads batch per drain; False = the
# sync-forcing debug mode (every site blocks where it stands).
_ASYNC_ENABLED = True


def configure_async(conf) -> None:
    """Apply spark.rapids.tpu.async.enabled (called from
    TpuSession.__init__; the most recent session wins)."""
    global _ASYNC_ENABLED
    from ..conf import ASYNC_ENABLED
    _ASYNC_ENABLED = bool(conf.get(ASYNC_ENABLED))


def async_enabled() -> bool:
    return _ASYNC_ENABLED


def resolve_scalars(*values) -> Tuple:
    """Materialize any number of device scalars in ONE bulk transfer.

    This is the sanctioned funnel for every host decision that needs a
    device scalar (row counts, expansion totals, uniqueness flags): call
    sites hand over everything they need for the next decision at once,
    so a control-flow boundary costs one ledgered round trip however
    many scalars it consumes. Python numbers pass through untouched.
    Under the sync-forcing debug conf (``spark.rapids.tpu.async.enabled
    =false``) each scalar transfers separately so a stall localizes to
    its site in the trace."""
    if not values:
        return ()
    if _ASYNC_ENABLED:
        t0 = movement.clock()
        with get_tracer().span("sync", "download", on=values,
                               scalars=len(values)):
            got = jax.device_get(list(values))  # srtpu: sync-ok(the deliberate batched-scalar funnel: one transfer per decision boundary)
        movement.note_d2h(_MOVE_RESOLVE, 4 * len(values), t0)
    else:
        # one ledger entry per transfer: the sync-forcing mode really
        # does N blocking crossings, and the ledger must say so (the
        # async-vs-sync blocking_count delta is the measured win)
        got = []
        for v in values:
            t0 = movement.clock()
            with get_tracer().span("sync", "download", on=v, scalars=1):
                got.append(jax.device_get(v))  # srtpu: sync-ok(sync-forcing debug mode: per-scalar blocking transfers localize stalls)
            movement.note_d2h(_MOVE_RESOLVE, 4, t0)
    return tuple(v.item() if hasattr(v, "item") else v for v in got)  # srtpu: sync-ok(item on numpy scalars the device_get above already fetched — no extra transfer)


class DeferredScalar:
    """A device scalar that stays async until the host actually branches
    on it (ROADMAP item 1: nonblocking row counts).

    ``DeviceTable.num_rows`` and friends are JAX arrays whose values are
    still in flight under async dispatch — wrapping one defers the
    blocking materialization to the first ``int()``/``bool()``, and
    several can resolve together through ``resolve_scalars`` with one
    transfer. Under the sync-forcing debug conf the constructor resolves
    eagerly, restoring blocking-at-site semantics."""

    __slots__ = ("_device", "_host")

    def __init__(self, value):
        if isinstance(value, (int, float, bool, np.generic)):
            self._device, self._host = None, value
        else:
            self._device, self._host = value, None
            if not _ASYNC_ENABLED:
                self.resolve()

    @property
    def is_resolved(self) -> bool:
        return self._host is not None

    def resolve(self):
        if self._host is None:
            (self._host,) = resolve_scalars(self._device)
            self._device = None
        return self._host

    @staticmethod
    def resolve_all(*scalars) -> Tuple:
        """Resolve many DeferredScalars with ONE transfer for the whole
        unresolved set (the batched-future boundary)."""
        pending = [s for s in scalars if isinstance(s, DeferredScalar)
                   and not s.is_resolved]
        if pending:
            got = resolve_scalars(*[s._device for s in pending])
            for s, v in zip(pending, got):
                s._host, s._device = v, None
        return tuple(s.resolve() if isinstance(s, DeferredScalar) else s
                     for s in scalars)

    def __int__(self) -> int:
        return int(self.resolve())

    __index__ = __int__

    def __bool__(self) -> bool:
        return bool(self.resolve())

    def __repr__(self) -> str:
        state = self._host if self._host is not None else "<deferred>"
        return f"DeferredScalar({state})"

# spark.rapids.tpu.debug.assertions snapshot (session-init chokepoint,
# like parallel/pipeline.configure_pipeline — columns have no conf at
# kernel-build time). Governs the gather all-valid guard below.
_DEBUG_ASSERTIONS = False


def configure_debug(conf) -> None:
    """Apply spark.rapids.tpu.debug.* (called from TpuSession.__init__;
    the most recent session wins)."""
    global _DEBUG_ASSERTIONS
    from ..conf import DEBUG_ASSERTIONS
    _DEBUG_ASSERTIONS = bool(conf.get(DEBUG_ASSERTIONS))


def debug_assertions_enabled() -> bool:
    return _DEBUG_ASSERTIONS


def canonical_names(n: int) -> Tuple[str, ...]:
    return tuple(f"c{i}" for i in range(n))


#: Block length of ``prefix_sum``.
_SCAN_BLOCK = 1024


def prefix_sum(x: jax.Array) -> jax.Array:
    """Inclusive prefix sum along the last axis: log-step shifted adds
    inside blocks of 1,024, the block totals scanned the same way and
    added back. ``jnp.cumsum`` over 2^20 elements computes the same no
    faster on the chip (0.8-1.3 ms against 0.6-0.8) and takes the TPU's
    compiler 17-31 s a program where this takes under a second (PERF.md,
    PR 27): keep ``jnp.cumsum`` off row-capacity vectors."""
    n = x.shape[-1]
    lead = [(0, 0)] * (x.ndim - 1)
    if n > _SCAN_BLOCK:
        blocks = jnp.pad(x, lead + [(0, -n % _SCAN_BLOCK)]).reshape(
            x.shape[:-1] + (-1, _SCAN_BLOCK))
        inner = prefix_sum(blocks)
        totals = inner[..., -1]
        before = prefix_sum(totals) - totals
        return (inner + before[..., None]).reshape(
            x.shape[:-1] + (-1,))[..., :n]
    step = 1
    while step < n:
        x = x + jnp.pad(x, lead + [(step, 0)])[..., :n]
        step *= 2
    return x


def open_rows_by_rank(open_rows: jax.Array, iota: jax.Array, tail_cap: int
                      ) -> Tuple[jax.Array, jax.Array]:
    """The compaction between the full rounds and the tail rounds of a loop
    that resolves a batch's rows round by round (both chain walks of the
    hash join, the group-by's bucket resolve): -> (the indices of the open
    rows by their rank, ``tail_cap`` long; which of those slots hold a
    row). At most ``tail_cap`` rows are open.
    The blocked ``prefix_sum``, never ``jnp.cumsum`` over a row-capacity
    vector (17-31 s of TPU compile a program)."""
    o32 = open_rows.astype(jnp.int32)
    dest = jnp.where(open_rows, prefix_sum(o32) - o32, tail_cap)
    rows = jnp.zeros(tail_cap, jnp.int32).at[dest].set(iota, mode="drop")
    live = jnp.arange(tail_cap, dtype=jnp.int32) < jnp.sum(o32)
    return rows, live


def stable_partition_order(mask: jax.Array) -> jax.Array:
    """Sort-free stable-partition permutation: gather indices that put
    mask=True rows first, preserving relative order in both segments —
    identical to ``argsort(!mask, stable=True)`` but built from one
    prefix sum + one scatter (O(n) work, and no lax.sort in the program —
    sorts are the pathological op for some TPU toolchains). A dropped
    row's rank among the dropped is its index minus the kept before it."""
    n = mask.shape[0]
    m32 = mask.astype(jnp.int32)
    kept_before = prefix_sum(m32) - m32
    iota = jnp.arange(n, dtype=jnp.int32)
    n_keep = jnp.sum(m32)
    dest = jnp.where(mask, kept_before, n_keep + iota - kept_before)
    return jnp.zeros(n, dtype=jnp.int32).at[dest].set(iota)


def stable_counting_order(keys: jax.Array, num_vals: int) -> jax.Array:
    """Sort-free stable permutation grouping equal small-domain keys in
    ascending order (counting sort): ``keys`` must lie in [0, num_vals).
    Equivalent to ``argsort(keys, stable=True)`` for partition ids — the
    shuffle write path's sort — with O(n * num_vals) elementwise work and
    no lax.sort. num_vals is the (small, static) partition count."""
    n = keys.shape[0]
    oh = (keys[:, None] == jnp.arange(num_vals, dtype=keys.dtype)[None, :]) \
        .astype(jnp.int32)
    within = jnp.cumsum(oh, axis=0) - oh
    counts = jnp.sum(oh, axis=0)
    offsets = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(counts)[:-1]])
    my_within = jnp.take_along_axis(
        within, jnp.clip(keys, 0, num_vals - 1)[:, None].astype(jnp.int32),
        axis=1)[:, 0]
    dest = jnp.take(offsets, jnp.clip(keys, 0, num_vals - 1)) + my_within
    iota = jnp.arange(n, dtype=jnp.int32)
    return jnp.zeros(n, dtype=jnp.int32).at[dest].set(iota)


def _gather_rows(table: "DeviceTable", order: jax.Array) -> "DeviceTable":
    """The rows ``order`` names, in that order, as a table of
    ``len(order)`` rows whose live rows are the first ``num_rows``: what
    both compaction programs do once they hold their gather indices."""
    # permutation + re-mask below: only real rows stay exposed
    cols = tuple(c.gather(order, keep_all_valid=True)
                 for c in table.columns)
    iota = jnp.arange(order.shape[0], dtype=jnp.int32)
    mask = iota < table.num_rows
    # masked-off tail keeps stale data; null it for hygiene
    cols = tuple(c.with_validity(jnp.logical_and(c.validity, mask),
                                 all_valid=c.all_valid)
                 for c in cols)
    return DeviceTable(cols, mask, table.num_rows, table.names)


def _compact_impl(table: "DeviceTable") -> "DeviceTable":
    return _gather_rows(table, stable_partition_order(table.row_mask))


def _compact_shrink_impl(table: "DeviceTable", out_cap: int
                         ) -> "DeviceTable":
    """``compact()`` cut to ``out_cap`` rows, built without touching more
    than ``out_cap`` rows of any column: kept rows scatter their own index
    to their rank among the kept, dropped rows (and kept ones past
    ``out_cap``) to a slot that ``mode="drop"`` discards, and every array
    is gathered at those ``out_cap`` indices."""
    mask = table.row_mask
    with jax.named_scope("shrink_index"):
        m32 = mask.astype(jnp.int32)
        dest = jnp.where(mask, prefix_sum(m32) - m32, out_cap)
        iota = jnp.arange(mask.shape[0], dtype=jnp.int32)
        order = jnp.zeros(out_cap, dtype=jnp.int32).at[dest].set(
            iota, mode="drop")
    with jax.named_scope("shrink_gather"):
        return _gather_rows(table, order)


_compact_jitted = named_program(_compact_impl, "compact")
_compact_shrink_jitted = named_program(_compact_shrink_impl, "compact_shrink",
                                       static_argnums=(1,))


# ---------------------------------------------------------------------------
# Canonical shape-bucket policy. XLA compiles one program per shape, so the
# set of row capacities the engine ever exposes IS the set of programs it
# ever compiles; one process-wide geometric ladder (instead of per-node
# ad-hoc bucket choices) keeps that set small and — critically for the
# persistent compile tier (utils/compile_cache.py) — REPEATABLE: the same
# query over the same data lands on the same capacities in every process,
# so a persisted executable serves every rerun.
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class BucketPolicy:
    """The process-wide bucket ladder (spark.rapids.tpu.shapeBuckets.*).

    Rungs are ``min_rows * growth^k``; within a rung, capacities quantize
    down toward the row count in steps of ``growth * rung * max_waste_frac``
    (never below ``min_rows``), bounding padded-row waste. The defaults
    (growth=2.0, max_waste_frac=0.5) reproduce the original power-of-two
    ladder exactly."""
    min_rows: int = 1024
    growth: float = 2.0
    max_waste_frac: float = 0.5

    def bucket(self, n: int, min_bucket: Optional[int] = None) -> int:
        base = int(min_bucket) if min_bucket is not None else self.min_rows
        cap = max(base, 1)
        while cap < n:
            # max(+1): a growth factor rounding to itself must still climb
            cap = max(cap + 1, int(cap * self.growth))
        if cap > base:
            # quantize down toward n in canonical steps derived from the
            # rung (NOT from n — a data-dependent quantum would make the
            # shape set unbounded)
            step = max(base, int(cap * self.max_waste_frac))
            cap = min(cap, -(-n // step) * step)
        return cap


_POLICY = BucketPolicy()


def configure_buckets(conf) -> None:
    """Apply spark.rapids.tpu.shapeBuckets.* to the process bucket ladder
    (called from TpuSession.__init__, like configure_debug; the most
    recent session wins)."""
    global _POLICY
    from ..conf import SHAPE_BUCKET_GROWTH, SHAPE_BUCKET_MAX_WASTE
    _POLICY = BucketPolicy(
        min_rows=int(conf.min_bucket_rows),
        growth=float(conf.get(SHAPE_BUCKET_GROWTH)),
        max_waste_frac=float(conf.get(SHAPE_BUCKET_MAX_WASTE)))


def current_bucket_policy() -> BucketPolicy:
    return _POLICY


def resolve_min_bucket(min_bucket: Optional[int]) -> int:
    """The bucket floor a node should use: an explicit value wins (planner
    threads conf.min_bucket_rows; tests pass tiny buckets), ``None`` falls
    back to the central policy — the one replacement for the per-node
    ``= 1024`` defaults that used to scatter the ladder."""
    return int(min_bucket) if min_bucket is not None else _POLICY.min_rows


def bucket_rows(n: int, min_bucket: Optional[int] = None) -> int:
    """Canonical row capacity for ``n`` rows: the central ladder's bucket,
    floored at ``min_bucket`` when given (policy floor otherwise)."""
    return _POLICY.bucket(n, min_bucket)


def bucket_width(w: int, min_width: int = 8, max_width: int = 4096) -> int:
    cap = min_width
    while cap < w:
        cap *= 2
    return min(cap, max(max_width, w))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DeviceColumn:
    """One device column: padded values + validity (+ lengths for strings
    and arrays, + per-element validity for arrays with containsNull,
    + child columns for STRUCT/MAP).

    STRUCT layout is struct-of-planes: one child DeviceColumn per field
    (field order = dtype.fields order), the parent holding only the struct
    validity; ``data`` is a zero-byte placeholder so every column has a
    capacity-bearing plane. MAP reuses it: exactly two children — the keys
    as an ARRAY column and the values as an ARRAY column with shared
    per-row lengths (reference: cuDF's LIST<STRUCT<K,V>> map layout,
    re-cut for static shapes; SURVEY §2.2)."""
    data: jax.Array                   # (capacity,) or (capacity, width) uint8
    validity: jax.Array               # (capacity,) bool — True = non-null
    dtype: dt.DataType                # static
    lengths: Optional[jax.Array] = None  # (capacity,) int32 for string/binary
    elem_validity: Optional[jax.Array] = None  # (capacity, width) bool, arrays
    children: Optional[Tuple["DeviceColumn", ...]] = None  # struct/map
    #: STATIC null-freedom promise: every row under the table's row_mask is
    #: valid. Kernels may then skip validity reads entirely and XLA DCEs the
    #: unused plane (the validity array itself stays correct either way).
    #: False is always safe. (The reference gets this from cuDF's null_count
    #: == 0 fast paths; here it must be static to specialize the program.)
    all_valid: bool = False

    # -- pytree protocol ------------------------------------------------------
    def tree_flatten(self):
        leaves = [self.data, self.validity]
        if self.lengths is not None:
            leaves.append(self.lengths)
        if self.elem_validity is not None:
            leaves.append(self.elem_validity)
        if self.children is not None:
            leaves.append(self.children)
        return tuple(leaves), (self.dtype, self.lengths is not None,
                               self.elem_validity is not None,
                               self.children is not None, self.all_valid)

    @classmethod
    def tree_unflatten(cls, aux, children):
        if len(aux) == 3:
            aux = (*aux, False)
        if len(aux) == 4:
            aux = (*aux, False)
        dtype, has_len, has_ev, has_kids, all_valid = aux
        it = iter(children)
        data, validity = next(it), next(it)
        lengths = next(it) if has_len else None
        ev = next(it) if has_ev else None
        kids = tuple(next(it)) if has_kids else None
        return cls(data, validity, dtype, lengths, ev, kids, all_valid)

    @property
    def capacity(self) -> int:
        return self.data.shape[0]

    @property
    def is_string_like(self) -> bool:
        return isinstance(self.dtype, (dt.StringType, dt.BinaryType))

    @property
    def is_nested(self) -> bool:
        return self.children is not None

    def gather(self, idx: jax.Array,
               keep_all_valid: Optional[bool] = None) -> "DeviceColumn":
        """Row gather. ``keep_all_valid`` is the caller's explicit
        statement about the static ``all_valid`` promise (ADVICE #3):
        a gather only preserves it when every row the caller EXPOSES
        under the result's row mask maps to a real source row
        (permutations, compaction, shuffle slices, join outputs that
        re-mask) — ``True`` asserts that and keeps the promise; ``False``
        drops it (always safe). ``None`` (implicit legacy call sites)
        preserves it too, EXCEPT under spark.rapids.tpu.debug.assertions,
        where the promise is dropped so an un-audited new call site
        cannot silently expose padding garbage as non-null data."""
        if keep_all_valid is None:
            keep_all_valid = not _DEBUG_ASSERTIONS
        take = lambda a: None if a is None else jnp.take(a, idx, axis=0)
        kids = None if self.children is None \
            else tuple(c.gather(idx, keep_all_valid=keep_all_valid)
                       for c in self.children)
        return DeviceColumn(jnp.take(self.data, idx, axis=0),
                            jnp.take(self.validity, idx, axis=0),
                            self.dtype, take(self.lengths),
                            take(self.elem_validity), kids,
                            self.all_valid and keep_all_valid)

    def with_validity(self, validity: jax.Array,
                      all_valid: bool = False) -> "DeviceColumn":
        return DeviceColumn(self.data, validity, self.dtype, self.lengths,
                            self.elem_validity, self.children, all_valid)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DeviceTable:
    """A batch of device columns + row mask (active rows) + row count."""
    columns: Tuple[DeviceColumn, ...]
    row_mask: jax.Array              # (capacity,) bool — True = row exists
    num_rows: jax.Array              # scalar int32 (traced) == sum(row_mask)
    names: Tuple[str, ...]           # static

    def tree_flatten(self):
        return (self.columns, self.row_mask, self.num_rows), self.names

    @classmethod
    def tree_unflatten(cls, names, children):
        columns, row_mask, num_rows = children
        return cls(tuple(columns), row_mask, num_rows, names)

    # -- shape info -----------------------------------------------------------
    @property
    def capacity(self) -> int:
        return self.row_mask.shape[0]

    @property
    def num_columns(self) -> int:
        return len(self.columns)

    def column(self, name: str) -> DeviceColumn:
        return self.columns[self.names.index(name)]

    def schema(self) -> Dict[str, dt.DataType]:
        return {n: c.dtype for n, c in zip(self.names, self.columns)}

    def with_columns(self, names: Sequence[str], columns: Sequence[DeviceColumn]
                     ) -> "DeviceTable":
        return DeviceTable(tuple(columns), self.row_mask, self.num_rows, tuple(names))

    def with_names(self, names: Sequence[str]) -> "DeviceTable":
        """Rename columns (free: names are pytree aux data, no device op)."""
        assert len(names) == len(self.columns)
        return DeviceTable(self.columns, self.row_mask, self.num_rows,
                           tuple(names))

    def canonical(self) -> "DeviceTable":
        """Positional names c0..cN — the schema-erased view that lets
        structurally identical kernels share one compiled program across
        queries (cache keys in utils/compile_cache.py stay name-free)."""
        return self.with_names(canonical_names(len(self.columns)))

    def filter_mask(self, keep: jax.Array) -> "DeviceTable":
        """AND a predicate into the row mask (no data movement)."""
        mask = jnp.logical_and(self.row_mask, keep)
        return DeviceTable(self.columns, mask, jnp.sum(mask, dtype=jnp.int32),
                           self.names)

    # -- compaction -----------------------------------------------------------
    def compact(self) -> "DeviceTable":
        """Move active rows to the front (stable). Same capacity.

        After this, ``row_mask == iota < num_rows`` so dense kernels (sort,
        join, contiguous slicing for shuffle) can assume a prefix layout.
        Jitted when called eagerly (one fused program instead of ~3 eager
        dispatches per column); inlines when already under a trace.
        """
        from ..shims import get_shims
        if get_shims().is_tracer(self.num_rows):
            return _compact_impl(self)
        return _compact_jitted(self)

    def nbytes(self) -> int:
        total = int(self.row_mask.nbytes) + 4
        def col_bytes(c: DeviceColumn) -> int:
            b = int(c.data.nbytes) + int(c.validity.nbytes)
            if c.lengths is not None:
                b += int(c.lengths.nbytes)
            if c.elem_validity is not None:
                b += int(c.elem_validity.nbytes)
            for k in (c.children or ()):
                b += col_bytes(k)
            return b

        for c in self.columns:
            total += col_bytes(c)
        return total

    # -- host <-> device ------------------------------------------------------
    @staticmethod
    def from_host(table: HostTable, min_bucket: Optional[int] = None,
                  capacity: Optional[int] = None) -> "DeviceTable":
        n = table.num_rows
        cap = capacity if capacity is not None else bucket_rows(max(n, 1), min_bucket)
        assert cap >= n, (cap, n)
        cols = []
        for hc in table.columns:
            cols.append(_upload_column(hc, cap))
        iota = np.arange(cap, dtype=np.int32)
        row_mask = jnp.asarray(iota < n)
        return DeviceTable(tuple(cols), row_mask,
                           jnp.asarray(n, dtype=jnp.int32), tuple(table.names))

    def to_host(self) -> HostTable:
        """Download and compact to exactly num_rows host rows."""
        _note_host_sync()
        t0 = movement.clock()
        with get_tracer().span("d2h", "download", on=self,
                               bytes=self.nbytes()):
            mask = np.asarray(self.row_mask)  # srtpu: sync-ok(result materialization: the deliberate D2H funnel)
            n = int(np.asarray(self.num_rows))  # srtpu: sync-ok(result materialization: the deliberate D2H funnel)
            # row_mask may be non-prefix (post-filter); boolean-index on host
            cols = [_download_column(c, mask, n) for c in self.columns]
        ht = HostTable(list(self.names), cols)
        movement.note_d2h(_MOVE_TO_HOST, self.nbytes, t0, table=ht)
        return ht


def _download_column(c: DeviceColumn, mask: np.ndarray, n: int) -> HostColumn:
    """One column's device->host decode over the active-row mask."""
    validity = np.asarray(c.validity)[mask][:n]  # srtpu: sync-ok(deliberate D2H download path, called from to_host)
    opt_valid = None if validity.all() else validity
    if c.is_string_like:
        data = np.asarray(c.data)[mask][:n]  # srtpu: sync-ok(deliberate D2H download path, called from to_host)
        lengths = np.asarray(c.lengths)[mask][:n]  # srtpu: sync-ok(deliberate D2H download path, called from to_host)
        return HostColumn(c.dtype, _decode_string_matrix(data, lengths,
                                                         c.dtype), opt_valid)
    if isinstance(c.dtype, dt.ArrayType):
        data = np.asarray(c.data)[mask][:n]  # srtpu: sync-ok(deliberate D2H download path, called from to_host)
        lengths = np.asarray(c.lengths)[mask][:n]  # srtpu: sync-ok(deliberate D2H download path, called from to_host)
        ev = None if c.elem_validity is None \
            else np.asarray(c.elem_validity)[mask][:n]  # srtpu: sync-ok(deliberate D2H download path, called from to_host)
        return HostColumn(c.dtype, _decode_list_matrix(data, lengths,
                                                       c.dtype, ev), opt_valid)
    if isinstance(c.dtype, dt.StructType):
        kids = [_download_column(k, mask, n) for k in c.children]
        names = [f.name for f in c.dtype.fields]
        kvms = [k.valid_mask() for k in kids]      # hoisted: O(1) per row
        out = _obj_array(n)
        for i in range(n):
            if validity[i]:
                out[i] = {nm: (k.values[i] if vm[i] else None)
                          for nm, k, vm in zip(names, kids, kvms)}
        return HostColumn(c.dtype, out, opt_valid)
    if isinstance(c.dtype, dt.MapType):
        kc = _download_column(c.children[0], mask, n)
        vc = _download_column(c.children[1], mask, n)
        kvm, vvm = kc.valid_mask(), vc.valid_mask()
        out = _obj_array(n)
        for i in range(n):
            if validity[i]:
                ks = kc.values[i] if kvm[i] else []
                vs = vc.values[i] if vvm[i] else []
                out[i] = list(zip(ks, vs))
        return HostColumn(c.dtype, out, opt_valid)
    if dt.is_d128(c.dtype):
        from ..expr.decimal128 import limbs_to_py_ints
        limbs = np.asarray(c.data)[mask][:n]  # srtpu: sync-ok(deliberate D2H download path, called from to_host)
        # hi limb is signed: the composition is already the signed
        # 128-bit value
        return HostColumn(c.dtype, limbs_to_py_ints(limbs), opt_valid)
    vals = np.asarray(c.data)[mask][:n]  # srtpu: sync-ok(deliberate D2H download path, called from to_host)
    if isinstance(c.dtype, dt.BooleanType):
        vals = vals.astype(np.bool_)
    return HostColumn(c.dtype, vals, opt_valid)


# bulk-download counters: the async-parity suite pins "<= 1 bulk
# device_get per output drain" against these (tests/test_async_exec.py)
_BULK_STATS = {"calls": 0, "tables": 0}


def bulk_download_stats() -> Dict[str, int]:
    with _HOST_SYNC_LOCK:
        return dict(_BULK_STATS)


def to_host_batched(tables: Sequence[DeviceTable]) -> List[HostTable]:
    """Download many device batches with ONE bulk transfer.

    The deferred-D2H funnel (ROADMAP item 1): a drain accumulates its
    device batches and materializes them here in a single ``device_get``
    over all pytrees, so the host blocks once per drain instead of once
    per batch and XLA keeps dispatching while earlier batches transfer.
    Under the sync-forcing debug conf this degrades to the per-batch
    ``to_host`` path so each download blocks at its own site."""
    tables = list(tables)
    if not tables:
        return []
    if not _ASYNC_ENABLED:
        return [t.to_host() for t in tables]
    _note_host_sync()
    t0 = movement.clock()
    nbytes = sum(t.nbytes() for t in tables)
    with get_tracer().span("d2h", "download", on=tables, bytes=nbytes,
                           batches=len(tables)):
        host_np = jax.device_get(tables)  # srtpu: sync-ok(the deliberate bulk-download funnel: one transfer for the whole drain)
        out: List[HostTable] = []
        for t in host_np:
            mask = np.asarray(t.row_mask)  # srtpu: sync-ok(already numpy after the bulk device_get above — no further transfer)
            n = int(np.asarray(t.num_rows))  # srtpu: sync-ok(already numpy after the bulk device_get above — no further transfer)
            cols = [_download_column(c, mask, n) for c in t.columns]
            out.append(HostTable(list(t.names), cols))
    movement.note_d2h(_MOVE_BULK, nbytes, t0, table=out[0])
    # propagate the lineage tag to every table of the drain so a re-upload
    # of ANY of them flags a round trip, not just the first
    tag = getattr(out[0], "_tpu_lineage", None)
    if tag is not None:
        for ht in out[1:]:
            try:
                ht._tpu_lineage = tag
            except (AttributeError, TypeError):
                pass
    with _HOST_SYNC_LOCK:
        _BULK_STATS["calls"] += 1
        _BULK_STATS["tables"] += len(tables)
    return out


def _obj_array(n: int) -> np.ndarray:
    return np.empty(n, dtype=object)


def _encode_string_matrix(values: np.ndarray, capacity: int, is_binary: bool,
                          arrow=None):
    """Vectorized object-array -> (capacity, width) byte matrix + lengths.

    Uses Arrow's C encode path + one fancy-index scatter instead of a
    per-row Python loop; columns fresh off an arrow scan skip the encode
    entirely via their cached arrow array (this sits on the hot upload
    path — reference: HostColumnarToGpu's bulk buffer copies)."""
    import pyarrow as pa
    n = len(values)
    arr = arrow if arrow is not None else pa.array(
        values, type=pa.binary() if is_binary else pa.string(),
        from_pandas=True)
    offsets = np.frombuffer(arr.buffers()[1], dtype=np.int32,
                            count=n + 1 + arr.offset)[arr.offset:]
    blob_buf = arr.buffers()[2]
    blob = np.frombuffer(blob_buf, dtype=np.uint8) if blob_buf is not None \
        else np.zeros(0, dtype=np.uint8)
    starts = offsets[:-1].astype(np.int64)
    lengths = (offsets[1:] - offsets[:-1]).astype(np.int32)
    width = bucket_width(max(int(lengths.max()) if n else 0, 1))
    mat = np.zeros((capacity, width), dtype=np.uint8)
    total = int(offsets[-1]) - int(offsets[0])
    if total:
        rows = np.repeat(np.arange(n, dtype=np.int64), lengths)
        flat = np.arange(int(offsets[0]), int(offsets[-1]), dtype=np.int64)
        cols = flat - np.repeat(starts, lengths)
        mat[rows, cols] = blob[flat]
    out_lengths = np.zeros(capacity, dtype=np.int32)
    out_lengths[:n] = lengths
    return mat, out_lengths


def _decode_string_matrix(data: np.ndarray, lengths: np.ndarray,
                          dtype: dt.DataType) -> np.ndarray:
    """Vectorized (n, w) byte matrix -> object array of str/bytes via Arrow
    varlen buffers (the download-path inverse of _encode_string_matrix)."""
    import pyarrow as pa
    n = len(lengths)
    lengths = lengths.astype(np.int64)
    total = int(lengths.sum())
    starts = np.cumsum(lengths) - lengths
    if total:
        rows = np.repeat(np.arange(n, dtype=np.int64), lengths)
        cols = np.arange(total, dtype=np.int64) - np.repeat(starts, lengths)
        blob = np.ascontiguousarray(data[rows, cols])
    else:
        blob = np.zeros(0, dtype=np.uint8)
    offsets = np.zeros(n + 1, dtype=np.int32)
    np.cumsum(lengths, out=offsets[1:])
    is_str = isinstance(dtype, dt.StringType)
    try:
        arr = pa.Array.from_buffers(
            pa.string() if is_str else pa.binary(), n,
            [None, pa.py_buffer(offsets.tobytes()),
             pa.py_buffer(blob.tobytes())])
        out = np.asarray(arr.to_pylist(), dtype=object)  # srtpu: sync-ok(host pyarrow decode; no device value)
    except (pa.ArrowInvalid, UnicodeDecodeError):
        # invalid utf-8 bytes: per-row fallback with replacement
        out = np.empty(n, dtype=object)
        for i in range(n):
            raw = bytes(data[i, :lengths[i]].tobytes())
            out[i] = raw.decode("utf-8", errors="replace") if is_str else raw
    return out


def _encode_list_matrix(hc: HostColumn, capacity: int):
    """ARRAY<fixed-width> column -> (capacity, W) element matrix + lengths
    (+ element-validity plane when the array has null elements) — the
    string byte-matrix layout generalized to typed elements (reference:
    cuDF list columns, SURVEY §2.9; containsNull rides the optional
    elem_validity plane)."""
    import pyarrow as pa
    et: dt.DataType = hc.dtype.element_type
    np_dt = np.bool_ if isinstance(et, dt.BooleanType) else et.np_dtype()
    n = len(hc)
    arr = getattr(hc, "_arrow", None)
    if arr is not None:
        child = arr.values
        offsets = np.frombuffer(arr.buffers()[1], dtype=np.int32,
                                count=n + 1 + arr.offset)[arr.offset:] \
            .astype(np.int64)
        child_valid = None
        if child.null_count:
            child_valid = np.asarray(child.is_valid())  # srtpu: sync-ok(host-side encode for upload; no device value)
            fill = False if pa.types.is_boolean(child.type) else 0
            childvals = np.asarray(child.fill_null(fill))  # srtpu: sync-ok(host-side encode for upload; no device value)
        else:
            childvals = np.asarray(child)  # srtpu: sync-ok(host-side encode for upload; no device value)
        lengths32 = (offsets[1:] - offsets[:-1]).astype(np.int32)
        # null rows keep offsets; force their length to 0
        vm = hc.valid_mask()
        lengths32 = np.where(vm, lengths32, 0).astype(np.int32)
        width = bucket_width(max(int(lengths32.max()) if n else 0, 1),
                             min_width=4)
        mat = np.zeros((capacity, width), dtype=np_dt)
        ev = None
        starts = offsets[:-1]
        total = int(lengths32.sum())
        if total:
            rows = np.repeat(np.arange(n, dtype=np.int64), lengths32)
            prefix = np.cumsum(lengths32.astype(np.int64)) - lengths32
            cols = np.arange(total, dtype=np.int64) \
                - np.repeat(prefix, lengths32)
            src = np.repeat(starts, lengths32) + cols
            mat[rows, cols] = childvals.astype(np_dt, copy=False)[src]
            if child_valid is not None:
                ev = np.zeros((capacity, width), dtype=np.bool_)
                ev[rows, cols] = child_valid[src]
                # rows without inner nulls keep ev=True over their extent
                if ev[rows, cols].all():
                    ev = None
        out_lengths = np.zeros(capacity, dtype=np.int32)
        out_lengths[:n] = lengths32
        return mat, out_lengths, ev
    # object-array path (post-transform columns): per-row encode
    vm = hc.valid_mask()
    lens = np.zeros(capacity, dtype=np.int32)
    rows_np = []
    any_inner_null = False
    for i in range(n):
        v = hc.values[i]
        if not vm[i] or v is None:
            rows_np.append(None)
            continue
        if any(e is None for e in v):
            any_inner_null = True
            a = np.asarray([0 if e is None else e for e in v], dtype=np_dt)  # srtpu: sync-ok(host-side encode for upload; no device value)
            m = np.asarray([e is not None for e in v], dtype=np.bool_)  # srtpu: sync-ok(host-side encode for upload; no device value)
            rows_np.append((a, m))
        else:
            rows_np.append((np.asarray(v, dtype=np_dt), None))  # srtpu: sync-ok(host-side encode for upload; no device value)
        lens[i] = len(v)
    width = bucket_width(max(int(lens.max()) if n else 0, 1), min_width=4)
    mat = np.zeros((capacity, width), dtype=np_dt)
    ev = np.ones((capacity, width), dtype=np.bool_) if any_inner_null else None
    for i, am in enumerate(rows_np):
        if am is None:
            continue
        a, m = am
        if len(a):
            mat[i, :len(a)] = a
            if ev is not None and m is not None:
                ev[i, :len(m)] = m
    return mat, lens, ev


def _decode_list_matrix(data: np.ndarray, lengths: np.ndarray,
                        dtype: dt.DataType, ev: Optional[np.ndarray] = None
                        ) -> np.ndarray:
    """(n, W) element matrix + lengths (+ element validity) -> object array
    of Python lists (the host engine's nested representation)."""
    n = len(lengths)
    out = np.empty(n, dtype=object)
    for i in range(n):
        row = data[i, :lengths[i]].tolist()
        if ev is not None:
            m = ev[i, :lengths[i]]
            row = [v if ok else None for v, ok in zip(row, m)]
        out[i] = row
    return out


def _host_field_column(hc: HostColumn, index: int) -> HostColumn:
    """Struct HostColumn -> one field's HostColumn (arrow fast path or
    per-row dict extraction)."""
    import pyarrow as pa
    f = hc.dtype.fields[index]
    arr = getattr(hc, "_arrow", None)
    if arr is not None:
        child = arr.field(index)
        if isinstance(child, pa.ChunkedArray):
            child = child.combine_chunks()
        return HostColumn.from_arrow(child)
    from .host import _dtype_to_arrow
    vm = hc.valid_mask()
    vals = [hc.values[i].get(f.name) if vm[i] and hc.values[i] is not None
            else None for i in range(len(hc))]
    return HostColumn.from_arrow(
        pa.array(vals, type=_dtype_to_arrow(f.data_type), from_pandas=True))


def _host_map_entry_columns(hc: HostColumn):
    """Map HostColumn -> (keys ARRAY HostColumn, values ARRAY HostColumn)
    with shared per-row lengths."""
    import pyarrow as pa
    from .host import _dtype_to_arrow
    mt: dt.MapType = hc.dtype
    arr = getattr(hc, "_arrow", None)
    if arr is not None and pa.types.is_map(arr.type):
        offsets = arr.offsets
        keys = pa.ListArray.from_arrays(offsets, arr.keys)
        items = pa.ListArray.from_arrays(offsets, arr.items)
        # propagate row validity (map offsets keep entries for null rows)
        if arr.null_count:
            vm = np.asarray(arr.is_valid())  # srtpu: sync-ok(host arrow buffers; no device value)
            kc = HostColumn.from_arrow(keys)
            vc = HostColumn.from_arrow(items)
            kc.validity = vm if kc.validity is None else (kc.validity & vm)
            vc.validity = vm if vc.validity is None else (vc.validity & vm)
            return kc, vc
        return HostColumn.from_arrow(keys), HostColumn.from_arrow(items)
    vm = hc.valid_mask()
    krows, vrows = [], []
    for i in range(len(hc)):
        row = hc.values[i]
        if not vm[i] or row is None:
            krows.append(None)
            vrows.append(None)
        else:
            pairs = row.items() if isinstance(row, dict) else row
            pairs = list(pairs)
            krows.append([k for k, _ in pairs])
            vrows.append([v for _, v in pairs])
    ktype = pa.list_(_dtype_to_arrow(mt.key_type))
    vtype = pa.list_(_dtype_to_arrow(mt.value_type))
    return (HostColumn.from_arrow(pa.array(krows, type=ktype,
                                           from_pandas=True)),
            HostColumn.from_arrow(pa.array(vrows, type=vtype,
                                           from_pandas=True)))


def _upload_column(hc: HostColumn, capacity: int) -> DeviceColumn:
    n = len(hc)
    validity = np.zeros(capacity, dtype=np.bool_)
    validity[:n] = hc.valid_mask()
    all_valid = hc.validity is None or bool(validity[:n].all())
    if isinstance(hc.dtype, dt.StructType):
        kids = tuple(_upload_column(_host_field_column(hc, i), capacity)
                     for i in range(len(hc.dtype.fields)))
        return DeviceColumn(jnp.zeros(capacity, jnp.uint8),
                            jnp.asarray(validity), hc.dtype, None, None, kids)
    if isinstance(hc.dtype, dt.MapType):
        kc, vc = _host_map_entry_columns(hc)
        kids = (_upload_column(kc, capacity), _upload_column(vc, capacity))
        return DeviceColumn(jnp.zeros(capacity, jnp.uint8),
                            jnp.asarray(validity), hc.dtype, None, None, kids)
    if isinstance(hc.dtype, (dt.StringType, dt.BinaryType)):
        mat, lengths = _encode_string_matrix(
            hc.values, capacity, isinstance(hc.dtype, dt.BinaryType),
            arrow=getattr(hc, "_arrow", None))
        return DeviceColumn(jnp.asarray(mat), jnp.asarray(validity), hc.dtype,
                            jnp.asarray(lengths), all_valid=all_valid)
    if isinstance(hc.dtype, dt.ArrayType):
        mat, lengths, ev = _encode_list_matrix(hc, capacity)
        return DeviceColumn(jnp.asarray(mat), jnp.asarray(validity), hc.dtype,
                            jnp.asarray(lengths),
                            None if ev is None else jnp.asarray(ev),
                            all_valid=all_valid)
    if dt.is_d128(hc.dtype):
        # wide decimals: host object ints -> (capacity, 2) int64 limbs
        from ..expr.decimal128 import limbs_from_py_ints
        limbs = limbs_from_py_ints(hc.values, capacity)
        return DeviceColumn(jnp.asarray(limbs), jnp.asarray(validity),
                            hc.dtype, None, all_valid=all_valid)
    np_dt = hc.dtype.np_dtype()
    vals = np.zeros(capacity, dtype=np_dt)
    vals[:n] = hc.values.astype(np_dt, copy=False)
    return DeviceColumn(jnp.asarray(vals), jnp.asarray(validity), hc.dtype,
                        None, all_valid=all_valid)


def concat_device_tables(tables: Sequence[DeviceTable],
                         min_bucket: Optional[int] = None) -> DeviceTable:
    """Device-side concatenation (reference: GpuCoalesceBatches concat).

    Compacts each input then concatenates into a bucketed output capacity.
    Jitted when called eagerly (per input-structure cache in jax.jit).
    """
    assert tables, "cannot concat zero device tables"
    min_bucket = resolve_min_bucket(min_bucket)
    if len(tables) == 1:
        return tables[0]
    from ..shims import get_shims
    if any(get_shims().is_tracer(t.num_rows) for t in tables):
        return _concat_impl(tuple(tables), min_bucket)
    # inputs may live on different chips (ICI-exchange shards read across
    # partitions, e.g. AQE coalesced stage reads): co-locate before the jit
    devs = set()
    for t in tables:
        if hasattr(t.row_mask, "devices"):
            devs |= t.row_mask.devices()
    if len(devs) > 1:
        target = next(iter(tables[0].row_mask.devices()))
        tables = [jax.device_put(t, target) for t in tables]
    return _concat_jitted(tuple(tables), min_bucket)


def _concat_impl(tables, min_bucket: int) -> DeviceTable:
    first = tables[0]
    total_cap = sum(t.capacity for t in tables)
    # pad the output to a power-of-two bucket: incremental merges would
    # otherwise see arbitrary capacity sums (8192+1024=9216, ...) and
    # compile a fresh program per sum; bucketing collapses them
    out_cap = bucket_rows(total_cap, min_bucket)
    tail = out_cap - total_cap
    compacted = [t.compact() for t in tables]
    out_cols: List[DeviceColumn] = []
    for ci in range(first.num_columns):
        out_cols.append(_concat_columns([t.columns[ci] for t in compacted],
                                        tail))
    row_mask = jnp.concatenate([t.row_mask for t in compacted])
    if tail:
        row_mask = jnp.pad(row_mask, (0, tail))
    num_rows = sum((t.num_rows for t in tables), jnp.asarray(0, jnp.int32))
    out = DeviceTable(tuple(out_cols), row_mask, num_rows, first.names)
    return out.compact()


def _concat_columns(parts: List[DeviceColumn], tail: int) -> DeviceColumn:
    """Concatenate one column's parts along rows, padding ``tail`` extra
    masked-off rows; recurses into struct/map children."""
    ev = None
    kids = None
    if parts[0].children is not None:
        kids = tuple(_concat_columns([p.children[i] for p in parts], tail)
                     for i in range(len(parts[0].children)))
        data = jnp.concatenate([p.data for p in parts])
        if tail:
            data = jnp.pad(data, (0, tail))
        lengths = None
    elif parts[0].lengths is not None:    # strings AND fixed-width lists
        width = max(p.data.shape[1] for p in parts)
        datas = [jnp.pad(p.data, ((0, 0), (0, width - p.data.shape[1])))
                 for p in parts]
        data = jnp.concatenate(datas, axis=0)
        lengths = jnp.concatenate([p.lengths for p in parts])
        if any(p.elem_validity is not None for p in parts):
            evs = [jnp.pad(p.elem_validity
                           if p.elem_validity is not None
                           else jnp.ones(p.data.shape, dtype=bool),
                           ((0, 0), (0, width - p.data.shape[1])))
                   for p in parts]
            ev = jnp.concatenate(evs, axis=0)
        if tail:
            data = jnp.pad(data, ((0, tail), (0, 0)))
            lengths = jnp.pad(lengths, (0, tail))
            if ev is not None:
                ev = jnp.pad(ev, ((0, tail), (0, 0)))
    else:
        data = jnp.concatenate([p.data for p in parts])
        if tail:
            data = jnp.pad(data, [(0, tail)] + [(0, 0)] * (data.ndim - 1))
        lengths = None
    validity = jnp.concatenate([p.validity for p in parts])
    if tail:
        validity = jnp.pad(validity, (0, tail))
    return DeviceColumn(data, validity, parts[0].dtype, lengths, ev, kids,
                        all(p.all_valid for p in parts))


_concat_jitted = named_program(_concat_impl, "concat",
                               static_argnums=(1,))


def slice_rows(table: DeviceTable, start, length: int) -> DeviceTable:
    """Static-length row window [start, start+length) (start may be traced).

    Rows past the table's active count are masked off. Building block for
    out-of-core chunking (reference: GpuOutOfCoreSortIterator splitting
    pending batches, GpuSortExec.scala:69). Jitted when called eagerly."""
    from ..shims import get_shims
    if get_shims().is_tracer(start) or get_shims().is_tracer(table.num_rows):
        return _slice_rows_impl(table, start, length)
    return _slice_rows_jitted(table, start, length)


def _slice_rows_impl(table: DeviceTable, start, length: int) -> DeviceTable:
    start = jnp.asarray(start, jnp.int32)
    # dynamic_slice clamps start to [0, cap-length]; pre-clamp identically so
    # the row mask agrees with the slice actually taken
    start = jnp.clip(start, 0, max(table.capacity - length, 0))

    def slc(a: jax.Array) -> jax.Array:
        # all start indices must share one dtype (2-D string data would
        # otherwise mix the int32 row start with default-int64 zeros)
        starts = (start,) + (jnp.int32(0),) * (a.ndim - 1)
        sizes = (min(length, a.shape[0]),) + a.shape[1:]
        out = jax.lax.dynamic_slice(a, starts, sizes)
        if length > a.shape[0]:
            pad = ((0, length - a.shape[0]),) + ((0, 0),) * (a.ndim - 1)
            out = jnp.pad(out, pad)
        return out

    def slc_col(c: DeviceColumn) -> DeviceColumn:
        return DeviceColumn(
            slc(c.data), slc(c.validity), c.dtype,
            None if c.lengths is None else slc(c.lengths),
            None if c.elem_validity is None else slc(c.elem_validity),
            None if c.children is None
            else tuple(slc_col(k) for k in c.children), c.all_valid)

    cols = tuple(slc_col(c) for c in table.columns)
    iota = jnp.arange(length, dtype=jnp.int32)
    mask = jnp.logical_and(slc(table.row_mask),
                           (iota + start) < table.num_rows)
    return DeviceTable(cols, mask, jnp.sum(mask, dtype=jnp.int32),
                       table.names)


_slice_rows_jitted = named_program(_slice_rows_impl, "slice_rows",
                                   static_argnums=(2,))


def shrink_to_fit(table: DeviceTable, min_bucket: Optional[int] = None,
                  num_rows: Optional[int] = None) -> DeviceTable:
    """Compact and shrink capacity to the bucket of the active row count,
    in one program that builds only the rows it keeps (``compact_shrink``).

    Syncs the row count to host (one int) — used between pipeline steps to
    stop capacities from growing across incremental merges. Callers that
    already hold the host count pass ``num_rows`` to skip the sync (a
    caller that wants more than the count reads it with the rest in one
    ``resolve_scalars`` first). Span ``shrink`` (``rows_in``,
    ``rows_out``) when the program runs, ``shrink.skip`` when the table
    already fits its bucket."""
    min_bucket = resolve_min_bucket(min_bucket)
    tracer = get_tracer()
    # cannot shrink below one bucket: skip the device sync too
    if table.capacity > min_bucket:
        if num_rows is not None:
            n = num_rows
        else:
            t0 = movement.clock()
            with tracer.span("sync", "download", on=table.num_rows,
                             scalars=1):
                n = int(table.num_rows)  # srtpu: sync-ok(capacity choice needs the host count; callers with one pass it in)
            movement.note_d2h(_MOVE_SHRINK, 4, t0)
        cap = bucket_rows(max(n, 1), min_bucket)
        if cap < table.capacity:
            with tracer.span("shrink", "shrink", rows_in=table.capacity,
                             rows_out=cap):
                return _compact_shrink_jitted(table, cap)
    with tracer.span("shrink.skip", "shrink", rows_in=table.capacity):
        return table


def append_column(table: DeviceTable, name: str, col: DeviceColumn
                  ) -> DeviceTable:
    return DeviceTable(table.columns + (col,), table.row_mask,
                       table.num_rows, table.names + (name,))


def drop_column(table: DeviceTable, name: str) -> DeviceTable:
    i = table.names.index(name)
    return DeviceTable(table.columns[:i] + table.columns[i + 1:],
                       table.row_mask, table.num_rows,
                       table.names[:i] + table.names[i + 1:])


def shard_row_counts(table: DeviceTable, n: int) -> List["jax.Array"]:
    """Per-shard active-row counts of a row-sharded table, in shard
    order. Each count is a LAZY device scalar (a sum over the shard's
    addressable mask piece) — callers bulk-resolve them in one funnel
    transfer (``resolve_scalars`` / ``jax.device_get``) instead of
    syncing per shard. Used by the keep-sharded exchange path, where
    the mask is never split into per-device tables."""
    shards = sorted(table.row_mask.addressable_shards,
                    key=lambda s: s.index[0].start or 0)
    assert len(shards) == n, f"{len(shards)} shards, expected {n}"
    return [jnp.sum(s.data, dtype=jnp.int32) for s in shards]


def pack_string_key_words(data: "jax.Array", lengths: "jax.Array"):
    """(cap, w) uint8 + lengths -> list of 1-D uint64 words, most-significant
    first, whose word-wise unsigned order equals lexicographic byte order;
    the length is the final word so zero padding can't conflate "ab" with
    "ab\\x00". Shared by the device groupby and sort kernels for string keys
    (the reference gets native string keys from cudf)."""
    cap, w = data.shape
    words = []
    for start in range(0, w, 8):
        chunk = data[:, start:start + 8]
        word = jnp.zeros((cap,), dtype=jnp.uint64)
        for j in range(chunk.shape[1]):
            word = word | (chunk[:, j].astype(jnp.uint64)
                           << jnp.uint64(8 * (7 - j)))
        words.append(word)
    words.append(lengths.astype(jnp.uint64))
    return words
