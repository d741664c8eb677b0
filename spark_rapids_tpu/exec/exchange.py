"""TpuShuffleExchangeExec — the planner-reachable device (ICI) exchange tier.

Reference mapping: GpuShuffleExchangeExecBase.scala:146 (device exchange
exec) + GpuPartitioning.sliceInternalOnGpu (GpuPartitioning.scala:49,130).
The TPU-native design replaces per-partition slicing + transport with ONE
``jax.lax.all_to_all`` over the mesh's ``dp`` axis (shuffle/ici.py): rows are
re-homed across ICI links inside a single XLA program, no host staging.

Right-sized quotas: a cheap count pass (download of the int32 partition-id
vector only) sizes the per-(source, destination) slot quota before the
exchange compiles, killing the n_devices× intermediate blowup of the naive
static shape. Quotas are bucketed so repeated exchanges reuse the cached XLA
program. The count pass runs on the coordinating process — the analogue of
the reference's driver-side sampling for range bounds (GpuRangePartitioner).

The host-staged ``ShuffleExchangeExec`` (plan/physical.py) remains the
always-available tier, exactly like the reference's default-Spark-shuffle
mode vs the RapidsShuffleManager (SURVEY §2.7).
"""
from __future__ import annotations

from typing import Iterator, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..columnar.device import (DeviceColumn, DeviceTable, bucket_rows,
                               resolve_min_bucket, shard_row_counts,
                               concat_device_tables)
from ..conf import register_conf
from ..plan.physical import HashPartitioning, PhysicalPlan
from ..shuffle import telemetry as shuffle_telemetry
from ..utils import metrics as M
from ..utils import movement
from ..utils.compile_cache import cached_jit
from ..utils.tracing import get_tracer
from .base import TpuExec

__all__ = ["TpuShuffleExchangeExec", "TpuLocalExchangeExec", "SHUFFLE_MODE",
           "pad_table_capacity"]

SHUFFLE_MODE = register_conf(
    "spark.rapids.tpu.shuffle.mode",
    "Shuffle exchange tier: 'auto' uses the on-device ICI all-to-all when "
    "the session has a device mesh attached and the device-local coalesce "
    "when it does not (single chip); 'ici' builds a mesh over all "
    "addressable devices; 'local' forces the single-device coalesce tier; "
    "'host' forces the host-staged tier (reference: rapids shuffle manager "
    "vs default Spark shuffle, SURVEY §2.7).", "auto",
    checker=lambda v: None if v in ("auto", "host", "ici", "local")
    else f"must be one of auto/host/ici/local, got {v!r}")

# movement-observatory site identities (utils/movement.py SITES)
_MOVE_CHUNK = ("spark_rapids_tpu/exec/exchange.py"
               "::TpuShuffleExchangeExec._exchange_chunk")

# shuffle-observatory identities for planner exchanges: a process-wide
# counter (manager shuffle ids are per-manager and the planner tiers
# never allocate one)
_EXCHANGE_IDS = __import__("itertools").count()
EXCHANGE_CHUNK_ROWS = register_conf(
    "spark.rapids.tpu.shuffle.exchangeChunkRows",
    "Max staged row capacity per device-exchange chunk. Child batches "
    "stream through the ICI all-to-all in bounded chunks instead of one "
    "concat of the entire input, so the exchange stays out-of-core: only "
    "one chunk is staged on devices at a time and finished output shards "
    "can spill (reference: the streaming per-batch exchange, "
    "GpuShuffleExchangeExecBase.scala:146).", 1 << 19,
    checker=lambda v: None if int(v) > 0 else "must be positive")


def _pid_program(key_names: List[str], n: int):
    """The count pass's program: the partition id of every row of a chunk,
    ``n`` for a masked-off row. One cache entry per (keys, partitions), so
    the chunks of every exchange of that shape share one trace."""
    def build():
        from ..shuffle.manager import device_partition_ids
        return lambda t: jnp.where(
            t.row_mask, device_partition_ids(t, key_names, n), n)
    return cached_jit(f"exchange_pid|{'|'.join(key_names)}|{n}", build,
                      name="exchange_pid")


def pad_table_capacity(table: DeviceTable, capacity: int) -> DeviceTable:
    """Grow a table's padded capacity (new slots masked off)."""
    if capacity <= table.capacity:
        return table
    extra = capacity - table.capacity

    def pad_col(c: DeviceColumn) -> DeviceColumn:
        pad_width = ((0, extra),) + ((0, 0),) * (c.data.ndim - 1)
        return DeviceColumn(
            jnp.pad(c.data, pad_width),
            jnp.pad(c.validity, (0, extra)), c.dtype,
            None if c.lengths is None else jnp.pad(c.lengths, (0, extra)),
            None if c.elem_validity is None
            else jnp.pad(c.elem_validity, ((0, extra), (0, 0))),
            None if c.children is None
            else tuple(pad_col(k) for k in c.children))

    return DeviceTable(tuple(pad_col(c) for c in table.columns),
                       jnp.pad(table.row_mask, (0, extra)),
                       table.num_rows, table.names)


class TpuShuffleExchangeExec(TpuExec):
    """Hash exchange as a mesh collective; output partition = mesh shard."""

    EXTRA_METRICS = (M.SHUFFLE_BYTES, M.PIPELINE_WAIT)

    def __init__(self, child: PhysicalPlan, partitioning: HashPartitioning,
                 mesh, min_bucket: Optional[int] = None, axis: str = "dp",
                 chunk_rows: int = 1 << 19):
        super().__init__()
        self.child = child
        self.children = (child,)
        self.partitioning = partitioning
        self.mesh = mesh
        self.axis = axis
        self.min_bucket = resolve_min_bucket(min_bucket)
        self.chunk_rows = max(int(chunk_rows), 1)
        self.schema = child.schema
        self.telemetry_sid = next(_EXCHANGE_IDS)
        # spill handles per partition, one per exchanged chunk
        self._shards: Optional[List[List]] = None
        # keep-sharded mode (exec/mesh.py): a mesh-capable consumer takes
        # the exchanged output STILL row-sharded over the mesh — no
        # _split_sharded, no per-shard spill registration; the chunk
        # tables live here until the mesh stage dispatches over them (or
        # a per-partition consumer forces a late split, _ensure_split)
        self._keep_sharded = False
        self._sharded_chunks: Optional[List[DeviceTable]] = None
        # per-chunk, per-shard input row counts (host ints — the batched
        # count sync pays for them anyway): the mesh stage uses them to
        # mirror the split path's non-empty-shard-only drain contract
        self._sharded_chunk_rows: Optional[List[List[int]]] = None
        # v7 skew telemetry: per-output-partition rows (free — the bulk
        # shard_rows sync) and byte estimates accumulated across chunks;
        # the event log turns this into a shuffle_skew record
        self._skew_rows: Optional[List[int]] = None
        self._skew_bytes: Optional[List[int]] = None
        # pipelined partition drains race to materialize; exactly one wins
        # (parallel/pipeline.py pipelined_collect contract)
        self._mat_lock = __import__("threading").Lock()

    @property
    def num_partitions(self) -> int:
        return int(self.mesh.shape[self.axis])

    def node_desc(self) -> str:
        return (f"ici keys={self.partitioning.key_names} "
                f"n={self.num_partitions}")

    def execute_columnar(self, pidx: int) -> Iterator[DeviceTable]:
        self._materialize()
        self._ensure_split()
        from ..io.file_block import clear_input_file
        clear_input_file()  # post-shuffle rows have no single source file
        for handle in self._shards[pidx]:
            yield handle.get()

    # -- keep-sharded consumer API (exec/mesh.py) -----------------------------
    def request_keep_sharded(self) -> None:
        """Planner hook: the consumer is mesh-capable, so materialization
        should keep exchanged chunks row-sharded over the mesh instead of
        splitting them into per-device spill-registered partitions. Must
        be called before the exchange materializes (plan rewrite time)."""
        self._keep_sharded = True

    def sharded_chunks(self) -> Optional[List[tuple]]:
        """Materialize and return ``(chunk, shard_rows)`` pairs — each
        exchanged chunk table still row-sharded over the mesh ``dp``
        axis (one entry per streamed chunk) with its per-shard input row
        counts (host ints, from the chunk's batched count sync). Returns
        None when the output already split per-partition (keep-sharded
        was never requested, or a per-partition consumer forced the
        split first) — the caller must use the per-partition
        ``execute_columnar`` path instead."""
        self._materialize()
        with self._mat_lock:
            if self._shards is not None:
                return None
            return list(zip(self._sharded_chunks or [],
                            self._sharded_chunk_rows or []))

    def _ensure_split(self) -> None:
        """Late per-partition conversion of keep-sharded output: a
        non-mesh consumer (the mesh stage's fallback path, or a plan that
        reused the exchange) needs spill-registered per-device shards
        after all."""
        if self._shards is not None:
            return
        with self._mat_lock:
            if self._shards is not None:
                return
            # registration's budget check can spill; never block on the
            # semaphore while holding this shared lock (PR-3 class)
            from ..parallel.pipeline import exempt_admission
            with exempt_admission():
                chunks, self._sharded_chunks = self._sharded_chunks, None
                self._sharded_chunk_rows = None
                n = self.num_partitions
                shards: List[List] = [[] for _ in range(n)]
                for t in chunks or []:
                    self._register_split(t, shards)
                self._shards = shards

    # -- the exchange ---------------------------------------------------------
    def _materialize(self) -> None:
        """Stream child batches through the all-to-all in bounded chunks.

        Only one chunk's input is staged on devices at a time (the in-
        flight chunk is catalog-registered at ACTIVE priority so earlier
        output shards spill first when the budget tightens), keeping the
        exchange out-of-core — the operator that sees the most data must
        not require the whole input resident (reference: per-batch
        streaming in GpuShuffleExchangeExecBase.scala:146)."""
        with self._mat_lock:
            if self._shards is not None or self._sharded_chunks is not None:
                return
            # never block on the semaphore while holding this shared lock
            # (parallel/pipeline.py exempt_admission invariant)
            from ..parallel.pipeline import exempt_admission
            with exempt_admission():
                self._materialize_locked()

    def _materialize_locked(self) -> None:
        from functools import partial
        from ..parallel.pipeline import OrderedFanIn
        n = self.num_partitions
        shards: List[List] = [[] for _ in range(n)]
        if self._keep_sharded:
            self._sharded_chunks = []
            self._sharded_chunk_rows = []
        self._skew_rows = [0] * n
        self._skew_bytes = [0] * n
        total_rows = 0
        # NOTE: child batch consumption stays OUTSIDE the op timer — the
        # upstream pipeline accounts its own opTime; only the exchange
        # work (concat/count/all-to-all, inside _exchange_chunk) is ours
        pending: List[DeviceTable] = []
        staged = 0
        # Map-side production: one bounded producer an input partition, all
        # started together, so every device runs its partition of the child
        # (a join's build, prep and probe) while this thread consumes them
        # in partition order: the batches reach _exchange_chunk in the order
        # of the serial drain, and the ICI collective itself stays on this
        # one thread.
        batches = OrderedFanIn(
            [partial(self.child_device_batches, p)
             for p in range(self.child.num_partitions)],  # srtpu: mesh-ok(map-side INPUT production: upstream partitions stream into the collective, the ICI all-to-all itself runs mesh-wide)
            stage="shuffle_map", registry=self.metrics)
        with get_tracer().span("exchange.map", "exchange",
                               producers=batches.producers):
            for b in batches:
                # no per-batch row-count sync here: int(b.num_rows) would
                # block the map loop on every upstream batch (ROADMAP item
                # 1). All-masked batches flow through — the count pass parks
                # their rows and the quota ignores them.
                if not b.capacity:
                    continue
                pending.append(b)
                staged += b.capacity
                if staged >= self.chunk_rows:
                    total_rows += self._exchange_chunk(pending, shards)
                    pending, staged = [], 0
            if pending:
                total_rows += self._exchange_chunk(pending, shards)
        if self._keep_sharded:
            # output stays one sharded table per chunk (the mesh stage
            # dispatches over all shards at once); _shards stays None
            # until a per-partition consumer forces _ensure_split
            self.metrics.add(M.NUM_OUTPUT_BATCHES,
                             len(self._sharded_chunks))
        else:
            self._shards = shards
            self.metrics.add(M.NUM_OUTPUT_BATCHES,
                             sum(len(s) for s in shards))
        self.metrics.add(M.NUM_OUTPUT_ROWS, total_rows)

    def _exchange_chunk(self, batches: List[DeviceTable],
                        shards: List[List]) -> int:
        """All-to-all one bounded chunk; append per-partition spill handles.

        Only this method sits inside the op timer — child batch
        production accounts its own opTime upstream."""
        from ..memory.catalog import SpillPriorities, get_catalog
        from ..shuffle.ici import ici_all_to_all_exchange, shard_table

        n = self.num_partitions
        catalog = get_catalog()
        tracer = get_tracer()
        with self.metrics.timed(M.OP_TIME):
            table = concat_device_tables(batches, self.min_bucket)
            chunk_nbytes = table.nbytes()
            self.metrics.add(M.SHUFFLE_BYTES, chunk_nbytes)
            # observatory enqueue note mirrors the shuffleBytes metric
            # exactly (pre-padding logical bytes), so the shuffle_summary
            # tier breakdown reconciles with the operator metric
            shuffle_telemetry.note_transfer(
                "ici", "enqueue", shuffle_id=self.telemetry_sid,
                logical_bytes=chunk_nbytes)
            per_shard = bucket_rows(
                max(1, -(-table.capacity // n)), self.min_bucket)
            table = pad_table_capacity(table, per_shard * n)
            # account the in-flight chunk: registration's budget check
            # spills already-finished output shards down-tier to make room
            inflight = catalog.register(table,
                                        SpillPriorities.ACTIVE_ON_DECK)
            try:
                # count pass: partition ids only (4 bytes/row) -> quota
                keys = self.partitioning.key_names
                with tracer.span("exchange.count", "exchange") as count:
                    pid = _pid_program(keys, n)(table)
                    count.note(bytes=pid.nbytes)
                    t0 = movement.clock()
                    with tracer.span("d2h", "download", on=pid,
                                     bytes=pid.nbytes):
                        pid_host = np.asarray(jax.device_get(pid))  # srtpu: sync-ok(the deliberate partition-count funnel: one transfer sizes every shard buffer for the chunk)
                    movement.note_d2h(_MOVE_CHUNK, pid_host.nbytes, t0)
                    src = np.arange(table.capacity) // per_shard
                    active = pid_host < n
                    counts = np.zeros((n, n), dtype=np.int64)
                    np.add.at(counts, (src[active], pid_host[active]), 1)
                    max_cnt = int(counts.max()) if active.any() else 1
                    quota = min(per_shard,
                                bucket_rows(max_cnt, self.min_bucket))
                    # what the all-to-all carries, padding included: 1 -
                    # rows / slots is its padding share
                    count.note(rows=int(counts.sum()), quota=quota,
                               slots=n * n * quota)

                # where the rows leave the device that produced them
                with tracer.span("exchange.shard", "exchange",
                                 bytes=chunk_nbytes):
                    sharded = shard_table(table, self.mesh, self.axis)
                del table, batches
                exchanged = ici_all_to_all_exchange(
                    sharded, keys, self.mesh, self.axis, quota=quota,
                    telemetry_sid=self.telemetry_sid)
                if self._keep_sharded and self._sharded_chunks:
                    # a SECOND chunk is streaming: kept-sharded chunks
                    # are not spill-registered, so accumulating them
                    # would break the exchange's out-of-core contract
                    # (only one chunk's worth resident, earlier output
                    # spillable). The contract wins — revert to split
                    # mode, registering the kept chunk; the mesh stage
                    # sees sharded_chunks() == None and falls back to
                    # the per-partition path (exec/mesh.py)
                    self._keep_sharded = False
                    kept, self._sharded_chunks = self._sharded_chunks, None
                    self._sharded_chunk_rows = None
                    for t in kept:
                        self._register_split(t, shards)
                if self._keep_sharded:
                    # mesh-capable consumer: the chunk stays ONE sharded
                    # table (no split, no per-shard spill registration —
                    # the mesh stage dispatches over it next); only the
                    # per-destination row counts sync, for skew + quota
                    # telemetry parity with the split path
                    t0 = movement.clock()
                    with tracer.span("sync", "download",
                                     on=exchanged.row_mask, scalars=n):
                        shard_rows = jax.device_get(  # srtpu: sync-ok(batched count sync, 4B per shard once per chunk)
                            shard_row_counts(exchanged, n))
                    movement.note_d2h(_MOVE_CHUNK, 4 * len(shard_rows), t0)
                    self._sharded_chunks.append(exchanged)
                    self._sharded_chunk_rows.append(
                        [int(c) for c in shard_rows])
                else:
                    shard_rows = self._register_split(exchanged, shards)
                # v7 skew: per-destination rows come free with the bulk
                # count sync; bytes are estimated as rows × the chunk's
                # mean row width (per-shard padded nbytes would read
                # uniform regardless of the actual distribution)
                chunk_total = int(sum(int(c) for c in shard_rows))
                bpr = chunk_nbytes / max(1, chunk_total)
                for i, cnt in enumerate(shard_rows):
                    self._skew_rows[i] += int(cnt)
                    self._skew_bytes[i] += int(round(int(cnt) * bpr))
                return chunk_total
            finally:
                inflight.close()

    def _register_split(self, exchanged: DeviceTable,
                        shards: List[List]) -> List[int]:
        """Split one exchanged chunk into per-device partition views and
        spill-register each non-empty shard so the catalog accounts for
        them and can spill them until downstream consumption; the entries
        release at query end (release_spill_handles), with a GC finalizer
        fallback. Returns the per-shard row counts."""
        from ..memory.catalog import SpillPriorities, get_catalog
        catalog = get_catalog()
        n = self.num_partitions
        tracer = get_tracer()
        with tracer.span("exchange.split", "exchange"):
            parts = _split_sharded(exchanged, n)
            # ONE bulk D2H of n 4-byte scalars replaces a blocking round
            # trip per shard plus one more for the row total
            t0 = movement.clock()
            with tracer.span("sync", "download", on=exchanged.row_mask,
                             scalars=n):
                shard_rows = jax.device_get(  # srtpu: sync-ok(batched count sync, 4B per shard once per chunk)
                    [t.num_rows for t in parts])
            movement.note_d2h(_MOVE_CHUNK, 4 * len(shard_rows), t0)
            for i, (t, cnt) in enumerate(zip(parts, shard_rows)):
                if not int(cnt):
                    continue
                h = catalog.register(t, SpillPriorities.OUTPUT_FOR_SHUFFLE)
                self._own_spill_handle(h)
                shards[i].append(h)
        return [int(c) for c in shard_rows]

    def shuffle_skew(self) -> Optional[dict]:
        """v7 event-log payload: the per-output-partition row/byte
        distribution accumulated across exchanged chunks. None until the
        exchange materialized (skew records only describe work done)."""
        if self._skew_rows is None:
            return None
        from ..utils.metrics import build_skew_record
        return build_skew_record(self._skew_rows, self._skew_bytes)


class TpuLocalExchangeExec(TpuExec):
    """Single-chip device-resident exchange: the whole input coalesces into
    ONE spill-registered output partition, never leaving the device.

    Under a mesh the same operator is the single-partition gather
    (``gather_device`` set): the child's partitions live one on each device
    of the mesh, and every non-empty batch is copied chip to chip onto
    ``gather_device`` before it is registered, so the consumer's concat sees
    one device.

    With one addressable chip there is no locality to exploit and no
    transport to ride: hash, range and single partitioning contracts are
    all trivially satisfied by a single output partition (all rows of any
    key land together; global order is whatever the downstream sort makes
    of its one partition). The host-staged tier's download-partition-upload
    round trip — the single largest overhead of single-chip plans — is
    gone; out-of-core pressure is handled downstream (grace join, OOC
    sort/agg) and by the catalog spill handles held here.

    The local analogue of Spark AQE's local shuffle reader; tier selection
    mirrors the reference's RapidsShuffleManager vs default-Spark-shuffle
    split (SURVEY §2.7; GpuShuffleExchangeExecBase.scala:146)."""

    EXTRA_METRICS = (M.SHUFFLE_BYTES,)

    def __init__(self, child: PhysicalPlan, partitioning,
                 min_bucket: Optional[int] = None, gather_device=None):
        super().__init__()
        self.child = child
        self.children = (child,)
        self.partitioning = partitioning
        self.min_bucket = resolve_min_bucket(min_bucket)
        self.gather_device = gather_device
        self.schema = child.schema
        self.telemetry_sid = next(_EXCHANGE_IDS)
        self._handles: Optional[List] = None
        # v7 skew telemetry: one output partition, so the distribution is
        # trivially balanced — recorded anyway for a uniform record set
        self._skew: Optional[tuple] = None
        self._mat_lock = __import__("threading").Lock()

    @property
    def num_partitions(self) -> int:
        return 1

    def node_desc(self) -> str:
        if self.gather_device is not None:
            return f"gather n=1 -> device {self.gather_device.id}"
        return "local n=1"

    def _materialize(self) -> None:
        with self._mat_lock:
            if self._handles is not None:
                return
            from ..parallel.pipeline import exempt_admission
            with exempt_admission():
                self._materialize_locked()

    def _materialize_locked(self) -> None:
        from ..memory.catalog import SpillPriorities, get_catalog
        from ..parallel.pipeline import parallel_map
        catalog = get_catalog()
        from ..columnar.device import resolve_scalars, shrink_to_fit
        from .aggregate import fused_grouped_aggregate, passed_through
        # a fused partial aggregate's batches arrive unshrunk: their row
        # count, read below, is the group count its branch was picked by
        # (a batch it passed through ran no branch: it booked agg.skip)
        fused_agg = fused_grouped_aggregate(self.child)
        # node context is thread-local; drain() runs on pool workers, so
        # capture the query identity here (the materializing thread holds
        # the instrumented node scope) and attribute notes explicitly
        from ..utils import node_context
        _ctx = node_context.current()
        _qid = _ctx.query_id if _ctx is not None else None

        def drain(p: int):
            """One map-side partition: drain, compact, spill-register.
            Runs per-partition on the bounded task pool (parallel map-side
            writes) — the catalog and metric registries are thread-safe."""
            out = []
            batches = list(self.child_device_batches(p))
            if not batches:
                return out
            # ONE batched-funnel transfer resolves every map batch's row
            # count for the partition (was one 4B sync per batch); every
            # batch's compute has dispatched before the host blocks
            ns = resolve_scalars(*[b.num_rows for b in batches])
            for b, n in zip(batches, ns):
                n = int(n)
                if fused_agg is not None and not passed_through(b):
                    fused_agg.book_branch(n, b.capacity, on=b.row_mask)
                if not n:
                    continue
                with self.metrics.timed(M.OP_TIME):
                    # the exchange is a compaction point (design rule 2 in
                    # columnar/device.py): post-filter / fused-partial-agg
                    # batches can be mostly masked slack — forwarding full
                    # capacity would inflate every downstream kernel
                    shrunk = shrink_to_fit(b, self.min_bucket, num_rows=n)
                    nbytes = shrunk.nbytes()
                    if self.gather_device is not None:
                        with get_tracer().span("exchange.gather", "exchange",
                                               bytes=nbytes):
                            shrunk = jax.device_put(shrunk,
                                                    self.gather_device)
                    self.metrics.add(M.SHUFFLE_BYTES, nbytes)
                    # mirrors the shuffleBytes metric add exactly so the
                    # shuffle_summary tier bytes reconcile with it
                    shuffle_telemetry.note_transfer(
                        "local", "enqueue",
                        shuffle_id=self.telemetry_sid, partition=p,
                        logical_bytes=nbytes, query_id=_qid)
                    h = catalog.register(
                        shrunk, SpillPriorities.OUTPUT_FOR_SHUFFLE)
                self._own_spill_handle(h)
                out.append((h, n, nbytes))
            return out

        per_part = parallel_map(drain, range(self.child.num_partitions),
                                stage="local_exchange_map")
        handles: List = [h for part in per_part for h, _n, _b in part]
        rows = sum(n for part in per_part for _h, n, _b in part)
        nbytes = sum(b for part in per_part for _h, _n, b in part)
        self._handles = handles
        self._skew = ([rows], [nbytes])
        self.metrics.add(M.NUM_OUTPUT_BATCHES, len(handles))
        self.metrics.add(M.NUM_OUTPUT_ROWS, rows)

    def execute_columnar(self, pidx: int) -> Iterator[DeviceTable]:
        self._materialize()
        from ..io.file_block import clear_input_file
        clear_input_file()  # post-shuffle rows have no single source file
        for handle in self._handles:
            yield handle.get()

    def shuffle_skew(self) -> Optional[dict]:
        """v7 event-log payload (single-partition tier: imbalance 1.0)."""
        if self._skew is None:
            return None
        from ..utils.metrics import build_skew_record
        return build_skew_record(*self._skew)


def _split_sharded(table: DeviceTable, n: int) -> List[Optional[DeviceTable]]:
    """Per-shard views of a row-sharded table (zero-copy: each output batch
    is the addressable shard living on its own device)."""

    def parts(arr: jax.Array) -> List[jax.Array]:
        shards = sorted(arr.addressable_shards,
                        key=lambda s: s.index[0].start or 0)
        assert len(shards) == n, f"{len(shards)} shards, expected {n}"
        return [s.data for s in shards]

    mask_parts = parts(table.row_mask)

    def split_col(c: DeviceColumn) -> List[DeviceColumn]:
        d = parts(c.data)
        v = parts(c.validity)
        l = None if c.lengths is None else parts(c.lengths)
        e = None if c.elem_validity is None else parts(c.elem_validity)
        kids = None if c.children is None \
            else [split_col(k) for k in c.children]
        return [DeviceColumn(d[i], v[i], c.dtype,
                             None if l is None else l[i],
                             None if e is None else e[i],
                             None if kids is None
                             else tuple(ks[i] for ks in kids))
                for i in range(n)]

    col_parts = [split_col(c) for c in table.columns]
    out: List[Optional[DeviceTable]] = []
    for i in range(n):
        cols = tuple(cp[i] for cp in col_parts)
        mask = mask_parts[i]
        out.append(DeviceTable(cols, mask, jnp.sum(mask, dtype=jnp.int32),
                               table.names))
    return out
