"""Adaptive query execution (AQE).

Reference mapping: the plugin re-enters planning per query stage via
GpuQueryStagePrepOverrides / columnarRules on AdaptiveSparkPlanExec
(GpuOverrides.scala:4010-4042), and rewrites shuffle reads with
GpuCustomShuffleReaderExec (coalesced / skew-split partition specs).

TPU-native shape: the engine owns the whole scheduler, so AQE is a loop over
*materialization frontiers* instead of a Spark-callback protocol:

1. find exchanges whose subtree holds no other exchange (the frontier),
2. materialize one stage (build sides of joins first), recording per-partition
   row/byte statistics — the MapOutputStatistics analogue,
3. re-plan the remainder with runtime stats:
   - join demotion: a shuffled hash join whose build side materialized under
     the broadcast threshold becomes a broadcast hash join, and the probe
     side's *unmaterialized* exchange is deleted (extraneous-shuffle removal),
   - skew split: an oversized probe partition is split into row ranges, the
     build partition repeated per chunk (OptimizeSkewedJoin),
   - partition coalescing: adjacent small output partitions merge toward the
     advisory size (CoalesceShufflePartitions),
4. repeat until no exchange remains, then lower the final segment through
   ``apply_overrides`` like any other plan.

Every rewrite is recorded in ``AdaptiveExec.events`` so tests and the
profiler can assert what AQE actually did.
"""
from __future__ import annotations

from typing import Iterator, List, Optional, Sequence

from ..conf import RapidsConf, register_conf
from ..columnar.host import HostTable
from ..utils.tracing import get_tracer
from .physical import (HashPartitioning, PhysicalPlan, RangePartitioning,
                       ShuffleExchangeExec, SinglePartitioning)
from .physical_joins import CpuBroadcastHashJoinExec, CpuShuffledHashJoinExec

__all__ = ["AdaptiveExec", "ShuffleStageExec", "CoalescedStageReader",
           "SplitStageReader", "MappedStageReader", "AQE_ENABLED"]

AQE_ENABLED = register_conf(
    "spark.rapids.tpu.aqe.enabled",
    "Adaptive query execution: re-plan at exchange boundaries using runtime "
    "partition statistics (join demotion to broadcast, partition coalescing, "
    "skew-join splitting). Spark's spark.sql.adaptive.enabled analogue.",
    True)

AQE_ADVISORY_BYTES = register_conf(
    "spark.rapids.tpu.aqe.advisoryPartitionSizeBytes",
    "Target bytes per shuffle partition after AQE coalescing "
    "(spark.sql.adaptive.advisoryPartitionSizeInBytes analogue).",
    64 * 1024 * 1024)

AQE_COALESCE_ENABLED = register_conf(
    "spark.rapids.tpu.aqe.coalescePartitions.enabled",
    "Merge adjacent small shuffle partitions toward the advisory size "
    "(spark.sql.adaptive.coalescePartitions.enabled analogue).", True)

AQE_MIN_PARTITIONS = register_conf(
    "spark.rapids.tpu.aqe.coalescePartitions.minPartitionNum",
    "Lower bound on the partition count coalescing may produce.", 1)

AQE_BROADCAST_BYTES = register_conf(
    "spark.rapids.tpu.aqe.autoBroadcastJoinThreshold",
    "Max materialized build-side bytes for AQE join demotion to broadcast; "
    "-1 disables demotion (spark.sql.adaptive + autoBroadcastJoinThreshold).",
    10 * 1024 * 1024)

AQE_SKEW_ENABLED = register_conf(
    "spark.rapids.tpu.aqe.skewJoin.enabled",
    "Split skewed probe-side partitions of shuffled hash joins "
    "(spark.sql.adaptive.skewJoin.enabled analogue).", True)

AQE_SKEW_FACTOR = register_conf(
    "spark.rapids.tpu.aqe.skewJoin.skewedPartitionFactor",
    "A partition is skewed when its bytes exceed this multiple of the "
    "median partition size (and the threshold below).", 5)

AQE_SKEW_THRESHOLD = register_conf(
    "spark.rapids.tpu.aqe.skewJoin.skewedPartitionThresholdBytes",
    "Minimum bytes for a partition to be considered skewed.",
    256 * 1024 * 1024)

AQE_RUNTIME_FILTER = register_conf(
    "spark.rapids.tpu.aqe.runtimeFilter.enabled",
    "When a join demotes to broadcast, push the build side's distinct join "
    "keys into the probe side's scan as an IN filter (the dynamic-partition-"
    "pruning / GpuSubqueryBroadcastExec analogue: the reader skips row "
    "groups whose statistics exclude every build key).", True)

AQE_RUNTIME_FILTER_MAX_KEYS = register_conf(
    "spark.rapids.tpu.aqe.runtimeFilter.maxKeys",
    "Skip the runtime IN-filter when the build side has more distinct keys "
    "than this.", 10_000)


class PartitionStats:
    """Per-partition rows/bytes of a materialized stage (the
    MapOutputStatistics analogue)."""

    def __init__(self, rows: List[int], nbytes: List[int]):
        self.rows = rows
        self.nbytes = nbytes

    @property
    def total_bytes(self) -> int:
        return sum(self.nbytes)

    @property
    def total_rows(self) -> int:
        return sum(self.rows)

    def __repr__(self):
        return f"PartitionStats(rows={self.total_rows}, bytes={self.total_bytes})"


class ShuffleStageExec(PhysicalPlan):
    """A materialized exchange, re-entering the plan as a leaf
    (ShuffleQueryStageExec analogue). ``inner`` is the *converted* exchange —
    either the host-tier ShuffleExchangeExec or the device-tier
    TpuShuffleExchangeExec — already materialized."""

    def __init__(self, inner: PhysicalPlan, partitioning, stats: PartitionStats):
        self.inner = inner
        self.children = ()
        self.schema = inner.schema
        self.partitioning = partitioning
        self.stats = stats

    @property
    def device_resident(self) -> bool:
        from ..exec.base import TpuExec
        return isinstance(self.inner, TpuExec)

    @property
    def num_partitions(self) -> int:
        return self.inner.num_partitions

    def execute(self, pidx: int) -> Iterator[HostTable]:
        from ..io.file_block import clear_input_file
        clear_input_file()  # stage output crossed a shuffle
        yield from self.inner.execute(pidx)

    def execute_columnar(self, pidx: int):
        from ..io.file_block import clear_input_file
        clear_input_file()
        yield from self.inner.execute_columnar(pidx)

    def node_desc(self) -> str:
        from ..exec.exchange import TpuLocalExchangeExec
        tier = ("local" if isinstance(self.inner, TpuLocalExchangeExec)
                else "ici" if self.device_resident else "host")
        return (f"{tier} n={self.num_partitions} rows={self.stats.total_rows} "
                f"bytes={self.stats.total_bytes}")

    def tree_string(self, indent: int = 0) -> str:
        # show the materialized stage subtree (explain/debug visibility —
        # AdaptiveSparkPlanExec prints its query stages the same way)
        pad = "  " * indent
        return "\n".join([f"{pad}{self.node_name()} [{self.node_desc()}]",
                          self.inner.tree_string(indent + 1)])


class CoalescedStageReader(PhysicalPlan):
    """Reads merged groups of stage partitions
    (GpuCustomShuffleReaderExec with CoalescedPartitionSpec)."""

    def __init__(self, stage: ShuffleStageExec, groups: List[List[int]]):
        self.stage = stage
        self.children = ()
        self.schema = stage.schema
        self.groups = groups

    @property
    def num_partitions(self) -> int:
        return len(self.groups)

    def execute(self, pidx: int) -> Iterator[HostTable]:
        for p in self.groups[pidx]:
            yield from self.stage.execute(p)

    def execute_columnar(self, pidx: int):
        for p in self.groups[pidx]:
            yield from self.stage.execute_columnar(p)

    @property
    def device_resident(self) -> bool:
        return self.stage.device_resident

    def node_desc(self) -> str:
        return f"{self.stage.num_partitions} -> {len(self.groups)}"

    def tree_string(self, indent: int = 0) -> str:
        pad = "  " * indent
        return "\n".join([f"{pad}{self.node_name()} [{self.node_desc()}]",
                          self.stage.tree_string(indent + 1)])


class SplitStageReader(PhysicalPlan):
    """Probe side of a skew-split join: each output partition is a row range
    of one stage partition (PartialReducerPartitionSpec analogue)."""

    def __init__(self, stage: ShuffleStageExec, entries: List[tuple]):
        # entries: (orig_partition, lo_row, hi_row); hi == -1 means "to end"
        self.stage = stage
        self.children = ()
        self.schema = stage.schema
        self.entries = entries
        self._cache = {}
        # chunks remaining per sliced partition: the concat cache drops as
        # soon as its last chunk is consumed (only SKEWED partitions are
        # cached; pass-through entries stream straight from the stage)
        # chunk indices per sliced partition + the set consumed since the
        # last eviction: the cache is evicted exactly when every chunk has
        # been read at least once (a full pass), so re-execution passes
        # (a join probe re-reading its build side) reuse the concat
        # instead of thrashing, and a partial retry can't double-evict
        self._chunk_ids: dict = {}
        for idx, (orig, lo, hi) in enumerate(entries):
            if not (lo == 0 and hi < 0):
                self._chunk_ids.setdefault(orig, set()).add(idx)
        self._consumed: dict = {}

    @property
    def num_partitions(self) -> int:
        return len(self.entries)

    def _partition_table(self, p: int) -> Optional[HostTable]:
        if p not in self._cache:
            batches = list(self.stage.execute(p))
            self._cache[p] = HostTable.concat(batches) if batches else None
        return self._cache[p]

    def execute(self, pidx: int) -> Iterator[HostTable]:
        orig, lo, hi = self.entries[pidx]
        if lo == 0 and hi < 0:  # pass-through: no slicing, no caching
            yield from self.stage.execute(orig)
            return
        t = self._partition_table(orig)
        seen = self._consumed.setdefault(orig, set())
        seen.add(pidx)
        if seen >= self._chunk_ids[orig]:  # full pass complete → evict
            self._cache.pop(orig, None)
            seen.clear()
        if t is None:
            return
        hi = t.num_rows if hi < 0 else min(hi, t.num_rows)
        if hi > lo:
            yield t.slice(lo, hi - lo)

    def node_desc(self) -> str:
        return f"{self.stage.num_partitions} -> {len(self.entries)} splits"


class MappedStageReader(PhysicalPlan):
    """Build side of a skew-split join: output partition p re-reads stage
    partition ``mapping[p]`` (repeated per probe chunk)."""

    def __init__(self, stage: ShuffleStageExec, mapping: List[int]):
        self.stage = stage
        self.children = ()
        self.schema = stage.schema
        self.mapping = mapping

    @property
    def num_partitions(self) -> int:
        return len(self.mapping)

    def execute(self, pidx: int) -> Iterator[HostTable]:
        yield from self.stage.execute(self.mapping[pidx])

    def node_desc(self) -> str:
        return f"map={self.mapping}"


# ---------------------------------------------------------------------------
# Stage materialization
# ---------------------------------------------------------------------------
def _device_shard_stats(handles) -> tuple:
    """(rows, bytes) of one output partition's spill handles. Every
    handle's row count is a read of a device scalar, a ``sync`` span each:
    the exchange's own count sync read the same numbers, but from other
    arrays (a ``shrink``, a gather or the split made these since), so each
    is a round trip of its own."""
    tracer = get_tracer()
    prows = pbytes = 0
    for h in handles:
        t = h.get()
        with tracer.span("sync", "download", on=t.num_rows, scalars=1):
            nrows = int(t.num_rows)  # srtpu: sync-ok(per-stage AQE statistics at materialization, not per-batch)
        prows += nrows
        # buffers are capacity-padded (pow2 buckets, min 1024 rows); scale
        # to the compacted row count so device-tier stats are comparable
        # with the host tier's true bytes — otherwise tiny build sides
        # look big and suppress AQE broadcast demotion
        for c in t.columns:
            cap = max(int(c.data.shape[0]), 1)
            pbytes += int(c.data.nbytes) * nrows // cap
    return prows, pbytes


def materialize_stage(cpu_exchange: ShuffleExchangeExec, conf: RapidsConf,
                      use_device: bool, events: List[str],
                      hook=None, stage: int = 0) -> ShuffleStageExec:
    """Plan the segment under ``cpu_exchange`` (span ``plan.aqe``), run it
    (``stage``), read its partition statistics (``stage.stats``); ``stage``
    numbers the query's stages for the spans."""
    from .overrides import apply_overrides
    tracer = get_tracer()
    with tracer.span("plan.aqe", "plan", stage=stage):
        converted = apply_overrides(cpu_exchange, conf) if use_device \
            else cpu_exchange
        # apply_overrides caps a device root with DeviceToHost for the
        # collect boundary; a stage is consumed by the next segment, so
        # unwrap it
        from ..exec.transitions import DeviceToHostExec
        if isinstance(converted, DeviceToHostExec):
            converted = converted.child
        if hook is not None:
            hook(converted)  # event-log instrumentation of the stage segment
    from ..exec.exchange import TpuLocalExchangeExec, TpuShuffleExchangeExec

    with tracer.span("stage", "stage", stage=stage,
                     exchange=type(converted).__name__):
        converted._materialize()
    if isinstance(converted, TpuLocalExchangeExec):
        with tracer.span("stage.stats", "stage", stage=stage, shards=1,
                         handles=len(converted._handles)):
            prows, pbytes = _device_shard_stats(converted._handles)
        stats = PartitionStats([prows], [pbytes])
    elif isinstance(converted, TpuShuffleExchangeExec):
        with tracer.span("stage.stats", "stage", stage=stage,
                         shards=len(converted._shards),
                         handles=sum(map(len, converted._shards))):
            per_shard = [_device_shard_stats(handles)
                         for handles in converted._shards]
        stats = PartitionStats([r for r, _ in per_shard],
                               [b for _, b in per_shard])
    else:
        assert isinstance(converted, ShuffleExchangeExec), type(converted)
        rows, nbytes = [], []
        for batches in converted._materialized:
            rows.append(sum(b.num_rows for b in batches))
            nbytes.append(sum(b.nbytes() for b in batches))
        stats = PartitionStats(rows, nbytes)
    events.append(f"materialized stage n={len(stats.rows)} "
                  f"rows={stats.total_rows} bytes={stats.total_bytes}")
    return ShuffleStageExec(converted, cpu_exchange.partitioning, stats)


# ---------------------------------------------------------------------------
# Plan surgery helpers
# ---------------------------------------------------------------------------
def _set_children(node: PhysicalPlan, children: List[PhysicalPlan]) -> PhysicalPlan:
    if list(node.children) == children:
        return node
    node.children = tuple(children)
    if hasattr(node, "child") and len(children) == 1:
        node.child = children[0]
    if hasattr(node, "left") and len(children) == 2:
        node.left, node.right = children
    return node


def _replace_node(node: PhysicalPlan, target: PhysicalPlan,
                  repl: PhysicalPlan) -> PhysicalPlan:
    if node is target:
        return repl
    return _set_children(
        node, [_replace_node(c, target, repl) for c in node.children])


def _walk(node: PhysicalPlan):
    yield node
    for c in node.children:
        yield from _walk(c)


def _frontier_exchanges(plan: PhysicalPlan) -> List[ShuffleExchangeExec]:
    """Exchanges with no exchange below them."""
    out = []
    for n in _walk(plan):
        if isinstance(n, ShuffleExchangeExec):
            if not any(isinstance(d, ShuffleExchangeExec)
                       for c in n.children for d in _walk(c)):
                out.append(n)
    return out


def _merge_groups(nbytes: Sequence[int], target: int,
                  min_parts: int) -> List[List[int]]:
    """Greedy adjacent merge toward the advisory size."""
    groups: List[List[int]] = []
    cur: List[int] = []
    acc = 0
    for i, b in enumerate(nbytes):
        if cur and acc + b > target:
            groups.append(cur)
            cur, acc = [], 0
        cur.append(i)
        acc += b
    if cur:
        groups.append(cur)
    # respect the floor by un-merging the largest groups
    while len(groups) < min_parts:
        big = max(range(len(groups)), key=lambda g: len(groups[g]))
        if len(groups[big]) < 2:
            break
        g = groups.pop(big)
        mid = len(g) // 2
        groups[big:big] = [g[:mid], g[mid:]]
    return groups


# ---------------------------------------------------------------------------
# The adaptive driver
# ---------------------------------------------------------------------------
class AdaptiveExec(PhysicalPlan):
    """Root node that owns the adaptive loop (AdaptiveSparkPlanExec
    analogue). The final plan is built lazily on first execution."""

    def __init__(self, cpu_plan: PhysicalPlan, conf: RapidsConf,
                 use_device: bool = True):
        self.cpu_plan = cpu_plan
        self.conf = conf
        self.use_device = use_device
        self.children = ()
        self.schema = cpu_plan.schema
        self.events: List[str] = []
        self._final: Optional[PhysicalPlan] = None
        import threading
        # pipelined partition drains may race into the adaptive loop; the
        # first caller runs it, the rest wait for the final plan
        self._final_lock = threading.Lock()

    # -- PhysicalPlan surface -------------------------------------------------
    @property
    def num_partitions(self) -> int:
        return self.final_plan().num_partitions

    def execute(self, pidx: int) -> Iterator[HostTable]:
        yield from self.final_plan().execute(pidx)

    def node_desc(self) -> str:
        return f"isFinal={self._final is not None}"

    def tree_string(self, indent: int = 0) -> str:
        pad = "  " * indent
        inner = self._final if self._final is not None else self.cpu_plan
        return "\n".join([f"{pad}AdaptiveExec [{self.node_desc()}]",
                          inner.tree_string(indent + 1)])

    # -- the loop -------------------------------------------------------------
    def final_plan(self) -> PhysicalPlan:
        with self._final_lock:
            if self._final is None:
                # stage materialization may run python-UDF execs that
                # release/reacquire the semaphore; never block on it while
                # holding this lock (pipeline.exempt_admission invariant)
                from ..parallel.pipeline import exempt_admission
                with exempt_admission():
                    self._final = self._run()
                self.children = (self._final,)
            return self._final

    def _run(self) -> PhysicalPlan:
        """The adaptive loop. Planning between stages is span ``plan.aqe``
        (``stage`` = the stage it plans; the final segment's is the count
        of stages), apart from ``plan``, the static planning before it."""
        hook = getattr(self, "_instrument_hook", None)
        plan = self.cpu_plan
        n = 0
        while True:
            with get_tracer().span("plan.aqe", "plan", stage=n):
                plan = self._demote_joins(plan)
                frontier = _frontier_exchanges(plan)
                if not frontier:
                    return self._final_segment(plan, hook)
                ex = self._pick(frontier, plan)
            stage = materialize_stage(ex, self.conf, self.use_device,
                                      self.events, hook, stage=n)
            plan = _replace_node(plan, ex, stage)
            n += 1

    def _final_segment(self, plan: PhysicalPlan, hook) -> PhysicalPlan:
        """The plan above the last stage, once no exchange is left."""
        plan = self._demote_joins(plan)
        if self.conf.get(AQE_SKEW_ENABLED):
            plan = self._apply_skew(plan)
        if self.conf.get(AQE_COALESCE_ENABLED):
            plan = self._apply_coalescing(plan)
        if self.use_device:
            from .overrides import apply_overrides
            plan = apply_overrides(plan, self.conf)
        if hook is not None:
            hook(plan)  # instrument the final segment
        return plan

    def _pick(self, frontier: List[ShuffleExchangeExec],
              plan: PhysicalPlan) -> ShuffleExchangeExec:
        """Materialize join build sides first so small builds can demote the
        join before the probe-side exchange wastes a materialization."""
        build_sides = set()
        for n in _walk(plan):
            if isinstance(n, CpuShuffledHashJoinExec):
                build_sides.add(id(n.right))
        for ex in frontier:
            if id(ex) in build_sides:
                return ex
        return frontier[0]

    # -- rule: join demotion --------------------------------------------------
    def _demote_joins(self, plan: PhysicalPlan) -> PhysicalPlan:
        threshold = self.conf.get(AQE_BROADCAST_BYTES)
        if threshold < 0:
            return plan

        def rewrite(node: PhysicalPlan) -> PhysicalPlan:
            node = _set_children(node, [rewrite(c) for c in node.children])
            if type(node) is not CpuShuffledHashJoinExec:
                return node
            right_small = isinstance(node.right, ShuffleStageExec) \
                and node.right.stats.total_bytes <= threshold
            left_small = isinstance(node.left, ShuffleStageExec) \
                and node.left.stats.total_bytes <= threshold
            if right_small and node.how in ("inner", "left", "left_semi",
                                            "left_anti", "cross"):
                probe = node.left
                if isinstance(probe, ShuffleExchangeExec):
                    probe = probe.child  # extraneous shuffle removed
                    self.events.append("removed probe-side exchange (left)")
                self.events.append(
                    f"demoted {node.how} join to broadcast (build side "
                    f"{node.right.stats.total_bytes}B <= {threshold}B)")
                if node.how in ("inner", "left_semi") \
                        and self.conf.get(AQE_RUNTIME_FILTER):
                    # dynamic filter (GpuSubqueryBroadcastExec/DPP analogue):
                    # probe rows whose key is absent from the build side can
                    # never join; the reader prunes them by statistics
                    self._push_runtime_filter(probe, node.left_keys,
                                              node.right, node.right_keys)
                return CpuBroadcastHashJoinExec(
                    probe, node.right, node.left_keys, node.right_keys,
                    node.how, node.condition, node.merge_keys)
            if left_small and node.how in ("inner", "right"):
                out_names = list(node.schema.names)
                if len(set(out_names)) != len(out_names):
                    return node  # can't restore order by name post-swap
                probe = node.right
                if isinstance(probe, ShuffleExchangeExec):
                    probe = probe.child
                    self.events.append("removed probe-side exchange (right)")
                how = "left" if node.how == "right" else "inner"
                self.events.append(
                    f"demoted {node.how} join to broadcast via side swap "
                    f"(build side {node.left.stats.total_bytes}B)")
                from ..expr.base import AttributeReference
                from .physical import CpuProjectExec
                swapped = CpuBroadcastHashJoinExec(
                    probe, node.left, node.right_keys, node.left_keys,
                    how, node.condition, node.merge_keys)
                exprs = [AttributeReference(n, swapped.schema.field(n).dtype,
                                            swapped.schema.field(n).nullable)
                         for n in out_names]
                return CpuProjectExec(swapped, exprs, out_names)
            return node

        return rewrite(plan)

    def _push_runtime_filter(self, probe: PhysicalPlan, lkeys, build_stage,
                             rkeys) -> None:
        """Push the build side's distinct keys into probe-side scans as an
        IN filter — only through nodes that provably preserve the key
        column (filters and identity projections)."""
        from ..expr.base import AttributeReference
        from .physical import CpuFilterExec, CpuProjectExec, CpuScanExec
        max_keys = self.conf.get(AQE_RUNTIME_FILTER_MAX_KEYS)

        def scan_for(node, key):
            """The scan below ``node`` if every step preserves ``key``."""
            if isinstance(node, CpuScanExec):
                return node if hasattr(node.source, "push_filter") else None
            if isinstance(node, CpuFilterExec):
                return scan_for(node.child, key)
            if isinstance(node, CpuProjectExec):
                for e, n in zip(node.exprs, node.names):
                    if n == key:
                        inner = e.child if type(e).__name__ == "Alias" else e
                        if isinstance(inner, AttributeReference) \
                                and inner.column_name == key:
                            return scan_for(node.child, key)
                        return None
                return None
            return None

        import numpy as _np
        candidates = [(lk, rk, scan_for(probe, lk))
                      for lk, rk in zip(lkeys, rkeys)]
        candidates = [(lk, rk, s) for lk, rk, s in candidates if s is not None]
        if not candidates:
            return
        # ONE pass over the build stage collects every key column's values
        values = {lk: set() for lk, _, _ in candidates}
        live = {lk for lk, _, _ in candidates}
        for p in range(build_stage.num_partitions):
            if not live:
                break
            for ht in build_stage.execute(p):
                for lk, rk, _ in candidates:
                    if lk not in live:
                        continue
                    col = ht.column(rk)
                    uniq = _np.unique(col.values[col.valid_mask()])
                    values[lk].update(uniq.tolist())
                    if len(values[lk]) > max_keys:
                        live.discard(lk)  # this key only; others continue
        for lk, rk, scan in candidates:
            if lk not in live or not values[lk]:
                continue
            try:
                import copy

                import pyarrow.dataset as pads
                src = copy.copy(scan.source)
                src.push_filter(pads.field(lk).isin(sorted(values[lk])))
                scan.source = src
                self.events.append(
                    f"pushed runtime IN-filter on {lk} "
                    f"({len(values[lk])} keys) into probe scan")
            except Exception:
                continue  # best-effort per key; the join is unaffected

    # -- rule: skew split -----------------------------------------------------
    def _apply_skew(self, plan: PhysicalPlan) -> PhysicalPlan:
        factor = self.conf.get(AQE_SKEW_FACTOR)
        threshold = self.conf.get(AQE_SKEW_THRESHOLD)
        target = max(1, self.conf.get(AQE_ADVISORY_BYTES))

        def rewrite(node: PhysicalPlan) -> PhysicalPlan:
            node = _set_children(node, [rewrite(c) for c in node.children])
            if type(node) is not CpuShuffledHashJoinExec \
                    or node.how not in ("inner", "left", "left_semi",
                                        "left_anti"):
                return node
            lt, rt = node.left, node.right
            if not (isinstance(lt, ShuffleStageExec)
                    and isinstance(rt, ShuffleStageExec)
                    and lt.num_partitions == rt.num_partitions
                    and lt.num_partitions > 1):
                return node
            sizes = lt.stats.nbytes
            med = sorted(sizes)[len(sizes) // 2]
            skewed = {p for p, b in enumerate(sizes)
                      if b > max(factor * med, threshold)}
            if not skewed:
                return node
            entries: List[tuple] = []
            mapping: List[int] = []
            for p, b in enumerate(sizes):
                rows = lt.stats.rows[p]
                if p in skewed and rows > 1:
                    k = min(rows, max(2, -(-b // target)))
                    per = -(-rows // k)
                    for c in range(k):
                        lo = c * per
                        hi = min(rows, (c + 1) * per)
                        if hi > lo:
                            entries.append((p, lo, hi))
                            mapping.append(p)
                    self.events.append(
                        f"skew split partition {p} ({b}B) into {k} chunks")
                else:
                    entries.append((p, 0, -1))
                    mapping.append(p)
            return _set_children(node, [SplitStageReader(lt, entries),
                                        MappedStageReader(rt, mapping)])

        return rewrite(plan)

    # -- rule: partition coalescing ------------------------------------------
    def _apply_coalescing(self, plan: PhysicalPlan) -> PhysicalPlan:
        target = max(1, self.conf.get(AQE_ADVISORY_BYTES))
        min_parts = max(1, self.conf.get(AQE_MIN_PARTITIONS))

        def coalesce_one(stage: ShuffleStageExec,
                         nbytes: Sequence[int]) -> Optional[List[List[int]]]:
            if stage.num_partitions <= max(1, min_parts):
                return None
            if isinstance(stage.partitioning, SinglePartitioning):
                return None
            groups = _merge_groups(nbytes, target, min_parts)
            if len(groups) >= stage.num_partitions:
                return None
            return groups

        def rewrite(node: PhysicalPlan) -> PhysicalPlan:
            # joins need BOTH sides read with identical groups (co-partition)
            if type(node) is CpuShuffledHashJoinExec \
                    and isinstance(node.left, ShuffleStageExec) \
                    and isinstance(node.right, ShuffleStageExec) \
                    and node.left.num_partitions == node.right.num_partitions:
                combined = [a + b for a, b in zip(node.left.stats.nbytes,
                                                  node.right.stats.nbytes)]
                groups = coalesce_one(node.left, combined)
                if groups is not None:
                    self.events.append(
                        f"coalesced join inputs {node.left.num_partitions} "
                        f"-> {len(groups)} partitions")
                    return _set_children(
                        node, [CoalescedStageReader(node.left, groups),
                               CoalescedStageReader(node.right, groups)])
                return node
            new_children = []
            for c in node.children:
                if isinstance(c, ShuffleStageExec):
                    groups = coalesce_one(c, c.stats.nbytes)
                    if groups is not None:
                        self.events.append(
                            f"coalesced stage {c.num_partitions} -> "
                            f"{len(groups)} partitions")
                        c = CoalescedStageReader(c, groups)
                    new_children.append(c)
                else:
                    new_children.append(rewrite(c))
            return _set_children(node, new_children)

        return rewrite(plan)


# ---------------------------------------------------------------------------
# Device-side stage readers: when the materialized stage is device-resident
# (ICI exchange tier), downstream device operators read the shards directly
# instead of bouncing through host (the reader analogue of
# GpuCustomShuffleReaderExec staying columnar).
# ---------------------------------------------------------------------------
def _register_reader_rules():
    from ..columnar.dtypes import TypeEnum, TypeSig
    from ..exec.base import TpuExec
    from .meta import register_exec_rule

    sig = (TypeSig.gpuNumeric
           + TypeSig.of(TypeEnum.BOOLEAN, TypeEnum.DATE, TypeEnum.TIMESTAMP,
                        TypeEnum.NULL, TypeEnum.STRING, TypeEnum.BINARY)
           ).with_decimal128()

    class TpuStageReaderExec(TpuExec):
        """Device-resident stage shard reader."""

        def __init__(self, stage: ShuffleStageExec,
                     groups: Optional[List[List[int]]] = None):
            super().__init__()
            self.stage = stage
            self.children = ()
            self.schema = stage.schema
            self.groups = groups

        @property
        def num_partitions(self) -> int:
            return len(self.groups) if self.groups is not None \
                else self.stage.num_partitions

        def execute_columnar(self, pidx: int):
            parts = self.groups[pidx] if self.groups is not None else [pidx]
            for p in parts:
                for b in self.stage.execute_columnar(p):
                    self.account_batch()
                    yield b

        def node_desc(self) -> str:
            return self.stage.node_desc()

        def tree_string(self, indent: int = 0) -> str:
            # show the materialized stage subtree (explain parity with
            # ShuffleStageExec.tree_string)
            pad = "  " * indent
            return "\n".join([f"{pad}{self.node_name()} "
                              f"[{self.node_desc()}]",
                              self.stage.inner.tree_string(indent + 1)])

    def tag_stage(meta, conf):
        if not meta.plan.device_resident:
            meta.cannot_run("stage materialized on the host tier")

    register_exec_rule(
        ShuffleStageExec, sig,
        lambda p, ch, conf: TpuStageReaderExec(p),
        tag_fn=tag_stage)

    def tag_reader(meta, conf):
        if not meta.plan.stage.device_resident:
            meta.cannot_run("stage materialized on the host tier")

    register_exec_rule(
        CoalescedStageReader, sig,
        lambda p, ch, conf: TpuStageReaderExec(p.stage, p.groups),
        tag_fn=tag_reader)

    return TpuStageReaderExec


TpuStageReaderExec = _register_reader_rules()
