"""Mesh-parallel stage execution: post-exchange operators as ONE shard_map.

The ICI exchange (shuffle/ici.py) re-homes rows with a single
``jax.lax.all_to_all``, but the per-partition consumer contract then
breaks its output into n per-device partitions that downstream operators
drain as n SEQUENTIAL single-device programs: serialized compute, not
shuffle, is then most of a multi-device query's wall. This module closes
that gap, the TPU analogue of the reference's "partitioned operators run
on all executors at once" property (SURVEY §2.7, the point of the UCX
tier): ``TpuMeshStageExec`` takes the exchange's output STILL sharded
(keep-sharded mode, exec/exchange.py) and runs the downstream stage —
the same project/filter/partial-aggregate set the whole-stage pass fuses
— as one ``shard_map`` XLA program over the ``dp`` axis, so all n
partitions compute simultaneously on n devices.

Chain membership goes one step beyond the fusible set: a FINAL-mode hash
aggregate (merge of partial states) is mesh-capable too, because after
the exchange each shard holds its entire hash partition — applying the
merge kernel once per shard IS the complete final aggregate, provided
the exchange streamed exactly ONE chunk. That single-chunk precondition
is the **unshard boundary rule**, and the exchange enforces it at the
source: kept chunks are not spill-registered, so on a SECOND streamed
chunk the exchange reverts to split mode mid-stream (registering the
kept chunk) to preserve its out-of-core contract, and every mesh
consumer sees ``sharded_chunks() == None``. On that, or when the mesh
program terminally fails (classified XLA error — a miscompile, an OOM
past the ladder), the stage falls back to the
existing per-partition path: the exchange late-splits its kept-sharded
chunks (``_ensure_split``) and the ORIGINAL operator topology — child
links intact underneath this node — executes with its own
``with_host_fallback`` boundaries, while the failure feeds the
quarantine store (exec/fallback.py) so the next session plans around it.

Telemetry: the mesh dispatch notes a ``mesh_stage`` phase and the
one-time XLA build a ``compile`` phase on the ici tier, so
shuffle_summary's tier breakdown reconciles post-exchange compute. The
executable is kept by ``compile_cache.aot_program``, beside the
all-to-all's.
"""
from __future__ import annotations

import threading
from typing import Iterator, List, Optional

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from ..columnar.device import DeviceTable, resolve_scalars, shrink_to_fit
from ..conf import register_conf
from ..shuffle import telemetry as shuffle_telemetry
from ..utils import faults
from ..utils import metrics as M
from ..utils.compile_cache import aot_program, named_jit
from ..utils.tracing import get_tracer
from .base import TpuExec
from .exchange import TpuShuffleExchangeExec, _split_sharded
from .wholestage import TpuWholeStageExec, _fusible, _with_children

__all__ = ["TpuMeshStageExec", "plan_mesh_stages", "MESH_STAGE_ENABLED"]

MESH_STAGE_ENABLED = register_conf(
    "spark.rapids.tpu.mesh.stageExecution.enabled",
    "Run post-exchange fusible stages (project/filter/partial-aggregate "
    "chains, plus the final-mode aggregate merge) as ONE shard_map XLA "
    "program over the device mesh, consuming the ICI exchange's output "
    "still sharded — all n partitions compute simultaneously instead of "
    "one sequential dispatch per partition. Only affects plans whose "
    "exchange runs on the ICI tier (session has a mesh); non-mesh plans "
    "and non-fusible consumers keep the per-partition path.", True)

_PROGRAM = "srt_mesh_stage"   # what its dispatch and compile spans carry


def _is_final_agg(node) -> bool:
    from .aggregate import TpuHashAggregateExec
    return isinstance(node, TpuHashAggregateExec) and node.mode == "final"


def _mesh_capable(node) -> bool:
    """Whether ``node`` can join a mesh-stage chain: the whole-stage
    fusible set, an already-fused whole stage, or a final-mode hash
    aggregate without collect ops (collects need a per-batch host-synced
    width pass, exec/aggregate.py)."""
    if not isinstance(node, TpuExec) or len(node.children) != 1:
        return False
    if isinstance(node, TpuWholeStageExec):
        return True
    if _is_final_agg(node):
        return not node._has_collect()
    return _fusible(node)


class TpuMeshStageExec(TpuExec):
    """Runs a chain of post-exchange operators SPMD across the mesh.

    ``chain`` is [bottom, ..., top] exactly as in TpuWholeStageExec; the
    bottom's child is the keep-sharded ICI exchange. The original
    per-partition topology stays linked underneath (chain[0] -> exchange,
    chain[i] -> chain[i-1]) so the fallback path can execute it
    unchanged."""

    EXTRA_METRICS = (M.PIPELINE_WAIT,)

    def __init__(self, exchange: TpuShuffleExchangeExec,
                 chain: List[TpuExec]):
        super().__init__()
        assert chain, "empty mesh-stage chain"
        self.exchange = exchange
        self.chain = list(chain)
        self.child = exchange
        self.children = (exchange,)
        self.schema = self.chain[-1].schema
        self.mesh = exchange.mesh
        self.axis = exchange.axis
        # per-partition output batches once materialized; None after a
        # fallback (the original topology serves execute_columnar then)
        self._results: Optional[List[List[DeviceTable]]] = None
        self._fell_back = False
        self._mat_lock = threading.Lock()
        exchange.request_keep_sharded()

    def absorb(self, node: TpuExec) -> "TpuMeshStageExec":
        """Grow the chain upward during the planner rewrite. The node's
        child link is pointed back at the current chain top (the rewrite
        had re-parented it onto this exec) so the fallback topology stays
        the original per-partition plan."""
        _with_children(node, [self.chain[-1]])
        self.chain.append(node)
        self.schema = node.schema
        return self

    @property
    def num_partitions(self) -> int:
        return self.exchange.num_partitions

    def node_name(self):
        inner = "+".join(type(n).__name__.replace("Tpu", "")
                         .replace("Exec", "") for n in self.chain)
        return f"TpuMeshStage[{inner}]"

    def node_desc(self) -> str:
        return f"mesh n={self.num_partitions} axis={self.axis}"

    def plan_signature(self) -> str:
        return "MESH|" + "||".join(n.plan_signature() for n in self.chain)

    def _has_final_agg(self) -> bool:
        return any(_is_final_agg(n) for n in self.chain)

    # -- execution ------------------------------------------------------------
    def execute_columnar(self, pidx: int) -> Iterator[DeviceTable]:
        self._materialize()
        if self._results is None:
            # unshard boundary / terminal-failure fallback: the original
            # per-partition topology (still linked under this node, with
            # its own retry + host-fallback boundaries) serves the drain
            yield from self.chain[-1].execute_columnar(pidx)
            return
        from ..io.file_block import clear_input_file
        clear_input_file()  # post-shuffle rows have no single source file
        for t in self._results[pidx]:
            yield t

    def _materialize(self) -> None:
        with self._mat_lock:
            if self._results is not None or self._fell_back:
                return
            from ..parallel.pipeline import exempt_admission
            with exempt_admission():
                self._materialize_locked()

    def _materialize_locked(self) -> None:
        from .fallback import classify_failure, quarantine_on_failure
        n = self.num_partitions
        chunks = self.exchange.sharded_chunks()
        if chunks is None:
            # a per-partition consumer split the output first (plan reuse)
            self._fell_back = True
            return
        if not chunks:
            self._results = [[] for _ in range(n)]
            return
        if self._has_final_agg() and len(chunks) > 1:
            # unshard boundary rule: the final-merge-per-shard shortcut is
            # only complete when each shard holds its ENTIRE partition —
            # true iff the exchange streamed exactly one chunk
            self._fell_back = True
            return
        try:
            with quarantine_on_failure(self):
                outs = [self._dispatch_chunk(c) for c, _ in chunks]
        except Exception as e:
            # classified terminal failures (miscompile, OOM past the
            # ladder) degrade to the per-partition path — quarantine was
            # already noted above; anything unclassified is a real bug
            # and propagates
            if classify_failure(e) is None:
                raise
            self._fell_back = True
            return
        per_part: List[List[DeviceTable]] = [[] for _ in range(n)]
        if self._has_final_agg():
            # final-aggregate contract parity (exec/aggregate.py): one
            # compacted batch per partition; counts resolve in ONE funnel
            # transfer, then feed the compaction so it never re-syncs. A
            # shard with NO input and NO output rows yields nothing — the
            # per-partition path's keyed aggregate skips input-less
            # partitions entirely (an ungrouped aggregate still emits its
            # one state row and is kept by the rows check)
            parts = outs[0]
            (_, in_rows) = chunks[0]
            counts = resolve_scalars(*[t.num_rows for t in parts])
            final_agg = next(n for n in self.chain if _is_final_agg(n))
            for i, (t, cnt) in enumerate(zip(parts, counts)):
                rows = int(cnt)
                if rows == 0 and in_rows[i] == 0:
                    continue
                final_agg.book_branch(rows, t.capacity, on=t.row_mask)
                out = shrink_to_fit(t, num_rows=rows)
                per_part[i].append(out)
                self.account_batch(rows)
        else:
            # the split path spill-registers only NON-EMPTY shards
            # (exchange._register_split), so a shard the exchange sent no
            # rows yields no batch downstream — mirror that; a 0-row
            # result a filter produced from a non-empty shard still
            # yields, exactly as per-partition execution would
            for parts, (_, in_rows) in zip(outs, chunks):
                for i, t in enumerate(parts):
                    if in_rows[i] == 0:
                        continue
                    per_part[i].append(t)
                    self.account_batch()
        self._results = per_part

    def _dispatch_chunk(self, chunk: DeviceTable) -> List[DeviceTable]:
        """Run the composed chain over one kept-sharded exchanged chunk as
        a single SPMD program; split the (still sharded) result into
        per-device partition views."""
        n = self.num_partitions
        action = faults.fire("mesh.dispatch")
        if action is not None and action != "delay":
            if action == "oom":
                raise RuntimeError(
                    "RESOURCE_EXHAUSTED: injected device OOM at "
                    "mesh.dispatch (faults action=oom)")
            # the INTERNAL status string a miscompiled mesh program
            # produces, so classify_failure routes it down the same
            # degrade-to-per-partition path a real miscompile would take
            raise RuntimeError(
                "INTERNAL: injected mesh-stage dispatch failure "
                "(mesh.dispatch)")
        prog = self._program(chunk)
        with self.metrics.timed(M.OP_TIME):
            t0 = shuffle_telemetry.clock()
            with get_tracer().span("dispatch", "dispatch",
                                   on=chunk.row_mask, program=_PROGRAM):
                out_cols, out_mask = prog(chunk.columns, chunk.row_mask)
                # the eager sum over the sharded mask is this step's too
                total = jnp.sum(out_mask, dtype=jnp.int32)
            shuffle_telemetry.note_transfer(
                "ici", "mesh_stage", shuffle_id=self.exchange.telemetry_sid,
                t0=t0, queue_depth=n, wire_bytes=lambda: chunk.nbytes())
        out = DeviceTable(tuple(out_cols), out_mask, total,
                          tuple(self.schema.names))
        return _split_sharded(out, n)

    def _program(self, chunk: DeviceTable):
        """AOT-build (or fetch) the shard_map executable for this chain at
        this chunk's shapes; the XLA build is timed as the observatory's
        ``compile`` phase (never as stage wall)."""
        leaves, treedef = jax.tree_util.tree_flatten(chunk.columns)
        key = (self.plan_signature(), self.axis,
               tuple(str(d) for d in self.mesh.devices.flat),
               str(treedef),
               tuple((l.shape, str(l.dtype)) for l in leaves),
               (chunk.row_mask.shape, str(chunk.row_mask.dtype)))
        t0 = shuffle_telemetry.clock()
        prog, compiled = aot_program(
            key, lambda: self._build(chunk),
            (chunk.columns, chunk.row_mask), name="mesh_stage")
        if compiled:
            shuffle_telemetry.note_transfer(
                "ici", "compile", shuffle_id=self.exchange.telemetry_sid,
                t0=t0, queue_depth=self.num_partitions)
        return prog

    def _build(self, chunk: DeviceTable):
        """The chain as one ``shard_map`` function over the mesh, for
        tables shaped like ``chunk``."""
        names = chunk.names
        axis = self.axis
        fns = [(type(node).__name__, node.batch_fn())
               for node in self.chain]

        def local(columns, mask):
            table = DeviceTable(columns, mask,
                                jnp.sum(mask, dtype=jnp.int32), names)
            for scope, f in fns:
                with jax.named_scope(scope):
                    table = f(table)
            return table.columns, table.row_mask

        col_specs = jax.tree_util.tree_map(lambda _: P(axis), chunk.columns)
        # check_vma off: the output specs are data-dependent in ways the
        # static replication checker rejects
        return named_jit(jax.shard_map(local, mesh=self.mesh,
                                       in_specs=(col_specs, P(axis)),
                                       out_specs=(P(axis), P(axis)),
                                       check_vma=False), "mesh_stage")


def plan_mesh_stages(plan, conf=None):
    """Bottom-up pass rewriting ``exchange -> mesh-capable chain`` into
    TpuMeshStageExec. Runs AFTER whole-stage fusion (plan/overrides.py),
    so a fused TpuWholeStageExec sitting directly on an ICI exchange is
    absorbed whole; consecutive mesh-capable unary parents (e.g. a final
    aggregate, then the projection above it) keep extending the chain.
    Non-fusible consumers (sorts, joins, collect aggregates) stop the
    chain — that node consumes per-partition output at the unshard
    boundary exactly as before."""
    from ..plan.physical import PhysicalPlan

    if conf is not None and not conf.get(MESH_STAGE_ENABLED):
        return plan

    def rebuild(node: PhysicalPlan) -> PhysicalPlan:
        node = _with_children(node, [rebuild(c) for c in node.children])
        if _mesh_capable(node):
            ch = node.children[0]
            if isinstance(ch, TpuMeshStageExec):
                # at most one final aggregate per chain (a second one
                # would need a re-exchange between them anyway)
                if not (_is_final_agg(node) and ch._has_final_agg()):
                    return ch.absorb(node)
            elif isinstance(ch, TpuShuffleExchangeExec) \
                    and ch.num_partitions > 1:
                return TpuMeshStageExec(ch, [node])
        return node

    return rebuild(plan)
