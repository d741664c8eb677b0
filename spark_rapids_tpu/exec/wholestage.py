"""Whole-stage fusion: compose adjacent fusible device operators into a single
jitted XLA computation.

The reference executes one cuDF kernel per operator call; on TPU the win is
the opposite — let XLA fuse a project/filter/partial-aggregate chain into one
program so intermediate columns never hit HBM. This is the TPU analogue of
Spark's whole-stage codegen (which the reference replaces with columnar
exec — see GpuExec.scala docs) and is inserted by plan/transitions.py after
lowering.

Input-buffer donation: when the chain CONSUMES its input batch (the batch
is exclusively owned — see exec/transitions.py mark_exclusive: uploads not
retained by the scan cache), the fused program runs with
``donate_argnums=(0,)`` so XLA may reuse the input buffers for the output,
cutting peak HBM per batch roughly in half for projection-shaped chains.
Shared batches (cached uploads, catalog/spill handles, broadcast tables)
never donate. Donated bytes are accounted in the ``donatedBytes`` metric.
"""
from __future__ import annotations

from typing import Iterator, List

import jax

from ..columnar.device import DeviceTable
from ..conf import register_conf
from ..utils import metrics as M
from .base import TpuExec

__all__ = ["TpuWholeStageExec", "fuse_stages", "DONATION_ENABLED",
           "donation_active"]

DONATION_ENABLED = register_conf(
    "spark.rapids.tpu.donation.enabled",
    "Donate exclusively-owned input batches to fused XLA programs "
    "(donate_argnums) so the output can reuse the input's HBM. Only "
    "batches the chain provably consumes are donated (uploads not "
    "retained by the scan device cache); cached/spillable batches are "
    "never donated. No effect on backends without buffer donation "
    "(XLA:CPU).", True)

DONATION_FORCE = register_conf(
    "spark.rapids.tpu.donation.force",
    "Testing only: request donation even on backends that do not "
    "implement it (XLA ignores the request with a warning).", False,
    internal=True)


def donation_active(conf) -> bool:
    """Whether fused stages should compile a donating entry point."""
    if not conf.get(DONATION_ENABLED):
        return False
    if conf.get(DONATION_FORCE):
        return True
    return jax.default_backend() != "cpu"


def _scoped_chain(chain: List[TpuExec]):
    """The composed chain function, each operator's ``batch_fn`` under a
    ``jax.named_scope`` of its node name: the ops of the fused program
    (``jit_srt_stage``) then say in a device profile which operator they
    came from. Free at run time."""
    fns = [(type(n).__name__, n.batch_fn()) for n in chain]

    def run(table: DeviceTable) -> DeviceTable:
        for scope, f in fns:
            with jax.named_scope(scope):
                table = f(table)
        return table
    return run


class TpuWholeStageExec(TpuExec):
    """Wraps a linear chain of fusible TpuExecs [bottom, ..., top]."""

    EXTRA_METRICS = (M.PIPELINE_WAIT, M.DONATED_BYTES)

    def __init__(self, chain: List[TpuExec], donate_inputs: bool = False):
        super().__init__()
        assert chain, "empty fusion chain"
        # flatten nested whole-stages: the bottom-up fuse pass wraps inner
        # chains before outer fusible parents are seen, so a parent's chain
        # may contain an already-fused node
        chain = [m for n in chain
                 for m in (n.chain if isinstance(n, TpuWholeStageExec)
                           else [n])]
        self.chain = chain
        self.donate_inputs = donate_inputs
        bottom = chain[0]
        # the producer feeding the chain (transition or other non-fused exec)
        self.source = bottom.children[0]
        self.children = (self.source,)
        self.schema = chain[-1].schema

    @property
    def num_partitions(self) -> int:
        return self.source.num_partitions

    def node_name(self):
        inner = "+".join(type(n).__name__.replace("Tpu", "").replace("Exec", "")
                         for n in self.chain)
        return f"TpuWholeStage[{inner}]"

    def plan_signature(self) -> str:
        return "WS|" + "||".join(n.plan_signature() for n in self.chain)

    def batch_fn(self):
        """Composed chain function — lets an outer fusible parent absorb
        this whole-stage into its own chain (see __init__ flattening)."""
        return _scoped_chain(self.chain)

    def host_batch_fn(self):
        """Composed host-engine chain, or None when any member lacks a
        host path — the whole stage then quarantines on terminal failure
        but cannot recover the failing batch."""
        fns = [n.host_batch_fn() for n in self.chain]
        if any(f is None for f in fns):
            return None

        def run(table):
            for f in fns:
                table = f(table)
            return table
        return run

    def passthrough_fn(self):
        """The chain with its grouped partial aggregate passing every row
        through as its own state (``TpuHashAggregateExec.passthrough_fn``),
        or None where the aggregate cannot (``can_pass_through``)."""
        from .aggregate import fused_grouped_aggregate
        agg = fused_grouped_aggregate(self)
        if agg is None or not agg.can_pass_through():
            return None
        below, top = _scoped_chain(self.chain[:-1]), agg.passthrough_fn()

        def run(table: DeviceTable) -> DeviceTable:
            table = below(table)
            with jax.named_scope("agg_passthrough"):
                return top(table)
        return run

    def execute_columnar(self, pidx: int) -> Iterator[DeviceTable]:
        from ..columnar.device import resolve_scalars
        from ..memory.retry import split_device_rows, with_retry_split
        from ..parallel.pipeline import maybe_prefetched, stage_name
        from ..utils.compile_cache import cached_jit
        from .aggregate import fused_grouped_aggregate
        from .fallback import with_host_fallback
        from .transitions import take_exclusive
        chain = self.chain
        sig = self.plan_signature()

        def runner(key, build):
            """One program of the stage under the degradation boundary:
            the OOM ladder escalates INSIDE (spill -> retry -> split); when
            it terminates — or the failure is a classified non-retryable
            XLA error — the boundary re-runs the batch through the
            composed host chain instead of failing the query
            (exec/fallback.py). The chain is row-wise, so halves of the
            input concat back into the same output; split halves lose the
            exclusive flag and dispatch through the non-donating entry."""
            plain = cached_jit(key, build, name="stage")
            donating = cached_jit(key + "|donate", build, name="stage",
                                  donate_argnums=(0,)) \
                if self.donate_inputs else None

            def dispatch(b: DeviceTable) -> DeviceTable:
                if donating is not None and take_exclusive(b):
                    # nbytes BEFORE the call: donated buffers may be dead
                    # the moment dispatch returns
                    self.metrics.add(M.DONATED_BYTES, b.nbytes())
                    return donating(b)
                return plain(b)
            return with_host_fallback(
                self,
                lambda b: with_retry_split(dispatch, b,
                                           splitter=split_device_rows,
                                           scope="wholestage",
                                           context=self.node_name()),
                self.host_batch_fn())

        run = runner(sig, lambda: _scoped_chain(chain))
        # a chain that ends in a grouped partial aggregate decides from a
        # partition's first batch whether the partial reduces: where its
        # groups are more than 1/SKIP_SHARE of the batch's live rows, the
        # rest of the partition runs the pass-through program (every row
        # its own state). The counts are read in one sync, once the next
        # batch is in hand (a partition of one batch reads nothing)
        agg = fused_grouped_aggregate(self)
        deciding = agg is not None and agg.can_pass_through()
        passing = runner(sig + "|pass", self.passthrough_fn) \
            if deciding else None
        # stage boundary: the source (typically the upload transition)
        # produces the NEXT batch on a prefetch worker while XLA runs the
        # current one (parallel/pipeline.py)
        source = iter(maybe_prefetched(
            lambda: self.source.execute_columnar(pidx),
            stage=f"source:{stage_name(self.source)}",
            registry=self.metrics))
        batch = next(source, None)
        if deciding and batch is not None:
            # not donated: its row count is read after the program
            take_exclusive(batch)
        while batch is not None:
            with self.metrics.timed(M.OP_TIME):
                out = run(batch)
            if run is passing:
                agg.book_skip(out)
            following = next(source, None)
            if deciding:
                deciding = False
                if following is not None:
                    groups, rows = resolve_scalars(out.num_rows,
                                                   batch.num_rows)
                    if agg.skips(groups, rows):
                        run = passing
            self.metrics.add(M.NUM_OUTPUT_BATCHES, 1)
            yield out
            batch = following


def fuse_stages(plan, conf=None):
    """Bottom-up pass replacing maximal fusible chains with TpuWholeStageExec.

    A node joins a chain when it is a TpuExec with ``batch_fn() is not None``
    and exactly one child. Chains of length 1 are left alone (plain jit in the
    node itself is equivalent). ``conf`` (when given) decides whether fused
    stages compile a donating entry point (see DONATION_ENABLED).
    """
    from ..plan.physical import PhysicalPlan

    donate = donation_active(conf) if conf is not None else False

    def rebuild(node: PhysicalPlan) -> PhysicalPlan:
        new_children = [rebuild(c) for c in node.children]
        node = _with_children(node, new_children)
        if _fusible(node):
            chain = [node]
            cur = node.children[0] if node.children else None
            while cur is not None and _fusible(cur):
                chain.insert(0, cur)
                cur = cur.children[0] if cur.children else None
            if len(chain) > 1:
                return TpuWholeStageExec(chain, donate_inputs=donate)
        return node

    return rebuild(plan)


def _fusible(node) -> bool:
    return isinstance(node, TpuExec) and len(node.children) == 1 \
        and node.fusible


def _with_children(node, children):
    if list(node.children) == list(children):
        return node
    node.children = tuple(children)
    if hasattr(node, "child") and len(children) == 1:
        node.child = children[0]
    return node
