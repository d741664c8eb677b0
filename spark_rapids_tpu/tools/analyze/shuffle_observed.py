"""shuffle checker: every shuffle-tier transfer must be observed.

The shuffle observatory (shuffle/telemetry.py) exists so per-tier
transfer cost, retries and stragglers are attributable from one place —
but only for transfers that actually note it. A new chokepoint added to
the shuffle package without a ``telemetry.note_transfer`` nearby is a
blind spot: its bytes vanish from the event log's ``shuffle_summary``,
the sentinel's shuffle-wall gate, and the per-tier breakdown,
and the first anyone learns of it is a straggler nobody can attribute.

Rule:

- ``shuffle-unobserved`` — a transfer-shaped call (``.sendall(``,
  ``.publish(``, ``.publish_table(``, ``.put_lazy(``, ``.fetch(``,
  ``.fetch_tables(``, ``.transfer(``) inside ``spark_rapids_tpu/
  shuffle/`` whose enclosing function never references the telemetry
  module: the transfer has no local evidence of observation. Where the
  observatory is fed by the caller for every path (an in-process mock,
  a helper whose callers all note), suppress inline with
  ``# srtpu: shuffle-ok(<reason>)``.

Scoped to the shuffle package only — transfer verbs like ``fetch`` are
too generic to match engine-wide, and the observatory's contract is
precisely that the shuffle tiers are where wire cost concentrates.
telemetry.py itself is exempt (the observatory does not observe
itself).
"""
from __future__ import annotations

import ast
from typing import List

from . import Finding, Project, ScopedVisitor

__all__ = ["check"]

#: attribute-call names that move shuffle payloads between processes,
#: tiers or peers — the transfer chokepoints the observatory instruments
_TRANSFER_ATTRS = frozenset({
    "sendall", "publish", "publish_table", "put_lazy",
    "fetch", "fetch_tables", "transfer",
})

_SCOPE_PREFIX = "spark_rapids_tpu/shuffle/"
_EXEMPT = (_SCOPE_PREFIX + "telemetry.py",)


def _telemetry_names(ctx) -> frozenset:
    """Local names that resolve to the telemetry module or a member of
    it (``from . import telemetry``, ``from .telemetry import
    note_transfer``, aliases included)."""
    names = {"telemetry"}
    for alias, full in ctx.imports.items():
        parts = full.split(".")
        if "telemetry" in parts:
            names.add(alias)
    return frozenset(names)


class _ShuffleVisitor(ScopedVisitor):
    def __init__(self, ctx):
        super().__init__()
        self.ctx = ctx
        self.findings: List[Finding] = []
        self._tele_names = _telemetry_names(ctx)
        #: per-function stack: does this function reference telemetry?
        self._observed_stack: List[bool] = []

    def _fn_references_telemetry(self, fn: ast.AST) -> bool:
        for node in ast.walk(fn):
            if isinstance(node, ast.Name) and node.id in self._tele_names:
                return True
        return False

    def _scoped_fn(self, node):
        self._observed_stack.append(self._fn_references_telemetry(node))
        try:
            ScopedVisitor._scoped(self, node)
        finally:
            self._observed_stack.pop()

    visit_FunctionDef = _scoped_fn
    visit_AsyncFunctionDef = _scoped_fn

    def visit_Call(self, node: ast.Call) -> None:
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr in _TRANSFER_ATTRS \
                and not any(self._observed_stack):
            self.findings.append(self.ctx.finding(
                "shuffle", "shuffle-unobserved", node, self.symbol,
                f".{f.attr}() moves shuffle payload but no enclosing "
                f"function references shuffle/telemetry.py — the "
                f"transfer is invisible to the observatory (per-tier "
                f"bytes, walls, stragglers); note_transfer() around it, "
                f"or suppress with where the observation happens"))
        self.generic_visit(node)


def check(project: Project) -> List[Finding]:
    out: List[Finding] = []
    for ctx in project.modules:
        if not ctx.relpath.startswith(_SCOPE_PREFIX) \
                or ctx.relpath in _EXEMPT:
            continue
        v = _ShuffleVisitor(ctx)
        v.visit(ctx.tree)
        out.extend(v.findings)
    return out
