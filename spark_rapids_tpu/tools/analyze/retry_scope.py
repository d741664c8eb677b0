"""retry checker: device work in hot packages outside any OOM retry scope.

PR-14's escalation ladder (memory/retry.py) only protects the call sites
that opt in: ``with_retry``/``with_retry_split`` scopes spill, retry and
split-and-retry a failing dispatch; everything else surfaces a raw
``RESOURCE_EXHAUSTED`` and fails the query. Two rules inventory the
unprotected surface statically:

- ``retry-unguarded-dispatch`` — a call to a name bound from
  ``cached_jit(...)`` whose enclosing scope chain never references the
  retry API. The jit wrapper itself carries the jit-level spill+retry
  (compile_cache routes through ``wrap_jit``), but a persistent OOM then
  raises a structured ``DeviceOomError`` — without an enclosing
  ``with_retry_split`` scope nothing can halve the batch, so the query
  dies where a split would have recovered it.
- ``retry-unguarded-upload`` — ``DeviceTable.from_host(...)`` in a scope
  chain with no retry reference. Uploads have no built-in guard at all:
  an HBM-exhausted H2D copy raises instead of walking the ladder
  (``with_retry_split`` + ``split_host_rows`` splits the host batch).

A scope counts as covered when it, or any enclosing function scope,
references ``with_retry``/``with_retry_split``/``wrap_jit``/
``wrap_jit_donating``: closures dispatched by a sibling
``with_retry_split`` call are defined in the covered enclosing scope, so
the chain test follows the value flow the AST can see. Sites that are
deliberately spill-only (merge kernels whose inputs cannot split,
broadcast builds) or that manage OOM themselves carry
``# srtpu: retry-ok(<reason>)``; pre-existing debt seeds the committed
baseline like every other check.
"""
from __future__ import annotations

import ast
from typing import List, Set, Tuple

from . import Finding, Project, ScopedVisitor

__all__ = ["check"]

#: only the per-batch execution path is reported — cold/warm packages
#: (tools, planning, session setup) run device work rarely enough that
#: a raw OOM failing the call is acceptable, and several do so before a
#: catalog even exists to spill from
REPORTED_SEVERITIES = ("hot",)

#: referencing any of these marks the scope chain as retry-covered
_RETRY_API = ("with_retry", "with_retry_split", "wrap_jit",
              "wrap_jit_donating")


class _RetryVisitor(ScopedVisitor):
    """Collects, per enclosing-scope symbol: retry-API references,
    names bound from ``cached_jit(...)``, and the flaggable sites."""

    def __init__(self, ctx):
        super().__init__()
        self.ctx = ctx
        self.covered: Set[str] = set()
        self.jit_bound: Set[Tuple[str, str]] = set()  # (scope, name)
        self.uploads: List[Tuple[str, ast.Call]] = []
        self.dispatches: List[Tuple[str, str, ast.Call]] = []

    def visit_Name(self, node: ast.Name) -> None:
        q = self.ctx.qualify(node)
        if q.rsplit(".", 1)[-1] in _RETRY_API:
            self.covered.add(self.symbol)
        self.generic_visit(node)

    def visit_Attribute(self, node: ast.Attribute) -> None:
        if node.attr in _RETRY_API:
            self.covered.add(self.symbol)
        self.generic_visit(node)

    def visit_Assign(self, node: ast.Assign) -> None:
        if isinstance(node.value, ast.Call):
            q = self.ctx.qualify(node.value.func)
            if q.rsplit(".", 1)[-1] == "cached_jit":
                for t in node.targets:
                    for n in ast.walk(t):
                        if isinstance(n, ast.Name):
                            self.jit_bound.add((self.symbol, n.id))
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call) -> None:
        attr = node.func.attr if isinstance(node.func, ast.Attribute) \
            else None
        if attr == "from_host" \
                and "DeviceTable" in self.ctx.qualify(node.func):
            self.uploads.append((self.symbol, node))
        elif isinstance(node.func, ast.Name):
            self.dispatches.append((self.symbol, node.func.id, node))
        self.generic_visit(node)


def _chain(symbol: str):
    parts = symbol.split(".")
    return [".".join(parts[:i]) for i in range(1, len(parts) + 1)]


def _scope_covered(symbol: str, covered: Set[str]) -> bool:
    """True when ``symbol`` or any enclosing scope references the retry
    API — closures a covered scope hands to with_retry* count."""
    return any(s in covered for s in _chain(symbol)) \
        or "<module>" in covered and symbol == "<module>"


def _bound_in_chain(symbol: str, name: str,
                    jit_bound: Set[Tuple[str, str]]) -> bool:
    return any((s, name) in jit_bound
               for s in _chain(symbol) + ["<module>"])


def check(project: Project) -> List[Finding]:
    out: List[Finding] = []
    for ctx in project.modules:
        if ctx.severity not in REPORTED_SEVERITIES:
            continue
        v = _RetryVisitor(ctx)
        v.visit(ctx.tree)
        for symbol, node in v.uploads:
            if _scope_covered(symbol, v.covered):
                continue
            out.append(ctx.finding(
                "retry", "retry-unguarded-upload", node, symbol,
                "DeviceTable.from_host outside any OOM retry scope — an "
                "HBM-exhausted upload raises instead of walking the "
                "spill/retry/split ladder (wrap with memory/retry.py "
                "with_retry_split + split_host_rows)"))
        for symbol, name, node in v.dispatches:
            if not _bound_in_chain(symbol, name, v.jit_bound):
                continue
            if _scope_covered(symbol, v.covered):
                continue
            out.append(ctx.finding(
                "retry", "retry-unguarded-dispatch", node, symbol,
                f"cached_jit program '{name}' dispatched with no "
                "enclosing retry scope — a persistent device OOM raises "
                "DeviceOomError with nothing able to split the batch "
                "(wrap the dispatch in memory/retry.py with_retry / "
                "with_retry_split)"))
    return out
