"""degrade checker: device failures that can escape the degradation ladder.

PR-15's graceful-degradation arc (exec/fallback.py, utils/deadline.py)
only helps at the call sites that participate: ``with_host_fallback``
re-executes a terminally-failing batch on the host engine,
``quarantine_on_failure`` at least notes the failure for plan-time
quarantine, and the ladder's structured errors (``DeviceOomError``,
``QueryTimeoutError``) must PROPAGATE to reach the boundary that knows
what to do with them. Two rules inventory the escape hatches statically:

- ``degrade-unguarded-dispatch`` — a call to a name bound from
  ``cached_jit(...)`` whose enclosing scope chain references neither the
  OOM retry API nor the degradation API. Such a site is outside BOTH
  the retry scope and the fallback boundary: a terminal device failure
  there kills the query with no retry, no host re-execution and no
  quarantine note — the planner will happily schedule the same doomed
  operator again next run.
- ``degrade-swallowed-failure`` — an ``except`` handler in engine
  packages that catches ``Exception``/``BaseException``/bare (or the
  ladder's own ``DeviceOomError``/``QueryTimeoutError``) and neither
  re-raises nor classifies the failure. A swallowed ``DeviceOomError``
  voids split-and-retry bookkeeping; a swallowed ``QueryTimeoutError``
  un-cancels a query the deadline already killed, leaking the very
  permits/threads the cooperative-cancellation design exists to free.

A scope chain counts as fallback-covered when it references
``with_host_fallback``/``quarantine_on_failure``/``classify_failure``
(or ``plan_quarantine_pass`` — planner-side routing); retry coverage
uses the same API set as the ``retry`` checker. Handlers that re-raise
(any ``raise``), call ``classify_failure``, or deliberately terminate a
worker loop carry ``# srtpu: degrade-ok(<reason>)``; pre-existing debt
seeds the committed baseline like every other check.
"""
from __future__ import annotations

import ast
from typing import List, Set, Tuple

from . import Finding, Project, ScopedVisitor

__all__ = ["check"]

#: dispatch rule: per-batch execution path only (matches the retry
#: checker); the swallow rule also covers warm packages — a swallowed
#: QueryTimeoutError in plan/parallel/io un-cancels the query just the
#: same
DISPATCH_SEVERITIES = ("hot",)
SWALLOW_SEVERITIES = ("hot", "warm")

#: referencing any of these marks the scope chain as retry-covered
#: (mirrors retry_scope._RETRY_API — the two checkers must agree on
#: what "inside the ladder" means)
_RETRY_API = ("with_retry", "with_retry_split", "wrap_jit",
              "wrap_jit_donating")

#: referencing any of these marks the scope chain as fallback-covered
_DEGRADE_API = ("with_host_fallback", "quarantine_on_failure",
                "classify_failure", "plan_quarantine_pass")

#: catching one of these (or a catch-all) without re-raising swallows a
#: structured degradation signal
_STRUCTURED = ("DeviceOomError", "QueryTimeoutError")
_CATCH_ALL = ("Exception", "BaseException")


class _DegradeVisitor(ScopedVisitor):
    """Collects, per enclosing-scope symbol: retry/fallback API
    references, names bound from ``cached_jit(...)``, dispatch sites
    and except handlers."""

    def __init__(self, ctx):
        super().__init__()
        self.ctx = ctx
        self.retry_covered: Set[str] = set()
        self.degrade_covered: Set[str] = set()
        self.jit_bound: Set[Tuple[str, str]] = set()  # (scope, name)
        self.dispatches: List[Tuple[str, str, ast.Call]] = []
        self.handlers: List[Tuple[str, ast.ExceptHandler]] = []

    def _note_ref(self, name: str) -> None:
        if name in _RETRY_API:
            self.retry_covered.add(self.symbol)
        if name in _DEGRADE_API:
            self.degrade_covered.add(self.symbol)

    def visit_Name(self, node: ast.Name) -> None:
        self._note_ref(self.ctx.qualify(node).rsplit(".", 1)[-1])
        self.generic_visit(node)

    def visit_Attribute(self, node: ast.Attribute) -> None:
        self._note_ref(node.attr)
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
        if isinstance(node.func, ast.Name):
            self.dispatches.append((self.symbol, node.func.id, node))
        self.generic_visit(node)

    def visit_ExceptHandler(self, node: ast.ExceptHandler) -> None:
        self.handlers.append((self.symbol, node))
        self.generic_visit(node)


def _chain(symbol: str):
    parts = symbol.split(".")
    return [".".join(parts[:i]) for i in range(1, len(parts) + 1)]


def _covered(symbol: str, covered: Set[str]) -> bool:
    return any(s in covered for s in _chain(symbol) + ["<module>"])


def _bound_in_chain(symbol: str, name: str,
                    jit_bound: Set[Tuple[str, str]]) -> bool:
    return any((s, name) in jit_bound
               for s in _chain(symbol) + ["<module>"])


def _caught_names(handler: ast.ExceptHandler) -> List[str]:
    """Leaf names of the caught type expression ('' for a bare except)."""
    if handler.type is None:
        return [""]
    nodes = handler.type.elts if isinstance(handler.type, ast.Tuple) \
        else [handler.type]
    out = []
    for n in nodes:
        if isinstance(n, ast.Attribute):
            out.append(n.attr)
        elif isinstance(n, ast.Name):
            out.append(n.id)
    return out


def _swallows(handler: ast.ExceptHandler) -> bool:
    """True when no path through the handler body re-raises or
    classifies the failure — the conservative static read is that the
    exception dies here."""
    for n in ast.walk(handler):
        if isinstance(n, ast.Raise):
            return False
        if isinstance(n, ast.Call):
            f = n.func
            leaf = f.attr if isinstance(f, ast.Attribute) else \
                f.id if isinstance(f, ast.Name) else ""
            if leaf == "classify_failure":
                return False
    return True


def check(project: Project) -> List[Finding]:
    out: List[Finding] = []
    for ctx in project.modules:
        if ctx.severity not in SWALLOW_SEVERITIES:
            continue
        v = _DegradeVisitor(ctx)
        v.visit(ctx.tree)
        if ctx.severity in DISPATCH_SEVERITIES:
            for symbol, name, node in v.dispatches:
                if not _bound_in_chain(symbol, name, v.jit_bound):
                    continue
                if _covered(symbol, v.retry_covered) \
                        or _covered(symbol, v.degrade_covered):
                    continue
                out.append(ctx.finding(
                    "degrade", "degrade-unguarded-dispatch", node, symbol,
                    f"cached_jit program '{name}' dispatched outside both "
                    "the OOM retry scope and the fallback boundary — a "
                    "terminal device failure here kills the query with no "
                    "retry, no host re-execution and no quarantine note "
                    "(wrap with exec/fallback.py with_host_fallback, or at "
                    "least quarantine_on_failure)"))
        for symbol, handler in v.handlers:
            names = _caught_names(handler)
            catches_all = any(n in _CATCH_ALL or n == "" for n in names)
            catches_structured = any(n in _STRUCTURED for n in names)
            if not (catches_all or catches_structured):
                continue
            if not _swallows(handler):
                continue
            what = "/".join(n for n in names if n in _STRUCTURED) \
                if catches_structured else "a catch-all"
            out.append(ctx.finding(
                "degrade", "degrade-swallowed-failure", handler, symbol,
                f"except handler ({what}) neither re-raises nor classifies "
                "— a swallowed DeviceOomError voids the split ladder and a "
                "swallowed QueryTimeoutError un-cancels a query the "
                "deadline already killed (re-raise, or route through "
                "exec/fallback.py classify_failure)"))
    return out
