"""jitname checker: every device program is compiled under a fixed name.

XLA names a module after the jitted function, and a profile, an HLO dump
or a per-layer benchmark metric finds a program by that name. The engine
therefore compiles through two funnels that rename the function to
``srt_<name>`` first (utils/compile_cache.py): ``cached_jit(key, builder,
name=...)`` and ``named_jit`` / ``named_program``, with every name listed
in ``PROGRAM_NAMES``.

- ``jitname-missing`` — a ``cached_jit(...)`` call without ``name=``.
- ``jitname-unknown`` — ``name=`` (or the name argument of ``named_jit`` /
  ``named_program``) is a string that ``PROGRAM_NAMES`` does not list, or
  is not a string literal (nor a conditional between literals), so it
  cannot be checked here: suppress with the names it takes,
  ``# srtpu: jitname-ok(<names and where they come from>)``.
- ``jitname-bare-jit`` — ``jax.jit`` used directly (call or decorator)
  outside utils/compile_cache.py: the program would be named after
  whatever inner function it was given (``jit_fn``, ``jit_run``,
  ``jit__lambda``).
"""
from __future__ import annotations

import ast
from typing import List, Optional

from . import Finding, Project, ScopedVisitor

__all__ = ["check"]

#: the funnels themselves
_ALLOWED_BARE_JIT = ("spark_rapids_tpu/utils/compile_cache.py",)
_NAMED_FUNNELS = ("named_jit", "named_program")


def _program_names() -> frozenset:
    from ...utils.compile_cache import PROGRAM_NAMES
    return frozenset(PROGRAM_NAMES)


def _literal_names(node: ast.AST) -> Optional[List[str]]:
    """The names an expression can evaluate to: a string literal, or a
    conditional expression between such; None when it cannot be told."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    if isinstance(node, ast.IfExp):
        a, b = _literal_names(node.body), _literal_names(node.orelse)
        return None if a is None or b is None else a + b
    return None


class _NameVisitor(ScopedVisitor):
    def __init__(self, ctx, names: frozenset):
        super().__init__()
        self.ctx = ctx
        self.names = names
        self.findings: List[Finding] = []

    def _hit(self, node, rule: str, msg: str) -> None:
        self.findings.append(self.ctx.finding(
            "jitname", rule, node, self.symbol, msg))

    def _check_name(self, call: ast.Call, node: Optional[ast.AST],
                    funnel: str) -> None:
        if node is None:
            self._hit(call, "jitname-missing",
                      f"{funnel}(...) without a program name: pass "
                      f"name=<an entry of compile_cache.PROGRAM_NAMES>")
            return
        names = _literal_names(node)
        if names is None:
            self._hit(call, "jitname-unknown",
                      f"{funnel}(...) program name is not a string literal "
                      f"and cannot be checked against PROGRAM_NAMES")
            return
        for n in names:
            if n not in self.names:
                self._hit(call, "jitname-unknown",
                          f"{funnel}(...) program name {n!r} is not in "
                          f"compile_cache.PROGRAM_NAMES")

    def _bare(self, node) -> None:
        if self.ctx.relpath not in _ALLOWED_BARE_JIT:
            self._hit(node, "jitname-bare-jit",
                      "jax.jit used directly: the XLA module is named after "
                      "the inner function; compile through cached_jit / "
                      "named_jit with a name from PROGRAM_NAMES")

    def _decorators(self, node) -> None:
        for dec in node.decorator_list:
            target = dec.func if isinstance(dec, ast.Call) else dec
            if self.ctx.qualify(target) == "jax.jit":
                self._bare(dec)
        self._scoped(node)

    visit_FunctionDef = _decorators
    visit_AsyncFunctionDef = _decorators

    def visit_Call(self, node: ast.Call) -> None:
        q = self.ctx.qualify(node.func)
        bare = q.rsplit(".", 1)[-1]
        if bare == "cached_jit":
            kw = next((k.value for k in node.keywords if k.arg == "name"),
                      None)
            self._check_name(node, kw, "cached_jit")
        elif bare in _NAMED_FUNNELS \
                and self.ctx.relpath not in _ALLOWED_BARE_JIT:
            arg = node.args[1] if len(node.args) > 1 else next(
                (k.value for k in node.keywords if k.arg == "name"), None)
            self._check_name(node, arg, bare)
        elif q == "jax.jit":
            self._bare(node)
        self.generic_visit(node)


def check(project: Project) -> List[Finding]:
    names = _program_names()
    out: List[Finding] = []
    for ctx in project.modules:
        v = _NameVisitor(ctx, names)
        v.visit(ctx.tree)
        out.extend(v.findings)
    return out
