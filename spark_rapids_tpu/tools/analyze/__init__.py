"""srtpu-analyze — AST static-analysis pass suite for the engine.

The reference plugin ships static tooling that reads *plans* (the
qualification tool and AutoTuner, tools/ in spark-rapids); this package
is the same idea pointed at our own *source*: a pluggable set of AST
checkers that inventory the blocking-sync surface (ROADMAP item 1 — sync
wait rivals device compute and we had no map of where the syncs live),
and statically enforce the concurrency conventions the PR-3/PR-4 arc
established only by comment (semaphore-under-materialize-lock, bounded
queues, named daemon threads, jit purity).

Checkers (see the sibling modules):

- ``sync``   — blocking device->host syncs (``.item()``, ``np.asarray``,
               ``jax.device_get``, ``block_until_ready``) in hot-path
               packages, severity-ranked by package.
- ``lock``   — TpuSemaphore acquisition reachable under a materialize
               lock outside ``exempt_admission``; context-manager misuse.
- ``thread`` — unbounded queues, unnamed/non-daemon threads, pools
               without a thread-name prefix, ``time.sleep`` in engine code.
- ``jit``    — side effects inside functions traced by ``cached_jit`` /
               ``jax.jit`` / ``shard_map``; use-after-donation of
               ``donate_argnums`` arguments.
- ``bucket`` — hardcoded shape-bucket floors (``min_bucket`` literals /
               ad-hoc numeric defaults) bypassing the central
               ``shapeBuckets`` policy in columnar/device.py.
- ``trace``  — tracer spans opened without a closing ``with`` scope;
               ProcessCluster task-queue submissions bypassing the
               ``_submit`` trace-context injection chokepoint.
- ``memtrack`` — ``DeviceTable.from_host`` uploads in hot packages whose
               enclosing function never reaches
               ``BufferCatalog.register`` — HBM invisible to spill,
               watermark attribution, and OOM postmortems
               (utils/memprof.py).
- ``net``    — socket deadline discipline: blocking socket calls with
               no timeout (a dead peer hangs them forever, defeating
               the fault-tolerance arc's retry/recompute machinery) and
               except-everything-pass handlers that swallow transport
               faults in hot/warm packages.
- ``retry``  — device compute (``cached_jit`` dispatch) and
               ``DeviceTable.from_host`` uploads in hot packages whose
               scope chain never references the OOM retry API
               (memory/retry.py) — a device OOM there raises instead of
               walking the spill/retry/split ladder.
- ``degrade`` — dispatch sites outside BOTH the retry scope and the
               fallback boundary (exec/fallback.py) — a terminal device
               failure there gets no host re-execution and no
               quarantine note; plus except handlers that swallow the
               ladder's structured errors (``DeviceOomError``,
               ``QueryTimeoutError``) without re-raising or
               classifying, breaking split-and-retry bookkeeping and
               cooperative cancellation.

- ``jitname`` — device programs compiled without a fixed name:
               ``cached_jit`` without ``name=`` (or with a name
               ``compile_cache.PROGRAM_NAMES`` does not list) and bare
               ``jax.jit`` outside the two naming funnels — the XLA
               module name is what profiles and the benchmark's
               per-layer metrics find a program by.

Workflow: findings are compared against a COMMITTED baseline
(``tools/analyze/baseline.json``) so pre-existing debt is inventoried
while any *new* violation fails tier-1 (tests/test_analyze.py). Sites
that are genuinely fine carry an inline suppression::

    np.asarray(mask)  # srtpu: sync-ok(result materialization, cold path)

The suppression syntax is ``# srtpu: <check>-ok(<reason>)``; a non-empty
reason is mandatory (an empty one is itself reported, check ``meta``).
A suppression on its own line applies to the next line of code.

CLI::

    python -m spark_rapids_tpu.tools.analyze spark_rapids_tpu/ [--json]
        [--checks sync,lock] [--baseline PATH | --no-baseline]
        [--write-baseline] [--top N]
"""
from __future__ import annotations

import ast
import dataclasses
import io
import json
import os
import re
import tokenize
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

__all__ = ["Finding", "ModuleContext", "Project", "Report",
           "analyze_paths", "default_baseline_path", "load_baseline",
           "write_baseline", "compare_to_baseline", "baseline_summary",
           "CHECKS", "SEVERITIES"]

#: package -> severity tier. ``hot`` packages sit on the per-batch
#: execution path (a sync there stalls the device pipeline); ``warm``
#: packages run per-partition or per-query; everything else is ``cold``
#: (tools, session setup, doc generators) and the sync checker skips it.
_HOT_PACKAGES = frozenset({"exec", "expr", "columnar", "shuffle", "memory"})
_WARM_PACKAGES = frozenset({"plan", "parallel", "io", "udf", "native"})
SEVERITIES = ("hot", "warm", "cold")

_PKG_NAME = "spark_rapids_tpu"


def canonical_relpath(path: str) -> str:
    """Stable repo-relative posix path: everything from the last
    ``spark_rapids_tpu`` component on; outside the package, the absolute
    posix path (fixture files in tests)."""
    parts = os.path.abspath(path).replace(os.sep, "/").split("/")
    if _PKG_NAME in parts:
        idx = len(parts) - 1 - parts[::-1].index(_PKG_NAME)
        return "/".join(parts[idx:])
    return "/".join(parts)


def severity_for(path: str) -> str:
    """Severity tier of a file, from its package. Files outside the
    package rank ``hot`` — analyzing a loose file should surface
    everything (this is what test fixtures rely on)."""
    rel = canonical_relpath(path)
    parts = rel.split("/")
    if parts[0] != _PKG_NAME:
        return "hot"
    if len(parts) < 3:          # spark_rapids_tpu/session.py etc.
        return "cold"
    pkg = parts[1]
    if pkg in _HOT_PACKAGES:
        return "hot"
    if pkg in _WARM_PACKAGES:
        return "warm"
    return "cold"


@dataclasses.dataclass
class Finding:
    """One checker hit at one source location."""
    check: str      # checker name: sync / lock / thread / jit / meta
    rule: str       # specific rule, e.g. sync-item
    path: str       # canonical relpath (baseline identity component)
    line: int
    col: int
    symbol: str     # enclosing def/class qualname, or "<module>"
    message: str
    severity: str   # hot / warm / cold

    def key(self) -> str:
        """Baseline identity: path + rule + enclosing symbol (NOT the
        line number, so unrelated edits don't churn the baseline)."""
        return f"{self.path}::{self.rule}::{self.symbol}"

    def to_dict(self) -> Dict:
        return dataclasses.asdict(self)

    def render(self) -> str:
        return (f"{self.path}:{self.line}:{self.col}: "
                f"[{self.rule}/{self.severity}] {self.message} "
                f"(in {self.symbol})")


# ---------------------------------------------------------------------------
# suppression comments
# ---------------------------------------------------------------------------
_SUPPRESS_RE = re.compile(r"srtpu:\s*([a-z0-9]+)-ok\(([^()]*)\)")


def scan_suppressions(source: str) -> Tuple[Dict[int, Dict[str, str]],
                                            List[Tuple[int, str]]]:
    """Map line -> {check: reason} plus a list of (line, check) whose
    reason is empty (reported as ``meta`` findings; an unexplained
    suppression is debt pretending to be an audit)."""
    supp: Dict[int, Dict[str, str]] = {}
    empty: List[Tuple[int, str]] = []
    try:
        tokens = tokenize.generate_tokens(io.StringIO(source).readline)
        for tok in tokens:
            if tok.type != tokenize.COMMENT:
                continue
            for m in _SUPPRESS_RE.finditer(tok.string):
                check, reason = m.group(1), m.group(2).strip()
                if not reason:
                    empty.append((tok.start[0], check))
                    continue
                lines = [tok.start[0]]
                if tok.line.strip().startswith("#"):
                    # standalone comment: applies to the next code line
                    lines.append(tok.start[0] + 1)
                for ln in lines:
                    supp.setdefault(ln, {})[check] = reason
    except tokenize.TokenizeError:
        pass
    return supp, empty


# ---------------------------------------------------------------------------
# per-module context
# ---------------------------------------------------------------------------
class ModuleContext:
    """One parsed source file plus the lookup tables checkers share:
    import aliases (so ``np.asarray`` qualifies to ``numpy.asarray``)
    and the suppression map."""

    def __init__(self, path: str, source: str, tree: ast.AST):
        self.path = path
        self.relpath = canonical_relpath(path)
        self.source = source
        self.tree = tree
        self.severity = severity_for(path)
        self.suppressions, self.empty_suppressions = \
            scan_suppressions(source)
        self.imports = self._collect_imports(tree)

    @staticmethod
    def _collect_imports(tree: ast.AST) -> Dict[str, str]:
        table: Dict[str, str] = {}
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    table[a.asname or a.name.split(".")[0]] = \
                        a.name if a.asname else a.name.split(".")[0]
            elif isinstance(node, ast.ImportFrom) and node.names:
                mod = (node.module or "").lstrip(".")
                for a in node.names:
                    if a.name == "*":
                        continue
                    full = f"{mod}.{a.name}" if mod else a.name
                    table[a.asname or a.name] = full
        return table

    def qualify(self, node: Optional[ast.AST]) -> str:
        """Dotted name of an expression with import aliases resolved:
        ``np.asarray`` -> ``numpy.asarray``, a bare ``device_get``
        imported from jax -> ``jax.device_get``. Non-name bases
        (calls, subscripts) qualify through their value so
        ``x.sum().item`` still ends with ``.item``."""
        if isinstance(node, ast.Name):
            return self.imports.get(node.id, node.id)
        if isinstance(node, ast.Attribute):
            base = self.qualify(node.value)
            return f"{base}.{node.attr}" if base else node.attr
        if isinstance(node, ast.Call):
            return self.qualify(node.func) + "()"
        if isinstance(node, ast.Subscript):
            return self.qualify(node.value) + "[]"
        return ""

    def finding(self, check: str, rule: str, node: ast.AST, symbol: str,
                message: str, severity: Optional[str] = None) -> Finding:
        return Finding(check=check, rule=rule, path=self.relpath,
                       line=getattr(node, "lineno", 0),
                       col=getattr(node, "col_offset", 0),
                       symbol=symbol, message=message,
                       severity=severity or self.severity)

    def is_suppressed(self, f: Finding) -> bool:
        entry = self.suppressions.get(f.line)
        return bool(entry) and (f.check in entry or "all" in entry)


class Project:
    """Every module under analysis — checkers get the whole set so
    cross-file passes (the lock call graph, jit builder resolution)
    see the full picture."""

    def __init__(self, modules: List[ModuleContext],
                 parse_failures: List[Finding]):
        self.modules = modules
        self.parse_failures = parse_failures

    def module_for(self, relpath: str) -> Optional[ModuleContext]:
        return next((m for m in self.modules if m.relpath == relpath), None)


class ScopedVisitor(ast.NodeVisitor):
    """NodeVisitor tracking the enclosing class/def qualname — findings
    key on the symbol so line drift never churns the baseline."""

    def __init__(self):
        self._scope: List[str] = []

    @property
    def symbol(self) -> str:
        return ".".join(self._scope) or "<module>"

    def _scoped(self, node):
        self._scope.append(node.name)
        try:
            self.generic_visit(node)
        finally:
            self._scope.pop()

    visit_FunctionDef = _scoped
    visit_AsyncFunctionDef = _scoped
    visit_ClassDef = _scoped


# ---------------------------------------------------------------------------
# project loading / running
# ---------------------------------------------------------------------------
def iter_py_files(paths: Sequence[str]) -> List[str]:
    out: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            for root, dirs, files in os.walk(p):
                dirs[:] = sorted(d for d in dirs
                                 if d not in ("__pycache__", ".git"))
                out.extend(os.path.join(root, f) for f in sorted(files)
                           if f.endswith(".py"))
        elif p.endswith(".py"):
            out.append(p)
    return out


def load_project(paths: Sequence[str]) -> Project:
    modules: List[ModuleContext] = []
    failures: List[Finding] = []
    for path in iter_py_files(paths):
        try:
            with open(path, "r", encoding="utf-8") as f:
                source = f.read()
            tree = ast.parse(source, filename=path)
        except (OSError, SyntaxError, ValueError) as e:
            failures.append(Finding(
                check="meta", rule="meta-parse-error",
                path=canonical_relpath(path),
                line=getattr(e, "lineno", 0) or 0, col=0,
                symbol="<module>", message=f"cannot analyze: {e}",
                severity=severity_for(path)))
            continue
        modules.append(ModuleContext(path, source, tree))
    return Project(modules, failures)


def _checkers() -> Dict[str, object]:
    from . import (buckets, degrade, eventlog_schema, host_sync, jit_purity,
                   locks, memtrack, mesh_loops, net, program_names,
                   retry_scope, shuffle_observed, threads, trace_ctx)
    return {"sync": host_sync, "lock": locks,
            "thread": threads, "jit": jit_purity, "bucket": buckets,
            "trace": trace_ctx, "memtrack": memtrack,
            "eventlog": eventlog_schema, "net": net, "retry": retry_scope,
            "degrade": degrade, "shuffle": shuffle_observed,
            "mesh": mesh_loops, "jitname": program_names}


CHECKS = ("sync", "lock", "thread", "jit", "bucket", "trace", "memtrack",
          "eventlog", "net", "retry", "degrade", "shuffle", "mesh",
          "jitname")


def analyze_paths(paths: Sequence[str],
                  checks: Optional[Sequence[str]] = None) -> "Report":
    """Run the selected checkers (default: all) over ``paths`` and
    return the Report (suppressed findings split out, meta findings for
    parse failures and empty-reason suppressions folded in)."""
    project = load_project(paths)
    registry = _checkers()
    names = list(checks) if checks else list(CHECKS)
    unknown = [n for n in names if n not in registry]
    if unknown:
        raise ValueError(f"unknown checks {unknown}; have {list(registry)}")
    findings: List[Finding] = list(project.parse_failures)
    for name in names:
        findings.extend(registry[name].check(project))
    for ctx in project.modules:
        for line, check in ctx.empty_suppressions:
            findings.append(ctx.finding(
                "meta", "meta-empty-suppression-reason",
                type("L", (), {"lineno": line, "col_offset": 0})(),
                "<module>",
                f"suppression '{check}-ok()' has no reason — every "
                f"suppression must say why the site is fine"))
    by_path = {m.relpath: m for m in project.modules}
    kept, suppressed = [], []
    for f in sorted(findings, key=lambda f: (f.path, f.line, f.rule)):
        ctx = by_path.get(f.path)
        if ctx is not None and f.check != "meta" and ctx.is_suppressed(f):
            suppressed.append(f)
        else:
            kept.append(f)
    return Report(kept, suppressed, files=len(project.modules),
                  checks=names)


# ---------------------------------------------------------------------------
# report
# ---------------------------------------------------------------------------
_SEV_ORDER = {"hot": 0, "warm": 1, "cold": 2}


class Report:
    def __init__(self, findings: List[Finding], suppressed: List[Finding],
                 files: int, checks: Sequence[str]):
        self.findings = findings
        self.suppressed = suppressed
        self.files = files
        self.checks = list(checks)

    def count(self, check: Optional[str] = None,
              severity: Optional[str] = None) -> int:
        return sum(1 for f in self.findings
                   if (check is None or f.check == check)
                   and (severity is None or f.severity == severity))

    def key_counts(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for f in self.findings:
            out[f.key()] = out.get(f.key(), 0) + 1
        return out

    def summary(self) -> Dict:
        """Per-check, per-severity counts + the top files by hot sync
        debt — the shape tools/diagnose.py cross-references against
        trace spans."""
        checks: Dict[str, Dict[str, int]] = {}
        for f in self.findings:
            c = checks.setdefault(f.check,
                                  {"hot": 0, "warm": 0, "cold": 0,
                                   "total": 0})
            c[f.severity] += 1
            c["total"] += 1
        per_file: Dict[str, int] = {}
        for f in self.findings:
            if f.check == "sync" and f.severity == "hot":
                per_file[f.path] = per_file.get(f.path, 0) + 1
        top = sorted(per_file.items(), key=lambda kv: (-kv[1], kv[0]))
        return {"files": self.files, "checks": checks,
                "suppressed": len(self.suppressed),
                "top_sync_files": [{"path": p, "hot_syncs": n}
                                   for p, n in top[:10]]}

    def render(self, top: int = 0) -> str:
        lines = [f"== srtpu-analyze: {self.files} files, "
                 f"checks={','.join(self.checks)} =="]
        shown = sorted(self.findings,
                       key=lambda f: (_SEV_ORDER[f.severity], f.path,
                                      f.line))
        cut = shown[:top] if top else shown
        lines.extend(f.render() for f in cut)
        if top and len(shown) > top:
            lines.append(f"... and {len(shown) - top} more")
        s = self.summary()
        for check, c in sorted(s["checks"].items()):
            lines.append(f"{check}: {c['total']} finding(s) "
                         f"(hot={c['hot']} warm={c['warm']} "
                         f"cold={c['cold']})")
        lines.append(f"suppressed: {len(self.suppressed)}")
        return "\n".join(lines)

    def to_json(self) -> str:
        return json.dumps({
            "summary": self.summary(),
            "findings": [f.to_dict() for f in self.findings],
            "suppressed": [f.to_dict() for f in self.suppressed],
        }, indent=1)


# ---------------------------------------------------------------------------
# baseline
# ---------------------------------------------------------------------------
def default_baseline_path() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "baseline.json")


def load_baseline(path: Optional[str] = None) -> Dict:
    with open(path or default_baseline_path(), encoding="utf-8") as f:
        return json.load(f)


def write_baseline(report: Report, path: Optional[str] = None) -> Dict:
    """Persist the report as the new baseline. ``initial_inventory`` is
    sticky: recorded the FIRST time a baseline is written and carried
    forward on every regeneration, so the sync-debt trajectory (current
    vs initial) survives baseline refreshes — the tier-1 test pins
    current < initial (real fixes landed, not just churn)."""
    path = path or default_baseline_path()
    initial = None
    if os.path.exists(path):
        try:
            initial = load_baseline(path).get("initial_inventory")
        except (OSError, ValueError):
            initial = None
    if not initial:
        initial = {c: report.count(c) for c in report.checks}
    else:
        # a checker added after the first baseline write records ITS
        # initial inventory the first time it appears; existing entries
        # stay sticky
        for c in report.checks:
            initial.setdefault(c, report.count(c))
    lines: Dict[str, List[int]] = {}
    for f in report.findings:
        lines.setdefault(f.key(), []).append(f.line)
    data = {
        "version": 1,
        "tool": "srtpu-analyze",
        "initial_inventory": initial,
        "summary": report.summary(),
        "counts": {k: {"count": len(v), "lines": sorted(v)}
                   for k, v in sorted(lines.items())},
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(data, f, indent=1, sort_keys=True)
        f.write("\n")
    return data


def compare_to_baseline(report: Report,
                        baseline: Dict) -> List[Finding]:
    """New violations: findings whose baseline key occurs MORE often than
    the baseline recorded (entirely new keys count from zero). For a
    grown key the latest occurrences (by line) are reported."""
    base_counts = {k: v.get("count", 0)
                   for k, v in (baseline.get("counts") or {}).items()}
    grouped: Dict[str, List[Finding]] = {}
    for f in report.findings:
        grouped.setdefault(f.key(), []).append(f)
    regressions: List[Finding] = []
    for key, fs in grouped.items():
        allowed = base_counts.get(key, 0)
        if len(fs) > allowed:
            fs = sorted(fs, key=lambda f: f.line)
            regressions.extend(fs[allowed:])
    return sorted(regressions, key=lambda f: (f.path, f.line))


def baseline_summary(path: Optional[str] = None) -> Dict:
    """The committed baseline's summary block (plus initial inventory) —
    so sync-site count can be tracked as a trajectory metric. Never
    raises: {} when absent/corrupt."""
    try:
        data = load_baseline(path)
    except (OSError, ValueError):
        return {}
    return {"initial_inventory": data.get("initial_inventory", {}),
            "summary": data.get("summary", {})}
