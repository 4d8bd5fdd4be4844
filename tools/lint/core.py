"""snaplint core: the pass driver, findings, allowlist and baseline.

Design (see docs/static_analysis.md):

- Every scanned file is parsed ONCE into a ``FileUnit`` (AST + a
  child→parent map + source lines); each registered pass walks that
  shared tree and yields structured ``Finding`` records.
- A finding is suppressed only by an ``Allow`` entry carrying a written
  justification (allowlists.py — validated, an empty justification is a
  configuration error), or by the ``baseline.json`` ratchet: legacy
  findings recorded there stay tolerated, but their count may only go
  DOWN, and any finding not in the baseline fails the run.
- Findings render as ``file:line: pass-id message`` and fingerprint as
  ``pass-id:file:context`` (context = enclosing def/class qualname) so
  unrelated edits that shift line numbers don't churn the baseline.

The driver is import-light on purpose: stdlib only, no imports of the
checked modules, so it runs in any environment — including ones where
jax or the package's optional deps are absent.
"""

from __future__ import annotations

import ast
import dataclasses
import json
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

# Directories/files scanned by a repo-wide run.  tests/ is deliberately
# excluded: tests exercise rank-conditional and swallow-everything
# shapes on purpose (and fixture snippets for THESE passes live there).
SCAN_DIRS: Tuple[str, ...] = (
    "torchsnapshot_tpu", "tools", "benchmarks", "examples",
)
SCAN_FILES: Tuple[str, ...] = ("chip_smoke.py",)
_EXCLUDE_PARTS = {"__pycache__"}


@dataclasses.dataclass(frozen=True)
class Finding:
    """One diagnostic: ``file:line: pass-id message``."""

    pass_id: str
    file: str  # repo-relative, '/'-separated
    line: int
    message: str
    context: str  # enclosing def/class qualname, or "<module>"

    def render(self) -> str:
        return f"{self.file}:{self.line}: {self.pass_id} {self.message}"

    @property
    def fingerprint(self) -> str:
        # context-based (not line-based): edits elsewhere in a file must
        # not invalidate the baseline/allowlist match
        return f"{self.pass_id}:{self.file}:{self.context}"

    def to_dict(self) -> Dict[str, object]:
        return dataclasses.asdict(self)


class LintConfigError(RuntimeError):
    """Invalid lint configuration (e.g. an allowlist entry without a
    written justification).  Distinct from findings: exit code 2."""


class FileUnit:
    """One parsed file shared by every pass: AST, parent links, source —
    plus, built lazily, the flow-sensitive substrate (per-function CFGs
    and the intra-module call graph, tools/lint/cfg.py)."""

    def __init__(
        self, relpath: str, source: str, root: Optional[str] = None
    ) -> None:
        self.relpath = relpath.replace(os.sep, "/")
        self.source = source
        # repo root for passes that need to consult sibling files (doc
        # cross-checks); None for in-memory fixture units, so fixtures
        # stay hermetic
        self.root = root
        # the interprocedural Project this unit belongs to (set by
        # Project.__init__); None for standalone fixture units, which
        # is how passes with summary hooks tell "whole-package run"
        # (hook active) from "single-file fixture" (hook inert)
        self.project = None
        self.tree = ast.parse(source, self.relpath)
        self._parents: Optional[Dict[ast.AST, ast.AST]] = None
        self._cfgs: Dict[ast.AST, "object"] = {}
        self._functions: Optional[List[Tuple[str, ast.AST]]] = None
        self._callers: Optional[Dict[str, List[Tuple[ast.AST, ast.Call]]]] = (
            None
        )

    @property
    def parents(self) -> Dict[ast.AST, ast.AST]:
        if self._parents is None:
            p: Dict[ast.AST, ast.AST] = {}
            for node in ast.walk(self.tree):
                for child in ast.iter_child_nodes(node):
                    p[child] = node
            self._parents = p
        return self._parents

    def ancestors(self, node: ast.AST) -> Iterable[ast.AST]:
        cur = node
        while cur in self.parents:
            cur = self.parents[cur]
            yield cur

    def context_of(self, node: ast.AST) -> str:
        """Qualname of the def/class chain at ``node`` ("<module>" at
        top level) — the stable half of a finding's fingerprint.  A
        node that IS a def/class contributes its own name: findings
        anchored on two sibling methods (e.g. instrumentation) must not
        share one fingerprint, or the baseline ratchet couldn't tell
        "fixed A" from "fixed A, regressed B"."""
        names: List[str] = []
        if isinstance(
            node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)
        ):
            names.append(node.name)
        for anc in self.ancestors(node):
            if isinstance(
                anc, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)
            ):
                names.append(anc.name)
        return ".".join(reversed(names)) or "<module>"

    # ------------------------------------------- flow-sensitive substrate

    def cfg(self, func: ast.AST):
        """The control-flow graph of one def (memoized per unit) —
        see tools/lint/cfg.py for the node/edge model."""
        g = self._cfgs.get(func)
        if g is None:
            from . import cfg as _cfg

            g = self._cfgs[func] = _cfg.build_cfg(func)
        return g

    def functions(self) -> List[Tuple[str, ast.AST]]:
        """Every def in the file as (qualname, node), methods included."""
        if self._functions is None:
            from . import cfg as _cfg

            self._functions = _cfg.function_defs(self.tree)
        return self._functions

    def local_defs(self, name: str) -> List[ast.AST]:
        """Defs in this module whose bare name is ``name`` — the
        resolution the intra-module call graph uses (``self.f()`` and
        ``f()`` both resolve by trailing name; cross-module calls
        resolve to nothing and are out of scope by design)."""
        return [n for qn, n in self.functions() if n.name == name]

    def callers(self, name: str) -> List[Tuple[ast.AST, ast.Call]]:
        """Call sites of trailing name ``name`` across the module:
        (enclosing def — or the module node for top-level code, call
        node) pairs.  Built once per unit."""
        if self._callers is None:
            idx: Dict[str, List[Tuple[ast.AST, ast.Call]]] = {}
            scopes: List[ast.AST] = [self.tree] + [
                n for _qn, n in self.functions()
            ]
            for scope in scopes:
                for call in calls_in_body(scope):
                    nm = call_name(call)
                    if nm:
                        idx.setdefault(nm, []).append((scope, call))
            self._callers = idx
        return self._callers.get(name, [])


class LintPass:
    """Base class: subclasses set ``pass_id``/``description`` and
    implement ``run`` yielding findings for one file."""

    pass_id: str = ""
    description: str = ""

    def run(self, unit: FileUnit) -> Iterable[Finding]:  # pragma: no cover
        raise NotImplementedError

    def finding(
        self, unit: FileUnit, node: ast.AST, message: str
    ) -> Finding:
        return Finding(
            pass_id=self.pass_id,
            file=unit.relpath,
            line=getattr(node, "lineno", 0),
            message=message,
            context=unit.context_of(node),
        )


class ProjectPass(LintPass):
    """An interprocedural pass: runs ONCE per project (all units, the
    call graph and the summary table in scope) instead of once per
    file.  ``run`` is inert — per-unit iteration would multiply the
    package-wide findings by the file count."""

    def run(self, unit: FileUnit) -> Iterable[Finding]:
        return []

    def run_project(self, project) -> Iterable[Finding]:  # pragma: no cover
        raise NotImplementedError

    def finding_at(
        self, relpath: str, lineno: int, context: str, message: str
    ) -> Finding:
        """Findings from summary data carry their location explicitly
        (the summary may have come from the cache, so there is no AST
        node in hand); ``context`` is the enclosing def qualname —
        exactly what ``FileUnit.context_of`` would have produced, so
        allowlist/baseline fingerprints stay stable either way."""
        return Finding(
            pass_id=self.pass_id,
            file=relpath,
            line=lineno,
            message=message,
            context=context,
        )


# --------------------------------------------------------- AST helpers


def call_name(node: ast.Call) -> str:
    """Trailing name of a call: ``f()`` → "f", ``a.b.c()`` → "c"."""
    func = node.func
    if isinstance(func, ast.Name):
        return func.id
    if isinstance(func, ast.Attribute):
        return func.attr
    return ""


def receiver_name(func: ast.Attribute) -> str:
    """Trailing name of a method call's receiver:
    ``self._fast_breaker.allow`` → "_fast_breaker", ``gate.release`` →
    "gate".  The shared receiver-identity notion for the flow-sensitive
    passes — one definition, so what two passes consider "the same
    receiver" cannot skew."""
    v = func.value
    if isinstance(v, ast.Name):
        return v.id
    if isinstance(v, ast.Attribute):
        return v.attr
    return ""


# Nodes that open a new execution scope: their bodies run when CALLED,
# possibly from a different rank/thread/lock context, so body-local
# rules must not descend into them.
SCOPE_NODES = (
    ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef, ast.Lambda,
)


def walk_skipping_nested_defs(node: ast.AST) -> Iterable[ast.AST]:
    """The nodes that execute as part of THIS body: descends the tree
    but neither yields nor enters nested def/class/lambda scopes.  The
    one shared walker for body-local pass rules."""
    stack = list(ast.iter_child_nodes(node))
    while stack:
        cur = stack.pop()
        if isinstance(cur, SCOPE_NODES):
            continue
        yield cur
        stack.extend(ast.iter_child_nodes(cur))


def calls_in_body(node: ast.AST) -> Iterable[ast.Call]:
    """Call nodes executing as part of ``node``'s own body (nested
    scopes excluded); includes ``node`` itself when it is a call."""
    if isinstance(node, ast.Call):
        yield node
    for inner in walk_skipping_nested_defs(node):
        if isinstance(inner, ast.Call):
            yield inner


# ------------------------------------------------------------ allowlist


@dataclasses.dataclass(frozen=True)
class Allow:
    """One reviewed suppression.  ``justification`` is mandatory prose —
    the driver rejects blank or token-length entries (LintConfigError)."""

    pass_id: str
    file: str  # repo-relative, '/'-separated
    context: str  # enclosing def/class qualname ("<module>" for top level)
    justification: str

    def matches(self, f: Finding) -> bool:
        return (
            f.pass_id == self.pass_id
            and f.file == self.file
            and f.context == self.context
        )


_MIN_JUSTIFICATION_CHARS = 20


def validate_allowlist(entries: Sequence[Allow]) -> None:
    bad = [
        e for e in entries
        if len(e.justification.strip()) < _MIN_JUSTIFICATION_CHARS
    ]
    if bad:
        lines = "\n".join(
            f"  {e.pass_id}:{e.file}:{e.context}" for e in bad
        )
        raise LintConfigError(
            f"{len(bad)} allowlist entr{'y' if len(bad) == 1 else 'ies'} "
            f"without a written justification (≥"
            f"{_MIN_JUSTIFICATION_CHARS} chars of prose explaining why "
            f"the finding is acceptable):\n{lines}"
        )


# ------------------------------------------------------------- baseline


def load_baseline(path: str) -> Dict[str, int]:
    """fingerprint → tolerated count.  Missing file == empty baseline."""
    try:
        with open(path) as f:
            data = json.load(f)
    except FileNotFoundError:
        return {}
    if not isinstance(data, dict):
        raise LintConfigError(f"baseline {path!r} is not a JSON object")
    counts = data.get("findings", data)
    try:
        return {str(k): int(v) for k, v in counts.items()}
    except (TypeError, ValueError, AttributeError) as e:
        raise LintConfigError(
            f"baseline {path!r} has a non-integer finding count: {e}"
        ) from e


def save_baseline(path: str, findings: Sequence[Finding]) -> Dict[str, int]:
    counts: Dict[str, int] = {}
    for f in findings:
        counts[f.fingerprint] = counts.get(f.fingerprint, 0) + 1
    with open(path, "w") as f:
        json.dump({"findings": dict(sorted(counts.items()))}, f, indent=2)
        f.write("\n")
    return counts


def check_ratchet(
    old: Dict[str, int], new_findings: Sequence[Finding]
) -> List[str]:
    """Violations a baseline update would introduce: any fingerprint
    whose count would GROW, or appear fresh.  Empty list == a pure
    ratchet-down (allowed)."""
    counts: Dict[str, int] = {}
    for f in new_findings:
        counts[f.fingerprint] = counts.get(f.fingerprint, 0) + 1
    out = []
    for fp, n in sorted(counts.items()):
        if n > old.get(fp, 0):
            out.append(
                f"{fp}: {old.get(fp, 0)} -> {n} (findings may only "
                f"decrease; fix it or allowlist with justification)"
            )
    return out


# --------------------------------------------------------------- driver


@dataclasses.dataclass
class LintResult:
    findings: List[Finding]          # everything the passes reported
    allowlisted: List[Finding]       # suppressed by an Allow entry
    baselined: List[Finding]         # tolerated by the baseline ratchet
    unbaselined: List[Finding]       # actionable: these fail the run
    unused_allows: List[Allow]       # stale entries (warned, not fatal)
    files_scanned: int = 0
    # per-pass wall time (seconds) and the summary-cache hit/miss
    # counts — repo_summary()'s cost attribution
    timings: Dict[str, float] = dataclasses.field(default_factory=dict)
    summary_cache: Dict[str, int] = dataclasses.field(
        default_factory=dict
    )

    @property
    def ok(self) -> bool:
        return not self.unbaselined

    def summary(self) -> Dict[str, object]:
        return {
            "files_scanned": self.files_scanned,
            "findings": len(self.findings),
            "allowlisted": len(self.allowlisted),
            "baselined": len(self.baselined),
            "unbaselined": len(self.unbaselined),
            "ok": self.ok,
        }


def run_passes_on_unit(
    unit: FileUnit, passes: Sequence[LintPass]
) -> List[Finding]:
    out: List[Finding] = []
    for p in passes:
        out.extend(p.run(unit))
    return out


def run_source(
    source: str,
    filename: str,
    passes: Sequence[LintPass],
) -> List[Finding]:
    """Run ``passes`` over one in-memory file — the fixture-test entry
    point.  ``filename`` is the repo-relative path the source pretends
    to live at (several passes scope rules by path)."""
    return run_passes_on_unit(FileUnit(filename, source), passes)


def run_project_sources(
    sources: Dict[str, str],
    passes: Sequence[LintPass],
) -> List[Finding]:
    """Run ``passes`` over an in-memory multi-file project — the
    fixture entry point for the interprocedural passes.  ``sources``
    maps repo-relative paths to source text; a Project (call graph +
    summaries, no on-disk cache) is built over all of them, per-unit
    passes run per file and ProjectPasses once."""
    from .interproc import Project

    units = [FileUnit(path, src) for path, src in sources.items()]
    Project(units)  # attaches itself as unit.project
    findings: List[Finding] = []
    for p in passes:
        if isinstance(p, ProjectPass):
            findings.extend(p.run_project(units[0].project))
        else:
            for unit in units:
                findings.extend(p.run(unit))
    findings.sort(key=lambda f: (f.file, f.line, f.pass_id))
    return findings


def iter_scan_files(root: str) -> Iterable[str]:
    for rel in SCAN_FILES:
        if os.path.isfile(os.path.join(root, rel)):
            yield rel
    for d in SCAN_DIRS:
        top = os.path.join(root, d)
        for dirpath, dirnames, filenames in os.walk(top):
            dirnames[:] = sorted(
                x for x in dirnames if x not in _EXCLUDE_PARTS
            )
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    yield os.path.relpath(
                        os.path.join(dirpath, fn), root
                    ).replace(os.sep, "/")


def run_repo(
    root: str,
    passes: Sequence[LintPass],
    allowlist: Sequence[Allow] = (),
    baseline: Optional[Dict[str, int]] = None,
    only_files: Optional[Iterable[str]] = None,
) -> LintResult:
    """Lint the tree at ``root``.

    ``only_files`` (the ``--changed`` mode) restricts which files the
    per-unit passes REPORT on; every file is still parsed and fed to
    the Project, because the interprocedural passes need the whole
    package — an orphaned KV consumer caused by a rename in a changed
    file may sit in an unchanged one, so ProjectPass findings are
    never filtered.
    """
    import time as _time

    validate_allowlist(allowlist)
    findings: List[Finding] = []
    units: List[FileUnit] = []
    only = (
        None if only_files is None
        else {f.replace(os.sep, "/") for f in only_files}
    )
    n_files = 0
    for rel in iter_scan_files(root):
        n_files += 1
        with open(os.path.join(root, rel), encoding="utf-8") as f:
            src = f.read()
        try:
            units.append(FileUnit(rel, src, root=root))
        except SyntaxError as e:
            # a broken file must surface as ONE actionable finding, not
            # kill the whole run: the other 100+ files' findings are
            # exactly what a mid-refactor lint exists to report
            findings.append(
                Finding(
                    pass_id="driver-parse-error",
                    file=rel.replace(os.sep, "/"),
                    line=e.lineno or 0,
                    message=f"cannot parse: {e.msg}",
                    context="<module>",
                )
            )
            continue
    from .interproc import Project

    project = Project(units, root=root)
    timings: Dict[str, float] = {}
    if any(isinstance(p, ProjectPass) for p in passes):
        # build the shared substrate (call graph, Tarjan SCCs, summary
        # extraction + bottom-up closures) under its own timing key —
        # lazily it would all be charged to whichever ProjectPass runs
        # first, misdirecting the cost attribution this exists for
        t0 = _time.monotonic()
        project.summaries
        timings["interproc-substrate"] = _time.monotonic() - t0
    for p in passes:
        t0 = _time.monotonic()
        if isinstance(p, ProjectPass):
            findings.extend(p.run_project(project))
        else:
            for unit in units:
                if only is not None and unit.relpath not in only:
                    continue
                findings.extend(p.run(unit))
        timings[p.pass_id] = (
            timings.get(p.pass_id, 0.0) + _time.monotonic() - t0
        )
    summary_cache = (
        {
            "hits": project.summaries.cache_hits,
            "misses": project.summaries.cache_misses,
        }
        if project._summaries is not None
        else {"hits": 0, "misses": 0}
    )
    findings.sort(key=lambda f: (f.file, f.line, f.pass_id))

    allowlisted: List[Finding] = []
    remaining: List[Finding] = []
    used = [False] * len(allowlist)
    for f in findings:
        for i, a in enumerate(allowlist):
            if a.matches(f):
                used[i] = True
                allowlisted.append(f)
                break
        else:
            remaining.append(f)

    budget = dict(baseline or {})
    baselined: List[Finding] = []
    unbaselined: List[Finding] = []
    for f in remaining:
        if budget.get(f.fingerprint, 0) > 0:
            budget[f.fingerprint] -= 1
            baselined.append(f)
        else:
            unbaselined.append(f)

    return LintResult(
        findings=findings,
        allowlisted=allowlisted,
        baselined=baselined,
        unbaselined=unbaselined,
        unused_allows=[a for i, a in enumerate(allowlist) if not used[i]],
        files_scanned=n_files,
        timings=timings,
        summary_cache=summary_cache,
    )
