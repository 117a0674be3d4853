"""``hvdlint`` core: finding/severity model, suppressions, baseline,
module loading and the rule-driver loop.

The analyzer is the compile-time half of the correctness contract the
runtime guards (HLO tests, chaos plans) enforce dynamically: every rule
is grounded in a failure class this repo has already paid for at least
once — a rank-divergent collective deadlocks a pod, a host sync inside
the jitted step stalls dispatch, an unstable cache key silently re-pays
the 40-50 s compile, an unlocked cross-thread mutation corrupts the
elastic bookkeeping.  Rules are AST-based (no imports of the analyzed
code, so a broken module can still be linted) and cheap enough that the
package-wide self-run is a tier-1 test.

Model:

* :class:`Finding` — one violation: rule id, severity (P0 worst → P3),
  location, message, and the stripped source line (``context``) that
  doubles as its line-shift-stable baseline identity.
* suppression — ``# hvd: disable=HVD001 -- <reason>`` on the flagged
  line or on a comment line directly above it.  The reason is
  mandatory: a reasonless disable is itself a finding (``HVD000``), so
  a suppression always documents *why* the rule is wrong here.
* baseline — a checked-in JSON of accepted findings, matched by
  ``(rule, path, context)``; new code cannot hide behind it because any
  new finding has a context line the baseline has never seen.
"""

from __future__ import annotations

import ast
import dataclasses
import enum
import json
import os
import re
import subprocess
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple


class Severity(enum.IntEnum):
    """P0 (pod-deadlock class) is the worst; P3 is advisory."""

    P0 = 0
    P1 = 1
    P2 = 2
    P3 = 3

    def __str__(self) -> str:  # noqa: D105
        return self.name


@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str
    severity: Severity
    path: str              # posix path relative to the scan root
    line: int
    col: int
    message: str
    context: str = ""      # stripped source line (baseline identity)

    def key(self) -> Tuple[str, str, str]:
        return (self.rule, self.path, self.context)

    def format(self) -> str:
        return (f"{self.path}:{self.line}:{self.col}: "
                f"{self.rule} [{self.severity}] {self.message}")

    def as_json(self) -> dict:
        return {"rule": self.rule, "severity": str(self.severity),
                "path": self.path, "line": self.line, "col": self.col,
                "message": self.message, "context": self.context}


# ``# hvd: disable=HVD001[,HVD004] -- reason`` (reason mandatory; the
# engine turns a missing one into an HVD000 finding)
_SUPPRESS_RE = re.compile(
    r"#\s*hvd:\s*disable=([A-Za-z0-9_,\s\*]+?)\s*(?:--\s*(.*?))?\s*$")


@dataclasses.dataclass
class Suppression:
    rules: Set[str]        # rule ids, or {"*"}
    reason: str
    line: int

    def covers(self, rule: str) -> bool:
        return "*" in self.rules or rule in self.rules


class Module:
    """One parsed source file plus its per-line suppression table."""

    def __init__(self, path: str, relpath: str, source: str):
        self.path = path
        self.relpath = relpath
        self.source = source
        self.lines = source.splitlines()
        self.tree: Optional[ast.AST] = None
        self.parse_error: Optional[SyntaxError] = None
        try:
            self.tree = ast.parse(source, filename=path)
        except SyntaxError as e:
            self.parse_error = e
        self.suppressions: Dict[int, Suppression] = {}
        self.bad_suppressions: List[int] = []
        for i, text in enumerate(self.lines, start=1):
            m = _SUPPRESS_RE.search(text)
            if not m:
                continue
            rules = {r.strip() for r in m.group(1).split(",") if r.strip()}
            reason = (m.group(2) or "").strip()
            if not reason:
                self.bad_suppressions.append(i)
                continue
            self.suppressions[i] = Suppression(rules, reason, i)

    def context_line(self, line: int) -> str:
        if 1 <= line <= len(self.lines):
            return self.lines[line - 1].strip()
        return ""

    def suppression_for(self, finding: Finding) -> Optional[Suppression]:
        """Inline on the finding's line, or a comment-only line directly
        above it."""
        s = self.suppressions.get(finding.line)
        if s is not None and s.covers(finding.rule):
            return s
        prev = finding.line - 1
        s = self.suppressions.get(prev)
        if s is not None and s.covers(finding.rule) and \
                self.context_line(prev).startswith("#"):
            return s
        return None


class Project:
    """The full analyzed file set plus repo-level context shared by the
    cross-module rules (docs text for HVD005, the knob registry, the
    lock graph for HVD004)."""

    def __init__(self, modules: Sequence[Module], root: str,
                 repo_root: Optional[str] = None):
        self.modules = list(modules)
        self.root = root
        self.repo_root = repo_root or find_repo_root(root) or root
        self._docs_text: Optional[str] = None

    def module(self, relpath_suffix: str) -> Optional[Module]:
        for m in self.modules:
            if m.relpath.endswith(relpath_suffix):
                return m
        return None

    def docs_text(self) -> str:
        """Concatenated documentation the HVD005 doc-drift check scans —
        the same corpus ``tests/test_env_knob_docs.py`` used before it
        delegated here."""
        if self._docs_text is not None:
            return self._docs_text
        texts = []
        docs = os.path.join(self.repo_root, "docs")
        if os.path.isdir(docs):
            for base, _, names in sorted(os.walk(docs)):
                for n in sorted(names):
                    if n.endswith(".md"):
                        texts.append(_read(os.path.join(base, n)))
        for name in ("README.md", "PERF_NOTES.md"):
            p = os.path.join(self.repo_root, name)
            if os.path.exists(p):
                texts.append(_read(p))
        self._docs_text = "\n".join(texts)
        return self._docs_text


class Rule:
    """One lint rule.  ``check`` runs per module; ``finalize`` runs once
    with the whole project (cross-module invariants)."""

    id: str = "HVD000"
    severity: Severity = Severity.P2
    name: str = ""
    rationale: str = ""

    def check(self, module: Module, project: Project) -> Iterable[Finding]:
        return ()

    def finalize(self, project: Project) -> Iterable[Finding]:
        return ()

    def finding(self, module: Module, node, message: str,
                severity: Optional[Severity] = None) -> Finding:
        line = getattr(node, "lineno", 1)
        col = getattr(node, "col_offset", 0)
        return Finding(rule=self.id,
                       severity=severity or self.severity,
                       path=module.relpath, line=line, col=col,
                       message=message,
                       context=module.context_line(line))


def _read(path: str) -> str:
    with open(path, "r", errors="replace") as f:
        return f.read()


def find_repo_root(start: str) -> Optional[str]:
    cur = os.path.abspath(start)
    if os.path.isfile(cur):
        cur = os.path.dirname(cur)
    while True:
        if os.path.exists(os.path.join(cur, "pyproject.toml")) or \
                os.path.isdir(os.path.join(cur, ".git")):
            return cur
        parent = os.path.dirname(cur)
        if parent == cur:
            return None
        cur = parent


def collect_files(paths: Sequence[str]) -> List[str]:
    out: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            for base, dirs, names in os.walk(p):
                dirs[:] = [d for d in dirs if d != "__pycache__"]
                for n in sorted(names):
                    if n.endswith(".py"):
                        out.append(os.path.join(base, n))
        elif p.endswith(".py"):
            out.append(p)
    return sorted(dict.fromkeys(out))


def changed_files(repo_root: str) -> List[str]:
    """``--changed`` scope: files touched vs HEAD (staged + unstaged)
    plus untracked — the pre-commit view of the working tree."""
    def git(*args: str) -> List[str]:
        res = subprocess.run(["git", "-C", repo_root, *args],
                             capture_output=True, text=True, check=True)
        return [ln for ln in res.stdout.splitlines() if ln.strip()]

    names = set(git("diff", "--name-only", "HEAD"))
    names.update(git("ls-files", "--others", "--exclude-standard"))
    return sorted(os.path.join(repo_root, n) for n in names
                  if n.endswith(".py") and
                  os.path.exists(os.path.join(repo_root, n)))


# -- baseline ---------------------------------------------------------------

BASELINE_VERSION = 1


def load_baseline(path: str) -> Set[Tuple[str, str, str]]:
    with open(path, "r") as f:
        data = json.load(f)
    return {(f_["rule"], f_["path"], f_.get("context", ""))
            for f_ in data.get("findings", [])}


def write_baseline(path: str, findings: Sequence[Finding]) -> None:
    entries = sorted({f.key() for f in findings})
    data = {"version": BASELINE_VERSION,
            "findings": [{"rule": r, "path": p, "context": c}
                         for (r, p, c) in entries]}
    with open(path, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)
        f.write("\n")


# -- the driver loop --------------------------------------------------------

@dataclasses.dataclass
class Report:
    findings: List[Finding]                       # live (actionable)
    suppressed: List[Tuple[Finding, str]]         # (finding, reason)
    baselined: List[Finding]
    files_scanned: int = 0

    @property
    def exit_code(self) -> int:
        return 1 if self.findings else 0

    def as_json(self) -> dict:
        return {
            "files_scanned": self.files_scanned,
            "findings": [f.as_json() for f in self.findings],
            "suppressed": [dict(f.as_json(), reason=r)
                           for f, r in self.suppressed],
            "baselined": [f.as_json() for f in self.baselined],
        }


def default_rules() -> List[Rule]:
    from horovod_tpu.analysis.rules_distributed import (
        CollectiveDivergenceRule,
        HostSyncInHotPathRule,
        RetraceHazardRule,
    )
    from horovod_tpu.analysis.rules_runtime import (
        EnvKnobRegistryRule,
        FaultHookCoverageRule,
    )
    from horovod_tpu.analysis.rules_threads import ThreadLockDisciplineRule

    return [CollectiveDivergenceRule(), HostSyncInHotPathRule(),
            RetraceHazardRule(), ThreadLockDisciplineRule(),
            EnvKnobRegistryRule(), FaultHookCoverageRule()]


def load_modules(files: Sequence[str], root: str) -> List[Module]:
    modules = []
    for path in files:
        rel = os.path.relpath(path, root).replace(os.sep, "/")
        modules.append(Module(path, rel, _read(path)))
    return modules


def run_analysis(paths: Sequence[str],
                 select: Optional[Set[str]] = None,
                 baseline_path: Optional[str] = None,
                 rules: Optional[Sequence[Rule]] = None,
                 root: Optional[str] = None) -> Report:
    """Lint ``paths`` (files or directories) and return the report.

    ``select`` restricts to a set of rule ids; ``baseline_path`` (when
    it exists) removes previously-accepted findings; ``root`` anchors
    the relative paths findings/baselines use (default: the repo root
    above the first path, so baselines are stable no matter where the
    CLI is invoked from)."""
    files = collect_files(paths)
    if root is None:
        root = find_repo_root(paths[0] if files else os.getcwd()) \
            or os.getcwd()
    modules = load_modules(files, root)
    project = Project(modules, root=root)
    active = [r for r in (rules if rules is not None else default_rules())
              if select is None or r.id in select]

    raw: List[Finding] = []
    for m in modules:
        if m.parse_error is not None:
            raw.append(Finding(
                rule="HVD000", severity=Severity.P1, path=m.relpath,
                line=m.parse_error.lineno or 1, col=0,
                message=f"syntax error: {m.parse_error.msg}",
                context=m.context_line(m.parse_error.lineno or 1)))
            continue
        for line in m.bad_suppressions:
            raw.append(Finding(
                rule="HVD000", severity=Severity.P1, path=m.relpath,
                line=line, col=0,
                message="suppression without a reason — write "
                        "'# hvd: disable=RULE -- why this is a false "
                        "positive here'",
                context=m.context_line(line)))
        for rule in active:
            raw.extend(rule.check(m, project))
    for rule in active:
        raw.extend(rule.finalize(project))

    by_path = {m.relpath: m for m in modules}
    baseline = load_baseline(baseline_path) \
        if baseline_path and os.path.exists(baseline_path) else set()

    live: List[Finding] = []
    suppressed: List[Tuple[Finding, str]] = []
    baselined: List[Finding] = []
    for f in sorted(raw, key=lambda f: (f.severity, f.path, f.line)):
        m = by_path.get(f.path)
        sup = m.suppression_for(f) if m is not None else None
        # HVD000 (engine hygiene) cannot be suppressed or baselined —
        # otherwise a reasonless disable could disable the rule that
        # flags reasonless disables
        if f.rule != "HVD000":
            if sup is not None:
                suppressed.append((f, sup.reason))
                continue
            if f.key() in baseline:
                baselined.append(f)
                continue
        live.append(f)
    return Report(findings=live, suppressed=suppressed,
                  baselined=baselined, files_scanned=len(files))
