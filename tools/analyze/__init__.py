"""Static-analysis framework: one AST walk, many passes (ISSUE 9).

PRs 1-8 grew three ad-hoc repo lints (fail points, metric names, remote
commands), each with its own file scan, README parser and test wiring —
and the concurrency they guard grew much faster than the lints did.
This package unifies them behind one registry and adds the concurrency
passes the review rounds kept doing by hand:

  fail_points       test-armed fail points exist; source hooks documented
  metric_names      counter registrations <-> README metric table
  remote_commands   command registrations <-> README command table
  events            events.emit() names <-> README event table (and the
                    names must be plain string literals)
  span_names        tracer span/hop names <-> README span-name table
                    (literal call sites only; dynamic names are exempt)
  lock_discipline   `#: guarded_by` fields only touched under their lock
  thread_lifecycle  raw Thread/ThreadPoolExecutor spawns must route
                    through runtime/tasking's tracked helpers
  env_knobs         every PEGASUS_* env read <-> README knob table
  doc_paths         a source file the README names exists

Run everything:  python -m tools.analyze  (exit 0 = clean; --json for
machine-readable findings). Individual passes: --pass NAME (repeat).
Per-pass baselines (tools/analyze/baseline.json) grandfather known
findings by stable key so new regressions fail while tracked debt does
not; a stale baseline entry (fixed finding still listed) also fails —
the baseline must shrink, never rot.

The annotation grammar the concurrency passes consume is documented in
README.md's "Static analysis" section and in the pass modules.
"""

import ast
import json
import re
from dataclasses import dataclass, field
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent.parent
BASELINE_PATH = Path(__file__).resolve().parent / "baseline.json"

# `#: <kind> <arg>` — the shared annotation grammar (lock_discipline,
# thread_lifecycle, env_knobs). Kind is one word; arg runs to end of line.
_ANNOT_RE = re.compile(r"#:\s*(guarded_by|requires|unguarded_ok|"
                       r"untracked_ok|env_knob)\b\s*(.*?)\s*$")


@dataclass
class Finding:
    """One pass finding. `key` is the stable baseline identity — never
    line-number-based (lines drift), always pass:file:symbol-ish."""

    pass_name: str
    file: str        # repo-relative path ('' for repo-level findings)
    line: int
    message: str
    key: str

    def as_dict(self) -> dict:
        return {"pass": self.pass_name, "file": self.file,
                "line": self.line, "message": self.message,
                "key": self.key}

    def render(self) -> str:
        loc = f"{self.file}:{self.line}: " if self.file else ""
        return f"[{self.pass_name}] {loc}{self.message}"


class SourceFile:
    """One parsed source file, shared across passes: text, line table,
    AST, and the `#:` annotations by line."""

    def __init__(self, path: Path, root: Path):
        self.path = path
        self.rel = str(path.relative_to(root))
        self.text = path.read_text()
        self.lines = self.text.splitlines()
        self._tree = None
        self.annotations = {}  # line(1-based) -> list[(kind, arg)]
        for i, line in enumerate(self.lines, 1):
            m = _ANNOT_RE.search(line)
            if m:
                self.annotations.setdefault(i, []).append(
                    (m.group(1), m.group(2)))

    @property
    def tree(self) -> ast.Module:
        if self._tree is None:
            self._tree = ast.parse(self.text, filename=self.rel)
        return self._tree

    def annotation(self, line: int, kind: str):
        """First annotation of `kind` on `line`, or None -> arg string."""
        for k, arg in self.annotations.get(line, []):
            if k == kind:
                return arg
        return None


class Repo:
    """The analysis target: a directory shaped like this repository.
    Tests build throwaway ones (tmp dir + synthetic modules + a tiny
    README) and run any pass against them."""

    def __init__(self, root=REPO):
        self.root = Path(root)
        self._files = {}

    def file(self, rel: str) -> SourceFile:
        sf = self._files.get(rel)
        if sf is None:
            sf = self._files[rel] = SourceFile(self.root / rel, self.root)
        return sf

    def _glob(self, patterns) -> list:
        out = []
        for pat in patterns:
            for p in sorted(self.root.glob(pat)):
                if p.is_file() and "__pycache__" not in p.parts:
                    out.append(self.file(str(p.relative_to(self.root))))
        return out

    def package_files(self) -> list:
        """The runtime package."""
        return self._glob(["pegasus_tpu/**/*.py"])

    def tool_files(self) -> list:
        return self._glob(["tools/*.py"])

    def test_files(self) -> list:
        return self._glob(["tests/**/*.py"])

    @property
    def readme(self) -> str:
        p = self.root / "README.md"
        return p.read_text() if p.exists() else ""

    def readme_section(self, heading: str) -> str:
        """Body of a `### heading` (or `## heading`) section up to the
        next same-or-higher heading — the ONE README slicer every
        table-driven pass shares."""
        level = "###" if not heading.startswith("## ") else "##"
        name = heading.removeprefix("## ")
        m = re.search(rf"^{level} {re.escape(name)}$(.*?)(?=^#{{2,3}} |\Z)",
                      self.readme, re.MULTILINE | re.DOTALL)
        return m.group(1) if m else ""

    def readme_table_rows(self, heading: str) -> list:
        """Markdown-table rows of a section: list of cell lists (outer
        pipes stripped, separator/header-rule rows dropped). The shared
        parser behind the metric/command/knob tables."""
        rows = []
        for line in self.readme_section(heading).splitlines():
            if not line.startswith("|"):
                continue
            # split on UNESCAPED pipes only: usage/alternation cells
            # legitimately contain `\|`
            cells = [c.strip() for c in
                     re.split(r"(?<!\\)\|", line.strip().strip("|"))]
            if cells and not all(set(c) <= {"-", " ", ":"} for c in cells):
                rows.append(cells)
        return rows


# ---------------------------------------------------------------- registry

_PASSES = {}


def register(name: str):
    """Decorator: register `fn(repo) -> list[Finding]` as a pass."""
    def deco(fn):
        _PASSES[name] = fn
        return fn
    return deco


def pass_names() -> list:
    _load_passes()
    return sorted(_PASSES)


def _load_passes() -> None:
    from . import (doc_paths, env_knobs, events,  # noqa: F401
                   fail_points, lock_discipline, metric_names,
                   remote_commands, span_names, thread_lifecycle)


def run_pass(name: str, repo: Repo = None) -> list:
    _load_passes()
    return _PASSES[name](repo or Repo())


def load_baseline(path=BASELINE_PATH) -> dict:
    """{pass_name: set(keys)} of grandfathered findings."""
    p = Path(path)
    if not p.exists():
        return {}
    data = json.loads(p.read_text())
    return {k: set(v) for k, v in data.items()}


@dataclass
class Report:
    findings: list = field(default_factory=list)     # new (failing)
    grandfathered: list = field(default_factory=list)
    stale_baseline: list = field(default_factory=list)  # (pass, key)
    ran: list = field(default_factory=list)

    @property
    def clean(self) -> bool:
        return not self.findings and not self.stale_baseline

    def as_dict(self) -> dict:
        return {
            "clean": self.clean,
            "passes": self.ran,
            "findings": [f.as_dict() for f in self.findings],
            "grandfathered": [f.as_dict() for f in self.grandfathered],
            "stale_baseline": [{"pass": p, "key": k}
                               for p, k in self.stale_baseline],
        }


def run_all(repo: Repo = None, passes=None, baseline=None) -> Report:
    """Run the registered passes against `repo`, splitting findings by
    the baseline. A baseline key with no live finding is STALE and fails
    the run (debt must be re-justified or deleted, never forgotten)."""
    repo = repo or Repo()
    baseline = load_baseline() if baseline is None else baseline
    _load_passes()
    names = passes or sorted(_PASSES)
    report = Report(ran=list(names))
    for name in names:
        allowed = baseline.get(name, set())
        seen = set()
        for f in _PASSES[name](repo):
            if f.key in allowed:
                report.grandfathered.append(f)
                seen.add(f.key)
            else:
                report.findings.append(f)
        for key in sorted(allowed - seen):
            report.stale_baseline.append((name, key))
    return report
