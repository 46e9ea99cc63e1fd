"""Env-knob registry pass: every PEGASUS_* read <-> README knob table.

Before this pass the repo read ~67 ``PEGASUS_*`` environment knobs and
documented roughly 28 of them, scattered through prose — an operator
could not enumerate the configuration surface, and a renamed knob left
its documentation silently lying. Now README.md carries a
'### Configuration-knob table' (name | default | effect) and this pass
enforces BOTH directions:

  * every knob the code READS must have a table row;
  * every table row must still be read somewhere (a deleted knob's row
    documents configuration that does nothing — worse than nothing).

What counts as a read (AST, not grep — a knob mentioned in a docstring
is documentation, not configuration surface):

  * ``os.environ.get("PEGASUS_X")`` / ``os.getenv`` / ``environ[...]``
    (Load context only — writes into a child process's env dict are not
    reads) / ``environ.setdefault``;
  * the same with the name behind a module-level constant
    (``_DEPTH_ENV = "PEGASUS_COMPACT_PIPELINE_DEPTH"``);
  * helper wrappers whose name starts with ``_env``
    (lane_guard's ``_env_float``/``_env_int``);
  * prefix families: an env-read of ``f"{env_prefix}_DEADLINE_S"``
    registers the template ``*_DEADLINE_S``; literal ``PEGASUS_*``
    prefixes flowing into an ``env_prefix`` parameter (as its default,
    or as the first argument of a ``*.from_env(...)`` call) expand every
    template — lane_guard's two lanes times four knobs resolve to all
    eight real names;
  * ``#: env_knob NAME [NAME...]`` declares knobs the walker cannot see
    (none today; the escape hatch for future dynamic composition).

Scanned: pegasus_tpu/, tools/*.py, tests/conftest.py (the
test harness reads real knobs like PEGASUS_TEST_TPU).
"""

import ast
import re

from . import Finding, Repo, register

_ENV_CALL_ATTRS = {"get", "getenv", "setdefault"}


def _is_environ(node) -> bool:
    """`os.environ` / `environ` / `os` (for os.getenv)."""
    s = ""
    try:
        s = ast.unparse(node)
    except Exception:  # noqa: BLE001
        return False
    return s in ("os.environ", "environ", "os")


def _const_str(node, consts) -> str:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.Name):
        return consts.get(node.id, "")
    return ""


def _fstring_template(node) -> str:
    """JoinedStr with a leading hole and literal tail -> '*<tail>'."""
    if not isinstance(node, ast.JoinedStr) or len(node.values) < 2:
        return ""
    if not isinstance(node.values[0], ast.FormattedValue):
        return ""
    tail = ""
    for v in node.values[1:]:
        if isinstance(v, ast.Constant) and isinstance(v.value, str):
            tail += v.value
        else:
            return ""
    return "*" + tail if tail else ""


def _collect_file(sf, knobs: set, templates: set, prefixes: set) -> None:
    # module-level string constants (name indirection)
    consts = {}
    for node in sf.tree.body:
        if isinstance(node, ast.Assign) and \
                isinstance(node.value, ast.Constant) and \
                isinstance(node.value.value, str):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    consts[t.id] = node.value.value

    def add_name_arg(arg) -> None:
        s = _const_str(arg, consts)
        if s.startswith("PEGASUS_"):
            knobs.add(s)
        else:
            t = _fstring_template(arg)
            if t:
                templates.add(t)

    for node in ast.walk(sf.tree):
        # environ["X"] in Load context
        if isinstance(node, ast.Subscript) and \
                isinstance(node.ctx, ast.Load) and _is_environ(node.value):
            add_name_arg(node.slice)
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        if isinstance(fn, ast.Attribute):
            # os.environ.get / os.getenv / environ.setdefault
            if fn.attr in _ENV_CALL_ATTRS and _is_environ(fn.value) \
                    and node.args:
                add_name_arg(node.args[0])
            # prefix families: SomeConfig.from_env("PEGASUS_READ_LANE",…)
            if fn.attr == "from_env" and node.args:
                s = _const_str(node.args[0], consts)
                if s.startswith("PEGASUS_"):
                    prefixes.add(s)
        elif isinstance(fn, ast.Name):
            # helper wrappers: _env_float(f"{env_prefix}_DEADLINE_S", …)
            if fn.id.startswith("_env") and node.args:
                add_name_arg(node.args[0])
    # env-prefix parameter DEFAULTS count as family prefixes too
    for node in ast.walk(sf.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            args = node.args
            defaults = args.defaults
            params = args.args[len(args.args) - len(defaults):]
            for p, d in zip(params, defaults):
                if p.arg.endswith("prefix") and \
                        isinstance(d, ast.Constant) and \
                        isinstance(d.value, str) and \
                        d.value.startswith("PEGASUS_"):
                    prefixes.add(d.value)
    # declared knobs the walker cannot see
    for anns in sf.annotations.values():
        for kind, arg in anns:
            if kind == "env_knob":
                for name in arg.split():
                    if name.startswith("PEGASUS_"):
                        knobs.add(name)


def source_knobs(repo: Repo) -> set:
    """Every PEGASUS_* env name the code reads (families expanded)."""
    knobs, templates, prefixes = set(), set(), set()
    files = repo.package_files() + repo.tool_files()
    conftest = repo.root / "tests" / "conftest.py"
    if conftest.exists():
        files.append(repo.file("tests/conftest.py"))
    for sf in files:
        if "PEGASUS_" not in sf.text and "environ" not in sf.text:
            continue
        _collect_file(sf, knobs, templates, prefixes)
    for t in templates:
        for p in prefixes:
            knobs.add(p + t[1:])
    return knobs


_ROW_NAME_RE = re.compile(r"`(PEGASUS_[A-Z0-9_]+)`")


def readme_knob_rows(repo: Repo) -> list:
    """Knob names from README's '### Configuration-knob table'."""
    rows = []
    for cells in repo.readme_table_rows("Configuration-knob table"):
        m = _ROW_NAME_RE.search(cells[0])
        if m:
            rows.append(m.group(1))
    return rows


def lint_findings(src: set, rows: list) -> list:
    out = []
    if not rows:
        return [Finding(
            "env_knobs", "", 0,
            "README.md has no '### Configuration-knob table' section "
            "(or it is empty) — every PEGASUS_* knob the code reads "
            "must be documented there", key="no-table")]
    documented = set(rows)
    for name in sorted(src - documented):
        out.append(Finding(
            "env_knobs", "", 0,
            f"env knob {name} is read in source but missing from "
            f"README.md's Configuration-knob table",
            key=f"undoc:{name}"))
    for name in sorted(documented - src):
        out.append(Finding(
            "env_knobs", "", 0,
            f"README Configuration-knob table row {name} is read "
            f"nowhere in source — delete the row or restore the knob",
            key=f"stale-row:{name}"))
    return out


@register("env_knobs")
def run(repo: Repo = None) -> list:
    repo = repo or Repo()
    return lint_findings(source_knobs(repo), readme_knob_rows(repo))
