"""The benchmark's contract with the program (ISSUE 32): what
BENCHMARK.json and benchmarks/metrics/ name must exist in the tree.

A per-layer metric reads a stage total, a counter or a rate BY NAME; when
a span or a counter is renamed or deleted under pegasus_tpu/ the metric
does not fail, it turns `null` in the ledger. This file is tier-1's guard
on that: it reads BENCHMARK.json and benchmarks/, edits neither, runs no
cell and imports no jax. It checks names, never values.
"""

import ast
import json
import subprocess
import sys
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
BENCHMARK = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCHMARK["workloads"]]

# the parameters of a metric file that hold a program name, by what kind
# of name: a windowed counter ("stage.<span>.us", "rate:<counter>", a
# plain counter; "ops:<kind>" is the harness's own count of completed
# operations) or a compaction stage of manual_compact's stats["trace"]
_COUNTER_PARAMS = ("num", "den", "of", "ranges", "calls")
_STAGE_PARAMS = ("stage", "stages", "requires")
_STAGE_FIELDS = (".n", ".us", ".self_us")


@pytest.fixture(scope="module")
def literals():
    """Every string literal under pegasus_tpu/, the literal pieces of
    f-strings among them."""
    out = set()
    for path in (REPO / "pegasus_tpu").rglob("*.py"):
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.Constant) and isinstance(node.value, str):
                out.add(node.value)
    return out


def _named(name: str, literals: set) -> bool:
    """`name` is a literal, or a literal stem and a literal rest that meet
    at one of its dots (f"rpc.server.{code}", stage + ".dispatch")."""
    if name in literals:
        return True
    return any(name[:j] in literals and name[j:] in literals
               for i, c in enumerate(name) if c == "."
               for j in (i, i + 1))


def _program_names(params: dict) -> list:
    """(kind, name) for every program name a metric's params hold."""
    def values(keys):
        for key in keys:
            value = params.get(key, [])
            yield from [value] if isinstance(value, str) else value

    out = [("stage", name) for name in values(_STAGE_PARAMS)]
    for name in values(_COUNTER_PARAMS):
        if name.startswith("ops:"):
            continue
        if name.startswith("stage."):
            assert name.endswith(_STAGE_FIELDS), name
            name = name[len("stage."):name.rindex(".")]
        out.append(("counter", name.removeprefix("rate:")))
    return out


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_exist_and_its_metrics_name_the_program(cell, literals):
    workload = next(w for w in BENCHMARK["workloads"] if w["name"] == cell)
    config = next(c for c in BENCHMARK["configs"]
                  if c["name"] == workload["config"])
    assert json.loads((REPO / config["file"]).read_text())
    described = json.loads(
        (REPO / "benchmarks" / "workloads" / f"{cell}.json").read_text())
    assert described["config"] == workload["config"]
    metrics = [m for m in BENCHMARK["per_layer"]
               if cell in m.get("workloads", CELLS)]
    assert metrics, f"no per-layer metric lists {cell}"
    missing = []
    for m in metrics:
        spec = json.loads((REPO / "benchmarks" / "metrics"
                           / f"{m['name']}.json").read_text())
        assert spec["name"] == m["name"]
        assert (REPO / "benchmarks" / "readers"
                / f"{spec['reader']}.py").is_file(), spec
        missing += [f"{m['name']}: {kind} {name!r}"
                    for kind, name in _program_names(spec["params"])
                    if not _named(name, literals)]
    assert not missing, (
        "named by a metric, found nowhere under pegasus_tpu/ (the metric "
        "would read null):\n" + "\n".join(missing))


def test_benchmark_paths_and_command():
    assert BENCHMARK["paths"] == ["benchmarks"]
    argv = BENCHMARK["command"]
    assert argv[0].startswith("python") and (REPO / argv[1]).is_file()
    assert {w["config"] for w in BENCHMARK["workloads"]} \
        <= {c["name"] for c in BENCHMARK["configs"]}


def test_benchmark_command_help_is_quick():
    """The entry point parses its arguments before it loads anything
    heavy: --help answers, names every option the driver passes, and
    takes no device."""
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable] + BENCHMARK["command"][1:] + ["--help"],
        cwd=REPO, capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr[-800:]
    assert time.monotonic() - t0 < 10
    for option in ("--workload", "--seed", "--seconds", "--trace",
                   "--rehearsal"):
        assert option in proc.stdout
