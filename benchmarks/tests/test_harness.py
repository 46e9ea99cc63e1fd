"""run.py as the driver calls it: the last line is the contract's object,
in both cells, traced and untraced; a run with no chip and no --rehearsal
prints no result; and a cell, a configuration and a per-layer metric come
in as new files plus manifest entries, with no edit to what is there."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def run(root: str, *args, env=None) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "run.py"), *args],
        cwd=root, env=env or dict(os.environ), capture_output=True, text=True,
        timeout=600)


def well_formed(proc, manifest: dict, cell: str, trace: int) -> dict:
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert list(line)[-1] == "compared" and line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    dev = line["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    assert dev["platform"] == "cpu" and line["rehearsal"] is True
    listed = {m["name"]: m for m in
              manifest["per_layer" if trace else "end_to_end"]
              if "workloads" not in m or cell in m["workloads"]}
    assert set(line["metrics"]) <= set(listed)
    for name, m in line["metrics"].items():
        assert m["unit"] == listed[name]["unit"]
        assert isinstance(m["value"], (int, float))
    if trace:
        assert {"busy_s", "window_s"} <= set(dev)
        assert len(line["breakdown"]["device_ops"]) <= 10
        assert len(line["breakdown"]["idle_gaps"]) <= 10
    else:
        assert set(line["metrics"]) == set(listed)    # every end-to-end one
        assert all(m["value"] > 0 for m in line["metrics"].values())
    return line


def load_json(*parts) -> dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


CELLS = [c["name"] for c in load_json("BENCHMARK.json")["workloads"]]


@pytest.fixture(scope="module")
def manifest():
    return load_json("BENCHMARK.json")


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_rehearses_to_a_well_formed_last_line(manifest, cell,
                                                         trace):
    proc = run(ROOT, "--workload", cell, "--seed",
               str(2_147_483_648 + 17 * trace), "--seconds", "4",
               "--trace", str(trace), "--rehearsal")
    well_formed(proc, manifest, cell, trace)
    # a warm-up sweep runs where the cell's own file asks for one, says how
    # many sweeps it took and what each left the read lane's compile_behind
    # at, and ends on one that moved nothing; no other cell's run has one
    sweeps = re.findall(r"sweep (\d+): \d+ hashkeys in \S+ left compile_behind "
                        r"at (\d+) \(\+(\d+)\), compiled at \d+ \(\+(\d+)\)",
                        proc.stdout)
    asks = "sweep" in load_json("benchmarks", "workloads", cell + ".json").get(
        "warm_up", {})
    if asks:
        assert [int(n) for n, *_ in sweeps] == list(range(1, len(sweeps) + 1))
        assert sweeps and sweeps[-1][2:] == ("0", "0"), proc.stdout[-3000:]
    else:
        assert "sweep" not in proc.stdout
    if cell in ("ycsb1kb.a", "ycsb1kb.c"):      # a runs as it always did
        assert asks == (cell == "ycsb1kb.c")


def test_no_chip_and_no_rehearsal_prints_no_result(manifest):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for cell in manifest["workloads"]:
        proc = run(ROOT, "--workload", cell["name"], "--seed", "1",
                   "--seconds", "1", "--trace", "0", env=env)
        assert proc.returncode != 0
        assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())


def test_nothing_but_the_benchmark_is_no_place_to_run(tmp_path):
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    proc = run(str(tmp_path), "--workload", "compact10m.fill_compact",
               "--seed", "1", "--seconds", "1", "--trace", "0", "--rehearsal")
    assert proc.returncode != 0
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())


def test_a_cell_a_configuration_and_a_metric_come_in_as_new_files(tmp_path,
                                                                  manifest):
    """A copy of the checkout's benchmark (the program linked beside it),
    plus: a new configuration file, a new traffic mix file, a new metric
    file with a new reader, and their manifest entries. No file that was
    there is touched; the new cell runs and reports the new metric."""
    for name in ("benchmarks",):
        shutil.copytree(os.path.join(ROOT, name), tmp_path / name)
    for name in ("pegasus_tpu", "onebox.ini"):
        os.symlink(os.path.join(ROOT, name), tmp_path / name)
    before = {p: p.read_bytes() for p in (tmp_path / "benchmarks").rglob("*")
              if p.is_file() and "__pycache__" not in p.parts}

    bench = tmp_path / "benchmarks"
    cfg = json.loads((bench / "configs" / "compact10m.json").read_text())
    cfg["name"] = "compact10m_ttl50"
    cfg["fill"]["ttl_expired_share"] = 0.5
    (bench / "configs" / "compact10m_ttl50.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "workloads"
                      / "compact10m.fill_compact.json").read_text())
    mix.update(config="compact10m_ttl50", point_read_sample=400)
    (bench / "workloads" / "compact10m_ttl50.fill_compact.json").write_text(
        json.dumps(mix))
    (bench / "metrics" / "engine.step_s.json").write_text(json.dumps(
        {"name": "engine.step_s", "reader": "step_wall", "params": {}}))
    (bench / "readers" / "step_wall.py").write_text(
        "import statistics\n\n\ndef read(observed, params):\n"
        "    return statistics.median(s['end'] - s['start']\n"
        "                             for s in observed['steps'])\n")
    new = json.loads(json.dumps(manifest))
    cell = "compact10m_ttl50.fill_compact"
    new["configs"].append({
        "name": "compact10m_ttl50", "source": "BASELINE.json configs[3]",
        "file": "benchmarks/configs/compact10m_ttl50.json",
        "reduced": ["records"], "why": "half the records expired"})
    new["workloads"].append({"name": cell, "config": "compact10m_ttl50",
                             "traffic": "fill_compact", "chips": 1,
                             "why": "the filter does most of the work"})
    for m in new["end_to_end"]:
        if m["name"] == "compact_rate":
            m["workloads"].append(cell)
    new["per_layer"].append({
        "name": "engine.step_s", "unit": "s", "better": "lower",
        "source": "host_clock", "layer": "engine", "moves": "compact_rate",
        "workloads": [cell]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(new))

    proc = run(str(tmp_path), "--workload", cell, "--seed", "99",
               "--seconds", "1", "--trace", "1", "--rehearsal")
    line = well_formed(proc, new, cell, 1)
    assert set(line["metrics"]) == {"engine.step_s"}
    proc = run(str(tmp_path), "--workload", cell, "--seed", "99",
               "--seconds", "1", "--trace", "0", "--rehearsal")
    line = well_formed(proc, new, cell, 0)
    assert set(line["metrics"]) == {"compact_rate", "setup_s"}
    after = {p: p.read_bytes() for p in before}
    assert after == before
