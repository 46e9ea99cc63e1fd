"""What PR 27 adds to the benchmark: four per-layer metrics of the compact
cell, read from the stage spans the program now closes. Every new metric
file names a reader that is there and a stage the program opens; the
`stage_rest_share` reader on numbers worked by hand; the compact cell
rehearses, traced, to a line that carries them; and the reader that is
there (`counter_ratio`) already turns the program's windowable `stage.`
totals into milliseconds, for the served cells a `benchmark` PR is to add
(PERF.md section 7)."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

COMPACT = {"lane.pack_share", "lane.h2d_share", "engine.sst_read_share",
           "engine.compact_unnamed_share"}


def load(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def reader(name: str):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, "readers", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def manifest():
    return load(ROOT, "BENCHMARK.json")


@pytest.mark.parametrize("name", sorted(COMPACT))
def test_a_new_metric_file_resolves_to_a_reader_and_a_manifest_entry(
        manifest, name):
    desc = load(BENCH, "metrics", name + ".json")
    assert desc["name"] == name
    assert callable(reader(desc["reader"]).read)
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    assert entry["source"] == "program_span"
    cells = {c["name"]: c for c in manifest["workloads"]}
    assert entry["workloads"] and set(entry["workloads"]) <= set(cells)
    reports = {e["name"]: e.get("workloads", list(cells))
               for e in manifest["end_to_end"]}
    for cell in entry["workloads"]:      # each cell reports what it moves
        assert cell in reports[entry["moves"]]


def test_counter_ratio_reads_windowed_stage_totals_in_ms():
    observed = {"counters": {
        "before": {"stage.rpc.queue.us": 1_000, "stage.rpc.queue.n": 10},
        "after": {"stage.rpc.queue.us": 601_000, "stage.rpc.queue.n": 310}}}
    params = {"num": "stage.rpc.queue.us", "den": "stage.rpc.queue.n",
              "scale": 0.001}
    got = reader("counter_ratio").read(observed, params)
    assert got == pytest.approx(2.0)     # 600,000 us over 300 frames
    # a program without the counters (the parent): nothing, never zero
    assert reader("counter_ratio").read(
        {"counters": {"before": {}, "after": {}}}, params) is None


def test_stage_rest_share_on_a_hand_made_observed():
    read = reader("stage_rest_share").read
    params = {"stages": ["sst_read", "device", "sst_write"],
              "requires": ["sst_read"]}
    observed = {"steps": [
        {"manual_compact_s": 10.0,
         "stages": {"sst_read": 2.0, "device": 1.0, "sst_write": 4.0,
                    "compact": 3.5}},          # a parent: not in the list
        {"manual_compact_s": 10.0,
         "stages": {"sst_read": 3.0, "sst_write": 5.0}}]}
    assert read(observed, params) == pytest.approx(100 * (1 - 15.0 / 20.0))
    # a program without the spans this metric rests on (the parent closes
    # device, gather and sst_write, but no sst_read) is not read as "45 %
    # unnamed": the metric is left out
    assert read({"steps": [{"manual_compact_s": 9.0,
                            "stages": {"compact": 4.0, "device": 1.0,
                                       "sst_write": 3.0}}]}, params) is None
    assert read({"steps": []}, params) is None
    assert read({}, params) is None


def test_the_unnamed_share_lists_stages_the_program_opens():
    desc = load(BENCH, "metrics", "engine.compact_unnamed_share.json")
    src = ""
    for rel in ("engine/db.py", "engine/sstable.py", "ops/compact.py"):
        with open(os.path.join(ROOT, "pegasus_tpu", rel)) as f:
            src += f.read()
    for stage in desc["params"]["stages"]:
        assert f'.span("{stage}"' in src, stage
    assert set(desc["params"]["requires"]) <= set(desc["params"]["stages"])
    assert "compact" not in desc["params"]["stages"]   # it nests the others


def test_the_compact_cell_rehearses_traced_with_every_stage_named(manifest):
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "compact10m.fill_compact", "--seed", str(2_147_483_701),
         "--seconds", "2", "--trace", "1", "--rehearsal"], cwd=ROOT,
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert COMPACT <= set(line["metrics"]), sorted(line["metrics"])
    assert 0 <= line["metrics"]["engine.compact_unnamed_share"]["value"] < 100
