"""What PR 27 and PR 29 add to the benchmark: four per-layer metrics of the
compact cell and eight of the served cells, read from the stage spans the
program closes. Every new metric
file names a reader that is there and a stage the program opens; the
`stage_rest_share` reader on numbers worked by hand; the compact cell
rehearses, traced, to a line that carries them; and the reader that is
there (`counter_ratio`) already turns the program's windowable `stage.`
totals into milliseconds: each served metric file on a hand-made window,
and absent, never 0, where the program publishes no such counter."""

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

# One window's counters, made by hand: 3,000 reads and 1,000 updates done;
# 5,000 frames waited 10 s for a pool thread and 7.5 s for their reply's
# write; 3,000 GET handlers took 60 s; the reads parked 45 s behind other
# leaders' drains; 400 device lookups took 5.2 s of host wall; the updates
# waited 12 s for a place in the prepare window, 1,000 prepares took 25 s
# and the private log flushed 1,400 times. Before the window every counter
# stood at 7, which must not show.
WINDOW = {
    "stage.rpc.queue.us": 10_000_000, "stage.rpc.queue.n": 5_000,
    "stage.rpc.reply.us": 7_500_000, "stage.rpc.reply.n": 5_000,
    "stage.rpc.server.RPC_RRDB_RRDB_GET.us": 60_000_000,
    "stage.rpc.server.RPC_RRDB_RRDB_GET.n": 3_000,
    "stage.read.coalesce_wait.us": 45_000_000,
    "stage.read.coalesce_wait.n": 2_000,
    "stage.read.device.us": 5_200_000, "stage.read.device.n": 400,
    "stage.replica.window_wait.us": 12_000_000,
    "stage.replica.window_wait.n": 900,
    "stage.replica.prepare.us": 25_000_000, "stage.replica.prepare.n": 1_000,
    "stage.plog.flush.us": 9_000_000, "stage.plog.flush.n": 1_400}
SERVED = {  # metric -> (by hand, unit, what it moves, its cells)
    "rpc.queue_ms": (2.0, "ms", "read_p95", "ac"),
    "rpc.reply_ms": (1.5, "ms", "read_p95", "ac"),
    "rpc.get_handler_ms": (20.0, "ms", "read_p95", "ac"),
    "lane.coalesce_wait_per_read_ms": (15.0, "ms", "read_p95", "ac"),
    "lane.read_device_call_ms": (13.0, "ms", "read_p95", "ac"),
    "replication.window_wait_per_update_ms": (12.0, "ms", "update_p95", "a"),
    "replication.prepare_ms": (25.0, "ms", "update_p95", "a"),
    "replication.flush_per_update": (1.4, "1/op", "update_p95", "a")}


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


@pytest.mark.parametrize("name", sorted(COMPACT) + sorted(SERVED))
def test_a_new_metric_file_resolves_to_a_reader_and_a_manifest_entry(
        manifest, name):
    desc = load(BENCH, "metrics", name + ".json")
    assert desc["name"] == name
    assert callable(reader(desc["reader"]).read)
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    assert entry["source"] == ("program_counter" if name.endswith(
        "flush_per_update") else "program_span")
    cells = {c["name"]: c for c in manifest["workloads"]}
    assert entry["workloads"] and set(entry["workloads"]) <= set(cells)
    reports = {e["name"]: e.get("workloads", list(cells))
               for e in manifest["end_to_end"]}
    for cell in entry["workloads"]:      # each cell reports what it moves
        assert cell in reports[entry["moves"]]


@pytest.mark.parametrize("name", sorted(SERVED))
def test_a_served_stage_metric_reads_a_hand_made_window(manifest, name):
    by_hand, unit, moves, cells = SERVED[name]
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    assert (entry["unit"], entry["moves"]) == (unit, moves)
    assert entry["workloads"] == ["ycsb1kb." + c for c in cells]
    desc = load(BENCH, "metrics", name + ".json")
    assert desc["reader"] == "counter_ratio"
    read = reader(desc["reader"]).read
    observed = {
        "ops": {"read": 3_000, "update": 1_000}, "window_s": 51.0,
        "counters": {"before": dict.fromkeys(WINDOW, 7),
                     "after": {k: v + 7 for k, v in WINDOW.items()}}}
    assert read(observed, desc["params"]) == pytest.approx(by_hand)
    # the cell's file asks the server for the counters the metric reads
    for cell in entry["workloads"]:
        prefixes = load(BENCH, "workloads", cell + ".json")["counters"]
        for key in ("num", "den"):
            counter = desc["params"][key]
            assert counter.startswith("ops:") or any(
                counter.startswith(p) for p in prefixes), (cell, counter)
    # a program that publishes no `stage.` counters (the parent of PR 27),
    # or a window in which the stage never closed: absent, never 0
    bare = {"ops": observed["ops"], "window_s": 51.0,
            "counters": {"before": {"read.device.keys": 1},
                         "after": {"read.device.keys": 9}}}
    assert read(bare, desc["params"]) is None
    assert read(dict(bare, counters=None), desc["params"]) is None
    if not desc["params"]["den"].startswith("ops:"):
        flat = dict(observed, counters={"before": observed["counters"]["after"],
                                        "after": observed["counters"]["after"]})
        assert read(flat, desc["params"]) is None


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
