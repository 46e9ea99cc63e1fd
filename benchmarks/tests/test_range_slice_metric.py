"""`engine.range_slice_share`: of the ranges the engine's merged-scan
generator served, the share, in %, that left as slices of their one SST's
block rather than through the k-way heap merge. Read by the reader that is
there (`counter_share`) from two windowed `number` counters. A program
that publishes neither counter reads nothing, never 0."""

import os

import pytest

from benchmarks.run import applies, load_json, load_module

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = "engine.range_slice_share"
CELL = "geo1m.radial500"
SLICE, MERGED = "read.range.slice_ranges", "read.range.merged_ranges"


@pytest.fixture(scope="module")
def entry():
    return next(m for m in load_json(ROOT, "BENCHMARK.json")["per_layer"]
                if m["name"] == NAME)


def read(observed: dict):
    desc = load_json(ROOT, "benchmarks", "metrics", NAME + ".json")
    assert desc["name"] == NAME and desc["reader"] == "counter_share"
    return load_module("readers", desc["reader"]).read(
        observed, desc.get("params", {}))


def test_the_entry_is_the_geo_cells_engine_metric(entry):
    manifest = load_json(ROOT, "BENCHMARK.json")
    assert entry == {"name": NAME, "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "engine",
                     "moves": "ycsb_ops", "workloads": [CELL]}
    assert applies(entry, CELL) and not applies(entry, "ycsb1kb.c")
    # the cell reports the end-to-end metric this one moves, and asks the
    # server for the counters it reads
    moved = next(m for m in manifest["end_to_end"]
                 if m["name"] == entry["moves"])
    assert CELL in moved["workloads"]
    prefixes = load_json(ROOT, "benchmarks", "workloads",
                         CELL + ".json")["counters"]
    assert all(any(c.startswith(p) for p in prefixes)
               for c in (SLICE, MERGED))


def test_it_reads_the_windowed_share_of_sliced_ranges():
    # 9,000 ranges in the window, 8,550 of them sliced; what the counters
    # held before it (the load's audit, the warm-up) must not show
    observed = {"ops": {"read": 2_200}, "window_s": 51.0, "counters": {
        "before": {SLICE: 40_000, MERGED: 7_000},
        "after": {SLICE: 48_550, MERGED: 7_450}}}
    assert read(observed) == pytest.approx(95.0)


@pytest.mark.parametrize("counters", [
    None,                                               # no scrape at all
    {"before": {}, "after": {}},                        # the parent: no
    {"before": {"read.range.rows": 1},                  # such counters
     "after": {"read.range.rows": 9}},
    {"before": {SLICE: 5}, "after": {SLICE: 9}},        # one of the two
    {"before": {SLICE: 5, MERGED: 2},                   # no range served
     "after": {SLICE: 5, MERGED: 2}}])                  # in the window
def test_a_program_without_both_counters_reads_nothing(counters):
    observed = {"ops": {"read": 100}, "window_s": 51.0, "counters": counters}
    assert read(observed) is None
