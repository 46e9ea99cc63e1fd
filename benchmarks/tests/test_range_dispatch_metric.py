"""`lane.range_dispatch_ms` (PR 31): the host wall of one
`read.range.dispatch`, the part of a range call that uploads the packed
bounds and launches `pegasus_range`, read by the reader that is there
(`counter_ratio`) from the span's windowed `stage.` totals. The parent of
PR 31 closes that span too, so both sides of a comparison have a number;
a program that never closed it reads nothing, never 0."""

import os

import pytest

from benchmarks.run import applies, load_json, load_module

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = "lane.range_dispatch_ms"
CELL = "geo1m.radial500"


@pytest.fixture(scope="module")
def entry():
    return next(m for m in load_json(ROOT, "BENCHMARK.json")["per_layer"]
                if m["name"] == NAME)


def read(observed: dict):
    desc = load_json(ROOT, "benchmarks", "metrics", NAME + ".json")
    assert desc["name"] == NAME and desc["reader"] == "counter_ratio"
    return load_module("readers", desc["reader"]).read(
        observed, desc.get("params", {}))


def test_the_entry_is_the_last_of_the_geo_cells_lane_metrics(entry):
    manifest = load_json(ROOT, "BENCHMARK.json")
    assert manifest["per_layer"][-1]["name"] == NAME      # appended
    assert entry == {"name": NAME, "unit": "ms", "better": "lower",
                     "source": "program_span", "layer": "device lanes",
                     "moves": "read_p95", "workloads": [CELL]}
    assert applies(entry, CELL) and not applies(entry, "ycsb1kb.c")
    # the cell reports the end-to-end metric this one moves, and asks the
    # server for the counters it reads
    moved = next(m for m in manifest["end_to_end"]
                 if m["name"] == entry["moves"])
    assert CELL in moved["workloads"]
    prefixes = load_json(ROOT, "benchmarks", "workloads",
                         CELL + ".json")["counters"]
    assert any("stage.read.range.dispatch.us".startswith(p)
               for p in prefixes)


def test_it_reads_us_over_n_of_the_window_in_ms():
    # 1,500 dispatches took 9.6 s of host wall inside the window; what the
    # counters held before it (the warm-up's calls) must not show
    observed = {"ops": {"read": 2_200}, "window_s": 51.0, "counters": {
        "before": {"stage.read.range.dispatch.us": 4_000_000,
                   "stage.read.range.dispatch.n": 300,
                   "stage.read.range.us": 5_000_000,
                   "stage.read.range.n": 300},
        "after": {"stage.read.range.dispatch.us": 13_600_000,
                  "stage.read.range.dispatch.n": 1_800,
                  "stage.read.range.us": 20_000_000,
                  "stage.read.range.n": 1_800}}}
    assert read(observed) == pytest.approx(6.4)


@pytest.mark.parametrize("counters", [
    None,                                               # no scrape at all
    {"before": {}, "after": {}},                        # no such counter
    {"before": {"stage.read.range.us": 1, "stage.read.range.n": 1},
     "after": {"stage.read.range.us": 9, "stage.read.range.n": 2}},
    {"before": {"stage.read.range.dispatch.us": 7,      # never closed in
                "stage.read.range.dispatch.n": 3},      # the window
     "after": {"stage.read.range.dispatch.us": 7,
               "stage.read.range.dispatch.n": 3}}])
def test_a_span_that_never_closed_reads_nothing(counters):
    observed = {"ops": {"read": 100}, "window_s": 51.0, "counters": counters}
    assert read(observed) is None
