"""The readers and the bytes function that came with `geo1m.radial500`:
each of the cell's thirteen per-layer metrics reads what a run observed,
and reads NOTHING (no zero, no error) from a program that publishes no
such counter or program name, as the parent of PR 30 does."""

import json
import os

import pytest

from benchmarks.lib import roofline_range
from benchmarks.run import applies, load_json, load_module

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "geo1m.radial500"


def observed(with_new: bool) -> dict:
    before = {"stage.read.range.us": 1000, "stage.read.range.n": 10,
              "read.range.rows": 500, "read.range.dispatch_count": 10,
              "stage.read.range.coalesce_wait.us": 0,
              "stage.rpc.queue.us": 0, "stage.rpc.queue.n": 0,
              "stage.rpc.reply.us": 0, "stage.rpc.reply.n": 0,
              "stage.rpc.server.RPC_RRDB_RRDB_GET_SCANNER.us": 0,
              "stage.rpc.server.RPC_RRDB_RRDB_GET_SCANNER.n": 0}
    after = {"stage.read.range.us": 13000, "stage.read.range.n": 110,
             "read.range.rows": 270500, "read.range.dispatch_count": 110,
             "stage.read.range.coalesce_wait.us": 5_000_000,
             "stage.rpc.queue.us": 800_000, "stage.rpc.queue.n": 1000,
             "stage.rpc.reply.us": 900_000, "stage.rpc.reply.n": 1000,
             "stage.rpc.server.RPC_RRDB_RRDB_GET_SCANNER.us": 16_000_000,
             "stage.rpc.server.RPC_RRDB_RRDB_GET_SCANNER.n": 400}
    if with_new:
        before.update({"read.range.device_ranges": 40,
                       "read.range.host_ranges": 10})
        after.update({"read.range.device_ranges": 440,
                      "read.range.host_ranges": 110})
    programs = {"pegasus_range": {"count": 20, "total_s": 0.002}} \
        if with_new else {"pegasus_lookup": {"count": 3, "total_s": 0.0002}}
    return {"ops": {"read": 100}, "window_s": 51.0,
            "trace": {"programs": programs, "busy_s": 0.002,
                      "window_s": 10.0},
            "client_tails": {"read_p99": 900.0},
            "counters": {"before": before, "after": after}, "rates": {},
            "peaks": {"hbm_bytes_per_s": 819e9},
            "range_shapes": {"rows": 250_000, "key_bytes": 51},
            "clients": {"cpu_s": 20.0, "window_s": 51.0, "processes": 4}}


def read_all(obs: dict) -> dict:
    out = {}
    for m in load_json(ROOT, "BENCHMARK.json")["per_layer"]:
        if not applies(m, CELL) or "workloads" not in m:
            continue
        desc = load_json(ROOT, "benchmarks", "metrics", m["name"] + ".json")
        value = load_module("readers", desc["reader"]).read(
            obs, desc.get("params", {}))
        if value is not None:
            out[m["name"]] = value
    return out


def test_every_metric_of_the_cell_reads_what_a_run_observed():
    got = read_all(observed(True))
    assert got["engine.device_range_share"] == pytest.approx(80.0)
    assert got["lane.range_batch_ranges"] == pytest.approx(4.0)
    assert got["lane.range_device_call_ms"] == pytest.approx(0.12)
    assert got["lane.range_coalesce_wait_per_search_ms"] == pytest.approx(50.0)
    assert got["engine.scan_rows_per_search"] == pytest.approx(2700.0)
    assert got["rpc.scan_handler_ms"] == pytest.approx(40.0)
    assert got["kernel.range_ms"] == pytest.approx(0.1)
    least = 20 * roofline_range.range_least_bytes(4.0, 250_000, 51)
    assert got["kernel.range_roofline"] == pytest.approx(
        100.0 * least / 819e9 / 0.002)
    assert 0 < got["kernel.range_roofline"] < 1
    assert {"client.cpu_share", "client.read_p99", "rpc.queue_ms",
            "rpc.reply_ms", "device.idle_share.serve"} <= set(got)
    assert len(got) == 13


def test_a_program_without_the_new_counters_leaves_the_metrics_out():
    got = read_all(observed(False))
    for name in ("engine.device_range_share", "lane.range_batch_ranges",
                 "kernel.range_ms", "kernel.range_roofline"):
        assert name not in got
    # what the parent does publish still reads
    assert got["lane.range_device_call_ms"] == pytest.approx(0.12)
    assert got["engine.scan_rows_per_search"] == pytest.approx(2700.0)


def test_range_least_bytes_counts_two_lower_bounds_a_range():
    # 250,000 rows: 4,096 fence samples 62 rows apart; a row is 13 lanes + 4
    one = roofline_range.range_least_bytes(1, 250_000, 51)
    assert one == 2 * (12 * 4 + 6 * 56) + 2 * 56 + 8
    assert roofline_range.range_least_bytes(4, 250_000, 51) == 4 * one
    # a short run: 16 samples at the least, a 26-byte key is 7 lanes + 4
    assert roofline_range.range_least_bytes(1, 100, 26) == \
        2 * (4 * 4 + 3 * 32) + 2 * 32 + 8
    with open(os.path.join(ROOT, "benchmarks", "metrics",
                           "kernel.range_roofline.json")) as f:
        assert json.load(f)["params"]["prefix"] == "pegasus_range"
