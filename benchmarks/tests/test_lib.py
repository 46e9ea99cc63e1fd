"""The yardstick's arithmetic: trace reduction, roofline bytes, zipfian,
percentile, the plain reference on rows worked by hand."""

import json
import os

import numpy as np

from benchmarks.lib import datagen, reference, roofline, tracered

HERE = os.path.dirname(os.path.abspath(__file__))


def test_union_merges_overlapping_and_touching_intervals():
    assert tracered.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]


def test_reduce_on_a_hand_made_trace():
    planes = [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                ("%fusion.1 = s32[8] fusion(...)", 100.0, 50.0),
                ("%sort = (s32[8]) sort(...)", 120.0, 80.0),      # overlaps
                ("%fusion.1 = s32[8] fusion(...)", 1000.0, 100.0)]},
            {"name": "XLA Modules", "events": [
                ("jit_pegasus_merge_cached(123)", 100.0, 100.0),
                ("jit_pegasus_lookup(9)", 1000.0, 100.0)]},
            {"name": "Steps", "events": [("0", 0.0, 5000.0)]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": [
                ("bench:step", 0.0, 2000.0),
                ("bench:ingest", 150.0, 700.0)]}]},
    ]
    out = tracered.reduce(planes, window_s=2e-6)
    assert out["busy_s"] == (100 + 100) / 1e9       # union, not sum
    assert out["programs"] == {
        "pegasus_merge_cached": {"count": 1, "total_s": 100 / 1e9},
        "pegasus_lookup": {"count": 1, "total_s": 100 / 1e9}}
    assert out["device_ops"][0] == ["fusion.1", 150 / 1e9]
    # the one idle gap, 200..1000, lies under the innermost bench span
    assert out["idle_gaps"] == [["bench:ingest", 800 / 1e9]]
    assert out["spans"] == {"step": 2000 / 1e9, "ingest": 700 / 1e9}


def test_reduce_finds_nothing_without_a_device():
    out = tracered.reduce([{"name": "/host:CPU", "lines": []}], 1.0)
    assert out["busy_s"] is None and out["programs"] == {}


def test_reduce_on_the_recorded_chip_trace():
    """A small piece of a real v5e trace (the earliest 300 events of each
    line of a traced ycsb1kb.a run): the reduction must agree with the
    same numbers worked out here the slow way."""
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        planes = json.load(f)
    out = tracered.reduce(planes, window_s=5.0)
    dev = [p for p in planes if p["name"].startswith("/device:TPU:")]
    assert len(dev) == 1
    lines = {ln["name"]: ln["events"] for ln in dev[0]["lines"]}
    ticks = set()
    for _, s, d in lines["XLA Ops"]:
        ticks.update(range(int(s), int(s + d)))       # 1 ns resolution
    assert abs(out["busy_s"] - len(ticks) / 1e9) < 1e-6 * max(1, len(ticks)) / 1e3
    total = sum(d for _, _, d in lines["XLA Modules"]) / 1e9
    assert abs(sum(p["total_s"] for p in out["programs"].values())
               - total) < 1e-12
    assert any(name.startswith("pegasus_") for name in out["programs"])
    assert 0 < out["busy_s"] < 5.0


def test_merge_least_bytes_against_hand_worked_shapes():
    # 26 B keys are 7 four-byte lanes: 28 + 4 (length) + 4 (expire) + 1
    # (tombstone) = 37 B a row in, 4 B a survivor out
    assert roofline.merge_least_bytes(1000, 600, 26) == 1000 * 37 + 600 * 4
    assert roofline.merge_least_bytes(10, 10, 8) == 10 * (8 + 9) + 40
    assert roofline.merge_least_bytes(4, 0, 1) == 4 * (4 + 9)


def test_scrambled_zipfian_head_mass():
    z = datagen.ZipfKeys(1_000_000, 0.99)
    ranks = z.ranks(np.random.default_rng(1), 400_000)
    # zipf 0.99 over 1M: rank 0 has 1/zeta = 6.5 % of the mass, the first
    # 10 ranks 19 %, the first 1,000 half of it (not the 91 % on rank 0
    # the continuous inverse transform gives)
    assert abs((ranks == 0).mean() - 1 / z.zetan) < 0.005
    assert 0.17 < (ranks < 10).mean() < 0.22
    assert 0.48 < (ranks < 1000).mean() < 0.54
    assert ranks.min() == 0 and ranks.max() < 1_000_000
    recs = z.scrambled(np.random.default_rng(1), 400_000)
    top = np.bincount(recs, minlength=1_000_000)
    hot = np.argsort(top)[::-1][:10]
    # the same head mass, spread over records that are not neighbours
    assert 0.17 < top[hot].sum() / len(recs) < 0.22
    assert np.abs(np.diff(np.sort(hot))).min() > 1


def test_percentile_is_nearest_rank_over_all_values():
    v = list(range(1, 101))
    assert reference.percentile(v, 99) == 99
    assert reference.percentile(v, 50) == 50
    assert reference.percentile([7.0], 99) == 7.0
    assert reference.percentile([], 99) is None


def test_reference_compact_on_rows_worked_by_hand():
    def run(rows):
        return {"keys": np.array([list(k) for k, *_ in rows], np.uint8),
                "vals": np.array([[v] for _, v, *_ in rows], np.uint8),
                "expire": np.array([e for *_, e, _ in rows], np.uint32),
                "deleted": np.array([d for *_, d in rows], bool)}

    old = run([(b"aa", 1, 0, False), (b"ab", 2, 0, False),
               (b"ba", 3, 0, False), (b"bb", 4, 0, False)])
    new = run([(b"aa", 9, 0, False),      # newer version wins
               (b"ab", 0, 0, True),       # tombstone hides the old one
               (b"bb", 8, 50, False),     # newer, but expired at now=100
               (b"ca", 7, 200, False)])   # TTL still ahead
    out = reference.compact([old, new], now=100)
    assert [bytes(k) for k in out["keys"]] == [b"aa", b"ba", b"ca"]
    assert out["vals"].reshape(-1).tolist() == [9, 3, 7]
    assert out["input_records"] == 8
    oldest = reference.compact([old, new], now=100, keep="oldest")
    assert [bytes(k) for k in oldest["keys"]] == [b"aa", b"ab", b"ba", b"bb",
                                                  b"ca"]
    assert reference.point_answers(
        [old, new], 100, np.array([list(b"aa"), list(b"ab"), list(b"bb"),
                                   list(b"zz")], np.uint8)
    ) == [b"\x09", None, None, None]


def test_values_describe_themselves():
    v = datagen.make_value(3, 77, 2, 5, 1000)
    assert len(v) == 1000
    assert datagen.check_value(3, 77, v, 1000) == (2, 5)
    assert datagen.check_value(3, 78, v, 1000) is None       # another record's
    assert datagen.check_value(4, 77, v, 1000) is None       # another seed's
    assert datagen.check_value(3, 77, v[:-1] + b"\x00", 1000) is None
    assert datagen.check_value(3, 77, None, 1000) is None
