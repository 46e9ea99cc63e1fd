"""The yardstick's arithmetic: trace reduction, roofline bytes, zipfian,
percentile, the plain reference on rows worked by hand."""

import json
import os
import random
import time

import numpy as np
import pytest

from benchmarks.lib import datagen, reference, roofline, tracered

HERE = os.path.dirname(os.path.abspath(__file__))


def test_union_merges_overlapping_and_touching_intervals():
    assert tracered.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]


def test_reduce_on_a_hand_made_trace():
    planes = hand_made_planes()
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


def host_doing_slow(start, end, bench, host, stages=()):
    """The reduction's rule as it stood before PR 29, every host event
    walked for every gap: the oracle the index is held to. `stages` (the
    program's `pegasus:` spans) is the one step PR 29 put between."""
    mid = (start + end) / 2
    for spans in (bench, stages):
        over = [e for e in spans if e[1] <= mid < e[1] + e[2]]
        if over:
            return min(over, key=lambda e: e[2])[0]
    best, best_s = "unattributed", 0.0
    for name, s, d in host:
        lap = min(end, s + d) - max(start, s)
        if lap > best_s:
            best, best_s = name, lap
    return best


def reduce_slow(planes, window_s, stages=False):
    """`tracered.reduce` with the oracle in the index's place."""
    class Walk:
        def __init__(self, host):
            self.host = host
            self.bench = [e for e in host if e[0].startswith("bench:")]
            self.stages = [e for e in host if e[0].startswith("pegasus:")
                           ] if stages else []

        def doing(self, gaps):
            return [host_doing_slow(a, b, self.bench, self.host, self.stages)
                    for a, b in gaps]

    index, tracered._HostIndex = tracered._HostIndex, Walk
    try:
        return tracered.reduce(planes, window_s)
    finally:
        tracered._HostIndex = index


def hand_made_planes():
    return [
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


def recorded_planes():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        return json.load(f)


def random_planes(seed, chips=2, threads=6, events=400):
    """Nested host spans on a few threads (some the benchmark's, some the
    program's, some jax's own, some of equal length and start) under
    device operations with gaps between them."""
    rng = random.Random(seed)
    planes = []
    for c in range(chips):
        t, ops = 0.0, []
        for _ in range(events // 4):
            t += rng.choice([0.0, 3.0, 40.0, 900.0])
            d = rng.choice([1.0, 5.0, 60.0])
            ops.append((f"%op.{rng.randrange(5)} = f32[] add()", t, d))
            t += d * rng.choice([0.5, 1.0])
        planes.append({"name": f"/device:TPU:{c}", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": [
                (f"jit_pegasus_k{i % 3}({i})", s, d)
                for i, (_, s, d) in enumerate(ops)]}]})
    lines = []
    for th in range(threads):
        evs = []
        for _ in range(events // threads):
            s = float(rng.randrange(0, 60_000))
            d = float(rng.choice([0, 2, 2, 30, 500, 20_000]))
            kind = rng.choice(["bench:", "pegasus:", "pegasus:", "", "", ""])
            if kind == "bench:" and rng.random() < 0.8:
                kind = ""           # most gaps fall through to the later rules
            evs.append((f"{kind}t{th}.{rng.randrange(8)}", s, d))
        lines.append({"name": f"thread-{th}", "events": evs})
    planes.append({"name": "/host:CPU", "lines": lines})
    return planes


@pytest.mark.parametrize("planes,stages", [
    (hand_made_planes, False), (recorded_planes, False)]
    + [(lambda seed=seed: random_planes(seed), True) for seed in range(12)])
def test_indexed_reduction_equals_the_walk_over_every_event(planes, stages):
    """Every number of `reduce`, idle gaps and their order included, as
    the old quadratic function gives it (with the `pegasus:` step put in,
    where the trace has such spans)."""
    planes = planes()
    got = tracered.reduce(planes, 5.0)
    want = reduce_slow(planes, 5.0, stages)
    assert got == want
    assert got["idle_gaps"]
    assert json.dumps(got, sort_keys=True) == json.dumps(want, sort_keys=True)


def test_a_stage_span_wins_over_a_longer_jax_event_and_bench_over_both():
    def planes(*host):
        return [
            {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
                ("%a = f32[] add()", 0.0, 10.0),
                ("%a = f32[] add()", 110.0, 10.0)]}]},
            {"name": "/host:CPU", "lines": [
                {"name": "t", "events": list(host)}]}]

    jax_event = ("PjitFunction(pegasus_lookup)", 0.0, 200.0)
    outer = ("pegasus:engine.get", 20.0, 90.0)
    inner = ("pegasus:read.device", 40.0, 50.0)     # over the middle, 60
    aside = ("pegasus:read.gather", 70.0, 30.0)     # in the gap, not over 60
    bench = ("bench:window", 5.0, 150.0)
    gap = 100 / 1e9
    assert tracered.reduce(planes(jax_event), 1.0)["idle_gaps"] == [
        ["PjitFunction(pegasus_lookup)", gap]]
    assert tracered.reduce(planes(jax_event, outer, inner, aside), 1.0)[
        "idle_gaps"] == [["pegasus:read.device", gap]]
    assert tracered.reduce(planes(jax_event, aside), 1.0)["idle_gaps"] == [
        ["PjitFunction(pegasus_lookup)", gap]]
    assert tracered.reduce(planes(jax_event, outer, inner, bench), 1.0)[
        "idle_gaps"] == [["bench:window", gap]]
    assert tracered.reduce(planes(), 1.0)["idle_gaps"] == [
        ["unattributed", gap]]


def test_reduce_20000_gaps_over_300000_host_events_in_seconds():
    """A served cell's trace: 80 device lookups a second and every request
    span of 16 clients on the host. The old walk made 6e9 comparisons."""
    rng = random.Random(29)
    ops = [("%lookup = s32[16] fusion()", 1000.0 * i, 70.0)
           for i in range(20_001)]
    lines = []
    for th in range(30):
        evs, t = [], 0.0
        for _ in range(2_500):                      # 4 nested spans each
            d = rng.choice([800.0, 2_000.0, 13_000.0])
            evs += [("pegasus:rpc.server.GET" if th % 3 else "jaxwork",
                     t, d),
                    ("pegasus:engine.get", t + 10, d - 20),
                    ("pegasus:read.batch", t + 20, d - 40),
                    ("PjitFunction(pegasus_lookup)", t + 30, d - 60)]
            t += d + rng.choice([50.0, 4_000.0])
        lines.append({"name": f"thread-{th}", "events": evs})
    lines.append({"name": "main", "events": [("idle wait", 0.0, 2.1e7)]})
    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": lines}]
    assert sum(len(ln["events"]) for ln in lines) > 300_000
    t0 = time.monotonic()
    out = tracered.reduce(planes, 20.0)
    took = time.monotonic() - t0
    assert took < 10.0, took
    assert out["busy_s"] == pytest.approx(20_001 * 70 / 1e9)
    assert sum(s for _, s in out["idle_gaps"]) == pytest.approx(
        20_000 * 930 / 1e9)
    # spot checks against the walk, which takes a second for 40 gaps
    host = [e for ln in lines for e in ln["events"]]
    stages = [e for e in host if e[0].startswith("pegasus:")]
    index = tracered._HostIndex(host)
    gaps = [(1000.0 * i + 70.0, 1000.0 * (i + 1))
            for i in range(0, 20_000, 500)]
    assert index.doing(gaps) == [host_doing_slow(a, b, [], host, stages)
                                 for a, b in gaps]


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
