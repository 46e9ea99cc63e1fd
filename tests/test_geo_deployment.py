"""The geo deployment's keys on the device lanes (ISSUE 30): a run of
51-byte index keys is HBM-resident, its point and range reads equal the
host walk, its merges take no host concat and no per-key rank loop, and
runs of 26-byte keys keep the window and the programs they had. Answers
are decided by benchmarks/lib/reference_geo.py, which knows no cells.
"""

import threading

import numpy as np

from benchmarks.lib import reference_geo as ref
from pegasus_tpu.base import key_schema
from pegasus_tpu.engine.block import KVBlock
from pegasus_tpu.engine.db import EngineOptions, LsmEngine
from pegasus_tpu.engine.server_impl import _ReadCoalescer, _ReadSlot
from pegasus_tpu.geo import GeoClient
from pegasus_tpu.ops import compact as cops
from pegasus_tpu.ops import device_lookup as dl
from pegasus_tpu.ops import packing
from pegasus_tpu.runtime.perf_counters import counters

SEED = 20301
V = b"\x82" + b"\x00" * 12          # v2 value header, no TTL
INDEX_KEY_BYTES = 2 + 6 + 15 + 4 + 16 + 8


def _counter(name: str) -> int:
    return counters.number(name).value()


def _index_keys(n: int, seed: int = SEED, same_place: int = 1):
    """The index table's stored keys for n seeded points, as GeoClient
    makes them; every `same_place` consecutive points share one position,
    so their keys are equal in their first 32 bytes (length, cell, Morton
    code, the owner hashkey's length and its first bytes)."""
    lat, lng = ref.points(seed, n)
    geo = GeoClient(None, None)
    keys = []
    for i in range(n):
        j = i - i % same_place
        hk, sk = ref.owner_key(seed, i)
        ghk, gsk = geo._geo_keys(lat[j], lng[j], hk, sk)
        keys.append(key_schema.generate_key(ghk, gsk))
    return keys


def _run_of(keys, tag: bytes) -> KVBlock:
    """A sorted run with one uniform layout, as an SST is born."""
    return cops.sort_block(KVBlock.from_records(
        [(k, V + tag + b"%08d" % (hash(k) % 10**8), 0, False)
         for k in keys]))


def _prime_all(engine):
    with engine._lock:
        ssts = engine._all_ssts_locked()
    for sst in ssts:
        engine._device_run_budgeted(sst)
    return ssts


# ------------------------------------------- (a) residency and read identity


def test_a_run_of_51_byte_keys_is_resident_and_reads_equal_the_host_walk(
        tmp_path):
    keys = sorted(set(_index_keys(600, same_place=4)))
    assert {len(k) for k in keys} == {INDEX_KEY_BYTES}
    assert len({k[:32] for k in keys}) < len(keys) / 3   # groups of four
    bypass = _counter("engine.hbm.long_key_bypass_count")
    eng = LsmEngine(str(tmp_path / "db"), EngineOptions(
        backend="tpu", device_reads=True, device_read_min_batch=1,
        l0_compaction_trigger=100))
    try:
        for j, k in enumerate(keys):
            if j % 7:                      # every 7th stays absent
                eng.put(k, V + b"v%05d" % j)
        eng.flush()
        eng.manual_compact(now=100)
        ssts = [s for s in _prime_all(eng) if s.n]
        assert ssts and all(s.device_index is not None for s in ssts)
        assert {s.device_index.w for s in ssts} == {13}
        assert _counter("engine.hbm.long_key_bypass_count") == bypass
        sst = max(ssts, key=lambda s: s.n)

        # point reads: present, absent, a 23-byte range bound (shorter
        # than any window), and keys longer than the 64-byte cap
        probe = keys[::3] + [k[:23] for k in keys[:20]] \
            + [k + b"x" * 30 for k in keys[:20]]
        looked = _counter("read.device.keys")
        assert eng.get_batch(probe, now=100) == [eng.get(k, now=100)
                                                 for k in probe]
        assert _counter("read.device.keys") > looked
        probe += [b"", b"\xff" * 80]        # no stored key: the kernel alone
        rows = dl.lookup_batch(sst.device_index, probe)
        assert [int(r) for r in rows] == [sst.find(k) for k in probe]

        # ranges: a search's own bounds (23 B), present and absent whole
        # keys, bounds over the cap, open, inverted, empty
        block = sst.block()
        k = [block.key(i) for i in range(block.n)]
        ranges = [(k[5][:23], k[40][:23]), (k[3], k[-3]),
                  (keys[0], keys[7]),
                  (k[9] + b"\x00", k[30] + b"z" * 40),
                  (k[2] + b"y" * 40, None), (b"", k[11][:23]),
                  (k[-1] + b"\xff", None), (k[50], k[4]), (k[8], k[8]),
                  (b"", None)]
        iv = dl.range_batch(sst.device_index, ranges)
        for (start, stop), (lo, hi) in zip(ranges, iv):
            want_lo = sst.lower_bound(start)
            want_hi = sst.n if stop is None else sst.lower_bound(stop)
            assert (int(lo), int(hi)) == (want_lo, max(want_hi, want_lo)), \
                (start, stop)
        dev, host = (_counter("read.range.device_ranges"),
                     _counter("read.range.host_ranges"))
        got = [list(it) for it in eng.scan_range_batch(ranges[:6], now=100)]
        assert got == [list(eng.scan(s, t, now=100)) for s, t in ranges[:6]]
        assert _counter("read.range.device_ranges") > dev
        # the six plain scans of the comparison walked the host
        assert _counter("read.range.host_ranges") > host
    finally:
        eng.close()


def test_a_key_over_the_cap_still_takes_the_rank_path_and_is_counted():
    bypass = _counter("engine.hbm.long_key_bypass_count")
    keys = [key_schema.generate_key(b"h" * 40, b"s%030d" % i)
            for i in range(50)]
    run = _run_of(keys, b"a")
    assert int(run.key_len.max()) > 4 * packing.DEFAULT_PREFIX_U32
    assert cops.pack_run_device(run) is None
    assert _counter("engine.hbm.long_key_bypass_count") == bypass + 1
    packed = cops.pack_runs([run], cops.CompactOptions(), need_sbytes=False)
    assert packed.has_rank and packed.w == packing.DEFAULT_PREFIX_U32


# --------------------------------------------------------- (b) the merge


def test_b_merge_of_four_51_byte_runs_is_byte_identical_and_copies_nothing(
        monkeypatch):
    keys = _index_keys(2000, same_place=4)
    rng = np.random.default_rng(SEED)
    runs = []
    for r in range(4):                      # overlapping: dedup has work
        take = rng.choice(len(keys), 800, replace=False)
        runs.append(_run_of(sorted({keys[i] for i in take}), b"r%d" % r))
    host = cops.compact_blocks(runs, cops.CompactOptions(
        backend="cpu", now=100, runs_sorted=True)).block

    def refuse(*a, **kw):
        raise AssertionError("a merge of keys within the cap took the "
                             "host's whole-table path")

    monkeypatch.setattr(KVBlock, "concat", refuse)
    monkeypatch.setattr(cops, "compute_suffix_ranks", refuse)
    concats = _counter("stage.concat.n")
    by_run = _counter("compact.gather.by_run_count")
    opts = cops.CompactOptions(backend="tpu", now=100, runs_sorted=True)
    device_runs = [cops.pack_run_device(b) for b in runs]
    assert all(d is not None and d.w == 13 for d in device_runs)
    for out in (cops.compact_blocks(runs, opts).block,            # host-packed
                cops.compact_blocks(runs, opts,
                                    device_runs=device_runs).block):
        assert out.n == host.n
        for name in ("key_arena", "val_arena", "key_len", "val_len",
                     "expire_ts", "hash32", "deleted"):
            np.testing.assert_array_equal(getattr(out, name),
                                          getattr(host, name))
    assert _counter("stage.concat.n") == concats
    assert _counter("compact.gather.by_run_count") == by_run + 2


# ----------------------------------------------------- (c) served searches


def _cluster(root):
    """A MiniCluster of tpu-backend engines whose every range takes the
    device lane (min batch 1)."""
    from tests.test_satellites import MiniCluster

    return MiniCluster(root, options_factory=lambda: EngineOptions(
        backend="tpu", device_read_min_batch=1))


def _table(c, name: str):
    """Create a table -> a client on it. The first range of a new (run
    shape, bucket) waits for its kernel here (tests/conftest.py), longer
    than a client's default timeout."""
    from pegasus_tpu.client import MetaResolver, PegasusClient

    c.create(name, partitions=4).close()
    return PegasusClient(MetaResolver([c.meta_addr], name), timeout=300.0)


def test_c_fifty_served_searches_equal_the_plain_reference(tmp_path):
    n = 4000
    want = ref.Reference(SEED, n)
    lat, lng = want.lat, want.lng
    bypass = _counter("engine.hbm.long_key_bypass_count")
    c = _cluster(tmp_path)
    try:
        common, index = _table(c, "geo_common"), _table(c, "geo_index")
        geo = GeoClient(common, index, min_level=12, max_level=16)
        by_hk = {}
        for i in range(n):
            hk, sk = ref.owner_key(SEED, i)
            value = want.value(i)
            by_hk.setdefault(("c", hk), {})[sk] = value
            ghk, gsk = geo._geo_keys(lat[i], lng[i], hk, sk)
            by_hk.setdefault(("i", ghk), {})[gsk] = value
        for (table, hk), kvs in by_hk.items():
            items = sorted(kvs.items())
            for a in range(0, len(items), 200):
                (common if table == "c" else index).multi_set(
                    hk, dict(items[a:a + 200]))
        for stub in c.stubs:
            for rep in list(stub._replicas.values()):
                rep.server.engine.manual_compact(now=100)
        assert _counter("engine.hbm.long_key_bypass_count") == bypass
        dev = _counter("read.range.device_ranges")
        rng = np.random.default_rng([SEED, 50])
        for q in range(50):
            clat = rng.uniform(*ref.RECT["lat"])
            clng = rng.uniform(*ref.RECT["lng"])
            radius = (500.0, 2000.0)[q % 2]
            rows = geo.search_radial(clat, clng, radius, count=-1,
                                     sort_by_distance=False)
            assert want.judge(clat, clng, radius,
                              [(hk, sk, v) for _, hk, sk, v in rows]), q
            if q % 2:
                assert rows, "a 2 km circle here holds some twenty points"
        assert _counter("read.range.device_ranges") > dev
        assert _counter("engine.hbm.long_key_bypass_count") == bypass
        # the judge itself: one point dropped or altered is a wrong answer
        rows = [(hk, sk, v) for _, hk, sk, v in geo.search_radial(
            clat, clng, 2000.0, count=-1, sort_by_distance=False)]
        assert want.judge(clat, clng, 2000.0, rows)
        assert not want.judge(clat, clng, 2000.0, rows[1:])
        hk, sk, v = rows[0]
        assert not want.judge(clat, clng, 2000.0,
                              [(hk, sk, v[:-1] + b"!")] + rows[1:])
        geo.close()
        common.close()
        index.close()
    finally:
        c.stop()


# ------------------------------------- (d) short keys keep what they had


def test_d_runs_of_26_byte_keys_keep_their_window_and_programs(monkeypatch):
    keys = sorted({key_schema.generate_key(*ref.owner_key(SEED, i))
                   for i in range(500)})
    assert {len(k) for k in keys} == {26}
    runs = [_run_of(keys[r::2], b"r%d" % r) for r in range(2)]
    assert packing.window_lanes(26) == 7 and packing.window_lanes(32) == 8
    assert packing.window_lanes(51) == 13 and packing.window_lanes(200) == 16
    seen = []

    def recording(name):
        real = getattr(cops if name.startswith("_compiled_pipeline")
                       else dl, name)

        def wrapped(*key):
            seen.append((name,) + key)
            return real(*key)

        monkeypatch.setattr(cops if name.startswith("_compiled_pipeline")
                            else dl, name, wrapped)

    for name in ("_compiled_pipeline_cached", "_compiled_lookup",
                 "_compiled_range"):
        recording(name)
    device_runs = [cops.pack_run_device(b) for b in runs]
    assert [d.w for d in device_runs] == [7, 7]
    cops.compact_blocks(runs, cops.CompactOptions(
        backend="tpu", now=100, runs_sorted=True), device_runs=device_runs)
    dl.lookup_batch(device_runs[0], keys[:3])
    dl.range_batch(device_runs[0], [(keys[0], keys[9])])
    # the keys these programs were cached under before the cap moved:
    # (padded run lengths, run widths, w) and (padded, w, fence, bucket)
    # (a guarded call that waited for its compile asks once more)
    assert list(dict.fromkeys(seen)) == [
        ("_compiled_pipeline_cached", (256, 256), (7, 7), 7),
        ("_compiled_lookup", 256, 7, 32, 8),
        ("_compiled_range", 256, 7, 32, 8)]


# ------------------------------------------- the coalescers' shared loop


def test_join_many_survives_a_slot_served_between_its_two_looks():
    """PERF.md §7.2 (PR 29): `all(s.done ...)` and then `next(s for s in
    slots if not s.done)` raised StopIteration into a GET answer when the
    slot was served between the two. The slot here reads as pending once
    and as served from then on: exactly that moment."""

    class Engine:
        def _device_reads_on(self):
            return True

        def get_batch(self, keys, now=None):
            return [b"v:" + k for k in keys]

    class Slot(_ReadSlot):
        __slots__ = ("looks", "_done")

        @property
        def done(self):
            self.looks += 1
            return self.looks > 1

        @done.setter
        def done(self, v):
            self._done = v

    co = _ReadCoalescer(Engine(), max_batch=8)
    slot = Slot.__new__(Slot)
    slot.key, slot.now, slot.looks = b"k", 0, 0
    slot.event = threading.Event()
    slot.value = slot.err = None
    co._join_many([slot])               # the old loop raised StopIteration
    assert slot.looks >= 1
    assert co.get(b"key", 0) == b"v:key"
