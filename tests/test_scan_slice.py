"""A range that one SST alone answers leaves the engine's merged-scan
generator as slices of that SST's block; two or more non-empty sources go
through the k-way heap merge. Both must yield exactly what a plain
newest-wins dict of the same writes says, through every entry point
(`engine.scan`, `scan_range_batch`, `_scan_over` with batch-resolved
bounds), forward and reverse, with and without deleted rows."""

import itertools

import pytest

from pegasus_tpu.base.key_schema import generate_key, key_hash
from pegasus_tpu.engine import db as engine_db
from pegasus_tpu.engine import EngineOptions, LsmEngine
from pegasus_tpu.runtime.perf_counters import counters

NOW = 1000
HK = b"ha"
ROWS = 1300


def k(i, hk=HK) -> bytes:
    return generate_key(hk, b"s%05d" % i)


def h32(hk: bytes) -> int:
    return key_hash(generate_key(hk, b"")) & 0xFFFFFFFF


def reference(log, start, stop, now, include_deleted, reverse) -> list:
    """Newest-wins over a dict of the same writes, in key order."""
    state = {}
    for op in log:
        state[op[1]] = ((b"", 0, True) if op[0] == "del"
                        else (op[2], op[3], False))
    rows = []
    for key in sorted(state):
        if key < start or (stop is not None and key >= stop):
            continue
        v, e, d = state[key]
        if not include_deleted and (d or 0 < e <= now):
            continue
        rows.append((key, v, e))
    return rows[::-1] if reverse else rows


def _puts(lo, hi, tag, hk=HK, dead=False):
    """Puts of rows [lo, hi); with `dead` every 7th row is then deleted
    and the expire_ts cycles 0, NOW (expired), NOW + 1."""
    ops = []
    for i in range(lo, hi):
        e = (0, NOW, NOW + 1)[i % 3] if dead else 0
        ops.append(("put", k(i, hk), b"%s-%05d" % (tag, i) + b"x" * (i % 13), e))
    if dead:
        ops += [("del", k(i, hk)) for i in range(lo, hi, 7)]
    return ops


# each scenario: a list of write phases; "flush" ends an SST
SCENARIOS = {
    "one_sst": [_puts(0, ROWS, b"a"), "flush"],
    "one_sst_tombstones_ttl": [_puts(0, ROWS, b"a", dead=True), "flush"],
    "sst_plus_memtable_in_range": [
        _puts(0, ROWS, b"a"), "flush",
        _puts(150, 170, b"m") + [("del", k(i)) for i in range(400, 420)]
        + [("put", k(i) + b"+", b"new", NOW + 1) for i in range(600, 610)]],
    "sst_plus_memtable_out_of_range": [
        _puts(0, ROWS, b"a"), "flush", _puts(0, 50, b"m", hk=b"hz")],
    "two_overlapping_ssts": [
        _puts(0, ROWS, b"a", dead=True), "flush",
        _puts(0, ROWS, b"b")[::2] + [("del", k(i)) for i in range(5, ROWS, 9)],
        "flush"],
    "two_ssts_one_in_range": [
        _puts(0, ROWS, b"a", dead=True), "flush",
        _puts(0, 400, b"b", hk=b"hb"), "flush"],
}

# (start, stop, hash32): names say what the range exercises
RANGES = {
    "hashkey": (generate_key(HK, b""), generate_key(b"hb", b""), h32(HK)),
    "longer_than_a_chunk": (k(100), k(1100), h32(HK)),
    "scanner_612": (k(200), k(812), h32(HK)),
    "open_stop": (k(1000), None, None),
    "empty_start_is_stop": (k(100), k(100), h32(HK)),
    "empty_between_keys": (k(100) + b"x", k(100) + b"y", h32(HK)),
    "whole_table": (b"", None, None),
}


@pytest.fixture(scope="module")
def engines(tmp_path_factory):
    built = {}
    for name, phases in SCENARIOS.items():
        eng = LsmEngine(str(tmp_path_factory.mktemp(name)),
                        EngineOptions(backend="cpu"))
        log = []
        for phase in phases:
            if phase == "flush":
                eng.flush()
                continue
            for op in phase:
                if op[0] == "del":
                    eng.delete(op[1])
                else:
                    eng.put(op[1], op[2], expire_ts=op[3])
                log.append(op)
        built[name] = (eng, log)
    yield built
    for eng, _ in built.values():
        eng.close()


def _rows(eng, entry, start, stop, hash32, include_deleted, reverse):
    if entry == "scan":
        it = eng.scan(start, stop, now=NOW, include_deleted=include_deleted,
                      reverse=reverse, hash32=hash32)
    elif entry == "batch":
        it = eng.scan_range_batch([(start, stop)], now=NOW, reverse=reverse,
                                  hash32s=[hash32])[0]
    else:  # the bounds a range batch resolved, handed to the generator
        snap = eng._scan_snapshot()
        bounds = eng._resolve_sst_bounds(snap[2], [(start, stop)], [hash32],
                                         False)[0]
        it = eng._scan_over(snap, start, stop, NOW, include_deleted, reverse,
                            hash32, sst_bounds=bounds)
    return it


def _count(name: str) -> int:
    return counters.number(name).value()


def _modes():
    for entry in ("scan", "batch", "bounds"):
        for reverse, include_deleted in itertools.product((False, True),
                                                          (False, True)):
            if entry == "batch" and include_deleted:
                continue  # scan_range_batch never yields deleted rows
            yield entry, reverse, include_deleted


def _path(scenario, rng):
    """Which way the generator should serve the range: None where no
    source holds a row of it."""
    if rng.startswith("empty"):
        return None
    tail = rng in ("open_stop", "whole_table")  # reaches keys past HK's
    several = (scenario == "two_overlapping_ssts"
               or (scenario == "sst_plus_memtable_in_range"
                   and rng != "open_stop")
               or (scenario in ("sst_plus_memtable_out_of_range",
                                "two_ssts_one_in_range") and tail))
    return "merged" if several else "slice"


@pytest.mark.parametrize("rng", sorted(RANGES))
@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_rows_match_a_newest_wins_dict(engines, scenario, rng):
    eng, log = engines[scenario]
    start, stop, hash32 = RANGES[rng]
    for entry, reverse, include_deleted in _modes():
        before = {p: _count(f"read.range.{p}_ranges")
                  for p in ("slice", "merged")}
        got = list(_rows(eng, entry, start, stop, hash32, include_deleted,
                         reverse))
        want = reference(log, start, stop, NOW, include_deleted, reverse)
        assert got == want, (entry, reverse, include_deleted)
        for key, value, expire_ts in got:
            assert type(key) is bytes and type(value) is bytes
            assert type(expire_ts) is int
        moved = {p: _count(f"read.range.{p}_ranges") - before[p]
                 for p in ("slice", "merged")}
        path = _path(scenario, rng)
        assert moved == {p: int(p == path) for p in moved}, (entry, moved)


def test_the_long_ranges_span_several_chunks(engines):
    eng, _ = engines["one_sst"]
    for rng in ("hashkey", "longer_than_a_chunk"):
        start, stop, hash32 = RANGES[rng]
        rows = list(eng.scan(start, stop, now=NOW, hash32=hash32))
        assert len(rows) > 1.5 * engine_db._SLICE_ROWS, rng


@pytest.mark.parametrize("entry", ["scan", "batch", "bounds"])
@pytest.mark.parametrize("scenario", ["one_sst", "one_sst_tombstones_ttl",
                                      "two_overlapping_ssts"])
def test_a_consumer_that_stops_mid_chunk_resumes_where_it_stopped(
        engines, scenario, entry):
    # the scanner's 500-row batch, then the 112 that are left
    eng, log = engines[scenario]
    start, stop, hash32 = RANGES["scanner_612"]
    it = iter(_rows(eng, entry, start, stop, hash32, False, False))
    first = list(itertools.islice(it, 500))
    rest = list(itertools.islice(it, 112))
    assert next(it, None) is None
    assert first + rest == reference(log, start, stop, NOW, False, False)


def _overwrites(eng):
    """Every key of rows [0, 300) written three times, a third of them
    then deleted: a memtable that saw each key more than once."""
    for rnd in range(3):
        for i in range(300):
            eng.put(k(i), b"r%d-%05d" % (rnd, i))
    for i in range(0, 300, 3):
        eng.delete(k(i))


def _ingest(eng, root):
    """A bulk-load set of two files that share keys, one of which holds a
    key twice, ingested as partition 0 of 1."""
    from pegasus_tpu.base.value_schema import SCHEMAS
    from pegasus_tpu.engine import bulk_load

    pdir = root / "prov" / "t" / "1" / "0"
    pdir.mkdir(parents=True)
    bulk_load.write_raw_set(str(pdir / "a.raw"), [
        (HK, b"s%05d" % i, b"a%d" % i, 0) for i in range(200)]
        + [(HK, b"s00007", b"again", 0)])
    bulk_load.write_raw_set(str(pdir / "b.raw"), [
        (HK, b"s%05d" % i, b"b%d" % i, 0) for i in range(100, 300)])
    bulk_load.ingest_partition(eng, str(root / "prov"), "t", 1, 0, SCHEMAS[2])


# every way an engine makes an SST: a memtable flush, a compaction (the
# cpu backend's compact_blocks), a bulk-load ingest
MAKERS = {
    "flush": lambda eng, root: (_overwrites(eng), eng.flush()),
    "compact": lambda eng, root: (_overwrites(eng), eng.flush(),
                                  _overwrites(eng), eng.flush(),
                                  eng.manual_compact()),
    "ingest": lambda eng, root: _ingest(eng, root),
}


@pytest.mark.parametrize("maker", sorted(MAKERS))
def test_every_sst_holds_each_key_once(tmp_path, maker):
    # the slice path rests on this: a run with a key twice would yield it
    # twice where the heap merge yields it once
    eng = LsmEngine(str(tmp_path / "db"), EngineOptions(backend="cpu"))
    try:
        MAKERS[maker](eng, tmp_path)
        ssts = eng._scan_snapshot()[2]
        assert ssts
        for sst in ssts:
            b = sst.block()
            keys = [b.key(i) for i in range(b.n)]
            assert b.n and all(x < y for x, y in zip(keys, keys[1:]))
            rows = list(engine_db._slice_rows(b, 0, b.n, NOW, True, False,
                                              flagged=True))
            assert [r[0] for r in rows] == keys
    finally:
        eng.close()


@pytest.mark.parametrize("ssts,path", [(1, "slice"), (2, "merged")])
def test_the_counters_say_which_path_served_a_range(tmp_path, ssts, path):
    eng = LsmEngine(str(tmp_path / "db"), EngineOptions(backend="cpu"))
    try:
        for lo, hi in [(0, 612)] if ssts == 1 else [(0, 306), (306, 612)]:
            for op in _puts(lo, hi, b"a"):
                eng.put(op[1], op[2], expire_ts=op[3])
            eng.flush()
        assert len(eng._scan_snapshot()[2]) == ssts
        names = ("read.range.slice_ranges", "read.range.merged_ranges",
                 "read.range.rows")
        before = {n: _count(n) for n in names}
        rows = list(eng.scan_range_batch([(k(0), k(612))], now=NOW)[0])
        moved = {n: _count(n) - before[n] for n in names}
        assert [r[0] for r in rows] == [k(i) for i in range(612)]
        assert moved == {"read.range.slice_ranges": int(path == "slice"),
                         "read.range.merged_ranges": int(path == "merged"),
                         "read.range.rows": 612}
        # a range with no rows in any source takes neither path
        before = {n: _count(n) for n in names}
        assert list(eng.scan(k(0) + b"x", k(0) + b"y", now=NOW)) == []
        assert all(_count(n) == before[n] for n in names)
    finally:
        eng.close()
