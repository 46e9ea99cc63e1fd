"""Device-served reads: HBM-resident point lookups (ISSUE 7) and
fence-bounded range reads (ISSUE 19).

Acceptance: device-vs-host read BYTE-IDENTITY on cpu — identical
ReadResponse/MultiGetResponse wire bytes for mixed hit/miss/TTL-expired/
tombstoned keys across flushed+compacted state, including a mid-read
fallback (wedge/raise in the device probe) — plus the fence index
unit-level contract, the HBM residency gauges, and the collector's
read-residency drive. The range half extends the same contract to
multi_get ranges / sortkey_count / scanner batches (forward, reverse,
inclusivity, limits, split-pmask, boundary-dense single-hashkey runs)
and to the `read.range` fail point. The read-lane chaos/breaker-
isolation cases live in tests/test_lane_guard.py next to the compact
lane's.
"""

import threading

import numpy as np
import pytest

from pegasus_tpu.base import key_schema
from pegasus_tpu.engine.db import EngineOptions, LsmEngine
from pegasus_tpu.engine.server_impl import PegasusServer
from pegasus_tpu.rpc import codec
from pegasus_tpu.rpc import messages as msg
from pegasus_tpu.rpc.messages import Status
from pegasus_tpu.runtime import fail_points as fp
from pegasus_tpu.runtime.lane_guard import READ_LANE_GUARD, LaneGuardConfig
from pegasus_tpu.runtime.perf_counters import counters

NOW = 1000
V = b"\x82" + b"\x00" * 12  # v2 value header, no TTL


@pytest.fixture
def read_guard():
    """Deterministic read-lane config; fail points armed; restored after
    (READ_LANE_GUARD is process-wide)."""
    saved = READ_LANE_GUARD.config
    READ_LANE_GUARD.config = LaneGuardConfig(
        deadline_s=30.0, max_retries=1, backoff_base_s=0.001,
        backoff_max_s=0.002, breaker_threshold=99, breaker_cooldown_s=60.0,
        compile_wait_s=600.0)
    READ_LANE_GUARD.probe_fn = lambda: True
    READ_LANE_GUARD.reset()
    fp.setup()
    yield READ_LANE_GUARD
    fp.teardown()
    READ_LANE_GUARD.config = saved
    READ_LANE_GUARD.probe_fn = None
    READ_LANE_GUARD.reset()


def _engine_opts(device_reads):
    return EngineOptions(backend="tpu", device_reads=device_reads,
                         device_read_min_batch=1, l0_compaction_trigger=100)


def _load_mixed(engine):
    """Flushed+compacted L1, a newer L0 with shadowing tombstones, live
    memtable records, TTL-expired and tombstoned rows at every layer."""
    for i in range(40):
        engine.put(key_schema.generate_key(b"h%d" % (i % 3), b"s%03d" % i),
                   V + b"v%d" % i)
    engine.put(key_schema.generate_key(b"h0", b"expired"), V + b"old",
               expire_ts=NOW - 100)
    engine.put(key_schema.generate_key(b"h0", b"gone"), V + b"dead")
    engine.flush()
    engine.compact()                 # -> L1
    engine.delete(key_schema.generate_key(b"h0", b"gone"))     # tombstone
    engine.put(key_schema.generate_key(b"h1", b"s001"), V + b"newer")
    for i in range(40, 50):
        engine.put(key_schema.generate_key(b"h%d" % (i % 3), b"s%03d" % i),
                   V + b"v%d" % i)
    engine.flush()                   # -> newer L0 shadowing L1
    engine.put(key_schema.generate_key(b"h2", b"memonly"), V + b"mem")


def _prime_all(engine):
    """Deterministic residency for tests: the flush-time prime is
    fire-and-forget, so force every SST's upload inline."""
    with engine._lock:
        ssts = engine._all_ssts_locked()
    for sst in ssts:
        engine._device_run_budgeted(sst)
    return ssts


def _query_keys():
    keys = [key_schema.generate_key(b"h%d" % (i % 3), b"s%03d" % i)
            for i in range(55)]                        # hits + misses
    keys += [key_schema.generate_key(b"h0", b"expired"),
             key_schema.generate_key(b"h0", b"gone"),
             key_schema.generate_key(b"h2", b"memonly"),
             key_schema.generate_key(b"zz", b"missing")]
    return keys


# ------------------------------------------------------ engine-level identity


def test_get_batch_byte_identical_to_single_gets(tmp_path):
    eng = LsmEngine(str(tmp_path / "db"), _engine_opts(device_reads=True))
    try:
        _load_mixed(eng)
        ssts = _prime_all(eng)
        assert any(s.device_index is not None for s in ssts)
        keys = _query_keys()
        before = counters.number("read.device.lookup_count").value()
        batch = eng.get_batch(keys, now=NOW)
        assert batch == [eng.get(k, now=NOW) for k in keys]
        # the device path actually served (not a silent host walk)
        assert counters.number("read.device.lookup_count").value() > before
        assert counters.number("read.device.hits").value() > 0
    finally:
        eng.close()


def test_fence_index_built_as_prime_byproduct(tmp_path):
    eng = LsmEngine(str(tmp_path / "db"), _engine_opts(device_reads=True))
    try:
        _load_mixed(eng)
        for sst in _prime_all(eng):
            dr = sst.device_index
            if dr is None:
                continue
            assert dr.fence_len > 0 and dr.fence_step > 0
            assert dr.fence_len * dr.fence_step >= dr.n
            fence = np.asarray(dr.fence)
            assert len(fence) == dr.fence_len
            assert bool(np.all(fence[1:] >= fence[:-1]))  # sorted samples
    finally:
        eng.close()


def test_lookup_batch_exact_rows(tmp_path):
    """The kernel's row indexes equal the host binary search's for every
    present key, and -1 for absent/truncating-prefix queries."""
    from pegasus_tpu.ops.device_lookup import lookup_batch

    eng = LsmEngine(str(tmp_path / "db"), _engine_opts(device_reads=True))
    try:
        _load_mixed(eng)
        ssts = [s for s in _prime_all(eng) if s.device_index is not None]
        assert ssts
        sst = max(ssts, key=lambda s: s.n)
        block = sst.block()
        present = [block.key(i) for i in range(0, block.n, 3)]
        absent = [b"\x00\x07nothere" + b"x" * 9,
                  present[0] + b"longer-than-any-resident-key-window" * 2]
        rows = lookup_batch(sst.device_index, present + absent)
        for k, r in zip(present, rows[: len(present)]):
            assert int(r) == sst.find(k)
        assert all(int(r) == -1 for r in rows[len(present):])
    finally:
        eng.close()


# ------------------------------------------------------ server wire identity


def _server_pair(tmp_path, load=_load_mixed):
    pair = []
    for name, dev in (("on", True), ("off", False)):
        srv = PegasusServer(str(tmp_path / name), options=_engine_opts(dev))
        load(srv.engine)
        _prime_all(srv.engine)
        pair.append(srv)
    return pair


def _assert_wire_identical(srv_on, srv_off):
    for k in _query_keys():
        assert codec.encode(srv_on.on_get(k, now=NOW)) == \
            codec.encode(srv_off.on_get(k, now=NOW)), k
    req = msg.MultiGetRequest(
        hash_key=b"h0",
        sort_keys=[b"s%03d" % i for i in range(0, 50, 3)]
        + [b"expired", b"gone", b"nope"])
    assert codec.encode(srv_on.on_multi_get(req, now=NOW)) == \
        codec.encode(srv_off.on_multi_get(req, now=NOW))


def test_responses_byte_identical_device_vs_host(tmp_path, read_guard):
    """Acceptance: identical ReadResponse/MultiGetResponse bytes for
    mixed hit/miss/TTL-expired/tombstoned keys across flushed+compacted
    state, device-served vs host-served."""
    srv_on, srv_off = _server_pair(tmp_path)
    try:
        before = counters.number("read.device.lookup_count").value()
        _assert_wire_identical(srv_on, srv_off)
        assert counters.number("read.device.lookup_count").value() > before
        assert read_guard.state()["fallbacks"] == 0
    finally:
        srv_on.close()
        srv_off.close()


def test_responses_byte_identical_through_mid_read_fallback(tmp_path,
                                                            read_guard):
    """Acceptance: the fallback path serves the same bytes — a raising
    device probe (retry -> host fallback) and a wedged one (deadline
    abandon -> host fallback) both leave responses identical."""
    srv_on, srv_off = _server_pair(tmp_path)
    try:
        fp.cfg("read.device", "raise(transient probe error)")
        _assert_wire_identical(srv_on, srv_off)
        st = read_guard.state()
        assert st["fallbacks"] >= 1 and st["retries"] >= 1
        fp.cfg("read.device", "off()")

        # the raise storm walked the consecutive-failure count past any
        # threshold; close the breaker so the wedge phase probes again
        read_guard.reset()
        read_guard.config.deadline_s = 0.3
        fp.cfg("read.device", "1*sleep(1500)")
        k = key_schema.generate_key(b"h0", b"s000")
        assert codec.encode(srv_on.on_get(k, now=NOW)) == \
            codec.encode(srv_off.on_get(k, now=NOW))
        st = read_guard.state()
        assert st["deadline_abandons"] == 1
        assert "read.device" in st["last_failure"]["error"]  # attribution
    finally:
        srv_on.close()
        srv_off.close()


def test_concurrent_gets_coalesce_and_match(tmp_path, read_guard):
    """Concurrent point reads group through the server's coalescer into
    device batches; every response still matches the host-served twin."""
    srv_on, srv_off = _server_pair(tmp_path)
    try:
        expected = {k: codec.encode(srv_off.on_get(k, now=NOW))
                    for k in _query_keys()}
        errors = []

        def worker(t):
            try:
                for i, (k, want) in enumerate(expected.items()):
                    if (i + t) % 3 == 0:
                        assert codec.encode(srv_on.on_get(k, now=NOW)) == want
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors
        # groups actually formed (p99 of the coalesced batch size > 1
        # would be flaky on a loaded box; the size histogram existing and
        # the engine's batch span firing is the mechanical assertion)
        assert counters.percentile("read.batch.size").percentiles()["p50"] >= 1
    finally:
        srv_on.close()
        srv_off.close()


# ------------------------------------------------------------- HBM gauges


def test_hbm_residency_gauges(tmp_path):
    eng = LsmEngine(str(tmp_path / "db"), _engine_opts(device_reads=True))
    try:
        budget0 = counters.number("engine.hbm.budget_bytes").value()
        assert budget0 >= eng.opts.device_cache_bytes  # registered at init
        bytes0 = counters.number("engine.hbm.resident_bytes").value()
        ssts0 = counters.number("engine.hbm.resident_ssts").value()
        _load_mixed(eng)
        primed = [s for s in _prime_all(eng) if s._device_budgeted]
        assert primed
        assert counters.number("engine.hbm.resident_bytes").value() \
            >= bytes0 + sum(s._device_run.nbytes() for s in primed)
        assert counters.number("engine.hbm.resident_ssts").value() \
            >= ssts0 + len(primed)
        st = eng.stats()
        assert st["device_resident_ssts"] == len(primed)
        assert st["device_resident_bytes"] > 0
        # compaction consumes the inputs: accounting releases, never
        # underflows
        eng.compact()
        assert eng.stats()["device_resident_bytes"] >= 0
    finally:
        eng.close()
    # close() drops this engine's contribution from the process gauges
    assert counters.number("engine.hbm.budget_bytes").value() <= budget0


def test_set_read_residency_primes_ssts(tmp_path):
    eng = LsmEngine(str(tmp_path / "db"), _engine_opts(device_reads=True))
    try:
        _load_mixed(eng)
        assert eng.stats()["read_hot"] is False
        eng.set_read_residency(True)
        assert eng.stats()["read_hot"] is True
        # primes ride the pipeline pool fire-and-forget; wait bounded
        import time

        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            with eng._lock:
                ssts = eng._all_ssts_locked()
            if any(s.device_index is not None for s in ssts):
                break
            time.sleep(0.02)
        assert any(s.device_index is not None for s in ssts)
        eng.set_read_residency(False)
        assert eng.stats()["read_hot"] is False
    finally:
        eng.close()


def test_read_hot_claims_reserved_budget_headroom(tmp_path):
    """The residency flag is a real budget input: a cold partition's
    primes stop at 7/8 of the HBM budget (reserved headroom), a read-hot
    pin may fill it."""
    eng = LsmEngine(str(tmp_path / "db"), _engine_opts(device_reads=True))
    try:
        eng._prime_async = lambda sst: None  # deterministic: prime inline
        for batch in range(2):
            for i in range(20):
                eng.put(key_schema.generate_key(b"h%d" % batch,
                                                b"s%03d" % i), V + b"v")
            eng.flush()
        with eng._lock:
            ssts = eng._all_ssts_locked()
        assert len(ssts) >= 2
        assert eng._device_run_budgeted(ssts[0]) is not None
        used = eng._device_cache_used
        assert used > 8
        # budget sized so only the FULL budget admits the second run
        eng.opts.device_cache_bytes = used + 1
        assert not ssts[1]._device_budgeted
        eng._device_run_budgeted(ssts[1])
        assert not ssts[1]._device_budgeted  # cold: stopped at 7/8
        eng.set_read_residency(True)
        assert eng._device_run_budgeted(ssts[1]) is not None
        assert ssts[1]._device_budgeted      # hot: headroom claimed
    finally:
        eng.close()


# --------------------------------------------- collector residency drive


def test_collector_hotkey_verdict_drives_read_residency():
    """A confirmed read-hotspot verdict turns the partition's device
    read residency ON via the set-read-residency remote command; the
    partition calming turns it OFF — the loop that decides which
    partitions' SSTs stay HBM-resident."""
    from pegasus_tpu.collector.info_collector import InfoCollector

    ic = InfoCollector([], interval_seconds=3600, hotkey_rounds=2)
    calls = []

    def fake_rc(node, command, args):
        calls.append((node, command, list(args)))
        if command == "detect_hotkey":
            return {"start": "started",
                    "query": "hotkey: user42",
                    "stop": "stopped"}[args[2]]
        return "read residency %s for %s" % (args[1], args[0])

    ic.remote_command = fake_rc
    primaries = {0: "n1:1", 1: "n1:1", 2: "n1:1", 3: "n1:1"}
    read_qps = {0: 500.0, 1: 1.0, 2: 1.0, 3: 1.0}
    for _ in range(ic.hotkey_rounds):
        ic.drive_hotkey_loop("t", 7, [0], primaries, read_qps, {})
    assert ("n1:1", "set-read-residency", ["7.0", "on"]) in calls
    assert ("t", 0) in ic.read_residency
    assert counters.number(
        "collector.app.t.hotkey.0.device_resident").value() == 1
    # partition calms, but the release RPC drops: bookkeeping must stay
    # so the NEXT calm round resends the off (a dropped RPC cannot leave
    # the server's residency flag hot forever)
    from pegasus_tpu.rpc.transport import RpcError

    fail_next = [True]
    real_rc = ic.remote_command

    def flaky_rc(node, command, args):
        if command == "set-read-residency" and fail_next[0]:
            fail_next[0] = False
            raise RpcError(7, "connection refused")
        return real_rc(node, command, args)

    ic.remote_command = flaky_rc
    ic.drive_hotkey_loop("t", 7, [], primaries, read_qps, {})
    assert ("t", 0) in ic.read_residency  # failed release kept for retry
    ic.drive_hotkey_loop("t", 7, [], primaries, read_qps, {})
    assert ("n1:1", "set-read-residency", ["7.0", "off"]) in calls
    assert ("t", 0) not in ic.read_residency
    assert counters.number(
        "collector.app.t.hotkey.0.device_resident").value() == 0


def test_replica_stub_set_read_residency_command(tmp_path):
    """The remote-command handler flips the engine flag (unit-level: a
    stub-shaped object with one replica)."""
    from pegasus_tpu.replication.replica_stub import ReplicaStub

    class _Rep:
        pass

    srv = PegasusServer(str(tmp_path / "db"),
                        options=_engine_opts(device_reads=True))
    try:
        stub = ReplicaStub.__new__(ReplicaStub)
        stub._lock = threading.Lock()
        rep = _Rep()
        rep.server = srv
        stub._replicas = {(1, 0): rep}
        out = stub._cmd_set_read_residency(["1.0", "on"])
        assert "on" in out
        assert srv.engine.stats()["read_hot"] is True
        out = stub._cmd_set_read_residency(["1.0", "off"])
        assert "off" in out
        assert srv.engine.stats()["read_hot"] is False
        assert "usage" in stub._cmd_set_read_residency(["1.0"])
        assert "no replica" in stub._cmd_set_read_residency(["9.9", "on"])
    finally:
        srv.close()


# ------------------------------------------- range reads (ISSUE 19)


DENSE_P = b"p" * 9  # long shared sortkey prefix: composite keys agree
#                     deep into the packed lanes (all-equal-first-lane)


def _load_dense(engine):
    """The range-read edge loader: ONE hash key whose sortkeys share a
    long prefix, so every packed first lane (and several more) is EQUAL
    and only deep lanes / the klen tiebreak discriminate — plus
    boundary-dense neighbors (keys differing in the last byte, and
    proper-prefix pairs exercising the klen tiebreak), TTL-expired and
    tombstoned rows, split across L1 / L0 / memtable."""
    for i in range(120):
        engine.put(key_schema.generate_key(b"hx", DENSE_P + b"%04d" % i),
                   V + b"d%d" % i)
    # proper-prefix pair: same lanes where they overlap, klen decides
    engine.put(key_schema.generate_key(b"hx", DENSE_P + b"0050x"), V + b"px")
    engine.put(key_schema.generate_key(b"hx", DENSE_P + b"expired"),
               V + b"old", expire_ts=NOW - 100)
    engine.put(key_schema.generate_key(b"hx", DENSE_P + b"gone"), V + b"dead")
    engine.flush()
    engine.compact()                 # -> L1
    engine.delete(key_schema.generate_key(b"hx", DENSE_P + b"gone"))
    engine.put(key_schema.generate_key(b"hx", DENSE_P + b"0001"), V + b"new")
    for i in range(120, 150):
        engine.put(key_schema.generate_key(b"hx", DENSE_P + b"%04d" % i),
                   V + b"d%d" % i)
    engine.flush()                   # -> newer L0 shadowing L1
    engine.put(key_schema.generate_key(b"hx", DENSE_P + b"zzmem"), V + b"mem")


def _range_combos(prefix=b""):
    """(start, stop, start_inclusive, stop_inclusive, reverse,
    max_kv_count) sweeps: open/bounded/inverted/absent bounds, both
    inclusivities, both directions, limited and unlimited."""
    combos = []
    for start, stop in ((b"", b""), (b"", prefix + b"0047"),
                        (prefix + b"0010", prefix + b"0047"),
                        (prefix + b"0010", b""),
                        (prefix + b"0046x", prefix + b"0123"),  # absent bounds
                        (prefix + b"0050", prefix + b"0050"),   # point range
                        (prefix + b"0090", prefix + b"0010")):  # inverted
        for si in (True, False):
            for ti in (True, False):
                for rev in (False, True):
                    for maxn in (0, 5):
                        combos.append((start, stop, si, ti, rev, maxn))
    return combos


def _assert_range_wire_identical(srv_on, srv_off, hash_keys, prefix=b""):
    for hk in hash_keys:
        assert codec.encode(srv_on.on_sortkey_count(hk, now=NOW)) == \
            codec.encode(srv_off.on_sortkey_count(hk, now=NOW)), hk
        for start, stop, si, ti, rev, maxn in _range_combos(prefix):
            req = msg.MultiGetRequest(
                hash_key=hk, sort_keys=[], max_kv_count=maxn,
                start_sortkey=start, stop_sortkey=stop,
                start_inclusive=si, stop_inclusive=ti, reverse=rev)
            assert codec.encode(srv_on.on_multi_get(req, now=NOW)) == \
                codec.encode(srv_off.on_multi_get(req, now=NOW)), \
                (hk, start, stop, si, ti, rev, maxn)
    assert _scan_wire(srv_on) == _scan_wire(srv_off)
    assert _scan_wire(srv_on, batch_size=7) == \
        _scan_wire(srv_off, batch_size=7)


def _scan_wire(srv, **req_kw):
    """Drain a full scanner session into normalized wire blobs (the
    context id is a server-local session handle, not wire contract —
    normalized to its completed/continuing sign)."""
    out = []
    resp = srv.on_get_scanner(msg.GetScannerRequest(**req_kw), now=NOW)
    for _ in range(10_000):
        out.append(codec.encode(msg.ScanResponse(
            error=resp.error, kvs=resp.kvs,
            context_id=min(resp.context_id, 0), app_id=resp.app_id,
            partition_index=resp.partition_index, server=resp.server)))
        if resp.error != Status.OK or resp.context_id < 0:
            return out
        resp = srv.on_scan(msg.ScanRequest(resp.context_id), now=NOW)
    raise AssertionError("scanner session never completed")


def test_range_responses_byte_identical_device_vs_host(tmp_path, read_guard):
    """Acceptance (ISSUE 19): identical MultiGetResponse/CountResponse/
    ScanResponse bytes for range reads over mixed hit/miss/TTL-expired/
    tombstoned state — and the forward queries actually took the device
    path while reverse ones were counted host-side."""
    srv_on, srv_off = _server_pair(tmp_path)
    try:
        dev0 = counters.number("read.range.device_count").value()
        rev0 = counters.number("read.range.reverse_host_count").value()
        rows0 = counters.number("read.range.rows").value()
        _assert_range_wire_identical(srv_on, srv_off,
                                     [b"h0", b"h1", b"h2", b"zz"],
                                     prefix=b"s0")
        assert counters.number("read.range.device_count").value() > dev0
        assert counters.number("read.range.reverse_host_count").value() > rev0
        assert counters.number("read.range.rows").value() > rows0
        assert read_guard.state()["fallbacks"] == 0
    finally:
        srv_on.close()
        srv_off.close()


def test_range_identity_dense_single_hashkey(tmp_path, read_guard):
    """The boundary-dense edge: one hash key, equal first lanes
    everywhere, proper-prefix sortkeys, shadowing layers — the fence
    degenerates to near-equal samples and only deep lanes / klen
    discriminate."""
    srv_on, srv_off = _server_pair(tmp_path, load=_load_dense)
    try:
        dev0 = counters.number("read.range.device_count").value()
        _assert_range_wire_identical(srv_on, srv_off, [b"hx"],
                                     prefix=DENSE_P)
        assert counters.number("read.range.device_count").value() > dev0
    finally:
        srv_on.close()
        srv_off.close()


def test_range_identity_under_split_pmask(tmp_path, read_guard):
    """Post-split state (partition_mask > 0): the scanner's filter-free
    fast path must correctly NOT engage (rows need the per-row partition
    hash check) and every response stays identical to the host twin."""
    srv_on, srv_off = _server_pair(tmp_path)
    try:
        for srv in (srv_on, srv_off):
            srv.engine.opts.partition_mask = 1
        _assert_range_wire_identical(srv_on, srv_off, [b"h0", b"h1"],
                                     prefix=b"s0")
    finally:
        srv_on.close()
        srv_off.close()


def test_range_responses_identical_through_mid_read_fallback(tmp_path,
                                                             read_guard):
    """The `read.range` fail point: a raising interval resolve (retry ->
    host fallback) and a wedged one (deadline abandon -> host fallback)
    both serve identical bytes, and the failed attempts land in
    host_count, not device_count."""
    srv_on, srv_off = _server_pair(tmp_path)
    try:
        req = msg.MultiGetRequest(hash_key=b"h0", sort_keys=[],
                                  start_sortkey=b"s000",
                                  stop_sortkey=b"s040")
        fp.cfg("read.range", "raise(transient resolve error)")
        dev0 = counters.number("read.range.device_count").value()
        host0 = counters.number("read.range.host_count").value()
        assert codec.encode(srv_on.on_multi_get(req, now=NOW)) == \
            codec.encode(srv_off.on_multi_get(req, now=NOW))
        st = read_guard.state()
        assert st["fallbacks"] >= 1 and st["retries"] >= 1
        assert counters.number("read.range.device_count").value() == dev0
        assert counters.number("read.range.host_count").value() > host0
        fp.cfg("read.range", "off()")

        # close the breaker the raise storm walked up, then wedge once:
        # the 0.3 s deadline abandons the kernel mid-flight
        read_guard.reset()
        read_guard.config.deadline_s = 0.3
        fp.cfg("read.range", "1*sleep(1500)")
        assert codec.encode(srv_on.on_multi_get(req, now=NOW)) == \
            codec.encode(srv_off.on_multi_get(req, now=NOW))
        st = read_guard.state()
        assert st["deadline_abandons"] == 1
        assert "read.range" in st["last_failure"]["error"]  # attribution
    finally:
        srv_on.close()
        srv_off.close()


def test_concurrent_ranges_coalesce_and_match(tmp_path, read_guard):
    """Concurrent range reads group through the server's range coalescer
    into one scan_range_batch; every response still matches the
    host-served twin."""
    srv_on, srv_off = _server_pair(tmp_path)
    try:
        reqs = []
        for i in range(0, 40, 4):
            reqs.append(msg.MultiGetRequest(
                hash_key=b"h%d" % (i % 3), sort_keys=[],
                start_sortkey=b"s%03d" % i, stop_sortkey=b"s%03d" % (i + 9)))
        expected = [codec.encode(srv_off.on_multi_get(r, now=NOW))
                    for r in reqs]
        batch0 = counters.number("read.range.batch_count").value()
        errors = []

        def worker(t):
            try:
                for i, (r, want) in enumerate(zip(reqs, expected)):
                    if (i + t) % 2 == 0:
                        assert codec.encode(
                            srv_on.on_multi_get(r, now=NOW)) == want
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors
        # grouping cut the engine calls below the request count (30 range
        # reads issued; followers ride the leader's batch), and the batch
        # size histogram recorded the groups
        served = sum(1 for t in range(6) for i in range(len(reqs))
                     if (i + t) % 2 == 0)
        assert counters.number("read.range.batch_count").value() - batch0 \
            <= served
        assert counters.percentile(
            "read.range.batch.size").percentiles()["p50"] >= 1
    finally:
        srv_on.close()
        srv_off.close()


def test_scan_context_eviction_closes_iterator():
    """An evicted or cleared scan session releases its engine snapshot
    NOW — iterator.close() fires the generator's finally (where the
    range iterators flush read.range.rows) instead of waiting on GC."""
    from pegasus_tpu.engine.scan_context import (ScanContext,
                                                 ScanContextCache)

    closed = []

    def gen(tag):
        try:
            yield tag
        finally:
            closed.append(tag)

    cache = ScanContextCache(max_contexts=2)
    ctxs = [ScanContext(gen(i), None) for i in range(3)]
    for c in ctxs:
        next(c.iterator)            # enter the body so finally is armed
    ids = [cache.put(c) for c in ctxs]
    assert closed == [0]            # LRU overflow closed the oldest
    cache.remove(ids[1])
    assert closed == [0, 1]         # explicit clear_scanner closes too
    assert cache.fetch(ids[2]) is ctxs[2]
    assert closed == [0, 1]         # the live session untouched


def test_range_batch_intervals_match_host_lower_bound(tmp_path):
    """Unit contract of the kernel: for arbitrary (start, stop) byte
    strings — present, absent, open, inverted, longer than the packed
    lane window — the device interval equals the host lower_bound pair
    (clamped to hi >= lo)."""
    from pegasus_tpu.ops.device_lookup import range_batch

    eng = LsmEngine(str(tmp_path / "db"), _engine_opts(device_reads=True))
    try:
        _load_dense(eng)
        ssts = [s for s in _prime_all(eng) if s.device_index is not None]
        assert ssts
        sst = max(ssts, key=lambda s: s.n)
        block = sst.block()
        k = [block.key(i) for i in range(block.n)]
        ranges = [(b"", None), (b"", k[3]), (k[2], k[-2]),
                  (k[5] + b"\x00", k[9] + b"zz"),        # absent bounds
                  (k[-1] + b"\xff", None),               # past the end
                  (k[9], k[2]),                          # inverted
                  (k[4], k[4]),                          # empty point
                  (k[0] + b"longer-than-any-lane-window" * 3, None)]
        iv = range_batch(sst.device_index, ranges)
        for (start, stop), (lo, hi) in zip(ranges, iv):
            want_lo = sst.lower_bound(start)
            want_hi = sst.n if stop is None else sst.lower_bound(stop)
            assert (int(lo), int(hi)) == (want_lo, max(want_hi, want_lo)), \
                (start, stop)
    finally:
        eng.close()


# --------------------------------------------- one upload a call (ISSUE 31)
#
# A device read call hands the chip ONE host array (the packed query
# image) and launches ONE program; the run's scalars are resident with
# its fence. Held at every window width the benchmark's tables pack
# (7 lanes: 26 B keys, 13: the geo index's 51 B, 16: the cap) and on both
# sides of each query bucket (8, 16, 32).

LANES = (7, 13, 16)
QUERIES = (1, 8, 9, 16, 17)


def _lane_block(w, n, tag=b"k", ragged=True):
    """A sorted run of n distinct keys whose longest packs exactly w
    lanes; every third key is shorter (unless not `ragged`), so lengths
    break lane ties."""
    from pegasus_tpu.engine.block import KVBlock
    from pegasus_tpu.ops.compact import sort_block

    def sort_key(i):
        short = 5 if ragged and i % 3 == 0 else 0
        return (tag + b"%06d" % (i * 7)).ljust(4 * w - 4 - short, b".")

    keys = {key_schema.generate_key(b"h%d" % (i % 5), sort_key(i))
            for i in range(n)}
    assert len(keys) == n and max(map(len, keys)) == 4 * w
    return sort_block(KVBlock.from_records(
        [(k, V + b"v", 0, False) for k in keys]))


def _lane_sst(w, n=300, ragged=True):
    """An in-memory SST over such a run, primed as a flush primes it."""
    from pegasus_tpu.engine.sstable import SSTable

    sst = SSTable.from_block("mem-%d-%d" % (w, n),
                             _lane_block(w, n, ragged=ragged))
    sst.device_run(16)
    assert sst.device_index is not None and sst.device_index.w == w
    return sst


@pytest.fixture(scope="module")
def lane_ssts():
    return {w: _lane_sst(w) for w in LANES}


def _query_pool(sst):
    """Present keys, absent ones on both sides of a row, keys longer than
    any window (64 B), a bare prefix, the empty key."""
    k = [sst.block().key(i) for i in range(sst.n)]
    return [k[0], k[7] + b"x" * 70, k[11] + b"\x00", k[-1], k[40][:-1],
            k[5], k[200][:9], b"", k[-1] + b"\xff", k[123], b"\xff" * 80,
            k[17], k[2] + b"y" * 3]


def _rotations(pool, nq):
    """Every call of exactly nq queries that starts at another kind."""
    return [[pool[(r + i) % len(pool)] for i in range(nq)]
            for r in range(len(pool))]


def _old_pack_queries(keys, w):
    """The per-column packing `pack_queries` had before ISSUE 31, kept as
    the reference: -> (w uint32[qpad] lanes, uint32[qpad] lengths)."""
    from pegasus_tpu.ops.compact import _pow2ceil
    from pegasus_tpu.ops.packing import pack_key_prefixes

    n = len(keys)
    arena = np.frombuffer(b"".join(keys), dtype=np.uint8).copy() \
        if n else np.zeros(0, np.uint8)
    lens = np.fromiter((len(k) for k in keys), dtype=np.int32, count=n)
    offs = np.zeros(n, dtype=np.int64)
    if n:
        np.cumsum(lens[:-1], out=offs[1:])
    pref = pack_key_prefixes(arena, offs, lens, w)
    qpad = _pow2ceil(max(1, n), 8)
    qcols = []
    for j in range(w):
        col = np.zeros(qpad, np.uint32)
        col[:n] = pref[:, j]
        qcols.append(col)
    qklen = np.zeros(qpad, np.uint32)
    qklen[:n] = lens
    return qcols, qklen


@pytest.mark.parametrize("nq", QUERIES)
@pytest.mark.parametrize("w", LANES)
def test_pack_queries_is_one_image_whose_rows_are_the_old_columns(
        lane_ssts, w, nq):
    from pegasus_tpu.ops.device_lookup import pack_queries

    for keys in _rotations(_query_pool(lane_ssts[w]), nq):
        image = pack_queries(keys, w)
        assert isinstance(image, np.ndarray) and image.dtype == np.uint32
        assert image.shape == (w + 1, max(8, 1 << (nq - 1).bit_length()))
        assert image.flags.c_contiguous
        qcols, qklen = _old_pack_queries(keys, w)
        for j in range(w):
            assert np.array_equal(image[j], qcols[j]), j
        assert np.array_equal(image[w], qklen)
        assert [int(n) for n in image[w, :nq]] == [len(k) for k in keys]


@pytest.mark.parametrize("nq", QUERIES)
@pytest.mark.parametrize("w", LANES)
def test_lookup_rows_equal_sstable_find(lane_ssts, w, nq):
    from pegasus_tpu.ops.device_lookup import lookup_batch

    sst = lane_ssts[w]
    for keys in _rotations(_query_pool(sst), nq):
        rows = lookup_batch(sst.device_index, keys)
        assert rows.shape == (nq,)
        assert [int(r) for r in rows] == [sst.find(k) for k in keys], keys


@pytest.mark.parametrize("nq", QUERIES)
@pytest.mark.parametrize("w", LANES)
def test_range_intervals_equal_lower_bound_pairs(lane_ssts, w, nq):
    from pegasus_tpu.ops.device_lookup import range_batch

    sst = lane_ssts[w]
    k = [sst.block().key(i) for i in range(sst.n)]
    pool = [(k[3], k[90]), (b"", None), (k[10] + b"\x00", k[30] + b"zz"),
            (k[50] + b"q" * 70, None),           # longer than any window
            (k[99], k[4]),                       # inverted
            (k[8], k[8]),                        # empty
            (k[-1] + b"\xff", None),             # past the end
            (b"", k[20][:9]), (k[0], k[5] + b"w" * 70), (k[150], None),
            (k[60][:-1], k[61])]
    for ranges in _rotations(pool, nq):
        iv = range_batch(sst.device_index, ranges)
        assert iv.shape == (nq, 2)
        for (start, stop), (lo, hi) in zip(ranges, iv):
            want_lo = sst.lower_bound(start)
            want_hi = sst.n if stop is None else sst.lower_bound(stop)
            assert (int(lo), int(hi)) == (want_lo, max(want_hi, want_lo)), \
                (start, stop)


def _one_call(kind, sst, nq=5):
    from pegasus_tpu.ops import device_lookup as dl

    k = [sst.block().key(i) for i in range(sst.n)]
    if kind == "lookup":
        return dl.lookup_batch(sst.device_index, k[:nq])
    return dl.range_batch(sst.device_index,
                          [(k[i], k[i + 9]) for i in range(nq - 1)]
                          + [(k[4], None)])


@pytest.mark.parametrize("w", LANES)
@pytest.mark.parametrize("kind", ("lookup", "range"))
def test_a_read_call_is_one_upload_and_one_launch(lane_ssts, monkeypatch,
                                                  kind, w):
    """Counted BOTH ways. Through the helper's one upload seam
    (`_upload`), and through `jax.transfer_guard_host_to_device`, which
    XLA:CPU honours: under "disallow_explicit" every host->device
    transfer raises, a `jax.device_put`, a numpy argument and the Python
    number inside a `jnp.int32(...)` alike (the two eager
    `convert_element_type` programs a call used to launch were such
    numbers), so the call only passes if its one transfer is the seam's.
    Launches are counted at DeviceKernel, the one door to a program, and
    every argument of the launch has to be on the device already."""
    import jax

    from pegasus_tpu.ops import device_lookup as dl
    from pegasus_tpu.ops.kernel import DeviceKernel

    sst = lane_ssts[w]
    want = _one_call(kind, sst)          # compiled before the guard
    uploads, launches = [], []
    real_upload, real_call = dl._upload, DeviceKernel.__call__

    def upload(image):
        uploads.append(image)
        with jax.transfer_guard_host_to_device("allow"):
            return real_upload(image)

    def call(self, *args):
        launches.append(self.name)
        assert all(isinstance(a, jax.Array)
                   for a in jax.tree_util.tree_leaves(args))
        return real_call(self, *args)

    monkeypatch.setattr(dl, "_upload", upload)
    monkeypatch.setattr(DeviceKernel, "__call__", call)
    with jax.transfer_guard_host_to_device("disallow_explicit"):
        got = _one_call(kind, sst)
        with pytest.raises(Exception, match="host-to-device"):
            jax.numpy.int32(sst.n)       # the guard does bite here
    assert np.array_equal(got, want)
    assert launches == [kind]
    assert len(uploads) == 1
    assert isinstance(uploads[0], np.ndarray)
    assert uploads[0].shape == ((w + 1, 8) if kind == "lookup"
                                else (2, w + 1, 8))


@pytest.mark.parametrize("kind,parent", (("lookup", "read.device"),
                                         ("range", "read.range")))
def test_one_call_closes_its_parent_and_three_inner_spans_once(
        lane_ssts, kind, parent):
    from pegasus_tpu.runtime.tracing import COMPACT_TRACER

    names = [parent] + [parent + part
                        for part in (".pack", ".dispatch", ".download")]

    def totals():
        return {n: (counters.number(f"stage.{n}.n").value(),
                    counters.number(f"stage.{n}.us").value())
                for n in names}

    _one_call(kind, lane_ssts[13])       # compiled outside the session
    before = totals()
    with COMPACT_TRACER.session() as sess:
        _one_call(kind, lane_ssts[13])
    after = totals()
    assert {n: sess.stages[n]["calls"] for n in names} == \
        dict.fromkeys(names, 1)
    assert {n: sess.stages[n]["records"] for n in names} == \
        dict.fromkeys(names, 5)
    for n in names:
        assert after[n][0] == before[n][0] + 1, n
        assert after[n][1] >= before[n][1]
    # the parts lie inside their parent
    inner = sum(after[n][1] - before[n][1] for n in names[1:])
    assert inner <= after[parent][1] - before[parent][1]


# ------------------------------------ the run's scalars live with the run


def test_scalars_are_made_with_the_fence_and_counted_in_nbytes(lane_ssts):
    for w, sst in lane_ssts.items():
        dr = sst.device_index
        assert (int(dr.n_dev), int(dr.step_dev)) == (dr.n, dr.fence_step)
        assert (dr.n_dev.dtype, dr.n_dev.shape) == (np.int32, ())
        assert (dr.step_dev.dtype, dr.step_dev.shape) == (np.int32, ())
        cols = (w + 3) * 4 * dr.padded_len + dr.padded_len
        assert dr.nbytes() == cols + 4 * dr.fence_len + 8


def test_scalars_follow_a_run_primed_again():
    """Residency dropped and primed again (here: the value-residency
    upgrade re-packs the file) makes a fresh fence AND fresh scalars."""
    from pegasus_tpu.ops.device_lookup import lookup_batch

    sst = _lane_sst(7, ragged=False)    # one layout: values can be pinned
    first = sst.device_index
    again = sst.device_run(16, with_values=True)
    assert again is not first and again.val2d is not None
    assert again.n_dev is not first.n_dev
    assert (int(again.n_dev), int(again.step_dev)) == (sst.n,
                                                       again.fence_step)
    keys = [sst.block().key(i) for i in range(0, sst.n, 11)]
    assert [int(r) for r in lookup_batch(sst.device_index, keys)] == \
        [sst.find(k) for k in keys]


def test_scalars_follow_the_run_a_compaction_put_in_the_same_bucket(
        tmp_path):
    """300 rows, then 450 after a compaction: one padded_len (512), one
    window, ONE program for both runs, so only the resident `n` and
    fence step tell the new run's last 150 rows from padding."""
    from pegasus_tpu.ops import device_lookup as dl

    def key(i):
        return key_schema.generate_key(b"h%d" % (i % 3), b"s%05d" % i)

    eng = LsmEngine(str(tmp_path / "db"), _engine_opts(device_reads=True))
    try:
        seen = []
        for n in (300, 450):
            for i in range(n):
                eng.put(key(i), V + b"v%d" % i)
            eng.flush()
            eng.manual_compact(now=NOW)
            (sst,) = [s for s in _prime_all(eng) if s.n]
            dr = sst.device_index
            assert (dr.n, dr.padded_len, int(dr.n_dev)) == (n, 512, n)
            assert int(dr.step_dev) == dr.fence_step
            seen.append((dl._compiled_lookup(dr.padded_len, dr.w,
                                             dr.fence_len, 8), dr))
            probe = [key(i) for i in (0, 299, 300, 449, 450)]
            rows = dl.lookup_batch(dr, probe)
            assert [int(r) for r in rows] == [sst.find(k) for k in probe]
            assert [int(r) >= 0 for r in rows] == [i < n for i in
                                                   (0, 299, 300, 449, 450)]
            looked = counters.number("read.device.keys").value()
            keys = [key(i) for i in range(0, 460, 9)]
            assert eng.get_batch(keys, now=NOW) == [eng.get(k, now=NOW)
                                                    for k in keys]
            assert counters.number("read.device.keys").value() > looked
        (fn_a, dr_a), (fn_b, dr_b) = seen
        assert fn_a is fn_b and dr_a is not dr_b
        assert dr_a.fence_step != dr_b.fence_step
    finally:
        eng.close()


def test_a_failed_fence_build_leaves_the_run_host_served(monkeypatch):
    from pegasus_tpu.engine.sstable import SSTable
    from pegasus_tpu.ops import device_lookup as dl

    def refuse(padded_len, fence_len):
        raise RuntimeError("no fence today")

    monkeypatch.setattr(dl, "_compiled_fence_build", refuse)
    fails = counters.number("read.device.fence_fail_count").value()
    looked = counters.number("read.device.lookup_count").value()
    sst = SSTable.from_block("mem-nofence", _lane_block(7, 300))
    dr = sst.device_run(16)
    assert dr is not None                 # the columns are resident
    assert (dr.fence, dr.n_dev, dr.step_dev) == (None, None, None)
    assert dr.nbytes() == (7 + 3) * 4 * dr.padded_len + dr.padded_len
    assert sst.device_index is None       # so the engine walks the host
    keys = [sst.block().key(i) for i in range(4)]
    assert [int(r) for r in dl.lookup_batch(dr, keys)] == [-1] * 4
    assert dl.range_batch(dr, [(keys[0], None)]).tolist() == [[0, 0]]
    assert counters.number("read.device.fence_fail_count").value() \
        == fails + 1
    assert counters.number("read.device.lookup_count").value() == looked
