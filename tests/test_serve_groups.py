"""Multi-process partition-group serving (replication/serve_groups.py).

The onebox coverage the tentpole requires: >=2 group-executor processes
behind one node address, cross-group routing via both the sharded
fd-handoff fast path (PegasusClient) and the unsharded per-frame relay
(raw RpcConnection), the partition-hash sanity error propagating through
the router, node-level fan-out, and the chaos path — kill one group mid
traffic (clean bounded errors, sibling group unaffected), restart it and
re-serve reads AND writes. conftest's session reaper guarantees no worker
process outlives the suite.
"""

import time

import pytest

from pegasus_tpu.base import key_schema
from pegasus_tpu.client.client import PegasusError
from pegasus_tpu.replication.serve_groups import group_of
from pegasus_tpu.rpc import codec
from pegasus_tpu.rpc.transport import (ERR_BUSY, ERR_INVALID_STATE,
                                       ERR_NETWORK_FAILURE, RpcConnection,
                                       RpcError, RpcServer)
from tests.test_satellites import MiniCluster

PARTITIONS = 4
GROUPS = 2


@pytest.fixture(scope="module")
def gcluster(tmp_path_factory):
    c = MiniCluster(tmp_path_factory.mktemp("grp"), n_nodes=2,
                    serve_groups=GROUPS)
    c.cli = c.create("gt", partitions=PARTITIONS, replicas=2)
    yield c
    c.cli.close()
    c.stop()


def _pidx(hk: bytes, sk: bytes = b"sk") -> int:
    return key_schema.key_hash(key_schema.generate_key(hk, sk)) % PARTITIONS


def _keys_for_group(g: int, n: int):
    """n hash keys whose partitions belong to group g."""
    out, i = [], 0
    while len(out) < n:
        hk = b"gk%d" % i
        if group_of(1, _pidx(hk), GROUPS) == g:
            out.append(hk)
        i += 1
    return out


def test_cross_group_routing_sharded_client(gcluster):
    """Every partition (both groups) serves point ops and scans through
    the public node address, AND the sharded client connections really
    were handed off to the owning executors — if the SCM_RIGHTS fast
    path silently regressed to all-relay, this must fail, not pass
    through the fallback."""
    from pegasus_tpu.runtime.perf_counters import counters

    cli = gcluster.cli
    hit = set()
    for i in range(60):
        hk = b"hk%d" % i
        cli.set(hk, b"sk", b"v%d" % i)
        hit.add(group_of(1, _pidx(hk), GROUPS))
    assert hit == {0, 1}, "keys must land on BOTH groups"
    for i in range(60):
        assert cli.get(b"hk%d" % i, b"sk") == b"v%d" % i
    rows = {hk for hk, _, _ in cli.get_scanner()}
    assert {b"hk%d" % i for i in range(60)} <= rows
    # raw accumulator, not value(): the rate's rolling window could have
    # rolled to 0 between the traffic and this read
    assert counters.rate("serve.group.handoff_count")._value >= 1, \
        "sharded connections must be handed off, not relayed"
    snap = counters.snapshot(prefix="serve.group")
    assert snap.get("serve.group.active") == GROUPS


def test_partition_hash_sanity_error_via_relay(gcluster):
    """An unsharded raw connection stays on the parent's relay path; a
    deliberately misrouted partition_index must surface the worker's
    partition-hash sanity rejection, not hang or misserve."""
    from pegasus_tpu.rpc import messages as msg

    node = gcluster.stubs[0]
    host, _, port = node.address.rpartition(":")
    conn = RpcConnection((host, int(port)))
    try:
        key = key_schema.generate_key(b"sane", b"sk")
        h = key_schema.key_hash(key)
        right = h % PARTITIONS
        wrong = (right + 1) % PARTITIONS
        with pytest.raises(RpcError) as ei:
            conn.call("RPC_RRDB_RRDB_GET", codec.encode(msg.KeyRequest(key)),
                      app_id=1, partition_index=wrong, partition_hash=h,
                      timeout=10.0)
        assert ei.value.err in (ERR_INVALID_STATE,), ei.value
        assert "partition hash" in ei.value.text
    finally:
        conn.close()


def test_node_level_fanout_merges_groups(gcluster):
    """A node-level remote command has no partition route: the router
    fans it out to every group executor and joins the results."""
    from pegasus_tpu.runtime.remote_command import (RemoteCommandRequest,
                                                    RemoteCommandResponse)

    node = gcluster.stubs[0]
    host, _, port = node.address.rpartition(":")
    conn = RpcConnection((host, int(port)))
    try:
        _, body = conn.call("RPC_CLI_CLI_CALL", codec.encode(
            RemoteCommandRequest("flush-log", [])), timeout=30.0)
        result = codec.decode(RemoteCommandResponse, body).output
        # one "flushed N logs" line per group executor
        assert len([l for l in result.splitlines() if "flushed" in l]) \
            == GROUPS, result
    finally:
        conn.close()


def test_batch_get_fanout(gcluster):
    """batch_get pipelines per-(node, partition) waves across both
    groups; order and NOT_FOUND semantics match per-key get."""
    cli = gcluster.cli
    items = [(b"bg%d" % i, b"sk") for i in range(20)]
    for hk, sk in items:
        cli.set(hk, sk, b"val-" + hk)
    vals = cli.batch_get(items + [(b"bg-missing", b"sk")])
    assert vals[:-1] == [b"val-" + hk for hk, _ in items]
    assert vals[-1] is None


def test_unordered_scanners_prefetch(gcluster):
    """get_unordered_scanners opens every partition's session as one
    fan-out wave; the union of scanners covers every written key."""
    cli = gcluster.cli
    want = set()
    for i in range(24):
        hk = b"sc%d" % i
        cli.set(hk, b"sk", b"x")
        want.add(hk)
    got = set()
    for sc in cli.get_unordered_scanners():
        for hk, _, _ in sc:
            got.add(hk)
    assert want <= got


def test_kill_group_clean_errors_then_restart_reserves(gcluster):
    """Kill group 0 on every node mid-traffic: its partitions fail FAST
    with clean errors (no hangs), group 1 keeps serving, and after
    restart_group the partitions re-serve reads AND writes (parent
    replays its cached open-replica state; decrees recover from plog)."""
    cli = gcluster.cli
    g0 = _keys_for_group(0, 6)
    g1 = _keys_for_group(1, 6)
    for hk in g0 + g1:
        cli.set(hk, b"sk", b"pre")
    for node in gcluster.stubs:
        node.kill_group(0)
    old_timeout, cli.timeout = cli.timeout, 5.0
    try:
        t0 = time.monotonic()
        for hk in g0[:3]:
            with pytest.raises(PegasusError):
                cli.get(hk, b"sk")
        assert time.monotonic() - t0 < 30, "dead-group errors must be fast"
        for hk in g1:     # the sibling group is unaffected
            assert cli.get(hk, b"sk") == b"pre"
        for node in gcluster.stubs:
            node.restart_group(0)
        for hk in g0:
            assert cli.get(hk, b"sk") == b"pre"   # state survived the kill
        cli.set(g0[0], b"sk", b"post")            # writes re-quorum too
        assert cli.get(g0[0], b"sk") == b"post"
    finally:
        cli.timeout = old_timeout
    from pegasus_tpu.runtime.perf_counters import counters

    # the monotone total, NOT the raw window accumulator: the metric-
    # history sampler (and any other scraper) rolls the rate window on a
    # cadence, zeroing _value at arbitrary points mid-test
    assert counters.rate("serve.group.restart_count").total() \
        >= len(gcluster.stubs), "every node must have restarted group 0"
    snap = counters.snapshot(prefix="serve.group")
    assert snap.get("serve.group.active") == GROUPS


def test_partition_split_crosses_groups(tmp_path):
    """Partition split on a grouped node: a child partition's owner group
    can differ from its parent's (child pidx = parent + old_count, and
    old_count % n_groups != 0 moves the group) — the stub must learn
    across sibling executors through the public router instead of
    silently skipping the seed. Partition counts are powers of two, so
    3 groups guarantees every child of a 4-partition app crosses."""
    from pegasus_tpu.meta import messages as mm
    from pegasus_tpu.meta.meta_server import RPC_CM_SPLIT_APP

    c = MiniCluster(tmp_path, n_nodes=2, serve_groups=3)
    cli = c.create("spl", partitions=4, replicas=2)
    try:
        before = cli.resolver.partition_count
        assert any(group_of(1, p, 3) != group_of(1, p + before, 3)
                   for p in range(before)), "fixture must cross groups"
        rows = {b"sp%d" % i: b"v%d" % i for i in range(40)}
        for hk, v in rows.items():
            cli.set(hk, b"sk", v)
        r = c.ddl(RPC_CM_SPLIT_APP, mm.SplitAppRequest("spl"),
                  mm.SplitAppResponse)
        assert r.error == 0, r.error_text
        cli.resolver.refresh()
        assert cli.resolver.partition_count == 2 * before
        for hk, v in rows.items():
            assert cli.get(hk, b"sk") == v, hk
    finally:
        cli.close()
        c.stop()


# --------------------------------------------------- dispatch chaos seam


def test_serve_dispatch_fail_point():
    """serve.dispatch is the wedged-group chaos seam: raise() rejects the
    request with ERR_BUSY (clean error, connection survives), sleep()
    stalls dispatch for its duration (the client's timeout is the
    bound)."""
    from pegasus_tpu.runtime import fail_points

    srv = RpcServer().start()
    srv.register("ECHO", lambda h, b: b)
    conn = RpcConnection(srv.address)
    fail_points.setup()
    try:
        fail_points.cfg("serve.dispatch", "raise(wedged group)")
        with pytest.raises(RpcError) as ei:
            conn.call("ECHO", b"x", timeout=5.0)
        assert ei.value.err == ERR_BUSY
        fail_points.cfg("serve.dispatch", "sleep(50)")
        t0 = time.monotonic()
        _, body = conn.call("ECHO", b"y", timeout=5.0)
        assert body == b"y" and time.monotonic() - t0 >= 0.05
        fail_points.cfg("serve.dispatch", "off()")
        _, body = conn.call("ECHO", b"z", timeout=5.0)
        assert body == b"z"
    finally:
        fail_points.teardown()
        conn.close()
        srv.stop()


def test_dispatch_queue_depth_gauge_exports():
    """Bounded dispatch: beyond-pool requests QUEUE (no raw thread per
    request) and the backlog is observable via
    rpc.server.dispatch_queue_depth."""
    import threading

    from pegasus_tpu.runtime.perf_counters import counters

    srv = RpcServer().start()
    gate = threading.Event()

    def slow(h, b):
        gate.wait(10.0)
        return b

    srv.register("SLOW", slow)
    conns = [RpcConnection(srv.address) for _ in range(4)]
    try:
        n = srv.POOL_WORKERS + 8
        pends = []
        for i in range(n):
            conn = conns[i % len(conns)]
            pends.append((conn, conn.call_many_send([("SLOW", b"x")])))
        # generous: late in a full tier-1 run this process carries many
        # hundreds of live threads, and GIL scheduling can take seconds
        # to drain 24 reads through 4 connection read loops
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            with srv._busy_lock:
                busy = srv._busy
            if busy == n:
                break
            time.sleep(0.02)
        # pool saturated (16 running) + 8 QUEUED — no raw overflow thread
        assert busy == n, f"expected {n} submitted-not-finished, saw {busy}"
        # the backlog is exported on /metrics (the gauge is process-global,
        # so other in-process servers may overwrite the value — presence +
        # final drain-to-zero are the stable assertions)
        assert "rpc.server.dispatch_queue_depth" in counters.snapshot()
        gate.set()
        for conn, pend in pends:
            conn.call_many_collect(pend, [("SLOW", b"x")], timeout=20.0)
        # a worker sends its reply before it leaves the pool's count
        deadline = time.monotonic() + 20.0
        while srv._busy and time.monotonic() < deadline:
            time.sleep(0.02)
        with srv._busy_lock:
            assert srv._busy == 0
        assert counters.number(
            "rpc.server.dispatch_queue_depth").value() >= 0
    finally:
        gate.set()
        for c in conns:
            c.close()
        srv.stop()
