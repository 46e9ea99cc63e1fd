"""ISSUE 8 acceptance + chaos: decree-anchored consistency audits, the
replication-lag plane, and the cluster doctor's one-verdict fold.

Onebox acceptance (pinned here):
  - under concurrent YCSB-A-style load, `trigger_audit` across all
    partitions reports ZERO mismatches, with identical digests at
    identical decrees on every replica;
  - with the `audit.digest` fail point armed on one secondary,
    `cluster_doctor` returns `critical` naming exactly that
    (app, pidx, node);
  - a mid-audit node kill degrades the audit to `inconclusive` — never a
    false mismatch.
"""

import json
import threading
import time

import pytest

from pegasus_tpu.collector.cluster_doctor import (ClusterCaller,
                                                  run_cluster_audit,
                                                  run_cluster_doctor)
from pegasus_tpu.collector.info_collector import rollup_slow_requests
from pegasus_tpu.meta import messages as mm
from pegasus_tpu.meta.meta_server import RPC_CM_QUERY_CONFIG
from pegasus_tpu.runtime import fail_points as fp
from pegasus_tpu.runtime.perf_counters import counters

from tests.test_satellites import MiniCluster


@pytest.fixture
def cluster(tmp_path):
    c = MiniCluster(tmp_path)
    yield c
    c.stop()


@pytest.fixture
def failpoints():
    fp.setup()
    yield fp
    fp.teardown()


def _quiet_breakers():
    """The counter registry is process-global: an earlier test's tripped
    lane breaker or queue-depth gauge must not leak into a healthy-verdict
    assertion here."""
    counters.number("compact.lane.breaker_open").set(0)
    counters.number("read.lane.breaker_open").set(0)
    counters.number("rpc.server.dispatch_queue_depth").set(0)


def _partition_members(cluster, app_name, pidx):
    cfg = cluster.ddl(RPC_CM_QUERY_CONFIG, mm.QueryConfigRequest(app_name),
                      mm.QueryConfigResponse)
    pc = cfg.partitions[pidx]
    return cfg.app.app_id, pc.primary, list(pc.secondaries)


class _Load:
    """Background YCSB-A-ish read/update mix against one table."""

    def __init__(self, cli, keys=64, threads=3):
        self.cli = cli
        self.stop = threading.Event()
        self.errors = []
        self.ops = 0

        def worker(tid):
            i = 0
            while not self.stop.is_set():
                k = b"user%05d" % ((i * 7 + tid * 13) % keys)
                try:
                    if i % 2:
                        self.cli.get(k, b"f0")
                    else:
                        self.cli.set(k, b"f0", b"v%d.%d" % (tid, i))
                    self.ops += 1
                except Exception as e:  # noqa: BLE001 - recorded, asserted
                    self.errors.append(repr(e))
                i += 1

        self.threads = [threading.Thread(target=worker, args=(t,))
                        for t in range(threads)]

    def __enter__(self):
        for t in self.threads:
            t.start()
        return self

    def __exit__(self, *exc):
        self.stop.set()
        for t in self.threads:
            t.join(timeout=10)
        return False


# ------------------------------------------------------- onebox acceptance


def test_audit_under_load_zero_mismatches(cluster):
    """The acceptance shape: concurrent load, audit across every
    partition, identical digests at identical decrees on ALL replicas."""
    cli = cluster.create("ycsbish", partitions=4)
    for i in range(64):
        cli.set(b"user%05d" % i, b"f0", b"init%d" % i)
    with _Load(cli) as load:
        time.sleep(0.2)  # the audit must race real traffic
        report = run_cluster_audit([cluster.meta_addr], wait_s=20.0)
    assert report["mismatches"] == []
    assert report["inconclusive"] == []
    assert sorted(report["ok"]) == sorted(report["digests"])
    assert report["partitions"] == 4 and len(report["ok"]) == 4
    for gpid, per_node in report["digests"].items():
        assert len(per_node) == 3, f"{gpid}: not every replica reported"
        decrees = {d["decree"] for d in per_node.values()}
        digests = {d["digest"] for d in per_node.values()}
        assert len(decrees) == 1, f"{gpid}: digests at different decrees"
        assert len(digests) == 1, f"{gpid}: digest mismatch {per_node}"
    assert load.ops > 0 and not load.errors
    cli.close()


def test_corrupt_secondary_flags_exactly_that_partition(cluster, failpoints):
    """audit.digest armed on ONE secondary of ONE partition: the audit
    names exactly (app, pidx, node); the doctor goes critical with the
    same naming; every other partition stays clean."""
    cli = cluster.create("audchaos", partitions=2)
    for i in range(40):
        cli.set(b"k%03d" % i, b"s", b"v%d" % i)
    app_id, primary, secondaries = _partition_members(cluster, "audchaos", 0)
    victim = secondaries[0]
    # clean baseline first: the doctor must call THIS cluster healthy
    clean = run_cluster_audit([cluster.meta_addr], wait_s=20.0)
    assert clean["mismatches"] == [] and len(clean["ok"]) == 2
    time.sleep(0.5)  # beacons fold the audit states into the meta
    _quiet_breakers()
    verdict = run_cluster_doctor([cluster.meta_addr])
    assert verdict["verdict"] == "healthy", verdict["causes"]
    assert verdict["evidence"]["audit"]["mismatches"] == []

    failpoints.cfg("audit.digest", f"return({victim}@{app_id}.0)")
    report = run_cluster_audit([cluster.meta_addr], wait_s=20.0)
    assert len(report["mismatches"]) == 1
    m = report["mismatches"][0]
    assert (m["app"], m["pidx"], m["node"]) == ("audchaos", 0, victim)
    assert m["digest"].startswith("deadbeef")
    assert m["digest"] != m["expected"]
    # the OTHER partition's replicas still agree
    assert f"{app_id}.1" in report["ok"]

    time.sleep(0.6)  # corrupted digest rides the next beacons
    verdict = run_cluster_doctor([cluster.meta_addr])
    assert verdict["verdict"] == "critical"
    crit = [c for c in verdict["causes"] if c["severity"] == "critical"]
    assert any(f"{app_id}.0" in c["cause"] and victim in c["cause"]
               for c in crit), crit
    mm_ = verdict["evidence"]["audit"]["mismatches"]
    assert any(e["gpid"] == f"{app_id}.0" and e["node"] == victim
               for e in mm_)
    cli.close()


def test_midaudit_node_kill_is_inconclusive_not_mismatch(cluster):
    """Kill a member mid-audit: the partition degrades to inconclusive
    (the dead node is named) and NEVER reports a false mismatch — the
    equal-decree comparison rule."""
    cli = cluster.create("audkill", partitions=2)
    for i in range(30):
        cli.set(b"k%03d" % i, b"s", b"v%d" % i)
    app_id, primary, secondaries = _partition_members(cluster, "audkill", 0)
    victim = secondaries[0]
    # trigger on the primary, then kill the secondary BEFORE collection —
    # a genuinely mid-audit death
    caller = ClusterCaller([cluster.meta_addr])
    out = json.loads(caller.remote_command(
        primary, "trigger-audit", [f"{app_id}.0"]))
    assert out["digest"] and out["decree"] > 0
    caller.close()
    for stub in list(cluster.stubs):
        if stub.address == victim:
            stub.stop()
            cluster.stubs.remove(stub)
    report = run_cluster_audit([cluster.meta_addr], wait_s=1.0)
    assert report["mismatches"] == [], \
        "a dead member must never fake a mismatch"
    assert any(e.get("node") == victim for e in report["inconclusive"]), \
        report["inconclusive"]
    # the doctor's audit evidence stays mismatch-free too (stale beacon
    # digests sit at an older decree: pending, not compared)
    verdict = run_cluster_doctor([cluster.meta_addr])
    assert verdict["evidence"]["audit"]["mismatches"] == []
    cli.close()


# ------------------------------------------------- replication-lag plane


def test_beacon_carries_committed_and_applied_distinctly(cluster):
    """Satellite: the beacon (and query_replica_info / replica-state)
    reports applied_decree distinct from committed_decree, so the lag
    gauges have a truthful source."""
    cli = cluster.create("lagt", partitions=1)
    for i in range(20):
        cli.set(b"k%d" % i, b"s", b"v")
    time.sleep(0.5)  # beacons land
    states = cluster.meta._node_states
    assert states, "beacons carried no replica_states"
    seen = 0
    for node, per_gpid in states.items():
        for gpid, st in per_gpid.items():
            assert "committed" in st and "applied" in st and "status" in st
            # healthy replicas: engine applied == replication committed
            assert st["applied"] == st["committed"]
            seen += 1
    assert seen >= 3  # every member of the 1-partition group reported
    # gauges exist per partition (process-global registry in the onebox)
    snap = counters.snapshot(prefix="replica.")
    assert any(k.endswith(".committed_decree") for k in snap)
    assert any(k.endswith(".applied_decree") for k in snap)
    assert any(k.endswith(".secondary_gap_max") for k in snap)
    # ReplicaStateResponse surfaces last_applied (append-only evolution)
    app_id, primary, _ = _partition_members(cluster, "lagt", 0)
    st = cluster.meta._query_replica_state(primary, app_id, 0)
    assert st is not None and st.last_applied == st.last_committed > 0
    cli.close()


def test_doctor_lag_fold_flags_commit_and_apply_distinctly(monkeypatch):
    """The lag fold names commit lag and apply lag as DISTINCT degraded
    causes (unit over the doctor's fold — deterministic, no beacon
    race)."""
    from pegasus_tpu.collector import cluster_doctor as cd

    monkeypatch.setenv("PEGASUS_DOCTOR_GAP_DEGRADED", "10")
    # lag is measured WITHIN each replica's own beacon snapshot (never
    # across nodes — beacons are asynchronous, cross-node compares would
    # flag healthy fast-writing clusters): commit lag = prepared-committed
    # (staged, commit point never arrived), apply lag = committed-applied
    state = {"replica_states": {
        "n1:1": {"1.0": {"gpid": "1.0", "status": "PRIMARY",
                         "prepared": 500, "committed": 500,
                         "applied": 500}},
        "n2:1": {"1.0": {"gpid": "1.0", "status": "SECONDARY",
                         "prepared": 500, "committed": 480,
                         "applied": 480}},   # commit lag
        "n3:1": {"1.0": {"gpid": "1.0", "status": "SECONDARY",
                         "prepared": 500, "committed": 500,
                         "applied": 420}},   # apply lag
    }}
    causes, evidence = [], {}
    cd._check_lag(state, causes, evidence)
    kinds = {(o["node"], o["kind"]) for o in evidence["lag"]["offenders"]}
    assert kinds == {("n2:1", "commit"), ("n3:1", "apply")}
    assert any("behind on COMMIT by 20" in c["cause"] and "n2:1" in c["cause"]
               for c in causes), causes
    assert any("behind on APPLY by 80" in c["cause"] and "n3:1" in c["cause"]
               for c in causes), causes
    assert evidence["lag"]["worst"] == {"commit_gap": 20, "apply_gap": 80}


# ------------------------------------------------ slow-request rollup


def test_slow_request_cluster_rollup_merges_worst_first():
    def fetch(node):
        base = {"n1": [{"trace_id": "a", "duration_us": 100, "op": "put"},
                       {"trace_id": "b", "duration_us": 900, "op": "get"}],
                "n2": [{"trace_id": "c", "duration_us": 500, "op": "put"}],
                "n3": "not json"}
        v = base[node]
        return v if isinstance(v, str) else json.dumps(v)

    merged = rollup_slow_requests(fetch, ["n1", "n2", "n3"], last=2)
    assert [t["trace_id"] for t in merged] == ["b", "c"]  # worst first
    assert merged[0]["node"] == "n1" and merged[1]["node"] == "n2"


def test_shell_slow_requests_cluster_and_doctor(cluster, monkeypatch):
    """`slow_requests --cluster` merges every node's ledger; the shell's
    cluster_doctor prints the one-verdict line."""
    import io

    from pegasus_tpu.runtime.tracing import REQUEST_TRACER
    from pegasus_tpu.shell.main import Shell

    cli = cluster.create("slowt", partitions=1)
    monkeypatch.setattr(REQUEST_TRACER, "slow_threshold_us", 1)
    cli.set(b"hk", b"s", b"v")  # every request is now "slow"
    _quiet_breakers()
    out = io.StringIO()
    sh = Shell([cluster.meta_addr], out=out)
    sh.run_line("slow_requests --cluster 5")
    merged = json.loads(out.getvalue())
    assert merged and all("node" in t and "spans" in t for t in merged)
    assert all(merged[i]["duration_us"] >= merged[i + 1]["duration_us"]
               for i in range(len(merged) - 1))
    out.truncate(0), out.seek(0)
    sh.run_line("cluster_doctor")
    text = out.getvalue()
    assert "cluster verdict: HEALTHY" in text
    cli.close()


# ------------------------------------------------------------ digest unit


def test_state_digest_batched_fold_equals_the_scalar_formula(tmp_path):
    """state_digest folds records through the batched crc64 (a 1 KB
    record costs ~0.2 ms in the scalar python loop): it must stay the
    digest the per-record formula defines, across a batch boundary."""
    import struct

    from pegasus_tpu.base.crc64 import crc64
    from pegasus_tpu.engine.db import EngineOptions, LsmEngine

    eng = LsmEngine(str(tmp_path / "e"), EngineOptions(backend="cpu"))
    for i in range(4100):   # one full 4096-record fold + a tail
        eng.put(b"\x00\x04hk%02dsk%05d" % (i % 7, i), b"v" * (i % 50),
                expire_ts=(0 if i % 3 else 10_000_000 + i), decree=i + 1)
    now = 5_000
    xor = add = n = 0
    for k, v, e in eng.scan(now=now):
        c = crc64(struct.pack("<I", len(k)) + k
                  + struct.pack("<q", int(e)) + v)
        xor ^= c
        add = (add + c) & 0xFFFFFFFFFFFFFFFF
        n += 1
    assert n == 4100
    assert eng.state_digest(now=now) == {
        "digest": f"{xor:016x}{add:016x}", "records": n, "now": now}
    eng.close()


def test_state_digest_layout_independent(tmp_path):
    """The digest is a function of logical contents only: flushing,
    compacting, or re-leveling must not change it; a data change must."""
    from pegasus_tpu.engine.db import EngineOptions, LsmEngine

    eng = LsmEngine(str(tmp_path / "e"), EngineOptions(backend="cpu"))
    d = 0
    for i in range(50):
        d += 1
        eng.put(b"k%03d" % i, b"v%d" % i, decree=d)
    now = 10_000
    base = eng.state_digest(now=now)
    assert base["records"] == 50
    eng.flush()
    assert eng.state_digest(now=now) == base, "flush changed the digest"
    eng.manual_compact(now=now)
    assert eng.state_digest(now=now) == base, "compaction changed the digest"
    # overwrite with the SAME bytes: still identical (newest-wins walk)
    d += 1
    eng.put(b"k000", b"v0", decree=d)
    assert eng.state_digest(now=now)["digest"] == base["digest"]
    # tombstone: digest changes, and compacting the tombstone away does
    # not change it back differently on this replica vs one that never
    # compacted (tombstones are excluded from the fold)
    d += 1
    eng.delete(b"k001", decree=d)
    after_del = eng.state_digest(now=now)
    assert after_del["digest"] != base["digest"]
    assert after_del["records"] == 49
    eng.flush()
    eng.manual_compact(now=now)
    assert eng.state_digest(now=now) == after_del
    eng.close()


def test_trigger_audit_is_a_noop_mutation(tmp_path):
    """trigger_audit advances the decree like any write but mutates no
    data; its digest matches an offline state_digest at the same clock."""
    from pegasus_tpu.engine.server_impl import PegasusServer
    from pegasus_tpu.rpc import messages as msg
    from pegasus_tpu.rpc.task_codes import RPC_TRIGGER_AUDIT

    srv = PegasusServer(str(tmp_path / "p"))
    srv.on_batched_write_requests(
        1, 0, [(RPC_TRIGGER_AUDIT,
                msg.TriggerAuditRequest(audit_id=7, now=5000))])
    assert srv.engine.last_committed_decree() == 1
    la = srv.last_audit
    assert la["audit_id"] == 7 and la["decree"] == 1 and la["records"] == 0
    assert la["digest"] == srv.engine.state_digest(now=5000)["digest"]
    srv.close()
