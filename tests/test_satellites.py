"""Satellite subsystem tests: bulk load, duplication, partition split,
cold backup/restore — over the real socket cluster (reference function-test
equivalents: bulk_load, test_split, backup_and_restore, dup tests)."""

import json
import time

import pytest

from pegasus_tpu.base import key_schema
from pegasus_tpu.base.value_schema import SCHEMAS
from pegasus_tpu.client import MetaResolver, PegasusClient
from pegasus_tpu.engine import bulk_load as bl
from pegasus_tpu.engine import EngineOptions
from pegasus_tpu.meta import MetaServer
from pegasus_tpu.meta import messages as mm
from pegasus_tpu.meta.meta_server import (RPC_CM_BACKUP_APP, RPC_CM_CREATE_APP,
                                          RPC_CM_QUERY_CONFIG,
                                          RPC_CM_RESTORE_APP, RPC_CM_SPLIT_APP,
                                          RPC_CM_START_BULK_LOAD)
from pegasus_tpu.replication.duplicator import MutationDuplicator
from pegasus_tpu.replication.replica_stub import ReplicaStub
from pegasus_tpu.rpc import codec
from pegasus_tpu.rpc.transport import RpcConnection, RpcServer


class MiniCluster:
    def __init__(self, root, n_nodes=3, serve_groups=0, remote_clusters=None,
                 cluster_id=1, fd_grace_seconds=60, options_factory=None):
        self.meta = MetaServer(str(root / "meta.json"),
                               fd_grace_seconds=fd_grace_seconds)
        self.rpc = RpcServer().start()
        for code, fn in self.meta.rpc_handlers().items():
            self.rpc.register(code, fn)
        self.meta_addr = f"{self.rpc.address[0]}:{self.rpc.address[1]}"
        if serve_groups and serve_groups >= 1:
            # shared-nothing partition-group serving: each node forks
            # serve_groups worker processes behind one public router
            from pegasus_tpu.replication.serve_groups import GroupedReplicaNode

            self.stubs = [GroupedReplicaNode(str(root / f"n{i}"),
                                             [self.meta_addr],
                                             groups=serve_groups,
                                             remote_clusters=remote_clusters,
                                             cluster_id=cluster_id).start(0.2)
                          for i in range(n_nodes)]
        else:
            self.stubs = [ReplicaStub(str(root / f"n{i}"),
                                      [self.meta_addr],
                                      options_factory=options_factory,
                                      remote_clusters=remote_clusters,
                                      cluster_id=cluster_id).start(0.2)
                          for i in range(n_nodes)]
        self._conn = RpcConnection(self.rpc.address)

    def ddl(self, code, req, resp_cls, timeout=30.0):
        _, body = self._conn.call(code, codec.encode(req), timeout=timeout)
        return codec.decode(resp_cls, body)

    def create(self, name, partitions=2, replicas=3):
        r = self.ddl(RPC_CM_CREATE_APP,
                     mm.CreateAppRequest(name, partitions, replicas),
                     mm.CreateAppResponse)
        assert r.error == 0
        return PegasusClient(MetaResolver([self.meta_addr], name))

    def stop(self):
        self._conn.close()
        for s in self.stubs:
            s.stop()
        self.rpc.stop()


@pytest.fixture
def cluster(tmp_path):
    c = MiniCluster(tmp_path)
    yield c
    c.stop()


# ------------------------------------------------------------- bulk load

def test_raw_set_roundtrip(tmp_path):
    p = str(tmp_path / "set.raw")
    rows = [(b"hk%d" % i, b"sk", b"v%d" % i, 0) for i in range(20)]
    assert bl.write_raw_set(p, rows) == 20
    assert list(bl.read_raw_set(p)) == rows


def test_bulk_load_end_to_end(cluster, tmp_path):
    cli = cluster.create("blt", partitions=2)
    provider = tmp_path / "provider"
    n_total = 60
    # offline producer: records partitioned by hash, like the Spark job
    per_part = {0: [], 1: []}
    for i in range(n_total):
        hk, sk, v = b"bl%d" % i, b"s", b"val%d" % i
        h = key_schema.key_hash(key_schema.generate_key(hk, sk))
        per_part[h % 2].append((hk, sk, v, 0))
    for pidx, rows in per_part.items():
        pdir = provider / "blt" / "2" / str(pidx)
        pdir.mkdir(parents=True)
        bl.write_raw_set(str(pdir / "part0.raw"), rows[: len(rows) // 2])
        bl.write_raw_set(str(pdir / "part1.raw"), rows[len(rows) // 2:])
    bl.write_metadata(str(provider), "blt", 2)
    r = cluster.ddl(RPC_CM_START_BULK_LOAD,
                    mm.StartBulkLoadRequest("blt", str(provider)),
                    mm.StartBulkLoadResponse)
    assert r.error == 0, r.error_text
    assert r.ingested_records == n_total
    for i in range(n_total):
        assert cli.get(b"bl%d" % i, b"s") == b"val%d" % i
    cli.close()


def test_bulk_load_async_session_controls(cluster, tmp_path):
    """Async bulk load is a controllable session: pause holds the partition
    walk, restart resumes it, query reports progress (reference bulk-load
    state machine, shell bulk_load.cpp control verbs)."""
    import time as _time

    from pegasus_tpu.meta.meta_server import (RPC_CM_CONTROL_BULK_LOAD,
                                              RPC_CM_QUERY_BULK_LOAD)

    cli = cluster.create("blas", partitions=2)
    provider = tmp_path / "prov_async"
    per_part = {0: [], 1: []}
    n_total = 40
    for i in range(n_total):
        hk, sk, v = b"as%d" % i, b"s", b"av%d" % i
        h = key_schema.key_hash(key_schema.generate_key(hk, sk))
        per_part[h % 2].append((hk, sk, v, 0))
    for pidx, rows in per_part.items():
        pdir = provider / "blas" / "2" / str(pidx)
        pdir.mkdir(parents=True)
        bl.write_raw_set(str(pdir / "set.raw"), rows)
    bl.write_metadata(str(provider), "blas", 2)
    # pause before starting the session: the worker must hold at 0 done
    app_id = cli.resolver.app_id
    r = cluster.ddl(RPC_CM_START_BULK_LOAD,
                    mm.StartBulkLoadRequest("blas", str(provider),
                                            async_start=True),
                    mm.StartBulkLoadResponse)
    assert r.error == 0, r.error_text
    r = cluster.ddl(RPC_CM_CONTROL_BULK_LOAD,
                    mm.ControlBulkLoadRequest("blas", "pause"),
                    mm.ControlBulkLoadResponse)
    # the session may legitimately finish before the pause lands on a fast
    # box; only assert the control surface behaves for whichever state
    q = cluster.ddl(RPC_CM_QUERY_BULK_LOAD, mm.QueryBulkLoadRequest("blas"),
                    mm.QueryBulkLoadResponse)
    assert q.status in ("paused", "ingesting", "succeed")
    if q.status == "paused":
        held = cluster.ddl(RPC_CM_QUERY_BULK_LOAD,
                           mm.QueryBulkLoadRequest("blas"),
                           mm.QueryBulkLoadResponse)
        r = cluster.ddl(RPC_CM_CONTROL_BULK_LOAD,
                        mm.ControlBulkLoadRequest("blas", "restart"),
                        mm.ControlBulkLoadResponse)
        assert r.error == 0
    deadline = _time.time() + 15
    while _time.time() < deadline:
        q = cluster.ddl(RPC_CM_QUERY_BULK_LOAD,
                        mm.QueryBulkLoadRequest("blas"),
                        mm.QueryBulkLoadResponse)
        if q.status == "succeed":
            break
        _time.sleep(0.2)
    assert q.status == "succeed", q.status
    assert q.ingested_records == n_total
    assert q.done_partitions == q.total_partitions == 2
    for i in range(n_total):
        assert cli.get(b"as%d" % i, b"s") == b"av%d" % i
    # double-start while a finished session exists is allowed again
    q = cluster.ddl(RPC_CM_CONTROL_BULK_LOAD,
                    mm.ControlBulkLoadRequest("blas", "pause"),
                    mm.ControlBulkLoadResponse)
    assert q.error == 1  # cannot pause a finished session
    cli.close()


def test_bulk_load_drops_misrouted_rows(tmp_path):
    """Rows that hash to another partition are filtered at ingest."""
    from pegasus_tpu.engine.db import LsmEngine

    eng = LsmEngine(str(tmp_path / "db"), EngineOptions(backend="cpu"))
    provider = tmp_path / "prov"
    pdir = provider / "t" / "4" / "1"
    pdir.mkdir(parents=True)
    rows = [(b"k%d" % i, b"s", b"v", 0) for i in range(40)]
    bl.write_raw_set(str(pdir / "all.raw"), rows)
    stats = bl.ingest_partition(eng, str(provider), "t", 4, 1, SCHEMAS[2])
    expect = sum(1 for hk, sk, _, _ in rows
                 if key_schema.key_hash(key_schema.generate_key(hk, sk)) % 4 == 1)
    assert stats["records"] == expect > 0
    eng.close()


# ------------------------------------------------------------ duplication

def test_duplication_ships_writes_to_remote_cluster(tmp_path):
    src = MiniCluster(tmp_path / "src", n_nodes=3)
    dst = MiniCluster(tmp_path / "dst", n_nodes=3)
    try:
        src_cli = src.create("dup", partitions=2)
        dst.create("dup", partitions=2).close()
        # attach a duplicator to every source replica (the dup framework's
        # per-replica mutation_duplicator)
        dups = []
        for stub in src.stubs:
            for rep in stub._replicas.values():
                d = MutationDuplicator(
                    MetaResolver([dst.meta_addr], "dup"), cluster_id=1)
                rep.commit_hooks.append(d.on_commit)
                dups.append(d)
        for i in range(20):
            src_cli.set(b"d%d" % i, b"s", b"dv%d" % i)
        src_cli.delete(b"d0", b"s")
        for d in dups:
            assert d.flush(timeout=15)
        dst_cli = PegasusClient(MetaResolver([dst.meta_addr], "dup"))
        for i in range(1, 20):
            assert dst_cli.get(b"d%d" % i, b"s") == b"dv%d" % i, i
        assert dst_cli.get(b"d0", b"s") is None  # the delete shipped too
        for d in dups:
            d.stop()
        src_cli.close()
        dst_cli.close()
    finally:
        src.stop()
        dst.stop()


def test_duplicate_verify_timetag_lww(tmp_path):
    """A stale duplicate must not clobber a newer local write."""
    from pegasus_tpu.engine.server_impl import PegasusServer
    from pegasus_tpu.rpc import messages as msg, task_codes

    srv = PegasusServer(str(tmp_path / "db"), options=EngineOptions(backend="cpu"))
    key = key_schema.generate_key(b"h", b"s")
    now_us = int(time.time() * 1e6)
    d = srv.engine.last_committed_decree() + 1
    srv.on_batched_write_requests(
        d, now_us, [(task_codes.RPC_PUT, msg.UpdateRequest(key, b"local", 0))])
    stale = msg.DuplicateRequest(
        timestamp=now_us - 10_000_000, task_code=task_codes.RPC_PUT,
        raw_message=codec.encode(msg.UpdateRequest(key, b"stale", 0)),
        cluster_id=2, verify_timetag=True)
    r = srv.on_batched_write_requests(
        d + 1, now_us, [(task_codes.RPC_DUPLICATE, stale)])[0]
    assert r.error == 0 and "ignored" in r.error_hint
    assert srv.on_get(key).value == b"local"
    # a NEWER duplicate wins
    fresh = msg.DuplicateRequest(
        timestamp=now_us + 10_000_000, task_code=task_codes.RPC_PUT,
        raw_message=codec.encode(msg.UpdateRequest(key, b"fresh", 0)),
        cluster_id=2, verify_timetag=True)
    srv.on_batched_write_requests(
        d + 2, now_us, [(task_codes.RPC_DUPLICATE, fresh)])
    assert srv.on_get(key).value == b"fresh"
    srv.close()


# --------------------------------------------------------------- split

def test_partition_split_doubles_and_rebalances_keys(cluster):
    cli = cluster.create("sp", partitions=2)
    rows = {b"sp%d" % i: b"v%d" % i for i in range(40)}
    for hk, v in rows.items():
        cli.set(hk, b"s", v)
    r = cluster.ddl(RPC_CM_SPLIT_APP, mm.SplitAppRequest("sp"),
                    mm.SplitAppResponse)
    assert r.error == 0 and r.new_partition_count == 4
    # a fresh client sees 4 partitions and every key
    cli2 = PegasusClient(MetaResolver([cluster.meta_addr], "sp"))
    assert cli2.resolver.partition_count == 4
    for hk, v in rows.items():
        assert cli2.get(hk, b"s") == v, hk
    # new writes land on the doubled space
    for i in range(40, 60):
        cli2.set(b"sp%d" % i, b"s", b"v%d" % i)
        assert cli2.get(b"sp%d" % i, b"s") == b"v%d" % i
    # stale client re-routes transparently (partition-hash rejection path)
    for hk, v in rows.items():
        assert cli.get(hk, b"s") == v
    cli.close()
    cli2.close()


def test_split_stale_keys_gc_after_compact(cluster):
    cli = cluster.create("spgc", partitions=1)
    for i in range(30):
        cli.set(b"g%d" % i, b"s", b"v")
    cluster.ddl(RPC_CM_SPLIT_APP, mm.SplitAppRequest("spgc"), mm.SplitAppResponse)
    # manual compact every replica: stale halves disappear from storage
    total = 0
    app_id = None
    for stub in cluster.stubs:
        for (aid, pidx), rep in list(stub._replicas.items()):
            if rep.server.engine.opts.partition_mask:
                rep.server.engine.manual_compact()
    # count rows remaining per partition primary: each key exactly once
    cfg = cluster.ddl(RPC_CM_QUERY_CONFIG, mm.QueryConfigRequest("spgc"),
                      mm.QueryConfigResponse)
    seen = {}
    for stub in cluster.stubs:
        for (aid, pidx), rep in stub._replicas.items():
            if aid != cfg.app.app_id:
                continue
            if cfg.partitions[pidx].primary != stub.address:
                continue
            for k, _, _ in rep.server.engine.scan(b"", None, now=1):
                assert key_schema.key_hash(k) % 2 == pidx % 2
                seen[k] = seen.get(k, 0) + 1
    assert len(seen) == 30 and all(c == 1 for c in seen.values())
    cli.close()


# ------------------------------------------------------- backup/restore

def test_cold_backup_and_restore(cluster, tmp_path):
    cli = cluster.create("bk", partitions=2)
    for i in range(25):
        cli.set(b"bk%d" % i, b"s", b"bv%d" % i)
    backup_root = str(tmp_path / "backups")
    r = cluster.ddl(RPC_CM_BACKUP_APP,
                    mm.BackupAppRequest("bk", backup_root),
                    mm.BackupAppResponse)
    assert r.error == 0 and r.backup_id > 0
    # mutate after the backup; restore must show the backup-time view
    for i in range(25):
        cli.set(b"bk%d" % i, b"s", b"MUTATED")
    rr = cluster.ddl(RPC_CM_RESTORE_APP,
                     mm.RestoreAppRequest(backup_root, r.backup_id, "bk",
                                          "bk_restored"),
                     mm.RestoreAppResponse)
    assert rr.error == 0, rr.error_text
    rcli = PegasusClient(MetaResolver([cluster.meta_addr], "bk_restored"))
    for i in range(25):
        assert rcli.get(b"bk%d" % i, b"s") == b"bv%d" % i
    # original table unaffected
    assert cli.get(b"bk3", b"s") == b"MUTATED"
    cli.close()
    rcli.close()


def test_bulk_load_survives_primary_failover(cluster, tmp_path):
    """code-review r2: ingestion must replicate (same decree on every
    replica), not land only on the primary."""
    cli = cluster.create("blf", partitions=1)
    provider = tmp_path / "prov2"
    pdir = provider / "blf" / "1" / "0"
    pdir.mkdir(parents=True)
    bl.write_raw_set(str(pdir / "set.raw"),
                     [(b"fk%d" % i, b"s", b"fv%d" % i, 0) for i in range(15)])
    bl.write_metadata(str(provider), "blf", 1)
    r = cluster.ddl(RPC_CM_START_BULK_LOAD,
                    mm.StartBulkLoadRequest("blf", str(provider)),
                    mm.StartBulkLoadResponse)
    assert r.error == 0 and r.ingested_records == 15
    # kill the partition's primary node; data must survive on the promoted
    # secondary because ingestion committed through PacificA
    cfg = cluster.ddl(RPC_CM_QUERY_CONFIG, mm.QueryConfigRequest("blf"),
                      mm.QueryConfigResponse)
    victim = cfg.partitions[0].primary
    for stub in list(cluster.stubs):
        if stub.address == victim:
            stub.stop()
            cluster.stubs.remove(stub)
    cluster.meta.mark_node_dead(victim)
    for i in range(15):
        assert cli.get(b"fk%d" % i, b"s") == b"fv%d" % i, f"lost fk{i}"
    cli.close()


def test_geo_nul_bytes_in_keys(cluster):
    """code-review r2: geo index keys containing NUL parse exactly."""
    from pegasus_tpu.geo import GeoClient

    common = cluster.create("geo_nul_d", partitions=1)
    index = cluster.create("geo_nul_i", partitions=1)
    g = GeoClient(common, index)
    v = b"|".join([b"x", b"", b"", b"", b"121.4737", b"31.2304"])
    g.set(b"a\x00b", b"s\x00k", v)
    hits = g.search_radial(31.2304, 121.4737, 100)
    assert len(hits) == 1
    _, hk, sk, _ = hits[0]
    assert hk == b"a\x00b" and sk == b"s\x00k"
    common.close()
    index.close()


def test_covering_cells_large_radius_no_gaps():
    from pegasus_tpu.geo import cells as C

    # 50km radius at level 12 (~5km cells): every cell within the bbox of
    # the circle must be covered — check a ring of probe points
    got = set(C.covering_cells(31.0, 121.0, 50_000, 12))
    import math
    for ang in range(0, 360, 15):
        la = 31.0 + math.degrees(40_000 / C.EARTH_RADIUS_M) * math.sin(math.radians(ang))
        ln = 121.0 + math.degrees(40_000 / (C.EARTH_RADIUS_M * math.cos(math.radians(31)))) * math.cos(math.radians(ang))
        assert C.cell_id(la, ln, 12) in got, ang
