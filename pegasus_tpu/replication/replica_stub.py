"""Replica node: hosts PacificA replicas, beacons to meta, serves clients.

The rDSN replica_stub + pegasus_replication_service_app role (SURVEY.md
§2.4 'Service-app container', §3.1 boot path): one process = one node
address; the meta server opens/closes replicas here (RPC_CONFIG_PROPOSAL_*),
client writes route through the local replica's PacificA 2PC
(replica.client_write), prepares arrive from peer nodes over RPC, learners
pull checkpoint+log-tail state, and a beacon thread keeps the meta lease.
"""

import json
import os
import socket
import threading
import time

from ..engine import EngineOptions
from ..engine.replica_service import ReplicaService, WRITE_CODES
from ..meta import messages as mm
from ..meta.meta_server import (RPC_CLOSE_REPLICA, RPC_FD_BEACON,
                                RPC_OPEN_REPLICA, RPC_REPLICA_STATE)
from ..rpc import codec
from ..rpc.transport import (ConnectionPool, ERR_INVALID_STATE,
                             ERR_OBJECT_NOT_FOUND, RpcError, RpcServer)
from ..runtime.tasking import spawn_thread
from .mutation_log import LogMutation
from .replica import GroupView, PRIMARY, PrepareRejected, Replica, ReplicaError

RPC_PREPARE = "RPC_PREPARE"
RPC_LEARN = "RPC_LEARN"
# block-shipped learn plane (ISSUE 13): manifest-diff handshake, chunked
# pinned-block fetch, log-tail pull, pin release
RPC_LEARN_PREPARE = "RPC_LEARN_PREPARE"
RPC_LEARN_FETCH = "RPC_LEARN_FETCH"
RPC_LEARN_TAIL = "RPC_LEARN_TAIL"
RPC_LEARN_FINISH = "RPC_LEARN_FINISH"
RPC_REMOTE_COMMAND = "RPC_CLI_CLI_CALL"


class _RemotePeer:
    """Peer-node proxy with the Replica peer interface (on_prepare,
    fetch_learn_state) over the RPC transport."""

    def __init__(self, stub: "ReplicaStub", addr: str, app_id: int, pidx: int):
        self.stub = stub
        self.addr = addr
        self.app_id = app_id
        self.pidx = pidx

    def _call(self, code, req):
        host, _, port = self.addr.rpartition(":")
        try:
            # one SHARDED connection per (peer, partition): the peer's
            # partition-group router can hand the whole socket to the
            # owning group executor, and the header carries the route
            conn = self.stub.pool.get((host, int(port)),
                                      shard=("rep", self.app_id, self.pidx))
            _, body = conn.call(code, codec.encode(req), app_id=self.app_id,
                                partition_index=self.pidx, timeout=10.0)
            return body
        except (RpcError, OSError) as e:
            raise ConnectionError(str(e))

    def on_prepare(self, ballot, m: LogMutation, committed_decree: int):
        body = self._call(RPC_PREPARE, mm.PrepareRequest(
            app_id=self.app_id, pidx=self.pidx, ballot=ballot,
            committed_decree=committed_decree, mutation=codec.encode(m)))
        resp = codec.decode(mm.PrepareResponse, body)
        if resp.error:
            raise PrepareRejected(resp.reason, resp.last_prepared)

    def on_prepare_batch(self, ballot, ms, committed_decree: int) -> int:
        """Windowed prepare: the whole decree window rides ONE RPC; the
        peer acks its highest contiguous prepared decree."""
        body = self._call(RPC_PREPARE, mm.PrepareRequest(
            app_id=self.app_id, pidx=self.pidx, ballot=ballot,
            committed_decree=committed_decree,
            mutations=[codec.encode(m) for m in ms]))
        resp = codec.decode(mm.PrepareResponse, body)
        if resp.error:
            raise PrepareRejected(resp.reason, resp.last_prepared)
        return resp.last_prepared

    def on_prepare_windows(self, ballot, windows, committed_decree: int) -> int:
        """Catch-up fast path: every chunked window of the backlog is
        encoded up front and the requests leave in ONE coalesced transport
        send (RpcConnection.call_many — writev-style), then the responses
        are collected in order. -> the peer's final acked decree."""
        host, _, port = self.addr.rpartition(":")
        reqs = [(RPC_PREPARE, codec.encode(mm.PrepareRequest(
            app_id=self.app_id, pidx=self.pidx, ballot=ballot,
            committed_decree=committed_decree,
            mutations=[codec.encode(m) for m in w])),
            self.app_id, self.pidx, 0) for w in windows]
        try:
            conn = self.stub.pool.get((host, int(port)),
                                      shard=("rep", self.app_id, self.pidx))
            results = conn.call_many(reqs, timeout=10.0)
        except (RpcError, OSError) as e:
            raise ConnectionError(str(e))
        last = 0
        for _, body in results:
            resp = codec.decode(mm.PrepareResponse, body)
            if resp.error:
                raise PrepareRejected(resp.reason, resp.last_prepared)
            last = resp.last_prepared
        return last

    def fetch_learn_state(self) -> dict:
        body = self._call(RPC_LEARN, mm.LearnRequest(self.app_id, self.pidx))
        resp = codec.decode(mm.LearnResponse, body)
        if resp.error:
            raise ConnectionError("learn failed")
        return {
            "files": [(f.name, f.data) for f in resp.files],
            "tail": [codec.decode(LogMutation, t) for t in resp.tail],
            "last_committed": resp.last_committed,
            "ballot": resp.ballot,
        }

    # block-shipped learn surface (ISSUE 13): one client implementation
    # (learn.RemoteLearnSource) shared with the duplicator bootstrap —
    # chunk fetches pipeline through call_many waves on the shard's
    # dedicated connection
    def _learn_source(self):
        if getattr(self, "_learn_src", None) is None:
            from .learn import RemoteLearnSource

            self._learn_src = RemoteLearnSource(
                self.stub.pool, self.addr, self.app_id, self.pidx)
        return self._learn_src

    def prepare_learn_state(self, have=None, delta=None) -> dict:
        return self._learn_source().prepare_learn_state(have, delta)

    def fetch_learn_chunks(self, learn_id, reqs) -> list:
        return self._learn_source().fetch_learn_chunks(learn_id, reqs)

    def fetch_learn_tail(self, learn_id) -> dict:
        return self._learn_source().fetch_learn_tail(learn_id)

    def finish_learn(self, learn_id) -> None:
        self._learn_source().finish_learn(learn_id)


class ReplicaStub:
    def __init__(self, root: str, meta_addrs, host: str = "127.0.0.1",
                 port: int = 0, options_factory=None,
                 block_service_provider: str = "local_service",
                 remote_clusters: dict = None, cluster_id: int = 1,
                 group_spec: dict = None):
        self.root = root
        self.meta_addrs = list(meta_addrs)
        # partition-group executor mode (replication/serve_groups.py): this
        # stub is ONE group worker of a grouped serving node — it owns only
        # partitions with group_of(app, pidx) == group_index, identifies as
        # the node's public address, never beacons (the parent aggregates),
        # and adopts handed-off client sockets over the control channel
        self.group_spec = group_spec or None
        self.block_service_provider = block_service_provider
        # [pegasus.clusters]: remote cluster name -> meta address list, the
        # duplication target directory (reference pegasus_const cluster
        # section; dup entries name clusters, this resolves them)
        self.remote_clusters = {k: (v if isinstance(v, list) else [v])
                                for k, v in (remote_clusters or {}).items()}
        self.cluster_id = cluster_id
        self.options_factory = options_factory or (lambda: EngineOptions(backend="cpu"))
        self.pool = ConnectionPool()
        self._lock = threading.RLock()
        self._replicas = {}      # (app_id, pidx) -> Replica
        # data-integrity plane (ISSUE 17): partitions pulled off the
        # serving path after a corruption hit; gpid "a.p" -> forensics
        # record. Reported in beacons (status QUARANTINED) so the meta
        # re-seeds and the doctor names them; cleared on re-open.
        self._quarantined = {}   #: guarded_by self._lock
        # gpids with an async read-path quarantine already in flight
        self._quarantining = set()  #: guarded_by self._lock
        # (app_id, pidx) -> monotonic ts of the last background scrub
        self._last_scrub = {}    #: guarded_by self._lock
        self._scrub_interval = float(
            os.environ.get("PEGASUS_SCRUB_INTERVAL_S", "300"))
        self._scrub_bps = float(os.environ.get("PEGASUS_SCRUB_BPS", "0"))
        self._quarantine_keep = int(
            os.environ.get("PEGASUS_QUARANTINE_KEEP", "4"))
        self._service = ReplicaService()
        self._service.set_write_router(self._route_write)
        self.rpc = RpcServer(host, port)
        self.rpc.register_serverlet(self._service)
        self.rpc.register(RPC_OPEN_REPLICA, self._on_open_replica)
        self.rpc.register(RPC_CLOSE_REPLICA, self._on_close_replica)
        self.rpc.register(RPC_REPLICA_STATE, self._on_replica_state)
        from ..meta.meta_server import RPC_QUERY_REPLICA_INFO

        self.rpc.register(RPC_QUERY_REPLICA_INFO, self._on_query_replica_info)
        from ..meta.meta_server import RPC_BULK_LOAD, RPC_COLD_BACKUP

        self.rpc.register(RPC_COLD_BACKUP, self._on_cold_backup)
        self.rpc.register(RPC_BULK_LOAD, self._on_bulk_load)
        self.rpc.register(RPC_PREPARE, self._on_prepare)
        self.rpc.register(RPC_LEARN, self._on_learn)
        self.rpc.register(RPC_LEARN_PREPARE, self._on_learn_prepare)
        self.rpc.register(RPC_LEARN_FETCH, self._on_learn_fetch)
        self.rpc.register(RPC_LEARN_TAIL, self._on_learn_tail)
        self.rpc.register(RPC_LEARN_FINISH, self._on_learn_finish)
        from ..runtime.remote_command import RemoteCommandService

        self.commands = RemoteCommandService()
        self.commands.register_defaults(node_kind="replica",
                                        describe=self._describe)
        self.commands.register("manual-compact", self._cmd_manual_compact)
        self.commands.register("batched-manual-compact",
                               self._cmd_batched_manual_compact)
        self.commands.register("replica-disk", self._cmd_replica_disk)
        self.commands.register("query-compact-state", self._cmd_compact_state)
        self.commands.register("detect_hotkey", self._cmd_detect_hotkey)
        self.commands.register("set-read-residency",
                               self._cmd_set_read_residency)
        self.commands.register("flush-log", self._cmd_flush_log)
        self.commands.register("trigger-audit", self._cmd_trigger_audit)
        self.commands.register("query-audit", self._cmd_query_audit)
        self.commands.register("compact-sched-policy",
                               self._cmd_compact_sched_policy)
        self.commands.register("compact-sched-status",
                               self._cmd_compact_sched_status)
        self.commands.register("learn-status", self._cmd_learn_status)
        self.commands.register("scrub-replica", self._cmd_scrub_replica)
        self.commands.register("quarantine-replica",
                               self._cmd_quarantine_replica)
        self.commands.register("quarantine-status",
                               self._cmd_quarantine_status)
        self.rpc.register(RPC_REMOTE_COMMAND, self.commands.rpc_handler)
        self.rpc.start()
        self.address = f"{self.rpc.address[0]}:{self.rpc.address[1]}"
        if self.group_spec:
            from .serve_groups import RPC_GROUP_STATE

            # replica naming / primary identity must be the PUBLIC address
            # the meta assigned to this node, not the worker's private port
            self.address = self.group_spec["public_address"]
            self.rpc.register(RPC_GROUP_STATE, self._on_group_state)
            # bind BEFORE the parent can read GROUP_READY; only accept()
            # runs on the thread
            srv = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            srv.bind(self.group_spec["control_path"])
            srv.listen(2)
            self._adoption_srv = srv
            spawn_thread(self._adoption_loop, daemon=True)
        self._stop = threading.Event()
        self._beacon_threads = {}  # meta addr -> in-flight ping thread
        self._beacon_thread = spawn_thread(self._beacon_loop, daemon=True,
                                           start=False)
        self._maint_thread = spawn_thread(self._maintenance_loop,
                                          daemon=True, start=False)

    def start(self, beacon_interval: float = 1.0,
              maintenance_interval: float = 60.0) -> "ReplicaStub":
        self._beacon_interval = beacon_interval
        self._maint_interval = maintenance_interval
        if not self.group_spec:   # a group worker's parent beacons for it
            self.send_beacon()
            self._beacon_thread.start()
        self._maint_thread.start()
        # flight recorder (ISSUE 12): every serving process samples its
        # counter registry into the history ring (refcounted process-wide
        # sampler — group workers are their own processes and get their
        # own, exactly like their own registry)
        from ..runtime.metric_history import HISTORY

        HISTORY.start()
        return self

    # --------------------------------------------- group-executor plumbing

    def _beacon_fragment_locked(self):  #: requires self._lock
        from ..runtime.perf_counters import counters

        alive = [f"{a}.{p}" for (a, p) in self._replicas]
        progress = []
        states = []
        for (a, p), rep in self._replicas.items():
            # dict() snapshot: _sync_duplications swaps the mapping
            # copy-on-write, so iteration here can never see a resize
            for dupid, d in dict(rep.duplicators).items():
                progress.append(f"{a}.{p}.{dupid}:{d.last_shipped_decree}")
                # duplicator ship-lag: decrees committed here but not yet
                # confirmed shipped (refreshed every beacon tick)
                counters.number(f"dup.lag.{a}.{p}.{dupid}").set(
                    max(0, rep.last_committed - d.last_shipped_decree))
            st = {"gpid": f"{a}.{p}", "status": rep.status,
                  "ballot": rep.ballot,
                  "committed": rep.last_committed,
                  "applied": rep.server.engine.last_committed_decree(),
                  "prepared": rep.last_prepared,
                  # compaction-debt plane (ISSUE 10): the scheduler folds
                  # this out of the meta's cluster-state snapshot; the
                  # call also refreshes the engine.compact.<a>.<p>.*
                  # gauges so every surface reads the same fold
                  "compact": rep.compact_debt()}
            la = rep.server.last_audit
            if la:
                st["audit"] = {"audit_id": la.get("audit_id", 0),
                               "decree": la.get("decree", 0),
                               "digest": la.get("digest", "")}
            states.append(json.dumps(st))
        # quarantined partitions ride the same state list as synthetic
        # entries: the meta's beacon fold sees status QUARANTINED and
        # treats the replica as lost (repair_quarantined), the doctor
        # names it — no wire-schema change needed
        for gpid, q in self._quarantined.items():
            states.append(json.dumps({"gpid": gpid, "status": "QUARANTINED",
                                      "quarantine": q}))
        # tenant ledger fragment (ISSUE 18): one synthetic entry carrying
        # this PROCESS's per-table totals, keyed by pid so group workers'
        # fragments survive the meta fold (which keys by gpid) next to
        # the parent's. Refresh the device-plane gauges first — per-table
        # HBM from the hosted engines, device seconds/offload bytes from
        # the causal-job window — so the shipped snapshot is current.
        frag = self._table_stats_fragment()
        if frag is not None:
            states.append(frag)
        return alive, progress, states

    def _table_stats_fragment(self):
        """json.dumps'd synthetic beacon entry with TABLE_STATS.snapshot(),
        or None when no table is wired in this process. The meta diverts
        status TABLE_STATS into its tables-only side map (_node_tables)
        at ingestion, so replica-state consumers (doctor lag fold,
        quarantine repair, scheduler debt) never iterate over it."""
        from ..runtime.job_trace import JOB_TRACER
        from ..runtime.table_stats import TABLE_STATS

        if not TABLE_STATS.tables():
            return None
        hbm = {}
        for (a, p), rep in self._replicas.items():
            name = TABLE_STATS.table_for_gpid(f"{a}.{p}")
            if name:
                hbm[name] = (hbm.get(name, 0)
                             + rep.server.engine.device_resident_bytes())
        for name, nbytes in hbm.items():
            TABLE_STATS.ledger(name).set_hbm_resident(nbytes)
        TABLE_STATS.attribute_jobs(JOB_TRACER.window(None))
        return json.dumps({"gpid": f"tables@pid:{os.getpid()}",
                           "status": "TABLE_STATS",
                           "tables": TABLE_STATS.snapshot()})

    def _on_group_state(self, header, body) -> bytes:
        """The parent's beacon-aggregation scrape: this worker's share of
        the node beacon (alive replicas + duplication progress + the
        per-replica lag/audit states the cluster doctor folds)."""
        with self._lock:
            alive, progress, states = self._beacon_fragment_locked()
        return json.dumps({"alive": alive, "dup_progress": progress,
                           "states": states}).encode("utf-8")

    def _owns(self, app_id: int, pidx: int) -> bool:
        if not self.group_spec:
            return True
        from .serve_groups import group_of

        return group_of(app_id, pidx, self.group_spec["group_count"]) \
            == self.group_spec["group_index"]

    def _adoption_loop(self):
        """Accept the parent's control connection and adopt handed-off
        client sockets (SCM_RIGHTS + length-prefixed already-read bytes).
        EOF on the control stream means the parent is gone: exit — an
        orphan worker must never outlive its node."""
        import struct as _struct

        srv = self._adoption_srv
        while True:
            try:
                conn, _ = srv.accept()
            except OSError:
                return
            try:
                while True:
                    msg, fds, _, _ = socket.recv_fds(conn, 1 << 16, 4)
                    if not msg and not fds:
                        raise ConnectionError("parent closed")
                    while len(msg) < 4:
                        chunk = conn.recv(4 - len(msg))
                        if not chunk:
                            raise ConnectionError("parent closed")
                        msg += chunk
                    (need,) = _struct.unpack("<I", msg[:4])
                    payload = bytearray(msg[4:])
                    while len(payload) < need:
                        chunk = conn.recv(min(1 << 16, need - len(payload)))
                        if not chunk:
                            raise ConnectionError("parent closed")
                        payload += chunk
                    if fds:
                        sock = socket.socket(fileno=fds[0])
                        for extra in fds[1:]:
                            os.close(extra)
                        self.rpc.serve_adopted(sock, bytes(payload))
                    conn.sendall(b"A")
            except (ConnectionError, OSError):
                pass
            # the parent never reconnects a control stream: it restarts
            # the whole worker instead — treat EOF as a death sentence
            os._exit(0)

    def _maintenance_loop(self):
        """Per-replica timers (the reference's replica-level checkpoint timer
        + manual-compact trigger checks, SURVEY §3.1/§3.5): periodic async
        checkpoint, plog GC behind the durable decree, and env-driven
        periodic manual compaction."""
        while not self._stop.wait(self._maint_interval):
            with self._lock:
                reps = list(self._replicas.values())
            for rep in reps:
                try:
                    rep.server.engine.async_checkpoint()
                    rep.gc_log()
                    rep.server.manual_compact_service \
                        .start_manual_compact_if_needed(rep.server.app_envs)
                except Exception as e:  # keep the timer alive
                    print(f"[maintenance] {rep.name}: {e!r}", flush=True)
            # idle retry of a scheduler-held L0 trigger: debt a lapsed
            # defer token or a freed device gate left above the trigger
            # must compact without waiting for the next flush. AFTER
            # the light per-replica work, and at most ONE synchronous
            # compaction per tick — a multi-second merge must not stall
            # every other replica's checkpoint/GC behind it
            for rep in reps:
                try:
                    if rep.server.engine.poke_compaction():
                        break
                except Exception as e:
                    print(f"[maintenance] {rep.name}: {e!r}", flush=True)
            # background scrub (ISSUE 17): re-verify on-disk checksums off
            # the serving path, one replica per tick past its cadence —
            # rate-limited inside engine.scrub so a cold multi-GB replica
            # can't starve the other timers for long
            try:
                self._scrub_tick(reps)
            except Exception as e:
                print(f"[maintenance] scrub: {e!r}", flush=True)

    # ------------------------------------------------------------- beacons

    def _beacon_loop(self):
        while not self._stop.wait(self._beacon_interval):
            try:
                self.send_beacon()
            except Exception as e:  # ANY error: a dead beacon thread gets
                # this healthy node declared dead after fd_grace
                print(f"[beacon] {self.address}: {e!r}", flush=True)

    def send_beacon(self):
        with self._lock:
            alive, progress, states = self._beacon_fragment_locked()
        req = mm.BeaconRequest(node=self.address, alive_replicas=alive,
                               dup_progress=progress, replica_states=states)
        body = codec.encode(req)
        # beacon EVERY configured meta, not just the first reachable one:
        # follower metas absorb beacons too (meta HA — a warm liveness map
        # makes leader takeover instant instead of re-declaring the world
        # dead), and a node partitioned from the leader still registers
        # with whoever can hear it. CONCURRENTLY: sequential 5s timeouts
        # with two black-holed metas ahead of the leader would eat ~10s of
        # the fd grace per round and get a healthy node declared dead.
        def ping(meta):
            host, _, port = meta.rpartition(":")
            try:
                conn = self.pool.get((host, int(port)))
                conn.call(RPC_FD_BEACON, body, timeout=2.0)
            except (RpcError, OSError):
                pass
        if len(self.meta_addrs) == 1:
            ping(self.meta_addrs[0])
            return
        # at most ONE in-flight ping per meta: a black-holed meta blocks
        # its thread ~connect-timeout seconds while beacons fire every
        # second — respawning per round would pile up threads without bound
        threads = []
        for m in self.meta_addrs:
            prev = self._beacon_threads.get(m)
            if prev is not None and prev.is_alive():
                continue
            t = spawn_thread(ping, m, daemon=True, start=False,
                             name=f"beacon:{self.address}->{m}")
            self._beacon_threads[m] = t
            threads.append(t)
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=2.5)

    # ------------------------------------------------- meta-driven lifecycle

    def _on_open_replica(self, header, body) -> bytes:
        req = codec.decode(mm.OpenReplicaRequest, body)
        if not self._owns(req.app_id, req.pidx):
            raise RpcError(ERR_INVALID_STATE,
                           f"partition {req.app_id}.{req.pidx} belongs to "
                           f"another group executor")
        key = (req.app_id, req.pidx)
        # a CROSS-partition learn is split child seeding (parent history
        # copied once); a same-pidx learn is a repair/failover re-seed
        # from the partition's own authoritative primary
        cross_learn = bool(req.learn_from) and 0 <= req.learn_pidx != req.pidx
        with self._lock:
            rep = self._replicas.get(key)
            if rep is None:
                path = os.path.join(self.root, f"{req.app_id}.{req.pidx}")
                if req.restore_dir and not os.path.exists(
                        os.path.join(path, "data", "MANIFEST")):
                    self._seed_from_restore(path, req.restore_dir)
                rep = Replica(f"{self.address}", path, req.app_id, req.pidx,
                              self.options_factory(),
                              peers=self._peer_factory(req.app_id, req.pidx),
                              cluster_id=self.cluster_id)
                # read-path corruption -> async quarantine; the Replica
                # re-installs the hook on every engine swap (learn re-seed)
                rep.set_corruption_hook(
                    self._corruption_hook(req.app_id, req.pidx))
                self._replicas[key] = rep
                # a re-open after quarantine is the heal: the meta seeded a
                # fresh learner dir — the partition is serving again
                self._quarantined.pop(f"{req.app_id}.{req.pidx}", None)
            # Split seeding must be ONCE-ONLY and seed-before-serve:
            #  * once-only — when the meta retries a split whose seeding
            #    RPC failed (timeout/partial), a child that DID seed and
            #    then accepted writes must not re-learn from the parent:
            #    the parent has rejected child-half writes since split
            #    phase 1, so its copy lacks them, and learn_from replaces
            #    the engine wholesale — the re-learn would silently wipe
            #    acked writes (the cross-cluster digest compare caught
            #    exactly this: the duplication target kept rows the
            #    re-learned source child had lost);
            #  * seed-before-serve — registering the child before its
            #    seed learn makes a child whose learn then fails servable
            #    EMPTY (clients would write into a hollow partition whose
            #    pre-split half later reads as lost), so a child pending
            #    its seed is registered only after the learn succeeds.
            seeded = getattr(rep, "split_seeded", False) \
                or rep.last_committed > 0
            need_seed = cross_learn and not seeded
            if not need_seed:
                # (re-)register: partition splits change the count for
                # existing replicas, which drives the misroute rejection
                self._service.add_replica(rep.server, req.partition_count)
        learn_self = (req.learn_from == self.address
                      and (req.learn_pidx < 0 or req.learn_pidx == req.pidx))
        if req.learn_from and not learn_self and (need_seed
                                                  or not cross_learn):
            learn_pidx = req.learn_pidx if req.learn_pidx >= 0 else req.pidx
            if req.learn_from == self.address:
                with self._lock:
                    src = self._replicas.get((req.app_id, learn_pidx))
                peer = src  # in-process parent (split on the same node)
                if peer is None and self.group_spec \
                        and not self._owns(req.app_id, learn_pidx):
                    # split across group executors: the parent partition
                    # lives in a SIBLING group's process — learn over RPC
                    # through the node's public router, which hands the
                    # LEARN to the owning group
                    peer = _RemotePeer(self, req.learn_from, req.app_id,
                                       learn_pidx)
            else:
                peer = _RemotePeer(self, req.learn_from, req.app_id, learn_pidx)
            if peer is not None:
                if need_seed:
                    from ..runtime import events

                    events.emit("split.seed_start",
                                gpid=f"{req.app_id}.{req.pidx}",
                                parent=f"{req.app_id}.{learn_pidx}",
                                source=req.learn_from)
                rep.learn_from(peer)
                with self._lock:
                    if cross_learn:
                        # seed complete: a split retry must never learn
                        # this child from its parent again
                        rep.split_seeded = True
                    self._service.remove_replica(req.app_id, req.pidx)
                    self._service.add_replica(rep.server, req.partition_count)
                if need_seed:
                    from ..runtime import events

                    events.emit("split.seeded",
                                gpid=f"{req.app_id}.{req.pidx}",
                                committed=rep.last_committed)
            elif need_seed:
                # no resolvable seed source (the in-process parent is gone,
                # e.g. mid-restart): replying success here would let the
                # meta count this child as seeded and spread the GC mask
                # over a hollow, unregistered partition — fail the open so
                # the split marks seeding incomplete and retries
                raise RpcError(ERR_INVALID_STATE,
                               f"split child {req.app_id}.{req.pidx} cannot "
                               f"seed: parent {req.app_id}.{learn_pidx} not "
                               f"found at {req.learn_from}")
        rep.app_name = req.app_name or rep.app_name
        if rep.app_name:
            # tenant accounting (ISSUE 18): the open request is where a
            # replica host learns which TABLE a partition serves
            rep.server.set_table_name(rep.app_name)
        rep.partition_count = req.partition_count or rep.partition_count
        rep.assume_view(GroupView(req.ballot, req.primary, req.secondaries))
        envs = json.loads(req.envs_json or "{}")
        if envs:
            rep.server.update_app_envs(envs)
        self._sync_duplications(rep)
        return codec.encode(mm.OpenReplicaResponse(
            last_committed=rep.last_committed, last_prepared=rep.last_prepared))

    def _sync_duplications(self, rep) -> None:
        """Reconcile the replica's mutation shippers against the dup entries
        the meta mirrors into the reserved app-env. Only the PRIMARY ships
        (the reference's duplication also runs on primaries); a demoted or
        removed primary tears its shippers down, a promoted one builds them
        and catches up from its plog + persisted confirmed decree."""
        from ..base import consts
        from ..client import MetaResolver
        from .duplicator import MutationDuplicator

        try:
            entries = json.loads(
                rep.server.app_envs.get(consts.ENV_DUPLICATION_KEY, "[]"))
        except ValueError:
            entries = []
        is_primary = rep.view is not None and rep.view.primary == rep.name
        want = {}
        if is_primary:
            for e in entries:
                if e.get("status") in ("start", "pause"):
                    want[int(e["dupid"])] = e
        # copy-on-write: concurrent readers (beacon thread, gc_log) snapshot
        # the mapping, so reconcile into a copy and swap it in at the end
        dups = dict(rep.duplicators)
        for dupid in list(dups):
            if dupid not in want:
                d = dups.pop(dupid)
                try:
                    rep.commit_hooks.remove(d.on_commit)
                except ValueError:
                    pass
                d.stop()
                from ..runtime.perf_counters import counters

                counters.remove(f"dup.lag.{rep.app_id}.{rep.pidx}.{dupid}")
        for dupid, e in want.items():
            d = dups.get(dupid)
            if d is None:
                metas = self.remote_clusters.get(e["remote"])
                if not metas:
                    print(f"[dup {dupid}] unknown remote cluster "
                          f"{e['remote']!r} (configure [pegasus.clusters])",
                          flush=True)
                    continue
                try:
                    resolver = MetaResolver(list(metas), rep.app_name)
                except Exception as ex:  # remote may be down; retry on next
                    print(f"[dup {dupid}] remote resolve failed: {ex!r}",
                          flush=True)                     # view/env install
                    continue
                floor = int(e.get("confirmed", {}).get(str(rep.pidx), 0))
                # born paused: catch_up must order the plog backlog ahead of
                # live hook traffic before anything ships, or a live decree
                # would advance the confirmed point past the backlog
                d = MutationDuplicator(
                    resolver, cluster_id=self.cluster_id,
                    fail_mode=e.get("fail_mode", "slow"), dupid=dupid,
                    progress_dir=os.path.join(rep.path, "dup"),
                    confirmed_floor=floor, paused=True)
                dups[dupid] = d
                rep.commit_hooks.append(d.on_commit)
                d.catch_up(rep.plog)
            d.fail_mode = e.get("fail_mode", "slow")
            d.set_paused(e.get("status") == "pause")
        rep.duplicators = dups

    def batched_manual_compact(self, app_id: int = None, now: int = None,
                               mesh=None) -> dict:
        """Node-level manual compaction: ALL this node's (optionally one
        app's) tpu-backend replicas compact in batched device dispatches —
        ops.batched_compact's dp-over-partitions as a SYSTEM operation,
        replacing N sequential per-replica CompactRange jobs with
        ceil(N/chunk) vmapped kernel launches. Replicas whose runs cannot
        be device-cached fall back to their own manual_compact.

        Every participating engine's compaction lock is held from file-set
        snapshot through output install (acquired in stable key order), so
        concurrent flush-triggered compactions cannot double-merge."""
        from ..ops.batched_compact import compact_partition_batch
        from ..ops.compact import CompactOptions

        from ..engine.db import META_LAST_MANUAL_COMPACT_FINISH_TIME

        def mark_done(eng):
            with eng._lock:
                eng._meta[META_LAST_MANUAL_COMPACT_FINISH_TIME] = \
                    int(time.time())
                eng._write_manifest_locked()  # finish time must persist

        with self._lock:
            reps = [(aid, rep)
                    for (aid, p), rep in sorted(self._replicas.items())
                    if app_id is None or aid == app_id]
        groups, fallback = {}, []
        held = set()  # engines whose compaction lock we currently hold

        def release(eng):
            if eng in held:
                held.discard(eng)
                eng._compaction_lock.release()

        stats = {"input_records": 0, "output_records": 0,
                 "partitions": 0, "batched": 0, "fallback": 0}
        try:
            for aid, rep in reps:
                eng = rep.server.engine
                if eng.opts.backend != "tpu":
                    fallback.append(rep)
                    continue
                eng.flush()
                eng._compaction_lock.acquire()
                held.add(eng)
                with eng._lock:
                    all_inputs = list(eng._l0)
                    for lv in sorted(eng._levels):
                        all_inputs.extend(eng._levels[lv])
                inputs = [s for s in all_inputs if s.n]
                if not inputs:
                    # nothing to merge — but zero-record SSTs (possible
                    # when a merge drops everything) must still be swept,
                    # as manual_compact's full-input merge would do
                    if all_inputs:
                        from ..engine.block import KVBlock

                        eng._install_merge_output(all_inputs, [],
                                                  KVBlock.empty(),
                                                  eng.opts.max_levels)
                    mark_done(eng)
                    release(eng)
                    stats["partitions"] += 1
                    stats["batched"] += 1
                    continue
                device_runs = [eng._device_run_budgeted(s) for s in inputs]
                if any(d is None for d in device_runs):
                    release(eng)  # its own manual_compact re-locks later
                    fallback.append(rep)
                    continue
                # dispatches group by (app, partition_mask): the mask
                # broadcasts in-kernel, and a mask change mid-env-spread
                # must not leak one replica's mask onto another. The HOST
                # post passes (user rules, default_ttl) use each engine's
                # OWN options via post_opts.
                groups.setdefault((aid, eng.opts.partition_mask),
                                  []).append((eng, all_inputs, inputs,
                                              device_runs))
            for (aid, pmask), group in groups.items():
                opts = CompactOptions(
                    now=now, bottommost=True, runs_sorted=True,
                    backend="tpu", partition_mask=pmask,
                    prefix_u32=group[0][0].opts.prefix_u32)
                jobs, post_opts = [], []
                for eng, all_inputs, inputs, drs in group:
                    jobs.append(([s.block() for s in inputs], drs,
                                 eng.opts.pidx))
                    post_opts.append(CompactOptions(
                        now=now, bottommost=True, runs_sorted=True,
                        backend="tpu", pidx=eng.opts.pidx,
                        partition_mask=pmask,
                        prefix_u32=eng.opts.prefix_u32,
                        default_ttl=eng.opts.default_ttl,
                        user_ops=tuple(eng.opts.user_ops)))
                outs = compact_partition_batch(jobs, opts, mesh=mesh,
                                               post_opts=post_opts)
                for (eng, all_inputs, inputs, _), out in zip(group, outs):
                    n_in = sum(s.n for s in inputs)
                    # remove EVERY input file incl. zero-record ones
                    eng._install_merge_output(all_inputs, [], out,
                                              eng.opts.max_levels)
                    mark_done(eng)
                    # this engine is done: let flush-triggered compactions
                    # proceed instead of stalling on other groups' work
                    release(eng)
                    stats["input_records"] += n_in
                    stats["output_records"] += out.n
                    stats["partitions"] += 1
                    stats["batched"] += 1
        finally:
            for eng in list(held):
                release(eng)
        for rep in fallback:
            fs = rep.server.engine.manual_compact(now=now)
            stats["input_records"] += fs.get("input_records", 0)
            stats["output_records"] += fs.get("output_records", 0)
            stats["partitions"] += 1
            stats["fallback"] += 1
        return stats

    def _cmd_replica_disk(self, args) -> str:
        """Per-replica on-disk footprint (the shell app_disk scrape)."""
        with self._lock:
            reps = list(self._replicas.items())
        out = {}
        for (aid, pidx), rep in reps:
            eng = rep.server.engine
            with eng._lock:
                files = list(eng._l0) + [f for fs in eng._levels.values()
                                         for f in fs]
            out[f"{aid}.{pidx}"] = {
                "sst_bytes": sum(f.data_bytes for f in files),
                "sst_files": len(files),
                "records": sum(f.n for f in files),
                "primary": rep.status == "PRIMARY",
            }
        return json.dumps(out)

    def _cmd_batched_manual_compact(self, args) -> str:
        from ..runtime.lane_guard import compile_wait

        app_id = int(args[0]) if args else None
        with compile_wait():  # operator-requested: wait for a cold kernel
            stats = self.batched_manual_compact(app_id=app_id)
        return json.dumps(stats)

    def _on_query_replica_info(self, header, body) -> bytes:
        """Everything this node holds — the disaster-recovery scan the meta
        `recover` command aggregates (reference query_replica_info)."""
        with self._lock:
            reps = list(self._replicas.values())
        out = []
        for rep in reps:
            out.append(mm.ReplicaInfo(
                app_name=rep.app_name, app_id=rep.app_id, pidx=rep.pidx,
                partition_count=rep.partition_count, ballot=rep.ballot,
                last_committed=rep.last_committed,
                last_prepared=rep.last_prepared,
                last_durable=rep.server.engine.last_durable_decree(),
                envs_json=json.dumps(rep.server.app_envs),
                last_applied=rep.server.engine.last_committed_decree()))
        return codec.encode(mm.QueryReplicaInfoResponse(replicas=out))

    def _seed_from_restore(self, replica_path: str, restore_dir: str) -> None:
        """Pre-open restore: download backup checkpoint files into the data
        dir through the block service (reference restore at open,
        pegasus_server_impl.cpp:1339)."""
        from ..runtime.block_service import create_block_service

        data = os.path.join(replica_path, "data")
        bs = create_block_service(self.block_service_provider, "/")
        bs.download_dir(restore_dir, data)

    def _on_close_replica(self, header, body) -> bytes:
        req = codec.decode(mm.CloseReplicaRequest, body)
        with self._lock:
            rep = self._replicas.pop((req.app_id, req.pidx), None)
            self._service.remove_replica(req.app_id, req.pidx)
            # a close is also the meta's quarantine ack (the re-seed may
            # have landed on another node): stop beaconing the lost copy
            self._quarantined.pop(f"{req.app_id}.{req.pidx}", None)
            self._quarantining.discard(f"{req.app_id}.{req.pidx}")
        if rep:
            rep.close()
        return b""

    # ------------------------------------- data integrity plane (ISSUE 17)

    def _corruption_hook(self, app_id: int, pidx: int):
        """Build the engine's read-path corruption callout for one
        partition: hand off to an async quarantine thread (the engine
        cannot close itself from inside a failing read) with in-flight
        dedup so a burst of reads against the same rotten SST spawns
        exactly one quarantine."""
        gpid = f"{app_id}.{pidx}"

        def on_corruption(exc):
            with self._lock:
                if gpid in self._quarantining or gpid in self._quarantined \
                        or (app_id, pidx) not in self._replicas:
                    return
                self._quarantining.add(gpid)
            spawn_thread(self.quarantine_replica, app_id, pidx,
                         str(getattr(exc, "detail", None) or exc), "read",
                         daemon=True, name=f"quarantine.{gpid}")

        return on_corruption

    def quarantine_replica(self, app_id: int, pidx: int, reason: str,
                           source: str = "command") -> dict:
        """Pull one partition off the serving path after a corruption hit
        (read path, scrub finding, or an audit-named mismatch): unregister
        it so clients get typed errors instead of garbage, close it, move
        its data dir into a bounded-retention `quarantine/` forensics dir,
        and record the state so beacons report QUARANTINED — the meta then
        re-seeds the partition elsewhere/afresh like any lost replica."""
        from ..runtime import events
        from ..runtime.perf_counters import counters

        gpid = f"{app_id}.{pidx}"
        key = (app_id, pidx)
        with self._lock:
            rep = self._replicas.pop(key, None)
            if rep is None:
                self._quarantining.discard(gpid)
                prior = self._quarantined.get(gpid)
                return dict(prior) if prior else {"error": f"no replica {gpid}"}
            self._service.remove_replica(app_id, pidx)
            self._last_scrub.pop(key, None)
        try:
            rep.close()
        except Exception as e:  # noqa: BLE001 - forensics move still runs
            print(f"[quarantine] {gpid}: close failed: {e!r}", flush=True)
        qroot = os.path.join(self.root, "quarantine")
        dest = os.path.join(qroot, f"{gpid}.{int(time.time() * 1000)}")
        try:
            os.makedirs(qroot, exist_ok=True)
            os.rename(rep.path, dest)
        except OSError as e:
            print(f"[quarantine] {gpid}: move failed: {e!r}", flush=True)
            dest = ""
        self._prune_quarantine(qroot)
        record = {"reason": reason, "source": source, "dir": dest,
                  "ts": time.time()}
        with self._lock:
            self._quarantined[gpid] = record
            self._quarantining.discard(gpid)
        counters.rate("replica.quarantine_count").increment()
        events.emit("replica.quarantine", "error", gpid=gpid,
                    node=self.address, reason=reason, source=source)
        return dict(record)

    def _prune_quarantine(self, qroot: str) -> None:
        """Bound the forensics dir: keep the newest PEGASUS_QUARANTINE_KEEP
        quarantined trees, delete the rest oldest-first."""
        import shutil

        try:
            entries = [os.path.join(qroot, n) for n in os.listdir(qroot)]
        except OSError:
            return
        entries.sort(key=lambda p: os.path.getmtime(p)
                     if os.path.exists(p) else 0.0)
        for victim in entries[:max(0, len(entries) - self._quarantine_keep)]:
            shutil.rmtree(victim, ignore_errors=True)

    def _scrub_tick(self, reps) -> None:
        """Maintenance-timer scrub cadence: pick at most ONE replica past
        its PEGASUS_SCRUB_INTERVAL_S and re-verify its on-disk checksums."""
        if self._scrub_interval <= 0:
            return
        now = time.monotonic()
        victim = None
        with self._lock:
            oldest = None
            for rep in reps:
                k = (rep.app_id, rep.pidx)
                if k not in self._replicas:
                    continue  # closed/quarantined since the snapshot
                last = self._last_scrub.get(k, 0.0)
                # OLDEST past-due replica, not the first in dict order: a
                # cadence shorter than the maintenance interval leaves every
                # replica past due at every tick, and first-match would
                # re-scrub one replica forever while the rest starve
                if (now - last >= self._scrub_interval
                        and (oldest is None or last < oldest)):
                    oldest = last
                    victim = rep
            if victim is not None:
                self._last_scrub[(victim.app_id, victim.pidx)] = now
        if victim is not None:
            self._scrub_replica(victim)

    def _scrub_replica(self, rep) -> dict:
        """Scrub one replica (engine-side checksum + manifest re-verify)
        and quarantine it on any finding. Never touches lane guards: the
        scrub is pure host-side file I/O under the engine's job tracer."""
        res = rep.server.engine.scrub(
            rate_bytes_per_s=self._scrub_bps or None)
        if res["findings"]:
            f0 = res["findings"][0]
            self.quarantine_replica(
                rep.app_id, rep.pidx,
                f"scrub: {f0.get('detail', '?')} ({f0.get('path', '?')})",
                "scrub")
            res["quarantined"] = True
        return res

    def _cmd_scrub_replica(self, args: list) -> str:
        """`scrub-replica [app_id.pidx]`: synchronously re-verify hosted
        replicas' on-disk checksums now (all hosted replicas, or just the
        named gpid). JSON keyed by gpid so the group router merges worker
        shards structurally."""
        with self._lock:
            targets = [(k, r) for k, r in self._replicas.items()]
        out = {}
        for (a, p), rep in targets:
            gpid = f"{a}.{p}"
            if args and args[0] != gpid:
                continue
            try:
                res = self._scrub_replica(rep)
            except Exception as e:  # noqa: BLE001 - report, don't drop shard
                out[gpid] = {"error": repr(e)}
                continue
            out[gpid] = {"files": res["files"], "bytes": res["bytes"],
                         "findings": res["findings"],
                         "errors": res.get("errors", []),
                         "quarantined": bool(res.get("quarantined"))}
        return json.dumps(out)

    def _cmd_quarantine_replica(self, args: list) -> str:
        """`quarantine-replica <app_id.pidx> [reason...]`: force one
        partition into quarantine (the collector's auto-heal driver uses
        this to convert an audit-named mismatch into a re-seed)."""
        if not args:
            return "usage: quarantine-replica <app_id.pidx> [reason]"
        a, _, p = args[0].partition(".")
        try:
            app_id, pidx = int(a), int(p)
        except ValueError:
            return f"bad gpid {args[0]!r}"
        reason = " ".join(args[1:]) or "remote-command"
        rec = self.quarantine_replica(app_id, pidx, reason, "command")
        if "error" in rec:
            return ""  # unhosted here: let the owning group's shard win
        return json.dumps({args[0]: rec})

    def _cmd_quarantine_status(self, args: list) -> str:
        """`quarantine-status`: this process's quarantined partitions
        (gpid-keyed JSON, group-router merge friendly)."""
        with self._lock:
            return json.dumps({g: dict(q)
                               for g, q in self._quarantined.items()})

    def _on_replica_state(self, header, body) -> bytes:
        req = codec.decode(mm.ReplicaStateRequest, body)
        with self._lock:
            rep = self._replicas.get((req.app_id, req.pidx))
        if rep is None:
            return codec.encode(mm.ReplicaStateResponse(error=1))
        return codec.encode(mm.ReplicaStateResponse(
            status=rep.status, ballot=rep.ballot,
            last_committed=rep.last_committed, last_prepared=rep.last_prepared,
            last_durable=rep.server.engine.last_durable_decree(),
            last_applied=rep.server.engine.last_committed_decree()))

    # ------------------------------------------------------- replication RPC

    def _peer_factory(self, app_id, pidx):
        def peers(addr: str):
            if addr == self.address:
                raise ConnectionError("self")
            return _RemotePeer(self, addr, app_id, pidx)

        return peers

    def _on_prepare(self, header, body) -> bytes:
        req = codec.decode(mm.PrepareRequest, body)
        with self._lock:
            rep = self._replicas.get((req.app_id, req.pidx))
        if rep is None:
            return codec.encode(mm.PrepareResponse(error=1, reason="no_replica"))
        if req.mutations:  # decree-pipelined window
            ms = [codec.decode(LogMutation, b) for b in req.mutations]
        elif req.mutation:  # single-mutation frame from an older sender
            ms = [codec.decode(LogMutation, req.mutation)]
        else:              # empty window: pure commit-point broadcast
            ms = []
        try:
            lp = rep.on_prepare_batch(req.ballot, ms, req.committed_decree)
            return codec.encode(mm.PrepareResponse(last_prepared=lp))
        except PrepareRejected as rej:
            return codec.encode(mm.PrepareResponse(
                error=1, reason=rej.reason, last_prepared=rej.last_prepared))

    def _on_learn(self, header, body) -> bytes:
        req = codec.decode(mm.LearnRequest, body)
        with self._lock:
            rep = self._replicas.get((req.app_id, req.pidx))
        if rep is None:
            return codec.encode(mm.LearnResponse(error=1))
        state = rep.fetch_learn_state()
        return codec.encode(mm.LearnResponse(
            files=[mm.FileBlob(n, d) for n, d in state["files"]],
            tail=[codec.encode(m) for m in state["tail"]],
            last_committed=state["last_committed"], ballot=state["ballot"]))

    # -------------------------------------------- block-shipped learn RPCs

    def _learn_replica(self, req):
        with self._lock:
            return self._replicas.get((req.app_id, req.pidx))

    def _on_learn_prepare(self, header, body) -> bytes:
        from ..rpc import messages as rpc_msg

        req = codec.decode(rpc_msg.LearnPrepareRequest, body)
        rep = self._learn_replica(req)
        if rep is None:
            return codec.encode(rpc_msg.LearnPrepareResponse(
                error=1, error_text="no_replica"))
        try:
            st = rep.prepare_learn_state(
                have=[{"name": e.name, "size": e.size, "digest": e.digest}
                      for e in req.have],
                delta=req.delta)
        except Exception as e:  # noqa: BLE001 - the learner retries
            return codec.encode(rpc_msg.LearnPrepareResponse(
                error=1, error_text=repr(e)))
        if req.job:
            # attribute this primary's checkpoint pin to the learner's
            # traced job (ISSUE 16) — opens a remote-view record here;
            # in a onebox the note lands straight in the learn timeline
            from ..runtime.job_trace import JOB_TRACER

            JOB_TRACER.note("learn.serve_prepare", job_id=req.job,
                            gpid=f"{req.app_id}.{req.pidx}",
                            blocks=len(st["blocks"]),
                            missing=len(st["missing"]))
        return codec.encode(rpc_msg.LearnPrepareResponse(
            learn_id=st["learn_id"], ckpt_decree=st["ckpt_decree"],
            ballot=st["ballot"], last_committed=st["last_committed"],
            blocks=[rpc_msg.LearnBlockEntry(e["name"], e["size"],
                                            e["digest"])
                    for e in st["blocks"]],
            missing=st["missing"], digest=st["digest"],
            digest_now=st["digest_now"], digest_pmask=st["digest_pmask"]))

    def _on_learn_fetch(self, header, body) -> bytes:
        from ..rpc import messages as rpc_msg

        req = codec.decode(rpc_msg.LearnFetchRequest, body)
        rep = self._learn_replica(req)
        if rep is None:
            return codec.encode(rpc_msg.LearnFetchResponse(
                error=1, error_text="no_replica"))
        try:
            ch = rep.fetch_learn_block(req.learn_id, req.name, req.offset,
                                       req.length)
        except Exception as e:  # noqa: BLE001 - incl. expired pins
            return codec.encode(rpc_msg.LearnFetchResponse(
                error=1, error_text=repr(e)))
        return codec.encode(rpc_msg.LearnFetchResponse(
            data=ch["data"], crc=ch["crc"], total=ch["total"]))

    def _on_learn_tail(self, header, body) -> bytes:
        from ..rpc import messages as rpc_msg

        req = codec.decode(rpc_msg.LearnTailRequest, body)
        rep = self._learn_replica(req)
        if rep is None:
            return codec.encode(rpc_msg.LearnTailResponse(
                error=1, error_text="no_replica"))
        try:
            st = rep.fetch_learn_tail(req.learn_id)
        except Exception as e:  # noqa: BLE001
            return codec.encode(rpc_msg.LearnTailResponse(
                error=1, error_text=repr(e)))
        return codec.encode(rpc_msg.LearnTailResponse(
            tail=[codec.encode(m) for m in st["tail"]],
            last_committed=st["last_committed"], ballot=st["ballot"]))

    def _on_learn_finish(self, header, body) -> bytes:
        from ..rpc import messages as rpc_msg

        req = codec.decode(rpc_msg.LearnFinishRequest, body)
        rep = self._learn_replica(req)
        if rep is not None:
            rep.finish_learn(req.learn_id)
        return codec.encode(rpc_msg.LearnFetchResponse())

    def _on_cold_backup(self, header, body) -> bytes:
        """Checkpoint this partition, then upload through the block service
        (reference: copy_checkpoint_to_dir -> block service upload)."""
        from ..runtime.block_service import create_block_service

        req = codec.decode(mm.OpenReplicaRequest, body)
        with self._lock:
            rep = self._replicas.get((req.app_id, req.pidx))
        if rep is None:
            raise RpcError(ERR_OBJECT_NOT_FOUND, "replica not served here")
        engine = rep.server.engine
        # hold the checkpoint lock across create+upload so a concurrent
        # maintenance checkpoint can neither GC this decree nor swap the
        # directory under the upload
        with engine.checkpoint_lock:
            decree = engine.sync_checkpoint()
            src = engine.get_checkpoint_dir(decree)
            bs = create_block_service(self.block_service_provider, "/")
            bs.upload_dir(src, req.restore_dir)
        return codec.encode(mm.OpenReplicaResponse(last_committed=decree))

    def _on_bulk_load(self, header, body) -> bytes:
        """Ingest this partition's bulk-load set from the provider root."""
        from ..engine import bulk_load as bl

        req = codec.decode(mm.OpenReplicaRequest, body)
        with self._lock:
            rep = self._replicas.get((req.app_id, req.pidx))
        if rep is None:
            raise RpcError(ERR_OBJECT_NOT_FOUND, "replica not served here")
        stats = bl.ingest_partition(
            rep.server.engine, req.restore_dir, req.app_name,
            req.partition_count, req.pidx, rep.server._schema)
        return int(stats["records"]).to_bytes(8, "little")

    # ------------------------------------------------------ remote commands

    def _describe(self) -> dict:
        with self._lock:
            return {
                "address": self.address,
                "replicas": {
                    f"{a}.{p}": {
                        "status": r.status, "ballot": r.ballot,
                        "last_committed": r.last_committed,
                        "last_prepared": r.last_prepared,
                        "last_durable": r.server.engine.last_durable_decree(),
                        "last_applied": r.server.engine.last_committed_decree(),
                    }
                    for (a, p), r in self._replicas.items()
                },
            }

    def _cmd_manual_compact(self, args: list) -> str:
        """manual-compact [app_id.pidx] — run a full compaction now."""
        done = []
        with self._lock:
            targets = list(self._replicas.items())
        for (a, p), rep in targets:
            if args and f"{a}.{p}" not in args:
                continue
            rep.server.manual_compact()
            done.append(f"{a}.{p}")
        return "compacted: " + ", ".join(done) if done else "no matching replica"

    def _cmd_compact_state(self, args: list) -> str:
        with self._lock:
            targets = list(self._replicas.items())
        return "\n".join(
            f"{a}.{p}: {rep.server.manual_compact_service.query_compact_state()}"
            for (a, p), rep in targets)

    def _cmd_detect_hotkey(self, args: list) -> str:
        """detect_hotkey <app_id.pidx> <read|write> <start|stop|query>."""
        if len(args) < 3:
            return "usage: detect_hotkey <app_id.pidx> <read|write> <start|stop|query>"
        gpid, kind, action = args[0], args[1], args[2]
        a, _, p = gpid.partition(".")
        with self._lock:
            rep = self._replicas.get((int(a), int(p)))
        if rep is None:
            return f"no replica {gpid}"
        return rep.server.on_detect_hotkey(kind, action)

    def _cmd_set_read_residency(self, args: list) -> str:
        """set-read-residency <app_id.pidx> <on|off> — pin/unpin one
        partition's SSTs HBM-resident for the device read path (the
        collector's hotkey loop drives this from read-hot verdicts)."""
        if len(args) < 2 or args[1] not in ("on", "off"):
            return "usage: set-read-residency <app_id.pidx> <on|off>"
        gpid = args[0]
        a, _, p = gpid.partition(".")
        with self._lock:
            rep = self._replicas.get((int(a), int(p)))
        if rep is None:
            return f"no replica {gpid}"
        on = args[1] == "on"
        rep.server.engine.set_read_residency(on)
        return f"read residency {'on' if on else 'off'} for {gpid}"

    def _cmd_trigger_audit(self, args: list) -> str:
        """trigger-audit <app_id.pidx> [audit_id] — ride a no-op mutation
        through the partition's PacificA prepare path so EVERY replica
        computes a consistency digest anchored at the same applied decree;
        then broadcast the commit point so idle secondaries apply it now.
        Must run on the primary. Returns the primary's digest as JSON; an
        empty reply means the partition is not served here (so a
        partition-group router's fan-out merge keeps the owner's reply)."""
        from ..base.utils import epoch_now
        from ..engine.server_impl import RPC_TRIGGER_AUDIT
        from ..rpc import messages as rpc_msg

        # now=<epoch>: auditor-supplied expiry anchor — the cross-cluster
        # compare digests BOTH clusters against one instant so a TTL
        # record expiring between the two audits cannot fake a mismatch
        now_arg = next((int(x[4:]) for x in args if x.startswith("now=")),
                       None)
        pos = [x for x in args if not x.startswith("now=")]
        if not pos:
            return ("usage: trigger-audit <app_id.pidx> [audit_id] "
                    "[now=<epoch>]")
        a, _, p = pos[0].partition(".")
        with self._lock:
            rep = self._replicas.get((int(a), int(p)))
        if rep is None:
            return ""
        if rep.status != PRIMARY:
            return json.dumps({"error": f"not primary ({rep.status})",
                               "gpid": pos[0], "node": self.address})
        audit_id = int(pos[1]) if len(pos) > 1 else int(time.time() * 1000)
        # partition_count - 1 = the ownership mask (hash % count == pidx);
        # carried IN the mutation so every replica digests against the
        # same mask at the same decree, mid-split or not
        pmask = max(0, (rep.partition_count or 0) - 1)
        req = rpc_msg.TriggerAuditRequest(
            audit_id=audit_id,
            now=epoch_now() if now_arg is None else now_arg, pmask=pmask)
        try:
            resp = rep.client_write(RPC_TRIGGER_AUDIT, req)
        except ReplicaError as e:
            return json.dumps({"error": str(e), "gpid": pos[0],
                               "node": self.address})
        if resp.error or not resp.digest:
            # a failed digest computation must surface as an ERROR the
            # audit driver turns into inconclusive — an empty digest
            # compared as real would fake a mismatch on every secondary
            return json.dumps({"error": f"digest failed ({resp.server})",
                               "gpid": pos[0], "node": self.address})
        rep.broadcast_commit_point()
        return json.dumps({"gpid": pos[0], "audit_id": audit_id,
                           "decree": resp.decree, "digest": resp.digest,
                           "records": resp.records, "node": self.address})

    def _cmd_query_audit(self, args: list) -> str:
        """query-audit [app_id.pidx] — each hosted (or the named) replica's
        latest decree-anchored digest plus its committed/applied decrees,
        keyed by gpid (JSON dict; disjoint keys merge cleanly through the
        partition-group router's structural fan-out merge)."""
        with self._lock:
            targets = list(self._replicas.items())
        out = {}
        for (a, p), rep in targets:
            gpid = f"{a}.{p}"
            if args and args[0] != gpid:
                continue
            ent = {"status": rep.status,
                   "committed": rep.last_committed,
                   "applied": rep.server.engine.last_committed_decree(),
                   "node": self.address}
            la = rep.server.last_audit
            if la:
                ent["audit"] = dict(la)
            out[gpid] = ent
        return json.dumps(out)

    def _cmd_compact_sched_policy(self, args: list) -> str:
        """compact-sched-policy <json> — the cluster compaction
        scheduler's delivery surface (ISSUE 10). The body is
        ``{"ttl_s": s, "decisions": {"<app>.<pidx>": {"policy":
        defer|normal|urgent, "reasons": [...]}}, "max_device": n?}``:
        each hosted partition named installs the policy token on its
        engine (expiring after ttl_s — a dead scheduler reverts to
        engine-local triggers), max_device caps this node's concurrent
        device compactions. Returns {gpid: policy} for what applied
        (disjoint keys merge cleanly through the group router)."""
        if not args:
            return "usage: compact-sched-policy <json>"
        try:
            req = json.loads(" ".join(args))
        except ValueError as e:
            return f"bad policy json: {e}"
        ttl = req.get("ttl_s")
        if "max_device" in req:
            from ..engine.db import SCHED_GATE

            # same lease as the tokens (set_max defaults the ttl): a
            # dead scheduler's cap expires back to the node's env
            # default instead of sticking forever. In partition-group
            # mode the command fans out to EVERY worker process and the
            # gate is per-process, so each worker takes its share of
            # the node cap (at least 1 — 0 would mean "no gate")
            cap = max(0, int(req["max_device"]))
            if cap > 0 and self.group_spec:
                cap = max(1, cap // self.group_spec["group_count"])
            SCHED_GATE.set_max(cap, ttl_s=ttl)
        with self._lock:
            reps = dict(self._replicas)
        applied = {}
        for gpid, dec in sorted((req.get("decisions") or {}).items()):
            a, _, p = gpid.partition(".")
            try:
                rep = reps.get((int(a), int(p)))
            except ValueError:
                continue
            if rep is None:
                continue
            policy = dec.get("policy", "normal")
            try:
                rep.server.engine.set_compact_policy(
                    policy, reasons=dec.get("reasons", ()), ttl_s=ttl,
                    job=dec.get("job", ""))
            except ValueError as e:
                applied[gpid] = f"error: {e}"
                continue
            if "where" in dec:
                # the placement half of the (when, where) pair (ISSUE
                # 14): same lease as the policy token — expiry reverts
                # this engine to local compaction
                rep.server.engine.set_offload_target(dec.get("where") or "",
                                                     ttl_s=ttl)
            applied[gpid] = policy
        return json.dumps(applied)

    def _cmd_compact_sched_status(self, args: list) -> str:
        """compact-sched-status [gpid] — each hosted (or the named)
        partition's live scheduler token (policy + the reasons that
        drove it + time to expiry) and its current compaction debt,
        keyed by gpid (JSON dict; disjoint keys merge cleanly through
        the group router's structural fan-out merge)."""
        with self._lock:
            targets = list(self._replicas.items())
        out = {}
        for (a, p), rep in targets:
            gpid = f"{a}.{p}"
            if args and args[0] != gpid:
                continue
            policy, reasons, expires_in = rep.server.engine.compact_policy()
            debt = rep.server.engine.compaction_debt()
            out[gpid] = {"policy": policy, "reasons": reasons,
                         "expires_in_s": round(expires_in, 3),
                         # the WHERE half (ISSUE 14): which compaction
                         # service this engine's merges ship to ("" =
                         # local), with the live-lease check applied
                         "offload": rep.server.engine.offload_target() or "",
                         "l0_files": debt["l0_files"],
                         "debt_bytes": debt["debt_bytes"],
                         "pending_installs": debt["pending_installs"],
                         "ceiling_files": debt["ceiling_files"],
                         "node": self.address}
        return json.dumps(out)

    def _cmd_learn_status(self, args: list) -> str:
        """learn-status — this process's block-ship totals (monotone, so
        the chaos harness can counter-assert the ship path was used)
        plus each hosted replica's learning flag and active primary-side
        learn pins. Shape is group-router-merge-friendly: the flat
        numeric `ship.*` totals SUM across worker processes and the
        per-gpid `replica.*` dicts are disjoint."""
        from ..runtime.perf_counters import counters

        with self._lock:
            targets = list(self._replicas.items())
        out = {
            "ship.blocks": counters.rate("learn.ship.blocks").total(),
            "ship.bytes": counters.rate("learn.ship.bytes").total(),
            "ship.delta_skipped_blocks": counters.rate(
                "learn.ship.delta_skipped_blocks").total(),
            "ship.replay_mutations": counters.rate(
                "learn.replay.mutations").total(),
        }
        for (a, p), rep in targets:
            ent = rep.learn_state()
            ent["pins"] = rep.learn_pins()
            ent["node"] = self.address
            out[f"replica.{a}.{p}"] = ent
        return json.dumps(out)

    def _cmd_flush_log(self, args: list) -> str:
        """flush-log: fsync every hosted replica's mutation log (reference
        flush_log remote command)."""
        with self._lock:
            reps = list(self._replicas.values())
        for rep in reps:
            rep.plog.flush()
        return f"flushed {len(reps)} logs"

    # ------------------------------------------------------------ write path

    def _route_write(self, server, code, req):
        with self._lock:
            rep = self._replicas.get((server.app_id, server.pidx))
        if rep is None:
            raise RpcError(ERR_OBJECT_NOT_FOUND, "replica closed")
        if rep.status != PRIMARY:
            raise RpcError(ERR_INVALID_STATE, f"not primary ({rep.status})")
        try:
            return rep.client_write(code, req)
        except ReplicaError as e:
            raise RpcError(ERR_INVALID_STATE, str(e))

    # -------------------------------------------------------------- control

    def stop(self):
        if not self._stop.is_set():
            # drop the refcounted sampler ref ONCE: a chaos node-kill plus
            # the harness teardown both call stop(), and a double drop
            # would stop the sampler out from under the surviving stubs
            from ..runtime.metric_history import HISTORY

            HISTORY.stop()
        self._stop.set()
        if getattr(self, "_adoption_srv", None) is not None:
            try:
                self._adoption_srv.close()
            except OSError:
                pass
        self.rpc.stop()
        with self._lock:
            reps = list(self._replicas.values())
            self._replicas.clear()
        for r in reps:
            r.close()
        self.pool.close()
