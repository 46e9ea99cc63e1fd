"""Service-app container: ini-driven process bootstrap (the dsn_run role).

Mirror of the rDSN app container Pegasus boots through
(src/server/main.cpp:94-111 `dsn_run`; pegasus_service_app.h:31-102;
config.ini [apps.meta]/[apps.replica]/[apps.collector]): a config file
declares which apps run in this process and on which ports; `run()`
instantiates each registered factory and starts it. One process can host
meta, replica, collector, or any mix — the onebox pattern.

Config shape (ini):

    [apps.meta]
    type = meta
    run = true
    port = 34601

    [apps.replica]
    type = replica
    run = true
    port = 34801
    data_dir = /tmp/pegasus/replica

    [pegasus.server]
    meta_servers = 127.0.0.1:34601
"""

import os
import threading

from .config import Config

_FACTORIES = {}


def register_app_factory(type_name: str, factory) -> None:
    """factory(name, config, section) -> app object with start()/stop()."""
    _FACTORIES[type_name] = factory


def _maybe_join_multihost() -> bool:
    """ADVICE r5: the multi-host join hook (parallel.mesh.init_multihost)
    existed but nothing invoked it. Service startup joins the
    jax.distributed job whenever the standard env is present; env-free
    processes never pay the jax import."""
    if not (os.environ.get("PEGASUS_COORDINATOR")
            or os.environ.get("JAX_NUM_PROCESSES")):
        return False
    try:
        from ..parallel.mesh import init_multihost

        return init_multihost()
    except Exception as e:  # noqa: BLE001 - a failed join must not stop
        # the control plane; the data plane degrades to single-host
        print(f"[service-app] multi-host join failed: {e!r}", flush=True)
        return False


class ServiceAppContainer:
    def __init__(self, config: Config):
        self.config = config
        self.apps = {}

    def start(self, only: list = None) -> dict:
        _maybe_join_multihost()
        for section in self.config.sections():
            if not section.startswith("apps."):
                continue
            name = section[len("apps."):]
            if only and name not in only:
                continue
            if not self.config.get_bool(section, "run", True):
                continue
            type_name = self.config.get_string(section, "type", name)
            factory = _FACTORIES.get(type_name)
            if factory is None:
                raise ValueError(f"no app factory registered for {type_name!r}")
            app = factory(name, self.config, section)
            app.start()
            self.apps[name] = app
        return self.apps

    def stop(self) -> None:
        for app in reversed(list(self.apps.values())):
            app.stop()
        self.apps.clear()

    def wait_forever(self) -> None:
        threading.Event().wait()


# ------------------------------------------------------ http info routes


def _version_info(kind: str) -> dict:
    import time as _time

    from .remote_command import VERSION, _START_TIME

    return {"version": VERSION, "server_type": kind,
            "uptime_seconds": int(_time.time() - _START_TIME)}


def _compact_trace_route(path: str) -> dict:
    """GET /compact/trace[?last=N]: the compaction stage-span ring buffer
    plus the device watchdog's liveness state — the JSON twin of the
    `compact-trace-dump` remote command. (`/metrics` itself is served by
    CounterReporter for every role; this is the structured-trace surface.)"""
    from urllib.parse import parse_qs, urlparse

    from ..ops.device_watchdog import WATCHDOG
    from .tracing import COMPACT_TRACER

    q = parse_qs(urlparse(path).query)
    try:
        last = int((q.get("last") or ["100"])[0])
    except ValueError:
        last = 100
    return {"watchdog": WATCHDOG.state(), "spans": COMPACT_TRACER.trace(last)}


def _request_trace_route(path: str) -> dict:
    """GET /requests/trace[?last=N][&slow=1][&id=<hex>]: the serving-path
    request tracer (runtime/tracing.py RequestTracer) — sampled completed
    traces plus the slow-request ledger, the HTTP twin of the
    `request-trace-dump`/`slow-requests` remote commands. ?id= looks a
    single trace up by its hex trace_id; ?slow=1 returns the ledger only."""
    from urllib.parse import parse_qs, urlparse

    from .tracing import REQUEST_TRACER

    q = parse_qs(urlparse(path).query)
    try:
        last = int((q.get("last") or ["50"])[0])
    except ValueError:
        last = 50
    trace_id = (q.get("id") or [""])[0]
    if trace_id:
        return {"trace": REQUEST_TRACER.find(trace_id)}
    if (q.get("slow") or ["0"])[0] not in ("0", ""):
        return {"slow_requests": REQUEST_TRACER.slow_requests(last)}
    return {"traces": REQUEST_TRACER.trace(last),
            "slow_requests": REQUEST_TRACER.slow_requests(last)}


def _jobs_route(path: str) -> dict:
    """GET /jobs[?last=N][&id=<j…>][&active=0]: the background-job
    tracer (runtime/job_trace.py JobTracer) — completed job timelines
    plus the still-open ones, the HTTP twin of the `job-trace` remote
    command and the shell's `job_trace`. ?id= looks one timeline up by
    its job id; ?active=0 returns completed jobs only."""
    from urllib.parse import parse_qs, urlparse

    from .job_trace import JOB_TRACER

    q = parse_qs(urlparse(path).query)
    try:
        last = int((q.get("last") or ["50"])[0])
    except ValueError:
        last = 50
    job_id = (q.get("id") or [""])[0]
    if job_id:
        return {"job": JOB_TRACER.find(job_id)}
    active = (q.get("active") or ["1"])[0] not in ("0", "")
    return {"jobs": JOB_TRACER.jobs(last=last, active=active)}


def _events_route(path: str) -> dict:
    """GET /events[?last=N][&prefix=p][&since=ts]: the process-wide
    structured event ring (runtime/events.py) — the HTTP twin of the
    `events-dump` remote command and the shell's `events`."""
    from urllib.parse import parse_qs, urlparse

    from .events import EVENTS

    q = parse_qs(urlparse(path).query)

    def _num(key, cast, default):
        try:
            return cast((q.get(key) or [""])[0])
        except ValueError:
            return default

    return {"events": EVENTS.snapshot(
        last=_num("last", int, None),
        since=_num("since", float, None),
        prefix=(q.get("prefix") or [None])[0])}


def _metrics_history_route(path: str) -> dict:
    """GET /metrics/history[?seconds=N][&prefix=p][&deltas=1]: the metric
    history ring (runtime/metric_history.py) — the sampled tail of the
    selected counter series, queryable by window."""
    from urllib.parse import parse_qs, urlparse

    from .metric_history import HISTORY

    q = parse_qs(urlparse(path).query)
    try:
        seconds = float((q.get("seconds") or [""])[0])
    except ValueError:
        seconds = None
    return HISTORY.window(
        seconds=seconds, prefix=(q.get("prefix") or [None])[0],
        deltas=(q.get("deltas") or ["0"])[0] not in ("0", ""))


def _incidents_route(path: str) -> dict:
    """GET /incidents[?id=<incident>]: the flight recorder's retained
    incident artifacts — the list, or one full artifact by id."""
    from urllib.parse import parse_qs, urlparse

    from ..collector.flight_recorder import RECORDER

    q = parse_qs(urlparse(path).query)
    incident_id = (q.get("id") or [""])[0]
    if incident_id:
        return {"incident": RECORDER.load(incident_id)}
    return {"incidents": RECORDER.list_incidents()}


def _health_cluster_route(meta_addrs):
    """GET /health/cluster[?scrape=0][&last=N]: the cluster doctor's ONE
    structured verdict (healthy|degraded|critical|inconclusive + named
    causes + evidence) — the HTTP twin of the `cluster-doctor` remote
    command and the shell's `cluster_doctor`. ?scrape=0 skips the
    per-node breaker/queue/slow-request scrapes (meta-state fold only);
    ?last=N bounds the slow-request rollup."""
    from urllib.parse import parse_qs, urlparse

    def route(path):
        from ..collector.cluster_doctor import run_cluster_doctor

        q = parse_qs(urlparse(path).query)
        try:
            last = int((q.get("last") or ["10"])[0])
        except ValueError:
            last = 10
        scrape = (q.get("scrape") or ["1"])[0] not in ("0",)
        return run_cluster_doctor(list(meta_addrs), scrape=scrape,
                                  slow_last=last)

    return route


def _slo_route(path: str) -> dict:
    """GET /slo: the per-table SLO burn-rate verdicts this process
    computed last round ({} on processes that never evaluate — the
    collector is the evaluator; the meta serves its own view when a
    collector runs in-process, e.g. a onebox)."""
    from ..collector.info_collector import latest_slo

    return {"slo": latest_slo()}


def _tables_meta_route(meta):
    """GET /tables on the meta: fold the TABLE_STATS beacon fragments
    (ISSUE 18 — every serving process ships its per-table ledger totals
    keyed tables@pid:<pid>; the meta diverts them into _node_tables so
    replica-state consumers never see them) into one cluster-wide
    per-table view + the top-k capacity attribution."""
    def route(path):
        from .table_stats import fold_snapshots, top_k

        frags = []
        with meta._lock:
            for tables in meta._node_tables.values():
                for st in tables.values():
                    frags.append(st.get("tables", {}))
        folded = fold_snapshots(frags)
        return {"tables": folded,
                "top": top_k(folded,
                             int(os.environ.get("PEGASUS_TABLE_TOPK", "5")))}

    return route


def _meta_http_routes(meta) -> dict:
    """The meta's rDSN-http_service analogues: /version, /meta/cluster_info,
    /meta/apps, /meta/app?name=<app>."""
    from urllib.parse import parse_qs, urlparse

    def cluster_info(path):
        with meta._lock:
            alive = meta._alive_nodes_locked()
            return {"meta_server": "self", "app_count": len(meta._apps),
                    "node_count": len(meta._nodes), "alive_nodes": alive}

    def apps(path):
        with meta._lock:
            return [{"app_name": a.app_name, "app_id": a.app_id,
                     "partition_count": a.partition_count,
                     "replica_count": a.replica_count, "status": a.status}
                    for a in meta._apps.values()]

    def app(path):
        q = parse_qs(urlparse(path).query)
        name = (q.get("name") or [""])[0]
        with meta._lock:
            a = meta._apps.get(name)
            if a is None:
                return {"error": f"no app {name!r}"}
            return {"app_name": a.app_name, "app_id": a.app_id,
                    "partition_count": a.partition_count,
                    "envs": a.envs_json,
                    "partitions": [{
                        "pidx": pc.pidx, "ballot": pc.ballot,
                        "primary": pc.primary,
                        "secondaries": list(pc.secondaries)}
                        for pc in meta._parts[a.app_id]]}

    return {"/version": lambda p: _version_info("meta"),
            "/meta/cluster_info": cluster_info,
            "/meta/apps": apps,
            "/meta/app": app,
            "/compact/trace": _compact_trace_route,
            "/requests/trace": _request_trace_route,
            "/jobs": _jobs_route,
            "/events": _events_route,
            "/metrics/history": _metrics_history_route,
            "/incidents": _incidents_route,
            "/tables": _tables_meta_route(meta),
            "/slo": _slo_route}


def _replica_http_routes(stub) -> dict:
    """/version + /replica/info on replica nodes."""

    def info(path):
        with stub._lock:
            reps = list(stub._replicas.values())
        return [{"app_name": r.app_name, "app_id": r.app_id, "pidx": r.pidx,
                 "status": r.status, "ballot": r.ballot,
                 "last_committed": r.last_committed,
                 "last_prepared": r.last_prepared,
                 "last_durable": r.server.engine.last_durable_decree()}
                for r in reps]

    return {"/version": lambda p: _version_info("replica"),
            "/replica/info": info,
            "/compact/trace": _compact_trace_route,
            "/requests/trace": _request_trace_route,
            "/jobs": _jobs_route,
            "/events": _events_route,
            "/metrics/history": _metrics_history_route}


# ---------------------------------------------------------- built-in apps


class MetaApp:
    def __init__(self, name, config: Config, section: str):
        from ..meta.meta_server import MetaServer
        from ..rpc.transport import RpcServer

        state_dir = config.get_string(section, "state_dir",
                                      os.path.join("pegasus-data", "meta"))
        state_path = os.path.join(state_dir, "state.json")
        self.rpc = RpcServer(config.get_string(section, "host", "127.0.0.1"),
                             config.get_int(section, "port", 34601))
        # meta HA: with >1 configured meta, run leader election over the
        # shared state dir (meta/election.py; every meta's state_dir must
        # point at the SAME shared path — the ZK-stand-in). Single meta:
        # no election, always leader.
        metas = config.get_list("pegasus.server", "meta_servers", ())
        self.election = None
        if len(metas) > 1:
            from ..meta.election import MetaElection

            self.election = MetaElection(
                state_path + ".lock", self.address,
                lease_seconds=config.get_float(section,
                                               "election_lease_seconds", 6.0),
                on_acquire=lambda: self.meta.reload_state(),
                # claims must exceed the durable state epoch even when the
                # lease file's lineage was lost (fresh mount, manual rm)
                claim_floor=lambda: self.meta._read_state_epoch())
        self.meta = MetaServer(
            state_path,
            fd_grace_seconds=config.get_float("failure_detector",
                                              "grace_seconds", 22.0),
            election=self.election)
        for code, fn in self.meta.rpc_handlers().items():
            self.rpc.register(code, fn)
        from .toollets import install_toollets

        install_toollets(self.rpc, config.get_list("core", "toollets", ()))
        self._fd_timer = None
        self._fd_interval = config.get_float("failure_detector",
                                             "check_interval_seconds", 5.0)
        # version/info HTTP endpoints (reference rDSN http_service on meta:
        # /version, /meta/cluster_info, /meta/app?name=...)
        http_port = config.get_int(section, "http_port", -1)
        self.reporter = None
        if http_port >= 0:
            from ..collector.reporter import CounterReporter

            # started here, not in start(): BaseServer.shutdown() hangs
            # forever unless serve_forever ran, so a start() that dies
            # before reaching the reporter would make stop() deadlock
            routes = _meta_http_routes(self.meta)
            routes["/health/cluster"] = _health_cluster_route([self.address])
            self.reporter = CounterReporter(
                port=http_port, routes=routes).start()

    @property
    def address(self):
        return f"{self.rpc.address[0]}:{self.rpc.address[1]}"

    def start(self):
        self._stopped = False
        self.rpc.start()
        if self.election is not None:
            self.election.start()
        self._schedule_fd()
        from .metric_history import HISTORY

        HISTORY.start()
        self._history_ref = True
        return self

    def _is_leader(self) -> bool:
        return self.election is None or self.election.is_leader()

    def _schedule_fd(self):
        def tick():
            try:
                if self._is_leader():  # followers watch, never act
                    self.meta.check_leases()
                    # heal quarantined replicas (ISSUE 17): a beacon
                    # reporting QUARANTINED is a lost copy — reconfigure
                    # + re-seed on the same cadence as lease expiry
                    self.meta.repair_quarantined()
            except Exception as e:  # a fenced persist (or any failure)
                # must not kill the FD timer for the process lifetime
                print(f"[meta] fd tick failed: {e!r}", flush=True)
            if self._stopped:
                return
            self._fd_timer = threading.Timer(self._fd_interval, tick)
            self._fd_timer.daemon = True
            self._fd_timer.start()

        self._fd_timer = threading.Timer(self._fd_interval, tick)
        self._fd_timer.daemon = True
        self._fd_timer.start()

        # backup policies + dup-progress env refresh run on their OWN timer:
        # a long synchronous backup inside the FD tick would stall lease
        # checks for its whole duration
        def policy_tick():
            try:
                if self._is_leader():
                    self.meta.run_backup_policies()
                    self.meta.push_dup_envs()
                    self.meta.purge_expired_dropped()
            except Exception as e:  # policy failure must not kill the timer
                print(f"[meta] maintenance tick failed: {e!r}", flush=True)
            if self._stopped:
                return  # stop() raced an in-flight tick: do not re-arm
            self._policy_timer = threading.Timer(
                max(self._fd_interval, 5.0), policy_tick)
            self._policy_timer.daemon = True
            self._policy_timer.start()

        self._policy_timer = threading.Timer(
            max(self._fd_interval, 5.0), policy_tick)
        self._policy_timer.daemon = True
        self._policy_timer.start()

    def stop(self):
        # refcounted sampler: drop OUR ref exactly once (a double stop,
        # or stop-before-start, must not steal a sibling app's ref)
        if getattr(self, "_history_ref", False):
            self._history_ref = False
            from .metric_history import HISTORY

            HISTORY.stop()
        self._stopped = True
        if self._fd_timer:
            self._fd_timer.cancel()
        if getattr(self, "_policy_timer", None):
            self._policy_timer.cancel()
        if self.election is not None:
            self.election.stop()
        if self.reporter:
            self.reporter.stop()
        self.rpc.stop()


class ReplicaApp:
    def __init__(self, name, config: Config, section: str):
        from ..engine import EngineOptions
        from ..replication.replica_stub import ReplicaStub

        metas = config.get_list("pegasus.server", "meta_servers",
                                ["127.0.0.1:34601"])
        backend = config.get_string("pegasus.server", "compaction_backend", "cpu")
        compression = config.get_string("pegasus.server", "sst_compression",
                                        "none")
        # multi-chip manual compaction over every visible device (the
        # engine resolves the mesh lazily; <2 devices = single-chip)
        sharded = config.get_bool("pegasus.server", "sharded_compaction",
                                  False)
        data_dir = config.get_string(section, "data_dir",
                                     os.path.join("pegasus-data", name))

        def options_factory():
            return EngineOptions(backend=backend, compression=compression,
                                 sharded_compaction=sharded)

        # [pegasus.clusters]: name = comma-separated meta list; the
        # duplication target directory (reference config.ini cluster section)
        remote_clusters = {}
        if "pegasus.clusters" in config.sections():
            for key in config.keys("pegasus.clusters"):
                remote_clusters[key] = config.get_list("pegasus.clusters",
                                                       key, [])
        # shared-nothing partition-group executors: PEGASUS_SERVE_GROUPS
        # (or [apps.replica] serve_groups) > 1 forks that many worker
        # processes, each owning a disjoint partition set, behind one
        # public acceptor/router (replication/serve_groups.py)
        groups = int(os.environ.get("PEGASUS_SERVE_GROUPS")
                     or config.get_int(section, "serve_groups", 1))
        if backend == "tpu" and groups <= 1:
            # this process will own the engines: refuse to boot a
            # tpu-backend node that jax would quietly run on the CPU (a
            # grouped node's workers each make the same check — the
            # router parent must stay off jax so they can have the chip)
            from ..base.utils import open_device_backend

            open_device_backend()
        if groups > 1:
            from ..replication.serve_groups import GroupedReplicaNode

            self.stub = GroupedReplicaNode(
                data_dir, list(metas),
                host=config.get_string(section, "host", "127.0.0.1"),
                port=config.get_int(section, "port", 0),
                groups=groups, backend=backend, compression=compression,
                sharded_compaction=sharded,
                remote_clusters=remote_clusters,
                cluster_id=config.get_int("pegasus.server", "cluster_id", 1))
        else:
            self.stub = ReplicaStub(
                data_dir, list(metas),
                host=config.get_string(section, "host", "127.0.0.1"),
                port=config.get_int(section, "port", 0),
                options_factory=options_factory,
                remote_clusters=remote_clusters,
                cluster_id=config.get_int("pegasus.server", "cluster_id", 1))
        self._beacon = config.get_float("failure_detector",
                                        "beacon_interval_seconds", 1.0)
        if hasattr(self.stub, "rpc"):
            # toollets wrap the in-process serverlet; a grouped node's
            # serving happens inside the worker processes (each worker's
            # own stub could grow toollets, but the router has no handlers)
            from .toollets import install_toollets

            install_toollets(self.stub.rpc,
                             config.get_list("core", "toollets", ()),
                             command_service=self.stub.commands)
        http_port = config.get_int(section, "http_port", -1)
        self.reporter = None
        if http_port >= 0:
            from ..collector.reporter import CounterReporter

            self.reporter = CounterReporter(
                port=http_port,
                routes=_replica_http_routes(self.stub)).start()

    @property
    def address(self):
        return self.stub.address

    def start(self):
        self.stub.start(self._beacon)
        return self

    def stop(self):
        if self.reporter:
            self.reporter.stop()
        self.stub.stop()


class CollectorApp:
    """The third server role (reference pegasus_service_app.h:31-102
    `pegasus::server::info_collector_app`): cluster stat scraping + hotspot
    analysis + the availability canary, with its own RPC port so the shell
    and tests can query what it publishes."""

    def __init__(self, name, config: Config, section: str):
        import json

        from ..collector.available_detector import AvailableDetector
        from ..collector.info_collector import InfoCollector
        from ..rpc.transport import RpcServer
        from .remote_command import RemoteCommandService

        self.metas = config.get_list("pegasus.server", "meta_servers",
                                     ["127.0.0.1:34601"])
        self._stopping = False
        self.detect_table = config.get_string(section, "available_detect_app",
                                              "test")
        self.collector = InfoCollector(
            list(self.metas),
            interval_seconds=config.get_float(section, "interval_seconds", 10.0))
        # cluster compaction scheduler (ISSUE 10): PEGASUS_SCHED=1 arms
        # the debt-driven control loop; the info collector's confirmed
        # read-hot pins and slow-request rollup feed the decision fold.
        # Off (the default), engines run their local triggers untouched.
        self.scheduler = None
        if os.environ.get("PEGASUS_SCHED", "") == "1":
            from ..collector.compact_scheduler import CompactScheduler

            def _hot_gpids():
                # read_residency publishes copy-on-write: lock-free
                # iteration always sees a stable snapshot
                return {t["gpid"]
                        for t in dict(self.collector.read_residency).values()}

            self.scheduler = CompactScheduler(
                list(self.metas), pool=self.collector.pool,
                hot_fn=_hot_gpids,
                slow_fn=lambda: len(self.collector.cluster_slow_requests))
        self.detector = AvailableDetector(
            list(self.metas), table_name=self.detect_table,
            interval_seconds=config.get_float(section,
                                              "detect_interval_seconds", 1.0))
        self.rpc = RpcServer(config.get_string(section, "host", "127.0.0.1"),
                             config.get_int(section, "port", 0))
        self.commands = RemoteCommandService()
        self.commands.register_defaults(node_kind="collector",
                                        describe=lambda: "collector")

        def info(args):
            return json.dumps({
                "availability": self.detector.report(),
                "hotspots": self.collector.hotspots,
                "hotkeys": self.collector.hotkey_results,
                "app_stats": self.collector.app_stats,
                "compact_stats": self.collector.compact_stats,
                "lag_stats": self.collector.lag_stats,
                "slow_requests": self.collector.cluster_slow_requests,
                "compact_sched": (
                    dict(self.scheduler.status(), enabled=True)
                    if self.scheduler else {"enabled": False}),
            })

        self.commands.register("collector-info", info)

        def compact_sched_status(args):
            """compact-sched-status — the scheduler's last decision round
            (per-partition policy + reasons, delivery map, errors); the
            replica-side command of the same name shows the tokens as
            the engines see them."""
            if self.scheduler is None:
                return json.dumps({"enabled": False})
            return json.dumps(dict(self.scheduler.status(), enabled=True),
                              indent=1)

        self.commands.register("compact-sched-status", compact_sched_status)

        def cluster_doctor(args):
            """cluster-doctor [last] — one structured cluster-health
            verdict (the collector is the doctor's native home: it
            already scrapes every node)."""
            from ..collector.cluster_doctor import run_cluster_doctor

            last = int(args[0]) if args else 10
            return json.dumps(run_cluster_doctor(
                list(self.metas), pool=self.collector.pool,
                slow_last=last), indent=1)

        def trigger_audit(args):
            """trigger-audit [app ...] — run the decree-anchored
            consistency audit across every (or the named) app."""
            from ..collector.cluster_doctor import run_cluster_audit

            return json.dumps(run_cluster_audit(
                list(self.metas), pool=self.collector.pool,
                apps=list(args) or None), indent=1)

        def trigger_incident(args):
            """trigger-incident [reason] — manually capture a flight-
            recorder incident NOW: pull every alive node's event ring +
            metric-history window + slow ledger + recent traces, align
            them on one anchor, run the first-cause heuristic and retain
            the artifact (served as GET /incidents + shell
            flight_recorder)."""
            from ..collector.flight_recorder import RECORDER

            reason = " ".join(args) if args else "manual trigger"
            inc = RECORDER.capture(list(self.metas), reason=reason,
                                   trigger="manual",
                                   pool=self.collector.pool)
            return json.dumps({"incident": inc["id"],
                               "path": inc.get("path", ""),
                               "first_cause": inc.get("first_cause")},
                              indent=1)

        self.commands.register("cluster-doctor", cluster_doctor)
        self.commands.register("trigger-audit", trigger_audit)
        self.commands.register("trigger-incident", trigger_incident)
        self.rpc.register("RPC_CLI_CLI_CALL", self.commands.rpc_handler)
        http_port = config.get_int(section, "http_port", -1)
        self.reporter = None
        if http_port >= 0:
            from ..collector.reporter import CounterReporter

            def tables_route(path):
                # the collector's own cluster fold (collect_table_stats):
                # copy-on-write published, so this read is lock-free
                return {"tables": self.collector.table_stats,
                        "top": self.collector.table_top}

            self.reporter = CounterReporter(
                port=http_port,
                routes={"/compact/trace": _compact_trace_route,
                        "/requests/trace": _request_trace_route,
                        "/jobs": _jobs_route,
                        "/events": _events_route,
                        "/metrics/history": _metrics_history_route,
                        "/incidents": _incidents_route,
                        "/tables": tables_route,
                        "/slo": _slo_route,
                        "/health/cluster":
                            _health_cluster_route(self.metas)}).start()

    @property
    def address(self):
        return f"{self.rpc.address[0]}:{self.rpc.address[1]}"

    def _ensure_probe_table(self) -> bool:
        """Auto-create the canary table (the reference's onebox ships a
        'test' table; a collector must not require manual DDL). -> True
        once a meta acknowledged the create."""
        from ..meta import messages as mm
        from ..meta.meta_server import RPC_CM_CREATE_APP
        from ..rpc import codec
        from ..rpc.transport import RpcConnection

        for m in self.metas:
            host, _, port = m.rpartition(":")
            try:
                conn = RpcConnection((host, int(port)))
                try:
                    conn.call(RPC_CM_CREATE_APP, codec.encode(
                        mm.CreateAppRequest(self.detect_table, 8, 3)),
                        timeout=10.0)
                    return True
                finally:
                    conn.close()
            except OSError:
                continue
        return False

    def _ensure_probe_table_loop(self):
        """The collector routinely boots BEFORE (or restarts independently
        of) the meta; keep trying until a create lands — no deadline, a
        meta that appears an hour later must still get its canary table
        (daemon thread; exits with the process or on stop())."""
        import time as _time

        while not self._stopping:
            try:
                if self._ensure_probe_table():
                    return
            except Exception:
                pass
            _time.sleep(1.0)

    def start(self):
        self._stopping = False
        self.rpc.start()
        from .metric_history import HISTORY
        from .tasking import spawn_thread

        HISTORY.start()
        self._history_ref = True
        spawn_thread(self._ensure_probe_table_loop, daemon=True)
        self.collector.start()
        if self.scheduler is not None:
            self.scheduler.start()
        self.detector.start()
        print(f"[pegasus-tpu] collector rpc on {self.address}", flush=True)
        return self

    def stop(self):
        # refcounted sampler: drop OUR ref exactly once (a double stop,
        # or stop-before-start, must not steal a sibling app's ref)
        if getattr(self, "_history_ref", False):
            self._history_ref = False
            from .metric_history import HISTORY

            HISTORY.stop()
        self._stopping = True
        if self.reporter:
            self.reporter.stop()
        self.detector.stop()
        if self.scheduler is not None:
            self.scheduler.stop()  # before the collector closes their pool
        self.collector.stop()
        self.rpc.stop()


class CompactOffloadApp:
    """The fourth server role (ISSUE 14): one device-owning compaction
    service per TPU host, serving many cpu-only replica nodes. Config:

        [apps.compact_offload]
        run = true
        port = 34901            ; what nodes' placement leases dial
        backend = tpu           ; default: pegasus.server compaction_backend
        job_dir = ...           ; staged-run + job spool (default per-app)

    Point the collector's scheduler at it with
    ``PEGASUS_OFFLOAD_SERVICES=host:34901`` and the fold starts emitting
    (when, where) pairs against its free merge budget."""

    def __init__(self, name, config: Config, section: str):
        from ..replication.compact_offload import CompactOffloadService

        backend = config.get_string(
            section, "backend",
            config.get_string("pegasus.server", "compaction_backend", "cpu"))
        root = config.get_string(section, "job_dir",
                                 os.path.join("pegasus-data", name))
        if backend == "tpu":
            from ..base.utils import open_device_backend

            open_device_backend()
        self.svc = CompactOffloadService(
            root,
            host=config.get_string(section, "host", "127.0.0.1"),
            port=config.get_int(section, "port", 0),
            backend=backend)

    @property
    def address(self):
        return self.svc.address

    def start(self):
        from .metric_history import HISTORY

        self.svc.start()
        HISTORY.start()
        self._history_ref = True
        print(f"[pegasus-tpu] compaction offload service on "
              f"{self.svc.address} (backend {self.svc.backend})", flush=True)
        return self

    def stop(self):
        if getattr(self, "_history_ref", False):
            self._history_ref = False
            from .metric_history import HISTORY

            HISTORY.stop()
        self.svc.stop()


register_app_factory("meta", MetaApp)
register_app_factory("replica", ReplicaApp)
register_app_factory("collector", CollectorApp)
register_app_factory("compact_offload", CompactOffloadApp)
