import argparse
import sys


def main(argv=None):
    from ..runtime.config import Config
    from ..runtime.service_app import ServiceAppContainer

    ap = argparse.ArgumentParser(prog="pegasus-server")
    ap.add_argument("--config", required=True, help="ini config path")
    ap.add_argument("--app", default="", help="comma-separated app names "
                    "(default: every [apps.*] with run=true)")
    ns = ap.parse_args(argv)
    cfg = Config(ns.config)
    container = ServiceAppContainer(cfg)
    only = [a for a in ns.app.split(",") if a] or None
    apps = container.start(only)
    for name, app in apps.items():
        addr = getattr(app, "address", "")
        print(f"[pegasus-tpu] app {name} started {addr}", flush=True)
    try:
        container.wait_forever()
    except KeyboardInterrupt:
        container.stop()


def group_worker_main(spec_path: str):
    """One partition-group executor (replication/serve_groups.py): a full
    ReplicaStub on an ephemeral localhost port owning this group's share
    of the node's partitions. Prints GROUP_READY <port> once serving; the
    parent's control-channel EOF (watched by the stub's adoption loop) is
    the exit signal, so an orphan worker can never outlive its node."""
    import json
    import threading

    with open(spec_path) as f:
        spec = json.load(f)
    if spec.get("backend") == "tpu":
        from ..base.utils import open_device_backend

        open_device_backend()
    from ..engine import EngineOptions
    from ..replication.replica_stub import ReplicaStub

    def options_factory():
        return EngineOptions(
            backend=spec.get("backend", "cpu"),
            compression=spec.get("compression", "none"),
            sharded_compaction=bool(spec.get("sharded_compaction")))

    stub = ReplicaStub(
        spec["root"], list(spec["metas"]), host="127.0.0.1", port=0,
        options_factory=options_factory,
        remote_clusters=spec.get("remote_clusters") or {},
        cluster_id=int(spec.get("cluster_id", 1)), group_spec=spec)
    stub.start()
    print(f"GROUP_READY {stub.rpc.address[1]}", flush=True)
    threading.Event().wait()


if "--group-worker" in sys.argv[1:]:
    group_worker_main(sys.argv[sys.argv.index("--group-worker") + 1])
else:
    main()
