"""Shared self-booting onebox for the tools/ benchmark harnesses."""

import os
import pathlib
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


class Onebox:
    """In-process 1-meta/3-replica cluster with one table, cleaned up on
    stop() (or `with Onebox(...) as box:`); `meta_addr` is the routing
    entry point."""

    def __init__(self, table: str, partitions: int = 8, n_nodes: int = 3,
                 serve_groups: int = 0, replicas: int = 3,
                 remote_clusters: dict = None, cluster_id: int = 1,
                 fd_grace_seconds: float = 60, create: bool = True):
        from tests.test_satellites import MiniCluster

        self._tmp = tempfile.TemporaryDirectory(prefix="pegasus_tool_")
        self.cluster = MiniCluster(pathlib.Path(self._tmp.name),
                                   n_nodes=n_nodes, serve_groups=serve_groups,
                                   remote_clusters=remote_clusters,
                                   cluster_id=cluster_id,
                                   fd_grace_seconds=fd_grace_seconds)
        if create:
            self.cluster.create(table, partitions=partitions,
                                replicas=replicas).close()
        self.meta_addr = self.cluster.meta_addr

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    def stop(self):
        self.cluster.stop()
        self._tmp.cleanup()


def resolve_cluster(meta: str, table: str, partitions: int = 8):
    """-> (meta_addr, onebox_or_None): boot an onebox when no --meta given."""
    if meta:
        return meta, None
    box = Onebox(table, partitions=partitions)
    return box.meta_addr, box
