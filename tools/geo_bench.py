"""Geo benchmark: set_geo_data fill + radial search latency + geo compact.

The BASELINE.json 'geo range-scan + compact' report row (reference
src/geo benchmarks its S2-indexed radial query path). Boots an in-process
MiniCluster, fills N points in a metro-sized box, measures search_radial
latency over random centers, then manual-compacts both geo tables.

Usage: python tools/geo_bench.py   (env: PEGASUS_GEOBENCH_N, _QUERIES,
_RADIUS_M)
"""

import json
import os
import random
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    n = int(os.environ.get("PEGASUS_GEOBENCH_N", 20_000))
    n_queries = int(os.environ.get("PEGASUS_GEOBENCH_QUERIES", 200))
    radius_m = float(os.environ.get("PEGASUS_GEOBENCH_RADIUS_M", 500))

    from pegasus_tpu.client import MetaResolver, PegasusClient
    from pegasus_tpu.geo.geo_client import GeoClient
    from tests.test_satellites import MiniCluster

    rng = random.Random(7)
    with tempfile.TemporaryDirectory() as root:
        import pathlib

        c = MiniCluster(pathlib.Path(root), n_nodes=3)
        try:
            c.create("geo_main", partitions=4).close()
            c.create("geo_idx", partitions=4).close()
            geo = GeoClient(
                PegasusClient(MetaResolver([c.meta_addr], "geo_main")),
                PegasusClient(MetaResolver([c.meta_addr], "geo_idx")),
                max_level=int(os.environ.get("PEGASUS_GEO_MAX_LEVEL", 16)),
                scan_threads=int(os.environ.get("PEGASUS_GEO_THREADS", 8)))
            # fill: a ~20km box around 40.06N 116.4E (the reference's
            # bench geography)
            t0 = time.perf_counter()
            for i in range(n):
                lat = 40.06 + rng.uniform(-0.1, 0.1)
                lng = 116.40 + rng.uniform(-0.1, 0.1)
                geo.set_geo_data(lat, lng, b"p%07d" % i, b"s", b"v%d" % i)
            fill_s = time.perf_counter() - t0
            # radial queries
            lat_ms = []
            found_total = 0
            for _ in range(n_queries):
                lat = 40.06 + rng.uniform(-0.08, 0.08)
                lng = 116.40 + rng.uniform(-0.08, 0.08)
                t0 = time.perf_counter()
                rows = geo.search_radial(lat, lng, radius_m, count=100)
                lat_ms.append((time.perf_counter() - t0) * 1000)
                found_total += len(rows)
            lat_ms.sort()
            # compact both geo tables through the serving stack
            t0 = time.perf_counter()
            for stub in c.stubs:
                for rep in list(stub._replicas.values()):
                    rep.server.engine.manual_compact(now=100)
            compact_s = time.perf_counter() - t0
            print(json.dumps({
                "metric": f"geo radial search p50 latency ({n} points, "
                          f"{radius_m:.0f}m radius)",
                "value": round(lat_ms[len(lat_ms) // 2], 2),
                "unit": "ms",
                "detail": {
                    "fill_s": round(fill_s, 2),
                    "fill_points_per_s": int(n / fill_s),
                    "queries": n_queries,
                    "p95_ms": round(lat_ms[int(len(lat_ms) * 0.95)], 2),
                    "avg_results_per_query": round(found_total / n_queries, 1),
                    "geo_tables_compact_s": round(compact_s, 2),
                },
            }), flush=True)
        finally:
            c.stop()


if __name__ == "__main__":
    main()
