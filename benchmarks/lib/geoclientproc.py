"""One client process of the geo cells: a few threads, each on its own
GeoClient (two PegasusClients over sockets and its scan pool), none
importing jax. What it does is in the spec file:

    python3 benchmarks/lib/geoclientproc.py <spec.json>

`"mode": "load"`: write this process's share of the points (those numbered
[lo, hi)) into both tables through `multi_set`, one hashkey's rows a call
and at most `batch` rows: the common table under each point's owner key,
the index table under the keys `GeoClient._geo_keys` makes. A call that
returns is an acknowledged write.

`"mode": "search"`: closed loop for `seconds`: each thread draws its
centres from (seed, phase, process, thread) and calls
`search_radial(lat, lng, radius_m, count=-1, sort_by_distance=False)`,
timed on this host's monotonic clock; one that raises counts as failed,
with the client's timeout as its latency. Every thread opens its
connections with one search before it says it is ready. The answers are
kept as they came and judged against the plain reference once the loop has
ended, so no judging shares a core with the window.

Spec: mode, metas, common, index, seed, process, threads, points, rect,
min_level, max_level, scan_threads, timeout_s, control (directory: this
process writes `ready.<process>`, waits for `go`, which holds the
wall-clock start), out (result path); load: lo, hi, batch; search: phase,
radius_m, seconds.
"""

import gc
import json
import os
import resource
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmarks.lib import markers, reference_geo  # noqa: E402


def connect(spec: dict):
    from pegasus_tpu.client import MetaResolver, PegasusClient
    from pegasus_tpu.geo import GeoClient

    common, index = (PegasusClient(MetaResolver(spec["metas"], spec[t]),
                                   timeout=spec["timeout_s"])
                     for t in ("common", "index"))
    return common, index, GeoClient(
        common, index, min_level=spec["min_level"],
        max_level=spec["max_level"], scan_threads=spec["scan_threads"])


def close(common, index, geo) -> None:
    geo.close()
    common.close()
    index.close()


# ------------------------------------------------------------------ load


def load_share(spec: dict, thread: int) -> dict:
    """Thread `thread`'s share of [lo, hi): every `threads`-th block of
    10,000 points, grouped by hashkey and written in calls of `batch`."""
    seed, step = spec["seed"], 10_000
    lat, lng = reference_geo.points(seed, spec["points"], spec["rect"])
    common, index, geo = connect(spec)
    done = {"common": 0, "index": 0}
    try:
        blocks = range(spec["lo"], spec["hi"], step)
        for a in list(blocks)[thread::spec["threads"]]:
            by_hk = {}
            for i in range(a, min(a + step, spec["hi"])):
                hk, sk = reference_geo.owner_key(seed, i)
                value = reference_geo.make_value(seed, i, lat[i], lng[i])
                by_hk.setdefault(("common", hk), []).append((sk, value))
                ghk, gsk = geo._geo_keys(lat[i], lng[i], hk, sk)
                by_hk.setdefault(("index", ghk), []).append((gsk, value))
            for (table, hk), rows in by_hk.items():
                cli = common if table == "common" else index
                for b in range(0, len(rows), spec["batch"]):
                    part = rows[b:b + spec["batch"]]
                    cli.multi_set(hk, dict(part))
                    done[table] += len(part)
    finally:
        close(common, index, geo)
    return done


def run_load(spec: dict) -> dict:
    results, errors = [None] * spec["threads"], []

    def worker(t: int) -> None:
        try:
            results[t] = load_share(spec, t)
        except Exception as e:  # noqa: BLE001 - reported, then fails the run
            errors.append(f"loader {spec['process']}.{t}: {e!r}")

    threads = [threading.Thread(target=worker, args=(t,), daemon=True)
               for t in range(spec["threads"])]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return {"errors": errors,
            "done": {k: sum(r[k] for r in results if r)
                     for k in ("common", "index")}}


# ---------------------------------------------------------------- search

DRAW = 4096     # centres drawn at a time


class Searcher:
    def __init__(self, spec: dict, thread: int):
        self.spec = spec
        self.rng = np.random.default_rng(
            [spec["seed"], 0xCE27, spec["phase"], spec["process"], thread])
        self.common, self.index, self.geo = connect(spec)
        self.lat, self.at, self.asked, self.answers = [], [], [], []
        self.done = self.failed = 0
        self.errors = []
        self.draw()
        self.search(*self.centre())     # connections open before `ready`

    def draw(self) -> None:
        rect = self.spec["rect"]
        self.lats = self.rng.uniform(*rect["lat"], DRAW)
        self.lngs = self.rng.uniform(*rect["lng"], DRAW)
        self.k = 0

    def centre(self):
        if self.k == DRAW:
            self.draw()
        self.k += 1
        return float(self.lats[self.k - 1]), float(self.lngs[self.k - 1])

    def search(self, lat: float, lng: float) -> list:
        return self.geo.search_radial(lat, lng, self.spec["radius_m"],
                                      count=-1, sort_by_distance=False)

    def one(self, end: float) -> None:
        lat, lng = self.centre()
        t0 = time.monotonic()
        self.at.append(t0 - self.start)
        try:
            rows = self.search(lat, lng)
        except Exception as e:  # noqa: BLE001 - counted; the run reports it
            self.failed += 1
            self.lat.append(self.spec["timeout_s"] * 1000.0)
            if len(self.errors) < 3:
                self.errors.append(repr(e))
            return
        t1 = time.monotonic()
        self.lat.append((t1 - t0) * 1000.0)
        if t1 <= end:
            self.done += 1
        self.asked.append((lat, lng))
        self.answers.append(rows)

    def loop(self, start: float, end: float) -> None:
        self.start = start
        while time.monotonic() < start:
            time.sleep(0.0005)
        try:
            while time.monotonic() < end:
                self.one(end)
        finally:
            close(self.common, self.index, self.geo)

    def judged(self, want) -> dict:
        wrong = sum(not want.judge(lat, lng, self.spec["radius_m"],
                                   [(hk, sk, v) for _, hk, sk, v in rows])
                    for (lat, lng), rows in zip(self.asked, self.answers))
        return {"lat": self.lat, "at": self.at, "done": self.done,
                "failed": self.failed, "wrong": wrong, "errors": self.errors,
                "rows_returned": sum(len(r) for r in self.answers)}


def run_search(spec: dict) -> dict:
    from pegasus_tpu.runtime.perf_counters import counters

    workers = [Searcher(spec, t) for t in range(spec["threads"])]
    ctl = spec["control"]
    markers.put(os.path.join(ctl, f"ready.{spec['process']}"))
    start_wall = float(markers.wait(os.path.join(ctl, "go"), poll_s=0.005))
    start = time.monotonic() + (start_wall - time.time())
    end = start + spec["seconds"]
    threads = [threading.Thread(target=w.loop, args=(start, end), daemon=True)
               for w in workers]
    late = max(0.0, time.monotonic() - start)
    gc.disable()        # the answers kept for judging are no garbage
    stages0 = counters.snapshot(prefix="stage.geo.")
    cpu0 = resource.getrusage(resource.RUSAGE_SELF)
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    cpu1 = resource.getrusage(resource.RUSAGE_SELF)
    stages1 = counters.snapshot(prefix="stage.geo.")
    want = reference_geo.Reference(spec["seed"], spec["points"], spec["rect"])
    return {"process": spec["process"], "late_s": late,
            "cpu_s": (cpu1.ru_utime + cpu1.ru_stime
                      - cpu0.ru_utime - cpu0.ru_stime),
            "stages": {k: v - stages0.get(k, 0) for k, v in stages1.items()},
            "workers": [w.judged(want) for w in workers]}


def main() -> int:
    with open(sys.argv[1]) as f:
        spec = json.load(f)
    out = run_load(spec) if spec["mode"] == "load" else run_search(spec)
    markers.put(spec["out"], json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
