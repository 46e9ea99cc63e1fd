"""The geo deployment served through the normal path: `onebox_serve`'s
server process and operator (3 metas, 3 replica nodes, collector;
compaction_backend = tpu; it alone holds the chip), with TWO tables where
that has one: the common table (a point under its owner's key) and the geo
index table (the same value under the 51-byte key `GeoClient._geo_keys`
makes). Both are loaded through `multi_set` by loader processes, compacted
through the shell, and searched by closed-loop client processes
(lib/geoclientproc.py does both). This process never imports jax.

What is compared once the window has closed (every number beside its
limit, all exact): every search of the warm-up and the window against the
plain reference (lib/reference_geo.py: brute force over all points, no
cells), judged by the client process that made it; a seed-drawn sample of
points, each read from the common table and searched for within 1 m of
its own coordinates; shell `trigger_audit` of both tables; that no run of
the index table was refused device residency for its keys' length from
boot to the window's end; and that no lane-guard total moved and nothing
compiled inside the window.

Before anything is loaded a probe (lib/geo_probe.py, jax held to the cpu)
asks the program under test whether a run of 51-byte keys is kept on the
device at all. A program that bypasses such runs would serve every search
from the host and this cell would measure that: the run ends there, with
no result.
"""

import io
import json
import os
import random
import re
import shutil
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor

from benchmarks.lib import device, markers, reference, reference_geo
from benchmarks.runners import onebox_serve
from benchmarks.runners.onebox_serve import Child, check

BYPASS = "engine.hbm.long_key_bypass_count"


class GeoDeployment(onebox_serve.Deployment):
    """onebox_serve's deployment over two tables: `on(which)` points the
    inherited one-table operations (create, manual compact, audit) at one
    of them."""

    def __init__(self, ctx, work: str):
        from pegasus_tpu.shell.main import Shell

        cfg = ctx.config
        self.ctx, self.work, self.seed = ctx, work, ctx.seed
        self.points = ctx.scale("points")
        self.rect = cfg["rectangle"]
        self.tables = {
            which: dict(cfg["tables"][which], partitions=ctx.scale("partitions"),
                        replicas=cfg["replicas"],
                        sortkeys=reference_geo.SORTKEYS)
            for which in ("common", "index")}
        self.app_ids = {}
        # what trigger_audit has to count in either table: one row a point
        # (the index row carries its owner's key, so it is as unique)
        self.records = self.points
        self.control = os.path.join(work, "control")
        os.makedirs(self.control)
        self.metas = onebox_serve.write_ini(ctx.root, work)
        self.shell_out = io.StringIO()
        self.shell = Shell(self.metas, out=self.shell_out)
        self.server, self.nodes, self.children = None, [], []
        self.sweep_wrong, self._reference = 0, None
        self.on("common")

    def on(self, which: str) -> "GeoDeployment":
        self.table = self.tables[which]
        self.name = self.table["name"]
        self.app_id = self.app_ids.get(which)
        self._which = which
        return self

    def create_table(self) -> None:
        super().create_table()
        self.app_ids[self._which] = self.app_id

    def geo_spec(self, **more) -> dict:
        wl, cfg = self.ctx.workload, self.ctx.config
        return dict(metas=self.metas, common=self.tables["common"]["name"],
                    index=self.tables["index"]["name"], seed=self.seed,
                    points=self.points, rect=self.rect,
                    min_level=cfg["geo"]["min_level"],
                    max_level=cfg["geo"]["max_level"],
                    scan_threads=wl["scan_threads"],
                    timeout_s=wl["client_timeout_s"], **more)

    @property
    def reference(self):
        """The plain reference over this run's points, made once."""
        if self._reference is None:
            self._reference = reference_geo.Reference(self.seed, self.points,
                                                      self.rect)
        return self._reference

    def geo_client(self):
        from benchmarks.lib import geoclientproc

        return geoclientproc.connect(self.geo_spec())

    # ---- the probe

    def start_probe(self) -> None:
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        self.probe = Child([sys.executable,
                            os.path.join(self.ctx.here, "lib", "geo_probe.py"),
                            os.path.join(self.work, "probe")],
                           self.ctx.root, env,
                           os.path.join(self.work, "probe.log"))
        self.children.append(self.probe)

    def probe_verdict(self) -> None:
        t0 = time.monotonic()
        while self.probe.alive():
            check(time.monotonic() - t0 < 300, "the residency probe hangs",
                  self.probe.log_tail())
            time.sleep(0.1)
        check(self.probe.proc.returncode == 0, "the residency probe failed",
              self.probe.log_tail())
        got = json.loads(self.probe.log_tail().strip().splitlines()[-1])
        self.ctx.say("residency probe: a run of 51-byte keys", **got)
        check(got["long_key_bypass"] == 0 and got["resident"],
              "the program under test refuses a run of the index table's "
              "51-byte keys device residency: every search would be served "
              "by the host, and this cell would measure the host", got)

    # ---- set-up

    def load(self) -> None:
        """Both tables through `multi_set`, by loader processes that each
        take a contiguous share of the points."""
        cfg = self.ctx.config["load"]
        n_proc = cfg["processes"]
        share = -(-self.points // n_proc)
        specs = [self.geo_spec(
            mode="load", process=p, threads=cfg["threads_per_process"],
            lo=p * share, hi=min(self.points, (p + 1) * share),
            batch=cfg["rows_per_multi_set"])
            for p in range(n_proc)]
        t0 = time.monotonic()
        results = self.run_clients("load", specs, limit_s=1800)
        errors = [e for r in results for e in r["errors"]]
        check(not errors, "load failed", errors)
        done = {k: sum(r["done"][k] for r in results)
                for k in ("common", "index")}
        check(done["common"] == done["index"] == self.points,
              "loaders stopped short", done)
        took = time.monotonic() - t0
        self.ctx.say(f"loaded {self.points:,} points into both tables "
                     f"({2 * self.points * 124 / 1e9:.2f} GB of user data) in "
                     f"{took:.0f}s, {2 * self.points / took:,.0f} records/s; "
                     f"every write acknowledged", **done)

    # ---- client processes

    def run_clients(self, label: str, specs: list, limit_s: float,
                    seconds: float = None, on_start=None):
        """One lib/geoclientproc.py a spec, to their end -> their results.
        With `seconds` (a search phase) they start together on `go`;
        -> (start, results) then."""
        ctl = os.path.join(self.control, label)
        os.makedirs(ctl)
        procs = []
        for spec in specs:
            p = spec["process"]
            spec.update(control=ctl, out=os.path.join(ctl, f"result.{p}.json"))
            path = os.path.join(ctl, f"spec.{p}.json")
            with open(path, "w") as f:
                json.dump(spec, f)
            child = Child([sys.executable,
                           os.path.join(self.ctx.here, "lib",
                                        "geoclientproc.py"), path],
                          self.ctx.root, dict(os.environ),
                          os.path.join(ctl, f"client.{p}.log"))
            procs.append((child, spec))
            self.children.append(child)
        start = None
        if seconds is not None:
            t0 = time.monotonic()
            while not all(os.path.exists(os.path.join(ctl, f"ready.{p}"))
                          for p in range(len(procs))):
                for child, _ in procs:
                    check(child.alive(), "a client process died before the "
                                         "start", child.log_tail())
                check(time.monotonic() - t0 < 120,
                      "clients not ready after 120 s")
                time.sleep(0.01)
            start = time.monotonic() + 0.25
            markers.put(os.path.join(ctl, "go"),
                        repr(time.time() + (start - time.monotonic())))
            if on_start is not None:
                on_start(start)
        limit = time.monotonic() + limit_s
        results = []
        for child, spec in procs:
            while child.alive():
                check(self.server.alive(), f"server died during {label}",
                      self.server.log_tail())
                check(time.monotonic() < limit, "a client process hangs")
                time.sleep(0.05)
            check(child.proc.returncode == 0, "a client process failed",
                  child.log_tail())
            child.stop()
            with open(spec["out"]) as f:
                results.append(json.load(f))
        return results if seconds is None else (start, results)

    def offer(self, label: str, seconds: float, phase: int,
              on_start=None) -> dict:
        wl = self.ctx.workload
        specs = [self.geo_spec(mode="search", process=p, phase=phase,
                               threads=wl["threads_per_process"],
                               radius_m=wl["radius_m"], seconds=seconds)
                 for p in range(wl["client_processes"])]
        # the clients judge their answers after the loop: 64 ms a search
        start, results = self.run_clients(
            label, specs, seconds=seconds, on_start=on_start,
            limit_s=seconds + wl["client_timeout_s"] + 600)
        return {"start": start, "window_s": seconds, "results": results}

    # ---- warm-up

    def sweep(self) -> None:
        """The range kernel is compiled per (SST shape, size of a coalesced
        batch of ranges), and a size that closed-loop searches form once a
        minute must not be met first inside the window. So, before the
        passes: at every hashkey of the index table (its cells: a few a
        partition, so every SST of every partition), as many clients as a
        step says each open a scanner over a few rows of that ONE hashkey
        at the same moment, again with other rows until the server's own
        spans show one `read.range` call of at least the step's
        `ranges_in_a_call`; the whole sweep again until one leaves the
        read lane's compile_behind and the compile report's `compiled`
        unmoved. Writes nothing; every row that comes back is held to be a
        stored point inside the range asked for."""
        sw = self.ctx.workload["warm_up"]["sweep"]
        want = self.reference
        common, index, geo = self.geo_client()
        by_cell = {}
        for i in range(min(self.points, sw["sample_points"])):
            ghk, gsk = geo._geo_keys(want.lat[i], want.lng[i],
                                     *reference_geo.owner_key(self.seed, i))
            by_cell.setdefault(ghk, []).append(int(gsk[:15], 16))
        geo.close()
        common.close()
        cells = sorted(by_cell)
        rng = random.Random(self.seed)
        wrong, failed = [], []      # appended to by the pool's threads
        n_clients = max(st["threads"] for st in sw["steps"])
        clients = [index] + [self.on("index").client()
                             for _ in range(n_clients - 1)]
        pool = ThreadPoolExecutor(n_clients)
        span = sw["morton_span"]

        def scan(cli, ghk: bytes, start_m: int) -> None:
            lo, hi = b"%015x" % start_m, b"%015x" % (start_m + span)
            try:
                rows = list(cli.get_scanner(ghk, start_sort_key=lo,
                                            stop_sort_key=hi, batch_size=500))
            except Exception as e:  # noqa: BLE001 - counted and told
                failed.append(repr(e))
                return
            for hk, gsk, value in rows:
                i = reference_geo.check_value(self.seed, value, want.lat,
                                              want.lng)
                if i is None or hk != ghk or not lo <= gsk[:15] < hi \
                        or (hk, gsk) != geo._geo_keys(
                            want.lat[i], want.lng[i],
                            *reference_geo.owner_key(self.seed, i)):
                    wrong.append((ghk, gsk))

        def together(ghk: bytes, k: int) -> None:
            """k clients, one range each of hashkey ghk, released at once."""
            starts = rng.choices(by_cell[ghk], k=k)
            gate = threading.Barrier(k)

            def one(c: int) -> None:
                gate.wait(60)
                scan(clients[c], ghk, starts[c])

            list(pool.map(one, range(k)))

        def largest_call(since: float) -> int:
            text = self.shell._node_command(self.nodes[0],
                                            "compact-trace-dump", ["400"])
            return max((int(n) for ts, n in re.findall(
                r"(?m)^(\d+\.\d+) +read\.range \d+us records=(\d+)", text)
                if float(ts) >= since), default=0)

        def still(health: dict) -> tuple:
            return (health["read_lane"]["compile_behind"],
                    health["compile"]["compiled"])

        try:
            for cli in clients:     # connect one by one: SYNs in a burst
                for ghk in cells:   # wait a second behind the listen queue
                    scan(cli, ghk, by_cell[ghk][0])
            for n_sweep in range(1, sw["max_sweeps"] + 1):
                t0, before = time.monotonic(), still(self.health())
                told = []
                for st in sw["steps"]:
                    need = st["ranges_in_a_call"]
                    proven = rounds = 0
                    for ghk in cells:
                        for _ in range(sw["tries"]):
                            since = time.time()
                            together(ghk, st["threads"])
                            rounds += 1
                            if largest_call(since) >= need:
                                proven += 1
                                break
                    told.append(dict(st, hashkeys_proven=proven,
                                     rounds=rounds))
                after = still(self.wait_compiles(f"sweep {n_sweep}"))
                self.ctx.say(
                    f"sweep {n_sweep}: {len(cells)} hashkeys in "
                    f"{time.monotonic() - t0:.1f}s left compile_behind at "
                    f"{after[0]} (+{after[0] - before[0]}), compiled at "
                    f"{after[1]} (+{after[1] - before[1]})", steps=told,
                    scans_failed=len(failed), errors=failed[:3])
                if after == before:
                    check(all(st["hashkeys_proven"] for st in told),
                          "the server's spans never showed a range call of "
                          "some step's size", told)
                    return
            check(False, f"no sweep of {sw['max_sweeps']} left the range "
                         f"kernels as it found them")
        finally:
            self.sweep_wrong = len(wrong) + len(failed)
            pool.shutdown()
            for cli in clients:
                cli.close()

    def warm_up(self) -> None:
        """The sweep; then the window's own searches in short passes,
        until one leaves the read lane's compile_behind and the compile
        report's `compiled` unmoved."""
        wl = self.ctx.workload["warm_up"]
        self.sweep()
        self.warm = []
        for n in range(1, wl["max_passes"] + 1):
            h = self.health()
            before = (h["read_lane"]["compile_behind"],
                      h["compile"]["compiled"])
            self.warm.append(self.offer(f"warm{n}", wl["pass_seconds"],
                                        phase=1000 + n))
            h = self.wait_compiles(f"warm-up pass {n}")
            if (h["read_lane"]["compile_behind"],
                    h["compile"]["compiled"]) == before \
                    and n >= wl["min_passes"]:
                return
        check(False, "no warm-up pass ran with every kernel compiled")

    # ---- the comparison

    def audit(self) -> dict:
        """Shell `trigger_audit <table> <timeout_s>`, as a user types it,
        counted as `onebox_serve` counts it. The shell's default wait (5 s
        + the largest replica's SST bytes at 4 MB/s) is sized for 1 KB
        records; a digest costs by the record, and a record here is 150 B
        (the largest index partition takes 8 s of a default of 21 s), so
        the cell states its wait. A report with a partition INCONCLUSIVE
        (the program's word: no digest at the audit's decree within the
        wait; never a mismatch) is told with its reasons and the table is
        audited again, `tries` times at most: the verdict is that of the
        last report, whole (every partition: three identical digests at
        one decree, every record counted). A mismatch is never asked
        about twice."""
        cfg = self.ctx.workload["audit"]
        reps, parts = self.table["replicas"], self.table["partitions"]
        for attempt in range(1, cfg["tries"] + 1):
            check(self.server.alive(), "server died before trigger_audit",
                  self.server.log_tail())
            t0 = time.monotonic()
            out = self.shell_line(
                f"trigger_audit {self.name} {cfg['timeout_s']:g}")
            try:
                report, _ = json.JSONDecoder().raw_decode(out)
            except ValueError:
                check(False, "trigger_audit printed no report", out[-2000:])
            differing = len(report["mismatches"]) + len(report["inconclusive"])
            for by_node in report["digests"].values():
                if not (len(by_node) == reps and len(
                        {(d["decree"], d["digest"])
                         for d in by_node.values()}) == 1):
                    differing += 1
            differing += abs(len(report["digests"]) - parts)
            records = sum(p["records"] for p in report["primaries"].values())
            self.ctx.say(f"trigger_audit {self.name}: {records:,} records on "
                         f"{reps} replicas in {time.monotonic() - t0:.0f}s")
            if not report["inconclusive"] or report["mismatches"]:
                break
            self.ctx.say(
                f"trigger_audit {self.name}, try {attempt} of {cfg['tries']}: "
                f"the program could not tell",
                inconclusive=report["inconclusive"],
                records={g: p["records"]
                         for g, p in report["primaries"].items()},
                server_log=self.server.log_tail(30))
        return {"replicas_differing": differing,
                "audit_record_gap": abs(records - self.records)}

    def unreachable(self) -> dict:
        """Of a seed-drawn sample of points, those a `get` on the common
        table does not return, or a 1 m search at their own coordinates."""
        want = self.reference
        rng = random.Random(self.seed)
        ids = rng.sample(range(self.points),
                         min(self.points, self.ctx.workload["point_sample"]))
        n_threads = 8
        missing = [0] * n_threads

        def worker(t: int) -> None:
            common, index, geo = self.geo_client()
            try:
                for i in ids[t::n_threads]:
                    hk, sk = reference_geo.owner_key(self.seed, i)
                    value = want.value(i)
                    found = [(h, s, v) for _, h, s, v in geo.search_radial(
                        want.lat[i], want.lng[i], 1.0, count=-1,
                        sort_by_distance=False)]
                    if common.get(hk, sk) != value \
                            or (hk, sk, value) not in found:
                        missing[t] += 1
            finally:
                geo.close()
                common.close()
                index.close()

        t0 = time.monotonic()
        with ThreadPoolExecutor(n_threads) as pool:
            list(pool.map(worker, range(n_threads)))
        self.ctx.say(f"looked for {len(ids):,} sampled points in both tables "
                     f"in {time.monotonic() - t0:.1f}s",
                     unreachable=sum(missing))
        return {"points_unreachable": sum(missing)}


def pooled(window: dict) -> dict:
    """All clients' searches of one phase, pooled."""
    out = {"lat": [], "at": [], "done": 0, "failed": 0, "wrong": 0,
           "errors": [], "cpu_s": 0.0, "late_s": 0.0, "rows_returned": 0,
           "stages": {}}
    for res in window["results"]:
        out["cpu_s"] += res["cpu_s"]
        out["late_s"] = max(out["late_s"], res["late_s"])
        for k, v in res["stages"].items():
            out["stages"][k] = out["stages"].get(k, 0) + v
        for w in res["workers"]:
            out["lat"] += w["lat"]
            out["at"] += w["at"]
            out["errors"] += w["errors"]
            for k in ("done", "failed", "wrong", "rows_returned"):
                out[k] += w[k]
    return out


def client_stages(stages: dict) -> dict:
    """The client processes' own `stage.geo.*` totals as ms a span."""
    return {name: round(stages[f"stage.{name}.us"] / 1000.0
                        / stages[f"stage.{name}.n"], 3)
            for name in ("geo.search", "geo.cover", "geo.scan", "geo.filter")
            if stages.get(f"stage.{name}.n")}


SERVER_STAGES = ("rpc.queue", "rpc.server.RPC_RRDB_RRDB_GET_SCANNER",
                 "rpc.server.RPC_RRDB_RRDB_SCAN", "rpc.reply",
                 "read.range.coalesce_wait", "read.range", "read.range.pack",
                 "read.range.dispatch", "read.range.download")


def server_stages(before: dict, after: dict) -> dict:
    """The server's `stage.` totals over the window -> {span: [closes,
    ms a close]}, for the spans a search crosses."""
    out = {}
    for name in SERVER_STAGES:
        n = after.get(f"stage.{name}.n", 0) - before.get(f"stage.{name}.n", 0)
        us = after.get(f"stage.{name}.us", 0) - before.get(f"stage.{name}.us", 0)
        if n:
            out[name] = [n, round(us / 1000.0 / n, 3)]
    return out


def run(ctx) -> dict:
    from pegasus_tpu import native

    native.available()          # build the native libraries once, before
    native.fastcodec()          # ten processes race to
    wl = ctx.workload
    work = tempfile.mkdtemp(prefix="bench_geo_")
    dep = GeoDeployment(ctx, work)
    try:
        dep.start_probe()
        ident = dep.boot()
        bypass0 = dep.health()["bypass"][BYPASS]
        dep.probe_verdict()
        for which in ("common", "index"):
            dep.on(which).create_table()
        dep.load()
        for which in ("common", "index"):
            dep.on(which).manual_compact()
        dep.wait_compiles("after manual_compact")
        dep.warm_up()

        names = sorted(wl["counters"])
        before_c = dep.counters(*names)
        before = dep.still_state()

        def on_start(start):
            if ctx.trace:
                dep.trace_schedule(start, ctx.seconds)

        window = dep.offer("window", ctx.seconds, phase=1, on_start=on_start)
        after = dep.still_state()
        after_c = dep.counters(*names)
        bypass = after["health"]["bypass"][BYPASS] - bypass0
        memory = after["health"]["device_memory"] or {}
        ops = pooled(window)
        ctx.say(f"window: {ops['done']} searches in {ctx.seconds:g}s, "
                f"{ops['rows_returned']:,} points returned",
                failed=ops["failed"], wrong=ops["wrong"],
                errors=ops["errors"][:3],
                clients_late_s=round(ops["late_s"], 4))

        trace = None
        if ctx.trace:
            trace = dep.trace_result()
            ident = dict(ident, busy_s=trace["busy_s"] or 0.0,
                         window_s=trace["window_s"])
        back = dep.unreachable()
        audits = [dep.on(which).audit() for which in ("common", "index")]
    finally:
        dep.stop()
        shutil.rmtree(work, ignore_errors=True)

    warm = [pooled(w) for w in dep.warm]
    tails = {f"read_p{q:g}": reference.percentile(ops["lat"], q)
             for q in (50, 90, 95, 99, 99.9) if ops["lat"]}
    delta = {k: v - before_c.get(k, 0) for k, v in after_c.items()
             if isinstance(v, (int, float)) and not k.startswith("stage.")}
    peaks = None if ctx.rehearsal else device.peaks(ident["kind"])
    return {
        "attempted": len(ops["lat"]), "failed": ops["failed"],
        "end_to_end": dict(tails, setup_s=window["start"] - ctx.t0,
                           ycsb_ops=ops["done"] / ctx.seconds),
        "observed": {
            "ops": {"read": ops["done"]}, "window_s": ctx.seconds,
            "trace": trace, "client_tails": tails,
            "counters": {"before": before_c, "after": after_c},
            "rates": {}, "peaks": peaks,
            "range_shapes": {
                "rows": dep.points // dep.tables["index"]["partitions"],
                "key_bytes": ctx.config["tables"]["index"]["key_bytes"]},
            "clients": {"cpu_s": ops["cpu_s"], "window_s": ctx.seconds,
                        "processes": wl["client_processes"]}},
        "breakdown": trace and {"device_ops": trace["device_ops"],
                                "idle_gaps": trace["idle_gaps"]},
        "device": dict(ident, memory_peak_bytes=int(
            memory.get("peak_bytes_in_use") or 0)),
        "compared": [
            ("searches_wrong", ops["wrong"] + sum(w["wrong"] for w in warm)
             + dep.sweep_wrong, 0),
            ("points_unreachable", back["points_unreachable"], 0),
            ("replicas_differing",
             sum(a["replicas_differing"] for a in audits), 0),
            ("audit_record_gap",
             sum(a["audit_record_gap"] for a in audits), 0),
            ("long_key_bypass", bypass, 0),
            ("guard_totals_moved", after["guard"] - before["guard"], 0),
            ("compiles_in_window", after["compiles"] - before["compiles"], 0),
        ],
        "notes": [
            f"ms {json.dumps({k: round(v, 3) for k, v in tails.items()})}; "
            f"points a search {ops['rows_returned'] / max(1, len(ops['lat']) - ops['failed']):.1f}; "
            f"client ms a span {json.dumps(client_stages(ops['stages']))}; "
            f"server [closes, ms a span] "
            f"{json.dumps(server_stages(before_c, after_c))}; "
            f"counters {json.dumps(delta)}; "
            f"compile {after['health']['compile']}"],
    }
