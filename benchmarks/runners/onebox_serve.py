"""A replicated table served through the normal path: one server process
(`python -m pegasus_tpu.server` under lib/serverproc.py: 3 metas, 3 replica
nodes, collector; compaction_backend = tpu; it alone holds the chip), this
process as its operator over sockets (shell `create`, load through
set/multi_set, shell `manual_compact`), and the traffic mix offered by
closed-loop client processes (lib/clientproc.py). This process never
imports jax. Copied from chip_smoke.py's serve phase, which the chip has
run: ini from onebox.ini, boot, create, load, manual_compact, the waits.

What is compared once the window has closed (every number beside its
limit, all exact): every read of the window, checked by the client that
made it; every record an acknowledged update touched, read back and held
to be the last write of one of its writers; a seed-drawn sample of records
nobody updated, held to be as loaded; shell `trigger_audit` (all replicas
of every partition digest-identical at identical decrees, and as many
records as were loaded); and that no lane-guard total moved and nothing
compiled inside the window.
"""

import io
import json
import os
import random
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor

from benchmarks.lib import datagen, device, markers, reference

LOAD_WRITER = 0


class Failure(Exception):
    """The deployment did not come up or did not hold still: no result."""


def check(cond, what: str, detail=None) -> None:
    if not cond:
        raise Failure(what if detail is None else f"{what}: {detail}")


def free_ports(n: int) -> list:
    socks = [socket.socket() for _ in range(n)]
    try:
        for s in socks:
            s.bind(("127.0.0.1", 0))
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


def write_ini(root: str, work: str) -> list:
    """An ini derived from the repo's onebox.ini: same apps, this run's
    directories and ports, the tpu compaction backend switched on, no
    serve_groups. -> the meta address list."""
    with open(os.path.join(root, "onebox.ini")) as f:
        ini = f.read()
    old_ports = sorted(set(re.findall(r"\b34[0-9]{3}\b", ini)))
    for old, new in zip(old_ports, free_ports(len(old_ports))):
        ini = ini.replace(old, str(new))
    ini = ini.replace("pegasus-data", os.path.join(work, "data"))
    ini, n = re.subn(r"(?m)^# (compaction_backend = tpu)\b.*$", r"\1", ini)
    check(n == 1, "onebox.ini no longer carries the commented "
                  "compaction_backend line")
    with open(os.path.join(work, "bench.ini"), "w") as f:
        f.write(ini)
    metas = re.search(r"(?m)^meta_servers = (.*)$", ini).group(1)
    return [m.strip() for m in metas.split(",")]


class Child:
    """A process this run starts, stops and waits for."""

    def __init__(self, argv: list, cwd: str, env: dict, log_path: str):
        self.log_path = log_path
        self._log = open(log_path, "w")
        self.proc = subprocess.Popen(argv, cwd=cwd, env=env, stdout=self._log,
                                     stderr=subprocess.STDOUT,
                                     stdin=subprocess.DEVNULL)

    def log_tail(self, n: int = 40) -> str:
        with open(self.log_path, errors="replace") as f:
            return "".join(f.readlines()[-n:])

    def alive(self) -> bool:
        return self.proc.poll() is None

    def stop(self, grace_s: float = 20) -> None:
        if self.alive():
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=grace_s)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._log.close()


class Deployment:
    def __init__(self, ctx, work: str):
        from pegasus_tpu.shell.main import Shell

        self.ctx, self.work = ctx, work
        self.table = dict(ctx.config["table"],
                          partitions=ctx.scale("partitions"),
                          hashkeys=ctx.scale("hashkeys"))
        self.seed = ctx.seed
        self.name = self.table["name"]
        self.records = self.table["hashkeys"] * self.table["sortkeys"]
        self.control = os.path.join(work, "control")
        os.makedirs(self.control)
        self.metas = write_ini(ctx.root, work)
        self.shell_out = io.StringIO()
        self.shell = Shell(self.metas, out=self.shell_out)
        self.server, self.nodes, self.children = None, [], []
        self.sweep_wrong = 0

    # ---- plumbing over sockets

    def shell_line(self, line: str) -> str:
        """One shell command, exactly as a user would type it -> its output."""
        self.shell_out.seek(0)
        self.shell_out.truncate()
        self.shell.run_line(line)
        return self.shell_out.getvalue()

    def node_json(self, node: str, command: str, args=()):
        return json.loads(self.shell._node_command(node, command, list(args)))

    def counters(self, *prefixes) -> dict:
        # all three nodes are one process and share one registry
        return self.node_json(self.nodes[0], "perf-counters-by-prefix",
                              prefixes)

    def client(self):
        """A client as a user gets it: the default 10 s timeout."""
        from pegasus_tpu.client import MetaResolver, PegasusClient

        return PegasusClient(MetaResolver(self.metas, self.name))

    def health(self) -> dict:
        return self.node_json(self.nodes[0], "device-health")

    def still_state(self) -> dict:
        """The totals that must stand still inside a window."""
        h = self.health()
        return dict(device.still_totals([h["lane"], h["read_lane"]],
                                        h["compile"]), health=h)

    def wait_compiles(self, why: str) -> dict:
        t0 = time.monotonic()
        while True:
            health = self.health()
            if health["compile"]["inflight"] == 0:
                break
            check(self.server.alive(), "server died while compiling",
                  self.server.log_tail())
            check(time.monotonic() - t0 < 900,
                  "kernels still compiling after 900 s", health["compile"])
            time.sleep(0.5)
        self.ctx.say(f"{why}: compile pool idle after "
                     f"{time.monotonic() - t0:.0f}s", compile=health["compile"])
        return health

    # ---- set-up

    def boot(self) -> dict:
        from pegasus_tpu.rpc.transport import RpcError

        t0 = time.monotonic()
        env = dict(os.environ)
        self.server = Child(
            [sys.executable, os.path.join(self.ctx.here, "lib",
                                          "serverproc.py"),
             "--config", os.path.join(self.work, "bench.ini"),
             "--control", self.control],
            self.ctx.root, env, os.path.join(self.work, "server.log"))
        while True:
            check(self.server.alive(), "server exited during boot",
                  self.server.log_tail())
            check(time.monotonic() - t0 < 180, "server not up after 180 s",
                  self.server.log_tail())
            try:
                nodes = [n.address for n in self.shell._nodes() if n.alive]
                if len(nodes) == 3:
                    break
            except (RpcError, OSError):
                pass
            time.sleep(0.5)
        self.nodes = sorted(nodes)
        dev = self.health()["device"]
        check(dev is not None, "server reports no device identity")
        ident = {"platform": dev["platform"], "kind": dev["device_kind"],
                 "count": dev["device_count"]}
        self.ctx.say(f"server up in {time.monotonic() - t0:.1f}s", device=ident)
        if not self.ctx.rehearsal:
            check(ident["platform"] == "tpu", "the server's kernels are not "
                  "on a TPU (use --rehearsal for a cpu run)", ident)
            check(ident["count"] >= self.ctx.cell["chips"],
                  "fewer chips than the cell asks for", ident)
        return ident

    def create_table(self) -> None:
        from pegasus_tpu.meta import messages as mm
        from pegasus_tpu.meta.meta_server import RPC_CM_QUERY_CONFIG

        parts, reps = self.table["partitions"], self.table["replicas"]
        out = self.shell_line(f"create {self.name} -p {parts} -r {reps}")
        m = re.search(rf"create app {self.name} succeed, id=(\d+)", out)
        check(m is not None, "create failed", out)
        self.app_id = int(m.group(1))
        t0 = time.monotonic()
        while True:
            cfg = self.shell._meta_call(RPC_CM_QUERY_CONFIG,
                                        mm.QueryConfigRequest(self.name),
                                        mm.QueryConfigResponse)
            if all(pc.primary and len(pc.secondaries) == reps - 1
                   for pc in cfg.partitions):
                break
            check(time.monotonic() - t0 < 60, "table not fully replicated "
                                              "after 60 s")
            time.sleep(0.2)

    def load(self) -> None:
        """Every record through set/multi_set; a call that returns is an
        acknowledged write. One hashkey in 50 goes record by record
        through `set`, the rest as one `multi_set` per hashkey."""
        n_threads, per = self.table["load_threads"], self.table["sortkeys"]
        size = self.table["value_bytes"]
        errors, done = [], [0] * n_threads

        def worker(tid):
            cli = self.client()
            try:
                for h in range(tid, self.table["hashkeys"], n_threads):
                    rows = [datagen.record_key(self.seed, i, per)
                            + (datagen.make_value(self.seed, i, LOAD_WRITER,
                                                  0, size),)
                            for i in range(h * per, (h + 1) * per)]
                    if h % 50 == 7:
                        for hk, sk, v in rows:
                            cli.set(hk, sk, v)
                    else:
                        cli.multi_set(rows[0][0], {sk: v for _, sk, v in rows})
                    done[tid] += len(rows)
            except Exception as e:  # noqa: BLE001 - reported, then fails the run
                errors.append(f"loader {tid} at hashkey {h}: {e!r}")
            finally:
                cli.close()

        t0 = time.monotonic()
        threads = [threading.Thread(target=worker, args=(t,), daemon=True)
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        while any(t.is_alive() for t in threads):
            check(self.server.alive(), "server died during load",
                  self.server.log_tail())
            check(not errors, "load failed", errors)
            time.sleep(0.5)
        check(not errors, "load failed", errors)
        check(sum(done) == self.records, "loaders stopped short", sum(done))
        self.ctx.say(f"loaded {self.records:,} records "
                     f"({self.records * (size + 24) / 1e9:.2f} GB of user "
                     f"data) in {time.monotonic() - t0:.0f}s; every write "
                     f"acknowledged")

    def manual_compact(self) -> None:
        """Shell `use <table>` + `manual_compact`, as a user types them;
        every replica then compacts in the background, waiting for its
        merge kernel, so the lane's compile_behind must not move."""
        before = self.health()
        t0 = time.monotonic()
        trigger_ms = int(time.time()) * 1000
        self.shell_line(f"use {self.name}")
        out = self.shell_line("manual_compact")
        check("manual compact triggered" in out and "ERROR" not in out,
              "shell manual_compact failed", out)
        want = {f"{self.app_id}.{p}" for p in range(self.table["partitions"])}
        total = self.table["partitions"] * self.table["replicas"]
        while True:
            check(self.server.alive(), "server died during manual compact",
                  self.server.log_tail())
            check(time.monotonic() - t0 < 900,
                  "manual compact not finished on every replica after 900 s")
            finished = 0
            for node in self.nodes:
                text = self.shell._node_command(node, "query-compact-state", [])
                for line in text.splitlines():
                    gpid, _, st = line.partition(": ")
                    if gpid not in want:
                        continue
                    check("FAILED" not in st, f"manual compact failed on "
                                              f"{node} {gpid}", st)
                    m = re.search(r"idle; last finish at (\d+)", st)
                    if m and int(m.group(1)) >= trigger_ms:
                        finished += 1
            if finished == total:
                break
            time.sleep(0.5)
        after = self.health()
        check(after["lane"]["compile_behind"]
              == before["lane"]["compile_behind"],
              "a manual compaction took the host lane instead of waiting "
              "for its kernel")
        self.ctx.say(f"manual compact finished on all {finished} replicas "
                     f"in {time.monotonic() - t0:.0f}s")

    # ---- traffic

    def offer(self, label: str, seconds: float, writer_base: int,
              on_start=None) -> dict:
        """Run the cell's mix from its client processes for `seconds`;
        -> {start (monotonic), window_s, results: [per process]}."""
        wl = self.ctx.workload
        ctl = os.path.join(self.control, label)
        os.makedirs(ctl)
        procs = []
        for p in range(wl["client_processes"]):
            spec = {"metas": self.metas, "table": self.name,
                    "seed": self.seed, "process": p,
                    "threads": wl["threads_per_process"],
                    "writer_base": writer_base, "records": self.records,
                    "sortkeys": self.table["sortkeys"],
                    "value_bytes": self.table["value_bytes"],
                    "theta": wl["zipfian_constant"], "mix": wl["mix"],
                    "seconds": seconds, "timeout_s": wl["client_timeout_s"],
                    "control": ctl,
                    "out": os.path.join(ctl, f"result.{p}.json")}
            path = os.path.join(ctl, f"spec.{p}.json")
            with open(path, "w") as f:
                json.dump(spec, f)
            child = Child([sys.executable,
                           os.path.join(self.ctx.here, "lib", "clientproc.py"),
                           path], self.ctx.root, dict(os.environ),
                          os.path.join(ctl, f"client.{p}.log"))
            procs.append((child, spec))
            self.children.append(child)
        t0 = time.monotonic()
        while not all(os.path.exists(os.path.join(ctl, f"ready.{p}"))
                      for p in range(len(procs))):
            for child, _ in procs:
                check(child.alive(), "a client process died before the start",
                      child.log_tail())
            check(time.monotonic() - t0 < 60, "clients not ready after 60 s")
            time.sleep(0.01)
        start = time.monotonic() + 0.25
        markers.put(os.path.join(ctl, "go"),
                    repr(time.time() + (start - time.monotonic())))
        if on_start is not None:
            on_start(start)
        limit = start + seconds + wl["client_timeout_s"] + 60
        results = []
        for child, spec in procs:
            while child.alive():
                check(self.server.alive(), "server died under load",
                      self.server.log_tail())
                check(time.monotonic() < limit, "a client process hangs")
                time.sleep(0.05)
            check(child.proc.returncode == 0, "a client process failed",
                  child.log_tail())
            child.stop()
            with open(spec["out"]) as f:
                results.append(json.load(f))
        return {"start": start, "window_s": seconds, "results": results}

    def sample_rates(self, names: list, start: float, seconds: float) -> None:
        """Some of the server's counters publish a rate over a rolling
        window of a second or more and no count: scrape them once a second
        through the window; the mean rate times the window is the count."""
        self.rate_samples = {n: [] for n in names}

        def run():
            time.sleep(max(0.0, start + 1.5 - time.monotonic()))
            while time.monotonic() < start + seconds:
                got = self.counters(*names)
                for n in names:
                    if n in got:
                        self.rate_samples[n].append(got[n])
                time.sleep(1.0)

        self.rate_thread = threading.Thread(target=run, daemon=True)
        self.rate_thread.start()

    def sweep(self) -> None:
        """Where the cell's `warm_up` has a `sweep`: the read kernels are
        compiled per (SST shape, size of a coalesced batch), and a shape
        or size that closed-loop traffic forms once a minute must not be
        met first inside the window. So, before the passes: at hashkeys
        spread evenly over every partition's share of the loaded key space
        (so over each of its SSTs), as many clients as a step says read
        different sortkeys of that ONE hashkey at the same moment, again
        with other sortkeys until the server's own spans show one lookup
        call of at least the step's `keys_in_a_call`; the whole
        sweep again until one leaves the read lane's compile_behind and the
        compile report's `compiled` unmoved. Writes nothing; every answer
        is checked like any read of the window."""
        sw = self.ctx.workload["warm_up"].get("sweep")
        if not sw:
            return
        from pegasus_tpu.base import key_schema

        per, parts = self.table["sortkeys"], self.table["partitions"]
        size = self.table["value_bytes"]
        by_part = [[] for _ in range(parts)]
        for h in range(self.table["hashkeys"]):     # ascending = key order
            hk = datagen.record_key(self.seed, h * per, per)[0]
            by_part[key_schema.hash_key_hash(hk) % parts].append(h)
        n = sw["hashkeys_per_partition"]
        hashkeys = [hs[(2 * j + 1) * len(hs) // (2 * n)] for hs in by_part
                    for j in range(min(n, len(hs)))]
        rng = random.Random(self.seed)
        wrong, failed = [], []      # appended to by the pool's threads
        clients = [self.client()
                   for _ in range(max(st["threads"] for st in sw["steps"]))]
        pool = ThreadPoolExecutor(len(clients))

        def read(cli, i: int) -> None:
            """One get, judged like a read of the window: an answer that
            is not the record's is wrong, a call that raises has failed."""
            try:
                value = cli.get(*datagen.record_key(self.seed, i, per))
            except Exception as e:  # noqa: BLE001 - counted and told
                failed.append(repr(e))
                return
            if datagen.check_value(self.seed, i, value, size) is None:
                wrong.append(i)

        def together(h: int, k: int) -> None:
            """k clients, one sortkey each of hashkey h, released at once."""
            ids = [h * per + j for j in rng.sample(range(per), k)]
            gate = threading.Barrier(k)

            def one(c: int) -> None:
                gate.wait(60)
                read(clients[c], ids[c])

            list(pool.map(one, range(k)))

        def largest_call(since: float) -> int:
            """The most keys that one lookup call on one SST took since
            `since` (this host's clock): the program's ring of closed stage
            spans keeps each `read.device` with the keys it carried."""
            text = self.shell._node_command(self.nodes[0],
                                            "compact-trace-dump", ["200"])
            return max((int(n) for ts, n in re.findall(
                r"(?m)^(\d+\.\d+) +read\.device \d+us records=(\d+)", text)
                if float(ts) >= since), default=0)

        def still(health: dict) -> tuple:
            return (health["read_lane"]["compile_behind"],
                    health["compile"]["compiled"])

        try:
            for cli in clients:     # connect one by one: SYNs in a burst
                for hs in by_part:  # wait a second behind the listen queue
                    read(cli, hs[0] * per)
            for n_sweep in range(1, sw["max_sweeps"] + 1):
                t0, before = time.monotonic(), still(self.health())
                told = []
                for st in sw["steps"]:
                    need = st["keys_in_a_call"]
                    proven = rounds = 0
                    for h in hashkeys:
                        for _ in range(sw["tries"]):
                            since = time.time()
                            together(h, min(st["threads"], per))
                            rounds += 1
                            if largest_call(since) >= need:
                                proven += 1
                                break
                    told.append(dict(st, hashkeys_proven=proven,
                                     rounds=rounds))
                after = still(self.wait_compiles(f"sweep {n_sweep}"))
                self.ctx.say(
                    f"sweep {n_sweep}: {len(hashkeys)} hashkeys in "
                    f"{time.monotonic() - t0:.1f}s left compile_behind at "
                    f"{after[0]} (+{after[0] - before[0]}), compiled at "
                    f"{after[1]} (+{after[1] - before[1]})", steps=told,
                    reads_failed=len(failed), errors=failed[:3])
                if after == before:
                    return
            check(False, f"no sweep of {sw['max_sweeps']} left the read "
                         f"kernels as it found them")
        finally:
            self.sweep_wrong = len(wrong)
            pool.shutdown()
            for cli in clients:
                cli.close()

    def warm_up(self) -> None:
        """A sweep, where the cell asks for one; then the window's own mix
        in short passes, until one leaves the read lane's compile_behind
        unmoved: every read kernel 16 clients can ask for is then compiled
        (or loaded from the cache)."""
        wl = self.ctx.workload
        self.sweep()
        self.warm = []
        for n in range(1, wl["warm_up"]["max_passes"] + 1):
            before = self.health()["read_lane"]["compile_behind"]
            self.warm.append(self.offer(f"warm{n}",
                                        wl["warm_up"]["pass_seconds"],
                                        writer_base=1000 * n))
            behind = (self.wait_compiles(f"warm-up pass {n}")
                      ["read_lane"]["compile_behind"] - before)
            if behind == 0 and n >= wl["warm_up"]["min_passes"]:
                return
        check(False, "no warm-up pass ran with every read kernel compiled")

    def marker(self, name: str) -> str:
        return os.path.join(self.control, name)

    def trace_schedule(self, start: float, seconds: float) -> None:
        """Marker files for the server's profiler switch: a few seconds
        inside the window, never the whole of it."""
        t = self.ctx.workload["trace"]
        a = start + seconds * t["start_share"]
        b = min(a + t["seconds"], start + seconds * t["latest_stop_share"])

        def run():
            time.sleep(max(0.0, a - time.monotonic()))
            markers.put(self.marker("trace.start"))
            markers.wait(self.marker("trace.started"))
            t0 = time.monotonic()
            time.sleep(max(0.0, b - time.monotonic()))
            markers.put(self.marker("trace.stop"))
            self.traced_s = time.monotonic() - t0

        self.traced_s = None
        self.trace_thread = threading.Thread(target=run, daemon=True)
        self.trace_thread.start()

    def trace_result(self) -> dict:
        self.trace_thread.join()
        markers.put(self.marker("trace.reduce"), repr(self.traced_s))
        t0 = time.monotonic()
        while not os.path.exists(self.marker("trace.json")):
            check(self.server.alive(), "server died reducing the trace",
                  self.server.log_tail())
            check(time.monotonic() - t0 < 120, "trace not reduced after 120 s")
            time.sleep(0.1)
        out = json.loads(markers.wait(self.marker("trace.json")))
        check("error" not in out, "trace reduction failed", out)
        self.ctx.say(f"trace of {self.traced_s:.1f}s reduced: tracered.load "
                     f"{out['load_s']:.1f}s, tracered.reduce "
                     f"{out['reduce_s']:.1f}s, "
                     f"{time.monotonic() - t0:.1f}s after the window",
                     programs={k: v["count"]
                               for k, v in out["programs"].items()})
        return out

    # ---- the comparison

    def read_back(self, phases: list) -> dict:
        """Every record an update touched, and a seed-drawn sample of the
        others, read once more: -> counts of what does not stand."""
        acked, attempted = merge_acks(phases)
        ids, untouched = records_to_read_back(
            self.seed, self.records, attempted,
            self.ctx.workload["untouched_sample"])
        items = [datagen.record_key(self.seed, i, self.table["sortkeys"])
                 for i in ids]
        cli = self.client()
        try:
            got = []
            for a in range(0, len(items), 500):
                got.extend(cli.batch_get(items[a:a + 500]))
        finally:
            cli.close()
        return judge_final(self.seed, self.table["value_bytes"], ids, got,
                           acked, attempted, untouched)

    def audit(self) -> dict:
        """Shell `trigger_audit <table>`, as a user types it."""
        t0 = time.monotonic()
        out = self.shell_line(f"trigger_audit {self.name}")
        try:
            report, _ = json.JSONDecoder().raw_decode(out)
        except ValueError:
            check(False, "trigger_audit printed no report", out[-2000:])
        differing = len(report["mismatches"]) + len(report["inconclusive"])
        reps = self.table["replicas"]
        for by_node in report["digests"].values():
            if not (len(by_node) == reps and len(
                    {(d["decree"], d["digest"]) for d in by_node.values()}) == 1):
                differing += 1
        differing += abs(len(report["digests"]) - self.table["partitions"])
        records = sum(p["records"] for p in report["primaries"].values())
        expected = len({datagen.record_key(self.seed, i, self.table["sortkeys"])
                        for i in range(self.records)})
        self.ctx.say(f"trigger_audit {self.name}: {records:,} records on "
                     f"{reps} replicas in {time.monotonic() - t0:.0f}s")
        return {"replicas_differing": differing,
                "audit_record_gap": abs(records - expected)}

    def stop(self) -> None:
        for child in self.children:
            child.stop(grace_s=2)
        if self.server is not None:
            self.server.stop()
        self.shell.pool.close()


def merge_acks(phases: list):
    """-> (acked, attempted): record -> {writer: its last sequence}, over
    every phase that wrote (the warm-up passes and the window)."""
    acked, attempted = {}, {}
    for phase in phases:
        for res in phase["results"]:
            for w in res["workers"]:
                for dst, src in ((acked, w["acked"]),
                                 (attempted, w["attempted"])):
                    for i, seq in src.items():
                        dst.setdefault(int(i), {})[w["writer"]] = seq
    return acked, attempted


def records_to_read_back(seed: int, records: int, attempted: dict,
                         sample: int):
    """-> (every record an update was sent to + a seed-drawn sample of the
    others, that sample as a set)."""
    rng = random.Random(seed)
    untouched = set()
    want = min(sample, records - len(attempted))
    while len(untouched) < want:
        i = rng.randrange(records)
        if i not in attempted:
            untouched.add(i)
    return sorted(attempted) + sorted(untouched), untouched


def judge_final(seed: int, size: int, ids: list, got: list, acked: dict,
                attempted: dict, untouched: set) -> dict:
    """What stands in the table after the window against what was
    acknowledged: a record nobody wrote to must be as loaded; a record
    that was written to must hold the LAST write of one of its writers (a
    last write that was never acknowledged may or may not stand), and the
    load's value only where no update was acknowledged."""
    lost = changed = 0
    for i, value in zip(ids, got):
        who = datagen.check_value(seed, i, value, size)
        if i in untouched:
            changed += who != (LOAD_WRITER, 0)
        elif who is None:
            lost += 1
        elif who[0] == LOAD_WRITER:
            lost += bool(acked.get(i))
        elif attempted[i].get(who[0]) != who[1]:
            lost += 1
    return {"updates_lost": lost, "untouched_changed": changed,
            "records_read_back": len(ids)}


def pooled(window: dict) -> dict:
    """All clients' operations of one window, pooled."""
    out = {"lat": {"read": [], "update": []}, "at": {"read": [], "update": []},
           "done": {"read": 0, "update": 0},
           "failed": {"read": 0, "update": 0}, "wrong": 0, "errors": [],
           "cpu_s": 0.0, "late_s": 0.0}
    for res in window["results"]:
        out["cpu_s"] += res["cpu_s"]
        out["late_s"] = max(out["late_s"], res["late_s"])
        for w in res["workers"]:
            out["wrong"] += w["wrong"]
            out["errors"] += w["errors"]
            for kind in ("read", "update"):
                out["lat"][kind] += w["lat"][kind]
                out["at"][kind] += w["at"][kind]
                out["done"][kind] += w["done"][kind]
                out["failed"][kind] += w["failed"][kind]
    return out


def tails_by_part(ops: dict, seconds: float, parts: int = 3) -> str:
    """p99 of each kind over each third of the window, by issue time: to
    see whether a tail is spread evenly or sits in one stall."""
    out = []
    for kind in ("read", "update"):
        cuts = [[] for _ in range(parts)]
        for at, lat in zip(ops["at"][kind], ops["lat"][kind]):
            cuts[min(parts - 1, int(parts * at / seconds))].append(lat)
        out.append(f"{kind} p99 by third " + " ".join(
            f"{reference.percentile(c, 99):.1f}" if c else "-" for c in cuts)
            + f" max {max(ops['lat'][kind] or [0]):.0f} ms")
    return "; ".join(out)


def run(ctx) -> dict:
    from pegasus_tpu import native

    native.available()          # build the native libraries once, before
    native.fastcodec()          # six processes race to
    wl = ctx.workload
    work = tempfile.mkdtemp(prefix="bench_serve_")
    dep = Deployment(ctx, work)
    try:
        ident = dep.boot()
        dep.create_table()
        dep.load()
        dep.manual_compact()
        dep.wait_compiles("after manual_compact")
        dep.warm_up()

        names = sorted(wl["counters"])
        before_c = dep.counters(*names)
        before = dep.still_state()
        def on_start(start):
            dep.sample_rates(wl["rate_counters"], start, ctx.seconds)
            if ctx.trace:
                dep.trace_schedule(start, ctx.seconds)

        window = dep.offer("window", ctx.seconds, writer_base=1,
                           on_start=on_start)
        after = dep.still_state()
        after_c = dep.counters(*names)
        dep.rate_thread.join()
        rates = {n: sum(v) / len(v) for n, v in dep.rate_samples.items() if v}
        memory = after["health"]["device_memory"] or {}
        ops = pooled(window)
        ctx.say(f"window: {ops['done']} in {ctx.seconds:g}s", failed=ops["failed"],
                wrong=ops["wrong"], errors=ops["errors"][:3],
                clients_late_s=round(ops["late_s"], 4))

        trace = None
        if ctx.trace:
            trace = dep.trace_result()
            ident = dict(ident, busy_s=trace["busy_s"] or 0.0,
                         window_s=trace["window_s"])
        t = time.monotonic()
        back = dep.read_back(dep.warm + [window])
        ctx.say(f"read back {back['records_read_back']:,} records in "
                f"{time.monotonic() - t:.1f}s")
        audit = dep.audit()
    finally:
        dep.stop()
        shutil.rmtree(work, ignore_errors=True)

    done = ops["done"]["read"] + ops["done"]["update"]
    failed = ops["failed"]["read"] + ops["failed"]["update"]
    end_to_end = {"setup_s": window["start"] - ctx.t0,
                  "ycsb_ops": done / ctx.seconds}
    tails = {f"{kind}_p{q:g}": reference.percentile(ops["lat"][kind], q)
             for kind in ("read", "update") if ops["lat"][kind]
             for q in (50, 90, 95, 99, 99.9)}
    end_to_end.update(tails)
    return {
        "attempted": sum(len(v) for v in ops["lat"].values()),
        "failed": failed, "end_to_end": end_to_end,
        "observed": {
            "ops": ops["done"], "window_s": ctx.seconds, "trace": trace,
            "client_tails": tails,
            "counters": {"before": before_c, "after": after_c},
            "rates": rates,
            "clients": {"cpu_s": ops["cpu_s"], "window_s": ctx.seconds,
                        "processes": wl["client_processes"]}},
        "breakdown": trace and {"device_ops": trace["device_ops"],
                                "idle_gaps": trace["idle_gaps"]},
        "device": dict(ident, memory_peak_bytes=int(
            memory.get("peak_bytes_in_use") or 0)),
        "compared": [
            ("reads_wrong", ops["wrong"] + dep.sweep_wrong, 0),
            ("updates_lost", back["updates_lost"], 0),
            ("untouched_changed", back["untouched_changed"], 0),
            ("replicas_differing", audit["replicas_differing"], 0),
            ("audit_record_gap", audit["audit_record_gap"], 0),
            ("guard_totals_moved", after["guard"] - before["guard"], 0),
            ("compiles_in_window", after["compiles"] - before["compiles"], 0),
        ],
        "notes": [tails_by_part(ops, ctx.seconds),
                  f"ms {json.dumps({k: round(v, 3) for k, v in tails.items()})}; "
                  f"counters {json.dumps({k: after_c.get(k, 0) - before_c.get(k, 0) for k in after_c})}; "
                  f"rates {json.dumps(rates)}; "
                  f"compile {after['health']['compile']}"],
    }
