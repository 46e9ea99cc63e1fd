#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that pegasus-tpu still starts, serves
and compacts on the chip.

Drives the main path once through the entry points a user would call, at a
data size its users would call real, and checks every answer against a
plain reference that shares no code with the engine. Phases, each a child
process that owns the chip alone and has exited before the next starts
(this parent never imports jax — a parent that has touched jax holds the
chip):

  serve    `python -m pegasus_tpu.server --config <ini>` booted from an ini
           derived from onebox.ini (3 metas, 3 replica nodes, collector,
           one process, compaction_backend = tpu). This process is a
           client over sockets (pegasus_tpu.client / pegasus_tpu.shell): it
           reads the server's device identity (`device-health`) and stops
           unless it is a TPU; creates table `smoke` (4 partitions x 3
           replicas); loads 1,000,000 records = 10,000 hashkeys x 100
           sortkeys, 16 B hashkey + 8 B sortkey, 1,000 B values through
           set/multi_set on the client's default 10 s timeout (a write
           never waits for the compiler: a kernel that is still compiling
           is served by the host lane and counted); shell `use smoke` +
           `manual_compact`, which waits for its kernels and must run on
           the device; then batch_get / multi_get ranges / sortkey_count /
           scanner, each compared byte for byte with the reference, passes
           repeated until one ran with every read kernel compiled; then
           shell `trigger_audit smoke` (every acknowledged write is on 3
           replicas, digest-identical at identical decrees). Passes only if
           the server's own counters say the DEVICE served: device lookups
           and device range reads > 0, no forward range read of the clean
           pass fell to the host, every live SST HBM-resident, both lane
           guards and every quiet-bypass counter at zero, no compile
           refused or waited out, and the compile cache gained entries
           while the server ran.
  compact  bench.py's default fill (10,000,000 records, 4 overlapping runs,
           100 B values, 10 % expired TTL, 5 % tombstones) through the
           functions bench.py's device lane uses; sha256 of keys and values
           equal to CpuBackend's; the Pallas merge compiled (not
           interpreted) on the same inputs, byte-compared. Before it the
           same child runs twice at a smaller size on one seed: the second
           start must add no compile-cache entry (the cache's path is
           stable across processes).
  mesh     only with >= 4 devices (else printed as skipped):
           __graft_entry__._dryrun_impl(4) on the real devices.

Exit code 0 and a last stdout line
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
only if every check of every phase held. Without --cpu-rehearsal a run
that finds no TPU exits non-zero before loading anything; with it the same
program runs at a tiny size on XLA:CPU and the last line says
"chip": false.
"""

import argparse
import hashlib
import io
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# ---- the deployment (sources: upstream scripts/pegasus_bench_run.sh per
# SURVEY.md:475 — value_size=1000; YCSB core's 10 x 100 B; bench.py
# make_run's key shape; BASELINE.json config 2 for the compaction fill)
HASHKEYS = 10_000
SORTKEYS = 100
VALUE_BYTES = 1000
PARTITIONS = 4
REPLICAS = 3
COMPACT_RECORDS = 10_000_000
# the cache-stability pair runs at this size: the ISSUE's 1,000,000 costs a
# 130 s cold compile per start on a v5e and the contract's 1200 s limit has
# no room for it (listed under `reduced`)
CACHE_PAIR_RECORDS = 16_384
REDUCED = ["serve: 4 partitions instead of BASELINE.json's 32",
           "serve: 1,000,000 records",
           f"compact: cache-stability pair at {CACHE_PAIR_RECORDS:,} records "
           "instead of 1,000,000 (cold compile time vs the 1200 s limit)"]
# --cpu-rehearsal sizes (control flow and answers only, never a timing)
REHEARSAL = dict(hashkeys=60, compact_records=40_000, cache_pair_records=8_000)

GUARD_TOTALS = ("fallbacks", "retries", "deadline_abandons", "breaker_trips",
                "device_failures", "compile_wait_timeouts")


class SmokeFailure(Exception):
    """A check did not hold. Never caught to let the run end 0."""


def check(cond, what: str, detail=None) -> None:
    if not cond:
        raise SmokeFailure(what if detail is None else f"{what}: {detail}")


def say(phase: str, msg: str, **fields) -> None:
    tail = (" " + json.dumps(fields, sort_keys=True)) if fields else ""
    print(f"[{phase}] {msg}{tail}", flush=True)


def cache_dir() -> str:
    """Where every process of this run keeps jax's compile cache: the
    directory placed from outside, else <checkout>/.jax_cache."""
    from pegasus_tpu.base.utils import compile_cache_dir  # jax-free import

    return compile_cache_dir()


def cache_entries() -> int:
    try:
        return len(os.listdir(cache_dir()))
    except FileNotFoundError:
        return 0


def kernel_cache_entries() -> list:
    """The cache's entries for the package's own kernels (ops/kernel.py
    names every program `pegasus_<kernel>`). jax's small eager programs
    (slices, converts) are cached only when their compile happens to take
    over 0.3 s, so their count can differ between two identical runs."""
    try:
        return sorted(f for f in os.listdir(cache_dir())
                      if f.startswith("jit_pegasus_"))
    except FileNotFoundError:
        return []


# ------------------------------------------------------------ the reference
# Independent of the engine: a function (seed, i) -> (hashkey, sortkey,
# value) plus ordinary dict/sort logic.


def sortkey(seed: int, i: int) -> bytes:
    """8 B, spread over the whole byte range."""
    return hashlib.blake2b(b"%d:%d" % (seed, i), digest_size=8).digest()


def record(seed: int, i: int):
    """-> (16 B hashkey, 8 B sortkey, 1000 B value) of record i."""
    value = hashlib.shake_128(b"%d:%d" % (seed, i)).digest(VALUE_BYTES)
    return b"userhash%08d" % (i // SORTKEYS), sortkey(seed, i), value


def hashkey_rows(seed: int, h: int) -> dict:
    """{sortkey: value} the table must hold under hashkey h (a later i
    overwrites an earlier one on a sortkey collision, as the load does)."""
    rows = {}
    for i in range(h * SORTKEYS, (h + 1) * SORTKEYS):
        _, sk, v = record(seed, i)
        rows[sk] = v
    return rows


# ------------------------------------------------------------------ children


def run_child(phase: str, argv: list, env: dict, timeout_s: float) -> dict:
    """Run one chip-holding child to its end; -> its last JSON stdout line.
    Its output streams through so a failure is read where it happened."""
    t0 = time.monotonic()
    proc = subprocess.Popen([sys.executable, os.path.abspath(__file__)] + argv,
                            cwd=HERE, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    last = None
    timer = threading.Timer(timeout_s, proc.kill)
    timer.daemon = True
    timer.start()
    try:
        for line in proc.stdout:
            line = line.rstrip("\n")
            print(f"[{phase}]   | {line}", flush=True)
            if line.startswith("{"):
                last = line
        rc = proc.wait()
    finally:
        timer.cancel()
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    check(rc == 0, f"{phase} child exited {rc} after "
                   f"{time.monotonic() - t0:.0f}s")
    check(last is not None, f"{phase} child printed no result line")
    return json.loads(last)


def child_identity(ns) -> dict:
    """First thing every chip child does: open the device backend through
    the package's one gate (refuses a silent CPU), and stop before any
    work when the run needs a TPU and this is not one."""
    from pegasus_tpu.base.utils import open_device_backend

    device = open_device_backend()
    if ns.require_tpu:
        check(device["platform"] == "tpu", "not a TPU (use --cpu-rehearsal "
                                           "for a CPU run)", device)
    return device


def child_compact(ns) -> int:
    """The compaction path through the functions bench.py's lane uses."""
    t0 = time.perf_counter()
    device = child_identity(ns)
    import jax

    import bench
    from pegasus_tpu.base.utils import device_report
    from pegasus_tpu.engine.block import KVBlock
    from pegasus_tpu.ops import compact as C
    from pegasus_tpu.ops.kernel import compile_report
    from pegasus_tpu.runtime.lane_guard import LANE_GUARD

    os.environ["PEGASUS_BENCH_N"] = str(ns.records)  # bench._fill's source
    n_total, n_runs, value_size, _ = bench._bench_params()
    runs, fill_s = bench._fill(n_total, n_runs, value_size)
    opts, fargs = bench._compact_opts()
    packed = C.pack_runs(runs, opts, need_sbytes=True)
    concat = KVBlock.concat(runs)
    del runs
    t = time.perf_counter()
    cpu_out = concat.gather(C.CpuBackend().survivors(packed, *fargs))
    cpu_s = time.perf_counter() - t
    want = bench._out_digest(cpu_out)
    del cpu_out

    backend = C.TpuBackend()
    prep = backend.prepare(packed)

    def device_lane(label):
        t = time.perf_counter()
        out = LANE_GUARD.run(
            lambda: C.gather_device_survivors(
                concat, *backend.survivors_device(prep, *fargs)),
            None, op=f"smoke-{label}")
        cold_s = time.perf_counter() - t
        got = bench._out_digest(out)
        check(got == want, f"{label} lane digest != CpuBackend's",
              {"got": got, "want": want})
        return round(cold_s, 2)

    result = {"phase": "compact", "records": n_total, "runs": n_runs,
              "value_bytes": value_size, "output_records": want["n_out"],
              "fill_s": round(fill_s, 2), "cpu_backend_s": round(cpu_s, 2),
              "xla_first_call_s": device_lane("xla"),
              "byte_equal_to_cpu_backend": True}
    # the Pallas merge-path kernel, COMPILED by Mosaic on a TPU
    # (interpret mode only on the rehearsal's CPU platform)
    os.environ["PEGASUS_PALLAS"] = "1"
    bench._clear_pipeline_caches()
    try:
        result["pallas_first_call_s"] = device_lane("pallas")
    finally:
        os.environ.pop("PEGASUS_PALLAS", None)
        bench._clear_pipeline_caches()
    result["pallas"] = ("compiled, byte-equal"
                        if jax.default_backend() == "tpu"
                        else "interpreted, byte-equal")
    lane = LANE_GUARD.state()
    check(all(lane[k] == 0 for k in GUARD_TOTALS + ("compile_behind",)),
          "compaction lane guard not clean", lane)
    report = device_report()
    result.update(lane_guard={k: lane[k] for k in GUARD_TOTALS},
                  device_memory=report["device_memory"],
                  wall_s=round(time.perf_counter() - t0, 1),
                  cache_entries=cache_entries(),
                  kernel_cache_entries=kernel_cache_entries(),
                  compile=compile_report(), device=device, ok=True)
    print(json.dumps(result), flush=True)
    return 0


def child_mesh(ns) -> int:
    """Multi-chip: the sharded all_to_all compaction, the dp-batched
    compaction and engine manual_compact over the mesh, each byte-checked
    inside _dryrun_impl, which also asserts the output shards sit on
    distinct device ids."""
    t0 = time.perf_counter()
    device = child_identity(ns)
    if device["device_count"] < 4:
        print(json.dumps({"phase": "mesh", "skipped":
                          f"{device['device_count']} device",
                          "device": device, "ok": True}), flush=True)
        return 0
    import __graft_entry__

    __graft_entry__._dryrun_impl(4)
    from pegasus_tpu.runtime.lane_guard import LANE_GUARD

    lane = LANE_GUARD.state()
    check(all(lane[k] == 0 for k in GUARD_TOTALS + ("compile_behind",)),
          "compaction lane guard not clean", lane)
    print(json.dumps({"phase": "mesh", "devices": 4,
                      "lane_guard": {k: lane[k] for k in GUARD_TOTALS},
                      "wall_s": round(time.perf_counter() - t0, 1),
                      "cache_entries": cache_entries(),
                      "device": device, "ok": True}), flush=True)
    return 0


# --------------------------------------------------------------- serve phase


def free_ports(n: int) -> list:
    socks = [socket.socket() for _ in range(n)]
    try:
        for s in socks:
            s.bind(("127.0.0.1", 0))
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


def write_ini(work: str) -> list:
    """An ini derived from the repo's onebox.ini: same apps, this run's
    directories and ports, and the tpu compaction backend switched on.
    No serve_groups — group workers are separate processes and would each
    want the chip. -> the meta address list."""
    with open(os.path.join(HERE, "onebox.ini")) as f:
        ini = f.read()
    old_ports = sorted(set(re.findall(r"\b34[0-9]{3}\b", ini)))
    for old, new in zip(old_ports, free_ports(len(old_ports))):
        ini = ini.replace(old, str(new))
    ini = ini.replace("pegasus-data", os.path.join(work, "data"))
    ini, n = re.subn(r"(?m)^# (compaction_backend = tpu)\b.*$", r"\1", ini)
    check(n == 1, "onebox.ini no longer carries the commented "
                  "compaction_backend line")
    with open(os.path.join(work, "smoke.ini"), "w") as f:
        f.write(ini)
    metas = re.search(r"(?m)^meta_servers = (.*)$", ini).group(1)
    return [m.strip() for m in metas.split(",")]


class Server:
    """The one chip-holding process of the serve phase."""

    def __init__(self, work: str, env: dict):
        self.log_path = os.path.join(work, "server.log")
        self._log = open(self.log_path, "w")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "pegasus_tpu.server", "--config",
             os.path.join(work, "smoke.ini")],
            cwd=HERE, env=env, stdout=self._log, stderr=subprocess.STDOUT,
            stdin=subprocess.DEVNULL)

    def log_tail(self, n: int = 40) -> str:
        with open(self.log_path, errors="replace") as f:
            return "".join(f.readlines()[-n:])

    def alive(self) -> bool:
        return self.proc.poll() is None

    def stop(self) -> None:
        if self.alive():
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=20)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._log.close()


class ServePhase:
    def __init__(self, ns, env: dict, work: str):
        from pegasus_tpu.shell.main import Shell

        self.ns, self.env, self.work = ns, env, work
        self.seed = ns.seed
        self.hashkeys = ns.hashkeys
        self.metas = write_ini(work)
        self.shell_out = io.StringIO()
        self.shell = Shell(self.metas, out=self.shell_out)
        self.server = None
        self.nodes = []
        self.device = None

    # ---- plumbing over sockets

    def shell_line(self, line: str) -> str:
        """One shell command, exactly as a user would type it -> its output."""
        self.shell_out.seek(0)
        self.shell_out.truncate()
        self.shell.run_line(line)
        return self.shell_out.getvalue()

    def node_json(self, node: str, command: str, args=()):
        return json.loads(self.shell._node_command(node, command,
                                                   list(args)))

    def counters(self, *prefixes) -> dict:
        # all three nodes are one process and share one registry
        return self.node_json(self.nodes[0], "perf-counters-by-prefix",
                              prefixes)

    def client(self):
        """A client as a user gets it: the default 10 s timeout."""
        from pegasus_tpu.client import MetaResolver, PegasusClient

        return PegasusClient(MetaResolver(self.metas, "smoke"))

    def health(self) -> dict:
        return self.node_json(self.nodes[0], "device-health")

    def wait_compiles(self, why: str) -> dict:
        """Block until the server's compile pool is idle (kernels a
        guarded call found cold compile behind it) -> device-health."""
        t0 = time.monotonic()
        while True:
            health = self.health()
            if health["compile"]["inflight"] == 0:
                break
            check(self.server.alive(), "server died while compiling",
                  self.server.log_tail())
            check(time.monotonic() - t0 < 900,
                  "kernels still compiling after 900 s", health["compile"])
            time.sleep(1.0)
        say("serve", f"{why}: compile pool idle after "
                     f"{time.monotonic() - t0:.0f}s",
            compile=health["compile"],
            compile_behind={lane: health[lane]["compile_behind"]
                            for lane in ("lane", "read_lane")})
        return health

    # ---- steps

    def boot(self) -> None:
        from pegasus_tpu.rpc.transport import RpcError

        before = cache_entries()
        t0 = time.monotonic()
        self.server = Server(self.work, self.env)
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
        self.cache_before = before
        health = self.health()
        self.device = health["device"]
        check(self.device is not None,
              "server reports no device identity — is it a tpu-backend "
              "server?", health)
        say("serve", f"server up in {time.monotonic() - t0:.1f}s",
            device=self.device)
        if not self.ns.cpu_rehearsal:
            check(self.device["platform"] == "tpu",
                  "the server's kernels are not on a TPU (use "
                  "--cpu-rehearsal for a CPU run)", self.device)

    def create_table(self) -> None:
        from pegasus_tpu.meta import messages as mm
        from pegasus_tpu.meta.meta_server import RPC_CM_QUERY_CONFIG

        out = self.shell_line(f"create smoke -p {PARTITIONS} -r {REPLICAS}")
        m = re.search(r"create app smoke succeed, id=(\d+)", out)
        check(m is not None, "create failed", out)
        self.app_id = int(m.group(1))
        t0 = time.monotonic()
        while True:
            cfg = self.shell._meta_call(RPC_CM_QUERY_CONFIG,
                                        mm.QueryConfigRequest("smoke"),
                                        mm.QueryConfigResponse)
            if all(pc.primary and len(pc.secondaries) == REPLICAS - 1
                   for pc in cfg.partitions):
                break
            check(time.monotonic() - t0 < 60, "table not fully replicated "
                                              "after 60 s")
            time.sleep(0.2)
        say("serve", f"table smoke id={self.app_id}: {PARTITIONS} partitions "
                     f"x {REPLICAS} replicas")

    def load(self) -> None:
        """Every record through set/multi_set; a call that returns is an
        acknowledged write. One hashkey in 50 goes record by record
        through `set`, the rest as one `multi_set` per hashkey."""
        n_threads = 8
        errors, done = [], [0] * n_threads

        def worker(tid):
            # the default 10 s timeout: a write that triggers a flush or an
            # L0 compaction must not wait for a cold kernel's compile
            cli = self.client()
            try:
                for h in range(tid, self.hashkeys, n_threads):
                    rows = [record(self.seed, i) for i in
                            range(h * SORTKEYS, (h + 1) * SORTKEYS)]
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
            time.sleep(1.0)
        check(not errors, "load failed", errors)
        total = self.hashkeys * SORTKEYS
        check(sum(done) == total, "loaders stopped short", sum(done))
        say("serve", f"loaded {total:,} records "
                     f"({total * (VALUE_BYTES + 24) / 1e9:.2f} GB of user "
                     f"data) in {time.monotonic() - t0:.0f}s; every write "
                     f"acknowledged")

    def manual_compact(self) -> None:
        """Shell `use smoke` + `manual_compact`, as a user types them. The
        command returns once the meta has spread the env; every replica
        then compacts in the background, waiting for its merge kernel
        instead of taking the host lane — so the lane's compile_behind
        total must not move while they run."""
        before = self.health()
        say("serve", "after the load", compile=before["compile"],
            compile_behind={lane: before[lane]["compile_behind"]
                            for lane in ("lane", "read_lane")})
        t0 = time.monotonic()
        trigger_ms = int(time.time()) * 1000
        self.shell_line("use smoke")
        out = self.shell_line("manual_compact")
        check("manual compact triggered" in out and "ERROR" not in out,
              "shell manual_compact failed", out)
        say("serve", "shell: use smoke + manual_compact -> " + out.strip()
            + f" ({time.monotonic() - t0:.1f}s)")
        want = {f"{self.app_id}.{p}" for p in range(PARTITIONS)}
        while True:
            check(self.server.alive(), "server died during manual compact",
                  self.server.log_tail())
            check(time.monotonic() - t0 < 900,
                  "manual compact not finished on every replica after 900 s",
                  self.compact_states())
            states = self.compact_states()
            finished = 0
            for node, lines in states.items():
                for gpid, st in lines.items():
                    if gpid not in want:
                        continue
                    check("FAILED" not in st, f"manual compact failed on "
                                              f"{node} {gpid}", st)
                    m = re.search(r"idle; last finish at (\d+)", st)
                    if m and int(m.group(1)) >= trigger_ms:
                        finished += 1
            if finished == PARTITIONS * REPLICAS:
                break
            time.sleep(1.0)
        after = self.health()
        check(after["lane"]["compile_behind"]
              == before["lane"]["compile_behind"],
              "a manual compaction took the host lane instead of waiting "
              "for its kernel",
              [before["lane"]["compile_behind"],
               after["lane"]["compile_behind"]])
        say("serve", f"manual compact finished on all {finished} replicas "
                     f"in {time.monotonic() - t0:.0f}s, on the device")
        self.background = self.background_work()
        say("serve", "device work so far", **self.background)

    def background_work(self) -> dict:
        """From the server's own job trace and compile totals: how many
        compactions the L0 trigger fired (at this size the fourth memtable
        of a partition is the partial one manual_compact's flush writes,
        so they fire there, not under load) and what compilation cost."""
        jobs = self.node_json(self.nodes[0], "job-trace", ["4096"])
        triggers = {}
        for job in next(iter(jobs.values())):
            if job["kind"] != "compact":
                continue
            for hop in job["hops"]:
                if hop.get("name") == "engine.trigger":
                    kind = hop.get("trigger", "?")
                    triggers[kind] = triggers.get(kind, 0) + 1
        health = self.health()
        return {"compactions_by_trigger": triggers,
                "compile": health["compile"],
                "compile_behind": {lane: health[lane]["compile_behind"]
                                   for lane in ("lane", "read_lane")}}

    def compact_states(self) -> dict:
        out = {}
        for node in self.nodes:
            text = self.shell._node_command(node, "query-compact-state", [])
            out[node] = dict(line.split(": ", 1)
                             for line in text.splitlines() if ": " in line)
        return out

    def reads(self) -> None:
        """The read workload, every answer compared with the reference, in
        passes: a read whose kernel is still compiling is served by the
        host walk (and counted), so a pass is repeated — after the compile
        pool went idle — until one ran with every kernel ready. That pass
        must not have sent one forward range read to the host."""
        for n in range(1, 6):
            before = self.health()["read_lane"]["compile_behind"]
            host_before = self.counters("read.range.host_count").get(
                "read.range.host_count", 0)
            summary = self.read_pass()
            behind = (self.wait_compiles(f"read pass {n}")
                      ["read_lane"]["compile_behind"] - before)
            host = self.counters("read.range.host_count").get(
                "read.range.host_count", 0) - host_before
            if behind == 0:
                check(host == 0, "forward range reads issued after the "
                                 "compact fell to the host with every "
                                 "kernel compiled", host)
                say("serve", f"reads byte-equal to the reference in each of "
                             f"{n} passes ({n - 1} while read kernels "
                             f"compiled): {summary}")
                return
            say("serve", f"read pass {n}: byte-equal; {behind} guarded "
                         f"reads served by the host while their kernel "
                         f"compiled ({host} range queries)")
        check(False, "no read pass ran with every kernel compiled")

    def read_pass(self) -> str:
        import random

        rng = random.Random(self.seed)
        total = self.hashkeys * SORTKEYS
        cli = self.client()
        try:
            # -- batch_get: present and absent keys, in pipelined waves
            items, want = [], []
            for _ in range(min(3000, total)):
                hk, sk, v = record(self.seed, rng.randrange(total))
                items.append((hk, sk))
                want.append(v)
            for j in range(min(1000, total)):
                if j % 2:   # a hashkey the table never saw
                    items.append((b"userhash%08d" % (self.hashkeys + j),
                                  b"\x00" * 8))
                else:       # a real hashkey, a sortkey it never saw
                    items.append((b"userhash%08d" % rng.randrange(
                        self.hashkeys), b"absent%02d" % (j % 100)))
                want.append(None)
            order = list(range(len(items)))
            rng.shuffle(order)
            got = [None] * len(items)
            for a in range(0, len(order), 500):
                wave = order[a:a + 500]
                for i, v in zip(wave, cli.batch_get([items[i] for i in wave])):
                    got[i] = v
            bad = [i for i in range(len(items)) if got[i] != want[i]]
            check(not bad, "batch_get answers differ from the reference",
                  [items[i] for i in bad[:5]])
            # -- multi_get sortkey ranges, sortkey_count
            n_hk = min(200, self.hashkeys)
            ranges = counted = 0
            for h in rng.sample(range(self.hashkeys), n_hk):
                rows = hashkey_rows(self.seed, h)
                sks = sorted(rows)
                hk = b"userhash%08d" % h
                lo, hi = sks[len(sks) // 10], sks[(6 * len(sks)) // 10]
                _, kvs = cli.multi_get(hk, None, start_sortkey=lo,
                                       stop_sortkey=hi)
                check(kvs == {sk: rows[sk] for sk in sks if lo <= sk < hi},
                      "multi_get range differs from the reference", hk)
                complete, kvs = cli.multi_get(hk, None, max_kv_count=20,
                                              start_sortkey=lo)
                first = [sk for sk in sks if sk >= lo][:20]
                check(kvs == {sk: rows[sk] for sk in first},
                      "multi_get limited range differs from the reference",
                      hk)
                ranges += 2
                check(cli.sortkey_count(hk) == len(rows),
                      "sortkey_count differs from the reference", hk)
                counted += 1
            # -- the same range reads from 8 threads at once: the server
            # coalesces concurrent ranges on a partition into one batch,
            # and only a per-SST batch of >= 2 dispatches the range kernel
            # (a lone range read resolves on the host inside a "device"
            # query) — device_proof requires read.range.dispatch_count > 0
            concurrent = self.concurrent_ranges(rng)
            # -- a scanner over several hashkeys
            scanned = 0
            for h in rng.sample(range(self.hashkeys), min(20, self.hashkeys)):
                rows = hashkey_rows(self.seed, h)
                hk = b"userhash%08d" % h
                sc = cli.get_scanner(hk, batch_size=37)
                got_rows = [(k, s, v) for k, s, v in sc]
                sc.close()
                check(got_rows == [(hk, sk, rows[sk]) for sk in sorted(rows)],
                      "scanner rows differ from the reference", hk)
                scanned += len(got_rows)
        finally:
            cli.close()
        return (f"batch_get {len(items)} keys "
                f"({sum(w is None for w in want)} absent), {ranges} "
                f"multi_get ranges, {counted} sortkey_count, {concurrent} "
                f"concurrent ranges, scanner {scanned} rows")

    def concurrent_ranges(self, rng) -> int:
        n_threads, per_thread = 8, 40
        # neighbouring hashkeys: after the compact a partition's share of
        # them sits in ONE of its SSTs, so a coalesced batch of two is a
        # per-SST batch of two
        span = min(64, self.hashkeys)
        base = rng.randrange(self.hashkeys - span + 1)
        plans = [[base + rng.randrange(span) for _ in range(per_thread)]
                 for _ in range(n_threads)]
        errors = []

        def worker(plan):
            cli = self.client()
            try:
                for h in plan:
                    rows = hashkey_rows(self.seed, h)
                    sks = sorted(rows)
                    hk = b"userhash%08d" % h
                    lo, hi = sks[len(sks) // 4], sks[(3 * len(sks)) // 4]
                    _, kvs = cli.multi_get(hk, None, start_sortkey=lo,
                                           stop_sortkey=hi)
                    if kvs != {sk: rows[sk] for sk in sks if lo <= sk < hi}:
                        errors.append(hk)
            except Exception as e:  # noqa: BLE001 - reported, then fails the run
                errors.append(repr(e))
            finally:
                cli.close()

        threads = [threading.Thread(target=worker, args=(p,), daemon=True)
                   for p in plans]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        check(not errors, "concurrent multi_get ranges differ from the "
                          "reference", errors[:5])
        return n_threads * per_thread

    def audit(self) -> None:
        """Shell `trigger_audit smoke`, as a user types it (the command
        sizes its timeouts by the largest replica on disk)."""
        t0 = time.monotonic()
        out = self.shell_line("trigger_audit smoke")
        try:
            report, end = json.JSONDecoder().raw_decode(out)
        except ValueError:
            check(False, "trigger_audit printed no report", out[-2000:])
        verdict = out[end:].strip()
        check(not report["mismatches"], "audit: replica digests differ",
              report["mismatches"])
        check(not report["inconclusive"], "audit inconclusive",
              report["inconclusive"])
        check(verdict == f"audit OK: {PARTITIONS} partition(s), all replicas "
                         "identical at identical decrees",
              "trigger_audit verdict", verdict)
        for gpid, by_node in report["digests"].items():
            check(len(by_node) == REPLICAS
                  and len({(d["decree"], d["digest"])
                           for d in by_node.values()}) == 1,
                  f"partition {gpid}: not {REPLICAS} identical replicas",
                  by_node)
        records = sum(p["records"] for p in report["primaries"].values())
        check(records == self.expected_records(),
              "audit record count differs from the reference",
              [records, self.expected_records()])
        say("serve", f"shell: trigger_audit smoke -> {verdict} "
                     f"({REPLICAS} replicas each, {records:,} records, "
                     f"{time.monotonic() - t0:.0f}s)")

    def expected_records(self) -> int:
        return sum(len({sortkey(self.seed, i) for i in
                        range(h * SORTKEYS, (h + 1) * SORTKEYS)})
                   for h in range(self.hashkeys))

    def device_proof(self) -> dict:
        """What the server itself says about where the work ran."""
        c = self.counters("read.device.", "read.range.", "engine.hbm.")
        health = self.health()
        live = 0
        for node in self.nodes:
            disk = self.node_json(node, "replica-disk")
            live += sum(d["sst_files"] for d in disk.values())
        check(c.get("read.device.lookup_count", 0) > 0,
              "no point read was served by the device", c)
        check(c.get("read.range.device_count", 0) > 0,
              "no range read was served by the device", c)
        check(c.get("engine.hbm.resident_ssts") == live and live > 0,
              "HBM-resident SSTs != live SSTs",
              {"resident": c.get("engine.hbm.resident_ssts"), "live": live})
        for lane in ("lane", "read_lane"):
            check(all(health[lane][k] == 0 for k in GUARD_TOTALS),
                  f"lane guard `{lane}` not clean", health[lane])
        check(all(v == 0 for v in health["bypass"].values()),
              "a quiet device bypass fired", health["bypass"])
        check(c.get("read.range.dispatch_count", 0) > 0,
              "the range kernel never ran (no coalesced range batch)", c)
        compiles = health["compile"]
        check(compiles["failed"] == 0, "the compiler refused a kernel",
              compiles)
        # every kernel of the served path was built for this platform in
        # the server: flush sort, merge over resident runs, fence build,
        # point and range lookups
        missing = {"merge_packed", "merge_cached", "fence_build", "lookup",
                   "range"} - set(compiles["kernels"])
        check(not missing, "kernels the server never compiled", sorted(missing))
        # the server, not only benches and tests, uses the compile cache:
        # it keeps it where this run placed it, and a cold directory
        # gained entries while it ran
        gained = cache_entries() - self.cache_before
        check(os.path.realpath(health["compile_cache_dir"])
              == os.path.realpath(cache_dir()),
              "the server's compile cache is not where this run placed it",
              [health["compile_cache_dir"], cache_dir()])
        check(gained > 0 or self.cache_before > 0,
              "the server added nothing to a cold compile cache",
              [self.cache_before, gained])
        return {
            "read.device.lookup_count": c["read.device.lookup_count"],
            "read.device.keys": c.get("read.device.keys"),
            "read.device.hits": c.get("read.device.hits"),
            "read.range.device_count": c["read.range.device_count"],
            "read.range.dispatch_count": c["read.range.dispatch_count"],
            "read.range.host_count": c.get("read.range.host_count", 0),
            "engine.hbm.resident_ssts": live,
            "engine.hbm.resident_bytes": c.get("engine.hbm.resident_bytes"),
            "engine.hbm.budget_bytes": c.get("engine.hbm.budget_bytes"),
            "device_memory": health["device_memory"],
            "background": self.background,
            "lane_guards": {lane: {k: health[lane][k] for k in GUARD_TOTALS
                                   + ("compile_behind",)}
                            for lane in ("lane", "read_lane")},
            "bypass": health["bypass"],
            "compile": compiles,
            "compile_cache": {"dir": health["compile_cache_dir"],
                              "entries_gained": gained},
        }

    def run(self) -> dict:
        t0 = time.monotonic()
        try:
            self.boot()
            self.create_table()
            self.load()
            self.manual_compact()
            self.reads()
            self.audit()
            proof = self.device_proof()
        finally:
            if self.server is not None:
                self.server.stop()
            self.shell.pool.close()
        return dict(proof, phase="serve",
                    records=self.hashkeys * SORTKEYS,
                    value_bytes=VALUE_BYTES, partitions=PARTITIONS,
                    replicas=REPLICAS,
                    wall_s=round(time.monotonic() - t0, 1),
                    cache_entries=cache_entries(), device=self.device,
                    ok=True)


# -------------------------------------------------------------------- parent


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run the same program at a tiny size on XLA:CPU "
                         "(JAX_PLATFORMS=cpu for every child); proves "
                         "control flow and answers, never the chip")
    ap.add_argument("--phases", default="serve,compact,mesh",
                    help="comma list (the four-chip host runs `mesh` alone)")
    ap.add_argument("--child", choices=("compact", "mesh"),
                    help=argparse.SUPPRESS)
    ap.add_argument("--records", type=int, help=argparse.SUPPRESS)
    ap.add_argument("--require-tpu", action="store_true",
                    help=argparse.SUPPRESS)
    ns = ap.parse_args()
    if ns.child:
        return {"compact": child_compact, "mesh": child_mesh}[ns.child](ns)

    t_start = time.monotonic()
    phases = [p for p in ns.phases.split(",") if p]
    check(set(phases) <= {"serve", "compact", "mesh"}, "unknown phase",
          phases)
    env = dict(os.environ)
    sizes = dict(hashkeys=HASHKEYS, compact_records=COMPACT_RECORDS,
                 cache_pair_records=CACHE_PAIR_RECORDS)
    if ns.cpu_rehearsal:
        env["JAX_PLATFORMS"] = "cpu"
        sizes = REHEARSAL
    ns.hashkeys = sizes["hashkeys"]

    # a checkout's .so files prove nothing about its sources (fastcodec's
    # is a gitignored leftover, libhostops.so a committed artifact):
    # rebuild both, and fail if that fails — later runs would quietly
    # serve the pure-Python twins
    from tools import build_native

    built = build_native.ensure(force=True)
    check(all(s == "rebuilt" for s in built.values()),
          "native build failed", built)
    from pegasus_tpu import native

    check(native.available() and native.fastcodec() is not None,
          "native libraries built but did not load")
    say("build", "native libraries rebuilt from source", **built)
    say("plan", "phases " + ",".join(phases), seed=ns.seed,
        chip=not ns.cpu_rehearsal,
        reduced=REDUCED if not ns.cpu_rehearsal else ["cpu rehearsal: "
                                                      "tiny sizes"],
        compile_cache=cache_dir(), cache_entries=cache_entries())

    results = {}
    device = None
    if "serve" in phases:
        work = tempfile.mkdtemp(prefix="chip_smoke_")
        try:
            results["serve"] = ServePhase(ns, env, work).run()
        finally:
            shutil.rmtree(work, ignore_errors=True)
        device = results["serve"]["device"]
        say("serve", "PASS", result=results["serve"])
    child_flags = [] if ns.cpu_rehearsal else ["--require-tpu"]
    if "compact" in phases:
        pair = ["--child", "compact", "--records",
                str(sizes["cache_pair_records"])] + child_flags
        first = run_child("compact", pair, env, 600)
        second = run_child("compact", pair, env, 600)
        check(first["kernel_cache_entries"]
              and second["kernel_cache_entries"]
              == first["kernel_cache_entries"],
              "the second start of the same program compiled a kernel "
              "again: the cache path is not stable across processes",
              [first["kernel_cache_entries"],
               second["kernel_cache_entries"]])
        say("compact", f"cache-stability pair at "
                       f"{sizes['cache_pair_records']:,} records: first "
                       f"start left "
                       f"{len(first['kernel_cache_entries'])} kernel "
                       f"entries of {first['cache_entries']} "
                       f"({first['compile']['seconds']}s in "
                       f"{first['compile']['compiled']} kernels, "
                       f"{first['wall_s']}s wall), the second added no "
                       f"kernel entry ({second['cache_entries']} entries, "
                       f"{second['compile']['seconds']}s in "
                       f"{second['compile']['compiled']} kernels, "
                       f"{second['wall_s']}s wall)", device=first["device"])
        results["compact"] = run_child(
            "compact", ["--child", "compact", "--records",
                        str(sizes["compact_records"])] + child_flags,
            env, 900)
        device = device or results["compact"]["device"]
        say("compact", "PASS", result=results["compact"])
    if "mesh" in phases:
        results["mesh"] = run_child("mesh", ["--child", "mesh"] + child_flags,
                                    env, 900)
        device = device or results["mesh"]["device"]
        say("mesh", ("skipped: " + results["mesh"]["skipped"])
            if "skipped" in results["mesh"] else "PASS",
            result=results["mesh"])

    check(device is not None, "no phase ran")
    if not ns.cpu_rehearsal:
        check(device["platform"] == "tpu", "not a TPU", device)
    say("done", f"all phases passed in {time.monotonic() - t_start:.0f}s")
    final = {"ok": True,
             "device": {"platform": device["platform"],
                        "kind": device["device_kind"],
                        "count": device["device_count"]}}
    if ns.cpu_rehearsal:
        final["chip"] = False
    if phases != ["serve", "compact", "mesh"]:
        final["phases"] = phases
    print(json.dumps(final), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
