"""Pressure test: sustained target-QPS load — now with a chaos scenario
engine (ISSUE 11's production-sim harness).

The reference's src/test/pressure_test + kill_test tiers in one driver: a
load generator holding a TARGET qps against a cluster with a configurable
op mix (point gets, RANGE reads — bounded multi_gets plus a periodic
full-table unordered-scanner sweep, exercising the device-served range
path under faults — and writes), writing SELF-CHECKING rows (value
derived from key) so every read verifies itself, while (optionally) a
scripted fault schedule runs
node kills, group-worker kills, remote fail-point wedges, a mid-load
partition split, a balancer primary move, compaction-scheduler token
flips and a duplication leg to a second cluster — all under periodic
decree-anchored audit rounds.

Pass criterion (exit 0) — every failure is NAMED in the event journal:

  * zero lost acked writes (self-verifying reads, with re-read
    verification before anything counts as lost);
  * every transient error fell inside a DECLARED fault window
    (steady-state errors fail the run);
  * every audit round mismatch-free, with at least one conclusive
    (non-vacuous) round;
  * scenario runs: every fault healed within its recovery deadline, the
    cross-cluster digest compare (anchored at the duplicator's confirmed
    decree) matched, and the final cluster_doctor verdict is healthy.

Usage:
    python tools/pressure_test.py [--meta host:port] [--table t]
        [--qps 500] [--seconds 30] [--threads 4] [--read-pct 50]
        [--scenario none|smoke|full] [--audit-every 5] [--journal out.json]
(no --meta: boots its own onebox; --scenario requires the self-booted
onebox — the fault actors need the cluster handles)
"""

import argparse
import json
import os
import random
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def expected_value(key: bytes) -> bytes:
    import hashlib

    return hashlib.md5(key).hexdigest().encode()


class LatencyReservoir:
    """Bounded-memory latency sample (Vitter's Algorithm R) replacing the
    old unbounded per-op list: a long chaos run at 500+ QPS would hold
    millions of floats. Up to `cap` samples the reservoir IS the full
    population, so `percentile` reproduces the old sorted-list semantics
    exactly (index ``min(n-1, int(n*p))``); past `cap` each op keeps a
    uniform cap/count chance of being sampled. Thread-safe."""

    def __init__(self, cap: int = 8192, seed: int = 0):
        self.cap = max(1, cap)
        self._rng = random.Random(seed)
        self._lock = threading.Lock()
        self._sample = []
        self.count = 0
        self.total = 0.0

    def add(self, v: float) -> None:
        with self._lock:
            self.count += 1
            self.total += v
            if len(self._sample) < self.cap:
                self._sample.append(v)
            else:
                j = self._rng.randrange(self.count)
                if j < self.cap:
                    self._sample[j] = v

    def percentile(self, p: float) -> float:
        with self._lock:
            s = sorted(self._sample)
        if not s:
            return 0.0
        return round(s[min(len(s) - 1, int(len(s) * p))], 2)

    def avg(self) -> float:
        with self._lock:
            return round(self.total / self.count, 2) if self.count else 0.0


def _parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--meta", default="")
    ap.add_argument("--table", default="pressure")
    ap.add_argument("--qps", type=int, default=500)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--threads", type=int, default=4)
    ap.add_argument("--read-pct", type=int, default=50)
    ap.add_argument("--scan-pct", type=int, default=10,
                    help="share of ops that are RANGE reads — a bounded "
                         "multi_get over the hash key's sortkey range, "
                         "carved out of the write share — so the "
                         "device-served range path (ISSUE 19) runs under "
                         "node kills, splits and audits; also enables a "
                         "periodic full-table unordered-scanner sweep on "
                         "thread 0 (every row self-verifies, with re-read "
                         "verification before anything counts); 0 "
                         "disables both")
    ap.add_argument("--key-space", type=int, default=100_000)
    ap.add_argument("--tables", type=int, default=1,
                    help="number of tables to load (table, table2..tableN; "
                         "a self-booted onebox creates the extras): each "
                         "table gets a DISTINCT key prefix and a skewed "
                         "share of the op mix (table k weighted 1/(k+1)), "
                         "the multi-tenant shape the per-table ledgers "
                         "attribute (ISSUE 18)")
    ap.add_argument("--scenario", default="none",
                    choices=["none", "smoke", "full", "offload",
                             "corruption"],
                    help="scripted chaos schedule to run under the load "
                         "(pegasus_tpu.chaos): smoke = group-worker kill + "
                         "remote fail-point wedge; full = + node "
                         "kill/restart, mid-load split, balancer primary "
                         "move, scheduler token flips, duplication leg "
                         "with cross-cluster digest compare; offload = "
                         "compaction-offload wire wedge + mid-merge "
                         "service kill against a harness-wired offload "
                         "service with every partition placed onto it; "
                         "corruption = scrub.verify fail-point chaos + a "
                         "byte-flipped live SST that must detect → "
                         "quarantine → re-seed with zero wrong reads")
    ap.add_argument("--offload-kill-every", type=float, default=15.0,
                    help="--scenario offload: repeat the mid-merge service "
                         "kill on this period for the whole run (ROADMAP "
                         "offload follow-on (d), the longer soak) instead "
                         "of once; must exceed the kill's 4 s heal window; "
                         "0 = single kill")
    ap.add_argument("--audit-every", type=float, default=5.0,
                    help="seconds between decree-anchored audit rounds "
                         "under the load (0 disables; a final quiesced "
                         "round always runs when enabled)")
    ap.add_argument("--journal", default="",
                    help="write the full event-journal artifact (JSON) here")
    ap.add_argument("--reservoir", type=int, default=8192,
                    help="latency reservoir sample size")
    ap.add_argument("--inject-fault", default="", metavar="POINT=ACTION",
                    help="arm one UNDECLARED fail point on the first node "
                         "at load start (e.g. audit.digest=return() to "
                         "corrupt that node's audit digests) — the "
                         "self-falsification knob: the run must exit 1 "
                         "with the failure named in the journal, proving "
                         "the harness can actually catch what it claims "
                         "to check (requires --scenario)")
    ap.add_argument("--no-audit", action="store_true",
                    help="legacy alias for --audit-every 0")
    return ap.parse_args(argv)


def _build_harness(args, journal):
    """-> (box, dst_box, actors, scenario) for --scenario runs. The
    source onebox serves through partition-group executors (so the
    group-kill leg is a real process kill); the full scenario adds a
    second onebox cluster as the duplication target."""
    from pegasus_tpu.chaos import actors as act
    from pegasus_tpu.chaos import scenario as sc
    from pegasus_tpu.collector.cluster_doctor import ClusterCaller
    from pegasus_tpu.meta import messages as mm
    from pegasus_tpu.meta.meta_server import RPC_CM_ADD_DUPLICATION

    from tools._onebox import Onebox

    box = dst = None
    try:
        if args.scenario == "full":
            dst = Onebox(args.table, partitions=8, n_nodes=3, cluster_id=2)
        # corruption leg (ISSUE 17) serves through PLAIN stubs: the
        # disk-corrupt actor byte-flips a live SST through the node's
        # in-process handle, and group workers are separate processes
        groups = 0 if args.scenario == "corruption" else 2
        box = Onebox(args.table, partitions=8, n_nodes=3,
                     serve_groups=groups,
                     remote_clusters={"chaos-dst": [dst.meta_addr]} if dst
                     else None, cluster_id=1)
        if dst is not None:
            r = box.cluster.ddl(RPC_CM_ADD_DUPLICATION,
                                mm.AddDuplicationRequest(args.table,
                                                         "chaos-dst"),
                                mm.AddDuplicationResponse)
            if r.error:
                raise RuntimeError(f"add_dup failed: {r.error_text}")
            journal.record("dup.added", dupid=r.dupid, remote=dst.meta_addr)
    except BaseException:
        # run_pressure's finally never sees these handles (the assignment
        # from _build_harness did not happen) — stop them here or the
        # half-built clusters' threads + tmpdirs outlive the run
        for b in (box, dst):
            if b is not None:
                b.stop()
        raise
    caller = ClusterCaller([box.meta_addr])

    def alive_nodes():
        return act._alive_nodes(box.cluster, caller)

    # ONE pooled caller shared by every actor: recovery polls run every
    # 0.2 s, and per-poll connections would pile onto a recovering cluster
    actors = {
        sc.A_FAILPOINT: act.FailPointActor(caller, nodes_fn=alive_nodes),
        sc.A_GROUP_KILL: act.GroupWorkerKill(box.cluster, node_index=0),
        sc.A_NODE_KILL: act.NodeKillRestart(box.cluster, node_index=-1,
                                            caller=caller),
        sc.A_SPLIT: act.SplitActor(box.cluster, args.table, caller=caller),
        sc.A_BALANCE: act.BalanceActor(box.cluster, args.table,
                                       caller=caller),
        sc.A_SCHED: act.SchedFlipActor(caller, box.cluster, args.table),
    }
    if args.scenario == "corruption":
        actors[sc.A_DISK_CORRUPT] = act.DiskCorruptActor(
            box.cluster, node_index=0, caller=caller)
    if args.scenario == "offload":
        # rack-scale offload leg (ISSUE 14): one cpu-backend compaction
        # service for the whole onebox rack, every partition placed onto
        # it for the run's duration — the scenario then wedges the wire
        # and hard-kills the service mid-load, and the nodes must ride
        # the offload lane's local-cpu fallback without losing a write
        ctl = _OffloadServiceCtl()
        box.offload_ctl = ctl
        _deliver_offload_placements(caller, box, ctl.address,
                                    ttl_s=args.seconds + 120)
        actors[sc.A_OFFLOAD] = act.OffloadServiceKill(ctl, caller=caller)
    box.chaos_caller = caller   # closed with the box in the run's finally
    box.alive_nodes = alive_nodes   # --inject-fault victim selection
    if args.scenario == "offload":
        # the soak shape (ISSUE 16 satellite): the service kill repeats
        # on --offload-kill-every for the run's whole duration, so a
        # longer --seconds means MORE kill/heal/re-adopt cycles — not
        # one kill followed by minutes of quiet
        scenario = sc.offload_scenario(
            kill_every_s=args.offload_kill_every or None)
    else:
        scenario = sc.SCENARIOS[args.scenario]()
    return box, dst, actors, scenario


class _OffloadServiceCtl:
    """stop()/restart()-able in-process compaction-offload service (the
    OffloadServiceKill actor's handle): restart rebinds the SAME address
    so placement leases delivered before the kill stay valid."""

    def __init__(self):
        import tempfile

        from pegasus_tpu.replication.compact_offload import \
            CompactOffloadService

        self.root = tempfile.mkdtemp(prefix="pegasus_offload_chaos_")
        self.svc = CompactOffloadService(self.root, backend="cpu").start()
        self.address = self.svc.address

    def stop(self):
        self.svc.stop()

    def restart(self):
        from pegasus_tpu.replication.compact_offload import \
            CompactOffloadService

        host, _, port = self.address.rpartition(":")
        self.svc = CompactOffloadService(self.root, host=host,
                                         port=int(port),
                                         backend="cpu").start()

    def close(self):
        import shutil

        try:
            self.svc.stop()
        except Exception:  # noqa: BLE001 - teardown best-effort
            pass
        shutil.rmtree(self.root, ignore_errors=True)


def _deliver_offload_placements(caller, box, svc_addr: str,
                                ttl_s: float) -> None:
    """Hand every alive node a (normal, svc_addr) token for each hosted
    partition — the compact-sched-policy surface the cluster scheduler
    itself uses, with a lease long enough to outlive the run."""
    import json as _json

    from pegasus_tpu.chaos.actors import _cluster_state

    state = _cluster_state(box.cluster, caller) or {}
    decisions = {}
    for app in state.get("apps", {}).values():
        for pc in app.get("partitions", []):
            decisions[f"{app['app_id']}.{pc['pidx']}"] = {
                "policy": "normal", "reasons": ["chaos.offload"],
                "where": svc_addr}
    body = _json.dumps({"ttl_s": ttl_s, "decisions": decisions})
    for node in sorted(a for a, n in state.get("nodes", {}).items()
                       if n.get("alive")):
        try:
            caller.remote_command(node, "compact-sched-policy", [body])
        except Exception:  # noqa: BLE001 - a node that missed the
            continue       # placement simply compacts locally


def _table_list(args):
    """--tables N -> [table, table2, .., tableN] (N=1: just --table)."""
    n = max(1, args.tables)
    return [args.table] + [f"{args.table}{i}" for i in range(2, n + 1)]


def _worker(tid, args, meta_addr, stop_at, stats, stats_lock, lat,
            written, written_lock, windows, journal, table_ops=None):
    from pegasus_tpu.client import MetaResolver, PegasusClient, PegasusError

    rng = random.Random(tid)
    tables = _table_list(args)
    clis = [PegasusClient(MetaResolver([meta_addr], t), timeout=10)
            for t in tables]
    cli = clis[0]
    # skewed tenant mix: table k draws weight 1/(k+1), so the first table
    # dominates and the per-table ledgers have an asymmetry to attribute
    weights = [1.0 / (k + 1) for k in range(len(tables))]
    wsum = sum(weights)
    local_tables = {t: 0 for t in tables}
    per_thread_qps = args.qps / args.threads
    interval = 1.0 / per_thread_qps if per_thread_qps > 0 else 0
    next_fire = time.time()
    local = {"reads": 0, "writes": 0, "scans": 0, "sweeps": 0,
             "sweep_rows": 0, "errors_in_window": 0,
             "errors_steady": 0, "recovered_reads": 0,
             "verify_failures": 0, "not_found": 0}

    def classify_error(t_err, what, detail=""):
        """In-fault-window errors are DECLARED (bounded, allowed);
        steady-state errors fail the run (ISSUE 11 satellite)."""
        if windows is not None and windows.in_window(t_err):
            local["errors_in_window"] += 1
        else:
            local["errors_steady"] += 1
            journal.record("error.steady", op=what, thread=tid,
                           detail=detail)

    def timed(fn, *fargs):
        """One client attempt with its latency sampled. Only FIRST
        attempts go through here — reread()'s retry sleeps are harness
        policy, not server latency, and would inflate p99 by orders of
        magnitude under chaos. An errored attempt still records (its
        duration is real server-observed time)."""
        t0 = time.perf_counter()
        try:
            return fn(*fargs)
        finally:
            lat.add((time.perf_counter() - t0) * 1000)

    def reread(hk, attempts=5, delay=0.2, op=None):
        """-> (ok, value): retry a read past transient routing blips
        before concluding anything about the key. `op` replays a
        NON-point read (the scan leg re-verifies through the same range
        path it failed on); default is the point get."""
        for _ in range(attempts):
            time.sleep(delay)
            try:
                return True, cli.get(hk, b"s") if op is None else op()
            except PegasusError:
                continue
        return False, None

    def verify_row(hk, i, v, was_written):
        """Self-check one read result (shared by the point-get and the
        range-scan legs — byte-identity means the SAME row must come
        back either way)."""
        if v is None:
            if was_written:
                # an acked write must be readable; re-read before
                # declaring it lost (routing may still be settling)
                ok, v2 = reread(hk, attempts=3, delay=0.3)
                if v2 == expected_value(hk):
                    local["recovered_reads"] += 1
                else:
                    local["verify_failures"] += 1
                    journal.record("verify.lost", key=i, thread=tid)
            else:
                local["not_found"] += 1
        elif v != expected_value(hk):
            local["verify_failures"] += 1
            journal.record("verify.corrupt", key=i, thread=tid)

    def range_read(hk):
        """The scan-leg op: a bounded multi_get RANGE ((start, stop]
        resolved through scan_range_batch server-side) that must surface
        the one self-verifying b\"s\" row. Untimed — the first attempt
        wraps it in timed(), rereads replay it raw."""
        _, kvs = cli.multi_get(hk, None, 0, 0, start_sortkey=b"",
                               stop_sortkey=b"t", stop_inclusive=True)
        return kvs.get(b"s")

    def sweep():
        """Full-table unordered-scanner sweep over the primary table:
        every surviving row must self-verify while the chaos schedule
        runs. Values are key-derived and never overwritten, so a
        mismatch is corruption, not a race — but it still gets one
        point-get re-read before it counts (a scanner batch fetched
        mid-failover is retried internally, this guards the residue)."""
        rows = 0
        scanners = []
        try:
            scanners = clis[0].get_unordered_scanners(batch_size=500)
            for sc in scanners:
                for h, s, val in sc:
                    rows += 1
                    if s != b"s" or val == expected_value(h):
                        continue
                    ok, v2 = reread(h, attempts=3, delay=0.3)
                    if v2 != expected_value(h):
                        local["verify_failures"] += 1
                        journal.record("verify.sweep_corrupt",
                                       key=h.decode("latin-1"), thread=tid)
        except PegasusError as e:
            classify_error(journal.now(), "sweep", repr(e))
            return
        finally:
            for sc in scanners:
                sc.close()
        local["sweeps"] += 1
        local["sweep_rows"] += rows

    next_sweep = time.time() + 10.0 if (tid == 0 and args.scan_pct) \
        else float("inf")

    while time.time() < stop_at:
        now = time.time()
        if now >= next_sweep:
            sweep()
            next_sweep = time.time() + 10.0
            next_fire = time.time()  # don't burst-repay the sweep time
        if interval and now < next_fire:
            time.sleep(min(interval, next_fire - now))
            continue
        next_fire += interval
        i = rng.randrange(args.key_space)
        if len(tables) == 1:
            hk = b"pres%07d" % i
        else:
            # distinct per-table key prefix: self-verification (value
            # derived from the FULL key) stays sound across tenants
            r = rng.random() * wsum
            t_idx = 0
            while t_idx < len(tables) - 1 and r > weights[t_idx]:
                r -= weights[t_idx]
                t_idx += 1
            cli = clis[t_idx]
            hk = b"%s:pres%07d" % (tables[t_idx].encode(), i)
            local_tables[tables[t_idx]] += 1
        roll = rng.randrange(100)
        if roll < args.read_pct:
            # snapshot BEFORE the read: a write completing between
            # the get and a later check would fake a lost write
            with written_lock:
                was_written = hk in written
            try:
                v = timed(cli.get, hk, b"s")
            except PegasusError as e:
                # re-read-verify before counting anything: a failover
                # blip is not a lost write. Only a read that keeps
                # erroring counts as an error at the ORIGINAL instant.
                t_err = journal.now()
                ok, v = reread(hk)
                if not ok:
                    classify_error(t_err, "get", repr(e))
                    continue
                local["recovered_reads"] += 1
            local["reads"] += 1
            verify_row(hk, i, v, was_written)
        elif roll < args.read_pct + args.scan_pct:
            with written_lock:
                was_written = hk in written
            try:
                v = timed(range_read, hk)
            except PegasusError as e:
                t_err = journal.now()
                ok, v = reread(hk, op=lambda: range_read(hk))
                if not ok:
                    classify_error(t_err, "multi_get_range", repr(e))
                    continue
                local["recovered_reads"] += 1
            local["scans"] += 1
            verify_row(hk, i, v, was_written)
        else:
            try:
                timed(cli.set, hk, b"s", expected_value(hk))
            except PegasusError as e:
                classify_error(journal.now(), "set", repr(e))
                continue
            with written_lock:
                written.add(hk)
            local["writes"] += 1
    for c in clis:
        c.close()
    with stats_lock:
        for k, v in local.items():
            stats[k] += v
        if table_ops is not None:
            for t, v in local_tables.items():
                table_ops[t] = table_ops.get(t, 0) + v


def run_pressure(argv=None) -> int:
    """The whole run; returns the process exit code (importable for
    tests — main() wraps it)."""
    args = _parse_args(argv)
    if args.no_audit:
        args.audit_every = 0.0
    if args.scenario != "none" and args.meta:
        print("pressure_test: --scenario needs the self-booted onebox "
              "(the fault actors hold cluster handles); drop --meta",
              file=sys.stderr)
        return 2
    if args.inject_fault and args.scenario == "none":
        print("pressure_test: --inject-fault requires --scenario "
              "(it arms over the harness's remote-command caller)",
              file=sys.stderr)
        return 2

    from pegasus_tpu.chaos.journal import EventJournal, FaultWindows
    from pegasus_tpu.chaos.scenario import ScenarioRunner
    from pegasus_tpu.collector.cluster_doctor import (
        AuditRounds, run_cluster_doctor, run_cross_cluster_audit)

    journal = EventJournal()
    windows = FaultWindows(journal)
    box = dst = runner = None
    meta_addr = args.meta
    try:
        if args.scenario != "none":
            box, dst, actors, scenario = _build_harness(args, journal)
            meta_addr = box.meta_addr
            runner = ScenarioRunner(scenario, actors, journal,
                                    windows=windows)
        elif not args.meta:
            from tools._onebox import Onebox

            box = Onebox(args.table, partitions=8)
            meta_addr = box.meta_addr
        tables = _table_list(args)
        if box is not None:
            for extra in tables[1:]:
                box.cluster.create(extra, partitions=8).close()

        stats = {"reads": 0, "writes": 0, "scans": 0, "sweeps": 0,
                 "sweep_rows": 0, "errors_in_window": 0,
                 "errors_steady": 0, "recovered_reads": 0,
                 "verify_failures": 0, "not_found": 0}
        stats_lock = threading.Lock()
        lat = LatencyReservoir(cap=args.reservoir)
        written = set()
        written_lock = threading.Lock()
        table_ops = {}  # per-table op counts (guarded by stats_lock)

        # flight recorder (ISSUE 12): the FIRST named failure of the run
        # captures an incident artifact AT failure time (the nodes' event
        # rings + metric history still hold the lead-up), and the
        # artifact rides the journal. One capture per run: later
        # failures of the same run share the same recorded past.
        incident_box = [None]
        incident_lock = threading.Lock()

        def _capture_on_fail(ev):
            # serialized: concurrent first failures (a node kill breaking
            # several reads at once) must still yield ONE capture; a
            # failed capture releases the latch so a later failure retries
            with incident_lock:
                if incident_box[0] is not None:
                    return
                from pegasus_tpu.collector.flight_recorder import RECORDER

                inc = RECORDER.capture(
                    [meta_addr], reason=f"chaos failure {ev['failure']}",
                    trigger="chaos")
                incident_box[0] = {"id": inc["id"], "path": inc["path"],
                                   "first_cause": inc["first_cause"]}
            journal.record("incident.captured", **incident_box[0])

        journal.on_fail = _capture_on_fail

        audits = None
        if args.audit_every > 0:
            audits = AuditRounds([meta_addr], apps=tables,
                                 every_s=args.audit_every,
                                 wait_s=min(5.0, args.audit_every),
                                 journal=journal).start()
        elif args.scenario in ("offload", "corruption"):
            # these legs ALWAYS conclude with one quiesced audit round,
            # even under --audit-every 0: a run that survived the faults
            # but never proved the digests match proved nothing — for
            # the corruption leg the conclusive mismatch-free round IS
            # the zero-wrong-reads claim. The huge cadence parks the
            # loop on its stop event; stop(final_round=True) below runs
            # the single post-quiesce round.
            audits = AuditRounds([meta_addr], apps=tables,
                                 every_s=3600.0, wait_s=5.0,
                                 journal=journal).start()
        if args.inject_fault:
            # UNDECLARED corruption on the first node — no fault window,
            # no heal: the audits/classifier must catch it and fail the
            # run, or the harness's green runs mean nothing
            point, _, action = args.inject_fault.partition("=")
            victim = box.alive_nodes()[0]
            reply = box.chaos_caller.remote_command(victim, "set-fail-point",
                                                    [point, action])
            if not (reply or "").lstrip().startswith("{"):
                # a rejected arming (bad name/action) would otherwise let
                # the run pass its self-falsification check with NO fault
                # planted — the journal would lie
                print(f"pressure_test: --inject-fault rejected: {reply}",
                      file=sys.stderr)
                return 2
            journal.record("fault.injected", point=point, action=action,
                           node=victim, declared=False)
        journal.record("load.start", qps=args.qps, seconds=args.seconds,
                       threads=args.threads, read_pct=args.read_pct,
                       scan_pct=args.scan_pct, scenario=args.scenario)
        t_start = time.time()
        stop_at = t_start + args.seconds
        if runner is not None:
            runner.start(args.seconds)
        from pegasus_tpu.runtime.tasking import spawn_thread

        threads = [spawn_thread(
            _worker, t, args, meta_addr, stop_at, stats, stats_lock, lat,
            written, written_lock,
            windows if args.scenario != "none" else None, journal,
            table_ops, name=f"pressure-{t}", start=False)
            for t in range(args.threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.time() - t_start
        journal.record("load.done", elapsed_s=round(elapsed, 1))
        if runner is not None:
            # every armed fault heals + verifies recovery (may run past
            # the load window); a wedged actor is bounded by its own
            # recovery deadline, so the join is finite
            runner.join(timeout=180)

        # ---- conclusions: audit rounds (final quiesced round), the
        # cross-cluster digest compare, the final doctor verdict
        audit_summary = None
        if audits is not None:
            audit_summary = audits.stop(final_round=True)
            if audit_summary["mismatches"]:
                pass  # already journal.fail'd per mismatch by AuditRounds
            elif audit_summary["conclusive"] == 0:
                journal.fail("audit.vacuous",
                             detail="zero conclusive audit rounds — zero "
                                    "mismatches proves nothing",
                             rounds=audit_summary["rounds"])
        xcluster = None
        if dst is not None:
            # retry while INCONCLUSIVE (match=None) only: right after the
            # node-kill leg a replica can still be mid-learn, which makes
            # a single audit attempt vacuous (not wrong) — writes are
            # quiesced, so waiting out the learn and re-auditing is
            # sound. A real mismatch (match=False) is never retried.
            for attempt in range(3):
                xcluster = run_cross_cluster_audit(
                    [meta_addr], [dst.meta_addr], args.table)
                if xcluster["match"] is not None:
                    break
                journal.record("cross_cluster.retry", attempt=attempt,
                               inconclusive=xcluster["inconclusive"])
                time.sleep(5.0)
            journal.record("cross_cluster.audit", match=xcluster["match"],
                           src=xcluster["src"], dst=xcluster["dst"],
                           anchors=xcluster["anchors"])
            if xcluster["match"] is not True:
                journal.fail("cross_cluster.digest",
                             match=xcluster["match"],
                             inconclusive=xcluster["inconclusive"],
                             mismatches=xcluster["mismatches"])
        doctor = None
        if args.scenario != "none":
            doctor = run_cluster_doctor([meta_addr])
            journal.record("doctor.final", verdict=doctor["verdict"],
                           causes=[c["cause"] for c in doctor["causes"]])
            if doctor["verdict"] != "healthy":
                journal.fail("doctor.unhealthy", verdict=doctor["verdict"],
                             causes=[c["cause"] for c in doctor["causes"]])

        # final quiesced fsck sweep (ISSUE 17): every surviving replica's
        # on-disk state must verify clean — a corruption the run's audits
        # missed (or one planted and never healed) fails the run here.
        # Engines are still live (background compaction can land files
        # between the walk and the verify), so transient error sets get
        # one re-check before they count.
        if box is not None:
            from tools.fsck import find_data_dirs, fsck_data_dir

            fsck_errors, ndirs = [], 0
            for attempt in range(2):
                fsck_errors, ndirs = [], 0
                for stub in list(box.cluster.stubs):
                    for d in find_data_dirs(stub.root):
                        ndirs += 1
                        fsck_errors.extend(
                            f for f in fsck_data_dir(d)
                            if f["severity"] == "error"
                            and os.path.exists(f["path"]))
                if not fsck_errors:
                    break
                time.sleep(2.0)
            journal.record("fsck.final", dirs=ndirs,
                           errors=len(fsck_errors))
            if fsck_errors:
                journal.fail("fsck.corruption", count=len(fsck_errors),
                             first=f"{fsck_errors[0]['path']}: "
                                   f"{fsck_errors[0]['detail']}")

        if stats["verify_failures"]:
            journal.fail("verify.lost_acked_writes",
                         count=stats["verify_failures"])
        if stats["errors_steady"]:
            journal.fail("errors.steady_state",
                         count=stats["errors_steady"],
                         detail="errors outside any declared fault window")

        total_ops = stats["reads"] + stats["writes"] + stats["scans"]
        failures = journal.failures
        detail = {**stats, "elapsed_s": round(elapsed, 1),
                  "avg_ms": lat.avg(), "p95_ms": lat.percentile(0.95),
                  "p99_ms": lat.percentile(0.99),
                  "lat_sampled": min(lat.count, lat.cap),
                  "audit_rounds": audit_summary,
                  "fault_windows": windows.bounds(),
                  "failures": [f["failure"] for f in failures]}
        if len(tables) > 1:
            detail["table_ops"] = dict(sorted(table_ops.items()))
        if xcluster is not None:
            detail["cross_cluster"] = {
                k: xcluster[k] for k in ("match", "src", "dst", "dupid")
                if k in xcluster}
        if doctor is not None:
            detail["doctor"] = doctor["verdict"]
        if incident_box[0] is not None:
            detail["incident"] = incident_box[0]
        print(json.dumps({
            "metric": f"pressure test achieved qps (target {args.qps}, "
                      f"{args.read_pct}% reads, {args.scan_pct}% scans, "
                      f"{args.threads} threads, "
                      f"scenario {args.scenario})",
            "value": round(total_ops / elapsed, 1),
            "unit": "ops/s",
            "detail": detail,
        }), flush=True)
        if args.journal:
            journal.write(args.journal)
        for f in failures:
            print(f"pressure_test: FAILED: {f['failure']}: "
                  f"{ {k: v for k, v in f.items() if k not in ('kind', 'failure')} }",
                  file=sys.stderr)
        return 1 if failures else 0
    finally:
        if runner is not None:
            runner.stop()
        for b in (box, dst):
            if b is not None:
                if getattr(b, "offload_ctl", None) is not None:
                    b.offload_ctl.close()
                if getattr(b, "chaos_caller", None) is not None:
                    b.chaos_caller.close()
                b.stop()


def main():
    sys.exit(run_pressure())


if __name__ == "__main__":
    main()
