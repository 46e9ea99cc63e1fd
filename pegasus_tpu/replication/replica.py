"""PacificA replica: prepare/ack/commit 2PC over the mutation log + engine.

The rDSN replication core this build re-provides (SURVEY.md §2.4
'PacificA replication'; knobs config.ini:205-215): one primary serializes
writes per partition; each mutation gets a decree, appends to the private
log, and is sent RPC_PREPARE to every secondary; the primary commits (=
applies to the storage engine via on_batched_write_requests) once
`mutation_2pc_min_replica_count` replicas (incl. itself) hold it in their
logs. Commit points piggyback on later prepares. DECREE PIPELINING:
mutations arriving while a prepare round is in flight coalesce into the
next round — one prepare RPC carries the contiguous decree window
[d1..dk], the plog lands the window as one group append, secondaries
append the window in order and ack the highest contiguous decree, and the
engine applies the committed window in one batched call (per-item
overheads amortize; the protocol itself is untouched). PacificA
invariants kept:

  - prepares apply in decree order; a secondary acks decree d only when its
    log holds every decree <= d (so last_prepared is contiguous coverage);
  - committed(d) => d is in the logs of a quorum => after any crash, the
    live replica with the highest (ballot, last_prepared) holds every
    committed mutation; failover promotes it and commits its whole prepare
    list ("prepared implies eventually committed");
  - a rejoining replica re-seeds as a learner: engine checkpoint copy +
    log tail from the current primary (reference learn flow, SURVEY §3.5).

Engine replay-on-open closes the WAL gap: committed-but-unflushed
mutations are re-applied from the plog before serving.
"""

import os
import threading
import time
from dataclasses import dataclass

from ..engine import EngineOptions
from ..engine.replica_service import WRITE_CODES
from ..engine.server_impl import PegasusServer
from ..rpc import codec
from ..runtime import lockrank
from ..runtime.perf_counters import counters
from ..runtime.tracing import REQUEST_TRACER
from .mutation_log import LogMutation, MutationLog

def _parallel_prepare() -> bool:
    """Concurrent prepare fan-out wins when peer RTT is real network wait
    (multi-host deployments: set PEGASUS_PARALLEL_PREPARE=1). On a
    single-core onebox the 'RTT' is mostly peer CPU under the same GIL and
    the pool dispatch only adds contention — measured 3.4k -> 2.9k ops/s
    YCSB-A at 8 threads — so the default stays sequential."""
    return os.environ.get("PEGASUS_PARALLEL_PREPARE", "0") == "1"


INACTIVE = "INACTIVE"
PRIMARY = "PRIMARY"
SECONDARY = "SECONDARY"
LEARNER = "POTENTIAL_SECONDARY"
ERROR = "ERROR"


class ReplicaError(Exception):
    pass


class PrepareRejected(ReplicaError):
    def __init__(self, reason, last_prepared=0):
        super().__init__(reason)
        self.reason = reason
        self.last_prepared = last_prepared


@dataclass
class GroupView:
    """What the (meta-server stand-in) controller tells members."""

    ballot: int
    primary: str
    secondaries: list


class _WriteSlot:
    __slots__ = ("code", "req", "resp", "err", "done")

    def __init__(self, code, req):
        self.code = code
        self.req = req
        self.resp = None
        self.err = None
        self.done = False


class Replica:
    """One partition replica. `peers` is a callable transport:
    peers(name) -> Replica-like proxy (direct object in-process; an RPC stub
    across processes). Raises ConnectionError for dead nodes."""

    def __init__(self, name: str, path: str, app_id: int = 1, pidx: int = 0,
                 options: EngineOptions = None, peers=None,
                 quorum: int = 2, fsync: bool = False, cluster_id: int = 0):
        self.name = name
        self.path = path
        self.app_id = app_id
        self.pidx = pidx
        self.cluster_id = cluster_id
        self.quorum = quorum
        self.peers = peers or (lambda n: (_ for _ in ()).throw(ConnectionError(n)))
        self._lock = lockrank.named_rlock("replica.lock")
        self.status = INACTIVE  #: guarded_by self._lock
        self.ballot = 0         #: guarded_by self._lock
        self.view = None        #: guarded_by self._lock
        # a streamed learn is staging blocks with self._lock RELEASED
        # (ISSUE 13): prepares arriving meanwhile are rejected instead of
        # interleaving with the staged state (the primary treats the
        # rejection as a missing ack; the post-swap gap path catches up)
        self._learning = False  #: guarded_by self._lock
        # primary-side learn pins (ISSUE 13): learn_id -> pin record.
        # While pinned, plog GC floors at the pinned checkpoint decree
        # (the tail fetch must stay replayable) and the engine holds the
        # pinned checkpoint out of its own GC. Leaf lock (never nests
        # another lock under it).
        self._learn_lock = lockrank.named_lock("replica.learn_pins")
        self._learn_pins = {}   #: guarded_by self._learn_lock
        self._learn_next_id = 0  #: guarded_by self._learn_lock
        # learner-side serialization: the transfer runs with self._lock
        # released, so without this a meta retry (its open RPC timing out
        # while the first learn still streams) would start a SECOND learn
        # staging into the same learn_ckpt/ dir mid-flight
        self._learn_serial = lockrank.named_lock("replica.learn_serial")
        self.server = PegasusServer(os.path.join(path, "data"), app_id=app_id,
                                    pidx=pidx, options=options, server=name,
                                    cluster_id=cluster_id)
        # on-disk corruption callout (ISSUE 17): the stub points this at
        # its quarantine machinery; kept on the Replica (not just the
        # engine) because a learn replaces the engine wholesale and the
        # fresh one must keep reporting
        self.corruption_hook = None
        self.plog = MutationLog(os.path.join(path, "plog"), fsync=fsync)
        # decree -> LogMutation (prepared, not applied)
        self._uncommitted = {}   #: guarded_by self._lock
        self._batch_cv = lockrank.named_condition("replica.batch")
        # _WriteSlots awaiting a group commit
        self._batch_pending = []  #: guarded_by self._batch_cv
        self._batch_leader_active = False  #: guarded_by self._batch_cv
        self.commit_hooks = []   # fn(LogMutation) after commit (duplication)
        self.duplicators = {}    # dupid -> MutationDuplicator (stub-managed)
        self.app_name = ""       # set by the stub at open
        self.partition_count = 0
        self.last_committed = self.server.engine.last_committed_decree()  #: guarded_by self._lock
        self.last_prepared = self.last_committed  #: guarded_by self._lock
        self._prep_pool = None
        # replication-lag plane (ISSUE 8): per-partition gauges resolved
        # ONCE (the registry lock is per-lookup and these fire per window)
        pfx = f"replica.{app_id}.{pidx}."
        self._c_inflight = counters.number(pfx + "inflight")
        self._c_backlog = counters.number(pfx + "backlog")
        self._c_committed = counters.number(pfx + "committed_decree")
        self._c_applied = counters.number(pfx + "applied_decree")
        self._c_gap = counters.number(pfx + "secondary_gap_max")
        # compaction-debt plane (ISSUE 10): per-partition gauges the
        # scheduler, doctor and collector read — refreshed per beacon
        # tick from the same engine fold the beacon state carries
        cpfx = f"engine.compact.{app_id}.{pidx}."
        self._c_debt_l0 = counters.number(cpfx + "l0_files")
        self._c_debt_bytes = counters.number(cpfx + "debt_bytes")
        self._c_debt_pending = counters.number(cpfx + "pending_installs")
        self._recover_from_log()

    def _prepare_pool(self):
        if self._prep_pool is None:
            from ..runtime.tasking import tracked_executor

            self._prep_pool = tracked_executor(
                4, thread_name_prefix=f"prep-{self.name}")
        return self._prep_pool

    def set_corruption_hook(self, fn) -> None:
        """Install the stub's read-path corruption callout on this replica
        AND its current engine (future engines — learn swaps — inherit it
        from self.corruption_hook in _swap_learned_state)."""
        self.corruption_hook = fn
        self.server.engine.corruption_hook = fn

    # ----------------------------------------------------------- recovery

    def _recover_from_log(self):  #: unguarded_ok construction-time: called only from __init__, before the replica is published to any other thread
        """Re-stage every logged mutation after the engine's committed point.
        They stay uncommitted until a view tells us our role (a new primary
        commits them all; a learner discards and re-seeds)."""
        for m in self.plog.replay(0):
            if m.decree > self.last_committed:
                self._uncommitted[m.decree] = m
                self.last_prepared = max(self.last_prepared, m.decree)
            self.ballot = max(self.ballot, m.ballot)

    # --------------------------------------------------------------- views

    def assume_view(self, view: GroupView):
        """Controller-installed configuration (meta server's reconfiguration)."""
        with self._lock:
            self.view = view
            self.ballot = max(self.ballot, view.ballot)
            if view.primary == self.name:
                self.status = PRIMARY
                # PacificA failover rule: commit the entire prepare list
                self._apply_up_to(self.last_prepared)
            elif self.name in view.secondaries:
                self.status = SECONDARY

    # -------------------------------------------------------------- primary

    def client_write(self, code: str, req, now: int = None):
        """The write path: 2PC from the primary (SURVEY §3.2 hot path).

        DECREE PIPELINING: every mutation gets its OWN decree (the
        reference's one-decree-per-mutation shape), but mutations that
        arrive while a prepare round is in flight coalesce into the NEXT
        round — one prepare RPC carries the whole contiguous decree
        window [d1..dk], the plog lands it as one group append, and the
        engine applies the committed window in one batched call. Commit
        points piggyback on later prepares exactly as before."""
        slot = _WriteSlot(code, req)
        with self._batch_cv:
            self._batch_pending.append(slot)
        # this writer's wait for a window leader: one span however often
        # the loop turns
        wait = REQUEST_TRACER.span("replica.window_wait")
        while True:
            with self._batch_cv:
                if slot.done:
                    break
                if self._batch_leader_active:
                    wait.begin()
                    # handoff is notify-driven (the leader's finally block
                    # notify_all's); the timeout is only a defensive bound,
                    # not a polling cadence (ADVICE r2 weak: 50ms poll)
                    self._batch_cv.wait(0.5)
                    continue
                self._batch_leader_active = True
                batch = self._batch_pending
                self._batch_pending = []
            wait.end()
            # this thread leads one window commit (outside the cv so
            # arriving writers can queue for the NEXT window meanwhile)
            try:
                with self._lock:
                    self._commit_window(batch, now=now)
            except Exception as e:  # every waiter must see the failure, not
                for s in batch:     # a silent resp=None "success"
                    if s.err is None and s.resp is None:
                        s.err = e if isinstance(e, ReplicaError) \
                            else ReplicaError(f"group commit failed: {e!r}")
            finally:
                with self._batch_cv:
                    self._batch_leader_active = False
                    for s in batch:
                        s.done = True
                    self._batch_cv.notify_all()
        wait.end()
        if slot.err is not None:
            raise slot.err
        return slot.resp

    def _commit_window(self, slots, now=None):  #: requires self._lock
        """One contiguous decree window for `slots` (one decree each);
        caller holds self._lock. Fills each slot's resp/err in place."""
        if self.status != PRIMARY:
            raise ReplicaError(f"{self.name} is not primary")
        d0 = self.last_prepared + 1
        ts = int(time.time() * 1e6)
        ms = [LogMutation(decree=d0 + i, ballot=self.ballot, timestamp_us=ts,
                          codes=[s.code], bodies=[codec.encode(s.req)])
              for i, s in enumerate(slots)]
        dk = ms[-1].decree
        t0 = time.perf_counter()
        with REQUEST_TRACER.span("replica.prepare", decree=dk,
                                 batch=len(ms)):
            self.plog.append_window(ms)
            self.last_prepared = dk
            for m in ms:
                self._uncommitted[m.decree] = m
            secs = list(self.view.secondaries)
            if len(secs) > 1 and _parallel_prepare():
                # prepares fan out concurrently: commit latency is
                # max(peer RTT), not the sum (the reference's parallel
                # RPC_PREPARE sends). Wait for ALL so per-peer prepare
                # order stays monotonic. The trace context is thread-local
                # — each worker adopts it so the peers' prepare spans (and
                # the trace_id on the wire) survive the pool hop.
                ctx = REQUEST_TRACER.current()

                def send(s):
                    with REQUEST_TRACER.adopt(ctx):
                        return self._send_prepare_window(s, ms)

                futs = [self._prepare_pool().submit(send, s) for s in secs]
                peer_lps = [f.result() for f in futs]
            else:
                peer_lps = [self._send_prepare_window(s, ms) for s in secs]
        counters.percentile("replica.prepare_latency_us").set(
            int((time.perf_counter() - t0) * 1e6))
        self._export_gauges()
        # commit point: the highest decree d in the window such that a
        # quorum (incl. us) holds every decree <= d — peers ack their
        # highest CONTIGUOUS prepared decree, so coverage is monotonic
        acks = [lp for lp in peer_lps if lp is not None]
        # worst responding secondary's prepare lag behind this window's
        # tail (dead peers surface via meta liveness, not this gauge)
        self._c_gap.set(max((max(0, dk - lp) for lp in acks), default=0))
        commit_d = d0 - 1
        for d in range(d0, dk + 1):
            if 1 + sum(1 for lp in acks if lp >= d) >= self.quorum:
                commit_d = d
            else:
                break
        if commit_d < d0:
            # cannot commit; leave prepared (a later view change decides)
            raise ReplicaError(
                f"quorum lost: {1 + len(acks)}/{self.quorum} "
                f"for decrees [{d0}..{dk}]")
        t1 = time.perf_counter()
        with REQUEST_TRACER.span("replica.commit", decree=commit_d):
            resps = self._apply_up_to(commit_d, now=now)
        counters.percentile("replica.commit_latency_us").set(
            int((time.perf_counter() - t1) * 1e6))
        self._export_gauges()
        for i, s in enumerate(slots):
            d = d0 + i
            if d <= commit_d:
                rl = resps.get(d)
                s.resp = rl[0] if rl else None
            else:
                s.err = ReplicaError(
                    f"quorum lost: decree {d} prepared but not committed")

    def _export_gauges(self):  #: requires self._lock
        """Per-partition write-path pressure + replication-lag plane:
        slots queued for the next group commit (inflight),
        prepared-but-uncommitted decrees (backlog), and the
        committed/applied decree pair — `committed_decree` is what
        replication knows is committed HERE, `applied_decree` is what the
        engine actually applied; they diverge exactly when a replica is
        behind on APPLY (mid-window engine failure) rather than behind on
        commit, which is the distinction the cluster doctor reports."""
        self._c_inflight.set(len(self._batch_pending))  #: unguarded_ok gauge snapshot of the queue length; the cv would add contention to every write for a stat
        self._c_backlog.set(len(self._uncommitted))
        self._c_committed.set(self.last_committed)
        self._c_applied.set(self.server.engine.last_committed_decree())

    def compact_debt(self) -> dict:
        """Per-partition compaction-debt snapshot (ISSUE 10): one engine
        fold feeding the `engine.compact.<a>.<p>.*` gauges, the beacon
        state the meta snapshot republishes, and db.stats() — the
        scheduler, the doctor and the collector all read the same
        series. Refreshed per beacon tick."""
        debt = self.server.engine.compaction_debt()
        self._c_debt_l0.set(debt["l0_files"])
        self._c_debt_bytes.set(debt["debt_bytes"])
        self._c_debt_pending.set(debt["pending_installs"])
        return debt

    def _send_prepare_window(self, peer_name: str, ms: list):
        """Send one windowed prepare to a peer. Returns the peer's highest
        contiguous prepared decree (its ack), or None for a dead/rejecting
        peer."""
        try:
            peer = self.peers(peer_name)
            try:
                return self._peer_prepare(peer, ms)
            except PrepareRejected as rej:
                if rej.reason == "gap":
                    return self._catch_up_peer(peer, rej.last_prepared, ms)
                return None
        except ConnectionError:
            return None

    def _peer_prepare(self, peer, ms: list):
        """One prepare round against a peer object: windowed when the peer
        supports it, per-mutation for a legacy peer. -> acked decree."""
        if hasattr(peer, "on_prepare_batch"):
            return peer.on_prepare_batch(self.ballot, ms, self.last_committed)  #: unguarded_ok stable during the fan-out: every ballot/commit-point writer needs self._lock, which the window leader holds until all prepare workers return
        for m in ms:
            peer.on_prepare(self.ballot, m, self.last_committed)  #: unguarded_ok stable during the fan-out (see on_prepare_batch above)
        return ms[-1].decree

    def _catch_up_peer(self, peer, peer_prepared: int, ms: list):
        """Stream the missing decrees from our log as chunked windows,
        then retry the current window. -> acked decree or None. A peer
        exposing on_prepare_windows (the RPC proxy) gets the whole backlog
        in ONE coalesced transport send."""
        try:
            backlog = {}
            for lm in self.plog.replay(peer_prepared):
                if lm.decree < ms[0].decree:
                    backlog[lm.decree] = lm  # dedup, newest copy wins
            chunks = [ms]
            ordered = [backlog[d] for d in sorted(backlog)]
            if ordered:
                chunks = [ordered[i:i + 64]
                          for i in range(0, len(ordered), 64)] + [ms]
            if hasattr(peer, "on_prepare_windows"):
                return peer.on_prepare_windows(
                    self.ballot, chunks, self.last_committed)  #: unguarded_ok stable during the fan-out (see on_prepare_batch above)
            lp = None
            for chunk in chunks:
                lp = self._peer_prepare(peer, chunk)
            return lp
        except (PrepareRejected, ConnectionError):
            return None

    # ------------------------------------------------------------ secondary

    def on_prepare_batch(self, ballot: int, ms: list, committed_decree: int):
        """Windowed prepare: stage a contiguous decree window with ONE
        plog group append and ack the highest contiguous prepared decree.
        The per-decree invariants are exactly on_prepare's — ack(d) only
        once the log holds every decree <= d. An EMPTY window is a pure
        commit-point broadcast (broadcast_commit_point): nothing stages,
        but staged decrees covered by `committed_decree` apply — how an
        idle partition's secondaries learn the last window committed."""
        with REQUEST_TRACER.span("replica.on_prepare",
                                 decree=ms[-1].decree if ms
                                 else committed_decree,
                                 batch=len(ms)), self._lock:
            if self._learning:
                # mid-learn: the staged state is about to replace this
                # replica wholesale — interleaving prepares would be
                # wiped (or worse, survive the swap). The primary treats
                # this as a missing ack; post-swap the gap path catches
                # up from the primary's log.
                raise PrepareRejected("learning", self.last_prepared)
            if ballot < self.ballot:
                raise PrepareRejected("stale_ballot", self.last_prepared)
            self.ballot = ballot
            fresh, gap = [], False
            for m in ms:
                if m.decree <= self.last_committed:
                    continue  # already committed: drop (see on_prepare)
                if m.decree <= self.last_prepared:
                    # duplicate (catch-up overlap): keep newest copy staged
                    self._uncommitted.setdefault(m.decree, m)
                elif m.decree == self.last_prepared + len(fresh) + 1:
                    fresh.append(m)
                elif m.decree <= self.last_prepared + len(fresh):
                    pass  # duplicates a decree already in this window
                else:
                    gap = True
                    break
            if fresh:
                # durability before ack: the window is in the log (one
                # group flush) before last_prepared moves
                self.plog.append_window(fresh)
                for m in fresh:
                    self._uncommitted[m.decree] = m
                self.last_prepared = fresh[-1].decree
            self._apply_up_to(min(committed_decree, self.last_prepared))
            self._export_gauges()
            if gap:
                raise PrepareRejected("gap", self.last_prepared)
            return self.last_prepared

    def broadcast_commit_point(self) -> int:
        """Push the current commit point to every secondary as an EMPTY
        prepare window, so decrees they hold prepared apply NOW instead
        of waiting for the next write's piggyback. trigger_audit needs
        this: on an idle partition the audit decree would otherwise sit
        staged on secondaries indefinitely and the audit could never
        conclude. -> number of peers that acked."""
        with self._lock:
            if self.status != PRIMARY or self.view is None:
                return 0
            secs = list(self.view.secondaries)
            ballot, committed = self.ballot, self.last_committed
        n = 0
        for s in secs:
            try:
                peer = self.peers(s)
                if hasattr(peer, "on_prepare_batch"):
                    peer.on_prepare_batch(ballot, [], committed)
                    n += 1
            except (PrepareRejected, ConnectionError):
                continue
        return n

    def on_prepare(self, ballot: int, m: LogMutation, committed_decree: int):
        with REQUEST_TRACER.span("replica.on_prepare", decree=m.decree), \
                self._lock:
            if self._learning:
                raise PrepareRejected("learning", self.last_prepared)
            if ballot < self.ballot:
                raise PrepareRejected("stale_ballot", self.last_prepared)
            self.ballot = ballot
            if m.decree <= self.last_committed:
                # already committed: drop — staging it would leak, since
                # _apply_up_to only ever pops decrees > last_committed
                # (ADVICE r2 low)
                pass
            elif m.decree <= self.last_prepared:
                # duplicate (catch-up overlap): keep newest copy staged
                self._uncommitted.setdefault(m.decree, m)
            elif m.decree == self.last_prepared + 1:
                self.plog.append(m)
                self.last_prepared = m.decree
                self._uncommitted[m.decree] = m
            else:
                raise PrepareRejected("gap", self.last_prepared)
            self._apply_up_to(min(committed_decree, self.last_prepared))

    # ---------------------------------------------------------------- apply

    def _apply_up_to(self, decree: int, now: int = None):  #: requires self._lock
        """Commit staged mutations in order through the storage engine —
        the whole contiguous window in ONE batched engine call
        (on_batched_write_window: consecutive batchable decrees share one
        WriteBatch and one engine lock acquisition). Returns
        {decree: response list} for every decree applied."""
        if self.last_committed >= decree:
            return {}
        window, ms = [], []
        for d in range(self.last_committed + 1, decree + 1):
            m = self._uncommitted.pop(d, None)
            if m is None:
                raise ReplicaError(f"{self.name}: commit gap at decree {d}")
            reqs = []
            for code, body in zip(m.codes, m.bodies):
                req_cls, _ = WRITE_CODES[code]
                reqs.append((code, codec.decode(req_cls, body)))
            window.append((d, m.timestamp_us, reqs))
            ms.append(m)
        try:
            resps = self.server.on_batched_write_window(window, now=now)
        except Exception:
            # a mid-window engine failure (fail points) leaves the engine
            # at its own committed point: re-stage what was not applied so
            # a later view change or retry can still commit it, and fire
            # the commit hooks for what WAS applied — a duplication
            # shipper advances past this window on the next commit, so a
            # decree skipped here would never ship
            applied = self.server.engine.last_committed_decree()
            for m in ms:
                if m.decree > applied:
                    self._uncommitted[m.decree] = m
                else:
                    for hook in self.commit_hooks:
                        hook(m)
            self.last_committed = max(self.last_committed, applied)
            raise
        self.last_committed = decree
        for m in ms:
            for hook in self.commit_hooks:
                hook(m)
        return resps

    # --------------------------------------------------------------- learner

    def learn_from(self, primary):
        """Re-seed from the primary: checkpoint copy + log tail
        (reference learn flow: get_checkpoint -> storage_apply_checkpoint ->
        replay private log, SURVEY §3.5). `primary` is anything exposing
        fetch_learn_state() — a local Replica or an RPC peer proxy (the
        NFS-like learn file copy of config.ini:64-73)."""
        from ..runtime import events

        learning = counters.number(
            f"replica.{self.app_id}.{self.pidx}.learning")
        learning.set(1)
        events.emit("learn.start", gpid=f"{self.app_id}.{self.pidx}")
        t0 = time.monotonic()
        ok = False
        try:
            self._learn_from(primary)
            ok = True
        finally:
            learning.set(0)
            events.emit("learn.finish", severity="info" if ok else "error",
                        gpid=f"{self.app_id}.{self.pidx}", ok=ok,
                        dur_s=round(time.monotonic() - t0, 3),
                        committed=self.last_committed)  #: unguarded_ok post-learn snapshot for the event record; _learn_from already released the lock and the value only moves forward
            self._export_gauges()

    def _learn_from(self, primary):
        with self._learn_serial:
            self._learn_from_serialized(primary)

    def _learn_from_serialized(self, primary):
        with self._lock:
            self.status = LEARNER
            self._learning = True
            self._uncommitted.clear()
        try:
            if hasattr(primary, "prepare_learn_state"):
                self._learn_streamed(primary)
            else:  # legacy peer: monolithic whole-state copy
                self._learn_monolithic(primary)
        finally:
            with self._lock:
                self._learning = False

    def _learn_streamed(self, primary):
        """Block-shipped learn (ISSUE 13): manifest-diff handshake, then
        chunked delta streaming into learn_ckpt/ with BOTH locks released
        (the primary serves pinned immutable files, this replica rejects
        prepares via _learning), then a decree-anchored digest proof of
        the staged state, and only then a short swap critical section."""
        import shutil

        from . import learn as learn_mod
        from ..runtime import events
        from ..runtime.job_trace import JOB_TRACER

        t0 = time.perf_counter()
        ckpt_dir = os.path.join(self.path, "learn_ckpt")
        data_dir = os.path.join(self.path, "data")
        # each learn is ONE traced background job (ISSUE 16): prepare /
        # fetch waves / digest proof / swap are its hops, and the job id
        # rides the prepare RPC so the serving primary can attribute its
        # checkpoint pin to this learn's timeline
        with JOB_TRACER.job("learn", gpid=f"{self.app_id}.{self.pidx}",
                            learner=self.name):
            self._learn_streamed_traced(primary, learn_mod, events, shutil,
                                        ckpt_dir, data_dir, t0)

    def _learn_streamed_traced(self, primary, learn_mod, events, shutil,
                               ckpt_dir, data_dir, t0):
        from ..runtime.job_trace import JOB_TRACER

        # the delta handshake: what this replica already holds — staged
        # blocks from an interrupted ship (resume) plus the live engine's
        # current files (a re-learn that still has 99% of the SSTs). The
        # live manifest is computed ONCE and reused as stage_blocks'
        # link-reuse index — no second full-directory digest scan.
        delta_on = learn_mod.delta_enabled()
        live = learn_mod.dir_manifest(data_dir) if delta_on else []
        have = (learn_mod.dir_manifest(ckpt_dir) + live) if delta_on else []
        with JOB_TRACER.hop("learn.prepare", have=len(have)) as jh:
            st = primary.prepare_learn_state(have=have, delta=delta_on)
            jh["blocks"] = len(st["blocks"])
            jh["missing"] = len(st["missing"])
        try:
            with JOB_TRACER.hop("learn.fetch") as jh:
                stats = learn_mod.stage_blocks(
                    primary, st, ckpt_dir, delta=delta_on,
                    reuse={e["digest"]: os.path.join(data_dir, e["name"])
                           for e in live})
                jh.update({k: stats[k] for k in
                           ("fetched", "bytes", "skipped", "resumed")})
            with JOB_TRACER.hop("learn.tail"):
                tail_state = primary.fetch_learn_tail(st["learn_id"])
        finally:
            primary.finish_learn(st["learn_id"])
        verify = ""
        if st.get("digest"):
            # the shipped replica proves itself byte-consistent on
            # arrival BEFORE it may serve. DELTA learns take the
            # INCREMENTAL proof (ISSUE 14 satellite, learn follow-on c):
            # stage_blocks' running fold over the per-block digests it
            # verified equals the fold of the primary's manifest, so the
            # staged dir holds exactly the checkpoint's bytes — cost
            # O(delta), no record rescan per learn. A learn that reused
            # NOTHING (a fresh seed, or delta off) still pays the full
            # decree-anchored rescan: it is the trust anchor that
            # cross-checks the primary's logical digest against what was
            # actually shipped, once, before incremental re-learns lean
            # on it. Fold mismatch (or PEGASUS_LEARN_INCREMENTAL_DIGEST
            # =0) falls back to the rescan; the mismatch behavior is
            # unchanged — fail the learn loudly, never a silent
            # divergent serve.
            with JOB_TRACER.hop("learn.digest_proof") as jh:
                if learn_mod.incremental_digest_enabled() \
                        and stats["skipped"] + stats["resumed"] > 0 \
                        and stats.get("fold") \
                        and stats["fold"] == learn_mod.manifest_fold(
                            st["blocks"]):
                    verify = "incremental"
                    counters.rate(
                        "learn.verify.incremental_count").increment()
                else:
                    verify = "rescan"
                    counters.rate("learn.verify.rescan_count").increment()
                    from ..engine import EngineOptions
                    from ..engine.db import LsmEngine

                    ver = LsmEngine(ckpt_dir, EngineOptions(
                        backend="cpu", pidx=self.pidx))
                    try:
                        d = ver.state_digest(now=st["digest_now"],
                                             pmask=st["digest_pmask"])
                    finally:
                        ver.close()
                    if d["digest"] != st["digest"]:
                        raise ReplicaError(
                            f"{self.name}: shipped state digest mismatch at "
                            f"checkpoint decree {st['ckpt_decree']}: "
                            f"{d['digest']} != primary {st['digest']}")
                jh["mode"] = verify
        with JOB_TRACER.hop("learn.swap") as jh:
            replayed = self._swap_learned_state(ckpt_dir, tail_state)
            jh["replayed"] = replayed
        shutil.rmtree(ckpt_dir, ignore_errors=True)  # staged blocks are
        # hardlinked into data/ now; keeping them would feed stale names
        # into the NEXT learn's have-set
        counters.percentile("learn.ship.duration_us").set(
            int((time.perf_counter() - t0) * 1e6))
        events.emit("learn.ship", gpid=f"{self.app_id}.{self.pidx}",
                    decree=st["ckpt_decree"], fetched=stats["fetched"],
                    bytes=stats["bytes"], delta_skipped=stats["skipped"],
                    resumed=stats["resumed"], replayed=replayed,
                    verify=verify)

    def _learn_monolithic(self, primary):
        """Legacy whole-state learn (a peer without the block-ship
        surface): the transfer still runs with this replica's lock
        released — only the swap is a critical section."""
        state = primary.fetch_learn_state()
        ckpt_dir = os.path.join(self.path, "learn_ckpt")
        if os.path.exists(ckpt_dir):
            import shutil

            shutil.rmtree(ckpt_dir)
        os.makedirs(ckpt_dir)
        nbytes = 0
        for fname, blob in state["files"]:
            with open(os.path.join(ckpt_dir, fname), "wb") as f:
                f.write(blob)
            nbytes += len(blob)
        counters.rate("learn.ship.blocks").increment(len(state["files"]))
        counters.rate("learn.ship.bytes").increment(nbytes)
        self._swap_learned_state(ckpt_dir, state)

    def _swap_learned_state(self, ckpt_dir: str, tail_state: dict) -> int:
        """The learn's ONLY critical section: swap the staged checkpoint
        in as the serving engine, reset the plog, stage + apply the log
        tail above the checkpoint decree. -> tail mutations replayed."""
        replayed = 0
        with self._lock:
            self.server.close()
            from ..engine.db import LsmEngine

            engine = LsmEngine.apply_checkpoint(
                ckpt_dir, os.path.join(self.path, "data"),
                self.server.engine.opts)
            self.server = PegasusServer.__new__(PegasusServer)
            self.server.__init__(os.path.join(self.path, "data"),
                                 app_id=self.app_id, pidx=self.pidx,
                                 options=engine.opts, server=self.name,
                                 cluster_id=self.cluster_id)
            # the swap built a brand-new engine: re-arm the corruption
            # callout or post-learn bit-rot would go unreported
            self.server.engine.corruption_hook = self.corruption_hook
            self.plog.reset()
            self.last_committed = self.server.engine.last_committed_decree()
            self.last_prepared = self.last_committed
            # replay ONLY the log tail beyond the checkpoint decree —
            # the whole point of shipping compacted state
            for m in tail_state["tail"]:
                if m.decree <= self.last_prepared:
                    continue
                self.plog.append(m)
                self.last_prepared = m.decree
                self._uncommitted[m.decree] = m
                replayed += 1
            self._apply_up_to(min(tail_state["last_committed"],
                                  self.last_prepared))
            self.ballot = max(self.ballot, tail_state["ballot"])
            self.status = SECONDARY
        counters.rate("learn.replay.mutations").increment(replayed)
        return replayed

    # ------------------------------------------------------ learn: primary

    def prepare_learn_state(self, have=None, delta=None) -> dict:
        """Manifest-diff handshake, primary side (ISSUE 13): pin an
        immutable checkpoint (checkpoint GC + plog GC of covered
        segments held while pinned), diff its block manifest against the
        learner's `have` set, and return only the missing blocks'
        metadata plus the checkpoint's decree-anchored digest. The
        replica lock is held only for the watermark snapshot — never
        across checkpointing or file reads (the old fetch_learn_state
        stalled the prepare path for the whole transfer)."""
        from . import learn as learn_mod

        eng = self.server.engine
        ttl = learn_mod.pin_ttl_s()
        with eng.checkpoint_lock:
            # flush=False: snapshot the DURABLE state only. Sequential
            # learns (the balancer moving many partitions, repair
            # retries) then share ONE checkpoint dir and its cached
            # digest instead of forcing a memtable flush + a fresh
            # full-state scan per learn — the un-flushed window rides
            # the log tail, which is exactly what the tail is for
            decree = eng.sync_checkpoint(flush=False)
            ckpt = eng.get_checkpoint_dir(decree)
            token = eng.pin_checkpoint(decree, ttl_s=ttl)
        try:
            manifest = learn_mod.dir_manifest(ckpt)
            digest = (eng.checkpoint_digest(decree)
                      if learn_mod.verify_enabled() else {})
        except BaseException:
            eng.unpin_checkpoint(decree, token)
            raise
        with self._learn_lock:
            self._learn_next_id += 1
            learn_id = self._learn_next_id
            self._learn_pins[learn_id] = {
                "decree": decree, "dir": ckpt, "token": token,
                "expires": time.monotonic() + ttl}
        delta_on = learn_mod.delta_enabled() if delta is None else bool(delta)
        have_set = {(e["name"], e["digest"])
                    for e in (have or [])} if delta_on else set()
        missing = [e["name"] for e in manifest
                   if (e["name"], e["digest"]) not in have_set]
        with self._lock:
            ballot, committed = self.ballot, self.last_committed
        return {"learn_id": learn_id, "ckpt_decree": decree,
                "ballot": ballot, "last_committed": committed,
                "blocks": manifest, "missing": missing,
                "digest": digest.get("digest", ""),
                "digest_now": digest.get("now", 0),
                "digest_pmask": digest.get("pmask", 0)}

    def _learn_pin(self, learn_id: int, renew: bool = True) -> dict:
        """Resolve (and lease-renew) an active learn pin; expired or
        unknown pins fail the fetch loudly so the learner restarts its
        learn instead of shipping from a GC-racing checkpoint."""
        from . import learn as learn_mod

        now = time.monotonic()
        ttl = learn_mod.pin_ttl_s()
        snap = None
        with self._learn_lock:
            pin = self._learn_pins.get(learn_id)
            if pin is not None and now < pin["expires"]:
                if renew:
                    pin["expires"] = now + ttl
                snap = dict(pin)
        if snap is None:
            raise ReplicaError(
                f"{self.name}: learn {learn_id} expired/unknown")
        if renew:  # engine lease renewed OUTSIDE the leaf pin lock
            self.server.engine.renew_checkpoint_pin(snap["decree"],
                                                    snap["token"], ttl)
        return snap

    def fetch_learn_block(self, learn_id: int, name: str, offset: int,
                          length: int) -> dict:
        """Serve one chunk of one pinned checkpoint block — LOCK-FREE:
        pinned files are immutable (checkpoint hardlinks are independent
        dir entries) and held out of GC by the pin."""
        from ..runtime.fail_points import inject
        import zlib

        inject("learn.ship")  # chaos seam: a mid-ship abort on the primary
        pin = self._learn_pin(learn_id)
        path = os.path.join(pin["dir"], os.path.basename(name))
        with open(path, "rb") as f:
            f.seek(offset)
            data = f.read(length)
        return {"data": data, "crc": zlib.crc32(data),
                "total": os.path.getsize(path)}

    def fetch_learn_chunks(self, learn_id: int, reqs) -> list:
        """In-process chunk wave (the RPC peer pipelines the same shape
        through call_many — learn.RemoteLearnSource)."""
        return [self.fetch_learn_block(learn_id, name, off, ln)
                for (name, off, ln) in reqs]

    def fetch_learn_tail(self, learn_id: int) -> dict:
        """Log tail above the pinned checkpoint decree + watermarks.
        The watermark snapshot is the only locked moment; the plog
        replay runs lock-free (segments covering the pin are held by
        gc_log's pin floor)."""
        pin = self._learn_pin(learn_id)
        with self._lock:
            ballot, committed = self.ballot, self.last_committed
        tail = list(self.plog.replay(pin["decree"]))
        return {"tail": tail, "last_committed": committed, "ballot": ballot}

    def finish_learn(self, learn_id: int) -> None:
        """Release the learn pin (GC of the checkpoint + covered log
        segments resumes). Idempotent; expiry covers a dead learner."""
        with self._learn_lock:
            pin = self._learn_pins.pop(learn_id, None)
        if pin is not None:
            self.server.engine.unpin_checkpoint(pin["decree"], pin["token"])

    def _live_learn_pin_floor(self) -> int:
        """Lowest pinned checkpoint decree (or a huge sentinel) — the
        plog GC floor while learns are in flight; expired pins reaped."""
        now = time.monotonic()
        dead = []
        with self._learn_lock:
            for lid, pin in list(self._learn_pins.items()):
                if now >= pin["expires"]:
                    dead.append(self._learn_pins.pop(lid))
            floor = min((p["decree"] for p in self._learn_pins.values()),
                        default=None)
        for pin in dead:
            self.server.engine.unpin_checkpoint(pin["decree"], pin["token"])
        return floor

    def learn_state(self) -> dict:
        """Learner-side learn snapshot (learn-status surface)."""
        with self._lock:
            return {"learning": self._learning, "status": self.status}

    def learn_pins(self) -> list:
        """Active primary-side learn pins (learn-status surface)."""
        now = time.monotonic()
        with self._learn_lock:
            return [{"learn_id": lid, "decree": p["decree"],
                     "expires_in_s": round(max(0.0, p["expires"] - now), 1)}
                    for lid, p in self._learn_pins.items()]

    def fetch_learn_state(self) -> dict:
        """Legacy monolithic learn state (old peers; the bench's
        monolithic A/B lane). Now pin-then-release: the checkpoint is
        pinned and every file read runs with NO replica lock held, so a
        learn can't stall this primary's prepare path for the duration
        of a multi-MB read (ISSUE 13 satellite)."""
        st = self.prepare_learn_state(have=(), delta=False)
        lid = st["learn_id"]
        try:
            pin = self._learn_pin(lid, renew=False)
            files = []
            for e in st["blocks"]:
                with open(os.path.join(pin["dir"], e["name"]), "rb") as f:
                    files.append((e["name"], f.read()))
            tail_state = self.fetch_learn_tail(lid)
            return {"files": files, "tail": tail_state["tail"],
                    "last_committed": tail_state["last_committed"],
                    "ballot": tail_state["ballot"]}
        finally:
            self.finish_learn(lid)

    # ------------------------------------------------------------- plumbing

    def gc_log(self, flush: bool = False):
        """Drop log segments the durable SSTs cover. flush=True forces the
        memtable down first (tests); the maintenance timer must NOT — a
        periodic forced flush would churn tiny L0 files on idle tables.
        Active duplications hold the log at their confirmed decree: a
        restarted/promoted shipper must be able to catch_up() from plog
        (the reference keeps plog for dup the same way)."""
        if flush:
            self.server.engine.flush()
        floor = self.server.engine.last_durable_decree()
        # active learn pins hold the log at their checkpoint decree: the
        # learner's tail fetch replays (pin decree, ...] and a segment
        # GC'd out from under it would open an unreplayable gap
        pin_floor = self._live_learn_pin_floor()
        if pin_floor is not None:
            floor = min(floor, pin_floor)
        # Per dup entry the holdback decree is the freshest confirmed point
        # we know: our own shipper's progress when we run one (primary),
        # else the meta-confirmed decree the env carries (secondaries hold
        # the log too — on promotion the new primary catches up from ITS
        # plog, so gc'ing past that floor would open a duplication gap; the
        # meta re-pushes refreshed entries periodically so this floor
        # advances on stable clusters instead of pinning the log at 0).
        entries = {e["dupid"]: e for e in self._dup_env_entries()
                   if e.get("status") in ("init", "start", "pause")}
        dups = dict(self.duplicators)
        for dupid, e in entries.items():
            conf = int(e.get("confirmed", {}).get(str(self.pidx), 0))
            d = dups.get(dupid)
            floor = min(floor, max(conf, d.last_shipped_decree) if d else conf)
        for dupid, d in dups.items():
            if dupid not in entries:  # shipper ahead of the env snapshot
                floor = min(floor, d.last_shipped_decree)
        self.plog.gc(floor)

    def _dup_env_entries(self) -> list:
        import json

        from ..base import consts

        try:
            return json.loads(
                self.server.app_envs.get(consts.ENV_DUPLICATION_KEY, "[]"))
        except ValueError:
            return []

    def close(self):
        for dupid, d in self.duplicators.items():
            d.stop()
            counters.remove(f"dup.lag.{self.app_id}.{self.pidx}.{dupid}")
        self.duplicators.clear()
        # unregister this partition's lag gauges: a closed (rebalanced
        # away) replica's frozen values must not keep feeding the
        # collector's cluster worst-offender series
        for name in ("inflight", "backlog", "committed_decree",
                     "applied_decree", "secondary_gap_max", "learning"):
            counters.remove(f"replica.{self.app_id}.{self.pidx}.{name}")
        for name in ("l0_files", "debt_bytes", "pending_installs"):
            counters.remove(
                f"engine.compact.{self.app_id}.{self.pidx}.{name}")
        if self._prep_pool is not None:
            self._prep_pool.shutdown(wait=False)
            self._prep_pool = None
        self.plog.close()
        self.server.close()
