"""Private mutation log (plog): the replica's WAL.

The rDSN mutation log this build re-provides (SURVEY.md §2.4 'Mutation
logs'; config.ini log_private_*): every prepared mutation appends here
BEFORE it is acknowledged, and replay-on-open re-applies committed-but-
unflushed mutations to the engine — the engine itself deliberately has no
WAL (engine/db.py docstring), exactly like the reference runs RocksDB with
WAL disabled because this log is the WAL.

File format: segments log.{start_decree} of framed records
    [u32 len][u32 crc32][payload]
payload = codec-encoded LogMutation. Torn tails (crash mid-append) are
detected by length/crc and truncated at recovery, like mutation_log's
replay cursor. Segments roll at `segment_bytes`; GC drops whole segments
whose decrees are all <= the durable decree.

Why plog-only (no shared log / slog) — a deliberate redesign, not a gap.
The reference historically wrote every mutation TWICE: once to a
node-global shared log (batched, sequential — the commit-latency path)
and once to a per-replica private log (the replay/learn path), because
hundreds of replicas each fsyncing a private WAL would shatter a
spinning disk's sequential bandwidth (config.ini:192-260 tunes both).
Pegasus itself later deprecated the slog (it is absent from modern
apache/incubator-pegasus; log_shared_* knobs were removed) for the same
reasons that apply here, only stronger:

  * this build acknowledges writes from the 2PC quorum over PacificA with
    group commit — one plog append per CONCURRENT BATCH, not per write,
    so the append rate is bounded by batch rounds, not ops;
  * plog appends are buffered sequential writes with fsync optional
    (`fsync=False` default, like log_private flush cadence), so there is
    no per-replica-seek penalty to amortize on modern storage;
  * a single log keyed by decree keeps recovery single-source: replay,
    learner catch-up, duplication catch_up, and mlog_dump all read the
    same stream — the reference needed slog->plog "log split" complexity
    precisely because recovery had two sources of truth.

The one capability the slog bought — cross-replica batched fsync on one
spindle — is irrelevant on flash and under group commit; nothing else in
the recovery story needs it.

Group commit (the batched fsync the docstring above promises): appends
buffer into a bounded group — the first appender with no active leader
claims everything buffered and lands it with ONE buffered write + ONE
flush (+ one fsync when `fsync=True`); appenders arriving meanwhile form
the next group. `PEGASUS_PLOG_GROUP_N` caps mutations per group (32);
`PEGASUS_PLOG_GROUP_US` (500) bounds how long a leader that claimed a
concurrent group lingers for stragglers — a solo appender never lingers,
so single-writer latency is unchanged. An append returns only after its
group is durable (never ack before durable); a leader wedged between
claim and flush (`plog.group` fail point) degrades unclaimed appends to
the per-append path instead of hanging the partition. Group sizes export
as `plog.append.group_size`, flushes as `plog.append.flush_count`.

Reachability note, to be honest about what runs where: PacificA holds
the replica lock across every append call site, so per-partition the
log sees ONE appender at a time and a group is normally exactly one
append_window entry — the decree window IS the group, and that is where
the batching win comes from. The leader/follower machinery above it is
the general multi-appender contract (chaos tests drive it with raw
threads; a future shared-log caller gets correct grouping for free) and
carries the wedge-degrade path; it adds one cv round-trip, no waiting,
on the solo path.
"""

import os
import struct
import threading
import time
import zlib
from dataclasses import dataclass, field
from typing import List

from ..rpc import codec
from ..runtime import lockrank
from ..runtime.fail_points import inject
from ..runtime.perf_counters import counters
from ..runtime.tracing import REQUEST_TRACER

_FRAME = struct.Struct("<II")


class _GroupEntry:
    """One append (or one decree window) waiting for its group to land."""

    __slots__ = ("frames", "decrees", "done", "err")

    def __init__(self, frames, decrees):
        self.frames = frames
        self.decrees = decrees
        self.done = False
        self.err = None


@dataclass
class LogMutation:
    """One decree's mutation batch as it travels prepare->log->apply."""

    decree: int = 0
    ballot: int = 0
    timestamp_us: int = 0
    requests: List[tuple] = field(default_factory=list)  # unused; see codes/bodies

    # codec has no Tuple support; parallel lists keep the frame simple
    codes: List[str] = field(default_factory=list)
    bodies: List[bytes] = field(default_factory=list)


class MutationLog:
    def __init__(self, log_dir: str, segment_bytes: int = 32 << 20,
                 fsync: bool = False, group_n: int = None,
                 group_us: int = None):
        self.dir = log_dir
        self.segment_bytes = segment_bytes
        self.fsync = fsync
        # group commit knobs: a group is capped at `group_n` mutations; a
        # leader that claimed a CONCURRENT group (>= 2 entries) lingers up
        # to `group_us` for stragglers. A solo appender never lingers, so
        # low-QPS latency is unchanged with the knobs at their defaults.
        self.group_n = group_n if group_n is not None else \
            int(os.environ.get("PEGASUS_PLOG_GROUP_N", 32))
        self.group_us = group_us if group_us is not None else \
            int(os.environ.get("PEGASUS_PLOG_GROUP_US", 500))
        # follower stall bound: a group leader wedged between buffer and
        # flush (chaos fail point `plog.group`, or a pathological fsync)
        # must degrade unclaimed appends to the per-append path instead of
        # hanging the partition
        self._stall_s = float(
            os.environ.get("PEGASUS_PLOG_GROUP_STALL_MS", 500)) / 1e3
        self._lock = lockrank.named_lock("plog.file")
        self._gcv = lockrank.named_condition("plog.group")
        # unclaimed _GroupEntry, submit order
        self._gbuf = []            #: guarded_by self._gcv
        # a leader is writing a group
        self._gleader = False      #: guarded_by self._gcv
        # monotonic ts; bypass grouping until
        self._degraded_until = 0.0  #: guarded_by self._gcv
        # monotonic totals (instance-level, so tests can assert the
        # grouping ratio)
        self.append_count = 0      #: guarded_by self._lock
        self.flush_count = 0       #: guarded_by self._lock
        self._file = None          #: guarded_by self._lock
        self._file_start = None    #: guarded_by self._lock
        self._file_bytes = 0       #: guarded_by self._lock
        self.last_decree = 0       #: guarded_by self._lock
        os.makedirs(log_dir, exist_ok=True)
        self._segments = self._scan_segments()
        if self._segments:
            self.last_decree = self._tail_decree()

    # ----------------------------------------------------------------- write

    @staticmethod
    def _frame(m: LogMutation) -> bytes:
        payload = codec.encode(m)
        return _FRAME.pack(len(payload), zlib.crc32(payload)) + payload

    def append(self, m: LogMutation) -> None:
        """Append one mutation; returns once it is DURABLE (its group's
        write+flush(+fsync) completed) — never before."""
        self._submit(_GroupEntry([self._frame(m)], [m.decree]))

    def append_window(self, ms: List[LogMutation]) -> None:
        """Append a contiguous decree window as ONE group member: the
        whole window lands with one buffered write + one flush (+ one
        fsync when armed) — the primary's decree-pipelined prepare path
        and the secondary's windowed on_prepare both land here."""
        if not ms:
            return
        self._submit(_GroupEntry([self._frame(m) for m in ms],
                                 [m.decree for m in ms]))

    def _submit(self, entry: _GroupEntry) -> None:
        t0 = time.perf_counter()
        nbytes = sum(len(f) for f in entry.frames)
        with REQUEST_TRACER.span("plog.append", decree=entry.decrees[-1],
                                 bytes=nbytes, batch=len(entry.frames)):
            if time.monotonic() < self._degraded_until:  #: unguarded_ok racy read of a monotonic degrade hint: worst case one extra grouped (or degraded) append
                # a recent group leader wedged: per-append fallback keeps
                # the partition moving (groups resume after the cooldown)
                self._write_group([entry])
            else:
                self._group_commit(entry)
        if entry.err is not None:
            raise entry.err
        counters.rate("plog.append.count").increment(len(entry.frames))
        counters.rate("plog.append.bytes").increment(nbytes)
        counters.percentile("plog.append.duration_us").set(
            int((time.perf_counter() - t0) * 1e6))

    def _group_commit(self, entry: _GroupEntry) -> None:
        """Leader/follower group commit: the first appender to find no
        active leader claims everything buffered and lands it as one
        group; appenders that arrive while it writes buffer into the NEXT
        group. A follower whose entry is still unclaimed after _stall_s
        steals it back and degrades to the per-append path. The time an
        appender waits for a leader (until its entry is durable, it leads
        itself, or it gives up) is ONE `plog.group_wait` span."""
        with self._gcv:
            self._gbuf.append(entry)
            self._gcv.notify_all()  # wake a lingering leader
        wait = REQUEST_TRACER.span("plog.group_wait")
        while True:
            fallback = False
            with self._gcv:
                if entry.done:
                    wait.end()
                    return
                if self._gleader:
                    wait.begin()
                    if self._gcv.wait(self._stall_s):
                        continue
                    if entry not in self._gbuf:
                        continue  # claimed: durability requires waiting
                    # leader wedged and never claimed us: steal our entry
                    # back and degrade to the per-append path for a while
                    self._gbuf.remove(entry)
                    self._degraded_until = time.monotonic() + self._stall_s
                    fallback = True
                else:
                    self._gleader = True
                    batch = self._claim_locked([])
            wait.end()
            if fallback:
                counters.rate("plog.group.fallback_count").increment()
                # one close per degrade: stage.plog.group_fallback.n is
                # the count the rate above never was
                with REQUEST_TRACER.span("plog.group_fallback"):
                    self._write_group([entry])
                return
            # ---- leader, outside the cv: stragglers queue for next group
            try:
                if len(batch) >= 2 and self.group_us > 0:
                    batch = self._linger(batch)
                inject("plog.group")  # chaos seam: between claim and flush
                self._write_group(batch)
            except Exception as e:  # noqa: BLE001 - every member must see it
                err = e if isinstance(e, OSError) else OSError(
                    f"plog group write failed: {e!r}")
                for b in batch:
                    b.err = err
            finally:
                with self._gcv:
                    self._gleader = False
                    for b in batch:
                        b.done = True
                    self._gcv.notify_all()

    def _claim_locked(self, batch: list) -> list:  #: requires self._gcv
        """Move buffered entries into `batch` up to the group_n cap.
        Caller holds self._gcv."""
        total = sum(len(b.frames) for b in batch)
        while self._gbuf and total < self.group_n:
            e = self._gbuf.pop(0)
            batch.append(e)
            total += len(e.frames)
        return batch

    def _linger(self, batch: list) -> list:
        """A leader that already claimed a concurrent group (>= 2 members)
        waits up to group_us for stragglers, growing toward group_n."""
        deadline = time.monotonic() + self.group_us / 1e6
        while sum(len(b.frames) for b in batch) < self.group_n:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                break
            with self._gcv:
                if not self._gbuf:
                    self._gcv.wait(remaining)
                batch = self._claim_locked(batch)
        return batch

    def _write_group(self, batch: list) -> None:
        """Land a claimed group: ONE buffered write + ONE flush (+ one
        fsync when armed) for every frame in every member. The `plog.group`
        fail point fires in _group_commit between claim and flush, OUTSIDE
        the file lock, so a chaos `sleep` wedges only that group — the
        degraded per-append path still reaches the file here."""
        n_frames = sum(len(b.frames) for b in batch)
        blob = b"".join(f for b in batch for f in b.frames)
        first_decree = batch[0].decrees[0]
        # one close per flush: stage.plog.flush.n IS the flush count
        with REQUEST_TRACER.span("plog.flush", bytes=len(blob),
                                 batch=n_frames), self._lock:
            if self._file is None or self._file_bytes >= self.segment_bytes:
                self._roll_locked(first_decree)
            self._file.write(blob)
            self._file.flush()
            if self.fsync:
                os.fsync(self._file.fileno())
            self._file_bytes += len(blob)
            for b in batch:
                self.last_decree = max(self.last_decree, b.decrees[-1])
            self.append_count += n_frames
            self.flush_count += 1
        counters.rate("plog.append.flush_count").increment()
        counters.percentile("plog.append.group_size").set(n_frames)

    def _roll_locked(self, start_decree: int) -> None:  #: requires self._lock
        if self._file:
            self._file.close()
        name = f"log.{start_decree}"
        path = os.path.join(self.dir, name)
        self._file = open(path, "ab")
        self._file_start = start_decree
        self._file_bytes = self._file.tell()
        if start_decree not in self._segments:
            self._segments.append(start_decree)
            self._segments.sort()

    # ------------------------------------------------------------------ read

    def replay(self, from_decree: int = 0):
        """Yield LogMutations with decree > from_decree, in append order.
        Stops (and truncates) at the first torn record."""
        with self._lock:
            segments = list(self._segments)
            if self._file:
                self._file.flush()
        for i, start in enumerate(segments):
            # skip segments that end before the replay point
            if i + 1 < len(segments) and segments[i + 1] <= from_decree + 1:
                continue
            path = os.path.join(self.dir, f"log.{start}")
            with open(path, "rb") as f:
                data = f.read()
            off = 0
            while off + _FRAME.size <= len(data):
                length, crc = _FRAME.unpack_from(data, off)
                body = data[off + _FRAME.size : off + _FRAME.size + length]
                if len(body) < length or zlib.crc32(body) != crc:
                    self._truncate_torn(path, off)
                    return
                off += _FRAME.size + length
                m = codec.decode(LogMutation, body)
                if m.decree > from_decree:
                    yield m

    def _truncate_torn(self, path: str, valid_bytes: int) -> None:
        with self._lock:
            if self._file and os.path.join(self.dir, f"log.{self._file_start}") == path:
                self._file.truncate(valid_bytes)
            else:
                with open(path, "r+b") as f:
                    f.truncate(valid_bytes)

    # -------------------------------------------------------------------- gc

    def flush(self) -> None:
        """Flush + fsync the open segment (shell flush_log; reference
        flush_log remote command)."""
        with self._lock:
            if self._file is not None:
                self._file.flush()
                os.fsync(self._file.fileno())

    def gc(self, durable_decree: int) -> int:
        """Drop whole segments strictly older than the segment containing
        durable_decree+1 (reference: log GC after checkpoint)."""
        with self._lock:
            dropped = 0
            while len(self._segments) > 1 and self._segments[1] <= durable_decree + 1:
                start = self._segments.pop(0)
                try:
                    os.unlink(os.path.join(self.dir, f"log.{start}"))
                except OSError:
                    pass
                dropped += 1
            return dropped

    def reset(self) -> None:
        """Wipe everything (learner re-seed from checkpoint)."""
        with self._lock:
            if self._file:
                self._file.close()
                self._file = None
            for start in self._segments:
                try:
                    os.unlink(os.path.join(self.dir, f"log.{start}"))
                except OSError:
                    pass
            self._segments = []
            self.last_decree = 0

    def close(self) -> None:
        with self._lock:
            if self._file:
                self._file.close()
                self._file = None

    # ---------------------------------------------------------------- helpers

    def _scan_segments(self) -> list:
        out = []
        for name in os.listdir(self.dir):
            if name.startswith("log.") and name[4:].isdigit():
                out.append(int(name[4:]))
        return sorted(out)

    def _tail_decree(self) -> int:
        last = 0
        for m in self.replay(0):
            last = max(last, m.decree)
        return last
